"""Analytic operations and bytes of the decide step, and the least time a
chip could take for it. A copy of ``benchmarks/roofline.py`` (the original
stays for ``bench.py``; PERF.md lists it for deletion), with the peaks taken
from ``cellbench/peaks.json`` by ``device_kind``.

The model covers the uniform + grouped variant of
``engine/decide._decide_core`` only, the one the service dispatches for
sorted batches whose rows all acquire the same count. The mixed-acquire
(refining) variant is not modelled and gets no roofline.

Counts follow the kernel source: every matmul or einsum is ``2*M*K*N``
operations, cummax counts as comparisons at the cumsum's shape, elementwise
work is a small constant per row. Bytes are HBM traffic per batch: state
gathers and scatters, rule-table gathers, batch in and verdicts out, and
the materialised one-hot and blocked-cumsum intermediates (an upper bound).
"""

from __future__ import annotations

_CUMSUM_BLOCK = 128  # ops/scan_mm.py blocked_cumsum


def _cumsum_flops(n: int, k: int) -> float:
    c = _CUMSUM_BLOCK
    r = -(-n // c)
    return 2.0 * r * c * c * k + 2.0 * r * r * k


def decide_step_model(batch: int, n_namespaces: int, n_buckets: int) -> dict:
    """Operations and HBM bytes of one uniform + grouped step of ``batch``."""
    n, ns, b = batch, n_namespaces, n_buckets
    flops = _cumsum_flops(n, ns)  # namespace one-hot inclusive cumsum
    flops += 3.0 * n * ns  # one-hot build, take_along_axis, guard einsum
    flops += 2.0 * (_cumsum_flops(n, 1) * 2)  # flow prefix: cumsum + cummax, twice
    flops += 40.0 * n  # thresholds, closed-form admission, verdict selects
    i32 = 4
    bytes_ = 2.0 * n * b * i32  # window reads: PASS rows and occupy rows
    bytes_ += 2.0 * 4.0 * n * i32  # scatter updates, 4 event channels
    bytes_ += 4.0 * n * i32  # rule-table gathers
    bytes_ += n * (i32 * 2 + 2) + n * (1 + i32 * 2)  # batch in, verdicts out
    bytes_ += 3.0 * n * ns * i32  # one-hot written and read by the cumsum
    bytes_ += (b * i32) * 3 + ns * b * i32  # window starts, ns window
    return {"flops": flops, "bytes": bytes_}


def least_seconds(rows: int, config: dict, peaks: dict) -> float:
    """The larger of operations over peak and bytes over peak bandwidth, for
    one dispatch of ``rows`` rows padded to its serve bucket. The step's
    matmuls run at float32 ``highest``: ``f32_highest_passes`` bf16 passes."""
    buckets = sorted(config["serve_buckets"])
    bucket = next((b for b in buckets if rows <= b), buckets[-1])
    steps = -(-rows // bucket)
    m = decide_step_model(bucket, int(config["engine"]["max_namespaces"]),
                          int(config["engine"]["n_buckets"]))
    f32_peak = peaks["bf16_flops_per_s"] / peaks["f32_highest_passes"]
    return steps * max(m["flops"] / f32_peak,
                       m["bytes"] / peaks["hbm_bytes_per_s"])
