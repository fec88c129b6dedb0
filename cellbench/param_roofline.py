"""Analytic bytes and operations of the hot-parameter step
(``sentinel_tpu/engine/param.py``, ``jit_param_decide_b<bucket>``), and the
least time a chip could take for the dispatches of a traced slice. Peaks
come from ``peaks.json`` by ``device_kind``.

What a dispatch of ``rows`` (request, value) rows must move, counted from
the step's source (every cell is an ``int32``):

    gathered cells    rows x depth x n_buckets of the sketch, and rows x
                      slim_depth x n_buckets of the slim twin
    scattered cells   rows x depth of the current bucket read and written
                      (the commit), rows x depth read again and rows x
                      slim_depth read and written by the twin's scatter-max
    packed in / out   the one host array ``int32[3 + depth + slim_depth + 1,
                      bucket]`` in, the verdicts ``int32[3, bucket]`` out,
                      both at the serve bucket the rows pad to
    stale plane       once per ``bucket_ms``, whatever the dispatches: the
                      plane of the bucket that went stale, ``max_param_rules
                      x depth x width`` cells written

Operations are elementwise and small (the admission refinement, the key mix,
the segment prefix's sort and sums): a few hundred per row. The step is
bound by HBM traffic; the share read is of that roofline.
"""

from __future__ import annotations

PARAM_LANE = 1  # sentinel_tpu.trace.ring.PARAM_LANE: a param DEVICE_IN's shard
_I32 = 4
_OPS_PER_ROW = 400.0  # key mix, three refinements, sort + sums of the prefix


def dispatch_model(rows: int, bucket: int, param: dict) -> dict:
    """Bytes and operations of one dispatch of ``rows`` rows padded to
    ``bucket``, the stale plane left out."""
    d, b = int(param["depth"]), int(param["n_buckets"])
    sd = int(param.get("slim_depth", 2))
    if int(param.get("slim_width", 256)) <= 0:
        sd = 0
    cells = rows * (d * b + sd * b)  # gathered
    cells += rows * (2 * d + d + 2 * sd)  # commit, twin's estimate and max
    cells += (3 + d + sd + 1) * bucket + 3 * bucket  # packed in and out
    return {"bytes": float(cells * _I32), "flops": _OPS_PER_ROW * rows}


def stale_plane_bytes(param: dict) -> float:
    return float(int(param["max_param_rules"]) * int(param["depth"])
                 * int(param["width"]) * _I32)


def least_seconds(dispatch_rows, seconds: float, config: dict,
                  peaks: dict) -> float:
    """The least time a chip could take for dispatches of ``dispatch_rows``
    rows each over ``seconds`` of serving."""
    param = config["param"]
    buckets = sorted(config["serve_buckets"])
    total = {"bytes": 0.0, "flops": 0.0}
    for rows in dispatch_rows:
        bucket = next((b for b in buckets if rows <= b), None)
        if bucket is None:
            bucket = 1 << (int(rows) - 1).bit_length()
        m = dispatch_model(int(rows), bucket, param)
        total["bytes"] += m["bytes"]
        total["flops"] += m["flops"]
    total["bytes"] += (seconds * 1000.0 / float(param["bucket_ms"])
                       * stale_plane_bytes(param))
    f32_peak = peaks["bf16_flops_per_s"] / peaks["f32_highest_passes"]
    return max(total["flops"] / f32_peak,
               total["bytes"] / peaks["hbm_bytes_per_s"])


def param_dispatch_rows(snap) -> list:
    """Rows of every param dispatch of a traced slice: the DEVICE_IN events
    a param dispatch marks (``shard`` 1)."""
    return [e["aux"] for e in snap["events"]
            if e["stage"] == "device_in" and e.get("shard") == PARAM_LANE]


def param_program_seconds(snap) -> float:
    """Device time of the ``jit_param_decide*`` programs of the slice."""
    return sum(s for name, s in snap["trace"]["modules"]
               if str(name).startswith("jit_param_decide"))
