"""When has a serve step taken its host argument? The drill behind the
ownership rule of ``DefaultTokenService`` ("the array handed to ``step`` is
this dispatch's own and written by nobody once the clock is in"; PERF.md
section 6, PR 43).

Calls the serve step of ``mesh-100k``'s size with a packed array whose rows
all have a rule that never blocks, overwrites the array with no-rule rows the
moment the call returns, and reads the verdicts: a call whose verdicts are
not all OK was decided on the overwritten bytes, so its argument was still
being read after the call returned. Per serve bucket, with the array aligned
to 64 bytes and one int32 off it (the CPU backend aliases an aligned argument;
the TPU's transfer is its runtime's), on an idle device and behind a queue of
steps, on one device and, where there are four, on the sharded step over a
mesh of them (one host argument replicated onto four devices).

    python benchmarks/arg_overwrite_drill.py [--rounds 40] [--only one|mesh]

Runs on whatever backend JAX has: the TPU on the chip machine
(``--chips 4`` for the mesh), the CPU's devices in the sandbox
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``). One JSON line per
case and a last line of totals.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from sentinel_tpu.cluster.token_service import DefaultTokenService  # noqa: E402
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig  # noqa: E402
from sentinel_tpu.engine.decide import (  # noqa: E402
    HEAD_NOW,
    PACKED_LINES,
    ROW_HEAD,
    pack_requests,
    unpack_verdicts,
)
from sentinel_tpu.engine.rules import ThresholdMode  # noqa: E402
from sentinel_tpu.parallel import make_flow_mesh  # noqa: E402

FLOWS = 100_000  # mesh-100k's
BUCKETS = (64, 1024, 4096, 16384)
OK = 0


def _array(bucket: int, aligned: bool) -> np.ndarray:
    """An ``int32[PACKED_LINES, bucket]`` whose first byte lies on a 64-byte
    line, or one int32 past one."""
    raw = np.empty(PACKED_LINES * bucket + 32, np.int32)
    at = (-raw.ctypes.data // 4) % 16 + (0 if aligned else 1)
    return raw[at:at + PACKED_LINES * bucket].reshape(PACKED_LINES, bucket)


def drill(mesh, flows: int, rounds: int, say) -> int:
    cfg = EngineConfig(max_flows=flows, max_namespaces=64,
                       batch_size=BUCKETS[-1])
    svc = DefaultTokenService(cfg, mesh=mesh, serve_buckets=BUCKETS,
                              fuse_depths=())
    svc.load_rules(
        [ClusterFlowRule(flow_id=i, count=1e9,
                         mode=ThresholdMode.GLOBAL) for i in range(flows)],
        ns_max_qps=1e12)
    rng = np.random.default_rng(43)
    followed_overwrite = 0
    for bucket in BUCKETS:
        bcfg = cfg._replace(batch_size=bucket)
        step = svc._step_fn(bucket, True)
        for aligned in (True, False):
            for backlog in (0, 12):
                wrong = 0
                for _ in range(rounds):
                    rows = pack_requests(bcfg, np.sort(rng.integers(
                        0, flows, bucket)).astype(np.int32))
                    now = svc._engine_now()
                    rows[ROW_HEAD, HEAD_NOW] = now
                    blank = pack_requests(bcfg, np.full(bucket, -1, np.int32))
                    blank[ROW_HEAD, HEAD_NOW] = now
                    for _b in range(backlog):  # a queue ahead of the call
                        svc._state, _v = step(svc._state, svc._table,
                                              rows.copy())
                    arg = _array(bucket, aligned)
                    arg[:] = rows
                    svc._state, verdicts = step(svc._state, svc._table, arg)
                    arg[:] = blank  # the moment the call returns
                    status, _wait, _rem = unpack_verdicts(
                        np.asarray(verdicts).reshape(3, -1), bucket, None)
                    wrong += int((status != OK).any())
                followed_overwrite += wrong
                say({"devices": 1 if mesh is None else mesh.size,
                     "bucket": bucket, "aligned_64": aligned,
                     "queued_steps_ahead": backlog, "calls": rounds,
                     "decided_on_the_overwritten_bytes": wrong})
    svc.close()
    return followed_overwrite


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--flows", type=int, default=FLOWS)
    ap.add_argument("--only", choices=("one", "mesh"),
                    help="one device, or the mesh of four, alone")
    args = ap.parse_args()
    devices = jax.devices()

    def say(row):
        row["platform"] = devices[0].platform
        print(json.dumps(row), flush=True)

    total = {}
    if args.only != "mesh":
        total["one_device"] = drill(None, args.flows, args.rounds, say)
    if len(devices) >= 4 and args.only != "one":
        total["mesh_of_4"] = drill(make_flow_mesh(devices[:4]), args.flows,
                                   args.rounds, say)
    say({"calls_decided_on_the_overwritten_bytes": total,
         "device_kind": devices[0].device_kind})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
