"""One seeded stream through every serving step builder, and the verdicts
the tree before PR 24 gave on it (``tests/data/decide_golden.npz``).

PR 24 named the jitted steps and wrapped the arms of ``_decide_core`` in
``jax.named_scope``: metadata only, so every verdict must stay bit-equal.
The file was written by running this module in a checkout of the parent
commit (``python tests/decide_golden.py tests/data/decide_golden.npz``); the
stream uses only builders both trees have. Since PR 25 the serve steps hand
back one packed ``int32[3, ...]`` buffer, read here through
``unpack_verdicts``; the file is still the one that tree wrote, so the
packing too must leave every verdict bit-equal. The rules cover every arm:
plain and AVG_LOCAL thresholds, WARM_UP, RATE_LIMITER, both together,
prioritized rows (occupy), mixed acquires (refinement), a namespace guard
that bites, unknown slots and padding.
"""

import sys

import numpy as np


NS_MAX_QPS = 120.0
CONNECTED = {"default": 1, "b": 2}
# flow ids the stream draws from; 999 has no rule
IDS = np.array(list(range(12)) + [20, 21, 22, 23, 999])


def config():
    from sentinel_tpu.engine import EngineConfig

    return EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)


def rules() -> list:
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    g = ThresholdMode.GLOBAL
    out = [ClusterFlowRule(flow_id=i, count=4.0 + (i % 5), mode=g)
           for i in range(12)]
    out += [
        ClusterFlowRule(flow_id=20, count=30.0,
                        mode=ThresholdMode.AVG_LOCAL, namespace="b"),
        ClusterFlowRule(flow_id=21, count=20.0, mode=g, namespace="b",
                        control_behavior=1, warm_up_period_sec=4),
        ClusterFlowRule(flow_id=22, count=50.0, mode=g, namespace="b",
                        control_behavior=2, max_queueing_time_ms=200),
        ClusterFlowRule(flow_id=23, count=40.0, mode=g, namespace="c",
                        control_behavior=3, warm_up_period_sec=2,
                        max_queueing_time_ms=100),
    ]
    return out


def _setup():
    from sentinel_tpu.engine import build_rule_table

    cfg = config()
    table, index = build_rule_table(cfg, rules(), ns_max_qps=NS_MAX_QPS,
                                    connected=CONNECTED)
    return cfg, table, index


def rows(rng, n, uniform):
    """``n`` rows of the stream in arrival order: (flow ids, acquires,
    prios)."""
    fid = rng.choice(IDS, size=n)
    acq = (np.ones(n, np.int32) if uniform
           else rng.integers(1, 4, size=n).astype(np.int32))
    pr = rng.random(n) < 0.15
    return fid, acq, pr


def slots_of(index, flow_ids):
    return np.array([index.lookup(int(f)) if f != 999 else -1
                     for f in flow_ids], np.int32)


def _frames(cfg, index, seed, n_frames, uniform):
    """``n_frames`` grouped frames: (slots, acquires, prios), rows sorted by
    slot as the service's batcher sorts them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_frames):
        n = int(rng.integers(cfg.batch_size // 2, cfg.batch_size + 1))
        fid, acq, pr = rows(rng, n, uniform)
        slots = slots_of(index, fid)
        order = np.argsort(slots, kind="stable")
        out.append((slots[order], acq[order], pr[order]))
    return out


def verdicts() -> dict:
    """``{case name: int32[frames, 3, batch]}`` (status, remaining, wait)."""
    import jax

    from sentinel_tpu.engine import make_state
    from sentinel_tpu.engine.decide import (
        decide_donating,
        decide_fused_donating,
        pack_requests,
        unpack_verdicts,
    )
    from sentinel_tpu.parallel import (
        make_flow_mesh,
        make_sharded_decide,
        shard_rules,
        shard_state,
    )

    cfg, table, index = _setup()
    mesh = make_flow_mesh(jax.devices()[:4])
    table_m = shard_rules(table, mesh)
    out = {}

    def pack(packed):
        v = unpack_verdicts(packed)
        return np.stack([v.status.astype(np.int32), v.remaining, v.wait_ms],
                        axis=-2)

    for uniform in (True, False):
        tag = "uniform" if uniform else "mixed"
        frames = _frames(cfg, index, 7 if uniform else 11, 8, uniform)
        # the serve steps' one host argument: request batch and clock
        batches = [pack_requests(cfg, s, a, p, now=10_000 + 130 * i)
                   for i, (s, a, p) in enumerate(frames)]

        step = decide_donating(cfg, grouped=True, uniform=uniform)
        state, got = make_state(cfg), []
        for b in batches:
            state, v = step(state, table, b)
            got.append(pack(v))
        out[f"single_{tag}"] = np.stack(got)

        step = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform,
                                   donate=True)
        state, got = shard_state(make_state(cfg), mesh), []
        for b in batches:
            state, v = step(state, table_m, b)
            got.append(pack(v))
        out[f"sharded_{tag}"] = np.stack(got)

        stacked = np.stack(batches[:4], axis=1)  # the clock is frame 0's
        step = decide_fused_donating(cfg, 4, grouped=True, uniform=uniform)
        _, v = step(make_state(cfg), table, stacked)
        out[f"fused_{tag}"] = pack(v)

        step = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform,
                                   donate=True, depth=4)
        _, v = step(shard_state(make_state(cfg), mesh), table_m, stacked)
        out[f"sharded_fused_{tag}"] = pack(v)
    return out


if __name__ == "__main__":
    np.savez_compressed(sys.argv[1], **verdicts())
