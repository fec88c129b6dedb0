"""One seeded life of a small service with every state family live, and the
three state documents the tree before PR 48 wrote of it
(``tests/data/state_docs/``).

PR 48 put the description of the device state behind one table
(``engine.state.STATE_COLUMNS``) and rewrote the snapshot, replication-delta
and MOVE codecs as loops over it: the documents must keep their keys and
arrays, and a document the old tree wrote must restore on the new one to the
state it restored to on the old. The files were written by running this
module in a checkout of the parent commit (``JAX_PLATFORMS=cpu python
tests/state_docs_golden.py tests/data/state_docs``); it uses only the
service's public entry points, which both trees have.

What is live when the documents are taken: metered flows in two namespaces
(so the namespace guard counts), a prioritized borrow in the occupy ring, a
WARM_UP and a RATE_LIMITER rule that have both run, completion reports that
trip breakers and leave one HALF_OPEN with its probe out in each document
(flow 13 in the snapshot, flow 15 in the delta and the MOVE blob), a
breaker-only flow, two param rules with the slim twin on, and a namespace
that arrived by MOVE before the snapshot (so the delta after it carries its
param row as a fat row beside the slim ones).

Files: ``snapshot.json`` (``ha.snapshot.encode_snapshot``), ``delta.bin``
(``ha.replication.encode_delta_blob``; applies on top of the snapshot),
``move.bin`` (``cluster.rebalance.encode_move_state_blob`` of namespace
``b``), and ``restored.npz``: every leaf of the engine and sketch state of a
second service after each restore, under ``<document>/<family>.<field>``.
"""

import json
import os
import sys

import numpy as np

T0 = 1_700_000_000_000
SEED = 0x57A7E
# the namespace whose MOVE blob is kept
MOVED_NS = "b"
DOCS = ("snapshot", "delta", "move")


def config():
    from sentinel_tpu.engine import EngineConfig

    return EngineConfig(max_flows=32, max_namespaces=4, batch_size=64)


def param_config():
    from sentinel_tpu.engine.param import ParamConfig

    return ParamConfig(max_param_rules=8, depth=2, width=32, impl="jax",
                       slim_depth=2, slim_width=16)


def flow_rules() -> list:
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    g = ThresholdMode.GLOBAL
    return [
        ClusterFlowRule(1, 10.0, g, "a"),
        ClusterFlowRule(2, 5.0, g, "a"),
        ClusterFlowRule(3, 20.0, g, "a", control_behavior=1,
                        warm_up_period_sec=4),
        ClusterFlowRule(4, 50.0, g, "a", control_behavior=2,
                        max_queueing_time_ms=200),
        ClusterFlowRule(11, 8.0, g, "b"),
        ClusterFlowRule(12, 1e9, g, "b"),
        ClusterFlowRule(13, 1e9, g, "b"),
        ClusterFlowRule(15, 6.0, g, "b", control_behavior=2,
                        max_queueing_time_ms=300),
    ]


def degrade_rules() -> list:
    from sentinel_tpu.engine.rules import DegradeRule, DegradeStrategy

    count = DegradeStrategy.ERROR_COUNT
    return [
        DegradeRule(12, count, 4.0, min_request_amount=5,
                    recovery_timeout_ms=2000, namespace="b"),
        DegradeRule(13, DegradeStrategy.ERROR_RATIO, 0.5,
                    min_request_amount=5, recovery_timeout_ms=300,
                    namespace="b"),
        # a breaker with no flow rule still owns a slot and moves with "b"
        DegradeRule(14, count, 2.0, min_request_amount=2,
                    recovery_timeout_ms=5000, namespace="b"),
        DegradeRule(15, count, 1.0, min_request_amount=2,
                    recovery_timeout_ms=700, namespace="b"),
    ]


def param_rules() -> list:
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule

    return [
        ClusterParamFlowRule(21, 30.0, ((7, 3.0),), "a"),
        ClusterParamFlowRule(22, 1e9, None, "b"),
    ]


def service(mesh=None):
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    return DefaultTokenService(config(), param_config=param_config(),
                               mesh=mesh, serve_buckets=(64,),
                               fuse_depths=())


def _ask(svc, ids, acquires=None, prios=None):
    ids = np.asarray(ids, np.int64)
    return svc.request_batch_arrays(
        ids,
        None if acquires is None else np.asarray(acquires, np.int32),
        None if prios is None else np.asarray(prios, bool),
    )


def _traffic(svc, rng, clock, rounds: int) -> None:
    """``rounds`` frames 30 ms apart over every flow: plain rows, rows of
    more than one token, prioritized rows on a flow that is over its count
    (they borrow the next bucket), and param requests on both rules."""
    ids = np.array([1, 2, 3, 4, 11, 12, 13, 15, 41])
    values = rng.integers(-2 ** 62, 2 ** 62, size=12, dtype=np.int64)
    for _ in range(rounds):
        n = int(rng.integers(8, 24))
        _ask(svc, rng.choice(ids, n), rng.integers(1, 3, n))
        _ask(svc, [2] * 6, prios=[True] * 6)
        svc.request_params_token(21, 1, [7, int(values[rng.integers(12)])])
        svc.request_params_token(
            22, 2, [int(v) for v in rng.choice(values, 3)])
        clock.advance(30)


def _moved_in_doc(clock):
    """The MOVE document of a namespace ``c`` with one flow and one param
    rule that have counted, from a service of its own."""
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    aux = service()
    try:
        aux.load_namespace_rules(
            "c", [ClusterFlowRule(41, 100.0, ThresholdMode.GLOBAL, "c")])
        aux.load_namespace_param_rules(
            "c", [ClusterParamFlowRule(31, 1e9, None, "c")])
        _ask(aux, [41] * 4)
        for v in (5, 5, 9, 11, 5):
            aux.request_params_token(31, 1, [v])
        clock.advance(10)
        return aux.export_namespace_state("c")
    finally:
        aux.close()


def run(clock):
    """The life. Returns ``(service, documents)``: the three documents as
    the service's export methods hand them over (not yet encoded). The
    caller closes the service."""
    clock.set_ms(T0)
    rng = np.random.default_rng(SEED)
    svc = service()
    svc.load_rules(flow_rules(), ns_max_qps=400.0,
                   connected={"a": 2, "b": 1})
    svc.load_degrade_rules(degrade_rules())
    svc.load_param_rules(param_rules())
    svc.namespace_set |= {"a", "b", "spare"}
    svc.warmup()
    svc.replication_enable()
    clock.advance(1000)
    _traffic(svc, rng, clock, 6)
    # flow 12 trips and stays OPEN; flow 14 (no flow rule) trips too
    svc.report_outcomes([12] * 6, [5] * 6, [True] * 5 + [False])
    svc.report_outcomes([14] * 3, [5] * 3, [True] * 3)
    # flows 13 and 15 trip; 13 waits out its recovery timeout and has its
    # probe out when the snapshot is taken
    svc.report_outcomes([13] * 8, [7] * 8, [True] * 6 + [False] * 2)
    svc.report_outcomes([15] * 2, [7] * 2, [True] * 2)
    clock.advance(10)
    _ask(svc, [12, 13, 14, 15])
    # a namespace arrives by MOVE: that reloads rules, so a sender ships
    # the snapshot next, and its param row rides the delta after it once as
    # a fat row (its mass is in no slim twin)
    svc.import_namespace_state(_moved_in_doc(clock))
    clock.advance(320)
    _ask(svc, [13, 13])
    _traffic(svc, rng, clock, 3)
    docs = {"snapshot": svc.export_state()}
    # between snapshot and delta: more of everything, one ring rotation at
    # least, 13's probe comes back well, 15's goes out
    clock.advance(130)
    _traffic(svc, rng, clock, 4)
    svc.report_outcomes([13], [7], [False])
    svc.report_outcomes([11, 1, 1], [3, 40, 2000], [False, False, True])
    clock.advance(300)
    _ask(svc, [13, 12, 15, 15])
    svc.request_params_token(31, 1, [5, 6])
    docs["move"] = svc.export_namespace_state(MOVED_NS)
    docs["delta"] = svc.export_delta()
    return svc, docs


def encode(docs) -> dict:
    """The documents as the wire and the snapshot directory hold them."""
    from sentinel_tpu.cluster.rebalance import encode_move_state_blob
    from sentinel_tpu.ha.replication import encode_delta_blob
    from sentinel_tpu.ha.snapshot import encode_snapshot

    return {
        "snapshot": json.dumps(encode_snapshot(docs["snapshot"]),
                               separators=(",", ":")).encode(),
        "delta": encode_delta_blob(docs["delta"]),
        "move": encode_move_state_blob(docs["move"]),
    }


FILES = {"snapshot": "snapshot.json", "delta": "delta.bin",
         "move": "move.bin"}


def decode(name: str, raw: bytes) -> dict:
    from sentinel_tpu.cluster.rebalance import decode_move_state_blob
    from sentinel_tpu.ha.replication import decode_delta_blob
    from sentinel_tpu.ha.snapshot import decode_snapshot

    if name == "snapshot":
        return decode_snapshot(json.loads(raw.decode()))
    return (decode_delta_blob if name == "delta"
            else decode_move_state_blob)(raw)


def standby(mesh=None):
    """Where the snapshot and the delta land: a service that loaded the
    same rules in the opposite order (slots are sticky across reloads, so
    the restore cannot reuse the primary's)."""
    svc = service(mesh)
    svc.load_rules(flow_rules()[::-1])
    svc.load_param_rules(param_rules()[::-1])
    return svc


def move_target(clock, mesh=None):
    """Where the MOVE blob lands: a service with rules of its own in the
    first slots and an engine epoch 12,345 ms older than the source's."""
    from sentinel_tpu.cluster.token_service import ClusterParamFlowRule
    from sentinel_tpu.engine import ClusterFlowRule
    from sentinel_tpu.engine.rules import ThresholdMode

    now = clock.now_ms()
    clock.set_ms(T0 - 12_345)
    svc = service(mesh)
    svc.load_rules([ClusterFlowRule(90 + i, 100.0, ThresholdMode.GLOBAL, "z")
                    for i in range(3)])
    svc.load_param_rules([ClusterParamFlowRule(95, 9.0, None, "z")])
    svc.warmup()
    clock.set_ms(now)
    _ask(svc, [90, 91, 91])
    return svc


def restore(name: str, decoded: dict, clock, mesh=None):
    """Land one decoded document on its destination (the delta on top of
    the snapshot, which ``decoded`` then holds under both names), at the
    wall clock the life ended on. Returns the destination; the caller
    closes it."""
    clock.set_ms(decoded["delta"]["wall_ms"])
    if name == "move":
        dst = move_target(clock, mesh)
        dst.import_namespace_state(decoded["move"])
        return dst
    dst = standby(mesh)
    dst.import_state(decoded["snapshot"])
    if name == "delta":
        dst.apply_replication_delta(decoded["delta"])
    return dst


def leaves(svc) -> dict:
    """Every leaf of the service's engine and sketch state as host arrays,
    under ``<family>.<field>``."""
    out = {}
    for family, value in svc._state._asdict().items():
        for field, leaf in value._asdict().items():
            out[f"{family}.{field}"] = np.asarray(leaf)
    for field, leaf in svc._param_state._asdict().items():
        out[f"param.{field}"] = np.asarray(leaf)
    return out


def main(out_dir: str) -> None:
    from sentinel_tpu.core import clock as clock_mod

    clock = clock_mod.ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        svc, docs = run(clock)
        svc.close()
        raw = encode(docs)
        os.makedirs(out_dir, exist_ok=True)
        for name, blob in raw.items():
            with open(os.path.join(out_dir, FILES[name]), "wb") as f:
                f.write(blob)
        decoded = {name: decode(name, blob) for name, blob in raw.items()}
        restored = {}
        for name in DOCS:
            dst = restore(name, decoded, clock)
            restored.update({f"{name}/{k}": v
                             for k, v in leaves(dst).items()})
            dst.close()
        np.savez_compressed(os.path.join(out_dir, "restored.npz"),
                            **restored)
    finally:
        clock_mod.set_clock(prev)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main(sys.argv[1])
