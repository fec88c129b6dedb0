"""Flow prep in one native pass (``sn_flow_prep`` through
``native.lib.flow_prep``) held to the numpy prep it replaces on the hot path:
``DefaultTokenService._lookup_from`` + ``_prep_batch``. The contract is
identity, not equivalence: the same ``slots``, the same ``order`` (None
exactly when the numpy path returns None) and every byte of ``packed``, so
that a device program given the native argument cannot answer differently,
on one chip or on four.

Skipped, not passed, where the library is not built; the service-level
cases at the end run the numpy fallback everywhere.
"""

import json
import os

import numpy as np
import pytest

from sentinel_tpu.cluster import token_service
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import (
    ClusterFlowRule,
    EngineConfig,
    alloc_packed_block,
    pack_requests_into,
)
from sentinel_tpu.engine.decide import PACKED_LINES, ROW_HEAD
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.native import lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("sorted", "grouped_unsorted", "random", "zipf", "one_flow",
         "unknown_mixed", "all_unknown", "mixed_acquire", "prioritized")


def _deployment(name):
    with open(os.path.join(ROOT, "cellbench", "configs", name + ".json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    return cfg["serve_buckets"], cfg["engine"]["max_flows"], cfg["rules"]


# (deployment, serve bucket, rows): n = 1, bucket - 1, bucket of every bucket
SHAPES = [
    (name, bucket, n)
    for name in ("mesh-100k", "demo-cluster-1k")
    for bucket in _deployment(name)[0]
    for n in (1, bucket - 1, bucket)
]


def _native_built() -> bool:
    """Whether ``lib.flow_prep`` runs the native pass here (a library that
    is missing, or older than the entry, makes it return None)."""
    return lib.flow_prep(
        (np.empty(0, np.int64), np.empty(0, np.int32)), np.zeros(1, np.int64),
        np.ones(1, np.int32), np.zeros(1, bool), 64) is not None


@pytest.fixture(scope="module")
def native():
    if not _native_built():
        pytest.skip(f"native library not built: {lib._load_error}")
    return lib


@pytest.fixture(scope="module")
def snapshots():
    """deployment -> a lookup snapshot of its size: sorted keys (sparse,
    negative ones among them) and a permutation of the slots."""
    out = {}
    for i, name in enumerate(("mesh-100k", "demo-cluster-1k")):
        _buckets, max_flows, rules = _deployment(name)
        n_keys = int(rules.get("n_flows", max_flows))
        rng = np.random.default_rng(7 + i)
        keys = np.unique(rng.integers(-2**40, 2**40, 2 * n_keys))[:n_keys]
        slots = rng.permutation(max_flows)[:keys.size].astype(np.int32)
        out[name] = (keys, slots)
    return out


def _frame(kind, n, snapshot, seed):
    """``(flow_ids, acquires, prios)`` of ``n`` rows of one kind."""
    keys, slots = snapshot
    rng = np.random.default_rng(seed)
    acq, pr = np.ones(n, np.int32), np.zeros(n, bool)
    strangers = rng.integers(2**41, 2**42, n)  # no key lies there
    by_slot = keys[np.argsort(slots)]  # keys in ascending slot order
    if kind == "sorted":  # ascending slots, duplicates among them
        ids = by_slot[np.sort(rng.integers(0, keys.size, n))]
    elif kind == "grouped_unsorted":  # same-flow rows adjacent, groups not
        groups = by_slot[rng.permutation(keys.size)[:max(1, n // 8)]]
        ids = np.repeat(groups, 8)[:n]
        ids = np.concatenate([ids, np.full(n - ids.size, groups[0])])
    elif kind == "random":
        ids = keys[rng.integers(0, keys.size, n)]
    elif kind == "zipf":  # a few hot flows, heavy duplicates
        ids = keys[np.minimum(rng.zipf(1.2, n) - 1, keys.size - 1)]
    elif kind == "one_flow":
        ids = np.full(n, keys[keys.size // 3])
    elif kind == "unknown_mixed":  # no-rule rows (-1) among ruled ones
        ids = np.where(rng.random(n) < 0.3, strangers,
                       keys[rng.integers(0, keys.size, n)])
    elif kind == "all_unknown":
        ids = strangers
    elif kind == "mixed_acquire":
        ids = keys[rng.integers(0, keys.size, n)]
        acq = rng.integers(1, 9, n).astype(np.int32)
    elif kind == "prioritized":
        ids = keys[np.minimum(rng.zipf(1.2, n) - 1, keys.size - 1)]
        acq = rng.integers(1, 4, n).astype(np.int32)
        pr = rng.random(n) < 0.1
    return ids.astype(np.int64), acq, pr


def _numpy_prep(snapshot, bucket, ids, acq, pr):
    cfg = EngineConfig(max_flows=8, max_namespaces=1, batch_size=bucket)
    slots = DefaultTokenService._lookup_from(snapshot, ids)
    order, packed = DefaultTokenService._prep_batch(cfg, slots, acq, pr)
    return slots, order, packed, bool(acq.min() == acq.max())


def _assert_identical(got, want):
    for g, w, name in zip(got[:3], want[:3], ("slots", "order", "packed")):
        if w is None:
            assert g is None, f"{name}: numpy gives None"
            continue
        assert g is not None, f"{name}: numpy gives an array"
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        assert g.tobytes() == w.tobytes(), name
    assert got[3] is want[3], "uniform"


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("deployment,bucket,n", SHAPES,
                         ids=[f"{d}-b{b}-n{n}" for d, b, n in SHAPES])
def test_the_native_pass_gives_the_numpy_preps_bytes(
        native, snapshots, deployment, bucket, n, kind):
    snapshot = snapshots[deployment]
    ids, acq, pr = _frame(kind, n, snapshot, seed=bucket * 31 + n)
    got = native.flow_prep(snapshot, ids, acq, pr, bucket)
    _assert_identical(got, _numpy_prep(snapshot, bucket, ids, acq, pr))
    assert got[2].flags.owndata and got[2].flags.writeable


@pytest.mark.parametrize("n", (1, 63, 64))
def test_an_empty_lookup_snapshot_resolves_no_row(native, n):
    empty = (np.empty(0, np.int64), np.empty(0, np.int32))
    ids = np.arange(n, dtype=np.int64)
    acq, pr = np.full(n, 2, np.int32), np.zeros(n, bool)
    got = native.flow_prep(empty, ids, acq, pr, 64)
    _assert_identical(got, _numpy_prep(empty, 64, ids, acq, pr))
    assert (got[0] == -1).all() and got[1] is None


@pytest.mark.parametrize("kind", ("random", "sorted", "prioritized"))
def test_a_fused_frame_is_written_into_its_staging_row(
        native, snapshots, kind):
    """``out=block[:, f]``: the request lines of frame ``f`` only, as
    ``pack_requests_into`` lays them; the head line and the other frames
    stay the block's own."""
    depth, cap, snapshot = 4, 1024, snapshots["mesh-100k"]
    cfg = EngineConfig(max_flows=8, max_namespaces=1, batch_size=cap)
    block, want = (alloc_packed_block(cfg, depth) for _ in range(2))
    block[ROW_HEAD] = want[ROW_HEAD] = 77  # the block's own
    for f in (2, 0):
        ids, acq, pr = _frame(kind, cap, snapshot, seed=f)
        slots, order, packed, _uniform = native.flow_prep(
            snapshot, ids, acq, pr, cap, out=block[:, f])
        w_slots, w_order, _packed, _u = _numpy_prep(
            snapshot, cap, ids, acq, pr)
        sel = slice(None) if w_order is None else w_order
        pack_requests_into(want, f, w_slots[sel], acq[sel], pr[sel])
        assert np.shares_memory(packed, block)
        _assert_identical((slots, order, block, True),
                          (w_slots, w_order, want, True))
    assert block.shape == (PACKED_LINES, depth, cap)


# -- the service: which prep ran, and that both answer alike ------------------
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
G = ThresholdMode.GLOBAL
SM = server_metrics()


def _verdicts(frames):
    svc = DefaultTokenService(CFG, fuse_depths=(2,))
    svc.load_rules([ClusterFlowRule(flow_id=i, count=6.0 + i % 3, mode=G)
                    for i in range(1, 30)])
    rng = np.random.default_rng(3)
    n0, d0 = SM.prep_native_total, SM.prep_ms.snapshot()["count"]
    out = []
    for n in frames:
        ids = rng.integers(0, 34, n).astype(np.int64)
        acq = rng.integers(1, 3, n).astype(np.int32)
        out.append(svc.request_batch_arrays(ids, acq, rng.random(n) < 0.2))
    svc.close()
    return out, SM.prep_native_total - n0, SM.prep_ms.snapshot()["count"] - d0


def test_prep_native_total_counts_the_dispatches_the_pass_prepped(
        manual_clock, monkeypatch):
    """A frame, a short one and an oversized pull (a fused pair and a tail):
    every dispatch counts once where the library is built, none where numpy
    prepped them, and the verdicts are the same row for row."""
    frames = (64, 17, 2 * 64 + 9)
    got, native_n, dispatches = _verdicts(frames)
    assert dispatches == 4
    assert native_n == (dispatches if _native_built() else 0)
    monkeypatch.setattr(token_service._native, "flow_prep",
                        lambda *a, **kw: None)  # the library is not built
    want, native_n, dispatches = _verdicts(frames)
    assert (native_n, dispatches) == (0, 4)
    for g, w in zip(got, want):
        for a, b, name in zip(g, w, ("status", "remaining", "wait")):
            np.testing.assert_array_equal(a, b, err_msg=name)
    assert "prep_native_total" in SM.stage_snapshot()
