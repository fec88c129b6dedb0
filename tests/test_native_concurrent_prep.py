"""Concurrent prep in one native pass (``sn_concurrent_prep`` through
``native.lib.concurrent_prep``) held to the numpy prep it replaces on the
concurrency lane's path: ``ConcurrentPlane.prep_numpy``. The contract is
identity, not equivalence (the rule PR 42 taught the flow lane): the same
``bucket``, every byte of ``packed`` and the same ``acq_rows`` / ``rel_rows``
a step, so that a concurrency step given the native argument cannot answer
differently.

The identity cases are skipped, not passed, where the library is not built;
the served-path case at the end runs everywhere, on whichever prep the
library gives.
"""

import json
import os

import numpy as np
import pytest

from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule, ConcurrentPlane
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine import concurrent as CE
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.native import lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INT64 = np.iinfo(np.int64)
SM = server_metrics()


def _deployment():
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "concurrent-mesh-100k.json"),
              encoding="utf-8") as f:
        doc = json.load(f)
    return (doc["engine"]["max_flows"], doc["max_tokens"],
            tuple(doc["serve_buckets"]))


# name -> (max_flows, max_tokens, serve buckets): the cell's deployment as its
# file has it, and the small plane of tests/test_concurrent_batch.py
PLANES = {"concurrent-mesh-100k": _deployment(), "small": (16, 64, (8, 32))}


def _edge_tokens(max_tokens):
    """Token ids that are none (``split_token_ids``: slot -1), the ring's
    edges and the last generation 31 bits reach."""
    top = (2**31 - 1) * max_tokens
    return np.array([INT64.min, INT64.min + 1, -max_tokens, -1, 0, 1,
                     max_tokens - 1, max_tokens, max_tokens + 1, top - 1, top,
                     top + max_tokens - 1, top + max_tokens, INT64.max - 1,
                     INT64.max], np.int64)


BUCKET_KINDS = ("acquires_only", "releases_only", "mixed", "full_bucket",
                "unknown_flows", "duplicate_flows", "duplicate_tokens",
                "edge_token_ids", "sorted_already", "empty_lookup")
CHAIN_KINDS = ("acquires_chain", "releases_chain", "both_chain",
               "more_release_steps", "exact_multiples")


def _native_built() -> bool:
    """Whether ``lib.concurrent_prep`` runs the native pass here (a library
    that is missing, or older than the entry, makes it return None)."""
    empty = (np.empty(0, np.int64), np.empty(0, np.int32))
    return lib.concurrent_prep(
        empty, np.zeros(1, np.int64), np.ones(1, np.int32),
        np.zeros(1, bool), 64, [(0, 1, 0, 0, 8)]) is not None


@pytest.fixture(scope="module")
def native():
    if not _native_built():
        pytest.skip(f"native library not built: {lib._load_error}")
    return lib


@pytest.fixture(scope="module")
def planes():
    """name -> (plane, look-up snapshot): sparse flow ids (negative ones
    among them) on a permutation of the slots, seven in eight slots taken."""
    out = {}
    for name, (max_flows, max_tokens, buckets) in PLANES.items():
        rng = np.random.default_rng(7)
        n = max_flows * 7 // 8
        fids = np.unique(rng.integers(-2**40, 2**40, 2 * n))[:n]
        slots = rng.permutation(max_flows)[:n].astype(np.int32)
        out[name] = (ConcurrentPlane(max_flows, max_tokens, buckets),
                     (fids, slots))
    return out


def _frame(rng, lookup, max_tokens, n_acq, n_rel, kind):
    """``(ids, counts, is_release)`` of ``n_acq`` acquires and ``n_rel``
    releases in one arrival order."""
    fids, _slots = lookup
    flows = rng.choice(fids, n_acq)
    tokens = rng.integers(1, 40 * max_tokens, n_rel)
    if kind == "unknown_flows":
        # beside the known keys, under the least and past the greatest
        unknown = np.concatenate([fids + 1, [fids[0] - 1, INT64.min,
                                             INT64.max, 0]])
        unknown = unknown[~np.isin(unknown, fids)]
        miss = rng.random(n_acq) < 0.5
        flows[miss] = rng.choice(unknown, int(miss.sum()))
    elif kind == "duplicate_flows":
        flows = rng.choice(fids[:3], n_acq)
    elif kind == "duplicate_tokens":
        tokens = rng.choice(tokens[:3], n_rel)
    elif kind == "edge_token_ids":
        edges = _edge_tokens(max_tokens)
        at = rng.random(n_rel) < 0.6
        tokens[at] = rng.choice(edges, int(at.sum()))
    ids = np.concatenate([flows, tokens]).astype(np.int64)
    rel = np.concatenate([np.zeros(n_acq, bool), np.ones(n_rel, bool)])
    counts = rng.integers(0, 5, n_acq + n_rel).astype(np.int32)
    if kind == "sorted_already":
        # each kind's rows arrive in the order the step wants them
        slot_of = dict(zip(fids.tolist(), lookup[1].tolist()))
        flows = np.array(sorted(flows.tolist(), key=slot_of.get), np.int64)
        ids = np.concatenate([flows, np.sort(tokens)]).astype(np.int64)
        return ids, counts, rel
    order = rng.permutation(n_acq + n_rel)
    return ids[order], counts[order], rel[order]


def _assert_identical(got, want):
    assert len(got) == len(want)
    for step, (g, w) in enumerate(zip(got, want)):
        assert g[0] == w[0], f"step {step}: bucket"
        for a, b, name in zip(g[1:], w[1:], ("packed", "acq_rows",
                                             "rel_rows")):
            assert a.dtype == b.dtype and a.shape == b.shape, (step, name)
            assert a.flags.c_contiguous, (step, name)
            np.testing.assert_array_equal(a, b, err_msg=f"{step}: {name}")
            assert a.tobytes() == b.tobytes(), (step, name)


def _both(native, plane, lookup, ids, counts, rel):
    """The native pass's parts and the numpy prep's of one dispatch, the
    native ones checked to be fresh arrays that only the caller holds."""
    n_rel = int(rel.sum())
    plan = plane.step_plan(len(ids) - n_rel, n_rel)
    before = ids.copy(), counts.copy(), rel.copy()
    got = native.concurrent_prep(lookup, ids, counts, rel,
                                 plane.config.max_tokens, plan)
    again = native.concurrent_prep(lookup, ids, counts, rel,
                                   plane.config.max_tokens, plan)
    for (bucket, packed, *_rows), (_b, packed2, *_rows2) in zip(got, again):
        assert not np.shares_memory(packed, packed2)
        assert packed.shape == (CE.PACKED_LINES, bucket)
    for a, b in zip(before, (ids, counts, rel)):
        np.testing.assert_array_equal(a, b)  # the frame is only read
    want = plane.prep_numpy(lookup, ids, counts, rel, plan)
    _assert_identical(got, want)
    return got


def _bucket_cases():
    for name, (_f, _t, buckets) in PLANES.items():
        for k, bucket in enumerate(buckets):
            for kind in BUCKET_KINDS:
                yield pytest.param(name, k, kind, id=f"{name}-{bucket}-{kind}")


@pytest.mark.parametrize("name, k, kind", _bucket_cases())
def test_every_part_is_the_numpy_preps_in_every_bucket(
        native, planes, name, k, kind):
    """One step a dispatch, the longer kind's rows past the bucket below
    and within this one."""
    plane, lookup = planes[name]
    bucket = plane.buckets[k]
    below = plane.buckets[k - 1] if k else 0
    rng = np.random.default_rng(1000 * k + len(kind))
    long = bucket if kind == "full_bucket" else int(
        rng.integers(below + 1, bucket + 1))
    short = bucket if kind == "full_bucket" else int(rng.integers(0, long + 1))
    n_acq, n_rel = (long, short) if rng.random() < 0.5 else (short, long)
    if kind == "acquires_only":
        n_acq, n_rel = long, 0
    elif kind == "releases_only":
        n_acq, n_rel = 0, long
    elif kind in ("unknown_flows", "duplicate_flows"):
        n_acq, n_rel = long, short
    elif kind in ("duplicate_tokens", "edge_token_ids"):
        n_acq, n_rel = short, long
    if kind == "empty_lookup":
        lookup = (np.empty(0, np.int64), np.empty(0, np.int32))
        ids, counts, rel = _frame(rng, planes[name][1],
                                  plane.config.max_tokens, n_acq, n_rel, kind)
    else:
        ids, counts, rel = _frame(rng, lookup, plane.config.max_tokens,
                                  n_acq, n_rel, kind)
    (got,) = _both(native, plane, lookup, ids, counts, rel)
    assert got[0] == bucket
    assert (len(got[2]), len(got[3])) == (n_acq, n_rel)
    head = got[1][CE.ROW_HEAD]
    assert head[:3].tolist() == [0, n_acq, n_rel] and not head[3:].any()
    slots = got[1][CE.ROW_SLOT]
    if kind == "empty_lookup":
        assert (slots[:n_acq] == CE.NO_SLOT).all()
    if kind == "unknown_flows":
        assert (slots[:n_acq] == CE.NO_SLOT).any()
    assert (slots[n_acq:] == CE.PAD_SLOT).all()


@pytest.mark.parametrize("name", PLANES)
@pytest.mark.parametrize("kind", ("one_acquire", "one_unknown_acquire",
                                  "one_release", "one_bad_release",
                                  "no_rows"))
def test_a_pull_of_one_row_or_none(native, planes, name, kind):
    """Single type-3 / type-4 frames ride the lane as one-row frames (PR
    45); a dispatch of no rows is one step of padding."""
    plane, lookup = planes[name]
    fid = int(lookup[0][3])
    ids, rel = {
        "one_acquire": ([fid], [False]),
        "one_unknown_acquire": ([fid + 1], [False]),
        "one_release": ([3 * plane.config.max_tokens + 5], [True]),
        "one_bad_release": ([0], [True]),
        "no_rows": ([], []),
    }[kind]
    ids, rel = np.array(ids, np.int64), np.array(rel, bool)
    (got,) = _both(native, plane, lookup, ids,
                   np.full(len(ids), 2, np.int32), rel)
    assert got[0] == plane.buckets[0]
    if kind == "one_release":
        assert got[1][CE.ROW_TOK_SLOT, 0] == 5
        assert got[1][CE.ROW_TOK_GEN, 0] == 3
    if kind == "one_bad_release":
        assert got[1][CE.ROW_TOK_SLOT, 0] == -1


@pytest.mark.parametrize("name", PLANES)
@pytest.mark.parametrize("kind", CHAIN_KINDS)
def test_rows_past_the_largest_bucket_chain_their_steps(
        native, planes, name, kind):
    """The releases' steps first, the last of them carrying the first chunk
    of the acquires, every step sorted and packed on its own."""
    plane, lookup = planes[name]
    cap = plane.buckets[-1]
    n_acq, n_rel, steps = {
        "acquires_chain": (2 * cap + 3, cap // 2, 3),
        "releases_chain": (5, 2 * cap + 1, 3),
        "both_chain": (cap + 7, cap + 9, 3),
        "more_release_steps": (cap + 1, 3 * cap + 2, 5),
        "exact_multiples": (2 * cap, 2 * cap, 3),
    }[kind]
    rng = np.random.default_rng(len(kind))
    ids, counts, rel = _frame(rng, lookup, plane.config.max_tokens, n_acq,
                              n_rel, "mixed")
    got = _both(native, plane, lookup, ids, counts, rel)
    assert len(got) == steps
    acq_rows = np.concatenate([part[2] for part in got])
    rel_rows = np.concatenate([part[3] for part in got])
    # every row in exactly one step, each kind's chunks in arrival order
    assert np.sort(acq_rows).tolist() == np.flatnonzero(~rel).tolist()
    assert np.sort(rel_rows).tolist() == np.flatnonzero(rel).tolist()
    assert all(len(part[2]) <= cap and len(part[3]) <= cap for part in got)


def test_a_frame_the_plan_does_not_fit_is_refused(native, planes):
    """The binding checks what it hands the library: a plan for other
    counts than the frame's, a run past its bucket, and arrays of different
    lengths raise and write nothing past an array's end."""
    plane, lookup = planes["small"]
    ids = np.arange(1, 7, dtype=np.int64)
    counts, rel = np.ones(6, np.int32), np.array([0, 1, 0, 1, 1, 0], bool)
    tokens = plane.config.max_tokens
    for plan in ([(0, 4, 0, 2, 8)],  # four acquires: the frame has three
                 [(0, 3, 0, 4, 8)],  # ... and three releases
                 [(0, 3, 0, 3, 2)],  # a bucket under its runs
                 [(0, 9, 0, 3, 8)],  # more rows than the frame
                 [(2, 1, 0, 3, 8)]):
        with pytest.raises(ValueError):
            native.concurrent_prep(lookup, ids, counts, rel, tokens, plan)
    with pytest.raises(ValueError):
        native.concurrent_prep(lookup, ids, counts[:5], rel, tokens,
                               [(0, 3, 0, 3, 8)])


# -- the service: which prep ran, and that both answer alike ------------------
RULES = [ConcurrentFlowRule(1, 3), ConcurrentFlowRule(2, 5),
         ConcurrentFlowRule(3, 40), ConcurrentFlowRule(4, 1),
         ConcurrentFlowRule(5, 0), ConcurrentFlowRule(6, 25)]


def _serve(clock):
    """A fixed script through one fresh service (a ring of 64 token slots,
    buckets 8 and 32): acquires, releases of what the last frames issued
    mixed with stale and duplicate ids, a single acquire and a single
    release (one-row frames), a frame past the largest bucket (two steps)
    and an expiry in between. ``(verdicts a frame, snapshot() a frame,
    native preps counted, dispatches)``."""
    svc = DefaultTokenService(
        EngineConfig(max_flows=16, max_namespaces=2, batch_size=32),
        serve_buckets=(8, 32), concurrent_max_tokens=64)
    svc.load_concurrent_rules(RULES)
    svc.close()  # the timer off: expiry is stepped by hand, on the clock
    rng = np.random.default_rng(5)
    n0 = SM.concurrent_prep_native_total
    d0 = SM.prep_ms.snapshot()["count"]
    out, snaps, live = [], [], []
    for step, n_acq in enumerate((6, 1, 20, 0, 13, 40, 3, 0, 9)):
        flows = rng.choice([1, 2, 3, 4, 5, 6, 9], n_acq)
        n_back = int(rng.integers(0, len(live) + 1)) if step != 1 else 0
        back = [live.pop(int(rng.integers(len(live)))) for _ in range(n_back)]
        rel_ids = back + back[:1] + ([0, -3, 10**15] if step % 2 else [])
        if step == 7:
            rel_ids = rel_ids[:1] or [77]  # a single release
        order = rng.permutation(n_acq + len(rel_ids))
        ids = np.concatenate([flows, rel_ids]).astype(np.int64)[order]
        rel = np.concatenate([np.zeros(n_acq, bool),
                              np.ones(len(rel_ids), bool)])[order]
        counts = np.where(rel, 0, 1 + ids % 2).astype(np.int32)
        got = svc.request_concurrent_batch(ids, counts, rel)
        live += [int(t) for t in got[3] if t]
        out.append(got)
        snaps.append(svc.concurrent_stats())
        clock.advance(2001 if step == 4 else 40)
        if step == 4:
            svc.concurrent_tick()
            live.clear()
    return (out, snaps, SM.concurrent_prep_native_total - n0,
            SM.prep_ms.snapshot()["count"] - d0)


def test_both_preps_serve_alike_and_the_counter_says_which_ran(
        manual_clock, monkeypatch):
    """The same frames through a service on the native pass and through one
    forced onto the fallback (``concurrent_prep`` returning None: the
    library is not built): the same verdicts and token ids row for row and
    the same ``snapshot()`` after every frame;
    ``concurrent_prep_native_total`` counts exactly the dispatches the pass
    prepped, in every surface of the counters."""
    got, got_snaps, native_n, dispatches = _serve(manual_clock)
    assert dispatches == len(got) == 9
    assert native_n == (dispatches if _native_built() else 0)
    monkeypatch.setattr(lib, "concurrent_prep", lambda *a, **kw: None)
    want, want_snaps, native_n, dispatches = _serve(manual_clock)
    assert (native_n, dispatches) == (0, 9)
    seen = set()
    for g, w in zip(got, want):
        for a, b, name in zip(g, w, ("status", "remaining", "wait",
                                     "token_ids")):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b, err_msg=name)
        seen |= set(g[0].tolist())
    assert {int(TokenStatus.OK), int(TokenStatus.BLOCKED),
            int(TokenStatus.NO_RULE_EXISTS), int(TokenStatus.RELEASE_OK),
            int(TokenStatus.ALREADY_RELEASE)} <= seen
    for g, w in zip(got_snaps, want_snaps):
        # each service counts its engine clock from its first use
        assert g == w
    assert any(snap["tokens"] for snap in got_snaps)
    assert "concurrent_prep_native_total" in SM.stage_snapshot()
    assert "concurrentPrepNativeTotal" in SM.snapshot()
    assert "sentinel_server_concurrent_prep_native_total " in SM.render()
