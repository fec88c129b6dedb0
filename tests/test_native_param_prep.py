"""Param prep in one native pass (``sn_param_prep`` through
``native.lib.param_prep``) held to the numpy prep it replaces on the
hot-parameter lane's path: ``DefaultTokenService._param_rows`` +
``engine.param.pack_param_rows``. The contract is identity, not equivalence
(the rule PR 42 taught the flow lane): the same ``req_slot`` and every byte
of ``packed``, so that a param step given the native argument cannot answer
differently.

The identity cases are skipped, not passed, where the library is not built;
the served-path cases at the end run everywhere, on whichever prep the
library gives.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from sentinel_tpu.cluster import token_service
from sentinel_tpu.cluster.token_service import (
    ClusterParamFlowRule,
    DefaultTokenService,
)
from sentinel_tpu.engine import EngineConfig, TokenStatus
from sentinel_tpu.engine.param import (
    ParamConfig,
    pack_param_rows,
    packed_lines,
    prep_geometry,
)
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.native import lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INT64 = np.iinfo(np.int64)
# hashes at the edges of int64, items of some rules and values of some rows
EDGES = np.array([INT64.min, INT64.min + 1, -1, 0, 1, INT64.max - 1,
                  INT64.max], np.int64)
REQUESTS = (1, 11, 63, 64, 65, 1024, 1025, 4096)
KINDS = ("mixed", "no_rule_first", "no_rule_last", "no_rule_all",
         "empty_rule_table", "rules_without_items", "item_hits",
         "near_misses", "edge_hashes", "ids_outside_the_rules")


def _deployment(name):
    """``(ParamConfig, serve buckets, the family's rule parameters)`` of a
    cell's configuration file."""
    with open(os.path.join(ROOT, "cellbench", "configs", name + ".json"),
              encoding="utf-8") as f:
        doc = json.load(f)
    param = {key: value for key, value in doc["param"].items()
             if key in ParamConfig._fields}
    return ParamConfig(**param), doc["serve_buckets"], doc["rules"]


def _geometries():
    """name -> (ParamConfig, serve buckets, rules, items a rule, count, item
    threshold): the two hot-parameter deployments as their files have them,
    and one with no width a power of two (SALSA doubles the hash width)."""
    out = {}
    for name in ("hot-param-1k", "demo-cluster-param-1k"):
        cfg, buckets, rules = _deployment(name)
        out[name] = (cfg, buckets, rules["n_rules"], rules["hot_values"],
                     float(rules["count"]), float(rules["hot_count"]))
    out["odd-widths"] = (
        ParamConfig(max_param_rules=64, depth=3, width=1000, sketch="salsa",
                    slim_depth=3, slim_width=100),
        [64, 1024], 37, 3, 2.5, 7.25)
    return out


GEOMETRIES = _geometries()


def _native_built() -> bool:
    """Whether ``lib.param_prep`` runs the native pass here (a library that
    is missing, or older than the entry, makes it return None)."""
    empty64, empty32 = np.empty(0, np.int64), np.empty(0, np.int32)
    emptyf = np.empty(0, np.float32)
    return lib.param_prep(
        (empty64, empty32, emptyf, empty64, empty64, emptyf),
        np.zeros(1, np.int64), np.ones(1, np.int32),
        np.zeros((1, 1), np.int64), 64, (2, 64, 0, 0, 0)) is not None


@pytest.fixture(scope="module")
def native():
    if not _native_built():
        pytest.skip(f"native library not built: {lib._load_error}")
    return lib


def _rule_table(geometry, table):
    """``{flow id: (slot, count, {item hash: threshold})}`` as the service
    keeps its param rules: sparse ids (negative ones among them) on a
    permutation of the slots. Every rule has one item nobody shares, and
    items drawn from a pool that many rules share (a hash known under
    another slot) with ``EDGES`` in it."""
    cfg, _buckets, n_rules, per_rule, count, item_thr = GEOMETRIES[geometry]
    if table == "empty":
        return {}
    rng = np.random.default_rng(11)
    fids = np.unique(rng.integers(-2**40, 2**40, 2 * n_rules))[:n_rules]
    slots = rng.permutation(cfg.max_param_rules)[:n_rules]
    pool = np.concatenate([EDGES, rng.integers(INT64.min, INT64.max, 57)])
    own = rng.integers(INT64.min, INT64.max, n_rules)
    rules = {}
    for r, (fid, slot) in enumerate(zip(fids.tolist(), slots.tolist())):
        items = {}
        if table == "items":
            shared = pool[(r + np.arange(per_rule - 1)) % pool.size]
            items = {int(h): item_thr + (r + j) % 3
                     for j, h in enumerate([own[r], *shared])}
        rules[fid] = (slot, count + r % 4, items)
    return rules


def _tables(rules):
    """The look-up snapshot the service makes of ``rules``."""
    return DefaultTokenService._param_tables(
        SimpleNamespace(_param_rules=rules))


def _frame(kind, n, k, rules, seed):
    """``(flow_ids int64[n], acquires int32[n], hashes int64[n, k])`` of one
    kind; the acquires are never uniform beyond one row."""
    rng = np.random.default_rng(seed)
    acq = rng.integers(1, 6, n).astype(np.int32)
    fids = np.array(sorted(rules), np.int64)
    anything = rng.integers(INT64.min, INT64.max, (n, k))
    if fids.size == 0:
        return rng.integers(-2**40, 2**40, n), acq, anything
    # no rule there: between two rules, below the first, above the last
    between = np.setdiff1d(fids + 1, fids)
    strangers = np.concatenate([
        between[rng.integers(0, between.size, n)][: n - n // 3],
        rng.integers(fids[0] - 9, fids[0], n // 6 + 1),
        rng.integers(fids[-1] + 1, fids[-1] + 9, n // 6 + 1)])[:n]
    at = rng.integers(0, fids.size, n)
    ids = fids[at]
    # [rule, item]: every rule has as many; column 0 is the one nobody shares
    items = np.array([list(rules[f][2]) for f in fids.tolist()], np.int64)
    if items.size:
        mine = items[at[:, None], rng.integers(0, items.shape[1], (n, k))]
        other = (at[:, None] + rng.integers(1, fids.size, (n, k))) % fids.size
        theirs = items[other, 0]  # an item, of another rule only
        known = np.unique(items)
        someones = known[rng.integers(0, known.size, (n, k))]
    else:
        mine = theirs = someones = anything
    if kind == "item_hits":
        hashes = mine
    elif kind == "near_misses":
        hashes = theirs
    elif kind == "edge_hashes":
        hashes = EDGES[rng.integers(0, EDGES.size, (n, k))]
    else:  # a third each: the rule's own item, another rule's, anything
        pick = rng.integers(0, 3, (n, 1))
        hashes = np.where(pick == 0, mine,
                          np.where(pick == 1, someones, anything))
        ids = np.where(rng.random(n) < 0.25, strangers, ids)
    if kind == "no_rule_first":
        ids[0] = strangers[0]
    elif kind == "no_rule_last":
        ids[-1] = strangers[-1]
    elif kind == "no_rule_all":
        ids = strangers
    elif kind == "ids_outside_the_rules":
        ids = np.array([fids[0] - 1, fids[0], fids[-1], fids[-1] + 1,
                        INT64.min, INT64.max])[np.arange(n) % 6]
    return ids.astype(np.int64), acq, hashes.reshape(n, k)


def _numpy_prep(lookup, cfg, bucket, ids, acq, hashes):
    n, k = hashes.shape
    req_slot, row_slot, row_acq, thr, idx, idx_slim = (
        DefaultTokenService._param_rows(lookup, cfg, ids, acq, hashes))
    return req_slot, pack_param_rows(
        cfg, bucket, row_slot, row_acq, thr, idx, idx_slim, 0, k, n)


def _bucket(buckets, rows):
    return DefaultTokenService._param_bucket(
        SimpleNamespace(_serve_buckets=buckets), rows)


def _assert_identical(got, want):
    for g, w, name in zip(got, want, ("req_slot", "packed")):
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        assert g.tobytes() == w.tobytes(), name


@pytest.fixture(scope="module")
def lookups():
    """(geometry, table) -> (the rules, their look-up snapshot)."""
    out = {}
    for geometry in GEOMETRIES:
        for table in ("items", "no_items", "empty"):
            rules = _rule_table(geometry, table)
            out[geometry, table] = rules, _tables(rules)
    return out


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("slim", (True, False), ids=("slim", "no_slim"))
@pytest.mark.parametrize("k", (1, 2, 4))
@pytest.mark.parametrize("n", REQUESTS)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_native_pass_gives_the_numpy_preps_bytes(
        native, lookups, geometry, n, k, slim, kind):
    cfg, buckets = GEOMETRIES[geometry][:2]
    if not slim:
        cfg = cfg._replace(slim_width=0)
    table = {"empty_rule_table": "empty",
             "rules_without_items": "no_items"}.get(kind, "items")
    rules, lookup = lookups[geometry, table]
    ids, acq, hashes = _frame(kind, n, k, rules, seed=n * 31 + k)
    bucket = _bucket(buckets, n * k)
    got = native.param_prep(lookup, ids, acq, hashes, bucket,
                            prep_geometry(cfg))
    want = _numpy_prep(lookup, cfg, bucket, ids, acq, hashes)
    _assert_identical(got, want)
    req_slot, packed = got
    assert packed.shape == (packed_lines(cfg), bucket)
    assert packed.flags.owndata and packed.flags.writeable
    # the kind is what its name says
    ruled, thr = req_slot >= 0, packed[2, :n * k].view(np.float32)
    by_slot = {slot: (count, items) for slot, count, items in rules.values()}
    item = np.array([
        int(h) in by_slot[s][1] if s >= 0 else False
        for s, h in zip(np.repeat(req_slot, k).tolist(),
                        hashes.reshape(-1).tolist())])
    if kind in ("no_rule_all", "empty_rule_table"):
        assert not ruled.any() and not thr.any()
    elif kind == "no_rule_first":
        assert not ruled[0]
    elif kind == "no_rule_last":
        assert not ruled[-1]
    elif kind == "item_hits":
        assert ruled.all() and item.all()
    elif kind == "near_misses":
        assert ruled.all() and not item.any()
    elif kind == "rules_without_items":
        assert not item.any()
    elif kind == "ids_outside_the_rules":
        assert ruled.tolist() == [i % 6 in (1, 2) for i in range(n)]
    assert n == 1 or acq.min() < acq.max()


def test_a_chunk_that_does_not_fit_its_bucket_is_refused(native, lookups):
    cfg = GEOMETRIES["odd-widths"][0]
    _rules, lookup = lookups["odd-widths", "items"]
    ids, acq = np.zeros(33, np.int64), np.ones(33, np.int32)
    hashes = np.zeros((33, 2), np.int64)
    for bad in ((ids, acq, hashes, 64),  # 66 rows
                (ids[:5], acq[:5], hashes[:4], 64),
                (ids[:1], acq[:1], hashes[:1], 2)):  # no room for the head
        with pytest.raises(ValueError):
            native.param_prep(lookup, *bad, prep_geometry(cfg))


# -- the service: which prep ran, and that both answer alike ------------------
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=256)
PCFG = ParamConfig(max_param_rules=16, depth=4, width=512)
BUCKETS = (64, 256)
SM = server_metrics()
NO_RULE = int(TokenStatus.NO_RULE_EXISTS)


def _param_rules(shift=0):
    """Twelve rules with items; ``shift`` moves the set to other flow ids (a
    reload that frees and re-deals the slots)."""
    return [
        ClusterParamFlowRule(
            r + shift, 3.0 + r % 3,
            item_thresholds=((100 + r, 6.0), (7, 2.0 + r % 2)),
            namespace=f"ns{r % 2}")
        for r in range(1, 13)]


def _service():
    svc = DefaultTokenService(CFG, param_config=PCFG, serve_buckets=BUCKETS,
                              fuse_depths=())
    svc.load_param_rules(_param_rules())
    return svc


def _frames(sizes, k, seed=5):
    """Frames on rules 1..14 (13 and 14 have none) and a few hot values."""
    rng = np.random.default_rng(seed)
    values = np.concatenate([np.arange(100, 114), [7, -1, INT64.max],
                             rng.integers(INT64.min, INT64.max, 8)])
    return [(rng.integers(1, 15, n).astype(np.int64),
             rng.integers(1, 3, n).astype(np.int32),
             values[rng.integers(0, values.size, (n, k))])
            for n in sizes]


def _spy(svc, calls):
    """Every param step's host argument, with the bytes it went in with."""
    build = svc._param_step_fn

    def built(bucket):
        step = build(bucket)

        def call(state, packed):
            calls.append((packed, packed.tobytes()))
            return step(state, packed)

        return call

    svc._param_step_fn = built


def _count_numpy_preps(monkeypatch):
    """How often the numpy prep ran: ``calls[0]``."""
    calls, rows = [0], DefaultTokenService._param_rows

    def counted(*a):
        calls[0] += 1
        return rows(*a)

    monkeypatch.setattr(DefaultTokenService, "_param_rows",
                        staticmethod(counted))
    return calls


def _without_the_library(monkeypatch):
    monkeypatch.setattr(token_service._native, "param_prep",
                        lambda *a, **kw: None)


def _serve(frames, clock, step_ms):
    """The frames through one fresh service, two in flight, ``step_ms`` of
    the clock apart: ``(verdicts, native preps counted, dispatches, every
    step's argument)``."""
    svc, calls = _service(), []
    _spy(svc, calls)
    n0, d0 = SM.param_prep_native_total, SM.prep_ms.snapshot()["count"]
    out, pending = [], []
    for ids, acq, hashes in frames:
        pending.append(svc.dispatch_params_batch(ids, acq, hashes))
        if len(pending) == 2:
            out.append(pending.pop(0)())
        clock.sleep(step_ms)
    out.extend(mat() for mat in pending)
    svc.close()
    return (out, SM.param_prep_native_total - n0,
            SM.prep_ms.snapshot()["count"] - d0, calls)


@pytest.mark.parametrize("k", (1, 2))
def test_both_preps_answer_alike_over_a_bucket_roll(
        manual_clock, monkeypatch, k):
    """The same frames, the clock rolling the sketch's 500 ms buckets under
    them, with the native pass live and with ``param_prep`` returning None
    (the library is not built): the same verdicts row for row, and the same
    bytes to every step. One prep a dispatch: with the library no dispatch
    runs ``_param_rows``, without it every one does, and
    ``param_prep_native_total`` counts exactly the dispatches the pass
    prepped."""
    frames = _frames((64, 17, 1, 200, 64, 33, 256 // k, 5, 90), k)
    numpy_preps = _count_numpy_preps(monkeypatch)
    got, native_n, dispatches, got_args = _serve(frames, manual_clock, 170)
    built = _native_built()
    assert dispatches == len(frames)
    assert native_n == (dispatches if built else 0)
    assert numpy_preps[0] == (0 if built else dispatches)
    _without_the_library(monkeypatch)
    want, native_n, dispatches, want_args = _serve(frames, manual_clock, 170)
    assert (native_n, dispatches) == (0, len(frames))
    assert numpy_preps[0] == (1 if built else 2) * dispatches
    blocked = 0
    for g, w in zip(got, want):
        for a, b, name in zip(g, w, ("status", "remaining", "wait")):
            np.testing.assert_array_equal(a, b, err_msg=name)
        blocked += int((g[0] == int(TokenStatus.BLOCKED)).sum())
    assert blocked  # the sketch had something to refuse
    # a step a chunk: k = 2 makes two of the frame of 200 requests
    assert len(got_args) == len(want_args) == len(frames) + (k == 2)
    for (g, g_bytes), (_w, w_bytes) in zip(got_args, want_args):
        # each service counts its engine clock from its first use
        assert g_bytes == w_bytes
        assert g.tobytes() == g_bytes  # nobody wrote after the clock
    assert {"param_prep_native_total",
            "prep_native_total"} <= set(SM.stage_snapshot())
    assert "paramPrepNativeTotal" in SM.snapshot()
    assert "sentinel_server_param_prep_native_total " in SM.render()


@pytest.mark.parametrize("native_pass", (True, False),
                         ids=("library", "numpy"))
def test_a_batch_past_the_largest_bucket_is_cut_into_whole_requests(
        manual_clock, monkeypatch, native_pass):
    """190 requests of 3 values against a largest bucket of 256 rows: 85
    requests a chunk and 20 left for a bucket of 64, each chunk a step of
    its own with the bytes the numpy prep gives for that chunk alone, all
    under one dispatch."""
    if not native_pass:
        _without_the_library(monkeypatch)
    (ids, acq, hashes), = _frames((190,), 3)
    svc, calls = _service(), []
    _spy(svc, calls)
    n0, d0 = SM.param_prep_native_total, SM.prep_ms.snapshot()["count"]
    status, _remaining, _wait = svc.dispatch_params_batch(ids, acq, hashes)()
    assert SM.prep_ms.snapshot()["count"] - d0 == 1
    assert SM.param_prep_native_total - n0 == int(
        native_pass and _native_built())
    assert [(c.shape, int(c[-1, 2])) for c, _b in calls] == [
        ((packed_lines(PCFG), 256), 85), ((packed_lines(PCFG), 256), 85),
        ((packed_lines(PCFG), 64), 20)]
    now = svc._engine_now()
    for (packed, _b), lo in zip(calls, (0, 85, 170)):
        req_slot, want = _numpy_prep(
            svc._param_lookup, PCFG, packed.shape[1], ids[lo:lo + 85],
            acq[lo:lo + 85], hashes[lo:lo + 85])
        want[-1, 0] = now
        assert packed.tobytes() == want.tobytes()
        assert ((status[lo:lo + 85] == NO_RULE) == (req_slot < 0)).all()
    svc.close()


def test_a_rule_reload_between_prep_and_lock_is_prepped_again(manual_clock):
    """``load_param_rules`` after the prep and before the lock: the step
    gets the prep against the live tables, not the one that was made."""
    (ids, acq, hashes), = _frames((64,), 1)
    svc, calls = _service(), []
    _spy(svc, calls)
    stale = svc._param_lookup
    spied = svc._param_step_fn

    def reload_then_build(bucket):  # runs between prep and lock
        svc._param_step_fn = spied
        svc.load_param_rules(_param_rules(shift=2))  # now rules 3..14
        return spied(bucket)

    svc._param_step_fn = reload_then_build
    status, _remaining, _wait = svc.dispatch_params_batch(ids, acq, hashes)()
    assert svc._param_lookup is not stale
    (packed, _bytes), = calls
    req_slot, want = _numpy_prep(svc._param_lookup, PCFG, 64, ids, acq,
                                 hashes)
    want[-1, 0] = svc._engine_now()
    assert packed.tobytes() == want.tobytes()
    assert want.tobytes() != _numpy_prep(stale, PCFG, 64, ids, acq,
                                         hashes)[1].tobytes()
    assert ((status == NO_RULE) == (ids < 3)).all() and (ids < 3).any()
    assert ((req_slot < 0) == (ids < 3)).all()
    svc.close()


def test_two_dispatches_in_flight_never_share_an_argument(manual_clock):
    """The ownership rule, as ``tests/test_sharding.py`` holds it for the
    flow lane: every step's host argument is its dispatch's own array (the
    CPU backend aliases an aligned numpy argument, so one written again
    would be read), and the caller's arrays may be overwritten the moment a
    dispatch returns."""
    svc, calls = _service(), []
    _spy(svc, calls)
    frames = _frames((64, 64, 200, 31, 256, 64), 1)
    got, pending = [], []
    for ids, acq, hashes in frames:
        args = ids.copy(), acq.copy(), hashes.copy()
        pending.append(svc.dispatch_params_batch(*args))
        for arr, junk in zip(args, (13, 9, 7)):
            arr[:] = junk  # a door recycles its decode block
        if len(pending) == 3:
            got.append(pending.pop(0)())
        manual_clock.sleep(40)
    got.extend(mat() for mat in pending)
    assert len(calls) == len(frames)
    for i, (packed, handed_over) in enumerate(calls):
        assert packed.tobytes() == handed_over, i
        assert packed.flags.owndata, i
        assert not any(np.shares_memory(packed, other)
                       for other, _b in calls[:i]), i
    # and the verdicts are those of the rows as they were sent
    ref = _service()
    for (ids, acq, hashes), verdicts in zip(frames, got):
        want = ref.request_params_batch(ids, acq, hashes)
        np.testing.assert_array_equal(verdicts[0], want[0])
        manual_clock.sleep(40)
    for s in (svc, ref):
        s.close()
