"""The table of state columns (``engine.state.STATE_COLUMNS``,
``engine.param.PARAM_COLUMNS``) and the codecs that loop over it
(``cluster.state_codec``).

Two things are held here. The documents: what the tree before PR 48 wrote
of one seeded life (``tests/state_docs_golden.py``,
``tests/data/state_docs/``) is what this tree writes, key for key and array
for array, and restores here to the state it restored to there, on one
device and on a 2x2 mesh. And the criterion a later layout PR leans on: a
column is one entry. One test, parametrised over the table so that it grows
with it, writes a value into one leaf and finds it again after a snapshot
restore, a delta, a MOVE to a service with other slots and another epoch,
and a forced re-base.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import state_docs_golden as golden  # noqa: E402

from sentinel_tpu.cluster import state_codec  # noqa: E402
from sentinel_tpu.cluster.state_codec import COLUMNS  # noqa: E402
from sentinel_tpu.cluster.token_service import (  # noqa: E402
    ClusterParamFlowRule,
    DefaultTokenService,
)
from sentinel_tpu.engine import ClusterFlowRule, EngineConfig  # noqa: E402
from sentinel_tpu.engine.param import (  # noqa: E402
    ParamConfig,
    make_param_state,
)
from sentinel_tpu.engine.rules import ThresholdMode  # noqa: E402
from sentinel_tpu.engine.state import (  # noqa: E402
    DELTA,
    MOVE,
    BreakerState,
    EngineState,
    ShapingState,
    make_state,
)
from sentinel_tpu.parallel.sharding import (  # noqa: E402
    _state_specs,
    make_flow_mesh,
)
from sentinel_tpu.stats.window import NEVER, WindowState  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "state_docs")
_NEVER = int(NEVER)
_IDS = [c.name for c in COLUMNS]
_FAMILIES = list(dict.fromkeys(c.family for c in COLUMNS))


def _mesh(on_mesh: bool):
    return make_flow_mesh(jax.devices()[:4]) if on_mesh else None


def _same(got, want, path=""):
    """Two decoded documents agree key for key, array for array (dtype and
    shape included) and value for value."""
    if isinstance(want, dict):
        assert isinstance(got, dict) and set(got) == set(want), (
            path, set(got) ^ set(want))
        for k in want:
            _same(got[k], want[k], f"{path}/{k}")
    elif isinstance(want, np.ndarray) or isinstance(got, np.ndarray):
        got, want = np.asarray(got), np.asarray(want)
        assert got.dtype == want.dtype and got.shape == want.shape, path
        np.testing.assert_array_equal(got, want, err_msg=path)
    else:
        assert got == want, path


# -- the documents, held to the parent's --------------------------------------
def _parent(name: str) -> dict:
    """One of the parent's documents, decoded anew (an import may keep what
    it is handed)."""
    with open(os.path.join(DATA, golden.FILES[name]), "rb") as f:
        return golden.decode(name, f.read())


@pytest.fixture(scope="module")
def recorded():
    with np.load(os.path.join(DATA, "restored.npz")) as z:
        return {k: z[k] for k in z.files}


@pytest.fixture(scope="module")
def own_documents():
    """This tree's documents of the same life, through its blob codecs."""
    from sentinel_tpu.core import clock as clock_mod

    clock = clock_mod.ManualClock()
    prev = clock_mod.set_clock(clock)
    try:
        svc, docs = golden.run(clock)
        svc.close()
        return {name: golden.decode(name, raw)
                for name, raw in golden.encode(docs).items()}
    finally:
        clock_mod.set_clock(prev)


@pytest.mark.parametrize("name", golden.DOCS)
def test_the_export_is_the_parents(own_documents, name):
    _same(own_documents[name], _parent(name), name)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "2x2"])
@pytest.mark.parametrize("name", golden.DOCS)
def test_a_parent_blob_restores_to_the_recorded_state(
        manual_clock, recorded, name, on_mesh):
    dst = golden.restore(name, {n: _parent(n) for n in golden.DOCS},
                         manual_clock, _mesh(on_mesh))
    try:
        for leaf_name, got in golden.leaves(dst).items():
            want = recorded[f"{name}/{leaf_name}"]
            assert got.dtype == want.dtype, leaf_name
            np.testing.assert_array_equal(got, want, err_msg=leaf_name)
    finally:
        dst.close()


def _cold(svc) -> dict:
    return state_codec._leaves(make_state(svc.config),
                               make_param_state(svc.param_config))


@pytest.mark.parametrize(
    "absent", _FAMILIES + [c for c in COLUMNS if c.family == "param"],
    ids=_FAMILIES + [c.name for c in COLUMNS if c.family == "param"])
def test_what_a_snapshot_lacks_restores_cold(manual_clock, recorded, absent):
    """A snapshot from before shaping, outcomes, breakers or the slim twin
    has no key for them: those leaves come up as a fresh service holds
    them, and every other leaf as the whole snapshot restores it."""
    doc = _parent("snapshot")
    if isinstance(absent, str):
        del doc[absent]
        gone = {c for c in COLUMNS if c.family == absent}
    else:
        del doc[absent.family][absent.field]
        gone = {absent}
    dst = golden.standby()
    try:
        dst.import_state(doc)
        cold, got = _cold(dst), state_codec.read(dst)
        for c in COLUMNS:
            want = cold[c] if c in gone else recorded[f"snapshot/{c.name}"]
            np.testing.assert_array_equal(
                np.asarray(got[c]), np.asarray(want), err_msg=c.name)
    finally:
        dst.close()


def test_a_wrong_geometry_is_refused_entry_by_entry(manual_clock):
    for c in COLUMNS:
        doc = _parent("snapshot")
        doc[c.family][c.field] = doc[c.family][c.field][..., :-1]
        dst = golden.standby()
        try:
            before = golden.leaves(dst)
            with pytest.raises(ValueError, match=c.name):
                dst.import_state(doc)
            _same(golden.leaves(dst), before)
        finally:
            dst.close()


# -- the mesh placement -------------------------------------------------------
def test_the_mesh_specs_are_the_hand_written_ones():
    """What ``parallel.sharding._state_specs`` listed by hand before it
    read the table."""
    axis = "flows"
    by_flow = WindowState(starts=P(), counts=P(axis))
    want = EngineState(
        flow=by_flow,
        occupy=by_flow,
        ns=WindowState(starts=P(), counts=P()),
        shaping=ShapingState(
            lpt=P(axis), warm_tokens=P(axis), warm_filled=P(axis)),
        outcome=by_flow,
        breaker=BreakerState(
            state=P(axis), opened_ms=P(axis), probe_ms=P(axis)),
    )
    got = _state_specs(axis)
    assert jax.tree.structure(got, is_leaf=lambda x: isinstance(x, P)) == \
        jax.tree.structure(want, is_leaf=lambda x: isinstance(x, P))
    assert got == want


# -- a column is one entry ----------------------------------------------------
CFG = EngineConfig(max_flows=16, max_namespaces=4, batch_size=64)
PCFG = ParamConfig(max_param_rules=4, depth=2, width=16, impl="jax",
                   slim_depth=2, slim_width=8)
G = ThresholdMode.GLOBAL
RULES = [ClusterFlowRule(1, 50.0, G, "a"), ClusterFlowRule(2, 50.0, G, "b"),
         ClusterFlowRule(3, 50.0, G, "b")]
PRULES = [ClusterParamFlowRule(21, 50.0, None, "a"),
          ClusterParamFlowRule(22, 50.0, None, "b")]
# what the marked row is keyed by, per key kind: the moved namespace and a
# flow and a param rule of it
MOVED = "b"
MARKED = {"flow": 3, "namespace": MOVED, "param": 22}
T0 = 1_700_000_000_000


def _service(rules, prules, mesh=None):
    svc = DefaultTokenService(CFG, param_config=PCFG, mesh=mesh,
                              serve_buckets=(64,), fuse_depths=())
    svc.load_rules(list(rules))
    svc.load_param_rules(list(prules))
    return svc


def _now(svc) -> int:
    with svc._lock:
        return svc._engine_now()


def _row(svc, kind: str) -> int:
    return state_codec._row_map(svc, kind)[MARKED[kind]]


def _bucket(column, leaves, now: int):
    """``(ring slot, aligned start)`` of ``now`` in the ring a window's
    counts are bucketed by."""
    cfg = PCFG if column.family == "param" else CFG
    n = leaves[state_codec._ring(column)].shape[0]
    return (now // cfg.bucket_ms) % n, now - now % cfg.bucket_ms


def _value(shape, dtype):
    """Something a cold leaf never holds, of the leaf's dtype."""
    if dtype == np.bool_:
        return np.arange(int(np.prod(shape))).reshape(shape) % 2 == 0
    if np.issubdtype(dtype, np.floating):
        return np.full(shape, 12.5, dtype)
    if dtype == np.int8:
        return np.full(shape, 1, dtype)
    return (np.arange(int(np.prod(shape, dtype=np.int64)), dtype=np.int64)
            .reshape(shape) % 5 + 2).astype(dtype)


def _mark(svc, column, now: int):
    """Write a recognisable value into ``column``'s leaf and nothing else:
    the marked id's row of a keyed leaf (a window's in the bucket of
    ``now``, a clock's 37 ms ago), a whole unkeyed leaf (a clock's with one
    ``NEVER`` left in it). Returns what was written, as the row or the
    leaf."""
    leaves = state_codec.read(svc)
    host = np.array(leaves[column])
    if column.key is None:
        if column.kind == "clock":
            mark = (now - now % 100 - 100 * np.arange(host.shape[0])
                    ).astype(host.dtype)
            mark[-1] = _NEVER
        else:
            mark = _value(host.shape, host.dtype)
        host[...] = mark
    else:
        row = _row(svc, column.key)
        if column.kind == "clock":
            mark = np.asarray(now - 37, host.dtype)
        elif column.kind == "window":
            mark = np.zeros(host.shape[1:], host.dtype)
            slot, _ = _bucket(column, leaves, now)
            mark[slot] = _value(mark.shape[1:], host.dtype)
        else:
            mark = _value(host.shape[1:], host.dtype)
        host[row] = mark
    leaves[column] = jnp.asarray(host)
    state_codec.install(svc, leaves)
    return mark


def _stamp_rings(svc, now: int) -> None:
    """Every ring's current bucket starts at ``now``'s, as after one write
    to each window: what makes a service's windows live."""
    leaves = state_codec.read(svc)
    for c in COLUMNS:
        if c.kind == "window":
            ring = state_codec._ring(c)
            slot, aligned = _bucket(c, leaves, now)
            leaves[ring] = leaves[ring].at[slot].set(aligned)
    state_codec.install(svc, leaves)


def _dirty_the_marked(svc) -> None:
    """What a dispatch that touched the marked rows leaves in every dirty
    set."""
    for name, (kind, _ids_key) in state_codec._DIRTY.items():
        svc._dirty[name].add(_row(svc, kind))


def _leaf(svc, column) -> np.ndarray:
    return np.asarray(state_codec.read(svc)[column])


def _at(svc, column) -> np.ndarray:
    leaf = _leaf(svc, column)
    return leaf if column.key is None else leaf[_row(svc, column.key)]


@pytest.mark.parametrize("column", COLUMNS, ids=_IDS)
def test_a_column_is_one_entry(manual_clock, column):
    manual_clock.set_ms(T0)
    src = _service(RULES, PRULES)
    _now(src)  # its engine clock starts here
    standby = _service(RULES[::-1], PRULES[::-1])
    heir = _service(RULES[::-1], PRULES[::-1])
    # another epoch (its engine clock started 7,777 ms earlier), and rules
    # of its own in the first slots
    manual_clock.set_ms(T0 - 7_777)
    other = _service([ClusterFlowRule(90 + i, 9.0, G, "z") for i in range(4)],
                     [ClusterParamFlowRule(95, 9.0, None, "z")])
    _now(other)
    manual_clock.set_ms(T0 + 1234)
    try:
        src.replication_enable()
        now = _now(src)
        _stamp_rings(src, now)
        standby.import_state(src.export_state())
        cold = _at(standby, column)
        mark = _mark(src, column, now)
        assert not np.array_equal(mark, cold), "the mark is a cold value"

        # a snapshot restore: onto other slots, the same epoch
        heir.import_state(src.export_state())
        np.testing.assert_array_equal(_at(heir, column), mark)

        # a delta, on top of the snapshot from before the mark
        _dirty_the_marked(src)
        standby.apply_replication_delta(src.export_delta())
        if DELTA in column.docs:
            np.testing.assert_array_equal(_at(standby, column), mark)
        else:
            assert not np.array_equal(_at(standby, column), mark)

        # a MOVE: other slots, another epoch
        other.import_namespace_state(src.export_namespace_state(MOVED))
        there = _now(other)
        assert there != now
        if MOVE not in column.docs:
            # a MOVE blob is ring-free, so nothing unkeyed rides it; a row
            # of a keyed leaf that rides none arrives cold
            if column.key is not None:
                np.testing.assert_array_equal(
                    _at(other, column),
                    _cold(other)[column][_row(other, column.key)])
        elif column.kind == "window":
            # the live sum, in the destination's current bucket
            slot, aligned = _bucket(column, state_codec.read(other), there)
            assert int(_leaf(other, state_codec._ring(column))[slot]) \
                == aligned
            np.testing.assert_array_equal(_at(other, column)[slot],
                                          mark.sum(axis=0))
        elif column.kind == "clock":
            # at the same distance from the destination's now
            assert int(_at(other, column)) - there == int(mark) - now
        else:
            np.testing.assert_array_equal(_at(other, column), mark)

        # a forced re-base: clocks shift, NEVER stays, the rest stands
        before = _leaf(src, column)
        manual_clock.advance(src._REBASE_AFTER_MS)
        shift = now + src._REBASE_AFTER_MS - 60_000
        assert _now(src) == 60_000
        want = before
        if column.kind == "clock":
            want = np.where(before == _NEVER, _NEVER, before - shift)
            assert (before == _NEVER).any() and (before != _NEVER).any()
        np.testing.assert_array_equal(_leaf(src, column), want)
    finally:
        for svc in (src, standby, heir, other):
            svc.close()


# -- two repairs the rewrite passed through -----------------------------------
class _CountingDict(dict):
    walks = 0

    def items(self):
        self.walks += 1
        return super().items()


def test_a_delta_inverts_the_slot_map_once(manual_clock):
    """Three dirty sets keyed by flow (admission, outcomes, breakers) used
    to invert the 100k-entry ``slot_of`` once each, every tick."""
    manual_clock.set_ms(T0)
    svc = _service(RULES, PRULES)
    try:
        svc.replication_enable()
        _now(svc)
        _dirty_the_marked(svc)
        svc._index.slot_of = _CountingDict(svc._index.slot_of)
        delta = svc.export_delta()
        assert svc._index.slot_of.walks == 1
        assert (delta["flow_ids"] == delta["outcome_fids"]
                == delta["breaker_fids"] == [MARKED["flow"]])
    finally:
        svc.close()


def _many(n: int, order) -> list:
    return [ClusterFlowRule(100 + int(i), 1e6, G, "a" if i % 2 else "b")
            for i in order]


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "2x2"])
def test_rows_remap_by_what_keys_them(manual_clock, on_mesh):
    """A restore and a delta onto a service whose slots are a permutation
    of the source's: every flow's row lands on its flow, a row of the
    snapshot that no rule names is dropped, and a delta that names a flow
    the service has no rule for is refused whole. (Both remap by index
    arrays, where the parent looped a flow at a time.)"""
    cfg = EngineConfig(max_flows=256, max_namespaces=4, batch_size=64)
    rng = np.random.default_rng(48)
    n = 200
    manual_clock.set_ms(T0)
    src = DefaultTokenService(cfg, serve_buckets=(64,), fuse_depths=())
    dst = DefaultTokenService(cfg, mesh=_mesh(on_mesh), serve_buckets=(64,),
                              fuse_depths=())
    try:
        src.load_rules(_many(n, range(n)))
        # the destination knows them in another order, and 100 + n + 5 too
        dst.load_rules(_many(n, list(rng.permutation(n)) + [n + 5]))
        src.replication_enable()
        _now(src)

        def write(svc, scale):
            """Every flow's lpt is its own id times ``scale``."""
            leaves = state_codec.read(svc)
            lpt = next(c for c in COLUMNS if c.name == "shaping.lpt")
            host = np.array(leaves[lpt])
            for fid, slot in svc._index.slot_of.items():
                host[slot] = fid * scale
            leaves[lpt] = jnp.asarray(host)
            state_codec.install(svc, leaves)
            return lpt

        def read(svc, lpt):
            host = _leaf(svc, lpt)
            return {fid: int(host[slot])
                    for fid, slot in svc._index.slot_of.items()}

        lpt = write(src, 1)
        snap = src.export_state()
        # the snapshot names one flow more than its rules do: a stale slot
        snap["slot_of"][999] = 255
        dst.import_state(snap)
        assert read(dst, lpt) == {100 + i: 100 + i for i in range(n)}
        write(src, 3)
        src._dirty["flow"].update(src._index.slot_of.values())
        dst.apply_replication_delta(src.export_delta())
        assert read(dst, lpt) == {100 + i: 3 * (100 + i) for i in range(n)}
        delta = src.export_delta()
        delta["flow_ids"] = [100, 4242]
        with pytest.raises(ValueError, match="unknown flow 4242"):
            dst.apply_replication_delta(delta)
    finally:
        src.close()
        dst.close()
