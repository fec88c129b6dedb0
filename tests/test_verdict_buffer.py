"""One verdict buffer per dispatch (PR 25): the serve steps hand back one
packed ``int32[3, ...]`` array, the device lane starts its copy to the host
at launch, and a materializer makes one blocking read.

Every serving path is driven on ``tests/decide_golden.py``'s stream beside a
plain reference: the un-jitted ``_decide_core`` on its own state, frame by
frame, with the grouping sort, the unsort and the MOVED overlay written out
as loops over ``VerdictBatch`` leaves. What the service returns must be the
same bits in the same dtypes.
"""

import gc
import os
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import (
    TokenStatus,
    VerdictBatch,
    make_batch,
    make_state,
    pack_verdicts,
    unpack_verdicts,
)
from sentinel_tpu.engine.decide import _decide_core
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.parallel import make_flow_mesh

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import decide_golden  # noqa: E402

CFG = decide_golden.config()
CAP = CFG.batch_size
SM = server_metrics()
MOVED_EPOCH = 7

# path -> (service arguments, rows per call, device dispatches per call)
PATHS = {
    "single": ({}, 40, 1),  # n below the 64-row bucket
    "oversized": ({"fuse_depths": ()}, 3 * CAP + 5, 4),
    "fused_d2": ({}, 2 * CAP, 1),
    "fused_d4": ({}, 4 * CAP, 1),
    "mesh": ({"mesh": 4}, 40, 1),
    "mesh_fused": ({"mesh": 4}, 2 * CAP, 1),
}
# call -> (frames that arrive sorted by slot, uniform acquire, MOVED overlay)
CALLS = {
    "unsorted_mixed": ("none", False, False),
    "sorted_uniform": ("all", True, False),
    "half_sorted_uniform": ("even", True, False),
    "moved_unsorted_mixed": ("none", False, True),
}


class Reference:
    """What the service must answer, computed the slow way."""

    def __init__(self):
        self.cfg, self.table, self.index = decide_golden._setup()
        self.state = make_state(self.cfg)
        self.moving = set()

    def slots(self, flow_ids):
        return decide_golden.slots_of(self.index, flow_ids)

    def answer(self, flow_ids, acq, pr, now):
        n = len(flow_ids)
        slots = self.slots(flow_ids)
        moved = np.isin(flow_ids, sorted(self.moving))
        status = np.empty(n, np.int8)
        remaining = np.empty(n, np.int32)
        wait = np.empty(n, np.int32)
        for lo in range(0, n, CAP):
            sl = slice(lo, min(lo + CAP, n))
            seen = np.where(moved[sl], -1, slots[sl]).astype(np.int32)
            order = np.argsort(seen, kind="stable")
            batch = make_batch(self.cfg, seen[order], acq[sl][order],
                               pr[sl][order])
            self.state, v = _decide_core(
                self.cfg, self.state, self.table, batch, jnp.int32(now),
                axis_name=None, grouped=True, uniform=False)
            assert isinstance(v, VerdictBatch)
            for k, row in enumerate(order):
                status[lo + row] = np.asarray(v.status)[k]
                remaining[lo + row] = np.asarray(v.remaining)[k]
                wait[lo + row] = np.asarray(v.wait_ms)[k]
        status[moved] = int(TokenStatus.MOVED)
        remaining[moved] = MOVED_EPOCH
        wait[moved] = 0
        return status, remaining, wait


def _call_rows(ref, rng, n, sorted_frames, uniform):
    fid, acq, pr = decide_golden.rows(rng, n, uniform)
    slots = ref.slots(fid)
    for f, lo in enumerate(range(0, n, CAP)):
        if sorted_frames == "all" or (sorted_frames == "even" and f % 2 == 0):
            sl = slice(lo, min(lo + CAP, n))
            order = np.argsort(slots[sl], kind="stable")
            for col in (fid, acq, pr):
                col[sl] = col[sl][order]
    return fid.astype(np.int64), acq, pr


def _reference_stream(n):
    """The four calls at ``n`` rows each: ``{call: (inputs, want)}``. The
    engine clock reads 1 at a service's first call and 130 ms more at each
    later one (``driven`` checks that it does)."""
    ref = Reference()
    rng = np.random.default_rng(25)
    out = {}
    for k, (call, (sorted_frames, uniform, moved)) in enumerate(CALLS.items()):
        if moved:
            ref.moving = {20, 21, 22}
        fid, acq, pr = _call_rows(ref, rng, n, sorted_frames, uniform)
        now = 1 + 130 * k
        out[call] = ((fid, acq, pr, ref.slots(fid), now),
                     ref.answer(fid, acq, pr, now))
    return out


@pytest.fixture(scope="module")
def driven():
    """Every path driven through every call beside the reference:
    ``{path: {call: (got, want, host reads, ready reads)}}``."""
    from sentinel_tpu.core import clock as clock_mod
    from sentinel_tpu.core.clock import ManualClock

    mc = ManualClock()
    prev = clock_mod.set_clock(mc)
    streams = {}  # the eager core takes a second a frame: once per size
    out = {}
    try:
        for path, (kw, n, _d) in PATHS.items():
            kw = dict(kw)
            if "mesh" in kw:
                kw["mesh"] = make_flow_mesh(jax.devices()[:kw["mesh"]])
            svc = _service(**kw)
            if n not in streams:
                streams[n] = _reference_stream(n)
            out[path] = {}
            for call, (inputs, want) in streams[n].items():
                fid, acq, pr, slots, now = inputs
                if CALLS[call][2]:
                    svc.begin_move("b", "10.0.0.9:1", MOVED_EPOCH)
                assert svc._engine_now() == now
                np.testing.assert_array_equal(svc.lookup_slots(fid), slots)
                reads0 = SM.verdict_host_reads_total
                ready0 = SM.verdict_copy_ready_total
                got = svc.request_batch_arrays(fid, acq, pr)
                reads = SM.verdict_host_reads_total - reads0
                ready = SM.verdict_copy_ready_total - ready0
                out[path][call] = (got, want, reads, ready)
                mc.advance(130)
            svc.close()
    finally:
        clock_mod.set_clock(prev)
    return out


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_service_answers_what_the_unjitted_core_answers(driven, path,
                                                            call):
    got, want, _reads, _ready = driven[path][call]
    for name, g, w, dtype in zip(("status", "remaining", "wait"), got, want,
                                 (np.int8, np.int32, np.int32)):
        assert g.dtype == dtype, (name, g.dtype)
        assert g.flags.writeable and g.shape == (PATHS[path][1],)
        np.testing.assert_array_equal(g, w, err_msg=f"{path} {call} {name}")
    if CALLS[call][2]:
        assert (got[0] == int(TokenStatus.MOVED)).any()
        assert (got[1][got[0] == int(TokenStatus.MOVED)] == MOVED_EPOCH).all()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_stream_reaches_every_verdict_on_every_path(driven, path):
    seen = set()
    for got, _want, _reads, _ready in driven[path].values():
        seen |= set(np.unique(got[0]).tolist())
    assert seen >= {int(TokenStatus.OK), int(TokenStatus.BLOCKED),
                    int(TokenStatus.NO_RULE_EXISTS), int(TokenStatus.MOVED)}


@pytest.mark.parametrize("call", sorted(CALLS))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_dispatch_makes_exactly_one_host_read(driven, path, call):
    _got, _want, reads, ready = driven[path][call]
    assert reads == PATHS[path][2]
    assert 0 <= ready <= reads


# -- the two helpers ----------------------------------------------------------
def _batch(shape, seed=0):
    rng = np.random.default_rng(seed)
    return VerdictBatch(
        status=rng.integers(-3, 13, shape).astype(np.int8),
        wait_ms=rng.integers(0, 2**31 - 1, shape).astype(np.int32),
        remaining=rng.integers(-2**31, 2**31 - 1, shape).astype(np.int32))


@pytest.mark.parametrize("shape", [(64,), (4, 64)])
def test_pack_then_unpack_is_the_identity_dtypes_included(shape):
    v = _batch(shape)
    packed = jax.jit(pack_verdicts)(jax.tree.map(jnp.asarray, v))
    assert packed.dtype == jnp.int32 and packed.shape == (3,) + shape
    back = unpack_verdicts(packed)
    for leaf, want in zip(back, v):
        assert leaf.dtype == want.dtype and leaf.flags.writeable
        np.testing.assert_array_equal(leaf, want)


def test_unpack_slices_the_padding_off_and_unsorts_through_the_order():
    v = _batch((64,), seed=1)
    packed = np.asarray(pack_verdicts(jax.tree.map(jnp.asarray, v)))
    n = 40
    order = np.random.default_rng(2).permutation(n)
    back = unpack_verdicts(packed, n, order)
    for leaf, want in zip(back, v):
        assert leaf.shape == (n,) and leaf.dtype == want.dtype
        for k, row in enumerate(order):  # entry k answers request order[k]
            assert leaf[row] == want[k]
    plain = unpack_verdicts(packed, n)
    for leaf, want in zip(plain, v):
        np.testing.assert_array_equal(leaf, want[:n])
        leaf[:] = 0  # a copy: writing it leaves the buffer alone
    np.testing.assert_array_equal(packed[1], v.wait_ms)


# -- a materializer nobody calls ----------------------------------------------
def _service(**kw):
    svc = DefaultTokenService(CFG, **kw)
    svc.load_rules(decide_golden.rules(), ns_max_qps=decide_golden.NS_MAX_QPS,
                   connected=decide_golden.CONNECTED)
    return svc


@pytest.mark.parametrize("rows", [40, 2 * CAP], ids=["single", "fused"])
def test_a_materializer_never_called_leaks_nothing_and_blocks_nothing(rows):
    """Shutdown drops dispatched groups unanswered: the copy started at
    launch must not pin the buffer anywhere, and nothing may wait for a
    read that never comes."""
    svc = _service()
    rng = np.random.default_rng(3)
    fid, acq, pr = decide_golden.rows(rng, rows, True)
    reads0 = SM.verdict_host_reads_total
    done = []

    def drive():
        for _ in range(3):
            mat = svc.dispatch_batch_arrays(fid.astype(np.int64), acq, pr)
            dropped = weakref.ref(mat)
            del mat
            gc.collect()
            done.append(dropped() is None)
        # the lane is not wedged behind the unread copies
        done.append(svc.request_batch_arrays(
            fid.astype(np.int64), acq, pr)[0].shape == (rows,))
        svc.close()

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "a dropped materializer blocked the service"
    assert done == [True, True, True, True]
    # only the materializer that ran made a read
    assert SM.verdict_host_reads_total - reads0 == 1


# -- the counters' three surfaces ---------------------------------------------
def test_the_read_counters_are_on_every_surface_and_reset():
    svc = _service()
    try:
        svc.request_batch_arrays(np.arange(10, dtype=np.int64))
    finally:
        svc.close()
    reads, ready = SM.verdict_host_reads_total, SM.verdict_copy_ready_total
    assert reads >= 1 and 0 <= ready <= reads
    stages = SM.stage_snapshot()
    assert stages["verdict_host_reads_total"] == reads
    assert stages["verdict_copy_ready_total"] == ready
    snap = SM.snapshot()
    assert snap["verdictHostReadsTotal"] == reads
    assert snap["verdictCopyReadyTotal"] == ready
    text = SM.render()
    assert f"sentinel_server_verdict_host_reads_total {reads}" in text
    assert f"sentinel_server_verdict_copy_ready_total {ready}" in text
    SM.reset()
    assert SM.verdict_host_reads_total == 0
    assert SM.verdict_copy_ready_total == 0
