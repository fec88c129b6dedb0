"""The serve steps' one host argument (PR 28): the request batch and the
clock as one packed ``int32`` array.

(a) the host packers against ``make_batch`` / ``make_batch_into`` field for
field after the traced unpack; (b) a serve step's lowered signature holds
the state, the rule table and exactly ONE further argument; (c) the packed
serve steps against the library entry (``decide`` with a ``RequestBatch``)
on ``tests/decide_golden.py``'s streams, verdicts and final state leaf by
leaf, single shard and over the CPU mesh; (d) a dispatch's clock is its
own: the same frame again, the rules-reloaded re-prep.
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.cluster import token_service
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.engine import (
    ClusterFlowRule,
    EngineConfig,
    TokenStatus,
    alloc_fused_batch,
    alloc_packed_block,
    decide,
    make_batch,
    make_batch_into,
    make_state,
    pack_batch,
    pack_requests,
    pack_requests_into,
    unpack_requests,
    unpack_verdicts,
)
from sentinel_tpu.engine.decide import (
    HEAD_NOW,
    PACKED_LINES,
    ROW_HEAD,
    decide_donating,
    decide_fused_donating,
)
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.parallel import (
    make_flow_mesh,
    make_sharded_decide,
    shard_rules,
    shard_state,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import decide_golden  # noqa: E402

G = ThresholdMode.GLOBAL
OK, BLOCKED = int(TokenStatus.OK), int(TokenStatus.BLOCKED)
# every serve bucket a cell's configuration compiles (PERF.md section 4)
BUCKETS = (64, 256, 1024, 4096, 16384)

_traced_unpack = jax.jit(unpack_requests)


def _rows(bucket, n, mixed, prios):
    rng = np.random.default_rng(bucket * 7 + n)
    slots = np.sort(rng.integers(0, 1000, n)).astype(np.int32)
    acq = rng.integers(1, 9, n).astype(np.int32) if mixed else None
    pr = (rng.random(n) < 0.3) if prios else None
    return slots, acq, pr


def _assert_is(batch, now, ref, want_now):
    for name, got, want in zip(ref._fields, batch, ref):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert np.asarray(now).dtype == np.int32 and int(now) == want_now


# -- (a) the layout -----------------------------------------------------------
@pytest.mark.parametrize("prios", (False, True), ids=("noprio", "prio"))
@pytest.mark.parametrize("mixed", (False, True), ids=("uniform", "mixed"))
@pytest.mark.parametrize("which", ("one", "short", "full"))
@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_packer_is_make_batch_after_the_traced_unpack(bucket, which,
                                                          mixed, prios):
    n = {"one": 1, "short": bucket - 1, "full": bucket}[which]
    cfg = EngineConfig(batch_size=bucket)
    slots, acq, pr = _rows(bucket, n, mixed, prios)
    now = 2**31 - 1 - n  # the clock is any int32
    packed = pack_requests(cfg, slots, acq, pr, now=now)
    assert packed.dtype == np.int32
    assert packed.shape == (PACKED_LINES, bucket)
    assert packed.nbytes <= 16 * bucket
    ref = make_batch(cfg, slots, acq, pr)
    _assert_is(*_traced_unpack(packed), ref, now)
    # the bridge from the library's arguments builds the same array
    np.testing.assert_array_equal(pack_batch(ref, now), packed)


@pytest.mark.parametrize("depth", (2, 4))
def test_the_staging_block_is_make_batch_into_after_the_traced_unpack(depth):
    cfg = EngineConfig(batch_size=64)
    block = alloc_packed_block(cfg, depth)
    ref = alloc_fused_batch(cfg, depth)
    assert block.dtype == np.int32
    assert block.shape == (PACKED_LINES, depth, 64)
    rng = np.random.default_rng(depth)
    for trial in range(12):  # rows are rewritten in place, as the pool does
        f = trial % depth
        n = (1, 63, 64, int(rng.integers(0, 65)))[trial % 4]
        slots, acq, pr = _rows(64, n, trial % 2 == 0, trial % 3 == 0)
        pack_requests_into(block, f, slots, acq, pr)
        make_batch_into(ref, f, slots, acq, pr)
        if trial < depth - 1:
            continue  # every row written once before the block is read
        block[ROW_HEAD, 0, HEAD_NOW] = 777 + trial
        _assert_is(*_traced_unpack(block), ref, 777 + trial)
        np.testing.assert_array_equal(pack_batch(ref, 777 + trial), block)
    with pytest.raises(ValueError):
        pack_requests_into(block, 0, np.zeros(65, np.int32))
    with pytest.raises(ValueError):
        pack_requests(cfg, np.zeros(65, np.int32))


# -- (b) one host argument ----------------------------------------------------
def _serve_step(builder):
    """``(jitted step, its arguments)`` of one serve-step builder."""
    cfg, table, _index = decide_golden._setup()
    frame = pack_requests(cfg, [0, 1, 2], now=1000)
    block = np.stack([frame] * 2, axis=1)
    if builder == "decide_donating":
        return (decide_donating(cfg, grouped=True, uniform=True),
                (make_state(cfg), table, frame))
    if builder == "decide_fused_donating":
        return (decide_fused_donating(cfg, 2, grouped=True, uniform=True),
                (make_state(cfg), table, block))
    mesh = make_flow_mesh(jax.devices()[:4])
    state, table = shard_state(make_state(cfg), mesh), shard_rules(table, mesh)
    fused = builder == "sharded_fused"
    step = make_sharded_decide(cfg, mesh, grouped=True, uniform=True,
                               donate=True, depth=2 if fused else None)
    return step.jitted(table), (state, table, block if fused else frame)


@pytest.mark.parametrize("builder", ("decide_donating",
                                     "decide_fused_donating", "sharded",
                                     "sharded_fused"))
def test_a_serve_step_takes_exactly_one_host_argument(builder):
    step, args = _serve_step(builder)
    low = step.lower(*args)
    (state_info, rules_info, *request), kwargs = low.args_info
    assert not kwargs
    assert len(jax.tree.leaves(state_info)) == len(jax.tree.leaves(args[0]))
    assert len(jax.tree.leaves(rules_info)) == len(jax.tree.leaves(args[1]))
    # what is left is ONE array: a sixth host argument does not creep back
    (leaf,) = jax.tree.leaves(request)
    assert leaf.dtype == jnp.int32 and leaf.shape == args[2].shape
    assert leaf.shape[0] == PACKED_LINES
    # and a step refuses the old call outright
    with pytest.raises((TypeError, ValueError)):
        step.lower(*args, jnp.int32(1000))


# -- (c) the served steps against the library entry ---------------------------
def _assert_states_equal(got, want):
    got_leaves, want_leaves = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def _stream(uniform):
    cfg, table, index = decide_golden._setup()
    frames = decide_golden._frames(cfg, index, 7 if uniform else 11, 6,
                                   uniform)
    batches = [make_batch(cfg, s, a, p) for s, a, p in frames]
    return cfg, table, batches, [10_000 + 130 * i for i in range(6)]


@pytest.mark.parametrize("uniform", (True, False), ids=("uniform", "mixed"))
@pytest.mark.parametrize("where", ("single", "mesh"))
def test_the_packed_step_is_the_library_entry_bit_for_bit(where, uniform):
    cfg, table, batches, times = _stream(uniform)
    if where == "single":
        lib = lambda st, b, now: decide(  # noqa: E731
            cfg, st, table, b, now, grouped=True, uniform=uniform)
        serve = decide_donating(cfg, grouped=True, uniform=uniform)
        fresh = lambda: make_state(cfg)  # noqa: E731
    else:
        mesh = make_flow_mesh(jax.devices()[:4])
        table = shard_rules(table, mesh)
        plain = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform)
        lib = lambda st, b, now: plain(  # noqa: E731
            st, table, b, jnp.int32(now))
        serve = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform,
                                    donate=True)
        fresh = lambda: shard_state(make_state(cfg), mesh)  # noqa: E731
    st_lib, st_serve = fresh(), fresh()
    seen = set()
    for b, now in zip(batches, times):
        st_lib, want = lib(st_lib, b, now)
        st_serve, got = serve(st_serve, table, pack_batch(b, now))
        got = unpack_verdicts(got)
        for name in want._fields:
            np.testing.assert_array_equal(
                getattr(got, name), np.asarray(getattr(want, name)),
                err_msg=f"{name} at {now}")
        seen |= set(np.unique(got.status).tolist())
    assert seen >= {0, 1, 3, 4}  # OK, BLOCKED, NO_RULE, TOO_MANY
    _assert_states_equal(st_serve, st_lib)


@pytest.mark.parametrize("uniform", (True, False), ids=("uniform", "mixed"))
@pytest.mark.parametrize("where", ("single", "mesh"))
def test_the_packed_fused_step_is_the_library_entry_frame_by_frame(where,
                                                                   uniform):
    cfg, table, batches, times = _stream(uniform)
    depth, now = 4, times[0]
    if where == "single":
        lib = lambda st, b: decide(  # noqa: E731
            cfg, st, table, b, now, grouped=True, uniform=uniform)
        serve = decide_fused_donating(cfg, depth, grouped=True,
                                      uniform=uniform)
        fresh = lambda: make_state(cfg)  # noqa: E731
    else:
        mesh = make_flow_mesh(jax.devices()[:4])
        table = shard_rules(table, mesh)
        plain = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform)
        lib = lambda st, b: plain(st, table, b, jnp.int32(now))  # noqa: E731
        serve = make_sharded_decide(cfg, mesh, grouped=True, uniform=uniform,
                                    donate=True, depth=depth)
        fresh = lambda: shard_state(make_state(cfg), mesh)  # noqa: E731
    st_lib, wants = fresh(), []
    for b in batches[:depth]:
        st_lib, want = lib(st_lib, b)
        wants.append(want)
    block = alloc_packed_block(cfg, depth)
    for f, b in enumerate(batches[:depth]):
        n = int(b.valid.sum())
        pack_requests_into(block, f, b.flow_slot[:n], b.acquire[:n],
                           b.prioritized[:n])
    block[ROW_HEAD, 0, HEAD_NOW] = now
    st_serve, got = serve(fresh(), table, block)
    got = unpack_verdicts(got)
    for f, want in enumerate(wants):
        for name in want._fields:
            np.testing.assert_array_equal(
                getattr(got, name)[f], np.asarray(getattr(want, name)),
                err_msg=f"{name} of frame {f}")
    _assert_states_equal(st_serve, st_lib)


# -- (d) a dispatch's clock is its own ----------------------------------------
CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)


def _service(count=5.0, **kw):
    svc = DefaultTokenService(CFG, **kw)
    svc.load_rules([ClusterFlowRule(flow_id=i, count=count, mode=G)
                    for i in range(1, 9)])
    return svc


@pytest.mark.parametrize("frames", (1, 2), ids=("frame", "fused"))
def test_byte_identical_frames_are_each_decided_at_their_own_clock(
        manual_clock, frames):
    """One dispatch's clock must not reach another that holds the same
    bytes: five tokens a second, the same frame dispatched once per window from
    two threads and read back later, as the lanes do. Every dispatch sees
    a fresh window and passes five; one decided at a neighbour's clock
    would find that window already spent."""
    svc = _service(count=5.0, fuse_depths=(2,))
    ids = np.full(frames * CFG.batch_size, 1, np.int64)
    svc.request_batch_arrays(ids)  # compile
    manual_clock.sleep(2000)
    rounds = 8
    calls = []  # (the step's host argument, the clock in it at the call)

    def spy(build):
        def built(*key):
            step = build(*key)

            def call(state, rules, packed):
                calls.append((packed, int(packed[ROW_HEAD].flat[HEAD_NOW])))
                return step(state, rules, packed)

            return call
        return built

    svc._step_fn, svc._fused_step_fn = spy(svc._step_fn), spy(
        svc._fused_step_fn)
    t0 = svc._engine_now()
    mats = [None] * rounds
    turn = [threading.Event() for _ in range(rounds + 1)]
    out, errors = [None] * rounds, []

    def lane(mine):
        try:
            for i in mine:
                turn[i].wait(30)
                mats[i] = svc.dispatch_batch_arrays(ids)
                manual_clock.sleep(1500)  # the next dispatch's window
                turn[i + 1].set()
            for i in mine:  # read back late, while the other lane reads too
                out[i] = mats[i]()
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)
            for ev in turn:
                ev.set()

    lanes = [threading.Thread(target=lane, args=(range(k, rounds, 2),))
             for k in (0, 1)]
    for t in lanes:
        t.start()
    turn[0].set()
    for t in lanes:
        t.join(60)
    assert not errors, errors
    assert not any(t.is_alive() for t in lanes)
    # whatever the backend does with a numpy argument after the call
    # returns (the CPU's aliases an aligned one), no dispatch wrote into an
    # array another was given: each went in with its own clock, and a
    # single frame's still holds it when all are done
    assert [now for _p, now in calls] == [t0 + 1500 * i
                                          for i in range(rounds)]
    if frames == 1:  # (staging blocks are recycled once they are read)
        for i, (packed, now) in enumerate(calls):
            assert packed[ROW_HEAD, HEAD_NOW] == now
            assert not any(np.shares_memory(packed, other)
                           for other, _n in calls[:i])
    for i, (status, remaining, _wait) in enumerate(out):
        assert int((status == OK).sum()) == 5, f"dispatch {i}"
        assert int((status == BLOCKED).sum()) == ids.size - 5
        assert remaining[:5].tolist() == [4, 3, 2, 1, 0]
    svc.close()


@pytest.mark.parametrize("frames", (1, 2), ids=("frame", "fused"))
def test_the_rules_reloaded_re_prep_answers_with_the_packed_form(
        manual_clock, monkeypatch, frames):
    """Rules reloaded between prep and the lock: the dispatch re-preps
    against the live table under the lock and is decided at its clock."""
    n = frames * CFG.batch_size
    ids = np.tile(np.arange(1, 11), n // 10 + 1)[:n].astype(np.int64)
    acq = (np.arange(n) % 3 + 1).astype(np.int32)
    want_svc = _service(count=7.0, fuse_depths=(2,))
    want_svc.load_rules([ClusterFlowRule(flow_id=i, count=9.0, mode=G)
                         for i in range(3, 12)])
    svc = _service(count=7.0, fuse_depths=(2,))
    prep, reloaded = token_service._native.flow_prep, []

    def prep_then_reload(*args, **kw):
        out = prep(*args, **kw)  # None where the library is not built
        if not reloaded:  # once, after the first frame's native pass
            reloaded.append(True)
            svc.load_rules([ClusterFlowRule(flow_id=i, count=9.0, mode=G)
                            for i in range(3, 12)])
        return out

    manual_clock.sleep(5000)
    monkeypatch.setattr(token_service._native, "flow_prep", prep_then_reload)
    got = svc.request_batch_arrays(ids, acq)
    want = want_svc.request_batch_arrays(ids, acq)
    for g, w, name in zip(got, want, ("status", "remaining", "wait")):
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert {OK, BLOCKED, int(TokenStatus.NO_RULE_EXISTS)} <= set(got[0])
    svc.close()
    want_svc.close()
