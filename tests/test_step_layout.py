"""No whole-window copy inside a decide step.

A step reads a window's rows by one whole-row gather (``stats.window.
rows_at``), writes into the current bucket's slab and nothing larger
(``add_event_rows``), and leaves the occupy window alone while none of its
ring slots is live. Three things are held here: the serve step (donated
state, packed request) gives the same verdicts and the same cells as the
library entry ``decide()`` (one device, the four-device mesh, and fused);
the program the TPU compiler makes of a serve step copies no window into
another layout (the point of the three forms above; the CPU backend would
never show it); and the service's row paths (snapshot, replication delta,
MOVE, lease credit, rule reload) round-trip as they did.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine import (
    ClusterFlowRule,
    EngineConfig,
    build_rule_table,
    decide,
    make_batch,
    make_state,
)
from sentinel_tpu.engine.decide import (
    decide_donating,
    decide_fused_donating,
    pack_batch,
    unpack_verdicts,
)
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.engine.state import N_CLUSTER_EVENTS, flow_spec
from sentinel_tpu.parallel import (
    make_flow_mesh,
    make_sharded_decide,
    shard_rules,
    shard_state,
)
from sentinel_tpu.stats import window as W

CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=48)
STEPS = 60


@pytest.fixture(scope="module")
def mesh4():
    return make_flow_mesh(jax.devices()[:4])


def _rules():
    """Tight counts (BLOCKED and borrows), paced and warm-up rules."""
    rules = []
    for i in range(40):
        kw = {}
        if i % 7 == 3:
            kw = dict(control_behavior=2, max_queueing_time_ms=500)
        elif i % 11 == 5:
            kw = dict(control_behavior=1, warm_up_period_sec=5)
        rules.append(ClusterFlowRule(
            flow_id=i, count=[3, 8, 20, 1e6][i % 4],
            mode=ThresholdMode.GLOBAL, namespace=f"ns{i % 4}", **kw))
    return build_rule_table(CFG, rules, ns_max_qps=2000)


def _traffic(uniform: bool, seed: int = 3):
    """``STEPS`` frames with prioritized rows; the clock walks inside a
    bucket, across several buckets at once, and over idle gaps longer than
    the window's interval."""
    rng = np.random.default_rng(seed)
    gaps = rng.choice([2, 15, 40, 130, 350, 1500], STEPS,
                      p=[.3, .3, .2, .1, .07, .03])
    gaps[7], gaps[20], gaps[33] = 250, 1500, 3100  # every kind is met
    now, frames = 1_000, []
    for t, gap in enumerate(gaps):
        now += int(gap)
        n = int(rng.integers(20, CFG.batch_size + 1))
        slots = np.sort(rng.integers(-1, 40, n)).astype(np.int32)
        acq = (np.full(n, 1 + t % 3) if uniform
               else rng.integers(1, 5, n)).astype(np.int32)
        frames.append((make_batch(CFG, slots, acq, rng.random(n) < 0.3), now))
    return frames


def _assert_same_state(serve, lib, what=""):
    for a, b in zip(jax.tree.leaves(serve), jax.tree.leaves(lib)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), what)


@pytest.mark.parametrize("uniform", [False, True], ids=["mixed", "uniform"])
@pytest.mark.parametrize("devices", [1, 4], ids=["one-device", "mesh4"])
def test_serve_step_matches_library_entry(devices, uniform, mesh4):
    table, _ = _rules()
    lib = make_state(CFG)
    serve = make_state(CFG)
    if devices == 1:
        step, table_s = decide_donating(CFG, grouped=True, uniform=uniform), table
    else:
        step = make_sharded_decide(
            CFG, mesh4, grouped=True, uniform=uniform, donate=True)
        serve, table_s = shard_state(serve, mesh4), shard_rules(table, mesh4)
    seen = set()
    for t, (batch, now) in enumerate(_traffic(uniform)):
        lib, want = decide(CFG, lib, table, batch, jnp.int32(now),
                           grouped=True, uniform=uniform)
        serve, got = step(serve, table_s, pack_batch(batch, now))
        got = unpack_verdicts(got)
        for leaf in ("status", "wait_ms", "remaining"):
            np.testing.assert_array_equal(
                getattr(got, leaf), np.asarray(getattr(want, leaf)),
                err_msg=f"step {t} {leaf}")
        seen.update(np.asarray(want.status).tolist())
    assert {0, 1, 2} <= seen  # OK, BLOCKED and SHOULD_WAIT were all met
    assert int(np.asarray(lib.occupy.counts).sum()) > 0  # borrows happened
    _assert_same_state(serve, lib)
    if devices == 4:
        assert len(serve.flow.counts.addressable_shards) == 4
        assert {s.data.shape for s in serve.flow.counts.addressable_shards
                } == {(16, 10, N_CLUSTER_EVENTS)}


@pytest.mark.parametrize("uniform", [False, True], ids=["mixed", "uniform"])
@pytest.mark.parametrize("devices", [1, 4], ids=["one-device", "mesh4"])
def test_fused_depth_two_matches_two_single_steps(devices, uniform, mesh4):
    table, _ = _rules()
    frames = _traffic(uniform, seed=9)
    if devices == 1:
        single = decide_donating(CFG, grouped=True, uniform=uniform)
        fused = decide_fused_donating(CFG, 2, grouped=True, uniform=uniform)
        place, table_s = (lambda s: s), table
    else:
        single = make_sharded_decide(
            CFG, mesh4, grouped=True, uniform=uniform, donate=True)
        fused = make_sharded_decide(
            CFG, mesh4, grouped=True, uniform=uniform, donate=True, depth=2)
        place, table_s = (lambda s: shard_state(s, mesh4)), shard_rules(table, mesh4)
    one, two = place(make_state(CFG)), place(make_state(CFG))
    for k in range(0, 20, 2):
        (a, now), (b, _) = frames[k], frames[k + 1]  # one shared clock
        one, va = single(one, table_s, pack_batch(a, now))
        one, vb = single(one, table_s, pack_batch(b, now))
        stacked = type(a)(*(np.stack([x, y]) for x, y in zip(a, b)))
        two, vf = fused(two, table_s, pack_batch(stacked, now))
        np.testing.assert_array_equal(
            np.asarray(vf), np.stack([np.asarray(va), np.asarray(vb)], axis=1))
    for x, y in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n_channels", [1, N_CLUSTER_EVENTS])
def test_slab_write_and_row_read_against_plain_numpy(n_channels):
    """``add_event_rows`` (the current bucket's slab out, a flat scatter per
    channel, the slab back) and the whole-row reads, against a dictionary of
    arrays: duplicate ids accumulate, an id past the end is dropped, a
    stale slot starts from zero, other buckets are untouched."""
    spec = flow_spec(CFG)
    rng = np.random.default_rng(n_channels)
    ws = W.make_window(spec, 16, n_channels)
    want = np.zeros((16, spec.n_buckets, n_channels), np.int64)
    starts = np.full(spec.n_buckets, int(W.NEVER), np.int64)
    chans = tuple(range(n_channels))[-4:]
    for now in (1_000, 1_050, 1_130, 1_990, 2_400, 9_000, 9_010):
        ids = rng.integers(0, 18, 24)  # 16 and 17 are past the end
        upd = rng.integers(0, 9, (24, len(chans)))
        ws = W.add_event_rows(spec, ws, now, jnp.asarray(ids, jnp.int32),
                              jnp.asarray(upd, jnp.int32), channels=chans)
        idx, start = (now // spec.bucket_ms) % spec.n_buckets, now - now % spec.bucket_ms
        if starts[idx] != start:
            want[:, idx, :], starts[idx] = 0, start
        for i, row in zip(ids, upd):
            if i < 16:
                want[i, idx, list(chans)] += row
        np.testing.assert_array_equal(np.asarray(ws.counts), want)
        np.testing.assert_array_equal(np.asarray(ws.starts), starts)
        live = (now - starts >= 0) & (now - starts < spec.interval_ms)
        at = jnp.asarray(rng.integers(0, 16, 9), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(W.rows_at(ws, at)), want[np.asarray(at)])
        np.testing.assert_array_equal(
            np.asarray(W.window_sum_at(spec, ws, now, chans[0], at)),
            (want[np.asarray(at), :, chans[0]] * live).sum(axis=1))
    assert want.sum() > 0


def test_matured_borrows_are_read_once_a_slot_is_live():
    """The step reads the occupy window only while one of its slots lies
    inside the interval: a borrow counts against the threshold from the
    moment its window arrives, and not before."""
    from sentinel_tpu.engine.decide import TokenStatus

    table, index = build_rule_table(
        CFG, [ClusterFlowRule(flow_id=1, count=10, mode=ThresholdMode.GLOBAL)])
    slot = index.lookup(1)
    state = make_state(CFG)

    def ask(state, now, n, acquire, prio):
        batch = make_batch(CFG, [slot] * n, [acquire] * n, [prio] * n)
        return decide(CFG, state, table, batch, jnp.int32(now))

    state, v = ask(state, 10_010, 1, 10, False)  # the window is full
    assert int(v.status[0]) == int(TokenStatus.OK)
    # 940 ms on, those 10 expire with the next bucket: room to borrow there
    assert not bool(W.valid_mask(flow_spec(CFG), state.occupy, 10_950).any())
    state, v = ask(state, 10_950, 1, 4, True)
    assert int(v.status[0]) == int(TokenStatus.SHOULD_WAIT)
    assert int(v.wait_ms[0]) == 50
    assert int(np.asarray(state.occupy.counts).sum()) == 4
    # the window has arrived: the 10 have expired, the 4 borrowed are live
    assert bool(W.valid_mask(flow_spec(CFG), state.occupy, 11_010).any())
    state, v = ask(state, 11_010, 8, 1, False)
    assert [int(x) for x in v.status[:8]] == [int(TokenStatus.OK)] * 6 + [
        int(TokenStatus.BLOCKED)] * 2


def test_outcome_step_on_the_mesh_matches_one_device(mesh4):
    """The outcome step's static-channel writes ride ``add_event_rows``'
    slab; under a mesh it is one GSPMD-partitioned program over the sharded
    window, not a ``shard_map``."""
    from sentinel_tpu.engine.outcome import outcome_step_donating

    step = outcome_step_donating(CFG)
    rng = np.random.default_rng(0)
    one, four = make_state(CFG), shard_state(make_state(CFG), mesh4)
    for t in range(6):
        args = (
            jnp.asarray(rng.integers(0, 66, 32), jnp.int32),  # 64, 65: dropped
            jnp.asarray(rng.integers(0, 300, 32), jnp.int32),
            jnp.asarray(rng.integers(0, 2, 32), jnp.int32),
            jnp.asarray(rng.random(32) < 0.9),
            jnp.int32(1_000 + 70 * t),
        )
        one, four = step(one, *args), step(four, *args)
        np.testing.assert_array_equal(
            np.asarray(four.outcome.counts), np.asarray(one.outcome.counts))
    assert int(np.asarray(one.outcome.counts).sum()) > 0


# -- what the TPU compiler makes of a serve step ------------------------------

@pytest.fixture(scope="module")
def compiled_for_v5e():
    """Per program the window-sized instructions of its entry computation,
    compiled in this process for a described v5e (no chip is needed; about
    6 s a program). Skips where libtpu cannot describe one."""
    from benchmarks.decide_hlo_check import compile_report, describe_v5e

    try:
        topo = describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with pytest.MonkeyPatch.context() as patch:
        # the step picks its TPU forms from the default backend; every jit
        # in it is built anew per call, so nothing traced here outlives it
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return compile_report(topo)


@pytest.mark.parametrize(
    "program", ["jit_decide_b1024_mixed", "jit_decide_b4096_uniform"])
def test_compiled_step_converts_no_window_layout(compiled_for_v5e, program):
    """At ``mesh-100k``'s geometry the entry computation holds no ``while``
    (the compiler's copy of a tiled window into a flat operand, cell by
    cell) and no ``copy`` of a window into another layout; an edit of
    ``commit`` or of a read that brings them back shows on no CPU test but
    this one."""
    report = compiled_for_v5e[program]
    assert report["window_cells"] == 100_000 * 10
    assert report["entry_while"] == 0, report
    assert report["window_layout_copies"] == [], report


@pytest.mark.parametrize("scope", ["threshold", "occupy", "commit"])
@pytest.mark.parametrize(
    "program", ["jit_decide_b1024_mixed", "jit_decide_b4096_uniform"])
def test_compiled_cond_branches_move_no_window(compiled_for_v5e, program, scope):
    """The arms that touch the occupy window sit in ``cond`` branches, which
    the entry computation does not show: no scatter there takes a window as
    its operand (``add_future`` writes a target bucket's column), nothing
    rewrites a whole window (its reset zeroes that column), and no window
    is copied into another layout, but for the one fetch of the occupy
    window's rows in ``threshold`` that ``matured`` and ``waiting`` share:
    the stored ``[F, 2B, 1]`` window lies ``T(1,128)`` and every gather the
    v5e compiler has for it retiles what it reads (PERF.md section 6,
    PR 32)."""
    from benchmarks.decide_hlo_check import violations

    report = compiled_for_v5e[program]
    assert [v for v in violations(report)
            if v.startswith(scope + ":")] == [], report
    if scope == "threshold":  # the copy that is allowed is the occupy window's
        assert all("s32[100000,20,1]" in c
                   for c in report["branch_layout_copies"]), report


@pytest.mark.parametrize("bucket", [64, 1024])
def test_compiled_param_step_moves_no_sketch(compiled_for_v5e, bucket):
    """``hot-param-1k``'s serve step, compiled for the described v5e: Mosaic
    takes the commit kernel at the deployment's size (sublane rolls, row
    DMAs; the interpreter of the CPU tests would take anything), the flat
    512 MiB sketch reaches it as a bitcast, and no computation holds a
    copy, reshape, transpose, scatter or fusion that yields the whole
    sketch: it is touched by the stale branch of ``param_roll`` and by the
    kernel's own DMAs, nothing else (PR 27: a layout copy of it costs
    2.6 ms a call; PR 39: without the barrier behind ``param_roll``'s cond
    the 64-row program carried the kernel's view into both branches and
    copied the sketch on every call)."""
    import json
    import os
    import re

    from jax.sharding import SingleDeviceSharding

    from benchmarks.decide_hlo_check import _instructions, describe_v5e
    from sentinel_tpu.engine import param as P

    del compiled_for_v5e  # this process holds libtpu, or the fixture skipped
    on = SingleDeviceSharding(describe_v5e().devices[0])
    with open(os.path.join(os.path.dirname(__file__), "..", "cellbench",
                           "configs", "hot-param-1k.json")) as f:
        cfg = P.ParamConfig(**json.load(f)["param"])
    cells = int(np.prod(P.fat_shape(cfg)))

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        state = jax.eval_shape(lambda: P.make_param_state(cfg, flat=True))
        packed = jax.ShapeDtypeStruct(
            (P.packed_lines(cfg), bucket), jnp.int32)
        text = P.make_param_step(cfg, bucket, "jax").lower(
            *jax.tree.map(described, (state, packed))).compile().as_text()
    whole = re.compile(rf"s32\[({cells}|{cells // 128},128)\]")
    made = {}
    for name, type_text, op, rest in _instructions(text):
        if whole.search(type_text) and "/param_roll/" not in rest:
            made.setdefault(op, []).append(name)
    assert set(made) <= {"parameter", "bitcast", "tuple", "custom-call",
                         "get-tuple-element", "opt-barrier"}, made
    assert len(made["custom-call"]) == 1


_HLO = """HloModule jit_step, is_scheduled=true

%fused_scatter (p0: s32[4000], p1: s32[8], p2: s32[8]) -> s32[4000] {
  %p0 = s32[4000]{0:T(1024)} parameter(0)
  %p1 = s32[8]{0:T(128)} parameter(1)
  %p2 = s32[8]{0:T(128)} parameter(2)
  ROOT %scatter.1 = s32[4000]{0:T(1024)} scatter(%p0, %p1, %p2), update_window_dims={}, to_apply=%add
}

%fused_reset (q0: s32[200,20,1], q1: s32[20]) -> s32[200,20,1] {
  %q0 = s32[200,20,1]{0,2,1:T(1,128)} parameter(0)
  %q1 = s32[20]{0:T(128)} parameter(1)
  %b = s32[200,20,1]{0,2,1:T(1,128)} broadcast(%q1), dimensions={1}
  ROOT %mul = s32[200,20,1]{0,2,1:T(1,128)} multiply(%q0, %b)
}

%fused_dus (r0: s32[200,20,1], r1: s32[200]) -> s32[200,20,1] {
  %r0 = s32[200,20,1]{0,2,1:T(1,128)} parameter(0)
  %r1 = s32[200]{0:T(1024)} parameter(1)
  %rs = s32[200,1,1]{0,2,1:T(1,128)} reshape(%r1)
  ROOT %dus = s32[200,20,1]{0,2,1:T(1,128)} dynamic-update-slice(%r0, %rs, %c, %c, %c)
}

%inner_taken (t: (s32[200,20,1])) -> (s32[200,20,1]) {
  %t = (s32[200,20,1]{0,2,1:T(1,128)}) parameter(0)
  %w = s32[200,20,1]{0,2,1:T(1,128)} get-tuple-element(%t), index=0
  %reset = s32[200,20,1]{0,2,1:T(1,128)} fusion(%w, %k), kind=kLoop, calls=%fused_reset
  ROOT %out = (s32[200,20,1]{0,2,1:T(1,128)}) tuple(%reset)
}

%inner_skipped (u: (s32[200,20,1])) -> (s32[200,20,1]) {
  ROOT %u = (s32[200,20,1]{0,2,1:T(1,128)}) parameter(0)
}

%commit_taken (a: (s32[200,20,1], s32[200])) -> (s32[200,20,1]) {
  %a = (s32[200,20,1]{0,2,1:T(1,128)}, s32[200]{0:T(1024)}) parameter(0)
  %win = s32[200,20,1]{0,2,1:T(1,128)} get-tuple-element(%a), index=0
  %col = s32[200]{0:T(1024)} get-tuple-element(%a), index=1
  %flat = s32[20,200]{1,0:T(8,128)S(1)} fusion(%win), kind=kLoop, calls=%fused_relayout
  %line = s32[4000]{0:T(1024)} reshape(%flat)
  %scattered = s32[4000]{0:T(1024)} fusion(%line, %i, %v), kind=kCustom, calls=%fused_scatter
  %inplace = s32[200,20,1]{0,2,1:T(1,128)} fusion(%win, %col), kind=kLoop, calls=%fused_dus
  %nested = (s32[200,20,1]{0,2,1:T(1,128)}) conditional(%p, %x, %y), branch_computations={%inner_skipped, %inner_taken}
  ROOT %r = (s32[200,20,1]{0,2,1:T(1,128)}) tuple(%inplace)
}

%commit_skipped (b: (s32[200,20,1], s32[200])) -> (s32[200,20,1]) {
  %b = (s32[200,20,1]{0,2,1:T(1,128)}, s32[200]{0:T(1024)}) parameter(0)
  %same = s32[200,20,1]{0,2,1:T(1,128)} get-tuple-element(%b), index=0
  ROOT %r2 = (s32[200,20,1]{0,2,1:T(1,128)}) tuple(%same)
}

%read_taken (c: (s32[200,20,1])) -> (s32[8]) {
  %c = (s32[200,20,1]{0,2,1:T(1,128)S(1)}) parameter(0)
  %got = s32[200,20,1]{0,2,1:T(1,128)S(1)} get-tuple-element(%c), index=0
  %copy.9 = s32[200,20,1]{1,0,2:T(8,128)S(1)} copy(%got)
  ROOT %r3 = (s32[8]{0:T(128)}) tuple(%z)
}

%read_skipped (d: (s32[200,20,1])) -> (s32[8]) {
  %d = (s32[200,20,1]{0,2,1:T(1,128)S(1)}) parameter(0)
  ROOT %r4 = (s32[8]{0:T(128)}) tuple(%z)
}

ENTRY %main (occ: s32[200,20,1]) -> (s32[200,20,1]) {
  %occ = s32[200,20,1]{0,2,1:T(1,128)} parameter(0)
  %copy-start.1 = (s32[200,20,1]{0,2,1:T(1,128)S(1)}, s32[200,20,1]{0,2,1:T(1,128)}, u32[]{:S(2)}) copy-start(%occ)
  %copy-done.1 = s32[200,20,1]{0,2,1:T(1,128)S(1)} copy-done(%copy-start.1)
  %cond.1 = (s32[8]{0:T(128)}) conditional(%p, %t0, %t1), branch_computations={%read_skipped, %read_taken}, metadata={op_name="jit(step)/threshold/cond" stack_frame_id=1}
  %cond.2 = (s32[200,20,1]{0,2,1:T(1,128)}) conditional(%q, %t2, %t3), true_computation=%commit_taken, false_computation=%commit_skipped, metadata={op_name="jit(step)/commit/cond" stack_frame_id=2}
  ROOT %res = (s32[200,20,1]{0,2,1:T(1,128)}) tuple(%w2)
}
"""


def test_entry_report_walks_the_branches_of_the_conds():
    """``entry_report`` on a hand-written program: the entry's prefetch is a
    memory move; the ``threshold`` cond's branch copies the window into
    another tiling; the ``commit`` cond's branch relayouts it in a fusion,
    scatters into all of it, and a cond nested in it multiplies all of it;
    the in-place ``dynamic-update-slice`` of one column is none of these."""
    from benchmarks.decide_hlo_check import entry_report, violations

    report = entry_report(_HLO, window_cells=4000)
    assert report["entry_while"] == 0
    assert report["window_layout_copies"] == []
    assert [m.split(" = ")[0] for m in report["window_memory_moves"]] == [
        "copy-start.1"]
    names = {key: sorted(x.split(" = ")[0] for x in report[key])
             for key in ("branch_layout_copies", "branch_window_scatters",
                         "branch_window_passes")}
    assert names == {
        "branch_layout_copies": ["commit: flat", "threshold: copy.9"],
        "branch_window_scatters": ["commit: scatter.1"],
        "branch_window_passes": ["commit: reset", "commit: scattered"],
    }
    assert sorted(v.split(" = ")[0] for v in violations(report)) == [
        "commit: flat", "commit: reset", "commit: scatter.1",
        "commit: scattered"]  # threshold's one copy is the allowed one
    # a smaller window than the program's: nothing is window-sized
    quiet = entry_report(_HLO, window_cells=4001)
    assert not any(quiet[key] for key in quiet if key != "entry_while")
    assert violations(quiet) == []


# -- the service's row paths on the flat serve state --------------------------

def _service(clock, **kw):
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    svc = DefaultTokenService(
        EngineConfig(max_flows=32, max_namespaces=4, batch_size=16), **kw)
    svc.load_rules([
        ClusterFlowRule(flow_id=i, count=[4, 30][i % 2], namespace=f"ns{i % 2}",
                        mode=ThresholdMode.GLOBAL)
        for i in range(12)
    ])
    return svc


def _drive(svc, clock, seed, steps=12):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        clock.advance(int(rng.choice([3, 40, 130])))
        ids = rng.integers(0, 13, 10).astype(np.int64)
        v = svc.request_batch_arrays(ids, rng.integers(1, 3, 10).astype(np.int32))
        out.append(np.stack([np.asarray(x).astype(np.int32) for x in v]))
    return np.stack(out)


@pytest.fixture
def clock():
    from sentinel_tpu.core import clock as clock_mod
    from sentinel_tpu.core.clock import ManualClock

    c = ManualClock(start_ms=1_700_000_000_000)
    prev = clock_mod.set_clock(c)
    yield c
    clock_mod.set_clock(prev)


def test_snapshot_restore_round_trip(clock):
    a = _service(clock)
    _drive(a, clock, 2)
    doc = a.export_state()
    assert doc["flow"]["counts"].shape == (32, 10, N_CLUSTER_EVENTS)
    # the occupy ring is twice the flow window's (engine.state.occupy_ring)
    assert doc["occupy"]["counts"].shape == (32, 20, 1)
    np.testing.assert_array_equal(
        doc["flow"]["counts"], np.asarray(a._state.flow.counts))
    b = _service(clock)
    b.import_state(doc)
    again = b.export_state()
    for win in ("flow", "occupy", "ns"):
        for leaf in ("starts", "counts"):
            np.testing.assert_array_equal(again[win][leaf], doc[win][leaf])
    t = clock.now_ms()  # the same clock for both: the same following verdicts
    after_a = _drive(a, clock, 3)
    clock.set_ms(t)
    np.testing.assert_array_equal(_drive(b, clock, 3), after_a)


def test_replication_delta_round_trip(clock):
    a, b = _service(clock), _service(clock)
    b.import_state(a.export_state())
    a.replication_enable()
    _drive(a, clock, 4)
    delta = a.export_delta()
    assert delta["flow_counts"].shape[1:] == (10, N_CLUSTER_EVENTS)
    assert delta["occupy_counts"].shape[1:] == (20, 1)
    b.apply_replication_delta(delta)
    np.testing.assert_array_equal(
        np.asarray(b._state.flow.counts), np.asarray(a._state.flow.counts))
    np.testing.assert_array_equal(
        np.asarray(b._state.occupy.counts), np.asarray(a._state.occupy.counts))


def test_move_hand_off_round_trip(clock):
    a, b = _service(clock), _service(clock)
    _drive(a, clock, 5)
    spec = flow_spec(a.config)
    doc = a.export_namespace_state("ns1")
    now = a._engine_now()
    want = np.asarray(W.window_sum_all(spec, a._state.flow, jnp.int32(now)))
    b.import_namespace_state(doc)
    got = np.asarray(W.window_sum_all(spec, b._state.flow, jnp.int32(now)))
    moved = [a._index.slot_of[f] for f in doc["flow_ids"]]
    landed = [b._index.slot_of[f] for f in doc["flow_ids"]]
    assert want[moved].sum() > 0
    np.testing.assert_array_equal(got[landed], want[moved])


def test_lease_credit_lands_in_the_grant_cell(clock):
    from sentinel_tpu.engine.state import ClusterEvent

    svc = _service(clock, lease_fraction=0.5, lease_ttl_ms=60_000)
    clock.advance(7)
    grant = svc.lease_grant(1, 10)
    assert grant.ok and grant.tokens > 0
    slot = svc._index.slot_of[1]
    leased = lambda: int(np.asarray(  # noqa: E731
        svc._state.flow.counts)[slot, :, int(ClusterEvent.LEASED)].sum())
    assert leased() == grant.tokens
    assert int(np.asarray(svc._state.flow.counts).sum()) == grant.tokens
    svc.lease_return(grant.lease_id, used=1)
    assert leased() == 1


def test_rule_reload_zeroes_a_freed_slot(clock):
    svc = _service(clock)
    _drive(svc, clock, 6)
    slot = svc._index.slot_of[1]
    before = np.asarray(svc._state.flow.counts)
    assert before[slot].sum() > 0
    svc.load_rules([  # flow 1 is gone
        ClusterFlowRule(flow_id=i, count=[4, 30][i % 2], namespace=f"ns{i % 2}",
                        mode=ThresholdMode.GLOBAL)
        for i in range(12) if i != 1
    ])
    after = np.asarray(svc._state.flow.counts)
    assert after[slot].sum() == 0
    keep = np.ones(32, bool)
    keep[slot] = False
    np.testing.assert_array_equal(after[keep], before[keep])
