"""Multi-chip sharding tests on the 8-device virtual CPU mesh.

The decisive property: the sharded step must produce byte-identical verdicts
to the single-device step for the same request stream (resource sharding is
an implementation detail, not a semantics change).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.engine import (
    ClusterFlowRule,
    EngineConfig,
    TokenStatus,
    build_rule_table,
    decide,
    make_batch,
    make_state,
)
from sentinel_tpu.engine.decide import pack_batch, unpack_verdicts
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.parallel import (
    make_flow_mesh,
    make_sharded_decide,
    shard_rules,
    shard_state,
)

CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
G = ThresholdMode.GLOBAL


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return make_flow_mesh()


def _build(num_rules=20, count=5.0):
    rules = [
        ClusterFlowRule(flow_id=i, count=count + (i % 3), mode=G)
        for i in range(num_rules)
    ]
    table, index = build_rule_table(CFG, rules)
    return rules, table, index


class TestShardedParity:
    @pytest.mark.parametrize("seed", range(3))
    def test_verdict_parity_with_single_device(self, mesh, seed):
        rules, table, index = _build()
        sharded_step = make_sharded_decide(CFG, mesh)

        state_1 = make_state(CFG)
        state_8 = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)

        rng = np.random.default_rng(seed)
        now = 10_000
        for step in range(6):
            now += int(rng.integers(20, 400))
            flows = rng.integers(-1, 20, size=48)
            slots = [index.lookup(int(f)) if f >= 0 else -1 for f in flows]
            prio = rng.random(48) < 0.2
            batch = make_batch(CFG, slots, prioritized=prio.tolist())
            state_1, v1 = decide(CFG, state_1, table, batch, jnp.int32(now))
            state_8, v8 = sharded_step(state_8, table_8, batch, jnp.int32(now))
            np.testing.assert_array_equal(
                np.asarray(v1.status), np.asarray(v8.status),
                err_msg=f"step {step} status diverged",
            )
            np.testing.assert_array_equal(
                np.asarray(v1.wait_ms), np.asarray(v8.wait_ms)
            )
            np.testing.assert_array_equal(
                np.asarray(v1.remaining), np.asarray(v8.remaining)
            )

    def test_parity_with_ns_guard_boundary_crossing(self, mesh):
        """The namespace guard's precise arm (budget boundary inside the
        batch → [N, NS] prefix behind the mesh-uniform cond) must produce
        byte-identical TOO_MANY placement on the mesh: a tight per-ns
        budget forces crossing batches, and repeated steps walk the window
        through fits-all, crossing, and none-pass regimes."""
        rules = [
            ClusterFlowRule(
                flow_id=i, count=1e9, mode=G, namespace=f"ns{i % 3}"
            )
            for i in range(12)
        ]
        table, index = build_rule_table(CFG, rules, ns_max_qps=7.0)
        sharded_step = make_sharded_decide(CFG, mesh)
        state_1 = make_state(CFG)
        state_8 = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)
        rng = np.random.default_rng(7)
        now = 10_000
        saw_crossing = False
        for step in range(5):
            now += int(rng.integers(20, 300))
            flows = rng.integers(0, 12, size=48)
            slots = [index.lookup(int(f)) for f in flows]
            batch = make_batch(CFG, slots)
            state_1, v1 = decide(CFG, state_1, table, batch, jnp.int32(now))
            state_8, v8 = sharded_step(state_8, table_8, batch, jnp.int32(now))
            np.testing.assert_array_equal(
                np.asarray(v1.status), np.asarray(v8.status),
                err_msg=f"step {step} status diverged under ns guard",
            )
            np.testing.assert_array_equal(
                np.asarray(v1.wait_ms), np.asarray(v8.wait_ms)
            )
            np.testing.assert_array_equal(
                np.asarray(v1.remaining), np.asarray(v8.remaining)
            )
            # crossing regime = one namespace with BOTH verdicts in one
            # batch (the precise prefix arm decides the split point);
            # whole-namespace rejection would only exercise the fast arm
            st = np.asarray(v1.status)[:48]
            ns_of = np.asarray([int(f) % 3 for f in flows])
            for ns in range(3):
                sel = st[ns_of == ns]
                saw_crossing |= bool(
                    (sel == TokenStatus.OK).any()
                    and (sel == TokenStatus.TOO_MANY_REQUEST).any()
                )
        assert saw_crossing, "scenario never hit the precise (crossing) arm"

    def test_state_actually_sharded(self, mesh):
        state = shard_state(make_state(CFG), mesh)
        shards = state.flow.counts.addressable_shards
        assert len(shards) == 8
        assert shards[0].data.shape[0] == CFG.max_flows // 8

    def test_occupy_starts_stay_replicated_after_borrow(self, mesh):
        # regression: a borrow on one shard must not let the "replicated"
        # occupy.starts diverge across devices (pmax-combined reset union)
        rules, table, index = _build(num_rules=4, count=3.0)
        sharded_step = make_sharded_decide(CFG, mesh)
        state = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)
        slot = index.lookup(0)
        state, _ = sharded_step(
            state, table_8, make_batch(CFG, [slot] * 3), jnp.int32(10_050)
        )
        state, v = sharded_step(
            state, table_8,
            make_batch(CFG, [slot], prioritized=[True]), jnp.int32(10_950),
        )
        assert np.asarray(v.status)[0] == TokenStatus.SHOULD_WAIT
        starts_shards = [
            np.asarray(s.data) for s in state.occupy.starts.addressable_shards
        ]
        for s in starts_shards[1:]:
            np.testing.assert_array_equal(starts_shards[0], s)

    def test_uneven_mesh_rejected(self, mesh):
        bad = EngineConfig(max_flows=60, max_namespaces=4, batch_size=16)
        with pytest.raises(ValueError, match="divisible"):
            make_sharded_decide(bad, mesh)

    def test_cross_shard_budget_enforced(self, mesh):
        # flows land on different shards; each still enforces its own budget
        rules, table, index = _build(num_rules=16, count=2.0)
        sharded_step = make_sharded_decide(CFG, mesh)
        state = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)
        # flows 0..15 → slots spread over shards (8 slots per shard)
        slots = [index.lookup(i % 16) for i in range(64)]
        batch = make_batch(CFG, slots)
        state, v = sharded_step(state, table_8, batch, jnp.int32(10_000))
        st = np.asarray(v.status)
        ok_per_flow = {}
        for i in range(64):
            f = i % 16
            ok_per_flow[f] = ok_per_flow.get(f, 0) + (st[i] == TokenStatus.OK)
        for f in range(16):
            assert ok_per_flow[f] == 2 + (f % 3)  # count=2+(f%3)


class TestShardedDonationAndFusion:
    """The donating + fused sharded step (PR 7): donation must hold (no
    full sharded-state copy per dispatch) and the fused scan must be
    bit-identical, frame by frame, to sequential sharded dispatches."""

    def test_sharded_step_donates_state(self, mesh):
        rules, table, index = _build()
        step = make_sharded_decide(CFG, mesh, donate=True)
        state = shard_state(make_state(CFG), mesh)
        table_8 = shard_rules(table, mesh)
        batch = make_batch(CFG, [index.lookup(0)] * 4)
        new_state, _ = step(state, table_8, pack_batch(batch, 10_000))
        # the donated input's buffers are gone — XLA updated them in place
        assert state.flow.counts.is_deleted()
        assert state.occupy.counts.is_deleted()
        # and the result is still properly sharded for the next dispatch
        assert len(new_state.flow.counts.addressable_shards) == 8

    @pytest.mark.parametrize("depth", [2, 4])
    def test_fused_sharded_bit_identical_per_frame(self, mesh, depth):
        """scan(depth) of the sharded step == depth sequential sharded
        dispatches, per-frame verdicts AND final state, bit for bit."""
        rules, table, index = _build(num_rules=16, count=6.0)
        table_8 = shard_rules(table, mesh)
        plain = make_sharded_decide(CFG, mesh, grouped=True, uniform=True)
        fused = make_sharded_decide(
            CFG, mesh, grouped=True, uniform=True, donate=True, depth=depth
        )
        rng = np.random.default_rng(11)
        frames = []
        for _ in range(depth):
            slots = np.sort(
                np.asarray(
                    [index.lookup(int(f))
                     for f in rng.integers(0, 16, CFG.batch_size)],
                    np.int32,
                )
            )
            frames.append(make_batch(CFG, slots))
        seq_state = shard_state(make_state(CFG), mesh)
        seq_verdicts = []
        for b in frames:
            seq_state, v = plain(seq_state, table_8, b, jnp.int32(10_000))
            seq_verdicts.append(jax.tree.map(np.asarray, v))
        stacked = type(frames[0])(
            *(np.stack([getattr(b, k) for b in frames])
              for k in frames[0]._fields)
        )
        fused_state = shard_state(make_state(CFG), mesh)
        out_state, fv = fused(fused_state, table_8, pack_batch(stacked, 10_000))
        assert fused_state.flow.counts.is_deleted()  # donated
        fv = unpack_verdicts(fv)
        for f in range(depth):
            for leaf in ("status", "wait_ms", "remaining"):
                np.testing.assert_array_equal(
                    getattr(seq_verdicts[f], leaf), getattr(fv, leaf)[f],
                    err_msg=f"fused frame {f} {leaf} diverged",
                )
        np.testing.assert_array_equal(
            np.asarray(out_state.flow.counts), np.asarray(seq_state.flow.counts)
        )

    def test_host_rows_gathers_sharded_and_replicated(self, mesh):
        from sentinel_tpu.parallel.sharding import host_rows

        state = shard_state(make_state(CFG), mesh)
        ramp = jnp.arange(64, dtype=state.flow.counts.dtype)[:, None, None]
        counts = state.flow.counts + ramp
        rows = np.asarray([0, 7, 8, 33, 63], np.int32)  # spans 4 shards
        got = host_rows(counts, rows)
        np.testing.assert_array_equal(got, np.asarray(counts)[rows])
        # replicated leaf takes the plain-copy path
        got_s = host_rows(state.flow.starts, np.asarray([0, 1], np.int32))
        np.testing.assert_array_equal(got_s, np.asarray(state.flow.starts)[:2])


class TestShardedSnapshotRoundTrip:
    """export_state on a mesh-backed primary → import_state on a standby
    with a DIFFERENT mesh shape (including no mesh at all): counters land
    bit-for-bit, re-sharded to the importer's own layout."""

    def _primed(self, mesh):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = DefaultTokenService(CFG, mesh=mesh)
        svc.load_rules(
            [ClusterFlowRule(flow_id=i, count=1e9, mode=G) for i in range(16)]
        )
        ids = np.tile(np.arange(16, dtype=np.int64), 8)
        svc.request_batch_arrays(ids)
        return svc

    @pytest.mark.parametrize("standby_devices", [1, 4])
    def test_mesh_snapshot_onto_different_mesh_shape(
        self, mesh, standby_devices
    ):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = self._primed(mesh)
        snap = svc.export_state()
        standby_mesh = (
            None if standby_devices == 1
            else make_flow_mesh(jax.devices()[:standby_devices])
        )
        standby = DefaultTokenService(CFG, mesh=standby_mesh)
        standby.import_state(snap)
        np.testing.assert_array_equal(
            np.asarray(standby._state.flow.counts),
            np.asarray(svc._state.flow.counts),
        )
        np.testing.assert_array_equal(
            np.asarray(standby._state.ns.counts),
            np.asarray(svc._state.ns.counts),
        )
        if standby_mesh is not None:
            assert (
                len(standby._state.flow.counts.addressable_shards)
                == standby_devices
            )
        # the promoted standby keeps enforcing: same verdicts as primary
        # for the next pull
        ids = np.tile(np.arange(16, dtype=np.int64), 4)
        s_p, r_p, w_p = svc.request_batch_arrays(ids)
        s_s, r_s, w_s = standby.request_batch_arrays(ids)
        np.testing.assert_array_equal(s_p, s_s)
        np.testing.assert_array_equal(r_p, r_s)
        svc.close()
        standby.close()

    def test_single_shard_snapshot_onto_mesh(self, mesh):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = self._primed(None)
        snap = svc.export_state()
        standby = DefaultTokenService(CFG, mesh=mesh)
        standby.import_state(snap)
        np.testing.assert_array_equal(
            np.asarray(standby._state.flow.counts),
            np.asarray(svc._state.flow.counts),
        )
        assert len(standby._state.flow.counts.addressable_shards) == 8
        svc.close()
        standby.close()

    @pytest.mark.parametrize("standby_devices", [1, 4])
    def test_param_sketch_state_survives_snapshot(
        self, mesh, standby_devices, manual_clock
    ):
        """The param sketch — SALSA merge state (in-band int16 encoding)
        AND the SF slim twin + its authority flags — must land bit-for-bit
        on a standby with a different mesh shape, and the standby's next
        param verdict must be bit-equal to the primary's."""
        from sentinel_tpu.cluster.token_service import (
            ClusterParamFlowRule,
            DefaultTokenService,
        )
        from sentinel_tpu.engine.param import ParamConfig

        pc = ParamConfig(
            max_param_rules=8, depth=2, width=32, sketch="salsa", impl="jax"
        )
        svc = DefaultTokenService(CFG, mesh=mesh, param_config=pc)
        # wide-open threshold: admissions must flow or nothing saturates
        svc.load_param_rules([ClusterParamFlowRule(flow_id=3, count=1e9)])
        rng = np.random.default_rng(3)
        vals = rng.integers(-2 ** 63, 2 ** 63 - 1, size=16, dtype=np.int64)
        stream = vals[rng.integers(0, 16, size=600)]
        for off in range(0, 600, 50):
            svc.request_params_token(
                3, 1024, [int(h) for h in stream[off:off + 50]]
            )
        assert int(np.asarray(svc._param_state.merges).sum()) > 0, (
            "stream too cold to exercise the merge path"
        )
        snap = svc.export_state()
        standby_mesh = (
            None if standby_devices == 1
            else make_flow_mesh(jax.devices()[:standby_devices])
        )
        standby = DefaultTokenService(
            CFG, mesh=standby_mesh, param_config=pc
        )
        standby.import_state(snap)
        for field in ("starts", "counts", "slim", "slim_auth", "merges"):
            np.testing.assert_array_equal(
                np.asarray(getattr(standby._param_state, field)),
                np.asarray(getattr(svc._param_state, field)),
                err_msg=field,
            )
        hot, cold = int(stream[0]), int(vals[-1])
        for value in (hot, cold):
            r_p = svc.request_params_token(3, 1, [value])
            r_s = standby.request_params_token(3, 1, [value])
            assert (r_p.status, r_p.remaining) == (r_s.status, r_s.remaining)
        svc.close()
        standby.close()


class TestMeshBackedService:
    """DefaultTokenService(mesh=...) — a pod's chips serving together
    (tier 1 of SURVEY §7.5; tier 2 is tests/test_namespace_partition.py)."""

    def test_serves_and_enforces_over_mesh(self, mesh):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = DefaultTokenService(CFG, mesh=mesh)
        svc.load_rules(
            [ClusterFlowRule(flow_id=i, count=3.0, mode=G) for i in range(16)]
        )
        svc.warmup()  # compile outside the metric window
        res = svc.request_batch([(1, 1, False)] * 5)
        statuses = [r.status for r in res]
        assert statuses.count(TokenStatus.OK) == 3, statuses
        assert statuses.count(TokenStatus.BLOCKED) == 2, statuses
        assert svc.request_token(99).status == TokenStatus.NO_RULE_EXISTS
        snap = svc.metrics_snapshot()
        assert snap[1]["pass_qps"] > 0
        # state is genuinely sharded across the mesh
        assert len(svc._state.flow.counts.addressable_shards) == 8
        svc.close()

    def test_fusion_ladder_active_under_mesh(self, mesh):
        """An oversized pull through a mesh-backed service takes the fused
        path (the PR-7 guard drop) and its verdicts are bit-identical to
        the same pull through a single-shard service."""
        from sentinel_tpu.cluster.token_service import DefaultTokenService
        from sentinel_tpu.metrics.server import server_metrics

        rules = [
            ClusterFlowRule(flow_id=i, count=1e9, mode=G) for i in range(16)
        ]
        svc8 = DefaultTokenService(CFG, mesh=mesh, fuse_depths=(4, 2))
        svc8.load_rules(rules, ns_max_qps=1e12)
        svc8.warmup()
        svc1 = DefaultTokenService(CFG, fuse_depths=(4, 2))
        svc1.load_rules(rules, ns_max_qps=1e12)
        svc1.warmup()
        before = server_metrics().fused_frames_total
        # 5 full frames: greedy ladder folds 4 into one scan + 1 plain
        ids = np.tile(np.arange(16, dtype=np.int64), (5 * CFG.batch_size) // 16)
        s8, r8, w8 = svc8.request_batch_arrays(ids)
        assert server_metrics().fused_frames_total - before >= 4
        s1, r1, w1 = svc1.request_batch_arrays(ids)
        np.testing.assert_array_equal(s8, s1)
        np.testing.assert_array_equal(r8, r1)
        np.testing.assert_array_equal(w8, w1)
        svc8.close()
        svc1.close()

    def test_rule_reload_keeps_serving(self, mesh):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = DefaultTokenService(CFG, mesh=mesh)
        svc.load_rules([ClusterFlowRule(flow_id=1, count=1e9, mode=G)])
        svc.warmup()
        assert svc.request_token(1).status == TokenStatus.OK
        svc.load_rules(
            [ClusterFlowRule(flow_id=f, count=1e9, mode=G) for f in (1, 2)]
        )
        assert svc.request_token(2).status == TokenStatus.OK
        assert svc.request_token(1).status == TokenStatus.OK
        svc.close()


class TestServedPathUnderTheMesh:
    """The served path (``DefaultTokenService(mesh=...)``: host prep, one
    replicated host argument, sharded step, psum stitch) against a
    single-device service from the same rules on the same clock, on the
    traffic the mesh cell sends: unsorted Zipf frames of about 1,000 rows,
    mixed acquires, two frames in flight as the lanes keep them. PR 42's
    native prep passed every CPU test and over-admitted on four chips; what a
    CPU can hold the path to is held here."""

    SCFG = EngineConfig(max_flows=2048, max_namespaces=4, batch_size=1024)
    # Zipf rank -> flow id (100 of them have no rule); the four hottest
    # flows are metered, flow -> count
    HOT = np.random.default_rng(43).permutation(1600)
    METERED = dict(zip(HOT[:4].tolist(), (400.0, 200.0, 120.0, 60.0)))

    def _service(self, mesh):
        from sentinel_tpu.cluster.token_service import DefaultTokenService

        svc = DefaultTokenService(self.SCFG, mesh=mesh)
        svc.load_rules(
            [ClusterFlowRule(flow_id=f, count=self.METERED.get(f, 1e9),
                             mode=G) for f in range(1500)],
            ns_max_qps=1e12,
        )
        return svc

    @staticmethod
    def _spy(svc, calls):
        """Every step's host argument, with the bytes it went in with."""
        build = svc._step_fn

        def built(*key):
            step = build(*key)

            def call(state, rules, packed):
                calls.append((packed, packed.tobytes()))
                return step(state, rules, packed)

            return call

        svc._step_fn = built

    @pytest.mark.parametrize("overwrite", (False, True),
                             ids=("plain", "arguments_overwritten"))
    def test_unsorted_zipf_frames_answer_as_one_device_does(
            self, mesh, manual_clock, overwrite):
        """Verdicts equal row for row and no metered flow over its count in
        a window. ``arguments_overwritten`` is Tentpole 1's drill as a test:
        the caller's arrays are overwritten the moment a dispatch returns
        (a door recycles its decode block), so nothing a dispatch keeps may
        alias them; and in both cases each step's host argument is its
        dispatch's own, still holding the bytes it was handed over with
        when every verdict is read (the CPU backend aliases an aligned
        argument, on the mesh too: a write after the clock would be read)."""
        rng, hot = np.random.default_rng(44), self.HOT
        services = {"mesh": self._service(mesh), "one": self._service(None)}
        calls = {name: [] for name in services}
        for name, svc in services.items():
            svc.warmup()
            self._spy(svc, calls[name])
        frames, pending, got = 24, [], {name: [] for name in services}
        sent = []

        def read(mats):
            for name, mat in mats.items():
                got[name].append(mat())

        for _k in range(frames):
            n = int(rng.integers(960, 1025))
            ids = hot[np.minimum(rng.zipf(1.1, n) - 1, 1599)].astype(np.int64)
            acq = rng.integers(1, 4, n).astype(np.int32)
            pr = np.zeros(n, bool)
            assert (np.diff(ids) < 0).any()  # unsorted
            sent.append((ids.copy(), acq.copy()))
            mats = {}
            for name, svc in services.items():
                args = (ids.copy(), acq.copy(), pr.copy())
                mats[name] = svc.dispatch_batch_arrays(*args)
                if overwrite:
                    args[0][:] = 3
                    args[1][:] = 7
                    args[2][:] = True
            pending.append(mats)
            if len(pending) == 2:  # two in flight, the older read first
                read(pending.pop(0))
            manual_clock.sleep(100)
        while pending:
            read(pending.pop(0))
        for k, (a, b) in enumerate(zip(got["mesh"], got["one"])):
            for x, y, what in zip(a, b, ("status", "remaining", "wait")):
                np.testing.assert_array_equal(
                    x, y, err_msg=f"{what} of frame {k}")
        # tokens a metered flow was let have, frame by frame; any 9 frames
        # in a row lie inside one 1 s window of ten 100 ms buckets
        ok = int(TokenStatus.OK)
        for flow, count in self.METERED.items():
            per_frame = np.array([
                int(acq[(ids == flow) & (st[0] == ok)].sum())
                for (ids, acq), st in zip(sent, got["mesh"])])
            assert per_frame.sum() > count  # demand was there to refuse
            spans = np.convolve(per_frame, np.ones(9, np.int64))
            assert spans.max() <= count, (flow, spans.max(), count)
        for name, seen in calls.items():
            assert len(seen) == frames
            for i, (packed, handed_over) in enumerate(seen):
                assert packed.tobytes() == handed_over, (name, i)
                assert not any(np.shares_memory(packed, other)
                               for other, _b in seen[:i]), (name, i)
        for svc in services.values():
            svc.close()
