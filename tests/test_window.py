"""Window kernel tests — the analog of the reference's LeapArray test suite
(``sentinel-core/src/test/.../slots/statistic/base/LeapArrayTest.java``,
``BucketLeapArrayTest``), with explicit time instead of a mocked clock."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.stats import window as W
from sentinel_tpu.stats.events import Event, N_EVENTS

SPEC = W.WindowSpec(bucket_ms=500, n_buckets=2)  # second-level default: 1000ms/2
R = 8


def add_pass(ws, now, res, n=1):
    return W.add_events(
        SPEC,
        ws,
        jnp.int32(now),
        jnp.array([res], jnp.int32),
        jnp.array([Event.PASS], jnp.int32),
        jnp.array([n], jnp.int32),
    )


def pass_sum(ws, now):
    return np.asarray(W.window_sum(SPEC, ws, jnp.int32(now), Event.PASS))


class TestBucketIndex:
    def test_ring_math(self):
        # mirrors LeapArrayTest.testCalculateTimeIdx / windowStart math
        idx, start = W.bucket_index(SPEC, jnp.int32(1_234))
        assert int(idx) == (1_234 // 500) % 2 == 0
        assert int(start) == 1_000

    def test_wraps(self):
        idx0, _ = W.bucket_index(SPEC, jnp.int32(0))
        idx1, _ = W.bucket_index(SPEC, jnp.int32(500))
        idx2, _ = W.bucket_index(SPEC, jnp.int32(1_000))
        assert int(idx0) == int(idx2) != int(idx1)


class TestAddAndSum:
    def test_counts_within_interval(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        now = 10_000
        ws = add_pass(ws, now, res=3, n=2)
        ws = add_pass(ws, now + 100, res=3)
        assert pass_sum(ws, now + 100)[3] == 3
        assert pass_sum(ws, now + 100)[0] == 0

    def test_window_slides_off(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=1, n=5)
        # still visible within the 1s interval
        assert pass_sum(ws, 10_900)[1] == 5
        # gone once the bucket's window start leaves (now - interval, now]
        assert pass_sum(ws, 11_500)[1] == 0

    def test_two_buckets_both_count(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=0, n=1)  # bucket A
        ws = add_pass(ws, 10_600, res=0, n=2)  # bucket B
        assert pass_sum(ws, 10_999)[0] == 3

    def test_stale_slot_reset_on_reuse(self):
        # After a full ring revolution the old slot must be zeroed when rewritten
        # (LeapArray.java:147-155 reset arm).
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=2, n=7)
        ws = add_pass(ws, 11_000, res=2, n=1)  # same ring slot, one interval later
        assert pass_sum(ws, 11_000)[2] == 1

    def test_idle_gap_masked_on_read(self):
        # Counts written long ago must not reappear even without intervening writes.
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=2, n=7)
        assert pass_sum(ws, 60_000)[2] == 0

    def test_batched_duplicate_accumulation(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        res = jnp.array([5, 5, 5, 1], jnp.int32)
        chan = jnp.array([Event.PASS, Event.PASS, Event.BLOCK, Event.PASS], jnp.int32)
        val = jnp.array([1, 2, 4, 8], jnp.int32)
        ws = W.add_events(SPEC, ws, jnp.int32(20_000), res, chan, val)
        assert pass_sum(ws, 20_000)[5] == 3
        assert np.asarray(W.window_sum(SPEC, ws, jnp.int32(20_000), Event.BLOCK))[5] == 4
        assert pass_sum(ws, 20_000)[1] == 8

    def test_valid_mask_respects_padding(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        res = jnp.array([5, 5], jnp.int32)
        chan = jnp.array([Event.PASS, Event.PASS], jnp.int32)
        val = jnp.array([1, 100], jnp.int32)
        ws = W.add_events(
            SPEC, ws, jnp.int32(20_000), res, chan, val,
            valid=jnp.array([True, False]),
        )
        assert pass_sum(ws, 20_000)[5] == 1

    def test_jit_compatible(self):
        fn = jax.jit(
            lambda ws, now, r, c, v: W.add_events(SPEC, ws, now, r, c, v)
        )
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = fn(
            ws,
            jnp.int32(10_000),
            jnp.array([0], jnp.int32),
            jnp.array([0], jnp.int32),
            jnp.array([3], jnp.int32),
        )
        assert pass_sum(ws, 10_000)[0] == 3


class TestReferenceParityWindowing:
    """Property test: tensor windows match a straightforward per-event replay
    (the oracle mirrors LeapArray read semantics)."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_replay(self, seed):
        rng = np.random.default_rng(seed)
        ws = W.make_window(SPEC, R, N_EVENTS)
        events = []  # (t, res, n)
        t = 5_000
        for _ in range(200):
            t += int(rng.integers(0, 180))
            res = int(rng.integers(0, R))
            n = int(rng.integers(1, 4))
            events.append((t, res, n))
            ws = add_pass(ws, t, res, n)
        now = t
        got = pass_sum(ws, now)
        # oracle: event counts whose *bucket window start* is within (now-interval, now]
        want = np.zeros(R, np.int64)
        for (et, res, n) in events:
            bstart = et - et % SPEC.bucket_ms
            if 0 <= now - bstart < SPEC.interval_ms:
                want[res] += n
        assert (got == want).all()


class TestFutureWindows:
    def test_add_future_and_sum(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        now = jnp.int32(10_000)
        ws = W.add_future(
            SPEC, ws, now,
            wait_ms=jnp.array([500], jnp.int32),
            resource_ids=jnp.array([4], jnp.int32),
            channel=Event.OCCUPIED_PASS,
            values=jnp.array([2], jnp.int32),
        )
        waiting = np.asarray(W.future_sum(SPEC, ws, now, Event.OCCUPIED_PASS))
        assert waiting[4] == 2
        # once time reaches the future bucket it is no longer "waiting"
        waiting_later = np.asarray(
            W.future_sum(SPEC, ws, jnp.int32(10_500), Event.OCCUPIED_PASS)
        )
        assert waiting_later[4] == 0
        # ...but it IS a valid current bucket now (borrowed tokens count as passed)
        cur = np.asarray(W.window_sum(SPEC, ws, jnp.int32(10_500), Event.OCCUPIED_PASS))
        assert cur[4] == 2

    def test_invalid_rows_do_not_reset_live_buckets(self):
        # regression: a valid=False (padded) row must not drive the slot-reset
        # union — previously it could wipe live current-window counts.
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=1, n=5)
        ws = W.add_future(
            SPEC, ws, jnp.int32(10_000),
            wait_ms=jnp.array([SPEC.interval_ms], jnp.int32),  # maps onto current slot pre-clamp
            resource_ids=jnp.array([1], jnp.int32),
            channel=Event.OCCUPIED_PASS,
            values=jnp.array([3], jnp.int32),
            valid=jnp.array([False]),
        )
        assert pass_sum(ws, 10_000)[1] == 5

    def test_wait_clamped_to_ring_capacity(self):
        # regression: wait_ms large enough to wrap the ring must be clamped to
        # at most n_buckets-1 windows ahead, never colliding with the current slot.
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=1, n=5)
        ws = W.add_future(
            SPEC, ws, jnp.int32(10_000),
            wait_ms=jnp.array([10 * SPEC.interval_ms], jnp.int32),
            resource_ids=jnp.array([1], jnp.int32),
            channel=Event.OCCUPIED_PASS,
            values=jnp.array([3], jnp.int32),
        )
        assert pass_sum(ws, 10_000)[1] == 5  # current bucket untouched
        waiting = np.asarray(W.future_sum(SPEC, ws, jnp.int32(10_000), Event.OCCUPIED_PASS))
        assert waiting[1] == 3  # landed in the farthest future slot instead

    def test_zero_wait_rows_masked(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = W.add_future(
            SPEC, ws, jnp.int32(10_000),
            wait_ms=jnp.array([0, 500], jnp.int32),
            resource_ids=jnp.array([4, 4], jnp.int32),
            channel=Event.OCCUPIED_PASS,
            values=jnp.array([1, 10], jnp.int32),
        )
        waiting = np.asarray(W.future_sum(SPEC, ws, jnp.int32(10_000), Event.OCCUPIED_PASS))
        assert waiting[4] == 10

    # -- add_future by its target buckets' columns, against plain numpy ------

    @staticmethod
    def _np_add_future(spec, starts, counts, now, wait, ids, values, valid,
                       channel):
        """One row after the other, as ``addWaiting`` would: the slot of the
        bucket ``k`` ahead is zeroed (every resource, every channel) when it
        holds another bucket, then takes the row."""
        starts, counts = starts.copy(), counts.copy()
        cur = now - now % spec.bucket_ms
        for w, i, v, ok in zip(wait, ids, values, valid):
            if w <= 0 or not ok:
                continue
            k = min(max((now + w - cur) // spec.bucket_ms, 1),
                    spec.n_buckets - 1)
            start = cur + k * spec.bucket_ms
            slot = (start // spec.bucket_ms) % len(starts)
            if starts[slot] != start:
                counts[:, slot, :], starts[slot] = 0, start
            if 0 <= i < counts.shape[0]:
                counts[i, slot, channel] += v
        return starts, counts

    @pytest.mark.parametrize("bucket_ms,n_buckets,ring,n_channels,channel", [
        (500, 2, 2, N_EVENTS, int(Event.OCCUPIED_PASS)),  # the ring the spec names
        (500, 2, 4, 1, 0),
        (100, 10, 20, 1, 0),  # the served occupy window: engine.state.occupy_ring
        (100, 10, 20, 3, 2),
        (100, 4, 5, 2, 1),  # a ring that is no multiple of the window
    ])
    def test_columns_against_numpy_over_ring_wraps(
            self, bucket_ms, n_buckets, ring, n_channels, channel):
        """Random bookings over many ring periods and idle gaps: stale slots
        are reset, live ones keep their counts, duplicate flows accumulate,
        ids past the end are dropped, masked rows leave no trace."""
        spec = W.WindowSpec(bucket_ms, n_buckets)
        rng = np.random.default_rng(ring * 31 + n_channels)
        ws = W.make_window(W.WindowSpec(bucket_ms, ring), R, n_channels)
        starts = np.full(ring, int(W.NEVER), np.int64)
        counts = np.zeros((R, ring, n_channels), np.int64)
        now, step = 7_000, jax.jit(W.add_future, static_argnums=(0, 5))
        for t in range(60):
            now += int(rng.choice([1, bucket_ms // 3, bucket_ms, 3 * bucket_ms,
                                   (ring + 2) * bucket_ms]))
            n = 24
            wait = rng.integers(-bucket_ms, (n_buckets + 2) * bucket_ms, n)
            ids = rng.integers(0, R + 2, n)  # R and R + 1 are past the end
            values = rng.integers(1, 5, n)
            valid = rng.random(n) < 0.8
            if t % 7 == 0:
                valid[:] = False  # a whole batch of masked rows
            ws = step(spec, ws, jnp.int32(now), jnp.asarray(wait, jnp.int32),
                      jnp.asarray(ids, jnp.int32), channel,
                      jnp.asarray(values, jnp.int32), jnp.asarray(valid))
            starts, counts = self._np_add_future(
                spec, starts, counts, now, wait, ids, values, valid, channel)
            np.testing.assert_array_equal(np.asarray(ws.starts), starts, f"step {t}")
            np.testing.assert_array_equal(np.asarray(ws.counts), counts, f"step {t}")
        assert counts[:, :, channel].sum() > 0
        assert counts.sum() == counts[:, :, channel].sum()

    def test_a_stale_target_is_reset_and_the_live_slot_behind_it_kept(self):
        """PR 31's defect: in a ring as long as the window, the bucket ``k``
        ahead shares its slot with the live bucket ``B - k`` behind and its
        reset wiped that one for every resource. In a ring of ``2 B`` slots
        the matured bucket keeps its counts while the target is reset."""
        spec = W.WindowSpec(100, 10)
        ws = W.make_window(W.WindowSpec(100, 20), R, 1)
        book = lambda ws, now, wait, res, n: W.add_future(  # noqa: E731
            spec, ws, jnp.int32(now), jnp.array([wait], jnp.int32),
            jnp.array([res], jnp.int32), 0, jnp.array([n], jnp.int32))
        ws = book(ws, 8_000, 300, 2, 9)  # bucket 8_300: slot 3 of 20
        # a ring period later slot 3 is stale: targeted, it starts from zero
        # for every resource
        ws = book(ws, 10_000, 300, 1, 5)  # bucket 10_300: slot 3 again
        assert int(np.asarray(ws.starts)[3]) == 10_300
        np.testing.assert_array_equal(
            np.asarray(ws.counts)[:, 3, 0], [0, 5, 0, 0, 0, 0, 0, 0])
        # 10_300 has matured and is 7 buckets behind 11_000; book 3 ahead
        # (bucket 11_300: slot 13 of 20, and slot 3 of a 10-slot ring)
        ws = book(ws, 11_000, 300, 3, 4)
        at = jnp.arange(R, dtype=jnp.int32)
        matured = np.asarray(W.window_sum_at(spec, ws, jnp.int32(11_000), 0, at))
        waiting = np.asarray(W.future_sum_at(spec, ws, jnp.int32(11_000), 0, at))
        assert matured[1] == 5 and matured[3] == 0
        assert waiting[3] == 4 and waiting[1] == 0
        assert [int(x) for x in np.asarray(ws.starts)[[3, 13]]] == [10_300, 11_300]

    @pytest.mark.parametrize("now,wait,ahead", [
        (10_000, 1, 1), (10_000, 99, 1), (10_000, 100, 1), (10_000, 101, 1),
        (10_000, 250, 2), (10_050, 250, 3), (10_099, 1, 1), (10_000, 899, 8),
        (10_000, 900, 9), (10_099, 850, 9), (10_000, 5_000, 9),
        (10_000, 2**30, 9),
    ])
    def test_the_target_is_clamped_at_both_ends(self, now, wait, ahead):
        spec = W.WindowSpec(100, 10)
        ws = W.make_window(W.WindowSpec(100, 20), R, 1)
        ws = W.add_future(
            spec, ws, jnp.int32(now), jnp.array([wait], jnp.int32),
            jnp.array([6], jnp.int32), 0, jnp.array([3], jnp.int32))
        slot = (10_000 // 100 + ahead) % 20
        want = np.zeros((R, 20, 1), np.int64)
        want[6, slot, 0] = 3
        np.testing.assert_array_equal(np.asarray(ws.counts), want)
        assert int(np.asarray(ws.starts)[slot]) == 10_000 + 100 * ahead

    @pytest.mark.parametrize("masked_by", ["wait", "valid", "both"])
    def test_masked_rows_drive_neither_counts_nor_resets(self, masked_by):
        spec = W.WindowSpec(100, 10)
        ws = W.make_window(W.WindowSpec(100, 20), R, 1)
        ws = W.add_future(  # slot 3 holds bucket 8_300, long stale at 10_000
            spec, ws, jnp.int32(8_000), jnp.array([300], jnp.int32),
            jnp.array([2], jnp.int32), 0, jnp.array([9], jnp.int32))
        before = jax.tree.map(np.asarray, ws)
        wait = [-5, 0] if masked_by != "valid" else [300, 300]
        valid = [False, False] if masked_by != "wait" else [True, True]
        ws = W.add_future(
            spec, ws, jnp.int32(10_000), jnp.array(wait, jnp.int32),
            jnp.array([2, 5], jnp.int32), 0, jnp.array([4, 4], jnp.int32),
            valid=jnp.array(valid))
        np.testing.assert_array_equal(np.asarray(ws.starts), before.starts)
        np.testing.assert_array_equal(np.asarray(ws.counts), before.counts)

    def test_duplicate_flows_in_one_batch_accumulate_per_target(self):
        spec = W.WindowSpec(100, 10)
        ws = W.make_window(W.WindowSpec(100, 20), R, 1)
        ws = W.add_future(
            spec, ws, jnp.int32(10_000),
            jnp.array([150, 150, 320, 150, 320, 150], jnp.int32),
            jnp.array([4, 4, 4, 7, 4, 4], jnp.int32), 0,
            jnp.array([1, 2, 10, 5, 20, 3], jnp.int32))
        counts = np.asarray(ws.counts)[:, :, 0]
        assert counts[4, 1] == 6 and counts[4, 3] == 30 and counts[7, 1] == 5
        assert counts.sum() == 41

    def test_combine_desired_keeps_starts_alike_on_the_mesh(self):
        """Four shards of the resource axis, each seeing only the rows it
        owns: with the pmax every shard resets the same slots (also the
        shards no row aims at), ``starts`` comes out the same everywhere and
        the stitched counts are one device's."""
        from functools import partial
        from jax.sharding import Mesh, PartitionSpec as P

        spec, ring, n_res = W.WindowSpec(100, 10), W.WindowSpec(100, 20), 16
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("flows",))

        def shard_step(starts, counts, now, wait, ids, values):
            local = ids - jax.lax.axis_index("flows") * counts.shape[0]
            mine = (local >= 0) & (local < counts.shape[0])
            ws = W.add_future(
                spec, W.WindowState(starts, counts), now, wait,
                jnp.where(mine, local, 0), 0, values, valid=mine,
                combine_desired=partial(jax.lax.pmax, axis_name="flows"))
            return ws.starts[None], ws.counts

        sharded = jax.jit(jax.shard_map(
            shard_step, mesh=mesh,
            in_specs=(P(), P("flows"), P(), P(), P(), P()),
            out_specs=(P("flows"), P("flows")), check_vma=False))
        single = jax.jit(lambda ws, now, wait, ids, values: W.add_future(
            spec, ws, now, wait, ids, 0, values))
        one = W.make_window(ring, n_res, 1)
        starts, counts = one.starts, one.counts
        rng = np.random.default_rng(4)
        for t in range(30):
            now = jnp.int32(5_000 + 170 * t + (2_500 if t == 20 else 0))
            wait = jnp.asarray(rng.integers(-50, 1_100, 12), jnp.int32)
            # few flows a step, so most steps leave some shard without a row
            ids = jnp.asarray(rng.integers(0, n_res, 2).repeat(6), jnp.int32)
            values = jnp.asarray(rng.integers(1, 4, 12), jnp.int32)
            one = single(one, now, wait, ids, values)
            per_shard, counts = sharded(starts, counts, now, wait, ids, values)
            for shard in np.asarray(per_shard):
                np.testing.assert_array_equal(shard, np.asarray(one.starts))
            starts = per_shard[0]
            np.testing.assert_array_equal(np.asarray(counts), np.asarray(one.counts))
        assert int(np.asarray(counts).sum()) > 0

    @pytest.mark.parametrize("n_channels,channel", [(1, 0), (N_EVENTS, 2)])
    def test_one_fetch_gives_matured_and_waiting(self, n_channels, channel):
        """``past_and_future_sums_at`` (the decide step's one read of the
        occupy window) against ``window_sum_at`` and ``future_sum_at``, and
        both against the dense sums, with buckets behind, ahead and stale."""
        spec = W.WindowSpec(100, 10)
        ws = W.make_window(W.WindowSpec(100, 20), R, n_channels)
        rng = np.random.default_rng(n_channels)
        book = jax.jit(lambda ws, now, wait, ids, values: W.add_future(
            spec, ws, now, wait, ids, channel, values))
        for now in (10_000, 10_250, 10_900, 11_400, 11_950):
            ws = book(
                ws, jnp.int32(now),
                jnp.asarray(rng.integers(1, 900, 20), jnp.int32),
                jnp.asarray(rng.integers(0, R, 20), jnp.int32),
                jnp.asarray(rng.integers(1, 6, 20), jnp.int32))
        at = jnp.asarray([3, 0, 7, 3, 5], jnp.int32)
        for now in (11_960, 12_300, 13_000, 14_500, 40_000):
            now = jnp.int32(now)
            matured, waiting = W.past_and_future_sums_at(spec, ws, now, channel, at)
            np.testing.assert_array_equal(
                np.asarray(matured),
                np.asarray(W.window_sum_at(spec, ws, now, channel, at)))
            np.testing.assert_array_equal(
                np.asarray(waiting),
                np.asarray(W.future_sum_at(spec, ws, now, channel, at)))
            np.testing.assert_array_equal(
                np.asarray(matured),
                np.asarray(W.window_sum(spec, ws, now, channel))[np.asarray(at)])
            np.testing.assert_array_equal(
                np.asarray(waiting),
                np.asarray(W.future_sum(spec, ws, now, channel))[np.asarray(at)])
        m, w = W.past_and_future_sums_at(spec, ws, jnp.int32(11_960), channel, at)
        assert int(m.sum()) > 0 and int(w.sum()) > 0

    def test_rebase(self):
        ws = W.make_window(SPEC, R, N_EVENTS)
        ws = add_pass(ws, 10_000, res=0, n=5)
        ws2 = ws._replace(starts=W.shift_clock(ws.starts, 4_000))
        assert pass_sum(ws2, 6_000)[0] == 5
