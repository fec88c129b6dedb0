"""The decide step against the shaped family's plain reference
(``cellbench/families/shaped_reference.py``: scalar float64, one request at a
time, written from the published controllers, nothing of the program in it).

Seeded random rule tables holding all four control behaviours, prioritized
rows, and a clock that walks over bucket and window edges and stands still
long enough for a WARM_UP flow to go cold again. Every status and every
``wait_ms`` of ``engine.decide`` (``uniform`` false) and of the fused serve
step must equal the reference's: 0 mismatches. Every flow asks one acquire
size, as the deployment's first guarantee says (mixed sizes inside one flow
may under-admit), and the reference's ``closest`` shows that no seed sits
where float32 and float64 round apart.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench.deploy import (  # noqa: E402
    BLOCKED, DEFAULT, OK, RATE_LIMITER, SHOULD_WAIT, WARM_UP,
    WARM_UP_RATE_LIMITER)
from cellbench.families import shaped_reference  # noqa: E402
from sentinel_tpu.engine import (  # noqa: E402
    ClusterFlowRule, EngineConfig, build_rule_table, decide, make_batch,
    make_state, unpack_verdicts)
from sentinel_tpu.engine.decide import (  # noqa: E402
    ARM_ALL, ARM_FIELDS, ARM_LIVE, ARM_OCCUPY, ARM_PACED_ROWS, ARM_PACING,
    ARM_PRIORITIZED_ROWS, ARM_SHAPED_ROWS, ARM_SHAPING, ROW_HEAD, HEAD_NOW,
    alloc_packed_block, decide_fused_donating, pack_requests_into,
    unpack_arms)
from sentinel_tpu.engine.rules import ThresholdMode  # noqa: E402

CFG = EngineConfig(max_flows=64, max_namespaces=4, batch_size=64)
NS_MAX_QPS = 150.0
DEPTH = 3
# Counts that keep a threshold or a cost off the edge of its rounding. No
# WARM_UP count is divisible by the cold factor (the cold rate would be a
# whole number). A paced cost is a whole number of ms; on the curve it is
# ``acquire * (1000 / c + above * 400 / c**2)`` with ``above`` whole stored
# tokens: steps of 0.16 ms (c = 50) and 0.04 ms (c = 100) never land on a
# half, where c = 40 (steps of 0.25) does every fourth token
COUNTS = {DEFAULT: (5, 12, 30, 60), WARM_UP: (20, 50, 100, 140),
          RATE_LIMITER: (10, 20, 50, 100, 200),
          WARM_UP_RATE_LIMITER: (50, 100)}


def table_of(seed: int):
    """``(rules, acquire of each flow)``: 24 flows in three namespaces, six
    of each behaviour."""
    rng = np.random.default_rng([seed, 11])
    rules, acquire = [], {}
    for k, fid in enumerate(rng.permutation(24) + 100):
        beh = k % 4
        count = float(rng.choice(COUNTS[beh]))
        rules.append(ClusterFlowRule(
            int(fid), count, ThresholdMode.GLOBAL, f"ns{k % 3}",
            control_behavior=beh))
        acquire[int(fid)] = int(rng.choice((1, 1, 2)))
    return rules, acquire


def stream_of(seed: int, acquire: dict, frames: int):
    """``frames`` frames of ``(gap before it in ms, rows)``, a row being
    ``(flow id, acquire, prioritized)``; one flow id has no rule."""
    rng = np.random.default_rng([seed, 23])
    fids = np.array(sorted(acquire) + [999])
    p = 1.0 / np.arange(1, len(fids) + 1) ** 0.9
    rng.shuffle(p)
    out = []
    for k in range(frames):
        gap = int(rng.choice((0, 7, 40, 99, 100, 180, 333)))
        if k == frames // 2:
            gap = 2600  # every window empties, every WARM_UP flow cools
        n = int(rng.integers(20, CFG.batch_size + 1))
        ids = rng.choice(fids, size=n, p=p / p.sum())
        rows = [(int(f), acquire.get(int(f), 1), bool(rng.random() < 0.3))
                for f in ids]
        out.append((gap, rows))
    return out


def reference_of(rules) -> shaped_reference.Reference:
    return shaped_reference.Reference(
        {r.flow_id: shaped_reference.Rule(r.count, r.namespace,
                                          r.control_behavior)
         for r in rules}, NS_MAX_QPS, CFG.bucket_ms, CFG.n_buckets)


def mismatches(status, wait, want) -> int:
    want_s, want_w = (np.asarray(w) for w in want)
    return int((status != want_s).sum()) + int((wait != want_w).sum())


@pytest.mark.parametrize("seed", range(6))
def test_decide_equals_the_plain_reference_row_for_row(seed):
    rules, acquire = table_of(seed)
    table, index = build_rule_table(CFG, rules, ns_max_qps=NS_MAX_QPS)
    ref, state, now, bad = reference_of(rules), make_state(CFG), 5_000, 0
    seen = np.zeros(16, int)
    for gap, rows in stream_of(seed, acquire, 70):
        now += gap
        batch = make_batch(
            CFG, [index.slot_of.get(f, -1) for f, _a, _p in rows],
            [a for _f, a, _p in rows], [p for _f, _a, p in rows])
        state, v = decide(CFG, state, table, batch, np.int32(now))
        n = len(rows)
        status, wait = np.asarray(v.status)[:n], np.asarray(v.wait_ms)[:n]
        bad += mismatches(status, wait, ref.decide_frame(now, *zip(*rows)))
        seen += np.bincount(status, minlength=16)
    assert bad == 0
    # the stream met every verdict the shapers give, and the guard
    assert all(seen[s] > 20 for s in (OK, BLOCKED, SHOULD_WAIT)), seen
    assert seen[3] > 0 and seen[4] > 0  # NO_RULE, TOO_MANY_REQUEST
    assert ref.closest > 1e-5  # float32 carries seven digits


@pytest.mark.parametrize("seed", range(6, 10))
def test_the_fused_serve_step_equals_it_and_says_which_arms_ran(seed):
    rules, acquire = table_of(seed)
    table, index = build_rule_table(CFG, rules, ns_max_qps=NS_MAX_QPS)
    behaviour = {r.flow_id: r.control_behavior for r in rules}
    ref, state, now, bad = reference_of(rules), make_state(CFG), 5_000, 0
    step = decide_fused_donating(CFG, DEPTH)
    stream, all_live = stream_of(seed, acquire, 20 * DEPTH), 0
    for k in range(0, len(stream), DEPTH):
        span = stream[k:k + DEPTH]
        now += sum(gap for gap, _rows in span)  # a span shares one clock
        block = alloc_packed_block(CFG, DEPTH)
        for f, (_gap, rows) in enumerate(span):
            pack_requests_into(
                block, f, [index.slot_of.get(i, -1) for i, _a, _p in rows],
                [a for _i, a, _p in rows], [p for _i, _a, p in rows])
        block[ROW_HEAD, 0, HEAD_NOW] = now
        state, packed = step(state, table, block)
        status, wait, _remaining = unpack_verdicts(packed)
        want_arms, want_live = np.zeros(4, int), 0
        for f, (_gap, rows) in enumerate(span):
            n = len(rows)
            want = ref.decide_frame(now, *zip(*rows))
            bad += mismatches(status[f, :n], wait[f, :n], want)
            # rows the guard refused (or that have no rule) reach no arm
            reached = [behaviour.get(i, 0) for (i, _a, _p), s in
                       zip(rows, want[0]) if s in (OK, BLOCKED, SHOULD_WAIT)]
            shaped = sum(b != DEFAULT for b in reached)
            paced = sum(b in (RATE_LIMITER, WARM_UP_RATE_LIMITER)
                        for b in reached)
            warm = sum(b in (WARM_UP, WARM_UP_RATE_LIMITER) for b in reached)
            prio = sum(p for _i, _a, p in rows)
            want_arms += [0, shaped, paced, prio]
            want_live |= ((warm > 0) * ARM_SHAPING | (paced > 0) * ARM_PACING
                          | (prio > 0) * ARM_OCCUPY)
        arms = unpack_arms(np.asarray(packed), DEPTH)
        assert arms[ARM_LIVE] == want_live
        assert arms[[ARM_SHAPED_ROWS, ARM_PACED_ROWS,
                     ARM_PRIORITIZED_ROWS]].tolist() == want_arms[1:].tolist()
        all_live += want_live == ARM_ALL
    assert bad == 0
    assert all_live >= 15  # of 20 spans: the arms were not idle
    assert ref.closest > 1e-5


def test_a_step_of_default_rows_says_no_arm_ran():
    rules = [ClusterFlowRule(1, 10, ThresholdMode.GLOBAL, "a")]
    table, index = build_rule_table(CFG, rules)
    block = alloc_packed_block(CFG, 2)
    for f in range(2):
        pack_requests_into(block, f, [index.slot_of[1]] * 9)
    block[ROW_HEAD, 0, HEAD_NOW] = 1_000
    _state, packed = decide_fused_donating(CFG, 2)(
        make_state(CFG), table, block)
    assert unpack_arms(np.asarray(packed), 2).tolist() == [0] * ARM_FIELDS
    status, _wait, _rem = unpack_verdicts(packed)
    # the arms ride above the status of a frame's first entries: the
    # statuses come out as they were
    assert status[0, :9].tolist() == [OK] * 9
    assert status[1, :9].tolist() == [OK] + [BLOCKED] * 8


def test_booked_tokens_count_for_all_of_their_window():
    """The defect the reference found (PR 31). In a ring as long as the flow
    window's, a booking ``k`` buckets ahead took the slot of the matured
    bucket ``n_buckets - k`` behind and zeroed it for every flow: here the
    pacer's grant at 1920 (90 ms ahead: bucket 2000) wiped the 10 tokens the
    DEFAULT flow had booked into bucket 1000, and that flow admitted 10 more
    at 1930, 20 inside one window of count 10."""
    rules = [ClusterFlowRule(1, 10, ThresholdMode.GLOBAL, "a"),
             ClusterFlowRule(2, 100, ThresholdMode.GLOBAL, "a",
                             control_behavior=RATE_LIMITER)]
    table, index = build_rule_table(CFG, rules)
    ref, state = reference_of(rules), make_state(CFG)
    assert state.occupy.starts.shape == (2 * CFG.n_buckets,)
    got = {}
    for now, fid, n, prio in ((10, 1, 10, False), (950, 1, 12, True),
                              (1920, 2, 10, False), (1930, 1, 12, False),
                              (2000, 1, 12, False)):
        batch = make_batch(CFG, [index.slot_of[fid]] * n, [1] * n, [prio] * n)
        state, v = decide(CFG, state, table, batch, np.int32(now))
        got[now] = np.asarray(v.status)[:n].tolist()
        want = ref.decide_frame(now, [fid] * n, [1] * n, [prio] * n)
        assert got[now] == want[0], now
    assert got[950] == [SHOULD_WAIT] * 10 + [BLOCKED] * 2
    assert got[1930] == [BLOCKED] * 12  # the booked 10 still hold the window
    assert got[2000] == [OK] * 10 + [BLOCKED] * 2
