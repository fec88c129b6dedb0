"""The shaped family (``cellbench/families/shaped.py``) at a tiny size on
the CPU: one cell end to end through the native door with every arm of the
step live, the probe's nine checks, each control caught; the reference in the
program's place, exact and in lower precision; the reference by hand; the
frames, the ledger, the mix and the readers."""

import json
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from cellbench import deploy, probe, run, wire
from cellbench.deploy import (BLOCKED, DEFAULT, OK, RATE_LIMITER, SHOULD_WAIT,
                              WARM_UP, WARM_UP_RATE_LIMITER)
from cellbench.families import shaped, shaped_reference

HERE = os.path.dirname(os.path.abspath(__file__))
EXTRA = os.path.join(HERE, "extra")
CELL = "tiny-shaped.tiny-prio-open"
CHECKS = ("tight", "big", "guard", "paced", "warm", "warm_slide",
          "warm_paced", "occupy", "occupy_mature")
READERS = ("step.shaped_arms_live_share", "service.should_wait_share",
           "lane.shaped_rows_per_dispatch")


def shaped_manifest(tmp) -> str:
    """The tests' manifest with the tiny deployment, its cell and the three
    new per-layer entries added: by entries alone, as BENCHMARK.json."""
    bench = deploy.load_json(os.path.join(HERE, "manifest.json"))
    bench["paths"] = [os.path.relpath(os.path.dirname(HERE), tmp),
                      os.path.relpath(EXTRA, tmp)]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(HERE, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-shaped", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(EXTRA, "configs", "tiny-shaped.json"), tmp),
        "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-shaped", "traffic": "tiny-prio-open",
        "chips": 1, "why": "test"})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path


def tiny_shaped() -> shaped.Deployment:
    return deploy.load(os.path.join(EXTRA, "configs", "tiny-shaped.json"),
                       [os.path.dirname(HERE)])


def tiny_mix() -> dict:
    return deploy.load_json(os.path.join(EXTRA, "traffic",
                                         "tiny-prio-open.json"))


@pytest.fixture(scope="module")
def shaped_run(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the program's counters
    before, and after)``."""
    from sentinel_tpu.metrics.server import server_metrics

    lines = []
    before = server_metrics().stage_snapshot()
    result = run.run_cell(shaped_manifest(str(tmp_path_factory.mktemp("cell"))),
                          CELL, seed=2_147_483_731, seconds=1.5, trace=0,
                          require_chip=False, out=lines.append)
    return result, lines, before, server_metrics().stage_snapshot()


def test_the_cell_runs_through_the_door_with_prioritized_rows(shaped_run):
    result, lines = shaped_run[:2]
    assert result["correct"] is True and result["failed"] == 0, lines[-25:]
    assert result["attempted"] == 150 * 64
    assert any("warm-up: depth-2 backlog of 512 rows in process" in ln
               for ln in lines)
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)


@pytest.mark.parametrize("check", CHECKS)
def test_the_probes_checks_read_no_mismatch(shaped_run, check):
    result, lines = shaped_run[:2]
    assert result["compared"]["probe_" + check] == [0, 0], [
        ln for ln in lines if "probe" in ln]


def test_the_windows_replies_hold_the_shaping_guarantees(shaped_run):
    compared = shaped_run[0]["compared"]
    for what in ("unmetered_rows_BLOCKED", "SHOULD_WAIT_rows_where_none_can_be",
                 "waits_over_their_bound", "rows_answered_twice"):
        assert compared[what] == [0, 0], what
    got, limit = compared["admitted_over_count"]
    assert 0 < got <= limit == 1  # hot flows were asked past their counts


def test_every_arm_of_the_step_ran_and_the_service_counted_it(shaped_run):
    lines, before, after = shaped_run[1:]
    grew = {k: after[k] - before[k] for k in after
            if k.startswith("decide_") and k.endswith("_total")}
    n = grew["decide_dispatch_total"]
    assert 0 < grew["decide_all_arms_live_total"] <= n
    for arm in ("shaping", "pacing", "occupy"):
        assert grew["decide_all_arms_live_total"] <= grew[
            f"decide_{arm}_live_total"] <= n
    assert 0 < grew["decide_paced_rows_total"] < grew[
        "decide_shaped_rows_total"] < grew["decide_rows_total"]
    assert 0 < grew["decide_prioritized_rows_total"] < grew[
        "decide_rows_total"]
    waits = (after["wait_assigned_ms"]["count"]
             - before["wait_assigned_ms"]["count"])
    assert 0 < waits < grew["decide_rows_total"]
    hist = [ln for ln in lines if "status OK" in ln][0]
    assert "SHOULD_WAIT 0 " not in hist


@pytest.mark.parametrize("control, caught_by", [
    ("over_admit", "tight"), ("unshaped", "warm")])
def test_a_broken_guarantee_is_not_correct(tmp_path, control, caught_by):
    lines = []
    result = run.run_cell(shaped_manifest(str(tmp_path)), CELL, seed=2_147_483_732,
                          seconds=1.5, trace=0, require_chip=False,
                          wrap_service=shaped.CONTROLS[control],
                          out=lines.append)
    assert result["correct"] is False
    assert result["compared"]["probe_" + caught_by][0] >= 1, [
        ln for ln in lines if "probe" in ln]
    if control == "unshaped":  # the other arms are as they were
        assert result["compared"]["probe_paced"] == [0, 0]
        assert result["compared"]["probe_occupy"] == [0, 0]
        assert result["compared"]["probe_warm_paced"][0] >= 1


# -- the reference in the program's place ---------------------------------------
class ReferenceDoor:
    """A token server made of the plain reference behind a plain socket, on
    the wall clock: BATCH_FLOW frames with their priority bytes in, the
    reference's verdicts out (``fake_door.FakeDoor`` drops the priority and
    decides every frame at one instant, which the shaping checks cannot
    live with)."""

    def __init__(self, ref):
        self.ref, self.lock = ref, threading.Lock()
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._accept, daemon=True).start()

    def close(self) -> None:
        self.sock.close()

    def _accept(self) -> None:
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn) -> None:
        buf = bytearray()
        while True:
            try:
                data = conn.recv(1 << 16)
            except OSError:
                return
            if not data:
                return
            buf += data
            while len(buf) >= 2:
                flen = struct.unpack_from(">H", buf, 0)[0]
                if len(buf) < 2 + flen:
                    break
                xid, _mtype = struct.unpack_from(">ib", buf, 2)
                n = struct.unpack_from(">H", buf, 7)[0]
                rows = np.frombuffer(bytes(buf[9:9 + 13 * n]), wire.REQ_ROW)
                del buf[:2 + flen]
                with self.lock:
                    status, wait = self.ref.decide_frame(
                        50_000 + int(time.monotonic() * 1000),
                        rows["flow_id"], rows["count"], rows["prio"])
                rsp = np.empty(n, wire.RSP_ROW)
                rsp["status"], rsp["wait_ms"] = status, wait
                rsp["remaining"] = 7  # an unmetered pass never reports 0
                conn.sendall(struct.pack(">HibH", 7 + 9 * n, xid,
                                         wire.BATCH_FLOW, n) + rsp.tobytes())


def probe_against(lower: bool, seed: int):
    # the lower-precision control needs a count past 256, where 8 bits run
    # out: the real deployment's file, with the tiny mix's 64-row frames
    dep = deploy.load(os.path.join(os.path.dirname(HERE), "configs",
                                   "shaped-mesh-100k.json"))
    tr = dict(tiny_mix(), frame_rows=128)
    door = ReferenceDoor(shaped_reference.for_deployment(
        dep, lower_precision=lower))
    try:
        return probe.Probe(door.port, dep, tr, seed=seed,
                           say=lambda m: None).run()
    finally:
        door.close()


@pytest.mark.parametrize("seed", [5, 6])
def test_exact_reference_in_the_programs_place_is_correct(seed):
    out = probe_against(lower=False, seed=seed)
    assert out["ok"], out
    assert [c["check"] for c in out["checks"]] == list(CHECKS)


def test_lower_precision_in_the_programs_place_is_not_correct():
    out = probe_against(lower=True, seed=5)
    assert not out["ok"]
    bad = {c["check"]: c["mismatches"] for c in out["checks"]}
    assert bad["big"] > 0 and bad["guard"] > 0  # counts 5000 and 30000
    # counts under 256 survive 8 bits, and so do the shapers' few tokens
    assert bad["tight"] == bad["paced"] == bad["occupy"] == 0


# -- the reference by hand --------------------------------------------------------
def ref(**kw):
    R = shaped_reference.Rule
    rules = {1: R(10, "a"), 2: R(100, "a", RATE_LIMITER),
             3: R(100, "a", WARM_UP), 4: R(100, "a", WARM_UP_RATE_LIMITER),
             5: R(5000, "b")}
    return shaped_reference.Reference(rules, 30000.0, 100, 10, **kw)


def test_warm_up_constants_are_the_controllers():
    r = shaped_reference.Rule(100, "a", WARM_UP)
    assert (r.warning, r.max_token, r.cold_count) == (500.0, 1000.0, 33.0)
    assert r.slope == pytest.approx(2.0 / 100 / 500)


def test_a_cold_flow_admits_a_third_and_the_curve_moves_with_the_second():
    r = ref()
    status, _ = r.decide_frame(10_400, [3] * 60, [1] * 60)
    assert status == [OK] * 33 + [BLOCKED] * 27
    # the same second: no sync, nothing passes
    assert r.decide(10_900, 3, 1) == (BLOCKED, 0)
    # the next second finds 33 passed in the window: 1000 - 33 stored
    # tokens, rate 1 / (467 * 0.00004 + 0.01) = 34.87: one row more
    assert r.decide_frame(11_100, [3] * 3, [1] * 3)[0] == [OK, BLOCKED,
                                                           BLOCKED]
    assert r.flows[3].stored == 967.0
    # idle for a window and a second: under the cold rate it refills to full
    assert r.decide_frame(13_000, [3] * 40, [1] * 40)[0].count(OK) == 33
    assert r.flows[3].stored == 1000.0
    assert 1e-3 < r.closest < 0.5  # no threshold near a whole number


def test_the_combined_controller_paces_at_the_cold_rate():
    status, wait = ref().decide_frame(0, [4] * 30, [1] * 30)
    assert status == [OK] + [SHOULD_WAIT] * 16 + [BLOCKED] * 13
    assert wait[:17] == [30 * j for j in range(17)]


def test_a_paced_wait_is_booked_where_it_ends():
    r = ref()
    r.decide_frame(1000, [2] * 60, [1] * 60)
    # waits 10 .. 500. The nine that end inside the bucket of 1000 go to the
    # next one (nothing is booked into the current bucket), with the ten
    # that end there; ten each in 1200 .. 1400, one at 1500
    assert r.flows[2].booked == {1100: 19.0, 1200: 10.0, 1300: 10.0,
                                 1400: 10.0, 1500: 1.0}
    assert r.flows[2].passed.total(1000) == 1.0
    assert r.flows[2].latest == 1500


def test_a_borrow_needs_room_in_the_window_that_begins_next():
    r = ref()
    assert r.decide_frame(1010, [1] * 12, [1] * 12, [0] * 10 + [1, 0])[0] == (
        [OK] * 10 + [BLOCKED] * 2)  # the coming bucket lets go of nothing
    # 1930: the bucket of 1000 is the oldest, its 10 tokens are about to go
    status, wait = r.decide_frame(1930, [1] * 14, [1] * 14, [1, 0] * 7)
    assert status == [SHOULD_WAIT, BLOCKED] * 7
    assert wait[::2] == [70] * 7
    status, _ = r.decide_frame(1935, [1] * 5, [1] * 5, [1] * 5)
    assert status == [SHOULD_WAIT] * 3 + [BLOCKED] * 2  # 7 + 3 booked
    # 2000: the 10 have left, the 10 booked count, for all of their window
    assert r.decide_frame(2000, [1] * 2, [1] * 2, [1, 0])[0] == [BLOCKED] * 2
    assert r.decide(2999, 1, 1) == (BLOCKED, 0)
    assert r.decide(3000, 1, 1) == (OK, 0)


def test_lower_precision_loses_the_stored_tokens():
    r = ref(lower_precision=True)
    r.decide_frame(10_400, [3] * 60, [1] * 60)
    r.decide(11_100, 3, 1)
    assert r.flows[3].stored == 968.0  # 967 has ten significant bits


# -- frames, ledger, mix, readers ---------------------------------------------------
def test_batch_frames_are_the_programs_codec_with_the_priority_byte():
    from sentinel_tpu.cluster import protocol as P

    ids, acq, prio = np.array([3, 4, 3]), np.array([1, 2, 1]), [0, 1, 0]
    raw = shaped.encode_batch(77, ids, acq, prio)
    assert raw == P.encode_batch_request(77, ids, acq, prio)
    xid, back_ids, back_acq, back_prio = P.decode_batch_request(raw[2:])
    assert (xid, back_ids.tolist(), back_acq.tolist()) == (77, [3, 4, 3],
                                                           [1, 2, 1])
    assert back_prio.tolist() == [False, True, False]
    one = shaped.encode_singles(5, ids, acq, prio)
    assert one["prio"].tolist() == prio and one["xid"].tolist() == [5, 6, 7]


def test_a_replys_wait_reaches_the_ledger_inside_remaining():
    row = np.zeros(2, wire.RSP_ROW)
    row["status"], row["remaining"], row["wait_ms"] = [0, 2], [9, 0], [0, 480]
    seen = np.frombuffer(row.tobytes(), shaped.BATCH_REPLIES[1])
    assert seen["status"].tolist() == [0, 2]
    assert seen["wait_ms"].tolist() == [0, 480]
    assert (seen["remaining"] >> 32).tolist() == [9, 0]
    assert (seen["remaining"] & 0xFFFFFFFF).tolist() == [0, 480]


def test_the_ledger_knows_grants_borrows_and_rows_that_cannot_be():
    dep = tiny_shaped()
    assert dep.family is shaped
    assert dep.metered_behaviours == [0, 1, 2, 3, 0, 1, 2, 3]
    assert dep.metered_counts == [40, 40, 20, 20, 20, 20, 10, 10]
    counts = dep.ledger_counts().reshape(2, 8, 8)
    assert counts[0, 0].tolist() == [40, 40, 38, 38, 20, 20, 23, 23]
    assert (counts[1] == np.asarray(dep.metered_counts)).all()
    # namespace 1: ranks 0 (DEFAULT), 1 (WARM_UP), 2 (RATE_LIMITER), 40
    ids = dep.flow_id(1, np.array([0, 0, 0, 1, 2, 2, 40, 40, 2, 1]))
    acq = np.array([1, 2, 3, 1, 4, 5, 1, 1, 1, 1], np.int32)
    prio = np.array([0, 1, 0, 0, 0, 0, 0, 0, 0, 1], np.uint8)
    st = np.array([OK, SHOULD_WAIT, SHOULD_WAIT, OK, OK, SHOULD_WAIT,
                   BLOCKED, OK, SHOULD_WAIT, SHOULD_WAIT], np.uint8)
    wait = np.array([0, 60, 60, 0, 0, 200, 0, 0, 501, 10], np.int64)
    rem = np.array([5, 0, 0, 3, 0, 0, 0, 0, 0, 0], np.int64)
    decided, brown, never, keys, tokens = dep.ledger_view(
        (ids, acq, prio), st, rem << 32 | wait)
    assert decided.all() and brown.tolist() == [False] * 7 + [True] + [
        False] * 2
    client = {"never_rows": never}
    assert dep.window_checks(client) == [
        ("unmetered rows BLOCKED", 1, 0),
        # an unprioritized DEFAULT row, a prioritized WARM_UP row
        ("SHOULD_WAIT rows where none can be", 2, 0),
        ("waits over their bound", 1, 0)]  # 501 ms on a paced flow
    # granted: OK on ranks 0, 1, 2 and the paced flow's waits; booked: the
    # prioritized DEFAULT row, under the second half of the keys
    assert keys.tolist() == [8, 9, 10, 10, 10, 64 + 8]
    assert tokens.tolist() == [1, 1, 4, 5, 1, 2]
    assert len(list(dep.rules())) == 2000
    roles = [r for _f, _c, _b, r in dep.probe_rules]
    assert roles.count("tight") == 4 and roles.count("clock") == 2


def test_the_mix_draws_the_flow_familys_rows_and_a_priority_flag():
    from cellbench.families import flow

    dep, tr = tiny_shaped(), tiny_mix()
    ids, acq, prio = shaped.Mix(tr, dep, 5, 1).frames(40)
    again = shaped.Mix(tr, dep, 5, 1).frames(40)
    other = shaped.Mix(tr, dep, 6, 1).frames(40)
    plain = flow.Mix(tr, dep, 5, 1).frames(40)
    assert prio.shape == ids.shape == (40, 64) and prio.dtype == np.uint8
    assert (prio == again[2]).all() and (prio != other[2]).any()
    assert (ids == plain[0]).all() and (acq == plain[1]).all()
    assert 0.05 < prio.mean() < 0.15
    assert 0.15 < (dep.behaviour_of(ids) != DEFAULT).mean() < 0.6
    tr["prioritized"] = {"share": 0.0}
    assert not shaped.Mix(tr, dep, 5, 1).frames(4)[2].any()


def test_the_arm_readers_read_the_programs_counters_or_nothing(tmp_path):
    from cellbench import manifest as mf

    r = mf.Cell(shaped_manifest(str(tmp_path)), CELL).readers()
    assert all(n in r for n in READERS)
    old = {"before": {"stages": {"wait_assigned_ms": {"count": 0}}},
           "after": {"stages": {"wait_assigned_ms": {"count": 9}}}}
    assert [r[n].reduce(old) for n in READERS] == [None] * 3  # a parent's
    before = {"decide_dispatch_total": 10, "decide_all_arms_live_total": 4,
              "decide_rows_total": 1000, "decide_shaped_rows_total": 100,
              "wait_assigned_ms": {"count": 7}}
    after = {"decide_dispatch_total": 30, "decide_all_arms_live_total": 23,
             "decide_rows_total": 21000, "decide_shaped_rows_total": 5100,
             "wait_assigned_ms": {"count": 1007}}
    snap = {"before": {"stages": before}, "after": {"stages": after}}
    assert r[READERS[0]].reduce(snap) == 95.0
    assert r[READERS[1]].reduce(snap) == 5.0
    assert r[READERS[2]].reduce(snap) == 250.0
    assert r[READERS[0]].reduce({"before": {"stages": before},
                                 "after": {"stages": before}}) is None
