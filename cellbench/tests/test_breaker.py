"""The breaker family (``cellbench/families/breaker.py``) at a tiny size on
the CPU: one cell end to end through the native door with the breaker arm
live and a report in front of every frame, the probe's twelve checks, each
control caught; the reference in the program's place; the frames, the ledger,
the mix and its health script, the readers and the roofline; and, at the
benchmark's own size, that the script makes every run the same run."""

import json
import os
import socket
import struct
import sys
import threading
import time

import numpy as np
import pytest

from cellbench import (deploy, loadgen, outcome_roofline, probe, run, traffic,
                       wire)
from cellbench.deploy import BLOCKED, DEGRADED, OK, SHOULD_WAIT, TOO_MANY
from cellbench.families import breaker, breaker_reference, flow

HERE = os.path.dirname(os.path.abspath(__file__))
EXTRA = os.path.join(HERE, "extra")
BENCH = os.path.dirname(HERE)
CELL = "tiny-breaker.tiny-health-cycle-open"
ADMITTED_CELL = "tiny-breaker.tiny-health-cycle-admitted-open"
REAL_CELL = "breaker-mesh-100k.tenants-zipf-health-cycle-open"
CHECKS = ("tight", "big", "guard", "paced", "trip_ratio", "trip_slow",
          "trip_count", "open_holds", "probe_one", "recover", "rollback",
          "fence")
READERS = ("service.degraded_share", "step.breaker_arm_live_share",
           "lane.outcome_rows_per_step", "service.outcome_ingest_avg_ms",
           "service.outcome_age_p95_ms", "step.outcome_device_ms_per_step",
           "service.breaker_transitions_per_s", "outcome_step_roofline")


def breaker_manifest(tmp) -> str:
    """The tests' manifest with the tiny deployment, its cell and the new
    per-layer entries added: by entries alone, as BENCHMARK.json."""
    bench = deploy.load_json(os.path.join(HERE, "manifest.json"))
    bench["paths"] = [os.path.relpath(BENCH, tmp), os.path.relpath(EXTRA, tmp)]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(HERE, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-breaker", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(EXTRA, "configs", "tiny-breaker.json"), tmp),
        "why": "test"})
    for cell in (CELL, ADMITTED_CELL):
        bench["workloads"].append({
            "name": cell, "config": "tiny-breaker",
            "traffic": cell.split(".")[1], "chips": 1, "why": "test"})
    real = deploy.load_json(os.path.join(os.path.dirname(BENCH),
                                         "BENCHMARK.json"))
    have = {m["name"] for m in bench["per_layer"]}
    for m in real["per_layer"]:
        if m["name"] in READERS and m["name"] not in have:
            bench["per_layer"].append(dict(m, workloads=[CELL]))
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path


def tiny_breaker() -> breaker.Deployment:
    return deploy.load(os.path.join(EXTRA, "configs", "tiny-breaker.json"),
                       [BENCH])


def tiny_mix(name: str = "tiny-health-cycle-open") -> dict:
    return deploy.load_json(os.path.join(EXTRA, "traffic", name + ".json"))


def real_cell():
    dep = deploy.load(os.path.join(BENCH, "configs", "breaker-mesh-100k.json"),
                      [BENCH])
    return dep, deploy.load_json(os.path.join(
        BENCH, "traffic", "tenants-zipf-health-cycle-open.json"))


@pytest.fixture(autouse=True)
def nothing_kept(monkeypatch):
    """What the family keeps of a run (the service, a control's reference)
    does not leak from one test into the next."""
    monkeypatch.setattr(breaker, "_RUN", dict(breaker._RUN))


@pytest.fixture(scope="module")
def breaker_run(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the program's counters
    before, and after)``."""
    from sentinel_tpu.metrics.server import server_metrics

    lines = []
    before = server_metrics().stage_snapshot()
    kept = dict(breaker._RUN)
    try:
        result = run.run_cell(
            breaker_manifest(str(tmp_path_factory.mktemp("cell"))), CELL,
            seed=2_147_483_741, seconds=6.0, trace=0, require_chip=False,
            out=lines.append)
    finally:
        breaker._RUN.clear()
        breaker._RUN.update(kept)
    return result, lines, before, server_metrics().stage_snapshot()


def test_the_cell_runs_through_the_door_with_a_report_before_every_frame(
        breaker_run):
    result, lines = breaker_run[:2]
    assert result["correct"] is True and result["failed"] == 0, lines[-40:]
    assert result["attempted"] == 600 * 64
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)
    hist = [ln for ln in lines if "status OK" in ln][0]
    assert "DEGRADED 0;" not in hist  # breakers tripped inside the window
    # nothing was left for the warm-up's traffic to compile: one pass
    assert any("warm-up pass 1" in ln and " 0 compiles" in ln for ln in lines)
    assert not any("warm-up pass 2" in ln for ln in lines)


@pytest.mark.parametrize("check", CHECKS)
def test_the_probes_checks_read_no_mismatch(breaker_run, check):
    result, lines = breaker_run[:2]
    assert result["compared"]["probe_" + check] == [0, 0], [
        ln for ln in lines if "probe" in ln]


def test_the_windows_replies_hold_the_breaker_guarantees(breaker_run):
    compared = breaker_run[0]["compared"]
    for what in ("unmetered_rows_BLOCKED", "unguarded_rows_DEGRADED",
                 "guarded_flows_with_rows_through_inside_an_OPEN_span",
                 "rows_answered_twice"):
        assert compared[what] == [0, 0], what
    shares = [k for k in compared if k.startswith("DEGRADED_share_of_")]
    assert len(shares) == 2
    assert all(compared[k][0] <= compared[k][1] == 450 for k in shares)
    got, limit = compared["admitted_over_count"]
    assert 0 < got <= limit == 1


def test_the_breaker_arm_ran_and_an_outcome_step_followed_the_frames(
        breaker_run):
    _result, _lines, before, after = breaker_run
    grew = {k: after[k] - before[k] for k in after
            if k.endswith("_total") and isinstance(after[k], int)}
    n = grew["decide_dispatch_total"]
    assert 0.9 * n <= grew["decide_breaker_live_total"] <= n
    assert 0 < grew["decide_degraded_rows_total"] < grew[
        "decide_guarded_rows_total"] < grew["decide_rows_total"]
    assert grew["breaker_to_open_total"] >= 8
    assert grew["breaker_probe_tickets_total"] >= grew[
        "breaker_to_closed_total"] + grew["breaker_reopened_total"] > 0
    assert 0 < grew["outcome_steps_total"] <= grew["outcome_frames_total"]
    assert grew["outcome_step_rows_total"] > 600 * 64 * 0.2
    for phase in ("outcome_lock_wait_ms", "outcome_launch_ms",
                  "outcome_age_ms"):
        assert after[phase]["count"] > before[phase]["count"], phase


@pytest.mark.parametrize("control, caught_by", [
    ("over_admit", ("tight",)),
    ("unguarded", ("trip_ratio", "trip_slow", "trip_count", "open_holds")),
    ("reference_8bit", ("big", "trip_ratio"))])
def test_a_broken_guarantee_is_not_correct(tmp_path, control, caught_by):
    lines = []
    result = run.run_cell(breaker_manifest(str(tmp_path)), CELL,
                          seed=2_147_483_742, seconds=2.0, trace=0,
                          require_chip=False,
                          wrap_service=breaker.CONTROLS[control],
                          out=lines.append)
    assert result["correct"] is False
    for check in caught_by:
        assert result["compared"]["probe_" + check][0] >= 1, [
            ln for ln in lines if "probe" in ln]
    if control == "unguarded":  # nothing was ever shed
        assert "DEGRADED 0;" in [ln for ln in lines if "status OK" in ln][0]
    if control == "reference_8bit":  # the server itself is sound
        assert result["compared"]["probe_trip_slow"] == [0, 0]
        assert result["compared"]["probe_fence"] == [0, 0]


def test_the_cell_is_in_the_cpu_rehearsal_by_files_alone(tmp_path,
                                                        monkeypatch, capsys):
    from cellbench import rehearse

    monkeypatch.setattr(rehearse, "MANIFEST", breaker_manifest(str(tmp_path)))
    monkeypatch.setattr(sys, "argv", ["rehearse", CELL])
    for _attempt in (1, 2):
        with pytest.raises(SystemExit) as done:
            rehearse.main()
        lines = capsys.readouterr().out.strip().splitlines()
        result = json.loads(lines[-1])
        # a correct 2 s window in which rows failed met a standstill of
        # this machine (2,304 of 12,800 rows, twice in 170 runs here, the
        # verdicts correct both times): once more
        if done.value.code != 0 or result["failed"] == 0:
            break
    assert done.value.code == 0, lines[-40:]
    assert result["correct"] is True and result["failed"] == 0, lines[-40:]


# -- the reference in the program's place ---------------------------------------
class ReferenceDoor:
    """A token server made of the plain reference behind a plain socket, on
    the wall clock: BATCH_FLOW frames in and the reference's verdicts out
    (a DEGRADED row's retry-after in ``remaining``), OUTCOME_REPORT frames
    ingested and not answered."""

    def __init__(self, ref):
        self.ref, self.lock = ref, threading.Lock()
        self.reports = []  # xids of the reports that came
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
                xid, mtype = struct.unpack_from(">ib", buf, 2)
                n = struct.unpack_from(">H", buf, 7)[0]
                body = bytes(buf[9:2 + flen])
                del buf[:2 + flen]
                now = 50_000 + int(time.monotonic() * 1000)
                if mtype == breaker.OUTCOME_REPORT:
                    rows = np.frombuffer(body, breaker.OUTCOME_ROW, n)
                    with self.lock:
                        self.reports.append(xid)
                        self.ref.report(now, rows["flow_id"], rows["rt_ms"],
                                        rows["exc"])
                    continue
                rows = np.frombuffer(body, wire.REQ_ROW, n)
                with self.lock:
                    status, rest = self.ref.decide_frame(
                        now, rows["flow_id"], rows["count"])
                rsp = np.empty(n, wire.RSP_ROW)
                status, rest = np.asarray(status), np.asarray(rest)
                rsp["status"] = status
                rsp["wait_ms"] = np.where(status == deploy.SHOULD_WAIT, rest, 0)
                # an unmetered pass never reports 0 tokens left
                rsp["remaining"] = np.where(status == DEGRADED, rest, 7)
                conn.sendall(struct.pack(">HibH", 7 + 9 * n, xid,
                                         wire.BATCH_FLOW, n) + rsp.tobytes())


def probe_against(seed: int, unguarded: bool = False, say=lambda m: None):
    dep = tiny_breaker()
    ref = breaker_reference.for_deployment(dep)
    if unguarded:
        ref.breakers = {}
    door = ReferenceDoor(ref)
    breaker._RUN.pop("service", None)  # no server of the program's here
    try:
        out = probe.Probe(door.port, dep, tiny_mix(), seed=seed,
                          say=say).run()
        return out, door
    finally:
        door.close()


@pytest.mark.parametrize("seed", [5, 6])
def test_the_reference_in_the_programs_place_is_correct(seed):
    out, door = probe_against(seed)
    assert out["ok"], out
    assert [c["check"] for c in out["checks"]] == list(CHECKS)
    assert door.reports and all(x < 0 for x in door.reports)


def test_a_trial_this_process_was_too_slow_for_is_made_again(monkeypatch):
    """The server stamps a report when it comes, the reference when the
    probe sees it counted. A probe that stalls a second between the two asks
    about a stat interval the server has left (the driver's loaded run of PR
    34's tests read OK where DEGRADED was due in ``lifecycle``, every row):
    such a trial is void and made again."""
    import types

    stalls = []

    def sleep(s):
        if s == 0.05 and len(stalls) < 2:  # the first two reports' waits
            stalls.append(s)
            s = 1.0
        time.sleep(s)

    monkeypatch.setattr(breaker, "time", types.SimpleNamespace(
        sleep=sleep, monotonic=time.monotonic))
    lines = []
    out, _door = probe_against(5, say=lines.append)
    assert out["ok"], lines
    assert len(stalls) == 2
    again = [ln for ln in lines if "trial" in ln]
    assert len(again) == 1 and "trip_ratio" in again[0] and (
        "trial 3" in again[0]), lines


def test_a_server_without_breakers_in_the_programs_place_is_not_correct():
    out, _door = probe_against(5, unguarded=True)
    bad = {c["check"]: c.get("mismatches") for c in out["checks"]}
    assert not out["ok"]
    assert all(bad[c] > 0 for c in ("trip_ratio", "trip_slow", "trip_count",
                                    "open_holds", "probe_one", "rollback"))
    assert all(bad[c] == 0 for c in ("tight", "big", "guard", "paced"))


def test_the_reference_with_8_bit_totals_is_not_correct():
    breaker._RUN["reference_control"] = {"lower_precision": True}
    out, _door = probe_against(5)
    bad = {c["check"]: c.get("mismatches") for c in out["checks"]}
    assert not out["ok"] and bad["big"] > 0 and bad["trip_ratio"] > 0
    assert all(bad[c] == 0 for c in ("trip_slow", "trip_count", "open_holds",
                                     "probe_one", "recover", "fence"))


# -- frames ------------------------------------------------------------------------
def test_a_frames_bytes_are_the_programs_report_and_then_its_request():
    from sentinel_tpu.cluster import protocol as P

    ids = np.array([3, 4, 3, 9], np.int64)
    acq = np.array([1, 2, 1, 1], np.int32)
    rt, exc = np.array([7, -1, 900, 0], np.int32), np.array([0, 0, 1, 1])
    told_ids = np.array([8, 8, 2, 5], np.int64)
    told_rt = np.array([12, -1, -1, 300], np.int32)
    told_exc = np.array([1, 0, 0, 0], np.uint8)
    raw = breaker.encode_batch(77, ids, acq, rt, exc, told_ids, told_rt,
                               told_exc)
    report = P.encode_outcome_report(-78, [8, 5], [12, 300], [1, 0])
    request = P.encode_batch_request(77, ids, acq, np.zeros(4, np.uint8))
    assert raw == report + request
    xid, fids, rts, excs = P.decode_outcome_report(raw[2:len(report)])
    assert (xid, fids.tolist(), rts.tolist(), excs.tolist()) == (
        -78, [8, 5], [12, 300], [True, False])
    assert P.peek_type(raw[2:]) in P.OUTCOME_TYPES
    assert breaker.OUTCOME_ROW == P.OUTCOME_ROW_DTYPE
    assert breaker.OUTCOME_REPORT == int(P.MsgType.OUTCOME_REPORT)
    # a frame whose connection had nothing to tell: the request frame alone
    none = np.full(4, -1, np.int32)
    assert breaker.encode_batch(5, ids, acq, rt, exc, told_ids, none,
                                told_exc) == P.encode_batch_request(
                                    5, ids, acq, np.zeros(4, np.uint8))
    one = breaker.encode_singles(5, ids, acq, rt, exc, told_ids, none,
                                 told_exc)
    assert one["xid"].tolist() == [5, 6, 7, 8]
    with pytest.raises(ValueError):
        breaker.encode_singles(5, ids, acq, rt, exc, told_ids, told_rt,
                               told_exc)


def test_the_splitter_is_untroubled_by_a_reports_xid():
    """No server answers a report; one that did, under the report's xid,
    would be skipped: the family names no such type."""
    rsp = np.zeros(3, wire.RSP_ROW)
    rsp["status"] = [0, 12, 1]
    reply = struct.pack(">HibH", 7 + 27, 41, wire.BATCH_FLOW, 3) + rsp.tobytes()
    stray = struct.pack(">HibH", 7, breaker.report_xid(41),
                        breaker.OUTCOME_REPORT, 0)
    split = wire.Splitter(breaker.SINGLE_REPLIES, breaker.BATCH_REPLIES)
    batch, singles = split.feed(stray + reply + stray)
    assert singles is None and [x for x, _r in batch] == [41]
    assert batch[0][1]["status"].tolist() == [0, 12, 1]
    assert breaker.report_xid(1) == -2 and breaker.report_xid(
        1_900_000_000) < 0
    struct.pack(">i", breaker.report_xid(2_000_000_000))  # fits the header


# -- the ledger ---------------------------------------------------------------------
def test_the_ledger_knows_what_a_breaker_may_answer():
    dep = tiny_breaker()
    assert dep.family is breaker and dep.guarded_ranks == 6
    assert len(list(dep.degrade_rules())) == 6 * 6 + 2 * 16
    assert len(list(dep.rules())) == 2000
    g = lambda ns, rank: int(dep.flow_id(ns, rank))  # noqa: E731
    assert dep.is_guarded([g(1, 0), g(1, 5), g(1, 6), g(6, 0),
                           flow.PROBE_BASE]).tolist() == [
                               True, True, False, False, False]
    m, n_g = dep.n_metered_keys, dep.n_guarded_keys
    assert (m, n_g) == (16, 48) and len(dep.ledger_counts()) == m + 2 * n_g
    # flow A (rank 0, metered) shed, B (rank 1, metered) through, C (rank 2)
    # through and BLOCKED-never, an unguarded row shed, an unmetered row
    # BLOCKED, a row the guard refused
    a, b, c, u = g(1, 0), g(1, 1), g(1, 2), g(1, 40)
    ids = np.array([a, a, b, b, c, u, u, a], np.int64)
    st = np.array([DEGRADED, DEGRADED, OK, BLOCKED, OK, DEGRADED, BLOCKED,
                   TOO_MANY], np.uint8)
    rem = np.array([2000, 700, 5, 0, 5, 20, 0, 0], np.int64)
    cols = (ids, np.array([1, 1, 3, 1, 2, 1, 1, 1], np.int32)) + (None,) * 5
    decided, brown, never, keys, tokens = dep.ledger_view(cols, st, rem)
    assert decided.all() and not brown.any()
    assert dep.window_checks({"never_rows": never})[:2] == [
        ("unmetered rows BLOCKED", 1, 0), ("unguarded rows DEGRADED", 1, 0)]
    slot = lambda f: int(dep.guarded_index([f])[0])  # noqa: E731
    assert keys.tolist() == [
        int(dep.metered_index([b])[0]),  # B's 3 tokens admitted
        m + slot(b), m + slot(b), m + slot(c),  # rows through
        m + n_g + slot(a), m + n_g + slot(a)]  # rows DEGRADED
    assert tokens.tolist() == [3, 1, 1, 1, 1, 1]
    # a sound frame reads nothing
    assert dep.ledger_view(cols, np.where(ids == u, OK, st).astype(np.uint8),
                           np.where(ids == u, 5, rem))[2] == 0


def test_rows_through_an_open_breaker_are_seen_in_the_ledger():
    dep = tiny_breaker()
    n_g, bins = dep.n_guarded_keys, 80
    through, shed = np.zeros((n_g, bins)), np.zeros((n_g, bins))
    quick = np.full(bins, 0.005)
    # slot 3: through until bin 9, DEGRADED for 2 s from bin 10, the probe
    # and everything after it through again: sound
    through[3, :10] = 4
    shed[3, 10:30] = 4
    through[3, 30:] = 4
    # slot 4, the same with a rollback: the probe's bin holds one row through
    # and the span goes on
    through[4, :10] = 4
    shed[4, 10:50] = 4
    through[4, 30] = 1
    assert dep.open_span_breaches(through, shed, quick) == 0
    # a row through in the middle of slot 3's span is not
    through[3, 20] = 1
    assert dep.open_span_breaches(through, shed, quick) == 1
    # ... unless the replies around it were slower than the span has room
    assert dep.open_span_breaches(through, shed, np.full(bins, 0.9)) == 0
    through[3, 20] = 0
    # the two bins at either end belong to the skew of reply times
    through[3, 11], through[3, 28] = 2, 2
    assert dep.open_span_breaches(through, shed, quick) == 0
    through[3, 12] = 1
    assert dep.open_span_breaches(through, shed, quick) == 1
    # a span that was open when the window began is not judged
    through[:], shed[:] = 0, 0
    shed[5, :12], through[5, 12:] = 4, 4
    assert dep.open_span_breaches(through, shed, quick) == 0
    # the parity shares: slots of even rank 1 in 4 shed, of odd rank 3 in 4
    odd = (np.arange(n_g) % 6) % 2 == 1
    through[:], shed[:] = 0, 0
    through[~odd, 0], shed[~odd, 0] = 3, 1
    through[odd, 0], shed[odd, 0] = 1, 3
    assert dep.parity_shares(through, shed) == {"even": 0.25, "odd": 0.75}
    breaker._RUN["health"] = {"expect": {
        "degraded_share": {"even": 0.3, "odd": 0.6}, "tolerance": 0.12}}
    adm = np.concatenate([np.zeros((dep.n_metered_keys, bins)), through, shed])
    checks = dep.window_checks({"never_rows": 0, "admitted": adm,
                                "lat_max": quick})
    assert [(got, limit) for _w, got, limit in checks[2:]] == [
        (0, 0), (50, 120), (150, 120)]


@pytest.mark.parametrize("stuck, caught", [
    (None, ()), ("never trips", (2, 3)), ("never recovers", (2, 3)),
    ("an odd one closes at every first probe", (3,))])
def test_one_stuck_breaker_of_the_hottest_tenant_is_seen(stuck, caught):
    """The parities' sums hide one breaker among hundreds; the hottest
    tenant's guarded flows are held against their band, one check a flow."""
    dep = tiny_breaker()
    tr = deploy.load_json(os.path.join(
        BENCH, "traffic", "tenants-zipf-health-cycle-open.json"))
    n_g, g, bins = dep.n_guarded_keys, dep.guarded_ranks, 120
    odd = (np.arange(n_g) % g) % 2 == 1
    through, shed = np.zeros((n_g, bins)), np.zeros((n_g, bins))
    through[~odd, 0], shed[~odd, 0] = 60, 40  # a sound window's shares
    through[odd, 0], shed[odd, 0] = 20, 80
    hot = int(dep.traffic_namespaces()[0]) * g
    for slot in (hot + 2, hot + 3):  # one even flow, one odd
        if stuck == "never trips":
            through[slot, 0], shed[slot, 0] = 100, 0
        elif stuck == "never recovers":
            through[slot, 0], shed[slot, 0] = 0, 100
        elif stuck and slot == hot + 3:  # an odd flow reading as an even one
            through[slot, 0], shed[slot, 0] = 55, 45
    bands = tr["health"]["expect"]["hottest_flows"]  # the cell's own
    breaker._RUN["health"] = {"period_s": 5, "expect": {
        "degraded_share": {"even": 0.4, "odd": 0.8}, "tolerance": 0.12,
        "hottest_flows": bands}}
    adm = np.concatenate([np.zeros((dep.n_metered_keys, bins)), through, shed])
    client = {"never_rows": 0, "admitted": adm, "lat_max": np.full(bins, 0.005)}
    checks = dep.window_checks(client)
    by_rank = {rank: (got, limit) for rank in range(g)
               for what, got, limit in checks
               if f"guarded flow of rank {rank} off" in what}
    assert sorted(by_rank) == list(range(g))
    assert {r for r, (got, limit) in by_rank.items() if got > limit} == set(
        caught)
    assert by_rank[0] == (0, 200) and by_rank[1] == (65, 235)
    # the sums over a parity do not see it
    assert all(got <= limit for what, got, limit in checks
               if "guarded ranks off the script" in what)
    # a window shorter than two periods holds no flow to its band
    short = dict(client, admitted=adm[:, :90])
    assert not [w for w, _g, _l in dep.window_checks(short) if "hottest" in w]
    assert dep.hottest_flow_shares(through * 0, shed * 0).tolist() == [2.0] * g


# -- the mix and its health script --------------------------------------------------
def test_the_mix_draws_the_flow_familys_rows_and_their_completions():
    dep, tr = tiny_breaker(), tiny_mix()
    who = breaker.Mix(tr, dep, 5, 0).frame_tenants(600)
    mix = breaker.Mix(tr, dep, 5, 1)
    ids, acq, rt, exc, told_ids, told_rt, told_exc = mix.rows(who)
    plain = flow.Mix(tr, dep, 5, 1).rows(who)
    assert (ids == plain[0]).all() and (acq == plain[1]).all()
    assert rt.dtype == np.int32 and exc.dtype == np.uint8
    guarded = dep.is_guarded(ids)
    assert 0.2 < guarded.mean() < 0.7
    assert ((rt >= 0) == guarded).all() and not exc[~guarded].any()
    # a frame's report holds the previous frame of its connection (2 here)
    assert (told_rt[:2] == -1).all()
    assert (told_ids[2:] == ids[:-2]).all() and (told_rt[2:] == rt[:-2]).all()
    assert (told_exc[2:] == exc[:-2]).all()
    # frame k is due k * 64 / 6400 s in; the script says who is sick then
    due = np.arange(600)[:, None] * 0.01
    slots = np.where(guarded, dep.guarded_index(ids), 0)
    sick = guarded & mix.script.sick(slots, due)
    slow = dep.strategy_of(ids) == breaker_reference.SLOW_REQUEST_RATIO
    assert 0.85 < (rt[sick & slow] >= 80).mean() < 0.95
    assert rt[sick & slow].max() <= 400
    assert 0.74 < exc[sick & ~slow].mean() < 0.86
    well = guarded & ~sick
    assert rt[well].max() <= 30 and rt[well].min() >= 5
    assert not exc[well].any() and not exc[sick & slow].any()
    assert rt[sick & ~slow].max() <= 30
    # an even rank is sick 1 s in 5, an odd one 3 s
    rank = ids // dep.namespaces
    assert 0.15 < sick[guarded & (rank % 2 == 0)].mean() < 0.25
    assert 0.55 < sick[guarded & (rank % 2 == 1)].mean() < 0.65
    # the script is the file's: every seed's and every process's alike
    other = breaker.Mix(tr, dep, 6, 1)
    assert (other.script.phase == mix.script.phase).all()
    assert (other.rows(who)[0] != ids).any()
    # the warm-up's frames follow the script from their own frame 0, a
    # burst's and a backlog's report healthy completions only
    n_warm = len(traffic.open_schedule(tr, tr["warm_seconds"]))
    assert mix.warm_frames == n_warm == 150
    warm = breaker.Mix(tr, dep, 5, 1).frames(n_warm)
    assert warm[2].max() > 30 or warm[3].any()
    burst = breaker.Mix(tr, dep, 5, 1).frames(64)
    assert burst[2].max() <= 30 and not burst[3].any()
    assert (burst[5][2:] == burst[2][:-2]).all()
    quiet = {k: v for k, v in tr.items() if k != "health"}
    assert (breaker.Mix(quiet, dep, 5, 1).rows(who)[2] == -1).all()


def test_the_phases_level_the_sick_share_of_every_slot():
    """Tentpole 3: in every 250 ms slot of the schedule the traffic-weighted
    share of sick dependencies is the same to within a tenth of itself."""
    dep, tr = real_cell()
    script = breaker.Mix(tr, dep, 1, 1).script
    assert len(script.phase) == 64 * 12
    at = np.arange(0.0, 5.0, 0.25) + 0.125
    share = np.array([script.sick_weight(t) for t in at])
    assert 0.3 < share.mean() < 0.45
    assert np.abs(share / share.mean() - 1).max() < 0.10
    # the hottest tenant's twelve are spread over the period
    hottest = int(dep.traffic_namespaces()[0])
    phases = script.phase[hottest * 12:hottest * 12 + 12]
    assert len(set(phases.tolist())) >= 6
    # the probe's namespaces carry no script, and the guarded ones' weights
    # are the mix's own: the hottest tenant's twelve hold a fifth of it
    assert script.weights[62 * 12:].sum() == 0
    assert 0.19 < script.weights[hottest * 12:hottest * 12 + 12].sum() / (
        script.weights.sum()) < 0.23


def simulate(dep, tr, seed: int, seconds: float, start_s: float = 0.0,
             ref=None, t_base: int = 100_000):
    """The script and the plain reference over one schedule: frame ``k``'s
    report is ingested and its guarded rows decided at its due time (rows of
    unguarded flows meet no breaker and are left out). Returns ``(due,
    DEGRADED rows a frame, completions told a frame, reference, rows through
    and rows DEGRADED by guarded slot)``."""
    tr = dict(tr, health=dict(tr["health"], start_s=start_s))
    due = traffic.open_schedule(tr, seconds)
    who = breaker.Mix(tr, dep, seed, 0).frame_tenants(len(due))
    ids, acq, _rt, _exc, told_ids, told_rt, told_exc = breaker.Mix(
        tr, dep, seed, 1).rows(who)
    if ref is None:
        ref = breaker_reference.for_deployment(
            dep, only={int(f) for f, _ns, _kw in dep.degrade_rules()})
    guarded = dep.is_guarded(ids)
    shed, told = np.zeros(len(due)), np.zeros(len(due))
    by_slot = np.zeros((2, dep.n_guarded_keys, 1))  # through, DEGRADED
    for k in range(len(due)):
        t = t_base + int(round(due[k] * 1000))
        done = told_rt[k] >= 0
        told[k] = done.sum()
        if told[k]:
            ref.report(t, told_ids[k][done], told_rt[k][done],
                       told_exc[k][done])
        at = np.flatnonzero(guarded[k])
        status, _rest = ref.decide_frame(t, ids[k][at], acq[k][at])
        refused = np.asarray(status) == DEGRADED
        shed[k] = refused.sum()
        np.add.at(by_slot[:, :, 0], (refused.astype(int),
                                     dep.guarded_index(ids[k][at])), 1)
    return due, shed, told, ref, by_slot[0], by_slot[1]


@pytest.fixture(scope="module")
def nine_windows():
    """6(a): three seeds and three places of the window's start inside a
    period, each a 20 s schedule of the benchmark's own cell on a cold
    table; and one window behind a warm-up, as a run has it."""
    dep, tr = real_cell()
    runs = {}
    for seed in (11, 12, 13):
        for start in (0.0, 1.75, 3.5):
            runs[seed, start] = simulate(dep, tr, seed, 20.0, start)[:3]
    ref = simulate(dep, tr, 11, float(tr["warm_seconds"]))[3]
    warm = simulate(dep, tr, 11, 20.0, 0.0, ref=ref, t_base=108_300)[:3]
    return dep, tr, runs, warm


def test_every_run_is_the_same_run(nine_windows):
    """What the script holds level by construction, and how level. A frame
    is one tenant's (cell 1's mix), so a flow's rows come in bursts of its
    tenant's frames at times the seed permutes: the sums are level, a
    second's DEGRADED share swings with which tenants sent in it."""
    dep, tr, runs, warm = nine_windows
    rows = int(tr["frame_rows"])
    totals, told_totals = [], []
    for (seed, start), (due, shed, told) in runs.items():
        second = (due // 1).astype(int)
        per_s = np.bincount(second, shed) / (np.bincount(second) * rows)
        told_s = np.bincount(second, told)
        # completions a second: level to a tenth from the second second on
        # (the first frames of a connection have nothing to tell)
        assert np.abs(told_s[1:] / told_s[1:].mean() - 1).max() < 0.10
        # the DEGRADED share of a second, once a cold table has cycled
        steady = per_s[5:]
        assert np.abs(steady / steady.mean() - 1).max() < 0.35, (seed, start)
        totals.append(shed[due >= 5.0].sum() / (rows * (due >= 5.0).sum()))
        told_totals.append(told.sum())
    totals = np.array(totals)
    assert 0.12 < totals.mean() < 0.32  # the cell's band for the share
    assert (totals.max() - totals.min()) / totals.mean() < 0.10
    assert (max(told_totals) - min(told_totals)) / np.mean(told_totals) < 0.02
    # a window behind a warm-up against the same seed's on a cold table:
    # what the breakers carry over is gone within a cycle
    due, shed, _told = warm
    cold = runs[11, 0.0][1]
    late = due >= 5.0
    assert abs(shed[late].sum() / cold[late].sum() - 1) < 0.05
    assert shed[~late].sum() >= cold[~late].sum()


def test_the_files_expected_shares_are_the_references(nine_windows):
    """``health.expect`` of the traffic file is what the script and the
    reference give, by parity of rank, over a window behind a warm-up."""
    dep, tr, _runs, _warm = nine_windows
    expect = tr["health"]["expect"]
    ref = simulate(dep, tr, 12, float(tr["warm_seconds"]))[3]
    before = dict(ref.moves)
    _d, _s, _t, ref, through, shed = simulate(dep, tr, 12, 20.0, ref=ref,
                                              t_base=108_300)
    got = dep.parity_shares(through, shed)
    for parity in ("even", "odd"):
        assert abs(got[parity] - expect["degraded_share"][parity]) < 0.04, got
    assert expect["tolerance"] == 0.12
    # the hottest tenant's flows, one by one, lie well inside their bands
    mine = dep.hottest_flow_shares(through, shed)
    for parity, shares in (("even", mine[0::2]), ("odd", mine[1::2])):
        lo, hi = expect["hottest_flows"][parity]
        room = (hi - lo) / 8
        assert lo + room < shares.min() and shares.max() < hi - room, mine
    moved = {k: ref.moves[k] - before[k] for k in before}
    assert moved["probe"] >= moved["close"] + moved["rollback"] > 0
    assert 100 < sum(moved.values()) / 20.0 < 300  # transitions a second


# -- the readers and the roofline -----------------------------------------------------
def _snap(before: dict, after: dict, events=(), modules=()):
    dep = tiny_breaker()
    return {"before": {"stages": before, "t": 10.0},
            "after": {"stages": after, "t": 30.0},
            "events": list(events), "trace": {"modules": list(modules)},
            "config": dep.spec, "slice_s": 3.0, "seconds": 20.0,
            "device_kind": "TPU v5 lite",
            "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}


def test_the_readers_read_the_programs_counters_or_nothing(tmp_path):
    from cellbench import manifest as mf

    r = mf.Cell(breaker_manifest(str(tmp_path)), CELL).readers()
    assert all(n in r for n in READERS)
    # a parent's snapshot: nothing to read, and nothing raised
    assert [r[n].reduce(_snap({"decide_ms": {"count": 1}},
                              {"decide_ms": {"count": 9}}))
            for n in READERS] == [None] * len(READERS)
    hist = lambda c, s: {"count": c, "sum": s}  # noqa: E731
    before = {"decide_dispatch_total": 10, "decide_rows_total": 1000,
              "decide_breaker_live_total": 5,
              "decide_degraded_rows_total": 100, "outcome_steps_total": 4,
              "outcome_step_rows_total": 400, "outcome_frames_total": 5,
              "breaker_to_open_total": 10, "breaker_probe_tickets_total": 10,
              "breaker_to_closed_total": 6, "breaker_reopened_total": 4,
              "outcome_lock_wait_ms": hist(5, 1.0),
              "outcome_launch_ms": hist(5, 4.0)}
    after = {"decide_dispatch_total": 30, "decide_rows_total": 21000,
             "decide_breaker_live_total": 24,
             "decide_degraded_rows_total": 4100, "outcome_steps_total": 24,
             "outcome_step_rows_total": 8400, "outcome_frames_total": 30,
             "breaker_to_open_total": 110, "breaker_probe_tickets_total": 130,
             "breaker_to_closed_total": 86, "breaker_reopened_total": 44,
             "outcome_lock_wait_ms": hist(30, 6.0),
             "outcome_launch_ms": hist(30, 24.0)}
    ev = lambda stage, t, shard, aux=0: {  # noqa: E731
        "stage": stage, "t_ns": t, "shard": shard, "aux": aux,
        "thread": "control", "xid": 0}
    events = [ev("outcome_in", 1_000_000, 1, 400),
              ev("outcome_in", 2_000_000, 1, 30),
              ev("outcome", 3_000_000, 1, 430),
              ev("outcome_in", 5_000_000, 2, 64), ev("outcome", 5_500_000, 2, 64),
              ev("outcome_in", 9_000_000, 3, 7),  # its step is not in the slice
              ev("outcome", 9_900_000, 4, 0)]  # a report of no valid row
    snap = _snap(before, after, events,
                 [("jit_decide_b1024_mixed(3)", 0.5),
                  ("jit_outcome_step(7)", 0.0008)])
    got = {n: r[n].reduce(snap) for n in READERS}
    assert got["service.degraded_share"] == 20.0
    assert got["step.breaker_arm_live_share"] == 95.0
    assert got["lane.outcome_rows_per_step"] == 400.0
    assert got["service.outcome_ingest_avg_ms"] == pytest.approx(1.0)
    assert got["service.outcome_age_p95_ms"] == pytest.approx(1.9)
    assert got["step.outcome_device_ms_per_step"] == pytest.approx(0.4)
    # 100 trips, 120 tickets, 80 closes and 40 rollbacks in a 20 s window
    assert got["service.breaker_transitions_per_s"] == pytest.approx(17.0)
    least = outcome_roofline.least_seconds([430, 64], 3.0, snap["config"],
                                           snap["peaks"]["TPU v5 lite"])
    assert got["outcome_step_roofline"] == pytest.approx(
        100 * least / 0.0008)
    assert 0 < got["outcome_step_roofline"] < 100


def test_the_outcome_steps_bytes_come_from_the_shapes():
    engine = {"max_flows": 1000, "n_buckets": 10, "bucket_ms": 100}
    assert [outcome_roofline.rung_of(k) for k in (1, 64, 65, 1024, 1025)] == [
        64, 64, 256, 1024, 4096]
    # 430 rows at the rung 1024: 13 bytes a slot of the rung and the clock,
    # the tally, 5 cells read and written a row, 19 bytes of breaker columns
    # a row, the bucket starts read and written
    assert outcome_roofline.ingest_bytes(430, engine) == (
        1024 * 13 + 4 + 8 + 430 * 5 * 8 + 430 * 19 + 80)
    assert outcome_roofline.ingest_bytes(430, engine, breakers=False) == (
        1024 * 13 + 4 + 8 + 430 * 4 * 8 + 80)
    assert outcome_roofline.rolled_bucket_bytes(engine) == 1000 * 16 * 4
    cfg = {"engine": engine, "rules": {"degrade": {}}}
    assert outcome_roofline.least_seconds(
        [430], 1.0, cfg, {"hbm_bytes_per_s": 1e9}) == pytest.approx(
            (outcome_roofline.ingest_bytes(430, engine) + 10 * 64000) / 1e9)
    from sentinel_tpu.engine.state import N_OUTCOME_CHANNELS

    assert outcome_roofline.CHANNELS == N_OUTCOME_CHANNELS


def test_the_manifest_holds_the_cell_as_the_issue_names_it():
    from cellbench import manifest as mf

    cell = mf.Cell(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                   REAL_CELL)
    assert cell.chips == 1 and cell.traffic["warm_seconds"] == 6
    one = deploy.load_json(os.path.join(BENCH, "traffic",
                                        "tenants-zipf-open.json"))
    for key in ("loop", "msg", "frame_rows", "processes", "connections",
                "inflight_window_frames", "tenants", "flows", "acquire",
                "timeout_ms", "trace_sample"):
        assert cell.traffic[key] == one[key], key
    h = cell.traffic["health"]
    assert (h["period_s"], h["sick_s"]) == (5, {"even": 1, "odd": 3})
    assert int(cell.traffic["rate_rows_per_s"]) % 10_000 == 0
    names = {m["name"] for m in cell.per_layer()}
    assert set(READERS) <= names
    assert {"client.send_lag_p99_ms",
            "step.decide_device_ms_per_dispatch"} <= names
    dep, _tr = real_cell()
    mesh = deploy.load_json(os.path.join(BENCH, "configs", "mesh-100k.json"))
    for key in ("engine", "ns_max_qps", "serve_buckets", "fuse_depths",
                "door", "mesh_chips"):
        assert dep.spec[key] == mesh[key], key
    for key in ("n_flows", "namespaces", "unmetered_count", "metered_counts",
                "probe_namespaces"):
        assert dep.spec["rules"][key] == mesh["rules"][key], key
    assert dep.spec["reduced"] == ["pod_chips", "recovery_timeout_ms"]
    assert len([1 for f, _n, _k in dep.degrade_rules()
                if f < flow.PROBE_BASE]) == 744
    assert dep.degrade == {
        "slow_rt_ms": 50, "slow_ratio_threshold": 0.6,
        "error_ratio_threshold": 0.5, "error_count_threshold": 4,
        "stat_interval_ms": 1000, "min_request_amount": 5,
        "recovery_timeout_ms": 2000}


# -- reports of admitted calls only: the session ------------------------------------
INGEST_SETTLE_S = 5.0  # a report is ingested well inside this


def ingested_rows() -> int:
    """Completion rows the outcome steps of this process's server took."""
    from sentinel_tpu.metrics.server import server_metrics

    return int(server_metrics().arm_totals()["outcome_step_rows_total"])


def reports_of(raw: bytes) -> list:
    """The rows of every OUTCOME_REPORT frame in one send."""
    out = []
    while raw:
        flen = struct.unpack_from(">H", raw, 0)[0]
        if raw[6] == breaker.OUTCOME_REPORT:
            n = struct.unpack_from(">H", raw, 7)[0]
            out.append(np.frombuffer(raw[9:2 + flen], breaker.OUTCOME_ROW, n))
        raw = raw[2 + flen:]
    return out


def test_the_session_is_built_for_a_file_that_asks_for_it_and_no_other():
    dep = tiny_breaker()
    assert breaker.Session(tiny_mix(), dep, 1, 0, 2) is None
    got = breaker.Session(tiny_mix("tiny-health-cycle-admitted-open"), dep,
                          1, 0, 2)
    assert isinstance(got, breaker.AdmittedReports)
    with pytest.raises(ValueError, match="reports"):
        breaker.Session(dict(tiny_mix(), reports="some"), dep, 1, 0, 2)
    # the benchmark's admitted mix is cell 7's file with that key
    mine = deploy.load_json(os.path.join(
        BENCH, "traffic", "tenants-zipf-health-cycle-admitted-open.json"))
    theirs = real_cell()[1]
    assert mine.pop("reports") == "admitted"
    assert mine.pop("name") == theirs.pop("name").replace("-open",
                                                         "-admitted-open")
    assert mine == theirs
    assert json.dumps(tiny_mix("tiny-health-cycle-admitted-open")).replace(
        '"reports": "admitted", ', "").replace("admitted-", "") == json.dumps(
            tiny_mix())


def test_a_report_holds_the_completions_of_the_rows_let_through():
    ses = breaker.AdmittedReports(2)
    ids = np.array([8, 16, 24, 7001, 32, 40], np.int64)
    acq = np.ones(6, np.int32)
    rt = np.array([5, 90, 7, -1, 9, 11], np.int32)  # 7001 is not guarded
    exc = np.array([0, 0, 1, 0, 0, 1], np.uint8)
    told = (np.zeros(6, np.int64), np.full(6, 55, np.int32),
            np.ones(6, np.uint8))  # the frame before, verdicts unseen: unread
    cols = (ids, acq, rt, exc) + told
    first = ses.encode(0, 100, *cols)
    assert first == wire.encode_batch(100, ids, acq)  # nothing to tell yet
    rsp = np.zeros(6, wire.RSP_ROW)
    rsp["status"] = [OK, DEGRADED, SHOULD_WAIT, OK, BLOCKED, OK]
    ses.back(0, 100, cols, rsp, 1.0)
    ses.back(0, 101, cols, rsp[:2], 1.1)  # a short reply: the rows that came
    assert ses.encode(1, 200, *cols) == first.replace(  # another connection
        struct.pack(">i", 100), struct.pack(">i", 200), 1)
    raw = ses.encode(0, 102, *cols)
    (rep,) = reports_of(raw)
    assert rep["flow_id"].tolist() == [8, 24, 40, 8]
    assert rep["rt_ms"].tolist() == [5, 7, 11, 5]
    assert rep["exc"].tolist() == [0, 1, 1, 0]
    assert struct.unpack_from(">i", raw, 2)[0] == breaker.report_xid(102)
    assert raw.endswith(wire.encode_batch(102, ids, acq))
    assert ses.encode(0, 103, *cols) == wire.encode_batch(103, ids, acq)


def test_more_completions_than_a_frame_holds_go_out_as_several_reports():
    ses = breaker.AdmittedReports(1)
    n = breaker.MAX_ROWS_PER_FRAME
    ids = np.arange(n + 10, dtype=np.int64)
    cols = (ids, np.ones(len(ids), np.int32),
            np.full(len(ids), 5, np.int32), np.zeros(len(ids), np.uint8))
    rsp = np.zeros(len(ids), wire.RSP_ROW)
    ses.back(0, 1, cols, rsp, 0.0)
    raw = ses.encode(0, 2, ids[:4], np.ones(4, np.int32))
    a, b = reports_of(raw)
    assert len(a) == n and len(b) == 10
    assert np.concatenate([a["flow_id"], b["flow_id"]]).tolist() == ids.tolist()
    assert raw.endswith(wire.encode_batch(2, ids[:4], np.ones(4, np.int32)))
    assert len(raw) == 2 * 9 + 13 * len(ids) + 9 + 13 * 4  # three whole frames


class EveryRow(breaker.AdmittedReports):
    """The control: today's reports through a session. Every row on a
    guarded flow completes, whatever it was answered."""

    def back(self, ci, xid, cols, reply_rows, t):
        passed = reply_rows.copy()
        passed["status"] = OK
        super().back(ci, xid, cols, passed, t)


@pytest.fixture(scope="module")
def tiny_server():
    """The tiny breaker deployment behind the native door, in this process."""
    import jax

    from cellbench import server

    kept = dict(breaker._RUN)
    dep = tiny_breaker()
    built = server.build(dep, jax.devices()[:1], lambda msg: None)
    try:
        # as a run does: the fused depths a backlog reaches compile now
        breaker.drive_before_window(
            built, tiny_mix("tiny-health-cycle-admitted-open"), dep, 31, [],
            lambda msg: None)
        yield dep, built
    finally:
        built.close()
        breaker._RUN.clear()
        breaker._RUN.update(kept)


def drive_admitted(dep, built, tmp_path, monkeypatch, session_class,
                   seconds: float = 4.0):
    """A warm-up and a window of the tiny admitted mix from a generator in
    this process, its session a ``session_class`` that is watched: ``(the
    window's summary, completion rows the service ingested, rows in the
    reports sent, rows on guarded flows answered OK or SHOULD_WAIT, of any
    answer, rows the session still kept at the end)``."""
    seen = {"let": 0, "guarded": 0, "told": 0}
    lock = threading.Lock()

    class Watched(session_class):
        def back(self, ci, xid, cols, reply_rows, t):
            st = reply_rows["status"]
            guarded = dep.is_guarded(cols[0][:len(st)])
            with lock:
                seen["let"] += int((guarded & ((st == OK)
                                               | (st == SHOULD_WAIT))).sum())
                seen["guarded"] += int(guarded.sum())
            super().back(ci, xid, cols, reply_rows, t)

        def encode(self, ci, xid, *cols):
            raw = super().encode(ci, xid, *cols)
            with lock:
                seen["told"] += sum(len(r) for r in reports_of(raw))
            return raw

    monkeypatch.setattr(breaker, "AdmittedReports", Watched)
    gen = loadgen.Generator({
        "traffic": tiny_mix("tiny-health-cycle-admitted-open"), "seed": 31,
        "proc": 0, "seconds": seconds, "warm_seconds": 1.5,
        "port_file": str(tmp_path / "port"), "family_dirs": [BENCH],
        "config_file": os.path.join(EXTRA, "configs", "tiny-breaker.json")})
    assert isinstance(gen.session, Watched)
    before = ingested_rows()
    gen.connect(built.server.port)
    try:
        gen.cmd_warm()
        summary = gen.cmd_measure(time.monotonic() + 0.05, seconds,
                                  str(tmp_path / "r.npz"))
    finally:
        for c in gen.conns:
            c.close()
    kept = sum(len(ids) for conn in gen.session.done for ids, _rt, _e in conn)
    deadline = time.monotonic() + INGEST_SETTLE_S
    while ingested_rows() - before < seen["told"] and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)  # nothing more may come
    return summary, ingested_rows() - before, seen["told"], seen["let"], seen[
        "guarded"], kept


def test_the_service_ingests_the_rows_let_through_and_no_other(
        tiny_server, tmp_path, monkeypatch):
    dep, built = tiny_server
    s, ingested, told, let, guarded, kept = drive_admitted(
        dep, built, tmp_path, monkeypatch, breaker.AdmittedReports)
    assert s["decided"] > 0.9 * s["attempted"] and s["status_hist"][DEGRADED]
    assert 0 < let < guarded  # breakers tripped: some calls were refused
    # exactly: on the wire what was let through (less what no later frame
    # carried), in the service what was on the wire
    assert told == let - kept and ingested == told


def test_a_session_that_reports_every_row_is_caught(tiny_server, tmp_path,
                                                    monkeypatch):
    dep, built = tiny_server
    s, ingested, told, let, guarded, kept = drive_admitted(
        dep, built, tmp_path, monkeypatch, EveryRow)
    assert s["decided"] > 0.9 * s["attempted"] and 0 < let < guarded
    assert ingested == told == guarded - kept
    assert ingested != let - kept and ingested - (let - kept) == guarded - let


def test_the_admitted_cell_runs_through_the_harness(tmp_path):
    """The generator is a child process here, as on the chip: the plan
    names the file, the file asks for the session."""
    lines = []
    result = run.run_cell(breaker_manifest(str(tmp_path)), ADMITTED_CELL,
                          seed=2_147_483_743, seconds=6.0, trace=0,
                          require_chip=False, out=lines.append)
    assert result["correct"] is True and result["failed"] == 0, (
        result["compared"], [ln for ln in lines if "window " in ln])
    assert result["attempted"] == 600 * 64
    assert "DEGRADED 0;" not in [ln for ln in lines if "status OK" in ln][0]
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)


def test_the_reference_replays_a_run_of_admitted_reports(tmp_path):
    """The tiny admitted mix through the native door a frame at a time on a
    clock this test moves (frame ``k`` at its due time), the session between
    each reply and the next frame; then the plain reference over the same
    frames, told what upstream's client would tell it: the completions of
    the rows let through, worked out here from the verdicts. A frame's
    report and its request travel different lanes with no order between
    them, so a frame is held to the reference under either order. Compared:
    which rows are DEGRADED and every retry-after, and which rows the
    namespace guard refused; OK against BLOCKED on a metered flow is the
    probe's ``tight`` to hold, one acquire size a flow (a frame of mixed
    sizes on one flow is not admitted greedily by the program)."""
    import copy

    import jax

    from cellbench import server
    from sentinel_tpu.core import clock as clock_mod

    dep = tiny_breaker()
    tr = tiny_mix("tiny-health-cycle-admitted-open")
    gen = loadgen.Generator({
        "traffic": tr, "seed": 47, "proc": 0, "seconds": 8.0,
        "warm_seconds": 1.5, "port_file": str(tmp_path / "port"),
        "family_dirs": [BENCH],
        "config_file": os.path.join(EXTRA, "configs", "tiny-breaker.json")})
    due, cols = gen.main
    ses, n_conn = gen.session, int(tr["connections"])
    kept = dict(breaker._RUN)
    mc = clock_mod.ManualClock(1_700_000_000_000)
    prev = clock_mod.set_clock(mc)
    built = socks = None
    frames = []  # (t_ms, the frame's columns, status, remaining)
    try:
        built = server.build(dep, jax.devices()[:1], lambda msg: None)
        socks = [socket.create_connection(("127.0.0.1", built.server.port))
                 for _ in range(n_conn)]
        splits = [wire.Splitter(breaker.SINGLE_REPLIES, breaker.BATCH_REPLIES)
                  for _ in socks]
        base, told = ingested_rows(), 0
        t0 = mc.now_ms() + 1000
        for k in range(len(due)):
            deadline = time.monotonic() + INGEST_SETTLE_S
            while ingested_rows() - base < told:  # the last report is in
                assert time.monotonic() < deadline, "a report was not ingested"
                time.sleep(0.001)
            mc.set_ms(t0 + int(round(due[k] * 1000)))
            ci, frame = k % n_conn, [col[k] for col in cols]
            raw = ses.encode(ci, 1000 + k, *frame)
            told += sum(len(r) for r in reports_of(raw))
            socks[ci].sendall(raw)
            got = []
            while not got:
                got = splits[ci].feed(socks[ci].recv(1 << 16))[0]
            (xid, rows), = got
            assert xid == 1000 + k and len(rows) == len(frame[0])
            ses.back(ci, xid, frame, rows, time.monotonic())
            frames.append((mc.now_ms() - built.service._epoch_ms, frame,
                           rows["status"].copy(), rows["remaining"].copy()))
    finally:
        for sock in socks or ():
            sock.close()
        if built is not None:
            built.close()
        clock_mod.set_clock(prev)
        breaker._RUN.clear()
        breaker._RUN.update(kept)
    ref = breaker_reference.for_deployment(dep)
    waiting = [[] for _ in range(n_conn)]  # completions a connection owes
    mismatches = degraded = reports_first = 0
    differ = []  # (frame, flow, served, wanted, retry served, retry wanted)
    for k, (t, frame, status, remaining) in enumerate(frames):
        ids, acq, rt, exc = frame[:4]
        owed, waiting[k % n_conn] = waiting[k % n_conn], []
        best = None
        for report_first in (True, False):
            trial = copy.deepcopy(ref)
            if report_first:
                for done in owed:
                    trial.report(t, *done)
            want, rest = trial.decide_frame(t, ids, acq)
            if not report_first:
                for done in owed:
                    trial.report(t, *done)
            want, rest = np.asarray(want), np.asarray(rest)
            bad = np.flatnonzero(
                ((status == DEGRADED) != (want == DEGRADED))
                | ((status == TOO_MANY) != (want == TOO_MANY))
                | ((want == DEGRADED) & (remaining != rest)))
            if best is None or len(bad) < best[0]:
                best = (len(bad), trial, report_first,
                        [(k, int(ids[i]), int(status[i]), int(want[i]),
                          int(remaining[i]), int(rest[i])) for i in bad])
            if not len(bad):
                break
        mismatches += best[0]
        differ += best[3]
        ref = best[1]
        reports_first += best[2] and bool(owed)
        degraded += int((status == DEGRADED).sum())
        let = (rt >= 0) & ((status == OK) | (status == SHOULD_WAIT))
        if let.any():
            waiting[k % n_conn].append((ids[let], rt[let], exc[let]))
    assert mismatches == 0, differ[:20]
    assert degraded > 200 and ref.moves["open"] >= 8
    assert ref.moves["close"] > 0 and ref.moves["rollback"] > 0
    assert ref.reported == told
