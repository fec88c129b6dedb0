"""The concurrency family (``cellbench/families/concurrent.py``) at a tiny
size on the CPU: one cell end to end through the native door with the session
live (tokens acquired, held, given back, some never), the probe's eight
checks, each control caught by the check named for it; the plain reference in
the program's place, sound and broken; the frames, the ledger's view, the
session's count of tokens in hand, the readers and the roofline."""

import json
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from cellbench import concurrent_roofline, deploy, manifest, probe, run
from cellbench.deploy import BLOCKED, NO_RULE, OK
from cellbench.families import concurrent, concurrent_reference

HERE = os.path.dirname(os.path.abspath(__file__))
EXTRA = os.path.join(HERE, "extra")
BENCH = os.path.dirname(HERE)
CELL = "tiny-concurrent.tiny-hold-open"
REAL_CELL = "concurrent-mesh-100k.tenants-zipf-hold-open"
CHECKS = concurrent.CHECKS
READERS = ("step.concurrent_device_ms_per_dispatch",
           "concurrent_step_roofline", "service.concurrent_blocked_share",
           "lane.release_rows_per_dispatch", "service.tokens_expired_per_s")


DRAINED = (
    "flows_whose_held_is_not_0_once_the_window_has_drained",
    "tokens_live_in_the_table_once_the_window_has_drained",
    "concurrent_tokens_live_once_the_window_has_drained",
    "tokens_issued_less_released_less_expired_since_the_rules_loaded")


def concurrent_manifest(tmp) -> str:
    """The tests' manifest with the tiny deployment, its cell and the new
    per-layer entries added: by entries alone, as BENCHMARK.json."""
    bench = deploy.load_json(os.path.join(HERE, "manifest.json"))
    bench["paths"] = [os.path.relpath(BENCH, tmp), os.path.relpath(EXTRA, tmp)]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(HERE, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-concurrent", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(EXTRA, "configs", "tiny-concurrent.json"), tmp),
        "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-concurrent",
        "traffic": "tiny-hold-open", "chips": 1, "why": "test"})
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


def tiny_dep() -> concurrent.Deployment:
    return deploy.load(os.path.join(EXTRA, "configs", "tiny-concurrent.json"),
                       [BENCH])


def tiny_mix() -> dict:
    return deploy.load_json(os.path.join(EXTRA, "traffic",
                                         "tiny-hold-open.json"))


@pytest.fixture(autouse=True)
def nothing_kept(monkeypatch):
    """What the family keeps of a run (the service, the counters' reading)
    does not leak from one test into the next."""
    monkeypatch.setattr(concurrent, "_RUN", dict(concurrent._RUN))


@pytest.fixture(scope="module")
def concurrent_run(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the program's counters
    before, and after)``."""
    from sentinel_tpu.metrics.server import server_metrics

    lines = []
    before = server_metrics().stage_snapshot()
    kept = dict(concurrent._RUN)
    try:
        result = run.run_cell(
            concurrent_manifest(str(tmp_path_factory.mktemp("cell"))), CELL,
            seed=2_147_483_741, seconds=4.0, trace=0, require_chip=False,
            out=lines.append)
    finally:
        concurrent._RUN.clear()
        concurrent._RUN.update(kept)
    return result, lines, before, server_metrics().stage_snapshot()


def test_the_cell_runs_through_the_door_with_the_session_live(concurrent_run):
    result, lines, before, after = concurrent_run
    assert result["correct"] is True, lines[-40:]
    # (a frame or two may fail where this machine stands still under the
    # other workers of a test run: the shed ladder's, not the mechanism's)
    assert result["failed"] <= 4 * 64, lines[-40:]
    assert result["attempted"] == 400 * 64
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)
    hist = [ln for ln in lines if "status OK" in ln][0]
    assert "BLOCKED 0 " not in hist  # the levels bit inside the window
    grew = {k: after[k] - before[k] for k in concurrent_roofline.COUNTERS}
    assert grew["concurrent_acquire_rows_total"] >= 400 * 64
    # most admitted rows were given back, through the data plane's frames
    assert grew["concurrent_release_rows_total"] > 0.5 * (
        grew["concurrent_acquire_rows_total"]
        - grew["concurrent_blocked_total"])
    assert grew["concurrent_expired_total"] > 0
    assert grew["concurrent_table_full_total"] == 0
    # (stale releases: the probe's, and tokens that expired over the pause
    # between the warm-up and the window, which outlasts this tiny timeout)
    assert grew["concurrent_already_release_total"] < 0.2 * grew[
        "concurrent_release_rows_total"]
    # no flow program ran: the mechanism does all of the device work
    assert after["decide_dispatch_total"] == before["decide_dispatch_total"]


@pytest.mark.parametrize("check", CHECKS)
def test_the_probes_checks_read_no_mismatch(concurrent_run, check):
    result, lines = concurrent_run[:2]
    assert result["compared"]["probe_" + check] == [0, 0], [
        ln for ln in lines if "probe" in ln]


def test_the_windows_replies_hold_the_guarantees(concurrent_run):
    compared = concurrent_run[0]["compared"]
    for what in ("acquire_rows_NO_RULE_or_tokens_in_hand_past_a_level",
                 "concurrent_table_full_total_over_the_window",
                 "windows_in_which_no_token_expired", "rows_answered_twice",
                 "rows_with_an_unknown_status") + DRAINED:
        assert compared[what] == [0, 0], what
    assert "admitted_over_count" not in compared  # no rate is metered here


@pytest.mark.parametrize("control, caught_by", [
    ("over_admit", "fill"), ("release_lost", "release_frees"),
    ("never_expires", "expiry")])
def test_a_broken_guarantee_is_not_correct(tmp_path, control, caught_by):
    lines = []
    result = run.run_cell(concurrent_manifest(str(tmp_path)), CELL,
                          seed=2_147_483_742, seconds=2.0, trace=0,
                          require_chip=False,
                          wrap_service=concurrent.CONTROLS[control],
                          out=lines.append)
    assert result["correct"] is False
    assert result["compared"]["probe_" + caught_by][0] >= 1, [
        ln for ln in lines if "probe" in ln]
    if control == "never_expires":  # the window sees it too
        assert result["compared"]["windows_in_which_no_token_expired"] == [
            1, 0]


def test_the_drained_state_holds_the_windows_own_dispatches():
    """What ``_drained`` reads of the program after the probe: a token left
    live on a plain flow, and a release counted for a token that expiry
    counted too, each move a check off 0 (the probe's quiet flows would show
    neither)."""
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import EngineConfig
    from sentinel_tpu.metrics.server import server_metrics

    dep = tiny_dep()
    eng = dep.spec["engine"]
    service = DefaultTokenService(
        EngineConfig(max_flows=eng["max_flows"],
                     max_namespaces=eng["max_namespaces"],
                     batch_size=eng["batch_size"]),
        serve_buckets=tuple(dep.spec["serve_buckets"]), fuse_depths=(),
        **concurrent.service_args(dep))
    try:
        concurrent.load_rules(service, dep)
        read = lambda: [got for _what, got, _limit in concurrent._drained(dep)]
        names = [what.replace(" ", "_")
                 for what, _g, _l in concurrent._drained(dep)]
        assert tuple(names) == DRAINED
        assert read() == [0, 0, 0, 0]
        status, _r, _w, tokens = service.request_concurrent_batch(
            np.array([3, 3, 11]))
        assert status.tolist() == [OK] * 3
        assert read() == [2, 3, 3, 3]  # two flows, three tokens in hand
        back = service.request_concurrent_batch(
            tokens, None, np.ones(3, bool))[0]
        assert back.tolist() == [concurrent.RELEASE_OK] * 3
        assert read() == [0, 0, 0, 0]
        # a token answered RELEASE_OK and kept: expiry counts it as well
        server_metrics().count_concurrent_step(0, 0, 0, 0, 1, 0, 0, tick=True)
        assert read() == [0, 0, 0, 1]
    finally:
        service.close()


# -- the reference in the program's place ---------------------------------------
class ReferenceDoor:
    """A token server made of the plain reference behind a plain socket, on
    the wall clock: frames of types 28 and 29 in, the reference's answers
    out, its expiry run before every frame."""

    def __init__(self, ref, lose_releases: bool = False):
        self.ref, self.lock = ref, threading.Lock()
        self.lose_releases = lose_releases
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
                with self.lock:
                    self.ref.expire(now)
                    if mtype == concurrent.RELEASE:
                        ids = np.frombuffer(body, ">i8", n)
                        status = ([concurrent.RELEASE_OK] * n
                                  if self.lose_releases
                                  else self.ref.release_frame(ids))
                        out = np.asarray(status, np.int8).tobytes()
                    else:
                        rows = np.frombuffer(body, concurrent.wire.REQ_ROW, n)
                        st, rm, tok = self.ref.acquire_frame(
                            now, rows["flow_id"], rows["count"])
                        rsp = np.zeros(n, concurrent.ACQ_ROW)
                        rsp["status"], rsp["remaining"] = st, rm
                        rsp["token_id"] = tok
                        out = rsp.tobytes()
                conn.sendall(struct.pack(">HibH", 7 + len(out), xid, mtype, n)
                             + out)


def probe_against(seed: int, level_plus: int = 0, timeout_ms=None,
                  lose_releases: bool = False):
    dep = tiny_dep()
    ref = concurrent_reference.Reference(
        {fid: level + level_plus for fid, level, _ns in dep.rules()},
        dep.resource_timeout_ms if timeout_ms is None else timeout_ms)
    door = ReferenceDoor(ref, lose_releases)
    concurrent._RUN.pop("service", None)  # no server of the program's here
    try:
        return probe.Probe(door.port, dep, tiny_mix(), seed=seed,
                           say=lambda m: None).run()
    finally:
        door.close()


@pytest.mark.parametrize("seed", [5, 6])
def test_the_reference_in_the_programs_place_is_correct(seed):
    out = probe_against(seed)
    assert out["ok"], out
    assert [c["check"] for c in out["checks"]] == list(CHECKS)


@pytest.mark.parametrize("broken, caught_by", [
    ({"level_plus": 1}, ("fill", "release_frees", "mixed")),
    ({"lose_releases": True}, ("release_frees", "double_release", "order")),
    ({"timeout_ms": 3_600_000}, ("expiry",))])
def test_a_broken_reference_in_the_programs_place_is_not_correct(
        broken, caught_by):
    out = probe_against(5, **broken)
    bad = {c["check"]: c.get("mismatches") for c in out["checks"]}
    assert not out["ok"]
    assert all(bad[c] > 0 for c in caught_by), bad
    assert bad["no_rule"] == 0


# -- frames, layout, ledger, session -------------------------------------------
def test_the_layout_is_the_flow_tables_with_levels():
    dep = tiny_dep()
    assert isinstance(dep, concurrent.flow.Deployment)
    rules = list(dep.rules())
    assert len(rules) == dep.n_flows == 2000
    assert len(dep.probe_rules) == 2 * len(CHECKS) * 4
    by_id = {fid: (level, ns) for fid, level, ns in rules}
    assert by_id[0] == (8, "ns0") and by_id[8 + 3] == (4, "ns3")
    assert by_id[16] == (256, "ns0")
    assert dep.level_of([0, 11, 16, 1900]).tolist() == [8, 4, 256, 256]
    probe_set = dep.probe_set(1)
    assert sorted(probe_set) == sorted(CHECKS)
    assert [lv for _f, lv in probe_set["fill"]] == [4, 8, 16, 64]
    assert all(by_id[f][1] == "ns6" for f, _l in probe_set["mixed"])
    assert not set(dep.traffic_namespaces()) & {6, 7}
    real = deploy.load(os.path.join(BENCH, "configs",
                                    "concurrent-mesh-100k.json"), [BENCH])
    assert real.n_flows == 100_000 and real.max_tokens == 1 << 20
    assert real.level_of([0, 64, 128, 192, 256]).tolist() == [
        64, 32, 16, 8, 1024]
    assert real.resource_timeout_ms == 2000 and len(
        real.spec["source"]) <= 200


def test_the_frames_are_the_programs_codec():
    from sentinel_tpu.cluster import protocol as P

    ids, counts = np.array([3, 1_000_002], np.int64), np.array([1, 2])
    raw = concurrent.encode_batch(77, ids, counts)
    assert raw == P.encode_batch_concurrent_acquire(77, ids, counts)
    assert P.decode_batch_concurrent_acquire(raw[2:])[1].tolist() == [
        3, 1_000_002]
    rel = concurrent.encode_release(-78, [5, 2**40 + 1])
    assert rel == P.encode_batch_concurrent_release(-78, [5, 2**40 + 1])
    assert concurrent.MAX_IDS_PER_RELEASE == P.MAX_RELEASE_PER_FRAME == 8191
    assert concurrent.ACQ_ROW == P.CONCURRENT_RSP_DTYPE
    rsp = P.encode_batch_concurrent_response(
        77, P.MsgType.BATCH_CONCURRENT_ACQUIRE, [0, 1], [5, 0], [0, 0],
        [2**40 + 9, 0])
    split = concurrent.wire.Splitter(concurrent.SINGLE_REPLIES,
                                     concurrent.BATCH_REPLIES)
    skipped = P.encode_batch_concurrent_response(
        -78, P.MsgType.BATCH_CONCURRENT_RELEASE, [6, 7])
    batch, singles = split.feed(skipped + rsp)
    assert singles is None and len(batch) == 1  # the release reply skipped
    assert batch[0][0] == 77
    assert batch[0][1]["token_id"].tolist() == [2**40 + 9, 0]


def test_the_ledgers_view_of_a_row():
    dep = tiny_dep()
    st = np.array([OK, BLOCKED, NO_RULE, 5, 8], np.uint8)
    cols = (np.arange(5), np.ones(5, np.int32))
    decided, brown, never, keys, tokens = dep.ledger_view(
        cols, st, np.zeros(5, np.int32))
    assert decided.tolist() == [True, True, True, False, False]
    assert not brown.any() and never == 1 and not len(keys)
    assert dep.ledger_counts().size == 0
    assert dep.window_checks({"never_rows": 3}) == [
        ("acquire rows NO_RULE or tokens in hand past a level", 3, 0)]


def _reply(status, tokens):
    rows = np.zeros(len(status), concurrent.ACQ_ROW)
    rows["status"], rows["token_id"] = status, tokens
    return rows


def test_the_session_gives_tokens_back_and_counts_those_in_hand():
    dep = tiny_dep()
    tr = dict(tiny_mix(), hold_ms=[{"share": 1.0, "lo": 20, "hi": 20}],
              never_released_share=0.0)
    ses = concurrent.Session(tr, dep, 1, 0, 2)
    assert dep.session is ses
    flows, ones = np.array([0, 0, 8, 16], np.int64), np.ones(4, np.int32)
    first = ses.encode(0, 10, flows, ones)
    assert first == concurrent.encode_batch(10, flows, ones)  # nothing due
    t = time.monotonic()
    ses.back(0, 10, [flows, ones], _reply([OK, OK, BLOCKED, OK],
                                          [101, 102, 0, 103]), t)
    assert ses.in_hand[[0, 8, 16]].tolist() == [2, 0, 1]
    assert ses.take_over() == 0
    # the other connection's frame carries none of connection 0's ids
    assert ses.encode(1, 11, flows, ones) == concurrent.encode_batch(
        11, flows, ones)
    time.sleep(0.03)
    nxt = ses.encode(0, 12, flows, ones)
    want = concurrent.encode_release(-13, [101, 102, 103])
    assert nxt == want + concurrent.encode_batch(12, flows, ones)
    assert ses.in_hand.sum() == 0 and ses.released == 3
    # tokens past a level are counted once, and handed to the ledger
    many = np.zeros(10, np.int64)
    ses.back(0, 12, [many, np.ones(10, np.int32)],
             _reply([OK] * 10, range(200, 210)), time.monotonic())
    assert ses.in_hand[0] == 10 > dep.level_of([0])[0]
    _d, _b, never, _k, _t = dep.ledger_view(
        (many, np.ones(10, np.int32)), np.zeros(10, np.uint8),
        np.zeros(10, np.int32))
    assert never == 1 and ses.take_over() == 0
    ses.lost(1, 11)
    assert ses.lost_xids == [11] and not ses.sent_at[1]


def test_a_token_never_given_back_is_forgotten_at_the_timeout():
    dep = tiny_dep()
    tr = dict(tiny_mix(), hold_ms=[{"share": 1.0, "lo": 1, "hi": 1}],
              never_released_share=1.0)
    ses = concurrent.Session(tr, dep, 1, 0, 1)
    ses.timeout_s = 0.05
    flows, ones = np.array([0, 8], np.int64), np.ones(2, np.int32)
    ses.encode(0, 1, flows, ones)
    ses.back(0, 1, [flows, ones], _reply([OK, OK], [7, 8]), time.monotonic())
    assert ses.in_hand.sum() == 2 and len(ses.fresh) == 1
    time.sleep(0.06)
    # never released; forgotten when the next reply is counted, by which
    # time the server's expiry may have given the room to another call
    assert ses.encode(0, 2, flows, ones) == concurrent.encode_batch(
        2, flows, ones)
    ses.back(0, 2, [flows, ones], _reply([OK, BLOCKED], [9, 0]),
             time.monotonic())
    assert ses.in_hand.sum() == 1 and ses.abandoned == 2
    assert ses.released == 0 and ses.take_over() == 0


def test_the_readers_and_the_roofline():
    cell = manifest.Cell(os.path.join(os.path.dirname(BENCH),
                                      "BENCHMARK.json"), REAL_CELL)
    readers = cell.readers()
    wanted = {m["name"] for m in cell.per_layer()}
    assert set(READERS) <= wanted and "client.send_lag_p99_ms" in wanted
    assert "step.decide_device_ms_per_dispatch" not in wanted
    assert "decide_step_roofline" not in wanted
    zero = dict.fromkeys(concurrent_roofline.COUNTERS, 0)
    after = dict(zero, concurrent_dispatch_total=100,
                 concurrent_acquire_rows_total=100_000,
                 concurrent_release_rows_total=80_000,
                 concurrent_blocked_total=15_000,
                 concurrent_already_release_total=0,
                 concurrent_expired_total=90)
    snap = {"before": {"stages": zero}, "after": {"stages": after},
            "events": [{"stage": "device_in", "shard": 2, "aux": 1800}] * 30
            + [{"stage": "device_in", "shard": 0, "aux": 5}],
            "trace": {"modules": [("jit_concurrent_step_b4096", 0.012),
                                  ("jit_decide_b64", 0.5)]},
            "peaks": deploy.load_json(cell.peaks_file),
            "device_kind": "TPU v5 lite", "seconds": 10.0, "slice_s": 3.0}
    assert readers["step.concurrent_device_ms_per_dispatch"].reduce(
        snap) == pytest.approx(0.4)
    assert readers["service.concurrent_blocked_share"].reduce(
        snap) == pytest.approx(15.0)
    assert readers["lane.release_rows_per_dispatch"].reduce(snap) == 800
    assert readers["service.tokens_expired_per_s"].reduce(snap) == 9.0
    model = concurrent_roofline.window_model(after)
    assert model["bytes"] == (80_000 * 16 + 80_090 * 24 + 90 * 16
                              + 100_000 * 8 + 85_000 * 20)
    share = readers["concurrent_step_roofline"].reduce(snap)
    assert 0 < share < 100
    assert share == pytest.approx(
        100 * model["bytes"] / 819e9 * 0.3 / 0.012)
    # a program without the lane (the parent): nothing to read, no raise
    parent = dict(snap, before={"stages": {}}, after={"stages": {}},
                  events=[], trace={"modules": [("jit_decide_b64", 0.5)]})
    for name in READERS:
        assert readers[name].reduce(parent) is None, name


def test_a_program_without_the_lane_is_refused_at_once(monkeypatch):
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    monkeypatch.delattr(DefaultTokenService, "dispatch_concurrent_batch")
    with pytest.raises(SystemExit) as refused:
        concurrent.service_args(tiny_dep())
    assert "no concurrency lane" in str(refused.value)
