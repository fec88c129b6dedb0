"""The family seam: a deployment that is not a flow table comes by files
alone; the flow family still sends, byte for byte, what the harness sent
before there were families; one-row frames keep an open loop's schedule."""

import json
import os
import sys
import time

import numpy as np
import pytest

from cellbench import deploy, loadgen, run, traffic, wire
from cellbench.families import flow, flow_reference

import frame_digest
from fake_door import FakeDoor, reference_decider

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "manifest.json")
EXTRA = os.path.join(HERE, "extra")

with open(os.path.join(HERE, "data", "frame_digests.json"),
          encoding="utf-8") as _f:
    DIGESTS = json.load(_f)


# -- (a) by files alone -------------------------------------------------------
def test_a_family_a_configuration_a_mix_and_a_cell_by_files_alone(tmp_path):
    """A new directory that ``paths`` gains holds a family, a configuration,
    a mix; the manifest gains the entries that name them. No file of
    ``cellbench/`` is touched, and the cell runs."""
    more = tmp_path / "morecells"
    for d in ("families", "configs", "traffic"):
        (more / d).mkdir(parents=True)
    with open(os.path.join(EXTRA, "families", "paramflow.py"),
              encoding="utf-8") as f:
        (more / "families" / "hotkeys.py").write_text(f.read())
    cfg = deploy.load_json(os.path.join(EXTRA, "configs", "tiny-param.json"))
    cfg.update(name="hot", family="hotkeys")
    cfg["rules"].update(n_rules=5, count=4)
    (more / "configs" / "hot.json").write_text(json.dumps(cfg))
    mix = deploy.load_json(os.path.join(EXTRA, "traffic", "closed.json"))
    mix.update(name="hot-closed", outstanding=3)
    (more / "traffic" / "hot-closed.json").write_text(json.dumps(mix))
    bench = deploy.load_json(MANIFEST)
    bench["paths"] = [os.path.relpath(os.path.dirname(HERE), tmp_path),
                      "morecells"]
    bench["configs"].append({"name": "hot", "source": "test", "reduced": [],
                             "file": "morecells/configs/hot.json",
                             "why": "test"})
    bench["workloads"].append({"name": "hot.hot-closed", "config": "hot",
                               "traffic": "hot-closed", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    lines = []
    result = run.run_cell(str(tmp_path / "BENCHMARK.json"), "hot.hot-closed",
                          seed=2_147_483_777, seconds=1.5, trace=0,
                          require_chip=False, out=lines.append)
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    assert result["attempted"] > 0
    fam = sys.modules["cellbench_family_hotkeys"]
    assert fam.__file__ == str(more / "families" / "hotkeys.py")
    assert any("probe pair" in ln and " 0 mismatches" in ln for ln in lines)
    assert set(result["compared"]) >= {"probe_count", "probe_slide",
                                       "admitted_over_count"}


def test_a_configuration_without_a_family_is_a_flow_table():
    dep = deploy.load(os.path.join(EXTRA, "configs", "tiny.json"))
    assert dep.family is flow and isinstance(dep, flow.Deployment)
    with pytest.raises(SystemExit):
        deploy.family("no-such-family")


# -- (b) the generators send what they sent -----------------------------------
@pytest.mark.parametrize("key", sorted(DIGESTS))
def test_frames_and_due_times_are_the_parents(key):
    mix, seed, proc = key.split("/")
    assert frame_digest.digest(mix, int(seed[4:]), int(proc[4:])) == \
        DIGESTS[key]


# -- (c) the fixture family on the program as it is ---------------------------
@pytest.mark.parametrize("cell", ["tiny-param.closed", "tiny-param.open"])
def test_the_param_fixture_is_correct_on_the_cpu(cell):
    lines = []
    result = run.run_cell(MANIFEST, cell, seed=2_147_483_650, seconds=1.5,
                          trace=0, require_chip=False, out=lines.append)
    assert result["correct"] is True and result["failed"] == 0, lines[-12:]
    assert any("param path: impl 'auto' resolved to 'jax'" in ln
               for ln in lines)


def test_a_service_that_lets_an_exhausted_value_pass_is_not_correct():
    paramflow = deploy.family("paramflow", [EXTRA])
    lines = []
    result = run.run_cell(MANIFEST, "tiny-param.closed", seed=2_147_483_651,
                          seconds=1.5, trace=0, require_chip=False,
                          wrap_service=paramflow.CONTROLS["over_admit"],
                          out=lines.append)
    assert result["correct"] is False
    assert any("probe count" in ln and " 0 mismatches" not in ln
               for ln in lines)


def test_the_param_reference_by_hand():
    paramflow = deploy.family("paramflow", [EXTRA])
    a, b = 11, 12
    ref = paramflow.Reference({1: (3.0, {a: 5.0})}, 500, 2)
    assert [ref.decide(0, 1, 1, [a]) for _ in range(6)] == [0] * 5 + [1]
    assert [ref.decide(0, 1, 1, [b]) for _ in range(3)] == [0, 0, 0]
    # b is exhausted: the pair is BLOCKED, and c stays counted
    c = 13
    assert ref.decide(100, 1, 1, [b, c]) == deploy.BLOCKED
    assert [ref.decide(100, 1, 1, [c]) for _ in range(3)] == [0, 0, 1]
    # the bucket that began at 0 is still one of the two at 999, gone at 1000
    assert ref.decide(999, 1, 1, [b]) == deploy.BLOCKED
    assert ref.decide(1000, 1, 1, [b]) == deploy.OK
    assert ref.decide(0, 2, 1, [a]) == deploy.NO_RULE


def test_param_frames_are_the_reference_clients():
    paramflow = deploy.family("paramflow", [EXTRA])
    arr = paramflow.encode_singles(7, np.array([3, 4]), np.array([1, 2]),
                                   np.array([[5, -6], [7, 8]]))
    raw = arr[1:2].tobytes()
    assert raw == (b"\x00\x23" + (8).to_bytes(4, "big") + b"\x02"
                   + (4).to_bytes(8, "big") + (2).to_bytes(4, "big")
                   + b"\x00\x02" + (7).to_bytes(8, "big")
                   + (8).to_bytes(8, "big"))
    split = wire.Splitter(paramflow.SINGLE_REPLIES, paramflow.BATCH_REPLIES)
    rsp = np.zeros(3, wire.SINGLE_RSP)
    rsp["len"], rsp["xid"], rsp["type"] = 14, [1, 2, 3], [2, 1, 2]
    batch, singles = split.feed(rsp.tobytes())
    assert batch == [] and singles["xid"].tolist() == [1, 3]  # FLOW skipped


# -- (d) one-row frames in the open loop --------------------------------------
def single_open(tmp_path, seconds, **over):
    tr = deploy.load_json(os.path.join(EXTRA, "traffic", "tiny-single.json"))
    tr.update(loop="open", rate_rows_per_s=2000, inflight_window_frames=256,
              **over)
    return loadgen.Generator({
        "traffic": tr, "seed": 5, "proc": 0, "seconds": seconds,
        "warm_seconds": 0.2, "port_file": str(tmp_path / "port"),
        "config_file": os.path.join(EXTRA, "configs", "tiny.json")})


def measure(gen, door, seconds, tmp_path):
    gen.connect(door.port)
    out = str(tmp_path / "r.npz")
    summary = gen.cmd_measure(time.monotonic() + 0.05, seconds, out)
    for c in gen.conns:
        c.close()
    door.close()
    return summary, np.load(out)


@pytest.fixture
def decide():
    dep = flow.Deployment(deploy.load_json(
        os.path.join(EXTRA, "configs", "tiny.json")))
    return reference_decider(flow_reference.for_deployment(dep))


def test_one_row_frames_keep_the_open_loops_schedule(tmp_path, decide):
    gen = single_open(tmp_path, 1.0)
    due = gen.main[0]
    assert len(due) == 2000 and np.allclose(np.diff(due), 1 / 2000)
    s, z = measure(gen, FakeDoor(decide), 1.0, tmp_path)
    assert s["attempted"] == 2000 and s["failed_rows"] == 0
    assert s["decided"] == 2000 and s["duplicates"] == 0
    assert len(z["lag_s"]) == 2000 and z["lag_s"].min() >= 0
    # sent when due, not when the one before was answered
    assert np.median(z["lag_s"]) < 0.01 and np.percentile(z["lag_s"], 99) < 0.2
    assert len(z["lat_s"]) == 2000


def test_one_row_frames_past_the_window_are_skipped_and_counted_once(
        tmp_path, decide):
    # a 1.5 s stall after frame 100: the 256-frame window fills, later
    # frames are skipped, and latency still runs from the due time
    gen = single_open(tmp_path, 2.0, connections=1)
    s, z = measure(gen, FakeDoor(decide, stall=(100, 1.5)), 2.0, tmp_path)
    assert s["attempted"] == 4000 and s["failed"]["skipped"] > 0
    assert s["decided"] + s["failed_rows"] == s["attempted"]
    assert z["lat_s"].max() > 1.2
    assert int(z["lat_w"].sum()) == s["decided"]


# -- (e) sessions: frames whose bytes depend on replies ------------------------
def generator_of(tmp_path, config, mix, seconds, family=None, **over):
    """A generator on a tests' configuration and mix; ``family`` writes the
    configuration out under another family (the semaphore fixture)."""
    tr = deploy.load_json(os.path.join(EXTRA, "traffic", mix + ".json"))
    tr.update(over)
    config_file = os.path.join(EXTRA, "configs", config + ".json")
    if family is not None:
        cfg = dict(deploy.load_json(config_file), family=family)
        config_file = str(tmp_path / f"{config}-{family}.json")
        with open(config_file, "w", encoding="utf-8") as f:
            json.dump(cfg, f)
    return loadgen.Generator({
        "traffic": tr, "seed": 7, "proc": 0, "seconds": seconds,
        "warm_seconds": 0.2, "port_file": str(tmp_path / "port"),
        "config_file": config_file,
        "family_dirs": [os.path.dirname(HERE), EXTRA]})


@pytest.fixture
def sent(monkeypatch):
    """Every ``Conn.send`` of a test: ``[(connection, bytes)]`` in order."""
    out = []
    send = loadgen.Conn.send

    def recording(self, data):
        out.append((self, bytes(data)))
        return send(self, data)

    monkeypatch.setattr(loadgen.Conn, "send", recording)
    return out


def xid_of(frame: bytes) -> int:
    return int.from_bytes(frame[2:6], "big", signed=True)


def frames_of(raw: bytes) -> list:
    """The frames of one send, length prefix and all."""
    out = []
    while raw:
        n = 2 + int.from_bytes(raw[:2], "big")
        out.append(raw[:n])
        raw = raw[n:]
    return out


@pytest.mark.parametrize("config, mix", [
    ("tiny", "tiny-open"), ("tiny-breaker", "tiny-health-cycle-open"),
    ("tiny-hotparam", "tiny-keys-open"), ("tiny-shaped", "tiny-prio-open")])
def test_an_open_loop_without_a_session_sends_what_encode_frames_gives(
        tmp_path, sent, decide, config, mix):
    gen = generator_of(tmp_path, config, mix, 0.5, timeout_ms=300)
    assert gen.session is None
    x0 = gen.next_xid
    want = traffic.encode_frames(gen.fam, gen.main[1], x0)
    door = FakeDoor(decide)
    gen.connect(door.port)
    gen.cmd_measure(time.monotonic() + 0.05, 0.5, str(tmp_path / "r.npz"))
    door.close()
    assert [raw for _c, raw in sent] == want
    assert [gen.conns.index(c) for c, _raw in sent] == [
        k % len(gen.conns) for k in range(len(want))]


def test_a_closed_loop_without_a_session_sends_what_encode_batch_gives(
        tmp_path, sent, decide):
    gen = generator_of(tmp_path, "tiny", "tiny-sat", 0.3)
    assert gen.session is None
    measure(gen, FakeDoor(decide), 0.3, tmp_path)
    assert len(sent) > 8
    n_conn, n_pool = len(gen.conns), len(gen.pool[0])
    for ci, c in enumerate(gen.conns):
        mine = [raw for conn, raw in sent if conn is c]
        x0 = xid_of(mine[0])
        for j, raw in enumerate(mine):
            p = (ci + j * n_conn) % n_pool
            assert raw == gen.fam.encode_batch(
                x0 + j, *[col[p] for col in gen.pool])


def semaphore(tmp_path, mix, seconds, **over):
    over.setdefault("hold_ms", [20, 120])
    return generator_of(tmp_path, "tiny", mix, seconds, family="semaphore",
                        **over)


def held_to_the_semaphores_rules(gen, door, lost_too=()):
    """Every release names an id that was issued, exactly once, not before
    its hold was over (so never before its acquire's reply was read), and
    never an id of a frame that was given up. Returns the ids released."""
    came = {}
    for per_conn in gen.session.came:
        came.update(per_conn)
    ids = [i for i, _t in door.released]
    assert ids and len(ids) == len(set(ids))  # none twice
    assert set(ids) <= set(door.issued)  # none that was not issued
    assert set(ids) <= set(came)  # none whose reply was not read
    for i, t in door.released:
        assert t >= came[i] > door.issued[i][1]
    gone = set(gen.session.lost_xids) | set(lost_too)
    assert not [i for i in ids if door.issued[i][0] in gone]
    return ids


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-sat"])
def test_a_release_follows_its_acquires_reply_in_both_loops(
        tmp_path, sent, decide, mix):
    gen = semaphore(tmp_path, mix, 1.0)
    door = FakeDoor(decide)
    gen.connect(door.port)
    warm = gen.cmd_warm()  # a session is warm when the window starts
    assert door.issued
    before = len(door.released)
    s, z = measure_on(gen, 1.0, tmp_path)
    door.close()
    # the rows a frame is counted by are the mix's: releases are not rows.
    # (A loaded machine may cost a frame: then it is failed, and lost.)
    assert s["decided"] + s["failed_rows"] == s["attempted"] > 0
    assert s["failed_rows"] + warm["failed_rows"] <= 0.05 * s["attempted"]
    assert int(z["lat_w"].sum()) == s["decided"]
    ids = held_to_the_semaphores_rules(gen, door)
    assert len(ids) > before and len(ids) > 0.5 * len(door.issued)
    assert len(gen.session.lost_xids) == (
        s["failed_rows"] + warm["failed_rows"]) // gen.rows
    # releases travel in front of a frame, in the same send, under its xid
    with_release = 0
    for _c, raw in sent:
        *releases, acquire = frames_of(raw)
        assert acquire[6] == 40 and all(
            r[6] == 41 and xid_of(r) == -1 - xid_of(acquire) for r in releases)
        with_release += bool(releases)
    assert with_release > 10


def measure_on(gen, seconds, tmp_path):
    out = str(tmp_path / "r.npz")
    return gen.cmd_measure(time.monotonic() + 0.05, seconds, out), np.load(out)


def test_skipped_and_timed_out_frames_are_lost_and_never_released(
        tmp_path, decide):
    # one connection, 300 ms of patience, a 1.5 s stall after frame 20: the
    # 64-frame window fills, later frames are skipped, those in flight time
    # out; the door answers them late, into the next window
    gen = semaphore(tmp_path, "tiny-open", 1.0, connections=1, timeout_ms=300)
    door = FakeDoor(decide, stall=(20, 1.5))
    gen.connect(door.port)
    s, _z = measure_on(gen, 1.0, tmp_path)
    assert s["failed"]["skipped"] > 0 and s["failed"]["timeout"] > 0
    lost = list(gen.session.lost_xids)
    assert len(lost) == len(set(lost)) == (
        s["failed"]["skipped"] + s["failed"]["timeout"]) // 64
    time.sleep(1.0)  # the stall ends; the late replies are on their way
    s2, _z = measure_on(gen, 1.0, tmp_path)
    door.close()
    assert s2["decided"] > 0.9 * s2["attempted"]
    assert gen.session.lost_xids[:len(lost)] == lost
    lost = gen.session.lost_xids
    late = {i for i, (xid, _t) in door.issued.items() if xid in set(lost)}
    assert late  # ids were issued for frames that had been given up
    ids = held_to_the_semaphores_rules(gen, door)
    assert not late & set(ids)


@pytest.mark.parametrize("mix", ["tiny-open", "tiny-sat"])
def test_frames_of_a_dropped_connection_are_lost(tmp_path, decide, mix):
    gen = semaphore(tmp_path, mix, 1.0, timeout_ms=300)
    s, _z = measure(gen, FakeDoor(decide, drop_at=30), 1.0, tmp_path)
    assert s["failed"]["connection"] > 0
    assert s["decided"] + s["failed_rows"] == s["attempted"]
    lost = gen.session.lost_xids
    assert len(lost) == len(set(lost)) == s["failed_rows"] // gen.rows


def test_a_session_with_one_row_frames_raises(tmp_path):
    with pytest.raises(ValueError, match="one-row frames"):
        semaphore(tmp_path, "tiny-single", 0.5)
