"""Failure accounting against a door that misbehaves: every row is counted
once, as decided or as failed, and latency runs from the time a frame was
due."""

import os
import time

import numpy as np
import pytest

from cellbench import loadgen
from cellbench.families import flow as deploy, flow_reference as reference

from fake_door import FakeDoor, reference_decider

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "extra", "configs", "tiny.json")


def generator(tmp_path, traffic_name, seconds, **over):
    tr = deploy.load_json(os.path.join(HERE, "extra", "traffic",
                                       traffic_name + ".json"))
    tr.update(over)
    plan = {"traffic": tr, "config_file": CONFIG, "seed": 11, "proc": 0,
            "seconds": seconds, "warm_seconds": 0.2,
            "port_file": str(tmp_path / "port")}
    return loadgen.Generator(plan)


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
    dep = deploy.Deployment(deploy.load_json(CONFIG))
    return reference_decider(reference.for_deployment(dep))


def test_sound_door_every_row_decided(tmp_path, decide):
    gen = generator(tmp_path, "tiny-open", 1.0)
    s, z = measure(gen, FakeDoor(decide), 1.0, tmp_path)
    assert s["attempted"] == 6400 and s["failed_rows"] == 0
    assert s["decided"] == 6400 and s["duplicates"] == 0
    assert int(z["lat_w"].sum()) == 6400


def test_stall_of_3s_counts_every_row_once_and_latency_from_due(
        tmp_path, decide):
    # rate 6400 rows/s in 64-row frames, in-flight window 64 frames: a 3 s
    # stall after frame 20 holds 64 frames, then sends are skipped
    gen = generator(tmp_path, "tiny-open", 4.0, connections=1)
    s, z = measure(gen, FakeDoor(decide, stall=(20, 3.0)), 4.0, tmp_path)
    assert s["attempted"] == 4 * 6400
    assert s["failed"]["skipped"] > 0
    assert s["decided"] + s["failed_rows"] == s["attempted"]
    # frames held through the stall carry it in their latency: it is taken
    # from the due time, not from a late send
    assert z["lat_s"].max() > 2.5
    assert int(z["lat_w"].sum()) == s["decided"]


def test_shed_rows_are_failed_not_decided(tmp_path, decide):
    gen = generator(tmp_path, "tiny-open", 1.0)
    s, z = measure(gen, FakeDoor(decide, shed_from=10, shed_n=5), 1.0,
                   tmp_path)
    assert s["failed"]["status"] == 5 * 64
    assert s["decided"] == 6400 - 5 * 64
    assert s["status_hist"][deploy.OVERLOAD] == 5 * 64
    assert int(z["lat_w"].sum()) == s["decided"]  # none in the percentiles


def test_brownout_pass_is_a_failure(tmp_path):
    def brownout(ids, acq):  # what DEGRADE answers locally: OK, remaining 0
        n = len(ids)
        return (np.zeros(n, np.int8), np.zeros(n, np.int32),
                np.zeros(n, np.int32))
    gen = generator(tmp_path, "tiny-sat", 0.5)
    s, _ = measure(gen, FakeDoor(brownout), 0.5, tmp_path)
    assert s["failed"]["brownout_pass"] > 0
    assert s["decided"] + s["failed_rows"] == s["attempted"]
    assert s["decided"] < s["attempted"] * 0.05  # only metered rows remain


def test_dropped_connection_fails_its_rows(tmp_path, decide):
    gen = generator(tmp_path, "tiny-open", 1.0, connections=2)
    s, _ = measure(gen, FakeDoor(decide, drop_at=30), 1.0, tmp_path)
    assert s["failed"]["connection"] > 0
    assert s["decided"] + s["failed_rows"] == s["attempted"]


def test_closed_single_counts_every_request(tmp_path, decide):
    gen = generator(tmp_path, "tiny-single", 0.5)
    s, z = measure(gen, FakeDoor(decide), 0.5, tmp_path)
    assert s["attempted"] > 0 and s["failed_rows"] == 0
    assert s["decided"] == s["attempted"]
    assert s["decided_in_window"] <= s["decided"]
    assert len(z["lat_s"]) == s["decided"]
