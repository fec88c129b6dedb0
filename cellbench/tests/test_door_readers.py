"""The readers PR 38 added, on canned ``snap``s: a verdict's time at the
server from the socket's last byte in to its last byte out. Each averages or
differences over the window, and gives ``None`` on a tree without the
histogram (a parent tree run with this benchmark laid over it) and on a
window in which no frame came."""

import json
import os

import numpy as np
import pytest

from cellbench import manifest
from sentinel_tpu.metrics.histogram import LatencyHistogram

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "BENCHMARK.json")

AVERAGES = {
    "door.rx_to_pull_avg_ms": "door_in_ms",
    "door.pull_wake_avg_ms": "door_wake_ms",
    "lane.queue_wait_avg_ms": "queue_wait_ms",
    "door.submit_to_wire_avg_ms": "door_out_ms",
}
QUANTILES = {"door.residence_p50_ms": 0.5, "door.residence_p95_ms": 0.95}
TILED = ("door_in_ms", "door_wake_ms", "intake_ms", "queue_wait_ms",
         "dispatch_ms", "reply_queue_wait_ms", "decide_ms", "door_out_ms")
NEW = sorted(AVERAGES) + sorted(QUANTILES) + [
    "door.residence_unattributed_avg_ms", "client.outside_server_p50_ms"]
# what a tree from before PR 38 snapshots: the series is there and empty
PARENT = {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0},
          "queue_wait_ms": {"count": 0, "sum": 0.0, "p50": None, "p99": None}}


def _readers():
    with open(BENCH, encoding="utf-8") as f:
        name = json.load(f)["workloads"][0]["name"]
    return manifest.Cell(BENCH, name).readers()


def _client(ms=()):
    lat = np.asarray(ms, np.float64) / 1e3
    return {"lat_s": lat, "lat_w": np.ones(lat.size)}


def _snap(before, after, client_ms=(3.0, 4.0, 5.0)):
    return {"before": {"stages": before}, "after": {"stages": after},
            "client": _client(client_ms)}


def _h(count, total):
    return {"count": count, "sum": total, "p50": 1.0, "p99": 1.0}


def _residence(hist):
    """``stage_snapshot()``'s entry of ``door_residence_ms`` for ``hist``."""
    le, cum, vmax = hist.cumulative()
    return {"count": hist.count, "sum": hist.sum, "p50": None, "p99": None,
            "le": list(le), "cum": list(cum), "max": vmax}


def _recorded(values):
    hist = LatencyHistogram(lo=0.1, hi=10_000.0, per_decade=20)
    for v in values:
        hist.record(v)
    return hist


@pytest.mark.parametrize("metric", sorted(AVERAGES))
def test_an_averaging_reader_averages_its_histogram_over_the_window(metric):
    hist = AVERAGES[metric]
    reader = _readers()[metric]
    before, after = {hist: _h(10, 5.0)}, {hist: _h(210, 65.0)}
    assert reader.reduce(_snap(before, after)) == pytest.approx(0.3)
    # no frame in the window: nothing to read
    assert reader.reduce(_snap(after, after)) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_door_reader_returns_none_on_a_tree_without_the_histograms(metric):
    assert _readers()[metric].reduce(_snap(PARENT, PARENT)) is None
    assert _readers()[metric].reduce(_snap({}, {})) is None


@pytest.mark.parametrize("metric", sorted(QUANTILES))
def test_the_quantile_reader_matches_the_histogram_on_the_same_counts(metric):
    q = QUANTILES[metric]
    reader = _readers()[metric]
    rng = np.random.default_rng(38)
    early = rng.lognormal(1.2, 0.5, 400)     # before the window
    window = rng.lognormal(1.0, 0.3, 2000)   # the window's own frames
    before = _recorded(early)
    after = _recorded(np.concatenate([early, window]))
    only = _recorded(window)
    got = reader.reduce(_snap({"door_residence_ms": _residence(before)},
                              {"door_residence_ms": _residence(after)}))
    # the window alone, not the process since its start
    assert after.quantile(q) != pytest.approx(only.quantile(q))
    if only._max == after._max:  # the clamp of the last bucket is the same
        assert got == pytest.approx(only.quantile(q))
    assert abs(got - float(np.quantile(window, q))) < 0.07 * got
    # from a process that just started, the difference is the histogram
    empty = _recorded([])
    assert reader.reduce(_snap(
        {"door_residence_ms": _residence(empty)},
        {"door_residence_ms": _residence(only)})) == pytest.approx(
            only.quantile(q))
    # no reply in the window
    assert reader.reduce(_snap({"door_residence_ms": _residence(after)},
                               {"door_residence_ms": _residence(after)})
                         ) is None


def test_unattributed_is_residence_less_the_means_of_the_phases():
    reader = _readers()["door.residence_unattributed_avg_ms"]
    before = {h: _h(100, 10.0) for h in TILED}
    after = {h: _h(300, 10.0 + 200 * 0.25) for h in TILED}
    before["door_residence_ms"] = _h(1000, 500.0)
    after["door_residence_ms"] = _h(3000, 500.0 + 2000 * 2.1)
    # 2.1 a frame, eight phases of 0.25 each: 0.1 in no span
    assert reader.reduce(_snap(before, after)) == pytest.approx(0.1)
    # it is a time and may read under 0
    after["door_residence_ms"] = _h(3000, 500.0 + 2000 * 1.9)
    assert reader.reduce(_snap(before, after)) == pytest.approx(-0.1)
    # one of the phases empty in the window: nothing to read
    after["queue_wait_ms"] = before["queue_wait_ms"]
    assert reader.reduce(_snap(before, after)) is None


def test_outside_the_server_is_the_clients_median_less_the_residence():
    reader = _readers()["client.outside_server_p50_ms"]
    p50 = _readers()["door.residence_p50_ms"]
    window = _recorded(np.random.default_rng(7).lognormal(1.0, 0.2, 1000))
    snap = _snap({"door_residence_ms": _residence(_recorded([]))},
                 {"door_residence_ms": _residence(window)},
                 client_ms=(3.0, 3.4, 3.2, 9.0, 3.1))
    inside = p50.reduce(snap)
    assert reader.reduce(snap) == pytest.approx(3.2 - inside)
    snap["client"] = _client(())
    assert reader.reduce(snap) is None


def test_the_new_entries_are_the_benchmarks_last_and_have_no_workloads_list():
    with open(BENCH, encoding="utf-8") as f:
        bench = json.load(f)
    tail = bench["per_layer"][-8:]
    assert sorted(m["name"] for m in tail) == sorted(NEW)
    readers = _readers()
    for m in tail:
        r = readers[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
        assert "workloads" not in m  # every cell serves through this door
