"""The reader PR 51 added: the control thread's idle wake-ups a second from
two snapshots, and ``None`` where the program lacks the counter (a parent
tree run with this benchmark laid over it)."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
METRIC = "lane.control_idle_wakeups_per_s"
# every cell that was accepted when the metric came: each starts the native
# server, whose one control thread counts
CELLS = [
    "mesh-100k.tenants-zipf-open",
    "demo-cluster-1k.single-token",
    "mesh-100k.sidecar-sat",
    "mesh-100k-pod4.tenants-zipf-open",
    "hot-param-1k.keys-zipf-open",
    "shaped-mesh-100k.tenants-zipf-prio-open",
    "breaker-mesh-100k.tenants-zipf-health-cycle-open",
    "concurrent-mesh-100k.tenants-zipf-hold-open",
    "demo-cluster-param-1k.single-param-open",
    "param-mesh-100k.tenants-zipf-callers-open",
]


def _bench():
    with open(BENCH, encoding="utf-8") as f:
        return json.load(f)


def _reader():
    return manifest.Cell(BENCH, CELLS[1]).readers()[METRIC]


def _side(t, idle, wakeups=None):
    stages = {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}}
    if idle is not None:
        stages["control_idle_wakeups_total"] = idle
        # every return of the wait, the ones that found an event too: not read
        stages["control_wakeups_total"] = idle if wakeups is None else wakeups
    return {"t": t, "stages": stages}


@pytest.mark.parametrize("idle,seconds,want", [
    (0, 20.0, 0.0),        # a door rang before every time-out
    (200, 20.0, 10.0),     # the 100 ms time-outs of an idle control plane
    (10_000, 20.0, 500.0),  # a library without the bell: the 2 ms sleep
])
def test_idle_wakeups_over_the_seconds_between_the_readings(
        idle, seconds, want):
    snap = {"before": _side(100.0, 7, wakeups=9),
            "after": _side(100.0 + seconds, 7 + idle, wakeups=9_999)}
    assert _reader().reduce(snap) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    (_side(1.0, None), _side(21.0, None)),  # PR 50's tree: no counter
    (_side(1.0, None), _side(21.0, 200)),
])
def test_a_tree_without_the_counter_reads_none(before, after):
    assert _reader().reduce({"before": before, "after": after}) is None


def test_readings_at_one_instant_are_nothing_to_read():
    snap = {"before": _side(5.0, 1), "after": _side(5.0, 2)}
    assert _reader().reduce(snap) is None


def test_the_manifest_entry_agrees_with_the_reader_file():
    bench = _bench()
    (m,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert bench["per_layer"][-1] is m  # appended, nothing moved
    r = _reader()
    assert (r.NAME, r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        METRIC, m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "lower"
    assert m["workloads"] == CELLS
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}


@pytest.mark.parametrize("name", CELLS)
def test_its_cells_are_accepted_cells_that_report_what_it_moves(name):
    bench = _bench()
    assert name in {w["name"] for w in bench["workloads"]}
    cell = manifest.Cell(BENCH, name)
    assert METRIC in {m["name"] for m in cell.per_layer()}
    assert _reader().MOVES in {m["name"] for m in cell.end_to_end()}
