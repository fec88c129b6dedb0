"""The reader PR 47 added: the hot-parameter lane's share of native preps
from two snapshots, and ``None`` where the program lacks the counter (a
parent tree run with this benchmark laid over it) or nothing was dispatched
in the window."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
METRIC = "service.param_native_prep_share"
# the cells that send param frames only
CELLS = ["hot-param-1k.keys-zipf-open",
         "demo-cluster-param-1k.single-param-open"]


def _bench():
    with open(BENCH, encoding="utf-8") as f:
        return json.load(f)


def _reader():
    return manifest.Cell(BENCH, CELLS[0]).readers()[METRIC]


def _stages(native, dispatched, flow_native=0):
    out = {"prep_ms": {"count": dispatched, "sum": 0.1 * dispatched,
                       "p50": 0.1, "p99": 0.2},
           "prep_native_total": flow_native}  # the flow lane's: not read
    if native is not None:
        out["param_prep_native_total"] = native
    return out


def _snap(before, after):
    return {"before": {"stages": before}, "after": {"stages": after}}


@pytest.mark.parametrize("native,want", [(0, 0.0), (150, 75.0), (200, 100.0)])
def test_the_share_is_native_param_preps_over_dispatches(native, want):
    snap = _snap(_stages(10, 50), _stages(10 + native, 250, flow_native=99))
    assert _reader().reduce(snap) == pytest.approx(want)


def test_nothing_dispatched_in_the_window_is_nothing_to_read():
    same = _stages(10, 50)
    assert _reader().reduce(_snap(same, same)) is None


@pytest.mark.parametrize("stages", [
    _stages(None, 50),  # PR 46's tree: the histogram and the flow counter
    {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}},
])
def test_a_tree_without_the_counter_reads_none(stages):
    later = dict(stages)
    if "prep_ms" in later:
        later["prep_ms"] = dict(later["prep_ms"], count=250)
    assert _reader().reduce(_snap(stages, later)) is None


def test_the_manifest_entry_agrees_with_the_reader_file():
    bench = _bench()
    (m,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    r = _reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "higher"
    assert m["workloads"] == CELLS
    for other in {w["name"] for w in bench["workloads"]} - set(CELLS):
        assert METRIC not in {
            m["name"] for m in manifest.Cell(BENCH, other).per_layer()}


@pytest.mark.parametrize("name", CELLS)
def test_its_cells_are_accepted_cells_that_report_what_it_moves(name):
    bench = _bench()
    assert name in {w["name"] for w in bench["workloads"]}
    cell = manifest.Cell(BENCH, name)
    assert METRIC in {m["name"] for m in cell.per_layer()}
    assert _reader().MOVES in {m["name"] for m in cell.end_to_end()}
