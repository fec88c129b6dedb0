"""The reader PR 25 added: a share from two snapshots, and ``None`` where the
program lacks the counter (a parent tree run with this benchmark laid over
it) or nothing was materialized in the window."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
METRIC = "service.verdict_copy_ready_share"


def _reader():
    with open(BENCH, encoding="utf-8") as f:
        name = json.load(f)["workloads"][0]["name"]
    return manifest.Cell(BENCH, name).readers()[METRIC]


def _stages(ready, waits):
    out = {"device_wait_ms": {"count": waits, "sum": 0.5 * waits,
                              "p50": 0.5, "p99": 0.6}}
    if ready is not None:
        out["verdict_copy_ready_total"] = ready
        out["verdict_host_reads_total"] = waits
    return out


def _snap(before, after):
    return {"before": {"stages": before}, "after": {"stages": after}}


@pytest.mark.parametrize("ready,want", [(0, 0.0), (150, 75.0), (200, 100.0)])
def test_the_share_is_ready_reads_over_materializations_in_the_window(
        ready, want):
    snap = _snap(_stages(40, 50), _stages(40 + ready, 250))
    assert _reader().reduce(snap) == pytest.approx(want)


def test_nothing_materialized_in_the_window_is_nothing_to_read():
    same = _stages(40, 50)
    assert _reader().reduce(_snap(same, same)) is None


@pytest.mark.parametrize("stages", [
    _stages(None, 50),  # PR 24's tree: the histogram, no counter
    {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}},
])
def test_a_tree_without_the_counter_reads_none(stages):
    later = dict(stages)
    if "device_wait_ms" in later:
        later["device_wait_ms"] = dict(later["device_wait_ms"], count=250)
    assert _reader().reduce(_snap(stages, later)) is None


def test_the_manifest_entry_agrees_with_the_reader_file():
    with open(BENCH, encoding="utf-8") as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == METRIC]
    r = _reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "higher" and "workloads" not in m
