"""The reader PR 35 added: a share from two snapshots, and ``None`` where the
program lacks the counter (a parent tree run with this benchmark laid over
it) or nothing was accounted in the window."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
METRIC = "service.reply_first_share"


def _reader():
    with open(BENCH, encoding="utf-8") as f:
        name = json.load(f)["workloads"][0]["name"]
    return manifest.Cell(BENCH, name).readers()[METRIC]


def _stages(first, accounted):
    out = {"account_ms": {"count": accounted, "sum": 0.5 * accounted,
                          "p50": 0.5, "p99": 0.6}}
    if first is not None:
        out["reply_first_total"] = first
    return out


def _snap(before, after):
    return {"before": {"stages": before}, "after": {"stages": after}}


@pytest.mark.parametrize("first,want", [(0, 0.0), (150, 75.0), (200, 100.0)])
def test_the_share_is_reply_first_dispatches_over_dispatches_accounted(
        first, want):
    # 40 of the 50 dispatches before the window were driven in process
    snap = _snap(_stages(10, 50), _stages(10 + first, 250))
    assert _reader().reduce(snap) == pytest.approx(want)


def test_nothing_accounted_in_the_window_is_nothing_to_read():
    same = _stages(10, 50)
    assert _reader().reduce(_snap(same, same)) is None


@pytest.mark.parametrize("stages", [
    _stages(None, 50),  # PR 34's tree: the histogram, no counter
    {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}},
])
def test_a_tree_without_the_counter_reads_none(stages):
    later = dict(stages)
    if "account_ms" in later:
        later["account_ms"] = dict(later["account_ms"], count=250)
    assert _reader().reduce(_snap(stages, later)) is None


def test_the_manifest_entry_agrees_with_the_reader_file():
    with open(BENCH, encoding="utf-8") as f:
        (m,) = [m for m in json.load(f)["per_layer"] if m["name"] == METRIC]
    r = _reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "higher" and "workloads" not in m
