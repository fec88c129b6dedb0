"""The reader PR 43 added: a share from two snapshots, and ``None`` where the
program lacks the counter (a parent tree run with this benchmark laid over
it) or nothing was dispatched in the window."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")
METRIC = "service.native_prep_share"
BYPASS = {"hot-param-1k.keys-zipf-open",
          "concurrent-mesh-100k.tenants-zipf-hold-open"}


def _reader():
    with open(BENCH, encoding="utf-8") as f:
        name = json.load(f)["workloads"][0]["name"]
    return manifest.Cell(BENCH, name).readers()[METRIC]


def _stages(native, dispatched):
    out = {"prep_ms": {"count": dispatched, "sum": 0.3 * dispatched,
                       "p50": 0.3, "p99": 0.4}}
    if native is not None:
        out["prep_native_total"] = native
    return out


def _snap(before, after):
    return {"before": {"stages": before}, "after": {"stages": after}}


@pytest.mark.parametrize("native,want", [(0, 0.0), (150, 75.0), (200, 100.0)])
def test_the_share_is_native_preps_over_dispatches(native, want):
    snap = _snap(_stages(10, 50), _stages(10 + native, 250))
    assert _reader().reduce(snap) == pytest.approx(want)


def test_nothing_dispatched_in_the_window_is_nothing_to_read():
    same = _stages(10, 50)
    assert _reader().reduce(_snap(same, same)) is None


@pytest.mark.parametrize("stages", [
    _stages(None, 50),  # PR 41's tree: the histogram, no counter
    {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}},
])
def test_a_tree_without_the_counter_reads_none(stages):
    later = dict(stages)
    if "prep_ms" in later:
        later["prep_ms"] = dict(later["prep_ms"], count=250)
    assert _reader().reduce(_snap(stages, later)) is None


def test_the_manifest_entry_agrees_with_the_reader_file():
    with open(BENCH, encoding="utf-8") as f:
        bench = json.load(f)
    (m,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    r = _reader()
    assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
        m["unit"], m["layer"], m["moves"], m["source"])
    assert m["better"] == "higher"
    # the cells that send flow frames; the two others have a prep of their own
    assert set(m["workloads"]) == {
        w["name"] for w in bench["workloads"]} - BYPASS
