"""The readers PR 24 added: each gives a value on a canned ``snap`` and
``None`` where the program lacks the histogram, counter or program names (a
parent tree run with this benchmark laid over it)."""

import json
import os

import pytest

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "BENCHMARK.json")

PHASES = {
    "lane.permit_wait_avg_ms": "permit_wait_ms",
    "service.prep_avg_ms": "prep_ms",
    "service.lock_wait_avg_ms": "lock_wait_ms",
    "service.launch_avg_ms": "launch_ms",
    "lane.reply_queue_wait_avg_ms": "reply_queue_wait_ms",
    "service.device_wait_avg_ms": "device_wait_ms",
    "service.fetch_avg_ms": "fetch_ms",
    "service.account_avg_ms": "account_ms",
}


def _readers():
    with open(BENCH, encoding="utf-8") as f:
        name = json.load(f)["workloads"][0]["name"]
    return manifest.Cell(BENCH, name).readers()


def _snap(stages_before, stages_after, modules=(), device_in=0):
    return {"before": {"stages": stages_before},
            "after": {"stages": stages_after},
            "events": [{"stage": "device_in"}] * device_in
            + [{"stage": "prep"}, {"stage": "device_out"}],
            "trace": {"modules": [list(m) for m in modules]}}


@pytest.mark.parametrize("metric", sorted(PHASES))
def test_a_phase_reader_averages_its_histogram_over_the_window(metric):
    hist = PHASES[metric]
    reader = _readers()[metric]
    before = {hist: {"count": 10, "sum": 5.0, "p50": 0.5, "p99": 0.6}}
    after = {hist: {"count": 210, "sum": 105.0, "p50": 0.5, "p99": 0.6}}
    assert reader.reduce(_snap(before, after)) == pytest.approx(0.5)
    # nothing dispatched in the window: nothing to read
    assert reader.reduce(_snap(before, before)) is None


@pytest.mark.parametrize("metric", sorted(PHASES))
def test_a_phase_reader_returns_none_on_a_tree_without_the_histogram(metric):
    old = {"decide_ms": {"count": 3, "sum": 9.0, "p50": 3.0, "p99": 3.0}}
    assert _readers()[metric].reduce(_snap(old, old)) is None


def test_the_program_compile_counter_is_a_difference_and_none_when_absent():
    reader = _readers()["step.program_compiles_in_window"]
    assert reader.reduce(_snap({"compiles_total": 44},
                               {"compiles_total": 44})) == 0.0
    assert reader.reduce(_snap({"compiles_total": 44},
                               {"compiles_total": 46})) == 2.0
    assert reader.reduce(_snap({}, {})) is None


def test_the_decide_programs_are_picked_by_name():
    reader = _readers()["step.decide_device_ms_per_dispatch"]
    named = [("jit_decide_b1024_mixed(123)", 0.8),
             ("jit_decide_fused_d2_b16384_mixed(9)", 0.1),
             ("jit_outcome_step(5)", 0.3), ("jit_convert_element_type", 0.2)]
    assert reader.reduce(_snap({}, {}, named, device_in=1000)) == (
        pytest.approx(0.9))
    unnamed = [("jit__unknown(123)", 0.8), ("jit_fused(9)", 0.1)]
    assert reader.reduce(_snap({}, {}, unnamed, device_in=1000)) is None
    assert reader.reduce(_snap({}, {}, named, device_in=0)) is None


def test_every_per_layer_entry_has_a_reader_file_that_agrees_with_it():
    with open(BENCH, encoding="utf-8") as f:
        bench = json.load(f)
    readers = _readers()
    for m in bench["per_layer"]:
        path = os.path.join(ROOT, "cellbench", "layers", m["name"] + ".py")
        assert os.path.exists(path), f"{m['name']} has no reader file"
        r = readers[m["name"]]
        assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"])
