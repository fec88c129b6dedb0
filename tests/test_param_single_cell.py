"""Tier-1 runs ``tests/`` only. This file brings the ninth cell's files
(PR 45: the configuration ``demo-cluster-param-1k``, the mix
``single-param-open`` and the four readers under ``cellbench/layers/``)
through the benchmark's own manifest checks
(``cellbench/tests/test_manifest.py``, every case counting), holds the
configuration to ``hot-param-1k``'s widths and guarantees and the mix to the
issue's parameters, and runs the mix once at a tiny size on the CPU: the
tests' tiny hot-parameter deployment under the real traffic file at a smaller
rate, through the native door's data plane, the probe's sets sent as single
frames, the program's counters read by the new readers. About half a minute. (Named
to sort away from the ``test_cellbench_*_bridge.py`` files: under ``--dist
loadfile`` those start together, each with a server and generator processes
of its own, and the breaker family's cell on the CPU does not take a fourth
beside it.)
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CELLBENCH_TESTS = os.path.join(ROOT, "cellbench", "tests")
if _CELLBENCH_TESTS not in sys.path:
    sys.path.insert(0, _CELLBENCH_TESTS)

from test_manifest import *  # noqa: E402,F401,F403

from cellbench import deploy, manifest, run  # noqa: E402

CELL = "demo-cluster-param-1k.single-param-open"
TINY = "tiny-hotparam.tiny-single-param-open"
NEW = ("door.param_single_frames_per_pull",
       "lane.param_single_rows_per_dispatch",
       "door.control_param_frames_per_s", "param_single_step_roofline")
CHECKS = ("count", "item", "pair", "slide", "order", "crowd", "crowd_other")


def _config(name):
    return deploy.load_json(os.path.join(
        ROOT, "cellbench", "configs", name + ".json"))


def test_the_configuration_is_hot_param_1k_behind_the_demos_door():
    new, hot, demo = (_config("demo-cluster-param-1k"),
                      _config("hot-param-1k"), _config("demo-cluster-1k"))
    assert new["family"] == "hotparam" and new["reduced"] == []
    assert new["rules"] == hot["rules"]  # no width or scale is cut
    assert {k: v for k, v in new["param"].items() if k != "impl"} == {
        k: v for k, v in hot["param"].items() if k != "impl"}
    assert new["param"]["impl"] == "jax"  # what hot-param-1k's auto reads
    assert new["guarantees"][:4] == hot["guarantees"]  # word for word
    assert new["guarantees"][4:] == [
        "a single frame is answered exactly once by one frame of its own "
        "type and xid"]
    assert new["serve_buckets"] == demo["serve_buckets"] == [64, 256, 1024]
    assert new["door"] == demo["door"]
    assert new["engine"]["batch_size"] == demo["engine"]["batch_size"]
    assert (new["mesh_chips"], len(new["source"]) < 200) == (0, True)


def test_the_cell_is_the_open_loop_the_issue_states():
    cell = manifest.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    mix = dict(cell.traffic)
    # ISSUE 45's one fallback: 4,000, with what was read at 6,000 in the rule
    rule = mix.pop("rate_rule")
    assert rule.startswith("open loop, 4,000 one-value") and "6,000" in rule
    zipf = {"theta": 0.99}
    assert mix == {
        "name": "single-param-open", "loop": "open", "msg": "single",
        "rate_rows_per_s": 4000, "processes": 2, "connections": 4,
        "inflight_window_frames": 2048,
        "rules": dict(zipf, popularity="zipf"),
        "values": dict(zipf, dist="zipf"),
        "values_per_request": 1, "acquire": 1, "timeout_ms": 4000,
        "trace_sample": 0.1,
    }
    assert cell.chips == 1 and len(cell.cell["why"]) <= 200
    listed = {m["name"]: m for m in cell.per_layer()}
    for name in NEW:
        assert listed[name]["workloads"] == [CELL]
    assert "param_step_roofline" not in listed  # cell 5's list is cell 5's


def test_what_the_parent_cannot_read_here_lists_the_accepted_cells():
    """On the parent a type-2 frame never reaches a pull, so in this cell the
    readers of the door's spans and the lanes' waits find nothing, and the
    check refuses a parent's traced line that lacks an accepted metric asked
    of the cell. Those metrics list the eight accepted cells, whole and in
    the file's order; a later PR, whose parent serves the cell on the data
    plane, appends the ninth. (PR 49 appended its own cell, the tenth, which
    its parent serves with batch frames.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    accepted = [
        "mesh-100k.tenants-zipf-open", "demo-cluster-1k.single-token",
        "mesh-100k.sidecar-sat", "mesh-100k-pod4.tenants-zipf-open",
        "hot-param-1k.keys-zipf-open",
        "shaped-mesh-100k.tenants-zipf-prio-open",
        "breaker-mesh-100k.tenants-zipf-health-cycle-open",
        "concurrent-mesh-100k.tenants-zipf-hold-open"]
    assert accepted == [w["name"] for w in bench["workloads"]][:8]
    # PR 49's cell, whose parent serves it on the data plane, is the last
    later = ["param-mesh-100k.tenants-zipf-callers-open"]
    nothing_to_read = {
        "door.intake_avg_ms", "door.rx_to_pull_avg_ms",
        "door.pull_wake_avg_ms", "door.submit_to_wire_avg_ms",
        "door.residence_p50_ms", "door.residence_p95_ms",
        "door.residence_unattributed_avg_ms", "lane.queue_wait_p50_ms",
        "lane.queue_wait_avg_ms", "lane.permit_wait_avg_ms",
        "lane.reply_queue_wait_avg_ms", "lane.fused_frames_per_dispatch",
        "service.decide_avg_ms", "client.outside_server_p50_ms"}
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert len(accepted) == 8 and CELL not in accepted
    for name in nothing_to_read:
        assert lists[name] == accepted + later, name


# -- the mix at a tiny size ------------------------------------------------------
def _tiny_manifest(tmp) -> str:
    """The tests' manifest with the tiny hot-parameter deployment, the real
    mix at a smaller rate, and the real file's per-layer entries that the
    ninth cell reports."""
    here = _CELLBENCH_TESTS
    extra = os.path.join(here, "extra")
    os.makedirs(os.path.join(tmp, "more", "traffic"))
    mix = deploy.load_json(os.path.join(
        ROOT, "cellbench", "traffic", "single-param-open.json"))
    mix.update(name="tiny-single-param-open", rate_rows_per_s=1000,
               processes=1, connections=2, inflight_window_frames=256,
               timeout_ms=3000)
    with open(os.path.join(tmp, "more", "traffic",
                           "tiny-single-param-open.json"), "w") as f:
        json.dump(mix, f)
    bench = deploy.load_json(os.path.join(here, "manifest.json"))
    real = deploy.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["paths"] = [os.path.relpath(os.path.dirname(here), tmp),
                      os.path.relpath(extra, tmp), "more"]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(here, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-hotparam", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(extra, "configs", "tiny-hotparam.json"), tmp),
        "why": "test"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny-hotparam",
        "traffic": "tiny-single-param-open", "chips": 1, "why": "test"})
    bench["per_layer"] = [dict(m, workloads=[TINY]) for m in real["per_layer"]
                          if m["name"] in NEW]
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the window's counters
    before and after, the manifest)``."""
    snaps = []
    counters = run.server_counters

    def keep():
        snaps.append(counters())
        return snaps[-1]

    path = _tiny_manifest(str(tmp_path_factory.mktemp("cell")))
    lines = []
    run.server_counters = keep
    try:
        result = run.run_cell(path, TINY, seed=2_147_483_777, seconds=1.5,
                              trace=0, require_chip=False, out=lines.append)
    finally:
        run.server_counters = counters
    return result, lines, snaps[-2], snaps[-1], path


def test_the_mix_runs_through_the_doors_data_plane(sound):
    result, lines, before, after, _path = sound
    assert result["correct"] is True and result["failed"] == 0, lines[-15:]
    a, b = before["stages"], after["stages"]
    # every frame of the window was a single frame on the data plane ...
    taken = b["param_single_frames_total"] - a["param_single_frames_total"]
    assert taken >= result["attempted"] > 1000
    # ... and none reached the control loop
    assert b["param_control_frames_total"] == a["param_control_frames_total"]
    assert (b["param_single_rows_total"]
            - a["param_single_rows_total"]) == taken  # one value a frame


@pytest.mark.parametrize("check", CHECKS)
def test_the_probes_sets_go_as_single_frames_and_agree(sound, check):
    result, lines = sound[:2]
    got, limit = result["compared"]["probe_" + check]
    assert limit == (5 if check == "crowd" else 0)
    assert got <= limit, [ln for ln in lines if "probe" in ln]


def test_the_windows_replies_hold_the_guarantees(sound):
    compared = sound[0]["compared"]
    assert compared["requests_answered_NO_RULE"] == [0, 0]
    assert compared["rows_answered_twice"] == [0, 0]
    assert compared["rows_with_an_unknown_status"] == [0, 0]
    assert compared["admitted_over_count"][0] <= 1.0
    assert compared["generators_lost"] == [0, 0]


def test_the_new_readers_read_the_programs_counters(sound):
    _result, _lines, before, after, path = sound
    readers = manifest.Cell(path, TINY).readers()
    snap = {"before": before, "after": after}
    per_pull = readers["door.param_single_frames_per_pull"].reduce(snap)
    per_dispatch = readers["lane.param_single_rows_per_dispatch"].reduce(snap)
    assert 1.0 <= per_pull <= per_dispatch <= 256  # the in-flight window
    assert readers["door.control_param_frames_per_s"].reduce(snap) == 0.0


@pytest.mark.parametrize("name", NEW[:3])
def test_a_program_without_the_counters_reads_nothing(sound, name):
    """The parent commit has none of them: the reader returns None there and
    the line leaves the metric out."""
    before, after, path = sound[2:]
    bare = {k: dict(c, stages={n: v for n, v in c["stages"].items()
                               if not n.startswith("param_single_")
                               and n != "param_control_frames_total"})
            for k, c in (("before", before), ("after", after))}
    assert manifest.Cell(path, TINY).readers()[name].reduce(bare) is None


def test_the_roofline_reader_calls_the_accepted_model(sound):
    from cellbench import param_roofline

    path = sound[4]
    reader = manifest.Cell(path, TINY).readers()["param_single_step_roofline"]
    config = _config("demo-cluster-param-1k")
    peaks = {"bf16_flops_per_s": 197e12, "f32_highest_passes": 6,
             "hbm_bytes_per_s": 819e9}
    snap = {
        "config": config, "device_kind": "TPU v5 lite", "slice_s": 3.0,
        "peaks": {"TPU v5 lite": peaks},
        "events": [{"stage": "device_in", "shard": 1, "aux": 200}] * 900
        + [{"stage": "device_in", "shard": 0, "aux": 512}],
        "trace": {"modules": [("jit_param_decide_b256", 0.9),
                              ("jit_decide_b64_mixed", 0.3)]},
    }
    want = 100.0 * param_roofline.least_seconds(
        [200] * 900, 3.0, config, peaks) / 0.9
    assert reader.reduce(snap) == pytest.approx(want) and 0 < want < 100
    assert reader.reduce(dict(snap, events=[])) is None
    assert reader.reduce(dict(snap, config={"serve_buckets": [64]})) is None
