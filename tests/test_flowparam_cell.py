"""The tenth cell's files (PR 49): the family ``flowparam`` (flow rules and
hot-parameter rules of the same resources on one token server) against its
call-level reference on the CPU at a tiny size, through the real service and
the native door; the mix; the manifest entries; the device lane's counters of
its turn between kinds; the seven new readers on synthetic snapshots. Two
runs of a tiny cell, about a minute. (Named to sort away from the
``test_cellbench_*_bridge.py`` files, as ``test_param_single_cell.py`` is.)
"""

import json
import os
import queue
import threading
import time

import numpy as np
import pytest

from cellbench import deploy, manifest, run
from cellbench.families import flowparam, flowparam_reference, hotparam

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "cellbench", "tests")
EXTRA = os.path.join(HERE, "extra")
CELL = "param-mesh-100k.tenants-zipf-callers-open"
TINY = "tiny-flowparam.tiny-callers-open"
FLOW_CHECKS = ("tight", "big", "guard", "paced")
PARAM_CHECKS = ("count", "item", "pair", "slide", "order", "crowd",
                "crowd_other")
NEW_CHECKS = ("collide", "chain", "interleave")
NEW_READERS = ("lane.kind_switches_per_s", "lane.held_pull_share",
               "lane.held_wait_avg_ms", "lane.param_rows_share",
               "service.flow_decide_avg_ms", "service.param_decide_avg_ms",
               "step.decide_device_ms_per_flow_dispatch")
F, P = flowparam.FLOW, flowparam.PARAM


def _json(*parts):
    return deploy.load_json(os.path.join(ROOT, "cellbench", *parts))


def _bench():
    return deploy.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def tiny() -> flowparam.Deployment:
    return deploy.load(os.path.join(EXTRA, "configs", "tiny-flowparam.json"),
                       [os.path.dirname(HERE)])


def _tiny_mix() -> dict:
    return deploy.load_json(os.path.join(EXTRA, "traffic",
                                         "tiny-callers-open.json"))


# -- the manifest's entries ----------------------------------------------------
def test_the_configuration_joins_mesh_100k_and_hot_param_1k():
    new, mesh, hot = (_json("configs", "param-mesh-100k.json"),
                      _json("configs", "mesh-100k.json"),
                      _json("configs", "hot-param-1k.json"))
    assert new["family"] == "flowparam"
    for key in ("engine", "ns_max_qps", "serve_buckets", "fuse_depths",
                "door", "mesh_chips", "rules", "reduced", "pod_chips"):
        assert new[key] == mesh[key], key  # mesh-100k's, key for key
    assert new["param"] == dict(hot["param"], impl="jax")
    pr = new["param_rules"]
    for key in ("values_per_rule", "count", "hot_values", "hot_count",
                "metered_hot", "metered_cold", "crowd_values", "crowd_fresh",
                "crowd_limit"):
        assert pr[key] == hot["rules"][key], key  # no width or scale is cut
    assert (pr["ranks"], pr["n_rules"]) == (8, 496)
    every = mesh["guarantees"] + hot["guarantees"]
    assert all(g in new["guarantees"] for g in every)
    assert new["guarantees"][-2:] == [
        "a param request never changes a flow's count, nor a flow request a "
        "caller's",
        "the namespace guard counts flow requests only (departure: upstream "
        "counts both)"]
    assert len(new["source"]) <= 200
    for word in ("DemoClusterInitFunc", "ParamFlowSlot", "FlowSlot",
                 "BASELINE.json configs[4]"):
        assert word in new["source"], word
    assert "ClusterParamFlowChecker" in new["assumed"]["namespace_guard"]


def test_the_cell_and_its_mix_are_what_the_issue_states():
    cell = manifest.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    mix = dict(cell.traffic)
    cell1 = _json("traffic", "tenants-zipf-open.json")
    assert "knee" in mix.pop("rate_rule") and "flow token" in mix.pop(
        "departure")
    rate = mix.pop("rate_rows_per_s")
    assert rate % 40_000 == 0 and 0 < rate <= 240_000
    assert mix == {
        "name": "tenants-zipf-callers-open", "loop": "open", "msg": "batch",
        "frame_rows": 1024, "processes": 1, "connections": 4,
        "inflight_window_frames": 256, "tenants": cell1["tenants"],
        "flows": cell1["flows"], "acquire": cell1["acquire"],
        "param_frames": {"of_every": 4, "acquire": 1,  # one caller each
                         "callers": {"dist": "zipf", "theta": 0.99}},
        "timeout_ms": 4000, "trace_sample": 1.0}
    assert cell.chips == 1 and len(cell.cell["why"]) <= 200
    assert f"{rate // 1000}k rows/s" in cell.cell["why"]
    bench = _bench()
    config = next(c for c in bench["configs"]
                  if c["name"] == "param-mesh-100k")
    assert config["reduced"] == ["pod_chips"]
    assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1] is config


def test_the_cell_is_listed_where_its_readers_find_something():
    lists = {m["name"]: m.get("workloads") for m in _bench()["per_layer"]}
    # appended as one run, in this order; later PRs append behind them
    names = [m["name"] for m in _bench()["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + len(NEW_READERS)] == list(NEW_READERS)
    for name in NEW_READERS:
        assert lists[name] == [CELL], name
    for name in ("param_step_roofline", "step.param_device_ms_per_dispatch",
                 "lane.param_values_per_dispatch",
                 "service.param_blocked_share", "client.send_lag_p99_ms"):
        assert lists[name][-1] == CELL, name
    # readers that assume one kind a trace, and the native prep's own list
    for name in ("step.decide_device_ms_per_dispatch", "decide_step_roofline",
                 "service.param_native_prep_share"):
        assert CELL not in lists[name], name
    readers = manifest.Cell(os.path.join(ROOT, "BENCHMARK.json"),
                            CELL).readers()
    assert all(name in readers for name in NEW_READERS)


# -- the deployment --------------------------------------------------------------
def test_the_real_deployment_holds_both_tables():
    dep = deploy.load(os.path.join(ROOT, "cellbench", "configs",
                                   "param-mesh-100k.json"))
    assert dep.family is flowparam
    flows = list(dep.flow_rules())
    assert len(flows) == len({f[0] for f in flows}) == 100_000
    assert dep.flow.flows_per_namespace() == 1562  # cell 1's ranks
    params = list(dep.param_rules())
    assert len(params) == len({p[0] for p in params}) == 496 + 12 + 12
    traffic = [p for p in params if 2_000_000 <= p[0] < 3_000_000]
    assert len(traffic) == 496
    # a traffic rule sits on a flow of ranks 0-7, in the flow's namespace,
    # and shares its number with no flow rule
    flow_ids = {f[0] for f in flows}
    for rule, count, items, ns in traffic:
        fid = rule - 2_000_000
        assert fid // 64 < 8 and ns == f"ns{fid % 64}" and count == 5
        assert len(items) == 2 and rule not in flow_ids
        assert fid % 64 < 62  # never a probe namespace
    assert len(dep.param_rule_of()) == 496 + 2 * 4
    assert len(dep.ledger_counts()) == 256 + 496 * 128 + 2
    # the one pair that does share a number is the probe's
    fid, _count, rule = dep.extra(0)["collide"]
    assert fid == rule and fid in flow_ids and rule in {p[0] for p in params}
    assert (dep.window_ms, dep.bucket_ms) == (1000, 500)


def test_the_ledger_holds_both_kinds_of_key():
    dep = tiny()
    n_flow = len(dep.flow.ledger_counts())
    n_keys = n_flow + len(dep.param.ledger_counts())
    hot_flow = int(dep.flow.flow_id(1, 0))  # namespace 1's hottest: metered
    rule = int(dep.param_id(hot_flow))
    index = int(dep.param_index(1, 0))
    kind = np.array([F, F, P, P, P], np.int8)
    ids = np.array([hot_flow, hot_flow, rule, rule, 77], np.int64)
    acq = np.array([3, 1, 1, 1, 1], np.int32)
    hashes = np.zeros((5, 1), np.int64)
    hashes[2:4, 0] = hotparam.value_hash(index, np.array([0, 0]))
    status = np.array([deploy.OK, deploy.BLOCKED, deploy.OK, deploy.BLOCKED,
                       deploy.NO_RULE], np.uint8)
    decided, brown, never, keys, tokens = dep.ledger_view(
        (kind, ids, acq, hashes), status, np.ones(5, np.int32))
    assert decided.all() and not brown.any() and never == 1
    flow_key = int(dep.flow.metered_index(np.array([hot_flow]))[0])
    assert keys.tolist() == [flow_key, n_flow + index * 16, n_keys,
                             n_keys + 1]
    assert tokens.tolist() == [3, 1, 2, 3]  # tokens, tokens, F rows, P rows
    adm = np.zeros((n_keys + 2, 30))
    adm[n_keys, 3], adm[n_keys + 1, 3] = 300, 100
    checks = dep.window_checks({"never_rows": 0, "admitted": adm,
                                "lat_max": np.zeros(30)})
    assert [c[1:] for c in checks] == [(0, 0), (0, 100), (0, 1_000_000)]
    adm[n_keys + 1, 3] = 120  # 28.6 % of the decided rows
    assert dep.window_checks({"never_rows": 0, "admitted": adm,
                              "lat_max": np.zeros(30)})[1][1] == 357
    adm[flow_key, 5] = 41  # hot flows of the tiny table: 40 a second
    assert dep.window_checks({"never_rows": 0, "admitted": adm,
                              "lat_max": np.zeros(30)})[2][1] == 1_025_000


# -- the mix ---------------------------------------------------------------------
def test_each_connections_own_sequence_is_f_f_f_p():
    dep, tr = tiny(), _tiny_mix()
    kind, ids, acq, hashes = flowparam.Mix(tr, dep, 5, 1).frames(64)
    assert kind.shape == ids.shape == acq.shape == (64, 64)
    assert hashes.shape == (64, 64, 1)
    assert (kind == kind[:, :1]).all()  # a frame is of one kind
    of = kind[:, 0]
    for conn in range(4):  # frame k goes to connection k mod 4: its own
        # sequence is the cycle F F F P, a frame later on each connection
        cycle = ([F, F, F, P] * 5)[conn:conn + 16]
        assert of[conn::4].tolist() == cycle, conn
    for k in range(0, 64, 4):  # of every four consecutive frames, one
        assert (of[k:k + 4] == P).sum() == 1
    assert (kind == P).mean() == 0.25


def test_param_rows_sit_on_the_ruled_ranks_of_their_frames_tenant():
    dep, tr = tiny(), _tiny_mix()
    mix = flowparam.Mix(tr, dep, 5, 1)
    who = mix.frame_tenants(400)
    kind, ids, acq, hashes = mix.rows(who)
    par = kind[:, 0] == P
    fid = ids[par] - dep.id_base
    assert (fid // 8 < dep.ranks).all() and (fid // 8 >= 0).all()
    assert (fid % 8 == who[par][:, None]).all()  # one tenant's frame
    assert (acq[par] == 1).all() and (hashes[~par] == 0).all()
    # the flow frames are the flow family's rows: every rank, mixed acquire
    assert (ids[~par] // 8).max() > 100 and set(np.unique(acq[~par])) == set(
        range(1, 9))
    assert (ids[~par] % 8 == who[~par][:, None]).all()
    # a caller's hash is the ledger's: the hottest caller of a rule is metered
    ns, rank = 1, 0
    h = hotparam.value_hash(dep.param_index(ns, rank), 0)
    assert h in hashes[par][ids[par] == dep.param_id(dep.flow.flow_id(
        ns, rank))]
    again = flowparam.Mix(tr, dep, 5, 1).rows(who)
    other = flowparam.Mix(tr, dep, 6, 1).rows(who)
    assert (again[3] == hashes).all() and (other[3] != hashes).any()


def test_a_frame_is_of_one_kind_on_the_wire():
    ids, acq = np.array([3, 4]), np.array([1, 2], np.int32)
    hashes = np.array([[5], [6]])
    assert flowparam.encode_batch(9, np.array([F, F]), ids, acq,
                                  hashes)[6] == 5
    raw = flowparam.encode_batch(9, np.array([P, P]), ids, acq, hashes)
    assert raw == hotparam.encode_batch(9, ids, acq, hashes) and raw[6] == 27
    with pytest.raises(ValueError):
        flowparam.encode_batch(9, np.array([F, P]), ids, acq, hashes)
    assert flowparam.BATCH_REPLIES[0] == (5, 27)
    assert flowparam.MAX_ROWS_PER_FRAME == hotparam.MAX_ROWS_PER_FRAME


# -- the call-level reference, by hand ---------------------------------------------
def test_a_call_is_the_param_check_then_the_flow_check():
    ok, blocked = deploy.OK, deploy.BLOCKED
    ref = flowparam_reference.Reference(
        {1: (3, "a", deploy.DEFAULT), 2: (2, "a", deploy.DEFAULT),
         7: (1, "a", deploy.DEFAULT)},
        {7: (2, {}), 11: (1, {})}, {1: 11}, 100, (100, 10), (500, 2))
    with open(flowparam_reference.__file__, encoding="utf-8") as f:
        assert not [ln for ln in f if "import" in ln and "sentinel_tpu" in ln]
    # resource 1 carries rule 11 (one token a caller): caller 5 passes once
    assert ref.call(0, 1, 1, 5) == (ok, ok)
    assert ref.call(0, 1, 1, 5) == (blocked, None)  # and took no flow token
    assert ref.call(0, 1, 1, 6) == (ok, ok)
    assert ref.call(0, 1, 1, 8) == (ok, ok)  # the flow's third and last
    assert ref.call(0, 1, 1, 9) == (ok, blocked)  # the caller stays counted
    assert ref.call(0, 1, 1, 9) == (blocked, None)
    # resource 2 has no param rule: the flow check alone
    assert ref.calls(0, [2, 2, 2], [1, 1, 1], [5, 5, 5]) == [
        (None, ok), (None, ok), (None, blocked)]
    # rule 7 and flow 7 share a number and nothing else
    assert ref.param_frame(0, [7, 7, 7], [1, 1, 1], [[5]] * 3) == [
        ok, ok, blocked]
    assert ref.flow_frame(0, [7, 7], [1, 1]) == [ok, blocked]
    # the guard saw the six flow requests that were made, no param request
    assert ref.flow.ns_win["a"].total(0) == 4 + 3 + 2


# -- the device lane's turn between kinds ------------------------------------------
class _Both:
    """A service with both dispatch halves, answering OK."""

    def _ok(self, n):
        return lambda: (np.zeros(n, np.int8), np.zeros(n, np.int32),
                        np.zeros(n, np.int32))

    def dispatch_batch_arrays(self, ids, counts, prios):
        time.sleep(0.002)  # a dispatch's host half
        return self._ok(len(ids))

    def dispatch_params_batch(self, ids, counts, hashes):
        time.sleep(0.002)
        return self._ok(len(ids))


def _pull(n: int, nv: int):
    frames = (None, None, np.arange(1, dtype=np.int64),
              np.array([n]), np.array([27 if nv else 5]))
    now = time.monotonic_ns()
    return (np.arange(n, dtype=np.int64), np.ones(n, np.int32),
            np.zeros(n, np.uint8), frames, now, None, None,
            np.zeros((n, nv), np.int64) if nv else None, now, nv)


def test_a_pull_of_another_kind_is_held_and_counted():
    """F, P, F queued on a lane that is not running: the lane takes the
    first, sets the second aside, and so on; one switch a turn after the
    first, two turns from ``held``, each with a wait above zero."""
    from sentinel_tpu.cluster.server_native import NativeTokenServer
    from sentinel_tpu.metrics.server import (reset_server_metrics_for_tests,
                                             server_metrics)
    from sentinel_tpu.trace import ring

    reset_server_metrics_for_tests()
    server = NativeTokenServer(_Both(), host="127.0.0.1", port=0,
                               max_device_inflight=8)  # nothing reads here
    server._shard_qs = [queue.Queue()]
    server._dispatch_sem = threading.Semaphore(0)
    server._reply_q = queue.Queue()
    for item in (_pull(30, 0), _pull(20, 1), _pull(10, 0), server._SENTINEL):
        server._shard_qs[0].put(item)
        server._dispatch_sem.release()
    ring.arm(sample=0.0)
    since = time.monotonic_ns()
    try:
        lane = threading.Thread(target=server._device_loop, daemon=True)
        lane.start()
        lane.join(timeout=20)
        assert not lane.is_alive()
        turns = ring.events(since_ns=since, stages={ring.LANE_TURN})
    finally:
        ring.disarm()
    got = server_metrics().stage_snapshot()
    assert got["lane_turns_flow_total"] == 2
    assert got["lane_turns_param_total"] == 1
    assert got["lane_turns_concurrent_total"] == 0
    assert got["lane_turn_rows_flow_total"] == 40
    assert got["lane_turn_rows_param_total"] == 20
    assert got["lane_turn_pulls_flow_total"] == 2
    assert got["lane_kind_switches_total"] == 2
    assert got["lane_held_turns_total"] == 2  # P behind F, then F behind P
    assert got["lane_held_wait_ms_total"] > 2 * 1.5  # a dispatch each
    assert got["lane_queue_wait_ms_param_total"] > 1.5
    assert got["lane_queue_wait_ms_flow_total"] > 3.0  # the third waited two
    assert got["queue_wait_ms"]["count"] == 3
    assert [e["shard"] for e in turns] == [ring.PARAM_LANE, 0]
    assert all(e["aux"] >= 1500 for e in turns)  # us in ``held``
    # the reply lane's half: decide_ms by kind
    assert server._reply_q.qsize() == 3 + 1  # three groups and the sentinel
    for kind, ms in (("flow", 2.0), ("param", 3.0), ("flow", 4.0)):
        server_metrics().count_lane_decide(kind, ms)
    got = server_metrics().stage_snapshot()
    assert (got["lane_decides_flow_total"],
            got["lane_decide_ms_flow_total"]) == (2, 6.0)
    assert (got["lane_decides_param_total"],
            got["lane_decide_ms_param_total"]) == (1, 3.0)
    assert "sentinel_server_lane_kind_switches_total 2" in (
        server_metrics().render())
    reset_server_metrics_for_tests()
    assert server_metrics().stage_snapshot()["lane_held_turns_total"] == 0


# -- the seven readers -------------------------------------------------------------
def _readers():
    return manifest.Cell(os.path.join(ROOT, "BENCHMARK.json"), CELL).readers()


def test_the_new_readers_read_nothing_on_a_parents_tree():
    r = _readers()
    old = {"before": {"stages": {}}, "after": {"stages": {}}, "events": [],
           "seconds": 20.0, "trace": {"modules": [["jit__unknown(1)", 0.5]]}}
    assert [r[n].reduce(old) for n in NEW_READERS] == [None] * 7


def test_the_new_readers_read_the_lanes_counters():
    r = _readers()
    kinds = ("flow", "param", "concurrent")
    before = dict.fromkeys(
        [f"lane_{what}_{k}_total" for k in kinds for what in (
            "turns", "turn_rows", "decides", "decide_ms")]
        + ["lane_kind_switches_total", "lane_held_turns_total",
           "lane_held_wait_ms_total"], 10)
    after = dict(before)
    after.update(lane_turns_flow_total=3010, lane_turns_param_total=1010,
                 lane_turn_rows_flow_total=3_072_010,
                 lane_turn_rows_param_total=1_024_010,
                 lane_kind_switches_total=2010, lane_held_turns_total=210,
                 lane_held_wait_ms_total=160.0, lane_decides_flow_total=3010,
                 lane_decide_ms_flow_total=3010.0,
                 lane_decides_param_total=1010,
                 lane_decide_ms_param_total=1510.0)
    snap = {"before": {"stages": before}, "after": {"stages": after},
            "seconds": 20.0}
    assert r["lane.kind_switches_per_s"].reduce(snap) == 100.0
    assert r["lane.held_pull_share"].reduce(snap) == 5.0
    assert r["lane.held_wait_avg_ms"].reduce(snap) == 0.75
    assert r["lane.param_rows_share"].reduce(snap) == 25.0
    assert r["service.flow_decide_avg_ms"].reduce(snap) == 1.0
    assert r["service.param_decide_avg_ms"].reduce(snap) == 1.5
    quiet = {"before": {"stages": before}, "after": {"stages": before},
             "seconds": 20.0}
    assert r["lane.kind_switches_per_s"].reduce(quiet) == 0.0
    for name in NEW_READERS[1:6]:
        assert r[name].reduce(quiet) is None, name


def test_the_decide_steps_time_is_over_the_flow_dispatches_alone():
    r = _readers()
    snap = {"events": [{"stage": "device_in", "aux": 1024, "shard": 0},
                       {"stage": "device_in", "aux": 1024, "shard": 0},
                       {"stage": "device_in", "aux": 1024, "shard": 0},
                       {"stage": "device_in", "aux": 1024, "shard": 1},
                       {"stage": "device_out", "aux": 1024, "shard": 0}],
            "trace": {"modules": [["jit_decide_b1024_mixed(3)", 0.0009],
                                  ["jit_param_decide_b1024(7)", 0.0005]]}}
    per_flow = r["step.decide_device_ms_per_flow_dispatch"].reduce(snap)
    assert per_flow == pytest.approx(0.3)
    # the accepted reader divides by the param dispatch too
    assert r["step.decide_device_ms_per_dispatch"].reduce(
        snap) == pytest.approx(0.225)
    assert r["step.param_device_ms_per_dispatch"].reduce(
        snap) == pytest.approx(0.5)
    only_param = dict(snap, events=snap["events"][3:])
    assert r["step.decide_device_ms_per_flow_dispatch"].reduce(
        only_param) is None


# -- the tiny cell, through the service and the native door -------------------------
def _tiny_manifest(tmp) -> str:
    bench = deploy.load_json(os.path.join(HERE, "manifest.json"))
    bench["paths"] = [os.path.relpath(os.path.dirname(HERE), tmp),
                      os.path.relpath(EXTRA, tmp)]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(HERE, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-flowparam", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(EXTRA, "configs", "tiny-flowparam.json"), tmp),
        "why": "test"})
    bench["workloads"].append({
        "name": TINY, "config": "tiny-flowparam",
        "traffic": "tiny-callers-open", "chips": 1, "why": "test"})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the window's counters
    before, and after)``."""
    snaps = []
    counters = run.server_counters

    def keep():
        snaps.append(counters())
        return snaps[-1]

    lines = []
    run.server_counters = keep
    try:
        result = run.run_cell(
            _tiny_manifest(str(tmp_path_factory.mktemp("cell"))), TINY,
            seed=2_147_483_749, seconds=1.5, trace=0, require_chip=False,
            out=lines.append)
    finally:
        run.server_counters = counters
    return result, lines, snaps[-2]["stages"], snaps[-1]["stages"]


def test_the_cell_runs_both_kinds_through_one_door(sound):
    result, lines = sound[:2]
    assert result["correct"] is True and result["failed"] == 0, lines[-25:]
    assert result["attempted"] == 3072  # 48 frames of 64 rows, 12 of them P
    assert any("2036 rules" in ln for ln in lines)  # 2000 flow + 36 param
    assert any("warm-up: 256 param requests in process" in ln for ln in lines)
    assert any("depth-4 backlog" in ln for ln in lines)
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)


@pytest.mark.parametrize("check", FLOW_CHECKS + PARAM_CHECKS + NEW_CHECKS)
def test_every_probe_set_agrees_with_its_reference(sound, check):
    result, lines = sound[:2]
    got, limit = result["compared"]["probe_" + check]
    assert limit == (5 if check == "crowd" else 0)
    assert got <= limit, [ln for ln in lines if "probe" in ln]


def test_the_windows_replies_hold_the_guarantees_of_both_kinds(sound):
    compared = sound[0]["compared"]
    assert compared[
        "unmetered_flow_rows_BLOCKED_or_param_requests_NO_RULE"] == [0, 0]
    assert compared["param_rows_share_of_decided_rows_off_25_percent_in_"
                    "hundredths_of_a_point"] == [0, 100]
    got, limit = compared[
        "flow_tokens_admitted_per_flow_window_over_count_in_millionths"]
    assert 0 < got <= limit == 1_000_000  # hot flows were asked past theirs
    assert compared["rows_answered_twice"] == [0, 0]
    assert 0 < compared["admitted_over_count"][0] <= 1.0
    assert compared["generators_lost"] == [0, 0]


def test_the_window_took_the_lanes_turn_between_kinds(sound):
    a, b = sound[2:]
    grew = {k: b[k] - a[k] for k in b if k.startswith("lane_")}
    assert grew["lane_turn_rows_flow_total"] == 36 * 64
    assert grew["lane_turn_rows_param_total"] == 12 * 64
    assert grew["lane_turn_rows_concurrent_total"] == 0
    assert 0 < grew["lane_turns_param_total"] <= 12
    # every param dispatch stands between flow dispatches
    assert grew["lane_kind_switches_total"] >= grew["lane_turns_param_total"]
    assert grew["lane_turns_param_total"] == (
        b["param_dispatch_total"] - a["param_dispatch_total"])
    assert (grew["lane_decides_flow_total"] + grew["lane_decides_param_total"]
            == b["decide_ms"]["count"] - a["decide_ms"]["count"])
    both = (grew["lane_decide_ms_flow_total"]
            + grew["lane_decide_ms_param_total"])
    assert both == pytest.approx(b["decide_ms"]["sum"] - a["decide_ms"]["sum"],
                                 abs=0.05)
    waits = (grew["lane_queue_wait_ms_flow_total"]
             + grew["lane_queue_wait_ms_param_total"])
    assert waits == pytest.approx(
        b["queue_wait_ms"]["sum"] - a["queue_wait_ms"]["sum"], abs=0.05)
    r = _readers()
    snap = {"before": {"stages": a}, "after": {"stages": b}, "seconds": 1.5}
    assert r["lane.param_rows_share"].reduce(snap) == 25.0
    assert r["lane.kind_switches_per_s"].reduce(snap) > 0
    assert r["service.param_decide_avg_ms"].reduce(snap) > 0
    assert r["service.flow_decide_avg_ms"].reduce(snap) > 0
    assert r["service.param_blocked_share"].reduce(snap) > 0
    assert r["lane.param_values_per_dispatch"].reduce(snap) >= 64


def test_a_param_request_charged_to_the_flow_of_its_number_is_caught(
        tmp_path):
    """``CrossTalk``: the one guarantee only this deployment has, broken.
    No traffic rule shares a number across the kinds, so the window and the
    sibling families' sets stay sound; ``collide`` reads it."""
    lines = []
    result = run.run_cell(_tiny_manifest(str(tmp_path)), TINY,
                          seed=2_147_483_750, seconds=1.5, trace=0,
                          require_chip=False,
                          wrap_service=flowparam.CONTROLS["cross_talk"],
                          out=lines.append)
    assert result["correct"] is False and result["failed"] == 0
    compared = result["compared"]
    assert compared["probe_collide"][0] >= 1, [
        ln for ln in lines if "probe" in ln]
    for check in FLOW_CHECKS + PARAM_CHECKS + ("chain",):
        assert compared["probe_" + check][0] <= compared[
            "probe_" + check][1], check
    assert compared["admitted_over_count"][0] <= 1.0


def test_the_sibling_families_controls_stand_in_both_entries():
    """The device lane asks a served object through the dispatch halves its
    own class defines: a control of this family defines all four, so the
    other kind is never asked a row at a time."""

    class Service:
        def dispatch_batch_arrays(self, ids, acq=None, prios=None):
            return lambda: (np.full(len(ids), deploy.BLOCKED, np.int8),
                            np.zeros(len(ids), np.int32),
                            np.zeros(len(ids), np.int32))

        def request_batch_arrays(self, ids, acq=None, prios=None):
            return self.dispatch_batch_arrays(ids)()

        dispatch_params_batch = dispatch_batch_arrays
        request_params_batch = request_batch_arrays
        marker = "the service's own"

    assert sorted(flowparam.CONTROLS) == ["cross_talk", "over_admit",
                                          "param_over_admit"]
    ids, acq = np.arange(4, dtype=np.int64), np.ones(4, np.int32)
    hashes = np.zeros((4, 1), np.int64)
    for name, flow_ok, param_ok in (("over_admit", 1, 0),
                                    ("param_over_admit", 0, 4),
                                    ("cross_talk", 0, 0)):
        served = flowparam.CONTROLS[name](Service())
        for half in ("dispatch_batch_arrays", "request_batch_arrays",
                     "dispatch_params_batch", "request_params_batch"):
            assert getattr(type(served), half, None) is not None, half
        assert served.marker == "the service's own"
        assert (served.request_batch_arrays(ids, acq)[0]
                == deploy.OK).sum() == flow_ok, name
        assert (served.request_params_batch(ids, acq, hashes)[0]
                == deploy.OK).sum() == param_ok, name
