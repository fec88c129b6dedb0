"""The hot-parameter family (``cellbench/families/hotparam.py``) at a tiny
size on the CPU: one cell end to end through the native door's data plane,
the probe's six checks, the control caught; the reference by hand; the
frames, the ledger, the mix, the roofline and the readers."""

import json
import os

import numpy as np
import pytest

from cellbench import deploy, param_roofline, run
from cellbench.families import hotparam, hotparam_reference

HERE = os.path.dirname(os.path.abspath(__file__))
EXTRA = os.path.join(HERE, "extra")
CELL = "tiny-hotparam.tiny-keys-open"
CHECKS = ("count", "item", "pair", "slide", "order", "crowd", "crowd_other")


def manifest(tmp) -> str:
    """The tests' manifest with the tiny deployment and its cell added, and
    the four new per-layer entries: by entries alone, as BENCHMARK.json."""
    bench = deploy.load_json(os.path.join(HERE, "manifest.json"))
    bench["paths"] = [os.path.relpath(os.path.dirname(HERE), tmp),
                      os.path.relpath(EXTRA, tmp)]
    for c in bench["configs"]:
        c["file"] = os.path.relpath(os.path.join(HERE, c["file"]), tmp)
    bench["configs"].append({
        "name": "tiny-hotparam", "source": "test", "reduced": [],
        "file": os.path.relpath(
            os.path.join(EXTRA, "configs", "tiny-hotparam.json"), tmp),
        "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-hotparam", "traffic": "tiny-keys-open",
        "chips": 1, "why": "test"})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f)
    return path


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """One run of the tiny cell: ``(result, lines, the program's counters
    before, and after)``."""
    from sentinel_tpu.metrics.server import server_metrics

    lines = []
    before = server_metrics().stage_snapshot()
    result = run.run_cell(manifest(str(tmp_path_factory.mktemp("cell"))),
                          CELL, seed=2_147_483_690, seconds=1.5, trace=0,
                          require_chip=False, out=lines.append)
    return result, lines, before, server_metrics().stage_snapshot()


def test_the_cell_runs_through_the_doors_data_plane(sound):
    result, lines = sound[:2]
    assert result["correct"] is True and result["failed"] == 0, lines[-15:]
    assert result["attempted"] == 2944  # 46 frames of 64 requests
    assert any("param path: impl 'auto' resolved to 'jax'" in ln
               for ln in lines)
    assert any("warm-up: 256 requests of 1 value(s) in process" in ln
               for ln in lines)


@pytest.mark.parametrize("check", CHECKS)
def test_the_probes_checks_read_inside_their_limits(sound, check):
    result, lines = sound[:2]
    got, limit = result["compared"]["probe_" + check]
    assert limit == (5 if check == "crowd" else 0)
    assert got <= limit, [ln for ln in lines if "probe" in ln]
    if check != "crowd":
        assert got == 0


def test_the_windows_replies_hold_the_guarantees(sound):
    compared = sound[0]["compared"]
    assert compared["requests_answered_NO_RULE"] == [0, 0]
    assert compared["rows_answered_twice"] == [0, 0]
    got, limit = compared["admitted_over_count"]
    assert 0 < got <= limit == 1  # hot values were asked past their count


def test_frames_of_several_requests_were_decided_by_fewer_dispatches(sound):
    lines, before, after = sound[1:]
    grew = {k: after[k] - before[k] for k in after
            if k.startswith("param_") and k.endswith("_total")}
    assert 0 < grew["param_dispatch_total"] < grew["param_requests_total"]
    # the pair check asks one request of two values
    assert grew["param_values_total"] > grew["param_requests_total"]
    assert grew["param_blocked_total"] > 0 == grew["param_no_rule_total"]
    # warmup() compiled every serve bucket: nothing compiled in the window
    assert not any("COMPILED INSIDE THE WINDOW" in ln for ln in lines)


def test_a_batched_service_that_over_admits_is_not_correct(tmp_path):
    lines = []
    result = run.run_cell(manifest(str(tmp_path)), CELL, seed=2_147_483_691,
                          seconds=1.5, trace=0, require_chip=False,
                          wrap_service=hotparam.CONTROLS["over_admit"],
                          out=lines.append)
    assert result["correct"] is False
    assert result["compared"]["probe_count"][0] >= 1
    assert result["compared"]["probe_crowd_other"][0] == 0  # count caught it


def test_the_reference_by_hand():
    a, b, c = 11, 12, 13
    ref = hotparam_reference.Reference({1: (3, {a: 5})}, 500, 2)
    assert [ref.decide(0, 1, 1, [a]) for _ in range(6)] == [0] * 5 + [1]
    assert ref.decide_all(0, [1, 1, 1], [1, 1, 1], [[b]] * 3) == [0, 0, 0]
    # b is exhausted: the pair is BLOCKED, and c stays counted
    assert ref.decide(100, 1, 1, [b, c]) == deploy.BLOCKED
    assert [ref.decide(100, 1, 1, [c]) for _ in range(3)] == [0, 0, 1]
    # an acquire larger than what is left is refused whole
    assert ref.decide(100, 1, 4, [14]) == deploy.BLOCKED
    assert ref.decide(100, 1, 3, [14]) == deploy.OK
    # the bucket that began at 0 is still one of the two at 999, gone at 1000
    assert ref.decide(999, 1, 1, [b]) == deploy.BLOCKED
    assert ref.decide(1000, 1, 1, [b]) == deploy.OK
    assert ref.decide(0, 2, 1, [a]) == deploy.NO_RULE


def test_batch_frames_are_the_programs_codec():
    from sentinel_tpu.cluster import protocol as P

    rules, acq = np.array([3, 4, 3]), np.array([1, 2, 1])
    hashes = np.array([[5, -6], [7, 8], [9, 10]])
    raw = hotparam.encode_batch(77, rules, acq, hashes)
    assert raw == P.encode_batch_param_request(77, rules, acq, hashes)
    assert raw[:10] == (b"\x00\x5f" + (77).to_bytes(4, "big") + b"\x1b"
                        + b"\x00\x03" + b"\x02")
    xid, ids, counts, _prios, back = P.decode_batch_param_request(raw[2:])
    assert (xid, ids.tolist(), counts.tolist()) == (77, [3, 4, 3], [1, 2, 1])
    assert back.tolist() == hashes.tolist()
    with pytest.raises(ValueError):
        hotparam.encode_batch(1, np.zeros(2300), np.zeros(2300),
                              np.zeros((2300, 2), np.int64))
    assert hotparam.MAX_ROWS_PER_FRAME == 2259


def tiny() -> hotparam.Deployment:
    return deploy.load(os.path.join(EXTRA, "configs", "tiny-hotparam.json"),
                       [os.path.dirname(HERE)])


def test_the_ledger_meters_hot_values_and_a_seeded_sample():
    dep = tiny()
    assert dep.family is hotparam
    assert dep.metered_ranks.shape == (8, 16)
    assert (dep.metered_ranks[:, :8] == np.arange(8)).all()
    assert (dep.metered_ranks[:, 8:] >= 8).all()
    assert (tiny().metered_ranks == dep.metered_ranks).all()  # the file's seed
    counts = dep.ledger_counts().reshape(8, 16)
    assert (counts[:, :2] == 10).all() and (counts[:, 2:] == 5).all()
    # rule 2: its hottest value passed twice (3 + 1 tokens), an unmetered
    # value passed, a metered value was BLOCKED
    cold = int(np.setdiff1d(np.arange(64), dep.metered_ranks[2])[0])
    ranks = np.array([0, cold, 0, 1])
    decided, brown, never, keys, tokens = dep.ledger_view(
        (np.full(4, 2), np.array([3, 1, 1, 1], np.int32),
         hotparam.value_hash(2, ranks)[:, None]),
        np.array([deploy.OK, deploy.OK, deploy.OK, deploy.BLOCKED], np.uint8),
        np.zeros(4, np.int32))
    assert decided.all() and not brown.any() and never == 0
    assert keys.tolist() == [2 * 16, 2 * 16] and tokens.tolist() == [3, 1]
    assert len(list(dep.rules())) == 8 + 2 * hotparam.PROBE_RULES_PER_SET


def test_the_mix_draws_requests_by_seed():
    dep = tiny()
    tr = deploy.load_json(os.path.join(EXTRA, "traffic",
                                       "tiny-keys-open.json"))
    tr["values_per_request"] = 2
    rules, acq, hashes = hotparam.Mix(tr, dep, 5, 1).frames(3)
    again = hotparam.Mix(tr, dep, 5, 1).frames(3)
    other = hotparam.Mix(tr, dep, 6, 1).frames(3)
    assert rules.shape == acq.shape == (3, 64) and hashes.shape == (3, 64, 2)
    assert (hashes == again[2]).all() and (hashes != other[2]).any()
    assert (hashes[..., 0] != hashes[..., 1]).all()  # a request's values differ
    assert rules.min() >= 0 and rules.max() < 8 and (acq == 1).all()
    # Zipf: the first rule is asked most
    assert np.bincount(rules.reshape(-1), minlength=8).argmax() == 0


def test_the_roofline_counts_what_the_step_moves():
    param = {"max_param_rules": 1024, "depth": 4, "width": 16384,
             "bucket_ms": 500, "n_buckets": 2}
    m = param_roofline.dispatch_model(1000, 1024, param)
    # gathered 1000 x (8 + 4), scattered 1000 x (8 + 4 + 4), packed 10 x
    # 1024 in and 3 x 1024 out, four bytes a cell
    assert m["bytes"] == 4 * (1000 * 12 + 1000 * 16 + 13 * 1024)
    assert param_roofline.stale_plane_bytes(param) == 1 << 28
    peaks = {"bf16_flops_per_s": 197e12, "f32_highest_passes": 6,
             "hbm_bytes_per_s": 819e9}
    cfg = {"param": param, "serve_buckets": [64, 1024]}
    least = param_roofline.least_seconds([1000] * 10, 3.0, cfg, peaks)
    assert least == pytest.approx((10 * m["bytes"] + 6 * (1 << 28)) / 819e9)


def readers(tmp):
    from cellbench import manifest as mf

    return mf.Cell(manifest(str(tmp)), CELL).readers()


def test_the_new_readers_read_the_programs_counters_or_nothing(tmp_path):
    r = readers(tmp_path)
    names = ("lane.param_values_per_dispatch", "service.param_blocked_share",
             "step.param_device_ms_per_dispatch", "param_step_roofline")
    assert all(n in r for n in names)
    old = {"before": {"stages": {}}, "after": {"stages": {}}, "events": [],
           "trace": {"modules": [["jit_decide_b64_uniform(1)", 0.5]]},
           "config": {}, "peaks": {}, "device_kind": "x", "slice_s": 3.0}
    assert [r[n].reduce(old) for n in names] == [None] * 4  # a parent's tree
    before = {"param_dispatch_total": 10, "param_values_total": 100,
              "param_requests_total": 50, "param_blocked_total": 5}
    after = {"param_dispatch_total": 30, "param_values_total": 2100,
             "param_requests_total": 1050, "param_blocked_total": 105}
    cfg = deploy.load_json(os.path.join(EXTRA, "configs",
                                        "tiny-hotparam.json"))
    snap = {"before": {"stages": before}, "after": {"stages": after},
            "events": [{"stage": "device_in", "aux": 64, "shard": 1},
                       {"stage": "device_in", "aux": 64, "shard": 1},
                       {"stage": "device_in", "aux": 9, "shard": 0}],
            "trace": {"modules": [["jit_param_decide_b64(7)", 0.004],
                                  ["jit_decide_b64_uniform(1)", 0.5]]},
            "config": cfg, "device_kind": "TPU v5 lite", "slice_s": 3.0,
            "peaks": deploy.load_json(os.path.join(
                os.path.dirname(HERE), "peaks.json"))}
    assert r[names[0]].reduce(snap) == 100.0
    assert r[names[1]].reduce(snap) == 10.0
    assert r[names[2]].reduce(snap) == 2.0
    share = r[names[3]].reduce(snap)
    assert 0 < share < 100
