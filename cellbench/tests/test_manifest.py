"""A cell, a deployment and a per-layer reader are added by files alone."""

import json
import os
import shutil

from cellbench import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_the_benchmark_file_finds_every_cell_and_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = manifest.Cell(os.path.join(ROOT, "BENCHMARK.json"), w["name"])
        assert os.path.exists(cell.config_file)
        assert cell.traffic["name"] == w["traffic"]
        readers = cell.readers()
        for m in cell.per_layer():
            r = readers[m["name"]]
            assert (r.UNIT, r.LAYER, r.MOVES, r.SOURCE) == (
                m["unit"], m["layer"], m["moves"], m["source"])
        assert {m["name"] for m in cell.end_to_end()} >= {"setup_s"}


def test_new_files_and_entries_alone_add_a_cell(tmp_path):
    """Copy the benchmark, add one config file, one traffic file, one reader
    file in a new directory and the entries that name them; no existing file
    is edited."""
    shutil.copytree(os.path.join(ROOT, "cellbench"), tmp_path / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    extra = tmp_path / "morecells"
    (extra / "configs").mkdir(parents=True)
    (extra / "traffic").mkdir()
    (extra / "layers").mkdir()
    with open(tmp_path / "cellbench/configs/demo-cluster-1k.json") as f:
        cfg = json.load(f)
    cfg["name"] = "demo-2k"
    cfg["rules"]["n_flows"] = 2000
    (extra / "configs/demo-2k.json").write_text(json.dumps(cfg))
    with open(tmp_path / "cellbench/traffic/single-token.json") as f:
        mix = json.load(f)
    mix["name"] = "single-token-16"
    mix["outstanding"] = 16
    (extra / "traffic/single-token-16.json").write_text(json.dumps(mix))
    (extra / "layers/client.rows.py").write_text(
        'NAME = "client.rows"\nUNIT = "rows"\nLAYER = "client"\n'
        'MOVES = "decided_verdicts_per_s"\nSOURCE = "program_counter"\n\n\n'
        'def reduce(snap):\n    return snap["client"]["attempted"]\n')
    bench["paths"].append("morecells")
    bench["configs"].append({"name": "demo-2k", "source": "test",
                             "file": "morecells/configs/demo-2k.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "demo-2k.single-token-16",
                               "config": "demo-2k",
                               "traffic": "single-token-16", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "client.rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "client",
        "moves": "decided_verdicts_per_s",
        "workloads": ["demo-2k.single-token-16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = manifest.Cell(str(tmp_path / "BENCHMARK.json"),
                         "demo-2k.single-token-16")
    assert cell.traffic["outstanding"] == 16
    assert cell.config_file.endswith("morecells/configs/demo-2k.json")
    assert "client.rows" in {m["name"] for m in cell.per_layer()}
    assert cell.readers()["client.rows"].reduce(
        {"client": {"attempted": 5}}) == 5
    old = manifest.Cell(str(tmp_path / "BENCHMARK.json"),
                        "mesh-100k.sidecar-sat")
    assert "client.rows" not in {m["name"] for m in old.per_layer()}
