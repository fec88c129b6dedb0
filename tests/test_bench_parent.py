"""bench.py's parent: one child on the chip, and no way to exit 0 without it.

The ladder (tpu retry, CPU rung, carried-forward results, the all-failed
zero document) is gone; what is left to pin down is that a child which finds
no TPU, or in which a stage fails after the headline was already streamed,
makes the parent exit non-zero without printing a result line.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(_REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "_record", lambda line: None)
    return mod


def test_failing_stage_after_headline_exits_nonzero(bench, monkeypatch,
                                                    capsys):
    """The child streamed its headline, then a stage raised (exit 7): the
    parent passes the code on and prints no result."""
    child = [
        sys.executable, "-c",
        "import sys; print('{\"metric\": \"m\", \"value\": 42}', flush=True); "
        "print('stage boom', file=sys.stderr); sys.exit(7)",
    ]
    doc, rc, tail = bench._run_child(child, 60)
    assert doc == {"metric": "m", "value": 42}
    assert rc == 7
    assert "stage boom" in tail
    monkeypatch.setattr(bench, "_run_child", lambda cmd, d: (doc, rc, tail))
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 7
    out = capsys.readouterr()
    assert out.out == ""
    assert "stage boom" in out.err


def test_clean_child_prints_its_last_document(bench, monkeypatch, capsys):
    monkeypatch.setattr(
        bench, "_run_child", lambda cmd, d: ({"value": 5}, 0, [])
    )
    bench.main()
    assert capsys.readouterr().out.strip() == '{"value": 5}'


def test_child_without_a_document_is_a_failure(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_run_child", lambda cmd, d: (None, 0, []))
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    assert capsys.readouterr().out == ""


def test_deadline_kills_the_child(bench):
    doc, rc, _ = bench._run_child(
        [sys.executable, "-c", "import time; time.sleep(60)"], 0.5
    )
    assert (doc, rc) == (None, 124)


def test_no_tpu_means_nonzero_exit_and_no_result():
    """The real thing, end to end on the CPU the suite runs on: the child
    names the device it found and exits 3, and so does the parent."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode == 3, proc.stderr[-500:]
    assert proc.stdout == ""
    assert "CpuDevice" in proc.stderr
