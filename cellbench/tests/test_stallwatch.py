"""The stall watch sees a server that stops finishing dispatches, a GIL held
through one long call, and stays quiet over a loop that keeps going."""

import re
import threading
import time

import pytest

from cellbench import stallwatch


def _worker(state: dict) -> None:
    while state["on"]:
        state["n"] += 1
        time.sleep(0.005)
        if state["n"] == state.get("stall_at"):
            time.sleep(state["stall_s"])


def _watch(tmp_path, state: dict, body) -> tuple:
    th = threading.Thread(target=_worker, args=(state,), daemon=True)
    th.start()
    dump = tmp_path / "stalls.txt"
    watch = stallwatch.StallWatch(lambda: state["n"], str(dump))
    t0 = time.monotonic()
    watch.start(t0)
    body()
    out = watch.stop(time.monotonic() + 1.0)
    state["on"] = False
    th.join()
    return out, dump.read_text()


def test_a_loop_that_keeps_going_is_no_stall(tmp_path):
    out, dump = _watch(tmp_path, {"on": True, "n": 0},
                       lambda: time.sleep(1.0))
    assert out["stalls"] == [] and out["stall_max_s"] == 0.0
    assert out["gap_max_s"] < stallwatch.STALL_S
    assert "no dispatch finished" not in dump


def test_a_server_that_stops_is_seen_with_its_stacks(tmp_path):
    state = {"on": True, "n": 0, "stall_at": 40, "stall_s": 1.0}
    out, dump = _watch(tmp_path, state, lambda: time.sleep(2.0))
    assert len(out["stalls"]) == 1
    began, length = out["stalls"][0]
    assert 0.1 < began < 1.0 and 0.8 < length < 1.4
    assert "no dispatch finished" in dump and "_worker" in dump
    assert "progress again after" in dump


def test_a_held_gil_is_a_gap_and_the_c_watchdog_dumps(tmp_path):
    def hold():
        time.sleep(0.3)
        re.match(r"(a+)+$", "a" * 24 + "b")  # about a second, GIL held
        time.sleep(0.2)

    out, dump = _watch(tmp_path, {"on": True, "n": 0}, hold)
    assert out["gap_max_s"] > stallwatch.DUMP_AFTER_S
    assert "Timeout" in dump  # faulthandler's thread, which needs no GIL


def test_host_counters_subtract():
    a = stallwatch.host_counters()
    d = stallwatch.host_delta(a, stallwatch.host_counters())
    assert d["steal_s_total"] >= 0 and d["steal_s_worst_cpu"] >= 0


# -- a window the machine stood still through is void -----------------------
from cellbench import run  # noqa: E402


def test_all_processes_still_at_once_is_the_machine():
    t0 = 1000.0
    froze = run.machine_froze(
        t0, server_gaps=[(2.0, 1.10), (14.3, 0.11)],
        generator_gaps=[[(2.01, 1.10)], [(1.99, 1.12), (14.3, 0.12)]],
        witness_gaps=[(t0 + 2.0, 1.11)])
    assert froze is not None
    at, length = froze
    assert 2.0 <= at <= 2.02 and 1.05 < length <= 1.10


@pytest.mark.parametrize("server, generators, witness", [
    # only the server stood still: the server's stall, the window counts
    ([(2.0, 3.0)], [[], []], []),
    # server and generators, but the idle witness ran: not the machine
    ([(2.0, 3.0)], [[(2.0, 3.0)]], []),
    # everyone, but never at the same time
    ([(2.0, 0.6)], [[(5.0, 0.6)]], [(1009.0, 0.6)]),
    # everyone at once, but shorter than a freeze: the 120 ms kind
    ([(2.0, 0.13)], [[(2.0, 0.13)]], [(1002.0, 0.13)]),
    # no generator answered: no evidence
    ([(2.0, 3.0)], [], [(1002.0, 3.0)]),
])
def test_anything_less_is_not(server, generators, witness):
    assert run.machine_froze(1000.0, server, generators, witness) is None


def test_a_void_window_is_measured_again_and_said(monkeypatch):
    import os

    manifest = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "manifest.json")
    calls = []

    def froze_once(t0, *gaps):
        calls.append(t0)
        return (1.0, 1.2) if len(calls) == 1 else None

    monkeypatch.setattr(run, "machine_froze", froze_once)
    lines = []
    result = run.run_cell(manifest, "tiny.tiny-single", seed=2_147_483_902,
                          seconds=1.5, trace=0, require_chip=False,
                          out=lines.append)
    assert len(calls) == 2 and calls[1] > calls[0] + 1.5
    assert result["void_windows"] == 1
    assert result["correct"] is True and result["failed"] == 0
    assert any("WINDOW 1 VOID" in ln for ln in lines)
    # set-up runs to the start of the window that is reported
    assert result["metrics"]["setup_s"]["value"] > 1.5


def test_a_frozen_process_group_voids_the_window_for_real():
    """The whole run (server, generator, witness) stopped by SIGSTOP for
    1.3 s inside its window: the harness's own watchers see it, say it and
    measure again."""
    import json
    import os
    import signal
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    code = (
        "import os, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from cellbench import run, rehearse\n"
        "r = run.run_cell(rehearse.MANIFEST, 'tiny.tiny-single',"
        " seed=3000000021, seconds=5.0, trace=0, require_chip=False)\n"
        "print(json.dumps(r), flush=True)\n")
    p = subprocess.Popen([sys.executable, "-c", code], cwd=root,
                         stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                         text=True, start_new_session=True)
    lines, froze = [], False
    try:
        for line in p.stdout:
            lines.append(line)
            if "set-up compiles" in line and not froze:
                froze = True  # the window starts about 1.5 s after this line
                time.sleep(3.0)
                os.killpg(p.pid, signal.SIGSTOP)
                time.sleep(1.3)
                os.killpg(p.pid, signal.SIGCONT)
        assert p.wait(timeout=60) == 0
    finally:
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
    result = json.loads(lines[-1])
    assert froze and result["void_windows"] == 1, "".join(lines[-12:])
    assert result["correct"] is True and result["failed"] == 0
    said = [ln for ln in lines if "VOID: the machine stood still" in ln]
    assert len(said) == 1
