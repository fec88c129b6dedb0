"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on the chip (PR 23)."""

import os

import numpy as np
import pytest

from cellbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


def test_union_of_overlapping_and_nested_intervals():
    start = np.array([10.0, 12.0, 30.0, 31.0, 50.0])
    dur = np.array([5.0, 10.0, 10.0, 2.0, 5.0])  # [10,22] [30,40] [50,55]
    s, e = trace.merge(start, dur, 0.0, 100.0)
    assert s.tolist() == [10.0, 30.0, 50.0]
    assert e.tolist() == [22.0, 40.0, 55.0]
    busy, (gs, gl) = trace.busy_and_gaps(start, dur, 0.0, 100.0)
    assert busy == 27.0
    assert gs.tolist() == [0.0, 22.0, 40.0, 55.0]
    assert gl.tolist() == [10.0, 8.0, 10.0, 45.0]


def test_window_clips_intervals_and_no_interval_means_all_idle():
    start, dur = np.array([0.0, 90.0]), np.array([20.0, 30.0])
    busy, (gs, gl) = trace.busy_and_gaps(start, dur, 10.0, 100.0)
    assert busy == 20.0 and gs.tolist() == [20.0] and gl.tolist() == [70.0]
    busy, (gs, gl) = trace.busy_and_gaps(np.empty(0), np.empty(0), 0.0, 9.0)
    assert busy == 0.0 and gl.tolist() == [9.0]


def test_top_by_time_counts_only_what_lies_inside():
    names = np.array(["a", "b", "a", "c"], object)
    start = np.array([0.0, 10.0, 20.0, 95.0])
    dur = np.array([5.0, 1.0, 5.0, 10.0])
    top = trace.top_by_time(names, start, dur, 0.0, 100.0, 2)
    assert top == [["a", 10.0 / 1e9], ["b", 1.0 / 1e9]]


def test_gaps_are_named_by_the_stage_before_them():
    stages_t = np.array([5.0, 9.0, 40.0, 60.0])
    stages = ["enqueue", "device_in", "reply_out", "client_in"]
    gaps = trace.name_gaps(np.array([10.0, 41.0, 70.0]),
                           np.array([3.0, 10.0, 5.0]), stages_t, stages)
    assert gaps == [["no_request", 10.0 / 1e9], ["after_client_in", 5.0 / 1e9],
                    ["after_device_in", 3.0 / 1e9]]


def test_the_counting_after_the_last_reply_is_no_request():
    """Since PR 35 a reply lane answers first and counts after: ``account``
    and ``device_out`` follow ``reply_out``. A gap behind them with nothing
    more recorded is ``no_request``; one that the counting's next stage falls
    into, or behind an ``account`` whose reply is still to go (a synchronous
    caller's order), keeps its name; ``rx`` and ``reply_taken`` (PR 38) name
    a gap like any stage."""
    stages = ["rx", "client_in", "device_in", "reply_taken", "ready",
              "fetched", "reply_out", "account", "device_out",
              "rx", "fetched", "account", "device_out", "reply_out"]
    stages_t = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0,
                         50.0, 51.0, 52.0, 53.0, 54.0])
    gaps = trace.name_gaps(
        np.array([0.5, 4.5, 7.5, 8.5, 9.5, 50.5, 52.5, 53.5, 54.5]),
        np.array([0.2, 0.3, 0.6, 0.65, 40.0, 0.1, 0.15, 0.25, 9.0]),
        stages_t, stages, n=9)
    assert dict((round(s * 1e9, 2), name) for name, s in gaps) == {
        0.2: "no_request", 0.3: "after_reply_taken", 0.6: "after_reply_out",
        0.65: "after_account", 40.0: "no_request", 0.1: "after_rx",
        0.15: "after_account", 0.25: "after_device_out", 9.0: "no_request"}
    # not quiet: a request came in before the gap ended
    gaps = trace.name_gaps(np.array([9.5]), np.array([45.0]), stages_t,
                           stages)
    assert gaps == [["after_device_out", 45.0 / 1e9]]


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_reduces_to_the_numbers_read_by_hand():
    t = trace.Trace(RECORDED)
    assert t.offset_ns is not None and len(t.devices) == 1
    (plane,) = t.devices
    names, start, dur = t.devices[plane]["ops"]
    lo = t.offset_ns + float(start.min()) - 1e6
    hi = t.offset_ns + float((start + dur).max()) + 1e6
    out = trace.reduce(t, lo, hi)
    # busy time is the union: never more than the sum, never more than the
    # window, and here the ops do not overlap on one core
    assert 0 < out["busy_s_mean"] <= float(dur.sum()) / 1e9 + 1e-12
    assert out["busy_s_mean"] < out["window_s"]
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1]
    assert out["module_runs_median_chip"] > 0
