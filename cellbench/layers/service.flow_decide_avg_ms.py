"""Mean time a reply lane waits for the verdicts of a flow dispatch
(device step plus materialise): ``lane_decide_ms_flow_total`` over
``lane_decides_flow_total``, after the window less before it.
``service.decide_avg_ms`` averages every kind of dispatch a window holds;
this is one kind's. None where the program has no such counters (a tree from
before PR 49) or read no such dispatch."""

NAME = "service.flow_decide_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers import _lane

    return _lane.share(snap, "lane_decide_ms_flow_total",
                       ["lane_decides_flow_total"])
