"""Share of the rows the device lane dispatched that were hot-parameter
requests: ``lane_turn_rows_param_total`` over the ``lane_turn_rows_*_total``
of every kind, after the window less before it. The mix as the lane saw it
(the client's ledger holds the same share of decided rows to the traffic
file's). None where the program has no such counters (a tree from before
PR 49) or the lane dispatched nothing."""

NAME = "lane.param_rows_share"
UNIT = "%"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers import _lane

    return _lane.share(snap, "lane_turn_rows_param_total",
                       [f"lane_turn_rows_{k}_total" for k in _lane.KINDS],
                       100.0)
