"""Share of the device lane's dispatches that began from a pull the turn
before had set aside in ``held`` because it was of another kind:
``lane_held_turns_total`` over the ``lane_turns_*_total`` of every kind,
after the window less before it. Low while pulls come one at a time; it
rises with the backlog, and every such turn cut a fusion short. None where
the program has no such counters (a tree from before PR 49) or the lane
dispatched nothing."""

NAME = "lane.held_pull_share"
UNIT = "%"
LAYER = "device lane"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers import _lane

    return _lane.share(snap, "lane_held_turns_total",
                       [f"lane_turns_{k}_total" for k in _lane.KINDS], 100.0)
