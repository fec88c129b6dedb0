"""Mean time a pull waited in the device lane's ``held`` (popped while the
lane collected a dispatch of another kind, dispatched on the next turn),
from its pop to the start of its own ``dispatch_ms``:
``lane_held_wait_ms_total`` over ``lane_held_turns_total``, after the window
less before it. About one ``dispatch_ms`` of the other kind: what living
together adds to such a pull's queue wait. None where the program has no such
counters (a tree from before PR 49) or no turn began from ``held``."""

NAME = "lane.held_wait_avg_ms"
UNIT = "ms"
LAYER = "device lane"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench.layers import _lane

    return _lane.share(snap, "lane_held_wait_ms_total",
                       ["lane_held_turns_total"])
