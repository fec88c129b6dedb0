"""Rows on a shaped rule (a control behaviour other than DEFAULT) per flow
dispatch over the window: the program's ``decide_shaped_rows_total`` over
``decide_dispatch_total``, after the window less before it: what the shaping
and pacing arms of one step work on. None where the program does not count
its arms (a tree from before PR 31) or made no flow dispatch."""

NAME = "lane.shaped_rows_per_dispatch"
UNIT = "rows"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("decide_shaped_rows_total" not in stages
                or "decide_dispatch_total" not in stages):
            return None
    n = b["decide_dispatch_total"] - a["decide_dispatch_total"]
    if n <= 0:
        return None
    return (b["decide_shaped_rows_total"] - a["decide_shaped_rows_total"]) / n
