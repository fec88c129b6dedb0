"""Share of the window's flow dispatches in which the decide step took the
live branch of its breaker cond (a row on a flow with a DegradeRule was in
the batch): the program's ``decide_breaker_live_total`` over
``decide_dispatch_total``, after the window less before it. A cell that
exists to keep that arm measured means nothing under 90. None where the
program does not count the arm (a tree from before PR 34) or made no flow
dispatch."""

NAME = "step.breaker_arm_live_share"
UNIT = "%"
LAYER = "decide step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("decide_breaker_live_total" not in stages
                or "decide_dispatch_total" not in stages):
            return None
    n = b["decide_dispatch_total"] - a["decide_dispatch_total"]
    if n <= 0:
        return None
    return 100.0 * (b["decide_breaker_live_total"]
                    - a["decide_breaker_live_total"]) / n
