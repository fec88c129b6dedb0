"""Share of the window's flow dispatches in which the decide step took the
live branch of all three of its cond-gated arms (shaping, pacing, occupy):
the program's ``decide_all_arms_live_total`` over ``decide_dispatch_total``,
after the window less before it. A cell that exists to keep those arms
measured means nothing under 90. None where the program does not count its
arms (a tree from before PR 31) or made no flow dispatch."""

NAME = "step.shaped_arms_live_share"
UNIT = "%"
LAYER = "decide step"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("decide_all_arms_live_total" not in stages
                or "decide_dispatch_total" not in stages):
            return None
    n = b["decide_dispatch_total"] - a["decide_dispatch_total"]
    if n <= 0:
        return None
    return 100.0 * (b["decide_all_arms_live_total"]
                    - a["decide_all_arms_live_total"]) / n
