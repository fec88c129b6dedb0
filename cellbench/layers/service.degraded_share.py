"""Share of the rows flow dispatches decided that the breaker arm answered
DEGRADED: the program's ``decide_degraded_rows_total`` over
``decide_rows_total``, after the window less before it. None where the
program does not count its breaker arm (a tree from before PR 34) or made
no flow dispatch."""

NAME = "service.degraded_share"
UNIT = "%"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("decide_degraded_rows_total" not in stages
                or "decide_rows_total" not in stages):
            return None
    n = b["decide_rows_total"] - a["decide_rows_total"]
    if n <= 0:
        return None
    return 100.0 * (b["decide_degraded_rows_total"]
                    - a["decide_degraded_rows_total"]) / n
