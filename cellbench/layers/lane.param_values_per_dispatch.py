"""(Request, value) rows per hot-parameter dispatch over the window: the
program's ``param_values_total`` over ``param_dispatch_total``, after the
window less before it: what one launch is amortised over on the param lane.
None where the program has no such counters (a tree from before PR 27) or
made no param dispatch."""

NAME = "lane.param_values_per_dispatch"
UNIT = "rows"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("param_values_total" not in stages
                or "param_dispatch_total" not in stages):
            return None
    n = b["param_dispatch_total"] - a["param_dispatch_total"]
    if n <= 0:
        return None
    return (b["param_values_total"] - a["param_values_total"]) / n
