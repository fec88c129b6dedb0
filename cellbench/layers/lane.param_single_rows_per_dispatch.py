"""(Request, value) rows of single PARAM_FLOW frames a hot-parameter
dispatch carried, over the window: the program's ``param_single_rows_total``
over ``param_single_dispatch_total`` (param dispatches that carried at
least one such frame), after the window less before it: what one launch is
amortised over when every request is a frame of its own. None where the
program has no such counters (a tree on which type 2 is control plane) or
no dispatch carried one."""

NAME = "lane.param_single_rows_per_dispatch"
UNIT = "rows"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("param_single_rows_total" not in stages
                or "param_single_dispatch_total" not in stages):
            return None
    n = b["param_single_dispatch_total"] - a["param_single_dispatch_total"]
    if n <= 0:
        return None
    return (b["param_single_rows_total"] - a["param_single_rows_total"]) / n
