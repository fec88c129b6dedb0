"""Single PARAM_FLOW frames (type 2, the reference client's one-request
frame) a pull of the native door's data plane carried, over the window: the
program's ``param_single_frames_total`` over ``param_single_pulls_total``
(pulls that carried at least one), after the window less before it: how many
callers' frames one turn of the intake lane is amortised over. None where
the program has no such counters (a tree on which type 2 is control plane)
or no pull carried one."""

NAME = "door.param_single_frames_per_pull"
UNIT = "frames"
LAYER = "door intake"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("param_single_frames_total" not in stages
                or "param_single_pulls_total" not in stages):
            return None
    n = b["param_single_pulls_total"] - a["param_single_pulls_total"]
    if n <= 0:
        return None
    return (b["param_single_frames_total"]
            - a["param_single_frames_total"]) / n
