"""Completions ingested per outcome step over the window: the program's
``outcome_step_rows_total`` over ``outcome_steps_total``, after the window
less before it. The control lane ingests a report a step, so this reads the
valid rows of a report: what the outcome step's rung and its roofline's
bytes are sized by. None where the program does not count its outcome steps
(a tree from before PR 34) or launched none."""

NAME = "lane.outcome_rows_per_step"
UNIT = "rows"
LAYER = "control lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("outcome_step_rows_total" not in stages
                or "outcome_steps_total" not in stages):
            return None
    n = b["outcome_steps_total"] - a["outcome_steps_total"]
    if n <= 0:
        return None
    return (b["outcome_step_rows_total"] - a["outcome_step_rows_total"]) / n
