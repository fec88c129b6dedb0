"""Rows the server shed (queue full, age, brownout, degrade) over rows the
clients attempted, in percent: ``shed_totals()`` over the window."""

NAME = "door.shed_share"
UNIT = "%"
LAYER = "door intake"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["shed"], snap["after"]["shed"]
    shed = sum(b.values()) - sum(a.values())
    attempted = snap["client"]["attempted"]
    if attempted <= 0:
        return None
    return 100.0 * shed / attempted
