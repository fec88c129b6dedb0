"""Share of the window's decided rows the service told to wait: the count of
the program's ``wait_assigned_ms`` histogram (SHOULD_WAIT verdicts with a
positive wait: paced rows and priority borrows) over ``decide_rows_total``,
after the window less before it. The shapers at work: it follows the mix and
the rules, not the program's speed. None where the program does not count
the rows of its flow dispatches (a tree from before PR 31) or decided none."""

NAME = "service.should_wait_share"
UNIT = "%"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("decide_rows_total" not in stages
                or "wait_assigned_ms" not in stages):
            return None
    n = b["decide_rows_total"] - a["decide_rows_total"]
    if n <= 0:
        return None
    return 100.0 * (b["wait_assigned_ms"]["count"]
                    - a["wait_assigned_ms"]["count"]) / n
