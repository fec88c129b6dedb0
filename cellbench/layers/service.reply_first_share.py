"""Share of the window's dispatches that were accounted after their reply
had been submitted: the program's ``reply_first_total`` over the count of its
``account_ms`` histogram, after the window less before it. The native reply
lane answers first and counts after (PR 35), so a cell served through that
door means nothing under 100: a lower share says dispatches were counted on
the verdict's path again. None where the program has no such counter (a tree
from before PR 35) or accounted no dispatch in the window."""

NAME = "service.reply_first_share"
UNIT = "%"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if "reply_first_total" not in stages or "account_ms" not in stages:
            return None
    n = b["account_ms"]["count"] - a["account_ms"]["count"]
    if n <= 0:
        return None
    return 100.0 * (b["reply_first_total"] - a["reply_first_total"]) / n
