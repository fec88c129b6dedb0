"""Share of the window's dispatches whose host prep was the one native pass
(``sn_flow_prep``, PR 43): the program's ``prep_native_total`` over the count
of its ``prep_ms`` histogram, after the window less before it. A cell that
sends flow frames means nothing under 100: the autobuild of the native
library degrades silently, so a lower share says numpy prepped dispatches
under the native pass's name (a machine without a compiler, a stale
library). None where the program has no such counter (a tree from before
PR 43) or dispatched nothing in the window."""

NAME = "service.native_prep_share"
UNIT = "%"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if "prep_native_total" not in stages or "prep_ms" not in stages:
            return None
    n = b["prep_ms"]["count"] - a["prep_ms"]["count"]
    if n <= 0:
        return None
    return 100.0 * (b["prep_native_total"] - a["prep_native_total"]) / n
