"""Dispatches accounted per fold of the verdict accounting (PR 46): the count
of the program's ``account_ms`` histogram over its ``account_folds_total``,
after the window less before it. A dispatch deposits one count matrix, and
the per-namespace fan-out to the verdict counters, the SLO plane and the
timeline is folded once a wall second and before every read: the quotient is
about the dispatches a second, in hundreds where the lane is busy. A reading
near 1 says something folds on every deposit (a reader on the hot path, two
``ns_names`` snapshots taking turns) and ``service.account_avg_ms`` pays the
fan-out per dispatch again. None where the program has no such counter (a
tree from before PR 46) or folded or accounted nothing in the window."""

NAME = "service.account_dispatches_per_fold"
UNIT = "dispatches"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if "account_folds_total" not in stages or "account_ms" not in stages:
            return None
    folds = b["account_folds_total"] - a["account_folds_total"]
    dispatches = b["account_ms"]["count"] - a["account_ms"]["count"]
    if folds <= 0 or dispatches <= 0:
        return None
    return dispatches / folds
