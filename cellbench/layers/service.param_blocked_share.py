"""Share of the window's hot-parameter requests the service answered
BLOCKED: ``param_blocked_total`` over ``param_requests_total``, after the
window less before it. The limiter at work: it follows the mix's skew and the
offered rate, not the program's speed. None where the program has no such
counters or decided no param request."""

NAME = "service.param_blocked_share"
UNIT = "%"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("param_blocked_total" not in stages
                or "param_requests_total" not in stages):
            return None
    n = b["param_requests_total"] - a["param_requests_total"]
    if n <= 0:
        return None
    return 100.0 * (b["param_blocked_total"] - a["param_blocked_total"]) / n
