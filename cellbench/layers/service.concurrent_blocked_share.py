"""Share of the window's acquire rows the service answered BLOCKED:
``concurrent_blocked_total`` over ``concurrent_acquire_rows_total``, after
the window less before it. The limiter at work: it follows the mix's skew,
the holds and the offered rate, not the program's speed. None where the
program has no such counters (a tree from before PR 41) or decided no
acquire row."""

NAME = "service.concurrent_blocked_share"
UNIT = "%"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench import concurrent_roofline

    moved = concurrent_roofline.window_counts(snap)
    if moved is None or moved["concurrent_acquire_rows_total"] <= 0:
        return None
    return (100.0 * moved["concurrent_blocked_total"]
            / moved["concurrent_acquire_rows_total"])
