"""Release rows per concurrency dispatch over the window: the program's
``concurrent_release_rows_total`` over ``concurrent_dispatch_total``, after
the window less before it: the rows a dispatch carries beside the acquire
rows the ledger counts. None where the program has no such counters (a tree
from before PR 41) or made no concurrency dispatch."""

NAME = "lane.release_rows_per_dispatch"
UNIT = "rows"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench import concurrent_roofline

    moved = concurrent_roofline.window_counts(snap)
    if moved is None:
        return None
    return (moved["concurrent_release_rows_total"]
            / moved["concurrent_dispatch_total"])
