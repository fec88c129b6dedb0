"""Mean time per completion report that its ingest spent waiting for the
service lock and holding it (engine clock, the jitted outcome step's call,
dirty-set): the sums of the server's ``outcome_lock_wait_ms`` and
``outcome_launch_ms`` phase histograms over ``outcome_frames_total``, over
the whole window. None where the program has no such histograms (a tree from
before PR 34) or ingested no report."""

NAME = "service.outcome_ingest_avg_ms"
UNIT = "ms"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    spent = 0.0
    for phase in ("outcome_lock_wait_ms", "outcome_launch_ms"):
        if a.get(phase) is None or b.get(phase) is None:
            return None
        spent += b[phase]["sum"] - a[phase]["sum"]
    if "outcome_frames_total" not in a or "outcome_frames_total" not in b:
        return None
    n = b["outcome_frames_total"] - a["outcome_frames_total"]
    if n <= 0:
        return None
    return spent / n
