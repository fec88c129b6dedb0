"""95th percentile of a completion report's wait from the door to the launch
of the outcome step that ingested it, in the traced slice: how stale a
breaker's view of its flow is. Flight recorder ``outcome_in`` (stamped with
the time the door queued the frame) -> the ``outcome`` event of the same
ingest (same thread, same ``shard``). None where the recorder holds no such
pair (a tree from before PR 34, or no report in the slice)."""

NAME = "service.outcome_age_p95_ms"
UNIT = "ms"
LAYER = "control lane"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_span"


def reduce(snap):
    import numpy as np

    launched, waiting = {}, []
    for e in snap["events"]:
        if e["stage"] == "outcome":
            launched[(e.get("thread"), e["shard"])] = e["t_ns"]
        elif e["stage"] == "outcome_in":
            waiting.append(e)
    ages = [launched[key] - e["t_ns"] for e in waiting
            for key in [(e.get("thread"), e["shard"])] if key in launched]
    if not ages:
        return None
    return float(np.percentile(ages, 95)) / 1e6
