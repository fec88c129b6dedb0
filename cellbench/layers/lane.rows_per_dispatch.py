"""Mean rows per device dispatch in the traced slice: flight recorder
DEVICE_IN events (aux = rows)."""

NAME = "lane.rows_per_dispatch"
UNIT = "rows"
LAYER = "device lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_span"


def reduce(snap):
    rows = [e["aux"] for e in snap["events"] if e["stage"] == "device_in"]
    if not rows:
        return None
    return sum(rows) / len(rows)
