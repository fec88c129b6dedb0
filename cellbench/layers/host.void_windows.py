"""Windows of this run that were measured again because the whole machine
stood still through them (server process, every generator process and the
witness process at once, 0.4 s or more): 0 in nearly every run, and a count
of how often the machine, not the server, decides a window."""

NAME = "host.void_windows"
UNIT = "count"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "host_clock"


def reduce(snap):
    if "voided" not in snap:
        return None
    return float(len(snap["voided"]))
