"""CPU seconds of the server process per million decided verdicts over the
window (all its threads: door, lanes, service, JAX dispatch)."""

NAME = "host.cpu_s_per_mverdict"
UNIT = "s/Mverdict"
LAYER = "service"
MOVES = "decided_verdicts_per_s"
SOURCE = "host_clock"


def reduce(snap):
    if snap["decided"] <= 0:
        return None
    cpu = snap["after"]["cpu_s"] - snap["before"]["cpu_s"]
    return cpu / (snap["decided"] / 1e6)
