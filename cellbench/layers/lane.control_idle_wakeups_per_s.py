"""Wake-ups of the server's control thread that found no control event on
any door, a second of the window: the program's
``control_idle_wakeups_total``, after the window less before it, over the
time between the two readings. A thread that sleeps on the doors' bell reads
its time-outs here (10 a second); one that polls every 2 ms, about 500. None
where the program has no such counter (a tree whose control thread does not
count its wake-ups)."""

NAME = "lane.control_idle_wakeups_per_s"
UNIT = "1/s"
LAYER = "control lane"
MOVES = "decided_verdicts_per_s"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"], snap["after"]
    for c in (a, b):
        if "control_idle_wakeups_total" not in c["stages"]:
            return None
    seconds = b["t"] - a["t"]
    if seconds <= 0:
        return None
    return (b["stages"]["control_idle_wakeups_total"]
            - a["stages"]["control_idle_wakeups_total"]) / seconds
