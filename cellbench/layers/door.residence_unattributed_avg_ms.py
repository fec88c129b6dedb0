"""What the spans still do not tile of a verdict's time at the server: the
mean ``door_residence_ms`` of the window less the window's means of the
phases between the socket's last byte in and its last byte out: ``door_in``,
``door_wake``, ``intake``, ``queue_wait``, ``dispatch``, ``reply_queue_wait``,
``decide`` and ``door_out``. What is left is the reply lane's slicing and the
``submit_many`` call up to ``sn_fd_submit``'s entry, and whatever else has no
span. A time, not a share, and it may read a little under 0: residence,
``door_in`` and ``door_out`` are means per frame, ``door_wake``, ``intake`` and
``queue_wait`` per pull, the rest per dispatch, and where frames and
dispatches are not one to one (a fused group, a pull of many frames) the
means weigh the same stretch differently. None where the program lacks one of
the histograms (a tree from before PR 38) or one of them is empty."""

NAME = "door.residence_unattributed_avg_ms"
UNIT = "ms"
LAYER = "door"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"

PHASES = ("door_in_ms", "door_wake_ms", "intake_ms", "queue_wait_ms",
          "dispatch_ms", "reply_queue_wait_ms", "decide_ms", "door_out_ms")


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    means = []
    for hist in ("door_residence_ms",) + PHASES:
        if hist not in a or hist not in b:
            return None
        n = b[hist]["count"] - a[hist]["count"]
        if n <= 0:
            return None
        means.append((b[hist]["sum"] - a[hist]["sum"]) / n)
    return means[0] - sum(means[1:])
