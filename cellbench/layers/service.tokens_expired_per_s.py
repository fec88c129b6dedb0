"""Tokens reclaimed by expiry per second of the window: the program's
``concurrent_expired_total``, after the window less before it, over the
window's seconds: the share of tokens the mix never gives back, once the
resource timeout has passed. None where the program has no such counter (a
tree from before PR 41) or made no concurrency dispatch."""

NAME = "service.tokens_expired_per_s"
UNIT = "1/s"
LAYER = "service"
MOVES = "verdict_latency_p95_ms"
SOURCE = "program_counter"


def reduce(snap):
    from cellbench import concurrent_roofline

    moved = concurrent_roofline.window_counts(snap)
    if moved is None or snap["seconds"] <= 0:
        return None
    return moved["concurrent_expired_total"] / snap["seconds"]
