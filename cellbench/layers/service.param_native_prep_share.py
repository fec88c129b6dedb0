"""Share of the window's dispatches whose host prep was the hot-parameter
lane's one native pass (``sn_param_prep``, PR 47): the program's
``param_prep_native_total`` over the count of its ``prep_ms`` histogram,
after the window less before it. The cells that list this metric send param
frames only, so every dispatch of the window is a param dispatch and nothing
under 100 is sound: the autobuild of the native library degrades silently,
and a lower share says numpy (``_param_rows`` + ``pack_param_rows``) prepped
dispatches under the native pass's name. None where the program has no such
counter (a tree from before PR 47) or dispatched nothing in the window."""

NAME = "service.param_native_prep_share"
UNIT = "%"
LAYER = "service"
MOVES = "verdict_latency_p50_ms"
SOURCE = "program_counter"


def reduce(snap):
    a, b = snap["before"]["stages"], snap["after"]["stages"]
    for stages in (a, b):
        if ("param_prep_native_total" not in stages
                or "prep_ms" not in stages):
            return None
    n = b["prep_ms"]["count"] - a["prep_ms"]["count"]
    if n <= 0:
        return None
    return 100.0 * (
        b["param_prep_native_total"] - a["param_prep_native_total"]) / n
