"""Analytic bytes of the outcome step (``sentinel_tpu/engine/outcome.py``,
``jit_outcome_step``), and the least time a chip could take for the ingests
of a traced slice. Peaks come from ``peaks.json`` by ``device_kind``.

What an ingest of ``rows`` completions padded to the rung ``cap`` must move,
counted from the shapes alone, whatever implements it (the outcome window is
``int32[flows, buckets, CHANNELS]``):

    arguments in      the step's four columns at the rung: slot, rt_ms and
                      exception flag as ``int32``, the validity mask as a
                      byte, and the clock; the tally out (two ``int32``)
    cells scattered   a row adds into ``CELLS_PER_ROW`` cells of its flow's
                      current bucket (RT sum, completions, exceptions, slow
                      completions where breakers are loaded, one cell of the
                      log2 RT histogram): each read and written once
    breaker columns   where breakers are loaded a row reads its flow's state
                      (a byte), probe ticket, strategy (a byte) and slow
                      cutoff; a resolved probe writes three of them back
                      (counted with the reads: at most one row a flow)
    window starts     the ``buckets`` bucket starts, read and written
    rolled bucket     once per ``bucket_ms``, whatever the ingests: the slab
                      of the bucket that went stale, ``flows x CHANNELS``
                      cells written

Operations are a handful per row (a compare, the histogram's bit length, the
probe election's prefix): the step is bound by HBM traffic, and the share
read is of that roofline.
"""

from __future__ import annotations

CHANNELS = 16  # engine.state.N_OUTCOME_CHANNELS: 4 counters + 12 RT cells
CELLS_PER_ROW = 5  # with breakers loaded; 4 without (no SLOW channel)
_I32 = 4
_BREAKER_BYTES_PER_ROW = 1 + 4 + 1 + 4 + (1 + 4 + 4)  # reads, and a resolve


def rung_of(rows: int, first: int = 64) -> int:
    """The padding ladder's rung for ``rows`` rows: 64, 256, 1024, ..."""
    cap = first
    while cap < rows:
        cap *= 4
    return cap


def ingest_bytes(rows: int, engine: dict, breakers: bool = True) -> float:
    """Bytes one ingest of ``rows`` completions must move, the rolled
    bucket left out."""
    cap = rung_of(max(1, int(rows)))
    cells = CELLS_PER_ROW if breakers else CELLS_PER_ROW - 1
    moved = cap * (3 * _I32 + 1) + _I32 + 2 * _I32  # arguments, tally
    moved += rows * cells * 2 * _I32
    if breakers:
        moved += rows * _BREAKER_BYTES_PER_ROW
    moved += 2 * int(engine["n_buckets"]) * _I32
    return float(moved)


def rolled_bucket_bytes(engine: dict) -> float:
    return float(int(engine["max_flows"]) * CHANNELS * _I32)


def least_seconds(ingest_rows, seconds: float, config: dict,
                  peaks: dict) -> float:
    """The least time a chip could take for ingests of ``ingest_rows``
    completions each over ``seconds`` of serving."""
    engine = config["engine"]
    breakers = "degrade" in config.get("rules", {})
    total = sum(ingest_bytes(r, engine, breakers) for r in ingest_rows)
    total += (seconds * 1000.0 / float(engine["bucket_ms"])
              * rolled_bucket_bytes(engine))
    return total / peaks["hbm_bytes_per_s"]


def ingest_rows(snap) -> list:
    """Completions of every outcome step launched in a traced slice: the
    ``outcome`` events of the flight recorder that scattered any."""
    return [e["aux"] for e in snap["events"]
            if e["stage"] == "outcome" and e["aux"] > 0]


def outcome_program_seconds(snap) -> float:
    """Device time of the ``jit_outcome_step*`` programs of the slice."""
    return sum(s for name, s in snap["trace"]["modules"]
               if str(name).startswith("jit_outcome_step"))
