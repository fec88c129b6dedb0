"""The batched token-verdict kernel.

One jitted pure function replaces the reference's per-request server hot loop
(``DefaultTokenService.requestToken`` → ``ClusterFlowChecker.acquireClusterToken``,
``ClusterFlowChecker.java:36-120``):

1. **Namespace guard** — ``GlobalRequestLimiter.tryPass`` (30k-QPS default
   self-protection, ``GlobalRequestLimiter.java:46-55``) as a windowed
   request counter per namespace.
2. **Threshold** — ``count × (GLOBAL ? 1 : connectedCount) × exceedCount``
   (``ClusterFlowChecker.java:38-48``).
3. **Admission** — window PASS sum + *in-batch prefix sums*: request *i*
   passes iff already-passed + tokens of earlier admitted same-flow requests
   + its own acquire fits the threshold. The prefix refinement iterates an
   odd number of times, which provably yields a subset of the exact
   sequential (greedy) admission set — a batch can *never* collectively
   overshoot a threshold, unlike the reference's benign cross-thread TOCTOU.
   Equal-acquire batches (the common case) are exact after one iteration.
4. **Priority occupy** — blocked prioritized requests borrow the next window
   if it has headroom (``ClusterFlowChecker.canOccupy`` + ``tryOccupyNext``),
   yielding SHOULD_WAIT + wait-ms. Borrowed tokens live in a future-window
   tensor; they fold into the PASS read automatically once their window
   arrives (no transfer step — the validity masks do it).

The in-batch prefix sums are [N, N] masked matmuls — MXU-friendly by
construction (N = batch_size ≤ ~2k ⇒ ≤ 4M MACs, noise for the systolic
array).
"""

from __future__ import annotations

import enum
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.engine.config import EngineConfig, named
from sentinel_tpu.engine.rules import RuleTable, ThresholdMode
from sentinel_tpu.engine.state import (
    ClusterEvent,
    EngineState,
    ShapingState,
    flow_spec,
)
from sentinel_tpu.stats import window as W


class TokenStatus(enum.IntEnum):
    """Verdict statuses (``TokenResultStatus.java`` names)."""

    OK = 0
    BLOCKED = 1
    SHOULD_WAIT = 2
    NO_RULE_EXISTS = 3
    TOO_MANY_REQUEST = 4
    FAIL = 5
    # concurrent (cluster-semaphore) mode only:
    RELEASE_OK = 6
    ALREADY_RELEASE = 7
    # server-side admission refusal (no reference analog): the token server
    # answered instead of deciding — queue full, deadline blown, or brownout
    # shed. Distinct from FAIL (broken) and BLOCKED (a rule's verdict): the
    # server is alive and asks the caller to back off (wait_ms carries a
    # retry hint). Never produced by the device kernels.
    OVERLOAD = 8
    # warm-standby refusal: the server answered instead of deciding because
    # it is replicating from a primary and has not been promoted — clients
    # should walk on to the (still-alive) primary. Like OVERLOAD, never
    # produced by the device kernels.
    STANDBY = 9
    # live-rebalance redirect: the namespace owning this flow is moving (or
    # has moved) to another token server; ``remaining`` carries the shard-map
    # epoch and, on the single-request wire path, the frame carries the new
    # owner's endpoint. Routing clients re-resolve and retry once; the
    # failover client treats it as proof of life. Like OVERLOAD/STANDBY,
    # never produced by the device kernels.
    MOVED = 10
    # wire rev 5 lease refusal: the flow is not leasable right now (no
    # headroom to delegate, leasing disabled, or the named lease was
    # revoked). The server is alive and still answers per-request RPCs —
    # clients back off leasing for this flow and fall back to the RPC
    # path; the failover client treats it as proof of life. Never produced
    # by the device kernels.
    NOT_LEASABLE = 11
    # circuit-breaker refusal (DegradeSlot / DegradeException): the flow's
    # breaker is OPEN (or HALF_OPEN with its single probe already in
    # flight), so the request is shed without touching the flow window.
    # ``remaining`` carries retry-after-ms — the time until the breaker
    # will admit a recovery probe. Unlike OVERLOAD..NOT_LEASABLE this IS
    # produced by the device kernels: the breaker state machine runs
    # batch-vectorized inside the decide step (engine/degrade.py).
    DEGRADED = 12


class RequestBatch(NamedTuple):
    flow_slot: jax.Array  # int32 [N]; -1 → NO_RULE
    acquire: jax.Array  # int32 [N]
    prioritized: jax.Array  # bool [N]
    valid: jax.Array  # bool [N] — padding mask


class VerdictBatch(NamedTuple):
    status: jax.Array  # int8 [N]
    wait_ms: jax.Array  # int32 [N]
    remaining: jax.Array  # int32 [N]


# What a step says of its cond-gated arms, as :func:`_decide_core_arms` hands it
# out: ``int32[ARM_FIELDS]``. ``ARM_LIVE`` holds one bit per arm whose
# ``lax.cond`` took its live branch; the rest count the rows the arms had
# before them.
ARM_LIVE, ARM_SHAPED_ROWS, ARM_PACED_ROWS, ARM_PRIORITIZED_ROWS = 0, 1, 2, 3
# the breaker arm (``engine.degrade.breaker_gate``): rows on a guarded flow,
# rows answered DEGRADED, probe tickets given (moves to HALF_OPEN, a stale
# probe armed again included) and flows tripped to OPEN. A move to CLOSED is
# the outcome step's (``engine.outcome.TALLY_CLOSED``).
ARM_GUARDED_ROWS, ARM_DEGRADED_ROWS, ARM_PROBES, ARM_TO_OPEN = 4, 5, 6, 7
ARM_FIELDS = 8
# bits of an ARM_LIVE entry
ARM_SHAPING, ARM_PACING, ARM_OCCUPY, ARM_BREAKER = 1, 2, 4, 8
ARM_ALL = ARM_SHAPING | ARM_PACING | ARM_OCCUPY
# a status is at most 12: the arm fields ride above it in the status line's
# first entries (no frame is shorter than the smallest serve bucket)
_ARM_SHIFT = 8


def pack_verdicts(verdicts: VerdictBatch,
                  arms: Optional[jax.Array] = None) -> jax.Array:
    """What a serve step hands its caller: the three verdict leaves as ONE
    ``int32[3, ...]`` array (rows in :class:`VerdictBatch` field order,
    status widened, values unchanged), so a dispatch's verdicts cross to the
    host in one copy instead of three. Traced inside the jitted step.

    ``arms`` (``int32[..., ARM_FIELDS]``, one per frame) rides in the same
    array, in the bits above the status of each frame's first
    ``ARM_FIELDS`` entries: no line and no copy of its own.
    :func:`unpack_verdicts` drops those bits, :func:`unpack_arms` reads
    them."""
    lines = jnp.stack([leaf.astype(jnp.int32) for leaf in verdicts])
    if arms is not None:
        lines = lines.at[0, ..., :ARM_FIELDS].add(arms << _ARM_SHIFT)
    return lines


def unpack_arms(packed, frames: int = 1) -> np.ndarray:
    """Host side of :func:`pack_verdicts`'s ``arms``: the ``int64[ARM_FIELDS]``
    of one dispatch from its packed verdict buffer on the host (``int32[3,
    ...]``, ``frames`` frames laid end to end). A fused span's live bits are
    OR-ed and its row counts summed over its frames."""
    per_frame = np.asarray(packed)[0].reshape(frames, -1)[
        :, :ARM_FIELDS].astype(np.int64) >> _ARM_SHIFT
    out = per_frame.sum(axis=0)
    out[ARM_LIVE] = np.bitwise_or.reduce(per_frame[:, ARM_LIVE])
    return out


def unpack_verdicts(packed, n: Optional[int] = None,
                    order: Optional[np.ndarray] = None) -> VerdictBatch:
    """Host side of :func:`pack_verdicts`: a :class:`VerdictBatch` of fresh,
    writable numpy leaves (``int8``, ``int32``, ``int32``). Blocks until the
    buffer is on the host unless ``packed`` is numpy already.

    ``n`` keeps the first ``n`` entries of the last axis (the rest is bucket
    padding). ``order`` undoes a grouping sort along that axis: entry ``k``
    answers request ``order[k]``."""
    rows = np.asarray(packed)
    if n is not None:
        rows = rows[..., :n]
    if order is None:
        # copy: the host view of a device buffer is read-only, and a [:n]
        # view would pin the whole padded bucket alive
        out = np.array(rows)
    else:
        out = np.empty_like(rows)
        for dst, src in zip(out, rows):  # half the time of one 2-D scatter
            dst[..., order] = src
    status, wait_ms, remaining = out
    # the cast drops what rides above the status (pack_verdicts' ``arms``)
    return VerdictBatch(
        status=status.astype(np.int8), wait_ms=wait_ms, remaining=remaining
    )


def make_batch(
    config: EngineConfig,
    flow_slots: Sequence[int],
    acquires: Optional[Sequence[int]] = None,
    prioritized: Optional[Sequence[bool]] = None,
) -> RequestBatch:
    """Pad host request lists to the static batch size."""
    n = len(flow_slots)
    N = config.batch_size
    if n > N:
        raise ValueError(f"batch of {n} exceeds configured size {N}")
    slot = np.full(N, -1, dtype=np.int32)
    acq = np.zeros(N, dtype=np.int32)
    prio = np.zeros(N, dtype=bool)
    valid = np.zeros(N, dtype=bool)
    slot[:n] = np.asarray(flow_slots, dtype=np.int32)
    acq[:n] = np.asarray(acquires, dtype=np.int32) if acquires is not None else 1
    if prioritized is not None:
        prio[:n] = np.asarray(prioritized, dtype=bool)
    valid[:n] = True
    # numpy leaves on purpose: jit dispatch converts them on its C++ fast
    # path, which is ~4× cheaper than eager per-array jnp.asarray here —
    # this is the serving hot path (one make_batch per micro-batch)
    return RequestBatch(
        flow_slot=slot, acquire=acq, prioritized=prio, valid=valid
    )


def alloc_fused_batch(config: EngineConfig, depth: int) -> RequestBatch:
    """One ``[depth, batch_size]`` stacked-frame block of numpy
    :class:`RequestBatch` leaves (filled by :func:`make_batch_into`): what
    the non-serving fused step, ``make_sharded_decide(depth=..., donate=
    False)``, scans. The serve steps take the packed form instead
    (:func:`alloc_packed_block`)."""
    N = config.batch_size
    return RequestBatch(
        flow_slot=np.empty((depth, N), np.int32),
        acquire=np.empty((depth, N), np.int32),
        prioritized=np.empty((depth, N), bool),
        valid=np.empty((depth, N), bool),
    )


def make_batch_into(
    out: RequestBatch,
    row: int,
    flow_slots,
    acquires=None,
    prioritized=None,
) -> None:
    """:func:`make_batch` writing into row ``row`` of a stacked staging
    block (see :func:`alloc_fused_batch`) instead of allocating fresh
    leaves — identical padding semantics (slot −1 / acquire 0 / prio False /
    valid False beyond n; acquire defaults to 1 for live rows),
    property-tested bit-identical against :func:`make_batch`."""
    N = out.flow_slot.shape[-1]
    n = len(flow_slots)
    if n > N:
        raise ValueError(f"batch of {n} exceeds configured size {N}")
    slot, acq, prio, valid = (
        out.flow_slot[row], out.acquire[row], out.prioritized[row],
        out.valid[row],
    )
    slot[:n] = flow_slots
    slot[n:] = -1
    acq[:n] = 1 if acquires is None else acquires
    acq[n:] = 0
    prio[:n] = False if prioritized is None else prioritized
    prio[n:] = False
    valid[:n] = True
    valid[n:] = False


# -- the serve steps' one host argument ---------------------------------------
# Every host argument of a jitted call is a host-to-device transfer of its
# own, 0.13-0.16 ms of launch each whatever the rows (PERF.md), so a serve
# step takes the request batch AND the clock as ONE ``int32[PACKED_LINES,
# batch_size]`` array (``[PACKED_LINES, depth, batch_size]`` fused): what
# :class:`RequestBatch` and ``now`` hold, 16 bytes a row.
ROW_SLOT, ROW_ACQUIRE, ROW_FLAGS, ROW_HEAD = 0, 1, 2, 3
PACKED_LINES = 4
FLAG_PRIORITIZED, FLAG_VALID = 1, 2  # bits of a ROW_FLAGS entry
# the head line is [now, 0...]; a fused block's clock is its frame 0's
HEAD_NOW = 0


def _pack_rows(out, flow_slots, acquires, prioritized) -> None:
    """:func:`make_batch`'s padding (slot -1 / acquire 0 / no flag beyond n;
    acquire 1 by default) written into the request lines of ``out``, an
    ``int32[PACKED_LINES, N]`` view. The head line is the caller's."""
    N = out.shape[-1]
    n = len(flow_slots)
    if n > N:
        raise ValueError(f"batch of {n} exceeds configured size {N}")
    slot, acq, flags = out[ROW_SLOT], out[ROW_ACQUIRE], out[ROW_FLAGS]
    slot[:n] = flow_slots
    slot[n:] = -1
    acq[:n] = 1 if acquires is None else acquires
    acq[n:] = 0
    if prioritized is None:
        flags[:n] = FLAG_VALID
    else:
        flags[:n] = np.asarray(prioritized, bool)  # FLAG_PRIORITIZED
        flags[:n] |= FLAG_VALID
    flags[n:] = 0


def pack_requests(
    config: EngineConfig,
    flow_slots: Sequence[int],
    acquires: Optional[Sequence[int]] = None,
    prioritized: Optional[Sequence[bool]] = None,
    now: int = 0,
) -> np.ndarray:
    """:func:`make_batch` and the clock as a serve step's one host argument:
    a fresh ``int32[PACKED_LINES, batch_size]``. The token service packs
    outside its lock and writes ``now`` into ``[ROW_HEAD, HEAD_NOW]`` under
    it."""
    out = np.empty((PACKED_LINES, config.batch_size), np.int32)
    _pack_rows(out, flow_slots, acquires, prioritized)
    out[ROW_HEAD] = 0
    out[ROW_HEAD, HEAD_NOW] = now
    return out


def pack_batch(batch: RequestBatch, now: int) -> np.ndarray:
    """A :class:`RequestBatch` (a frame, or ``[depth, batch_size]`` stacked
    leaves) and the clock in the serve steps' packed form: the bridge from
    the library entry's arguments (:func:`make_batch`, ``now``) for tests
    and drills. The serving path packs rows directly
    (:func:`pack_requests`)."""
    slot = np.asarray(batch.flow_slot)
    out = np.zeros((PACKED_LINES,) + slot.shape, np.int32)
    out[ROW_SLOT] = slot
    out[ROW_ACQUIRE] = batch.acquire
    out[ROW_FLAGS] = (
        np.asarray(batch.prioritized, bool) * FLAG_PRIORITIZED
        + np.asarray(batch.valid, bool) * FLAG_VALID
    )
    out[ROW_HEAD].flat[HEAD_NOW] = now
    return out


def alloc_packed_block(config: EngineConfig, depth: int) -> np.ndarray:
    """One ``int32[PACKED_LINES, depth, batch_size]`` staging block, the one
    host argument of the fused serve steps (:func:`decide_fused_donating`):
    frame ``f`` is ``block[:, f]``, the group's clock ``block[ROW_HEAD, 0,
    HEAD_NOW]``. Freelist-recycled by the fused dispatcher
    (`cluster.protocol.StagingPool`) once the group's verdicts are on the
    host, never sooner: the CPU backend aliases an aligned numpy argument
    outright, on one device and replicated over a mesh alike, so a block in
    flight must not be written. The TPU's runtime has taken its copy when
    the call returns, on one chip (0 of 320 calls decided on bytes
    overwritten right after it) and with the argument replicated over a 2x2
    mesh (0 of 128): ``benchmarks/arg_overwrite_drill.py``, PR 43; PERF.md
    section 6."""
    return np.zeros((PACKED_LINES, depth, config.batch_size), np.int32)


def pack_requests_into(out: np.ndarray, row: int, flow_slots, acquires=None,
                       prioritized=None) -> None:
    """:func:`pack_requests` writing frame ``row`` of a staging block
    (:func:`alloc_packed_block`) instead of allocating; the clock is
    written once per block, not per frame."""
    _pack_rows(out[:, row], flow_slots, acquires, prioritized)


def unpack_requests(packed) -> tuple:
    """``(RequestBatch, now)`` from a packed request array, frame or fused
    block: traced at the head of every serve step, so the cores below keep
    the arguments they always took."""
    flags = packed[ROW_FLAGS]
    batch = RequestBatch(
        flow_slot=packed[ROW_SLOT],
        acquire=packed[ROW_ACQUIRE],
        prioritized=(flags & FLAG_PRIORITIZED) != 0,
        valid=(flags & FLAG_VALID) != 0,
    )
    return batch, packed[ROW_HEAD].ravel()[HEAD_NOW]


from sentinel_tpu.engine.degrade import breaker_gate as _breaker_gate
from sentinel_tpu.engine.prefix import segment_prefix_builder as _segment_prefix_builder
from sentinel_tpu.ops.scan_mm import blocked_cumsum as _blocked_cumsum


def _warmup_curve(
    spec,
    now,
    passed,
    cnt,
    cnt_safe,
    warn,
    max_token,
    slope,
    cold_count,
    filled,
    tokens,
    warm_rows,
):
    """WARM_UP lazy token sync + slope curve on gathered ``[N]`` columns.

    The body of ``_decide_core``'s ``warm_on`` branch. Returns ``(qps,
    tokens_new, do_sync, cur_sec)``; rows outside ``warm_rows`` come back
    with ``qps = cnt`` and ``do_sync = False``, the values the caller's
    ``lax.cond`` gives when no WARM_UP row is in the batch.
    """
    # lazy once-per-second token sync (WarmUpController.syncToken):
    # refill below the warning line (or above it while pass qps stays
    # under count/coldFactor), clamp to maxToken, then drain one
    # second's worth of passes. The reference syncs with the previous
    # second's pass QPS; here the sliding-window pass rate stands in —
    # the scalar port in tests/test_shaping.py mirrors exactly this.
    # A NEVER fill stamp makes the first sync see a huge idle gap and
    # clamp to maxToken: the cold state, for free.
    pass_qps = passed * (1000.0 / spec.interval_ms)
    cur_sec = now - now % 1000
    can_refill = (tokens < warn) | ((tokens > warn) & (pass_qps < cold_count))
    elapsed = (cur_sec - filled).astype(jnp.float32)
    cooled = jnp.minimum(
        tokens + jnp.where(can_refill, elapsed * cnt_safe / 1000.0, 0.0),
        max_token,
    )
    synced = jnp.maximum(cooled - pass_qps, 0.0)
    do_sync = warm_rows & (cur_sec > filled)
    tokens_new = jnp.where(do_sync, synced, tokens)
    # above the warning line the system is still cold and the allowed
    # rate follows the slope curve (WarmUpController.canPass)
    above = jnp.maximum(tokens_new - warn, 0.0)
    warning_qps = 1.0 / (above * slope + 1.0 / cnt_safe)
    qps = jnp.where(warm_rows & (tokens_new >= warn), warning_qps, cnt)
    return qps, tokens_new, do_sync, cur_sec


def _occupy_feasible(
    config,
    try_occupy,
    passed,
    expiring,
    admitted_prefix,
    waiting,
    occ_prefix,
    acquire_f,
    threshold,
):
    """The priority-occupy headroom check (``ClusterFlowChecker.canOccupy``)
    on gathered ``[N]`` columns."""
    # admitted_prefix: tokens admitted earlier in THIS batch land in the
    # current bucket, which is still valid at the next window — without
    # this term a borrow could overcommit the window the batch just filled
    return try_occupy & (
        passed - expiring + admitted_prefix + waiting + occ_prefix + acquire_f
        <= config.max_occupy_ratio * threshold
    )


def _ns_guard(config, spec, ns_state, rules, now, psum, owned, safe_slot, live):
    """Namespace guard (request-count qps, ``GlobalRequestLimiter.java:46``)
    — computed identically on every device from global inputs
    ([N]/[NS]-sized prologue math on the tiny replicated namespace window).

    Returns ``(ns_id, ns_ok, seg_ns_sum)`` where ``seg_ns_sum`` is the
    per-namespace segment-sum closure reused for the guard-counter update.
    """
    ns_id = psum(jnp.where(owned, rules.namespace_id[safe_slot], 0))
    live_f = live.astype(jnp.float32)
    # per-namespace totals: on TPU a one-hot matvec (the MXU eats it, a
    # 64-wide scatter serializes); off-TPU the scatter-add wins ~4× and
    # skips materializing the [N, NS] one-hot on the fast path entirely
    on_tpu = jax.default_backend() == "tpu"

    def _ns_one_hot():
        return (
            ns_id[:, None] == jnp.arange(config.max_namespaces)[None, :]
        ).astype(jnp.float32)

    def seg_ns_sum(vals):
        if on_tpu:
            # XLA CSE dedupes the identical one-hot across call sites
            return jnp.einsum(
                "nk,n->k", _ns_one_hot(), vals,
                precision=jax.lax.Precision.HIGHEST,  # exact int counts
            )
        return jnp.zeros(
            (config.max_namespaces,), jnp.float32
        ).at[ns_id].add(vals)
    # Dense per-namespace view ([NS], cheap): a request's verdict needs the
    # per-request in-batch prefix ONLY when a namespace's budget boundary
    # falls inside this batch. With already = valid-window count and
    # total = live requests of that namespace in the batch:
    #   fits-all:   already + total <= budget  → every request passes
    #   none-pass:  already + 1     >  budget  → every request blocks
    # and both reduce to ok = (already + 1 <= budget) applied per
    # namespace. Only a boundary-crossing namespace (already+total >
    # budget AND already+1 <= budget) needs the [N, NS] cumsum — rare in
    # steady state, so it lives behind a cond. All inputs here are global
    # (ns window replicated, ns_id/live psum-stitched), making the
    # predicate mesh-uniform and the cond safe under shard_map.
    ns_live_tot = seg_ns_sum(live_f)
    ns_ids_dense = jnp.arange(config.max_namespaces, dtype=jnp.int32)
    ns_already_dense = W.window_sum_at(
        spec, ns_state, now, 0, ns_ids_dense
    ).astype(jnp.float32)
    ns_budget_dense = rules.ns_max_qps * (spec.interval_ms / 1000.0)
    crossing = (
        (ns_live_tot > 0)
        & (ns_already_dense + ns_live_tot > ns_budget_dense)
        & (ns_already_dense + 1.0 <= ns_budget_dense)
    )

    def ns_ok_precise(_):
        ns_incl = _blocked_cumsum(_ns_one_hot() * live_f[:, None])
        ns_prefix = (
            jnp.take_along_axis(ns_incl, ns_id[:, None], axis=1)[:, 0]
            - live_f
        )
        ns_already = ns_already_dense[ns_id]
        ns_budget = ns_budget_dense[ns_id]
        return (ns_already + ns_prefix + 1.0) <= ns_budget

    def ns_ok_fast(_):
        ok_ns = (ns_already_dense + 1.0) <= ns_budget_dense
        return ok_ns[ns_id]

    ns_ok = jax.lax.cond(
        jnp.any(crossing), ns_ok_precise, ns_ok_fast, None
    )
    return ns_id, ns_ok, seg_ns_sum


def _decide_core(config, state, rules, batch, now, **kw) -> tuple:
    """:func:`_decide_core_arms` without its ``arms``: ``(state', verdicts)``,
    what the library entries, the benches and the tests take."""
    state, verdicts, _arms = _decide_core_arms(
        config, state, rules, batch, now, **kw
    )
    return state, verdicts


def _decide_core_arms(
    config: EngineConfig,
    state: EngineState,
    rules: RuleTable,
    batch: RequestBatch,
    now: jax.Array,
    axis_name: Optional[str] = None,
    grouped: bool = False,
    uniform: bool = False,
) -> tuple:
    """The decision pipeline, single-shard or mesh-sharded.

    With ``axis_name`` set (inside ``shard_map`` over a mesh axis that shards
    the flow dimension of ``state.flow``/``state.occupy`` and the per-flow
    rule arrays), each device evaluates the requests whose flow slot it owns
    and three ``psum``\\ s stitch the global picture together: rule ownership,
    namespace ids, and the final verdicts. The namespace window is replicated
    and updated identically on every device (its inputs are all global), so
    no collective is needed for its state. These are tiny ``[N]``-sized
    collectives riding ICI — the flow tensors themselves never move.

    Serving fast-path flags (static — the host batcher picks the compiled
    variant per batch):

    - ``grouped``: the batcher placed same-flow requests contiguously (e.g.
      sorted by slot; padding rows at the end are fine). Skips the device
      argsort in the segment-prefix builder.
    - ``uniform``: all live requests acquire the same token count (the
      overwhelmingly common acquire=1 traffic). Greedy admission then has
      the closed form ``admit = rank < floor((threshold - passed)/acquire)``
      — ONE prefix pass, exact (the iterative refinement is only needed for
      mixed acquire sizes, where greedy admission is not associative).

    Returns ``(state', verdicts, arms)``; ``arms`` (``int32[ARM_FIELDS]``) is
    what the step's cond-gated arms did: which of the ``shaping``, ``pacing``,
    ``occupy`` and ``breaker`` conds took its live branch, the shaped, paced
    and prioritized rows of the batch, and what the breaker arm did with its
    rows (``ARM_*``). The serve steps hand it out inside their
    packed verdicts (:func:`pack_verdicts`); :func:`_decide_core` drops it.
    """
    spec = flow_spec(config)
    now = jnp.asarray(now, jnp.int32)
    N = config.batch_size
    f_local = rules.valid.shape[0]

    if axis_name is not None:
        offset = jax.lax.axis_index(axis_name).astype(jnp.int32) * f_local

        def psum(x):
            # every [N]-sized collective that stitches the shards together
            # reads <arm>/psum_stitch in a device trace
            with jax.named_scope("psum_stitch"):
                return jax.lax.psum(x, axis_name=axis_name)

        pmax = partial(jax.lax.pmax, axis_name=axis_name)
    else:
        offset = jnp.int32(0)
        psum = lambda x: x  # noqa: E731
        pmax = lambda x: x  # noqa: E731

    with jax.named_scope("roll_guard"):
        local_slot = batch.flow_slot - offset
        in_range = (batch.flow_slot >= 0) & (local_slot >= 0) & (local_slot < f_local)
        safe_slot = jnp.where(in_range, local_slot, 0)
        owned = in_range & rules.valid[safe_slot]
        has_rule = psum(owned.astype(jnp.int32)) > 0
        live = batch.valid & has_rule
        no_rule = batch.valid & ~has_rule

        acquire_f = batch.acquire.astype(jnp.float32)

        ns_id, ns_ok, seg_ns_sum = _ns_guard(
            config, spec, state.ns, rules, now, psum, owned, safe_slot, live
        )
        too_many = live & ~ns_ok
        ns_admitted = live & ns_ok  # global mask — identical on every device
        active = ns_admitted & owned  # flow evaluation happens on the owner

    if config.prefix_impl == "grouped":
        # "grouped" is only sound when the host batcher sorted the batch —
        # that guarantee arrives via decide()'s grouped flag, never via
        # config (on an interleaved batch it would silently drop earlier
        # same-flow contributions and break the no-overshoot guarantee)
        raise ValueError(
            "prefix_impl='grouped' is not a config value; pass grouped=True "
            "to decide() from a batcher that groups same-flow requests"
        )
    flow_prefix = _segment_prefix_builder(
        safe_slot, "grouped" if grouped else config.prefix_impl
    )

    # ------------------------------------------------------------------
    # 1b. circuit breakers (DegradeSlot): OPEN/HALF_OPEN rows shed here —
    #     they write NO flow-window events (like the namespace-guard
    #     refusals above) and answer DEGRADED with retry-after-ms. The
    #     HALF_OPEN probe winner stays in `active` and runs the normal
    #     admission below. Skipped at trace time when the table carries
    #     no degrade rules (None br_* columns); otherwise cond-gated
    #     inside breaker_gate on a mesh-uniform "any breaker row"
    #     predicate.
    # ------------------------------------------------------------------
    with jax.named_scope("breaker"):
        degraded, br_retry, breaker_ws, br_said = _breaker_gate(
            config, spec, state, rules, now, safe_slot, active, flow_prefix, psum
        )
        active = active & ~degraded

    # ------------------------------------------------------------------
    # 2. per-request threshold (ClusterFlowChecker.java:38-48)
    # ------------------------------------------------------------------
    with jax.named_scope("threshold"):
        conn = rules.ns_connected[ns_id].astype(jnp.float32)
        factor = jnp.where(
            rules.mode[safe_slot] == int(ThresholdMode.AVG_LOCAL), conn, 1.0
        )

        # the occupy window's rows at the batch's slots, fetched once for
        # both of their readers: `matured` (borrows whose bucket has arrived
        # count as passed, here) and `waiting` (tokens still booked ahead,
        # read by the occupy check below). Only while the window can matter:
        # a ring slot behind `now` inside the interval, or one ahead of it
        # with a prioritized row to ask about it. Without prioritized or
        # paced traffic no slot is ever live and the step leaves the window
        # alone. starts, clock and batch are replicated: a mesh-uniform
        # predicate
        n_prio = jnp.sum((batch.prioritized & batch.valid).astype(jnp.int32))
        any_prio = n_prio > 0
        matured, waiting = jax.lax.cond(
            jnp.any(W.valid_mask(spec, state.occupy, now))
            | (any_prio & jnp.any(W.future_valid_mask(spec, state.occupy, now))),
            lambda occ: W.past_and_future_sums_at(spec, occ, now, 0, safe_slot),
            lambda occ: (jnp.zeros((N,), occ.counts.dtype),) * 2,
            state.occupy,
        )
        passed = (
            W.window_sum_at(spec, state.flow, now, ClusterEvent.PASS, safe_slot)
            + matured
            # wire rev 5: tokens delegated to clients as local-admission leases
            # are pre-paid — charged at grant time — so they occupy the window
            # exactly like passed tokens until they expire or are credited back
            + W.window_sum_at(spec, state.flow, now, ClusterEvent.LEASED, safe_slot)
        ).astype(jnp.float32)

    # ------------------------------------------------------------------
    # 2b. traffic shaping (FlowRule.controlBehavior): WARM_UP modulates the
    #     admission rate along the stored-token slope curve; RATE_LIMITER
    #     rows skip window admission entirely and are paced below. Both
    #     blocks are cond-gated on mesh-uniform "any shaped row in this
    #     batch" predicates, so a reject-only batch pays two [N] psums and
    #     nothing else.
    # ------------------------------------------------------------------
    with jax.named_scope("shaping"):
        beh = rules.behavior[safe_slot].astype(jnp.int32)
        is_warm = (beh == 1) | (beh == 3)
        is_pace = (beh == 2) | (beh == 3)
        warm_rows = active & is_warm
        pace_try = active & is_pace
        active_window = active & ~is_pace
        # the owner's 0/1 per row, stitched: the same two collectives the
        # predicates always took, summed as well for the step's `arms`
        warm_seen = psum(warm_rows.astype(jnp.int32))
        pace_seen = psum(pace_try.astype(jnp.int32))
        any_warm = jnp.any(warm_seen > 0)
        n_paced = jnp.sum(pace_seen)
        any_pace = n_paced > 0
        n_shaped = jnp.sum(((warm_seen + pace_seen) > 0).astype(jnp.int32))

        cnt = rules.count[safe_slot]
        cnt_safe = jnp.maximum(cnt, 1e-6)

        def warm_on(_):
            qps_, tokens_new, do_sync, cur_sec = _warmup_curve(
                spec, now, passed, cnt, cnt_safe,
                rules.warning_token[safe_slot],
                rules.max_token[safe_slot],
                rules.slope[safe_slot],
                rules.cold_count[safe_slot],
                state.shaping.warm_filled[safe_slot],
                state.shaping.warm_tokens[safe_slot],
                warm_rows,
            )
            # duplicate same-flow rows scatter identical values (pure function
            # of state + now), so .set stays deterministic
            scat = jnp.where(do_sync, safe_slot, f_local)
            wt = state.shaping.warm_tokens.at[scat].set(tokens_new, mode="drop")
            wf = state.shaping.warm_filled.at[scat].set(cur_sec, mode="drop")
            return qps_, wt, wf

        def warm_off(_):
            return cnt, state.shaping.warm_tokens, state.shaping.warm_filled

        qps, warm_tokens_ws, warm_filled_ws = jax.lax.cond(
            any_warm, warm_on, warm_off, None
        )

        # rule count is per-second (ClusterMetric.getAvg divides by interval
        # seconds before comparing); the window budget scales by interval length
        rate_qps = qps * factor * config.exceed_count
        threshold = rate_qps * (spec.interval_ms / 1000.0)

    # ------------------------------------------------------------------
    # 3. prefix-sum admission (odd refinement count ⇒ ⊆ sequential-exact)
    # ------------------------------------------------------------------
    with jax.named_scope("admit"):
        if uniform:
            # closed-form greedy admission: with one acquire size `a` per batch,
            # the admitted set of each flow is exactly its first
            # floor((threshold - passed)/a) active requests
            a = jnp.max(jnp.where(live, batch.acquire, 0)).astype(jnp.float32)
            a_safe = jnp.maximum(a, 1.0)
            rank = flow_prefix(active_window.astype(jnp.float32))
            admit = active_window & (passed + rank * a + a <= threshold)
            quota = jnp.floor(jnp.maximum(threshold - passed, 0.0) / a_safe)
            admitted_prefix = jnp.minimum(rank, quota) * a
        else:
            admit = active_window
            iters = config.admission_refine_iters
            if iters % 2 == 0:
                raise ValueError(
                    "admission_refine_iters must be odd: an odd iteration count "
                    "makes the final admission mask a subset of the "
                    "sequential-greedy set (no-overshoot guarantee)"
                )
            for _ in range(iters):
                contrib = jnp.where(admit, acquire_f, 0.0)
                prefix = flow_prefix(contrib)  # earlier admitted same-flow tokens
                admit = active_window & (passed + prefix + acquire_f <= threshold)
            admitted_prefix = flow_prefix(jnp.where(admit, acquire_f, 0.0))

    # ------------------------------------------------------------------
    # 3b. pacing (RateLimiterController.canPass as a batch closed form):
    #     within one flow only the FIRST admitted row can pull
    #     latestPassedTime up to now, so under the all-admit assumption
    #     L_j = max(L0, now - cost_first) + inclusive-cost-prefix_j holds
    #     exactly; a row rejects when its wait exceeds maxQueueingTimeMs.
    #     With uniform costs the waits are monotone within a flow, rejects
    #     form a suffix, and one pass is exact. Mixed-acquire batches
    #     refine like the window-admission loop plus a final tightening
    #     recompute — the accepted set stays a subset of the
    #     sequential-exact one, so pacing can never over-admit. All the
    #     arithmetic is done relative to `now` so f32 stays exact (engine
    #     ms exceeds the f32 integer range after ~4.6h; waits never do).
    # ------------------------------------------------------------------
    with jax.named_scope("pacing"):
        def pace_on(_):
            cost_f = jnp.round(1000.0 * acquire_f / jnp.maximum(rate_qps, 1e-6))
            rel0 = jnp.maximum(
                state.shaping.lpt[safe_slot] - now, jnp.int32(-(2**20))
            ).astype(jnp.float32)
            maxq = rules.max_queue_ms[safe_slot].astype(jnp.float32)

            def pace_pass(accept):
                contrib = jnp.where(accept, cost_f, 0.0)
                # a row's own cost always counts toward its hypothetical
                # schedule (contrib only carries it into LATER rows' prefixes) —
                # otherwise a rejected row sheds its own cost and oscillates
                # back into the accepted set on the next refinement pass
                incl = flow_prefix(contrib) + cost_f
                rank_p = flow_prefix(accept.astype(jnp.float32))
                first = accept & (rank_p == 0.0)
                scat_first = jnp.where(first, safe_slot, f_local)
                c_first = jnp.zeros((f_local,), jnp.float32).at[scat_first].set(
                    cost_f, mode="drop"
                )[safe_slot]
                # L_row - now, directly: base_rel = max(L0 - now, -cost_first)
                l_rel = jnp.maximum(rel0, -c_first) + incl
                return l_rel

            accept = pace_try
            l_rel = pace_pass(accept)
            for _i in range(0 if uniform else config.admission_refine_iters):
                accept = pace_try & (l_rel <= maxq)
                l_rel = pace_pass(accept)
            accept = pace_try & (l_rel <= maxq)
            wait_i = jnp.maximum(l_rel, 0.0).astype(jnp.int32)
            # scatter-max: the last accepted row's schedule is the flow's new
            # latestPassedTime; non-accepted rows leave it untouched
            scat = jnp.where(accept, safe_slot, f_local)
            lpt_ = state.shaping.lpt.at[scat].max(
                now + jnp.round(l_rel).astype(jnp.int32), mode="drop"
            )
            return accept, wait_i, lpt_

        def pace_off(_):
            return (
                jnp.zeros((N,), bool),
                jnp.zeros((N,), jnp.int32),
                state.shaping.lpt,
            )

        pace_admit, pace_wait, lpt_ws = jax.lax.cond(
            any_pace, pace_on, pace_off, None
        )
        pace_now = pace_admit & (pace_wait == 0)
        pace_later = pace_admit & (pace_wait > 0)
        pace_reject = pace_try & ~pace_admit

    # ------------------------------------------------------------------
    # 4. priority occupy of the next window (ClusterFlowChecker.java:84-97)
    #    — the whole occupy path (reads, prefix, future-window write) is
    #    gated on "any prioritized request in the batch", which is a global
    #    property of the replicated batch and therefore a mesh-uniform
    #    predicate (safe around the pmax inside add_future)
    # ------------------------------------------------------------------
    with jax.named_scope("occupy"):
        blocked = active_window & ~admit
        wait_next = spec.bucket_ms - (now % spec.bucket_ms)
        # occupy borrowing stays a DEFAULT-behavior feature: a shaped rule's
        # admission curve is the whole point, and the reference's shapers have
        # no occupy interplay either
        try_occupy = blocked & batch.prioritized & (beh == 0)

        def occupy_check(_):
            next_start = now + wait_next
            # currently-valid PASS tokens that will have expired by the next window
            horizon = next_start - spec.interval_ms
            cur_valid = W.valid_mask(spec, state.flow, now)
            expiring_mask = cur_valid & (state.flow.starts <= horizon)
            pass_rows = W.rows_at(state.flow, safe_slot)[
                :, :, ClusterEvent.PASS
            ]  # [N, B]
            expiring = jnp.sum(
                pass_rows * expiring_mask[None, :].astype(pass_rows.dtype), axis=1
            ).astype(jnp.float32)
            occ_contrib = jnp.where(try_occupy, acquire_f, 0.0)
            occ_prefix = flow_prefix(occ_contrib)  # conservative: all triers count
            return _occupy_feasible(
                config, try_occupy, passed, expiring, admitted_prefix,
                waiting.astype(jnp.float32), occ_prefix, acquire_f, threshold,
            )

        can_occupy = jax.lax.cond(
            any_prio, occupy_check, lambda _: jnp.zeros((N,), bool), None
        )
        hard_block = blocked & ~can_occupy

    # ------------------------------------------------------------------
    # 5. window updates: one scatter per static event channel into the
    #    current bucket's slab (the form measured fastest on v5e — see
    #    add_event_rows: no scatter ever sees the whole window), with the rare
    #    OCCUPIED_PASS channel cond-gated. Rows whose masks are false
    #    contribute zeros (scatter targets stay in range, so no drops
    #    needed).
    # ------------------------------------------------------------------
    # paced rows with wait 0 pass NOW and count as ordinary PASS traffic;
    # paced rows with a wait charge the future window below (like occupy
    # borrows — they fold into the PASS read when their window matures, so
    # they are never double-counted); paced rejects count as BLOCK
    with jax.named_scope("commit"):
        admit_i = (admit | pace_now).astype(jnp.int32)
        hard_i = (hard_block | pace_reject).astype(jnp.int32)
        ev = ClusterEvent
        row_updates = jnp.stack(
            [
                batch.acquire * admit_i,  # PASS
                admit_i,  # PASS_REQUEST
                batch.acquire * hard_i,  # BLOCK
                hard_i,  # BLOCK_REQUEST
            ],
            axis=1,
        )
        flow_ws = W.add_event_rows(
            spec, state.flow, now, safe_slot, row_updates,
            channels=(ev.PASS, ev.PASS_REQUEST, ev.BLOCK, ev.BLOCK_REQUEST),
        )
        # OCCUPIED_PASS marks prioritized requests admitted normally (the
        # reference's OK branch adds OCCUPIED_PASS when prioritized; the occupy
        # path records only the future-window WAITING, which is `occupy_ws`
        # below). Prioritized traffic is rare, so this scatter is cond-gated on
        # the same mesh-uniform predicate as the occupy path.
        flow_ws = jax.lax.cond(
            any_prio,
            lambda ws: W.add_event_rows(
                spec, ws, now, safe_slot,
                (batch.acquire
                 * (admit & batch.prioritized).astype(jnp.int32))[:, None],
                channels=(ev.OCCUPIED_PASS,),
            ),
            lambda ws: ws,
            flow_ws,
        )
        # pmax over the mesh axis keeps the replicated occupy.starts identical on
        # every device even when only the owner shard sees a borrow (each shard
        # then also zeroes its own stale counts column for the reset slot).
        # Paced SHOULD_WAIT admissions charge the same future-window tensor at
        # their assigned wait — the cross-batch borrow that makes open-loop
        # bursts unable to over-admit: the tokens are pre-paid into the window
        # where the waiter is scheduled to pass.
        charge_wait = jnp.where(
            can_occupy, jnp.full((N,), wait_next, jnp.int32), pace_wait
        )
        charge_valid = can_occupy | pace_later
        occupy_ws = jax.lax.cond(
            any_prio | any_pace,
            lambda occ: W.add_future(
                spec, occ, now,
                wait_ms=charge_wait,
                resource_ids=safe_slot,
                channel=0,
                values=batch.acquire,
                valid=charge_valid,
                combine_desired=pmax,
            ),
            lambda occ: occ,
            state.occupy,
        )
        # namespace guard counts every ns-admitted request (the guard counts
        # arrivals, not flow verdicts — GlobalRequestLimiter adds on tryPass);
        # the mask is global, so the replicated ns window stays consistent. The
        # per-namespace deltas ride seg_ns_sum (MXU matvec on TPU, scatter-add
        # elsewhere).
        ns_deltas = seg_ns_sum(ns_admitted.astype(jnp.float32))
        ns_ws = W.add_column(spec, state.ns, now, ns_deltas)

    # ------------------------------------------------------------------
    # 6. verdicts — owner emits status+1, psum stitches shards together
    # ------------------------------------------------------------------
    with jax.named_scope("verdicts"):
        local_status = jnp.where(
            degraded,
            int(TokenStatus.DEGRADED) + 1,
            jnp.where(
                admit | pace_now,
                int(TokenStatus.OK) + 1,
                jnp.where(
                    can_occupy | pace_later,
                    int(TokenStatus.SHOULD_WAIT) + 1,
                    jnp.where(
                        hard_block | pace_reject, int(TokenStatus.BLOCKED) + 1, 0
                    ),
                ),
            ),
        ).astype(jnp.int32)
        combined = psum(local_status)
        status = jnp.where(
            ~batch.valid,
            int(TokenStatus.FAIL),
            jnp.where(
                no_rule,
                int(TokenStatus.NO_RULE_EXISTS),
                jnp.where(
                    too_many,
                    int(TokenStatus.TOO_MANY_REQUEST),
                    jnp.where(combined > 0, combined - 1, int(TokenStatus.FAIL)),
                ),
            ),
        ).astype(jnp.int8)

        wait_ms = psum(
            jnp.where(
                can_occupy, wait_next, jnp.where(pace_later, pace_wait, 0)
            ).astype(jnp.int32)
        )
        remaining_local = jnp.clip(
            threshold - passed - admitted_prefix - jnp.where(admit, acquire_f, 0.0),
            0.0,
            2**30,
        ).astype(jnp.int32)
        # blockedResult() in the reference always carries remaining=0 — and so
        # do paced admissions (RateLimiterController has no token count to
        # report); DEGRADED rows carry retry-after-ms instead
        remaining = psum(
            jnp.where(admit, remaining_local, jnp.where(degraded, br_retry, 0))
        )

    new_state = EngineState(
        flow=flow_ws, occupy=occupy_ws, ns=ns_ws,
        shaping=ShapingState(
            lpt=lpt_ws, warm_tokens=warm_tokens_ws, warm_filled=warm_filled_ws
        ),
        # completion outcomes are written by the decoupled outcome step
        # (engine/outcome.py), never by the admission kernel — the serve
        # path's donated buffers just flow through
        outcome=state.outcome,
        breaker=breaker_ws,
    )
    verdicts = VerdictBatch(status=status, wait_ms=wait_ms, remaining=remaining)
    # the three cond predicates above and the rows behind them: ARM_* order
    arms = jnp.stack([
        any_warm * ARM_SHAPING + any_pace * ARM_PACING + any_prio * ARM_OCCUPY
        + br_said[0] * ARM_BREAKER,
        n_shaped, n_paced, n_prio, *br_said[1:],
    ]).astype(jnp.int32)
    return new_state, verdicts, arms


@partial(jax.jit, static_argnames=("config", "grouped", "uniform"))
def decide(
    config: EngineConfig,
    state: EngineState,
    rules: RuleTable,
    batch: RequestBatch,
    now: jax.Array,
    grouped: bool = False,
    uniform: bool = False,
) -> tuple:
    """``(state, rules, batch, now) -> (state', verdicts)`` — single shard.

    ``grouped``/``uniform`` are the serving fast-path flags (see
    :func:`_decide_core`); the host batcher sets them per batch when its
    layout guarantees hold, selecting one of four compiled variants.
    """
    return _decide_core(
        config, state, rules, batch, now, axis_name=None,
        grouped=grouped, uniform=uniform,
    )


def step_name(kind: str, config: EngineConfig, uniform: bool,
              depth: Optional[int] = None) -> str:
    """The name a serving step is jitted under, so that a device trace reads
    ``jit_decide_b1024_mixed`` and not ``jit__unknown``:
    ``<kind>[_d<depth>]_b<batch_size>_<uniform|mixed>``."""
    d = "" if depth is None else f"_d{depth}"
    return f"{kind}{d}_b{config.batch_size}_{'uniform' if uniform else 'mixed'}"


def decide_donating(config: EngineConfig, grouped: bool = False,
                    uniform: bool = False):
    """A single-shard step like :func:`decide` that DONATES the state
    buffers: every step scatter-updates the full
    ``[max_flows, buckets, events]`` window tensors, and without donation
    XLA must copy them first (measured 22% of a 64-bucket step at 100k
    flows on CPU; on TPU it is HBM traffic and allocator churn).

    Returns a cached-callable ``step(state, rules, packed) -> (state',
    verdicts)``: ``packed`` is the ONE host argument of a dispatch, the
    ``int32[PACKED_LINES, N]`` array of :func:`pack_requests` (request batch
    and clock; :func:`unpack_requests` is traced at the step's head), and
    ``verdicts`` the ``int32[3, N]`` buffer of :func:`pack_verdicts` (host
    side: :func:`unpack_verdicts`). The caller contract: nothing else may
    hold the passed state (the token service's lock makes ``self._state, v
    = step(self._state, …)`` the only reader), and warmup-style calls must
    feed throwaway states.
    """
    def step(state, rules, packed):
        batch, now = unpack_requests(packed)
        state, verdicts, arms = _decide_core_arms(
            config, state, rules, batch, now, axis_name=None,
            grouped=grouped, uniform=uniform,
        )
        return state, pack_verdicts(verdicts, arms)

    return jax.jit(
        named(step, step_name("decide", config, uniform)),
        donate_argnums=(0,),
    )


def decide_fused_donating(config: EngineConfig, depth: int,
                          grouped: bool = False, uniform: bool = False):
    """A chained multi-frame step: ``lax.scan`` of :func:`_decide_core`
    over ``depth`` stacked request frames, donating the state buffers like
    :func:`decide_donating`.

    Returns ``step(state, rules, packed) -> (state', verdicts)`` where
    ``packed`` is the ONE host argument, an ``int32[PACKED_LINES, depth,
    batch_size]`` block (:func:`alloc_packed_block`: the per-frame
    :func:`pack_requests` lines stacked along a new middle axis, one shared
    clock) and ``verdicts`` is ONE ``int32[3, depth, batch_size]`` buffer
    (:func:`pack_verdicts` of the ``[depth, batch_size]`` verdict leaves,
    same frame order). Frame ``k`` sees exactly the state frame
    ``k-1`` produced — the on-device equivalent of ``depth`` consecutive
    :func:`decide_donating` calls at one shared ``now``, with the
    per-dispatch host/RTT overhead paid once for the whole chain.

    The scanned batch VARIES per iteration, so XLA cannot hoist the
    request-dependent chains out of the loop body (it does hoist them for
    a loop-constant batch, which is why a scan of one repeated batch
    under-reports the step).
    """
    if depth < 1:
        raise ValueError(f"fused depth must be >= 1, got {depth}")
    core = partial(
        _decide_core_arms, config, axis_name=None, grouped=grouped,
        uniform=uniform,
    )

    def fused(state, rules, packed):
        batches, now = unpack_requests(packed)

        def body(st, batch):
            st, verdicts, arms = core(st, rules, batch, now)
            return st, (verdicts, arms)

        state, (verdicts, arms) = jax.lax.scan(
            body, state, batches, length=depth
        )
        return state, pack_verdicts(verdicts, arms)

    return jax.jit(
        named(fused, step_name("decide_fused", config, uniform, depth)),
        donate_argnums=(0,),
    )
