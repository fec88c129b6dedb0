"""Cluster concurrency limiting on the device: a gauge that goes down as well
as up, a table of live tokens, and expiry.

Upstream (``sentinel-cluster-server-default``): ``ConcurrentClusterFlowChecker``
(synchronized check-and-add of ``nowCalls`` against the rule's count),
``CurrentConcurrencyManager`` (``nowCalls`` per flow), ``TokenCacheNodeManager``
(the issued token ids) and ``RegularExpireStrategy`` (tokens of dead clients
reclaimed after ``ClusterFlowConfig#resourceTimeout``). Here they are one
donated state and one jitted step a serve bucket,
``jit_concurrent_step_b<bucket>``, which the token service
(``DefaultTokenService.dispatch_concurrent_batch``) is the only caller of.

State (:class:`ConcurrentState`), on the rule slots ``F`` of the service's
concurrency look-up and on a ring of ``max_tokens`` token slots:

    held, level, timeout_ms   int32[F]    tokens out, the rule's level (-1:
                                          no rule), its resource timeout
    tok_flow, tok_count,      int32[T + pad]   per token slot: the flow's
    tok_expire, tok_gen                   rule slot, the count held (0:
                                          free), the engine-ms it expires
                                          at, the generation that issued it
                                          (four flat columns: a scatter into
                                          a line of a [4, T] table makes the
                                          TPU's compiler relay the whole
                                          table out and back, 0.47 ms a step
                                          on the chip, PR 41)
    cursor      int32[8]                  head slot, head generation, the
                                          expiry scan's place, live tokens

A token id is ``generation * max_tokens + slot``. The head moves by one
slot an **acquire row** (passed or not: a refused row leaves its slot as it
was and burns the id), so a dispatch's tokens are one contiguous block of
the ring, written with one slice update and no scatter; a block that would
straddle the ring's end starts again at slot 0 under the next generation.
No free list and no ABA: a slot is told from the id that held it by its
generation, and with every token dead within ``max(timeout) + slack`` the
ring is its own time wheel. An acquire whose slot still holds a live token
answers FAIL (``table_full``): the ring is too small for the traffic.

One step, in this order (the named scopes of a profile):

    concurrent_release   rows sorted by token id (the host's one argsort):
                         slot look-up, liveness and generation match, first
                         occurrence of a duplicate id, ``held`` lowered
    concurrent_expire    ``expire_block`` slots from the scan's place: every
                         live token past its time is reclaimed (up to
                         ``EXPIRE_MAX`` of them by a compaction and a small
                         scatter; more, a client that died holding many, by
                         one scatter over the block); the whole ring is
                         examined every ``ceil(max_tokens / expire_block)``
                         steps
    concurrent_admit     rows grouped by rule slot (the host's other
                         argsort): a row passes iff ``held + counts of the
                         rows before it on its flow + its own <= level``,
                         exact for one acquire size a flow; mixed sizes may
                         under-admit and never over-admit
    concurrent_issue     ids, slots and expiry written, ``held`` raised

Sums are float32 matmul prefixes (``ops/scan_mm``) over counts split in two
10-bit halves, exact for levels up to ``MAX_LEVEL``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.ops.scan_mm import blocked_cummax, blocked_cumsum

# statuses (engine.decide.TokenStatus; not imported: decide imports nothing
# of this module and this module nothing of decide)
ST_OK, ST_BLOCKED, ST_NO_RULE, ST_FAIL = 0, 1, 3, 5
ST_RELEASE_OK, ST_ALREADY_RELEASE = 6, 7

MAX_LEVEL = (1 << 20) - 1  # the prefix sums are exact up to here
EXPIRE_MAX = 128  # due tokens a step reclaims without a block-wide scatter
EXPIRE_STEPS = 16  # the scan goes round the ring in this many steps

# cursor entries
CUR_HEAD, CUR_GEN, CUR_SCAN, CUR_LIVE = 0, 1, 2, 3
# the packed host argument: int32[PACKED_LINES, bucket]
ROW_SLOT, ROW_COUNT, ROW_TOK_SLOT, ROW_TOK_GEN, ROW_HEAD = 0, 1, 2, 3, 4
PACKED_LINES = 5
HEAD_NOW, HEAD_ACQUIRES, HEAD_RELEASES = 0, 1, 2
# the packed verdicts: int32[VERDICT_LINES, bucket]
OUT_STATUS, OUT_REMAINING, OUT_ID_SLOT, OUT_ID_GEN, OUT_RELEASE, OUT_MISC = (
    0, 1, 2, 3, 4, 5)
VERDICT_LINES = 6
MISC_EXPIRED, MISC_TABLE_FULL, MISC_LIVE = 0, 1, 2
NO_SLOT = -1  # an acquire row on a flow with no rule
PAD_SLOT = -2  # bucket padding


class ConcurrentConfig(NamedTuple):
    max_flows: int  # F: rule slots
    max_tokens: int = 1 << 20  # T: token slots in the ring
    max_bucket: int = 16384  # the largest serve bucket (pads the table)

    @property
    def expire_block(self) -> int:
        """Token slots one step's expiry examines: a sixteenth of the ring,
        and all of a ring under 1,024 slots."""
        t = self.max_tokens
        return t if t < 1024 else -(-t // EXPIRE_STEPS)

    @property
    def table_len(self) -> int:
        """Slots the table holds: the ring, and behind it room for a block
        that starts at the ring's last slot (never addressed by an id, so
        that a slice never has to be clamped)."""
        return self.max_tokens + max(self.expire_block, self.max_bucket)


class ConcurrentState(NamedTuple):
    held: jax.Array
    level: jax.Array
    timeout_ms: jax.Array
    tok_flow: jax.Array
    tok_count: jax.Array
    tok_expire: jax.Array
    tok_gen: jax.Array
    cursor: jax.Array


def make_concurrent_state(config: ConcurrentConfig) -> ConcurrentState:
    f = config.max_flows
    cursor = np.zeros(8, np.int32)
    cursor[CUR_HEAD] = 1  # id 0 is "no token"
    return ConcurrentState(
        held=jnp.zeros(f, jnp.int32),
        level=jnp.full(f, -1, jnp.int32),
        timeout_ms=jnp.zeros(f, jnp.int32),
        tok_flow=jnp.zeros(config.table_len, jnp.int32),
        tok_count=jnp.zeros(config.table_len, jnp.int32),
        tok_expire=jnp.zeros(config.table_len, jnp.int32),
        tok_gen=jnp.zeros(config.table_len, jnp.int32),
        cursor=jnp.asarray(cursor),
    )


def state_bytes(config: ConcurrentConfig) -> int:
    return 4 * (3 * config.max_flows + 4 * config.table_len + 8)


def pack_concurrent_rows(bucket: int, slots, counts, tok_slots, tok_gens,
                         now: int = 0) -> np.ndarray:
    """The step's one host argument. ``slots`` / ``counts``: the acquire
    rows grouped by rule slot (``NO_SLOT`` where the flow has no rule);
    ``tok_slots`` / ``tok_gens``: the release rows sorted by token id (slot
    -1 for an id no table of this size can have issued)."""
    a, r = len(slots), len(tok_slots)
    out = np.zeros((PACKED_LINES, bucket), np.int32)
    out[ROW_SLOT, :a] = slots
    out[ROW_SLOT, a:] = PAD_SLOT
    out[ROW_COUNT, :a] = counts
    out[ROW_TOK_SLOT, :r] = tok_slots
    out[ROW_TOK_SLOT, r:] = -1
    out[ROW_TOK_GEN, :r] = tok_gens
    out[ROW_HEAD, :3] = (now, a, r)
    return out


def split_token_ids(config: ConcurrentConfig, token_ids: np.ndarray):
    """``(slot, generation)`` of wire token ids, int32 each; slot -1 for an
    id that is not one (0, negative, or past what 31 bits of generation
    reach)."""
    ids = np.asarray(token_ids, np.int64)
    gen, slot = np.divmod(ids, np.int64(config.max_tokens))
    bad = (ids <= 0) | (gen > np.int64(2**31 - 1))
    return (np.where(bad, -1, slot).astype(np.int32),
            np.where(bad, 0, gen).astype(np.int32))


def join_token_ids(config: ConcurrentConfig, slot, gen) -> np.ndarray:
    """Wire ids of the step's ``(slot, generation)`` pairs."""
    return (np.asarray(gen, np.int64) * np.int64(config.max_tokens)
            + np.asarray(slot, np.int64))


def _group_prefix(seg_start, contrib):
    """Exclusive sum of the int32 ``contrib`` (0 .. 2^20) over the rows
    before each row in its group, groups being contiguous runs that begin
    where ``seg_start`` is set. Two exact float32 prefixes over the low and
    the high ten bits, joined in int32 and saturated at 2^30 (past any
    level)."""
    parts = jnp.stack([contrib & 1023, contrib >> 10], axis=1)
    incl = blocked_cumsum(parts.astype(jnp.float32))
    excl = incl - parts
    out = []
    for k in range(2):
        base = blocked_cummax(jnp.where(seg_start, excl[:, k], -1.0))
        out.append((excl[:, k] - base).astype(jnp.int32))
    lo, hi = out
    return jnp.minimum(hi, 1 << 20) * 1024 + lo


def make_concurrent_step(config: ConcurrentConfig, bucket: int):
    """The jitted step of one serve bucket: ``(state, packed
    int32[PACKED_LINES, bucket]) -> (state', verdicts int32[VERDICT_LINES,
    bucket])``, state donated. Acquire verdicts are in the packed order
    (grouped by rule slot), release statuses in theirs (sorted by id)."""
    if bucket > config.max_bucket:
        raise ValueError(f"bucket {bucket} over max_bucket {config.max_bucket}")
    n_flows, ring = config.max_flows, config.max_tokens
    block = config.expire_block
    i = jnp.arange(bucket, dtype=jnp.int32)

    def step(state: ConcurrentState, packed: jax.Array):
        head = packed[ROW_HEAD]
        now, n_acq = head[HEAD_NOW], head[HEAD_ACQUIRES]
        n_rel = head[HEAD_RELEASES]
        held, cursor = state.held, state.cursor
        tok_flow, tok_count = state.tok_flow, state.tok_count
        tok_expire, tok_gen = state.tok_expire, state.tok_gen
        n_slots = tok_count.shape[0]

        with jax.named_scope("concurrent_release"):
            t_slot, t_gen = packed[ROW_TOK_SLOT], packed[ROW_TOK_GEN]
            at = jnp.maximum(t_slot, 0)
            out = tok_count[at]
            first = jnp.concatenate([
                jnp.ones((1,), bool),
                (t_slot[1:] != t_slot[:-1]) | (t_gen[1:] != t_gen[:-1]),
            ])
            freed = ((i < n_rel) & (t_slot >= 0) & first & (out > 0)
                     & (tok_gen[at] == t_gen))
            rel_status = jnp.where(freed, ST_RELEASE_OK, ST_ALREADY_RELEASE)
            held = held.at[jnp.where(freed, tok_flow[at], n_flows)].add(
                -jnp.where(freed, out, 0), mode="drop")
            tok_count = tok_count.at[jnp.where(freed, at, n_slots)].set(
                0, mode="drop")
            n_freed = freed.sum(dtype=jnp.int32)

        with jax.named_scope("concurrent_expire"):
            scan = cursor[CUR_SCAN]
            part = jax.lax.dynamic_slice(tok_count, (scan,), (block,))
            j = jnp.arange(block, dtype=jnp.int32)
            due = ((part > 0) & (scan + j < ring) & (
                jax.lax.dynamic_slice(tok_expire, (scan,), (block,)) <= now))
            n_due = due.sum(dtype=jnp.int32)

            def few(held):
                # the places of the due tokens, one fused masked reduce a
                # place: nothing of [EXPIRE_MAX, block] is ever written,
                # and the scatter sees EXPIRE_MAX updates
                rank = blocked_cumsum(due.astype(jnp.float32)).astype(
                    jnp.int32)
                want = jnp.arange(1, EXPIRE_MAX + 1, dtype=jnp.int32)
                where = jnp.sum(
                    jnp.where(due[None, :] & (rank[None, :] == want[:, None]),
                              j[None, :], 0), axis=1)
                taken = want <= n_due
                return held.at[
                    jnp.where(taken, tok_flow[scan + where], n_flows)
                ].add(-jnp.where(taken, part[where], 0), mode="drop")

            def many(held):
                # a client died holding many: the whole block's worth in
                # one scatter (rare; 1.3 ms for 65,536 slots on a v5e)
                flows = jax.lax.dynamic_slice(tok_flow, (scan,), (block,))
                return held.at[jnp.where(due, flows, n_flows)].add(
                    -jnp.where(due, part, 0), mode="drop")

            held = jax.lax.cond(n_due > EXPIRE_MAX, many, few, held)
            tok_count = jax.lax.dynamic_update_slice(
                tok_count, jnp.where(due, 0, part), (scan,))
            scan = jnp.where(scan + block >= ring, 0, scan + block)

        with jax.named_scope("concurrent_admit"):
            slot, count = packed[ROW_SLOT], packed[ROW_COUNT]
            real = i < n_acq
            ruled = real & (slot >= 0)
            at = jnp.clip(slot, 0, n_flows - 1)
            lv = jnp.where(ruled, state.level[at], -1)
            ruled = ruled & (lv >= 0)
            had = held[at]
            # the block of the ring this dispatch's rows take, one slot a
            # row: from the head, or from slot 0 of the next generation
            # where it would pass the ring's end
            wrap = cursor[CUR_HEAD] + n_acq > ring
            start = jnp.where(wrap, 0, cursor[CUR_HEAD])
            gen = cursor[CUR_GEN] + wrap.astype(jnp.int32)
            old_count = jax.lax.dynamic_slice(tok_count, (start,), (bucket,))
            full = ruled & (count > 0) & (old_count > 0)
            active = ruled & (count > 0) & ~full
            # a count past the level is refused whatever it is: held to
            # level + 1 it keeps the sums inside what float32 counts
            c = jnp.where(active, jnp.minimum(count, lv + 1), 0)
            seg_start = jnp.concatenate(
                [jnp.ones((1,), bool), slot[1:] != slot[:-1]])
            before = _group_prefix(seg_start, c)
            admit = active & (had + before + c <= lv)
            got = jnp.where(admit, c, 0)
            admitted_before = _group_prefix(seg_start, got)
            status = jnp.where(
                ~ruled, ST_NO_RULE,
                jnp.where(admit, ST_OK,
                          jnp.where(active, ST_BLOCKED, ST_FAIL)))
            remaining = jnp.where(
                ruled, jnp.maximum(lv - had - admitted_before - got, 0), 0)

        with jax.named_scope("concurrent_issue"):
            def write(column, new, old=None):
                if old is None:
                    old = jax.lax.dynamic_slice(column, (start,), (bucket,))
                return jax.lax.dynamic_update_slice(
                    column, jnp.where(admit, new, old), (start,))

            tok_flow = write(tok_flow, at)
            tok_count = write(tok_count, got, old_count)
            tok_expire = write(tok_expire, now + state.timeout_ms[at])
            tok_gen = write(tok_gen, jnp.full_like(at, gen))
            held = held.at[jnp.where(admit, at, n_flows)].add(
                got, mode="drop")
            n_admit = admit.sum(dtype=jnp.int32)
            n_expired = n_due
            live = cursor[CUR_LIVE] + n_admit - n_freed - n_expired
            nxt = start + n_acq
            cursor = cursor.at[CUR_HEAD].set(nxt).at[CUR_GEN].set(gen)
            cursor = cursor.at[CUR_SCAN].set(scan).at[CUR_LIVE].set(live)
            misc = jnp.zeros(bucket, jnp.int32)
            misc = misc.at[MISC_EXPIRED].set(n_expired)
            misc = misc.at[MISC_TABLE_FULL].set(full.sum(dtype=jnp.int32))
            misc = misc.at[MISC_LIVE].set(live)
            verdicts = jnp.stack([
                status, remaining, jnp.where(admit, start + i, 0),
                jnp.where(admit, gen, 0), rel_status, misc,
            ])
        return ConcurrentState(held, state.level, state.timeout_ms, tok_flow,
                               tok_count, tok_expire, tok_gen,
                               cursor), verdicts

    step.__name__ = step.__qualname__ = f"concurrent_step_b{bucket}"
    return jax.jit(step, donate_argnums=(0,))
