"""Cluster-wide concurrency (semaphore) flow control: the rule, and the host
half of the plane that serves it.

Analog of the reference's concurrent token mode
(``sentinel-cluster-server-default``):

- ``CurrentConcurrencyManager.java:37-95`` — per-flowId ``nowCalls`` counter;
- ``ConcurrentClusterFlowChecker.java:48-74`` — synchronized check+add with
  ``concurrencyLevel = count × (GLOBAL ? 1 : connectedCount)``;
- ``TokenCacheNodeManager.java:28-71`` — issued token-id cache;
- ``RegularExpireStrategy`` — sweep of expired tokens so a crashed client
  cannot leak permits forever (``ClusterFlowConfig#resourceTimeout``, 2 s).

Until PR 41 this module was the whole mechanism: a Python dict under one
lock, asked one frame at a time on the doors' control lane. The gauge, the
token table and expiry now live on the device (``engine/concurrent.py``, one
jitted step a serve bucket) and are served through the doors' data plane in
batch frames (``BATCH_CONCURRENT_ACQUIRE`` / ``BATCH_CONCURRENT_RELEASE``,
codec rev 9) as well as by the reference's single frames (types 3 and 4):
``DefaultTokenService.dispatch_concurrent_batch`` is the one entry, whichever
frame asks. What is left here is what the host keeps: the rule, and
:class:`ConcurrentPlane` — the flow-id -> rule-slot look-up, the level column
(``AVG_LOCAL`` rewrites it when a namespace's client count changes), the
packing of a dispatch into the step's one argument and the unpacking of its
verdicts, no Python per row.

Semantics (the configuration ``concurrent-mesh-100k`` states them as its
guarantees; ``cellbench/families/concurrent_reference.py`` is their plain
reference): an acquire of ``count`` on flow ``f`` passes iff ``held[f] +
count <= level[f]`` at its turn, rows of one dispatch taking their turns in
row order, the releases of a dispatch applied before its acquires; a passed
row carries a token id that is non-zero and never issued before by this
process; a release of a live id lowers ``held`` by the id's count, of any
other id answers ALREADY_RELEASE and changes nothing; a token not released
is reclaimed no earlier than the rule's ``resource_timeout_ms`` after it
was issued and no later than that plus :data:`EXPIRY_SLACK_MS`.

Not carried, as upstream carries none of it: tokens in snapshots,
replication deltas and MOVE blobs (a new primary starts at ``held = 0`` and
answers old ids ALREADY_RELEASE), ``resourceTimeoutStrategy`` KEEP, and
client-offline expiry apart from the resource timeout.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

from sentinel_tpu.engine import concurrent as E
from sentinel_tpu.engine.rules import ThresholdMode
from sentinel_tpu.native import lib as _native

DEFAULT_RESOURCE_TIMEOUT_MS = 2_000  # ClusterFlowConfig#resourceTimeout default
# A token past its time is reclaimed within this: the expiry scan goes round
# the ring in EXPIRE_STEPS steps and the service's timer makes sure a step
# runs every TICK_MS (16 x 25 ms, and room for the steps themselves).
EXPIRY_SLACK_MS = 500
TICK_MS = 25


@dataclass(frozen=True)
class ConcurrentFlowRule:
    """Concurrency-mode cluster rule: at most ``concurrency_level`` permits
    held at once across the cluster (× connected clients when AVG_LOCAL)."""

    flow_id: int
    concurrency_level: int
    mode: ThresholdMode = ThresholdMode.GLOBAL
    resource_timeout_ms: int = DEFAULT_RESOURCE_TIMEOUT_MS
    namespace: str = "default"  # AVG_LOCAL scales by this namespace's clients


class ConcurrentPlane:
    """The host half of the concurrency plane of one token service. The
    service owns the lock, the clock and the counters; every method here
    that touches ``state`` is called with the service's lock held."""

    def __init__(self, max_flows: int, max_tokens: int,
                 serve_buckets: Sequence[int]):
        self.buckets = tuple(int(b) for b in serve_buckets)
        if max_tokens < self.buckets[-1]:
            raise ValueError(
                f"max_tokens {max_tokens} is under the largest serve bucket "
                f"{self.buckets[-1]}: one dispatch's rows take a slot each"
            )
        self.config = E.ConcurrentConfig(int(max_flows), int(max_tokens),
                                         self.buckets[-1])
        self.state = E.make_concurrent_state(self.config)
        self.rules: Dict[int, ConcurrentFlowRule] = {}
        # flow id -> rule slot, of live rules and of retired ones whose
        # tokens are still out (they drain by release and expiry)
        self.slot_of: Dict[int, int] = {}
        self._free = list(range(self.config.max_flows - 1, -1, -1))
        self._steps: Dict[int, object] = {}
        # what a dispatch preps against: one immutable snapshot
        self.lookup = (np.empty(0, np.int64), np.empty(0, np.int32))
        # per slot, for the level column: the rule's count, and the index
        # of its namespace in ``_ns_names`` where the rule is AVG_LOCAL
        self._count = np.zeros(self.config.max_flows, np.int64)
        self._avg_ns = np.full(self.config.max_flows, -1, np.int32)
        self._ns_names: List[str] = []

    # -- rules ---------------------------------------------------------------
    def load_rules(self, rules: Sequence[ConcurrentFlowRule],
                   connected: Dict[str, int]) -> None:
        """Replace the rule set. Slots are stable across reloads; a flow
        whose rule went keeps its slot (and its ``held``) until its tokens
        are back, and answers NO_RULE meanwhile."""
        import jax.numpy as jnp

        rules = list(rules)
        for r in rules:
            if not 0 <= int(r.concurrency_level) <= E.MAX_LEVEL:
                raise ValueError(
                    f"concurrency_level {r.concurrency_level} of flow "
                    f"{r.flow_id} is outside 0..{E.MAX_LEVEL}"
                )
        live = {int(r.flow_id) for r in rules}
        new = [f for f in live if f not in self.slot_of]
        if len(new) > len(self._free):
            # retired flows with nothing out give their slots back
            held = np.asarray(self.state.held)
            for fid in [f for f in self.slot_of if f not in live]:
                if held[self.slot_of[fid]] == 0:
                    self._free.append(self.slot_of.pop(fid))
        if len(new) > len(self._free):
            raise ValueError(
                f"concurrent rule capacity exceeded: {len(new)} new flows, "
                f"{len(self._free)} free slots of {self.config.max_flows}"
            )
        for fid in new:
            self.slot_of[fid] = self._free.pop()
        self.rules = {int(r.flow_id): r for r in rules}
        n = len(rules)
        fids = np.fromiter((int(r.flow_id) for r in rules), np.int64, n)
        slots = np.fromiter((self.slot_of[int(f)] for f in fids), np.int32, n)
        ns_index = {name: k for k, name in enumerate(self._ns_names)}
        avg = np.full(n, -1, np.int32)
        for k, r in enumerate(rules):
            if r.mode != ThresholdMode.GLOBAL:
                if r.namespace not in ns_index:
                    ns_index[r.namespace] = len(self._ns_names)
                    self._ns_names.append(r.namespace)
                avg[k] = ns_index[r.namespace]
        self._count[:] = -1
        self._avg_ns[:] = -1
        self._count[slots] = np.fromiter(
            (int(r.concurrency_level) for r in rules), np.int64, n)
        self._avg_ns[slots] = avg
        timeout = np.zeros(self.config.max_flows, np.int32)
        timeout[slots] = np.fromiter(
            (int(r.resource_timeout_ms) for r in rules), np.int64, n)
        order = np.argsort(fids)
        self.lookup = (fids[order], slots[order])
        self.state = self.state._replace(
            level=jnp.asarray(self.levels(connected)),
            timeout_ms=jnp.asarray(timeout),
        )

    def levels(self, connected: Dict[str, int]) -> np.ndarray:
        """The level column: the rule's count, times the connected clients
        of its namespace where the rule is AVG_LOCAL; -1 where no rule."""
        per_ns = np.array(
            [max(1, int(connected.get(name, 1))) for name in self._ns_names]
            + [1], np.int64)  # the last entry serves index -1: GLOBAL
        level = self._count * per_ns[self._avg_ns]
        return np.where(self._count < 0, -1,
                        np.minimum(level, E.MAX_LEVEL)).astype(np.int32)

    def connected_changed(self, namespace: str,
                          connected: Dict[str, int]) -> None:
        """Rewrite the level column if an AVG_LOCAL rule scales by
        ``namespace``."""
        import jax.numpy as jnp

        if namespace in self._ns_names:
            self.state = self.state._replace(
                level=jnp.asarray(self.levels(connected)))

    # -- one dispatch ----------------------------------------------------------
    def step_fn(self, bucket: int):
        step = self._steps.get(bucket)
        if step is None:
            step = self._steps[bucket] = E.make_concurrent_step(
                self.config, bucket)
        return step

    def bucket_for(self, rows: int) -> int:
        return next(b for b in self.buckets if rows <= b)

    def step_plan(self, n_acq: int, n_rel: int):
        """The steps of a dispatch of ``n_acq`` acquires and ``n_rel``
        releases: ``(a_lo, a_hi, r_lo, r_hi, bucket)`` a step, each kind's
        rows counted in arrival order. One step unless a kind's rows pass
        the largest bucket. The releases' steps come first: the last of
        them also carries the first chunk of the acquires."""
        cap = self.buckets[-1]
        rel_chunks = [(lo, min(lo + cap, n_rel))
                      for lo in range(0, n_rel, cap)] or [(0, 0)]
        acq_chunks = [(lo, min(lo + cap, n_acq))
                      for lo in range(0, n_acq, cap)] or [(0, 0)]
        plan = []
        for k in range(len(rel_chunks) - 1 + len(acq_chunks)):
            r_lo, r_hi = rel_chunks[k] if k < len(rel_chunks) else (0, 0)
            j = k - (len(rel_chunks) - 1)
            a_lo, a_hi = acq_chunks[j] if j >= 0 else (0, 0)
            plan.append((a_lo, a_hi, r_lo, r_hi,
                         self.bucket_for(max(a_hi - a_lo, r_hi - r_lo, 1))))
        return plan

    def prep(self, lookup, ids: np.ndarray, counts: np.ndarray,
             is_release: np.ndarray):
        """A dispatch's rows as the packed arguments of its steps
        (:meth:`step_plan`), with what :meth:`unpack` needs to put the
        verdicts back in request order, and whether the native pass prepped
        them (``native.lib.concurrent_prep``, one call with the GIL
        released) or, where the library is not built, :meth:`prep_numpy`:
        the same bytes either way."""
        n_rel = int(np.count_nonzero(is_release))
        plan = self.step_plan(len(ids) - n_rel, n_rel)
        parts = _native.concurrent_prep(lookup, ids, counts, is_release,
                                        self.config.max_tokens, plan)
        if parts is not None:
            return parts, True
        return self.prep_numpy(lookup, ids, counts, is_release, plan), False

    def prep_numpy(self, lookup, ids, counts, is_release, plan):
        """:meth:`prep` in numpy: the fallback, and the tests' reference."""
        rel_rows = np.flatnonzero(is_release)
        acq_rows = np.flatnonzero(~is_release)
        fids, fslots = lookup
        flow_ids = ids[acq_rows]
        if fids.size:
            at = np.minimum(np.searchsorted(fids, flow_ids), fids.size - 1)
            slots = np.where(fids[at] == flow_ids, fslots[at],
                             np.int32(E.NO_SLOT))
        else:
            slots = np.full(len(acq_rows), E.NO_SLOT, np.int32)
        acq_counts = counts[acq_rows]
        tok_slot, tok_gen = E.split_token_ids(self.config, ids[rel_rows])
        parts = []
        for a_lo, a_hi, r_lo, r_hi, bucket in plan:
            order_a = np.argsort(slots[a_lo:a_hi], kind="stable")
            ids_r = ids[rel_rows[r_lo:r_hi]]
            order_r = np.argsort(ids_r, kind="stable")
            packed = E.pack_concurrent_rows(
                bucket, slots[a_lo:a_hi][order_a],
                acq_counts[a_lo:a_hi][order_a],
                tok_slot[r_lo:r_hi][order_r], tok_gen[r_lo:r_hi][order_r])
            parts.append((bucket, packed, acq_rows[a_lo:a_hi][order_a],
                          rel_rows[r_lo:r_hi][order_r]))
        return parts

    def unpack(self, n: int, parts, hosts):
        """``(status int8[n], remaining int32[n], token_ids int64[n],
        expired, table_full, live)`` from the steps' verdicts (``hosts``,
        one ``int32[VERDICT_LINES, bucket]`` a part)."""
        status = np.empty(n, np.int8)
        remaining = np.zeros(n, np.int32)
        token_ids = np.zeros(n, np.int64)
        expired = table_full = live = 0
        for (_b, _p, acq_at, rel_at), host in zip(parts, hosts):
            a, r = len(acq_at), len(rel_at)
            status[acq_at] = host[E.OUT_STATUS, :a]
            remaining[acq_at] = host[E.OUT_REMAINING, :a]
            token_ids[acq_at] = E.join_token_ids(
                self.config, host[E.OUT_ID_SLOT, :a], host[E.OUT_ID_GEN, :a])
            status[rel_at] = host[E.OUT_RELEASE, :r]
            misc = host[E.OUT_MISC]
            expired += int(misc[E.MISC_EXPIRED])
            table_full += int(misc[E.MISC_TABLE_FULL])
            live = int(misc[E.MISC_LIVE])
        return status, remaining, token_ids, expired, table_full, live

    # -- introspection ---------------------------------------------------------
    def snapshot(self) -> dict:
        """The plane read to the host (tests, drills, ``concurrent_stats``):
        ``held`` and ``level`` by flow id (retired flows too), the live
        tokens as ``{token id: (flow id, count, expire_ms)}``."""
        st, ring = self.state, self.config.max_tokens
        held, level = np.asarray(st.held), np.asarray(st.level)
        count = np.asarray(st.tok_count)[:ring]
        flow_of = {slot: fid for fid, slot in self.slot_of.items()}
        at = np.flatnonzero(count > 0)
        ids = E.join_token_ids(self.config, at, np.asarray(st.tok_gen)[at])
        return {
            "held": {fid: int(held[s]) for fid, s in self.slot_of.items()},
            "level": {fid: int(level[s]) for fid, s in self.slot_of.items()},
            "tokens": {
                int(i): (flow_of.get(int(f)), int(c), int(e))
                for i, f, c, e in zip(
                    ids, np.asarray(st.tok_flow)[at], count[at],
                    np.asarray(st.tok_expire)[at])
            },
            "cursor": np.asarray(self.state.cursor).tolist(),
        }
