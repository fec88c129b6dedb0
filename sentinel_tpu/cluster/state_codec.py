"""What leaves a ``DefaultTokenService`` and comes back: the snapshot a
standby boots from, the replication delta that keeps it warm, the MOVE
document a namespace changes servers in, and the engine clock's re-base.

Each is a loop over the table of state columns (``engine.state.Column``:
``STATE_COLUMNS`` beside ``EngineState``, ``PARAM_COLUMNS`` beside
``ParamState``), so a leaf added to the state with its entry rides all four
and the mesh with no edit here. What is not a column keeps its code: rules,
slot maps, ``connected``, ``namespace_set``, the hierarchy ledger's
piggyback. The concurrency plane (``engine.concurrent``) is in no document,
as upstream carries none of it; its one clock is re-based by name.

Three rules of the sketch stay code, marked where they apply: (1) a MOVE
sums over *decoded* cells, (2) a delta ships the slim twin and, of the fat
counters, only the rows a MOVE folded in, (3) the twin's authority flags
follow the buckets a delta touched.

The functions take the service; ``DefaultTokenService``'s six public
methods of the same names are what ``ha``, ``cluster.rebalance``, the doors
and ``transport.handlers`` call.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import clock as _clock
from sentinel_tpu.engine.param import (
    PARAM_COLUMNS,
    ParamState,
    make_param_state,
)
from sentinel_tpu.engine.state import (
    DELTA,
    MOVE,
    SNAPSHOT,
    STATE_COLUMNS,
    Column,
    flow_spec,
    leaf,
    make_state,
    state_of,
)
from sentinel_tpu.stats import window as W

COLUMNS = STATE_COLUMNS + PARAM_COLUMNS
_SKETCH = frozenset(PARAM_COLUMNS)
# the slim twin and its authority flags, which rule 3 reaches by name
_SLIM, _SLIM_AUTH = (
    next(c for c in PARAM_COLUMNS if c.field == field)
    for field in ("slim", "slim_auth")
)
_NEVER = int(W.NEVER)

# dirty set -> what keys its slots, and the delta's key for their ids
_DIRTY = {
    "flow": ("flow", "flow_ids"),
    "outcome": ("flow", "outcome_fids"),
    "breaker": ("flow", "breaker_fids"),
    "param": ("param", "param_fids"),
    "param_fat": ("param", "param_fat_fids"),
}
# a delta's key for the names of the namespace rows its flow slots feed
_NS_NAMES = "ns_names"
# key kind -> the snapshot's key for its id -> row map, and what a refusal
# calls such an id
_SNAPSHOT_MAP = {"flow": "slot_of", "namespace": "ns_of",
                 "param": "param_slot_of"}
_NOUN = {"flow": "flow", "namespace": "namespace", "param": "param rule"}


def fresh_dirty() -> Dict[str, set]:
    return {name: set() for name in _DIRTY}


# -- the leaves of a service -------------------------------------------------
def _sketch_leaves(sketch: ParamState) -> Dict[Column, object]:
    return {c: getattr(sketch, c.field) for c in PARAM_COLUMNS}


def _leaves(state, sketch: ParamState) -> Dict[Column, object]:
    return {**{c: leaf(state, c) for c in STATE_COLUMNS},
            **_sketch_leaves(sketch)}


def read(svc) -> Dict[Column, object]:
    """Every leaf of the service's state by its column (the sketch's fat
    counters in their ``[P, B, depth, cells]`` shape)."""
    return _leaves(svc._state, svc._param_state)


def _sketch_of(leaves: Dict[Column, object]) -> ParamState:
    return ParamState(**{c.field: leaves[c] for c in PARAM_COLUMNS})


def install(svc, leaves: Dict[Column, object], sketch: bool = True) -> None:
    svc._state = svc._place_state(state_of(leaves.__getitem__))
    if sketch:
        svc._param_state = _sketch_of(leaves)


def _row_map(svc, kind: str) -> Dict[object, int]:
    """Durable id -> row of this service, for one key kind."""
    if kind == "param":
        return {fid: entry[0] for fid, entry in svc._param_rules.items()}
    return svc._index.slot_of if kind == "flow" else svc._index.ns_of


# family -> the ``starts`` its window's ``counts`` are bucketed by
_RINGS = {c.family: c for c in COLUMNS if c.field == "starts"}


def _ring(column: Column) -> Column:
    return _RINGS[column.family]


def _delta_set(column: Column, slim_on: bool):
    """The dirty set whose rows ship ``column`` in a delta: its entry's,
    but for the sketch (rule 2). The SF-sketch split: while the slim twin
    is on, the every-tick document ships the twin's rows and not the fat
    update sketch (that is the ``sentinel_repl_bytes_total`` cut; the fat
    rows still ship in full snapshots for a bit-exact bootstrap). Rows a
    MOVE import just folded are the exception: their mass exists only in
    the fat sketch, so they ride along once, keyed separately."""
    if column in _SKETCH and column.dirty is not None:
        if column.kind == "window":
            return "param_fat" if slim_on else column.dirty
        return column.dirty if slim_on else None
    return column.dirty


# -- the engine clock's re-base ----------------------------------------------
def rebase(svc, delta_ms: int) -> None:
    """Move the engine epoch forward by ``delta_ms``: every engine-ms leaf
    (the rings' ``starts``, the shaper and breaker clocks) shifts back by
    as much and ``NEVER`` stays ``NEVER``. Callers hold the service lock."""
    state = svc._state
    svc._state = state_of(
        lambda c: W.shift_clock(leaf(state, c), delta_ms)
        if c.kind == "clock" else leaf(state, c)
    )
    # the served sketch keeps its fat counters flat: shift its clocks in
    # place of a reshape either way
    served = svc._param_serve
    svc._param_serve = served._replace(**{
        c.field: W.shift_clock(getattr(served, c.field), delta_ms)
        for c in PARAM_COLUMNS if c.kind == "clock"
    })
    if svc._conc is not None:
        # a token's expiry is engine-ms too (a free slot's is unread)
        cs = svc._conc.state
        svc._conc.state = cs._replace(
            tok_expire=cs.tok_expire - jnp.int32(delta_ms))


# -- state snapshot / restore (ha.snapshot backing) ---------------------------
def export_state(svc) -> Dict[str, object]:
    with svc._rules_mutex, svc._lock:
        now = svc._engine_now()  # pins the epoch, runs a due rebase
        doc: Dict[str, object] = {
            "engine_now": int(now),
            "epoch_ms": int(svc._epoch_ms),
            "wall_ms": int(_clock.now_ms()),
            "ns_max_qps": float(svc._ns_max_qps),
            "connected": dict(svc._connected),
            "namespace_set": sorted(svc.namespace_set),
            "rules": [
                r for m in svc._rules_by_ns.values() for r in m.values()
            ],
            "param_rules": list(svc._param_rules_src.values()),
            "degrade_rules": list(svc._degrade_rules_src.values()),
            **{
                key: dict(_row_map(svc, kind))
                for kind, key in _SNAPSHOT_MAP.items()
            },
        }
        # every leaf RAW under family -> field: clocks share the exported
        # epoch, so a restore on the same rules is bit-exact (for SALSA the
        # in-band merge encoding rides inside the fat int16 cells)
        for c, value in read(svc).items():
            if SNAPSHOT in c.docs:
                doc.setdefault(c.family, {})[c.field] = np.asarray(value)
        # hierarchy ledger piggyback (pure JSON; absent when no coordinator
        # is co-located). A standby imports it into ITS attached
        # coordinator so promotion inherits the share map.
        if svc.hierarchy is not None:
            doc["hier"] = svc.hierarchy.export_doc()
        return doc


def _index_pair(old: Dict[object, int], new: Dict[object, int]):
    """``(new rows, old rows)`` of the ids both maps hold: what remaps
    every leaf of one key kind, ``out[new] = got[old]``."""
    ids = [i for i in new if i in old]
    return (
        np.fromiter((new[i] for i in ids), np.int64, len(ids)),
        np.fromiter((old[i] for i in ids), np.int64, len(ids)),
    )


def import_state(svc, state: Dict[str, object]) -> None:
    with svc._rules_mutex:
        with svc._lock:
            # cold is what a fresh service holds. A leaf the snapshot lacks
            # stays so: a snapshot from before shaping, outcomes, breakers
            # or the slim twin restores those cold, the conservative side
            # (no shaper history, an empty completion window, CLOSED, which
            # under-protects until the stat window refills and never wrongly
            # rejects)
            cold = _leaves(make_state(svc.config),
                           make_param_state(svc.param_config))
            got: Dict[Column, np.ndarray] = {}
            for c in COLUMNS:
                arr = state.get(c.family, {}).get(c.field)
                if arr is None or SNAPSHOT not in c.docs:
                    continue
                arr = np.asarray(arr, cold[c].dtype)
                if arr.shape != tuple(cold[c].shape):
                    raise ValueError(
                        f"snapshot geometry mismatch: {c.name} {arr.shape} "
                        f"!= {tuple(cold[c].shape)}"
                    )
                got[c] = arr
            # degrade rules must be in place BEFORE load_rules so the
            # rebuilt RuleTable carries the br_* columns the restored
            # breaker state refers to
            svc._degrade_rules_src = {
                d.flow_id: d for d in state.get("degrade_rules", ())
            }
        svc.load_rules(
            list(state["rules"]),
            ns_max_qps=float(state["ns_max_qps"]),
            connected=dict(state["connected"]),
        )
        svc.load_param_rules(list(state["param_rules"]))
        with svc._lock:
            svc.namespace_set |= set(state["namespace_set"])
            # rows remap snapshot row -> this service's row by what keys
            # them (flow id, namespace name, param rule); an id this service
            # has no rule for is dropped, a rule the snapshot lacks is cold
            pairs = {
                kind: _index_pair(state[key], _row_map(svc, kind))
                for kind, key in _SNAPSHOT_MAP.items()
            }
            leaves = dict(cold)
            for c, arr in got.items():
                if c.key is not None:
                    new, old = pairs[c.key]
                    remapped = np.array(cold[c])
                    remapped[new] = arr[old]
                    arr = remapped
                leaves[c] = jnp.asarray(arr)
            install(svc, leaves)
            # re-baseline the transition mirror from CLOSED so the restore
            # surfaces still-open breakers as closed→open edges
            svc._breaker_prev = None
            # resume the snapshot's engine timeline: wall − epoch keeps
            # advancing, so windows older than interval_ms expire on the
            # next read instead of resurrecting stale quota
            svc._epoch_ms = int(state["epoch_ms"])
    # hierarchy ledger piggyback: a standby with an attached (idle)
    # coordinator inherits the primary's share map, so promotion keeps
    # every pod's share continuous
    hier_doc = state.get("hier")
    if hier_doc is not None and svc.hierarchy is not None:
        svc.hierarchy.import_doc(hier_doc)


# -- warm-standby delta replication (ha.replication backing) ------------------
def export_delta(svc) -> Dict[str, object]:
    # row gathers go through the shard-aware host collector: on a mesh it
    # walks addressable shards and numpy-gathers each one's slab (the
    # delta's row keys stay GLOBAL slots, so the wire document is identical
    # whatever mesh produced it); single-shard it is one host copy + numpy
    # index. Either way no device gather kernel — the dirty set's size
    # varies every tick, and a device gather would pay a fresh XLA compile
    # per distinct row count.
    from sentinel_tpu.parallel.sharding import host_rows

    with svc._rules_mutex, svc._lock:
        if svc._dirty is None:
            raise RuntimeError("replication tracking not enabled")
        dirty = {name: sorted(svc._dirty.get(name, ())) for name in _DIRTY}
        svc._dirty = fresh_dirty()
        now = svc._engine_now()  # pins the epoch, runs a due rebase
        delta: Dict[str, object] = {
            "gen": int(svc._state_gen),
            "engine_now": int(now),
            "epoch_ms": int(svc._epoch_ms),
            "wall_ms": int(_clock.now_ms()),
        }
        leaves = read(svc)
        shipped = [c for c in COLUMNS if DELTA in c.docs]
        # the rings' starts always: they advance with engine time, and a
        # starts-only document is the sender's liveness heartbeat
        for c in shipped:
            if c.key is None:
                delta[c.delta_key] = np.asarray(leaves[c])
        slim_on = svc.param_config.slim_enabled
        inverse: Dict[str, Dict[int, object]] = {}  # built once a call
        for name, slots in dirty.items():
            columns = [c for c in shipped if _delta_set(c, slim_on) == name]
            if not slots or not columns:
                continue
            kind, ids_key = _DIRTY[name]
            if kind not in inverse:
                inverse[kind] = {
                    row: i for i, row in _row_map(svc, kind).items()
                }
            delta[ids_key] = [int(inverse[kind][s]) for s in slots]
            at = {kind: np.asarray(slots, np.int32)}
            if any(c.key == "namespace" for c in columns):
                # the namespace guard rows these slots feed
                ns_names, slot_ns = svc._ns_snapshot
                fed = sorted(
                    {int(slot_ns[s]) for s in slots if slot_ns[s] >= 0}
                )
                if fed:
                    delta[_NS_NAMES] = [ns_names[r] for r in fed]
                    at["namespace"] = np.asarray(fed, np.int32)
            for c in columns:
                if c.key in at:
                    delta[c.delta_key] = host_rows(leaves[c], at[c.key])
        if svc.hierarchy is not None:
            # hier ledger rides every tick as plain JSON (non-array keys
            # pass through encode_delta_blob untouched); it's tiny — one
            # entry per (global flow × pod)
            delta["hier"] = svc.hierarchy.export_doc()
        return delta


def _local_rows(svc, kind: str, ids) -> jnp.ndarray:
    """This service's rows for a delta's durable ids; an id it has no rule
    for refuses the delta."""
    rows_of = _row_map(svc, kind)
    rows = []
    for i in ids:
        row = rows_of.get(i if kind == "namespace" else int(i))
        if row is None:
            raise ValueError(f"delta names unknown {_NOUN[kind]} {i!r}")
        rows.append(row)
    return jnp.asarray(np.asarray(rows, np.int32))


def _rotated(counts, starts, new_starts):
    """Mirror the primary's ring rotation on rows the delta does NOT carry:
    when the primary advanced ``starts[b]`` it zeroed column ``b`` for
    every resource (window.py rotation), so any local row whose column
    still holds counts from the previous occupancy of that ring slot must
    be zeroed too — otherwise applying the new starts would resurrect
    those stale counts as current-window traffic. Dirty rows are scattered
    with authoritative values afterwards, so pre-zeroing them is
    harmless."""
    changed = np.asarray(starts) != np.asarray(new_starts)
    if not changed.any():
        return counts
    keep = jnp.asarray((~changed).astype(np.int32))
    shape = (1, keep.shape[0]) + (1,) * (counts.ndim - 2)
    return counts * keep.reshape(shape).astype(counts.dtype)


def apply_replication_delta(svc, delta: Dict[str, object]) -> None:
    with svc._rules_mutex, svc._lock:
        if (
            svc._epoch_ms is None
            or int(delta["epoch_ms"]) != svc._epoch_ms
        ):
            raise ValueError("replication epoch mismatch")
        # every id resolves before anything lands: one this service has no
        # rule for means its base state predates a reload on the primary
        rows_of = {
            name: _local_rows(svc, kind, delta[ids_key])
            for name, (kind, ids_key) in _DIRTY.items()
            if delta.get(ids_key)
        }
        ns_rows = (
            _local_rows(svc, "namespace", delta[_NS_NAMES])
            if delta.get(_NS_NAMES) else None
        )
        was = read(svc)
        leaves = dict(was)
        shipped = [c for c in COLUMNS if DELTA in c.docs]
        # a sender from before a family ships no starts for it: that ring
        # stays as it is (it is empty on such a standby anyway)
        for c in shipped:
            if c.kind != "window":
                continue
            new_starts = delta.get(_ring(c).delta_key)
            if new_starts is not None:
                leaves[c] = _rotated(was[c], was[_ring(c)], new_starts)
        # (rule 3) the slim twin rotates with the fat ring: a rotated
        # column's slim cells describe a dead window, so they are zeroed
        # and the bucket loses its authority flag
        ring = _ring(_SLIM)
        changed = np.asarray(was[ring]) != np.asarray(delta[ring.delta_key])
        if changed.any():
            leaves[_SLIM] = _rotated(was[_SLIM], was[ring],
                                     delta[ring.delta_key])
            leaves[_SLIM_AUTH] = was[_SLIM_AUTH] & jnp.asarray(~changed)
        # rows land on the local slot assignment. Clocks are raw engine-ms:
        # the epoch check above guarantees both sides share the timeline.
        slim_on = _SLIM.delta_key in delta
        for c in shipped:
            value = delta.get(c.delta_key)
            if value is None:
                continue
            if c.key is None:
                leaves[c] = jnp.asarray(value)
                continue
            rows = (ns_rows if c.key == "namespace"
                    else rows_of.get(_delta_set(c, slim_on)))
            if rows is not None:
                leaves[c] = leaves[c].at[rows].set(jnp.asarray(value))
        if slim_on and "param" in rows_of:
            # (rule 3) landing any slim rows makes every live bucket
            # slim-authoritative — the decide path then serves fat + slim,
            # which double-counts at most one snapshot-to-delta gap
            # (over-estimate, the safe direction) and converges to
            # fat-only as the flagged buckets rotate off the ring
            leaves[_SLIM_AUTH] = jnp.ones_like(leaves[_SLIM_AUTH])
        install(svc, leaves)
    # hier ledger piggyback: landed OUTSIDE the counter locks (the
    # coordinator has its own) and only when a coordinator is attached —
    # an old standby without one ignores the key, like any unknown key
    hier_doc = delta.get("hier")
    if hier_doc is not None and svc.hierarchy is not None:
        svc.hierarchy.import_doc(hier_doc)


# -- live namespace move (cluster.rebalance backing) --------------------------
def _moved_rows(svc, namespace: str, flow_ids, param_fids):
    """Key kind -> this service's rows for a moving namespace's ids."""
    row = svc._index.ns_of.get(namespace)
    return {
        "flow": np.asarray(
            [svc._index.slot_of[f] for f in flow_ids], np.int32),
        "namespace": np.asarray([] if row is None else [row], np.int32),
        "param": np.asarray(
            [svc._param_rules[f][0] for f in param_fids], np.int32),
    }


def export_namespace_state(svc, namespace: str) -> Dict[str, object]:
    from sentinel_tpu.parallel.sharding import host_rows
    from sentinel_tpu.sketch import decoded_counts_np

    with svc._rules_mutex, svc._lock:
        rules = list(svc._rules_by_ns.get(namespace, {}).values())
        param_rules = [
            r for r in svc._param_rules_src.values()
            if r.namespace == namespace
        ]
        degrade_rules = [
            d for d in svc._degrade_rules_src.values()
            if d.namespace == namespace
        ]
        now = svc._engine_now()
        spec = flow_spec(svc.config)
        # breaker-only flows (a DegradeRule with no flow rule) still own
        # a slot and breaker state; walk the union so they move too
        exported = {r.flow_id for r in rules}
        movers = rules + [
            d for d in degrade_rules if d.flow_id not in exported
        ]
        flow_ids = [
            int(r.flow_id) for r in movers
            if r.flow_id in svc._index.slot_of
        ]
        param_fids = [
            int(r.flow_id) for r in param_rules
            if r.flow_id in svc._param_rules
        ]
        rows = _moved_rows(svc, namespace, flow_ids, param_fids)
        doc: Dict[str, object] = {
            "namespace": namespace,
            "wall_ms": int(_clock.now_ms()),
            "interval_ms": int(spec.interval_ms),
            "rules": rules,
            "param_rules": param_rules,
            "degrade_rules": degrade_rules,
            "flow_ids": flow_ids,
            "param_fids": param_fids,
        }
        leaves = read(svc)
        for c in COLUMNS:
            if MOVE not in c.docs:
                continue
            at = rows[c.key]
            if c.kind == "value":
                out = np.asarray(leaves[c])[at]
            elif c.kind == "clock":
                # clocks ship RELATIVE to now — the destination's engine
                # epoch is its own; NEVER stays NEVER. An OPEN breaker
                # stays OPEN over there, its recovery clock re-anchored.
                out = np.asarray(leaves[c])[at].astype(np.int64)
                out = np.where(out == _NEVER, _NEVER, out - now)
            elif c in _SKETCH:
                # (rule 1) per-row live-window cell sums [depth, cells],
                # summed over DECODED cells, so the wire document is plain
                # int sums whatever the in-memory encoding (int32 cms or
                # int16 SALSA pairs). The sketch is linear over decoded
                # values, so summing live buckets preserves every estimate
                # the destination will read.
                age = now - np.asarray(leaves[_ring(c)])
                live = (age >= 0) & (age < svc.param_config.interval_ms)
                out = decoded_counts_np(
                    svc.param_config, host_rows(leaves[c], at)
                )[:, live].sum(axis=1).astype(np.int64)
            else:
                # live-window sums, not the raw ring: sums are ring- and
                # epoch-free, so the destination folds them into its OWN
                # current bucket regardless of clock skew or ring phase
                out = np.asarray(W.window_sum_all(
                    spec, W.WindowState(leaves[_ring(c)], leaves[c]),
                    jnp.int32(now),
                ))[at]
            if c.key == "namespace":
                out = out[0] if len(at) else np.zeros(out.shape[1:],
                                                      out.dtype)
            doc[c.move_key] = out
        return doc


def import_namespace_state(svc, doc: Dict[str, object]) -> None:
    namespace = str(doc["namespace"])
    degrade_rules = list(doc.get("degrade_rules", ()))
    param_rules = list(doc["param_rules"])
    with svc._rules_mutex:
        svc.load_namespace_rules(namespace, list(doc["rules"]))
        if degrade_rules:
            # the namespace's breakers move with it: rules first (slots +
            # br_* columns), then the state columns re-anchor below
            svc.load_namespace_degrade_rules(namespace, degrade_rules)
        if param_rules:
            svc.load_namespace_param_rules(namespace, param_rules)
        with svc._lock:
            now = svc._engine_now()
            spec = flow_spec(svc.config)
            rows = _moved_rows(
                svc, namespace,
                [int(f) for f in doc.get("flow_ids", [])],
                [int(f) for f in doc.get("param_fids", [])],
            )
            leaves = read(svc)
            landed = set()
            folded_rows = ()
            for c in COLUMNS:
                # a blob from before a family carries no key for it: the
                # moved flows start cold there, the conservative default
                got = doc.get(c.move_key) if MOVE in c.docs else None
                if got is None:
                    continue
                at, got = rows[c.key], np.asarray(got)
                if c.kind == "window" and c in _SKETCH:
                    if len(at):
                        from sentinel_tpu.sketch import fold_param_sums

                        # (rule 1) the decoded sums fold into the fat
                        # sketch's current bucket
                        leaves.update(_sketch_leaves(fold_param_sums(
                            svc.param_config, _sketch_of(leaves), now, at,
                            got,
                        )))
                        folded_rows = at
                elif c.kind == "window":
                    ring = _ring(c)
                    ws = svc._fold_into_current(
                        W.WindowState(leaves[ring], leaves[c]), spec, now,
                        at, got[None] if c.key == "namespace" else got,
                    )
                    leaves[ring], leaves[c] = ws.starts, ws.counts
                elif len(at):
                    if c.kind == "clock":
                        # re-anchor to THIS engine's epoch: the blob ships
                        # clocks relative to the source's export now
                        got = np.where(
                            got == _NEVER, _NEVER,
                            np.clip(now + got.astype(np.int64), _NEVER,
                                    2**30),
                        )
                    host = np.array(leaves[c])
                    host[at] = got
                    leaves[c] = jnp.asarray(host)
                    landed.add(c.family)
            if "breaker" in landed:
                # drop the stale transition mirror: the next scan
                # re-baselines from CLOSED, so moved-in OPEN breakers
                # surface as closed→open edges on the destination
                svc._breaker_prev = None
            if len(folded_rows) and svc._dirty is not None:
                # (rule 2) the fold lands in the FAT sketch only — the slim
                # twin never saw the source's touches. Mark the rows for a
                # one-shot fat shipment so a delta-fed standby doesn't miss
                # the moved-in window (moves are rare; one fat row per
                # moved rule, not per tick).
                for name in ("param", "param_fat"):
                    svc._dirty[name].update(int(r) for r in folded_rows)
            install(svc, leaves, sketch=bool(len(folded_rows)))
