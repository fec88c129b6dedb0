"""shard_map-based multi-chip decision step.

The mesh has one axis, ``"flows"``: ``state.flow`` / ``state.occupy`` and the
per-flow rule arrays are sharded along it; the namespace window, namespace
config arrays, request batch and clock are replicated. ``_decide_core`` runs
per shard with ``axis_name="flows"`` and stitches global verdicts with psums
(see its docstring).

Requests need no routing: every device sees the whole batch and answers only
for flows it owns — the right trade for this workload, where a batch row is
16 bytes but a flow's window history is O(buckets × events) and must not
move. (The scaling-book recipe: pick the mesh, annotate shardings, let the
collectives ride ICI.)
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sentinel_tpu.engine.config import EngineConfig, named
from sentinel_tpu.engine.decide import (
    RequestBatch,
    _decide_core_arms,
    pack_verdicts,
    step_name,
    unpack_requests,
)
from sentinel_tpu.engine.rules import RuleTable
from sentinel_tpu.engine.state import EngineState, state_of

def make_flow_mesh(devices=None, axis: str = "flows") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def _state_specs(axis: str) -> EngineState:
    """A leaf whose rows are flows is sharded along the flow axis; a leaf
    keyed by namespace or by nothing (every ring's ``starts``) is
    replicated."""
    return state_of(lambda c: P(axis) if c.key == "flow" else P())


def _rules_specs(axis: str, br: bool = True) -> RuleTable:
    # ``br=False`` mirrors a table built with no degrade rules, whose six
    # br_* columns are None (and so absent from the pytree structure)
    brp = P(axis) if br else None
    return RuleTable(
        valid=P(axis),
        count=P(axis),
        mode=P(axis),
        namespace_id=P(axis),
        ns_max_qps=P(),
        ns_connected=P(),
        behavior=P(axis),
        warning_token=P(axis),
        max_token=P(axis),
        slope=P(axis),
        cold_count=P(axis),
        max_queue_ms=P(axis),
        br_strategy=brp,
        br_threshold=brp,
        br_slow_rt_ms=brp,
        br_min_request=brp,
        br_stat_ms=brp,
        br_recovery_ms=brp,
    )


def _batch_specs() -> RequestBatch:
    return RequestBatch(flow_slot=P(), acquire=P(), prioritized=P(), valid=P())


def shard_state(state: EngineState, mesh: Mesh, axis: str = "flows") -> EngineState:
    """Place an EngineState on the mesh with flow-axis sharding."""
    specs = _state_specs(axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs
    )


def shard_rules(rules: RuleTable, mesh: Mesh, axis: str = "flows") -> RuleTable:
    specs = _rules_specs(axis, br=rules.br_strategy is not None)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), rules, specs
    )


def host_rows(arr, rows: np.ndarray) -> np.ndarray:
    """Gather ``arr[rows]`` (global row indices, axis 0) to host numpy,
    shard-aware.

    For an array sharded along axis 0 this walks the addressable shards and
    copies each shard's slab ONCE per shard that owns a requested row, then
    numpy-gathers locally — no device gather kernel, so the replication tick
    never pays a per-row-count XLA compile (the dirty set's size varies every
    delta). Replicated/unsharded arrays (and plain numpy) take one host copy.
    Requires every shard to be addressable (single-process mesh or a fully
    replicated axis) — the only topologies the host-side exporter runs in.
    """
    rows = np.asarray(rows, np.int64)
    if rows.size == 0:
        return np.empty((0,) + tuple(arr.shape[1:]), np.asarray(arr[:0]).dtype)
    if not isinstance(arr, jax.Array) or arr.is_fully_replicated:
        return np.asarray(arr)[rows]
    shards = arr.addressable_shards
    out = None
    seen = np.zeros(rows.shape[0], bool)
    for shard in shards:
        idx = shard.index[0]
        start = idx.start or 0
        stop = idx.stop if idx.stop is not None else arr.shape[0]
        mask = (rows >= start) & (rows < stop) & ~seen
        if not mask.any():
            continue
        data = np.asarray(shard.data)
        if out is None:
            out = np.empty((rows.shape[0],) + data.shape[1:], data.dtype)
        out[mask] = data[rows[mask] - start]
        seen |= mask
    if not seen.all():
        raise ValueError(
            "host_rows: rows not covered by addressable shards "
            f"(multi-process mesh?): {rows[~seen].tolist()}"
        )
    return out


def make_sharded_decide(
    config: EngineConfig,
    mesh: Mesh,
    axis: str = "flows",
    grouped: bool = False,
    uniform: bool = False,
    donate: bool = False,
    depth: Optional[int] = None,
):
    """Build the jitted multi-chip step.

    ``config.max_flows`` must divide evenly by the mesh size; each shard owns
    ``max_flows // n_devices`` consecutive slots (the host RuleIndex hands
    out global slots, which the kernel maps to shard-local via its
    ``axis_index``).

    ``donate=True`` is the serve step, exactly like the single-shard
    ``decide_donating``: ``step(state, rules, packed)``. It donates the
    state buffers (XLA updates the sharded window tensors in place instead
    of copying the full per-shard state every dispatch), takes the request
    batch and the clock as the ONE replicated host array of
    ``pack_requests`` (a host argument is placed on every device of the
    mesh) and returns the verdicts as the one replicated ``int32[3, ...]``
    buffer of ``pack_verdicts``. Without it the step is the library entry
    ``step(state, rules, batch, now)`` with a ``RequestBatch`` in and a
    ``VerdictBatch`` out.

    ``depth=F`` builds the fused variant: one ``lax.scan`` of the sharded
    step over ``[F, batch_size]`` stacked request frames (packed:
    ``alloc_packed_block``), inside a single
    ``shard_map`` entry. Each scan iteration psum-stitches that frame's
    verdicts over ICI before the next frame decides, so per-frame verdicts
    are bit-identical to F sequential sharded dispatches — but the host
    pays one dispatch, one shard_map entry, and (with ``donate``) zero
    state copies for the whole group.
    """
    n = mesh.devices.size
    if config.max_flows % n != 0:
        raise ValueError(
            f"max_flows={config.max_flows} must be divisible by mesh size {n}"
        )

    if depth is None:
        def decide_shard(state, rules, batch, now):
            state, verdicts, arms = _decide_core_arms(
                config, state, rules, batch, now, axis_name=axis,
                grouped=grouped, uniform=uniform,
            )
            return state, (verdicts, arms)
    else:
        if depth < 2:
            raise ValueError(f"fused depth must be >= 2, got {depth}")

        def decide_shard(state, rules, batches, now):
            def body(st, batch):
                st, verdicts, arms = _decide_core_arms(
                    config, st, rules, batch, now, axis_name=axis,
                    grouped=grouped, uniform=uniform,
                )
                return st, (verdicts, arms)

            return jax.lax.scan(body, state, batches, length=depth)

    if donate:
        def step(state, rules, packed):
            batch, now = unpack_requests(packed)
            state, (verdicts, arms) = decide_shard(state, rules, batch, now)
            return state, pack_verdicts(verdicts, arms)

        request_specs = (P(),)
    else:
        def step(state, rules, batch, now):
            state, (verdicts, _arms) = decide_shard(state, rules, batch, now)
            return state, verdicts

        request_specs = (_batch_specs(), P())

    # two spec shapes, matching the two RuleTable pytree structures: with
    # br_* columns (degrade rules loaded) and without (None columns, so the
    # compile skips the breaker arm). Built lazily on first use of each.
    def _build(br: bool):
        # check_vma off: the verdict outputs are replicated *by value*
        # (every shard psums the same global answer), which the checker
        # cannot infer through the cond-gated namespace guard
        mapped = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(
                _state_specs(axis),
                _rules_specs(axis, br=br),
                *request_specs,  # replicated
            ),
            # verdicts replicated, as a VerdictBatch or packed into one
            out_specs=(_state_specs(axis), P()),
            check_vma=False,
        )
        return jax.jit(
            named(mapped, name), donate_argnums=(0,) if donate else ()
        )

    name = step_name(
        "decide_sharded" if depth is None else "decide_sharded_fused",
        config, uniform, depth,
    )
    impls = {}

    def jitted(rules):
        """The jitted program for this rule table's pytree structure."""
        br = rules.br_strategy is not None
        if br not in impls:
            impls[br] = _build(br)
        return impls[br]

    def sharded_step(state, rules, *request):
        return jitted(rules)(state, rules, *request)

    sharded_step.jitted = jitted
    return named(sharded_step, name)
