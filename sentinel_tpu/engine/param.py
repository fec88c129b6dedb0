"""Hot-parameter counting as a windowed count-min sketch.

The reference bounds per-value cardinality with LRU maps — 4,000 values per
bucket / 200k per resource (``ParameterMetric.java:37-39``,
``ClusterParamMetric.java:37``) — which *undercounts* evicted keys. The TPU
build replaces LRU truncation with a count-min sketch per (rule, time
bucket): fixed memory, vectorized, and it *over*-estimates (CMS guarantee) —
the safe direction for rate limiting. The documented drift (SURVEY.md §7):
a value sharing all ``depth`` cells with heavy hitters may be throttled
early; width/depth trade that probability.

Shapes: ``counts[P, B, depth, width]`` int32 — P param-rule slots, B time
buckets with the same ring/mask-on-read discipline as ``stats.window``.
Hash *indices* are computed host-side from the application's stable 64-bit
value hash (values never cross the wire — only hashes, see
``cluster.protocol``), so the device kernel is pure gather/scatter/min.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Mixing constants for the host-side index derivation (splitmix64 finalizer
# per depth lane — public-domain construction).
_MIX = np.uint64(0x9E3779B97F4A7C15)
_FIN1 = np.uint64(0xBF58476D1CE4E5B9)
_FIN2 = np.uint64(0x94D049BB133111EB)


def hash_indices(
    value_hashes: np.ndarray, depth: int, width: int, salt: int = 0
) -> np.ndarray:
    """``[N] int64 -> [N, depth] int32`` CMS cell indices (host, vectorized).

    One broadcast over a ``[depth]`` lane-constant vector — this runs on the
    host for every param batch, so no per-depth Python loop. ``salt`` offsets
    the lane constants so an auxiliary sketch (the SF slim twin) draws its
    lanes from a disjoint part of the splitmix sequence; ``salt=0`` is
    byte-identical to the original per-depth loop.
    """
    h = value_hashes.astype(np.uint64)
    with np.errstate(over="ignore"):
        lane = np.arange(salt + 1, salt + depth + 1, dtype=np.uint64) * _MIX
        x = h[:, None] + lane[None, :]
        x = (x ^ (x >> np.uint64(30))) * _FIN1
        x = (x ^ (x >> np.uint64(27))) * _FIN2
        x = x ^ (x >> np.uint64(31))
        return (x % np.uint64(width)).astype(np.int32)


class ParamConfig(NamedTuple):
    max_param_rules: int = 256  # P
    depth: int = 2
    width: int = 2048
    bucket_ms: int = 500
    n_buckets: int = 2  # 1s sliding window like the local second-level
    # "jax" = pure-XLA path below; "pallas" = ops/cms_pallas.py kernel
    # (compiled by Mosaic, or it raises); "auto" = measured selection: off
    # the TPU it resolves straight to "jax", on the TPU it micro-probes
    # both kernels once per process, so production never runs a kernel
    # that was never timed on its own backend. SENTINEL_PARAM_IMPL=
    # jax|pallas overrides the probe for deployments that pin a choice.
    impl: str = "auto"
    # "cms" = plain int32 count-min (the seed); "salsa" = self-adjusting
    # int16 counters (sketch/salsa.py, arXiv:2102.12531): 2× the cells at
    # the same HBM bytes, neighboring cells merging into double-width
    # logical counters on saturation.
    sketch: str = "cms"
    # SF-sketch slim twin geometry (sketch/slim.py, arXiv:1701.04148):
    # updates go to the fat sketch above, a [P, B, slim_depth, slim_width]
    # int32 twin is maintained incrementally and is what replication deltas
    # ship. slim_width=0 disables the twin (deltas ship fat rows).
    slim_depth: int = 2
    slim_width: int = 256

    @property
    def interval_ms(self) -> int:
        return self.bucket_ms * self.n_buckets

    @property
    def cell_width(self) -> int:
        """Host hash width: SALSA packs 2× int16 cells into the int32
        footprint, so its index space is ``2*width``."""
        return self.width * (2 if self.sketch == "salsa" else 1)

    @property
    def slim_enabled(self) -> bool:
        return self.slim_depth > 0 and self.slim_width > 0


class ParamState(NamedTuple):
    starts: jax.Array  # [B] int32 engine-ms (shared ring, as stats.window)
    counts: jax.Array  # fat: [P, B, depth, width] int32 (cms)
    #                        [P, B, depth, 2*width] int16 (salsa)
    slim: jax.Array  # [P, B, slim_depth, slim_width] int32 SF slim twin
    slim_auth: jax.Array  # [B] bool — buckets whose slim rows arrived via a
    #     replication delta and must contribute to estimates (standby only;
    #     cleared as buckets rotate, so a promoted standby converges to
    #     fat-only serving within one window)
    merges: jax.Array  # [P] int32 cumulative SALSA pair merges (metrics)


NEVER = jnp.int32(-(2**30))


def make_param_state(config: ParamConfig) -> ParamState:
    P, B = config.max_param_rules, config.n_buckets
    fat_dtype = jnp.int16 if config.sketch == "salsa" else jnp.int32
    return ParamState(
        starts=jnp.full((B,), NEVER, jnp.int32),
        counts=jnp.zeros((P, B, config.depth, config.cell_width), fat_dtype),
        slim=jnp.zeros((P, B, config.slim_depth, config.slim_width),
                       jnp.int32),
        slim_auth=jnp.zeros((B,), bool),
        merges=jnp.zeros((P,), jnp.int32),
    )


def param_decide(
    config: ParamConfig,
    state: ParamState,
    rule_slot: jax.Array,
    idx: jax.Array,
    acquire: jax.Array,
    threshold: jax.Array,
    valid: jax.Array,
    now: jax.Array,
    idx_slim: jax.Array = None,
) -> Tuple[ParamState, jax.Array, jax.Array]:
    """Dispatch on ``config.sketch`` × ``config.impl``.

    The fat-sketch cores share one contract (see :func:`_param_decide_jax`);
    the SF slim twin is composed *around* whichever core runs, in three
    steps that keep every kernel slim-agnostic: (1) roll the slim ring and
    compute the per-request slim estimate over delta-authoritative buckets,
    (2) run the core with the threshold reduced by that estimate (identical
    admissions to adding it to the fat estimate), (3) scatter-max the
    post-update fat current-bucket estimate into the slim twin. Callers
    that pass ``idx_slim=None`` (probes, micro-benchmarks) skip the twin
    entirely — on a primary the twin is then simply not maintained.
    """
    core = _param_cores(config.sketch)[
        resolve_param_impl(config.impl, config.sketch)
    ]
    if idx_slim is None or not config.slim_enabled:
        return core(config, state, rule_slot, idx, acquire, threshold, valid,
                    now)
    from sentinel_tpu.sketch.slim import slim_poststep, slim_prestep

    slim, slim_auth, est_slim = slim_prestep(
        config, state, rule_slot, idx_slim, now
    )
    state = state._replace(slim=slim, slim_auth=slim_auth)
    thr = jnp.asarray(threshold, jnp.float32) - est_slim.astype(jnp.float32)
    state2, admit, est_fat = core(
        config, state, rule_slot, idx, acquire, thr, valid, now
    )
    slim2 = slim_poststep(config, state2, rule_slot, idx, idx_slim, valid, now)
    return state2._replace(slim=slim2), admit, est_fat + est_slim


# Per-process cache: (backend platform, sketch) → (choice, reason).
_AUTO_IMPL: dict = {}


def explain_param_impl(impl: str, sketch: str = "cms") -> tuple:
    """Resolve ``impl`` to ``(kernel, reason)`` with ``kernel`` in
    ("jax" | "pallas").

    An explicit "jax"/"pallas" (config or ``SENTINEL_PARAM_IMPL``) is taken
    as given — a forced "pallas" whose kernel Mosaic refuses raises the
    compiler's error, it is never served from XLA instead. "auto" picks per
    platform: off-TPU the XLA path outright (Mosaic compiles for the TPU
    only); on TPU both kernels of ``sketch`` are micro-probed once per
    process and the faster one is cached. A kernel that fails to build
    loses the probe out loud: the compiler's message is logged and
    returned in the reason.
    """
    if impl in ("jax", "pallas"):
        return impl, f"impl={impl!r} set explicitly"
    if impl != "auto":
        raise ValueError(
            f"unknown param impl {impl!r}; use 'auto'|'jax'|'pallas'"
        )
    env = os.environ.get("SENTINEL_PARAM_IMPL", "").strip().lower()
    if env in ("jax", "pallas"):
        return env, f"SENTINEL_PARAM_IMPL={env}"
    platform = jax.default_backend()
    resolved = _AUTO_IMPL.get((platform, sketch))
    if resolved is None:
        if platform != "tpu":
            resolved = (
                "jax", f"platform {platform!r}: Mosaic compiles for TPU only"
            )
        else:
            resolved = _probe_param_impl(sketch)
        _AUTO_IMPL[(platform, sketch)] = resolved
    return resolved


def resolve_param_impl(impl: str, sketch: str = "cms") -> str:
    """The kernel half of :func:`explain_param_impl`."""
    return explain_param_impl(impl, sketch)[0]


def _param_cores(sketch: str) -> dict:
    """``{"jax": core, "pallas": core}`` for one sketch encoding."""
    if sketch == "salsa":
        from sentinel_tpu.sketch.salsa import (
            salsa_decide_jax,
            salsa_decide_pallas,
        )

        return {"jax": salsa_decide_jax, "pallas": salsa_decide_pallas}
    if sketch == "cms":
        return {"jax": _param_decide_jax, "pallas": _param_decide_pallas}
    raise ValueError(
        f"unknown param sketch {sketch!r}; use 'cms'|'salsa'"
    )


# rows of the probe batch: request_params_token pads a request's values to a
# power of two ≥ 8, and 64 values in one request is already unusual
_PROBE_ROWS = 64


def _probe_param_impl(sketch: str) -> tuple:
    """Time one warm step of each kernel of ``sketch`` on the live backend
    at the default geometry and the widest batch the serving path commonly
    pads to. Returns ``(choice, reason)``."""
    import time as _time

    from sentinel_tpu.core.log import record_log
    from sentinel_tpu.ops import KERNEL_BUILD_ERRORS

    cfg = ParamConfig(impl="jax", sketch=sketch)
    state = make_param_state(cfg)
    n = _PROBE_ROWS
    args = (
        jnp.zeros(n, jnp.int32),
        jnp.zeros((n, cfg.depth), jnp.int32),
        jnp.ones(n, jnp.int32),
        jnp.full(n, 1e9, jnp.float32),
        jnp.zeros(n, bool),  # nothing valid → probe leaves state unchanged
        jnp.int32(0),
    )
    times = {}
    refused = None
    for name, fn in _param_cores(sketch).items():
        try:
            _, ok, _ = fn(cfg, state, *args)  # compile + warm
        except KERNEL_BUILD_ERRORS as e:
            if name == "jax":
                raise  # no kernel at all: nothing to fall back to
            refused = f"{type(e).__name__}: {e}"
            record_log.error(
                "[param] %s pallas kernel refused by the compiler, auto "
                "resolves to jax: %s", sketch, refused,
            )
            continue
        jax.block_until_ready(ok)
        t0 = _time.perf_counter()
        for _ in range(3):
            _, ok, _ = fn(cfg, state, *args)
        jax.block_until_ready(ok)
        times[name] = (_time.perf_counter() - t0) / 3
    choice = min(times, key=times.get)
    reason = f"probe ({sketch}, {n} rows): " + ", ".join(
        f"{k} {v * 1e3:.3f} ms/step" for k, v in times.items()
    )
    if refused is not None:
        reason += f"; pallas refused: {refused}"
    return choice, reason


@partial(jax.jit, static_argnames=("config",))
def _param_decide_pallas(
    config: ParamConfig,
    state: ParamState,
    rule_slot: jax.Array,
    idx: jax.Array,
    acquire: jax.Array,
    threshold: jax.Array,
    valid: jax.Array,
    now: jax.Array,
) -> Tuple[ParamState, jax.Array, jax.Array]:
    """Same contract as :func:`_param_decide_jax`, via the VMEM-resident
    one-hot-matmul kernel (``ops/cms_pallas.py``). The kernel's plane-major
    layout ``[B*D, P, W]`` is converted at the boundary."""
    from sentinel_tpu.ops.cms_pallas import cms_decide_update_pallas

    P, B, D, W = (
        config.max_param_rules,
        config.n_buckets,
        config.depth,
        config.width,
    )
    planes = jnp.transpose(state.counts, (1, 2, 0, 3)).reshape(B * D, P, W)
    planes, starts, admit, est = cms_decide_update_pallas(
        planes,
        state.starts,
        rule_slot,
        idx,
        acquire,
        threshold,
        valid,
        now,
        P=P,
        B=B,
        D=D,
        W=W,
        bucket_ms=config.bucket_ms,
    )
    counts = jnp.transpose(planes.reshape(B, D, P, W), (2, 0, 1, 3))
    return state._replace(starts=starts, counts=counts), admit, est


@partial(jax.jit, static_argnames=("config",))
def _param_decide_jax(
    config: ParamConfig,
    state: ParamState,
    rule_slot: jax.Array,  # [N] int32, -1 → no rule
    idx: jax.Array,  # [N, depth] int32 CMS cell indices
    acquire: jax.Array,  # [N] int32
    threshold: jax.Array,  # [N] float32 (rule count or per-item override)
    valid: jax.Array,  # [N] bool
    now: jax.Array,
) -> Tuple[ParamState, jax.Array, jax.Array]:
    """``-> (state', admit[N] bool, estimate[N] int32)``.

    Mirrors the cluster param checker (``ClusterParamFlowChecker.java:42-96``:
    sum per-value across buckets vs threshold) with CMS estimates and the
    same in-batch prefix discipline as the flow kernel: requests on the same
    (rule, value) are admitted in order against the shared budget. The
    prefix key uses the full index tuple so distinct values never couple
    unless they collide in *every* lane (exactly the CMS overestimate case).
    """
    now = jnp.asarray(now, jnp.int32)
    B = config.n_buckets
    cur_idx = (now // config.bucket_ms) % B
    cur_start = now - now % config.bucket_ms

    # roll current bucket (shared-ring lazy reset, as stats.window.roll)
    stale = state.starts[cur_idx] != cur_start
    counts = jnp.where(
        (jnp.arange(B)[None, :, None, None] == cur_idx) & stale,
        0,
        state.counts,
    )
    starts = state.starts.at[cur_idx].set(cur_start)

    age = now - starts
    bucket_ok = (age >= 0) & (age < config.interval_ms)  # [B]

    safe_slot = jnp.where(rule_slot >= 0, rule_slot, 0)
    live = valid & (rule_slot >= 0)

    # estimate = min over depth of windowed sums  [N]
    d_ar = jnp.arange(config.depth)[None, :]  # [1, D]

    def gather_sum(b):
        # counts[safe_slot, b, d, idx[:, d]] for each d → [N, D]
        per_d = counts[safe_slot[:, None], b, d_ar, idx]  # [N, D]
        return per_d * bucket_ok[b].astype(jnp.int32)

    sums = sum(gather_sum(b) for b in range(B))  # [N, D]
    estimate = jnp.min(sums, axis=1)  # [N]

    # in-batch prefix on the (slot, full index tuple) key — int32 wraparound
    # mix; a 32-bit key collision merely couples two values' in-batch budgets
    # conservatively (same direction as the CMS overestimate)
    from sentinel_tpu.engine.prefix import segment_prefix_builder

    key = safe_slot
    for d in range(config.depth):
        key = key * jnp.int32(-1640531527) + idx[:, d]  # 0x9E3779B9 mix
    seg_prefix = segment_prefix_builder(key, "sort")

    acq = acquire.astype(jnp.int32)
    admit = live
    for _ in range(3):  # odd refinement ⇒ never overshoot (see decide.py)
        contrib = jnp.where(admit, acq, 0)
        prefix = seg_prefix(contrib)
        admit = live & (
            estimate.astype(jnp.float32) + prefix + acq.astype(jnp.float32)
            <= threshold
        )

    # update: scatter admitted acquires into all depth lanes of current bucket
    upd_vals = jnp.where(admit, acq, 0)[:, None].repeat(config.depth, 1)
    counts = counts.at[
        safe_slot[:, None], cur_idx, d_ar, idx
    ].add(upd_vals, mode="drop")

    return state._replace(starts=starts, counts=counts), admit, estimate