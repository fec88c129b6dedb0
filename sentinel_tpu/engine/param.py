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
buckets with the same ring/mask-on-read discipline as ``stats.window``. The
serve step (:func:`make_param_step`, ``jit_param_decide_b<bucket>``) keeps
the same cells **flat** and donated, takes a whole batch of (request, value)
rows as one packed host array and answers per request; the service
(``DefaultTokenService.request_params_batch``) is its only caller.
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

from sentinel_tpu.engine.state import DELTA, SNAPSHOT, Column

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

# The sketch's leaves in the table of state columns (``engine.state.Column``
# says what an entry means). The fat counters are a window like any other,
# keyed by param rule, but three things about them stay code, in
# ``cluster.state_codec``: a MOVE sums *decoded* cells, a delta ships the
# slim twin in their place while the twin is on, and the authority flags
# follow the buckets a delta touched rather than being shipped. The twin,
# its flags and the merge counters ride no MOVE blob; the flags and the
# counters no delta either.
PARAM_COLUMNS = (
    Column("param", "starts", None, "clock", None, "param_starts",
           frozenset({SNAPSHOT, DELTA})),
    Column("param", "counts", "param", "window", "param", "param"),
    Column("param", "slim", "param", "value", "param", "param_slim",
           frozenset({SNAPSHOT, DELTA})),
    Column("param", "slim_auth", None, "value", None, "param_slim_auth",
           frozenset({SNAPSHOT})),
    Column("param", "merges", "param", "value", None, "param_merges",
           frozenset({SNAPSHOT})),
)


def fat_shape(config: ParamConfig) -> tuple:
    return (config.max_param_rules, config.n_buckets, config.depth,
            config.cell_width)


def make_param_state(config: ParamConfig, flat: bool = False) -> ParamState:
    """An empty sketch. ``flat=True`` lays the fat counters out as one flat
    array (``counts.reshape(-1)``): the layout the serve step keeps them in
    (:func:`make_param_step`)."""
    B = config.n_buckets
    fat_dtype = jnp.int16 if config.sketch == "salsa" else jnp.int32
    shape = fat_shape(config)
    return ParamState(
        starts=jnp.full((B,), NEVER, jnp.int32),
        counts=jnp.zeros(
            (int(np.prod(shape)),) if flat else shape, fat_dtype),
        slim=jnp.zeros((shape[0], B, config.slim_depth, config.slim_width),
                       jnp.int32),
        slim_auth=jnp.zeros((B,), bool),
        merges=jnp.zeros((shape[0],), jnp.int32),
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
    kernel: str = None,
) -> Tuple[ParamState, jax.Array, jax.Array]:
    """Dispatch on ``config.sketch`` × ``config.impl`` (``kernel``, when
    given, is the caller's own resolution of ``impl``: the serve step
    resolves it once for the service's geometry).

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
        kernel or resolve_param_impl(config.impl, config.sketch, config)
    ]
    if idx_slim is None or not config.slim_enabled:
        return core(config, state, rule_slot, idx, acquire, threshold, valid,
                    now)
    from sentinel_tpu.sketch.slim import slim_poststep, slim_prestep

    with jax.named_scope("slim_pre"):
        slim, slim_auth, est_slim = slim_prestep(
            config, state, rule_slot, idx_slim, now
        )
    state = state._replace(slim=slim, slim_auth=slim_auth)
    thr = jnp.asarray(threshold, jnp.float32) - est_slim.astype(jnp.float32)
    state2, admit, est_fat = core(
        config, state, rule_slot, idx, acquire, thr, valid, now
    )
    with jax.named_scope("slim_post"):
        slim2 = slim_poststep(
            config, state2, rule_slot, idx, idx_slim, valid, now
        )
    return state2._replace(slim=slim2), admit, est_fat + est_slim


# Per-process cache of what "auto" resolved to: (platform, sketch, geometry,
# rows) → (choice, reason), and under (platform, sketch) the latest
# resolution of that sketch, which a caller that names no geometry gets.
_AUTO_IMPL: dict = {}

# rows of the probe batch for a caller that names none
_PROBE_ROWS = 64


def _geometry(config: "ParamConfig") -> tuple:
    return (config.max_param_rules, config.n_buckets, config.depth,
            config.width)


def explain_param_impl(impl: str, sketch: str = "cms", config=None,
                       rows: int = None) -> tuple:
    """Resolve ``impl`` to ``(kernel, reason)`` with ``kernel`` in
    ("jax" | "pallas").

    An explicit "jax"/"pallas" (config or ``SENTINEL_PARAM_IMPL``) is taken
    as given — a forced "pallas" whose kernel Mosaic refuses raises the
    compiler's error, it is never served from XLA instead. "auto" picks per
    platform: off-TPU the XLA path outright (Mosaic compiles for the TPU
    only); on TPU both kernels of ``sketch`` are timed once per process
    **at the geometry of ``config`` and a batch of ``rows``** (a service
    passes its own config and its largest serve bucket; the default
    geometry and 64 rows otherwise) and the faster one is cached. A kernel
    that fails to build loses the probe out loud: the compiler's message is
    logged and returned in the reason. Without ``config`` the latest
    resolution of ``sketch`` in this process is returned, if there is one.
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
    if platform != "tpu":
        return "jax", f"platform {platform!r}: Mosaic compiles for TPU only"
    latest = (platform, sketch)
    if config is None:
        if latest in _AUTO_IMPL:
            return _AUTO_IMPL[latest]
        config = ParamConfig(sketch=sketch)
    key = (platform, sketch, _geometry(config), rows)
    resolved = _AUTO_IMPL.get(key)
    if resolved is None:
        resolved = _probe_param_impl(
            config._replace(sketch=sketch), rows or _PROBE_ROWS
        )
        _AUTO_IMPL[key] = resolved
    _AUTO_IMPL[latest] = resolved
    return resolved


def resolve_param_impl(impl: str, sketch: str = "cms", config=None,
                       rows: int = None) -> str:
    """The kernel half of :func:`explain_param_impl`."""
    return explain_param_impl(impl, sketch, config, rows)[0]


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


def _probe_param_impl(config: "ParamConfig", rows: int) -> tuple:
    """Time one warm step of each kernel of ``config.sketch`` on the live
    backend, on a throwaway state of ``config``'s own geometry and a batch
    of ``rows`` rows. Returns ``(choice, reason)``."""
    import time as _time

    from sentinel_tpu.core.log import record_log
    from sentinel_tpu.ops import KERNEL_BUILD_ERRORS

    cfg = config._replace(impl="jax")
    n = int(rows)
    packed = np.zeros((packed_lines(cfg), n), np.int32)
    packed[ROW_SLOT] = -1  # nothing valid → the sketch stays empty
    packed[-1, :3] = (0, 1, n)
    times = {}
    refused = None
    for name in _param_cores(cfg.sketch):
        # the serve step as it is served: flat donated state, threaded
        step = make_param_step(cfg, n, name)
        state = make_param_state(cfg, flat=True)
        try:
            state, ok = step(state, packed)  # compile + warm
        except KERNEL_BUILD_ERRORS as e:
            if name == "jax":
                raise  # no kernel at all: nothing to fall back to
            refused = f"{type(e).__name__}: {e}"
            record_log.error(
                "[param] %s pallas kernel refused by the compiler, auto "
                "resolves to jax: %s", cfg.sketch, refused,
            )
            continue
        jax.block_until_ready(ok)
        t0 = _time.perf_counter()
        for _ in range(3):
            state, ok = step(state, packed)
        jax.block_until_ready(ok)
        times[name] = (_time.perf_counter() - t0) / 3
        del state
    choice = min(times, key=times.get)
    reason = (
        f"probe ({cfg.sketch} {cfg.max_param_rules}x{cfg.n_buckets}x"
        f"{cfg.depth}x{cfg.width}, {n} rows): "
        + ", ".join(f"{k} {v * 1e3:.3f} ms/step" for k, v in times.items())
    )
    if refused is not None:
        reason += f"; pallas refused: {refused}"
    record_log.info("[param] impl 'auto' resolved to %r: %s", choice, reason)
    return choice, reason


# -- the serve step -----------------------------------------------------------
# One host array carries a whole param dispatch to the device (a host
# argument costs 0.13-0.16 ms of launch each, PERF.md): ``int32[C, rows]``,
# one row of the batch a column, rows request-major (request i's k values
# at columns i*k .. i*k+k-1), padded with slot -1 to the serve bucket.
ROW_SLOT, ROW_ACQUIRE, ROW_THRESHOLD, ROW_IDX = 0, 1, 2, 3
# the last line is the header: [now, values per request, requests, 0...]
HEAD_NOW, HEAD_K, HEAD_REQUESTS = 0, 1, 2
# verdict codes of the step (TokenStatus OK / BLOCKED / NO_RULE_EXISTS)
_ST_OK, _ST_BLOCKED, _ST_NO_RULE = 0, 1, 3


def packed_lines(config: ParamConfig) -> int:
    """Lines of the serve step's packed host array."""
    slim = config.slim_depth if config.slim_enabled else 0
    return ROW_IDX + config.depth + slim + 1


def prep_geometry(config: ParamConfig) -> tuple:
    """``(depth, cell_width, slim_depth, slim_width, slim_salt)``: what the
    host prep of a batch takes from the config (``native.lib.param_prep``);
    ``slim_depth`` 0 where the twin is off."""
    from sentinel_tpu.sketch.slim import SLIM_SALT

    slim = config.slim_depth if config.slim_enabled else 0
    return (config.depth, config.cell_width, slim, config.slim_width,
            SLIM_SALT)


def pack_param_rows(config: ParamConfig, bucket: int, slots, acquires,
                    thresholds, idx, idx_slim, now: int, k: int,
                    n_requests: int) -> np.ndarray:
    """The serve step's one host argument. ``slots`` / ``acquires`` /
    ``thresholds`` are per (request, value) row, ``idx`` ``[rows, depth]``,
    ``idx_slim`` ``[rows, slim_depth]`` or None; a row with slot -1 (no
    rule, or padding) never touches the sketch."""
    r = len(slots)
    out = np.zeros((packed_lines(config), bucket), np.int32)
    out[ROW_SLOT, :r] = slots
    out[ROW_SLOT, r:] = -1
    out[ROW_ACQUIRE, :r] = acquires
    out[ROW_THRESHOLD, :r] = np.asarray(thresholds, np.float32).view(np.int32)
    d = config.depth
    out[ROW_IDX:ROW_IDX + d, :r] = idx.T
    if config.slim_enabled:
        out[ROW_IDX + d:ROW_IDX + d + config.slim_depth, :r] = idx_slim.T
    out[-1, :3] = (now, k, n_requests)
    return out


def make_param_step(config: ParamConfig, bucket: int, kernel: str):
    """The jitted serve step of one bucket, ``jit_param_decide_b<bucket>``
    in a trace: ``(state, packed int32[C, bucket]) -> (state', verdicts
    int32[3, bucket])``. The state is **donated** and its fat counters are
    **flat** (``make_param_state(config, flat=True)``): the step updates the
    sketch in place and touches what its rows touch (plus, once per
    ``bucket_ms``, the plane of the bucket that went stale), with no layout
    conversion around the gathers or the commit: the gathers take the flat
    cells, and the commit (``ops/cms_commit.py``, PR 39: the admitted
    pairs sorted by cell, each touched row of 128 cells read, added to and
    written back once by DMA) sees the same bytes as rows of 128 through a
    bitcast. The plain count-min core on XLA and the
    slim twin's steps work on the flat cells; any other kernel (Pallas,
    SALSA) is handed its 4-D view inside the step. ``verdicts`` holds (status, remaining, wait_ms) per
    *request*, in request order: a request passes only if every one of its
    ``k`` rows was admitted, and the rows that were admitted stay counted
    (``request_params_batch``)."""
    d = config.depth
    ds = config.slim_depth if config.slim_enabled else 0
    flat_core = config.sketch == "cms" and kernel == "jax"

    def decide(state, *rows, idx_slim):
        if flat_core:  # param_decide and the twin's steps take flat cells
            return param_decide(config, state, *rows, idx_slim=idx_slim,
                                kernel=kernel)[:2]
        shaped, admit, _est = param_decide(
            config,
            state._replace(counts=state.counts.reshape(fat_shape(config))),
            *rows, idx_slim=idx_slim, kernel=kernel,
        )
        return shaped._replace(counts=shaped.counts.reshape(-1)), admit

    def step(state: ParamState, packed: jax.Array):
        slot = packed[ROW_SLOT]
        acquire = packed[ROW_ACQUIRE]
        threshold = jax.lax.bitcast_convert_type(
            packed[ROW_THRESHOLD], jnp.float32
        )
        idx = packed[ROW_IDX:ROW_IDX + d].T
        idx_slim = packed[ROW_IDX + d:ROW_IDX + d + ds].T if ds else None
        head = packed[-1]
        now, k, n_req = head[HEAD_NOW], head[HEAD_K], head[HEAD_REQUESTS]
        valid = slot >= 0
        state, admit = decide(
            state, slot, idx, acquire, threshold, valid, now,
            idx_slim=idx_slim,
        )
        with jax.named_scope("param_request_and"):
            from sentinel_tpu.ops.scan_mm import blocked_cumsum

            # blocked rows among a request's k, by one cumulative sum (an
            # exact float32 count: a bucket has far fewer than 2^24 rows)
            refused = blocked_cumsum(
                (valid & ~admit).astype(jnp.float32)
            ).astype(jnp.int32)
            i = jnp.arange(bucket, dtype=jnp.int32)
            first = jnp.minimum(i * k, bucket - 1)
            last = jnp.minimum(i * k + k - 1, bucket - 1)
            before = jnp.where(
                first > 0, refused[jnp.maximum(first - 1, 0)], 0
            )
            n_refused = refused[last] - before
            status = jnp.where(
                slot[first] < 0, _ST_NO_RULE,
                jnp.where(n_refused > 0, _ST_BLOCKED, _ST_OK),
            )
            status = jnp.where(i < n_req, status, _ST_NO_RULE)
            zero = jnp.zeros_like(status)
            verdicts = jnp.stack([status, zero, zero])
        return state, verdicts

    step.__name__ = step.__qualname__ = f"param_decide_b{bucket}"
    return jax.jit(step, donate_argnums=(0,))


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

    ``state.counts`` is ``[P, B, depth, width]`` or the same cells flat
    (``make_param_state(config, flat=True)``), and comes back as it came.
    The work is done on the flat cells either way: the TPU's gather wants
    its operand flat (and the commit's rows of 128 are a bitcast of it),
    and converting a tiled 4-D sketch there and back costs two copies of
    the whole sketch a call (at 0.5 GiB, 2.6 ms), which a caller that keeps
    the sketch flat (the serve step) never pays.
    """
    shape = state.counts.shape
    flat, starts, admit, estimate = _cms_flat(
        config, state.counts.reshape(-1), state.starts, rule_slot, idx,
        acquire, threshold, valid, now,
    )
    return (state._replace(starts=starts, counts=flat.reshape(shape)),
            admit, estimate)


def _cms_flat(config, counts, starts, rule_slot, idx, acquire, threshold,
              valid, now):
    """The plain count-min core on flat cells ``int32[P*B*depth*width]``
    (cell ``(p, b, d, w)`` at ``((p*B + b)*depth + d)*width + w``):
    ``-> (counts', starts', admit, estimate)``.

    The commit is ``ops/cms_commit.py`` (PR 39) and the only one this core
    has, on every backend (off the TPU the same kernel runs under the Pallas
    interpreter). What it replaced was ``counts.at[cells].add(acquires,
    mode="drop")``: on the TPU a read-modify-write per update against HBM,
    one after the other, 92 ns a cell in the step and 110 alone, which was
    half of ``hot-param-1k``'s device time. Sorted, pre-reduced and
    duplicate-free indices with ``unique_indices=True`` cost the same 110
    ns; ``indices_are_sorted=True`` made it a pass over the whole sketch
    (1.6 ms at any batch); writing ``old + total`` by a ``set`` cost 128.
    The kernel sorts the pairs and moves each touched row of 128 cells by
    DMA, a chunk's worth in flight: 36 ns a cell (my chip runs, PR 39:
    PERF.md section 6)."""
    now = jnp.asarray(now, jnp.int32)
    P, B, D, W = fat_shape(config)
    size = P * B * D * W
    cur_idx = (now // config.bucket_ms) % B
    cur_start = now - now % config.bucket_ms

    with jax.named_scope("param_roll"):
        # roll the current bucket (shared-ring lazy reset, as
        # stats.window.roll): its plane is cleared only when it is stale,
        # once per bucket_ms, so a dispatch on a donated sketch touches
        # what its rows touch and not the whole ring
        stale = starts[cur_idx] != cur_start

        def clear(c):
            cell = jax.lax.iota(jnp.int32, size)
            return jnp.where((cell // (D * W)) % B == cur_idx, 0, c)

        # the barrier keeps the compiler from moving the commit's view of
        # the cells as rows of 128 into the branches: at 64 rows it did, and
        # answered with a copy of the whole sketch on every call (PR 39)
        counts = jax.lax.optimization_barrier(
            jax.lax.cond(stale, clear, lambda c: c, counts))
        starts = starts.at[cur_idx].set(cur_start)
        age = now - starts
        bucket_ok = (age >= 0) & (age < config.interval_ms)  # [B]

    live = valid & (rule_slot >= 0)
    # a row that is not live reads and writes past the end: the gather
    # fills 0 there and the scatter drops it
    slot = jnp.where(live, rule_slot, 0)
    d_ar = jnp.arange(D, dtype=jnp.int32)[None, :]  # [1, D]
    # cell of (row, depth lane) in bucket 0; bucket b is b*D*W further on
    base = (slot[:, None] * (B * D) + d_ar) * W + idx  # [N, D]
    base = jnp.where(live[:, None], base, size)

    with jax.named_scope("param_estimate"):
        # estimate = min over depth of the windowed sums
        b_off = jnp.arange(B, dtype=jnp.int32)[None, :, None] * (D * W)
        per = counts.at[base[:, None, :] + b_off].get(
            mode="fill", fill_value=0
        )  # [N, B, D]
        sums = jnp.sum(
            per * bucket_ok.astype(jnp.int32)[None, :, None], axis=1
        )  # [N, D]
        estimate = jnp.min(sums, axis=1)  # [N]

    with jax.named_scope("param_prefix"):
        # in-batch prefix on the (slot, full index tuple) key — int32
        # wraparound mix; a 32-bit key collision merely couples two values'
        # in-batch budgets conservatively (same direction as the CMS
        # overestimate)
        from sentinel_tpu.engine.prefix import segment_prefix_builder

        key = jnp.where(live, rule_slot, P)
        for d in range(D):
            key = key * jnp.int32(-1640531527) + idx[:, d]  # 0x9E3779B9 mix
        seg_prefix = segment_prefix_builder(key, "sort")

    with jax.named_scope("param_admit"):
        acq = acquire.astype(jnp.int32)
        admit = live
        for _ in range(3):  # odd refinement ⇒ never overshoot (decide.py)
            contrib = jnp.where(admit, acq, 0)
            prefix = seg_prefix(contrib)
            admit = live & (
                estimate.astype(jnp.float32) + prefix
                + acq.astype(jnp.float32) <= threshold
            )

    with jax.named_scope("param_commit"):
        # admitted acquires into all depth lanes of the current bucket;
        # refused rows go past the end and are dropped
        from sentinel_tpu.ops.cms_commit import commit_cells

        cur_cell = base + cur_idx * (D * W)
        counts = commit_cells(
            counts,
            jnp.where(admit[:, None], cur_cell, size).reshape(-1),
            jnp.broadcast_to(acq[:, None], cur_cell.shape).reshape(-1),
            interpret=jax.default_backend() != "tpu",
        )

    return counts, starts, admit, estimate
