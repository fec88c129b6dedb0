"""TokenService SPI and its engine-backed default implementation.

Analogs: ``sentinel-core/.../cluster/TokenService.java`` (the SPI seam),
``TokenResult``/``TokenResultStatus``, and the server-side
``DefaultTokenService.java:36-97`` whose per-request logic is replaced by the
jitted batch kernel ``sentinel_tpu.engine.decide``.

Both deployment shapes of the reference exist here:
- **standalone** (``SentinelDefaultTokenServer``): ``server.TokenServer``
  wraps a ``DefaultTokenService`` behind the TCP front door;
- **embedded** (``DefaultEmbeddedTokenServer``): the same object serves
  in-process calls from the local flow checker *and* remote clients.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu import chaos as _chaos
from sentinel_tpu.cluster import state_codec
from sentinel_tpu.core import clock as _clock
from sentinel_tpu.core import compile_cache as _compile_cache
from sentinel_tpu.core.log import record_log
from sentinel_tpu.engine import (
    ClusterFlowRule,
    DegradeRule,
    EngineConfig,
    EngineState,
    TokenStatus,
    build_rule_table,
    decide,
    drain_pending_clear,
    make_state,
    pack_requests,
    pack_requests_into,
    unpack_verdicts,
)
from sentinel_tpu.engine.decide import (
    ARM_BREAKER,
    ARM_DEGRADED_ROWS,
    ARM_GUARDED_ROWS,
    ARM_LIVE,
    ARM_OCCUPY,
    ARM_PACED_ROWS,
    ARM_PACING,
    ARM_PRIORITIZED_ROWS,
    ARM_PROBES,
    ARM_SHAPED_ROWS,
    ARM_SHAPING,
    ARM_TO_OPEN,
    HEAD_NOW,
    ROW_HEAD,
    unpack_arms,
)
from sentinel_tpu.engine import outcome as _outcome
from sentinel_tpu.engine.outcome import TALLY_CLOSED, TALLY_REOPENED
from sentinel_tpu.engine.param import (
    ParamConfig,
    explain_param_impl,
    hash_indices,
    make_param_state,
    make_param_step,
    pack_param_rows,
    prep_geometry,
)
from sentinel_tpu.engine.rules import RuleIndex
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.metrics.stat_logger import log_cluster
from sentinel_tpu.native import lib as _native
from sentinel_tpu.trace import ring as _TR

_SM = server_metrics()
# flight-recorder identity of a service (the ``shard`` field of its phase
# events): dispatch sequence numbers are per service
_SERVICE_IDS = itertools.count(1)
# the cluster stat log's events of a dispatch, and the verdict each counts
_STAT_LOG_EVENTS = (
    ("pass", int(TokenStatus.OK)),
    ("block", int(TokenStatus.BLOCKED)),
    ("occupied", int(TokenStatus.SHOULD_WAIT)),
    ("tooManyRequest", int(TokenStatus.TOO_MANY_REQUEST)),
    ("degraded", int(TokenStatus.DEGRADED)),
)


@dataclass(frozen=True)
class ClusterParamFlowRule:
    """Cluster hot-param rule (``ParamFlowRule`` + ``ClusterFlowConfig``):
    per-value QPS threshold, with per-item overrides keyed by the value's
    stable hash (``ParamFlowItem`` analog — compute with
    ``sentinel_tpu.core.hashing.stable_param_hash``)."""

    flow_id: int
    count: float
    item_thresholds: Optional[Tuple[Tuple[int, float], ...]] = None
    namespace: str = "default"


@dataclass(frozen=True)
class TokenResult:
    """``TokenResult.java`` — status + remaining + wait hint (+ token id in
    concurrent mode)."""

    status: TokenStatus
    remaining: int = 0
    wait_ms: int = 0
    token_id: int = 0
    # MOVED only: the new owner's "host:port" (``remaining`` then carries the
    # shard-map epoch). Empty for every other status.
    endpoint: str = ""

    @property
    def ok(self) -> bool:
        # RELEASE_OK is the success status of a concurrent release — the one
        # natural success predicate must cover both acquire and release paths
        return self.status in (TokenStatus.OK, TokenStatus.RELEASE_OK)

    @property
    def retry_after_ms(self) -> int:
        """DEGRADED only: how long until the flow's breaker admits a
        recovery probe (``remaining`` carries it on the wire, like the
        MOVED epoch). 0 for every other status."""
        return (
            int(self.remaining)
            if self.status == TokenStatus.DEGRADED else 0
        )


def params_batch_entry(service):
    """The entry a door decides single PARAM_FLOW requests through: the
    served object's own ``request_params_batch`` where its class defines
    one, else the SPI default over its ``request_params_token`` — so an
    object that forwards attributes to a service and intercepts only the
    one-request entry (a control harness, a chaos shim, an older SPI
    implementation) is still asked through what it intercepts."""
    if getattr(type(service), "request_params_batch", None) is not None:
        return service.request_params_batch
    return partial(TokenService.request_params_batch, service)


def decide_param_requests(service, requests, fail_status: int):
    """Single PARAM_FLOW requests (objects with ``flow_id``, ``count``,
    ``param_hashes``) in queue order -> ``[(status, remaining, wait_ms)]``:
    one call of the batched entry per run of requests with the same number
    of values, not one dispatch a request. A run whose call raises is
    answered ``fail_status``. Both doors decide their single frames here."""
    out = []
    entry = params_batch_entry(service)
    lo = 0
    while lo < len(requests):
        k = len(requests[lo].param_hashes)
        hi = lo
        while hi < len(requests) and len(requests[hi].param_hashes) == k:
            hi += 1
        run = requests[lo:hi]
        try:
            status, remaining, wait = entry(
                np.array([r.flow_id for r in run], np.int64),
                np.array([r.count for r in run], np.int32),
                np.array([r.param_hashes for r in run],
                         np.int64).reshape(len(run), k),
            )
            out.extend(zip(status.tolist(), remaining.tolist(),
                           wait.tolist()))
        except Exception:
            record_log.exception("PARAM_FLOW requests failed")
            out.extend([(fail_status, 0, 0)] * len(run))
        lo = hi
    return out


def concurrent_batch_entry(service):
    """The entry a door decides concurrency rows through: as
    :func:`params_batch_entry`, the served object's own
    ``request_concurrent_batch`` where its class defines one, else the SPI
    default over its one-row calls."""
    if getattr(type(service), "request_concurrent_batch", None) is not None:
        return service.request_concurrent_batch
    return partial(TokenService.request_concurrent_batch, service)


def decide_concurrent_requests(service, requests, is_release,
                               fail_status: int):
    """Single CONCURRENT_ACQUIRE / CONCURRENT_RELEASE requests (objects with
    ``flow_id`` and ``count``; a release's ``flow_id`` slot carries its
    token id) in queue order -> ``[(status, remaining, wait_ms, token_id)]``:
    one call of the batched entry for all of them. A call that raises
    answers ``fail_status``."""
    n = len(requests)
    try:
        status, remaining, wait, token_ids = concurrent_batch_entry(service)(
            np.fromiter((r.flow_id for r in requests), np.int64, n),
            np.fromiter((r.count for r in requests), np.int32, n),
            np.asarray(is_release, bool),
        )
        return list(zip(status.tolist(), remaining.tolist(), wait.tolist(),
                        token_ids.tolist()))
    except Exception:
        record_log.exception("CONCURRENT requests failed")
        return [(fail_status, 0, 0, 0)] * n


class Materializer:
    """What a dispatch hands back: the zero-arg callable that blocks on the
    device and yields ``(status, remaining, wait)`` in request order, made
    of two halves. :meth:`read` ends with the verdicts in host hands;
    :meth:`account` then counts them (``DefaultTokenService._account``).
    Calling the object is the two one after the other, so whoever calls it
    whole sees counters that are complete when the call returns. The native
    reply lane runs ``read()``, submits the reply, and only then
    ``account(replied=True)``: nothing the accounting computes is in a reply.

    ``read`` is the dispatch's own closure and returns ``(verdicts,
    account)``: ``account`` takes ``replied``, or is None where there is
    nothing to count. A dispatch is accounted at most once, and never when
    its read raised."""

    def __init__(self, read):
        self._read = read
        self._pending = None

    def read(self):
        verdicts, self._pending = self._read()
        return verdicts

    def account(self, replied: bool = False) -> None:
        pending, self._pending = self._pending, None
        if pending is not None:
            pending(replied)

    def __call__(self):
        verdicts = self.read()
        self.account()
        return verdicts


def halves(mat):
    """``(read, account)`` of any materializer. A plain callable (a foreign
    service's, a wrapper's) is read whole and has no account half."""
    if isinstance(mat, Materializer):
        return mat.read, mat.account
    return mat, None


class TokenService:
    """The SPI: local flow checkers and the transport both speak this."""

    def request_token(
        self, flow_id: int, acquire: int = 1, prioritized: bool = False
    ) -> TokenResult:
        raise NotImplementedError

    def request_params_token(
        self, flow_id: int, acquire: int, param_hashes: Sequence[int]
    ) -> TokenResult:
        raise NotImplementedError

    def request_params_batch(self, flow_ids, acquires, hashes):
        """Array form of :meth:`request_params_token`: ``n`` requests of
        ``k`` value hashes each (``hashes int64[n, k]``) -> (status int8[n],
        remaining int32[n], wait_ms int32[n]) in request order. The doors
        speak this for BATCH_PARAM_FLOW frames and for drained single
        PARAM_FLOW frames; the default asks one request at a time, so any
        SPI implementation serves them."""
        n = len(flow_ids)
        results = [
            self.request_params_token(
                int(flow_ids[i]), int(acquires[i]),
                [int(h) for h in hashes[i]],
            )
            for i in range(n)
        ]
        status = np.fromiter((int(r.status) for r in results), np.int8, n)
        remaining = np.fromiter((r.remaining for r in results), np.int32, n)
        wait = np.fromiter((r.wait_ms for r in results), np.int32, n)
        return status, remaining, wait

    def request_batch(
        self, requests: Sequence[Tuple[int, int, bool]]
    ) -> List[TokenResult]:
        """Vectorized form: list of (flow_id, acquire, prioritized)."""
        return [self.request_token(f, a, p) for f, a, p in requests]

    def request_batch_arrays(self, flow_ids, acquires=None, prios=None):
        """Array form: (status int8[N], remaining int32[N], wait_ms int32[N])
        in request order. The transport speaks this; the default delegates to
        ``request_batch`` so any SPI implementation serves batch frames."""
        n = len(flow_ids)
        results = self.request_batch(
            [
                (
                    int(flow_ids[i]),
                    1 if acquires is None else int(acquires[i]),
                    False if prios is None else bool(prios[i]),
                )
                for i in range(n)
            ]
        )
        status = np.fromiter((int(r.status) for r in results), np.int8, n)
        remaining = np.fromiter((r.remaining for r in results), np.int32, n)
        wait = np.fromiter((r.wait_ms for r in results), np.int32, n)
        return status, remaining, wait

    def request_concurrent_token(
        self, flow_id: int, acquire: int = 1, prioritized: bool = False
    ) -> TokenResult:
        """Cluster-semaphore acquire (``ConcurrentClusterFlowChecker``)."""
        raise NotImplementedError

    def release_concurrent_token(self, token_id: int) -> TokenResult:
        raise NotImplementedError

    def request_concurrent_batch(self, ids, counts=None, is_release=None):
        """Array form of the two calls above: row ``i`` acquires
        ``counts[i]`` on flow ``ids[i]`` or, where ``is_release[i]``,
        releases token ``ids[i]`` -> (status int8[n], remaining int32[n],
        wait_ms int32[n], token_ids int64[n]) in request order. The doors
        speak this for BATCH_CONCURRENT_ACQUIRE / _RELEASE frames and for
        drained single frames; the default asks one row at a time, so any
        SPI implementation serves them."""
        n = len(ids)
        results = [
            self.release_concurrent_token(int(ids[i]))
            if is_release is not None and is_release[i]
            else self.request_concurrent_token(
                int(ids[i]), 1 if counts is None else int(counts[i]))
            for i in range(n)
        ]
        return (
            np.fromiter((int(r.status) for r in results), np.int8, n),
            np.fromiter((r.remaining for r in results), np.int32, n),
            np.zeros(n, np.int32),
            np.fromiter((r.token_id for r in results), np.int64, n),
        )


@dataclass(frozen=True)
class LeaseResult:
    """Outcome of a wire-rev-5 lease operation (grant/renew/return).

    ``status`` is a TokenStatus code: OK carries a live lease
    (``lease_id``/``tokens``/``ttl_ms``), NOT_LEASABLE means admit
    per-request instead (no headroom, revoked, or leasing disabled),
    NO_RULE_EXISTS / MOVED / STANDBY mean what they mean on the decision
    path — MOVED fills ``endpoint`` with the new owner."""

    status: int
    lease_id: int = 0
    tokens: int = 0
    ttl_ms: int = 0
    endpoint: str = ""

    @property
    def ok(self) -> bool:
        return int(self.status) == int(TokenStatus.OK)


class _Lease:
    """One outstanding lease: host registry entry only. The token charge
    itself lives in the LEASED column of the flow window — the registry is
    what lets renew/return credit unused tokens back and lets the drill
    bound crash over-admission by ``outstanding_leases()``. Deliberately
    NOT part of snapshots/deltas: a promoted standby starts with an empty
    registry, renews become credit-less re-grants, and the charge (which
    IS replicated) keeps the limit conservative."""

    __slots__ = ("lease_id", "flow_id", "slot", "tokens", "granted_ms",
                 "expiry_ms")

    def __init__(self, lease_id, flow_id, slot, tokens, granted_ms,
                 expiry_ms):
        self.lease_id = int(lease_id)
        self.flow_id = int(flow_id)
        self.slot = int(slot)
        self.tokens = int(tokens)
        self.granted_ms = int(granted_ms)
        self.expiry_ms = int(expiry_ms)


class DefaultTokenService(TokenService):
    """Engine-backed token service.

    The reference hot loop (rule lookup → LeapArray read-sum → LongAdder adds,
    ``ClusterFlowChecker.java:55-120``) runs as one device step per
    micro-batch; this class owns the device state and the host-side
    flow_id → slot index, and serializes steps with a lock (single-writer —
    the race-free analog of the JVM's CAS storm).
    """

    def __init__(
        self,
        config: Optional[EngineConfig] = None,
        param_config: Optional[ParamConfig] = None,
        mesh=None,
        serve_buckets: Optional[Sequence[int]] = None,
        fuse_depths: Optional[Sequence[int]] = (8, 4, 2),
        lease_ttl_ms: int = 500,
        lease_fraction: float = 0.5,
        concurrent_max_tokens: int = 1 << 20,
    ):
        self.config = config or EngineConfig()
        _compile_cache.install_compile_listener()
        # dispatches issued so far, bumped under the service lock: with
        # _trace_sid it joins one dispatch's phase events across threads
        self._dispatch_seq = 0
        self._trace_sid = next(_SERVICE_IDS) & 0x7FFF
        # serving shape buckets: a lightly-loaded step pads to the smallest
        # bucket that fits instead of the full batch size (the decide cost is
        # shape-proportional — ~4× cheaper at 64 than 1024 — and state
        # tensors are batch-agnostic, so each bucket is just one more
        # compiled variant of the same kernel). Default: geometric ×4 ladder
        # 64, 256, 1024, … up to batch_size, so no batch pays more than ~4×
        # its size. Warmup compiles 2 variants per bucket; trim the set if
        # compile time matters more than tail latency.
        if serve_buckets is None:
            buckets = set()
            b = 64
            while b < self.config.batch_size:
                buckets.add(b)
                b *= 4
            buckets.add(self.config.batch_size)
        else:
            buckets = {
                min(int(b), self.config.batch_size) for b in serve_buckets
            }
            buckets.add(self.config.batch_size)
        self._serve_buckets = sorted(buckets)
        # Optional jax.sharding.Mesh: the flow axis of the engine state and
        # rule table shards across the mesh's devices and the decision step
        # runs under shard_map with psums over ICI — one pod's chips serve
        # one namespace partition together (SURVEY §7.5 tier 1; tier 2 —
        # namespaces across pods — is sentinel_tpu.cluster.namespaces).
        self.mesh = mesh
        self._sharded_steps: Dict[Tuple[int, bool], object] = {}
        # fused multi-frame dispatch ladder: an oversized pull splits into
        # full-batch_size frames and each run of F consecutive frames folds
        # into ONE chained device step (lax.scan over the donated-state
        # step) — the per-dispatch overhead is paid once per F frames
        # instead of once per frame. Ladder entries are the
        # compiled scan depths (greedy largest-fit split, e.g. 7 frames →
        # scan(4) + scan(2) + single); empty disables fusion (per-frame
        # dispatch, the pre-fusion behavior). Mesh-sharded services skip
        # fusion — the shard_map step has its own dispatch discipline.
        self._fuse_depths = tuple(sorted(
            {int(d) for d in (fuse_depths or ()) if int(d) >= 2},
            reverse=True,
        ))
        self._fused_steps: Dict[Tuple[int, bool], object] = {}
        # fused staging freelists: per scan depth, recycled packed request
        # blocks (alloc_packed_block: ONE int32[lines, depth, batch] array,
        # the fused step's one host argument) the fused dispatch writes
        # prepped frames into — replaces a per-dispatch np.stack with copies
        # into reused memory. Blocks are released after verdict
        # materialization (the device has definitely consumed the host
        # buffer by then).
        self._fused_staging: Dict[int, object] = {}
        self._lock = threading.Lock()
        # outer mutex for rule read-modify-write sequences: a namespace
        # replacement (merge current rules + load) must be atomic against a
        # concurrent replacement of ANOTHER namespace, or the later load
        # silently drops the earlier one's rules. Reentrant so
        # load_namespace_rules → load_rules nests.
        self._rules_mutex = threading.RLock()
        self._state = self._place_state(make_state(self.config))
        table, self._index = build_rule_table(self.config, [])
        self._table = self._place_rules(table)
        # vectorized flow_id → slot lookup: one (sorted keys, slots) tuple,
        # swapped atomically on rule load, read lock-free on the hot path
        self._lookup = (np.empty(0, np.int64), np.empty(0, np.int32))
        # slot → namespace row snapshot for per-namespace verdict counters,
        # same atomic-swap discipline: (names tuple, int32[max_flows + 1]
        # of namespace indices, -1 where the slot holds no rule and in the
        # last entry, which is what slot -1 (no rule) indexes)
        self._ns_snapshot: Tuple[Tuple[str, ...], np.ndarray] = (
            (), np.full(self.config.max_flows + 1, -1, np.int32),
        )
        self._epoch_ms: Optional[int] = None
        self._connected: Dict[str, int] = {}  # namespace → client count
        self._ns_max_qps = 30_000.0
        # namespace-scoped rule bookkeeping (ClusterFlowRuleManager keeps
        # namespace → flowId sets; the command surface edits one namespace
        # at a time while the device table always holds the union)
        self._rules_by_ns: Dict[str, Dict[int, ClusterFlowRule]] = {}
        # flat flow_id → rule view of _rules_by_ns (same lifecycle): the
        # lease grant path needs the rule's count/mode/namespace per call
        # without walking namespaces
        self._rule_of: Dict[int, ClusterFlowRule] = {}
        self._param_rules_src: Dict[int, "ClusterParamFlowRule"] = {}
        # device-resident circuit breakers (engine/degrade.py): the source
        # DegradeRule objects keyed by flow_id (compiled into the br_*
        # rule-table columns on every load_rules), the slots that carry a
        # breaker (dirty-set and lease-refusal gating), and the host-side
        # state mirror the transition scanner diffs against (int8[F] copy
        # of the last breaker.state this host observed — the device is the
        # authority; the mirror only exists to emit
        # sentinel_breaker_transitions_total edges and the CLOSED→OPEN
        # blackbox dump without a device round-trip per transition).
        self._degrade_rules_src: Dict[int, "DegradeRule"] = {}
        self._has_breakers = False
        self._breaker_slots: set = set()
        self._breaker_fid: Dict[int, int] = {}  # breaker slot -> flow_id
        self._breaker_prev: Optional[np.ndarray] = None
        self._breaker_scan_ts = 0.0
        # namespaces this server explicitly serves (modifyNamespaceSet);
        # unioned with namespaces of loaded rules for info/fetchConfig
        self.namespace_set: set = set()
        # hot-param sketch path (ClusterParamFlowChecker analog)
        self.param_config = param_config or ParamConfig()
        self._param_geometry = prep_geometry(self.param_config)
        self._param_state = make_param_state(self.param_config)
        self._param_rules: Dict[int, Tuple[int, float, Dict[int, float]]] = {}
        self._param_free = list(range(self.param_config.max_param_rules - 1, -1, -1))
        # the batched entry's view of the rules: one immutable snapshot of
        # look-up arrays, rebuilt by load_param_rules (_param_tables)
        self._param_lookup = self._param_tables()
        # jitted serve steps by bucket, and what impl resolved to
        self._param_steps: Dict[int, object] = {}
        self._param_kernel: Optional[Tuple[str, str]] = None
        # sketch observability (sentinel_sketch_* series + the `sketch`
        # block of clusterServerStats): the process-wide ServerMetrics pulls
        # through a weakref so a dead service never pins memory; the most
        # recently constructed service is the one scraped
        import weakref

        _self = weakref.ref(self)
        _SM.register_sketch_provider(
            lambda: (lambda s: s.sketch_stats() if s is not None else {})(
                _self()
            )
        )
        # concurrent (semaphore) mode: the plane (cluster.concurrent,
        # engine.concurrent) is allocated by the first load of concurrency
        # rules and never before; its timer ticks the expiry scan when no
        # dispatch does
        self._concurrent_max_tokens = int(concurrent_max_tokens)
        self._conc = None
        self._warmed = False  # warmup() has run: later loads compile at once
        self._conc_timer: Optional[threading.Thread] = None
        self._conc_timer_stop = threading.Event()
        self._conc_timer_closed = False  # close() until reopen()
        self._conc_last_step_ns = 0
        # warm-standby replication hooks (ha.replication): dirty-slot sets
        # collected by the dispatch paths since the last export_delta().
        # None until replication_enable() — the serving hot path pays one
        # `is not None` check when no standby is attached. _state_gen bumps
        # on every rule/param-rule reload: slot assignments (the delta's
        # row keys) are only stable within a generation, so a bump tells
        # the sender to re-bootstrap standbys with a full snapshot.
        self._state_gen = 0
        self._dirty: Optional[Dict[str, set]] = None
        # live-rebalance MOVING set (cluster.rebalance): namespace →
        # (destination "host:port", shard-map epoch). While a namespace is
        # here its flows are masked OUT of every device batch (their rows
        # never count a token — the zero-over-admission invariant) and the
        # materializers overlay TokenStatus.MOVED. _moving_snap is the
        # dispatch-path view: an immutable (mask bool[max_namespaces],
        # epoch int32[max_namespaces]) pair rebuilt under self._lock on
        # every begin/abort/end and rule reload, or None when nothing is
        # moving — the idle hot path pays one `is not None` check.
        self._moving: Dict[str, Tuple[str, int]] = {}
        self._moving_snap: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # wire rev 5 token leases: short-TTL client-local admission slices.
        # A grant charges the whole slice into the LEASED event column of
        # the flow window at grant time (pre-paid — see ClusterEvent.LEASED)
        # and records it here so renew/return can credit unused tokens back.
        # lease_fraction caps each grant at that share of the flow's CURRENT
        # headroom, so k clients racing for leases geometrically share the
        # window instead of the first one draining it; lease_ttl_ms bounds
        # how long a crashed client's slice stays admitted-but-unobserved
        # (the over-admission window). lease_fraction <= 0 disables leasing
        # (every grant answers NOT_LEASABLE).
        self.lease_ttl_ms = max(1, int(lease_ttl_ms))
        self.lease_fraction = float(lease_fraction)
        self._leases: Dict[int, _Lease] = {}
        self._lease_seq = itertools.count(1)
        self._lease_stats = {
            "granted": 0, "renewed": 0, "returned": 0, "revoked": 0,
        }
        _SM.register_lease_provider(
            lambda: (lambda s: s.lease_stats() if s is not None else {})(
                _self()
            )
        )
        # hierarchy tier (cluster/hierarchy.py): when this pod participates
        # in a global flow budget, its share agent pins the UNPROVISIONED
        # remainder of the budget as a LEASED-column hold — local headroom
        # == the pod's share with zero hot-path changes. Entries are
        # (granted_ms, tokens) charges with the same exact-bucket lifecycle
        # as leases; the agent re-tops them every tick because bucket
        # rotation expires them (conservative: a stale hold only
        # under-admits). `hierarchy` is the co-located coordinator, if any;
        # both doors route HIER_TYPES frames to it.
        self._share_holds: Dict[int, List[Tuple[int, int]]] = {}
        self.hierarchy = None
        self.share_agent = None
        _SM.register_hier_provider(
            lambda: (lambda s: s.hier_stats() if s is not None else {})(
                _self()
            )
        )
        # rev-6 outcome plane: the donated completion-scatter step compiles
        # lazily (reports arrive on the clients' cadence, not the serve
        # path's — the first report pays the compile; row counts pad to a
        # geometric shape ladder so retraces stay bounded); host counters
        # back sentinel_outcome_reported_total /
        # sentinel_outcome_dropped_total{reason} and the reconciliation
        # gate. All mutated under self._lock.
        self._outcome_step = None
        # (rung, with br_* columns) pairs the outcome step is compiled for;
        # a report whose pair is missing compiles it first, outside the
        # service lock and before its clock is read (_ensure_outcome_warm)
        self._outcome_warm: set = set()
        self._outcome_warm_lock = threading.Lock()
        self._outcome_seq = 0  # ingests so far, bumped under the lock
        # tallies of issued outcome steps, not yet counted
        # (_settle_outcome_tallies)
        self._outcome_tallies: deque = deque()
        self._outcome_counts: Dict[str, object] = {
            "reported": 0,  # rows accepted and scattered
            "exceptions": 0,  # subset of reported with exc=1
            "rt_sum_ms": 0,  # host-side mirror of the accepted RT mass
            "batches": 0,  # OUTCOME_REPORT frames ingested
            # reason -> count; reasons: negative, too_large, unknown_flow
            "dropped": {},
        }
        _SM.register_outcome_provider(
            lambda: (lambda s: s.outcome_stats() if s is not None else {})(
                _self()
            )
        )
        _SM.register_breaker_provider(
            lambda: (lambda s: s.breaker_stats() if s is not None else {})(
                _self()
            )
        )
        # rev-7 push plane: front doors attach their PushHub here so the
        # service can emit unsolicited server→client frames at the moment
        # server truth changes (lease revoked, breaker flipped, rules
        # reloaded) instead of waiting for clients to poll into it. Emits
        # are fire-and-forget through non-blocking sinks — safe to call
        # under self._lock (see _emit_push).
        self._push_hubs: List[object] = []

    @staticmethod
    def _prep_batch(cfg, slots, acq, pr):
        """Build the device batch; returns ``(order, packed)``: the step's
        one host argument (``pack_requests``, its clock still 0) and the
        grouping order, None when slots arrived ascending-SORTED (stable
        argsort would be the identity) — skipping an O(n log n) sort and
        three fancy-index passes each way. Grouped-but-unsorted input
        still sorts. With :meth:`_lookup_from` the numpy form of the hot
        prep: what runs where the native library is not built, the rare
        re-preps under the lock (rules reloaded, MOVING rows), and the
        reference ``native.lib.flow_prep`` is held to, byte for byte."""
        sorted_already = bool((slots[:-1] <= slots[1:]).all())
        if sorted_already:
            return None, pack_requests(cfg, slots, acq, pr)
        order = np.argsort(slots, kind="stable")
        return order, pack_requests(cfg, slots[order], acq[order], pr[order])

    # -- mesh placement -----------------------------------------------------
    def _place_state(self, state):
        if self.mesh is None:
            return state
        from sentinel_tpu.parallel.sharding import shard_state

        return shard_state(state, self.mesh)

    def _place_rules(self, table):
        if self.mesh is None:
            return table
        from sentinel_tpu.parallel.sharding import shard_rules

        return shard_rules(table, self.mesh)

    def _step_fn(self, bucket: int, uniform: bool):
        """The device step for one (shape bucket, uniform) variant —
        single-shard ``decide`` or the mesh-sharded shard_map step —
        called ``step(state, rules, packed)`` with ONE host argument, the
        packed request batch and clock of ``pack_requests``.

        Cached per variant for BOTH paths: a fresh closure + fresh config
        object per call would route every dispatch through pjit's slow
        Python cache-miss path (~1ms/call on CPU — measured; the C++
        fast path keys on the callable identity), which at serving rates
        costs more than the kernel itself.

        BOTH steps DONATE the state buffers: every serving step
        scatter-updates the full [max_flows, buckets, events] window
        tensors, and without donation XLA must copy them first (measured
        22% of the 64-bucket step at 100k flows on CPU; on TPU it is HBM
        traffic and allocator churn — and under a mesh the copy is paid
        per shard, every dispatch). Safe because the service lock makes
        `self._state, verdicts = step(self._state, …)` the only reader of
        the old buffer, and warmup feeds throwaway states. If a dispatch
        ever raises AFTER consuming its donated input, later steps fail
        loudly with a donated-buffer error (visible, not silent)."""
        key = (bucket, uniform)
        step = self._sharded_steps.get(key)
        if step is not None:
            return step
        cfg = self.config._replace(batch_size=bucket)
        if self.mesh is None:
            from sentinel_tpu.engine.decide import decide_donating

            step = decide_donating(cfg, grouped=True, uniform=uniform)
        else:
            from sentinel_tpu.parallel.sharding import make_sharded_decide

            step = make_sharded_decide(
                cfg, self.mesh, grouped=True, uniform=uniform, donate=True
            )
        self._sharded_steps[key] = step
        return step

    def _fused_step_fn(self, depth: int, uniform: bool):
        """The chained multi-frame device step for one (scan depth, uniform)
        variant — ``lax.scan`` of the donated-state step over ``depth``
        stacked full-``batch_size`` frames. Cached per variant for the same
        reason as :meth:`_step_fn` (fresh closures would route every fused
        dispatch through pjit's slow path). Under a mesh the scan runs
        inside one ``shard_map`` entry and psum-stitches each frame's
        verdicts before the next frame decides — same per-frame semantics,
        one dispatch."""
        key = (depth, uniform)
        step = self._fused_steps.get(key)
        if step is not None:
            return step
        if self.mesh is None:
            from sentinel_tpu.engine.decide import decide_fused_donating

            step = decide_fused_donating(
                self.config, depth, grouped=True, uniform=uniform
            )
        else:
            from sentinel_tpu.parallel.sharding import make_sharded_decide

            step = make_sharded_decide(
                self.config, self.mesh, grouped=True, uniform=uniform,
                donate=True, depth=depth,
            )
        self._fused_steps[key] = step
        return step

    def _fused_block_pool(self, depth: int):
        """The staging freelist for one scan depth (lazily built)."""
        pool = self._fused_staging.get(depth)
        if pool is None:
            from sentinel_tpu.cluster.protocol import StagingPool
            from sentinel_tpu.engine.decide import alloc_packed_block

            pool = self._fused_staging.setdefault(
                depth,
                StagingPool(
                    partial(alloc_packed_block, self.config, depth),
                    capacity=8,
                ),
            )
        return pool

    # -- rule management (ClusterFlowRuleManager analog) --------------------
    def load_rules(
        self,
        rules: List[ClusterFlowRule],
        ns_max_qps: Optional[float] = None,
        connected: Optional[Dict[str, int]] = None,
    ) -> None:
        with self._rules_mutex, self._lock:
            if ns_max_qps is not None:
                self._ns_max_qps = ns_max_qps
            if connected is not None:
                self._connected.update(connected)
            by_ns: Dict[str, Dict[int, ClusterFlowRule]] = {}
            for r in rules:
                by_ns.setdefault(r.namespace, {})[r.flow_id] = r
            self._rules_by_ns = by_ns
            self._rule_of = {r.flow_id: r for r in rules}
            degrade = list(self._degrade_rules_src.values())
            table, self._index = build_rule_table(
                self.config, rules, index=self._index,
                ns_max_qps=self._ns_max_qps, connected=self._connected,
                degrade_rules=degrade,
            )
            self._table = self._place_rules(table)
            # breaker bookkeeping: which slots carry a breaker (dirty-set
            # and lease gating) and a fresh transition-scan mirror — slot
            # assignments may have moved, so the old mirror is meaningless
            self._has_breakers = bool(degrade)
            self._breaker_fid = {
                self._index.slot_of[d.flow_id]: d.flow_id for d in degrade
                if d.flow_id in self._index.slot_of
            }
            self._breaker_slots = set(self._breaker_fid)
            self._breaker_prev = None
            # re-place after the drain scatter: eager sharding propagation
            # through .at[].set isn't guaranteed to keep the flow layout
            self._state = self._place_state(
                drain_pending_clear(self._index, self._state)
            )
            items = sorted(self._index.slot_of.items())
            self._lookup = (
                np.fromiter((k for k, _ in items), np.int64, len(items)),
                np.fromiter((v for _, v in items), np.int32, len(items)),
            )
            # rebuild the slot → namespace snapshot for the verdict counters
            # (ns_of rows persist across reloads, so removed namespaces keep
            # their index; only live rules point at them)
            n_ns = max(self._index.ns_of.values(), default=-1) + 1
            ns_names = [""] * n_ns
            for ns_name, row in self._index.ns_of.items():
                ns_names[row] = ns_name
            slot_ns = np.full(self.config.max_flows + 1, -1, np.int32)
            for r in rules:
                slot_ns[self._index.slot_of[r.flow_id]] = (
                    self._index.ns_of[r.namespace]
                )
            self._ns_snapshot = (tuple(ns_names), slot_ns)
            # a reload can introduce rules (hence slots) for a namespace
            # that is mid-move; refresh the dispatch-path MOVING view so
            # those new slots are masked too
            self._rebuild_moving_snap()
            # slot assignments may have moved: deltas collected against the
            # old generation are meaningless, so drop them and force the
            # replication sender into a full-snapshot resync
            self._state_gen += 1
            if self._dirty is not None:
                self._dirty = state_codec.fresh_dirty()
            # leases pin flow_id → slot; a reload may have reassigned the
            # slot or dropped the rule, so re-resolve every outstanding
            # lease and revoke those whose rule vanished (their LEASED
            # charge simply expires with the window — conservative)
            dead = []
            if self._leases:
                for lid, lease in self._leases.items():
                    slot = self._index.slot_of.get(lease.flow_id)
                    if slot is None:
                        dead.append(lease)
                    else:
                        lease.slot = int(slot)
                for lease in dead:
                    del self._leases[lease.lease_id]
                self._lease_stats["revoked"] += len(dead)
            gen = self._state_gen
        # rev-7 push, emitted after the rule locks drop: recall the leases
        # the reload killed and invalidate client-cached rule-derived state
        # (backoffs, cached NO_RULE answers) within one RTT instead of a
        # TTL — the generation bump above is the epoch clients fence on
        for lease in dead:
            self._emit_push(
                "push_lease_revoke", lease.lease_id, lease.flow_id,
                lease.tokens,
            )
        self._emit_push("push_rule_epoch", gen)

    def load_namespace_rules(
        self, namespace: str, rules: List[ClusterFlowRule]
    ) -> None:
        """Replace ONE namespace's flow rules, keeping every other
        namespace's intact (``ClusterFlowRuleManager.loadRules(namespace,
        rules)`` — the shape the cluster/server/modifyFlowRules command
        edits)."""
        import dataclasses as _dc

        # replace() keeps every field (including the shaping knobs) — a
        # positional rebuild here would silently strip control_behavior
        fixed = [
            r if r.namespace == namespace
            else _dc.replace(r, namespace=namespace)
            for r in rules
        ]
        with self._rules_mutex:
            with self._lock:
                merged = {
                    ns: dict(m) for ns, m in self._rules_by_ns.items()
                    if ns != namespace
                }
                if fixed:
                    merged[namespace] = {r.flow_id: r for r in fixed}
                flat = [r for m in merged.values() for r in m.values()]
            self.load_rules(flat)

    def current_rules(
        self, namespace: Optional[str] = None
    ) -> List[ClusterFlowRule]:
        with self._lock:
            if namespace is not None:
                return list(self._rules_by_ns.get(namespace, {}).values())
            return [
                r for m in self._rules_by_ns.values() for r in m.values()
            ]

    # -- degrade (circuit-breaker) rules (DegradeRuleManager analog) --------
    def load_degrade_rules(self, rules: List[DegradeRule]) -> None:
        """Replace the full degrade-rule set. Rules compile into the
        ``br_*`` rule-table columns next to the flow rules (one table, one
        gather on the hot path); a flow may carry a breaker with or without
        a flow rule — breaker-only flows get an effectively-unlimited slot
        so the gate still sees them. Breaker STATE survives the reload for
        flows whose rule persists (the state columns are keyed by slot and
        slots are sticky across reloads); a removed rule's slot resets to
        CLOSED via ``drain_pending_clear``."""
        with self._rules_mutex:
            with self._lock:
                self._degrade_rules_src = {r.flow_id: r for r in rules}
            self.load_rules(self.current_rules())

    def load_namespace_degrade_rules(
        self, namespace: str, rules: List[DegradeRule]
    ) -> None:
        """Replace ONE namespace's degrade rules, keeping the others (the
        same shape as :meth:`load_namespace_rules`; the MOVE import path
        uses this to land a namespace's breakers on the destination)."""
        import dataclasses as _dc

        fixed = [
            r if r.namespace == namespace
            else _dc.replace(r, namespace=namespace)
            for r in rules
        ]
        with self._rules_mutex:
            with self._lock:
                keep = [
                    r for r in self._degrade_rules_src.values()
                    if r.namespace != namespace
                ]
            self.load_degrade_rules(keep + fixed)

    def current_degrade_rules(
        self, namespace: Optional[str] = None
    ) -> List[DegradeRule]:
        with self._lock:
            rules = list(self._degrade_rules_src.values())
        if namespace is not None:
            rules = [r for r in rules if r.namespace == namespace]
        return rules

    def served_namespaces(self) -> List[str]:
        """Explicit namespace set ∪ namespaces with loaded rules."""
        with self._lock:
            return sorted(self.namespace_set | set(self._rules_by_ns))

    def set_max_allowed_qps(self, qps: float) -> None:
        """Dynamic ``ServerFlowConfig.maxAllowedQps`` update — rebuilds the
        namespace-guard row of the rule table without retracing."""
        with self._rules_mutex:
            self.load_rules(self.current_rules(), ns_max_qps=float(qps))

    def config_snapshot(self) -> Dict[str, object]:
        """Flow-config view (cluster/server/fetchConfig shape)."""
        from sentinel_tpu.engine.state import flow_spec

        spec = flow_spec(self.config)
        return {
            "exceedCount": self.config.exceed_count,
            "maxOccupyRatio": self.config.max_occupy_ratio,
            "intervalMs": spec.interval_ms,
            "sampleCount": self.config.n_buckets,
            "maxAllowedQps": self._ns_max_qps,
            "maxFlows": self.config.max_flows,
            "batchSize": self.config.batch_size,
            "namespaceSet": self.served_namespaces(),
        }

    def connected_count_changed(self, namespace: str, n: int) -> None:
        """``ConnectionManager`` callback: AVG_LOCAL thresholds scale with it.
        Counts persist across rule reloads. Namespaces no rule uses are
        remembered host-side but allocate no device slot."""
        with self._lock:
            self._connected[namespace] = max(1, int(n))
            if self._conc is not None:
                self._conc.connected_changed(namespace, self._connected)
            ns = self._index.ns_of.get(namespace)
            if ns is None:
                return  # no rule in this namespace yet; applied on next load
            conn = np.array(self._table.ns_connected)  # writable copy
            conn[ns] = max(1, int(n))
            self._table = self._place_rules(
                self._table._replace(ns_connected=jnp.asarray(conn))
            )

    # -- time ---------------------------------------------------------------
    # int32 engine-ms wraps after ~24.8 days; re-base well before that.
    # Callers hold self._lock.
    _REBASE_AFTER_MS = 2**30  # ~12.4 days

    def _engine_now(self) -> int:
        """Engine-relative int32 ms; automatically re-bases the epoch (and
        shifts all window starts) long before int32 wraparound."""
        wall = _clock.now_ms()
        if self._epoch_ms is None:
            self._epoch_ms = wall - 1  # keep engine time strictly positive
        now = wall - self._epoch_ms
        if now > self._REBASE_AFTER_MS:
            delta = now - 60_000  # keep the last minute of history addressable
            state_codec.rebase(self, delta)
            self._epoch_ms += delta
            now -= delta
        return now

    # -- decision path ------------------------------------------------------
    def warmup(self) -> None:
        """Trigger XLA compilation of the decision kernels before serving.

        First-compile latency (~1s on CPU, tens of seconds on TPU) must not be
        paid by the first real request — it would blow the 20ms client budget
        *and* let early traffic slip through an expired window."""
        _SM.set_warm(False)
        with self._lock:
            now = self._engine_now()
            # compile both serving variants (uniform acquire and mixed) for
            # every shape bucket the serving path can pick (mesh-sharded
            # variants when this service runs over a pod mesh). ONE
            # throwaway state threads through every variant: the
            # single-shard step donates its state argument (passing the
            # live self._state would invalidate it), and since each step
            # returns a same-shaped state, chaining keeps warmup at a
            # single extra state allocation instead of one per variant.
            ws = self._place_state(make_state(self.config))
            compiles = 0
            for bucket in self._serve_buckets:
                cfg = self.config._replace(batch_size=bucket)
                packed = pack_requests(cfg, [-1], now=now)
                for uniform in (True, False):
                    step = self._step_fn(bucket, uniform)
                    ws, _ = step(ws, self._table, packed)
                    compiles += 1
            # fused multi-frame variants (full batch_size frames only):
            # compile the ladder's scan depths so the first oversized pull
            # doesn't pay scan compilation while holding the service lock.
            # Single-shard warms the uniform-acquire common case only
            # (mixed-acquire fused spans are rare and compile lazily);
            # under a mesh, warm EVERY (depth, uniform) sharded-fused
            # bucket — mesh compiles are far slower, and a cold bucket in
            # the serving window would stall the whole pod's device lane.
            fused_uniforms = (True,) if self.mesh is None else (True, False)
            base = pack_requests(self.config, [-1], now=now)
            for fdepth in self._fuse_depths:
                block = np.stack([base] * fdepth, axis=1)
                for uniform in fused_uniforms:
                    step = self._fused_step_fn(fdepth, uniform)
                    ws, _ = step(ws, self._table, block)
                    compiles += 1
            # the outcome step, every rung of its padding ladder, where
            # degrade rules are loaded (the decide programs above carry the
            # breaker arm then, by the table's br_* columns): a breaker reads
            # a report by the bucket of its clock, and a first report that
            # compiled its step was seconds late for that. A table without
            # breakers leaves the step to its first report
            # (_ensure_outcome_warm: outside the lock, before the clock).
            if self._has_breakers:
                ws = self._warm_outcome_steps(ws, self._table, now)
                compiles += len(self._outcome_rungs())
            # compile counts on the cluster stat log: a serving window
            # that shows more compiles than warmup recorded hit a cold
            # bucket (shape drift, ladder change) — visible, not silent.
            log_cluster("warmup_step_compiles", count=compiles)
            # the hot-parameter step, every serve bucket, on a throwaway
            # sketch (the step donates its state)
            self.param_impl()
            ps = make_param_state(self.param_config, flat=True)
            for bucket in self._serve_buckets:
                packed = pack_param_rows(
                    self.param_config, bucket, (), (), (),
                    np.zeros((0, self.param_config.depth), np.int32),
                    np.zeros((0, self.param_config.slim_depth), np.int32),
                    now, 1, 0,
                )
                ps, verdicts = self._param_step_fn(bucket)(ps, packed)
            jax.block_until_ready(verdicts)
            del ps
            # the concurrency step, every serve bucket, on a throwaway
            # plane: where concurrency rules are loaded, and only there
            if self._conc is not None:
                self._warm_concurrent_steps(self._conc, now)
        # from here on a compile is one in front of live traffic: counted
        # (compiles_after_warmup_total) and logged by name
        self._warmed = True
        _SM.set_warm(True)

    def _warm_concurrent_steps(self, plane, now: int = 0) -> None:
        """Compile the concurrency step of every serve bucket on a
        throwaway plane (the step donates its state)."""
        from sentinel_tpu.engine import concurrent as _CE

        cs = _CE.make_concurrent_state(plane.config)
        for bucket in self._serve_buckets:
            cs, verdicts = plane.step_fn(bucket)(
                cs, _CE.pack_concurrent_rows(bucket, (), (), (), (), now))
        jax.block_until_ready(verdicts)

    def request_token(self, flow_id, acquire=1, prioritized=False) -> TokenResult:
        return self.request_batch([(flow_id, acquire, prioritized)])[0]

    def lookup_slots(self, flow_ids: np.ndarray) -> np.ndarray:
        """Vectorized flow_id → slot (-1 when no rule). Lock-free: reads one
        immutable (keys, slots) snapshot."""
        return self._lookup_from(self._lookup, flow_ids)

    @staticmethod
    def _lookup_from(snapshot, flow_ids: np.ndarray) -> np.ndarray:
        keys, slots = snapshot
        if keys.size == 0:
            return np.full(flow_ids.shape, -1, np.int32)
        pos = np.searchsorted(keys, flow_ids)
        pos = np.minimum(pos, keys.size - 1)
        return np.where(keys[pos] == flow_ids, slots[pos], -1).astype(np.int32)

    def request_batch_arrays(
        self,
        flow_ids: np.ndarray,
        acquires: Optional[np.ndarray] = None,
        prios: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Array-in/array-out decision path: (status int8[N], remaining
        int32[N], wait_ms int32[N]) in request order.

        Dispatch + materialize in one call; pipelining callers use
        :meth:`dispatch_batch_arrays` directly.
        """
        return self.dispatch_batch_arrays(flow_ids, acquires, prios)()

    def dispatch_batch_arrays(
        self,
        flow_ids: np.ndarray,
        acquires: Optional[np.ndarray] = None,
        prios: Optional[np.ndarray] = None,
    ):
        """Serving hot path, phase 1: host prep + device dispatch. Returns a
        zero-arg **materializer** (a :class:`Materializer`: read half, then
        account half) that blocks on the async dispatch and yields
        ``(status, remaining, wait)`` in request order.

        The service lock covers ONLY the device dispatch + state swap — host
        prep (slot lookup, grouping sort, batch padding) runs before it and
        verdict materialization after it (the lock-free analog of the
        reference's unsynchronized ``ClusterFlowChecker.java:55-120`` hot
        loop). Because JAX dispatch is asynchronous and consecutive steps
        chain on-device through the state future, a caller that dispatches
        batch k+1 before materializing batch k keeps the device busy end to
        end — the serving-path analog of the netty pipeline that amortizes
        the reference's per-RPC cost (``NettyTransportServer.java:73-101``).
        Oversized bursts are split into per-bucket chunks whose dispatches
        are ALL issued before any chunk materializes, so one big pull
        pipelines internally too; runs of full-``batch_size`` chunks are
        additionally FUSED into single chained device steps (see
        :meth:`_dispatch_oversized`) so the fixed per-dispatch overhead is
        paid once per fused group instead of once per frame.
        """
        if _chaos.ARMED:  # device_stall injection: a slow/preempted step
            _chaos.maybe_sleep("device_stall")
        t_enter = time.monotonic_ns()
        flow_ids = np.asarray(flow_ids, np.int64)
        n = flow_ids.shape[0]
        if n == 0:
            def _empty():
                empty32 = np.empty(0, np.int32)
                return np.empty(0, np.int8), empty32, empty32

            return _empty
        acq = (
            np.ones(n, np.int32) if acquires is None
            else np.asarray(acquires, np.int32)
        )
        pr = (
            np.zeros(n, bool) if prios is None
            else np.asarray(prios, bool)
        )
        cap = self.config.batch_size
        if n > cap:  # split oversized bursts; dispatch all chunks first
            return self._dispatch_oversized(flow_ids, acq, pr, n, cap)
        # -- host prep, outside the lock --
        lookup_snap = self._lookup
        # serving fast path: group same-flow requests contiguously (stable,
        # so greedy admission order within a flow is arrival order) and
        # detect the uniform-acquire common case — together they skip the
        # device argsort and the iterative admission refinement (see
        # decide()'s grouped/uniform flags). ``packed`` is allocated for
        # this dispatch and written by nobody once the clock is in: the
        # step may still be reading it when the next frame is prepped (the
        # CPU backend aliases an aligned numpy argument outright, on a mesh
        # too; the TPU's runtime has copied it when the call returns, one
        # chip and a 2x2 mesh alike: benchmarks/arg_overwrite_drill.py,
        # PERF.md section 6, PR 43). No pool and no per-thread output array
        # may ever stand here
        # smallest compiled shape bucket that fits this batch
        bucket = next(b for b in self._serve_buckets if n <= b)
        cfg = self.config._replace(batch_size=bucket)
        prep = _native.flow_prep(lookup_snap, flow_ids, acq, pr, bucket)
        if prep is None:  # the library is not built: the same in numpy
            uniform = bool(acq.min() == acq.max())
            slots = self._lookup_from(lookup_snap, flow_ids)
            order, packed = self._prep_batch(cfg, slots, acq, pr)
        else:
            slots, order, packed, uniform = prep
        step = self._step_fn(bucket, uniform)
        slots_ns = slots  # pre-mask slots: verdict→namespace attribution
        moved_mask = moved_epochs = None
        t_prep = time.monotonic_ns()
        # -- device step: the only serialized section --
        with self._lock:
            t_locked = time.monotonic_ns()
            seq = self._dispatch_seq = self._dispatch_seq + 1
            if self._lookup is not lookup_snap:
                # rules reloaded between prep and step: slot assignments
                # may have moved, so redo the slot-dependent prep against
                # the live table (rare, and still under the lock — the
                # same atomicity load_rules callers had before the
                # narrowing)
                slots = self._lookup_from(self._lookup, flow_ids)
                slots_ns = slots
                order, packed = self._prep_batch(cfg, slots, acq, pr)
            mv = self._moving_snap
            if mv is not None:
                # live rebalance: rows of a MOVING namespace are masked
                # out of the device batch — their counters never move
                # (the zero-over-admission half of the lossless move) —
                # and the materializer overlays MOVED. Checked under the
                # lock so a begin_move strictly orders against every
                # dispatch.
                moved_mask, moved_epochs = self._moving_mask_for(slots, mv)
                if moved_mask is not None:
                    slots = np.where(
                        moved_mask, np.int32(-1), slots
                    ).astype(np.int32)
                    order, packed = self._prep_batch(cfg, slots, acq, pr)
            # the clock rides the one host argument; read under the lock,
            # written into an array only this dispatch holds
            packed[ROW_HEAD, HEAD_NOW] = self._engine_now()
            self._state, verdicts = step(self._state, self._table, packed)
            if self._dirty is not None:
                touched = np.unique(slots[slots >= 0]).tolist()
                self._dirty["flow"].update(touched)
                if self._has_breakers:
                    # breaker transitions only happen for batched rows, so
                    # touched ∩ breaker-slots is exactly the dirty set
                    self._dirty.setdefault("breaker", set()).update(
                        s for s in touched if s in self._breaker_slots
                    )
        # the verdicts' one copy to the host starts now, behind the step on
        # the device's queue, not when a reply lane gets round to asking
        verdicts.copy_to_host_async()
        self._dispatched(t_enter, t_prep, t_locked, seq, n,
                         native_prep=prep is not None)

        def _read():
            # blocks on the async dispatch; runs outside the lock
            t_mat = time.monotonic_ns()
            t_ready, host = self._read_verdicts(verdicts)
            status, wait, remaining = unpack_verdicts(host, n, order)
            arms = unpack_arms(host)
            if moved_mask is not None:
                # MOVED overlay: the device saw these rows as no-rule; the
                # client sees a redirect carrying the shard-map epoch
                status[moved_mask] = np.int8(int(TokenStatus.MOVED))
                remaining[moved_mask] = moved_epochs[moved_mask]
                wait[moved_mask] = 0
                from sentinel_tpu.metrics.ha import ha_metrics
                ha_metrics().count_rebalance_redirects(
                    int(moved_mask.sum())
                )
            return (status, remaining, wait), partial(
                self._account, status, wait, slots_ns, seq, n, t_enter,
                t_mat, t_ready, time.monotonic_ns(), arms=arms,
            )

        return Materializer(_read)

    @staticmethod
    def _read_verdicts(packed):
        """A materializer's ONE blocking device-to-host read: the
        dispatch's packed verdict buffer, whose copy was started at launch,
        as host ``int32[3, rows]`` (a fused span's frames laid end to end),
        with the ``monotonic_ns`` stamp of its arrival."""
        ready = packed.is_ready()
        host = np.asarray(packed)
        t_ready = time.monotonic_ns()
        _SM.count_verdict_read(ready)
        return t_ready, host.reshape(3, -1)

    def _dispatched(self, t_enter, t_prep, t_locked, seq, rows,
                    lane: int = 0, native_prep: bool = False) -> None:
        """One dispatch left the service lock: its three dispatch-side
        phases (``monotonic_ns`` stamps of entry, prep done, lock acquired)
        go to the always-on histograms and, armed, to the flight recorder
        with the same stamps. ``native_prep``: a dispatch whose prep was
        the native pass (``prep_native_total``, of the flow lane;
        ``param_prep_native_total``, of the hot-parameter lane;
        ``concurrent_prep_native_total``, of the concurrency lane)."""
        t_out = time.monotonic_ns()
        _SM.prep_ms.record((t_prep - t_enter) * 1e-6)
        if native_prep:
            _SM.count_prep_native(lane)
        _SM.lock_wait_ms.record((t_locked - t_prep) * 1e-6)
        _SM.launch_ms.record((t_out - t_locked) * 1e-6)
        if _TR.ARMED:
            sid, aux = self._trace_sid, seq & 0x7FFFFFFF
            _TR.record(_TR.PREP, shard=sid, aux=aux, t_ns=t_prep)
            _TR.record(_TR.LOCKED, shard=sid, aux=aux, t_ns=t_locked)
            _TR.record(_TR.DEVICE_IN, shard=lane, aux=rows, t_ns=t_out)

    def _account(
        self, status, wait, slots_ns, seq, rows, t_enter, t_mat, t_ready,
        t_fetched, replied: bool = False, lane: int = 0, arms=None,
    ) -> None:
        """The account half of a :class:`Materializer`, and the record of
        its three phases. Nothing here is in a reply: whoever calls the
        materializer whole runs it before the call returns, the native
        reply lane after the dispatch's reply was submitted (``replied``;
        counted in ``reply_first_total``), so there a reader of the
        counters may assume nothing until the lane has counted.
        ``slots_ns`` is request-order and PRE-mask, so MOVED verdicts land
        on their namespace (a fused span passes its frames' slots as a
        list, a param dispatch None); ``t_enter``/``t_mat``/``t_ready``/
        ``t_fetched`` are the ``monotonic_ns`` stamps of the dispatch's
        entry, the read half's entry, the verdict buffer reaching the host
        and the read half's end: the decision latency the SLO plane gets
        ends there, and ``account_ms`` counts from this call's own start.
        ``arms`` is what a flow dispatch's step said of its cond-gated arms
        (``unpack_arms``). The dispatch is counted here and fanned out per
        namespace later (``ServerMetrics.record_verdict_batch``)."""
        t_account = time.monotonic_ns()
        if isinstance(slots_ns, list):
            slots_ns = np.concatenate(slots_ns)
        # per-namespace verdict counters (sentinel_server_verdicts_total):
        # attribute each request's verdict to its rule's namespace via the
        # lock-free slot→namespace snapshot (slot -1 reads its last entry)
        if slots_ns is None:  # a param dispatch: no flow slots
            ns_idx, ns_names = None, ()
        else:
            ns_names, slot_ns = self._ns_snapshot
            ns_idx = slot_ns[slots_ns]
        by_code = _SM.record_verdict_batch(
            status, ns_idx, ns_names,
            latency_ms=(t_fetched - t_enter) * 1e-6,
            wait_ms=wait,
        )
        live = 0
        if arms is not None:
            live = int(arms[ARM_LIVE])
            _SM.count_decide_arms(
                rows, live & ARM_SHAPING, live & ARM_PACING,
                live & ARM_OCCUPY, int(arms[ARM_SHAPED_ROWS]),
                int(arms[ARM_PACED_ROWS]), int(arms[ARM_PRIORITIZED_ROWS]),
                breaker=(live & ARM_BREAKER, int(arms[ARM_GUARDED_ROWS]),
                         int(arms[ARM_DEGRADED_ROWS]),
                         int(arms[ARM_PROBES]), int(arms[ARM_TO_OPEN])),
            )
        if _TR.ARMED:  # flight recorder: verdicts on the host and counted
            sid, aux = self._trace_sid, seq & 0x7FFFFFFF
            _TR.record(_TR.READY, shard=sid, aux=aux, t_ns=t_ready)
            _TR.record(_TR.FETCHED, shard=sid, aux=aux, t_ns=t_fetched)
            _TR.record(_TR.ACCOUNT, shard=sid, aux=aux, t_ns=t_account)
            _TR.record(_TR.DEVICE_OUT, aux=rows,
                       shard=lane | live << _TR.ARM_SHIFT)
        # cluster server stat log (ClusterServerStatLogUtil analog): one
        # aggregated counter per verdict class per window
        n_degraded = 0
        if by_code is not None:
            by_code = by_code.tolist()
            for event, code in _STAT_LOG_EVENTS:
                if by_code[code]:
                    log_cluster(event, count=by_code[code])
            n_degraded = by_code[TokenStatus.DEGRADED]
        if n_degraded:
            # breaker activity observed: fold the device transitions
            # into the host transition counters / blackbox plane
            self._breaker_scan()
        _SM.device_wait_ms.record((t_ready - t_mat) * 1e-6)
        _SM.fetch_ms.record((t_fetched - t_ready) * 1e-6)
        _SM.account_ms.record((time.monotonic_ns() - t_account) * 1e-6)
        if replied:
            _SM.count_reply_first()

    def _dispatch_oversized(self, flow_ids, acq, pr, n, cap):
        """Split an oversized burst into ``cap``-sized frames and fold runs
        of FULL frames into fused chained device steps — greedy largest-fit
        over the fusion ladder (``fuse_depths``), so e.g. 7 full frames with
        ladder (8, 4, 2) dispatch as scan(4) + scan(2) + 1 plain step. The
        fixed per-dispatch overhead is then paid once per fused group
        instead of once per frame. Leftovers and sub-``cap`` tails take the ordinary per-chunk
        path. As before, ALL dispatches are issued before any chunk
        materializes, so one big pull pipelines internally. The ladder runs
        identically over a mesh — the fused step is then one ``shard_map``
        entry scanning the sharded step (psum stitch per frame), and the
        staging/prep machinery below is mesh-oblivious by construction.
        """
        mats = []
        pos = 0
        ladder = self._fuse_depths
        while ladder and (n - pos) // cap >= ladder[-1]:
            depth = next(
                (d for d in ladder if d <= (n - pos) // cap), None
            )
            if depth is None:
                break
            end = pos + depth * cap
            mats.append(
                self._dispatch_fused(
                    flow_ids[pos:end], acq[pos:end], pr[pos:end], depth, cap
                )
            )
            pos = end
        for i in range(pos, n, cap):
            mats.append(
                self.dispatch_batch_arrays(
                    flow_ids[i : i + cap], acq[i : i + cap], pr[i : i + cap]
                )
            )

        def _read():
            parts = [m.read() for m in mats]

            def account(replied):
                for m in mats:
                    m.account(replied)

            return tuple(np.concatenate(ps) for ps in zip(*parts)), account

        return Materializer(_read)

    def _dispatch_fused(self, flow_ids, acq, pr, depth, cap):
        """Phase-1 dispatch of ``depth`` consecutive full-``cap`` frames as
        ONE chained device step (``lax.scan`` of the donated-state step —
        see :func:`decide_fused_donating`). Returns a materializer yielding
        request-order ``(status, remaining, wait)`` for the whole span.

        Each frame is prepped independently (slot lookup + grouping sort)
        and its packed request lines written straight into row ``f`` of ONE
        ``[lines, depth, cap]`` staging block, the step's one host
        argument; the single device call then replaces ``depth``
        dispatches. The fused group shares one ``now`` — frames in one
        pull arrived together, so this only collapses sub-millisecond
        clock skew a per-frame loop would have read anyway.
        """
        t_enter = time.monotonic_ns()
        lookup_snap = self._lookup
        # a fused span is uniform only if acquire is constant across ALL its
        # frames; mixed spans scan the general (refining) body for every
        # frame, which is still correct for the uniform ones among them
        uniform = bool(acq.min() == acq.max())
        pool = self._fused_block_pool(depth)
        block = pool.acquire()
        frames = [slice(f * cap, (f + 1) * cap) for f in range(depth)]

        def _restage(f, slots_f):
            # the numpy prep, straight into the staging rows: the rare
            # re-preps under the lock, and every frame where the native
            # library is not built
            sl = frames[f]
            if bool((slots_f[:-1] <= slots_f[1:]).all()):
                order_f = None
                pack_requests_into(block, f, slots_f, acq[sl], pr[sl])
            else:
                order_f = np.argsort(slots_f, kind="stable")
                pack_requests_into(
                    block, f, slots_f[order_f], acq[sl][order_f],
                    pr[sl][order_f],
                )
            return slots_f, order_f

        # (slots, order) a frame; the zero-alloc replacement for a
        # per-dispatch np.stack: the head line stays the block's own
        preps, native_prep = [], True
        for f, sl in enumerate(frames):
            prep = _native.flow_prep(
                lookup_snap, flow_ids[sl], acq[sl], pr[sl], cap,
                out=block[:, f],
            )
            if prep is None:  # the library is not built: the same in numpy
                native_prep = False
                prep = _restage(
                    f, self._lookup_from(lookup_snap, flow_ids[sl])
                )
            preps.append(prep[:2])
        step = self._fused_step_fn(depth, uniform)
        moved_span = moved_epochs_span = span_ns = None
        t_prep = time.monotonic_ns()
        # -- device step: the only serialized section --
        with self._lock:
            t_locked = time.monotonic_ns()
            seq = self._dispatch_seq = self._dispatch_seq + 1
            if self._lookup is not lookup_snap:
                # rules reloaded between prep and step (see
                # dispatch_batch_arrays): redo slot-dependent prep against
                # the live table
                preps = [
                    _restage(
                        f, self._lookup_from(self._lookup, flow_ids[sl])
                    )
                    for f, sl in enumerate(frames)
                ]
            mv = self._moving_snap
            if mv is not None:
                # live rebalance (see dispatch_batch_arrays): mask MOVING-
                # namespace rows out of every staged frame so the fused
                # step never counts their tokens, and remember the span
                # mask for the MOVED overlay
                span0 = np.concatenate([p[0] for p in preps])
                m, eps = self._moving_mask_for(span0, mv)
                if m is not None:
                    moved_span, moved_epochs_span, span_ns = m, eps, span0
                    preps = [
                        _restage(
                            f,
                            np.where(
                                m[sl], np.int32(-1), span0[sl]
                            ).astype(np.int32),
                        )
                        for f, sl in enumerate(frames)
                    ]
            block[ROW_HEAD, 0, HEAD_NOW] = self._engine_now()
            self._state, verdicts = step(self._state, self._table, block)
            if self._dirty is not None:
                span = np.concatenate([p[0] for p in preps])
                touched = np.unique(span[span >= 0]).tolist()
                self._dirty["flow"].update(touched)
                if self._has_breakers:
                    self._dirty.setdefault("breaker", set()).update(
                        s for s in touched if s in self._breaker_slots
                    )
        verdicts.copy_to_host_async()  # see dispatch_batch_arrays
        self._dispatched(t_enter, t_prep, t_locked, seq, depth * cap,
                         native_prep=native_prep)
        _SM.record_fused(depth)
        if _TR.ARMED:  # flight recorder: fused group submitted
            _TR.record(_TR.FUSE, aux=depth)

        def _read():
            # blocks on the async dispatch; runs outside the lock. The
            # buffer is [3, depth, cap] with the frames already contiguous
            # along the span, so the per-frame grouping sorts are undone as
            # ONE span-wide order.
            t_mat = time.monotonic_ns()
            t_ready, host = self._read_verdicts(verdicts)
            # verdicts are ready → the device has consumed the staging
            # block's host buffer; recycle it for the next fused group
            pool.release(block)
            total = depth * cap
            span_order = None
            if any(p[1] is not None for p in preps):
                span_order = np.concatenate([
                    np.arange(f * cap, (f + 1) * cap) if order_f is None
                    else order_f + f * cap
                    for f, (_s, order_f) in enumerate(preps)
                ])
            status, wait, remaining = unpack_verdicts(
                host, order=span_order
            )
            arms = unpack_arms(host, depth)
            if moved_span is not None:
                status[moved_span] = np.int8(int(TokenStatus.MOVED))
                remaining[moved_span] = moved_epochs_span[moved_span]
                wait[moved_span] = 0
                from sentinel_tpu.metrics.ha import ha_metrics
                ha_metrics().count_rebalance_redirects(
                    int(moved_span.sum())
                )
            # per-namespace verdict counters + cluster stat log, once for
            # the whole span; span_ns is the PRE-mask slot span when a move
            # masked rows
            return (status, remaining, wait), partial(
                self._account, status, wait,
                span_ns if span_ns is not None else [p[0] for p in preps],
                seq, total, t_enter, t_mat, t_ready, time.monotonic_ns(),
                arms=arms,
            )

        return Materializer(_read)

    def request_batch(self, requests) -> List[TokenResult]:
        if not requests:
            return []
        n = len(requests)
        flow_ids = np.fromiter((f for f, _, _ in requests), np.int64, n)
        acquires = np.fromiter((a for _, a, _ in requests), np.int32, n)
        prios = np.fromiter((p for _, _, p in requests), bool, n)
        status, remaining, wait = self.request_batch_arrays(
            flow_ids, acquires, prios
        )
        moved = int(TokenStatus.MOVED)
        out = []
        for i in range(n):
            st = int(status[i])
            if st == moved:
                # enrich the redirect with the destination endpoint so
                # in-process callers (and the single-request wire path)
                # can follow it without a second lookup
                red = self.moved_redirect(int(flow_ids[i]))
                out.append(TokenResult(
                    TokenStatus(st), int(remaining[i]), int(wait[i]),
                    endpoint=red[0] if red else "",
                ))
            else:
                out.append(TokenResult(
                    TokenStatus(st), int(remaining[i]), int(wait[i])
                ))
        return out

    def load_param_rules(self, rules: List[ClusterParamFlowRule]) -> None:
        """``ClusterParamFlowRuleManager`` analog; slots stable across
        reloads, freed slots cleared."""
        with self._rules_mutex, self._lock:
            live = {r.flow_id for r in rules}
            # validate capacity BEFORE mutating so a failed load cannot leave
            # a half-applied rule set
            n_new = len({r.flow_id for r in rules if r.flow_id not in self._param_rules})
            n_freed = sum(1 for fid in self._param_rules if fid not in live)
            if n_new > len(self._param_free) + n_freed:
                raise ValueError(
                    f"param rule capacity exceeded: need {n_new} new slots, "
                    f"have {len(self._param_free) + n_freed}"
                )
            for fid in list(self._param_rules):
                if fid not in live:
                    slot, _, _ = self._param_rules.pop(fid)
                    self._param_free.append(slot)
                    # clear the whole sketch row: fat cells (for SALSA the
                    # zeroed int16 cells are unmerged zeros, so the merge
                    # state clears with them), the slim twin row, and the
                    # slot's merge counter
                    self._param_state = self._param_state._replace(
                        counts=self._param_state.counts.at[slot].set(0),
                        slim=self._param_state.slim.at[slot].set(0),
                        merges=self._param_state.merges.at[slot].set(0),
                    )
            for rule in rules:
                existing = self._param_rules.get(rule.flow_id)
                slot = existing[0] if existing else None
                if slot is None:
                    if not self._param_free:
                        raise ValueError("param rule capacity exceeded")
                    slot = self._param_free.pop()
                items = dict(rule.item_thresholds or ())
                self._param_rules[rule.flow_id] = (slot, rule.count, items)
            self._param_rules_src = {r.flow_id: r for r in rules}
            self._param_lookup = self._param_tables()
            # same resync discipline as load_rules: param slot moves/frees
            # invalidate any delta collected against the old generation
            self._state_gen += 1
            if self._dirty is not None:
                self._dirty = state_codec.fresh_dirty()

    def load_namespace_param_rules(
        self, namespace: str, rules: List[ClusterParamFlowRule]
    ) -> None:
        """Replace one namespace's param rules, keeping the others
        (``ClusterParamFlowRuleManager`` namespace scope — the
        cluster/server/modifyParamRules command edits one namespace)."""
        fixed = [
            r if r.namespace == namespace
            else ClusterParamFlowRule(r.flow_id, r.count, r.item_thresholds,
                                      namespace)
            for r in rules
        ]
        with self._rules_mutex:
            with self._lock:
                keep = [
                    r for r in self._param_rules_src.values()
                    if r.namespace != namespace
                ]
            self.load_param_rules(keep + fixed)

    def current_param_rules(
        self, namespace: Optional[str] = None
    ) -> List[ClusterParamFlowRule]:
        with self._lock:
            rules = list(self._param_rules_src.values())
        if namespace is not None:
            rules = [r for r in rules if r.namespace == namespace]
        return rules

    def request_params_token(self, flow_id, acquire, param_hashes) -> TokenResult:
        """CMS-windowed per-value admission. All values of the request are
        judged together; any blocked value blocks the request (reference
        ``ClusterParamFlowChecker``: every param value must have headroom).
        Admitted values are counted; on a mixed verdict the passed values'
        counts stand (conservative overcount, same direction as CMS error).
        The one-row case of :meth:`request_params_batch`.
        """
        if not param_hashes:
            return TokenResult(TokenStatus.OK)
        status, remaining, wait = self.request_params_batch(
            np.array([flow_id], np.int64), np.array([acquire], np.int32),
            np.asarray([list(param_hashes)], np.int64),
        )
        return TokenResult(
            TokenStatus(int(status[0])), int(remaining[0]), int(wait[0])
        )

    def request_params_batch(self, flow_ids, acquires, hashes):
        """``n`` hot-parameter requests of ``k`` values each in one call:
        (status int8[n], remaining int32[n], wait_ms int32[n]) in request
        order. Dispatch + materialize; pipelining callers use
        :meth:`dispatch_params_batch`."""
        return self.dispatch_params_batch(flow_ids, acquires, hashes)()

    def _param_tables(self):
        """The look-up arrays of the param rules, one immutable snapshot
        (called with the rules stable: the constructor, or
        ``load_param_rules`` under the lock): rule ids sorted with their
        slots and counts, and ONE sorted table of the item thresholds keyed
        by ``slot * n_hashes + rank(hash)`` (``item_hashes`` is every item's
        hash, sorted and unique, so the key is exact and fits an int64)."""
        fids = np.fromiter(self._param_rules, np.int64, len(self._param_rules))
        order = np.argsort(fids)
        entries = list(self._param_rules.values())
        slots = np.array([e[0] for e in entries], np.int32)[order]
        counts = np.array([e[1] for e in entries], np.float32)[order]
        i_slot, i_hash, i_thr = [], [], []
        for slot, _count, items in entries:
            i_slot.extend([slot] * len(items))
            i_hash.extend(items.keys())
            i_thr.extend(items.values())
        item_hashes = np.unique(np.asarray(i_hash, np.int64))
        keys = (np.asarray(i_slot, np.int64) * max(1, item_hashes.size)
                + np.searchsorted(item_hashes, np.asarray(i_hash, np.int64)))
        by_key = np.argsort(keys)
        return (fids[order], slots, counts, item_hashes, keys[by_key],
                np.asarray(i_thr, np.float32)[by_key])

    @staticmethod
    def _param_rows(lookup, cfg, flow_ids, acq, hashes):
        """Host prep of one param batch against one look-up snapshot, no
        Python per row: per request its rule slot (-1: no rule); per
        (request, value) row the slot, acquire, threshold (the item's, else
        the rule's count) and the sketch's cell indices."""
        fids, slots, counts, item_hashes, item_keys, item_thr = lookup
        n, k = hashes.shape
        if fids.size:
            at = np.minimum(np.searchsorted(fids, flow_ids), fids.size - 1)
            found = fids[at] == flow_ids
            req_slot = np.where(found, slots[at], np.int32(-1))
            req_count = np.where(found, counts[at], np.float32(0))
        else:
            req_slot = np.full(n, -1, np.int32)
            req_count = np.zeros(n, np.float32)
        flat = hashes.reshape(-1)
        row_slot = np.repeat(req_slot, k)
        thr = np.repeat(req_count, k)
        if item_keys.size:
            rank = np.searchsorted(item_hashes, flat)
            known = item_hashes[np.minimum(rank, item_hashes.size - 1)] == flat
            key = row_slot.astype(np.int64) * item_hashes.size + rank
            at = np.minimum(np.searchsorted(item_keys, key),
                            item_keys.size - 1)
            hit = known & (row_slot >= 0) & (item_keys[at] == key)
            thr = np.where(hit, item_thr[at], thr)
        idx = hash_indices(flat, cfg.depth, cfg.cell_width)
        idx_slim = None
        if cfg.slim_enabled:
            from sentinel_tpu.sketch.slim import slim_indices

            idx_slim = slim_indices(cfg, flat)
        return req_slot, row_slot, np.repeat(acq, k), thr, idx, idx_slim

    # The sketch as the serve step keeps it: the fat counters flat, donated
    # to every step (engine.param.make_param_step). Everything else of the
    # service (rule loads, snapshots, deltas, MOVE, stats) reads and writes
    # ``_param_state``, the same state with the counters in their
    # ``[P, B, depth, cells]`` shape: a reshape either way, on paths that
    # are rare beside a dispatch.
    @property
    def _param_state(self):
        from sentinel_tpu.engine.param import fat_shape

        served = self._param_serve
        return served._replace(
            counts=served.counts.reshape(fat_shape(self.param_config))
        )

    @_param_state.setter
    def _param_state(self, state) -> None:
        self._param_serve = state._replace(counts=state.counts.reshape(-1))

    def _param_bucket(self, rows: int) -> int:
        """The serve bucket a param dispatch of ``rows`` (request, value)
        rows pads to; past the largest (one request with more values than
        it has rows) the next power of two, compiled when first met."""
        for b in self._serve_buckets:
            if rows <= b:
                return b
        return 1 << (rows - 1).bit_length()

    def param_impl(self) -> Tuple[str, str]:
        """``(kernel, reason)``: what ``param_config.impl`` resolved to for
        this service's own geometry and largest serve bucket. Resolved once
        (on a TPU "auto" times both kernels there), logged, exported."""
        if self._param_kernel is None:
            cfg = self.param_config
            self._param_kernel = explain_param_impl(
                cfg.impl, cfg.sketch, cfg, self._serve_buckets[-1]
            )
            _SM.set_param_impl(*self._param_kernel)
            record_log.info(
                "[param] impl %r serves %r: %s", cfg.impl,
                *self._param_kernel,
            )
        return self._param_kernel

    def _param_step_fn(self, bucket: int):
        step = self._param_steps.get(bucket)
        if step is None:
            step = make_param_step(
                self.param_config, bucket, self.param_impl()[0]
            )
            self._param_steps[bucket] = step
        return step

    def dispatch_params_batch(self, flow_ids, acquires, hashes):
        """The hot-parameter serving path, phase 1: host prep + device
        dispatch of ``n`` requests of ``k`` values (``hashes int64[n, k]``).
        Returns a zero-arg **materializer** like
        :meth:`dispatch_batch_arrays`, with the same halves, phases and
        histograms.

        Semantics are :meth:`request_params_token`'s, for every request: all
        values of a request are judged together, any blocked value blocks
        the request, the values that had headroom stay counted; requests on
        one (rule, value) are admitted in batch order against the shared
        budget. A request on a flow id with no param rule is answered
        NO_RULE_EXISTS and never touches the sketch. The ``n x k`` (request,
        value) rows are padded to the service's serve buckets and travel to
        the step as one packed host array; a batch past the largest bucket
        is cut into chunks of whole requests, all launched under one hold
        of the lock, which covers the launches only."""
        t_enter = time.monotonic_ns()
        flow_ids = np.asarray(flow_ids, np.int64)
        n = flow_ids.shape[0]
        hashes = np.asarray(hashes, np.int64)
        k = hashes.size // n if n else 0
        hashes = hashes.reshape(n, k)
        if n == 0 or k == 0:
            # a request with no values passes, as the one-row entry says
            def _trivial():
                zero = np.zeros(n, np.int32)
                return np.zeros(n, np.int8), zero, zero

            return _trivial
        acq = np.asarray(acquires, np.int32)
        if acq.shape != (n,):  # one acquire for every request
            acq = np.broadcast_to(acq, (n,))
        cfg = self.param_config
        per = max(1, self._serve_buckets[-1] // k)  # requests per chunk
        chunks = []  # (lo, hi, serve bucket) of whole requests
        for lo in range(0, n, per):
            hi = min(n, lo + per)
            chunks.append((lo, hi, self._param_bucket((hi - lo) * k)))

        def prep_numpy(lookup):
            req_slot, row_slot, row_acq, thr, idx, idx_slim = (
                self._param_rows(lookup, cfg, flow_ids, acq, hashes)
            )
            packs = []
            for lo, hi, bucket in chunks:
                r = slice(lo * k, hi * k)
                packs.append((bucket, hi - lo, pack_param_rows(
                    cfg, bucket, row_slot[r], row_acq[r], thr[r], idx[r],
                    None if idx_slim is None else idx_slim[r], 0, k, hi - lo,
                )))
            return req_slot, packs, False

        def prep(lookup):
            """``(req_slot, [(bucket, requests, packed)], native)``: a
            chunk a call of the native pass, each ``packed`` a fresh array
            that only this dispatch holds (the ownership rule of
            ``dispatch_batch_arrays``)."""
            slots, packs = [], []
            for lo, hi, bucket in chunks:
                done = _native.param_prep(
                    lookup, flow_ids[lo:hi], acq[lo:hi], hashes[lo:hi],
                    bucket, self._param_geometry,
                )
                if done is None:  # the library is not built: the same in numpy
                    return prep_numpy(lookup)
                slots.append(done[0])
                packs.append((bucket, hi - lo, done[1]))
            req_slot = slots[0] if len(slots) == 1 else np.concatenate(slots)
            return req_slot, packs, True

        lookup = self._param_lookup
        req_slot, packs, native_prep = prep(lookup)
        steps = [self._param_step_fn(b) for b, _m, _p in packs]
        t_prep = time.monotonic_ns()
        with self._lock:
            t_locked = time.monotonic_ns()
            seq = self._dispatch_seq = self._dispatch_seq + 1
            if self._param_lookup is not lookup:
                # param rules reloaded between prep and step: redo the
                # slot-dependent prep against the live tables
                req_slot, packs, native_prep = prep(self._param_lookup)
            now = self._engine_now()
            outs = []
            for step, (_b, _m, packed) in zip(steps, packs):
                packed[-1, 0] = now
                self._param_serve, verdicts = step(self._param_serve, packed)
                outs.append(verdicts)
            if self._dirty is not None:
                self._dirty["param"].update(
                    np.unique(req_slot[req_slot >= 0]).tolist()
                )
        for verdicts in outs:
            verdicts.copy_to_host_async()
        self._dispatched(t_enter, t_prep, t_locked, seq, n * k,
                         lane=_TR.PARAM_LANE, native_prep=native_prep)

        def _read():
            t_mat = time.monotonic_ns()
            parts = []
            for verdicts, (_b, m, _p) in zip(outs, packs):
                t_ready, host = self._read_verdicts(verdicts)
                parts.append(host[:, :m])
            host = parts[0] if len(parts) == 1 else np.concatenate(parts, 1)
            status, wait, remaining = unpack_verdicts(host)
            _SM.count_param_dispatch(
                n, n * k, int((status == int(TokenStatus.BLOCKED)).sum()),
                int((req_slot < 0).sum()),
            )
            return (status, remaining, wait), partial(
                self._account, status, wait, None, seq, n * k, t_enter,
                t_mat, t_ready, time.monotonic_ns(), lane=_TR.PARAM_LANE,
            )

        return Materializer(_read)

    # -- concurrent (semaphore) mode ----------------------------------------
    def load_concurrent_rules(self, rules) -> None:
        """``ConcurrentFlowRule``s, replacing the set that was loaded. The
        first non-empty load allocates the plane (``held``, the token table
        and expiry on the device: ``engine/concurrent.py``) and starts its
        timer; a service that never loads one holds nothing of it. Live
        tokens survive a reload: a flow whose rule went answers NO_RULE and
        keeps draining by release and expiry."""
        from sentinel_tpu.cluster.concurrent import ConcurrentPlane

        rules = list(rules)
        with self._rules_mutex:
            plane = self._conc
            if plane is None:
                if not rules:
                    return
                plane = ConcurrentPlane(
                    self.config.max_flows, self._concurrent_max_tokens,
                    self._serve_buckets,
                )
                if self._warmed:
                    # a service that is serving compiles the plane's steps
                    # here, on the loader's thread and outside the serving
                    # lock: the first concurrency frame shares the native
                    # device lane with every other kind of row, and a
                    # compile there would age what queues behind it
                    self._warm_concurrent_steps(plane)
            with self._lock:
                self._conc = plane
                plane.load_rules(rules, self._connected)
        self._start_concurrent_timer()

    def current_concurrent_rules(self) -> list:
        with self._lock:
            return [] if self._conc is None else list(
                self._conc.rules.values())

    def _start_concurrent_timer(self) -> None:
        """The timer behind the expiry guarantee: a step of the plane at
        least every ``TICK_MS``, so that the scan goes round the token ring
        within ``EXPIRY_SLACK_MS`` with no traffic as well."""
        from sentinel_tpu.cluster.concurrent import TICK_MS

        if (self._conc is None or self._conc_timer is not None
                or self._conc_timer_closed):
            return
        stop = self._conc_timer_stop = threading.Event()

        def run():
            while not stop.wait(TICK_MS / 1000.0):
                idle_ns = time.monotonic_ns() - self._conc_last_step_ns
                if idle_ns >= TICK_MS * 1_000_000:
                    try:
                        self.concurrent_tick()
                    except Exception:
                        record_log.exception("concurrent tick failed")

        self._conc_timer = threading.Thread(
            target=run, name="sentinel-concurrent-tick", daemon=True
        )
        self._conc_timer.start()

    def close(self) -> None:
        self._conc_timer_closed = True  # a later rule load starts none
        timer, self._conc_timer = self._conc_timer, None
        if timer is not None:
            self._conc_timer_stop.set()
            timer.join(timeout=5)

    def reopen(self) -> None:
        """Re-arm background resources after a close() when the service is
        put back behind a transport (e.g. a token-server port move reuses
        the service): without this, concurrent-mode tokens held by crashed
        clients would never be reclaimed while no request arrives."""
        self._conc_timer_closed = False
        self._start_concurrent_timer()

    def concurrent_tick(self) -> int:
        """One step of the plane with no rows: the expiry scan's next
        block. Returns the tokens it reclaimed. The timer calls it when no
        dispatch has stepped the plane for ``TICK_MS``; tests with a clock
        of their own call it by hand (after ``close()`` stopped the
        timer)."""
        from sentinel_tpu.engine import concurrent as _CE

        plane = self._conc
        if plane is None:
            return 0
        bucket = self._serve_buckets[0]
        packed = _CE.pack_concurrent_rows(bucket, (), (), (), ())
        step = plane.step_fn(bucket)
        with self._lock:
            packed[_CE.ROW_HEAD, _CE.HEAD_NOW] = self._engine_now()
            plane.state, verdicts = step(plane.state, packed)
            self._conc_last_step_ns = time.monotonic_ns()
        misc = np.asarray(verdicts)[_CE.OUT_MISC]  # no op on the device
        expired = int(misc[_CE.MISC_EXPIRED])
        _SM.count_concurrent_step(0, 0, 0, 0, expired, 0,
                                  int(misc[_CE.MISC_LIVE]), tick=True)
        return expired

    def request_concurrent_batch(self, ids, counts=None, is_release=None):
        """Acquire and release rows in one call: ``(status int8[n],
        remaining int32[n], wait_ms int32[n], token_ids int64[n])`` in
        request order. Dispatch + materialize; pipelining callers use
        :meth:`dispatch_concurrent_batch`."""
        return self.dispatch_concurrent_batch(ids, counts, is_release)()

    def dispatch_concurrent_batch(self, ids, counts=None, is_release=None):
        """The concurrency serving path, phase 1: host prep + device
        dispatch of ``n`` rows. Row ``i`` is an acquire of ``counts[i]`` on
        flow ``ids[i]``, or, where ``is_release[i]``, a release of token
        ``ids[i]``. Returns a zero-arg **materializer** like
        :meth:`dispatch_batch_arrays`, with the same halves, phases and
        histograms; its verdicts carry a fourth array, the token ids
        (``int64``; 0 where the row is not an acquire that passed).

        The releases of a dispatch are applied before its acquires (a
        release can only free room), the acquires in row order:
        ``cluster/concurrent.py`` has the semantics, ``engine/concurrent.py``
        the step. Rows travel as one packed host array a step; a kind's
        rows past the largest serve bucket are cut into steps launched
        under one hold of the lock."""
        t_enter = time.monotonic_ns()
        ids = np.asarray(ids, np.int64)
        n = ids.shape[0]
        rel = (np.zeros(n, bool) if is_release is None
               else np.asarray(is_release, bool))
        plane = self._conc
        if plane is None or n == 0:
            # no concurrency rule was ever loaded: nothing is allocated,
            # nothing compiled, and every flow is without a rule
            def _ruleless():
                status = np.where(
                    rel, int(TokenStatus.ALREADY_RELEASE),
                    int(TokenStatus.NO_RULE_EXISTS)).astype(np.int8)
                zero = np.zeros(n, np.int32)
                return status, zero, zero, np.zeros(n, np.int64)

            return _ruleless
        acq = (np.ones(n, np.int32) if counts is None
               else np.broadcast_to(np.asarray(counts, np.int32), (n,)))
        lookup = plane.lookup
        parts, native_prep = plane.prep(lookup, ids, acq, rel)
        steps = [plane.step_fn(b) for b, *_rest in parts]
        t_prep = time.monotonic_ns()
        with self._lock:
            t_locked = time.monotonic_ns()
            seq = self._dispatch_seq = self._dispatch_seq + 1
            if plane.lookup is not lookup:
                # rules reloaded between prep and step: the slots moved
                parts, native_prep = plane.prep(plane.lookup, ids, acq, rel)
                steps = [plane.step_fn(b) for b, *_rest in parts]
            now = self._engine_now()
            outs = []
            for step, (_b, packed, _a, _r) in zip(steps, parts):
                packed[-1, 0] = now  # engine.concurrent ROW_HEAD, HEAD_NOW
                plane.state, verdicts = step(plane.state, packed)
                outs.append(verdicts)
            self._conc_last_step_ns = t_locked
        for verdicts in outs:
            verdicts.copy_to_host_async()
        self._dispatched(t_enter, t_prep, t_locked, seq, n,
                         lane=_TR.CONCURRENT_LANE, native_prep=native_prep)
        n_rel = int(rel.sum())

        def _read():
            t_mat = time.monotonic_ns()
            hosts = []
            for verdicts in outs:
                ready = verdicts.is_ready()
                hosts.append(np.asarray(verdicts))
                t_ready = time.monotonic_ns()
                _SM.count_verdict_read(ready)
            status, remaining, token_ids, expired, full, live = (
                plane.unpack(n, parts, hosts))
            _SM.count_concurrent_step(
                n - n_rel, n_rel,
                int((status == int(TokenStatus.BLOCKED)).sum()),
                int((status == int(TokenStatus.ALREADY_RELEASE)).sum()),
                expired, full, live,
            )
            wait = np.zeros(n, np.int32)
            return (status, remaining, wait, token_ids), partial(
                self._account, status, wait, None, seq, n, t_enter, t_mat,
                t_ready, time.monotonic_ns(), lane=_TR.CONCURRENT_LANE,
            )

        return Materializer(_read)

    def request_concurrent_token(self, flow_id, acquire=1, prioritized=False):
        """The one-row case of :meth:`request_concurrent_batch`."""
        status, remaining, _wait, token_ids = self.request_concurrent_batch(
            np.array([flow_id], np.int64), np.array([acquire], np.int32))
        return TokenResult(TokenStatus(int(status[0])), int(remaining[0]), 0,
                           int(token_ids[0]))

    def release_concurrent_token(self, token_id):
        status, _r, _w, _t = self.request_concurrent_batch(
            np.array([token_id], np.int64), None, np.ones(1, bool))
        return TokenResult(TokenStatus(int(status[0])))

    def concurrent_stats(self) -> Dict[str, object]:
        """The plane read to the host: ``held`` and ``level`` by flow id,
        the live tokens by id (``ConcurrentPlane.snapshot``); empty where
        no concurrency rule was ever loaded."""
        with self._lock:
            return {} if self._conc is None else self._conc.snapshot()

    # -- live rebalance (cluster.rebalance backing) --------------------------
    def _rebuild_moving_snap(self) -> None:
        """Rebuild the dispatch-path MOVING view from ``self._moving``.
        Caller holds ``self._lock`` (the lock is the linearization point:
        a dispatch that entered the lock before a ``begin_move`` decides
        pre-move and its tokens are included in the exported sums)."""
        if not self._moving:
            self._moving_snap = None
            return
        n = self.config.max_namespaces
        mask = np.zeros(n, bool)
        epochs = np.zeros(n, np.int32)
        for ns_name, (_dest, epoch) in self._moving.items():
            row = self._index.ns_of.get(ns_name)
            if row is not None and row < n:
                mask[row] = True
                epochs[row] = np.int32(epoch)
        self._moving_snap = (mask, epochs) if mask.any() else None

    def _moving_mask_for(self, slots: np.ndarray, mv):
        """Request-order bool mask of rows whose rule's namespace is MOVING
        (plus the per-row shard-map epoch vector), or ``(None, None)`` when
        this batch touches no moving namespace. Caller holds ``self._lock``
        (reads the live ``_ns_snapshot``)."""
        mask_arr, epoch_arr = mv
        _names, slot_ns = self._ns_snapshot
        ns_idx = slot_ns[slots]  # slot -1 reads the last entry, -1
        m = (ns_idx >= 0) & mask_arr[np.maximum(ns_idx, 0)]
        if not m.any():
            return None, None
        return m, epoch_arr[np.maximum(ns_idx, 0)]

    def begin_move(self, namespace: str, endpoint: str, epoch: int) -> None:
        """Mark ``namespace`` MOVING to ``endpoint`` under shard-map
        ``epoch``: from the next device step its flows stop counting tokens
        and answer ``TokenStatus.MOVED`` instead. Idempotent re-begin to the
        same destination is allowed (coordinator retry); a different
        destination while moving raises."""
        with self._lock:
            cur = self._moving.get(namespace)
            if cur is not None and cur[0] != endpoint:
                raise ValueError(
                    f"namespace {namespace!r} already moving to {cur[0]}"
                )
            self._moving[namespace] = (str(endpoint), int(epoch))
            self._rebuild_moving_snap()
            # recall the namespace's outstanding leases: registry entries
            # drop here (renews now answer MOVED → clients fall back and
            # re-grant at the destination) while the LEASED charge stays in
            # the flow window, so the MOVE's window-sum export carries it to
            # the new owner — "transfer the charge, recall the lease"
            flows = set(self._rules_by_ns.get(namespace, ()))
            dead = []
            if flows and self._leases:
                dead = [
                    l for l in self._leases.values() if l.flow_id in flows
                ]
                for l in dead:
                    del self._leases[l.lease_id]
                self._lease_stats["revoked"] += len(dead)
            # same contract for hierarchy share holds: the LEASED hold
            # charge rides the window-sum export to the new owner (so the
            # global budget stays pinned through the handoff) while the
            # registry drops — the destination's own share agent re-tops
            # its hold from ITS share on its next tick
            for fid in flows:
                self._share_holds.pop(int(fid), None)
        # rev-7 push: recalled leases cut over within one RTT — without
        # this the leased fast path keeps admitting against the recalled
        # slice until its next renew answers MOVED
        for l in dead:
            self._emit_push(
                "push_lease_revoke", l.lease_id, l.flow_id, l.tokens
            )
        if _TR.ARMED:  # flight recorder: MOVE begin (phase 0)
            _TR.record(_TR.MOVE, aux=0)

    def abort_move(self, namespace: str) -> None:
        """Restore normal serving for ``namespace``. Lossless by
        construction: MOVED-masked requests never touched the counters, so
        un-masking resumes from exactly the pre-move state."""
        with self._lock:
            self._moving.pop(namespace, None)
            self._rebuild_moving_snap()
        if _TR.ARMED:  # flight recorder: MOVE abort (phase 2)
            _TR.record(_TR.MOVE, aux=2)
        from sentinel_tpu.trace import blackbox as _blackbox

        _blackbox.maybe_dump(f"move_abort:{namespace}")

    def end_redirect(self, namespace: str) -> None:
        """Drop the post-commit redirect tombstone AND the namespace's rules
        (the destination owns them now). Until this is called a committed
        move keeps answering MOVED so stale clients learn the new owner."""
        with self._lock:
            self._moving.pop(namespace, None)
            self._rebuild_moving_snap()
        # degrade rules leave with the namespace too (the MOVE blob carried
        # them; keeping them here would pin dead breaker slots)
        if any(
            d.namespace == namespace
            for d in self._degrade_rules_src.values()
        ):
            self.load_namespace_degrade_rules(namespace, [])
        self.load_namespace_rules(namespace, [])

    def moving_namespaces(self) -> Dict[str, Tuple[str, int]]:
        """namespace → (destination endpoint, shard-map epoch)."""
        with self._lock:
            return dict(self._moving)

    def moved_redirect(self, flow_id: int) -> Optional[Tuple[str, int]]:
        """``(destination endpoint, shard-map epoch)`` when ``flow_id``'s
        namespace is MOVING (or committed-away), else None. The single-
        request wire path uses this to fill the MOVED endpoint trailer."""
        if not self._moving:
            return None
        with self._lock:
            slot = int(self._lookup_from(
                self._lookup, np.asarray([flow_id], np.int64)
            )[0])
            if slot < 0:
                return None
            names, slot_ns = self._ns_snapshot
            row = int(slot_ns[slot])
            if row < 0 or row >= len(names):
                return None
            return self._moving.get(names[row])

    def namespace_index(
        self, flow_ids
    ) -> Tuple[np.ndarray, Tuple[str, ...]]:
        """``(ns_idx int32[N], ns_names)`` for a batch of flow ids — the
        front doors' per-tenant attribution of rows that never reach the
        device (queue full, brownout, degrade), shaped for
        ``ServerMetrics.record_verdict_batch``. Lock-free (snapshot
        reads); shed paths only, never the serving hot path."""
        slots = self._lookup_from(
            self._lookup, np.asarray(flow_ids, np.int64)
        )
        names, slot_ns = self._ns_snapshot
        return slot_ns[slots], names  # slot -1 reads the last entry, -1

    # -- rev-7 push plane (server→client control frames) ---------------------
    def attach_push_hub(self, hub) -> None:
        """Register a front door's :class:`~sentinel_tpu.cluster.push.PushHub`.
        Every service-side truth change that clients may be caching (lease
        registry, breaker states, rule generation) is mirrored onto every
        attached hub; both doors of a server attach the same hub."""
        if hub not in self._push_hubs:
            self._push_hubs.append(hub)

    def _emit_push(self, method: str, *args) -> None:
        """Fan one emit across every attached hub. Never raises and never
        blocks — hub sinks are the same non-blocking enqueues the reply
        lanes use — so call sites inside ``self._lock`` are safe (the hub's
        own lock never calls back into the service)."""
        for hub in self._push_hubs:
            try:
                getattr(hub, method)(*args)
            except Exception:
                pass

    # -- wire rev 5: token leases (client-local admission) -------------------
    def _sweep_leases_locked(self, now: int) -> None:
        """Drop leases past their TTL. Their LEASED charge stays in the flow
        window and expires with it — a crashed client therefore causes
        *under*-admission for up to one window, never over-admission.
        Caller holds ``self._lock``."""
        if not self._leases:
            return
        dead = [
            l for l in list(self._leases.values()) if now >= l.expiry_ms
        ]
        if dead:
            for l in dead:
                del self._leases[l.lease_id]
            self._lease_stats["revoked"] += len(dead)
            # push the revocations so a live-but-slow client drops its
            # cached slice now instead of admitting against a lease the
            # server already wrote off
            for l in dead:
                self._emit_push(
                    "push_lease_revoke", l.lease_id, l.flow_id, l.tokens
                )

    def _credit_lease_locked(self, lease: _Lease, used: int) -> None:
        """Credit a lease's unused tokens back into the EXACT ring bucket
        its grant charged — but only when the start stamp proves that
        bucket is still the grant's epoch. Charge and credit then rotate
        out *together*, so a flow's LEASED window sum can never go net
        negative (crediting into a *different* bucket could outlive the
        charge and briefly over-admit). When the bucket has rotated (or
        an engine-time rebase shifted the stamps) the credit is dropped
        and the unused tokens expire with the window — the conservative
        direction. Caller holds ``self._lock``."""
        from sentinel_tpu.engine.state import ClusterEvent, flow_spec

        unused = lease.tokens - max(0, int(used))
        if unused <= 0:
            return
        spec = flow_spec(self.config)
        idx = int((lease.granted_ms // spec.bucket_ms) % spec.n_buckets)
        aligned = int(lease.granted_ms - lease.granted_ms % spec.bucket_ms)
        ws = self._state.flow
        if int(np.asarray(ws.starts)[idx]) != aligned:
            return
        counts = ws.counts.at[
            lease.slot, idx, int(ClusterEvent.LEASED)
        ].add(jnp.asarray(-unused, ws.counts.dtype))
        self._state = self._state._replace(
            flow=ws._replace(counts=counts)
        )
        if self._dirty is not None:
            self._dirty["flow"].add(int(lease.slot))

    def _lease_admit_locked(
        self, flow_id: int, want: int, now: int, stat: str
    ) -> LeaseResult:
        """Grant core: prorate a slice of the flow's CURRENT headroom
        (threshold − PASS − LEASED − matured borrows, the same occupancy
        the device kernel reads), charge it into the LEASED column, and
        register the lease. Caller holds ``self._lock`` and has swept."""
        from sentinel_tpu.engine.rules import ThresholdMode
        from sentinel_tpu.engine.state import (
            N_CLUSTER_EVENTS, ClusterEvent, flow_spec,
        )
        from sentinel_tpu.stats import window as W

        flow_id = int(flow_id)
        rule = self._rule_of.get(flow_id)
        if rule is None:
            return LeaseResult(int(TokenStatus.NO_RULE_EXISTS))
        mv = self._moving.get(rule.namespace)
        if mv is not None:
            # namespace mid-move or committed away: same redirect contract
            # as the decision path — tokens carries the shard-map epoch
            return LeaseResult(
                int(TokenStatus.MOVED), tokens=int(mv[1]), endpoint=mv[0]
            )
        want = int(want)
        if want <= 0 or self.lease_fraction <= 0.0:
            return LeaseResult(int(TokenStatus.NOT_LEASABLE))
        if int(getattr(rule, "control_behavior", 0)) != 0:
            # a shaped rule's admission curve lives in the device shaper
            # state — client-local lease admission would bypass warmup and
            # pacing entirely, so shaped flows are simply not leasable
            return LeaseResult(int(TokenStatus.NOT_LEASABLE))
        if self._has_breakers and flow_id in self._degrade_rules_src:
            # a breaker-guarded flow must answer per-request: a client-local
            # slice would keep admitting for a full TTL after the breaker
            # OPENs, and its traffic would never produce the DEGRADED
            # verdicts that tell the client to back off. Refusing the lease
            # bounds breaker over-admission to in-flight requests only.
            return LeaseResult(int(TokenStatus.NOT_LEASABLE))
        slot = self._index.slot_of.get(flow_id)
        if slot is None:
            return LeaseResult(int(TokenStatus.NO_RULE_EXISTS))
        spec = flow_spec(self.config)
        now32 = jnp.int32(now)
        ids = jnp.asarray(np.asarray([slot], np.int32))
        occupied = float(np.asarray(
            W.window_sum_at(spec, self._state.flow, now32,
                            int(ClusterEvent.PASS), ids)
            + W.window_sum_at(spec, self._state.flow, now32,
                              int(ClusterEvent.LEASED), ids)
            + W.window_sum_at(spec, self._state.occupy, now32, 0, ids)
        )[0])
        # same per-window budget the device kernel enforces: rule count is
        # per-second, scaled by connected clients under AVG_LOCAL
        factor = (
            max(1, int(self._connected.get(rule.namespace, 1)))
            if rule.mode == ThresholdMode.AVG_LOCAL else 1
        )
        threshold = (
            float(rule.count) * factor * self.config.exceed_count
            * (spec.interval_ms / 1000.0)
        )
        grant = min(want, int((threshold - occupied) * self.lease_fraction))
        if grant < 1:
            return LeaseResult(int(TokenStatus.NOT_LEASABLE))
        row = [0] * int(N_CLUSTER_EVENTS)
        row[int(ClusterEvent.LEASED)] = grant
        self._state = self._state._replace(
            flow=self._fold_into_current(
                self._state.flow, spec, now, [slot], [row]
            )
        )
        if self._dirty is not None:
            self._dirty["flow"].add(int(slot))
        lease_id = next(self._lease_seq)
        self._leases[lease_id] = _Lease(
            lease_id, flow_id, slot, grant, now, now + self.lease_ttl_ms
        )
        self._lease_stats[stat] += 1
        return LeaseResult(
            int(TokenStatus.OK), lease_id=lease_id, tokens=grant,
            ttl_ms=self.lease_ttl_ms,
        )

    def lease_grant(self, flow_id: int, want: int) -> LeaseResult:
        """Grant a short-TTL local-admission slice of ``flow_id``'s window:
        up to ``want`` tokens, capped at ``lease_fraction`` of the flow's
        current headroom. The slice is pre-paid (charged to the LEASED
        column now), so the client's local admissions never touch the
        server and every replica's psum'd limit already accounts them."""
        with self._lock:
            now = self._engine_now()
            self._sweep_leases_locked(now)
            res = self._lease_admit_locked(flow_id, want, now, "granted")
        if _TR.ARMED:  # flight recorder: lease grant
            _TR.record(_TR.LEASE, aux=getattr(res, "tokens", 0) or 0)
        return res

    def lease_renew(
        self, lease_id: int, flow_id: int, used: int, want: int
    ) -> LeaseResult:
        """Atomically credit the old lease's unused tokens and grant a
        fresh slice. An unknown ``lease_id`` (expired, revoked, or a
        promoted standby that never saw the grant) degrades to a
        credit-less grant — no handshake needed after failover; the old
        charge, wherever it lives, expires with its window."""
        with self._lock:
            now = self._engine_now()
            self._sweep_leases_locked(now)
            lease = self._leases.get(int(lease_id))
            if lease is not None and lease.flow_id == int(flow_id):
                del self._leases[int(lease_id)]
                self._credit_lease_locked(lease, used)
            res = self._lease_admit_locked(flow_id, want, now, "renewed")
        if _TR.ARMED:  # flight recorder: lease renew
            _TR.record(_TR.LEASE, aux=getattr(res, "tokens", 0) or 0)
        return res

    def lease_return(self, lease_id: int, used: int) -> LeaseResult:
        """Give a lease back early, crediting its unused tokens. Idempotent:
        returning an expired/revoked/unknown lease is OK (the charge simply
        expires with the window)."""
        with self._lock:
            now = self._engine_now()
            self._sweep_leases_locked(now)
            lease = self._leases.pop(int(lease_id), None)
            if lease is None:
                return LeaseResult(int(TokenStatus.OK))
            self._credit_lease_locked(lease, used)
            self._lease_stats["returned"] += 1
        if _TR.ARMED:  # flight recorder: lease returned early
            _TR.record(_TR.LEASE, aux=int(used))
        return LeaseResult(int(TokenStatus.OK))

    def outstanding_leases(self) -> int:
        """Sum of tokens currently delegated on live leases — the bound on
        crash over-admission (a dead client can have locally admitted at
        most what it was granted and never reported back). The ha drill
        gates against exactly this number at SIGKILL time."""
        with self._lock:
            self._sweep_leases_locked(self._engine_now())
            return sum(l.tokens for l in self._leases.values())

    def lease_stats(self) -> Dict[str, int]:
        """Counter block behind the ``sentinel_lease_*`` series and the
        bench artifact: cumulative granted/renewed/returned/revoked plus
        the live outstanding gauge (leases and delegated tokens).
        ``revoked`` covers every server-side end of life: TTL expiry,
        rule-reload drop, and MOVE recall."""
        with self._lock:
            if self._leases:
                self._sweep_leases_locked(self._engine_now())
            out = dict(self._lease_stats)
            out["outstanding"] = len(self._leases)
            out["outstanding_tokens"] = sum(
                l.tokens for l in self._leases.values()
            )
            return out

    # -- hierarchy tier: global-budget share holds ---------------------------
    # A globally-limited flow is loaded locally at its FULL global budget;
    # the pod's share agent then pins (window_budget − share) tokens as a
    # LEASED-column "hold", leaving exactly the pod's share as local
    # headroom. The decision hot path is untouched — the device kernel
    # already reads LEASED — and psum'd limits, snapshots, deltas, and MOVE
    # all carry the hold automatically, like any lease charge.

    def _live_hold_locked(self, spec, entries, now):
        """Filter hold entries to those whose grant bucket still counts
        toward the window sum: start-stamp equality (the bucket was never
        reused — same proof as lease credit) AND in-window age (the same
        ``(now − interval, now]`` test as ``stats.window.valid_mask``).
        Stamp equality alone is not enough: a rotated-out bucket keeps its
        stale stamp until some writer reuses it, so an age-expired hold
        would look live here while the admission read already dropped it —
        and the re-top would never fire. Expired entries are simply gone:
        their charge aged out with the bucket, so the hold decayed and the
        agent must re-top it (the conservative direction: a decayed hold
        admits MORE locally, only up to the full budget, and only until
        the next agent tick)."""
        starts = np.asarray(self._state.flow.starts)
        live = []
        for granted_ms, tokens in entries:
            idx = int((granted_ms // spec.bucket_ms) % spec.n_buckets)
            aligned = int(granted_ms - granted_ms % spec.bucket_ms)
            age = int(now) - aligned
            if int(starts[idx]) == aligned and 0 <= age < spec.interval_ms:
                live.append((granted_ms, tokens))
        return live

    def set_share_hold(self, flow_id: int, hold_tokens: int) -> int:
        """Pin exactly ``hold_tokens`` of ``flow_id``'s window as a
        LEASED-column hold. A hold is a STANDING reservation, not traffic:
        left where it was charged it would age out of the sliding window
        one interval later and dump its whole worth of headroom at once
        (a flat-out client eats that before the next tick — measured, not
        hypothetical). So every call *migrates* the hold forward: live
        entries are credited back into their exact grant buckets
        (start-stamp guarded, same invariant as lease credit) and the full
        target re-charges into the CURRENT bucket — the window sum is
        unchanged within the call, and as long as the agent ticks more
        often than one window the hold never decays. If ticks stop
        entirely (agent dead), the hold expires one window later and the
        flow reverts to its full local budget — the documented degrade.
        Returns the live hold after the call."""
        from sentinel_tpu.engine.state import (
            N_CLUSTER_EVENTS, ClusterEvent, flow_spec,
        )

        flow_id = int(flow_id)
        hold_tokens = max(0, int(hold_tokens))
        with self._lock:
            slot = self._index.slot_of.get(flow_id)
            if slot is None:
                self._share_holds.pop(flow_id, None)
                return 0
            spec = flow_spec(self.config)
            now = self._engine_now()
            entries = self._live_hold_locked(
                spec, self._share_holds.get(flow_id, []), now
            )
            ws = self._state.flow
            counts = ws.counts
            for granted_ms, tokens in entries:
                idx = int((granted_ms // spec.bucket_ms) % spec.n_buckets)
                counts = counts.at[
                    slot, idx, int(ClusterEvent.LEASED)
                ].add(jnp.asarray(-tokens, counts.dtype))
            ws = ws._replace(counts=counts)
            if hold_tokens > 0:
                row = [0] * int(N_CLUSTER_EVENTS)
                row[int(ClusterEvent.LEASED)] = hold_tokens
                ws = self._fold_into_current(ws, spec, now, [slot], [row])
                self._share_holds[flow_id] = [(now, hold_tokens)]
            else:
                self._share_holds.pop(flow_id, None)
            self._state = self._state._replace(flow=ws)
            if self._dirty is not None:
                self._dirty["flow"].add(int(slot))
            return hold_tokens

    def share_holds(self) -> Dict[int, int]:
        """Live hold tokens per flow (rotation-decayed entries excluded)."""
        from sentinel_tpu.engine.state import flow_spec

        with self._lock:
            spec = flow_spec(self.config)
            now = self._engine_now()
            out = {
                fid: sum(
                    t for _, t in self._live_hold_locked(spec, ents, now)
                )
                for fid, ents in self._share_holds.items()
            }
            # a fully-decayed hold is indistinguishable from no hold — the
            # registry entry is just garbage awaiting the next set
            return {fid: t for fid, t in out.items() if t > 0}

    def window_budget(self, flow_id: int) -> int:
        """The flow's full per-window token budget — the same threshold
        the device kernel enforces (count × connected-factor ×
        exceed_count × window). The share agent holds
        ``window_budget − share`` so local headroom equals the share."""
        from sentinel_tpu.engine.rules import ThresholdMode
        from sentinel_tpu.engine.state import flow_spec

        with self._lock:
            rule = self._rule_of.get(int(flow_id))
            if rule is None:
                return 0
            spec = flow_spec(self.config)
            factor = (
                max(1, int(self._connected.get(rule.namespace, 1)))
                if rule.mode == ThresholdMode.AVG_LOCAL else 1
            )
            return int(
                float(rule.count) * factor * self.config.exceed_count
                * (spec.interval_ms / 1000.0)
            )

    def demand_rates(self, flow_ids) -> Dict[int, float]:
        """Observed arrival rate per flow in tokens/s: (PASS + BLOCK)
        window sums over the window interval. BLOCK counts *blocked*
        tokens, so a pod squeezed to a tiny share still reports its true
        demand — which is exactly what lets the coordinator's
        water-filling move share back toward it."""
        from sentinel_tpu.engine.state import ClusterEvent, flow_spec
        from sentinel_tpu.stats import window as W

        out: Dict[int, float] = {}
        known = []
        with self._lock:
            spec = flow_spec(self.config)
            now32 = jnp.int32(self._engine_now())
            for fid in flow_ids:
                slot = self._index.slot_of.get(int(fid))
                if slot is None:
                    out[int(fid)] = 0.0
                else:
                    known.append((int(fid), int(slot)))
            if known:
                ids = jnp.asarray(
                    np.asarray([s for _, s in known], np.int32)
                )
                sums = np.asarray(
                    W.window_sum_at(spec, self._state.flow, now32,
                                    int(ClusterEvent.PASS), ids)
                    + W.window_sum_at(spec, self._state.flow, now32,
                                      int(ClusterEvent.BLOCK), ids)
                )
                interval_s = spec.interval_ms / 1000.0
                for (fid, _), v in zip(known, sums):
                    out[fid] = float(v) / interval_s
        return out

    def attach_hierarchy(self, coordinator) -> None:
        """Co-locate the global budget coordinator with this pod: both
        doors route HIER_TYPES frames to it, its ledger piggybacks on
        this service's replication stream, and its counters join
        ``hier_stats``."""
        self.hierarchy = coordinator

    def attach_share_agent(self, agent) -> None:
        """Register this pod's share agent so its counters join
        ``hier_stats`` (the agent itself talks to the coordinator over
        the wire, not through the service)."""
        self.share_agent = agent

    def hier_stats(self) -> Dict[str, object]:
        """Counter block behind the ``sentinel_hier_*`` series: agent-side
        share/tick counters overlaid (coordinator wins) with the
        coordinator ledger, when either is attached."""
        out: Dict[str, object] = {}
        agent = self.share_agent
        if agent is not None:
            try:
                out.update(agent.stats())
            except Exception:  # pragma: no cover - stats never raise
                pass
        coord = self.hierarchy
        if coord is not None:
            try:
                out.update(coord.stats())
            except Exception:  # pragma: no cover
                pass
        if out:
            out["hold_tokens"] = sum(self.share_holds().values())
        return out

    @staticmethod
    def _fold_into_current(ws, spec, now: int, rows, sums):
        """Add per-row event sums into the CURRENT ring bucket of ``ws``,
        host-side pre-rotating that column when its recorded start is stale
        (zero it across ALL rows and stamp the aligned start — exactly what
        :func:`stats.window.roll` would do on the next write) so the fold
        cannot resurrect a dead bucket's counts. Conservative direction:
        imported counts are all attributed to *now*, so they expire at most
        one window later than they would have at the source — never
        earlier, which is what zero-over-admission needs."""
        starts = np.asarray(ws.starts)
        # the ring is as long as the window was made (the occupy window's
        # is twice the flow window's: engine.state.occupy_ring)
        idx = int((now // spec.bucket_ms) % starts.shape[0])
        aligned = int(now - now % spec.bucket_ms)
        counts = ws.counts
        if int(starts[idx]) != aligned:
            counts = counts.at[:, idx].set(0)
            starts = np.array(starts)
            starts[idx] = aligned
        if rows is not None and len(rows):
            counts = counts.at[np.asarray(rows, np.int32), idx].add(
                jnp.asarray(np.asarray(sums), counts.dtype)
            )
        return ws._replace(starts=jnp.asarray(starts), counts=counts)

    def export_namespace_state(self, namespace: str) -> Dict[str, object]:
        """The *slim* representation of one namespace for a live move: its
        rules plus per-row **live-window sums** (flow/occupy event sums, the
        namespace guard row, and the param CMS cells), not the raw ring
        buckets, and the shaper and breaker clocks relative to now. Sums
        are ring- and epoch-free, so the destination can fold them into its
        OWN current bucket regardless of clock skew or ring phase — the
        fat-update/slim-query split of SF-sketch applied to the handoff
        (ISSUE 8). Rules come back as rule objects; the rebalance codec
        serializes them."""
        return state_codec.export_namespace_state(self, namespace)

    def import_namespace_state(self, doc: Dict[str, object]) -> None:
        """Install an :meth:`export_namespace_state` capture into THIS
        service: load the namespace's rules through the normal reload path
        (fresh local slots), then fold every shipped sum into the current
        ring bucket (see :meth:`_fold_into_current`). Token-lossless: the
        destination's first window sum over an imported row equals the
        source's last — admission resumes exactly where the source
        stopped."""
        state_codec.import_namespace_state(self, doc)

    # -- state snapshot / restore (ha.snapshot backing) ----------------------
    def export_state(self) -> Dict[str, object]:
        """Device→host capture of everything a warm standby needs to resume
        counting: rule sources, slot assignments, every leaf of the engine
        state and the param sketch, and the engine epoch. Arrays come back
        as host numpy copies; keys are stable (``ha.snapshot`` encodes them
        into the versioned artifact)."""
        return state_codec.export_state(self)

    def import_state(self, state: Dict[str, object]) -> None:
        """Restore an :meth:`export_state` capture into THIS service.

        Slot assignments are not trusted: rules reload through the normal
        path (fresh ``RuleIndex`` slots), then counter rows remap
        old-slot→new-slot per flow_id / namespace / param rule, so a standby
        that loaded rules in a different order still lands every counter on
        the right rule. Window starts carry over verbatim — engine time
        continues from the snapshot epoch, so counters older than one window
        expire naturally via the mask-on-read reads. Geometry (window/sketch
        shapes) must match this service's config; mismatch raises
        ``ValueError`` before anything mutates."""
        state_codec.import_state(self, state)

    # -- warm-standby delta replication (ha.replication backing) -------------
    def replication_enable(self) -> None:
        """Arm dirty-slot tracking so :meth:`export_delta` has rows to ship.
        Idempotent; until called the dispatch paths skip the bookkeeping."""
        with self._lock:
            if self._dirty is None:
                self._dirty = state_codec.fresh_dirty()

    def replication_disable(self) -> None:
        with self._lock:
            self._dirty = None

    def state_generation(self) -> int:
        """Bumped on every rule/param-rule reload. Deltas are row-keyed by
        slot assignments that only hold within one generation; a sender that
        observes a bump must ship a full snapshot before more deltas."""
        with self._lock:
            return self._state_gen

    def export_delta(self) -> Dict[str, object]:
        """Collect-and-clear the dirty counter rows since the last call.

        Returns a compact host-side document: the shared window ``starts``
        ring vectors (``[n_buckets]`` each — always shipped, they advance
        with engine time), plus per-dirty-slot rows keyed by flow_id /
        namespace name / param flow_id so the standby can land them on its
        OWN slot assignment. ``gen`` is the generation the rows were
        collected under; ``epoch_ms`` pins the engine timeline the starts
        are relative to (the standby refuses a delta from a foreign epoch).
        An idle tick returns a starts-only document — the sender's liveness
        heartbeat. Destructive: the dirty sets are cleared, so a sender
        that fails to deliver must fall back to a full snapshot."""
        return state_codec.export_delta(self)

    def apply_replication_delta(self, delta: Dict[str, object]) -> None:
        """Scatter a primary's :meth:`export_delta` into THIS (standby)
        service. Rows remap by flow_id / namespace / param flow_id onto the
        local slot assignment — the standby loaded the same rules from the
        bootstrap snapshot, but possibly in a different slot order. A delta
        naming a flow this service doesn't know, or carrying a foreign
        engine epoch, raises ``ValueError``: both mean the standby's base
        state predates a reload on the primary, and the caller must answer
        NEED_SNAPSHOT rather than apply rows against the wrong baseline."""
        state_codec.apply_replication_delta(self, delta)

    # -- introspection (FetchClusterMetricCommandHandler analog) ------------
    def sketch_stats(self) -> Dict[str, object]:
        """Host snapshot of the param-sketch observability block: variant,
        fat/slim HBM bytes, SALSA merge counters. Pulled by the process-wide
        ``ServerMetrics`` on every scrape and by ``clusterServerStats``."""
        from sentinel_tpu.sketch import sketch_stats as _sketch_stats

        with self._lock:
            stats = _sketch_stats(self.param_config, self._param_state)
        stats["impl"], stats["implReason"] = self.param_impl()
        return stats

    def metrics_snapshot(self) -> Dict[int, Dict[str, float]]:
        from sentinel_tpu.engine.state import (
            ClusterEvent,
            OutcomeChannel,
            flow_spec,
        )
        from sentinel_tpu.stats import window as W

        with self._lock:
            now = self._engine_now()
            spec = flow_spec(self.config)
            sums = np.asarray(W.window_sum_all(spec, self._state.flow, jnp.int32(now)))
            osums = np.asarray(
                W.window_sum_all(spec, self._state.outcome, jnp.int32(now))
            )
            interval_s = spec.interval_ms / 1000.0
            out = {}
            for fid, slot in self._index.slot_of.items():
                n_complete = float(osums[slot, OutcomeChannel.COMPLETE])
                rt_sum = float(osums[slot, OutcomeChannel.RT_SUM])
                out[fid] = {
                    "pass_qps": float(sums[slot, ClusterEvent.PASS]) / interval_s,
                    "block_qps": float(sums[slot, ClusterEvent.BLOCK]) / interval_s,
                    "pass_req_qps": float(sums[slot, ClusterEvent.PASS_REQUEST]) / interval_s,
                    # hierarchy tier reads this for fleet-wide occupancy:
                    # live LEASED charge (client leases + share holds)
                    "leased_tokens": float(sums[slot, ClusterEvent.LEASED]),
                    # completion-outcome plane (MetricNode success/exception
                    # parity): windowed success rate, exception rate, avg RT
                    "success_qps": n_complete / interval_s,
                    "exception_qps": (
                        float(osums[slot, OutcomeChannel.EXCEPTION])
                        / interval_s
                    ),
                    "rt_avg_ms": rt_sum / n_complete if n_complete else 0.0,
                }
                rule = self._rule_of.get(fid)
                mv = (
                    self._moving.get(rule.namespace)
                    if rule is not None else None
                )
                if mv is not None:
                    # MOVING / committed-away: the counters froze at the
                    # begin-move device step and the DESTINATION now counts
                    # this flow. Stamp the shard-map epoch so
                    # aggregate_snapshots can drop this pod's stale copy
                    # instead of double-reporting during the redirect window.
                    out[fid]["moved_epoch"] = float(mv[1])
            return out

    # -- rev-6 completion-outcome ingest (OUTCOME_REPORT wire op) ------------
    _OUTCOME_MIN_RUNG = 64

    def _outcome_rungs(self) -> Tuple[int, ...]:
        """The padding ladder of the outcome step: 64, 256, ... up to the
        first rung that holds a full report (a report rides a request frame,
        so it has at most ``batch_size`` rows, and the wire's
        ``MAX_OUTCOME_PER_FRAME``). A larger in-process report pads on up
        the same ladder and compiles at its first use
        (``_ensure_outcome_warm``)."""
        from sentinel_tpu.cluster import protocol as P

        most = min(self.config.batch_size, P.MAX_OUTCOME_PER_FRAME)
        rungs = [self._OUTCOME_MIN_RUNG]
        while rungs[-1] < most:
            rungs.append(rungs[-1] * 4)
        return tuple(rungs)

    def _outcome_args(self, cap: int, table):
        """All-padding arguments of one rung (what a warm call passes)."""
        f = self.config.max_flows
        br = (
            (table.br_strategy, table.br_slow_rt_ms)
            if table.br_strategy is not None else ()
        )
        return (
            jnp.asarray(np.full(cap, f, np.int32)),
            jnp.asarray(np.zeros(cap, np.int32)),
            jnp.asarray(np.zeros(cap, np.int32)),
            jnp.asarray(np.zeros(cap, bool)),
        ), br

    def _warm_outcome_steps(self, ws, table, now: int, rungs=None):
        """Compile the outcome step for ``rungs`` (default: the whole
        ladder) against ``table``'s shape (with its ``br_*`` columns or
        without) on the throwaway state ``ws``, which the step donates;
        returns the state that comes back."""
        if self._outcome_step is None:
            # looked up at the call: tests stand a slow compile in its place
            self._outcome_step = _outcome.outcome_step_donating(
                self.config, tally=True)
        has_br = table.br_strategy is not None
        for cap in (self._outcome_rungs() if rungs is None else rungs):
            cols, br = self._outcome_args(cap, table)
            ws, tally = self._outcome_step(ws, *cols, jnp.int32(now), *br)
            jax.block_until_ready(tally)
            self._outcome_warm.add((cap, has_br))
        return ws

    def _ensure_outcome_warm(self, cap: int) -> None:
        """Compile the outcome step for rung ``cap`` and the rule table as
        it stands, unless it is. No compile under the service lock, and none
        between a step's clock and its launch: a report is written into the
        bucket of the clock read under the lock, and a compile of seconds
        after that read put it in a bucket already older than the breakers'
        stat interval by the next decide step (ROADMAP Reach A1: OK where
        DEGRADED was due, on the chip, where this compile takes seconds).
        ``warmup()`` has normally been here first; this is for the service
        nobody warmed, or whose degrade rules changed shape since."""
        table = self._table
        key = (cap, table.br_strategy is not None)
        if key in self._outcome_warm:
            return
        with self._outcome_warm_lock:
            if key not in self._outcome_warm:
                with self._lock:
                    now = self._engine_now()
                self._warm_outcome_steps(
                    self._place_state(make_state(self.config)), table, now,
                    rungs=(cap,),
                )

    def report_outcomes(self, flow_ids, rt_ms, exceptions, xid: int = 0,
                        t_in_ns: Optional[int] = None) -> int:
        """Ingest one batched completion report: validate at the wire
        boundary, scatter accepted rows into the per-flow outcome window via
        the donated fused step, and feed every host metric plane (timeline,
        SLO burn, flight recorder, ServerMetrics counters).

        Returns the number of rows accepted. Fire-and-forget from the wire's
        point of view — both doors call this with no response frame, so the
        lease/request fast path stays at zero extra RPCs. ``t_in_ns``
        (``time.monotonic_ns()``) is when the report reached the server, for
        its age at ingest; absent, the call's own start.

        Wire-boundary validation (never scattered, counted into
        ``sentinel_outcome_dropped_total{reason}``):

        - ``negative``: RT < 0 after the int cast (also where a client's
          NaN/int-cast garbage lands — the cast maps non-finite to INT_MIN)
        - ``non_finite``: RT arrived as a non-finite float (in-process
          callers; the wire always carries int32)
        - ``too_large``: RT > ``protocol.OUTCOME_MAX_RT_MS`` — a bogus
          report that would poison ``rt_sum`` for the whole window
        - ``unknown_flow``: no rule slot holds this flow_id
        """
        from sentinel_tpu.cluster import protocol as P

        if t_in_ns is None:
            t_in_ns = time.monotonic_ns()
        flow_ids = np.asarray(flow_ids, np.int64).reshape(-1)
        k = int(flow_ids.shape[0])
        rt_in = np.asarray(rt_ms).reshape(-1)
        exc_in = np.asarray(exceptions).reshape(-1).astype(bool)
        if rt_in.shape[0] != k or exc_in.shape[0] != k:
            raise ValueError("outcome report arrays must share one length")
        if rt_in.dtype.kind == "f":
            finite = np.isfinite(rt_in)
            # non-finite floats must not reach the int cast (UB-ish numpy
            # warning + garbage); park them at -1, counted separately below
            rt = np.where(finite, rt_in, -1.0).astype(np.int64)
        else:
            finite = np.ones(k, bool)
            rt = rt_in.astype(np.int64)
        negative = finite & (rt < 0)
        too_large = finite & (rt > P.OUTCOME_MAX_RT_MS)
        slots = self.lookup_slots(flow_ids)
        unknown = slots < 0
        valid = finite & ~negative & ~too_large & ~unknown
        n_ok = int(valid.sum())
        drops = (
            ("non_finite", int((~finite).sum())),
            ("negative", int(negative.sum())),
            ("too_large", int((too_large & ~negative).sum())),
            ("unknown_flow", int((unknown & finite & ~negative & ~too_large).sum())),
        )
        # pad to a geometric shape ladder so the jitted scatter retraces a
        # bounded number of times, not once per distinct report size
        # (_outcome_rungs: warmup() compiles the rungs a wire report reaches)
        cap = self._OUTCOME_MIN_RUNG
        while cap < k:
            cap *= 4
        pad = cap - k
        f = self.config.max_flows
        slots_p = np.concatenate(
            [np.where(valid, slots, f).astype(np.int32),
             np.full(pad, f, np.int32)]
        )
        rt_p = np.concatenate(
            [np.where(valid, rt, 0).astype(np.int32),
             np.zeros(pad, np.int32)]
        )
        exc_p = np.concatenate(
            [(exc_in & valid).astype(np.int32), np.zeros(pad, np.int32)]
        )
        valid_p = np.concatenate([valid, np.zeros(pad, bool)])
        args = (jnp.asarray(slots_p), jnp.asarray(rt_p), jnp.asarray(exc_p),
                jnp.asarray(valid_p))
        # what earlier steps did to the breakers, where the device is done
        # with them: never waited for here
        self._settle_outcome_tallies(wait=False)
        tally = None
        t_prep = time.monotonic_ns()
        while True:
            if n_ok:
                self._ensure_outcome_warm(cap)
            with self._lock:
                has_br = self._table.br_strategy is not None
                if n_ok and (cap, has_br) not in self._outcome_warm:
                    continue  # the rules changed shape while it compiled
                t_locked = time.monotonic_ns()
                seq = self._outcome_seq = self._outcome_seq + 1
                for reason, n in drops:
                    if n:
                        d = self._outcome_counts["dropped"]
                        d[reason] = d.get(reason, 0) + n
                self._outcome_counts["batches"] += 1
                if n_ok:
                    now = self._engine_now()
                    # breakers loaded: the step additionally counts the SLOW
                    # channel against each flow's DegradeRule cutoff and
                    # resolves HALF_OPEN probes (a separate jit trace; the
                    # 6-arg form stays bit-identical to the pre-breaker step)
                    br = (
                        (self._table.br_strategy, self._table.br_slow_rt_ms)
                        if has_br else ()
                    )
                    self._state, tally = self._outcome_step(
                        self._state, *args, jnp.int32(now), *br
                    )
                    self._outcome_counts["reported"] += n_ok
                    n_exc = int((exc_in & valid).sum())
                    self._outcome_counts["exceptions"] += n_exc
                    self._outcome_counts["rt_sum_ms"] += int(rt[valid].sum())
                    if self._dirty is not None:
                        touched = {int(s) for s in np.unique(slots[valid])}
                        self._dirty.setdefault("outcome", set()).update(touched)
                        if self._has_breakers:
                            # a report can resolve a probe (HALF_OPEN →
                            # CLOSED/OPEN), so reported breaker slots are
                            # breaker-dirty too
                            self._dirty.setdefault("breaker", set()).update(
                                touched & self._breaker_slots
                            )
                ns_names, slot_ns = self._ns_snapshot
            break
        t_out = time.monotonic_ns()
        if tally is not None:
            tally.copy_to_host_async()
            self._outcome_tallies.append(tally)
        _SM.outcome_lock_wait_ms.record((t_locked - t_prep) * 1e-6)
        _SM.outcome_launch_ms.record((t_out - t_locked) * 1e-6)
        _SM.outcome_age_ms.record(max(0, t_out - t_in_ns) * 1e-6)
        _SM.count_outcome_report(n_ok)
        if _TR.ARMED:
            # one span per report, door to step issued: both ends carry the
            # ingest's sequence number. xid 0 on the step's own record: it
            # is no data-plane event, and must not fall to the xid sample
            sid = seq & 0x7FFF
            _TR.record(_TR.OUTCOME_IN, xid=xid, shard=sid, aux=k, t_ns=t_in_ns)
            _TR.record(_TR.OUTCOME, shard=sid, aux=n_ok, t_ns=t_out)
        if not n_ok:
            return 0
        log_cluster("outcome_reported", count=n_ok)
        # per-namespace fan-out to the timeline + SLO burn planes (host-side
        # aggregation off the already-validated rows; no device read)
        from sentinel_tpu.metrics.timeline import timeline as _timeline
        from sentinel_tpu.trace.slo import slo_plane as _slo_plane

        ns_idx = slot_ns[slots[valid]]
        rt_ok = rt[valid]
        exc_ok = exc_in[valid]
        tl = _timeline()
        plane = _slo_plane()
        for ni in np.unique(ns_idx):
            if ni < 0:
                continue
            name = ns_names[int(ni)]
            m = ns_idx == ni
            rts = rt_ok[m]
            n_exc_ns = int(exc_ok[m].sum())
            tl.record(
                name, 0, 0, 0, 0,
                n_complete=int(m.sum()),
                n_exception=n_exc_ns,
                rt_sum_ms=float(rts.sum()),
            )
            plane.record_completion(name, rts, n_exception=n_exc_ns)
        return n_ok

    def _settle_outcome_tallies(self, wait: bool) -> None:
        """Count what finished outcome steps say they did to the breakers
        (``engine.outcome.TALLY_*``; each tally was sent towards the host
        when its step was issued). Without ``wait`` only those the device is
        done with: the next report's ingest calls this, a step or more
        later, and never blocks on the device; :meth:`outcome_stats` (every
        scrape) waits for the rest."""
        pending = self._outcome_tallies
        while pending:
            try:
                if not wait and not pending[0].is_ready():
                    return
                said = np.asarray(pending.popleft())
            except IndexError:
                return  # another thread took the last one
            _SM.count_breaker_resolved(
                int(said[TALLY_CLOSED]), int(said[TALLY_REOPENED])
            )

    def outcome_stats(self) -> Dict[str, object]:
        """Host snapshot of the outcome plane: ingest counters (the
        reconciliation gate's server-side truth) plus per-flow windowed
        RT/exception reads for the ``sentinel_flow_rt_*`` scrape families.
        Pulled by the process-wide ``ServerMetrics`` on every scrape."""
        from sentinel_tpu.engine.state import (
            N_RT_BUCKETS,
            OutcomeChannel,
            RT_BUCKET_UPPER_MS,
            flow_spec,
        )
        from sentinel_tpu.stats import window as W

        self._settle_outcome_tallies(wait=True)
        with self._lock:
            c = self._outcome_counts
            out: Dict[str, object] = {
                "reported": int(c["reported"]),
                "exceptions": int(c["exceptions"]),
                "rt_sum_ms": int(c["rt_sum_ms"]),
                "batches": int(c["batches"]),
                "dropped": dict(c["dropped"]),
            }
            if not self._index.slot_of:
                out["flows"] = {}
                return out
            now = self._engine_now()
            spec = flow_spec(self.config)
            sums = np.asarray(
                W.window_sum_all(spec, self._state.outcome, jnp.int32(now))
            )
            interval_s = spec.interval_ms / 1000.0
            h0 = int(OutcomeChannel.RT_HIST0)
            flows: Dict[int, Dict[str, float]] = {}
            for fid, slot in self._index.slot_of.items():
                complete = int(sums[slot, OutcomeChannel.COMPLETE])
                exc = int(sums[slot, OutcomeChannel.EXCEPTION])
                if not complete and not exc:
                    continue  # idle flows stay off the scrape surface
                rt_sum = float(sums[slot, OutcomeChannel.RT_SUM])
                hist = sums[slot, h0 : h0 + N_RT_BUCKETS]
                total = int(hist.sum())
                if total:
                    target = -(-99 * total // 100)  # ceil(0.99 * total)
                    b = int(np.searchsorted(np.cumsum(hist), target))
                    b = min(b, N_RT_BUCKETS - 1)
                    edge = RT_BUCKET_UPPER_MS[b]
                    p99 = (
                        float(edge) if edge != float("inf")
                        else float((1 << N_RT_BUCKETS) - 1)
                    )
                else:
                    p99 = 0.0
                flows[int(fid)] = {
                    "complete_qps": complete / interval_s,
                    "exception_qps": exc / interval_s,
                    "rt_avg_ms": rt_sum / complete if complete else 0.0,
                    "rt_p99_ms": p99,
                }
            out["flows"] = flows
            return out

    # -- circuit-breaker observability (engine/degrade.py host plane) --------
    _BR_STATE_NAMES = ("closed", "open", "half_open")

    def _breaker_scan(self, force: bool = False) -> None:
        """Diff the device breaker state column against the host mirror and
        fold observed transitions into ``ServerMetrics`` (the
        ``sentinel_breaker_transitions_total{from,to}`` edges) plus a
        rate-limited blackbox dump on a trip to OPEN. The device is the
        authority — transitions happen inside the decide/outcome steps with
        no host round-trip — so this scan sees edges at its own cadence: a
        breaker that OPENs and recovers between two scans reports the net
        edge, not the intermediate states. ``force`` skips the ~1/s rate
        limit (scrape and drill paths; the serving materializer only scans
        when a batch actually produced DEGRADED verdicts)."""
        if not self._has_breakers:
            return
        # the rate limit is looked at before the service lock is asked for:
        # a materializer calls this for every dispatch that shed a row, and
        # waiting for the lock there, to be told "not yet", puts every reply
        # lane behind the decide and outcome launches
        if not force and time.monotonic() - self._breaker_scan_ts < 1.0:
            return
        edges: Dict[Tuple[int, int], int] = {}
        tripped: List[object] = []
        flips: List[Tuple[int, int]] = []  # (flow_id, new state) per edge
        with self._lock:
            now_s = time.monotonic()
            if not force and now_s - self._breaker_scan_ts < 1.0:
                return  # another lane scanned while this one waited
            self._breaker_scan_ts = now_s
            st = np.array(np.asarray(self._state.breaker.state))
            prev = self._breaker_prev
            self._breaker_prev = st
            if prev is None:
                # first observation since the (re)load: surface non-CLOSED
                # states (a snapshot restore's open breakers) as edges
                # from CLOSED rather than losing them
                prev = np.zeros_like(st)
            changed = np.nonzero(st != prev)[0]
            if changed.size == 0:
                return
            rev = self._breaker_fid  # slot -> flow_id of the breaker slots
            for s in changed.tolist():
                if s not in rev:
                    continue  # stale mirror rows of dropped rules
                frm, to = int(prev[s]), int(st[s])
                edges[(frm, to)] = edges.get((frm, to), 0) + 1
                fid = rev.get(s)
                if fid is not None:
                    flips.append((int(fid), to))
                if to == 1:  # BR_OPEN
                    tripped.append(rev.get(s, s))
        names = self._BR_STATE_NAMES
        for (frm, to), count in edges.items():
            _SM.count_breaker_transition(
                names[frm] if frm < 3 else str(frm),
                names[to] if to < 3 else str(to),
                count,
            )
        # rev-7 push: every observed edge goes to the clients — OPEN parks
        # their local admission clocks (retry-after = the rule's recovery
        # timeout, the earliest the device could HALF_OPEN), CLOSED and
        # HALF_OPEN lift them so probe traffic reaches the wire again
        # (one emit for the scan: its edges ride back to back in one write
        # per connection, not an encode and a send per edge per connection
        # on this reply lane; with the lock-first scan, in the cell
        # breaker-mesh-100k.tenants-zipf-health-cycle-open at 80k rows/s:
        # `account` 2.62 against 1.11 ms a dispatch, p95 7.55 against
        # 5.85 ms, PERF.md section 6, PR 34)
        if flips:
            src = self._degrade_rules_src
            self._emit_push("push_breaker_flips", [
                (fid, to, int(getattr(
                    src.get(fid), "recovery_timeout_ms", 0) or 0)
                 if to == 1 else 0)
                for fid, to in flips
            ])
        if tripped:
            from sentinel_tpu.trace import blackbox as _blackbox

            _blackbox.maybe_dump(
                "breaker_open:" + ",".join(str(f) for f in tripped)
            )

    def breaker_stats(self) -> Dict[str, object]:
        """Host snapshot of the breaker plane: per-flow state (read from
        the device ``BreakerState`` columns) plus clock ages, for the
        ``sentinel_breaker_state`` gauge and the ``breaker`` block of
        ``clusterServerStats``. Scans for transitions first, so a scrape
        is also the liveness floor of the transition counters."""
        if not self._has_breakers:
            return {}
        self._breaker_scan(force=True)
        from sentinel_tpu.stats.window import NEVER as _WNEVER

        names = self._BR_STATE_NAMES
        with self._lock:
            br = self._state.breaker
            st = np.asarray(br.state)
            opened = np.asarray(br.opened_ms)
            probe = np.asarray(br.probe_ms)
            now = self._engine_now()
            flows: Dict[int, Dict[str, object]] = {}
            for fid, rule in self._degrade_rules_src.items():
                slot = self._index.slot_of.get(fid)
                if slot is None:
                    continue
                code = int(st[slot])
                entry: Dict[str, object] = {
                    "state": names[code] if code < 3 else str(code),
                    "state_code": code,
                    "strategy": int(rule.strategy),
                }
                if int(opened[slot]) != int(_WNEVER):
                    entry["since_transition_ms"] = (
                        int(now) - int(opened[slot])
                    )
                if int(probe[slot]) != int(_WNEVER):
                    entry["probe_age_ms"] = int(now) - int(probe[slot])
                flows[int(fid)] = entry
            return {"rules": len(self._degrade_rules_src), "flows": flows}
