"""Native-front-door token server: C++ epoll data plane, Python device loop.

The round-3 gap: the asyncio front door served ~1/8 of the device kernel's
ceiling — per-frame Python costs dominated. Here the whole per-frame path
(socket reads, length-prefixed framing, request decode, verdict
frame encode, socket writes, idle reaping) lives in
``native/src/sentinel_frontdoor.cpp``; Python's serving loop is one blocking
``wait_batch`` → ``TokenService.request_batch_arrays`` → ``submit`` cycle
per DEVICE STEP, regardless of how many frames or connections fed it.
This is the netty-pipeline analog (``NettyTransportServer.java:73-101``)
taken to its TPU conclusion: the host's job is to keep the device fed.

Every frame that asks for verdicts is data plane on the TCP door: the
reference client's four single request types (FLOW, PARAM_FLOW,
CONCURRENT_ACQUIRE, CONCURRENT_RELEASE: one request a frame) and this
wire's batch frames (BATCH_FLOW; BATCH_PARAM_FLOW, codec rev 8;
BATCH_CONCURRENT_ACQUIRE / _RELEASE, codec rev 9). A pull is flow rows,
param rows or concurrency rows, never two kinds, and a dispatch is one
kind; a single frame is a one-row frame of its kind's arena, so single and
batch frames share pulls, dispatches, serve buckets, permits and reply lanes
(a param pull is a run of frames with one number of values a request), and
the door answers each frame in its own layout under its own type. STANDBY,
brownout, age shed and overload answer a single PARAM_FLOW or CONCURRENT_*
frame as they answer a single FLOW frame.

Control-plane frames (PING handshake, replication, moves, leases, shares,
completion reports) and open/close events surface through one low-rate
control thread, which sleeps on a bell the doors ring when they queue one,
so namespace connection groups (AVG_LOCAL scaling) and the host-side
paths stay exactly as in the asyncio server. The shm door stays flow-only:
the single PARAM_FLOW and CONCURRENT_* frames of its clients still reach
the control thread, as does a TCP door's PARAM_FLOW frame that carries no value
(no row of the sketch); what a door has queued of them is drained and
decided through the services' batched entries.
API-compatible with ``TokenServer`` (start/stop/
port/connections/tuning_kwargs) so ``apply_cluster_mode`` and the benches
can switch via ``native=True``.

Serving pipeline: three decoupled lanes with bounded handoff queues,
instead of one thread doing wait→step→submit in series. The **intake
lane** pulls decoded frames from the C++ door and hands copies to the
**device lane**, which drains everything queued (bounded by
``fuse_depth`` pulls of host prep; a group that is still under one full
pull of rows, a trickle of one-row frames, keeps folding past it),
concatenates it, and issues ONE dispatch — the token service's fusion ladder then folds full engine
frames into a single chained ``lax.scan`` device step, so the fixed
per-dispatch overhead is paid once per fused group. ``n_dispatchers``
**reply lanes** block on the async verdicts, slice them back per pull, and
submit — so host-side prep and reply encoding overlap device time instead
of serializing behind it.
Fusion depth adapts to load by construction: an idle queue yields
single-frame dispatches (no added latency), a backed-up queue yields
deep fused steps (max amortization).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from sentinel_tpu import chaos
from sentinel_tpu.cluster import protocol as P
from sentinel_tpu.cluster.connection import ConnectionManager
from sentinel_tpu.cluster.token_service import (
    Materializer,
    TokenService,
    concurrent_batch_entry,
    decide_concurrent_requests,
    decide_param_requests,
    halves,
    params_batch_entry,
)
from sentinel_tpu.core.log import record_log
from sentinel_tpu.engine import TokenStatus
from sentinel_tpu.metrics.profiler import ProfilerHook
from sentinel_tpu.metrics.server import server_metrics
from sentinel_tpu.overload import AdmissionController, BrownoutLevel
from sentinel_tpu.trace import ring as _TR
from sentinel_tpu.trace.slo import slo_plane as _slo_plane

_SM = server_metrics()
_OVERLOAD = int(TokenStatus.OVERLOAD)
_STANDBY = int(TokenStatus.STANDBY)
# ``f_type`` (the wire's type byte) of the frames whose rows are releases,
# batch and single, and of a single PARAM_FLOW frame
_TYPE_RELEASE = int(P.MsgType.CONCURRENT_RELEASE)
_TYPE_BATCH_RELEASE = int(P.MsgType.BATCH_CONCURRENT_RELEASE)
_TYPE_PARAM_FLOW = int(P.MsgType.PARAM_FLOW)
# the flight recorder's lane of each kind of dispatch the device lane makes
# (``ServerMetrics.LANE_KINDS``)
_LANE_OF = {"flow": 0, "param": _TR.PARAM_LANE,
            "concurrent": _TR.CONCURRENT_LANE}
# the longest the control thread sleeps on the doors' bell before it looks
# at ``_stop`` again: the lanes' own cadence (``wait_any_into``'s default)
_CONTROL_WAIT_MS = 100
# how long the control thread stays asleep after a ring before it looks at
# its doors, ms: what the 2 ms poll cost an event at the mean
# (``_control_loop`` says what the wait is good for)
_CONTROL_SETTLE_MS = 1


def _lane_kind(pull) -> str:
    """Which of ``ServerMetrics.LANE_KINDS`` a pull's rows are, from its
    count of values a request: 0 flow rows, above 0 hot-parameter rows,
    below 0 the rows of concurrency frames."""
    nv = pull[9]
    return "flow" if nv == 0 else "param" if nv > 0 else "concurrent"


# single frames that still reach the control loop (the shm door's, and a TCP
# door's PARAM_FLOW frame with no value), drained a queue at a time and
# decided through the services' batched entries (_answer_params,
# _answer_concurrent)
_DRAINED_SINGLES = frozenset({
    P.MsgType.PARAM_FLOW, P.MsgType.CONCURRENT_ACQUIRE,
    P.MsgType.CONCURRENT_RELEASE,
})


def native_available() -> bool:
    try:
        from sentinel_tpu.native import lib as native_lib

        return native_lib.available()
    except Exception:
        return False


class NativeTokenServer:
    def __init__(
        self,
        service: TokenService,
        host: str = "127.0.0.1",
        port: int = 18730,
        max_batch: int = 16384,
        n_dispatchers: int = 2,
        fuse_depth: int = 4,
        max_device_inflight: int = 2,
        intake_shards: int = 1,
        intake_timeout_ms: int = 20,
        idle_ttl_s: Optional[float] = 600.0,
        arena_cap: int = 65536,
        profile_dir: Optional[str] = None,
        metrics_port: Optional[int] = None,
        snapshot_dir: Optional[str] = None,
        snapshot_period_s: Optional[float] = None,
        shed_age_ms: Optional[float] = 1000.0,
        drain_timeout_s: float = 10.0,
        overload: Optional[AdmissionController] = None,
        standby_of: Optional[str] = None,
        promote_after_ms: Optional[float] = None,
        replicate_to: Optional[Sequence] = None,
        repl_interval_ms: Optional[float] = None,
        shm_dir: Optional[str] = None,
        shm_spin_us: Optional[int] = None,
        push: bool = True,
    ):
        from sentinel_tpu.native.lib import Frontdoor, require

        # a door asked for by name that cannot be had is an error carrying
        # the build command and the compiler's output — now, not at start()
        require()
        self._Frontdoor = Frontdoor
        # opt-in shared-memory ring door for co-located sidecar clients:
        # one extra intake lane pulls from the ring poller and drains into
        # the SAME dispatch semaphore, so the fusion ladder fuses the union
        # of TCP and shm bursts; replies scatter-encode straight into each
        # client's response ring (zero syscalls steady-state)
        self.shm_dir = shm_dir
        self.shm_spin_us = shm_spin_us
        self._shm_door = None
        if shm_dir is not None:
            from sentinel_tpu.native.lib import ShmDoor  # raises if stale

            self._ShmDoor = ShmDoor
        self.service = service
        self.host = host
        self.port = port
        self.max_batch = max_batch
        self.n_dispatchers = max(1, int(n_dispatchers))
        # SO_REUSEPORT intake sharding: N doors bound to the SAME port, the
        # kernel hash-spreads connections across them, and each door gets a
        # dedicated intake thread with its own bounded handoff queue. The
        # single device lane drains the UNION of the shard queues, so the
        # fusion ladder still sees one merged burst — sharding multiplies
        # intake pull/decode bandwidth without forking the device pipeline.
        self.intake_shards = max(1, int(intake_shards))
        # fuse_depth bounds how many queued intake pulls the device lane
        # folds into one dispatch (each pull is itself up to max_batch
        # rows) — the host-prep budget of the adaptive frame fusion; a
        # group still under max_batch rows in all keeps folding past it
        self.fuse_depth = max(1, int(fuse_depth))
        # double-buffering bound: fused groups dispatched but not yet
        # materialized. 2 overlaps the next group's host prep (queue
        # drain, concat, shed masks, staging) with the previous group's
        # device compute; higher depths only add verdict latency, since
        # dispatch order is already the state-chain order. 1 restores
        # the serialized lane.
        self.max_device_inflight = max(1, int(max_device_inflight))
        self._device_inflight = 0
        self._device_cv = threading.Condition()
        # intake poll granularity only — the C++ door wakes the waiter the
        # moment the first frame queues, so this never delays a ready frame
        self.intake_timeout_ms = max(1, int(intake_timeout_ms))
        self.idle_ttl_s = idle_ttl_s
        self.arena_cap = arena_cap
        # the C++ door strips the wire deadline before Python sees a pull,
        # so the native lanes shed by AGE instead: a pull older than this
        # when the device lane picks it up is answered OVERLOAD without a
        # dispatch (every client budget is long gone at 1s; None disables).
        # Also the bounded-wait budget for the intake→device handoff — a
        # full dispatch queue refuses (answers OVERLOAD) after this long
        # instead of blocking the intake lane forever.
        self.shed_age_ms = shed_age_ms
        # lane join budget in stop() before _abandon flips drops on
        self.drain_timeout_s = max(0.1, float(drain_timeout_s))
        # BBR-style admission gate + brownout ladder (overload/admission.py)
        self.overload = (
            overload if overload is not None else AdmissionController()
        )
        self._door = None  # door 0 (back-compat handle; owns self.port)
        self._doors: List = []
        self._bell = None  # the doors' control bell (start())
        self._threads: List[threading.Thread] = []
        self._lane_threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self._intake_stop = threading.Event()
        self._abandon = threading.Event()  # give up lane drain (dead lane)
        self._shard_qs: List[queue.Queue] = []
        self._dispatch_sem: Optional[threading.Semaphore] = None
        self._dispatch_q: Optional[queue.Queue] = None  # alias: shard 0's q
        self._reply_q: Optional[queue.Queue] = None
        self._staging = None  # StagingPool of intake decode blocks
        notify = getattr(service, "connected_count_changed", None)
        self.connections = ConnectionManager(on_count_changed=notify)
        self._addr_by_conn = {}  # (fd, gen) → address
        self._addr_lock = threading.Lock()
        # same observability surface as the asyncio front door: opt-in
        # profiler command target + optional standalone /metrics endpoint
        self.profile_dir = profile_dir
        self.profiler = ProfilerHook(default_dir=profile_dir)
        self.metrics_port = metrics_port
        self._metrics_exporter = None
        self._gauge_fns: dict = {}
        # HA state snapshots: same contract as the asyncio front door
        self.snapshot_dir = snapshot_dir or os.environ.get(
            "SENTINEL_SNAPSHOT_DIR"
        ) or None
        self.snapshot_period_s = snapshot_period_s
        self._snapshots = None
        # warm-standby replication roles: same contract as TokenServer —
        # standby_of= refuses data-plane traffic with TokenStatus.STANDBY
        # until promoted while rev-3 frames stream state in; replicate_to=
        # ships deltas out (see cluster/server.py for the full rationale)
        self.standby_of = standby_of
        self.promote_after_ms = promote_after_ms
        self.replicate_to = list(replicate_to) if replicate_to else None
        self.repl_interval_ms = repl_interval_ms
        self.applier = None
        self.replicator = None
        self._repl_sessions: dict = {}  # (fd, gen) → ReplSession
        # rev-4 namespace-move channel (cluster.rebalance): one MoveSession
        # per inbound connection, same lifecycle as _repl_sessions
        from sentinel_tpu.cluster.rebalance import MoveTarget

        self.move_target = MoveTarget(service)
        self._move_sessions: dict = {}  # (fd, gen) → MoveSession
        # rev-7 push plane (cluster.push): sinks registered per (fd, gen)
        # at CTRL_OPEN hand encoded push frames to door.send — the same
        # non-blocking C++ send queue the control replies use, which also
        # covers shm ring connections (their door routes sends onto the
        # response lane). push=False disarms every emit.
        from sentinel_tpu.cluster.push import PushHub

        self.push_hub = PushHub(enabled=push)
        attach_hub = getattr(service, "attach_push_hub", None)
        if attach_hub is not None:
            attach_hub(self.push_hub)
        self.overload.on_level_change = (
            lambda level, retry_ms: self.push_hub.push_brownout(
                level, retry_ms
            )
        )

    def tuning_kwargs(self) -> dict:
        return dict(
            max_batch=self.max_batch,
            n_dispatchers=self.n_dispatchers,
            fuse_depth=self.fuse_depth,
            max_device_inflight=self.max_device_inflight,
            intake_shards=self.intake_shards,
            intake_timeout_ms=self.intake_timeout_ms,
            idle_ttl_s=self.idle_ttl_s,
            arena_cap=self.arena_cap,
            profile_dir=self.profile_dir,
            metrics_port=self.metrics_port,
            snapshot_dir=self.snapshot_dir,
            snapshot_period_s=self.snapshot_period_s,
            shed_age_ms=self.shed_age_ms,
            drain_timeout_s=self.drain_timeout_s,
            overload=self.overload,
            standby_of=self.standby_of,
            promote_after_ms=self.promote_after_ms,
            replicate_to=self.replicate_to,
            repl_interval_ms=self.repl_interval_ms,
            shm_dir=self.shm_dir,
            shm_spin_us=self.shm_spin_us,
            push=self.push_hub.enabled,
        )

    @property
    def is_standby(self) -> bool:
        """True while this server refuses data-plane traffic (unpromoted
        warm standby)."""
        return self.applier is not None and not self.applier.promoted

    def promote(self, reason: str = "manual") -> bool:
        """Promote a standby to serving. Returns True if the server was a
        standby and is now (or already was) promoted."""
        if self.applier is None:
            return False
        self.applier.promote(reason)
        return True

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        if self._door is not None:
            return
        warmup = getattr(self.service, "warmup", None)
        if warmup is not None:
            warmup()
        if self.snapshot_dir and hasattr(self.service, "import_state"):
            from sentinel_tpu.ha.snapshot import restore_latest

            if not self.service.current_rules():  # cold service only
                restore_latest(self.service, self.snapshot_dir)
        reopen = getattr(self.service, "reopen", None)
        if reopen is not None:
            reopen()
        if self.standby_of is not None:
            # before the listener: the first control frame a standby sees
            # may be the primary's REPL_HELLO
            from sentinel_tpu.ha.replication import StandbyApplier

            self.applier = StandbyApplier(
                self.service, promote_after_ms=self.promote_after_ms,
            ).start()
        self._stop.clear()
        self._intake_stop.clear()
        self._abandon.clear()
        # bounded handoffs: each shard's dispatch queue depth caps how far
        # its intake runs ahead of the device (their union IS the fusion
        # opportunity); reply queue depth caps device-step in-flight count.
        # The semaphore counts queued pulls across ALL shard queues so the
        # device lane blocks on one primitive instead of polling N queues.
        # the shm door (when enabled) is one more intake lane with its own
        # shard queue at index intake_shards — the device lane's union
        # drain and sentinel accounting see it as just another shard
        n_lanes = self.intake_shards + (1 if self.shm_dir is not None else 0)
        self._shard_qs = [
            queue.Queue(maxsize=max(2, 2 * self.fuse_depth))
            for _ in range(n_lanes)
        ]
        self._dispatch_q = self._shard_qs[0]
        self._dispatch_sem = threading.Semaphore(0)
        self._reply_q = queue.Queue(maxsize=max(2, 2 * self.n_dispatchers))
        # recycled intake decode blocks: the C++ arena memcpys each pull
        # straight into one of these (wait_batch_into) and the block rides
        # the pull through device prep and reply submit, then returns to
        # the pool — zero steady-state allocation on the intake path
        from sentinel_tpu.cluster.protocol import StagingPool

        self._staging = StagingPool(
            self._alloc_staging_block,
            capacity=2 * self.fuse_depth + self.n_dispatchers
            + n_lanes + 2,
        )
        # door 0 binds the requested port (possibly 0 → ephemeral); the
        # remaining shards bind the LEARNED concrete port via SO_REUSEPORT
        # (set unconditionally in sn_fd_create) so the kernel spreads
        # accepted connections across the shard listeners
        doors = [self._Frontdoor(self.host, self.port,
                                 arena_cap=self.arena_cap)]
        self.port = doors[0].port
        for _ in range(1, self.intake_shards):
            doors.append(
                self._Frontdoor(self.host, self.port,
                                arena_cap=self.arena_cap)
            )
        if self.shm_dir is not None:
            kw = {}
            if self.shm_spin_us is not None:
                kw["spin_us"] = self.shm_spin_us
            self._shm_door = self._ShmDoor(
                self.shm_dir, arena_cap=self.arena_cap, **kw
            )
            doors.append(self._shm_door)  # control loop + stats cover it
        self._doors = doors
        self._door = doors[0]
        # one bell a server: every door rings it after a push to its control
        # queue, the control thread sleeps on it. None where the loaded
        # library is older than the entry: the thread then polls
        from sentinel_tpu.native.lib import control_bell

        self._bell = control_bell()
        if self._bell is not None:
            for d in doors:
                d.set_bell(self._bell)
        # the TCP doors count their spans (door_in / door_out /
        # door_residence) on the registry's own bounds, and the registry
        # folds what they counted in on every read
        for d in doors[:self.intake_shards]:
            for name in d.SPANS:
                d.set_span_bounds(name, getattr(_SM, name).bounds)
        _SM.register_door_spans(self._door_spans)
        if self.idle_ttl_s:
            for d in doors:
                d.set_idle_ttl(int(self.idle_ttl_s * 1000))
        lanes = [
            threading.Thread(
                target=self._intake_loop,
                args=(i, doors[i], self._shard_qs[i]),
                name=f"sentinel-native-intake-{i}", daemon=True,
            )
            for i in range(self.intake_shards)
        ]
        if self._shm_door is not None:
            # shard index intake_shards: its pulls/occupancy surface under
            # the per-shard intake series like any TCP shard's
            lanes.append(
                threading.Thread(
                    target=self._intake_loop,
                    args=(self.intake_shards, self._shm_door,
                          self._shard_qs[self.intake_shards]),
                    name="sentinel-native-intake-shm", daemon=True,
                )
            )
        lanes.append(
            threading.Thread(
                target=self._device_loop, name="sentinel-native-device",
                daemon=True,
            )
        )
        lanes.extend(
            threading.Thread(
                target=self._reply_loop,
                name=f"sentinel-native-reply-{i}", daemon=True,
            )
            for i in range(self.n_dispatchers)
        )
        for t in lanes:
            t.start()
        self._lane_threads = lanes
        t = threading.Thread(
            target=self._control_loop, name="sentinel-native-control",
            daemon=True,
        )
        t.start()
        self._threads.append(t)
        if self.profile_dir:
            try:
                self.profiler.start(self.profile_dir)
            except Exception:
                record_log.exception("profiler start failed; serving anyway")
        # gauges: the native door keeps its own counters (stats()); surface
        # the in-flight depth and the namespace connection groups. The C++
        # plane owns the request queue, so queue_depth reads pending frames
        # when the door exports them, else 0.
        self._gauge_fns = {
            "queue_depth": lambda: float(
                (self.stats() or {}).get("pending_frames", 0)
            ),
            "dispatch_lane_depth": lambda: float(
                sum(q.qsize() for q in self._shard_qs)
            ),
            "reply_lane_depth": lambda: float(
                self._reply_q.qsize() if self._reply_q else 0
            ),
            "device_inflight": lambda: float(self._device_inflight),
            "connections": lambda: sum(
                len(addrs) for addrs in self.connections.snapshot().values()
            ),
        }
        if self._shm_door is not None:
            def _ring_occupancy(door=self._shm_door):
                try:
                    st = door.stats()
                except Exception:
                    return 0.0
                total = st.get("shm_req_slots_total", 0)
                return st.get("shm_req_slots_used", 0) / total if total else 0.0

            self._gauge_fns["shm_ring_occupancy"] = _ring_occupancy
            # counter series (sentinel_server_shm_{polls,doorbells,
            # ring_full}_total) render from the door's relaxed atomics via
            # this provider — each independently monotonic, no snapshot
            _SM.register_shm_provider(self._shm_stats_provider)
        for name, fn in self._gauge_fns.items():
            _SM.register_gauge(name, fn)
        # hub half of the clusterServerStats `push` block (single-slot
        # provider, same contract as the asyncio door's)
        _SM.register_push_provider(self.push_hub.stats)
        if self.metrics_port is not None:
            from sentinel_tpu.metrics.exporter import PrometheusExporter

            self._metrics_exporter = PrometheusExporter(
                host="0.0.0.0", port=self.metrics_port
            ).start()
            self.metrics_port = self._metrics_exporter.port
        if self.snapshot_dir and hasattr(self.service, "export_state"):
            from sentinel_tpu.ha.snapshot import SnapshotManager

            self._snapshots = SnapshotManager(
                self.service, self.snapshot_dir,
                period_s=self.snapshot_period_s,
            ).start()
        if self.replicate_to and hasattr(self.service, "export_delta"):
            from sentinel_tpu.ha.replication import ReplicationSender

            self.replicator = ReplicationSender(
                self.service, self.replicate_to,
                interval_ms=self.repl_interval_ms,
                sender_id=f"{self.host}:{self.port}",
            ).start()
        record_log.info(
            "native token server listening on %s:%d "
            "(%d intake shards, %d dispatchers)",
            self.host, self.port, self.intake_shards, self.n_dispatchers,
        )

    def _door_spans(self) -> dict:
        """The TCP doors' span counters, summed (``Frontdoor.span_stats``
        shape): what ``ServerMetrics`` folds into ``door_in_ms``,
        ``door_out_ms`` and ``door_residence_ms``."""
        total: dict = {}
        for d in self._doors[:self.intake_shards]:
            for name, (n, sum_ms, max_ms, counts) in d.span_stats().items():
                if name in total:
                    n0, s0, m0, c0 = total[name]
                    n, sum_ms, max_ms, counts = (
                        n0 + n, s0 + sum_ms, max(m0, max_ms), c0 + counts
                    )
                total[name] = (n, sum_ms, max_ms, counts)
        return total

    def _shm_stats_provider(self) -> dict:
        door = self._shm_door
        if door is None:
            return {}
        try:
            st = door.stats()
        except Exception:
            return {}
        return {
            "polls": st.get("shm_polls", 0),
            "doorbells": st.get("shm_doorbells", 0),
            "ring_full": st.get("shm_ring_full", 0),
            "segments": st.get("shm_segments", 0),
        }

    def _alloc_staging_block(self) -> dict:
        """One intake decode block: row arrays sized for the largest pull
        (``max_batch``, clamped so a max-size frame always fits) plus frame
        metadata. ``prios`` is the raw wire byte (what the C++ arena
        holds); ``prios_bool`` is its normalized boolean row, converted in
        place per pull so downstream masking (`~`, shed_mask) sees real
        booleans whatever byte a client sent."""
        rows = max(
            min(int(self.max_batch), int(self.arena_cap)),
            P.MAX_BATCH_PER_FRAME,
        )
        # frames per pull is bounded by rows except for degenerate 0-row
        # frames; the frame capacity below also CAPS how many frames one
        # wait_batch_into may take, so a smaller array just splits a
        # pathological all-empty-frame burst across pulls
        max_f = rows + 64
        return dict(
            ids=np.empty(rows, np.int64),
            counts=np.empty(rows, np.int32),
            prios=np.empty(rows, np.uint8),
            prios_bool=np.empty(rows, bool),
            f_fd=np.empty(max_f, np.int32),
            f_gen=np.empty(max_f, np.int32),
            f_xid=np.empty(max_f, np.int32),
            f_n=np.empty(max_f, np.int32),
            f_type=np.empty(max_f, np.uint8),
            # the door's stamps (monotonic_ns; 0 = none, the shm door):
            # each frame's last byte read, and the pull about to return
            f_rx_ns=np.empty(max_f, np.int64),
            wake_ns=np.zeros(1, np.int64),
            # value hashes of a param pull (BATCH_PARAM_FLOW), request-major;
            # one max-size frame holds under 8192 of them
            hashes=np.empty(max(rows, 8192), np.int64),
        )

    def stop(self) -> None:
        if self._door is None:
            return
        if self.replicator is not None:
            self.replicator.stop()
            self.replicator = None
        if self.applier is not None:
            self.applier.stop()
            self.applier = None
        self._repl_sessions.clear()
        for sess in self._move_sessions.values():
            sess.closed()  # discard any staged (uncommitted) move state
        self._move_sessions.clear()
        if self._snapshots is not None:
            self._snapshots.stop(final_save=True)
            self._snapshots = None
        if self.profiler.active:
            self.profiler.stop()
        if self._metrics_exporter is not None:
            self._metrics_exporter.stop()
            self._metrics_exporter = None
        for name, fn in self._gauge_fns.items():
            _SM.unregister_gauge(name, fn)
        self._gauge_fns = {}
        # drain shutdown, in lane order: stop intake first so every frame
        # already pulled still gets answered, then let the sentinel flow
        # intake → device → reply before the door closes. A wedged lane
        # can't deadlock stop(): after the join timeout we flip _abandon,
        # which turns every blocking lane handoff into a drop.
        self._intake_stop.set()
        for t in self._lane_threads:
            t.join(timeout=self.drain_timeout_s)
            if t.is_alive():
                self._abandon.set()
                t.join(timeout=2)
        self._lane_threads = []
        # staging-leak audit (abandoned shutdown): a dead or abandoned lane
        # can strand pulls inside the shard/reply queues — nobody will
        # answer them, but their staging blocks must still go back to the
        # pool or the freelist never quiesces. Lanes are joined, so a
        # nowait drain here sees every stranded item.
        pool = self._staging
        if pool is not None:
            stranded = []
            for q in self._shard_qs:
                while True:
                    try:
                        item = q.get_nowait()
                    except queue.Empty:
                        break
                    if item is not self._SENTINEL:
                        stranded.append(item)
            for pull in stranded:
                n = len(pull[0])
                self.overload.note_done(n)
                _SM.count_shed("lane_abandon", n)
                pool.release(pull[6])
            if self._reply_q is not None:
                while True:
                    try:
                        item = self._reply_q.get_nowait()
                    except queue.Empty:
                        break
                    if item is self._SENTINEL:
                        continue
                    pulls, lengths = item[:2]
                    self.overload.note_done(sum(lengths))
                    _SM.count_shed("lane_abandon", sum(lengths))
                    for p in pulls:
                        pool.release(p[6])
        self._stop.set()
        if self._bell is not None:
            self._bell.ring()  # the control thread leaves its wait at once
        for d in self._doors:
            d.stop()
        # the IO threads are joined: what they counted is final
        _SM.unregister_door_spans(self._door_spans)
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []
        self._shard_qs = []
        self._dispatch_sem = None
        self._dispatch_q = None
        self._reply_q = None
        self._staging = None
        self._doors = []
        self._door = None
        self._shm_door = None
        self._bell = None
        # the door closed every socket without emitting CTRL_CLOSE (the
        # control thread is already down), so deregister the clients here —
        # a restart would otherwise inherit phantom connections that keep
        # deflating AVG_LOCAL per-connection budgets
        for key in list(self._addr_by_conn):
            address = self._addr_by_conn.pop(key, None)
            if address is not None:
                self.connections.remove_address(address)
        close = getattr(self.service, "close", None)
        if close is not None:
            close()

    # -- data plane ---------------------------------------------------------
    _SENTINEL = object()  # lane shutdown marker, flows intake→device→reply

    def _lane_put(
        self, q: queue.Queue, item, give_up_after_s: Optional[float] = None
    ) -> bool:
        """Blocking bounded-queue handoff (the lanes' backpressure). Never
        deadlocks shutdown: once ``_abandon`` is set (a lane died and its
        join timed out) the put gives up and drops instead. With
        ``give_up_after_s`` the put also refuses after that long against a
        full queue — the caller then answers OVERLOAD instead of wedging
        its lane (sentinel handoffs pass None and keep the forever
        semantics: a dropped sentinel would strand the downstream lane)."""
        deadline = (
            None if give_up_after_s is None
            else time.monotonic() + give_up_after_s
        )
        while True:
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                if self._abandon.is_set():
                    return False
                if deadline is not None and time.monotonic() >= deadline:
                    return False

    def _intake_loop(self, shard: int, door, q: queue.Queue) -> None:
        """Lane 1 (×``intake_shards``): pull decoded frames from this
        shard's C++ door straight into a recycled staging block, hand the
        block to the device lane. The door wakes ``wait_batch_into`` the
        moment the first frame queues — ``intake_timeout_ms`` is only the
        shutdown-poll granularity, never a batching stall.

        Zero-copy shape: the C++ IO thread memcpys its arena directly into
        the staging arrays (no thread-local bounce buffer, no per-pull
        ``np.array`` copies); the block travels with the pull and returns
        to the pool after the reply lane submits its verdicts. Pulls this
        lane answers itself (standby/overload refusals, chaos drops) reuse
        the block immediately — ``sn_fd_submit`` copies synchronously."""
        pool = self._staging
        if self.intake_shards > 1:
            # best-effort shard→core pinning so each intake lane's cache
            # stays hot; harmless no-op on single-core or restricted hosts
            try:
                cpus = sorted(os.sched_getaffinity(0))
                if len(cpus) > 1:
                    os.sched_setaffinity(0, {cpus[shard % len(cpus)]})
            except (AttributeError, OSError):
                pass
        block = pool.acquire()
        try:
            while not self._intake_stop.is_set():
                try:
                    # max_batch bounds one pull (clamped to >= one max
                    # frame); the remainder stays queued for the next cycle
                    got = door.wait_any_into(
                        block, timeout_ms=self.intake_timeout_ms,
                        max_n=self.max_batch,
                    )
                except Exception:
                    if self._stop.is_set() or self._intake_stop.is_set():
                        break
                    record_log.exception(
                        "native wait_batch failed; intake %d down", shard
                    )
                    break
                if got is None:
                    continue
                # the lane's one clock, the recorder's and the door's:
                # first thing with the GIL back. What it reads past the
                # door's own stamp, taken in C just before the pull
                # returned to ctypes, went on that return and on waiting
                # for the GIL (0: a door that does not stamp)
                t_py = time.monotonic_ns()
                wake_ns = int(block["wake_ns"][0])
                if wake_ns:
                    _SM.door_wake_ms.record((t_py - wake_ns) * 1e-6)
                # nv: values per request of a param pull (its rows are the
                # requests of BATCH_PARAM_FLOW frames and of single
                # PARAM_FLOW frames, a row each), 0 for a flow pull, -1 for
                # a concurrency pull (the rows of BATCH_CONCURRENT_ACQUIRE /
                # _RELEASE and of single CONCURRENT_ACQUIRE / _RELEASE
                # frames in arrival order; a release row's id column holds
                # its token id)
                n, k, nv = got
                if chaos.ARMED:
                    chaos.maybe_sleep("lane_delay")
                    if chaos.should("frame_drop"):
                        _SM.count_shed("chaos_drop", n)
                        continue
                # normalize the wire prio bytes into the block's boolean
                # row in place (clients send 0/1 but the wire admits any
                # byte; masking downstream needs real booleans)
                prios = np.not_equal(
                    block["prios"][:n], 0, out=block["prios_bool"][:n]
                )
                # the one host copy this path pays: C arena → staging
                # (13B/row + 17B/frame) plus the 1B/row bool normalize
                _SM.count_copy_bytes(n * (14 + 8 * max(nv, 0)) + k * 17)
                # the frames of the pull, as the door takes them back: the
                # sixth column is each frame's rx stamp, which the door
                # closes its spans from when the reply has gone out
                frames = (
                    block["f_fd"][:k], block["f_gen"][:k],
                    block["f_xid"][:k], block["f_n"][:k],
                    block["f_type"][:k], block["f_rx_ns"][:k],
                )
                if _TR.ARMED:  # flight recorder: frames entered the host
                    if door is self._shm_door:
                        _TR.record(_TR.SHM_POLL, shard=shard, aux=n)
                    _TR.record_many(
                        _TR.RX, frames[2], shard=shard, aux=n,
                        t_ns=frames[5],
                    )
                    _TR.record_many(
                        _TR.CLIENT_IN, frames[2], shard=shard, aux=n
                    )
                if self.is_standby:
                    # unpromoted warm standby: data plane is closed. Refuse
                    # the whole pull with STANDBY so the failover client
                    # walks on to the live primary (no retry hint — this is
                    # not backpressure)
                    _SM.count_shed("standby", n)
                    if _TR.ARMED:
                        _TR.record_many(
                            _TR.SHED, frames[2], shard=shard, aux=n
                        )
                    status = np.full(n, _STANDBY, np.int8)
                    _SM.record_verdict_batch(status, None, ())
                    try:
                        # an answer the intake lane gives itself is no
                        # verdict's residence: the stamps stay behind
                        door.submit(
                            frames[:5], status, np.zeros(n, np.int32),
                            np.zeros(n, np.int32),
                        )
                    except Exception:
                        if not self._stop.is_set():
                            record_log.exception(
                                "native standby submit failed"
                            )
                    continue
                _SM.batch_size.record(n)
                self.overload.note_enqueued(n)
                give_up = (
                    None if self.shed_age_ms is None
                    else self.shed_age_ms / 1000.0
                )
                # pull = (rows..., frames, age stamp, owning door, block,
                # value hashes [n, nv] of a param pull or None, hand-over
                # stamp, nv): the age stamp (the lane's first clock read, ns)
                # is the shed-by-age deadline proxy (the C++ door strips
                # the wire deadline); the door routes replies and refusals
                # back to the shard that owns the connection; the hand-over
                # stamp ends intake_ms and starts the pull's queue_wait_ms,
                # which so holds a put that blocks on a full queue
                t_enq = time.monotonic_ns()
                pull = (
                    block["ids"][:n], block["counts"][:n], prios, frames,
                    t_py, door, block,
                    block["hashes"][:n * nv].reshape(n, nv)
                    if nv > 0 else None,
                    t_enq, nv,
                )
                if self._lane_put(q, pull, give_up_after_s=give_up):
                    self._dispatch_sem.release()
                    if _TR.ARMED:
                        _TR.record_many(
                            _TR.ENQUEUE, frames[2], shard=shard, aux=n
                        )
                    dt_ms = (t_enq - t_py) * 1e-6
                    _SM.intake_ms.record(dt_ms)
                    _SM.count_shard_pull(shard, n, dt_ms)
                    # the block now rides the pull; next cycle decodes
                    # into a fresh (usually recycled) one
                    block = pool.acquire()
                else:
                    # dispatch lane saturated past the age budget: refuse
                    # the whole pull explicitly rather than queue frames
                    # that will only expire — the clients get an immediate
                    # retry hint
                    self.overload.note_done(n)
                    _SM.count_shed("queue_full", n)
                    if _TR.ARMED:
                        _TR.record_many(
                            _TR.SHED, frames[2], shard=shard, aux=n
                        )
                    status = np.full(n, _OVERLOAD, np.int8)
                    wait = np.full(
                        n, self.overload.retry_hint_ms, np.int32
                    )
                    # per-tenant attribution: these rows never reach the
                    # device path, so resolve namespaces here (the SLO
                    # plane's shed accounting rides the verdict counters)
                    ns_fn = getattr(
                        self.service, "namespace_index", None
                    )
                    _SM.record_verdict_batch(
                        status,
                        *(ns_fn(pull[0]) if ns_fn is not None
                          else (None, ())),
                    )
                    try:
                        door.submit(
                            frames[:5], status, np.zeros(n, np.int32), wait
                        )
                    except Exception:
                        if not self._stop.is_set():
                            record_log.exception(
                                "native overload submit failed"
                            )
        finally:
            pool.release(block)
            # sentinel handoff keeps the forever semantics; only a
            # successful put may release the semaphore (the device lane
            # trusts every release to have a queued item behind it)
            if self._lane_put(q, self._SENTINEL):
                self._dispatch_sem.release()

    # -- device pipelining ---------------------------------------------------
    def _acquire_device_permit(self) -> bool:
        """Block until a dispatch slot frees (``max_device_inflight``
        bound). Returns True when another fused group was already in
        flight — i.e. this group's host prep just ran overlapped with
        device compute that a depth-1 lane would have serialized behind.
        On abandoned shutdown the wait gives up and over-admits; the
        release path tolerates it."""
        with self._device_cv:
            while (
                self._device_inflight >= self.max_device_inflight
                and not self._abandon.is_set()
            ):
                self._device_cv.wait(timeout=0.1)
            overlapped = self._device_inflight > 0
            self._device_inflight += 1
            return overlapped

    def _release_device_permit(self) -> None:
        with self._device_cv:
            self._device_inflight = max(0, self._device_inflight - 1)
            self._device_cv.notify()

    def _tracked_dispatch(self, dispatch, ids, counts, third):
        """Issue one device dispatch under the inflight bound: a flow
        dispatch (``third`` the priorities), a param dispatch (``third``
        the value hashes ``[n, k]``) or a concurrency dispatch (``third``
        the rows that are releases).

        Returns ``(mat, release, overlapped)``: ``mat`` is a
        :class:`Materializer` whose read half reads the verdicts and gives
        the permit back (exactly once, even if the read raises) and whose
        account half is the dispatch's own, if it has one: the permit
        bounds device work in flight and does not wait on counters.
        ``release`` is the idempotent escape hatch for paths that never
        read (dispatch exception handled by the caller, abandoned-shutdown
        drop). ``overlapped`` reports whether the permit wait found earlier
        work still in flight."""
        t0 = time.monotonic_ns()
        overlapped = self._acquire_device_permit()
        waited_ns = time.monotonic_ns() - t0
        _SM.permit_wait_ms.record(waited_ns * 1e-6)
        if _TR.ARMED:  # flight recorder: permit granted (aux = wait, us)
            _TR.record(_TR.PERMIT, aux=min(waited_ns // 1000, 2**31 - 1))
        done = [False]

        def release():
            if not done[0]:
                done[0] = True
                self._release_device_permit()

        try:
            inner = dispatch(ids, counts, third)
        except Exception:
            release()
            raise

        read, account = halves(inner)

        def read_and_release():
            try:
                return read(), account
            finally:
                release()

        return Materializer(read_and_release), release, overlapped

    def _device_loop(self) -> None:
        """Lane 2: the only thread issuing device work — dispatch order IS
        state-chain order. Drains every queued pull (bounded by
        ``fuse_depth``), concatenates, and issues ONE dispatch; the token
        service's fusion ladder folds the full engine frames inside into a
        single chained scan step. Dispatch returns before the device
        finishes (async), so this lane loops back to prep the next group
        while the reply lanes block on the verdicts. Up to
        ``max_device_inflight`` fused groups may be dispatched and not yet
        materialized — the permit wait applies backpressure beyond that,
        and the overlap the pipeline wins is accounted in
        ``overlap_saved_ms_total``.

        With intake sharding the drain is the UNION of the shard queues:
        the semaphore counts queued pulls across all of them, and a
        round-robin ``get_nowait`` scan fetches the item each acquired
        permit guarantees — so a burst split across N doors by the kernel
        still fuses into one device step. Shutdown ends after every
        shard's sentinel has been consumed."""
        qs = self._shard_qs
        sem = self._dispatch_sem
        n_shards = len(qs)
        done_shards = 0
        rr = 0
        service = self.service
        flow_dispatch = getattr(service, "dispatch_batch_arrays", None)

        def own(name):
            # a dispatch half counts only where the served object's own
            # class defines it: a wrapper that alters the one-row SPI calls
            # and delegates the rest (``__getattr__``) is asked through
            # them, as ``params_batch_entry`` asks it, and not past them.
            # Single frames come this way since PR 45, and a one-row call
            # is what such a wrapper sees of a single frame
            if getattr(type(service), name, None) is None:
                return None
            return getattr(service, name)

        # a pull is either flow rows or param rows (the rows of
        # BATCH_PARAM_FLOW and single PARAM_FLOW frames, with their value
        # hashes), and a dispatch is one or the other: a queued pull of
        # another kind (or of another number of values per request) waits
        # in ``held`` for the next turn
        param_dispatch = own("dispatch_params_batch")
        # ... or, third, the rows of concurrency frames (kind -1): the
        # release ids and acquire rows of its pulls in their arrival order,
        # which the service applies releases first (a release is never
        # applied later than an acquire frame behind it on its connection)
        conc_dispatch = own("dispatch_concurrent_batch")
        held = None
        # the turn between kinds, counted and changing nothing: when the
        # pull in ``held`` was set aside, and the kind of the last dispatch
        held_at = held_since = 0
        last_kind = None

        def kind(pull) -> int:
            return pull[9]

        def pop_next():
            # every sem permit has a queued item behind it and this lane
            # is the sole consumer, so one scan pass finds it; the spin
            # guard only matters if a lane died mid-shutdown
            nonlocal rr
            while True:
                for j in range(n_shards):
                    qi = (rr + j) % n_shards
                    try:
                        item = qs[qi].get_nowait()
                    except queue.Empty:
                        continue
                    rr = (qi + 1) % n_shards
                    return item
                if self._abandon.is_set():
                    return None

        try:
            while True:
                from_held = held is not None
                if from_held:
                    item, held, held_since = held, None, held_at
                else:
                    if not sem.acquire(timeout=0.5):
                        if self._abandon.is_set():
                            break
                        continue
                    item = pop_next()
                if item is None:
                    break
                if item is self._SENTINEL:
                    done_shards += 1
                    if done_shards >= n_shards:
                        break
                    continue
                pulls = [item]
                rows = len(item[0])
                # adaptive frame fusion: everything already queued joins
                # this dispatch. Idle queues → depth 1 (no added latency);
                # backlog → deep fused step (max amortization). The depth
                # is a budget of host prep, counted in pulls of up to
                # max_batch rows: pulls that together are less than ONE
                # such pull (a trickle of one-row frames, a few a pull) do
                # not use it up, or a dispatch's fixed cost is paid once
                # every fuse_depth tiny pulls and the lane saturates on
                # their number, not their rows (PERF.md section 6, PR 45)
                stop_after = False
                while (len(pulls) < self.fuse_depth
                       or 1 < self.fuse_depth and rows < self.max_batch):
                    if not sem.acquire(blocking=False):
                        break
                    nxt = pop_next()
                    if nxt is None:
                        break
                    if nxt is self._SENTINEL:
                        done_shards += 1
                        if done_shards >= n_shards:
                            stop_after = True  # all intake done; finish
                            break
                        continue
                    if kind(nxt) != kind(item):
                        held = nxt
                        held_at = time.monotonic_ns()
                        break
                    pulls.append(nxt)
                    rows += len(nxt[0])
                hashes = item[7]
                if len(pulls) == 1:
                    ids, counts, prios = item[0], item[1], item[2]
                else:
                    ids = np.concatenate([p[0] for p in pulls])
                    counts = np.concatenate([p[1] for p in pulls])
                    prios = np.concatenate([p[2] for p in pulls])
                    _SM.count_copy_bytes(
                        ids.nbytes + counts.nbytes + prios.nbytes
                    )
                    if hashes is not None:
                        hashes = np.concatenate([p[7] for p in pulls])
                        _SM.count_copy_bytes(hashes.nbytes)
                is_conc = kind(item) < 0
                if is_conc:
                    # which rows are releases: whole frames, by their type
                    dispatch = conc_dispatch
                    third = np.concatenate([
                        np.repeat((p[3][4] == _TYPE_BATCH_RELEASE)
                                  | (p[3][4] == _TYPE_RELEASE), p[3][3])
                        for p in pulls
                    ])
                    sync = concurrent_batch_entry(service)
                elif hashes is None:
                    # what a dispatch takes beside ids and counts, and what
                    # answers for a service without the dispatch/materialize
                    # split
                    dispatch, third = flow_dispatch, prios
                    sync = getattr(service, "request_batch_arrays", None)
                else:
                    dispatch, third = param_dispatch, hashes
                    sync = params_batch_entry(service)
                    # the reference client's one-request frames among them
                    singles = [
                        int(np.count_nonzero(p[3][4] == _TYPE_PARAM_FLOW))
                        for p in pulls
                    ]
                    if any(singles):
                        _SM.count_param_singles(
                            sum(singles), sum(1 for m in singles if m),
                            sum(singles) * kind(item),
                        )
                lengths = [len(p[0]) for p in pulls]
                n_rows = len(ids)
                # deadline proxy: pulls older than shed_age_ms are answered
                # OVERLOAD without touching the device (row mask via repeat)
                shed = None
                n_deadline = 0
                if self.shed_age_ms is not None:
                    cutoff = time.monotonic_ns() - int(
                        self.shed_age_ms * 1e6
                    )
                    expired = np.array(
                        [p[4] < cutoff for p in pulls], bool
                    )
                    if expired.any():
                        shed = np.repeat(expired, lengths)
                        if is_conc:
                            # a release is never shed: refusing it would
                            # hold its tokens until they expire
                            shed &= ~third
                        n_deadline = int(shed.sum())
                        if not n_deadline:
                            shed = None
                level = self.overload.level()
                # tenant attribution is the flow table's; param rules have
                # none on this lane
                ns_fn = (
                    getattr(service, "namespace_index", None)
                    if hashes is None and not is_conc else None
                )
                if _TR.ARMED:  # flight recorder: fused group dispatched
                    for p in pulls:
                        _TR.record_many(
                            _TR.DISPATCH, p[3][2], aux=len(pulls)
                        )
                # dispatch_ms starts where each pull's queue_wait_ms ends:
                # the fusion collect, the concatenation of a fused group
                # and the shed-by-age test above are queue wait, not
                # dispatch (recorded below, past the span's end)
                t0 = time.monotonic_ns()
                permit_rel = None
                overlapped = False
                try:
                    if level >= BrownoutLevel.DEGRADE:
                        # brownout floor: no device dispatch at all; a BDP
                        # slice gets probabilistic local answers, the rest
                        # (and every expired row) OVERLOAD
                        deg = self.overload.shed_mask(prios, level)
                        if shed is not None:
                            deg = deg | shed
                        if is_conc:
                            # no local answer can carry a token: the whole
                            # pull is refused, releases too (their tokens
                            # expire unless the client asks again)
                            deg = np.ones(n_rows, bool)
                        status, remaining, wait = (
                            self.overload.degrade_verdicts(deg)
                        )
                        if n_deadline:
                            _SM.count_shed("deadline", n_deadline)
                        _SM.count_shed(
                            "degrade", int(deg.sum()) - n_deadline
                        )
                        _SM.record_verdict_batch(
                            status,
                            *(ns_fn(ids) if ns_fn is not None
                              else (None, ())),
                        )
                        mat = (  # noqa: E731
                            lambda r=(status, remaining, wait): r
                        )
                    else:
                        mask = shed
                        if level >= BrownoutLevel.SHED_LOW:
                            # tenant attribution up front so the shed is
                            # share-weighted when shares are configured
                            ns_pair = (
                                ns_fn(ids) if ns_fn is not None
                                else (None, ())
                            )
                            m = self.overload.shed_mask(
                                prios, level,
                                ns_idx=ns_pair[0], ns_names=ns_pair[1],
                            )
                            if is_conc:
                                m = m & ~third
                            mask = m if mask is None else (mask | m)
                            if not mask.any():
                                mask = None
                        if mask is None:
                            if dispatch is not None:
                                mat, permit_rel, overlapped = (
                                    self._tracked_dispatch(
                                        dispatch, ids, counts, third
                                    )
                                )
                            else:
                                # SPI implementations without the dispatch/
                                # materialize split run synchronously here
                                res = sync(ids, counts, third)
                                mat = lambda res=res: res  # noqa: E731
                        else:
                            if n_deadline:
                                _SM.count_shed("deadline", n_deadline)
                            n_brown = int(mask.sum()) - n_deadline
                            if n_brown > 0:
                                _SM.count_shed("brownout", n_brown)
                            keep = np.nonzero(~mask)[0]
                            if keep.size:
                                if dispatch is not None:
                                    inner, permit_rel, overlapped = (
                                        self._tracked_dispatch(
                                            dispatch, ids[keep],
                                            counts[keep], third[keep],
                                        )
                                    )
                                else:
                                    res = sync(
                                        ids[keep], counts[keep], third[keep]
                                    )
                                    inner = lambda res=res: res  # noqa: E731
                            else:
                                inner = None
                            hint = self.overload.retry_hint_ms
                            n_shed = n_rows - int(keep.size)
                            _SM.record_verdict_batch(
                                np.full(n_shed, _OVERLOAD, np.int8),
                                *(ns_fn(ids[mask]) if ns_fn is not None
                                  else (None, ())),
                            )

                            # scatter the dispatched slice back into full-
                            # width arrays so the reply lane's per-pull
                            # offsets stay valid
                            def scatter(
                                inner=inner, keep=keep, n=n_rows, hint=hint
                            ):
                                status = np.full(n, _OVERLOAD, np.int8)
                                remaining = np.zeros(n, np.int32)
                                wait = np.full(n, hint, np.int32)
                                account = None
                                tokens = ()
                                if inner is not None:
                                    read, account = halves(inner)
                                    st, rm, wt, *tok = read()
                                    status[keep] = st
                                    remaining[keep] = rm
                                    wait[keep] = wt
                                    if tok:  # a concurrency dispatch's ids
                                        tokens = (np.zeros(n, np.int64),)
                                        tokens[0][keep] = tok[0]
                                return (status, remaining, wait,
                                        *tokens), account

                            mat = Materializer(scatter)
                except Exception:
                    record_log.exception("device step failed; failing batch")
                    if permit_rel is not None:
                        permit_rel()
                    n = n_rows
                    mat = lambda n=n: (  # noqa: E731
                        np.full(n, int(TokenStatus.FAIL), np.int8),
                        np.zeros(n, np.int32),
                        np.zeros(n, np.int32),
                    )
                t_put = time.monotonic_ns()
                dt_ms = (t_put - t0) * 1e-6
                _SM.dispatch_ms.record(dt_ms)
                # one record per pull, never per frame or row, and outside
                # the span that permit, prep, lock and launch split
                waited_ms = 0.0
                for p in pulls:
                    wait_ms = (t0 - p[8]) * 1e-6
                    _SM.queue_wait_ms.record(wait_ms)
                    waited_ms += wait_ms
                # the lane's turn: its kind, whether the kind changed, and
                # what a pull set aside for this turn waited in ``held``
                switched = last_kind is not None and kind(item) != last_kind
                last_kind = kind(item)
                held_ns = t0 - held_since if from_held else 0
                lane = _lane_kind(item)
                _SM.count_lane_turn(
                    lane, n_rows, len(pulls), waited_ms, switched,
                    held_ns * 1e-6 if from_held else None,
                )
                if _TR.ARMED and (switched or from_held):
                    _TR.record(
                        _TR.LANE_TURN, shard=_LANE_OF[lane],
                        aux=min(held_ns // 1000, 2**31 - 1), t_ns=t0,
                    )
                if overlapped:
                    # this group's whole dispatch arm ran while the prior
                    # group still computed — the pipelining win
                    _SM.count_overlap_saved_ms(dt_ms)
                # the stamp rides the item: reply_queue_wait_ms runs from
                # dispatch_ms's end (so it holds the records above and the
                # put) to a reply lane's get() returning
                if not self._lane_put(
                    self._reply_q,
                    (pulls, lengths, mat, t_put),
                ):
                    # abandoned shutdown drop: nobody will materialize or
                    # answer these rows — account for them and park the
                    # staging blocks the reply lane would have returned
                    if permit_rel is not None:
                        permit_rel()
                    self.overload.note_done(n_rows)
                    _SM.count_shed("lane_abandon", n_rows)
                    if self._staging is not None:
                        for p in pulls:
                            self._staging.release(p[6])
                if stop_after:
                    break
        finally:
            # always propagate shutdown, even if this lane died — the
            # reply lanes must not block forever on an empty queue
            self._lane_put(self._reply_q, self._SENTINEL)

    def _reply_loop(self) -> None:
        """Lane 3 (×``n_dispatchers``): block on the async verdicts, slice
        them back per intake pull, submit to each pull's owning door, and
        only then count them. While one reply thread waits on device
        results the device lane keeps dispatching, and a second reply
        thread overlaps the next group's encode. Consecutive pulls from the
        same door collapse into one ``submit_many`` call — one outbox lock
        and one IO wakeup per run, with the C++ scatter encode grouping
        same-connection frames across pull boundaries. Once the verdicts
        are submitted (``sn_fd_submit`` copies synchronously) the pulls'
        staging blocks go back to the intake pool.

        Answer first, count after: per item the order is the
        materializer's read half (``decide_ms``; the device permit comes
        back at its end) -> ``submit_many`` per door run ->
        ``overload.note_done`` -> ``write_ms`` -> staging release -> the
        account half (``account_ms``, ``reply_first_total``). The account
        half reads the verdict arrays and the slots the dispatch resolved,
        none of which aliases a staging block. It runs here, on the lane
        that read, in the order the lane took its items: every dispatch
        that was read is counted once, a submit that raised or a stop
        notwithstanding, and one whose read raised never. Counters are
        complete once the lanes have drained (``stop()``); right after a
        reply they may lack that reply's dispatch."""
        rq = self._reply_q
        while True:
            item = rq.get()
            if item is self._SENTINEL:
                rq.put(item)  # release sibling reply lanes
                return
            pulls, lengths, mat, t_put = item
            t_taken = time.monotonic_ns()
            _SM.reply_queue_wait_ms.record((t_taken - t_put) * 1e-6)
            if _TR.ARMED:  # flight recorder: a reply lane has the group
                _TR.record(
                    _TR.REPLY_TAKEN, t_ns=t_taken,
                    aux=min((t_taken - t_put) // 1000, 2**31 - 1),
                )
            # a foreign service's materializer has no account half
            read, account = halves(mat)
            t0 = time.monotonic_ns()
            try:
                # a concurrency dispatch's verdicts carry a fourth array,
                # the token ids of its acquire rows
                status, remaining, wait, *tok = read()
            except Exception:
                record_log.exception("materialize failed; failing batch")
                n = sum(lengths)
                status = np.full(n, int(TokenStatus.FAIL), np.int8)
                remaining = np.zeros(n, np.int32)
                wait = np.zeros(n, np.int32)
                tok = ()
            t_write = time.monotonic_ns()
            decide_ms = (t_write - t0) * 1e-6
            _SM.decide_ms.record(decide_ms)
            _SM.count_lane_decide(_lane_kind(pulls[0]), decide_ms)
            off = 0
            i = 0
            n_pulls = len(pulls)
            while i < n_pulls:
                door = pulls[i][5]
                frames_list = []
                span = 0
                j = i
                while j < n_pulls and pulls[j][5] is door:
                    frames_list.append(pulls[j][3])
                    span += lengths[j]
                    j += 1
                try:
                    # the C++ scatter encode carries (status, remaining,
                    # wait) only, so MOVED verdicts ship the shard-map
                    # epoch in ``remaining`` without the endpoint trailer
                    # the asyncio door appends — clients re-resolve the
                    # destination through the shard map on the epoch bump
                    door.submit_many(
                        frames_list,
                        status[off : off + span],
                        remaining[off : off + span],
                        wait[off : off + span],
                        *(t[off : off + span] for t in tok),
                    )
                    # flight recorder: replies submitted to the door (parked
                    # in its outbox; the IO thread's send() comes later and
                    # ends the frames' door_out_ms and door_residence_ms)
                    if _TR.ARMED:
                        for fr in frames_list:
                            _TR.record_many(
                                _TR.REPLY_OUT, fr[2], aux=span
                            )
                except Exception:
                    if not self._stop.is_set():
                        record_log.exception("native submit failed")
                off += span
                i = j
            self.overload.note_done(off)
            _SM.write_ms.record((time.monotonic_ns() - t_write) * 1e-6)
            pool = self._staging
            if pool is not None:
                for p in pulls:
                    pool.release(p[6])
            if account is not None:
                try:
                    account(True)
                except Exception:
                    record_log.exception("accounting failed after the reply")

    # -- control plane ------------------------------------------------------
    def _control_loop(self) -> None:
        # one thread covers every shard door: control traffic is low-rate
        # (handshakes, params, repl frames), and (fd, gen) keys are
        # globally unique across doors, so the session maps need no
        # per-door namespacing — only the REPLY must go out through the
        # door that owns the connection. It does not poll: when no door
        # had anything it sleeps in native code, the GIL released, on the
        # bell every door rings after a push to its control queue
        # (native.lib.Bell), and wakes for an event, for stop(), or after
        # _CONTROL_WAIT_MS. The bell's generation, read before the last
        # empty drain, is what the wait compares: a ring since then
        # returns it at once. Where the loaded library is older than the
        # bell it keeps the 2 ms poll it had. A ring does not wake it at
        # once: the wait stays asleep _CONTROL_SETTLE_MS more, in native
        # code, before it returns. A report comes in the same send as the
        # data frame behind it, and handled in that very moment its
        # ingest shares the GIL with that frame's intake, prep and launch
        # (0.7 ms of a verdict's 3.9 on the chip's host, PERF.md section
        # 6, PR 51). The poll, by coming 0-2 ms late, had kept most of
        # them apart; this is its mean. What arrives while a drain is
        # being answered waits for nothing, as before.
        #
        # The TCP doors serve single PARAM_FLOW and CONCURRENT_ACQUIRE /
        # _RELEASE frames on their data plane; those of the shm door's
        # clients, and a TCP door's PARAM_FLOW frame with no value, arrive
        # here. They are not answered one dispatch a request: what a door
        # has queued is drained, the PARAM_FLOW requests among it are set
        # aside in queue order, and each run of equal value counts is
        # decided by ONE call of the service's batched entry
        # (_answer_params). Single CONCURRENT_ACQUIRE / _RELEASE frames are
        # set aside the same way in a list of their own
        # (_answer_concurrent).
        doors = list(self._doors)
        bell = self._bell
        seen = 0  # the bell's generation before the last drain
        woke = False  # this drain follows a return of the wait
        while not self._stop.is_set():
            got_any = False
            for door in doors:
                # (fd, gen, request, address) in queue order: the single
                # PARAM_FLOW frames, and the single CONCURRENT_ACQUIRE /
                # _RELEASE frames
                params, conc = [], []
                while True:
                    try:
                        item = door.next_control()
                    except Exception:
                        if self._stop.is_set():
                            return
                        raise
                    if item is None:
                        break
                    got_any = True
                    self._handle_control_item(
                        door, item, params, conc,
                        getattr(door, "last_control_ns", 0) or None,
                    )
                    if len(params) + len(conc) >= self.max_batch:
                        break
                if params:
                    self._answer_params(door, params)
                if conc:
                    self._answer_concurrent(door, conc)
            if woke:
                _SM.count_control_wakeup(idle=not got_any)
            woke = not got_any
            if bell is not None:
                # after a drain that found something only read the
                # generation (what rang during it is no news to the next
                # drain) and drain again before any sleep
                seen = bell.wait(seen, 0 if got_any else _CONTROL_WAIT_MS,
                                 _CONTROL_SETTLE_MS)
            elif not got_any:
                self._stop.wait(0.002)

    def _answer_params(self, door, params) -> None:
        """Decide the drained single PARAM_FLOW requests of one door, in
        queue order, through ``decide_param_requests``: one call of the
        service's batched entry per run of equal value counts."""
        reqs = [req for _fd, _gen, req, _addr in params]
        _SM.count_param_control_frames(len(reqs))
        if self.is_standby:
            verdicts = [(_STANDBY, 0, 0)] * len(reqs)
        else:
            verdicts = decide_param_requests(
                self.service, reqs, int(TokenStatus.FAIL)
            )
        for (fd, gen, req, _addr), (st, rm, wt) in zip(params, verdicts):
            door.send(fd, gen, P.encode_response(
                P.FlowResponse(req.xid, req.msg_type, st, rm, wt)
            ))

    def _answer_concurrent(self, door, items) -> None:
        """Decide the drained single CONCURRENT_ACQUIRE / CONCURRENT_RELEASE
        requests of one door, in queue order, through ONE call of the
        service's batched concurrency entry (the entry the data plane's
        batch frames reach: one gauge, whichever frame asks)."""
        reqs = [req for _fd, _gen, req, _addr in items]
        if self.is_standby:
            verdicts = [(_STANDBY, 0, 0, 0)] * len(reqs)
        else:
            verdicts = decide_concurrent_requests(
                self.service, reqs,
                [r.msg_type == P.MsgType.CONCURRENT_RELEASE for r in reqs],
                int(TokenStatus.FAIL),
            )
        for (fd, gen, req, _addr), (st, rm, wt, tok) in zip(items, verdicts):
            door.send(fd, gen, P.encode_response(
                P.FlowResponse(req.xid, req.msg_type, st, rm, wt, tok)
            ))

    def _handle_control_item(self, door, item, params, conc,
                             t_door_ns=None) -> None:
        """One control event. A PARAM_FLOW request is not answered here but
        appended to ``params``, a CONCURRENT_ACQUIRE / _RELEASE request to
        ``conc`` (the control loop's drains), to be decided with the
        others queued beside it. ``t_door_ns`` is the
        ``monotonic_ns`` at which the door queued the frame: a completion
        report's age counts from it."""
        kind, fd, gen, payload = item
        if kind == door.CTRL_OPEN:
            address = payload.decode("latin-1")
            with self._addr_lock:
                self._addr_by_conn[(fd, gen)] = address
            self.connections.attach_closer(
                address,
                lambda fd=fd, gen=gen, door=door: door.close_conn(fd, gen),
            )
            # rev-7 push sink: door.send enqueues on the C++ plane's
            # non-blocking per-connection send queue (encoded push frames
            # carry their length prefix, same as control replies)
            self.push_hub.attach(
                (fd, gen),
                lambda b, fd=fd, gen=gen, door=door: door.send(fd, gen, b),
            )
            return
        if kind == door.CTRL_CLOSE:
            self.push_hub.detach((fd, gen))
            with self._addr_lock:
                address = self._addr_by_conn.pop((fd, gen), None)
            if address:
                self.connections.remove_address(address)
            self._repl_sessions.pop((fd, gen), None)
            move_sess = self._move_sessions.pop((fd, gen), None)
            if move_sess is not None:
                # crash matrix: a source that dies mid-move never sent
                # MOVE_COMMIT, so discarding its staged state here leaves
                # the source as the sole owner
                move_sess.closed()
            return
        # kind == CTRL_FRAME: a non-data-plane request
        with self._addr_lock:
            address = self._addr_by_conn.get((fd, gen), f"fd{fd}")
        # rev-3 replication frames ride the control lane but are not
        # requests (decode_request would reject their type bytes) —
        # route them to the standby applier's per-connection session
        if len(payload) >= 5 and P.peek_type(payload) in P.REPL_TYPES:
            if self.applier is None:
                record_log.warning(
                    "repl frame on non-standby server; closing %s",
                    address,
                )
                door.close_conn(fd, gen)
                return
            sess = self._repl_sessions.get((fd, gen))
            if sess is None:
                sess = self.applier.connection()
                self._repl_sessions[(fd, gen)] = sess
            try:
                sess.handle(
                    payload,
                    lambda b, fd=fd, gen=gen, door=door: door.send(
                        fd, gen, b
                    ),
                )
            except ValueError:
                record_log.warning("torn repl stream; closing %s",
                                   address)
                self._repl_sessions.pop((fd, gen), None)
                door.close_conn(fd, gen)
            return
        # rev-4 namespace-move frames: same control-lane treatment, routed
        # to the MoveTarget's per-connection session
        if len(payload) >= 5 and P.peek_type(payload) in P.MOVE_TYPES:
            sess = self._move_sessions.get((fd, gen))
            if sess is None:
                sess = self.move_target.connection()
                self._move_sessions[(fd, gen)] = sess
            try:
                sess.handle(
                    payload,
                    lambda b, fd=fd, gen=gen, door=door: door.send(
                        fd, gen, b
                    ),
                )
            except ValueError:
                record_log.warning("torn move stream; closing %s",
                                   address)
                self._move_sessions.pop((fd, gen), None)
                sess.closed()
                door.close_conn(fd, gen)
            return
        # rev-5 lease frames ride the control lane too (one per TTL per hot
        # flow — never on the per-decision path, which is the whole point)
        if len(payload) >= 5 and P.peek_type(payload) in P.LEASE_TYPES:
            try:
                rsp_bytes = self._handle_lease(payload, address)
            except ValueError:
                record_log.warning("bad lease frame; closing %s", address)
                door.close_conn(fd, gen)
                return
            door.send(fd, gen, rsp_bytes)
            return
        # hierarchy-tier frames (pod share ops + demand reports): same
        # control-lane treatment, dispatched to the co-located coordinator
        if len(payload) >= 5 and P.peek_type(payload) in P.HIER_TYPES:
            try:
                rsp_bytes = self._handle_hier(payload, address)
            except ValueError:
                record_log.warning("bad hier frame; closing %s", address)
                door.close_conn(fd, gen)
                return
            door.send(fd, gen, rsp_bytes)
            return
        # rev-6 outcome reports: fire-and-forget (no door.send — the whole
        # point is zero extra round-trips on the lease fast path). Covers
        # both the TCP and shm doors: each routes non-data type bytes here.
        if len(payload) >= 5 and P.peek_type(payload) in P.OUTCOME_TYPES:
            try:
                oxid, ofids, orts, oexcs = P.decode_outcome_report(payload)
            except Exception:
                record_log.warning("bad outcome frame; closing %s", address)
                door.close_conn(fd, gen)
                return
            if self.is_standby:
                # outcome columns replicate from the primary; counting here
                # would double on promotion
                return
            self.service.report_outcomes(ofids, orts, oexcs, oxid, t_door_ns)
            return
        try:
            req = P.decode_request(payload)
        except Exception:
            record_log.warning("bad control frame; closing %s", address)
            door.close_conn(fd, gen)
            return
        if not isinstance(req, P.Ping) and req.msg_type in _DRAINED_SINGLES:
            self.connections.touch(address)
            drain = params if req.msg_type == P.MsgType.PARAM_FLOW else conc
            drain.append((fd, gen, req, address))
            return
        try:
            rsp = self._handle_control(req, address)
        except Exception:
            record_log.exception("%s control request failed",
                                 type(req).__name__)
            rsp = P.FlowResponse(
                req.xid, getattr(req, "msg_type", P.MsgType.PING),
                int(TokenStatus.FAIL),
            )
        door.send(fd, gen, P.encode_response(rsp))

    def _handle_lease(self, payload, address: str) -> bytes:
        """Wire rev 5: decode a lease request, run the service's host-side
        grant/renew/return, encode the reply. Raises ValueError on a torn
        frame (caller closes the connection — the containment contract)."""
        xid, lmt, lease_id, flow_id, used, want = (
            P.decode_lease_request(payload)
        )
        if _TR.ARMED:
            _TR.record(_TR.LEASE, xid=xid, aux=want)
        self.connections.touch(address)
        if self.is_standby:
            # proof-of-life refusal, same as the decision path: the client
            # falls back to per-request RPCs, the breaker records success
            return P.encode_lease_response(xid, lmt, _STANDBY)
        service = self.service
        if getattr(service, "lease_grant", None) is None:
            return P.encode_lease_response(
                xid, lmt, P.NOT_LEASABLE_STATUS
            )
        try:
            if lmt == P.MsgType.LEASE_GRANT:
                res = service.lease_grant(flow_id, want)
            elif lmt == P.MsgType.LEASE_RENEW:
                res = service.lease_renew(lease_id, flow_id, used, want)
            else:
                res = service.lease_return(lease_id, used)
        except Exception:
            record_log.exception("lease op failed")
            return P.encode_lease_response(
                xid, lmt, int(TokenStatus.FAIL)
            )
        return P.encode_lease_response(
            xid, lmt, int(res.status), lease_id=res.lease_id,
            tokens=res.tokens, ttl_ms=res.ttl_ms, endpoint=res.endpoint,
        )

    def _handle_hier(self, payload, address: str) -> bytes:
        """Hierarchy tier: decode a share op or demand report, run the
        co-located coordinator's ledger op, encode the (lease-layout)
        reply. Raises ValueError on a torn frame (caller closes)."""
        mtype = P.peek_type(payload)
        if mtype == int(P.MsgType.DEMAND_REPORT):
            xid, pod_id, entries = P.decode_demand_report(payload)
            hmt = P.MsgType.DEMAND_REPORT
            args = None
        else:
            xid, hmt, share_id, flow_id, used, want = (
                P.decode_lease_request(payload)
            )
            args = (share_id, flow_id, used, want)
        if _TR.ARMED:
            _TR.record(_TR.HIER, xid=xid)
        self.connections.touch(address)
        if self.is_standby:
            return P.encode_lease_response(xid, hmt, _STANDBY)
        hier = getattr(self.service, "hierarchy", None)
        if hier is None:
            # no coordinator co-located here: refuse so the agent's
            # failover walk tries the next endpoint
            return P.encode_lease_response(
                xid, hmt, P.NOT_LEASABLE_STATUS
            )
        try:
            if hmt == P.MsgType.DEMAND_REPORT:
                res = hier.handle_demand_report(pod_id, entries)
            elif hmt == P.MsgType.SHARE_GRANT:
                res = hier.share_grant(args[1], args[3])
            elif hmt == P.MsgType.SHARE_RENEW:
                res = hier.share_renew(args[0], args[1], args[2], args[3])
            else:
                res = hier.share_return(args[0], args[2])
        except Exception:
            record_log.exception("hier op failed")
            return P.encode_lease_response(
                xid, hmt, int(TokenStatus.FAIL)
            )
        return P.encode_lease_response(
            xid, hmt, int(res.status), lease_id=res.lease_id,
            tokens=res.tokens, ttl_ms=res.ttl_ms, endpoint=res.endpoint,
        )

    def _handle_control(self, req, address: str) -> P.FlowResponse:
        if isinstance(req, P.Ping):
            count = self.connections.add(req.namespace, address)
            return P.FlowResponse(req.xid, P.MsgType.PING, 0, remaining=count)
        self.connections.touch(address)
        if self.is_standby:
            # control-lane verdicts get the same closed-door refusal as the
            # data plane (PING above still answers: standbys stay pingable)
            return P.FlowResponse(req.xid, req.msg_type, _STANDBY)
        # (PARAM_FLOW and CONCURRENT_ACQUIRE / _RELEASE never come here: the
        # control loop drains them into the services' batched entries)
        return P.FlowResponse(req.xid, req.msg_type, int(TokenStatus.FAIL))

    def stats(self) -> dict:
        """Door counters, summed across the intake shards. Every summand
        is an independently monotonic relaxed atomic read without pausing
        the IO threads, so the result is NOT a consistent cross-counter
        snapshot — each key is its own monotonic series; derived deltas
        between two calls must be clamped at zero."""
        doors = list(self._doors)
        if not doors:
            return {}
        out: dict = {}
        for d in doors:
            for key, v in d.stats().items():
                out[key] = out.get(key, 0) + v
        return out
