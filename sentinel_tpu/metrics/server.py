"""Server-side pipeline metrics: stage histograms, verdict counters, gauges.

The analog of the reference's ``ClusterServerStatLogUtil`` + dashboard state
commands, grown into an always-on Prometheus surface: the ``TokenServer``
micro-batcher (asyncio and native front doors) records per-stage timings
here, ``DefaultTokenService`` feeds per-namespace verdict counters from each
materialized batch, and the Envoy RLS adapter mirrors its OK/OVER_LIMIT
responses in. One process-wide singleton — multiple servers in one process
(tests, port moves) share it, which matches Prometheus's per-process scrape
model.

Everything here renders under the ``sentinel_server_*`` prefix via
:func:`ServerMetrics.render` (appended to the exporter body) and as JSON via
:func:`ServerMetrics.snapshot` (the ``clusterServerStats`` command).
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from sentinel_tpu.core import clock as _clock
from sentinel_tpu.metrics.histogram import LatencyHistogram
from sentinel_tpu.metrics.timeline import _EDGES as _TIMELINE_EDGES
from sentinel_tpu.metrics.timeline import timeline

# TokenStatus codes that appear on the batch paths → series label.
VERDICT_NAMES: Dict[int, str] = {
    0: "pass",            # OK
    1: "block",           # BLOCKED
    2: "should_wait",     # SHOULD_WAIT (occupied-ahead admission)
    3: "no_rule",         # NO_RULE_EXISTS
    4: "too_many_request",  # namespace guard tripped
    5: "fail",            # device step failed / degraded
    6: "release_ok",      # a concurrency token given back
    7: "already_release",  # a release of an id that holds nothing
    8: "overload",        # admission refused: queue full / deadline / brownout
    9: "standby",         # unpromoted warm standby refused to decide
    10: "moved",          # namespace rebalanced away: redirect to new owner
    12: "degraded",       # circuit breaker OPEN/HALF_OPEN refused the row
}

# reasons on the sentinel_server_shed_total counter: every dropped or
# refused frame lands in exactly one of these
SHED_REASONS = (
    "queue_full",    # front-door queue at capacity → answered OVERLOAD
    "deadline",      # client deadline already blown → dropped (no answer)
    "brownout",      # SHED_LOW: non-prioritized rows answered OVERLOAD
    "degrade",       # DEGRADE: rows refused by the probabilistic local gate
    "lane_abandon",  # shutdown abandoned a wedged lane handoff
    "chaos_drop",    # a chaos frame_drop injector ate the frame
)

NO_RULE_NAMESPACE = "(no-rule)"  # requests whose flow_id has no loaded rule

# refusal verdict → the SLO-plane shed reason it is attributed under; every
# other verdict is a served row (it waited for a device step: it has the
# dispatch's latency)
_SLO_SHED_REASONS = {"overload": "overload", "too_many_request":
                     "namespace_guard", "moved": "moved",
                     "degraded": "degraded"}

# The count matrix of one dispatch: a row per TokenStatus code up to the
# largest VERDICT_NAMES names, a column per namespace of the dispatch's
# ns_names snapshot behind column 0, (no-rule). _CODE_ROW takes a status
# byte to its row; a byte that names no verdict goes to a row past the
# matrix, which is cut off.
_N_CODES = max(VERDICT_NAMES) + 1
_CODE_ROW = np.full(256, _N_CODES, np.intp)
_CODE_ROW[list(VERDICT_NAMES)] = list(VERDICT_NAMES)
_SHED_CODES = tuple(
    (code, _SLO_SHED_REASONS[name]) for code, name in VERDICT_NAMES.items()
    if name in _SLO_SHED_REASONS
)
_SERVED_CODES = np.array(
    [code for code, name in VERDICT_NAMES.items()
     if name not in _SLO_SHED_REASONS], np.intp,
)
_PASS, _BLOCK, _SHOULD_WAIT = 0, 1, 2  # the timeline's own columns


class _PendingAccount:
    """What the dispatches of ONE wall second, attributed under ONE
    ``ns_names`` snapshot, have deposited and the sinks have not seen: the
    summed count matrix and, per namespace column, what the SLO plane and
    the timeline need of each dispatch's one latency. Guarded by
    ``ServerMetrics._verdict_lock``; folded by ``ServerMetrics._fold``."""

    __slots__ = ("sec", "names", "verdicts", "slo_lat", "tl_lat", "lat_rows",
                 "over", "lat_sum", "lat_max", "wait_counts", "wait_sum",
                 "wait_max")

    def __init__(self, sec: int, names: Tuple[str, ...], n_slo_buckets: int,
                 n_wait_buckets: int):
        self.sec = sec
        self.names = names
        cols = len(names) + 1
        self.verdicts = np.zeros((_N_CODES, cols), np.int64)
        # served rows that carried a latency: by DECISION_BOUNDS bucket
        # (a latency no histogram takes, negative or NaN, is in none), by
        # the timeline's _EDGES bucket, all of them, and those over the
        # objective; the latencies' sum over rows and their largest
        self.slo_lat = np.zeros((n_slo_buckets, cols), np.int64)
        self.tl_lat = np.zeros((len(_TIMELINE_EDGES) + 1, cols), np.int64)
        self.lat_rows = np.zeros(cols, np.int64)
        self.over = np.zeros(cols, np.int64)
        self.lat_sum = np.zeros(cols, np.float64)
        self.lat_max = np.zeros(cols, np.float64)
        # positive wait hints by wait_assigned_ms bucket
        self.wait_counts = np.zeros(n_wait_buckets, np.int64)
        self.wait_sum = 0.0
        self.wait_max = 0.0


def _escape(label: str) -> str:
    return label.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _RateWindow:
    """Windowed events/sec over the last ``seconds`` wall seconds, current
    second included (so short-lived tests and fresh servers report > 0)."""

    def __init__(self, seconds: int = 8):
        self.seconds = max(1, int(seconds))
        self._slots = [(-1, 0)] * self.seconds  # (second, count)
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        sec = _clock.now_ms() // 1000
        i = sec % self.seconds
        with self._lock:
            slot_sec, count = self._slots[i]
            self._slots[i] = (sec, count + n if slot_sec == sec else n)

    def rate(self) -> float:
        sec = _clock.now_ms() // 1000
        lo = sec - self.seconds + 1
        with self._lock:
            total = sum(c for s, c in self._slots if s >= lo)
        return total / float(self.seconds)

    def reset(self) -> None:
        with self._lock:
            self._slots = [(-1, 0)] * self.seconds


class ServerMetrics:
    """All ``sentinel_server_*`` state for this process's token server(s)."""

    # gauges every scrape shows even before a server registers a live reader
    _GAUGE_NAMES = (
        "queue_depth", "inflight_batches", "connections",
        "dispatch_lane_depth", "reply_lane_depth",
        "shm_ring_occupancy", "device_inflight",
    )

    # phase histograms inside one dispatch: (member, what it covers). Always
    # on, one record per phase per dispatch, never per row. Over any window
    # permit_wait + prep + lock_wait + launch reconciles with dispatch_ms's
    # sum. decide_ms's sum reconciles with device_wait + fetch on the native
    # lane (it times the read half there: account runs after the reply) and
    # holds account too where the materializer is called whole. The first
    # four are timed on the dispatching thread, the rest on the
    # materializing one.
    # (compile_ms is no phase; it rides the same four loops below.)
    _PHASES = (
        ("permit_wait_ms",
         "Device lane blocked on max_device_inflight, per dispatch (ms)."),
        ("prep_ms",
         "Token service host prep before the service lock: asarray, uniform "
         "test, bucket pick, slot lookup + grouping sort, step lookup (ms)."),
        ("lock_wait_ms",
         "Wait for the token service lock per dispatch: leases, outcome "
         "step, rule loads, snapshots, a compile in front of a decide (ms)."),
        ("launch_ms",
         "Service lock acquired to dispatch issued: re-prep after a reload, "
         "engine clock, the jitted call (transfer + enqueue), dirty-set, "
         "and, past the lock, starting the verdict buffer's copy to the "
         "host (ms)."),
        ("reply_queue_wait_ms",
         "Dispatched group parked between the device lane and a reply lane "
         "(ms)."),
        ("device_wait_ms",
         "Materialize until the one verdict buffer is on the host: what is "
         "left of the device step and of the copy started at launch (ms)."),
        ("fetch_ms",
         "Unpack the verdict buffer, unsort to request order, MOVED "
         "overlay; no copy from the device (ms)."),
        ("account_ms",
         "Always-on accounting per dispatch: namespace attribution, verdict "
         "counters, SLO plane, timeline, stat log, breaker scan; on the "
         "native lane after the reply was submitted (ms)."),
        ("compile_ms",
         "Backend compiles (persistent-cache hits included), each (ms)."),
        # the outcome path, one record per completion report
        ("outcome_lock_wait_ms",
         "Wait for the token service lock per outcome ingest (ms)."),
        ("outcome_launch_ms",
         "Service lock acquired to outcome step issued: drop counters, "
         "engine clock, the jitted call, dirty-set (ms)."),
        ("outcome_age_ms",
         "A completion report's wait from the door (or the in-process "
         "call) to the launch of the step that ingested it (ms)."),
    )

    _PARAM_COUNTERS = {
        "param_dispatch_total":
            "Hot-parameter dispatches: calls of the batched param entry "
            "that reached the sketch step (cumulative).",
        "param_requests_total":
            "Requests decided by hot-parameter dispatches (cumulative).",
        "param_values_total":
            "(request, value) rows those requests carried (cumulative).",
        "param_blocked_total":
            "Hot-parameter requests answered BLOCKED (cumulative).",
        "param_no_rule_total":
            "Hot-parameter requests on a flow id with no param rule, "
            "answered without touching the sketch (cumulative).",
    }

    # the reference client's one-request PARAM_FLOW frames (type 2) on the
    # native door: served on its data plane, counted by the device lane a
    # dispatch at a time; those the control loop still answers (the shm
    # door's, and a TCP door's frame with no value) are counted there
    _PARAM_SINGLE_COUNTERS = {
        "param_single_frames_total":
            "Single PARAM_FLOW frames the native door's data plane pulls "
            "carried to a hot-parameter dispatch (cumulative).",
        "param_single_pulls_total":
            "Data-plane pulls that carried at least one single PARAM_FLOW "
            "frame (cumulative).",
        "param_single_dispatch_total":
            "Hot-parameter dispatches of the native device lane that "
            "carried at least one single PARAM_FLOW frame (cumulative).",
        "param_single_rows_total":
            "(request, value) rows of the single PARAM_FLOW frames those "
            "dispatches carried (cumulative).",
        "param_control_frames_total":
            "Single PARAM_FLOW frames the native server's control loop "
            "answered: none of a TCP door's that carry a value "
            "(cumulative).",
    }

    # the native server's control thread (server_native._control_loop): it
    # sleeps on the doors' bell and drains their control queues when it wakes
    _CONTROL_COUNTERS = {
        "control_wakeups_total":
            "Returns of the native control thread's wait: a door rang the "
            "bell, or the 100 ms time-out passed (every 2 ms sleep, where "
            "the library has no bell) (cumulative).",
        "control_idle_wakeups_total":
            "Those after which no door had a control event queued: the "
            "time-outs of an idle control plane (cumulative).",
    }

    # the concurrency lane (DefaultTokenService.dispatch_concurrent_batch)
    _CONCURRENT_COUNTERS = {
        "concurrent_dispatch_total":
            "Concurrency dispatches: calls of the batched entry that "
            "reached the device step, idle ticks left out (cumulative).",
        "concurrent_acquire_rows_total":
            "Acquire rows those dispatches carried (cumulative).",
        "concurrent_release_rows_total":
            "Release rows those dispatches carried (cumulative).",
        "concurrent_blocked_total":
            "Acquire rows answered BLOCKED: the flow's level was held "
            "(cumulative).",
        "concurrent_already_release_total":
            "Release rows answered ALREADY_RELEASE: released, expired, "
            "never issued, or the slot reused since (cumulative).",
        "concurrent_expired_total":
            "Tokens reclaimed by expiry, dispatches and idle ticks "
            "(cumulative).",
        "concurrent_table_full_total":
            "Acquire rows answered FAIL because their slot of the token "
            "ring still held a live token (cumulative).",
    }

    # the native device lane's turns (server_native._device_loop): a
    # dispatch is one kind of pull (flow rows, hot-parameter rows,
    # concurrency rows), a queued pull of another kind waits in ``held`` for
    # the next turn and cuts the fusion short. Per kind, and what the turn
    # between kinds costs; sums of milliseconds are floats
    LANE_KINDS = ("flow", "param", "concurrent")
    _LANE_TURN_COUNTERS = {
        "lane_turns_flow_total":
            "Dispatches of the native device lane whose pulls were "
            "flow rows (cumulative).",
        "lane_turns_param_total":
            "Dispatches of the native device lane whose pulls were "
            "hot-parameter rows (cumulative).",
        "lane_turns_concurrent_total":
            "Dispatches of the native device lane whose pulls were "
            "concurrency rows (cumulative).",
        "lane_turn_rows_flow_total":
            "Rows the lane's dispatches of flow rows carried "
            "(cumulative).",
        "lane_turn_rows_param_total":
            "Rows the lane's dispatches of hot-parameter rows carried "
            "(cumulative).",
        "lane_turn_rows_concurrent_total":
            "Rows the lane's dispatches of concurrency rows carried "
            "(cumulative).",
        "lane_turn_pulls_flow_total":
            "Pulls the lane's dispatches of flow rows carried: the "
            "count beside lane_queue_wait_ms_flow_total (cumulative).",
        "lane_turn_pulls_param_total":
            "Pulls the lane's dispatches of hot-parameter rows "
            "carried: the count beside lane_queue_wait_ms_param_total "
            "(cumulative).",
        "lane_turn_pulls_concurrent_total":
            "Pulls the lane's dispatches of concurrency rows carried: "
            "the count beside lane_queue_wait_ms_concurrent_total "
            "(cumulative).",
        "lane_queue_wait_ms_flow_total":
            "queue_wait_ms summed over the pulls of flow rows, ms "
            "(cumulative).",
        "lane_queue_wait_ms_param_total":
            "queue_wait_ms summed over the pulls of hot-parameter "
            "rows, ms (cumulative).",
        "lane_queue_wait_ms_concurrent_total":
            "queue_wait_ms summed over the pulls of concurrency rows, "
            "ms (cumulative).",
        "lane_decides_flow_total":
            "Dispatches of flow rows whose verdicts a native reply "
            "lane has read: the count beside "
            "lane_decide_ms_flow_total (cumulative).",
        "lane_decides_param_total":
            "Dispatches of hot-parameter rows whose verdicts a native "
            "reply lane has read: the count beside "
            "lane_decide_ms_param_total (cumulative).",
        "lane_decides_concurrent_total":
            "Dispatches of concurrency rows whose verdicts a native "
            "reply lane has read: the count beside "
            "lane_decide_ms_concurrent_total (cumulative).",
        "lane_decide_ms_flow_total":
            "decide_ms summed over the lane's dispatches of flow "
            "rows, ms (cumulative).",
        "lane_decide_ms_param_total":
            "decide_ms summed over the lane's dispatches of "
            "hot-parameter rows, ms (cumulative).",
        "lane_decide_ms_concurrent_total":
            "decide_ms summed over the lane's dispatches of "
            "concurrency rows, ms (cumulative).",
        "lane_kind_switches_total":
            "Dispatches of the native device lane whose kind differed "
            "from the dispatch before (cumulative).",
        "lane_held_turns_total":
            "Dispatches that began from the pull the turn before had "
            "set aside in held, because it was of another kind "
            "(cumulative).",
        "lane_held_wait_ms_total":
            "Time those pulls waited in held, from their pop to the "
            "start of their own dispatch_ms, ms (cumulative).",
    }

    # what the decide step says of its cond-gated arms, per flow dispatch
    # (engine.decide.ARM_*): the step hands the predicates and row counts out
    # inside its packed verdicts, the service counts them here
    _ARM_COUNTERS = {
        "decide_dispatch_total":
            "Flow dispatches accounted: single decide steps and fused "
            "spans, one each (cumulative).",
        "decide_rows_total":
            "Rows those flow dispatches decided (cumulative).",
        "decide_shaping_live_total":
            "Flow dispatches whose step took the live branch of its "
            "shaping cond: a WARM_UP or WARM_UP_RATE_LIMITER row was in "
            "the batch (cumulative).",
        "decide_pacing_live_total":
            "Flow dispatches whose step took the live branch of its pacing "
            "cond: a RATE_LIMITER or WARM_UP_RATE_LIMITER row was in the "
            "batch (cumulative).",
        "decide_occupy_live_total":
            "Flow dispatches whose step took the live branch of its occupy "
            "cond: a prioritized row was in the batch (cumulative).",
        "decide_all_arms_live_total":
            "Flow dispatches whose step took all three live branches "
            "(cumulative).",
        "decide_shaped_rows_total":
            "Rows on a rule with a control behaviour other than DEFAULT "
            "that reached the shaping or pacing arm (cumulative).",
        "decide_paced_rows_total":
            "Rows on a RATE_LIMITER or WARM_UP_RATE_LIMITER rule that "
            "reached the pacing arm (cumulative).",
        "decide_prioritized_rows_total":
            "Prioritized rows in flow dispatches (cumulative).",
        # the breaker arm (engine.decide.ARM_BREAKER and the four counts
        # behind it), per flow dispatch
        "decide_breaker_live_total":
            "Flow dispatches whose step took the live branch of its breaker "
            "cond: a row on a flow with a DegradeRule was in the batch "
            "(cumulative).",
        "decide_guarded_rows_total":
            "Rows on a flow with a DegradeRule that reached the breaker arm "
            "(cumulative).",
        "decide_degraded_rows_total":
            "Rows the breaker arm answered DEGRADED (cumulative).",
        "breaker_probe_tickets_total":
            "Probe tickets the breaker arm gave: moves OPEN to HALF_OPEN, "
            "and stale probes armed again (cumulative).",
        "breaker_to_open_total":
            "Flows the breaker arm tripped CLOSED to OPEN (cumulative).",
        # what the outcome steps say (engine.outcome.TALLY_*), counted when
        # a later ingest or a scrape finds the step finished
        "breaker_to_closed_total":
            "HALF_OPEN breakers a completion report closed (cumulative).",
        "breaker_reopened_total":
            "HALF_OPEN breakers a completion report sent back to OPEN "
            "(cumulative).",
        "outcome_frames_total":
            "Completion reports (OUTCOME_REPORT frames or in-process calls) "
            "ingested (cumulative).",
        "outcome_steps_total":
            "Outcome steps launched: one per report with a valid row "
            "(cumulative).",
        "outcome_step_rows_total":
            "Completion rows those steps scattered (cumulative).",
    }

    # A verdict's time at the native TCP door, from the socket's last byte
    # in to its last byte out, all on CLOCK_MONOTONIC (time.monotonic_ns):
    #   door_in | door_wake | intake | queue_wait | dispatch_ms |
    #   reply_queue_wait | decide_ms | (slice + submit call) | door_out
    # and door_residence over the whole of it. door_in, door_out and
    # door_residence are counted per frame by the door's own threads in C++
    # (sentinel_frontdoor.cpp) and folded in here on every read
    # (register_door_spans); door_wake is recorded per pull by the intake
    # lane. A frame with no stamp (the shm door, an answer the intake lane
    # gave itself) is in none of the door's three.
    _DOOR_SPANS = (
        ("door_in_ms",
         "Native TCP door, per frame: the recv() that read its last byte to "
         "the pull that took it about to return to its caller (decode, the "
         "wait in the arena for an intake lane, the copy to staging) (ms)."),
        ("door_wake_ms",
         "Native TCP door, per pull: the pull returned in C to the intake "
         "lane running again in Python: the ctypes return and the wait for "
         "the GIL (ms)."),
        ("door_out_ms",
         "Native TCP door, per frame: its verdicts submitted to the door to "
         "send() having taken the last byte of its reply (outbox, eventfd "
         "wake, the IO thread's turn, EPOLLOUT stalls) (ms)."),
        ("door_residence_ms",
         "Native TCP door, per frame: last byte in to last byte out, the "
         "server's own verdict latency (ms)."),
    )

    def __init__(self):
        for name, _help in self._PHASES:
            setattr(self, name, LatencyHistogram(lo=0.001, hi=100_000.0))
        # door_residence_ms is the one histogram whose quantiles over a
        # window are read by difference (stage_snapshot): 20 bounds a decade
        # from 0.1 ms, so that a median of 3 ms is read to about 0.1 ms and
        # not to 1
        for name, _help in self._DOOR_SPANS:
            setattr(self, name, (
                LatencyHistogram(lo=0.1, hi=10_000.0, per_decade=20)
                if name == "door_residence_ms"
                else LatencyHistogram(lo=0.001, hi=10_000.0)
            ))
        # readers of live doors' span counters -> their last reading
        self._door_span_readers: Dict[Callable[[], dict], dict] = {}
        self._door_span_lock = threading.Lock()
        # backend compiles seen by the program's own jax.monitoring listener
        # (core/compile_cache.py); after_warmup = ended after
        # DefaultTokenService.warmup() returned, i.e. while serving
        self._compiles = 0
        self._compiles_after_warmup = 0
        self._warm = False  # see set_warm
        self._compile_lock = threading.Lock()
        # the hot-parameter lane (request_params_batch): dispatches, the
        # requests and (request, value) rows they carried, requests refused
        # by the sketch and requests on no rule; and what ParamConfig.impl
        # resolved to for the serving geometry, with the reason
        self._param_lock = threading.Lock()
        self._param = dict.fromkeys(self._PARAM_COUNTERS, 0)
        self._param_single = dict.fromkeys(self._PARAM_SINGLE_COUNTERS, 0)
        self._arm_lock = threading.Lock()
        self._arms = dict.fromkeys(self._ARM_COUNTERS, 0)
        self._param_impl = ("", "")
        # the concurrency lane's counters, and its gauge of live tokens
        # (what the last step said it left)
        self._concurrent_lock = threading.Lock()
        self._concurrent = dict.fromkeys(self._CONCURRENT_COUNTERS, 0)
        self._concurrent_live = 0
        self._lane_turn_lock = threading.Lock()
        self._lane_turn = dict.fromkeys(self._LANE_TURN_COUNTERS, 0)
        self._control_lock = threading.Lock()
        self._control = dict.fromkeys(self._CONTROL_COUNTERS, 0)
        # stage histograms, all in milliseconds except batch_size (requests).
        # 1µs..10s covers a sub-100µs device step and a 1s cold compile alike.
        # queue_wait_ms: per queue item on the asyncio door; on the native
        # lane per pull, from its hand-over by the intake lane (where
        # intake_ms ends) to the start of the dispatch_ms that took it
        self.queue_wait_ms = LatencyHistogram(lo=0.001, hi=10_000.0)
        self.decide_ms = LatencyHistogram(lo=0.001, hi=10_000.0)
        self.write_ms = LatencyHistogram(lo=0.001, hi=10_000.0)
        self.batch_size = LatencyHistogram(
            bounds=[float(1 << i) for i in range(17)]  # 1..65536, ×2 ladder
        )
        # per-lane stage histograms for the staged native pipeline:
        # intake_ms = wait_batch pull → handoff enqueue (decode copy + prep);
        # dispatch_ms = drain of the handoff queue → device dispatch issued
        # (host prep + async enqueue; the device step itself is decide_ms).
        self.intake_ms = LatencyHistogram(lo=0.001, hi=10_000.0)
        self.dispatch_ms = LatencyHistogram(lo=0.001, hi=10_000.0)
        # fused multi-frame dispatch: how many engine-batch frames each
        # chained device step folded together (depth 1 = unfused)
        self.fused_depth = LatencyHistogram(
            bounds=[float(1 << i) for i in range(7)]  # 1..64, ×2 ladder
        )
        self._fused_frames = 0
        self._fused_lock = threading.Lock()
        # the materializers' device-to-host reads: how many blocked, and how
        # many found the device already done with the verdict buffer
        self._verdict_host_reads = 0
        self._verdict_copy_ready = 0
        # dispatches whose account half ran after their reply was submitted
        self._reply_first = 0
        # dispatches whose host prep was the native pass, by lane (the
        # trace ring's numbers: flow, hot-parameter, concurrency)
        self._prep_native = [0, 0, 0]
        self._verdict_read_lock = threading.Lock()
        # traffic-shaping waits: every SHOULD_WAIT verdict that carried a
        # positive wait hint (paced admission or priority occupy) — count
        # plus the distribution of assigned waits (whole ms, ≥ 1)
        self._wait_assigned_ms = LatencyHistogram(lo=1.0, hi=60_000.0)
        self._wait_bounds = np.asarray(self._wait_assigned_ms.bounds)
        self._wait_assigned = 0
        self._verdicts: Dict[Tuple[str, str], int] = {}
        self._verdict_lock = threading.Lock()
        # what record_verdict_batch has deposited and not yet fanned out to
        # the dict above, the SLO plane and the timeline: at most one
        # second's worth (the deposit of a later second folds it), folded
        # by every reader first. _fold_lock is held from taking the record
        # to the last sink's update, so a reader that waited for it has
        # everything deposited before it asked
        self._pending: Optional[_PendingAccount] = None
        self._fold_lock = threading.Lock()
        self._account_folds = 0
        self._rate = _RateWindow()
        # shed accounting: frames the server refused (answered OVERLOAD) or
        # dropped (deadline blown, abandoned lane), by reason — the number
        # that used to be invisible when _lane_put gave up silently
        self._shed: Dict[str, int] = {}
        self._shed_lock = threading.Lock()
        # per-intake-shard pull accounting (multi-door native server):
        # shard → {pulls, requests, busy_ms}. busy_ms is cumulative lane
        # busy time, so occupancy over a window is rate(busy_ms)/1000.
        self._shards: Dict[int, Dict[str, float]] = {}
        self._shard_lock = threading.Lock()
        # host bytes copied on the serving path (arena→staging memcpy,
        # fusion concatenate) — the bench divides by verdicts served to
        # report bytes-copied-per-verdict
        self._copy_bytes = 0
        self._copy_lock = threading.Lock()
        # double-buffered device lane: host prep/dispatch time spent while
        # an earlier fused group was still computing on device — work a
        # depth-1 lane would have serialized behind block_until_ready
        self._overlap_ms = 0.0
        self._overlap_lock = threading.Lock()
        self._gauges: Dict[str, Callable[[], float]] = {}
        self._gauge_lock = threading.Lock()
        # sketch observability: the live token service registers a zero-arg
        # provider returning sketch.sketch_stats() (variant, fat/slim bytes,
        # merge counters). Most recent registration wins — same model as a
        # replacement server's gauges.
        self._sketch_provider: Optional[Callable[[], dict]] = None
        self._sketch_lock = threading.Lock()
        # shm front-door observability: the native server registers a
        # zero-arg provider returning the shm door's poll/doorbell/ring-full
        # counters (each independently monotonic; no cross-counter snapshot)
        self._shm_provider: Optional[Callable[[], dict]] = None
        self._shm_lock = threading.Lock()
        # wire-rev-5 lease observability: the live token service registers
        # a zero-arg provider returning its lease_stats() block (cumulative
        # granted/renewed/returned/revoked + outstanding gauges). Same
        # most-recent-wins weakref model as the sketch provider.
        self._lease_provider: Optional[Callable[[], dict]] = None
        self._lease_lock = threading.Lock()
        self._hier_provider: Optional[Callable[[], dict]] = None
        self._hier_lock = threading.Lock()
        # wire-rev-6 outcome observability: the live token service registers
        # a zero-arg provider returning its outcome_stats() block (reported/
        # exception/drop counters + per-flow windowed RT reads off the device
        # outcome columns). Same most-recent-wins weakref model as the rest.
        self._outcome_provider: Optional[Callable[[], dict]] = None
        self._outcome_lock = threading.Lock()
        # circuit-breaker observability: the live token service registers
        # a zero-arg reader returning its breaker_stats() block (per-flow
        # breaker state + clocks, read from the device state columns), and
        # pushes CLOSED/OPEN/HALF_OPEN transition edges through
        # count_breaker_transition as its host mirror observes them.
        self._breaker_provider: Optional[Callable[[], dict]] = None
        self._breaker_transitions: Dict[Tuple[str, str], int] = {}
        self._breaker_lock = threading.Lock()
        # wire-rev-7 push-plane observability: frames emitted by the push
        # hub (by type), lease revocations pushed, and the server-emit →
        # client-apply staleness histogram. Staleness is recorded by the
        # client-side apply off the frame's emit stamp — co-located
        # clients (shm, drills, sidecars sharing the exporter) land it in
        # this process; a remote client's applies surface on its own
        # exporter. A provider exposes the live hub's connection count
        # and drop counters.
        self._push_frames: Dict[str, int] = {}
        self._push_revocations = 0
        self._push_lock = threading.Lock()
        self.push_staleness_ms = LatencyHistogram(lo=0.01, hi=60_000.0)
        self._push_provider: Optional[Callable[[], dict]] = None

    # -- fused dispatch counters --------------------------------------------
    def record_fused(self, depth: int) -> None:
        """One fused device dispatch folding ``depth`` engine-batch frames
        into a single chained step (records the amortization the serving
        path achieved; depth 1 would mean no fusion and is not recorded)."""
        with self._fused_lock:
            self._fused_frames += int(depth)
        self.fused_depth.record(float(depth))

    @property
    def fused_frames_total(self) -> int:
        with self._fused_lock:
            return self._fused_frames

    def count_verdict_read(self, ready: bool) -> None:
        """One materializer made its one blocking read of a dispatch's
        verdict buffer; ``ready`` is the buffer's ``is_ready()`` on entry:
        the device had finished before the reply lane asked, so what
        ``device_wait_ms`` then holds is the copy and the GIL."""
        with self._verdict_read_lock:
            self._verdict_host_reads += 1
            if ready:
                self._verdict_copy_ready += 1

    @property
    def verdict_host_reads_total(self) -> int:
        with self._verdict_read_lock:
            return self._verdict_host_reads

    def count_reply_first(self) -> None:
        """One dispatch was accounted after its reply had been submitted:
        the native reply lane ran the materializer's two halves apart.
        Whoever calls a materializer whole never counts here."""
        with self._verdict_read_lock:
            self._reply_first += 1

    @property
    def reply_first_total(self) -> int:
        with self._verdict_read_lock:
            return self._reply_first

    def count_prep_native(self, lane: int = 0) -> None:
        """One dispatch of ``lane`` (``trace.ring``'s number: 0 flow,
        ``PARAM_LANE``, ``CONCURRENT_LANE``) was prepped by its native pass
        (``sn_flow_prep`` / ``sn_param_prep`` / ``sn_concurrent_prep``). A
        dispatch prepped in numpy, because the library is not built, does
        not count: over ``prep_ms``'s count this says whether the mechanism
        engaged."""
        with self._verdict_read_lock:
            self._prep_native[lane] += 1

    @property
    def prep_native_total(self) -> int:
        with self._verdict_read_lock:
            return self._prep_native[0]

    @property
    def param_prep_native_total(self) -> int:
        with self._verdict_read_lock:
            return self._prep_native[1]

    @property
    def concurrent_prep_native_total(self) -> int:
        with self._verdict_read_lock:
            return self._prep_native[2]

    def count_param_dispatch(self, requests: int, values: int, blocked: int,
                             no_rule: int) -> None:
        """One hot-parameter dispatch was accounted."""
        with self._param_lock:
            p = self._param
            p["param_dispatch_total"] += 1
            p["param_requests_total"] += int(requests)
            p["param_values_total"] += int(values)
            p["param_blocked_total"] += int(blocked)
            p["param_no_rule_total"] += int(no_rule)

    def count_param_singles(self, frames: int, pulls: int,
                            rows: int) -> None:
        """One hot-parameter dispatch of the native device lane carried
        ``frames`` single PARAM_FLOW frames of ``rows`` (request, value)
        rows, which came in ``pulls`` of its pulls."""
        with self._param_lock:
            p = self._param_single
            p["param_single_frames_total"] += int(frames)
            p["param_single_pulls_total"] += int(pulls)
            p["param_single_dispatch_total"] += 1
            p["param_single_rows_total"] += int(rows)

    def count_param_control_frames(self, frames: int) -> None:
        """The control loop answered ``frames`` single PARAM_FLOW frames."""
        with self._param_lock:
            self._param_single["param_control_frames_total"] += int(frames)

    def count_control_wakeup(self, idle: bool) -> None:
        """The native control thread's wait returned; ``idle``: the drain
        that followed found no control event on any door."""
        with self._control_lock:
            self._control["control_wakeups_total"] += 1
            self._control["control_idle_wakeups_total"] += bool(idle)

    def control_totals(self) -> Dict[str, int]:
        with self._control_lock:
            return dict(self._control)

    def count_concurrent_step(self, acquires: int, releases: int,
                              blocked: int, already: int, expired: int,
                              table_full: int, live: int,
                              tick: bool = False) -> None:
        """One concurrency step was read back: a dispatch, or an idle tick
        (which counts what it expired and nothing else)."""
        with self._concurrent_lock:
            c = self._concurrent
            if not tick:
                c["concurrent_dispatch_total"] += 1
                c["concurrent_acquire_rows_total"] += int(acquires)
                c["concurrent_release_rows_total"] += int(releases)
                c["concurrent_blocked_total"] += int(blocked)
                c["concurrent_already_release_total"] += int(already)
                c["concurrent_table_full_total"] += int(table_full)
            c["concurrent_expired_total"] += int(expired)
            self._concurrent_live = int(live)

    def concurrent_totals(self) -> Dict[str, int]:
        """The lane's counters and the gauge ``concurrent_tokens_live``."""
        with self._concurrent_lock:
            return dict(self._concurrent,
                        concurrent_tokens_live=self._concurrent_live)

    def count_lane_turn(self, kind: str, rows: int, pulls: int,
                        queue_wait_ms: float, switched: bool,
                        held_wait_ms: Optional[float] = None) -> None:
        """The native device lane dispatched ``pulls`` pulls of ``kind``
        (one of ``LANE_KINDS``) with ``rows`` rows, whose queue waits add up
        to ``queue_wait_ms``; ``switched``: the dispatch before was of
        another kind; ``held_wait_ms``: how long the first pull had waited
        in ``held``, None when the turn did not begin from it."""
        with self._lane_turn_lock:
            t = self._lane_turn
            t[f"lane_turns_{kind}_total"] += 1
            t[f"lane_turn_rows_{kind}_total"] += int(rows)
            t[f"lane_turn_pulls_{kind}_total"] += int(pulls)
            t[f"lane_queue_wait_ms_{kind}_total"] += queue_wait_ms
            t["lane_kind_switches_total"] += bool(switched)
            if held_wait_ms is not None:
                t["lane_held_turns_total"] += 1
                t["lane_held_wait_ms_total"] += held_wait_ms

    def count_lane_decide(self, kind: str, ms: float) -> None:
        """A reply lane read the verdicts of one ``kind`` dispatch in
        ``ms`` (what it recorded in ``decide_ms``)."""
        with self._lane_turn_lock:
            self._lane_turn[f"lane_decides_{kind}_total"] += 1
            self._lane_turn[f"lane_decide_ms_{kind}_total"] += ms

    def lane_turn_totals(self) -> Dict[str, float]:
        with self._lane_turn_lock:
            return dict(self._lane_turn)

    def count_decide_arms(self, rows: int, shaping: bool, pacing: bool,
                          occupy: bool, shaped: int, paced: int,
                          prioritized: int, breaker=(0, 0, 0, 0, 0)) -> None:
        """One flow dispatch was accounted: which of its step's arms ran,
        and the rows those arms had before them. ``breaker`` is the breaker
        arm's ``(live, guarded rows, degraded rows, probe tickets, flows
        tripped)``."""
        live, guarded, degraded, probes, to_open = breaker
        with self._arm_lock:
            a = self._arms
            a["decide_dispatch_total"] += 1
            a["decide_rows_total"] += rows
            a["decide_shaping_live_total"] += bool(shaping)
            a["decide_pacing_live_total"] += bool(pacing)
            a["decide_occupy_live_total"] += bool(occupy)
            a["decide_all_arms_live_total"] += bool(
                shaping and pacing and occupy)
            a["decide_shaped_rows_total"] += shaped
            a["decide_paced_rows_total"] += paced
            a["decide_prioritized_rows_total"] += prioritized
            a["decide_breaker_live_total"] += bool(live)
            a["decide_guarded_rows_total"] += guarded
            a["decide_degraded_rows_total"] += degraded
            a["breaker_probe_tickets_total"] += probes
            a["breaker_to_open_total"] += to_open

    def count_outcome_report(self, rows: int) -> None:
        """One completion report was ingested; ``rows`` valid rows went
        into an outcome step (none: no step was launched)."""
        with self._arm_lock:
            a = self._arms
            a["outcome_frames_total"] += 1
            a["outcome_steps_total"] += bool(rows)
            a["outcome_step_rows_total"] += rows

    def count_breaker_resolved(self, closed: int, reopened: int) -> None:
        """A finished outcome step's tally: the HALF_OPEN breakers its
        report closed and sent back to OPEN."""
        with self._arm_lock:
            a = self._arms
            a["breaker_to_closed_total"] += closed
            a["breaker_reopened_total"] += reopened

    def arm_totals(self) -> Dict[str, int]:
        with self._arm_lock:
            return dict(self._arms)

    def param_totals(self) -> Dict[str, int]:
        with self._param_lock:
            return dict(self._param)

    def param_single_totals(self) -> Dict[str, int]:
        with self._param_lock:
            return dict(self._param_single)

    def set_param_impl(self, kernel: str, reason: str) -> None:
        """What ``ParamConfig.impl`` resolved to for the serving geometry."""
        with self._param_lock:
            self._param_impl = (str(kernel), str(reason))

    @property
    def param_impl(self) -> tuple:
        with self._param_lock:
            return self._param_impl

    @property
    def verdict_copy_ready_total(self) -> int:
        with self._verdict_read_lock:
            return self._verdict_copy_ready

    def set_warm(self, warm: bool) -> None:
        """``DefaultTokenService.warmup()`` brackets itself with
        ``set_warm(False)`` ... ``set_warm(True)``: a compile that ends
        while the flag is up happened in front of live traffic. ``reset()``
        leaves the flag alone (benches reset between load points while
        serving)."""
        with self._compile_lock:
            self._warm = bool(warm)

    def record_compile(self, ms: float) -> bool:
        """One backend compile that took ``ms`` (the program's
        ``jax.monitoring`` listener calls this). True when it ended after
        warm-up."""
        with self._compile_lock:
            self._compiles += 1
            after_warmup = self._warm
            if after_warmup:
                self._compiles_after_warmup += 1
        self.compile_ms.record(ms)
        return after_warmup

    @property
    def compiles_total(self) -> int:
        with self._compile_lock:
            return self._compiles

    @property
    def compiles_after_warmup_total(self) -> int:
        with self._compile_lock:
            return self._compiles_after_warmup

    @property
    def wait_assigned_total(self) -> int:
        self._fold_pending()
        with self._verdict_lock:
            return self._wait_assigned

    # -- intake shard + host-copy counters ----------------------------------
    def count_shard_pull(
        self, shard: int, n_rows: int, busy_ms: float
    ) -> None:
        """One intake pull handed to the device lane by ``shard``:
        ``n_rows`` requests, ``busy_ms`` of lane busy time."""
        with self._shard_lock:
            s = self._shards.setdefault(
                int(shard), {"pulls": 0, "requests": 0, "busy_ms": 0.0}
            )
            s["pulls"] += 1
            s["requests"] += int(n_rows)
            s["busy_ms"] += float(busy_ms)

    def shard_totals(self) -> Dict[int, Dict[str, float]]:
        with self._shard_lock:
            return {k: dict(v) for k, v in self._shards.items()}

    def count_copy_bytes(self, n: int) -> None:
        if n <= 0:
            return
        with self._copy_lock:
            self._copy_bytes += int(n)

    @property
    def host_copy_bytes_total(self) -> int:
        with self._copy_lock:
            return self._copy_bytes

    def count_overlap_saved_ms(self, ms: float) -> None:
        """``ms`` of host prep/dispatch that ran while an earlier fused
        group was still in flight on device (the pipelined device lane's
        measured win over a serialized depth-1 lane)."""
        if ms <= 0:
            return
        with self._overlap_lock:
            self._overlap_ms += float(ms)

    @property
    def overlap_saved_ms_total(self) -> float:
        with self._overlap_lock:
            return self._overlap_ms

    # -- shed counters ------------------------------------------------------
    def count_shed(self, reason: str, n: int = 1) -> None:
        """``n`` requests shed for ``reason`` (one of :data:`SHED_REASONS`,
        free-form tolerated so callers can't lose a count to a typo)."""
        if n <= 0:
            return
        with self._shed_lock:
            self._shed[reason] = self._shed.get(reason, 0) + int(n)

    def shed_totals(self) -> Dict[str, int]:
        with self._shed_lock:
            return dict(self._shed)

    @property
    def shed_total(self) -> int:
        with self._shed_lock:
            return sum(self._shed.values())

    def verdict_rate(self) -> float:
        """Windowed verdicts/sec — the throughput input of the BBR
        admission estimator (``overload/admission.py``)."""
        return self._rate.rate()

    # -- verdict counters ---------------------------------------------------
    def count_verdict(self, verdict: str, namespace: str, n: int = 1) -> None:
        key = (verdict, namespace)
        with self._verdict_lock:
            self._verdicts[key] = self._verdicts.get(key, 0) + n

    def verdict_totals(self) -> Dict[Tuple[str, str], int]:
        """Cumulative verdicts by ``(verdict, namespace)``."""
        self._fold_pending()
        with self._verdict_lock:
            return dict(self._verdicts)

    def verdict_totals_by_namespace(self) -> Dict[str, int]:
        """Cumulative verdicts served per namespace, all verdict classes
        summed — the admission gate diffs successive reads to rank the
        hottest namespaces for its rebalance advisories."""
        out: Dict[str, int] = {}
        for (_verdict, ns), count in self.verdict_totals().items():
            out[ns] = out.get(ns, 0) + count
        return out

    @property
    def wait_assigned_ms(self) -> LatencyHistogram:
        """Wait assigned per SHOULD_WAIT verdict, pending deposits in."""
        self._fold_pending()
        return self._wait_assigned_ms

    def record_verdict_batch(
        self,
        status: np.ndarray,
        ns_idx: Optional[np.ndarray],
        ns_names: Tuple[str, ...],
        latency_ms: Optional[float] = None,
        wait_ms: Optional[np.ndarray] = None,
        now_s: Optional[int] = None,
    ) -> Optional[np.ndarray]:
        """Count one materialized batch: ``status`` int8[N] TokenStatus
        codes, ``ns_idx`` int32[N] namespace row per request (-1 → no rule;
        None → attribute everything to ``(no-rule)``). Returns the batch's
        rows by status code, int64[_N_CODES] (None for an empty batch).

        Counted at deposit, fanned out at the fold: the batch is reduced to
        one count matrix ``[status code, namespace + 1]`` (one bincount on
        a combined key, never a Python loop over requests or namespaces)
        and added into the pending record of its wall second; the
        per-namespace updates of the verdict counters, the SLO plane and
        the timeline happen once per pending record, when a deposit of the
        next second or of another ``ns_names`` snapshot arrives and before
        every read (:meth:`_fold_pending`), so each read is exact.

        ``latency_ms`` (decision latency shared by the whole batch) feeds
        the per-tenant SLO plane; refusal statuses are attributed there as
        sheds either way. ``wait_ms`` int32[N] (the verdicts' wait hints)
        feeds the assigned-wait counter/histogram — only positive hints
        count, and only SHOULD_WAIT verdicts carry them. ``now_s`` stands
        in for the wall second (tests)."""
        # trace.slo imports this package: it cannot be imported with it
        from sentinel_tpu.trace.slo import DECISION_BOUNDS, slo_plane

        status = np.asarray(status)
        n = int(status.shape[0])
        if n == 0:
            return None
        self._rate.add(n)
        waits = None
        if wait_ms is not None:
            w = np.asarray(wait_ms)
            w = w[w > 0]
            if w.size:
                waits = (
                    np.bincount(np.searchsorted(self._wait_bounds, w),
                                minlength=len(self._wait_bounds) + 1),
                    float(w.sum()), float(w.max()),
                )
        key = _CODE_ROW[status.astype(np.uint8)]
        named = ns_idx is not None and len(ns_names) > 0
        width = len(ns_names) + 1 if named else 1
        if named:
            key = key * width + (np.asarray(ns_idx) + 1)
        counts = np.bincount(key, minlength=(_N_CODES + 1) * width)[
            :_N_CODES * width].reshape(_N_CODES, width)
        by_code = counts.sum(axis=1)
        lat = None
        if latency_ms is not None:
            # what the sinks need of the one latency, per namespace column:
            # the served rows, in the two sinks' buckets
            lat = float(latency_ms)
            served = counts[_SERVED_CODES].sum(axis=0)
            tl_bucket = int(np.searchsorted(_TIMELINE_EDGES, lat))
            slo_bucket = bisect_left(DECISION_BOUNDS, lat) if lat >= 0 else -1
            is_over = lat > slo_plane().objective_ms
        while True:
            with self._verdict_lock:
                sec = int(time.time()) if now_s is None else int(now_s)
                pend = self._pending
                if pend is None:
                    pend = self._pending = _PendingAccount(
                        sec, ns_names if named else (),
                        len(DECISION_BOUNDS) + 1, len(self._wait_bounds) + 1)
                # an unnamed batch has column 0 alone, which every record has
                if pend.sec == sec and (
                        not named or ns_names is pend.names
                        or ns_names == pend.names):
                    pend.verdicts[:, :width] += counts
                    if lat is not None:
                        pend.lat_rows[:width] += served
                        pend.tl_lat[tl_bucket, :width] += served
                        if slo_bucket >= 0:
                            pend.slo_lat[slo_bucket, :width] += served
                            pend.lat_sum[:width] += lat * served
                        if is_over:
                            pend.over[:width] += served
                        np.maximum(pend.lat_max[:width], lat * (served > 0),
                                   out=pend.lat_max[:width])
                    if waits is not None:
                        pend.wait_counts += waits[0]
                        pend.wait_sum += waits[1]
                        pend.wait_max = max(pend.wait_max, waits[2])
                    return by_code
            # another second's record, or another snapshot's: fold it first
            self._fold_pending(pend)

    def _fold_pending(self, stale: Optional[_PendingAccount] = None) -> None:
        """Hand the pending record to the sinks. Every reader of what
        :meth:`record_verdict_batch` counts calls this first. A depositor
        names the ``stale`` record it met: if another thread folded that
        one meanwhile, the record that took its place stays."""
        with self._fold_lock:
            with self._verdict_lock:
                pend = self._pending
                if pend is None or (stale is not None and pend is not stale):
                    return
                self._pending = None
            self._fold(pend)

    def _fold(self, pend: _PendingAccount) -> None:
        """The per-namespace fan-out of one pending record: verdict totals,
        the assigned-wait histogram, each touched tenant's SLO histogram,
        burn windows and shed counts, the timeline's second. Caller holds
        ``_fold_lock``."""
        from sentinel_tpu.trace.slo import slo_plane

        names = (NO_RULE_NAMESPACE,) + pend.names
        v = pend.verdicts
        rows, cols = np.nonzero(v)
        with self._verdict_lock:
            totals = self._verdicts
            for code, j, c in zip(rows.tolist(), cols.tolist(),
                                  v[rows, cols].tolist()):
                key = (VERDICT_NAMES[code], names[j])
                totals[key] = totals.get(key, 0) + c
            self._wait_assigned += int(pend.wait_counts.sum())
            self._account_folds += 1
        if pend.wait_max:
            self._wait_assigned_ms.merge(
                pend.wait_counts.tolist(), pend.wait_sum, pend.wait_max)
        # each row lands in exactly one window bucket, served OR shed, and
        # in one timeline column: pass, block, shed, other, waited. So
        # timeline sums reconcile with sentinel_server_verdicts_total and
        # with sentinel_slo_shed_total deltas
        served = v[_SERVED_CODES].sum(axis=0)
        shed = [(reason, v[code]) for code, reason in _SHED_CODES]
        n_shed = sum(col for _reason, col in shed)
        tl_counts = np.stack([
            v[_PASS], v[_BLOCK], n_shed,
            served - v[_PASS] - v[_BLOCK] - v[_SHOULD_WAIT],
            v[_SHOULD_WAIT],
        ])
        plane = slo_plane()
        lat_rows, over = pend.lat_rows.tolist(), pend.over.tolist()
        lat_sum, lat_max = pend.lat_sum.tolist(), pend.lat_max.tolist()
        waited = v[_SHOULD_WAIT].tolist()
        shed = [(reason, col.tolist()) for reason, col in shed]
        tl_rows = []
        for j in np.nonzero(served + n_shed)[0].tolist():
            ns = names[j]
            tl_rows.append((ns, tl_counts[:, j],
                            pend.tl_lat[:, j] if lat_rows[j] else None,
                            lat_max[j]))
            ns_shed = [(reason, col[j]) for reason, col in shed if col[j]]
            if lat_rows[j] or ns_shed or waited[j]:
                plane.fold(
                    ns, pend.sec,
                    lat_counts=pend.slo_lat[:, j].tolist() if lat_rows[j]
                    else None,
                    lat_sum=lat_sum[j], lat_max=lat_max[j],
                    lat_rows=lat_rows[j], over=over[j], shed=ns_shed,
                    waited=waited[j],
                )
        timeline().fold(pend.sec, tl_rows)

    @property
    def account_folds_total(self) -> int:
        """Pending records folded: beside ``account_ms``'s count, how many
        dispatches one per-namespace fan-out stood for."""
        with self._verdict_lock:
            return self._account_folds

    def count_rls(self, domain: str, ok_n: int, over_n: int) -> None:
        """Envoy RLS responses, per domain. The descriptors already counted
        once on the engine path under their rule namespace; this adds the
        RLS-shaped view (``namespace="rls:<domain>"``) without touching the
        verdicts/sec rate (no double counting)."""
        ns = f"rls:{domain}"
        with self._verdict_lock:
            if ok_n:
                key = ("pass", ns)
                self._verdicts[key] = self._verdicts.get(key, 0) + int(ok_n)
            if over_n:
                key = ("block", ns)
                self._verdicts[key] = self._verdicts.get(key, 0) + int(over_n)

    # -- gauges -------------------------------------------------------------
    def register_gauge(self, name: str, fn: Callable[[], float]) -> None:
        with self._gauge_lock:
            self._gauges[name] = fn

    def unregister_gauge(self, name: str, fn: Optional[Callable] = None) -> None:
        """Remove a gauge; with ``fn`` given, only if it is still the
        registered reader (a replacement server's gauge survives the old
        server's teardown)."""
        with self._gauge_lock:
            if fn is None or self._gauges.get(name) is fn:
                self._gauges.pop(name, None)

    def _gauge_values(self) -> Dict[str, float]:
        with self._gauge_lock:
            readers = dict(self._gauges)
        out = {name: 0.0 for name in self._GAUGE_NAMES}
        for name, fn in readers.items():
            try:
                out[name] = float(fn())
            except Exception:
                out[name] = 0.0  # a dying server's reader must not 500 a scrape
        return out

    # -- sketch provider ----------------------------------------------------
    def register_sketch_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the param-sketch stats block
        (``sentinel_tpu.sketch.sketch_stats`` shape). The most recently
        constructed service wins; providers return ``{}`` once their
        service is gone."""
        with self._sketch_lock:
            self._sketch_provider = fn

    def sketch_stats(self) -> dict:
        with self._sketch_lock:
            fn = self._sketch_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down service's reader must not 500 a scrape

    # -- the native door's spans ---------------------------------------------
    def register_door_spans(self, fn: Callable[[], dict]) -> None:
        """Install a zero-arg reader of a live server's door span counters
        (``Frontdoor.span_stats`` shape, cumulative, summed over its doors).
        What the doors have counted is folded into ``door_in_ms``,
        ``door_out_ms`` and ``door_residence_ms`` by difference on every
        read of this registry, so the series stay monotonic across servers
        and restarts; unregister (which folds once more) before the doors
        go."""
        with self._door_span_lock:
            self._door_span_readers.setdefault(fn, {})

    def unregister_door_spans(self, fn: Callable[[], dict]) -> None:
        with self._door_span_lock:
            if fn in self._door_span_readers:
                self._fold_door_reader(fn)
                del self._door_span_readers[fn]

    def _fold_door_spans(self) -> None:
        with self._door_span_lock:
            for fn in self._door_span_readers:
                self._fold_door_reader(fn)

    def _fold_door_reader(self, fn) -> None:
        try:
            now = fn() or {}
        except Exception:
            return  # a torn-down door's reader must not 500 a scrape
        last = self._door_span_readers[fn]
        for name, (_count, sum_ms, max_ms, counts) in now.items():
            prev = last.get(name)
            if prev is None:
                d_counts, d_sum, d_max = counts, sum_ms, max_ms
            else:
                # the door's max is since its start: it is this stretch's
                # own only where it grew (a reset() is not undone by it)
                d_counts, d_sum = counts - prev[1], sum_ms - prev[0]
                d_max = max_ms if max_ms > prev[2] else 0.0
            getattr(self, name).merge(d_counts, d_sum, d_max)
            last[name] = (sum_ms, counts, max_ms)

    # -- shm front door provider --------------------------------------------
    def register_shm_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the shm ring door's counters
        (``{"polls", "doorbells", "ring_full", "segments"}``). Most recent
        registration wins; providers return ``{}`` once their door is
        gone. Values are independently monotonic relaxed atomics — the
        exporter renders each as its own counter, never arithmetic across
        them."""
        with self._shm_lock:
            self._shm_provider = fn

    def shm_stats(self) -> dict:
        with self._shm_lock:
            fn = self._shm_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down door's reader must not 500 a scrape

    # -- lease provider -----------------------------------------------------
    def register_lease_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the token service's lease stats
        (``DefaultTokenService.lease_stats`` shape). Most recent
        registration wins; providers return ``{}`` once their service is
        gone."""
        with self._lease_lock:
            self._lease_provider = fn

    def lease_stats(self) -> dict:
        with self._lease_lock:
            fn = self._lease_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down service's reader must not 500 a scrape

    # -- hierarchy provider -------------------------------------------------
    def register_hier_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the hierarchy tier's stats
        (``DefaultTokenService.hier_stats`` shape — coordinator ledger
        and/or share-agent counters, ``{}`` when neither is attached).
        Most recent registration wins."""
        with self._hier_lock:
            self._hier_provider = fn

    def hier_stats(self) -> dict:
        with self._hier_lock:
            fn = self._hier_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down service's reader must not 500 a scrape

    # -- outcome provider ---------------------------------------------------
    def register_outcome_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the token service's completion
        outcome stats (``DefaultTokenService.outcome_stats`` shape:
        cumulative reported/exception/drop counters plus per-flow windowed
        complete/exception QPS and RT avg/p99 read from the device outcome
        columns). Most recent registration wins; providers return ``{}``
        once their service is gone."""
        with self._outcome_lock:
            self._outcome_provider = fn

    def outcome_stats(self) -> dict:
        with self._outcome_lock:
            fn = self._outcome_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down service's reader must not 500 a scrape

    # -- breaker provider ---------------------------------------------------
    def register_breaker_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the token service's circuit
        breaker stats (``DefaultTokenService.breaker_stats`` shape:
        per-flow breaker state name + clocks read from the device
        ``BreakerState`` columns; ``{}`` with no breakers loaded). Most
        recent registration wins; providers return ``{}`` once their
        service is gone."""
        with self._breaker_lock:
            self._breaker_provider = fn

    def breaker_stats(self) -> dict:
        with self._breaker_lock:
            fn = self._breaker_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down service's reader must not 500 a scrape

    def count_breaker_transition(
        self, from_state: str, to_state: str, n: int = 1
    ) -> None:
        """``n`` breaker transitions ``from_state`` → ``to_state`` observed
        by the host mirror (state names: closed / open / half_open)."""
        if n <= 0:
            return
        key = (str(from_state), str(to_state))
        with self._breaker_lock:
            self._breaker_transitions[key] = (
                self._breaker_transitions.get(key, 0) + int(n)
            )

    def breaker_transition_totals(self) -> Dict[Tuple[str, str], int]:
        with self._breaker_lock:
            return dict(self._breaker_transitions)

    # -- push plane ---------------------------------------------------------
    def count_push_frame(self, type_name: str, n: int = 1) -> None:
        """``n`` rev-7 push frames of ``type_name`` handed to connection
        sinks (counted per delivery attempt that reached a sink, not per
        broadcast call — a hub with no connections counts nothing)."""
        if n <= 0:
            return
        with self._push_lock:
            self._push_frames[type_name] = (
                self._push_frames.get(type_name, 0) + int(n)
            )

    def count_push_revocation(self, n: int = 1) -> None:
        """``n`` leases recalled through pushed LEASE_REVOKE frames (one
        per revoked lease, regardless of how many connections heard it)."""
        if n <= 0:
            return
        with self._push_lock:
            self._push_revocations += int(n)

    def record_push_staleness(self, ms: float, n: int = 1) -> None:
        """One server-emit → client-apply staleness sample (ms), recorded
        by the client-side push apply off the frame's emit stamp."""
        self.push_staleness_ms.record(max(0.0, float(ms)), n)

    def push_frame_totals(self) -> Dict[str, int]:
        with self._push_lock:
            return dict(self._push_frames)

    @property
    def push_revocations_total(self) -> int:
        with self._push_lock:
            return self._push_revocations

    def register_push_provider(self, fn: Callable[[], dict]) -> None:
        """Install the zero-arg reader for the live push hub's state
        (``PushHub.stats`` shape: attached connections, per-type emit
        counts, drops). Most recent registration wins; providers return
        ``{}`` once their hub is gone."""
        with self._push_lock:
            self._push_provider = fn

    def push_stats(self) -> dict:
        with self._push_lock:
            fn = self._push_provider
        if fn is None:
            return {}
        try:
            return dict(fn() or {})
        except Exception:
            return {}  # a torn-down hub's reader must not 500 a scrape

    # -- snapshots ----------------------------------------------------------
    def snapshot(self) -> dict:
        """JSON shape served by the ``clusterServerStats`` command — the
        same numbers the Prometheus surface renders."""
        self._fold_door_spans()
        self._fold_pending()
        with self._verdict_lock:
            verdicts = [
                {"verdict": v, "namespace": ns, "count": c}
                for (v, ns), c in sorted(self._verdicts.items())
            ]
        control = self.control_totals()
        return {
            "verdicts": verdicts,
            "verdictsPerSec": self._rate.rate(),
            "fusedFramesTotal": self.fused_frames_total,
            "compilesTotal": self.compiles_total,
            "compilesAfterWarmupTotal": self.compiles_after_warmup_total,
            "verdictHostReadsTotal": self.verdict_host_reads_total,
            "verdictCopyReadyTotal": self.verdict_copy_ready_total,
            "replyFirstTotal": self.reply_first_total,
            "prepNativeTotal": self.prep_native_total,
            "paramPrepNativeTotal": self.param_prep_native_total,
            "concurrentPrepNativeTotal": self.concurrent_prep_native_total,
            "accountFoldsTotal": self.account_folds_total,
            "controlWakeupsTotal": control["control_wakeups_total"],
            "controlIdleWakeupsTotal": control["control_idle_wakeups_total"],
            "shedTotal": self.shed_total,
            "shedByReason": self.shed_totals(),
            "hostCopyBytesTotal": self.host_copy_bytes_total,
            "overlapSavedMsTotal": round(self.overlap_saved_ms_total, 3),
            "intakeShards": {
                str(k): v for k, v in sorted(self.shard_totals().items())
            },
            "sketch": self.sketch_stats(),
            "shm": self.shm_stats(),
            "lease": self.lease_stats(),
            "hier": self.hier_stats(),
            "outcome": self.outcome_stats(),
            "breaker": {
                **self.breaker_stats(),
                "transitions": [
                    {"from": f, "to": t, "count": c}
                    for (f, t), c in sorted(
                        self.breaker_transition_totals().items()
                    )
                ],
            },
            "push": {
                **self.push_stats(),
                "frames": self.push_frame_totals(),
                "revocations": self.push_revocations_total,
                "stalenessMs": self.push_staleness_ms.snapshot(),
            },
            "stages": {
                "queue_wait_ms": self.queue_wait_ms.snapshot(),
                "decide_ms": self.decide_ms.snapshot(),
                "write_ms": self.write_ms.snapshot(),
                "batch_size": self.batch_size.snapshot(),
                "intake_ms": self.intake_ms.snapshot(),
                "dispatch_ms": self.dispatch_ms.snapshot(),
                "fused_depth": self.fused_depth.snapshot(),
                "wait_assigned_ms": self._wait_assigned_ms.snapshot(),
                **{name: getattr(self, name).snapshot()
                   for name, _help in self._PHASES + self._DOOR_SPANS},
            },
            "waitAssignedTotal": self.wait_assigned_total,
            "gauges": self._gauge_values(),
        }

    def stage_snapshot(self) -> Dict[str, dict]:
        """Trimmed per-stage view for bench artifacts: p50/p99/count/sum;
        of ``door_residence_ms`` also the bounds (``le``), the cumulative
        bucket counts (``cum``, the last the whole count) and ``max``, so
        that two snapshots differ into the window's own quantiles."""
        self._fold_door_spans()
        self._fold_pending()
        out = {}
        for name, hist in (
            ("queue_wait_ms", self.queue_wait_ms),
            ("decide_ms", self.decide_ms),
            ("write_ms", self.write_ms),
            ("batch_size", self.batch_size),
            ("intake_ms", self.intake_ms),
            ("dispatch_ms", self.dispatch_ms),
            ("fused_depth", self.fused_depth),
            ("wait_assigned_ms", self._wait_assigned_ms),
            *((name, getattr(self, name))
              for name, _help in self._PHASES + self._DOOR_SPANS),
        ):
            snap = hist.snapshot()
            out[name] = {
                "p50": snap["p50"], "p99": snap["p99"],
                "count": snap["count"],
                # per-lane busy time over the snapshot window — the serve
                # bench derives lane occupancy from sum/wall
                "sum": round(snap["sum"], 3),
            }
        le, cum, vmax = self.door_residence_ms.cumulative()
        out["door_residence_ms"].update(
            le=list(le), cum=list(cum), max=vmax
        )
        out["fused_frames_total"] = self.fused_frames_total
        out["compiles_total"] = self.compiles_total
        out["compiles_after_warmup_total"] = self.compiles_after_warmup_total
        out["verdict_host_reads_total"] = self.verdict_host_reads_total
        out["verdict_copy_ready_total"] = self.verdict_copy_ready_total
        out.update(self.param_totals())
        out.update(self.param_single_totals())
        out.update(self.arm_totals())
        out.update(self.concurrent_totals())
        out.update(self.lane_turn_totals())
        out.update(self.control_totals())
        out["reply_first_total"] = self.reply_first_total
        out["prep_native_total"] = self.prep_native_total
        out["param_prep_native_total"] = self.param_prep_native_total
        out["concurrent_prep_native_total"] = (
            self.concurrent_prep_native_total)
        out["account_folds_total"] = self.account_folds_total
        out["param_impl"], out["param_impl_reason"] = self.param_impl
        out["shed_total"] = self.shed_totals()
        out["host_copy_bytes_total"] = self.host_copy_bytes_total
        out["overlap_saved_ms_total"] = round(self.overlap_saved_ms_total, 3)
        out["intake_shards"] = {
            str(k): v for k, v in sorted(self.shard_totals().items())
        }
        return out

    def render(self) -> str:
        """``sentinel_server_*`` Prometheus exposition (no trailing
        newline; the exporter joins sections)."""
        self._fold_door_spans()
        self._fold_pending()
        lines = [
            "# HELP sentinel_server_verdicts_total Cluster token verdicts "
            "by class and namespace (cumulative).",
            "# TYPE sentinel_server_verdicts_total counter",
        ]
        with self._verdict_lock:
            items = sorted(self._verdicts.items())
        if items:
            for (verdict, ns), count in items:
                lines.append(
                    "sentinel_server_verdicts_total"
                    f'{{verdict="{_escape(verdict)}",'
                    f'namespace="{_escape(ns)}"}} {count}'
                )
        else:
            # zero-sample so the series exists on an idle server and rate()
            # queries don't gap at startup
            lines.append(
                'sentinel_server_verdicts_total{verdict="pass",'
                'namespace="default"} 0'
            )
        lines.append(
            "# HELP sentinel_server_verdicts_per_sec Verdicts per second "
            "(8s window)."
        )
        lines.append("# TYPE sentinel_server_verdicts_per_sec gauge")
        lines.append(f"sentinel_server_verdicts_per_sec {self._rate.rate():g}")
        lines.append(
            "# HELP sentinel_server_fused_frames_total Engine-batch frames "
            "folded into chained multi-frame device dispatches (cumulative)."
        )
        lines.append("# TYPE sentinel_server_fused_frames_total counter")
        lines.append(
            f"sentinel_server_fused_frames_total {self.fused_frames_total}"
        )
        lines.append(
            "# HELP sentinel_server_shed_total Requests refused (OVERLOAD) "
            "or dropped by the server, by reason (cumulative)."
        )
        lines.append("# TYPE sentinel_server_shed_total counter")
        shed = self.shed_totals()
        if shed:
            for reason, count in sorted(shed.items()):
                lines.append(
                    "sentinel_server_shed_total"
                    f'{{reason="{_escape(reason)}"}} {count}'
                )
        else:
            # zero-sample so the series exists before the first shed and
            # rate() queries don't gap when overload begins
            lines.append('sentinel_server_shed_total{reason="queue_full"} 0')
        lines.append(
            "# HELP sentinel_server_host_copy_bytes_total Host bytes "
            "copied on the serving path (arena staging + fusion concat)."
        )
        lines.append("# TYPE sentinel_server_host_copy_bytes_total counter")
        lines.append(
            f"sentinel_server_host_copy_bytes_total "
            f"{self.host_copy_bytes_total}"
        )
        lines.append(
            "# HELP sentinel_server_overlap_saved_ms_total Host prep/"
            "dispatch time spent while an earlier fused group was still "
            "computing on device — serialized time a depth-1 device lane "
            "would have added (ms, cumulative)."
        )
        lines.append("# TYPE sentinel_server_overlap_saved_ms_total counter")
        lines.append(
            "sentinel_server_overlap_saved_ms_total "
            f"{self.overlap_saved_ms_total:g}"
        )
        shards = self.shard_totals()
        if shards:
            for mname, skey, help_text in (
                ("shard_pulls_total", "pulls",
                 "Intake pulls handed to the device lane, per shard."),
                ("shard_requests_total", "requests",
                 "Requests pulled through each intake shard."),
                ("shard_intake_busy_ms_total", "busy_ms",
                 "Cumulative intake-lane busy time per shard (ms); "
                 "rate()/1000 is the shard's occupancy."),
            ):
                lines.append(f"# HELP sentinel_server_{mname} {help_text}")
                lines.append(f"# TYPE sentinel_server_{mname} counter")
                for shard, vals in sorted(shards.items()):
                    lines.append(
                        f'sentinel_server_{mname}{{shard="{shard}"}} '
                        f"{vals[skey]:g}"
                    )
        sketch = self.sketch_stats()
        lines.append(
            "# HELP sentinel_sketch_merges_total SALSA counter-pair merges "
            "in the param sketch, by rule slot (cumulative)."
        )
        lines.append("# TYPE sentinel_sketch_merges_total counter")
        by_slot = sketch.get("mergesBySlot") or {}
        if by_slot:
            for slot, count in sorted(
                (int(s), int(c)) for s, c in by_slot.items()
            ):
                lines.append(
                    f'sentinel_sketch_merges_total{{slot="{slot}"}} {count}'
                )
        else:
            # zero-sample so the series exists before the first merge (or on
            # the cms variant, which never merges)
            lines.append('sentinel_sketch_merges_total{slot="0"} 0')
        for mname, skey, help_text in (
            ("sentinel_sketch_slim_bytes_total", "slimBytes",
             "HBM bytes held by the SF slim twin of the param sketch "
             "(what per-tick replication deltas ship)."),
            ("sentinel_sketch_fat_bytes_total", "fatBytes",
             "HBM bytes held by the fat (update) param sketch."),
        ):
            lines.append(f"# HELP {mname} {help_text}")
            lines.append(f"# TYPE {mname} gauge")
            lines.append(f"{mname} {int(sketch.get(skey, 0) or 0)}")
        shm = self.shm_stats()
        for mname, skey, help_text in (
            ("shm_polls_total", "polls",
             "Shm ring poller wake-to-idle cycles (spin or futex) "
             "(cumulative)."),
            ("shm_doorbells_total", "doorbells",
             "Futex doorbell rings by co-located shm clients — each one is "
             "a syscall the steady state avoided elsewhere (cumulative)."),
            ("shm_ring_full_total", "ring_full",
             "Response-ring pushes dropped after the bounded wait because "
             "the client stopped draining (cumulative)."),
        ):
            lines.append(f"# HELP sentinel_server_{mname} {help_text}")
            lines.append(f"# TYPE sentinel_server_{mname} counter")
            lines.append(
                f"sentinel_server_{mname} {int(shm.get(skey, 0) or 0)}"
            )
        lease = self.lease_stats()
        for mname, skey, help_text in (
            ("sentinel_lease_granted_total", "granted",
             "Wire-rev-5 leases granted: short-TTL client-local admission "
             "slices charged to the LEASED window column (cumulative)."),
            ("sentinel_lease_renewed_total", "renewed",
             "Lease renewals: unused tokens credited, fresh slice granted "
             "(cumulative)."),
            ("sentinel_lease_returned_total", "returned",
             "Leases returned early by clients (cumulative)."),
            ("sentinel_lease_revoked_total", "revoked",
             "Leases ended server-side: TTL expiry, rule-reload drop, or "
             "MOVE recall (cumulative)."),
        ):
            lines.append(f"# HELP {mname} {help_text}")
            lines.append(f"# TYPE {mname} counter")
            lines.append(f"{mname} {int(lease.get(skey, 0) or 0)}")
        for mname, skey, help_text in (
            ("sentinel_lease_outstanding", "outstanding",
             "Live (unexpired, unreturned) leases right now."),
            ("sentinel_lease_outstanding_tokens", "outstanding_tokens",
             "Tokens currently delegated on live leases — the bound on "
             "crash over-admission."),
        ):
            lines.append(f"# HELP {mname} {help_text}")
            lines.append(f"# TYPE {mname} gauge")
            lines.append(f"{mname} {int(lease.get(skey, 0) or 0)}")
        hier = self.hier_stats()
        if hier:
            for mname, skey, help_text in (
                ("sentinel_hier_share_grants_total", "share_grants",
                 "Global-budget shares granted/regranted to pods by the "
                 "coordinator (cumulative)."),
                ("sentinel_hier_reconciles_total", "reconciles",
                 "Coordinator reconciliation passes: water-fill share "
                 "targets over reported demand (cumulative)."),
                ("sentinel_hier_demand_reports_total", "demand_reports",
                 "Per-tick pod demand reports received by the coordinator "
                 "(cumulative)."),
            ):
                lines.append(f"# HELP {mname} {help_text}")
                lines.append(f"# TYPE {mname} counter")
                lines.append(f"{mname} {int(hier.get(skey, 0) or 0)}")
            shares = hier.get("share_tokens") or {}
            if isinstance(shares, dict):
                lines.append(
                    "# HELP sentinel_hier_share_tokens Tokens of the global "
                    "budget currently provisioned to pod shares, per flow "
                    "(coordinator view when co-located, else this pod's own "
                    "share)."
                )
                lines.append("# TYPE sentinel_hier_share_tokens gauge")
                for fid in sorted(shares, key=str):
                    lines.append(
                        f'sentinel_hier_share_tokens{{flow="{fid}"}} '
                        f"{int(shares[fid] or 0)}"
                    )
        outcome = self.outcome_stats()
        for mname, skey, help_text in (
            ("sentinel_outcome_reported_total", "reported",
             "Completion outcomes accepted into the device outcome columns "
             "(OUTCOME_REPORT rows past validation) (cumulative)."),
            ("sentinel_outcome_exceptions_total", "exceptions",
             "Accepted completion outcomes flagged as exceptions "
             "(cumulative)."),
            ("sentinel_outcome_batches_total", "batches",
             "OUTCOME_REPORT batches ingested (cumulative)."),
            ("sentinel_outcome_rt_sum_ms_total", "rt_sum_ms",
             "Sum of accepted reported response times (ms, cumulative) — "
             "divide rates for the fleet RT average."),
        ):
            lines.append(f"# HELP {mname} {help_text}")
            lines.append(f"# TYPE {mname} counter")
            lines.append(f"{mname} {int(outcome.get(skey, 0) or 0)}")
        lines.append(
            "# HELP sentinel_outcome_dropped_total Reported outcomes "
            "rejected at the wire boundary, by reason (negative / "
            "non_finite / too_large / unknown_flow) (cumulative)."
        )
        lines.append("# TYPE sentinel_outcome_dropped_total counter")
        dropped = outcome.get("dropped") or {}
        if dropped:
            for reason, count in sorted(dropped.items()):
                lines.append(
                    "sentinel_outcome_dropped_total"
                    f'{{reason="{_escape(str(reason))}"}} {int(count)}'
                )
        else:
            # zero-sample so the series exists before the first bad report
            lines.append(
                'sentinel_outcome_dropped_total{reason="negative"} 0'
            )
        flows = outcome.get("flows") or {}
        if flows:
            for mname, fkey, help_text in (
                ("sentinel_flow_complete_qps", "complete_qps",
                 "Windowed reported completions per second, per flow "
                 "(device outcome columns)."),
                ("sentinel_flow_exception_qps", "exception_qps",
                 "Windowed reported exceptions per second, per flow."),
                ("sentinel_flow_rt_avg_ms", "rt_avg_ms",
                 "Windowed average reported RT per flow (ms)."),
                ("sentinel_flow_rt_p99_ms", "rt_p99_ms",
                 "Windowed p99 reported RT per flow (ms), from the "
                 "device-side log2 RT histogram (bucket upper edge)."),
            ):
                lines.append(f"# HELP {mname} {help_text}")
                lines.append(f"# TYPE {mname} gauge")
                for fid in sorted(flows, key=int):
                    vals = flows[fid] or {}
                    lines.append(
                        f'{mname}{{flow_id="{int(fid)}"}} '
                        f"{float(vals.get(fkey, 0.0) or 0.0):g}"
                    )
        lines.append(
            "# HELP sentinel_breaker_transitions_total Circuit-breaker "
            "state transitions observed by the host mirror, by edge "
            "(cumulative)."
        )
        lines.append("# TYPE sentinel_breaker_transitions_total counter")
        transitions = self.breaker_transition_totals()
        if transitions:
            for (frm, to), count in sorted(transitions.items()):
                lines.append(
                    "sentinel_breaker_transitions_total"
                    f'{{from="{_escape(frm)}",to="{_escape(to)}"}} {count}'
                )
        else:
            # zero-sample so the series exists before the first trip
            lines.append(
                'sentinel_breaker_transitions_total'
                '{from="closed",to="open"} 0'
            )
        lines.append(
            "# HELP sentinel_push_frames_total Wire-rev-7 push frames "
            "handed to connection sinks, by type (cumulative)."
        )
        lines.append("# TYPE sentinel_push_frames_total counter")
        push_frames = self.push_frame_totals()
        if push_frames:
            for tname, count in sorted(push_frames.items()):
                lines.append(
                    "sentinel_push_frames_total"
                    f'{{type="{_escape(tname)}"}} {count}'
                )
        else:
            # zero-sample so the series exists before the first push
            lines.append('sentinel_push_frames_total{type="lease_revoke"} 0')
        lines.append(
            "# HELP sentinel_push_revocations_total Leases recalled through "
            "pushed LEASE_REVOKE frames (cumulative)."
        )
        lines.append("# TYPE sentinel_push_revocations_total counter")
        lines.append(
            f"sentinel_push_revocations_total {self.push_revocations_total}"
        )
        lines.append(self.push_staleness_ms.render_prometheus(
            "sentinel_push_staleness_ms",
            "Server-emit to client-apply staleness of rev-7 push frames "
            "(ms), recorded by co-located client applies off the frame's "
            "emit stamp.",
        ))
        breaker = self.breaker_stats()
        br_flows = breaker.get("flows") or {}
        if br_flows:
            lines.append(
                "# HELP sentinel_breaker_state Circuit-breaker state per "
                "flow (0 = closed, 1 = open, 2 = half_open), read from the "
                "device BreakerState columns."
            )
            lines.append("# TYPE sentinel_breaker_state gauge")
            for fid in sorted(br_flows, key=int):
                vals = br_flows[fid] or {}
                lines.append(
                    f'sentinel_breaker_state{{flow_id="{int(fid)}"}} '
                    f"{int(vals.get('state_code', 0) or 0)}"
                )
        gauges = self._gauge_values()
        for name, help_text in (
            ("queue_depth", "Requests queued awaiting a device step."),
            ("inflight_batches", "Batches currently in the device pipeline."),
            ("connections", "Open client connections."),
            ("dispatch_lane_depth",
             "Decoded pulls queued between the intake and device lanes."),
            ("reply_lane_depth",
             "Dispatched batches queued between the device and reply lanes."),
            ("shm_ring_occupancy",
             "Fraction of shm request-ring slots occupied across attached "
             "segments (sampled; 0 when no shm door is serving)."),
            ("device_inflight",
             "Fused groups dispatched to the device and not yet "
             "materialized (bounded by max_device_inflight)."),
        ):
            lines.append(f"# HELP sentinel_server_{name} {help_text}")
            lines.append(f"# TYPE sentinel_server_{name} gauge")
            lines.append(f"sentinel_server_{name} {gauges[name]:g}")
        for name, help_text, hist in (
            ("sentinel_server_queue_wait_ms",
             "Enqueue-to-batch-drain wait per queue item (asyncio door); "
             "on the native lane per pull, intake hand-over to the start of "
             "the dispatch that took it (ms).",
             self.queue_wait_ms),
            ("sentinel_server_decide_ms",
             "Materialize per batch: wait for the device, unpack, unsort. "
             "The native reply lane stops the clock there (account_ms runs "
             "after the reply); the asyncio door's holds account_ms too (ms).",
             self.decide_ms),
            ("sentinel_server_write_ms",
             "Host write-out per batch: verdict encode + socket write (ms).",
             self.write_ms),
            ("sentinel_server_batch_size",
             "Requests per device batch.",
             self.batch_size),
            ("sentinel_server_intake_ms",
             "Intake lane: front-door pull to handoff enqueue (ms).",
             self.intake_ms),
            ("sentinel_server_dispatch_ms",
             "Device lane: handoff drain to device dispatch issued (ms).",
             self.dispatch_ms),
            ("sentinel_server_fused_depth",
             "Engine-batch frames per fused device dispatch.",
             self.fused_depth),
            ("sentinel_server_wait_assigned_ms",
             "Wait assigned per SHOULD_WAIT verdict: paced admission or "
             "priority occupy delay (ms).",
             self._wait_assigned_ms),
            *((f"sentinel_server_{name}", help_text, getattr(self, name))
              for name, help_text in self._PHASES + self._DOOR_SPANS),
        ):
            lines.append(hist.render_prometheus(name, help_text))
        for name, help_text, value in (
            ("compiles_total",
             "Backend compiles in this process, persistent-cache hits "
             "included (cumulative).", self.compiles_total),
            ("compiles_after_warmup_total",
             "Backend compiles that ended after the token service's "
             "warmup() returned: a step compiled while serving "
             "(cumulative).", self.compiles_after_warmup_total),
            ("verdict_host_reads_total",
             "Blocking device-to-host reads made by materializers: one per "
             "dispatch (cumulative).", self.verdict_host_reads_total),
            ("verdict_copy_ready_total",
             "Materializations whose verdict buffer was ready on entry: the "
             "device had finished before the reply lane asked "
             "(cumulative).", self.verdict_copy_ready_total),
            *((name, self._PARAM_COUNTERS[name], value)
              for name, value in self.param_totals().items()),
            *((name, self._PARAM_SINGLE_COUNTERS[name], value)
              for name, value in self.param_single_totals().items()),
            *((name, self._ARM_COUNTERS[name], value)
              for name, value in self.arm_totals().items()),
            *((name, self._CONCURRENT_COUNTERS[name], value)
              for name, value in self.concurrent_totals().items()
              if name in self._CONCURRENT_COUNTERS),
            *((name, self._LANE_TURN_COUNTERS[name], value)
              for name, value in self.lane_turn_totals().items()),
            *((name, self._CONTROL_COUNTERS[name], value)
              for name, value in self.control_totals().items()),
            ("reply_first_total",
             "Dispatches accounted after their reply was submitted: the "
             "native reply lane answers first and counts after "
             "(cumulative).", self.reply_first_total),
            ("prep_native_total",
             "Flow dispatches whose host prep was the native pass; beside "
             "prep_ms's count a shortfall says the library is not built "
             "and numpy prepped them (cumulative).",
             self.prep_native_total),
            ("param_prep_native_total",
             "Hot-parameter dispatches whose host prep was the native pass; "
             "beside param_dispatch_total, as prep_native_total "
             "(cumulative).", self.param_prep_native_total),
            ("concurrent_prep_native_total",
             "Concurrency dispatches whose host prep was the native pass; "
             "beside concurrent_dispatch_total, as prep_native_total "
             "(cumulative).", self.concurrent_prep_native_total),
            ("account_folds_total",
             "Per-namespace fan-outs of the verdict accounting: dispatches "
             "are counted at deposit and folded into the verdict counters, "
             "the SLO plane and the timeline once a wall second and before "
             "every read; beside account_ms's count, the dispatches one "
             "fold stood for (cumulative).", self.account_folds_total),
        ):
            lines.append(f"# HELP sentinel_server_{name} {help_text}")
            lines.append(f"# TYPE sentinel_server_{name} counter")
            lines.append(f"sentinel_server_{name} {value}")
        lines.append("# HELP sentinel_server_concurrent_tokens_live "
                     "Concurrency tokens held, as the last step left them.")
        lines.append("# TYPE sentinel_server_concurrent_tokens_live gauge")
        lines.append("sentinel_server_concurrent_tokens_live "
                     f"{self.concurrent_totals()['concurrent_tokens_live']}")
        kernel, reason = self.param_impl
        if kernel:
            reason = reason.replace("\\", "/").replace('"', "'").replace(
                "\n", " ")
            lines.append(
                "# HELP sentinel_server_param_impl_info The kernel "
                "ParamConfig.impl resolved to for the serving geometry, and "
                "why (constant 1)."
            )
            lines.append("# TYPE sentinel_server_param_impl_info gauge")
            lines.append(
                f'sentinel_server_param_impl_info{{impl="{kernel}",'
                f'reason="{reason}"}} 1'
            )
        lines.append(
            "# HELP sentinel_server_wait_assigned_total SHOULD_WAIT "
            "verdicts that carried a positive wait hint (cumulative)."
        )
        lines.append("# TYPE sentinel_server_wait_assigned_total counter")
        lines.append(
            f"sentinel_server_wait_assigned_total {self.wait_assigned_total}"
        )
        return "\n".join(lines)

    def reset(self) -> None:
        """Zero counters and histograms in place (gauge readers stay —
        their owners' lifecycles manage them). Benches call this between
        load points; tests via :func:`reset_server_metrics_for_tests`."""
        self.queue_wait_ms.reset()
        self.decide_ms.reset()
        self.write_ms.reset()
        self.batch_size.reset()
        self.intake_ms.reset()
        self.dispatch_ms.reset()
        self.fused_depth.reset()
        for name, _help in self._PHASES:
            getattr(self, name).reset()
        # what the live doors counted so far goes with the reset: fold it
        # in first, so that only what they count from here on comes back
        self._fold_door_spans()
        for name, _help in self._DOOR_SPANS:
            getattr(self, name).reset()
        with self._compile_lock:
            self._compiles = 0
            self._compiles_after_warmup = 0
        with self._fused_lock:
            self._fused_frames = 0
        with self._verdict_read_lock:
            self._verdict_host_reads = 0
            self._verdict_copy_ready = 0
            self._reply_first = 0
            self._prep_native = [0, 0, 0]
        with self._param_lock:
            self._param = dict.fromkeys(self._PARAM_COUNTERS, 0)
            self._param_single = dict.fromkeys(
                self._PARAM_SINGLE_COUNTERS, 0)
        with self._arm_lock:
            self._arms = dict.fromkeys(self._ARM_COUNTERS, 0)
        with self._concurrent_lock:
            self._concurrent = dict.fromkeys(self._CONCURRENT_COUNTERS, 0)
            self._concurrent_live = 0
        with self._lane_turn_lock:
            self._lane_turn = dict.fromkeys(self._LANE_TURN_COUNTERS, 0)
        with self._control_lock:
            self._control = dict.fromkeys(self._CONTROL_COUNTERS, 0)
        with self._fold_lock, self._verdict_lock:
            self._pending = None  # what nobody has read goes unread
            self._verdicts.clear()
            self._wait_assigned = 0
            self._account_folds = 0
        self._wait_assigned_ms.reset()
        with self._shed_lock:
            self._shed.clear()
        with self._shard_lock:
            self._shards.clear()
        with self._copy_lock:
            self._copy_bytes = 0
        with self._sketch_lock:
            self._sketch_provider = None
        with self._shm_lock:
            self._shm_provider = None
        with self._lease_lock:
            self._lease_provider = None
        with self._hier_lock:
            self._hier_provider = None
        with self._outcome_lock:
            self._outcome_provider = None
        with self._breaker_lock:
            self._breaker_provider = None
            self._breaker_transitions.clear()
        with self._push_lock:
            self._push_provider = None
            self._push_frames.clear()
            self._push_revocations = 0
        self.push_staleness_ms.reset()
        self._rate.reset()


_SINGLETON = ServerMetrics()


def fold_pending_accounts() -> None:
    """The SLO plane's and the timeline's readers call this first: the
    process-wide registry hands them what it counted at deposit. (Another
    ``ServerMetrics`` folds on its own reads and deposits.)"""
    _SINGLETON._fold_pending()


def server_metrics() -> ServerMetrics:
    """The process-wide server metrics registry."""
    return _SINGLETON


def reset_server_metrics_for_tests() -> None:
    _SINGLETON.reset()
    # the SLO plane, metric timeline, and flight-recorder rings are fed off
    # this registry's paths; a test that resets one expects all to start clean
    from sentinel_tpu.metrics.timeline import reset_timeline_for_tests
    from sentinel_tpu.trace import ring as _trace_ring
    from sentinel_tpu.trace.slo import reset_slo_plane_for_tests

    reset_slo_plane_for_tests()
    reset_timeline_for_tests()
    _trace_ring.reset_for_tests()
