"""Million-user scenario harness: multi-tenant, phased, chaos-laced, gated.

Every other bench measures one thing at peak (closed-loop ceiling, RPC
amortization, failover time). Production is none of those: it is many
tenants with skewed keys, ramps and flash crowds, one tenant misbehaving,
and faults landing mid-load. This harness makes that realism a first-class,
continuously-gated observable (ROADMAP item 5):

- the **workload model** (``benchmarks/workload.py``) is seeded and phased:
  Zipf-skewed tenants with guaranteed shares drive ramp / spike /
  flashcrowd / diurnal schedules as open-loop senders (absolute schedule —
  a slow server cannot slow the offered load down);
- **chaos phases** arm the ``sentinel_tpu.chaos`` registry mid-run
  (lane_delay, device_stall, conn_reset...) with a fixed seed;
- the server runs the real stack: the tcp front door (asyncio, or the
  native epoll door with SO_REUSEPORT intake shards and optional shm ring
  when built), the BBR brownout ladder with **per-namespace weighted
  shedding** (tenant shares installed on the admission controller), the
  wire-rev-5 lease path (one tenant drives ``TokenClient`` with leases),
  and optionally a warm standby receiving per-tick replication deltas;
- gates read the same surfaces operators do: per-tenant p99 **burn** via
  ``trace/slo.py merge_fleet``, **fairness** (no tenant served below its
  guaranteed share while shedding) and **flood attribution** from the
  per-namespace metric timeline (``metrics/timeline.py`` — also the
  ``cluster/server/metric`` command's backend, and the harness verifies
  that command's series reconcile exactly with the
  ``sentinel_server_verdicts_total`` deltas), **bounded over-admission**
  on metered flows (threshold × windows + outstanding lease tokens), and
  **zero unrecoverable client errors**.

Artifacts: ``benchmarks/results/scenario-<ts>.json`` (full per-phase,
per-tenant, per-second series + gate verdicts) and a ``SCENARIO_r0N.json``
round summary at the repo root — the realism trajectory next to the
``BENCH_r0N`` peak-rate trajectory. ``--smoke`` is the CI profile: 2
tenants, ramp + spike + one chaos phase, tcp door, fixed seed, ~15 s.

    JAX_PLATFORMS=cpu python benchmarks/scenario_bench.py --smoke

See docs/SCENARIOS.md for the phase grammar, gate definitions, and how to
read an artifact.
"""

from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import jax  # noqa: E402  (import first so the platform pin lands early)

jax.config.update("jax_platforms", "cpu")

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import socket  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

from benchmarks.workload import (  # noqa: E402
    Phase,
    TenantSpec,
    WorkloadModel,
    degraded_dependency_tenant,
    error_storm_profile,
    slow_dependency_profile,
)

# OutcomeProfile factories the degraded-tenant drivers can name in
# TenantSpec.outcome_profile (the driver reports its admitted rows'
# completions back over the wire, sampled from this profile)
_OUTCOME_PROFILES = {
    "error-storm": error_storm_profile,
    "slow-dependency": slow_dependency_profile,
}

SCHEMA = "sentinel-scenario/1"
RESULTS_DIR = os.path.join(_REPO, "benchmarks", "results")

# TokenStatus codes the drivers tally (mirrors metrics/server.VERDICT_NAMES)
_OK, _BLOCKED, _TOO_MANY, _OVERLOAD, _DEGRADED = 0, 1, 4, 8, 12


# -- configuration ------------------------------------------------------------
@dataclass
class ScenarioConfig:
    name: str
    model: WorkloadModel
    door: str = "tcp"  # tcp | native (native falls back to tcp if unbuilt)
    objective_ms: float = 150.0  # p99 objective for this run (CPU loopback)
    # per-tenant burn-rate gates over the trailing 1m window; the flooding
    # tenant's gate is 100 (the scale's maximum: its sheds are its own
    # burn — its SLO contract during a self-inflicted flood)
    burn_gates: Dict[str, float] = field(default_factory=dict)
    flood_tenant: Optional[str] = None
    # the metered flow per tenant: its hottest flow (first_flow) gets a
    # finite threshold of metered_frac × base_rate — the over-admission
    # gate's subject
    metered_frac: float = 0.35
    over_admission_slack: float = 0.25
    fairness_tolerance: float = 0.25
    lease_tenant: Optional[str] = None
    lease_want: int = 256
    # the tenant whose metered flow sits behind a circuit breaker (see
    # degraded_config): the degrade-attribution gate must name it from
    # the verdict stream, and the breaker must trip AND recover in-run
    degraded_tenant: Optional[str] = None
    replica: bool = False
    # overload ladder knobs for the run (aggressive vs the conservative
    # production defaults, so a CPU-scale flood actually engages SHED_LOW)
    min_bdp: float = 8.0
    headroom_shed: float = 1.5
    headroom_degrade: float = 512.0  # effectively: never DEGRADE here
    sustain_ms: float = 100.0
    max_queue: int = 512  # frames per loop before queue_full refusals
    window_frames: int = 256  # per-driver in-flight frame cap
    enforce_gates: bool = True
    out_dir: str = RESULTS_DIR
    publish_round: bool = True


def smoke_config(seed: int = 20260805) -> ScenarioConfig:
    """The CI profile: 2 tenants, ramp + spike + one chaos phase, tcp."""
    tenants = [
        TenantSpec("tenant-0", 0, 64, share=0.35, base_rate=2400.0,
                   zipf_alpha=1.1, batch=24),
        TenantSpec("tenant-1", 64, 64, share=0.35, base_rate=2400.0,
                   zipf_alpha=1.1, batch=24),
    ]
    phases = [
        Phase("warmup", 2.0, "steady", measured=False),
        Phase("ramp", 4.0, "ramp", magnitude=2.0),
        Phase("spike", 5.0, "spike", magnitude=8.0,
              shape_tenants=["tenant-0"]),
        Phase("chaos", 4.0, "steady",
              chaos="lane_delay:p=0.2,ms=2;device_stall:p=0.1,ms=2"),
    ]
    model = WorkloadModel(tenants=tenants, phases=phases, seed=seed)
    return ScenarioConfig(
        name="smoke", model=model, flood_tenant="tenant-0",
        burn_gates={"tenant-0": 100.0, "tenant-1": 60.0},
        lease_tenant=None, replica=False,
    )


def full_config(seed: int = 20260805) -> ScenarioConfig:
    """The local acceptance profile: 5 tenants (4 open-loop + 1 lease),
    ramp + flashcrowd flood + chaos + diurnal, replication on."""
    tenants = [
        TenantSpec("tenant-0", 0, 96, share=0.22, base_rate=3600.0,
                   zipf_alpha=1.1, batch=48),
        TenantSpec("tenant-1", 96, 96, share=0.22, base_rate=3600.0,
                   zipf_alpha=1.05, batch=48),
        TenantSpec("tenant-2", 192, 96, share=0.22, base_rate=3600.0,
                   zipf_alpha=1.2, batch=48),
        TenantSpec("tenant-3", 288, 96, share=0.22, base_rate=3600.0,
                   zipf_alpha=1.1, batch=48, prioritized=True),
        # the lease tenant admits hot flows client-locally (wire rev 5);
        # it is excluded from the server-side fairness math (its local
        # admits are invisible to the door by design)
        TenantSpec("tenant-lease", 384, 32, share=0.0, base_rate=400.0,
                   zipf_alpha=1.3, batch=1),
    ]
    phases = [
        Phase("warmup", 2.0, "steady", measured=False),
        Phase("ramp", 5.0, "ramp", magnitude=2.0),
        # the flood lands WITH a device fault — a flash crowd arriving
        # while the accelerator is degraded is the overload story this
        # harness exists to gate (the stall is answer-preserving, so the
        # zero-client-error gate still holds)
        Phase("flashcrowd", 6.0, "flashcrowd", magnitude=12.0,
              shape_tenants=["tenant-0"],
              chaos="device_stall:p=0.6,ms=6"),
        Phase("chaos", 5.0, "steady",
              chaos="lane_delay:p=0.2,ms=2;device_stall:p=0.15,ms=3"),
        Phase("diurnal", 6.0, "diurnal", magnitude=2.5),
    ]
    model = WorkloadModel(tenants=tenants, phases=phases, seed=seed)
    return ScenarioConfig(
        name="full", model=model, flood_tenant="tenant-0",
        burn_gates={"tenant-0": 100.0, "tenant-1": 60.0, "tenant-2": 60.0,
                    "tenant-3": 60.0, "tenant-lease": 100.0},
        lease_tenant="tenant-lease", replica=True,
    )


def degraded_config(seed: int = 20260807) -> ScenarioConfig:
    """The circuit-breaker profile: one healthy tenant plus one tenant
    whose metered flow guards a flaky dependency (error-storm outcome
    profile). Phase timing is matched to the profile's storm window
    (the middle third of the 12 s run = the ``storm`` phase exactly), so
    the breaker trips OPEN mid-run, and the ``recovery-probe`` phase —
    deliberately laced with ``conn_reset`` + ``device_stall`` chaos —
    must still elect HALF_OPEN probes and re-close the breaker. Gates:
    the degrade-attribution gate names the degraded tenant from the
    verdict stream alone, and the transition counters must show the
    full trip AND the in-chaos recovery."""
    tenants = [
        TenantSpec("tenant-0", 0, 64, share=0.35, base_rate=2000.0,
                   zipf_alpha=1.1, batch=24),
        degraded_dependency_tenant(
            "tenant-dep", 64, 64, share=0.35, base_rate=2000.0,
            strategy=1, threshold=0.25, min_requests=20,
            stat_ms=1000, recovery_ms=1500,
            outcome_profile="error-storm",
            zipf_alpha=1.1, batch=24,
        ),
    ]
    phases = [
        Phase("warmup", 2.0, "steady", measured=False),
        Phase("steady", 2.0, "steady"),
        # 4 s..8 s of the 12 s run = frac [1/3, 2/3): exactly the
        # error-storm profile's 40%-failure window
        Phase("storm", 4.0, "steady"),
        Phase("recovery-probe", 4.0, "steady",
              chaos="conn_reset:p=0.01;device_stall:p=0.1,ms=2"),
    ]
    model = WorkloadModel(tenants=tenants, phases=phases, seed=seed)
    return ScenarioConfig(
        name="degraded", model=model, flood_tenant=None,
        degraded_tenant="tenant-dep",
        # the degraded tenant's DEGRADED refusals are its own
        # dependency's burn — its gate is the scale's maximum
        burn_gates={"tenant-0": 60.0, "tenant-dep": 100.0},
        lease_tenant=None, replica=False,
    )


# -- tenant drivers -----------------------------------------------------------
class TenantDriver(threading.Thread):
    """Open-loop raw-wire driver for one tenant: frames on an ABSOLUTE
    schedule per phase (send time ``t0 + phase_off + sched[k]``, never
    "previous send + dt" — the coordinated-omission guard), a bounded
    in-flight window (a saturated server shows up as skipped sends, not
    client OOM), and a reader thread tallying verdicts per phase.
    ``conn_reset`` chaos is survivable: the driver reconnects and counts
    the reset, only an unrecoverable failure lands in ``errors``."""

    def __init__(self, tenant: TenantSpec, model: WorkloadModel,
                 port: int, t0: float, phase_offsets: List[float],
                 window_frames: int, metered_flow: int):
        super().__init__(name=f"driver-{tenant.name}", daemon=True)
        self.tenant = tenant
        self.model = model
        self.port = port
        self.t0 = t0
        self.phase_offsets = phase_offsets
        self.window_frames = window_frames
        self.metered_flow = metered_flow
        self.stats = [self._zero_stats() for _ in model.phases]
        self._lock = threading.Lock()
        # sender and reader both write the socket (requests vs piggy-backed
        # OUTCOME_REPORT frames) — the write lock keeps frames whole
        self._wlock = threading.Lock()
        self._inflight: Dict[int, tuple] = {}  # xid → (phase_idx, flow_ids)
        self._halt = threading.Event()
        self._sock: Optional[socket.socket] = None
        self._reader: Optional[threading.Thread] = None
        # degraded tenants close the outcome loop: every answered OK row's
        # completion is reported back over the wire (rev 6), sampled from
        # the tenant's OutcomeProfile at the run's normalized time — the
        # error storm these reports carry is what trips the breaker
        self._profile = (
            _OUTCOME_PROFILES[tenant.outcome_profile]()
            if getattr(tenant, "outcome_profile", None) else None
        )
        self._total_s = max(sum(ph.seconds for ph in model.phases), 1e-9)

    @staticmethod
    def _zero_stats() -> dict:
        return {
            "demand_rows": 0, "sent_rows": 0, "answered_rows": 0,
            "pass": 0, "block": 0, "overload": 0, "too_many": 0,
            "degraded": 0, "other": 0, "metered_pass": 0,
            "skipped_frames": 0, "lost_inflight": 0, "reconnects": 0,
            "reported_rows": 0, "errors": 0,
        }

    # -- socket lifecycle --------------------------------------------------
    def _connect(self) -> None:
        self._sock = socket.create_connection(
            ("127.0.0.1", self.port), timeout=10.0)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(0.5)

    def _reconnect(self, phase_idx: int) -> bool:
        with self._lock:
            lost = len(self._inflight)
            for _xid, (pi, ids) in self._inflight.items():
                self.stats[pi]["lost_inflight"] += len(ids)
            self._inflight.clear()
        self.stats[phase_idx]["reconnects"] += 1
        del lost
        for _ in range(5):
            try:
                self._connect()
                return True
            except OSError:
                time.sleep(0.05)
        self.stats[phase_idx]["errors"] += 1
        return False

    # -- reader ------------------------------------------------------------
    def _read_loop(self) -> None:
        from sentinel_tpu.cluster import protocol as P

        frames = P.FrameReader()
        while not self._halt.is_set():
            sock = self._sock
            if sock is None:
                time.sleep(0.01)
                continue
            try:
                data = sock.recv(65536)
            except socket.timeout:
                continue
            except OSError:
                time.sleep(0.01)  # sender handles the reconnect
                frames = P.FrameReader()
                continue
            if not data:
                time.sleep(0.01)
                frames = P.FrameReader()
                continue
            for payload in frames.feed(data):
                if P.peek_type(payload) != P.MsgType.BATCH_FLOW:
                    continue
                try:
                    xid, status, _rem, _wait = (
                        P.decode_batch_response(payload)
                    )
                except Exception:
                    continue
                with self._lock:
                    rec = self._inflight.pop(xid, None)
                if rec is None:
                    continue
                pi, ids = rec
                st = self.stats[pi]
                n = len(status)
                st["answered_rows"] += n
                st["pass"] += int((status == _OK).sum())
                st["block"] += int((status == _BLOCKED).sum())
                st["overload"] += int((status == _OVERLOAD).sum())
                st["too_many"] += int((status == _TOO_MANY).sum())
                st["degraded"] += int((status == _DEGRADED).sum())
                st["other"] += n - int(
                    np.isin(status,
                            (_OK, _BLOCKED, _OVERLOAD, _TOO_MANY,
                             _DEGRADED)).sum()
                )
                st["metered_pass"] += int(
                    ((status == _OK) & (ids == self.metered_flow)).sum()
                )
                if self._profile is not None:
                    ok_ids = ids[status == _OK]
                    if ok_ids.size:
                        # only admitted rows reach the dependency, so only
                        # they produce completions — a breaker that is OPEN
                        # starves its own stat window, exactly the real
                        # semantics
                        frac = (
                            (time.perf_counter() - self.t0) / self._total_s
                        )
                        rt, exc, _inv = self._profile.sample(
                            ok_ids.size,
                            self.model.seed ^ (xid & 0xFFFF), frac,
                        )
                        out = P.encode_outcome_report(
                            xid, ok_ids,
                            np.maximum(rt, 1.0).astype(np.int32),
                            exc.astype(np.uint8),
                        )
                        try:
                            with self._wlock:
                                sock.sendall(out)
                            st["reported_rows"] += int(ok_ids.size)
                        except OSError:
                            pass  # sender owns the reconnect

    # -- sender ------------------------------------------------------------
    def run(self) -> None:
        from sentinel_tpu.cluster import protocol as P

        try:
            self._connect()
        except OSError:
            self.stats[0]["errors"] += 1
            return
        self._reader = threading.Thread(
            target=self._read_loop, name=self.name + "-rx", daemon=True)
        self._reader.start()
        xid = (abs(hash(self.tenant.name)) % 1000) * 1_000_000
        batch = self.tenant.batch
        prios = (
            np.ones(batch, bool) if self.tenant.prioritized else None
        )
        for pi, phase in enumerate(self.model.phases):
            sched = self.model.send_schedule(phase, self.tenant)
            st = self.stats[pi]
            st["demand_rows"] = int(sched.size) * batch
            if sched.size == 0:
                continue
            stream = self.tenant.flow_stream(
                int(sched.size) * batch, self.model.seed + 7 * pi
            ).reshape(-1, batch)
            base = self.t0 + self.phase_offsets[pi]
            for k in range(sched.size):
                target = base + float(sched[k])
                now = time.perf_counter()
                if now < target:
                    time.sleep(target - now)
                with self._lock:
                    full = len(self._inflight) >= self.window_frames
                if full:
                    st["skipped_frames"] += 1
                    continue
                xid += 1
                ids = stream[k]
                frame = P.encode_batch_request(xid, ids, prios=prios)
                with self._lock:
                    self._inflight[xid] = (pi, ids)
                try:
                    with self._wlock:
                        self._sock.sendall(frame)
                    st["sent_rows"] += batch
                except OSError:
                    with self._lock:
                        self._inflight.pop(xid, None)
                    if not self._reconnect(pi):
                        self._halt.set()
                        return
        # drain grace: let in-flight answers land before teardown
        deadline = time.perf_counter() + 3.0
        while time.perf_counter() < deadline:
            with self._lock:
                if not self._inflight:
                    break
            time.sleep(0.02)
        with self._lock:
            for _xid, (pi, ids) in self._inflight.items():
                self.stats[pi]["lost_inflight"] += len(ids)
            self._inflight.clear()
        self._halt.set()
        try:
            self._sock.close()
        except OSError:
            pass

    def finish(self) -> None:
        self._halt.set()
        self.join(timeout=10)
        if self._reader is not None:
            self._reader.join(timeout=2)


class LeaseDriver(threading.Thread):
    """Closed-loop single-decision driver through ``TokenClient`` with
    wire-rev-5 leases on: hot flows admit client-locally, so this tenant
    exercises the lease leg (grants, renewals, the over-admission bound)
    while barely touching the door."""

    def __init__(self, tenant: TenantSpec, model: WorkloadModel,
                 port: int, total_seconds: float, lease_want: int,
                 metered_flow: int):
        super().__init__(name=f"driver-{tenant.name}", daemon=True)
        self.tenant = tenant
        self.model = model
        self.port = port
        self.total_seconds = total_seconds
        self.lease_want = lease_want
        self.metered_flow = metered_flow
        self.stats = {
            "decisions": 0, "ok": 0, "metered_pass": 0, "errors": 0,
            "lease_stats": {},
        }

    def run(self) -> None:
        from sentinel_tpu.cluster.client import TokenClient

        flows = self.tenant.flow_stream(100_000, self.model.seed)
        client = TokenClient(
            "127.0.0.1", self.port, timeout_ms=2000, lease=True,
            lease_want=self.lease_want,
        )
        st = self.stats
        try:
            client.request_token(int(flows[0]))  # warmup: connect + compile
            k = 1
            stop_at = time.perf_counter() + self.total_seconds
            while time.perf_counter() < stop_at:
                fid = int(flows[k % flows.size])
                k += 1
                try:
                    r = client.request_token(fid)
                except Exception:
                    st["errors"] += 1
                    continue
                st["decisions"] += 1
                if r is not None and r.ok:
                    st["ok"] += 1
                    if fid == self.metered_flow:
                        st["metered_pass"] += 1
            st["lease_stats"] = dict(client.lease_stats())
        except Exception:
            st["errors"] += 1
        finally:
            try:
                client.close()
            except Exception:
                pass

    def finish(self) -> None:
        self.join(timeout=self.total_seconds + 30)


# -- stack construction -------------------------------------------------------
def _build_stack(cfg: ScenarioConfig):
    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.rules import ThresholdMode
    from sentinel_tpu.overload.admission import (
        AdmissionController,
        OverloadConfig,
    )

    model = cfg.model
    total_flows = max(t.first_flow + t.n_flows for t in model.tenants)
    rules = []
    metered: Dict[str, int] = {}
    for t in model.tenants:
        # the tenant's hottest flow (Zipf rank 1) carries a finite
        # threshold — blocks are real, and the over-admission gate has a
        # concrete bound to check
        metered[t.name] = t.first_flow
        metered_qps = max(1.0, cfg.metered_frac * t.base_rate)
        for f in range(t.first_flow, t.first_flow + t.n_flows):
            count = metered_qps if f == t.first_flow else 1e9
            # the tenant's shaping profile (workload.cold_start_tenant /
            # paced_tenant) rides on the metered flow only — the long tail
            # stays plain so shaping effects are attributable
            shaped = f == t.first_flow and t.control_behavior != 0
            rules.append(
                ClusterFlowRule(
                    f, count, ThresholdMode.GLOBAL, namespace=t.name,
                    control_behavior=t.control_behavior if shaped else 0,
                    warm_up_period_sec=t.warm_up_period_sec,
                    cold_factor=t.cold_factor,
                    max_queueing_time_ms=t.max_queueing_time_ms,
                )
            )
    svc = DefaultTokenService(
        EngineConfig(max_flows=total_flows, max_namespaces=len(
            model.tenants) + 2, batch_size=256),
        lease_ttl_ms=2000,
    )
    svc.load_rules(rules, ns_max_qps=1e12)

    # degraded tenants: the metered flow guards the flaky dependency —
    # a DegradeRule with the tenant's knobs turns its br_* rule columns on
    degrade_rules = []
    for t in model.tenants:
        if getattr(t, "degraded", False):
            from sentinel_tpu.engine import DegradeRule, DegradeStrategy

            degrade_rules.append(DegradeRule(
                t.first_flow, DegradeStrategy(t.degrade_strategy),
                threshold=t.degrade_threshold,
                slow_rt_ms=t.degrade_slow_rt_ms,
                min_request_amount=t.degrade_min_requests,
                stat_interval_ms=t.degrade_stat_ms,
                recovery_timeout_ms=t.degrade_recovery_ms,
                namespace=t.name,
            ))
    if degrade_rules:
        svc.load_degrade_rules(degrade_rules)

    overload = AdmissionController(OverloadConfig(
        min_bdp=cfg.min_bdp,
        headroom_shed=cfg.headroom_shed,
        headroom_degrade=cfg.headroom_degrade,
        sustain_ms=cfg.sustain_ms,
        recheck_ms=10.0,
        ns_shares=model.shares(),
    ))

    standby = standby_svc = None
    replicate_to = None
    if cfg.replica:
        standby_svc = DefaultTokenService(
            EngineConfig(max_flows=total_flows, max_namespaces=len(
                model.tenants) + 2, batch_size=256),
        )
        standby_svc.load_rules(list(rules), ns_max_qps=1e12)
        if degrade_rules:
            # the standby needs the same br_* rule columns so replicated
            # breaker rows mean the same thing after a promotion
            standby_svc.load_degrade_rules(list(degrade_rules))
        standby = TokenServer(standby_svc, port=0, standby_of="primary")
        standby.start()
        replicate_to = [f"127.0.0.1:{standby.port}"]

    door = "asyncio"
    server = None
    if cfg.door == "native":
        try:
            from sentinel_tpu.cluster.server_native import (
                NativeTokenServer,
                native_available,
            )

            if native_available():
                server = NativeTokenServer(
                    svc, port=0, overload=overload, intake_shards=2,
                    replicate_to=replicate_to,
                )
                door = "native-epoll"
        except Exception:
            server = None
    if server is None:
        server = TokenServer(
            svc, port=0, overload=overload, max_queue=cfg.max_queue,
            replicate_to=replicate_to,
        )
    server.start()
    return svc, server, standby, standby_svc, door, metered


# -- gate math ---------------------------------------------------------------
def _phase_series(samples: List[dict], begin_ms: int,
                  end_ms: int) -> List[dict]:
    return [s for s in samples if begin_ms <= s["timestampMs"] < end_ms]


def _series_sums(series: List[dict]) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for s in series:
        t = out.setdefault(
            s["namespace"], {"pass": 0, "block": 0, "shed": 0, "other": 0}
        )
        for k in ("pass", "block", "shed", "other"):
            t[k] += int(s[k] or 0)
    return out


def fairness_check(sums: Dict[str, Dict[str, int]],
                   shares: Dict[str, float],
                   demand_rows: Dict[str, int],
                   tolerance: float,
                   exclude=()) -> dict:
    """The fairness gate over one shed phase: every tenant must be SERVED
    (pass + block — an answered request, whatever the verdict) at least
    ``share × total_served × (1 − tolerance)`` rows, unless its own demand
    was below that floor (a tenant that asked for less than its share was
    not starved — it was idle). Pure math on timeline sums, unit-tested
    directly in tests/test_scenario.py."""
    served = {
        ns: t["pass"] + t["block"] for ns, t in sums.items()
        if ns not in exclude
    }
    total = sum(served.values())
    verdicts = {}
    ok = True
    for ns, share in shares.items():
        if ns in exclude or ns not in served:
            continue
        floor = share * total * (1.0 - tolerance)
        demand = demand_rows.get(ns, 0)
        starved = served[ns] < floor and demand > floor
        verdicts[ns] = {
            "served": served[ns], "floor": round(floor, 1),
            "demand": demand, "starved": bool(starved),
        }
        if starved:
            ok = False
    return {"ok": ok, "totalServed": total, "tenants": verdicts}


def flood_attribution(base_sums: Dict[str, Dict[str, int]],
                      flood_sums: Dict[str, Dict[str, int]],
                      base_s: float, flood_s: float,
                      exclude=()) -> Optional[str]:
    """Name the flooding tenant from the timeline alone: the namespace
    with the largest ARRIVAL rate increase (pass + block + shed — sheds
    are arrivals too; that is exactly what distinguishes a flooder whose
    excess got shed from a tenant that was merely served more)."""
    best, best_delta = None, -1.0
    for ns, t in flood_sums.items():
        if ns in exclude:
            continue
        arr_flood = (t["pass"] + t["block"] + t["shed"]) / max(flood_s, 1e-9)
        b = base_sums.get(ns, {"pass": 0, "block": 0, "shed": 0})
        arr_base = (b["pass"] + b["block"] + b["shed"]) / max(base_s, 1e-9)
        delta = arr_flood - arr_base
        if delta > best_delta:
            best, best_delta = ns, delta
    return best


def degrade_attribution(base_counts: Dict[str, int],
                        storm_counts: Dict[str, int],
                        base_s: float, storm_s: float,
                        exclude=()) -> Optional[str]:
    """Name the degraded RESOURCE from the verdict stream alone — the
    flood-attribution mirror for breakers: the tenant with the largest
    DEGRADED-verdict rate increase between a baseline phase and the storm
    phase. A breaker refusal is attributed to the dependency that tripped
    it, not to whichever tenant happened to be loudest; requiring a
    strictly positive delta means a run where no breaker tripped names
    nobody."""
    best, best_delta = None, 0.0
    for name, c in storm_counts.items():
        if name in exclude:
            continue
        delta = (
            c / max(storm_s, 1e-9)
            - base_counts.get(name, 0) / max(base_s, 1e-9)
        )
        if delta > best_delta:
            best, best_delta = name, delta
    return best


# -- the scenario -------------------------------------------------------------
def run_scenario(cfg: ScenarioConfig) -> dict:
    import sentinel_tpu.chaos as chaos
    import sentinel_tpu.transport.handlers as handlers
    from sentinel_tpu.core.config import SentinelConfig
    from sentinel_tpu.metrics.server import (
        reset_server_metrics_for_tests,
        server_metrics,
    )
    from sentinel_tpu.metrics.timeline import configure_timeline
    from sentinel_tpu.trace.slo import (
        KEY_OBJECTIVE_MS,
        merge_fleet,
        reset_slo_plane_for_tests,
        slo_plane,
    )

    model = cfg.model
    os.makedirs(cfg.out_dir, exist_ok=True)
    # clean slate BEFORE the stack exists: the reset clears provider
    # registrations, so it must precede service construction
    reset_server_metrics_for_tests()
    SentinelConfig.set(KEY_OBJECTIVE_MS, str(cfg.objective_ms))
    # per-run file dir: the timeline log is persistent by design (a prior
    # run's seconds are still queryable), so the reconciliation gate gets
    # a dir and a time bound that are unambiguously this run's
    run_stamp = time.strftime("%Y%m%d-%H%M%S")
    tl = configure_timeline(
        base_dir=os.path.join(cfg.out_dir, f"timeline-{run_stamp}"))
    svc, server, standby, standby_svc, door, metered = _build_stack(cfg)

    phase_offsets: List[float] = []
    off = 0.0
    for ph in model.phases:
        phase_offsets.append(off)
        off += ph.seconds
    total_seconds = off

    started_ms = int(time.time() * 1000)
    failures: List[str] = []
    phase_bounds: List[tuple] = []  # (begin_ms, end_ms) wall clock
    chaos_fired: Dict[str, Dict[str, int]] = {}
    max_lease_tokens = 0

    drivers: List[TenantDriver] = []
    lease_driver: Optional[LeaseDriver] = None
    t0 = time.perf_counter() + 0.25  # let every driver arm before phase 0
    for t in model.tenants:
        if cfg.lease_tenant == t.name:
            lease_driver = LeaseDriver(
                t, model, server.port, total_seconds, cfg.lease_want,
                metered[t.name],
            )
        else:
            drivers.append(TenantDriver(
                t, model, server.port, t0, phase_offsets,
                cfg.window_frames, metered[t.name],
            ))
    try:
        for d in drivers:
            d.start()
        if lease_driver is not None:
            lease_driver.start()
        # phase conductor: chaos arming + wall-clock phase boundaries +
        # the post-warmup SLO reset (gates measure measured phases only)
        for pi, ph in enumerate(model.phases):
            target = t0 + phase_offsets[pi]
            now = time.perf_counter()
            if now < target:
                time.sleep(target - now)
            if pi > 0 and not model.phases[pi - 1].measured:
                # warmup (compile, connect) must not pollute the burn
                # windows; counters and the timeline keep warmup (the
                # reconciliation gate spans the whole run)
                reset_slo_plane_for_tests()
            begin_ms = int(time.time() * 1000)
            if ph.chaos:
                chaos.arm(ph.chaos, seed=model.seed)
            end_target = t0 + phase_offsets[pi] + ph.seconds
            while time.perf_counter() < end_target:
                time.sleep(0.1)
                out = svc.outstanding_leases() if hasattr(
                    svc, "outstanding_leases") else 0
                max_lease_tokens = max(max_lease_tokens, out)
            if ph.chaos:
                chaos_fired[ph.name] = chaos.fired()
                chaos.disarm()
            phase_bounds.append((begin_ms, int(time.time() * 1000)))
        # burn snapshot IMMEDIATELY after the last phase: the 1m windows
        # still hold every measured second
        slo_local = slo_plane().snapshot()
        fleet = merge_fleet([slo_local])
    finally:
        for d in drivers:
            d.finish()
        if lease_driver is not None:
            lease_driver.finish()
        chaos.disarm()
        # the objective was this run's: the plane that measured it holds
        # its copy, and whoever shares the process next reads the default
        with SentinelConfig._lock:
            SentinelConfig._props.pop(KEY_OBJECTIVE_MS, None)

    wall_s = round(time.time() - started_ms / 1000.0, 3)
    tl.flush()

    # -- the command surface is the read path (cluster/server/metric) -----
    end_all_ms = int(time.time() * 1000) + 2000
    samples = handlers.cmd_cluster_server_metric(
        {"startTime": str(started_ms // 1000 * 1000),
         "endTime": str(end_all_ms), "maxLines": "200000"}, "")

    # -- reconciliation gate: timeline sums == verdict counter deltas -----
    sm = server_metrics()
    counter_pass: Dict[str, int] = {}
    counter_block: Dict[str, int] = {}
    for (v, ns), c in sm.verdict_totals().items():
        if ns.startswith("rls:"):
            continue
        if v == "pass":
            counter_pass[ns] = counter_pass.get(ns, 0) + c
        elif v == "block":
            counter_block[ns] = counter_block.get(ns, 0) + c
    tl_sums = _series_sums(samples)
    recon_diffs = {}
    for ns in set(counter_pass) | set(counter_block) | set(tl_sums):
        tp = tl_sums.get(ns, {}).get("pass", 0)
        tb = tl_sums.get(ns, {}).get("block", 0)
        dp = tp - counter_pass.get(ns, 0)
        db = tb - counter_block.get(ns, 0)
        if dp or db:
            recon_diffs[ns] = {"passDiff": dp, "blockDiff": db}
    recon_ok = not recon_diffs
    if not recon_ok:
        failures.append(
            f"timeline does not reconcile with verdict counters: "
            f"{recon_diffs}"
        )

    # -- per-phase assembly ------------------------------------------------
    driver_stats = {d.tenant.name: d.stats for d in drivers}
    phases_doc = []
    measured_shed_phases = []
    for pi, ph in enumerate(model.phases):
        begin_ms, end_ms = phase_bounds[pi]
        series = _phase_series(samples, begin_ms // 1000 * 1000, end_ms)
        sums = _series_sums(series)
        tenants_doc = {}
        for t in model.tenants:
            st = (
                driver_stats.get(t.name, [None] * len(model.phases))[pi]
                if t.name in driver_stats else None
            )
            tenants_doc[t.name] = {
                "driver": st,
                "timeline": sums.get(t.name),
                "series": [s for s in series if s["namespace"] == t.name],
            }
        shed_rows = sum(t["shed"] for t in sums.values())
        if ph.measured and shed_rows > 0:
            measured_shed_phases.append(pi)
        phases_doc.append({
            "name": ph.name, "shape": ph.shape, "seconds": ph.seconds,
            "measured": ph.measured, "chaos": ph.chaos,
            "beginMs": begin_ms, "endMs": end_ms,
            "shedRows": shed_rows,
            "chaosFired": chaos_fired.get(ph.name),
            "tenants": tenants_doc,
        })

    # -- gate: per-tenant p99 burn ----------------------------------------
    burn_doc = {}
    burn_ok = True
    for t in model.tenants:
        if t.name == cfg.lease_tenant:
            continue
        gate = cfg.burn_gates.get(t.name, 60.0)
        snap = fleet["tenants"].get(t.name, {})
        burn = (snap.get("burnRate") or {}).get("1m")
        within = burn is not None and burn <= gate
        burn_doc[t.name] = {
            "burn1m": burn, "gate": gate, "p99Ms": snap.get("p99Ms"),
            "ok": bool(within),
        }
        if not within:
            burn_ok = False
            failures.append(
                f"{t.name}: burn(1m)={burn} exceeds gate {gate} "
                f"(p99={snap.get('p99Ms')}ms, objective "
                f"{cfg.objective_ms}ms)"
            )

    # -- gate: fairness during shed phases ---------------------------------
    exclude = {cfg.lease_tenant} if cfg.lease_tenant else set()
    fairness_doc = {}
    fairness_ok = True
    for pi in measured_shed_phases:
        ph = model.phases[pi]
        begin_ms, end_ms = phase_bounds[pi]
        series = _phase_series(samples, begin_ms // 1000 * 1000, end_ms)
        demand = {
            name: stats[pi]["demand_rows"]
            for name, stats in driver_stats.items()
        }
        res = fairness_check(
            _series_sums(series), model.shares(), demand,
            cfg.fairness_tolerance, exclude=exclude,
        )
        fairness_doc[ph.name] = res
        if not res["ok"]:
            fairness_ok = False
            starved = [
                ns for ns, v in res["tenants"].items() if v["starved"]
            ]
            failures.append(
                f"fairness violated in phase {ph.name}: {starved} served "
                f"below guaranteed share"
            )

    # -- gate: bounded over-admission on metered flows ---------------------
    lease_bound = max(
        max_lease_tokens,
        int((svc.lease_stats() or {}).get("outstanding_tokens", 0)),
    )
    over_doc = {}
    over_ok = True
    for t in model.tenants:
        metered_qps = max(1.0, cfg.metered_frac * t.base_rate)
        if t.name in driver_stats:
            passes = sum(
                st["metered_pass"] for st in driver_stats[t.name]
            )
        elif lease_driver is not None and t.name == cfg.lease_tenant:
            passes = lease_driver.stats["metered_pass"]
        else:
            continue
        # the documented bound: threshold × (windows + 2 boundary windows),
        # with slack for window phase, plus everything delegated on leases
        windows = int(np.ceil(wall_s)) + 2
        bound = metered_qps * windows * (1.0 + cfg.over_admission_slack) \
            + lease_bound
        ok = passes <= bound
        over_doc[t.name] = {
            "flow": metered[t.name], "thresholdQps": metered_qps,
            "passes": passes, "bound": round(bound, 1),
            "leaseTokensBound": lease_bound, "ok": bool(ok),
        }
        if not ok:
            over_ok = False
            failures.append(
                f"{t.name}: metered flow {metered[t.name]} admitted "
                f"{passes} > bound {bound:.0f}"
            )

    # -- gate: zero unrecoverable client errors ----------------------------
    client_errors = sum(
        st["errors"] for stats in driver_stats.values() for st in stats
    )
    if lease_driver is not None:
        client_errors += lease_driver.stats["errors"]
    if client_errors:
        failures.append(f"{client_errors} unrecoverable client errors")

    # -- gate: the timeline names the flooding tenant ----------------------
    flood_doc = None
    if cfg.flood_tenant is not None:
        flood_pi = next(
            (i for i, ph in enumerate(model.phases)
             if ph.shape in ("spike", "flashcrowd")), None)
        base_pi = next(
            (i for i, ph in enumerate(model.phases)
             if ph.measured and i != flood_pi), None)
        if flood_pi is not None and base_pi is not None:
            fb, fe = phase_bounds[flood_pi]
            bb, be = phase_bounds[base_pi]
            suspect = flood_attribution(
                _series_sums(
                    _phase_series(samples, bb // 1000 * 1000, be)),
                _series_sums(
                    _phase_series(samples, fb // 1000 * 1000, fe)),
                (be - bb) / 1000.0, (fe - fb) / 1000.0,
                exclude=exclude,
            )
            flood_doc = {
                "expected": cfg.flood_tenant, "named": suspect,
                "ok": suspect == cfg.flood_tenant,
            }
            if not flood_doc["ok"]:
                failures.append(
                    f"timeline named {suspect!r} as the flooder, expected "
                    f"{cfg.flood_tenant!r}"
                )

    # -- gate: the verdict stream names the degraded resource, and the
    # breaker both trips AND recovers (the recovery landing inside the
    # chaos-laced recovery-probe phase is the point of the profile) ------
    degrade_doc = None
    breaker_doc = None
    if cfg.degraded_tenant is not None:
        deg_counts = {
            name: [st["degraded"] for st in stats]
            for name, stats in driver_stats.items()
        }
        measured_pis = [
            i for i, ph in enumerate(model.phases) if ph.measured
        ]
        storm_pi = max(
            measured_pis,
            key=lambda i: sum(c[i] for c in deg_counts.values()),
        )
        base_pi = next(i for i in measured_pis if i != storm_pi)

        def _dur(pi: int) -> float:
            b, e = phase_bounds[pi]
            return (e - b) / 1000.0

        suspect = degrade_attribution(
            {n: c[base_pi] for n, c in deg_counts.items()},
            {n: c[storm_pi] for n, c in deg_counts.items()},
            _dur(base_pi), _dur(storm_pi),
        )
        # breaker_stats forces a final transition scan, so the totals
        # below include everything up to the last answered frame
        br = svc.breaker_stats() if hasattr(svc, "breaker_stats") else {}
        transitions = {
            f"{frm}->{to}": c
            for (frm, to), c in sm.breaker_transition_totals().items()
        }
        dep_flow = metered[cfg.degraded_tenant]
        final_state = (
            (br.get("flows") or {}).get(dep_flow, {}).get("state")
        )
        tripped = transitions.get("closed->open", 0) >= 1
        # the host scan sees NET edges between its ~1/s ticks, so a fast
        # HALF_OPEN→CLOSED probe cycle may fold into open->closed — either
        # edge back to CLOSED is the recovery proof
        recovered = (
            transitions.get("open->closed", 0)
            + transitions.get("half_open->closed", 0) >= 1
            and final_state == "closed"
        )
        degraded_rows = sum(c[storm_pi] for c in deg_counts.values())
        degrade_doc = {
            "expected": cfg.degraded_tenant, "named": suspect,
            "stormPhase": model.phases[storm_pi].name,
            "basePhase": model.phases[base_pi].name,
            "degradedRowsInStorm": degraded_rows,
            "tripped": tripped, "recovered": recovered,
            "finalState": final_state,
            "ok": bool(
                suspect == cfg.degraded_tenant and tripped and recovered
            ),
        }
        breaker_doc = {"transitions": transitions, "flows": {
            str(fid): snap for fid, snap in (br.get("flows") or {}).items()
        }}
        if suspect != cfg.degraded_tenant:
            failures.append(
                f"verdict stream named {suspect!r} as the degraded "
                f"resource, expected {cfg.degraded_tenant!r}"
            )
        if not tripped:
            failures.append(
                "breaker never tripped: no closed->open transition "
                f"observed (transitions={transitions})"
            )
        if not recovered:
            failures.append(
                f"breaker did not recover under chaos: final state "
                f"{final_state!r}, transitions={transitions}"
            )

    overload_snap = server.overload.snapshot() if hasattr(
        server, "overload") else {}
    shed_by_reason = sm.shed_totals()
    repl_doc = None
    if standby is not None:
        applier = getattr(standby, "applier", None)
        repl_doc = {
            "standbyPort": standby.port,
            "standby": applier.status() if applier is not None else None,
        }

    doc = {
        "schema": SCHEMA,
        "name": cfg.name,
        "seed": model.seed,
        "door": door,
        "startedMs": started_ms,
        "wallS": wall_s,
        "objectiveMs": cfg.objective_ms,
        "shares": model.shares(),
        "burnGates": cfg.burn_gates,
        "floodTenant": cfg.flood_tenant,
        "degradedTenant": cfg.degraded_tenant,
        "tenants": [
            {"name": t.name, "flows": t.n_flows, "share": t.share,
             "baseRate": t.base_rate, "zipfAlpha": t.zipf_alpha,
             "batch": t.batch, "prioritized": t.prioritized,
             "lease": t.name == cfg.lease_tenant,
             "degraded": bool(getattr(t, "degraded", False)),
             "outcomeProfile": getattr(t, "outcome_profile", None),
             "meteredFlow": metered[t.name]}
            for t in model.tenants
        ],
        "phases": phases_doc,
        "gates": {
            "p99Burn": {"ok": burn_ok, "tenants": burn_doc},
            "fairness": {"ok": fairness_ok, "phases": fairness_doc,
                         "tolerance": cfg.fairness_tolerance},
            "overAdmission": {"ok": over_ok, "tenants": over_doc},
            "clientErrors": {"ok": client_errors == 0,
                             "count": client_errors},
            "floodAttribution": flood_doc,
            "degradeAttribution": degrade_doc,
            "timelineReconciles": {"ok": recon_ok, "diffs": recon_diffs},
        },
        "slo": fleet,
        "server": {
            "overload": overload_snap,
            "shedByReason": shed_by_reason,
            "lease": svc.lease_stats() if hasattr(
                svc, "lease_stats") else {},
            "maxLeaseTokens": max_lease_tokens,
            "breaker": breaker_doc,
        },
        "leaseDriver": (
            lease_driver.stats if lease_driver is not None else None
        ),
        "replication": repl_doc,
        "failures": failures,
    }

    server.stop()
    if standby is not None:
        standby.stop()
    svc.close()
    if standby_svc is not None:
        standby_svc.close()
    return doc


# -- artifacts ----------------------------------------------------------------
def _round_number(prefix: str) -> int:
    rounds = glob.glob(os.path.join(_REPO, f"{prefix}_r*.json"))
    best = 0
    for p in rounds:
        try:
            best = max(best, int(
                os.path.basename(p)[len(prefix) + 2:-len(".json")]))
        except ValueError:
            continue
    return best + 1


def publish(doc: dict, cfg: ScenarioConfig) -> dict:
    os.makedirs(cfg.out_dir, exist_ok=True)
    stamp = time.strftime("%Y%m%d-%H%M%S")
    full_path = os.path.join(cfg.out_dir, f"scenario-{stamp}.json")
    with open(full_path, "w") as f:
        json.dump(doc, f, indent=2)
    paths = {"full": full_path}
    if cfg.publish_round:
        # the round summary drops the per-second series (the full artifact
        # keeps them) — the trajectory file stays reviewable
        slim = json.loads(json.dumps(doc))
        for ph in slim["phases"]:
            for t in ph["tenants"].values():
                t.pop("series", None)
        n = _round_number("SCENARIO")
        round_path = os.path.join(_REPO, f"SCENARIO_r{n:02d}.json")
        with open(round_path, "w") as f:
            json.dump(slim, f, indent=2)
        paths["round"] = round_path
    return paths


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: 2 tenants, ramp+spike+chaos, ~15s")
    ap.add_argument("--degraded", action="store_true",
                    help="circuit-breaker profile: error-storm tenant, "
                         "trip + chaos-laced recovery-probe phase, ~12s")
    ap.add_argument("--seed", type=int, default=20260805)
    ap.add_argument("--door", choices=("tcp", "native"), default="tcp")
    ap.add_argument("--objective-ms", type=float, default=None,
                    help="p99 objective (default 150 CPU loopback)")
    ap.add_argument("--no-replica", action="store_true",
                    help="skip the warm-standby replication leg")
    ap.add_argument("--no-round", action="store_true",
                    help="skip the SCENARIO_r0N round summary")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    if args.smoke:
        cfg = smoke_config(args.seed)
    elif args.degraded:
        cfg = degraded_config(args.seed)
    else:
        cfg = full_config(args.seed)
    cfg.door = args.door
    cfg.out_dir = args.out_dir
    if args.objective_ms is not None:
        cfg.objective_ms = args.objective_ms
    if args.no_replica:
        cfg.replica = False
    if args.no_round:
        cfg.publish_round = False

    doc = run_scenario(cfg)
    paths = publish(doc, cfg)
    gates = doc["gates"]
    print(json.dumps({
        "artifact": paths, "failures": doc["failures"],
        "gates": {k: (v or {}).get("ok") for k, v in gates.items()},
        "shedByReason": doc["server"]["shedByReason"],
    }, indent=2))
    if doc["failures"]:
        print(f"SCENARIO FAILED: {doc['failures']}", file=sys.stderr)
        sys.exit(1)
    print(
        f"scenario ok: {cfg.name} seed={doc['seed']} door={doc['door']} "
        f"wall={doc['wallS']}s — all gates green"
    )


if __name__ == "__main__":
    main()
