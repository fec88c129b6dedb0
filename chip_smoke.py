"""Proof that the token server starts and answers correctly on the chip.

Drives the main path once — ``TokenClient`` → TCP front door → micro-batcher
→ ``DefaultTokenService`` → jitted decide step → verdict on the wire — at the
size of BASELINE config 5 cut to one chip (100k flow rules over 64
namespaces, 16384-row engine batches; the deployment
``benchmarks/serve_bench.py::build_server`` builds), through both TCP doors,
and checks every verdict against numbers worked out in plain Python from the
rules. Any phase that fails raises: the exit code is non-zero and no result
line is printed. The times it prints are for the record, not a benchmark.

    python chip_smoke.py              # one chip
    python chip_smoke.py --mesh 4     # flow axis sharded over four chips

One process holds the chip; the clients are threads in it. The last line of
standard output is ``{"ok": true, "device": {...}}`` as JAX reports the
device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

N_FLOWS = 100_000
N_NAMESPACES = 64
BATCH = 16384
SERVE_BUCKETS = (64, 1024, 4096, 16384)
FRAME_SIZES = (1024, 4096, 16384)
NS_MAX_QPS = 30_000  # the reference's default namespace guard
PLAIN_COUNT = 1e6
# namespaces kept free of background traffic: each namespace-guard check
# needs a namespace whose window holds nothing but its own frames
GUARD_NAMESPACES = ("ns60", "ns61", "ns62", "ns63")
UNKNOWN_FLOW = 9_999_999
BREAKER_RECOVERY_MS = 1000  # short, so that probe, recover and reopen fit
SPECIAL_BASE = 1_000_000  # special flow ids start here, clear of the plain ones

OK, BLOCKED, SHOULD_WAIT, NO_RULE, TOO_MANY, DEGRADED = 0, 1, 2, 3, 4, 12


def say(msg: str) -> None:
    print(msg, flush=True)


def expect(what: str, got, want) -> None:
    """One exact check. Raises — there is no record-and-carry-on."""
    if isinstance(got, np.ndarray) or isinstance(want, np.ndarray):
        same = np.array_equal(np.asarray(got), np.asarray(want))
    else:
        same = got == want
    if not same:
        raise RuntimeError(f"CHECK FAILED {what}: got {got!r}, want {want!r}")
    say(f"  ok  {what}: {want if np.ndim(want) == 0 else 'exact'}")


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


class Lane:
    """The special flows one run of the verdict checks owns. Every lane
    (native door, asyncio door, in-process fused dispatch) gets fresh flow
    ids, so each check starts from an empty window and its expected
    verdicts follow from the rule alone."""

    N_TIGHT = 8

    def __init__(self, k: int, guard_ns: str):
        base = SPECIAL_BASE + 100 * k
        self.tight = [base + i for i in range(self.N_TIGHT)]  # count=20
        self.tight5000 = base + 10
        self.paced = base + 11  # RATE_LIMITER, 100/s
        self.warm = base + 12  # WARM_UP, 100/s, cold factor 3
        self.warm_paced = base + 13  # WARM_UP_RATE_LIMITER
        self.breaker = base + 14  # plain rule + DegradeRule
        self.param = base + 15  # ClusterParamFlowRule, 5 per value
        self.conc = base + 16  # ConcurrentFlowRule, 3 calls in flight
        self.conc_wide = base + 17  # ConcurrentFlowRule, 1,000
        self.guard_ns = guard_ns
        self._next_tight = 0

    def fresh_tight(self) -> int:
        fid = self.tight[self._next_tight]
        self._next_tight += 1
        return fid

    def flow_rules(self):
        from sentinel_tpu.engine import ClusterFlowRule
        from sentinel_tpu.engine.rules import ControlBehavior, ThresholdMode

        g = ThresholdMode.GLOBAL
        ns = "ns0"
        rules = [ClusterFlowRule(f, 20.0, g, ns) for f in self.tight]
        rules += [
            ClusterFlowRule(self.tight5000, 5000.0, g, ns),
            ClusterFlowRule(self.paced, 100.0, g, ns,
                            control_behavior=int(ControlBehavior.RATE_LIMITER)),
            ClusterFlowRule(self.warm, 100.0, g, ns,
                            control_behavior=int(ControlBehavior.WARM_UP)),
            ClusterFlowRule(
                self.warm_paced, 100.0, g, ns,
                control_behavior=int(ControlBehavior.WARM_UP_RATE_LIMITER),
            ),
            ClusterFlowRule(self.breaker, PLAIN_COUNT, g, ns),
        ]
        return rules

    def degrade_rules(self):
        from sentinel_tpu.engine import DegradeRule, DegradeStrategy

        return [DegradeRule(
            self.breaker, DegradeStrategy.ERROR_COUNT, threshold=5,
            min_request_amount=5, recovery_timeout_ms=BREAKER_RECOVERY_MS,
            namespace="ns0",
        )]

    def param_rules(self):
        from sentinel_tpu.cluster.token_service import ClusterParamFlowRule

        return [ClusterParamFlowRule(self.param, 5.0, namespace="ns0")]

    def concurrent_rules(self):
        from sentinel_tpu.cluster.concurrent import ConcurrentFlowRule

        return [ConcurrentFlowRule(self.conc, 3, namespace="ns0"),
                ConcurrentFlowRule(self.conc_wide, 1000, namespace="ns0")]


def build_service(lanes, mesh_chips: int):
    """100k rules over 64 namespaces on the device, warmed up bare."""
    import jax

    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.engine import ClusterFlowRule, EngineConfig
    from sentinel_tpu.engine.param import explain_param_impl
    from sentinel_tpu.engine.rules import ThresholdMode

    config = EngineConfig(
        max_flows=N_FLOWS, max_namespaces=N_NAMESPACES, batch_size=BATCH
    )
    mesh = None
    if mesh_chips:
        from sentinel_tpu.parallel import make_flow_mesh

        mesh = make_flow_mesh(jax.devices()[:mesh_chips])
    service = DefaultTokenService(
        config, serve_buckets=SERVE_BUCKETS, mesh=mesh
    )
    special = [r for lane in lanes for r in lane.flow_rules()]
    n_plain = N_FLOWS - len(special)
    t0 = time.perf_counter()
    service.load_degrade_rules([d for lane in lanes
                                for d in lane.degrade_rules()])
    service.load_rules(
        [
            ClusterFlowRule(i, PLAIN_COUNT, ThresholdMode.GLOBAL,
                            f"ns{i % N_NAMESPACES}")
            for i in range(n_plain)
        ] + special,
        ns_max_qps=float(NS_MAX_QPS),
    )
    service.load_param_rules([p for lane in lanes for p in lane.param_rules()])
    service.load_concurrent_rules([c for lane in lanes
                                   for c in lane.concurrent_rules()])
    n_rules = len(service.current_rules())
    expect("flow rules loaded", n_rules, N_FLOWS)
    say(f"  rule load {time.perf_counter() - t0:.1f}s: {n_plain} plain GLOBAL "
        f"+ {len(special)} special over {N_NAMESPACES} namespaces, window "
        f"{config.n_buckets}x{config.bucket_ms}ms, ns guard {NS_MAX_QPS}/s")

    pcfg = service.param_config
    pimpl, pwhy = explain_param_impl(pcfg.impl, pcfg.sketch)
    say(f"  param impl {pcfg.impl!r} ({pcfg.sketch}) -> {pimpl}: {pwhy}")
    say(f"  serve buckets {list(SERVE_BUCKETS)}, fused depths "
        f"{service._fuse_depths} under lax.scan")

    t0 = time.perf_counter()
    service.warmup()
    jax.block_until_ready(service._state)
    say(f"  warmup {time.perf_counter() - t0:.1f}s")
    return service, n_plain


def check_mesh(service, mesh_chips: int) -> None:
    counts = service._state.flow.counts
    shards = counts.addressable_shards
    expect("state shards", len(shards), mesh_chips)
    expect("distinct shard devices", len({s.device for s in shards}),
           mesh_chips)
    expect("rows per shard", sorted({s.data.shape[0] for s in shards}),
           [N_FLOWS // mesh_chips])
    say(f"  param sketch lives on {service._param_state.counts.devices()}")


class Traffic:
    """Seeded background ids: plain flows outside the guard namespaces."""

    def __init__(self, n_plain: int, seed: int):
        self.rng = np.random.default_rng(seed)
        ids = np.arange(n_plain, dtype=np.int64)
        free = N_NAMESPACES - len(GUARD_NAMESPACES)
        self.background = ids[ids % N_NAMESPACES < free]
        self.by_ns = {
            ns: ids[ids % N_NAMESPACES == int(ns[2:])]
            for ns in GUARD_NAMESPACES
        }

    def plain(self, n: int) -> np.ndarray:
        return self.rng.choice(self.background, size=n)


def frame_with_tight(traffic, n, tight_flow, acquire_tight, mixed):
    """``n`` ids: background rows with 30 rows of one tight flow scattered
    through them. ``mixed`` gives the background rows acquires 1..3, which
    makes the frame non-uniform (the refine path); the tight rows all
    acquire ``acquire_tight``."""
    ids = traffic.plain(n)
    acq = (traffic.rng.integers(1, 4, size=n) if mixed
           else np.ones(n, np.int64)).astype(np.int32)
    at = np.sort(traffic.rng.choice(n, size=30, replace=False))
    ids[at] = tight_flow
    acq[at] = acquire_tight
    return ids, acq, at


def check_frames(send, traffic, lane) -> None:
    """1024-, 4096- and 16384-id frames, uniform and mixed acquire. Each
    carries 30 rows of a fresh count=20 flow: exactly the first
    ``20 // acquire`` of them pass, every background row passes."""
    for n in FRAME_SIZES:
        for mixed, a in ((False, 1), (True, 3)):
            ids, acq, at = frame_with_tight(
                traffic, n, lane.fresh_tight(), a, mixed
            )
            t0 = time.perf_counter()
            status, _, _ = send(ids, acq)
            ms = (time.perf_counter() - t0) * 1e3
            want = np.full(n, OK, np.int8)
            want[at[20 // a:]] = BLOCKED
            kind = "mixed acquire" if mixed else "uniform"
            expect(f"{n}-id frame, {kind}: {20 // a} of 30 tight rows pass "
                   f"({ms:.1f} ms)", status, want)


def check_small(send, lane) -> None:
    fid = lane.fresh_tight()
    status, remaining, _ = send(np.full(30, fid, np.int64), None)
    expect("count=20 flow sent 30: 20 OK then 10 BLOCKED", status,
           np.array([OK] * 20 + [BLOCKED] * 10, np.int8))
    expect("remaining counts down to 0", remaining[:20],
           np.arange(19, -1, -1, dtype=np.int32))
    status, _, _ = send(np.array([UNKNOWN_FLOW, fid], np.int64), None)
    expect("unknown flow id", status, np.array([NO_RULE, BLOCKED], np.int8))


def check_5000(send, lane) -> None:
    """A count above 256 is what a bf16-pass matmul would get wrong."""
    status, _, _ = send(np.full(6000, lane.tight5000, np.int64), None)
    expect("count=5000 flow sent 6000: OK", int((status == OK).sum()), 5000)
    expect("count=5000 flow sent 6000: BLOCKED",
           int((status == BLOCKED).sum()), 1000)
    expect("the 5000 OK are the first 5000", status[:5000],
           np.full(5000, OK, np.int8))


def check_shaping(send, lane) -> None:
    # RATE_LIMITER at 100/s: cost = round(1000 * 1 / 100) = 10 ms per row.
    # An idle flow's first row passes now; row j is scheduled 10*j ms out
    # (docs/SHAPING.md "Pacing"); the default 500 ms queue holds rows 0..50.
    status, _, wait = send(np.full(60, lane.paced, np.int64), None)
    expect("paced flow: first row OK, 50 SHOULD_WAIT, 9 BLOCKED", status,
           np.array([OK] + [SHOULD_WAIT] * 50 + [BLOCKED] * 9, np.int8))
    expect("paced flow: waits 0,10,..,500 ms", wait[:51],
           np.arange(51, dtype=np.int32) * 10)
    # WARM_UP at 100/s, cold factor 3: a cold flow admits count/3 per
    # second, so a 1 s window holds floor(33.3) rows
    status, _, _ = send(np.full(100, lane.warm, np.int64), None)
    expect("cold WARM_UP flow sent 100: OK", int((status == OK).sum()), 33)
    # both: paced at the cold rate, cost = round(1000 / 33.3) = 30 ms;
    # 30*j <= 500 holds for rows 0..16
    status, _, wait = send(np.full(30, lane.warm_paced, np.int64), None)
    expect("cold WARM_UP_RATE_LIMITER flow", status,
           np.array([OK] + [SHOULD_WAIT] * 16 + [BLOCKED] * 13, np.int8))
    expect("cold paced waits 0,30,..,480 ms", wait[:17],
           np.arange(17, dtype=np.int32) * 30)


def check_guard(send, traffic, lane) -> None:
    """32768 rows into one otherwise idle namespace against the 30000/s
    guard: exactly the overflow answers TOO_MANY_REQUEST — the one-hot
    einsum and the blocked cumsum of the guard's precise arm."""
    n = 2 * BATCH
    ids = traffic.rng.choice(traffic.by_ns[lane.guard_ns], size=n)
    status, _, _ = send(ids, None)
    expect(f"{n} rows into {lane.guard_ns}: OK",
           int((status == OK).sum()), NS_MAX_QPS)
    expect(f"{n} rows into {lane.guard_ns}: TOO_MANY_REQUEST",
           int((status == TOO_MANY).sum()), n - NS_MAX_QPS)


def check_single_param_frames(label, server, lane, n_values: int = 50,
                              per_value: int = 100) -> None:
    """The lane of single PARAM_FLOW frames (type 2, what the reference's
    own client sends: one request a frame) beside the batched one: 5,000
    frames pipelined on one raw connection, ``n_values`` values of the
    lane's rule asked ``per_value`` times each in turn. Of every value the
    first 5 in frame order pass and the rest are BLOCKED, while the frames
    fall inside one window; every frame is answered once, under type 2, by
    its own xid; the native door counts them all on its data plane and its
    control loop sees none."""
    import socket

    from sentinel_tpu.cluster import protocol as P

    n = n_values * per_value
    for attempt in range(3):
        base = 500_000 + attempt * n_values  # fresh values every attempt
        values = base + np.arange(n) % n_values
        blob = b"".join(
            P.encode_request(P.FlowRequest(
                1 + i, lane.param, 1, False, P.MsgType.PARAM_FLOW,
                (int(v),)))
            for i, v in enumerate(values))
        door0, ctl0 = server.stats(), service_metrics().param_single_totals()
        reader, frames = P.FrameReader(), []
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=60) as sock:
            t0 = time.perf_counter()
            sock.sendall(blob)
            while len(frames) < n:
                data = sock.recv(1 << 18)
                if not data:
                    raise RuntimeError(f"{label}: connection closed after "
                                       f"{len(frames)} of {n} replies")
                frames += reader.feed(data)
            took = time.perf_counter() - t0
        if took < 0.45:  # two buckets of 500 ms: one window holds it all
            break
        say(f"  {n} single PARAM_FLOW frames took {took * 1e3:.0f} ms, "
            f"past one bucket of the window: asked again on fresh values")
    else:
        raise RuntimeError(f"{label}: {n} single frames never fit a window")
    replies = [P.decode_response(f) for f in frames]
    expect("single PARAM_FLOW frames: reply types",
           {int(r.msg_type) for r in replies}, {int(P.MsgType.PARAM_FLOW)})
    expect("single PARAM_FLOW frames: every xid once",
           sorted(r.xid for r in replies), list(range(1, n + 1)))
    status = np.zeros(n, np.int8)
    for r in replies:
        status[r.xid - 1] = r.status
    want = np.where(np.arange(n) // n_values < 5, OK, BLOCKED)
    early = int(((status == BLOCKED) & (want == OK)).sum())
    expect("single PARAM_FLOW frames: differences other than an early "
           "refusal", int((status != want).sum()) - early, 0)
    if early > 20:  # the sketch may refuse early, within its stated share
        raise AssertionError(f"{early} of {n} rows refused early (limit 20)")
    door1, ctl1 = server.stats(), service_metrics().param_single_totals()
    expect("single PARAM_FLOW frames the door took on its data plane",
           door1["param_single_frames_in"] - door0["param_single_frames_in"],
           n)
    expect("single PARAM_FLOW frames that reached the control loop",
           ctl1["param_control_frames_total"]
           - ctl0["param_control_frames_total"], 0)
    pulls = ctl1["param_single_pulls_total"] - ctl0["param_single_pulls_total"]
    say(f"  {n} single PARAM_FLOW frames through {label}: "
        f"{took * 1e3:.1f} ms, {early} refused early, "
        f"{n / max(pulls, 1):.0f} frames a pull")


def check_concurrency(label, client, lane) -> None:
    """The concurrency lane: a rule of 3 calls in flight asked by the
    reference's single frames (types 3 and 4: one-row dispatches), then one
    of 1,000 by a batch frame of 1,024 acquires and the batch release of
    what it issued. Every dispatch of the lane was prepped by the native
    pass (``sn_concurrent_prep``): a shortfall is a build without the
    entry."""
    from sentinel_tpu.engine import TokenStatus

    m = service_metrics()
    d0 = m.concurrent_totals()["concurrent_dispatch_total"]
    n0 = m.concurrent_prep_native_total
    got = [client.request_concurrent_token(lane.conc) for _ in range(4)]
    expect("concurrency rule of 3, asked 4 times", [r.status for r in got],
           [TokenStatus.OK] * 3 + [TokenStatus.BLOCKED])
    expect("a release of a live token",
           client.release_concurrent_token(got[0].token_id).status,
           TokenStatus.RELEASE_OK)
    expect("the same token again",
           client.release_concurrent_token(got[0].token_id).status,
           TokenStatus.ALREADY_RELEASE)
    again = client.request_concurrent_token(lane.conc)
    expect("the room it freed", again.status, TokenStatus.OK)
    out = client.request_concurrent_batch(np.full(1024, lane.conc_wide))
    if out is None:
        raise RuntimeError(f"{label}: concurrency batch timed out or failed")
    status, _remaining, _wait, tokens = out
    expect("1024 acquires on a rule of 1,000: OK",
           int((status == OK).sum()), 1000)
    expect("... and BLOCKED", int((status == BLOCKED).sum()), 24)
    back = np.concatenate([tokens[tokens != 0],
                           [r.token_id for r in (*got[1:3], again)]])
    released = client.release_concurrent_batch(back)
    expect("every token given back: RELEASE_OK",
           int((released == int(TokenStatus.RELEASE_OK)).sum()), len(back))
    dispatches = m.concurrent_totals()["concurrent_dispatch_total"] - d0
    native = m.concurrent_prep_native_total - n0
    say(f"  concurrency lane through {label}: {dispatches} dispatches, "
        f"concurrent_prep_native_total +{native}")
    expect("concurrency dispatches the native pass prepped", native,
           dispatches)


def check_door(label, server, service, traffic, lane) -> None:
    from sentinel_tpu.cluster.client import TokenClient
    from sentinel_tpu.engine import TokenStatus

    server.start()
    client = TokenClient("127.0.0.1", server.port, timeout_ms=60_000)
    try:
        def send(ids, acq):
            out = client.request_batch_arrays(ids, acq)
            if out is None:
                raise RuntimeError(f"{label}: batch timed out or failed")
            return out

        t0 = time.perf_counter()
        first = client.request_token(int(traffic.plain(1)[0]))
        say(f"  first request_token through {label}: {first.status.name} in "
            f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
        expect("single request_token, plain flow", first.status,
               TokenStatus.OK)
        expect("single request_token, unknown flow",
               client.request_token(UNKNOWN_FLOW).status,
               TokenStatus.NO_RULE_EXISTS)
        check_frames(send, traffic, lane)
        check_small(send, lane)
        check_5000(send, lane)
        check_shaping(send, lane)
        check_guard(send, traffic, lane)

        # a burst long enough for the device lane to fold full engine
        # frames into one chained dispatch, timing permitting; the fused
        # path's own exact check is check_fused_inprocess
        fused0 = service_metrics().fused_frames_total
        status, _, _ = send(traffic.plain(4 * BATCH), None)
        expect(f"{4 * BATCH}-row burst: all OK",
               int((status == OK).sum()), 4 * BATCH)
        say(f"  engine frames fused by the {label} device lane in that "
            f"burst: {service_metrics().fused_frames_total - fused0}")

        # param sketch: 5 per value per second; the 6th and 7th of one
        # value are blocked, another value is untouched by them
        got = [client.request_params_token(lane.param, 1, [4242]).status
               for _ in range(7)]
        expect("param rule count=5, one value sent 7 times", got,
               [TokenStatus.OK] * 5 + [TokenStatus.BLOCKED] * 2)
        expect("param rule, another value",
               client.request_params_token(lane.param, 1, [77]).status,
               TokenStatus.OK)

        if hasattr(server, "stats"):  # the native door's data plane
            check_single_param_frames(label, server, lane)

        check_concurrency(label, client, lane)

        # breaker: CLOSED passes; 8 reported exceptions (> 5, with at
        # least 5 completions) open it at the next request, which is shed
        # with the rule's recovery timeout as retry-after; past the timeout
        # one request passes as the probe and the next is shed; a good
        # completion closes; tripped again, a bad one on the probe opens it
        # for the whole timeout once more (ROADMAP Reach A1's ground)
        def report(outcomes) -> None:
            """Completions of the breaker flow, sent and seen ingested."""
            done = ingested() + len(outcomes)
            for rt_ms, failed in outcomes:
                client.record_outcome(lane.breaker, rt_ms, exception=failed)
            if not client.flush_outcomes():
                raise RuntimeError(f"{label}: outcome report not sent")
            deadline = time.monotonic() + 10
            while ingested() < done:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"{label}: outcome report never ingested")
                time.sleep(0.005)

        def ask():
            return client.request_token(lane.breaker)

        expect("breaker flow while CLOSED", ask().status, TokenStatus.OK)
        t_report = time.perf_counter()
        report([(5, True)] * 8)
        say(f"  8 exceptions reported and ingested in "
            f"{(time.perf_counter() - t_report) * 1e3:.1f} ms")
        res = ask()
        t_open = time.monotonic()
        expect("breaker flow after 8 exceptions", res.status,
               TokenStatus.DEGRADED)
        expect("retry-after is the recovery timeout", res.remaining,
               BREAKER_RECOVERY_MS)
        time.sleep(BREAKER_RECOVERY_MS / 1000 + 0.05)
        expect("past the timeout one request passes as the probe",
               ask().status, TokenStatus.OK)
        expect("and the next is shed until a completion resolves it",
               ask().status, TokenStatus.DEGRADED)
        report([(5, False)])
        expect("a good completion closes the breaker", ask().status,
               TokenStatus.OK)
        time.sleep(0.12)  # past the fence: the close hides its own bucket
        report([(5, True)] * 8)
        expect("tripped again", ask().status, TokenStatus.DEGRADED)
        time.sleep(BREAKER_RECOVERY_MS / 1000 + 0.05)
        expect("the second probe passes", ask().status, TokenStatus.OK)
        report([(5, True)])
        res = ask()
        expect("a bad completion opens it again", res.status,
               TokenStatus.DEGRADED)
        expect("for most of the timeout still",
               BREAKER_RECOVERY_MS * 0.6 < res.remaining
               <= BREAKER_RECOVERY_MS, True)
        say(f"  breaker lane took {time.monotonic() - t_open:.2f} s "
            f"from its first trip")
    finally:
        client.close()
        server.stop()


def service_metrics():
    from sentinel_tpu.metrics.server import server_metrics

    return server_metrics()


def ingested() -> int:
    """Completion rows the outcome steps have scattered so far (a host
    counter: ``outcome_stats()`` reads the whole window from the device)."""
    return service_metrics().arm_totals()["outcome_step_rows_total"]


def check_fused_inprocess(service, traffic, lane) -> None:
    """Four full engine frames in one call: the service folds them into one
    ``lax.scan`` dispatch (depth 4). 30 rows of a count=20 flow are spread
    over all four frames — the first 20 in arrival order pass, so frame
    k+1 saw the window frame k wrote — and one guard namespace takes 32768
    rows across the frames."""
    n = 4 * BATCH
    ids = traffic.plain(n)
    at = np.sort(traffic.rng.choice(n, size=30, replace=False))
    ids[at] = lane.fresh_tight()
    free = np.setdiff1d(np.arange(n), at)
    guard_at = traffic.rng.choice(free, size=2 * BATCH, replace=False)
    ids[guard_at] = traffic.rng.choice(
        traffic.by_ns[lane.guard_ns], size=2 * BATCH
    )
    fused0 = service_metrics().fused_frames_total
    status, _, _ = service.request_batch_arrays(ids)
    expect("frames folded into one chained dispatch",
           service_metrics().fused_frames_total - fused0, 4)
    expect("tight flow across fused frames: first 20 OK, then BLOCKED",
           status[at], np.array([OK] * 20 + [BLOCKED] * 10, np.int8))
    expect("guard namespace across fused frames: TOO_MANY_REQUEST",
           int((status[guard_at] == TOO_MANY).sum()),
           2 * BATCH - NS_MAX_QPS)
    expect("every other row OK", int((status == OK).sum()),
           n - 10 - (2 * BATCH - NS_MAX_QPS))


def run(mesh_chips: int, seed: int) -> None:
    # first, before any import that loads the library on its own
    from sentinel_tpu.native import lib as native_lib

    say("== native library ==")
    t0 = time.perf_counter()
    stale = native_lib._stale()
    native_lib.require()  # builds from native/src when missing or stale
    say(f"  {native_lib._SO_PATH}: "
        f"{'built with g++' if stale else 'up to date'} "
        f"({time.perf_counter() - t0:.1f}s)")

    from sentinel_tpu.cluster.server import TokenServer
    from sentinel_tpu.cluster.server_native import NativeTokenServer

    lanes = [Lane(k, ns) for k, ns in enumerate(GUARD_NAMESPACES[:3])]
    say("== service ==")
    service, n_plain = build_service(lanes, mesh_chips)
    traffic = Traffic(n_plain, seed)
    try:
        if mesh_chips:
            say(f"== mesh over {mesh_chips} chips ==")
            check_mesh(service, mesh_chips)
        doors = (
            ("NativeTokenServer", NativeTokenServer),
            ("TokenServer", TokenServer),
        )
        for (label, door), lane in zip(doors, lanes):
            say(f"== {label} ==")
            check_door(
                label, door(service, port=0, max_batch=BATCH), service,
                traffic, lane,
            )
        say("== fused dispatch, in process ==")
        check_fused_inprocess(service, traffic, lanes[2])
    finally:
        service.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="CHIPS",
                    help="shard the flow axis over this many chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"chip_smoke needs a TPU; JAX found {devices}")
        sys.exit(2)
    if args.mesh > len(devices):
        say(f"--mesh {args.mesh} needs that many chips; JAX found {devices}")
        sys.exit(2)
    from importlib.metadata import version

    say(f"platform {devices[0].platform}, device_kind "
        f"{devices[0].device_kind!r}, {len(devices)} device(s); jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{version('libtpu')}")

    from sentinel_tpu.core.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    before = cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {before} entries")
    t0 = time.perf_counter()
    run(args.mesh, args.seed)
    say(f"compile cache {cache_dir}: {before} -> {cache_entries(cache_dir)} "
        f"entries; total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
