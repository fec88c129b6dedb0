"""Batched decision engine: the TPU data plane.

This is where the reference's per-request JVM hot path
(``DefaultTokenService.requestToken`` → ``ClusterFlowChecker.acquireClusterToken``,
``ClusterFlowChecker.java:36-120``) becomes one jitted pure function over
micro-batches::

    decide(state, rules, batch, now) -> (state', verdicts)

Counters live in device-resident ``[flows, buckets, events]`` tensors; rules
are padded tensor tables (reloadable without retrace); admission inside a
batch uses masked prefix sums so a batch can never collectively overshoot a
threshold — strictly stronger than the reference's cross-thread TOCTOU.
"""

from sentinel_tpu.engine.config import EngineConfig
from sentinel_tpu.engine.rules import (
    RuleTable,
    ClusterFlowRule,
    DegradeRule,
    DegradeStrategy,
    build_rule_table,
    drain_pending_clear,
)
from sentinel_tpu.engine.state import EngineState, make_state
from sentinel_tpu.engine.decide import (
    RequestBatch,
    VerdictBatch,
    TokenStatus,
    alloc_fused_batch,
    alloc_packed_block,
    decide,
    make_batch,
    make_batch_into,
    pack_batch,
    pack_requests,
    pack_requests_into,
    pack_verdicts,
    unpack_requests,
    unpack_verdicts,
)

__all__ = [
    "alloc_fused_batch",
    "make_batch_into",
    "EngineConfig",
    "RuleTable",
    "ClusterFlowRule",
    "DegradeRule",
    "DegradeStrategy",
    "build_rule_table",
    "drain_pending_clear",
    "EngineState",
    "make_state",
    "RequestBatch",
    "VerdictBatch",
    "TokenStatus",
    "decide",
    "make_batch",
    "alloc_packed_block",
    "pack_batch",
    "pack_requests",
    "pack_requests_into",
    "unpack_requests",
    "pack_verdicts",
    "unpack_verdicts",
]
