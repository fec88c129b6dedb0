"""Static engine geometry. Everything here is baked into the jit trace;
changing it forces a recompile (rule *contents* are dynamic, sizes are not).
"""

from __future__ import annotations

from typing import NamedTuple


class EngineConfig(NamedTuple):
    """Sizes for the device tensors.

    Defaults mirror the reference cluster server: 1s interval / 10 buckets
    (``ServerFlowConfig.java:29-30``), 30k default namespace guard
    (``ServerFlowConfig.java:31``).
    """

    max_flows: int = 4096  # rule slots (F)
    max_namespaces: int = 64  # NS
    batch_size: int = 1024  # N — requests per device step
    bucket_ms: int = 100
    n_buckets: int = 10
    max_occupy_ratio: float = 1.0  # ServerFlowConfig.maxOccupyRatio
    exceed_count: float = 1.0  # ServerFlowConfig.exceedCount
    # in-batch prefix refinement passes — MUST be odd (odd counts guarantee
    # the admission mask is a subset of the sequential-greedy set; decide()
    # rejects even values)
    admission_refine_iters: int = 3
    # segment-prefix implementation for the flow axis: "matmul" ([N,N]
    # masked matmuls — cheap on the MXU for small N), "sort" (one argsort
    # per batch + blocked-matmul cumsums, wins beyond ~2k), or "auto"
    # (matmul ≤ 2048, sort above). Grouped host batches bypass this and use
    # the sort-free "grouped" impl (see decide()'s grouped flag).
    prefix_impl: str = "auto"

    @property
    def interval_ms(self) -> int:
        return self.bucket_ms * self.n_buckets


def named(fn, name: str):
    """``fn`` under ``name``: what ``jax.jit`` calls the program it builds
    from it (``jit_<name>`` in a device trace, ``fun_name`` in a compile
    event). A ``functools.partial`` has no name and reads ``jit__unknown``."""
    fn.__name__ = fn.__qualname__ = name
    return fn
