"""Where JAX keeps compiled programs between runs.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
``benchmarks/`` scripts): the operator places the cache from outside with
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself; only when it is unset
does the program pick a directory, and then always the same one — the path
is part of the cache key's environment, so a name that moves (temp dir, pid,
timestamp) never hits.

Also the program's own compile counter: one process-wide ``jax.monitoring``
listener (:func:`install_compile_listener`) feeds ``ServerMetrics`` with every
backend compile, and says out loud when one ends while the service is
serving — a step compiled under the service lock stalls every dispatch
behind it.
"""

from __future__ import annotations

import os
import threading

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_LISTENER_LOCK = threading.Lock()
_LISTENING = False

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is configured; return its path.

    Call before the first compile. With ``JAX_COMPILATION_CACHE_DIR`` set
    this touches nothing; otherwise it points JAX at ``<checkout>/.jax_cache``
    (gitignored).
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _on_compile(event: str, duration_secs: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    from sentinel_tpu.metrics.server import server_metrics
    from sentinel_tpu.trace import ring as trace_ring

    ms = float(duration_secs) * 1e3
    after_warmup = server_metrics().record_compile(ms)
    if trace_ring.ARMED:
        trace_ring.record(trace_ring.COMPILE, aux=min(int(ms), 2**31 - 1))
    if after_warmup:
        from sentinel_tpu.core.log import record_log

        record_log.warning(
            "compiled %s in %.0f ms after warmup(): dispatches behind it "
            "waited", kw.get("fun_name", "?"), ms,
        )


def install_compile_listener() -> None:
    """Register the compile listener, once per process (idempotent; JAX
    keeps listeners for the life of the process)."""
    global _LISTENING
    with _LISTENER_LOCK:
        if _LISTENING:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _LISTENING = True
