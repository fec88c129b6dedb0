"""ctypes loader + thin object wrappers over the native C API.

ctypes releases the GIL around every call, so under free-threaded Python the
native windows scale across threads the way the reference's LongAdders do —
the Python fallbacks serialize on the owning node's lock instead.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
# SENTINEL_NATIVE_SO overrides the library path — the ASan fuzz harness
# (`make -C native asan-check`) points it at the sanitizer build
_SO_PATH = os.environ.get(
    "SENTINEL_NATIVE_SO", os.path.join(_HERE, "_sentinel_native.so")
)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_load_error = ""  # why load() returned None — the compiler's output included


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    P, I32, I64, F64 = (
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_int64,
        ctypes.c_double,
    )
    sig = {
        "sn_window_create": ([I32, I32, I32], P),
        "sn_window_destroy": ([P], None),
        "sn_window_add": ([P, I64, I32, F64], None),
        "sn_window_sum": ([P, I64, I32], F64),
        "sn_window_snapshot": ([P, I64, ctypes.POINTER(F64)], None),
        "sn_window_prev_bucket": ([P, I64, I32], F64),
        "sn_window_min_ratio": ([P, I64, I32, I32], F64),
        "sn_window_start_at": ([P, I32], I64),
        "sn_window_count_at": ([P, I32, I32], F64),
        "sn_window_add_future": ([P, I64, I32, F64], None),
        "sn_window_future_waiting": ([P, I64, I32], F64),
        "sn_window_take_matured": ([P, I64, I32], F64),
        "sn_stat_pass": ([P, P, P, I64, F64], None),
        "sn_stat_event": ([P, P, I64, I32, F64], None),
        "sn_stat_rt_success": ([P, P, I64, F64, F64], None),
        "sn_stat_touched_sum": ([P, P, P, I64, I32], F64),
        "sn_tb_create": ([I32], P),
        "sn_tb_destroy": ([P], None),
        "sn_tb_reset": ([P, I32], None),
        "sn_tb_try_acquire": ([P, I32, I64, I32, F64, F64, I64], I32),
        "sn_pacer_create": ([I32], P),
        "sn_pacer_destroy": ([P], None),
        "sn_pacer_reset": ([P, I32], None),
        "sn_pacer_try_pass": ([P, I32, I64, I32, F64, I64], I64),
        "sn_batch_decode_req": (
            [
                ctypes.c_char_p, I32, ctypes.POINTER(I32),
                ctypes.POINTER(I64), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32,
            ],
            I32,
        ),
        "sn_batch_encode_rsp": (
            [
                I32, I32, ctypes.POINTER(ctypes.c_int8),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32,
            ],
            I32,
        ),
        # native TCP front door (sentinel_frontdoor.cpp)
        "sn_fd_create": ([ctypes.c_char_p, I32, I32], P),
        "sn_fd_port": ([P], I32),
        "sn_fd_stop": ([P], None),
        "sn_fd_destroy": ([P], None),
        # the frame columns end with f_rx_ns (i64: the frame's rx stamp),
        # and the last out-value is wake_ns (door spans, see the .cpp head)
        "sn_fd_wait_batch": (
            [
                P, I32, ctypes.POINTER(I64), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32, ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(I64), I32, ctypes.POINTER(I32),
                ctypes.POINTER(I64),
            ],
            I32,
        ),
        # flow OR param rows of one pull (codec rev 8, BATCH_PARAM_FLOW)
        # ... or of concurrency frames (rev 9: k_out = -1)
        "sn_fd_wait_any": (
            [
                P, I32, ctypes.POINTER(I64), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(I64), I32,
                I32, ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(I64), I32,
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I64),
            ],
            I32,
        ),
        "sn_fd_submit": (
            [
                P, I32, ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(I64),
                ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I64),
            ],
            None,
        ),
        "sn_fd_set_span_bounds": (
            [P, I32, ctypes.POINTER(I64), I32], None
        ),
        "sn_fd_span_stats": (
            [P, I32, ctypes.POINTER(ctypes.c_uint64), I32], I32
        ),
        "sn_fd_send": ([P, I32, I32, ctypes.c_char_p, I32], None),
        "sn_fd_next_control": (
            [
                P, ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32, ctypes.POINTER(I32),
                ctypes.POINTER(I64),
            ],
            I32,
        ),
        "sn_fd_stats": ([P, ctypes.POINTER(ctypes.c_uint64)], None),
        "sn_fd_set_idle_ttl": ([P, I64], None),
        "sn_fd_close_conn": ([P, I32, I32], None),
    }
    for name, (argtypes, restype) in sig.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    # shared-memory ring door (sentinel_shm.cpp) — resolved defensively so
    # a stale .so built before these exports existed still loads (the TCP
    # door and kernels keep working; ShmDoor raises with a rebuild hint)
    shm_sig = {
        "sn_shm_create": ([ctypes.c_char_p, I64, I32], P),
        "sn_shm_stop": ([P], None),
        "sn_shm_destroy": ([P], None),
        "sn_shm_wait_batch": (
            [
                P, I32, ctypes.POINTER(I64), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32, ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(ctypes.c_uint8), I32,
                ctypes.POINTER(I32),
            ],
            I32,
        ),
        "sn_shm_submit": (
            [
                P, I32, ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(I32),
                ctypes.POINTER(I32),
            ],
            None,
        ),
        "sn_shm_send": ([P, I32, I32, ctypes.c_char_p, I32], None),
        "sn_shm_next_control": (
            [
                P, ctypes.POINTER(I32), ctypes.POINTER(I32),
                ctypes.POINTER(ctypes.c_uint8), I32, ctypes.POINTER(I32),
                ctypes.POINTER(I64),
            ],
            I32,
        ),
        "sn_shm_close_conn": ([P, I32, I32], None),
        "sn_shm_stats": ([P, ctypes.POINTER(ctypes.c_uint64)], None),
        "sn_shm_echo_start": ([P], None),
        "sn_shm_echo_stop": ([P], None),
        # TCP-door echo mirror, shipped in the same rebuild as the shm
        # exports — resolved in this defensive block for the same reason
        "sn_fd_echo_start": ([P], None),
        "sn_fd_echo_stop": ([P], None),
        "sn_shm_client_create": ([ctypes.c_char_p, I32, I32, I32], P),
        "sn_shm_client_destroy": ([P], None),
        "sn_shm_client_send": ([P, ctypes.c_char_p, I32], I32),
        "sn_shm_client_recv": (
            [P, ctypes.POINTER(ctypes.c_uint8), I32, I32], I32
        ),
        "sn_shm_client_rtt": (
            [P, ctypes.c_char_p, I32, I32, ctypes.POINTER(I64)], I32
        ),
        "sn_shm_client_fuzz": ([P, ctypes.c_char_p, I32, I32], I32),
        "sn_shm_client_alive": ([P], I32),
    }
    try:
        for name, (argtypes, restype) in shm_sig.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = restype
        lib._sn_has_shm = True
    except AttributeError:
        lib._sn_has_shm = False
    # single PARAM_FLOW frames on the data plane (sentinel_frontdoor.cpp),
    # resolved defensively for the same reason: a library from before the
    # counter serves none there and counts none
    try:
        lib.sn_fd_param_single_frames.argtypes = [P]
        lib.sn_fd_param_single_frames.restype = ctypes.c_uint64
        lib._sn_has_param_singles = True
    except AttributeError:
        lib._sn_has_param_singles = False
    # flow prep (sentinel_native.cpp), resolved defensively for the same
    # reason. Every pointer goes in as an integer (``arr.ctypes.data``): a
    # ``data_as`` object costs microseconds each, ten times a dispatch
    try:
        lib.sn_flow_prep.argtypes = [
            P, P, I64, P, P, P, I64, I64, I64, I32, P, P, P,
        ]
        lib.sn_flow_prep.restype = I32
        lib._sn_has_flow_prep = True
    except AttributeError:
        lib._sn_has_flow_prep = False
    # param prep (sentinel_native.cpp), as flow prep
    try:
        lib.sn_param_prep.argtypes = [
            P, P, P, I64, P, I64, P, P, I64, P, P, P, I64, I64, I64, I32,
            I64, I32, I64, I64, P, P,
        ]
        lib.sn_param_prep.restype = None
        lib._sn_has_param_prep = True
    except AttributeError:
        lib._sn_has_param_prep = False
    # concurrent prep (sentinel_native.cpp), as flow prep
    try:
        lib.sn_concurrent_prep.argtypes = [
            P, P, I64, P, P, P, I64, I64, I64, P, I64, P, P,
        ]
        lib.sn_concurrent_prep.restype = I32
        lib._sn_has_concurrent_prep = True
    except AttributeError:
        lib._sn_has_concurrent_prep = False
    # the control lane's bell (sentinel_frontdoor.cpp; sn_shm_set_bell in
    # sentinel_shm.cpp), as flow prep
    try:
        U64 = ctypes.c_uint64
        lib.sn_bell_new.argtypes, lib.sn_bell_new.restype = [], P
        lib.sn_bell_free.argtypes, lib.sn_bell_free.restype = [P], None
        lib.sn_bell_ring.argtypes, lib.sn_bell_ring.restype = [P], None
        lib.sn_bell_wait.argtypes = [P, U64, I32, I32]
        lib.sn_bell_wait.restype = U64
        lib.sn_fd_set_bell.argtypes, lib.sn_fd_set_bell.restype = [P, P], None
        if lib._sn_has_shm:
            lib.sn_shm_set_bell.argtypes = [P, P]
            lib.sn_shm_set_bell.restype = None
        lib._sn_has_bell = True
    except AttributeError:
        lib._sn_has_bell = False
    return lib


def _stale() -> bool:
    """True when the autobuilt library is missing or older than any of its
    sources. The ``.so`` is gitignored but sits in working trees, so after a
    pull that touched ``native/src`` the file on disk is yesterday's build —
    it loads fine and only lacks the newer exports."""
    from sentinel_tpu.native.build import SOURCES

    if not os.path.exists(_SO_PATH):
        return True
    built = os.path.getmtime(_SO_PATH)
    return any(
        os.path.exists(src) and os.path.getmtime(src) > built
        for src in SOURCES
    )


def load() -> Optional[ctypes.CDLL]:
    """Load (once) the native library, building it first when it is missing
    or older than ``native/src/*.cpp``.

    The ``.so`` is a build artifact (gitignored), so first use on a clean
    tree compiles it with the ambient C++ toolchain (~seconds; same
    command as ``make -C native``). A failure degrades the optional
    accelerations (codecs, windows) to their pure-Python paths and is kept
    for :func:`require`, which the native doors call — a door asked for by
    name is never quietly swapped for another. Set
    ``SENTINEL_NATIVE_AUTOBUILD=0`` to disable building, or
    ``SENTINEL_NATIVE_SO`` to point at a prebuilt library (never built over).
    """
    global _lib, _load_failed, _load_error
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        autobuild = not (
            "SENTINEL_NATIVE_SO" in os.environ
            or os.environ.get("SENTINEL_NATIVE_AUTOBUILD") == "0"
        )
        if autobuild and _stale():
            import subprocess

            from sentinel_tpu.native.build import build

            try:
                build(verbose=False)
            except subprocess.CalledProcessError as e:
                _load_error = (
                    f"{' '.join(e.cmd)} exited {e.returncode}:\n{e.stderr}"
                )
            except (OSError, RuntimeError) as e:
                _load_error = f"{type(e).__name__}: {e}"
        if not _load_error and not os.path.exists(_SO_PATH):
            _load_error = f"{_SO_PATH} does not exist"
        if not _load_error:
            try:
                _lib = _configure(ctypes.CDLL(_SO_PATH))
            except OSError as e:
                _load_error = f"dlopen {_SO_PATH}: {e}"
        _load_failed = _lib is None
    return _lib


def require() -> ctypes.CDLL:
    """:func:`load`, or raise with the reason (build command and compiler
    output) when the library cannot be had."""
    lib = load()
    if lib is None:
        raise RuntimeError(f"native library not loadable: {_load_error}")
    return lib


def available() -> bool:
    return load() is not None


def shm_available() -> bool:
    """True when the loaded .so exports the shared-memory ring door (a
    stale artifact from an older tree loads fine but lacks the exports —
    rebuild with ``python -m sentinel_tpu.native.build``)."""
    lib = load()
    return lib is not None and bool(getattr(lib, "_sn_has_shm", False))


def flow_prep(snapshot, flow_ids, acq, pr, width: int, out=None):
    """One flow frame's host prep in ONE native pass with the GIL released
    (``sn_flow_prep``): slot lookup in ``snapshot`` (the token service's
    ``(sorted int64 keys, int32 slots)``), the stable ascending grouping and
    the padded request lines of the decide step's packed argument. Returns
    ``(slots, order, packed, uniform)``: ``slots`` in request order, ``order``
    None where they arrived ascending, ``uniform`` whether every acquire is
    the same; value for value and byte for byte what
    ``DefaultTokenService._lookup_from`` + ``_prep_batch`` give
    (``tests/test_native_prep.py``). ``packed`` is a fresh
    ``int32[4, width]`` (``engine.decide.pack_requests``'s form, clock 0)
    that only the caller holds, or ``out``, a frame's ``[4, width]`` view of
    a fused staging block, whose head line is then left alone. None where
    the library is absent or older than this entry: the caller preps in
    numpy."""
    lib = load()
    if lib is None or not lib._sn_has_flow_prep:
        return None
    import numpy as np

    keys, tab = snapshot
    flow_ids = np.ascontiguousarray(flow_ids, np.int64)
    acq = np.ascontiguousarray(acq, np.int32)
    pr = np.ascontiguousarray(pr, np.bool_)
    n = flow_ids.shape[0]
    packed = np.empty((4, width), np.int32) if out is None else out
    slots = np.empty(n, np.int32)
    order = np.empty(n, np.int64)
    flags = lib.sn_flow_prep(
        keys.ctypes.data, tab.ctypes.data, keys.shape[0],
        flow_ids.ctypes.data, acq.ctypes.data, pr.ctypes.data, n, width,
        packed.strides[0] // 4, out is None, slots.ctypes.data,
        order.ctypes.data, packed.ctypes.data,
    )
    return slots, None if flags & 1 else order, packed, bool(flags & 2)


def param_prep(snapshot, flow_ids, acq, hashes, bucket: int, geometry):
    """One chunk of whole hot-parameter requests (``hashes int64[n, k]``)
    prepped in ONE native pass with the GIL released (``sn_param_prep``):
    the rule and item look-ups in ``snapshot`` (the token service's
    ``_param_tables``: six C-contiguous arrays), the sketch's cell indices
    and the param step's packed argument. ``geometry`` is ``(depth,
    cell_width, slim_depth, slim_width, slim_salt)`` of the service's
    ``ParamConfig``, ``slim_depth`` 0 where the twin is off. Returns
    ``(req_slot int32[n], packed)``, byte for byte what
    ``DefaultTokenService._param_rows`` + ``engine.param.pack_param_rows``
    give (``tests/test_native_param_prep.py``); ``packed`` is a fresh
    ``int32[4 + depth + slim_depth, bucket]`` with the clock at 0 that only
    the caller holds. None where the library is absent or older than this
    entry: the caller preps in numpy."""
    lib = load()
    if lib is None or not lib._sn_has_param_prep:
        return None
    import numpy as np

    fids, slots, counts, item_hashes, item_keys, item_thr = snapshot
    flow_ids = np.ascontiguousarray(flow_ids, np.int64)
    acq = np.ascontiguousarray(acq, np.int32)
    hashes = np.ascontiguousarray(hashes, np.int64)
    n, k = hashes.shape
    depth, width, slim_depth, slim_width, slim_salt = geometry
    if flow_ids.shape != (n,) or acq.shape != (n,) or not (
            0 < n * k <= bucket and bucket >= 3):
        raise ValueError(
            f"param_prep: {flow_ids.shape} ids, {acq.shape} acquires and "
            f"{hashes.shape} hashes do not make a chunk of bucket {bucket}")
    req_slot = np.empty(n, np.int32)
    packed = np.empty((4 + depth + slim_depth, bucket), np.int32)
    lib.sn_param_prep(
        fids.ctypes.data, slots.ctypes.data, counts.ctypes.data,
        fids.shape[0], item_hashes.ctypes.data, item_hashes.shape[0],
        item_keys.ctypes.data, item_thr.ctypes.data, item_keys.shape[0],
        flow_ids.ctypes.data, acq.ctypes.data, hashes.ctypes.data, n, k,
        bucket, depth, width, slim_depth, slim_width, slim_salt,
        req_slot.ctypes.data, packed.ctypes.data,
    )
    return req_slot, packed


def concurrent_prep(lookup, ids, counts, is_release, max_tokens: int, plan):
    """One concurrency dispatch's rows prepped in ONE native pass with the
    GIL released (``sn_concurrent_prep``): the two kinds split in arrival
    order, the acquires' flows looked up in ``lookup`` (the plane's
    ``(sorted int64 flow ids, int32 slots)``), the releases' token ids split
    by ``max_tokens`` (``engine.concurrent.split_token_ids``), each step's
    runs sorted (acquires by slot, releases by token id, ties in arrival
    order) and its packed argument written. ``plan`` is
    ``ConcurrentPlane.step_plan``'s: ``(a_lo, a_hi, r_lo, r_hi, bucket)`` a
    step. Returns the ``parts`` of ``ConcurrentPlane.prep``, ``(bucket,
    packed, acq_rows, rel_rows)`` a step, byte for byte what its numpy body
    gives (``tests/test_native_concurrent_prep.py``); every ``packed`` is a
    fresh ``int32[5, bucket]`` with the clock at 0 that only the caller
    holds. None where the library is absent or older than this entry: the
    caller preps in numpy."""
    lib = load()
    if lib is None or not lib._sn_has_concurrent_prep:
        return None
    import numpy as np

    keys, tab = lookup
    ids = np.ascontiguousarray(ids, np.int64)
    counts = np.ascontiguousarray(counts, np.int32)
    is_release = np.ascontiguousarray(is_release, np.bool_)
    n = ids.shape[0]
    n_acq = plan[-1][1]  # the acquires' last chunk is the last step's
    n_rel = n - n_acq
    if counts.shape != (n,) or is_release.shape != (n,) or n_rel < 0:
        raise ValueError(
            f"concurrent_prep: {ids.shape} ids, {counts.shape} counts and "
            f"{is_release.shape} kinds against a plan of {n_acq} acquires")
    acq_rows, rel_rows = np.empty(n_acq, np.int64), np.empty(n_rel, np.int64)
    parts, steps = [], []
    for a_lo, a_hi, r_lo, r_hi, bucket in plan:
        if not (0 <= a_lo <= a_hi <= n_acq and 0 <= r_lo <= r_hi <= n_rel
                and bucket >= max(a_hi - a_lo, r_hi - r_lo, 3)):
            raise ValueError(
                f"concurrent_prep: step {(a_lo, a_hi, r_lo, r_hi, bucket)} "
                f"of {n_acq} acquires and {n_rel} releases")
        packed = np.empty((5, bucket), np.int32)
        parts.append((bucket, packed, acq_rows[a_lo:a_hi],
                      rel_rows[r_lo:r_hi]))
        steps.append((a_lo, a_hi, r_lo, r_hi, bucket, packed.ctypes.data))
    steps = np.array(steps, np.int64)
    if lib.sn_concurrent_prep(
            keys.ctypes.data, tab.ctypes.data, keys.shape[0], ids.ctypes.data,
            counts.ctypes.data, is_release.ctypes.data, n, n_acq, max_tokens,
            steps.ctypes.data, len(parts), acq_rows.ctypes.data,
            rel_rows.ctypes.data):
        raise ValueError(
            f"concurrent_prep: the plan's {n_acq} acquires are not the "
            f"frame's")
    return parts


class Bell:
    """The control lane's bell (``sn_bell_*``, ``sentinel_frontdoor.cpp``):
    a mutex, a condition variable of its own and a generation counter. A
    door handed the bell (``set_bell``) rings it after every push to its
    control queue; the server's one control thread sleeps in :meth:`wait`
    with the GIL released. Made by :func:`control_bell`."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        self._h = lib.sn_bell_new()
        if not self._h:
            raise MemoryError("sn_bell_new")

    def wait(self, seen: int, timeout_ms: int, settle_ms: int = 0) -> int:
        """Block until the generation is no longer ``seen`` or
        ``timeout_ms`` passed; the generation found, which is the caller's
        next ``seen``. A ring since the caller read ``seen`` returns at
        once: no wake-up is lost between a look at the queues and the wait.
        A wait that a ring ended stays asleep ``settle_ms`` more (still off
        the GIL) before it returns; one that timed out does not.
        ``timeout_ms`` 0 only reads the generation."""
        return self._lib.sn_bell_wait(self._h, seen, timeout_ms, settle_ms)

    def ring(self) -> None:
        self._lib.sn_bell_ring(self._h)

    def __del__(self):
        # a door keeps the bell it was handed (``set_bell``), so no door's
        # IO thread is left to ring a freed one
        h = getattr(self, "_h", None)
        if h:
            self._lib.sn_bell_free(h)
            self._h = None


def control_bell() -> Optional[Bell]:
    """A new :class:`Bell`. None where the library is absent or older than
    the entry: the control thread then polls its doors."""
    lib = load()
    if lib is None or not lib._sn_has_bell:
        return None
    return Bell(lib)


def batch_decode_req(payload: bytes):
    """BATCH_FLOW request payload → (xid, flow_ids int64[N], counts int32[N],
    prios bool[N]); None when the native lib is absent; raises ValueError on
    a malformed frame (mirrors the numpy codec's behavior)."""
    lib = load()
    if lib is None:
        return None
    import numpy as np

    max_n = max((len(payload) - 7) // 13, 0)
    xid = ctypes.c_int32()
    flow_ids = np.empty(max_n, np.int64)
    counts = np.empty(max_n, np.int32)
    prios = np.empty(max_n, np.uint8)
    n = lib.sn_batch_decode_req(
        payload, len(payload), ctypes.byref(xid),
        flow_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        prios.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        max_n,
    )
    if n < 0:
        raise ValueError("malformed BATCH_FLOW frame")
    return (
        int(xid.value), flow_ids[:n], counts[:n], prios[:n].astype(bool)
    )


def batch_encode_rsp(xid: int, status, remaining, wait_ms):
    """(status int8[N], remaining int32[N], wait int32[N]) → full response
    frame bytes (length prefix included); None when the lib is absent."""
    lib = load()
    if lib is None:
        return None
    import numpy as np

    status = np.ascontiguousarray(status, np.int8)
    remaining = np.ascontiguousarray(remaining, np.int32)
    wait_ms = np.ascontiguousarray(wait_ms, np.int32)
    n = status.shape[0]
    out = np.empty(2 + 7 + n * 9, np.uint8)
    wrote = lib.sn_batch_encode_rsp(
        xid, n,
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        remaining.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        wait_ms.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.shape[0],
    )
    if wrote < 0:
        raise ValueError("batch too large for one frame")
    return out[:wrote].tobytes()


class NativeWindow:
    """Sliding window backed by the native lib — drop-in for
    ``local.stat.HostWindow`` plus the future/occupy ops."""

    __slots__ = ("_lib", "_h", "bucket_ms", "n_buckets", "n_channels",
                 "interval_ms")

    def __init__(self, bucket_ms: int, n_buckets: int, n_channels: int):
        lib = require()
        self._lib = lib
        self._h = lib.sn_window_create(bucket_ms, n_buckets, n_channels)
        if not self._h:
            raise MemoryError("sn_window_create failed")
        self.bucket_ms = bucket_ms
        self.n_buckets = n_buckets
        self.n_channels = n_channels
        self.interval_ms = bucket_ms * n_buckets

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.sn_window_destroy(h)
            self._h = None

    def add(self, now: int, chan: int, n: float = 1.0) -> None:
        self._lib.sn_window_add(self._h, now, chan, n)

    def sum(self, now: int, chan: int) -> float:
        return self._lib.sn_window_sum(self._h, now, chan)

    def qps(self, now: int, chan: int) -> float:
        return self.sum(now, chan) * 1000.0 / self.interval_ms

    def snapshot(self, now: int) -> list:
        out = (ctypes.c_double * self.n_channels)()
        self._lib.sn_window_snapshot(self._h, now, out)
        return list(out)

    def previous_bucket(self, now: int, chan: int) -> float:
        return self._lib.sn_window_prev_bucket(self._h, now, chan)

    def min_ratio(self, now: int, num_chan: int, den_chan: int) -> float:
        return self._lib.sn_window_min_ratio(self._h, now, num_chan, den_chan)

    def start_at(self, b: int) -> int:
        return self._lib.sn_window_start_at(self._h, b)

    def count_at(self, b: int, chan: int) -> float:
        return self._lib.sn_window_count_at(self._h, b, chan)

    # future/occupy ops (FutureWindow analog; use a dedicated instance)
    def add_future(self, future_time: int, n: float, chan: int = 0) -> None:
        self._lib.sn_window_add_future(self._h, future_time, chan, n)

    def future_waiting(self, now: int, chan: int = 0) -> float:
        return self._lib.sn_window_future_waiting(self._h, now, chan)

    def take_matured(self, now: int, chan: int = 0) -> float:
        return self._lib.sn_window_take_matured(self._h, now, chan)


class NativeTokenBuckets:
    """Array of token buckets (hot-param local QPS mode)."""

    __slots__ = ("_lib", "_h", "n_slots")

    def __init__(self, n_slots: int):
        lib = require()
        self._lib = lib
        self._h = lib.sn_tb_create(n_slots)
        if not self._h:
            raise MemoryError("sn_tb_create failed")
        self.n_slots = n_slots

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.sn_tb_destroy(h)
            self._h = None

    def reset(self, slot: int) -> None:
        self._lib.sn_tb_reset(self._h, slot)

    def try_acquire(
        self,
        slot: int,
        now: int,
        acquire: int,
        count: float,
        burst: float,
        interval_ms: int,
    ) -> bool:
        return bool(
            self._lib.sn_tb_try_acquire(
                self._h, slot, now, acquire, count, burst, interval_ms
            )
        )


class NativePacerArray:
    """Array of leaky-bucket pacers (RateLimiter behavior)."""

    __slots__ = ("_lib", "_h", "n_slots")

    def __init__(self, n_slots: int):
        lib = require()
        self._lib = lib
        self._h = lib.sn_pacer_create(n_slots)
        if not self._h:
            raise MemoryError("sn_pacer_create failed")
        self.n_slots = n_slots

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.sn_pacer_destroy(h)
            self._h = None

    def reset(self, slot: int) -> None:
        self._lib.sn_pacer_reset(self._h, slot)

    def try_pass(
        self,
        slot: int,
        now: int,
        acquire: int,
        count_per_sec: float,
        max_queue_ms: int,
    ) -> int:
        """wait-ms to sleep (0 = immediate) or -1 = block."""
        return int(
            self._lib.sn_pacer_try_pass(
                self._h, slot, now, acquire, count_per_sec, max_queue_ms
            )
        )


class Frontdoor:
    """The native epoll TCP front door (``sentinel_frontdoor.cpp``).

    One IO thread owns sockets, framing, decode, and response writes; Python
    pulls whole request batches with :meth:`wait_batch` (GIL released while
    blocked), runs the device step, and answers with :meth:`submit`.
    Every frame that asks for verdicts is data plane: the reference
    client's single frames (FLOW, PARAM_FLOW, CONCURRENT_ACQUIRE / _RELEASE)
    as one-row frames beside the batch frames of their arena, each answered
    in its own layout by :meth:`submit`. Control-plane frames (PING, a
    PARAM_FLOW frame with no value, replication, moves, leases, reports)
    surface through :meth:`next_control`; replies go back via :meth:`send`.

    Spans, all on ``time.monotonic_ns()``'s clock: a data frame is stamped
    when the ``recv()`` that completed it returned. A pull hands the stamps
    out as the frames' sixth column (``f_rx_ns``) beside ``wake_ns``, taken
    in C just before the pull returns to ``ctypes``; :meth:`submit` hands
    them back, and the IO thread closes each frame's span when ``send()``
    has taken the last byte of its reply. :meth:`span_stats` reads the three
    histograms the door counts where the spans end (:data:`SPANS`).
    """

    CTRL_FRAME, CTRL_OPEN, CTRL_CLOSE = 0, 1, 2
    # ``f_type`` of the batch frames of a concurrency pull (codec rev 9);
    # every ``f_type`` is the wire's own type byte (``protocol.MsgType``),
    # the reference client's single frames (types 1-4) included
    TYPE_BATCH_ACQUIRE, TYPE_BATCH_RELEASE = 28, 29
    # rx -> pull about to return; submit entered -> last byte sent; rx ->
    # last byte sent. The order of sn_fd_span_stats.
    SPANS = ("door_in_ms", "door_out_ms", "door_residence_ms")
    MAX_SPAN_BOUNDS = 128  # kMaxSpanBounds of sentinel_frontdoor.cpp

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 arena_cap: int = 65536):
        import numpy as np

        lib = require()
        self._lib = lib
        # the arena must fit at least one max-size frame or a full frame
        # could never be admitted and its connection would park forever
        # (MAX_BATCH_PER_FRAME is derived from the wire layout in
        # protocol.py, the single source of truth the C++ codec mirrors)
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        arena_cap = max(arena_cap, MAX_BATCH_PER_FRAME)
        # the C side binds with inet_addr (IPv4 literals only) — resolve
        # names like "localhost" here so the API matches the asyncio server
        if host:
            import socket as _socket

            host = _socket.gethostbyname(host)
        self._h = lib.sn_fd_create(host.encode(), port, arena_cap)
        if not self._h:
            raise OSError(f"native front door failed to bind {host}:{port}")
        self.port = int(lib.sn_fd_port(self._h))
        self.arena_cap = arena_cap
        # batch buffers are per-THREAD (threading.local): multiple
        # dispatcher threads may call wait_batch concurrently, and each
        # result stays valid until that same thread's next call
        self._tls = threading.local()
        self._ctrl_buf = ctypes.create_string_buffer(70000)
        self._ctrl_lock = threading.Lock()
        self.last_control_ns = 0  # see next_control
        self._stopped = False

    def _bufs(self):
        import numpy as np

        b = getattr(self._tls, "bufs", None)
        if b is None:
            cap = self.arena_cap
            b = dict(
                ids=np.empty(cap, np.int64),
                counts=np.empty(cap, np.int32),
                prios=np.empty(cap, np.uint8),
                f_fd=np.empty(cap, np.int32),
                f_gen=np.empty(cap, np.int32),
                f_xid=np.empty(cap, np.int32),
                f_n=np.empty(cap, np.int32),
                f_type=np.empty(cap, np.uint8),
                f_rx_ns=np.empty(cap, np.int64),
                wake_ns=np.zeros(1, np.int64),
            )
            self._tls.bufs = b
        return b

    def _span_ptrs(self, staging: dict):
        """The ``f_rx_ns`` column and the ``wake_ns`` cell of a staging
        block, or nulls for a block without them (the door then neither
        stamps the pull nor counts its ``door_in_ms``)."""
        ptrs = staging.get("_span_ptrs")
        if ptrs is None:
            # made once per block (blocks are recycled; ``data_as`` costs
            # microseconds) and kept beside the arrays they point into
            rx, wake = staging.get("f_rx_ns"), staging.get("wake_ns")
            ptrs = staging["_span_ptrs"] = (
                None if rx is None else self._ptr(rx, ctypes.c_int64),
                None if wake is None else self._ptr(wake, ctypes.c_int64),
            )
        return ptrs

    def _ptr(self, arr, ctype):
        return arr.ctypes.data_as(ctypes.POINTER(ctype))

    def wait_batch(self, timeout_ms: int = 100, max_n: Optional[int] = None):
        """Block for data-plane requests. Returns ``None`` on timeout, else
        ``(ids, counts, prios, frames)`` where the first three are int64/
        int32/bool views in request order and ``frames`` is the opaque
        per-frame metadata to hand back to :meth:`submit`. ``max_n`` bounds
        one pull (whole frames only, so it is clamped to at least one
        max-size frame); the remainder stays queued for the next pull."""
        if max_n is None:
            max_n = self.arena_cap
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        max_n = min(max(int(max_n), MAX_BATCH_PER_FRAME), self.arena_cap)
        b = self._bufs()
        n_frames = ctypes.c_int32()
        n = self._lib.sn_fd_wait_batch(
            self._h, timeout_ms,
            self._ptr(b["ids"], ctypes.c_int64),
            self._ptr(b["counts"], ctypes.c_int32),
            self._ptr(b["prios"], ctypes.c_uint8),
            max_n,
            self._ptr(b["f_fd"], ctypes.c_int32),
            self._ptr(b["f_gen"], ctypes.c_int32),
            self._ptr(b["f_xid"], ctypes.c_int32),
            self._ptr(b["f_n"], ctypes.c_int32),
            self._ptr(b["f_type"], ctypes.c_uint8),
            self._ptr(b["f_rx_ns"], ctypes.c_int64),
            self.arena_cap, ctypes.byref(n_frames),
            self._ptr(b["wake_ns"], ctypes.c_int64),
        )
        if n <= 0:
            return None
        k = n_frames.value
        frames = (
            b["f_fd"][:k], b["f_gen"][:k], b["f_xid"][:k], b["f_n"][:k],
            b["f_type"][:k], b["f_rx_ns"][:k],
        )
        return (
            b["ids"][:n], b["counts"][:n],
            b["prios"][:n].astype(bool), frames,
        )

    def wait_batch_into(self, staging: dict, timeout_ms: int = 100,
                        max_n: Optional[int] = None):
        """:meth:`wait_batch`, but decoded rows land directly in the
        caller's ``staging`` arrays (same keys/dtypes as :meth:`_bufs`)
        instead of thread-local buffers — the zero-copy intake path: the
        IO thread's arena is memcpy'd once into a recycled staging block
        and never touched by the allocator again. Returns ``None`` on
        timeout, else ``(n, k)`` row/frame counts; the caller owns slicing
        views out of ``staging`` and keeping the block alive until the
        verdicts for those rows have been submitted. ``max_n`` additionally
        clamps to the staging row capacity, and the frame-array length
        bounds how many frames one pull may take (the remainder stays
        queued). A block that has them gets the frames' rx stamps in
        ``f_rx_ns[:k]`` and the pull's wake stamp in ``wake_ns[0]``."""
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        cap = int(staging["ids"].shape[0])
        max_f = int(staging["f_fd"].shape[0])
        if max_n is None:
            max_n = cap
        max_n = min(
            max(int(max_n), MAX_BATCH_PER_FRAME), cap, self.arena_cap
        )
        n_frames = ctypes.c_int32()
        rx, wake = self._span_ptrs(staging)
        n = self._lib.sn_fd_wait_batch(
            self._h, timeout_ms,
            self._ptr(staging["ids"], ctypes.c_int64),
            self._ptr(staging["counts"], ctypes.c_int32),
            self._ptr(staging["prios"], ctypes.c_uint8),
            max_n,
            self._ptr(staging["f_fd"], ctypes.c_int32),
            self._ptr(staging["f_gen"], ctypes.c_int32),
            self._ptr(staging["f_xid"], ctypes.c_int32),
            self._ptr(staging["f_n"], ctypes.c_int32),
            self._ptr(staging["f_type"], ctypes.c_uint8),
            rx, max_f, ctypes.byref(n_frames), wake,
        )
        if n <= 0:
            return None
        return n, n_frames.value

    def wait_any_into(self, staging: dict, timeout_ms: int = 100,
                      max_n: Optional[int] = None):
        """:meth:`wait_batch_into` for a host that serves hot-parameter
        and concurrency rows too: one pull is flow rows, the rows of
        BATCH_PARAM_FLOW frames, or the rows of BATCH_CONCURRENT_ACQUIRE /
        _RELEASE frames, never two kinds (the door hands out whichever
        arrived first). Returns ``None`` on timeout, else ``(n, frames,
        k)``: ``k`` is 0 for a flow pull, the values per request of a
        param pull, whose hashes then fill ``staging["hashes"][:n * k]``
        request-major (a pull takes frames of one ``k``, single PARAM_FLOW
        frames and BATCH_PARAM_FLOW frames alike, and at most as many
        values as that array holds), or -1 for a concurrency pull: a
        connection-ordered run of frames, of which those with an ``f_type``
        of CONCURRENT_RELEASE (4) or :data:`TYPE_BATCH_RELEASE` carry a
        token id a row in ``ids``. The
        stamps as :meth:`wait_batch_into` leaves them."""
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        cap = int(staging["ids"].shape[0])
        if max_n is None:
            max_n = cap
        max_n = min(
            max(int(max_n), MAX_BATCH_PER_FRAME), cap, self.arena_cap
        )
        n_frames = ctypes.c_int32()
        k = ctypes.c_int32()
        rx, wake = self._span_ptrs(staging)
        n = self._lib.sn_fd_wait_any(
            self._h, timeout_ms,
            self._ptr(staging["ids"], ctypes.c_int64),
            self._ptr(staging["counts"], ctypes.c_int32),
            self._ptr(staging["prios"], ctypes.c_uint8),
            self._ptr(staging["hashes"], ctypes.c_int64),
            max_n, int(staging["hashes"].shape[0]),
            self._ptr(staging["f_fd"], ctypes.c_int32),
            self._ptr(staging["f_gen"], ctypes.c_int32),
            self._ptr(staging["f_xid"], ctypes.c_int32),
            self._ptr(staging["f_n"], ctypes.c_int32),
            self._ptr(staging["f_type"], ctypes.c_uint8),
            rx, int(staging["f_fd"].shape[0]), ctypes.byref(n_frames),
            ctypes.byref(k), wake,
        )
        if n <= 0:
            return None
        return n, n_frames.value, k.value

    def submit(self, frames, status, remaining, wait_ms,
               token_ids=None) -> None:
        """Encode + send verdict frames for a ``wait_batch`` result. A
        frames tuple with the sixth column (``f_rx_ns``) hands the frames'
        rx stamps back, and the door counts ``door_out_ms`` and
        ``door_residence_ms`` of each frame whose stamp is not 0 when its
        reply has gone out; five columns count nothing. ``token_ids``
        (int64, a row each) fills the wider reply rows of
        BATCH_CONCURRENT_ACQUIRE frames and the reply of a single
        CONCURRENT_ACQUIRE frame; None writes 0 there."""
        import numpy as np

        # every array binds to a local: an unnamed ascontiguousarray copy
        # would be freed the moment _ptr() returns, leaving sn_fd_submit
        # reading freed memory whenever a caller passes a non-contiguous
        # or wrongly-typed array
        f_fd, f_gen, f_xid, f_n, f_type = frames[:5]
        f_rx_ns = (
            np.ascontiguousarray(frames[5], np.int64)
            if len(frames) > 5 else None
        )
        f_fd = np.ascontiguousarray(f_fd, np.int32)
        if f_rx_ns is not None and len(f_rx_ns) != len(f_fd):
            raise ValueError("one rx stamp per frame")
        f_gen = np.ascontiguousarray(f_gen, np.int32)
        f_xid = np.ascontiguousarray(f_xid, np.int32)
        f_n = np.ascontiguousarray(f_n, np.int32)
        f_type = np.ascontiguousarray(f_type, np.uint8)
        status = np.ascontiguousarray(status, np.int8)
        remaining = np.ascontiguousarray(remaining, np.int32)
        wait_ms = np.ascontiguousarray(wait_ms, np.int32)
        if token_ids is not None:
            token_ids = np.ascontiguousarray(token_ids, np.int64)
        self._lib.sn_fd_submit(
            self._h, len(f_fd),
            self._ptr(f_fd, ctypes.c_int32),
            self._ptr(f_gen, ctypes.c_int32),
            self._ptr(f_xid, ctypes.c_int32),
            self._ptr(f_n, ctypes.c_int32),
            self._ptr(f_type, ctypes.c_uint8),
            None if f_rx_ns is None else self._ptr(f_rx_ns, ctypes.c_int64),
            self._ptr(status, ctypes.c_int8),
            self._ptr(remaining, ctypes.c_int32),
            self._ptr(wait_ms, ctypes.c_int32),
            None if token_ids is None
            else self._ptr(token_ids, ctypes.c_int64),
        )

    def submit_many(self, frames_list, status, remaining, wait_ms,
                    token_ids=None) -> None:
        """Answer SEVERAL ``wait_batch`` pulls with one native call.

        ``frames_list`` holds each pull's frame-metadata tuple, in the same
        order their requests are concatenated in the verdict arrays. One
        ``sn_fd_submit`` call means one outbox lock acquisition and one IO
        wakeup for the whole fused group, and the C++ scatter encode can
        group consecutive same-connection frames ACROSS pull boundaries
        into single per-writer buffers."""
        import numpy as np

        if len(frames_list) == 1:
            return self.submit(frames_list[0], status, remaining, wait_ms,
                               token_ids)
        merged = tuple(
            np.concatenate([np.asarray(fr[i]) for fr in frames_list])
            for i in range(min(len(fr) for fr in frames_list))
        )
        self.submit(merged, status, remaining, wait_ms, token_ids)

    def send(self, fd: int, gen: int, frame: bytes) -> None:
        self._lib.sn_fd_send(self._h, fd, gen, frame, len(frame))

    def set_idle_ttl(self, ttl_ms: int) -> None:
        """Enable the IO-thread idle sweep (0 disables)."""
        self._lib.sn_fd_set_idle_ttl(self._h, int(ttl_ms))

    def close_conn(self, fd: int, gen: int) -> None:
        self._lib.sn_fd_close_conn(self._h, fd, gen)

    def set_bell(self, bell: Bell) -> None:
        """Ring ``bell`` after every push to this door's control queue
        (what :meth:`next_control` pops). The door keeps the bell alive."""
        self._lib.sn_fd_set_bell(self._h, bell._h)
        self._bell = bell

    def next_control(self):
        """``None`` or ``(kind, fd, gen, payload bytes)``. ``last_control_ns``
        is then the ``time.monotonic_ns()`` at which the IO thread queued
        that frame (0 for an open or close event): how long it waited for
        its consumer."""
        fd = ctypes.c_int32()
        gen = ctypes.c_int32()
        ln = ctypes.c_int32()
        t_ns = ctypes.c_int64()
        with self._ctrl_lock:
            kind = self._lib.sn_fd_next_control(
                self._h, ctypes.byref(fd), ctypes.byref(gen),
                ctypes.cast(self._ctrl_buf, ctypes.POINTER(ctypes.c_uint8)),
                len(self._ctrl_buf), ctypes.byref(ln), ctypes.byref(t_ns),
            )
            if kind < 0:
                return None
            self.last_control_ns = t_ns.value
            # string_at copies only the written bytes — .raw would build
            # the full 70KB buffer as bytes for every 7-byte PING
            payload = (
                ctypes.string_at(self._ctrl_buf, ln.value)
                if ln.value > 0 else b""
            )
        return kind, fd.value, gen.value, payload

    def stats(self):
        """Counters are independently monotonic (relaxed atomics read
        without a common lock): the dict is NOT a consistent cross-counter
        snapshot — e.g. ``frames_in`` may already include a frame whose
        rows are not yet in ``requests_in``. Consumers diffing two reads
        (bench occupancy math) must clamp derived deltas at zero."""
        import numpy as np

        out = np.zeros(4, np.uint64)
        self._lib.sn_fd_stats(
            self._h, self._ptr(out, ctypes.c_uint64)
        )
        stats = {
            "frames_in": int(out[0]), "requests_in": int(out[1]),
            "bytes_in": int(out[2]), "bytes_out": int(out[3]),
        }
        if getattr(self._lib, "_sn_has_param_singles", False):
            # single PARAM_FLOW frames decoded into the param arena
            stats["param_single_frames_in"] = int(
                self._lib.sn_fd_param_single_frames(self._h)
            )
        return stats

    def set_span_bounds(self, name: str, bounds_ms) -> None:
        """Hand the door the bucket bounds (ms, ascending; at most
        :data:`MAX_SPAN_BOUNDS`) of the host's histogram for span histogram ``name`` (one of
        :data:`SPANS`): once, at start. Without them a span still counts
        into count, sum and max."""
        import numpy as np

        ns = np.ascontiguousarray(
            np.rint(np.asarray(bounds_ms, np.float64) * 1e6), np.int64
        )
        if len(ns) > self.MAX_SPAN_BOUNDS:
            raise ValueError(
                f"{len(ns)} span bounds; the door holds "
                f"{self.MAX_SPAN_BOUNDS}"
            )
        self._lib.sn_fd_set_span_bounds(
            self._h, self.SPANS.index(name),
            self._ptr(ns, ctypes.c_int64), len(ns),
        )

    def span_stats(self) -> dict:
        """``{name: (count, sum_ms, max_ms, bucket counts)}`` for each of
        :data:`SPANS`, cumulative since the door started; the bucket counts
        are per bucket of the bounds handed over, the last the overflow.
        Relaxed atomics like :meth:`stats`: no one consistent snapshot."""
        import numpy as np

        out = {}
        buf = np.zeros(self.MAX_SPAN_BOUNDS + 4, np.uint64)
        for which, name in enumerate(self.SPANS):
            n = self._lib.sn_fd_span_stats(
                self._h, which, self._ptr(buf, ctypes.c_uint64), len(buf)
            )
            out[name] = (
                int(buf[0]), float(buf[1]) * 1e-6, float(buf[2]) * 1e-6,
                buf[3:n].astype(np.int64),
            )
        return out

    def echo_start(self) -> None:
        """Bench/test helper: a pure-C wait→all-GRANTED-submit loop — the
        TCP mirror of :meth:`ShmDoor.echo_start`, so both doors' transport
        host cost is measured behind an identical serving loop."""
        if not getattr(self._lib, "_sn_has_shm", False):
            raise RuntimeError(
                "native library predates the door echo exports — rebuild "
                "with `python -m sentinel_tpu.native.build`"
            )
        self._lib.sn_fd_echo_start(self._h)

    def echo_stop(self) -> None:
        if getattr(self._lib, "_sn_has_shm", False):
            self._lib.sn_fd_echo_stop(self._h)

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self._lib.sn_fd_stop(self._h)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            try:
                self.stop()
            except Exception:
                pass
            self._lib.sn_fd_destroy(h)
            self._h = None


class ShmDoor:
    """The shared-memory ring front door (``sentinel_shm.cpp``).

    Same batch contract as :class:`Frontdoor` — ``wait_batch_into`` /
    ``submit`` / ``submit_many`` / ``next_control`` / ``send`` — so the
    server's intake, reply, and control lanes drive either door through
    one code path. The "fd" of a frame is the client segment id; replies
    are scatter-encoded straight into that client's response ring by the
    C side. A C++ poller thread (spin-then-sleep on a shared futex
    doorbell) replaces the epoll IO thread; co-located clients attach by
    dropping a segment file into ``shm_dir``.
    """

    CTRL_FRAME, CTRL_OPEN, CTRL_CLOSE = 0, 1, 2

    def __init__(self, shm_dir: str, arena_cap: int = 65536,
                 spin_us: Optional[int] = None):
        # Adaptive spin default: on a single-core host the spinner only
        # burns the peer's timeslice (measured: RTT ~= 2x the spin window),
        # so go straight to the futex; with spare cores a short spin dodges
        # the syscall entirely in the steady state.
        if spin_us is None:
            spin_us = 0 if (os.cpu_count() or 1) <= 1 else 100
        lib = require()
        if not getattr(lib, "_sn_has_shm", False):
            raise RuntimeError(
                "native library predates the shm door — rebuild with "
                "`python -m sentinel_tpu.native.build`"
            )
        self._lib = lib
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        arena_cap = max(arena_cap, MAX_BATCH_PER_FRAME)
        self._h = lib.sn_shm_create(
            os.fsencode(shm_dir), arena_cap, int(spin_us)
        )
        if not self._h:
            raise OSError(f"shm door failed to initialize in {shm_dir!r}")
        self.dir = shm_dir
        self.arena_cap = arena_cap
        self.port = -1  # no TCP endpoint; keeps door-agnostic logging sane
        self._tls = threading.local()
        self._ctrl_buf = ctypes.create_string_buffer(70000)
        self._ctrl_lock = threading.Lock()
        self.last_control_ns = 0  # see next_control
        self._stopped = False

    _ptr = Frontdoor._ptr
    _bufs = Frontdoor._bufs

    def wait_any_into(self, staging: dict, timeout_ms: int = 100,
                      max_n: Optional[int] = None):
        """The intake lane's pull; the ring carries flow rows only, so
        ``k`` is always 0 (see :meth:`Frontdoor.wait_any_into`)."""
        got = self.wait_batch_into(staging, timeout_ms, max_n)
        return None if got is None else (got[0], got[1], 0)

    # identical pull/answer surface — the ctypes marshaling only differs in
    # the export name, so rebind the TCP door's methods over sn_shm_*
    def wait_batch_into(self, staging: dict, timeout_ms: int = 100,
                        max_n: Optional[int] = None):
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        cap = int(staging["ids"].shape[0])
        max_f = int(staging["f_fd"].shape[0])
        if max_n is None:
            max_n = cap
        max_n = min(
            max(int(max_n), MAX_BATCH_PER_FRAME), cap, self.arena_cap
        )
        n_frames = ctypes.c_int32()
        n = self._lib.sn_shm_wait_batch(
            self._h, timeout_ms,
            self._ptr(staging["ids"], ctypes.c_int64),
            self._ptr(staging["counts"], ctypes.c_int32),
            self._ptr(staging["prios"], ctypes.c_uint8),
            max_n,
            self._ptr(staging["f_fd"], ctypes.c_int32),
            self._ptr(staging["f_gen"], ctypes.c_int32),
            self._ptr(staging["f_xid"], ctypes.c_int32),
            self._ptr(staging["f_n"], ctypes.c_int32),
            self._ptr(staging["f_type"], ctypes.c_uint8),
            max_f, ctypes.byref(n_frames),
        )
        if n <= 0:
            return None
        # the ring stamps nothing: 0 is "no stamp" to whoever reads the
        # block's span cells (the intake lane, Frontdoor.submit)
        if "f_rx_ns" in staging:
            staging["f_rx_ns"][:n_frames.value] = 0
            staging["wake_ns"][0] = 0
        return n, n_frames.value

    def wait_batch(self, timeout_ms: int = 100, max_n: Optional[int] = None):
        if max_n is None:
            max_n = self.arena_cap
        from sentinel_tpu.cluster.protocol import MAX_BATCH_PER_FRAME

        max_n = min(max(int(max_n), MAX_BATCH_PER_FRAME), self.arena_cap)
        b = self._bufs()
        n_frames = ctypes.c_int32()
        n = self._lib.sn_shm_wait_batch(
            self._h, timeout_ms,
            self._ptr(b["ids"], ctypes.c_int64),
            self._ptr(b["counts"], ctypes.c_int32),
            self._ptr(b["prios"], ctypes.c_uint8),
            max_n,
            self._ptr(b["f_fd"], ctypes.c_int32),
            self._ptr(b["f_gen"], ctypes.c_int32),
            self._ptr(b["f_xid"], ctypes.c_int32),
            self._ptr(b["f_n"], ctypes.c_int32),
            self._ptr(b["f_type"], ctypes.c_uint8),
            self.arena_cap, ctypes.byref(n_frames),
        )
        if n <= 0:
            return None
        k = n_frames.value
        frames = (
            b["f_fd"][:k], b["f_gen"][:k], b["f_xid"][:k], b["f_n"][:k],
            b["f_type"][:k],
        )
        return (
            b["ids"][:n], b["counts"][:n],
            b["prios"][:n].astype(bool), frames,
        )

    def submit(self, frames, status, remaining, wait_ms,
               token_ids=None) -> None:
        import numpy as np

        # (token_ids: Frontdoor.submit's; the ring carries flow rows only)
        f_fd, f_gen, f_xid, f_n, f_type = frames[:5]  # a 6th: rx stamps
        f_fd = np.ascontiguousarray(f_fd, np.int32)
        f_gen = np.ascontiguousarray(f_gen, np.int32)
        f_xid = np.ascontiguousarray(f_xid, np.int32)
        f_n = np.ascontiguousarray(f_n, np.int32)
        f_type = np.ascontiguousarray(f_type, np.uint8)
        status = np.ascontiguousarray(status, np.int8)
        remaining = np.ascontiguousarray(remaining, np.int32)
        wait_ms = np.ascontiguousarray(wait_ms, np.int32)
        self._lib.sn_shm_submit(
            self._h, len(f_fd),
            self._ptr(f_fd, ctypes.c_int32),
            self._ptr(f_gen, ctypes.c_int32),
            self._ptr(f_xid, ctypes.c_int32),
            self._ptr(f_n, ctypes.c_int32),
            self._ptr(f_type, ctypes.c_uint8),
            self._ptr(status, ctypes.c_int8),
            self._ptr(remaining, ctypes.c_int32),
            self._ptr(wait_ms, ctypes.c_int32),
        )

    submit_many = Frontdoor.submit_many

    def send(self, fd: int, gen: int, frame: bytes) -> None:
        # TCP frames carry a 2-byte length prefix; ring slots carry the
        # payload with the slot len word playing the prefix's role. A
        # caller may hand over several frames back to back (a push hub's
        # batch): each gets a slot of its own
        pos, end = 0, len(frame)
        while pos + 2 <= end:
            n = (frame[pos] << 8) | frame[pos + 1]
            payload = frame[pos + 2:pos + 2 + n]
            self._lib.sn_shm_send(self._h, fd, gen, payload, len(payload))
            pos += 2 + n

    def set_idle_ttl(self, ttl_ms: int) -> None:
        # liveness is pid-based (the poller sweep), not activity-based
        pass

    def close_conn(self, fd: int, gen: int) -> None:
        self._lib.sn_shm_close_conn(self._h, fd, gen)

    def set_bell(self, bell: Bell) -> None:
        """As :meth:`Frontdoor.set_bell`."""
        self._lib.sn_shm_set_bell(self._h, bell._h)
        self._bell = bell

    def next_control(self):
        fd = ctypes.c_int32()
        gen = ctypes.c_int32()
        ln = ctypes.c_int32()
        t_ns = ctypes.c_int64()
        with self._ctrl_lock:
            kind = self._lib.sn_shm_next_control(
                self._h, ctypes.byref(fd), ctypes.byref(gen),
                ctypes.cast(self._ctrl_buf, ctypes.POINTER(ctypes.c_uint8)),
                len(self._ctrl_buf), ctypes.byref(ln), ctypes.byref(t_ns),
            )
            if kind < 0:
                return None
            self.last_control_ns = t_ns.value
            payload = (
                ctypes.string_at(self._ctrl_buf, ln.value)
                if ln.value > 0 else b""
            )
        return kind, fd.value, gen.value, payload

    def stats(self):
        """Counters are independently monotonic (relaxed atomics): the
        dict is NOT a consistent cross-counter snapshot. Consumers diffing
        two reads must clamp derived deltas at zero."""
        import numpy as np

        out = np.zeros(10, np.uint64)
        self._lib.sn_shm_stats(self._h, self._ptr(out, ctypes.c_uint64))
        return {
            "frames_in": int(out[0]), "requests_in": int(out[1]),
            "bytes_in": int(out[2]), "bytes_out": int(out[3]),
            "shm_polls": int(out[4]), "shm_doorbells": int(out[5]),
            "shm_ring_full": int(out[6]), "shm_segments": int(out[7]),
            "shm_req_slots_used": int(out[8]),
            "shm_req_slots_total": int(out[9]),
        }

    def echo_start(self) -> None:
        """Bench/test helper: a pure-C wait→all-GRANTED-submit loop, for
        measuring the raw transport round trip with no Python in it."""
        self._lib.sn_shm_echo_start(self._h)

    def echo_stop(self) -> None:
        self._lib.sn_shm_echo_stop(self._h)

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self._lib.sn_shm_stop(self._h)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            try:
                self.stop()
            except Exception:
                pass
            self._lib.sn_shm_destroy(h)
            self._h = None


class ShmRingClient:
    """Low-level client half of one shm segment (``sn_shm_client_*``).

    Byte-level transport only: callers hand it full wire frames (with the
    2-byte length prefix, exactly what the TCP socket would carry) and get
    response payloads back; the prefix is stripped/re-added here so
    ``cluster.shm_client`` reuses the ``protocol.py`` codecs verbatim.
    Raises ``ConnectionRefusedError`` when no live door owns ``shm_dir``.
    """

    def __init__(self, shm_dir: str, slot_payload: int = 65536,
                 n_slots: int = 16, spin_us: Optional[int] = None):
        if spin_us is None:  # same adaptive rule as ShmDoor
            spin_us = 0 if (os.cpu_count() or 1) <= 1 else 50
        lib = require()
        if not getattr(lib, "_sn_has_shm", False):
            raise RuntimeError(
                "native library predates the shm door — rebuild with "
                "`python -m sentinel_tpu.native.build`"
            )
        self._lib = lib
        self._h = lib.sn_shm_client_create(
            os.fsencode(shm_dir), int(slot_payload), int(n_slots),
            int(spin_us)
        )
        if not self._h:
            raise ConnectionRefusedError(
                f"no live shm door in {shm_dir!r}"
            )
        self._rbuf = ctypes.create_string_buffer(70000)
        self._lock = threading.Lock()

    def send_frame(self, frame: bytes, timeout_ms: int = 100) -> bool:
        """Publish one length-prefixed wire frame. Spins/backs off while
        the request ring is full, up to ``timeout_ms``. False = give up
        (ring still full); raises ``ConnectionResetError`` once the server
        dropped the segment or died."""
        import time as _time

        payload = frame[2:]
        deadline = _time.monotonic() + timeout_ms / 1000.0
        while True:
            h = self._h
            if not h:
                raise ConnectionResetError("shm segment closed")
            rc = self._lib.sn_shm_client_send(h, payload, len(payload))
            if rc == 1:
                return True
            if rc < 0:
                raise ConnectionResetError("shm door dropped this segment")
            if _time.monotonic() >= deadline:
                return False
            _time.sleep(0.0002)

    def recv_payload(self, timeout_ms: int = 100) -> Optional[bytes]:
        """One response frame payload (no length prefix), or ``None`` on
        timeout; raises ``ConnectionResetError`` when the server is gone."""
        with self._lock:
            n = self._lib.sn_shm_client_recv(
                self._h,
                ctypes.cast(self._rbuf, ctypes.POINTER(ctypes.c_uint8)),
                len(self._rbuf), int(timeout_ms),
            )
            if n > 0:
                return ctypes.string_at(self._rbuf, n)
        if n < 0:
            raise ConnectionResetError("shm door dropped this segment")
        return None

    def rtt_probe(self, frame: bytes, iters: int = 1000):
        """Per-iteration transport round-trip times in ns (C-side send +
        spin-recv loop — no ctypes/codec cost inside the timed region)."""
        import numpy as np

        payload = frame[2:]
        out = np.zeros(iters, np.int64)
        done = self._lib.sn_shm_client_rtt(
            self._h, payload, len(payload), iters,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return out[:max(done, 0)]

    def fuzz(self, data: bytes, stage: int) -> bool:
        """Test hook: torn/hostile slot writes (see sn_shm_client_fuzz)."""
        return bool(
            self._lib.sn_shm_client_fuzz(self._h, data, len(data), stage)
        )

    def alive(self) -> bool:
        return bool(self._lib.sn_shm_client_alive(self._h))

    def close(self) -> None:
        h = self._h
        if h:
            self._h = None
            self._lib.sn_shm_client_destroy(h)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
