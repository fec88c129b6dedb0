"""The token server's wire format, as far as the load generators speak it.

Written out here (not imported from ``sentinel_tpu.cluster.protocol``) so a
generator process needs numpy only and never loads JAX, and so that a later
PR cannot change what the benchmark sends by changing the program's client
codec. The layout is the reference's: a 2-byte big-endian length, then
``xid:i32, type:i8`` and the body.

    FLOW (type 1)        request  flow_id:i64 count:i32 prio:u8
                         response status:i8 remaining:i32 wait_ms:i32
    BATCH_FLOW (type 5)  request  n:u16 then n rows of the FLOW request body
                         response n:u16 then n rows of the FLOW response body

These two are the flow family's frames. A family with frames of its own
(``cellbench/families/``) writes their encoders beside its other code and
tells ``Splitter`` which reply types to return, and in which layout.
"""

from __future__ import annotations

import struct

import numpy as np

FLOW = 1
BATCH_FLOW = 5
MAX_ROWS_PER_FRAME = (65535 - 5 - 2) // 13  # 5040, the wire's own limit

REQ_ROW = np.dtype([("flow_id", ">i8"), ("count", ">i4"), ("prio", "u1")])
RSP_ROW = np.dtype([("status", "i1"), ("remaining", ">i4"), ("wait_ms", ">i4")])
# single-token frames are fixed-size, so a run of them is one packed array
SINGLE_REQ = np.dtype([("len", ">u2"), ("xid", ">i4"), ("type", "i1"),
                       ("flow_id", ">i8"), ("count", ">i4"), ("prio", "u1")])
SINGLE_RSP = np.dtype([("len", ">u2"), ("xid", ">i4"), ("type", "i1"),
                       ("status", "i1"), ("remaining", ">i4"),
                       ("wait_ms", ">i4")])
_BATCH_HEAD = struct.Struct(">HibH")


def encode_batch(xid: int, flow_ids, counts) -> bytes:
    """One BATCH_FLOW request frame."""
    n = len(flow_ids)
    if n > MAX_ROWS_PER_FRAME:
        raise ValueError(f"{n} rows exceed the wire's {MAX_ROWS_PER_FRAME}")
    rows = np.empty(n, REQ_ROW)
    rows["flow_id"] = flow_ids
    rows["count"] = counts
    rows["prio"] = 0
    return _BATCH_HEAD.pack(5 + 2 + n * 13, xid, BATCH_FLOW, n) + rows.tobytes()


def encode_singles(first_xid: int, flow_ids, counts) -> np.ndarray:
    """``len(flow_ids)`` FLOW request frames with consecutive xids, as one
    packed array: ``arr[i:j].tobytes()`` is frames i..j-1 ready to send."""
    n = len(flow_ids)
    arr = np.empty(n, SINGLE_REQ)
    arr["len"] = 5 + 13
    arr["xid"] = first_xid + np.arange(n)
    arr["type"] = FLOW
    arr["flow_id"] = flow_ids
    arr["count"] = counts
    arr["prio"] = 0
    return arr


class Splitter:
    """Incremental splitter of the response stream of one connection.

    ``feed`` returns ``(batch, singles)``: a list of ``(xid, rows)`` for the
    batch responses completed by this chunk (``rows`` an array of the batch
    row layout) and one array of the one-row responses, in their frame
    layout. Which reply types those are, and their layouts, is the family's
    (``SINGLE_REPLIES``, ``BATCH_REPLIES``; FLOW and BATCH_FLOW by default).
    Frames of any other type (pushes, pings) are skipped by their length."""

    def __init__(self, singles=((FLOW,), SINGLE_RSP),
                 batches=((BATCH_FLOW,), RSP_ROW)):
        self._buf = bytearray()
        self._s_types, self._s_dtype = singles
        self._b_types, self._b_row = batches

    def feed(self, data: bytes):
        buf = self._buf
        buf += data
        batch = []
        singles = []
        pos = 0
        end = len(buf)
        s_types, b_types = self._s_types, self._b_types
        size = self._s_dtype.itemsize  # a one-row reply, length prefix and all
        len_hi, len_lo = (size - 2) >> 8, (size - 2) & 0xFF
        row = self._b_row.itemsize
        while end - pos >= 2:
            flen = (buf[pos] << 8) | buf[pos + 1]
            if end - pos < 2 + flen:
                break
            if flen >= 5:
                mtype = buf[pos + 6]
                if mtype in s_types and flen == size - 2:
                    # a run of fixed-size frames: take them all at once
                    q = pos + size
                    while (end - q >= size and buf[q + 1] == len_lo
                           and buf[q] == len_hi and buf[q + 6] == mtype):
                        q += size
                    singles.append(np.frombuffer(bytes(buf[pos:q]),
                                                 self._s_dtype))
                    pos = q
                    continue
                if mtype in b_types and flen >= 7:
                    xid = struct.unpack_from(">i", buf, pos + 2)[0]
                    n = (buf[pos + 7] << 8) | buf[pos + 8]
                    rows = np.frombuffer(
                        bytes(buf[pos + 9:pos + 9 + row * n]), self._b_row)
                    batch.append((xid, rows))
            pos += 2 + flen
        del buf[:pos]
        one = (np.concatenate(singles) if len(singles) > 1
               else singles[0] if singles else None)
        return batch, one
