"""PS wire format: framed messages of JSON meta + raw numpy blobs.

TPU-native equivalent of the reference's message framing
(ref: include/multiverso/message.h:26-69 — 8-int header + vector<Blob>;
serialized into one buffer per send, mpi_net.h:195-216). Here the header is
a fixed struct and each blob is a length-prefixed numpy array (dtype/shape
header + raw bytes, no pickling), so a message deserializes with zero
copies beyond the socket reads. The framing is deliberately simple enough
that a native (C++) transport can speak it; the Python implementation
releases the GIL inside ``recv_into``/``sendall`` so handler threads and
device dispatch overlap.

Frame layout (little-endian)::

    magic   4s   b"MVPS"
    type    u16  message type (service.py MSG_*)
    flags   u16  reserved
    msg_id  i64  request/reply correlation id
    metalen u32  length of the UTF-8 JSON meta dict
    narr    u32  number of numpy blobs
    paylen  i64  total bytes after the header (meta + all blobs)
    meta    bytes[metalen]
    narr x: dlen u8, dtype bytes[dlen], ndim u8, shape i64[ndim], raw bytes

``paylen`` exists so a frame body reads in ONE ``recv_into`` — under GIL
contention every socket read pays a GIL reacquisition (measured ~100 us
with a saturated core), so per-field reads made small messages 3-4x more
expensive than their bytes. Arrays decode as zero-copy views into the
frame buffer.

Safety: reads are bounded (MAX_META, MAX_BLOB, MAX_FRAME) so a garbage or
malicious peer can't OOM the process with one header.

Telemetry: a request's trace ID travels in the JSON meta under
:data:`TRACE_META_KEY` (``"tr"``) — an int minted by telemetry/trace.py
at the client, echoed into the serve/apply spans at the owning shard.
MSG_BATCH inner frames each carry their OWN meta (and therefore their own
trace ID), so a windowed multi-op frame preserves per-SUB-OP correlation
end to end (a client-merged group ships one sub-op carrying its first
logical op's ID; the full set rides the client window spans). Absent
key = untraced request (the default); the binary frame layout is
unchanged either way.
"""

from __future__ import annotations

import json
import socket
import struct
from typing import Dict, List, Sequence, Tuple

import numpy as np

MAGIC = b"MVPS"
_HEADER = struct.Struct("<4sHHqIIq")
_U8 = struct.Struct("<B")
MAX_META = 64 << 20
MAX_BLOB = 4 << 30
# total-frame sanity bound: must admit legitimate multi-blob frames (a
# checkpoint dump is [keys, rows, every updater-state leaf] in ONE frame),
# so it bounds garbage headers, not real payloads
MAX_FRAME = MAX_META + 8 * MAX_BLOB


class WireError(RuntimeError):
    pass


# JSON-meta key carrying the per-request trace ID (see module docstring)
TRACE_META_KEY = "tr"

# Exactly-once replay meta (elastic failover, docs/FAILOVER.md). A
# windowed add frame (MSG_ADD_ROWS / MSG_BATCH shipped by a replay-
# enabled _SendWindow) stamps its OUTER meta with the sending client's
# identity and a per-(client, table) monotonic sequence number; the
# owning shard dedupes by per-client high-water mark (a frame arriving
# twice — replay racing a late ack, or a survivor re-flushing to a
# restored incarnation — applies exactly once). Replies echo the
# shard's DURABLE (checkpointed) high-water mark for that client, which
# is the client's retention-prune signal. The binary frame layout is
# unchanged; unstamped frames behave exactly as before. The native C++
# server's meta whitelist does not know these keys, so stamped frames
# always punt to the Python handler — dedupe runs under the native
# shard mutex there, one implementation on both wire planes.
REPLAY_CLIENT_KEY = "cl"     # request: client identity string
REPLAY_SEQ_KEY = "seq"       # request: per-(client, table) sequence
REPLAY_DURABLE_KEY = "dseq"  # reply: durable high-water mark for cl
REPLAY_DUP_KEY = "dup"       # reply: frame was a dedup'd duplicate

# Multi-owner super-frame sub-op addressing (MSG_MULTI, ps/spmd.py):
# each inner frame of a super-frame names its OWNING rank here, so the
# receiving process can dispatch it to the right colocated shard. The
# native C++ server's meta whitelist does not know the key — a
# super-frame always punts to Python, like MSG_BATCH. Absent key = the
# receiving rank owns the sub-op.
OWNER_META_KEY = "ow"

# Tenant attribution (telemetry/tenants.py): the effective tenant id of
# the CALLER rides here on add/get/window/pull frames so the owning
# shard can account per-tenant op/byte counters. Stamped ONLY for
# non-default tenants — default traffic keeps the cached meta bytes and
# the native fast path. The native C++ server's meta whitelist does not
# know the key, so stamped frames punt to the Python handler like every
# modern meta key: one accounting implementation on both wire planes.
TENANT_META_KEY = "tn"


def with_trace(meta: Dict, trace) -> Dict:
    """Meta dict + trace ID (no-op passthrough for ``trace=None`` so
    call sites stay branch-free)."""
    if trace is None:
        return meta
    meta = dict(meta)
    meta[TRACE_META_KEY] = trace
    return meta


def with_tenant(meta: Dict, tenant) -> Dict:
    """Meta dict + tenant id (no-op passthrough for the default tenant
    so call sites stay branch-free, mirroring :func:`with_trace`)."""
    if not tenant:
        return meta
    meta = dict(meta)
    meta[TENANT_META_KEY] = tenant
    return meta


class ChunkedReply:
    """A streamed get reply: ``meta`` is the FINAL frame's meta (carries
    ``chunks``/``rows`` so the client knows the stream's shape) and
    ``chunks`` an iterator of ``(chunk_meta, chunk_arrays)`` sub-frames.
    A handler returns one of these instead of a blob list when the
    client asked for a chunk-streamed reply (request meta ``"chunk"``);
    the service sends each sub-frame as ``MSG_REPLY_CHUNK`` under the
    request's msg_id as the iterator yields — so the peer's decode +
    ``out=`` scatter overlaps the network receive — and closes the
    stream with an ordinary ``MSG_REPLY_OK`` carrying ``meta``. An
    exception raised mid-iteration becomes a ``MSG_REPLY_ERR`` like any
    handler failure; the client discards accumulated chunks on ERR."""

    __slots__ = ("meta", "chunks")

    def __init__(self, meta: Dict, chunks):
        self.meta, self.chunks = meta, chunks


WIRE_MODES = ("none", "bf16")


def encode_payload(arr: np.ndarray, wire: str) -> List[np.ndarray]:
    """The ONE place PS payloads are wire-encoded: an array -> the blob
    list that travels in the frame, shared by client sends and shard
    replies. "none" -> [arr]; "bf16" -> [arr as bfloat16], half the
    bytes, a cast with no state."""
    if wire not in WIRE_MODES:
        raise ValueError(f"unknown wire {wire!r}")
    if wire == "bf16":
        import ml_dtypes
        return [np.asarray(arr).astype(ml_dtypes.bfloat16)]
    return [arr]


def decode_payload(arrays: Sequence[np.ndarray], wire: str,
                   shape: Tuple[int, ...], dtype) -> np.ndarray:
    """Inverse of :func:`encode_payload` (the other endpoint): the cast
    back to the table's dtype. A mode this build does not speak (a
    peer's frame meta is outside input) is refused, not guessed at."""
    if wire not in WIRE_MODES:
        raise ValueError(f"unknown wire {wire!r}")
    return np.asarray(arrays[0], dtype).reshape(shape)


def _recv_exact(sock: socket.socket, n: int, *, sof: bool = False
                ) -> memoryview:
    """Read exactly ``n`` bytes. ``sof`` (start-of-frame): a timeout with
    ZERO bytes consumed is an idle socket and re-raises as TimeoutError so
    callers may keep the connection; any timeout after bytes were consumed
    desyncs the framing and is fatal (WireError)."""
    try:
        buf = bytearray(n)
    except MemoryError:
        raise WireError(f"cannot buffer {n}-byte frame") from None
    view = memoryview(buf)
    got = 0
    while got < n:
        try:
            r = sock.recv_into(view[got:], n - got)
        except TimeoutError:
            if sof and got == 0:
                raise
            raise WireError("timeout mid-message (framing lost)") from None
        if r == 0:
            raise WireError("peer closed connection mid-message")
        got += r
    return memoryview(buf)


def pack_meta(meta: Dict) -> bytes:
    """Pre-serialize a meta dict. Ops that fan one logical request out to
    many owners serialize the (identical) meta once, not once per peer."""
    return json.dumps(meta).encode()


def _frame_parts(msg_type: int, msg_id: int, meta,
                 arrays: Sequence[np.ndarray]) -> List:
    """Frame as a buffer list (header+meta+per-array header, array bodies
    interleaved as zero-copy memoryviews where the layout allows)."""
    meta_b = meta if isinstance(meta, (bytes, bytearray)) else \
        json.dumps(meta).encode()
    parts: List = [None, meta_b]   # header patched once paylen is known
    paylen = len(meta_b)
    for a in arrays:
        # asarray, not ascontiguousarray: the latter promotes 0-d to 1-d,
        # and the non-contiguous fallback below linearizes via tobytes()
        a = np.asarray(a)
        # custom dtypes (bfloat16 etc.) stringify as '<V2' which does NOT
        # round-trip; their registered NAME does
        ds = a.dtype.str
        if np.dtype(ds) != a.dtype:
            ds = a.dtype.name
        dt = ds.encode()
        head = struct.pack(f"<B{len(dt)}sB{a.ndim}q",
                           len(dt), dt, a.ndim, *a.shape)
        try:   # custom dtypes (bfloat16) and 0-d views can't always export
            body = (a.data.cast("B") if a.flags.c_contiguous
                    else memoryview(a.tobytes()))
        except (ValueError, TypeError):
            body = memoryview(a.tobytes())
        parts.append(head)
        parts.append(body)
        paylen += len(head) + a.nbytes
    parts[0] = _HEADER.pack(MAGIC, msg_type, 0, msg_id, len(meta_b),
                            len(arrays), paylen)
    return parts


def encode(msg_type: int, msg_id: int, meta,
           arrays: Sequence[np.ndarray] = ()) -> bytes:
    return b"".join(bytes(p) if isinstance(p, memoryview) else p
                    for p in _frame_parts(msg_type, msg_id, meta, arrays))


def send(sock: socket.socket, msg_type: int, msg_id: int, meta,
         arrays: Sequence[np.ndarray] = ()) -> None:
    """Send one frame with ``sendmsg`` scatter-gather: array payloads go
    to the kernel straight from their own buffers — no join/tobytes copy
    of the (dominant) data bytes. ``meta`` may be a dict or pre-packed
    ``pack_meta`` bytes."""
    views = [p if isinstance(p, memoryview) else memoryview(p)
             for p in _frame_parts(msg_type, msg_id, meta, arrays)]
    while views:
        sent = sock.sendmsg(views)
        while views and sent >= len(views[0]):   # drop fully-sent parts
            sent -= len(views[0])
            views.pop(0)
        if views and sent:                        # resume mid-part
            views[0] = views[0][sent:]


def recv(sock: socket.socket) -> Tuple[int, int, Dict, List[np.ndarray]]:
    """Read one message; returns (msg_type, msg_id, meta, arrays).
    Raises TimeoutError (connection still usable) only when the socket was
    idle — i.e. the timeout hit before any byte of a frame arrived.
    Arrays are zero-copy views into the frame buffer (each frame owns its
    buffer, so views never alias across messages)."""
    head = _recv_exact(sock, _HEADER.size, sof=True)
    magic, msg_type, _flags, msg_id, metalen, narr, paylen = \
        _HEADER.unpack(head)
    if magic != MAGIC:
        raise WireError(f"bad magic {bytes(magic)!r}")
    if metalen > MAX_META:
        raise WireError(f"meta too large ({metalen} bytes)")
    if paylen < metalen or paylen > MAX_FRAME:
        raise WireError(f"frame length out of bounds ({paylen} bytes)")
    body = _recv_exact(sock, paylen)
    meta, arrays = _parse_body(body, metalen, narr, paylen)
    return msg_type, msg_id, meta, arrays


def parse_frame(frame: bytes) -> Tuple[int, int, Dict, List[np.ndarray]]:
    """Parse one complete frame already in memory (header + body) — the
    entry point for frames handed over by the native transport's punt
    callback (native/mv_ps.cpp). Same validation as :func:`recv`; arrays
    are views into ``frame``, whose immutability/lifetime the views pin."""
    if len(frame) < _HEADER.size:
        raise WireError("short frame")
    magic, msg_type, _flags, msg_id, metalen, narr, paylen = \
        _HEADER.unpack_from(frame)
    if magic != MAGIC:
        raise WireError(f"bad magic {bytes(magic)!r}")
    if metalen > MAX_META or paylen < metalen or paylen > MAX_FRAME:
        raise WireError("frame length out of bounds")
    body = memoryview(frame)[_HEADER.size:]
    if len(body) != paylen:
        raise WireError(f"frame body {len(body)} != paylen {paylen}")
    meta, arrays = _parse_body(body, metalen, narr, paylen)
    return msg_type, msg_id, meta, arrays


# bound on logical sub-ops per MSG_BATCH frame: far above any real send
# window (batch_window_ops defaults to 64), small enough that a garbage
# header can't make the unpack loop spin
MAX_BATCH_OPS = 4096


def pack_batch(subframes: Sequence[bytes]) -> List[np.ndarray]:
    """Pack complete inner frames (each a full :func:`encode` output —
    header + meta + blobs, so every sub-op keeps its own meta and codec
    wire) as the blob list of ONE outer MSG_BATCH frame. Each blob is
    length-prefixed by the ordinary frame layout; the outer frame costs
    one send, one recv, and one reply for the whole window."""
    if not subframes:
        raise WireError("empty batch")
    if len(subframes) > MAX_BATCH_OPS:
        raise WireError(f"batch of {len(subframes)} sub-ops exceeds "
                        f"MAX_BATCH_OPS ({MAX_BATCH_OPS})")
    return [np.frombuffer(f, np.uint8) for f in subframes]


def unpack_batch(arrays: Sequence[np.ndarray]
                 ) -> List[Tuple[int, Dict, List[np.ndarray]]]:
    """Inverse of :func:`pack_batch`: the received blob list back into
    ``(msg_type, meta, arrays)`` sub-ops, in window order. Sub-arrays are
    zero-copy views into the outer frame's buffer (same lifetime rule as
    :func:`recv`). Inner msg_ids are the window indices — correlation
    lives on the OUTER frame; they are only used to name a failing
    sub-op."""
    if len(arrays) > MAX_BATCH_OPS:
        raise WireError(f"batch of {len(arrays)} sub-ops exceeds "
                        f"MAX_BATCH_OPS ({MAX_BATCH_OPS})")
    out = []
    for blob in arrays:
        msg_type, _mid, meta, arrs = parse_frame(np.ascontiguousarray(blob))
        out.append((msg_type, meta, arrs))
    return out


def peek_msg_id(frame: bytes) -> int:
    """msg_id from a frame whose header is known-sane (the native
    transport validates magic/bounds before punting) — lets a server
    send a bound ERR reply even when the BODY fails to parse."""
    if len(frame) < _HEADER.size:
        raise WireError("short frame")
    return _HEADER.unpack_from(frame)[3]


def _parse_body(body, metalen: int, narr: int, paylen: int
                ) -> Tuple[Dict, List[np.ndarray]]:
    try:
        meta = json.loads(bytes(body[:metalen]) or b"{}")
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        # corrupt meta must surface as a WireError like every other
        # malformed-body shape — callers key their fail-fast paths on it
        # (the native plane's _punt replies ERR instead of parking the
        # peer for the full ps_timeout)
        raise WireError(f"malformed meta json: {e}") from None
    arrays: List[np.ndarray] = []
    off = metalen
    try:
        for _ in range(narr):
            (dlen,) = _U8.unpack_from(body, off)
            off += 1
            dtype = np.dtype(bytes(body[off:off + dlen]).decode())
            off += dlen
            (ndim,) = _U8.unpack_from(body, off)
            off += 1
            shape = struct.unpack_from(f"<{ndim}q", body, off) if ndim else ()
            off += 8 * ndim
            if any(d < 0 for d in shape):
                # a negative dim would make count=-1, which frombuffer
                # reads as "the rest of the buffer" — garbage accepted
                # silently and the cursor walked backwards
                raise WireError(f"negative dim in blob shape {shape}")
            count = int(np.prod(shape, dtype=np.int64)) if ndim else 1
            nbytes = count * dtype.itemsize
            if nbytes > MAX_BLOB or off + nbytes > paylen:
                raise WireError(f"blob out of bounds ({nbytes} bytes)")
            arrays.append(np.frombuffer(body, dtype=dtype, count=count,
                                        offset=off).reshape(shape))
            off += nbytes
    except (struct.error, ValueError, TypeError) as e:
        # TypeError: np.dtype() on a garbage dtype string
        raise WireError(f"malformed frame: {e}") from None
    return meta, arrays
