"""Binary tensor wire contract — the zero-copy ingress lane.

For a payload-heavy predict the host cost is the WIRE FORMAT, not the
framework.  A JSON predict burns the payload four times before the device sees it —
socket bytes -> str decode -> json parse -> list -> numpy — and four
more on the way out.  The reference shipped an experimental flatbuffers
contract (``fbs/prediction.fbs``) for exactly this reason; this module
is its TPU-native equivalent: a length-delimited frame whose tensor
payload is the raw row-major bytes the device DMA wants, so a request
parses with ONE ``np.frombuffer`` view and a response is framed straight
from the device readback buffer.

Frame layout (all integers big-endian)::

    offset  size      field
    0       4         magic  b"SLDT"
    4       1         version (currently 1)
    5       1         flags  (bit0 RESPONSE, bit1 SCALES, bit2 MULTI)
    6       1         dtype code (0 = no tensor payload)
    7       1         ndim  (<= 8)
    8       2         status (response frames; sub-frame COUNT for MULTI;
                      0 on requests)
    10      4         meta_len (sidecar bytes)
    14      4*ndim    shape dims (u32 each)
    ...     meta_len  sidecar (below)
    [flags&SCALES]    u32 scale_len + f32 scale plane, one entry per row
                      (int8/uint8 payloads: value = q * scale[row])
    pad               zeros to the next 8-byte boundary from frame start
    ...               payload: prod(shape) * itemsize raw row-major bytes

The payload length is IMPLIED by dtype x shape and validated strictly:
a frame whose byte count disagrees with its header answers a typed 400
(dtype/shape mismatch), never a crash, and a declared size beyond the
lane cap answers a typed 413 before any allocation.

Sidecar (``meta_len`` bytes): the per-request metadata that rides HTTP
headers on the JSON lane, packed binary so the hot path never touches a
dict of header strings::

    !Bd              sidecar version, deadline_ms (<= 0 = absent)
    uvarint+utf8 x5  puid, traceparent, tenant, tier, extra_json

``extra_json`` is a (small) JSON object for the cold envelope fields —
``names``, ``kind``, ``tags``, ``routing``, ``requestPath``, ``error`` —
the flatbuffers-style split: metadata stays cheap-and-flexible, the
numeric payload stays bytes.  An unknown future sidecar version degrades
to "no metadata" (the deadline-header rule: bad metadata must never fail
a request that would otherwise serve); an unknown FRAME version is a
typed 400 (the payload bytes cannot be trusted).

Multi-tensor frames (``FLAG_MULTI``): the gateway coalesces co-arriving
requests for the same deployment into ONE engine frame — ``status``
carries the sub-frame count and the body is ``count x (u32 len +
complete single frame)``.  De-coalescing is positional, verified by each
sub-response's echoed puid.

Content negotiation: HTTP lanes carry frames under ``Content-Type:
application/x-seldon-tensor``; the framed relay (runtime/udsrelay.py)
carries them as ``OP_WIRE`` payloads.  ``SELDON_TPU_WIRE=0`` is the kill
switch — binary ingress answers a typed 415 and every client lane falls
back to JSON, restoring the pre-wire path bit-for-bit.

Copy accounting: every host-side byte copy the codec (or a lane feeding
it) makes is recorded via :func:`account_copy` into
``seldon_tpu_wire_bytes_copied_total``, so the lane is priced against
JSON in bytes copied per request (tests/test_wire.py pins the ratio).
"""

from __future__ import annotations

import json
import os
import struct
from dataclasses import dataclass, field
from typing import Any, List, Optional

import numpy as np

from seldon_core_tpu.messages import (
    DefaultData,
    Meta,
    SeldonMessage,
    SeldonMessageError,
    Status,
)

__all__ = [
    "WIRE_CONTENT_TYPE",
    "WIRE_MAGIC",
    "WIRE_VERSION",
    "FLAG_RESPONSE",
    "FLAG_SCALES",
    "FLAG_MULTI",
    "WireError",
    "WireFrameTooLarge",
    "WireFrame",
    "wire_enabled",
    "coalesce_window_s",
    "coalesce_max",
    "encode_frame",
    "encode_multi",
    "decode_frame",
    "join_parts",
    "parts_nbytes",
    "frame_from_message",
    "message_from_frame",
    "frame_eligible",
    "current_wire_sidecar",
    "quantize_rows",
    "account_copy",
    "uvarint",
    "read_uvarint",
    "pack_str",
]

WIRE_CONTENT_TYPE = "application/x-seldon-tensor"
WIRE_MAGIC = b"SLDT"
WIRE_VERSION = 1
SIDECAR_VERSION = 1

FLAG_RESPONSE = 0x01
FLAG_SCALES = 0x02
FLAG_MULTI = 0x04

_HEAD = struct.Struct("!4sBBBBHI")  # magic, version, flags, dtype, ndim, status, meta_len
_META_HEAD = struct.Struct("!Bd")   # sidecar version, deadline_ms
_SUB_LEN = struct.Struct("!I")
_MAX_NDIM = 8
#: matches the HTTP lanes' 256 MiB body cap (rest.py client_max_size,
#: httpfast._MAX_BODY, udsrelay._MAX_FRAME)
MAX_FRAME_BYTES = 256 * 1024 * 1024
#: sub-frame count cap in a MULTI frame — far above any coalesce window
MAX_MULTI = 4096

# dtype code <-> numpy dtype.  bf16 rides code 10 when ml_dtypes is
# importable (it always is next to jax); a peer without it answers a
# typed 400 for bf16 frames instead of misreading the bytes.
_CODE_TO_DTYPE = {
    1: np.dtype(np.float32),
    2: np.dtype(np.float64),
    3: np.dtype(np.int8),
    4: np.dtype(np.int16),
    5: np.dtype(np.int32),
    6: np.dtype(np.int64),
    7: np.dtype(np.uint8),
    8: np.dtype(np.bool_),
    9: np.dtype(np.float16),
}
try:  # pragma: no cover - exercised wherever jax's ml_dtypes is present
    import ml_dtypes as _ml_dtypes

    _CODE_TO_DTYPE[10] = np.dtype(_ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    pass
_DTYPE_TO_CODE = {dt: code for code, dt in _CODE_TO_DTYPE.items()}


class WireError(SeldonMessageError):
    """Malformed binary frame (bad magic/version/dtype/shape/truncation).
    400 at the edge — the bytes cannot be trusted, the connection can."""

    http_code = 400


class WireFrameTooLarge(WireError):
    """Declared frame size beyond the lane cap — typed 413 BEFORE any
    allocation, riding the same writer discipline as the relay's 413."""

    http_code = 413


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------


def wire_enabled() -> bool:
    """Kill switch: ``SELDON_TPU_WIRE=0`` restores the JSON path
    bit-for-bit (binary ingress answers 415, client lanes speak JSON)."""
    return os.environ.get("SELDON_TPU_WIRE", "1") != "0"


def coalesce_window_s() -> float:
    """Gateway-side coalesce window (``SELDON_TPU_WIRE_COALESCE_US``,
    default 200 us; 0 disables): co-arriving requests for the same engine
    within this window ride ONE multi-tensor relay frame — the hop cost
    amortizes exactly where the MicroBatcher would have re-batched the
    rows anyway."""
    try:
        us = float(os.environ.get("SELDON_TPU_WIRE_COALESCE_US", "") or 200.0)
    except ValueError:
        us = 200.0
    return max(0.0, us) / 1e6


def coalesce_max() -> int:
    """Per-flush sub-frame cap (``SELDON_TPU_WIRE_COALESCE_MAX``, default
    16 — the batcher's default pad-bucket ceiling class, so one coalesced
    frame never exceeds what the engine would co-flush)."""
    try:
        n = int(os.environ.get("SELDON_TPU_WIRE_COALESCE_MAX", "") or 16)
    except ValueError:
        n = 16
    return max(2, min(n, MAX_MULTI))


# ---------------------------------------------------------------------------
# copy accounting
# ---------------------------------------------------------------------------


def account_copy(nbytes: int) -> None:
    """One host-side byte copy of ``nbytes`` — the codec's honesty
    counter.  Lanes that must materialize request bytes out of a receive
    buffer account that copy here too, so ``bytes_copied_per_request`` in
    the bench is end-to-end, not codec-flattering.

    When the cost ledger is on, the same copy lands tenant-attributed
    (utils/costledger.py lane ``wire_copy``) — calls on a bound request
    context bill the copying tenant, dispatch-thread calls book under
    the anonymous tenant so lane totals stay complete either way."""
    if nbytes > 0:
        from seldon_core_tpu.utils.telemetry import RECORDER

        RECORDER.record_wire_copy(int(nbytes))
        from seldon_core_tpu.utils.costledger import (
            LEDGER,
            costledger_enabled,
        )
        if costledger_enabled():
            from seldon_core_tpu.runtime.qos import current_tenant

            LEDGER.note_bytes(current_tenant() or "", "", "wire_copy",
                              int(nbytes))


# ---------------------------------------------------------------------------
# shared framing helpers (udsrelay.py imports these — one uvarint, not two)
# ---------------------------------------------------------------------------


def uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def read_uvarint(view, off: int) -> "tuple[int, int]":
    shift = 0
    val = 0
    while True:
        if off >= len(view):
            raise ValueError("truncated varint")
        b = view[off]
        off += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, off
        shift += 7
        if shift > 35:
            raise ValueError("varint too long")


def pack_str(s: "str | None") -> bytes:
    raw = (s or "").encode("utf-8", "replace")
    return uvarint(len(raw)) + raw


# ---------------------------------------------------------------------------
# sidecar
# ---------------------------------------------------------------------------


def pack_wire_meta(puid: "str | None" = None,
                   deadline_ms: "float | None" = None,
                   traceparent: "str | None" = None,
                   tenant: "str | None" = None,
                   tier: "str | None" = None,
                   extra: "dict | None" = None) -> bytes:
    """The per-request sidecar: what the JSON lane carries as HTTP
    headers (deadline/trace/tenant/tier) plus the cold envelope fields
    (``extra``) as one small JSON object."""
    extra_json = (
        json.dumps(extra, separators=(",", ":")) if extra else ""
    )
    return (
        _META_HEAD.pack(SIDECAR_VERSION,
                        float(deadline_ms) if deadline_ms else -1.0)
        + pack_str(puid) + pack_str(traceparent) + pack_str(tenant)
        + pack_str(tier) + pack_str(extra_json)
    )


_EMPTY_META = {"puid": None, "deadline_ms": None, "traceparent": None,
               "tenant": None, "tier": None, "extra": None}


def unpack_wire_meta(view) -> dict:
    """Sidecar parse.  A FUTURE sidecar version degrades to 'no metadata'
    (forward compatibility — the payload is still trustworthy); a
    structurally torn sidecar raises :class:`WireError` (the frame is
    corrupt)."""
    if len(view) == 0:
        return dict(_EMPTY_META)
    out = dict(_EMPTY_META)
    try:
        version, deadline_ms = _META_HEAD.unpack_from(view, 0)
        if version != SIDECAR_VERSION:
            return dict(_EMPTY_META)
        if deadline_ms > 0:
            out["deadline_ms"] = float(deadline_ms)
        off = _META_HEAD.size
        vals = []
        for _ in range(5):
            n, off = read_uvarint(view, off)
            if off + n > len(view):
                raise ValueError("truncated sidecar string")
            raw = bytes(view[off:off + n])
            off += n
            vals.append(raw.decode("utf-8", "replace") if raw else None)
    except (struct.error, ValueError) as e:
        raise WireError(f"torn wire sidecar: {e}") from e
    out["puid"], out["traceparent"], out["tenant"], out["tier"] = vals[:4]
    if vals[4]:
        try:
            extra = json.loads(vals[4])
        except ValueError as e:
            raise WireError(f"malformed wire sidecar extra: {e}") from e
        if not isinstance(extra, dict):
            raise WireError("wire sidecar extra must be a JSON object")
        out["extra"] = extra
    return out


def current_wire_sidecar(extra: "dict | None" = None,
                         puid: "str | None" = None) -> bytes:
    """The calling context's deadline/trace/tenant/tier as sidecar bytes
    — what the JSON lanes forward as headers, for frames that hop
    gateway->engine or node->node."""
    from seldon_core_tpu.runtime.qos import current_tenant, current_tier
    from seldon_core_tpu.runtime.resilience import remaining_s
    from seldon_core_tpu.utils.tracing import traceparent_header_value

    rem = remaining_s()
    tier = current_tier()
    return pack_wire_meta(
        puid=puid,
        deadline_ms=max(rem * 1e3, 1.0) if rem is not None else None,
        traceparent=traceparent_header_value(),
        tenant=current_tenant(),
        tier=None if tier == "interactive" else tier,
        extra=extra,
    )


# ---------------------------------------------------------------------------
# frames
# ---------------------------------------------------------------------------


@dataclass
class WireFrame:
    """A decoded frame.  ``array`` is a zero-copy ``np.frombuffer`` view
    over the wire buffer unless the decoder was asked to copy — callers
    that keep the buffer alive (bytes bodies) never pay a host copy
    between the socket and ``jnp.asarray``'s host->device DMA."""

    array: Optional[np.ndarray] = None
    scales: Optional[np.ndarray] = None
    status: int = 0
    flags: int = 0
    meta: dict = field(default_factory=lambda: dict(_EMPTY_META))
    subframes: List[Any] = field(default_factory=list)  # memoryviews

    @property
    def is_response(self) -> bool:
        return bool(self.flags & FLAG_RESPONSE)

    @property
    def is_multi(self) -> bool:
        return bool(self.flags & FLAG_MULTI)

    def extra(self) -> dict:
        return self.meta.get("extra") or {}

    def rows(self) -> np.ndarray:
        """The tensor as 2D rows for the batcher — dequantized through
        the per-row scale plane when one rides the frame."""
        if self.array is None:
            raise WireError("wire frame has no tensor payload")
        a = self.array
        if a.ndim < 2:
            a = a.reshape(1, -1)
        if self.scales is not None:
            a = a.astype(np.float32) * self.scales.reshape(-1, 1)
        return a


def _dims_nbytes(dtype: np.dtype, shape: "tuple[int, ...]") -> int:
    n = dtype.itemsize
    for d in shape:
        n *= int(d)
    return n


def _pad_to(off: int, align: int = 8) -> int:
    return (-off) % align


def encode_frame(array: "np.ndarray | None" = None, *,
                 status: int = 0, response: bool = False,
                 meta_bytes: "bytes | None" = None,
                 scales: "np.ndarray | None" = None) -> List[Any]:
    """Encode one frame as a list of buffer parts ``[header_block,
    payload_view]`` — the caller writes them sequentially (writev
    discipline), so a response is framed FROM the device readback buffer
    with zero intermediate concatenation.  ``meta_bytes`` is a
    pre-packed sidecar (:func:`pack_wire_meta`)."""
    flags = FLAG_RESPONSE if response else 0
    meta_bytes = meta_bytes or b""
    parts: List[Any] = []
    if array is None:
        head = _HEAD.pack(WIRE_MAGIC, WIRE_VERSION, flags, 0, 0,
                          status & 0xFFFF, len(meta_bytes))
        return [head + meta_bytes]
    a = np.asarray(array)
    dt = a.dtype
    if dt not in _DTYPE_TO_CODE:
        raise WireError(f"dtype {dt} has no wire code")
    if a.ndim > _MAX_NDIM:
        raise WireError(f"ndim {a.ndim} > wire max {_MAX_NDIM}")
    if not a.flags.c_contiguous:
        a = np.ascontiguousarray(a)
        account_copy(a.nbytes)
    scale_block = b""
    if scales is not None:
        # scale planes (like payloads) are little-endian on the wire
        s = np.ascontiguousarray(np.asarray(scales, dtype="<f4"))
        flags |= FLAG_SCALES
        scale_block = _SUB_LEN.pack(s.nbytes) + s.tobytes()
    head = _HEAD.pack(
        WIRE_MAGIC, WIRE_VERSION, flags, _DTYPE_TO_CODE[dt],
        a.ndim, status & 0xFFFF, len(meta_bytes),
    )
    shape = struct.pack("!%dI" % a.ndim, *(int(d) for d in a.shape))
    off = len(head) + len(shape) + len(meta_bytes) + len(scale_block)
    pad = b"\x00" * _pad_to(off)
    parts.append(head + shape + meta_bytes + scale_block + pad)
    # the payload rides as a memoryview of the (readback) array — the
    # transport writes it straight out, no .tobytes() materialization
    parts.append(memoryview(a).cast("B"))
    return parts


def encode_multi(frames: List[bytes]) -> List[Any]:
    """Pack complete single-frame byte strings into one MULTI frame (the
    gateway's coalesced engine hop).  Returned as parts for writev."""
    if not frames:
        raise WireError("empty multi frame")
    if len(frames) > MAX_MULTI:
        raise WireError(f"multi frame count {len(frames)} > {MAX_MULTI}")
    head = _HEAD.pack(WIRE_MAGIC, WIRE_VERSION, FLAG_MULTI, 0, 0,
                      len(frames), 0)
    parts: List[Any] = [head]
    for f in frames:
        parts.append(_SUB_LEN.pack(len(f)))
        parts.append(f)
    return parts


def parts_nbytes(parts: List[Any]) -> int:
    return sum(len(p) for p in parts)


def join_parts(parts: List[Any]) -> bytes:
    """Materialize frame parts into one bytes (lanes that need a single
    body, e.g. the relay client's payload).  This IS a copy — counted."""
    if len(parts) == 1:
        p = parts[0]
        return p if isinstance(p, bytes) else bytes(p)
    out = b"".join(parts)
    account_copy(len(out))
    return out


def decode_frame(buf, *, copy: bool = False,
                 max_bytes: int = MAX_FRAME_BYTES) -> WireFrame:
    """Strict decode of one frame.  ``buf`` is any bytes-like; tensor
    payloads come back as zero-copy views unless ``copy=True`` (callers
    whose buffer is mutable/recycled — then the one copy lands directly
    in the numpy allocation and is accounted).

    Every malformed shape answers typed: bad magic / unknown version /
    unknown dtype / truncated header / truncated payload / trailing
    bytes (dtype x shape disagrees with the byte count) -> 400
    :class:`WireError`; a declared size beyond ``max_bytes`` -> 413
    :class:`WireFrameTooLarge` before any allocation."""
    view = memoryview(buf)
    if len(view) > max_bytes:
        raise WireFrameTooLarge(
            f"wire frame {len(view)}B exceeds cap {max_bytes}B")
    if len(view) < _HEAD.size:
        raise WireError("truncated wire header")
    magic, version, flags, dcode, ndim, status, meta_len = \
        _HEAD.unpack_from(view, 0)
    if magic != WIRE_MAGIC:
        raise WireError("bad wire magic")
    if version != WIRE_VERSION:
        raise WireError(f"unsupported wire version {version}")
    off = _HEAD.size
    if flags & FLAG_MULTI:
        count = status
        if count == 0 or count > MAX_MULTI:
            raise WireError(f"bad multi frame count {count}")
        subs = []
        for _ in range(count):
            if off + _SUB_LEN.size > len(view):
                raise WireError("truncated multi frame")
            (sub_len,) = _SUB_LEN.unpack_from(view, off)
            off += _SUB_LEN.size
            if sub_len > max_bytes:
                raise WireFrameTooLarge(
                    f"wire sub-frame {sub_len}B exceeds cap {max_bytes}B")
            if off + sub_len > len(view):
                raise WireError("truncated multi frame")
            subs.append(view[off:off + sub_len])
            off += sub_len
        if off != len(view):
            raise WireError("trailing bytes after multi frame")
        return WireFrame(flags=flags, status=0, subframes=subs)
    if ndim > _MAX_NDIM:
        raise WireError(f"ndim {ndim} > wire max {_MAX_NDIM}")
    shape_len = 4 * ndim
    if off + shape_len + meta_len > len(view):
        raise WireError("truncated wire frame")
    shape = (
        struct.unpack_from("!%dI" % ndim, view, off) if ndim else ()
    )
    off += shape_len
    meta = unpack_wire_meta(view[off:off + meta_len])
    off += meta_len
    if dcode == 0:
        if off != len(view):
            raise WireError("trailing bytes after payload-less frame")
        return WireFrame(array=None, status=status, flags=flags, meta=meta)
    dtype = _CODE_TO_DTYPE.get(dcode)
    if dtype is None:
        raise WireError(f"unknown wire dtype code {dcode}")
    scales = None
    if flags & FLAG_SCALES:
        if dtype.itemsize != 1:
            raise WireError("scale plane on a non-8-bit payload")
        if off + _SUB_LEN.size > len(view):
            raise WireError("truncated scale plane")
        (scale_len,) = _SUB_LEN.unpack_from(view, off)
        off += _SUB_LEN.size
        rows = int(shape[0]) if ndim else 1
        if scale_len != 4 * rows or off + scale_len > len(view):
            raise WireError("scale plane disagrees with shape")
        scales = np.frombuffer(view[off:off + scale_len], dtype="<f4")
        off += scale_len
    off += _pad_to(off)
    nbytes = _dims_nbytes(dtype, shape)
    if nbytes > max_bytes:
        raise WireFrameTooLarge(
            f"declared tensor {nbytes}B exceeds cap {max_bytes}B")
    if off + nbytes != len(view):
        raise WireError(
            f"payload is {max(0, len(view) - off)}B but dtype x shape "
            f"{tuple(int(d) for d in shape)} implies {nbytes}B"
        )
    flat = np.frombuffer(view[off:off + nbytes], dtype=dtype)
    arr = flat.reshape(shape)
    if copy:
        arr = arr.copy()
        account_copy(arr.nbytes)
    return WireFrame(array=arr, scales=scales, status=status, flags=flags,
                     meta=meta)


# ---------------------------------------------------------------------------
# SeldonMessage bridges
# ---------------------------------------------------------------------------


def frame_eligible(msg: SeldonMessage) -> bool:
    """Can this message ride the binary lane?  Numeric DefaultData only —
    strData/binData/object payloads stay on JSON (they were never the
    bytes problem)."""
    if msg.data is None or msg.data.array is None:
        return False
    a = np.asarray(msg.data.array)
    return a.dtype in _DTYPE_TO_CODE


def frame_from_message(msg: SeldonMessage, *, response: bool = False,
                       sidecar: bool = True) -> List[Any]:
    """A SeldonMessage as frame parts.  ``sidecar=True`` additionally
    packs the ambient deadline/trace/tenant/tier (client lanes: the
    binary analogue of forwarding the HTTP headers)."""
    extra: dict = {}
    if msg.data is not None:
        if msg.data.names:
            extra["names"] = list(msg.data.names)
        if msg.data.kind != "tensor":
            extra["kind"] = msg.data.kind
    if msg.meta.tags:
        extra["tags"] = dict(msg.meta.tags)
    if msg.meta.routing:
        extra["routing"] = {k: int(v) for k, v in msg.meta.routing.items()}
    if msg.meta.requestPath:
        extra["requestPath"] = dict(msg.meta.requestPath)
    status = 0
    if msg.status is not None:
        status = int(msg.status.code or (200 if msg.status.status == "SUCCESS"
                                         else 500))
        if msg.status.status == "FAILURE":
            extra["error"] = msg.status.info or "FAILURE"
    elif response:
        status = 200
    if sidecar:
        meta_bytes = current_wire_sidecar(
            extra=extra or None, puid=msg.meta.puid or None)
    else:
        meta_bytes = pack_wire_meta(puid=msg.meta.puid or None,
                                    extra=extra or None)
    arr = None
    if msg.data is not None and msg.data.array is not None:
        arr = np.asarray(msg.data.array)
    return encode_frame(arr, status=status, response=response,
                        meta_bytes=meta_bytes)


def message_from_frame(frame: WireFrame) -> SeldonMessage:
    """A decoded frame as a SeldonMessage — the bridge the gateway and
    the node client use so everything above the wire (routing, shadow,
    firehose, autopilot shape pricing) sees the same object the JSON
    lane builds, minus the JSON."""
    extra = frame.extra()
    meta = Meta(
        puid=frame.meta.get("puid") or "",
        tags=dict(extra.get("tags") or {}),
        routing={k: int(v) for k, v in (extra.get("routing") or {}).items()},
        requestPath=dict(extra.get("requestPath") or {}),
    )
    status = None
    if frame.is_response:
        if frame.status and frame.status != 200:
            status = Status.failure(
                str(extra.get("error") or f"wire status {frame.status}"),
                code=int(frame.status),
            )
        else:
            status = Status()
    data = None
    if frame.array is not None:
        arr = frame.rows() if frame.scales is not None else frame.array
        data = DefaultData(
            array=arr,
            names=list(extra.get("names") or []),
            kind=str(extra.get("kind") or "tensor"),
        )
    return SeldonMessage(data=data, meta=meta, status=status)


def quantize_rows(rows: np.ndarray) -> "tuple[np.ndarray, np.ndarray]":
    """Symmetric per-row int8 quantization for the optional scale-plane
    payload: ``(q, scales)`` with ``value ~= q * scales[row]`` — halves
    (vs f16) or quarters (vs f32) the wire bytes for clients that opt
    in.  Lossy by construction; parity-pinned lanes use exact dtypes."""
    rows = np.asarray(rows)
    if rows.ndim < 2:
        rows = rows.reshape(1, -1)
    amax = np.max(np.abs(rows), axis=1)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(rows / scales[:, None]), -127, 127).astype(np.int8)
    return q, scales
