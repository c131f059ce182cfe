"""KV-block streaming — the disaggregated handoff wire format.

A prefill replica finishes a sequence's chunked prefill holding exactly
two things a decode replica needs: the sequence's finished KV blocks and
its sampling state (pending token, emitted prefix, PRNG key).  This
module is the typed, binary contract that moves them over the PR-8
relay lane (``runtime/udsrelay.py`` ``OP_KVSTREAM``): length-prefixed
tensor frames with memoryview discipline — no JSON, no base64, one
``np.frombuffer`` per tensor on the receive side.

Frame layout (inside the relay frame's payload):

    payload := sub_op(u8) | handoff_id(16s) | body

    KV_BEGIN   header struct + prompt/emitted/key tensors + tier utf8
               -> reserve: the decode replica allocates the blocks
                  (typed 503 when its pool cannot hold them)
    KV_BLOCKS  first_block(u32) n(u32) | per layer, per tensor:
               len(u32) | raw bytes  (k, v [, k_s, v_s] — int8 pools
               ship their scale planes; shapes [n, bs, KV, hd])
               -> receive: staged host-side, NOT yet in the pool
    KV_COMMIT  empty -> the decode replica scatters the staged blocks
               into its pool (one compiled chunk-scatter executable),
               admits the sequence into the decode loop, and answers
               with the finished tokens: n(u32) | int32 raw
    KV_ABORT   empty -> reclaim the reservation (torn handoff)
    KV_STATS   empty -> free(u32) total(u32) waiting(u32) inflight(u32)
               — the free-KV-block score the prefill side's p2c uses

The handoff is chunked (``SELDON_TPU_KV_CHUNK_BLOCKS`` blocks per
KV_BLOCKS frame, default 4) so a 512-token prefill streams while the
decode replica's admission overlaps, and the import path is staged:
reserve -> receive -> commit, with typed failure + block reclaim on a
torn handoff (``runtime/genserver.py`` owns the state machine;
``runtime/servingmesh.py`` drives the sending side)."""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

__all__ = [
    "KV_BEGIN", "KV_BLOCKS", "KV_COMMIT", "KV_ABORT", "KV_STATS",
    "KV_WIRE_VERSION", "KvBeginMeta", "KvExport", "KvWireError",
    "export_blocks", "begin_frame", "block_frames", "commit_frame",
    "abort_frame", "stats_frame", "parse_frame", "parse_begin",
    "parse_blocks", "pack_stats", "unpack_stats", "pack_tokens",
    "unpack_tokens", "chunk_blocks_default", "kv_scatter_chunk_jit",
]

KV_BEGIN = 1
KV_BLOCKS = 2
KV_COMMIT = 3
KV_ABORT = 4
KV_STATS = 5

KV_WIRE_VERSION = 1

_SUB_HEAD = struct.Struct("!B16s")
#: version, n_layers, block_size, kv_heads, head_dim, dtype_code,
#: n_blocks, n_valid, pending, max_new, prompt_len, prefix_len,
#: emitted_len, key_words
_BEGIN_HEAD = struct.Struct("!BHHHHBIIiIIIHH")
_BLOCKS_HEAD = struct.Struct("!II")
_TENSOR_HEAD = struct.Struct("!I")
_STATS_BODY = struct.Struct("!IIII")
_TOKENS_HEAD = struct.Struct("!I")

#: dtype wire codes — int8 pools additionally carry k_s/v_s f32 planes
_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2, "int8": 3}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


class KvWireError(ValueError):
    """Malformed or incompatible KV-stream frame — surfaces as a typed
    4xx/5xx on the relay, never a crash."""


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def chunk_blocks_default() -> int:
    import os

    try:
        return max(1, int(
            os.environ.get("SELDON_TPU_KV_CHUNK_BLOCKS", "") or 4))
    except ValueError:
        return 4


@dataclass
class KvBeginMeta:
    """Everything a decode replica needs to reserve + admit, parsed off
    a KV_BEGIN frame (or built locally for in-process handoffs)."""

    n_layers: int
    block_size: int
    kv_heads: int
    head_dim: int
    dtype: str          # pool dtype name ("float32"|"bfloat16"|"int8"...)
    n_blocks: int       # PRIVATE blocks streamed (prefix blocks excluded)
    n_valid: int        # cache positions already written (global)
    pending: int        # sampled-not-yet-cached token
    max_new: int        # TOTAL generation budget incl. already-emitted
    prefix_len: int     # shared-prefix length the receiver must match
    prompt: np.ndarray  # int32 suffix prompt (recompute-on-preempt base)
    emitted: List[int]  # tokens already emitted (the prefill first token)
    key_data: Optional[np.ndarray]  # per-sequence PRNG key words
    tier: str = "interactive"


@dataclass
class KvExport:
    """A finished prefill, lifted off the device: per-layer block tensors
    plus the sequence's sampling state.  Built on the prefill scheduler
    thread (the device->host gather happens here, before the pool is
    donated into the next dispatch), then handed to the coordinator.

    ``trace_ctx`` is the handoff's pre-minted span context (the
    ``kind="kv_handoff"`` span the coordinator records when the stream
    completes): its traceparent rides the relay METADATA SIDECAR on
    every frame of this handoff — not the kvstream wire format — so the
    decode replica's import/decode spans parent under the handoff span
    and one federated tree covers both processes.  ``tenant`` rides the
    same sidecar for decode-side accounting."""

    meta: KvBeginMeta
    layers: List[Dict[str, np.ndarray]] = field(default_factory=list)
    #: utils/tracing.TraceContext of the kv_handoff span (None = the
    #: request was unsampled or tracing is off — ship no sidecar trace)
    trace_ctx: Any = None
    #: parent span id the kv_handoff span links under (the request span)
    parent_span_id: str = ""
    #: resolved tenant of the originating request ("" = unknown/anon)
    tenant: str = ""
    #: correlation id of the originating request
    puid: str = ""

    @property
    def nbytes(self) -> int:
        return sum(
            arr.nbytes for layer in self.layers for arr in layer.values()
        )


def _layer_names(dtype: str) -> List[str]:
    return ["k", "v", "k_s", "v_s"] if dtype == "int8" else ["k", "v"]


def export_blocks(pool, blocks: List[int]) -> List[Dict[str, np.ndarray]]:
    """Gather ``blocks`` out of every layer of the paged pool to host
    arrays ``[n_blocks, bs, KV, hd]`` (scales ``[n_blocks, bs, KV]``).
    One fancy-index gather per tensor; materialized to numpy so the pool
    can be donated into the next dispatch immediately after."""
    idx = np.asarray(blocks, np.int32)
    out: List[Dict[str, np.ndarray]] = []
    for li in range(len(pool)):
        layer = pool[f"l{li}"]
        out.append({
            name: np.asarray(layer[name][idx]) for name in layer
        })
    return out


# -- frame building (sender side) ---------------------------------------

def begin_frame(export: KvExport, hid: bytes) -> bytes:
    m = export.meta
    code = _DTYPE_CODES.get(m.dtype)
    if code is None:
        raise KvWireError(f"unsupported pool dtype {m.dtype!r}")
    emitted = np.asarray(m.emitted, np.int32)
    key = (np.asarray(m.key_data, np.uint32).reshape(-1)
           if m.key_data is not None else np.zeros((0,), np.uint32))
    prompt = np.asarray(m.prompt, np.int32).reshape(-1)
    head = _BEGIN_HEAD.pack(
        KV_WIRE_VERSION, m.n_layers, m.block_size, m.kv_heads,
        m.head_dim, code, m.n_blocks, m.n_valid, m.pending, m.max_new,
        len(prompt), m.prefix_len, len(emitted), len(key),
    )
    return (_SUB_HEAD.pack(KV_BEGIN, hid) + head + prompt.tobytes()
            + emitted.tobytes() + key.tobytes()
            + m.tier.encode("utf-8", "replace"))


def block_frames(export: KvExport, hid: bytes,
                 chunk_blocks: Optional[int] = None):
    """Yield KV_BLOCKS frames, ``chunk_blocks`` blocks per frame — the
    streaming grain that lets decode-side admission overlap a long
    prefill's transfer."""
    C = chunk_blocks or chunk_blocks_default()
    names = _layer_names(export.meta.dtype)
    n = export.meta.n_blocks
    for first in range(0, n, C):
        hi = min(first + C, n)
        parts = [_SUB_HEAD.pack(KV_BLOCKS, hid),
                 _BLOCKS_HEAD.pack(first, hi - first)]
        for layer in export.layers:
            for name in names:
                raw = np.ascontiguousarray(layer[name][first:hi]).tobytes()
                parts.append(_TENSOR_HEAD.pack(len(raw)))
                parts.append(raw)
        yield b"".join(parts)


def commit_frame(hid: bytes) -> bytes:
    return _SUB_HEAD.pack(KV_COMMIT, hid)


def abort_frame(hid: bytes) -> bytes:
    return _SUB_HEAD.pack(KV_ABORT, hid)


def stats_frame() -> bytes:
    return _SUB_HEAD.pack(KV_STATS, b"\0" * 16)


def pack_stats(free: int, total: int, waiting: int, inflight: int) -> bytes:
    return _STATS_BODY.pack(
        max(0, free), max(0, total), max(0, waiting), max(0, inflight))


def unpack_stats(body: bytes) -> Dict[str, int]:
    if len(body) < _STATS_BODY.size:
        raise KvWireError("short KV_STATS response")
    free, total, waiting, inflight = _STATS_BODY.unpack_from(body, 0)
    return {"free": free, "total": total, "waiting": waiting,
            "inflight": inflight}


def pack_tokens(tokens: np.ndarray) -> bytes:
    t = np.asarray(tokens, np.int32).reshape(-1)
    return _TOKENS_HEAD.pack(t.size) + t.tobytes()


def unpack_tokens(body: bytes) -> np.ndarray:
    if len(body) < _TOKENS_HEAD.size:
        raise KvWireError("short KV_COMMIT token response")
    (n,) = _TOKENS_HEAD.unpack_from(body, 0)
    raw = memoryview(body)[_TOKENS_HEAD.size:_TOKENS_HEAD.size + 4 * n]
    if len(raw) != 4 * n:
        raise KvWireError("truncated KV_COMMIT token response")
    return np.frombuffer(raw, np.int32).copy()


# -- frame parsing (receiver side) --------------------------------------

def parse_frame(payload: bytes) -> "tuple[int, bytes, memoryview]":
    """``(sub_op, handoff_id, body_view)`` off a relay OP_KVSTREAM
    payload."""
    if len(payload) < _SUB_HEAD.size:
        raise KvWireError("short KV-stream frame")
    sub_op, hid = _SUB_HEAD.unpack_from(payload, 0)
    return sub_op, hid, memoryview(payload)[_SUB_HEAD.size:]


def parse_begin(body: memoryview) -> KvBeginMeta:
    if len(body) < _BEGIN_HEAD.size:
        raise KvWireError("short KV_BEGIN header")
    (version, n_layers, block_size, kv_heads, head_dim, code, n_blocks,
     n_valid, pending, max_new, prompt_len, prefix_len, emitted_len,
     key_words) = _BEGIN_HEAD.unpack_from(body, 0)
    if version != KV_WIRE_VERSION:
        raise KvWireError(f"KV wire version {version} not supported")
    dtype = _CODE_DTYPES.get(code)
    if dtype is None:
        raise KvWireError(f"unknown pool dtype code {code}")
    off = _BEGIN_HEAD.size
    need = 4 * (prompt_len + emitted_len + key_words)
    if len(body) < off + need:
        raise KvWireError("truncated KV_BEGIN tensors")
    prompt = np.frombuffer(
        body[off:off + 4 * prompt_len], np.int32).copy()
    off += 4 * prompt_len
    emitted = np.frombuffer(
        body[off:off + 4 * emitted_len], np.int32)
    off += 4 * emitted_len
    key = None
    if key_words:
        key = np.frombuffer(
            body[off:off + 4 * key_words], np.uint32).copy()
        off += 4 * key_words
    tier = bytes(body[off:]).decode("utf-8", "replace") or "interactive"
    return KvBeginMeta(
        n_layers=n_layers, block_size=block_size, kv_heads=kv_heads,
        head_dim=head_dim, dtype=dtype, n_blocks=n_blocks,
        n_valid=n_valid, pending=pending, max_new=max_new,
        prefix_len=prefix_len, prompt=prompt,
        emitted=[int(t) for t in emitted], key_data=key, tier=tier,
    )


def parse_blocks(body: memoryview, meta: KvBeginMeta
                 ) -> "tuple[int, List[Dict[str, np.ndarray]]]":
    """``(first_block_index, per-layer tensors)`` off a KV_BLOCKS body.
    Each tensor is ONE np.frombuffer over the wire bytes (copied into
    the staging buffer by the caller) — the memoryview discipline."""
    if len(body) < _BLOCKS_HEAD.size:
        raise KvWireError("short KV_BLOCKS header")
    first, n = _BLOCKS_HEAD.unpack_from(body, 0)
    off = _BLOCKS_HEAD.size
    names = _layer_names(meta.dtype)
    dt = _np_dtype(meta.dtype) if meta.dtype != "int8" else np.dtype(np.int8)
    shapes = {
        "k": (n, meta.block_size, meta.kv_heads, meta.head_dim),
        "v": (n, meta.block_size, meta.kv_heads, meta.head_dim),
        "k_s": (n, meta.block_size, meta.kv_heads),
        "v_s": (n, meta.block_size, meta.kv_heads),
    }
    dtypes = {
        "k": dt, "v": dt,
        "k_s": np.dtype(np.float32), "v_s": np.dtype(np.float32),
    }
    layers: List[Dict[str, np.ndarray]] = []
    for _ in range(meta.n_layers):
        layer = {}
        for name in names:
            if len(body) < off + _TENSOR_HEAD.size:
                raise KvWireError("truncated KV_BLOCKS frame")
            (nbytes,) = _TENSOR_HEAD.unpack_from(body, off)
            off += _TENSOR_HEAD.size
            raw = body[off:off + nbytes]
            if len(raw) != nbytes:
                raise KvWireError("truncated KV_BLOCKS tensor")
            shape = shapes[name]
            want = int(np.prod(shape)) * dtypes[name].itemsize
            if nbytes != want:
                raise KvWireError(
                    f"KV_BLOCKS tensor {name} carries {nbytes} bytes, "
                    f"expected {want} for shape {shape}")
            layer[name] = np.frombuffer(raw, dtypes[name]).reshape(shape)
            off += nbytes
        layers.append(layer)
    return first, layers


# -- the import scatter --------------------------------------------------

def _kv_scatter_chunk(pool, idx, chunk):
    """Scatter one staged chunk of blocks into the paged pool at local
    block ids ``idx`` — padded entries target the scratch block 0 (their
    values are zeros; scratch exists to absorb garbage), so a single
    fixed chunk width compiles exactly one executable per model."""
    out = {}
    for li, layer in pool.items():
        new = dict(layer)
        for name, vals in chunk[li].items():
            # staged blocks have the wire's shape, the model's heads; a
            # pool row may carry several (models/generate.py
            # init_block_pool): the same values in the same order
            new[name] = layer[name].at[idx].set(
                vals.reshape(vals.shape[:1] + layer[name].shape[1:])
                .astype(layer[name].dtype))
        out[li] = new
    return out


_scatter_jit = None


def kv_scatter_chunk_jit():
    global _scatter_jit
    if _scatter_jit is None:
        import jax

        _scatter_jit = jax.jit(_kv_scatter_chunk, donate_argnums=(0,))
    return _scatter_jit


def scatter_staged(pool, local_blocks: List[int],
                   staged: List[Dict[str, np.ndarray]],
                   chunk_blocks: Optional[int] = None):
    """Write a fully-staged import into the pool, ``chunk_blocks`` at a
    time through the one compiled scatter.  Runs on the scheduler thread
    only — the pool pytree is single-owner by contract."""
    import jax.numpy as jnp

    C = chunk_blocks or chunk_blocks_default()
    n = len(local_blocks)
    fn = kv_scatter_chunk_jit()
    for lo in range(0, n, C):
        hi = min(lo + C, n)
        idx = np.zeros((C,), np.int32)  # pad -> scratch block 0
        idx[: hi - lo] = local_blocks[lo:hi]
        chunk = {}
        for li, layer in enumerate(staged):
            ch = {}
            for name, arr in layer.items():
                pad = np.zeros((C,) + arr.shape[1:], arr.dtype)
                pad[: hi - lo] = arr[lo:hi]
                ch[name] = jnp.asarray(pad)
            chunk[f"l{li}"] = ch
        pool = fn(pool, jnp.asarray(idx), chunk)
    return pool


def validate_against_pool(meta: KvBeginMeta, pool, block_size: int,
                          prefix_len: int,
                          head_dim: Optional[int] = None) -> None:
    """Typed compatibility check before any block is reserved: layer
    count, geometry, dtype and shared-prefix agreement must all match
    the receiving pool or the handoff is refused up front.  ``head_dim``
    is the receiving MODEL's head width: a pool row may carry several
    heads (models/generate.py init_block_pool; None: a row is a head)."""
    n_layers = len(pool)
    l0 = pool["l0"]
    hd = int(head_dim or l0["k"].shape[3])
    kv = int(l0["k"].shape[2]) * int(l0["k"].shape[3]) // hd
    dtype = str(np.dtype(l0["k"].dtype)) if "k_s" not in l0 else "int8"
    # jax bf16 dtype stringifies as 'bfloat16' through np.dtype
    if (meta.n_layers, meta.block_size, meta.kv_heads, meta.head_dim) != \
            (n_layers, block_size, kv, hd):
        raise KvWireError(
            f"handoff geometry (layers={meta.n_layers} "
            f"bs={meta.block_size} kv={meta.kv_heads} hd={meta.head_dim})"
            f" does not match this pool (layers={n_layers} "
            f"bs={block_size} kv={kv} hd={hd})")
    if meta.dtype != dtype:
        raise KvWireError(
            f"handoff pool dtype {meta.dtype} != local {dtype}")
    if meta.prefix_len != prefix_len:
        raise KvWireError(
            f"handoff shared-prefix length {meta.prefix_len} != local "
            f"{prefix_len} — prefill and decode replicas must serve the "
            "same deployment spec")


def pool_dtype_name(pool) -> str:
    l0 = pool["l0"]
    if "k_s" in l0:
        return "int8"
    return str(np.dtype(l0["k"].dtype))
