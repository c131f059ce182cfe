"""Continuous-batching generation server — paged KV blocks, per-step
admission, chunked prefill.

One KV layout and three device programs serve all generation
(``models/generate.py``: the block pool, ``paged_forward``,
``paged_decode_round``, ``paged_spec_round``).  The STATIC lane
(``generate()``) gives each request a private pool: the request's batch
owns the device for its whole lifetime and a long prefill stalls every
co-batched decode.  This module is the CONTINUOUS lane over the same
programs — the scheduler shape production TPU serving stacks use
(Orca/vLLM-style) — and differs from the static lane only in who owns the
pool and the tables:

  * **Paged KV pool** — one process-wide per-layer block pool
    (``models/generate.py init_block_pool``); sequences hold block
    tables, the :class:`BlockAllocator` does alloc/free/eviction
    (preempt-youngest recompute) and occupancy accounting.  A shared
    prefix is computed once, at boot, into PINNED blocks: every sequence's
    table references the same physical blocks (a partly filled boundary
    block is copied per sequence, pool to pool).
  * **Per-step admission** — each scheduler iteration admits newly
    arrived sequences into the in-flight decode batch, runs one decode
    ROUND (``span`` single-token steps as one ``lax.scan`` — one device
    program, one host sync), retires finished rows (the device-side
    after-eos latch composing with the ``mask_after_eos`` output
    contract), and hands tokens to the per-request streams.
  * **One round ahead** — the iteration dispatches round k+1 before it
    reads round k back: which rows ride a round is arithmetic, and what
    a round hands the next (pending token -- or, diffusion blocks, the
    block whose K/V the next round writes -- after-eos latch, sampling
    key) stays on the device in a per-slot carry, so the device goes
    from round to round without waiting for the host (``GenServer
    ._tick``, ``_depth``, ``_carry_ops``).
  * **Chunked prefill** — prompts are consumed ``prefill_chunk`` tokens
    at a time, interleaved between decode rounds, so a 512-token prompt
    stalls in-flight streams for at most one chunk instead of a full
    prefill.
  * **Composition** — int8 KV pools, shared-prefix block reuse, and
    speculative draft/verify rounds (``paged_spec_round``) all run
    through the same admission/retirement machinery.

What it serves it is TOLD (``models/served.py``, one description built
from the configuration): how a round is driven, what the pool holds, the
lanes such a generator cannot take, which kernels serve it over the pool,
and the counts each dispatched call is given -- the same numbers on the
dispatching span and in the tick record.  Nothing here reads a layer's
kind or branches on an architecture.

Greedy scheduler output is token-identical to one-shot ``generate()``
(tests/test_genserver.py pins it); sampled decoding uses per-SEQUENCE
PRNG keys, so co-batched requests cannot couple through a shared batch
key (the static lane keys each row of a request the same way).

What the scheduler cannot serve stays on the static lane: an MoE
generator (capacity routing couples co-batched rows —
``continuous_spec`` returns None) and a generator inside a graph of
several units (runtime/engine.py).

Tuning knobs (docs/operations.md "tuning the generation scheduler"):
``SELDON_TPU_GEN_BLOCK_SIZE`` (16), ``SELDON_TPU_GEN_POOL_BLOCKS``
(1024), ``SELDON_TPU_GEN_SLOTS`` (64), ``SELDON_TPU_GEN_SPAN`` (8),
``SELDON_TPU_GEN_PREFILL_CHUNK`` (128, the interleave floor),
``SELDON_TPU_GEN_PREFILL_CHUNK_MAX`` (512, the adaptive-chunk
ceiling).  Kill switch:
``SELDON_TPU_GEN_CONTINUOUS=0`` serves every generator on the static
lane (runtime/engine.py).
"""

from __future__ import annotations

import collections
import concurrent.futures
import functools
import hashlib
import json
import logging
import os
import queue
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from seldon_core_tpu.messages import LoadShedError
from seldon_core_tpu.runtime.autopilot import SHED_INFO_PREFIX
from seldon_core_tpu.runtime.brownout import BROWNOUT, BROWNOUT_INFO_PREFIX
from seldon_core_tpu.runtime.compilecache import (
    ProgramStore,
    program_record_path,
    read_program_record,
    write_program_record,
)
from seldon_core_tpu.runtime.qos import current_tier, tier_rank
from seldon_core_tpu.utils.costledger import costledger_enabled
from seldon_core_tpu.utils.genperf import BOOT
from seldon_core_tpu.utils.hotrecord import SPINE
from seldon_core_tpu.utils.perf import OBSERVATORY
from seldon_core_tpu.utils.telemetry import (
    RECORDER,
    install_compile_cache_listener,
    thread_cache_hits,
)

__all__ = ["BlockAllocator", "GenRequest", "GenServer"]

logger = logging.getLogger(__name__)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class _Phase:
    """One scheduler phase on both clocks: a ``jax.profiler.TraceAnnotation``
    (always on — an inactive TraceMe costs well under a microsecond — so a
    ``POST /profile/start`` window shows the phase on the profiler's clock
    beside the device ops) and, with ``into``, its ``perf_counter`` seconds
    added to ``into[key]`` for ``/genperf``.  One helper opens both, so the
    two can never mean different intervals.  ``args`` ride the annotation
    (read back from the trace by bench/lib/trace_scopes.py).  Device
    seconds are not booked here: a program's are taken where its completion
    is observed (``GenServer._await``, utils/genperf.py
    ``booked_device_s``)."""

    __slots__ = ("_ann", "_into", "_key", "_t0")

    def __init__(self, name: str, into: Optional[Dict[str, float]] = None,
                 key: str = "", **args: int):
        import jax.profiler

        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._into = into
        self._key = key

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._into is not None:
            self._into[self._key] = (self._into.get(self._key, 0.0)
                                     + time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


class _BootPhase(_Phase):
    """A phase of the boot: a ``_Phase`` -- so the interval is on the
    profiler's clock too -- whose two ``time.monotonic()`` stamps (the boot
    timeline's clock, utils/genperf.py ``BOOT``) and the executables the
    persistent cache handed this thread meanwhile go to ``done(start, end,
    cache_hits)`` when it ends, raised through or not.  Opened a few dozen
    times a boot and once a shape's first dispatch; never by a tick that
    dispatches a shape it has dispatched before."""

    __slots__ = ("_done", "_start", "_hits")

    def __init__(self, name: str, done, **args: int):
        super().__init__(name, **args)
        self._done = done

    def __enter__(self) -> "_BootPhase":
        self._start = time.monotonic()
        self._hits = thread_cache_hits()
        super().__enter__()
        return self

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self._done(self._start, time.monotonic(),
                   thread_cache_hits() - self._hits)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


# The scheduler function that dispatches each kind of program: its spans'
# names begin with it.
_FUNCTIONS = {"prefill": "GenServer._prefill_tick",
              "decode": "GenServer._decode_round"}

# What a dispatching span says of its program's work beside ``seq``, ``rows``,
# ``real_rows``, ``nblk`` and a round's ``inplace``: the call's counts by these
# names (bench/lib/trace_calls.py joins them to the device's calls).
_DECODE_SPAN = ("kv_positions", "passes", "blocks", "expert_slots")
_PREFILL_SPAN = ("tokens", "kv_positions", "attended", "expert_slots",
                 "carried_rows")

# The most int32 entries (rows x width) a decode round's block table is
# widened to where the in-place kernel serves.  The flattened table is the
# kernel's scalar-prefetch operand, copied to scalar memory once a layer a
# step, and the budget bounds that copy.  Measured on one TPU v5e (PERF.md
# section 6, PR 28: 30 layers, 32 padded rows of which 8 live, block 256):
# device time of a decode round at widths 8 / 32 / 128 / 512 blocks
# 66.479 / 66.481 / 66.482 / 66.492 ms, attention's share of it
# 6.42 / 6.44 / 6.53 / 6.86% -- 4 Ki entries (16 KiB, width 128 at 32
# rows) cost 0.005% of a step, so the first budget tried stands.
_DECODE_TABLE_ENTRIES = 4096


def _device_memory_bytes() -> Optional[int]:
    """What the first device says it can hold, or None where the backend
    does not say (the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _decode_table_width(inplace, rows: int, need: int, row_max: int) -> int:
    """Width of a decode round's block table: ``rows`` padded rows, the
    longest live row needing ``need`` blocks, a row never holding more than
    ``row_max``.

    On the gather path the width sizes the gathered ``[B, KV, nblk*bs, hd]``
    copy and stays as narrow as a power of two allows.  Where the in-place
    kernel serves (ops/paged_attention.py) an entry past a row's length
    costs no DMA and no compute, and every width is one more compiled
    program to trace and load: the table gets one width per row count, the
    widest power of two a row can fill that keeps the table within
    ``_DECODE_TABLE_ENTRIES``, and grows by powers of two only for a row
    that outgrows it."""
    width = _pow2(need)
    cap = min(row_max, _DECODE_TABLE_ENTRIES // rows)
    if inplace and cap >= 1:
        width = max(width, 1 << (cap.bit_length() - 1))
    return width


# Of how many decode rounds the scheduler runs one the synchronous way, to
# read a fenced round's slack again (``GenServer._depth``).  The slack --
# dispatch -> the module starting, the module ending -> ``block_until_ready``
# returning -- is what the wake-up before a round's end aims at (``_pace``):
# round k+1 has to be dispatched that long before round k is seen to end, and
# the host cannot see it while it keeps a round ahead.  It moves with the
# machine's moment (PERF.md section 6, PR 24: 8.5-11 ms after a cold start,
# 3.2 ms warm), so it is read again; a fenced tick costs two hand-overs, so
# one round in 19 keeps that under 1% of the rounds' time whatever a round
# lasts.
_FENCE_EVERY = 19

# A round's device seconds are the least of this many booked readings
# (``GenServer._await``): a reading can only be too long -- the host came
# late to a completion -- so the least of a few is what the sleep before
# the next round cannot move.
_ROUND_READINGS = 8


# How many threads fetch (or, cold, compile) and load the programs a boot
# brings up ahead of its first request, behind the ONE thread that traces and
# lowers them (``GenServer._load``).  Measured on one TPU v5e host of 13 cores
# (PERF.md section 6, PR 33, calls P33a / P33b: the benchmark's 24 programs
# of 30 layers, warm persistent cache).  A program is ~1.5 s of Python under
# the GIL (trace 0.8-1.3 s, jaxpr -> MLIR 0.35-0.45 s) and 1.43 s of C++
# (cache key, file, deserialise, load); 24 of them one after another 70.0 s.
# Threads that each do both fight for the GIL: 2 / 4 / 8 / 24 of them read
# 60.3 / 50.3 / 52.9-54.7 / 54.3 s.  One tracer with 1 / 2 / 3 / 4 / 8
# loaders behind it reads 49.1 / 43.3 / 41.7 / 44.7 / 43.9 s -- the tracer's
# 40-43 s is the floor, two loaders reach it and more cost nothing.  Cold,
# the loaders are what compiles side by side (5-12 s a program: six decode
# programs behind eighteen fetched ones in 49.1 s with 8), so the constant is
# the widest that still leaves the tracer and the scheduler a core each.
# Since PR 37 a program traces its decoder block once, not once a layer
# (``generate._paged_block`` is a ``jit`` of its own), and the same 24
# programs are 10.5-10.7 s of the tracer (/stats ``boot_trace_s``) inside a
# load of 14.3-14.7 s (PERF.md section 6, PR 37, call P37b; the sparse
# configuration's 24 of 7 layers: 28.1-29.1 s inside 29.4-30.3, its decode
# programs 2.4 s each, most of it the kernels' and the expert layer's own
# trace and lowering): the tracer is still the longer of the two, so the
# arrangement stands.  Since PR 53 a boot whose programs the store holds has
# no tracer: the eight deserialise and load 10-24 executables in 2.9-14.9 s
# (four read 14.2-14.3 s where eight read 14.4-14.9: PERF.md section 6,
# PR 53), a thread's first one costing it seconds (ROADMAP A8 (k)).
_LOAD_THREADS = 8

#: the executables that JAX's persistent cache handed some compile of this
#: process: not serialised again where the backend does not give such a one
#: back whole (``GenServer._keep``)
_LOADED_FROM_DISK: "weakref.WeakSet" = weakref.WeakSet()


@functools.lru_cache(maxsize=None)
def _keep_out_of_program_locations() -> None:
    """Tell JAX that this file's frames are no part of a traced program's
    source locations.  A Pallas kernel's body is serialised with its MLIR
    locations, up to ten frames of the Python stack it was traced under,
    and -- unlike the HLO around it -- hashed as it stands into the
    persistent compile cache's key (PERF.md section 6, PR 33).  The frames
    above ``paged_decode_round`` are this file's: the tick's when a request
    first needs a shape, ``_load``'s worker's when a boot loads it from the
    record.  With them out, both trace one program under one key, and an
    edit that moves a line here no longer re-compiles the decode programs.
    JAX's own libraries register themselves the same way; where this JAX
    has no such registry the keys differ as before, and a program is at
    worst compiled once more."""
    try:
        from jax._src import source_info_util

        source_info_util.register_exclusion(os.path.abspath(__file__))
    except Exception:  # noqa: BLE001 - a private registry: absent, keys differ
        logger.debug("no source-location registry in this JAX", exc_info=True)


def _abstract(x):
    """``x`` as a lowering sees it and no more: shape, dtype and, where
    the array is committed to its devices, the sharding -- uncommitted
    arrays and host arrays lower alike (``jax.jit`` keys on exactly this)."""
    import jax

    committed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if committed else None)


@functools.lru_cache(maxsize=None)
def _carry_ops():
    """The tiny jitted programs over the scheduler's per-slot carry -- what
    one decode round hands the next, kept on the device so that a round
    can be dispatched before the one ahead of it was read back.

    ``carry``: ``{"tok": int32 [S], "seen": bool [S]}`` and, for sampled
    decoding, ``"keys": uint32 [S, K]`` key data; S = slots + 1, the last
    entry scratch for padded rows.  For a generator whose prefill picks no
    token (diffusion blocks, models/served.py ``picks_first``) ``tok`` is
    ``[S, quantum]``: the block a round left fixed and not yet in the pool,
    as the program hands it on (``generate._denoising_round``).  Every
    shape but the batch's row count is fixed, so each program compiles
    once a row count (``GenServer._init_device`` loads them all).

    * ``take(carry, idx)`` -> a round's ``token``, ``seen_eos`` and
      ``keys`` inputs: a gather by the batch's slot indices.  ``take(carry,
      idx, held)`` is the same for such a generator, ``held`` being what
      the host uploads (``Served.held``): a row it marks as bringing a
      block takes the block and the latch at its slot, every other row
      its ``held`` ids and an open latch -- whatever the slot's last
      holder left there -- so a row carries its block by slot, between
      padded row counts too, and the host reads nothing back to say which.
    * ``put(carry, idx, tok, seen, keys)`` -> ``(carry', key data)``: the
      round's ``token'`` / ``seen_eos'`` / ``keys'`` scattered back (keys
      typed as the program returns them, or raw key data from the host).
    * ``first(carry, logits, idx, held, held_tok, key_data)`` ->
      ``(carry', first tokens [B], key data')``: the first token of every
      row whose prompt ended in this chunk, picked on the device --
      argmax, or ``sample_token`` under the first half of the row's split
      key as the host used to -- and scattered into the carry.  ``held``
      marks rows readmitted after a preemption, whose pending token
      ``held_tok`` (and key) the host restores instead of sampling."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.generate import sample_token

    def take(carry, idx, held=None):
        keys = carry.get("keys")
        tok, seen = carry["tok"][idx], carry["seen"][idx]
        if held is not None:
            brings = held[:, 0] < 0     # ``Served.held``'s mark (BRINGS)
            tok, seen = jnp.where(brings[:, None], tok, held), seen & brings
        return (tok, seen,
                None if keys is None
                else jax.random.wrap_key_data(keys[idx]))

    def put(carry, idx, tok, seen, keys):
        out = {"tok": carry["tok"].at[idx].set(tok),
               "seen": carry["seen"].at[idx].set(seen)}
        if keys is not None and jnp.issubdtype(keys.dtype,
                                               jax.dtypes.prng_key):
            keys = jax.random.key_data(keys)
        if "keys" in carry:
            out["keys"] = carry["keys"].at[idx].set(keys)
        return out, keys

    def first(carry, logits, idx, held, held_tok, key_data, *,
              temperature, top_k, top_p, eos_token):
        if temperature > 0.0:
            split = jax.vmap(jax.random.split)(
                jax.random.wrap_key_data(key_data))
            tok = jax.vmap(lambda lg, kk: sample_token(
                lg[None, :], kk, temperature, top_k, top_p)[0])(
                    logits, split[:, 0])
            key_data = jnp.where(held[:, None], key_data,
                                 jax.random.key_data(split[:, 1]))
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = jnp.where(held, held_tok, tok)
        seen = (tok == eos_token) if eos_token >= 0 else jnp.zeros_like(held)
        carry, key_data = put(carry, idx, tok, seen, key_data)
        return carry, tok, key_data

    return (jax.jit(take), jax.jit(put, donate_argnums=(0,)),
            jax.jit(first, donate_argnums=(0,), static_argnames=(
                "temperature", "top_k", "top_p", "eos_token")))


class _Flight:
    """One dispatched device program whose results the host has not read:
    what ``_await`` waits on, what the collect step reads back, and the
    rows it will credit -- decided by arithmetic at dispatch, because the
    next program may already be queued behind this one."""

    __slots__ = ("kind", "rows", "ready", "out", "keys", "t_dispatch",
                 "t_done", "size", "chunk", "host_s", "attr", "read", "skip",
                 "seq")

    def __init__(self, kind: str, size: int, rows: list, ready, out, keys,
                 t_dispatch: float, attr: tuple, seq: int = 0):
        self.kind = kind              # "decode" | "prefill"
        self.seq = seq                # the server's dispatch number: what
        #                               its spans on a trace are joined by
        self.size = size              # padded row count
        self.rows = rows              # decode: (seq, take); prefill:
        #                               (seq, row index, fresh first token)
        self.ready = ready            # the output whose readiness is waited
        self.out = out                # tokens to read back (None: nothing)
        self.keys = keys              # sampled: the rows' new key data
        self.t_dispatch = t_dispatch
        self.attr = attr              # cost ledger: (padded units, rows)
        self.t_done: Optional[float] = None   # observed completion
        self.chunk = 0                # prefill: the saturated chunk width
        self.read = None              # the experts read (an int32), if any
        self.skip = None              # decode: a row's tokens start so far
        #                               into the round (its prompt's remainder)
        self.host_s = 0.0             # prefill: build + dispatch wall


class BlockAllocator:
    """Host-side free-list allocator over the device block pool.

    Block 0 is the scratch block (masked/pad writes) and is never handed
    out.  ``pin`` marks shared-prefix blocks permanent: they count toward
    occupancy once and ``free`` refuses them, so a retiring sequence can
    never return a block every other sequence's table still references.
    Freed ids go back on the free list FIFO — fragmentation cannot exist
    by construction (any free block serves any sequence; the table adds
    the indirection), which is the point of paging.

    Thread-safety + the remote-import path (runtime/servingmesh.py): the
    relay handler reserves blocks for an in-flight KV handoff from the
    event-loop thread while the scheduler thread allocs/frees for live
    sequences, so every mutation takes the internal lock.  ``reserve``
    puts blocks in a typed RESERVED state: they are out of the free list
    (so eviction pressure cannot re-allocate them mid-import — victims
    only ever free blocks owned by a live sequence, and a reserved block
    belongs to none) and ``free`` REFUSES them until ``commit_reserved``
    turns them into normally-owned blocks or ``release_reserved``
    reclaims them (torn handoff) — a double release can't corrupt the
    free list either way."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("pool needs at least 2 blocks (1 is scratch)")
        self.num_blocks = int(num_blocks)
        self._free: deque = deque(range(1, self.num_blocks))
        self._pinned: set = set()
        self._reserved: set = set()
        self._lock = threading.Lock()
        self.high_water = 0

    @property
    def capacity(self) -> int:
        return self.num_blocks - 1  # scratch excluded

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> Optional[List[int]]:
        """n blocks or None — the caller queues (never crashes) on a full
        pool."""
        with self._lock:
            if n < 0 or len(self._free) < n:
                return None
            out = [self._free.popleft() for _ in range(n)]
            self.high_water = max(self.high_water, self.used)
            return out

    def reserve(self, n: int) -> Optional[List[int]]:
        """Allocate n blocks into the RESERVED state for an in-flight
        remote import — invisible to eviction, refused by ``free``."""
        blocks = self.alloc(n)
        if blocks is not None:
            with self._lock:
                self._reserved.update(blocks)
        return blocks

    def commit_reserved(self, blocks: List[int]) -> None:
        """Reserved -> owned: the import committed and a live sequence's
        table now references these blocks (normal free applies)."""
        with self._lock:
            self._reserved.difference_update(blocks)

    def release_reserved(self, blocks: List[int]) -> None:
        """Reclaim a torn handoff's reservation back to the free list."""
        with self._lock:
            for b in blocks:
                if b in self._reserved:
                    self._reserved.discard(b)
                    self._free.append(b)

    def pin(self, blocks: List[int]) -> None:
        with self._lock:
            self._pinned.update(blocks)

    def free(self, blocks: List[int]) -> None:
        with self._lock:
            for b in blocks:
                if b not in self._pinned and b not in self._reserved:
                    self._free.append(b)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "total": self.capacity,
                "used": self.used,
                "pinned": len(self._pinned),
                "reserved": len(self._reserved),
                "high_water": self.high_water,
            }


class _Sequence:
    """One row of one request riding the scheduler."""

    __slots__ = (
        "sid", "request", "row", "prompt", "prompt0", "max_new", "state",
        "n_valid", "blocks", "draft_blocks", "pending", "prefill_pos",
        "emitted", "done", "key_data", "admit_order", "retire_reason",
        "t_start", "events", "slot", "inflight", "rode",
    )
    WAITING, PREFILL, RUNNING, DONE = range(4)

    def __init__(self, sid: int, request: "GenRequest", row: int,
                 prompt: np.ndarray, max_new: int):
        self.sid = sid
        self.request = request
        self.row = row
        self.prompt = prompt            # int32 [S] (suffix when prefixed)
        self.prompt0 = prompt           # as submitted: preempt rebuild base
        self.max_new = int(max_new)
        self.state = self.WAITING
        self.n_valid = 0                # cache positions written (global)
        self.blocks: List[int] = []     # PRIVATE blocks only
        self.draft_blocks: List[int] = []   # speculative mode
        #: sampled, not yet in cache.  The host's copy: current whenever
        #: nothing of this row is in flight (``inflight == 0``); the round
        #: itself reads the device's, in the carry at ``slot``
        self.pending: Optional[int] = None
        self.slot = -1                  # index into the device-side carry
        self.inflight = 0               # tokens dispatched, not yet read
        #: a decode round was dispatched for it since ``_admit``: where a
        #: round hands a block on (``Served.held``), its slot holds one
        self.rode = False
        self.prefill_pos = 0            # prompt tokens consumed
        self.emitted: List[int] = []
        self.done = False
        self.key_data: Optional[np.ndarray] = None  # per-seq PRNG key
        self.admit_order = -1
        self.retire_reason = ""
        self.t_start = 0.0              # epoch at admission (span base)
        #: lifecycle timeline (enqueue -> admit -> prefill chunks ->
        #: decode rounds -> retire, with preemption/recompute events) —
        #: populated ONLY for sampled traces, emitted as one
        #: "gen_sequence" span's events at retirement
        self.events: List[Dict[str, Any]] = []


class _KvImport:
    """One in-flight remote-block import on a decode replica: reserved
    pool blocks + host-side staging buffers, keyed by handoff id.
    reserve -> receive -> commit; a torn handoff (abort, or the TTL
    reaper) releases the reservation with zero leaked blocks."""

    __slots__ = ("hid", "meta", "blocks", "staged", "received",
                 "created", "created_epoch", "seq", "trace_ctx")

    def __init__(self, hid: bytes, meta, blocks: List[int], staged):
        self.hid = hid
        self.meta = meta
        self.blocks = blocks
        self.staged = staged          # per-layer host arrays [n, bs, ...]
        self.received = np.zeros((meta.n_blocks,), bool)
        self.created = time.monotonic()
        self.created_epoch = time.time()
        self.seq: Optional[_Sequence] = None
        #: the handoff span's context off the relay sidecar (the BEGIN
        #: frame's traceparent) — decode-side import/decode spans parent
        #: under the prefill side's kv_handoff span through this
        self.trace_ctx = None

    def receive(self, first: int, layers) -> None:
        from seldon_core_tpu.runtime.kvstream import KvWireError

        n = layers[0]["k"].shape[0] if layers else 0
        if first < 0 or first + n > self.meta.n_blocks:
            raise KvWireError(
                f"block chunk [{first}, {first + n}) outside the "
                f"announced {self.meta.n_blocks} blocks")
        for stage, chunk in zip(self.staged, layers):
            for name, arr in chunk.items():
                stage[name][first:first + n] = arr
        self.received[first:first + n] = True

    def complete(self) -> bool:
        return bool(self.received.all())


class GenRequest:
    """One client request: N sequences plus the delivery surface — a
    Future holding the assembled ``[B, max_new]`` token array (unary) or
    a bounded queue of ``[B, <=chunk]`` arrays (streaming)."""

    def __init__(self, rows: int, chunk: Optional[int], max_new: int,
                 tier: Optional[str] = None):
        self.rows = rows
        self.chunk = chunk              # None = unary
        self.max_new = int(max_new)
        #: latency tier (runtime/qos.py): admission prefers interactive
        #: sequences, and preemption prefers victims from lower tiers
        self.tier = tier or "interactive"
        self.seqs: List[_Sequence] = []
        self.future: concurrent.futures.Future = concurrent.futures.Future()
        # unbounded on purpose: a stream buffers at most max_new tokens
        # per row, so the natural bound is the generation length — a
        # bounded queue could deadlock a slow consumer against the
        # scheduler thread
        self.queue: "queue.Queue" = queue.Queue()
        self.delivered = 0              # stream tokens handed out per row
        self.cancelled = False
        # the scheduler's stamps of a stream's time to first token
        # (/genperf ``requests``), perf_counter only: submitted, first
        # sequence admitted, first chunk put on ``queue``.  The lane takes
        # the two around them (handler entry, chunk handed to its writer)
        # itself, in engine.generate_stream
        self.t_submit = time.perf_counter()
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.ttft_recorded = False
        # the submitting request's trace context + QoS identity, captured
        # on the CALLER's thread (contextvars don't cross into the
        # scheduler thread): per-sequence prefill/decode spans parent
        # under the request span, and handoff sidecars carry the tenant
        from seldon_core_tpu.runtime.qos import current_tenant
        from seldon_core_tpu.utils.tracing import current_trace_context

        self.trace_ctx = current_trace_context()
        self.tenant = current_tenant() or ""

    def cancel(self) -> None:
        self.cancelled = True


class GenServer:
    """The continuous-batching scheduler for one generator deployment.

    Device work and all bookkeeping run on ONE daemon worker thread
    (started lazily at the first submit; jax dispatch from a single
    thread, callers bridge through thread-safe queues/futures).  The
    engine builds one of these from the unit's ``continuous_spec``
    (runtime/engine.py); ``SELDON_TPU_GEN_CONTINUOUS=0`` keeps the old
    static path."""

    def __init__(
        self,
        params,
        cfg,
        *,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_token: int = -1,
        max_new_tokens: int = 32,
        prefix_ids=None,
        draft_params=None,
        draft_cfg=None,
        spec_k: int = 4,
        seed: int = 0,
        block_size: Optional[int] = None,
        num_blocks: Optional[int] = None,
        slots: Optional[int] = None,
        span: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        mesh=None,
        role: str = "unified",
        coordinator=None,
    ):
        self.params = params
        self.cfg = cfg
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token = int(eos_token)
        self.max_new_tokens = int(max_new_tokens)
        self.prefix_ids = prefix_ids  # int32 [P] shared-prefix token ids
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec = draft_params is not None
        self.spec_k = int(spec_k)
        self.seed = int(seed)
        if self.spec and (self.temperature > 0.0
                          or cfg.kv_quant == "int8"
                          or prefix_ids is not None):
            # mirror speculative_generate's guards: greedy, float KV
            raise ValueError(
                "speculative continuous mode is greedy/float-KV only")
        if self.spec and role in ("prefill", "decode"):
            # a handoff would need the draft pool streamed too — out of
            # the disaggregation contract; serve speculative unified
            raise ValueError(
                "speculative decoding does not compose with "
                "disaggregated prefill/decode roles")
        self.block_size = block_size or _env_int(
            "SELDON_TPU_GEN_BLOCK_SIZE", 16)
        self.num_blocks = num_blocks or _env_int(
            "SELDON_TPU_GEN_POOL_BLOCKS", 1024)
        self.slots = slots or _env_int("SELDON_TPU_GEN_SLOTS", 64)
        self.span = span or _env_int("SELDON_TPU_GEN_SPAN", 8)
        self.prefill_chunk = prefill_chunk or _env_int(
            "SELDON_TPU_GEN_PREFILL_CHUNK", 128)
        # what the scheduler is told about the generator it serves, and
        # the lanes, sizes and pools such a generator cannot take
        from seldon_core_tpu.models.served import served

        self._served = served(cfg)
        self._served.refuse(
            draft=self.spec, prefix=prefix_ids is not None,
            sampled=self.temperature > 0.0,
            roles=role in ("prefill", "decode"), mesh=mesh is not None)
        self._served.whole(span=self.span, block_size=self.block_size,
                           prefill_chunk=self.prefill_chunk)
        self._served.refuse_pool(self.num_blocks, params,
                                 _device_memory_bytes)
        # bounded admission queue: sustained overload must fail typed
        # (retryable 503 via LoadShedError) with flat memory, never grow
        # the waiting deques without limit.  Generous by default — the
        # bound exists to cap the failure mode, not to shape traffic
        # (token buckets and the brownout ladder do that)
        self.max_waiting = _env_int("SELDON_TPU_GEN_MAX_WAITING", 4096)
        # dispatch-latency-aware adaptive chunking: prefill_chunk is the
        # FLOOR (the guaranteed interleave grain); when a prefill tick's
        # wall time is dispatch-dominated — doubling the chunk leaves the
        # wall nearly flat — the effective chunk probes upward toward
        # PREFILL_CHUNK_MAX, because a bigger chunk then shortens every
        # TTFT path at zero stall cost.  When doubling makes the tick
        # materially slower (compute-bound), it backs off and latches.
        self.prefill_chunk_max = max(
            _env_int("SELDON_TPU_GEN_PREFILL_CHUNK_MAX", 512),
            self.prefill_chunk,
        )
        self._chunk_eff = self.prefill_chunk
        self._chunk_wall: Dict[int, List[float]] = {}  # C -> [ema_s, n]
        self._chunk_latched = self._chunk_eff >= self.prefill_chunk_max
        # scheduler state (worker thread only, except arrivals)
        self._arrivals: deque = deque()
        self._waiting: deque = deque()
        self._prefilling: List[_Sequence] = []
        self._active: List[_Sequence] = []
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        self._pool = None
        self._kernels = None    # which ones serve over the pool, once it is
        # what one decode round hands the next, on the device (_carry_ops):
        # a sequence holds a slot of it from admission to retirement
        self._carry = None
        self._slot_free: deque = deque(range(self.slots))
        self._zero_keys: Dict[int, Any] = {}   # greedy: one a row count
        #: dispatched programs whose results are unread, in device order.
        #: Between ticks it holds at most the one decode round the tick
        #: keeps ahead of its own readback (_depth)
        self._unread: deque = deque()
        #: programs dispatched since boot, prefill and decode alike: the
        #: number a program's spans carry (dispatch, ``/wait``, ``/emit``)
        self._dispatched = 0
        # the server's own observations the wake-up before a round's end
        # rests on (_pace): the last observed completion and whether the
        # host was waiting when it came, a round's booked device seconds by
        # row count (the last few: the estimate is their least), a fenced
        # round's slack and how many rounds ago it was read, and the one
        # closed-loop term, the guard
        self._last_done = 0.0
        self._last_done_seen = False
        self._round_s: Dict[int, deque] = {}
        self._slack_s = 0.0
        self._guard_s = 0.0
        self._since_fence = _FENCE_EVERY
        self._paced: Optional[_Flight] = None
        self._device_ready = False
        self._device_init_lock = threading.Lock()
        self._draft_pool = None
        self._allocator: Optional[BlockAllocator] = None
        self._draft_allocator: Optional[BlockAllocator] = None
        self._prefix_blocks: List[int] = []     # shared full blocks
        self._prefix_tail: Optional[int] = None  # pinned boundary block
        self._prefix_len = 0
        self._seq_counter = 0
        self._admit_counter = 0
        # disaggregated serving mesh (runtime/servingmesh.py): the
        # replica's generation role, the optional device mesh the paged
        # pool (and the unit's params) shard over, and — prefill role —
        # the coordinator that streams finished KV blocks to a decode
        # peer.  Unified role with no mesh is bit-for-bit the PR-7 path.
        self.role = role if role in ("unified", "prefill", "decode") \
            else "unified"
        self.mesh = mesh
        self.coordinator = coordinator
        #: finished handoffs coming back from the coordinator thread:
        #: (seq, tokens-or-exception) drained on the scheduler thread
        self._handoff_done: deque = deque()
        #: sequences whose handoff is in flight (exported, not yet
        #: drained) — they live in no scheduler list, so _fail_all must
        #: fail them from here or their requests hang at stop()
        self._handoff_seqs: "dict" = {}
        self._handoff_inflight = 0
        #: decode role: in-flight remote imports keyed by handoff id
        #: (reserve -> receive -> commit; the TTL reaper reclaims torn
        #: ones) and committed imports awaiting scheduler admission
        self._imports: Dict[bytes, Any] = {}
        self._remote_arrivals: deque = deque()
        self._import_ttl_s = float(
            _env_int("SELDON_TPU_KV_HANDOFF_TTL_S", 30))
        self.imports_committed_total = 0
        self.imports_reclaimed_total = 0
        # lifetime counters for /stats + the gen_* Prometheus families
        self.admitted_total = 0
        self.retired_total: Dict[str, int] = {}
        self.preempted_total = 0
        self.steps_total: Dict[str, int] = {}
        self.tokens_emitted_total = 0
        self.tick_errors_total = 0
        # the distinct shapes dispatched since boot — each one a compiled
        # program a fresh process traces and loads: (rows, chunk, nblk) of
        # prefill, (rows, nblk) of decode.  /stats reports their counts
        self._programs: Dict[str, set] = {"prefill": set(), "decode": set()}
        # ... and the shapes this boot loaded before its first request,
        # from the record an earlier boot of the same deployment left beside
        # the persistent compile cache (_load_programs; '' = no record is
        # kept: no cache, a mesh, a draft model)
        self._loaded: Dict[str, set] = {"prefill": set(), "decode": set()}
        self._missed = 0            # dispatched shapes it had not loaded
        #: this server's number in the process's boot timeline (``BOOT``):
        #: its engine's, set by the engine like ``cost_deployment``, else
        #: its own from ``_init_device`` on
        self.boot_server = 0
        # its wall seconds inside ticks and inside idle waits since,
        # summed by the two ``_Phase``s of ``_run``
        self._since_boot: Dict[str, float] = {}
        self._boot_logged = False
        self._record_path = ""
        self._identity = ""
        # the executables of the two paged programs this server dispatches,
        # by (kind, shape): loaded from the program store beside the record
        # or compiled here, and then stored (_load, _bring_up).  A server
        # that keeps no record keeps no store and no table: its ticks make
        # the ``jit`` call
        self._store: Optional[ProgramStore] = None
        self._executables: Dict[tuple, Any] = {}
        self._stored = 0            # of the loaded, how many the store held
        self._storing: Optional[concurrent.futures.Executor] = None
        # flight-recorder scratch (utils/genperf.py): the bubble ledger
        # stamps the END of every tick and classifies the gap before the
        # NEXT one by how this one ended; the per-tick accumulators are
        # reset at tick start and folded into one enriched HOP_GEN_STEP
        # record by _publish.  Scheduler thread only.
        self._last_tick_end = 0.0
        self._bubble_cause = "idle"
        self._pool_dry = False               # _admit broke on a dry pool
        self._dev_s: Dict[str, float] = {}   # phase -> booked device s
        #: what this tick's calls add up to, under the tick record's own
        #: names: cleared at a tick's start, added to where a call is
        #: counted, and the record's counts as they stand (``_tick``)
        self._counts: collections.Counter = collections.Counter()
        #: ... and what it notes a value at a time: (n_blocks, age_s) of
        #: blocks freed; streamed requests' stages submit -> admit and
        #: admit -> first chunk queued (/genperf ``requests``)
        self._noted: Dict[str, list] = {
            "kv_ages": [], "req_queue_s": [], "req_prefill_s": []}
        self._phases: Dict[str, float] = {}  # phase -> host wall s
        # cost-ledger scratch (utils/costledger.py): per-phase tenant
        # splits of the tick's padded capacity + KV-block-seconds freed
        # this tick.  None when the ledger kill switch is off — the
        # accumulators then cost nothing, and the tick record carries no
        # "attr" payload (so the spine never sets WANT_COST)
        self._tick_attr: Optional[Dict[str, Any]] = None
        self._tick_kv_attr: List[tuple] = []   # (tenant, block_s) freed
        #: deployment identity on /costs rows; the engine stamps it
        self.cost_deployment = ""
        # this scheduler's waiting queue is an overload signal: the
        # brownout ladder reads it as queue depth.  Registered through a
        # weakref (and finalized) so the registry never pins a scheduler
        # a test dropped without stop()
        import weakref

        self._brownout_key = f"genserver:{id(self)}"
        ref = weakref.ref(self)
        BROWNOUT.register_depth(
            self._brownout_key,
            # len() on deques is safe without the lock; this is a
            # signal read, not an invariant
            lambda: (lambda s: 0 if s is None else
                     len(s._waiting) + len(s._arrivals))(ref()),
        )
        weakref.finalize(self, BROWNOUT.unregister_depth,
                         self._brownout_key)

    # -- client surface (any thread) ------------------------------------

    def submit(self, rows, max_new: Optional[int] = None,
               tier: Optional[str] = None) -> GenRequest:
        """Unary generation: rows [B, S] (float wire rows fine — the
        sanitize_prompt clamp applies).  Returns the request handle; its
        ``future`` resolves to the eos-padded int32 ``[B, max_new]``
        array — exactly ``generate()``'s output contract."""
        return self._enqueue(rows, chunk=None, max_new=max_new, tier=tier)

    def stream(self, rows, chunk: int = 8, max_new: Optional[int] = None,
               tier: Optional[str] = None):
        """Streaming generation: a plain generator of ``[B, <=chunk]``
        int32 arrays whose concatenation equals the unary output —
        the stream_tokens contract, served by the scheduler."""
        return self.open_stream(rows, chunk, max_new, tier)[1]

    def open_stream(self, rows, chunk: int = 8,
                    max_new: Optional[int] = None,
                    tier: Optional[str] = None):
        """``stream`` for a lane that times its own side of the request:
        returns ``(request, chunks)``; the request carries the scheduler's
        stamps (``t_submit``, ``t_admit``, ``t_first``) back."""
        req = self._enqueue(rows, chunk=max(1, int(chunk)),
                            max_new=max_new, tier=tier)

        def _iter():
            try:
                while True:
                    item = req.queue.get()
                    if item is None:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    yield item
            finally:
                if not req.future.done():
                    req.cancel()
                    with self._wake:
                        self._wake.notify_all()

        return req, _iter()

    def _enqueue(self, rows, chunk, max_new,
                 tier: Optional[str] = None) -> GenRequest:
        if self.role == "decode":
            # phase routing contract (runtime/servingmesh.py): decode
            # replicas serve KV handoffs only — a client generation
            # request landing here is a routing misconfig, answered
            # typed + retryable so the gateway can re-route
            from seldon_core_tpu.runtime.servingmesh import (
                RoleMismatchError,
            )

            raise RoleMismatchError(
                "this replica is decode-only (--gen-role decode): client "
                "generation requests route to prefill/unified replicas")
        tier = tier or current_tier()
        if BROWNOUT.sheds_tier(tier):
            # typed, retryable, BEFORE anything is allocated or queued —
            # the ladder's contract (runtime/brownout.py)
            RECORDER.record_brownout_shed(tier)
            raise LoadShedError(
                f"{BROWNOUT_INFO_PREFIX}: {tier!r}-tier generation shed "
                f"at brownout stage {BROWNOUT.stage()} — retry later or "
                "resubmit as a higher tier"
            )
        rows = np.asarray(rows, dtype=np.float64)
        if rows.ndim < 2:
            rows = rows.reshape(1, -1)
        # sanitize_prompt's clamp, host-side: NaN -> 0, clip to vocab
        prompts = np.clip(
            np.nan_to_num(rows), 0, self.cfg.vocab - 1
        ).astype(np.int32)
        max_new = int(max_new or self.max_new_tokens)
        scale = BROWNOUT.gen_max_new_scale()
        if scale < 1.0:
            # stage-2 degradation: shorter generations free KV blocks and
            # slots sooner; clamped at admission so a request's contract
            # (its future's [B, max_new] shape) is consistent throughout
            max_new = max(1, int(max_new * scale))
        req = GenRequest(len(prompts), chunk, max_new, tier=tier)
        with self._wake:
            if self._stopped:
                raise RuntimeError("generation scheduler stopped")
            waiting = len(self._waiting) + len(self._arrivals)
            if (self.max_waiting > 0
                    and waiting + len(prompts) > self.max_waiting):
                # bounded admission: beyond the cap the queue would only
                # grow memory, never goodput — fail typed and retryable
                # (503 downstream; composes with breakers/retry budget)
                RECORDER.record_autopilot_shed("gen_queue")
                # the shed prefix is the wire contract (autopilot.py):
                # without it the gateway would count this deliberate
                # backpressure as a replica fault AND feed the ~1 ms
                # refusal into the routing EWMA, herding MORE traffic
                # onto the saturated replica
                raise LoadShedError(
                    f"{SHED_INFO_PREFIX}: generation admission queue "
                    f"full ({waiting}/{self.max_waiting} sequences "
                    "waiting; grow SELDON_TPU_GEN_MAX_WAITING or add "
                    "replicas)"
                )
            for r, p in enumerate(prompts):
                self._seq_counter += 1
                seq = _Sequence(self._seq_counter, req, r, p, req.max_new)
                if self.temperature > 0.0:
                    import jax

                    seq.key_data = np.asarray(jax.random.key_data(
                        jax.random.fold_in(
                            jax.random.key(self.seed), self._seq_counter)
                    ))
                req.seqs.append(seq)
                # caller-thread stamp: the lifecycle timeline's origin
                self._seq_event(seq, "enqueue", prompt_len=len(p))
                self._arrivals.append(seq)
            self._ensure_thread()
            self._wake.notify_all()
        return req

    def prewarm(self, widths=()) -> int:
        """Compile the serving-path executables before traffic: one probe
        request per prompt width runs admission -> chunked prefill ->
        decode rounds end to end (backed by the persistent compile
        cache).  Returns the number of probes served.  A probe that
        fails stops the boot: any token-row width is a valid prompt, so
        a failure here is the serving path itself failing."""
        if self.role != "unified":
            # prefill probes would fire real handoffs at peers that may
            # not be up yet; decode replicas reject submits by contract.
            # Both compile on first traffic (persistent compile cache).
            return 0
        count = 0
        for width in list(widths) or [4]:
            w = width if isinstance(width, int) else int(np.prod(width))
            probe = np.zeros((1, max(1, min(w, 4096))))
            req = self.submit(probe, max_new=min(self.span + 1,
                                                 self.max_new_tokens))
            req.future.result(timeout=900)
            count += 1
        return count

    _LEDGER_STATES = {_Sequence.WAITING: "waiting",
                      _Sequence.PREFILL: "prefill",
                      _Sequence.RUNNING: "running",
                      _Sequence.DONE: "done"}

    def snapshot(self) -> Dict[str, Any]:
        alloc = self._allocator
        now = time.time()
        with self._lock:
            waiting = len(self._waiting) + len(self._arrivals)
            inflight = len(self._active) + len(self._prefilling)
            tiers: Dict[str, int] = {}
            ledger: List[Dict[str, Any]] = []
            for coll in (self._waiting, self._arrivals,
                         self._prefilling, self._active):
                for s in coll:
                    t = s.request.tier
                    tiers[t] = tiers.get(t, 0) + 1
                    # the sequence ledger: enough per-sequence progress
                    # (prompt length, tokens emitted so far, remaining
                    # budget) for an operator — or a failover peer doing
                    # re-prefill resume — to reconstruct where a killed
                    # replica's streams stood.  The gateway's own resume
                    # path keeps the emitted tokens client-side; this is
                    # the server-side journal of the same truth.
                    ledger.append({
                        "sid": s.sid,
                        "tier": t,
                        "state": self._LEDGER_STATES.get(s.state, "?"),
                        "prompt_len": int(s.prompt0.shape[-1]),
                        "emitted": len(s.emitted),
                        "max_new": s.max_new,
                        "streaming": s.request.chunk is not None,
                        "age_s": round(now - s.t_start, 3)
                        if s.t_start else None,
                    })
        boot_load_s, boot_trace_s = BOOT.load_seconds(self.boot_server)
        doc = {
            "mode": "speculative" if self.spec else "decode",
            # disaggregated serving mesh: this replica's generation role
            # plus the handoff/import flow (the /stats block the
            # gateway's scrape and the disagg runbook read)
            "role": self.role,
            "mesh": (
                None if self.mesh is None
                else dict(zip(self.mesh.axis_names,
                              self.mesh.devices.shape))
            ),
            "slots": self.slots,
            "inflight_sequences": inflight,
            "waiting_sequences": waiting,
            "max_waiting": self.max_waiting,
            "sequences_by_tier": tiers,
            "kv_blocks": alloc.snapshot() if alloc is not None else {
                "total": self.num_blocks - 1, "used": 0, "pinned": 0,
                "high_water": 0,
            },
            "block_size": self.block_size,
            "span": self.span,
            "round": self._served.round,
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunk_effective": self._chunk_eff,
            "admitted_total": self.admitted_total,
            "retired_total": dict(self.retired_total),
            "preempted_total": self.preempted_total,
            "steps_total": dict(self.steps_total),
            "tokens_emitted_total": self.tokens_emitted_total,
            "tick_errors_total": self.tick_errors_total,
            # distinct shapes dispatched since boot; of the record's, how
            # many this boot loaded ahead, how many of those the program
            # store held (neither traced nor lowered), in how long, and how
            # much of that its one tracer thread spent tracing and lowering
            # (the interpreter's share of the load); dispatched shapes it
            # had not loaded (each traced and loaded by a request)
            "programs": {
                **{k: len(v) for k, v in self._programs.items()},
                "loaded_at_boot": sum(map(len, self._loaded.values())),
                "stored_at_boot": self._stored,
                "boot_load_s": round(boot_load_s, 3),
                "boot_trace_s": round(boot_trace_s, 3),
                "missed": self._missed,
            },
            # what the wake-up before a round's end rests on (_pace): a
            # round's device ms by row count, the guard it wakes ahead by,
            # the fenced round's slack the guard aims at
            "pace": {
                "round_ms": {str(b): round(min(v) * 1e3, 3)
                             for b, v in sorted(self._round_s.items())},
                "guard_ms": round(self._guard_s * 1e3, 3),
                "slack_ms": round(self._slack_s * 1e3, 3),
            },
            "sequence_ledger": ledger,
        }
        if self.spec:
            dalloc = self._draft_allocator
            doc["draft_kv_blocks"] = (
                dalloc.snapshot() if dalloc is not None else {})
        if self.role == "prefill":
            doc["disagg"] = (
                self.coordinator.snapshot()
                if self.coordinator is not None else None
            )
            doc["handoff_inflight"] = self._handoff_inflight
        if self.role == "decode":
            doc["imports"] = {
                "pending": len(self._imports),
                "committed_total": self.imports_committed_total,
                "reclaimed_total": self.imports_reclaimed_total,
            }
        return doc

    def chunk_history(self) -> Dict[str, Any]:
        """The adaptive prefill-chunk probe's state for ``GET /genperf``:
        floor/ceiling/effective width, whether the probe latched, and
        the per-width EMA walls the latch decision was made from."""
        return {
            "floor": self.prefill_chunk,
            "max": self.prefill_chunk_max,
            "effective": self._chunk_eff,
            "latched": self._chunk_latched,
            "wall_ema_s": {
                str(c): {"ema_s": round(v[0], 6), "ticks": v[1]}
                for c, v in sorted(self._chunk_wall.items())
            },
        }

    def boot_document(self) -> Dict[str, Any]:
        """The ``boot`` block of ``GET /stats``: the process's boot
        timeline with this server's part of it."""
        return BOOT.document(self.boot_server,
                             self._since_boot.get("serving_s", 0.0),
                             self._since_boot.get("waiting_s", 0.0))

    def stop(self) -> None:
        BROWNOUT.unregister_depth(self._brownout_key)
        with self._wake:
            self._stopped = True
            self._wake.notify_all()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=10)
        if self._storing is not None:
            # what a request compiled is on disk before the process goes
            self._storing.shutdown(wait=True)
        if self.coordinator is not None:
            self.coordinator.close()

    # -- worker thread ---------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="genserver", daemon=True)
            self._thread.start()

    def _ensure_device(self) -> None:
        if self._device_ready:
            return
        with self._device_init_lock:
            if not self._device_ready:
                self._init_device()
                self._device_ready = True

    def _init_device(self) -> None:
        # normally scheduler-thread-only; a decode replica's relay
        # handler also lands here when a KV handoff arrives before any
        # local tick ran (the init lock makes that safe — pool MUTATION
        # stays scheduler-thread-only afterwards)
        if not self.boot_server:   # an engine's scheduler has the engine's
            self.boot_server = BOOT.server()
        with self._boot_span("device_init", None):
            self._init_device_spans()

    def _boot_span(self, name: str, parent: Optional[str] = "device_init",
                   **note) -> _BootPhase:
        """One span of this server's ``_init_device`` on both clocks: the
        profiler's (``GenServer._init_device[/<name>]``) and the boot
        timeline's (``name`` under ``parent``, with ``note``)."""
        return _BootPhase(
            "GenServer._init_device" + ("" if parent is None else "/" + name),
            lambda start, end, _: BOOT.span(
                name, parent, start, end, self.boot_server, **note))

    def _init_device_spans(self) -> None:
        from seldon_core_tpu.models.generate import init_block_pool

        # nothing here waits for the device: the pool's zeros (and, behind
        # them, whatever of the parameters' making is still queued) finish
        # under the spans that follow
        with self._boot_span("pool", note="dispatched, not awaited"):
            self._pool = init_block_pool(
                self.cfg, self.num_blocks, self.block_size, self.mesh)
            self._allocator = BlockAllocator(self.num_blocks)
            if self.mesh is not None:
                # tensor-parallel dispatch (runtime/servingmesh.py): the
                # paged pool lays out over the unit's device mesh (KV heads
                # over 'tp' when divisible) so the scheduler's compiled
                # prefill/decode programs partition across chips together
                # with the mesh-sharded params
                from seldon_core_tpu.runtime.servingmesh import shard_gen_pool

                self._pool = shard_gen_pool(self.mesh, self._pool)
            if self.spec:
                self._draft_pool = init_block_pool(
                    self.draft_cfg, self.num_blocks, self.block_size)
                self._draft_allocator = BlockAllocator(self.num_blocks)
        with self._boot_span("kernels"):
            # the Pallas kernels or the jax.numpy forms: decided here, once,
            # because only the scheduler sees the mesh its pool is sharded
            # over
            self._kernels = self._served.kernels(
                self._pool, self.mesh, _pow2(self.slots),
                self.params["embed"].dtype)
            # what a decoded token costs: utils/genperf.py prices served
            # decode with it (``OBSERVATORY.cost_features``)
            OBSERVATORY.record_compile(
                "gen_decode_step", self._served.decode_costs(), None)
            _keep_out_of_program_locations()
        with self._boot_span("carry"):
            self._init_carry()
        self._load_programs()
        if self.prefix_ids is not None:
            with self._boot_span("prefix"):
                self._init_prefix()

    def _init_prefix(self) -> None:
        """The shared prefix is computed ONCE, here, into pinned blocks:
        one row whose table is those blocks.  Its full blocks are shared
        by table reference; a partly filled boundary block is copied into
        each row's first private block at admission."""
        import jax.numpy as jnp

        from seldon_core_tpu.models.generate import paged_forward_jit

        ids = np.asarray(self.prefix_ids, np.int32)[None, :]
        P = ids.shape[1]
        blocks = self._allocator.alloc(self._blocks_needed(P))
        if blocks is None:
            raise RuntimeError(
                f"{self._served.holds} pool ({self.num_blocks} blocks) "
                f"smaller than the shared prefix "
                f"({self._blocks_needed(P)} blocks)")
        _, self._pool = paged_forward_jit(
            self.params, jnp.asarray(ids), self._pool,
            jnp.asarray([blocks], jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), P, jnp.int32), cfg=self.cfg, last_only=True,
            **self._kernels.experts_how)
        self._allocator.pin(blocks)
        self._prefix_len = P
        full = P // self.block_size
        self._prefix_blocks = blocks[:full]
        self._prefix_tail = blocks[full] if P % self.block_size else None

    def _new_carry(self):
        """A zeroed carry on the device (``_carry_ops``).  Under a mesh it is
        replicated over it, so that what the helpers return can enter the
        sharded programs beside the parameters and the pool."""
        import jax

        width = self.slots + 1          # the last entry is scratch
        # a pending token a slot, or the block a round hands on
        tok = () if self._served.picks_first else (self._served.quantum,)
        carry = {"tok": np.zeros((width,) + tok, np.int32),
                 "seen": np.zeros((width,), bool)}
        if self.temperature > 0.0:
            carry["keys"] = np.zeros((width, self._key_width), np.uint32)
        if self.mesh is None:
            return jax.device_put(carry)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            carry, NamedSharding(self.mesh, PartitionSpec()))

    def _init_carry(self) -> None:
        """The carry, and every helper over it compiled for every row count
        a batch can have: they are keyed by nothing else, so none of them
        can compile once traffic runs, whatever it drives.  The row counts
        compile side by side (each on a carry of its own: ``put`` and
        ``first`` donate theirs): a dozen and a half tiny programs, none
        slow enough for the persistent cache to keep, are a second of a
        boot instead of five."""
        import jax

        take, put, first = _carry_ops()
        self._key_width = (
            int(np.asarray(jax.random.key_data(
                jax.random.key(self.seed))).shape[-1])
            if self.temperature > 0.0 else 0)

        def load(rows: int) -> None:
            carry = self._new_carry()
            idx = np.full((rows,), self.slots, np.int32)   # scratch only
            held = self._served.held(rows)
            tok, seen, keys = take(carry, idx, held)
            carry, key_data = put(carry, idx, tok, seen, keys)
            if key_data is not None:
                # raw key data from the host: an imported row's (decode role)
                key_data = np.asarray(key_data)
                carry, _ = put(carry, idx, tok, seen, key_data)
            if not self._served.picks_first:
                return      # no prefill of such a generator picks a token
            first(carry, np.zeros((rows, self.cfg.vocab), np.float32), idx,
                  np.zeros((rows,), bool), np.zeros((rows,), np.int32),
                  key_data, temperature=self.temperature, top_k=self.top_k,
                  top_p=self.top_p, eos_token=self.eos_token)

        counts = [1 << i for i in range(_pow2(self.slots).bit_length())]
        with concurrent.futures.ThreadPoolExecutor(len(counts)) as pool:
            list(pool.map(load, counts))
        self._zero_keys = {
            rows: jax.device_put(np.zeros((rows,), np.uint32))
            for rows in counts}
        self._carry = self._new_carry()

    def _first(self, logits, idx, held, held_tok, key_data):
        """First tokens picked on the device into the carry (``_carry_ops``
        ``first``); returns the ``[B]`` tokens and the rows' new key data,
        both still on the device."""
        self._carry, tok, key_data = _carry_ops()[2](
            self._carry, logits, idx, held, held_tok, key_data,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, eos_token=self.eos_token)
        return tok, key_data

    # -- the programs, and the record of which ones this deployment runs ----

    def _program(self, kind: str, *operands, state=None):
        """What a dispatch of ``kind`` calls, and with which arguments:
        ``(fn, args, kw)``, ``operands`` being the ones a batch brings
        (prefill: tokens, tables, start, width; decode: tables, token,
        n_valid, active, seen_eos, keys).  The one place that states them:
        a tick passes arrays and calls ``fn(*args, **kw)``, the boot passes
        their shapes (and, as ``state``, the parameters' and the pool's)
        and lowers the very same, and the two cannot drift into different
        programs.  ``args`` are the dynamic arguments and ``kw`` the static
        ones, all of them.

        For the boot, and for a server that keeps no table (``_store`` is
        None: a mesh, a draft model, no persistent cache), ``fn`` is the
        jitted program.  A tick of a server that keeps one is handed the
        table's executable of the batch's shape and ``args`` alone (a
        ``jax.stages.Compiled`` takes no static argument and converts
        nothing: the operands arrive with the shapes and dtypes ``_load``
        lowers them with); where the table lacks the shape, ``fn`` brings
        it up first (``_bring_up``)."""
        from seldon_core_tpu.models.generate import (
            paged_decode_round_jit,
            paged_forward_jit,
        )

        params, pool = state or (self.params, self._pool)
        if kind == "prefill":
            toks, tables, start, width = operands
            fn, shape = paged_forward_jit, toks.shape + tables.shape[1:]
            args = (params, toks, pool, tables, start, width)
            kw = {"cfg": self.cfg, "last_only": True}
            if not self._served.picks_first:
                # no token is chosen from a prompt: no head, and the
                # experts read in the logits' place (paged_forward)
                kw["head"] = False
            fused = self._kernels.fused(toks.shape[1])
            if fused is not None:
                kw["fused"] = fused
            kw.update(self._kernels.experts_how)
        else:
            tables, token, n_valid, active, seen, keys = operands
            fn, shape = paged_decode_round_jit, tables.shape
            args = (params, pool, tables, token, n_valid, active, seen, keys)
            kw = {"cfg": self.cfg, "span": self.span,
                  "temperature": self.temperature, "top_k": self.top_k,
                  "top_p": self.top_p, "eos_token": self.eos_token,
                  **self._kernels.round_how}
        if state is not None or self._store is None:
            return fn, args, kw
        compiled = self._executables.get((kind, shape))
        if compiled is None:
            return functools.partial(self._bring_up, kind, shape, fn), args, kw
        return compiled, args, {}

    def _bring_up(self, kind: str, shape: tuple, fn, *args, **kw):
        """The first dispatch of a shape the table lacks (a cold boot, a
        shape the record never saw): traced, lowered and compiled here, on
        the scheduler thread and under the tick's ``first_dispatch`` phase
        -- the work the ``jit`` call's first trace did, ``missed`` in /stats
        -- then entered in the table, handed to the store, and called.  So
        a cold boot stores everything it compiles, and the first warm boot
        after it traces nothing."""
        hits = thread_cache_hits()
        compiled = fn.lower(*args, **kw).compile()
        self._executables[kind, shape] = compiled
        self._keep(kind, shape, kw, compiled, hits < thread_cache_hits())
        return compiled(*args)

    def _keep(self, kind: str, shape: tuple, kw: dict, compiled,
              from_cache: bool) -> None:
        """Hand ``compiled`` to the store, off the calling thread: the
        serialising and the write (tens of megabytes a program) are one
        worker's, one program after another.  Whatever the server obtained,
        compiled here or handed over by JAX's persistent cache
        (``from_cache``) -- so the boot after a package upgrade, whose
        lowered modules are mostly the old ones, traces once and is stored
        again -- except on a backend that does not give back whole an
        executable it loaded from a file (``ProgramStore.reserialises``):
        there such a shape takes the traced path for as long as its cache
        entry hits."""
        # (one ``MeshExecutable`` serves every lowering of one program in
        # a process -- a second server of this deployment is handed the
        # first one's without any event -- so the set is the process's)
        if from_cache:
            _LOADED_FROM_DISK.add(compiled._executable)
        if (compiled._executable in _LOADED_FROM_DISK
                and not self._store.reserialises):
            return
        if self._storing is None:
            self._storing = concurrent.futures.ThreadPoolExecutor(
                1, "genserver-store")
        try:
            # (``save`` raises nothing: it warns and returns False)
            self._storing.submit(
                self._store.save, self._store.path(kind, shape, kw), compiled)
        except RuntimeError:    # ``stop`` shut the worker down meanwhile
            pass

    def _note_program(self, kind: str, shape: tuple
                      ) -> Optional[_BootPhase]:
        """A tick is about to dispatch ``shape``.  One the boot did not
        load is traced and loaded by the ``jit`` call of this tick --
        seconds on the scheduler thread, ``missed`` in /stats -- and
        enters the record, so the next boot loads it ahead.  Returns
        None for a shape dispatched before; for a new one, the phase to
        make the ``jit`` call under: its wall seconds are the shape's
        ``first_dispatch`` in the boot timeline (a loaded program's:
        the call finding its executable; a missed one's: the trace, the
        compile or the cache's fetch, and the load)."""
        seen = self._programs[kind]
        if shape in seen:
            return None
        seen.add(shape)
        loaded = shape in self._loaded[kind]
        if not loaded:
            self._missed += 1
            self._write_record()
        return _BootPhase(
            _FUNCTIONS[kind] + "/first_dispatch",
            lambda start, end, hits: BOOT.first_dispatch(
                self.boot_server, {
                    "kind": kind, "shape": list(shape), "loaded": loaded,
                    "host_s": round(end - start, 6),
                    **({} if loaded else {"from_cache": hits > 0})}),
            rows=shape[0], nblk=shape[-1], loaded=int(loaded))

    def _write_record(self) -> None:
        """The record anew: what this boot loaded and what it dispatched.
        A directory that cannot be written ends the recording."""
        if self._record_path and not write_program_record(
                self._record_path, self._identity, {
                    kind: self._loaded[kind] | self._programs[kind]
                    for kind in self._programs}):
            self._record_path = ""

    def _deployment_identity(self) -> str:
        """Everything that is static to the two programs, as one string:
        a record is read only by a boot that would build the very same
        programs from the very same shapes (a sweep at other ``slots``,
        another model, the CPU's gather path beside the chip's kernel in
        one cache directory each keep their own)."""
        import jax

        def digest(tree) -> str:
            leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
            return hashlib.sha256(repr([
                (jax.tree_util.keystr(path), x.shape, str(x.dtype))
                for path, x in leaves]).encode()).hexdigest()[:16]

        return json.dumps({
            "cfg": repr(self.cfg), "params": digest(self.params),
            "pool": digest(self._pool), "slots": self.slots,
            "block_size": self.block_size, "num_blocks": self.num_blocks,
            "prefill_chunk": self.prefill_chunk, "span": self.span,
            "temperature": self.temperature, "top_k": self.top_k,
            "top_p": self.top_p, "eos_token": self.eos_token,
            "inplace": self._kernels.inplace, "role": self.role,
        }, sort_keys=True)

    def _load_programs(self) -> None:
        """Load every shape the record lists before the first request.
        Blocks ``_init_device``; a request that arrives meanwhile waits as
        it waits for a compile.  A listed shape whose load raises is
        dropped from the record and left to its first request.  A server
        whose programs this cannot state exactly -- partitioned over a
        mesh, with a draft model -- keeps no record and loads nothing, as
        does one without a persistent cache."""
        import jax

        if self.mesh is not None or self.spec:
            return
        self._identity = self._deployment_identity()
        self._record_path = program_record_path(self._identity)
        if not self._record_path:
            return
        # an entry's ``from_cache``, and what may be stored (_keep)
        install_compile_cache_listener()
        # from here on the ticks dispatch from the table (_program)
        self._store = ProgramStore(
            os.path.dirname(self._record_path), self._identity,
            sorted(jax.tree_util.tree_leaves(self._pool)[0].devices(),
                   key=lambda d: d.id))
        self._store.sweep()     # another package's files serve no boot
        listed = read_program_record(self._record_path, self._identity)
        # a prefill before a round, as a tick first needs them: where both
        # programs hold a Pallas kernel (retention layers: the chunk's and
        # the step's) the kernels share jax.numpy's cached helper traces,
        # whose source locations are those of whichever was traced first --
        # and a kernel's locations are hashed into the persistent cache's
        # key (``_keep_out_of_program_locations``): another order here than
        # the ticks' and a second boot compiles every program once more
        jobs = [(kind, shape)
                for kind in sorted(listed, key=lambda k: k != "prefill")
                for shape in sorted(listed[kind])]
        if not jobs:
            return
        with self._boot_span("load"):
            for kind, shape in self._load(jobs):
                self._loaded[kind].add(shape)
        n = sum(map(len, self._loaded.values()))
        logger.info("loaded %d of the record's %d programs in %.1f s, "
                    "%.1f s of them tracing, %d from the program store (%s)",
                    n, len(jobs), *BOOT.load_seconds(self.boot_server),
                    self._stored, self._record_path)
        if n < len(jobs):
            self._write_record()    # without the ones that raised

    def _load(self, jobs: list) -> list:
        """Bring each ``(kind, shape)`` into the table its first dispatch
        reads (``_program``), from abstract arguments: nothing runs, the
        pool is not donated.  A shape the program store holds under this
        boot's key (runtime/compilecache.py ``ProgramStore``: no trace
        goes into it) is deserialised and loaded by one of the
        ``_LOAD_THREADS`` loaders, and that is all.  Any other -- no file,
        or one that does not load -- takes the traced path: ONE worker
        traces and lowers, program after program in the jobs' order
        (Python under the GIL: threads that share it only slow each other;
        and the order decides the persistent cache's key where both
        programs hold a kernel, ``_load_programs``), a loader behind it
        fetches the executable from JAX's persistent cache (its key is the
        one a tick's own lowering gives: ``_keep_out_of_program_locations``)
        or, cold, compiles it, and the store is handed the result.  Returns
        the jobs that loaded; one that raised is logged.  Each job enters
        the boot timeline as one ``programs`` entry, whole, once it has
        ended either way: ``trace_s`` the tracer's seconds in ``.lower()``
        (0.0 for a stored program), ``load_s`` a loader's in the
        deserialising or in ``.compile()``, ``from_cache`` whether the disk
        handed that loader the executable (the store, or the persistent
        cache), ``stored`` whether it was the store, ``error`` what it
        raised."""
        import jax

        state = jax.tree_util.tree_map(_abstract, (self.params, self._pool))
        take = _carry_ops()[0]

        def stated(job):
            """The program of ``job`` over abstract operands."""
            kind, shape = job
            B = shape[0]

            def S(shape, dtype=np.int32):
                return jax.ShapeDtypeStruct(shape, dtype)

            if kind == "prefill":
                _, C, nblk = shape
                operands = (S((B, C)), S((B, nblk)), S((B,)), S((B,)))
            else:
                # what `take` hands a round, by its own account
                held = self._served.held(B)
                token, seen, keys = jax.eval_shape(
                    take, self._carry, S((B,)),
                    None if held is None else _abstract(held))
                operands = (S(shape), token, S((B,)), S((B,), bool), seen,
                            _abstract(self._zero_keys[B])
                            if keys is None else keys)
            return self._program(kind, *operands, state=state)

        def lower(program, entry):
            fn, args, kw = program
            # the one tracer thread's own seconds: trace and lowering
            with _Phase("GenServer._init_device/load/trace/" + entry["kind"],
                        entry, "trace_s", rows=entry["shape"][0],
                        nblk=entry["shape"][-1]):
                return fn.lower(*args, **kw)

        def loading(entry, stored):
            return _BootPhase(
                "GenServer._init_device/load/compile/" + entry["kind"],
                lambda start, end, hits: entry.update(
                    load_s=end - start, from_cache=stored or hits > 0,
                    stored=stored), stored=int(stored))

        def compile_(job, entry, program, lowered):
            lowered = lowered.result()
            with loading(entry, False):
                compiled = lowered.compile()
            self._keep(*job, program[2], compiled, entry["from_cache"])
            return compiled

        def fetch(job, entry, program, path):
            with loading(entry, True):
                compiled = self._store.load(path)
            if compiled is None:
                # a file that did not load: the traced path, behind the rest
                compiled = compile_(job, entry, program,
                                    tracer.submit(lower, program, entry))
            return compiled

        loaded = []
        entries = [{"kind": kind, "shape": list(shape), "trace_s": 0.0,
                    "load_s": 0.0} for kind, shape in jobs]
        programs, paths = [], []
        for job in jobs:
            try:
                programs.append(stated(job))
                paths.append(self._store.path(*job, programs[-1][2]))
            except Exception as e:  # noqa: BLE001 - this job's alone
                programs.append(e)
                paths.append("")
        held = {path for path in paths if os.path.exists(path)}
        with concurrent.futures.ThreadPoolExecutor(
                1, "genserver-trace") as tracer, \
                concurrent.futures.ThreadPoolExecutor(
                    min(_LOAD_THREADS, len(jobs)),
                    "genserver-load") as loaders:
            done = []
            for job, entry, program, path in zip(jobs, entries, programs,
                                                 paths):
                if isinstance(program, Exception):
                    done.append(concurrent.futures.Future())
                    done[-1].set_exception(program)
                elif path in held:
                    done.append(loaders.submit(
                        fetch, job, entry, program, path))
                else:
                    done.append(loaders.submit(
                        compile_, job, entry, program,
                        tracer.submit(lower, program, entry)))
            for job, entry, fut in zip(jobs, entries, done):
                try:
                    self._executables[job] = fut.result()
                    loaded.append(job)
                    self._stored += entry["stored"]
                except Exception as e:  # noqa: BLE001 - a hint must not stop a boot
                    for key in ("from_cache", "stored"):
                        entry.pop(key, None)
                    entry["error"] = f"{type(e).__name__}: {e}"[:200]
                    logger.warning(
                        "%s program %s did not load ahead of its dispatch "
                        "(%s: %s)", *job, type(e).__name__, e, exc_info=True)
                entry.update(trace_s=round(entry["trace_s"], 6),
                             load_s=round(entry["load_s"], 6))
                BOOT.program(self.boot_server, entry)
        return loaded

    def _run(self) -> None:
        while True:
            with self._wake:
                while (not self._stopped and not self._arrivals
                       and not self._waiting and not self._prefilling
                       and not self._active and not self._remote_arrivals
                       and not self._handoff_done
                       # a round queued behind its rows' last one (an eos
                       # found a round late) is read before the loop parks:
                       # its completion is no later arrival's to find
                       and not self._unread):
                    if self._device_ready and not self._boot_logged:
                        # first idle after the boot: the pod's log holds
                        # the timeline without a scrape
                        self._boot_logged = True
                        logger.info("boot timeline: %s", json.dumps(
                            self.boot_document(), separators=(",", ":")))
                    with _Phase("GenServer._run/wait", self._since_boot,
                                "waiting_s"):
                        if self._imports:
                            # an in-flight remote import holds reserved
                            # blocks: wake periodically so the TTL reaper
                            # can reclaim a torn handoff even when no
                            # other work arrives
                            self._wake.wait(1.0)
                            break
                        self._wake.wait()
                if self._stopped:
                    break
            try:
                if not self._device_ready:
                    # the boot's seconds (``device_init`` in the boot
                    # timeline) stay out of the first tick's wall
                    self._ensure_device()
                with _Phase("GenServer._tick", self._since_boot,
                            "serving_s"):
                    progress = self._tick()
            except Exception as e:  # noqa: BLE001 - fail loudly per request
                logger.exception("genserver tick failed")
                # a silently-erroring scheduler must be visible beyond
                # process logs: count it (/stats + the
                # seldon_tpu_gen_tick_errors_total family) and stamp an
                # error span into any sampled trace riding this tick
                self.tick_errors_total += 1
                RECORDER.record_gen_tick_error()
                from seldon_core_tpu.utils.genperf import GENPERF

                GENPERF.observe_tick_error()
                self._stamp_tick_error(e)
                self._fail_all(e)
                progress = True
            if not progress:
                # queued work that cannot run yet (pool dry, waiting on a
                # retirement that cannot come this tick): don't spin hot
                with self._wake, _Phase("GenServer._run/wait",
                                        self._since_boot, "waiting_s"):
                    self._wake.wait(0.005)
        self._fail_all(RuntimeError("generation scheduler stopped"))

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            committed = list(self._remote_arrivals)
            seqs = (list(self._waiting) + list(self._prefilling)
                    + list(self._active) + list(self._arrivals)
                    # rows of a program dispatched and not read: some left
                    # the lists by arithmetic when it was dispatched (a
                    # finished prompt awaiting hand-off; see _Flight)
                    + [row[0] for fl in self._unread for row in fl.rows]
                    + [imp.seq for imp in committed]
                    # sequences whose handoff is at the coordinator (or
                    # already completed into _handoff_done): they live in
                    # no scheduler list, but their requests still await
                    + list(self._handoff_seqs))
            seqs = list(dict.fromkeys(seqs))
            self._waiting.clear()
            self._arrivals.clear()
            self._remote_arrivals.clear()
            self._handoff_seqs.clear()
            self._handoff_done.clear()
            self._prefilling, self._active = [], []
            self._unread.clear()
            imports = list(self._imports.values())
            self._imports.clear()
        for imp in imports + committed:
            # committed-but-unadmitted imports still hold RESERVED
            # blocks (commit_reserved only runs at admission) — release
            # them too or each aborted tick permanently shrinks the pool
            if self._allocator is not None:
                self._allocator.release_reserved(imp.blocks)
        for seq in seqs:
            self._release_blocks(seq)
            seq.inflight = 0
            req = seq.request
            if not req.future.done():
                req.future.set_exception(exc)
            # plain put, not put_nowait-under-except-Full: the per-request
            # queues are unbounded today, so Full is impossible — but a
            # future bounded-queue change must BLOCK here rather than
            # silently drop the shutdown error a consumer is waiting on
            req.queue.put(exc)
        if self._carry is not None and not self._stopped:
            # whatever failed may have left the carry's chain of donated
            # buffers broken: the next request starts from a fresh one
            try:
                self._carry = self._new_carry()
            except Exception:  # noqa: BLE001 - the requests are failed already
                logger.exception("could not rebuild the decode carry")

    # -- the scheduler step ----------------------------------------------

    def _tick(self) -> bool:
        """One scheduler iteration: admit, one prefill chunk, one decode
        round, retire, account.

        **The order.**  The tick dispatches the next device work before it
        reads back the last.  Entered with round k on the device (left
        there by the tick before), it sleeps until that round is about to
        end (``_pace``), admits, builds and dispatches this tick's prefill
        chunk and round k+1 -- which rows ride it is arithmetic, the
        tokens they carry stay on the device (``_carry_ops``) -- and only
        then waits for round k's tokens, reads them back, emits, retires
        and publishes, under cover of the device running what it was just
        given; a first token is read and delivered as soon as its chunk is
        done.  The synchronous order -- every program fenced and read
        before the next is built -- is the same code at depth 0
        (``_depth``): the wait placed before the dispatch.  The tick
        drains to it wherever a decision needs tokens the host has not
        seen: speculative rounds, a prefill replica's hand-off, a cancel
        or a preemption that touches a row in flight, a dry pool,
        ``stop()``, a device error.

        Exactly one fused telemetry record per step (utils/hotrecord.py
        HOP_GEN_STEP) -- enriched with the flight-recorder decomposition:
        per-phase host walls, the device seconds booked for every program
        whose completion this tick observed (``_await``), and the
        inter-tick bubble classified by how the PREVIOUS tick ended.
        Returns False when no work could run (the loop then backs off
        instead of spinning)."""
        t0 = time.perf_counter()
        bubble_s = (max(t0 - self._last_tick_end, 0.0)
                    if self._last_tick_end > 0.0 else 0.0)
        bubble_cause = self._bubble_cause
        self._pool_dry = False
        self._dev_s = {}
        self._counts.clear()
        self._tick_attr = {} if costledger_enabled() else None
        self._tick_kv_attr = []
        self._paced = None
        self._ensure_device()
        phases = self._phases = {}
        depth = self._depth()
        decoded = bool(self._unread)    # a round's tokens come back below
        if self._unread and depth:
            with _Phase("GenServer._decode_round", phases, "decode"):
                self._pace()
            if self._stopped:
                return True
        elif self._unread:
            # the synchronous order: the wait comes before everything else,
            # admission included, so that a request arriving while the round
            # in flight ends still gets into this tick's chunk
            self._drain()
        with self._wake:
            while self._arrivals:
                self._waiting.append(self._arrivals.popleft())
        self._drop_cancelled()
        with _Phase("GenServer._admit", phases, "admit"):
            admitted = self._admit()
            admitted += self._import_admit()
            handed_back = self._drain_handoff_done()
            self._reap_stale_imports()
        prefilled = False
        if self._prefilling:
            with _Phase("GenServer._prefill_tick", phases, "prefill"):
                fl = self._prefill_tick(fenced=depth == 0)
                if fl is not None:
                    prefilled = True
                    if depth == 0:
                        self._prefill_collect(fl)
        if depth == 0:
            # a first token can finish a sequence (eos / max_new == 1):
            # retire BEFORE the round so it neither wastes a slot nor a
            # dispatch.  Ahead of the readback the same rows are left out
            # by arithmetic, or ride one round as padding (an eos)
            with _Phase("GenServer._retire", phases, "retire"):
                self._retire_finished()
        ahead = None
        if self._decodable():
            decoded = True
            with _Phase("GenServer._decode_round", phases, "decode"):
                if self.spec:
                    self._spec_round()
                else:
                    fl = self._decode_round(fenced=depth == 0)
                    if fl is not None and depth == 0:
                        self._decode_collect(fl)
                    else:
                        ahead = fl
        # read what the device has finished or is finishing, in its order:
        # everything but the round just put behind it
        while self._unread and self._unread[0] is not ahead:
            self._collect(self._unread[0])
        with _Phase("GenServer._retire", phases, "retire"):
            self._retire_finished()
        tokens, retired = self._counts["tokens"], self._counts["retired"]
        kind = None
        if prefilled:
            kind = "mixed" if decoded else "prefill"
        elif decoded:
            kind = "spec" if self.spec else "decode"
        # idle spins count explicitly: a hot-spinning scheduler must
        # read as a bubble on /genperf, not as silence in steps_total
        self.steps_total[kind or "idle"] = (
            self.steps_total.get(kind or "idle", 0) + 1)
        if kind is not None:
            self.tokens_emitted_total += tokens
        wall = time.perf_counter() - t0
        detail = {
            "wall_s": wall,
            "device_s": sum(self._dev_s.values()),
            "phases": phases,
            "device_phases": dict(self._dev_s),
            "retention_row_bytes": self._served.retention_row_bytes,
            "ssm_row_bytes": self._served.ssm_row_bytes,
            # what the tick's calls counted, each where it was dispatched
            **self._counts,
        }
        for name, values in self._noted.items():
            if values:
                detail[name] = tuple(values)
                values.clear()
        if bubble_s > 0.0:
            detail["bubble_s"] = bubble_s
            detail["bubble_cause"] = bubble_cause
        if self._tick_attr is not None:
            # cost-ledger payload: per-phase tenant splits of the padded
            # capacity, KV-block-seconds freed this tick, deployment
            # identity.  Attached even on idle ticks so bubbles fold to
            # the ledger's idle bucket (its accounting identity needs
            # every second of wall, busy or not)
            detail["attr"] = {
                "dep": self.cost_deployment,
                "phases": {
                    phase: {
                        "padded": d["padded"],
                        "tenants": [
                            (t, tr, u, r, tok)
                            for (t, tr), (u, r, tok)
                            in d["tenants"].items()
                        ],
                    }
                    for phase, d in self._tick_attr.items()
                },
                "kv": tuple(self._tick_kv_attr),
            }
        with _Phase("GenServer._publish"):
            self._publish(admitted, retired, kind or "idle", tokens, wall,
                          detail=detail)
        progress = (kind is not None or admitted > 0 or retired > 0
                    or handed_back > 0)
        # the bubble ledger: stamp this tick's end and decide what the
        # gap before the NEXT tick will mean.  Progress means the loop
        # re-enters immediately — the gap is scheduler host work.  A dry
        # pool means the device idles until a retirement frees blocks;
        # queued-but-unadmitted work is an admission stall; otherwise
        # the device is idle because there is simply no work.
        self._last_tick_end = time.perf_counter()
        if progress:
            self._bubble_cause = "host"
        elif self._pool_dry:
            self._bubble_cause = "pool_exhaustion"
        elif self._waiting or self._arrivals:
            self._bubble_cause = "admission_stall"
        else:
            self._bubble_cause = "idle"
        return progress

    def _depth(self) -> int:
        """How many decode rounds the tick leaves on the device's queue
        when it returns: 1 keeps round k+1 queued behind round k before
        k's tokens are read; 0 is the synchronous order, every program
        fenced and read where it is dispatched.  It follows what the
        server observes in its own state and nothing else: a speculative
        round's verify step decides the next draft and a prefill replica
        hands a finished prompt off, so both need every token on the host
        (always 0); and the wake-up guard aims at a fenced round's slack
        (``_pace``), which only a depth-0 tick can read -- one round in
        ``_FENCE_EVERY`` is run so."""
        if self.spec or self.role == "prefill":
            return 0
        return int(self._since_fence < _FENCE_EVERY - 1)

    def _pace(self) -> None:
        """With a round on the device: sleep until it is about to end, so
        that admission decides as late as the synchronous order did (a
        request arriving during round k still gets into this tick's
        prefill) and the device still finds round k+1 queued when k ends.
        Woken at ``round start + its device seconds at this row count -
        guard``.  The start is the later of the round's dispatch and the
        completion seen before it.  The device seconds are the least of
        the last few readings ``_await`` booked for such a round, each
        taken between two completions the host was waiting for: a reading
        can only come out too long, so the least is what this sleep
        cannot move.  The guard is the one closed-loop term: ``_await``
        moves it by how long the wait for round k lasts once round k+1 is
        out, which should be a fenced round's slack (``_decode_round``)
        -- shorter and round k+1 reached the queue too late, longer and
        admission closed earlier than it had to.  Waking too late is the
        synchronous order's gap; too early admits that much early."""
        fl = self._unread[-1]
        readings = self._round_s.get(fl.size)
        if not readings:
            return          # nothing observed yet at this row count
        wake = (max(fl.t_dispatch, self._last_done) + min(readings)
                - self._guard_s)
        self._paced = fl
        with _Phase("GenServer._decode_round/wait", seq=fl.seq), self._wake:
            while not self._stopped:
                left = wake - time.perf_counter()
                if left <= 0:
                    break
                self._wake.wait(left)

    def _decodable(self) -> List[_Sequence]:
        """The rows the next decode round carries, by arithmetic alone: a
        row whose tokens read so far and in flight reach ``max_new`` has
        left; a row whose prompt ended in this tick's chunk has joined."""
        return sorted(
            (s for s in self._active
             if not s.done and len(s.emitted) + s.inflight < s.max_new),
            key=lambda s: s.sid)

    def _await(self, fl: _Flight, name: str) -> None:
        """Observe ``fl``'s completion and book its device seconds: the
        observed completion minus the later of its dispatch and the
        previous program's observed completion (utils/genperf.py
        ``booked_device_s``).  A fenced program was waited for where it
        was dispatched, under ``<name>/device``; the wait for one that
        ran queued behind another is ``<name>/wait``, so that a trace
        reader pairing ``/device`` with the module starting inside it
        never reads the wrong round.  Here too the wake-up before a
        round's end gets its observations (``_pace``): a reading of a
        round's device seconds, and the wait the guard is steered by."""
        import jax

        from seldon_core_tpu.utils.genperf import booked_device_s

        seen = fl.t_done is not None    # fenced: waited for at dispatch
        if fl.t_done is None:
            # a completion the host was not waiting for when it came is an
            # upper bound, no reading: the program may have ended any time
            # since its dispatch
            seen = not fl.ready.is_ready()
            t_wait = time.perf_counter()
            with _Phase(name + "/wait", seq=fl.seq):
                jax.block_until_ready(fl.ready)
            fl.t_done = time.perf_counter()
            if fl is self._paced and self._unread[-1] is not fl:
                # the round this tick paced itself against, with the next
                # one out: had that reached the queue in time, this wait
                # lasts about a fenced round's slack.  The guard follows
                # the difference, up faster than down (too late idles the
                # device, too early only admits early), within half a round
                err = self._slack_s - (fl.t_done - t_wait)
                self._guard_s = max(0.0, min(
                    0.5 * min(self._round_s[fl.size]),
                    self._guard_s + (0.5 if err > 0 else 0.2) * err))
        queued = self._last_done > fl.t_dispatch
        booked = booked_device_s(fl.t_dispatch, fl.t_done, self._last_done)
        readings = self._round_s.get(fl.size) if fl.kind == "decode" else None
        if not seen and readings:
            # found finished: it ran no longer than a fenced round reads
            booked = min(booked, min(readings) + self._slack_s)
        if fl.kind == "decode" and queued and seen and self._last_done_seen:
            # it started when the program before it ended and the host was
            # waiting at both ends: what was booked is the round's own
            # device time, no launch and no late look in it
            if readings is None:
                readings = self._round_s[fl.size] = deque(
                    maxlen=_ROUND_READINGS)
            readings.append(booked)
        self._last_done, self._last_done_seen = fl.t_done, seen
        self._dev_s[fl.kind] = self._dev_s.get(fl.kind, 0.0) + booked
        self._attr_note(fl.kind, *fl.attr)
        if fl.chunk:
            self._adapt_chunk(fl.chunk, fl.host_s + booked)
        self._unread.remove(fl)

    def _collect(self, fl: _Flight) -> None:
        """Read one dispatched program back under its function's phase."""
        if fl.kind == "decode":
            with _Phase("GenServer._decode_round", self._phases, "decode"):
                self._decode_collect(fl)
        else:
            with _Phase("GenServer._prefill_tick", self._phases, "prefill"):
                self._prefill_collect(fl)

    def _drain(self) -> None:
        """Down to depth 0 from wherever the tick stands: read everything
        dispatched, retire what finished.  After it the host's copy of
        every row (``emitted``, ``pending``, ``key_data``) is current, so
        a preemption may rebuild a prompt from it and a cancel may drop
        the row."""
        while self._unread:
            self._collect(self._unread[0])
        with _Phase("GenServer._retire", self._phases, "retire"):
            self._retire_finished()

    def _drop_cancelled(self) -> None:
        gone = [s for coll in (self._waiting, self._prefilling, self._active)
                for s in coll if s.request.cancelled]
        if any(s.inflight for s in gone):
            # a row of a program not read yet: read it first, so that the
            # row leaves with its books closed (it may have finished there)
            self._drain()
        for seq in gone:
            for coll in (self._waiting, self._prefilling, self._active):
                if seq in coll:
                    coll.remove(seq)
                    self._retire(seq, "cancelled")

    def _blocks_needed(self, upto: int) -> int:
        return -(-upto // self.block_size)  # ceil

    def _ensure_capacity(self, seq: _Sequence, upto: int,
                         draft: bool = False) -> bool:
        """Grow ``seq``'s table to cover positions [0, upto), evicting
        (preempt-youngest, recompute-on-readmit) when the pool is dry."""
        alloc = self._draft_allocator if draft else self._allocator
        shared = 0 if draft else len(self._prefix_blocks)
        owned = seq.draft_blocks if draft else seq.blocks
        need = self._blocks_needed(upto) - shared - len(owned)
        if need <= 0:
            return True
        while not alloc.can_alloc(need):
            if self._unread:
                # a dry pool: read everything in flight first -- a row
                # that finished frees blocks without an eviction, and
                # _preempt rebuilds a victim's prompt from its ``emitted``
                self._drain()
                if seq.state == _Sequence.DONE:
                    return True     # it finished in what was just read
                continue
            victim = self._pick_victim(exclude=seq)
            if victim is None:
                return False
            self._preempt(victim)
        got = alloc.alloc(need)
        if got is None:
            return False
        owned.extend(got)
        return True

    def _pick_victim(self, exclude: _Sequence) -> Optional[_Sequence]:
        pool = [s for s in self._active + self._prefilling
                if s is not exclude]
        if not pool:
            return None
        # tier-aware preempt-youngest: victims come from the LOWEST
        # priority tier present (offline before batch before
        # interactive), youngest-within-tier — interactive sequences
        # keep their KV blocks while any lower-tier victim exists
        return max(pool, key=lambda s: (tier_rank(s.request.tier),
                                        s.admit_order))

    def _preempt(self, seq: _Sequence) -> None:
        """Evict a running sequence: free its blocks and push it to the
        FRONT of the waiting queue for recompute.  Its already-delivered
        tokens become part of the re-prefill prompt and the pending token
        is restored (never re-sampled), so the stream resumes exactly
        where it stopped."""
        for coll in (self._active, self._prefilling):
            if seq in coll:
                coll.remove(seq)
        self._seq_event(seq, "preempt", n_valid=seq.n_valid,
                        emitted=len(seq.emitted))
        self._release_blocks(seq)
        if seq.emitted:
            # rebuild from the ORIGINAL prompt: emitted keeps growing, so
            # folding into the already-folded prompt would duplicate
            # context on a second preemption.  The last token is pending,
            # not yet in the cache -- but where none ever is (``picks_first``):
            # everything emitted becomes prompt (the last block's K/V, which
            # the row's next round would have written, the prefill writes:
            # ``_admit`` clears ``rode``), and the readmitted row's next
            # round starts where those tokens end
            cached = seq.emitted
            if self._served.picks_first:
                cached, seq.pending = seq.emitted[:-1], seq.emitted[-1]
            seq.prompt = np.concatenate(
                [seq.prompt0,
                 np.asarray(cached, np.int32)]).astype(np.int32)
        seq.prefill_pos = 0
        seq.n_valid = 0
        seq.inflight = 0
        seq.state = _Sequence.WAITING
        self._waiting.appendleft(seq)
        self.preempted_total += 1
        # mirrored into retired_total so /stats per-reason retirement
        # sums to the same figure as seldon_tpu_gen_retired_total
        self.retired_total["preempted"] = (
            self.retired_total.get("preempted", 0) + 1)
        RECORDER.record_gen_retired("preempted")

    def _attr_note(self, phase: str, padded_units: float,
                   rows) -> None:
        """Cost-ledger accumulation: ``rows`` increments of
        ``(tenant, tier, real_units, requests, tokens)`` against the
        tick's ``phase`` bucket.  No-op when the ledger is off."""
        if self._tick_attr is None:
            return
        d = self._tick_attr.setdefault(
            phase, {"padded": 0.0, "tenants": {}})
        d["padded"] += padded_units
        for tenant, tier, units, requests, toks in rows:
            row = d["tenants"].setdefault((tenant, tier), [0.0, 0.0, 0])
            row[0] += units
            row[1] += requests
            row[2] += toks

    def _release_blocks(self, seq: _Sequence) -> None:
        if self._allocator is not None and seq.blocks:
            if seq.t_start > 0.0:
                # KV residency at release — the pool-sizing histogram
                # (seldon_tpu_gen_kv_block_age_seconds via the spine fold)
                self._noted["kv_ages"].append(
                    (len(seq.blocks), time.time() - seq.t_start))
                if self._tick_attr is not None:
                    # KV-block-seconds (blocks x held-time) land on the
                    # owning tenant at retire/preempt — the ledger's
                    # memory-residency axis
                    self._tick_kv_attr.append((
                        seq.request.tenant or "",
                        len(seq.blocks) * (time.time() - seq.t_start),
                    ))
            self._allocator.free(seq.blocks)
        seq.blocks = []
        if seq.slot >= 0:
            # a row still riding a queued round as padding writes this
            # slot once more; the carry's own chain of programs orders
            # that before whatever the slot's next holder puts there
            self._slot_free.append(seq.slot)
            seq.slot = -1
        if self._draft_allocator is not None and seq.draft_blocks:
            self._draft_allocator.free(seq.draft_blocks)
        seq.draft_blocks = []

    def _next_waiting_index(self) -> int:
        """Admission order: highest-priority tier first, FIFO within a
        tier — the genserver's latency-tier lane.  With homogeneous
        traffic (everything interactive, the default) this is index 0,
        i.e. exactly the old FIFO."""
        best, best_rank = 0, None
        for i, s in enumerate(self._waiting):
            r = tier_rank(s.request.tier)
            if best_rank is None or r < best_rank:
                best, best_rank = i, r
                if r == 0:
                    break  # nothing outranks interactive
        return best

    def _admit(self) -> int:
        """Tier-priority FIFO admission into free slots; a sequence whose
        FIRST chunk of blocks cannot be allocated stays queued (pool
        exhaustion queues, never crashes).  A sequence that cannot fit
        even with the scheduler otherwise EMPTY can never be served —
        that one fails with a typed error instead of deadlocking the
        queue."""
        admitted = 0
        while self._waiting and self._slot_free and (
            len(self._active) + len(self._prefilling) < self.slots
        ):
            idx = self._next_waiting_index()
            seq = self._waiting[idx]
            first = min(len(seq.prompt), self.prefill_chunk)
            upto = self._prefix_len + first
            shared = len(self._prefix_blocks)
            need = self._blocks_needed(upto) - shared
            d_need = self._blocks_needed(first) if self.spec else 0
            if (not self._allocator.can_alloc(need)
                    or (self.spec
                        and not self._draft_allocator.can_alloc(d_need))):
                if self._unread:
                    # a dry pool with a round in flight: what it finishes
                    # may free the blocks, so read it before giving up
                    self._drain()
                    continue
                if not self._active and not self._prefilling:
                    # nothing will ever retire to free blocks: the pool
                    # is smaller than one request's first chunk
                    del self._waiting[idx]
                    self._finish_error(seq, RuntimeError(
                        f"{self._served.holds} pool ({self.num_blocks} "
                        f"blocks of {self.block_size}) cannot hold one "
                        "prefill chunk (grow SELDON_TPU_GEN_POOL_BLOCKS)"))
                    continue
                self._pool_dry = True   # bubble ledger: pool_exhaustion
                break  # pool dry: wait for a retirement to free blocks
            del self._waiting[idx]
            seq.blocks = self._allocator.alloc(need) or []
            if self.spec:
                seq.draft_blocks = (
                    self._draft_allocator.alloc(d_need) or [])
            # shared-prefix tail: the partially-filled boundary block is
            # private — copy the pinned one into this sequence's first block
            if self._prefix_tail is not None and seq.blocks:
                import jax.numpy as jnp

                from seldon_core_tpu.models.generate import (
                    paged_copy_block_jit,
                )

                self._pool = paged_copy_block_jit(
                    self._pool, jnp.int32(self._prefix_tail),
                    jnp.int32(seq.blocks[0]))
            seq.n_valid = self._prefix_len
            seq.state = _Sequence.PREFILL
            seq.slot, seq.rode = self._slot_free.popleft(), False
            seq.prefill_pos = 0
            seq.t_start = time.time()
            self._seq_event(seq, "admit", blocks=len(seq.blocks),
                            recompute=bool(seq.emitted))
            self._admit_counter += 1
            seq.admit_order = self._admit_counter
            self._prefilling.append(seq)
            self.admitted_total += 1
            admitted += 1
            RECORDER.record_gen_admitted()
            req = seq.request
            if req.t_admit is None:
                # admission wait is this lane's queue wait — same family
                # the MicroBatcher feeds, so /stats reads unchanged
                req.t_admit = time.perf_counter()
                RECORDER.observe_queue_wait(req.t_admit - req.t_submit)
                if req.chunk is not None:
                    self._noted["req_queue_s"].append(
                        req.t_admit - req.t_submit)
        return admitted

    def _table(self, seq: _Sequence, nblk: int, draft: bool = False
               ) -> np.ndarray:
        blocks = (seq.draft_blocks if draft
                  else self._prefix_blocks + seq.blocks)
        row = np.zeros((nblk,), np.int32)
        row[: len(blocks)] = blocks[:nblk]
        return row

    # -- prefill ----------------------------------------------------------

    def _prefill_tick(self, fenced: bool) -> Optional[_Flight]:
        """Dispatch one chunk of EVERY prefilling sequence's prompt as a
        single batched device program — the interleave grain that keeps a
        long prompt from stalling in-flight decode for more than ~one
        chunk's worth of time, without serializing one dispatch per
        prompt (16 co-arriving 512-token prompts at chunk 128 are 4
        batched ticks, not 64 sequential ones).

        A row whose prompt ends in this chunk gets its first token on the
        device (``_carry_ops`` ``first``: only a ``[B]`` int32 comes
        back, never the ``[B, vocab]`` logits) and joins ``_active`` here,
        by arithmetic, so the decode round of the SAME tick carries it.
        ``fenced`` (depth 0) waits for the program under ``…/device``;
        otherwise it is left on the device's queue.  Either way
        ``_prefill_collect`` reads it back."""
        import jax

        from seldon_core_tpu.models.generate import paged_forward_jit

        t0 = time.perf_counter()
        with _Phase("GenServer._prefill_tick/build"):
            # brownout stage >= 2: drop to the floor grain (the guaranteed
            # interleave) so in-flight decode stalls minimally; the adaptive
            # probe pauses rather than learning from degraded-mode walls
            floored = BROWNOUT.gen_chunk_floor()
            C = self.prefill_chunk if floored else self._chunk_eff
            # capacity pass first: eviction inside it may requeue OTHER
            # prefilling sequences, so the batch is built only afterwards
            for seq in list(self._prefilling):
                if seq not in self._prefilling:
                    continue  # preempted by an earlier row's eviction
                w = min(C, len(seq.prompt) - seq.prefill_pos)
                upto = self._prefix_len + seq.prefill_pos + w
                ok = self._ensure_capacity(seq, upto)
                if ok and self.spec:
                    # draft pool sized like the target pool; best effort
                    self._ensure_capacity(
                        seq, seq.prefill_pos + w, draft=True)
                if not ok:
                    # cannot even hold this chunk: re-queue and wait.
                    # _admit OVERWRITES seq.blocks on re-admission (and
                    # resets prefill_pos — recompute-on-readmit), so the
                    # blocks held so far must go back to the pool now
                    self._prefilling.remove(seq)
                    self._release_blocks(seq)
                    if not self._active and not self._prefilling:
                        # alone and still failing: no retirement can ever
                        # free more — the prompt simply exceeds the pool.
                        # Requeueing would livelock (admit -> prefill ->
                        # requeue at full device utilization, forever)
                        self._finish_error(seq, RuntimeError(
                            f"{self._served.holds} pool ({self.num_blocks} "
                            f"blocks of {self.block_size}) too small for "
                            f"prompt length {len(seq.prompt)} (grow "
                            "SELDON_TPU_GEN_POOL_BLOCKS)"))
                        continue
                    self._waiting.appendleft(seq)
                    seq.state = _Sequence.WAITING
            batch = list(self._prefilling)
            if not batch:
                return None
            B = _pow2(len(batch))
            toks = np.zeros((B, C), np.int32)
            start = np.zeros((B,), np.int32)
            width = np.zeros((B,), np.int32)
            widths = []
            for i, seq in enumerate(batch):
                lo = seq.prefill_pos
                w = min(C, len(seq.prompt) - lo)
                toks[i, :w] = seq.prompt[lo:lo + w]
                start[i] = self._prefix_len + lo
                width[i] = w
                widths.append(w)
            nblk = _pow2(max(
                self._blocks_needed(int(start[i]) + widths[i])
                for i in range(len(batch))
            ))
            first_dispatch = self._note_program("prefill", (B, C, nblk))
            tables = np.zeros((B, nblk), np.int32)
            for i, seq in enumerate(batch):
                tables[i] = self._table(seq, nblk)
            # the rows whose prompt ends here: their first token is picked
            # into the carry, or restored there when the host holds it (a
            # row readmitted after a preemption is never re-sampled)
            picks = self._served.picks_first
            ending = [(seq, i, picks and seq.pending is None)
                      for i, seq in enumerate(batch)
                      if seq.prefill_pos + widths[i] >= len(seq.prompt)]
            if ending and picks:
                idx = np.full((B,), self.slots, np.int32)
                held = np.zeros((B,), bool)
                held_tok = np.zeros((B,), np.int32)
                key_data = (np.zeros((B, self._key_width), np.uint32)
                            if self.temperature > 0.0 else None)
                for seq, i, fresh in ending:
                    idx[i] = seq.slot
                    if not fresh:
                        held[i], held_tok[i] = True, seq.pending
                    if key_data is not None:
                        key_data[i] = seq.key_data
            OBSERVATORY.note_padding(len(batch), B)
            # cost attribution: real units are this chunk's REAL prompt
            # tokens per sequence; the dispatched capacity is B x C (pad
            # rows and pad columns both burn the same device program).
            # Noted by the tick that books the program's device seconds
            attr = (B * C, [
                (s.request.tenant, s.request.tier, int(widths[i]), 0, 0)
                for i, s in enumerate(batch)
            ])
            # what this call is given, counted once: the tick record's
            # (/genperf ``served_prefill``) and the span's are these numbers
            starts = start[:len(batch)].tolist()
            work = self._served.prefill_counts(starts, widths)
            self._counts.update(
                {"prefill_" + name: n for name, n in {
                    **work, **self._kernels.prefill_counts(C, len(batch)),
                    "calls": 1, "rows": len(batch)}.items()},
                rows=B, real_rows=len(batch),
                kv_blocks=sum(self._blocks_needed(lo + w)
                              for lo, w in zip(starts, widths)))
            self._dispatched += 1
            work = dict(seq=self._dispatched, rows=B, real_rows=len(batch),
                        nblk=nblk, **{k: work[k] for k in _PREFILL_SPAN})
        # fenced (depth 0): dispatch -> ready with nothing queued ahead, the
        # annotation a trace reduction sets the module event against (how
        # much of the fence is not device time).  Otherwise the dispatch is
        # one more piece of building, behind the round still running.
        # Either way the span says what work the program was given
        with _Phase("GenServer._prefill_tick/"
                    + ("device" if fenced else "build"), **work):
            t_dispatch = time.perf_counter()
            fn, args, kw = self._program(
                "prefill", toks, tables, start, width)
            if first_dispatch is None:
                logits, self._pool = fn(*args, **kw)
            else:
                with first_dispatch:
                    logits, self._pool = fn(*args, **kw)
            if self.spec:
                d_nblk = _pow2(max(
                    self._blocks_needed(seq.prefill_pos + widths[i])
                    for i, seq in enumerate(batch)
                ))
                d_tables = np.zeros((B, d_nblk), np.int32)
                d_start = np.zeros((B,), np.int32)
                for i, seq in enumerate(batch):
                    d_tables[i] = self._table(seq, d_nblk, draft=True)
                    d_start[i] = seq.prefill_pos
                _, self._draft_pool = paged_forward_jit(
                    self.draft_params, toks, self._draft_pool, d_tables,
                    d_start, width, cfg=self.draft_cfg, last_only=True,
                )
            first = keys = None
            if ending and picks:
                first, keys = self._first(logits, idx, held, held_tok,
                                          key_data)
                first.copy_to_host_async()
            fl = _Flight("prefill", B, ending, logits, first, keys,
                         t_dispatch, attr, work["seq"])
            if not picks and self._served.counts_experts:
                # what came in the logits' place: one int32 a chunk
                fl.read = logits
                fl.read.copy_to_host_async()
            self._unread.append(fl)
            if fenced:
                jax.block_until_ready(fl.ready)
                fl.t_done = time.perf_counter()
        # what the chunk will have done, by arithmetic: the host does not
        # wait for it to know
        for i, seq in enumerate(batch):
            seq.prefill_pos += widths[i]
            self._seq_event(seq, "prefill_chunk", pos=seq.prefill_pos,
                            width=int(widths[i]))
            seq.n_valid = int(start[i]) + widths[i]
        for seq, _, fresh in ending:
            self._prefilling.remove(seq)
            seq.inflight = int(fresh)
            if self.role != "prefill":
                seq.state = _Sequence.RUNNING
                self._active.append(seq)
        if max(widths) == C and not floored:
            # only adapt on SATURATED ticks: short prompts never use a
            # wider executable, so probing one would compile it for
            # nothing (and the wall of an unsaturated tick says nothing
            # about width-C compute anyway)
            fl.chunk = C
        fl.host_s = time.perf_counter() - t0 - (
            fl.t_done - fl.t_dispatch if fenced else 0.0)
        return fl

    def _prefill_collect(self, fl: _Flight) -> None:
        """Wait for a dispatched chunk, read its first tokens back (a
        ``[B]`` int32, and only when a prompt ended in it), deliver them,
        and hand finished prompts off on a prefill replica."""
        self._await(fl, "GenServer._prefill_tick")
        first = key_data = None
        counted = {}        # what the program counted itself, if anything
        with _Phase("GenServer._prefill_tick/readback"):
            if fl.out is not None:
                first = np.asarray(fl.out)
                if fl.keys is not None:
                    key_data = np.asarray(fl.keys)
            if fl.read is not None:
                counted["experts_read"] = int(np.asarray(fl.read))
                self._counts["prefill_experts_read"] += counted["experts_read"]
        with _Phase("GenServer._prefill_tick/emit", seq=fl.seq, **counted):
            for seq, i, fresh in fl.rows:
                # the per-sequence prefill span (admission -> prompt fully
                # cached): the "prefill dispatch" leg of a federated trace's
                # critical path.  One record per sequence, trace-gated — the
                # per-step hot-path budget is untouched when tracing is off
                self._record_seq_span(seq, "prefill", "prefill")
                if key_data is not None:
                    seq.key_data = key_data[i]
                if fresh:
                    seq.inflight -= 1
                    seq.pending = int(first[i])
                    self._emit_tokens(seq, [seq.pending])
                    self._counts["tokens"] += 1
                    # one completed prefill = one request for the ledger's
                    # per-request usage normalization; the first served token
                    self._attr_note("prefill", 0, [
                        (seq.request.tenant, seq.request.tier, 0, 1, 1)])
                if self.role == "prefill":
                    if seq.done:
                        # the first token already finished the sequence
                        # (max_new==1 / immediate eos): nothing to hand off
                        self._retire(seq, seq.retire_reason or "length")
                    else:
                        self._handoff_out(seq)

    def _adapt_chunk(self, C: int, wall_s: float) -> None:
        """Probe the effective prefill chunk upward while ticks stay
        dispatch-dominated.  Evidence rule: after >= 2 ticks at width C,
        if doubling from C/2 left the EMA wall under 1.6x (compute would
        have doubled it), keep probing; if the doubled width is >1.6x
        slower, shrink back and LATCH — the floor is the configured
        interleave grain and the ceiling is PREFILL_CHUNK_MAX."""
        ema = self._chunk_wall.setdefault(C, [wall_s, 0])
        ema[0] = 0.5 * ema[0] + 0.5 * wall_s
        ema[1] += 1
        if self._chunk_latched or ema[1] < 2:
            return
        prev = self._chunk_wall.get(C // 2)
        if C > self.prefill_chunk and prev and ema[0] > 1.6 * prev[0]:
            self._chunk_eff = C // 2
            self._chunk_latched = True
        elif C < self.prefill_chunk_max:
            self._chunk_eff = min(2 * C, self.prefill_chunk_max)
        else:
            self._chunk_latched = True

    # -- decode -----------------------------------------------------------

    def _decode_round(self, fenced: bool) -> Optional[_Flight]:
        """Dispatch one ``span``-step decode round for every decodable
        sequence as a single device program.

        The host uploads what it knows by arithmetic (block tables,
        ``n_valid``, which rows are live; where a round hands a block on,
        which rows rode the round before and so bring one: models/served.py
        ``held``); the values that depend on the round before -- pending
        token or block, after-eos latch, sampling key -- are gathered from
        the carry on the device and scattered back (``_carry_ops``), so
        this round can be queued while the one before it is still running
        and unread.  Each row's share of the
        round's tokens (``take``) is fixed here.  A row that sampled eos
        in a round not read yet rides this one as padding: the latch is
        set on the device, ``_emit_tokens`` drops what follows a stop,
        and its blocks go back a round later.  ``fenced`` (depth 0) waits
        for the program under ``…/device``; ``_decode_collect`` is the
        token readback the streams need, now or a tick later."""
        import jax

        with _Phase("GenServer._decode_round/capacity"):
            for seq in self._decodable():
                if seq not in self._active or seq.done:
                    continue  # preempted, or finished in a drain, above
                upto = self._served.round_base(seq.n_valid) + self.span
                if not self._ensure_capacity(seq, upto):
                    # pool exhausted even after eviction: this sequence is
                    # alone and cannot fit — surface a typed failure
                    self._active.remove(seq)
                    self._finish_error(seq, RuntimeError(
                        f"{self._served.holds} pool too small for sequence "
                        f"length {upto} (grow SELDON_TPU_GEN_POOL_BLOCKS)"))
                    return None

        with _Phase("GenServer._decode_round/build"):
            batch = self._decodable()
            if not batch:
                return None
            B = _pow2(len(batch))
            nblk = _decode_table_width(
                self._kernels.attends_inplace, B,
                max(self._blocks_needed(
                    self._served.round_base(s.n_valid) + self.span)
                    for s in batch),
                self._allocator.capacity)
            first_dispatch = self._note_program("decode", (B, nblk))
            tables = np.zeros((B, nblk), np.int32)
            n_valid = np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            idx = np.full((B,), self.slots, np.int32)   # pads: scratch
            batch_rode = [s.rode for s in batch]
            # what the host says beside the carry where no pending token
            # rides it: a row's first block, or that it brings one
            # (models/served.py ``held``)
            held = self._served.held(B, batch_rode)
            rows, skip = [], []
            for i, s in enumerate(batch):
                tables[i] = self._table(s, nblk)
                n_valid[i] = s.n_valid
                active[i] = True
                idx[i] = s.slot
                # the round's first `off` positions are the row's own
                # prompt again (0 but for such a row's first round)
                off = s.n_valid - self._served.round_base(s.n_valid)
                if off:
                    held[i, :off] = s.prompt[len(s.prompt) - off:]
                skip.append(off)
                rows.append((s, min(
                    self.span - off,
                    s.max_new - len(s.emitted) - s.inflight)))
            OBSERVATORY.note_padding(len(batch), B)
            # cost attribution: one real unit per LIVE sequence, capacity B
            # (the pow-2 row padding is the decode round's whole pad tax);
            # noted by the tick that books the round's device seconds
            attr = (B, [
                (s.request.tenant, s.request.tier, 1, 0, 0) for s in batch])
            # what the round is given, counted once: the tick record's
            # (/genperf ``served_decode``) and the span's are these numbers
            work = self._served.round_counts(
                [s.n_valid for s in batch], self.span, batch_rode)
            self._counts.update(
                work, **self._kernels.round_counts(self.span,
                                                   work["passes"]),
                rows=B, real_rows=len(batch), steps=self.span,
                # queued behind a program whose results are still unread:
                # the device goes from that one to this without the host
                ahead_steps=self.span if self._unread else 0,
                kv_blocks=sum(self._blocks_needed(s.n_valid + self.span)
                              for s in batch))
            self._dispatched += 1
            work = dict(seq=self._dispatched, rows=B, real_rows=len(batch),
                        nblk=nblk,
                        inplace=int(bool(self._kernels.attends_inplace)),
                        **{k: work[k] for k in _DECODE_SPAN})
        # fenced (depth 0): dispatch -> ready with nothing queued ahead, the
        # annotation the trace sets paged_decode_round's module event against
        # (decode_fence_slack_ms) and the guard's second half.  Otherwise
        # the dispatch is one more piece of building.  Either way the span
        # says what work the program was given
        with _Phase("GenServer._decode_round/"
                    + ("device" if fenced else "build"), **work):
            t_dispatch = time.perf_counter()
            take, put, _ = _carry_ops()
            token, seen, keys = take(self._carry, idx, held)
            fn, args, kw = self._program(
                "decode", tables, token, n_valid, active, seen,
                self._zero_keys[B] if keys is None else keys)
            if first_dispatch is None:
                out = fn(*args, **kw)
            else:
                with first_dispatch:
                    out = fn(*args, **kw)
            toks, self._pool, token, _nv, seen, keys, *extra = out
            self._carry, key_data = put(
                self._carry, idx, token, seen,
                keys if self.temperature > 0.0 else None)
            toks.copy_to_host_async()
            fl = _Flight("decode", B, rows, toks, toks, key_data, t_dispatch,
                         attr, work["seq"])
            fl.skip = skip
            if extra and "experts_read" in extra[0]:
                # an int32 each that the round counted itself (the experts
                # read; the picks on held experts), read back beside the
                # round's tokens
                fl.read = extra[0]
                for count in fl.read.values():
                    count.copy_to_host_async()
            self._unread.append(fl)
            if fenced:
                jax.block_until_ready(fl.ready)
                fl.t_done = time.perf_counter()
        self._since_fence += 1
        if fenced:
            self._since_fence = 0
            readings = self._round_s.get(B)
            if readings:
                # what the wake-up guard aims at: dispatch -> ready less the
                # round's own device seconds.  A moving average; one stalled
                # round moves it no further than a reading of twice itself
                slack = max(fl.t_done - t_dispatch - min(readings), 0.0)
                old = self._slack_s
                self._slack_s = (slack if old == 0.0
                                 else 0.5 * (old + min(slack, 2.0 * old)))
        for (s, share), off in zip(rows, skip):
            s.inflight += share
            s.n_valid += self.span - off
            s.rode = True
        return fl

    def _decode_collect(self, fl: _Flight) -> None:
        """Wait for a dispatched round and read its tokens back -- the one
        host sync a round needs -- then emit each row's share."""
        self._await(fl, "GenServer._decode_round")
        key_data = None
        counted = {}        # what the round counted itself, if anything
        with _Phase("GenServer._decode_round/readback"):
            toks = np.asarray(fl.out)
            if fl.keys is not None:
                key_data = np.asarray(fl.keys)
            if fl.read is not None:
                counted = {name: int(np.asarray(count))
                           for name, count in fl.read.items()}
                self._counts.update(counted)
        with _Phase("GenServer._decode_round/emit", seq=fl.seq, **counted):
            for i, (s, take) in enumerate(fl.rows):
                off = fl.skip[i]
                s.inflight -= take
                if self._served.picks_first:
                    s.pending = int(toks[i, -1])
                if key_data is not None:
                    s.key_data = key_data[i]
                if s.done:
                    continue    # stopped a round ago: this one was padding
                # one request for the cost ledger's per-request usage: noted
                # with the prefill's first token where it picks one; where
                # it chose none, the row's first tokens bring it (a row
                # readmitted after a preemption has emitted)
                first = not self._served.picks_first and not s.emitted
                self._emit_tokens(
                    s, [int(t) for t in toks[i, off:off + take]])
                self._seq_event(s, "decode_round", n_valid=s.n_valid,
                                take=take)
                self._counts["tokens"] += take
                if take > 0:
                    self._attr_note("decode", 0, [
                        (s.request.tenant, s.request.tier, 0, int(first),
                         take)])

    def _spec_round(self) -> int:
        """One speculative draft/verify round for every RUNNING sequence
        (greedy): up to k+1 tokens per row per device program."""
        import jax
        import jax.numpy as jnp

        from seldon_core_tpu.models.generate import paged_spec_round_jit

        W = self.spec_k + 1
        batch = sorted(self._active, key=lambda s: s.sid)
        for seq in batch:
            if seq not in self._active:
                continue  # preempted by an earlier row's eviction
            ok = (self._ensure_capacity(seq, seq.n_valid + W)
                  and self._ensure_capacity(seq, seq.n_valid + W,
                                            draft=True))
            if not ok:
                self._active.remove(seq)
                self._finish_error(seq, RuntimeError(
                    "KV pool too small for speculative round (grow "
                    "SELDON_TPU_GEN_POOL_BLOCKS)"))
                return 0
        batch = sorted(self._active, key=lambda s: s.sid)
        if not batch:
            return 0
        B = _pow2(len(batch))
        nblk = _pow2(max(
            self._blocks_needed(s.n_valid + W) for s in batch))
        # draft tables mirror the target's coverage: spec mode forbids
        # prefix caches, the only source of asymmetry
        d_nblk = nblk
        tables = np.zeros((B, nblk), np.int32)
        d_tables = np.zeros((B, d_nblk), np.int32)
        token = np.zeros((B,), np.int32)
        n_valid = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        for i, s in enumerate(batch):
            tables[i] = self._table(s, nblk)
            d_tables[i] = self._table(s, d_nblk, draft=True)
            token[i] = s.pending
            n_valid[i] = s.n_valid
            active[i] = True
        OBSERVATORY.note_padding(len(batch), B)
        self._attr_note("decode", B, [
            (s.request.tenant, s.request.tier, 1, 0, 0) for s in batch])
        self._counts.update(
            rows=B, real_rows=len(batch),
            # k sequential draft steps + one verify pass per round
            steps=W,
            kv_blocks=sum(self._blocks_needed(s.n_valid + W) for s in batch),
            kv_positions=sum(W * (s.n_valid + W // 2) for s in batch))
        td = time.perf_counter()
        new_toks, gained, corrected, self._pool, self._draft_pool = (
            paged_spec_round_jit(
                self.params, self.draft_params, self._pool,
                self._draft_pool, jnp.asarray(tables),
                jnp.asarray(d_tables), jnp.asarray(token),
                jnp.asarray(n_valid), jnp.asarray(active),
                self.cfg, self.draft_cfg, k=self.spec_k,
            )
        )
        jax.block_until_ready(new_toks)
        self._last_done = time.perf_counter()
        self._dev_s["decode"] = (
            self._dev_s.get("decode", 0.0) + self._last_done - td)
        new_toks = np.asarray(new_toks)
        gained = np.asarray(gained)
        corrected = np.asarray(corrected)
        emitted = 0
        accept_sum, accept_rounds = 0.0, 0
        for i, s in enumerate(batch):
            g = int(gained[i])
            remaining = s.max_new - len(s.emitted)
            take = min(g, remaining)
            s.n_valid += g
            s.pending = int(corrected[i])
            self._emit_tokens(s, [int(t) for t in new_toks[i, :take]])
            self._seq_event(s, "decode_round", n_valid=s.n_valid,
                            take=take, gained=g)
            emitted += take
            if take > 0:
                self._attr_note("decode", 0, [
                    (s.request.tenant, s.request.tier, 0, 0, take)])
            accept_sum += (g - 1) / max(self.spec_k, 1)
            accept_rounds += 1
        if accept_rounds:
            RECORDER.observe_accept_ratio(accept_sum / accept_rounds)
        self._counts["tokens"] += emitted
        return emitted

    # -- disaggregated handoff: prefill side ------------------------------

    def _handoff_out(self, seq: _Sequence) -> None:
        """Export a finished prefill (its private KV blocks + sampling
        state) and hand it to the coordinator; the blocks go straight
        back to the pool — the prefill replica's whole point is that its
        residency recycles at prompt cadence, not generation cadence."""
        from seldon_core_tpu.runtime import kvstream
        from seldon_core_tpu.runtime.servingmesh import HandoffError

        if self.coordinator is None:
            self._finish_error(seq, HandoffError(
                "prefill-role replica has no decode peers configured "
                "(--decode-peers / ENGINE_DECODE_PEERS)"))
            return
        meta = kvstream.KvBeginMeta(
            n_layers=len(self._pool),
            block_size=self.block_size,
            # the MODEL's heads: a pool row may carry several of them
            # (models/generate.py init_block_pool), the wire's bytes are
            # a token's KV x hd values either way
            kv_heads=self.cfg.kv_heads,
            head_dim=self.cfg.hd,
            dtype=kvstream.pool_dtype_name(self._pool),
            n_blocks=len(seq.blocks),
            n_valid=seq.n_valid,
            pending=int(seq.pending),
            max_new=int(seq.max_new),
            prefix_len=self._prefix_len,
            prompt=np.asarray(seq.prompt, np.int32),
            emitted=list(seq.emitted),
            key_data=seq.key_data,
            tier=seq.request.tier,
        )
        export = kvstream.KvExport(
            meta=meta,
            # device->host gather NOW, on the scheduler thread, before
            # the pool is donated into the next dispatch
            layers=kvstream.export_blocks(self._pool, seq.blocks),
            tenant=getattr(seq.request, "tenant", "") or "",
        )
        # mint the kv_handoff span's identity UP FRONT: its traceparent
        # rides the relay sidecar on every frame, so the decode side's
        # import/decode spans parent under a span id that already exists
        # when they are recorded; the coordinator records the span itself
        # when the stream completes (runtime/servingmesh.py)
        from seldon_core_tpu.utils.tracing import TRACER

        req_ctx = getattr(seq.request, "trace_ctx", None)
        if req_ctx is not None and req_ctx.sampled and TRACER.enabled:
            export.trace_ctx = req_ctx.child(req_ctx.puid)
            export.parent_span_id = req_ctx.span_id
            export.puid = req_ctx.puid
        self._seq_event(seq, "handoff", n_valid=seq.n_valid)
        self._release_blocks(seq)
        seq.state = _Sequence.DONE
        self._handoff_inflight += 1
        self._handoff_seqs[seq] = True

        def _done(result, seq=seq):
            self._handoff_done.append((seq, result))
            with self._wake:
                self._wake.notify_all()

        self.coordinator.submit(export, _done)

    def _drain_handoff_done(self) -> int:
        """Fold completed handoffs back into the request surfaces: the
        decode peer's token array becomes the sequence's emitted stream
        (first token unchanged — it was emitted at prefill time), or a
        typed failure fails the request retryably."""
        n = 0
        while self._handoff_done:
            seq, result = self._handoff_done.popleft()
            self._handoff_seqs.pop(seq, None)
            self._handoff_inflight -= 1
            n += 1
            if isinstance(result, BaseException):
                self._finish_error(seq, result)
                continue
            toks = [int(t) for t in np.asarray(result).reshape(-1)]
            prev = len(seq.emitted)
            seq.emitted = toks[: seq.max_new]
            if len(seq.emitted) < seq.max_new:
                # defensive eos-padding; the decode side pads already
                pad = (self.eos_token if self.eos_token >= 0
                       else (seq.emitted[-1] if seq.emitted else 0))
                seq.emitted += [pad] * (seq.max_new - len(seq.emitted))
            self.tokens_emitted_total += max(0, len(seq.emitted) - prev)
            seq.done = True
            self._retire(seq, "handoff")
        return n

    # -- disaggregated handoff: decode side (relay-handler threads) -------

    def kv_reserve(self, hid: bytes, meta) -> None:
        """BEGIN: validate the handoff against this pool and reserve its
        blocks.  Raises typed — KvWireError for geometry/dtype/prefix
        mismatches (a deployment misconfig), LoadShedError when the pool
        cannot hold the blocks (retryable: the prefill side's p2c walks
        to the next peer)."""
        from seldon_core_tpu.runtime import kvstream

        self._ensure_device()
        kvstream.validate_against_pool(
            meta, self._pool, self.block_size, self._prefix_len,
            head_dim=self.cfg.hd)
        blocks = self._allocator.reserve(meta.n_blocks)
        if blocks is None:
            RECORDER.record_kv_handoff("refused")
            raise LoadShedError(
                f"{SHED_INFO_PREFIX}: decode KV pool cannot hold "
                f"{meta.n_blocks} handoff blocks "
                f"({self._allocator.used}/{self._allocator.capacity} "
                "used) — try another decode replica")
        names = (("k", "v", "k_s", "v_s") if meta.dtype == "int8"
                 else ("k", "v"))
        dt = (np.int8 if meta.dtype == "int8"
              else kvstream._np_dtype(meta.dtype))
        staged = []
        for _ in range(meta.n_layers):
            layer = {}
            for name in names:
                if name.endswith("_s"):
                    shape = (meta.n_blocks, meta.block_size,
                             meta.kv_heads)
                    layer[name] = np.zeros(shape, np.float32)
                else:
                    shape = (meta.n_blocks, meta.block_size,
                             meta.kv_heads, meta.head_dim)
                    layer[name] = np.zeros(shape, dt)
            staged.append(layer)
        imp = _KvImport(hid, meta, blocks, staged)
        # the relay sidecar bound the BEGIN frame's traceparent around
        # this handler (udsrelay.py): capture it so the import + decode
        # spans of this handoff parent under the prefill side's
        # kv_handoff span
        from seldon_core_tpu.utils.tracing import current_trace_context

        imp.trace_ctx = current_trace_context()
        with self._wake:
            if self._stopped:
                self._allocator.release_reserved(blocks)
                raise RuntimeError("generation scheduler stopped")
            self._imports[hid] = imp
            # the scheduler thread must run while a reservation is
            # outstanding: it IS the TTL reaper for torn handoffs
            self._ensure_thread()
            self._wake.notify_all()

    def kv_receive(self, hid: bytes, first: int, layers) -> None:
        """KV_BLOCKS: stage one chunk host-side (nothing touches the
        device pool until commit — the scheduler thread owns it)."""
        from seldon_core_tpu.runtime.kvstream import KvWireError

        imp = self._imports.get(hid)
        if imp is None:
            raise KvWireError("unknown or expired handoff id")
        imp.receive(first, layers)

    def kv_commit(self, hid: bytes) -> GenRequest:
        """KV_COMMIT: the import is complete — build the sequence and
        queue it for scheduler admission (the device scatter happens on
        the scheduler thread).  Returns the request whose future
        resolves to the finished ``[1, max_new]`` token array."""
        from seldon_core_tpu.runtime.kvstream import KvWireError

        # pop FIRST: the claim on this handoff must be atomic against
        # the scheduler's TTL reaper (which also pops before releasing).
        # A get-then-pop would let a commit landing exactly at the TTL
        # admit a reservation the reaper already returned to the free
        # list — two sequences sharing blocks, silently
        imp = self._imports.pop(hid, None)
        if imp is None:
            raise KvWireError("unknown or expired handoff id")
        if not imp.complete():
            # torn: the sender committed before streaming every block
            self._allocator.release_reserved(imp.blocks)
            self.imports_reclaimed_total += 1
            RECORDER.record_kv_handoff("reclaimed")
            raise KvWireError(
                "commit before every block was received — torn handoff "
                "reclaimed")
        meta = imp.meta
        req = GenRequest(1, None, meta.max_new, tier=meta.tier)
        if imp.trace_ctx is not None:
            # parent the decode-side spans under the kv_handoff span the
            # BEGIN sidecar named (the COMMIT may arrive on a different
            # relay connection — the BEGIN-time capture is authoritative)
            req.trace_ctx = imp.trace_ctx
        from seldon_core_tpu.utils.tracing import TRACER

        if imp.trace_ctx is not None and TRACER.enabled:
            # the import leg: reserve -> every block staged -> commit
            TRACER.record_span(
                "kv_import", kind="kv_import", method="kv_handoff",
                start_s=imp.created_epoch,
                duration_ms=(time.time() - imp.created_epoch) * 1e3,
                ctx=imp.trace_ctx, blocks=len(imp.blocks),
                n_valid=int(meta.n_valid),
            )
        with self._wake:
            if self._stopped:
                self._allocator.release_reserved(imp.blocks)
                raise RuntimeError("generation scheduler stopped")
            self._seq_counter += 1
            seq = _Sequence(self._seq_counter, req, 0,
                            np.asarray(meta.prompt, np.int32),
                            meta.max_new)
            seq.n_valid = int(meta.n_valid)
            seq.pending = int(meta.pending)
            seq.emitted = list(meta.emitted)
            seq.key_data = (np.asarray(meta.key_data)
                            if meta.key_data is not None else None)
            req.seqs.append(seq)
            imp.seq = seq
            self._remote_arrivals.append(imp)
            self._ensure_thread()
            self._wake.notify_all()
        return req

    def kv_abort(self, hid: bytes) -> bool:
        imp = self._imports.pop(hid, None)
        if imp is None:
            return False
        self._allocator.release_reserved(imp.blocks)
        self.imports_reclaimed_total += 1
        RECORDER.record_kv_handoff("reclaimed")
        return True

    def kv_stats(self) -> Dict[str, int]:
        """The free-KV-block score a prefill coordinator's p2c reads
        (KV_STATS frame) — cheap enough to answer before the device pool
        even exists."""
        alloc = self._allocator
        if alloc is not None:
            snap = alloc.snapshot()
            free = snap["total"] - snap["used"]
            total = snap["total"]
        else:
            free = total = self.num_blocks - 1
        with self._lock:
            waiting = len(self._waiting) + len(self._arrivals)
            inflight = len(self._active) + len(self._prefilling)
        return {"free": free, "total": total, "waiting": waiting,
                "inflight": inflight}

    # -- disaggregated handoff: decode side (scheduler thread) ------------

    def _import_admit(self) -> int:
        """Committed imports enter the decode loop: one compiled chunk
        scatter writes the staged blocks into the pool, the reservation
        becomes ownership, and the sequence joins ``_active`` mid-
        stream — exactly where the unified path would have put it after
        local prefill."""
        if not self._remote_arrivals:
            return 0
        from seldon_core_tpu.runtime import kvstream

        n = 0
        joined = []
        # a row needs a slot of the carry: an import beyond them waits for
        # a retirement, as a local request waits for a free slot
        while self._remote_arrivals and self._slot_free:
            imp = self._remote_arrivals.popleft()
            self._pool = kvstream.scatter_staged(
                self._pool, imp.blocks, imp.staged)
            self._allocator.commit_reserved(imp.blocks)
            seq = imp.seq
            seq.blocks = list(imp.blocks)
            seq.slot = self._slot_free.popleft()
            joined.append(seq)
            seq.state = _Sequence.RUNNING
            seq.t_start = time.time()
            self._seq_event(seq, "admit", blocks=len(seq.blocks),
                            imported=True)
            self._admit_counter += 1
            seq.admit_order = self._admit_counter
            self._active.append(seq)
            self.admitted_total += 1
            self.imports_committed_total += 1
            RECORDER.record_gen_admitted()
            RECORDER.record_kv_handoff("imported")
            n += 1
        if joined:
            # what the prefill side carried over -- pending token, after-eos
            # latch, sampling key -- goes into the carry, where a local
            # row's first token would have put it
            rows = _pow2(len(joined))
            idx = np.full((rows,), self.slots, np.int32)
            tok = np.zeros((rows,), np.int32)
            seen = np.zeros((rows,), bool)
            key_data = (np.zeros((rows, self._key_width), np.uint32)
                        if self.temperature > 0.0 else None)
            for i, seq in enumerate(joined):
                idx[i], tok[i] = seq.slot, seq.pending
                seen[i] = self.eos_token >= 0 and self.eos_token in seq.emitted
                if key_data is not None and seq.key_data is not None:
                    key_data[i] = seq.key_data
            self._carry, _ = _carry_ops()[1](
                self._carry, idx, tok, seen, key_data)
        return n

    def _reap_stale_imports(self) -> None:
        """Torn-handoff backstop: a reservation never committed within
        the TTL goes back to the pool — the leak bound is TTL, not
        forever."""
        if not self._imports:
            return
        now = time.monotonic()
        for hid, imp in list(self._imports.items()):
            if now - imp.created > self._import_ttl_s:
                if self._imports.pop(hid, None) is not None:
                    self._allocator.release_reserved(imp.blocks)
                    self.imports_reclaimed_total += 1
                    RECORDER.record_kv_handoff("reclaimed")
                    logger.warning(
                        "reclaimed torn KV handoff (%d blocks) after "
                        "%.0fs TTL", len(imp.blocks), self._import_ttl_s)

    # -- emission / retirement --------------------------------------------

    def _emit_tokens(self, seq: _Sequence, toks: List[int]) -> None:
        if not toks or seq.done:
            return
        seq.emitted.extend(toks)
        if self.eos_token >= 0 and self.eos_token in seq.emitted:
            # finished early: eos-pad the tail now so assembly never
            # waits on a retired row (the mask_after_eos output contract)
            first = seq.emitted.index(self.eos_token)
            seq.emitted = (
                seq.emitted[: first + 1]
                + [self.eos_token] * (seq.max_new - first - 1)
            )
            seq.retire_reason = "eos"
            seq.done = True
        elif len(seq.emitted) >= seq.max_new:
            seq.emitted = seq.emitted[: seq.max_new]
            seq.retire_reason = "length"
            seq.done = True
        req = seq.request
        if not req.ttft_recorded:
            req.ttft_recorded = True
            if req.chunk is not None:
                # TTFT is a STREAMING-lane metric (one observation per
                # stream, the scheduler is its canonical recorder now);
                # unary requests only surface total latency
                RECORDER.observe_ttft(time.perf_counter() - req.t_submit)
        self._deliver(req)

    def _deliver(self, req: GenRequest) -> None:
        """Assemble per-request output from the per-row sequences: stream
        chunks when every row has them, the final array at completion."""
        if req.cancelled or req.future.done():
            return
        if req.chunk is not None:
            while True:
                avail = min(len(s.emitted) for s in req.seqs)
                n = min(req.chunk, req.max_new - req.delivered)
                if n <= 0 or avail - req.delivered < n:
                    break
                arr = np.asarray(
                    [s.emitted[req.delivered:req.delivered + n]
                     for s in req.seqs], np.int32)
                req.delivered += n
                if req.t_first is None:
                    # stamped BEFORE the put: the consumer may wake, and
                    # take its own stamp, before put() returns
                    req.t_first = time.perf_counter()
                    if req.t_admit is not None:
                        self._noted["req_prefill_s"].append(
                            req.t_first - req.t_admit)
                req.queue.put(arr)
        if all(s.done for s in req.seqs):
            out = np.asarray([s.emitted for s in req.seqs], np.int32)
            elapsed = time.perf_counter() - req.t_submit
            if req.chunk is not None and elapsed > 0:
                # like TTFT above: the decode-rate SLO family is fed once
                # per STREAM (matching the static path, where the unary
                # lane ran generate(eager=False) and recorded nothing)
                RECORDER.observe_decode_rate(out.size / elapsed)
            if not req.future.done():
                req.future.set_result(out)
            if req.chunk is not None:
                req.queue.put(None)

    def _retire_finished(self) -> None:
        for seq in [s for s in self._active if s.done]:
            self._active.remove(seq)
            self._retire(seq, seq.retire_reason or "length")
            self._counts["retired"] += 1

    def _record_seq_span(self, seq: _Sequence, name: str,
                         method: str) -> None:
        """One per-sequence span (prefill / decode leg) parented under
        the request's captured trace context — the scheduler's phases
        become visible legs of a (federated) trace tree.  No-op unless
        tracing is on AND the request's trace was sampled; ``record_span``
        enforces both."""
        from seldon_core_tpu.utils.tracing import TRACER

        ctx = getattr(seq.request, "trace_ctx", None)
        if ctx is None or not TRACER.enabled or seq.t_start <= 0.0:
            return
        TRACER.record_span(
            name, kind="dispatch", method=method, start_s=seq.t_start,
            duration_ms=(time.time() - seq.t_start) * 1e3, ctx=ctx,
            rows=1, n_valid=seq.n_valid, tokens=len(seq.emitted),
            role=self.role,
        )

    def _seq_event(self, seq: _Sequence, name: str, **attrs: Any) -> None:
        """Append one lifecycle event to a SAMPLED sequence's timeline.
        Strictly a no-op for untraced requests — the per-tick hot path
        pays one attribute read and one boolean test."""
        ctx = getattr(seq.request, "trace_ctx", None)
        if ctx is None:
            return
        from seldon_core_tpu.utils.tracing import TRACER

        if not ctx.sampled and not (
            getattr(ctx, "pm", False) and TRACER.pm_hook is not None
        ):
            # not sampled AND not under postmortem tail capture: the
            # preempt/admit timeline would reach no surface — skip it
            return
        if not TRACER.enabled or len(seq.events) >= 512:
            return
        ev: Dict[str, Any] = {"name": name, "ts": round(time.time(), 6)}
        if attrs:
            ev["attrs"] = attrs
        seq.events.append(ev)

    def _emit_seq_timeline(self, seq: _Sequence, reason: str) -> None:
        """One ``gen_sequence`` span per retired SAMPLED sequence,
        carrying the whole lifecycle (enqueue -> admit -> prefill chunks
        -> decode rounds -> retire, preemptions included) as span events
        — the per-sequence leg of the causal trace tree."""
        if not seq.events:
            return
        ctx = getattr(seq.request, "trace_ctx", None)
        if ctx is None:
            return
        from seldon_core_tpu.utils.tracing import TRACER, Span, new_span_id

        pm_only = not ctx.sampled
        if pm_only and not (
            getattr(ctx, "pm", False) and TRACER.pm_hook is not None
        ):
            return
        if not TRACER.enabled:
            return
        start_s = seq.events[0]["ts"]
        TRACER.add(Span(
            puid=ctx.puid, name="gen_sequence", kind="gen_seq",
            method=reason, start_s=start_s,
            duration_ms=(time.time() - start_s) * 1e3,
            attrs={"sid": seq.sid, "row": seq.row,
                   "tokens": len(seq.emitted), "n_valid": seq.n_valid,
                   "role": self.role},
            trace_id=ctx.trace_id, span_id=new_span_id(),
            parent_span_id=ctx.span_id, events=list(seq.events),
            pm_only=pm_only,
        ))
        seq.events = []

    def _stamp_tick_error(self, exc: BaseException) -> None:
        """Error-path visibility in traces: stamp one ``gen_tick_error``
        span under any sampled request riding the failing tick (the
        batch is about to be failed wholesale by ``_fail_all``)."""
        from seldon_core_tpu.utils.tracing import TRACER

        if not TRACER.enabled:
            return
        for s in (list(self._active) + list(self._prefilling)
                  + [row[0] for fl in self._unread for row in fl.rows]):
            ctx = getattr(s.request, "trace_ctx", None)
            if ctx is not None and ctx.sampled:
                TRACER.record_span(
                    "gen_tick_error", kind="gen_step", method="error",
                    start_s=time.time(), duration_ms=0.0, ctx=ctx,
                    error=repr(exc)[:200],
                )
                return

    def _retire(self, seq: _Sequence, reason: str) -> None:
        self._release_blocks(seq)
        seq.state = _Sequence.DONE
        if self.role == "decode" and reason not in ("cancelled",):
            # the decode leg of a disaggregated generation: one span per
            # imported sequence, parented under the prefill side's
            # kv_handoff span (the context rode the relay sidecar)
            self._record_seq_span(seq, "decode", "decode")
        self.retired_total[reason] = self.retired_total.get(reason, 0) + 1
        RECORDER.record_gen_retired(reason)
        self._seq_event(seq, "retire", reason=reason,
                        emitted=len(seq.emitted))
        self._emit_seq_timeline(seq, reason)
        self._deliver(seq.request)

    def _finish_error(self, seq: _Sequence, exc: BaseException) -> None:
        self._retire(seq, "error")
        req = seq.request
        if not req.future.done():
            req.future.set_exception(exc)
        # plain put (see _fail_all): the unbounded queue makes Full
        # impossible, and a silent drop here would hang a stream consumer
        req.queue.put(exc)
        # the request is dead: its sibling rows must not keep decoding
        # (or holding KV blocks) for a client that already got the error
        # — _drop_cancelled sweeps them at the next tick
        req.cancelled = True

    # -- accounting --------------------------------------------------------

    def _publish(self, admitted: int, retired: int, kind: str,
                 tokens: int, duration_s: float,
                 detail: Optional[Dict[str, Any]] = None) -> None:
        alloc = self._allocator
        used = alloc.used if alloc is not None else 0
        total = alloc.capacity if alloc is not None else 0
        hw = alloc.high_water if alloc is not None else 0
        with self._lock:
            waiting = len(self._waiting) + len(self._arrivals)
        inflight = len(self._active) + len(self._prefilling)
        RECORDER.set_gen_scheduler(
            inflight=inflight, waiting=waiting, blocks_used=used,
            blocks_total=total, blocks_high_water=hw,
        )
        RECORDER.set_kv_slots(
            active=used * self.block_size,
            reserved=(total - used) * self.block_size,
        )
        # idle spins included: steps_total["idle"] + the /genperf duty
        # cycle make a hot-spinning scheduler visible (satellite of the
        # flight-recorder PR — idle used to be invisible here)
        RECORDER.record_gen_step(kind)
        # a traced sequence in this step tags the record so the step's
        # seldon_tpu_dispatch_seconds observation carries its trace_id as
        # an OpenMetrics exemplar — on a decode replica that is the
        # handoff's trace, so exemplars join handoffs to federated traces
        trace_id = ""
        if kind != "idle":
            from seldon_core_tpu.utils.tracing import TRACER

            if TRACER.enabled:
                for s in self._active + self._prefilling:
                    ctx = getattr(s.request, "trace_ctx", None)
                    if ctx is not None and ctx.sampled:
                        trace_id = ctx.trace_id
                        break
        SPINE.record_gen_step(
            kind=kind, duration_s=duration_s, active=inflight,
            waiting=waiting, admitted=admitted, retired=retired,
            blocks_used=used, blocks_total=total, tokens=tokens,
            executable="" if kind == "idle" else f"gen_step:{kind}",
            trace_id=trace_id, detail=detail,
        )
