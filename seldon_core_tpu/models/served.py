"""What the scheduler is told about the generator it serves.

``served(cfg)`` reads an ``LMConfig`` once -- by ``LMConfig.kinds``, never
by the letters -- into one frozen description, and runtime/genserver.py
asks it instead of branching on the configuration: how a round is driven,
what the pool holds, which lanes such a generator cannot take
(``TransformerGenerator`` asks the same list), which kernels serve it over
a pool, what a dispatched call is given (the counts its span and the tick
record carry, utils/genperf.py) and what a decoded token costs.  A new
architecture states itself here and in the programs of models/generate.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from seldon_core_tpu.models.transformer import LMConfig

__all__ = ["BRINGS", "Kernels", "Served", "served"]

#: what ``Served.held`` puts in every place of a row that brings a block
#: (no id is negative)
BRINGS = -1


def _tree_bytes(tree) -> int:
    import jax

    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@dataclasses.dataclass
class Kernels:
    """Which kernels serve one generator over one pool: decided once the
    pool exists (``Served.kernels``), because only its owner sees the mesh
    the pool is sharded over -- a traced program cannot."""

    #: a decode round attends over the pool in place (ops/paged_attention.py)
    #: and not over a gathered view
    attends_inplace: Any
    #: ... updates every live row's retention states where they lie (the
    #: step kernel of ops/retention.py) and not row by row in jax.numpy
    states_inplace: Any
    _chunk: Any             # width -> the same of a prefill call, or None
    #: ... updates every live row's Mamba-2 state where it lies (the step
    #: kernel of ops/ssm.py) and not over gathered rows: an answer of its
    #: own, because such layers stand beside attention layers
    ssm_inplace: Any = False
    #: a layer of dropless experts takes an expert's whole feed-forward as
    #: one kernel (parallel/moe.py ``_experts_fused``) and not as two
    #: grouped matmuls, in both programs; None without such layers: the
    #: argument is not given
    experts_fused: Any = None

    @property
    def inplace(self):
        """What the decode program takes as ``inplace=``."""
        return self.attends_inplace or self.states_inplace

    @property
    def experts_how(self) -> Dict[str, Any]:
        """The keyword either program takes because of its expert layers."""
        return ({} if self.experts_fused is None
                else {"experts_fused": self.experts_fused})

    @property
    def round_how(self) -> Dict[str, Any]:
        """The keywords the decode program takes because of them."""
        return {"inplace": self.inplace, "ssm_inplace": self.ssm_inplace,
                **self.experts_how}

    def fused(self, width: int):
        """What a prefill call of ``width`` positions a row takes as
        ``fused=`` (the chunk kernel of ops/retention.py), asked once a
        width; None without retention layers: the argument is not given."""
        return self._chunk(width)

    def round_counts(self, span: int, passes: int) -> Dict[str, int]:
        """What a round of ``span`` steps, ``passes`` of the model, counts
        because of them."""
        return {"inplace_steps": span if self.attends_inplace else 0,
                "retention_fused_steps": span if self.states_inplace else 0,
                "ssm_fused_steps": span if self.ssm_inplace else 0,
                "experts_fused_passes": passes if self.experts_fused else 0}

    def prefill_counts(self, width: int, rows: int) -> Dict[str, int]:
        """... and a prefill call of ``rows`` real rows."""
        return {"retention_fused_rows": rows if self.fused(width) else 0,
                "experts_fused_calls": int(bool(self.experts_fused))}


@dataclasses.dataclass(frozen=True)
class Served:
    """One generator as its scheduler sees it (``served``)."""

    cfg: LMConfig
    #: what a round, a KV block and a prefill chunk are whole multiples of:
    #: 1, or the block a generator by diffusion denoises at once
    quantum: int
    #: passes of the model a block of ``quantum`` positions takes: 1 a
    #: token, or the denoising passes and the one that writes the K/V -- of
    #: which the last shares a pass of the device with the first of the
    #: row's NEXT block, in this round or the next (``round_counts``
    #: ``shared_passes``)
    block_passes: int
    #: a prompt's last chunk picks the row's first token, which then rides
    #: from round to round PENDING (sampled, not yet in the cache), in the
    #: device's carry and on the host.  False for diffusion blocks: a
    #: prefill chooses no token (no head, no ``first`` program), and what
    #: rides from round to round in the carry's ``tok`` is a BLOCK, ``[S,
    #: quantum]``: a round leaves its last block fixed and NOT YET IN THE
    #: POOL, and the row's next round writes its K/V in its first pass
    #: (``generate._denoising_round``).  So **the pool of a row that brings
    #: a block lags the host's ``n_valid`` -- the positions fixed -- by that
    #: block**, from the row's first round to its last.  Nobody reads a
    #: lagging pool: a preempted row's tokens become prompt and its prefill
    #: writes every block; a finished row's last block is never written;
    #: and the lanes that would read K/V a round has just made (``roles``:
    #: a handoff streams blocks; ``prefix``; ``draft``; ``sampled``) are
    #: refused for such a generator (``served``)
    picks_first: bool
    #: the word for what the pool holds, in errors: "KV", or "state" where
    #: no layer holds K/V and the pool is one state entry a block
    holds: str
    #: some layer keeps a fixed-size state a sequence beside or in place of
    #: K/V (a gated short convolution, power retention, a Mamba-2
    #: state-space layer), in the pool at the
    #: id of the sequence's FIRST block: zero at position 0, carried over
    #: chunks and rounds, freed with the block, recomputed from the prompt
    #: after a preemption -- and never snapshotted or rolled back
    stateful: bool
    #: layers whose FFN is dropless routed experts (not the leading dense
    #: ones) and the experts each holds HERE (all the router scores, or the
    #: chip's share of them): what expert slots are counted over
    routed: int
    experts: int
    #: the programs count the experts they read (the programs' own test)
    counts_experts: bool
    #: ``(lane, why)``: the lanes such a generator cannot take (``refuse``)
    refusals: Tuple[Tuple[str, str], ...]

    # -- the lanes it cannot take ------------------------------------------

    def refuse(self, **taken: bool) -> None:
        """Raise for the first lane of ``taken`` this generator cannot
        serve: ``draft`` (speculative decoding), ``prefix`` (shared),
        ``roles`` (prefill / decode), ``sampled``, ``mesh``."""
        for lane, why in self.refusals:
            if taken.get(lane):
                raise ValueError(why)

    def whole(self, **sizes: int) -> None:
        """Raise for a size that is no whole number of ``quantum``."""
        for name, n in sizes.items():
            if n % self.quantum:
                raise ValueError(
                    f"{name}={n} is no whole number of diffusion blocks "
                    f"of {self.quantum}")

    # -- how a round is driven ---------------------------------------------

    @property
    def round(self) -> Dict[str, int]:
        """How a round decodes its span, for ``/stats``: blocks of so many
        positions, so many denoising passes each (1 and 1: a token a step)."""
        return {"block_length": self.quantum,
                "denoising_steps": int(self.cfg.denoising_steps)}

    def round_base(self, n_valid: int) -> int:
        """The position a row holding ``n_valid`` starts its next round on:
        right after them, or -- diffusion blocks -- where their last whole
        block ends (the rest of the prompt goes into the round's first
        block again, and the round emits so many tokens fewer)."""
        return n_valid - n_valid % self.quantum

    def held(self, rows: int, brings: Sequence[bool] = ()
             ) -> Optional[np.ndarray]:
        """What the host uploads for a round of ``rows`` beside the carry:
        None where the pending token rides the carry and nothing else is to
        say, else ``[rows, quantum]`` int32 -- for a row in its first round
        since it was admitted the ids that round finds in its first block
        (the caller fills the prompt's remainder in), and ``BRINGS`` in
        every place of a row that ``brings`` the last block of the round
        before: the host knows which by arithmetic (the row rode a round
        since ``_admit``), and the carry's ``take`` puts the block and the
        eos latch the device holds at its slot in that row's place -- a row
        that brings nothing starts with neither."""
        if self.picks_first:
            return None
        held = np.zeros((rows, self.quantum), np.int32)
        held[:len(brings)][np.asarray(brings, bool)] = BRINGS
        return held

    # -- what a call is given ------------------------------------------------

    def round_counts(self, n_valid: Sequence[int], span: int,
                     brings: Sequence[bool] = ()) -> Dict[str, int]:
        """What a decode round of ``span`` positions over live rows holding
        ``n_valid`` is given, by its span's and the tick record's names:
        ``passes`` of the model in ``blocks`` -- a block's passes, whoever
        shares them and whichever round runs them -- the ``kv_positions``
        they attend over and ``expert_slots`` (experts held x expert layers
        x passes: what the round's own ``experts_read`` is a share of), all
        three by the round's OWN blocks, which is what the benchmark takes
        a round for (bench/archs/<arch>/needs.py; tests/bench holds
        ``passes`` to ``block_passes`` a block); and, by what the round
        RUNS, ``row_passes`` (passes of the model a real row, summed) and
        ``shared_passes`` (the passes of the device that served two of
        them); 0 shared for a token a step.

        A round of diffusion blocks runs every block's denoising passes
        and, riding the first of them, the pass that writes the K/V of the
        block before: the round's own but for its first block, where it is
        the block a row ``brings`` (``held``).  So a row runs ``passes - 1``
        of them and one more if it brings a block -- its last block's K/V
        pass is the next round's, and the last of all nobody's
        (``generate._denoising_round``) -- and the round shares ``blocks -
        1`` passes of the device, one more if any row brings a block."""
        blocks = span // self.quantum
        passes = blocks * self.block_passes
        if self.quantum > 1:
            # every pass of a block reads the row's cache up to the block's
            # end once, whatever the queries in it
            kv_positions = sum(
                self.block_passes
                * (self.round_base(n) + (b + 1) * self.quantum)
                for n in n_valid for b in range(blocks))
            brought = sum(map(bool, brings))
            row_passes = (passes - 1) * len(n_valid) + brought
            shared = blocks - 1 + bool(brought)
        else:
            # each of the span steps attends over ~n_valid + step positions
            kv_positions = sum(span * (n + span // 2) for n in n_valid)
            row_passes, shared = passes * len(n_valid), 0
        # the pass that writes a block's K/V stops at its last layer's K/V:
        # one expert layer fewer
        skipped = blocks if self.quantum > 1 else 0
        return {
            "passes": passes, "blocks": blocks, "row_passes": row_passes,
            "kv_positions": kv_positions, "shared_passes": shared,
            "expert_slots": ((passes * self.routed - skipped) * self.experts
                             if self.routed else 0)}

    def prefill_counts(self, start: Sequence[int], width: Sequence[int]
                       ) -> Dict[str, int]:
        """What a prefill call is given whose real rows bring ``width``
        tokens from position ``start`` on: ``tokens``, the ``kv_positions``
        held and the positions ``attended`` (causal: a token's own index +
        1), ``expert_slots``, and ``carried_rows``, the rows whose layers'
        state comes from an earlier chunk."""
        tokens = sum(width)
        return {
            "tokens": tokens, "kv_positions": sum(start) + tokens,
            "attended": sum(w * s + w * (w + 1) // 2
                            for s, w in zip(start, width)),
            # the experts a call could read, where the call counts the ones
            # it did (a prefill that chooses no token, in the logits'
            # place): slots nobody counts against would read as 0 read
            "expert_slots": (0 if self.picks_first
                             else self.routed * self.experts),
            "carried_rows": (sum(s > 0 for s in start)
                             if self.stateful else 0)}

    # -- what the pool holds, and the kernels over it ------------------------

    def _state_bytes(self, mixer: str) -> int:
        """Bytes of ONE pool entry's states over the layers of ``mixer``:
        a row's, since a row's state lives at its first block's id."""
        import jax

        from seldon_core_tpu.models.generate import init_block_pool

        pool = jax.eval_shape(lambda: init_block_pool(self.cfg, 1, 1))
        return _tree_bytes([pool[f"l{i}"] for i, (m, _) in enumerate(
            self.cfg.kinds) if m == mixer])

    @functools.cached_property
    def retention_row_bytes(self) -> int:
        """Bytes of one row's states over the retention layers (0 without):
        what a decode step or a prefill chunk reads and writes a row."""
        return self._state_bytes("ret")

    @functools.cached_property
    def ssm_row_bytes(self) -> int:
        """... and over the Mamba-2 state-space layers: the float32 matrix
        state and the convolution's taps."""
        return self._state_bytes("ssm")

    def refuse_pool(self, num_blocks: int, params, limit) -> None:
        """A retention or state-space layer holds one state entry a BLOCK
        of the pool (models/generate.py init_block_pool): refuse a pool
        whose entries a device of ``limit()`` bytes (None: it does not say)
        cannot hold beside the parameters -- the default block of 16
        positions and pool of a thousand blocks are 35 GB a retention layer
        and 2.2 GB a state-space layer at the published widths -- and say
        which two settings to change, where the allocation would only
        fail."""
        row = self.retention_row_bytes + self.ssm_row_bytes
        limit = limit() if row else None
        held = _tree_bytes(params)
        if limit and num_blocks * row + held > limit:
            advice = (
                "Deploy a block a row: set "
                "SELDON_TPU_GEN_BLOCK_SIZE to the longest row (prompt + "
                "answer + one round, a multiple of the prefill chunk) and "
                "SELDON_TPU_GEN_POOL_BLOCKS to the rows held at once + 1"
                if self.holds == "state" else
                "Deploy few, large blocks: raise SELDON_TPU_GEN_BLOCK_SIZE "
                "(the attention layers' K/V a position costs the same) and "
                "lower SELDON_TPU_GEN_POOL_BLOCKS to the blocks the rows "
                "held at once need")
            raise ValueError(
                f"a generator of retention or state-space layers keeps a "
                f"state of {row / 1e6:.1f} MB a BLOCK of the pool, whatever "
                f"the block holds: {num_blocks} blocks are "
                f"{num_blocks * row / 1e9:.1f} GB beside "
                f"{held / 1e9:.1f} GB of parameters, and the device has "
                f"{limit / 1e9:.1f} GB.  " + advice)

    def kernels(self, pool, mesh, rows: int, dtype) -> Kernels:
        """The kernels that serve this generator over ``pool``, sharded
        over ``mesh`` or on one device, for batches of up to ``rows``
        padded rows and activations of ``dtype``: ``decode_inplace`` /
        ``retention_fused`` / ``ssm_fused`` / ``experts_fused`` of
        models/generate.py over what they observe."""
        import jax

        from seldon_core_tpu.models import generate as G

        cfg = self.cfg
        # only its shapes are asked: what outlives the call holds no buffer
        pool = jax.eval_shape(lambda: pool)
        attends = G.decode_inplace(
            pool, mesh, width=self.quantum, heads=cfg.n_heads, rows=rows,
            head_dim=cfg.hd)
        states = G.retention_fused(pool, mesh, heads=cfg.n_heads, rows=rows)

        @functools.lru_cache(maxsize=None)
        def chunk(width: int):
            if not self.retention_row_bytes:
                return None
            return G.retention_fused(pool, mesh, heads=cfg.n_heads,
                                     rows=rows, width=width, dtype=dtype)

        return Kernels(attends, states, chunk,
                       G.ssm_fused(pool, mesh, rows=rows),
                       G.experts_fused(cfg, mesh, dtype) if self.routed
                       else None)

    # -- what a token costs --------------------------------------------------

    def decode_costs(self) -> Dict[str, float]:
        """Analytic cost features of one decoded token: utils/genperf.py
        prices served decode MFU / HBM-BW utilisation with them against
        REAL tokens (matmul weights at serving dtype, two KV tensors a
        position plus int8 scales)."""
        cfg = self.cfg
        d, ff, v = cfg.d_model, cfg.d_ff, cfg.vocab
        kvh, hd = cfg.kv_heads, cfg.hd
        q_out = cfg.n_heads * hd
        qkv_out = q_out + 2 * kvh * hd
        # a token's own work in a layer, by the layer's kind: the mixer's
        # matrices and the FFN's -- of an expert layer the router and the
        # token's moe_k experts
        mixers = {"attn": d * qkv_out + q_out * d, "conv": 4 * d * d,
                  "ret": d * (qkv_out + kvh) + q_out * d,
                  "ssm": d * (2 * cfg.ssm_inner + cfg.ssm_conv_dim
                              + cfg.ssm_heads), None: 0}
        # an expert is three matrices, or two where it has no gate; of a
        # token's moe_k picks the share that falls on experts held here
        mats = 3 if cfg.expert_act == "silu" else 2
        ffns = {"gelu": 2 * d * ff, "moe": 2 * d * ff, "gated": 3 * d * ff,
                "experts": d * (cfg.n_experts + mats * (
                    cfg.moe_k * cfg.d_expert * cfg.held / cfg.n_experts
                    + cfg.d_shared)), None: 0}
        layers = sum(mixers[m] + ffns[f] for m, f in cfg.kinds)
        attending = sum(m == "attn" for m, _ in cfg.kinds)
        wb = 1 if cfg.quant == "int8" else 2
        kv_int8 = cfg.kv_quant == "int8"
        return {
            # matmul FLOPs per generated token (attention's
            # position-dependent term excluded)
            "flops": float(2 * (layers + d * v)),
            # HBM bytes ONE device step streams regardless of batch: every
            # matmul'd weight once, the bf16 unembed once
            "bytes_accessed": float(wb * layers + 2 * d * v),
            "output_bytes": 0.0,
            # HBM bytes per CACHE POSITION a step's attention reads (k + v
            # across the layers that attend, + f32 scales when int8 KV)
            "kv_bytes_per_position": float(
                attending * (2 * kvh * hd * (1 if kv_int8 else 2)
                             + (8 * kvh if kv_int8 else 0))),
        }


def served(cfg: LMConfig) -> Served:
    """The description of a generator of ``cfg``."""
    mixers = {mixer for mixer, _ in cfg.kinds}
    stateful = bool(mixers & {"conv", "ret", "ssm"})
    refusals = []
    if cfg.block_length > 1:
        why = ("a generator by diffusion over blocks is served greedy, "
               "unified, without a draft model or a shared prefix")
        refusals += [(lane, why)
                     for lane in ("draft", "prefix", "sampled", "roles")]
    if stateful:
        # nothing snapshots a layer's state or rolls it back, so the lanes
        # that would have to are refused by name
        lead = ("a generator with gated short-convolution, retention or "
                "state-space layers is served unified and cannot take ")
        refusals += [
            ("draft", lead + "speculative decoding: a rejected draft would "
             "have to roll the layers' state back"),
            ("prefix", lead + "a shared prefix: its pinned blocks are shared "
             "by table reference, the state after it is one sequence's"),
            ("roles", lead + "the prefill / decode roles: a handoff streams "
             "K/V blocks, not the layers' state")]
    if mixers & {"ret", "ssm"}:
        refusals.append((
            "mesh", "a generator of retention or state-space layers is "
            "served on one chip: "
            "nothing shards a layer's state over a mesh yet (by head, "
            "beside the parameters)"))
    elif cfg.experts_held:
        refusals.append((
            "mesh", "an expert layer told which experts it holds is one "
            "chip's share of a layer: no ``ep`` mesh exchanges the other "
            "chips' parts yet"))
    return Served(
        cfg=cfg, quantum=int(cfg.block_length),
        block_passes=cfg.denoising_steps + 1 if cfg.block_length > 1 else 1,
        picks_first=cfg.block_length == 1,
        holds="state" if "ret" in mixers else "KV", stateful=stateful,
        routed=cfg.expert_layers,
        experts=cfg.held if cfg.expert_layers else 0,
        counts_experts=bool(cfg.d_expert), refusals=tuple(refusals))
