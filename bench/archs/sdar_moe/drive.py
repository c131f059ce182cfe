"""How a round of the ``sdar_moe`` generator is driven, and what of it the
reference is asked (lib/children.py says what an event is).

The prefill has run over each whole prompt, its last block short where the
prompt is no whole number of blocks: that is the row's ``prefill`` event
(its logits choose no token).  The round is the program's own,
``paged_decode_round`` — the function ``GenServer`` drives — asked, by the
static argument only this driver sets (``trace_passes``), for what every
denoising pass saw, picked and chose: ``span / block_length`` blocks a
row, the first one begun by the prompt's remainder, which the round is
handed as the scheduler hands it.  Every denoising pass that fixed
something in a row is an event: the context is the prompt's whole blocks,
the blocks this round has finished and the block AS THAT PASS SAW IT (the
mask id where it stood); the positions judged are the ones it fixed, with
the ids it put there.  The pass that writes a finished block's K/V chooses
nothing and is no event: the next block's events see what it wrote."""

import jax.numpy as jnp
import numpy as np

NOT_JUDGED = -1


def drive(unit, params, pool, tables, prompts, logits, deployment):
    from seldon_core_tpu.models.generate import paged_decode_round_jit

    block = unit.cfg.block_length
    R, B = len(prompts), tables.shape[0]
    n_valid = np.zeros((B,), np.int32)
    n_valid[:R] = [len(p) for p in prompts]
    whole = [len(p) - len(p) % block for p in prompts]
    held = np.zeros((B, block), np.int32)
    for r, p in enumerate(prompts):
        held[r, :len(p) - whole[r]] = p[whole[r]:]
    blocks, pool, *rest = paged_decode_round_jit(
        params, pool, tables, jnp.asarray(held), jnp.asarray(n_valid),
        jnp.asarray(n_valid > 0), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.uint32), unit.cfg, span=deployment["span"],
        temperature=unit.temperature, top_k=unit.top_k, top_p=unit.top_p,
        eos_token=unit.eos_token, trace_passes=True)
    blocks = np.asarray(blocks)
    saw, picked, chose = (np.asarray(rest[-1][k])        # [blocks, steps, B, L]
                          for k in ("saw", "picked", "chose"))
    events = [{"row": r, "ids": p, "at": np.asarray([len(p) - 1]),
               "chose": np.asarray([NOT_JUDGED]), "prefill": 0}
              for r, p in enumerate(prompts)]
    for b in range(saw.shape[0]):
        for step in range(saw.shape[1]):
            for r, p in enumerate(prompts):
                at = np.flatnonzero(picked[b, step, r])
                if len(at):
                    events.append({
                        "row": r, "chose": chose[b, step, r, at],
                        "ids": np.concatenate([
                            p[:whole[r]], blocks[r, :b * block],
                            saw[b, step, r]]),
                        "at": whole[r] + b * block + at})
    return {"tokens": [blocks[r, len(p) - whole[r]:]
                       for r, p in enumerate(prompts)],
            "events": events}
