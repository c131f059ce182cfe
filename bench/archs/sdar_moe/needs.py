"""Bytes and FLOPs the two paged programs NEED for the ``sdar_moe`` block
(reference.py beside this file), from the configuration's sizes — what the
algorithm requires, not what today's program moves.  Plain arithmetic: the
benchmark's parent imports this file, so it may not import JAX.

Per configuration (bf16 = 2 bytes), a layer:
  attention + router   D*hd*(H + 2*KV) + H*hd*D + D*E
  one expert           3 * D * F  (gate, up, down)
  KV per position      2 * KV * hd * 2 bytes
and once: the untied head V*D (the embedding is a gather of a few rows).

A decode round is NOT ``span`` single-token steps: it is ``span /
block_length`` blocks, each ``denoising_steps`` passes of the whole block
through every layer and the head, and one more pass that writes the
block's K/V — without the head, and stopping at its last layer's K/V.
``readers/trace.py`` multiplies ``decode_step`` by calls x ``span``, so
``decode_step`` is a ``span``-th of what ONE ROUND needs.

A pass reads the experts its tokens CHOSE, not the experts held: the
program's own count where the window's ``/genperf`` deltas carry it
(``served_decode.experts_read`` over ``.expert_slots``, the experts held x
expert layers x passes; a prefill call's likewise from
``served_prefill``), so a program that reads fewer experts is not credited
with the bytes of all of them; else what that many tokens' ``rows *
block_length * num_experts_per_tok`` uniform picks are expected to hit."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    D, hd = config["hidden_size"], config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    E, k = config["num_experts"], config["num_experts_per_tok"]
    F, L, V = (config["moe_intermediate_size"], config["num_hidden_layers"],
               config["vocab_size"])
    return {
        "D": D, "hd": hd, "H": H, "KV": KV, "E": E, "k": k, "F": F, "L": L,
        "V": V,
        "qkv_params": D * hd * (H + 2 * KV),
        "attn_params": D * hd * (H + 2 * KV) + H * hd * D + D * E,
        "expert_params": 3 * D * F,
        "head_params": V * D,
        "kv_bytes_per_position": L * 2 * KV * hd * 2,
        "attn_flops_per_position": 4 * L * H * hd,
    }


def expected_read(config: dict, tokens: float) -> float:
    """Distinct experts ``tokens`` tokens are expected to choose in one
    layer under uniform routing."""
    s = sizes(config)
    return s["E"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (s["k"] * tokens))


def experts_read(config: dict, tokens: float, counters: dict,
                 program: str = "served_decode") -> float:
    """Experts one layer reads in one pass (``served_prefill``: in one
    call): the program's own mean over the window, else the expectation for
    ``tokens`` tokens a pass."""
    served = (counters or {}).get(program, {})
    slots, read = served.get("expert_slots"), served.get("experts_read")
    if slots and read is not None:
        return sizes(config)["E"] * read / slots
    return expected_read(config, tokens)


def round_shape(config: dict) -> dict:
    """Passes of one decode round, from the file's own sizes."""
    block, steps = config["block_length"], config["denoising_steps"]
    blocks = config["deployment"]["span"] // block
    L = config["num_hidden_layers"]
    return {"block": block, "blocks": blocks, "denoise": blocks * steps,
            "commit": blocks,
            # the K/V-writing pass stops at its last layer's K/V
            "expert_layer_passes": blocks * (steps * L + L - 1)}


def experts(config: dict, rows: float, counters: dict) -> dict:
    """What the expert layers of ONE ROUND need: the chosen experts'
    weights once a layer a pass, and every token's ``k`` experts' FLOPs."""
    s, r = sizes(config), round_shape(config)
    read = experts_read(config, rows * r["block"], counters)
    return {"bytes": 2.0 * r["expert_layer_passes"] * read
            * s["expert_params"],
            "flops": 2.0 * r["expert_layer_passes"] * rows * r["block"]
            * s["k"] * s["expert_params"]}


def round_needs(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    s, r = sizes(config), round_shape(config)
    L, tokens = s["L"], rows * r["block"]
    moe = experts(config, rows, counters)
    # attention and router weights: every layer of a denoising pass; of the
    # K/V-writing pass all but the last layer's, of which the q, k, v only
    dense_params = (r["denoise"] * L * s["attn_params"]
                    + r["commit"] * ((L - 1) * s["attn_params"]
                                     + s["qkv_params"]))
    passes = r["denoise"] + r["commit"]
    return {
        "bytes": 2.0 * (dense_params + r["denoise"] * s["head_params"])
        + moe["bytes"]
        # every pass reads the rows' live K/V once; a block's K/V is
        # written once (the passes before the last are the program's)
        + s["kv_bytes_per_position"] * (passes * live_positions
                                        + r["blocks"] * tokens),
        "flops": 2.0 * tokens * (dense_params
                                 + r["denoise"] * s["head_params"])
        + moe["flops"]
        + s["attn_flops_per_position"] * passes * live_positions
        * r["block"]}


def decode_step(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    span = config["deployment"]["span"]
    return {key: value / span for key, value in round_needs(
        config, rows, live_positions, counters).items()}


def prefill(config: dict, calls: float, tokens: float,
            attended_positions: float, counters: dict) -> dict:
    """``calls`` prefill programs over ``tokens`` real prompt tokens: the
    attention and router weights once a call, the experts a call's tokens
    chose (the program's own count, as ``decode_step``'s: calls differ
    widely in size and the experts tokens choose are concave in the tokens,
    so the expectation at a call's MEAN tokens over-counts; it is the
    fallback only), each token's K/V written once and read once.  No head:
    a prompt chooses no token, the program computes none."""
    s = sizes(config)
    read = experts_read(config, tokens / max(calls, 1.0), counters,
                        "served_prefill")
    weights = s["L"] * (s["attn_params"] + read * s["expert_params"])
    return {
        "bytes": 2.0 * calls * weights
        + 2 * s["kv_bytes_per_position"] * tokens,
        "flops": 2.0 * tokens * s["L"] * (
            s["attn_params"] + s["k"] * s["expert_params"])
        + s["attn_flops_per_position"] * attended_positions}
