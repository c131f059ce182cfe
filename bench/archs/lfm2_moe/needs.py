"""Bytes and FLOPs the two paged programs NEED for the ``lfm2_moe`` block
(reference.py beside this file), from the configuration's sizes — what the
algorithm requires, not what today's program moves.  Plain arithmetic: the
benchmark's parent imports this file, so it may not import JAX.

Per configuration (bf16 = 2 bytes), by the kind of a layer
(``layer_types``; the first ``num_dense_layers`` hold a dense FFN, the
others a router and ``num_experts`` experts):
  attention mixer      D*hd*(H + 2*KV) + H*hd*D,  hd = D // H
  conv mixer           D*3D + D*D + K*D           (K = conv_L_cache)
  dense FFN            3 * D * I                  (gate, up, down)
  router               D * E
  one expert           3 * D * F
  KV per position      2 * KV * hd * 2 bytes, over the ATTENTION layers only
  state per row        (K-1) * D * 2 bytes a conv layer: read and written
                       once a step (a prefill call: once a row), whatever
                       the row's length
and once: the tied head V*D (the embedding is a gather of a few rows).

A decode round is ``span`` single-token steps.  A step reads the experts
its rows CHOSE, not the experts held: the program's own count where the
call's (or the window's) counters carry it (``served_decode.experts_read``
over ``.expert_slots``, the experts held x ROUTED layers x steps), so a
program that reads fewer experts is not credited with the bytes of all of
them; else what that many rows' ``rows * num_experts_per_tok`` uniform
picks are expected to hit.  A prefill that chooses a token returns no
count (``served_prefill.expert_slots`` is 0 for it): a call's experts are
then the expectation at the call's tokens, all 32 from 40 tokens up."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    D, H, KV = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    hd = D // H
    E, k = config["num_experts"], config["num_experts_per_tok"]
    F, I = config["moe_intermediate_size"], config["intermediate_size"]
    L, V, K = (config["num_hidden_layers"], config["vocab_size"],
               config["conv_L_cache"])
    types = config["layer_types"][:L]
    attn = sum(t == "full_attention" for t in types)
    dense = min(config["num_dense_layers"], L)
    attn_params = D * hd * (H + 2 * KV) + H * hd * D
    conv_params = D * 3 * D + D * D + K * D
    return {
        "D": D, "hd": hd, "H": H, "KV": KV, "E": E, "k": k, "F": F, "I": I,
        "L": L, "V": V, "K": K,
        "attn_layers": attn, "conv_layers": L - attn,
        "dense_layers": dense, "routed_layers": L - dense,
        "attn_params": attn_params, "conv_params": conv_params,
        "dense_ffn_params": 3 * D * I, "router_params": D * E,
        "expert_params": 3 * D * F, "head_params": V * D,
        # every weight a token's step reads whatever it chooses
        "fixed_params": (attn * attn_params + (L - attn) * conv_params
                         + dense * 3 * D * I + (L - dense) * D * E),
        "kv_bytes_per_position": attn * 2 * KV * hd * 2,
        "state_bytes_per_row": (L - attn) * (K - 1) * D * 2,
        "attn_flops_per_position": 4 * attn * H * hd,
        "conv_flops_per_token": (L - attn) * 2 * (K + 2) * D,
    }


def expected_read(config: dict, tokens: float) -> float:
    """Distinct experts ``tokens`` tokens are expected to choose in one
    layer under uniform routing."""
    s = sizes(config)
    return s["E"] * (1.0 - (1.0 - 1.0 / s["E"]) ** (s["k"] * tokens))


def experts_read(config: dict, tokens: float, counters: dict,
                 program: str = "served_decode") -> float:
    """Experts one routed layer reads in one step (``served_prefill``: in
    one call): the program's own mean where it counts, else the
    expectation for ``tokens`` tokens."""
    served = (counters or {}).get(program, {})
    slots, read = served.get("expert_slots"), served.get("experts_read")
    if slots and read is not None:
        return sizes(config)["E"] * read / slots
    return expected_read(config, tokens)


def experts(config: dict, rows: float, counters: dict) -> dict:
    """What the expert layers of ONE ROUND (``span`` steps) need: the
    chosen experts' weights once a routed layer a step, and every row's
    ``k`` experts' FLOPs."""
    s = sizes(config)
    steps = config["deployment"]["span"] * s["routed_layers"]
    read = experts_read(config, rows, counters)
    return {"bytes": 2.0 * steps * read * s["expert_params"],
            "flops": 2.0 * steps * rows * s["k"] * s["expert_params"]}


def decode_step(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    """One single-token step over ``rows`` rows that hold
    ``live_positions`` cache positions between them."""
    s = sizes(config)
    read = experts_read(config, rows, counters)
    routed = s["routed_layers"]
    return {
        "bytes": 2.0 * (s["fixed_params"] + s["head_params"]
                        + routed * read * s["expert_params"])
        # the attention layers read the rows' live K/V and write one
        # position a row; the conv layers read and write a row's state
        + s["kv_bytes_per_position"] * (live_positions + rows)
        + 2 * s["state_bytes_per_row"] * rows,
        "flops": 2.0 * rows * (s["fixed_params"] + s["head_params"]
                               + routed * s["k"] * s["expert_params"])
        + s["attn_flops_per_position"] * live_positions
        + s["conv_flops_per_token"] * rows}


def prefill(config: dict, calls: float, tokens: float,
            attended_positions: float, counters: dict) -> dict:
    """``calls`` prefill programs over ``tokens`` real prompt tokens: every
    fixed weight and the head once a call (the head on a row's last
    position only: its FLOPs are left out), the experts a call's tokens
    chose, each token's K/V written once and read once.  The conv layers'
    state, a row's two positions a call, is left out: under a thousandth of
    a call's weights."""
    s = sizes(config)
    read = experts_read(config, tokens / max(calls, 1.0), counters,
                        "served_prefill")
    weights = (s["fixed_params"] + s["head_params"]
               + s["routed_layers"] * read * s["expert_params"])
    return {
        "bytes": 2.0 * calls * weights
        + 2 * s["kv_bytes_per_position"] * tokens,
        "flops": 2.0 * tokens * (
            s["fixed_params"]
            + s["routed_layers"] * s["k"] * s["expert_params"])
        + s["attn_flops_per_position"] * attended_positions
        + s["conv_flops_per_token"] * tokens}
