"""Bytes and FLOPs the two paged programs NEED for the ``dense_gelu``
block (reference.py beside this file), from the configuration's sizes —
what the algorithm requires, not what today's program moves (the gathered
view padded to the longest row, written and read again, is the program's
choice and is not counted).  Plain arithmetic: the benchmark's parent
imports this file, so it may not import JAX.

Per configuration (bf16 = 2 bytes):
  layer matmul parameters   D*(D + 2*KV*hd) + D*D + 2*D*F
  embedding (tied head)     V*D, read once per step by the unembedding
  KV per position           2 * KV * hd * 2 bytes per layer

``counters`` (the window's deltas of ``GET /genperf``) is there for a block
whose needs depend on what the program counted — the experts a step read;
a dense block reads every weight in every step and ignores it."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    D = config["hidden_size"]
    H = config["num_attention_heads"]
    KV = config["num_key_value_heads"]
    F = config["intermediate_size"]
    L = config["num_hidden_layers"]
    V = config["vocab_size"]
    hd = D // H
    layer = D * (D + 2 * KV * hd) + D * D + 2 * D * F
    return {
        "D": D, "H": H, "KV": KV, "hd": hd, "F": F, "L": L, "V": V,
        "layer_params": layer,
        "matmul_params": L * layer + V * D,
        "weight_bytes": 2 * (L * layer + V * D),
        "kv_bytes_per_position": L * 2 * KV * hd * 2,
    }


def decode_step(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    """One single-token step over ``rows`` real rows whose caches hold
    ``live_positions`` positions in total: weights once, live KV once,
    ``rows`` new KV entries written."""
    s = sizes(config)
    nbytes = (s["weight_bytes"]
              + s["kv_bytes_per_position"] * (live_positions + rows))
    flops = (2.0 * s["matmul_params"] * rows
             + 4.0 * s["L"] * s["H"] * s["hd"] * live_positions)
    return {"bytes": nbytes, "flops": flops}


def prefill(config: dict, calls: float, tokens: float,
            attended_positions: float, counters: dict) -> dict:
    """``calls`` prefill programs consuming ``tokens`` real prompt tokens in
    all; ``attended_positions`` is the sum over those tokens of the
    positions each attends to (causal: its own index + 1).  Weights once
    per call, each token's KV written once and the earlier chunks' KV read
    once per call (bounded above by one read per token's row — counted as
    one read of every written position)."""
    s = sizes(config)
    nbytes = (calls * s["weight_bytes"]
              + 2 * s["kv_bytes_per_position"] * tokens)
    # the unembedding runs for the LAST position of a row only
    flops = (2.0 * (s["matmul_params"] - s["V"] * s["D"]) * tokens
             + 2.0 * s["V"] * s["D"] * calls
             + 4.0 * s["L"] * s["H"] * s["hd"] * attended_positions)
    return {"bytes": nbytes, "flops": flops}
