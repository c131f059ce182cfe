"""Bytes and FLOPs the two paged programs NEED for the ``brumby`` block
(reference.py beside this file), from the configuration's sizes.  Plain
arithmetic: the benchmark's parent imports this file, so it may not import
JAX.

What is counted is what the PUBLISHED RECURRENT FORM moves, whatever kernel
implements it: one read and one write of a row's state a token and a layer
in a decode step, one read and one write a row and a layer in a prefill
chunk (the chunk form folds a chunk's positions into the state once).  A
later design that folds the state less often than once a token -- the open
chunk's K/V kept beside it -- moves less than this count and would read
over 100%: it comes after a ``benchmark`` PR that restates ``retention``
(PERF.md section 7).

Per configuration (bf16 = 2 bytes; ``d`` = ``head_dim``, ``P = d (d + 1) /
2`` = 8,256 products a head):
  mixer                D*d*(H + 2*KV) + H*d*D + D*KV + KV   (q, k, v, o,
                       the gate and its bias)
  FFN                  3 * D * I                            (gate, up, down)
  state per row        KV * P * (d + 1) * 4 bytes a layer (float32; the
                       normaliser is the + 1): 34.08 MB, whatever the
                       row's length
  a token's retention  the rank-1 update 2 * KV * P * (d + 1) and the
                       read-out 2 * H * P * (d + 1) FLOPs a layer
and once: the embedding's rows (a gather) and the untied head V*D."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    D, H, KV = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    hd, I = config["head_dim"], config["intermediate_size"]
    L, V = config["num_hidden_layers"], config["vocab_size"]
    P = hd * (hd + 1) // 2
    mixer = D * hd * (H + 2 * KV) + H * hd * D + D * KV + KV
    return {
        "D": D, "H": H, "KV": KV, "hd": hd, "I": I, "L": L, "V": V, "P": P,
        "mixer_params": mixer, "ffn_params": 3 * D * I,
        "layer_params": mixer + 3 * D * I,
        "head_params": V * D,
        # every weight a token's step reads (the embedding is a gather)
        "matmul_params": L * (mixer + 3 * D * I) + V * D,
        "state_bytes_per_row_layer": KV * P * (hd + 1) * 4,
        "state_bytes_per_row": L * KV * P * (hd + 1) * 4,
        "update_flops_per_token_layer": 2 * KV * P * (hd + 1),
        "readout_flops_per_token_layer": 2 * H * P * (hd + 1),
    }


def _state(config: dict, rows: float) -> dict:
    """One read and one write of ``rows`` rows' state in every layer, with
    one token's update and read-out a row."""
    s = sizes(config)
    return {"bytes": 2.0 * rows * s["state_bytes_per_row"],
            "flops": rows * s["L"] * (s["update_flops_per_token_layer"]
                                      + s["readout_flops_per_token_layer"])}


def retention(config: dict, rows: float, counters: dict) -> dict:
    """What the ``retention`` scope of ONE ROUND (``span`` single-token
    steps over ``rows`` live rows) needs."""
    span = config["deployment"]["span"]
    return {k: span * v for k, v in _state(config, rows).items()}


def decode_step(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    """One single-token step over ``rows`` rows: every weight once and
    every row's state read and written; nothing is kept by position, so
    ``live_positions`` is not read."""
    s = sizes(config)
    state = _state(config, rows)
    return {"bytes": 2.0 * s["matmul_params"] + state["bytes"],
            "flops": 2.0 * rows * s["matmul_params"] + state["flops"]}


def _chunks(config: dict, tokens: float, chunks: float) -> dict:
    """The chunk form over ``tokens`` tokens in ``chunks`` (row, chunk)
    pairs: every token's update and read-out against the carried state,
    the attention form inside a chunk (4 * H * d a position seen, at most
    (C + 1) / 2 of them a token), and a read and a write of the state a
    pair."""
    s = sizes(config)
    C = config["deployment"]["prefill_chunk"]
    inside = 4 * s["H"] * s["hd"] * (C + 1) / 2
    return {"bytes": 2.0 * chunks * s["state_bytes_per_row"],
            "flops": tokens * s["L"] * (
                s["update_flops_per_token_layer"]
                + s["readout_flops_per_token_layer"] + inside)}


def retention_prefill(config: dict, rows: float, counters: dict) -> dict:
    """What the ``retention`` scope of ONE MEAN prefill call of the window
    needs.  ``rows`` is the DECODE rounds' mean and says nothing of a
    prefill call: the call is reckoned from the window's own counters
    (``served_prefill``: ``rows`` -- a real row of a call is one (row,
    chunk) pair through every layer -- and ``tokens`` over ``calls``)."""
    served = (counters or {}).get("served_prefill", {})
    calls = served.get("calls") or 0
    if not calls or not served.get("rows"):
        return {"bytes": 0.0, "flops": 0.0}
    return _chunks(config, served["tokens"] / calls,
                   served["rows"] / calls)


def prefill(config: dict, calls: float, tokens: float,
            attended_positions: float, counters: dict) -> dict:
    """``calls`` prefill programs over ``tokens`` real prompt tokens: every
    weight once a call (the head on a row's last position only: its FLOPs
    are left out), and the chunk form -- its (row, chunk) pairs at least
    ``tokens / prefill_chunk``, a part-filled chunk counted by its share.
    ``attended_positions`` counts what a causal attention would see and is
    not read: a token sees its own chunk and the state."""
    s = sizes(config)
    C = config["deployment"]["prefill_chunk"]
    chunks = _chunks(config, tokens, tokens / C)
    return {"bytes": 2.0 * calls * s["matmul_params"] + chunks["bytes"],
            "flops": 2.0 * tokens * (s["matmul_params"] - s["head_params"])
            + chunks["flops"]}
