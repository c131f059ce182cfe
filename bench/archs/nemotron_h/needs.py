"""Bytes and FLOPs the two paged programs NEED for the ``nemotron_h`` block
(reference.py beside this file), from the configuration's sizes -- what the
algorithm requires, not what today's program moves.  Plain arithmetic: the
benchmark's parent imports this file, so it may not import JAX.

Per configuration (bf16 = 2 bytes), by the letter of a block
(``hybrid_override_pattern``, the first ``num_hidden_layers`` of it; ``H`` /
``P`` = ``mamba_num_heads`` / ``mamba_head_dim``, ``G`` = ``n_groups``, ``N``
= ``ssm_state_size``, ``K`` = ``conv_kernel``, ``inner`` = H P, ``C`` = inner
+ 2 G N):
  M  mixer             D*(inner + C + H) + inner*D + K*C + C  (in_proj,
                       out_proj, the taps and their bias; the per-head A, dt
                       bias and D and the gated norm's weight are vectors)
  *  mixer             D*hd*(Hq + 2*KV) + Hq*hd*D
  E  router            D * E_router        (all the router's outputs)
     one expert        2 * D * F           (up, down: relu^2 has no gate)
     shared expert     2 * D * Fs          (every token, every chip)
  KV per position      2 * KV * hd * 2 bytes, over the * blocks only
  state per row        H*P*N * 4 bytes (float32) + (K-1)*C * 2 bytes an M
                       block: read and written once a step (a prefill call:
                       once a row), whatever the row's length
  a token's recurrence 5 * H*P*N FLOPs an M block (the decay's product, the
                       update's two and the read-out's two an element)
and once: the untied head V*D (the embedding is a gather of a few rows).

THE CHIP'S SHARE.  The file's ``n_routed_experts`` is the experts HELD here
(``published.n_routed_experts`` the router's width): a step reads the held
experts its rows CHOSE -- the program's own count where the call's (or the
window's) counters carry it (``served_decode.experts_read`` over
``.expert_slots``, the experts held x E blocks x steps), else what that many
rows' uniform picks are expected to hit among the held -- and computes the
picks that fell on them: the program's own count too
(``.expert_slots_held`` over ``.row_passes``), else ``num_experts_per_tok``
x held / width a row and a block.  A prefill that chooses a token returns no
count, and under the benchmark's seeded weights a call's routing is far
from uniform (PERF.md section 6, PR 46: 12-42 of the 64 held experts of a
layer went unread by a call of 2,048 tokens), so the uniform expectation
would credit a call with bytes it never moves: a prefill call is credited
with the experts ONE token's picks reach here, which every call reads, and
with no more.  Its share of its roofline is then a floor."""

from __future__ import annotations


def sizes(config: dict) -> dict:
    D, Hq, KV, hd = (config["hidden_size"], config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, K = (config["n_groups"], config["ssm_state_size"],
               config["conv_kernel"])
    held, k = config["n_routed_experts"], config["num_experts_per_tok"]
    width = config.get("published", {}).get("n_routed_experts", held)
    F, Fs = (config["moe_intermediate_size"],
             config["moe_shared_expert_intermediate_size"])
    L, V = config["num_hidden_layers"], config["vocab_size"]
    letters = config["hybrid_override_pattern"][:L]
    ssm, attn, routed = (letters.count(c) for c in "M*E")
    inner = H * P
    C = inner + 2 * G * N
    ssm_params = D * (inner + C + H) + inner * D + K * C + C
    state_bytes = H * P * N * 4 + (K - 1) * C * 2    # a row, an M block
    recurrence_flops = 5 * H * P * N                 # a token, an M block
    attn_params = D * hd * (Hq + 2 * KV) + Hq * hd * D
    return {
        "D": D, "hd": hd, "H": Hq, "KV": KV, "E": held, "E_router": width,
        "k": k, "F": F, "Fs": Fs, "L": L, "V": V, "K": K,
        "ssm_H": H, "ssm_P": P, "ssm_G": G, "ssm_N": N, "inner": inner,
        "conv_dim": C,
        "ssm_layers": ssm, "attn_layers": attn, "routed_layers": routed,
        "ssm_params": ssm_params, "attn_params": attn_params,
        "router_params": D * width, "expert_params": 2 * D * F,
        "shared_params": 2 * D * Fs, "head_params": V * D,
        # every weight a token's step reads whatever it chooses
        "fixed_params": (ssm * ssm_params + attn * attn_params
                         + routed * (D * width + 2 * D * Fs)),
        "kv_bytes_per_position": attn * 2 * KV * hd * 2,
        "state_bytes_per_row_layer": state_bytes,
        "state_bytes_per_row": ssm * state_bytes,
        "attn_flops_per_position": 4 * attn * Hq * hd,
        "ssm_flops_per_token_layer": recurrence_flops,
        "ssm_flops_per_token": ssm * recurrence_flops,
    }


def expected_read(config: dict, tokens: float) -> float:
    """Distinct HELD experts ``tokens`` tokens are expected to choose in
    one block under uniform routing over the router's whole width."""
    s = sizes(config)
    return s["E"] * (1.0 - (1.0 - 1.0 / s["E_router"]) ** (s["k"] * tokens))


def experts_read(config: dict, tokens: float, counters: dict,
                 program: str = "served_decode") -> float:
    """Held experts one E block reads in one step (``served_prefill``: in
    one call): the program's own mean where it counts, else the
    expectation for ``tokens`` tokens."""
    served = (counters or {}).get(program, {})
    slots, read = served.get("expert_slots"), served.get("experts_read")
    if slots and read is not None:
        return sizes(config)["E"] * read / slots
    return expected_read(config, tokens)


def picks_here(config: dict, counters: dict) -> float:
    """Of a row's picks in one E block, those that fall on a held expert:
    the program's own mean where it counts, else the held share of
    ``num_experts_per_tok``."""
    s = sizes(config)
    served = (counters or {}).get("served_decode", {})
    held, passes = served.get("expert_slots_held"), served.get("row_passes")
    if passes and held is not None and s["routed_layers"]:
        return held / (passes * s["routed_layers"])
    return s["k"] * s["E"] / s["E_router"]


def experts(config: dict, rows: float, counters: dict) -> dict:
    """What the ``experts`` scope of ONE ROUND (``span`` steps) needs: the
    chosen held experts' weights once an E block a step, and the FLOPs of
    the rows' picks that fell on them (the shared expert has a scope of its
    own)."""
    s = sizes(config)
    steps = config["deployment"]["span"] * s["routed_layers"]
    read = experts_read(config, rows, counters)
    return {"bytes": 2.0 * steps * read * s["expert_params"],
            "flops": 2.0 * steps * rows * picks_here(config, counters)
            * s["expert_params"]}


def _state(config: dict, rows: float, tokens: float) -> dict:
    """One read and one write of ``rows`` rows' state in every M block,
    with ``tokens`` tokens through the recurrence as written."""
    s = sizes(config)
    return {"bytes": 2.0 * rows * s["state_bytes_per_row"],
            "flops": float(tokens * s["ssm_flops_per_token"])}


def ssm(config: dict, rows: float, counters: dict) -> dict:
    """What the ``ssm`` scope of ONE ROUND (``span`` single-token steps over
    ``rows`` live rows) needs, whatever implements it."""
    span = config["deployment"]["span"]
    return {k: span * v for k, v in _state(config, rows, rows).items()}


def ssm_prefill(config: dict, rows: float, counters: dict) -> dict:
    """What the ``ssm`` scope of ONE MEAN prefill call of the window needs.
    ``rows`` is the DECODE rounds' mean and says nothing of a prefill call:
    the call is reckoned from the window's own counters (``served_prefill``:
    ``rows`` -- a real row of a call is one (row, chunk) pair through every
    block -- and ``tokens`` over ``calls``)."""
    served = (counters or {}).get("served_prefill", {})
    calls = served.get("calls") or 0
    if not calls or not served.get("rows"):
        return {"bytes": 0.0, "flops": 0.0}
    return _state(config, served["rows"] / calls, served["tokens"] / calls)


def decode_step(config: dict, rows: float, live_positions: float,
                counters: dict) -> dict:
    """One single-token step over ``rows`` rows that hold
    ``live_positions`` cache positions between them."""
    s = sizes(config)
    read = experts_read(config, rows, counters)
    routed = s["routed_layers"]
    state = _state(config, rows, rows)
    return {
        "bytes": 2.0 * (s["fixed_params"] + s["head_params"]
                        + routed * read * s["expert_params"])
        # the * blocks read the rows' live K/V and write one position a
        # row; the M blocks read and write a row's state
        + s["kv_bytes_per_position"] * (live_positions + rows)
        + state["bytes"],
        "flops": 2.0 * rows * (
            s["fixed_params"] + s["head_params"]
            + routed * picks_here(config, counters) * s["expert_params"])
        + s["attn_flops_per_position"] * live_positions + state["flops"]}


def prefill(config: dict, calls: float, tokens: float,
            attended_positions: float, counters: dict) -> dict:
    """``calls`` prefill programs over ``tokens`` real prompt tokens: every
    fixed weight and the head once a call (the head on a row's last
    position only: its FLOPs are left out), the held experts a call is
    known to read (module docstring), each token's K/V written once and
    read once, and a read and a write of the state a (row, chunk) pair --
    at least ``tokens / prefill_chunk`` of them, a part-filled chunk counted
    by its share."""
    s = sizes(config)
    served = (counters or {}).get("served_prefill", {})
    if served.get("expert_slots") and served.get("experts_read") is not None:
        read = experts_read(config, 0.0, counters, "served_prefill")
    else:
        read = min(float(s["E"]), s["k"] * s["E"] / s["E_router"])
    weights = (s["fixed_params"] + s["head_params"]
               + s["routed_layers"] * read * s["expert_params"])
    state = _state(config, tokens / config["deployment"]["prefill_chunk"],
                   tokens)
    return {
        "bytes": 2.0 * calls * weights
        + 2 * s["kv_bytes_per_position"] * tokens + state["bytes"],
        "flops": 2.0 * tokens * (
            s["fixed_params"] + s["routed_layers"] * s["k"] * s["E"]
            / s["E_router"] * s["expert_params"])
        + s["attn_flops_per_position"] * attended_positions
        + state["flops"]}
