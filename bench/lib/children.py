"""The child that owns the chip once the engine has gone.  It prints ONE
JSON object as its last stdout line.

  numerics    a batch the deployment really runs (lib/sample.py: as many
              rows as it has slots, at the lengths of the window's own
              schedule) through the program's ``paged_forward``, chunk by
              chunk as the scheduler prefills, then one round over all
              rows AS THE ARCHITECTURE DRIVES IT (archs/<arch>/drive.py;
              where it brings none, ``one_token_a_step``) — against the
              plain reference the configuration names
              (archs/<arch>/reference.py), asked for the logits of every
              judged EVENT of that round, judged row by row
              (lib/verdict.py).
  limits      the readings a limit is set from, in one process: over the
              spec's seeds the program's per-row numbers, and the
              control's — the reference on weights rounded to fp8 e4m3,
              one scale a matrix, in the program's place.

    python bench/lib/children.py numerics|limits <spec.json>

An event is one pass of the program over one row, as the round's driver
reports it: ``{"row", "ids", "at", "chose"[, "prefill"]}`` — ``ids`` the
context as that pass saw it (a mask id where one stood; its true length is
``len(ids)``), ``at`` the positions whose logits the reference is asked
for, ``chose`` the id the program put where each of them decides, or
``NOT_JUDGED``.  One event a row carries ``"prefill": j``: ``at[j]`` is
where the logits the prefill itself returned for the row are held against
the reference's.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(spec: dict):
    sys.path.insert(0, spec["repo"])
    sys.path.insert(0, BENCH)
    import jax

    from seldon_core_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    # keep the child's one-off programs too (a gather, a cast, the head at
    # each group's shape): each compiles in under the second below which
    # JAX keeps none, so every run compiled them anew — the reference's
    # first pass over the judged batch 7.14 s, 2.27 s once they are loaded,
    # 1.44 s of it arithmetic (my chip run, PR 31, call P31x)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] not in spec["platforms"]:
        raise SystemExit(
            f"device is {device}, the run needs one of {spec['platforms']}")
    return device


def build_unit(unit: dict):
    """The program's own generator unit, built the way the engine builds
    it from a deployment file (``graph/units.py``): the class the
    ``class_path`` resolves to, with the typed parameter list as its
    keywords — so ``cfg`` is the object the engine jits with, and nothing
    the configuration passes can be dropped on the way."""
    from seldon_core_tpu.graph.spec import Parameter, params_to_kwargs
    from seldon_core_tpu.graph.units import resolve_unit_class

    cls = resolve_unit_class(unit["class_path"])
    return cls(**params_to_kwargs(
        [Parameter.from_json_dict(d) for d in unit["parameters"]]))


NOT_JUDGED = -1


def sample_tokens(lens: list, vocab: int, seed: int, reserved=()) -> list:
    """The judged rows' ids: uniform over the ids the configuration does
    not reserve (lib/traffic.py ``skip_reserved``)."""
    import numpy as np

    from lib.traffic import skip_reserved

    rng = np.random.default_rng(seed)
    return [skip_reserved(rng.integers(0, vocab - len(reserved), n),
                          reserved).astype(np.int32) for n in lens]


def run_program(unit, params, dep: dict, prompts: list, drive=None) -> dict:
    """The timed programs over the judged rows, as the scheduler drives
    them (runtime/genserver.py ``_prefill_tick``, ``_decode_round``): one
    ``paged_forward`` a chunk over the rows still prefilling, ``start``
    advancing per row, rows and tables padded to powers of two — the
    shapes the cell's ladder loads; then one round of ``span`` positions
    over all rows, driven by ``drive`` (the architecture's
    archs/<arch>/drive.py; ``one_token_a_step`` where it brings none).
    Returns each row's logits from the call that consumed its last prompt
    token, and what the driver returns: the tokens the round emitted for
    each row and its judged events."""
    import jax.numpy as jnp
    import numpy as np

    from lib.buckets import blocks, pow2
    from seldon_core_tpu.models.generate import (
        init_block_pool,
        paged_forward_jit,
    )

    cfg = unit.cfg
    span, C, bs = dep["span"], dep["prefill_chunk"], dep["block_size"]
    lens = [len(p) for p in prompts]
    R = len(lens)
    own, nxt = [], 1                     # disjoint blocks; 0 is scratch
    for n in lens:
        k = blocks(n + span, bs)
        own.append(np.arange(nxt, nxt + k, dtype=np.int32))
        nxt += k
    if nxt > dep["pool_blocks"]:
        raise SystemExit(f"the judged rows need {nxt} blocks, the pool "
                         f"has {dep['pool_blocks']}")

    def tables(rows: list, upto: list, B: int):
        nblk = pow2(max(blocks(u, bs) for u in upto))
        t = np.zeros((B, nblk), np.int32)
        for i, r in enumerate(rows):
            t[i, :min(len(own[r]), nblk)] = own[r][:nblk]
        return jnp.asarray(t)

    pool = init_block_pool(cfg, dep["pool_blocks"], bs)
    sys_logits = [None] * R
    pos = [0] * R
    while True:
        batch = [r for r in range(R) if pos[r] < lens[r]]
        if not batch:
            break
        B = pow2(len(batch))
        toks = np.zeros((B, C), np.int32)
        start = np.zeros((B,), np.int32)
        width = np.zeros((B,), np.int32)
        for i, r in enumerate(batch):
            w = min(C, lens[r] - pos[r])
            toks[i, :w] = prompts[r][pos[r]:pos[r] + w]
            start[i], width[i] = pos[r], w
        logits, pool = paged_forward_jit(
            params, jnp.asarray(toks), pool,
            tables(batch, [int(s + w) for s, w in zip(start, width)], B),
            jnp.asarray(start), jnp.asarray(width), cfg=cfg, last_only=True)
        host = None
        for i, r in enumerate(batch):
            pos[r] += int(width[i])
            if pos[r] == lens[r]:
                host = np.asarray(logits) if host is None else host
                sys_logits[r] = host[i]
    sys_logits = np.stack(sys_logits)
    table = tables(list(range(R)), [n + span for n in lens], pow2(R))
    return {"logits": sys_logits,
            **(drive or one_token_a_step)(unit, params, pool, table, prompts,
                                          sys_logits, dep)}


def teacher_forced(prompts: list, first, tokens) -> list:
    """One event a row for a round of single-token steps: the prompt, the
    first token and the round's in ONE context — position n-1 is where the
    prefill's logits stood, n .. n+span-1 where each step chose."""
    import numpy as np

    span = tokens.shape[1]
    return [{"row": r, "ids": np.concatenate([p, first[r:r + 1],
                                              tokens[r, :-1]]),
             "at": len(p) - 1 + np.arange(1 + span),
             "chose": np.concatenate([[NOT_JUDGED], tokens[r]]),
             "prefill": 0} for r, p in enumerate(prompts)]


def one_token_a_step(unit, params, pool, tables, prompts: list, logits,
                     dep: dict) -> dict:
    """The round of an architecture that brings no ``drive.py``: the first
    token is the argmax of the prefill's logits, one
    ``paged_decode_round`` of ``span`` single-token steps follows over all
    rows (tables of the rows' prompt + span positions, padded as the
    scheduler pads), and a row is one event, ``teacher_forced``."""
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models.generate import paged_decode_round_jit

    R, B = len(prompts), tables.shape[0]
    first = logits.argmax(-1).astype(np.int32)

    def padded(values, dtype):
        out = np.zeros((B,), dtype)
        out[:R] = values
        return jnp.asarray(out)

    out, pool, *_ = paged_decode_round_jit(
        params, pool, tables, padded(first, np.int32),
        padded([len(p) for p in prompts], np.int32),
        padded(True, bool), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.uint32), unit.cfg, span=dep["span"],
        temperature=unit.temperature, top_k=unit.top_k, top_p=unit.top_p,
        eos_token=unit.eos_token)
    toks = np.asarray(out)[:R]            # [R, span]
    return {"first": first, "tokens": toks,
            "events": teacher_forced(prompts, first, toks)}


def run_reference(reference, params, config: dict, events: list,
                  quantum: int):
    """The reference's logits at every event's positions, [E, A, V] (A the
    most positions an event asks for; an event's own come first).  Each
    event's ``ids`` go through the reference as one row of its own, told
    its true length: ``reference`` is the architecture's module, its
    ``forward`` is handed the positions (``at``) and the lengths
    (``lengths``: a mask that is not causal keeps the pad out by them) and
    unembeds those positions alone, its ``row_bytes`` says what a row
    holds, by which lib/sample.py groups the events; a group is
    right-padded to a multiple of ``quantum``."""
    import jax.numpy as jnp
    import numpy as np

    from lib.sample import reference_groups

    judged = max(len(e["at"]) for e in events)
    out = np.zeros((len(events), judged, config["vocab_size"]), np.float32)
    groups = reference_groups(
        [len(e["ids"]) for e in events],
        lambda length: reference.row_bytes(config, length, judged),
        quantum)
    for length, rows in groups:
        toks = np.zeros((len(rows), length), np.int32)
        at = np.zeros((len(rows), judged), np.int32)
        lengths = np.zeros((len(rows),), np.int32)
        for i, k in enumerate(rows):
            e = events[k]
            lengths[i] = len(e["ids"])
            toks[i, :lengths[i]] = e["ids"]
            at[i, :len(e["at"])] = e["at"]
        out[rows] = np.asarray(reference.forward(
            params, jnp.asarray(toks), config, jnp.asarray(at),
            jnp.asarray(lengths)))
    return out


def prefill_rows(events: list, ref):
    """[R, V]: of ``ref`` [E, A, V], each row's logits where its prefill's
    own are held against the reference (the event that says ``prefill``)."""
    import numpy as np

    found = {e["row"]: ref[k, e["prefill"]]
             for k, e in enumerate(events) if "prefill" in e}
    return np.stack([found[r] for r in range(len(found))])


def without(logits, reserved):
    """``logits`` with the ids no answer may hold out of the running."""
    if not len(reserved):
        return logits
    logits = logits.copy()
    logits[..., list(reserved)] = -float("inf")
    return logits


def by_row(events: list, ref, logits, chose=None, reserved=()) -> dict:
    """Per row: ``prefill_err`` = max |Δ| of ``logits`` (what the prefill
    returned for the row) against the reference where the row's event says
    ``prefill``; ``decode_margin`` = the worst, over the row's judged
    (event, position) pairs, of the reference's best logit among the ids an
    answer may hold minus its logit of the id chosen; ``rms`` of the
    reference's prefill logits.  ``chose`` [E, A] stands in for the events'
    own ids where another's choices are judged at the same positions (the
    control, lib ``limits``)."""
    import numpy as np

    want = prefill_rows(events, ref)
    margin = [[] for _ in want]
    for k, e in enumerate(events):
        n = len(e["at"])
        ids = np.asarray(e["chose"] if chose is None else chose[k][:n])
        at = np.flatnonzero(np.asarray(e["chose"]) != NOT_JUDGED)
        step = ref[k, at]                                    # [judged, V]
        took = np.take_along_axis(step, ids[at][:, None], -1)[:, 0]
        margin[e["row"]].extend(without(step, reserved).max(-1) - took)
    return {
        "prefill_err": np.abs(want - logits).max(-1).tolist(),
        "decode_margin": [float(np.max(m)) for m in margin],
        "rms": np.sqrt((want ** 2).mean(-1)).tolist()}


def reserved_emitted(tokens, reserved) -> int:
    """How many of the ids the round emitted no answer may hold: an exact
    check, limit 0."""
    return sum(int(t) in reserved for row in tokens for t in row)


def fp8_rounded(params: dict) -> dict:
    """The control's weights: every matrix rounded to fp8 e4m3 (the
    precision below bf16) with ONE scale a matrix, a power of two so that
    the rounded values are exact in the weights' own dtype.  ``params``
    is emptied as it goes, so both trees never lie on the chip whole."""
    import jax
    import jax.numpy as jnp

    # two programs: inside one, XLA's TPU compiler may drop a narrowing
    # conversion that is widened again at once (excess precision allowed),
    # and the control would be the program's own weights (my chip run,
    # PR 27: every row read 0.0)
    @jax.jit
    def narrow(a):
        w = a.astype(jnp.float32)
        top = jnp.maximum(jnp.abs(w).max(), jnp.finfo(jnp.float32).tiny)
        scale = jnp.exp2(jnp.ceil(jnp.log2(top / 448.0)))
        return (w / scale).astype(jnp.float8_e4m3fn), scale

    @functools.partial(jax.jit, static_argnames=("dtype",))
    def widen(q, scale, dtype):
        return (q.astype(jnp.float32) * scale).astype(dtype)

    def rounded(a):
        return widen(*narrow(a), dtype=a.dtype)

    out = {}
    for key in list(params):
        out[key] = jax.tree.map(
            lambda a: rounded(a) if a.ndim >= 2 else a, params.pop(key))
    return out


def _peak() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def compare(spec: dict, unit, params, token_seed: int) -> dict:
    """The judged batch through the program, then through the reference:
    every row's numbers and the verdict over them."""
    from lib.manifest import arch_module, reserved_ids
    from lib.verdict import judge

    config = spec["config"]
    reference = arch_module(spec["bench_dir"], config, "reference")
    driver = arch_module(spec["bench_dir"], config, "drive", optional=True)
    reserved = reserved_ids(config)
    prompts = sample_tokens(spec["sample"]["lens"], config["vocab_size"],
                            token_seed, reserved)
    t0 = time.monotonic()
    prog = run_program(unit, params, spec["deployment"], prompts,
                       driver and driver.drive)
    # the program's peak: the reference comes after it and is not counted
    peak = _peak()
    t1 = time.monotonic()
    ref = run_reference(reference, params, config, prog["events"],
                        spec["deployment"]["block_size"])
    rows = by_row(prog["events"], ref, prog["logits"], reserved=reserved)
    rms = sum(rows["rms"]) / len(rows["rms"])
    return {"reference": reference, "reserved": reserved, "prog": prog,
            "ref": ref, "rows": rows, "rms": rms, "memory_peak_bytes": peak,
            "reserved_emitted": reserved_emitted(prog["tokens"], reserved),
            "verdict": judge(rows["prefill_err"], rows["decode_margin"],
                             config["numerics"], rms),
            "seconds": {"program": t1 - t0,
                        "reference": time.monotonic() - t1}}


def numerics(spec: dict, device: dict) -> dict:
    unit = build_unit(spec["unit"])
    c = compare(spec, unit, unit.init_state(None)["params"],
                spec["sample_seed"])
    v, sample = c["verdict"], spec["sample"]
    return {
        "device": device, "ref_logit_rms": c["rms"],
        "ok": v["ok"] and not c["reserved_emitted"],
        "reserved_emitted": c["reserved_emitted"],
        "prefill_max_abs_err": v["prefill"]["max"],
        "decode_max_margin": v["decode"]["max"],
        "tolerance": v["tolerance"], "verdict": v,
        "rows_offered": sample["offered"], "chunks": sample["chunks"],
        "lens": sample["lens"], "by_row": c["rows"],
        "memory_peak_bytes": c["memory_peak_bytes"], "seconds": c["seconds"],
    }


def limits(spec: dict, device: dict) -> dict:
    """Program and control over ``spec["seeds"]``, each seed its own
    weights and token ids, every judged row's numbers."""
    from lib.verdict import judge

    config = spec["config"]
    seeds = []
    for seed, unit_doc in zip(spec["seeds"], spec["units"]):
        unit = build_unit(unit_doc)
        params = unit.init_state(None)["params"]
        c = compare(spec, unit, params, seed % 9973)
        entry = {"seed": seed, "ref_logit_rms": c["rms"],
                 "program": c["rows"], "program_verdict": c["verdict"]}
        if seed in spec["control_seeds"]:
            events = c["prog"]["events"]
            ctl = run_reference(c["reference"], fp8_rounded(params), config,
                                events, spec["deployment"]["block_size"])
            rows = by_row(events, c["ref"], prefill_rows(events, ctl),
                          without(ctl, c["reserved"]).argmax(-1))
            if not max(rows["prefill_err"]) > 0.0:
                raise SystemExit("the control reads what the reference "
                                 "reads: its weights were not rounded")
            entry.update(control=rows, control_verdict=judge(
                rows["prefill_err"], rows["decode_margin"],
                config["numerics"], c["rms"]))
        del params, c
        seeds.append(entry)
        sys.stderr.write(f"seed {seed} done\n")
    return {"device": device, "lens": spec["sample"]["lens"],
            "chunks": spec["sample"]["chunks"], "seeds": seeds}


if __name__ == "__main__":
    with open(sys.argv[2]) as f:
        _spec = json.load(f)
    _result = {"numerics": numerics, "limits": limits}[sys.argv[1]](
        _spec, _setup(_spec))
    print(json.dumps(_result), flush=True)
