"""Which compiled programs a cell can reach, and the warm-up ladder that
takes each of them once.  Pure arithmetic on the scheduler's own rules
(runtime/genserver.py): rows and block tables pad to powers of two, a
prompt is consumed ``prefill_chunk`` tokens at a time, a decode round
reserves ``span`` more positions.  Two counts of a round are the
deployment's to state, with the program's where they are absent
(``after_prefill``): ``prefill_emits``, the tokens a row has emitted when
its prefill ends (1: the prefill's logits choose the first token; 0 where
a prefill chooses none), and ``round_quantum``, the multiple of positions
a row's first round starts on (1: right after the prompt; a generator
that decodes whole blocks takes the prompt's remainder into its first
block, and that round emits so many tokens fewer).  It re-states those
rules because the parent may not import the program (it must stay off
JAX); it is held to the program by tests/bench/test_bench_readers.py
(after this ladder a live engine compiles nothing under the mix's traffic)
and, in every run, by the compile counters standing still through the soak
and the window."""

from __future__ import annotations

from typing import Dict, List, Set, Tuple


def pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def blocks(positions: int, block_size: int) -> int:
    return -(-positions // block_size)


def caps(mix: dict) -> dict:
    """Length caps of a mix: shortest and longest prompt, longest output,
    and the most positions one row can hold."""
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    most = min(p["max"] + o["max"], mix.get("max_positions", 1 << 30))
    return {"min_prompt": p["min"], "max_prompt": p["max"],
            "max_out": o["max"], "max_positions": most}


def after_prefill(prompt_len: int, dep: dict) -> Tuple[int, int]:
    """(positions a row's first round starts after, tokens it has emitted
    by then): the prompt and one token, unless the deployment states
    ``round_quantum`` / ``prefill_emits``.  A round that starts before the
    prompt's end emits the prompt's remainder again: so many fewer new."""
    start = prompt_len - prompt_len % dep.get("round_quantum", 1)
    return start, dep.get("prefill_emits", 1) - (prompt_len - start)


def touched(prompt_len: int, max_new: int, dep: dict
            ) -> Tuple[Set[int], Set[int]]:
    """Block-table widths one row of ``prompt_len`` tokens generating
    ``max_new`` takes: (prefill widths, decode widths)."""
    C, bs, span = dep["prefill_chunk"], dep["block_size"], dep["span"]
    pre, dec = set(), set()
    pos = 0
    while pos < prompt_len:
        w = min(C, prompt_len - pos)
        pre.add(pow2(blocks(pos + w, bs)))
        pos += w
    n_valid, emitted = after_prefill(prompt_len, dep)
    while emitted < max_new:
        dec.add(pow2(blocks(n_valid + span, bs)))
        n_valid += span
        emitted += min(span, max_new - emitted)
    return pre, dec


def reachable(dep: dict, cp: dict) -> Tuple[Set[int], Set[int]]:
    """Every block-table width the cell's lengths can reach."""
    bs, span = dep["block_size"], dep["span"]
    pre = {pow2(blocks(x, bs))
           for x in range(cp["min_prompt"], cp["max_prompt"] + 1)}
    # a round starts on a multiple of the quantum and is whole quanta long;
    # the last one starts before the row's last token is out
    quantum = dep.get("round_quantum", 1)
    first = after_prefill(cp["min_prompt"], dep)[0] + span
    last = cp["max_positions"] - 1 - dep.get("prefill_emits", 1) + span
    dec = {pow2(blocks(x, bs)) for x in range(first, last + 1, quantum)}
    return pre, dec


def row_buckets(slots: int) -> List[int]:
    out, b = [], 1
    while b < pow2(slots):
        out.append(b)
        b *= 2
    return out + [pow2(slots)]


def ladder_rows(dep: dict, cp: dict) -> List[Tuple[int, int]]:
    """(prompt_len, max_new) pairs that between them touch every reachable
    width, for one row count; greedy cover over the lengths where a width
    first appears."""
    bs = dep["block_size"]
    want_pre, want_dec = reachable(dep, cp)
    cands = {cp["min_prompt"], cp["max_prompt"]}
    quantum = dep.get("round_quantum", 1)
    for u in sorted(want_pre | want_dec):
        for L in ((u // 2) * bs + 1, (u // 2) * bs + 1 - dep["span"]):
            # ... and the next length whose first round starts there
            for L in (L, -(-L // quantum) * quantum):
                if cp["min_prompt"] <= L <= cp["max_prompt"]:
                    cands.add(L)
    chosen: List[Tuple[int, int]] = []
    left_pre, left_dec = set(want_pre), set(want_dec)
    while left_pre or left_dec:
        best, gain = None, 0
        for L in sorted(cands):
            pre, dec = touched(L, 2, dep)
            g = len(pre & left_pre) + len(dec & left_dec)
            if g > gain:
                best, gain = (L, 2), g
        if best is None:
            # a decode width only a long generation grows into
            v = min(left_dec)
            L = cp["max_prompt"]
            need = (v // 2) * bs + 1 - dep["span"] - L
            best = (L, 2 + max(need, 0) + dep["span"])
            if not touched(*best, dep)[1] & left_dec:
                raise ValueError(f"no ladder row reaches decode width {v}")
        chosen.append(best)
        pre, dec = touched(*best, dep)
        left_pre -= pre
        left_dec -= dec
    return sorted(chosen)


def programs(dep: dict, cp: dict) -> Dict[str, list]:
    """The (rows, chunk, blocks) prefill programs and (rows, blocks) decode
    programs the cell can reach with ``slots`` rows."""
    pre, dec = reachable(dep, cp)
    rows = row_buckets(dep["slots"])
    return {
        "prefill": [(b, dep["prefill_chunk"], u)
                    for b in rows for u in sorted(pre)],
        "decode": [(b, v) for b in rows for v in sorted(dec)],
    }
