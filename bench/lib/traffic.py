"""The one traffic generator.  A mix is a data file of parameters
(bench/traffic/<mix>.json); a schedule is a pure function of mix and
arrival parameters, and the token ids a pure function of the seed and
of the ids the configuration reserves.

The lengths and gaps are the stratified quantiles of the mix's
distributions, in ONE order, and the order is BALANCED: the arrivals are
dealt in runs of ``BLOCK`` consecutive requests, every run holds one value
from each ``BLOCK``-quantile stratum of each of the three series, and the
runs' sums are nearly equal (``balanced``).  So every stretch of
``BLOCK / rate`` seconds offers nearly the same work.

Every seed offers that same schedule; the seed draws the token ids (and the
weights).  Any re-ordering by the seed changed the work: a free shuffle of
the same multiset left tails 10-25% apart between seeds, runs re-shuffled
by the seed 8-9%, and even a rotation of one base order moved the 90th
percentile of time per output token by 4% where two runs of one seed
differed by under 1% (my chip runs, PR 23).  The measured part of a window
and its drain tail are dealt apart."""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List, NamedTuple

import numpy as np

_STD = NormalDist()
BLOCK = 8


class Request(NamedTuple):
    index: int
    due_s: float        # offset from the start of the window
    prompt_len: int
    out_len: int
    measured: bool      # due before seconds - drain_s


def _lognormal_quantiles(spec: dict, n: int) -> List[int]:
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = _STD.inv_cdf((i + 0.5) / n)
        v = math.exp(mu + spec["sigma"] * z)
        out.append(int(round(min(max(v, spec["min"]), spec["max"]))))
    return out


def balanced(values: list, rng: random.Random) -> list:
    """``values`` (sorted) in an order in which every run of BLOCK
    consecutive places holds one value of each of the BLOCK contiguous
    strata, and the runs' SUMS are nearly equal: within a stratum the
    values go to the runs in rising order for one stratum and falling
    order for the next, so a run that gets the top of one stratum gets
    the bottom of its neighbour.  ``rng`` decides which run comes when and
    the order inside a run.  (A last, shorter run takes the middle value
    of each stratum that has one to spare.)"""
    n = len(values)
    full, extra = divmod(n, BLOCK)
    runs: List[list] = [[] for _ in range(full)]
    short: list = []
    pos = 0
    for s in range(BLOCK):
        size = full + (1 if s < extra else 0)
        stratum = list(values[pos:pos + size])
        pos += size
        if size > full:
            short.append(stratum.pop(size // 2))
        if s % 2:
            stratum.reverse()
        for run, v in zip(runs, stratum):
            run.append(v)
    rng.shuffle(runs)
    out = []
    for run in runs + [short]:
        rng.shuffle(run)
        out.extend(run)
    return out


def lengths(mix: dict, n: int, rng: random.Random) -> List[tuple]:
    """n (prompt_len, out_len) pairs: the two marginals stratified and
    balanced independently; the prompt is cut so that prompt + output
    stays within ``max_positions`` where the mix states one."""
    prompts = balanced(_lognormal_quantiles(mix["prompt_tokens"], n), rng)
    outs = balanced(_lognormal_quantiles(mix["output_tokens"], n), rng)
    cap = mix.get("max_positions")
    pairs = []
    for p, o in zip(prompts, outs):
        if cap is not None:
            p = max(mix["prompt_tokens"]["min"], min(p, cap - o))
        pairs.append((p, o))
    return pairs


def _gaps(n: int, span_s: float, rng: random.Random) -> List[float]:
    """n exponential inter-arrival gaps (stratified), scaled to fill
    span_s exactly, balanced."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return balanced([g * scale for g in raw], rng)


def open_loop(mix: dict, rate: float, seconds: float, drain_s: float
              ) -> List[Request]:
    """Open-loop arrivals with exponential gaps over the whole of
    ``seconds``; the measured set is what is due before
    ``seconds - drain_s``."""
    rng = random.Random("open")      # one order, whatever the seed
    parts = []
    t0 = 0.0
    for span, measured in ((seconds - drain_s, True), (drain_s, False)):
        n = int(round(rate * span))
        if n <= 0:
            t0 += span
            continue
        pairs = lengths(mix, n, rng)
        t = t0
        for (p, o), gap in zip(pairs, _gaps(n, span, rng)):
            parts.append((t, p, o, measured))
            t += gap
        t0 += span
    return [Request(i, t, p, o, m) for i, (t, p, o, m) in enumerate(parts)]


def skip_reserved(drawn: np.ndarray, reserved) -> np.ndarray:
    """``drawn`` uniform in ``[0, vocab - len(reserved))`` moved onto the
    ids that are not reserved, in order: uniform over them, and with
    nothing reserved the draw as it stands."""
    for r in sorted(reserved):
        drawn = drawn + (drawn >= r)
    return drawn


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  reserved=()) -> List[int]:
    """Token ids uniform over the vocabulary's ids that the configuration
    does not reserve (``reserved_ids``: a mask id arriving in a prompt
    would be a hole to fill, not a token); no two requests share a prefix
    beyond chance."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(index)])
    return skip_reserved(
        rng.integers(0, vocab - len(reserved), size=length, dtype=np.int64),
        reserved).tolist()
