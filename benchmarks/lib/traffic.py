"""The one traffic generator: every mix is a data file of parameters
(``traffic/<mix>.json``), read here. Two kinds.

``packed_tokens`` (training): seeded token ids packed to ``seq_len``, one
fresh batch a step, every row different.

``closed_loop`` (serving): ``clients`` callers that each wait for a reply
before sending the next item. The lengths are a FIXED stratified schedule
(evenly spaced quantiles of the stated ranges), dealt in blocks of
``clients`` entries that each hold one entry of every stratum, so any run
of consecutive requests carries the same work whatever the seed; the seed
only permutes the order inside and of the blocks and draws the token ids.
"""

from __future__ import annotations

import math

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# -- training ----------------------------------------------------------------

def packed_batches(traffic: dict, seed: int, rows: int, vocab: int):
    """Endless ``[rows, seq_len]`` int32 batches; the first ones are the
    steps the reference follows."""
    r = rng(seed, 1)
    while True:
        yield r.integers(0, vocab, (rows, int(traffic["seq_len"])),
                         dtype=np.int32)


# -- serving -----------------------------------------------------------------

def quantiles(lo: int, hi: int, n: int) -> list[int]:
    """``n`` evenly spaced values over ``[lo, hi]``, both ends in."""
    return [int(round(lo + (hi - lo) * i / (n - 1))) for i in range(n)]


def schedule(traffic: dict, seed: int) -> list[tuple[int, int]]:
    """``(item tokens, output tokens)`` per request, ``blocks`` blocks of
    ``clients`` entries. Item and output strata are paired by a fixed
    rotation per block (so long items meet short and long outputs alike)
    — the multiset is the same for every seed; the seed permutes."""
    n, blocks = int(traffic["clients"]), int(traffic["blocks"])
    items = quantiles(*traffic["item_tokens"], n)
    outs = quantiles(*traffic["output_tokens"], n)
    r = rng(seed, 2)
    step = next(m for m in range(5, 6 + n) if math.gcd(m, n) == 1)
    out = []
    for b in r.permutation(blocks):
        block = [(items[i], outs[(i * step + 3 * int(b)) % n])
                 for i in range(n)]
        out.extend(block[i] for i in r.permutation(n))
    return out


def requests(traffic: dict, seed: int, vocab: int):
    """Endless ``(prompt ids, output tokens)``: the shared template
    followed by the item, cycling the schedule."""
    r = rng(seed, 3)
    template = r.integers(1, vocab, int(traffic["template_tokens"]),
                          dtype=np.int32)
    sched = schedule(traffic, seed)
    i = 0
    while True:
        item, out = sched[i % len(sched)]
        yield (np.concatenate([template,
                               r.integers(1, vocab, item, dtype=np.int32)]),
               out)
        i += 1
