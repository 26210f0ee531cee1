"""The one traffic generator.  A traffic mix is a data file under
``hvdbench/traffic/``; this module turns it and a seed into work.

Stratified draws: a length distribution is not sampled.  A block of
``n`` requests takes its lengths at the ``n`` evenly spaced quantiles
``(i + 0.5) / n`` of the declared distribution, so every block holds the
same multiset of (prompt length, output length) pairs, for every seed.
The seed decides the order inside each block, the token ids and, for an
open loop, where inside its own ``1 / rate`` slot each arrival falls.
Every seed therefore offers the same tokens, the same prefill buckets
and the same number of arrivals per second; it changes which tokens, and
in what order.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(dist: dict, u: float) -> int:
    """The length at quantile ``u`` (0 < u < 1) of a declared
    distribution, rounded and clipped to ``[min, max]``."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(dist["max"], max(dist["min"], round(x))))


def block_multiset(traffic: dict) -> List[Tuple[int, int]]:
    """The (prompt length, output length) pairs every block holds.
    Prompt quantile ``i`` is paired with output quantile
    ``(i * pair_stride) mod n``: a fixed rule, with a stride coprime to
    ``n`` so that long prompts do not all get long outputs."""
    n = int(traffic["block"])
    stride = int(traffic.get("pair_stride", 1))
    if math.gcd(stride, n) != 1:
        raise ValueError(f"pair_stride {stride} shares a factor with the "
                         f"block size {n}")
    prompts = [quantile(traffic["prompt_len"], (i + 0.5) / n)
               for i in range(n)]
    outputs = [quantile(traffic["output_len"], (i + 0.5) / n)
               for i in range(n)]
    return [(prompts[i], outputs[(i * stride) % n]) for i in range(n)]


@dataclasses.dataclass(frozen=True)
class Request:
    index: int            # position in the stream; index // block = block
    prompt: Tuple[int, ...]
    max_new_tokens: int
    due_s: float          # open loop: offset from the stream's start


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def request_block(traffic: dict, seed: int, block: int,
                  vocab_size: int) -> List[Request]:
    """Block number ``block`` of the stream for ``seed``: the block's
    multiset in a seeded order, seeded token ids and, where the traffic
    declares a ``rate``, one arrival inside each ``1 / rate`` slot."""
    pairs = block_multiset(traffic)
    n = len(pairs)
    rng = _rng(seed, 1, block)
    order = rng.permutation(n)
    rate = traffic.get("rate_per_s")
    jitter = rng.random(n)
    out = []
    for j, i in enumerate(order):
        p_len, o_len = pairs[int(i)]
        index = block * n + j
        due = (index + float(jitter[j])) / rate if rate else 0.0
        prompt = tuple(int(t) for t in rng.integers(0, vocab_size, p_len))
        out.append(Request(index, prompt, o_len, due))
    return out


def warmup_prompts(lengths, seed: int, vocab_size: int) -> List[List[int]]:
    """One seeded prompt per given length, sharing no prefix with each
    other or (but by chance) with the stream."""
    rng = _rng(seed, 2)
    return [rng.integers(0, vocab_size, int(n)).tolist() for n in lengths]


def train_batch(traffic: dict, seed: int, index: int, rows: int,
                vocab_size: int):
    """Batch ``index`` of the training ring: ``rows`` rows of
    ``seq_len + 1`` seeded token ids, as (inputs, targets) shifted by
    one.  All rows differ."""
    tokens = _rng(seed, 3, index).integers(
        0, vocab_size, (rows, int(traffic["seq_len"]) + 1), dtype=np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def sample_indices(seed: int, candidates: List[int], k: int,
                   must_include: int) -> List[int]:
    """``k`` of ``candidates`` drawn from the seed, ``must_include``
    among them (the longest finished request)."""
    rest = [c for c in candidates if c != must_include]
    rng = _rng(seed, 4)
    picked = rng.permutation(len(rest))[:max(0, k - 1)]
    return [must_include] + [rest[int(i)] for i in picked]
