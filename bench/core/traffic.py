"""The one traffic generator: a mix's parameters in, a request list out.

A mix is a JSON file under bench/traffic/.  Every seed gets the same
multiset of sizes, in another order: lengths are quantiles of the mix's
distributions, laid out in blocks of `block` requests, and the seed only
orders each block and draws the token ids.  So any whole number of blocks
holds exactly the same work whatever the seed.

The order is stratified at every scale: each aligned run of 2^k requests
holds one length from each of 2^k bands of equal probability, for every
k, as a base-2 van der Corput sequence does.  The seed scrambles it
(Owen's nested scrambling), independently for prompts and outputs.  So a
window that sees only part of a block still sees nearly the same work on
every seed: over 60 consecutive requests of the offline mix the output
tokens vary by 1.3% from seed to seed, against 5% for a block in plain
random order (bench/tests/test_bench_traffic.py).

Keys of a mix (all lengths in tokens):
    loop            "closed": `clients` callers, each sends its next request
                    when its last one completes
    requests        how many requests the list holds
    block           requests per block, a power of two
    prompt, output  {"dist": "lognormal", "median", "sigma", "min", "max"}
                    or {"dist": "uniform", "min", "max"}
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    prompt: np.ndarray          # int32 token ids
    max_new: int


def _quantile(dist: dict, u: float) -> float:
    if dist["dist"] == "lognormal":
        return dist["median"] * math.exp(dist["sigma"]
                                         * NormalDist().inv_cdf(u))
    if dist["dist"] == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def block_lengths(dist: dict, block: int) -> np.ndarray:
    """The `block` lengths every block holds, ascending: quantiles at
    (i + 1/2)/block, rounded and clipped to [min, max]."""
    u = (np.arange(block) + 0.5) / block
    raw = np.array([_quantile(dist, x) for x in u])
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def scrambled_order(rng: np.random.Generator, block: int) -> np.ndarray:
    """A permutation of range(block), block = 2^n, in which each aligned
    run of 2^k entries holds one index from each band of block / 2^k:
    bit-reversed positions, each bit flipped by a random bit drawn for
    the higher bits above it."""
    n = block.bit_length() - 1
    if block != 1 << n:
        raise ValueError(f"block {block} is not a power of two")
    i = np.arange(block)
    rev = np.zeros(block, np.int64)
    for b in range(n):
        rev |= ((i >> b) & 1) << (n - 1 - b)
    out = np.zeros(block, np.int64)
    for level in range(n):
        bit = n - 1 - level
        flips = rng.integers(0, 2, 1 << level)
        out |= (((rev >> bit) & 1) ^ flips[rev >> (bit + 1)]) << bit
    return out


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for (seed, stream...); any seed up to 64 bits."""
    return np.random.default_rng([seed & 0xFFFFFFFF, (seed >> 32)
                                  & 0xFFFFFFFF, *stream])


def make_requests(mix: dict, seed: int, vocab: int) -> List[Request]:
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n, block = mix["requests"], mix["block"]
    p_len = block_lengths(mix["prompt"], block)
    o_len = block_lengths(mix["output"], block)
    tok_rng = rng_for(seed, 2)
    reqs: List[Request] = []
    for b in range(-(-n // block)):
        rng = rng_for(seed, 3, b)
        pl = p_len[scrambled_order(rng, block)]
        ol = o_len[scrambled_order(rng, block)]
        for i in range(min(block, n - b * block)):
            prompt = tok_rng.integers(0, vocab, int(pl[i])).astype(np.int32)
            reqs.append(Request(idx=len(reqs), prompt=prompt,
                                max_new=int(ol[i])))
    return reqs
