"""Seeded corpora with the paper's blocking skew (arXiv:1108.1631, Fig. 8).

A copy of the generator behind ``make_products`` / ``make_publications``
(``src/repro/er/datasets.py``), kept here so that the benchmark's inputs
do not move when the program changes. One general generator: the
configuration file gives its parameters (in
``bench/configs/<name>.json``):

    n_records   entities, including the injected duplicates
    head_frac   share of the entities in the largest block
    pair_share  share of all within-block pairs in the largest block
    dup_frac    duplicates injected per base entity

The base block sizes depend on the parameters only, never on the seed;
the seed draws the words, serials and record order, and which records
get a duplicate (a duplicate joins its source's block, so pair counts
move by a few tenths of a percent from seed to seed). Each block has its
own fixed-width prefix over [a-z0-9], so first-k-letters blocking
recovers the layout exactly.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

__all__ = ["Corpus", "build_corpus", "block_sizes", "perturb", "ALPHABET",
           "seed_rng"]

WORDS = [
    "laptop", "phone", "camera", "monitor", "keyboard", "mouse", "printer",
    "router", "speaker", "headset", "tablet", "charger", "adapter", "cable",
    "drive", "memory", "battery", "case", "stand", "dock", "hub", "lens",
    "pro", "max", "ultra", "mini", "air", "plus", "lite", "neo", "prime",
]
ALPHABET = "abcdefghijklmnopqrstuvwxyz0123456789"


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream."""
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


@dataclass
class Corpus:
    titles: List[str]
    prefix_len: int = 3

    @property
    def n(self) -> int:
        return len(self.titles)


def block_sizes(n: int, head_frac: float, pair_share: float) -> np.ndarray:
    """One head block of ``head_frac·n`` entities and a power-law tail
    whose exponent is bisected so the head holds ``pair_share`` of all
    pairs."""
    head = max(2, int(round(head_frac * n)))
    rest = n - head
    head_pairs = head * (head - 1) // 2

    def tail_sizes(a: float) -> np.ndarray:
        b_guess = max(8, rest // 3)
        w = np.power(np.arange(1, b_guess + 1, dtype=np.float64), -a)
        s = np.maximum(1, np.round(w * (rest / w.sum()))).astype(np.int64)
        s = np.minimum(s, head)
        c = np.cumsum(s)
        cut = int(np.searchsorted(c, rest, side="left")) + 1
        s = s[:cut]
        s[-1] -= int(c[min(cut - 1, len(c) - 1)] - rest)
        if s[-1] <= 0:
            s = s[:-1]
        return s[s > 0]

    lo_a, hi_a = 0.01, 3.0
    for _ in range(48):
        mid = 0.5 * (lo_a + hi_a)
        s = tail_sizes(mid)
        share = head_pairs / (head_pairs + float((s * (s - 1) // 2).sum()))
        if share > pair_share:
            lo_a = mid
        else:
            hi_a = mid
    sizes = np.concatenate([[head], tail_sizes(0.5 * (lo_a + hi_a))])
    if sizes[0] < sizes[1:].max():
        raise ValueError("the head block must stay the largest")
    return sizes.astype(np.int64)


def _prefixes(count: int) -> Tuple[List[str], int]:
    width = 3
    while len(ALPHABET) ** width < count:
        width += 1
    return ["".join(t) for t in itertools.islice(
        itertools.product(ALPHABET, repeat=width), count)], width


def perturb(rng: np.random.Generator, title: str, keep: int) -> str:
    """One or two character edits after position ``keep``: the block is
    kept and the edit similarity stays near or above 0.8."""
    s = list(title)
    for _ in range(int(rng.integers(1, 3))):
        op = int(rng.integers(0, 3))
        pos = keep + int(rng.integers(0, max(1, len(s) - keep)))
        ch = ALPHABET[int(rng.integers(0, 26))]
        if op == 0 and len(s) > 12:
            del s[min(pos, len(s) - 1)]
        elif op == 1:
            s.insert(min(pos, len(s)), ch)
        else:
            s[min(pos, len(s) - 1)] = ch
    return "".join(s)


def build_corpus(params: dict, seed: int) -> Corpus:
    """The corpus a configuration's parameters describe."""
    n = int(params["n_records"])
    dup_frac = float(params["dup_frac"])
    rng = seed_rng(seed, 0)
    base = int(n / (1 + dup_frac))
    sizes = block_sizes(base, float(params["head_frac"]),
                        float(params["pair_share"]))
    prefixes, width = _prefixes(len(sizes))
    titles: List[str] = []
    for blk, size in enumerate(sizes):
        pre = prefixes[blk]
        w = rng.integers(0, len(WORDS), (size, 2))
        serial = rng.integers(0, 10_000, size)
        titles.extend(f"{pre} {WORDS[a]} {WORDS[b]} {v:04d}"
                      for a, b, v in zip(w[:, 0], w[:, 1], serial))
    n_dup = int(len(titles) * dup_frac)
    dup_src = rng.choice(len(titles), size=n_dup, replace=False)
    for src in dup_src:
        titles.append(perturb(rng, titles[int(src)], keep=width))
    perm = rng.permutation(len(titles))
    return Corpus(titles=[titles[int(i)] for i in perm], prefix_len=width)
