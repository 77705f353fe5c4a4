"""The plain reference of a replicate analysis: the benchmark's own
bootstrap draw and the f64 per-pattern likelihood it is dotted with.

Independent of the program under test, as `reference.py` is: it imports
nothing of `examl_tpu` and reads only the generator's matrix and what a
step answered (a tree as edges with their z, the parameters a step
optimises).

* The draw, as `examl_tpu/fleet/seeds.py` and `fleet/bootstrap.py`
  document it: replicate j of a run with parent seed P has the seed
  `derive(P, "bootstrap", j)` (splitmix64, stream tag 0xB001), its
  partition k the seed `derive(that, "partition", k)` (tag 0x9A27); the
  partition's sites are drawn with replacement over its distinct column
  patterns, `numpy.random.default_rng(seed).multinomial(L, w / L)`, `w`
  the patterns' column counts and `L` the partition's site count.  The
  patterns are in the order a parser that sorts distinct columns
  lexicographically gives them (`numpy.unique` over columns).
* A replicate's lnL is its weights dotted with the per-pattern lnLs of
  `reference._block`: one pass a tree serves every replicate.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks import reference

MASK64 = (1 << 64) - 1
GOLDEN = 0x9E3779B97F4A7C15
STREAMS = {"bootstrap": 0xB001, "partition": 0x9A27}


def splitmix64(x: int) -> int:
    x = (x + GOLDEN) & MASK64
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
    return (z ^ (z >> 31)) & MASK64


def derive(parent: int, stream: str, index: int) -> int:
    """A job's seed: two splitmix64 rounds, the stream's then the
    index's, clamped to 63 bits."""
    state = splitmix64((parent & MASK64) ^ (STREAMS[stream] * GOLDEN & MASK64))
    return splitmix64((state + index * GOLDEN) & MASK64) >> 1


def distinct(columns: np.ndarray):
    """(patterns [n, U] in sorted order, their column counts [U], the
    pattern of every column [W])."""
    uniq, inverse, counts = np.unique(columns.T, axis=0, return_inverse=True,
                                      return_counts=True)
    return np.ascontiguousarray(uniq.T), counts, inverse.reshape(-1)


def draw(counts: np.ndarray, seed: int) -> np.ndarray:
    """One partition's replicate weights over its distinct patterns."""
    w = np.asarray(counts, dtype=np.float64)
    total = int(round(w.sum()))
    return np.random.default_rng(seed).multinomial(
        total, w / w.sum()).astype(np.float64)


def replicate_weights(patterns: np.ndarray, bounds, parent_seed: int,
                      count: int):
    """Every replicate's weight of every column of the generator's matrix
    `[count, W]`: a column carries its pattern's weight over the
    pattern's column count, so a dot with per-column lnLs is the
    replicate's lnL whatever order the columns come in."""
    out = np.zeros((count, patterns.shape[1]))
    for k, (s, e) in enumerate(bounds):
        _, counts, inverse = distinct(patterns[:, s:e])
        for j in range(count):
            w = draw(counts, derive(derive(parent_seed, "bootstrap", j),
                                    "partition", k))
            out[j, s:e] = (w / counts)[inverse]
    return out


def site_lnls(patterns: np.ndarray, edges, ntips: int, rates, freqs,
              alpha: float, ncat: int = 4, block: int = 2048) -> np.ndarray:
    """f64 lnL of every column of `patterns` on the tree `edges` =
    [(node_a, node_b, z)] (plain pruning, `reference._block`)."""
    freqs = np.asarray(freqs, dtype=np.float64)
    Q = reference.generator(np.asarray(rates, dtype=np.float64), freqs)
    grates = reference.discrete_gamma(float(alpha), ncat)
    W = patterns.shape[1]

    def run(s):
        return reference._block(
            patterns[:, s:min(W, s + block)].astype(np.int64), edges, ntips,
            Q, freqs, grates, False)[0]

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        return np.concatenate(list(pool.map(run, range(0, W, block))))
