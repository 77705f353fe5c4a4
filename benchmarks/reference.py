"""The plain reference: f64 NumPy likelihood of a tree, and how far its
branches still are from their optimum.

Independent of the program under test: it imports nothing of
`examl_tpu`, takes the alignment as the benchmark generated it (not as
the program loaded it), and is given only what a timed step *answered*:
the tree as a list of edges with their z = exp(-t), and the parameters
a step optimises (alpha, and DNA's exchangeabilities).  What is no free
parameter it makes itself: the empirical frequencies are its own column
counts of the generator's matrix, a protein model's exchangeabilities
the benchmark's own copy of the published table (`models/<name>.json`).
Transition matrices come from `scipy.linalg.expm` of the normalised
reversible generator, the discrete gamma rates from the incomplete gamma
function; no eigendecomposition, no rescaling, no packing.

Two numbers per state:

* `lnl`: plain pruning (Felsenstein) in float64;
* `newton_dz_max`: the largest move in z that one Newton step of the
  *reference's* analytic first and second derivatives would still make
  on any branch (both directions of every edge by the pulley principle,
  d/dt through Q P(t)).  A tree whose branches an optimiser has settled
  reads about its stopping rule; a tree left at its default branch
  lengths reads near 1.

Sites are processed in blocks on a few threads so that 140 x 131,072
fits in about 1.5 GB of host memory.  An alignment in several parts, a
model each (and with `-M` a branch-length class each), is the sum of
`evaluate` over the parts (`evaluate_parts`).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.linalg import expm
from scipy.special import gammainc, gammaincinv


def discrete_gamma(alpha: float, ncat: int = 4) -> np.ndarray:
    """Mean rate of each of `ncat` equiprobable categories of a
    Gamma(alpha, 1/alpha) distribution (Yang 1994), mean 1."""
    cuts = gammaincinv(alpha, np.arange(1, ncat) / ncat) / alpha
    upper = np.concatenate([gammainc(alpha + 1.0, cuts * alpha), [1.0]])
    lower = np.concatenate([[0.0], upper[:-1]])
    return (upper - lower) * ncat


def empirical_freqs(patterns: np.ndarray, weights, K: int) -> np.ndarray:
    """Empirical state frequencies: weighted counts of the state codes.
    The generator writes no ambiguity codes, so the fixed point of the
    program's (and ExaML's) EM over ambiguity classes is the plain
    count."""
    w = (np.ones(patterns.shape[1]) if weights is None
         else np.asarray(weights, dtype=np.float64))
    counts = np.array([((patterns == k) * w).sum() for k in range(K)])
    return counts / counts.sum()


def generator(rates: np.ndarray, freqs: np.ndarray) -> np.ndarray:
    """Reversible rate matrix from upper-triangle exchangeabilities and
    stationary frequencies, scaled to one expected change per unit t."""
    K = len(freqs)
    R = np.zeros((K, K))
    R[np.triu_indices(K, 1)] = rates
    R = R + R.T
    Q = R * freqs[None, :]
    np.fill_diagonal(Q, -Q.sum(axis=1))
    return Q / -(freqs @ np.diag(Q))


def _orient(edges, ntips):
    """Root the edge list at an inner node; returns (root, children,
    parent edge index per node, post-order of non-root nodes)."""
    adj = {}
    for ei, (a, b, _z) in enumerate(edges):
        adj.setdefault(a, []).append((b, ei))
        adj.setdefault(b, []).append((a, ei))
    root = next(n for n in adj if n > ntips)
    children, edge_of, order = {}, {}, []
    stack = [(root, None)]
    while stack:
        n, par = stack.pop()
        kids = [(m, ei) for m, ei in adj[n] if m != par]
        children[n] = [m for m, _ in kids]
        for m, ei in kids:
            edge_of[m] = ei
            stack.append((m, n))
        order.append(n)
    return root, children, edge_of, order[::-1]      # children first


def _block(codes, edges, ntips, Q, freqs, grates, want_derivs):
    """One block of sites.  codes [ntips, B] int; returns
    (site lnL [B], d1 [E, B] or None, d2 [E, B] or None) where d1, d2
    are per-site contributions to d lnL / d lz and d2 lnL / d lz2."""
    R, K, B = len(grates), len(freqs), codes.shape[1]
    root, children, edge_of, post = _orient(edges, ntips)
    t = {ei: -np.log(z) for ei, (_a, _b, z) in enumerate(edges)}
    eye = np.eye(K)

    def P(ei):                                   # [R, K, K], transposed
        return np.stack([expm(Q * (r * t[ei])).T for r in grates])

    Pt = {ei: P(ei) for ei in t}
    down, msg = {}, {}              # X_{v->parent}, P X_{v->parent}
    for v in post:
        if v == root:
            continue
        if v <= ntips:
            x = np.broadcast_to(eye[codes[v - 1]], (R, B, K))
        else:
            c1, c2 = children[v]
            x = msg[c1] * msg[c2]
        down[v] = x
        msg[v] = np.matmul(x, Pt[edge_of[v]])
    c1, c2, c3 = children[root]
    site = np.einsum("rbk,k->b", msg[c1] * msg[c2] * msg[c3], freqs) / R
    if not want_derivs:
        return np.log(site), None, None

    E = len(edges)
    d1 = np.empty((E, B))
    d2 = np.empty((E, B))
    rQt = np.stack([(Q * r).T for r in grates])          # [R, K, K]
    stack = [(c1, msg[c2] * msg[c3]), (c2, msg[c1] * msg[c3]),
             (c3, msg[c1] * msg[c2])]
    while stack:
        v, up = stack.pop()         # up = X_{parent->v}, at the parent
        ei = edge_of[v]
        q1 = np.matmul(msg[v], rQt)                      # rQ P X
        q2 = np.matmul(q1, rQt)
        a = up * freqs
        l1 = np.einsum("rbk,rbk->b", a, q1) / R          # dL/dt
        l2 = np.einsum("rbk,rbk->b", a, q2) / R
        g = l1 / site
        d1[ei] = -g                                      # lz = -t
        d2[ei] = l2 / site - g * g
        if v > ntips:
            m_up = np.matmul(up, Pt[ei])     # message from above into v
            w1, w2 = children[v]
            stack.append((w1, m_up * msg[w2]))
            stack.append((w2, m_up * msg[w1]))
    return np.log(site), d1, d2


def evaluate(patterns: np.ndarray, weights, edges, ntips: int,
             rates, freqs, alpha: float, ncat: int = 4,
             want_derivs: bool = True, threads: int | None = None):
    """lnL (and per-edge d1, d2 with respect to log z) of the tree
    `edges` = [(node_a, node_b, z)], nodes 1..ntips being the rows of
    `patterns` [ntips, W] (state codes 0..K-1), inner nodes above."""
    freqs = np.asarray(freqs, dtype=np.float64)
    Q = generator(np.asarray(rates, dtype=np.float64), freqs)
    grates = discrete_gamma(float(alpha), ncat)
    W = patterns.shape[1]
    K = len(freqs)
    weights = (np.ones(W) if weights is None
               else np.asarray(weights, dtype=np.float64))
    # two stored arrays a node, about 1.5 GB in flight over the threads
    threads = threads or min(8, os.cpu_count() or 1)
    per_site = 2 * (2 * ntips) * ncat * K * 8
    B = int(max(256, min(W, 1.5e9 // (per_site * threads))))
    blocks = [(s, min(W, s + B)) for s in range(0, W, B)]

    def run(se):
        s, e = se
        ll, b1, b2 = _block(patterns[:, s:e].astype(np.int64), edges,
                            ntips, Q, freqs, grates, want_derivs)
        w = weights[s:e]
        if not want_derivs:
            return float(w @ ll), None, None
        return float(w @ ll), b1 @ w, b2 @ w

    with ThreadPoolExecutor(max_workers=threads) as pool:
        parts = list(pool.map(run, blocks))
    lnl = sum(p[0] for p in parts)
    if not want_derivs:
        return lnl, None, None
    return lnl, sum(p[1] for p in parts), sum(p[2] for p in parts)


def evaluate_parts(patterns: np.ndarray, bounds, edges, ntips: int, models,
                   ncat: int = 4, want_derivs: bool = True):
    """The tree's lnL over an alignment in parts: `evaluate` on each
    part's own columns `patterns[:, s:e]` with the part's own (rates,
    freqs, alpha) and the z of its branch-length class, summed.  `edges`
    = [(node_a, node_b, z_0, .., z_{C-1})]: with C = 1 every part reads
    z_0; with C > 1 (`-M`) part k reads z_k.  Returns (lnl, the parts'
    lnLs, d1 [C, E], d2 [C, E]), the derivatives summed over the parts of
    a class (None without `want_derivs`)."""
    C = len(edges[0]) - 2
    lnls, d1, d2 = [], [None] * C, [None] * C
    for k, ((s, e), (rates, freqs, alpha)) in enumerate(zip(bounds, models)):
        c = k if C > 1 else 0
        part_edges = [(row[0], row[1], row[2 + c]) for row in edges]
        lnl, p1, p2 = evaluate(patterns[:, s:e], None, part_edges, ntips,
                               rates, freqs, alpha, ncat, want_derivs)
        lnls.append(lnl)
        if want_derivs:
            d1[c] = p1 if d1[c] is None else d1[c] + p1
            d2[c] = p2 if d2[c] is None else d2[c] + p2
    total = sum(lnls[1:], lnls[0])
    if not want_derivs:
        return total, lnls, None, None
    return total, lnls, np.stack(d1), np.stack(d2)


def newton_dz(edges, d1, d2, zmin: float, zmax: float) -> np.ndarray:
    """|z_new - z| per edge for one Newton step in log z on the
    reference's derivatives, clipped to the model's domain [zmin, zmax];
    where the curvature is not negative the step is ExaML's fallback
    0.37 z + 0.63 (no step at zmax)."""
    z = np.clip(np.array([e[2] for e in edges], dtype=np.float64),
                zmin, zmax)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        step = np.clip(np.where(d2 < 0.0, -d1 / d2, 0.0), -100.0, 100.0)
    znew = np.where(d2 < 0.0, z * np.exp(step),
                    np.where(z < zmax, 0.37 * z + 0.63, z))
    return np.abs(np.clip(znew, zmin, zmax) - z)
