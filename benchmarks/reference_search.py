"""The plain reference of the SPR candidate window: which insertions a
pruned node's scan must score, and what each scores.

Beside `reference.py` and as independent of the program: NumPy f64,
nothing of `examl_tpu` imported.  Given the tree as an edge list
[(a, b, z)], the pruned node p with the neighbour s its subtree hangs
on, and a radius, it

* enumerates the window itself: with p cut out and its other two
  neighbours q1, q2 joined, every edge 1..radius edges away from the
  joined branch on either side, not below a tip (the candidate edges of
  ExaML's `addTraverseBIG` under `rearrangeBIG`, `searchAlgo.c:785-833`,
  without the lnL cutoff's early stops: the batched scan scores the
  whole window);
* builds each regrafted tree: the joined branch q1 -- q2 as given (the
  program optimises it, so it is an input), the candidate edge (v, w)
  split at p into two branches of `clip(sqrt(z_vw))` (the lazy arm's
  rule), the subtree's branch p -- s as given;
* scores it with `reference.evaluate`.

For a thorough candidate the three branches around p are the program's
answer, so the reference takes them as given, scores that tree and says
how far one Newton step of its own derivatives would still move each of
the three (`thorough_candidate`).
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference

Z_MIN, Z_MAX = 1e-15, 0.999999        # the configurations' `domain`


def _adjacency(edges):
    adj = {}
    for a, b, z in edges:
        adj.setdefault(a, {})[b] = z
        adj.setdefault(b, {})[a] = z
    return adj


def prune(edges, p: int, s: int, zqr: float):
    """The tree without p and what hangs on it through s: p's other two
    neighbours q1, q2 joined by a branch `zqr`.  Returns (edges of the
    whole pruned forest: the joined tree and the subtree behind s,
    q1, q2)."""
    adj = _adjacency(edges)
    q1, q2 = (n for n in adj[p] if n != s)
    rest = [(a, b, z) for a, b, z in edges if p not in (a, b)]
    return rest + [(q1, q2, zqr)], q1, q2


def window(edges, p: int, s: int, radius: int, ntips: int,
           mintrav: int = 1):
    """Candidate edges (v, w), w nearer the joined branch, in no
    particular order, with their depth (1 = touching q1 or q2)."""
    adj = _adjacency(edges)
    q1, q2 = (n for n in adj[p] if n != s)
    out = []
    for a, b in ((q1, q2), (q2, q1)):
        if a <= ntips:
            continue
        stack = [(v, a, 1) for v in adj[a] if v not in (b, p)]
        while stack:
            v, w, depth = stack.pop()
            if depth >= mintrav:
                out.append((v, w, depth))
            if v > ntips and depth < radius:
                stack += [(u, v, depth + 1) for u in adj[v] if u != w]
    return out


def regraft(edges, p: int, s: int, zqr: float, v: int, w: int, zs: float,
            zv=None, zw=None):
    """Edge list with p (and its subtree behind s) moved into edge
    (v, w): branches p -- v, p -- w (each `clip(sqrt(z_vw))` unless
    given), p -- s `zs`."""
    pruned, _q1, _q2 = prune(edges, p, s, zqr)
    (zvw,) = [z for a, b, z in pruned if {a, b} == {v, w}]
    half = float(np.clip(np.sqrt(zvw), Z_MIN, Z_MAX))
    out = [(a, b, z) for a, b, z in pruned if {a, b} != {v, w}]
    return out + [(p, v, half if zv is None else zv),
                  (p, w, half if zw is None else zw), (p, s, zs)]


def lazy_candidate(patterns, model: dict, edges, p, s, zqr, zs, v, w,
                   ncat: int = 4) -> float:
    """lnL of the lazy arm's insertion of p into (v, w)."""
    tree = regraft(edges, p, s, zqr, v, w, zs)
    return reference.evaluate(
        patterns, None, tree, patterns.shape[0], model["rates"],
        model["freqs"], model["alpha"], ncat, want_derivs=False)[0]


def thorough_candidate(patterns, model: dict, edges, p, s, zqr, v, w,
                       triplet, ncat: int = 4):
    """(lnL, newton_dz of the three branches around p) with the
    program's branch triplet (p -- v, p -- w, p -- s) in force."""
    zv, zw, zs = (float(z) for z in triplet)
    tree = regraft(edges, p, s, zqr, v, w, zs, zv, zw)
    lnl, d1, d2 = reference.evaluate(
        patterns, None, tree, patterns.shape[0], model["rates"],
        model["freqs"], model["alpha"], ncat)
    dz = reference.newton_dz(tree, d1, d2, Z_MIN, Z_MAX)
    return lnl, dz[-3:]
