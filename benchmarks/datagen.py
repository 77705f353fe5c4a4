"""Seeded inputs: a random tree, an alignment evolved down it, and
topologies a few SPR moves away.

Self-contained NumPy: nothing of `examl_tpu` is imported to make data,
so a change to the program cannot move the benchmark's inputs.  The
same (configuration, seed) gives the same files byte for byte.

The work must not depend on the seed: an optimiser's iteration counts
follow the data, and runs on different data differ by far more than two
runs on the same.  So the *problem* (tree, alignment, moved topologies)
is drawn from the configuration's `data_seed`, and `--seed` draws only
the order of the PHYLIP file's site columns.  The program's parser
compresses the columns to sorted patterns, so every seed reaches the
engine as the same byteFile: the same dispatches and the same answers.
That is meant: parsed with the parser's `-c`, which keeps the seed's
order, the sums ran in another order, the Brents and Newton sweeps took
another path, and a `modopt` step moved by 8% from seed to seed where
two runs of one seed agree to 0.5% (v5e, PR 27, PERF.md section 4).
The trees are written the same for every seed: the program keys its
compiled programs by the traversal's layout, which follows the node
numbering, so trees relabelled by the seed would compile anew in every
run (seen on the v5e, PR 27) and move `setup_s` with the seed.

An alignment may be in several parts (`parts_of`): one tree, and for
each part a generating model of its own and exactly its `patterns`
distinct columns evolved on the tree's lengths times the part's `rate`,
concatenated in part order; `--seed` then orders the columns inside each
part, so every column stays in the partition the partition file puts it
in.  A configuration without `parts` is one part, and its rng is drawn
call for call as it was before parts existed: the accepted cells'
problems are byte for byte what they were (tests/benchmarks/
test_partitioned.py pins their sha256).

A tree is an adjacency map {node: [neighbours]} over tips 0..n-1 and
inner nodes n..2n-3, with branch lengths keyed by the sorted node pair.
"""

from __future__ import annotations

import numpy as np
from scipy.linalg import expm

from benchmarks.reference import discrete_gamma, generator

ALPHABETS = {"DNA": "ACGT", "AA": "ARNDCQEGHILKMFPSTWYV"}


def random_tree(rng, ntaxa: int, tmin: float = 0.02, tmax: float = 0.25):
    """Stepwise random addition: each new tip splits a uniformly drawn
    branch.  Returns (adj, lengths)."""
    adj = {0: [ntaxa], 1: [ntaxa], 2: [ntaxa], ntaxa: [0, 1, 2]}
    edges = [(0, ntaxa), (1, ntaxa), (2, ntaxa)]
    for tip in range(3, ntaxa):
        inner = ntaxa + tip - 2
        a, b = edges.pop(int(rng.integers(len(edges))))
        adj[a][adj[a].index(b)] = inner
        adj[b][adj[b].index(a)] = inner
        adj[inner] = [a, b, tip]
        adj[tip] = [inner]
        edges += [(a, inner), (b, inner), (tip, inner)]
    lengths = {tuple(sorted(e)): float(rng.uniform(tmin, tmax))
               for e in edges}
    return adj, lengths


def _side(adj, start: int, block: int) -> set:
    """Nodes reached from `start` without passing through `block`."""
    seen, stack = {start}, [start]
    while stack:
        n = stack.pop()
        for m in adj[n]:
            if m != block and m not in seen:
                seen.add(m)
                stack.append(m)
    return seen


def spr_move(rng, adj, ntaxa: int, lengths=None) -> None:
    """One random subtree-prune-and-regraft, in place: cut the subtree
    behind branch (a, b) at inner node a, join a's other two neighbours,
    and put a into a uniformly drawn other branch of what remains.  With
    `lengths`, the joined branch gets the sum of the two it replaces and
    the split branch is halved."""
    inner = sorted(n for n in adj if n >= ntaxa)
    while True:
        a = inner[int(rng.integers(len(inner)))]
        b = adj[a][int(rng.integers(3))]
        c, d = (m for m in adj[a] if m != b)
        moved = _side(adj, b, a) | {a}
        rest = [(x, y) for x in sorted(adj) if x not in moved
                for y in adj[x] if x < y and y not in moved
                and {x, y} != {c, d}]
        if not rest:
            continue
        adj[c][adj[c].index(a)] = d
        adj[d][adj[d].index(a)] = c
        x, y = rest[int(rng.integers(len(rest)))]
        adj[x][adj[x].index(y)] = a
        adj[y][adj[y].index(x)] = a
        adj[a] = [b, x, y]
        if lengths is not None:
            key = lambda u, v: tuple(sorted((u, v)))   # noqa: E731
            lengths[key(c, d)] = (lengths.pop(key(a, c))
                                  + lengths.pop(key(a, d)))
            half = lengths.pop(key(x, y)) / 2.0
            lengths[key(a, x)] = lengths[key(a, y)] = half
        return


def newick(adj, ntaxa: int, lengths=None) -> str:
    """Unrooted Newick with a trifurcation at the first inner node; tip
    i is named t<i+1>."""
    def fmt(n, par):
        s = (f"t{n + 1}" if n < ntaxa else
             "(" + ",".join(fmt(m, n) for m in adj[n] if m != par) + ")")
        if lengths is not None and par is not None:
            s += f":{lengths[tuple(sorted((n, par)))]:.6f}"
        return s
    return fmt(ntaxa, None) + ";"


def parts_of(config: dict) -> list:
    """The alignment's partitions as the configuration states them.  With
    `parts`, each `{name, model (the parser's token), patterns,
    exchangeabilities (a table under models/; absent where the rates are
    free), generating: {alpha, rate, rates, freqs}}`; without, ONE part
    made of the configuration's own keys."""
    if "parts" in config:
        return config["parts"]
    part = {"name": "p1", "patterns": config["patterns"],
            "generating": config["generating"]}
    if config.get("exchangeabilities"):
        part["exchangeabilities"] = config["exchangeabilities"]
    return [part]


def model_params(gen: dict, K: int, rng):
    """(rates, freqs, alpha) a part's data are evolved under: its
    `generating` group's own numbers, or for `"rates": "random"` a
    reversible matrix drawn from the seed."""
    if gen.get("rates") == "random":
        rates = np.exp(rng.normal(0.0, 1.0, K * (K - 1) // 2))
        freqs = rng.dirichlet(np.full(K, 8.0))
    else:
        rates = np.asarray(gen["rates"], dtype=np.float64)
        freqs = np.asarray(gen["freqs"], dtype=np.float64)
    return rates, freqs / freqs.sum(), float(gen["alpha"])


def evolve(rng, adj, lengths, ntaxa, nsites, rates, freqs, alpha,
           ncat: int = 4) -> np.ndarray:
    """[ntaxa, nsites] uint8 state codes: a root state from `freqs` at
    the first inner node, one gamma category a site, sampled down every
    branch through expm(Q r t)."""
    Q = generator(rates, freqs)
    grates = discrete_gamma(alpha, ncat)
    K = len(freqs)
    cat = rng.integers(0, ncat, nsites)
    out = np.empty((ntaxa, nsites), dtype=np.uint8)
    stack = [(ntaxa, None, rng.choice(K, size=nsites, p=freqs))]
    while stack:
        n, par, states = stack.pop()
        if n < ntaxa:
            out[n] = states
            continue
        for m in adj[n]:
            if m == par:
                continue
            t = lengths[tuple(sorted((n, m)))]
            P = np.stack([expm(Q * (r * t)) for r in grates])
            cum = np.cumsum(np.clip(P[cat, states, :], 0.0, None), axis=1)
            u = rng.random(nsites)[:, None] * cum[:, -1:]
            child = (u > cum[:, :-1]).sum(axis=1).astype(np.uint8)
            stack.append((m, n, child))
    return out


def alignment(rng, adj, lengths, ntaxa, npatterns, rates, freqs, alpha):
    """Exactly `npatterns` distinct columns, each once, in the order they
    were first drawn."""
    cols, have = [], 0
    while have < npatterns:
        n = int((npatterns - have) * 1.25) + 64
        cols.append(evolve(rng, adj, lengths, ntaxa, n, rates, freqs,
                           alpha))
        allc = np.concatenate(cols, axis=1)
        _, first = np.unique(allc.T, axis=0, return_index=True)
        have = first.size
    return np.ascontiguousarray(allc[:, np.sort(first)[:npatterns]])


def write_phylip(path: str, mat: np.ndarray, datatype: str) -> None:
    letters = np.frombuffer(ALPHABETS[datatype].encode(), dtype=np.uint8)
    with open(path, "w") as f:
        f.write(f"{mat.shape[0]} {mat.shape[1]}\n")
        for i in range(mat.shape[0]):
            f.write(f"t{i + 1} {letters[mat[i]].tobytes().decode()}\n")


def problem(config: dict, trees: int, spr_moves: int,
            branch_lengths: bool = False) -> dict:
    """The cell's problem, from the configuration's `data_seed` alone:
    the generating tree; for each part of `parts_of(config)`, in order, a
    generating model and exactly its `patterns` distinct columns evolved
    on the tree's lengths times the part's `rate`, concatenated; `trees`
    topologies `spr_moves` random SPR moves from the generating one
    (with the branch lengths the moves leave them, if asked).  With one
    part the rng is drawn as it was before parts existed, call for
    call."""
    ntaxa = config["taxa"]
    parts = parts_of(config)
    widths = [p["patterns"] for p in parts]
    rng = np.random.default_rng([config["data_seed"], ntaxa, sum(widths)])
    adj, lengths = random_tree(rng, ntaxa)
    K = len(ALPHABETS[config["datatype"]])
    models, mats = [], []
    for part in parts:
        rates, freqs, alpha = model_params(part["generating"], K, rng)
        rate = float(part["generating"].get("rate", 1.0))
        scaled = {e: t * rate for e, t in lengths.items()}
        mats.append(alignment(rng, adj, scaled, ntaxa, part["patterns"],
                              rates, freqs, alpha))
        models.append({"rates": rates, "freqs": freqs, "alpha": alpha})
    moved = []
    for _ in range(trees):
        other = {n: list(v) for n, v in adj.items()}
        other_len = dict(lengths)
        for _ in range(spr_moves):
            spr_move(rng, other, ntaxa, other_len)
        moved.append(newick(other, ntaxa,
                            other_len if branch_lengths else None))
    ends = np.cumsum(widths).tolist()
    return {"patterns": np.concatenate(mats, axis=1),
            "tree": newick(adj, ntaxa), "moved_trees": moved,
            "models": models,
            "bounds": [(e - w, e) for w, e in zip(widths, ends)]}


def present(prob: dict, seed: int) -> dict:
    """The problem as run `seed` sees it: the site columns of each part
    in an order drawn from the seed, every column in its own part."""
    rng = np.random.default_rng([seed, 0x5EED])
    order = np.concatenate([s + rng.permutation(e - s)
                            for s, e in prob["bounds"]])
    return {**prob,
            "patterns": np.ascontiguousarray(prob["patterns"][:, order])}
