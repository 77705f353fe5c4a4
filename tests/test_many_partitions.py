"""Many partitions through the normal path (CPU, tier-1).

The partitioned protein cell (`aa144p58x16k.modopt`) runs 58 genes with
a model each in one K = 20 engine.  Here the same shape at 12 taxa:
58 ragged LG+GAMMA4 genes, 54 of 4 to 40 patterns and four wider ones
(two of 120, two of 200: two blocks), drawn by the benchmark's generator
from seeded
random frequencies and alphas, written as a PHYLIP file and a 58-line
`-q` file, parsed by `cli.parse` and loaded as `cli.main` loads them.
What is held: the total and every gene's lnL against `tests/oracle.py`
(plain pruning with `expm`); the gradient pass's per-edge d1 and d2
against the oracle's finite differences; one `mod_opt` round fits every
gene's alpha on its own, in one batched Brent; the two site counters
every engine raises equal `pack_layout`'s arithmetic; and a protein row
wider than one piece, read in pieces by the traversal's one-entry step,
is the row read whole, bit for bit.  No number here is a device number.
"""

import contextlib
import os
import sys

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.io.alignment import AlignmentData
from examl_tpu.parallel.packing import pack_layout

from tests.oracle import oracle_lnl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import datagen  # noqa: E402
from benchmarks import run as bench  # noqa: E402

NTAXA, M = 12, 58
# gene index -> width of the wider genes, and the alpha two of each
# width are evolved under in the test that fits alphas
WIDE = {10: 120, 20: 120, 40: 200, 56: 200}
SWAPPED = {10: 0.2, 20: 4.0, 40: 4.0, 56: 0.2}


def _widths():
    w = np.random.default_rng(1478).integers(4, 41, M)
    for k, v in WIDE.items():
        w[k] = v
    return [int(x) for x in w]


def _genes(alphas=None):
    """The 58 genes as a configuration's `parts`: LGF, random reversible
    generating matrices, each gene its own alpha and rate."""
    rng = np.random.default_rng(42)
    a = np.exp(rng.uniform(np.log(0.3), np.log(1.5), M))
    r = np.exp(rng.uniform(np.log(0.4), np.log(2.2), M))
    if alphas:
        for k, v in alphas.items():
            a[k], r[k] = v, 2.0
    return [{"name": f"gene{k + 1}", "model": "LGF", "patterns": w,
             "exchangeabilities": "LG",
             "generating": {"rates": "random", "alpha": float(a[k]),
                            "rate": float(r[k])}}
            for k, w in enumerate(_widths())]


def _load(tmp, parts, dtype=None):
    """(instance, alignment, tree with the generating lengths after one
    SPR move) of `parts` through `cli.parse -q` and the CLI's loader."""
    from examl_tpu.cli import main as cli_main
    from examl_tpu.cli import parse as cli_parse
    from examl_tpu.instance import PhyloInstance
    config = {"taxa": NTAXA, "datatype": "AA", "data_seed": 20261015,
              "parts": parts}
    prob = datagen.problem(config, trees=1, spr_moves=1, branch_lengths=True)
    aln = os.path.join(tmp, "aln")
    datagen.write_phylip(aln + ".phy", prob["patterns"], "AA")
    with open(aln + ".model", "w") as f:
        f.write("".join(f"LGF, {p['name']} = {s + 1}-{e}\n"
                        for p, (s, e) in zip(parts, prob["bounds"])))
    with contextlib.redirect_stdout(sys.stderr):
        assert cli_parse.main(["-s", aln + ".phy", "-n", aln, "-m", "PROT",
                               "-q", aln + ".model"]) == 0
    # loaded as `cli.main` loads it; one device, as on the cell's chip
    # (the CLI would shard the site axis over the tests' 8 CPU devices)
    data = cli_main._load_alignment(aln + ".binary")
    assert [p.width for p in data.partitions] == _widths()
    inst = PhyloInstance(data) if dtype is None else PhyloInstance(
        data, dtype=dtype)
    return inst, data, inst.tree_from_newick(prob["moved_trees"][0])


@pytest.fixture(scope="module")
def genes(tmp_path_factory):
    return _load(str(tmp_path_factory.mktemp("genes")), _genes())


def _oracle_parts(inst, data, tree):
    return [oracle_lnl(tree, AlignmentData(data.taxon_names, [part]),
                       [inst.models[k]])
            for k, part in enumerate(data.partitions)]


def test_every_gene_against_the_oracle(genes):
    """Both sides are f64: the engine's eigensystems against `expm`
    differ in rounding alone (about 1e-13 of a gene's lnL), so 1e-9 is
    room for that and catches a model on the wrong gene, which moves a
    gene's lnL by a percent and more."""
    inst, data, tree = genes
    (eng,) = inst.engines.values()
    assert eng.num_parts == M and eng.dtype == np.float64
    assert eng.B == M + 2                  # one block a gene, two for two
    total = inst.evaluate(tree, full=True)
    got = np.asarray(inst.per_partition_lnl, dtype=np.float64)
    want = np.array(_oracle_parts(inst, data, tree))
    assert got.shape == (M,)
    np.testing.assert_allclose(got, want, rtol=1e-9)
    assert total == pytest.approx(want.sum(), rel=1e-9)
    # every gene has a model of its own: frequencies of its own columns
    assert len({tuple(np.round(m.freqs, 12)) for m in inst.models}) == M


def test_gradient_pass_per_edge_against_the_oracle(genes):
    """d1 and d2 by lz of the one-pass gradient (every edge, all 58
    genes summed) against central differences of the oracle's total.
    The lnL's third derivative here is of order 1e5 a unit of lz: a
    step of 1e-5 leaves about 1e-7 of d1 (1e-4 would leave 1e-5), and
    the second difference at 1e-4 about 5e-6 of d2, where f64 rounding
    is still far under it; so 1e-6 and 1e-4."""
    from examl_tpu.optimize.branch import tree_gradients
    inst, data, tree = genes
    inst.evaluate(tree, full=True)
    slots, d1, d2 = tree_gradients(inst, tree)
    assert d1.shape == (2 * NTAXA - 3, 1)
    for k in (0, 7, len(slots) - 1):
        s = slots[k]
        z0 = list(s.z)
        lz = float(np.log(z0[0]))
        at = {}
        for dz in (1e-5, -1e-5, 1e-4, 0.0, -1e-4):
            s.z[:] = [float(np.exp(lz + dz))]
            at[dz] = sum(_oracle_parts(inst, data, tree))
        s.z[:] = z0
        assert float(d1[k, 0]) == pytest.approx(
            (at[1e-5] - at[-1e-5]) / 2e-5, rel=1e-6)
        assert float(d2[k, 0]) == pytest.approx(
            (at[1e-4] - 2 * at[0.0] + at[-1e-4]) / 1e-8, rel=1e-4)
    tree.invalidate_all()


def test_one_round_fits_each_genes_alpha_on_its_own(tmp_path, monkeypatch):
    """Two genes of each wide width, evolved under alphas 0.2 and 4 with
    the pairs swapped (the 120-pattern genes 0.2 then 4, the 200-pattern
    ones 4 then 0.2): one `mod_opt` round (epsilon 0.1, as the cell's
    step) fits every gene's alpha from its own columns, so each fitted
    alpha follows the one its data were evolved under, whatever the
    gene's width or place, and all 58 move in ONE batched Brent (a
    vector of 58), not gene by gene."""
    import jax.numpy as jnp

    from examl_tpu.optimize import model_opt
    seen = []
    real = model_opt.minimize_vector

    def spy(x0, *a, **k):
        seen.append(len(x0))
        return real(x0, *a, **k)
    monkeypatch.setattr(model_opt, "minimize_vector", spy)
    inst, _, tree = _load(str(tmp_path), _genes(SWAPPED), jnp.float32)
    before = inst.evaluate(tree, full=True)
    after = model_opt.mod_opt(inst, tree, 0.1, max_rounds=1)
    assert after > before
    assert seen == [M]                     # LG rates are fixed: alpha only
    alpha = np.array([m.alpha for m in inst.models])
    assert (alpha != 1.0).sum() >= M - 2         # each moved from 1.0
    for k, generating in SWAPPED.items():
        assert (alpha[k] < 1.0) == (generating < 1.0), (k, alpha[k])


def test_site_counters_equal_the_packing_arithmetic(genes):
    """Every engine raises `engine.site_lanes` (its bucket's padded site
    axis) and `engine.site_patterns` (the live patterns) once, at
    construction: here 58 genes of 4 to 200 patterns in 60 blocks, and
    the benchmark's `lane_padding_pct` reads 1 - patterns / lanes."""
    from examl_tpu.instance import PhyloInstance
    _, data, _ = genes
    obs.reset()
    PhyloInstance(data)
    (lay,) = pack_layout([(g, 20, w) for g, w in enumerate(_widths())]
                         ).values()
    assert lay.total == (M + 2) * 128
    assert obs.counter("engine.site_lanes") == lay.total
    assert obs.counter("engine.site_patterns") == sum(_widths())
    from benchmarks.readers import counter_gap_at_setup
    spec = bench.read_json(bench.HERE, "layers", "lane_padding_pct.json")
    run = {"counters0": obs.registry().snapshot()["counters"]}
    assert counter_gap_at_setup.read(run, spec) == pytest.approx(
        100.0 * (1.0 - sum(_widths()) / lay.total))
    # a program without the counters gives nothing to read, not 0
    assert counter_gap_at_setup.read({"counters0": {}}, spec) is None


def test_wide_protein_row_read_in_pieces_is_the_row(monkeypatch):
    """The one-entry step reads a protein row wider than
    `kernels.ONE_PIECE_SITES` in pieces of at most that many sites (the
    v5e compiler would slice the whole arena for the row gathered
    whole).  24 taxa (a tree deep enough for a one-entry tail) x two LG
    genes in 31 blocks, f32 (1.27 MB a row): with the width forced down
    to 8 blocks the rows come in four pieces, the last overlapping the
    third, and the arena and the lnL are those of the rows read whole,
    bit for bit."""
    import jax.numpy as jnp

    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.partitions import PartitionSpec
    from examl_tpu.ops import kernels
    rng = np.random.default_rng(31)
    names = [f"t{i}" for i in range(24)]
    seqs = ["".join("ARNDCQEGHILKMFPSTWYV"[c]
                    for c in rng.integers(0, 20, 3900)) for _ in names]
    data = build_alignment_data(names, seqs, [
        PartitionSpec(g, "AA", "LG", sites, empirical_freqs=True)
        for g, sites in (("g1", np.arange(1900)),
                         ("g2", np.arange(1900, 3900)))])

    def traversed():
        inst = PhyloInstance(data, dtype=jnp.float32)
        (eng,) = inst.engines.values()
        assert eng.B == 31
        tree = inst.random_tree(2)
        lnl = inst.evaluate(tree, full=True)
        flat = tree.flat_full_traversal(tree.centroid_branch())
        return lnl, np.asarray(eng.clv), eng._fast_structure(flat).profile

    lnl, clv, profile = traversed()
    assert profile[-1][0] == "e", profile          # a one-entry tail ran
    monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 8 * 128)
    lnl_pieces, clv_pieces, _ = traversed()
    assert np.isfinite(lnl) and lnl == lnl_pieces
    assert np.array_equal(clv, clv_pieces)


@pytest.mark.parametrize("blocks,shards,pieces", [
    (31, 1, 4), (24, 1, 3), (31, 4, 1), (64, 4, 2), (5, 1, 1)])
def test_take_row_is_the_row_in_every_number_of_pieces(monkeypatch, blocks,
                                                       shards, pieces):
    """`kernels.take_row` is `arena[i][None]`, bit for bit, whatever
    number of pieces it takes (8 blocks a piece here): pieces that do
    not divide the blocks (31 in four, the last overlapping the third),
    pieces that do (24 in three), and pieces counted from a SHARD's
    blocks (31 blocks over four shards is 8 a shard: one piece, the
    gather that indexes no block; 64 over four, two)."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels
    monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 8 * 128)
    rng = np.random.default_rng(blocks)
    arena = jnp.asarray(rng.standard_normal((3, blocks, 128, 4, 20)),
                        jnp.float32)
    fn = jax.jit(lambda a, i: kernels.take_row(a, i, shards))
    for i in (0, 2):
        got = fn(arena, jnp.int32(i))
        assert np.array_equal(np.asarray(got), np.asarray(arena[i][None]))
    (gather,) = [e for e in jax.make_jaxpr(
        lambda a: kernels.take_row(a, jnp.int32(1), shards))(arena).eqns
        if e.primitive.name == "gather"]
    # two halves of the rate axis a piece; a block start only in pieces
    assert gather.invars[1].aval.shape == (2 * pieces,
                                           2 if pieces == 1 else 3)
