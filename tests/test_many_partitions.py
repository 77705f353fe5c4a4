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


# -- the one-entry tail's P built before its scan (fastpath.chunk_applier) ---

# genes of one, two and three 128-lane blocks in turn
GENE_WIDTHS = (90, 200, 300)


def _packed(datatype, m, dtype=None, block_multiple=4, ntaxa=24):
    """(instance, alignment) of `m` random genes of 1-3 blocks each,
    packed to a multiple of `block_multiple` blocks (the trailing
    padding blocks carry the last gene's id), each gene with its own
    empirical frequencies and its own alpha."""
    import jax.numpy as jnp

    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.partitions import PartitionSpec
    from examl_tpu.models.gtr import with_alpha
    alphabet = {"AA": "ARNDCQEGHILKMFPSTWYV", "DNA": "ACGT"}[datatype]
    widths = [GENE_WIDTHS[k % 3] for k in range(m)]
    ends = np.cumsum(widths)
    rng = np.random.default_rng(m)
    names = [f"t{i}" for i in range(ntaxa)]
    letters = np.array(list(alphabet))
    seqs = ["".join(letters[rng.integers(0, len(alphabet), ends[-1])])
            for _ in names]
    data = build_alignment_data(names, seqs, [
        PartitionSpec(f"g{k}", datatype,
                      "GTR" if datatype == "DNA" else "LG",
                      np.arange(e - w, e), empirical_freqs=True)
        for k, (w, e) in enumerate(zip(widths, ends))])
    inst = PhyloInstance(data, dtype=dtype or jnp.float64,
                         block_multiple=block_multiple)
    inst.models[:] = [with_alpha(model, 0.3 + 0.25 * k)
                      for k, model in enumerate(inst.models)]
    inst.push_models()
    return inst, data


def _site_rel_err(got, want):
    """Largest difference of CLV entries [..., R, K] over their site's
    largest entry: f32 rounding in P's small, cancelling entries moves
    a tiny likelihood by a large share of itself and its site by
    nothing."""
    scale = np.abs(want).max(axis=(-2, -1), keepdims=True)
    return float((np.abs(got - want) / np.where(scale > 0, scale, 1)).max())


def _random_lengths(tree):
    """The tree with a branch length of its own on every branch, so a
    step that took another entry's or the other child's matrices would
    show."""
    rng = np.random.default_rng(43)
    for p, _ in tree.all_branches():
        p.z[:] = [float(rng.uniform(0.2, 0.95))]
    return tree


def _tail_structure(inst):
    """(engine, tree, its full traversal, the structure planned with the
    one-entry tail, the kinds of the tail's entries).  A full
    traversal's cherries are all in its first wave, so the tail holds
    kinds 1 and 2 only."""
    from examl_tpu.ops import fastpath
    (eng,) = inst.engines.values()
    tree = _random_lengths(inst.random_tree(3))
    flat = tree.flat_full_traversal(tree.centroid_branch())
    st = fastpath.build_structure(flat, inst.alignment.ntaxa,
                                  fastpath.ONE_ENTRY_ROW_BYTES)
    assert st.profile[-1][0] == "e", st.profile
    L = st.profile[-1][1]
    kinds = ((np.asarray(st.lidx)[-L:] >= 0).astype(int)
             + (np.asarray(st.ridx)[-L:] >= 0))
    return eng, tree, flat, st, set(kinds.tolist())


def _both_forms(datatype, m, dtype):
    """A full traversal's arena rows and scalers, each site's CLV over
    its largest entry (the f32 and f64 scale thresholds differ), by the
    unrolled reference `run_chunks`, whose steps build their own P, and
    by the segment program, whose one-entry tail takes P built before
    its scan."""
    import jax.numpy as jnp

    from examl_tpu.ops import fastpath
    inst, _ = _packed(datatype, m, dtype)
    eng, tree, flat, st, kinds = _tail_structure(inst)
    assert kinds == {1, 2} and eng.num_parts == m and eng.B % 4 == 0
    bp = np.asarray(eng.block_part)
    assert bp[-1] == m - 1 and (bp == m - 1).sum() > -(-GENE_WIDTHS[
        (m - 1) % 3] // 128)                 # trailing padding blocks
    zl, zr = fastpath.refresh_z(st, flat, eng.num_branch_slots, eng.dtype)
    apply = fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                   eng.scale_exp, eng.fast_precision)
    assert apply.tail_p is not None
    rows = np.sort(st.row_of[st.row_of >= 0])
    out = []
    for c, s in (fastpath.run_chunks(
            eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
            jnp.array(eng.scaler), fastpath.structure_chunks(st, zl, zr),
            eng.scale_exp, eng.fast_precision),
                 fastpath.run_segments(
            st.profile, st.base, st.lidx, st.ridx, st.lcode, st.rcode, zl,
            zr, jnp.array(eng.clv), jnp.array(eng.scaler), apply)):
        c = np.asarray(c)[rows].astype(np.float64)
        out += [c / np.abs(c).max(axis=(-2, -1), keepdims=True),
                np.asarray(s)[rows]]
    return out


@pytest.mark.parametrize("m", [2, 7])
@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_tail_p_built_before_the_scan_is_each_steps_own(datatype, m):
    """With M > 1 models the chunk program's one-entry tail takes each
    step's transition matrices from P built for all its entries before
    the scan; the unrolled reference `run_chunks` builds them in the
    step, with the same einsum (`kernels.p_matrices_wave`).  Over
    genes of one to three blocks and trailing padding blocks, in f64
    the two are one arena to 1e-12 of a site, and in f32 they leave the
    same scalers and an arena no further from the f64 one than twice
    the reference's own distance (f32 rounding: an einsum over a batch
    of entries sums in another order than over one; 3.6e-6 to 1.5e-4
    of a site's largest entry here, which either form reaches within a
    tenth; a gene's model on another gene's blocks moves a site by
    percents); with one model there is nothing to build before the
    scan."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import fastpath
    ref, _, new, _ = _both_forms(datatype, m, jnp.float64)
    assert _site_rel_err(new, ref) < 1e-12
    ref32, s1, new32, s2 = _both_forms(datatype, m, jnp.float32)
    assert np.array_equal(s1, s2) and not np.array_equal(ref32, new32)
    assert _site_rel_err(new32, ref) <= 2 * _site_rel_err(ref32, ref)
    inst, _ = _packed(datatype, 1, jnp.float32)
    (eng,) = inst.engines.values()
    assert fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                  eng.scale_exp,
                                  eng.fast_precision).tail_p is None


@pytest.mark.parametrize("m", [2, 7])
@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_every_gene_through_the_one_entry_tail_against_the_oracle(
        datatype, m, monkeypatch):
    """The engine's own dispatch with the one-entry tail planned (its
    row threshold forced down to these small rows): every gene's lnL,
    f64, against `tests/oracle.py` to 1e-9, as in the cell-shaped test
    above, and `engine.grouped_dispatches` counts the dispatch."""
    from examl_tpu.ops import fastpath
    monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", 1)
    obs.reset()
    inst, data = _packed(datatype, m)
    (eng,) = inst.engines.values()
    assert obs.registry().snapshot()["gauges"]["engine.model_groups"] == m
    tree = _random_lengths(inst.random_tree(3))
    total = inst.evaluate(tree, full=True)
    flat = tree.flat_full_traversal(tree.centroid_branch())
    assert eng._fast_structure(flat).profile[-1][0] == "e"
    assert obs.counter("engine.grouped_dispatches") == 1
    got = np.asarray(inst.per_partition_lnl, dtype=np.float64)
    want = np.array(_oracle_parts(inst, data, tree))
    np.testing.assert_allclose(got, want, rtol=1e-9)
    assert total == pytest.approx(want.sum(), rel=1e-9)


def test_one_model_counts_no_groups(monkeypatch):
    """One partition: the gauge reads 0 and no dispatch counts."""
    from examl_tpu.ops import fastpath
    monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", 1)
    obs.reset()
    inst, _ = _packed("DNA", 1)
    assert obs.registry().snapshot()["gauges"]["engine.model_groups"] == 0
    inst.evaluate(inst.random_tree(3), full=True)
    assert obs.counter("engine.grouped_dispatches") == 0


@pytest.mark.parametrize("nprocs", [1, 2, 4])
def test_packed_block_part_is_non_decreasing(nprocs):
    """`pack_partitions` lays each partition out as whole contiguous
    blocks in order, and `pack_partitions_local` each process's window
    of that layout: every block_part is non-decreasing, each gene's
    blocks one run, and the trailing padding blocks carry the last
    gene's id."""
    import dataclasses

    from examl_tpu.parallel.packing import (pack_partitions,
                                            pack_partitions_local)
    _, data = _packed("AA", 7)
    parts = data.partitions
    (glob,) = pack_partitions(parts, block_multiple=4).values()
    bp = glob.block_part
    assert (np.diff(bp) >= 0).all() and bp[-1] == len(parts) - 1
    assert sorted(set(bp.tolist())) == list(range(len(parts)))
    B = bp.shape[0]
    lane = glob.lane
    windows = []
    for procid in range(nprocs):
        s0 = procid * B // nprocs * lane
        s1 = (procid + 1) * B // nprocs * lane
        sliced = []
        for k, part in enumerate(parts):
            off = int(glob.part_offsets[k])
            lo = min(max(s0 - off, 0), part.width)
            hi = min(max(s1 - off, 0), part.width)
            sliced.append(dataclasses.replace(
                part, patterns=part.patterns[:, lo:hi],
                weights=part.weights[lo:hi], global_width=part.width,
                global_col_offset=lo))
        (loc,) = pack_partitions_local(sliced, procid, nprocs,
                                       block_multiple=4).values()
        assert (np.diff(loc.block_part) >= 0).all()
        windows.append(loc.block_part)
    assert np.array_equal(np.concatenate(windows), bp)


@pytest.mark.parametrize("width", [1, 8])
@pytest.mark.parametrize("kind", [0, 1, 2])
@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_a_step_given_its_p_is_the_step_building_it(datatype, kind, width):
    """`chunk_applier`'s kernel of every kind, at one entry and at
    eight, on 7 genes of 1-3 blocks with trailing padding blocks: the
    rows and scalers it computes from the children's P handed to it
    (`tail_p`, as the one-entry tail's steps take theirs) are those it
    computes building P itself, to f32 rounding and exactly."""
    import jax.numpy as jnp

    from examl_tpu.ops import fastpath
    inst, _ = _packed(datatype, 7, jnp.float32)
    (eng,) = inst.engines.values()
    ntips = inst.alignment.ntaxa
    inst.evaluate(inst.random_tree(3), full=True)   # rows to read
    rng = np.random.default_rng(kind * 10 + width)
    rows = jnp.asarray(rng.integers(0, ntips - 2, (2, width)), jnp.int32)
    tips_ = jnp.asarray(rng.integers(0, ntips, (2, width)), jnp.int32)
    z = jnp.asarray(rng.uniform(0.2, 0.95, (2, width, 1)), jnp.float32)
    ch = fastpath.FastChunk(kind, width, jnp.int32(0), rows[0], rows[1],
                            tips_[0], tips_[1], z[0], z[1])
    apply = fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                   eng.scale_exp, eng.fast_precision)
    v1, s1 = apply.values(eng.clv, eng.scaler, ch)
    v2, s2 = apply.values(eng.clv, eng.scaler, ch, apply.tail_p(z[0]),
                          apply.tail_p(z[1]))
    assert v2.shape == (width, eng.B, eng.lane, eng.R, eng.K)
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert _site_rel_err(np.asarray(v2), np.asarray(v1)) < 1e-5
