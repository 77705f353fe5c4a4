"""Multi-device correctness: 1-device vs 8-virtual-device bit compares.

The reference is rank-count-invariant by construction — every rank holds
the whole tree and only sites are distributed, so lnL and derivatives
must not depend on the process count (`communication.c:120-182`,
deterministic-reduction note `makenewzGenericSpecial.c:1241-1248`).
These tests pin the same property on a `jax.sharding.Mesh`: an 8-way
site-sharded instance must reproduce the unsharded instance's
likelihoods, Newton-Raphson derivatives, optimized branch lengths, and a
full SPR search cycle on the 8 virtual CPU devices provisioned by
conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import load_alignment
from examl_tpu.parallel.sharding import (default_site_sharding,
                                         fabric_sharding, make_fabric_mesh,
                                         make_mesh, site_sharding)

from tests.conftest import TESTDATA

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 (virtual) devices")
# ~6 min of 8-virtual-device programs on testData/49 on one CPU: slow
# tier (the driver's dryrun_multichip covers the sharded path in CI
# cadence).  The gradient-pass cases at the end of the file are tier-1.
slow = pytest.mark.slow


@pytest.fixture(scope="module")
def data49():
    return load_alignment(f"{TESTDATA}/49", f"{TESTDATA}/49.model")


@pytest.fixture(scope="module")
def tree49_text():
    with open(f"{TESTDATA}/49.tree") as f:
        return f.read()


@pytest.fixture(scope="module")
def pair49(data49):
    """(unsharded, 8-way sharded) instances, built ONCE for the module:
    instances are tree-agnostic (the tree is a per-call argument and
    every test starts with a fresh tree + full evaluate), so sharing
    them drops the repeated engine construction/compile cost that
    dominated this battery's wall time."""
    sh = default_site_sharding(8)
    inst1 = PhyloInstance(data49)
    inst8 = PhyloInstance(data49, block_multiple=8, sharding=sh)
    return inst1, inst8


def _pair_trees(pair, text):
    inst1, inst8 = pair
    return (inst1, inst1.tree_from_newick(text),
            inst8, inst8.tree_from_newick(text))


@slow
def test_sharded_lnl_matches_unsharded(pair49, tree49_text):
    inst1, tree1, inst8, tree8 = _pair_trees(pair49, tree49_text)
    lnl1 = inst1.evaluate(tree1, full=True)
    lnl8 = inst8.evaluate(tree8, full=True)
    # Same math, different block padding/summation grouping: f64 agreement
    # far below any decision threshold of the search.
    assert lnl8 == pytest.approx(lnl1, rel=1e-12, abs=1e-7)
    # Verify the CLV tensor really is distributed over 8 devices.
    eng = next(iter(inst8.engines.values()))
    assert len(eng.clv.sharding.device_set) == 8


@slow
def test_sharded_derivatives_match(pair49, tree49_text):
    inst1, tree1, inst8, tree8 = _pair_trees(pair49, tree49_text)
    inst1.evaluate(tree1, full=True)
    inst8.evaluate(tree8, full=True)
    for (inst, tree) in ((inst1, tree1), (inst8, tree8)):
        p = tree.nodep[tree.ntips + 3]
        inst.new_view(tree, p)
        inst.new_view(tree, p.back)
    p1 = tree1.nodep[tree1.ntips + 3]
    p8 = tree8.nodep[tree8.ntips + 3]
    d1 = []
    for inst, p in ((inst1, p1), (inst8, p8)):
        eng = next(iter(inst.engines.values()))
        st = eng.make_sumtable(p.number, p.back.number)
        d1.append(eng.branch_derivatives(st, p.z))
    (a1, a2), (b1, b2) = d1
    np.testing.assert_allclose(a1, b1, rtol=1e-9)
    np.testing.assert_allclose(a2, b2, rtol=1e-9)


@slow
def test_sharded_newton_branch_matches(pair49, tree49_text):
    inst1, tree1, inst8, tree8 = _pair_trees(pair49, tree49_text)
    inst1.evaluate(tree1, full=True)
    inst8.evaluate(tree8, full=True)
    z1 = inst1.makenewz(tree1, tree1.nodep[5], tree1.nodep[5].back,
                        tree1.nodep[5].z, maxiter=16)
    z8 = inst8.makenewz(tree8, tree8.nodep[5], tree8.nodep[5].back,
                        tree8.nodep[5].z, maxiter=16)
    np.testing.assert_allclose(z1, z8, rtol=1e-10)


@slow
def test_sharded_spr_cycle(pair49, tree49_text):
    """One lazy SPR rearrangement cycle must pick the same moves sharded."""
    from examl_tpu.search.raxml_search import tree_optimize_rapid
    from examl_tpu.search.snapshots import BestList, InfoList
    from examl_tpu.search.spr import SprContext

    inst1, tree1, inst8, tree8 = _pair_trees(pair49, tree49_text)
    out = []
    for inst, tree in ((inst1, tree1), (inst8, tree8)):
        inst.evaluate(tree, full=True)
        ctx = SprContext(inst)
        bt = BestList(20)
        ilist = InfoList(50)
        tree_optimize_rapid(inst, tree, ctx, 1, 5, bt, None, ilist)
        inst.evaluate(tree, full=True)
        out.append((inst.likelihood, tree.to_newick(
            inst.alignment.taxon_names, with_lengths=False)))
    (l1, n1), (l8, n8) = out
    assert n1 == n8, "sharded SPR cycle chose a different topology"
    assert l8 == pytest.approx(l1, rel=1e-10, abs=1e-5)


@slow
def test_mesh_shapes():
    mesh = make_mesh(n_devices=8)
    sh = site_sharding(mesh)
    assert sh.num_devices == 8


@slow
def test_cli_auto_shards_over_devices(tmp_path):
    """The CLI shards the site axis over every visible device by default
    (the reference's mpirun -np N surface) and the result matches a
    --single-device run."""
    import re

    from examl_tpu.cli.main import main as cli_main
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile

    rng = np.random.default_rng(7)
    cur = rng.integers(0, 4, 600)
    seqs = []
    for _ in range(12):
        flip = rng.random(600) < 0.2
        cur = np.where(flip, rng.integers(0, 4, 600), cur)
        seqs.append("".join("ACGT"[c] for c in cur))
    data = build_alignment_data([f"t{i}" for i in range(12)], seqs)
    write_bytefile(str(tmp_path / "a.binary"), data)
    inst = PhyloInstance(data)
    t = inst.random_tree(seed=3)
    (tmp_path / "start.nwk").write_text(t.to_newick(data.taxon_names))

    def run(extra, tag):
        wd = str(tmp_path / tag)
        rc = cli_main(["-s", str(tmp_path / "a.binary"), "-t",
                       str(tmp_path / "start.nwk"), "-n", tag, "-f", "e",
                       "-w", wd] + extra)
        assert rc == 0
        info = open(f"{wd}/ExaML_info.{tag}").read()
        m = re.findall(r"Likelihood tree 0: (-[\d.]+)", info)
        return float(m[0]), info

    lnl_multi, info_multi = run([], "MULTI")
    assert "sharded over 8 devices" in info_multi
    lnl_single, _ = run(["--single-device"], "SINGLE")
    assert lnl_multi == pytest.approx(lnl_single, abs=2e-4)


# -- the whole-tree gradient pass on site-sharded arenas (tier-1) -------------
# Seeded data from the benchmark's own generator (self-contained NumPy),
# small enough for the CPU: 4 of the 8 virtual devices as the 1-D mesh a
# four-chip host gets by default, and as the `4x1` fabric.

MESHES = {
    "mesh4": lambda: site_sharding(make_mesh(n_devices=4)),
    "fabric4x1": lambda: fabric_sharding(make_fabric_mesh(4, 1)),
}
# (taxa, distinct patterns): neither count fills a multiple of 4 blocks
# of 128, so the last shards hold zero-weight padding blocks
SIZES = {"DNA": (12, 700), "AA": (8, 600)}


def _seeded_problem(datatype):
    """(AlignmentData, the matrix of distinct patterns it was built from
    [ntaxa, patterns], a Newick string of a random tree with lengths)."""
    from benchmarks import datagen
    from examl_tpu.io.alignment import build_alignment_data
    ntaxa, nsites = SIZES[datatype]
    alphabet = datagen.ALPHABETS[datatype]
    K = len(alphabet)
    rng = np.random.default_rng([30, K])
    adj, lengths = datagen.random_tree(rng, ntaxa)
    freqs = rng.dirichlet(np.full(K, 20.0))
    rates = rng.uniform(0.5, 3.0, K * (K - 1) // 2)
    mat = datagen.alignment(rng, adj, lengths, ntaxa, nsites, rates, freqs,
                            0.7)
    names = [f"t{i}" for i in range(ntaxa)]
    data = build_alignment_data(
        names, ["".join(alphabet[c] for c in row) for row in mat],
        datatype_name=datatype)
    tree = PhyloInstance(data).random_tree(seed=3)
    return data, mat, tree.to_newick(names)


@pytest.fixture(scope="module", params=sorted(SIZES))
def problem(request):
    data, mat, newick = _seeded_problem(request.param)
    return data, mat, newick, PhyloInstance(data)


@pytest.fixture(params=sorted(MESHES))
def sharded(request, problem):
    """(one-device instance, site-sharded instance, state matrix,
    Newick) for one datatype on one mesh."""
    from examl_tpu import obs
    data, mat, newick, inst1 = problem
    obs.reset()
    inst4 = PhyloInstance(data, block_multiple=4,
                          sharding=MESHES[request.param]())
    (eng,) = inst4.engines.values()
    assert len(eng.clv.sharding.device_set) == 4
    return inst1, inst4, mat, newick


def test_sharded_whole_tree_gradients_match(sharded):
    """`whole_tree_gradients` on site-sharded arenas against the
    one-device engine and against the benchmark's plain f64 reference.
    f64 here, so the two engines differ only by the order of the site
    sums (a shard's sum, then the all-reduce): 1e-9 relative.  The
    reference computes P(t) by `expm` where the engine uses its eigen
    decomposition: 1e-6 relative on d1 and d2 (absolute 1e-6 of the
    largest entry for derivatives near nought)."""
    from benchmarks import reference
    from examl_tpu.optimize.branch import (grad_smooth_ineligible,
                                           tree_gradients)
    inst1, inst4, mat, newick = sharded
    assert grad_smooth_ineligible(inst4) is None
    out = []
    for inst in (inst1, inst4):
        tree = inst.tree_from_newick(newick)
        inst.evaluate(tree, full=True)
        out.append(tree_gradients(inst, tree))
    (slots, a1, a2), (_, b1, b2) = out
    np.testing.assert_allclose(b1, a1, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(b2, a2, rtol=1e-9, atol=1e-9)
    (m,) = inst4.models
    edges = [(s.number, s.back.number, float(s.z[0])) for s in slots]
    _, r1, r2 = reference.evaluate(mat, None, edges, mat.shape[0],
                                   m.rates, m.freqs, float(m.alpha))
    np.testing.assert_allclose(b1[:, 0], r1, rtol=1e-6,
                               atol=1e-6 * np.abs(r1).max())
    np.testing.assert_allclose(b2[:, 0], r2, rtol=1e-6,
                               atol=1e-6 * np.abs(r2).max())


def test_sharded_engine_takes_the_wave_cap_from_a_shards_row(sharded,
                                                             monkeypatch):
    """The outroot step width follows the bytes a row holds in the
    program that runs: inside the `shard_map` a chip sees its shard's
    blocks, a quarter of the engine's, and `grad_wave_cap` asks with
    those.  With b1 blocks to the threshold's row, an engine of
    4 x b1 - 4 blocks takes eight entries a step on four shards (a
    shard's row is one block short) where one device takes one; at
    4 x b1 the shards take one too."""
    from examl_tpu.ops import gradient
    from examl_tpu.optimize.branch import tree_gradients
    _, inst4, _, newick = sharded
    (eng,) = inst4.engines.values()
    seen = []
    impl = eng._grad_impl

    def recording(clv, *rest):
        seen.append(clv.shape[1] * clv.shape[2])
        return impl(clv, *rest)

    monkeypatch.setattr(eng, "_grad_impl", recording)
    tree = inst4.tree_from_newick(newick)
    inst4.evaluate(tree, full=True)
    tree_gradients(inst4, tree)
    assert seen == [eng.B * eng.lane // 4]        # traced once, a shard's
    block = eng.lane * eng.R * eng.K * np.dtype(eng.dtype).itemsize
    assert eng.grad_wave_cap() == gradient.wave_cap(
        seen[0] // eng.lane * block) == 8
    b1 = -(-gradient.ONE_ENTRY_ROW_BYTES // block)
    monkeypatch.setattr(eng, "B", 4 * b1 - 4)
    assert eng.grad_wave_cap() == gradient.wave_cap((b1 - 1) * block) == 8
    monkeypatch.setattr(eng, "B", 4 * b1)
    assert eng.grad_wave_cap() == gradient.wave_cap(b1 * block) == 1
    monkeypatch.setattr(eng, "B", 4 * b1 - 4)
    monkeypatch.setattr(eng, "sharding", None)
    assert eng.grad_wave_cap() == 1


def test_sharded_gradients_read_by_index_bitwise(problem, monkeypatch):
    """The mapped pass reads a shard's wide rows by index
    (`kernels.take_rows`, a loop of dynamic slices inside the
    `shard_map`): d1 and d2 after the all-reduce equal, bit for bit,
    those of a sharded engine that gathers them."""
    from examl_tpu.ops import kernels
    from examl_tpu.optimize.branch import tree_gradients
    data, _, newick, _ = problem
    got = []
    for one_piece in (0, kernels.ONE_PIECE_SITES):
        monkeypatch.setattr(kernels, "ONE_PIECE_SITES", one_piece)
        inst = PhyloInstance(data, block_multiple=4,
                             sharding=MESHES["mesh4"]())
        tree = inst.tree_from_newick(newick)
        inst.evaluate(tree, full=True)
        got.append(tree_gradients(inst, tree)[1:])
    (a1, a2), (b1, b2) = got
    assert np.isfinite(a1).all() and np.abs(a1).max() > 0
    assert np.array_equal(a1, b1) and np.array_equal(a2, b2)


def test_sharded_traversal_reads_by_index_bitwise(problem, monkeypatch):
    """The chunk program under GSPMD reads child rows and scalers by
    index (`fastpath.chunk_applier` through `kernels.take_rows`) where
    the global row is wider than one piece: the sharded arena, its
    scalers and lnL equal, bit for bit, those of a sharded engine that
    gathers them."""
    from examl_tpu.ops import kernels
    data, _, newick, _ = problem
    got = []
    for one_piece in (0, kernels.ONE_PIECE_SITES):
        monkeypatch.setattr(kernels, "ONE_PIECE_SITES", one_piece)
        inst = PhyloInstance(data, block_multiple=4,
                             sharding=MESHES["mesh4"]())
        (eng,) = inst.engines.values()
        lnl = inst.evaluate(inst.tree_from_newick(newick), full=True)
        assert eng._dispatch_tier(True) == "chunk"
        assert len(eng.clv.sharding.device_set) == 4
        got.append((lnl, np.asarray(eng.clv), np.asarray(eng.scaler)))
    (lnl_a, clv_a, sc_a), (lnl_b, clv_b, sc_b) = got
    assert np.isfinite(lnl_a) and lnl_a == lnl_b
    assert np.array_equal(clv_a, clv_b) and np.array_equal(sc_a, sc_b)


def test_sharded_tree_evaluate_makes_gradient_passes(sharded):
    """`tree_evaluate` on a site-sharded instance smooths with
    whole-tree gradient passes (O(1) dispatches a sweep, no fallback, no
    per-branch Newton) and leaves lnL and every z where the one-device
    run leaves them."""
    from examl_tpu import obs
    from examl_tpu.optimize.branch import tree_evaluate
    inst1, inst4, _, newick = sharded
    tree1 = inst1.tree_from_newick(newick)
    inst1.evaluate(tree1, full=True)
    lnl1 = tree_evaluate(inst1, tree1, 1.0)
    tree4 = inst4.tree_from_newick(newick)
    inst4.evaluate(tree4, full=True)
    before = {k: obs.counter(k) for k in (
        "engine.grad_pass_dispatches", "optimize.grad_smooth_fallbacks",
        "engine.collectives", "engine.dispatch_count")}
    lnl4 = tree_evaluate(inst4, tree4, 1.0)
    rose = {k: obs.counter(k) - v for k, v in before.items()}
    passes = rose["engine.grad_pass_dispatches"]
    assert passes > 0 and rose["optimize.grad_smooth_fallbacks"] == 0
    # a sweep is one traversal and one gradient pass; then one evaluation
    assert rose["engine.dispatch_count"] == 2 * passes + 1
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["engine.dispatches_per_smoothing_round"] == 2
    # one all-reduce a gradient pass and one for the closing lnL, read
    # from the compiled programs' own text; none of them in a loop, so
    # the text's count is the count that ran
    assert rose["engine.collectives"] == passes + 1
    from examl_tpu.obs import programs
    rows = [r for r in programs.table() if r.get("collective_total")]
    assert {r["family"] for r in rows} >= {"grad"}, rows
    assert all(r["collectives_in_loops"] == 0 for r in rows), rows
    assert lnl4 == pytest.approx(lnl1, rel=1e-10)
    z1 = [p.z[0] for p, _ in tree1.all_branches()]
    z4 = [p.z[0] for p, _ in tree4.all_branches()]
    np.testing.assert_allclose(z4, z1, rtol=1e-7, atol=1e-9)


def test_padding_in_the_last_shard_changes_no_lnl(sharded):
    """The pattern count is no multiple of 4 x 128: `block_multiple`
    rounds the packed blocks up with zero-weight ones, which land in the
    last shards, and every site is still counted once."""
    inst1, inst4, _, newick = sharded
    (e1,), (e4,) = inst1.engines.values(), inst4.engines.values()
    true = int(e4.bucket.part_widths.sum())
    assert true % (4 * 128) and e4.B % 4 == 0 and e4.B > e1.B
    t1, t4 = (i.tree_from_newick(newick) for i in (inst1, inst4))
    assert inst4.evaluate(t4, full=True) == pytest.approx(
        inst1.evaluate(t1, full=True), rel=1e-12)


@pytest.mark.parametrize("where,in_loops", [
    ("after", 0), ("inside", 1), ("inside_cond", 1)])
def test_collectives_in_loops_sees_an_all_reduce_in_a_loop(where, in_loops):
    """`engine.collectives` counts the collectives in a compiled
    program's text a dispatch.  That is what ran only while none sits in
    a loop, and `obs.programs.collectives_in_loops` is what says so: a
    site sum reduced after a loop, inside its body, and inside a
    conditional the body calls, each ONE all-reduce in the text."""
    from jax.sharding import PartitionSpec as P

    from examl_tpu.obs.programs import (collective_census,
                                        collectives_in_loops)
    from examl_tpu.parallel.sharding import SITE_AXIS

    def site_sum(x, i):
        return jax.lax.psum(jnp.sin(x * i).sum(), SITE_AXIS)

    def impl(x):
        def body(i, acc):
            if where == "inside":
                return acc + site_sum(x, i)
            if where == "inside_cond":
                return acc + jax.lax.cond(i % 2 == 0,
                                          lambda: site_sum(x, i),
                                          lambda: jnp.zeros((), x.dtype))
            return acc + jnp.sin(x * i).sum()
        acc = jax.lax.fori_loop(0, 5, body, jnp.zeros((), x.dtype))
        return jax.lax.psum(acc, SITE_AXIS) if where == "after" else acc

    mesh = make_mesh(n_devices=4)
    compiled = jax.jit(jax.shard_map(
        impl, mesh=mesh, in_specs=P(SITE_AXIS), out_specs=P(),
        check_vma=False)).lower(jnp.ones((8, 16))).compile()
    text = compiled.as_text()
    assert collective_census(compiled) == {"all-reduce": 1}
    assert " while(" in text
    assert collectives_in_loops(text) == in_loops


@pytest.mark.parametrize("case,reason", [
    ("save_memory", "-S SEV pools"),
    ("multi_process", "multi-process meshes"),
    ("tree_slices", "fabric with tree slices"),
])
def test_gradient_pass_still_refused_with_its_reason(case, reason,
                                                     monkeypatch):
    """What keeps the per-branch Newton path, each by name: -S pools, a
    mesh over several processes (faked: no multi-host run exists yet),
    a fabric whose tree axis is wider than one."""
    from examl_tpu.optimize.branch import grad_smooth_ineligible
    data, _, _ = _seeded_problem("DNA")
    if case == "save_memory":
        inst = PhyloInstance(data, block_multiple=4, save_memory=True,
                             sharding=MESHES["mesh4"]())
    elif case == "multi_process":
        inst = PhyloInstance(data, block_multiple=4,
                             sharding=MESHES["mesh4"]())
        monkeypatch.setattr(jax, "process_count", lambda: 2)
    else:
        inst = PhyloInstance(data, block_multiple=2,
                             sharding=fabric_sharding(
                                 make_fabric_mesh(2, 2)))
    assert reason in grad_smooth_ineligible(inst)
