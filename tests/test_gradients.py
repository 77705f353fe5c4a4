"""One-pass analytic branch gradients (ops/gradient.py) and the
whole-tree gradient smoothing mode (optimize/branch.py, fleet).

The contract under test (ROADMAP §5 / ISSUE 12 acceptance):

* analytic d1 matches central finite differences of the engine's own
  lnL across the parity matrix (GAMMA, -M C>1, PSR);
* gradient-mode `tree_evaluate` reaches the per-branch-NR endpoint lnL
  within pinned tolerance, with O(1) dispatches per smoothing round
  (the `engine.dispatches_per_smoothing_round` gauge) instead of O(n);
* the gradient dispatch is bitwise-stable across sched-cache
  invalidation / SPR-commit seams (content-keyed plans);
* `EXAML_GRAD_SMOOTH=0` pins the per-branch reference path;
* the deep-recursion fix: `smooth_subtree`/`region_smooth` survive a
  caterpillar tree thousands of nodes deep (previously RecursionError);
* the fleet batched gradient step agrees per job with the sequential
  gradient smoother.
"""

import os
import sys

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.constants import SMOOTHINGS
from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data

from tests.conftest import correlated_dna


@pytest.fixture
def grad_on(monkeypatch):
    monkeypatch.setenv("EXAML_GRAD_SMOOTH", "")


@pytest.fixture
def grad_off(monkeypatch):
    monkeypatch.setenv("EXAML_GRAD_SMOOTH", "0")


def _partitioned_dna(ntaxa=10, width=100, seed=1):
    """Two-partition DNA (slow/fast) for the -M / C>1 arm."""
    import tempfile

    from examl_tpu.io.partitions import parse_partition_file
    rng = np.random.default_rng(seed)
    cur1 = rng.integers(0, 4, width)
    cur2 = rng.integers(0, 4, width)
    seqs = []
    for _ in range(ntaxa):
        cur1 = np.where(rng.random(width) < 0.05,
                        rng.integers(0, 4, width), cur1)
        cur2 = np.where(rng.random(width) < 0.35,
                        rng.integers(0, 4, width), cur2)
        seqs.append("".join("ACGT"[c]
                            for c in np.concatenate([cur1, cur2])))
    with tempfile.NamedTemporaryFile("w", suffix=".model",
                                     delete=False) as f:
        f.write(f"DNA, g1 = 1-{width}\n"
                f"DNA, g2 = {width + 1}-{2 * width}\n")
        mp = f.name
    try:
        specs = parse_partition_file(mp)
    finally:
        os.unlink(mp)
    return build_alignment_data([f"t{i}" for i in range(ntaxa)], seqs,
                                specs=specs)


def _psr_instance(ntaxa=10, sites=200, seed=3):
    data = correlated_dna(ntaxa, sites, seed=seed)
    inst = PhyloInstance(data, rate_model="PSR")
    rng = np.random.default_rng(0)
    for gid, part in enumerate(data.partitions):
        inst.per_site_rates[gid] = np.array([0.5, 1.0, 2.2])
        inst.rate_category[gid] = rng.integers(
            0, 3, len(inst.patrat[gid])).astype(np.int32)
    inst.push_site_rates()
    return inst


def _fd_check(inst, tree, edge_picks=(0, 3, -1), h=1e-6,
              rtol=5e-5):
    """Central finite differences of inst.evaluate vs analytic d1,
    per branch slot."""
    from examl_tpu.optimize.branch import tree_gradients
    from examl_tpu.utils import z_slots
    inst.evaluate(tree, full=True)
    slots, d1, d2 = tree_gradients(inst, tree)
    C = inst.num_branch_slots
    E = len(slots)
    for k in [p % E for p in edge_picks]:
        s = slots[k]
        z0 = list(s.z)
        for c in range(C):
            lz = float(np.log(z_slots(z0, C)[c]))
            zs = list(z0)
            zs[c if len(z0) == C else 0] = float(np.exp(lz + h))
            s.z[:] = zs
            tree.invalidate_all()
            lp = inst.evaluate(tree, full=True)
            zs[c if len(z0) == C else 0] = float(np.exp(lz - h))
            s.z[:] = zs
            tree.invalidate_all()
            lm = inst.evaluate(tree, full=True)
            s.z[:] = z0
            fd = (lp - lm) / (2 * h)
            assert float(d1[k, c]) == pytest.approx(
                fd, rel=rtol, abs=1e-3), (k, c, fd, d1[k, c])
    # curvature sanity: at least finite everywhere
    assert np.isfinite(d1).all() and np.isfinite(d2).all()


def test_gradients_match_fd_gamma():
    data = correlated_dna(12, 300)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=3)
    _fd_check(inst, tree)


def test_gradients_match_fd_per_partition_branches():
    data = _partitioned_dna()
    inst = PhyloInstance(data, per_partition_branches=True)
    assert inst.num_branch_slots == 2
    tree = inst.random_tree(seed=5)
    _fd_check(inst, tree, edge_picks=(0, 2))


def test_gradients_match_fd_psr():
    inst = _psr_instance()
    tree = inst.random_tree(seed=3)
    _fd_check(inst, tree)


def test_edge_count_and_root_edge():
    """E == 2n-3 edges, and edge 0 is the traversal's root edge."""
    from examl_tpu.optimize.branch import tree_gradients
    data = correlated_dna(9, 120)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=1)
    inst.evaluate(tree, full=True)
    slots, d1, _ = tree_gradients(inst, tree)
    assert len(slots) == 2 * 9 - 3 == d1.shape[0]
    p = tree.centroid_branch()
    assert slots[0] is p
    # every branch's z list appears exactly once
    assert len({id(s.z) for s in slots}) == len(slots)


# -- the gradient program's loops follow the tree's live slots ---------------

def _balanced_newick(names):
    if len(names) == 1:
        return names[0]
    h = len(names) // 2
    return f"({_balanced_newick(names[:h])},{_balanced_newick(names[h:])})"


def _shaped_newick(kind, n):
    """An n-taxon Newick string: maximally unbalanced, balanced, or None
    for the instance's own random tree."""
    if kind == "caterpillar":
        return _caterpillar_newick(n)
    if kind == "balanced":
        return _balanced_newick([f"t{i}" for i in range(n)]) + ";"
    return None


TREE_KINDS = ("caterpillar", "balanced", "random")


@pytest.mark.parametrize("kind", TREE_KINDS)
@pytest.mark.parametrize("cap,n_taxa", [(1, 41), (8, 41), (1, 140)])
def test_grad_structure_holds_live_slots_in_order(cap, n_taxa, kind):
    """`GradStructure` at the two outroot step widths the rule gives
    (`gradient.wave_cap`: 1 from 0.5 MiB a row, 8 under it): every entry
    sits in exactly one slot, an entry reads its `up_row` only after
    the step that wrote it (the two root rows are there before the
    loop), padding slots touch the scratch row alone, the edge loop has
    ceil(E / GRAD_CHUNK) chunks, and one entry a step means n steps
    whatever the topology, at 41 taxa and at the cells' 140."""
    from examl_tpu.ops import gradient
    from examl_tpu.tree.topology import Tree
    names = [f"t{i}" for i in range(n_taxa)]
    newick = _shaped_newick(kind, n_taxa)
    if newick is None:
        inst = PhyloInstance(correlated_dna(n_taxa, 40))
        newick = inst.random_tree(seed=11).to_newick(names)
    tree = Tree.from_newick(newick, names, 1)
    flat = tree.flat_full_traversal(tree.centroid_branch())
    gs = gradient.build_structure(flat, cap)
    n, E = n_taxa - 2, 2 * n_taxa - 3
    assert (gs.n, gs.n_edges) == (n, E)
    assert gs.wave_w <= cap and gs.pk.shape == (gs.n_steps, gs.wave_w)
    live = ~gs.pk_pad
    assert sorted(gs.pk[live]) == list(range(n))
    assert (gs.up_row[gs.pk_pad] == gs.scratch).all()
    assert (gs.lrow[gs.pk_pad] == gs.scratch).all()
    assert (gs.rrow[gs.pk_pad] == gs.scratch).all()
    written = {r - 1: -1 for r in gs.roots}       # row -> step written
    for t in range(gs.n_steps):
        for w in np.flatnonzero(live[t]):
            assert written[int(gs.up_row[t, w])] < t
        for w in np.flatnonzero(live[t]):
            for row in (int(gs.lrow[t, w]), int(gs.rrow[t, w])):
                assert row not in written
                written[row] = t
    assert len(written) == 2 * n + 2 and gs.scratch not in written
    assert gs.n_chunks == -(-E // gradient.GRAD_CHUNK) == {41: 3, 140: 9}[
        n_taxa]
    assert (~gs.edge_pad).sum() == E
    # an edge a written row, but one for the root edge's two
    assert sorted(gs.edge_x_row[~gs.edge_pad]) == \
        sorted(set(written) - {gs.roots[1] - 1})
    if cap == 1:
        assert gs.n_steps == n and live.all()
    else:
        from examl_tpu.utils import bucket_len
        assert gs.n_steps == bucket_len(gs.n_steps) >= -(-n // cap)


def _arm_instance(arm, ntaxa=14):
    if arm == "gamma":
        return PhyloInstance(correlated_dna(ntaxa, 260))
    if arm == "per_partition_branches":
        inst = PhyloInstance(_partitioned_dna(ntaxa=ntaxa),
                             per_partition_branches=True)
        assert inst.num_branch_slots == 2
        return inst
    return _psr_instance(ntaxa=ntaxa)


def _per_branch_derivatives(inst, tree, slot):
    """(d1, d2) [C] of one branch by the per-branch Newton path's own
    programs: both end views, `sumtable`, `nr_derivatives`."""
    inst.new_view(tree, slot)
    inst.new_view(tree, slot.back)
    d1 = d2 = 0.0
    for eng in inst.engines.values():
        st = eng.make_sumtable(slot.number, slot.back.number)
        e1, e2 = eng.branch_derivatives(st, slot.z)
        d1, d2 = d1 + np.asarray(e1, float), d2 + np.asarray(e2, float)
    return d1, d2


@pytest.mark.parametrize("kind", TREE_KINDS)
@pytest.mark.parametrize("arm", ["gamma", "per_partition_branches", "psr"])
def test_whole_tree_gradients_equal_at_every_wave_cap(arm, kind,
                                                      monkeypatch):
    """(d1, d2) of `whole_tree_gradients` with eight entries an outroot
    step, with one (the reversed waves laid end to end) and with one in
    another valid order (plain reversed post-order) are one result (f64
    here: 1e-12 of the largest derivative), and the per-branch
    `sumtable` / `nr_derivatives` path's on the branches checked: the
    step width and the order inside a wave move scratch work, not an
    entry's or an edge's arithmetic."""
    from examl_tpu.ops import gradient
    from examl_tpu.optimize.branch import tree_gradients
    from examl_tpu.tree.topology import FlatTraversal
    from examl_tpu.utils import next_pow2
    inst = _arm_instance(arm)
    ntaxa = len(inst.alignment.taxon_names)
    newick = _shaped_newick(kind, ntaxa)
    tree = (inst.tree_from_newick(newick) if newick
            else inst.random_tree(seed=7))
    rng = np.random.default_rng(5)
    for s in tree_gradients(inst, tree)[0]:       # distinct lengths
        s.z[:] = list(rng.uniform(0.6, 0.95, len(s.z)))
    tree.invalidate_all()
    widest = next_pow2(int(max(tree.flat_full_traversal(
        tree.centroid_branch()).wave_sizes)))
    assert widest >= 2
    build = gradient.build_structure

    def plain(flat, cap):
        """Every entry a wave of its own: plain reversed post-order."""
        return build(FlatTraversal(
            flat.parent, flat.left, flat.right, flat.zl, flat.zr,
            np.ones(flat.n, dtype=np.int64), flat.ntips), cap)

    got, orders = {}, {}
    for name, cap, builder in (("waves8", 8, build), ("waves1", 1, build),
                               ("plain1", 1, plain)):
        monkeypatch.setattr(gradient, "build_structure", builder)
        for eng in inst.engines.values():
            monkeypatch.setattr(eng, "grad_wave_cap", lambda cap=cap: cap)
            eng._grad_structs.clear()
        s0 = obs.counter("engine.grad_slots")
        l0 = obs.counter("engine.grad_live_slots")
        inst.evaluate(tree, full=True)
        slots, d1, d2 = tree_gradients(inst, tree)
        (gs,) = eng._grad_structs.values()
        assert gs.wave_w == min(cap, widest)
        E = 2 * ntaxa - 3
        assert obs.counter("engine.grad_live_slots") - l0 == \
            len(inst.engines) * (ntaxa - 2 + E)
        assert obs.counter("engine.grad_slots") - s0 == len(inst.engines) \
            * (gs.n_steps * gs.wave_w + gs.n_chunks * gradient.GRAD_CHUNK)
        if cap == 1:
            assert gs.n_steps == ntaxa - 2
        got[name], orders[name] = (d1, d2), gs.pk[~gs.pk_pad].tolist()
    assert orders["plain1"] == list(range(ntaxa - 3, -1, -1))
    if kind != "caterpillar":                     # its waves are entries
        assert orders["waves1"] != orders["plain1"]
    assert sorted(orders["waves8"]) == sorted(orders["waves1"])
    ref = got["waves8"]
    assert np.isfinite(ref[0]).all() and np.abs(ref[0]).max() > 0
    for name in ("waves1", "plain1"):
        for a, b in zip(got[name], ref):
            np.testing.assert_allclose(a, b, rtol=1e-12,
                                       atol=1e-12 * np.abs(b).max())
    for k in (0, 3, len(slots) - 1):
        r1, r2 = _per_branch_derivatives(inst, tree, slots[k])
        for d1, d2 in got.values():
            np.testing.assert_allclose(d1[k], r1, rtol=1e-9,
                                       atol=1e-9 * np.abs(ref[0]).max())
            np.testing.assert_allclose(d2[k], r2, rtol=1e-9,
                                       atol=1e-9 * np.abs(ref[1]).max())


@pytest.mark.parametrize("sites,cap", [
    (128, 8), (2_048, 8), (3_968, 8), (4_096, 1), (8_192, 1),
    (65_536, 1), (262_144, 1)])
def test_wave_cap_follows_the_rows_bytes(sites, cap):
    """One entry an outroot step from 0.5 MiB a row of the outroot arena
    (`gradient.ONE_ENTRY_ROW_BYTES`: the crossing the chip read, PERF.md
    section 6, PR 39), eight under it; the engine asks with its own
    arena's blocks x lanes x R x K values of its compute dtype, f64
    here, so 4,096 DNA sites are the chip's 8,192 in f32.  At one entry
    a step both loops run n + 32 x n_chunks slots a pass whatever the
    tree."""
    from examl_tpu.ops import gradient
    assert gradient.wave_cap(sites * 4 * 4 * 8) == cap
    assert gradient.wave_cap(8_192 * 4 * 4 * 4) == 1        # DNA, f32
    assert gradient.wave_cap(8_064 * 4 * 4 * 4) == 8
    assert gradient.wave_cap(1_664 * 4 * 20 * 4) == 1       # protein, f32
    assert gradient.wave_cap(1_536 * 4 * 20 * 4) == 8
    inst = PhyloInstance(correlated_dna(6, 60))
    (eng,) = inst.engines.values()
    assert (eng.R, eng.K, np.dtype(eng.dtype).itemsize) == (4, 4, 8)
    assert eng.grad_wave_cap() == 8               # one block of 128
    eng.B = sites // eng.lane
    assert eng.grad_wave_cap() == cap
    tree = inst.random_tree(seed=2)
    flat = tree.flat_full_traversal(tree.centroid_branch())
    gs = eng._grad_structure(flat)
    assert gs.wave_w <= cap
    if cap == 1:
        assert gs.n_steps * gs.wave_w + gs.n_chunks * gradient.GRAD_CHUNK \
            == flat.n + 32 * gs.n_chunks


def _spr_moved_newicks(ntaxa, trees, moves, seed):
    """`trees` topologies `moves` random SPR moves from one random tree
    (the benchmark's tree-set generator; tips t1..t<ntaxa>)."""
    from benchmarks import datagen
    rng = np.random.default_rng(seed)
    adj, _ = datagen.random_tree(rng, ntaxa)
    out = []
    for _ in range(trees):
        other = {n: list(v) for n, v in adj.items()}
        for _ in range(moves):
            datagen.spr_move(rng, other, ntaxa)
        out.append(datagen.newick(other, ntaxa))
    return out


def test_one_gradient_program_an_engine_whatever_the_tree():
    """From 0.5 MiB a row (24 taxa x 4,096 DNA patterns in f64 here) the
    gradient program's shapes hold nothing of the topology: four
    SPR-moved trees of one engine compile it ONCE, and every pass runs
    n + 32 x n_chunks slots.  Eight entries a step (a narrow row's
    width) packs four such trees of 140 taxa into different step
    counts: the programs a search used to meet uncompiled."""
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.ops import gradient
    from examl_tpu.optimize.branch import tree_gradients
    ntaxa = 24
    rng = np.random.default_rng(3)
    names = [f"t{i + 1}" for i in range(ntaxa)]
    seqs = ["".join("ACGT"[c] for c in rng.integers(0, 4, 4096))
            for _ in names]
    inst = PhyloInstance(build_alignment_data(names, seqs))
    (eng,) = inst.engines.values()
    assert eng.B * eng.lane == 4096 and eng.grad_wave_cap() == 1

    def grad_compiles():
        t = obs.registry().snapshot()["timers"]
        return t.get("engine.compile_seconds.grad", {"count": 0})["count"]

    newicks = _spr_moved_newicks(ntaxa, 4, 5, seed=39)
    c0, s0 = grad_compiles(), obs.counter("engine.grad_slots")
    keys = set()
    for nw in newicks:
        tree = inst.tree_from_newick(nw)
        inst.evaluate(tree, full=True)
        _, d1, _ = tree_gradients(inst, tree)
        assert np.isfinite(d1).all()
        flat = tree.flat_full_traversal(tree.centroid_branch())
        keys.add(flat.topo_key)
    n_chunks = -(-(2 * ntaxa - 3) // gradient.GRAD_CHUNK)
    assert len(keys) == 4                         # four topologies
    assert grad_compiles() - c0 == 1
    assert [k for k in eng._fast_jit_cache if k[0] == "grad"] == \
        [("grad", ntaxa - 2, 1, n_chunks)]
    assert obs.counter("engine.grad_slots") - s0 == \
        4 * (ntaxa - 2 + 32 * n_chunks)
    from examl_tpu.tree.topology import Tree
    wide = [f"t{i + 1}" for i in range(140)]
    packed = set()
    for nw in _spr_moved_newicks(140, 4, 5, seed=39):
        tree = Tree.from_newick(nw, wide, 1)
        flat = tree.flat_full_traversal(tree.centroid_branch())
        assert gradient.build_structure(flat, 1).n_steps == 138
        packed.add(gradient.build_structure(flat, 8).n_steps)
    assert len(packed) > 1, packed


def test_gradient_bitwise_stable_across_invalidation():
    """The pre-order plan is content-keyed: an SPR-commit-style
    sched-cache invalidation (cold plan rebuild) must reproduce the
    gradient dispatch bit for bit."""
    from examl_tpu.optimize.branch import tree_gradients
    data = correlated_dna(12, 200)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=2)
    inst.evaluate(tree, full=True)
    _, d1a, d2a = tree_gradients(inst, tree)
    inst.invalidate_schedules()          # the SPR-commit seam
    tree.invalidate_all()
    inst.evaluate(tree, full=True)
    _, d1b, d2b = tree_gradients(inst, tree)
    assert np.array_equal(d1a, d1b)
    assert np.array_equal(d2a, d2b)


def _walk_data(datatype_name, ntaxa=12, nsites=300, seed=11):
    """A shared mutation walk over the datatype's concrete states with
    a sprinkle of ambiguity and gap characters (so tips carry multi-bit
    state masks too)."""
    alphabet, extras = {"DNA": ("ACGT", "RYN-"),
                        "AA": ("ARNDCQEGHILKMFPSTWYV", "BZX-")}[datatype_name]
    rng = np.random.default_rng(seed)
    cur = rng.integers(0, len(alphabet), nsites)
    seqs = []
    for _ in range(ntaxa):
        flip = rng.random(nsites) < 0.15
        cur = np.where(flip, rng.integers(0, len(alphabet), nsites), cur)
        row = np.array(list(alphabet))[cur]
        amb = rng.random(nsites) < 0.05
        row[amb] = rng.choice(list(extras), int(amb.sum()))
        seqs.append("".join(row))
    return build_alignment_data([f"t{i}" for i in range(ntaxa)], seqs,
                                datatype_name=datatype_name)


@pytest.mark.parametrize("datatype_name", ["DNA", "AA"])
def test_whole_tree_gradient_bitwise_equals_table_lookup(datatype_name,
                                                         monkeypatch):
    """`tip_partials` changed where a tip's 0/1 row comes from, not its
    value: d1 and d2 of every branch equal, bit for bit, those of an
    engine whose tip side is the old `tips.table[codes]` gather."""
    from examl_tpu.ops import kernels
    from examl_tpu.optimize.branch import tree_gradients
    from tests.test_tip_partials import table_lookup
    data = _walk_data(datatype_name)

    def grads():
        inst = PhyloInstance(data)
        tree = inst.random_tree(seed=4)
        inst.evaluate(tree, full=True)
        _, d1, d2 = tree_gradients(inst, tree)
        return d1, d2

    d1, d2 = grads()
    # a fresh instance traces its programs anew, through the old lookup
    monkeypatch.setattr(kernels, "tip_partials", table_lookup)
    d1_old, d2_old = grads()
    assert np.isfinite(d1).all() and np.abs(d1).max() > 0
    assert np.array_equal(d1, d1_old)
    assert np.array_equal(d2, d2_old)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan bodies, pjit calls,
    custom rules) included."""
    from jax.extend import core as jex_core
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    yield from _eqns(sub)


def _gathers(jaxpr):
    return (e for e in _eqns(jaxpr) if e.primitive.name == "gather")


def test_grad_program_has_no_tip_table_gather():
    """Structural: the gradient program, walked through its scans, holds
    no gather whose operand is the [num_codes, K] indicator table."""
    import jax

    from examl_tpu.ops import gradient
    from examl_tpu.ops.kernels import OutrootTraversal
    inst = PhyloInstance(correlated_dna(12, 200))
    tree = inst.random_tree(seed=2)
    inst.evaluate(tree, full=True)
    eng = next(iter(inst.engines.values()))
    flat = tree.flat_full_traversal(tree.centroid_branch())
    gs = eng._grad_structure(flat)
    pre, ex_rows, ey_gidx, ez = gradient.grad_arrays(
        gs, flat, eng.row_map, eng.num_branch_slots, [0.9])
    jaxpr = jax.make_jaxpr(eng._grad_impl)(
        eng.clv, eng.scaler, 0, 1, 0, 1, OutrootTraversal(*pre), ex_rows,
        ey_gidx, ez, eng.models, eng.block_part, eng.weights, eng.tips,
        eng.site_rates)
    shapes = [tuple(e.invars[0].aval.shape) for e in _gathers(jaxpr.jaxpr)]
    assert shapes, "the walk found no gather at all (CLV rows are gathers)"
    assert tuple(eng.tips.table.shape) not in shapes, shapes


# -- arena rows read by index (kernels.take_rows) ------------------------------


@pytest.fixture
def indexed_rows(monkeypatch):
    """Every arena, however narrow, read by index: the form arenas wider
    than `ONE_PIECE_SITES` sites a row take."""
    from examl_tpu.ops import kernels
    monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 0)


def _prims(jaxpr):
    return [e.primitive.name for e in _eqns(jaxpr)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("idx_shape", [(), (8,), (3, 4)],
                         ids=["scalar", "W", "LxW"])
@pytest.mark.parametrize("form", ["gather", "indexed"])
def test_take_rows_equals_fancy_index(form, idx_shape, dtype, monkeypatch):
    """The helper alone: `arena[idx]` to the last bit in both forms, for
    a scalar, a wave's [W] and a schedule's [L, W] indices, repeats and
    the scratch row included."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels
    if form == "indexed":
        monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 0)
    rng = np.random.default_rng(5)
    arena = jnp.asarray(rng.standard_normal((7, 2, 128, 4, 4)), dtype=dtype)
    idx = jnp.asarray(rng.integers(0, 7, idx_shape), dtype=jnp.int32)
    def take(a, i):          # traced anew: jax caches a trace by function
        return kernels.take_rows(a, i)

    got = jax.jit(take)(arena, idx)
    want = arena[idx]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))
    prims = _prims(jax.make_jaxpr(take)(arena, idx).jaxpr)
    assert ("gather" in prims) == (form == "gather" and idx_shape != ())


@pytest.mark.parametrize("blocks, lane_k, gathered", [
    (128, (128, 4, 4), True),          # 140 x 16,384 DNA: one piece
    (128, (128, 4, 20), True),         # protein: bytes do not count
    (128, (128,), True),               # the scaler's rows
    (129, (128, 4, 4), False),
    (512, (128, 4, 4), False),         # a shard of the four-chip cell
    (1024, (128, 4, 4), False)])       # 140 x 131,072
def test_take_rows_form_follows_row_width(blocks, lane_k, gathered):
    """Which width takes which: up to 128 blocks a row the gather the
    compiler runs in one piece, above it a loop of dynamic slices and
    no gather; chosen from the arena's shape, nothing else."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels
    arena = jax.ShapeDtypeStruct((9, blocks) + lane_k, jnp.float32)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32)
    prims = _prims(jax.make_jaxpr(kernels.take_rows)(arena, idx).jaxpr)
    if gathered:
        assert "gather" in prims and "scan" not in prims
    else:
        assert "gather" not in prims
        assert "scan" in prims and "dynamic_slice" in prims


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("nsites, blocks", [(100, 1), (500, 4)])
@pytest.mark.parametrize("datatype_name", ["DNA", "AA"])
def test_whole_tree_gradient_bitwise_equals_row_gather(
        datatype_name, nsites, blocks, dtype, indexed_rows, monkeypatch):
    """Reading rows by index changed how a row reaches the kernels, not
    the row: d1 and d2 of every branch equal, bit for bit, those of an
    engine whose helper is `arena[idx]` again."""
    import jax.numpy as jnp

    from examl_tpu.ops import kernels
    from examl_tpu.optimize.branch import tree_gradients
    data = _walk_data(datatype_name, nsites=nsites)

    def grads():
        inst = PhyloInstance(data, dtype=jnp.dtype(dtype))
        (eng,) = inst.engines.values()
        assert eng.B == blocks and eng.dtype == jnp.dtype(dtype)
        tree = inst.random_tree(seed=4)
        inst.evaluate(tree, full=True)
        _, d1, d2 = tree_gradients(inst, tree)
        return d1, d2

    d1, d2 = grads()
    # a fresh instance traces its programs anew, through the gather
    monkeypatch.setattr(kernels, "take_rows", lambda arena, idx: arena[idx])
    d1_old, d2_old = grads()
    assert np.isfinite(d1).all() and np.abs(d1).max() > 0
    assert np.array_equal(d1, d1_old)
    assert np.array_equal(d2, d2_old)


def test_scan_tier_lnl_bitwise_equals_row_gather(indexed_rows, monkeypatch):
    """`gather_child` is shared: the scan tier's `traverse` reads a
    wave's children through the same helper, scalers too, and its lnL
    is the gathering engine's bit for bit."""
    from examl_tpu.ops import kernels
    data = _walk_data("DNA", nsites=500)

    def lnl():
        inst = PhyloInstance(data)
        for eng in inst.engines.values():
            eng.force_scan = True
        return inst.evaluate(inst.random_tree(seed=4), full=True)

    got = lnl()
    monkeypatch.setattr(kernels, "take_rows", lambda arena, idx: arena[idx])
    assert np.isfinite(got) and got == lnl()


@pytest.mark.parametrize("form", ["gather", "indexed"])
def test_grad_program_gathers_no_wide_arena(form, monkeypatch):
    """Structural: read by index, the gradient program walked through its
    scans holds no gather whose operand is a rank-5 arena (CLV or
    outroot: `xu`, `xl`, `xr`, `X`, `Y`); the narrow form holds those
    five, which the compiler runs in one piece each."""
    import jax

    from examl_tpu.ops import gradient, kernels
    from examl_tpu.ops.kernels import OutrootTraversal
    if form == "indexed":
        monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 0)
    inst = PhyloInstance(correlated_dna(12, 200))
    tree = inst.random_tree(seed=2)
    inst.evaluate(tree, full=True)
    eng = next(iter(inst.engines.values()))
    flat = tree.flat_full_traversal(tree.centroid_branch())
    gs = eng._grad_structure(flat)
    pre, ex_rows, ey_gidx, ez = gradient.grad_arrays(
        gs, flat, eng.row_map, eng.num_branch_slots, [0.9])
    jaxpr = jax.make_jaxpr(eng._grad_impl)(
        eng.clv, eng.scaler, 0, 1, 0, 1, OutrootTraversal(*pre), ex_rows,
        ey_gidx, ez, eng.models, eng.block_part, eng.weights, eng.tips,
        eng.site_rates)
    shapes = [tuple(e.invars[0].aval.shape) for e in _gathers(jaxpr.jaxpr)]
    assert tuple(eng.tips.masks.shape) in shapes, "the walk lost the tips"
    rows = tuple(eng.clv.shape[1:])              # [B, lane, R, K]
    arenas = [s for s in shapes if len(s) == 5 and s[1:] == rows]
    assert len(arenas) == (5 if form == "gather" else 0), shapes


def test_grad_smooth_reaches_nr_endpoint(grad_on):
    """Gradient-mode tree_evaluate vs the per-branch-NR endpoint from
    a COMMON near-optimal start, plus the O(n)->O(1) dispatch gauge.

    (From a degenerate all-DEFAULTZ random start the two optimizers
    may legitimately land in different bound-constrained local optima
    — measured: the simultaneous update often finds the better one —
    so the endpoint-parity contract is pinned where it is meaningful:
    both modes polishing the same smoothed tree must agree.)"""
    from examl_tpu.optimize.branch import tree_evaluate

    data = correlated_dna(16, 400)
    os.environ["EXAML_GRAD_SMOOTH"] = "0"
    inst0 = PhyloInstance(data)
    t0 = inst0.random_tree(seed=7)
    inst0.evaluate(t0, full=True)
    tree_evaluate(inst0, t0)                   # common pre-smoothed start
    nwk = t0.to_newick(data.taxon_names)

    def endpoint(env):
        os.environ["EXAML_GRAD_SMOOTH"] = env
        inst = PhyloInstance(data)
        tree = inst.tree_from_newick(nwk)
        inst.evaluate(tree, full=True)
        d0 = obs.counter("engine.dispatch_count")
        g0 = obs.counter("engine.grad_pass_dispatches")
        lnl = tree_evaluate(inst, tree)
        snap = obs.registry().snapshot_light()
        return (lnl, obs.counter("engine.dispatch_count") - d0,
                obs.counter("engine.grad_pass_dispatches") - g0,
                snap["gauges"].get(
                    "engine.dispatches_per_smoothing_round"))

    lnl_g, disp_g, gp_g, gauge_g = endpoint("")
    lnl_n, disp_n, gp_n, gauge_n = endpoint("0")
    n_branches = 2 * 16 - 3
    assert lnl_g == pytest.approx(lnl_n, abs=1e-4)
    assert gp_g > 0 and gp_n == 0
    # O(1) vs O(n): per gradient round, 1 traversal + 1 gradient
    # dispatch per engine; the per-branch round pays >= one dispatch
    # per branch.
    assert gauge_g is not None and gauge_g <= 4
    assert gauge_n is not None and gauge_n >= n_branches
    assert disp_g < disp_n / 3


def test_grad_smooth_env_off_uses_per_branch_path(grad_off):
    from examl_tpu.optimize.branch import tree_evaluate
    data = correlated_dna(10, 150)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=4)
    inst.evaluate(tree, full=True)
    g0 = obs.counter("engine.grad_pass_dispatches")
    tree_evaluate(inst, tree)
    assert obs.counter("engine.grad_pass_dispatches") == g0


def test_local_and_region_smooth_keep_per_branch_path(grad_on):
    """local/region smoothing stays on the per-branch path even with
    gradient mode on (a handful of branches — no pass to amortize)."""
    from examl_tpu.optimize.branch import local_smooth, region_smooth
    data = correlated_dna(10, 150)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=4)
    inst.evaluate(tree, full=True)
    g0 = obs.counter("engine.grad_pass_dispatches")
    p = tree.centroid_branch()
    p = p if not tree.is_tip(p.number) else p.back
    assert local_smooth(inst, tree, p, 2)
    assert region_smooth(inst, tree, p, 2, 2)
    assert obs.counter("engine.grad_pass_dispatches") == g0


def _caterpillar_newick(n):
    """Maximally unbalanced n-taxon tree: recursion depth ~ n."""
    out = "(t0,t1)"
    for i in range(2, n):
        out = f"({out},t{i})"
    return out + ";"


def test_deep_tree_smoothing_no_recursion_error():
    """smooth_subtree / region_smooth on a ~6000-deep caterpillar: the
    recursive reference implementation died with RecursionError at
    Python's default limit long before reference scale (50k taxa).
    Branch updates are stubbed (host-only traversal-order test — the
    hazard is stack depth, not arithmetic)."""
    from examl_tpu.optimize import branch as branch_mod
    from examl_tpu.tree.topology import Tree

    n = 6000
    assert n > sys.getrecursionlimit()
    tree = Tree.from_newick(_caterpillar_newick(n),
                            [f"t{i}" for i in range(n)], 1)

    class _StubInst:
        num_branch_slots = 1
        partition_smoothed = np.ones(1, dtype=bool)
        partition_converged = np.zeros(1, dtype=bool)
        updates = 0
        views = 0

        def makenewz(self, tree, p, q, z0, maxiter=1,
                     mask_converged=False):
            self.updates += 1
            return np.asarray(z0, dtype=np.float64)

        def new_view(self, tree, slot):
            self.views += 1

    inst = _StubInst()
    branch_mod.smooth_subtree(inst, tree, tree.start.back)
    # one update per branch, one new_view per inner node
    assert inst.updates == 2 * n - 3
    assert inst.views == n - 2
    inst.updates = inst.views = 0
    p = tree.start.back
    assert branch_mod.region_smooth(inst, tree, p, n, 1)
    assert inst.updates > n                    # both directions covered


def test_fleet_smooth_batch_matches_sequential(grad_on):
    """The vmapped batched whole-tree gradient step lands each job on
    the sequential gradient smoother's endpoint."""
    from examl_tpu.optimize.branch import smooth_tree
    data = correlated_dna(12, 200)
    inst = PhyloInstance(data)
    ev = inst.batch_evaluator()
    assert ev is not None and ev.fast
    groups = {}
    for s in range(20):
        t = inst.random_tree(seed=s)
        prep = ev.prepare(t)
        groups.setdefault(prep.key, []).append((s, t, prep))
    best = max(groups.values(), key=len)[:3]
    assert len(best) >= 2, "fixture produced no shared profile group"
    seeds = [s for s, _, _ in best]
    trees = [t for _, t, _ in best]
    preps = [p for _, _, p in best]
    d0 = obs.counter("engine.dispatch_count")
    ev.smooth_batch(preps, SMOOTHINGS)
    batched_disp = obs.counter("engine.dispatch_count") - d0
    batched = [inst.evaluate(t, full=True) for t in trees]
    # sequential reference: same smoother, one tree at a time
    inst2 = PhyloInstance(data)
    for s, lnl_b in zip(seeds, batched):
        t = inst2.random_tree(seed=s)
        inst2.evaluate(t, full=True)
        smooth_tree(inst2, t, SMOOTHINGS)
        lnl_s = inst2.evaluate(t, full=True)
        assert lnl_b == pytest.approx(lnl_s, abs=1e-5), s
    # one dispatch per engine per sweep for the WHOLE batch: far fewer
    # than 3 jobs x sweeps x 2; the win grows with batch size.
    sweeps = obs.counter("fleet.grad_smooth_sweeps")
    assert batched_disp <= 2 * sweeps + 4


def test_grad_bank_family_enumerated(grad_on):
    from examl_tpu.ops import bank
    fams = bank.enumerate_families()
    assert "grad" in fams
    os.environ["EXAML_GRAD_SMOOTH"] = "0"
    try:
        assert "grad" not in bank.enumerate_families(
            env={"EXAML_GRAD_SMOOTH": "0"})
    finally:
        os.environ["EXAML_GRAD_SMOOTH"] = ""


@pytest.mark.slow
def test_grad_smooth_large_tree_wall_clock_win(grad_on):
    """>=1k taxa: gradient smoothing beats the per-branch path on warm
    wall clock (the BENCH r03/r04 dispatch-storm fix, measured).

    From a degenerate all-DEFAULTZ random start at this scale NEITHER
    mode reaches full DELTAZ convergence inside its maxtimes budget
    (both accept exhaustion, the reference semantics), so the endpoint
    contract here is "at least as good", not equality — measured, the
    simultaneous update lands thousands of lnL units higher; the
    equality contract is pinned at convergence by
    test_grad_smooth_reaches_nr_endpoint."""
    import time
    from examl_tpu.optimize.branch import tree_evaluate

    def run(env):
        os.environ["EXAML_GRAD_SMOOTH"] = env
        data = correlated_dna(1000, 64, seed=9)
        inst = PhyloInstance(data)
        tree = inst.random_tree(seed=11)
        inst.evaluate(tree, full=True)
        tree_evaluate(inst, tree, 0.25)        # warm compiles
        tree2 = inst.random_tree(seed=13)
        inst.evaluate(tree2, full=True)
        t0 = time.perf_counter()
        lnl = tree_evaluate(inst, tree2)
        return lnl, time.perf_counter() - t0

    lnl_g, dt_g = run("")
    lnl_n, dt_n = run("0")
    assert lnl_g >= lnl_n - 1.0, (lnl_g, lnl_n)
    assert dt_g < dt_n, (dt_g, dt_n)
