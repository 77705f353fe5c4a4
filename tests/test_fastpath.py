"""Fast full-traversal path (ops/fastpath.py) vs the scan path.

The fast path relayouts CLV rows in wave order and executes case-split
chunk dots; it must agree with the scan-based traversal bit-for-bit in
f64 and stay consistent when partial (scan-path) traversals follow a
fast full traversal — the mixed regime the SPR search runs in.
"""

import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data, load_alignment
from examl_tpu.tree.topology import Tree

from tests.conftest import TESTDATA
from tests.oracle import oracle_lnl


@pytest.fixture(scope="module")
def data49():
    return load_alignment(f"{TESTDATA}/49", f"{TESTDATA}/49.model")


@pytest.fixture(scope="module")
def tree49_text():
    with open(f"{TESTDATA}/49.tree") as f:
        return f.read()


def _fresh(data, text, **kw):
    inst = PhyloInstance(data, **kw)
    return inst, inst.tree_from_newick(text)


@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_fast_matches_scan(datatype):
    """K = 4 and K = 20 through the chunk tier against the scan tier."""
    data = _synth(datatype=datatype)
    inst_f = PhyloInstance(data)
    lnl_fast = inst_f.evaluate(inst_f.random_tree(3), full=True)
    assert any(len(e._fast_jit_cache) > 0 for e in inst_f.engines.values()), \
        "full evaluate did not take the fast path"

    inst_s = PhyloInstance(data)
    for eng in inst_s.engines.values():
        eng.fast_slack = 0          # force scan path
    lnl_scan = inst_s.evaluate(inst_s.random_tree(3), full=True)
    assert lnl_fast == pytest.approx(lnl_scan, rel=1e-12, abs=1e-7)


def test_partial_after_fast_full(data49, tree49_text):
    """Partial traversals must resolve rows through the wave-order map."""
    inst, tree = _fresh(data49, tree49_text)
    lnl0 = inst.evaluate(tree, full=True)          # fast path, relayout
    # Change one internal branch, then evaluate at it with partial
    # traversals only (scan path through row_map).
    p = None
    for s, _ in tree.all_branches():
        if not tree.is_tip(s.number) and not tree.is_tip(s.back.number):
            p = s
            break
    new_z = [max(min(z * 0.8, 0.99), 1e-6) for z in p.z]
    from examl_tpu.tree.topology import hookup
    hookup(p, p.back, new_z)
    lnl1 = inst.evaluate(tree, p)                  # partial, mixed layout
    ref = oracle_lnl(tree, data49, inst.models)
    assert lnl1 == pytest.approx(ref, rel=1e-9)
    assert lnl1 != pytest.approx(lnl0, abs=1e-6)   # branch change took effect


def test_centroid_traversal_equivalent(data49, tree49_text):
    inst, tree = _fresh(data49, tree49_text)
    lnl0 = inst.evaluate(tree, full=True)
    s, entries = tree.full_traversal_centroid()
    assert len(entries) == inst.alignment.ntaxa - 2
    lnl_c = inst.evaluate(tree, s, full=True)
    assert lnl_c == pytest.approx(lnl0, rel=1e-10)


def test_fast_path_per_partition_branches(data49, tree49_text):
    inst_f, tree = _fresh(data49, tree49_text, per_partition_branches=True)
    lnl_fast = inst_f.evaluate(tree, full=True)
    inst_s, tree_s = _fresh(data49, tree49_text, per_partition_branches=True)
    for eng in inst_s.engines.values():
        eng.fast_slack = 0
    lnl_scan = inst_s.evaluate(tree_s, full=True)
    assert lnl_fast == pytest.approx(lnl_scan, rel=1e-12, abs=1e-7)


def test_fast_path_binary_and_small():
    """2-state data and a minimal 4-taxon tree go through the fast path."""
    names = ["a", "b", "c", "d"]
    seqs = ["0101100110", "0111100110", "1101001100", "1100001101"]
    ad = build_alignment_data(names, seqs, datatype_name="BIN")
    inst = PhyloInstance(ad)
    tree = inst.random_tree(0)
    lnl = inst.evaluate(tree, full=True)
    ref = oracle_lnl(tree, ad, inst.models)
    assert lnl == pytest.approx(ref, rel=1e-10)


# -- bounded-program equivalence matrix (ISSUE 5) ----------------------------
# Width bucketing + chunk coalescing + the scanned long tail must be
# invisible to the numbers: the bounded layout's lnL matches the scan
# tier bit-for-bit on these
# fixtures, the lax.scan groups match their own unrolled execution
# bit-for-bit BY CONSTRUCTION (same kernel body, same order), and any
# valid re-split of the waves preserves per-node arena contents.

import jax.numpy as jnp

from examl_tpu import obs
from examl_tpu.ops import fastpath


def _synth(n=40, width=97, seed=0, datatype="DNA"):
    rng = np.random.default_rng(seed)
    alphabet = {"DNA": "ACGT", "AA": "ARNDCQEGHILKMFPSTWYV"}[datatype]
    names = [f"t{i}" for i in range(n)]
    seqs = ["".join(alphabet[b]
                    for b in rng.integers(0, len(alphabet), width))
            for _ in range(n)]
    return build_alignment_data(names, seqs, datatype_name=datatype)


@pytest.fixture(scope="module")
def sdata():
    return _synth()


def _counter(name):
    return obs.counter(name)


def _eval(data, seed=3, force_scan=False, **kw):
    inst = PhyloInstance(data, **kw)
    tree = inst.random_tree(seed)
    if force_scan:
        for e in inst.engines.values():
            e.force_scan = True
    return inst, tree, inst.evaluate(tree, full=True)


@pytest.mark.parametrize("per_partition_branches", [False, True],
                         ids=["joint", "per_partition"])
def test_bounded_matches_scan_bitwise(sdata, per_partition_branches):
    """The tentpole acceptance: bounded layout vs the scan tier,
    bit-identical lnL on the f64 fixture (all three tip cases present
    in a 40-taxon random tree), with one branch-length slot and with
    C>1 slots through the packed z plumbing."""
    kw = {"per_partition_branches": per_partition_branches}
    _, _, lnl_b = _eval(sdata, **kw)
    _, _, lnl_s = _eval(sdata, force_scan=True, **kw)
    assert lnl_b == lnl_s


def test_entry_list_takes_scan_tier_bitwise(sdata):
    """One way into the fast tier: a full traversal handed over as a
    TraversalEntry LIST runs the scan tier, and leaves the lnL the flat
    form's chunk program leaves, to the bit."""
    inst_f, tree_f, lnl_flat = _eval(sdata)
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    entries = tree.flat_full_traversal(p).to_entries()
    (eng,) = inst.engines.values()
    assert eng._tier_for(entries, True) == "scan"
    vals = eng.traverse_evaluate(entries, p.number, p.back.number, p.z,
                                 full=True)
    assert not any(k[0] == "fast" for k in eng._fast_jit_cache)
    assert float(np.sum(vals)) == lnl_flat
    (eng_f,) = inst_f.engines.values()
    assert any(k[0] == "fast" for k in eng_f._fast_jit_cache)


@pytest.mark.parametrize("var,value,refused", [
    ("EXAML_PALLAS", "1", True), ("EXAML_PALLAS", "whole", True),
    ("EXAML_BOUNDED_CHUNKS", "0", True),
    ("EXAML_PALLAS_INTERPRET", "1", True), ("EXAML_PALLAS", "0", False)])
def test_removed_switches_raise_by_name(sdata, monkeypatch, var, value,
                                        refused):
    """A run that asks for a deleted kernel or layout gets an error that
    names the variable, not the default path in silence; a supervisor's
    child that inherits EXAML_PALLAS=0 from an older parent passes."""
    monkeypatch.setenv(var, value)
    if refused:
        with pytest.raises(ValueError, match=var + "="):
            PhyloInstance(sdata)
    else:
        PhyloInstance(sdata)


def test_bounded_matches_sev_scan(sdata):
    """-S (SEV pools) has no fast path; the bounded chunk tier must
    agree with the pooled scan evaluation on the same tree."""
    _, _, lnl_b = _eval(sdata)
    _, _, lnl_s = _eval(sdata, save_memory=True)
    assert lnl_s == pytest.approx(lnl_b, rel=1e-12, abs=1e-7)


def test_profile_bounded(sdata):
    """The profile is made of ladder widths only, its operation count is
    far below the raw chunk count, and its writes fit the arena the
    engine provisions."""
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    flat = tree.flat_full_traversal(p)
    n = inst.alignment.ntaxa
    st = fastpath.build_structure(flat, n)
    (eng,) = inst.engines.values()
    assert st.num_rows == n - 2
    assert st.num_rows <= st.max_write <= eng.num_rows - 1
    un, sc, total = fastpath.profile_stats(st.profile)
    assert sc >= 1, st.profile            # the long tail actually scans
    assert un + sc < total                # fewer ops than chunks
    kinds = {0, 1, 2}
    for k, w in fastpath.iter_profile_chunks(st.profile):
        assert k in kinds
        assert w >= fastpath.MIN_WIDTH and w <= fastpath.CHUNK_CAP
        assert w & (w - 1) == 0           # ladder = powers of two


def _unrolled(eng, flat, ntips):
    """(structure, z arrays, arena and scaler after the unrolled
    reference executor ran the structure's materialised chunks)."""
    st = fastpath.build_structure(flat, ntips)
    zl, zr = fastpath.refresh_z(st, flat, eng.num_branch_slots, eng.dtype)
    c, s = fastpath.run_chunks(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler), fastpath.structure_chunks(st, zl, zr),
        eng.scale_exp, eng.fast_precision)
    return st, zl, zr, np.asarray(c), np.asarray(s)


@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_segment_program_matches_unrolled_bitwise(datatype):
    """The lax.scan groups execute the identical chunk kernel in the
    identical order: real arena rows and scalers bit-equal to the
    unrolled execution of the same chunk list (K = 4 and K = 20)."""
    inst = PhyloInstance(_synth(datatype=datatype))
    tree = inst.random_tree(3)
    (eng,) = inst.engines.values()
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    flat = tree.flat_full_traversal(p)
    st, zl, zr, c1, s1 = _unrolled(eng, flat, inst.alignment.ntaxa)
    apply = fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                   eng.scale_exp, eng.fast_precision)
    c2, s2 = fastpath.run_segments(
        st.profile, st.base, st.lidx, st.ridx, st.lcode, st.rcode,
        zl, zr, jnp.array(eng.clv), jnp.array(eng.scaler), apply)
    assert any(seg[0] == "s" for seg in st.profile)   # a scan group ran
    rows = np.sort(st.row_of[st.row_of >= 0])
    assert (c1[rows] == np.asarray(c2)[rows]).all()
    assert (s1[rows] == np.asarray(s2)[rows]).all()


# -- arena rows read by index (kernels.take_rows) ----------------------------


def _traversed(data, executor, monkeypatch, arena):
    """(lnL or None, real arena rows, their scalers) of a full traversal
    of one f32 engine through `executor`: the chunk tier's segment
    program, the unrolled `run_chunks`, or the universal interpreter."""
    monkeypatch.setenv("EXAML_CLV_DTYPE",
                       "bf16" if arena == "bfloat16" else "same")
    monkeypatch.setenv("EXAML_UNIVERSAL",
                       "force" if executor == "universal" else "0")
    inst = PhyloInstance(data, dtype=jnp.float32)
    (eng,) = inst.engines.values()
    assert eng.B > 1 and eng.clv.dtype == jnp.dtype(arena)
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    if executor == "chunks":
        flat = tree.flat_full_traversal(p.back if tree.is_tip(p.number)
                                        else p)
        st, _, _, clv, sc = _unrolled(eng, flat, inst.alignment.ntaxa)
        rows = np.sort(st.row_of[st.row_of >= 0])
        return None, clv[rows].astype(np.float32), sc[rows]
    lnl = inst.evaluate(tree, full=True)
    assert eng._last_universal == (executor == "universal")
    return (lnl, np.asarray(eng.clv.astype(jnp.float32)),
            np.asarray(eng.scaler))


@pytest.mark.parametrize("executor", ["segments", "chunks", "universal"])
@pytest.mark.parametrize("arena", ["float32", "bfloat16"])
@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_rows_read_by_index_bitwise_equal_row_gather(datatype, arena,
                                                     executor, monkeypatch):
    """`chunk_applier` reads child rows and scalers through
    `kernels.take_rows`.  Forced to its by-index form (what an arena
    wider than 128 blocks takes), every executor of the chunk layout
    leaves the arena, the scalers and lnL that the parent's `clv[idx]`
    and `scaler[idx]` leave, bit for bit; a bf16 arena still casts after
    the read."""
    from examl_tpu.ops import kernels
    data = _synth(width=300, datatype=datatype)
    monkeypatch.setattr(kernels, "ONE_PIECE_SITES", 0)
    lnl, clv, sc = _traversed(data, executor, monkeypatch, arena)
    # a fresh instance traces its programs anew, through the gather
    monkeypatch.setattr(kernels, "take_rows", lambda a, idx: a[idx])
    lnl_old, clv_old, sc_old = _traversed(data, executor, monkeypatch, arena)
    assert np.isfinite(clv).all() and np.abs(clv).max() > 0
    assert np.array_equal(clv, clv_old) and np.array_equal(sc, sc_old)
    assert lnl == lnl_old and (lnl is None or np.isfinite(lnl))


@pytest.mark.parametrize("blocks, gathered", [(128, True), (129, False),
                                              (1024, False)])
def test_chunk_program_gathers_no_wide_arena(blocks, gathered):
    """Structural: above 128 blocks a row the chunk program, walked
    through its scans, holds no gather of the rank-5 CLV arena nor of
    the rank-3 scaler; at 128 blocks it holds the parent's (one a read:
    the compiler runs those in one piece)."""
    import jax

    from tests.test_gradients import _gathers

    inst = PhyloInstance(_synth(), dtype=jnp.float32)
    tree = inst.random_tree(3)
    (eng,) = inst.engines.values()
    assert eng.B == 1
    flat = tree.flat_full_traversal(tree.centroid_branch())
    st = fastpath.build_structure(flat, inst.alignment.ntaxa)
    zl, zr = fastpath.refresh_z(st, flat, eng.num_branch_slots, eng.dtype)

    def wide(a, axis):
        shape = a.shape[:axis] + (blocks,) + a.shape[axis + 1:]
        return jax.ShapeDtypeStruct(shape, a.dtype)

    def program(clv, scaler, block_part, tips):
        apply = fastpath.chunk_applier(eng.models, block_part, tips,
                                       eng.scale_exp, eng.fast_precision)
        return fastpath.run_segments(
            st.profile, st.base, st.lidx, st.ridx, st.lcode, st.rcode,
            zl, zr, clv, scaler, apply)

    tips = eng.tips._replace(codes=wide(eng.tips.codes, 1),
                             masks=wide(eng.tips.masks, 1))
    clv, scaler = wide(eng.clv, 1), wide(eng.scaler, 1)
    jaxpr = jax.make_jaxpr(program)(clv, scaler, wide(eng.block_part, 0),
                                    tips)
    shapes = [tuple(e.invars[0].aval.shape) for e in _gathers(jaxpr.jaxpr)]
    assert tuple(tips.codes.shape) in shapes, "the walk lost the tips"
    # inner children a chunk = its kind; a scan group's body is traced once
    reads = sum(seg[1] if seg[0] == "u" else sum(k for k, _ in seg[2])
                for seg in st.profile)
    assert reads > 0 and tips.codes.shape != scaler.shape
    arenas = [s for s in shapes if s in (clv.shape, scaler.shape)]
    assert len(arenas) == (2 * reads if gathered else 0), shapes


def test_wave_resplit_preserves_arena_rows(sdata):
    """Property: entries within a wave are independent, so any valid
    re-split/reorder of the waves (here: random within-wave entry
    permutations, which reshuffle chunk membership and row assignment)
    preserves every node's arena row contents bit-for-bit."""
    from examl_tpu.tree.topology import FlatTraversal
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    (eng,) = inst.engines.values()
    n = inst.alignment.ntaxa
    flat = tree.flat_full_traversal(tree.centroid_branch())

    def run(fl):
        st, _, _, c, s = _unrolled(eng, fl, n)
        return {num: (c[r], s[r]) for num, r in enumerate(st.row_of)
                if r >= 0}

    base = run(flat)
    assert len(base) == n - 2
    rng = np.random.default_rng(11)
    starts = np.r_[0, np.cumsum(flat.wave_sizes)]
    for trial in range(3):
        perm = np.concatenate([lo + rng.permutation(hi - lo)
                               for lo, hi in zip(starts[:-1], starts[1:])])
        got = run(FlatTraversal(
            flat.parent[perm], flat.left[perm], flat.right[perm],
            flat.zl[perm], flat.zr[perm], flat.wave_sizes, flat.ntips))
        assert got.keys() == base.keys()
        for num in base:
            assert (got[num][0] == base[num][0]).all(), (trial, num)
            assert (got[num][1] == base[num][1]).all(), (trial, num)


def test_bounded_after_spr_commit_seam(sdata):
    """The cache-invalidation seam: a real SPR rearrange + commit, then
    a full evaluate — bounded layout vs scan tier on the same moved
    tree, bit-identical."""
    from examl_tpu.constants import UNLIKELY
    from examl_tpu.search.spr import (SprContext, rearrange,
                                      restore_tree_fast)

    def run(force_scan):
        inst = PhyloInstance(sdata)
        tree = inst.random_tree(9)
        if force_scan:
            for eng in inst.engines.values():
                eng.force_scan = True
        inst.evaluate(tree, full=True)
        ctx = SprContext(inst)
        ctx.start_lh = ctx.end_lh = inst.likelihood
        ctx.best_of_node = UNLIKELY
        p = next(s for s in (tree.nodep[i]
                             for i in tree.inner_numbers())
                 if not tree.is_tip(s.back.number))
        assert rearrange(inst, tree, ctx, p, 1, 3)
        if ctx.end_lh > ctx.start_lh:
            restore_tree_fast(inst, tree, ctx)
        lnl = inst.evaluate(tree, full=True)
        return float(lnl), tree.to_newick(inst.alignment.taxon_names)

    lnl_f, nwk_f = run(False)
    lnl_s, nwk_s = run(True)
    assert nwk_f == nwk_s
    assert lnl_f == lnl_s


def test_cross_topology_profile_shares_program(sdata):
    """The point of width bucketing: two DIFFERENT topologies (distinct
    topo_key, so the structure cache misses twice) with the same
    bucketed profile dispatch through ONE compiled program — the second
    evaluate is a jit-cache hit and compiles nothing new."""
    inst = PhyloInstance(sdata)
    tree_a = inst.random_tree(3)
    names = inst.alignment.taxon_names
    text = tree_a.to_newick(names)
    # Same shape, different tip placement: rotate the taxon labels one
    # position, so node numbers (and the topology signature) change
    # while every wave/kind/width — and therefore the profile — stays.
    rot = {names[i]: names[(i + 1) % len(names)] for i in range(len(names))}
    import re
    text_b = re.sub("|".join(sorted(rot, key=len, reverse=True)),
                    lambda m: rot[m.group(0)], text)
    tree_b = inst.tree_from_newick(text_b)

    (eng,) = inst.engines.values()
    m0 = _counter("engine.sched_cache.miss")
    c0 = _counter("engine.compile_count")
    lnl_a = inst.evaluate(tree_a, full=True)
    keys_after_a = len(eng._fast_jit_cache)
    misses_a = _counter("engine.sched_cache.miss")
    compiles_a = _counter("engine.compile_count")
    assert misses_a >= m0 + 1
    h0 = _counter("engine.cache_hits")
    lnl_b = inst.evaluate(tree_b, full=True)
    assert np.isfinite(lnl_b) and lnl_b != pytest.approx(lnl_a, abs=1e-6)
    # Different topology: new structure (cache miss) ...
    assert _counter("engine.sched_cache.miss") >= misses_a + 1
    # ... same bucketed profile: the jitted program is REUSED.
    st_a = next(iter(eng._sched_cache.values()))
    assert _counter("engine.cache_hits") >= h0 + 1
    assert len(eng._fast_jit_cache) == keys_after_a
    assert _counter("engine.compile_count") == compiles_a
    # The jit key is the bucketed profile (small-fix satellite): the
    # shared entry is keyed by the segment tuple both schedules mint.
    assert ("fast", st_a.profile, "flat", True) in eng._fast_jit_cache


def test_program_gauges_published(sdata):
    """obs satellite: program_chunks / scan_groups /
    dispatches_per_traversal gauges land in metrics snapshots, tagged
    per engine so multiple engines never overwrite each other."""
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    inst.evaluate(tree, full=True)
    (eng,) = inst.engines.values()
    tag = "." + eng._obs_tag
    g = obs.snapshot()["gauges"]
    assert g.get("engine.program_chunks" + tag, 0) >= 1
    assert "engine.scan_groups" + tag in g
    assert g.get("engine.dispatches_per_traversal" + tag, 0) >= 1
    assert (g["engine.program_chunks" + tag]
            + g["engine.scan_groups" + tag]
            == g["engine.dispatches_per_traversal" + tag])
    assert g["engine.program_chunks" + tag] <= 256


# -- the one-entry tail (PR 41) ----------------------------------------------
# From `fastpath.ONE_ENTRY_ROW_BYTES` a row, the narrow tail waves from
# where the first tail scan group starts run as ONE ("e", L) segment:
# one entry a step in wave order, its kind data (a tip child's row
# index is -1), replays repeating the final entry.

from examl_tpu.tree.topology import Tree  # noqa: E402
from examl_tpu.utils import bucket_len  # noqa: E402


def _random_flat(ntaxa, seed=3):
    tree = Tree.random([f"t{i}" for i in range(ntaxa)], seed=seed)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    return tree.flat_full_traversal(p)


def _entry_steps(st):
    """(first chunk index, first slot, L) of a structure's ("e", L)
    segment, the profile's last."""
    *head, (tag, L) = st.profile
    assert tag == "e" and all(seg[0] != "e" for seg in head)
    return (sum(1 for _ in fastpath.iter_profile_chunks(head)),
            fastpath.profile_slots(head), L)


FIELDS = ("base", "lidx", "ridx", "lcode", "rcode")


@pytest.mark.parametrize("ntaxa", [49, 140, 2000])
def test_threshold_selects_the_one_entry_tail(ntaxa):
    """Under the threshold a structure is today's, byte for byte; at it
    the head is today's up to the first tail scan group and the rest is
    one ("e", L) segment of L = bucket_len(n_tail) one-slot steps."""
    flat = _random_flat(ntaxa)
    today = fastpath.build_structure(flat, ntaxa)
    below = fastpath.build_structure(flat, ntaxa,
                                     fastpath.ONE_ENTRY_ROW_BYTES - 1)
    at = fastpath.build_structure(flat, ntaxa, fastpath.ONE_ENTRY_ROW_BYTES)
    assert below.profile == today.profile
    for f in FIELDS:
        assert np.array_equal(np.asarray(getattr(below, f)),
                              np.asarray(getattr(today, f))), f
    for f in ("row_of", "z_src", "z_swap"):
        assert np.array_equal(getattr(below, f), getattr(today, f)), f
    assert below.max_write == today.max_write
    c0, s0, L = _entry_steps(at)
    k = len(at.profile) - 1
    assert at.profile[:k] == today.profile[:k]
    assert today.profile[k][0] == "s" and len(today.profile[k][2]) > 1
    n_tail = flat.n - int(np.asarray(at.base)[c0])
    assert L == bucket_len(n_tail)
    assert fastpath.profile_slots(at.profile) == s0 + L < \
        fastpath.profile_slots(today.profile)
    assert at.max_write <= today.max_write


@pytest.mark.parametrize("ntaxa", [49, 140, 2000])
def test_one_entry_tail_layout(ntaxa):
    """The ("e", L) segment writes every tail entry exactly once, each
    after the entries whose rows it reads (the planner asserts it too);
    a tip child's slot reads no arena row; replays only rewrite their
    source row; `row_of` still maps every node."""
    flat = _random_flat(ntaxa)
    n = flat.n
    st = fastpath.build_structure(flat, ntaxa, fastpath.ONE_ENTRY_ROW_BYTES)
    c0, s0, L = _entry_steps(st)
    base = np.asarray(st.base)[c0:]
    li, ri, lc, rc = (np.asarray(getattr(st, f))[s0:] for f in FIELDS[1:])
    src, swap = st.z_src[s0:], st.z_swap[s0:]
    assert base.shape[0] == L == li.shape[0]
    n_tail = n - int(base[0])
    real = slice(0, n_tail)
    # every tail row once, in step order; every tail entry once
    assert np.array_equal(base[real], np.arange(n - n_tail, n))
    assert sorted(src[real]) == sorted(set(range(n)) - set(st.z_src[:s0]))
    assert np.array_equal(st.row_of[flat.parent[src[real]]], base[real])
    # children: tips read no row (-1), inner children earlier rows
    left = np.where(swap, flat.right[src], flat.left[src])
    right = np.where(swap, flat.left[src], flat.right[src])
    for child, idx, code in ((left, li, lc), (right, ri, rc)):
        tip = child <= ntaxa
        assert (idx[tip] == -1).all() and (code[tip] == child[tip] - 1).all()
        assert (idx[~tip] == st.row_of[child[~tip]]).all()
        assert (idx[~tip] < base[~tip]).all()
    assert ((li < 0) | (ri >= 0)).all()       # canonical: the tip is left
    # replays: the final real step again, rows and all
    for a in (base, li, ri, lc, rc, src, swap):
        assert (a[n_tail:] == a[n_tail - 1]).all()
    # the arena map is whole
    assert np.array_equal(np.sort(st.row_of[st.row_of >= 0]), np.arange(n))
    assert (st.row_of[flat.parent] >= 0).all()
    assert fastpath.profile_slots(st.profile) == st.z_src.shape[0]


def _partitioned(nparts=4, ntaxa=24, width=60, seed=5):
    """DNA in `nparts` partitions: one engine, M = nparts (block_part)."""
    import os
    import tempfile

    from examl_tpu.io.partitions import parse_partition_file
    rng = np.random.default_rng(seed)
    seqs = ["".join("ACGT"[c] for c in rng.integers(0, 4, nparts * width))
            for _ in range(ntaxa)]
    with tempfile.NamedTemporaryFile("w", suffix=".model",
                                     delete=False) as f:
        for k in range(nparts):
            f.write(f"DNA, g{k} = {k * width + 1}-{(k + 1) * width}\n")
        path = f.name
    try:
        return build_alignment_data([f"t{i}" for i in range(ntaxa)], seqs,
                                    specs=parse_partition_file(path))
    finally:
        os.unlink(path)


ONE_ENTRY_CASES = {
    "DNA": (lambda: _synth(), {}),
    "AA": (lambda: _synth(datatype="AA"), {}),
    "parts4": (_partitioned, {}),
    "per_partition_branches": (_partitioned,
                               {"per_partition_branches": True}),
}


@pytest.mark.parametrize("case", sorted(ONE_ENTRY_CASES))
def test_one_entry_tail_matches_today_and_scan(case, monkeypatch):
    """f64: the one-entry tail's lnL against today's tail and against
    the scan tier, to 1e-12 relative (K = 4, K = 20, four partitions in
    one engine through `block_part`, per-partition branch lengths)."""
    make, kw = ONE_ENTRY_CASES[case]
    data = make()
    got = {}
    for mode, threshold in (("one_entry", 0), ("today", 1 << 62),
                            ("scan", 1 << 62)):
        monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", threshold)
        inst, _, got[mode] = _eval(data, force_scan=mode == "scan", **kw)
        (eng,) = inst.engines.values()
        fast = [k[1] for k in eng._fast_jit_cache if k[0] == "fast"]
        if mode == "scan":
            assert not fast
        else:
            assert len(fast) == 1 and \
                (fast[0][-1][0] == "e") == (mode == "one_entry")
    if case.startswith("parts"):
        assert eng.num_parts == 4
    assert got["one_entry"] == pytest.approx(got["today"], rel=1e-12)
    assert got["one_entry"] == pytest.approx(got["scan"], rel=1e-12)


@pytest.mark.parametrize("datatype", ["DNA", "AA"])
def test_one_entry_segment_matches_unrolled_bitwise(datatype):
    """The ("e", L) scan switches over the step's kind and runs the
    chunk kernel at W = 1: rows and scalers bit-equal to the unrolled
    `run_chunks` over the same steps, whose kinds the host reads from
    the -1 marks (`structure_chunks`)."""
    inst = PhyloInstance(_synth(datatype=datatype))
    (eng,) = inst.engines.values()
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    flat = tree.flat_full_traversal(p.back if tree.is_tip(p.number) else p)
    n = inst.alignment.ntaxa
    st = fastpath.build_structure(flat, n, fastpath.ONE_ENTRY_ROW_BYTES)
    _entry_steps(st)
    zl, zr = fastpath.refresh_z(st, flat, eng.num_branch_slots, eng.dtype)
    chunks = fastpath.structure_chunks(st, zl, zr)
    assert {c.kind for c in chunks if c.width == 1} == {1, 2}
    c1, s1 = fastpath.run_chunks(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler), chunks, eng.scale_exp, eng.fast_precision)
    apply = fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                   eng.scale_exp, eng.fast_precision)
    c2, s2 = fastpath.run_segments(
        st.profile, st.base, st.lidx, st.ridx, st.lcode, st.rcode,
        zl, zr, jnp.array(eng.clv), jnp.array(eng.scaler), apply)
    rows = np.sort(st.row_of[st.row_of >= 0])
    assert (np.asarray(c1)[rows] == np.asarray(c2)[rows]).all()
    assert (np.asarray(s1)[rows] == np.asarray(s2)[rows]).all()


@pytest.mark.parametrize("mode", ["force", "route"])
def test_one_entry_tail_stays_off_the_interpreter_path(sdata, monkeypatch,
                                                       mode):
    """The universal interpreter's alphabet is uniform-width: it refuses
    a one-entry tail by name, so an engine that may hand a dispatch to
    it (forced, or novel profiles routed there) plans the 8-wide tail
    whatever its row, and the interpreter runs it with the one-entry
    tail's lnL."""
    from examl_tpu.ops import universal
    with pytest.raises(universal.UniversalIneligible, match="one-entry"):
        universal.build_table((("u", 0, 8), ("e", 4)), np.zeros(5))
    monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", 1)
    inst = PhyloInstance(sdata)
    (eng,) = inst.engines.values()
    tree = inst.random_tree(3)
    lnl_e = inst.evaluate(tree, full=True)
    assert [st.profile[-1][0] for st in eng._sched_cache.values()] == ["e"]
    setattr(eng, {"force": "universal_force",
                  "route": "route_novel_to_universal"}[mode], True)
    assert eng.trav_row_bytes() == 0
    c0 = obs.counter("engine.universal_ineligible")
    u0 = obs.counter("engine.universal_dispatches")
    lnl_u = inst.evaluate(tree, full=True)
    assert eng._last_universal
    assert obs.counter("engine.universal_ineligible") == c0
    assert obs.counter("engine.universal_dispatches") > u0
    assert lnl_u == pytest.approx(lnl_e, rel=1e-12)


def test_trav_slots_count_the_program_slots(sdata, monkeypatch):
    """`engine.trav_slots` rises by the write slots the program ran
    (padding and replays included), `engine.trav_live_slots` by the
    entries: the one-entry tail takes its padding slots away."""
    got = {}
    for threshold in (1 << 62, 0):
        monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", threshold)
        s0 = obs.counter("engine.trav_slots")
        l0 = obs.counter("engine.trav_live_slots")
        inst, _, _ = _eval(sdata)
        (eng,) = inst.engines.values()
        st = next(iter(eng._sched_cache.values()))
        assert obs.counter("engine.trav_slots") - s0 == \
            fastpath.profile_slots(st.profile)
        assert obs.counter("engine.trav_live_slots") - l0 == st.num_rows
        got[threshold] = fastpath.profile_slots(st.profile)
    assert got[0] < got[1 << 62]
