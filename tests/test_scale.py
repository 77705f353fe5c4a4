"""Host-bookkeeping scale hardening: no recursion limits, big-tree smoke.

The reference's ambition is ~120k taxa (SURVEY §6, manual FAQ); the host
side (tree build, traversal scheduling, newick I/O, SPR iteration order)
must therefore be iterative.  5,000 taxa comfortably exceeds Python's
default recursion limit via any per-level recursion.
"""

import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data
from examl_tpu.io.newick import format_newick, parse_newick
from examl_tpu.search.spr import dfs_slot_order
from examl_tpu.tree.topology import Tree

N = 5000


@pytest.fixture(scope="module")
def caterpillar_newick():
    """Worst-case (maximum height) topology: fully unbalanced."""
    parts = ["(t0:0.1,t1:0.1)"]
    for i in range(2, N):
        parts.append(f"(%s:0.1,t{i}:0.1)" % parts[-1])
        parts.pop(-2)
    return parts[-1] + ";"


def test_newick_roundtrip_caterpillar(caterpillar_newick):
    root = parse_newick(caterpillar_newick)
    assert sum(1 for _ in root.leaves()) == N
    text = format_newick(root)
    root2 = parse_newick(text)
    assert sum(1 for _ in root2.leaves()) == N


def test_tree_build_traverse_5k(caterpillar_newick):
    names = [f"t{i}" for i in range(N)]
    tree = Tree.from_newick(caterpillar_newick, names)
    _, entries = tree.full_traversal()
    assert len(entries) == N - 2
    waves = Tree.schedule_waves(entries)
    assert sum(len(w) for w in waves) == N - 2
    # centroid rooting must cut the wave depth roughly in half on a
    # caterpillar
    _, entries_c = tree.full_traversal_centroid()
    assert len(entries_c) == N - 2
    assert len(Tree.schedule_waves(entries_c)) <= len(waves) / 2 + 2
    order = dfs_slot_order(tree)
    assert len(order) == N + (N - 2)
    text = tree.to_newick(names)
    assert text.count(",") == N - 1


def test_flat_host_path_5k_smoke():
    """Non-slow synthetic host-path smoke (ISSUE 4): flat traversal +
    vectorized structure build + z refresh at 5k taxa."""
    import time

    import jax.numpy as jnp

    from examl_tpu.ops import fastpath

    names = [f"t{i}" for i in range(N)]
    tree = Tree.random(names, seed=3)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    t0 = time.time()
    flat = tree.flat_full_traversal(p)
    t_cold = time.time() - t0
    assert flat.n == N - 2
    assert int(flat.wave_sizes.sum()) == N - 2
    st = fastpath.build_structure(flat, N)
    assert st.num_rows == N - 2 <= st.max_write
    assert (np.sort(st.row_of[st.row_of >= 0]) == np.arange(N - 2)).all()
    un, sc, total = fastpath.profile_stats(st.profile)
    assert 1 <= un <= 256 and un + sc < total
    assert fastpath.profile_slots(st.profile) == st.z_src.shape[0]
    t0 = time.time()
    for _ in range(3):
        f = tree.flat_full_traversal(p)
        zl, zr = fastpath.refresh_z(st, f, 1, jnp.float32)
    t_hit = (time.time() - t0) / 3
    # Padding slots carry z=1 (identity P), real slots the branch z.
    zl_h = np.asarray(zl)
    assert (zl_h[st.z_src < 0] == 1.0).all()
    assert t_cold < 3.0, t_cold              # measured ~0.03 s
    assert t_hit < 1.0, t_hit                # measured ~0.008 s


@pytest.mark.slow
def test_random_tree_5k():
    names = [f"t{i}" for i in range(N)]
    tree = Tree.random(names, seed=1)
    _, entries = tree.full_traversal()
    assert len(entries) == N - 2


@pytest.mark.slow
def test_small_lnl_on_1k_taxa():
    """End-to-end device path on a 1,000-taxon synthetic alignment."""
    n = 1000
    rng = np.random.default_rng(0)
    names = [f"t{i}" for i in range(n)]
    bases = "ACGT"
    seqs = ["".join(bases[b] for b in rng.integers(0, 4, 256))
            for _ in range(n)]
    ad = build_alignment_data(names, seqs)
    inst = PhyloInstance(ad)
    tree = inst.random_tree(0)
    lnl = inst.evaluate(tree, full=True)
    assert np.isfinite(lnl) and lnl < 0


def test_native_newick_scanner_parity():
    """C++ scanner (native/newickscan.cpp) agrees with the pure-Python
    parser on real trees and rejects malformed input identically."""
    pytest.importorskip("examl_tpu._newickscan")
    from examl_tpu.io.newick import (_Parser, _parse_newick_native,
                                     format_newick)
    from tests.conftest import TESTDATA
    for path in (f"{TESTDATA}/49.tree", f"{TESTDATA}/140.tree"):
        text = open(path).read()
        assert (format_newick(_parse_newick_native(text))
                == format_newick(_Parser(text).parse()))
    for bad in ("((A,B)(C,D));", "(A,B", "(A:x,B);"):
        with pytest.raises(ValueError):
            _parse_newick_native(bad)


@pytest.mark.slow
def test_chunk_tier_50k_bounded_compile():
    """ISSUE 5 acceptance: the bounded chunk program at 50k synthetic
    taxa stays under the 256-unrolled-block cap, compiles on CPU
    (measured ~37 s vs tens of minutes unrolled), and its lnL matches
    the scan tier."""
    import jax.numpy as jnp

    from examl_tpu.ops import fastpath

    n = 50_000
    rng = np.random.default_rng(7)
    names = [f"t{i}" for i in range(n)]
    lut = np.frombuffer(b"ACGT", dtype=np.uint8)
    seqs = [bytes(row).decode()
            for row in lut[rng.integers(0, 4, (n, 64), dtype=np.int8)]]
    inst = PhyloInstance(build_alignment_data(names, seqs),
                         dtype=jnp.float32)
    tree = Tree.random(names, seed=1)
    (eng,) = inst.engines.values()
    eng.force_scan = True
    lnl = inst.evaluate(tree, full=True)
    eng.force_scan = False
    lnl_fast = inst.evaluate(tree, full=True)
    assert lnl_fast == inst.evaluate(tree, full=True)  # cached structure
    (st,) = eng._sched_cache.values()
    un, sc, total = fastpath.profile_stats(st.profile)
    assert 1 <= un <= 256, un
    assert un + sc < total / 5, (un, sc, total)
    assert np.isfinite(lnl) and abs(lnl - lnl_fast) <= max(
        1e-6 * abs(lnl), 1e-3), (lnl, lnl_fast)


@pytest.mark.slow
def test_host_paths_50k_taxa_within_budget():
    """The host-side pipeline at 50k taxa (reference ambition ~120k,
    SURVEY §6) stays interactive: random-addition build is O(n) via the
    incremental branch list, and one full-tree fast-path schedule builds
    in well under a second (generous bounds absorb CI host
    contention).  Spot-measured at 100k taxa (one-off,
    2026-07): build 2.4 s, traversal 0.29 s, to_newick 1.67 s,
    from_newick 3.43 s, schedule 0.94 s — all linear in n."""
    import time

    import jax.numpy as jnp

    from examl_tpu.ops import fastpath

    n = 50_000
    names = [f"t{i}" for i in range(n)]
    t0 = time.time()
    tree = Tree.random(names, seed=1)
    t_build = time.time() - t0
    t0 = time.time()
    _, entries = tree.full_traversal()
    t_trav = time.time() - t0
    assert len(entries) == n - 2
    t0 = time.time()
    waves = Tree.schedule_waves(entries)
    t_waves = time.time() - t0
    assert sum(len(w) for w in waves) == n - 2
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    fastpath.build_structure(tree.flat_full_traversal(p), n)  # warm jax
    t0 = time.time()
    flat = tree.flat_full_traversal(p)
    st = fastpath.build_structure(flat, n)
    t_sched = time.time() - t0
    assert int((st.row_of >= 0).sum()) == n - 2
    assert t_build < 5.0, t_build            # measured 0.56 s
    assert t_trav < 2.0, t_trav              # measured 0.13 s
    assert t_waves < 1.0, t_waves            # measured 0.02 s
    assert t_sched < 3.0, t_sched
    # The cached path: repeated fixed-topology traversals refresh z
    # only.
    t0 = time.time()
    for _ in range(3):
        f = tree.flat_full_traversal(p)
        fastpath.refresh_z(st, f, 1, jnp.float32)
    t_hit = (time.time() - t0) / 3
    assert t_hit < 1.0, t_hit                # measured 0.05 s
