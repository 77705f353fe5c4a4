"""Schedule-structure cache: equivalence matrix + flat-traversal parity.

The tentpole contract (ISSUE 4): splitting the fast-path schedule into
a topology-keyed immutable structure + per-call z refresh must be
invisible to the numbers — cached and rebuilt traversals produce
BIT-identical likelihoods, topology changes (SPR/NNI) invalidate by
signature, and a -R checkpoint restore starts cold.  Plus parity of the
vectorized host scheduling (`flat_full_traversal`, array
`schedule_waves`) against the per-entry reference implementations.
"""

import time

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data
from examl_tpu.tree.topology import (Tree, _TOPO_CLOCK, _wave_order,
                                     hookup)


def _data(n=16, width=120, seed=0):
    rng = np.random.default_rng(seed)
    names = [f"t{i}" for i in range(n)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, width))
            for _ in range(n)]
    return build_alignment_data(names, seqs)


@pytest.fixture(scope="module")
def data16():
    return _data()


def _counter(name):
    return obs.counter(name)


# -- flat traversal parity ---------------------------------------------------


def test_flat_matches_compute_traversal(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    flat = tree.flat_full_traversal(p)
    flags_flat = {num: [s.x for s in tree.slots(num)]
                  for num in tree.inner_numbers()}

    tree.invalidate_all()
    ref = (tree.compute_traversal(p, full=True)
           + tree.compute_traversal(p.back, full=True))
    flags_ref = {num: [s.x for s in tree.slots(num)]
                 for num in tree.inner_numbers()}

    ents = flat.to_entries()
    assert len(ents) == len(ref) == tree.ntips - 2
    key = lambda e: (e.parent, e.left, e.right, e.zl, e.zr)
    assert sorted(map(key, ents)) == sorted(map(key, ref))
    # Same wave partition (membership per wave, as sets).
    wf = [sorted(e.parent for e in w) for w in Tree.schedule_waves(ents)]
    wr = [sorted(e.parent for e in w) for w in Tree.schedule_waves(ref)]
    assert wf == wr
    assert [int(s) for s in flat.wave_sizes] == [len(w) for w in wr]
    # Same final x-flag orientation.
    assert flags_flat == flags_ref


def test_flat_cache_reuses_structure_and_rereads_z(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(5)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    f1 = tree.flat_full_traversal(p)
    f2 = tree.flat_full_traversal(p)
    assert f2.parent is f1.parent          # structural arrays shared
    assert f2.topo_key == f1.topo_key
    # Branch-length change: same structure, fresh z.
    s = next(s for s, _ in tree.all_branches()
             if not tree.is_tip(s.number))
    hookup(s, s.back, [v * 0.5 + 0.25 for v in s.z])
    f3 = tree.flat_full_traversal(p)
    assert f3.topo_key == f1.topo_key and f3.parent is f1.parent
    assert not (np.c_[f3.zl, f3.zr] == np.c_[f1.zl, f1.zr]).all()
    # Topology change: new structure, new signature.
    clock0 = _TOPO_CLOCK[0]
    a = next(s for s, _ in tree.all_branches()
             if not tree.is_tip(s.number)
             and not tree.is_tip(s.back.number))
    b = a.back
    ax, by = a.next.back, b.next.back
    hookup(a.next, by, list(a.next.z))
    hookup(b.next, ax, list(b.next.z))     # NNI swap across edge (a, b)
    assert _TOPO_CLOCK[0] > clock0
    f4 = tree.flat_full_traversal(p)
    assert f4.topo_key != f1.topo_key


def test_vectorized_schedule_waves_matches_dict(data16):
    # Above the vectorization threshold on a worst-case (caterpillar)
    # and a random topology: identical waves, identical within-wave
    # order, to the dict-based reference loop.
    n = 700
    names = [f"t{i}" for i in range(n)]
    part = "(t0:0.1,t1:0.1)"
    for i in range(2, n):
        part = f"({part}:0.1,t{i}:0.1)"
    for tree in (Tree.from_newick(part + ";", names),
                 Tree.random(names, seed=2)):
        _, entries = tree.full_traversal_centroid()
        assert len(entries) == n - 2 and len(entries) >= 512
        got = Tree.schedule_waves(entries)
        level, waves = {}, []
        for e in entries:
            lv = max(level.get(e.left, 0), level.get(e.right, 0))
            level[e.parent] = lv + 1
            if lv == len(waves):
                waves.append([])
            waves[lv].append(e)
        assert [[id(e) for e in w] for w in got] \
            == [[id(e) for e in w] for w in waves]


def test_wave_order_rejects_cycles():
    parent = np.asarray([10, 11], np.int64)
    left = np.asarray([11, 10], np.int64)   # mutual dependency
    right = np.asarray([1, 2], np.int64)
    with pytest.raises(ValueError):
        _wave_order(parent, left, right)


# -- cache equivalence matrix ------------------------------------------------


def test_cached_vs_rebuilt_lnl_bit_identical(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(1)
    m0, h0 = (_counter("engine.sched_cache.miss"),
              _counter("engine.sched_cache.hit"))
    lnl1 = inst.evaluate(tree, full=True)      # miss: builds structure
    lnl2 = inst.evaluate(tree, full=True)      # hit: z refresh only
    assert _counter("engine.sched_cache.miss") == m0 + 1
    assert _counter("engine.sched_cache.hit") == h0 + 1
    assert lnl1 == lnl2
    # Against a cold-cache rebuild in a fresh instance: bit-identical.
    inst2 = PhyloInstance(data16)
    tree2 = inst2.random_tree(1)
    assert inst2.evaluate(tree2, full=True) == lnl1
    # Against the scan tier, which an entry LIST takes even when full
    # (one way into the fast tier: the flat form): bit-identical.
    inst3 = PhyloInstance(data16)
    tree3 = inst3.random_tree(1)
    s, entries = tree3.full_traversal_centroid()
    (eng,) = inst3.engines.values()
    vals = eng.traverse_evaluate(entries, s.number, s.back.number, s.z,
                                 full=True)
    assert float(np.sum(vals)) == lnl1
    assert not any(k[0] == "fast" for k in eng._fast_jit_cache)


def test_branch_length_change_hits_cache_correctly(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(2)
    inst.evaluate(tree, full=True)
    s = next(s for s, _ in tree.all_branches()
             if not tree.is_tip(s.number))
    new_z = [max(min(v * 0.7, 0.99), 1e-6) for v in s.z]
    hookup(s, s.back, new_z)
    h0 = _counter("engine.sched_cache.hit")
    lnl = inst.evaluate(tree, full=True)       # same topology: hit
    assert _counter("engine.sched_cache.hit") == h0 + 1
    # Fresh instance, same mutated tree: identical lnL.
    inst2 = PhyloInstance(data16)
    tree2 = inst2.random_tree(2)
    s2 = next(s for s, _ in tree2.all_branches()
              if not tree2.is_tip(s.number))
    hookup(s2, s2.back, new_z)
    assert inst2.evaluate(tree2, full=True) == lnl


def _nni(tree):
    """Deterministic NNI across the first inner-inner edge."""
    a = next(s for s, _ in tree.all_branches()
             if not tree.is_tip(s.number)
             and not tree.is_tip(s.back.number))
    b = a.back
    ax, by = a.next.back, b.next.back
    axz, byz = list(a.next.z), list(b.next.z)
    hookup(a.next, by, axz)
    hookup(b.next, ax, byz)


def test_topology_change_misses_and_matches_fresh(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(4)
    inst.evaluate(tree, full=True)
    _nni(tree)
    m0 = _counter("engine.sched_cache.miss")
    lnl = inst.evaluate(tree, full=True)       # new signature: miss
    assert _counter("engine.sched_cache.miss") >= m0 + 1
    inst2 = PhyloInstance(data16)
    tree2 = inst2.random_tree(4)
    _nni(tree2)
    assert inst2.evaluate(tree2, full=True) == lnl


def test_spr_move_through_commit_seam_with_cache(data16):
    """A real SPR rearrange + restore_tree_fast commit (the invalidation
    seam) stays bit-identical to the same move with the schedule cache
    disabled, and the post-commit full evaluate re-misses the cache."""
    from examl_tpu.constants import UNLIKELY
    from examl_tpu.search.spr import (SprContext, rearrange,
                                      restore_tree_fast)

    def run(disable_cache):
        inst = PhyloInstance(data16)
        tree = inst.random_tree(9)
        if disable_cache:
            for eng in inst.engines.values():
                eng._sched_cache_cap = 0
        inst.evaluate(tree, full=True)
        ctx = SprContext(inst)
        ctx.start_lh = ctx.end_lh = inst.likelihood
        ctx.best_of_node = UNLIKELY
        p = next(s for s in (tree.nodep[n]
                             for n in tree.inner_numbers())
                 if not tree.is_tip(s.back.number))
        assert rearrange(inst, tree, ctx, p, 1, 3)
        if ctx.end_lh > ctx.start_lh:
            restore_tree_fast(inst, tree, ctx)
        lnl = inst.evaluate(tree, full=True)
        return float(lnl), tree.to_newick(inst.alignment.taxon_names)

    m0 = _counter("engine.sched_cache.miss")
    lnl_c, nwk_c = run(False)
    assert _counter("engine.sched_cache.miss") > m0
    lnl_u, nwk_u = run(True)
    assert lnl_c == lnl_u
    assert nwk_c == nwk_u


def test_invalidate_counter_and_restore_cold(tmp_path, data16):
    from examl_tpu.search.checkpoint import CheckpointManager
    inst = PhyloInstance(data16)
    tree = inst.random_tree(6)
    inst.evaluate(tree, full=True)
    (eng,) = inst.engines.values()
    assert len(eng._sched_cache) == 1
    i0 = _counter("engine.sched_cache.invalidate")
    inst.invalidate_schedules()
    assert _counter("engine.sched_cache.invalidate") == i0 + 1
    assert len(eng._sched_cache) == 0
    inst.invalidate_schedules()                # empty: no double count
    assert _counter("engine.sched_cache.invalidate") == i0 + 1

    # -R restore: the cache is explicitly cold after a restore.
    mgr = CheckpointManager(str(tmp_path), "sc")
    inst.evaluate(tree, full=True)
    mgr.write("FAST_SPRS", {"radius": 1}, inst, tree)
    inst2 = PhyloInstance(data16)
    tree2 = inst2.random_tree(0)               # overwritten by restore
    m0 = _counter("engine.sched_cache.miss")
    blob = mgr.restore(inst2, tree2)
    assert blob is not None and blob["state"] == "FAST_SPRS"
    assert _counter("engine.sched_cache.miss") == m0 + 1  # cold rebuild
    assert inst2.likelihood == inst.likelihood


def test_per_partition_branches_flat_path(tmp_path):
    """C>1 branch vectors ride the cached z-refresh path intact."""
    import tempfile

    from examl_tpu.io.partitions import parse_partition_file

    rng = np.random.default_rng(1)
    names = [f"t{i}" for i in range(12)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 160))
            for _ in range(12)]
    spec = tmp_path / "parts.model"
    spec.write_text("DNA, g0 = 1-80\nDNA, g1 = 81-160\n")
    data = build_alignment_data(names, seqs,
                                specs=parse_partition_file(str(spec)))
    inst = PhyloInstance(data, per_partition_branches=True)
    assert inst.num_branch_slots == 2
    tree = inst.random_tree(8)
    lnl1 = inst.evaluate(tree, full=True)
    lnl2 = inst.evaluate(tree, full=True)      # hit path, C=2 z refresh
    assert lnl1 == lnl2
    inst2 = PhyloInstance(data, per_partition_branches=True)
    tree2 = inst2.random_tree(8)
    assert inst2.evaluate(tree2, full=True) == lnl1


def test_scan_tier_agrees_with_cached_fast_path(data16):
    inst = PhyloInstance(data16)
    tree = inst.random_tree(7)
    lnl_fast = inst.evaluate(tree, full=True)
    inst.evaluate(tree, full=True)             # exercise the hit path
    inst2 = PhyloInstance(data16)
    tree2 = inst2.random_tree(7)
    for eng in inst2.engines.values():
        eng.force_scan = True
    lnl_scan = inst2.evaluate(tree2, full=True)
    assert lnl_fast == pytest.approx(lnl_scan, rel=1e-12, abs=1e-7)


# -- setup-phase heartbeats (PARSE/PACK/SCHEDULE) ---------------------------


def test_phase_beats_emitted_by_setup_paths(monkeypatch, data16):
    from examl_tpu.parallel.packing import pack_partitions
    from examl_tpu.resilience import heartbeat

    states = []
    monkeypatch.setattr(heartbeat, "phase_beat",
                        lambda state="": states.append(state))
    names = [f"t{i}" for i in range(300)]
    tree = Tree.random(names, seed=0)
    text = tree.to_newick(names)
    Tree.from_newick(text, names)
    pack_partitions(data16.partitions)
    t16 = Tree.random([f"t{i}" for i in range(16)], seed=0)
    t16.flat_full_traversal(t16.nodep[1])
    assert "PARSE" in states and "PACK" in states \
        and "SCHEDULE" in states


def test_phase_beat_does_not_tick_search_fault_points(monkeypatch,
                                                      tmp_path):
    from examl_tpu.resilience import faults, heartbeat
    monkeypatch.setenv(faults.ENV_VAR, "heartbeat.stall:after=1")
    monkeypatch.setenv(heartbeat.ENV_VAR, str(tmp_path / "hb.json"))
    faults.reset()
    heartbeat.reset()
    try:
        # Setup-phase beats must NOT advance the search-iteration fault
        # clock (chaos specs address "the Nth search iteration").
        heartbeat.phase_beat("PARSE")
        heartbeat.phase_beat("PACK")
        rec = heartbeat.read(str(tmp_path / "hb.json"))
        assert rec is not None and rec["state"] == "PARSE"  # rate-limited
        # The first real search beat trips the armed stall fault.
        heartbeat.beat("FAST_SPRS")
        assert heartbeat._STATE["stalled"]
    finally:
        faults.reset()
        heartbeat.reset()


def test_phase_beats_keep_stall_detector_quiet_under_real_delay(
        monkeypatch, tmp_path):
    """A supervisor-style watcher (real wall clock, 1.0 s stall window)
    must never see a stall while a legitimate multi-second host setup
    phase runs and emits phase beats — a REAL delay, not a suppressed
    beat stream (the production loops below are the actual seams)."""
    import threading

    from examl_tpu.resilience import heartbeat

    hb = str(tmp_path / "hb.json")
    monkeypatch.setattr(heartbeat, "MIN_INTERVAL", 0.05)
    heartbeat.reset()
    heartbeat.install(hb)
    # Nominal worst beat age here is <0.1 s, but one build iteration
    # can stretch past 1 s under post-suite memory/CPU pressure on a
    # 2-CPU container; 1.5 s keeps >15x slack above nominal while
    # staying well below the ~2.2 s age a NO-beats regression reaches
    # by the deadline — the failure this test exists to catch.
    stall_window = 1.5
    worst = [0.0]
    stop = threading.Event()

    def watcher():
        while not stop.is_set():
            age = heartbeat.age(hb)
            if age is not None:
                worst[0] = max(worst[0], age)
            time.sleep(0.05)

    t = threading.Thread(target=watcher)
    t.start()
    try:
        names = [f"t{i}" for i in range(2000)]
        deadline = time.time() + 2.2
        while time.time() < deadline:       # >2x the stall window of
            tree = Tree.random(names, seed=1)   # real setup work
            tree.flat_full_traversal(tree.nodep[1])
    finally:
        stop.set()
        t.join()
        heartbeat.reset()
    rec = heartbeat.read(hb)
    assert rec is not None and rec["seq"] >= 2
    assert worst[0] < stall_window, worst[0]
