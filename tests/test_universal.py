"""Universal interpreter tier (ops/universal.py): topology-as-data
execution of the bounded chunk layout through ONE compiled program.

The equivalence contract: the interpreter runs the IDENTICAL chunk
sequence through the IDENTICAL `chunk_applier` arithmetic in the
IDENTICAL order as the specialized segment program, so lnL must be
bit-identical to the bounded chunk tier (and therefore to the scan
tier) — including -M C>1 branch slots, the SPR-commit seam, env-tuned
ladder alphabets, and replay-padded dispatches through larger
already-compiled buckets.  On top of that sits the point of the tier:
the jit key is bucket sizes + alphabet, NOT the profile, so evaluating
structurally distinct trees after the first compiles NOTHING new.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from examl_tpu import obs
from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data
from examl_tpu.ops import fastpath, universal
from examl_tpu.utils import bucket_len


def _synth(n=40, width=97, seed=0):
    rng = np.random.default_rng(seed)
    names = [f"t{i}" for i in range(n)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, width))
            for _ in range(n)]
    return build_alignment_data(names, seqs)


@pytest.fixture(scope="module")
def sdata():
    return _synth()


def _counter(name):
    return obs.counter(name)


def _eval(data, seed=3, env=None, force_scan=False, **kw):
    """Build an instance under optional env overrides (engines read
    EXAML_UNIVERSAL at construction), evaluate a
    random tree, restore the environment."""
    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        inst = PhyloInstance(data, **kw)
        tree = inst.random_tree(seed)
        if force_scan:
            for e in inst.engines.values():
                e.force_scan = True
        return inst, tree, inst.evaluate(tree, full=True)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


FORCE = {"EXAML_UNIVERSAL": "force"}


# -- the equivalence matrix --------------------------------------------------


def test_universal_matches_chunk_and_scan_bitwise(sdata):
    """Tentpole acceptance: interpreter vs specialized bounded-chunk vs
    scan tier, bit-identical lnL on the f64-path fixture."""
    inst_u, _, lnl_u = _eval(sdata, env=FORCE)
    (eng,) = inst_u.engines.values()
    assert any(k[0] == "universal" for k in eng._fast_jit_cache), \
        "forced universal run did not dispatch the interpreter"
    assert not any(k[0] == "fast" for k in eng._fast_jit_cache)
    _, _, lnl_c = _eval(sdata)
    _, _, lnl_s = _eval(sdata, force_scan=True)
    assert lnl_u == lnl_c
    assert lnl_u == lnl_s


def test_universal_per_partition_branches(sdata):
    """-M C>1 branch slots through the padded packed-z plumbing."""
    _, _, lnl_u = _eval(sdata, env=FORCE, per_partition_branches=True)
    _, _, lnl_c = _eval(sdata, per_partition_branches=True)
    assert lnl_u == lnl_c


def test_universal_env_tuned_alphabet(sdata, monkeypatch):
    """A retuned width ladder (fastpath.MIN_WIDTH / CHUNK_CAP) changes
    the alphabet; the interpreter must key on it and stay bit-identical
    to the specialized program under the same constants."""
    monkeypatch.setattr(fastpath, "MIN_WIDTH", 4)
    monkeypatch.setattr(fastpath, "CHUNK_CAP", 64)
    inst_u, _, lnl_u = _eval(sdata, env=FORCE)
    _, _, lnl_c = _eval(sdata)
    assert lnl_u == lnl_c
    (eng,) = inst_u.engines.values()
    assert any(k[:2] == ("universal", (4, 64))
               for k in eng._fast_jit_cache)
    assert universal.alphabet((4, 64)) != universal.alphabet((8, 1024))
    assert universal.alphabet((4, 64)) == ((0, 4), (1, 4), (2, 4))
    assert universal.width_ladder(4, 64) == (4, 8, 16, 32, 64)


def test_universal_after_spr_commit_seam(sdata):
    """A real SPR rearrange + commit, then a full evaluate: interpreter
    vs specialized chunk tier on the same moved tree, bit-identical."""
    from examl_tpu.constants import UNLIKELY
    from examl_tpu.search.spr import (SprContext, rearrange,
                                      restore_tree_fast)

    def run(env):
        saved = {k: os.environ.get(k) for k in env}
        for k, v in env.items():
            os.environ[k] = v
        try:
            inst = PhyloInstance(sdata)
            tree = inst.random_tree(9)
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
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    lnl_u, nwk_u = run(FORCE)
    lnl_c, nwk_c = run({})
    assert nwk_u == nwk_c
    assert lnl_u == lnl_c


def test_replay_padding_idempotent(sdata):
    """A dispatch through a LARGER bucket pair replays the final chunk
    (PR5 discipline) and pads the slot axis: real arena rows and
    scalers stay bit-equal to the reference unrolled execution."""
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    (eng,) = inst.engines.values()
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    flat = tree.flat_full_traversal(p)
    n = inst.alignment.ntaxa
    sch = fastpath.build_structure(flat, n)
    zl_h, zr_h = (np.asarray(a) for a in fastpath.refresh_z(
        sch, flat, 1, eng.dtype))
    knobs = eng._universal_akey()
    alpha = universal.alphabet(knobs)
    table = universal.build_table(sch.profile, np.asarray(sch.base), knobs)
    npad = bucket_len(table.n_chunks) + 8     # deliberately oversized
    ppad = bucket_len(table.slots) + 64
    cls, slot, base = universal.pad_table(table, npad)
    idx = [universal.pad_slots(np.asarray(a), ppad)
           for a in (sch.lidx, sch.ridx, sch.lcode, sch.rcode)]
    zl = jnp.asarray(universal.pad_slots(zl_h, ppad, fill=1), eng.dtype)
    zr = jnp.asarray(universal.pad_slots(zr_h, ppad, fill=1), eng.dtype)
    apply = fastpath.chunk_applier(eng.models, eng.block_part, eng.tips,
                                   eng.scale_exp, eng.fast_precision)
    c1, s1 = fastpath.run_chunks(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler),
        fastpath.structure_chunks(sch, zl_h, zr_h), eng.scale_exp,
        eng.fast_precision)
    c2, s2 = universal.run_universal(
        alpha, jnp.asarray(cls), jnp.asarray(slot), jnp.asarray(base),
        *(jnp.asarray(a) for a in idx), zl, zr, jnp.array(eng.clv),
        jnp.array(eng.scaler), apply.values)
    rows = np.sort(sch.row_of[sch.row_of >= 0])
    assert (np.asarray(c1)[rows] == np.asarray(c2)[rows]).all()
    assert (np.asarray(s1)[rows] == np.asarray(s2)[rows]).all()


# -- the point of the tier: zero compiles across topologies ------------------


def test_zero_compile_cross_topology(sdata):
    """Evaluate structurally DISTINCT trees (different profiles — the
    specialized tier would compile one program each): after the first
    dispatch, `engine.compile_count` must not move."""
    saved = os.environ.get("EXAML_UNIVERSAL")
    os.environ["EXAML_UNIVERSAL"] = "force"
    try:
        inst = PhyloInstance(sdata)
        (eng,) = inst.engines.values()
        trees = [inst.random_tree(s) for s in (3, 7, 11, 19, 23)]
        profiles = set()
        for t in trees:
            p = t.centroid_branch()
            if t.is_tip(p.number):
                p = p.back
            st = fastpath.build_structure(t.flat_full_traversal(p),
                                          inst.alignment.ntaxa)
            profiles.add(st.profile)
        assert len(profiles) >= 3, \
            "fixture regression: trees are not structurally distinct"
        lnl0 = inst.evaluate(trees[0], full=True)
        c0 = _counter("engine.compile_count")
        h0 = _counter("engine.cache_hits")
        u0 = _counter("engine.universal_dispatches")
        lnls = [inst.evaluate(t, full=True) for t in trees[1:]]
        assert _counter("engine.compile_count") == c0
        assert _counter("engine.cache_hits") >= h0 + len(trees) - 1
        assert _counter("engine.universal_dispatches") >= u0 + 4
        # One shared bucket pair = one resident interpreter program.
        assert len(eng._universal_minted(eng._universal_akey(),
                                         True)) == 1
        assert np.isfinite([lnl0] + lnls).all()
    finally:
        if saved is None:
            os.environ.pop("EXAML_UNIVERSAL", None)
        else:
            os.environ["EXAML_UNIVERSAL"] = saved


def test_novel_profile_routing_engine_level(sdata):
    """`route_novel_to_universal`: a profile with no specialized
    program dispatches the interpreter; once the specialized program
    exists, it wins (it is the faster warm path)."""
    inst = PhyloInstance(sdata)
    (eng,) = inst.engines.values()
    tree = inst.random_tree(3)
    eng.route_novel_to_universal = True
    lnl_u = inst.evaluate(tree, full=True)
    assert any(k[0] == "universal" for k in eng._fast_jit_cache)
    assert not any(k[0] == "fast" for k in eng._fast_jit_cache)
    eng.route_novel_to_universal = False
    lnl_c = inst.evaluate(tree, full=True)    # mints the specialized fn
    assert lnl_c == lnl_u
    assert any(k[0] == "fast" for k in eng._fast_jit_cache)
    eng.route_novel_to_universal = True
    u0 = _counter("engine.universal_dispatches")
    lnl2 = inst.evaluate(tree, full=True)
    assert lnl2 == lnl_u
    assert _counter("engine.universal_dispatches") == u0  # specialized won


# -- fleet/serve routing + profile-miss observability ------------------------


def test_fleet_routes_novel_profiles_and_counts_misses(sdata, tmp_path):
    """Driver-level: with routing on, tree jobs dispatch through the
    interpreter (no specialized fleet program minted), per-job lnL is
    bit-identical to the un-routed specialized run, and grouping time
    counts `fleet.profile_misses` + emits `job.profile_new`."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.obs import ledger as L

    def run(route):
        inst = PhyloInstance(sdata)
        drv = FleetDriver(inst, batch_cap=4, route_universal=route)
        out = drv.run(make_jobs("start", 3, 7))
        assert all(j.done and not j.failed for j in out)
        return inst, {j.job_id: j.lnl for j in out}

    L.reset()
    L.enable(str(tmp_path))
    try:
        m0 = _counter("fleet.profile_misses")
        inst_u, lnls_u = run(True)
        misses = _counter("fleet.profile_misses") - m0
        assert misses >= 1
        (eng,) = inst_u.engines.values()
        assert any(k[0] == "universal" for k in eng._fast_jit_cache)
        assert not any(k[0] in ("fleet", "fast")
                       for k in eng._fast_jit_cache)
        evs = [e for e in L.read_events(str(tmp_path / "ledger.p0.jsonl"))
               if e["kind"] == "job.profile_new"]
        assert len(evs) == misses
    finally:
        L.reset()
    _, lnls_c = run(False)
    assert lnls_u == lnls_c


def test_fleet_specialize_after_promotes(sdata):
    """EXAML_FLEET_SPECIALIZE_AFTER=1: a profile promotes to the
    specialized batched program on first sighting (routing becomes a
    pure pass-through), proving the promotion threshold is honored."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    os.environ["EXAML_FLEET_SPECIALIZE_AFTER"] = "1"
    try:
        inst = PhyloInstance(sdata)
        drv = FleetDriver(inst, batch_cap=4, route_universal=True)
        out = drv.run(make_jobs("start", 2, 7))
        assert all(j.done and not j.failed for j in out)
        (eng,) = inst.engines.values()
        assert any(k[0] == "fleet" for k in eng._fast_jit_cache)
    finally:
        os.environ.pop("EXAML_FLEET_SPECIALIZE_AFTER", None)


# -- units: alphabet / table / bucket picking --------------------------------


def test_table_splits_chunks_to_floor_width(sdata):
    """Every chunk the bounded planner emits expands into floor-width
    steps whose slot/base offsets tile the chunk exactly (per-entry
    arithmetic is width-batched, so the split is bitwise-invisible —
    the dispatch tests above prove it end to end)."""
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    if tree.is_tip(p.number):
        p = p.back
    st = fastpath.build_structure(tree.flat_full_traversal(p),
                                  inst.alignment.ntaxa)
    knobs = universal.alphabet_key()
    mw = knobs[0]
    table = universal.build_table(st.profile, np.asarray(st.base), knobs)
    chunks = list(fastpath.iter_profile_chunks(st.profile))
    base_h = np.asarray(st.base)
    assert table.n_chunks == sum(w // mw for _, w in chunks)
    i = off = 0
    for ci, (kind, w) in enumerate(chunks):
        for j in range(w // mw):
            assert table.cls[i] == kind
            assert table.slot[i] == off + j * mw
            assert table.base[i] == base_h[ci] + j * mw
            i += 1
        off += w
    assert table.slots == off == fastpath.profile_slots(st.profile)


def test_table_rejects_non_ladder_widths():
    with pytest.raises(universal.UniversalIneligible):
        universal.build_table((("u", 0, 2048),), np.zeros(1, np.int32),
                              knobs=(8, 1024))
    with pytest.raises(universal.UniversalIneligible):
        universal.build_table((("u", 1, 12),), np.zeros(1, np.int32),
                              knobs=(8, 1024))
    with pytest.raises(universal.UniversalIneligible):
        universal.build_table((), np.zeros(0, np.int32))


def test_pad_table_replays_final_chunk():
    t = universal.UniversalTable(
        n_chunks=3, slots=24,
        cls=np.array([2, 0, 1], np.int32),
        slot=np.array([0, 8, 16], np.int32),
        base=np.array([0, 8, 16], np.int32))
    cls, slot, base = universal.pad_table(t, 5)
    assert list(cls) == [2, 0, 1, 1, 1]
    assert list(slot) == [0, 8, 16, 16, 16]
    assert list(base) == [0, 8, 16, 16, 16]
    same = universal.pad_table(t, 3)
    assert same[0] is t.cls                   # no-copy fast path


def test_pick_pads_reuses_compiled_buckets():
    minted = set()
    nb, pb = bucket_len(10), bucket_len(100)
    assert universal.pick_pads(minted, 10, 100) == (nb, pb)
    minted.add((nb, pb))
    # A smaller table reuses the minted bucket (least waste wins) ...
    assert universal.pick_pads(minted, 9, 90) == (nb, pb)
    # ... until the 2x-of-REAL-size waste cap: a far larger compiled
    # bucket must not be reused (replay steps are real work), and the
    # cap is against the real counts, not the bucketed ones.
    big = {(100, 1000)}
    assert universal.pick_pads(big, 10, 100) == (nb, pb)
    assert universal.pick_pads({(2 * 10 + 1, pb)}, 10, 100) == (nb, pb)
    assert universal.pick_pads({(2 * 10, pb)}, 10, 100) == (2 * 10, pb)
    # A table that outgrows every minted bucket mints its own.
    assert universal.pick_pads(minted, nb + 1, 100) == \
        (bucket_len(nb + 1), pb)


# -- bank / ladder integration ----------------------------------------------


def test_bank_enumerates_universal_before_fast():
    from examl_tpu.ops import bank
    fams = bank.enumerate_families(env={})
    assert "universal" in fams and "fast" in fams
    assert fams.index("universal") < fams.index("fast")
    fams_off = bank.enumerate_families(env={"EXAML_UNIVERSAL": "0"})
    assert "universal" not in fams_off
    assert "universal" in bank.FALLBACK_ENV
    var, _ = bank.FALLBACK_ENV["universal"][0], None
    assert bank.FALLBACK_ENV["universal"][0] == ("EXAML_UNIVERSAL", "0")
    info = bank.chunk_layout_info()
    assert info["universal"]["enabled"]
    assert info["universal"]["alphabet_classes"] >= 3


def test_degradation_ladder_has_universal_rung():
    """chunk -> universal -> scan: the interpreter rung sits between
    the chunk tier and the scan floor, and the floor pins the
    interpreter OFF."""
    from examl_tpu.resilience import supervisor as sup
    rungs = list(sup.DEGRADE_LADDER)
    uni = next(i for i, r in enumerate(rungs)
               if r.get("EXAML_UNIVERSAL") == "force")
    scan = next(i for i, r in enumerate(rungs)
                if r.get("EXAML_FAST_TRAVERSAL") == "0")
    assert (uni, scan) == (2, 3) and len(rungs) == 4
    assert rungs[0] == rungs[1] == {}      # a plain retry comes first
    assert rungs[scan].get("EXAML_UNIVERSAL") == "0"


def test_ladder_floor_reached_within_retry_budget():
    """A --supervise-retries budget SMALLER than the ladder must still
    reach the scan-tier floor (the universal rung is skipped, not the
    floor): the escalation step is ceil(floor / budget)."""
    from examl_tpu.resilience import exitcause
    from examl_tpu.resilience import supervisor as sup

    class Stub:
        degrade_level = 0
    cause = next(iter(exitcause.TIER_SUSPECT))
    floor = len(sup.DEGRADE_LADDER) - 1
    for budget in (1, 2, 3, 5):
        st = Stub()
        st.max_retries = budget
        for _ in range(budget):
            sup.Supervisor._escalate(st, cause)
        assert st.degrade_level == floor, (budget, st.degrade_level)
    # The default budget still walks every rung in order.
    st = Stub()
    st.max_retries = sup.DEFAULT_RETRIES
    sup.Supervisor._escalate(st, cause)
    assert st.degrade_level == 1
    assert sup.DEGRADE_LADDER[st.degrade_level] == {}


def test_minted_buckets_track_resident_programs(sdata):
    """The bucket set `pick_pads` consults is DERIVED from the jit
    cache, so every invalidation path — LRU eviction, a bulk clear,
    a retuned ladder changing the
    alphabet key — drops gone programs automatically (reusing a gone
    bucket would silently recompile at a padded size forever)."""
    inst = PhyloInstance(sdata)
    (eng,) = inst.engines.values()
    eng.universal_force = True
    inst.evaluate(inst.random_tree(3), full=True)
    akey = eng._universal_akey()
    (pair,) = eng._universal_minted(akey, True)
    key = next(k for k in eng._fast_jit_cache if k[0] == "universal")
    assert (key[2], key[3]) == pair
    # A different alphabet key never sees this program's bucket.
    assert eng._universal_minted((4, 64), True) == set()
    # LRU eviction drops it ...
    eng._fast_jit_cache_cap = 1
    eng.cache_put(("dummy", 0), lambda *a: None)   # evicts universal
    assert key not in eng._fast_jit_cache
    assert eng._universal_minted(akey, True) == set()
    # ... and so does a bulk clear.
    eng._fast_jit_cache_cap = 32
    inst.evaluate(inst.random_tree(3), full=True)
    assert eng._universal_minted(akey, True) == {pair}
    eng._fast_jit_cache.clear()
    assert eng._universal_minted(akey, True) == set()


def test_profile_miss_not_counted_when_specialized_exists(sdata):
    """A profile whose specialized program already exists (bank warm /
    pre-universal run) is NOT a miss and is NOT routed — the counter
    only ever counts would-have-been compiles."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    inst = PhyloInstance(sdata)
    # Pre-compile the specialized program for job start7-job0's tree.
    drv0 = FleetDriver(inst, batch_cap=4, route_universal=False)
    drv0.run(make_jobs("start", 1, 7))
    m0 = _counter("fleet.profile_misses")
    drv = FleetDriver(inst, batch_cap=4, route_universal=True)
    out = drv.run(make_jobs("start", 1, 7))
    assert out[0].done and not out[0].failed
    assert _counter("fleet.profile_misses") == m0
    (eng,) = inst.engines.values()
    assert not any(k[0] == "universal" for k in eng._fast_jit_cache)


def test_universal_warm_family(sdata):
    """bank.warm_family('universal') compiles both interpreter variants
    (traverse-only + fused eval) so a banked serve does ZERO
    search-phase first-call compiles afterwards."""
    from examl_tpu.ops import bank
    inst = PhyloInstance(sdata)
    tree = inst.random_tree(3)
    assert bank._applicability(inst, "universal") is None
    bank.warm_family(inst, tree, "universal")
    (eng,) = inst.engines.values()
    keys = [k for k in eng._fast_jit_cache if k[0] == "universal"]
    assert {k[-1] for k in keys} == {False, True}
    # Post-warm: a DIFFERENT topology through the interpreter compiles
    # nothing (the serve acceptance, one level down).
    eng.universal_force = True
    c0 = _counter("engine.compile_count")
    inst.evaluate(inst.random_tree(11), full=True)
    assert _counter("engine.compile_count") == c0
