"""Pallas chunk kernels: interpret-mode parity with the XLA fast path.

The fused Mosaic kernels (ops/pallas_newview.py) must be drop-in
replacements for fastpath.run_chunks — same arena contents, same scaler
events — across datatypes and under heavy rescaling.  On CPU they run
through the Pallas interpreter; the TPU numerics of the contained
dot_generals are pinned separately by NUMERICS.md bounds.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from examl_tpu.instance import PhyloInstance  # noqa: E402
from examl_tpu.io.alignment import build_alignment_data  # noqa: E402
from examl_tpu.ops import fastpath, pallas_newview  # noqa: E402


def _instance(datatype, ntaxa, nsites, seed=0):
    rng = np.random.default_rng(seed)
    alphabet = {"AA": "ARNDCQEGHILKMFPSTWYV", "DNA": "ACGT"}[datatype]
    names = [f"t{i}" for i in range(ntaxa)]
    seqs = ["".join(alphabet[c]
                    for c in rng.integers(0, len(alphabet), nsites))
            for _ in names]
    ad = build_alignment_data(names, seqs, datatype_name=datatype)
    return PhyloInstance(ad, dtype=jnp.float32)


def _compare(inst, tree, z_override=None):
    eng = inst.engines[max(inst.engines)]
    _, entries = tree.full_traversal_centroid()
    if z_override is not None:
        from examl_tpu.tree.topology import TraversalEntry
        entries = [TraversalEntry(e.parent, e.left, e.right,
                                  [z_override] * len(e.zl),
                                  [z_override] * len(e.zr))
                   for e in entries]
    sched = eng._fast_schedule(entries)
    ref_clv, ref_sc = fastpath.run_chunks(
        eng.models, eng.block_part, eng.tips, eng.clv, eng.scaler,
        sched.chunks, eng.scale_exp, eng.fast_precision)
    pal_clv, pal_sc = pallas_newview.run_chunks(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler), sched.chunks, eng.scale_exp,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(ref_sc), np.asarray(pal_sc))
    np.testing.assert_allclose(np.asarray(ref_clv), np.asarray(pal_clv),
                               rtol=1e-6, atol=1e-7)
    return ref_sc


@pytest.mark.slow
def test_pallas_matches_fastpath_aa():
    inst = _instance("AA", 24, 300)
    _compare(inst, inst.random_tree(1))


def test_pallas_matches_fastpath_dna():
    inst = _instance("DNA", 30, 700)
    _compare(inst, inst.random_tree(2))


@pytest.mark.slow
def test_pallas_scaling_events_match():
    """Short branches force rescale events; the int32 scaler rows must be
    identical (they feed the lnL correction term)."""
    inst = _instance("DNA", 40, 256, seed=3)
    sc = _compare(inst, inst.random_tree(3), z_override=0.05)
    assert int(np.asarray(sc).sum()) > 0     # the test exercised rescaling


def test_engine_full_traversal_pallas(monkeypatch):
    """End to end through the engine: EXAML_PALLAS=1 asks for the
    kernels and EXAML_PALLAS_INTERPRET routes the jitted fast program
    through the interpreter; lnL must match the XLA fast path."""
    monkeypatch.delenv("EXAML_PALLAS", raising=False)
    inst = _instance("AA", 16, 200, seed=4)
    tree = inst.random_tree(4)
    lnl_ref = inst.evaluate(tree, full=True)

    monkeypatch.setenv("EXAML_PALLAS", "1")
    monkeypatch.setenv("EXAML_PALLAS_INTERPRET", "1")
    inst2 = _instance("AA", 16, 200, seed=4)
    eng2 = inst2.engines[20]
    assert eng2.use_pallas and eng2.pallas_interpret
    tree2 = inst2.random_tree(4)
    lnl_pal = inst2.evaluate(tree2, full=True)
    assert lnl_pal == pytest.approx(lnl_ref, abs=5e-3)


def test_engine_pallas_unset_is_xla_tier(monkeypatch):
    """With EXAML_PALLAS unset no engine takes a kernel tier, and the
    interpret switch alone does not ask for one."""
    monkeypatch.delenv("EXAML_PALLAS", raising=False)
    monkeypatch.setenv("EXAML_PALLAS_INTERPRET", "1")
    eng = _instance("DNA", 8, 64, seed=6).engines[4]
    assert not eng.use_pallas and not eng.pallas_whole


def test_engine_pallas_kernel_error_surfaces(monkeypatch):
    """A kernel that raises (what a Mosaic refusal looks like) is the
    run's error: nothing demotes the engine to the XLA tier."""
    monkeypatch.setenv("EXAML_PALLAS", "1")
    monkeypatch.setenv("EXAML_PALLAS_INTERPRET", "1")
    inst = _instance("DNA", 8, 64, seed=6)
    eng = inst.engines[4]
    assert eng.use_pallas

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pallas_newview, "chunk_applier", refuse)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        inst.evaluate(inst.random_tree(6), full=True)
    assert eng.use_pallas


def test_whole_traversal_matches_fastpath():
    """Stage-2 whole-traversal kernel (ops/pallas_whole.py): same CLVs
    and scalers as the chunked fast path, modulo row layout and f32
    rounding from the algebraically-equivalent tip expansion order."""
    from examl_tpu.ops import pallas_whole

    inst = _instance("AA", 24, 300)
    tree = inst.random_tree(1)
    eng = inst.engines[20]
    _, entries = tree.full_traversal_centroid()
    fsched = eng._fast_schedule(entries)
    ref_clv, ref_sc = fastpath.run_chunks(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler), fsched.chunks, eng.scale_exp,
        eng.fast_precision)
    wsched = pallas_whole.build_flat(entries, eng.ntips,
                                     eng.num_branch_slots)
    w_clv, w_sc = pallas_whole.run_flat(
        eng.models, eng.block_part, eng.tips, jnp.array(eng.clv),
        jnp.array(eng.scaler), wsched, eng.scale_exp, interpret=True)
    ref_clv, ref_sc = np.asarray(ref_clv), np.asarray(ref_sc)
    w_clv, w_sc = np.asarray(w_clv), np.asarray(w_sc)
    for num, frow in fsched.row_of.items():
        wrow = wsched.row_of[num]
        np.testing.assert_allclose(ref_clv[frow], w_clv[wrow],
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(ref_sc[frow], w_sc[wrow])


def test_engine_whole_mode(monkeypatch):
    """EXAML_PALLAS=whole routes full traversals (and the fused
    traverse+evaluate) through the single-kernel path; lnL must match."""
    inst = _instance("DNA", 20, 500, seed=5)
    tree = inst.random_tree(5)
    lnl_ref = inst.evaluate(tree, full=True)

    monkeypatch.setenv("EXAML_PALLAS", "whole")
    monkeypatch.setenv("EXAML_PALLAS_INTERPRET", "1")
    inst2 = _instance("DNA", 20, 500, seed=5)
    eng2 = inst2.engines[4]
    assert eng2.pallas_whole
    tree2 = inst2.random_tree(5)
    lnl_w = inst2.evaluate(tree2, full=True)
    assert lnl_w == pytest.approx(lnl_ref, abs=5e-3)
    # partial traversals after a full one read through the flat row map
    p = tree2.nodep[30]
    inst2.makenewz(tree2, p, p.back, list(p.z), maxiter=8)
    lnl3 = inst2.evaluate(tree2)
    assert lnl3 >= lnl_w - 1e-3
