"""Precision bounds pinned (see NUMERICS.md).

The f32 engine (the TPU production configuration) must stay within the
documented lnL error bounds of the f64 engine on the reference test data;
on a real TPU backend the same comparison runs against the recorded f64
values (the driver's bench environment exercises that path).
"""

import jax
import jax.numpy as jnp
import pytest

from examl_tpu.instance import default_instance

from tests.conftest import TESTDATA

F64_LNL = {"49": -19685.568664, "140": -129866.801078}
ABS_BOUND = {"49": 5e-4, "140": 8e-2}      # covers the measured TPU
                                           # HIGHEST error (5.7e-2 on 140,
                                           # NUMERICS.md) with headroom


@pytest.mark.parametrize("name", ["49", "140"])
def test_f32_engine_within_documented_bound(name):
    inst = default_instance(f"{TESTDATA}/{name}",
                            f"{TESTDATA}/{name}.model", dtype=jnp.float32)
    with open(f"{TESTDATA}/{name}.tree") as f:
        tree = inst.tree_from_newick(f.read())
    lnl = inst.evaluate(tree, full=True)
    assert lnl == pytest.approx(F64_LNL[name], abs=ABS_BOUND[name])


def test_f64_engine_matches_recorded():
    inst = default_instance(f"{TESTDATA}/49", f"{TESTDATA}/49.model")
    with open(f"{TESTDATA}/49.tree") as f:
        tree = inst.tree_from_newick(f.read())
    assert inst.evaluate(tree, full=True) == pytest.approx(
        F64_LNL["49"], abs=1e-5)


def test_rerun_determinism():
    """Re-evaluating must be bit-identical (XLA's fixed reduction order —
    the property the reference needed MPI_Reduce+Bcast for,
    `makenewzGenericSpecial.c:1241-1248`)."""
    inst = default_instance(f"{TESTDATA}/49", f"{TESTDATA}/49.model",
                            dtype=jnp.float32)
    with open(f"{TESTDATA}/49.tree") as f:
        tree = inst.tree_from_newick(f.read())
    a = inst.evaluate(tree, full=True)
    b = inst.evaluate(tree, full=True)
    c = inst.evaluate(tree, full=True)
    assert a == b == c


@pytest.mark.slow
def test_bf16x3_child_dot_bound():
    """The fast path's default child-contraction precision (HIGH, 3-pass
    bf16) must stay inside the NUMERICS.md bound.  Emulated exactly as
    the MXU decomposes it: bf16 hi/lo split of both operands, hi*hi +
    hi*lo + lo*hi, f32 accumulation — applied ONLY to the child CLV
    contractions (P construction and root eval stay full precision)."""
    import functools

    import numpy as np

    from examl_tpu.ops import fastpath as fp

    orig_dg = jax.lax.dot_general

    def bf16x3(x, p):
        xh = x.astype(jnp.bfloat16).astype(jnp.float32)
        xl = (x - xh).astype(jnp.bfloat16).astype(jnp.float32)
        ph = p.astype(jnp.bfloat16).astype(jnp.float32)
        plo = (p - ph).astype(jnp.bfloat16).astype(jnp.float32)
        dn = (((3,), (2,)), ((0, 1), (0, 1)))
        d = functools.partial(orig_dg, dimension_numbers=dn)
        return d(xh, ph) + d(xh, plo) + d(xl, ph)

    def patched(lhs, rhs, dimension_numbers, precision=None, **kw):
        if (dimension_numbers == (((3,), (2,)), ((0, 1), (0, 1)))
                and lhs.ndim == 4 and lhs.dtype == jnp.float32):
            return bf16x3(lhs, rhs)
        return orig_dg(lhs, rhs, dimension_numbers, precision=precision,
                       **kw)

    inst = default_instance(f"{TESTDATA}/49", f"{TESTDATA}/49.model",
                            dtype=jnp.float32)
    with open(f"{TESTDATA}/49.tree") as f:
        tree = inst.tree_from_newick(f.read())
    exact = float(inst.evaluate(tree, full=True))

    eng = inst.engines[4]
    root = tree.centroid_branch()
    flat = tree.flat_full_traversal(root)
    sched = fp.build_structure(flat, eng.ntips)
    zl, zr = fp.refresh_z(sched, flat, eng.num_branch_slots, eng.dtype)
    chunks = fp.structure_chunks(sched, zl, zr)
    jax.lax.dot_general = patched
    fp.jax.lax.dot_general = patched
    try:
        clv, sc = fp.run_chunks(eng.models, eng.block_part, eng.tips,
                                jnp.array(eng.clv), jnp.array(eng.scaler),
                                chunks, eng.scale_exp,
                                jax.lax.Precision.HIGHEST)
    finally:
        jax.lax.dot_general = orig_dg
        fp.jax.lax.dot_general = orig_dg
    eng.clv, eng.scaler = clv, sc
    eng._install_row_map(sched)
    mixed = float(np.sum(eng.evaluate(root.number, root.back.number,
                                      root.z)))
    assert abs(mixed - exact) < 0.01, (mixed, exact)


@pytest.mark.slow
def test_bf16_clv_storage_bound(monkeypatch):
    """EXAML_CLV_DTYPE=bf16 (ROOFLINE.md lever 3: the arena stores bf16,
    compute stays f32 — halves HBM bytes/update) keeps the testData/49
    lnL within the measured 1.7-absolute bound (8.5e-5 relative), on
    both the fast chunk path and the scan path."""
    import jax.numpy as jnp

    from examl_tpu.instance import default_instance
    from tests.conftest import TESTDATA

    def build(env):
        if env:
            monkeypatch.setenv("EXAML_CLV_DTYPE", env)
        else:
            monkeypatch.delenv("EXAML_CLV_DTYPE", raising=False)
        inst = default_instance(f"{TESTDATA}/49", f"{TESTDATA}/49.model",
                                dtype=jnp.float32)
        tree = inst.tree_from_newick(open(f"{TESTDATA}/49.tree").read())
        full = float(inst.evaluate(tree, full=True))
        partial = float(inst.evaluate(tree, tree.nodep[tree.ntips + 5]))
        return inst, full, partial

    _, f32_full, f32_part = build("")
    inst, bf_full, bf_part = build("bf16")
    (eng,) = inst.engines.values()
    assert eng.clv.dtype == jnp.bfloat16
    assert abs(bf_full - f32_full) < 4.0, (bf_full, f32_full)
    assert abs(bf_part - f32_part) < 4.0, (bf_part, f32_part)
