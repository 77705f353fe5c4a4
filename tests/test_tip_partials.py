"""Tip partials by arithmetic (`kernels.tip_partials`): a tip's 0/1
indicator row comes from its state bitmask, never from an XLA gather of
`tips.table`.  The old lookup lives here only, as the reference: the
values must be the table's, bit for bit, for every code of every
datatype, and `gather_child` / `gather_child_pooled` must select exactly
what they selected with it."""

import jax.numpy as jnp
import numpy as np
import pytest

from examl_tpu import datatypes
from examl_tpu.ops import kernels

DATATYPES = {"DNA": datatypes.DNA, "AA": datatypes.AA,
             "BIN": datatypes.BINARY}


def table_lookup(tips, tip_idx):
    """The lookup `tip_partials` replaced (reference for the tests)."""
    return tips.table[tips.codes[tip_idx]]


def _tips(dt, codes, dtype):
    """TipState over host codes [ntips, B, lane], built the way
    `LikelihoodEngine._build_tip_state` builds it."""
    codes = np.asarray(codes, dtype=np.uint8)
    masks = dt.code_bitmasks[codes].astype(kernels.tip_mask_dtype(dt.states))
    return kernels.TipState(
        codes=jnp.asarray(codes), masks=jnp.asarray(masks),
        table=jnp.asarray(dt.tip_indicator_table(), dtype=dtype))


def _random_tips(dt, rng, ntips, B, lane, dtype):
    return _tips(dt, rng.integers(0, dt.num_codes, (ntips, B, lane)), dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("name", sorted(DATATYPES))
def test_tip_partials_equal_indicator_table(name, dtype):
    """Every code, ambiguity and gap codes included: one tip row whose
    lane axis walks the datatype's codes."""
    dt = DATATYPES[name]
    codes = np.arange(dt.num_codes)
    tips = _tips(dt, codes.reshape(1, 1, -1), dtype)
    got = np.asarray(kernels.tip_partials(tips, jnp.zeros((), jnp.int32)))
    assert got.dtype == dtype
    assert got.shape == (1, dt.num_codes, dt.states)
    want = dt.tip_indicator_table().astype(dtype)
    np.testing.assert_array_equal(got[0], want)
    assert kernels.tip_mask_dtype(dt.states) == (
        np.uint32 if dt.states > 8 else np.uint8)


@pytest.mark.parametrize("pooled", [False, True], ids=["dense", "pooled"])
@pytest.mark.parametrize("name", sorted(DATATYPES))
def test_gather_child_equals_table_select(name, pooled, monkeypatch):
    """A mixed tip / inner index vector (both ends of each range, a
    padding-style repeat) selects the same rows, bit for bit, as the
    old `table[codes]` select."""
    dt = DATATYPES[name]
    rng = np.random.default_rng(7)
    ntips, n_inner, B, lane, R, K = 6, 5, 3, 8, 4, dt.states
    tips = _random_tips(dt, rng, ntips, B, lane, np.float64)
    scaler = jnp.asarray(rng.integers(0, 3, (n_inner, B, lane)), jnp.int32)
    idx = jnp.asarray([[0, ntips - 1, ntips, ntips + n_inner - 1],
                       [3, ntips + 2, 0, 0]], jnp.int32)
    if pooled:
        cells = 1 + n_inner * B
        store = jnp.asarray(rng.random((cells, lane, R, K)))
        slot_read = jnp.asarray(
            rng.integers(0, cells, (n_inner, B)), jnp.int32)

        def gather():
            return kernels.gather_child_pooled(tips, store, slot_read,
                                               scaler, idx, ntips)
    else:
        store = jnp.asarray(rng.random((n_inner, B, lane, R, K)))

        def gather():
            return kernels.gather_child(tips, store, scaler, idx, ntips)

    x, sc = gather()
    assert x.shape == idx.shape + (B, lane, R, K)
    monkeypatch.setattr(kernels, "tip_partials", table_lookup)
    x_old, sc_old = gather()
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_old))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(sc_old))
    # and the selection itself: tips read 0/1 rows with scaler 0,
    # inner nodes the stored row.
    tip_rows = np.asarray(x)[0, 1]
    want = dt.tip_indicator_table()[np.asarray(tips.codes)[ntips - 1]]
    np.testing.assert_array_equal(
        tip_rows, np.broadcast_to(want[:, :, None, :], tip_rows.shape))
    assert not np.asarray(sc)[0, :2].any()
    if not pooled:
        np.testing.assert_array_equal(np.asarray(x)[0, 2],
                                      np.asarray(store)[0])
