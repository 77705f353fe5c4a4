"""Device kernels of the likelihood engine (jnp).

TPU-native re-design of the reference's hand-vectorized kernel inventory
(ExaML `newviewGenericSpecial.c`, `evaluateGenericSpecial.c`,
`makenewzGenericSpecial.c`, SSE3/AVX/MIC backends): ONE shape-polymorphic
kernel set over a packed site axis, with the state count (2/4/20), rate
count and partition count as static dimensions.  All functions are pure and
jit/vmap/shard-safe; the site axis is laid out as [B blocks x lane] so
per-partition P matrices are gathered per block (see parallel/packing.py).

Index conventions (einsum letters):
  b block, l lane, r rate category, j eigen index, a/k state, m partition,
  n CLV row, e traversal entry, c branch slot (per-partition branch lengths).

Every kernel traces under a `jax.named_scope` that survives XLA's
renumbering: `examl/newview`, `examl/evaluate`, `examl/sumtable`,
`examl/derivs`, `examl/outroot` here, `examl/edge_grad` in
ops/gradient.py.  Metadata only (an operation's `op_name`); where scopes
nest, the outermost names the kernel an operation belongs to.

CLV scaling follows the reference scheme (`newviewGenericSpecial.c:604-616`):
when every entry of a site's CLV drops below 2^-E the site is multiplied by
2^E and an integer per-(node, site) scaler increments; lnL adds
scaler * log(2^-E).  E is 256 for float64 (as the reference) and 64 for
float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# All contractions run at full input precision: on TPU the MXU otherwise
# truncates f32 operands to bf16, which costs ~4 decimal digits of CLV
# accuracy — far outside the reference-parity budget.  HIGHEST keeps f32
# einsums exact (multi-pass) and is a no-op for f64/CPU.
einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def _acc_dtype(dtype) -> jnp.dtype:
    """Accumulator dtype for site sums: f64 when x64 is live, else f32.

    Per-site values are fine in f32, but summing O(10^5)-magnitude lnL over
    many sites in f32 loses ~1e-2 absolute; the (cheap, elementwise) final
    reductions therefore accumulate in f64 whenever available.
    """
    if jnp.dtype(dtype) == jnp.float64 or jax.config.jax_enable_x64:
        return jnp.dtype(jnp.float64)
    return jnp.dtype(dtype)


class DeviceModels(NamedTuple):
    """Stacked per-partition model tensors for one state-count bucket.

    Eigensystems and frequencies carry a rate-category axis so LG4M/LG4X
    (one matrix per category, reference `makeP_FlexLG4`) and plain models
    (identical slices across R) share one kernel set.
    """
    eign: jax.Array         # [M, R, K]  negated eigenvalues, [...,0] == 0
    ev: jax.Array           # [M, R, K, K] right eigenvectors (columns)
    ei: jax.Array           # [M, R, K, K] left eigenvectors (rows)
    freqs: jax.Array        # [M, R, K]
    gamma_rates: jax.Array  # [M, R]
    rate_weights: jax.Array  # [M, R] category weights (1/R for GAMMA)
    part_branch: jax.Array  # [M] int32: branch slot per partition (0 if linked)


class Traversal(NamedTuple):
    """Fixed-size padded traversal descriptor (host-built).

    Entries are wave-scheduled (`Tree.schedule_waves`): axis 0 runs over
    dependency waves executed sequentially, axis 1 over the independent
    entries of a wave executed as one batched newview.  `parent` indexes
    INNER CLV rows (node number - ntips - 1); `left`/`right` are 0-based
    node indices (tips < ntips resolve against `TipState`, the
    reference's yVector+tipVector scheme — tip CLVs are never stored).
    Padding entries point children at node 0 and the parent at the
    scratch row.
    """
    parent: jax.Array       # [L, W] int32 inner CLV row
    left: jax.Array         # [L, W] int32 node index (tip or inner)
    right: jax.Array        # [L, W] int32
    zl: jax.Array           # [L, W, C] branch z to left child
    zr: jax.Array           # [L, W, C]


class TipState(NamedTuple):
    """Device-resident tip data, [ntips, B, lane] a field: the packed
    state codes (the chunk tier contracts their one-hot with
    `table`) and, for the jnp kernels, each site's state BITMASK
    (`DataType.code_bitmasks[codes]`, bit k set where state k is
    compatible), from which `tip_partials` makes the 0/1 partial row by
    arithmetic.  `table`'s dtype is the compute dtype."""
    codes: jax.Array        # [ntips, B, lane] uint8 state codes
    masks: jax.Array        # [ntips, B, lane] uint8/uint32 state bitmasks
    table: jax.Array        # [num_codes, K] 0/1 indicator vectors


def tip_mask_dtype(K: int) -> np.dtype:
    """Narrowest unsigned type holding a K-bit state mask (uint8 for DNA
    and binary, uint32 for protein)."""
    return np.min_scalar_type((1 << K) - 1)


def tip_partials(tips: TipState, tip_idx: jax.Array) -> jax.Array:
    """0/1 partials [..., B, lane, K] of tip rows tip_idx [...], in the
    compute dtype: bit k of each site's state mask.

    The one place the jnp kernels turn tip data into indicator vectors.
    The values are `tips.table[tips.codes[tip_idx]]` exactly, made by
    elementwise arithmetic because that lookup is an XLA gather of
    K-element rows, which a v5e ran at ~2 ns a site: two thirds of the
    gradient program's loops (PERF.md §6, PR 29).  `masks[tip_idx]` is a
    gather of whole contiguous rows of a byte a site: cheap at every
    width (131 KB a row at 131,072 patterns, never split), as a CLV
    or scaler row's gather is only up to 128 blocks: those rows, in the
    gradient pass and in the traversal alike, are read through
    `take_rows`."""
    K = tips.table.shape[1]
    masks = tips.masks[tip_idx]                      # [..., B, lane]
    bits = (masks[..., None] >> jnp.arange(K, dtype=masks.dtype)) & 1
    return bits.astype(tips.table.dtype)


def _select_tip(tips: TipState, idx: jax.Array, ntips: int,
                inner_clv: jax.Array, inner_sc: jax.Array):
    """Per child of idx [...]: the tip's partials (broadcast over the R
    rate categories) with scaler 0 where idx < ntips, else the inner
    node's row inner_clv [..., B, lane, R, K] and scaler inner_sc."""
    is_tip = idx < ntips
    tip_clv = tip_partials(tips, jnp.clip(idx, 0, ntips - 1))
    tip_clv = jnp.broadcast_to(tip_clv[..., None, :], inner_clv.shape)
    x = jnp.where(is_tip[..., None, None, None, None], tip_clv, inner_clv)
    sc = jnp.where(is_tip[..., None, None], 0, inner_sc)
    return x, sc


# Widest row, in sites (blocks x lanes), that the TPU compiler gathers
# in one piece: 128 blocks of 128 lanes, whatever R, K and the dtype.
ONE_PIECE_SITES = 128 * 128


def take_rows(arena: jax.Array, idx: jax.Array) -> jax.Array:
    """`arena[idx]`, bit for bit: whole rows [B, lane, ...] of an arena
    [rows, B, lane, ...] at in-range row indices idx [...].

    Every read of whole arena rows inside a device loop comes here: the
    gradient pass (`outroot_pass`, `gather_child`,
    `gradient.edge_gradients`; PR 32) and the traversal
    (`fastpath.chunk_applier`'s child rows and scalers, PR 34; the scan
    tier through `gather_child`).

    Up to `ONE_PIECE_SITES` sites a row it IS `arena[idx]`, a gather
    the compiler runs near the HBM roofline.  A wider gather the v5e
    compiler cuts into B/128 pieces by slicing its OPERAND: every piece
    copies its share of the whole arena, in every iteration of the loop
    the read sits in, to take 8 or 32 rows of it.  A row is a
    contiguous block of HBM and its index a scalar, so there a loop
    over idx reads each row by a dynamic slice into a
    [len(idx), B, lane, ...] block: the rows' bytes and no more.  The
    form follows from the arena's shape alone.  (PERF.md §6, PR 32:
    the loop costs a 1 MiB row 2 us more than its gather; unrolled,
    the slices cost a relayout copy of each whole arena.)

    Under GSPMD (the site-sharded traversal programs) the shape seen
    here is the GLOBAL one, so a four-chip run between 16,385 and
    65,536 global patterns takes the loop where a shard's row is one
    piece and its gather would do: 2 us a row, no cell is there, no
    knob for it.  Inside `shard_map` (the gradient pass) it is the
    shard's."""
    idx = jnp.asarray(idx)
    if idx.ndim == 0 or arena.shape[1] * arena.shape[2] <= ONE_PIECE_SITES:
        return arena[idx]
    rows = jax.lax.map(
        lambda i: jax.lax.dynamic_index_in_dim(arena, i, 0, keepdims=False),
        idx.reshape(-1))
    return rows.reshape(idx.shape + arena.shape[1:])


def take_row(arena: jax.Array, i: jax.Array, shards: int = 1) -> jax.Array:
    """`arena[i][None]`, bit for bit: ONE row [1, B, lane, R, K] of a
    K > R arena (protein), R even; the traversal's one-entry step.

    A one-row dynamic slice hands the dot the arena's own layout, and
    the v5e keeps a K = 20 arena's R second-minor: for the slice the
    compiler copies the WHOLE arena into the dot's layout and back,
    three times a call (PERF.md section 6).  So the row comes as
    a gather of its two halves of the rate axis, which the compiler
    expands into slices copied into a block of the dot's layout: the
    row's bytes, not the arena's.  A gather wider than `ONE_PIECE_SITES`
    it would cut by slicing the whole arena (`take_rows`: a 156-block
    arena ten times slower a step, PERF.md section 6), so a wider row
    is gathered in n pieces of at most that many sites along the block
    axis, each half of the rate axis, and joined again.

    n counts a SHARD's blocks (`shards`, the site axis' mesh): under
    GSPMD the shape seen here is the global one, and a shard's row of
    one piece keeps the gather that indexes no block, the axis the mesh
    cuts.  A shard's row of more pieces would index it; no deployment
    is there (PERF.md section 7)."""
    _, B, lane, R, K = arena.shape
    h = R // 2
    n = -(-(B // shards) // max(1, ONE_PIECE_SITES // lane))
    # n pieces of b blocks, the last one moved back to end at B (it
    # overlaps the one before where n * b > B); one piece indexes no block
    b = -(-B // n)
    firsts = [min(j * b, B - b) for j in range(n)]
    axes = (0, 3) if n == 1 else (0, 1, 3)
    starts = jnp.stack([
        jnp.stack([i, jnp.full_like(i, r)] if n == 1 else
                  [i, jnp.full_like(i, s), jnp.full_like(i, r)])
        for s in firsts for r in (0, h)])
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(1, 2, 3, 4), collapsed_slice_dims=(0,),
        start_index_map=axes)
    pieces = jax.lax.gather(
        arena, starts, dn, (1, b, lane, h, K),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    # [n x 2, b, lane, R/2, K] -> [n, b, lane, R, K] -> the row
    pieces = jnp.moveaxis(pieces.reshape(n, 2, b, lane, h, K),
                          1, 3).reshape(n, b, lane, R, K)
    return jnp.concatenate(
        [pieces[j, j * b - s:min((j + 1) * b, B) - s]
         for j, s in enumerate(firsts)])[None]


def gather_child(tips: TipState, clv: jax.Array, scaler: jax.Array,
                 idx: jax.Array, ntips: int):
    """CLV + scaler of child nodes given 0-based node indices idx [...].

    Tips (idx < ntips) materialize their indicator vectors from their
    state masks on the fly (`tip_partials`, scaler 0); inner nodes read
    the stored CLV row (idx - ntips) through `take_rows`: a gather up
    to 128 blocks, a dynamic slice a row above, where the gather would
    copy the arena.  Both sides are computed for every child and a
    select picks.
    """
    idx = jnp.asarray(idx)          # plain ints (static callers) included
    inner_idx = jnp.clip(idx - ntips, 0, clv.shape[0] - 1)
    # astype: the arena may store CLVs in a narrower dtype (bf16 storage
    # tier, EXAML_CLV_DTYPE) — the cast happens after the (halved) HBM
    # read and is a no-op when storage == compute.
    inner_clv = take_rows(clv, inner_idx).astype(tips.table.dtype)
    return _select_tip(tips, idx, ntips, inner_clv,
                       take_rows(scaler, inner_idx))


def default_scale_exponent(dtype, backend: str | None = None) -> int:
    """Rescale threshold exponent E (threshold 2^-E, multiplier 2^E).

    float64 on CPU uses the reference's 256.  On TPU float64 is emulated as
    float-float pairs whose exponent range is float32's (underflow near
    2^-126), and float32 anywhere has the same floor — both need rescaling
    long before products of two CLVs approach 2^-126, so use 32.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    if jnp.dtype(dtype) == jnp.float64 and backend == "cpu":
        return 256
    return 32


def scale_constants(dtype, scale_exp: int):
    e = scale_exp
    two_e = jnp.asarray(2.0, dtype) ** e
    minlik = jnp.asarray(2.0, dtype) ** (-e)
    log_min = -e * jnp.log(jnp.asarray(2.0, dtype))
    return minlik, two_e, log_min


def branch_decay(models: DeviceModels, z: jax.Array) -> jax.Array:
    """d[m, r, j] = exp(eign_rj * rate_r * log z_m), the eigenvalue decay.

    z: [C] per-branch-slot values; each partition selects its slot.
    Mirrors reference `makeP`/`makeP_FlexLG4`
    (`newviewGenericSpecial.c:78-206`).
    """
    zm = z[models.part_branch]                              # [M]
    lz = jnp.log(zm)
    return jnp.exp(models.eign
                   * models.gamma_rates[:, :, None]
                   * lz[:, None, None])                     # [M, R, K]


def p_matrices(models: DeviceModels, z: jax.Array) -> jax.Array:
    """P[m, r, a, k] = sum_j ev[r,a,j] d[r,j] ei[r,j,k] per partition."""
    d = branch_decay(models, z)
    return einsum("mraj,mrj,mrjk->mrak", models.ev, d, models.ei)


def apply_p(pmat: jax.Array, block_part: jax.Array, x: jax.Array) -> jax.Array:
    """y[b,l,r,a] = sum_k P[part(b),r,a,k] * x[b,l,r,k]."""
    pb = pmat[block_part]                                   # [B, R, K, K]
    return einsum("brak,blrk->blra", pb, x)


def p_matrices_wave(models: DeviceModels, z: jax.Array) -> jax.Array:
    """P[w, m, r, a, k] for one wave of branch vectors z [W, C]."""
    d = jax.vmap(lambda zz: branch_decay(models, zz))(z)    # [W, M, R, K]
    return einsum("mraj,wmrj,mrjk->wmrak", models.ev, d, models.ei)


def psr_decay(models: DeviceModels, block_part: jax.Array,
              site_rates: jax.Array, z: jax.Array) -> jax.Array:
    """Per-site eigenvalue decay d[b,l,r,j] = exp(eign_j * rate_blr * log z).

    The PSR (CAT) analogue of `branch_decay`: every site carries its own
    rate multiplier (reference per-site `patrat`/`rateCategory`,
    `optimizeModel.c:1792-2507`), so the transition matrix differs per
    site and is never materialized — newview/evaluate apply it in
    factorized form (EI contraction, decay scaling, EV contraction).
    site_rates: [B, lane, R] (R = 1 in normal PSR compute; R = G during
    the batched rate-grid scan).
    """
    zb = z[models.part_branch][block_part]                  # [B]
    lz = jnp.log(zb)
    # PSR models are single-category; use the category-0 eigensystem.
    eb = models.eign[block_part][:, 0, :]                   # [B, K]
    return jnp.exp(eb[:, None, None, :]
                   * site_rates[:, :, :, None]
                   * lz[:, None, None, None])               # [B, lane, R, K]


def apply_p_factorized(models: DeviceModels, block_part: jax.Array,
                       d: jax.Array, x: jax.Array) -> jax.Array:
    """y = EV · (d * (EI · x)) with per-site decay d [..., B, lane, R, K].

    Equivalent to applying P(z, r_site) without building per-site P
    matrices; the two contractions are MXU matmuls over the state axis.
    """
    eib = models.ei[block_part][:, 0]                       # [B, K, K] (PSR)
    evb = models.ev[block_part][:, 0]
    u = einsum("bjk,...blrk->...blrj", eib, x)
    u = u * d
    return einsum("baj,...blrj->...blra", evb, u)


@jax.named_scope("examl/newview")
def newview_wave(models: DeviceModels, block_part: jax.Array,
                 xl: jax.Array, xr: jax.Array,
                 zl: jax.Array, zr: jax.Array, scale_exp: int,
                 site_rates=None):
    """Combine child CLVs into parent CLVs for one wave of W entries.

    xl, xr: [W, B, lane, R, K]; zl, zr: [W, C].
    Returns (clv [W,B,lane,R,K], scale_inc [W,B,lane]).
    Reference semantics: `newviewGAMMA_FLEX` (`newviewGenericSpecial.c:430-682`)
    and the CAT kernels when site_rates is given, batched over independent
    traversal entries.
    """
    if site_rates is None:
        pl = p_matrices_wave(models, zl)[:, block_part]     # [W, B, R, K, K]
        pr = p_matrices_wave(models, zr)[:, block_part]
        yl = einsum("wbrak,wblrk->wblra", pl, xl)
        yr = einsum("wbrak,wblrk->wblra", pr, xr)
    else:
        dl = jax.vmap(lambda zz: psr_decay(models, block_part, site_rates,
                                           zz))(zl)         # [W, B, l, R, K]
        dr = jax.vmap(lambda zz: psr_decay(models, block_part, site_rates,
                                           zz))(zr)
        yl = apply_p_factorized(models, block_part, dl, xl)
        yr = apply_p_factorized(models, block_part, dr, xr)
    v = yl * yr
    minlik, two_e, _ = scale_constants(v.dtype, scale_exp)
    vmax = jnp.max(jnp.abs(v), axis=(3, 4))                 # [W, B, lane]
    needs = vmax < minlik
    v = jnp.where(needs[:, :, :, None, None], v * two_e, v)
    return v, needs.astype(jnp.int32)


@jax.named_scope("examl/newview")
def traverse(models: DeviceModels, block_part: jax.Array, tips: TipState,
             clv: jax.Array, scaler: jax.Array, tv: Traversal,
             scale_exp: int, ntips: int, site_rates=None):
    """Execute a wave-scheduled traversal: lax.scan over waves, each wave a
    batched newview over its independent entries.

    clv: [Ninner, B, lane, R, K]; scaler: [Ninner, B, lane] int32 (inner
    nodes + one scratch row; tip children materialize from `tips`).
    Padding entries write to the scratch row (host sets parent=Ninner-1);
    within a wave the scatter indices are unique except for scratch
    duplicates, whose value is never read.
    Reference: `newviewIterative` (`newviewGenericSpecial.c:917-1515`).
    """
    def body(carry, e):
        clv, scaler = carry
        parent, left, right, zl, zr = e
        xl, sl = gather_child(tips, clv, scaler, left, ntips)
        xr, sr = gather_child(tips, clv, scaler, right, ntips)
        v, inc = newview_wave(models, block_part, xl, xr,
                              zl, zr, scale_exp, site_rates)
        sc = sl + sr + inc                                  # [W, B, lane]
        clv = clv.at[parent].set(v.astype(clv.dtype),
                                 unique_indices=False)
        scaler = scaler.at[parent].set(sc, unique_indices=False)
        return (clv, scaler), None

    (clv, scaler), _ = jax.lax.scan(
        body, (clv, scaler),
        (tv.parent, tv.left, tv.right, tv.zl, tv.zr))
    return clv, scaler


def gather_child_pooled(tips: TipState, pool: jax.Array,
                        slot_read: jax.Array, scaler: jax.Array,
                        idx: jax.Array, ntips: int):
    """SEV variant of `gather_child`: inner CLVs live in a block-cell pool.

    pool: [S, lane, R, K]; slot_read: [rows, B] int32 mapping (row, block)
    to a pool cell, with all-gap cells mapped to the shared constant
    all-ones cell 0 — the TPU-native form of the reference's single shared
    `gapColumn` CLV per node (`newviewGenericSpecial.c:139-160`).
    """
    idx = jnp.asarray(idx)
    row = jnp.clip(idx - ntips, 0, slot_read.shape[0] - 1)
    cells = slot_read[row]                           # [..., B]
    inner_clv = pool[cells].astype(tips.table.dtype)  # [..., B, lane, R, K]
    return _select_tip(tips, idx, ntips, inner_clv, scaler[row])


@jax.named_scope("examl/newview")
def traverse_pooled(models: DeviceModels, block_part: jax.Array,
                    tips: TipState, pool: jax.Array, slot_read: jax.Array,
                    slot_write: jax.Array, scaler: jax.Array,
                    tv: Traversal, scale_exp: int, ntips: int,
                    site_rates=None):
    """SEV traversal: like `traverse`, but CLV cells live in the pool.

    slot_write maps all-gap (row, block) cells to a scratch cell whose
    content is never read; their value is the constant cell 0 on the read
    side, so all-gap subtrees cost one shared cell of memory — the
    reference's `-S` design (`axml.c:2152-2171`, `_GAPPED_SAVE` kernels)
    re-expressed as static-shape pool indirection.
    """
    def body(carry, e):
        pool, scaler = carry
        parent, left, right, zl, zr = e
        xl, sl = gather_child_pooled(tips, pool, slot_read, scaler, left,
                                     ntips)
        xr, sr = gather_child_pooled(tips, pool, slot_read, scaler, right,
                                     ntips)
        v, inc = newview_wave(models, block_part, xl, xr,
                              zl, zr, scale_exp, site_rates)
        sc = sl + sr + inc                               # [W, B, lane]
        cells = slot_write[parent]                       # [W, B]
        pool = pool.at[cells].set(v.astype(pool.dtype),
                                  unique_indices=False)
        scaler = scaler.at[parent].set(sc, unique_indices=False)
        return (pool, scaler), None

    (pool, scaler), _ = jax.lax.scan(
        body, (pool, scaler),
        (tv.parent, tv.left, tv.right, tv.zl, tv.zr))
    return pool, scaler


class OutrootTraversal(NamedTuple):
    """Fixed-size padded PRE-ORDER traversal descriptor (host-built by
    ops/gradient.py): the post-order wave schedule executed in REVERSE
    wave order, each entry emitting the root-directed (outroot)
    partials of its two children.  `up_row` indexes the outroot arena
    (node number - 1; every node has a row, the last row is scratch);
    `left`/`right` are gather indices against the post-order CLV arena
    (tips by code slot, inner by ntips + arena row, exactly
    `gather_child`'s convention).  `zu` is the branch ABOVE the entry's
    parent node (the root edge z for the two root-adjacent entries).
    Padding entries read and write the scratch row."""
    up_row: jax.Array       # [L, W] int32 outroot-arena row of the parent
    lrow: jax.Array         # [L, W] int32 outroot row written for left
    rrow: jax.Array         # [L, W] int32 outroot row written for right
    left: jax.Array         # [L, W] int32 gather index of left child
    right: jax.Array        # [L, W] int32 gather index of right child
    zu: jax.Array           # [L, W, C] branch above the parent
    zl: jax.Array           # [L, W, C]
    zr: jax.Array           # [L, W, C]


def outroot_wave(models: DeviceModels, block_part: jax.Array,
                 xu: jax.Array, xl: jax.Array, xr: jax.Array,
                 zu: jax.Array, zl: jax.Array, zr: jax.Array,
                 scale_exp: int, site_rates=None):
    """Sibling-combine for one wave of W pre-order entries.

    xu: the parent's outroot partial [W, B, lane, R, K] (complement of
    the parent's subtree, located at the grandparent's end of the
    parent's upper branch); xl, xr: the children's post-order CLVs.
    Returns (out_l, out_r): out_l = (P(zu) xu) * (P(zr) xr) is the
    complement of the LEFT child's subtree located at the parent — the
    mirror image of `newview_wave`'s child combine, with the sibling's
    down partial standing in for one child and the transported outroot
    partial for the other (Ji et al. 2303.04390's pre-order recursion;
    BEAGLE 4.1's edge-derivative pre-order buffers).

    Rescaling applies the same threshold/multiplier discipline as
    `newview_wave` but tracks NO counts: every edge-gradient consumer
    is a dsite/lsite ratio in which per-site scale factors cancel
    exactly (`nr_derivatives` never reads scalers), so keeping the
    values in floating range is sufficient.
    """
    if site_rates is None:
        pu = p_matrices_wave(models, zu)[:, block_part]     # [W, B, R, K, K]
        pl = p_matrices_wave(models, zl)[:, block_part]
        pr = p_matrices_wave(models, zr)[:, block_part]
        yu = einsum("wbrak,wblrk->wblra", pu, xu)
        yl = einsum("wbrak,wblrk->wblra", pl, xl)
        yr = einsum("wbrak,wblrk->wblra", pr, xr)
    else:
        du = jax.vmap(lambda zz: psr_decay(models, block_part, site_rates,
                                           zz))(zu)          # [W, B, l, R, K]
        dl = jax.vmap(lambda zz: psr_decay(models, block_part, site_rates,
                                           zz))(zl)
        dr = jax.vmap(lambda zz: psr_decay(models, block_part, site_rates,
                                           zz))(zr)
        yu = apply_p_factorized(models, block_part, du, xu)
        yl = apply_p_factorized(models, block_part, dl, xl)
        yr = apply_p_factorized(models, block_part, dr, xr)
    minlik, two_e, _ = scale_constants(yu.dtype, scale_exp)

    def rescale(v):
        vmax = jnp.max(jnp.abs(v), axis=(3, 4))             # [W, B, lane]
        return jnp.where((vmax < minlik)[:, :, :, None, None], v * two_e, v)

    return rescale(yu * yr), rescale(yu * yl)


@jax.named_scope("examl/outroot")
def outroot_pass(models: DeviceModels, block_part: jax.Array,
                 tips: TipState, clv: jax.Array, scaler: jax.Array,
                 out: jax.Array, tv: OutrootTraversal, scale_exp: int,
                 ntips: int, site_rates=None) -> jax.Array:
    """Execute a pre-order traversal: lax.scan over reversed waves, each
    wave a batched `outroot_wave` over its independent entries — the
    exact mirror of `traverse`, filling the outroot arena `out`
    [2*ntips-1, B, lane, R, K] (rows by node number - 1, last row
    scratch) instead of the CLV arena.  `out` must arrive with the two
    root rows initialized (out[p-1] = D(q), out[q-1] = D(p)); `clv` and
    `scaler` are read-only (the post-order partials)."""
    def body(carry, e):
        out = carry
        up_row, lrow, rrow, left, right, zu, zl, zr = e
        xu = take_rows(out, up_row)
        xl, _ = gather_child(tips, clv, scaler, left, ntips)
        xr, _ = gather_child(tips, clv, scaler, right, ntips)
        ol, orr = outroot_wave(models, block_part, xu, xl, xr,
                               zu, zl, zr, scale_exp, site_rates)
        out = out.at[lrow].set(ol.astype(out.dtype), unique_indices=False)
        out = out.at[rrow].set(orr.astype(out.dtype), unique_indices=False)
        return out, None

    out, _ = jax.lax.scan(
        body, out, (tv.up_row, tv.lrow, tv.rrow, tv.left, tv.right,
                    tv.zu, tv.zl, tv.zr))
    return out


def site_likelihoods(models: DeviceModels, block_part: jax.Array,
                     xp: jax.Array, xq: jax.Array, z: jax.Array,
                     site_rates=None):
    """Per-site likelihood L[b,l] at the root branch (p,q) with branch z.

    L = sum_r w_r sum_k f_k * xp_k * (P(z) xq)_k
    Reference: `evaluateGAMMA_FLEX` (`evaluateGenericSpecial.c:154-231`) or
    the CAT evaluate kernels when site_rates is given.
    """
    if site_rates is None:
        y = apply_p(p_matrices(models, z), block_part, xq)  # [B,l,R,K]
    else:
        d = psr_decay(models, block_part, site_rates, z)
        y = apply_p_factorized(models, block_part, d, xq)
    fb = models.freqs[block_part]                           # [B, R, K]
    wb = models.rate_weights[block_part]                    # [B, R]
    return einsum("brk,br,blrk,blrk->bl", fb, wb, xp, y)


def per_rate_site_lnls(models: DeviceModels, block_part: jax.Array,
                       tips: TipState, clv: jax.Array, scaler: jax.Array,
                       p_idx, q_idx, z: jax.Array, site_rates: jax.Array,
                       scale_exp: int, ntips: int):
    """Per-site, per-rate-candidate log likelihood [B, lane, R].

    The batched on-device replacement for the reference's per-site rate
    scan (`evaluatePartialGeneric` called once per site per trial rate,
    `optimizeModel.c:1792-1922`): one traversal per rate-grid chunk
    produces every site's lnL under every candidate rate at once.
    """
    xp, sp = gather_child(tips, clv, scaler, p_idx, ntips)
    xq, sq = gather_child(tips, clv, scaler, q_idx, ntips)
    d = psr_decay(models, block_part, site_rates, z)
    y = apply_p_factorized(models, block_part, d, xq)
    fb = models.freqs[block_part][:, 0]                     # [B, K] (PSR)
    lsite = einsum("bk,blrk,blrk->blr", fb, xp, y)          # [B, lane, R]
    acc = _acc_dtype(lsite.dtype)
    _, _, log_min = scale_constants(acc, scale_exp)
    sc = (sp + sq).astype(acc)                              # [B, lane]
    lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
    return jnp.log(lsite).astype(acc) + sc[:, :, None] * log_min


@jax.named_scope("examl/evaluate")
def root_log_likelihood(models: DeviceModels, block_part: jax.Array,
                        weights: jax.Array, tips: TipState,
                        clv: jax.Array, scaler: jax.Array,
                        p_idx, q_idx, z: jax.Array, num_parts: int,
                        scale_exp: int, ntips: int, site_rates=None):
    """Per-partition log likelihoods [M] after a traversal.

    weights: [B, lane] pattern weights (0 on padding); p_idx/q_idx are
    0-based node indices (tip or inner).
    Reference: `evaluateGeneric` + the lnL Allreduce
    (`evaluateGenericSpecial.c:897-1001`); here the cross-device sum is the
    segment/jnp sum over the sharded block axis (XLA inserts the collective).
    """
    xp, sp = gather_child(tips, clv, scaler, p_idx, ntips)
    xq, sq = gather_child(tips, clv, scaler, q_idx, ntips)
    return root_log_likelihood_from(models, block_part, weights, xp, sp,
                                    xq, sq, z, num_parts, scale_exp,
                                    site_rates)


@jax.named_scope("examl/evaluate")
def root_log_likelihood_from(models: DeviceModels, block_part: jax.Array,
                             weights: jax.Array, xp, sp, xq, sq,
                             z: jax.Array, num_parts: int, scale_exp: int,
                             site_rates=None, axis_name=None):
    """root_log_likelihood over pre-gathered root CLVs (pooled/SEV path).

    axis_name: set when tracing under shard_map (SEV x sharding) — the
    segment sum then only covers the device-local blocks, so the
    cross-device half of the reference's lnL Allreduce
    (`evaluateGenericSpecial.c:968-973`) is an explicit psum here
    (GSPMD inserts it automatically on the dense path; shard_map does
    not)."""
    lsite = site_likelihoods(models, block_part, xp, xq, z, site_rates)
    acc = _acc_dtype(lsite.dtype)
    _, _, log_min = scale_constants(acc, scale_exp)
    sc = (sp + sq).astype(acc)
    lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
    site_lnl = weights.astype(acc) * (jnp.log(lsite).astype(acc)
                                      + sc * log_min)       # [B, lane]
    block_lnl = jnp.sum(site_lnl, axis=1)                   # [B]
    out = jax.ops.segment_sum(block_lnl, block_part, num_segments=num_parts)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    return out


@jax.named_scope("examl/derivs")
def newton_raphson_branch(models: DeviceModels, block_part: jax.Array,
                          weights: jax.Array, st: jax.Array, z0: jax.Array,
                          maxiters0: jax.Array, conv0: jax.Array,
                          num_slots: int, site_rates=None, axis_name=None):
    """Branch-length Newton-Raphson to convergence, fully on device.

    Replaces the reference's host-driven NR loop with one Allreduce per
    iteration (`topLevelMakenewz`, `makenewzGenericSpecial.c:1133-1349`)
    by a single `lax.while_loop` whose body computes the derivative sums
    (with their cross-device psum via the sharded-site reduction) — the
    fusion SURVEY §7.3(2) calls out as the key latency fix on TPU.

    Semantics per branch slot (mirroring the reference, including the
    bad-curvature branch-shortening z <- 0.37 z + 0.63, the 0.25 zprev +
    0.75 step cap, and the give-up-after-(maxiter+20) reset to z0):
    iterate z <- z * exp(-lnL'/lnL'') until |z - zprev| <= zstep.
    """
    from examl_tpu.constants import ZMAX, ZMIN

    acc = _acc_dtype(st.dtype)
    z0a = z0.astype(acc)
    zmin = jnp.asarray(ZMIN, acc)
    zmax = jnp.asarray(ZMAX, acc)

    def derivs(z):
        d1, d2 = nr_derivatives(models, block_part, weights, st,
                                z.astype(st.dtype), num_slots, site_rates,
                                axis_name)
        return d1.astype(acc), d2.astype(acc)

    def cond(s):
        return ~jnp.all(s[4])

    def body(s):
        z, zprev, zstep, maxiters, outer, curvat = s
        fresh = ~outer & curvat
        zprev = jnp.where(fresh, z, zprev)
        zstep = jnp.where(fresh, (1.0 - ZMAX) * z + ZMIN, zstep)
        curvat = jnp.where(fresh, False, curvat)
        z = jnp.clip(z, zmin, zmax)
        d1, d2 = derivs(z)
        active = ~outer & ~curvat
        bad = active & (d2 >= 0.0) & (z < zmax)
        z = jnp.where(bad, 0.37 * z + 0.63, z)
        zprev = jnp.where(bad, z, zprev)
        curvat = jnp.where(active & ~bad, True, curvat)
        step = curvat & ~outer
        tantmp = jnp.where(d2 < 0.0, -d1 / jnp.where(d2 < 0.0, d2, 1.0),
                           jnp.inf)
        cap = 0.25 * zprev + 0.75
        znr = jnp.where(tantmp < 100.0,
                        jnp.maximum(z * jnp.exp(jnp.minimum(tantmp, 100.0)),
                                    zmin),
                        cap)
        znr = jnp.minimum(znr, cap)
        z2 = jnp.where(step & (d2 < 0.0), znr, z)
        z2 = jnp.minimum(z2, zmax)
        maxiters = jnp.where(step, maxiters - 1, maxiters)
        moving = jnp.abs(z2 - zprev) > zstep
        gave_up = moving & (maxiters < -20)
        z2 = jnp.where(step & gave_up, z0a, z2)
        outer = jnp.where(step, ~moving | gave_up, outer)
        return (z2, zprev, zstep, maxiters, outer, curvat)

    init = (z0a, z0a, jnp.zeros_like(z0a), maxiters0, conv0,
            jnp.ones_like(conv0))
    z, *_ = jax.lax.while_loop(cond, body, init)
    return z


@jax.named_scope("examl/sumtable")
def sumtable(models: DeviceModels, block_part: jax.Array,
             xp: jax.Array, xq: jax.Array) -> jax.Array:
    """st[b,l,r,j] = (sum_k f_rk xp_k ev_r[k,j]) * (sum_k ei_r[j,k] xq_k).

    With this table L(lz) = sum_j st_j exp(eign_rj rate_r lz) per site, so
    branch derivatives w.r.t. lz = log z are cheap per NR iteration.
    Reference: `makenewzIterative` sum kernels
    (`makenewzGenericSpecial.c:251-326`).
    """
    evb = models.ev[block_part]                             # [B, R, K, K]
    eib = models.ei[block_part]
    fb = models.freqs[block_part]                           # [B, R, K]
    ap = einsum("brk,blrk,brkj->blrj", fb, xp, evb)
    bq = einsum("brjk,blrk->blrj", eib, xq)
    return ap * bq


@jax.named_scope("examl/derivs")
def nr_derivatives(models: DeviceModels, block_part: jax.Array,
                   weights: jax.Array, st: jax.Array, z: jax.Array,
                   num_slots: int, site_rates=None, axis_name=None):
    """(lnL', lnL'') w.r.t. lz summed over sites, per branch slot [C].

    Reference: `coreGAMMA_FLEX` / `coreGTRCAT` + derivative Allreduce
    (`makenewzGenericSpecial.c:394-619, 1241-1248`).
    """
    wb = models.rate_weights[block_part]                    # [B, R]
    if site_rates is None:
        d = branch_decay(models, z)                         # [M, R, K]
        e1 = models.eign * models.gamma_rates[:, :, None]   # [M, R, K]
        db = d[block_part]                                  # [B, R, K]
        e1b = e1[block_part]
        lsite = einsum("br,blrj,brj->bl", wb, st, db)
        dsite = einsum("br,blrj,brj,brj->bl", wb, st, db, e1b)
        d2site = einsum("br,blrj,brj,brj,brj->bl", wb, st, db, e1b, e1b)
    else:
        db = psr_decay(models, block_part, site_rates, z)   # [B, l, R, K]
        e1b = (models.eign[block_part][:, 0][:, None, None, :]
               * site_rates[:, :, :, None])                 # [B, l, R, K]
        lsite = einsum("br,blrj,blrj->bl", wb, st, db)
        dsite = einsum("br,blrj,blrj,blrj->bl", wb, st, db, e1b)
        d2site = einsum("br,blrj,blrj,blrj,blrj->bl", wb, st, db, e1b, e1b)

    lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
    acc = _acc_dtype(lsite.dtype)
    dlnl = (dsite / lsite).astype(acc)
    d2lnl = (d2site / lsite).astype(acc) - dlnl * dlnl
    wacc = weights.astype(acc)
    blk_d1 = jnp.sum(wacc * dlnl, axis=1)
    blk_d2 = jnp.sum(wacc * d2lnl, axis=1)
    per_part_d1 = jax.ops.segment_sum(blk_d1, block_part,
                                      num_segments=models.eign.shape[0])
    per_part_d2 = jax.ops.segment_sum(blk_d2, block_part,
                                      num_segments=models.eign.shape[0])
    d1 = jax.ops.segment_sum(per_part_d1, models.part_branch,
                             num_segments=num_slots)
    d2 = jax.ops.segment_sum(per_part_d2, models.part_branch,
                             num_segments=num_slots)
    if axis_name is not None:                # shard_map (SEV x sharding):
        d1 = jax.lax.psum(d1, axis_name)     # the derivative Allreduce
        d2 = jax.lax.psum(d2, axis_name)     # (makenewz...c:1241-1248)
    return d1, d2
