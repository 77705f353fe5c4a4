"""Whole-traversal Pallas kernel: one Mosaic program per full traversal.

Stage 2 of the SURVEY §7.2(9) Pallas path (stage 1 = per-chunk kernels,
ops/pallas_newview.py): the ENTIRE wave-scheduled traversal runs as one
`pallas_call` with grid=(entries,), eliminating every XLA op boundary
between chunks and letting output DMA overlap the next entry's compute.

Uniformity: a one-hot tip contraction costs the same MXU passes as the
dense child dot (both pad to 128 lanes), so tip children are expanded
in-kernel from their uint8 codes with a rate-tiled indicator table
`tab2[c, (r,k)] = table[c,k]` — ONE dot, no case split; every grid step
is identical:

  x_child = is_tip ? one_hot(codes) @ tab2 : DMA(clv[row])
  y       = x_child @ blockdiag_R(P)       (streamed from XLA; HIGH
                                            precision, all-positive sums,
                                            NUMERICS.md)
  v       = yl * yr, rescale check, async DMA out to clv[write_row]

Write-after-read safety: children always come from earlier waves and at
most ONE output copy is ever in flight (single landing slot), so a wait
on the pending copy at each wave boundary — flagged by the prefetched
`sync[e]` bit — is sufficient; within a wave the copy overlaps compute.

Reference semantics: `newviewIterative` over a full traversal
(`newviewGenericSpecial.c:917-1515`), tip handling per the MIC tip
scheme (`mic_native_dna.c:132-165`).  f32 only, like stage 1.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from examl_tpu.ops import kernels
from examl_tpu.tree.topology import Tree, TraversalEntry


class FlatSchedule(NamedTuple):
    """Wave-ordered per-entry metadata (host arrays)."""
    e_real: int                 # entry count
    meta: np.ndarray            # [E, 8] int32: l_tip r_tip l_row r_row
                                #                w_row sync pad pad
    l_code: np.ndarray          # [E] tip index of left child (or 0)
    r_code: np.ndarray
    zl: np.ndarray              # [E, C]
    zr: np.ndarray
    row_of: Dict[int, int]


def build_flat(entries: List[TraversalEntry], ntips: int,
               num_slots: int) -> FlatSchedule:
    """Wave-order entries; parents take consecutive arena rows from 0
    (same row-layout discipline as the chunked fast path)."""
    from examl_tpu.utils import z_slots

    waves = Tree.schedule_waves(entries)
    flat: List[TraversalEntry] = []
    sync_flags: List[int] = []
    for wave in waves:
        for i, e in enumerate(wave):
            flat.append(e)
            sync_flags.append(1 if i == 0 else 0)
    E = len(flat)
    row_of: Dict[int, int] = {e.parent: i for i, e in enumerate(flat)}

    def child(num: int) -> Tuple[int, int, int]:
        if num <= ntips:
            return 1, 0, num - 1
        return 0, row_of[num], 0

    meta = np.zeros((E, 8), np.int32)
    l_code = np.zeros(E, np.int32)
    r_code = np.zeros(E, np.int32)
    zl = np.ones((E, num_slots))
    zr = np.ones((E, num_slots))
    for i, e in enumerate(flat):
        lt, lr, lc = child(e.left)
        rt, rr, rc = child(e.right)
        meta[i, :6] = (lt, rt, lr, rr, i, sync_flags[i])
        l_code[i], r_code[i] = lc, rc
        zl[i] = z_slots(e.zl, num_slots)
        zr[i] = z_slots(e.zr, num_slots)
    return FlatSchedule(e_real=E, meta=meta, l_code=l_code, r_code=r_code,
                        zl=zl, zr=zr, row_of=row_of)


def _kernel(meta_ref, clv_hbm, scaler_hbm, pb_ref, codes_ref, tab_ref,
            clv_out, scaler_out,
            xl_s, xr_s, scl_s, scr_s, v_s, sc_s,
            sem_xl, sem_sl, sem_xr, sem_sr, sem_v, sem_sc,
            *, E: int, C: int, minlik: float, two_e: float,
            precision):
    e = pl.program_id(0)
    l_tip = meta_ref[e, 0]
    r_tip = meta_ref[e, 1]
    l_row = meta_ref[e, 2]
    r_row = meta_ref[e, 3]
    w_row = meta_ref[e, 4]
    sync = meta_ref[e, 5]

    def out_wait():
        pltpu.make_async_copy(v_s, clv_out.at[0], sem_v).wait()
        pltpu.make_async_copy(sc_s, scaler_out.at[0], sem_sc).wait()

    # Wave boundary: the (single) in-flight output copy must land before
    # this wave reads any arena row.
    @pl.when(jnp.logical_and(sync == 1, e > 0))
    def _():
        out_wait()

    # Child fetches: DMA for inner children, in-kernel one-hot expansion
    # for tips (started first so the DMA overlaps the tip dots).
    @pl.when(l_tip == 0)
    def _():
        pltpu.make_async_copy(clv_out.at[l_row], xl_s, sem_xl).start()
        pltpu.make_async_copy(scaler_out.at[l_row], scl_s, sem_sl).start()

    @pl.when(r_tip == 0)
    def _():
        pltpu.make_async_copy(clv_out.at[r_row], xr_s, sem_xr).start()
        pltpu.make_async_copy(scaler_out.at[r_row], scr_s, sem_sr).start()

    tab = tab_ref[:]                                        # [C, RK]

    def tip_x(codes):                                       # [B, L] int32
        oh = (codes[:, :, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2))
        return jax.lax.dot_general(oh.astype(tab.dtype), tab,
                                   (((2,), (0,)), ((), ())),
                                   precision=precision)     # [B, L, RK]

    def dot_b(x, pb):
        return jax.lax.dot_general(
            x, pb, (((2,), (1,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32)

    @pl.when(l_tip == 1)
    def _():
        xl_s[:] = tip_x(codes_ref[0, 0])
        scl_s[:] = jnp.zeros_like(scl_s)

    @pl.when(r_tip == 1)
    def _():
        xr_s[:] = tip_x(codes_ref[0, 1])
        scr_s[:] = jnp.zeros_like(scr_s)

    @pl.when(l_tip == 0)
    def _():
        pltpu.make_async_copy(clv_out.at[l_row], xl_s, sem_xl).wait()
        pltpu.make_async_copy(scaler_out.at[l_row], scl_s, sem_sl).wait()

    @pl.when(r_tip == 0)
    def _():
        pltpu.make_async_copy(clv_out.at[r_row], xr_s, sem_xr).wait()
        pltpu.make_async_copy(scaler_out.at[r_row], scr_s, sem_sr).wait()

    yl = dot_b(xl_s[:], pb_ref[0, 0])
    yr = dot_b(xr_s[:], pb_ref[0, 1])
    v = yl * yr
    needs = jnp.max(jnp.abs(v), axis=2) < minlik            # [B, L]
    v = jnp.where(needs[:, :, None], v * two_e, v)
    sc = scl_s[:] + scr_s[:] + needs.astype(jnp.int32)

    # The landing slot is reused every entry: mid-wave, wait the previous
    # entry's copy before overwriting (its target row is disjoint from
    # everything this wave reads, so only the slot needs protecting).
    @pl.when(jnp.logical_and(sync == 0, e > 0))
    def _():
        out_wait()

    v_s[:] = v
    sc_s[:] = sc
    pltpu.make_async_copy(v_s, clv_out.at[w_row], sem_v).start()
    pltpu.make_async_copy(sc_s, scaler_out.at[w_row], sem_sc).start()

    @pl.when(e == E - 1)                                    # drain
    def _():
        out_wait()


def run_flat(models, block_part, tips, clv, scaler, sched: FlatSchedule,
             scale_exp: int, precision=None, interpret: bool = False):
    """Execute a flat schedule as ONE pallas_call.  clv [rows,B,L,R,K]."""
    return run_flat_arrays(models, block_part, tips, clv, scaler,
                           sched.e_real, jnp.asarray(sched.meta),
                           jnp.asarray(sched.l_code),
                           jnp.asarray(sched.r_code), sched.zl, sched.zr,
                           scale_exp, precision, interpret)


def run_flat_arrays(models, block_part, tips, clv, scaler, E: int,
                    meta, l_code, r_code, zl, zr, scale_exp: int,
                    precision=None, interpret: bool = False):
    """Traceable form: schedule as arrays (meta is the scalar-prefetch
    operand; E is static)."""
    if precision is None:
        # Explicit HIGH passes through and fails in Mosaic lowering —
        # see pallas_newview.run_chunks_pallas; the engine maps HIGH to
        # HIGHEST for the Pallas tiers (engine.py `pallas_precision`).
        precision = jax.lax.Precision.HIGHEST
    rows, B, L, R, K = clv.shape
    RK = R * K
    C = tips.table.shape[0]
    minlik = float(np.asarray(2.0, np.float64) ** (-scale_exp))
    two_e = float(np.asarray(2.0, np.float64) ** scale_exp)

    # Every P matrix of the traversal in one batched einsum, expanded to
    # block-diagonal form in XLA and streamed per entry: [E, 2, B, RK, RK].
    eyeR = jnp.eye(R, dtype=clv.dtype)

    def blockdiag(z):
        p = kernels.p_matrices_wave(models, jnp.asarray(z, clv.dtype))
        pb = jnp.einsum("wmrak,rs->wmrksa", p, eyeR)
        return pb.reshape(pb.shape[0], -1, RK, RK)[:, block_part]

    pb_all = jnp.stack([blockdiag(zl), blockdiag(zr)], axis=1)

    codes = jnp.stack([tips.codes[l_code].astype(jnp.int32),
                       tips.codes[r_code].astype(jnp.int32)],
                      axis=1)                               # [E, 2, B, L]

    # tab2[c, (r,k)] = table[c, k]: the rate-tiled tip indicator, so a
    # tip expands with ONE dot.  Tiled in-graph so the whole function
    # is traceable.
    tab2 = jnp.tile(tips.table.astype(jnp.float32), (1, R))

    clvf = clv.reshape(rows, B, L, RK)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E,),
        in_specs=[
            any_spec,                                       # clv
            any_spec,                                       # scaler
            pl.BlockSpec((1, 2, B, RK, RK),
                         lambda e, *_: (e, 0, 0, 0, 0)),
            pl.BlockSpec((1, 2, B, L), lambda e, *_: (e, 0, 0, 0)),
            pl.BlockSpec((C, RK), lambda e, *_: (0, 0)),    # tab2
        ],
        out_specs=[any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((B, L, RK), clv.dtype),              # xl
            pltpu.VMEM((B, L, RK), clv.dtype),              # xr
            pltpu.VMEM((B, L), jnp.int32),                  # scl
            pltpu.VMEM((B, L), jnp.int32),                  # scr
            pltpu.VMEM((B, L, RK), clv.dtype),              # v slot
            pltpu.VMEM((B, L), jnp.int32),                  # sc slot
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(
        _kernel, E=E, C=C, minlik=minlik, two_e=two_e,
        precision=precision)
    clvf, scaler = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(clvf.shape, clvf.dtype),
                   jax.ShapeDtypeStruct(scaler.shape, scaler.dtype)],
        # inputs: 0 meta, 1 clv, 2 scaler, 3 pb_all, 4 codes, 5 tab2
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(meta, clvf, scaler, pb_all, codes, tab2)
    return clvf.reshape(rows, B, L, R, K), scaler
