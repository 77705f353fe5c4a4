"""Pallas TPU kernels for the fast-traversal chunk pipeline.

One fused kernel per case-split chunk (see ops/fastpath.py for the
schedule): the two child P-applications, the elementwise product, the
scaling check, and the arena write happen in ONE Mosaic program per wave
chunk, so the intermediate child products never round-trip through HBM
and no XLA fusion boundary can reintroduce layout copies.  This is the
SURVEY §7.2(9) Pallas step over the reference's newview inner loops
(ExaML `newviewGenericSpecial.c:1263-1497`; MIC tip-product analogue
`mic_native_dna.c:132-165`).

Memory plan per grid step w (one wave-chunk entry):

* child CLV rows are fetched by MANUAL async DMA from the arena with
  scalar-prefetched row numbers (`lidx`/`ridx`) — the arena is passed
  ONCE in `pl.ANY` space and aliased to the output, so XLA updates it in
  place (the arena is donated by the engine; a second blocked operand on
  the same buffer would force a defensive copy of the whole arena, the
  exact failure the fast path exists to avoid);
* P-matrix blocks (`pb*`, block-diagonal over rates) and tip-product
  tables (`um*`, MIC-style) are tiny, built in XLA per chunk, and stream
  through the automatic VMEM pipeline;
* results are DMA'd to arena row `base + w`.  Within one chunk no
  written row is ever read (children live in strictly earlier waves), so
  the in-place alias is race-free; across chunks the XLA data dependence
  serializes.

Only f32 is supported (TPU Pallas has no f64); the engine keeps the
plain-XLA fast path for CPU/f64 parity runs.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from examl_tpu.ops import kernels


HIGHEST = jax.lax.Precision.HIGHEST


def _dot_b(x, p, precision):
    """[B, L, K] x [B, K, N] -> [B, L, N], batched over B on the MXU."""
    return jax.lax.dot_general(
        x, p, (((2,), (1,)), ((0,), (0,))), precision=precision,
        preferred_element_type=jnp.float32)


def _one_hot_apply(codes, um, C, precision):
    """Tip-child P application: one-hot(code) @ um, [B,L] -> [B,L,RK]."""
    oh = (codes[:, :, None] ==
          jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2))
    return _dot_b(oh.astype(um.dtype), um, precision)


def _chunk_kernel(lidx_ref, ridx_ref, base_ref, clv_hbm, scaler_hbm,
                  opl_ref, opr_ref, lcode_ref, rcode_ref, scsum_ref,
                  clv_out, scaler_out,
                  xl_s, xr_s, v_s, sc_s, sem_l, sem_r, sem_v, sem_s,
                  *, kind: int, C: int, minlik: float, two_e: float,
                  precision):
    w = pl.program_id(0)
    b0 = base_ref[0]

    # Start child-row DMAs first so they overlap the tip-side compute.
    if kind == 2:
        cl = pltpu.make_async_copy(clv_hbm.at[lidx_ref[w]], xl_s, sem_l)
        cl.start()
    if kind >= 1:
        cr = pltpu.make_async_copy(clv_hbm.at[ridx_ref[w]], xr_s, sem_r)
        cr.start()

    if kind == 2:
        cl.wait()
        yl = _dot_b(xl_s[:], opl_ref[0], precision)
    else:
        yl = _one_hot_apply(lcode_ref[0], opl_ref[0], C, precision)
    if kind >= 1:
        cr.wait()
        yr = _dot_b(xr_s[:], opr_ref[0], precision)
    else:
        yr = _one_hot_apply(rcode_ref[0], opr_ref[0], C, precision)

    v = yl * yr
    needs = jnp.max(jnp.abs(v), axis=2) < minlik          # [B, L]
    v = jnp.where(needs[:, :, None], v * two_e, v)
    v_s[:] = v
    sc_s[:] = scsum_ref[0] + needs.astype(jnp.int32)

    cv = pltpu.make_async_copy(v_s, clv_out.at[b0 + w], sem_v)
    cs = pltpu.make_async_copy(sc_s, scaler_out.at[b0 + w], sem_s)
    cv.start()
    cs.start()
    cv.wait()
    cs.wait()


def _run_chunk(clv, scaler, lidx, ridx, base, opl, opr, lcodes, rcodes,
               scsum, *, kind: int, W: int, C: int, scale_exp: int,
               precision, interpret: bool):
    """One chunk: clv [rows,B,L,RK] f32, scaler [rows,B,L] int32.

    Traced inline under the caller's jit (the engine's fast-path program
    or the bench harness); the pallas_call's input_output_aliases keeps
    the arena update in place chunk to chunk.
    """
    rows, B, L, RK = clv.shape
    minlik = float(np.asarray(2.0, np.float64) ** (-scale_exp))
    two_e = float(np.asarray(2.0, np.float64) ** scale_exp)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    row3 = pl.BlockSpec((1, B, L), lambda w, *_: (w, 0, 0))

    in_specs = [
        any_spec,                                          # clv arena
        any_spec,                                          # scaler arena
        pl.BlockSpec((1,) + opl.shape[1:],
                     lambda w, *_: (w,) + (0,) * (opl.ndim - 1)),
        pl.BlockSpec((1,) + opr.shape[1:],
                     lambda w, *_: (w,) + (0,) * (opr.ndim - 1)),
        row3,                                              # lcodes
        row3,                                              # rcodes
        row3,                                              # scsum
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(W,),
        in_specs=in_specs,
        out_specs=[any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((B, L, RK), clv.dtype),             # xl
            pltpu.VMEM((B, L, RK), clv.dtype),             # xr
            pltpu.VMEM((B, L, RK), clv.dtype),             # v
            pltpu.VMEM((B, L), jnp.int32),                 # sc
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(
        _chunk_kernel, kind=kind, C=C, minlik=minlik, two_e=two_e,
        precision=precision)
    flops_dot = 2 * W * B * L * RK * (RK if kind == 2 else C) * 2
    clv, scaler = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(clv.shape, clv.dtype),
                   jax.ShapeDtypeStruct(scaler.shape, scaler.dtype)],
        # inputs: 0 lidx, 1 ridx, 2 base, 3 clv, 4 scaler, 5 opl, 6 opr,
        # 7 lcodes, 8 rcodes, 9 scsum
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=flops_dot, transcendentals=0,
            bytes_accessed=3 * W * B * L * RK * 4),
        interpret=interpret,
    )(lidx, ridx, base, clv, scaler, opl, opr, lcodes, rcodes, scsum)
    return clv, scaler


def _block_diag_p(p, block_part, eyeR):
    """[W,M,R,A,K] -> [W,B,RK,RK] block-diagonal over rates (exact)."""
    W, M, R, A, K = p.shape
    pb = jnp.einsum("wmrak,rs->wmrksa", p, eyeR).reshape(W, M, R * K, R * A)
    return pb[:, block_part]


def _ump(p, table, block_part):
    """MIC-style tip-product table: [W,B,C,RK]."""
    W, M, R, A, K = p.shape
    um = jnp.einsum("ck,wmrak->wmcra", table, p, precision=HIGHEST)
    return um.reshape(W, M, table.shape[0], R * A)[:, block_part]


def chunk_applier(models, block_part, tips, scale_exp: int,
                  precision=None, interpret: bool = False):
    """Per-chunk Pallas kernel body (f32 only): the fused-kernel twin of
    fastpath.chunk_applier, shared by the unrolled chunk loop and the
    bounded program's lax.scan group bodies (ops/fastpath.run_segments).
    The [rows,B,lane,R,K]<->[rows,B,lane,RK] reshapes around each call
    are layout metadata XLA elides.

    `precision` applies to the child CLV contractions only (all-positive
    sums; HIGH is within the NUMERICS.md budget); the ump/block-diagonal
    operand construction in XLA stays at HIGHEST.
    """
    if precision is None:
        precision = HIGHEST
    # NOTE: Mosaic rejects HIGH ("Unsupported dot precision: HIGH" on
    # v5e); only DEFAULT and HIGHEST lower.  An explicit HIGH is passed
    # through so harnesses sweeping precisions fail loudly rather than
    # silently measuring a duplicate HIGHEST row; the engine maps its
    # HIGH default to HIGHEST before dispatching here (engine.py
    # `pallas_precision`).
    C = tips.table.shape[0]

    def apply(clv, scaler, ch):
        rows, B, lane, R, K = clv.shape
        RK = R * K
        eyeR = jnp.eye(R, dtype=clv.dtype)
        clvf = clv.reshape(rows, B, lane, RK)
        pml = kernels.p_matrices_wave(models, ch.zl)       # [W,M,R,A,K]
        pmr = kernels.p_matrices_wave(models, ch.zr)
        W = ch.width
        if ch.kind == 0:
            opl = _ump(pml, tips.table, block_part)
            opr = _ump(pmr, tips.table, block_part)
            scsum = jnp.zeros((W, B, lane), jnp.int32)
        elif ch.kind == 1:
            opl = _ump(pml, tips.table, block_part)
            opr = _block_diag_p(pmr, block_part, eyeR)
            scsum = scaler[ch.ridx]
        else:
            opl = _block_diag_p(pml, block_part, eyeR)
            opr = _block_diag_p(pmr, block_part, eyeR)
            scsum = scaler[ch.lidx] + scaler[ch.ridx]
        # tip codes as int32 rows [W,B,lane] (uint8 gather done in XLA)
        lcodes = tips.codes[ch.lcode].astype(jnp.int32)
        rcodes = tips.codes[ch.rcode].astype(jnp.int32)
        base = (ch.base[None] if getattr(ch.base, "ndim", 0) == 0
                else ch.base)
        clvf, scaler = _run_chunk(
            clvf, scaler, ch.lidx, ch.ridx, base, opl, opr,
            lcodes, rcodes, scsum, kind=ch.kind, W=W, C=C,
            scale_exp=scale_exp, precision=precision, interpret=interpret)
        return clvf.reshape(rows, B, lane, R, K), scaler

    return apply


def run_chunks(models, block_part, tips, clv, scaler, chunks,
               scale_exp: int, precision=None,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Drop-in Pallas equivalent of fastpath.run_chunks (f32 only).

    Per-chunk host loop: each chunk is one pallas_call whose donated
    arena threads through, so the XLA data dependence serializes chunks
    while everything inside a chunk stays fused in VMEM.
    """
    apply = chunk_applier(models, block_part, tips, scale_exp,
                          precision=precision, interpret=interpret)
    for ch in chunks:
        clv, scaler = apply(clv, scaler, ch)
    return clv, scaler
