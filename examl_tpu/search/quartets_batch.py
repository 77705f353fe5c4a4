"""Batched quartet scoring: many quartets x 3 topologies in one dispatch.

The reference scores one quartet topology at a time inside the big tree
structure: 5 branches hooked up, ~16 NNI smoothing passes each doing a
per-branch Newton update, then one evaluation — every step a separate
newview/evaluate/derivative round-trip (`quartets.c:176-323`).  On TPU
that is ~80 dispatches per topology for microscopic 4-taxon compute.

A quartet tree needs NO CLV arena: with tip vectors t_a..t_d and the 5
branch lengths, every directional CLV is a closed-form product

    x_ab = P(z1) t_a ⊙ P(z2) t_b        x_cd = P(z3) t_c ⊙ P(z4) t_d

so the ENTIRE procedure — smoothing passes (each branch one Newton step
to the reference's update() semantics, DELTAZ movement test, early stop
when a pass moves nothing) and the final evaluation — runs as one jitted
program vmapped over jobs = quartets x topologies.  Scaling is omitted:
a 4-taxon product of two P-applied tip vectors is bounded well above
every rescale threshold (min entry ~ P_min^2 >> 2^-32).

Eligible when the instance has ONE state bucket, ONE branch slot, GAMMA
rates, and no SEV pool; the sequential path remains for everything else
and under EXAML_BATCH_QUARTETS=0.  Output rows and their order are
identical to the sequential scorer.
"""

from __future__ import annotations

import os
from typing import List, Sequence, Tuple

import numpy as np

from examl_tpu.constants import DEFAULTZ, DELTAZ

JOB_CHUNK = 48          # jobs per dispatch (= 16 quartet sets)


def batch_eligible(inst) -> bool:
    if os.environ.get("EXAML_BATCH_QUARTETS", "1") == "0":
        return False
    if getattr(inst, "psr", False) or inst.num_branch_slots != 1:
        return False
    if len(inst.engines) != 1:
        return False
    eng = next(iter(inst.engines.values()))
    return not eng.save_memory


def _program(eng, n_jobs: int):
    """Jitted [n_jobs]-batched smoothing+scoring program (cached)."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels

    key = ("quartets", n_jobs)
    fn = eng.cache_get(key)
    if fn is not None:
        return fn

    R = eng.R
    NNI_SMOOTHINGS = 16                       # ref quartets.c:254

    def one_job(codes4, dm, block_part, weights, tips):
        tipv = kernels.tip_partials(tips, codes4)      # [4, B, lane, K]
        tipv = jnp.broadcast_to(tipv[:, :, :, None, :],
                                tipv.shape[:3] + (R,) + tipv.shape[-1:])
        ta, tb, tc, td = (tipv[i] for i in range(4))

        def papply(z, x):
            return kernels.apply_p(
                kernels.p_matrices(dm, z[None]), block_part, x)

        def nr(xp, xq, z):
            """One reference update(): single Newton iteration on the
            branch between CLVs xp, xq (makenewz maxiter=1)."""
            st = kernels.sumtable(dm, block_part, xp, xq)
            return kernels.newton_raphson_branch(
                dm, block_part, weights, st, z[None],
                jnp.ones(1, jnp.int32), jnp.zeros(1, bool), 1)[0]

        z0 = jnp.full(5, DEFAULTZ, dtype=eng.dtype)
        # z[0]=internal, z[1..4]=branches to a,b,c,d; smoothing order is
        # the reference's: internal, a, b, c, d (nniSmooth node list).

        def body(state):
            z, it, done = state
            moved = jnp.zeros((), bool)

            def upd(i, xp, xq, z, moved):
                znew = nr(xp, xq, z[i])
                # NOT dead code: under vmap the batched while_loop keeps
                # running every job until ALL are done, so finished jobs
                # must be frozen here.
                znew = jnp.where(done, z[i], znew)
                moved = moved | (jnp.abs(znew - z[i]) > DELTAZ)
                return z.at[i].set(znew), moved

            x_ab = papply(z[1], ta) * papply(z[2], tb)
            x_cd = papply(z[3], tc) * papply(z[4], td)
            z, moved = upd(0, x_ab, x_cd, z, moved)
            x_cd5 = papply(z[0], x_cd)
            z, moved = upd(1, ta, papply(z[2], tb) * x_cd5, z, moved)
            z, moved = upd(2, tb, papply(z[1], ta) * x_cd5, z, moved)
            x_ab5 = papply(z[0], papply(z[1], ta) * papply(z[2], tb))
            z, moved = upd(3, tc, papply(z[4], td) * x_ab5, z, moved)
            z, moved = upd(4, td, papply(z[3], tc) * x_ab5, z, moved)
            done = done | ~moved
            return z, it + 1, done

        def cond(state):
            _, it, done = state
            return (it < NNI_SMOOTHINGS) & ~done

        z, _, _ = jax.lax.while_loop(
            cond, body, (z0, jnp.zeros((), jnp.int32),
                         jnp.zeros((), bool)))

        # evaluate across the d-branch: CLV at the c/d-side inner node
        # viewing away from d, vs tip d (reference evaluates at
        # q2.next.next after smoothing).
        x_ab = papply(z[1], ta) * papply(z[2], tb)
        xp = papply(z[0], x_ab) * papply(z[3], tc)
        lsite = kernels.site_likelihoods(dm, block_part, xp, td, z[4][None])
        acc = kernels._acc_dtype(lsite.dtype)
        lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
        return jnp.sum(weights.astype(acc) * jnp.log(lsite).astype(acc))

    def impl(codes, dm, block_part, weights, tips):
        return jax.vmap(one_job, in_axes=(0, None, None, None, None))(
            codes, dm, block_part, weights, tips)

    return eng.cache_put(key, jax.jit(impl))


def score_jobs(inst, jobs: Sequence[Tuple[int, int, int, int]]
               ) -> np.ndarray:
    """lnL for each job (a,b,c,d) meaning topology ((a,b),(c,d)); taxon
    numbers are 1-based."""
    import jax.numpy as jnp

    (eng,) = inst.engines.values()
    out = np.zeros(len(jobs))
    fn = _program(eng, JOB_CHUNK)
    for lo in range(0, len(jobs), JOB_CHUNK):
        chunk = list(jobs[lo:lo + JOB_CHUNK])
        real = len(chunk)
        while len(chunk) < JOB_CHUNK:
            chunk.append(chunk[0])
        codes = jnp.asarray(np.asarray(chunk, np.int32) - 1)
        lnls = fn(codes, eng.models, eng.block_part, eng.weights,
                  eng.tips)
        out[lo:lo + real] = np.asarray(lnls)[:real]
    return out


def three_topology_jobs(t1: int, t2: int, t3: int, t4: int
                        ) -> List[Tuple[int, int, int, int]]:
    """The reference's fixed topology order (`computeAllThreeQuartets`)."""
    return [(t1, t2, t3, t4), (t1, t3, t2, t4), (t1, t4, t2, t3)]
