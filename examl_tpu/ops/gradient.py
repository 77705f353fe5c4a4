"""One-pass analytic branch gradients: all 2n-3 edge derivatives of a
tree in O(1) device dispatches.

ExaML's `smoothTree`/`treeEvaluate` (reference `searchAlgo.c:127-436`)
serialize one Newton solve per branch — O(n) sequential
sumtable+derivative round trips per smoothing sweep, the dispatch
storm BENCH r03/r04 measured at ~10x the cost of a full likelihood
evaluation.  Ji et al. (arXiv:2303.04390) show every branch gradient
is computable from one post-order plus one pre-order linear pass;
BEAGLE 4.1 ships the same edge-derivative machinery as its production
gradient path.  This module is that machinery for the jax engine:

* The POST-ORDER partials are the engine's ordinary full traversal —
  the CLV arena after `run_traversal(flat, full=True)`, unchanged.
* The PRE-ORDER ("outroot") pass is the SAME wave schedule executed in
  reverse wave order (`GradStructure` packs `FlatTraversal`'s waves
  backwards into the scan-tier [L, W] shape): each post-order entry
  (v <- l, r) emits the root-directed complements of its two children,
  out(l) = (P(z_up(v)) out(v)) * (P(zr) D(r)) and symmetrically for r
  (`kernels.outroot_wave`), filling a second arena indexed by node
  number.  The recursion grounds at the traversal's root edge (p, q):
  out(p) = D(q) and out(q) = D(p), copied from the CLV arena.
* The EDGE-DERIVATIVE contraction then runs for EVERY edge at once:
  for edge (v, c) with branch z, `kernels.sumtable(out(c), D(c))`
  followed by `kernels.nr_derivatives(st, z)` yields (dlnL/dlz,
  d2lnL/dlz2) — identical arithmetic to the per-branch Newton path,
  batched over edges in fixed-size chunks inside one `lax.scan` so
  peak memory stays at one chunk of sumtables, not E of them.

Per-site CLV rescaling cancels in every dsite/lsite ratio the
derivatives are built from, so the outroot pass rescales VALUES (same
threshold/multiplier as newview) but tracks no counts.

Both loops run the tree's live slots, not a rounded-up count.  The
edge loop has ceil(E / GRAD_CHUNK) chunks, and E = 2 * ntips - 3 for
every full traversal: a constant of the engine.  The outroot loop's
step width W follows the bytes a row of the outroot arena holds in the
program that runs (`wave_cap`): ONE entry a step from 0.5 MiB a row
(8,192 DNA or 1,639 protein patterns under GAMMA4 in f32), where a
one-entry step reads its rows by a dynamic slice, scatters one row and
runs no scratch slot (a tree's root-side waves hold one to three
entries); 8 under it, where a loop iteration's fixed cost (11 to 20 us
on a v5e) is no longer small beside a row's bytes and a wide step
amortises it over the wide waves of a deep tree.  At W = 1 the step
count is n = ntips - 2 whatever the topology; under the threshold the
steps are bucketed (`bucket_len`).  The jitted gradient program is
keyed ("grad", L, W, n_chunks), and therefore eligible for the
exported program bank (ops/export_bank.py: a restart deserializes the
compiled gradient pass instead of recompiling it): ONE program an
engine from 0.5 MiB a row, whatever tree a search moves to, a few step
buckets by topology under it; topology ships as data.
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

from examl_tpu.ops import kernels
from examl_tpu.ops.kernels import OutrootTraversal
from examl_tpu.utils import bucket_len, next_pow2, z_slots

# Edges per edge-derivative chunk: one chunk of sumtables
# [GRAD_CHUNK, B, lane, R, K] is the gradient program's peak transient
# beyond the outroot arena (mirrors batchscan.CAND_CHUNK).
GRAD_CHUNK = 32


# Bytes of an outroot-arena row from which an outroot step holds ONE
# entry: the measured crossing (v5e, PERF.md section 6, PR 39).  On a
# 140-taxon tree the pass costs 11.4 ms at one entry a step for 14.0 at
# eight at 1 MiB a row, 55 for 92 at 5 MiB, 6.7 for 9.0 at 0.5 MiB,
# 7.2 for 6.9 at 0.625 MiB (K = 20), 4.1 for 3.65 at 0.25 MiB, 2.7 for
# 1.8 at 0.125 MiB: one entry wins or ties from 0.5 MiB and loses
# under it.  On a deep tree (2,000 taxa, 96 KiB a row) it costs 49.0
# ms for 25.0 at eight (34.4 at two, 27.7 at four, 23.6 at sixteen):
# the wide step stays under the threshold, and by the 140-taxon tree's
# step and slot costs at 0.5 MiB (27 us a one-entry step, 147 us an
# 8-entry one) the deep tree ties there too (1,998 steps for 384).
ONE_ENTRY_ROW_BYTES = 1 << 19


def wave_cap(row_bytes: int) -> int:
    """Entries an outroot step may hold, from the bytes of an
    outroot-arena row (blocks x lanes x R x K x itemsize) in the program
    that runs (a shard's under the mesh): one from
    `ONE_ENTRY_ROW_BYTES`, eight under it."""
    return 1 if row_bytes >= ONE_ENTRY_ROW_BYTES else 8


class GradStructure:
    """The topology+root structural half of a gradient plan (cacheable
    per `FlatTraversal.topo_key`, like the engine's schedule-structure
    cache): the reversed wave packing, the per-entry upper-branch
    source map, and the edge table.  Branch-length values and CLV
    gather indices are refreshed per dispatch by `grad_arrays` (z moves
    every smoothing sweep; the row map follows the engine's layout)."""

    __slots__ = ("n", "ntips", "n_edges", "n_steps", "wave_w",
                 "n_chunks", "scratch", "roots",
                 "pk", "pk_pad", "up_row", "lrow", "rrow",
                 "zu_src", "zu_side", "edge_node", "edge_pad",
                 "edge_x_row", "edge_z_src", "edge_z_side")

    def __init__(self, flat, wave_cap: int):
        n = flat.n
        ntips = flat.ntips
        parent = np.asarray(flat.parent, dtype=np.int64)
        left = np.asarray(flat.left, dtype=np.int64)
        right = np.asarray(flat.right, dtype=np.int64)
        self.n = n
        self.ntips = ntips
        self.scratch = 2 * ntips - 2          # outroot arena scratch row
        # Root-edge endpoints: the two nodes no entry computes as a
        # child (the traversal is rooted at the edge between them).
        mask = np.ones(2 * ntips - 1, dtype=bool)
        mask[0] = False
        mask[left] = False
        mask[right] = False
        roots = np.flatnonzero(mask)
        assert roots.shape[0] == 2, roots
        self.roots = (int(roots[0]), int(roots[1]))
        # Branch ABOVE each entry's parent node: the (entry, side)
        # whose zl/zr defines it; root-adjacent entries (-1) read the
        # root-edge z.
        src_e = np.full(2 * ntips - 1, -1, dtype=np.int64)
        src_s = np.zeros(2 * ntips - 1, dtype=np.int64)
        src_e[left] = np.arange(n)
        src_s[left] = 0
        src_e[right] = np.arange(n)
        src_s[right] = 1
        self.zu_src = src_e[parent]
        self.zu_side = src_s[parent]
        # Reverse wave packing into [L, W]: post-order waves walked
        # backwards, each wave split into <=W-wide sub-steps (entries
        # within a wave are independent in the pre-order direction too
        # — a same-wave entry can never have written the outroot row
        # another reads, since that would put its defining entry in an
        # earlier post-order wave than itself).
        sizes = np.asarray(flat.wave_sizes, dtype=np.int64)
        W = min(next_pow2(int(sizes.max())), wave_cap) if n else 1
        offs = np.concatenate([[0], np.cumsum(sizes)])
        steps = []
        for w in range(len(sizes) - 1, -1, -1):
            lo, hi = int(offs[w]), int(offs[w + 1])
            for s in range(lo, hi, W):
                steps.append(np.arange(s, min(s + W, hi), dtype=np.int64))
        # One entry a step: n steps whatever the topology, no bucket.
        L = max(len(steps), 1)
        if W > 1:
            L = bucket_len(L)
        pk = np.full((L, W), -1, dtype=np.int64)
        for i, st in enumerate(steps):
            pk[i, :st.shape[0]] = st
        self.pk = pk
        self.pk_pad = pk < 0
        self.n_steps = L
        self.wave_w = W
        pke = np.where(self.pk_pad, 0, pk)
        self.up_row = np.where(self.pk_pad, self.scratch,
                               parent[pke] - 1).astype(np.int32)
        self.lrow = np.where(self.pk_pad, self.scratch,
                             left[pke] - 1).astype(np.int32)
        self.rrow = np.where(self.pk_pad, self.scratch,
                             right[pke] - 1).astype(np.int32)
        # Edge table: edge 0 is the root edge (its complement partial is
        # the initialized out[p-1] = D(q)); edges 1+2i / 2+2i are entry
        # i's left / right child edges.  E = 2n+1 = 2*ntips-3.
        E = 2 * n + 1
        self.n_edges = E
        edge_node = np.empty(E, dtype=np.int64)
        edge_node[0] = self.roots[0]
        edge_node[1::2] = left
        edge_node[2::2] = right
        ez_src = np.empty(E, dtype=np.int64)
        ez_src[0] = -1
        ez_src[1::2] = np.arange(n)
        ez_src[2::2] = np.arange(n)
        ez_side = np.zeros(E, dtype=np.int64)
        ez_side[2::2] = 1
        nc = -(-E // GRAD_CHUNK)
        Epad = nc * GRAD_CHUNK
        self.n_chunks = nc

        def padE(a, fill):
            out = np.full(Epad, fill, dtype=a.dtype)
            out[:E] = a
            return out

        self.edge_node = padE(edge_node, 1)
        self.edge_pad = padE(np.zeros(E, dtype=np.int64), 1).astype(bool)
        self.edge_x_row = np.where(
            self.edge_pad, self.scratch,
            padE(edge_node, 1) - 1).astype(np.int32)
        self.edge_z_src = padE(ez_src, -1)
        self.edge_z_side = padE(ez_side, 0)


def build_structure(flat, wave_cap: int) -> GradStructure:
    return GradStructure(flat, wave_cap)


def _entry_z(flat, num_slots: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-entry branch vectors widened to the engine's slot count
    (same normalization as fastpath.refresh_z)."""
    zl, zr = flat.zl, flat.zr
    if zl.shape[1] != num_slots:
        zl = np.stack([z_slots(z, num_slots) for z in zl])
        zr = np.stack([z_slots(z, num_slots) for z in zr])
    return zl, zr


def grad_arrays(gs: GradStructure, flat, row_map: np.ndarray,
                num_slots: int, root_z):
    """The per-dispatch dynamic half: CLV gather indices resolved
    through the engine's CURRENT row map and branch vectors re-read
    from the (freshly smoothed) traversal.  Pure numpy fancy indexing —
    the only per-sweep host work on a structure-cache hit.

    Returns (pre [OutrootTraversal leaves as numpy], ex_rows, ey_gidx,
    ez) ready for device_put."""
    ntips = gs.ntips
    zl, zr = _entry_z(flat, num_slots)
    rz = np.asarray(z_slots(root_z, num_slots), dtype=np.float64)
    src = np.where(gs.zu_src < 0, 0, gs.zu_src)
    zu = np.where((gs.zu_side == 0)[:, None], zl[src], zr[src])
    zu = np.where((gs.zu_src < 0)[:, None], rz[None, :], zu)  # root edge

    def gidx(nodes):
        r = row_map[nodes]
        return np.where(nodes <= ntips, nodes - 1,
                        ntips + r).astype(np.int32)

    pke = np.where(gs.pk_pad, 0, gs.pk)
    lnode = np.asarray(flat.left, dtype=np.int64)[pke]
    rnode = np.asarray(flat.right, dtype=np.int64)[pke]
    lg = np.where(gs.pk_pad, 0, gidx(lnode)).astype(np.int32)
    rg = np.where(gs.pk_pad, 0, gidx(rnode)).astype(np.int32)

    def pkz(zarr):
        out = np.ones(gs.pk.shape + (num_slots,), dtype=np.float64)
        out[~gs.pk_pad] = zarr[gs.pk[~gs.pk_pad]]
        return out

    pre = (gs.up_row, gs.lrow, gs.rrow, lg, rg,
           pkz(zu), pkz(zl), pkz(zr))

    T = GRAD_CHUNK
    ey = np.where(gs.edge_pad, 0, gidx(gs.edge_node)).astype(np.int32)
    ezs = np.where(gs.edge_z_src < 0, 0, gs.edge_z_src)
    ez = np.where((gs.edge_z_side == 0)[:, None], zl[ezs], zr[ezs])
    ez = np.where((gs.edge_z_src < 0)[:, None], rz[None, :], ez)
    ez[gs.edge_pad] = 1.0
    return (pre,
            gs.edge_x_row.reshape(gs.n_chunks, T),
            ey.reshape(gs.n_chunks, T),
            ez.reshape(gs.n_chunks, T, num_slots))


@jax.named_scope("examl/edge_grad")
def edge_gradients(models, block_part, weights, tips, clv, scaler, out,
                   ex_rows, ey_gidx, ez, num_slots: int, ntips: int,
                   site_rates=None):
    """(d1, d2) [n_chunks*GRAD_CHUNK, C] for every edge at once: one
    `lax.scan` over edge chunks, each chunk a batched sumtable +
    derivative contraction (identical arithmetic to the per-branch
    Newton path's `sumtable`/`nr_derivatives`)."""
    def body(carry, x):
        xr, yg, z = x
        X = kernels.take_rows(out, xr)            # [T, B, lane, R, K]
        Y, _sc = kernels.gather_child(tips, clv, scaler, yg, ntips)
        st = jax.vmap(
            lambda a, b: kernels.sumtable(models, block_part, a, b))(X, Y)
        d1, d2 = jax.vmap(
            lambda s, zz: kernels.nr_derivatives(
                models, block_part, weights, s, zz, num_slots,
                site_rates))(st, z)
        return carry, (d1, d2)

    _, (d1, d2) = jax.lax.scan(body, None, (ex_rows, ey_gidx, ez))
    return d1.reshape(-1, num_slots), d2.reshape(-1, num_slots)


def newton_step(z: np.ndarray, d1: np.ndarray, d2: np.ndarray
                ) -> np.ndarray:
    """One batched full-Newton update over all branches [E, C] — the
    single-iteration body of the reference NR loop
    (`makenewzGenericSpecial.c:1133-1349`) vectorized over edges: the
    bad-curvature branch-shortening move (z <- 0.37 z + 0.63), the
    0.25 z + 0.75 step cap, the exp(min(-d1/d2, 100)) multiplicative
    step.  Where curvature is unusable (d2 >= 0) the shortening move
    IS the safeguarded line-search direction the reference uses.
    Damping is the CALLERS' job: the smoothers scale the returned step
    in lz space through their per-branch Rprop ladder (capped at
    EXAML_GRAD_DAMPING) — one mechanism, not two."""
    from examl_tpu.constants import ZMAX, ZMIN

    z = np.clip(z, ZMIN, ZMAX)
    bad = (d2 >= 0.0) & (z < ZMAX)
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        tantmp = np.where(d2 < 0.0, -d1 / np.where(d2 < 0.0, d2, 1.0),
                          np.inf)
        cap = 0.25 * z + 0.75
        znr = np.where(tantmp < 100.0,
                       np.maximum(z * np.exp(np.minimum(tantmp, 100.0)),
                                  ZMIN),
                       cap)
    znr = np.minimum(np.minimum(znr, cap), ZMAX)
    return np.where(bad, 0.37 * z + 0.63, znr)
