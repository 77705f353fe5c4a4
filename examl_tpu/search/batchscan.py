"""Batched SPR radius scan: every candidate insertion in ONE dispatch.

TPU-native re-architecture of the reference's per-candidate insertion
loop (ExaML `addTraverseBIG`/`testInsertBIG`, `searchAlgo.c:682-833`):
the reference pays one newview + one evaluate round-trip per candidate
branch; on TPU each round-trip is dominated by dispatch latency, so the
scan is restructured around directional CLVs:

* after `remove_node` the tree is conceptually rooted at the merged
  branch (q1, q2).  Every candidate edge (v, parent(v)) needs
  `down(v)` — v's CLV away from the merged edge, maintained by the
  x-flag machinery — and `uppass(v)` — the CLV at parent(v) directed
  away from v, folding in everything on the far side of the edge;
* `uppass` obeys the same recurrence as newview:
      uppass(v) = P_{z(w,pw)} uppass(w) ⊙ P_{z(w,sib)} down(sib)
  for w = parent(v), pw = parent(w) — so the window's uppass vectors
  are just MORE newview entries, wave-scheduled into a scratch region
  of the CLV arena and computed by the SAME traversal kernel;
* the lazy insertion score at (v, parent(v)) with the sqrt-branch rule
  z' = clip(sqrt(z_v)) (reference `insertBIG` lazy arm) is
      lnL = root_eval( P_{z_p} down(subtree) ⊙ P_{z'} down(v),
                       uppass(v), z' )
  which batches over all candidates as one wave.

One jitted program per shape bucket runs the uppass traversal AND the
batched scoring: one device dispatch per pruned node, versus
O(candidates) round-trips in the reference.  The two programs' XLA
modules are `jit_spr_scan_impl` (lazy arm) and `jit_spr_thorough_impl`
(thorough arm): names of their own, so a device trace tells them from
the full-traversal family `jit_impl` / `jit_impl_eval`; their scoring
operations trace under the named scopes `examl/spr_score` and
`examl/spr_thorough`.

The candidate SET matches `addTraverseBIG`'s full radius window; the
reference's lnL-cutoff additionally skips descendants of bad branches
mid-scan (a CPU-cost heuristic, `searchAlgo.c:710-742`) — the batched
scan evaluates the whole window (a superset: never loses a move the
sequential scan would have found) and feeds the same per-insertion
statistics to the cutoff auto-tuner.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from examl_tpu import obs
from examl_tpu.constants import DEFAULTZ, DELTAZ, ZMAX, ZMIN
from examl_tpu.obs.traffic import count_tip_children
from examl_tpu.tree.topology import Node, Tree


class Candidate(NamedTuple):
    q_slot: Node            # slot of the edge's far end (q_slot.back = parent)
    up_slot: int            # scan-slot index of uppass(q)
    z: tuple                # candidate branch vector (sqrt rule, clipped)
    depth: int              # edges from the merged branch (>= 1)

    @property
    def q_num(self) -> int:
        return self.q_slot.number


class UpEntry(NamedTuple):
    """uppass(slot) = P_{zl}·left ⊙ P_{zr}·right; left/right reference
    either a tree node ("node", number) or an earlier slot ("slot", s)."""
    slot: int
    left: Tuple[str, int]
    right: Tuple[str, int]
    zl: tuple
    zr: tuple


class ScanPlan(NamedTuple):
    down_entries: list          # TraversalEntry list (orientation fixes)
    up_entries: List[UpEntry]
    candidates: List[Candidate]
    s_num: int                  # subtree CLV node (p.back)
    zp: tuple                   # branch vector p -- subtree


def _zt(z) -> tuple:
    return tuple(float(x) for x in np.asarray(z, dtype=np.float64))


def plan_for_endpoints(inst, tree: Tree, p: Node, q1: Node, q2: Node,
                       mintrav: int, maxtrav: int, constraint=None,
                       pruned_clusters=None) -> Optional[ScanPlan]:
    """Build the scan plan after remove_node(p) joined q1 -- q2.

    The descent mirrors `rearrangeBIG`/`addTraverseBIG`: from each
    non-tip endpoint, the two windows rooted at its children, testing
    each edge (v, parent v) once mintrav is consumed, stopping at tips
    or when maxtrav runs out.  Iterative (explicit stack) so deep scan
    radii cannot hit the recursion limit.
    """
    from examl_tpu.utils import z_slots

    C = inst.num_branch_slots

    def sqrt_z(z) -> tuple:
        return tuple(np.clip(np.sqrt(z_slots(z, C)), ZMIN, ZMAX))

    def allowed(v: Node) -> bool:
        if constraint is None:
            return True
        return constraint.insertion_ok(p, v, pruned_clusters)

    up_entries: List[UpEntry] = []
    candidates: List[Candidate] = []
    gather_nodes: List[Node] = []       # nodes whose down-CLV is read
    zqr = _zt(q1.z)

    roots: List[Tuple[Node, int, int, int, int]] = []
    for a, b in ((q1, q2), (q2, q1)):
        if tree.is_tip(a.number):
            continue
        for child_link, sib_link in ((a.next, a.next.next),
                                     (a.next.next, a.next)):
            child, sib = child_link.back, sib_link.back
            slot = len(up_entries)
            # root uppass: CLV at a away from child
            up_entries.append(UpEntry(
                slot, ("node", b.number), ("node", sib.number),
                zqr, _zt(sib_link.z)))
            gather_nodes.append(b)
            gather_nodes.append(sib)
            roots.append((child, slot, 1, mintrav - 1, maxtrav - 1))

    # Candidate order replicates addTraverseBIG's recursion (test the
    # edge, then the v.next subtree, then v.next.next): the order decides
    # which move wins exact lnL ties and when end_lh rises for the
    # cutoff statistics, so it must match the sequential scan.
    for item in roots:
        stack = [item]
        while stack:
            v, up_slot, depth, mint, maxt = stack.pop()
            if mint <= 0 and allowed(v):
                candidates.append(Candidate(v, up_slot, sqrt_z(v.z),
                                            depth))
                gather_nodes.append(v)
            if tree.is_tip(v.number) or maxt <= 0:
                continue
            pushes = []
            for child_link, sib_link in ((v.next, v.next.next),
                                         (v.next.next, v.next)):
                child, sib = child_link.back, sib_link.back
                slot = len(up_entries)
                up_entries.append(UpEntry(
                    slot, ("slot", up_slot), ("node", sib.number),
                    _zt(v.z), _zt(sib_link.z)))
                gather_nodes.append(sib)
                pushes.append((child, slot, depth + 1, mint - 1,
                               maxt - 1))
            stack.extend(reversed(pushes))   # LIFO: v.next pops first

    if not candidates:
        return None

    # Invalidation seam: this plan is built against the PRUNED topology
    # (remove_node's hookup already bumped the tree topology clock, so
    # any flat-traversal/schedule-structure cache from before the prune
    # is already unservable by key); the scan itself dispatches only
    # partial traversals, which never consult the cached structures.
    #
    # Down-CLV orientation: every gathered node must view away from the
    # merged edge; compute_traversal resolves staleness via the x-flags
    # (dedup by parent -- windows overlap heavily).  The deduped union
    # must then be DEPENDENCY-SORTED: compute_traversal always recomputes
    # its top node, so a later call can emit a rewrite of a node that an
    # earlier call's entry reads -- list order alone would let
    # schedule_waves place the reader at or before the writer and gather
    # a stale CLV.
    need = {}
    subtree_root = p.back
    for v in gather_nodes + [subtree_root]:
        if tree.is_tip(v.number):
            continue
        for e in tree.compute_traversal(v, full=False):
            need.setdefault(e.parent, e)

    down_entries: list = []
    emitted = set()

    def emit(entry) -> None:
        stack = [(entry, False)]
        while stack:
            e, expanded = stack.pop()
            if e.parent in emitted:
                continue
            if expanded:
                emitted.add(e.parent)
                down_entries.append(e)
                continue
            stack.append((e, True))
            for child in (e.left, e.right):
                if child in need and child not in emitted:
                    stack.append((need[child], False))

    for e in need.values():
        emit(e)

    return ScanPlan(down_entries=down_entries,
                    up_entries=up_entries, candidates=candidates,
                    s_num=subtree_root.number, zp=_zt(p.z))


def _count_dispatch(inst, plan: ScanPlan, thorough: bool = False) -> None:
    """The counters of one scan dispatch, by what it carries: entries
    (orientation fixes and uppass rows), the tip children among their
    operands, candidates, and the tips among the scoring operands (a
    candidate's far end, the pruned subtree's root): rows a byte a site
    where the others are CLV rows.  `search.scan_*` count both arms,
    `search.thorough_*` the thorough arm's share of them."""
    ntips = next(iter(inst.engines.values())).ntips
    tip_children = count_tip_children(plan.down_entries, ntips) + sum(
        kind == "node" and v <= ntips for e in plan.up_entries
        for kind, v in (e.left, e.right))
    tip_operands = (plan.s_num <= ntips) + sum(
        c.q_num <= ntips for c in plan.candidates)
    n_cand = len(plan.candidates)
    n_entries = len(plan.down_entries) + len(plan.up_entries)
    obs.inc("search.scan_dispatches")
    obs.inc("search.scan_candidates", n_cand)
    obs.inc("search.scan_entries", n_entries)
    obs.inc("search.scan_tip_children", tip_children)
    obs.inc("search.scan_tip_operands", tip_operands)
    if thorough:
        obs.inc("search.thorough_dispatches")
        obs.inc("search.thorough_candidates", n_cand)
        obs.inc("search.thorough_entries", n_entries)
        obs.inc("search.thorough_tip_children", tip_children)
        obs.inc("search.thorough_tip_operands", tip_operands)


def run_plan(inst, tree: Tree, plan: ScanPlan) -> np.ndarray:
    """Execute the plan; returns per-candidate total lnL [N].

    Orientation fixes, uppass traversal, and all candidate scores run as
    ONE device program per engine — one dispatch per pruned node.
    """
    N = len(plan.candidates)
    _count_dispatch(inst, plan)
    total = np.zeros(N, dtype=np.float64)
    with obs.span("search:spr_batched_scan", args={"candidates": N}):
        for eng in inst.engines.values():
            total += np.asarray(eng.batched_scan(plan), dtype=np.float64)
    return total


# -- device side ------------------------------------------------------------

CAND_CHUNK = 16


def scan_program(eng, n_chunks: int):
    """Build (or fetch) the jitted uppass+scoring program for one
    candidate-chunk count.  Traversal shape variation is handled inside
    by the engine's bucketed traversal arrays.  Under PSR the engine's
    per-site rate multipliers ride along and every P application uses
    the factorized per-site form (`apply_p_factorized`); the GAMMA path
    keeps the batched P-matrix contraction.  The traversal and every CLV
    gather go through the engine's state-agnostic primitives, so the
    same program text serves the dense arena (aux=()) and the -S SEV
    pool (aux=(slot_read, slot_write), scan region carved from the
    pool)."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels

    key = ("scan", n_chunks)
    fn = eng.cache_get(key)
    if fn is not None:
        return fn

    scale_exp = eng.scale_exp
    ntips = eng.ntips
    psr = eng.psr

    def spr_scan_impl(clv, scaler, aux, tv, qg, upg, zc, sg, zp, dm,
                      block_part, weights, tips, sr_rates):
        clv, scaler = eng._traverse_kernel(clv, aux, scaler, tv, dm,
                                           block_part, tips, sr_rates)
        xs, ss = eng._gather(clv, aux, scaler, sg, tips)
        if psr:
            ds = kernels.psr_decay(dm, block_part, sr_rates, zp)
            u = kernels.apply_p_factorized(dm, block_part, ds, xs)
        else:
            u = kernels.apply_p(kernels.p_matrices(dm, zp), block_part,
                                xs)

        cdt = tips.table.dtype        # compute dtype (arena may store bf16)
        minlik, two_e, _ = kernels.scale_constants(cdt, scale_exp)
        acc = kernels._acc_dtype(cdt)
        _, _, log_min = kernels.scale_constants(acc, scale_exp)

        @jax.named_scope("examl/spr_score")
        def chunk(carry, args):
            qg_c, upg_c, z_c = args                       # [T], [T], [T,C]
            xq, sq = eng._gather(clv, aux, scaler, qg_c, tips)
            xr, sr = eng._gather(clv, aux, scaler, upg_c, tips)
            if psr:
                d_c = jax.vmap(lambda zz: kernels.psr_decay(
                    dm, block_part, sr_rates, zz))(z_c)   # [T,B,l,R,K]
                t = kernels.apply_p_factorized(dm, block_part, d_c, xq)
                y = kernels.apply_p_factorized(dm, block_part, d_c, xr)
            else:
                pw = kernels.p_matrices_wave(dm, z_c)     # [T,M,R,K,K]
                pwb = pw[:, block_part]                   # [T,B,R,K,K]
                t = kernels.einsum("tbrak,tblrk->tblra", pwb, xq)
                y = kernels.einsum("tbrak,tblrk->tblra", pwb, xr)
            v = t * u[None]
            vmax = jnp.max(jnp.abs(v), axis=(3, 4))       # [T,B,l]
            needs = vmax < minlik
            v = jnp.where(needs[:, :, :, None, None], v * two_e, v)
            sc_v = sq + ss[None] + needs.astype(jnp.int32)
            fb = dm.freqs[block_part]                     # [B,R,K]
            wb = dm.rate_weights[block_part]              # [B,R]
            lsite = kernels.einsum("brk,br,tblrk,tblrk->tbl",
                                   fb, wb, v, y)
            lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
            sc = (sc_v + sr).astype(acc)
            site_lnl = weights.astype(acc)[None] * (
                jnp.log(lsite).astype(acc) + sc * log_min)
            return carry, jnp.sum(site_lnl, axis=(1, 2))  # [T]

        _, lnls = jax.lax.scan(chunk, 0, (qg, upg, zc))
        if eng._axis_name is not None:
            # SEV x sharding: ONE explicit lnL Allreduce per dispatch
            # for the whole candidate window (hoisted out of the scan —
            # a per-chunk psum would serialize latency-bound collectives).
            lnls = jax.lax.psum(lnls, eng._axis_name)
        return clv, scaler, lnls.reshape(-1)

    if eng._axis_name is not None:
        # SEV x sharding: same shard_map treatment as the engine's core
        # programs (engine._site_spec_vocab) — each device scans its pool
        # region / block range, candidate lnLs psum across the mesh.
        v = eng._site_spec_vocab()
        REP = v["rep"]
        fn = v["wrap"](
            spr_scan_impl,
            (v["pool"], v["scaler"], v["aux"], v["traversal"], REP, REP,
             REP, REP, REP, v["models"], v["blocks"], v["sites"],
             v["tips"], v["sr"]),
            (v["pool"], v["scaler"], REP), donate=(0, 1))
    else:
        fn = jax.jit(spr_scan_impl, donate_argnums=(0, 1))
    return eng.cache_put(key, fn)


# -- thorough arm -----------------------------------------------------------

TH_CHUNK = 8


def thorough_program(eng, n_chunks: int):
    """Jitted thorough-insertion scorer: orientation+uppass traversal,
    then per candidate the reference's full Thorough procedure
    (`insertBIG` thorough arm + `localSmooth`, `searchAlgo.c:495-533`,
    :196-436) in closed form:

    * three pairwise Newton optimizations to convergence between
      down(q), uppass(q), and the subtree CLV (the star triangle's
      virtual branches), started like `_triangle_branches`;
    * the log-space triangle solve with the reference's degenerate
      caps;
    * up to 32 localSmooth passes — each branch one Newton iteration
      with the DELTAZ movement test — where the three CLVs around the
      insertion node are closed-form products of P-applied operands
      (no arena writes needed);
    * the final evaluation across the r-side branch.

    Newton derivatives are invariant to the operands' scaling counters
    (a per-site constant factor), so only the final lnL applies them.

    Like the lazy arm, the traversal and CLV gathers go through the
    engine's state-agnostic primitives, so the same program text serves
    the dense arena and the -S SEV pool; under SEV x sharding it
    shard_maps with per-NR-iteration derivative psums (the reference's
    per-iteration Allreduce, `makenewzGenericSpecial.c:1241-1248`) and
    one final lnL psum.
    """
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops import kernels

    key = ("thscan", n_chunks)
    fn = eng.cache_get(key)
    if fn is not None:
        return fn

    from examl_tpu.constants import SMOOTHINGS
    from examl_tpu.search.spr import SPR_NR_ITERATIONS

    scale_exp = eng.scale_exp
    ntips = eng.ntips
    psr = eng.psr
    lzmax = float(np.log(ZMAX))

    def spr_thorough_impl(clv, scaler, aux, tv, qg, upg, zq0, sg, dm,
                          block_part, weights, tips, sr_rates):
        clv, scaler = eng._traverse_kernel(clv, aux, scaler, tv, dm,
                                           block_part, tips, sr_rates)
        xs, ss = eng._gather(clv, aux, scaler, sg, tips)
        cdt = tips.table.dtype        # compute dtype (arena may store bf16)
        minlik, two_e, _ = kernels.scale_constants(cdt, scale_exp)
        acc = kernels._acc_dtype(cdt)
        _, _, log_min = kernels.scale_constants(acc, scale_exp)

        def papply(z, x):
            if psr:
                d = kernels.psr_decay(dm, block_part, sr_rates, z[None])
                return kernels.apply_p_factorized(dm, block_part, d, x)
            return kernels.apply_p(kernels.p_matrices(dm, z[None]),
                                   block_part, x)

        def nr(xp, xq, z0, iters):
            st = kernels.sumtable(dm, block_part, xp, xq)
            return kernels.newton_raphson_branch(
                dm, block_part, weights, st,
                jnp.full(1, z0, dtype=cdt),
                jnp.full(1, iters, jnp.int32), jnp.zeros(1, bool), 1,
                site_rates=sr_rates, axis_name=eng._axis_name)[0]

        def one(xq1, sq1, xr1, sr1, z01):
            zqr = nr(xq1, xr1, z01, SPR_NR_ITERATIONS)
            zqs = nr(xq1, xs, DEFAULTZ, SPR_NR_ITERATIONS)
            zrs = nr(xr1, xs, DEFAULTZ, SPR_NR_ITERATIONS)
            lzqr = jnp.log(jnp.maximum(zqr, ZMIN))
            lzqs = jnp.log(jnp.maximum(zqs, ZMIN))
            lzrs = jnp.log(jnp.maximum(zrs, ZMIN))
            lzsum = 0.5 * (lzqr + lzqs + lzrs)
            lzq, lzr, lzs = lzsum - lzrs, lzsum - lzqs, lzsum - lzqr
            e1 = jnp.exp(lzq)
            e2 = jnp.exp(lzr)
            e3 = jnp.exp(lzs)
            # degenerate triangles: reference's elif chain
            c1 = lzq > lzmax
            c2 = ~c1 & (lzr > lzmax)
            c3 = ~c1 & ~c2 & (lzs > lzmax)
            e1 = jnp.where(c1, ZMAX, jnp.where(c2, zqr,
                           jnp.where(c3, zqs, e1)))
            e2 = jnp.where(c1, zqr, jnp.where(c2, ZMAX,
                           jnp.where(c3, zrs, e2)))
            e3 = jnp.where(c1, zqs, jnp.where(c2, zrs,
                           jnp.where(c3, ZMAX, e3)))

            def body(state):
                e1, e2, e3, it, done = state
                moved = jnp.zeros((), bool)

                def step(znew, zold, moved):
                    znew = jnp.where(done, zold, znew)
                    return znew, moved | (jnp.abs(znew - zold) > DELTAZ)

                # localSmooth order: (p: e3), (p.next: e1), (p.next.next: e2)
                slot_s = papply(e1, xq1) * papply(e2, xr1)
                e3, moved = step(nr(slot_s, xs, e3, 1), e3, moved)
                slot_q = papply(e2, xr1) * papply(e3, xs)
                e1, moved = step(nr(slot_q, xq1, e1, 1), e1, moved)
                slot_r = papply(e1, xq1) * papply(e3, xs)
                e2, moved = step(nr(slot_r, xr1, e2, 1), e2, moved)
                return e1, e2, e3, it + 1, done | ~moved

            def cond(state):
                _, _, _, it, done = state
                return (it < SMOOTHINGS) & ~done

            e1, e2, e3, _, _ = jax.lax.while_loop(
                cond, body, (e1, e2, e3, jnp.zeros((), jnp.int32),
                             jnp.zeros((), bool)))

            xp = papply(e1, xq1) * papply(e3, xs)
            needs = jnp.max(jnp.abs(xp), axis=(2, 3)) < minlik   # [B,l]
            xp = jnp.where(needs[:, :, None, None], xp * two_e, xp)
            scp = sq1 + ss + needs.astype(jnp.int32)
            lsite = kernels.site_likelihoods(dm, block_part, xp, xr1,
                                             e2[None],
                                             site_rates=sr_rates)
            lsite = jnp.maximum(lsite, jnp.finfo(lsite.dtype).tiny)
            sc = (scp + sr1).astype(acc)
            lnl = jnp.sum(weights.astype(acc)
                          * (jnp.log(lsite).astype(acc) + sc * log_min))
            return lnl, e1, e2, e3

        @jax.named_scope("examl/spr_thorough")
        def chunk(carry, args):
            qg_c, upg_c, z0_c = args
            xq, sq = eng._gather(clv, aux, scaler, qg_c, tips)
            xr, sr = eng._gather(clv, aux, scaler, upg_c, tips)
            lnl, e1, e2, e3 = jax.vmap(one)(xq, sq, xr, sr, z0_c)
            return carry, (lnl, e1, e2, e3)

        _, (lnls, e1, e2, e3) = jax.lax.scan(chunk, 0, (qg, upg, zq0))
        if eng._axis_name is not None:
            # SEV x sharding: the branch triplets are already globally
            # agreed (every NR iteration psums its derivatives); only
            # the final per-candidate lnLs need the one Allreduce.
            lnls = jax.lax.psum(lnls, eng._axis_name)
        return (clv, scaler, lnls.reshape(-1),
                jnp.stack([e1.reshape(-1), e2.reshape(-1),
                           e3.reshape(-1)], axis=1))

    if eng._axis_name is not None:
        v = eng._site_spec_vocab()
        REP = v["rep"]
        fn = v["wrap"](
            spr_thorough_impl,
            (v["pool"], v["scaler"], v["aux"], v["traversal"], REP, REP,
             REP, REP, v["models"], v["blocks"], v["sites"], v["tips"],
             v["sr"]),
            (v["pool"], v["scaler"], REP, REP), donate=(0, 1))
    else:
        fn = jax.jit(spr_thorough_impl, donate_argnums=(0, 1))
    return eng.cache_put(key, fn)


def run_plan_thorough(inst, tree: Tree, plan: ScanPlan
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Thorough scores for every plan candidate: (lnls [N], e [N, 3])
    with e = the smoothed (lzq, lzr, lzs) branch triplet per candidate.
    Single-engine, single-branch-slot instances only (the caller
    gates); the padding/chunk/dispatch plumbing lives on the engine
    next to the lazy arm's (`LikelihoodEngine.batched_thorough`)."""
    _count_dispatch(inst, plan, thorough=True)
    (eng,) = inst.engines.values()
    with obs.span("search:spr_batched_thorough",
                  args={"candidates": len(plan.candidates)}):
        return eng.batched_thorough(plan)
