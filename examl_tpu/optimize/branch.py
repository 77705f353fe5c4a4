"""Branch-length smoothing passes over the tree.

Semantics of the reference's `update`/`smooth`/`smoothTree`/`localSmooth`/
`treeEvaluate` (ExaML `searchAlgo.c:127-436, 2635-2650`): repeated
Newton-Raphson passes over every branch until no branch moves by more than
`deltaz`, tracked per branch slot through the instance's
`partition_smoothed` / `partition_converged` flags.

Two execution modes for the FULL-tree pass (`smooth_tree`):

* PER-BRANCH (the reference's): one fused traversal+sumtable+Newton
  dispatch per branch per sweep — O(n) sequential dispatches per sweep,
  the dispatch storm BENCH r03/r04 measured at `newton_branch_ms` ~10x
  `evaluate_ms`.  Retained verbatim for `local_smooth`/`region_smooth`
  (a handful of branches), for -S pools, multi-process meshes and
  fabrics with tree slices, and as the fallback ladder rung
  (`EXAML_GRAD_SMOOTH=0` restores it exactly).
* WHOLE-TREE GRADIENT (default where eligible): per sweep, ONE
  post-order traversal dispatch plus ONE analytic gradient dispatch
  per engine yield (d1, d2) for all 2n-3 branches at once
  (ops/gradient.py — the pre-order/outroot pass of Ji et al.
  2303.04390), followed by a batched damped-Newton update applied to
  every branch simultaneously; sweeps repeat to the same DELTAZ
  movement criterion.  O(1) dispatches per sweep — the
  `engine.dispatches_per_smoothing_round` gauge is the acceptance
  evidence (ROADMAP §5).
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import jax
import numpy as np

from examl_tpu import obs
from examl_tpu.constants import DELTAZ, SMOOTHINGS
from examl_tpu.instance import PhyloInstance
from examl_tpu.tree.topology import Node, Tree


def update_branch(inst: PhyloInstance, tree: Tree, p: Node) -> None:
    """One-branch NR update + smoothed-flag bookkeeping (ref `update`)."""
    from examl_tpu.utils import z_slots
    q = p.back
    z0 = z_slots(q.z, inst.num_branch_slots)
    z = inst.makenewz(tree, p, q, z0, maxiter=1,
                      mask_converged=inst.num_branch_slots > 1)
    moved = np.abs(z - z0) > DELTAZ
    upd = ~inst.partition_converged
    inst.partition_smoothed &= ~(upd & moved)
    znew = np.where(upd, z, z0)
    p.z[:] = znew.tolist()
    q.z[:] = znew.tolist()


def smooth_subtree(inst: PhyloInstance, tree: Tree, p: Node) -> None:
    """Adjust branch (p, p.back) then descend below p (ref `smooth`).

    Iterative two-visit stack: the reference recursed per node, which
    blows Python's recursion limit on a deep (caterpillar-shaped) tree
    of a few thousand taxa — long before the 50k-taxon host path does
    (pinned by tests/test_gradients.py's deep-tree smoke)."""
    stack: List[Tuple[Node, bool]] = [(p, False)]
    while stack:
        s, expanded = stack.pop()
        if expanded:
            inst.new_view(tree, s)
            continue
        update_branch(inst, tree, s)
        if not tree.is_tip(s.number):
            stack.append((s, True))
            stack.append((s.next.next.back, False))
            stack.append((s.next.back, False))


def _all_smoothed(inst: PhyloInstance) -> bool:
    result = True
    for i in range(inst.num_branch_slots):
        if not inst.partition_smoothed[i]:
            result = False
        else:
            inst.partition_converged[i] = True
    return result


# -- whole-tree gradient smoothing (ops/gradient.py) -------------------------


def grad_smooth_enabled() -> bool:
    """Gradient smoothing unless EXAML_GRAD_SMOOTH=0 (escape hatch and
    the bit-identical-to-HEAD reference mode)."""
    return os.environ.get("EXAML_GRAD_SMOOTH", "") != "0"


def grad_smooth_ineligible(inst: PhyloInstance) -> Optional[str]:
    """None when the whole-tree gradient pass can serve this instance,
    else the reason the per-branch path is kept."""
    if inst.save_memory:
        return "-S SEV pools keep the per-branch Newton path"
    for eng in inst.engines.values():
        if eng.sharding is None:
            continue
        # Site-sharded arenas of one process take the pass (the 1-D
        # mesh and the Sx1 fabric: `LikelihoodEngine._grad_program`).
        if jax.process_count() > 1:
            return ("multi-process meshes keep the per-branch Newton "
                    "path (no multi-host run of the gradient pass yet)")
        if eng.sharding.tree_shards > 1:
            return ("a fabric with tree slices keeps the per-branch "
                    "Newton path (the gradient pass is mapped over the "
                    "site axis only)")
    return None


def _slot_facing(tree: Tree, child: int, parent: int) -> Node:
    """The slot at `child` whose back is `parent` — the Node owning the
    branch's shared z list (hookup aliases both endpoints' z to ONE
    list, so writing through either slot updates the branch)."""
    if tree.is_tip(child):
        return tree.nodep[child]
    for sl in tree.slots(child):
        if sl.back is not None and sl.back.number == parent:
            return sl
    raise KeyError(f"no slot at node {child} faces node {parent}")


def _edge_slots(tree: Tree, flat, p: Node) -> List[Node]:
    """Node slots in the engine's edge order (ops/gradient.py): edge 0
    the traversal's root edge, then each entry's (left, right) child
    branches in flat order."""
    slots = [p]
    for v, l, r in zip(flat.parent.tolist(), flat.left.tolist(),
                       flat.right.tolist()):
        slots.append(_slot_facing(tree, l, v))
        slots.append(_slot_facing(tree, r, v))
    return slots


def tree_gradients(inst: PhyloInstance, tree: Tree):
    """Analytic (d1, d2) w.r.t. lz for EVERY branch, plus the Node
    slots owning them, in O(1) dispatches per engine: one post-order
    full traversal + one fused pre-order/edge-derivative dispatch.
    Mixed state buckets sum their per-engine derivatives (the same
    cross-engine reduction `makenewz` performs per NR iteration)."""
    from examl_tpu.utils import z_slots
    p = tree.centroid_branch()
    # The tree's own schedule, shared by the traversal and the gradient
    # dispatch of every engine: a span of its own, outside both.
    with obs.span("engine:tree/schedule", also="host_schedule"):
        flat = tree.flat_full_traversal(p)
    C = inst.num_branch_slots
    root_z = z_slots(p.z, C)
    d1 = d2 = None
    for eng in inst.engines.values():
        eng.run_traversal(flat, full=True)
        e1, e2 = eng.whole_tree_gradients(flat, root_z)
        d1 = e1 if d1 is None else d1 + e1
        d2 = e2 if d2 is None else d2 + e2
    slots = _edge_slots(tree, flat, p)
    assert len(slots) == d1.shape[0], (len(slots), d1.shape)
    return slots, d1, d2


def gradient_smooth_tree(inst: PhyloInstance, tree: Tree,
                         maxtimes: int) -> bool:
    """Simultaneous whole-tree branch-length optimization: per sweep,
    one analytic gradient pass (all branches at once) and one batched
    damped-Newton update (`gradient.newton_step` — the reference NR
    body's single iteration, vectorized over edges), converging to the
    same DELTAZ movement criterion as the per-branch path.

    Simultaneous (Jacobi-style) Newton updates can make an adjacent
    branch pair overshoot in antiphase where the sequential per-branch
    solve would damp through the coupling, so each branch carries an
    Rprop-style step scale in lz space: a direction flip between
    sweeps halves it, a consistent direction grows it back (x1.2,
    capped at the EXAML_GRAD_DAMPING base, default 1).  Sweeps are
    O(1) dispatches each, so the budget is 4x `maxtimes` single-step
    sweeps against the per-branch path's `maxtimes` full-solve sweeps;
    returns False if branches still moved at the end (caller falls
    back to the per-branch ladder rung)."""
    from examl_tpu.constants import ZMAX, ZMIN
    from examl_tpu.ops import gradient
    from examl_tpu.utils import z_slots
    try:
        damping = float(os.environ.get("EXAML_GRAD_DAMPING", "") or 1.0)
    except ValueError:
        damping = 1.0
    C = inst.num_branch_slots
    scale = prev_step = None
    for _ in range(max(1, 4 * maxtimes)):
        with obs.span("opt:smooth_sweep"):
            d0 = obs.counter("engine.dispatch_count")
            inst.partition_smoothed[:] = True
            slots, d1, d2 = tree_gradients(inst, tree)
            # The host's half of a sweep: the batched Newton/Rprop
            # update and the write-back of z into the tree.
            with obs.span("opt:newton_update"):
                z0 = np.clip(np.stack([z_slots(s.z, C) for s in slots]),
                             ZMIN, ZMAX)
                znew = gradient.newton_step(z0, d1, d2)
                step = np.log(znew) - np.log(z0)
                if scale is None:
                    scale = np.full_like(step, damping)
                else:
                    flip = prev_step * step < 0.0
                    scale = np.maximum(
                        np.where(flip, scale * 0.5,
                                 np.minimum(scale * 1.2, damping)),
                        1.0 / 64)
                prev_step = step
                zapp = np.clip(z0 * np.exp(step * scale), ZMIN, ZMAX)
                upd = ~inst.partition_converged
                zapp = np.where(upd[None, :], zapp, z0)
                moved = np.abs(zapp - z0) > DELTAZ
                inst.partition_smoothed &= ~(upd & moved.any(axis=0))
                for i, s in enumerate(slots):
                    s.z[:] = zapp[i].tolist()
            # The ROADMAP §5 acceptance gauge: device dispatches this
            # sweep cost — O(1) per engine here vs O(n) on the
            # per-branch path (which publishes the same gauge from its
            # own loop).
            obs.gauge("engine.dispatches_per_smoothing_round",
                      obs.counter("engine.dispatch_count") - d0)
            obs.inc("optimize.grad_smooth_sweeps")
            if _all_smoothed(inst):
                return True
    return False


def smooth_tree(inst: PhyloInstance, tree: Tree, maxtimes: int) -> None:
    """Smoothing passes over every branch (ref `smoothTree`).

    tree.start is always tip 1, so one recursion from start.back covers
    every branch (the reference's extra non-tip start case is unreachable
    here).  Full-tree smoothing routes through the whole-tree gradient
    mode where eligible (EXAML_GRAD_SMOOTH=0 pins the per-branch
    reference path); a gradient pass that fails to settle within its
    sweep budget falls back to the per-branch rung below."""
    inst.partition_converged[:] = False
    if grad_smooth_enabled() and grad_smooth_ineligible(inst) is None:
        try:
            converged = gradient_smooth_tree(inst, tree, maxtimes)
        except Exception:                      # noqa: BLE001 — the
            # per-branch rung below is the in-run fallback; the env pin
            # (EXAML_GRAD_SMOOTH=0, bank/supervisor ladder) is the
            # cross-run one.
            obs.inc("optimize.grad_smooth_fallbacks")
            converged = None
        inst.partition_converged[:] = False
        if converged is not None:
            # A budget-exhausted sweep set (converged=False) is
            # ACCEPTED, exactly as the per-branch path accepts its own
            # maxtimes exhaustion — rerunning the O(n) per-branch pass
            # on top would pay both costs (counted for visibility).
            if not converged:
                obs.inc("optimize.grad_smooth_unconverged")
            return
    p = tree.start
    while maxtimes > 0:
        maxtimes -= 1
        d0 = obs.counter("engine.dispatch_count")
        inst.partition_smoothed[:] = True
        smooth_subtree(inst, tree, p.back)
        obs.gauge("engine.dispatches_per_smoothing_round",
                  obs.counter("engine.dispatch_count") - d0)
        if _all_smoothed(inst):
            break
    inst.partition_converged[:] = False


def local_smooth(inst: PhyloInstance, tree: Tree, p: Node,
                 maxtimes: int) -> bool:
    """Smooth only the three branches of inner node p (ref `localSmooth`)."""
    if tree.is_tip(p.number):
        return False
    inst.partition_converged[:] = False
    while maxtimes > 0:
        maxtimes -= 1
        inst.partition_smoothed[:] = True
        for s in (p, p.next, p.next.next):
            update_branch(inst, tree, s)
        if _all_smoothed(inst):
            break
    inst.partition_smoothed[:] = False
    inst.partition_converged[:] = False
    return True


def region_smooth(inst: PhyloInstance, tree: Tree, p: Node, region: int,
                  maxtimes: int) -> bool:
    """Smooth branches within `region` hops of branch (p, p.back)
    (ref `regionalSmooth`, `searchAlgo.c:368-436`).  Iterative like
    `smooth_subtree` — the same recursion-depth hazard, one level down."""
    def smooth_region(s0: Node, region: int) -> None:
        stack: List[Tuple[Node, int, bool]] = [(s0, region, False)]
        while stack:
            s, depth, expanded = stack.pop()
            if expanded:
                inst.new_view(tree, s)
                continue
            update_branch(inst, tree, s)
            if depth > 0 and not tree.is_tip(s.number):
                stack.append((s, depth, True))
                stack.append((s.next.next.back, depth - 1, False))
                stack.append((s.next.back, depth - 1, False))

    if tree.is_tip(p.number) and tree.is_tip(p.back.number):
        return False
    inst.partition_converged[:] = False
    while maxtimes > 0:
        maxtimes -= 1
        inst.partition_smoothed[:] = True
        smooth_region(p, region)
        smooth_region(p.back, region)
        if _all_smoothed(inst):
            break
    inst.partition_smoothed[:] = False
    inst.partition_converged[:] = False
    return True


def tree_evaluate(inst: PhyloInstance, tree: Tree,
                  smooth_factor: float = 1.0) -> float:
    """Smooth all branches then evaluate (ref `treeEvaluate`)."""
    with obs.span("opt:tree_evaluate",
                  args={"smooth_factor": smooth_factor}):
        smooth_tree(inst, tree, int(SMOOTHINGS * smooth_factor))
        return inst.evaluate(tree, tree.start, full=True)
