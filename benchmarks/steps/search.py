"""Step kind `search`: one bounded fast-search iteration of `-f d` from
a reset start.

A step is the body of `compute_big_rapid`'s fast loop with the slot loop
cut to the traffic's counts, made of the program's own functions
(`search/raxml_search.py`: `spr_cycle_head`, `spr_slot`, `rescore_best`):

1. reset: a fresh parse of the start tree (the generating topology after
   the traffic's `spr_moves` seeded SPR moves, with the branch lengths
   the moves leave), the generating model in force (as `steps/treeset.py`),
   a fresh `SprContext` (lnL cutoff on, `it_count` 0), `InfoList(50)`,
   `BestList(20)`; `evaluate(full)`, `tree_evaluate(inst, tree, 1.0)`:
   its lnL is what the step starts from;
2. the cycle's head, then the lazy slot body (radius 1..`radius`) on
   `lazy_slots` slots: every `len(slots) // lazy_slots`-th slot of the
   cycle's slot order from the first (all where there are fewer);
3. the thorough slot body on the first `thorough_slots` origins the lazy
   pass noted (`ilist.active_nodes()`), as the cycle's re-pass does;
4. the re-score loop over the best `rescore_trees` saved trees; the best
   tree is recalled and returned with its lnL.

Two failures of a step that would read as a fast step otherwise.  A
scan that scores wrongly: a kept tree's full evaluation differs from the
likelihood the scan or the commit gave it by more than `rescore_rel_tol`
(relative).  Held at every recall from the saved list (`BestList.recall`
returns the full evaluation) and once between the arms, where the lazy
commits took the scan's scores on trust (`ctx.start_lh` against one
`evaluate(full)` of the tree they left: the one dispatch a step makes
that the program's cycle does not).  A search that finds nothing: no
move was committed in the step.  The largest such difference of the
last step is kept on the cell (`rescore_rel_err`) for
`benchmarks/calibrate_search.py`.

Set-up ends the run non-zero, with one `refused:` line on stderr and no
result line (as `steps/treeset_sharded.py` does), where the program
lacks the slot functions, would score candidates one by one
(`batched_scan_enabled`, `thorough_batched_ok`), or the warm-up moved
neither `search.scan_dispatches` nor `engine.grad_pass_dispatches`.
`warm` is one whole step, so the scan region has its last size and every
program of a step is compiled before the window.  Under `--rehearse`
(a CPU, where the program gates both batched arms off by default) the
step kind sets the program's own switches `EXAML_BATCH_SCAN=1` and
`EXAML_BATCH_THOROUGH=1`; on the chip it sets nothing.
"""

from __future__ import annotations

import os
import sys

from benchmarks.steps import treeset

SCANS = "search.scan_dispatches"
PASSES = "engine.grad_pass_dispatches"
MOVES = "search.moves_committed"
EPSILON = 0.01                  # compute_big_rapid's own


def refuse(why: str):
    raise SystemExit(f"benchmarks/steps/search.py: refused: {why}")


def prepare(cell, params: dict) -> int:
    if cell.config.get("rehearsed"):
        os.environ["EXAML_BATCH_SCAN"] = "1"
        os.environ["EXAML_BATCH_THOROUGH"] = "1"
    from examl_tpu import obs
    from examl_tpu.search import raxml_search, spr
    missing = [f for f in ("spr_cycle_head", "spr_slot", "rescore_best")
               if not hasattr(raxml_search, f)]
    if missing:
        refuse(f"examl_tpu/search/raxml_search.py has no {missing}: the "
               "SPR cycle is not callable a slot at a time")
    treeset.prepare(cell, params)
    if not (spr.batched_scan_enabled(cell.inst)
            and spr.thorough_batched_ok(cell.inst)):
        refuse("the program would score SPR candidates one by one here "
               "(batched_scan_enabled / thorough_batched_ok)")
    cell.params = params
    cell.rescore_rel_err = 0.0
    cell.saved_list = _checked_best_list(cell)
    cell.before_warm = (obs.counter(SCANS), obs.counter(PASSES))
    return 1


def warm(cell, k: int) -> None:
    from examl_tpu import obs
    step(cell, 0)
    for name, before in zip((SCANS, PASSES), cell.before_warm):
        if obs.counter(name) == before:
            refuse(f"the warm-up step did not move {name}")


def _hold(cell, what: str, kept: float, got: float) -> None:
    """A kept tree's likelihood against its full evaluation."""
    tol = cell.params["rescore_rel_tol"]
    err = abs(got - kept) / abs(kept)
    cell.rescore_rel_err = max(cell.rescore_rel_err, err)
    if not err <= tol:
        raise RuntimeError(
            f"{what} was kept at lnL {kept!r}; its full evaluation gives "
            f"{got!r} (relative {err:.3e}, limit {tol:.1e})")


def _checked_best_list(cell):
    """The program's `BestList`, holding every recall to the likelihood
    saved with the tree."""
    from examl_tpu.search.snapshots import BestList

    class Checked(BestList):
        def recall(self, inst, tree, rank=1):
            kept = self.entries[rank - 1].likelihood
            got = super().recall(inst, tree, rank)
            _hold(cell, f"tree {rank} of the saved list", kept, got)
            return got
    return Checked


def step(cell, i: int):
    from examl_tpu import obs
    from examl_tpu.optimize.branch import tree_evaluate
    from examl_tpu.search.raxml_search import (rescore_best, spr_cycle_head,
                                               spr_slot)
    from examl_tpu.search.snapshots import BestList, InfoList
    from examl_tpu.search.spr import SprContext
    inst, par = cell.inst, cell.params
    moves = obs.counter(MOVES)
    cell.rescore_rel_err = 0.0
    tree = inst.tree_from_newick(cell.newicks[0])
    ctx = SprContext(inst)
    ilist, bt, best_t = InfoList(50), cell.saved_list(20), BestList(1)
    inst.evaluate(tree, full=True)
    before = float(tree_evaluate(inst, tree, 1.0))
    best_t.save(tree, inst.likelihood)

    slots, maxtrav = spr_cycle_head(inst, tree, ctx, par["radius"], bt, ilist)
    stride = max(1, len(slots) // par["lazy_slots"])
    for p in slots[::stride][:par["lazy_slots"]]:
        spr_slot(inst, tree, ctx, p, 1, maxtrav, bt, None, ilist, "SPR_LAZY")
    _hold(cell, "the tree the lazy commits left", ctx.start_lh,
          float(inst.evaluate(tree, full=True)))
    ctx.thorough = True
    for p in ilist.active_nodes()[:par["thorough_slots"]]:
        spr_slot(inst, tree, ctx, p, 1, maxtrav, bt, None, ilist,
                 "SPR_REPASS")
    ctx.thorough = False

    del bt.entries[par["rescore_trees"]:]
    rescore_best(inst, tree, bt, best_t, before, before, 10.0, EPSILON)
    lnl = float(best_t.recall(inst, tree, 1))
    if obs.counter(MOVES) == moves:
        raise RuntimeError(f"{MOVES} did not move: the step's "
                           f"{par['lazy_slots']} lazy and "
                           f"{par['thorough_slots']} thorough slots "
                           "committed no move")
    print(f"search step {i}: lnL {before!r} -> {lnl!r}, "
          f"{int(obs.counter(MOVES) - moves)} moves, rescore_rel_err "
          f"{cell.rescore_rel_err:.3e}", file=sys.stderr)
    return tree, lnl, before
