"""Step kind `treeset_sharded`: `treeset`'s step on a site-sharded
engine, refused where the program would do other work than the cell
names.

The step is `steps/treeset.py`'s, called: a fresh parse of the tree,
`evaluate(full)`, `tree_evaluate(inst, tree, 2.0)`.  What the cell
measures is the whole-tree gradient pass on arenas sharded over the
configuration's `site_shards` chips.  `smooth_tree` takes the
per-branch Newton path without a word wherever the pass is refused, so
two checks stand around `treeset`'s set-up and each ends the run
non-zero with one line on stderr and no result line, as run.py does for
a precision the configuration does not state:

* in `prepare`, before anything is dispatched: the engine is sharded
  over `site_shards` devices, and the program's own predicate
  (`optimize.branch.grad_smooth_ineligible`) gives no reason to keep
  the per-branch path;
* after the last `warm`: the warm-up dispatched at least one gradient
  pass (the counter the program raises a pass).  This one does not
  depend on a function's name, and `EXAML_GRAD_SMOOTH=0` ends here.

A program that cannot run the pass on shards therefore fails in set-up,
traced and untraced alike, and the cell never times the per-branch path.
"""

from __future__ import annotations

from benchmarks.steps import treeset

PASSES = "engine.grad_pass_dispatches"


def refuse(why: str):
    raise SystemExit(f"benchmarks/steps/treeset_sharded.py: refused: {why}")


def prepare(cell, params: dict) -> int:
    from examl_tpu import obs
    from examl_tpu.optimize import branch
    want = cell.config["site_shards"]
    for eng in cell.inst.engines.values():
        got = eng.sharding.site_shards if eng.sharding is not None else 1
        if got != want:
            refuse(f"the engine holds its sites in {got} shard(s), the "
                   f"configuration states {want}")
    reason = branch.grad_smooth_ineligible(cell.inst)
    if reason is not None:
        refuse("the program keeps the whole-tree gradient pass from this "
               f"engine: {reason}")
    cell.passes_before_warm = obs.counter(PASSES)
    return treeset.prepare(cell, params)


def warm(cell, k: int) -> None:
    from examl_tpu import obs
    treeset.warm(cell, k)
    if (k == len(cell.newicks) - 1
            and obs.counter(PASSES) == cell.passes_before_warm):
        refuse("the warm-up dispatched no whole-tree gradient pass "
               f"({PASSES} did not move): the smoothing ran branch by branch")


step = treeset.step
