"""Step kind `modopt`: one whole model-optimisation round from scratch.

A step resets the start tree to default branch lengths and the models
to the CLI's initial ones, then runs the call `cli.main -f e` makes on
its first tree and `-f d` between SPR phases, for one round:
`model_opt.mod_opt(inst, tree, 0.1, max_rounds=1)` (rate Brents,
`tree_evaluate`, alpha Brent, `tree_evaluate`).  Every step does the
same work, so every step leaves the same state.
"""

from __future__ import annotations


def prepare(cell, params: dict) -> int:
    """Build the step's fixed inputs; returns how many distinct steps a
    cycle has (the warm-up visits each once)."""
    cell.tree = cell.inst.tree_from_newick(cell.gen["tree"])
    cell.lnl_reset = None
    return 1


def _reset(cell):
    cell.tree.reset_branches()
    cell.inst.models[:] = cell.initial_models
    cell.inst.push_models()


def warm(cell, k: int) -> None:
    """A whole step (the Brent's path depends on the data, so nothing
    smaller is sure to dispatch every program), and the lnL of the reset
    state every step starts from."""
    _reset(cell)
    cell.lnl_reset = float(cell.inst.evaluate(cell.tree, full=True))
    step(cell, 0)


def step(cell, i: int):
    from examl_tpu.optimize.model_opt import mod_opt
    _reset(cell)
    lnl = mod_opt(cell.inst, cell.tree, 0.1, max_rounds=1)
    return cell.tree, float(lnl), cell.lnl_reset
