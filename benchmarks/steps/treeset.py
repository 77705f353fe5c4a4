"""Step kind `treeset`: one tree of a `-f e -t <many trees>` run after
the first.

A step is what `cli/main.py run_tree_evaluation` does in its
`fast and i > 0` branch: `inst.tree_from_newick`, `inst.evaluate(tree,
full=True)`, `branch.tree_evaluate(inst, tree, 2.0)`.  The trees are
`trees` topologies, each `spr_moves` seeded SPR moves from the
generating one, cycled; with the traffic file's `branch_lengths:
generating` they carry the lengths the moves leave them, without it
none (the parser's defaults).  The model in force on each partition is
its own generating one where the model has that parameter free
(exchangeabilities of DNA, alpha), standing for what the first tree's
`mod_opt` would have fitted.
"""

from __future__ import annotations


def prepare(cell, params: dict) -> int:
    from examl_tpu.models.gtr import with_alpha, with_rates
    cell.newicks = cell.gen["moved_trees"][:params["trees"]]
    models = []
    for part, m, gen in zip(cell.data.partitions, cell.initial_models,
                            cell.gen["models"]):
        if part.datatype.name != "AA" or part.model_name == "GTR":
            m = with_rates(m, gen["rates"])
        models.append(with_alpha(m, gen["alpha"]))
    cell.inst.models[:] = models
    cell.inst.push_models()
    return len(cell.newicks)


def warm(cell, k: int) -> None:
    """Every program a step on tree k dispatches, at the smallest
    budget: the full evaluation, one smoothing round (four gradient
    sweeps) and the closing evaluation at the tip edge.  Programs are
    keyed by topology, not by branch lengths."""
    from examl_tpu.optimize.branch import tree_evaluate
    tree = cell.inst.tree_from_newick(cell.newicks[k])
    cell.inst.evaluate(tree, full=True)
    tree_evaluate(cell.inst, tree, 1.0 / 32.0)


def step(cell, i: int):
    from examl_tpu.optimize.branch import tree_evaluate
    tree = cell.inst.tree_from_newick(cell.newicks[i % len(cell.newicks)])
    before = float(cell.inst.evaluate(tree, full=True))
    lnl = float(tree_evaluate(cell.inst, tree, 2.0))
    return tree, lnl, before
