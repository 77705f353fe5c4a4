"""Step kind `replicates`: the replicate analysis of one alignment.

The analysis is the three runs a user makes on an alignment, through the
calls `cli/main.py` makes for them:

1. `-N starts --fleet-batch fleet_batch --fleet-cycles cycles`: a fresh
   `FleetDriver` runs `make_jobs("start", starts, parent_seed, cycles)`,
   random start trees whose seeds derive from the traffic's fixed
   `parent_seed` (every run and every step scores the same topologies);
   cycle 0 scores each tree, each later cycle smooths its branch lengths
   through the batched gradient sweep, then scores it again;
2. `-f e -t <the best start>`: the start with the highest lnL, parsed
   from the newick the driver leaves, gets `inst.evaluate(full)` and
   `branch.tree_evaluate(inst, tree, tree_epsilon)`;
3. `-b replicates -t <that tree>`: a fresh `FleetDriver` runs
   `make_jobs("bootstrap", replicates, parent_seed)`, weight replicates
   of the tree: one CLV pass, then the batched root reduction.

The warm-up is one analysis in that order.  A step is one analysis too,
its runs taken from where the last one left off: 2 and 3 on the best
start the previous analysis found, then 1, which finds the same best
start again; so the device trace, which the profiler cuts off partway
through a step this long, holds every program.  The models in force are
the generating ones (as `steps/treeset.py` sets them, standing for the
first tree's `mod_opt`).

The step returns the `-f e` tree, which run.py holds to the f64
reference.  The rest of the analysis is held here, as `steps/search.py`
holds its scan's scores (run.py compares one tree a step): after the
warm-up, against `benchmarks/reference_replicates.py`: each start's lnL
against the f64 reference on its tree, each replicate's against the
benchmark's own draw of its weights dotted with the f64 per-pattern lnLs
of the `-f e` tree (relative, `lnl_rel_tol`), and the move one Newton
step of the reference would still make on the best start's branches
(`newton_dz_tol`); a failure ends set-up non-zero with one `failed:`
line.  Every window step, the same analysis, is held to those reference
lnLs, and fails past `lnl_rel_tol`.  A step also fails where the fleet
smoothed a start one job at a time (its log says so): that is not the
path this cell measures.  The readings are kept on the cell
(`check_readings`) for `benchmarks/calibrate_replicates.py`.

Set-up ends the run non-zero, with one `refused:` line on stderr and no
result line, where the program would not run the analysis through the
fleet's tree axis (no batched tier, no batched gradient smoothing) or
does not count its batched dispatches (`fleet.batch.count_dispatch`),
which the cell's fleet metrics read.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks import datagen, reference, reference_replicates
from benchmarks.steps import treeset

PER_JOB = "smoothing per job"        # FleetDriver logs it where it falls back


def refuse(why: str):
    raise SystemExit(f"benchmarks/steps/replicates.py: refused: {why}")


def prepare(cell, params: dict) -> int:
    from examl_tpu.fleet import batch
    from examl_tpu.optimize.branch import (grad_smooth_enabled,
                                           grad_smooth_ineligible)
    if not hasattr(batch, "count_dispatch"):
        refuse("examl_tpu/fleet/batch.py has no count_dispatch: the "
               "batched dispatches are neither counted nor named apart, "
               "so the cell's fleet metrics would have nothing to read")
    ev = cell.inst.batch_evaluator()
    if ev is None or not ev.fast:
        refuse("the fleet's batched fast tier is not available here")
    why = grad_smooth_ineligible(cell.inst)
    if not grad_smooth_enabled() or why is not None:
        refuse(f"the fleet would smooth its starts one job at a time "
               f"({why or 'EXAML_GRAD_SMOOTH=0'})")
    treeset.prepare(cell, {"trees": 0})     # the generating models
    cell.params = params
    cell.check_readings = {}
    return 1


def warm(cell, k: int) -> None:
    """One whole analysis in the user's order (every program a step
    dispatches, each batch pad it picks), then the reference values of
    its answers, and the analysis held to them."""
    cell.starts = _starts(cell)
    starts, best = cell.starts
    tree, _, _, reps = _evaluate_and_resample(cell, starts[best][0])
    cell.analysis = {"best": best, "tree": tree, "starts": starts,
                     "replicates": reps}
    cell.expected = _reference(cell, cell.analysis)
    try:
        _hold(cell, cell.analysis)
        _hold_smoothed(cell)
    except RuntimeError as exc:
        raise SystemExit(f"benchmarks/steps/replicates.py: failed: {exc}")


def _run_fleet(cell, jobs, **kw):
    from examl_tpu.fleet.driver import FleetDriver
    lines = []
    done = FleetDriver(cell.inst, batch_cap=cell.params["fleet_batch"],
                       log=lines.append, **kw).run(jobs)
    bad = [j.job_id for j in done if j.failed or not j.done]
    if bad or any(PER_JOB in m for m in lines):
        raise RuntimeError(f"the fleet failed jobs {bad} or left the "
                           f"batched path: {lines}")
    return done


def _starts(cell):
    """`-N`: the starts through the fleet; returns them and the index of
    the best."""
    from examl_tpu.fleet.jobs import make_jobs
    par = cell.params
    starts = _run_fleet(cell, make_jobs("start", par["starts"],
                                        par["parent_seed"],
                                        cycles=par["cycles"]),
                        cycles=par["cycles"])
    return ([(j.newick, j.lnl) for j in starts],
            max(range(len(starts)), key=lambda k: starts[k].lnl))


def _evaluate_and_resample(cell, newick: str):
    """`-f e` on the best start, then `-b` on the tree it leaves."""
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.optimize.branch import tree_evaluate
    inst, par = cell.inst, cell.params
    tree = inst.tree_from_newick(newick)
    before = float(inst.evaluate(tree, full=True))
    lnl = float(tree_evaluate(inst, tree, par["tree_epsilon"]))
    reps = _run_fleet(cell, make_jobs("bootstrap", par["replicates"],
                                      par["parent_seed"]), start_tree=tree)
    return tree, lnl, before, np.array([j.lnl for j in reps])


def step(cell, i: int):
    """One whole analysis, its runs in turn from where the last step
    left off: `-f e` and `-b` on the best start the previous step (or
    the warm-up) found, then the starts, which find the same best start
    again.  Every step does one analysis's work; the device trace, which
    the profiler cuts off partway through a step this long, sees every
    program of the analysis in its first seconds."""
    starts, best = cell.starts
    tree, lnl, before, reps = _evaluate_and_resample(cell, starts[best][0])
    cell.starts = _starts(cell)
    cell.analysis = {"best": best, "tree": tree, "starts": cell.starts[0],
                     "replicates": reps}
    _hold(cell, cell.analysis)
    print(f"replicates step {i}: best start {best} lnL {before!r} -> "
          f"{lnl!r}; replicates {float(reps.min())!r} .. "
          f"{float(reps.max())!r}", file=sys.stderr)
    return tree, lnl, before


def _edges(tree, C: int):
    return [(p.number, q.number, *p.z[:C]) for p, q in tree.all_branches()]


def _reference(cell, analysis) -> dict:
    """f64 lnL of every start on its tree and of every replicate on the
    best tree, under the models in force and the benchmark's own
    frequencies (as run.py gives them to the reference), and the largest
    move one Newton step would still make on the best start."""
    inst, config, gen = cell.inst, cell.config, cell.gen
    patterns, bounds = gen["patterns"], gen["bounds"]
    C = inst.num_branch_slots
    models = [(np.array(m.rates, dtype=np.float64),
               reference.empirical_freqs(patterns[:, s:e], None,
                                         config["states"]),
               float(m.alpha)) for m, (s, e) in zip(inst.models, bounds)]

    def columns(edges):
        return np.concatenate([
            reference_replicates.site_lnls(
                patterns[:, s:e], [(a, b, z[k if C > 1 else 0])
                                   for a, b, *z in edges],
                config["taxa"], *mdl, config["rate_categories"])
            for k, ((s, e), mdl) in enumerate(zip(bounds, models))])

    trees = [inst.tree_from_newick(nw) for nw, _ in analysis["starts"]]
    best = _edges(trees[analysis["best"]], C)
    _, _, d1, d2 = reference.evaluate_parts(
        patterns, bounds, best, config["taxa"], models,
        config["rate_categories"])
    dom = config["domain"]
    weights = reference_replicates.replicate_weights(
        patterns, bounds, cell.params["parent_seed"],
        len(analysis["replicates"]))
    return {"starts": np.array([columns(_edges(t, C)).sum() for t in trees]),
            "replicates": weights @ columns(_edges(analysis["tree"], C)),
            "start_newton_dz": max(float(reference.newton_dz(
                [(e[0], e[1], e[2 + c]) for e in best], d1[c], d2[c],
                dom["z_min"], dom["z_max"]).max()) for c in range(C))}


def _hold(cell, analysis) -> None:
    """The analysis against the reference values: the largest relative
    difference of a start's and of a replicate's lnL."""
    want = cell.expected
    got = {"starts": np.array([lnl for _, lnl in analysis["starts"]]),
           "replicates": analysis["replicates"]}
    readings = {f"{k}_lnl_rel_err": float(np.max(np.abs(got[k] - want[k])
                                                 / np.abs(want[k])))
                for k in got}
    for k, v in readings.items():
        cell.check_readings[k] = max(cell.check_readings.get(k, 0.0), v)
    print("check " + " ".join(f"{k} {v:.3e}" for k, v in readings.items()),
          file=sys.stderr)
    tol = cell.params["lnl_rel_tol"]
    over = {k: v for k, v in readings.items() if not v <= tol}
    if over:
        raise RuntimeError(f"{over} over lnl_rel_tol {tol:.1e}")


def _hold_smoothed(cell) -> None:
    """The best start's branches against their optimum: the largest
    move one Newton step of the reference would still make on it."""
    dz = cell.expected["start_newton_dz"]
    cell.check_readings["start_newton_dz_max"] = dz
    print(f"check start_newton_dz_max {dz:.3e}", file=sys.stderr)
    if not dz <= cell.params["newton_dz_tol"]:
        raise RuntimeError(f"the best start's start_newton_dz_max {dz:.3e} "
                           f"is over newton_dz_tol "
                           f"{cell.params['newton_dz_tol']:.1e}")
