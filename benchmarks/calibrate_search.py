#!/usr/bin/env python3
"""Readings for the search cells' limits, on the chip at a cell's size.

    python benchmarks/calibrate_search.py --workload <cell> --seed N \
        [--control dot|clv]                          # the slot readings
    python benchmarks/calibrate_search.py --workload <cell> --seed N \
        --fault unchanged|stale|half_scan [--seconds S]    # a whole run

The benchmark's own runs never come here.

**Slot readings** (no `--fault`): the cell's engine, start tree and
model as a step has them after `tree_evaluate(1.0)`; one slot's prune
(the first inner slot of the cycle's order with an inner node on both
sides), its plan, ONE dispatch of the lazy scan program and ONE of the
thorough one, and of each 8 seed-drawn candidates against
`benchmarks/reference_search.py`:

* `window_equal`: the plan's candidate edges are the reference's window;
* `scan_lnl_rel_err`: max |scan's lnL - reference's| / |reference's|
  over the lazy candidates;
* `thorough_lnl_rel_err`, `thorough_newton_dz`: the same for the thorough
  candidates at the program's branch triplets, and the largest move one
  Newton step of the reference's derivatives still makes on a triplet.

With `--control dot|clv` the program runs under `calibrate.py`'s
lower-precision switches.

**Faults** (`--fault`): `run.run_cell` as the driver's run makes it,
with the fault planted when the warm-up step has returned (planted
before, a step failure would end set-up, not a step):

* `unchanged`: `calibrate.py`'s fault of that name (the branch smoothing
  returns the state it was given);
* `stale`: the scan dispatches leave their uppass rows out of the
  traversal, so candidates are scored against whatever the scan region
  held;
* `half_scan`: both scan programs score their candidates over every
  other site pattern.

The line says whether the run came out correct, how many steps failed
and why (the step kind's two failures), and the numbers compared.

One JSON line on stdout, appended to `chiprun_out/calibrate_search.jsonl`.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import calibrate, reference_search  # noqa: E402
from benchmarks import run as bench  # noqa: E402

SAMPLE = 8


def build_cell(workload: str, seed: int, rehearse: bool, env):
    """The cell's engine and inputs as `run.run_cell` makes them, up to
    the step kind's `prepare`."""
    manifest, entry, config, traffic = bench.find_cell(workload)
    if rehearse:
        config = {**config, **config["rehearse"], "rehearsed": True}
    dev, _peak = bench.claim_device(entry["chips"], rehearse)
    for k, v in (env or {}).items():
        os.environ[k] = v
    from examl_tpu import obs
    from examl_tpu.config import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    tag = f"{entry['config']}-{entry['traffic']}" + (
        "-rehearse" if rehearse else "")
    gen, bytefile, workdir = bench.make_inputs(config, traffic, tag, seed)
    obs.reset()
    inst, data = bench.build_instance(bytefile)
    cell = types.SimpleNamespace(
        inst=inst, data=data, gen=gen, config=config,
        initial_models=list(inst.models), traced_steps=0)
    kind = importlib.import_module(f"benchmarks.steps.{traffic['kind']}")
    kind.prepare(cell, traffic)
    return cell, traffic, dev


def slot_readings(workload: str, seed: int, rehearse: bool, env) -> dict:
    from examl_tpu.optimize.branch import tree_evaluate
    from examl_tpu.search import batchscan, spr
    from examl_tpu.tree.topology import hookup
    cell, traffic, dev = build_cell(workload, seed, rehearse, env)
    inst, config = cell.inst, cell.config
    ntips = config["taxa"]
    tree = inst.tree_from_newick(cell.newicks[0])
    inst.evaluate(tree, full=True)
    tree_evaluate(inst, tree, 1.0)
    p = next(s for s in spr.dfs_slot_order(tree)
             if not tree.is_tip(s.number)
             and not tree.is_tip(s.next.back.number)
             and not tree.is_tip(s.next.next.back.number))
    edges = [(a.number, b.number, float(a.z[0]))
             for a, b in tree.all_branches()]
    p1, p2 = p.next.back, p.next.next.back
    p1z, p2z = list(p1.z), list(p2.z)
    ctx = spr.SprContext(inst)
    spr.remove_node(inst, tree, ctx, p)
    zqr, zs, s = float(ctx.zqr[0]), float(p.z[0]), p.back.number
    plan = batchscan.plan_for_endpoints(inst, tree, p, p1, p2, 1,
                                        traffic["radius"])
    t0 = time.time()
    lazy = batchscan.run_plan(inst, tree, plan)
    t1 = time.time()
    thorough, triplets = batchscan.run_plan_thorough(inst, tree, plan)
    t2 = time.time()
    hookup(p.next, p1, p1z)
    hookup(p.next.next, p2, p2z)

    got = sorted((c.q_num, c.q_slot.back.number) for c in plan.candidates)
    want = sorted((v, w) for v, w, _d in reference_search.window(
        edges, p.number, s, traffic["radius"], ntips))
    (generating,) = cell.gen["models"]          # the search cells: one part
    model = {"rates": generating["rates"],
             "freqs": bench.own_model(config, cell.gen["patterns"])["freqs"],
             "alpha": generating["alpha"]}
    pick = np.random.default_rng([seed, 0x5CA9]).permutation(
        len(plan.candidates))[:SAMPLE]
    out = {"scan_lnl_rel_err": 0.0, "thorough_lnl_rel_err": 0.0,
           "thorough_newton_dz": 0.0}
    for i in (int(j) for j in pick):
        c = plan.candidates[i]
        v, w = c.q_num, c.q_slot.back.number
        ref = reference_search.lazy_candidate(
            cell.gen["patterns"], model, edges, p.number, s, zqr, zs, v, w,
            config["rate_categories"])
        ref_t, dz = reference_search.thorough_candidate(
            cell.gen["patterns"], model, edges, p.number, s, zqr, v, w,
            triplets[i], config["rate_categories"])
        print(f"candidate {i} ({v}, {w}) depth {c.depth}: lazy "
              f"{float(lazy[i])!r} reference {ref!r}; thorough "
              f"{float(thorough[i])!r} reference {ref_t!r} dz {dz}",
              file=sys.stderr)
        out["scan_lnl_rel_err"] = max(
            out["scan_lnl_rel_err"], abs(float(lazy[i]) - ref) / abs(ref))
        out["thorough_lnl_rel_err"] = max(
            out["thorough_lnl_rel_err"],
            abs(float(thorough[i]) - ref_t) / abs(ref_t))
        out["thorough_newton_dz"] = max(out["thorough_newton_dz"],
                                        float(dz.max()))
    return {**out, "window_equal": got == want,
            "candidates": len(plan.candidates), "checked": len(pick),
            "entries": len(plan.down_entries) + len(plan.up_entries),
            "pruned": p.number, "scan_dispatch_s": t1 - t0,
            "thorough_dispatch_s": t2 - t1,
            "precision": bench.stated_precision(inst),
            "device": dev["kind"]}


# -- faults ------------------------------------------------------------------


def plant_stale():
    """Scan dispatches without their uppass entries."""
    from examl_tpu.ops.engine import LikelihoodEngine
    real = LikelihoodEngine._scan_traversal_arrays

    def arrays(self, down_entries, up_entries, base):
        return real(self, down_entries, [], base)
    LikelihoodEngine._scan_traversal_arrays = arrays
    return lambda: setattr(LikelihoodEngine, "_scan_traversal_arrays", real)


def plant_half_scan():
    """Both scan programs score over every other site pattern."""
    from examl_tpu.search import batchscan
    real = {n: getattr(batchscan, n)
            for n in ("scan_program", "thorough_program")}

    def halved(build):
        def program(eng, n_chunks):
            fn = build(eng, n_chunks)

            def call(*args):
                args = list(args)
                w = args[-3]                   # ..., weights, tips, rates
                args[-3] = w.reshape(-1).at[::2].set(0).reshape(w.shape)
                return fn(*args)
            return call
        return program
    for n, build in real.items():
        setattr(batchscan, n, halved(build))
    return lambda: [setattr(batchscan, n, f) for n, f in real.items()]


PLANTS = {"unchanged": lambda: calibrate.plant("unchanged"),
          "stale": plant_stale, "half_scan": plant_half_scan}


def fault_run(workload: str, seed: int, seconds: float, fault: str,
              rehearse: bool) -> dict:
    kind = importlib.import_module("benchmarks.steps.search")
    real_warm, real_step = kind.warm, kind.step
    undo, why = [], []

    def warm(cell, k):
        real_warm(cell, k)
        undo.append(PLANTS[fault]())

    def step(cell, i):
        try:
            return real_step(cell, i)
        except Exception as exc:      # noqa: BLE001 - kept and re-raised
            why.append(f"{type(exc).__name__}: {exc}"[:300])
            raise
    kind.warm, kind.step = warm, step
    try:
        r = bench.run_cell(workload, seed, seconds, False, rehearse=rehearse)
    finally:
        kind.warm, kind.step = real_warm, real_step
        for u in undo:
            u()
    return {"correct": r["correct"], "check": r["check"],
            "attempted": r["attempted"], "failed": r["failed"],
            "why_failed": why, "steps": r["steps"],
            "step_seconds": r["step_seconds"],
            "precision": r["precision"], "device": r["device"]["kind"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", choices=sorted(calibrate.CONTROLS))
    ap.add_argument("--fault", choices=sorted(PLANTS))
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    if a.fault:
        rec = fault_run(a.workload, a.seed, a.seconds, a.fault, a.rehearse)
    else:
        rec = slot_readings(a.workload, a.seed, a.rehearse,
                            calibrate.CONTROLS.get(a.control))
    line = json.dumps({"workload": a.workload, "seed": a.seed,
                       "control": a.control, "fault": a.fault, **rec})
    print(line, flush=True)
    if not a.rehearse:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "calibrate_search.jsonl"), "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
