#!/usr/bin/env python3
"""Readings of a site-sharded cell that `calibrate.py` cannot take.

    python benchmarks/calibrate_sharded.py --workload <cell> --seed N \
        --seconds S [--fault unchanged|one_shard] [--trace 1 --all-layers]

The benchmark's own runs never come here.  A run is `run.run_cell` as
the driver's run makes it, so what is compared is what the timed steps
left at the timed sizes; sound runs, the controls and the faults that
need no mesh stay with `calibrate.py`.

* `--fault unchanged`: calibrate.py's fault of that name (the optimiser
  returns the state it was given), planted after the step kind's last
  `warm` has returned.  Planted before, as calibrate.py plants it, the
  warm-up makes no gradient pass and `steps/treeset_sharded.py` ends
  the run in set-up: the guard working, and no reading.  Here the
  warm-up is sound and every timed step leaves its state as given.
* `--fault one_shard`: the derivative all-reduce left out, the fault
  that exists only across chips.  Every chip keeps its own shard's
  partial (d1, d2) and the optimiser is handed the first shard's.
  Planted so that the program keeps its shape and its one all-reduce:
  in `jax.lax.psum` over the site axis every shard but the first adds
  zeros.  The traversal programs (GSPMD, no `psum` of the program's
  own) are untouched, so lnL is still the whole alignment's.
* `--all-layers` (with `--trace 1`): read every per-layer metric of
  BENCHMARK.json in the traced run, also those whose `workloads` list
  or `chips` rule leaves the cell out (since PR 38 PR 28's seven carry
  no list and are the cell's own; what is left out is the one-chip
  rooflines and the search's).  For PERF.md's breakdown of the cell; a
  reader that finds nothing is left out.

One JSON line on stdout, appended to `chiprun_out/calibrate.jsonl`:
seed, what was planted, `correct`, the numbers compared with their
limits, `step_s`, and in a traced run every metric read, the device's
busy seconds and the owners of its idle gaps.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import calibrate  # noqa: E402
from benchmarks import run as bench  # noqa: E402


def plant_after_warm(kind_name: str, fault: str):
    """calibrate.py's `fault`, planted when the step kind's last `warm`
    returns; returns the undo."""
    kind = importlib.import_module(f"benchmarks.steps.{kind_name}")
    real, undo = kind.warm, []

    def warm(cell, k):
        real(cell, k)
        if k == len(cell.newicks) - 1:
            undo.append(calibrate.plant(fault))
    kind.warm = warm

    def restore():
        kind.warm = real
        for u in undo:
            u()
    return restore


def plant_one_shard():
    """Only the first site shard reaches the program's own all-reduce."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.parallel.sharding import SITE_AXIS
    real = jax.lax.psum

    def psum(x, axis_name, **kw):
        if axis_name == SITE_AXIS:
            first = jax.lax.axis_index(SITE_AXIS) == 0
            x = jax.tree.map(
                lambda a: jnp.where(first, a, jnp.zeros_like(a)), x)
        return real(x, axis_name, **kw)
    jax.lax.psum = psum
    return lambda: setattr(jax.lax, "psum", real)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fault", choices=("unchanged", "one_shard"))
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--all-layers", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    traffic = bench.find_cell(a.workload)[3]
    undo = []
    if a.fault == "unchanged":
        undo.append(plant_after_warm(traffic["kind"], "unchanged"))
    elif a.fault == "one_shard":
        undo.append(plant_one_shard())
    if a.all_layers:
        real_metrics_of = bench.metrics_of
        bench.metrics_of = lambda manifest, group, cell: manifest[group]
        undo.append(lambda: setattr(bench, "metrics_of", real_metrics_of))
    try:
        r = bench.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                           rehearse=a.rehearse)
    finally:
        for u in undo:
            u()
    rec = {"workload": a.workload, "seed": a.seed, "fault": a.fault,
           "trace": a.trace, "correct": r["correct"], "check": r["check"],
           "attempted": r["attempted"], "failed": r["failed"],
           "steps": r["steps"], "precision": r["precision"],
           "step_s": r.get("step_s_traced")
           or r["metrics"]["step_s"]["value"],
           "step_seconds": r["step_seconds"],
           "metrics": {k: m["value"] for k, m in r["metrics"].items()},
           "device": r["device"]}
    if "breakdown" in r:
        rec["idle_gaps"] = r["breakdown"]["idle_gaps"]
    line = json.dumps(rec)
    print(line, flush=True)
    if not a.rehearse:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "calibrate.jsonl"), "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
