#!/usr/bin/env python3
"""Readings for the replicate cell's limits: sound runs, the
lower-precision control, and planted faults, several seeds in one
process.  The benchmark's own runs never come here.

    python benchmarks/calibrate_replicates.py --workload <cell> \
        --seeds 1,2 [--seconds 1] [--control dot|clv] \
        [--fault weights_shift|unchanged|altered|half] [--rehearse]

Each run is `run.run_cell` as a benchmark run makes it.  Two sets of
numbers come back: run.py's comparison of the best tree (`check`), and
the step kind's own of the starts and the replicates
(`benchmarks/steps/replicates.py`: `starts_lnl_rel_err`,
`replicates_lnl_rel_err`, `start_newton_dz_max`), which ends set-up
where the warm-up analysis fails it (`setup_failed`).

* `--control`: `calibrate.py`'s lower-precision switches.
* `--fault weights_shift`: the fleet scores bootstrap replicate j with
  replicate j+1's weights; `unchanged`: the fleet's later cycles smooth
  nothing (the starts keep their default branch lengths); `altered`: the
  `-f e` lnL and every replicate's altered by 1e-4 of themselves where
  they are produced; `half`:
  `calibrate.py`'s fault of that name (half of the site patterns left
  out of the engine's weights, the rest counted double).

One JSON line a run on stdout (appended to
`chiprun_out/calibrate_replicates.jsonl`).
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

FAULTS = ("weights_shift", "unchanged", "altered", "half")


def plant(fault: str):
    """Break the analysis underneath the harness; returns the undo."""
    from examl_tpu.fleet.driver import FleetDriver
    kind = importlib.import_module("benchmarks.steps.replicates")
    if fault == "half":
        return calibrate.plant("half")
    if fault == "weights_shift":
        obj, name = FleetDriver, "_weights_for"
        real = FleetDriver._weights_for

        def new(self, job):
            return real(self, self.jobs[(self.jobs.index(job) + 1)
                                        % len(self.jobs)])
    elif fault == "unchanged":
        obj, name = FleetDriver, "_smooth_if_due"

        def new(self, batch):
            return None
    elif fault == "altered":
        obj, name = kind, "_evaluate_and_resample"
        real = kind._evaluate_and_resample

        def new(cell, newick):
            tree, lnl, before, reps = real(cell, newick)
            return (tree, lnl * (1.0 + 1e-4), before * (1.0 + 1e-4),
                    reps * (1.0 + 1e-4))
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def one_run(workload: str, seed: int, seconds: float, fault, control,
            rehearse: bool) -> dict:
    kind = importlib.import_module("benchmarks.steps.replicates")
    real_prepare, cells = kind.prepare, []

    def prepare(cell, params):
        cells.append(cell)
        return real_prepare(cell, params)
    kind.prepare = prepare
    undo = plant(fault) if fault else (lambda: None)
    env = calibrate.CONTROLS.get(control)
    out = {"correct": None, "setup_failed": None}
    try:
        r = bench.run_cell(workload, seed, seconds, False,
                           rehearse=rehearse, control_env=env)
        out.update(correct=r["correct"], check=r["check"],
                   attempted=r["attempted"], failed=r["failed"],
                   step_seconds=r["step_seconds"],
                   precision=r["precision"], device=r["device"]["kind"])
    except SystemExit as exc:
        out["setup_failed"] = str(exc)[:400]
    finally:
        kind.prepare = real_prepare
        undo()
        for k in env or {}:
            os.environ.pop(k, None)
    out["step_check"] = cells[-1].check_readings if cells else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", choices=sorted(calibrate.CONTROLS))
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    for seed in (int(s) for s in a.seeds.split(",")):
        rec = one_run(a.workload, seed, a.seconds, a.fault, a.control,
                      a.rehearse)
        line = json.dumps({"workload": a.workload, "seed": seed,
                           "control": a.control, "fault": a.fault, **rec})
        print(line, flush=True)
        if not a.rehearse:
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "calibrate_replicates.jsonl"),
                      "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
