#!/usr/bin/env python3
"""Readings for the limits in `correct/<cell>.json`: sound runs, the
lower-precision control, and planted faults, several seeds in one
process.  The benchmark's own runs never come here.

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 6 [--control dot|clv] [--data-seed N] \
        [--fault unchanged|half|altered|freqs|swap_parts|part_freqs] \
        [--manifest FILE]

Each run is `run.run_cell` as the driver's run makes it, so what is
compared is what the timed steps produced at the timed sizes.

* `--control dot`: the program with `EXAML_DOT_PRECISION=default` (one
  bf16 pass where the configuration states three); `--control clv`: the
  program with `EXAML_CLV_DTYPE=bf16` (the arena stored in bf16 where
  the configuration states f32).  Both are the program's own
  lower-precision paths, so they stand as the control.
* `--fault unchanged`: the optimiser returns the state it was given
  (no Brent, no smoothing); `half`: half of the site patterns are left
  out and the rest counted double; `altered`: the step's lnL is altered
  where it is produced (by 1e-4 of itself); `freqs`: the parser counts
  its empirical frequencies over every other column only.  Two that only
  a cell of several partitions can show: `swap_parts`: at engine build
  the block ids of the two widest partitions are exchanged, so the
  program applies each one's model to the other's sites; `part_freqs`:
  `freqs` on the second partition only.
* `--data-seed N`: another problem (tree, alignment, moved trees) than
  the configuration's `data_seed`, to read the numbers on problems the
  limits were not set on.

One JSON line a run on stdout (and appended to
`chiprun_out/calibrate.jsonl`): seed, what was planted, `correct`, the
numbers compared with their limits, steps and `step_s`.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench  # noqa: E402

CONTROLS = {"dot": {"EXAML_DOT_PRECISION": "default"},
            "clv": {"EXAML_CLV_DTYPE": "bf16"}}


def plant(fault: str):
    """Break the timed path underneath the harness; returns the undo."""
    import importlib

    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io import alignment
    from examl_tpu.optimize import branch, model_opt
    undo = []

    def patch(obj, name, new):
        old = getattr(obj, name)
        setattr(obj, name, new)
        undo.append(lambda: setattr(obj, name, old))

    if fault == "unchanged":
        patch(branch, "smooth_tree", lambda inst, tree, maxtimes: None)
        patch(model_opt, "_opt_param", lambda *a, **k: None)
    elif fault == "half":
        real = PhyloInstance.__init__

        def init(self, *a, **k):
            real(self, *a, **k)
            for eng in self.engines.values():
                w = eng.weights
                keep = (w.reshape(-1).at[::2].set(0) * 2).reshape(w.shape)
                eng.weights = keep.astype(w.dtype)
        patch(PhyloInstance, "__init__", init)
    elif fault in ("freqs", "part_freqs"):
        real_freqs = alignment.empirical_frequencies
        calls = itertools.count()        # the parser counts a partition a call

        def some_freqs(codes, weights, dt, *a, **k):
            if fault == "freqs" or next(calls) == 1:
                codes, weights = codes[:, ::2], weights[::2]
            return real_freqs(codes, weights, dt, *a, **k)
        patch(alignment, "empirical_frequencies", some_freqs)
    elif fault == "swap_parts":
        from examl_tpu import instance
        real_pack = instance.pack_partitions

        def pack(partitions, **k):
            buckets = real_pack(partitions, **k)
            for bucket in buckets.values():
                if bucket.num_parts < 2:
                    raise SystemExit("swap_parts needs two partitions of "
                                     "one state count")
                a, b = np.argsort(bucket.part_widths)[-2:]
                ids = bucket.block_part
                bucket.block_part = np.where(
                    ids == a, b, np.where(ids == b, a, ids)).astype(ids.dtype)
            return buckets
        patch(instance, "pack_partitions", pack)
    elif fault == "altered":
        for kind in ("modopt", "treeset"):
            mod = importlib.import_module(f"benchmarks.steps.{kind}")
            real_step = mod.step

            def step(cell, i, real_step=real_step):
                tree, lnl, before = real_step(cell, i)
                return tree, lnl * (1.0 + 1e-4), before * (1.0 + 1e-4)
            patch(mod, "step", step)
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return lambda: [u() for u in undo]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--control", choices=sorted(CONTROLS))
    ap.add_argument("--fault", choices=("unchanged", "half", "altered",
                                        "freqs", "swap_parts", "part_freqs"))
    ap.add_argument("--manifest", default="BENCHMARK.json", metavar="FILE")
    ap.add_argument("--data-seed", type=int)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    out = os.path.join(ROOT, "chiprun_out")
    for seed in (int(s) for s in a.seeds.split(",")):
        env = CONTROLS.get(a.control)
        undo = plant(a.fault) if a.fault else (lambda: None)
        try:
            r = bench.run_cell(a.workload, seed, a.seconds, False,
                               rehearse=a.rehearse, control_env=env,
                               data_seed=a.data_seed,
                               manifest_file=a.manifest)
        finally:
            undo()
            for k in env or {}:
                os.environ.pop(k, None)
        line = json.dumps({
            "workload": a.workload, "seed": seed, "control": a.control,
            "fault": a.fault, "data_seed": a.data_seed, "correct": r["correct"], "check": r["check"],
            "attempted": r["attempted"], "failed": r["failed"],
            "steps": r["steps"], "precision": r["precision"],
            "step_s": r["metrics"]["step_s"]["value"],
            "step_seconds": r["step_seconds"],
            "device": r["device"]["kind"]})
        print(line, flush=True)
        if not a.rehearse:
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, "calibrate.jsonl"), "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
