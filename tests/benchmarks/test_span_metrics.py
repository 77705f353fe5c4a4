"""The per-layer metrics that read the program's spans (PR 28), CPU.

A rehearsed traced run of the host-bound cell reports all seven, they do
not count a second twice, and the reader leaves a metric out where the
program has no such span (the parent of the PR that brought them).  No
number of this file is a device number.
"""

import importlib
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, MANIFEST, _py  # noqa: E402  (puts the
# checkout on sys.path, reads the manifest, runs a benchmark script)

SPAN_METRICS = ("stage_ms", "staged_arrays_per_step", "launch_ms",
                "wait_ms", "set_models_ms", "opt_control_ms",
                "trav_evals_per_step")
# every cell of the manifest, and any that a later PR adds: since ISSUE
# 38 the seven carry no `workloads` list (every step of every kind is
# made of the phases they read), so no name is typed here
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _spec(metric):
    with open(os.path.join(BENCH, "layers", metric + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_modopt():
    proc, lines = _py("run.py", ["--workload", "dna140x16k.modopt",
                                 "--seed", str(2**31 + 28), "--seconds",
                                 "2", "--trace", "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is True and rec["rehearse"] is True
    return rec


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_rehearsed_modopt_traced_run_reports_the_span_metric(traced_modopt,
                                                             metric):
    m = traced_modopt["metrics"][metric]
    assert math.isfinite(m["value"]) and m["value"] >= 0
    assert m["unit"] == _spec(metric)["unit"]
    entry = next(e for e in MANIFEST["per_layer"] if e["name"] == metric)
    assert "workloads" not in entry and entry["moves"] == "step_s"


def test_span_metrics_tile_the_step_and_count_no_second_twice(
        traced_modopt):
    v = {k: m["value"] for k, m in traced_modopt["metrics"].items()}
    step_ms = 1000.0 * traced_modopt["step_s_traced"]
    host_ms = (v["stage_ms"] + v["launch_ms"] + v["wait_ms"]
               + v["host_schedule_ms"] + v["set_models_ms"]
               + v["opt_control_ms"])
    assert 0 < host_ms <= step_ms
    assert v["trav_evals_per_step"] + v["grad_passes_per_step"] \
        <= v["dispatches_per_step"]
    assert v["trav_evals_per_step"] >= 1
    # each trav_eval stages three values, each gradient pass fifteen, a
    # model push seven: `engine.staged_arrays`, per step
    assert v["staged_arrays_per_step"] >= (
        3 * v["trav_evals_per_step"] + 15 * v["grad_passes_per_step"])
    with open(os.path.join(BENCH, "layers",
                           "staged_arrays_per_step.json")) as f:
        assert json.load(f)["counter"] == "engine.staged_arrays"


def test_reader_sums_fields_over_matching_timers_and_reads_nothing_absent():
    reader = importlib.import_module("benchmarks.readers.span_self_per_step")

    def t(count, total, self_s=None):
        d = {"count": count, "total_s": total}
        if self_s is not None:
            d["self_s"] = self_s
        return d

    run = {"spans": [(0.0, 1.0), (1.0, 2.0)],
           "timers0": {"engine:a/stage": t(1, 0.5, 0.5)},
           "timers1": {"engine:a/stage": t(3, 2.5, 1.5),
                       "engine:b/stage": t(2, 1.0, 1.0),
                       "engine:a/launch": t(9, 9.0, 9.0),
                       "engine:a": t(3, 8.0, 0.25),
                       "host_schedule": t(4, 0.1)}}
    spec = {"timers": ["^engine:[^/]+/stage$"], "field": "self_s",
            "scale": 1000.0}
    assert reader.read(run, spec) == pytest.approx(1000.0 * 2.0 / 2)
    assert reader.read(run, {**spec, "field": "total_s"}) == \
        pytest.approx(1000.0 * 3.0 / 2)
    assert reader.read(run, {"timers": ["^engine:a$"], "field": "count"}) \
        == pytest.approx(1.5)
    # no such span (the parent commit), or a timer without self seconds
    assert reader.read(run, {"timers": ["^opt:"]}) is None
    assert reader.read(run, {"timers": ["^host_schedule$"]}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_span_metrics_are_read_in_every_cell(cell):
    """Until ISSUE 38 they listed cells 1 and 3; the phases are the
    engine's own in every step kind, so the device-bound, the four-chip
    and the search cells read them too (PERF.md section 3)."""
    from benchmarks import run
    assert set(SPAN_METRICS) <= {m["name"] for m in run.metrics_of(
        MANIFEST, "per_layer", cell)}
