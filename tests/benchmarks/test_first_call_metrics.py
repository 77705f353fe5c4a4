"""The per-layer metrics of the first-call path (ISSUE 40), CPU.

A rehearsed traced run of the host-bound cell prints all six, they hold
no second twice, no program reaches the compiler in the window, and the
reader of the two span-fed ones leaves a metric out where the program
has no such timer (the parent of the PR that brought them).  A file of
its own: `test_span_metrics.py` is an accepted file of the benchmark,
which a PR adds to and does not edit.  No number of this file is a
device number.
"""

import importlib
import json
import math
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, CELLS, MANIFEST, _py  # noqa: E402  (puts
# the checkout on sys.path, reads the manifest, runs a benchmark script)

FIRST_CALL_METRICS = {          # metric: (unit, the end-to-end it moves)
    "first_call_s": ("s", "setup_s"),
    "jit_trace_lower_s": ("s", "setup_s"),
    "backend_compile_s": ("s", "setup_s"),
    "program_obs_s": ("s", "setup_s"),
    "jit_programs_at_setup": ("count", "setup_s"),
    "jit_programs_in_window": ("count", "step_s"),
}


@pytest.fixture(scope="module")
def traced_modopt():
    proc, lines = _py("run.py", ["--workload", "dna140x16k.modopt",
                                 "--seed", str(2**31 + 40), "--seconds",
                                 "2", "--trace", "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is True and rec["rehearse"] is True
    marks = re.search(r"load\+engine ([\d.]+), warm-up ([\d.]+)",
                      proc.stderr)
    rec["marks_s"] = float(marks.group(1)) + float(marks.group(2))
    return rec


@pytest.mark.parametrize("metric", sorted(FIRST_CALL_METRICS))
def test_rehearsed_traced_run_prints_the_first_call_metric(traced_modopt,
                                                           metric):
    unit, moves = FIRST_CALL_METRICS[metric]
    m = traced_modopt["metrics"][metric]
    assert math.isfinite(m["value"]) and m["value"] >= 0
    assert m["unit"] == unit
    (entry,) = [e for e in MANIFEST["per_layer"] if e["name"] == metric]
    assert "workloads" not in entry          # every cell, later ones too
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "compile", moves, "lower")


def test_first_call_metrics_hold_no_second_twice(traced_modopt):
    v = {k: m["value"] for k, m in traced_modopt["metrics"].items()}
    # a sound cell compiles nothing in its window, by either count
    assert v["jit_programs_in_window"] == 0 == v["compiles_in_window"]
    # every guarded program reached the compiler; the rest are eager
    assert v["jit_programs_at_setup"] >= v["compiled_programs"] >= 1
    # the parent span holds its children: the jitted call (`compile_s`,
    # what it read before) and the observatory's analysis
    assert v["first_call_s"] >= v["compile_s"] + v["program_obs_s"]
    assert v["program_obs_s"] > 0 and v["jit_trace_lower_s"] > 0
    # outermost events only, and the observatory's own compile left out:
    # the three are disjoint wall seconds of the marks they fall in
    assert (v["jit_trace_lower_s"] + v["backend_compile_s"]
            + v["program_obs_s"]) <= traced_modopt["marks_s"]


def test_timer_at_setup_reads_a_field_and_nothing_where_the_timer_is_not():
    reader = importlib.import_module("benchmarks.readers.timer_at_setup")
    run = {"timers0": {"engine.first_call": {"count": 4, "total_s": 2.5}},
           "timers1": {"engine.first_call": {"count": 9, "total_s": 7.0}}}
    assert reader.read(run, {"timer": "engine.first_call"}) == 2.5
    assert reader.read(run, {"timer": "engine.first_call",
                             "field": "count"}) == 4
    assert reader.read(run, {"timer": "program.obs"}) is None
    for metric in ("first_call_s", "program_obs_s"):
        with open(os.path.join(BENCH, "layers", metric + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "timer_at_setup"
        assert spec["source"] == "program_span"


@pytest.mark.parametrize("cell", CELLS)
def test_first_call_metrics_are_read_in_every_cell(cell):
    from benchmarks import run
    assert set(FIRST_CALL_METRICS) <= {m["name"] for m in run.metrics_of(
        MANIFEST, "per_layer", cell)}
