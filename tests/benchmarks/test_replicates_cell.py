"""The replicate cell `dna140x16k.bootstrap`, CPU.

`run.py --rehearse` of the cell (12 taxa x 256) ends in the contract's
line and reports the fleet layers' counter metrics; the step kind
`replicates` ends a run non-zero in set-up, with one line and no result
line, where the program does not count its batched dispatches (the
parent of the cell); the warm-up analysis is held to the plain
reference and the planted faults fail that hold; the benchmark's own
bootstrap draw is the program's on an alignment with repeated columns,
and a replicate's lnL from the per-pattern reference is the reference's
weighted lnL; the readers on synthetic runs.  No number of this file is
a device number.
"""

import ast
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, MANIFEST, _py  # noqa: E402  (puts the
# checkout on sys.path, reads the manifest, runs a benchmark script)

from benchmarks import bytemodel, bytemodel_fleet  # noqa: E402
from benchmarks import reference, reference_replicates  # noqa: E402

CELL = "dna140x16k.bootstrap"
CONFIG = "dna140x16k_replicates"
COUNTER_METRICS = ("fleet_occupancy_pct", "fleet_jobs_per_dispatch",
                   "smooth_sweeps_per_step")
NEW_METRICS = COUNTER_METRICS + ("fleet_grad_roofline",
                                 "fleet_eval_roofline",
                                 "fleet_weights_roofline")
GUARD = "benchmarks/steps/replicates.py: refused: "


# -- the manifest --------------------------------------------------------------


def test_manifest_entries_of_the_cell():
    (w,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "replicates_n10b100", 1)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    want = {"kind": "replicates", "starts": 10, "fleet_batch": 16,
            "cycles": 2, "tree_epsilon": 2.0, "replicates": 100}
    assert {k: traffic[k] for k in want} == want
    assert 0 < traffic["lnl_rel_tol"] < 1e-4
    assert 0 < traffic["newton_dz_tol"] < 0.1
    for name in NEW_METRICS:
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "step_s"
    # the one-chip rooflines, by their rule and written out
    for name in ("traverse_roofline", "gradient_roofline"):
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert CELL in m["workloads"]


def test_configuration_is_cell_ones_alignment_running_the_traffics_analysis():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "dna140x16k.json")) as f:
        cell_one = json.load(f)
    # the same alignment, drawn from the same seed
    analysis = {"multi_start", "bootstrap_replicates", "fleet_cycles",
                "fleet_batch", "tree_epsilon"}
    own = {"name", "source", "deployment", "reduced", "reduced_from",
           "assumed"}
    assert set(config) == set(cell_one) | analysis
    for key in set(cell_one) - own:
        assert config[key] == cell_one[key], key
    assert config["reduced"] == ["patterns", "multi_start"]
    assert set(config["reduced_from"]) == set(config["reduced"])
    # the analysis the configuration states is the one the step runs
    with open(os.path.join(BENCH, "traffic", "replicates_n10b100.json")) as f:
        traffic = json.load(f)
    assert (config["multi_start"], config["bootstrap_replicates"],
            config["fleet_cycles"], config["fleet_batch"],
            config["tree_epsilon"]) == (
        traffic["starts"], traffic["replicates"], traffic["cycles"],
        traffic["fleet_batch"], traffic["tree_epsilon"])


@pytest.mark.parametrize("module", ["reference_replicates.py",
                                    "bytemodel_fleet.py",
                                    "readers/fleet_roofline.py",
                                    "readers/counter_ratio.py"])
def test_yardstick_imports_nothing_of_the_program_or_the_tests(module):
    with open(os.path.join(BENCH, module)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        mods = ([a.name for a in node.names]
                if isinstance(node, ast.Import)
                else [node.module or ""]
                if isinstance(node, ast.ImportFrom) else [])
        for mod in mods:
            assert not mod.startswith(("examl_tpu", "tests")), (module, mod)


# -- run.py end to end, rehearsed ------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsed_replicate_cell_ends_in_the_contracts_line(trace):
    proc, lines = _py("run.py", ["--workload", CELL, "--seed",
                                 str(2**31 + 44 + trace), "--seconds", "1",
                                 "--trace", str(trace), "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert list(rec)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert rec["correct"] is True and rec["failed"] == 0
    assert "check starts_lnl_rel_err" in proc.stderr
    v = {k: m["value"] for k, m in rec["metrics"].items()}
    if not trace:
        assert set(v) == {"step_s", "setup_s"}
        return
    # the CPU has no device plane: the roofline shares are left out
    assert not {m for m in v if m.endswith("_roofline")}
    assert set(COUNTER_METRICS) <= set(v)
    assert 0 < v["fleet_occupancy_pct"] <= 100
    assert v["fleet_jobs_per_dispatch"] >= 1
    assert v["smooth_sweeps_per_step"] >= 1
    assert v["compiles_in_window"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_guard_refuses_a_program_that_does_not_count_its_dispatches(trace):
    """The parent of the cell under its benchmark files: `prepare` finds
    no `count_dispatch` and ends the run in set-up, traced and untraced
    alike.  The parent is stood in for by a run started with a `-c` that
    deletes it from `fleet.batch` before `run.main`."""
    code = ("import sys; sys.argv = ['run.py'] + sys.argv[1:]; "
            "sys.path.insert(0, %r); "
            "from examl_tpu.fleet import batch; "
            "delattr(batch, 'count_dispatch'); "
            "from benchmarks import run; sys.exit(run.main())"
            % os.path.dirname(BENCH))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE",
                        "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--seed", "44",
         "--seconds", "1", "--trace", str(trace), "--rehearse"], env=env,
        cwd=os.path.dirname(BENCH), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode not in (0, None), proc.stdout[-2000:]
    assert not [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith(GUARD) and "count_dispatch" in last, \
        proc.stderr[-3000:]


# -- the step kind, in this process ----------------------------------------------


@pytest.fixture(scope="module")
def cell():
    """The cell rehearsed up to the step kind's `prepare` (12 x 256),
    warmed: the warm-up analysis and its reference values."""
    from benchmarks import calibrate_search
    flags = os.environ.get("XLA_FLAGS")
    try:
        built, _traffic, _dev = calibrate_search.build_cell(
            CELL, 44, True, None)
        kind = importlib.import_module("benchmarks.steps.replicates")
        kind.warm(built, 0)
        yield built
    finally:
        if flags is not None:
            os.environ["XLA_FLAGS"] = flags


def test_the_warm_up_analysis_holds_and_every_step_repeats_it(cell):
    kind = importlib.import_module("benchmarks.steps.replicates")
    tol = cell.params["lnl_rel_tol"]
    r = cell.check_readings
    assert set(r) == {"starts_lnl_rel_err", "replicates_lnl_rel_err",
                      "start_newton_dz_max"}
    assert r["starts_lnl_rel_err"] <= tol
    assert r["replicates_lnl_rel_err"] <= tol
    assert r["start_newton_dz_max"] <= cell.params["newton_dz_tol"]
    warm = dict(cell.analysis)
    tree, lnl, before = kind.step(cell, 0)
    assert lnl >= before and cell.analysis["best"] == warm["best"]
    assert len(cell.analysis["starts"]) == 10
    assert cell.analysis["replicates"].shape == (100,)
    np.testing.assert_allclose(cell.analysis["replicates"],
                               warm["replicates"], rtol=1e-12)
    # 100 replicates, batched 16 a dispatch: not every lnL alike
    assert np.unique(cell.analysis["replicates"]).size > 50


@pytest.mark.parametrize("fault,number", [
    ("weights_shift", "replicates_lnl_rel_err"),
    ("unchanged", "start_newton_dz_max"),
    ("altered", "replicates_lnl_rel_err"),
    ("half", "starts_lnl_rel_err")])
def test_a_planted_fault_fails_the_warm_up_analysis(cell, fault, number):
    """`calibrate_replicates.py`'s faults under the step kind's hold:
    replicate j scored with replicate j+1's weights, the starts left
    unsmoothed, every replicate's lnL altered by 1e-4, half of the sites
    counted double (planted in the engine's weights here, as the engine
    holds them)."""
    from benchmarks import calibrate_replicates
    kind = importlib.import_module("benchmarks.steps.replicates")
    readings = dict(cell.check_readings)
    if fault == "half":
        (eng,) = cell.inst.engines.values()
        saved = eng.weights
        w = saved.reshape(-1)
        eng.weights = (w.at[::2].set(0) * 2).reshape(saved.shape)
        undo = lambda: setattr(eng, "weights", saved)   # noqa: E731
    else:
        undo = calibrate_replicates.plant(fault)
    try:
        with pytest.raises(SystemExit, match=f"failed: .*{number}"):
            kind.warm(cell, 0)
    finally:
        undo()
        cell.check_readings.clear()
        cell.check_readings.update(readings)
    kind.warm(cell, 0)                     # sound again once taken back


def test_a_fleet_that_smooths_per_job_fails_the_step(cell, monkeypatch):
    from examl_tpu.fleet.batch import BatchEvaluator
    kind = importlib.import_module("benchmarks.steps.replicates")

    def broken(self, jobs, maxtimes):
        raise RuntimeError("planted")
    monkeypatch.setattr(BatchEvaluator, "smooth_batch", broken)
    with pytest.raises(RuntimeError, match="left the batched path"):
        kind.step(cell, 0)


# -- the benchmark's own draw and reference ---------------------------------------


def _repeated_columns(seed=3, ntaxa=6, distinct=15, width=60):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, 4, (ntaxa, distinct))
    pick = np.concatenate([np.arange(distinct),
                           rng.integers(0, distinct, width - distinct)])
    return cols[:, rng.permutation(pick)]


def test_the_benchmarks_draw_is_the_programs_on_repeated_columns():
    """Multinomial over the site multiplicity, in the parser's pattern
    order, under the seeds fleet/seeds.py derives; a uniform draw over
    the patterns differs there."""
    from examl_tpu.fleet import bootstrap, seeds
    from examl_tpu.io.alignment import build_alignment_data
    codes = _repeated_columns()
    data = build_alignment_data([f"t{i}" for i in range(codes.shape[0])],
                                ["".join("ACGT"[c] for c in row)
                                 for row in codes])
    (part,) = data.partitions
    uniq, counts, inverse = reference_replicates.distinct(codes)
    assert (uniq[:, inverse] == codes).all()
    # the program's patterns, decoded, are the benchmark's in its order
    enc = {int(data.partitions[0].datatype.encode(b)[0]): k
           for k, b in enumerate("ACGT")}
    decoded = np.vectorize(enc.get)(np.asarray(part.patterns))
    assert (decoded == uniq).all()
    assert (np.asarray(part.weights) == counts).all() and counts.max() > 1
    parent = 2**31 + 44
    for j in range(5):
        assert reference_replicates.derive(parent, "bootstrap", j) == \
            seeds.derive(parent, "bootstrap", j)
        rep = seeds.derive(parent, "bootstrap", j)
        (program,) = bootstrap.bootstrap_weights(data, rep)
        ours = reference_replicates.draw(
            counts, reference_replicates.derive(rep, "partition", 0))
        assert np.array_equal(ours, program)
        uniform = np.random.default_rng(
            reference_replicates.derive(rep, "partition", 0)).multinomial(
            int(counts.sum()), np.full(counts.size, 1.0 / counts.size))
        assert not np.array_equal(uniform, program)
    # per column: a pattern's weight over its column count
    per_col = reference_replicates.replicate_weights(
        codes, [(0, codes.shape[1])], parent, 3)
    for j in range(3):
        rep = seeds.derive(parent, "bootstrap", j)
        (program,) = bootstrap.bootstrap_weights(data, rep)
        np.testing.assert_allclose(np.bincount(inverse, per_col[j]), program)


def test_a_replicates_lnl_is_the_references_weighted_lnl():
    """The per-pattern pass dotted with a replicate's column weights is
    `reference.evaluate` given those weights."""
    codes = _repeated_columns(seed=5, ntaxa=5, distinct=20, width=90)
    edges = [(6, 1, 0.9), (6, 2, 0.8), (6, 7, 0.7), (7, 3, 0.95),
             (7, 8, 0.6), (8, 4, 0.85), (8, 5, 0.75)]
    model = (np.array([1.2, 3.1, 0.9, 1.1, 3.4, 1.0]),
             reference.empirical_freqs(codes, None, 4), 0.7)
    sites = reference_replicates.site_lnls(codes, edges, 5, *model, 4,
                                           block=32)
    weights = reference_replicates.replicate_weights(
        codes, [(0, 40), (40, 90)], 7, 4)
    for w in weights:
        want, _, _ = reference.evaluate(codes, w, edges, 5, *model, 4,
                                        want_derivs=False)
        assert w @ sites == pytest.approx(want, rel=1e-12)
        # each part keeps its own site count
        assert w[:40].sum() == pytest.approx(40) and \
            w[40:].sum() == pytest.approx(50)


# -- readers and bytes ---------------------------------------------------------------


def _synthetic_run(calls, seconds):
    c0 = {"fleet.batch_jobs": 5, "fleet.batch_slots": 8,
          "fleet.batched_dispatches": 4,
          "fleet.batched_dispatches.grad": 2, "fleet.batch_jobs.grad": 3}
    c1 = {"fleet.batch_jobs": 5 + 30, "fleet.batch_slots": 8 + 40,
          "fleet.batched_dispatches": 4 + 20,
          "fleet.batched_dispatches.grad": 2 + 10,
          "fleet.batch_jobs.grad": 3 + 15,
          "fleet.batched_dispatches.weights": 2,
          "fleet.batch_jobs.weights": 32}
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    return {"counters0": c0, "counters1": c1, "config": config,
            "peak": {"hbm_bytes_per_s": 819e9},
            "trace": {"families": {
                "fleet_grad": {"calls": calls, "seconds": seconds},
                "fleet_weights": {"calls": calls, "seconds": seconds}}}}


def _layer(name):
    with open(os.path.join(BENCH, "layers", name + ".json")) as f:
        return json.load(f)


def test_counter_ratio_reader_on_a_synthetic_run():
    reader = importlib.import_module("benchmarks.readers.counter_ratio")
    run = _synthetic_run(1, 1.0)
    assert reader.read(run, _layer("fleet_occupancy_pct")) == \
        pytest.approx(100.0 * 30 / 40)
    assert reader.read(run, _layer("fleet_jobs_per_dispatch")) == \
        pytest.approx(30 / 20)
    # a program without the counters (the parent), no dispatch
    assert reader.read({**run, "counters1": {}},
                       _layer("fleet_occupancy_pct")) is None
    same = {**run, "counters0": dict(run["counters1"])}
    assert reader.read(same, _layer("fleet_jobs_per_dispatch")) is None


def test_fleet_roofline_reader_on_a_synthetic_run():
    reader = importlib.import_module("benchmarks.readers.fleet_roofline")
    grad, weights = _layer("fleet_grad_roofline"), _layer(
        "fleet_weights_roofline")
    assert grad["modules"] == ["^jit_fleet_grad_impl\\("]
    run = _synthetic_run(calls=4, seconds=0.5)
    config = run["config"]
    # 15 live jobs in 10 grad dispatches: 1.5 a dispatch, each a full
    # traversal and a gradient pass
    job = bytemodel.traversal_bytes(config) + bytemodel.gradient_bytes(
        config)
    assert bytemodel_fleet.grad_job_bytes(config) == job
    assert reader.read(run, grad) == pytest.approx(
        100.0 * 4 * 1.5 * job / 819e9 / 0.5)
    # weights: two root rows a dispatch, a weight row a replicate
    row = 16384 * (4 * 4 * 4 + 4)
    assert bytemodel_fleet.weights_call_bytes(config) == 2 * row
    per_call = 2 * row + 16 * 16384 * 4
    assert reader.read(run, weights) == pytest.approx(
        100.0 * 4 * per_call / 819e9 / 0.5)
    # nothing to read: no trace, a family with no call, a program
    # without the per-family counters (the parent)
    assert reader.read({**run, "trace": None}, grad) is None
    assert reader.read(_synthetic_run(0, 0.0), grad) is None
    assert reader.read({**run, "counters1": {}}, grad) is None
