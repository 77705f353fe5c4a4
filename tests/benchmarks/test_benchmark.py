"""The benchmark's own tests (CPU, tier-1).

What is held here: the manifest obeys the contract's character and
cross-reference rules; `run.py --rehearse` (12 taxa x 256, the sizes of
tests/test_chip_smoke.py) ends in the contract's last line for one
`modopt` and one `treeset` cell; without `--rehearse` a CPU is refused;
the window rule (whole cycles) on a fake clock; the byte model's closed forms and its
agreement with the program's `obs/traffic.py`; the trace reduction on a
trace recorded on a v5e; the reference against finite differences; the
lower-precision control and each planted fault come out not correct.
No number of this file is a device number.
"""

import ast
import glob
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import (bytemodel, datagen, reference,  # noqa: E402
                        tracereduce, window)

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a deployment that waits for a public source: no cell of BENCHMARK.json,
# run through a manifest of its own (PERF.md section 7)
DRAFT = ["--manifest", "benchmarks/drafts/manifest.json"]


def _py(script, args, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE",
                        "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), *args], env=env,
        cwd=REPO, capture_output=True, text=True, timeout=timeout)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


# -- the manifest -------------------------------------------------------------


def test_manifest_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    runs = 2 + 14 * 24                    # a full check with 24 cells
    assert (runs * (MANIFEST["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    assert MANIFEST["command"][-1].startswith(tuple(MANIFEST["paths"]))
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves"}}
    for group, keys in allowed.items():
        for e in MANIFEST[group]:
            assert keys <= set(e) <= keys | ({"workloads"} if "unit" in e
                                             else set()), e


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_character_rules(group):
    names = [e["name"] for e in MANIFEST[group]]
    assert len(set(names)) == len(names)
    for e in MANIFEST[group]:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e and group != "end_to_end":
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    if group == "workloads":
        for w in MANIFEST["workloads"]:
            assert NAME.match(w["config"]) and NAME.match(w["traffic"])
            assert w["chips"] in (1, 4)
        pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
        assert len(set(pairs)) == len(pairs)
    if group == "end_to_end":
        by = {m["name"]: m for m in MANIFEST["end_to_end"]}
        assert by["setup_s"]["bound"] <= 0.25
        assert all(0.01 <= m["bound"] <= 0.25 for m in by.values())
        assert all(m["source"] in ("host_clock", "device_trace")
                   for m in by.values())


def test_every_moves_names_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m.get("workloads", CELLS)
           for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", CELLS):
            assert cell in e2e[m["moves"]], (m["name"], cell)
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert all(len(x) <= 200 for x in layers)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith("benchmarks/configs/")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    for key in ("taxa", "patterns", "precision", "domain", "guarantees"):
        assert key in config
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert os.path.isfile(os.path.join(BENCH, "steps",
                                       traffic["kind"] + ".py"))
    with open(os.path.join(BENCH, "correct", cell + ".json")) as f:
        limits = json.load(f)
    for number in ("lnl_rel_err", "newton_dz_max", "model_table_err"):
        lim = limits[number]
        assert lim["lower"] < lim["limit"] < lim["upper"], (cell, lim)
        assert lim["upper"] >= 3 * max(lim["lower"], 1e-300)
    if config["datatype"] == "AA":
        for part in datagen.parts_of(config):
            assert os.path.isfile(os.path.join(
                BENCH, "models", part["exchangeabilities"] + ".json"))
    if "parts" in config:            # what the file states beside them
        assert config["patterns"] == sum(p["patterns"]
                                         for p in config["parts"])
        assert config["partitions"] == len(config["parts"])


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in MANIFEST["per_layer"]])
def test_layer_metric_has_its_own_file_and_reader(metric):
    m = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    with open(os.path.join(BENCH, "layers", metric + ".json")) as f:
        spec = json.load(f)
    for key in ("layer", "unit", "better", "moves", "source"):
        assert spec[key] == m[key], (metric, key)
    assert os.path.isfile(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    if metric.endswith("_roofline"):
        assert spec["unit"] == "%" and spec["source"] == "device_trace"


def test_files_under_paths_are_named_from_a_names_characters():
    files = [os.path.relpath(os.path.join(d, f), REPO)
             for p in MANIFEST["paths"]
             for d, _, fs in os.walk(os.path.join(REPO, p)) for f in fs]
    assert files
    assert all(re.fullmatch(r"[A-Za-z0-9_.\-/]+", f) for f in files)


@pytest.mark.parametrize("module", ["datagen.py", "reference.py",
                                    "bytemodel.py", "tracereduce.py",
                                    "window.py"])
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


# -- window, bytes, peaks ------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("durations,seconds,cycle,expect", [
    ([3.0] * 20, 10.0, 1, 3),      # 3 steps end at 9; a 4th would pass 10
    ([30.0] * 5, 10.0, 1, 1),      # the first step always runs
    ([1.0, 1.0, 5.0, 1.0, 1.0, 1.0], 10.0, 1, 3),   # 7 + longest 5 > 10
    ([2.0] * 20, 10.0, 1, 5),      # ends exactly on the budget
    # a cycle of 4 unequal trees: whole cycles only, the first always
    ([6.6, 5.3, 10.0, 7.4] * 3, 40.0, 4, 4),
    ([6.6, 5.3, 10.0, 7.4] * 3, 60.0, 4, 8),
    ([6.6, 5.3, 10.0, 7.4] * 3, 5.0, 4, 4),
    ([1.0, 2.0] * 20, 10.0, 2, 6),
])
def test_window_rule_whole_cycles_never_past_the_budget(durations, seconds,
                                                        cycle, expect):
    clock = FakeClock()

    def step(i):
        clock.t += durations[i]

    seen = []
    spans = window.run_window(step, seconds, cycle=cycle, clock=clock,
                              on_cycle=lambda n, t, longest: seen.append(
                                  (n, t, longest)))
    assert len(spans) == expect and expect % cycle == 0
    assert [n for n, _, _ in seen] == list(range(cycle, expect + 1, cycle))
    if expect > cycle:
        assert spans[-1][1] <= seconds
    # every kind of step is timed as often as every other: the mean is
    # the whole cycle's, whatever the budget
    assert window.step_seconds(spans) == pytest.approx(
        sum(durations[:expect]) / expect)
    if durations[:cycle] * (expect // cycle) == durations[:expect]:
        assert window.step_seconds(spans) == pytest.approx(
            sum(durations[:cycle]) / cycle)


def test_byte_model_closed_forms_140x131072_dna_f32():
    config = {"taxa": 140, "patterns": 131072, "rate_categories": 4,
              "states": 4, "precision": {"clv_dtype": "f32",
                                         "dot_precision": "high"}}
    assert bytemodel.traversal_bytes(config) == 2_460_483_584
    assert bytemodel.gradient_bytes(config) == 9_495_904_256
    t = bytemodel.floor_seconds(bytemodel.traversal_bytes(config),
                                {"hbm_bytes_per_s": 819e9})
    assert t == pytest.approx(2_460_483_584 / 819e9)


@pytest.mark.parametrize("shape", [(138, 140, 131072, 4, 4, 4),
                                   (138, 139, 16384, 4, 20, 4),
                                   (10, 12, 256, 4, 4, 2)])
def test_byte_model_agrees_with_the_programs_while_that_exists(shape):
    traffic = pytest.importorskip("examl_tpu.obs.traffic")
    n, tips, w, r, k, item = shape
    assert bytemodel.bytes_per_traversal_counts(n, tips, w, r, k, item) \
        == traffic.bytes_per_traversal_counts(n, tips, w, r, k, item)
    assert bytemodel.bytes_per_grad_pass(n, tips, 2 * n + 1, w, r, k, item) \
        == traffic.bytes_per_grad_pass(n, tips, 2 * n + 1, w, r, k, item)


def test_peaks_refuse_an_unknown_device_kind():
    from benchmarks import run
    assert run.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="not in benchmarks/peaks.json"):
        run.peak_for("TPU v9 imaginary")


# -- generator and reference ---------------------------------------------------

TOY = {"taxa": 9, "patterns": 96, "datatype": "DNA", "data_seed": 3,
       "generating": {"rates": [1.2, 3.1, 0.9, 1.1, 3.4, 1.0],
                      "freqs": [0.30, 0.21, 0.24, 0.25], "alpha": 0.7}}


def test_problem_comes_from_the_data_seed_and_is_well_formed():
    a = datagen.problem(TOY, trees=3, spr_moves=4, branch_lengths=True)
    b = datagen.problem(TOY, trees=3, spr_moves=4, branch_lengths=True)
    assert np.array_equal(a["patterns"], b["patterns"])
    assert a["moved_trees"] == b["moved_trees"] and a["tree"] == b["tree"]
    assert a["patterns"].shape == (9, 96)
    assert len({col.tobytes() for col in a["patterns"].T}) == 96
    assert len(set(a["moved_trees"])) == 3
    for t in a["moved_trees"]:
        assert sorted(re.findall(r"t\d+", t)) == sorted(
            f"t{i + 1}" for i in range(9))
        assert len(re.findall(r":([0-9.]+)", t)) == 2 * 9 - 3
    bare = datagen.problem(TOY, trees=3, spr_moves=4)
    assert ":" not in bare["moved_trees"][0]
    assert re.sub(r":[0-9.]+", "", a["moved_trees"][0]) == \
        bare["moved_trees"][0]
    other = datagen.problem({**TOY, "data_seed": 4}, trees=1, spr_moves=1)
    assert not np.array_equal(other["patterns"], a["patterns"])


def test_the_seed_orders_the_columns_and_changes_nothing_else():
    """Every seed (large ones too) gives the same problem, so the same
    work, with its site columns in another order."""
    prob = datagen.problem(TOY, trees=2, spr_moves=3)
    a = datagen.present(prob, 2**31 + 11)
    again = datagen.present(prob, 2**31 + 11)
    c = datagen.present(prob, 2**31 + 12)
    assert np.array_equal(a["patterns"], again["patterns"])
    assert not np.array_equal(a["patterns"], c["patterns"])
    cols = lambda m: sorted(col.tobytes() for col in m.T)   # noqa: E731
    assert cols(a["patterns"]) == cols(c["patterns"]) == \
        cols(prob["patterns"])
    assert a["moved_trees"] == c["moved_trees"] and a["tree"] == c["tree"]


def test_reference_derivatives_against_finite_differences():
    rng = np.random.default_rng(5)
    adj, lengths = datagen.random_tree(rng, 9)
    gen = TOY["generating"]
    rates, freqs = np.array(gen["rates"]), np.array(gen["freqs"])
    mat = datagen.evolve(rng, adj, lengths, 9, 200, rates, freqs, 0.7)
    edges = [(a + 1, b + 1, float(np.exp(-t)))
             for (a, b), t in lengths.items()]
    lnl, d1, d2 = reference.evaluate(mat, None, edges, 9, rates, freqs, 0.7)
    assert np.isfinite(lnl) and lnl < 0

    def at(ei, lz):
        e = list(edges)
        e[ei] = (e[ei][0], e[ei][1], float(np.exp(lz)))
        return reference.evaluate(mat, None, e, 9, rates, freqs, 0.7,
                                  want_derivs=False)[0]

    h = 1e-4
    for ei in (0, 7, len(edges) - 1):
        lz = np.log(edges[ei][2])
        fp, f0, fm = at(ei, lz + h), at(ei, lz), at(ei, lz - h)
        assert d1[ei] == pytest.approx((fp - fm) / (2 * h), rel=1e-5)
        assert d2[ei] == pytest.approx((fp - 2 * f0 + fm) / h ** 2,
                                       rel=1e-3)
    # the generating lengths are near the optimum, default ones are not
    near = reference.newton_dz(edges, d1, d2, 1e-15, 0.999999).max()
    flat = [(a, b, 0.9) for a, b, _ in edges]
    _, f1, f2 = reference.evaluate(mat, None, flat, 9, rates, freqs, 0.7)
    far = reference.newton_dz(flat, f1, f2, 1e-15, 0.999999).max()
    assert near < 0.08 <= far


def test_own_frequencies_and_the_published_table():
    """What is no free parameter the benchmark makes itself, and holds
    the program's against it."""
    from benchmarks import run
    rng = np.random.default_rng(7)
    mat = rng.integers(0, 20, (12, 300)).astype(np.uint8)
    own = run.own_model({"states": 20, "exchangeabilities": "LG"}, mat)
    counts = np.bincount(mat.reshape(-1), minlength=20)
    assert own["freqs"] == pytest.approx(counts / counts.sum(), abs=1e-15)
    assert own["rates"].shape == (190,) and (own["rates"] > 0).all()
    # Le and Gascuel's first and largest entries, as published
    assert own["rates"][0] == 0.425093 and own["rates"].max() == 10.649107
    st = {"freqs": own["freqs"].copy(), "rates": own["rates"] * 0.939}
    assert run.table_err(own, st) < 1e-12          # a common scale is free
    st["rates"][17] *= 1.0 + 1e-5
    assert 5e-6 < run.table_err(own, st) < 2e-5
    st = {"freqs": np.roll(own["freqs"], 1), "rates": own["rates"]}
    assert run.table_err(own, st) > 1e-3
    dna = run.own_model({"states": 4}, mat % 4)
    assert set(dna) == {"freqs"} and dna["freqs"].sum() == pytest.approx(1)
    programs = pytest.importorskip("examl_tpu.models.protein")
    theirs, _ = programs.get_matrix("LG")
    assert run.table_err(own, {"freqs": own["freqs"], "rates": theirs}) \
        < 1e-9


def test_a_metric_is_read_only_in_the_cells_it_lists():
    """A later PR adds a metric with a `workloads` key and edits no file:
    the harness must then leave it out elsewhere (a traced cell whose
    trace lacks a listed family is an error of the run)."""
    from benchmarks import run
    manifest = {"per_layer": [{"name": "a"},
                              {"name": "b", "workloads": ["x.y"]}]}
    assert [m["name"] for m in run.metrics_of(manifest, "per_layer",
                                              "x.y")] == ["a", "b"]
    assert [m["name"] for m in run.metrics_of(manifest, "per_layer",
                                              "x.z")] == ["a"]


def test_every_seed_loads_the_same_patterns_and_frequencies(tmp_path):
    """`--seed` orders the site columns of the PHYLIP file and the
    parser's compression sorts them again: the engine sees one problem
    whatever the seed (with the parser's `-c` the order reached it and
    moved a `modopt` step by 8% on the v5e, PR 27), and the frequencies
    it counts are the benchmark's own."""
    pytest.importorskip("examl_tpu.cli.parse")
    from examl_tpu.cli import main as cli
    from examl_tpu.cli import parse as cli_parse
    prob = datagen.problem(TOY, trees=0, spr_moves=0)
    loaded = []
    for seed in (5, 6):
        gen = datagen.present(prob, seed)
        aln = str(tmp_path / f"aln{seed}")
        datagen.write_phylip(aln + ".phy", gen["patterns"], "DNA")
        assert cli_parse.main(["-s", aln + ".phy", "-n", aln,
                               "-m", "DNA"]) == 0
        (part,) = cli._load_alignment(aln + ".binary").partitions
        assert (part.weights == 1).all()
        back = part.datatype.tip_indicator_table()[part.patterns].argmax(-1)
        assert sorted(map(bytes, back.T.astype(np.uint8))) == sorted(
            map(bytes, gen["patterns"].T))
        assert part.empirical_freqs == pytest.approx(
            reference.empirical_freqs(gen["patterns"], None, 4), abs=1e-12)
        loaded.append(part.patterns)
    assert np.array_equal(loaded[0], loaded[1])


def test_reference_gamma_rates_have_mean_one_and_match_yang():
    r = reference.discrete_gamma(0.7, 4)
    assert r.mean() == pytest.approx(1.0, abs=1e-12)
    assert r == pytest.approx([0.07418828, 0.3635302, 0.92486841,
                               2.63741312], rel=1e-6)


# -- the trace reduction, on a trace recorded on a v5e --------------------------

FIXTURE = os.path.join(BENCH, "fixtures", "v5e-treeset-12x256.xplane.pb.gz")


def test_union_and_gaps():
    busy, gaps = tracereduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (60, 70)], 0, 50)
    assert busy == pytest.approx(30e-9)
    assert gaps == [(20, 30), (40, 50)]


def test_trace_reduction_on_the_recorded_v5e_trace():
    with open(os.path.join(BENCH, "fixtures", "expected.json")) as f:
        want = json.load(f)
    families = {}
    for path in glob.glob(os.path.join(BENCH, "layers", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if "family" in spec:
            families[spec["family"]] = spec["modules"]
    got = tracereduce.reduce(tracereduce.load(FIXTURE), families,
                             window_annotation="bench:step")
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for fam, w in want["families"].items():
        assert got["families"][fam]["calls"] == w["calls"] > 0
        assert got["families"][fam]["seconds"] == pytest.approx(
            w["seconds"], rel=1e-9)
    # the device saw what the program counted in that run: one module
    # execution a dispatch
    counted = want["program_counters"]
    assert got["families"]["gradient"]["calls"] == \
        counted["grad_passes_per_step"]
    assert sum(f["calls"] for f in got["families"].values()) == \
        counted["dispatches_per_step"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert all(len(name) <= 120 for name, _ in got["device_ops"])
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    # without the harness's own annotation the window is the device's
    # first to last operation: no longer than the annotated one
    bare = tracereduce.reduce(tracereduce.load(FIXTURE), families)
    assert bare["window_s"] <= got["window_s"]
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        tracereduce.reduce(types.SimpleNamespace(planes=[]), families)


def test_short_op_names():
    assert tracereduce.short_op(
        "%while.23 = (s32[]{:T(128)}, f32[16,32]{1,0:T(8,128)}) "
        "while((s32[]{:T(128)}) %tuple.108), condition=%c") == \
        "while.23 while"
    assert tracereduce.short_op(
        "%fusion.2 = f32[8,4]{1,0:T(8,128)} fusion(f32[16,4] %g), "
        "kind=kCustom") == "fusion.2 fusion"


# -- run.py end to end, rehearsed -----------------------------------------------


def _last_line(proc, lines):
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert list(rec)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(rec)[-1] == "check" and rec["rehearse"] is True
    assert rec["device"]["platform"] == "cpu" and rec["device"]["count"] == 1
    assert rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] == rec["steps"] >= 1
    for name, (value, limit) in rec["check"].items():
        assert value <= limit
        assert f"check {name} " in proc.stderr
    return rec


def test_rehearsed_modopt_run_ends_in_the_contracts_line():
    proc, lines = _py("run.py", ["--workload", "dna140x16k.modopt", "--seed",
                                 str(2**31 + 7), "--seconds", "2",
                                 "--trace", "0", "--rehearse"])
    rec = _last_line(proc, lines)
    assert set(rec["metrics"]) == {"step_s", "setup_s"}
    for m in rec["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"
    assert proc.stderr.rstrip().splitlines()[-1].startswith("check ")


def test_rehearsed_treeset_traced_run_reports_layer_metrics():
    proc, lines = _py("run.py", ["--workload", "dna140x131k.treeset1_bl",
                                 "--seed", "19", "--seconds", "2",
                                 "--trace", "1", "--rehearse"])
    rec = _last_line(proc, lines)
    # the manifest's own list for the cell; the CPU has no device plane
    # and no memory statistics: what is read from them is left out, never 0
    from benchmarks import run
    listed = {m["name"]: run.read_json(BENCH, "layers", m["name"] + ".json")
              for m in run.metrics_of(MANIFEST, "per_layer",
                                      "dna140x131k.treeset1_bl")}
    assert set(rec["metrics"]) == {
        name for name, spec in listed.items()
        if spec["source"] != "device_trace"
        and spec["reader"] != "memory_peak"}
    assert {"grad_slots_per_step", "dispatches_per_step"} <= set(
        rec["metrics"])
    assert not {"gradient_chip_roofline", "collectives_per_step"} & set(
        listed)
    assert rec["metrics"]["compiles_in_window"]["value"] == 0
    assert rec["metrics"]["grad_passes_per_step"]["value"] >= 1
    assert not glob.glob(os.path.join(
        BENCH, ".cache", "trace-dna140x131k-treeset1_bl-rehearse-*"))


def test_a_cpu_without_rehearse_is_refused():
    proc, lines = _py("run.py", ["--workload", "dna140x16k.modopt",
                                 "--seed", "1", "--seconds", "1",
                                 "--trace", "0"])
    assert proc.returncode != 0 and not lines
    assert "no accelerator" in proc.stderr


@pytest.mark.parametrize("cell,planted", [
    ("dna140x16k.modopt", ["--fault", "unchanged"]),
    ("dna140x131k.treeset1_bl", ["--fault", "unchanged"]),
    ("dna140x131k.treeset1_bl", ["--fault", "half"]),
    ("dna140x16k.modopt", ["--fault", "altered"]),
    ("dna140x16k.modopt", ["--control", "clv"]),
    ("aa140x16k.treeset4_bl", ["--control", "clv"]),
    ("dna140x131k.treeset1_bl", ["--fault", "freqs"]),
    ("aa140x16k.treeset4_bl", ["--fault", "freqs"]),
    # ISSUE 38's cell, once a fault it can have
    ("dna140x16k.treeset", ["--fault", "unchanged"]),
    ("dna140x16k.treeset", ["--fault", "half"]),
    ("dna140x16k.treeset", ["--fault", "altered"]),
    ("dna140x16k.treeset", ["--fault", "freqs"]),
    # and the draft of the partitioned protein deployment, through its
    # own manifest (the two faults that only a partitioned cell can
    # show: test_parts_and_treeset_cells.py)
    ("aa140p8x16k.modopt", ["--fault", "unchanged", *DRAFT]),
    ("aa140p8x16k.modopt", ["--fault", "half", *DRAFT]),
    ("aa140p8x16k.modopt", ["--fault", "altered", *DRAFT]),
])
def test_control_and_planted_faults_come_out_not_correct(cell, planted):
    """The rest of a run driven with the timed path broken underneath
    (benchmarks/calibrate.py plants the fault in the program), and the
    program's own bf16 arena as the lower-precision control: `correct`
    has to read false, by the number that is that fault's to catch."""
    proc, lines = _py("calibrate.py", ["--workload", cell, "--seeds", "23",
                                       "--seconds", "1", "--rehearse",
                                       *planted])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False
    number = {"unchanged": "newton_dz_max",
              "freqs": "model_table_err"}.get(planted[1], "lnl_rel_err")
    value, limit = rec["check"][number]
    assert value > limit
