"""The four-chip cell `dna140x262k.treeset-c4` (PR 31), CPU.

`run.py --rehearse` of the cell on four forced host devices ends in the
contract's line, untraced and traced, takes the whole-tree gradient pass
on its site-sharded arenas and counts its collectives; its step kind
`treeset_sharded` ends the run non-zero in set-up, with one line and no
result line, wherever the program would smooth branch by branch; the
per-chip roofline reader divides the whole alignment's bytes by the
configuration's `site_shards`; the planted faults and the program's
lower-precision arena come out not correct.  No number of this file is a
device number.
"""

import importlib
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, MANIFEST, _py  # noqa: E402  (puts the
# checkout on sys.path, reads the manifest, runs a benchmark script)

CELL = "dna140x262k.treeset-c4"
NEW_METRICS = ("gradient_chip_roofline", "traverse_chip_roofline",
               "collectives_per_step")
GUARD = "benchmarks/steps/treeset_sharded.py: refused: "


def _rehearsed(trace: int):
    proc, lines = _py("run.py", ["--workload", CELL, "--seed",
                                 str(2**31 + 30 + trace), "--seconds", "2",
                                 "--trace", str(trace), "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert list(rec)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(rec)[-1] == "check" and rec["rehearse"] is True
    assert rec["device"]["platform"] == "cpu" and rec["device"]["count"] == 4
    assert rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] == rec["steps"] >= 1
    assert "site axis sharded over 4 devices" in proc.stderr
    return rec


def test_manifest_entries_of_the_cell():
    (cell,) = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dna140x262k", "treeset1_bl_c4", 4)
    with open(os.path.join(BENCH, "traffic", "treeset1_bl_c4.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "treeset1_bl.json")) as f:
        accepted = json.load(f)
    assert traffic["kind"] == "treeset_sharded"
    assert ({k: v for k, v in traffic.items() if k not in ("kind", "what")}
            == {k: v for k, v in accepted.items()
                if k not in ("kind", "what")})
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "dna140x262k"]
    with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"] == ["patterns"]
    assert config["patterns"] == 4 * 65536 and config["site_shards"] == 4
    assert "number of chips" in config["guarantees"]
    for name in NEW_METRICS:
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "step_s"


def test_rehearsed_four_device_run_ends_in_the_contracts_line():
    rec = _rehearsed(0)
    assert set(rec["metrics"]) == {"step_s", "setup_s"}
    for m in rec["metrics"].values():
        assert m["value"] > 0 and m["unit"] == "s"


def test_rehearsed_traced_run_makes_gradient_passes_and_counts_collectives():
    rec = _rehearsed(1)
    v = {k: m["value"] for k, m in rec["metrics"].items()}
    # the CPU has no device plane: the roofline shares are left out
    assert not {"gradient_chip_roofline", "traverse_chip_roofline",
                "gradient_roofline", "traverse_roofline"} & set(v)
    passes = v["grad_passes_per_step"]
    assert passes >= 1 and v["compiles_in_window"] == 0
    # a step: evaluate, (traverse + gradient pass) a sweep, evaluate
    assert v["dispatches_per_step"] == 2 * passes + 2
    # one all-reduce a gradient pass and one an evaluation
    assert v["collectives_per_step"] == passes + 2
    assert rec["metrics"]["collectives_per_step"]["unit"] == "count"


@pytest.mark.parametrize("mode", ["rows", "0"])
def test_collectives_are_left_out_where_no_program_text_was_read(
        mode, monkeypatch):
    """`EXAML_PROGRAM_OBS=rows|0`: the observatory compiles nothing for
    analysis, so no program's collectives are known; the counter is
    then never raised and the line leaves the metric out, it does not
    read 0."""
    monkeypatch.setenv("EXAML_PROGRAM_OBS", mode)
    proc, lines = _py("run.py", ["--workload", CELL, "--seed",
                                 str(2**31 + 34), "--seconds", "1",
                                 "--trace", "1", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    metrics = json.loads(lines[-1])["metrics"]
    assert "collectives_per_step" not in metrics
    assert metrics["grad_passes_per_step"]["value"] >= 1


def _refused(proc, lines, why: str):
    """Non-zero, the guard's one line last on stderr, no result line."""
    assert proc.returncode not in (0, None), proc.stdout[-2000:]
    assert not lines, lines
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith(GUARD) and why in last, proc.stderr[-3000:]


@pytest.mark.parametrize("trace", [0, 1])
def test_guard_refuses_a_run_pinned_to_the_per_branch_path(trace,
                                                           monkeypatch):
    """`EXAML_GRAD_SMOOTH=0`: the predicate has no reason, the program
    smooths branch by branch all the same; the check after the warm-up
    reads the program's own counter and ends the run before any step,
    traced and untraced alike."""
    monkeypatch.setenv("EXAML_GRAD_SMOOTH", "0")
    proc, lines = _py("run.py", ["--workload", CELL, "--seed",
                                 str(2**31 + 31), "--seconds", "2",
                                 "--trace", str(trace), "--rehearse"])
    _refused(proc, lines, "dispatched no whole-tree gradient pass")
    assert "site axis sharded over 4 devices" in proc.stderr


def test_guard_refuses_the_unchanged_fault_before_any_step():
    """calibrate.py's `unchanged` fault makes `smooth_tree` a no-op: a
    program that makes no pass is refused in set-up, before it can be
    found not correct.  (Its reading of `newton_dz_max` in
    correct/<cell>.json comes from calibrate_sharded.py, which plants
    the fault after the warm-up: the next test.)"""
    proc, lines = _py("calibrate.py", ["--workload", CELL, "--seeds", "31",
                                       "--seconds", "1", "--rehearse",
                                       "--fault", "unchanged"])
    _refused(proc, lines, "dispatched no whole-tree gradient pass")


@pytest.mark.parametrize("fault", ["unchanged", "one_shard"])
def test_faults_that_need_the_mesh_or_a_sound_warm_up_are_not_correct(fault):
    """calibrate_sharded.py: `unchanged` planted after the warm-up (the
    guard passes, the timed steps leave their state as given) and the
    derivative all-reduce left out (the optimiser is handed the first
    shard's d1, d2; lnL is still the whole alignment's) both leave
    branches a Newton step of the reference would still move."""
    proc, lines = _py("calibrate_sharded.py", [
        "--workload", CELL, "--seed", str(2**31 + 32), "--seconds", "1",
        "--rehearse", "--fault", fault])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False and rec["failed"] == 0
    value, limit = rec["check"]["newton_dz_max"]
    assert value > 10 * limit
    value, limit = rec["check"]["lnl_rel_err"]
    assert value <= limit


def test_all_layers_reads_the_metrics_whose_lists_leave_the_cell_out():
    """PR 28's seven metrics list the one-chip cells; the cell runs
    every one of their layers, and `--all-layers` reads them there."""
    proc, lines = _py("calibrate_sharded.py", [
        "--workload", CELL, "--seed", str(2**31 + 33), "--seconds", "1",
        "--rehearse", "--trace", "1", "--all-layers"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is True
    v = rec["metrics"]
    assert {"stage_ms", "staged_arrays_per_step", "launch_ms", "wait_ms",
            "set_models_ms", "opt_control_ms", "trav_evals_per_step",
            "collectives_per_step"} <= set(v)
    passes = v["grad_passes_per_step"]
    assert v["trav_evals_per_step"] == 2
    assert v["collectives_per_step"] == passes + v["trav_evals_per_step"]
    assert v["stage_ms"] > 0 and v["wait_ms"] > 0


class _Sharding:
    def __init__(self, site_shards, tree_shards=1):
        self.site_shards, self.tree_shards = site_shards, tree_shards


def _cell(sharding, save_memory=False):
    eng = types.SimpleNamespace(sharding=sharding)
    return types.SimpleNamespace(
        config={"site_shards": 4},
        inst=types.SimpleNamespace(engines={0: eng},
                                   save_memory=save_memory))


@pytest.mark.parametrize("sharding,save_memory,why", [
    (None, False, "in 1 shard(s), the configuration states 4"),
    (_Sharding(2), False, "in 2 shard(s), the configuration states 4"),
    (_Sharding(4, tree_shards=2), False, "a fabric with tree slices"),
    (_Sharding(4), True, "-S SEV pools"),
])
def test_prepare_refuses_before_anything_is_dispatched(sharding,
                                                       save_memory, why):
    """The first check needs no device: an engine that is not sharded
    as the configuration states, or one the program's own predicate
    keeps from the gradient pass, exits with the reason quoted; the
    stand-in cell has nothing else, so reaching `treeset.prepare` would
    raise another error than SystemExit."""
    kind = importlib.import_module("benchmarks.steps.treeset_sharded")
    with pytest.raises(SystemExit) as exc:
        kind.prepare(_cell(sharding, save_memory), {"trees": 1})
    assert str(exc.value).startswith(GUARD) and why in str(exc.value)


def test_prepare_lets_a_four_shard_engine_through_to_treesets_prepare():
    kind = importlib.import_module("benchmarks.steps.treeset_sharded")
    with pytest.raises(AttributeError):     # the stand-in has no `gen`
        kind.prepare(_cell(_Sharding(4)), {"trees": 1})


def test_chip_roofline_is_the_whole_roofline_over_the_shards():
    whole = importlib.import_module("benchmarks.readers.family_roofline")
    chip = importlib.import_module("benchmarks.readers.family_roofline_chip")
    with open(os.path.join(BENCH, "configs", "dna140x262k.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    run = {"config": config, "peak": peak, "trace": {"families": {
        "gradient": {"seconds": 9.0, "calls": 30.0},
        "traverse": {"seconds": 0.0, "calls": 0.0}}}}
    for name in ("gradient_chip_roofline", "traverse_chip_roofline"):
        with open(os.path.join(BENCH, "layers", name + ".json")) as f:
            spec = json.load(f)
        assert (spec["unit"], spec["source"]) == ("%", "device_trace")
        if spec["family"] == "traverse":     # no execution in the trace
            assert chip.read(run, spec) is None
            continue
        got = chip.read(run, spec)
        # the accepted reader holds the whole alignment's bytes against
        # one chip's peak: four times the chip's share here
        assert whole.read(run, spec) == pytest.approx(4 * got)
        assert 0 < got < 100
        assert chip.read({**run, "trace": None}, spec) is None
        one_chip = {k: v for k, v in config.items() if k != "site_shards"}
        assert chip.read({**run, "config": one_chip}, spec) is None


@pytest.mark.parametrize("planted,number", [
    (["--fault", "half"], "lnl_rel_err"),
    # (`unchanged` never reaches the comparison here: the guard's test)
    (["--fault", "freqs"], "model_table_err"),
    # the `dot` control changes nothing on a CPU (its dots are f32
    # whatever the precision asked for); the bf16 arena does
    (["--control", "clv"], "lnl_rel_err"),
])
def test_faults_and_control_come_out_not_correct_on_four_devices(planted,
                                                                 number):
    proc, lines = _py("calibrate.py", ["--workload", CELL, "--seeds", "30",
                                       "--seconds", "1", "--rehearse",
                                       *planted])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False
    value, limit = rec["check"][number]
    assert value > limit
