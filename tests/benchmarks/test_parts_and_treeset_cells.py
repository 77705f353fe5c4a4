"""The narrow tree set, and the draft of the partitioned protein
deployment (ISSUE 38; CPU, tier-1).

What is held here: `BENCHMARK.json` holds `dna140x16k.treeset` and NOT
the partitioned cell, whose scheme (8 LG parts, their widths) no public
partition file bears out: `aa140p8x16k` waits under `benchmarks/drafts/`
with a manifest of its own that says so; which cells read a per-layer
metric follows from data (no list, a list, or the `chips` a `layers/`
file states), with no cell's name typed here; the draft through
`run.py --rehearse --manifest` ends in the contract's line with 8
partitions, every part at its stated width; `dna140x16k.treeset`'s
rehearsal starts from trees WITHOUT branch lengths (the traffic file's
missing `branch_lengths` reaches `steps/treeset.prepare`); the two
faults only a partitioned cell can have read not correct by the number
named for them (the faults every cell can have are cases of
test_benchmark.py's test of them).  No number of this file is a device
number.
"""

import importlib
import json
import os
import re
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import (BENCH, DRAFT, MANIFEST, REPO,  # noqa: E402
                            _last_line, _py)
# (puts the checkout on sys.path, reads the manifest, runs a benchmark
# script, holds a rehearsed run's last line to the contract)

from benchmarks import datagen  # noqa: E402
from benchmarks import run as bench  # noqa: E402

PARTS, TREESET = "aa140p8x16k.modopt", "dna140x16k.treeset"
WIDTHS = [4731, 3102, 2460, 1893, 1544, 1201, 877, 576]
REHEARSED = [160, 144, 128, 112, 104, 96, 88, 80]
# what a one-chip cell of these step kinds reads beside the metrics of
# every cell: metric names, not cells
EVERYWHERE = ("grad_slots_per_step", "compiled_programs", "stage_ms",
              "staged_arrays_per_step", "launch_ms", "wait_ms",
              "set_models_ms", "opt_control_ms", "trav_evals_per_step")
ONE_CHIP = ("traverse_roofline", "gradient_roofline")
with open(os.path.join(REPO, DRAFT[1])) as _f:
    DRAFTED = json.load(_f)
MANIFESTS = {PARTS: (DRAFT[1], DRAFTED), TREESET: ("BENCHMARK.json",
                                                   MANIFEST)}


def _rehearsed(cell):
    _, _, config, traffic = bench.find_cell(cell, MANIFESTS[cell][0])
    return bench.stated({**config, **config["rehearse"],
                         "rehearsed": True}), traffic


# -- the manifest and the files it names ---------------------------------------


def test_manifest_holds_the_tree_set_and_the_draft_is_no_cell():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert (cells[TREESET]["config"], cells[TREESET]["traffic"],
            cells[TREESET]["chips"]) == ("dna140x16k", "treeset4", 1)
    # no public partition file bears out the draft's scheme: it is in no
    # list of the manifest and has no file among the accepted ones
    assert PARTS not in json.dumps(MANIFEST)
    assert "aa140p8x16k" not in json.dumps(MANIFEST)
    for kind in ("configs", "correct", "pins"):
        assert not [f for f in os.listdir(os.path.join(BENCH, kind))
                    if f.startswith("aa140p8x16k")]
    (w,) = DRAFTED["workloads"]
    assert (w["name"], w["config"], w["traffic"], w["chips"]) == (
        PARTS, "aa140p8x16k", "modopt", 1)
    assert DRAFTED["command"][-2:] == DRAFT
    # a cell takes four chips only for what exists across chips: at most
    # half of the cells, as the contract counts
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2)


def test_the_partitioned_draft_states_what_it_is():
    (entry,) = DRAFTED["configs"]
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    # it names what upstream's file holds and that this is not it
    assert entry["source"].startswith("NONE PUBLIC")
    assert "140.model" in entry["source"] and "WAG" in entry["source"]
    assert entry["file"] == "benchmarks/drafts/configs/aa140p8x16k.json"
    config = bench.read_json(bench.ROOT, entry["file"])
    assert config["source"] == entry["source"]
    assert config["deployment"].startswith("DRAFT")
    assert [p["patterns"] for p in config["parts"]] == WIDTHS
    assert (config["patterns"], config["partitions"]) == (16384, 8)
    assert bench.stated(config)["patterns"] == 16384
    assert config["cli_args"] == [] and config["reduced"] == []
    assert config["precision"] == {"clv_dtype": "f32",
                                   "dot_precision": "high"}
    assert len(config["assumed"]) >= 4
    for part in config["parts"] + config["rehearse"]["parts"]:
        assert part["model"] == "LGF" and part["exchangeabilities"] == "LG"
    assert [p["patterns"] for p in config["rehearse"]["parts"]] == REHEARSED
    alphas = [p["generating"]["alpha"] for p in config["parts"]]
    rates = [p["generating"]["rate"] for p in config["parts"]]
    assert (min(alphas), max(alphas), min(rates), max(rates)) == (
        0.3, 1.5, 0.4, 2.2)


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in MANIFEST["per_layer"]])
def test_which_cells_read_a_metric_follows_from_data(metric):
    """No list: every cell.  A `layers/` file that states `chips`: the
    cells on as many chips, and the manifest's list is that rule written
    out for the driver.  Else the list.  `metrics_of` agrees with all
    three, and no list names a cell twice or one that is no workload."""
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    spec = bench.read_json(BENCH, "layers", metric + ".json")
    cells = MANIFEST["workloads"]
    if "chips" in spec:
        want = [w["name"] for w in cells if w["chips"] == spec["chips"]]
        assert entry["workloads"] == want
    else:
        want = entry.get("workloads", [w["name"] for w in cells])
    assert len(set(want)) == len(want) >= 1
    assert set(want) <= {w["name"] for w in cells}
    read = [w["name"] for w in cells if metric in [
        m["name"] for m in bench.metrics_of(MANIFEST, "per_layer",
                                            w["name"])]]
    assert read == want
    assert (metric in EVERYWHERE) <= ("workloads" not in entry)
    assert (metric in ONE_CHIP) <= (spec.get("chips") == 1)


def test_a_cell_added_later_reads_the_shared_metrics_by_its_entry_alone():
    """One more workload in the manifest and no other edit: on one chip
    it reads both rooflines, PR 28's seven, the slots and the compiled
    programs; on four the rooflines of one chip are not its."""
    for chips in (1, 4):
        more = {**MANIFEST, "workloads": MANIFEST["workloads"] + [
            {"name": "later.cell", "config": "dna140x16k",
             "traffic": "treeset4", "chips": chips, "why": "a test's"}]}
        names = {m["name"] for m in bench.metrics_of(more, "per_layer",
                                                     "later.cell")}
        assert set(EVERYWHERE) <= names
        assert set(ONE_CHIP) & names == (set(ONE_CHIP) if chips == 1
                                         else set())
        # and what it reads is what the tree set reads, less nothing
        if chips == 1:
            assert names == {m["name"] for m in bench.metrics_of(
                MANIFEST, "per_layer", TREESET)}


def test_the_new_cells_read_no_metric_of_the_mesh_or_the_search():
    for cell in (PARTS, TREESET):
        names = {m["name"] for m in bench.metrics_of(
            MANIFESTS[cell][1], "per_layer", cell)}
        assert set(EVERYWHERE + ONE_CHIP) <= names
        assert not {n for n in names if "_chip_" in n or "spr_" in n
                    or n.startswith(("search_", "collectives", "moves_"))}


# -- the inputs, in process ------------------------------------------------------


def test_partitioned_cell_loads_eight_parts_each_at_its_width(tmp_path,
                                                              monkeypatch):
    pytest.importorskip("examl_tpu.cli.parse")
    from examl_tpu.cli import main as cli
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    config, traffic = _rehearsed(PARTS)
    assert (config["patterns"], config["partitions"]) == (912, 8)
    gen, bytefile, _wd = bench.make_inputs(config, traffic,
                                           "test-aa140p8x16k", 2**31 + 38)
    data = cli._load_alignment(bytefile)
    assert [p.width for p in data.partitions] == REHEARSED
    assert [p.name for p in data.partitions] == [f"gene{k}"
                                                 for k in range(1, 9)]
    assert all(p.model_name == "LG" and p.use_empirical_freqs
               for p in data.partitions)
    ends = [sum(REHEARSED[:k + 1]) for k in range(8)]
    assert gen["bounds"] == [(e - w, e) for w, e in zip(REHEARSED, ends)]
    assert len(gen["models"]) == 8
    # every state occurs in every part: the reference counts a frequency
    # where the program would floor one (PERF.md section 2)
    for s, e in gen["bounds"]:
        assert len(set(gen["patterns"][:, s:e].reshape(-1).tolist())) == 20


def test_tree_set_without_branch_lengths_reaches_prepare(tmp_path,
                                                         monkeypatch):
    """`treeset4.json` is `treeset4_bl.json` less `branch_lengths`: the
    generator writes bare topologies, `prepare` hands them on, and the
    parser gives every branch its default."""
    pytest.importorskip("examl_tpu.cli.parse")
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    config, traffic = _rehearsed(TREESET)
    assert traffic["kind"] == "treeset" and "branch_lengths" not in traffic
    with_bl = bench.read_json(BENCH, "traffic", "treeset4_bl.json")
    assert {k: v for k, v in with_bl.items()
            if k not in ("branch_lengths", "what")} == {
        k: v for k, v in traffic.items() if k != "what"}
    gen, bytefile, _wd = bench.make_inputs(config, traffic,
                                           "test-treeset4", 2**31 + 39)
    inst, data = bench.build_instance(bytefile)
    cell = types.SimpleNamespace(inst=inst, data=data, gen=gen,
                                 config=config,
                                 initial_models=list(inst.models),
                                 traced_steps=0)
    kind = importlib.import_module("benchmarks.steps.treeset")
    assert kind.prepare(cell, traffic) == 4
    assert len(set(cell.newicks)) == 4
    assert all(":" not in text for text in cell.newicks)
    # the same moves as the traffic WITH lengths draws, less the lengths
    carried = datagen.problem(config, 4, 5, True)["moved_trees"]
    assert [re.sub(r":[0-9.]+", "", t) for t in carried] == cell.newicks
    tree = inst.tree_from_newick(cell.newicks[0])
    assert len({float(p.z[0]) for p, _ in tree.all_branches()}) == 1
    # and a step smooths them: it leaves other lengths and a better lnL
    tree, lnl, before = kind.step(cell, 0)
    assert lnl > before
    assert len({float(p.z[0]) for p, _ in tree.all_branches()}) > 1


# -- run.py --rehearse, traced ----------------------------------------------------


@pytest.mark.parametrize("cell", [PARTS, TREESET])
def test_rehearsed_traced_run_ends_in_the_contracts_line(cell):
    proc, lines = _py("run.py", [
        "--workload", cell, "--seed", str(2**31 + 40), "--seconds", "1",
        "--trace", "1", "--rehearse", *(DRAFT if cell == PARTS else [])])
    rec = _last_line(proc, lines)
    assert proc.stderr.rstrip().splitlines()[-1].startswith("check ")
    # what the manifest lists for the cell, less what only a device gives
    listed = {m["name"]: bench.read_json(BENCH, "layers",
                                         m["name"] + ".json")
              for m in bench.metrics_of(MANIFESTS[cell][1], "per_layer",
                                        cell)}
    assert set(rec["metrics"]) == {
        name for name, spec in listed.items()
        if spec["source"] != "device_trace"
        and spec["reader"] != "memory_peak"}
    assert set(EVERYWHERE) <= set(rec["metrics"])
    v = {k: m["value"] for k, m in rec["metrics"].items()}
    assert v["compiles_in_window"] == 0 and v["grad_passes_per_step"] >= 1
    if cell == PARTS:
        # 912 patterns, every part padded to whole blocks of 128
        assert rec["padding_share"] == pytest.approx(1 - 912 / 1280)
        assert "912 patterns in 1280 lanes" in proc.stderr
        assert "(total " in proc.stderr and "largest part " in proc.stderr
        assert v["set_models_ms"] > 0 and v["trav_evals_per_step"] >= 2
    else:
        # whole cycles of the four trees, no model moved in a step
        assert rec["steps"] % 4 == 0 and rec["steps"] >= 4
        assert rec["padding_share"] == 0 and v["set_models_ms"] == 0
        assert "largest part" not in proc.stderr


# -- the timed path broken underneath ----------------------------------------------


@pytest.mark.parametrize("fault,number", [
    ("swap_parts", "lnl_rel_err"), ("part_freqs", "model_table_err")])
def test_faults_only_a_partitioned_cell_can_have_come_out_not_correct(
        fault, number):
    """The other faults of both cells are cases of test_benchmark.py's
    `test_control_and_planted_faults_come_out_not_correct`."""
    proc, lines = _py("calibrate.py", ["--workload", PARTS, "--seeds", "38",
                                       "--seconds", "1", "--rehearse",
                                       "--fault", fault, *DRAFT])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False and rec["fault"] == fault
    value, limit = rec["check"][number]
    assert value > limit
    if fault == "swap_parts":
        # the two widest parts read each other's model: a part's error,
        # not the total's, is the reading
        ref = next(ln for ln in proc.stderr.splitlines()
                   if ln.startswith("reference: "))
        assert ref.split("largest part ")[1].split()[0] in ("0", "1")
    else:
        assert value > 1e-4
