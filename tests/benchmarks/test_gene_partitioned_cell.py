"""The gene-partitioned protein cell `aa144p58x16k.modopt` (CPU, tier-1).

What is held here: the manifest holds the configuration and the cell as
the configuration's file states them (144 taxa, 58 whole LGF genes whose
widths sum to 16,384 patterns in 19,968 lanes, `reduced` the partitions
and the patterns); the lane padding `lane_padding_pct` reads is the
packing's arithmetic, 17.95% here and 0 in every one-part cell; the
cell's rehearsal (12 taxa, the same 58 genes at 64 to 200 patterns) ends
`correct` in the contract's line with every metric the manifest lists
for the cell but those read from the device; the fault only a
partitioned cell can show, two genes' block ids exchanged, reads not
correct.  No number of this file is a device number.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, MANIFEST, _last_line, _py  # noqa: E402

from benchmarks import datagen  # noqa: E402
from benchmarks import run as bench  # noqa: E402

CELL, CONFIG = "aa144p58x16k.modopt", "aa144p58x16k"
REHEARSED = (5571, 7552)            # patterns, lanes at rehearsal size


def _lanes(config):
    """The packed site axis `parallel/packing.py` lays the stated parts
    on: every part padded to whole 128-lane blocks."""
    return sum(-(-p["patterns"] // 128) * 128
               for p in datagen.parts_of(config))


def test_the_cell_is_the_configuration_as_its_file_states_it():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == (CONFIG, "modopt", 1)
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["partitions", "patterns"]
    assert "Misof" in entry["source"] and "1,478" in entry["source"]
    config = bench.stated(bench.read_json(bench.ROOT, entry["file"]))
    assert (config["taxa"], config["states"], config["rate_categories"],
            config["partitions"], config["patterns"]) == (144, 20, 4, 58,
                                                          16384)
    assert config["precision"] == {"clv_dtype": "f32",
                                   "dot_precision": "high"}
    for part in config["parts"] + config["rehearse"]["parts"]:
        assert part["model"] == "LGF" and part["exchangeabilities"] == "LG"
        gen = part["generating"]
        assert gen["rates"] == "random"
        assert 0.3 <= gen["alpha"] <= 1.5 and 0.4 <= gen["rate"] <= 2.2
    widths = [p["patterns"] for p in config["parts"]]
    assert (min(widths), max(widths), _lanes(config)) == (64, 977, 19968)
    # the rehearsal keeps the genes and their generating models
    assert [p["name"] for p in config["rehearse"]["parts"]] == [
        p["name"] for p in config["parts"]]
    rehearsed = bench.stated({**config, **config["rehearse"]})
    assert (rehearsed["patterns"], _lanes(rehearsed)) == REHEARSED
    # the same guarantees as the one-part protein cell, and one more
    with open(os.path.join(BENCH, "configs", "aa140x16k.json")) as f:
        one = json.load(f)
    assert config["guarantees"].startswith(one["guarantees"])
    assert config["domain"] == one["domain"]
    assert "every partition's lnL" in config["guarantees"]


def test_every_state_of_every_rehearsed_gene_is_over_the_floor():
    """The reference counts a frequency where the program floors it at
    0.001: a rehearsed gene lacking a state would read `model_table_err`
    1e-3.  At full size the narrowest gene holds 64 x 144 characters."""
    _, _, config, _ = bench.find_cell(CELL)
    prob = datagen.problem({**config, **config["rehearse"]}, 0, 0)
    for s, e in prob["bounds"]:
        counts = np.bincount(prob["patterns"][:, s:e].ravel(), minlength=20)
        assert counts.min() / counts.sum() > 0.001


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_lane_padding_is_the_packings_arithmetic(cell):
    """What `lane_padding_pct` reads in each cell: every one-part cell's
    width is whole blocks (0 lanes of padding), the gene-partitioned
    cell pads 3,584 of 19,968."""
    _, _, config, _ = bench.find_cell(cell)
    config = bench.stated(config)
    share = 100.0 * (1.0 - config["patterns"] / _lanes(config))
    if cell == CELL:
        assert share == pytest.approx(100.0 * 3584 / 19968)
    else:
        assert share == 0.0


def test_rehearsed_traced_run_reads_correct_and_every_listed_metric():
    proc, lines = _py("run.py", ["--workload", CELL, "--seed",
                                 str(2**31 + 42), "--seconds", "1",
                                 "--trace", "1", "--rehearse"])
    rec = _last_line(proc, lines)
    listed = {m["name"]: bench.read_json(BENCH, "layers", m["name"] + ".json")
              for m in bench.metrics_of(MANIFEST, "per_layer", CELL)}
    assert {"traverse_roofline", "gradient_roofline",
            "lane_padding_pct"} <= set(listed)
    assert set(rec["metrics"]) == {
        name for name, spec in listed.items()
        if spec["source"] != "device_trace"
        and spec["reader"] != "memory_peak"}
    v = {k: m["value"] for k, m in rec["metrics"].items()}
    patterns, lanes = REHEARSED
    assert v["lane_padding_pct"] == pytest.approx(
        100.0 * (1.0 - patterns / lanes))
    assert rec["padding_share"] == pytest.approx(1.0 - patterns / lanes)
    assert f"{patterns} patterns in {lanes} lanes" in proc.stderr
    assert v["compiles_in_window"] == 0 and v["set_models_ms"] > 0
    assert v["trav_evals_per_step"] >= 2 and v["grad_passes_per_step"] >= 1
    # the reference's line gives the total's error and the largest gene's
    assert "(total " in proc.stderr and "largest part " in proc.stderr


def test_two_genes_on_each_others_blocks_read_not_correct():
    """`swap_parts` exchanges the block ids of the two widest genes at
    engine build: each is evaluated under the other's model, which a
    gene's own lnL shows."""
    proc, lines = _py("calibrate.py", ["--workload", CELL, "--seeds", "42",
                                       "--seconds", "1", "--rehearse",
                                       "--fault", "swap_parts"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False and rec["fault"] == "swap_parts"
    value, limit = rec["check"]["lnl_rel_err"]
    assert value > limit
