"""The search cells `dna140x16k.search` and `dna49x131k.search` (PR 35),
CPU.

`run.py --rehearse` of both cells (12 taxa x 256, slot counts clipped to
the tree's) ends in the contract's line and reports the search layers'
metrics; the step kind `search` ends a run non-zero in set-up, with one
line and no result line, where the program would score candidates one by
one or lacks the slot functions (the parent of PR 35); both step
failures are provoked by planted faults; the plain reference's window,
the byte model of a scan dispatch and the roofline reader on synthetic
runs.  No number of this file is a device number.
"""

import ast
import importlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark import BENCH, MANIFEST, _py  # noqa: E402  (puts the
# checkout on sys.path, reads the manifest, runs a benchmark script)

from benchmarks import bytemodel, bytemodel_search  # noqa: E402
from benchmarks import reference_search  # noqa: E402

CELLS = {"dna140x16k.search": ("dna140x16k", "search_l64t8", 64, 8, 5),
         "dna49x131k.search": ("dna49x131k", "search_l16t2", 16, 2, 3)}
NEW_METRICS = ("spr_slots_per_step", "scan_candidates_per_step",
               "moves_per_step", "search_plan_ms", "search_commit_ms",
               "compiled_programs", "spr_scan_roofline",
               "spr_thorough_roofline")
GUARD = "benchmarks/steps/search.py: refused: "


# -- the manifest --------------------------------------------------------------


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_manifest_entries_of_the_cell(cell):
    config, traffic_name, lazy, thorough, rescore = CELLS[cell]
    (w,) = [w for w in MANIFEST["workloads"] if w["name"] == cell]
    assert (w["config"], w["traffic"], w["chips"]) == (config,
                                                       traffic_name, 1)
    with open(os.path.join(BENCH, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "search"
    want = {"trees": 1, "spr_moves": 20, "branch_lengths": "generating",
            "radius": 10, "lazy_slots": lazy, "thorough_slots": thorough,
            "rescore_trees": rescore, "check_states": 1}
    assert {k: traffic[k] for k in want} == want
    assert 0 < traffic["rescore_rel_tol"] < 1e-3
    for name in NEW_METRICS:
        (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        # `compiled_programs` reads a counter every engine has: every
        # cell since ISSUE 38, so no list
        assert m.get("workloads") == (None if name == "compiled_programs"
                                      else sorted(CELLS))


def test_the_new_configuration_is_the_searchs_own():
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "dna49x131k"]
    with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "dna140x131k.json")) as f:
        wide = json.load(f)
    assert (config["taxa"], config["patterns"]) == (49, 131072)
    assert entry["reduced"] == config["reduced"] == ["patterns"]
    assert len(entry["source"]) <= 200 and "testData/49" in entry["source"]
    for key in ("datatype", "states", "model", "parse", "generating",
                "rate_categories", "partitions", "precision", "domain",
                "data_seed", "rehearse"):
        assert config[key] == wide[key], key
    assert config["guarantees"].startswith(wide["guarantees"])
    assert "full evaluation of that tree" in config["guarantees"]


@pytest.mark.parametrize("module", ["reference_search.py",
                                    "bytemodel_search.py"])
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


@pytest.mark.parametrize("cell,trace", [("dna140x16k.search", 1),
                                        ("dna49x131k.search", 0)])
def test_rehearsed_search_cell_ends_in_the_contracts_line(cell, trace):
    proc, lines = _py("run.py", ["--workload", cell, "--seed",
                                 str(2**31 + 35 + trace), "--seconds", "2",
                                 "--trace", str(trace), "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert list(rec)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(rec)[-1] == "check" and rec["rehearse"] is True
    assert rec["correct"] is True and rec["failed"] == 0
    assert rec["attempted"] == rec["steps"] >= 1
    v = {k: m["value"] for k, m in rec["metrics"].items()}
    if not trace:
        assert set(v) == {"step_s", "setup_s"}
        return
    # the CPU has no device plane: the roofline shares are left out
    assert not {m for m in v if m.endswith("_roofline")}
    assert set(NEW_METRICS[:6]) <= set(v)
    assert v["compiles_in_window"] == 0 and v["compiled_programs"] >= 4
    # 12 taxa: 22 slots, all of them lazy slots (64 are asked for)
    assert 8 <= v["spr_slots_per_step"] <= 22 + 8
    assert v["moves_per_step"] >= 1
    assert v["scan_candidates_per_step"] > v["spr_slots_per_step"]
    assert v["search_plan_ms"] > 0 and v["search_commit_ms"] > 0
    assert v["grad_passes_per_step"] >= 1


def _refused(proc, lines, why: str):
    """Non-zero, the guard's one line last on stderr, no result line."""
    assert proc.returncode not in (0, None), proc.stdout[-2000:]
    assert not lines, lines
    last = proc.stderr.strip().splitlines()[-1]
    assert last.startswith(GUARD) and why in last, proc.stderr[-3000:]


@pytest.mark.parametrize("trace", [0, 1])
def test_guard_refuses_a_program_without_the_slot_functions(trace):
    """The parent of PR 35 under this PR's benchmark files: `prepare`
    finds no `spr_slot` and ends the run in set-up, traced and untraced
    alike.  The parent is stood in for by a run started with a `-c` that
    deletes the three functions from `raxml_search` before `run.main`."""
    code = ("import sys; sys.argv = ['run.py'] + sys.argv[1:]; "
            "sys.path.insert(0, %r); "
            "from examl_tpu.search import raxml_search as r; "
            "[delattr(r, n) for n in ('spr_cycle_head', 'spr_slot', "
            "'rescore_best')]; "
            "from benchmarks import run; sys.exit(run.main())"
            % os.path.dirname(BENCH))
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE",
                        "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", "dna140x16k.search",
         "--seed", "35", "--seconds", "1", "--trace", str(trace),
         "--rehearse"], env=env, cwd=os.path.dirname(BENCH),
        capture_output=True, text=True, timeout=600)
    _refused(proc, [ln for ln in proc.stdout.splitlines() if ln.strip()],
             "spr_slot")


# -- the step kind, in this process ----------------------------------------------


@pytest.fixture(scope="module")
def cell():
    """The narrow cell rehearsed up to the step kind's `prepare` (12
    taxa x 256), with the two switches the step kind sets on a CPU taken
    back afterwards."""
    from benchmarks import calibrate_search
    mp = pytest.MonkeyPatch()
    for k in ("EXAML_BATCH_SCAN", "EXAML_BATCH_THOROUGH"):
        mp.setenv(k, "1")
    flags = os.environ.get("XLA_FLAGS")
    try:
        built, _traffic, _dev = calibrate_search.build_cell(
            "dna140x16k.search", 35, True, None)
        yield built
    finally:
        mp.undo()
        if flags is not None:
            os.environ["XLA_FLAGS"] = flags


def test_a_sound_step_commits_moves_and_keeps_its_scores(cell):
    from examl_tpu import obs
    kind = importlib.import_module("benchmarks.steps.search")
    moves = obs.counter("search.moves_committed")
    tree, lnl, before = kind.step(cell, 0)
    assert lnl > before and obs.counter("search.moves_committed") > moves
    assert cell.rescore_rel_err <= cell.params["rescore_rel_tol"]
    assert len(list(tree.all_branches())) == 2 * cell.config["taxa"] - 3


def test_a_search_that_commits_nothing_fails_the_step(cell, monkeypatch):
    from examl_tpu.search import raxml_search
    kind = importlib.import_module("benchmarks.steps.search")
    monkeypatch.setattr(raxml_search, "spr_slot", lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="committed no move"):
        kind.step(cell, 0)


@pytest.mark.parametrize("fault,where", [
    ("stale", "was kept at lnL"),
    ("half_scan", "the tree the lazy commits left was kept at lnL")])
def test_a_scan_that_scores_wrongly_fails_the_step(cell, fault, where):
    """`calibrate_search.py`'s faults under the step: uppass rows left
    stale are caught where a kept tree is evaluated in full (the lazy
    commits' tree or a recalled one, whichever the wrong scores reach
    first), candidates scored over half the sites where the lazy
    commits' tree is."""
    from benchmarks import calibrate_search
    kind = importlib.import_module("benchmarks.steps.search")
    undo = calibrate_search.PLANTS[fault]()
    try:
        with pytest.raises(RuntimeError, match=where):
            kind.step(cell, 0)
    finally:
        undo()
    kind.step(cell, 0)                      # sound again once taken back


def test_guard_refuses_candidates_scored_one_by_one(cell, monkeypatch):
    kind = importlib.import_module("benchmarks.steps.search")
    monkeypatch.setitem(cell.config, "rehearsed", False)
    monkeypatch.setenv("EXAML_BATCH_SCAN", "0")
    with pytest.raises(SystemExit, match="refused: the program would score "
                                         "SPR candidates one by one"):
        kind.prepare(cell, cell.params)


def test_guard_refuses_a_warm_up_that_moved_no_scan_dispatch(cell,
                                                             monkeypatch):
    from examl_tpu import obs
    kind = importlib.import_module("benchmarks.steps.search")
    monkeypatch.setattr(kind, "step", lambda cell, i: None)
    monkeypatch.setattr(cell, "before_warm", (
        obs.counter(kind.SCANS), obs.counter(kind.PASSES)))
    with pytest.raises(SystemExit, match="did not move search.scan_disp"):
        kind.warm(cell, 0)


# -- the plain reference's window ---------------------------------------------------


def _caterpillar(n: int):
    """Tips 1..n on a chain of inner nodes n+1..2n-2: tip 1 and 2 on the
    first, tip n-1 and n on the last, one tip on each between."""
    inner = list(range(n + 1, 2 * n - 1))
    edges = [(1, inner[0], 0.9), (2, inner[0], 0.9),
             (n, inner[-1], 0.9)]
    edges += [(a, b, 0.8) for a, b in zip(inner, inner[1:])]
    edges += [(t, inner[t - 2], 0.9) for t in range(3, n)]
    return edges


def test_window_on_a_caterpillar_by_hand():
    n = 8
    edges = _caterpillar(n)               # inner 9..14; tip t on t + 7
    # prune tip 5 at its inner node 12: 11 and 13 are joined
    got = reference_search.window(edges, 12, 5, 2, n)
    want = {(4, 11, 1), (10, 11, 1), (3, 10, 2), (9, 10, 2),
            (6, 13, 1), (14, 13, 1), (7, 14, 2), (8, 14, 2)}
    assert set(got) == want and len(got) == len(want)
    # radius 1 keeps the edges that touch the joined branch
    assert {e[:2] for e in reference_search.window(edges, 12, 5, 1, n)} \
        == {(4, 11), (10, 11), (6, 13), (14, 13)}
    # mintrav 2 (the second endpoint's rule) leaves them out
    assert {e[2] for e in reference_search.window(edges, 12, 5, 3, n, 2)} \
        == {2, 3}
    # a tip end of the joined branch opens no window on its side
    assert {w for _v, w, _d in reference_search.window(edges, 9, 1, 1, n)} \
        == {10}


def test_regraft_moves_the_node_and_keeps_every_other_branch():
    n = 8
    edges = _caterpillar(n)
    moved = reference_search.regraft(edges, 12, 5, 0.5, 3, 10, 0.7)
    assert len(moved) == len(edges) == 2 * n - 3
    as_map = {frozenset(e[:2]): e[2] for e in moved}
    assert as_map[frozenset((11, 13))] == 0.5          # the joined branch
    assert as_map[frozenset((12, 5))] == 0.7           # the subtree's
    half = np.sqrt(0.9)
    assert as_map[frozenset((12, 3))] == pytest.approx(half)
    assert as_map[frozenset((12, 10))] == pytest.approx(half)
    assert frozenset((3, 10)) not in as_map
    kept = {frozenset(e[:2]): e[2] for e in edges if 12 not in e[:2]
            and set(e[:2]) != {3, 10}}
    assert all(as_map[k] == z for k, z in kept.items())
    # the thorough arm's branches are taken as given
    given = reference_search.regraft(edges, 12, 5, 0.5, 3, 10, 0.7,
                                     0.2, 0.3)
    assert {frozenset(e[:2]): e[2] for e in given}[frozenset((12, 10))] \
        == 0.3


# -- the byte model and the roofline reader ---------------------------------------------


def test_scan_bytes_closed_form_by_hand():
    patterns, R, K, item = 131072, 4, 4, 4
    row = patterns * 16 * 4 + patterns * 4
    assert row == 8912896
    # one dispatch: 10 entries with 6 tip children, 5 candidates of which
    # 2 at a tip, the subtree an inner node
    got = bytemodel_search.scan_bytes(10, 6, 5, 2, 1, patterns, R, K, item)
    entries = (10 + 14) * row + 6 * patterns
    assert entries == bytemodel.bytes_per_traversal_counts(
        10, 6, patterns, R, K, item)
    assert got == entries + (2 * 5 + 1 - 2) * row + 2 * patterns


def _synthetic_run(calls, seconds, thorough_calls=0):
    c0 = {"search.scan_entries": 100, "search.scan_dispatches": 10}
    c1 = {"search.scan_dispatches": 10 + 8, "search.scan_entries": 100 + 80,
          "search.scan_tip_children": 48, "search.scan_candidates": 40,
          "search.scan_tip_operands": 16,
          "search.thorough_dispatches": 2, "search.thorough_entries": 20,
          "search.thorough_tip_children": 12,
          "search.thorough_candidates": 10,
          "search.thorough_tip_operands": 4}
    with open(os.path.join(BENCH, "configs", "dna49x131k.json")) as f:
        config = json.load(f)
    return {"counters0": c0, "counters1": c1, "config": config,
            "peak": {"hbm_bytes_per_s": 819e9},
            "trace": {"families": {
                "spr_scan": {"calls": calls, "seconds": seconds},
                "spr_thorough": {"calls": thorough_calls,
                                 "seconds": seconds}}}}


def test_scan_roofline_reader_on_a_synthetic_run():
    reader = importlib.import_module("benchmarks.readers.scan_roofline")
    with open(os.path.join(BENCH, "layers", "spr_scan_roofline.json")) as f:
        lazy = json.load(f)
    with open(os.path.join(BENCH, "layers",
                           "spr_thorough_roofline.json")) as f:
        thorough = json.load(f)
    assert (lazy["arm"], thorough["arm"]) == ("lazy", "thorough")
    run = _synthetic_run(calls=3, seconds=0.3, thorough_calls=1)
    # the window's lazy dispatches: 6, carrying 60 entries, 36 tip
    # children, 30 candidates, 12 tip operands: 10 / 6 / 5 / 2 a dispatch
    floor = bytemodel_search.scan_bytes(
        10, 6, 5, 2, 1, 131072, 4, 4, 4) / 819e9
    assert reader.read(run, lazy) == pytest.approx(
        100.0 * 3 * floor / 0.3)
    assert reader.read(run, thorough) == pytest.approx(
        100.0 * 1 * floor / 0.3)
    # nothing to read: no trace, a family with no call, a program
    # without the counters (the parent), an arm with no dispatch
    assert reader.read({**run, "trace": None}, lazy) is None
    assert reader.read(_synthetic_run(0, 0.0), lazy) is None
    assert reader.read({**run, "counters1": {}}, lazy) is None
    same = {**run, "counters0": dict(run["counters1"])}
    assert reader.read(same, lazy) is None
