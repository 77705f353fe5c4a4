"""The harness reads a cell's partitions, models and flags from its
configuration (ISSUE 37; CPU, tier-1).

What is held here: every cell's problem and two seeds' column orders
are the bytes `benchmarks/pins/<cell>.json` holds (ISSUE 38: a file a
cell, so a later PR pins its cell by adding one; for the cells accepted
before `parts` existed, sha256 taken from that parent commit, 36ead58);
`state_key` of a
one-part state is the parent's formula; a configuration in several
parts gives each part exactly its distinct columns and `--seed` keeps
every column in its part; the sum-of-parts reference against one
`evaluate` and against finite differences under two branch-length
classes; the fixture (`fixture/manifest.json`: 12 taxa, three `LGF`
protein parts of 100 / 90 / 66 patterns, linked and under `-M`) through
`run.py --rehearse --manifest`; the faults only a partitioned cell can
show come out not correct by the number named for them.  No number of
this file is a device number.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
FIXTURE = "tests/benchmarks/fixture/manifest.json"
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import datagen, reference  # noqa: E402
from benchmarks import run as bench  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

# a deployment that waits for a public source (PERF.md section 7): no
# cell of BENCHMARK.json, pinned beside its own manifest all the same
DRAFT = "benchmarks/drafts/manifest.json"
with open(os.path.join(REPO, DRAFT)) as _f:
    DRAFTED = [w["name"] for w in json.load(_f)["workloads"]]
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PINNED = [(cell, "BENCHMARK.json") for cell in CELLS] + [
    (cell, DRAFT) for cell in DRAFTED]


def _width(cell: str, manifest_file: str) -> int:
    return bench.stated(bench.find_cell(cell, manifest_file)[2])["patterns"]


# the cells pinned at full size too: the 16,384-wide ones (wider, the
# problem takes minutes to make)
FULL = [(cell, mf) for cell, mf in PINNED if _width(cell, mf) <= 16384]


def _digests(cell: str, full: bool, manifest_file: str = "BENCHMARK.json"):
    """sha256 of (problem, present(seed 7), present(seed 2**31 + 11)):
    patterns, trees, and every part's model in part order."""
    _, _, config, traffic = bench.find_cell(cell, manifest_file)
    if not full:
        config = {**config, **config["rehearse"]}
    prob = datagen.problem(
        config, traffic.get("trees", 0), traffic.get("spr_moves", 0),
        traffic.get("branch_lengths") == "generating")
    h = hashlib.sha256(np.ascontiguousarray(prob["patterns"]).tobytes())
    for text in [prob["tree"], *prob["moved_trees"]]:
        h.update(text.encode())
    for m in prob["models"]:
        h.update(np.asarray(m["rates"], dtype=np.float64).tobytes())
        h.update(np.asarray(m["freqs"], dtype=np.float64).tobytes())
        h.update(np.float64(m["alpha"]).tobytes())
    ends = np.cumsum([p["patterns"]
                      for p in datagen.parts_of(config)]).tolist()
    assert prob["bounds"] == list(zip([0, *ends[:-1]], ends))
    return [h.hexdigest(), *(
        hashlib.sha256(datagen.present(prob, seed)["patterns"].tobytes())
        .hexdigest() for seed in (7, 2**31 + 11))]


def _pin(cell: str, size: str, manifest_file: str = "BENCHMARK.json"):
    """The digests `pins/<cell>.json` holds (beside the manifest first,
    as a cell's traffic and `correct/` files): `rehearse`, and `full`
    where the cell is 16,384 wide.  The six cells accepted before ISSUE
    38 hold the strings this file held, taken from 36ead58's datagen.  A
    cell without the file, or without the size, fails here with the
    file's name: a later PR pins its cell by ADDING that file."""
    path = bench.cell_file(manifest_file, "pins", cell)
    if not os.path.isfile(path):
        pytest.fail(
            f"cell {cell} is not pinned: missing "
            f"{os.path.relpath(path, REPO)}; this tree's digests are "
            + json.dumps({"rehearse": _digests(cell, False, manifest_file)}))
    with open(path) as f:
        pin = json.load(f)
    if size not in pin:
        pytest.fail(f"{os.path.relpath(path, REPO)} holds no {size!r} "
                    f"digests for cell {cell}")
    return pin[size]


@pytest.mark.parametrize("cell,mf", PINNED)
def test_accepted_problem_and_column_orders_are_the_parents(cell, mf):
    """Another problem is another benchmark (another `data_seed` moved
    cell 2 from 39 to 147 s a step): one part draws the rng as before,
    and a cell's problem is what it was when the cell was accepted."""
    assert _digests(cell, False, mf) == _pin(cell, "rehearse", mf)


@pytest.mark.slow
@pytest.mark.parametrize("cell,mf", FULL)
def test_accepted_problem_at_full_size_is_the_parents(cell, mf):
    assert _digests(cell, True, mf) == _pin(cell, "full", mf)


@pytest.mark.parametrize("cell,mf", PINNED)
def test_every_accepted_cell_is_pinned(cell, mf):
    """By a file of its own, well formed: three sha256 a size, `full`
    exactly where the cell is 16,384 wide."""
    with open(bench.cell_file(mf, "pins", cell)) as f:
        pin = json.load(f)
    assert set(pin) == {"rehearse"} | ({"full"} if (cell, mf) in FULL
                                       else set())
    for digests in pin.values():
        assert len(digests) == 3
        assert all(len(d) == 64 and set(d) <= set("0123456789abcdef")
                   for d in digests)


def test_a_ninth_cell_is_pinned_by_adding_files_and_editing_none(tmp_path):
    """A manifest with one more workload (a traffic file of its own over
    an accepted configuration): without `pins/<cell>.json` the pin test
    fails and names the file; with it, written from the digests the
    message gives, it holds; the accepted cells' pins are still found."""
    cell, mf = "dna140x16k.treeset2", str(tmp_path / "BENCHMARK.json")
    manifest = {**MANIFEST, "workloads": MANIFEST["workloads"] + [
        {"name": cell, "config": "dna140x16k", "traffic": "treeset2",
         "chips": 1, "why": "a test's: two trees without branch lengths"}]}
    with open(mf, "w") as f:
        json.dump(manifest, f)
    os.mkdir(tmp_path / "traffic")
    with open(tmp_path / "traffic" / "treeset2.json", "w") as f:
        json.dump({"kind": "treeset", "trees": 2, "spr_moves": 3,
                   "check_states": 1}, f)
    with pytest.raises(pytest.fail.Exception,
                       match=r"pins/dna140x16k\.treeset2\.json") as exc:
        _pin(cell, "rehearse", mf)
    given = json.loads(str(exc.value).split("digests are ")[1])
    os.mkdir(tmp_path / "pins")
    with open(tmp_path / "pins" / (cell + ".json"), "w") as f:
        json.dump(given, f)
    assert _digests(cell, False, mf) == _pin(cell, "rehearse", mf)
    # two trees and no lengths: another problem than any accepted cell's,
    # under cell 1's column orders
    mine, accepted = given["rehearse"], _pin("dna140x16k.modopt", "rehearse",
                                             mf)
    assert mine[0] != accepted[0] and mine[1:] == accepted[1:]
    with pytest.raises(pytest.fail.Exception, match="holds no 'full'"):
        _pin(cell, "full", mf)
    # and it reads the metrics its kind of cell reads, named in no list:
    # both rooflines by its `chips`, and in a rehearsed traced run's line
    # PR 28's seven, the gradient slots and the compiled programs
    names = {m["name"] for m in bench.metrics_of(manifest, "per_layer",
                                                 cell)}
    assert names == {m["name"] for m in bench.metrics_of(
        MANIFEST, "per_layer", "dna140x16k.treeset")}
    assert {"traverse_roofline", "gradient_roofline"} <= names
    os.mkdir(tmp_path / "correct")
    with open(os.path.join(BENCH, "correct", "dna140x16k.treeset.json")) as f:
        (tmp_path / "correct" / (cell + ".json")).write_text(f.read())
    proc, lines = _py("run.py", ["--workload", cell, "--seed", "38",
                                 "--seconds", "1", "--trace", "1",
                                 "--rehearse"], manifest=mf)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is True and rec["steps"] % 2 == 0
    assert {"stage_ms", "staged_arrays_per_step", "launch_ms", "wait_ms",
            "set_models_ms", "opt_control_ms", "trav_evals_per_step",
            "grad_slots_per_step", "compiled_programs"} <= set(
        rec["metrics"])


def test_state_key_of_one_part_and_one_class_is_the_parents_formula():
    rng = np.random.default_rng(3)
    edges = np.column_stack([rng.integers(1, 22, (21, 2)),
                             rng.random(21)]).astype(np.float64)
    model = {"rates": rng.random(6), "freqs": rng.dirichlet(np.ones(4)),
             "alpha": 0.61}
    lnl = -12345.678
    h = hashlib.sha1(np.float64(lnl).tobytes())       # run.py at 36ead58
    for arr in (edges, model["rates"], model["freqs"]):
        h.update(arr.tobytes())
    h.update(np.float64(model["alpha"]).tobytes())
    state = {"edges": edges, "models": [model],
             "part_lnl": np.array([lnl])}
    assert bench.state_key(lnl, state) == h.hexdigest()
    # a second model or class is another state
    two = {**state, "models": [model, {**model, "alpha": 0.62}]}
    wide = {**state, "edges": np.column_stack([edges, edges[:, 2]])}
    assert len({bench.state_key(lnl, s) for s in (state, two, wide)}) == 3


# -- a configuration in several parts ------------------------------------------


def _fixture_config(name="aa12p3"):
    with open(os.path.join(REPO, "tests", "benchmarks", "fixture", "configs",
                           name + ".json")) as f:
        return bench.stated(json.load(f))


def test_parts_state_the_sum_and_one_part_is_made_of_todays_keys():
    config = _fixture_config()
    assert (config["patterns"], config["partitions"]) == (256, 3)
    assert [p["patterns"] for p in datagen.parts_of(config)] == [100, 90, 66]
    with open(os.path.join(BENCH, "configs", "aa140x16k.json")) as f:
        aa = json.load(f)
    assert bench.stated(aa) is aa
    (one,) = datagen.parts_of(aa)
    assert one["patterns"] == 16384 and one["exchangeabilities"] == "LG"
    assert one["generating"] is aa["generating"]
    with open(os.path.join(BENCH, "configs", "dna140x16k.json")) as f:
        (dna,) = datagen.parts_of(json.load(f))
    assert "exchangeabilities" not in dna and dna["patterns"] == 16384


def test_fixture_problem_each_part_exactly_its_distinct_columns():
    config = _fixture_config()
    prob = datagen.problem(config, trees=2, spr_moves=3,
                           branch_lengths=True)
    again = datagen.problem(config, trees=2, spr_moves=3,
                            branch_lengths=True)
    assert np.array_equal(prob["patterns"], again["patterns"])
    assert prob["bounds"] == [(0, 100), (100, 190), (190, 256)]
    assert prob["patterns"].shape == (12, 256) and len(prob["models"]) == 3
    for (s, e), m, part in zip(prob["bounds"], prob["models"],
                               config["parts"]):
        cols = prob["patterns"][:, s:e]
        assert len({c.tobytes() for c in cols.T}) == e - s
        assert m["alpha"] == part["generating"]["alpha"]
        assert m["freqs"].sum() == pytest.approx(1.0)
    # a model a part, drawn from the seed one after the other
    assert not np.array_equal(prob["models"][0]["rates"],
                              prob["models"][1]["rates"])
    # the part's `rate` stretches every branch: the fast gene differs
    # between taxa at more sites than the slow one
    differ = [np.mean(prob["patterns"][0, s:e] != prob["patterns"][1, s:e])
              for s, e in prob["bounds"]]
    assert differ[0] < differ[2]


def test_the_seed_keeps_every_column_in_its_part():
    prob = datagen.problem(_fixture_config(), trees=0, spr_moves=0)
    a = datagen.present(prob, 2**31 + 11)
    b = datagen.present(prob, 2**31 + 12)
    assert not np.array_equal(a["patterns"], b["patterns"])
    cols = lambda m: sorted(c.tobytes() for c in m.T)        # noqa: E731
    for s, e in prob["bounds"]:
        assert cols(a["patterns"][:, s:e]) == cols(b["patterns"][:, s:e]) \
            == cols(prob["patterns"][:, s:e])
        assert not np.array_equal(a["patterns"][:, s:e],
                                  prob["patterns"][:, s:e])
    assert a["bounds"] == prob["bounds"] and a["tree"] == prob["tree"]


def test_two_seeds_load_one_bytefile_with_every_part_at_its_width(
        tmp_path, monkeypatch):
    """The partition file `make_inputs` writes from `parts`, through the
    program's parser and loader: widths a part, one byteFile a cell
    whatever the seed, and the frequencies the reference will hold the
    program's against."""
    pytest.importorskip("examl_tpu.cli.parse")
    from examl_tpu.cli import main as cli
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    config = _fixture_config()
    traffic = {"trees": 0}
    loaded = []
    for seed in (5, 2**31 + 6):
        gen, bytefile, workdir = bench.make_inputs(
            config, traffic, "test-aa12p3-parts", seed)
        data = cli._load_alignment(bytefile)
        assert [p.width for p in data.partitions] == [100, 90, 66]
        assert [p.name for p in data.partitions] == ["gene1", "gene2",
                                                     "gene3"]
        for part, (s, e) in zip(data.partitions, gen["bounds"]):
            assert part.model_name == "LG" and part.use_empirical_freqs
            assert part.empirical_freqs == pytest.approx(
                reference.empirical_freqs(gen["patterns"][:, s:e], None, 20),
                abs=1e-12)
        loaded.append([p.patterns for p in data.partitions])
        assert not os.path.exists(os.path.join(workdir, "aln.phy"))
    for x, y in zip(*loaded):
        assert np.array_equal(x, y)
    # the cached problem loads to what was made
    cached = bench.make_inputs(config, traffic, "test-aa12p3-parts", 5)[0]
    fresh = datagen.present(datagen.problem(config, 0, 0), 5)
    assert np.array_equal(cached["patterns"], fresh["patterns"])
    assert cached["bounds"] == fresh["bounds"]
    for m, n in zip(cached["models"], fresh["models"]):
        assert np.array_equal(m["rates"], n["rates"]) \
            and m["alpha"] == n["alpha"]


def test_a_problem_cached_before_parts_existed_is_made_again(tmp_path,
                                                             monkeypatch):
    toy = {"taxa": 9, "patterns": 96, "datatype": "DNA", "data_seed": 3,
           "parse": {"model": "DNA"},
           "generating": {"rates": [1.2, 3.1, 0.9, 1.1, 3.4, 1.0],
                          "freqs": [0.30, 0.21, 0.24, 0.25], "alpha": 0.7}}
    prob = datagen.problem(toy, 1, 2)
    (model,) = prob["models"]
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    np.savez(tmp_path / "problem-old.npz", patterns=prob["patterns"],
             tree=prob["tree"],
             moved_trees=np.array(prob["moved_trees"], dtype=str), **model)
    pytest.importorskip("examl_tpu.cli.parse")
    gen, _bytefile, _wd = bench.make_inputs(toy, {"trees": 1,
                                                  "spr_moves": 2}, "old", 4)
    assert gen["bounds"] == [(0, 96)] and len(gen["models"]) == 1
    assert np.array_equal(gen["models"][0]["rates"], model["rates"])
    assert np.array_equal(gen["patterns"],
                          datagen.present(prob, 4)["patterns"])
    assert sorted(os.listdir(tmp_path)) == [
        "old-4", "problem-old.npz", "problem-old.parts.npz"]


# -- the reference over parts ----------------------------------------------------


def _toy_tree(rng, ntaxa=8):
    adj, lengths = datagen.random_tree(rng, ntaxa)
    return adj, lengths, [(a + 1, b + 1, float(np.exp(-t)))
                          for (a, b), t in lengths.items()]


def test_sum_of_parts_equals_one_evaluate_when_every_part_has_one_model():
    rng = np.random.default_rng(11)
    adj, lengths, edges = _toy_tree(rng)
    rates = np.array([1.2, 3.1, 0.9, 1.1, 3.4, 1.0])
    freqs = np.array([0.30, 0.21, 0.24, 0.25])
    mat = datagen.evolve(rng, adj, lengths, 8, 150, rates, freqs, 0.6)
    whole, w1, w2 = reference.evaluate(mat, None, edges, 8, rates, freqs,
                                       0.6)
    bounds = [(0, 40), (40, 41), (41, 150)]
    total, parts, d1, d2 = reference.evaluate_parts(
        mat, bounds, edges, 8, [(rates, freqs, 0.6)] * 3)
    assert total == pytest.approx(whole, rel=1e-13)
    assert sum(parts) == pytest.approx(whole, rel=1e-13) and len(parts) == 3
    assert d1.shape == d2.shape == (1, len(edges))
    assert d1[0] == pytest.approx(w1, rel=1e-10, abs=1e-10)
    assert d2[0] == pytest.approx(w2, rel=1e-10, abs=1e-10)
    # one part is one call: the same digits
    one, (only,), o1, _ = reference.evaluate_parts(
        mat, [(0, 150)], edges, 8, [(rates, freqs, 0.6)])
    assert one == whole == only and np.array_equal(o1[0], w1)
    # a model on the wrong part: the parts tell, whatever the total does
    other = (rates[::-1].copy(), freqs, 1.4)
    _, swapped, _, _ = reference.evaluate_parts(
        mat, bounds, edges, 8, [other, (rates, freqs, 0.6), other])
    assert abs(swapped[0] - parts[0]) > 1e-3 * abs(parts[0])
    assert swapped[1] == parts[1]


def test_parts_derivatives_against_finite_differences_under_two_classes():
    """`-M`: part k reads z of class k, and a class's d1, d2 are its own
    part's alone."""
    rng = np.random.default_rng(12)
    adj, lengths, edges = _toy_tree(rng)
    models = [(np.array([1.2, 3.1, 0.9, 1.1, 3.4, 1.0]),
               np.array([0.30, 0.21, 0.24, 0.25]), 0.5),
              (np.array([0.7, 2.0, 1.3, 0.8, 2.9, 1.0]),
               np.array([0.22, 0.28, 0.27, 0.23]), 1.3)]
    mats = [datagen.evolve(rng, adj, {e: t * r for e, t in lengths.items()},
                           8, w, *m)
            for w, r, m in zip((90, 70), (0.6, 1.7), models)]
    mat = np.concatenate(mats, axis=1)
    bounds = [(0, 90), (90, 160)]
    two = [(a, b, z ** 0.6, z ** 1.7) for a, b, z in edges]
    lnl, parts, d1, d2 = reference.evaluate_parts(mat, bounds, two, 8,
                                                  models)
    assert d1.shape == (2, len(edges)) and lnl == sum(parts)

    def at(ei, c, lz):
        e = [list(row) for row in two]
        e[ei][2 + c] = float(np.exp(lz))
        return reference.evaluate_parts(mat, bounds, [tuple(r) for r in e],
                                        8, models, want_derivs=False)

    h = 1e-4
    for ei, c in ((0, 0), (5, 1), (len(edges) - 1, 0), (3, 1)):
        lz = np.log(two[ei][2 + c])
        (fp, pp, _, _), (f0, _, _, _), (fm, pm, _, _) = (
            at(ei, c, lz + h), at(ei, c, lz), at(ei, c, lz - h))
        assert d1[c, ei] == pytest.approx((fp - fm) / (2 * h), rel=1e-5)
        assert d2[c, ei] == pytest.approx((fp - 2 * f0 + fm) / h ** 2,
                                          rel=1e-3)
        assert pp[1 - c] == pm[1 - c]     # the other class's part is deaf
    # a class left at its default lengths shows in that class alone
    flat = [(a, b, z, 0.9) for a, b, z, _ in two]
    _, _, f1, f2 = reference.evaluate_parts(mat, bounds, flat, 8, models)
    assert np.array_equal(f1[0], d1[0]) and np.array_equal(f2[0], d2[0])
    assert not np.allclose(f1[1], d1[1], rtol=1e-3)


# -- the fixture through run.py --rehearse ----------------------------------------


def _py(script, args, timeout=600, manifest=FIXTURE):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE",
                        "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--manifest", manifest,
         *args], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


@pytest.mark.parametrize("cell,classes", [("aa12p3.modopt", 1),
                                          ("aa12p3M.modopt", 3),
                                          ("aa12p3.treeset4_bl", 1)])
def test_rehearsed_fixture_run_ends_in_the_contracts_line(cell, classes):
    proc, lines = _py("run.py", ["--workload", cell, "--seed",
                                 str(2**31 + 7), "--seconds", "1",
                                 "--trace", "0", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert list(rec)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(rec)[-1] == "check" and rec["rehearse"] is True
    assert rec["correct"] is True and rec["failed"] == 0
    assert set(rec["metrics"]) == {"step_s", "setup_s"}
    # 256 patterns in three parts, each padded to whole blocks of 128
    assert rec["padding_share"] == pytest.approx(1 - 256 / 384)
    for name, (value, limit) in rec["check"].items():
        assert value <= limit
        assert f"check {name} " in proc.stderr
    assert proc.stderr.rstrip().splitlines()[-1].startswith("check ")
    # the reference's line gives the total's error and the largest part's
    assert "(total " in proc.stderr and "largest part " in proc.stderr
    assert "256 patterns in 384 lanes" in proc.stderr


def test_the_fixtures_flags_reach_the_engine(tmp_path, monkeypatch):
    """`cli_args: ["-M"]` is the only difference between the fixture's
    two configurations, and `capture` reads a z a class."""
    pytest.importorskip("examl_tpu.cli.parse")
    monkeypatch.setattr(bench, "CACHE", str(tmp_path))
    seen = {}
    for name in ("aa12p3", "aa12p3M"):
        config = _fixture_config(name)
        gen, bytefile, _wd = bench.make_inputs(config, {"trees": 0},
                                               f"test-{name}", 9)
        inst, data = bench.build_instance(bytefile, config["cli_args"])
        tree = inst.tree_from_newick(gen["tree"])
        inst.evaluate(tree, full=True)
        st = bench.capture(tree, inst)
        seen[name] = st
        assert len(st["models"]) == 3 and st["part_lnl"].shape == (3,)
        assert np.isfinite(st["part_lnl"]).all()
        assert st["part_lnl"].sum() == pytest.approx(inst.likelihood)
    assert seen["aa12p3"]["edges"].shape == (21, 3)
    assert seen["aa12p3M"]["edges"].shape == (21, 5)


@pytest.mark.parametrize("fault,number", [
    ("swap_parts", "lnl_rel_err"), ("part_freqs", "model_table_err"),
    ("unchanged", "newton_dz_max")])
def test_faults_of_a_partitioned_cell_come_out_not_correct(fault, number):
    proc, lines = _py("calibrate.py", [
        "--workload", "aa12p3.modopt", "--seeds", "23", "--seconds", "1",
        "--rehearse", "--fault", fault])
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(lines[-1])
    assert rec["correct"] is False
    value, limit = rec["check"][number]
    assert value > limit
    if fault == "swap_parts":
        # the widest two parts each read the other's model: the largest
        # part's error stands beside the total's, and is the larger
        ref = next(ln for ln in proc.stderr.splitlines()
                   if ln.startswith("reference: "))
        total = float(ref.split("(total ")[1].split(",")[0])
        part = float(ref.split("largest part ")[1].split()[1].rstrip(")"))
        assert part == pytest.approx(value, rel=1e-3) and part > total > limit
        assert ref.split("largest part ")[1].split()[0] in ("0", "1")
    if fault == "part_freqs":
        # the other two parts' frequencies are sound: `freqs` on one part
        assert rec["check"]["model_table_err"][0] > 1e-4
