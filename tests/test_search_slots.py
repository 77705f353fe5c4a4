"""The SPR cycle a slot at a time (PR 35): `tree_optimize_rapid` and
`compute_big_rapid` as compositions of `spr_cycle_head`, `spr_slot` and
`rescore_best`.

Float64, CPU, `EXAML_BATCH_SCAN=1` / `EXAML_BATCH_THOROUGH=1` (the arms
an accelerator runs).  The refactored cycle leaves the topology, lnL and
`ctx` the unrefactored loop left: the values below were printed by the
parent commit (c66bfc9) on the same fixture.  `-f d` through `cli.main`
on a 12-taxon case (the CPU's default sequential arms) gives the
parent's tree and lnL.  The lazy scan's candidate set and lnLs, and the
thorough arm's lnL at its branch triplets with the Newton step still
left on them, are held against the benchmark's plain reference
(`benchmarks/reference_search.py`): relative 1e-9 on lnL, f64 on both
sides with another order of summation and `expm` against the eigen
decomposition.
"""

import inspect
import re

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data
from examl_tpu.optimize.branch import tree_evaluate
from examl_tpu.search import batchscan, raxml_search, spr
from examl_tpu.search.snapshots import BestList, InfoList

from benchmarks import datagen, reference, reference_search
from tests.conftest import correlated_dna

# What the parent commit (c66bfc9) printed on this file's fixtures:
# `lazy` / `thorough`: tree_optimize_rapid(inst, tree, ctx, 1, 5, bt,
# None, ilist) twice on correlated_dna(12, 300), random_tree(seed=5),
# tree_evaluate(1.0), ctx.thorough False / True, both batched arms on;
# `cli`: cli.main -f d -i 5 on correlated_dna(12, 200, seed=7) from
# random_tree(seed=3), the last row of ExaML_log and ExaML_result.
PARENT = {'lazy': {'lnl': -2135.638515872248,
          'lnl2': -2043.765214620577,
          'lh_cutoff': 2.954045807926086,
          'lh_avg': 86452.34238373711,
          'lh_dec': 491,
          'cutoff2': 176.074017074821,
          'best_of_node': -2185.9868232799613,
          'ilist_valid': 22,
          'bt': [20, -2135.638515872248, -2452.8303366036184],
          'newick': '(t0:0.104392,t1:0.008378,((t2:0.094740,t3:0.009328):0.000001,((t5:0.000001,(t6:0.000001,((t9:0.000001,(t10:0.000001,t11:0.141247):0.317193):0.160980,(t8:0.000001,t7:0.193213):0.000001):0.386337):0.163847):0.163847,t4:0.015448):0.163847):0.266911);',
          'newick2': '(t0:0.104392,t1:0.008378,(t2:0.047370,(t3:0.009328,((t5:0.000001,((t7:0.000001,((t9:0.000001,(t10:0.000001,t11:0.141247):0.317193):0.160980,t8:0.000001):0.386337):0.081923,t6:0.000001):0.081923):0.163847,t4:0.015448):0.174939):0.047370):0.266911);'},
 'thorough': {'lnl': -2200.6055938297977,
              'lnl2': -2035.1968025481365,
              'lh_cutoff': 2.954045807926086,
              'lh_avg': 33126.45758190242,
              'lh_dec': 259,
              'cutoff2': 127.90138062510586,
              'best_of_node': -2261.0453072712958,
              'ilist_valid': 0,
              'bt': [20, -2200.6055938297977, -2913.1717918325953],
              'newick': '(t0:0.116758,t1:0.008322,(t2:0.011969,((((t5:0.000001,(t6:0.000001,t7:0.131363):0.113695):0.099722,t4:0.000001):0.000001,((t8:0.000001,t9:0.133220):0.000001,(t10:0.000001,t11:0.120890):0.268463):0.644900):0.164331,t3:0.000001):0.076942):0.126129);',
              'newick2': '(t0:0.116758,t1:0.008322,(t2:0.011969,((t4:0.000001,(t5:0.000001,((((t9:0.000001,(t10:0.000001,t11:0.120890):0.268463):0.156652,t8:0.000001):0.154802,t7:0.144366):0.293163,t6:0.000001):0.110965):0.102689):0.168098,t3:0.000001):0.076942):0.126129);'},
 'cli': {'result': '(t0:0.067240,t1:0.000668,(((t4:0.000001,(t5:0.002668,((t7:0.000989,(t8:0.000001,(t9:0.000001,(t10:0.000861,t11:0.153591):0.095279):0.137521):0.136526):0.112695,t6:0.000001):0.116680):0.122929):0.112430,t3:0.000586):0.129816,t2:0.005937):0.153875);',
         'lnl': -1322.922741}}


# what a scan dispatch carries (batchscan._count_dispatch): both arms'
# counter, the thorough arm's share of it
CARRIED = {
    "dispatches": ("search.scan_dispatches", "search.thorough_dispatches"),
    "candidates": ("search.scan_candidates", "search.thorough_candidates"),
    "entries": ("search.scan_entries", "search.thorough_entries"),
    "tip_children": ("search.scan_tip_children",
                     "search.thorough_tip_children"),
    "tip_operands": ("search.scan_tip_operands",
                     "search.thorough_tip_operands")}


@pytest.fixture()
def batched(monkeypatch):
    monkeypatch.setenv("EXAML_BATCH_SCAN", "1")
    monkeypatch.setenv("EXAML_BATCH_THOROUGH", "1")


def _cycle(thorough: bool):
    data = correlated_dna(12, 300)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=5)
    tree_evaluate(inst, tree, 1.0)
    ctx = spr.SprContext(inst, thorough=thorough)
    bt, ilist = BestList(20), InfoList(50)
    lnl = raxml_search.tree_optimize_rapid(inst, tree, ctx, 1, 5, bt, None,
                                           ilist)
    first = {"lnl": lnl, "likelihood": inst.likelihood,
             "newick": tree.to_newick(data.taxon_names),
             "lh_cutoff": ctx.lh_cutoff, "lh_avg": ctx.lh_avg,
             "lh_dec": ctx.lh_dec, "best_of_node": ctx.best_of_node,
             "start_lh": ctx.start_lh, "end_lh": ctx.end_lh,
             "it_count": ctx.it_count, "thorough": ctx.thorough,
             "ilist_valid": ilist.valid,
             "bt": (bt.nvalid, bt.entries[0].likelihood,
                    bt.entries[-1].likelihood)}
    lnl2 = raxml_search.tree_optimize_rapid(inst, tree, ctx, 1, 5, bt, None,
                                            ilist)
    return {**first, "lnl2": lnl2, "cutoff2": ctx.lh_cutoff,
            "newick2": tree.to_newick(data.taxon_names)}


LENGTH = re.compile(r":([0-9.eE+-]+)")


def _same_tree(got: str, want: str) -> None:
    """The same Newick string up to the sixth decimal of a length."""
    assert LENGTH.sub("", got) == LENGTH.sub("", want)
    np.testing.assert_allclose(
        [float(x) for x in LENGTH.findall(got)],
        [float(x) for x in LENGTH.findall(want)], rtol=0, atol=2e-6)


@pytest.mark.parametrize("arm", ["lazy", "thorough"])
def test_refactored_cycle_leaves_what_the_parents_loop_left(arm, batched,
                                                            monkeypatch):
    calls = {"spr_cycle_head": 0, "spr_slot": 0}
    for name in calls:
        real = getattr(raxml_search, name)

        def counted(*a, _real=real, _name=name, **k):
            calls[_name] += 1
            return _real(*a, **k)
        monkeypatch.setattr(raxml_search, name, counted)
    slots0 = obs.counter("search.spr_slots")
    moves0 = obs.counter("search.moves_committed")
    scans0 = {n: (obs.counter(a), obs.counter(b))
              for n, (a, b) in CARRIED.items()}
    got, want = _cycle(arm == "thorough"), PARENT[arm]
    for key in ("lnl", "lnl2", "lh_cutoff", "lh_avg", "cutoff2",
                "best_of_node"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["likelihood"] == got["start_lh"] == got["end_lh"] \
        == got["lnl"]
    assert (got["lh_dec"], got["it_count"], got["thorough"]) == (
        want["lh_dec"], 1, arm == "thorough")
    assert got["ilist_valid"] == want["ilist_valid"]
    assert got["bt"][0] == want["bt"][0]
    assert got["bt"][1:] == pytest.approx(want["bt"][1:], rel=1e-9)
    _same_tree(got["newick"], want["newick"])
    _same_tree(got["newick2"], want["newick2"])
    # the cycle is the composition: one head a cycle, one body a slot
    # (22 slots of a 12-taxon tree, and the lazy cycle's re-pass)
    assert calls["spr_cycle_head"] == 2
    assert calls["spr_slot"] >= 2 * 22
    assert 0 < obs.counter("search.spr_slots") - slots0 <= calls["spr_slot"]
    assert obs.counter("search.moves_committed") > moves0
    # what the scan dispatches carried: both arms under `search.scan_*`,
    # the thorough arm's share under `search.thorough_*` (all of it in a
    # thorough cycle, the re-pass's in a lazy one)
    both = {n: obs.counter(a) - scans0[n][0]
            for n, (a, _b) in CARRIED.items()}
    own = {n: obs.counter(b) - scans0[n][1]
           for n, (_a, b) in CARRIED.items()}
    assert own["dispatches"] > 0 and own["candidates"] > 0
    if arm == "thorough":
        assert own == both
    else:
        assert all(0 < own[n] < both[n] for n in CARRIED)
    assert both["candidates"] > both["dispatches"]
    assert both["tip_children"] < 2 * both["entries"]
    assert both["tip_operands"] <= both["candidates"] + both["dispatches"]


def test_compute_big_rapid_and_the_step_kind_call_the_same_functions():
    big = inspect.getsource(raxml_search.compute_big_rapid)
    assert big.count("rescore_best(") == 2          # fast and slow loops
    assert "bt.recall(" not in big
    cycle = inspect.getsource(raxml_search._tree_optimize_rapid)
    assert "spr_cycle_head(" in cycle and cycle.count("spr_slot(") == 2
    import benchmarks.steps.search as kind
    step = inspect.getsource(kind.step)
    for name in ("spr_cycle_head(", "spr_slot(", "rescore_best("):
        assert name in step, name


def test_cli_search_gives_the_parents_tree_and_lnl(tmp_path):
    """`-f d` through `cli.main` on a 12-taxon case, the CPU's default
    (sequential) arms: the parent's result to the last digit it
    printed."""
    from examl_tpu.cli.main import main as run_main
    from examl_tpu.io.bytefile import write_bytefile
    data = correlated_dna(12, 200, seed=7)
    write_bytefile(str(tmp_path / "a.binary"), data)
    start = PhyloInstance(data).random_tree(seed=3)
    (tmp_path / "start.nwk").write_text(start.to_newick(data.taxon_names))
    rc = run_main(["-s", str(tmp_path / "a.binary"), "-n", "PIN",
                   "-t", str(tmp_path / "start.nwk"), "-f", "d", "-i", "5",
                   "-w", str(tmp_path)])
    assert rc == 0
    last = (tmp_path / "ExaML_log.PIN").read_text().splitlines()[-1]
    assert float(last.split()[1]) == pytest.approx(PARENT["cli"]["lnl"],
                                                   abs=2e-6)
    _same_tree((tmp_path / "ExaML_result.PIN").read_text().strip(),
               PARENT["cli"]["result"])


# -- the scan programs against the benchmark's plain reference -----------------------


@pytest.fixture(scope="module")
def pruned():
    """A 14-taxon alignment evolved by the benchmark's generator (state
    codes, no ambiguity), the program's instance on it, and one slot
    pruned: what both scan programs are then asked."""
    config = {"taxa": 14, "patterns": 300, "data_seed": 35,
              "datatype": "DNA",
              "generating": {"rates": [1.2, 3.1, 0.9, 1.1, 3.4, 1.0],
                             "freqs": [0.3, 0.21, 0.24, 0.25],
                             "alpha": 0.7}}
    prob = datagen.problem(config, 1, 6, True)
    names = [f"t{i + 1}" for i in range(14)]
    seqs = ["".join("ACGT"[c] for c in row) for row in prob["patterns"]]
    inst = PhyloInstance(build_alignment_data(names, seqs))
    tree = inst.tree_from_newick(prob["moved_trees"][0])
    inst.evaluate(tree, full=True)
    tree_evaluate(inst, tree, 1.0)
    p = next(s for s in spr.dfs_slot_order(tree)
             if not tree.is_tip(s.number)
             and not tree.is_tip(s.next.back.number)
             and not tree.is_tip(s.next.next.back.number))
    edges = [(a.number, b.number, float(a.z[0]))
             for a, b in tree.all_branches()]
    p1, p2 = p.next.back, p.next.next.back
    ctx = spr.SprContext(inst)
    spr.remove_node(inst, tree, ctx, p)
    plan = batchscan.plan_for_endpoints(inst, tree, p, p1, p2, 1, 10)
    (m,) = inst.models
    model = {"rates": np.asarray(m.rates, dtype=np.float64),
             "freqs": reference.empirical_freqs(prob["patterns"], None, 4),
             "alpha": float(m.alpha)}
    np.testing.assert_allclose(np.asarray(m.freqs), model["freqs"],
                               rtol=0, atol=1e-12)
    return dict(inst=inst, tree=tree, plan=plan, edges=edges, p=p.number,
                s=p.back.number, zqr=float(ctx.zqr[0]), zs=float(p.z[0]),
                patterns=prob["patterns"], model=model)


def test_lazy_scan_against_the_plain_reference(pruned):
    f = pruned
    plan = f["plan"]
    got = sorted((c.q_num, c.q_slot.back.number) for c in plan.candidates)
    want = sorted((v, w) for v, w, _d in reference_search.window(
        f["edges"], f["p"], f["s"], 10, 14))
    assert got == want and len(got) >= 12
    depth = {(v, w): d for v, w, d in reference_search.window(
        f["edges"], f["p"], f["s"], 10, 14)}
    assert all(depth[(c.q_num, c.q_slot.back.number)] == c.depth
               for c in plan.candidates)
    lnls = batchscan.run_plan(f["inst"], f["tree"], plan)
    for c, lnl in zip(plan.candidates, lnls):
        ref = reference_search.lazy_candidate(
            f["patterns"], f["model"], f["edges"], f["p"], f["s"],
            f["zqr"], f["zs"], c.q_num, c.q_slot.back.number)
        assert float(lnl) == pytest.approx(ref, rel=1e-9)


@pytest.mark.parametrize("span", [slice(0, 4), slice(4, None)],
                         ids=["first4", "rest"])
def test_thorough_scan_against_the_plain_reference(pruned, span, batched):
    """lnL at the program's branch triplets, and the Newton step the
    reference's own derivatives would still make on the three branches:
    `localSmooth` stops a branch that moves by less than DELTAZ (1e-5
    in z), so what is left on it is below that (6e-7 at most here)."""
    f = pruned
    plan = f["plan"]
    lnls, triplets = batchscan.run_plan_thorough(f["inst"], f["tree"], plan)
    assert triplets.shape == (len(plan.candidates), 3)
    for c, lnl, e in list(zip(plan.candidates, lnls, triplets))[span]:
        ref, dz = reference_search.thorough_candidate(
            f["patterns"], f["model"], f["edges"], f["p"], f["s"],
            f["zqr"], c.q_num, c.q_slot.back.number, e)
        assert float(lnl) == pytest.approx(ref, rel=1e-9)
        assert dz.max() < 1e-5, (c.q_num, dz)
