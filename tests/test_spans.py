"""The span primitive (examl_tpu/obs/trace.py) and its call sites on the
timed path: self time and parents on a fake clock, the three sinks and
their switches, the four phases that tile a dispatch, the timers the
benchmark reads, and the kernels' named scopes.  CPU only: no number of
this file is a device number."""

import json
import os

import jax
import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.obs import trace
from examl_tpu.obs.metrics import MetricsRegistry

PHASES = ("schedule", "stage", "launch", "wait")


class FakeClock:
    """Every reading is one second after the last."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture()
def quiet(monkeypatch):
    """No writer, no annotations, no look at the environment: the state
    of a run with every optional sink off."""
    monkeypatch.setattr(trace, "_writer", None)
    monkeypatch.setattr(trace, "_annotate", False)
    monkeypatch.setattr(trace, "_env_checked", True)


@pytest.fixture()
def registry(monkeypatch):
    reg = MetricsRegistry()
    monkeypatch.setattr(trace._metrics, "registry", lambda: reg)
    return reg


@pytest.fixture()
def fake_clock(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(trace, "_clock", clock)
    return clock


def _instance(ntaxa=12, seed=0):
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    rng = np.random.default_rng(seed)
    names = [f"t{i}" for i in range(ntaxa)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 300))
            for _ in names]
    inst = PhyloInstance(build_alignment_data(names, seqs))
    return inst, inst.random_tree(seed)


# -- the primitive on a fake clock -------------------------------------------


def test_nested_spans_record_self_seconds(quiet, registry, fake_clock):
    # readings: outer in 1, a in 2, a out 3, b in 4, c in 5, c out 6,
    # b out 7, outer out 8
    with obs.span("outer") as outer:
        with obs.span("a"):
            pass
        with obs.span("b"):
            with obs.span("c"):
                pass
    t = registry.snapshot()["timers"]
    assert outer.elapsed == 7.0
    assert (t["outer"]["total_s"], t["outer"]["self_s"]) == (7.0, 3.0)
    assert (t["a"]["total_s"], t["a"]["self_s"]) == (1.0, 1.0)
    assert (t["b"]["total_s"], t["b"]["self_s"]) == (3.0, 2.0)
    assert (t["c"]["total_s"], t["c"]["self_s"]) == (1.0, 1.0)
    # self seconds tile the outermost span: nothing counted twice
    assert sum(v["self_s"] for v in t.values()) == t["outer"]["total_s"]


def test_sibling_spans_of_one_name_add_up(quiet, registry, fake_clock):
    with obs.span("parent"):
        for _ in range(3):
            with obs.span("child"):
                pass
    t = registry.snapshot()["timers"]
    assert t["child"]["count"] == 3 and t["child"]["total_s"] == 3.0
    assert t["parent"]["total_s"] == 7.0 and t["parent"]["self_s"] == 4.0


def test_a_span_left_by_an_exception_is_closed_and_counted(
        quiet, registry, fake_clock):
    with pytest.raises(KeyError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise KeyError("x")
    t = registry.snapshot()["timers"]
    assert t["inner"]["count"] == 1 and t["outer"]["self_s"] == 2.0
    assert trace._tls.stack == []
    with obs.span("after"):          # the stack is usable again, no parent
        pass
    assert registry.snapshot()["timers"]["after"]["self_s"] == 1.0


def test_also_feeds_a_second_timer_and_plain_timers_are_all_self(
        quiet, registry, fake_clock):
    with obs.span("engine:x/schedule", also="host_schedule"):
        with obs.span("child"):
            pass
    registry.observe("plain", 2.5)
    t = registry.snapshot()["timers"]
    assert t["host_schedule"]["count"] == 1
    assert t["host_schedule"]["total_s"] == t["engine:x/schedule"][
        "total_s"] == 3.0
    assert t["engine:x/schedule"]["self_s"] == 2.0
    assert t["plain"]["self_s"] == t["plain"]["total_s"] == 2.5


def test_spans_of_another_thread_have_their_own_parents(quiet, registry):
    import threading
    seen = []

    def work():
        with obs.span("worker"):
            seen.append([s.name for s in trace._tls.stack])

    with obs.span("main"):
        th = threading.Thread(target=work)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive() and seen == [["worker"]]
    t = registry.snapshot()["timers"]
    # the worker's seconds are not taken off the main thread's span
    assert t["main"]["self_s"] == t["main"]["total_s"]


# -- the sinks and their switches --------------------------------------------


def test_with_everything_off_no_writer_and_no_annotation(quiet, registry,
                                                         monkeypatch):
    def refuse(name):
        raise AssertionError(f"annotation {name!r} entered with "
                             "annotations off")

    monkeypatch.setattr(trace, "_open_annotation", refuse)
    with obs.span("engine:quiet", args={"k": 1}) as sp:
        pass
    assert trace._writer is None and not obs.tracing_enabled()
    assert sp._ann is None and sp._w is None
    assert registry.snapshot()["timers"]["engine:quiet"]["count"] == 1


def test_annotations_follow_the_switch_and_the_span(quiet, registry,
                                                    monkeypatch):
    entered = []

    class Ann:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            entered.append("exit")

    monkeypatch.setattr(trace, "_annotation_cls", Ann)
    obs.set_annotations(True)
    with obs.span("engine:a/stage"):
        pass
    with obs.span("engine:a", annotate=False):   # a dispatch: timer only
        pass
    assert entered == ["engine:a/stage", "exit"]
    obs.set_annotations(False)
    with obs.span("engine:a/stage"):
        pass
    assert entered == ["engine:a/stage", "exit"]
    assert trace._writer is None


def test_jsonl_events_name_parent_and_sequence(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "_annotate", False)
    path = obs.enable_tracing(str(tmp_path / "tr"), procid=0)
    try:
        seq = obs.counter("engine.dispatch_count")
        with obs.span("opt:outer", args={"k": 1}):
            with obs.span("engine:f/stage", cat="dispatch"):
                pass
    finally:
        obs.finalize_tracing()
        obs.set_annotations(False)
    begins = [e for e in json.loads(open(path).read()) if e["ph"] == "B"]
    outer, inner = begins
    assert outer["args"] == {"k": 1, "seq": seq}
    assert inner["args"] == {"parent": "opt:outer", "seq": seq}
    assert inner["cat"] == "dispatch" and outer["cat"] == "host"


# -- the timed path: phases tile their dispatch -------------------------------


def _dispatches(events, name):
    """For each B..E pair of `name`: the events strictly inside it."""
    out, inside = [], None
    for ev in events:
        if ev["ph"] not in ("B", "E"):
            continue
        if ev["name"] == name:
            if ev["ph"] == "B":
                inside = [ev]
            else:
                out.append(inside)
                inside = None
        elif inside is not None:
            inside.append(ev)
    return out


@pytest.fixture()
def traced_instance(tmp_path, monkeypatch):
    """A 12-taxon instance with warm programs, then one full evaluation
    and one whole-tree gradient with the JSONL writer on and a fake
    clock under the spans."""
    from examl_tpu.optimize.branch import tree_gradients
    inst, tree = _instance()
    inst.evaluate(tree, full=True)                  # compile outside
    tree_gradients(inst, tree)
    reg = MetricsRegistry()
    monkeypatch.setattr(trace._metrics, "registry", lambda: reg)
    monkeypatch.setattr(trace, "_clock", FakeClock())
    monkeypatch.setattr(trace, "_annotate", False)
    path = obs.enable_tracing(str(tmp_path / "tr"), procid=0)
    try:
        lnl = inst.evaluate(tree, full=True)
        tree_gradients(inst, tree)
    finally:
        obs.finalize_tracing()
        obs.set_annotations(False)
    assert np.isfinite(lnl)
    return reg.snapshot()["timers"], json.loads(open(path).read())


@pytest.mark.parametrize("family,phases", [
    ("trav_eval", ["schedule", "schedule", "stage", "launch", "wait"]),
    ("grad_pass", ["schedule", "stage", "launch", "wait"]),
    # does not block on its result, hands nothing but z to the device
    ("traverse", ["schedule", "schedule", "launch"]),
])
def test_phases_tile_their_dispatch(traced_instance, family, phases):
    timers, events = traced_instance
    (inside,) = _dispatches(events, f"engine:{family}")
    head, body = inside[0], inside[1:]
    # depth 1 under the dispatch holds the phases and nothing else, each
    # opening right where the last closed: B, E, B, E, ...
    assert [e["ph"] for e in body] == ["B", "E"] * len(phases)
    assert [e["name"] for e in body[0::2]] == [
        f"engine:{family}/{p}" for p in phases]
    assert [e["name"] for e in body[1::2]] == [e["name"]
                                               for e in body[0::2]]
    assert all(a["ts"] <= b["ts"] for a, b in zip(body, body[1:]))
    # one sequence number a dispatch, and the dispatch is the parent
    seqs = {e["args"]["seq"] for e in [head] + body[0::2]}
    assert len(seqs) == 1
    assert all(e["args"]["parent"] == f"engine:{family}"
               for e in body[0::2])
    # phase totals never exceed the dispatch's, whose self time is
    # exactly the rest
    disp = timers[f"engine:{family}"]
    parts = sum(timers[f"engine:{family}/{p}"]["total_s"]
                for p in set(phases))
    assert parts <= disp["total_s"]
    assert disp["self_s"] == disp["total_s"] - parts
    assert set(PHASES) >= set(phases)


def test_dispatches_of_one_step_have_rising_sequence_numbers(
        traced_instance):
    _, events = traced_instance
    seqs = [e["args"]["seq"] for e in events if e["ph"] == "B"
            and e["name"] in ("engine:trav_eval", "engine:traverse",
                              "engine:grad_pass")]
    assert seqs == sorted(seqs) and len(set(seqs)) == 3
    # the tree's own schedule runs before the dispatch it serves
    tree_sched = [e for e in events if e["ph"] == "B"
                  and e["name"] == "engine:tree/schedule"]
    assert len(tree_sched) == 2


def test_timers_the_benchmark_reads_count_as_on_the_parent():
    """`host_schedule` (host_schedule_ms) and `engine.grad_pass`
    (grad_passes_per_step) keep their counts: 7 and 1 for one full
    evaluation and one whole-tree gradient, read on the parent commit
    8f28e7b, where `obs.timer` blocks and a `perf_counter` pair fed
    them.  `engine.staged_arrays` counts what was handed to jnp."""
    from examl_tpu.optimize.branch import tree_gradients
    inst, tree = _instance()
    inst.evaluate(tree, full=True)
    tree_gradients(inst, tree)
    obs.reset()
    inst.evaluate(tree, full=True)
    tree_gradients(inst, tree)
    snap = obs.snapshot()
    t = snap["timers"]
    assert t["host_schedule"]["count"] == 7
    assert t["engine.grad_pass"]["count"] == 1
    assert t["engine.grad_pass"]["total_s"] == t["engine:grad_pass"][
        "total_s"]
    assert snap["counters"]["engine.dispatch_count"] == 3
    schedule = sum(v["count"] for k, v in t.items()
                   if k.endswith("/schedule"))
    assert schedule == t["host_schedule"]["count"]
    # trav_eval stages p, q and z; the gradient four indices, the eight
    # outroot arrays and three edge arrays
    assert snap["counters"]["engine.staged_arrays"] == 3 + 15
    inst.push_models()
    assert obs.counter("engine.staged_arrays") == 3 + 15 + 7
    assert obs.snapshot()["timers"]["engine:set_models"]["count"] == 1


def test_optimiser_control_is_under_opt_spans():
    from examl_tpu.optimize.branch import tree_evaluate
    from examl_tpu.optimize.model_opt import opt_alphas
    inst, tree = _instance()
    obs.reset()
    tree_evaluate(inst, tree, 0.0625)
    opt_alphas(inst, tree)
    t = obs.snapshot()["timers"]
    assert t["opt:tree_evaluate"]["count"] == 1
    assert t["opt:brent"]["count"] == 1
    sweeps = t["opt:smooth_sweep"]["count"]
    assert sweeps >= 1 and t["opt:newton_update"]["count"] == sweeps
    assert t["engine:grad_pass"]["count"] == sweeps
    assert t["engine:set_models"]["count"] >= 2
    # control time is what the optimiser's spans do not hand down
    for name in ("opt:tree_evaluate", "opt:smooth_sweep", "opt:brent"):
        assert 0 <= t[name]["self_s"] < t[name]["total_s"]


# -- kernels under named scopes ----------------------------------------------


def _loops(jaxpr, out):
    """(primitive, name stack) of every loop of a jaxpr, nested ones
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            out.append((eqn.primitive.name, str(eqn.source_info.name_stack)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _loops(inner, out)
    return out


def test_programs_name_their_kernels():
    """The lowered text of the traversal and gradient programs names the
    kernels' scopes, and the gradient program's two loops (XLA's
    `while.22`, `while.23` of PERF.md) are `examl/outroot` and
    `examl/edge_grad`."""
    from examl_tpu.optimize.branch import tree_gradients
    inst, tree = _instance()
    (eng,) = inst.engines.values()
    programs = {}

    def put(key, fn):                # hand back the raw jit, recorded
        def call(*args):
            programs.setdefault(key[0], (fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)))
            return fn(*args)
        return call

    eng.cache_put = put
    inst.evaluate(tree, full=True)
    tree_gradients(inst, tree)
    fast, shapes = programs["fast"]
    text = fast.lower(*shapes).as_text(debug_info=True)
    assert "examl/newview" in text and "examl/evaluate" in text
    grad, shapes = programs["grad"]
    text = grad.lower(*shapes).as_text(debug_info=True)
    for scope in ("examl/outroot", "examl/edge_grad", "examl/sumtable",
                  "examl/derivs"):
        assert scope in text, scope
    assert "examl/newview" not in text
    loops = _loops(jax.make_jaxpr(grad)(*shapes).jaxpr, [])
    assert loops == [("scan", "examl/outroot"), ("scan", "examl/edge_grad")]


def test_scan_tier_traversal_is_under_newview():
    from examl_tpu.ops import kernels
    inst, tree = _instance()
    (eng,) = inst.engines.values()
    p = tree.centroid_branch()
    tv = eng._traversal_arrays(tree.flat_full_traversal(p).to_entries())

    def run(clv, scaler):
        return kernels.traverse(eng.models, eng.block_part, eng.tips, clv,
                                scaler, tv, eng.scale_exp, eng.ntips)

    loops = _loops(jax.make_jaxpr(run)(eng.clv, eng.scaler).jaxpr, [])
    assert loops == [("scan", "examl/newview")]
    # a schedule helper called outside any dispatch is named so
    assert "engine:direct/schedule" in obs.snapshot()["timers"]


def test_lnl_is_bit_identical_with_and_without_annotations():
    from examl_tpu.optimize.branch import tree_evaluate
    got = []
    for on in (False, True, False):
        inst, tree = _instance(seed=3)
        obs.set_annotations(on)
        try:
            got.append((inst.evaluate(tree, full=True),
                        tree_evaluate(inst, tree, 0.25),
                        [tuple(p.z) for p, _ in tree.all_branches()]))
        finally:
            obs.set_annotations(False)
    assert got[0] == got[1] == got[2]
    assert not os.environ.get("EXAML_TRACE_DIR")
