"""A program's first call measured from inside (ISSUE 40), CPU.

JAX's own trace / lower / compile events become `jax.*` registry
counters (obs/programs.py), the first-call path of a guarded program is
a span tree (`first_call:<family>` > lower / `compile:<family>` /
analyze), and the newest events are kept with the span they fell in.
Counts and host seconds only: no number of this file is a device number.
"""

import os
import sys

import pytest

from examl_tpu import obs
from examl_tpu.obs import programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("EXAML_PROGRAM_OBS", raising=False)
    monkeypatch.delenv("EXAML_LEDGER_DIR", raising=False)
    programs.install_listener()
    programs.reset()
    obs.reset()
    yield
    programs.reset()


def _counters(prefix="jax."):
    return {k: v for k, v in obs.registry().snapshot_light()[
        "counters"].items() if k.startswith(prefix)}


class _Event:
    """One `log_elapsed_time` block as JAX fires it: the scalar on
    entry, the duration on exit."""

    def __init__(self, event, seconds, fun_name):
        self.event, self.seconds, self.fun_name = event, seconds, fun_name

    def __enter__(self):
        import jax.monitoring as mon
        mon.record_scalar(self.event, 0.0, fun_name=self.fun_name)

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.record_event_duration_secs(self.event, self.seconds,
                                       fun_name=self.fun_name)


# -- the listeners, fed synthetic events -------------------------------------


def test_an_inner_trace_adds_no_count_and_no_seconds():
    with _Event(TRACE, 5.0, "f"):
        with _Event(TRACE, 2.0, "matmul"):
            pass
        inside = _counters()
        with _Event(TRACE, 1.0, "_reduce_sum"):
            pass
    assert inside == {}              # the inner trace alone counted nothing
    c = _counters()
    assert c == {"jax.trace_count": 1, "jax.trace_seconds": 5.0,
                 "jax.trace_lower_seconds": 5.0}
    assert [(e["event"], e["fun_name"], e["seconds"])
            for e in programs.jit_events()] == [("trace", "f", 5.0)]


def test_a_program_met_while_tracing_is_counted_but_not_its_seconds():
    # an eager operation inside a traced function lowers and compiles
    # inside the outer trace, whose duration holds its seconds
    with _Event(TRACE, 4.0, "f"):
        with _Event(LOWER, 0.5, "broadcast_in_dim"):
            pass
        with _Event(BACKEND, 1.5, "broadcast_in_dim"):
            pass
    with _Event(LOWER, 0.25, "f"):
        pass
    with _Event(BACKEND, 2.0, "f"):
        pass
    c = _counters()
    assert c["jax.lower_count"] == 2 and c["jax.backend_compile_count"] == 2
    assert c["jax.trace_seconds"] == 4.0 and c["jax.lower_seconds"] == 0.25
    assert c["jax.backend_compile_seconds"] == 2.0
    # one counter for the accepted `counter_at_setup` reader, and the
    # three sums are wall seconds: nothing is counted twice
    assert c["jax.trace_lower_seconds"] == 4.25
    assert len(programs.jit_events()) == 5


def test_cache_events_feed_the_counters_xla_cache_hits_reads():
    import jax.monitoring as mon
    before = programs.xla_cache_hits()
    mon.record_event("/jax/compilation_cache/cache_hits")
    mon.record_event("/jax/compilation_cache/cache_misses")
    mon.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    mon.record_event("/jax/compilation_cache/tasks_using_cache")
    assert programs.xla_cache_hits() == before + 1
    assert _counters() == {"jax.cache_hits": before + 1,
                           "jax.cache_misses": 1,
                           "jax.cache_retrieval_seconds": 0.75}


def test_only_the_newest_events_are_kept_and_reset_clears_them():
    for i in range(programs.JIT_EVENTS_KEPT + 6):
        with _Event(LOWER, 0.001, f"f{i}"):
            pass
    kept = programs.jit_events()
    assert len(kept) == programs.JIT_EVENTS_KEPT
    assert kept[0]["fun_name"] == "f6" and kept[-1]["fun_name"] == "f69"
    assert obs.snapshot()["jit_events"] == kept
    obs.reset()
    assert programs.jit_events() == [] and _counters() == {}
    assert "jit_events" not in obs.snapshot()


# -- real programs -----------------------------------------------------------


def test_a_fresh_jit_function_lowers_once_and_reset_keeps_the_listener():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0)              # its own eager programs come first
    x.block_until_ready()

    def fresh(v):
        return (v * 3.0 + 1.0).sum()

    f = jax.jit(fresh)
    before = _counters()
    f(x).block_until_ready()
    first = _counters()
    assert first["jax.lower_count"] == before.get("jax.lower_count", 0) + 1
    assert first["jax.trace_count"] == before.get("jax.trace_count", 0) + 1
    assert first["jax.backend_compile_count"] == before.get(
        "jax.backend_compile_count", 0) + 1
    assert first["jax.trace_lower_seconds"] == pytest.approx(
        first["jax.trace_seconds"] + first["jax.lower_seconds"])
    f(x).block_until_ready()         # the second call compiles nothing
    assert _counters() == first
    # JAX names the trace by the function, the other two by the module
    assert {(e["event"], e["fun_name"]) for e in programs.jit_events()
            if "fresh" in e["fun_name"]} == {
                ("trace", "fresh"), ("lower", "jit(fresh)"),
                ("backend_compile", "jit(fresh)")}
    obs.reset()                      # zeroes the counters ...
    assert _counters() == {}
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    assert _counters()["jax.lower_count"] == 1      # ... not the listener


def test_jit_events_carry_the_enclosing_span_and_become_instants(tmp_path):
    import jax
    import jax.numpy as jnp
    from examl_tpu.obs import trace
    x = jnp.ones(5)
    path = obs.enable_tracing(str(tmp_path), procid=0)
    try:
        with obs.span("opt:outer"):
            with obs.span("engine:probe/launch"):
                assert trace.current() == "engine:probe/launch"
                jax.jit(lambda v: v * 5.0)(x).block_until_ready()
            assert trace.current() == "opt:outer"
        assert trace.current() is None
    finally:
        obs.finalize_tracing()
        obs.set_annotations(False)
    rows = [e for e in programs.jit_events() if "<lambda>" in e["fun_name"]]
    assert {e["event"] for e in rows} == {"trace", "lower",
                                          "backend_compile"}
    assert {e["span"] for e in rows} == {"engine:probe/launch"}
    instants = [e for e in obs.read_events(path) if e.get("ph") == "i"]
    assert {e["name"] for e in instants} >= {"jit:trace", "jit:lower",
                                            "jit:backend_compile"}
    assert all(e["args"]["span"] == "engine:probe/launch"
               for e in instants if "<lambda>" in e["args"]["fun_name"])


# -- the first-call path of a guarded program --------------------------------


def _tiny_instance():
    from conftest import correlated_dna
    from examl_tpu.instance import PhyloInstance
    inst = PhyloInstance(correlated_dna(9, 240, seed=40))
    return inst, inst.random_tree(0)


def _first_calls():
    inst, tree = _tiny_instance()
    inst.evaluate(tree, full=True)
    inst.makenewz(tree, tree.start.back, tree.start, tree.start.z,
                  maxiter=2)
    return obs.snapshot()


def test_first_call_spans_tile_a_guarded_programs_first_call():
    snap = _first_calls()
    timers, counters = snap["timers"], snap["counters"]
    families = [n.split(":", 1)[1] for n in timers
                if n.startswith("first_call:") and "/" not in n]
    assert families
    for fam in families:
        parent = timers[f"first_call:{fam}"]
        children = [timers[f"first_call:{fam}/lower"],
                    timers[f"compile:{fam}"],
                    timers[f"first_call:{fam}/analyze"]]
        assert all(t["count"] == parent["count"] for t in children)
        assert parent["total_s"] >= sum(t["total_s"] for t in children)
        assert parent["self_s"] == pytest.approx(
            parent["total_s"] - sum(t["total_s"] for t in children))
    n = counters["engine.compile_count"]
    assert timers["engine.first_call"]["count"] == n == sum(
        timers[f"first_call:{f}"]["count"] for f in families)
    assert timers["program.obs"]["count"] == n
    # `compile_seconds` is the jitted call alone, as before
    assert counters["engine.compile_seconds"] == pytest.approx(sum(
        timers[f"compile:{f}"]["total_s"] for f in families))
    # every guarded program reached the compiler once, whatever else did;
    # the observatory's analysis compile is the observatory's, not JAX's
    assert counters["jax.lower_count"] >= n
    assert counters["jax.backend_compile_count"] == \
        counters["jax.lower_count"]
    # the program's trace and lowering fell in the prelower's span, the
    # compile (or the cache's retrieval) in the jitted call's
    import re
    spans = {(e["event"], re.sub(r":[^/]*", "", e["span"] or ""))
             for e in snap["jit_events"]}
    assert ("lower", "first_call/lower") in spans
    assert ("backend_compile", "compile") in spans


def test_the_observatorys_own_compile_is_a_row_but_no_jax_counter():
    """`lowered.compile()` of the analyze span is `program.obs`'s cost:
    kept out of `jax.backend_compile_*`, so the metrics that read the
    two do not hold one second twice; the row names the span."""

    class Lowered:
        def compile(self):
            import jax.monitoring as mon
            mon.record_event("/jax/compilation_cache/cache_hits")
            mon.record_event_duration_secs(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
            with _Event(BACKEND, 0.75, "jit(impl)"):
                pass
            raise RuntimeError("no analyses needed here")

        def as_text(self):
            return ""

    row = programs.record("fast", "k", "xla-cache", 0.1, lowered=Lowered())
    assert row["missing"] == ["compile"]
    assert _counters() == {}
    assert programs.jit_events() == [{
        "event": "backend_compile", "fun_name": "jit(impl)",
        "seconds": 0.75, "span": "first_call:fast/analyze"}]
    with _Event(BACKEND, 0.25, "jit(impl)"):     # the flag is lowered again
        pass
    assert _counters() == {"jax.backend_compile_count": 1,
                           "jax.backend_compile_seconds": 0.25}


@pytest.mark.parametrize("mode, lower, analyze", [("0", False, False),
                                                  ("rows", False, True)])
def test_spans_of_work_that_is_switched_off_are_not_opened(
        mode, lower, analyze, monkeypatch):
    monkeypatch.setenv(programs.ENV_VAR, mode)
    snap = _first_calls()
    timers = snap["timers"]
    assert any(n.startswith("compile:") for n in timers)
    assert timers["engine.first_call"]["count"] == \
        snap["counters"]["engine.compile_count"]
    assert any(n.endswith("/lower") for n in timers) is lower
    assert any(n.endswith("/analyze") for n in timers) is analyze
    assert ("program.obs" in timers) is analyze
    # without the prelower the jitted call traces and lowers itself
    assert snap["counters"]["jax.lower_count"] >= \
        snap["counters"]["engine.compile_count"]


# -- the operator's report ---------------------------------------------------


def test_run_report_prints_the_jit_events_after_the_program_table():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import run_report
    snap = {"counters": {"jax.trace_count": 2, "jax.trace_seconds": 1.5,
                         "jax.lower_count": 3, "jax.lower_seconds": 0.5,
                         "jax.backend_compile_count": 3,
                         "jax.backend_compile_seconds": 2.25,
                         "jax.cache_hits": 2, "jax.cache_misses": 1,
                         "jax.cache_retrieval_seconds": 0.125},
            "programs": [{"family": "newton", "source": "xla-cache",
                          "compile_s": 0.2, "key": "newton"}],
            "jit_events": [{"event": "lower", "fun_name": "broadcast_in_dim",
                            "seconds": 0.0042,
                            "span": "engine:newton/stage"}]}
    lines = []
    run_report.render_programs(lines.append, snap)
    run_report.render_jit_events(lines.append, snap)
    text = "\n".join(lines)
    assert text.index("Programs (") < text.index("JIT events")
    assert "lower=3/500.00ms" in text and "hits=2 misses=1" in text
    (row,) = [ln for ln in lines if "broadcast_in_dim" in ln]
    assert "engine:newton/stage" in row and "4.20ms" in row
    lines.clear()
    run_report.render_jit_events(lines.append, {"counters": {}})
    assert lines == []               # a snapshot from before: no section
