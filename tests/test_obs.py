"""Observability subsystem (examl_tpu/obs): registry semantics, trace
JSONL well-formedness, engine counter wiring, CLI --metrics/--trace-events,
and per-process trace artifacts on the 2-process multihost path."""

import json
import os
import time

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.obs.metrics import MetricsRegistry


# -- registry semantics ------------------------------------------------------


def test_registry_counter_gauge_timer_semantics():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.inc("c", 2)
    reg.inc("f", 0.25)                 # float increments (compile seconds)
    reg.gauge("g", 7)
    reg.gauge("g", 9)                  # gauges overwrite
    with reg.timer("t"):
        pass
    with reg.timer("t"):
        pass
    reg.observe("t", 1.5)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["counters"]["f"] == pytest.approx(0.25)
    assert snap["gauges"]["g"] == 9
    t = snap["timers"]["t"]
    assert t["count"] == 3
    assert t["total_s"] >= 1.5
    assert t["max_s"] >= 1.5 and t["min_s"] <= t["max_s"]
    assert reg.counter("c") == 3 and reg.counter("absent") == 0
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "timers": {}}


def test_registry_timer_context_exposes_elapsed():
    reg = MetricsRegistry()
    with reg.timer("t") as tm:
        time.sleep(0.01)
    assert tm.elapsed >= 0.005
    assert reg.snapshot()["timers"]["t"]["total_s"] == pytest.approx(
        tm.elapsed)


def test_registry_collector_runs_at_snapshot_and_unregisters():
    reg = MetricsRegistry()
    calls = []

    def collect():
        calls.append(1)
        reg.gauge("live", len(calls))
        return len(calls) < 2          # unregister after 2nd snapshot

    reg.add_collector(collect)
    assert reg.snapshot()["gauges"]["live"] == 1
    assert reg.snapshot()["gauges"]["live"] == 2
    reg.snapshot()
    assert len(calls) == 2             # dropped after returning False


# -- trace JSONL -------------------------------------------------------------


def _check_balanced(events):
    """Every B has a matching E per (pid, tid), properly nested."""
    stacks = {}
    for ev in events:
        key = (ev.get("pid"), ev.get("tid"))
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ev["ph"] == "E":
            assert stacks.get(key), f"E without B: {ev}"
            assert stacks[key].pop() == ev["name"], ev
    for key, stack in stacks.items():
        assert not stack, f"unclosed spans on {key}: {stack}"


def test_trace_jsonl_wellformed_and_balanced(tmp_path):
    d = str(tmp_path / "tr")
    path = obs.enable_tracing(d, procid=0)
    try:
        with obs.span("outer", args={"k": 1}):
            with obs.span("inner"):
                pass
        with obs.span("engine:fake", cat="dispatch"):
            pass
        obs.instant("marker", args={"why": "test"})
    finally:
        obs.finalize_tracing()
    # The finalized file is strictly valid Chrome-trace JSON ...
    events = json.loads(open(path).read())
    assert isinstance(events, list) and events
    for ev in events:
        assert ev["ph"] in ("B", "E", "X", "i", "M")
        assert "ts" in ev and "pid" in ev
        if ev["ph"] in ("B", "i", "M"):
            assert "name" in ev
    # ... and the line-by-line reader agrees with the array parse.
    assert len(obs.read_events(path)) == len(events)
    _check_balanced([e for e in events if e["ph"] in ("B", "E")])
    names = {e.get("name") for e in events}
    assert {"outer", "inner", "engine:fake", "marker"} <= names
    # process 0 merged a summary
    summary = json.load(open(os.path.join(d, "summary.json")))
    assert os.path.basename(path) in summary["files"]
    assert summary["spans"]["outer"]["count"] == 1


def test_trace_survives_unfinished_span(tmp_path):
    """A span still open when the writer dies must already be on disk
    (the wedged-compile postmortem artifact: the B line names the guilty
    program)."""
    from examl_tpu.obs import trace as trace_mod

    path = str(tmp_path / "t.jsonl")
    w = trace_mod.TraceWriter(path, procid=0)
    w.event({"ph": "B", "name": "compile:fast", "pid": 0, "tid": 0,
             "ts": 1})
    # no E, no close — simulate a wedged process; the flushed file must
    # still be readable and name the open span.
    events = obs.read_events(path)
    assert events[-1]["name"] == "compile:fast"
    assert events[-1]["ph"] == "B"
    w.close()


# -- engine wiring -----------------------------------------------------------


def _tiny_instance():
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data

    rng = np.random.default_rng(0)
    names = [f"t{i}" for i in range(10)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 300))
            for _ in names]
    inst = PhyloInstance(build_alignment_data(names, seqs))
    return inst, inst.random_tree(0)


def test_engine_cache_and_dispatch_counters():
    """A full traversal compiles (cache miss) and a recompute of the same
    wave profile hits the shared program cache; every device call counts
    a dispatch."""
    inst, tree = _tiny_instance()
    reg = obs.registry()
    c0 = {k: reg.counter("engine." + k) for k in
          ("cache_hits", "cache_misses", "dispatch_count",
           "compile_count", "traversal_entries")}
    inst.evaluate(tree, full=True)
    c1 = {k: reg.counter("engine." + k) for k in c0}
    assert c1["cache_misses"] > c0["cache_misses"]     # first build
    assert c1["compile_count"] > c0["compile_count"]
    assert c1["dispatch_count"] > c0["dispatch_count"]
    assert c1["traversal_entries"] >= c0["traversal_entries"] + 8
    inst.evaluate(tree, full=True)                     # same profile again
    c2 = {k: reg.counter("engine." + k) for k in c0}
    assert c2["cache_hits"] > c1["cache_hits"]
    assert c2["cache_misses"] == c1["cache_misses"]
    assert reg.counter("engine.compile_seconds") > 0


def test_engine_compile_seconds_per_family_and_arena_gauge():
    inst, tree = _tiny_instance()
    inst.evaluate(tree, full=True)
    inst.makenewz(tree, tree.start.back, tree.start, tree.start.z,
                  maxiter=2)
    snap = obs.snapshot()
    fams = [k for k in snap["counters"] if
            k.startswith("engine.compile_seconds.")]
    assert any(k.endswith(".fast") for k in fams), fams
    assert any(k.endswith(".newton") for k in fams), fams
    (eng,) = inst.engines.values()
    expect = (eng.num_rows * eng.B * eng.lane * eng.R * eng.K
              * np.dtype(eng.storage_dtype).itemsize)
    # gauge names are unique per engine (s<K>.e<ordinal>)
    assert eng._obs_tag.startswith("s4.e")
    assert snap["gauges"]["engine.clv_arena_bytes." + eng._obs_tag] == expect


# -- CLI ---------------------------------------------------------------------


def test_report_phases_zero_total_no_zerodivision(tmp_path, monkeypatch):
    """Satellite fix: all-~0.0s phases with a zero wall total must report
    instead of raising ZeroDivisionError on the percentage line."""
    from examl_tpu.cli import main as cli_main

    files = cli_main.RunFiles(str(tmp_path), "Z")
    files._phases = {"startup": 0.0, "inference": 0.0}
    frozen = files.start_time
    monkeypatch.setattr(cli_main.time, "time", lambda: frozen)
    files.report_phases()              # must not raise
    info = open(files.info_path).read()
    assert "Wall-clock by phase" in info
    assert "startup" in info and "0.0%" in info


def test_cli_metrics_and_trace_artifacts(tmp_path):
    """Acceptance-shaped: a CLI run with --metrics and --trace-events
    leaves (1) a metrics JSON with nonzero dispatch/compile/cache
    counters and (2) a per-process Chrome-trace file with nested
    compile/dispatch spans plus the process-0 summary."""
    from examl_tpu.cli.main import main
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile

    rng = np.random.default_rng(5)
    names = [f"t{i}" for i in range(8)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 200))
            for _ in names]
    data = build_alignment_data(names, seqs)
    bf = str(tmp_path / "tiny.binary")
    write_bytefile(bf, data)
    tree = PhyloInstance(data).random_tree(5)
    tf = str(tmp_path / "tiny.tree")
    open(tf, "w").write(tree.to_newick(names))
    m = str(tmp_path / "m.json")
    tr = str(tmp_path / "tr")

    rc = main(["-s", bf, "-n", "OBS", "-t", tf, "-f", "e",
               "-w", str(tmp_path / "out"), "--metrics", m,
               "--trace-events", tr, "--single-device"])
    assert rc == 0
    snap = json.load(open(m))
    c = snap["counters"]
    assert c["engine.dispatch_count"] > 0
    assert c["engine.compile_seconds"] > 0
    assert c["engine.cache_misses"] > 0 and c["engine.cache_hits"] > 0
    assert any(k.startswith("phase.") for k in snap["timers"])
    events = json.loads(open(os.path.join(tr, "trace.p0.jsonl")).read())
    names_seen = {e.get("name") for e in events}
    assert any(n and n.startswith("compile:") for n in names_seen)
    assert any(n and n.startswith("engine:") for n in names_seen)
    _check_balanced([e for e in events if e["ph"] in ("B", "E")])
    assert os.path.exists(os.path.join(tr, "summary.json"))
    # watchdog/info-file routing is wired: the log sink points at the
    # run info file (exercised for real only when a compile exceeds 180s)
    info = open(tmp_path / "out" / "ExaML_info.OBS").read()
    assert "trace events ->" in info and "metrics snapshot ->" in info


# -- multihost ---------------------------------------------------------------


def test_two_process_trace_files_and_summary_merge(tmp_path):
    """Two OS processes sharing one trace dir (procid via EXAML_PROCID,
    the non-distributed override): each writes its own file named by
    procid, and process 0 merges summary.json at exit — the artifact
    layout of the multihost path without needing multiprocess
    collectives on the CPU backend."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = str(tmp_path / "tr")
    code = ("from examl_tpu import obs\n"
            "with obs.span('child_work', args={'p': %d}):\n"
            "    obs.instant('mark')\n")
    procs = []
    for p in (1, 0):                   # proc 0 last: its exit merges both
        env = dict(os.environ, EXAML_PROCID=str(p), EXAML_TRACE_DIR=d,
                   PYTHONPATH=repo)
        procs.append(subprocess.Popen([sys.executable, "-c", code % p],
                                      env=env, cwd=repo))
        procs[-1].wait(timeout=120)
    assert all(pr.returncode == 0 for pr in procs)
    for p in (0, 1):
        events = json.loads(open(os.path.join(
            d, f"trace.p{p}.jsonl")).read())
        assert any(e.get("name") == "child_work" for e in events)
        _check_balanced([e for e in events if e["ph"] in ("B", "E")])
    summary = json.load(open(os.path.join(d, "summary.json")))
    assert set(summary["files"]) == {"trace.p0.jsonl", "trace.p1.jsonl"}
    assert summary["spans"]["child_work"]["count"] == 2


@pytest.mark.slow
def test_multihost_per_process_trace_files(tmp_path, monkeypatch):
    """The 2-process dryrun_multihost path with EXAML_TRACE_DIR set:
    each process writes its own trace file named by procid, both are
    well-formed, and process 0 merges a summary."""
    from __graft_entry__ import dryrun_multihost

    d = str(tmp_path / "tr")
    monkeypatch.setenv("EXAML_TRACE_DIR", d)
    try:
        dryrun_multihost(2, 4)
    except RuntimeError as exc:
        if "Multiprocess computations aren't implemented" in str(exc):
            # This jaxlib build cannot run multi-PROCESS collectives on
            # the CPU backend at all (the whole seed multihost battery
            # fails the same way); the trace-artifact assertion needs a
            # build where the dryrun itself works.
            pytest.skip("jaxlib CPU backend lacks multiprocess "
                        "collectives")
        raise
    files = sorted(os.listdir(d))
    assert "trace.p0.jsonl" in files and "trace.p1.jsonl" in files
    for name in ("trace.p0.jsonl", "trace.p1.jsonl"):
        events = json.loads(open(os.path.join(d, name)).read())
        assert any(e.get("name", "").startswith("engine:")
                   for e in events), name
        _check_balanced([e for e in events if e["ph"] in ("B", "E")])
    assert "summary.json" in files
