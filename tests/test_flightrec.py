"""Roofline flight recorder: histograms, traffic model, ledger, report.

The measurement layer (obs/hist.py, obs/traffic.py, obs/ledger.py,
tools/run_report.py, tools/top.py) must make any run produce the
roofline artifact by itself: log-bucketed latency quantiles in every
--metrics snapshot, ONE shared bytes-per-traversal model for bench and
engine (bit-for-bit), a dispatch-bound vs bandwidth-meaningful regime
verdict on every achieved-GB/s number, and a merged per-rank event
timeline tolerant of crash-truncated writers — the artifact shape the
r04 postmortem lacked.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tests.conftest import correlated_dna

from examl_tpu import obs
from examl_tpu.obs import hist, ledger, traffic
from examl_tpu.obs.metrics import MetricsRegistry
from examl_tpu.resilience import faults, heartbeat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state(monkeypatch):
    """Ledger/autoflush are process-global; every test starts clean."""
    monkeypatch.delenv(ledger.ENV_VAR, raising=False)
    monkeypatch.delenv(heartbeat.ENV_VAR, raising=False)
    ledger.reset()
    heartbeat.reset()
    obs.set_autoflush(None)
    yield
    ledger.reset()
    heartbeat.reset()
    obs.set_autoflush(None)


# -- histograms --------------------------------------------------------------


def test_bucket_index_edges_and_clamps():
    assert hist.bucket_index(0.0) == 0
    assert hist.bucket_index(hist.FLOOR) == 0           # at the floor
    assert hist.bucket_index(1e30) == hist.MAX_INDEX    # clamped, kept
    # monotone over decades, and bounds contain the midpoint
    prev = -1
    for s in (1e-6, 1e-4, 1e-2, 1.0, 1e2):
        i = hist.bucket_index(s)
        assert i > prev
        prev = i
        lo, hi = hist.bucket_bounds(i)
        assert lo <= s < hi
        assert lo < hist.bucket_mid(i) < hi


def test_histogram_quantiles_resolve_the_tail():
    """The motivating case: sub-ms dispatches with one slow outlier.
    count/total/min/max averages it away; the histogram's p99 names
    it (within the ~12% bucket width)."""
    h = hist.Histogram()
    for _ in range(99):
        h.observe(1e-3)
    h.observe(2.0)                      # one recompile-sized stall
    q = h.quantiles()
    assert q["p50_s"] == pytest.approx(1e-3, rel=0.13)
    assert q["p95_s"] == pytest.approx(1e-3, rel=0.13)
    assert q["p99_s"] == pytest.approx(1e-3, rel=0.13)   # rank 99 of 100
    assert h.quantile(0.999) == pytest.approx(2.0, rel=0.13)
    assert h.count == 100
    assert hist.quantile_from_buckets({}, 0.5) is None   # empty -> None


def test_histogram_buckets_merge_exactly():
    """Two workers' bucket dicts sum to exactly the union histogram —
    the property bench worker accumulation and supervisor attempt
    merging rely on (quantiles recompute; they never average)."""
    a, b, u = hist.Histogram(), hist.Histogram(), hist.Histogram()
    rng = np.random.default_rng(7)
    for v in rng.lognormal(-6, 2, 200):
        a.observe(v)
        u.observe(v)
    for v in rng.lognormal(-2, 1, 50):
        b.observe(v)
        u.observe(v)
    # serialize through JSON like a real snapshot round-trip
    da = json.loads(json.dumps(a.to_dict()))
    db = json.loads(json.dumps(b.to_dict()))
    merged = hist.merge_bucket_dicts(da, db)
    assert merged == u.to_dict()
    for q in hist.QUANTILES:
        assert hist.quantile_from_buckets(merged, q) == u.quantile(q)
    # folding into a live histogram agrees too
    c = hist.Histogram()
    c.merge_dict(da)
    c.merge_dict(db)
    assert c.to_dict() == u.to_dict() and c.count == u.count


def test_timerstat_snapshot_carries_quantiles_and_buckets():
    reg = MetricsRegistry()
    for ms in (1, 1, 1, 1, 500):
        reg.observe("t", ms * 1e-3)
    t = reg.snapshot()["timers"]["t"]
    assert t["count"] == 5
    assert t["p50_s"] == pytest.approx(1e-3, rel=0.13)
    assert t["p99_s"] == pytest.approx(0.5, rel=0.13)
    assert sum(t["buckets"].values()) == 5
    json.dumps(t)                       # snapshot stays JSON-safe


# -- traffic model + regime classifier ---------------------------------------


class _E:
    def __init__(self, parent, left, right):
        self.parent, self.left, self.right = parent, left, right


def _entries(ntips=4):
    # 3 inner nodes over 4 tips: children 1..4 are tips, 5..6 inner
    return [_E(5, 1, 2), _E(6, 3, 4), _E(7, 5, 6)], ntips


def test_bytes_model_closed_form():
    """The obs/traffic closed form, both signatures, against a hand
    count."""
    entries, ntips = _entries()
    patterns, R, K, itemsize = 97, 4, 4, 4
    clv_row = patterns * R * K * itemsize
    sc_row = patterns * 4
    # hand count: 3 rows written, tips {1,2,3,4} read as codes, inner
    # children {5,6} read as CLV+scaler rows
    expect = (3 * (clv_row + sc_row)            # written
              + 2 * (clv_row + sc_row)          # inner children read
              + 4 * patterns)                   # tip code rows
    got = traffic.bytes_per_traversal(entries, ntips, patterns, R, K,
                                      itemsize)
    assert got == expect
    assert traffic.bytes_per_traversal_counts(3, 4, patterns, R, K,
                                              itemsize) == got


def test_regime_classifier_dispatch_vs_bandwidth(monkeypatch):
    """A wall time at `ops x launch latency` is a launch-floor artifact
    (r02's 23 GB/s); one well clear of it is a bandwidth measurement."""
    lat = traffic.DEFAULT_LAUNCH_LATENCY_S
    small = traffic.classify_regime(138 * lat * 1.1, 138)   # r02 shape
    assert small["regime"] == "dispatch-bound"
    assert small["floor_ratio"] == pytest.approx(1.1, abs=0.01)
    large = traffic.classify_regime(138 * lat * 20, 138)
    assert large["regime"] == "bandwidth-meaningful"
    # measured-latency override
    monkeypatch.setenv("EXAML_LAUNCH_LATENCY_S", str(lat * 100))
    assert traffic.classify_regime(138 * lat * 20,
                                   138)["regime"] == "dispatch-bound"


def test_traffic_window_accumulates_then_verdicts():
    win = traffic.TrafficWindow(min_dispatches=3, min_wall_s=100.0)
    assert win.add(1_000_000, 0.5, 10) is None
    assert win.add(1_000_000, 0.5, 10) is None
    gbps, regime, n = win.add(1_000_000, 0.5, 10)
    assert n == 3
    assert gbps == pytest.approx(3e6 / 1.5 / 1e9)
    assert regime["regime"] in ("dispatch-bound", "bandwidth-meaningful")
    assert win.n == 0                   # reset for the next window
    # env knobs (the CI smoke's 1-dispatch window)
    os.environ["EXAML_TRAFFIC_WINDOW_DISPATCHES"] = "1"
    os.environ["EXAML_TRAFFIC_WINDOW_WALL_S"] = "0"
    try:
        assert traffic.TrafficWindow().add(8, 1.0, 1) is not None
    finally:
        del os.environ["EXAML_TRAFFIC_WINDOW_DISPATCHES"]
        del os.environ["EXAML_TRAFFIC_WINDOW_WALL_S"]


def test_engine_traffic_agrees_with_model():
    """The engine's per-dispatch byte accounting (entry-list AND
    FlatTraversal forms) equals the obs/traffic model: one definition,
    bit-for-bit."""
    from examl_tpu.instance import PhyloInstance

    inst = PhyloInstance(correlated_dna(8, 120, seed=11))
    tree = inst.random_tree(seed=2)
    inst.evaluate(tree, full=True)
    (eng,) = inst.engines.values()
    flat = tree.flat_full_traversal(tree.start)
    entries = flat.to_entries()
    itemsize = np.dtype(eng.storage_dtype).itemsize
    expect = traffic.bytes_per_traversal(
        entries, eng.ntips, eng._patterns_true, eng.R, eng.K, itemsize)
    assert eng._traversal_traffic_bytes(entries) == expect
    assert eng._traversal_traffic_bytes(flat) == expect
    # and the run recorded bytes through the same model
    assert obs.registry().counter("engine.traffic_bytes") > 0


# -- ledger ------------------------------------------------------------------


def test_ledger_stream_and_rank0_merge(tmp_path):
    d = str(tmp_path)
    path = ledger.enable(d, proc=0)
    assert path.endswith("ledger.p0.jsonl")
    ledger.event("phase", name="startup", status="begin")
    ledger.event("compile", family="fast", status="end", seconds=1.2)
    evs = ledger.read_events(path)
    assert [e["kind"] for e in evs] == ["phase", "compile"]
    assert evs[0]["seq"] == 1 and evs[1]["seq"] == 2
    assert evs[1]["ts"] >= evs[0]["ts"] > 1e15          # epoch-us
    ledger.finalize()                                   # rank 0 merges
    merged = os.path.join(d, ledger.MERGED_NAME)
    assert [e["kind"] for e in ledger.read_events(merged)] == \
        ["phase", "compile"]
    assert not ledger.enabled()
    ledger.event("late", x=1)                           # silently dropped
    assert len(ledger.read_events(path)) == 2


def test_ledger_merge_total_order_and_truncation(tmp_path):
    """The gang merge: (ts, proc, seq) total order across rank files,
    with a SIGKILLed writer's torn final line skipped, not fatal."""
    d = str(tmp_path)

    def rec(ts, proc, seq, kind):
        return json.dumps({"ts": ts, "proc": proc, "seq": seq,
                           "kind": kind})

    with open(os.path.join(d, "ledger.p0.jsonl"), "w") as f:
        f.write(rec(100, 0, 1, "a") + "\n" + rec(300, 0, 2, "d") + "\n")
    with open(os.path.join(d, "ledger.p1.jsonl"), "w") as f:
        f.write(rec(200, 1, 1, "b") + "\n" + rec(200, 1, 2, "c") + "\n")
        f.write('{"ts": 400, "proc": 1, "se')       # torn: killed mid-write
    with open(os.path.join(d, "ledger.psup.jsonl"), "w") as f:
        f.write(rec(250, "sup", 1, "kill") + "\n")
    merged = ledger.merge(d)
    kinds = [e["kind"] for e in ledger.read_events(merged)]
    assert kinds == ["a", "b", "c", "kill", "d"]
    # idempotent: re-merge includes the merged file's dir unchanged
    assert [e["kind"] for e in ledger.read_events(ledger.merge(d))] == kinds
    assert ledger.merge(str(tmp_path / "empty")) is None


def test_ledger_env_enable_for_subprocesses(tmp_path, monkeypatch):
    """EXAML_LEDGER_DIR (exported by the CLI) lazily enables the ledger
    in bank workers / gang ranks that never call enable() themselves."""
    monkeypatch.setenv(ledger.ENV_VAR, str(tmp_path))
    monkeypatch.setenv("EXAML_PROCID", "3")
    ledger.reset()
    ledger.event("fault", point="engine.dispatch")
    evs = ledger.read_events(str(tmp_path / "ledger.p3.jsonl"))
    assert evs and evs[0]["proc"] == 3
    # EVERY rank merges at finalize (last exit completes the gang
    # timeline) — a rank-0-only merge would race peers' final events
    # in unsupervised multi-rank runs.
    merged = ledger.finalize()
    assert merged == str(tmp_path / ledger.MERGED_NAME)
    assert [e["proc"] for e in ledger.read_events(merged)] == [3]
    assert ledger.default_dir(None, None) is None
    assert ledger.default_dir("x", "/a/m.json") == "x"
    assert ledger.default_dir(None, "/a/m.json") == "/a"


# -- periodic metrics flush --------------------------------------------------


def test_autoflush_writes_partial_snapshot(tmp_path):
    obs.reset()                         # registry is process-global
    m = str(tmp_path / "m.json")
    obs.set_autoflush(m, interval=0.0)
    obs.inc("engine.dispatch_count", 41)
    assert obs.maybe_autoflush()
    snap = json.load(open(m))
    assert snap["partial"] is True
    assert snap["counters"]["engine.dispatch_count"] == 41
    assert "timers" in snap and "gauges" in snap
    obs.set_autoflush(None)
    os.unlink(m)
    assert not obs.maybe_autoflush()    # disarmed
    assert not os.path.exists(m)


def test_heartbeat_beats_tick_autoflush_without_heartbeat_file(tmp_path):
    """The kill-evidence seam: an unsupervised --metrics run has NO
    heartbeat file, yet its beats must still flush the snapshot — a
    SIGKILL mid-search then leaves last-known counters, not nothing."""
    m = str(tmp_path / "m.json")
    obs.set_autoflush(m, interval=0.0)
    heartbeat.install(None)             # no EXAML_HEARTBEAT_FILE
    heartbeat.beat("FAST_SPRS")
    assert json.load(open(m))["partial"] is True


def test_supervisor_partial_counters_staleness_gate(tmp_path):
    """An attempt killed before its FIRST flush must not inherit the
    previous attempt's partial snapshot: the flush timestamp is gated
    against the attempt's start time."""
    from examl_tpu.resilience import supervisor as sup

    m = str(tmp_path / "m.json")
    s = sup.Supervisor([], workdir=str(tmp_path / "w"), run_id="PC",
                       metrics_file=m, log=lambda *_: None)
    assert s._partial_counters(0.0) is None          # no file yet
    json.dump({"partial": True, "flushed_at": 100.0,
               "counters": {"engine.dispatch_count": 7}}, open(m, "w"))
    assert s._partial_counters(50.0) == {"engine.dispatch_count": 7}
    assert s._partial_counters(200.0) is None        # earlier attempt's
    json.dump({"counters": {"engine.dispatch_count": 9}}, open(m, "w"))
    assert s._partial_counters(0.0) is None          # full exit snapshot


# -- report tools ------------------------------------------------------------


def _tools_import(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    return __import__(name)


def test_run_report_renders_synthetic_artifacts(tmp_path):
    run_report = _tools_import("run_report")
    reg = MetricsRegistry()
    for ms in (1, 2, 400):
        reg.observe("dispatch", ms * 1e-3)
        reg.observe("host_schedule", ms * 1e-4)
    snap = reg.snapshot()
    snap["counters"] = {"engine.dispatch_count": 3,
                        "engine.traffic_bytes": 3e9,
                        "faults.fired.compile_hang": 1}
    snap["gauges"] = {"engine.achieved_gbps.scan": 21.0,
                      "engine.regime_dispatch_bound.scan": 1.0}
    ledger.enable(str(tmp_path), proc=0)
    ledger.event("compile", family="fast", status="start")
    ledger.event("compile", family="fast", status="end", seconds=2.0)
    # The wedge-postmortem artifact: an UNMATCHED compile start (the
    # run died compiling this family) must survive the timeline's
    # matched-start filtering.
    ledger.event("compile", family="wedged", status="start")
    ledger.finalize()
    bench_doc = {"bench": "fleet", "trees_per_sec": 12.5,
                 "single_trees_per_sec": 0.5, "speedup_vs_single": 25.0,
                 "target_speedup": 11.2, "meets_target": True,
                 "batch_occupancy": 0.9}
    lines = []
    run_report.render(snap, ledger.read_events(
        str(tmp_path / ledger.MERGED_NAME)), bench_doc,
        out=lines.append)
    text = "\n".join(lines)
    assert "21.00 GB/s" in text and "dispatch-bound" in text
    assert "[NOT a bandwidth number]" in text   # the regime flag
    assert "trees_per_sec 12.5" in text and "MET" in text  # fleet row
    assert "dispatch" in text and "p95" in text
    assert "compile" in text                    # timeline event
    assert "family=wedged" in text              # unmatched start kept
    assert text.count("status=start") == 1      # matched start dropped
    assert "faults fired" in text and "compile_hang=1" in text
    assert f"{traffic.ROOFLINE_TARGET_GBPS:.0f} GB/s" in text


def test_top_once_renders_gang_and_ledger(tmp_path):
    top = _tools_import("top")
    d = str(tmp_path)
    # two-rank heartbeat set (the supervisor's naming convention)
    base = os.path.join(d, ".heartbeat.R.json")
    for rank, path in ((0, base), (1, base + ".p1")):
        with open(path, "w") as f:
            json.dump({"t": 1.0, "pid": 100 + rank, "seq": 7,
                       "state": "FAST_SPRS",
                       "counters": {"engine.dispatch_count": 42}}, f)
    with open(os.path.join(d, "m.json"), "w") as f:
        json.dump({"counters": {}, "partial": True,
                   "gauges": {"engine.achieved_gbps.chunk": 12.5}}, f)
    ledger.enable(d, proc=0)
    ledger.event("supervisor.kill", reason="heartbeat-stall")
    ledger.finalize()
    lines = []
    beats = top.find_heartbeats(d, None)
    assert [r for r, _ in beats] == [0, 1]
    top.render_frame(lines.append, d, beats, top.find_metrics(d, None),
                     top.ledger_tail(d, 5))
    text = "\n".join(lines)
    assert "FAST_SPRS" in text and "42" in text
    assert "12.5GB/s" in text and "mid-run flush" in text
    assert "supervisor.kill" in text
    assert top.main(["--workdir", d, "--once"]) == 0
    empty = str(tmp_path / "nothing")
    os.makedirs(empty)
    assert top.main(["--workdir", empty, "--once"]) == 3


# -- e2e: the acceptance run -------------------------------------------------


def test_e2e_cli_run_produces_roofline_artifacts(tmp_path, monkeypatch):
    """A small CPU run with metrics + ledger yields: dispatch and
    host_schedule quantiles in the snapshot, a merged timeline with
    compile/phase/checkpoint events, and run_report/top rendering the
    per-tier achieved GB/s with its regime — the chip-window artifact,
    produced by the run itself."""
    from examl_tpu.cli.main import main as run_main
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.bytefile import write_bytefile

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # 1-dispatch traffic windows so the tiny run emits the gauge
    monkeypatch.setenv("EXAML_TRAFFIC_WINDOW_DISPATCHES", "1")
    monkeypatch.setenv("EXAML_TRAFFIC_WINDOW_WALL_S", "0")
    data = correlated_dna(8, 120, seed=5)
    bf = str(tmp_path / "a.binary")
    write_bytefile(bf, data)
    inst = PhyloInstance(data)
    tf = str(tmp_path / "start.nwk")
    open(tf, "w").write(inst.random_tree(seed=3).to_newick(
        data.taxon_names))
    w = str(tmp_path / "w")
    m = os.path.join(w, "m.json")
    os.makedirs(w)

    rc = run_main(["-s", bf, "-n", "FRE2E", "-t", tf, "-f", "d",
                   "-i", "5", "-w", w, "--single-device",
                   "--metrics", m, "--trace-events",
                   os.path.join(w, "tr")])
    assert rc == 0

    # snapshot: histogram quantiles for the hot timers
    snap = json.load(open(m))
    for name in ("dispatch", "host_schedule"):
        t = snap["timers"][name]
        assert t["count"] >= 1
        for q in ("p50_s", "p95_s", "p99_s"):
            assert t[q] is not None, (name, q)
    assert not snap.get("partial")         # the exit snapshot won
    assert snap["counters"]["engine.traffic_bytes"] > 0
    tiers = [k for k in snap["gauges"]
             if k.startswith("engine.achieved_gbps.")]
    assert tiers, snap["gauges"]

    # merged single-timeline ledger with the real seams on it
    merged = os.path.join(w, "ledger.merged.jsonl")
    evs = ledger.read_events(merged)
    kinds = {e["kind"] for e in evs}
    assert {"run", "phase", "compile", "search.state",
            "checkpoint.publish", "traffic.window"} <= kinds
    assert sum(1 for e in evs if e["kind"] == "compile"
               and e["status"] == "end") >= 1
    ts = [(e["ts"], str(e["proc"]), e["seq"]) for e in evs]
    assert ts == sorted(ts)                # totally ordered timeline

    # the report tools render it (as real subprocesses, like CI)
    env = dict(os.environ, PYTHONPATH=REPO)
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         "--metrics", m, "--ledger", w],
        capture_output=True, text=True, env=env, timeout=120)
    assert rep.returncode == 0, rep.stderr
    assert "GB/s" in rep.stdout and "% of target" in rep.stdout
    assert "p95" in rep.stdout and "host_schedule" in rep.stdout
    assert "Event timeline" in rep.stdout
    assert ("dispatch-bound" in rep.stdout
            or "bandwidth-meaningful" in rep.stdout)
    topp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "top.py"),
         "--workdir", w, "--once"],
        capture_output=True, text=True, env=env, timeout=120)
    assert topp.returncode == 0, topp.stderr
    assert "ledger events" in topp.stdout
