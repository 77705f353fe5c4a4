"""Program observatory (examl_tpu/obs/programs.py): the analysis-
availability matrix (partial / empty / raising XLA analyses degrade to
`program.analysis_missing.*` counters, never a crash), the registry /
stream / snapshot-embed plumbing, the model-vs-compiler drift gate,
live memory sampling, and the run_report snapshot diff."""

import json
import os
import sys

import numpy as np
import pytest

from examl_tpu import obs
from examl_tpu.obs import programs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_observatory(monkeypatch):
    """Each test starts with an empty registry and the default knobs
    (the observatory is process-global by design)."""
    monkeypatch.delenv("EXAML_PROGRAM_OBS", raising=False)
    monkeypatch.delenv("EXAML_DRIFT_TOL_PCT", raising=False)
    monkeypatch.delenv("EXAML_LEDGER_DIR", raising=False)
    monkeypatch.setenv("EXAML_MEM_SAMPLE_S", "0")
    programs.reset()
    yield
    programs.reset()
    # Scrub fake-device gauges out of the process-global registry: a
    # stale 1-byte mem.device.*.in_use would poison the memory
    # governor's usage signal for every later in-process test.
    reg = obs.registry()
    with reg._lock:
        for k in [k for k in reg._gauges if k.startswith("mem.device.")]:
            del reg._gauges[k]


def _counter(name):
    return obs.registry().counter(name)


# -- fakes spanning the analysis-availability matrix -------------------------


class FakeMem:
    def __init__(self, arg=100, out=50, temp=25, peak=None):
        if arg is not None:
            self.argument_size_in_bytes = arg
        if out is not None:
            self.output_size_in_bytes = out
        if temp is not None:
            self.temp_size_in_bytes = temp
        if peak is not None:
            self.peak_memory_in_bytes = peak


class FakeCompiled:
    """cost= list-of-dicts (jaxlib's shape), a plain dict, None, [] or
    an exception instance (raised); mem= FakeMem, None or exception;
    text= optimized-HLO text for the collective census (default: one
    all-reduce, the fabric's invariant shape) or an exception."""

    _HLO = 'ar = f64[] all-reduce(f64[] x), replica_groups={}'

    def __init__(self, cost=None, mem=None, text=_HLO):
        self._cost, self._mem, self._text = cost, mem, text

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost

    def memory_analysis(self):
        if isinstance(self._mem, Exception):
            raise self._mem
        return self._mem

    def as_text(self):
        if isinstance(self._text, Exception):
            raise self._text
        return self._text


class FakeLowered:
    def __init__(self, compiled):
        self._compiled = compiled
        self.compile_calls = 0

    def compile(self):
        self.compile_calls += 1
        if isinstance(self._compiled, Exception):
            raise self._compiled
        return self._compiled


# -- the matrix --------------------------------------------------------------


def test_record_full_analyses_populates_row_and_gauges():
    compiled = FakeCompiled(
        cost=[{"flops": 1e6, "bytes accessed": 4e5,
               "transcendentals": 300.0}],
        mem=FakeMem(arg=100, out=50, temp=25))
    row = programs.record("fast", ("fast", 1, 2), "fresh", 1.5,
                          compiled=compiled)
    assert row["family"] == "fast" and row["source"] == "fresh"
    assert row["flops"] == 1e6 and row["bytes_accessed"] == 4e5
    assert row["transcendentals"] == 300.0
    assert (row["argument_bytes"], row["output_bytes"],
            row["temp_bytes"]) == (100, 50, 25)
    assert row["peak_bytes"] == 175          # structural: arg+out+temp
    assert row["collectives"] == {"all-reduce": 1}
    assert row["collective_total"] == 1
    assert "missing" not in row
    snap = obs.registry().snapshot_light()
    assert snap["gauges"]["program.count"] == 1
    assert snap["gauges"]["program.bytes_accessed.fast"] == 4e5
    assert snap["gauges"]["program.flops.fast"] == 1e6
    assert snap["gauges"]["program.peak_bytes.fast"] == 175
    assert snap["gauges"]["program.collectives.fast"] == 1
    assert [r["family"] for r in programs.table()] == ["fast"]


def test_record_dict_cost_and_explicit_peak_win():
    row = programs.record(
        "scan", "k", "xla-cache", 0.2,
        compiled=FakeCompiled(cost={"flops": 5.0,
                                    "bytes_accessed": 7.0,
                                    "transcendentals": 1.0},
                              mem=FakeMem(peak=9999)))
    assert row["bytes_accessed"] == 7.0      # underscore key accepted
    assert row["peak_bytes"] == 9999         # explicit attr beats sum


@pytest.mark.parametrize("cost", [None, [], Exception("boom")])
def test_cost_analysis_unavailable_counts_not_crashes(cost):
    c0 = _counter("program.analysis_missing.cost_analysis")
    row = programs.record("fast", "k", "fresh", 0.1,
                          compiled=FakeCompiled(cost=cost,
                                                mem=FakeMem()))
    assert row is not None and "bytes_accessed" not in row
    assert _counter("program.analysis_missing.cost_analysis") == c0 + 1
    assert "cost_analysis" in row["missing"]
    assert row["peak_bytes"] == 175          # memory side still lands


def test_memory_analysis_unavailable_counts_not_crashes():
    c0 = _counter("program.analysis_missing.memory_analysis")
    row = programs.record(
        "fast", "k", "fresh", 0.1,
        compiled=FakeCompiled(cost=[{"flops": 1.0}],
                              mem=Exception("no mem analysis")))
    assert row["flops"] == 1.0 and "peak_bytes" not in row
    assert _counter("program.analysis_missing.memory_analysis") == c0 + 1


def test_partial_analyses_count_each_missing_field():
    c_b = _counter("program.analysis_missing.bytes_accessed")
    c_t = _counter("program.analysis_missing.temp_bytes")
    row = programs.record(
        "fast", "k", "fresh", 0.1,
        compiled=FakeCompiled(cost=[{"flops": 2.0}],       # no bytes key
                              mem=FakeMem(temp=None)))     # no temp attr
    assert row["flops"] == 2.0 and "bytes_accessed" not in row
    assert _counter("program.analysis_missing.bytes_accessed") == c_b + 1
    assert _counter("program.analysis_missing.temp_bytes") == c_t + 1
    assert row["peak_bytes"] == 150          # peak from the fields present
    assert set(row["missing"]) >= {"bytes_accessed", "temp_bytes"}


# What the v5e compiler writes where it splits a wide row gather by
# slicing the gather's operand (names and shapes of PERF.md, PR 32).
_SPLIT_GATHER = """
%fused_computation.294 (param_0.1: f32[279,1024,128,4,4]) -> (f32[279,128,128,4,4], f32[279,128,128,4,4]) {
  %param_0.1 = f32[279,1024,128,4,4]{2,4,3,1,0:T(4,128)} parameter(0)
  %mini-gather-slice.8 = f32[279,128,128,4,4]{2,4,3,1,0:T(4,128)} slice(%param_0.1), slice={[0:279], [0:128], [0:128], [0:4], [0:4]}
  %mini-gather-slice.9 = f32[279,128,128,4,4]{2,4,3,1,0:T(4,128)} slice(%param_0.1), slice={[0:279], [128:256], [0:128], [0:4], [0:4]}
  ROOT %tuple.7 = (f32[279,128,128,4,4], f32[279,128,128,4,4]) tuple(%mini-gather-slice.8, %mini-gather-slice.9)
}
"""


@pytest.mark.parametrize("text, n", [
    (_SPLIT_GATHER, 2),          # uses of a slice's name are not slices
    ("  %slice.3 = f32[8,128]{1,0} slice(%p), slice={[0:8], [0:128]}\n"
     "  %gather.1 = f32[8,128,4]{2,1,0} gather(%a, %i), "
     "slice_sizes={1,128,4}\n", 0),
    (FakeCompiled._HLO, 0)])
def test_operand_slices_counts_the_split_gathers_pieces(text, n):
    assert programs.operand_slices(text) == n
    row = programs.record("grad", ("grad", n), "fresh", 0.1,
                          compiled=FakeCompiled(text=text))
    assert row["operand_slices"] == n


def test_record_never_raises_on_hostile_compiled():
    class Hostile:
        def __getattr__(self, name):
            raise RuntimeError("deleted backend")

    c0 = _counter("program.analysis_missing.cost_analysis")
    m0 = _counter("program.analysis_missing.memory_analysis")
    k0 = _counter("program.analysis_missing.collectives")
    row = programs.record("fast", "k", "fresh", 0.1, compiled=Hostile())
    assert row is not None                   # degraded row, not a crash
    assert set(row["missing"]) == {"cost_analysis", "memory_analysis",
                                   "collectives"}
    assert _counter("program.analysis_missing.cost_analysis") == c0 + 1
    assert _counter("program.analysis_missing.memory_analysis") == m0 + 1
    assert _counter("program.analysis_missing.collectives") == k0 + 1


def test_off_mode_disables_everything(monkeypatch):
    monkeypatch.setenv(programs.ENV_VAR, "off")
    assert not programs.enabled()
    assert programs.record("fast", "k", "fresh", 0.1,
                           compiled=FakeCompiled()) is None
    assert programs.table() == []
    assert programs.model_vs_xla("chunk.x", 100) == "model"
    assert programs.sample_memory(devices=[], force=True) is False


def test_rows_mode_skips_the_analysis_compile(monkeypatch):
    monkeypatch.setenv(programs.ENV_VAR, "rows")
    low = FakeLowered(FakeCompiled(cost=[{"flops": 1.0}], mem=FakeMem()))
    row = programs.record("fast", "k", "fresh", 0.1, lowered=low)
    assert low.compile_calls == 0            # no second compile in rows mode
    assert row["family"] == "fast" and "flops" not in row


def test_deep_mode_compiles_the_lowering_and_times_it():
    low = FakeLowered(FakeCompiled(cost=[{"flops": 3.0,
                                          "bytes accessed": 8.0}],
                                   mem=FakeMem()))
    row = programs.record("fast", "k", "fresh", 0.1, lowered=low)
    assert low.compile_calls == 1
    assert row["bytes_accessed"] == 8.0
    t = obs.registry().snapshot_light()["timers"].get("program.obs")
    assert t and t["count"] >= 1


def test_deep_mode_compile_failure_is_a_counted_rung():
    c0 = _counter("program.analysis_missing.compile")
    row = programs.record("fast", "k", "fresh", 0.1,
                          lowered=FakeLowered(Exception("wedged")))
    assert row is not None and "bytes_accessed" not in row
    assert _counter("program.analysis_missing.compile") == c0 + 1


def test_record_loaded_is_the_exported_source():
    row = programs.record_loaded(
        "fast", "sig123",
        FakeCompiled(cost=[{"bytes accessed": 1e4, "flops": 1.0,
                            "transcendentals": 0.0}],
                     mem=FakeMem()))
    assert row["source"] == "exported" and row["compile_s"] == 0.0
    assert row["bytes_accessed"] == 1e4
    assert _counter("program.records.exported") >= 1


# -- drift gate ---------------------------------------------------------------


def _seed_fast_row(xla_bytes=1000.0):
    programs.record("fast", "k", "fresh", 0.1,
                    compiled=FakeCompiled(
                        cost=[{"bytes accessed": xla_bytes,
                               "flops": 1.0, "transcendentals": 0.0}],
                        mem=FakeMem()))


def test_model_vs_xla_within_tolerance_tags_xla():
    _seed_fast_row(1000.0)
    src = programs.model_vs_xla("chunk.s4.e0", 1100)
    assert src == "xla"
    g = obs.registry().snapshot_light()["gauges"]
    assert g["program.model_drift_pct.chunk.s4.e0"] == pytest.approx(
        10.0, abs=0.01)


def test_model_vs_xla_past_tolerance_counts_documented_divergence(
        monkeypatch):
    monkeypatch.setenv("EXAML_DRIFT_TOL_PCT", "25")
    _seed_fast_row(1000.0)
    c0 = _counter("program.model_drift_exceeded.chunk.x")
    src = programs.model_vs_xla("chunk.x", 2000)   # 100% drift
    assert src == "xla"                            # still compiler-backed
    assert _counter("program.model_drift_exceeded.chunk.x") == c0 + 1
    g = obs.registry().snapshot_light()["gauges"]
    assert g["program.model_drift_pct.chunk.x"] == pytest.approx(100.0)


def test_model_vs_xla_without_compiler_figure_stays_model():
    assert programs.model_vs_xla("chunk.x", 500) == "model"
    _seed_fast_row(1000.0)
    assert programs.model_vs_xla("chunk.y", 500) == "xla"   # fast serves it
    assert programs.model_vs_xla("grad.x", 500) == "model"  # no grad row
    assert programs.model_vs_xla("chunk.x", 0) == "model"   # no bytes


def test_tier_families_cover_every_engine_tier():
    assert set(programs.TIER_FAMILIES) == {
        "scan", "chunk", "universal", "grad"}


# -- live memory sampling -----------------------------------------------------


class FakeDevice:
    def __init__(self, dev_id, stats):
        self.id = dev_id
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_sample_memory_gauges_and_cpu_degradation():
    c0 = _counter("program.analysis_missing.memory_stats")
    ok = programs.sample_memory(devices=[
        FakeDevice(0, {"bytes_in_use": 100, "peak_bytes_in_use": 200,
                       "bytes_limit": 1000}),
        FakeDevice(1, None),                 # CPU-style: no stats
    ], force=True)
    assert ok is True
    g = obs.registry().snapshot_light()["gauges"]
    assert g["mem.device.0.in_use"] == 100
    assert g["mem.device.0.peak"] == 200
    assert g["mem.device.0.limit"] == 1000
    assert "mem.device.1.in_use" not in g
    # a stats-less device degrades to the host-RSS gauge (the memory
    # governor's CPU usage signal), not to the missing counter — that
    # only ticks when the RSS fallback is ALSO unavailable
    assert g["mem.host.rss"] > 0
    assert _counter("program.analysis_missing.memory_stats") == c0


def test_host_rss_fallback_reports_live_bytes():
    rss = programs.host_rss_bytes()
    assert rss is not None and rss > 1024 * 1024   # a real process RSS


def test_sample_memory_raising_backend_counts_and_returns_false():
    c0 = _counter("program.analysis_missing.memory_stats")
    assert programs.sample_memory(
        devices=[FakeDevice(0, Exception("backend gone"))],
        force=True) is False
    assert _counter("program.analysis_missing.memory_stats") == c0 + 1


def test_sample_memory_rate_limit(monkeypatch):
    monkeypatch.setenv("EXAML_MEM_SAMPLE_S", "3600")
    dev = [FakeDevice(0, {"bytes_in_use": 1})]
    assert programs.sample_memory(devices=dev) is True
    assert programs.sample_memory(devices=dev) is False   # throttled
    assert programs.sample_memory(devices=dev, force=True) is True


# -- jsonl stream -------------------------------------------------------------


def test_stream_writes_next_to_ledger_and_reads_torn(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("EXAML_LEDGER_DIR", str(tmp_path))
    programs.record("fast", "k1", "fresh", 0.1,
                    compiled=FakeCompiled(cost=[{"flops": 1.0}],
                                          mem=FakeMem()))
    programs.record("scan", "k2", "xla-cache", 0.2,
                    compiled=FakeCompiled())
    programs.reset()                         # close the stream handle
    (path,) = [p for p in os.listdir(tmp_path)
               if p.startswith("programs.p") and p.endswith(".jsonl")]
    with open(tmp_path / path, "a") as f:
        f.write('{"family": "torn...')       # killed-writer torn line
    rows = programs.read_stream(str(tmp_path / path))
    assert [r["family"] for r in rows] == ["fast", "scan"]
    assert programs.read_dir(str(tmp_path)) == rows
    assert programs.read_dir(str(tmp_path / "absent")) == []


def test_snapshot_embeds_the_programs_table():
    programs.record("fast", "k", "fresh", 0.1, compiled=FakeCompiled())
    snap = obs.snapshot()
    assert [r["family"] for r in snap["programs"]] == ["fast"]


# -- engine integration: real dispatches carry both bytes figures ------------


def _tiny_instance():
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data

    rng = np.random.default_rng(3)
    names = [f"t{i}" for i in range(10)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 300))
            for _ in names]
    inst = PhyloInstance(build_alignment_data(names, seqs))
    return inst, inst.random_tree(0)


def test_engine_dispatches_populate_observatory_with_drift(monkeypatch):
    """The acceptance fixture: chunk-tier (full traversal) and
    scan-tier (Newton smoothing) dispatches on the CPU parity fixture
    leave rows carrying BOTH the analytic model bytes (traffic
    counters) and XLA bytes-accessed, with the drift gauge computed
    per tier."""
    monkeypatch.setenv("EXAML_TRAFFIC_WINDOW_DISPATCHES", "1")
    monkeypatch.setenv("EXAML_TRAFFIC_WINDOW_WALL_S", "0")
    inst, tree = _tiny_instance()
    inst.evaluate(tree, full=True)
    # The second, compile-free traversal is the one whose traffic
    # window can close (windows exclude first-call compiles).
    inst.evaluate(tree, full=True)
    inst.makenewz(tree, tree.start.back, tree.start, tree.start.z,
                  maxiter=2)
    rows = programs.table()
    fams = {r["family"] for r in rows}
    assert "fast" in fams                    # chunk tier
    assert fams & {"newton", "sumtable", "trav_eval", "traverse"}
    with_bytes = [r for r in rows if r.get("bytes_accessed")]
    assert with_bytes, rows                  # compiler truth landed
    assert all(r["source"] in ("fresh", "xla-cache") for r in rows)
    snap = obs.registry().snapshot_light()
    assert snap["counters"]["engine.traffic_bytes"] > 0   # model side
    drift = {k: v for k, v in snap["gauges"].items()
             if k.startswith("program.model_drift_pct.")}
    assert drift, snap["gauges"]             # the gate actually ran
    src = {k: v for k, v in snap["gauges"].items()
           if k.startswith("engine.traffic_source_xla.")}
    assert src and all(v in (0.0, 1.0) for v in src.values())


@pytest.mark.parametrize("one_piece_sites, gathers", [(None, 5), (0, 0)])
def test_grad_row_names_how_its_rows_are_read(one_piece_sites, gathers,
                                              monkeypatch):
    """The gradient program's row, written at its first call: how many
    arena gathers its lowering handed the compiler (the five row reads
    of a narrow arena, none where rows are read by index) and how many
    operand slices the compiler made of them."""
    from examl_tpu.ops import kernels
    from examl_tpu.optimize.branch import tree_gradients
    if one_piece_sites is not None:
        monkeypatch.setattr(kernels, "ONE_PIECE_SITES", one_piece_sites)
    inst, tree = _tiny_instance()
    inst.evaluate(tree, full=True)
    tree_gradients(inst, tree)
    (row,) = [r for r in programs.table() if r["family"] == "grad"]
    assert row["arena_gathers"] == gathers
    assert row["operand_slices"] == 0        # no TPU compiler here
    assert "missing" not in row


@pytest.fixture
def export_env(tmp_path, monkeypatch):
    """Isolated persistent cache + export bank ON (test_export_bank's
    isolation pattern); restores the real cache config afterwards."""
    from examl_tpu import config
    from examl_tpu.ops import export_bank

    monkeypatch.setenv("EXAML_COMPILE_CACHE", str(tmp_path / "xla"))
    monkeypatch.setenv("EXAML_EXPORT_BANK", "on")
    assert config.enable_persistent_compilation_cache()
    export_bank.reset()
    yield
    export_bank.reset()
    monkeypatch.delenv("EXAML_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("EXAML_EXPORT_BANK", raising=False)
    config.enable_persistent_compilation_cache()


def test_exported_cold_start_populates_observatory(export_env):
    """Acceptance: a compile-count-free exported start still populates
    the observatory — the deserialized executable answers
    cost_analysis() directly (source "exported", zero compile
    seconds), which is how a zero-compile cold start stays
    observable."""
    import jax
    import jax.numpy as jnp
    from examl_tpu.ops import export_bank

    def impl(x):
        return (x @ x).sum()

    x = jnp.ones((8, 8))
    export_bank.wrap(jax.jit(impl), jax.jit(impl), "toy",
                     ("toy", 0))(x)          # populate the bank
    export_bank.reset()                      # cold-process emulation
    programs.reset()

    def boom(*a):
        raise AssertionError("fallback dispatched — artifact not served")

    out = export_bank.wrap(jax.jit(impl), boom, "toy", ("toy", 0))(x)
    assert float(out) == 512.0
    rows = [r for r in programs.table() if r["source"] == "exported"]
    assert len(rows) == 1 and rows[0]["family"] == "toy"
    assert rows[0]["compile_s"] == 0.0
    assert rows[0].get("bytes_accessed")     # analyses free off the load
    assert _counter("program.records.exported") >= 1


# -- run_report --diff --------------------------------------------------------


def _tools_import(name):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    return __import__(name)


def _snap(gbps=None, counters=None, timers=None, programs_=None):
    s = {"counters": dict(counters or {}), "gauges": {}, "timers": {}}
    for tier, (v, bound) in (gbps or {}).items():
        s["gauges"][f"engine.achieved_gbps.{tier}"] = v
        s["gauges"][f"engine.regime_dispatch_bound.{tier}"] = bound
    for name, p95 in (timers or {}).items():
        s["timers"][name] = {"count": 10, "total_s": p95 * 10,
                             "min_s": p95, "max_s": p95, "p95_s": p95}
    if programs_:
        s["programs"] = programs_
    return s


def test_diff_snapshots_ok_on_identical():
    run_report = _tools_import("run_report")
    s = _snap(gbps={"chunk.x": (50.0, 0.0)},
              counters={"engine.dispatch_count": 100},
              timers={"dispatch": 0.01})
    lines = []
    assert run_report.diff_snapshots(s, s, out=lines.append) == []
    assert any("DIFF VERDICT: OK" in ln for ln in lines)


def test_diff_snapshots_flags_gbps_drop_and_alarm_growth():
    run_report = _tools_import("run_report")
    old = _snap(gbps={"chunk.x": (50.0, 0.0)},
                counters={"engine.watchdog_barks": 0})
    new = _snap(gbps={"chunk.x": (30.0, 0.0)},              # -40%
                counters={"engine.watchdog_barks": 2})
    lines = []
    findings = run_report.diff_snapshots(old, new, out=lines.append)
    text = "\n".join(lines)
    assert len(findings) == 2
    assert "DIFF VERDICT: REGRESSION" in text
    assert "chunk.x" in text and "watchdog_barks" in text


def test_diff_snapshots_ignores_dispatch_bound_windows_and_noise():
    run_report = _tools_import("run_report")
    old = _snap(gbps={"scan.x": (50.0, 1.0)},    # dispatch-bound: not
                timers={"dispatch": 0.010})      # a bandwidth number
    new = _snap(gbps={"scan.x": (10.0, 1.0)},
                timers={"dispatch": 0.011})      # +10% < 25% tolerance
    assert run_report.diff_snapshots(old, new, out=lambda s: None) == []


def test_diff_snapshots_flags_latency_and_program_bytes_growth():
    run_report = _tools_import("run_report")
    old = _snap(timers={"dispatch": 0.010},
                programs_=[{"family": "fast", "bytes_accessed": 1000}])
    new = _snap(timers={"dispatch": 0.020},
                programs_=[{"family": "fast", "bytes_accessed": 2000}])
    findings = run_report.diff_snapshots(old, new, out=lambda s: None)
    joined = " ".join(findings)
    assert "dispatch" in joined and "fast" in joined
