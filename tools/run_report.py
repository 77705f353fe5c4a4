#!/usr/bin/env python
"""Render the roofline measurement report from a run's artifacts.

The chip-window contract (ROADMAP §1, ROOFLINE.md "Measurement
protocol"): every run — CLI search, supervised gang — leaves a
metrics snapshot and a run ledger, and THIS tool turns them into the
human report: per-tier achieved GB/s against the 306 GB/s roofline
target with the dispatch-bound vs bandwidth-meaningful regime verdict,
latency-histogram quantiles for the hot timers, and the merged event
timeline.

    python tools/run_report.py --metrics m.json [--ledger DIR|FILE]
                               [--bench FLEET_BENCH.json] [--timeline N]

stdlib-only (plus the jax-free examl_tpu.obs helpers): runnable on any
host with no backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from examl_tpu.obs import ledger as _ledger      # noqa: E402
from examl_tpu.obs import traffic as _traffic    # noqa: E402

# Timers whose quantiles the report always surfaces when present
# (ISSUE: dispatch, host_schedule, compile families, CLI phases and
# the bank compile/warm phases).
_KEY_TIMER_PREFIXES = ("dispatch", "host_schedule",
                       "bank.compile.", "bank.warm.",
                       "bank.export_load_seconds",
                       "bank.export_write_seconds",
                       "engine.compile_seconds.", "engine.grad_pass",
                       "phase.", "engine.first_call", "program.obs")


def _fmt_s(v) -> str:
    if v is None:
        return "-"
    if v >= 1.0:
        return f"{v:.2f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.0f}us"


def load_metrics(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_ledger(path: str) -> list:
    """Events from a merged ledger file, a single rank file, or a
    directory (merged IN MEMORY on the fly — the tool must work on a
    crashed run's directory where rank 0 never reached its exit merge,
    and must never write into a possibly read-only artifact dir)."""
    if os.path.isdir(path):
        return _ledger.read_dir(path)
    return _ledger.read_events(path)


# -- roofline section --------------------------------------------------------


def tier_rows_from_metrics(snap: dict) -> list:
    """[(tier, gbps, regime, source, drift_pct)] from the engine's
    windowed gauges.  `source` is the bytes-figure provenance tag
    ("xla" when the program observatory holds a compiler bytes figure
    for the serving tier, "model" otherwise) and `drift_pct` the
    model-vs-compiler reconciliation gauge for the tier, when set."""
    gauges = snap.get("gauges") or {}
    rows = []
    for name, gbps in sorted(gauges.items()):
        if not name.startswith("engine.achieved_gbps."):
            continue
        tier = name[len("engine.achieved_gbps."):]
        db = gauges.get(f"engine.regime_dispatch_bound.{tier}")
        regime = ("dispatch-bound" if db else
                  "bandwidth-meaningful" if db is not None else "?")
        xla = gauges.get(f"engine.traffic_source_xla.{tier}")
        source = ("xla" if xla else "model" if xla is not None else None)
        drift = gauges.get(
            f"program.model_drift_pct.{tier.split('.', 1)[0]}")
        rows.append((tier, float(gbps), regime, source, drift))
    return rows


def render_roofline(out, rows: list, source: str) -> None:
    target = _traffic.ROOFLINE_TARGET_GBPS
    out(f"Roofline ({source}; target {target:.0f} GB/s sustained "
        "= the >=10x goal):")
    if not rows:
        out("  (no achieved-GB/s evidence in this artifact)")
        return
    for tier, gbps, regime, src, drift in rows:
        pct = 100.0 * gbps / target
        flag = ("" if regime == "bandwidth-meaningful"
                else "  [NOT a bandwidth number]")
        tag = ""
        if src is not None:
            tag = f"  source={src}"
            if drift is not None:
                tag += f" drift={drift:.1f}%"
        out(f"  {tier:24s} {gbps:10.2f} GB/s  ({pct:6.2f}% of target)"
            f"  {regime}{flag}{tag}")


# -- program observatory -----------------------------------------------------


def _fmt_bytes(v) -> str:
    if v is None:
        return "-"
    v = float(v)
    if v >= 1e9:
        return f"{v / 1e9:.2f}G"
    if v >= 1e6:
        return f"{v / 1e6:.1f}M"
    if v >= 1e3:
        return f"{v / 1e3:.1f}K"
    return f"{v:.0f}"


def render_programs(out, snap: dict) -> None:
    """The Programs table (obs/programs.py): one row per compiled or
    deserialized executable with its compile source and the compiler's
    own cost/memory accounting — the memory column is XLA's structural
    peak (argument+output+temp), the figure the analytic model cannot
    provide."""
    rows = snap.get("programs") or []
    if not rows:
        return
    out("")
    out("Programs (compiler-truth observatory, obs/programs.py):")
    out(f"  {'family':12s} {'source':9s} {'compile':>8s} {'flops':>8s} "
        f"{'bytes_acc':>9s} {'arg':>7s} {'out':>7s} {'tmp':>7s} "
        f"{'peak':>7s}  key")
    for r in rows:
        out(f"  {str(r.get('family', '?')):12s} "
            f"{str(r.get('source', '?')):9s} "
            f"{_fmt_s(r.get('compile_s')):>8s} "
            f"{_fmt_bytes(r.get('flops')):>8s} "
            f"{_fmt_bytes(r.get('bytes_accessed')):>9s} "
            f"{_fmt_bytes(r.get('argument_bytes')):>7s} "
            f"{_fmt_bytes(r.get('output_bytes')):>7s} "
            f"{_fmt_bytes(r.get('temp_bytes')):>7s} "
            f"{_fmt_bytes(r.get('peak_bytes')):>7s}  "
            f"{str(r.get('key', ''))[:28]}")
    c = snap.get("counters") or {}
    srcs = {k[len("program.records."):]: int(v) for k, v in c.items()
            if k.startswith("program.records.")}
    if srcs:
        out("  sources                    "
            + "  ".join(f"{s}={v}" for s, v in sorted(srcs.items())))
    missing = {k[len("program.analysis_missing."):]: int(v)
               for k, v in c.items()
               if k.startswith("program.analysis_missing.")}
    if missing:
        out("  analyses degraded          "
            + "  ".join(f"{f}={v}" for f, v in sorted(missing.items()))
            + "  (fallback-not-crash ladder: the analytic model "
              "carried these)")
    exceeded = {k[len("program.model_drift_exceeded."):]: int(v)
                for k, v in c.items()
                if k.startswith("program.model_drift_exceeded.")}
    if exceeded:
        out("  drift gate                 "
            + "  ".join(f"{t}={v}" for t, v in sorted(exceeded.items()))
            + "  dispatches past tolerance (documented divergence — "
              "see ROOFLINE.md 'Compiler-truth bytes')")
    colls = {k[len("program.collectives."):]: int(v)
             for k, v in (snap.get("gauges") or {}).items()
             if k.startswith("program.collectives.")}
    if colls:
        out("  collectives                "
            + "  ".join(f"{f}={v}" for f, v in sorted(colls.items()))
            + "  (cross-shard ops in the compiled HLO; a fabric "
              "program carries exactly 1 — the site-axis lnL "
              "all-reduce)")


def render_jit_events(out, snap: dict) -> None:
    """What JAX traced, lowered and compiled (obs/programs.py): the
    `jax.*` counters of every program, guarded or eager, then the newest
    events with the span each fell in — what compiled late, and inside
    which dispatch, without a profiler."""
    c = snap.get("counters") or {}
    events = snap.get("jit_events") or []
    if not events and "jax.lower_count" not in c:
        return
    out("")
    out("JIT events (jax.monitoring; seconds of outermost events only):")
    out("  " + "  ".join(
        f"{kind}={int(c.get(f'jax.{kind}_count', 0))}"
        f"/{_fmt_s(c.get(f'jax.{kind}_seconds', 0.0))}"
        for kind in ("trace", "lower", "backend_compile"))
        + f"  cache hits={int(c.get('jax.cache_hits', 0))}"
        f" misses={int(c.get('jax.cache_misses', 0))}"
        f" retrieval={_fmt_s(c.get('jax.cache_retrieval_seconds', 0.0))}")
    if events:
        out(f"  {'event':16s} {'seconds':>8s}  {'fun_name':28s} span")
    for e in events:
        out(f"  {str(e.get('event', '?')):16s} "
            f"{_fmt_s(e.get('seconds')):>8s}  "
            f"{str(e.get('fun_name') or '-')[:28]:28s} "
            f"{e.get('span') or '-'}")


def render_memory(out, snap: dict) -> None:
    """Live HBM telemetry: mem.device.<k>.* allocator gauges
    cross-checked against the modeled CLV arena
    (engine.clv_arena_bytes.*).  A backend with no allocator stats
    (CPU) shows the degradation counter instead of fake numbers."""
    g = snap.get("gauges") or {}
    devs = {}
    for k, v in g.items():
        if not k.startswith("mem.device."):
            continue
        rest = k[len("mem.device."):]
        if "." not in rest:
            continue
        dev, field = rest.split(".", 1)
        devs.setdefault(dev, {})[field] = v
    arena = sum(v for k, v in g.items()
                if k.startswith("engine.clv_arena_bytes."))
    c = snap.get("counters") or {}
    missing = int(c.get("program.analysis_missing.memory_stats", 0))
    rss = g.get("mem.host.rss")
    budget = g.get("mem.budget_bytes")
    # Memory-governor evidence (resilience/memgov.py): admission and
    # recovery counters next to the budget they enforced.
    gov = [(label, int(c.get(k, 0)))
           for label, k in (("admission denials", "mem.admission_denials"),
                            ("admissions unknown", "mem.admission_unknown"),
                            ("evictions", "mem.evictions"),
                            ("oom events", "mem.oom_events"),
                            ("oom retries (recovered)", "mem.oom_retries"))
           if c.get(k)]
    if not devs and not arena and not rss and not gov \
            and budget is None:
        return
    out("")
    out("Device memory (live allocator stats vs modeled arena):")
    for dev in sorted(devs):
        d = devs[dev]
        line = (f"  device {dev:4s} "
                f"in_use={_fmt_bytes(d.get('in_use'))} "
                f"peak={_fmt_bytes(d.get('peak'))} "
                f"limit={_fmt_bytes(d.get('limit'))}")
        if arena and d.get("in_use"):
            line += (f"  (CLV arena {_fmt_bytes(arena)} = "
                     f"{100.0 * arena / d['in_use']:.0f}% of in_use)")
        out(line)
    if not devs:
        out(f"  CLV arena (modeled)        {_fmt_bytes(arena)}"
            + (f"  host RSS {_fmt_bytes(rss)}" if rss else "")
            + (f"  (no allocator stats on this backend; "
               f"memory_stats degraded x{missing})" if missing else ""))
    if budget is not None or gov:
        out("")
        out("Memory governor (admission budget, resilience/memgov.py):")
        if budget is not None:
            used = None
            for d in devs.values():
                if d.get("in_use"):
                    used = max(used or 0, d["in_use"])
            if used is None:
                used = rss or arena or None
            out(f"  budget                     {_fmt_bytes(budget)}"
                + (f"  (live usage {_fmt_bytes(used)} = "
                   f"{100.0 * used / budget:.0f}%)"
                   if used and budget else ""))
        for label, v in gov:
            out(f"  {label:26s} {v}")


# -- timers / histogram quantiles -------------------------------------------


def render_timers(out, snap: dict) -> None:
    timers = snap.get("timers") or {}
    keys = [k for k in sorted(timers)
            if any(k == p or k.startswith(p)
                   for p in _KEY_TIMER_PREFIXES)]
    if not keys:
        return
    out("")
    out("Latency quantiles (log-bucketed histograms, ~6% bucket "
        "resolution):")
    out(f"  {'timer':32s} {'count':>8s} {'p50':>10s} {'p95':>10s} "
        f"{'p99':>10s} {'max':>10s}")
    for k in keys:
        t = timers[k]
        out(f"  {k:32s} {t.get('count', 0):>8d} "
            f"{_fmt_s(t.get('p50_s')):>10s} {_fmt_s(t.get('p95_s')):>10s} "
            f"{_fmt_s(t.get('p99_s')):>10s} {_fmt_s(t.get('max_s')):>10s}")


def render_fleet(out, snap: dict, events: list) -> None:
    """Fleet serving evidence: the `fleet.*` counters/gauges plus the
    job timeline summary (job.start / job.done / batch.dispatch ledger
    events) for `-b` / `-N` / `--serve` runs."""
    c = snap.get("counters") or {}
    g = snap.get("gauges") or {}
    jc = {"job.start": 0, "job.done": 0, "job.failed": 0,
          "job.quarantined": 0, "job.rejected": 0, "batch.dispatch": 0}
    for ev in events:
        k = ev.get("kind")
        if k in jc:
            jc[k] += 1
    if not (any(k.startswith("fleet.") for k in c)
            or any(k.startswith("fleet.") for k in g)
            or any(jc.values())):
        return
    out("")
    out("Fleet (many-tree batched serving):")
    total = int(g.get("fleet.jobs_total", 0))
    done = int(g.get("fleet.jobs_done", 0))
    out(f"  jobs done                  {done}/{total}"
        + (f"  ({int(c['fleet.jobs_failed'])} failed)"
           if c.get("fleet.jobs_failed") else ""))
    if c.get("fleet.batches"):
        trees = c.get("fleet.trees_evaluated", 0)
        secs = c.get("fleet.eval_seconds", 0.0)
        out(f"  batches                    {int(c['fleet.batches'])}"
            f"  ({trees:.0f} tree evals in {secs:.2f}s eval wall)")
    if g.get("fleet.trees_per_sec") is not None:
        out(f"  trees_per_sec (last batch) "
            f"{g['fleet.trees_per_sec']:.3f}")
    if g.get("fleet.batch_occupancy") is not None:
        out(f"  batch occupancy            "
            f"{g['fleet.batch_occupancy']:.2f}")
    # Job-level fault domains: quarantine/reject/retry/bisect evidence
    # (a healthy serving run shows none of these rows' counters).
    fd = [(label, int(c.get(k, 0)))
          for label, k in (("quarantined", "fleet.quarantined"),
                           ("rejected", "fleet.rejected"),
                           ("job_retries", "fleet.job_retries"),
                           ("bisect_dispatches",
                            "fleet.bisect_dispatches"),
                           ("journal_errors", "fleet.journal_errors"))
          if c.get(k)]
    if fd:
        out("  fault domains              "
            + "  ".join(f"{label}={v}" for label, v in fd))
    # Tree-axis device sharding (ISSUE 14): one evaluation lane per
    # local device — per-lane dispatch counters plus the degraded-lane
    # evidence (a lane that failed init, never an abort).
    lanes = [(k.rsplit(".", 1)[-1], int(v))
             for k, v in sorted(c.items())
             if k.startswith("fleet.device_dispatches.")]
    if lanes or g.get("fleet.devices"):
        jobs_per = {k.rsplit(".", 1)[-1]: int(v)
                    for k, v in c.items()
                    if k.startswith("fleet.device_jobs.")}
        out(f"  device lanes               "
            f"{int(g.get('fleet.devices', len(lanes) or 1))}"
            + (f"  degraded={int(c['fleet.device_degraded'])}"
               if c.get("fleet.device_degraded") else "")
            + ("  " + "  ".join(
                f"{d}={n}({jobs_per.get(d, 0)}j)" for d, n in lanes)
               if lanes else ""))
    # The likelihood fabric (ISSUE 17): declared (sites, tree) mesh
    # shape plus per-tree-slice dispatch/job counters — every slice's
    # row of each batch, so an idle slice (occupancy rounding) is
    # visible next to the lanes it replaced.
    ms = g.get("engine.mesh_site_shards")
    mt = g.get("engine.mesh_tree_shards") or g.get(
        "fleet.mesh_tree_shards")
    slices = [(k.rsplit(".", 1)[-1], int(v))
              for k, v in sorted(c.items())
              if k.startswith("fleet.mesh_slice_dispatches.")]
    if ms or mt or slices:
        sjobs = {k.rsplit(".", 1)[-1]: int(v)
                 for k, v in c.items()
                 if k.startswith("fleet.mesh_slice_jobs.")}
        out(f"  likelihood fabric          "
            f"{int(ms or 1)}x{int(mt or 1)} (sites x tree)"
            f"  batches={int(c.get('fleet.mesh_batches', 0))}"
            + ("  " + "  ".join(
                f"{t}={n}({sjobs.get(t, 0)}j)" for t, n in slices)
               if slices else ""))
    # Rank-level fault domain (leased gangs): lease traffic + the
    # recovery evidence — reaped = a dead rank's in-flight jobs
    # re-served; lost = completions fenced off (exactly-once guard);
    # absorbed = peers' journaled results folded in.
    lease = [(label, int(c.get(k, 0)))
             for label, k in (("acquired", "fleet.leases_acquired"),
                              ("reaped", "fleet.leases_reaped"),
                              ("lost", "fleet.leases_lost"),
                              ("errors", "fleet.lease_errors"),
                              ("absorbed", "fleet.jobs_absorbed"))
             if c.get(k)]
    if lease:
        out("  job leases                 "
            + "  ".join(f"{label}={v}" for label, v in lease))
    # Batched-universal serving (opt-in EXAML_FLEET_UNIBATCH=1):
    # uni_batches = mixed-profile batches through the vmapped select_n
    # program; universal_retrace = solo novel-profile dispatches a
    # batched program would have merged (the re-measurement evidence).
    if c.get("fleet.uni_batches") or c.get("fleet.universal_retrace"):
        out("  batched universal          "
            f"uni_batches={int(c.get('fleet.uni_batches', 0))}"
            f"  universal_retrace="
            f"{int(c.get('fleet.universal_retrace', 0))}")
    # Universal-interpreter serving: how many NOVEL profiles arrived
    # (each one would have been a silent first-call compile before the
    # topology-as-data tier) and how many dispatches the interpreter
    # took — profile_misses > 0 with universal_dispatches > 0 and zero
    # unbanked first calls IS the zero-recompile-serving evidence.
    if c.get("fleet.profile_misses") or c.get("engine.universal_dispatches"):
        out("  universal interpreter      "
            f"profile_misses={int(c.get('fleet.profile_misses', 0))}"
            f"  dispatches={int(c.get('engine.universal_dispatches', 0))}"
            f"  unbanked_first_calls="
            f"{int(c.get('engine.first_calls.unbanked', 0))}")
    if any(jc.values()):
        out("  job timeline events        "
            + "  ".join(f"{k}={v}" for k, v in sorted(jc.items()) if v))


def render_bank(out, snap: dict) -> None:
    """AOT program-bank evidence: how many families were enumerated,
    compiled where, degraded or skipped.  A chip round reads this next
    to `engine.first_calls.*` to confirm the search phase ran with zero
    unplanned first-call compiles."""
    c = snap.get("counters") or {}
    rows = [(label, int(c[k]))
            for label, k in (("families enumerated", "bank.families"),
                             ("banked (compiled)", "bank.banked"),
                             ("served from exported bank",
                              "bank.exported_families"),
                             ("skipped (already cached)", "bank.skipped"),
                             ("compile timeouts", "bank.timeouts"),
                             ("worker errors", "bank.errors"),
                             ("worker wedges", "bank.worker_wedges"),
                             ("degraded to fallback env", "bank.fallbacks"),
                             ("cache disabled (no_cache)", "bank.no_cache"),
                             ("sharded in-process residual",
                              "bank.sharded_residual_families"),
                             ("mesh shardings declared",
                              "bank.mesh_declared"),
                             ("warm-phase errors", "bank.warm_errors"))
            if c.get(k)]
    exp = [(label, int(c[k]))
           for label, k in (("hits", "bank.export.hits"),
                            ("misses", "bank.export.misses"),
                            ("writes", "bank.export.writes"),
                            ("write errors", "bank.export.write_errors"),
                            ("corrupt", "bank.export.corrupt"),
                            ("quarantined", "bank.export.quarantined"))
           if c.get(k)]
    rejected = {k[len("bank.export.rejected."):]: int(v)
                for k, v in c.items()
                if k.startswith("bank.export.rejected.") and v}
    if not rows and not exp and not rejected:
        return
    out("")
    out("Program bank (AOT banking phase):")
    for label, v in rows:
        out(f"  {label:28s} {v:,d}")
    if c.get("bank.wall_seconds"):
        out(f"  {'bank wall':28s} {c['bank.wall_seconds']:.2f}s")
    fc = [(label, int(c[k]))
          for label, k in (("banked", "engine.first_calls.banked"),
                           ("unbanked", "engine.first_calls.unbanked"),
                           ("degraded in-process",
                            "engine.first_calls.degraded_inprocess"),
                           ("sharded in-process",
                            "engine.first_calls.inprocess_sharded"))
          if c.get(k)]
    if fc:
        out("  first calls                "
            + "  ".join(f"{label}={v}" for label, v in fc))
    # Exported-artifact ladder evidence: hits with zero compiles is the
    # zero-compile cold start; rejected.<reason> names exactly which
    # rung each bad artifact fell through (and quarantined says it
    # cannot re-fail the next restart).
    if exp:
        out("  exported artifacts         "
            + "  ".join(f"{label}={v}" for label, v in exp))
    if rejected:
        out("  export rejections          "
            + "  ".join(f"{r}={v}" for r, v in sorted(rejected.items())))
    t = (snap.get("timers") or {}).get("bank.export_load_seconds")
    if t:
        out(f"  export load                {t['count']} loads, "
            f"total {t['total_s']:.3f}s, p95 {t['p95_s'] * 1e3:.1f}ms")


def render_counters(out, snap: dict) -> None:
    c = snap.get("counters") or {}
    picks = [
        ("engine.dispatch_count", "device dispatches"),
        ("engine.traversal_entries", "traversal entries"),
        ("engine.grad_pass_dispatches", "whole-tree gradient passes"),
        ("optimize.grad_smooth_sweeps", "gradient smoothing sweeps"),
        ("optimize.grad_smooth_fallbacks", "gradient->NR fallbacks"),
        ("optimize.grad_smooth_unconverged",
         "gradient sweep budgets exhausted"),
        ("fleet.grad_smooth_sweeps", "fleet gradient sweeps"),
        ("fleet.grad_smooth_unconverged",
         "fleet gradient budgets exhausted"),
        ("engine.traffic_bytes", "modeled HBM bytes"),
        ("engine.compile_count", "compiles"),
        ("engine.compile_seconds", "compile seconds"),
        ("engine.watchdog_barks", "watchdog barks"),
        ("search.spr_cycles", "SPR cycles"),
        ("search.fast_cycles", "fast SPR cycles"),
        ("search.thorough_cycles", "thorough SPR cycles"),
        ("search.scan_dispatches", "batched-scan dispatches"),
        ("search.scan_candidates", "batched-scan candidates"),
        ("search.model_opt_rounds", "model-opt rounds"),
        ("checkpoint.gang_publishes", "gang checkpoint publishes"),
        ("checkpoint.partial_cycles_gced", "partial cycles GCed"),
        ("resilience.heartbeats", "heartbeats published"),
        ("resilience.preempt_checkpoints", "preempt checkpoints"),
        ("resilience.restarts", "supervisor restarts"),
        ("resilience.heartbeat_stalls", "heartbeat stalls"),
    ]
    lines = [(label, c[k]) for k, label in picks if c.get(k)]
    g = snap.get("gauges") or {}
    if g.get("engine.dispatches_per_smoothing_round") is not None:
        # The ROADMAP §5 acceptance gauge: O(1) in gradient mode, O(n)
        # on the per-branch Newton path.
        lines.append(("dispatches / smoothing round",
                      g["engine.dispatches_per_smoothing_round"]))
    faults = {k[len("faults.fired."):]: v for k, v in c.items()
              if k.startswith("faults.fired.")}
    if not (lines or faults):
        return
    out("")
    out("Run evidence (counters):")
    for label, v in lines:
        if label == "modeled HBM bytes":
            out(f"  {label:28s} {v / 1e9:,.2f} GB")
        else:
            out(f"  {label:28s} {v:,.0f}")
    if faults:
        out("  faults fired             "
            + "  ".join(f"{k}={int(v)}" for k, v in sorted(faults.items())))


# -- timeline ----------------------------------------------------------------


def _event_line(ev: dict) -> str:
    ts = ev.get("ts", 0) / 1e6
    kind = ev.get("kind", "?")
    return (f"  {ts:17.6f}  p{ev.get('proc')}  {kind:24s} "
            f"{_ledger.format_fields(ev)}")


def _drop_matched_compile_starts(events: list) -> list:
    """Compile start events whose end arrived are timeline noise (the
    end carries the duration) — but an UNMATCHED start is the wedge
    postmortem itself: the rank's last event naming the family the run
    died compiling.  Drop only starts with a matching end."""
    ends: dict = {}
    for ev in events:
        if ev.get("kind") == "compile" and ev.get("status") == "end":
            key = (ev.get("proc"), ev.get("family"))
            ends[key] = ends.get(key, 0) + 1
    kept = []
    for ev in events:
        if ev.get("kind") == "compile" and ev.get("status") == "start":
            key = (ev.get("proc"), ev.get("family"))
            if ends.get(key, 0) > 0:
                ends[key] -= 1        # matched: its end is on the line
                continue
        kept.append(ev)
    return kept


def render_timeline(out, events: list, limit: int) -> None:
    if not events:
        return
    out("")
    interesting = _drop_matched_compile_starts(events)
    n = len(interesting)
    out(f"Event timeline ({n} events"
        + (f"; showing last {limit}" if n > limit else "") + "):")
    t0 = events[0].get("ts", 0) / 1e6
    out(f"  (epoch seconds; run began at {t0:.3f})")
    for ev in interesting[-limit:]:
        out(_event_line(ev))


def render(metrics: dict, events: list, bench: dict,
           out=print, timeline: int = 60) -> None:
    out("=" * 72)
    out("examl-tpu run report (roofline flight recorder)")
    out("=" * 72)
    if metrics.get("partial"):
        out("NOTE: metrics snapshot is a MID-RUN flush (the process was "
            "killed before its exit snapshot) — counters are last-known, "
            "not final.")
    rows = tier_rows_from_metrics(metrics)
    if rows:
        render_roofline(out, rows, "in-engine windowed gauges")
    if bench and bench.get("bench") == "fleet":
        out("")
        out("Fleet bench row (tools/fleet_smoke.py):")
        out(f"  trees_per_sec {bench.get('trees_per_sec')}  "
            f"(single-tree {bench.get('single_trees_per_sec')}/s; "
            f"speedup {bench.get('speedup_vs_single')}x vs target "
            f"{bench.get('target_speedup')}x = 0.7*N, "
            + ("MET" if bench.get("meets_target") else "not met")
            + f"; occupancy {bench.get('batch_occupancy')})")
    if not rows and not bench:
        render_roofline(out, [], "no artifact")
    render_timers(out, metrics)
    render_programs(out, metrics)
    render_jit_events(out, metrics)
    render_memory(out, metrics)
    render_bank(out, metrics)
    render_fleet(out, metrics, events)
    render_counters(out, metrics)
    render_timeline(out, events, timeline)


# -- snapshot diff (the perf-regression sentinel) ----------------------------

# Counters whose mere GROWTH between two comparable runs is a finding
# (error/degradation evidence, not workload scale).
_DIFF_ALARM_COUNTERS = (
    "engine.watchdog_barks",
    "bank.export.write_errors", "bank.export.corrupt",
    "bank.export.quarantined", "fleet.quarantined", "fleet.rejected",
    "engine.first_calls.unbanked",
)
# Context counters rendered for scale calibration (a diff of runs with
# wildly different dispatch counts is a workload change, not a perf
# regression).
_DIFF_SCALE_COUNTERS = (
    "engine.dispatch_count", "engine.compile_count",
    "engine.compile_seconds", "engine.traffic_bytes",
)


def _pct(old: float, new: float):
    if not old:
        return None
    return 100.0 * (new - old) / old


def _fmt_pct(p) -> str:
    return "   -  " if p is None else f"{p:+6.1f}%"


def diff_snapshots(old: dict, new: dict, out=print,
                   gbps_tol_pct: float = 10.0,
                   latency_tol_pct: float = 25.0) -> list:
    """Compare two `--metrics` snapshots — counters, timer quantiles,
    per-tier achieved GB/s, program table — and return the list of
    regression findings (empty = OK).  The verdict line is the last
    line printed, so a CI log tail always carries it."""
    findings = []
    oc = old.get("counters") or {}
    nc = new.get("counters") or {}

    out("Snapshot diff (OLD -> NEW):")
    out("  scale:")
    for k in _DIFF_SCALE_COUNTERS:
        if oc.get(k) or nc.get(k):
            out(f"    {k:36s} {oc.get(k, 0):>12,.0f} -> "
                f"{nc.get(k, 0):>12,.0f}  "
                f"{_fmt_pct(_pct(oc.get(k, 0), nc.get(k, 0)))}")
    for k in _DIFF_ALARM_COUNTERS:
        delta = nc.get(k, 0) - oc.get(k, 0)
        if delta > 0:
            findings.append(f"{k} grew by {delta:.0f}")
            out(f"    {k:36s} {oc.get(k, 0):>12,.0f} -> "
                f"{nc.get(k, 0):>12,.0f}  REGRESSION")

    # Per-tier achieved GB/s: a drop past tolerance on a tier both
    # snapshots measured is the roofline regression this sentinel
    # exists for (dispatch-bound rows compare but cannot regress —
    # their number is a launch-floor artifact by definition).
    o_rows = {t: (g, r) for t, g, r, _, _ in tier_rows_from_metrics(old)}
    n_rows = {t: (g, r) for t, g, r, _, _ in tier_rows_from_metrics(new)}
    tiers = sorted(set(o_rows) | set(n_rows))
    if tiers:
        out("  per-tier achieved GB/s:")
    for t in tiers:
        og, orr = o_rows.get(t, (None, None))
        ng, nrr = n_rows.get(t, (None, None))
        if og is None or ng is None:
            out(f"    {t:28s} "
                f"{'-' if og is None else f'{og:.2f}':>10s} -> "
                f"{'-' if ng is None else f'{ng:.2f}':>10s}  "
                "(tier present in one snapshot only)")
            continue
        p = _pct(og, ng)
        flag = ""
        if (p is not None and p < -gbps_tol_pct
                and orr == "bandwidth-meaningful"
                and nrr == "bandwidth-meaningful"):
            flag = "  REGRESSION"
            findings.append(f"tier {t} gbps {og:.2f} -> {ng:.2f} "
                            f"({p:+.1f}%)")
        out(f"    {t:28s} {og:>10.2f} -> {ng:>10.2f}  {_fmt_pct(p)}"
            f"  [{nrr}]{flag}")

    # Timer quantiles: p95 growth past tolerance on the key timers.
    ot = old.get("timers") or {}
    nt = new.get("timers") or {}
    keys = [k for k in sorted(set(ot) & set(nt))
            if any(k == p or k.startswith(p)
                   for p in _KEY_TIMER_PREFIXES)]
    if keys:
        out("  timer p95:")
    for k in keys:
        op, np_ = ot[k].get("p95_s"), nt[k].get("p95_s")
        if op is None or np_ is None:
            continue
        p = _pct(op, np_)
        flag = ""
        if p is not None and p > latency_tol_pct and np_ > 1e-4:
            flag = "  REGRESSION"
            findings.append(f"timer {k} p95 {_fmt_s(op)} -> "
                            f"{_fmt_s(np_)} ({p:+.1f}%)")
        out(f"    {k:36s} {_fmt_s(op):>10s} -> {_fmt_s(np_):>10s}  "
            f"{_fmt_pct(p)}{flag}")

    # Program table: per-family compiler-truth bytes must be stable
    # between comparable runs — a moved bytes_accessed is a program
    # (or model) change arriving with its cause attached.
    op_rows = {r.get("family"): r for r in old.get("programs") or []}
    np_rows = {r.get("family"): r for r in new.get("programs") or []}
    fams = sorted(set(op_rows) | set(np_rows))
    if fams:
        out("  programs (bytes_accessed per family):")
    for fam in fams:
        ob = (op_rows.get(fam) or {}).get("bytes_accessed")
        nb = (np_rows.get(fam) or {}).get("bytes_accessed")
        p = _pct(ob or 0, nb or 0) if ob and nb else None
        note = ("new family" if fam not in op_rows else
                "family gone" if fam not in np_rows else "")
        flag = ""
        if p is not None and abs(p) > gbps_tol_pct:
            flag = "  REGRESSION"
            findings.append(f"program {fam} bytes_accessed "
                            f"{_fmt_bytes(ob)} -> {_fmt_bytes(nb)} "
                            f"({p:+.1f}%)")
        out(f"    {str(fam):28s} {_fmt_bytes(ob):>10s} -> "
            f"{_fmt_bytes(nb):>10s}  {_fmt_pct(p)}  {note}{flag}")

    if findings:
        out(f"DIFF VERDICT: REGRESSION ({len(findings)} finding(s))")
        for f in findings:
            out(f"  - {f}")
    else:
        out("DIFF VERDICT: OK (no regressions past tolerance)")
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--metrics", default=None,
                    help="--metrics snapshot JSON (exit or mid-run flush)")
    ap.add_argument("--ledger", default=None,
                    help="ledger directory, merged file, or rank file")
    ap.add_argument("--bench", default=None,
                    help="FLEET_BENCH json (tools/fleet_smoke.py's "
                         "output line saved to a file)")
    ap.add_argument("--timeline", type=int, default=60,
                    help="max timeline events to print (default 60)")
    ap.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"),
                    default=None,
                    help="compare two --metrics snapshots (counters, "
                         "timer quantiles, per-tier GB/s, program "
                         "table) and print a regression verdict; exit "
                         "4 on regression")
    ap.add_argument("--diff-gbps-tol", type=float, default=10.0,
                    help="achieved-GB/s drop tolerated before a diff "
                         "regression verdict (percent, default 10)")
    ap.add_argument("--diff-latency-tol", type=float, default=25.0,
                    help="timer-p95 growth tolerated before a diff "
                         "regression verdict (percent, default 25)")
    args = ap.parse_args(argv)
    if args.diff:
        findings = diff_snapshots(
            load_metrics(args.diff[0]), load_metrics(args.diff[1]),
            gbps_tol_pct=args.diff_gbps_tol,
            latency_tol_pct=args.diff_latency_tol)
        return 4 if findings else 0
    if not (args.metrics or args.ledger or args.bench):
        ap.error("at least one of --metrics/--ledger/--bench is required")
    metrics = load_metrics(args.metrics) if args.metrics else {}
    events = load_ledger(args.ledger) if args.ledger else []
    bench = load_metrics(args.bench) if args.bench else {}
    render(metrics, events, bench, timeline=args.timeline)
    return 0


if __name__ == "__main__":
    sys.exit(main())
