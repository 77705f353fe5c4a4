"""Process-wide metrics registry: counters, gauges, timers.

The runtime's single source of numeric truth (SURVEY §5.5: the reference
has only ExaML_info prints and gettime() deltas).  Everything here is
stdlib-only and always on — a counter increment is a dict update under a
lock, negligible against the millisecond-scale device dispatches it
counts — while the *expensive* instruments (trace events, device-array
gauges) stay behind explicit opt-ins (`obs.trace`, snapshot collectors).

Naming convention (dotted, lowercase):

  engine.dispatch_count        device program invocations
  engine.traversal_entries     newview entries submitted (retraversal size)
  engine.cache_hits/misses/evictions   shared fast-program LRU
  engine.sched_cache.hit/miss          topology-keyed schedule-structure
  engine.sched_cache.invalidate/evictions   cache (ops/engine.py)
  host_schedule                timer: host-side schedule building
                               (flat traversal + structure/z assembly,
                               scan-tier packing) — the host floor,
                               split from device dispatch; fed by every
                               `engine:<family>/schedule` span
  engine.staged_arrays         host arrays/scalars handed to jnp as
                               device arguments (stage phases, set_models)
  engine.grad_pass             timer: whole-tree gradient dispatches
                               (fed by the `engine:grad_pass` span)
  engine.collectives           cross-chip collectives dispatched: every
                               call of a guarded program adds the count
                               in its compiled text (read once, at its
                               first call: obs/programs.py, deep mode
                               only).  Never raised on one chip or where
                               the text was not read: absent, not 0.  It
                               is the executed count while no collective
                               sits in a loop (`collectives_in_loops`)

Spans (obs/trace.py) feed timers of their own, colon-named, with self
seconds (`self_s`: duration less child spans'):

  opt:model_opt_round > opt:brent | opt:tree_evaluate >
    opt:smooth_sweep > opt:newton_update     optimiser control
  engine:tree/schedule, engine:set_models    engine work between dispatches
  engine:<family>                            one dispatch, tiled by
    engine:<family>/schedule|stage|launch|wait   its four phases
  first_call:<family>                        under launch: a program's
    first_call:<family>/lower                  whole first call, tiled by
    compile:<family>                           the observatory's prelower,
    first_call:<family>/analyze                the jitted call (the compile
                                               or the cache load), and the
                                               program table's row
  search:*, phase:*                          off the benchmark's timed path
  fleet:eval, fleet:grad_smooth,             the fleet's batched dispatches
    fleet:smooth_update, fleet:weights_eval    and the host update between
                                               smoothing sweeps

  engine.compile_count, engine.compile_seconds[.family]   the jitted
                               first call alone (the `compile:` span)
  engine.first_call            timer: the whole guarded first call (fed
                               by the `first_call:<family>` span)
  program.obs                  timer: what the program observatory's
                               analysis costs (the `.../analyze` span)
  jax.trace_*, jax.lower_*, jax.backend_compile_* (_seconds, _count),
  jax.trace_lower_seconds, jax.cache_hits/misses/retrieval_seconds
                               JAX's own compile-pipeline events of
                               EVERY program, guarded or eager
                               (obs/programs.py: outermost seconds only)
  engine.compile_count.bank_phase      first calls inside the bank phase
  engine.first_calls.banked/unbanked[.family]   post-bank first calls
  engine.first_calls.degraded_inprocess[.family]   deadline-degraded
                               scan-tier family compiled in-process
                               (watchdogged; expected, not a gap)
  engine.watchdog_barks        compile-deadline watchdog firings
  fleet.batched_dispatches, fleet.batch_jobs, fleet.batch_slots
                               every batched fleet dispatch: +1, +its
                               live jobs, +its padded slots, in all and
                               per family (`.eval`, `.scan`, `.uni`,
                               `.weights`, `.grad`; fleet/batch.py)
  engine.nonfinite_retries/.nonfinite_recovered   NaN-lnL scan-tier retries
  bank.families/banked/timeouts/errors/skipped/fallbacks   AOT banking
  bank.compile.<family>        per-family subprocess compile (timers)
  bank.engine.*                worker-side compile counters, merged
  resilience.heartbeats        published search-loop liveness beats
  resilience.restarts/heartbeat_stalls/preempts   supervisor (merged
                               into the --metrics snapshot at exit)
  resilience.preempt_checkpoints   emergency checkpoints before exit 75
  checkpoint.corrupt_skipped   unreadable checkpoints skipped at restore
  engine.traffic_bytes         modeled HBM bytes moved by traversal
                               dispatches (obs/traffic.py: the ONE
                               bytes-per-traversal model).
                               Under a mesh the whole alignment's, a
                               sum over chips: a chip's part is that
                               over the gauge engine.mesh_site_shards
  engine.achieved_gbps.<tier>.<engine-tag>   windowed achieved GB/s
                               gauge per tier (scan/chunk/universal/
                               grad) and engine, from the timed
                               blocking dispatch path
  engine.regime_dispatch_bound.<tier>.<engine-tag>   1.0 = the
                               window's wall time sits at the
                               launch-latency floor (dispatch-bound),
                               0.0 = bandwidth-meaningful
                               (obs/traffic.classify_regime)
  faults.fired.<point>         injected faults that fired (chaos tests)
  search.spr_cycles, search.fast_cycles, search.thorough_cycles
  search.scan_dispatches, search.scan_candidates
  phase.<name>                 CLI wall-clock phases (timers)

Counters accept float increments (compile_seconds accumulates wall
seconds); timers record count/total/self/min/max of observed durations PLUS
a log-bucketed latency histogram (obs/hist.py), so every snapshot
carries p50/p95/p99 per timer — one slow outlier (a launch-floor
stall, a recompile) is visible instead of vanishing into a `total_s`
sum.  Snapshot collectors let owners of live state (engines) publish
gauges lazily — they run only when `snapshot()` is taken, so per-call
cost is zero, and they hold weak references so a registry never keeps
a CLV arena alive.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Optional

from examl_tpu.obs import hist as _hist


class TimerStat:
    __slots__ = ("count", "total", "self_total", "min", "max", "hist")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.self_total = 0.0    # total less the time inside child spans
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.hist = _hist.Histogram()

    def observe(self, seconds: float,
                self_seconds: Optional[float] = None) -> None:
        self.count += 1
        self.total += seconds
        self.self_total += seconds if self_seconds is None else self_seconds
        self.min = seconds if self.min is None else min(self.min, seconds)
        self.max = seconds if self.max is None else max(self.max, seconds)
        self.hist.observe(seconds)

    def as_dict(self) -> dict:
        d = {"count": self.count, "total_s": self.total,
             "self_s": self.self_total,
             "min_s": self.min, "max_s": self.max}
        # Quantiles + the raw sparse buckets: the buckets are what lets
        # two snapshots MERGE exactly (bank worker accumulation,
        # supervisor attempt merging) — merged quantiles recompute from
        # summed buckets, never from quantiles.
        d.update(self.hist.quantiles())
        d["buckets"] = self.hist.to_dict()
        return d


class _TimerContext:
    """Context manager that observes its own wall duration into a timer;
    exposes `.elapsed` (seconds) after exit so callers can reuse the one
    measurement instead of re-bracketing with perf_counter."""

    __slots__ = ("_registry", "_name", "_t0", "elapsed")

    def __init__(self, registry: "MetricsRegistry", name: str) -> None:
        self._registry = registry
        self._name = name
        self.elapsed = 0.0

    def __enter__(self) -> "_TimerContext":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = time.perf_counter() - self._t0
        self._registry.observe(self._name, self.elapsed)


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._timers: Dict[str, TimerStat] = {}
        self._collectors: list = []

    # -- counters / gauges / timers ----------------------------------------

    def inc(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def counter(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0)

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, seconds: float,
                self_seconds: Optional[float] = None) -> None:
        """`self_seconds` is a span's duration less its child spans'
        (obs/trace.py); a plain timer's self time is its duration."""
        with self._lock:
            stat = self._timers.get(name)
            if stat is None:
                stat = self._timers[name] = TimerStat()
            stat.observe(seconds, self_seconds)

    def timer(self, name: str) -> _TimerContext:
        return _TimerContext(self, name)

    # -- collectors ---------------------------------------------------------

    def add_collector(self, fn: Callable[[], bool]) -> None:
        """Register a zero-arg callable run at every snapshot().  It may
        set gauges; returning False (or raising) unregisters it — the
        idiom for weakref-bound owners that have been collected."""
        with self._lock:
            self._collectors.append(fn)

    def _run_collectors(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        dead = []
        for fn in collectors:
            try:
                if fn() is False:
                    dead.append(fn)
            except Exception:
                dead.append(fn)
        if dead:
            with self._lock:
                self._collectors = [c for c in self._collectors
                                    if c not in dead]

    # -- snapshot / reset ---------------------------------------------------

    def snapshot(self) -> dict:
        self._run_collectors()
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timers": {n: t.as_dict() for n, t in self._timers.items()},
            }

    def snapshot_counters(self) -> Dict[str, float]:
        """Counters only, WITHOUT running collectors: the cheap form for
        high-frequency consumers (the resilience heartbeat embeds this
        in every published beat — collectors may touch device state and
        must not run on the search loop's iteration clock)."""
        with self._lock:
            return dict(self._counters)

    def snapshot_light(self) -> dict:
        """Full snapshot shape WITHOUT running collectors: counters,
        last-set gauges, timers.  The periodic-flush form — safe on the
        search loop's clock for the same reason as snapshot_counters
        (collectors may touch device state)."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timers": {n: t.as_dict() for n, t in self._timers.items()},
            }

    def reset(self) -> None:
        """Clear counters/gauges/timers (collectors stay registered —
        their owners are still live)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._timers.clear()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


# -- periodic snapshot flush -------------------------------------------------
# `--metrics` snapshots used to be written only at exit (try/finally),
# so a SIGKILLed / hang-killed child left NOTHING — the supervisor had
# no last-known counters to merge for the killed attempt.  The CLI arms
# this and the resilience heartbeat ticks it on every published beat:
# a cheap collector-free snapshot lands on disk on a rate-limited
# cadence (atomic tmp+rename, so the supervisor never reads torn JSON),
# marked `"partial": true` so consumers can tell a mid-run flush from
# the final at-exit snapshot that overwrites it.

_FLUSH = {"path": None, "interval": 5.0, "last": 0.0}

DEFAULT_FLUSH_INTERVAL_S = 5.0


def set_autoflush(path: Optional[str],
                  interval: Optional[float] = None) -> None:
    """Arm (or, with None, disarm) the periodic snapshot flush.
    `interval` defaults to EXAML_METRICS_FLUSH_S (else 5 s) — chaos
    tests pin it to 0 so a warm-cache attempt killed seconds in still
    leaves counter-bearing evidence, not just the startup flush."""
    if interval is None:
        try:
            interval = float(os.environ.get("EXAML_METRICS_FLUSH_S")
                             or DEFAULT_FLUSH_INTERVAL_S)
        except ValueError:
            interval = DEFAULT_FLUSH_INTERVAL_S
    _FLUSH.update(path=path, interval=float(interval), last=0.0)


def maybe_autoflush(force: bool = False) -> bool:
    """Write the collector-free snapshot if armed and the cadence is
    due; returns True when a flush happened.  Never raises: a full or
    read-only disk must not kill the run it observes."""
    path = _FLUSH["path"]
    if path is None:
        return False
    now = time.time()
    if not force and now - _FLUSH["last"] < _FLUSH["interval"]:
        return False
    _FLUSH["last"] = now
    snap = _REGISTRY.snapshot_light()
    snap["partial"] = True
    snap["flushed_at"] = now
    try:
        # Collector-free by design, but the program table is plain
        # host data — a killed run's last flush should still name the
        # programs it had compiled (obs/programs.py).
        from examl_tpu.obs import programs as _programs
        rows = _programs.table()
        if rows:
            snap["programs"] = rows
    except Exception:                        # noqa: BLE001 — never-raise
        pass
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(snap, f, sort_keys=True, default=str)
        # graftlint: disable=GL007 -- best-effort mid-run flush on the
        # heartbeat clock (never-raise contract); the exit snapshot
        # overwrites it, and a lost flush costs one cadence of counters.
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    return True
