"""examl_tpu.obs — unified runtime observability.

Dependency-free pieces (SURVEY §5.1/§5.5: the reference's only
instruments are gettime() deltas and ExaML_info prints):

* a process-wide **metrics registry** (`obs.metrics`): counters, gauges,
  timers with log-bucketed latency histograms (`obs.hist`) — always on,
  dict-update cheap — plus a heartbeat-ticked periodic snapshot flush
  so a killed process leaves its last-known counters behind;
* ONE **span primitive** (`obs.trace.span`): every span always feeds
  the registry timer of its name (count, total and SELF seconds: its
  duration less its child spans'); with annotations on
  (`set_annotations`: the benchmark's `--trace 1`, the CLI's
  `--profile`) it is a `jax.profiler.TraceAnnotation` on the profiler's
  clock; with the JSONL writer on (`--trace-events` /
  `EXAML_TRACE_DIR`) a Chrome-trace/Perfetto B/E pair naming its parent
  and the dispatch's sequence number.  The span tree of the timed path
  (`opt:` > `engine:<family>` > schedule / stage / launch / wait,
  `first_call:<family>` > lower / `compile:<family>` / analyze under
  launch) is drawn in `obs/trace.py`; JAX's own trace / lower / compile
  events of every program are `jax.*` counters (`obs/programs.py`);
* a **run ledger** (`obs.ledger`): append-only per-rank JSONL event
  stream (compiles, phases, faults, checkpoint cycles, supervisor
  decisions, probe verdicts), merged by rank 0 into one ordered gang
  timeline at exit;
* the shared **roofline traffic model** (`obs.traffic`): the one
  bytes-per-traversal definition the engine uses, plus the
  dispatch-bound vs bandwidth-meaningful regime classifier.

This module is the flat facade the rest of the runtime imports:

    from examl_tpu import obs
    obs.inc("engine.dispatch_count")
    with obs.span("engine:set_models"):
        ...
"""

from __future__ import annotations

import sys
from typing import Callable, Optional

from examl_tpu.obs import ledger as _ledger
from examl_tpu.obs import metrics as _metrics
from examl_tpu.obs import trace as _trace
from examl_tpu.obs import traffic  # noqa: F401  (shared roofline model)
from examl_tpu.obs.ledger import (  # noqa: F401
    enable as enable_ledger, enabled as ledger_enabled,
    event as ledger_event, finalize as finalize_ledger,
    merge as merge_ledger, read_events as read_ledger)
from examl_tpu.obs.metrics import (  # noqa: F401
    maybe_autoflush, set_autoflush)
from examl_tpu.obs.trace import (  # noqa: F401
    enable as enable_tracing, enabled as tracing_enabled,
    finalize as finalize_tracing, instant, merge_summary, read_events,
    set_annotations, span)

# -- metrics facade ---------------------------------------------------------


def registry() -> _metrics.MetricsRegistry:
    return _metrics.registry()


def inc(name: str, value: float = 1) -> None:
    _metrics.registry().inc(name, value)


def counter(name: str) -> float:
    return _metrics.registry().counter(name)


def gauge(name: str, value: float) -> None:
    _metrics.registry().gauge(name, value)


def observe(name: str, seconds: float) -> None:
    _metrics.registry().observe(name, seconds)


def timer(name: str):
    return _metrics.registry().timer(name)


def add_collector(fn: Callable[[], bool]) -> None:
    _metrics.registry().add_collector(fn)


def snapshot() -> dict:
    snap = _metrics.registry().snapshot()
    # The program observatory's registry rows ride in every snapshot so
    # tools/run_report.py can render the Programs table from the same
    # artifact that carries the gauges.
    from examl_tpu.obs import programs as _programs
    rows = _programs.table()
    if rows:
        snap["programs"] = rows
    # ... and the newest compile-pipeline events with the span each fell
    # in: what compiled late, and inside which dispatch.
    events = _programs.jit_events()
    if events:
        snap["jit_events"] = events
    return snap


def snapshot_counters() -> dict:
    """Counters only, no collectors — safe on hot loops (heartbeat)."""
    return _metrics.registry().snapshot_counters()


def reset() -> None:
    """Clear the registry (the `jax.*` counters with it) and the kept
    compile-pipeline events; listeners and collectors stay."""
    _metrics.registry().reset()
    from examl_tpu.obs import programs as _programs
    _programs.clear_jit_events()


# -- operator log sink ------------------------------------------------------
# Runtime components that must reach the operator (the compile watchdog)
# write through here: always stderr, plus whatever sink the driver
# installed (the CLI points this at the ExaML_info file so a wedged run's
# info file names the guilty program family).

_log_sink: Optional[Callable[[str], None]] = None


def set_log_sink(fn: Optional[Callable[[str], None]]) -> None:
    global _log_sink
    _log_sink = fn


def log(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sink = _log_sink
    if sink is not None:
        try:
            sink(msg)
        except Exception:
            pass
