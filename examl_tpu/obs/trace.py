"""One span primitive: registry timer, profiler annotation, JSONL event.

`span(name)` is the only way the program brackets a stretch of host
work.  One `with` feeds up to three sinks:

* ALWAYS the registry timer of the span's name (`obs/metrics.py`), with
  self seconds: a per-thread stack makes every span add its duration to
  its parent's child time on exit, so the timer records `self_s`
  (duration minus children) beside `total_s` and `count`.  With the
  other sinks off this is two clock reads, a list push/pop and one
  timer update.
* when ANNOTATIONS are on (`set_annotations(True)`: the benchmark's
  `--trace 1`, the CLI's `--profile`, or `enable()`): a
  `jax.profiler.TraceAnnotation` of the same name, so the span sits on
  the profiler's clock beside the device's operations and an idle gap
  of the device can be given to the span the host was in.  A span
  opened with `annotate=False` skips this sink: the dispatch-level
  `engine:<family>` spans do, because their four phases tile them and
  the trace reduction gives a gap to the `engine:*` annotation that
  covers most of it (a parent would shadow its phases).
* when the JSONL WRITER is on (`enable(dir, procid)`, the CLI's
  `--trace-events`, or `EXAML_TRACE_DIR`, checked lazily on the first
  span so subprocesses inherit tracing for free): Chrome-trace /
  Perfetto B/E events whose `args` carry the enclosing span's name
  (`parent`) and the dispatch sequence number (`seq`:
  `engine.dispatch_count` at entry), so the phases of one dispatch
  share an identifier.

The span tree on the timed path (names are the timers' names too):

    opt:model_opt_round > opt:brent | opt:tree_evaluate
      opt:tree_evaluate > opt:smooth_sweep > opt:newton_update
        engine:tree/schedule          the tree's flat traversal
        engine:set_models             model push (once a Brent probe)
        engine:<family>               one dispatch (not annotated)
          engine:<family>/schedule    also feeds the `host_schedule` timer
          engine:<family>/stage       host values -> device arguments
          engine:<family>/launch      the jitted call until it returns
            first_call:<family>       a program's whole first call (also
                                      feeds the `engine.first_call` timer)
              first_call:<family>/lower    trace + lowering for the program
                                      table (obs/programs.prelower; not
                                      opened under EXAML_PROGRAM_OBS=rows|0)
              compile:<family>        the jitted call: the compile or the
                                      cache load, and the launch
              first_call:<family>/analyze  the table's row: analysis
                                      compile, analyses, text scans (also
                                      feeds `program.obs`; not under =0)
          engine:<family>/wait        the blocking read-back

JAX's own trace / lower / compile events of any program, guarded or
eager, are counters (`jax.*`, obs/programs.py) and, with the JSONL
writer on, `jit:<kind>` instants naming the span they fell in.

Design constraints of the JSONL file, all from the round-4 postmortem
(a compile wedged in `recv` with no visibility into which program or
what had completed):

* spans are B/E *pairs*, flushed per event — a wedged compile leaves an
  unmatched "B" naming the guilty program family as the file's last
  line, exactly the artifact the postmortem lacked;
* one file per process, named by procid (`trace.p<procid>.jsonl`), so
  multi-host runs never interleave writers; process 0 merges a
  cross-process `summary.json` at exit;
* the file is a streaming Chrome-trace JSON array: a `[` header, one
  event object per line each terminated by a comma, closed with a
  metadata event + `]` at finalize.  Perfetto and chrome://tracing load
  both the finalized file and a crash-truncated one (the format is
  specified to tolerate a missing terminator).

JSONL timestamps are epoch microseconds (`time.time_ns() // 1000`) so
traces from different processes of one job line up on a shared axis;
durations in the registry are `time.perf_counter` differences.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Optional

from examl_tpu.obs import metrics as _metrics


_lock = threading.Lock()
_writer: Optional["TraceWriter"] = None
_env_checked = False
_annotate = False


def _now_us() -> int:
    return time.time_ns() // 1000


class TraceWriter:
    def __init__(self, path: str, procid: int) -> None:
        self.path = path
        self.procid = procid
        self._lock = threading.Lock()
        self._tids: dict = {}
        self._f = open(path, "w")
        self._f.write("[\n")
        self.event({"ph": "M", "name": "process_name", "pid": procid,
                    "tid": 0, "ts": _now_us(),
                    "args": {"name": f"examl-tpu proc {procid}"}})

    def tid(self) -> int:
        ident = threading.get_ident()
        t = self._tids.get(ident)
        if t is None:
            with self._lock:
                t = self._tids.setdefault(ident, len(self._tids))
        return t

    def event(self, obj: dict) -> None:
        line = json.dumps(obj, separators=(",", ":"))
        with self._lock:
            if self._f.closed:
                return
            self._f.write(line + ",\n")
            self._f.flush()           # crash-robust: the last span survives

    def close(self) -> None:
        with self._lock:
            if self._f.closed:
                return
            # Final metadata event carries no trailing comma so the file
            # closes as strictly valid JSON.
            self._f.write(json.dumps(
                {"ph": "M", "name": "trace_shutdown", "pid": self.procid,
                 "tid": 0, "ts": _now_us(), "args": {}},
                separators=(",", ":")) + "\n]\n")
            self._f.close()


_clock = time.perf_counter    # tests put a fake clock here
_tls = threading.local()      # .stack: this thread's open spans
_annotation_cls = None        # jax.profiler.TraceAnnotation, on first use


def _open_annotation(name: str):
    """Enter a profiler annotation; None where JAX cannot give one."""
    global _annotation_cls
    try:
        if _annotation_cls is None:
            import jax
            _annotation_cls = jax.profiler.TraceAnnotation
        ann = _annotation_cls(name)
        ann.__enter__()
        return ann
    except Exception:            # noqa: BLE001 - tracing never fails a run
        return None


class span:
    """The span primitive (module docstring): `with span(name): ...`.
    `also` names a second registry timer that takes the same duration
    (the schedule phases feed `host_schedule`, the gradient dispatch
    `engine.grad_pass`); `.elapsed` holds the duration after exit, for
    callers that need the one measurement again."""

    __slots__ = ("name", "elapsed", "_cat", "_args", "_annotate", "_also",
                 "_t0", "_child_s", "_ann", "_w")

    def __init__(self, name: str, args: Optional[dict] = None, *,
                 cat: str = "host", annotate: bool = True,
                 also: Optional[str] = None) -> None:
        self.name = name
        self.elapsed = 0.0
        self._cat = cat
        self._args = args
        self._annotate = annotate
        self._also = also

    def __enter__(self):
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        self._ann = (_open_annotation(self.name)
                     if _annotate and self._annotate else None)
        if not _env_checked:
            _maybe_env_enable()
        w = self._w = _writer
        if w is not None:
            args = dict(self._args) if self._args else {}
            if stack:
                args["parent"] = stack[-1].name
            args["seq"] = _metrics.registry().counter(
                "engine.dispatch_count")
            w.event({"ph": "B", "name": self.name, "cat": self._cat,
                     "pid": w.procid, "tid": w.tid(), "ts": _now_us(),
                     "args": args})
        stack.append(self)
        self._child_s = 0.0
        self._t0 = _clock()
        return self

    def __exit__(self, *exc):
        dt = self.elapsed = _clock() - self._t0
        stack = _tls.stack
        stack.pop()
        if stack:
            stack[-1]._child_s += dt
        reg = _metrics.registry()
        reg.observe(self.name, dt, dt - self._child_s)
        if self._also is not None:
            reg.observe(self._also, dt)
        w = self._w
        if w is not None:
            w.event({"ph": "E", "name": self.name, "cat": self._cat,
                     "pid": w.procid, "tid": w.tid(), "ts": _now_us()})
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:    # noqa: BLE001 - tracing never fails a run
                pass
        return False


def current() -> Optional[str]:
    """The name of this thread's innermost open span, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1].name if stack else None


def _default_procid() -> int:
    env = os.environ.get("EXAML_PROCID")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    try:
        # Only consult jax when a distributed client already exists:
        # jax.process_index() initializes backends, which tracing setup
        # must never force on its own.
        from jax._src import distributed
        if getattr(distributed.global_state, "client", None) is not None:
            import jax
            return jax.process_index()
    except Exception:
        pass
    return 0


def enable(trace_dir: str, procid: Optional[int] = None) -> str:
    """Open this process's trace file under `trace_dir`; returns its
    path.  Idempotent: re-enabling returns the existing file."""
    global _writer, _env_checked, _annotate
    with _lock:
        _env_checked = True
        if _writer is not None:
            return _writer.path
        if procid is None:
            procid = _default_procid()
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, f"trace.p{procid}.jsonl")
        _writer = TraceWriter(path, procid)
        _annotate = True
        atexit.register(finalize)
        return path


def enabled() -> bool:
    return _writer is not None


def set_annotations(on: bool) -> None:
    """Turn jax.profiler.TraceAnnotation scopes on/off independently of
    the JSONL writer (the CLI sets this under --profile so xprof traces
    get named scopes even without --trace-events)."""
    global _annotate
    _annotate = on


def _maybe_env_enable() -> bool:
    global _env_checked
    if _env_checked:
        return _writer is not None
    with _lock:
        _env_checked = True
    env = os.environ.get("EXAML_TRACE_DIR")
    if env:
        try:
            enable(env)
        except OSError:
            pass
    return _writer is not None


def instant(name: str, args: Optional[dict] = None) -> None:
    """A zero-duration marker event (a watchdog bark)."""
    if _writer is None and not _maybe_env_enable():
        return
    ev = {"ph": "i", "s": "p", "name": name, "cat": "event",
          "pid": _writer.procid, "tid": _writer.tid(), "ts": _now_us()}
    if args:
        ev["args"] = args
    _writer.event(ev)


def read_events(path: str) -> list:
    """Parse a trace file (finalized or crash-truncated) into a list of
    event dicts — the shared reader for the summary merge and the tests."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip().rstrip(",")
            if not line or line in ("[", "]"):
                continue
            try:
                events.append(json.loads(line))
            except ValueError:
                continue              # torn final line of a crashed writer
    return events


def merge_summary(trace_dir: str) -> Optional[str]:
    """Merge every per-process trace file in `trace_dir` into
    summary.json: per-file event counts plus aggregate span wall time by
    name.  Best-effort — files from still-running processes are summed
    as far as they have been written."""
    try:
        names = sorted(n for n in os.listdir(trace_dir)
                       if n.startswith("trace.p") and n.endswith(".jsonl"))
    except OSError:
        return None
    files = {}
    spans: dict = {}
    for name in names:
        events = read_events(os.path.join(trace_dir, name))
        files[name] = {"events": len(events)}
        open_spans: dict = {}
        for ev in events:
            key = (ev.get("pid"), ev.get("tid"), ev.get("name"))
            if ev.get("ph") == "B":
                open_spans.setdefault(key, []).append(ev.get("ts", 0))
            elif ev.get("ph") == "E" and open_spans.get(key):
                t0 = open_spans[key].pop()
                agg = spans.setdefault(
                    ev.get("name"), {"count": 0, "total_us": 0})
                agg["count"] += 1
                agg["total_us"] += max(0, ev.get("ts", t0) - t0)
        for key, starts in open_spans.items():
            if starts:
                agg = spans.setdefault(key[2], {"count": 0, "total_us": 0})
                agg["unfinished"] = agg.get("unfinished", 0) + len(starts)
    # Top spans by wall time — but unfinished spans (the wedged-compile
    # evidence this file exists to preserve) are ALWAYS included, even
    # with zero completed time.
    top = dict(sorted(spans.items(),
                      key=lambda kv: -kv[1].get("total_us", 0))[:50])
    top.update({n: s for n, s in spans.items() if s.get("unfinished")})
    out = os.path.join(trace_dir, "summary.json")
    try:
        with open(out, "w") as f:
            json.dump({"files": files, "spans": top}, f, indent=2,
                      sort_keys=True)
    except OSError:
        return None
    return out


def finalize() -> None:
    """Close this process's trace file; process 0 merges the summary."""
    global _writer
    with _lock:
        w = _writer
        _writer = None
    if w is None:
        return
    w.close()
    if w.procid == 0:
        merge_summary(os.path.dirname(w.path) or ".")
