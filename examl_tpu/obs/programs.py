"""Program observatory: compiler-truth cost/memory accounting.

Every roofline number this runtime can state divides by the hand-written
analytic bytes model (obs/traffic.py) — without the compiler's own
accounting next to it, an `achieved_gbps` row cannot be distinguished
from a model bug, and the HBM budgets the bf16-arena and multi-tenant
items must prove have no telemetry to stand on.  This module is the
process-wide registry of every compiled or deserialized executable the
run dispatched: one row per program with its family, jit key, compile
source (fresh / xla-cache / exported), compile seconds, and — behind a
fallback-not-crash ladder, because some backends return empty analyses —
XLA's `cost_analysis()` flops / bytes-accessed / transcendentals and
`memory_analysis()` argument / output / temp / peak bytes.

Three consumers, all fed from the one registry:

* `program.*` gauges + the table embedded in every `--metrics`
  snapshot (obs.snapshot) and BENCH row — `tools/run_report.py`
  renders it as the "Programs" table;
* a `programs.p<procid>.jsonl` stream next to the run ledger (same
  per-rank suffix, append + flush-per-row, torn-line-tolerant readers)
  so a SIGKILLed process leaves its program evidence behind;
* the **drift gate**: `model_vs_xla()` reconciles the analytic
  bytes-per-traversal model against the serving program's XLA
  bytes-accessed per tier (`program.model_drift_pct.<tier>`), so the
  `achieved_gbps` gauges can carry a `source: model|xla` tag.  Scan-
  and chunk-tier programs on the CPU fixtures sit within tolerance;
  a tier past EXAML_DRIFT_TOL_PCT is *documented divergence* — it
  increments `program.model_drift_exceeded.<tier>` and keeps serving
  (the model stays the accounting denominator; the gate is evidence,
  never a crash).

Deep analysis needs a `Compiled`, and jax's jit path does not expose
the executable it cached — so the observatory AOT-compiles the traced
lowering once per first call (`lowered.compile()`; with a persistent
XLA cache armed this is a cache deserialize, not a second codegen).
What the observatory costs a run is timed by two spans of the first-call
path (`obs/trace.py` draws it): `first_call:<family>/lower` around
`prelower` and `first_call:<family>/analyze` around `record` (that
compile, the analyses, the text scans, the row), the second also into
the timer `program.obs`.  `EXAML_PROGRAM_OBS=rows` keeps registry rows
but skips the lowering and that compile; `0` disables the observatory
(neither span opens).  Exported-bank hits get their analyses free: a
deserialized executable answers `cost_analysis()` directly, which is
how a zero-compile cold start still populates the table.

Live HBM telemetry rides the same module: `sample_memory()` reads
`device.memory_stats()` (rate-limited by EXAML_MEM_SAMPLE_S) into
`mem.device.<k>.{in_use,peak,limit}` gauges — sampled at the engine's
traffic-window cadence, per fleet drain round, and at every metrics
snapshot, cross-checkable against `engine.clv_arena_bytes`.  CPU
backends return no memory stats; that is the
`program.analysis_missing.memory_stats` rung of the ladder, not an
error.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from examl_tpu.obs import ledger as _ledger
from examl_tpu.obs import metrics as _metrics
from examl_tpu.obs import trace as _trace

ENV_VAR = "EXAML_PROGRAM_OBS"

# Which program families serve which traffic tier (engine._dispatch_tier
# labels): the drift gate compares a tier's modeled dispatch bytes with
# the newest registry row of the family that actually moved them.
TIER_FAMILIES: Dict[str, Tuple[str, ...]] = {
    "scan": ("trav_eval", "traverse", "newton", "scan", "thscan",
             "sumtable", "derivs"),
    "chunk": ("fast",),
    "universal": ("universal",),
    "grad": ("grad",),
}

_lock = threading.Lock()
_STATE: Dict[str, object] = {
    "rows": {},            # (family, key) -> row dict, insertion-ordered
    "by_family": {},       # family -> newest row with analyses
    "stream": None,        # open programs.p<proc>.jsonl handle
    "stream_dir": None,
    "mem_last": None,      # monotonic of the last memory sample
    "collector": False,
    "listener": False,
}


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name) or default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name) or default)
    except ValueError:
        return default


def mode() -> str:
    """"deep" (default: rows + AOT analyses), "rows" (registry only,
    no analysis compile), or "off"."""
    m = _env_str(ENV_VAR, "deep").strip().lower()
    if m in ("0", "off", "false"):
        return "off"
    if m == "rows":
        return "rows"
    return "deep"


def enabled() -> bool:
    return mode() != "off"


def drift_tolerance_pct() -> float:
    return _env_float("EXAML_DRIFT_TOL_PCT", 25.0)


def reset() -> None:
    """Forget rows and close the stream (tests; one in-process run must
    not inherit a previous run's table)."""
    with _lock:
        f = _STATE["stream"]
        _STATE.update(rows={}, by_family={}, stream=None,
                      stream_dir=None, mem_last=None)
    if f is not None:
        try:
            f.close()
        except OSError:
            pass


# -- JAX's compile pipeline, as registry counters ----------------------------
# `jax.monitoring` reports every program of the process, guarded by the
# engine or eager (`jnp.full` at a new shape): its trace to a jaxpr, its
# lowering to MLIR, the backend compile (on a persistent-cache hit: the
# retrieval) and the cache's own hits and misses.  One listener a kind
# (duration, event and, for nesting, scalar: `log_elapsed_time` fires
# `record_scalar(event, start)` on entry and the duration on exit) turns
# them into counters:
#
#   jax.trace_seconds / jax.trace_count      jaxpr_trace_duration
#   jax.lower_seconds / jax.lower_count      jaxpr_to_mlir_module_duration:
#                                            one a program that reaches the
#                                            compiler, THE count of new
#                                            programs
#   jax.backend_compile_seconds / _count     backend_compile_duration
#   jax.trace_lower_seconds                  raised by trace and lower both
#   jax.cache_hits / jax.cache_misses / jax.cache_retrieval_seconds
#
# Inner jitted functions fire the trace event inside their caller's
# (`matmul` inside `f`) and the outer duration holds theirs, and an eager
# operation met while tracing lowers and compiles inside that trace: so
# SECONDS are counted only of an event that is outermost on its thread,
# whatever its kind, and the three sums are wall seconds; `trace_count`
# counts outermost traces, the other two every event.  The observatory's
# own `lowered.compile()` (`_record`) is kept out of the counters: it is
# `program.obs`'s, not the program's.  The newest JIT_EVENTS_KEPT events
# (inner traces left out) are kept with the innermost open `obs.span` of
# their thread, for `obs.snapshot()["jit_events"]` and, with the JSONL
# writer on, as instants: which program compiled late, in which dispatch.
# Registration is best-effort: a jax without the hooks counts nothing
# and reports every in-process compile as "fresh".

JIT_EVENTS_KEPT = 64
_PIPELINE = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax.cache_hits",
    "/jax/compilation_cache/cache_misses": "jax.cache_misses",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_jit_events: collections.deque = collections.deque(maxlen=JIT_EVENTS_KEPT)
_jit_tls = threading.local()    # .depth: open pipeline events; .own: the
                                # observatory's analysis compile is running


def _on_scalar(event, value, **kw):
    if event in _PIPELINE:
        _jit_tls.depth = getattr(_jit_tls, "depth", 0) + 1


def _on_duration(event, seconds, **kw):
    own = getattr(_jit_tls, "own", False)
    if event == _CACHE_RETRIEVAL:
        if not own:
            _metrics.registry().inc("jax.cache_retrieval_seconds", seconds)
        return
    kind = _PIPELINE.get(event)
    if kind is None:
        return
    depth = _jit_tls.depth = max(0, getattr(_jit_tls, "depth", 1) - 1)
    if kind == "trace" and depth:
        return
    if not own:
        reg = _metrics.registry()
        reg.inc(f"jax.{kind}_count")
        if not depth:
            reg.inc(f"jax.{kind}_seconds", seconds)
            if kind != "backend_compile":
                reg.inc("jax.trace_lower_seconds", seconds)
    row = {"event": kind, "fun_name": kw.get("fun_name"),
           "seconds": round(float(seconds), 6), "span": _trace.current()}
    _jit_events.append(row)
    _trace.instant(f"jit:{kind}", row)


def _on_event(event, **kw):
    name = _CACHE_EVENTS.get(event)
    if name is not None and not getattr(_jit_tls, "own", False):
        _metrics.registry().inc(name)


def install_listener() -> None:
    """Register the three listeners, once a process (`obs.reset()`
    clears what they counted and keeps them).  Called where the compile
    cache is armed (`config.enable_persistent_compilation_cache`, before
    the CLI's and the benchmark's first program), so the eager programs
    of the load are counted too, and at the latest by the first guarded
    call (`xla_cache_hits`)."""
    if _STATE["listener"]:
        return
    _STATE["listener"] = True
    try:
        import jax.monitoring as _mon
        _mon.register_scalar_listener(_on_scalar)
        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_listener(_on_event)
    except Exception:                        # noqa: BLE001 — optional hook
        pass


def xla_cache_hits() -> int:
    """Persistent-cache hits counted since the last `obs.reset()`
    (`jax.cache_hits`; installs the listeners on first use).  Counting
    them around a first call is the only non-invasive way to tell a
    fresh codegen from a cache deserialize."""
    install_listener()
    return int(_metrics.registry().counter("jax.cache_hits"))


def jit_events() -> List[dict]:
    """The newest JIT_EVENTS_KEPT compile-pipeline events (copies),
    oldest first: {event, fun_name, seconds, span}."""
    return [dict(r) for r in list(_jit_events)]


def clear_jit_events() -> None:
    _jit_events.clear()


# -- the fallback-not-crash analysis ladder ----------------------------------


def _missing(field: str, row: dict) -> None:
    _metrics.registry().inc(f"program.analysis_missing.{field}")
    row.setdefault("missing", []).append(field)


def prelower(fn, args, family: str):
    """Trace `fn` to a Lowered BEFORE the dispatch donates its buffers
    (lowering reads only avals).  Returns None — counting, never
    raising — when the callable cannot lower (non-jit wrappers,
    backend refusals) or deep analysis is off."""
    if mode() != "deep":
        return None
    with _trace.span(f"first_call:{family}/lower", cat="compile"):
        try:
            return fn.lower(*args)
        except Exception:                    # noqa: BLE001 — ladder rung
            _metrics.registry().inc("program.analysis_missing.lower")
            return None


def _cost_analysis(compiled, row: dict) -> None:
    try:
        cost = compiled.cost_analysis()
    except Exception:                        # noqa: BLE001 — ladder rung
        cost = None
    if isinstance(cost, (list, tuple)):      # jaxlib returns [dict]
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        _missing("cost_analysis", row)
        return
    for field, keys in (("flops", ("flops",)),
                        ("bytes_accessed", ("bytes accessed",
                                            "bytes_accessed")),
                        ("transcendentals", ("transcendentals",))):
        for k in keys:
            if k in cost:
                row[field] = float(cost[k])
                break
        else:
            _missing(field, row)


def _memory_analysis(compiled, row: dict) -> None:
    try:
        ma = compiled.memory_analysis()
    except Exception:                        # noqa: BLE001 — ladder rung
        ma = None
    if ma is None:
        _missing("memory_analysis", row)
        return
    for field, attr in (("argument_bytes", "argument_size_in_bytes"),
                        ("output_bytes", "output_size_in_bytes"),
                        ("temp_bytes", "temp_size_in_bytes")):
        v = getattr(ma, attr, None)
        if v is None:
            _missing(field, row)
        else:
            row[field] = int(v)
    # No jaxlib to date reports a live peak; the structural peak is
    # what the executable can address at once.  An explicit attribute
    # (future backends) wins when present.
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        parts = [row.get(f) for f in ("argument_bytes", "output_bytes",
                                      "temp_bytes")]
        if any(p is not None for p in parts):
            peak = sum(p or 0 for p in parts)
        else:
            _missing("peak_bytes", row)
    if peak is not None:
        row["peak_bytes"] = int(peak)


# Collective kinds GSPMD can insert; the fabric's contract (ISSUE 17)
# is that a compiled mesh program carries EXACTLY ONE all-reduce (the
# root lnL segment-sum over `sites` — ExaML's single Allreduce) and
# zero of every other kind.  tests/test_mesh.py pins this census.
_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")


def collective_census(compiled) -> Optional[Dict[str, int]]:
    """Count the collective ops in a Compiled's optimized HLO text
    ({kind: n}, kinds with zero occurrences omitted), or None when the
    backend will not serve the text.  Async pairs count once (the
    `-start` op; `-done` is its completion, not a second collective)."""
    import re
    try:
        text = compiled.as_text()
    except Exception:                        # noqa: BLE001 — ladder rung
        return None
    if not text:
        return None
    census: Dict[str, int] = {}
    for kind in _COLLECTIVE_KINDS:
        n = len(re.findall(rf"\b{kind}(?:-start)?\(", text))
        if n:
            census[kind] = n
    return census


def loop_bodies(text: str) -> Dict[str, str]:
    """The computations of an optimized HLO text that run inside a
    `while` loop, {name: body text}: a loop's body or condition, or a
    computation one of those calls (a fusion's, a nested loop's)."""
    import re
    name = r"%?([\w.\-]+)"
    bodies: Dict[str, str] = {}
    for m in re.finditer(rf"^(?:ENTRY\s+)?{name}\s*\(.*?\{{\s*$(.*?)^\}}",
                         text, re.M | re.S):
        bodies[m.group(1)] = m.group(2)
    calls = {c: set(re.findall(
        rf"(?:body|condition|to_apply|calls)={name}", b)) | {
            n.strip().lstrip("%") for g in re.findall(
                r"(?:branch|called)_computations=\{([^}]*)\}", b)
            for n in g.split(",") if n.strip()}
        for c, b in bodies.items()}
    todo = [n for b in bodies.values()
            for n in re.findall(rf"(?:body|condition)={name}", b)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in bodies and c not in looped:
            looped.add(c)
            todo.extend(calls[c])
    return {c: bodies[c] for c in sorted(looped)}


def collectives_in_loops(text: str) -> int:
    """How many of an optimized HLO text's collective ops sit in a
    `while` loop (`loop_bodies`).  The census counts ops in the text,
    and `engine.collectives` adds it a dispatch, so both are the
    EXECUTED count only while this is 0: an all-reduce the partitioner
    moved into the gradient pass's chunk loop would still read one a
    pass and run once a chunk.  tests/test_tpu_compile.py and
    tests/test_sharding.py hold the mesh programs to 0."""
    import re
    kinds = "|".join(_COLLECTIVE_KINDS)
    return sum(len(re.findall(rf"\b(?:{kinds})(?:-start)?\(", b))
               for b in loop_bodies(text).values())


def operand_slices(text: str) -> int:
    """How many `mini-gather-slice` instructions an optimized HLO text
    holds.  The TPU compiler gathers rows wider than 128 blocks in
    pieces, and makes the pieces by slicing the gather's OPERAND: each
    such slice copies a share of a whole arena to take a few rows of it
    (PERF.md §6, PR 32).  0 on other backends and where every row is
    read by index (`kernels.take_rows`)."""
    import re
    return len(re.findall(r"^\s*(?:ROOT\s+)?%?mini-gather-slice[\w.\-]*\s*=",
                          text, re.M))


def arena_gathers(text: str) -> int:
    """How many gathers of a program text read whole rows of a CLV or
    outroot arena [rows, B, lane, R, K]: a rank-5 operand whose slices
    span the lane axis.  In a lowering's StableHLO and in optimized HLO
    alike the gather states its `slice_sizes`.  With `operand_slices`
    it names the form `kernels.take_rows` took and what the compiler
    made of it: a lowering that holds such gathers reads rows by
    gather, one that holds none by index."""
    import re
    from examl_tpu.constants import TPU_LANE
    sizes = re.findall(r"gather.*?slice_sizes\s*=\s*(?:array<i64:|\{)"
                       r"\s*([\d,\s]+)[>}]", text)
    return sum(len(d) == 5 and d[2] == TPU_LANE
               for d in ([int(x) for x in t.split(",")] for t in sizes))


def _collectives(compiled, row: dict) -> None:
    census = collective_census(compiled)
    if census is None:
        _missing("collectives", row)
        return
    row["collectives"] = census
    row["collective_total"] = sum(census.values())
    text = compiled.as_text()
    row["collectives_in_loops"] = collectives_in_loops(text)
    row["operand_slices"] = operand_slices(text)


def _analyze(compiled, row: dict) -> None:
    _cost_analysis(compiled, row)
    _memory_analysis(compiled, row)
    _collectives(compiled, row)


# -- the registry ------------------------------------------------------------


def record(family: str, key, source: str, compile_s: float,
           lowered=None, compiled=None) -> Optional[dict]:
    """One registry row per (family, jit key): called by the engine's
    first-call guard (lowered: the pre-dispatch trace; the analysis
    compile runs here, inside the analyze span) and by the export
    bank's load ladder (compiled: the deserialized executable —
    analyses are free).
    Never raises; returns the row (or None when disabled)."""
    if not enabled():
        return None
    with _trace.span(f"first_call:{family}/analyze", cat="compile",
                     also="program.obs"):
        try:
            return _record(family, key, source, compile_s, lowered,
                           compiled)
        except Exception:                    # noqa: BLE001 — observability
            _metrics.registry().inc("program.analysis_missing.record")
            return None


def _record(family, key, source, compile_s, lowered, compiled):
    reg = _metrics.registry()
    row = {"ts": round(time.time(), 3), "family": family,
           "key": str(key)[:200], "source": source,
           "compile_s": round(float(compile_s), 4)}
    if compiled is None and lowered is not None and mode() == "deep":
        _jit_tls.own = True
        try:
            compiled = lowered.compile()
        except Exception:                    # noqa: BLE001 — ladder rung
            _missing("compile", row)
        finally:
            _jit_tls.own = False
    if compiled is not None:
        _analyze(compiled, row)
    if lowered is not None:
        try:
            row["arena_gathers"] = arena_gathers(lowered.as_text())
        except Exception:                    # noqa: BLE001 — ladder rung
            _missing("arena_gathers", row)
    with _lock:
        rows = _STATE["rows"]
        rows[(family, row["key"])] = row
        if row.get("bytes_accessed") is not None:
            _STATE["by_family"][family] = row
        n = len(rows)
    reg.inc(f"program.records.{source}")
    reg.gauge("program.count", n)
    if row.get("bytes_accessed") is not None:
        reg.gauge(f"program.bytes_accessed.{family}",
                  row["bytes_accessed"])
    if row.get("flops") is not None:
        reg.gauge(f"program.flops.{family}", row["flops"])
    if row.get("peak_bytes") is not None:
        reg.gauge(f"program.peak_bytes.{family}", row["peak_bytes"])
    if row.get("collective_total") is not None:
        reg.gauge(f"program.collectives.{family}",
                  row["collective_total"])
    _stream_write(row)
    _ensure_collector()
    return row


def record_loaded(family: str, sig: str, loaded) -> Optional[dict]:
    """A deserialized exported-bank executable: zero compile seconds,
    analyses straight off the loaded Compiled — the row that keeps an
    `engine.compile_count == 0` cold start observable."""
    return record(family, sig, "exported", 0.0, compiled=loaded)


def table() -> List[dict]:
    """Every registry row (copies), oldest first — the list embedded
    under "programs" in metrics snapshots and BENCH artifacts."""
    with _lock:
        return [dict(r) for r in _STATE["rows"].values()]


def xla_bytes_for(tier: str, family: Optional[str] = None):
    """(family, bytes_accessed) of the newest analyzed program that
    serves `tier` (engine tier labels; an explicit family wins), or
    None when no compiler figure exists yet."""
    fams = (family,) if family else \
        TIER_FAMILIES.get(tier.split(".", 1)[0], ())
    with _lock:
        by = _STATE["by_family"]
        for f in fams:
            row = by.get(f)
            if row is not None:
                return f, row["bytes_accessed"]
    return None


def model_vs_xla(tier: str, model_bytes: int,
                 family: Optional[str] = None) -> str:
    """The drift gate: reconcile one dispatch's analytic bytes with
    the serving program's XLA bytes-accessed.  Publishes
    `program.model_drift_pct.<tier>` and counts
    `program.model_drift_exceeded.<tier>` past the pinned tolerance
    (documented divergence — the run keeps serving).  Returns the
    source tag for the tier's achieved-GB/s row: "xla" when a
    compiler figure backs the number, "model" otherwise."""
    if not enabled() or model_bytes <= 0:
        return "model"
    hit = xla_bytes_for(tier, family)
    if hit is None or not hit[1]:
        return "model"
    _, xla = hit
    drift = abs(float(model_bytes) - xla) / xla * 100.0
    reg = _metrics.registry()
    reg.gauge(f"program.model_drift_pct.{tier}", round(drift, 2))
    if drift > drift_tolerance_pct():
        reg.inc(f"program.model_drift_exceeded.{tier}")
    return "xla"


# -- live HBM telemetry ------------------------------------------------------


def host_rss_bytes() -> Optional[int]:
    """This process's resident set size: psutil when the container has
    it, else `/proc/self/statm` (field 1 × page size).  None on
    platforms with neither — the caller counts the missing rung."""
    try:
        import psutil                            # type: ignore
        return int(psutil.Process().memory_info().rss)
    except Exception:                            # noqa: BLE001 — optional
        pass
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:                            # noqa: BLE001 — non-Linux
        return None


def sample_memory(devices=None, force: bool = False) -> bool:
    """`device.memory_stats()` -> `mem.device.<k>.{in_use,peak,limit}`
    gauges, rate-limited by EXAML_MEM_SAMPLE_S (0 samples every call).
    Backends without allocator stats (CPU) fall back to the HOST
    resident set (`mem.host.rss` via psutil or /proc/self/statm) so CPU
    runs still carry real memory telemetry; only when even that rung is
    missing does `program.analysis_missing.memory_stats` count a truly
    absent sample.  Returns True when a sample was taken."""
    if not enabled():
        return False
    now = time.monotonic()
    interval = _env_float("EXAML_MEM_SAMPLE_S", 5.0)
    with _lock:
        last = _STATE["mem_last"]
        if not force and last is not None and now - last < interval:
            return False
        _STATE["mem_last"] = now
    reg = _metrics.registry()
    try:
        if devices is None:
            import jax
            devices = jax.local_devices()
        for d in devices:
            stats = d.memory_stats()
            if not stats:
                rss = host_rss_bytes()
                if rss is None:
                    reg.inc("program.analysis_missing.memory_stats")
                else:
                    reg.gauge("mem.host.rss", int(rss))
                continue
            k = getattr(d, "id", 0)
            for field, src in (("in_use", "bytes_in_use"),
                               ("peak", "peak_bytes_in_use"),
                               ("limit", "bytes_limit")):
                if src in stats:
                    reg.gauge(f"mem.device.{k}.{field}",
                              int(stats[src]))
                else:
                    reg.inc("program.analysis_missing.memory_stats")
    except Exception:                        # noqa: BLE001 — telemetry
        reg.inc("program.analysis_missing.memory_stats")
        return False
    return True


def _ensure_collector() -> None:
    """Every metrics snapshot carries a fresh memory sample (snapshot
    collectors are the designed place for device-touching gauges;
    `snapshot_light` flushes skip them by contract)."""
    if _STATE["collector"]:
        return
    _STATE["collector"] = True

    def _collect() -> bool:
        sample_memory()
        return True

    _metrics.registry().add_collector(_collect)


# -- the programs.p<procid>.jsonl stream -------------------------------------
# PR7 ledger discipline (obs/ledger.py): per-rank file next to the run
# ledger, append mode, flush per row, readers tolerate a torn final
# line.  The stream is the crash-durable form of the table; the
# metrics-snapshot embed is the queryable one.


def stream_name(proc) -> str:
    return f"programs.p{proc}.jsonl"


def _stream_write(row: dict) -> None:
    d = _ledger.active_dir() or os.environ.get(_ledger.ENV_VAR)
    if not d:
        return
    with _lock:
        f = _STATE["stream"]
        if f is None or _STATE["stream_dir"] != d:
            try:
                os.makedirs(d, exist_ok=True)
                f = open(os.path.join(
                    d, stream_name(_ledger._default_proc())), "a")
            except OSError:
                return
            _STATE.update(stream=f, stream_dir=d)
        try:
            f.write(json.dumps(row, separators=(",", ":"),
                               default=str) + "\n")
            f.flush()             # crash-robust: the last row lands
        except (OSError, ValueError):
            pass


def read_stream(path: str) -> List[dict]:
    """Rows of one programs stream, torn-final-line tolerant (same
    reader contract as ledger.read_events)."""
    rows: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue      # torn final line of a killed writer
                if isinstance(row, dict):
                    rows.append(row)
    except OSError:
        pass
    return rows


def read_dir(stream_dir: str) -> List[dict]:
    """Every rank's programs stream in `stream_dir`, merged in memory
    (viewers must not write into a run's artifact directory)."""
    try:
        names = sorted(n for n in os.listdir(stream_dir)
                       if n.startswith("programs.p")
                       and n.endswith(".jsonl"))
    except OSError:
        return []
    rows: List[dict] = []
    for name in names:
        rows.extend(read_stream(os.path.join(stream_dir, name)))
    rows.sort(key=lambda r: r.get("ts", 0))
    return rows
