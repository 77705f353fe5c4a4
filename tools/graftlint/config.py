"""graftlint configuration: scan roots, registered seams and helpers.

This module is the REGISTRY half of the linter: checks consult these
tables instead of hard-coding repo knowledge, so registering a new
blocking seam or bounding helper is a reviewed one-line diff here —
not a silent convention drift in the code it guards.
"""

from __future__ import annotations

import fnmatch

# -- scan scope --------------------------------------------------------------
# Lint targets (repo-relative).  tests/ and the docs are EVIDENCE
# corpora (GL004-GL006 diff against them) but are not themselves linted
# — tests monkeypatch env vars, read private counters and exercise
# hazards on purpose.
LINT_ROOTS = ("examl_tpu", "tools")
EVIDENCE_TEST_ROOT = "tests"
EVIDENCE_DOCS = ("README.md",)
EVIDENCE_WORKFLOWS = (".github/workflows",)

# -- GL002: bounding helpers -------------------------------------------------
# A raw int is allowed into a program-cache key only after passing one
# of these (final path component matched): the size bucketers and the
# smallest-already-compiled pad pickers.  `min`/`max` over already-
# bounded values stay bounded, so they are OK combinators, not sources.
BOUNDING_HELPERS = frozenset({
    "bucket_len", "_bucket_len", "next_pow2",
    "_pick_jpad", "pick_pads",
})

# Methods whose first argument is a program-cache key (the engine's
# shared LRU: ops/engine.py cache_get/cache_put).
CACHE_KEY_METHODS = frozenset({"cache_get", "cache_put"})

# -- GL003: registered host-sync seams ---------------------------------------
# (path glob, function name) pairs allowed to block on a dispatch
# result.  These are the BLOCKING trav-eval paths — their wall time is
# what feeds the achieved-GB/s windows, so the sync is the measurement
# — plus the shared dispatch stopwatch.  Everything else must stay
# async: a stray float() on a hot path serializes the dispatch pipe.
SYNC_SEAMS = (
    # The engine's blocking trav-eval family: these fused eval paths
    # return host lnL BY CONTRACT — their blocking wall time is what
    # feeds the achieved-GB/s traffic windows (engine._account_traffic),
    # so the sync here IS the measurement.
    ("examl_tpu/ops/engine.py", "_run_fast_flat"),
    ("examl_tpu/ops/engine.py", "_universal_dispatch"),
    # Batched SPR scan/thorough scoring: one sync per candidate batch —
    # the candidate lnls ARE the selection input on the host.
    ("examl_tpu/ops/engine.py", "batched_scan"),
    ("examl_tpu/ops/engine.py", "batched_thorough"),
    # Whole-tree gradient pass: d1/d2 for all branches feed the
    # host-side batched Newton update — one sync per smoothing sweep
    # (vs one per BRANCH on the per-branch path), and its blocking
    # wall is the "grad" tier's achieved-GB/s measurement.
    ("examl_tpu/ops/engine.py", "whole_tree_gradients"),
    ("examl_tpu/fleet/batch.py", "_grad_batch"),
    # Fleet batched evaluation: per-job host lnL rows at the batch
    # boundary feed the results table and the fsync'd journal.  The
    # launch half (launch_eval / launch_universal) stays ASYNC so
    # device lanes overlap; `collect` is the one blocking seam.
    ("examl_tpu/fleet/batch.py", "collect"),
    # Batched quartet scoring returns host lnls for candidate selection
    # at the batch boundary (one sync per n_jobs-sized batch).
    ("examl_tpu/search/quartets_batch.py", "score_jobs"),
    # Fleet weights-batch evaluation: per-job host lnL rows feed the
    # fsync'd results journal at the batch boundary.
    ("examl_tpu/fleet/batch.py", "eval_weights_batch"),
)


def is_sync_seam(path: str, func_name: str) -> bool:
    return any(fnmatch.fnmatch(path, pat) and func_name == name
               for pat, name in SYNC_SEAMS)


# Names that taint a local as "compiled dispatch function" when they
# appear in its assignment (cache fetch/insert and direct jit); the
# sync sinks themselves (float/bool/int, np.asarray/np.array, .item())
# are structural in checks_jax.check_host_sync.
DISPATCH_FN_SOURCES = frozenset({"cache_get", "cache_put", "jit"})

# -- GL005: obs-name drift ---------------------------------------------------
# Emitters: obs facade methods whose first argument is a metric name.
OBS_EMIT_METHODS = frozenset({"inc", "gauge", "observe", "timer"})
# Span openers: `also=` names a second registry timer the span feeds
# (obs/trace.py; the engine's `_dispatch` passes it through).
OBS_SPAN_METHODS = frozenset({"span", "_dispatch"})
# Ledger event emitters (first argument is the event kind).
LEDGER_EMIT_METHODS = frozenset({"ledger_event", "event"})
# Consumers inside runtime code (reading back a counter by name).
OBS_CONSUME_METHODS = frozenset({"counter"})
# Render surfaces diffed against the emit set.
RENDER_FILES = ("tools/run_report.py", "tools/top.py")
# Files whose dotted string constants count as EMITS: the jax-free
# supervisor writes counter names as raw dict keys into the snapshot
# it merges (no obs facade available by contract).
EMIT_SURFACES = ("examl_tpu/resilience/supervisor.py",)

# Dotted string constants in the render files that look like metric
# names but are not (bench-JSON field paths etc.) — entries here are
# excluded from the phantom-render direction of GL005.  Currently
# empty: every dotted constant the render surfaces use IS a metric or
# ledger name.
RENDER_NAME_ALLOW = frozenset()

# -- GL004: env helpers ------------------------------------------------------
# Functions whose first argument is an env-var NAME (the typed-read
# helpers); a constant EXAML_* first arg at their call sites counts as
# a read of that var.
ENV_READ_HELPERS = frozenset({"_env_int", "_env_float", "_env_str"})

# -- GL007 -------------------------------------------------------------------
# Any call whose final name component contains this substring counts
# as the staged-file fsync (os.fsync, self._fsync_file, _fsync_dir).
FSYNC_MARKER = "fsync"
