"""The EXAML_* environment-variable registry (GL004's ground truth).

Every env var the runtime reads has exactly one entry here.  Fields:

* ``doc``: "readme" — operator-facing; GL004 verifies the README names
  it literally (the "Environment flags" table).  "registry" — an
  internal process contract (parent->child export, test hook); this
  entry's ``note`` IS the documentation and GL004 requires it
  non-empty.
* ``note``: one line on what the flag does / who sets it.
* ``import_time_ok``: justification string when a module-scope read is
  intentional (default: forbidden — import-time reads freeze the value
  before a supervisor/bank parent can pin the child's env).

Adding a read without an entry, deleting the last read of an entry, or
registering README documentation that is not actually there all fail
`python -m tools.graftlint`.
"""

ENV_REGISTRY = {
    # -- tier escape hatches / degradation ladder ------------------------
    "EXAML_FAST_TRAVERSAL": {
        "doc": "readme",
        "note": "0 pins the scan tier for full traversals (ladder rung)."},
    "EXAML_BATCH_SCAN": {
        "doc": "readme",
        "note": "0 disables the batched SPR scan tier."},
    "EXAML_BATCH_THOROUGH": {
        "doc": "readme",
        "note": "0 disables the batched thorough-insertion scorer."},
    "EXAML_BATCH_QUARTETS": {
        "doc": "readme",
        "note": "0 disables the batched quartet scorer."},
    "EXAML_UNIVERSAL": {
        "doc": "readme",
        "note": "0 opts out of the universal interpreter; force pins it "
                "(the supervisor's chunk->scan ladder rung)."},
    "EXAML_GRAD_SMOOTH": {
        "doc": "readme",
        "note": "0 restores the per-branch Newton smoothing path "
                "(whole-tree analytic gradients otherwise)."},
    "EXAML_GRAD_DAMPING": {
        "doc": "readme",
        "note": "base step scale for gradient-mode branch smoothing "
                "(default 1.0; the per-branch Rprop ladder caps at it)."},
    # -- numerics ---------------------------------------------------------
    "EXAML_CLV_DTYPE": {
        "doc": "readme",
        "note": "CLV storage dtype (f64 default; bf16 opt-in tier)."},
    "EXAML_DOT_PRECISION": {
        "doc": "readme",
        "note": "jax dot precision for the likelihood contractions."},
    "EXAML_PSR_REFINE": {
        "doc": "readme",
        "note": "0 restores exact reference PSR categorization."},
    # -- compile cache / banking ------------------------------------------
    "EXAML_COMPILE_CACHE": {
        "doc": "readme",
        "note": "persistent compile-cache path; 0 disables."},
    "EXAML_COMPILE_TIMEOUT": {
        "doc": "readme",
        "note": "per-family compile deadline (bank workers AND the "
                "in-process watchdog; --compile-timeout exports it)."},
    "EXAML_HOST_FINGERPRINT": {
        "doc": "readme",
        "note": "overrides the CPU-feature fingerprint keying the "
                "persistent cache (cross-host SIGILL guard)."},
    "EXAML_BANK_WORKERS": {
        "doc": "readme",
        "note": "parallel bank compile-worker count."},
    "EXAML_BANK_TEST_HANG": {
        "doc": "registry",
        "note": "test hook: bank worker hangs on the named family "
                "(tests/test_bank.py forced-hang e2e)."},
    "EXAML_EXPORT_BANK": {
        "doc": "readme",
        "note": "exported program bank (ops/export_bank.py): on "
                "serializes/deserializes compiled executables next to "
                "the persistent cache (zero-compile restart); require "
                "hard-fails any fall-through (CI gate); default off — "
                "artifacts are jaxlib+platform locked."},
    # -- observability -----------------------------------------------------
    "EXAML_TRACE_DIR": {
        "doc": "readme",
        "note": "enables the Perfetto span tracer (--trace-events)."},
    "EXAML_LEDGER_DIR": {
        "doc": "readme",
        "note": "enables the run ledger in subprocesses (--ledger "
                "exports it to bank workers and gang ranks)."},
    "EXAML_METRICS_FLUSH_S": {
        "doc": "readme",
        "note": "periodic --metrics flush cadence (chaos tests pin 0)."},
    "EXAML_LAUNCH_LATENCY_S": {
        "doc": "readme",
        "note": "launch-latency floor for the dispatch-bound regime "
                "classifier (default 45 us)."},
    "EXAML_TRAFFIC_WINDOW_DISPATCHES": {
        "doc": "readme",
        "note": "min blocking dispatches per achieved-GB/s window."},
    "EXAML_TRAFFIC_WINDOW_WALL_S": {
        "doc": "readme",
        "note": "min wall seconds per achieved-GB/s window."},
    "EXAML_PROGRAM_OBS": {
        "doc": "readme",
        "note": "program observatory mode: deep (default: registry rows "
                "+ XLA cost/memory analyses), rows (no analyses), "
                "off/0 (disabled)."},
    "EXAML_MEM_SAMPLE_S": {
        "doc": "readme",
        "note": "min seconds between device memory_stats() samples "
                "(default 5; 0 samples every call)."},
    "EXAML_MEM_BUDGET_BYTES": {
        "doc": "readme",
        "note": "absolute memory-governor admission budget in bytes "
                "(resilience/memgov.py; wins over the fraction)."},
    "EXAML_MEM_BUDGET_FRACTION": {
        "doc": "readme",
        "note": "memory-governor budget as a fraction of the device "
                "limit (default 0.90 headroom; the supervisor's "
                "alloc-oom restart pins it down by halving)."},
    "EXAML_MEM_OOM_STRIKES": {
        "doc": "readme",
        "note": "consecutive unrecovered allocator-OOM strikes before "
                "the governor escalates to the supervisor as "
                "alloc-oom (default 3; 0 escalates on the first)."},
    "EXAML_DRIFT_TOL_PCT": {
        "doc": "readme",
        "note": "model-vs-XLA bytes drift tolerance in percent "
                "(default 25; past it program.model_drift_exceeded "
                "counts)."},
    # -- resilience / gang process contract --------------------------------
    "EXAML_FAULTS": {
        "doc": "readme",
        "note": "armed fault-injection specs (--inject-fault appends)."},
    "EXAML_HEARTBEAT_FILE": {
        "doc": "readme",
        "note": "heartbeat publish path (supervisor exports it to the "
                "child; rank files add .p<k>)."},
    "EXAML_PROCID": {
        "doc": "readme",
        "note": "gang rank of this process (supervisor/launch export)."},
    "EXAML_GANG_RANKS": {
        "doc": "readme",
        "note": "gang world size (supervisor/launch export)."},
    "EXAML_RESTART_COUNT": {
        "doc": "registry",
        "note": "supervisor attempt number exported to retries; gates "
                "attempt-scoped fault specs and backoff jitter."},
    "EXAML_FLEET_HANG_ATTEMPTS": {
        "doc": "readme",
        "note": "job-stuck evidence ('id=n,id=n') the supervisor "
                "exports so a resumed fleet driver quarantines repeat "
                "hang offenders."},
    # -- fleet tier --------------------------------------------------------
    "EXAML_FLEET_UNIVERSAL": {
        "doc": "readme",
        "note": "1/0 forces/disables universal-interpreter routing for "
                "fleet jobs (default: on for --serve only)."},
    "EXAML_FLEET_SPECIALIZE_AFTER": {
        "doc": "readme",
        "note": "promote a recurring novel profile to the specialized "
                "batched program after K jobs."},
    "EXAML_MESH": {
        "doc": "readme",
        "note": "SxT likelihood-fabric mesh (same as --mesh; the flag "
                "wins): S site shards x T tree slices over S*T "
                "devices; 1x1 disables."},
    "EXAML_FLEET_UNIBATCH": {
        "doc": "readme",
        "note": "1 batches mixed-profile novel jobs through the "
                "vmapped select_n universal program (measured ~3x "
                "per-step compute: a dispatch-bound-only win, so "
                "default off; fleet.universal_retrace counts the "
                "forgone batching)."},
    # -- tools -------------------------------------------------------------
    "EXAML_DEBUG_MODOPT": {
        "doc": "registry",
        "note": "1 prints per-round model-optimizer traces (dev aid; "
                "tests/test_reference_parity.py uses it)."},
}
