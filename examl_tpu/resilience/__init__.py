"""examl_tpu.resilience — fault injection + self-healing run supervision.

Why this subsystem exists (VERDICT r04/r05): two accelerator windows
were lost to wedges even after AOT banking made *compiles* killable — a
dispatch/collective wedge, a SIGTERM, or a corrupt checkpoint still
killed the whole run.  The reference survives interruption through its
checkpoint/restart machinery (`searchAlgo.c:1102-1750`, SURVEY §5.4);
this package makes our version actually survive the failure modes we
have observed, and makes every recovery path *testable on CPU*:

* `faults`    — registry of named, deterministic injection points armed
                via `EXAML_FAULTS` / `--inject-fault`, wired into the
                real seams (engine dispatch, compile monitor, lnL
                boundary, checkpoint write, bank worker, heartbeat).
* `exitcause` — the ONE worker/child exit-classification used by
                ops/bank.py and the supervisor (SIGILL vs
                OOM vs hang-kill vs preempt).
* `heartbeat` — per-iteration liveness file emitted by the search loop
                from the obs registry; the supervisor's only way to see
                a dispatch/collective wedge (the compile watchdog
                covers compiles, nothing covered dispatches).
* `preempt`   — SIGTERM/SIGINT → flag → emergency checkpoint at the
                next checkpoint-callback site → clean resumable exit
                (EXIT_PREEMPTED).
* `supervisor`— `--supervise`: runs the search as a killable child,
                watches the heartbeat, classifies failures, restarts
                from the newest checkpoint with capped retries, backoff
                and escalating degradation pins (chunk→universal→scan).

IMPORT CONTRACT: this `__init__` and the `exitcause`/`faults` modules
are stdlib-only and must stay that way — the supervisor parent
imports them and must never load jax (a broken
accelerator plugin can hang the importing process, and on
exclusive-access accelerators the parent must never take the device
handle the child needs).
"""
