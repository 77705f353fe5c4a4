"""Self-healing run supervisor (`--supervise`): converts "lost window"
into "resumed run".

The supervisor is a thin, jax-free parent (IMPORT CONTRACT in the
package `__init__`: on exclusive-access accelerators the parent must
never take the device handle the child needs, and a hung accelerator
plugin must not be able to hang the watcher).  It:

* runs the search CLI as a KILLABLE child in its own process group
  (`python -m examl_tpu.cli.main`, `--supervise` stripped);
* exports `EXAML_HEARTBEAT_FILE` and watches it — once the search loop
  starts beating, a stall longer than `--supervise-stall` means a
  dispatch/collective wedge (the class the compile watchdog cannot
  see) and the whole child process group is SIGKILLed;
* classifies every death through the shared exit taxonomy
  (`resilience/exitcause.py`: SIGILL vs OOM vs hang-kill vs preempt);
* restarts from the newest checkpoint (`-R` once one exists) with
  capped retries, exponential backoff, and ESCALATING degradation pins
  mirroring the bank's escape hatches: retry 1 is a plain retry (the
  chunk tier again), retry 2 pins `EXAML_UNIVERSAL=force`
  (chunk→universal: the topology-as-data interpreter compiles ONE
  program regardless of topology, so a wedge inside a per-profile
  chunk compile cannot recur), retry 3+ pins the scan tier
  (`EXAML_FAST_TRAVERSAL=0`, `EXAML_UNIVERSAL=0`,
  `EXAML_BATCH_SCAN=0`, `EXAML_BATCH_THOROUGH=0`) — the one tier
  hardware-proven everywhere;
* advertises the exported program bank (ops/export_bank.py) to every
  respawned child via EXAML_EXPORT_BANK passthrough: a retry's load
  ladder deserializes executables instead of recompiling, so restart
  MTTR is the failure, not the bank phase.  "Exported bank unusable"
  is NOT a failure cause in this ladder — the child degrades to its
  normal bank/compile phase in-process with `bank.export.rejected.*`
  counters carrying the evidence;
* treats a child exit of EXIT_PREEMPTED (75) as RESUMABLE: restarted
  immediately, no retry consumed (capped separately so a preemption
  storm still terminates);
* forwards its own SIGTERM/SIGINT to the child as SIGTERM, so
  preempting the supervisor preempts the run gracefully end-to-end;
* merges its `resilience.*` counters into the child's `--metrics`
  snapshot, so one artifact carries both sides' evidence
  (`resilience.restarts`, `resilience.heartbeat_stalls`,
  `resilience.preempts`, plus the child's `engine.nonfinite_retries`).

`EXAML_RESTART_COUNT` is exported to each attempt so fault-injection
specs (`resilience/faults.py`) can target a single attempt — the
mechanism that makes "crash once, then recover" chaos tests converge.

`--launch N` (GangSupervisor, below) extends the same contract to
multi-process runs: the supervisor spawns all N ranks itself, watches
the per-rank heartbeat files, implements rank-level failure domains
(rank death / collective wedge / single-rank straggler), and restarts
the WHOLE gang — lockstep data parallelism makes partial survival
useless — from the newest coordinated checkpoint, shrinking the world
elastically when one rank keeps dying.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

from examl_tpu.obs import ledger as _ledger
from examl_tpu.resilience import exitcause, heartbeat

# Degradation ladder, in escalation order (mirrors ops/bank.FALLBACK_ENV
# without importing it: bank pulls in obs/jax, this parent must not).
DEGRADE_LADDER = (
    {},
    {},
    {"EXAML_UNIVERSAL": "force"},
    {"EXAML_FAST_TRAVERSAL": "0",
     "EXAML_UNIVERSAL": "0", "EXAML_BATCH_SCAN": "0",
     "EXAML_BATCH_THOROUGH": "0", "EXAML_GRAD_SMOOTH": "0"},
)

DEFAULT_RETRIES = 3
DEFAULT_STALL = 300.0
POLL_S = 0.25

# Supervisor flags stripped from the child's argv.  Values live with the
# flag (argparse two-token form) — single-token "--flag=value" is also
# handled by prefix match.
_SUPERVISOR_FLAGS = {"--supervise": 0, "--supervise-retries": 1,
                     "--supervise-stall": 1, "--supervise-backoff": 1,
                     "--launch": 1, "--launch-emulate": 0,
                     "--launch-min-ranks": 1}

# Elastic resume: after the SAME rank has caused this many CONSECUTIVE
# failed attempts, the gang degrades to N-1 ranks instead of burning the
# retry budget on a slot that keeps dying (site slices re-derive from
# the byteFile window at parse time; checkpoint state is topology+model,
# so a smaller world resumes the same search).
ELASTIC_CONSECUTIVE_DEATHS = 2

# Gang causes that count as a RANK DEATH (a process died) as opposed to
# a watcher stall verdict.
_RANK_DEATH_CAUSES = frozenset({
    exitcause.CAUSE_CRASH, exitcause.CAUSE_OOM_KILL,
    exitcause.CAUSE_SIGILL, exitcause.CAUSE_ERROR,
    exitcause.CAUSE_TERMINATED})


def backoff_delay(base: float, retry: int, key: str = "",
                  cap: float = 60.0) -> float:
    """Exponential restart backoff with deterministic-seeded jitter.

    N gang ranks — or a future fleet of supervised jobs — all sleeping
    the same `base * 2**k` ladder synchronize into restart storms that
    slam a recovering device or coordinator simultaneously.  The jitter
    fraction in [0.5, 1.0) is drawn from a blake2b hash of (key, retry),
    so one run's delay sequence is REPRODUCIBLE (unit-testable, and a
    resumed supervisor re-derives the same schedule) while distinct run
    ids decorrelate across the fleet.  The cap bounds both the raw
    exponential and the jittered result."""
    raw = min(cap, base * (2 ** max(0, int(retry) - 1)))
    h = int.from_bytes(hashlib.blake2b(f"{key}:{retry}".encode(),
                                       digest_size=8).digest(), "big")
    return min(cap, raw * (0.5 + 0.5 * h / 2.0 ** 64))


def classify_stall(ages: List[float], stall: float) -> Optional[str]:
    """The gang watcher's stall verdict from the LIVE ranks' beat ages.

    * every rank stale  -> collective wedge (the lockstep program is
      blocked inside a collective/dispatch on all ranks at once);
    * one rank stale while the freshest rank is actively beating
      (age <= stall/2) -> single-rank straggler;
    * one rank stale while the others are MERELY AGING (> stall/2 but
      not yet stale) -> ambiguous: a collective wedge reaches ranks an
      allreduce apart, so keep watching — either the fresh ranks beat
      again (straggler) or everyone crosses the line (collective).
      Deciding early here would misread a wedge's first victim as a
      straggler and skip the tier-degradation ladder.
    """
    if not ages:
        return None
    stale = [a > stall for a in ages]
    if all(stale):
        return exitcause.CAUSE_COLLECTIVE_WEDGE
    if any(stale) and min(ages) <= stall / 2.0:
        return exitcause.CAUSE_STRAGGLER
    return None


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_argv(argv: List[str]) -> List[str]:
    """The supervised child's argument list: the original CLI argv minus
    the supervisor-only flags (`--inject-fault` passes THROUGH — the
    child arms the registry; attempt gating keeps retries clean)."""
    out: List[str] = []
    skip = 0
    for tok in argv:
        if skip:
            skip -= 1
            continue
        flag = tok.split("=", 1)[0]
        if flag in _SUPERVISOR_FLAGS:
            if "=" not in tok:
                skip = _SUPERVISOR_FLAGS[flag]
            continue
        out.append(tok)
    return out


def checkpoint_glob(workdir: str, run_id: str) -> List[str]:
    """Checkpoint files for (workdir, run_id) — the same naming
    CheckpointManager publishes (search/checkpoint.py; that module
    imports jax via the instance, so the pattern is mirrored here and
    pinned by a cross-check test)."""
    return sorted(glob.glob(os.path.join(
        workdir, f"ExaML_binaryCheckpoint.{run_id}.ckpt_*.json.gz")))


def resume_evidence(workdir: str, run_id: str) -> List[str]:
    """Everything a retry can resume FROM: published checkpoints plus
    the fleet results journal(s) (fleet/quarantine.py — written per
    finished job, so one can exist before the first checkpoint
    publishes when a crash lands between a batch and its checkpoint;
    run_fleet reconciles journal ∪ checkpoint under -R).  Leased gangs
    write one journal per rank (`.r<k>` suffix)."""
    return checkpoint_glob(workdir, run_id) + sorted(set(
        glob.glob(os.path.join(workdir, f"ExaML_fleetJournal.{run_id}"))
        + glob.glob(os.path.join(
            workdir, f"ExaML_fleetJournal.{run_id}.r*"))))


def _repo_env() -> Dict[str, str]:
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if repo not in pp:
        env["PYTHONPATH"] = os.pathsep.join([repo] + pp)
    return env


class Supervisor:
    def __init__(self, argv: List[str], workdir: str, run_id: str,
                 max_retries: int = DEFAULT_RETRIES,
                 stall_timeout: float = DEFAULT_STALL,
                 backoff: float = 2.0,
                 metrics_file: Optional[str] = None,
                 ledger_dir: Optional[str] = None,
                 log=print):
        self.base_argv = child_argv(argv)
        self.workdir = workdir
        self.run_id = run_id
        self.max_retries = max_retries
        self.stall_timeout = stall_timeout
        self.backoff = backoff
        self.metrics_file = metrics_file
        self.log = lambda msg: log(f"supervise: {msg}")
        os.makedirs(workdir, exist_ok=True)
        # Run ledger: the supervisor writes its OWN stream
        # (`ledger.psup.jsonl` — sharing the children's directory, never
        # their rank files) so kill/restart/elastic decisions land on
        # the same merged timeline as the children's compile/phase
        # events.  obs.ledger is stdlib-only, honoring the jax-free
        # parent contract.
        self.ledger_dir = _ledger.default_dir(ledger_dir, metrics_file)
        if self.ledger_dir:
            _ledger.enable(self.ledger_dir, proc="sup")
        self.hb_path = os.path.join(workdir,
                                    f".heartbeat.{run_id}.json")
        # Counters mirrored into the metrics snapshot at the end — the
        # supervisor is jax/obs-free, so it keeps its own dict.
        self.counters: Dict[str, float] = {}
        self.attempts: List[dict] = []
        self.degrade_level = 0
        self._preempt_signal: Optional[str] = None
        self._child: Optional[subprocess.Popen] = None
        self._last_argv: List[str] = []
        # Job-level fault domain (fleet runs): per-job hang-attempt
        # counts accumulated across fleet-job-stuck kills, exported to
        # every retry as EXAML_FLEET_HANG_ATTEMPTS so the fleet driver
        # can quarantine a job that keeps blowing its deadline instead
        # of burning run-level retries on it.
        self._hang_attempts: Dict[str, int] = {}
        self._last_stuck_jobs: List[str] = []
        self._job_stuck_kills = 0
        # Memory fault domain: an alloc-oom exit (EXIT_ALLOC_OOM — the
        # child's memory governor gave up on evict+shrink) pins the
        # admission budget fraction DOWN for every later attempt,
        # halving toward the floor — the tier ladder's discipline
        # applied to memory instead of program tiers.
        self._mem_fraction_pin: Optional[float] = None

    # -- bookkeeping --------------------------------------------------------

    def _inc(self, name: str, v: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + v

    def _pins(self) -> Dict[str, str]:
        pins = dict(DEGRADE_LADDER[min(self.degrade_level,
                                       len(DEGRADE_LADDER) - 1)])
        if self._mem_fraction_pin is not None:
            pins["EXAML_MEM_BUDGET_FRACTION"] = \
                f"{self._mem_fraction_pin:.4g}"
        return pins

    def _attempt_argv(self) -> List[str]:
        argv = list(self.base_argv)
        if "-R" not in argv and resume_evidence(self.workdir,
                                                self.run_id):
            argv.append("-R")
        return argv

    # Shared retry scalars (used verbatim by both supervision loops —
    # keep the semantics in ONE place so the single-child and gang
    # policies can never drift):

    def _escalate(self, cause: str) -> None:
        if cause == exitcause.CAUSE_ALLOC_OOM:
            # The child diagnosed a device-allocator OOM itself: the
            # program tier is fine, its working set is not — halve the
            # admission budget fraction instead of degrading the tier.
            # 0.90 mirrors memgov.DEFAULT_FRACTION, 0.05 its floor
            # (this parent is jax/obs-free by contract and must not
            # import memgov's dependency closure).
            cur = self._mem_fraction_pin
            if cur is None:
                try:
                    cur = float(os.environ.get(
                        "EXAML_MEM_BUDGET_FRACTION") or 0.90)
                except ValueError:
                    cur = 0.90
            self._mem_fraction_pin = max(0.05, cur / 2.0)
            self._inc("resilience.mem_budget_pins")
            _ledger.event("supervisor.mem_budget_pin",
                          fraction=self._mem_fraction_pin)
            return
        if cause in exitcause.TIER_SUSPECT:
            # The step guarantees the scan-tier FLOOR (the ladder's
            # last rung) is reached within the configured retry
            # budget: a --supervise-retries smaller than the ladder
            # skips intermediate rungs (e.g. the universal rung)
            # rather than dying with the hardware-proven floor
            # untried.
            floor = len(DEGRADE_LADDER) - 1
            step = -(-floor // max(1, self.max_retries))   # ceil div
            self.degrade_level = min(self.degrade_level + step, floor)

    def _retry_delay(self, retries: int) -> float:
        return backoff_delay(self.backoff, retries, key=self.run_id)

    @staticmethod
    def _exhausted_rc(rc: Optional[int]) -> int:
        """Final exit status when the retry budget is spent.  Signal
        deaths surface as the conventional 128+signum (a raw negative
        rc through sys.exit becomes an unclassifiable 247-style
        status)."""
        if rc is None:
            return 1
        return 128 - rc if rc < 0 else (rc or 1)

    # -- signal forwarding --------------------------------------------------

    def _live_children(self) -> List[subprocess.Popen]:
        """Children a preemption must be forwarded to (the gang
        supervisor overrides this with its whole rank list)."""
        return [self._child] if self._child is not None else []

    def _signal_children(self, sig) -> None:
        for child in self._live_children():
            if child is not None and child.poll() is None:
                try:
                    os.killpg(child.pid, sig)
                except (OSError, ProcessLookupError):
                    pass

    def _install_signals(self):
        if not hasattr(signal, "SIGTERM"):
            return None

        def handler(signum, frame):
            self._preempt_signal = signal.Signals(signum).name
            # graceful: the children checkpoint and exit resumable
            self._signal_children(signal.SIGTERM)

        try:
            return (signal.signal(signal.SIGTERM, handler),
                    signal.signal(signal.SIGINT, handler))
        except ValueError:                  # non-main thread (tests)
            return None

    def _restore_signals(self, prior) -> None:
        if prior is not None:
            signal.signal(signal.SIGTERM, prior[0])
            signal.signal(signal.SIGINT, prior[1])

    # -- one attempt --------------------------------------------------------

    def _spawn(self, restarts_total: int) -> subprocess.Popen:
        env = _repo_env()
        env["EXAML_HEARTBEAT_FILE"] = self.hb_path
        env["EXAML_RESTART_COUNT"] = str(restarts_total)
        if self._hang_attempts:
            # Fleet job-stuck evidence rides into the retry: the driver
            # bumps these jobs' attempt counts and quarantines any past
            # its cap (fleet/quarantine.py parses this).
            env["EXAML_FLEET_HANG_ATTEMPTS"] = ",".join(
                f"{jid}={n}" for jid, n in sorted(
                    self._hang_attempts.items()))
        env.update(self._pins())
        if restarts_total and (env.get("EXAML_EXPORT_BANK") or "") \
                .strip().lower() not in ("", "0", "off", "no"):
            # Zero-compile restart (ops/export_bank.py): the exported
            # program bank rides the environment into every respawned
            # child, whose load ladder deserializes executables instead
            # of re-running the bank/warm compile phase — MTTR is the
            # failure, not the recompilation.  An unusable exported
            # bank is a counter-carrying downgrade to the normal bank
            # phase inside the child (bank.export.rejected.*), never a
            # distinct exit cause this ladder reacts to.
            self.log("attempt %d: exported program bank advertised "
                     "(EXAML_EXPORT_BANK=%s)"
                     % (restarts_total, env["EXAML_EXPORT_BANK"]))
        argv = self._last_argv = self._attempt_argv()
        pins = self._pins()
        self.log(f"attempt {restarts_total}: starting "
                 + ("(resume -R) " if "-R" in argv else "")
                 + (f"[pins {pins}] " if pins else "")
                 + " ".join(argv))
        try:
            os.unlink(self.hb_path)         # stale beats must not mask
        except OSError:                     # a child that never starts
            pass
        return subprocess.Popen(
            [sys.executable, "-m", "examl_tpu.cli.main"] + argv,
            env=env, start_new_session=True)

    def _kill_group(self, child: subprocess.Popen) -> None:
        """SIGKILL the child's whole process group: bank workers and any
        other helpers must die with it, or the retry races them for the
        accelerator."""
        for target in (child.pid,):
            try:
                os.killpg(target, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                try:
                    child.kill()
                except OSError:
                    pass
        child.wait()

    def _watch(self, child: subprocess.Popen) -> str:
        """Wait for exit or heartbeat stall; returns the exit cause."""
        spawned = time.time()
        # Startup (data load, banking, first compiles, the pre-search
        # model opt) legitimately produces no beats, so the deadline
        # for the FIRST beat is much more generous than the stall
        # window — but it must exist: a dispatch that wedges before the
        # first search iteration would otherwise hang the supervisor
        # forever.
        first_beat_deadline = max(4.0 * self.stall_timeout, 900.0)
        while True:
            rc = child.poll()
            if rc is not None:
                return exitcause.classify(rc)
            hb_age = heartbeat.age(self.hb_path)
            # Fleet job-level fault domain: the last beat may DECLARE
            # an in-flight batch (job ids + wall-clock deadline).  The
            # deadline is enforced INDEPENDENTLY of the generic stall
            # window — the kill lands when the DEADLINE expires (not at
            # max(stall, deadline)), and it works under
            # --supervise-stall 0, where only declared deadlines are
            # watched.  A completed batch clears the declaration, so a
            # fresh record without one can never trigger this verdict.
            deadline = None
            fl = {}
            if hb_age is not None:
                last_rec = heartbeat.read(self.hb_path) or {}
                fl = last_rec.get("fleet") or {}
                if fl.get("jobs") and fl.get("deadline"):
                    deadline = float(fl["deadline"])
            if deadline is not None and time.time() > deadline:
                jobs = [str(j) for j in fl["jobs"]]
                self._last_stuck_jobs = jobs
                self.log(
                    "fleet batch exceeded its per-job deadline "
                    f"(jobs {','.join(jobs)}; beat age "
                    + (f"{hb_age:.0f}s" if hb_age is not None
                       else "n/a")
                    + "); killing the child process group "
                    "(job-level fault domain: no run-level "
                    "retry consumed)")
                self._inc("resilience.fleet_job_stuck_kills")
                _ledger.event("supervisor.kill",
                              reason="fleet-job-stuck",
                              jobs=",".join(jobs),
                              beat_age_s=(round(hb_age, 1)
                                          if hb_age is not None
                                          else None))
                self._kill_group(child)
                return exitcause.CAUSE_FLEET_JOB_STUCK
            if self.stall_timeout:
                stalled = (hb_age > self.stall_timeout
                           if hb_age is not None else
                           time.time() - spawned > first_beat_deadline)
                if stalled and deadline is not None:
                    # A declared batch with a live deadline is
                    # legitimately allowed to outlast the stall window:
                    # keep watching until the deadline verdict above.
                    time.sleep(POLL_S)
                    continue
                if stalled:
                    # The search loop stopped beating (or never
                    # started): dispatch/collective wedge.  Kill the
                    # whole group and classify ourselves — our SIGKILL
                    # must not read as an OOM kill.
                    last = heartbeat.read(self.hb_path) or {}
                    self.log(
                        "heartbeat stalled ("
                        + (f"{hb_age:.0f}s > {self.stall_timeout:.0f}s"
                           if hb_age is not None else
                           f"no first beat within {first_beat_deadline:.0f}s")
                        + f"; last state {last.get('state')!r} seq "
                        f"{last.get('seq')}); killing the child process "
                        "group")
                    self._inc("resilience.heartbeat_stalls")
                    _ledger.event("supervisor.kill",
                                  reason="heartbeat-stall",
                                  beat_age_s=(round(hb_age, 1)
                                              if hb_age is not None
                                              else None),
                                  last_state=last.get("state"))
                    self._kill_group(child)
                    return exitcause.CAUSE_HANG_KILL
            time.sleep(POLL_S)

    # -- the supervision loop -----------------------------------------------

    def run(self) -> int:
        prior = self._install_signals()
        retries = 0
        preempts = 0
        restarts_total = 0
        rc = 1
        try:
            while True:
                if self._preempt_signal is not None:
                    # Preempted BETWEEN children (during the backoff
                    # sleep or before the first spawn): there is no
                    # child to forward to — exit resumable now instead
                    # of launching an attempt the grace window will
                    # just SIGKILL.
                    self.log(f"supervisor preempted "
                             f"({self._preempt_signal}) between "
                             "attempts; not restarting")
                    self._inc("resilience.preempts")
                    return exitcause.EXIT_PREEMPTED
                t0 = time.time()
                self._child = child = self._spawn(restarts_total)
                cause = self._watch(child)
                self._child = None
                rc = child.returncode
                rec = {
                    "attempt": restarts_total, "cause": cause,
                    "returncode": rc, "seconds": round(time.time() - t0, 2),
                    "pins": self._pins(),
                    "resumed": "-R" in self._last_argv}
                if cause != exitcause.CAUSE_OK:
                    rec["partial_counters"] = self._partial_counters(t0)
                self.attempts.append(rec)
                desc = exitcause.exit_desc(rc, none_desc="(hang-killed)")

                if cause == exitcause.CAUSE_OK:
                    self.log(f"run completed after {restarts_total} "
                             "restart(s)")
                    _ledger.event("supervisor.done",
                                  restarts=restarts_total)
                    return 0
                if self._preempt_signal is not None:
                    # WE were preempted: the child checkpointed (or
                    # died); do not restart — exit resumable ourselves.
                    self.log(f"supervisor preempted ({self._preempt_signal})"
                             f"; child exited {desc}; not restarting")
                    self._inc("resilience.preempts")
                    return exitcause.EXIT_PREEMPTED
                if cause == exitcause.CAUSE_PREEMPT:
                    # The CHILD was preempted externally but we were
                    # not: resume immediately, no retry consumed.
                    preempts += 1
                    self._inc("resilience.preempts")
                    if preempts > max(10, 5 * self.max_retries):
                        self.log("preemption storm: giving up")
                        return exitcause.EXIT_PREEMPTED
                    restarts_total += 1
                    self._inc("resilience.restarts")
                    _ledger.event("supervisor.restart", cause="preempt",
                                  retry_consumed=False)
                    self.log(f"child preempted {desc}; resuming "
                             "(no retry consumed)")
                    continue
                if cause == exitcause.CAUSE_FLEET_JOB_STUCK:
                    # JOB-level fault domain: the batch's jobs pay (the
                    # restarted driver bumps their hang-attempt counts
                    # and quarantines repeat offenders), the RUN does
                    # not — no retry consumed, no tier pin (the tier is
                    # not suspect; one job is).  Bounded separately: a
                    # storm of job-stuck kills beyond what the per-job
                    # attempt caps can produce means something else is
                    # wrong.
                    self._job_stuck_kills += 1
                    for jid in self._last_stuck_jobs:
                        self._hang_attempts[jid] = \
                            self._hang_attempts.get(jid, 0) + 1
                    if self._job_stuck_kills > max(10,
                                                   5 * self.max_retries):
                        self.log("fleet job-stuck kill storm: giving up")
                        return self._exhausted_rc(rc)
                    restarts_total += 1
                    self._inc("resilience.restarts")
                    _ledger.event("supervisor.restart",
                                  cause="fleet-job-stuck",
                                  retry_consumed=False,
                                  hang_attempts=dict(self._hang_attempts))
                    self.log(
                        "fleet job(s) "
                        + ",".join(self._last_stuck_jobs)
                        + " blew their deadline; resuming with "
                        f"hang-attempt record {self._hang_attempts} "
                        "(no retry consumed, no tier pin)")
                    continue
                if cause == exitcause.CAUSE_USAGE:
                    self.log(f"usage error {desc}: not retryable")
                    return rc
                # Failure: classify, maybe degrade, retry with backoff.
                retries += 1
                self._inc("resilience.restarts")
                self._inc(f"resilience.exits.{cause.replace('-', '_')}")
                if retries > self.max_retries:
                    self.log(f"child failed ({cause} {desc}); retry "
                             f"budget exhausted after {self.max_retries}")
                    return self._exhausted_rc(rc)
                self._escalate(cause)
                delay = self._retry_delay(retries)
                have_ckpt = bool(checkpoint_glob(self.workdir,
                                                 self.run_id))
                _ledger.event("supervisor.restart", cause=cause,
                              retry=retries, resumed=have_ckpt,
                              delay_s=round(delay, 2),
                              pins=sorted(self._pins()))
                self.log(
                    f"child failed ({cause} {desc}); retry "
                    f"{retries}/{self.max_retries} in {delay:.1f}s "
                    + ("from newest checkpoint"
                       if have_ckpt else "from scratch (no checkpoint)")
                    + (f", degradation level {self.degrade_level} "
                       f"pins {self._pins()}"
                       if self._pins() else ""))
                time.sleep(delay)
                restarts_total += 1
        finally:
            child = self._child
            if child is not None and child.poll() is None:
                self._kill_group(child)
            self._restore_signals(prior)
            self._merge_metrics()
            self._finalize_ledger()

    # -- metrics ------------------------------------------------------------

    def _finalize_ledger(self) -> None:
        """Close the supervisor's ledger stream and merge the directory
        into one ordered timeline — the children have exited, so their
        rank files (including a SIGKILLed attempt's crash-truncated
        one) are complete as far as they will ever be."""
        if self.ledger_dir:
            # finalize() runs the directory merge itself (proc "sup"
            # is in its auto-merge set) — one pass, no double I/O.
            merged = _ledger.finalize()
            if merged:
                self.log(f"run ledger (merged) -> {merged}")

    def _partial_counters(self, since: float) -> Optional[dict]:
        """The killed attempt's last-known counters: a SIGKILLed /
        hang-killed child never writes its exit snapshot, but the
        heartbeat-ticked periodic flush (obs.metrics.maybe_autoflush)
        leaves a `"partial": true` snapshot behind.  Read it NOW —
        before the restarted attempt overwrites the file — so the
        attempt record preserves where progress stopped.  `since` is
        the attempt's start time: a flush stamped before it belongs to
        a PREVIOUS attempt (this one died before its first flush) and
        must not be attributed here."""
        if not self.metrics_file:
            return None
        try:
            with open(self.metrics_file) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            return None
        if not snap.get("partial"):
            return None               # a full exit snapshot: not a kill
        if snap.get("flushed_at", 0) < since:
            return None               # stale: an earlier attempt's flush
        return snap.get("counters") or {}

    def _resilience_blob(self) -> dict:
        blob = {"attempts": self.attempts,
                "final_pins": self._pins(),
                "heartbeat_file": self.hb_path}
        if self._hang_attempts:
            blob["fleet_hang_attempts"] = dict(self._hang_attempts)
        return blob

    def _merge_metrics(self) -> None:
        """Fold the supervisor's evidence into the child's --metrics
        snapshot (the child rewrites the file at every exit, so the
        LAST attempt's registry is on disk; the supervisor's counters
        span all attempts).  Without --metrics, write nothing — the log
        lines remain the record."""
        if not self.metrics_file:
            return
        snap: dict = {}
        try:
            with open(self.metrics_file) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            snap = {}
        snap.setdefault("counters", {}).update(self.counters)
        snap.setdefault("gauges", {})["resilience.degrade_level"] = \
            self.degrade_level
        snap["resilience"] = self._resilience_blob()
        try:
            with open(self.metrics_file, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True, default=str)
            self.log(f"metrics snapshot (merged) -> {self.metrics_file}")
        except OSError as exc:
            self.log(f"metrics merge failed ({exc})")


class GangSupervisor(Supervisor):
    """Rank-level failure domains for multi-process runs (`--launch N`).

    ExaML's parallelism is LOCKSTEP: every rank runs the search loop in
    unison and synchronizes through small allreduces, so one dead or
    wedged rank stalls the whole gang indefinitely — partial survival
    is useless, and the only sane recovery unit is the gang.  The gang
    supervisor therefore:

    * spawns all N ranks itself, each a killable process group with
      `EXAML_PROCID=<k>` / `EXAML_GANG_RANKS=<N>` exported (plus
      `--coordinator/--nprocs/--procid` in real distributed mode;
      EMULATED mode — `--launch-emulate`, for CPU containers whose
      jaxlib lacks multi-process collectives, and for the chaos tests —
      spawns N independent single-process ranks that follow the same
      rank contract);
    * aggregates the per-rank heartbeat files
      (`parallel/launch.install_heartbeat` suffixes `.p<k>`) and
      distinguishes the failure domains: RANK DEATH (a process died),
      COLLECTIVE WEDGE (every rank's beats went stale together — the
      blocked-allreduce class) and SINGLE-RANK STRAGGLER (one rank
      stale while peers actively beat) — see `classify_stall`;
    * on any failure kills the WHOLE gang, classifies the first-failing
      rank through the shared taxonomy, and restarts the gang from the
      newest COORDINATED checkpoint (two-phase publish,
      search/checkpoint.py) with the same backoff/retry/tier-pin
      ladder as the single-process supervisor, applied gang-wide;
    * ELASTIC RESUME: a rank that causes ELASTIC_CONSECUTIVE_DEATHS
      failed attempts in a row shrinks the gang to N-1 ranks (down to
      `--launch-min-ranks`) — checkpoint state is topology+model and
      site slices re-derive at parse time, so a smaller world resumes
      the same search instead of burning the window.
    """

    def __init__(self, argv: List[str], workdir: str, run_id: str,
                 ranks: int, emulate: bool = False, min_ranks: int = 1,
                 fleet: bool = False, **kwargs):
        super().__init__(argv, workdir, run_id, **kwargs)
        self.world = max(1, int(ranks))
        self._max_world = self.world
        self.emulate = bool(emulate)
        self.min_ranks = max(1, int(min_ranks))
        # Fleet gangs are NOT lockstep (ISSUE 14): every rank leases
        # independent jobs from the shared board, so the failure domain
        # is the RANK, not the gang — `run()` takes the leased loop
        # (`_run_fleet`) instead of the lockstep kill-the-world policy.
        self.fleet = bool(fleet)
        self._children: List[subprocess.Popen] = []
        self._death_streak = 0
        self._last_dead_rank: Optional[int] = None

    # -- plumbing -----------------------------------------------------------

    def _live_children(self) -> List[subprocess.Popen]:
        return list(self._children)

    def _kill_gang(self) -> None:
        for child in self._children:
            if child.poll() is None:
                self._kill_group(child)

    def _drain_gang(self, timeout: float = 30.0) -> None:
        """Graceful gang teardown (preemption): SIGTERM every live rank
        so each checkpoints, then SIGKILL whatever outlives the grace."""
        self._signal_children(signal.SIGTERM)
        deadline = time.time() + timeout
        while time.time() < deadline and any(
                c.poll() is None for c in self._children):
            time.sleep(POLL_S)
        self._kill_gang()

    def _spawn_gang(self, restarts_total: int) -> List[subprocess.Popen]:
        argv = self._last_argv = self._attempt_argv()
        pins = self._pins()
        port = None if self.emulate else _free_port()
        self.log(f"attempt {restarts_total}: starting gang of "
                 f"{self.world} rank(s) "
                 + ("(emulated, no process group) " if self.emulate else
                    f"(coordinator 127.0.0.1:{port}) ")
                 + ("(resume -R) " if "-R" in argv else "")
                 + (f"[pins {pins}] " if pins else "")
                 + " ".join(argv))
        # Stale beats (including ranks beyond a shrunken world) must not
        # mask a rank that never starts.
        for path in heartbeat.gang_paths(self.hb_path, self._max_world):
            try:
                os.unlink(path)
            except OSError:
                pass
        children = []
        for k in range(self.world):
            env = _repo_env()
            env["EXAML_HEARTBEAT_FILE"] = self.hb_path
            env["EXAML_RESTART_COUNT"] = str(restarts_total)
            env[heartbeat.PROCID_VAR] = str(k)
            env[heartbeat.GANG_VAR] = str(self.world)
            env.update(pins)
            rank_argv = list(argv)
            if not self.emulate:
                rank_argv += ["--coordinator", f"127.0.0.1:{port}",
                              "--nprocs", str(self.world),
                              "--procid", str(k)]
            children.append(subprocess.Popen(
                [sys.executable, "-m", "examl_tpu.cli.main"] + rank_argv,
                env=env, start_new_session=True))
        self._children = children
        return children

    # -- the gang watcher ---------------------------------------------------

    def _watch_gang(self) -> Tuple[str, Optional[int], Dict[str, str]]:
        """Wait for gang completion, first rank failure, or a stall
        verdict; returns (cause, guilty rank or None, per-rank exits)."""
        children = self._children
        spawned = time.time()
        first_beat_deadline = max(4.0 * self.stall_timeout, 900.0) \
            if self.stall_timeout else float("inf")
        grace = self.stall_timeout or 300.0
        done: Dict[int, str] = {}

        def exits(guilty: Optional[int], cause: str) -> Dict[str, str]:
            out = {}
            for k, ch in enumerate(children):
                if k == guilty:
                    out[f"r{k}"] = cause
                elif k in done:
                    out[f"r{k}"] = done[k]
                elif ch.poll() is None:
                    out[f"r{k}"] = "gang-killed"
                else:
                    out[f"r{k}"] = exitcause.classify(ch.returncode)
            return out

        while True:
            for k, ch in enumerate(children):
                if k in done:
                    continue
                rc = ch.poll()
                if rc is None:
                    continue
                cause = exitcause.classify(rc)
                if cause == exitcause.CAUSE_OK:
                    done[k] = exitcause.CAUSE_OK
                    continue
                if 0 in done and done[0] == exitcause.CAUSE_OK:
                    # Rank 0 already completed the run: a peer dying
                    # during teardown cannot un-finish it.  Record, do
                    # not fail the attempt.
                    self.log(f"rank {k} exited {cause} "
                             f"{exitcause.exit_desc(rc)} after rank 0 "
                             "completed; ignoring")
                    done[k] = cause
                    continue
                self.log(f"rank {k} died: {cause} "
                         f"{exitcause.exit_desc(rc)}; killing the gang "
                         "(lockstep — partial survival is useless)")
                _ledger.event("supervisor.kill", reason="rank-death",
                              rank=k, cause=cause, returncode=rc)
                return cause, k, exits(k, cause)
            if len(done) == len(children):
                return exitcause.CAUSE_OK, None, exits(None, "")
            if done.get(0) == exitcause.CAUSE_OK:
                # The primary finished; lockstep peers exit within an
                # allreduce of it.  Give them a grace window, then
                # sweep — their outputs are per-rank scratch.
                if not hasattr(self, "_rank0_done_t"):
                    self._rank0_done_t = time.time()
                if time.time() - self._rank0_done_t > grace:
                    self.log("rank 0 completed; sweeping "
                             f"{len(children) - len(done)} lingering "
                             "peer(s) after the grace window")
                    # Snapshot exits BEFORE our kill: swept peers must
                    # read "gang-killed", not the SIGKILL we send.
                    ex = exits(None, "")
                    self._kill_gang()
                    return exitcause.CAUSE_OK, None, ex
            elif self.stall_timeout:
                live = [k for k in range(len(children)) if k not in done]
                ages = []
                waiting_first_beat = False
                for k in live:
                    a = heartbeat.age(
                        heartbeat.rank_path(self.hb_path, k))
                    if a is None:
                        # Never beaten.  Within the (generous)
                        # first-beat deadline this rank's liveness is
                        # UNKNOWN — it may legitimately still be in
                        # setup/first compiles, and its lockstep peers
                        # may already be blocked waiting on it, so NO
                        # stall verdict can be attributed yet (calling
                        # the blocked-but-healthy peer a straggler
                        # would skip the tier ladder).  Past the
                        # deadline it is maximally stale.
                        elapsed = time.time() - spawned
                        if elapsed <= first_beat_deadline:
                            waiting_first_beat = True
                            break
                        a = elapsed
                    ages.append(a)
                if waiting_first_beat:
                    time.sleep(POLL_S)
                    continue
                verdict = classify_stall(ages, self.stall_timeout)
                if verdict is not None:
                    guilty = live[max(range(len(ages)),
                                      key=ages.__getitem__)]
                    self.log(
                        f"{verdict}: rank beat ages "
                        + ", ".join(f"r{k}={a:.0f}s"
                                    for k, a in zip(live, ages))
                        + f" against a {self.stall_timeout:.0f}s stall "
                        "window; killing the gang")
                    self._inc("resilience.heartbeat_stalls")
                    _ledger.event("supervisor.kill", reason=verdict,
                                  rank=guilty,
                                  beat_ages_s=[round(a, 1)
                                               for a in ages])
                    # Snapshot per-rank exits BEFORE our kill: the
                    # still-running peers must read "gang-killed", not
                    # the SIGKILL we are about to send them.
                    ex = exits(guilty, verdict)
                    self._kill_gang()
                    return verdict, guilty, ex
            time.sleep(POLL_S)

    # -- the leased fleet gang (non-lockstep rank domains) -------------------

    def _spawn_fleet_rank(self, k: int, attempt: int) -> subprocess.Popen:
        """One fleet rank, env-contract only: fleet ranks never join a
        collective process group (jobs are independent), so even
        non-emulated launches spawn plain single-process ranks with
        EXAML_PROCID/EXAML_GANG_RANKS exported.  NO tier pins: a fleet
        rank death indicts the rank's environment, never the program
        tier.  EXAML_EXPORT_BANK rides `_repo_env`'s passthrough, so a
        respawned rank deserializes its programs from the exported bank
        (ops/export_bank.py) and re-leases its first job without paying
        the compile phase that used to dominate rank-respawn MTTR."""
        argv = self._last_argv = self._attempt_argv()
        env = _repo_env()
        env["EXAML_HEARTBEAT_FILE"] = self.hb_path
        env["EXAML_RESTART_COUNT"] = str(attempt)
        env[heartbeat.PROCID_VAR] = str(k)
        env[heartbeat.GANG_VAR] = str(self.world)
        if self._hang_attempts:
            env["EXAML_FLEET_HANG_ATTEMPTS"] = ",".join(
                f"{jid}={n}" for jid, n in sorted(
                    self._hang_attempts.items()))
        try:
            os.unlink(heartbeat.rank_path(self.hb_path, k))
        except OSError:
            pass
        self.log(f"fleet rank {k}: starting (attempt {attempt}) "
                 + ("(resume -R) " if "-R" in argv else "")
                 + " ".join(argv))
        return subprocess.Popen(
            [sys.executable, "-m", "examl_tpu.cli.main"] + argv,
            env=env, start_new_session=True)

    def _rank_fleet_deadline(self, k: int):
        """(deadline, jobs) declared by rank k's last FLEET beat, or
        (None, []) — the per-rank version of `_watch`'s in-flight
        declaration read."""
        rec = heartbeat.read(heartbeat.rank_path(self.hb_path, k)) or {}
        fl = rec.get("fleet") or {}
        if fl.get("jobs") and fl.get("deadline"):
            try:
                return float(fl["deadline"]), [str(j) for j in
                                               fl["jobs"]]
            except (TypeError, ValueError):
                pass
        return None, []

    def _run_fleet(self) -> int:
        """The leased-gang loop: rank-level fault domains.  A dead rank
        costs ONLY its in-flight leases — the rank is restarted alone
        (cause `fleet-rank-death`, no gang-wide kill, no tier pin, no
        run-level retry), its expired leases are reaped by surviving
        ranks, and a rank that keeps dying is eventually ABANDONED
        while the rest of the gang serves on (the elastic-resume lesson
        applied at the rank level)."""
        prior = self._install_signals()
        respawn_cap = max(5, 3 * self.max_retries)
        children: Dict[int, subprocess.Popen] = {}
        respawns: Dict[int, int] = {k: 0 for k in range(self.world)}
        spawn_at: Dict[int, float] = {}
        spawned_t: Dict[int, float] = {}
        done: Dict[int, int] = {}
        abandoned: set = set()
        first_beat_deadline = (max(4.0 * self.stall_timeout, 900.0)
                               if self.stall_timeout else float("inf"))
        last_rc = 1

        def rank_died(k: int, cause: str, rc) -> None:
            nonlocal last_rc
            last_rc = rc if rc is not None else 1
            self._inc("resilience.gang.fleet_rank_deaths")
            self._inc("resilience.restarts")
            self._inc(f"resilience.gang.rank_exits.r{k}."
                      f"{cause.replace('-', '_')}")
            self.attempts.append({
                "rank": k, "cause": exitcause.CAUSE_FLEET_RANK_DEATH,
                "rank_cause": cause, "returncode": rc,
                "respawn": respawns[k],
                "seconds": round(time.time() - spawned_t.get(k, 0.0),
                                 2)})
            respawns[k] += 1
            if respawns[k] > respawn_cap:
                abandoned.add(k)
                self._inc("resilience.gang.rank_abandoned")
                _ledger.event("supervisor.rank_abandoned", rank=k,
                              respawns=respawns[k] - 1)
                self.log(f"fleet rank {k} died {respawns[k] - 1} "
                         "time(s); ABANDONING the rank slot (its "
                         "leases expire; peers absorb the queue)")
                return
            delay = backoff_delay(self.backoff, respawns[k],
                                  key=f"{self.run_id}:r{k}")
            spawn_at[k] = time.time() + delay
            _ledger.event("supervisor.restart",
                          cause=exitcause.CAUSE_FLEET_RANK_DEATH,
                          rank=k, rank_cause=cause,
                          retry_consumed=False,
                          delay_s=round(delay, 2))
            self.log(
                f"fleet rank {k} died ({cause} "
                f"{exitcause.exit_desc(rc, none_desc='(killed)')}); "
                f"restarting ONLY this rank in {delay:.1f}s — "
                "fleet-rank-death: its in-flight leases expire and "
                "peers reap them (no gang kill, no tier pin, no "
                "run-level retry)")

        try:
            for k in range(self.world):
                children[k] = self._spawn_fleet_rank(k, 0)
                spawned_t[k] = time.time()
            while True:
                self._children = [ch for k, ch in sorted(children.items())
                                  if k not in done]
                if self._preempt_signal is not None:
                    self.log(f"supervisor preempted "
                             f"({self._preempt_signal}); draining the "
                             "fleet gang")
                    self._inc("resilience.preempts")
                    self._drain_gang()
                    return exitcause.EXIT_PREEMPTED
                for k in sorted(children):
                    if k in done or k in abandoned:
                        continue
                    ch = children[k]
                    if k in spawn_at:
                        # waiting out the respawn backoff
                        if time.time() >= spawn_at[k]:
                            del spawn_at[k]
                            children[k] = self._spawn_fleet_rank(
                                k, respawns[k])
                            spawned_t[k] = time.time()
                        continue
                    rc = ch.poll()
                    if rc is not None:
                        cause = exitcause.classify(rc)
                        if cause == exitcause.CAUSE_OK:
                            done[k] = 0
                            self.log(f"fleet rank {k}: queue drained, "
                                     "exited cleanly")
                            continue
                        _ledger.event("supervisor.kill",
                                      reason="fleet-rank-death", rank=k,
                                      cause=cause, returncode=rc)
                        rank_died(k, cause, rc)
                        continue
                    # Per-rank liveness: a stalled or job-stuck rank is
                    # killed ALONE (the peers are not blocked on it —
                    # nothing is lockstep here) and restarted through
                    # the same rank-death path.
                    hb = heartbeat.rank_path(self.hb_path, k)
                    hb_age = heartbeat.age(hb)
                    deadline, jobs = self._rank_fleet_deadline(k)
                    if deadline is not None and time.time() > deadline:
                        for jid in jobs:
                            self._hang_attempts[jid] = \
                                self._hang_attempts.get(jid, 0) + 1
                        self._inc("resilience.fleet_job_stuck_kills")
                        _ledger.event("supervisor.kill",
                                      reason="fleet-job-stuck", rank=k,
                                      jobs=",".join(jobs))
                        self.log(f"fleet rank {k}: batch blew its "
                                 f"per-job deadline (jobs "
                                 f"{','.join(jobs)}); killing and "
                                 "restarting the rank (jobs pay "
                                 "attempts, the run pays nothing)")
                        self._kill_group(ch)
                        rank_died(k, exitcause.CAUSE_FLEET_JOB_STUCK,
                                  ch.returncode)
                        continue
                    if self.stall_timeout:
                        stalled = (
                            hb_age > self.stall_timeout
                            if hb_age is not None else
                            time.time() - spawned_t[k]
                            > first_beat_deadline)
                        if stalled and deadline is None:
                            self._inc("resilience.heartbeat_stalls")
                            _ledger.event("supervisor.kill",
                                          reason="heartbeat-stall",
                                          rank=k,
                                          beat_age_s=(round(hb_age, 1)
                                                      if hb_age
                                                      is not None
                                                      else None))
                            self.log(f"fleet rank {k}: heartbeat "
                                     "stalled; killing and restarting "
                                     "the rank")
                            self._kill_group(ch)
                            rank_died(k, exitcause.CAUSE_HANG_KILL,
                                      ch.returncode)
                            continue
                if len(done) + len(abandoned) >= self.world:
                    break
                time.sleep(POLL_S)
            if done:
                self.log(f"fleet gang completed: {len(done)} rank(s) "
                         f"drained the queue"
                         + (f", {len(abandoned)} abandoned"
                            if abandoned else ""))
                _ledger.event("supervisor.done", world=self.world,
                              ranks_ok=len(done),
                              ranks_abandoned=len(abandoned))
                return 0
            self.log("every fleet rank was abandoned; giving up")
            return self._exhausted_rc(last_rc)
        finally:
            self._children = list(children.values())
            self._kill_gang()
            self._restore_signals(prior)
            self._merge_metrics()
            self._finalize_ledger()

    # -- the gang supervision loop ------------------------------------------

    def run(self) -> int:
        if self.fleet:
            return self._run_fleet()
        prior = self._install_signals()
        retries = 0
        preempts = 0
        restarts_total = 0
        try:
            while True:
                if self._preempt_signal is not None:
                    self.log(f"supervisor preempted "
                             f"({self._preempt_signal}) between "
                             "attempts; not restarting")
                    self._inc("resilience.preempts")
                    return exitcause.EXIT_PREEMPTED
                if hasattr(self, "_rank0_done_t"):
                    del self._rank0_done_t
                t0 = time.time()
                self._spawn_gang(restarts_total)
                cause, rank, rank_exits = self._watch_gang()
                if cause == exitcause.CAUSE_PREEMPT:
                    self._drain_gang()       # peers checkpoint, then die
                elif cause != exitcause.CAUSE_OK:
                    self._kill_gang()
                rc = (self._children[rank].returncode
                      if rank is not None
                      else self._children[0].returncode)
                rec = {
                    "attempt": restarts_total, "cause": cause,
                    "rank": rank, "rank_exits": rank_exits,
                    "world": self.world, "returncode": rc,
                    "seconds": round(time.time() - t0, 2),
                    "pins": self._pins(),
                    "resumed": "-R" in self._last_argv}
                if cause != exitcause.CAUSE_OK:
                    rec["partial_counters"] = self._partial_counters(t0)
                self.attempts.append(rec)
                desc = exitcause.exit_desc(rc, none_desc="(gang-killed)")

                if cause == exitcause.CAUSE_OK:
                    self.log(f"gang run completed after {restarts_total} "
                             "restart(s)")
                    _ledger.event("supervisor.done",
                                  restarts=restarts_total,
                                  world=self.world)
                    return 0
                if self._preempt_signal is not None:
                    self.log(f"supervisor preempted "
                             f"({self._preempt_signal}); gang exited "
                             f"{desc}; not restarting")
                    self._inc("resilience.preempts")
                    return exitcause.EXIT_PREEMPTED
                if cause == exitcause.CAUSE_PREEMPT:
                    preempts += 1
                    self._inc("resilience.preempts")
                    if preempts > max(10, 5 * self.max_retries):
                        self.log("preemption storm: giving up")
                        return exitcause.EXIT_PREEMPTED
                    restarts_total += 1
                    self._inc("resilience.restarts")
                    _ledger.event("supervisor.restart", cause="preempt",
                                  rank=rank, retry_consumed=False)
                    self.log(f"rank {rank} preempted {desc}; resuming "
                             "the gang (no retry consumed)")
                    continue
                if cause == exitcause.CAUSE_USAGE:
                    self.log(f"usage error {desc}: not retryable")
                    return rc
                # Gang failure: count the domain, maybe shrink, retry.
                retries += 1
                self._inc("resilience.restarts")
                self._inc(f"resilience.exits.{cause.replace('-', '_')}")
                if rank is not None:
                    self._inc("resilience.gang.rank_exits."
                              f"r{rank}.{cause.replace('-', '_')}")
                if cause == exitcause.CAUSE_COLLECTIVE_WEDGE:
                    self._inc("resilience.gang.collective_wedges")
                elif cause == exitcause.CAUSE_STRAGGLER:
                    self._inc("resilience.gang.straggler_kills")
                elif cause in _RANK_DEATH_CAUSES:
                    self._inc("resilience.gang.rank_deaths")
                # Elastic resume bookkeeping: the streak tracks one
                # rank dying on consecutive attempts; any other outcome
                # resets it.
                if cause in _RANK_DEATH_CAUSES and rank is not None:
                    if rank == self._last_dead_rank:
                        self._death_streak += 1
                    else:
                        self._last_dead_rank = rank
                        self._death_streak = 1
                else:
                    self._last_dead_rank = None
                    self._death_streak = 0
                if (self._death_streak >= ELASTIC_CONSECUTIVE_DEATHS
                        and self.world > self.min_ranks):
                    self.world -= 1
                    self._inc("resilience.gang.elastic_resumes")
                    _ledger.event("supervisor.elastic_resume",
                                  dead_rank=rank, world=self.world)
                    self.log(
                        f"elastic resume: rank {rank} died "
                        f"{self._death_streak} consecutive time(s); "
                        f"degrading the gang to {self.world} rank(s) "
                        "(site slices re-derive at parse time; "
                        "checkpoint state is world-size independent)")
                    self._last_dead_rank = None
                    self._death_streak = 0
                if retries > self.max_retries:
                    self.log(f"gang failed ({cause} {desc}); retry "
                             f"budget exhausted after {self.max_retries}")
                    return self._exhausted_rc(rc)
                self._escalate(cause)
                delay = self._retry_delay(retries)
                have_ckpt = bool(checkpoint_glob(self.workdir,
                                                 self.run_id))
                _ledger.event("supervisor.restart", cause=cause,
                              rank=rank, retry=retries,
                              resumed=have_ckpt, world=self.world,
                              delay_s=round(delay, 2),
                              pins=sorted(self._pins()))
                self.log(
                    f"gang failed ({cause} {desc}); retry "
                    f"{retries}/{self.max_retries} in {delay:.1f}s "
                    + ("from newest coordinated checkpoint"
                       if have_ckpt else "from scratch (no checkpoint)")
                    + (f", degradation level {self.degrade_level} "
                       f"pins {self._pins()}"
                       if self._pins() else ""))
                time.sleep(delay)
                restarts_total += 1
        finally:
            self._kill_gang()
            self._restore_signals(prior)
            self._merge_metrics()
            self._finalize_ledger()

    def _resilience_blob(self) -> dict:
        blob = super()._resilience_blob()
        blob["gang"] = {"ranks_initial": self._max_world,
                        "ranks_final": self.world,
                        "emulate": self.emulate,
                        "min_ranks": self.min_ranks}
        return blob


def launch_gang(argv: List[str], args, log=print) -> int:
    """CLI entry for `--launch N`: spawn and supervise the whole gang.
    Like `supervise()`, this parent stays jax-free — every rank is a
    killable child process group.  Fleet modes (-b/-N/--serve) get the
    NON-LOCKSTEP leased-rank policy: a rank death restarts only that
    rank (`fleet-rank-death`) instead of killing the world."""
    workdir = getattr(args, "workdir", ".") or "."
    fleet = bool(getattr(args, "bootstrap", 0)
                 or getattr(args, "multi_start", 0)
                 or getattr(args, "serve", None))
    sup = GangSupervisor(
        argv, workdir=workdir, run_id=args.run_id,
        ranks=getattr(args, "launch", 1) or 1,
        emulate=getattr(args, "launch_emulate", False),
        min_ranks=getattr(args, "launch_min_ranks", 1),
        fleet=fleet,
        max_retries=getattr(args, "supervise_retries", DEFAULT_RETRIES),
        stall_timeout=getattr(args, "supervise_stall", DEFAULT_STALL),
        backoff=getattr(args, "supervise_backoff", 2.0),
        metrics_file=getattr(args, "metrics_file", None),
        ledger_dir=getattr(args, "ledger_dir", None),
        log=log)
    return sup.run()


def supervise(argv: List[str], args, log=print) -> int:
    """CLI entry: run `argv` (the full original command line) under
    supervision.  `args` is the parsed namespace — only supervisor and
    file-placement flags are read; everything jax-flavored happens in
    the child."""
    workdir = getattr(args, "workdir", ".") or "."
    sup = Supervisor(
        argv, workdir=workdir, run_id=args.run_id,
        max_retries=getattr(args, "supervise_retries", DEFAULT_RETRIES),
        stall_timeout=getattr(args, "supervise_stall", DEFAULT_STALL),
        backoff=getattr(args, "supervise_backoff", 2.0),
        metrics_file=getattr(args, "metrics_file", None),
        ledger_dir=getattr(args, "ledger_dir", None),
        log=log)
    return sup.run()
