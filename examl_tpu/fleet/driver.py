"""The fleet job-queue driver: profile-grouped batched dispatch.

Pending jobs group by their batch key — the fastpath segment profile
(PR5: the jit key, shared across topologies of similar shape), the
scan-tier [L, W] shape under PSR/force_scan, or the shared-topology
weights group for bootstrap replicates — so compile cost, the launch
floor, and the batched root reduction amortize fleet-wide: the first
job of a group compiles the group's ONE program, every later batch of
that group is a cache hit.

Resilience rides the existing stack: the driver beats the search-loop
heartbeat per batch (so `--supervise` stall detection and the
`search.kill` chaos seam work unchanged), checkpoints the whole job
table through CheckpointManager after every batch (state "FLEET" —
numbered, fsynced, corrupt-tolerant, gang-two-phase under --launch),
and a `-R` restart (or a supervisor resume) skips finished jobs — a
kill loses at most each in-flight job's current cycle.

Observability: `fleet.*` counters/gauges (queue depth, jobs done,
batch occupancy, trees_per_sec) and ledger events `job.start` /
`job.done` / `batch.dispatch` so a serving run is visible live
(tools/top.py) and in the post-run report (tools/run_report.py).

FAILURE DOMAINS are job-level (fleet/quarantine.py): a raise inside a
batched dispatch bisects to the guilty job(s), a non-finite row fails
only its own job, each failure burns one of the job's capped attempts
(jittered backoff between retries), and a job past its cap lands in
the dead-letter file with a `job.quarantined` event — healthy
cohabitants keep results bit-identical to a clean run and no run-level
supervisor retry is consumed for a job-level fault.  Finished results
additionally append to the fsync'd per-run journal so a SIGKILL loses
compute, never a finished result.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from examl_tpu import obs
from examl_tpu.fleet import bootstrap as _bootstrap
from examl_tpu.fleet import lease as _lease
from examl_tpu.fleet import quarantine
from examl_tpu.fleet.batch import WEIGHTS_GROUP, batch_eligible
from examl_tpu.fleet.jobs import JobSpec
from examl_tpu.resilience import faults, memgov


class FleetDriver:
    def __init__(self, inst, start_tree=None, batch_cap: int = 16,
                 cycles: int = 1, mgr=None, log=None,
                 checkpoint_every: int = 1,
                 policy: Optional[quarantine.JobFaultPolicy] = None,
                 journal: Optional[quarantine.ResultsJournal] = None,
                 deadletters: Optional[quarantine.DeadLetters] = None,
                 route_universal: bool = False,
                 devices: int = 1,
                 leases: Optional[_lease.LeaseBoard] = None,
                 peer_journals: Optional[Callable[[], list]] = None):
        self.inst = inst
        self.start_tree = start_tree          # bootstrap topology (+ ckpt
        self.batch_cap = max(1, int(batch_cap))   # scaffold)
        self.cycles = max(1, int(cycles))
        self.mgr = mgr
        self.log = log or (lambda *_: None)
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.policy = policy or quarantine.JobFaultPolicy()
        self.journal = journal
        self.deadletters = deadletters
        reason = batch_eligible(inst)
        self.evaluator = inst.batch_evaluator()
        if reason is not None:
            self.log(f"fleet: batched tier unavailable ({reason}); "
                     "jobs evaluate one at a time")
        # Tree-axis device sharding (fleet/shard.py): one evaluation
        # lane per surviving local device; `devices` <= 1 keeps the
        # classic single-lane behavior, 0 means every local device.
        from examl_tpu.fleet.shard import ShardSet
        if self.evaluator is not None and devices != 1:
            self.shards = ShardSet(inst, self.evaluator,
                                   max_devices=devices, log=self.log)
        else:
            self.shards = None       # single lane: the plain evaluator
        # Durable per-rank job leases (fleet/lease.py): under a leased
        # gang every rank leases jobs from the shared board; peers'
        # fsync'd results journals are absorbed so a job finished by
        # any rank finishes everywhere.
        self.leases = leases
        self.peer_journals = peer_journals
        # Fabric dispatch (ISSUE 17): a fabric-sharded instance hands
        # the driver a MeshShard evaluator — every batch spans the
        # whole (sites, tree) mesh in ONE dispatch, so the driver's
        # lane logic above stays single-lane and untouched.  Lease
        # records carry the shape so the evidence trail names the
        # fabric that held each job.
        from examl_tpu.fleet.shard import MeshShard
        if isinstance(self.evaluator, MeshShard):
            shape = (f"{self.evaluator.site_shards}x"
                     f"{self.evaluator.tree_shards}")
            self.log(f"fleet: batches dispatch on the {shape} "
                     "likelihood fabric (tree axis partitions each "
                     "batch's jobs; site axis shards each job's blocks)")
            if self.leases is not None:
                self.leases.mesh = shape
        self._reap_after: Dict[str, float] = {}
        self._reap_tries: Dict[str, int] = {}
        self._last_absorb = 0.0
        # Zero-recompile serving (ops/universal.py): with routing on, a
        # tree job whose fastpath profile was never specialized runs
        # through the universal interpreter — one banked program per
        # bucket size, no per-profile compile inside a batch's wall.
        # A profile that keeps recurring can optionally be PROMOTED to
        # the ~1.3x-faster specialized batched program after
        # EXAML_FLEET_SPECIALIZE_AFTER sightings (0 = never promote:
        # the pure interpreter-serving default).
        engines = list(inst.engines.values())
        self.route_universal = (
            route_universal and self.evaluator is not None
            and self.evaluator.fast and bool(engines)
            and not any(e.universal_off for e in engines))
        try:
            self._specialize_after = max(0, int(os.environ.get(
                "EXAML_FLEET_SPECIALIZE_AFTER", "0") or 0))
        except ValueError:
            self._specialize_after = 0
        # Mixed-profile batched-universal serving (ISSUE 14 / ROADMAP
        # §8b): novel-profile jobs group by bucketed table shape and
        # batch through ONE vmapped select_n interpreter program.
        # MEASURED VERDICT (CPU, 24x400, 12 novel profiles): the
        # select over all three tip-case branches costs ~3x per-step
        # compute — warm batched 0.34x of solo — and a vmapped
        # lax.switch would execute every branch too (its batching rule
        # degenerates to the same select), so batching only pays where
        # the launch floor dominates (J solo dispatches x latency >
        # 3x compute): OFF by default, EXAML_FLEET_UNIBATCH=1 opts in
        # for dispatch-bound backends; `fleet.universal_retrace`
        # counts the solo dispatches a batched program would merge —
        # the evidence for re-measuring on-chip.
        self._unibatch = os.environ.get("EXAML_FLEET_UNIBATCH",
                                        "") == "1"
        if self.route_universal:
            # The sequential/bisection-leaf paths must route novel
            # profiles identically, so a quarantine probe is
            # bit-identical to its batch row AND mints no specialized
            # compile either.
            for e in engines:
                e.route_novel_to_universal = True
            self.log("fleet: universal interpreter routing ON — novel "
                     "topology profiles dispatch through the "
                     "topology-as-data program (EXAML_UNIVERSAL=0 "
                     "opts out)")
        self._profiles_seen: Dict[object, int] = {}
        self.jobs: List[JobSpec] = []
        self._trees: Dict[str, object] = {}       # job_id -> Tree
        self._prepared: Dict[str, object] = {}    # job_id -> PreparedJob
        self._weights: Dict[str, list] = {}       # job_id -> per-part w
        self._keys: Dict[str, object] = {}        # job_id -> batch key
        self._started: set = set()                # job.start emitted (this
        self._batches_since_ckpt = 0              # process)
        self._not_before: Dict[str, float] = {}   # job_id -> retry time
        self._smoothed: Dict[str, int] = {}       # job_id -> cycle whose
                                                  # smoothing already ran
        self._solo: set = set()                   # deadline suspects:
                                                  # dispatch one at a time

    def _evict(self, job: JobSpec) -> None:
        """Drop a finished job's host-side state: a long-running
        `--serve` process must not keep every completed job's Tree,
        FastStructure and weight arrays alive forever."""
        for cache in (self._trees, self._prepared, self._weights,
                      self._keys, self._not_before, self._smoothed):
            cache.pop(job.job_id, None)
        self._solo.discard(job.job_id)

    # -- job-table persistence (rides CheckpointManager) --------------------

    def extras(self) -> dict:
        return {"fleet": {"jobs": [j.to_dict() for j in self.jobs],
                          "cycles": self.cycles}}

    def restore_jobs(self, extras: dict, jobs=None) -> int:
        """Merge a restored job table into `jobs` (default: the whole
        queue), matched by job_id: finished jobs stay finished,
        in-flight jobs keep their completed cycles and their current
        tree.  Returns the number of jobs restored as done.

        The serve loop passes each poll's FRESH specs only, so the
        snapshot applies to every job exactly once — at the moment it
        joins the queue.  Re-applying it to the whole table would
        regress jobs completed after the resume; never applying it to
        late-arriving lines (a torn final line consumed a poll later)
        would re-run a job the checkpoint knows is done."""
        blob = (extras or {}).get("fleet") or {}
        by_id = {d.get("job_id"): d for d in blob.get("jobs", [])}
        done = 0
        for job in (self.jobs if jobs is None else jobs):
            d = by_id.get(job.job_id)
            if d is None:
                continue
            rj = JobSpec.from_dict(d)
            job.cycles_done = rj.cycles_done
            job.lnl = rj.lnl
            job.done = rj.done
            job.failed = rj.failed
            # Fault-domain state persists across restarts: the retry
            # ladder must resume where it was, not hand a poison job a
            # fresh attempt budget per restart.
            job.attempts = max(job.attempts, rj.attempts)
            job.cause = rj.cause or job.cause
            job.last_error = rj.last_error or job.last_error
            if rj.newick:
                job.newick = rj.newick
            done += int(job.done)
        return done

    def apply_hang_attempts(self, jobs: Optional[List[JobSpec]] = None
                            ) -> None:
        """Fold the supervisor's EXAML_FLEET_HANG_ATTEMPTS export into
        the job table: a job the supervisor killed for blowing its
        per-batch deadline carries those attempts here, and one at or
        past the policy cap is quarantined BEFORE it can hang the
        resumed fleet again (the elastic-resume lesson one level down:
        exclude the thing that keeps dying, keep serving)."""
        counts = quarantine.parse_hang_attempts(
            os.environ.get(quarantine.ENV_HANG_ATTEMPTS))
        if not counts:
            return
        for job in (self.jobs if jobs is None else jobs):
            n = counts.get(job.job_id)
            if not n or job.done:
                continue
            job.attempts = max(job.attempts, n)
            if job.attempts >= self.policy.max_attempts:
                self._quarantine(
                    job, quarantine.CAUSE_HANG,
                    f"exceeded the per-job deadline in {job.attempts} "
                    "attempt(s) (supervisor hang-attempt record)")
            else:
                # A deadline kill attributes the whole STUCK BATCH (the
                # supervisor cannot see inside a hung dispatch), so the
                # suspects re-dispatch ONE AT A TIME — the hang analog
                # of poison bisection: an innocent cohabitant completes
                # solo and stops accumulating attempts; the real hang
                # job hangs alone and quarantines at the cap.
                self._solo.add(job.job_id)
                self.log(f"fleet: job {job.job_id} is a deadline "
                         f"suspect (attempt {job.attempts}); "
                         "re-dispatching it solo")

    # -- job materialization -------------------------------------------------

    def _tree_for(self, job: JobSpec):
        t = self._trees.get(job.job_id)
        if t is not None:
            return t
        if job.kind == "bootstrap":
            if self.start_tree is None:
                raise ValueError("bootstrap jobs need a starting tree (-t)")
            t = self.start_tree
        elif job.newick:                       # eval job / resumed start job
            t = self.inst.tree_from_newick(job.newick)
        else:                                  # multi-start: derived seed
            t = self.inst.random_tree(seed=job.seed)
        self._trees[job.job_id] = t
        return t

    def _key_for(self, job: JobSpec):
        if job.kind == "bootstrap":
            self._tree_for(job)                # raises without a -t tree
            return WEIGHTS_GROUP
        if self.evaluator is None:
            return ("seq", job.job_id)         # no grouping: one per batch
        prep = self.evaluator.prepare(self._tree_for(job),
                                      self._prepared.get(job.job_id))
        self._prepared[job.job_id] = prep
        key = prep.key
        if isinstance(key, tuple) and key and key[0] == "fast":
            # Profile-miss observability (batch-key grouping time): a
            # NOVEL profile used to compile its specialized program
            # silently inside the next batch's wall — now it is
            # counted and on the timeline, the before/after evidence
            # for the zero-recompile claim.  A profile whose
            # specialized program ALREADY exists (bank warm, an
            # earlier universal-off run, a promotion) is not a miss
            # and keeps its ~1.3x-faster specialized dispatch — the
            # same already-compiled check the engine's routing makes.
            profile = key[1]
            seen = self._profiles_seen.get(profile, 0)
            self._profiles_seen[profile] = seen + 1
            compiled = self._profile_compiled(profile)
            if seen == 0 and not compiled:
                obs.inc("fleet.profile_misses")
                obs.ledger_event("job.profile_new", job=job.job_id,
                                 profile_segments=len(profile))
            if self.route_universal and not compiled and not (
                    self._specialize_after
                    and seen + 1 >= self._specialize_after):
                # Route through the interpreter.  By default novel
                # profiles group by their BUCKETED universal-table
                # shape and batch through the vmapped select_n
                # interpreter program (batch.py launch_universal) —
                # mixed-profile serving traffic compiles ONCE.  With
                # EXAML_FLEET_UNIBATCH=0 (or an ineligible layout)
                # each job dispatches solo through the engine's
                # switch-based interpreter; `fleet.universal_retrace`
                # counts those solo dispatches — the batching the
                # vmapped program would have merged.
                ub = (self.evaluator.unibatch_key(
                          self._prepared[job.job_id])
                      if self._unibatch and self.evaluator is not None
                      and self.evaluator.fast else None)
                if ub is not None:
                    key = ub
                else:
                    obs.inc("fleet.universal_retrace")
                    key = ("uniseq", job.job_id)
        return key

    def _profile_compiled(self, profile) -> bool:
        """Does ANY engine already hold a compiled specialized program
        (one-at-a-time "fast" or batched "fleet") for this profile?"""
        for eng in self.inst.engines.values():
            for k in eng._fast_jit_cache:
                if isinstance(k, tuple) and len(k) > 1 \
                        and k[0] in ("fast", "fleet") and k[1] == profile:
                    return True
        return False

    def _weights_for(self, job: JobSpec) -> list:
        w = self._weights.get(job.job_id)
        if w is None:
            w = _bootstrap.bootstrap_weights(self.inst.alignment, job.seed)
            self._weights[job.job_id] = w
        return w

    # -- the job-level failure ladder ---------------------------------------

    def _journal_job(self, job: JobSpec) -> None:
        if self.journal is not None:
            self.journal.append(quarantine.job_record(job))

    def _quarantine(self, job: JobSpec, cause: str, error: str) -> None:
        """Terminal failure: the job leaves the queue for the dead
        letters — with cause, attempts and last error — and never costs
        another dispatch or a run-level retry."""
        error = (error or "")[:200]
        job.done = job.failed = True
        job.cause = cause
        job.last_error = error
        self._evict(job)
        obs.inc("fleet.quarantined")
        obs.inc("fleet.jobs_failed")
        obs.ledger_event("job.quarantined", job=job.job_id, cause=cause,
                         attempts=job.attempts, error=error)
        if self.deadletters is not None:
            self.deadletters.append(job, cause, error)
        self._journal_job(job)
        if self.leases is not None:
            self.leases.release(job.job_id)
        self.log(f"fleet: job {job.job_id} QUARANTINED ({cause} after "
                 f"{job.attempts} attempt(s): {error})")

    def _fail(self, job: JobSpec, cause: str, error) -> None:
        """One failed attempt: burn it, then retry with jittered
        backoff or quarantine at the cap."""
        err = str(error)[:200]
        job.attempts += 1
        job.cause = cause
        job.last_error = err
        obs.ledger_event("job.failed", job=job.job_id, cause=cause,
                         attempt=job.attempts, error=err)
        if job.attempts >= self.policy.max_attempts:
            self._quarantine(job, cause, err)
            return
        obs.inc("fleet.job_retries")
        delay = self.policy.backoff(job.job_id, job.attempts)
        self._not_before[job.job_id] = time.time() + delay
        self.log(f"fleet: job {job.job_id} attempt {job.attempts} "
                 f"failed ({cause}: {err}); retrying in {delay:.2f}s")

    # -- the queue loop ------------------------------------------------------

    def run(self, jobs: List[JobSpec],
            resume_extras: Optional[dict] = None) -> List[JobSpec]:
        self.jobs = list(jobs)
        restored = 0
        if resume_extras:
            restored = self.restore_jobs(resume_extras)
            self.log(f"fleet: resumed job table — {restored} of "
                     f"{len(self.jobs)} jobs already done")
        self.apply_hang_attempts()
        obs.gauge("fleet.jobs_total", len(self.jobs))
        self.drain()
        return self.jobs

    def pending(self) -> List[JobSpec]:
        return [j for j in self.jobs if not j.done]

    def drain(self) -> None:
        """Run batches until no job is pending."""
        from examl_tpu.resilience import heartbeat
        while True:
            if self.leases is not None:
                # A leased rank first absorbs peers' journaled results
                # (a job finished by ANY rank finishes everywhere) and
                # renews the leases it still holds so a long queue
                # never lets its own leases expire under it.
                self._absorb_remote()
                self._renew_leases()
            pending = self.pending()
            obs.gauge("fleet.queue_depth", len(pending))
            # "done" means SUCCEEDED: failed jobs leave the queue but
            # must not read as successes on the operator's live view.
            obs.gauge("fleet.jobs_done",
                      sum(1 for j in self.jobs
                          if j.done and not j.failed))
            if not pending:
                break
            # Retry backoff: a job whose jittered delay has not expired
            # is pending but not READY.  When nothing is ready, sleep
            # toward the earliest retry while still beating (the queue
            # is alive, just backing off — the supervisor must not read
            # the wait as a stall).
            now = time.time()
            ready = [j for j in pending
                     if self._not_before.get(j.job_id, 0.0) <= now]
            if self.leases is not None:
                ready = self._lease_ready(ready, now)
            if not ready:
                wake = min((self._not_before.get(j.job_id, now)
                            for j in pending), default=now)
                heartbeat.phase_beat("FLEET")
                floor = 0.25 if self.leases is not None else 0.01
                time.sleep(min(max(wake - now, floor), 1.0))
                continue
            # Group by batch key; dispatch the largest group first so
            # occupancy stays high while the queue is deep.  A job that
            # cannot even materialize (malformed eval newick, a
            # bootstrap job with no -t tree in serve mode) is
            # quarantined ALONE — retrying an identical host-side parse
            # cannot succeed, and one poisoned job must not kill the
            # serving process.
            groups: Dict[object, List[JobSpec]] = {}
            for job in ready:
                # The batch key is a function of the job's topology,
                # which no current work kind changes — computed once
                # per job, so regrouping a deep queue costs O(pending)
                # dict lookups, not O(pending) schedule builds.
                key = self._keys.get(job.job_id)
                if key is None:
                    try:
                        key = self._key_for(job)
                    except Exception as exc:   # noqa: BLE001
                        job.attempts += 1
                        self._quarantine(job, quarantine.CAUSE_ERROR,
                                         f"failed to materialize: {exc}")
                        continue
                    self._keys[job.job_id] = key
                if job.job_id in self._solo:
                    key = ("solo", job.job_id)
                groups.setdefault(key, []).append(job)
            if not groups:
                continue                       # everything failed: re-check
            # Cut up to one batch per device lane, largest group first
            # (a single deep group splits across lanes — the jobs are
            # independent, so any cut is valid), and round-robin the
            # cuts across the shard set.
            nlanes = len(self.shards) if self.shards is not None else 1
            order = sorted(groups.items(),
                           key=lambda kv: (-len(kv[1]), str(kv[0])))
            # Memory governor (resilience/memgov.py): under pressure
            # the drain cuts SMALLER batches — occupancy shrinks
            # instead of the batch arena OOMing.  Each cut below the
            # configured cap is a counted admission denial.
            cap = memgov.effective_cap(self.batch_cap)
            batches: List = []
            for key, members in order:
                for i in range(0, len(members), cap):
                    batches.append((key, members[i:i + cap]))
                    if len(batches) >= nlanes:
                        break
                if len(batches) >= nlanes:
                    break
            assignments = []
            for lane, (key, batch) in enumerate(batches):
                shard = (self.shards.shard_for(key, lane)
                         if self.shards is not None else self.evaluator)
                assignments.append((shard, batch))
            self._dispatch_round(assignments)
            # Clear the in-flight declaration: a later non-fleet wedge
            # (checkpoint I/O, model push) must not be misattributed to
            # jobs that already finished.  phase_beat: bookkeeping, not
            # an iteration — the search.kill clock stays one per round.
            heartbeat.phase_beat("FLEET", payload={"fleet": None})
            self._batches_since_ckpt += 1
            if self.mgr is not None and \
                    self._batches_since_ckpt >= self.checkpoint_every:
                self._batches_since_ckpt = 0
                self._checkpoint()
                # Preemption cadence: the job table just persisted, so
                # a pending SIGTERM/SIGINT exits resumable HERE (exit
                # 75; a --supervise parent resumes without consuming a
                # retry) — at most the next batch's cycle is redone.
                from examl_tpu.resilience import preempt
                preempt.check_after_checkpoint(log=self.log)
            elif self.mgr is None and self.leases is not None:
                # Leased serving checkpoints nothing (the per-job
                # fsync'd journal is the durable record, and lockstep
                # two-phase gang checkpoints cannot apply to ranks
                # that are deliberately NOT in lockstep) — but the
                # preemption contract still holds at the same cadence:
                # everything finished is journaled, so exiting 75 here
                # loses only in-flight compute.
                from examl_tpu.resilience import preempt
                preempt.check_after_checkpoint(log=self.log)
        obs.gauge("fleet.queue_depth", 0)
        obs.gauge("fleet.jobs_done",
                  sum(1 for j in self.jobs if j.done and not j.failed))
        if self.mgr is not None and self._batches_since_ckpt:
            self._batches_since_ckpt = 0
            self._checkpoint()

    def _checkpoint(self) -> None:
        tree = self.start_tree
        if tree is None:
            live = next((self._trees[j.job_id] for j in self.jobs
                         if j.job_id in self._trees), None)
            tree = live if live is not None \
                else self.inst.random_tree(seed=0)
        self.mgr.write("FLEET", self.extras(), self.inst, tree)

    # -- lease bookkeeping (the rank-level fault domain) --------------------

    def _absorb_remote(self) -> None:
        """Fold peers' journaled results into the local job table: a
        job any rank finished (done OR quarantined) finishes here too —
        WITHOUT re-emitting its `job.done` (the finishing rank already
        did, and the merged-ledger acceptance counts them exactly
        once).  Also the expired-but-journaled guard: an absorbed job
        is no longer pending, so its stale lease is scrubbed instead of
        reaped-and-re-dispatched."""
        if self.peer_journals is None:
            return
        now = time.time()
        if now - self._last_absorb < 0.5:
            return
        self._last_absorb = now
        try:
            recs = self.peer_journals()
        except OSError:
            return
        by_id = {r.get("job_id"): r for r in recs if r.get("done")}
        for job in self.jobs:
            if job.done:
                continue
            rec = by_id.get(job.job_id)
            if rec is None:
                continue
            rj = JobSpec.from_dict({k: v for k, v in rec.items()
                                    if k != "t"})
            job.cycles_done = rj.cycles_done
            job.lnl = rj.lnl
            job.done = True
            job.failed = rj.failed
            job.attempts = max(job.attempts, rj.attempts)
            job.cause = rj.cause
            job.last_error = rj.last_error
            if rj.newick:
                job.newick = rj.newick
            obs.inc("fleet.jobs_absorbed")
            self._evict(job)
            self._reap_after.pop(job.job_id, None)
            if self.leases is not None:
                self.leases.scrub(job.job_id)

    def _renew_leases(self) -> None:
        for jid in self.leases.held():
            self.leases.renew(jid)

    def _lease_ready(self, ready: List[JobSpec],
                     now: float) -> List[JobSpec]:
        """The leased view of the ready set: only jobs THIS rank holds
        a lease on may dispatch.  Free jobs are acquired (bounded to
        ~2 rounds of work so one rank never hogs the whole shared
        queue), live foreign leases wait, and expired foreign leases —
        a dead rank's in-flight jobs — are reaped after a
        blake2b-jittered backoff so surviving ranks never stampede the
        steal."""
        out: List[JobSpec] = []
        nlanes = len(self.shards) if self.shards is not None else 1
        cap = 2 * nlanes * self.batch_cap
        held = set(self.leases.held())
        nheld = len(held)
        claimed = False
        for job in ready:
            jid = job.job_id
            if jid in held:
                out.append(job)
                continue
            if nheld >= cap:
                continue
            state = self.leases.expired(jid)
            if state is None:                      # free: claim it
                if self.leases.acquire(jid):
                    out.append(job)
                    claimed = True
                    nheld += 1
                continue
            if state is False:
                if self.leases.stale_own(jid):
                    # A dead predecessor of THIS rank slot held it: a
                    # restarted rank reclaims its own lost jobs NOW
                    # instead of idling out the ttl.
                    if self.leases.reap(jid, own=True):
                        self.log(f"fleet: reclaimed own stale lease "
                                 f"for {jid} (restarted rank)")
                        out.append(job)
                        claimed = True
                        nheld += 1
                    continue
                continue                           # live foreign lease
            due = self._reap_after.get(jid)
            if due is None:
                att = self._reap_tries.get(jid, 0) + 1
                self._reap_tries[jid] = att
                self._reap_after[jid] = now + _lease.reap_backoff(
                    jid, self.leases.rank, att)
                continue
            if now < due:
                continue
            self._reap_after.pop(jid, None)
            if self.leases.reap(jid):
                self.log(f"fleet: reaped expired lease for {jid} "
                         "(its rank died or stalled); re-dispatching")
                out.append(job)
                claimed = True
                nheld += 1
        if claimed:
            # Close the release-vs-stale-journal race: a finishing rank
            # journals BEFORE it releases (fsync'd), so any job we just
            # saw free-or-expired and claimed has its result VISIBLE
            # now if it ever finished.  Force a journal re-read (past
            # the absorb rate limit) and drop claimed-but-done jobs —
            # `_absorb_remote` scrubs their just-taken leases — before
            # a single duplicate dispatch can happen.
            self._last_absorb = 0.0
            self._absorb_remote()
            out = [j for j in out if not j.done]
        return out

    def _fenced(self, job: JobSpec) -> bool:
        """True when a leased job's completion must be DISCARDED: the
        lease expired under us and another rank reaped it mid-dispatch.
        The reaper owns the job now — recording our result too would
        double-count it (the exactly-once `job.done` contract)."""
        if self.leases is None:
            return False
        if self.leases.still_mine(job.job_id):
            return False
        obs.inc("fleet.leases_lost")
        self.log(f"fleet: lease for {job.job_id} was reaped mid-"
                 "dispatch; discarding this result (the reaper owns "
                 "the job)")
        return True

    # -- batch dispatch ------------------------------------------------------

    def _dispatch(self, batch: List[JobSpec]) -> None:
        """Single-batch dispatch (bisection-era entry point, kept for
        harnesses): one round with one lane."""
        shard = (self.shards.shard_for(self._keys.get(batch[0].job_id),
                                       0)
                 if self.shards is not None else self.evaluator)
        self._dispatch_round([(shard, batch)])

    def _dispatch_round(self, assignments: List) -> None:
        """One drain round: LAUNCH every lane's batch (jax async
        dispatch — lanes on distinct devices execute concurrently),
        then collect and run each batch through the job-level fault
        ladder.  A failed collect takes the same quarantine bisection a
        synchronous raise always took."""
        for _, batch in assignments:
            for job in batch:
                if job.job_id not in self._started:
                    self._started.add(job.job_id)
                    obs.ledger_event("job.start", job=job.job_id,
                                     job_kind=job.kind, index=job.index,
                                     seed=job.seed, cycle=job.cycles_done)
        from examl_tpu.resilience import heartbeat
        compiles0 = obs.counter("engine.compile_count")
        bisects0 = obs.counter("fleet.bisect_dispatches")
        t0 = time.perf_counter()

        def declare(batch):
            """The in-flight declaration stays per-BATCH even in a
            multi-lane round: the launch loop and the collect loop are
            sequential host code, so (re)declaring exactly the batch
            the host is about to block on keeps hang attribution as
            tight as the single-lane flow — a deadline kill indicts
            one batch's jobs, never innocent cohabitant lanes."""
            fl = {"jobs": [j.job_id for j in batch]}
            if self.policy.deadline_s > 0:
                fl["deadline"] = time.time() + self.policy.deadline_s
            return {"fleet": fl}

        launches = []
        for shard, batch in assignments:
            lane = getattr(shard, "index", 0)
            obs.ledger_event("batch.dispatch", jobs=len(batch),
                             job_kind=batch[0].kind, lane=lane,
                             ids=",".join(j.job_id for j in batch[:8]))
            obs.inc(f"fleet.device_dispatches.d{lane}")
            obs.inc(f"fleet.device_jobs.d{lane}", len(batch))
            # The heartbeat IS the fleet's iteration clock: supervise
            # stall detection, search.kill chaos addressing ("the Nth
            # batch"), and the periodic metrics flush all tick here,
            # once per BATCH — identical to the single-lane flow.
            heartbeat.beat("FLEET", payload=declare(batch))
            try:
                launches.append(self._launch_batch(batch, shard))
            except Exception as exc:      # noqa: BLE001 — attributed
                launches.append(exc)      # through bisection below
        njobs = 0
        for (shard, batch), launched in zip(assignments, launches):
            # Narrow the declaration to the batch this collect blocks
            # on (bookkeeping re-publish, not an iteration: the
            # search.kill clock stays one tick per batch).
            if len(assignments) > 1:
                heartbeat.phase_beat("FLEET", payload=declare(batch))
            # Job-level isolation: a raise anywhere inside the batched
            # dispatch bisects to the guilty job(s); every healthy
            # cohabitant keeps its result (bit-identical to a clean run
            # — per-row vmap independence, pinned by test_quarantine).
            results = self._isolate_launched(batch, launched, shard)
            njobs += len(batch)
            obs.inc("fleet.batches")
            obs.inc("fleet.trees_evaluated", len(batch))
            self._apply_results(batch, results)
        dt = time.perf_counter() - t0
        obs.inc("fleet.eval_seconds", dt)
        clean = obs.counter("fleet.bisect_dispatches") == bisects0
        # The throughput gauge only takes WARM, CLEAN rounds: a round
        # whose wall contained a first-call compile (or a bisection
        # cascade) would publish a near-zero trees/sec wrongly read as
        # serving throughput (the same discipline as the engine's
        # bandwidth windows).
        if dt > 0 and clean \
                and obs.counter("engine.compile_count") == compiles0:
            obs.gauge("fleet.trees_per_sec", round(njobs / dt, 3))
        # Per-lane HBM telemetry (obs/programs.py): one rate-limited
        # device.memory_stats() sample per drain round, covering every
        # lane's device — the mem.device.<k>.* gauges a multi-tenant
        # admission decision (ROADMAP §10) needs next to
        # engine.clv_arena_bytes.
        from examl_tpu.obs import programs as _programs
        _programs.sample_memory()

    def _isolate_launched(self, batch: List[JobSpec], launched,
                          shard) -> List:
        """Resolve one launched batch through `quarantine.isolate`
        without re-running the clean path: the already-launched outcome
        (collected rows, or the exception that killed launch/collect)
        stands in for isolate's first top-level dispatch, so fault-hit
        counters tick exactly as in the synchronous flow."""
        if isinstance(launched, Exception):
            outcome = launched
        else:
            try:
                outcome = self._finish_batch(batch, launched)
            except Exception as exc:      # noqa: BLE001
                outcome = exc
        oomed = isinstance(outcome, Exception) and memgov.is_oom(outcome)
        if oomed:
            # Allocator OOM at the dispatch seam: count it, evict cold
            # compiled programs + per-topology device caches, then let
            # the existing halving re-dispatch below retry at a reduced
            # shape.  Repeated strikes raise MemoryBudgetExhausted from
            # memgov (→ EXIT_ALLOC_OOM: the supervisor pins the budget
            # fraction down on restart).
            memgov.oom_event(outcome, seam="fleet.dispatch")
            for eng in self.inst.engines.values():
                memgov.evict_engine(eng)
        consumed: List[int] = []

        def evaluate(b, nested=False):
            if not nested and not consumed:
                consumed.append(1)
                if isinstance(outcome, Exception):
                    raise outcome
                return outcome
            return self._evaluate_batch(b, nested, shard=shard)

        results = quarantine.isolate(
            batch, evaluate,
            lambda j: self._evaluate_leaf(j, shard=shard))
        if oomed:
            # The reduced-shape re-dispatch completed: the evict+shrink
            # ladder recovered, counted as mem.oom_retries.
            memgov.oom_recovered()
        return results

    def _apply_results(self, batch: List[JobSpec], results: List) -> None:
        for job, row, err in results:
            if self._fenced(job):
                # The reaper owns this job now: no attempt burned, no
                # result recorded, nothing re-dispatched by us — the
                # job completes (exactly once) on the reaper's rank
                # and arrives back here through journal absorption.
                continue
            if err is not None:
                cause = (quarantine.CAUSE_POISON
                         if isinstance(err, FloatingPointError)
                         else quarantine.CAUSE_ERROR)
                self._fail(job, cause, err)
                continue
            lnl = float(row.sum())
            if not np.isfinite(lnl):
                self._fail(job, quarantine.CAUSE_POISON,
                           "non-finite lnL")
                continue
            job.lnl = lnl
            # A retried job that now succeeded is healthy: stale
            # cause/last_error from the failed attempt must not leak
            # into a "done" results-table row (attempts stays — it IS
            # the retry evidence).
            job.cause = None
            job.last_error = None
            job.cycles_done += 1
            obs.inc("fleet.cycles")
            if job.kind != "bootstrap":
                tree = self._trees.get(job.job_id)
                if tree is not None:
                    job.newick = tree.to_newick(
                        self.inst.alignment.taxon_names)
            if job.cycles_done >= job.cycles:
                job.done = True
                obs.inc("fleet.jobs_done_total")
                obs.ledger_event("job.done", job=job.job_id,
                                 job_kind=job.kind, lnl=round(lnl, 6),
                                 cycles=job.cycles_done)
                # Durable result BEFORE eviction: the journal record is
                # what a post-SIGKILL resume reconciles against the
                # (older, per-batch) checkpoint.
                self._journal_job(job)
                if self.leases is not None:
                    # Journal first, THEN release: a kill between the
                    # two leaves an expired lease whose reap consults
                    # the journal — absorbed, never re-run.
                    self.leases.release(job.job_id)
                self._evict(job)

    # -- the evaluation seams (fault-injectable, bisectable) ----------------

    def _evaluate_batch(self, batch: List[JobSpec],
                        nested: bool = False, shard=None) -> np.ndarray:
        """One batched dispatch, synchronously: launch + finish.  Used
        by the bisection ladder (`nested` marks a sub-dispatch;
        occupancy gauge suppressed) and by single-lane harnesses."""
        return self._finish_batch(batch,
                                  self._launch_batch(batch, shard, nested))

    def _launch_batch(self, batch: List[JobSpec], shard=None,
                      nested: bool = False):
        """ENQUEUE one batch on a lane.  The fleet fault points live
        here — the real seam where a poison job, a hang inside a
        batched dispatch, or a whole-dispatch failure strikes.  Returns
        a PendingBatch (async, collected in `_finish_batch`) or a host
        ndarray for the synchronous paths (bootstrap weights,
        sequential/universal-routed jobs)."""
        faults.fire("fleet.dispatch")
        for job in batch:
            # A REAL sleep (not beat suppression): the in-flight
            # declaration published just before the dispatch goes
            # stale exactly like a genuine hang inside the batch.
            faults.fire("fleet.job.hang", job=job.job_id)
            # Synthetic RESOURCE_EXHAUSTED at the dispatch seam: the
            # raised FaultInjected classifies as OOM in memgov.is_oom,
            # driving the evict + halving-retry recovery on CPU.
            faults.fire("mem.oom", job=job.job_id)
        if batch[0].kind == "bootstrap":
            return self._dispatch_bootstrap(batch, nested)
        return self._dispatch_trees(batch, nested, shard)

    def _finish_batch(self, batch: List[JobSpec], launched) -> np.ndarray:
        """Materialize one launched batch (the lane's registered
        blocking collect) and apply the per-job poison fault — the
        seam order is identical to the old synchronous dispatch, so
        fault addressing and chaos tests are unchanged."""
        from examl_tpu.fleet.batch import PendingBatch
        if isinstance(launched, PendingBatch):
            launched = launched.ev.collect(launched)
        per_part = np.asarray(launched, dtype=np.float64)
        for i, job in enumerate(batch):
            if faults.fire("fleet.job.poison", job=job.job_id):
                per_part[i] = np.nan
        return per_part

    def _evaluate_leaf(self, job: JobSpec, shard=None) -> np.ndarray:
        """Bisection leaf: ONE job through the one-at-a-time path the
        batched tier is parity-pinned against — so a healthy job
        isolated out of a poisoned batch scores bit-identically to a
        clean run, and the engine's own scan-tier non-finite retry
        gets its shot before the job is declared poison."""
        if job.kind == "bootstrap":
            row = self._sequential_weights(
                self._tree_for(job), [self._weights_for(job)])[0]
        else:
            self._smooth_if_due([job])
            row = self._sequential_eval(self._tree_for(job))
        row = np.asarray(row, dtype=np.float64)
        if faults.fire("fleet.job.poison", job=job.job_id):
            row[:] = np.nan
        return row

    def _dispatch_bootstrap(self, batch: List[JobSpec],
                            nested: bool = False) -> np.ndarray:
        tree = self._tree_for(batch[0])
        weights = [self._weights_for(j) for j in batch]
        if self.evaluator is not None:
            return self.evaluator.eval_weights_batch(
                tree, weights, record_occupancy=not nested)
        return self._sequential_weights(tree, weights)

    def _smooth_if_due(self, batch: List[JobSpec]) -> None:
        """Branch-length smoothing for jobs entering a later cycle —
        AT MOST ONCE per (job, cycle): smoothing mutates the tree's z,
        so a bisection re-dispatch (or a post-failure retry) running it
        again would double-smooth and break the bit-identical contract
        for healthy cohabitants."""
        later = [j for j in batch if j.cycles_done > 0
                 and self._smoothed.get(j.job_id) != j.cycles_done]
        if not later:
            return
        from examl_tpu.constants import SMOOTHINGS
        from examl_tpu.optimize.branch import (grad_smooth_enabled,
                                               grad_smooth_ineligible,
                                               smooth_tree)
        remaining = list(later)
        if (grad_smooth_enabled() and self.evaluator is not None
                and self.evaluator.fast
                and grad_smooth_ineligible(self.inst) is None):
            # Batched whole-tree gradient smoothing: ONE vmapped
            # dispatch per engine per sweep covers every job in the
            # batch (fleet/batch.py smooth_batch) instead of the
            # per-job per-branch Newton loop.  Jobs whose prepared
            # state is missing (bisection leaves arriving solo) or
            # that fail to settle fall through to the per-job path.
            grouped = [j for j in later if j.job_id in self._prepared
                       and self._prepared[j.job_id].st is not None]
            if grouped:
                preps = [self._prepared[j.job_id] for j in grouped]
                try:
                    # Budget exhaustion is accepted like the per-branch
                    # path accepts its own maxtimes exhaustion; only a
                    # hard failure re-runs the per-job rung.
                    self.evaluator.smooth_batch(preps, SMOOTHINGS)
                    ok = True
                except Exception as exc:   # noqa: BLE001 — job-level
                    # fault domain: smoothing failures re-run per job
                    self.log("fleet: batched gradient smoothing failed "
                             f"({exc}); smoothing per job")
                    ok = False
                if ok:
                    for job in grouped:
                        self._smoothed[job.job_id] = job.cycles_done
                    remaining = [j for j in later if j not in grouped]
        for job in remaining:
            tree = self._tree_for(job)
            # Smoothing's per-branch Newton steps gather CLVs
            # through the ENGINE's live arena/row map, which the
            # batched cycles never touched — a real full traversal
            # on the engine orients it to THIS tree first, exactly
            # the precondition tree_evaluate's callers establish.
            self.inst.evaluate(tree, full=True)
            smooth_tree(self.inst, tree, SMOOTHINGS)
            self._smoothed[job.job_id] = job.cycles_done
        if self.evaluator is not None:
            # Re-prepare AFTER smoothing: the PreparedJobs captured
            # at grouping time hold pre-smoothing z arrays; the
            # topology is unchanged, so the cached structure (and
            # the batch group key) survive and only z refreshes.
            for job in later:
                self._prepared[job.job_id] = self.evaluator.prepare(
                    self._tree_for(job),
                    self._prepared.get(job.job_id))

    def _dispatch_trees(self, batch: List[JobSpec],
                        nested: bool = False, shard=None):
        # Later cycles smooth branch lengths before re-evaluating (the
        # multi-start refinement loop); cycle 0 scores the tree as is.
        # Smoothing runs synchronously on the PRIMARY lane (the live
        # engine arenas anchor it there) before the lane launch.
        self._smooth_if_due(batch)
        key = self._keys.get(batch[0].job_id)
        routed = (isinstance(key, tuple) and key
                  and key[0] == "uniseq")
        ev = shard if shard is not None else self.evaluator
        if ev is not None and isinstance(key, tuple) and key \
                and key[0] == "uni":
            # Mixed-profile batch through the vmapped universal
            # interpreter (primary lane: the per-topology descriptor
            # caches are device-resident there).
            preps = [self._prepared[j.job_id] for j in batch]
            return ev.launch_universal(preps, key,
                                       record_occupancy=not nested)
        if ev is not None and not routed:
            preps = [self._prepared[j.job_id] for j in batch]
            return ev.launch_eval(preps, record_occupancy=not nested)
        # Sequential: no batched tier, or a universal-routed job — the
        # instance's evaluate path, where the engine's novel-profile
        # routing dispatches the topology-as-data interpreter.
        out = np.stack([self._sequential_eval(self._tree_for(j))
                        for j in batch])
        return out

    # -- sequential fallback (SEV / sharded instances) -----------------------

    def _sequential_eval(self, tree) -> np.ndarray:
        self.inst.evaluate(tree, full=True)
        return np.array(self.inst.per_partition_lnl, copy=True)

    def _sequential_weights(self, tree, weights: List[list]) -> np.ndarray:
        import jax.numpy as jnp
        self.inst.evaluate(tree, full=True)
        out = []
        p = tree.centroid_branch()
        for per_part in weights:
            row = np.full(len(self.inst.models), np.nan)
            for eng in self.inst.engines.values():
                saved = eng.weights
                eng.weights = jnp.asarray(
                    _bootstrap.packed_weights(eng.bucket, per_part),
                    eng.dtype)
                try:
                    vals = eng.evaluate(p.number, p.back.number, p.z)
                finally:
                    eng.weights = saved
                for li, gid in enumerate(eng.bucket.part_ids):
                    row[gid] = vals[li]
            out.append(row)
        return np.stack(out)
