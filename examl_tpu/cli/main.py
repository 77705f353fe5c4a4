"""Inference driver CLI — the counterpart of the reference `examl` binary.

Flag surface and output files mirror the reference driver (`examl/axml.c`:
`get_args` :935-1302, `printREADME` :777-900, `makeFileNames` :1316-1357;
modes dispatched at `main` :2719-2781):

  -s byteFile  -n runId  -t startTree | -R (restart from checkpoint)
  -m GAMMA|PSR  -a (median gamma)  -c #categories (PSR)
  -f d|o|e|E|q  -e lnL-epsilon  -i radius  -D (RF convergence)
  -B #best trees  -M (per-partition branches)  -S (memory saving)
  -w workdir  --auto-prot=ml|bic|aic|aicc

Outputs in workdir: ExaML_info.RUNID (config + progress),
ExaML_log.RUNID ("seconds lnL" rows), ExaML_result.RUNID (newick),
ExaML_modelFile.RUNID (final model parameters),
ExaML_TreeFile.RUNID (-f e/E per-tree results).
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="examl-tpu", description="TPU-native maximum-likelihood "
        "phylogenetic tree inference")
    ap.add_argument("-s", dest="bytefile", required=True,
                    help="binary alignment file from the parser "
                         "(PHYLIP also accepted)")
    ap.add_argument("-n", dest="run_id", required=True, help="run name")
    ap.add_argument("-t", dest="tree_file", default=None,
                    help="starting tree (newick)")
    ap.add_argument("-R", dest="restart", action="store_true",
                    help="restart from the newest checkpoint")
    ap.add_argument("-m", dest="model", default="GAMMA",
                    choices=["GAMMA", "PSR"], help="rate heterogeneity model")
    ap.add_argument("-a", dest="median", action="store_true",
                    help="median instead of mean discrete gamma rates")
    ap.add_argument("-c", dest="categories", type=int, default=25,
                    help="maximum PSR rate categories")
    ap.add_argument("-f", dest="mode", default="d",
                    choices=["d", "o", "e", "E", "q"], help="algorithm: "
                    "d/o tree search (o disables the lnL cutoff), "
                    "e/E evaluate trees (E re-optimizes the model per "
                    "tree), q quartets")
    ap.add_argument("-e", dest="epsilon", type=float, default=0.1,
                    help="lnL epsilon for quartet-mode model optimization "
                         "(the search and tree-evaluation modes use the "
                         "reference's fixed modOpt schedule)")
    ap.add_argument("-i", dest="initial", type=int, default=None,
                    help="fixed initial rearrangement radius")
    ap.add_argument("-D", dest="rf_convergence", action="store_true",
                    help="stop when consecutive SPR cycles are <=1%% RF "
                         "apart")
    ap.add_argument("-B", dest="save_best", type=int, default=0,
                    help="also report the N best distinct trees found")
    ap.add_argument("-M", dest="per_partition_bl", action="store_true",
                    help="estimate per-partition branch lengths")
    ap.add_argument("-S", dest="save_memory", action="store_true",
                    help="memory saving for gappy alignments")
    ap.add_argument("-w", dest="workdir", default=".",
                    help="output directory")
    ap.add_argument("-b", "--bootstrap", dest="bootstrap", type=int,
                    default=0, metavar="K",
                    help="fleet mode: evaluate K bootstrap weight "
                         "replicates of the -t topology (site-"
                         "multiplicity resampling, seeds derived from "
                         "-p; one shared CLV pass + a batched weight "
                         "matrix in the lnL reduction)")
    ap.add_argument("-N", "--multi-start", dest="multi_start", type=int,
                    default=0, metavar="K",
                    help="fleet mode: evaluate K random starting trees "
                         "(seeds derived from -p), batching same-"
                         "profile topologies through one vmapped "
                         "program; --fleet-cycles adds branch-length "
                         "smoothing rounds per tree")
    ap.add_argument("--serve", dest="serve", default=None, metavar="JOBS",
                    help="fleet mode: drain a JSONL jobs file "
                         "(fleet/jobs.py format), polling for appended "
                         "jobs until an {\"op\": \"stop\"} line; "
                         "--serve-poll 0 drains once and exits")
    ap.add_argument("--serve-poll", dest="serve_poll", type=float,
                    default=1.0,
                    help="seconds between jobs-file polls under --serve "
                         "(0 = drain current contents and exit; "
                         "default 1)")
    ap.add_argument("--serve-max-pending", dest="serve_max_pending",
                    type=int, default=10000,
                    help="admission control: stop consuming new jobs-"
                         "file lines while this many jobs are pending "
                         "(the queue drains, then ingestion resumes; "
                         "default 10000)")
    ap.add_argument("--fleet-job-attempts", dest="fleet_job_attempts",
                    type=int, default=2,
                    help="per-job attempt cap: a job whose dispatch "
                         "fails this many times (non-finite lnL, "
                         "dispatch error, blown deadline) is "
                         "quarantined to ExaML_fleetFailed.<run> "
                         "instead of retried (default 2)")
    ap.add_argument("--fleet-job-deadline", dest="fleet_job_deadline",
                    type=float, default=0.0,
                    help="wall-clock seconds one batched fleet dispatch "
                         "may take before a --supervise parent kills "
                         "the attempt as JOB-stuck (no run-level retry "
                         "consumed; repeat offenders quarantine).  "
                         "0 disables the per-job deadline (default)")
    ap.add_argument("--fleet-batch", dest="fleet_batch", type=int,
                    default=16,
                    help="max jobs per batched fleet dispatch "
                         "(padded to a power of two; default 16)")
    ap.add_argument("--fleet-cycles", dest="fleet_cycles", type=int,
                    default=1,
                    help="evaluation cycles per fleet job; cycles "
                         "after the first smooth branch lengths "
                         "before re-scoring (default 1)")
    ap.add_argument("--fleet-devices", dest="fleet_devices", type=int,
                    default=1,
                    help="tree-axis device sharding: cut one batch per "
                         "local device lane and round-robin the "
                         "profile groups across them (0 = every local "
                         "device; default 1 = classic single-lane; a "
                         "device that fails init degrades the set, "
                         "never aborts)")
    ap.add_argument("--fleet-lease-ttl", dest="fleet_lease_ttl",
                    type=float, default=60.0,
                    help="leased gang serving (--launch N + a fleet "
                         "mode): seconds a rank's job lease stays "
                         "live without renewal; a dead rank's leases "
                         "expire after this and surviving ranks reap "
                         "them (default 60)")
    ap.add_argument("--bank", dest="bank", action="store_true",
                    help="ahead-of-time program banking: compile every "
                         "device-program family this run will dispatch "
                         "in parallel killable subprocess workers at "
                         "startup (persistent host-fingerprinted cache); "
                         "a family whose compile exceeds "
                         "--compile-timeout is killed and the run "
                         "degrades to the scan tier instead of wedging")
    ap.add_argument("--compile-timeout", dest="compile_timeout",
                    type=float, default=180.0,
                    help="per-family compile deadline in seconds: hard "
                         "(kill + scan-tier fallback) for --bank "
                         "workers, watchdog-bark threshold for any "
                         "in-process compile (default 180)")
    ap.add_argument("--launch", dest="launch", type=int, default=None,
                    metavar="N",
                    help="gang mode: the supervisor spawns all N ranks "
                         "itself (per-rank EXAML_PROCID, killable "
                         "process groups, local coordinator), watches "
                         "the per-rank heartbeats, and on any rank "
                         "death / single-rank straggler / collective "
                         "wedge kills and restarts the WHOLE gang from "
                         "the newest coordinated checkpoint "
                         "(--supervise-* flags apply gang-wide); a rank "
                         "that keeps dying shrinks the gang to N-1 "
                         "(elastic resume, down to --launch-min-ranks)")
    ap.add_argument("--launch-emulate", dest="launch_emulate",
                    action="store_true",
                    help="spawn the --launch gang WITHOUT a jax "
                         "distributed process group (N independent "
                         "single-process ranks honoring the same "
                         "rank/heartbeat/checkpoint contract) — for "
                         "backends without multi-process collectives "
                         "and for chaos tests")
    ap.add_argument("--launch-min-ranks", dest="launch_min_ranks",
                    type=int, default=1,
                    help="elastic-resume floor: never shrink the gang "
                         "below this many ranks (default 1)")
    ap.add_argument("--supervise", dest="supervise", action="store_true",
                    help="self-healing supervision: run the search as a "
                         "killable child, watch its search-loop "
                         "heartbeat, and on crash/stall restart from "
                         "the newest checkpoint with capped retries, "
                         "backoff and escalating degradation pins "
                         "(chunk->universal->scan); SIGTERM/SIGINT "
                         "preemptions resume without consuming a retry")
    ap.add_argument("--supervise-retries", dest="supervise_retries",
                    type=int, default=3,
                    help="max failure restarts under --supervise "
                         "(preemption resumes are not counted; "
                         "default 3)")
    ap.add_argument("--supervise-stall", dest="supervise_stall",
                    type=float, default=300.0,
                    help="seconds without a search-loop heartbeat "
                         "before the supervisor declares a dispatch/"
                         "collective wedge and kills the child "
                         "(default 300; 0 disables stall detection)")
    ap.add_argument("--supervise-backoff", dest="supervise_backoff",
                    type=float, default=2.0,
                    help="base seconds for the supervisor's exponential "
                         "restart backoff (default 2)")
    ap.add_argument("--inject-fault", dest="inject_fault",
                    action="append", metavar="SPEC", default=None,
                    help="arm a named fault-injection point (repeatable; "
                         "resilience/faults.py): "
                         "point[@rank=R][:after=N][:attempt=K]"
                         "[:signal=NAME][:hang[=S]] — e.g. "
                         "search.kill:after=10 or "
                         "search.kill@rank=1:after=10 (gang rank 1 "
                         "only); equivalent to EXAML_FAULTS entries")
    ap.add_argument("--profile", dest="profile_dir", default=None,
                    help="write a jax profiler trace to this directory "
                         "(SURVEY §5.1; view with xprof/tensorboard)")
    ap.add_argument("--metrics", dest="metrics_file", default=None,
                    help="write the runtime metrics-registry snapshot "
                         "(dispatch/compile/cache counters, phase timers) "
                         "to this JSON file at exit (process 0 only)")
    ap.add_argument("--trace-events", dest="trace_events_dir", default=None,
                    help="write Chrome-trace/Perfetto span events to "
                         "per-process JSONL files in this directory "
                         "(trace.p<procid>.jsonl; open in ui.perfetto.dev)")
    ap.add_argument("--ledger", dest="ledger_dir", default=None,
                    help="write the append-only run ledger (compiles, "
                         "phases, faults, checkpoint cycles, supervisor "
                         "decisions) to per-rank JSONL files in this "
                         "directory (ledger.p<procid>.jsonl; rank 0 "
                         "merges ledger.merged.jsonl at exit).  Defaults "
                         "to the --metrics file's directory when "
                         "--metrics is given")
    ap.add_argument("-g", dest="constraint_file", default=None,
                    help="multifurcating constraint tree")
    ap.add_argument("-p", dest="seed", type=int, default=12345,
                    help="random seed (constraint-tree resolution)")
    ap.add_argument("-Y", "-Q", dest="quartet_file", default=None,
                    help="quartet grouping file (-f q; the reference "
                         "spells this -Y, axml.c:1063 — -Q kept as an "
                         "alias for earlier revisions of this CLI)")
    ap.add_argument("-r", dest="quartet_samples", type=int, default=0,
                    help="number of random quartets to evaluate (-f q)")
    ap.add_argument("-I", dest="quartet_ckpt_interval", type=int,
                    default=10000,
                    help="quartet checkpoint interval (-f q)")
    ap.add_argument("--auto-prot", dest="auto_prot", default="ml",
                    choices=["ml", "bic", "aic", "aicc"],
                    help="criterion for AUTO protein model selection")
    from examl_tpu.parallel.launch import add_launch_args
    add_launch_args(ap)
    return ap


class RunFiles:
    """Rank-0 output files (reference `makeFileNames`/`printBothOpen`).

    On a -R restart, existing info/log files are appended to, preserving
    the interrupted run's history (the reference appends likewise)."""

    def __init__(self, workdir: str, run_id: str, append: bool = False,
                 primary: bool = True):
        """primary=False (non-zero process of a multi-host job) computes
        the same SPMD program but writes NO output files — the
        reference's processID==0 gating (`axml.c`, every print site)."""
        self.primary = primary
        os.makedirs(workdir, exist_ok=True)
        pre = os.path.join(workdir, "ExaML_")
        self.info_path = f"{pre}info.{run_id}"
        self.log_path = f"{pre}log.{run_id}"
        self.result_path = f"{pre}result.{run_id}"
        self.model_path = f"{pre}modelFile.{run_id}"
        self.treefile_path = f"{pre}TreeFile.{run_id}"
        self.quartets_path = f"{pre}quartets.{run_id}"
        self.start_time = time.time()
        self._phases = {}
        if not append and primary:
            for p in (self.info_path, self.log_path):
                open(p, "w").close()

    def info(self, msg: str) -> None:
        if not self.primary:
            return
        print(msg)
        with open(self.info_path, "a") as f:
            f.write(msg + "\n")

    # -- per-phase wall-time accounting (SURVEY §5.1: the reference has
    # only gettime()/accumulatedTime; phase times feed the metrics
    # registry as `phase.<name>` timers and emit trace spans, so the
    # info-file report, --metrics, and --trace-events share one record) --

    @contextlib.contextmanager
    def phase(self, name: str):
        from examl_tpu import obs
        t0 = time.time()
        obs.ledger_event("phase", name=name, status="begin")
        try:
            with obs.span(f"phase:{name}", cat="phase"):
                yield
        finally:
            dt = time.time() - t0
            self._phases[name] = self._phases.get(name, 0.0) + dt
            obs.observe(f"phase.{name}", dt)
            obs.ledger_event("phase", name=name, status="end",
                             seconds=round(dt, 3))

    def report_phases(self) -> None:
        # This instance's phases, merged with any `phase.*` timers other
        # components recorded straight into the registry.
        phases = dict(self._phases)
        try:
            from examl_tpu import obs
            for name, t in obs.snapshot().get("timers", {}).items():
                if name.startswith("phase.") and name[6:] not in phases:
                    phases[name[6:]] = t["total_s"]
        except Exception:
            pass
        if not phases:
            return
        total = time.time() - self.start_time
        self.info("")
        self.info("Wall-clock by phase:")
        for name, dt in phases.items():
            # Guard total == 0: a run whose phases are all ~0 s (mocked
            # clocks, sub-tick runs) must report, not ZeroDivisionError.
            pct = 100.0 * dt / total if total > 0 else 0.0
            self.info(f"  {name:24s} {dt:10.2f} s  ({pct:5.1f}%)")
        self.info(f"  {'total':24s} {total:10.2f} s")

    def log_lnl(self, lnl: float) -> None:
        if not self.primary:
            return
        with open(self.log_path, "a") as f:
            f.write(f"{time.time() - self.start_time:.6f} {lnl:.6f}\n")

    def write_result(self, text: str) -> None:
        if not self.primary:
            return
        with open(self.result_path, "w") as f:
            f.write(text if text.endswith("\n") else text + "\n")


def write_model_params(path: str, inst) -> None:
    """Final model parameters (reference `printModelParams`,
    `axml.c:1733-1835`)."""
    with open(path, "w") as f:
        for gid, (part, m) in enumerate(
                zip(inst.alignment.partitions, inst.models)):
            name = inst.auto_prot_models.get(gid, part.model_name)
            f.write(f"Partition: {gid} {part.name}\n")
            f.write(f"DataType: {part.datatype.name}\n")
            f.write(f"Substitution model: {name}\n")
            if getattr(inst, "psr", False):
                psr = inst.per_site_rates[gid]
                f.write(f"categories: {len(psr)}\n")
                f.write("category rates: "
                        + " ".join(f"{r:.6f}" for r in psr) + "\n")
            else:
                f.write(f"alpha: {m.alpha:.6f}\n")
            f.write("rates: " + " ".join(f"{r:.6f}" for r in m.rates) + "\n")
            f.write("freqs: " + " ".join(f"{x:.6f}" for x in m.freqs) + "\n")
            f.write("\n")


def selective_read_decision(model: str, is_bytefile: bool,
                            has_auto_aa: bool, nprocs: int,
                            save_memory: bool = False):
    """("slice" | "whole" | "error"), reason — the per-process data-
    loading policy, pure so it is unit-testable without a process group:

    * "slice": each process seeks only its site blocks (readMyData,
      `byteFile.c:278-382`) — including -m PSR, whose per-site rate
      state stays host-global via allgathers (engine.rate_scan output;
      one weight-window gather, instance.psr_packed_weights — the
      reference's CAT Gatherv/Scatterv, `optimizeModel.c:2135-2254`,
      as collectives);
    * "whole": every process reads the full file (single-process jobs;
      AUTO-protein partitions, whose BIC/AICc sample sizes must be
      global; non-byteFile inputs);
    * "error": currently unreachable — kept for future hard
      incompatibilities so callers keep handling it.
    """
    if nprocs <= 1:
        return "whole", "single process"
    if not is_bytefile:
        return "whole", "input is not a byteFile"
    if has_auto_aa:
        return "whole", ("AUTO protein model selection needs global "
                         "sample sizes")
    note = ""
    if model == "PSR":
        note = " (-m PSR rate state allgathers to every process)"
    if save_memory:
        note += " (-S gap bookkeeping follows the window)"
    return "slice", "selective byteFile read" + note


def _is_bytefile(path: str) -> bool:
    from examl_tpu.io.bytefile import BYTEFILE_MAGIC
    import struct
    with open(path, "rb") as f:
        head = f.read(12)
    return (len(head) == 12
            and struct.unpack("<iii", head)[2] == BYTEFILE_MAGIC)


def _load_alignment(path: str, local_window=None, block_multiple: int = 1):
    """Full read, or — in a multi-process job — only this process's site
    columns (reference per-rank loading, `byteFile.c:278-382`)."""
    if _is_bytefile(path):
        if local_window is not None:
            from examl_tpu.io.bytefile import read_bytefile_for_process
            procid, nprocs = local_window
            return read_bytefile_for_process(path, procid, nprocs,
                                             block_multiple=block_multiple)
        from examl_tpu.io.bytefile import read_bytefile
        return read_bytefile(path)
    from examl_tpu.io.alignment import load_alignment
    return load_alignment(path)             # convenience: raw PHYLIP, DNA


def _read_trees(path: str):
    with open(path) as f:
        text = f.read()
    return [t.strip() + ";" for t in text.split(";") if t.strip()]


def _checkpoint_manager(args, **kwargs):
    """The run's CheckpointManager: gang ranks (`--launch N`) share the
    two-phase manager over the ORIGINAL workdir — rank>0's output files
    are diverted to per-rank scratch, but checkpoint cycles must stage
    and publish in ONE directory or the commit protocol has nothing to
    coordinate."""
    from examl_tpu.search.checkpoint import CheckpointManager
    gang = getattr(args, "_gang", None)
    if gang is not None:
        rank, size, shared = gang
        return CheckpointManager(shared, args.run_id, gang_rank=rank,
                                 gang_size=size, **kwargs)
    return CheckpointManager(args.workdir, args.run_id, **kwargs)


def run_search(args, inst, files: RunFiles) -> int:
    from examl_tpu.search.convergence import RfConvergence
    from examl_tpu.search.raxml_search import (SearchOptions,
                                               compute_big_rapid)

    mgr = _checkpoint_manager(args)
    resume = None
    constraint = None
    if args.restart:
        tree = inst.random_tree(seed=args.seed)     # overwritten by restore
        resume = mgr.restore(inst, tree)
        if resume is None:
            files.info("no checkpoint found; cannot restart")
            return 1
        files.info(f"restart from state {resume['state']} with likelihood "
                   f"{inst.likelihood:.6f}")
        if args.constraint_file:
            # Keep enforcing the constraint after the restart (the
            # restored tree already honors it; only the checker is
            # rebuilt — the random resolution is NOT redone).
            from examl_tpu.tree.constraint import load_constraint
            with open(args.constraint_file) as f:
                _, constraint = load_constraint(
                    f.read(), inst.alignment.taxon_names, args.seed,
                    inst.num_branch_slots)
            constraint._tree = tree
    elif args.constraint_file:
        from examl_tpu.tree.constraint import load_constraint
        with open(args.constraint_file) as f:
            tree, constraint = load_constraint(
                f.read(), inst.alignment.taxon_names, args.seed,
                inst.num_branch_slots)
        inst.evaluate(tree, full=True)
        files.info(f"constraint tree randomly resolved (seed {args.seed}), "
                   f"lnL {inst.likelihood:.6f}")
    else:
        if not args.tree_file:
            files.info("a starting tree (-t), a constraint tree (-g), or "
                       "-R is required for the tree search")
            return 1
        tree = inst.tree_from_newick(_read_trees(args.tree_file)[0])
        inst.evaluate(tree, full=True)
        files.info(f"starting tree lnL {inst.likelihood:.6f}")
    files.log_lnl(inst.likelihood)

    def log(msg: str) -> None:
        files.info(msg)
        files.log_lnl(inst.likelihood)

    opts = SearchOptions(
        initial=args.initial if args.initial is not None else 10,
        initial_set=args.initial is not None,
        save_best_trees=args.save_best,
        constraint=constraint,
        do_cutoff=args.mode != "o",
        search_convergence=args.rf_convergence,
        log=log)
    from examl_tpu.search.spr import batched_scan_enabled
    files.info("SPR lazy-arm scan: "
               + ("batched (one dispatch per pruned node)"
                  if batched_scan_enabled(inst) else "sequential"))
    conv = (RfConvergence(inst.alignment.ntaxa, log=files.info)
            if args.rf_convergence else None)
    if conv is not None and resume is not None:
        blob = resume.get("extras", {}).get("rf_history")
        if blob:
            conv.load_blob(blob)
            files.info("restored RF-convergence history from checkpoint")
    inner_cb = mgr.callback(inst, tree)

    def checkpoint_cb(state: str, extras: dict) -> None:
        # Persist the -D convergence evidence with every checkpoint so a
        # restart keeps comparing against the pre-restart cycle's tree
        # (reference restores this via stored newick strings,
        # `restartHashTable.c:279-357`).
        if conv is not None:
            extras = dict(extras, rf_history=conv.to_blob())
        inner_cb(state, extras)
        # Preemption cadence: the checkpoint just written is coherent,
        # so a pending SIGTERM/SIGINT exits resumable HERE (raises
        # PreemptCheckpointed -> EXIT_PREEMPTED in main).
        from examl_tpu.resilience import preempt
        preempt.check_after_checkpoint(log=files.info)

    res = compute_big_rapid(inst, tree, opts, convergence_cb=conv,
                            checkpoint_cb=checkpoint_cb,
                            resume=resume)

    files.info(f"Likelihood of best tree: {res.likelihood:.6f}")
    files.write_result(tree.to_newick(inst.alignment.taxon_names))
    if files.primary:       # processID==0 gating (axml.c, every output)
        _write_per_gene_trees(args, inst, tree, files)
        write_model_params(files.model_path, inst)
    if res.good_trees and files.primary:
        good = os.path.join(args.workdir,
                            f"ExaML_goodTrees.{args.run_id}")
        with open(good, "w") as f:
            for snap in res.good_trees:
                snap.restore_into(tree)
                f.write(tree.to_newick(inst.alignment.taxon_names) + "\n")
        files.info(f"{len(res.good_trees)} other good trees written to "
                   f"{good}")
    return 0


def _write_per_gene_trees(args, inst, tree, files: RunFiles) -> None:
    """Under -M, write one tree per partition with that partition's own
    branch lengths (reference `printTreePerGene`, `treeIO.c:348`)."""
    if not args.per_partition_bl:
        return
    path = os.path.join(args.workdir,
                        f"ExaML_perGeneBranchLengths.{args.run_id}")
    with open(path, "w") as f:
        for gid, part in enumerate(inst.alignment.partitions):
            f.write(f"[partition {gid} {part.name}]\n")
            f.write(tree.to_newick(inst.alignment.taxon_names,
                                   branch_index=gid) + "\n")
    files.info(f"Per-partition branch-length trees written to {path}")


def run_fleet(args, inst, files: RunFiles) -> int:
    """Fleet modes (-b K / -N K / --serve): the profile-grouped batched
    job queue (examl_tpu/fleet/driver.py) with per-job checkpoints and
    `-R` resume through the normal CheckpointManager stack, job-level
    fault domains (retry/quarantine, fleet/quarantine.py) and a
    durable per-job results journal reconciled at resume."""
    from examl_tpu.fleet import jobs as jobs_mod
    from examl_tpu.fleet import lease as lease_mod
    from examl_tpu.fleet import quarantine
    from examl_tpu.fleet.driver import FleetDriver

    # Leased gang serving (ISSUE 14): under `--launch N` (or the
    # manually-launched rank contract) every rank runs its OWN driver
    # against the shared workdir — jobs are held under durable per-rank
    # leases, results journal per rank, and there are NO coordinated
    # checkpoints (fleet ranks are deliberately not in lockstep; the
    # per-job fsync'd journal is the durable record).
    gang = getattr(args, "_gang", None)
    rank, world, shared_dir = (gang if gang is not None
                               else (0, 1, args.workdir))
    leased = gang is not None
    board = None
    peer_journals = None
    if leased:
        mgr = None
        board = lease_mod.LeaseBoard(
            lease_mod.lease_dir(shared_dir, args.run_id), rank,
            ttl_s=args.fleet_lease_ttl,
            attempt=int(os.environ.get("EXAML_RESTART_COUNT", "0") or 0))
        # Incremental tail reads: the absorb loop polls these journals
        # for the rank's whole life, so each poll parses only appended
        # records, not every journal from byte 0.
        peer_journals = quarantine.JournalTail(shared_dir,
                                               args.run_id).records
        files.info(f"fleet: leased serving rank {rank} of {world} "
                   f"(lease board {board.path}, ttl "
                   f"{args.fleet_lease_ttl:.0f}s)")
    else:
        mgr = _checkpoint_manager(args, keep_last=2)
    journal = quarantine.ResultsJournal(quarantine.journal_path(
        shared_dir, args.run_id, rank if leased else None))
    deadletters = quarantine.DeadLetters(os.path.join(
        shared_dir, f"ExaML_fleetFailed.{args.run_id}"
        + (f".r{rank}" if leased else "")))
    if not args.restart:
        # A FRESH run (no -R) reusing a run id must not inherit an
        # abandoned incarnation's journal/dead letters: `-R` later
        # would reconcile the OLD records as done and silently skip
        # jobs whose inputs changed.  Checkpoints rotate via keep_last;
        # these files are removed so they exist only once this
        # incarnation appends (the supervisor keys its automatic -R on
        # that existence).
        stale_files = [journal.path, deadletters.path]
        if leased and rank == 0:
            # The primary also clears records NO rank of this world
            # will write (so they cannot race a live writer): the
            # BASE (single-process) journal/dead letters a previous
            # unleased incarnation left, and rank journals beyond the
            # current world size.  Peers' own `.r<k>` files are each
            # rank's own fresh-run cleanup.
            import glob as _glob
            for pat in (f"ExaML_fleetJournal.{args.run_id}",
                        f"ExaML_fleetFailed.{args.run_id}"):
                stale_files.append(os.path.join(shared_dir, pat))
                for p in _glob.glob(os.path.join(shared_dir,
                                                 pat + ".r*")):
                    try:
                        r = int(p.rsplit(".r", 1)[1])
                    except ValueError:
                        continue
                    if r >= world:
                        stale_files.append(p)
        for stale in stale_files:
            try:
                os.unlink(stale)
            except OSError:
                pass
    policy = quarantine.JobFaultPolicy(
        max_attempts=args.fleet_job_attempts,
        deadline_s=args.fleet_job_deadline)
    start_tree = None
    if args.tree_file:
        start_tree = inst.tree_from_newick(_read_trees(args.tree_file)[0])
        inst.evaluate(start_tree, full=True)
        files.info(f"starting tree lnL {inst.likelihood:.6f}")
        files.log_lnl(inst.likelihood)
    resume = None
    if args.restart and leased:
        # Leased ranks resume from the MERGED per-rank journals alone
        # (no coordinated checkpoints exist on purpose); a restarted
        # rank with no evidence yet — it died before any rank finished
        # a job — simply starts serving against the lease board.
        journal_recs = quarantine.read_all_journals(shared_dir,
                                                    args.run_id)
        resume = quarantine.reconcile_extras({}, journal_recs)
        files.info(f"restart (leased rank {rank}): "
                   f"{len(journal_recs)} journal record(s) reconciled "
                   "across ranks")
    elif args.restart:
        scaffold = (start_tree if start_tree is not None
                    else inst.random_tree(seed=args.seed))
        # GC-ordering contract: the journal is read and reconciled
        # HERE, strictly before the driver's first checkpoint write —
        # the only place keep_last pruning runs — and the journal /
        # dead-letter files never match the checkpoint glob, so a
        # concurrent-looking resume can never have its evidence
        # collected out from under it (tests/test_quarantine.py pins
        # both properties).
        res = mgr.restore(inst, scaffold)
        journal_recs = journal.read()
        if res is not None and res["state"] != "FLEET":
            files.info(f"checkpoint state {res['state']} is not a fleet "
                       "checkpoint")
            return 1
        if res is None and not journal_recs:
            if os.path.exists(journal.path):
                # A journal that exists but yields no intact record (a
                # kill inside the very first append): nothing finished,
                # so a fresh start IS the correct resume.
                files.info("no checkpoint and no intact journal "
                           "record; starting the fleet from scratch")
            else:
                files.info("no checkpoint found; cannot restart")
                return 1
        # Journal ∪ checkpoint: a SIGKILL between a batch and its
        # checkpoint must not replay the batch's finished jobs — the
        # journal (written per job, fsync'd) is the fresher record.
        resume = quarantine.reconcile_extras(
            res["extras"] if res is not None else {}, journal_recs)
        files.info(
            "restart from fleet "
            + ("checkpoint" if res is not None else "results journal")
            + (f" (+ {len(journal_recs)} journal record(s) reconciled)"
               if journal_recs and res is not None else ""))
    # Zero-recompile serving: under --serve (a long-lived process that
    # keeps meeting novel topology profiles) tree jobs route through
    # the universal interpreter by default; finite -b/-N batches keep
    # the specialized batched tier (their profiles amortize).
    # EXAML_FLEET_UNIVERSAL=1 forces routing everywhere, =0 disables.
    _uni_env = os.environ.get("EXAML_FLEET_UNIVERSAL", "")
    route_universal = (_uni_env == "1"
                       or (bool(args.serve) and _uni_env != "0"))
    driver = FleetDriver(inst, start_tree=start_tree,
                         batch_cap=args.fleet_batch,
                         cycles=args.fleet_cycles, mgr=mgr,
                         log=files.info, policy=policy,
                         journal=journal, deadletters=deadletters,
                         route_universal=route_universal,
                         devices=args.fleet_devices,
                         leases=board, peer_journals=peer_journals)
    if board is not None:
        # Keepalive: a long blocking dispatch (a cold first-call
        # compile easily outlasts any sane ttl) must not let this
        # rank's leases expire under it.
        board.start_keepalive()
    try:
        if args.serve:
            jobs = _serve_loop(args, driver, files, resume)
        else:
            if args.bootstrap:
                jobs = jobs_mod.make_jobs("bootstrap", args.bootstrap,
                                          args.seed, cycles=1)
                files.info(f"fleet: {len(jobs)} bootstrap replicates "
                           "of the starting topology")
                if args.fleet_cycles > 1:
                    files.info("note: --fleet-cycles applies to tree "
                               "jobs; bootstrap replicates are "
                               "weights-only (always 1 cycle)")
            else:
                jobs = jobs_mod.make_jobs("start", args.multi_start,
                                          args.seed,
                                          cycles=args.fleet_cycles)
                files.info(f"fleet: {len(jobs)} multi-start trees, "
                           f"{args.fleet_cycles} cycle(s) each")
            jobs = driver.run(jobs, resume)
    finally:
        if board is not None:
            # Release whatever this rank still holds (a stop sentinel
            # with jobs in retry backoff, an exception): leases left
            # behind would make peers wait out the ttl for jobs nobody
            # owns.
            board.close()
        journal.close()
    return _write_fleet_results(args, inst, files, jobs)


def _reject_job(files: RunFiles, job_id, reason: str) -> None:
    """Admission rejection: ledger event + counter + operator line —
    the driver never sees the spec, so a rejected job can neither
    crash the loop nor occupy the queue."""
    from examl_tpu import obs
    obs.inc("fleet.rejected")
    obs.ledger_event("job.rejected", job=job_id, reason=reason[:200])
    files.info(f"fleet: job "
               + (f"{job_id!r} " if job_id else "")
               + f"REJECTED at admission ({reason})")


def _serve_loop(args, driver, files: RunFiles, resume):
    """Drain + poll the jobs file until a stop sentinel (or, with
    --serve-poll 0, until the current contents are drained).  Jobs are
    addressed by line index, so appends never re-seed earlier jobs and
    a resume re-parses the whole file and skips finished ones.

    ADMISSION CONTROL: specs that parse but cannot run (bad tree
    strings, taxa mismatch vs the alignment, duplicate ids, malformed
    lines) are rejected with a `job.rejected` event instead of joining
    the queue, and ingestion pauses — `--serve-max-pending` — while the
    pending queue is full, so a runaway producer bounds memory instead
    of growing the job table without limit."""
    from examl_tpu import obs
    from examl_tpu.fleet import quarantine
    from examl_tpu.fleet.jobs import parse_jobs_lines
    from examl_tpu.resilience import heartbeat, preempt

    max_pending = max(1, int(getattr(args, "serve_max_pending", 10000)))
    processed = 0
    stop = False
    torn_prev = None
    driver.jobs = []
    while True:
        try:
            with open(args.serve) as f:
                lines = f.readlines()
        except OSError as exc:
            files.info(f"fleet: jobs file unreadable ({exc}); stopping")
            break
        # A producer appending non-atomically can leave a torn final
        # line (no trailing newline): leave it unconsumed until the
        # next poll completes it.  A line UNCHANGED across two polls is
        # taken as complete — a producer that stops mid-write forever
        # (or writes its last line via `echo -n`, stop sentinel
        # included) must not starve the queue.  In drain-once mode
        # (poll <= 0) no more appends are coming, so take it as is.
        if lines and args.serve_poll > 0 and not lines[-1].endswith("\n"):
            if lines[-1] != torn_prev:
                torn_prev = lines[-1]
                lines = lines[:-1]
        else:
            torn_prev = None
        # Bounded pending queue (--serve-max-pending): consume at most
        # `budget` new jobs per poll; the rest of the file (line
        # indexing keeps the derived seeds stable) re-parses once the
        # queue drains.  The budget subtracts live pending jobs
        # defensively — today drain() empties the queue before each
        # poll, so the bound is enforced by the per-poll cut alone.
        budget = max_pending - len(driver.pending())
        if len(lines) > processed and budget > 0:
            tail = lines[processed:]
            if any(ln.strip() and not ln.strip().startswith("#")
                   for ln in tail):
                errors = []
                specs, stop_seen = parse_jobs_lines(
                    tail, args.seed,
                    default_cycles=args.fleet_cycles,
                    start_index=processed, on_error=errors.append)
                if len(specs) > budget:
                    # Cut at the first unadmitted spec's line and
                    # RE-PARSE only the consumed prefix: its errors are
                    # reported exactly once, and a stop sentinel before
                    # the cut is honored (forcing stop_seen=False here
                    # would consume and permanently lose it), while
                    # everything past the cut re-parses next poll.
                    cut = specs[budget].index
                    errors = []
                    specs, stop_seen = parse_jobs_lines(
                        tail[:cut - processed], args.seed,
                        default_cycles=args.fleet_cycles,
                        start_index=processed, on_error=errors.append)
                    processed = cut
                else:
                    processed = len(lines)
                for msg in errors:
                    _reject_job(files, None, f"malformed line: {msg}")
                stop = stop or stop_seen
                # Duplicate ids — within a poll or ACROSS polls — would
                # alias the driver's per-job caches and collapse
                # table/resume records: first definition wins, later
                # ones are rejected (visibly, not silently dropped).
                existing = {j.job_id for j in driver.jobs}
                fresh = []
                for s in specs:
                    if s.job_id in existing:
                        _reject_job(files, s.job_id, "duplicate job id")
                        continue
                    # The admission parse seeds the driver's tree cache
                    # (one parse per eval job) — but NOT on a resumed
                    # loop: restore_jobs below may replace job.newick
                    # with the checkpointed current tree, and a
                    # pre-seeded cache would serve the stale original
                    # (and pin trees for already-done jobs forever).
                    reason = quarantine.admission_error(
                        s, driver.inst, driver.start_tree,
                        tree_cache=None if resume else driver._trees)
                    if reason is not None:
                        _reject_job(files, s.job_id, reason)
                        continue
                    existing.add(s.job_id)
                    fresh.append(s)
                specs = fresh
                if specs:
                    driver.jobs.extend(specs)
                    if resume:
                        # Apply the checkpoint snapshot to the FRESH
                        # specs only — each job sees it exactly once,
                        # as it joins the queue.  A whole-table
                        # re-application would regress jobs completed
                        # after the resume; a one-shot application
                        # would miss a finished job whose torn final
                        # line is consumed a poll later (re-running it
                        # and double-counting job.done).
                        driver.restore_jobs(resume, specs)
                    driver.apply_hang_attempts(specs)
                    files.info(f"fleet: {len(specs)} new jobs from "
                               f"{args.serve} (queue {len(driver.jobs)})")
                obs.gauge("fleet.jobs_total", len(driver.jobs))
            else:
                # Whitespace/comment-only append: a no-op, not a parse
                # attempt (and not a log line per poll).
                processed = len(lines)
        if driver.pending():
            driver.drain()
            continue
        if stop:
            files.info("fleet: stop sentinel seen and queue drained")
            break
        if args.serve_poll <= 0:
            break
        heartbeat.phase_beat("SERVE")
        preempt.check_after_checkpoint(log=files.info)
        time.sleep(args.serve_poll)
    return driver.jobs


def _write_fleet_results(args, inst, files: RunFiles, jobs) -> int:
    """Per-job results table + result trees (rank-0 gated like every
    other output).  Failed rows carry their failure cause and attempt
    count — `fleet.jobs_failed` equals the quarantine count, and each
    quarantined job's full record is in ExaML_fleetFailed.<run>."""
    ok = [j for j in jobs if j.done and not j.failed]
    failed = [j for j in jobs if j.failed]
    files.info(f"fleet: {len(ok)} jobs done, {len(failed)} failed, "
               f"{len(jobs) - len(ok) - len(failed)} pending")
    if failed:
        files.info(f"fleet: {len(failed)} quarantined job(s) with cause/"
                   "attempts/last-error in "
                   + os.path.join(args.workdir,
                                  f"ExaML_fleetFailed.{args.run_id}"))
    if ok:
        best = max(ok, key=lambda j: j.lnl)
        files.info(f"fleet: best job {best.job_id} ({best.kind}) "
                   f"likelihood {best.lnl:.6f}")
        files.log_lnl(best.lnl)
    if files.primary:
        table = os.path.join(args.workdir, f"ExaML_fleet.{args.run_id}")
        with open(table, "w") as f:
            f.write("# job_id kind index seed cycles lnl status "
                    "cause attempts\n")
            for j in jobs:
                lnl = f"{j.lnl:.6f}" if j.lnl is not None else "nan"
                status = ("failed" if j.failed
                          else "done" if j.done else "pending")
                f.write(f"{j.job_id} {j.kind} {j.index} {j.seed} "
                        f"{j.cycles_done}/{j.cycles} {lnl} {status} "
                        f"{j.cause or '-'} {j.attempts}\n")
        files.info(f"fleet results -> {table}")
        trees = [j for j in ok if j.newick]
        if trees:
            tf = os.path.join(args.workdir,
                              f"ExaML_fleetTrees.{args.run_id}")
            with open(tf, "w") as f:
                for j in trees:
                    f.write(j.newick.strip() + "\n")
            files.info(f"{len(trees)} fleet trees -> {tf}")
    return 0 if ok or not jobs else 1


def run_tree_evaluation(args, inst, files: RunFiles) -> int:
    """-f e / -f E: optimize model+branches on each tree in the file
    (reference `optimizeTrees`, `axml.c:2251-2356`), checkpointing with
    the MOD_OPT state per optimizer round and per finished tree
    (reference `axml.h:655-659`, restart dispatch `searchAlgo.c:1730-1749`
    and the -f e checkpoint leg `axml.c:2276-2296`)."""
    from examl_tpu.optimize.branch import tree_evaluate
    from examl_tpu.optimize.model_opt import mod_opt

    if not args.tree_file:
        files.info("tree evaluation mode requires -t")
        return 1
    trees_txt = _read_trees(args.tree_file)
    if not trees_txt:
        files.info(f"no trees found in {args.tree_file}")
        return 1
    files.info(f"Found {len(trees_txt)} trees to evaluate")
    fast = args.mode == "e"
    # -f e over thousands of trees: keep only the last 2 numbered
    # checkpoints (each embeds the accumulated results) and rate-limit
    # the mid-optimization cadence, else checkpoint bytes grow O(N^2).
    mgr = _checkpoint_manager(args, keep_last=2)
    last_ckpt = [0.0]
    # Gang runs (--launch) must skip the wall-clock mid-tree cadence
    # below: two-phase cycle numbers are each rank's write COUNT, and a
    # per-rank wall-clock gate would let ranks' counts drift apart —
    # once the drift exceeds keep_last the staged halves of a cycle
    # never meet and publishing stalls until the next restart resyncs
    # counters from the published set.  Gang ranks checkpoint per
    # FINISHED tree (a deterministic, rank-aligned cadence); a pending
    # preemption still stages immediately, which is safe even when
    # ranks sit on different trees — an incomplete cycle never
    # publishes, restore GCs it, and at most the in-flight tree is
    # redone.
    gang = getattr(args, "_gang", None) is not None

    start_i = 0
    results = []
    lnls = []
    resumed_tree = None
    if args.restart:
        tree = inst.tree_from_newick(trees_txt[0])   # scaffold for restore
        resume = mgr.restore(inst, tree)
        if resume is None:
            files.info("no checkpoint found; cannot restart")
            return 1
        if resume["state"] != "MOD_OPT":
            files.info(f"checkpoint state {resume['state']} is not a "
                       "tree-evaluation checkpoint")
            return 1
        ex = resume["extras"]
        start_i = ex["tree_iteration"]
        results = list(ex.get("results", []))
        lnls = list(ex.get("lnls", []))
        # Only a mid-optimization checkpoint carries a tree worth resuming
        # into; a per-finished-tree checkpoint restarts at trees_txt[i+1].
        resumed_tree = tree if ex.get("mid_tree") else None
        files.info(f"restart at tree {start_i} with likelihood "
                   f"{inst.likelihood:.6f}")

    for i in range(start_i, len(trees_txt)):
        if i == start_i and resumed_tree is not None:
            tree = resumed_tree        # mid-optimization topology+branches
        else:
            tree = inst.tree_from_newick(trees_txt[i])
        inst.evaluate(tree, full=True)

        def ckpt_cb(state: str, extras: dict, i=i, tree=tree) -> None:
            from examl_tpu.resilience import preempt
            if gang and not preempt.requested():
                return                      # gang cadence: per finished tree
            if (time.time() - last_ckpt[0] < 60.0
                    and not preempt.requested()):
                return                      # mid-tree cadence: >= 60 s apart
            merged = dict(extras)           # (a pending preemption writes
            merged.update(tree_iteration=i,  # regardless of the cadence)
                          results=results, lnls=lnls, mid_tree=True)
            mgr.write(state, merged, inst, tree)
            last_ckpt[0] = time.time()
            preempt.check_after_checkpoint(log=files.info)

        if fast and i > 0:
            tree_evaluate(inst, tree, 2.0)
        else:
            tree_evaluate(inst, tree, 1.0)
            mod_opt(inst, tree, 0.1, checkpoint_cb=ckpt_cb)
        files.info(f"Likelihood tree {i}: {inst.likelihood:.6f}")
        files.log_lnl(inst.likelihood)
        results.append(tree.to_newick(inst.alignment.taxon_names))
        lnls.append(inst.likelihood)
        # Per-finished-tree checkpoint so a restart moves on to tree i+1.
        mgr.write("MOD_OPT", {"tree_iteration": i + 1, "results": results,
                              "lnls": lnls}, inst, tree)
        last_ckpt[0] = time.time()
        from examl_tpu.resilience import heartbeat, preempt
        heartbeat.beat("TREE_EVAL")
        preempt.check_after_checkpoint(log=files.info)
    best = max(range(len(lnls)), key=lambda i: lnls[i])
    files.info(f"Evaluated {len(lnls)} trees; best is tree {best} "
               f"with likelihood {lnls[best]:.6f}")
    if files.primary:       # processID==0 gating (axml.c, every output)
        with open(files.treefile_path, "w") as f:
            f.write("\n".join(results) + "\n")
        write_model_params(files.model_path, inst)
    return 0


def _packing_report(inst, files: RunFiles) -> None:
    """Startup site-packing / load report (the reference's
    `printAssignments`/`printLoad`, `partitionAssignment.c:461-502` —
    here the 'load balance' is lane padding per state bucket)."""
    for states, bucket in sorted(inst.buckets.items()):
        true_sites = int(sum(bucket.part_widths))
        padded = bucket.num_sites
        files.info(
            f"bucket states={states}: {bucket.num_parts} partitions, "
            f"{true_sites} patterns -> {bucket.num_blocks} blocks x "
            f"{bucket.lane} lanes ({padded - true_sites} padding sites, "
            f"{100.0 * (padded - true_sites) / padded:.1f}% pad)")
        if getattr(inst, "save_memory", False):
            files.info(f"  SEV (-S) pool active for this bucket")


def main(argv=None) -> int:
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    ap = build_argparser()
    args = ap.parse_args(argv)

    # The reference's quartet flag-combination checks (axml.c:1206-1222):
    # -Y and -r belong to -f q only, and are mutually exclusive.
    if args.quartet_file and args.mode != "q":
        ap.error('you must specify "-Y quartetGroupingFileName" in '
                 'combination with "-f q"')
    if args.quartet_samples > 0 and args.mode != "q":
        ap.error('you must specify "-r randomQuartetNumber" in '
                 'combination with "-f q"')
    if args.quartet_samples > 0 and args.quartet_file:
        ap.error('you must specify either "-r randomQuartetNumber" or '
                 '"-Y quartetGroupingFileName"')

    # Fleet-mode flag hygiene: one fleet mode at a time, and the modes
    # that conflict with the batched tier's assumptions error up front.
    if args.bootstrap < 0 or args.multi_start < 0:
        ap.error("-b/-N replicate counts must be positive")
    fleet_modes = sum(bool(x) for x in (args.bootstrap, args.multi_start,
                                        args.serve))
    if fleet_modes > 1:
        ap.error("-b, -N and --serve are mutually exclusive fleet modes")
    # Declared (sites, tree) likelihood fabric (ISSUE 17): parse the
    # mesh spec at argument time so every shape error is an ap.error,
    # and pin down exactly which (S, T) combinations cannot compose.
    from examl_tpu.parallel.launch import mesh_spec_requested
    mesh_shape = None
    _mesh_spec = mesh_spec_requested(args)
    if _mesh_spec is not None:
        from examl_tpu.parallel.sharding import parse_mesh_spec
        try:
            mesh_shape = parse_mesh_spec(_mesh_spec)
        except ValueError as exc:
            ap.error(f"--mesh: {exc}")
        if args.single_device and mesh_shape != (1, 1):
            ap.error("--mesh SxT declares a device mesh; it cannot "
                     "combine with --single-device (use --mesh 1x1 "
                     "for an explicit single-device run)")
        if mesh_shape[1] > 1 and not fleet_modes:
            ap.error(f"mesh {mesh_shape[0]}x{mesh_shape[1]}: the tree "
                     "axis batches independent fleet jobs, so T>1 "
                     "needs a fleet mode (-b/-N/--serve); a single "
                     f"-f search uses --mesh {mesh_shape[0]}x1")
        if args.save_memory and mesh_shape[1] > 1:
            ap.error(f"mesh {mesh_shape[0]}x{mesh_shape[1]} cannot "
                     "compose with -S: the SEV pool holds ONE arena "
                     "per instance, so per-job arenas cannot stack "
                     "along the tree axis — only Sx1 meshes support "
                     f"-S (use --mesh {mesh_shape[0]}x1 without a "
                     "fleet mode)")
    if fleet_modes:
        if args.mode == "q":
            ap.error("fleet modes (-b/-N/--serve) replace the -f "
                     "algorithm; they cannot combine with -f q")
        if args.save_memory:
            # The one genuinely unsupported composition: the SEV pool
            # holds ONE arena per instance, so per-job arenas cannot
            # stack along the tree axis for ANY (S, T) — the precise
            # shape is named so the operator knows the mesh router
            # looked and declined, not that routing is missing.
            s_sh = mesh_shape[0] if mesh_shape else 1
            t_sh = mesh_shape[1] if mesh_shape else "J"
            ap.error(f"fleet modes do not support -S: the (S={s_sh}, "
                     f"T={t_sh}) combination cannot compose because "
                     "the SEV pool holds one arena per instance and "
                     "per-job arenas cannot stack along the tree "
                     "axis; drop -S, or run Sx1 site sharding "
                     "without a fleet mode")
        if mesh_shape is not None and args.fleet_devices != 1:
            ap.error("--mesh and --fleet-devices are mutually "
                     "exclusive: the fabric's tree axis replaces the "
                     "per-device lane round-robin (T slices of one "
                     "mesh instead of whole-device lanes)")
        if args.bootstrap and not args.tree_file:
            ap.error("-b bootstrap replicates resample weights on a "
                     "fixed topology: a starting tree (-t) is required")
        if args.fleet_job_attempts < 1:
            ap.error("--fleet-job-attempts must be at least 1")
        if args.fleet_job_deadline < 0:
            ap.error("--fleet-job-deadline must be >= 0")
        if args.serve_max_pending < 1:
            ap.error("--serve-max-pending must be at least 1")
        if args.fleet_devices < 0:
            ap.error("--fleet-devices must be >= 0 (0 = all local)")
        if args.fleet_lease_ttl <= 0:
            ap.error("--fleet-lease-ttl must be positive")
        if args.launch is None and (args.nprocs is not None
                                    or args.coordinator is not None):
            # Manually-launched multi-rank fleets route into the LEASED
            # rank contract instead of erroring: fleet ranks are NOT a
            # lockstep SPMD gang (jobs are independent), so the ranks
            # never join a collective process group — each becomes an
            # emulated gang rank leasing jobs from the shared board.
            if (args.nprocs or 1) > 1 and args.procid is None:
                # Two ranks silently sharing slot 0 would steal each
                # other's LIVE leases through the own-rank reclaim
                # path — the rank id must be explicit.
                ap.error("fleet ranks never join a collective process "
                         "group; every rank needs an explicit id: use "
                         "--nprocs N --procid K per rank (or --launch "
                         "N, which spawns the ranks itself)")
            if args.coordinator is not None and args.procid is None:
                ap.error("fleet ranks never join a collective process "
                         "group; use --nprocs N --procid K per rank "
                         "(or --launch N, which spawns the ranks)")
            # Applied to the environment inside the run (with restore),
            # so repeated in-process main() calls never leak a rank.
            args._fleet_rank = (args.procid or 0, args.nprocs or 1)
            args.nprocs = args.coordinator = args.procid = None
        # Without a declared mesh the batched tier owns the whole LOCAL
        # device set: per-job arenas stack along a leading tree axis
        # and round-robin across device lanes instead of sharding one
        # tree's site axis (exactly BEAGLE's multi-analysis
        # device-sharing trade).  A `--mesh SxT` run composes BOTH
        # instead (ISSUE 17): site shards and tree slices on one
        # fabric, so the blanket single-device pin must not fire.
        if mesh_shape is None and not getattr(args, "single_device",
                                              False):
            args.single_device = True

    from examl_tpu.resilience import faults as _faults
    if args.inject_fault:
        try:                         # validate at argument time, arm later
            _faults.parse_spec(",".join(args.inject_fault))
        except ValueError as exc:
            ap.error(f"--inject-fault: {exc}")

    if args.launch is not None:
        if args.launch < 1:
            ap.error("--launch requires at least 1 rank")
        if args.procid is not None or args.coordinator is not None \
                or args.nprocs is not None:
            ap.error("--launch spawns every rank itself (it supplies "
                     "--coordinator/--nprocs/--procid per rank); it "
                     "cannot be combined with --nprocs/--procid/"
                     "--coordinator — for a manually-launched multi-host "
                     "job drop --launch")
        # Gang mode: this process becomes the jax-free gang supervisor
        # (resilience/supervisor.GangSupervisor); every rank is a
        # killable child with EXAML_PROCID/EXAML_GANG_RANKS exported.
        # --supervise is implied (the gang IS the supervision unit).
        from examl_tpu.resilience import supervisor as _supervisor
        return _supervisor.launch_gang(raw_argv, args, log=print)

    if args.supervise:
        # Self-healing supervision: this process becomes a thin, jax-free
        # watcher (resilience/supervisor.py) and the ENTIRE run — faults,
        # banking, search — happens in killable child processes.  The
        # child gets the original argv minus the supervisor flags;
        # --inject-fault passes through so the child arms the registry.
        from examl_tpu.resilience import supervisor as _supervisor
        return _supervisor.supervise(raw_argv, args, log=print)

    from examl_tpu import obs
    from examl_tpu.parallel.launch import (enable_process_tracing,
                                           init_distributed)
    from examl_tpu.resilience import heartbeat as _heartbeat
    from examl_tpu.resilience import memgov as _memgov
    from examl_tpu.resilience import preempt as _preempt

    # One run = one metrics record: callers invoking main() repeatedly in
    # a single process (tests) must not accumulate counters across runs
    # (nor inherit a previous run's bank verdicts, fault hit-counts, or
    # heartbeat stream).
    obs.reset()
    from examl_tpu.ops import bank as _bank
    from examl_tpu.ops import export_bank as _export_bank
    _bank.reset()
    _export_bank.reset()
    _faults.reset()
    _heartbeat.reset()
    _memgov.reset()
    prior_faults_env = os.environ.get(_faults.ENV_VAR)
    from examl_tpu.obs import ledger as _ledger_mod
    _ledger_mod.reset()
    prior_ledger_env = os.environ.get(_ledger_mod.ENV_VAR)
    for spec in (args.inject_fault or []):
        _faults.arm(spec)
    # Manually-launched leased fleet rank (--nprocs/--procid routed at
    # parse time): publish the rank contract through the same env vars
    # the gang supervisor exports, restored at exit so in-process
    # callers (tests) never inherit a rank identity.
    prior_rank_env = {k: os.environ.get(k)
                      for k in (_heartbeat.PROCID_VAR,
                                _heartbeat.GANG_VAR)}
    if getattr(args, "_fleet_rank", None) is not None:
        k, n = args._fleet_rank
        os.environ[_heartbeat.PROCID_VAR] = str(k)
        if n > 1:
            os.environ[_heartbeat.GANG_VAR] = str(n)
    # One deadline definition for every compile monitor: the bank
    # workers' hard per-family kill AND the in-process watchdog bark
    # read the same knob (exported so subprocess workers inherit it).
    os.environ["EXAML_COMPILE_TIMEOUT"] = repr(float(args.compile_timeout))
    # Join the multi-host job BEFORE any output: only process 0 writes
    # run files (the reference's processID==0 gating); other processes
    # compute the same SPMD program with their files diverted to a
    # per-process scratch dir so nothing clobbers.
    init_distributed(args, log=print)
    primary = True
    gang_rank = 0
    gang_dir = args.workdir            # shared dir, BEFORE any diversion
    if args.nprocs is not None or args.coordinator is not None:
        import jax
        gang_rank = jax.process_index()
        primary = gang_rank == 0
        # Canonicalize the rank into EXAML_PROCID for manually-launched
        # multi-host jobs too (the gang supervisor already exports it):
        # rank-targeted fault specs (`point@rank=R`) and the trace
        # procid resolver key off this env var.
        os.environ.setdefault(_heartbeat.PROCID_VAR, str(gang_rank))
    elif _heartbeat.env_gang_size():
        # Emulated gang rank (--launch N --launch-emulate): no process
        # group exists, but the rank contract — process-0 output
        # gating, per-rank scratch dirs, per-rank heartbeats,
        # coordinated checkpoints in the SHARED dir — is identical.
        gang_rank = _heartbeat.env_rank()
        primary = gang_rank == 0
    if not primary:
        args.workdir = os.path.join(args.workdir, f".proc{gang_rank}")
    # Coordinated (two-phase) checkpointing applies exactly when the
    # gang supervisor spawned us: it guarantees one shared filesystem
    # and exports the world size.  Manually-launched multi-host jobs
    # keep the classic per-process checkpoint behavior.
    gang_size = _heartbeat.env_gang_size()
    args._gang = ((gang_rank, gang_size, gang_dir)
                  if gang_size and gang_size > 1 else None)
    files = RunFiles(args.workdir, args.run_id, append=args.restart,
                     primary=primary)
    # Observability wiring: per-process trace files named by procid
    # (process 0 merges a summary at exit), TraceAnnotation scopes when
    # any tracer is active, and the operator log sink into the info file
    # so watchdog barks name the guilty program family there too.
    if args.trace_events_dir:
        enable_process_tracing(args.trace_events_dir, log=files.info)
    if args.profile_dir or args.trace_events_dir:
        obs.set_annotations(True)
    # Run ledger: per-rank JSONL event stream (explicit --ledger DIR, or
    # auto-on next to the --metrics file).  Exported so subprocesses
    # (bank compile workers) append their events to the same timeline.
    from examl_tpu.obs import ledger as _ledger
    ledger_dir = _ledger.default_dir(args.ledger_dir, args.metrics_file)
    if ledger_dir:
        lpath = obs.enable_ledger(ledger_dir, proc=gang_rank)
        if lpath:
            os.environ[_ledger.ENV_VAR] = ledger_dir
            files.info(f"run ledger -> {lpath}")
    obs.ledger_event("run", status="start", run_id=args.run_id,
                     mode=args.mode, restart=bool(args.restart),
                     rank=gang_rank,
                     attempt=os.environ.get("EXAML_RESTART_COUNT"))
    # Periodic --metrics flush (heartbeat-ticked): a SIGKILLed child
    # must leave its last-known counters for the supervisor to merge,
    # not nothing (the exit-time snapshot below still wins when the
    # run ends normally).
    if args.metrics_file and files.primary:
        obs.set_autoflush(args.metrics_file)
    obs.set_log_sink(files.info)
    # Preemption safety: SIGTERM/SIGINT only SET A FLAG; the search
    # loop's checkpoint cadence turns it into an emergency checkpoint
    # and a clean resumable exit (EXIT_PREEMPTED) — no-op off the main
    # thread (threaded test drivers).  Heartbeats publish to
    # $EXAML_HEARTBEAT_FILE when set (the supervisor sets it).
    preempt_installed = _preempt.install(log=obs.log)
    from examl_tpu.parallel.launch import install_heartbeat
    install_heartbeat(args, log=files.info)
    rc = 1
    try:
        rc = _run(args, files)
        return rc
    except _preempt.PreemptCheckpointed as exc:
        obs.ledger_event("run", status="preempted", signame=exc.signame)
        files.info(f"run preempted ({exc.signame}): emergency checkpoint "
                   "written; restart with -R to resume (a --supervise "
                   "parent resumes automatically)")
        rc = _preempt.EXIT_PREEMPTED
        return rc
    except _memgov.MemoryBudgetExhausted as exc:
        # The memory governor's in-process ladder (evict + shrink +
        # halving re-dispatch) is out of moves: exit with the
        # self-diagnosed allocator-OOM status so a --supervise parent
        # classifies alloc-oom and restarts with the budget fraction
        # pinned down (NOT a tier pin — the program tier is fine).
        obs.ledger_event("run", status="alloc-oom", error=str(exc)[:200])
        files.info(f"run stopped on device-allocator OOM: {exc} "
                   "(a --supervise parent retries with a lower "
                   "EXAML_MEM_BUDGET_FRACTION pin)")
        rc = _memgov.MemoryBudgetExhausted.exit_code
        return rc
    finally:
        # The metrics snapshot and trace finalize must survive FAILED
        # runs — a wedged compile or mid-search crash is exactly when
        # the counters and the last completed span matter (the round-4
        # postmortem this subsystem exists for).
        obs.ledger_event("run", status="end", rc=rc)
        obs.set_autoflush(None)      # exit snapshot below is the record
        if args.metrics_file and files.primary:
            import json

            try:
                with open(args.metrics_file, "w") as f:
                    json.dump(obs.snapshot(), f, indent=2, sort_keys=True,
                              default=str)
                files.info(f"metrics snapshot -> {args.metrics_file}")
            except OSError as exc:
                files.info(f"metrics snapshot failed ({exc})")
        obs.set_log_sink(None)       # don't leak this run's info file
        obs.set_annotations(False)   # no TraceAnnotation cost after the run
        obs.finalize_tracing()
        obs.finalize_ledger()   # every rank merges; last exit completes it
        if preempt_installed:
            _preempt.uninstall()
        _heartbeat.reset()
        # --inject-fault arming is per-run: restore the env so repeated
        # in-process main() calls (tests) never inherit armed faults.
        if args.inject_fault:
            if prior_faults_env is None:
                os.environ.pop(_faults.ENV_VAR, None)
            else:
                os.environ[_faults.ENV_VAR] = prior_faults_env
        # Ledger export is per-run likewise.
        if prior_ledger_env is None:
            os.environ.pop(_ledger_mod.ENV_VAR, None)
        else:
            os.environ[_ledger_mod.ENV_VAR] = prior_ledger_env
        # Routed fleet-rank identity is per-run too.
        if getattr(args, "_fleet_rank", None) is not None:
            for key, val in prior_rank_env.items():
                if val is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = val


def _run(args, files: RunFiles) -> int:
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.parallel.launch import select_sharding

    files.info("examl-tpu: TPU-native maximum likelihood inference "
               "(capability parity with ExaML 3.0.22)")
    files.info(f"alignment: {args.bytefile}  mode: -f {args.mode}  "
               f"model: {args.model}")

    # Validate EXAML_EXPORT_BANK ONCE, before the bank phase: a typo'd
    # opt-in must fail here in seconds, not as a per-worker engine
    # error minutes into banking (enabled()/family_coverage swallow
    # the ValueError by design — they run in seams that must not
    # crash).
    from examl_tpu.ops import export_bank as _eb
    try:
        _eb.mode()
    except ValueError as exc:
        files.info(f"ERROR: {exc}")
        return 1

    bank_report = None
    if getattr(args, "bank", False):
        # Ahead-of-time program banking, BEFORE this process touches
        # its backend: killable subprocess workers populate the
        # persistent cache (and must be able to own an
        # exclusive-access accelerator, then release it to us), wedged
        # families get their scan-tier escape hatches pinned, and —
        # multi-host — every process banks before the collective
        # barrier so no peer enters the SPMD program while another is
        # still compiling.
        from examl_tpu.ops import bank
        from examl_tpu.parallel.launch import bank_barrier
        with files.phase("bank (aot compile)"):
            bank_report = bank.run_bank(args, log=files.info)
            bank_barrier(args, log=files.info)

    with files.phase("startup (io + engines)"):
        from examl_tpu.config import enable_persistent_compilation_cache
        cache = enable_persistent_compilation_cache()
        if cache:
            files.info(f"persistent compile cache: {cache}")
        from examl_tpu.ops import export_bank
        if export_bank.enabled():
            # Zero-compile restart path (ops/export_bank.py): engines
            # built below resolve exported-artifact -> persistent-XLA-
            # cache -> fresh-compile per program; a restarted or cold
            # process reaches its first dispatch without compiling.
            files.info(export_bank.startup_info())
        try:
            sharding = select_sharding(args, args.save_memory,
                                       log=files.info)
        except ValueError as exc:
            # A declared mesh that does not fit the visible devices
            # (e.g. --mesh 4x2 on 4 chips): a configuration error with
            # the exact (S, T)-vs-devices arithmetic, not a traceback.
            files.info(f"ERROR: {exc}")
            return 1
        # Multi-process jobs read only their own site columns (the
        # reference's readMyData) — policy in selective_read_decision.
        local_window = None
        if sharding is not None:
            import jax
            nprocs = jax.process_count()
            is_bf = _is_bytefile(args.bytefile)
            has_auto = False
            if nprocs > 1 and is_bf:
                from examl_tpu.io.bytefile import (PROT_MODELS,
                                                   read_bytefile_meta)
                meta = read_bytefile_meta(args.bytefile)
                has_auto = any(PROT_MODELS[pm.prot] == "AUTO"
                               for pm in meta.parts if pm.dtype_i == 2)
            policy, reason = selective_read_decision(
                args.model, is_bf, has_auto, nprocs,
                save_memory=getattr(args, "save_memory", False))
            if policy == "error":
                files.info("ERROR: " + reason)
                return 1
            if policy == "slice":
                local_window = (jax.process_index(), nprocs)
                files.info(
                    f"{reason}: process {local_window[0]} of "
                    f"{local_window[1]} loads only its site blocks")
            elif nprocs > 1:
                files.info(f"whole-file reads per process ({reason})")
        # Setup-phase liveness (PARSE/PACK, plus SCHEDULE beats from the
        # traversal builders): large-tree host phases are minutes of
        # legitimate silence the --supervise stall detector must not
        # hang-kill — until now it only saw beats from the search loop.
        from examl_tpu.resilience import heartbeat as _hb
        _hb.phase_beat("PARSE")
        data = _load_alignment(
            args.bytefile, local_window=local_window,
            block_multiple=(sharding.num_devices if sharding else 1))
        files.info(f"{data.ntaxa} taxa, {data.total_patterns} patterns"
                   + (" (this process)" if local_window else "")
                   + f", {len(data.partitions)} partitions")

        _hb.phase_beat("PACK")
        inst = PhyloInstance(
            data, ncat=4, use_median=args.median,
            per_partition_branches=args.per_partition_bl,
            rate_model=args.model, psr_categories=args.categories,
            save_memory=args.save_memory, sharding=sharding,
            block_multiple=(sharding.num_devices if sharding else 1),
            local_window=local_window)
        inst.auto_prot_criterion = args.auto_prot
        _packing_report(inst, files)

    if bank_report is not None:
        # First-call every banked family NOW, as persistent-cache hits:
        # the engine's compile monitors fire inside this phase (counted
        # as engine.compile_count.bank_phase), so the search performs
        # zero first-call compiles — any later shape-variant compile is
        # a cache-warm member of a banked family.
        from examl_tpu.ops import bank
        with files.phase("bank (warm programs)"):
            try:
                warm_tree = (inst.tree_from_newick(
                    _read_trees(args.tree_file)[0])
                    if args.tree_file else inst.random_tree(args.seed))
                bank.warm_instance(inst, warm_tree, bank_report,
                                   files.info)
            except Exception as exc:       # noqa: BLE001 — warm is an
                # optimization; its failure must not kill the run
                files.info(f"bank warm pass failed ({exc}); programs "
                           "compile lazily (watchdogged)")

    with contextlib.ExitStack() as stack:
        if args.profile_dir:
            import jax

            stack.enter_context(jax.profiler.trace(args.profile_dir))
            files.info(f"profiler trace -> {args.profile_dir}")
        fleet = bool(args.bootstrap or args.multi_start or args.serve)
        phase_name = ("inference (fleet)" if fleet
                      else f"inference (-f {args.mode})")
        with files.phase(phase_name):
            if fleet:
                rc = run_fleet(args, inst, files)
            elif args.mode in ("d", "o"):
                rc = run_search(args, inst, files)
            elif args.mode in ("e", "E"):
                rc = run_tree_evaluation(args, inst, files)
            elif args.mode == "q":
                from examl_tpu.cli.quartets import run_quartets
                rc = run_quartets(args, inst, files)
            else:
                raise AssertionError(args.mode)
    if getattr(inst, "save_memory", False):
        for states, eng in inst.engines.items():
            st = eng.sev.stats()
            files.info(
                f"SEV bucket states={states}: {st['allocated_cells']} of "
                f"{st['dense_cells']} CLV cells allocated "
                f"({100.0 * st['saving_ratio']:.1f}% saved)")
    files.report_phases()
    return rc


if __name__ == "__main__":
    sys.exit(main())
