"""Leased 2D fleet serving (ISSUE 14): device-sharded batches +
per-rank job leases.

The unit that dies (a rank, a device) is now smaller than the unit
that matters (the serve window): one BatchEvaluator lane per local
device with graceful init degradation, and durable per-rank job leases
over the shared workdir so a rank death costs ONLY its in-flight
leases — surviving/restarted ranks reap the expired ones (jittered),
reconciled against the results journal so a completed-but-unreaped job
never re-runs, and per-job lnL is bit-identical regardless of which
device, rank, or lease order evaluated it.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance

from tests.conftest import correlated_dna

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- lease board unit matrix -------------------------------------------------


def _boards(tmp_path, ttl=0.3):
    from examl_tpu.fleet.lease import LeaseBoard
    d = str(tmp_path / "leases")
    return (LeaseBoard(d, rank=0, ttl_s=ttl),
            LeaseBoard(d, rank=1, ttl_s=ttl))


def test_lease_acquire_excl_renew_release(tmp_path):
    a, b = _boards(tmp_path, ttl=5.0)
    assert a.acquire("j1") is True
    assert b.acquire("j1") is False          # os.link EXCL: one holder
    assert a.still_mine("j1") and not b.still_mine("j1")
    assert b.expired("j1") is False          # live foreign lease
    assert a.renew("j1") is True
    rec = b.read("j1")
    assert rec["rank"] == 0 and rec["job_id"] == "j1"
    a.release("j1")
    assert a.read("j1") is None
    assert b.acquire("j1") is True           # released -> free


def test_lease_expiry_reap_and_fencing(tmp_path):
    a, b = _boards(tmp_path, ttl=0.25)
    assert a.acquire("j1")
    time.sleep(0.35)
    assert b.expired("j1") is True
    assert b.reap("j1") is True              # steal the expired lease
    assert b.still_mine("j1")
    # the old holder is FENCED: renew discovers the loss and refuses
    # to republish over the reaper's lease
    assert a.still_mine("j1") is False
    assert a.renew("j1") is False
    assert b.still_mine("j1")                # reaper unharmed


def test_lease_reap_single_winner(tmp_path):
    """Two ranks reaping the same expired lease: the rename steal is
    atomic, so ownership never splits — exactly one ends up holding."""
    a, b = _boards(tmp_path, ttl=0.2)
    from examl_tpu.fleet.lease import LeaseBoard
    c = LeaseBoard(str(tmp_path / "leases"), rank=2, ttl_s=0.2)
    assert a.acquire("j1")
    time.sleep(0.3)
    got_b = b.reap("j1")
    got_c = c.reap("j1")
    assert got_b != got_c or not (got_b and got_c)
    assert int(got_b) + int(got_c) == 1
    holders = [x for x in (b, c) if x.still_mine("j1")]
    assert len(holders) == 1


def test_lease_torn_record_tolerated(tmp_path):
    """A torn/corrupt lease file reads as held-but-unreadable (the
    ledger's one torn-line read path) and expires by FILE AGE — never a
    crash, never treated as free."""
    a, b = _boards(tmp_path, ttl=0.2)
    path = os.path.join(a.path, "j9.lease")
    with open(path, "w") as f:
        f.write('{"job_id": "j9", "ran')     # torn mid-publish
    assert b.read("j9") == {"job_id": "j9"}
    assert b.expired("j9") is False          # young: conservative hold
    assert b.acquire("j9") is False          # file exists: not free
    past = time.time() - 10.0
    os.utime(path, (past, past))
    assert b.expired("j9") is True           # 2x ttl file age fallback
    assert b.reap("j9") is True


def test_lease_write_fault_survivable(tmp_path, monkeypatch):
    """fleet.lease.write: a failed lease publish (full disk) leaves the
    job unleased this round — counted, logged, never a crash."""
    from examl_tpu import obs
    from examl_tpu.resilience import faults
    a, _ = _boards(tmp_path, ttl=5.0)
    monkeypatch.setenv("EXAML_FAULTS", "fleet.lease.write")
    faults.reset()
    errs0 = obs.counter("fleet.lease_errors")
    assert a.acquire("j1") is False
    assert obs.counter("fleet.lease_errors") == errs0 + 1
    assert a.read("j1") is None              # nothing half-published
    faults.reset()
    monkeypatch.delenv("EXAML_FAULTS")
    assert a.acquire("j1") is True           # clean retry succeeds


def test_lease_reap_fault_survivable(tmp_path, monkeypatch):
    """fleet.lease.reap: a reap that dies mid-steal leaves the expired
    lease in place for the next (jittered) attempt."""
    from examl_tpu import obs
    from examl_tpu.resilience import faults
    a, b = _boards(tmp_path, ttl=0.2)
    assert a.acquire("j1")
    time.sleep(0.3)
    monkeypatch.setenv("EXAML_FAULTS", "fleet.lease.reap")
    faults.reset()
    errs0 = obs.counter("fleet.lease_errors")
    assert b.reap("j1") is False
    assert obs.counter("fleet.lease_errors") == errs0 + 1
    assert b.read("j1") is not None          # still on the board
    faults.reset()
    monkeypatch.delenv("EXAML_FAULTS")
    assert b.reap("j1") is True


def test_reap_backoff_deterministic_and_decorrelated():
    from examl_tpu.fleet.lease import reap_backoff
    a = [reap_backoff("j1", 0, k) for k in (1, 2, 3)]
    assert a == [reap_backoff("j1", 0, k) for k in (1, 2, 3)]
    assert all(0 < d <= 1.0 for d in a)
    assert a != [reap_backoff("j1", 1, k) for k in (1, 2, 3)]


# -- driver + lease integration ---------------------------------------------


def test_expired_but_journaled_job_never_reruns(tmp_path):
    """THE reconciliation guarantee: a job whose holder died AFTER
    journaling the result but BEFORE releasing the lease is absorbed as
    done — its stale lease is scrubbed, nothing re-dispatches, and no
    second job.done is emitted."""
    from examl_tpu import obs
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.fleet.lease import LeaseBoard
    data = correlated_dna(8, 120, seed=4)
    inst = PhyloInstance(data)
    jobs = make_jobs("start", 4, 7)
    # "rank 1" journaled start1 done, then died holding its lease
    dead = LeaseBoard(str(tmp_path / "leases"), rank=1, ttl_s=0.01)
    dead.acquire("start1")
    dead._held.clear()                       # rank 1 is gone
    peer_rec = {"job_id": "start1", "kind": "start", "index": 1,
                "seed": jobs[1].seed, "cycles": 1, "cycles_done": 1,
                "lnl": -555.5, "done": True, "failed": False,
                "attempts": 1}
    time.sleep(0.05)
    board = LeaseBoard(str(tmp_path / "leases"), rank=0, ttl_s=0.01)
    drv = FleetDriver(inst, batch_cap=4, leases=board,
                      peer_journals=lambda: [peer_rec])
    absorbed0 = obs.counter("fleet.jobs_absorbed")
    out = drv.run(jobs)
    by_id = {j.job_id: j for j in out}
    assert by_id["start1"].done and by_id["start1"].lnl == -555.5
    assert obs.counter("fleet.jobs_absorbed") == absorbed0 + 1
    assert "start1" not in drv._started      # never dispatched here
    assert board.read("start1") is None      # stale lease scrubbed
    assert all(j.done for j in out)


def test_leased_run_matches_unleased_bitwise(tmp_path):
    """Lease-order independence: the same queue through a leased
    single-rank driver scores bit-identically to the classic driver,
    and every lease is released at the end."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.fleet.lease import LeaseBoard
    data = correlated_dna(8, 120, seed=4)
    ref_inst = PhyloInstance(data)
    ref = {j.job_id: j.lnl
           for j in FleetDriver(ref_inst, batch_cap=3).run(
               make_jobs("start", 6, 9))}
    inst = PhyloInstance(data)
    board = LeaseBoard(str(tmp_path / "leases"), rank=0, ttl_s=30.0)
    drv = FleetDriver(inst, batch_cap=3, leases=board,
                      peer_journals=lambda: [])
    out = drv.run(make_jobs("start", 6, 9))
    assert {j.job_id: j.lnl for j in out} == ref
    assert board.held() == []
    assert os.listdir(board.path) == []      # all released


def test_two_leased_ranks_split_queue_bitwise(tmp_path):
    """Two concurrent in-process 'ranks' over one lease board: the
    queue splits with no double evaluation (mutual exclusion), both
    tables converge through journal absorption, and per-job lnL is
    bit-identical to the single-driver run regardless of which rank
    evaluated what."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.fleet.lease import LeaseBoard
    from examl_tpu.fleet.quarantine import ResultsJournal, journal_path
    data = correlated_dna(8, 120, seed=4)
    ref = {j.job_id: j.lnl
           for j in FleetDriver(PhyloInstance(data), batch_cap=2).run(
               make_jobs("start", 8, 3))}
    wd = str(tmp_path)
    drivers = []
    for rank in (0, 1):
        inst = PhyloInstance(data)
        board = LeaseBoard(str(tmp_path / "leases"), rank=rank,
                           ttl_s=30.0)
        journal = ResultsJournal(journal_path(wd, "T", rank))
        drv = FleetDriver(
            inst, batch_cap=2, leases=board, journal=journal,
            peer_journals=lambda: __import__(
                "examl_tpu.fleet.quarantine",
                fromlist=["q"]).read_all_journals(wd, "T"))
        drivers.append(drv)
    outs = [None, None]
    errs = []

    def run(i):
        try:
            outs[i] = drivers[i].run(make_jobs("start", 8, 3))
        except Exception as exc:            # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert not errs, errs
    for out in outs:
        assert out is not None
        assert {j.job_id: j.lnl for j in out} == ref
    # mutual exclusion: each job dispatched by exactly one rank
    evaluated = [set(d._started) for d in drivers]
    assert not (evaluated[0] & evaluated[1])
    assert evaluated[0] | evaluated[1] == set(ref)


def test_journal_tail_incremental_and_torn(tmp_path):
    """The absorb loop's incremental journal reader: only appended
    bytes parse on each poll, an incomplete final line (mid-append
    read) is left unconsumed until its newline lands, and a
    truncated/recreated file re-reads from zero."""
    from examl_tpu.fleet.quarantine import JournalTail, journal_path
    tail = JournalTail(str(tmp_path), "T")
    p = journal_path(str(tmp_path), "T", 0)
    rec = ('{"job_id": "a", "done": true, "lnl": -1.0}\n')
    with open(p, "w") as f:
        f.write(rec)
        f.write('{"job_id": "b", "done": tr')     # torn mid-append
    got = {r["job_id"] for r in tail.records()}
    assert got == {"a"}
    with open(p, "a") as f:
        f.write('ue}\n')                          # the append completes
    got = {r["job_id"] for r in tail.records()}
    assert got == {"a", "b"}
    # a second rank's journal joins the set mid-run
    with open(journal_path(str(tmp_path), "T", 1), "w") as f:
        f.write('{"job_id": "c", "done": true}\n')
    assert {r["job_id"] for r in tail.records()} == {"a", "b", "c"}
    # truncation (a peer's fresh-run cleanup recreated the file)
    with open(p, "w") as f:
        f.write('{"job_id": "d", "done": true}\n')
    assert "d" in {r["job_id"] for r in tail.records()}


# -- placement independence (device lanes) -----------------------------------


def test_device_sharded_parity_matrix():
    """Per-job lnL bit-identical regardless of which DEVICE lane
    evaluated it (conftest forces 8 XLA host devices): sharded run ==
    single-lane run == one-at-a-time anchor, GAMMA fast tier."""
    from examl_tpu.fleet import seeds
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    data = correlated_dna(8, 120, seed=4)
    anchor_inst = PhyloInstance(data)
    anchor = {}
    for k in range(10):
        t = anchor_inst.random_tree(
            seed=seeds.derive(7, "start", k))
        anchor_inst.evaluate(t, full=True)
        anchor[f"start{k}"] = float(
            np.sum(anchor_inst.per_partition_lnl))
    single = {j.job_id: j.lnl
              for j in FleetDriver(PhyloInstance(data), batch_cap=4,
                                   devices=1).run(
                  make_jobs("start", 10, 7))}
    inst = PhyloInstance(data)
    drv = FleetDriver(inst, batch_cap=4, devices=0)
    assert drv.shards is not None and len(drv.shards) >= 2
    sharded = {j.job_id: j.lnl for j in drv.run(make_jobs("start",
                                                          10, 7))}
    assert sharded == single
    for k, v in anchor.items():
        assert sharded[k] == v


def test_device_sharded_parity_psr():
    """The scan-tier (PSR) batch takes the device lanes too: per-job
    lnL bit-identical across lanes with non-trivial per-site rates."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    data = correlated_dna(6, 90, seed=2)
    single_inst = PhyloInstance(data, rate_model="PSR")
    single = {j.job_id: j.lnl
              for j in FleetDriver(single_inst, batch_cap=3,
                                   devices=1).run(
                  make_jobs("start", 6, 5))}
    inst = PhyloInstance(data, rate_model="PSR")
    drv = FleetDriver(inst, batch_cap=3, devices=0)
    out = drv.run(make_jobs("start", 6, 5))
    assert {j.job_id: j.lnl for j in out} == single


def test_device_degraded_init_survives(monkeypatch):
    """A device whose lane fails INIT degrades the set (counter +
    surviving lanes), never aborts."""
    from examl_tpu import obs
    from examl_tpu.fleet import shard as shard_mod
    data = correlated_dna(6, 90, seed=2)
    inst = PhyloInstance(data)
    primary = inst.batch_evaluator()
    real_init = shard_mod.DeviceShard.__init__
    calls = []

    def flaky_init(self, inst_, device, index):
        calls.append(index)
        if index == 2:
            raise RuntimeError("device 2 is toast")
        return real_init(self, inst_, device, index)

    monkeypatch.setattr(shard_mod.DeviceShard, "__init__", flaky_init)
    d0 = obs.counter("fleet.device_degraded")
    ss = shard_mod.ShardSet(inst, primary, max_devices=4)
    assert obs.counter("fleet.device_degraded") == d0 + 1
    assert len(ss) == 3                      # 4 requested, 1 degraded
    assert 2 in calls


# -- batched universal (select_n) --------------------------------------------


@pytest.mark.parametrize("one_entry", [False, True])
def test_unibatch_bit_identical_and_measured(monkeypatch, one_entry):
    """The vmapped select_n universal interpreter scores mixed-profile
    novel jobs bit-identically to solo switch-based routing.  The
    measured CPU verdict (driver.py): ~3x per-step compute makes it a
    dispatch-bound-only win, so it is OPT-IN (EXAML_FLEET_UNIBATCH=1)
    and `fleet.universal_retrace` counts the solo dispatches a batched
    program would merge.  With every row past the one-entry threshold
    the routed layouts keep the 8-wide tail the interpreter runs, and a
    one-entry layout gets no batch key: it dispatches solo."""
    from examl_tpu import obs
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    from examl_tpu.ops import fastpath
    if one_entry:
        monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", 1)
    # 40 taxa: wide enough that the jobs' trees plan a one-entry tail.
    data = correlated_dna(40, 60, seed=1) if one_entry \
        else correlated_dna(10, 160, seed=1)

    def run(unibatch):
        if unibatch:
            monkeypatch.setenv("EXAML_FLEET_UNIBATCH", "1")
        else:
            monkeypatch.delenv("EXAML_FLEET_UNIBATCH", raising=False)
        inst = PhyloInstance(data)
        drv = FleetDriver(inst, batch_cap=4, route_universal=True)
        out = drv.run(make_jobs("start", 6, 13))
        assert all(j.done and not j.failed for j in out), \
            [(j.job_id, j.last_error) for j in out if j.failed]
        return {j.job_id: j.lnl for j in out}

    retrace0 = obs.counter("fleet.universal_retrace")
    solo = run(False)
    assert obs.counter("fleet.universal_retrace") > retrace0
    uni0 = obs.counter("fleet.uni_batches")
    batched = run(True)
    assert obs.counter("fleet.uni_batches") > uni0
    assert batched == solo                   # bitwise, not tolerance
    if one_entry:
        inst = PhyloInstance(data)
        ev = inst.batch_evaluator()
        prep = ev.prepare(inst.random_tree(1))
        assert prep.st.profile[-1][0] == "e"
        assert ev.unibatch_key(prep) is None


# -- supervisor: fleet gangs are NOT lockstep --------------------------------


def test_fleet_gang_rank_death_restarts_only_that_rank(tmp_path):
    """A fleet rank death restarts ONLY the dead rank: the healthy
    rank is never gang-killed (it finishes its own work and exits 0),
    no tier pin is applied, and the evidence counters say
    fleet-rank-death, not a run-level retry."""
    from examl_tpu.resilience.supervisor import GangSupervisor
    marker = tmp_path / "rank0.done"
    sup = GangSupervisor([], workdir=str(tmp_path), run_id="FG",
                         ranks=2, fleet=True, backoff=0.05,
                         stall_timeout=0.0)
    spawned = []

    def fake_spawn(k, attempt):
        spawned.append((k, attempt))
        if k == 0:
            code = (f"import time; time.sleep(1.5); "
                    f"open({str(marker)!r}, 'w').write('ok')")
        elif attempt == 0:
            code = "import sys; sys.exit(3)"      # first life: dies
        else:
            code = "import time; time.sleep(0.2)"  # respawn: clean
        return subprocess.Popen([sys.executable, "-c", code],
                                start_new_session=True)

    sup._spawn_fleet_rank = fake_spawn
    rc = sup.run()
    assert rc == 0
    assert marker.exists()                   # rank 0 never killed
    assert (0, 0) in spawned and (1, 0) in spawned
    assert (1, 1) in spawned                 # only rank 1 respawned
    assert all(k == 1 for k, a in spawned if a > 0)
    assert sup.counters.get("resilience.gang.fleet_rank_deaths") == 1
    assert sup._pins() == {}                 # no tier pin ever


def test_launch_gang_selects_fleet_policy(tmp_path, monkeypatch):
    """launch_gang hands fleet modes the non-lockstep leased policy."""
    from examl_tpu.resilience import supervisor as sup_mod
    captured = {}

    class Stub:
        def __init__(self, *a, **kw):
            captured.update(kw)

        def run(self):
            return 0

    monkeypatch.setattr(sup_mod, "GangSupervisor", Stub)
    from types import SimpleNamespace
    args = SimpleNamespace(workdir=str(tmp_path), run_id="X", launch=2,
                           launch_emulate=True, launch_min_ranks=1,
                           supervise_retries=3, supervise_stall=10,
                           supervise_backoff=1.0, metrics_file=None,
                           ledger_dir=None, bootstrap=0, multi_start=0,
                           serve="jobs.jsonl")
    assert sup_mod.launch_gang([], args) == 0
    assert captured["fleet"] is True
    args.serve = None
    sup_mod.launch_gang([], args)
    assert captured["fleet"] is False


# -- the acceptance chaos e2e ------------------------------------------------


def _chaos_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    for k in ("EXAML_FAULTS", "EXAML_HEARTBEAT_FILE",
              "EXAML_FLEET_HANG_ATTEMPTS", "EXAML_RESTART_COUNT",
              "EXAML_PROCID", "EXAML_GANG_RANKS"):
        env.pop(k, None)
    return env


def _leased_fixture(tmp_path, njobs=8, ntaxa=6, nsites=60):
    from examl_tpu.io.bytefile import write_bytefile
    data = correlated_dna(ntaxa, nsites, seed=0)
    bf = str(tmp_path / "a.binary")
    write_bytefile(bf, data)
    jf = str(tmp_path / "jobs.jsonl")
    with open(jf, "w") as f:
        for _ in range(njobs):
            f.write('{"kind": "start"}\n')
        f.write('{"op": "stop"}\n')
    return bf, jf


def test_leased_gang_rank_death_chaos(tmp_path):
    """ISSUE 14 acceptance: SIGKILL rank 1 of a 2-rank emulated leased
    `--serve` gang mid-batch — the run completes, the merged ledger
    shows every job.done EXACTLY once, and only rank-1's leased
    in-flight jobs were re-dispatched (zero re-runs of journaled
    jobs)."""
    bf, jf = _leased_fixture(tmp_path, njobs=8)
    env = _chaos_env()
    m = str(tmp_path / "m.json")
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "LCHAOS", "--serve", jf, "--serve-poll", "0.5",
         "--fleet-batch", "2", "--fleet-lease-ttl", "3",
         "-w", str(tmp_path), "--metrics", m,
         "--launch", "2", "--launch-emulate",
         "--supervise-stall", "60", "--supervise-backoff", "0.2",
         "--inject-fault", "search.kill@rank=1:after=2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    table = {}
    for line in open(tmp_path / "ExaML_fleet.LCHAOS"):
        if line.startswith("#"):
            continue
        jid, _, _, _, _, lnl, status, _, _ = line.split()
        table[jid] = status
    assert len(table) == 8 and all(v == "done" for v in table.values())
    from examl_tpu.obs import ledger as L
    evs = L.read_events(str(tmp_path / "ledger.merged.jsonl"))
    # every job.done exactly once, across all ranks and attempts
    done = [e["job"] for e in evs if e["kind"] == "job.done"]
    assert sorted(done) == sorted(set(done)) and len(done) == 8
    # rank-1's in-flight leases AT DEATH = leases it acquired and
    # neither released nor completed before the supervisor's kill
    # verdict; ONLY those jobs may re-dispatch
    kill_ts = min(e["ts"] for e in evs
                  if e["kind"] == "supervisor.kill"
                  and e.get("reason") == "fleet-rank-death")
    r1_acq = {e["job"] for e in evs if e["kind"] == "lease.acquire"
              and e["rank"] == 1 and e["ts"] < kill_ts}
    r1_closed = ({e["job"] for e in evs
                  if e["kind"] == "lease.release"
                  and e["rank"] == 1 and e["ts"] < kill_ts}
                 | {e["job"] for e in evs if e["kind"] == "job.done"
                    and e["proc"] == 1 and e["ts"] < kill_ts})
    in_flight = r1_acq - r1_closed
    assert in_flight                        # the kill landed mid-batch
    started = [e["job"] for e in evs if e["kind"] == "job.start"]
    multi = {j for j in started if started.count(j) > 1}
    # only rank-1's leased in-flight jobs re-dispatched; every job
    # JOURNALED before the kill keeps exactly one job.start (zero
    # re-runs of journaled jobs)
    assert multi <= in_flight
    journaled_pre_kill = {e["job"] for e in evs
                          if e["kind"] == "job.done"
                          and e["ts"] < kill_ts}
    assert not (multi & journaled_pre_kill)
    # the lost leases were recovered by reap (survivor or restarted
    # rank) and every one of those jobs completed
    assert {e["job"] for e in evs if e["kind"] == "lease.reap"} \
        >= in_flight
    assert in_flight <= set(done)
    # the dead rank's lost jobs were re-served: reap or rank-1 restart
    snap = json.load(open(m))
    c = snap["counters"]
    assert c.get("resilience.gang.fleet_rank_deaths", 0) >= 1
    # rank death is NOT a run-level failure domain: no retry-consuming
    # exits, no tier pins
    assert not any(k.startswith("resilience.exits.") for k in c)
    assert snap["resilience"]["final_pins"] == {}
    kills = [e for e in evs if e["kind"] == "supervisor.kill"]
    assert any(e.get("reason") == "fleet-rank-death" for e in kills)
    assert not any(e.get("reason") == "rank-death" for e in kills)


@pytest.mark.slow
def test_leased_gang_deadline_rank_kill(tmp_path):
    """Slow variant: a REAL hang inside rank 0's batch blows the
    per-job deadline — the supervisor kills and restarts ONLY rank 0
    (fleet-job-stuck), the hang job quarantines via the exported hang
    attempts, and every other job completes exactly once.  The lease
    ttl deliberately exceeds the deadline so the HANG ladder (not a
    peer's reap — the non-slow chaos test covers that recovery) owns
    the job."""
    bf, jf = _leased_fixture(tmp_path, njobs=6)
    env = _chaos_env()
    m = str(tmp_path / "m.json")
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "LHANG", "--serve", jf, "--serve-poll", "0.5",
         "--fleet-batch", "2", "--fleet-lease-ttl", "30",
         "--fleet-job-deadline", "6", "--fleet-job-attempts", "2",
         "-w", str(tmp_path), "--metrics", m,
         "--launch", "2", "--launch-emulate",
         "--supervise-stall", "60", "--supervise-backoff", "0.2",
         "--inject-fault", "fleet.job.hang@rank=0:job=start0:attempt=*"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    from examl_tpu.obs import ledger as L
    evs = L.read_events(str(tmp_path / "ledger.merged.jsonl"))
    done = [e["job"] for e in evs if e["kind"] == "job.done"]
    assert sorted(done) == sorted(set(done))
    quar = [e["job"] for e in evs if e["kind"] == "job.quarantined"]
    assert quar.count("start0") == 1
    assert set(done) | set(quar) == {f"start{k}" for k in range(6)}
    snap = json.load(open(m))
    assert snap["counters"].get("resilience.fleet_job_stuck_kills",
                                0) >= 1


# -- CLI routing (satellite 1) -----------------------------------------------


def test_cli_fleet_nprocs_routes_to_leased_rank(tmp_path, monkeypatch):
    """--nprocs/--procid + a fleet mode no longer errors: the flags
    route into the leased rank contract (env vars the gang supervisor
    would export), no collective process group is joined, and the rank
    identity is restored after the run."""
    import examl_tpu.cli.main as cli
    captured = {}

    def fake_run(args, files):
        captured["nprocs"] = args.nprocs
        captured["procid"] = os.environ.get("EXAML_PROCID")
        captured["ranks"] = os.environ.get("EXAML_GANG_RANKS")
        captured["gang"] = args._gang
        return 0

    monkeypatch.setattr(cli, "_run", fake_run)
    monkeypatch.delenv("EXAML_PROCID", raising=False)
    monkeypatch.delenv("EXAML_GANG_RANKS", raising=False)
    rc = cli.main(["-s", "unused.binary", "-n", "RT", "-N", "2",
                   "--nprocs", "2", "--procid", "1",
                   "-w", str(tmp_path)])
    assert rc == 0
    assert captured["nprocs"] is None        # no collective join
    assert captured["procid"] == "1"
    assert captured["ranks"] == "2"
    assert captured["gang"] is not None      # leased-rank contract on
    assert "EXAML_PROCID" not in os.environ  # restored after the run


def test_cli_fleet_nprocs_requires_explicit_rank(tmp_path, capsys):
    """--nprocs N>1 without --procid must error: two ranks silently
    sharing slot 0 would steal each other's LIVE leases through the
    own-rank reclaim path."""
    import examl_tpu.cli.main as cli
    with pytest.raises(SystemExit):
        cli.main(["-s", "x.binary", "-n", "T", "-N", "2",
                  "--nprocs", "2", "-w", str(tmp_path)])
    assert "explicit id" in capsys.readouterr().err


def test_fresh_leased_run_clears_stale_base_journal(tmp_path):
    """A FRESH leased run reusing a run id must not absorb a previous
    (unleased) incarnation's base journal as finished work: the
    primary rank clears the base + beyond-world rank journals, which
    no rank of this world writes."""
    from examl_tpu.fleet.quarantine import journal_path
    from examl_tpu.fleet.seeds import derive
    bf, _ = _leased_fixture(tmp_path, njobs=1)
    stale = {"job_id": "start0", "kind": "start", "index": 0,
             "seed": derive(12345, "start", 0), "cycles": 1,
             "cycles_done": 1, "lnl": -1.25, "done": True,
             "failed": False, "attempts": 0}
    with open(journal_path(str(tmp_path), "RJ"), "w") as f:
        f.write(json.dumps(stale) + "\n")
    with open(journal_path(str(tmp_path), "RJ", 7), "w") as f:
        f.write(json.dumps(stale) + "\n")
    env = _chaos_env()
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "RJ", "-N", "1", "-p", "12345", "--nprocs", "2",
         "--procid", "0", "-w", str(tmp_path)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    row = [line.split() for line in open(tmp_path / "ExaML_fleet.RJ")
           if line.startswith("start0")][0]
    assert row[6] == "done" and row[5] != "-1.250000"  # re-evaluated
    assert not os.path.exists(journal_path(str(tmp_path), "RJ"))
    assert not os.path.exists(journal_path(str(tmp_path), "RJ", 7))


def test_cli_fleet_sev_error_names_issue(tmp_path, capsys):
    """-S under a fleet mode stays a PRECISE error: since the mesh
    fabric (ISSUE 17) it names the (S, T) combination that cannot
    compose — the SEV pool holds one arena per instance."""
    import examl_tpu.cli.main as cli
    with pytest.raises(SystemExit):
        cli.main(["-s", "x.binary", "-n", "T", "-N", "2", "-S",
                  "-w", str(tmp_path)])
    err = capsys.readouterr().err
    assert "(S=1, T=J)" in err and "SEV" in err
