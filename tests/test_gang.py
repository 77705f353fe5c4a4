"""Gang supervision chaos matrix (`--launch N`).

Rank-level failure domains for multi-process runs: rank death mid-
search, single-rank straggler vs collective wedge, two-phase
coordinated checkpoints (publish only when every rank staged), elastic
2->1 resume — all injected deterministically on CPU.  The e2e tier uses
the cheap EXAML_PROCID-style gang EMULATION (`--launch-emulate`: N real
OS processes honoring the rank contract, no jax process group — this
container's jaxlib has no multi-process CPU collectives); one real
`--nprocs 2` gang rides in the slow tier.

Stall tests use REAL hangs (a child that sleeps forever), never beat
suppression: a suppressed-beat child can still finish inside the stall
window and race the watcher (the chaos timing pitfall).
"""

import glob
import gzip
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from tests.conftest import correlated_dna

from examl_tpu.resilience import exitcause, faults, heartbeat
from examl_tpu.resilience import supervisor as sup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Same tolerance rationale as tests/test_resilience.py.
LNL_TOL = 0.5


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    monkeypatch.delenv(faults.ATTEMPT_VAR, raising=False)
    monkeypatch.delenv(heartbeat.ENV_VAR, raising=False)
    monkeypatch.delenv(heartbeat.PROCID_VAR, raising=False)
    monkeypatch.delenv(heartbeat.GANG_VAR, raising=False)
    faults.reset()
    heartbeat.reset()
    yield
    faults.reset()
    heartbeat.reset()


# -- rank-targeted fault grammar --------------------------------------------


def test_rank_fault_grammar_parses():
    spec = faults.parse_spec("search.kill@rank=1:after=12")["search.kill"]
    assert spec.rank == 1 and spec.after == 12
    # field form is equivalent
    spec = faults.parse_spec("engine.dispatch:rank=2:after=3")[
        "engine.dispatch"]
    assert spec.rank == 2 and spec.after == 3
    # untargeted specs fire on every rank
    assert faults.parse_spec("search.kill")["search.kill"].rank is None
    with pytest.raises(ValueError, match="rank qualifier"):
        faults.parse_spec("search.kill@procid=1")
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.parse_spec("no.such@rank=1")
    # two specs for one point would silently arm a different scenario
    with pytest.raises(ValueError, match="duplicate spec"):
        faults.parse_spec("search.kill@rank=0,search.kill@rank=1")


def test_rank_fault_gating(monkeypatch):
    """A rank-targeted spec is INERT in non-target ranks and must not
    tick their hit counters — `after=N` addresses rank R's own
    iteration clock."""
    monkeypatch.setenv(faults.ENV_VAR, "engine.dispatch@rank=1:after=2")
    faults.reset()
    # rank 0 (default): never fires, never counts
    for _ in range(5):
        assert not faults.fire("engine.dispatch")
    monkeypatch.setenv(heartbeat.PROCID_VAR, "1")
    faults.reset()
    assert not faults.fire("engine.dispatch")      # hit 1 of rank 1
    with pytest.raises(faults.FaultInjected):
        faults.fire("engine.dispatch")             # hit 2 fires


# -- heartbeat: torn-read safety + gang aggregation -------------------------


def test_heartbeat_atomic_publish_under_interleaved_reader(tmp_path,
                                                           monkeypatch):
    """Satellite: the gang watcher polls heartbeat files from another
    process while ranks rewrite them — every read must see a COMPLETE
    record (tmp + os.replace) or nothing, never torn JSON."""
    import threading
    hb = str(tmp_path / "hb.json")
    monkeypatch.setattr(heartbeat, "MIN_INTERVAL", 0.0)  # every beat writes
    heartbeat.install(hb)
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            rec = heartbeat.read(hb)
            if rec is not None and not (
                    {"t", "pid", "seq", "state", "counters"} <= set(rec)):
                torn.append(rec)

    th = threading.Thread(target=reader)
    th.start()
    try:
        for i in range(400):
            heartbeat.beat(f"S{i}")
    finally:
        stop.set()
        th.join()
    assert not torn, f"torn heartbeat reads: {torn[:3]}"
    rec = heartbeat.read(hb)
    assert rec["seq"] == 400 and rec["state"] == "S399"
    assert not glob.glob(hb + ".tmp.*")        # no leaked tmp files


def test_gang_heartbeat_helpers(tmp_path, monkeypatch):
    base = str(tmp_path / "hb.json")
    assert heartbeat.rank_path(base, 0) == base
    assert heartbeat.rank_path(base, 2) == base + ".p2"
    assert heartbeat.gang_paths(base, 2) == [base, base + ".p1"]
    open(base, "w").write("{}")
    ages = heartbeat.gang_ages(base, 2)
    assert ages[0] is not None and ages[1] is None
    monkeypatch.setenv(heartbeat.GANG_VAR, "3")
    monkeypatch.setenv(heartbeat.PROCID_VAR, "2")
    assert heartbeat.env_gang_size() == 3 and heartbeat.env_rank() == 2


def test_install_heartbeat_suffixes_emulated_rank(tmp_path, monkeypatch):
    """parallel/launch.install_heartbeat follows the gang rank contract
    without a jax process group (`--launch-emulate`)."""
    from argparse import Namespace
    from examl_tpu.parallel.launch import install_heartbeat
    base = str(tmp_path / "hb.json")
    monkeypatch.setenv(heartbeat.ENV_VAR, base)
    monkeypatch.setenv(heartbeat.GANG_VAR, "2")
    monkeypatch.setenv(heartbeat.PROCID_VAR, "1")
    args = Namespace(nprocs=None, coordinator=None)
    assert install_heartbeat(args) == base + ".p1"
    monkeypatch.setenv(heartbeat.PROCID_VAR, "0")
    heartbeat.reset()
    assert install_heartbeat(args) == base


# -- backoff jitter (satellite) ---------------------------------------------


def test_backoff_jitter_deterministic_bounded_capped():
    seq = [sup.backoff_delay(2.0, r, key="RUN") for r in range(1, 8)]
    # deterministic: same (key, retry) -> same delay
    assert seq == [sup.backoff_delay(2.0, r, key="RUN")
                   for r in range(1, 8)]
    # bounded: within [raw/2, raw] of the exponential ladder, capped
    for r, d in enumerate(seq, start=1):
        raw = min(60.0, 2.0 * 2 ** (r - 1))
        assert raw / 2.0 <= d <= raw
    assert all(d <= 60.0 for d in seq)
    # distinct run ids decorrelate (no restart storms)
    other = [sup.backoff_delay(2.0, r, key="RUN2") for r in range(1, 8)]
    assert other != seq


# -- gang watcher verdicts (pure) -------------------------------------------


def test_classify_stall_verdicts():
    COLL, STRAG = (exitcause.CAUSE_COLLECTIVE_WEDGE,
                   exitcause.CAUSE_STRAGGLER)
    assert sup.classify_stall([31.0, 33.0], 30.0) == COLL
    assert sup.classify_stall([31.0], 30.0) == COLL   # gang of one
    assert sup.classify_stall([31.0, 2.0], 30.0) == STRAG
    # ambiguous: the "fresh" rank is itself aging past stall/2 — a
    # collective wedge reaches ranks an allreduce apart, keep watching
    assert sup.classify_stall([31.0, 20.0], 30.0) is None
    assert sup.classify_stall([5.0, 2.0], 30.0) is None
    assert sup.classify_stall([], 30.0) is None
    assert COLL in exitcause.TIER_SUSPECT       # wedges degrade the tier
    assert STRAG not in exitcause.TIER_SUSPECT  # stragglers do not
    assert COLL in exitcause.RETRYABLE and STRAG in exitcause.RETRYABLE


def test_child_argv_strips_launch_flags():
    argv = ["-s", "a.bin", "-n", "R", "--launch", "2", "--launch-emulate",
            "--launch-min-ranks", "1", "--supervise-stall", "20",
            "--inject-fault", "search.kill@rank=1:after=3"]
    got = sup.child_argv(argv)
    for tok in ("--launch", "--launch-emulate", "--launch-min-ranks"):
        assert tok not in got
    assert "2" not in got[:4]
    assert "--inject-fault" in got        # passes through to the ranks


def test_stage_files_invisible_to_supervisor_glob(tmp_path):
    """The jax-free supervisor's -R decision keys off PUBLISHED
    checkpoints only: staged-but-uncommitted cycles must not count."""
    from examl_tpu.search.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), "XY", gang_rank=0, gang_size=2)
    for p in (mgr._stage_blob(0), mgr._stage_marker(0, 0),
              mgr._stage_marker(0, 1)):
        open(p, "w").write("x")
    assert sup.checkpoint_glob(str(tmp_path), "XY") == []
    open(mgr.path_for(0), "w").write("x")
    assert sup.checkpoint_glob(str(tmp_path), "XY") == [mgr.path_for(0)]


# -- two-phase coordinated checkpoints (unit) -------------------------------


def _gang_pair(tmp_path, run_id="TP"):
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data = correlated_dna(8, 80, seed=2)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    mgr0 = CheckpointManager(str(tmp_path), run_id, gang_rank=0,
                             gang_size=2)
    mgr1 = CheckpointManager(str(tmp_path), run_id, gang_rank=1,
                             gang_size=2)
    return data, inst, tree, mgr0, mgr1


def test_two_phase_publishes_only_when_all_ranks_staged(tmp_path):
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    obs.reset()
    data, inst, tree, mgr0, mgr1 = _gang_pair(tmp_path)
    mgr0.write("FAST_SPRS", {"mark": 0}, inst, tree)
    # rank 1 has not staged cycle 0: NOTHING published yet
    assert not os.path.exists(mgr0.path_for(0))
    assert os.path.exists(mgr0._stage_blob(0))
    assert os.path.exists(mgr0._stage_marker(0, 0))
    # the last rank to stage performs the publish
    mgr1.write("FAST_SPRS", {"mark": 0}, inst, tree)
    assert os.path.exists(mgr0.path_for(0))
    assert not glob.glob(mgr0._stage_pattern())     # markers swept
    assert obs.counter("checkpoint.gang_publishes") == 1
    inst2 = PhyloInstance(data)
    resume = CheckpointManager(str(tmp_path), "TP").restore(
        inst2, inst2.random_tree(seed=9))
    assert resume["extras"]["mark"] == 0


def test_two_phase_partial_cycle_gc_falls_back(tmp_path):
    """THE two-phase acceptance: a gang killed mid-cycle (rank 0 staged
    cycle 1, rank 1 never reached it) must restore the previous
    COMPLETE cycle, with the evidence in
    `checkpoint.partial_cycles_gced`."""
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    obs.reset()
    data, inst, tree, mgr0, mgr1 = _gang_pair(tmp_path)
    mgr0.write("FAST_SPRS", {"mark": 0}, inst, tree)
    mgr1.write("FAST_SPRS", {"mark": 0}, inst, tree)   # cycle 0 publishes
    mgr0.write("FAST_SPRS", {"mark": 1}, inst, tree)   # cycle 1: rank 0
    assert not os.path.exists(mgr0.path_for(1))        # only — gang dies
    inst2 = PhyloInstance(data)
    resume = CheckpointManager(str(tmp_path), "TP").restore(
        inst2, inst2.random_tree(seed=9))
    assert resume["extras"]["mark"] == 0               # complete cycle
    assert obs.counter("checkpoint.partial_cycles_gced") == 1
    assert not glob.glob(mgr0._stage_pattern())        # leftovers gone


def test_two_phase_stale_attempt_markers_never_complete_a_cycle(
        tmp_path, monkeypatch):
    """A dead attempt's stage markers are attempt-stamped: the NEW
    attempt's rank 0 staging the same cycle number must not publish
    against the old attempt's attest."""
    _, inst, tree, mgr0, mgr1 = _gang_pair(tmp_path)
    mgr1.write("FAST_SPRS", {"mark": 0}, inst, tree)   # attempt-0 marker
    monkeypatch.setenv(faults.ATTEMPT_VAR, "1")        # gang restarted
    mgr0.write("FAST_SPRS", {"mark": 0}, inst, tree)
    assert not os.path.exists(mgr0.path_for(0))        # NOT published
    # rank 1 of the new attempt re-stages; now the cycle commits
    mgr1b = type(mgr1)(str(tmp_path), "TP", gang_rank=1, gang_size=2)
    mgr1b.write("FAST_SPRS", {"mark": 0}, inst, tree)
    assert os.path.exists(mgr0.path_for(0))


def test_checkpoint_publish_fault_seam(tmp_path, monkeypatch):
    """`checkpoint.publish` fires BETWEEN complete staging and the
    publish rename — the gang-dies-between-phases injection."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data, inst, tree, mgr0, mgr1 = _gang_pair(tmp_path)
    mgr0.write("FAST_SPRS", {"mark": 0}, inst, tree)
    monkeypatch.setenv(faults.ENV_VAR, "checkpoint.publish:after=1")
    faults.reset()
    with pytest.raises(faults.FaultInjected):
        mgr1.write("FAST_SPRS", {"mark": 0}, inst, tree)
    assert not os.path.exists(mgr0.path_for(0))        # never published
    assert os.path.exists(mgr0._stage_blob(0))         # staged, stranded
    monkeypatch.delenv(faults.ENV_VAR)
    faults.reset()
    inst2 = PhyloInstance(data)
    assert CheckpointManager(str(tmp_path), "TP").restore(
        inst2, inst2.random_tree(seed=9)) is None      # GC'd, nothing left
    assert not glob.glob(mgr0._stage_pattern())


# -- elastic restore (unit) -------------------------------------------------


def test_elastic_restore_permits_nprocs_change(tmp_path, monkeypatch):
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    obs.reset()
    data = correlated_dna(8, 80, seed=2)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    monkeypatch.setenv(heartbeat.GANG_VAR, "2")        # written at world 2
    CheckpointManager(str(tmp_path), "EL").write(
        "FAST_SPRS", {"mark": 0}, inst, tree)
    monkeypatch.delenv(heartbeat.GANG_VAR)             # restored at world 1
    inst2 = PhyloInstance(data)
    resume = CheckpointManager(str(tmp_path), "EL").restore(
        inst2, inst2.random_tree(seed=9))
    assert resume["extras"]["mark"] == 0
    assert obs.counter("checkpoint.elastic_restores") == 1


def _tamper(path, fn):
    with gzip.open(path, "rt") as f:
        blob = json.load(f)
    fn(blob)
    with gzip.open(path, "wt") as f:
        json.dump(blob, f)


def test_elastic_restore_still_hard_fails_real_mismatch(tmp_path,
                                                        monkeypatch):
    """Only the allowlisted world-size key may differ: any other
    fingerprint section — and a genuinely SLICED PSR rate-state
    section — still hard-fails."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data = correlated_dna(8, 80, seed=2)
    inst = PhyloInstance(data, rate_model="PSR")
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    monkeypatch.setenv(heartbeat.GANG_VAR, "2")
    mgr = CheckpointManager(str(tmp_path), "EL2")
    path = mgr.write("FAST_SPRS", {"mark": 0}, inst, tree)
    monkeypatch.delenv(heartbeat.GANG_VAR)

    with gzip.open(path, "rt") as f:
        true_ncat = json.load(f)["fingerprint"]["ncat"]

    # non-elastic fingerprint key mismatch: operator error, hard fail
    _tamper(path, lambda b: b["fingerprint"].update(ncat=true_ncat + 7))
    inst2 = PhyloInstance(data, rate_model="PSR")
    with pytest.raises(ValueError, match="different run configuration"):
        CheckpointManager(str(tmp_path), "EL2").restore(
            inst2, inst2.random_tree(seed=9), path=path)

    # a sliced (wrong-length) PSR rate-category section: hard fail even
    # though the fingerprint (incl. the allowlisted nprocs) is fine
    def slice_psr(b):
        b["fingerprint"]["ncat"] = true_ncat
        b["models"][0]["rate_category"] = \
            b["models"][0]["rate_category"][: 10]
    _tamper(path, slice_psr)
    inst3 = PhyloInstance(data, rate_model="PSR")
    with pytest.raises(ValueError, match="cannot restore elastically"):
        CheckpointManager(str(tmp_path), "EL2").restore(
            inst3, inst3.random_tree(seed=9), path=path)


# -- bank satellite: mesh-sharded in-process first calls --------------------


def test_inprocess_sharded_first_call_counter():
    """ROADMAP §4 observability: in a banked multi-process run a
    mesh-sharded family's in-process first compile counts
    `engine.first_calls.inprocess_sharded`, not the enumeration-gap
    acceptance counter `unbanked`."""
    from examl_tpu import obs
    from examl_tpu.ops import bank
    from examl_tpu.ops.engine import LikelihoodEngine
    obs.reset()
    bank.reset()
    try:
        bank._STATE["active"] = True
        bank._STATE["sharded_residual"] = True
        bank._STATE["enumerated"] = {"fast"}
        assert bank.sharded_residual("fast")
        wrapped = LikelihoodEngine._guard_first_call(
            None, lambda: 42, "fast")
        assert wrapped() == 42
        c = obs.snapshot_counters()
        assert c["engine.first_calls.inprocess_sharded"] == 1
        assert c["engine.first_calls.inprocess_sharded.fast"] == 1
        assert "engine.first_calls.unbanked" not in c
        # a family the enumeration MISSED is a genuine gap: it must
        # still trip `unbanked` even in a multi-process run
        assert not bank.sharded_residual("mystery")
        wrapped2 = LikelihoodEngine._guard_first_call(
            None, lambda: 7, "mystery")
        assert wrapped2() == 7
        c = obs.snapshot_counters()
        assert c["engine.first_calls.unbanked"] == 1
        assert c["engine.first_calls.unbanked.mystery"] == 1
    finally:
        bank.reset()
    assert not bank.sharded_residual()          # reset clears the flag


# -- gang watcher over real (stub) processes --------------------------------

_STUB = """
import os, sys, time
sys.path.insert(0, {repo!r})
from examl_tpu.resilience import heartbeat
rank = int(os.environ.get("EXAML_PROCID", "0"))
attempt = int(os.environ.get("EXAML_RESTART_COUNT", "0"))
heartbeat.install(heartbeat.rank_path(os.environ["EXAML_HEARTBEAT_FILE"],
                                      rank))
mode = sys.argv[1]
if attempt > 0:                     # retries run clean and finish
    for _ in range(4):
        heartbeat.beat("CLEAN"); time.sleep(0.1)
    sys.exit(0)
t0 = time.time()
hang_me = (mode == "collective") or rank == 1
while time.time() - t0 < 1.0 or not hang_me:
    heartbeat.beat("STUB"); time.sleep(0.2)
time.sleep(600)                     # a REAL hang: cannot finish early
"""


class _StubGang(sup.GangSupervisor):
    """GangSupervisor whose ranks are tiny stdlib stubs: beats are
    real files from real processes, hangs are real sleeps — only the
    search itself is elided, so the watcher/classify/restart loop runs
    at full fidelity in seconds."""

    def __init__(self, mode, **kw):
        super().__init__([], **kw)
        self._mode = mode

    def _spawn_gang(self, restarts_total):
        self._last_argv = []
        for path in heartbeat.gang_paths(self.hb_path, self._max_world):
            try:
                os.unlink(path)
            except OSError:
                pass
        children = []
        for k in range(self.world):
            env = dict(os.environ,
                       EXAML_HEARTBEAT_FILE=self.hb_path,
                       EXAML_RESTART_COUNT=str(restarts_total))
            env[heartbeat.PROCID_VAR] = str(k)
            env[heartbeat.GANG_VAR] = str(self.world)
            children.append(subprocess.Popen(
                [sys.executable, "-c", _STUB.format(repo=REPO),
                 self._mode],
                env=env, start_new_session=True))
        self._children = children
        return children


def test_gang_collective_wedge_detected_and_classified(tmp_path):
    """All ranks' beats going stale together is a COLLECTIVE WEDGE —
    hang-killed, classified `collective-wedge` (not crash), tier
    ladder escalated; the retry completes."""
    gang = _StubGang("collective", workdir=str(tmp_path), run_id="CW",
                     ranks=2, emulate=True, backoff=0.05,
                     stall_timeout=2.5, log=lambda m: None)
    assert gang.run() == 0
    att = gang.attempts
    assert att[0]["cause"] == exitcause.CAUSE_COLLECTIVE_WEDGE
    assert att[-1]["cause"] == "ok"
    assert gang.counters["resilience.gang.collective_wedges"] == 1
    assert gang.counters["resilience.heartbeat_stalls"] == 1
    assert gang.degrade_level >= 1              # wedge => tier suspect
    assert "resilience.gang.straggler_kills" not in gang.counters


def test_gang_straggler_distinguished_from_collective(tmp_path):
    """One rank stale while its peer actively beats is a STRAGGLER
    kill: the guilty rank is named and the tier ladder does NOT
    escalate (presumed environmental)."""
    gang = _StubGang("straggler", workdir=str(tmp_path), run_id="ST",
                     ranks=2, emulate=True, backoff=0.05,
                     stall_timeout=2.5, log=lambda m: None)
    assert gang.run() == 0
    att = gang.attempts
    assert att[0]["cause"] == exitcause.CAUSE_STRAGGLER
    assert att[0]["rank"] == 1                  # the stale rank, named
    assert att[0]["rank_exits"]["r0"] == "gang-killed"
    assert att[-1]["cause"] == "ok"
    assert gang.counters["resilience.gang.straggler_kills"] == 1
    assert gang.degrade_level == 0
    assert "resilience.gang.collective_wedges" not in gang.counters


# -- e2e gang chaos (emulated ranks, real CLI searches) ---------------------


def _final_lnl(info_path: str) -> float:
    import re
    text = open(info_path).read()
    m = re.findall(r"Likelihood of best tree: (-[\d.]+)", text)
    assert m, text[-2000:]
    return float(m[-1])


@pytest.fixture(scope="module")
def gang_run(tmp_path_factory):
    """Tiny alignment + start tree + the UNINTERRUPTED single-process
    run's final lnL (gang emulation ranks compute the identical full
    program, so this is the parity target for every gang outcome,
    including the elastic 1-rank finish)."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.bytefile import write_bytefile
    root = tmp_path_factory.mktemp("gang")
    data = correlated_dna(8, 120, seed=7)
    bf = str(root / "a.binary")
    write_bytefile(bf, data)
    inst = PhyloInstance(data)
    t = inst.random_tree(seed=3)
    tf = str(root / "start.nwk")
    open(tf, "w").write(t.to_newick(data.taxon_names))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    for var in (faults.ENV_VAR, heartbeat.ENV_VAR, heartbeat.GANG_VAR,
                heartbeat.PROCID_VAR):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "BASE", "-t", tf, "-f", "d", "-i", "5", "-w",
         str(root / "base"), "--single-device"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stdout + out.stderr
    lnl = _final_lnl(str(root / "base" / "ExaML_info.BASE"))
    return {"root": root, "bf": bf, "tf": tf, "lnl": lnl, "env": env}


def _gang_cli(gang_run, name, inject, ranks=2, retries=3, stall=0.0,
              extra=None):
    from examl_tpu.cli.main import main
    root = gang_run["root"]
    w = str(root / name)
    m = str(root / f"{name}.metrics.json")
    argv = ["-s", gang_run["bf"], "-n", name, "-t", gang_run["tf"],
            "-f", "d", "-i", "5", "-w", w, "--single-device",
            "--launch", str(ranks), "--launch-emulate",
            "--supervise-backoff", "0.2",
            "--supervise-retries", str(retries),
            "--supervise-stall", str(stall), "--metrics", m]
    for spec in inject:
        argv += ["--inject-fault", spec]
    argv += extra or []
    rc = main(argv)
    snap = json.load(open(m)) if os.path.exists(m) else {}
    return rc, w, snap


def test_e2e_rank_death_gang_killed_coordinated_resume(gang_run,
                                                       monkeypatch):
    """THE gang acceptance: SIGKILL of one rank mid-FAST_SPRS under
    `--launch 2` kills the whole gang (lockstep), and the restart
    resumes BOTH ranks from a coordinated (two-phase-published)
    checkpoint, reaching the uninterrupted run's final lnL — at most
    the in-flight cycle is lost."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, w, snap = _gang_cli(gang_run, "GKILL",
                            ["search.kill@rank=1:after=12"])
    assert rc == 0
    c = snap["counters"]
    assert c["resilience.gang.rank_deaths"] == 1
    assert c["resilience.restarts"] >= 1
    # Two-phase commit evidence: `checkpoint.gang_publishes` is counted
    # in the process of whichever rank stages LAST and wins the publish
    # rename — when that is rank 1 (a scheduling race), the counter
    # lives in rank 1's registry, which the rank-0-only --metrics
    # snapshot never persists.  The rank-COMPLETE record is the merged
    # ledger: every rank's `checkpoint.publish` events survive there.
    pubs = c.get("checkpoint.gang_publishes", 0)
    if not pubs:
        from examl_tpu.obs import ledger as _ledger_mod
        merged = os.path.join(str(gang_run["root"]), "ledger.merged.jsonl")
        pubs = sum(1 for e in _ledger_mod.read_events(merged)
                   if e["kind"] == "checkpoint.publish")
    assert pubs >= 1                               # two-phase commits
    att = snap["resilience"]["attempts"]
    assert att[0]["cause"] == "oom-kill" and att[0]["rank"] == 1
    assert att[0]["rank_exits"]["r0"] == "gang-killed"
    assert att[-1]["cause"] == "ok" and att[-1]["resumed"]
    assert att[-1]["world"] == 2                   # no shrink needed
    info = open(os.path.join(w, "ExaML_info.GKILL")).read()
    assert "restart from state" in info            # resumed, not redone
    assert _final_lnl(os.path.join(w, "ExaML_info.GKILL")) \
        == pytest.approx(gang_run["lnl"], abs=LNL_TOL)


@pytest.mark.slow          # ~40 s: tier-1 keeps the rank-death coordinated
                           # resume e2e; elastic shrink stays covered by
                           # the stub-children unit tests (PR8 audit)
def test_e2e_elastic_shrink_to_one_rank(gang_run, monkeypatch):
    """Elastic resume: a gang that loses rank 1 on every attempt
    degrades to 1 rank after ELASTIC_CONSECUTIVE_DEATHS and FINISHES,
    with the final lnL matching the uninterrupted 1-process run — the
    checkpoint written at world 2 restores at world 1
    (`checkpoint.elastic_restores`)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, w, snap = _gang_cli(gang_run, "ELAS",
                            ["search.kill@rank=1:attempt=*:after=12"])
    assert rc == 0
    c = snap["counters"]
    assert c["resilience.gang.rank_deaths"] == 2
    assert c["resilience.gang.elastic_resumes"] == 1
    assert c["checkpoint.elastic_restores"] >= 1   # world 2 -> world 1
    att = snap["resilience"]["attempts"]
    assert att[-1]["cause"] == "ok" and att[-1]["world"] == 1
    assert snap["resilience"]["gang"]["ranks_final"] == 1
    assert _final_lnl(os.path.join(w, "ExaML_info.ELAS")) \
        == pytest.approx(gang_run["lnl"], abs=LNL_TOL)


# -- real distributed gang (slow) -------------------------------------------


@pytest.mark.slow
def test_e2e_real_two_process_gang(gang_run):
    """One REAL `--launch 2` gang (jax.distributed process group over a
    local coordinator).  Skips on jaxlib builds without multi-process
    CPU collectives (this container's known seed limit — the emulated
    matrix above covers the supervision machinery there)."""
    root = gang_run["root"]
    w = str(root / "REAL2")
    env = dict(gang_run["env"])
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = \
        (f"{flags} --xla_force_host_platform_device_count=2").strip()
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s",
         gang_run["bf"], "-n", "REAL2", "-t", gang_run["tf"], "-f", "d",
         "-i", "5", "-w", w, "--launch", "2", "--supervise-retries", "0",
         "--supervise-stall", "0", "--supervise-backoff", "0.2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        blob = out.stdout + out.stderr
        for info in glob.glob(os.path.join(w, "**", "ExaML_info.*"),
                              recursive=True):
            blob += open(info).read()
        if "Multiprocess computations" in blob \
                or "not implemented" in blob.lower():
            pytest.skip("jaxlib: no multi-process collectives on this "
                        "backend")
        pytest.fail(f"real gang failed:\n{blob[-4000:]}")
    assert _final_lnl(os.path.join(w, "ExaML_info.REAL2")) \
        == pytest.approx(gang_run["lnl"], abs=LNL_TOL)
