"""Fault injection + self-healing supervision: the chaos matrix.

Every failure mode that has actually cost an accelerator window —
mid-search SIGKILL, dispatch/collective wedge (heartbeat stall),
checkpoint-write crash, non-finite lnL, SIGTERM preemption, corrupt
checkpoint at restart — is injected deterministically on CPU
(resilience/faults.py) and must be survived: the supervised run resumes
and reaches the uninterrupted run's final likelihood, with the evidence
in the obs counters (`resilience.restarts`,
`resilience.heartbeat_stalls`, `engine.nonfinite_retries`).
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

from examl_tpu.resilience import exitcause, faults, heartbeat, preempt
from examl_tpu.resilience import supervisor as sup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Final-lnL agreement tolerance for resumed vs uninterrupted runs: the
# search is deterministic on CPU, but a resume re-enters the cycle
# machinery mid-stream; NUMERICS.md puts f32 lnL noise far below the
# search's own 0.01 epsilon, and the existing restart-parity test
# (test_checkpoint.py) accepts 0.5 lnL.
LNL_TOL = 0.5


@pytest.fixture(autouse=True)
def _clean_fault_state(monkeypatch):
    """Every test starts with an empty fault registry and no leaked
    EXAML_FAULTS / heartbeat / restart-count environment."""
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    monkeypatch.delenv(faults.ATTEMPT_VAR, raising=False)
    monkeypatch.delenv(heartbeat.ENV_VAR, raising=False)
    faults.reset()
    heartbeat.reset()
    yield
    faults.reset()
    heartbeat.reset()


# -- fault spec parsing / arming --------------------------------------------


def test_fault_spec_parsing():
    specs = faults.parse_spec(
        "search.kill:after=3:signal=TERM,engine.nonfinite:after=2:"
        "attempt=1,compile.hang:hang=7,checkpoint.write")
    assert specs["search.kill"].after == 3
    assert specs["search.kill"].action == "signal"
    assert specs["search.kill"].arg == "TERM"
    assert specs["engine.nonfinite"].attempt == 1
    assert specs["engine.nonfinite"].action == "flag"
    assert specs["compile.hang"].action == "hang"
    assert specs["compile.hang"].arg == 7.0
    assert specs["checkpoint.write"].action == "raise"
    # default actions
    assert faults.parse_spec("search.kill")["search.kill"].arg == "KILL"
    assert faults.parse_spec("bank.worker")["bank.worker"].action == "signal"
    # attempt=* fires on every attempt
    assert faults.parse_spec("search.kill:attempt=*")[
        "search.kill"].attempt is None


def test_fault_spec_rejects_unknown():
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.parse_spec("no.such.point")
    with pytest.raises(ValueError, match="unknown fault field"):
        faults.parse_spec("search.kill:frobnicate=1")


def test_fault_after_counting(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "engine.dispatch:after=3")
    faults.reset()
    assert not faults.fire("engine.dispatch")
    assert not faults.fire("engine.dispatch")
    with pytest.raises(faults.FaultInjected):
        faults.fire("engine.dispatch")
    # non-sticky points fire exactly once
    assert not faults.fire("engine.dispatch")


def test_fault_attempt_gating(monkeypatch):
    """attempt=K specs fire only when EXAML_RESTART_COUNT == K — the
    mechanism that lets a supervised chaos run crash once and then
    complete on the retry."""
    monkeypatch.setenv(faults.ENV_VAR, "engine.dispatch:attempt=1")
    faults.reset()
    assert not faults.fire("engine.dispatch")      # attempt 0: inert
    monkeypatch.setenv(faults.ATTEMPT_VAR, "1")
    with pytest.raises(faults.FaultInjected):
        faults.fire("engine.dispatch")


def test_heartbeat_stall_fault_is_sticky(tmp_path, monkeypatch):
    hb = str(tmp_path / "hb.json")
    monkeypatch.setenv(faults.ENV_VAR, "heartbeat.stall:after=3")
    faults.reset()
    heartbeat.install(hb)
    heartbeat.beat("A")
    heartbeat.beat("B")
    assert heartbeat.read(hb)["state"] == "A"      # rate-limited: 1 write
    for _ in range(5):
        heartbeat.beat("C")                        # stalled from beat 3 on
    rec = heartbeat.read(hb)
    assert rec["state"] == "A" and rec["seq"] == 1
    assert rec["pid"] == os.getpid()
    assert "counters" in rec
    assert heartbeat.age(hb) is not None
    assert heartbeat.age(str(tmp_path / "missing")) is None


# -- exit-cause taxonomy (the deduped _exit_desc) ---------------------------


def test_exitcause_taxonomy():
    assert exitcause.exit_desc(-int(signal.SIGILL)) == "(signal SIGILL)"
    assert exitcause.exit_desc(3) == "(returncode 3)"
    assert exitcause.exit_desc(None) == "(still running)"
    assert exitcause.exit_desc(None, none_desc="(hang-killed)") \
        == "(hang-killed)"
    assert exitcause.classify(0) == "ok"
    assert exitcause.classify(75) == "preempt"
    assert exitcause.classify(2) == "usage"
    assert exitcause.classify(1) == "error"
    assert exitcause.classify(-int(signal.SIGILL)) == "sigill"
    assert exitcause.classify(-int(signal.SIGKILL)) == "oom-kill"
    assert exitcause.classify(-int(signal.SIGSEGV)) == "crash"
    # the watcher's own kill outranks the raw signal
    assert exitcause.classify(-int(signal.SIGKILL), hang_killed=True) \
        == "hang-kill"
    assert "hang-kill" in exitcause.TIER_SUSPECT
    assert "usage" not in exitcause.RETRYABLE


def test_exit_desc_shared_by_bank():
    """One taxonomy (satellite): the bank delegates to
    resilience/exitcause.py, with its own rc-None wording."""
    from examl_tpu.ops import bank
    assert bank._exit_desc(-int(signal.SIGILL)) == "(signal SIGILL)"
    assert bank._exit_desc(None) == "(still running)"
    assert exitcause.exit_desc(None, "(hang-killed)") == "(hang-killed)"


def test_every_pinned_variable_is_one_the_package_reads():
    """DEGRADE_LADDER is a hand copy of bank.FALLBACK_ENV (the jax-free
    parent cannot import it): every variable a rung or an escape hatch
    pins must still be READ (`.get("X"`, `getenv("X"`, `environ["X"]`)
    somewhere under examl_tpu/, or a pin lands on a deleted switch."""
    import re
    from examl_tpu.ops import bank
    ladder = {var for rung in sup.DEGRADE_LADDER for var in rung}
    hatches = {hatch[0][0] for hatch in bank.FALLBACK_ENV.values()}
    assert ladder and ladder <= hatches    # the copy pins nothing new
    text = ""
    for root, _dirs, files in os.walk(os.path.join(REPO, "examl_tpu")):
        for name in files:
            if name.endswith(".py") and name != "supervisor.py":
                with open(os.path.join(root, name)) as f:
                    text += f.read()
    for var in sorted(ladder | hatches):
        assert re.search(r'(\.get|getenv)\(\s*"%s"|environ\[\s*"%s"\s*\]'
                         % (var, var), text), \
            f"{var} is pinned but nothing under examl_tpu/ reads it"


# -- supervisor plumbing (jax-free paths) -----------------------------------


def test_child_argv_strips_supervisor_flags():
    argv = ["-s", "a.bin", "-n", "R", "--supervise", "--supervise-retries",
            "5", "--supervise-stall=60", "--inject-fault",
            "search.kill:after=3", "-w", "out"]
    got = sup.child_argv(argv)
    assert "--supervise" not in got
    assert "--supervise-retries" not in got and "5" not in got
    assert "--supervise-stall=60" not in got
    # --inject-fault passes THROUGH: the child arms the registry
    assert "--inject-fault" in got and "search.kill:after=3" in got
    assert got[:4] == ["-s", "a.bin", "-n", "R"]


def test_checkpoint_glob_matches_manager_naming(tmp_path):
    """The supervisor's jax-free checkpoint glob must track the
    CheckpointManager file naming (it cannot import it — jax)."""
    from examl_tpu.search.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), "XY")
    with open(mgr.path_for(0), "w") as f:
        f.write("x")
    assert sup.checkpoint_glob(str(tmp_path), "XY") == [mgr.path_for(0)]
    assert sup.checkpoint_glob(str(tmp_path), "other") == []


def test_degrade_ladder_mirrors_bank_escape_hatches():
    from examl_tpu.ops.bank import FALLBACK_ENV
    ladder_vars = set().union(*(d.keys() for d in sup.DEGRADE_LADDER))
    bank_vars = {var for (var, _), _ in FALLBACK_ENV.values()}
    assert bank_vars <= ladder_vars          # scan tier is the floor


# -- preemption flag --------------------------------------------------------


def test_preempt_flag_and_emergency_checkpoint_site():
    assert preempt.requested() is None
    installed = preempt.install()
    assert installed                           # pytest runs on main thread
    try:
        preempt.check_after_checkpoint()       # no signal: no-op
        signal.raise_signal(signal.SIGTERM)
        assert preempt.requested() == "SIGTERM"
        with pytest.raises(preempt.PreemptCheckpointed) as ei:
            preempt.check_after_checkpoint()
        assert ei.value.signame == "SIGTERM"
        assert preempt.EXIT_PREEMPTED == 75
    finally:
        preempt.uninstall()
    assert preempt.requested() is None


# -- non-finite lnL guard ---------------------------------------------------


def test_nonfinite_lnl_retries_on_scan_tier(monkeypatch):
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    obs.reset()
    faults.reset()
    monkeypatch.setenv(faults.ENV_VAR, "engine.nonfinite:after=1")
    inst = PhyloInstance(correlated_dna(6, 60, seed=1))
    tree = inst.random_tree(seed=0)
    lnl = inst.evaluate(tree, full=True)
    assert np.isfinite(lnl)
    c = obs.snapshot_counters()
    assert c["engine.nonfinite_retries"] == 1
    assert c["engine.nonfinite_recovered"] == 1
    # engine state restored: a later evaluate is clean and counts no
    # further retries
    assert np.isfinite(inst.evaluate(tree, full=True))
    assert obs.counter("engine.nonfinite_retries") == 1


def test_nonfinite_lnl_persistent_is_fatal(monkeypatch):
    """A second non-finite result on the scan-tier retry must raise:
    searching on a poisoned lnL silently corrupts the tree."""
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    obs.reset()
    inst = PhyloInstance(correlated_dna(6, 60, seed=1))
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    eng = next(iter(inst.engines.values()))

    def poisoned(entries, p, q, z, full=False):
        return np.full(len(eng.bucket.part_ids), np.nan)

    monkeypatch.setattr(eng, "traverse_evaluate", poisoned)
    with pytest.raises(FloatingPointError, match="non-finite"):
        inst.evaluate(tree, full=True)
    assert obs.counter("engine.nonfinite_retries") == 1


# -- checkpoint corruption fallback + durability ----------------------------


def _two_checkpoints(tmp_path, run_id="CR"):
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data = correlated_dna(8, 80, seed=2)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    mgr = CheckpointManager(str(tmp_path), run_id)
    mgr.write("FAST_SPRS", {"impr": True, "mark": 0}, inst, tree)
    mgr.write("FAST_SPRS", {"impr": False, "mark": 1}, inst, tree)
    return data, mgr


def test_restore_falls_back_over_corrupt_latest(tmp_path):
    """Satellite: a truncated/corrupt newest checkpoint (the
    partial-write-at-kill-time artifact) costs one checkpoint interval,
    not every restart forever."""
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    obs.reset()
    data, mgr = _two_checkpoints(tmp_path)
    # Truncate the newest published file mid-gzip-stream.
    latest = mgr.latest_path()
    raw = open(latest, "rb").read()
    with open(latest, "wb") as f:
        f.write(raw[: len(raw) // 2])

    inst2 = PhyloInstance(data)
    tree2 = inst2.random_tree(seed=9)
    resume = CheckpointManager(str(tmp_path), "CR").restore(inst2, tree2)
    assert resume is not None
    assert resume["extras"]["mark"] == 0       # the next-newest one
    assert obs.counter("checkpoint.corrupt_skipped") == 1


def test_restore_skips_garbage_and_missing_sections(tmp_path):
    from examl_tpu import obs
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    obs.reset()
    data, mgr = _two_checkpoints(tmp_path)
    # newest: valid gzip, valid JSON, wrong shape; next: plain garbage
    with gzip.open(mgr.path_for(3), "wt") as f:
        json.dump({"magic": "examl-tpu-checkpoint", "version": 1}, f)
    with open(mgr.path_for(2), "wb") as f:
        f.write(b"this is not gzip at all")
    inst2 = PhyloInstance(data)
    resume = CheckpointManager(str(tmp_path), "CR").restore(
        inst2, inst2.random_tree(seed=9))
    assert resume["extras"]["mark"] == 1       # ckpt_1, the newest intact
    assert obs.counter("checkpoint.corrupt_skipped") == 2


def test_restore_all_corrupt_returns_none(tmp_path):
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data, mgr = _two_checkpoints(tmp_path)
    for p in glob.glob(mgr._pattern()):
        with open(p, "wb") as f:
            f.write(b"garbage")
    inst2 = PhyloInstance(data)
    assert CheckpointManager(str(tmp_path), "CR").restore(
        inst2, inst2.random_tree(seed=9)) is None


def test_restore_explicit_path_still_raises(tmp_path):
    """An explicitly requested file gets no fallback."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import (CheckpointManager,
                                             CorruptCheckpoint)
    data, mgr = _two_checkpoints(tmp_path)
    latest = mgr.latest_path()
    with open(latest, "wb") as f:
        f.write(b"garbage")
    inst2 = PhyloInstance(data)
    with pytest.raises(CorruptCheckpoint):
        CheckpointManager(str(tmp_path), "CR").restore(
            inst2, inst2.random_tree(seed=9), path=latest)


def test_checkpoint_write_fault_preserves_published(tmp_path, monkeypatch):
    """The checkpoint.write injection fires pre-publish: the write
    fails, the previously published checkpoint stays intact and
    restorable, and no half-published file exists."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search.checkpoint import CheckpointManager
    data, mgr = _two_checkpoints(tmp_path)
    monkeypatch.setenv(faults.ENV_VAR, "checkpoint.write:after=1")
    faults.reset()
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=0)
    inst.evaluate(tree, full=True)
    with pytest.raises(faults.FaultInjected):
        mgr.write("FAST_SPRS", {"mark": 2}, inst, tree)
    assert not os.path.exists(mgr.path_for(2))
    monkeypatch.delenv(faults.ENV_VAR)
    faults.reset()
    inst2 = PhyloInstance(data)
    resume = CheckpointManager(str(tmp_path), "CR").restore(
        inst2, inst2.random_tree(seed=9))
    assert resume["extras"]["mark"] == 1


# -- e2e chaos matrix (supervised CLI subprocess runs) ----------------------


def _chaos_fixture(tmp_path_factory):
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.bytefile import write_bytefile
    root = tmp_path_factory.mktemp("chaos")
    data = correlated_dna(8, 120, seed=7)
    bf = str(root / "a.binary")
    write_bytefile(bf, data)
    inst = PhyloInstance(data)
    t = inst.random_tree(seed=3)
    tf = str(root / "start.nwk")
    open(tf, "w").write(t.to_newick(data.taxon_names))
    return root, bf, tf


def _final_lnl(info_path: str) -> float:
    import re
    text = open(info_path).read()
    m = re.findall(r"Likelihood of best tree: (-[\d.]+)", text)
    assert m, text[-2000:]
    return float(m[-1])


@pytest.fixture(scope="module")
def chaos_run(tmp_path_factory):
    """Fixture shared by the e2e chaos tests: the tiny alignment, the
    start tree, and the UNINTERRUPTED run's final lnL (the parity
    target every resumed run must reach)."""
    root, bf, tf = _chaos_fixture(tmp_path_factory)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    env.pop(faults.ENV_VAR, None)
    env.pop(heartbeat.ENV_VAR, None)
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "BASE", "-t", tf, "-f", "d", "-i", "5", "-w",
         str(root / "base"), "--single-device"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stdout + out.stderr
    lnl = _final_lnl(str(root / "base" / "ExaML_info.BASE"))
    return {"root": root, "bf": bf, "tf": tf, "lnl": lnl, "env": env}


def _supervised(chaos_run, name, inject, extra=None, retries=3,
                stall=0.0):
    """Run the CLI under --supervise in-process (the supervisor parent
    is jax-free; all jax work happens in its child subprocesses)."""
    from examl_tpu.cli.main import main
    root = chaos_run["root"]
    w = str(root / name)
    m = str(root / f"{name}.metrics.json")
    argv = ["-s", chaos_run["bf"], "-n", name, "-t", chaos_run["tf"],
            "-f", "d", "-i", "5", "-w", w, "--single-device",
            "--supervise", "--supervise-backoff", "0.2",
            "--supervise-retries", str(retries),
            "--supervise-stall", str(stall), "--metrics", m]
    for spec in inject:
        argv += ["--inject-fault", spec]
    argv += extra or []
    rc = main(argv)
    snap = json.load(open(m)) if os.path.exists(m) else {}
    return rc, w, snap


def test_e2e_sigkill_mid_search_resumes_to_same_lnl(chaos_run,
                                                    monkeypatch):
    """THE acceptance test: a supervised CPU run SIGKILLed mid-FAST_SPRS
    auto-resumes from the newest checkpoint and reaches the
    uninterrupted run's final lnL; a NaN injected on the resumed
    attempt is retried on the scan tier — all asserted via obs counters
    (resilience.restarts, engine.nonfinite_retries)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # Flush the metrics snapshot on EVERY beat: with the warm compile
    # cache the killed attempt lives only a few seconds, so the default
    # 5 s cadence could leave just the counter-empty startup flush —
    # the partial_counters assertion below needs real evidence.
    monkeypatch.setenv("EXAML_METRICS_FLUSH_S", "0")
    rc, w, snap = _supervised(
        chaos_run, "KILL",
        ["search.kill:after=12",               # SIGKILL, attempt 0 only
         "engine.nonfinite:after=2:attempt=1"])  # NaN on the RESUMED run
    assert rc == 0
    c = snap["counters"]
    assert c["resilience.restarts"] >= 1
    assert c["engine.nonfinite_retries"] == 1
    assert c["engine.nonfinite_recovered"] == 1
    attempts = snap["resilience"]["attempts"]
    assert attempts[0]["cause"] == "oom-kill"      # external SIGKILL
    assert attempts[-1]["cause"] == "ok"
    assert attempts[-1]["resumed"]                 # -R from checkpoint
    # Flight-recorder acceptance: the SIGKILLed attempt never wrote its
    # exit snapshot, but the heartbeat-ticked periodic flush left a
    # partial one, and the supervisor preserved its last-known counters
    # in the attempt record before the retry overwrote the file.
    pc = attempts[0]["partial_counters"]
    assert pc and pc.get("engine.dispatch_count", 0) > 0
    # ...and the merged ledger is the single timeline of the whole
    # supervised run: both attempts' run-starts, the supervisor's
    # restart decision, and the checkpoint cycles the resume used.
    merged = os.path.join(str(chaos_run["root"]), "ledger.merged.jsonl")
    from examl_tpu.obs import ledger as _ledger_mod
    evs = _ledger_mod.read_events(merged)
    assert sum(1 for e in evs if e["kind"] == "run"
               and e.get("status") == "start") >= 2
    assert any(e["kind"] == "supervisor.restart" for e in evs)
    assert any(e["kind"] == "checkpoint.publish" for e in evs)
    assert any(e["kind"] == "supervisor.done" for e in evs)
    order = [(e["ts"], str(e["proc"]), e["seq"]) for e in evs]
    assert order == sorted(order)
    info = open(os.path.join(w, "ExaML_info.KILL")).read()
    assert "restart from state" in info            # resumed, not redone
    assert _final_lnl(os.path.join(w, "ExaML_info.KILL")) \
        == pytest.approx(chaos_run["lnl"], abs=LNL_TOL)


@pytest.mark.slow          # ~60 s REAL stall wait (chaos timing pitfall:
                           # needs a genuine hang) — tier-1 keeps the
                           # SIGKILL and SIGTERM chaos e2e (PR8 audit)
def test_e2e_heartbeat_stall_killed_and_degraded_retry(chaos_run,
                                                       monkeypatch):
    """A dispatch/collective wedge — the main thread blocks INSIDE a
    dispatch (injected: a 900 s hang at the 40th engine dispatch, well
    after the search loop started beating) — freezes the heartbeat;
    the supervisor detects the stall, kills the child process group,
    and the retry runs with the degraded-tier pin and completes.  (A
    bare `heartbeat.stall` beat-suppression would race a warm-cache
    child that finishes inside the stall window; a hang cannot.)"""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, w, snap = _supervised(
        chaos_run, "STALL", ["engine.dispatch:after=40:hang=900"],
        stall=20.0)
    assert rc == 0
    c = snap["counters"]
    assert c["resilience.heartbeat_stalls"] >= 1
    assert c["resilience.restarts"] >= 1
    assert snap["gauges"]["resilience.degrade_level"] >= 1
    attempts = snap["resilience"]["attempts"]
    assert attempts[0]["cause"] == "hang-kill"
    assert attempts[-1]["cause"] == "ok"
    assert attempts[-1]["pins"]                    # degraded-tier pin set
    assert _final_lnl(os.path.join(w, "ExaML_info.STALL")) \
        == pytest.approx(chaos_run["lnl"], abs=LNL_TOL)


@pytest.mark.slow
def test_e2e_checkpoint_write_crash_resumes(chaos_run, monkeypatch):
    """Dying INSIDE a checkpoint write (SIGKILL between the tmp write
    and the publish) leaves the previous published checkpoint intact;
    the supervised retry resumes from it.  (slow: the fast tier covers
    the same failure at unit level in
    test_checkpoint_write_fault_preserves_published, and the SIGKILL
    resume path in test_e2e_sigkill_mid_search_resumes_to_same_lnl.)"""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc, w, snap = _supervised(
        chaos_run, "CKPT", ["checkpoint.write:after=2:signal=KILL"])
    assert rc == 0
    c = snap["counters"]
    assert c["resilience.restarts"] >= 1
    attempts = snap["resilience"]["attempts"]
    assert attempts[0]["cause"] == "oom-kill"
    assert attempts[-1]["cause"] == "ok"
    assert _final_lnl(os.path.join(w, "ExaML_info.CKPT")) \
        == pytest.approx(chaos_run["lnl"], abs=LNL_TOL)


def test_e2e_sigterm_preempts_with_resumable_exit(chaos_run):
    """Preemption safety: SIGTERM mid-search -> emergency checkpoint at
    the next checkpoint site -> clean EXIT_PREEMPTED (75)."""
    root = chaos_run["root"]
    w = str(root / "PRE")
    proc = subprocess.Popen(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s",
         chaos_run["bf"], "-n", "PRE", "-t", chaos_run["tf"], "-f", "d",
         "-i", "5", "-w", w, "--single-device"],
        env=chaos_run["env"], cwd=REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    info = os.path.join(w, "ExaML_info.PRE")
    try:
        deadline = time.time() + 300
        # preempt once real search work is under way
        while time.time() < deadline:
            if os.path.exists(info) and "fast cycle" in open(info).read():
                break
            if proc.poll() is not None:
                pytest.fail("run finished before it could be preempted")
            time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert rc == exitcause.EXIT_PREEMPTED
    text = open(info).read()
    assert "emergency checkpoint" in text
    assert sup.checkpoint_glob(w, "PRE")           # resumable state exists
