"""Fleet tier: many-tree batched evaluation + the job-queue driver.

The parity contract is BITWISE: every batched program is built from the
engine's own traced bodies, so a job's lnL through the batched tier
must equal the one-at-a-time evaluation exactly (f64 CPU), including
per-partition branch lengths (-M, C>1) and PSR.  The driver tests pin
seed hygiene, bootstrap resampling semantics, profile grouping,
checkpoint resume, and the supervised kill/resume acceptance e2e.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import build_alignment_data

from tests.conftest import correlated_dna

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- seed hygiene ------------------------------------------------------------


def test_seed_derivation_deterministic_and_distinct():
    from examl_tpu.fleet import seeds
    a = [seeds.derive(12345, "bootstrap", k) for k in range(64)]
    b = [seeds.derive(12345, "bootstrap", k) for k in range(64)]
    assert a == b
    assert len(set(a)) == 64                       # no collisions
    assert all(0 <= s < 2 ** 63 for s in a)
    # streams are disjoint domains
    c = [seeds.derive(12345, "start", k) for k in range(64)]
    assert not set(a) & set(c)
    # nearby parents decorrelate
    assert seeds.derive(12345, "bootstrap", 0) != \
        seeds.derive(12346, "bootstrap", 0)
    with pytest.raises(ValueError):
        seeds.derive(1, "nope", 0)
    with pytest.raises(ValueError):
        seeds.derive(1, "start", -1)


def test_seed_derivation_ignores_environment(monkeypatch):
    """Replicate K is the same analysis on every resume: the derivation
    must not see world size, attempt count, or any ambient state."""
    from examl_tpu.fleet import seeds
    base = seeds.derive(777, "start", 5)
    monkeypatch.setenv("EXAML_RESTART_COUNT", "3")
    monkeypatch.setenv("EXAML_GANG_RANKS", "4")
    monkeypatch.setenv("EXAML_PROCID", "2")
    assert seeds.derive(777, "start", 5) == base


# -- bootstrap resampling ----------------------------------------------------


def test_bootstrap_weights_sum_and_determinism():
    from examl_tpu.fleet import bootstrap, seeds
    data = correlated_dna(8, 150, seed=1)
    part = data.partitions[0]
    nsites = int(round(float(np.sum(part.weights))))
    s = seeds.derive(9, "bootstrap", 0)
    w1 = bootstrap.resample_weights(part.weights, s)
    w2 = bootstrap.resample_weights(part.weights, s)
    assert np.array_equal(w1, w2)                  # deterministic
    assert w1.sum() == nsites                      # sums to site count
    assert np.all(w1 == np.floor(w1)) and np.all(w1 >= 0)
    assert not np.array_equal(
        w1, bootstrap.resample_weights(part.weights, s + 1))


def test_bootstrap_draws_over_site_multiplicity():
    """The draw is per SITE, not per pattern: a pattern of multiplicity
    m must be drawn ~m times as often as a singleton (the classic
    uniform-over-patterns bug would give them equal mass)."""
    from examl_tpu.fleet import bootstrap
    w = np.array([50.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    draws = np.stack([bootstrap.resample_weights(w, 1000 + i)
                      for i in range(200)])
    assert draws.shape == (200, 6)
    assert np.all(draws.sum(axis=1) == 55)
    mean = draws.mean(axis=0)
    assert abs(mean[0] - 50.0) < 2.0               # E = 50
    assert np.all(np.abs(mean[1:] - 1.0) < 0.5)    # E = 1


def test_packed_weights_layout_matches_engine():
    from examl_tpu.fleet import bootstrap
    data = correlated_dna(8, 150, seed=1)
    inst = PhyloInstance(data)
    (eng,) = inst.engines.values()
    per_part = [np.asarray(p.weights, dtype=np.float64)
                for p in data.partitions]
    packed = bootstrap.packed_weights(eng.bucket, per_part)
    assert np.array_equal(packed, np.asarray(eng.weights))


# -- batched evaluation parity (bit-identical) -------------------------------


def _profile_group(inst, nseeds=20, want=4):
    """Random trees sharing the largest fastpath profile group."""
    from examl_tpu.fleet.batch import BatchEvaluator
    ev = BatchEvaluator(inst)
    groups = {}
    for s in range(nseeds):
        t = inst.random_tree(seed=s)
        prep = ev.prepare(t)
        groups.setdefault(prep.key, []).append((t, prep))
    best = max(groups.values(), key=len)[:want]
    assert len(best) >= 2, "fixture produced no shared profile group"
    return ev, best


def test_tree_batch_bit_identical_gamma():
    data = correlated_dna(14, 200, seed=3)
    inst = PhyloInstance(data)
    ev, group = _profile_group(inst)
    singles = [inst.evaluate(t, full=True) for t, _ in group]
    per_part = ev.eval_batch([prep for _, prep in group])
    assert per_part.shape == (len(group), len(inst.models))
    for j, lnl in enumerate(singles):
        assert float(per_part[j].sum()) == lnl     # BITWISE


def test_tree_batch_one_entry_tail(monkeypatch):
    """The vmapped chunk program runs the one-entry tail (its per-step
    `lax.switch` becomes a select under vmap) where every engine's row
    is that wide: the batch equals the one-at-a-time evaluations, which
    run it too."""
    from examl_tpu.ops import fastpath
    monkeypatch.setattr(fastpath, "ONE_ENTRY_ROW_BYTES", 0)
    data = correlated_dna(14, 200, seed=3)
    inst = PhyloInstance(data)
    ev, group = _profile_group(inst)
    assert all(prep.key[1][-1][0] == "e" for _, prep in group)
    singles = [inst.evaluate(t, full=True) for t, _ in group]
    per_part = ev.eval_batch([prep for _, prep in group])
    for j, lnl in enumerate(singles):
        assert float(per_part[j].sum()) == pytest.approx(lnl, rel=1e-12)


def test_tree_batch_bit_identical_per_partition_branches():
    """C>1 (-M): per-partition branch lengths ride the batched z axis."""
    from examl_tpu.io.partitions import parse_partition_file
    rng = np.random.default_rng(1)
    seqs = []
    cur1 = rng.integers(0, 4, 100)
    cur2 = rng.integers(0, 4, 100)
    for _ in range(10):
        cur1 = np.where(rng.random(100) < 0.05,
                        rng.integers(0, 4, 100), cur1)
        cur2 = np.where(rng.random(100) < 0.35,
                        rng.integers(0, 4, 100), cur2)
        seqs.append("".join("ACGT"[c]
                            for c in np.concatenate([cur1, cur2])))
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".model",
                                     delete=False) as f:
        f.write("DNA, g1 = 1-100\nDNA, g2 = 101-200\n")
        mp = f.name
    data = build_alignment_data([f"t{i}" for i in range(10)], seqs,
                                specs=parse_partition_file(mp))
    os.unlink(mp)
    inst = PhyloInstance(data, per_partition_branches=True)
    assert inst.num_branch_slots == 2
    ev, group = _profile_group(inst)
    singles = [np.array(inst.per_partition_lnl, copy=True)
               for t, _ in group
               if inst.evaluate(t, full=True) is not None]
    per_part = ev.eval_batch([prep for _, prep in group])
    for j in range(len(group)):
        assert np.array_equal(per_part[j], singles[j])   # BITWISE per part


def test_tree_batch_bit_identical_psr():
    """PSR takes the vmapped scan-tier program; non-trivial per-site
    rates make the parity meaningful."""
    data = correlated_dna(12, 160, seed=5)
    inst = PhyloInstance(data, rate_model="PSR")
    rng = np.random.default_rng(0)
    for gid, part in enumerate(data.partitions):
        inst.per_site_rates[gid] = np.array([0.5, 1.0, 2.2])
        inst.rate_category[gid] = rng.integers(
            0, 3, len(part.weights)).astype(np.int32)
    inst.push_site_rates()
    ev, group = _profile_group(inst)
    assert not ev.fast                              # scan-tier batch
    singles = [inst.evaluate(t, full=True) for t, _ in group]
    per_part = ev.eval_batch([prep for _, prep in group])
    for j, lnl in enumerate(singles):
        assert float(per_part[j].sum()) == lnl     # BITWISE


def test_weights_batch_bit_identical_and_shares_programs():
    """Bootstrap replicates on a fixed topology: one CLV pass + a
    batched weight matrix must equal swapping each weight vector into
    the engine one at a time — and the second replicate batch must be
    pure cache hits (zero new compiles), the program-sharing evidence
    ISSUE 8 names."""
    import jax.numpy as jnp

    from examl_tpu import obs
    from examl_tpu.fleet import bootstrap, seeds
    from examl_tpu.fleet.batch import BatchEvaluator
    data = correlated_dna(10, 180, seed=2)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=3)
    ev = BatchEvaluator(inst)
    reps = [bootstrap.bootstrap_weights(data,
                                        seeds.derive(1, "bootstrap", k))
            for k in range(5)]
    per_part = ev.eval_weights_batch(tree, reps)
    (eng,) = inst.engines.values()
    p = tree.centroid_branch()
    inst.evaluate(tree, p, full=True)              # CLVs at the same edge
    saved = eng.weights
    try:
        for k, rep in enumerate(reps):
            eng.weights = jnp.asarray(
                bootstrap.packed_weights(eng.bucket, rep), eng.dtype)
            vals = eng.evaluate(p.number, p.back.number, p.z)
            assert np.array_equal(np.asarray(vals), per_part[k])  # BITWISE
    finally:
        eng.weights = saved
    # Second batch on the same topology: schedule cache + jit cache hit,
    # compile_count frozen.
    reg = obs.registry()
    compiles0 = reg.counter("engine.compile_count")
    hits0 = reg.counter("engine.cache_hits")
    sched_hits0 = reg.counter("engine.sched_cache.hit")
    ev.eval_weights_batch(tree, reps)
    assert reg.counter("engine.compile_count") == compiles0
    assert reg.counter("engine.cache_hits") > hits0
    assert reg.counter("engine.sched_cache.hit") > sched_hits0


def test_weights_batch_reuses_clv_pass():
    """Consecutive weight batches on the same tree skip the CLV
    traversal entirely (the arenas already hold this tree's CLVs): only
    the batched root reductions dispatch, and any intervening device
    program conservatively invalidates the cached pass."""
    from examl_tpu import obs
    from examl_tpu.fleet import bootstrap, seeds
    from examl_tpu.fleet.batch import BatchEvaluator
    data = correlated_dna(10, 180, seed=5)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=3)
    ev = BatchEvaluator(inst)
    reps = [bootstrap.bootstrap_weights(data,
                                        seeds.derive(7, "bootstrap", k))
            for k in range(4)]
    first = ev.eval_weights_batch(tree, reps)
    reg = obs.registry()
    reuse0 = reg.counter("fleet.clv_pass_reuses")
    disp0 = reg.counter("engine.dispatch_count")
    again = ev.eval_weights_batch(tree, reps)
    assert np.array_equal(first, again)                # BITWISE
    assert reg.counter("fleet.clv_pass_reuses") == reuse0 + 1
    # Only the per-engine weight reductions dispatched — no traversal.
    assert reg.counter("engine.dispatch_count") == disp0 + len(inst.engines)
    # An intervening dispatch (another tree's CLVs in the live arena)
    # invalidates the cached pass: the next batch re-traverses and
    # still agrees.
    inst.evaluate(inst.random_tree(seed=9), full=True)
    third = ev.eval_weights_batch(tree, reps)
    assert np.array_equal(first, third)
    assert reg.counter("fleet.clv_pass_reuses") == reuse0 + 1


def test_batch_occupancy_padding():
    """A 3-job batch pads to 4; padding jobs replay job 0 and are
    dropped from the result."""
    from examl_tpu import obs
    data = correlated_dna(14, 200, seed=3)
    inst = PhyloInstance(data)
    ev, group = _profile_group(inst, want=3)
    group = group[:3]
    per_part = ev.eval_batch([prep for _, prep in group])
    assert per_part.shape[0] == len(group)
    occ = obs.registry().snapshot()["gauges"]["fleet.batch_occupancy"]
    assert occ == len(group) / 4


# -- jobs file ---------------------------------------------------------------


def test_jobs_file_parsing_and_seed_stability():
    from examl_tpu.fleet.jobs import parse_jobs_lines
    lines = ['{"kind": "start"}', "", "# comment",
             '{"kind": "eval", "newick": "(a,b);", "id": "mine"}',
             '{"op": "stop"}']
    jobs, stop = parse_jobs_lines(lines, 42)
    assert stop
    assert [j.job_id for j in jobs] == ["start0", "mine"]
    assert jobs[1].index == 3                      # line-indexed
    # appending jobs never re-seeds earlier ones: parsing the tail with
    # start_index continues the same derivation
    jobs2, _ = parse_jobs_lines(['{"kind": "start"}'], 42, start_index=5)
    from examl_tpu.fleet import seeds
    assert jobs2[0].seed == seeds.derive(42, "start", 5)
    assert jobs[0].seed == seeds.derive(42, "start", 0)
    with pytest.raises(ValueError, match="line 1"):
        parse_jobs_lines(["{bad json"], 42)
    with pytest.raises(ValueError, match="newick"):
        parse_jobs_lines(['{"kind": "eval"}'], 42)
    # `$`-anchored match would accept a trailing newline and split the
    # space-delimited results table record across two lines.
    with pytest.raises(ValueError, match="must match"):
        parse_jobs_lines(['{"kind": "start", "id": "abc\\n"}'], 42)


# -- driver: grouping, resume ------------------------------------------------


def test_driver_resume_skips_done_jobs():
    from examl_tpu import obs
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import make_jobs
    data = correlated_dna(10, 160, seed=4)
    inst = PhyloInstance(data)
    tree = inst.random_tree(seed=3)
    drv = FleetDriver(inst, start_tree=tree, batch_cap=4)
    jobs = make_jobs("bootstrap", 4, 99)
    done = drv.run(jobs)
    assert all(j.done and not j.failed for j in done)
    extras = drv.extras()
    # A fresh driver resuming the full table dispatches NOTHING.
    reg = obs.registry()
    batches0 = reg.counter("fleet.batches")
    drv2 = FleetDriver(inst, start_tree=tree, batch_cap=4)
    out = drv2.run(make_jobs("bootstrap", 4, 99), extras)
    assert reg.counter("fleet.batches") == batches0
    assert [j.lnl for j in out] == [j.lnl for j in done]
    # A half-done table redoes only the pending half.
    half = json.loads(json.dumps(extras))
    for d in half["fleet"]["jobs"][2:]:
        d["done"] = False
        d["lnl"] = None
    drv3 = FleetDriver(inst, start_tree=tree, batch_cap=4)
    out3 = drv3.run(make_jobs("bootstrap", 4, 99), half)
    assert reg.counter("fleet.batches") == batches0 + 1
    assert [j.lnl for j in out3] == [j.lnl for j in done]  # same seeds


def test_driver_cycles_smooth_then_rescore_matches_sequential():
    """cycles=2: the batched re-score must see the SMOOTHED branch
    lengths (regression: PreparedJobs captured at grouping time held
    pre-smoothing z) and match the sequential evaluate+smooth+evaluate
    reference bitwise."""
    from examl_tpu.constants import SMOOTHINGS
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import JobSpec
    from examl_tpu.optimize.branch import smooth_tree
    data = correlated_dna(10, 160, seed=6)
    inst = PhyloInstance(data)
    base = inst.random_tree(seed=11)
    nwk = base.to_newick(data.taxon_names)
    # Three eval jobs on ONE topology -> one profile group, one batch.
    jobs = [JobSpec(job_id=f"e{k}", kind="eval", index=k, seed=0,
                    cycles=2, newick=nwk) for k in range(3)]
    drv = FleetDriver(inst, batch_cap=4, cycles=2)
    out = drv.run(jobs)
    assert all(j.done and j.cycles_done == 2 for j in out)
    # Sequential reference: the exact smoothing contract the driver
    # must reproduce (engine oriented to the tree, then smoothed, then
    # scored) on a FRESH instance.
    inst2 = PhyloInstance(data)
    tree = inst2.tree_from_newick(nwk)
    inst2.evaluate(tree, full=True)
    smooth_tree(inst2, tree, SMOOTHINGS)
    ref = inst2.evaluate(tree, full=True)
    for j in out:
        assert j.lnl == ref                    # BITWISE
    assert ref > inst2.evaluate(inst2.tree_from_newick(nwk), full=True), \
        "smoothing did not improve lnL — the cycle did nothing"


def test_driver_poisoned_job_fails_alone():
    """A job that cannot materialize (malformed newick) fails ALONE;
    the rest of the queue still serves."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import JobSpec, make_jobs
    data = correlated_dna(10, 160, seed=4)
    inst = PhyloInstance(data)
    jobs = make_jobs("start", 2, 7)
    jobs.append(JobSpec(job_id="bad", kind="eval", index=9, seed=0,
                        newick="((broken"))
    drv = FleetDriver(inst, batch_cap=4)
    out = drv.run(jobs)
    by_id = {j.job_id: j for j in out}
    assert by_id["bad"].failed and by_id["bad"].done
    assert all(by_id[f"start{k}"].done and not by_id[f"start{k}"].failed
               and by_id[f"start{k}"].lnl is not None for k in range(2))
    # the operator-facing gauge counts SUCCESSES only
    from examl_tpu import obs
    assert obs.registry().snapshot()["gauges"]["fleet.jobs_done"] == 2


def test_jobs_parse_on_error_skips_bad_lines():
    from examl_tpu.fleet.jobs import parse_jobs_lines
    errs = []
    jobs, stop = parse_jobs_lines(
        ["{bad", '{"kind": "nope"}', '{"kind": "start"}',
         '[1, 2]', '"oops"', '{"kind": "start", "seed": "x"}',
         '{"kind": "start", "cycles": "two"}',
         '{"op": "stop"}'], 42, on_error=errs.append)
    assert [j.job_id for j in jobs] == ["start2"]
    assert stop and len(errs) == 6      # every malformed SHAPE skips too
    assert "line 1" in errs[0] and "line 2" in errs[1]
    # bootstrap jobs normalize to 1 cycle (weights-only work)
    (bs,), _ = parse_jobs_lines(['{"kind": "bootstrap", "cycles": 5}'],
                                42, default_cycles=3)
    assert bs.cycles == 1
    # ids with whitespace/newlines would corrupt the space-delimited
    # results table: rejected at parse time.
    with pytest.raises(ValueError, match="must match"):
        parse_jobs_lines(['{"kind": "start", "id": "job 1"}'], 42)


def test_serve_resume_snapshot_applies_once(tmp_path):
    """Regression: the --serve loop must apply a -R resume snapshot to
    the job table ONCE — re-applying it after a later append would flip
    jobs completed since the resume back to the stale pending state and
    re-run them (duplicate job.done)."""
    import threading
    import time as _time
    from types import SimpleNamespace

    from examl_tpu.cli.main import _serve_loop
    from examl_tpu.fleet.driver import FleetDriver
    data = correlated_dna(10, 160, seed=4)
    inst = PhyloInstance(data)
    jobs_file = tmp_path / "jobs.jsonl"
    jobs_file.write_text('{"kind": "start"}\n{"kind": "start"}\n')
    drv = FleetDriver(inst, batch_cap=4)
    dispatched = []
    orig = drv._dispatch_round
    drv._dispatch_round = lambda assignments: (dispatched.extend(
        j.job_id for _, b in assignments for j in b),
        orig(assignments))[1]
    # Stale snapshot: start0 done (sentinel lnl), start1 pending — as a
    # checkpoint taken before start1 finished would record.
    resume = {"fleet": {"jobs": [
        {"job_id": "start0", "kind": "start", "index": 0, "seed": 1,
         "cycles": 1, "cycles_done": 1, "lnl": -123.456, "done": True,
         "failed": False},
        {"job_id": "start1", "kind": "start", "index": 1, "seed": 2,
         "cycles": 1, "cycles_done": 0, "lnl": None, "done": False,
         "failed": False}]}}
    args = SimpleNamespace(serve=str(jobs_file), seed=42, fleet_cycles=1,
                           serve_poll=0.1)
    files = SimpleNamespace(info=lambda *_: None)

    def append_later():
        _time.sleep(1.0)           # after round 1 drained start1
        with open(jobs_file, "a") as f:
            # includes a DUPLICATE id: must be skipped, not alias the
            # done job's cached state
            f.write('{"kind": "start"}\n'
                    '{"kind": "start", "id": "start0"}\n'
                    '{"op": "stop"}\n')

    t = threading.Thread(target=append_later)
    t.start()
    out = _serve_loop(args, drv, files, resume)
    t.join()
    by_id = {j.job_id: j for j in out}
    assert by_id["start0"].lnl == -123.456     # never re-evaluated
    assert dispatched.count("start0") == 0
    assert dispatched.count("start1") == 1     # not regressed by round 2
    assert by_id["start2"].done


def test_restore_jobs_subset_applies_to_fresh_specs_only():
    """The serve loop restores each poll's FRESH specs against the
    resume snapshot — so a finished job whose torn final line is only
    consumed a poll later still gets its checkpointed done state
    (instead of re-running and double-counting job.done), while jobs
    already in the queue are never regressed by a re-application."""
    from examl_tpu.fleet.driver import FleetDriver
    from examl_tpu.fleet.jobs import JobSpec
    drv = FleetDriver.__new__(FleetDriver)
    snap = {"fleet": {"jobs": [
        {"job_id": "a", "kind": "start", "index": 0, "seed": 1,
         "cycles": 1, "cycles_done": 1, "lnl": -1.5, "done": True,
         "failed": False}]}}
    early = JobSpec("x", "start", 1, 2)
    drv.jobs = [early]
    assert drv.restore_jobs(snap, [early]) == 0
    late = JobSpec("a", "start", 0, 1)         # the torn-line job
    drv.jobs.append(late)
    assert drv.restore_jobs(snap, [late]) == 1
    assert late.done and late.lnl == -1.5
    assert not early.done


def test_serve_accepts_torn_final_line(tmp_path, monkeypatch):
    """A producer whose LAST write omits the trailing newline (an
    `echo -n` stop sentinel, a crashed producer) must not starve the
    serve loop: a torn final line UNCHANGED across two polls is taken
    as complete."""
    from types import SimpleNamespace

    from examl_tpu.cli import main as cli_main_mod
    from examl_tpu.cli.main import _serve_loop
    from examl_tpu.fleet.driver import FleetDriver
    data = correlated_dna(10, 160, seed=4)
    inst = PhyloInstance(data)
    jobs_file = tmp_path / "jobs.jsonl"
    jobs_file.write_text('{"kind": "start"}\n{"op": "stop"}')  # no \n
    drv = FleetDriver(inst, batch_cap=4)
    args = SimpleNamespace(serve=str(jobs_file), seed=42, fleet_cycles=1,
                           serve_poll=0.02)
    files = SimpleNamespace(info=lambda *_: None)
    polls = {"n": 0}

    def counting_sleep(_s):
        polls["n"] += 1
        assert polls["n"] < 20, "serve loop starved on torn stop sentinel"

    monkeypatch.setattr(cli_main_mod.time, "sleep", counting_sleep)
    out = _serve_loop(args, drv, files, None)
    assert [j.job_id for j in out] == ["start0"]
    assert all(j.done and not j.failed for j in out)


# -- CLI e2e -----------------------------------------------------------------


def _fleet_fixture(tmp_path, ntaxa=10, nsites=200, seed=0):
    from examl_tpu.io.bytefile import write_bytefile
    data = correlated_dna(ntaxa, nsites, seed=seed)
    bf = str(tmp_path / "a.binary")
    write_bytefile(bf, data)
    inst = PhyloInstance(data)
    t = inst.random_tree(seed=3)
    tf = str(tmp_path / "start.nwk")
    open(tf, "w").write(t.to_newick(data.taxon_names))
    return data, bf, tf


def _read_table(path):
    rows = {}
    for line in open(path):
        if line.startswith("#"):
            continue
        (jid, kind, idx, seed, cyc, lnl, status,
         cause, attempts) = line.split()
        rows[jid] = (kind, int(seed), float(lnl), status, cause,
                     int(attempts))
    return rows


def test_cli_bootstrap_fleet_end_to_end(tmp_path):
    from examl_tpu.cli.main import main as run_main
    from examl_tpu.obs import ledger as _ledger
    data, bf, tf = _fleet_fixture(tmp_path)
    m = str(tmp_path / "m.json")
    rc = run_main(["-s", bf, "-n", "FB", "-t", tf, "-b", "5",
                   "--fleet-batch", "3", "-w", str(tmp_path),
                   "--metrics", m])
    assert rc == 0
    table = _read_table(tmp_path / "ExaML_fleet.FB")
    assert len(table) == 5
    assert all(v[3] == "done" for v in table.values())
    snap = json.load(open(m))
    assert snap["gauges"]["fleet.jobs_done"] == 5
    assert 0 < snap["gauges"]["fleet.batch_occupancy"] <= 1.0
    assert snap["gauges"].get("fleet.trees_per_sec", 0) > 0  # warm batch
    assert snap["counters"]["fleet.batches"] >= 2
    evs = _ledger.read_dir(str(tmp_path))
    assert sum(1 for e in evs if e["kind"] == "job.done") == 5
    assert sum(1 for e in evs if e["kind"] == "batch.dispatch") >= 2
    # Parity at the table's 6-decimal resolution: replicate 0
    # re-derived and evaluated one at a time.
    import jax.numpy as jnp

    from examl_tpu.fleet import bootstrap, seeds
    inst = PhyloInstance(data)
    tree = inst.tree_from_newick(open(tf).read())
    w = bootstrap.bootstrap_weights(
        data, seeds.derive(12345, "bootstrap", 0))   # default -p seed
    for eng in inst.engines.values():
        eng.weights = jnp.asarray(
            bootstrap.packed_weights(eng.bucket, w), eng.dtype)
    lnl = inst.evaluate(tree, full=True)
    assert table["bootstrap0"][2] == pytest.approx(lnl, abs=5e-6)


def test_cli_multistart_and_serve(tmp_path):
    from examl_tpu.cli.main import main as run_main
    data, bf, tf = _fleet_fixture(tmp_path)
    rc = run_main(["-s", bf, "-n", "FN", "-N", "4", "-w", str(tmp_path)])
    assert rc == 0
    table = _read_table(tmp_path / "ExaML_fleet.FN")
    assert len(table) == 4 and all(v[3] == "done" for v in table.values())
    trees = open(tmp_path / "ExaML_fleetTrees.FN").read().splitlines()
    assert len(trees) == 4 and all(t.startswith("(") for t in trees)
    # one-at-a-time parity for a multi-start job (6-decimal table)
    inst = PhyloInstance(data)
    kind, seed, lnl = table["start1"][:3]
    t = inst.random_tree(seed=seed)
    assert inst.evaluate(t, full=True) == pytest.approx(lnl, abs=5e-6)

    # --serve drains a jobs file: an eval job scores the -t tree exactly.
    jobs = tmp_path / "jobs.jsonl"
    jobs.write_text(json.dumps({"kind": "eval",
                                "newick": open(tf).read().strip()}) + "\n"
                    + '{"kind": "start"}\n{"op": "stop"}\n')
    rc = run_main(["-s", bf, "-n", "FS", "--serve", str(jobs),
                   "-w", str(tmp_path)])
    assert rc == 0
    stable = _read_table(tmp_path / "ExaML_fleet.FS")
    assert set(stable) == {"eval0", "start1"}
    tree0 = inst.tree_from_newick(open(tf).read())
    assert stable["eval0"][2] == pytest.approx(
        inst.evaluate(tree0, full=True), abs=5e-6)


def test_cli_fleet_flag_validation(tmp_path, capsys):
    from examl_tpu.cli.main import main as run_main
    _, bf, tf = _fleet_fixture(tmp_path)
    for argv in (["-b", "2", "-N", "2"],           # two fleet modes
                 ["-b", "2"],                       # bootstrap without -t
                 ["-b", "2", "-t", tf, "-S"],       # -S unsupported
                 ["-N", "2", "-f", "q"],            # quartets conflict
                 ["-b", "-5", "-t", tf],            # negative K: a typo,
                 ["-N", "-3"]):                     # not an empty "success"
        with pytest.raises(SystemExit):
            run_main(["-s", bf, "-n", "X", "-w", str(tmp_path)] + argv)
        capsys.readouterr()


# -- the acceptance e2e: supervised kill mid-fleet ---------------------------


def test_supervised_kill_mid_fleet_resumes(tmp_path):
    """ISSUE 8 acceptance: a supervised kill mid-fleet resumes losing at
    most one job's current cycle — jobs finished before the kill are
    never re-dispatched (their job.start/job.done appear exactly once
    across both attempts) and the job timeline is visible in the merged
    ledger."""
    _, bf, tf = _fleet_fixture(tmp_path, ntaxa=8, nsites=120)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [REPO, os.environ.get("PYTHONPATH", "")]))
    env.pop("EXAML_FAULTS", None)
    env.pop("EXAML_HEARTBEAT_FILE", None)
    m = str(tmp_path / "m.json")
    out = subprocess.run(
        [sys.executable, "-m", "examl_tpu.cli.main", "-s", bf, "-n",
         "FCHAOS", "-t", tf, "-b", "6", "--fleet-batch", "2",
         "-w", str(tmp_path), "--metrics", m, "--supervise",
         "--supervise-backoff", "0.2",
         "--inject-fault", "search.kill:after=2"],   # 2nd fleet batch beat
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    table = _read_table(tmp_path / "ExaML_fleet.FCHAOS")
    assert len(table) == 6
    assert all(v[3] == "done" for v in table.values())
    snap = json.load(open(m))
    assert snap["counters"]["resilience.restarts"] >= 1
    from examl_tpu.obs import ledger as _ledger
    evs = _ledger.read_events(str(tmp_path / "ledger.merged.jsonl"))
    runs = [e for e in evs if e["kind"] == "run"
            and e.get("status") == "start"]
    assert len(runs) >= 2                          # killed + resumed
    done = [e["job"] for e in evs if e["kind"] == "job.done"]
    started = [e["job"] for e in evs if e["kind"] == "job.start"]
    assert sorted(done) == sorted(set(done))       # each job done ONCE
    assert len(done) == 6
    # jobs finished in attempt 1 were not re-started in attempt 2: at
    # most one in-flight batch (2 jobs) repeats its cycle.
    assert len(started) <= 6 + 2
    assert sum(1 for e in evs if e["kind"] == "batch.dispatch") >= 3
