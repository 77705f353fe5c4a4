#!/usr/bin/env python3
"""Chip smoke: the plain CLI's main path, once, on the device JAX gives.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # the site-sharded path on four chips
    python chip_smoke.py --rehearse   # the ONLY way it runs on CPU: tiny
                                      # sizes, control flow only, no
                                      # device number is worth anything

One process, JAX touched only here, every phase calls the package's own
entry points in-process (`examl_tpu.cli.parse.main`,
`examl_tpu.cli.main.main`).  Data is made from `--seed`: a PHYLIP file
evolved down a random tree under GTR+GAMMA(4), kept to exactly the
number of distinct site patterns the phase names.  Nothing outside the
checkout is read; everything written lands under `.chip_smoke/`
(git-ignored), the compile cache where `JAX_COMPILATION_CACHE_DIR` says
or else `<checkout>/.xla_cache`, and a copy of the JSON lines in
`chiprun_out/`.

Sizes, and where they come from:

* `fullwidth`: 140 taxa x 131,072 DNA patterns, GTR+GAMMA(4), one
  partition — f32 CLV arena about 1.7 GB.  140 is the taxon count of
  upstream ExaML's `testData/140`; 131,072 patterns is the ExaML manual's
  production load ("How many cores shall I use?": on the order of a
  thousand site patterns per core for DNA) times a chip's worth of cores
  (128 lanes x 1,024 blocks is also the engine's own packing unit).
  PHYLIP -> cli.parse -> byteFile -> the loader cli.main uses -> full
  traversal + root evaluation of the start tree -> lnL against the
  independent f64 NumPy oracle (tests/oracle.py) -> one
  `smooth_tree(inst, tree, 1)` (whole-tree gradient passes).
* `evaluate`: `cli.main -f e` at 140 x 16,384 of the same generator.
  The width is cut from `fullwidth`'s because `-f e` follows a fixed
  modOpt schedule to convergence and measured 417 s warm at 65,536
  patterns on a v5e (0.71 s per gradient pass x 380 passes) against 35 s
  at 16,384 — a smoke cannot carry the former.
* `search`: `cli.main -f d` on 10 taxa x 200 sites from a fixed start
  tree and `-p` seed; lnL must rise monotonically in `ExaML_log.*` and
  the SPR scan programs must each compile and run.
* `--chips 4` runs only `placement`, `sharded`, `single` at 140 x 16,384
  (per-device arena shards, `-f e` sharded by default against
  `--single-device`).

Output: one JSON line per phase on stdout (the CLI's own prose goes to
stderr), then as the LAST line exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`.
Any failed check, any demotion the run's metrics or ledger show, any OOM
event, or a platform other than `tpu` (without --rehearse) exits
non-zero and prints no result line; no phase is wrapped in a catch.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")
OUT_DIR = os.path.join(ROOT, "chiprun_out")

# f32 engine against the f64 oracle.  ROADMAP used to quote 1e-6 and
# ISSUE 23 asked for 1e-5 (an earlier attempt's chip runs on
# near-saturated data, -203 lnL a site, showed 2.4e-6..2.8e-6).  On this
# generator's data (-99 lnL a site) a v5e measured 1.04e-5 at 140 x
# 131,072 (PR 23): the engine's lnL sits BELOW the oracle's by about
# 1e-3 a site whatever the width, which is what 277 edges times a 2^-18
# downward bias a contraction gives — the chunk tier's child-CLV dots
# run at Precision.HIGH (3-pass bf16, NUMERICS.md), and its error does
# not average out.  It is an absolute error per site and edge, so its
# size RELATIVE to lnL depends on how negative the data's lnL is.  2e-5
# is twice what the chip showed on realistic data; the f32 lnL's own
# last place (1.0 at 1.3e7) is 8e-8.
RTOL = 2e-5
# Sharded against single-device `-f e`: whole-tree gradient passes on
# both since PR 31 (before it, sharded arenas smoothed branch by branch),
# the site sums in another order.
AGREE_RTOL = 1e-5

# (taxa, patterns) per phase: real sizes, and the --rehearse toys.
SIZES = {
    "fullwidth": ((140, 131072), (12, 256)),
    "evaluate": ((140, 16384), (12, 256)),
    "search": ((10, 200), (7, 120)),
    "four": ((140, 16384), (12, 1024)),
}
ONE_CHIP_PHASES = ("fullwidth", "evaluate", "search")

# Counters that mean the run gave way somewhere (a tier, a family, a
# device, memory): any of them non-zero fails the phase that shows it.
DEMOTION_COUNTERS = (
    "bank.fallbacks", "fleet.device_degraded", "engine.watchdog_barks",
    "mem.oom_events", "optimize.grad_smooth_fallbacks",
    "engine.nonfinite_retries", "engine.universal_ineligible")
DEMOTION_EVENTS = ("tier.fallback", "mem.oom")


def emit(ctx, phase: str, **fields) -> None:
    """One JSON line per phase; every line names the device it ran on."""
    rec = {"phase": phase, "platform": ctx["platform"],
           "device_kind": ctx["kind"], "rehearse": ctx["rehearse"]}
    rec.update(fields)
    line = json.dumps(rec, sort_keys=True)
    ctx["lines"].append(line)
    print(line, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# -- data: sequences evolved down a random tree under GTR+GAMMA(4) ----------

_GTR_RATES = np.array([1.2, 3.1, 0.9, 1.1, 3.4, 1.0])   # AC AG AT CG CT GT
_FREQS = np.array([0.30, 0.21, 0.24, 0.25])
_ALPHA = 0.7


def random_tree(rng, ntaxa: int):
    """Random unrooted binary topology by random joining, as nested
    (left, right, length) tuples; returns (root triple, names)."""
    names = [f"t{i + 1}" for i in range(ntaxa)]
    nodes = [(n, float(rng.uniform(0.02, 0.25))) for n in names]
    while len(nodes) > 3:
        i, j = sorted(rng.choice(len(nodes), size=2, replace=False))
        b, a = nodes.pop(j), nodes.pop(i)
        nodes.append(((a, b), float(rng.uniform(0.02, 0.25))))
    return nodes, names


def newick(root3, lengths: bool) -> str:
    def fmt(node):
        sub, t = node
        s = sub if isinstance(sub, str) else \
            "(" + ",".join(fmt(c) for c in sub) + ")"
        return f"{s}:{t:.6f}" if lengths else s
    return "(" + ",".join(fmt(c) for c in root3) + ");"


def evolve(rng, root3, names, nsites: int) -> dict:
    """{name: uint8 [nsites] states} evolved from a root at the
    trifurcation under the package's own GTR+GAMMA(4) model code (only
    to MAKE data; the oracle that checks lnL shares none of it)."""
    from examl_tpu.datatypes import DNA
    from examl_tpu.models.gtr import build_model, transition_matrix
    model = build_model(DNA, _FREQS, rates=_GTR_RATES, alpha=_ALPHA)
    cat = rng.integers(0, 4, nsites)
    root = rng.choice(4, size=nsites, p=_FREQS)
    out = {}

    def down(node, parent_states):
        sub, t = node
        P = np.stack([transition_matrix(model, t, r)
                      for r in model.gamma_rates])             # [4, 4, 4]
        cum = np.cumsum(np.clip(P[cat, parent_states, :], 0.0, None),
                        axis=1)                                # [n, 4]
        u = rng.random(nsites)[:, None] * cum[:, -1:]
        states = (u > cum[:, :3]).sum(axis=1).astype(np.uint8)
        if isinstance(sub, str):
            out[sub] = states
        else:
            for c in sub:
                down(c, states)

    for c in root3:
        down(c, root)
    return out


def make_data(tag: str, ntaxa: int, npatterns: int, seed: int):
    """Write <tag>.phy (exactly `npatterns` distinct columns, each once)
    plus the generating topology without lengths (<tag>.tree: the start
    tree of every phase but `search`) and a random other topology
    (<tag>.start.tree)."""
    rng = np.random.default_rng(seed)
    root3, names = random_tree(rng, ntaxa)
    want, cols, seen = npatterns, [], 0
    while seen < want:
        n = int((want - seen) * 1.25) + 64
        seqs = evolve(rng, root3, names, n)
        cols.append(np.stack([seqs[nm] for nm in names]))       # [taxa, n]
        allc = np.concatenate(cols, axis=1)
        _, first = np.unique(allc.T, axis=0, return_index=True)
        seen = first.size
    keep = np.sort(first)[:want]
    mat = allc[:, keep]
    letters = np.frombuffer(b"ACGT", dtype=np.uint8)
    phy = os.path.join(WORK, f"{tag}.phy")
    with open(phy, "w") as f:
        f.write(f"{ntaxa} {want}\n")
        for i, nm in enumerate(names):
            f.write(f"{nm} {letters[mat[i]].tobytes().decode()}\n")
    tree_path = os.path.join(WORK, f"{tag}.tree")
    with open(tree_path, "w") as f:
        f.write(newick(root3, lengths=False) + "\n")
    other, _ = random_tree(np.random.default_rng(seed + 1), ntaxa)
    start_path = os.path.join(WORK, f"{tag}.start.tree")
    with open(start_path, "w") as f:
        f.write(newick(other, lengths=False) + "\n")
    return phy, tree_path, start_path


def parse_phase(ctx, tag: str, ntaxa: int, npatterns: int, seed: int):
    """Generate + `cli.parse` -> byteFile; says which compression core
    ran (the committed tree carries no built extension, so: numpy)."""
    import importlib.util

    from examl_tpu.cli import parse as cli_parse
    t0 = time.time()
    phy, tree_path, start_path = make_data(tag, ntaxa, npatterns, seed)
    t_gen = time.time() - t0
    base = os.path.join(WORK, tag)
    t0 = time.time()
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli_parse.main(["-s", phy, "-m", "DNA", "-n", base])
    check(rc == 0, f"cli.parse exited {rc}")
    bytefile = base + ".binary"
    from examl_tpu.io.bytefile import read_bytefile_meta
    meta = read_bytefile_meta(bytefile)
    got = sum(p.width for p in meta.parts)
    core = ("native" if importlib.util.find_spec("examl_tpu._patterncrunch")
            else "numpy")
    emit(ctx, "parse", data=tag, taxa=ntaxa, patterns=got, seed=seed,
         model="GTR+GAMMA(4)", partitions=len(meta.parts),
         compression_core=core, phylip_bytes=os.path.getsize(phy),
         bytefile_bytes=os.path.getsize(bytefile),
         generate_seconds=round(t_gen, 3),
         seconds=round(time.time() - t0, 3))
    check(got == npatterns,
          f"{tag}: {got} compressed patterns, phase names {npatterns}")
    return bytefile, tree_path, start_path


# -- the CLI, in-process -----------------------------------------------------


def cli_instance(bytefile: str, extra=()):
    """The engine exactly as `cli.main._run` builds it: same argument
    parser, same sharding choice, same loader, same constructor."""
    from examl_tpu.cli import main as cli
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.parallel.launch import select_sharding
    args = cli.build_argparser().parse_args(
        ["-s", bytefile, "-n", "SMOKE", *extra])
    sharding = select_sharding(args, args.save_memory,
                               log=lambda m: print(m, file=sys.stderr))
    mult = sharding.num_devices if sharding else 1
    data = cli._load_alignment(bytefile, block_multiple=mult)
    inst = PhyloInstance(
        data, ncat=4, use_median=args.median,
        per_partition_branches=args.per_partition_bl,
        rate_model=args.model, psr_categories=args.categories,
        save_memory=args.save_memory, sharding=sharding,
        block_multiple=mult)
    return inst, data, sharding


def peak_bytes():
    """Per device, the process's high-water mark so far (monotone over
    the phases, so a later phase's line also holds an earlier peak)."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def demotions(counters: dict, events=()) -> dict:
    found = {k: counters[k] for k in DEMOTION_COUNTERS if counters.get(k)}
    for ev in events:
        if ev.get("kind") in DEMOTION_EVENTS:
            found[ev["kind"]] = found.get(ev["kind"], 0) + 1
    return found


def tiers(gauges: dict) -> list:
    pre = "program.model_drift_pct."
    return sorted(k[len(pre):] for k in gauges if k.startswith(pre))


def compile_fields(c: dict) -> dict:
    """Compiles of one run, from its own counters: how many programs,
    how long, which families, and how many were served from the
    persistent cache against compiled fresh."""
    pre = "engine.compile_seconds."
    return {
        "compile_count": int(c.get("engine.compile_count", 0)),
        "compile_seconds": round(c.get("engine.compile_seconds", 0.0), 3),
        "programs_fresh": int(c.get("program.records.fresh", 0)),
        "programs_from_xla_cache": int(c.get("program.records.xla-cache",
                                             0)),
        "program_families": sorted(k[len(pre):] for k in c
                                   if k.startswith(pre)),
    }


def run_cli(name: str, argv: list):
    """`cli.main.main(argv)` with a metrics snapshot + ledger of its
    own; returns (rc, snapshot, ledger events, wall seconds, workdir)."""
    from examl_tpu.cli.main import main as cli_main
    from examl_tpu.obs import ledger
    wd = os.path.join(WORK, name)
    shutil.rmtree(wd, ignore_errors=True)
    os.makedirs(wd)
    metrics = os.path.join(wd, "metrics.json")
    t0 = time.time()
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli_main([*argv, "-n", name, "-w", wd, "--metrics", metrics])
    wall = time.time() - t0
    with open(metrics) as f:
        snap = json.load(f)
    events = ledger.read_events(os.path.join(wd, ledger.MERGED_NAME))
    check(any(ev.get("kind") == "run" for ev in events),
          f"{name}: the run left no ledger to read demotions from")
    return rc, snap, events, wall, wd


def cli_fields(snap: dict, events: list, wall: float) -> dict:
    """The counters every CLI phase reports, from the run's own
    snapshot."""
    c, t = snap.get("counters", {}), snap.get("timers", {})

    def timer(name):
        r = t.get(name)
        return [r["count"], round(r["total_s"], 3)] if r else None

    gp = t.get("engine.grad_pass")
    return {
        "seconds": round(wall, 3),
        **compile_fields(c),
        "dispatch_count": int(c.get("engine.dispatch_count", 0)),
        "grad_pass": timer("engine.grad_pass"),
        "grad_pass_seconds_steady": steady_seconds(gp) if gp else None,
        "dispatch": timer("dispatch"),
        "host_schedule": timer("host_schedule"),
        "tiers": tiers(snap.get("gauges", {})),
        "ledger_events": len(events),
        "demotions": demotions(c, events),
        "oom_events": int(c.get("mem.oom_events", 0)),
        "peak_bytes_in_use": peak_bytes(),
    }


def steady_seconds(timer: dict):
    """Mean of a blocking timer without its slowest reading (the first
    call, which holds the compile); None with fewer than two."""
    if timer["count"] < 2:
        return None
    return round((timer["total_s"] - timer["max_s"])
                 / (timer["count"] - 1), 4)


def log_lnls(wd: str, name: str) -> list:
    with open(os.path.join(wd, f"ExaML_log.{name}")) as f:
        return [float(ln.split()[1]) for ln in f if ln.strip()]


# -- phases ------------------------------------------------------------------


def phase_device(ctx) -> None:
    import jax

    from examl_tpu.config import enable_persistent_compilation_cache
    t0 = time.time()
    cache = enable_persistent_compilation_cache()
    if ctx["rehearse"]:
        # The toys compile in 0.2-0.5 s each, around the cache's
        # threshold: keep every one, so a second rehearsal is warmer
        # whatever the host's speed.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit(ctx, "device", count=ctx["count"], jax=jax.__version__,
         compile_cache=cache,
         jax_compilation_cache_dir_env=os.environ.get(
             "JAX_COMPILATION_CACHE_DIR"),
         seconds=round(time.time() - t0, 3))
    check(cache is not None, "no persistent compile cache")


def start_tree_vs_oracle(ctx, inst, data, tree_path):
    """Full traversal + root evaluation of the start tree on the device
    against tests/oracle.py (f64, NumPy, expm — independent code)."""
    from tests.oracle import oracle_lnl
    with open(tree_path) as f:
        tree = inst.tree_from_newick(f.read())
    t0 = time.time()
    lnl = float(inst.evaluate(tree, full=True))
    t_first = time.time() - t0
    t0 = time.time()
    lnl_again = float(inst.evaluate(tree, full=True))
    t_second = time.time() - t0
    t0 = time.time()
    sys.setrecursionlimit(10000)
    ref = float(oracle_lnl(tree, data, inst.models))
    t_oracle = time.time() - t0
    rel = abs(lnl - ref) / abs(ref)
    fields = dict(lnl_engine=lnl, lnl_oracle=ref, rel_err=rel,
                  rtol=ctx["rtol"],
                  first_evaluation_seconds=round(t_first, 3),
                  second_evaluation_seconds=round(t_second, 4),
                  oracle_seconds=round(t_oracle, 3))
    ok = (np.isfinite(lnl) and lnl == lnl_again and rel <= ctx["rtol"])
    return tree, fields, ok


def engine_fields(inst) -> dict:
    (eng,) = inst.engines.values()
    return {"dtype": str(eng.dtype), "states": eng.K, "rate_cats": eng.R,
            "blocks": eng.B, "lane": eng.lane, "clv_rows": eng.num_rows,
            "clv_arena_bytes": int(eng.clv.nbytes)}


def phase_fullwidth(ctx) -> None:
    from examl_tpu import obs
    from examl_tpu.optimize.branch import smooth_tree
    ntaxa, npat = ctx["size"]("fullwidth")
    bytefile, tree_path, _ = parse_phase(ctx, "fullwidth", ntaxa, npat,
                                         ctx["seed"])
    t_phase = time.time()
    obs.reset()
    inst, data, sharding = cli_instance(bytefile)
    check(sharding is None, "one-chip phase got a sharding")
    tree, fields, ok = start_tree_vs_oracle(ctx, inst, data, tree_path)
    # A few steps: one smoothing sweep = whole-tree gradient passes
    # (ops/gradient.py) + batched Newton updates.
    t0 = time.time()
    smooth_tree(inst, tree, 1)
    t_smooth = time.time() - t0
    lnl_after = float(inst.evaluate(tree, full=True))
    snap = obs.snapshot()
    c, t = snap["counters"], snap["timers"]
    gp = t.get("engine.grad_pass",
               {"count": 0, "total_s": 0.0, "max_s": 0.0})
    dem, eng = demotions(c), engine_fields(inst)
    emit(ctx, "fullwidth", taxa=ntaxa, patterns=npat, **eng,
         **fields, lnl_after_smooth=lnl_after,
         smooth_seconds=round(t_smooth, 3),
         grad_passes=int(gp["count"]),
         grad_pass_seconds_total=round(gp["total_s"], 3),
         grad_pass_seconds_steady=steady_seconds(gp),
         grad_smooth_sweeps=int(c.get("optimize.grad_smooth_sweeps", 0)),
         **compile_fields(c),
         tiers=tiers(snap["gauges"]), demotions=dem,
         oom_events=int(c.get("mem.oom_events", 0)),
         peak_bytes_in_use=peak_bytes(),
         seconds=round(time.time() - t_phase, 3))
    check(ok, f"fullwidth: lnL {fields['lnl_engine']} vs oracle "
              f"{fields['lnl_oracle']} (rel {fields['rel_err']:.3g} > "
              f"{ctx['rtol']}), or not finite / not repeatable")
    check(gp["count"] >= 1, "fullwidth: no gradient pass ran")
    check(np.isfinite(lnl_after) and lnl_after >= fields["lnl_engine"],
          f"fullwidth: lnL fell over a smoothing sweep "
          f"({fields['lnl_engine']} -> {lnl_after})")
    check(not dem, f"fullwidth: demotions {dem}")


def evaluate_run(name: str, bytefile, tree_path, extra=()):
    """`cli.main -f e` + the checks every -f e phase shares."""
    rc, snap, events, wall, wd = run_cli(
        name, ["-s", bytefile, "-t", tree_path, "-f", "e", *extra])
    f = cli_fields(snap, events, wall)
    check(rc == 0, f"{name}: cli.main -f e exited {rc}")
    (lnl_end,) = log_lnls(wd, name)
    return f, lnl_end


def phase_evaluate(ctx) -> None:
    ntaxa, npat = ctx["size"]("evaluate")
    bytefile, tree_path, _ = parse_phase(ctx, "evaluate", ntaxa, npat,
                                         ctx["seed"] + 10)
    # lnl_start: the start tree under the CLI's initial model, from the
    # f64 oracle (the CLI logs only the end of -f e).
    lnl_start = oracle_start_lnl(bytefile, tree_path)
    f, lnl_end = evaluate_run("evaluate", bytefile, tree_path)
    emit(ctx, "evaluate", taxa=ntaxa, patterns=npat,
         lnl_start_oracle=lnl_start, lnl_end=lnl_end, **f)
    check(np.isfinite(lnl_end) and lnl_end > lnl_start,
          f"evaluate: lnl_end {lnl_end} not above lnl_start {lnl_start}")
    check(f["grad_pass"] is not None and f["grad_pass"][0] > 0,
          "evaluate: no gradient pass ran")
    check(not f["demotions"], f"evaluate: demotions {f['demotions']}")


def oracle_start_lnl(bytefile: str, tree_path: str) -> float:
    """f64 NumPy lnL of the start tree under the initial model (GTR all
    ones, empirical frequencies, alpha 1), no device involved."""
    from examl_tpu.cli.main import _load_alignment
    from examl_tpu.models.gtr import build_model
    from examl_tpu.tree.topology import Tree
    from tests.oracle import oracle_lnl
    data = _load_alignment(bytefile)
    models = [build_model(p.datatype, p.empirical_freqs, rates=None,
                          alpha=1.0, ncat=4, use_median=False)
              for p in data.partitions]
    with open(tree_path) as f:
        tree = Tree.from_newick(f.read(), data.taxon_names, 1)
    sys.setrecursionlimit(10000)
    return float(oracle_lnl(tree, data, models))


def phase_search(ctx) -> None:
    ntaxa, nsites = ctx["size"]("search")
    bytefile, _, start_path = parse_phase(ctx, "search", ntaxa, nsites,
                                          ctx["seed"] + 20)
    rc, snap, events, wall, wd = run_cli(
        "search", ["-s", bytefile, "-t", start_path, "-f", "d",
                   "-p", "12345"])
    f = cli_fields(snap, events, wall)
    lnls = log_lnls(wd, "search") if rc == 0 else []
    c = snap.get("counters", {})
    emit(ctx, "search", taxa=ntaxa, sites=nsites,
         lnl_first=lnls[0] if lnls else None,
         lnl_last=lnls[-1] if lnls else None, log_rows=len(lnls),
         spr_cycles=int(c.get("search.spr_cycles", 0)), **f)
    check(rc == 0, f"search: cli.main -f d exited {rc}")
    # "Rises": no row below its predecessor by more than f32 last-place
    # noise of the sum (the engine computes and prints lnL in f32).
    check(len(lnls) >= 2 and all(np.isfinite(lnls))
          and all(b >= a - 2e-6 * abs(a) for a, b in zip(lnls, lnls[1:]))
          and lnls[-1] > lnls[0],
          f"search: lnL does not rise monotonically in ExaML_log: {lnls}")
    need = {"scan", "thscan", "newton", "trav_eval"}
    missing = need - set(f["program_families"])
    check(not missing, f"search: programs never compiled+ran: {missing}")
    check(not f["demotions"], f"search: demotions {f['demotions']}")


def phases_four_chips(ctx) -> None:
    """`placement`, `sharded`, `single` — the whole of `--chips 4`."""
    import jax
    ntaxa, npat = ctx["size"]("four")
    bytefile, tree_path, _ = parse_phase(ctx, "four", ntaxa, npat,
                                         ctx["seed"] + 10)
    # placement: the engine the plain CLI path builds on four devices.
    t_phase = time.time()
    inst, data, sharding = cli_instance(bytefile)
    check(sharding is not None and sharding.num_devices == 4,
          "placement: the plain CLI path did not shard over 4 devices")
    (eng,) = inst.engines.values()
    shards = [(str(s.device), int(s.data.nbytes))
              for s in eng.clv.addressable_shards]
    tree, fields, ok = start_tree_vs_oracle(ctx, inst, data, tree_path)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    emit(ctx, "placement", taxa=ntaxa, patterns=npat, count=ctx["count"],
         **engine_fields(inst), **fields, arena_shards=shards,
         bytes_in_use=in_use, peak_bytes_in_use=peak_bytes(),
         seconds=round(time.time() - t_phase, 3))
    check(len({d for d, _ in shards}) == 4
          and len({n for _, n in shards}) == 1 and shards[0][1] > 0,
          f"placement: arena shards not equal+non-zero on 4 devices: "
          f"{shards}")
    if not ctx["rehearse"]:          # the CPU backend reports no stats
        check(all(b is not None and b >= shards[0][1] for b in in_use),
              f"placement: a device does not hold its share: {in_use}")
    check(ok, f"placement: lnL {fields['lnl_engine']} vs oracle "
              f"{fields['lnl_oracle']} (rel {fields['rel_err']:.3g})")
    del inst, eng, tree

    # sharded (the default: no mesh option) against --single-device.
    out = {}
    for name, extra in (("sharded", ()), ("single", ("--single-device",))):
        f, out[name] = evaluate_run(name, bytefile, tree_path, extra)
        emit(ctx, name, taxa=ntaxa, patterns=npat, count=ctx["count"],
             lnl_end=out[name], **f)
        check(np.isfinite(out[name]), f"{name}: lnl_end not finite")
        check(not f["demotions"], f"{name}: demotions {f['demotions']}")
    rel = abs(out["sharded"] - out["single"]) / abs(out["single"])
    emit(ctx, "sharded_vs_single", lnl_sharded=out["sharded"],
         lnl_single=out["single"], rel_diff=rel, rtol=AGREE_RTOL)
    check(rel <= AGREE_RTOL,
          f"sharded {out['sharded']} vs single {out['single']}: rel {rel}")


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on CPU at toy sizes (control flow only)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--phases", default=",".join(ONE_CHIP_PHASES),
                    help="one-chip phases to run, comma list "
                         "(--chips 4 always runs its three)")
    ap.add_argument("--rtol", type=float, default=RTOL,
                    help="engine-vs-oracle relative tolerance")
    a = ap.parse_args(argv)

    one_chip = {"fullwidth": phase_fullwidth, "evaluate": phase_evaluate,
                "search": phase_search}
    phases = a.phases.split(",")
    bad = [p for p in phases if p not in one_chip]
    if bad:
        ap.error(f"unknown phase(s) {bad}; known: {ONE_CHIP_PHASES}")

    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={a.chips}"])
        # Steer the CPU down the branch a TPU placement takes by default
        # (search/spr.py gates the batched SPR scans to accelerators).
        os.environ["EXAML_BATCH_SCAN"] = "1"
        os.environ["EXAML_BATCH_THOROUGH"] = "1"
    import jax
    devs = jax.devices()
    ctx = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "rehearse": a.rehearse, "seed": a.seed,
           "rtol": a.rtol, "lines": [],
           "size": lambda ph: SIZES[ph][1 if a.rehearse else 0]}
    if not a.rehearse and ctx["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator (platform {ctx['platform']!r}); "
            "on CPU only --rehearse runs")
    check(ctx["count"] == a.chips,
          f"--chips {a.chips} but JAX reports {ctx['count']} device(s)")

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.time()
    phase_device(ctx)
    if a.chips == 4:
        phases_four_chips(ctx)
    else:
        for ph in phases:
            one_chip[ph](ctx)
    emit(ctx, "total", seconds=round(time.time() - t0, 3))
    last = json.dumps({"ok": True, "device": {
        "platform": ctx["platform"], "kind": ctx["kind"],
        "count": ctx["count"]}})
    os.makedirs(OUT_DIR, exist_ok=True)
    name = "chip_smoke" + ("_4chip" if a.chips == 4 else "") \
        + ("_rehearse" if a.rehearse else "") + ".jsonl"
    with open(os.path.join(OUT_DIR, name), "w") as f:
        f.write("\n".join(ctx["lines"] + [last]) + "\n")
    print(last, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
