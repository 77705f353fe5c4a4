#!/usr/bin/env python
"""Universal-interpreter smoke: zero-recompile serving + warm ratio.

Two phases, both on a synthetic CPU fixture:

1. WARM-DISPATCH RATIO (in-process): one instance, one topology; the
   warm specialized bounded-chunk dispatch vs the warm universal
   interpreter dispatch on the same tree.  The acceptance bar is
   ratio <= 1.3 (ISSUE 10 / ROADMAP item 5); CPU smokes RECORD the
   ratio in the output JSON, `--require-ratio F` gates on it.

2. ZERO-RECOMPILE SERVING (real CLI `--serve` session): the jobs file
   carries >= 3 topologies whose fastpath profiles were never seen by
   any program — each would have minted its own specialized compile
   before the interpreter tier.  Asserts:
     * zero search/fleet-phase compiles after universal warmup (no
       ledger `compile` start after the first job finished — the
       warmup is the first job's universal-program compile);
     * no `fast`/`fleet` family (per-profile) compiles at all;
     * engine.first_calls.unbanked == 0;
     * fleet.profile_misses >= 3 (the profiles really were distinct)
       and every job dispatched through the interpreter;
     * per-job lnL agrees with a bounded-chunk tier re-evaluation at
       the results table's 1e-6 resolution (the bitwise matrix lives
       in tests/test_universal.py);
     * tools/run_report.py and tools/top.py render the universal row.

    python tools/universal_smoke.py                    # CI smoke
    python tools/universal_smoke.py --require-ratio 1.3

Exit 0 = all assertions held; 1 = evidence missing or parity broken.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_fixture(workdir: str, ntaxa: int, nsites: int):
    import numpy as np

    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile
    rng = np.random.default_rng(7)
    cur = rng.integers(0, 4, nsites)
    seqs = []
    for _ in range(ntaxa):
        flip = rng.random(nsites) < 0.15
        cur = np.where(flip, rng.integers(0, 4, nsites), cur)
        seqs.append("".join("ACGT"[c] for c in cur))
    data = build_alignment_data([f"t{i}" for i in range(ntaxa)], seqs)
    path = os.path.join(workdir, "a.binary")
    write_bytefile(path, data)
    return data, path


def distinct_profile_trees(inst, want: int):
    """Newicks of trees with pairwise-DISTINCT fastpath profiles (each
    would be its own specialized jit key / compile)."""
    from examl_tpu.ops import fastpath
    out, seen = [], set()
    for seed in range(100):
        tree = inst.random_tree(seed)
        p = tree.centroid_branch()
        if tree.is_tip(p.number):
            p = p.back
        st = fastpath.build_structure(
            tree.flat_full_traversal(p), inst.alignment.ntaxa,
            min(e.trav_row_bytes() for e in inst.engines.values()))
        if st.profile in seen:
            continue
        seen.add(st.profile)
        out.append((tree.to_newick(inst.alignment.taxon_names), tree))
        if len(out) >= want:
            return out
    raise SystemExit(f"fixture cannot mint {want} distinct profiles")


def measure_ratio(data, reps: int) -> dict:
    """Warm universal dispatch vs warm specialized dispatch, same
    instance, same topology (compiles excluded on both sides)."""
    from examl_tpu.instance import PhyloInstance
    inst = PhyloInstance(data)
    (eng,) = inst.engines.values()
    tree = inst.random_tree(3)

    def warm_best(label):
        inst.evaluate(tree, full=True)          # compile / warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            inst.evaluate(tree, full=True)
            best = min(best, time.perf_counter() - t0)
        return best

    t_spec = warm_best("chunk")
    eng.universal_force = True
    t_uni = warm_best("universal")
    eng.universal_force = False
    return {"t_specialized_s": round(t_spec, 6),
            "t_universal_s": round(t_uni, 6),
            "warm_dispatch_ratio": round(t_uni / t_spec, 3)
            if t_spec > 0 else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ntaxa", type=int, default=24)
    ap.add_argument("--nsites", type=int, default=600)
    ap.add_argument("--jobs", type=int, default=4,
                    help="distinct-profile serve jobs (>= 3)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None,
                    help="evidence JSON (default <workdir>/"
                         "UNIVERSAL_BENCH.json)")
    ap.add_argument("--require-ratio", type=float, default=None,
                    metavar="F", help="fail unless warm universal <= "
                    "F x specialized (quiet hosts; CI records only)")
    args = ap.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="universal_smoke_")
    os.makedirs(workdir, exist_ok=True)
    data, bf = build_fixture(workdir, args.ntaxa, args.nsites)
    failures = []

    # -- phase 1: warm-dispatch ratio ------------------------------------
    ratio = measure_ratio(data, args.reps)
    print(f"warm dispatch: specialized {ratio['t_specialized_s']*1e3:.2f}ms"
          f"  universal {ratio['t_universal_s']*1e3:.2f}ms"
          f"  ratio {ratio['warm_dispatch_ratio']}")
    if args.require_ratio is not None and \
            ratio["warm_dispatch_ratio"] > args.require_ratio:
        failures.append(
            f"warm universal dispatch {ratio['warm_dispatch_ratio']}x "
            f"specialized exceeds --require-ratio {args.require_ratio}")

    # -- phase 2: zero-recompile serving through the real CLI ------------
    from examl_tpu.instance import PhyloInstance
    inst0 = PhyloInstance(data)
    jobs = distinct_profile_trees(inst0, max(3, args.jobs))
    jobs_path = os.path.join(workdir, "jobs.jsonl")
    with open(jobs_path, "w") as f:
        for i, (nwk, _tree) in enumerate(jobs):
            f.write(json.dumps({"kind": "eval", "id": f"u{i}",
                                "newick": nwk}) + "\n")
        f.write('{"op": "stop"}\n')

    from examl_tpu.cli.main import main as cli_main
    metrics_path = os.path.join(workdir, "metrics.json")
    rc = cli_main(["-s", bf, "-n", "USMOKE", "-p", "1", "-w", workdir,
                   "--serve", jobs_path, "--serve-poll", "0",
                   "--metrics", metrics_path])
    if rc != 0:
        print(f"UNIVERSAL-SMOKE FAIL: --serve CLI run rc={rc}")
        return 1

    with open(metrics_path) as f:
        snap = json.load(f)
    c = snap.get("counters") or {}
    if c.get("engine.first_calls.unbanked", 0):
        failures.append("engine.first_calls.unbanked != 0")
    if c.get("fleet.profile_misses", 0) < 3:
        failures.append(f"fleet.profile_misses = "
                        f"{c.get('fleet.profile_misses', 0)} < 3")
    if c.get("engine.universal_dispatches", 0) < len(jobs):
        failures.append("not every job dispatched the interpreter "
                        f"({c.get('engine.universal_dispatches', 0)} "
                        f"< {len(jobs)})")

    from examl_tpu.obs import ledger as _ledger
    events = _ledger.read_dir(workdir)
    first_done = next((i for i, e in enumerate(events)
                       if e.get("kind") == "job.done"), None)
    if first_done is None:
        failures.append("no job.done ledger events")
    else:
        late = [e for e in events[first_done:]
                if e.get("kind") == "compile"
                and e.get("status") == "start"]
        if late:
            failures.append(
                "compiles AFTER universal warmup (first finished job): "
                + ", ".join(e.get("family", "?") for e in late))
    per_profile = [e for e in events if e.get("kind") == "compile"
                   and e.get("family") in ("fast", "fleet")]
    if per_profile:
        failures.append(f"{len(per_profile)//2 or 1} per-profile "
                        "(fast/fleet family) compile events — the "
                        "interpreter was bypassed")
    news = [e for e in events if e.get("kind") == "job.profile_new"]
    if len(news) < 3:
        failures.append(f"only {len(news)} job.profile_new events")

    # -- parity vs the bounded-chunk tier --------------------------------
    table_path = os.path.join(workdir, "ExaML_fleet.USMOKE")
    rows = {}
    with open(table_path) as f:
        for line in f:
            if line.startswith("#") or not line.strip():
                continue
            parts = line.split()
            rows[parts[0]] = {"lnl": float(parts[5]),
                              "status": parts[6]}
    for i, (nwk, _tree) in enumerate(jobs):
        row = rows.get(f"u{i}")
        if row is None or row["status"] != "done":
            failures.append(f"job u{i} missing/not done in results")
            continue
        lnl = inst0.evaluate(inst0.tree_from_newick(nwk), full=True)
        if abs(lnl - row["lnl"]) > 5e-6:       # table rounds at 1e-6
            failures.append(f"job u{i}: universal {row['lnl']} vs "
                            f"chunk tier {lnl}")

    # -- report tools render the universal row ---------------------------
    import subprocess
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_report.py"),
         "--metrics", metrics_path, "--ledger", workdir],
        capture_output=True, text=True)
    if rep.returncode != 0 or "universal" not in rep.stdout:
        failures.append("run_report.py did not render a universal row")
    topp = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "top.py"),
         "--workdir", workdir, "--metrics", metrics_path, "--once"],
        capture_output=True, text=True)
    if topp.returncode not in (0, 3) or "uni" not in topp.stdout:
        failures.append("top.py --once did not render the universal "
                        "tail")

    evidence = {
        "kind": "universal_smoke", "ntaxa": args.ntaxa,
        "nsites": args.nsites, "jobs": len(jobs),
        "profile_misses": int(c.get("fleet.profile_misses", 0)),
        "universal_dispatches":
            int(c.get("engine.universal_dispatches", 0)),
        "unbanked_first_calls":
            int(c.get("engine.first_calls.unbanked", 0)),
        "compile_count": int(c.get("engine.compile_count", 0)),
        **ratio,
    }
    out_path = args.out or os.path.join(workdir, "UNIVERSAL_BENCH.json")
    with open(out_path, "w") as f:
        json.dump(evidence, f, indent=2, sort_keys=True)
    print(f"evidence -> {out_path}")

    if failures:
        print("UNIVERSAL-SMOKE FAIL:")
        for msg in failures:
            print(f"  - {msg}")
        return 1
    print(f"UNIVERSAL-SMOKE OK: {len(jobs)} unseen profiles served with "
          "zero post-warmup compiles "
          f"(ratio {ratio['warm_dispatch_ratio']}x)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
