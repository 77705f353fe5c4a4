#!/usr/bin/env python3
"""The benchmark: timed steps of the CLI's own optimiser on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
                             --trace <0|1>

One process, JAX touched only here.  Everything about a cell is data:
`BENCHMARK.json` names the cell's configuration and traffic,
`configs/<configuration>.json` holds the sizes, `traffic/<traffic>.json`
the step kind and its parameters, `steps/<kind>.py` the step,
`layers/<metric>.json` each per-layer metric with the reader under
`readers/` that takes it (and, where it states `chips`, the cells that
read it: `metrics_of`), `correct/<cell>.json` the limits of the
comparison with the plain reference, `peaks.json` the chip's peaks.
The configuration also says what the alignment is: `parts` (a list of
partitions with a model, a width and a generating model each; without
it ONE part made of the configuration's own keys, `datagen.parts_of`)
and `cli_args` (appended to the program's own command line, e.g.
`-M`).  `--manifest FILE` reads another manifest than `BENCHMARK.json`
(the tests' fixture, a draft cell): its `traffic/` and `correct/` files
are looked for beside it first, then here.

Set-up (counted in `setup_s`, process start to the window's first
step): imports, inputs from `--seed` by the benchmark's own generator,
`cli.parse` -> byteFile -> the engine built as `cli.main._run` builds
it, the compile cache where `JAX_COMPILATION_CACHE_DIR` says or else
`<checkout>/.xla_cache`, and a warm-up of every distinct step.  The
seconds inside the first `jax.devices()` are left out of `setup_s` and
printed beside it: the TPU runtime's start-up is no file of this
repository's to change, and on the v5e it moved between 5 and 11 s from
one run to the next (PR 27), more than all the rest of a narrow cell's
set-up moves.  Then whole steps for `--seconds`
(benchmarks/window.py); then, outside the window, the f64 reference on a
sample of the states the timed steps left (benchmarks/reference.py).

No chip, fewer chips than the cell asks for, or a chip that
`peaks.json` does not know: exit non-zero, no result line.  The only
CPU path is `--rehearse` (tiny sizes, for the tests; its line says so).
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import datagen, reference, tracereduce, window  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
TRACE_SECONDS = 5.0     # the profiler covers whole steps up to about this

# Counters that mean a step gave way somewhere (a tier, a family, a
# device, memory) and so did other work than the cell names: the list of
# chip_smoke.py (PR 23), read per step.
DEMOTION_COUNTERS = (
    "bank.fallbacks", "fleet.device_degraded", "engine.watchdog_barks",
    "mem.oom_events", "optimize.grad_smooth_fallbacks",
    "engine.nonfinite_retries", "engine.universal_ineligible")


def fail(msg: str):
    raise SystemExit(f"benchmarks/run.py: {msg}")


def read_json(*parts):
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        fail(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def cell_file(manifest: str, kind: str, name: str) -> str:
    """`<kind>/<name>.json` beside the manifest where it is there, else
    the benchmark's own."""
    beside = os.path.join(os.path.dirname(os.path.join(ROOT, manifest)),
                          kind, name + ".json")
    return beside if os.path.isfile(beside) else os.path.join(
        HERE, kind, name + ".json")


def find_cell(name: str, manifest_file: str = "BENCHMARK.json"):
    """(manifest, cell, configuration, traffic) for a workload name."""
    manifest = read_json(ROOT, manifest_file)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = read_json(ROOT, entry["file"])
    traffic = read_json(cell_file(manifest_file, "traffic", cell["traffic"]))
    return manifest, cell, config, traffic


def stated(config: dict) -> dict:
    """A configuration that lists `parts` states their sum as `patterns`
    and their count as `partitions`."""
    if "parts" not in config:
        return config
    return {**config, "partitions": len(config["parts"]),
            "patterns": sum(p["patterns"] for p in config["parts"])}


def metrics_of(manifest, group: str, cell_name: str):
    """The metrics of a group that are read in a cell: every metric
    without a `workloads` list, and one with a list where the list names
    the cell.  A metric whose `layers/` file states `chips` is read in
    every cell on as many chips, named by the list yet or not: the list
    is then that rule over the manifest's cells, written out for the
    driver (tests/benchmarks holds the two equal), and a cell added
    later reads the metric by its own entry alone."""
    chips = {w["name"]: w["chips"]
             for w in manifest.get("workloads", [])}.get(cell_name)

    def read_here(m):
        layer = os.path.join(HERE, "layers", m["name"] + ".json")
        if chips is not None and os.path.isfile(layer):
            rule = read_json(layer).get("chips")
            if rule is not None:
                return rule == chips
        return cell_name in m.get("workloads", [cell_name])

    return [m for m in manifest[group] if read_here(m)]


# -- device ------------------------------------------------------------------


def claim_device(chips: int, rehearse: bool):
    """Touch JAX; refuse anything but the chips the cell asks for."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={chips}"])
    import jax
    t0 = time.time()
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        fail(f"JAX found no device: {exc}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "init_s": time.time() - t0}
    if rehearse:
        return dev, None
    if dev["platform"] != "tpu":
        fail(f"no accelerator (platform {dev['platform']!r}); on a CPU "
             "only --rehearse runs")
    if dev["count"] != chips:
        fail(f"the cell asks for {chips} chip(s), JAX reports "
             f"{dev['count']}")
    return dev, peak_for(dev["kind"])


def peak_for(kind: str) -> dict:
    """The chip's peaks with their source; an unknown chip is an error,
    not a default."""
    peaks = read_json(HERE, "peaks.json")
    if kind not in peaks:
        fail(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return peaks[kind]


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


# -- inputs ------------------------------------------------------------------


def make_inputs(config: dict, traffic: dict, tag: str, seed: int):
    """The cell's problem (made once in a checkout, from the
    configuration's `data_seed`) as this seed presents it, parsed to a
    byteFile; returns (datagen's dict, byteFile path)."""
    from examl_tpu.cli import parse as cli_parse
    os.makedirs(CACHE, exist_ok=True)
    # `problem-<tag>.npz`, the name before parts existed, held one model:
    # such a file is left alone and the problem made again, to the same
    # bytes (tests/benchmarks/test_partitioned.py pins them)
    base = os.path.join(CACHE, f"problem-{tag}.parts.npz")
    if os.path.isfile(base):
        with np.load(base) as z:
            prob = {"patterns": z["patterns"], "tree": str(z["tree"]),
                    "moved_trees": [str(t) for t in z["moved_trees"]],
                    "models": [{"rates": z[f"rates_{k}"],
                                "freqs": z[f"freqs_{k}"],
                                "alpha": float(z[f"alpha_{k}"])}
                               for k in range(len(z["bounds"]))],
                    "bounds": [tuple(b) for b in z["bounds"].tolist()]}
    else:
        prob = datagen.problem(
            config, traffic.get("trees", 0), traffic.get("spr_moves", 0),
            traffic.get("branch_lengths") == "generating")
        tmp = base + f".{os.getpid()}.npz"
        np.savez(tmp, patterns=prob["patterns"], tree=prob["tree"],
                 moved_trees=np.array(prob["moved_trees"], dtype=str),
                 bounds=np.array(prob["bounds"]),
                 **{f"{key}_{k}": m[key]
                    for k, m in enumerate(prob["models"]) for key in m})
        os.replace(tmp, base)
    gen = datagen.present(prob, seed)
    wd = os.path.join(CACHE, f"{tag}-{seed}")
    shutil.rmtree(wd, ignore_errors=True)
    os.makedirs(wd)
    aln = os.path.join(wd, "aln")
    datagen.write_phylip(aln + ".phy", gen["patterns"], config["datatype"])
    argv = ["-s", aln + ".phy", "-n", aln, "-m", config["parse"]["model"]]
    if "parts" in config:
        lines = [f"{p['model']}, {p['name']} = {s + 1}-{e}"
                 for p, (s, e) in zip(config["parts"], gen["bounds"])]
    elif config["parse"].get("partition_line"):
        lines = [config["parse"]["partition_line"].format(
            patterns=config["patterns"])]
    else:
        lines = []
    if lines:
        with open(aln + ".model", "w") as f:
            f.write("\n".join(lines) + "\n")
        argv += ["-q", aln + ".model"]
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli_parse.main(argv)
    if rc != 0:
        fail(f"cli.parse exited {rc}")
    os.remove(aln + ".phy")
    return gen, aln + ".binary", wd


def build_instance(bytefile: str, cli_args=()):
    """The engine exactly as `cli.main._run` builds it: same argument
    parser, same sharding choice, same loader, same constructor (a copy
    of chip_smoke.py's `cli_instance`, PR 23).  `cli_args` are the
    configuration's own flags (`-M`), after the two every run has."""
    from examl_tpu.cli import main as cli
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.parallel.launch import select_sharding
    args = cli.build_argparser().parse_args(
        ["-s", bytefile, "-n", "BENCH", *cli_args])
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
    return inst, data


def stated_precision(inst) -> dict:
    (eng,) = inst.engines.values()
    short = {"float32": "f32", "float64": "f64", "bfloat16": "bf16"}
    return {"compute_dtype": short[str(eng.dtype)],
            "clv_dtype": short[str(eng.storage_dtype)],
            "dot_precision": eng.fast_precision.name.lower()}


# -- steps -------------------------------------------------------------------


def capture(tree, inst):
    """What a step answered, read from the program's containers: every
    edge with the z of every branch-length class the instance has
    (`[E, 2 + C]`; C = 1 without `-M`), every partition's model as the
    step left it, and the partitions' lnLs as the step's last evaluation
    left them.  The reference is given z, alpha and (DNA) the
    exchangeabilities, which a step optimises; frequencies and a protein
    table are read only to be held against the benchmark's own
    (`model_table_err`)."""
    C = inst.num_branch_slots
    edges = np.array([(p.number, q.number, *p.z[:C])
                      for p, q in tree.all_branches()], dtype=np.float64)
    return {"edges": edges,
            "models": [{"rates": np.array(m.rates, dtype=np.float64),
                        "freqs": np.array(m.freqs, dtype=np.float64),
                        "alpha": float(m.alpha)} for m in inst.models],
            "part_lnl": np.array(inst.per_partition_lnl, dtype=np.float64)}


def state_key(lnl: float, st: dict) -> str:
    """One part and one class hash the bytes they hashed before parts
    existed, so `sample_states` draws the states it drew."""
    h = hashlib.sha1(np.float64(lnl).tobytes())
    h.update(st["edges"].tobytes())
    for m in st["models"]:
        h.update(m["rates"].tobytes())
        h.update(m["freqs"].tobytes())
        h.update(np.float64(m["alpha"]).tobytes())
    return h.hexdigest()


def run_window(cell, kind, cycle: int, seconds: float, trace_dir):
    """The measured window: whole cycles of `cycle` steps.  Returns
    (spans, per-step records)."""
    import jax

    from examl_tpu import obs
    records = []
    tracing = [trace_dir is not None]

    def demotions():
        return {k: obs.counter(k) for k in DEMOTION_COUNTERS}

    def one(i):
        before = demotions()
        rec = {"ok": False}
        records.append(rec)
        try:
            ann = (jax.profiler.TraceAnnotation("bench:step")
                   if tracing[0] else contextlib.nullcontext())
            with ann:
                tree, lnl, lnl_before = kind.step(cell, i)
        except Exception:                  # noqa: BLE001 - a failed step
            # is counted and the window goes on; the traceback is kept
            rec["why"] = traceback.format_exc(limit=6)
            print(rec["why"], file=sys.stderr)
            return
        moved = {k: v - before[k] for k, v in demotions().items()
                 if v != before[k]}
        if not np.isfinite(lnl):
            rec["why"] = f"lnL {lnl} is not finite"
        elif moved:
            rec["why"] = f"ran on a demoted path: {moved}"
        elif lnl < lnl_before:
            rec["why"] = f"lnL fell over the step: {lnl_before} -> {lnl}"
        else:
            rec["ok"] = True
        rec["lnl"] = lnl
        rec["state"] = capture(tree, cell.inst)

    def between(n_done, elapsed, longest):
        if tracing[0] and elapsed + longest > TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing[0] = False
            cell.traced_steps = n_done

    if tracing[0]:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        spans = window.run_window(one, seconds, cycle=cycle,
                                  on_cycle=between)
    finally:
        if tracing[0]:
            jax.profiler.stop_trace()
            cell.traced_steps = len(records)
    return spans, records


# -- the comparison with the plain reference ---------------------------------


def sample_states(records, spans, n: int, seed: int):
    """Indices of the steps whose states are compared: the longest
    step's, and of the other distinct states a sample drawn from the
    seed, `n` in all."""
    done = [i for i, r in enumerate(records) if "state" in r]
    by_key = {}
    for i in done:
        by_key.setdefault(
            state_key(records[i]["lnl"], records[i]["state"]), i)
    if not done:
        return []
    longest = max(done, key=lambda i: spans[i][1] - spans[i][0])
    first = state_key(records[longest]["lnl"], records[longest]["state"])
    rest = sorted(k for k in by_key if k != first)
    np.random.default_rng([seed, 0xC0FFEE]).shuffle(rest)
    return [by_key[k] for k in [first] + rest[:max(0, n - 1)]]


def own_model(config: dict, patterns) -> dict:
    """What is no free parameter, made by the benchmark: empirical
    frequencies from the generator's matrix (a part's own columns), and
    the published table of a protein model.  `config` holds `states` and,
    where the rates are not free, `exchangeabilities`."""
    own = {"freqs": reference.empirical_freqs(patterns, None,
                                              config["states"])}
    if config.get("exchangeabilities"):
        own["rates"] = np.array(read_json(
            HERE, "models", config["exchangeabilities"] + ".json")["rates"])
    return own


def table_err(own: dict, st: dict) -> float:
    """How far the program's frequencies (absolute) and fixed
    exchangeabilities (relative, after the best common scale) are from
    the benchmark's own."""
    err = float(np.abs(st["freqs"] - own["freqs"]).max())
    if "rates" in own:
        scale = (st["rates"] @ own["rates"]) / (own["rates"] @ own["rates"])
        err = max(err, float(np.abs(st["rates"] / scale / own["rates"]
                                    - 1.0).max()))
    return err


def check_states(records, chosen, gen, config, limits):
    """Run the f64 reference on the chosen states the timed steps left:
    the sum over the parts of `reference.evaluate` on the part's own
    columns with its alpha, its free rates or its table, its OWN
    frequencies and the z of its class.  `lnl_rel_err` is the largest of
    the total's error and, where there are several, every part's (the
    program's `per_partition_lnl[k]` against the reference's part k: a
    model on the wrong partition that the total forgives does not pass);
    `newton_dz_max` the largest over the classes; `model_table_err` the
    largest over the parts.  Returns {number: (reading, limit)} and
    whether all hold."""
    names = ("lnl_rel_err", "newton_dz_max", "model_table_err")
    # no step left a state: nothing was shown to be correct
    numbers = {k: 0.0 if chosen else float("inf") for k in names}
    dom = config["domain"]
    patterns, bounds = gen["patterns"], gen["bounds"]
    owns = [own_model({"states": config["states"], **part},
                      patterns[:, s:e])
            for part, (s, e) in zip(datagen.parts_of(config), bounds)]
    for i in chosen:
        r = records[i]
        st = r["state"]
        edges = [(int(row[0]), int(row[1]), *map(float, row[2:]))
                 for row in st["edges"]]
        t0 = time.time()
        ref, part_refs, d1, d2 = reference.evaluate_parts(
            patterns, bounds, edges, patterns.shape[0],
            [(own.get("rates", m["rates"]), own["freqs"], m["alpha"])
             for own, m in zip(owns, st["models"])],
            config["rate_categories"])
        errs = [abs(r["lnl"] - ref) / abs(ref)]
        if len(owns) > 1:
            errs += [abs(got - want) / abs(want)
                     for got, want in zip(st["part_lnl"], part_refs)]
        got = {"lnl_rel_err": float(max(errs)),
               "newton_dz_max": max(float(reference.newton_dz(
                   [(e[0], e[1], e[2 + c]) for e in edges], d1[c], d2[c],
                   dom["z_min"], dom["z_max"]).max())
                   for c in range(len(d1))),
               "model_table_err": max(table_err(own, m)
                                      for own, m in zip(owns, st["models"]))}
        print(f"reference: step {i} lnL {r['lnl']!r} reference {ref!r} "
              + " ".join(f"{k} {v:.3e}" for k, v in got.items())
              + (f" (total {errs[0]:.3e}, largest part "
                 f"{int(np.argmax(errs[1:]))} {max(errs[1:]):.3e})"
                 if len(errs) > 1 else "")
              + f" ({time.time() - t0:.1f} s)", file=sys.stderr)
        for k, v in got.items():
            numbers[k] = max(numbers[k], v)
    which = "rehearse_limit" if config.get("rehearsed") else "limit"
    check = {k: [v, limits[k][which]] for k, v in numbers.items()}
    return check, all(v <= lim for v, lim in check.values())


# -- main --------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, keep_trace: str | None = None,
             control_env: dict | None = None,
             data_seed: int | None = None,
             manifest_file: str = "BENCHMARK.json") -> dict:
    """One run of one cell; returns the result (the last line's dict).
    `control_env` and `data_seed` are for benchmarks/calibrate.py only:
    the first sets the program's lower-precision switches, and the run
    then does not hold the program to the precision the configuration
    states; the second draws another problem than the configuration's."""
    marks = [("start", T_START)]

    def mark(name):
        marks.append((name, time.time()))

    manifest, cell_entry, config, traffic = find_cell(workload,
                                                      manifest_file)
    limits = read_json(cell_file(manifest_file, "correct", workload))
    if rehearse:
        config = {**config, **config["rehearse"], "rehearsed": True}
    config = stated(config)
    dev, peak = claim_device(cell_entry["chips"], rehearse)
    device_init_s = dev.pop("init_s")
    mark("import+device")
    for k, v in (control_env or {}).items():
        os.environ[k] = v

    from examl_tpu import obs
    from examl_tpu.config import enable_persistent_compilation_cache
    cache = enable_persistent_compilation_cache()
    if cache is None:
        fail("no persistent compile cache")
    tag = f"{cell_entry['config']}-{cell_entry['traffic']}" + (
        "-rehearse" if rehearse else "")
    if data_seed is not None:
        config = {**config, "data_seed": data_seed}
        tag += f"-d{data_seed}"
    gen, bytefile, workdir = make_inputs(config, traffic, tag, seed)
    mark("inputs+parse")
    obs.reset()
    inst, data = build_instance(bytefile, config.get("cli_args", ()))
    shutil.rmtree(workdir, ignore_errors=True)    # write little, keep less
    mark("load+engine")
    got = [p.width for p in data.partitions]
    want = [p["patterns"] for p in datagen.parts_of(config)]
    if (data.ntaxa, got, len(got)) != (config["taxa"], want,
                                       config["partitions"]):
        fail(f"loaded {data.ntaxa} taxa x {got} patterns in "
             f"{len(got)} partition(s); the configuration states "
             f"{config['taxa']} x {want} in {config['partitions']}")
    # zero-weight lanes that pad every partition to whole blocks
    lanes = sum(b.num_sites for b in inst.buckets.values())
    padding_share = 1.0 - sum(got) / lanes
    precision = stated_precision(inst)
    if control_env is None and not rehearse and any(
            precision[k] != v for k, v in config["precision"].items()):
        fail(f"the program runs at {precision}, the configuration states "
             f"{config['precision']}")

    cell = types.SimpleNamespace(
        inst=inst, data=data, gen=gen, config=config,
        initial_models=list(inst.models), traced_steps=0)
    kind = importlib.import_module(f"benchmarks.steps.{traffic['kind']}")
    obs.set_annotations(trace)
    cycle = kind.prepare(cell, traffic)
    for k in range(cycle):
        kind.warm(cell, k)

    mark("warm-up")
    snap0 = obs.registry().snapshot()
    setup_s = time.time() - T_START - device_init_s
    print("setup_s %.2f without device start-up %.2f: " % (
        setup_s, device_init_s) + ", ".join(
        f"{b[0]} {b[1] - a[1]:.2f}" for a, b in zip(marks, marks[1:]))
        + "; compile_s %.2f" % snap0["counters"].get(
            "engine.compile_seconds", 0.0)
        + "; %d patterns in %d lanes" % (sum(got), lanes), file=sys.stderr)
    trace_dir = os.path.join(CACHE, f"trace-{tag}-{seed}") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    spans, records = run_window(cell, kind, cycle, seconds, trace_dir)
    snap1 = obs.registry().snapshot()
    chosen = sample_states(records, spans, traffic["check_states"], seed)
    mem_peak = 0 if rehearse else memory_peak_bytes()
    traced_steps = cell.traced_steps
    del cell, inst

    run = {"spans": spans, "config": config, "peak": peak,
           "counters0": snap0["counters"], "counters1": snap1["counters"],
           "timers0": snap0["timers"], "timers1": snap1["timers"],
           "memory_peak_bytes": mem_peak}
    device = {**dev, "memory_peak_bytes": mem_peak}
    result = {"correct": False,
              "attempted": len(records),
              "failed": sum(not r["ok"] for r in records),
              "metrics": {}, "device": device}
    if trace:
        layers = {m["name"]: read_json(HERE, "layers", m["name"] + ".json")
                  for m in metrics_of(manifest, "per_layer", workload)}
        families = {spec["family"]: spec["modules"]
                    for spec in layers.values() if "family" in spec}
        (pb,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(pb, os.path.join(
                keep_trace, f"{workload}-{seed}.xplane.pb"))
        if rehearse:                  # a CPU trace has no device plane
            run["trace"] = None
        else:
            run["trace"] = tracereduce.reduce(
                tracereduce.load(pb), families, window_annotation="bench:step")
            missing = [f for f, v in run["trace"]["families"].items()
                       if not v["calls"]]
            if missing:
                fail(f"the trace of {traced_steps} step(s) shows no "
                     f"program of {missing}; modules seen: "
                     f"{run['trace']['other_modules']}")
            device.update(busy_s=run["trace"]["busy_s"],
                          window_s=run["trace"]["window_s"])
            result["breakdown"] = {
                "device_ops": run["trace"]["device_ops"],
                "idle_gaps": run["trace"]["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name, spec in layers.items():
            reader = importlib.import_module(
                f"benchmarks.readers.{spec['reader']}")
            value = reader.read(run, spec)
            if value is not None:
                result["metrics"][name] = {"value": float(value),
                                           "unit": spec["unit"]}
        result["traced_steps"] = traced_steps
        if run["trace"]:
            result["trace_families"] = run["trace"]["families"]
        result["step_s_traced"] = window.step_seconds(spans)
    else:
        result["metrics"] = {
            "step_s": {"value": window.step_seconds(spans), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["steps"] = len(spans)
    result["step_seconds"] = [round(e - s, 4) for s, e in spans]
    result["device_init_s"] = device_init_s
    result["precision"] = precision
    result["padding_share"] = padding_share
    if rehearse:
        result["rehearse"] = True

    t0 = time.time()
    check, ok = check_states(records, chosen, gen, config, limits)
    result["reference_s"] = time.time() - t0
    result["correct"] = bool(ok)
    result["check"] = check
    for name, (value, limit) in check.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes: control flow only (tests)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb there")
    ap.add_argument("--manifest", default="BENCHMARK.json", metavar="FILE",
                    help="another manifest than BENCHMARK.json (a path "
                    "from the checkout's root): a fixture, a draft cell")
    a = ap.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                      rehearse=a.rehearse, keep_trace=a.keep_trace,
                      manifest_file=a.manifest)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
