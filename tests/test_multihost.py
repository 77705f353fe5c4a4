"""Multi-host execution: >= 2 OS processes via jax.distributed.

The reference's entire identity is a multi-node MPI program
(`axml.c:2573-2577`: MPI_Init, rank discovery; `communication.c:120-182`:
per-rank reductions).  These tests launch REAL separate processes over a
local coordinator — 2 processes x 4 virtual CPU devices — and assert the
global SPMD program computes the single-process answer, with per-process
selective data loading and process-0 output gating."""

import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

from tests.conftest import TESTDATA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _mh_env(ndev: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    pp = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([REPO] + pp)
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={ndev}").strip()
    env.pop("JAX_PLATFORM_NAME", None)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    return env


def _launch(codes, ndev: int, timeout: int = 600):
    """Run one python per code string concurrently; return stdouts."""
    env = _mh_env(ndev)
    procs = [subprocess.Popen([sys.executable, "-c", c], env=env, cwd=REPO,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for c in codes]
    outs = []
    for i, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"proc {i} rc={p.returncode}:\n{err[-3000:]}"
        outs.append(out)
    return outs


def test_multihost_dryrun_matches_single_process():
    """2 processes x 4 devices == 1 process x 8 devices, same lnL."""
    from __graft_entry__ import dryrun_multihost
    dryrun_multihost(2, 4)      # asserts children agree internally


CHILD = """
import sys; sys.path.insert(0, {repo!r})
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id={procid})
import numpy as np
from examl_tpu.io.bytefile import read_bytefile_for_process
from examl_tpu.instance import PhyloInstance
from examl_tpu.parallel.sharding import default_site_sharding

ndev = jax.device_count()
sl = read_bytefile_for_process({bf!r}, {procid}, 2, block_multiple=ndev)
print("local_patterns:", sum(p.width for p in sl.partitions))
inst = PhyloInstance(sl, sharding=default_site_sharding(),
                     block_multiple=ndev, local_window=({procid}, 2))
tree = inst.tree_from_newick(open({tree!r}).read())
print("lnL= %.6f" % float(inst.evaluate(tree, full=True)))
"""


def test_multihost_selective_load_matches_full_read(tmp_path):
    """Each process reads ONLY its site columns (readMyData,
    byteFile.c:278-382) yet the global program computes the full-read
    lnL."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import load_alignment
    from examl_tpu.io.bytefile import write_bytefile

    data = load_alignment(f"{TESTDATA}/49", f"{TESTDATA}/49.model")
    bf = str(tmp_path / "t49.binary")
    write_bytefile(bf, data)
    # Single-process full-read reference value (float32 default dtype,
    # like the children).
    inst = PhyloInstance(data)
    tree = inst.tree_from_newick(open(f"{TESTDATA}/49.tree").read())
    ref = float(inst.evaluate(tree, full=True))

    port = _free_port()
    outs = _launch(
        [CHILD.format(repo=REPO, port=port, procid=p, bf=bf,
                      tree=f"{TESTDATA}/49.tree") for p in range(2)],
        ndev=4)
    lnls, widths = [], []
    for out in outs:
        lnls.append(float(re.search(r"lnL= (-?[\d.]+)", out).group(1)))
        widths.append(int(re.search(r"local_patterns: (\d+)",
                                    out).group(1)))
    assert lnls[0] == lnls[1]
    # Both processes loaded strict subsets that tile the alignment.
    total = data.total_patterns
    assert sum(widths) == total and all(0 < w < total for w in widths)
    assert lnls[0] == pytest.approx(ref, abs=0.02)


CLI_CHILD = """
import sys; sys.path.insert(0, {repo!r})
from examl_tpu.cli.main import main
rc = main(["-s", {bf!r}, "-n", "MH", "-t", {tree!r}, "-f", "e",
           "-w", {wd!r}, "--coordinator", "127.0.0.1:{port}",
           "--nprocs", "2", "--procid", "{procid}"])
sys.exit(rc)
"""


def test_multihost_cli_process0_gating(tmp_path):
    """Only process 0 writes the primary run files; other processes
    divert to a per-process scratch dir (the reference's processID==0
    gating throughout axml.c)."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile

    rng = np.random.default_rng(3)
    bases = "ACGT"
    names = [f"t{i}" for i in range(8)]
    seqs = ["".join(bases[b] for b in rng.integers(0, 4, 600))
            for _ in names]
    data = build_alignment_data(names, seqs)
    bf = str(tmp_path / "tiny.binary")
    write_bytefile(bf, data)
    inst = PhyloInstance(data)
    tree = inst.random_tree(3)
    treefile = str(tmp_path / "tiny.tree")
    with open(treefile, "w") as f:
        f.write(tree.to_newick(names))
    wd = str(tmp_path / "out")

    port = _free_port()
    _launch([CLI_CHILD.format(repo=REPO, bf=bf, tree=treefile, wd=wd,
                              port=port, procid=p) for p in range(2)],
            ndev=4, timeout=900)
    top = set(os.listdir(wd))
    assert "ExaML_info.MH" in top
    assert "ExaML_TreeFile.MH" in top          # -f e primary outputs
    assert "ExaML_modelFile.MH" in top
    # Non-zero processes write NO run files: RunFiles is gated off and
    # their (diverted) scratch dir holds at most checkpoints.
    proc1 = os.path.join(wd, ".proc1")
    if os.path.isdir(proc1):
        leaked = [f for f in os.listdir(proc1)
                  if f.startswith("ExaML_") and "binaryCheckpoint" not in f]
        assert not leaked, leaked


PSR_CHILD = """
import sys; sys.path.insert(0, {repo!r})
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id={procid})
from examl_tpu.config import enable_x64; enable_x64()
from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import load_alignment
from examl_tpu.parallel.sharding import make_mesh, site_sharding
from examl_tpu.optimize.psr import optimize_rate_categories

sh = site_sharding(make_mesh())
data = load_alignment({aln!r}, {model!r})
inst = PhyloInstance(data, rate_model="PSR", sharding=sh,
                     block_multiple=jax.device_count())
tree = inst.tree_from_newick(open({tree!r}).read())
l0 = float(inst.evaluate(tree, full=True))
optimize_rate_categories(inst, tree)
l1 = float(inst.evaluate(tree, full=True))
print("PSR lnL0=", l0, " lnL1=", l1)
"""


def test_multihost_psr_rate_optimization():
    """PSR (-m PSR / the reference's CAT) under 2 real processes: the
    per-site rate scan allgathers to every process, categorization runs
    identically everywhere, and the optimized rates improve lnL — the
    reference's Gatherv/Scatterv CAT pipeline
    (`optimizeModel.c:2135-2254`) as one collective."""
    import re

    port = _free_port()
    outs = _launch(
        [PSR_CHILD.format(repo=REPO, port=port, procid=p,
                          aln=f"{TESTDATA}/49", model=f"{TESTDATA}/49.model",
                          tree=f"{TESTDATA}/49.tree") for p in range(2)],
        ndev=4, timeout=900)
    vals = []
    for out in outs:
        m = re.search(r"lnL0= (-?[\d.]+)\s+lnL1= (-?[\d.]+)", out)
        assert m, out[-2000:]
        vals.append((float(m.group(1)), float(m.group(2))))
    (a0, a1), (b0, b1) = vals
    assert a0 == b0 and a1 == b1           # processes agree exactly
    assert a1 > a0 + 100.0                 # categorization really helped


PSR_SLICE_CHILD = """
import sys; sys.path.insert(0, {repo!r})
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id={procid})
from examl_tpu.config import enable_x64; enable_x64()
from examl_tpu.io.bytefile import read_bytefile_for_process
from examl_tpu.instance import PhyloInstance
from examl_tpu.parallel.sharding import default_site_sharding
from examl_tpu.optimize.psr import optimize_rate_categories

ndev = jax.device_count()
sl = read_bytefile_for_process({bf!r}, {procid}, 2, block_multiple=ndev)
print("local_patterns:", sum(p.width for p in sl.partitions))
inst = PhyloInstance(sl, rate_model="PSR",
                     sharding=default_site_sharding(),
                     block_multiple=ndev, local_window=({procid}, 2))
tree = inst.tree_from_newick(open({tree!r}).read())
l0 = float(inst.evaluate(tree, full=True))
optimize_rate_categories(inst, tree)
l1 = float(inst.evaluate(tree, full=True))
print("PSR lnL0= %.6f  lnL1= %.6f" % (l0, l1))
"""


def test_multihost_psr_selective_loading(tmp_path):
    """PSR under per-process SELECTIVE loading (the engine.py rejection
    lifted): each process reads only its site columns, the rate scan's
    per-site lnls and the packed weights allgather to every process
    (the reference's CAT Gatherv/Scatterv, `optimizeModel.c:2135-2254`,
    as collectives), and the identical global categorization improves
    lnL in lockstep on both processes."""
    from examl_tpu.io.alignment import load_alignment
    from examl_tpu.io.bytefile import write_bytefile

    data = load_alignment(f"{TESTDATA}/49", f"{TESTDATA}/49.model")
    bf = str(tmp_path / "t49.binary")
    write_bytefile(bf, data)

    port = _free_port()
    outs = _launch(
        [PSR_SLICE_CHILD.format(repo=REPO, port=port, procid=p, bf=bf,
                                tree=f"{TESTDATA}/49.tree")
         for p in range(2)],
        ndev=4, timeout=900)
    vals, widths = [], []
    for out in outs:
        m = re.search(r"lnL0= (-?[\d.]+)\s+lnL1= (-?[\d.]+)", out)
        assert m, out[-2000:]
        vals.append((float(m.group(1)), float(m.group(2))))
        widths.append(int(re.search(r"local_patterns: (\d+)",
                                    out).group(1)))
    (a0, a1), (b0, b1) = vals
    assert a0 == b0 and a1 == b1           # processes agree exactly
    assert a1 > a0 + 100.0                 # categorization really helped
    # Both processes loaded strict subsets tiling the alignment.
    total = data.total_patterns
    assert sum(widths) == total and all(0 < w < total for w in widths)


# Shared preamble: distributed init + selective -S load (formatted with
# repo/port/procid/bf, leaving {tree} for the test-specific tail).
SEV_PREAMBLE = """
import os; os.environ["EXAML_BATCH_SCAN"] = "1"
import sys; sys.path.insert(0, {repo!r})
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes=2, process_id={procid})
from examl_tpu.config import enable_x64; enable_x64()
from examl_tpu.io.bytefile import read_bytefile_for_process
from examl_tpu.instance import PhyloInstance
from examl_tpu.parallel.sharding import default_site_sharding

ndev = jax.device_count()
sl = read_bytefile_for_process({bf!r}, {procid}, 2, block_multiple=ndev)
inst = PhyloInstance(sl, sharding=default_site_sharding(),
                     block_multiple=ndev, local_window=({procid}, 2),
                     save_memory=True)
"""

SEV_CHILD = SEV_PREAMBLE + """
print("local_patterns:", sum(p.width for p in sl.partitions))
tree = inst.tree_from_newick(open({tree!r}).read())
lnl = float(inst.evaluate(tree, full=True))
(eng,) = inst.engines.values()
st = eng.sev.stats()
print("lnL= %.6f" % lnl)
print("alloc=", st["allocated_cells"], " dense=", st["dense_cells"])
"""


def _gappy_two_gene_bytefile(tmp_path, seed, ntaxa=16, gene=640):
    """The shared -S multihost fixture: two gene blocks, each covered by
    half the taxa (clade-structured gaps), written as a byteFile."""
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile
    from examl_tpu.io.partitions import parse_partition_file

    rng = np.random.default_rng(seed)
    names = [f"t{i}" for i in range(ntaxa)]
    seqs = ["" for _ in range(ntaxa)]
    for g in range(2):
        cov = range(g * ntaxa // 2, (g + 1) * ntaxa // 2)
        for i in range(ntaxa):
            if i in cov:
                seqs[i] += "".join("ACGT"[b]
                                   for b in rng.integers(0, 4, gene))
            else:
                seqs[i] += "-" * gene
    mp = tmp_path / "parts.model"
    mp.write_text(f"DNA, g1 = 1-{gene}\nDNA, g2 = {gene+1}-{2*gene}\n")
    data = build_alignment_data(names, seqs,
                                specs=parse_partition_file(str(mp)))
    bf = str(tmp_path / "gappy.binary")
    write_bytefile(bf, data)
    return data, bf


SEV_SCAN_CHILD = SEV_PREAMBLE + """
from examl_tpu.search import batchscan, spr

tree = inst.tree_from_newick(open({tree!r}).read())
inst.evaluate(tree, full=True)
assert spr.batched_scan_enabled(inst)
ctx = spr.SprContext(inst, thorough=False, do_cutoff=False)
c = tree.centroid_branch()
p = c if not tree.is_tip(c.number) else c.back
q1, q2 = p.next.back, p.next.next.back
spr.remove_node(inst, tree, ctx, p)
plan = batchscan.plan_for_endpoints(inst, tree, p, q1, q2, 1, 4)
assert plan is not None
lnls = batchscan.run_plan(inst, tree, plan)
print("scan_lnls=", ",".join("%.6f" % float(v) for v in lnls))
"""


def _sev_plan_reference(tmp_path, seed, thorough, maxtrav):
    """Shared parent-side setup for the SEV batched-arm multihost
    tests: whole-read -S instance, pruned centroid node, plan, and the
    single-process reference scores."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.search import batchscan, spr

    data, bf = _gappy_two_gene_bytefile(tmp_path, seed=seed)
    inst = PhyloInstance(data, save_memory=True)
    tree = inst.random_tree(11)
    treef = tmp_path / "t.nwk"
    treef.write_text(tree.to_newick(data.taxon_names))
    inst.evaluate(tree, full=True)
    ctx = spr.SprContext(inst, thorough=thorough, do_cutoff=False)
    c = tree.centroid_branch()
    p = c if not tree.is_tip(c.number) else c.back
    q1, q2 = p.next.back, p.next.next.back
    saved = (p, list(q1.z), list(q2.z), q1, q2)
    spr.remove_node(inst, tree, ctx, p)
    plan = batchscan.plan_for_endpoints(inst, tree, p, q1, q2, 1,
                                        maxtrav)
    assert plan is not None and plan.candidates
    if thorough:
        ref = batchscan.run_plan_thorough(inst, tree, plan)
    else:
        ref = batchscan.run_plan(inst, tree, plan)
    return inst, tree, bf, treef, saved, ref


SEV_THOROUGH_CHILD = SEV_PREAMBLE + """
import os as _os; _os.environ["EXAML_BATCH_THOROUGH"] = "1"
from examl_tpu.search import batchscan, spr

tree = inst.tree_from_newick(open({tree!r}).read())
inst.evaluate(tree, full=True)
assert spr.thorough_batched_ok(inst)
ctx = spr.SprContext(inst, thorough=True, do_cutoff=False)
c = tree.centroid_branch()
p = c if not tree.is_tip(c.number) else c.back
q1, q2 = p.next.back, p.next.next.back
spr.remove_node(inst, tree, ctx, p)
plan = batchscan.plan_for_endpoints(inst, tree, p, q1, q2, 1, 3)
assert plan is not None
lnls, es = batchscan.run_plan_thorough(inst, tree, plan)
print("th_lnls=", ",".join("%.6f" % float(v) for v in lnls))
print("th_es=", ",".join("%.8f" % float(v) for v in es.reshape(-1)))
"""


def test_multihost_sev_batched_thorough(tmp_path):
    """The batched THOROUGH arm under -S with 2 REAL processes: the
    on-device triangle/localSmooth Newton loops psum their derivatives
    per iteration across the processes, so candidate lnLs AND the
    smoothed branch triplets must agree exactly between processes and
    match the whole-read single-process SEV run."""
    _, _, bf, treef, _, (ref_lnls, ref_es) = _sev_plan_reference(
        tmp_path, seed=27, thorough=True, maxtrav=3)

    port = _free_port()
    outs = _launch(
        [SEV_THOROUGH_CHILD.format(repo=REPO, port=port, procid=p_,
                                   bf=bf, tree=str(treef))
         for p_ in range(2)],
        ndev=4, timeout=900)
    got = []
    for out in outs:
        lnls = [float(v) for v in
                re.search(r"th_lnls= (\S+)", out).group(1).split(",")]
        es = [float(v) for v in
              re.search(r"th_es= (\S+)", out).group(1).split(",")]
        got.append((lnls, es))
    assert got[0] == got[1]
    assert got[0][0] == pytest.approx([float(v) for v in ref_lnls],
                                      abs=0.05)
    # Branch triplets (children run f64 via the preamble's enable_x64):
    # the only remaining difference vs the unsharded in-process
    # reference is psum summation order, so agreement is tight except
    # on near-ZMIN branches where the lnL is flat in z.
    ref_flat = [float(v) for v in np.asarray(ref_es).reshape(-1)]
    for ours, ref in zip(got[0][1], ref_flat):
        if ref > 1e-3:           # one-sided: a near-ZMIN `ours` against
            # a well-conditioned `ref` must FAIL, not be skipped
            assert ours == pytest.approx(ref, rel=1e-4), (ours, ref)


def test_multihost_sev_batched_scan(tmp_path):
    """The batched SPR radius scan under -S with 2 REAL processes: the
    scan region is carved from the sharded pool and the DENSE scaler
    must grow as a committed global array (engine.ensure_scan_rows /
    _grow_rows — eager concat with a process-local pad is undefined
    multi-process).  Candidate lnLs must agree across processes and
    match the whole-read single-process SEV scan."""
    _, _, bf, treef, _, ref_scores = _sev_plan_reference(
        tmp_path, seed=21, thorough=False, maxtrav=4)
    ref = [float(v) for v in ref_scores]

    port = _free_port()
    outs = _launch(
        [SEV_SCAN_CHILD.format(repo=REPO, port=port, procid=p_, bf=bf,
                               tree=str(treef)) for p_ in range(2)],
        ndev=4, timeout=900)
    got = [[float(v) for v in
            re.search(r"scan_lnls= (\S+)", out).group(1).split(",")]
           for out in outs]
    assert got[0] == got[1]
    assert got[0] == pytest.approx(ref, abs=0.05)


def test_multihost_sev_selective_load(tmp_path):
    """-S with per-process selective loading: each process reads only
    its site columns, keeps gap bookkeeping for its own block window,
    and the shard_mapped pooled programs reproduce the whole-read
    single-process SEV lnL — the reference's -S under MPI with per-rank
    reads (`axml.c:874-876`, `byteFile.c:278-382`)."""
    from examl_tpu.instance import PhyloInstance

    data, bf = _gappy_two_gene_bytefile(tmp_path, seed=8)
    inst = PhyloInstance(data, save_memory=True)   # whole-read reference
    tree = inst.random_tree(11)
    treef = tmp_path / "t.nwk"
    treef.write_text(tree.to_newick(data.taxon_names))
    ref = float(inst.evaluate(tree, full=True))

    port = _free_port()
    outs = _launch(
        [SEV_CHILD.format(repo=REPO, port=port, procid=p, bf=bf,
                          tree=str(treef)) for p in range(2)],
        ndev=4, timeout=900)
    lnls, allocs = [], []
    for out in outs:
        lnls.append(float(re.search(r"lnL= (-?[\d.]+)", out).group(1)))
        m = re.search(r"alloc= (\d+)\s+dense= (\d+)", out)
        allocs.append((int(m.group(1)), int(m.group(2))))
    assert lnls[0] == lnls[1]
    assert lnls[0] == pytest.approx(ref, abs=0.02)
    # each process allocated cells for its window only, and saved memory
    for a, dtot in allocs:
        assert 0 < a < dtot
