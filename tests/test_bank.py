"""Ahead-of-time program banking (examl_tpu/ops/bank.py), the
host-fingerprinted persistent compile cache (config.py), wedge-immune
dispatch (bench manifest gating), and the PSR x selective-loading window
arithmetic the banked multi-process runs rely on."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from examl_tpu import config
from examl_tpu.ops import bank

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_run(tmp_path, seed=5, ntaxa=8, width=200):
    """Tiny synthetic byteFile + tree for CLI-level bank tests."""
    from examl_tpu.instance import PhyloInstance
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile

    rng = np.random.default_rng(seed)
    names = [f"t{i}" for i in range(ntaxa)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, width))
            for _ in names]
    data = build_alignment_data(names, seqs)
    bf = str(tmp_path / "tiny.binary")
    write_bytefile(bf, data)
    tree = PhyloInstance(data).random_tree(seed)
    tf = str(tmp_path / "tiny.tree")
    open(tf, "w").write(tree.to_newick(names))
    return bf, tf


# -- host fingerprint / cache partitioning (VERDICT Weak §2) ----------------


def test_host_fingerprint_env_override(monkeypatch):
    monkeypatch.setenv("EXAML_HOST_FINGERPRINT", "cafe01")
    assert config.host_feature_fingerprint() == "cafe01"
    monkeypatch.setenv("EXAML_HOST_FINGERPRINT", "")
    assert config.host_feature_fingerprint() is None    # explicit unknown


def test_host_fingerprint_reads_cpuinfo():
    fp = config.host_feature_fingerprint()
    if not os.path.exists("/proc/cpuinfo"):
        pytest.skip("no /proc/cpuinfo on this platform")
    assert fp is not None and len(fp) == 12
    assert fp == config.host_feature_fingerprint()      # stable


def test_distinct_fingerprints_get_disjoint_cache_dirs(monkeypatch,
                                                       tmp_path):
    """The satellite fix proper: two hosts whose CPU features differ must
    never share a persistent-cache partition (the r05 SIGILL hazard)."""
    monkeypatch.setenv("EXAML_COMPILE_CACHE", str(tmp_path / "xla"))
    try:
        monkeypatch.setenv("EXAML_HOST_FINGERPRINT", "hostA-features")
        path_a = config.enable_persistent_compilation_cache()
        monkeypatch.setenv("EXAML_HOST_FINGERPRINT", "hostB-features")
        path_b = config.enable_persistent_compilation_cache()
        assert path_a and path_b and path_a != path_b
        assert os.path.isdir(path_a) and os.path.isdir(path_b)
        assert "hostA-features" in os.path.basename(path_a)
    finally:
        # Restore the real cache config for the rest of the suite.
        monkeypatch.delenv("EXAML_HOST_FINGERPRINT", raising=False)
        monkeypatch.delenv("EXAML_COMPILE_CACHE", raising=False)
        config.enable_persistent_compilation_cache()


def test_cpu_cache_disabled_without_fingerprint(monkeypatch):
    """No fingerprint on a CPU backend -> no persistence (never serve a
    possibly mis-featured executable), and startup must not fail."""
    monkeypatch.setenv("EXAML_HOST_FINGERPRINT", "")    # force unknown
    assert config.enable_persistent_compilation_cache() is None
    monkeypatch.delenv("EXAML_HOST_FINGERPRINT", raising=False)
    if config.host_feature_fingerprint() is not None:   # Linux hosts
        assert config.enable_persistent_compilation_cache() is not None


_CACHE_PROBE = (
    "import jax; from examl_tpu import config; "
    "p = config.enable_persistent_compilation_cache(); "
    "print('PATH', p); print('JAXDIR', jax.config.jax_compilation_cache_dir)")


def _cache_probe(env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return dict(line.split(" ", 1) for line in out.stdout.splitlines()
                if line.startswith(("PATH ", "JAXDIR "))), out.stderr


def test_jax_cache_dir_variable_stands(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the package sets no directory:
    jax keeps the one it was given, and a path in EXAML_COMPILE_CACHE
    ranks below it (ignored, with one line saying so)."""
    want = str(tmp_path / "from_outside")
    got, err = _cache_probe({"JAX_COMPILATION_CACHE_DIR": want,
                             "EXAML_COMPILE_CACHE": str(tmp_path / "x")})
    assert got == {"PATH": want, "JAXDIR": want}
    assert err.count("ignoring EXAML_COMPILE_CACHE") == 1
    assert not (tmp_path / "x").exists()


def test_default_cache_is_fixed_under_checkout():
    """Unset, two processes resolve the same path under the checkout —
    no pid, time or temp name in it (the path is part of the key)."""
    a, _ = _cache_probe({})
    b, _ = _cache_probe({})
    assert a == b and a["PATH"] == a["JAXDIR"]
    assert a["PATH"].startswith(os.path.join(REPO, ".xla_cache") + os.sep)


# -- family enumeration / manifest / exit diagnosis -------------------------


def test_enumerate_families_config_matrix():
    base = {"EXAML_FAST_TRAVERSAL": None}
    fams = bank.enumerate_families("d", env={})
    assert fams[:6] == list(bank.CORE_FAMILIES)          # scan tier first
    assert "fast" in fams and "scan" in fams and "thscan" in fams
    assert "rate_scan" not in fams
    assert "whole" not in fams
    fams = bank.enumerate_families("d", psr=True, env={})
    assert "rate_scan" in fams and "fast" not in fams    # PSR: scan path
    fams = bank.enumerate_families("e", env={})
    assert "scan" not in fams and "thscan" not in fams   # no SPR in -f e
    fams = bank.enumerate_families("d", save_memory=True, env={})
    assert "fast" not in fams                            # -S: pooled scan
    fams = bank.enumerate_families("d", env={"EXAML_FAST_TRAVERSAL": "0"})
    assert "fast" not in fams
    assert set(bank.FALLBACK_ENV) <= set(bank.enumerate_families(
        "d", env={}))                 # no hatch for a family never run
    fams = bank.enumerate_families("d", env={"EXAML_BATCH_SCAN": "0"})
    assert "scan" not in fams and "thscan" not in fams
    del base


def test_exit_desc_names_signals():
    import signal
    assert "SIGILL" in bank._exit_desc(-int(signal.SIGILL))
    assert "SIGKILL" in bank._exit_desc(-int(signal.SIGKILL))
    assert bank._exit_desc(3) == "(returncode 3)"
    assert bank._exit_desc(None) == "(still running)"


def test_manifest_roundtrip_and_degraded_set(tmp_path):
    report = {"fast": {"status": "timeout", "seconds": 5.0},
              "traverse": {"status": "banked", "seconds": 1.2},
              "scan": {"status": "skipped", "reason": "cpu"},
              "grad": {"status": "error",
                       "error": "worker died mid-stage (signal SIGILL)"},
              "derivs": {"status": "error",
                         "error": "worker exited (returncode 1)"}}
    bank._save_manifest(str(tmp_path), report, lambda m: None)
    m = bank.load_manifest(cache_path=str(tmp_path))
    assert m["families"]["fast"]["status"] == "timeout"
    # Wedge verdicts gate (deadline kill, death-by-signal); plain
    # environment errors (returncode) do not.
    assert bank.manifest_degraded_families(m) == {"fast", "grad"}
    assert bank.manifest_degraded_families(None) == set()
    assert bank.load_manifest(cache_path=str(tmp_path / "nope")) is None
    # A later run that does not enumerate 'fast' must not erase its
    # verdict.
    bank._save_manifest(str(tmp_path),
                        {"traverse": {"status": "banked"}},
                        lambda m: None)
    m2 = bank.load_manifest(cache_path=str(tmp_path))
    assert m2["families"]["fast"]["status"] == "timeout"


def test_older_manifest_with_removed_family_loads_and_survives(tmp_path):
    """Outside input: a manifest an older version wrote, with a wedge
    verdict for the `whole` family this version no longer has and its
    `bounded` layout field.  It loads, pins no escape hatch and gates no
    family a run enumerates, and a save keeps it beside the new
    verdicts."""
    old = {"version": 1, "updated": 1.0,
           "chunk_layout": {"bounded": True, "min_width": 8,
                            "chunk_cap": 1024, "tail_width": 64},
           "families": {"whole": {"status": "timeout", "seconds": 180.0},
                        "traverse": {"status": "banked", "seconds": 1.0}}}
    (tmp_path / bank.MANIFEST_NAME).write_text(json.dumps(old))
    m = bank.load_manifest(cache_path=str(tmp_path))
    assert m["families"]["whole"]["status"] == "timeout"
    gated = bank.manifest_degraded_families(m)
    assert gated == {"whole"}
    assert not gated & set(bank.enumerate_families("d", env={}))
    assert "whole" not in bank.FALLBACK_ENV
    bank.reset()
    env0 = dict(os.environ)
    try:
        bank._apply_degradations(m["families"], lambda msg: None)
        assert dict(os.environ) == env0        # no hatch pulled
    finally:
        bank.reset()
    bank._save_manifest(str(tmp_path), {"fast": {"status": "banked"}},
                        lambda msg: None)
    m2 = bank.load_manifest(cache_path=str(tmp_path))
    assert m2["families"]["whole"]["status"] == "timeout"
    assert m2["families"]["fast"]["status"] == "banked"
    assert "bounded" not in m2["chunk_layout"]


# -- CLI end-to-end: compile time moves into the bank phase -----------------


def test_cli_bank_moves_compiles_off_the_search_path(tmp_path,
                                                     monkeypatch):
    """Acceptance-shaped: a --bank run performs its first-call compiles
    inside the bank phase (subprocess workers + main-process warm), so
    the inference phase sees zero unbanked first calls and zero
    watchdog barks, and the obs snapshot carries per-family bank
    compile seconds."""
    from examl_tpu.cli.main import main

    monkeypatch.setenv("EXAML_COMPILE_TIMEOUT", "180")   # restore after
    # Isolated cache: the per-host bank manifest must land in tmp, not
    # in the real user cache where later bench runs would honor it.
    monkeypatch.setenv("EXAML_COMPILE_CACHE", str(tmp_path / "xla"))
    bf, tf = _tiny_run(tmp_path)
    m = str(tmp_path / "m.json")
    try:
        rc = main(["-s", bf, "-n", "BK", "-t", tf, "-f", "e",
                   "-w", str(tmp_path / "out"), "--bank",
                   "--compile-timeout", "300", "--metrics", m,
                   "--single-device"])
    finally:
        monkeypatch.delenv("EXAML_COMPILE_CACHE", raising=False)
        config.enable_persistent_compilation_cache()     # re-point jax
    assert rc == 0
    snap = json.load(open(m))
    c = snap["counters"]
    assert c["bank.families"] >= 7
    assert c["bank.banked"] >= 5
    assert c.get("bank.timeouts", 0) == 0
    assert c["engine.compile_count.bank_phase"] > 0      # warm pass fired
    assert c.get("engine.first_calls.unbanked", 0) == 0  # nothing missed
    assert c.get("engine.watchdog_barks", 0) == 0
    # Per-family compile seconds from the subprocess workers, merged.
    assert any(k.startswith("bank.engine.compile_seconds.")
               for k in c)
    assert any(k.startswith("bank.compile.") for k in snap["timers"])
    assert "phase.bank (aot compile)" in snap["timers"]
    assert "phase.bank (warm programs)" in snap["timers"]
    info = open(tmp_path / "out" / "ExaML_info.BK").read()
    assert "banking" in info and "bank manifest ->" in info


@pytest.mark.slow          # ~130 s: the heaviest tier-1 case (PR8 runtime
                           # audit) — the hang->degrade contract also has
                           # non-slow unit coverage in this file
def test_cli_bank_hanging_compile_degrades_to_scan_tier(tmp_path,
                                                        monkeypatch):
    """The satellite acceptance test: a WEDGED first compile of a
    non-scan family (the chunk fast path, simulated via
    EXAML_BANK_TEST_HANG) is killed at --compile-timeout, the run pins
    the scan-tier escape hatch and completes the search — instead of
    hanging forever as before banking existed — with the timeout and
    fallback recorded in the obs registry."""
    from examl_tpu.cli.main import main

    monkeypatch.setenv("EXAML_BANK_TEST_HANG", "fast")
    monkeypatch.setenv("EXAML_FAST_TRAVERSAL", "")       # restore after
    monkeypatch.setenv("EXAML_COMPILE_TIMEOUT", "180")   # restore after
    # Isolated cache: this test WRITES a manifest marking 'fast' as
    # degraded — it must never land in the real user cache, where bench
    # workers would skip the chunk stages on later real runs.
    monkeypatch.setenv("EXAML_COMPILE_CACHE", str(tmp_path / "xla"))
    bf, tf = _tiny_run(tmp_path)
    m = str(tmp_path / "m.json")
    t0 = time.time()
    try:
        rc = main(["-s", bf, "-n", "HG", "-t", tf, "-f", "d",
                   "-w", str(tmp_path / "out"), "--bank",
                   "--compile-timeout", "8", "--metrics", m,
                   "--single-device"])
    finally:
        monkeypatch.delenv("EXAML_COMPILE_CACHE", raising=False)
        config.enable_persistent_compilation_cache()     # re-point jax
    wall = time.time() - t0
    assert rc == 0
    assert os.path.exists(tmp_path / "out" / "ExaML_result.HG")
    snap = json.load(open(m))
    c = snap["counters"]
    assert c["bank.timeouts"] >= 1                       # the kill
    assert c["bank.fallbacks"] >= 1                      # the degradation
    assert os.environ.get("EXAML_FAST_TRAVERSAL") == "0"
    assert c.get("engine.first_calls.unbanked", 0) == 0
    assert c.get("engine.watchdog_barks", 0) == 0
    info = open(tmp_path / "out" / "ExaML_info.HG").read()
    assert "pinned EXAML_FAST_TRAVERSAL=0" in info
    # The hang cost one compile deadline inside the bank phase, not an
    # unbounded wedge: the bank phase is bounded by timeout + the other
    # families' healthy compiles (generous slack for a loaded CI host).
    assert snap["timers"]["phase.bank (aot compile)"]["total_s"] < 120
    assert wall < 600


# -- PSR x selective loading (VERDICT Weak §6 / Next §6) --------------------


def test_engine_local_block_window_arithmetic():
    """The engine's global->local bridge, unit-level: a local bucket's
    window of a global block-axis array is exactly its packed slice (and
    the identity on global buckets) — no devices needed."""
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import read_bytefile_for_process, \
        write_bytefile
    from examl_tpu.ops.engine import LikelihoodEngine
    from examl_tpu.parallel.packing import pack_partitions, \
        pack_partitions_local
    import tempfile

    rng = np.random.default_rng(11)
    names = [f"t{i}" for i in range(6)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 300))
            for _ in names]
    data = build_alignment_data(names, seqs)
    with tempfile.TemporaryDirectory() as d:
        bf = os.path.join(d, "a.binary")
        write_bytefile(bf, data)
        (gbucket,) = pack_partitions(data.partitions,
                                     block_multiple=2).values()
        arr = np.arange(gbucket.num_blocks * gbucket.lane,
                        dtype=np.float64).reshape(gbucket.num_blocks,
                                                  gbucket.lane)

        class _Fake:
            pass

        windows = []
        for p in range(2):
            sl = read_bytefile_for_process(bf, p, 2, block_multiple=2)
            (lbucket,) = pack_partitions_local(sl.partitions, p, 2,
                                               block_multiple=2).values()
            fake = _Fake()
            fake.bucket = lbucket
            win = LikelihoodEngine._local_block_window(fake, arr)
            assert win.shape[0] == lbucket.local_num_blocks
            windows.append(win)
        fake = _Fake()
        fake.bucket = gbucket
        assert LikelihoodEngine._local_block_window(fake, arr) is arr
        np.testing.assert_array_equal(np.concatenate(windows), arr)


PSR_WINDOW_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
import numpy as np
procid = int(os.environ["EXAML_PROCID"])
from examl_tpu.io.bytefile import read_bytefile_for_process
from examl_tpu.parallel.packing import pack_partitions_local
from examl_tpu.instance import packed_site_rates
from examl_tpu.ops.engine import LikelihoodEngine

sl = read_bytefile_for_process({bf!r}, procid, 2, block_multiple=2)
(bucket,) = pack_partitions_local(sl.partitions, procid, 2,
                                  block_multiple=2).values()
widths = [p.global_width if p.global_width is not None else p.width
          for p in sl.partitions]
# Deterministic GLOBAL rate state: identical on every process, exactly
# like the post-allgather categorization in optimize/psr.py.
rng = np.random.default_rng(7)
psr = [np.sort(rng.gamma(2.0, 0.5, 5)) for _ in widths]
cat = [rng.integers(0, 5, w).astype(np.int32) for w in widths]
packed = packed_site_rates(bucket, psr, cat)

class _F: pass
f = _F(); f.bucket = bucket
win = LikelihoodEngine._local_block_window(f, packed)
np.save({out!r}, win)
print("offset=", bucket.block_offset, "local=", bucket.local_num_blocks,
      "global=", bucket.num_blocks)
"""


def test_psr_selective_loading_windows_tile_global(tmp_path):
    """PSR under per-process selective loading, EXAML_PROCID-style (2
    real subprocesses, no distributed collectives needed): each process
    reads only its byteFile slice, rebuilds the GLOBAL packed rate
    state from the (deterministic, post-allgather) per-site rate
    arrays, and materializes only its block window — the windows must
    tile the full-read global packing exactly.  This is the host-side
    half of lifting the engine.py rejection; the device-side allgather
    runs in the slow 2-process battery (test_multihost)."""
    from examl_tpu.instance import packed_site_rates
    from examl_tpu.io.alignment import build_alignment_data
    from examl_tpu.io.bytefile import write_bytefile
    from examl_tpu.parallel.packing import pack_partitions

    rng = np.random.default_rng(3)
    names = [f"t{i}" for i in range(6)]
    seqs = ["".join("ACGT"[b] for b in rng.integers(0, 4, 300))
            for _ in names]
    data = build_alignment_data(names, seqs)
    bf = str(tmp_path / "a.binary")
    write_bytefile(bf, data)

    outs = []
    procs = []
    for p in range(2):
        out = str(tmp_path / f"win{p}.npy")
        outs.append(out)
        env = dict(os.environ, EXAML_PROCID=str(p), JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             PSR_WINDOW_CHILD.format(repo=REPO, bf=bf, out=out)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    for p, pr in enumerate(procs):
        o, e = pr.communicate(timeout=300)
        assert pr.returncode == 0, f"proc {p}: {e[-2000:]}"
        assert "global= " in o

    (gbucket,) = pack_partitions(data.partitions,
                                 block_multiple=2).values()
    widths = [pp.width for pp in data.partitions]
    rng = np.random.default_rng(7)
    psr = [np.sort(rng.gamma(2.0, 0.5, 5)) for _ in widths]
    cat = [rng.integers(0, 5, w).astype(np.int32) for w in widths]
    ref = packed_site_rates(gbucket, psr, cat)

    wins = [np.load(o) for o in outs]
    assert all(0 < w.shape[0] < gbucket.num_blocks for w in wins)
    np.testing.assert_array_equal(np.concatenate(wins), ref)


def test_psr_pattern_weights_full_read_identity():
    """On a full read psr_pattern_weights is the partition's own weight
    vector and psr_packed_weights is the packed layout (no gather)."""
    from examl_tpu.instance import PhyloInstance
    from tests.conftest import correlated_dna

    data = correlated_dna(6, 240, seed=9)
    inst = PhyloInstance(data, rate_model="PSR")
    w = inst.psr_pattern_weights(0)
    np.testing.assert_array_equal(w, data.partitions[0].weights)
    (bucket,) = inst.buckets.values()
    packed = inst.psr_packed_weights(bucket)
    assert packed.shape == (bucket.num_blocks, bucket.lane)
    np.testing.assert_array_equal(
        packed.reshape(-1)[bucket.site_indices(0)],
        np.asarray(data.partitions[0].weights, dtype=np.float64))
