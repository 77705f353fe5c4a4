"""byteFile format: roundtrip fidelity and reference-parser compatibility."""

import os

import numpy as np
import pytest

from examl_tpu.instance import PhyloInstance
from examl_tpu.io.alignment import load_alignment
from examl_tpu.io.bytefile import read_bytefile, write_bytefile

from tests.conftest import TESTDATA

# A byteFile produced by the reference parser (parser/axml.c) if one has
# been generated locally; the roundtrip tests do not require it.
REF_BYTEFILE = os.path.join(os.path.dirname(__file__), "fixtures",
                            "ref49", "aln49.binary")


@pytest.fixture(scope="module")
def data49():
    return load_alignment(f"{TESTDATA}/49", f"{TESTDATA}/49.model")


@pytest.fixture(scope="module")
def tree49_text():
    with open(f"{TESTDATA}/49.tree") as f:
        return f.read()


def test_write_read_roundtrip_exact(tmp_path_factory, data49, tree49_text):
    path = str(tmp_path_factory.mktemp("bf") / "t49.binary")
    write_bytefile(path, data49)
    rt = read_bytefile(path)
    assert rt.taxon_names == data49.taxon_names
    for a, b in zip(rt.partitions, data49.partitions):
        assert a.name == b.name
        assert a.datatype.name == b.datatype.name
        np.testing.assert_array_equal(a.patterns, b.patterns)
        np.testing.assert_array_equal(a.weights, b.weights)
        np.testing.assert_allclose(a.empirical_freqs, b.empirical_freqs)
    i1 = PhyloInstance(data49)
    t1 = i1.tree_from_newick(tree49_text)
    i2 = PhyloInstance(rt)
    t2 = i2.tree_from_newick(tree49_text)
    assert i1.evaluate(t1, full=True) == pytest.approx(
        i2.evaluate(t2, full=True), abs=1e-9)


def test_meta_matches_full_read(tmp_path_factory, data49):
    from examl_tpu.io.bytefile import read_bytefile_meta
    path = str(tmp_path_factory.mktemp("bf") / "t49.binary")
    write_bytefile(path, data49)
    meta = read_bytefile_meta(path)
    assert meta.ntaxa == data49.ntaxa
    assert meta.taxon_names == data49.taxon_names
    assert meta.num_pattern == data49.total_patterns
    lower = 0
    for pm, p in zip(meta.parts, data49.partitions):
        assert (pm.lower, pm.upper) == (lower, lower + p.width)
        assert pm.states == p.states
        lower += p.width


def test_sliced_read_reproduces_full_read(tmp_path_factory, data49):
    """Per-process selective reads concatenate back to the full arrays
    (reference `readMyData` equivalence, `byteFile.c:278-382`)."""
    from examl_tpu.io.bytefile import read_bytefile_for_process
    from examl_tpu.parallel.packing import pack_layout
    path = str(tmp_path_factory.mktemp("bf") / "t49.binary")
    write_bytefile(path, data49)
    full = read_bytefile(path)
    nprocs = 4
    layouts = pack_layout(
        [(g, p.states, p.width) for g, p in enumerate(full.partitions)],
        block_multiple=nprocs)
    got_cols = {g: [] for g in range(len(full.partitions))}
    for proc in range(nprocs):
        sl = read_bytefile_for_process(path, proc, nprocs)
        assert sl.taxon_names == full.taxon_names
        windows = {}
        for lay in layouts.values():
            for gid, lo, hi in lay.process_columns(proc, nprocs):
                windows[gid] = (lo, hi)
        for gid, (sp, fp) in enumerate(zip(sl.partitions, full.partitions)):
            lo, hi = windows.get(gid, (0, 0))
            assert sp.width == hi - lo
            np.testing.assert_array_equal(sp.patterns,
                                          fp.patterns[:, lo:hi])
            np.testing.assert_array_equal(sp.weights, fp.weights[lo:hi])
            got_cols[gid].append((lo, hi))
    # The windows tile every partition: each column owned exactly once.
    for gid, p in enumerate(full.partitions):
        spans = sorted(w for w in got_cols[gid] if w[0] != w[1])
        covered = 0
        for lo, hi in spans:
            assert lo == covered, (gid, spans)
            covered = hi
        assert covered == p.width, (gid, covered, p.width)


@pytest.mark.slow
def test_sliced_read_memory_scales(tmp_path_factory):
    """Peak host RSS of a sliced read is a small fraction of the full
    read's on a ~1M-pattern byteFile (the reference-scale regime where
    whole-file reads per process stop being viable, byteFile.c:278-382)."""
    import subprocess
    import sys

    from examl_tpu import datatypes
    from examl_tpu.io.alignment import AlignmentData, PartitionData

    ntaxa, width = 48, 1_000_000
    rng = np.random.default_rng(7)
    patterns = rng.integers(1, 16, size=(ntaxa, width), dtype=np.uint8)
    part = PartitionData(
        name="big", datatype=datatypes.get("DNA"), model_name="DNA",
        patterns=patterns, weights=np.ones(width, dtype=np.int64),
        empirical_freqs=np.full(4, 0.25), use_empirical_freqs=True,
        optimize_freqs=False)
    path = str(tmp_path_factory.mktemp("bigbf") / "big.binary")
    write_bytefile(path, AlignmentData([f"t{i}" for i in range(ntaxa)],
                                       [part]))
    del patterns, part

    def child_read_rss_delta(body: str) -> int:
        """Bytes of RSS the read itself retains, measured in a fresh
        process (package import baseline — jax — is subtracted by
        sampling /proc/self/statm around the read)."""
        code = ("import examl_tpu.io.bytefile as bf\n"
                "def rss():\n"
                "    import os\n"
                "    with open('/proc/self/statm') as f:\n"
                "        return int(f.read().split()[1]) * os.sysconf("
                "'SC_PAGE_SIZE')\n"
                "pre = rss()\n"
                f"{body}\n"
                "print(rss() - pre)")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        return int(out.stdout.strip().splitlines()[-1])

    full = child_read_rss_delta(f"d = bf.read_bytefile({path!r})")
    sliced = child_read_rss_delta(
        f"d = bf.read_bytefile_for_process({path!r}, 0, 8)")
    assert full > 40_000_000, full                  # full read ~48MB+
    assert sliced < full / 3, (full, sliced)


def test_read_reference_parser_output(data49, tree49_text):
    """Our reader consumes the reference parser's binary; patterns and
    weights agree exactly, lnL agrees to the empirical-frequency rounding
    (the file stores the parser's own EM-smoothed frequencies)."""
    bf = read_bytefile(REF_BYTEFILE)
    assert bf.ntaxa == data49.ntaxa
    for a, b in zip(bf.partitions, data49.partitions):
        assert a.width == b.width
        assert int(a.weights.sum()) == int(b.weights.sum())
    i1 = PhyloInstance(bf)
    t1 = i1.tree_from_newick(tree49_text)
    i2 = PhyloInstance(data49)
    t2 = i2.tree_from_newick(tree49_text)
    assert i1.evaluate(t1, full=True) == pytest.approx(
        i2.evaluate(t2, full=True), abs=0.01)


def test_slice_validation_errors(tmp_path_factory, data49):
    from examl_tpu.io.bytefile import (read_bytefile_for_process,
                                       read_bytefile_slice)
    path = str(tmp_path_factory.mktemp("bf") / "t49.binary")
    write_bytefile(path, data49)
    with pytest.raises(ValueError, match="outside"):
        read_bytefile_slice(path, {0: (0, 10 ** 9)})
    with pytest.raises(ValueError, match="procid"):
        read_bytefile_for_process(path, 5, 4)
    # slice metadata: global width/offset recorded, weight sums global
    sl = read_bytefile_for_process(path, 1, 4)
    full = read_bytefile(path)
    for sp, fp in zip(sl.partitions, full.partitions):
        assert sp.global_weight_sum == int(fp.weights.sum())
        if sp.width != fp.width:
            assert sp.global_width == fp.width
