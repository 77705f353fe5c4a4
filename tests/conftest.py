"""Test configuration: CPU backend with 8 virtual devices, float64 on.

Multi-chip sharding is validated on a virtual CPU mesh (the driver separately
dry-runs the multi-chip path); numerics tests need float64 like the
reference.
"""

import os

# Force CPU: numerics tests must run on host CPU, whatever platform the
# environment presets (a chip belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Belt and braces: something may have imported jax before this file
# ran, in which case the env var alone is too late.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache: the sharded/SEV batteries build many
# engine instances whose per-instance jit closures lower to identical
# HLO — the disk cache (keyed on HLO + backend build) shares compiles
# across instances AND across pytest runs, cutting the slow tiers'
# wall time.  It lives where JAX_COMPILATION_CACHE_DIR says, else under
# <checkout>/.xla_cache; EXAML_COMPILE_CACHE=0 disables.
from examl_tpu.config import enable_persistent_compilation_cache  # noqa: E402

enable_persistent_compilation_cache()

import pytest  # noqa: E402,F401

TESTDATA = "/root/reference/testData"


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end tests")


# The reference fixture set (/root/reference/testData, built binaries)
# exists on the dev container but not on hosted CI runners.  A test that
# needs it should read as SKIPPED there, not as a failure that turns the
# tier-1 gate permanently red — the product never writes under
# /root/reference, so a FileNotFoundError naming it is always the
# missing fixture set, never a regression.

@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    try:
        return (yield)
    except FileNotFoundError as exc:
        if "/root/reference" in str(exc):
            pytest.skip(f"reference fixture set missing: {exc}")
        raise


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    try:
        return (yield)
    except FileNotFoundError as exc:
        if "/root/reference" in str(exc):
            pytest.skip(f"reference fixture set missing: {exc}")
        raise


def correlated_dna(ntaxa, nsites, seed=42, mut=0.15):
    """Correlated random DNA (a shared mutation walk, so trees have real
    signal) — the common generator for the e2e test fixtures."""
    import numpy as np

    from examl_tpu.io.alignment import build_alignment_data
    rng = np.random.default_rng(seed)
    cur = rng.integers(0, 4, nsites)
    seqs = []
    for _ in range(ntaxa):
        flip = rng.random(nsites) < mut
        cur = np.where(flip, rng.integers(0, 4, nsites), cur)
        seqs.append("".join("ACGT"[c] for c in cur))
    return build_alignment_data([f"t{i}" for i in range(ntaxa)], seqs)
