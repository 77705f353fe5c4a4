"""`chip_smoke.py --rehearse`: the chip check's control flow, on CPU.

The script is what proves on the chip that the system still starts; here
only its plumbing is held: every line parses and names its device, the
last line is the contract's, the compile cache goes where
JAX_COMPILATION_CACHE_DIR says, a failed phase is a non-zero exit, and
without --rehearse a CPU is refused.  The `search` phase is left to
tests/test_cli.py::test_driver_search_end_to_end (a -f d search on CPU
takes minutes).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(args, cache_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "EXAML_COMPILE_CACHE")}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jaxcache"))
    proc, lines = _smoke(["--rehearse", "--phases", "fullwidth,evaluate"],
                         cache_dir=cache)
    return proc, lines, cache


def test_rehearsal_lines_and_contract(rehearsal):
    proc, lines, cache = rehearsal
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(ln) for ln in lines]          # every line parses
    *phases, last = recs
    assert [r["phase"] for r in phases] == [
        "device", "parse", "fullwidth", "parse", "evaluate", "total"]
    for r in phases:                 # every line says where it ran
        assert r["platform"] == "cpu" and r["rehearse"] is True
        assert r["device_kind"]
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}
    by = {r["phase"]: r for r in phases}
    assert by["device"]["compile_cache"] == cache    # placed from outside
    assert os.listdir(cache)
    full = by["fullwidth"]
    assert full["rel_err"] <= full["rtol"] == 2e-5
    assert full["lnl_after_smooth"] >= full["lnl_engine"]
    assert full["grad_passes"] >= 1 and full["tiers"] == ["chunk", "grad"]
    assert not full["demotions"]
    ev = by["evaluate"]
    assert ev["lnl_end"] > ev["lnl_start_oracle"] and not ev["demotions"]
    assert ev["ledger_events"] > 0      # the demotion check read a ledger


def test_failed_phase_exits_nonzero_and_second_run_is_warmer(rehearsal):
    """A phase made to fail (a tolerance f32 cannot meet) exits non-zero
    and prints no result line; being the second run on the same cache
    directory, it compiles fewer programs fresh than the first."""
    _, first_lines, cache = rehearsal
    proc, lines = _smoke(["--rehearse", "--phases", "fullwidth",
                          "--rtol", "1e-15"], cache_dir=cache)
    assert proc.returncode != 0
    assert "FAILED: fullwidth" in proc.stderr
    recs = [json.loads(ln) for ln in lines]
    assert all("ok" not in r for r in recs)
    first = next(json.loads(ln) for ln in first_lines
                 if '"phase": "fullwidth"' in ln)
    second = next(r for r in recs if r["phase"] == "fullwidth")
    assert second["programs_fresh"] < first["programs_fresh"]
    assert second["programs_from_xla_cache"] >= 1


def test_cpu_without_rehearse_is_refused():
    proc, lines = _smoke([])
    assert proc.returncode != 0
    assert not lines                          # no result is printed
    assert "no accelerator" in proc.stderr
