"""graftlint (tools/graftlint): the static checks that pin this repo's
dispatch, observability and durability disciplines.

Each GL00x check gets a seeded-violation fixture (detected), a clean
fixture (passes) and a suppression path; plus the acceptance run: the
REPO ITSELF lints clean under --strict, which is what the CI
`lint-smoke` step gates on.  Everything here is pure-AST string work —
no jax import, no fixtures on disk — so the whole module adds seconds
to tier-1, not minutes.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint import core                      # noqa: E402
from tools.graftlint import checks_env                # noqa: E402
from tools.graftlint.checks_env import check_env_registry   # noqa: E402
from tools.graftlint.checks_faults import check_fault_drift  # noqa: E402
from tools.graftlint.checks_io import check_durability       # noqa: E402
from tools.graftlint.checks_jax import (                     # noqa: E402
    check_cond_write, check_host_sync, check_jit_key)
from tools.graftlint.checks_obs import check_obs_drift       # noqa: E402


def project(files, tests=None, readme="", workflows=""):
    return core.Project(
        files=[core.LintFile.parse(p, src) for p, src in files],
        test_files=[core.LintFile.parse(p, src)
                    for p, src in (tests or [])],
        readme=readme, workflows=workflows)


def idents(findings, check=None):
    return [f.ident for f in findings
            if check is None or f.check == check]


# -- GL001: cond-write hazard ------------------------------------------------

COND_WRITE_BAD = '''
import jax

def run(clv, pred, v):
    def true_fun(c):
        return c.at[0].set(v)          # the 7.6x pitfall
    def false_fun(c):
        return c
    return jax.lax.cond(pred, true_fun, false_fun, clv)
'''

COND_WRITE_FACTORY_BAD = '''
import jax

def dispatch(clv, ci, vals):
    def make_branch(k):
        def branch(c, off):
            return jax.lax.dynamic_update_slice(c, vals[k], (off,))
        return branch
    branches = [make_branch(k) for k in (0, 1, 2)]
    return jax.lax.switch(ci, branches, clv, 0)
'''

COND_WRITE_CLEAN = '''
import jax

def dispatch(clv, ci, vals):
    def make_branch(k):
        def branch(c, off):
            return c[off] * vals[k]    # branches only COMPUTE
        return branch
    branches = [make_branch(k) for k in (0, 1, 2)]
    v = jax.lax.switch(ci, branches, clv, 0)
    # ... and the write happens OUTSIDE the conditional (scan-body
    # writes are the correct pattern and must not be flagged):
    def body(carry, x):
        return jax.lax.dynamic_update_slice(carry, v, (x,)), None
    out, _ = jax.lax.scan(body, clv, vals)
    return out
'''


def test_gl001_detects_at_set_in_cond_branch():
    p = project([("examl_tpu/ops/fake.py", COND_WRITE_BAD)])
    ids = idents(check_cond_write(p), "GL001")
    assert ids == ["examl_tpu/ops/fake.py::cond-write::true_fun"
                   "::.at[...].set"]


def test_gl001_detects_dus_through_branch_factory():
    p = project([("examl_tpu/ops/fake.py", COND_WRITE_FACTORY_BAD)])
    ids = idents(check_cond_write(p), "GL001")
    assert any("dynamic_update_slice" in i for i in ids)


def test_gl001_clean_compute_only_branches_and_scan_writes():
    p = project([("examl_tpu/ops/fake.py", COND_WRITE_CLEAN)])
    assert check_cond_write(p) == []


def test_gl001_pragma_suppression_requires_reason():
    bad = COND_WRITE_BAD.replace(
        "return c.at[0].set(v)          # the 7.6x pitfall",
        "return c.at[0].set(v)  # graftlint: disable=GL001 -- proven "
        "copy-free on this shape")
    p = project([("examl_tpu/ops/fake.py", bad)])
    out = core.apply_suppressions(p, check_cond_write(p), [])
    assert [f for f in out if f.suppressed is None] == []
    reasonless = COND_WRITE_BAD.replace(
        "return c.at[0].set(v)          # the 7.6x pitfall",
        "return c.at[0].set(v)  # graftlint: disable=GL001 --")
    p2 = project([("examl_tpu/ops/fake.py", reasonless)])
    out2 = core.apply_suppressions(p2, check_cond_write(p2), [])
    active = [f for f in out2 if f.suppressed is None]
    # The finding stays active AND the reasonless pragma is flagged.
    assert {f.check for f in active} == {"GL001", "GL000"}


# -- GL002: jit-key hygiene --------------------------------------------------

JIT_KEY_BAD = '''
def fetch(eng, entries):
    key = ("fast", len(entries))
    fn = eng.cache_get(key)
    return fn
'''

JIT_KEY_CLEAN = '''
from examl_tpu.utils import bucket_len

def fetch(eng, entries, profile, with_eval):
    L = bucket_len(len(entries))
    key = ("fast", profile, L, with_eval)
    fn = eng.cache_get(key)
    if fn is None:
        fn = eng.cache_put(key, object())
    return fn
'''

JIT_KEY_PARAM_PROPAGATION = '''
from examl_tpu.utils import bucket_len

def _program(eng, n_chunks):
    key = ("scan", n_chunks)
    return eng.cache_get(key)

def caller_bad(eng, cands):
    return _program(eng, len(cands))

def caller_good(eng, cands):
    return _program(eng, bucket_len(len(cands)))
'''


def test_gl002_detects_raw_len_in_key():
    p = project([("examl_tpu/ops/fake.py", JIT_KEY_BAD)])
    ids = idents(check_jit_key(p), "GL002")
    assert ids == ["examl_tpu/ops/fake.py::jit-key::fetch::len(entries)"]


def test_gl002_bucketed_key_is_clean():
    p = project([("examl_tpu/ops/fake.py", JIT_KEY_CLEAN)])
    assert check_jit_key(p) == []


def test_gl002_propagates_one_level_to_call_sites():
    p = project([("examl_tpu/ops/fake.py", JIT_KEY_PARAM_PROPAGATION)])
    ids = idents(check_jit_key(p), "GL002")
    # caller_bad's raw len() is flagged; caller_good's bucketed arg not.
    assert ids == ["examl_tpu/ops/fake.py::jit-key::"
                   "caller_bad->_program::len(cands)"]


def test_gl002_method_call_sites_shift_past_self():
    # Bound-method calls don't pass `self` positionally — the caller's
    # first positional arg is the SECOND callee parameter (review-fix:
    # the dominant engine idiom is methods, and the unshifted index
    # silently inspected the wrong argument).
    src = '''
class Engine:
    def _lookup(self, jpad):
        key = ("fast", jpad)
        return self.cache_get(key)

    def bad(self, arr):
        return self._lookup(len(arr))

    def good(self, arr):
        from examl_tpu.utils import bucket_len
        return self._lookup(bucket_len(len(arr)))
'''
    p = project([("examl_tpu/ops/fake.py", src)])
    ids = idents(check_jit_key(p), "GL002")
    assert ids == ["examl_tpu/ops/fake.py::jit-key::"
                   "bad->_lookup::len(arr)"]


# -- GL003: hidden host-sync -------------------------------------------------

HOST_SYNC_BAD = '''
import numpy as np

def evaluate(self, key, x):
    fn = self.cache_get(key)
    out = fn(x)
    return float(out)
'''

HOST_SYNC_CLEAN = '''
import jax.numpy as jnp

def evaluate(self, key, x):
    fn = self.cache_get(key)
    out = fn(x)
    return jnp.asarray(out)       # stays on device: not a sync
'''


def test_gl003_detects_float_on_dispatch_result():
    p = project([("examl_tpu/ops/fake.py", HOST_SYNC_BAD)])
    ids = idents(check_host_sync(p), "GL003")
    assert ids == ["examl_tpu/ops/fake.py::host-sync::evaluate"
                   "::float(out)"]


def test_gl003_taints_through_guarded_cache_fetch():
    # review-fix: a dispatch fn assigned inside a try/if block is seen
    # AFTER the statement using it in ast.walk's breadth-first order —
    # the taint pass must collect dispatch fns before results.
    src = '''
def evaluate(self, key, x):
    fn = None
    try:
        fn = self.cache_get(key)
    except KeyError:
        pass
    out = fn(x)
    return float(out)
'''
    p = project([("examl_tpu/ops/fake.py", src)])
    assert idents(check_host_sync(p), "GL003") == [
        "examl_tpu/ops/fake.py::host-sync::evaluate::float(out)"]


def test_gl003_device_side_asarray_is_clean():
    p = project([("examl_tpu/ops/fake.py", HOST_SYNC_CLEAN)])
    assert check_host_sync(p) == []


def test_gl003_registered_seam_may_block():
    # The same blocking pattern inside a registered seam (path AND
    # function name must match config.SYNC_SEAMS) is the measurement.
    p = project([("examl_tpu/fleet/batch.py",
                  HOST_SYNC_BAD.replace("def evaluate",
                                        "def collect"))])
    assert check_host_sync(p) == []


# -- GL004: env-var registry -------------------------------------------------

ENV_FIXTURE = '''
import os

MY_VAR = "EXAML_TEST_CONSTANT"
FROZEN = os.environ.get("EXAML_TEST_IMPORT")      # import-time read

def read_things():
    a = os.environ.get("EXAML_TEST_OK", "")
    b = os.environ.get(MY_VAR)
    c = os.environ.get("EXAML_TEST_ROGUE")
    return a, b, c
'''


def test_gl004_registry_directions(monkeypatch):
    monkeypatch.setattr(checks_env, "ENV_REGISTRY", {
        "EXAML_TEST_OK": {"doc": "readme", "note": "documented flag"},
        "EXAML_TEST_CONSTANT": {"doc": "registry", "note": "via const"},
        "EXAML_TEST_IMPORT": {"doc": "registry", "note": "frozen"},
        "EXAML_TEST_MISSING_DOC": {"doc": "readme", "note": "x"},
        "EXAML_TEST_DEAD": {"doc": "registry", "note": "nobody reads"},
    })
    p = project([("examl_tpu/fake.py", ENV_FIXTURE)],
                readme="flags: EXAML_TEST_OK does things")
    kinds = sorted(i.split("::")[1] + "::" + i.split("::")[2]
                   for i in idents(check_env_registry(p), "GL004"))
    assert kinds == [
        "env-dead::EXAML_TEST_DEAD",          # registered, never read
        "env-dead::EXAML_TEST_MISSING_DOC",
        "env-import-time::EXAML_TEST_IMPORT",  # module-scope read
        "env-unregistered::EXAML_TEST_ROGUE",  # read, not registered
    ]


def test_gl004_import_time_ok_justification(monkeypatch):
    monkeypatch.setattr(checks_env, "ENV_REGISTRY", {
        "EXAML_TEST_IMPORT": {"doc": "registry", "note": "frozen",
                              "import_time_ok": "read once by design"},
    })
    p = project([("examl_tpu/fake.py",
                  'import os\nX = os.environ.get("EXAML_TEST_IMPORT")\n')])
    assert check_env_registry(p) == []


def test_gl004_repo_registry_entries_are_all_justified():
    # The real registry: every entry carries a non-empty note (the
    # baseline-policy analogue for env documentation).
    from tools.graftlint.envregistry import ENV_REGISTRY
    for var, entry in ENV_REGISTRY.items():
        assert str(entry.get("note", "")).strip(), var
        assert entry.get("doc") in ("readme", "registry"), var


# -- GL005: obs-name drift ---------------------------------------------------

OBS_EMIT = '''
from examl_tpu import obs

def work(family):
    obs.inc("engine.test_hits")
    obs.inc(f"engine.test_by_family.{family}")
    obs.gauge("engine.test_orphan_gauge", 1.0)
    obs.ledger_event("test.event")
'''

OBS_RENDER = '''
def render(counters):
    print(counters.get("engine.test_hits"))
    for k in counters:
        if k.startswith("engine.test_by_family."):
            print(k)
    print(counters.get("engine.test_phantom_row"))
'''


def test_gl005_drift_both_directions():
    p = project([("examl_tpu/ops/fake.py", OBS_EMIT),
                 ("tools/run_report.py", OBS_RENDER)])
    ids = idents(check_obs_drift(p), "GL005")
    assert ("examl_tpu/ops/fake.py::obs-unrendered::"
            "engine.test_orphan_gauge" in ids)          # emitted, dead
    assert ("tools/run_report.py::obs-phantom::"
            "engine.test_phantom_row" in ids)           # rendered, dead
    # Exact and f-string-prefix emits matched by render/prefix scans:
    assert not any("engine.test_hits" in i for i in ids)
    assert not any("test_by_family" in i for i in ids)
    # Ledger kinds are exempt from the unrendered direction (the merged
    # timeline renders every kind generically).
    assert not any("test.event" in i for i in ids)


def test_gl005_tests_count_as_consumers():
    p = project([("examl_tpu/ops/fake.py", OBS_EMIT)],
                tests=[("tests/test_fake.py",
                        'def t(c):\n'
                        '    assert c["engine.test_hits"] == 1\n'
                        '    assert c["engine.test_by_family.x"] == 1\n'
                        '    assert c["engine.test_orphan_gauge"]\n')])
    assert idents(check_obs_drift(p), "GL005") == []


# -- GL006: fault-point drift ------------------------------------------------

FAULTS_FIXTURE = '''
POINTS = {
    "test.wired": "fully evidenced",
    "test.dead": "registered but never fired",
}
'''

SEAM_FIXTURE = '''
from examl_tpu.resilience import faults

def seam():
    faults.fire("test.wired")
    faults.fire("test.typo")      # not in POINTS: can never arm
'''


def test_gl006_all_four_directions():
    p = project(
        [("examl_tpu/resilience/faults.py", FAULTS_FIXTURE),
         ("examl_tpu/ops/fake.py", SEAM_FIXTURE)],
        tests=[("tests/test_chaos.py",
                'SPEC = "test.wired:after=2"\n')],
        readme="taxonomy: `test.wired` kills the run")
    ids = idents(check_fault_drift(p), "GL006")
    assert ("examl_tpu/ops/fake.py::fault-unregistered::test.typo"
            in ids)
    assert ("examl_tpu/resilience/faults.py::fault-unfired::test.dead"
            in ids)
    assert ("examl_tpu/resilience/faults.py::fault-untested::test.dead"
            in ids)
    assert ("examl_tpu/resilience/faults.py::fault-undocumented::"
            "test.dead" in ids)
    # The fully-evidenced point is silent in every direction.
    assert not any("::test.wired" in i for i in ids)


def test_gl006_repo_taxonomy_table_lists_fleet_points():
    # The ISSUE's satellite: the README failure-taxonomy table names
    # the PR9/PR10 fleet fault points literally.
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    table = readme[readme.index("### Failure taxonomy"):]
    table = table[:table.index("\n## ")]
    for point in ("fleet.dispatch", "fleet.job.poison",
                  "fleet.job.hang", "fleet.results.write"):
        assert point in table, point


# -- GL007: durability -------------------------------------------------------

DURABILITY_BAD = '''
import os, json

def publish(path, blob):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f)
    os.replace(tmp, path)
'''

DURABILITY_CLEAN = '''
import os, json

def publish(path, blob):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(blob, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
'''


def test_gl007_detects_unfsynced_publish():
    p = project([("examl_tpu/search/fake.py", DURABILITY_BAD)])
    ids = idents(check_durability(p), "GL007")
    assert ids == ["examl_tpu/search/fake.py::durability::publish"]


def test_gl007_fsync_before_replace_is_clean():
    p = project([("examl_tpu/search/fake.py", DURABILITY_CLEAN)])
    assert check_durability(p) == []


def test_gl007_comment_block_pragma_suppresses():
    src = DURABILITY_BAD.replace(
        "    os.replace(tmp, path)",
        "    # graftlint: disable=GL007 -- derived artifact, wrapped\n"
        "    # justification continues on a second comment line\n"
        "    os.replace(tmp, path)")
    p = project([("examl_tpu/search/fake.py", src)])
    out = core.apply_suppressions(p, check_durability(p), [])
    assert [f for f in out if f.suppressed is None] == []


# -- review-fix regressions --------------------------------------------------


def test_gl004_default_argument_reads_are_import_time(monkeypatch):
    # Defaults evaluate at `def` time: the env value freezes at import
    # exactly like a module-level read.
    monkeypatch.setattr(checks_env, "ENV_REGISTRY", {
        "EXAML_TEST_DEFAULT": {"doc": "registry", "note": "x"}})
    p = project([("examl_tpu/fake.py",
                  'import os\n\n'
                  'def f(x=os.environ.get("EXAML_TEST_DEFAULT")):\n'
                  '    return x\n')])
    ids = idents(check_env_registry(p), "GL004")
    assert ids == ["examl_tpu/fake.py::env-import-time::"
                   "EXAML_TEST_DEFAULT"]


def test_gl004_and_gl006_doc_matching_is_whole_token(monkeypatch):
    # A documented EXAML_COMPILE_CACHE must not vacuously document a new
    # EXAML_COMPILE; a registered fleet.job point is not documented by
    # the text mentioning fleet.job.poison.
    monkeypatch.setattr(checks_env, "ENV_REGISTRY", {
        "EXAML_TEST": {"doc": "readme", "note": "x"}})
    p = project([("examl_tpu/fake.py",
                  'import os\n\ndef f():\n'
                  '    return os.environ.get("EXAML_TEST")\n')],
                readme="only EXAML_TEST_CAP is documented here")
    assert idents(check_env_registry(p), "GL004") == [
        "examl_tpu/fake.py::env-undocumented::EXAML_TEST"]
    p2 = project(
        [("examl_tpu/resilience/faults.py",
          'POINTS = {"test.job": "prefix of the documented point"}\n'),
         ("examl_tpu/ops/fake.py",
          'from examl_tpu.resilience import faults\n\n'
          'def seam():\n    faults.fire("test.job")\n')],
        tests=[("tests/t.py", 'S = "test.job.poison"\n')],
        readme="taxonomy: `test.job.poison`")
    ids = idents(check_fault_drift(p2), "GL006")
    assert ("examl_tpu/resilience/faults.py::fault-untested::test.job"
            in ids)
    assert ("examl_tpu/resilience/faults.py::fault-undocumented::"
            "test.job" in ids)


def test_pragma_without_separator_is_reasonless_not_invisible():
    # `# graftlint: disable=GL007` (no `--`) must parse as a pragma and
    # fail as GL000, not silently fail to suppress.
    src = DURABILITY_BAD.replace(
        "    os.replace(tmp, path)",
        "    os.replace(tmp, path)  # graftlint: disable=GL007")
    p = project([("examl_tpu/search/fake.py", src)])
    out = core.apply_suppressions(p, check_durability(p), [])
    active = [f for f in out if f.suppressed is None]
    assert {f.check for f in active} == {"GL007", "GL000"}


def test_gl002_propagation_dedups_across_get_and_put():
    src = '''
class Engine:
    def _lookup(self, n):
        key = ("fam", n)
        fn = self.cache_get(key)
        if fn is None:
            fn = self.cache_put(key, object())
        return fn

    def bad(self, xs):
        return self._lookup(len(xs))
'''
    p = project([("examl_tpu/ops/fake.py", src)])
    hits = [f for f in check_jit_key(p) if f.check == "GL002"]
    assert len(hits) == 1


def test_strict_select_does_not_report_out_of_scope_stale(tmp_path):
    from tools.graftlint.__main__ import main
    root = tmp_path / "repo"
    (root / "examl_tpu").mkdir(parents=True)
    (root / "tools").mkdir()
    (root / "examl_tpu" / "ok.py").write_text("X = 1\n")
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps({"entries": [
        {"check": "GL004", "ident": "whatever::*",
         "justification": "belongs to a check this run skips"}]}))
    rc = main(["--root", str(root), "--select", "GL001", "--strict",
               "--baseline", str(bp)])
    assert rc == 0
    # ... while a full strict run still reports it stale.
    rc2 = main(["--root", str(root), "--strict", "--baseline", str(bp)])
    assert rc2 == 1


# -- every check: seeded fixture fires AND is pragma-suppressible ------------


def test_every_check_fires_and_is_suppressible(monkeypatch):
    """The ISSUE's acceptance matrix in one loop: per check, the seeded
    violation is detected, and appending an inline justified pragma on
    the finding's own line suppresses exactly it."""
    monkeypatch.setattr(checks_env, "ENV_REGISTRY", {})
    cases = [
        (check_cond_write, "GL001",
         [("examl_tpu/ops/fake.py", COND_WRITE_BAD)], {}),
        (check_jit_key, "GL002",
         [("examl_tpu/ops/fake.py", JIT_KEY_BAD)], {}),
        (check_host_sync, "GL003",
         [("examl_tpu/ops/fake.py", HOST_SYNC_BAD)], {}),
        (check_env_registry, "GL004",
         [("examl_tpu/fake.py",
           'import os\n\ndef r():\n'
           '    return os.environ.get("EXAML_TEST_ROGUE")\n')], {}),
        (check_obs_drift, "GL005",
         [("examl_tpu/ops/fake.py",
           'from examl_tpu import obs\n\ndef w():\n'
           '    obs.inc("engine.test_orphan")\n')], {}),
        (check_fault_drift, "GL006",
         [("examl_tpu/resilience/faults.py", FAULTS_FIXTURE),
          ("examl_tpu/ops/fake.py", SEAM_FIXTURE)],
         {"readme": "`test.wired` and `test.dead`",
          "tests": [("tests/t.py", 'S = "test.wired,test.dead"\n')]}),
        (check_durability, "GL007",
         [("examl_tpu/search/fake.py", DURABILITY_BAD)], {}),
    ]
    for check, cid, files, evidence in cases:
        p = project(files, **evidence)
        findings = [f for f in check(p) if f.check == cid]
        assert findings, f"{cid} did not fire on its seeded fixture"
        pick = findings[0]
        # Append the pragma to the finding's own line and re-run.
        patched = []
        for path, src in files:
            if path == pick.path:
                lines = src.splitlines()
                lines[pick.line - 1] += (f"  # graftlint: disable={cid}"
                                         " -- justified in test")
                src = "\n".join(lines) + "\n"
            patched.append((path, src))
        p2 = project(patched, **evidence)
        out = core.apply_suppressions(
            p2, [f for f in check(p2) if f.check == cid], [])
        assert all(f.suppressed for f in out
                   if f.ident == pick.ident), f"{cid} not suppressible"


# -- mutation pins: the HISTORICAL pitfalls on the REAL modules --------------


def test_gl001_pins_the_pr10_cond_copy_in_real_universal_py():
    """Reintroduce the measured 7.6x pitfall — move the arena write
    into the switch branch of ops/universal.py — and GL001 must fire.
    This is the permanent pin the ROOFLINE note refers to."""
    path = os.path.join(REPO, "examl_tpu", "ops", "universal.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    bad = src.replace(
        "            return values(clv, scaler, ch)",
        "            v, sc = values(clv, scaler, ch)\n"
        "            c2 = jax.lax.dynamic_update_slice(\n"
        "                clv, v, (off, 0, 0, 0, 0))\n"
        "            return c2, sc")
    assert bad != src, "universal.py branch body moved; update the pin"
    p = project([("examl_tpu/ops/universal.py", bad)])
    assert any(f.check == "GL001" for f in check_cond_write(p))
    # ... and the shipped file is clean.
    assert check_cond_write(project(
        [("examl_tpu/ops/universal.py", src)])) == []


def test_gl002_pins_the_compile_storm_in_real_engine_py():
    """Replace the bucketed universal jit key with a raw len() in
    ops/engine.py and GL002 must fire (key cardinality would grow with
    topology size — the compile-storm failure mode)."""
    path = os.path.join(REPO, "examl_tpu", "ops", "engine.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    bad = src.replace(
        'key = ("universal", akey, npad, ppad, with_eval)',
        'key = ("universal", akey, len(cls_h), ppad, with_eval)')
    assert bad != src, "engine.py universal key moved; update the pin"
    p = project([("examl_tpu/ops/engine.py", bad)])
    hits = [f for f in check_jit_key(p) if f.check == "GL002"]
    assert len(hits) == 1            # deduped across cache_get/put
    assert "len(cls_h)" in hits[0].ident


# -- baseline policy ---------------------------------------------------------

def test_baseline_blanket_gl001_gl007_rejected(tmp_path):
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps({"entries": [
        {"check": "GL001", "ident": "*", "justification": "meh"},
        {"check": "GL007", "ident": "examl_tpu/*", "justification": "x"},
        {"check": "GL005", "ident": "*::obs-unrendered::legacy.*",
         "justification": "legacy counters kept for dashboards"},
        {"check": "GL004", "ident": "a::b"},          # no justification
    ]}))
    entries, problems = core.load_baseline(str(bp))
    # Only the justified, non-blanket GL005 entry loads.
    assert [e.check for e in entries] == ["GL005"]
    assert len(problems) == 3
    assert all(p.check == "GL000" for p in problems)


def test_baseline_suppresses_and_reports_stale(tmp_path):
    bp = tmp_path / "baseline.json"
    bp.write_text(json.dumps({"entries": [
        {"check": "GL002", "ident": "examl_tpu/ops/fake.py::jit-key::*",
         "justification": "pre-linter key, bounded by construction"},
        {"check": "GL002", "ident": "never/matches.py::*",
         "justification": "stale"},
    ]}))
    entries, problems = core.load_baseline(str(bp))
    assert problems == []
    p = project([("examl_tpu/ops/fake.py", JIT_KEY_BAD)])
    out = core.apply_suppressions(p, check_jit_key(p), entries)
    assert [f for f in out if f.suppressed is None] == []
    stale = core.stale_baseline_findings(entries, str(bp))
    assert len(stale) == 1 and "never/matches.py" in stale[0].ident


# -- the acceptance run: THE REPO LINTS CLEAN --------------------------------

def test_repo_lints_clean_under_strict(capsys):
    """`python -m tools.graftlint --strict` exits 0 on this checkout —
    every GL001-GL007 invariant holds (or carries an inline-pragma /
    baseline justification), the baseline has no stale entries, and the
    run costs seconds (pure AST)."""
    from tools.graftlint.__main__ import main
    rc = main(["--strict", "--root", REPO])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 active finding(s)" in out


def test_cli_json_artifact_and_exit_codes(tmp_path, monkeypatch):
    """Seeded violation through the real CLI: exit 1, JSON artifact
    carries the finding; --select narrows to one check."""
    from tools.graftlint.__main__ import main
    root = tmp_path / "repo"
    (root / "examl_tpu").mkdir(parents=True)
    (root / "tools").mkdir()
    (root / "examl_tpu" / "bad.py").write_text(DURABILITY_BAD)
    out_json = tmp_path / "gl.json"
    rc = main(["--root", str(root), "--select", "GL007",
               "--json", str(out_json)])
    assert rc == 1
    blob = json.loads(out_json.read_text())
    assert blob["counts"] == {"GL007": 1}
    assert blob["active"][0]["check"] == "GL007"
    rc2 = main(["--root", str(root), "--select", "GL001"])
    assert rc2 == 0
