"""graftlint — JAX-aware static analysis for this repo's load-bearing
disciplines.

Ten PRs of measurement earned a set of conventions that nothing
enforced: the arena write stays OUTSIDE `lax.cond`/`lax.switch`
branches (the 7.6x carry-copy pitfall measured in PR10), jit-cache
keys bucket their raw ints so the program family stays CLOSED (the
compile-once premise of the bank and the AOT-export roadmap),
checkpoint publishes fsync-then-rename, and 150+ `EXAML_*` env reads
plus dozens of obs counter / ledger-event / fault-point names are
consumed by `tools/run_report.py`, `tools/top.py`, the supervisor and
the README with zero drift detection — one typo silently produces a
roofline report with a missing row.  This package turns each
discipline into a numbered, individually-suppressible check over the
stdlib `ast` (no jax import, seconds not minutes):

    GL001  cond-write hazard   arena/carry writes lexically inside a
                               callable passed to lax.cond/lax.switch
    GL002  jit-key hygiene     raw ints in engine program-cache keys
                               that never passed a bounding helper
                               (utils.bucket_len / next_pow2 / the
                               registered pad pickers)
    GL003  hidden host-sync    float()/.item()/bool()/np.asarray on a
                               dispatch result outside the registered
                               blocking trav-eval seams
    GL004  env-var registry    EXAML_* reads vs tools/graftlint/
                               envregistry.py and the README flag
                               tables: unregistered, dead and
                               import-time-scoped reads all fail
    GL005  obs-name drift      counters/gauges/timers/ledger events
                               emitted but never rendered (run_report/
                               top/tests) or rendered but never emitted
    GL006  fault-point drift   resilience/faults.py POINTS vs fire()
                               seams vs chaos-test/CI specs vs the
                               README failure-taxonomy table
    GL007  durability          os.replace publishes not preceded by an
                               fsync of the staged file in-function

Run `python -m tools.graftlint --strict` (CI does); suppress a single
finding with an inline pragma carrying a justification

    os.replace(tmp, path)  # graftlint: disable=GL007 -- derived file

or a baseline entry in tools/graftlint/baseline.json.  Blanket
suppressions of GL001/GL007 are rejected at baseline load time.
"""

from __future__ import annotations

__version__ = "1.0"

from tools.graftlint.core import Finding, LintFile, Project, run_checks  # noqa: F401,E501
