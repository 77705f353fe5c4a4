"""An SPR scan program's share of its roofline, from the device trace.

`family_roofline` with a floor that varies with the plan: the family's
device seconds and executions in the traced steps come from the trace;
the bytes of a call are `benchmarks/bytemodel_search.py`'s closed form
over what the arm's dispatches of the whole window carried (the
program's `search.scan_*` / `search.thorough_*` counters) divided by
those dispatches, the steps of a cell being identical.  Nothing to read
(no trace, no execution of the family in it, a program without the
counters, no dispatch of the arm in the window) returns nothing.
"""

from benchmarks import bytemodel, bytemodel_search


def read(run, spec):
    trace = run.get("trace")
    if trace is None:
        return None
    fam = trace["families"].get(spec["family"])
    if not fam or not fam["calls"] or not fam["seconds"]:
        return None
    counts = bytemodel_search.window_counts(
        run["counters0"], run["counters1"], spec["arm"])
    if not counts or not counts["dispatches"]:
        return None
    floor = bytemodel.floor_seconds(
        bytemodel_search.bytes_per_dispatch(counts, run["config"]),
        run["peak"])
    return 100.0 * fam["calls"] * floor / fam["seconds"]
