"""A program family's share of its roofline, from the device trace.

Device seconds and executions of the family's XLA modules in the traced
steps; the least time the chip could take for as many calls, from the
algorithm's bytes (benchmarks/bytemodel.py) and the chip's HBM peak
(benchmarks/peaks.json).  Nothing to read (no trace, or no execution of
the family in it) returns nothing; run.py makes a family that a traced
cell must show and does not an error of the run.
"""

from benchmarks import bytemodel


def read(run, spec):
    trace = run.get("trace")
    if trace is None:
        return None
    fam = trace["families"].get(spec["family"])
    if not fam or not fam["calls"] or not fam["seconds"]:
        return None
    floor = bytemodel.floor_seconds(
        getattr(bytemodel, spec["bytes"])(run["config"]), run["peak"])
    return 100.0 * fam["calls"] * floor / fam["seconds"]
