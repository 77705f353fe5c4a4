"""A batched fleet program's share of its roofline, from the device
trace.

Device seconds and executions of the family's XLA modules in the traced
steps, against the least time the chip could take for the LIVE jobs of
those executions: a dispatch's own bytes (`call_bytes`, where the layer
file names one) and a live job's (`job_bytes`) times the live jobs a
dispatch, from `benchmarks/bytemodel_fleet.py` and the chip's HBM peak.
Live jobs a dispatch are the rise of `fleet.batch_jobs.<dispatches>`
over that of `fleet.batched_dispatches.<dispatches>` in the window,
whose steps all run the same batches; the padding slots of a batch are
no work a user asked for, so a change that drops them or fills them
reads higher.  No trace, no execution of the family in it, or a program
that does not count its dispatches by family: nothing to read.
"""

from benchmarks import bytemodel, bytemodel_fleet


def read(run, spec):
    trace = run.get("trace")
    if trace is None:
        return None
    fam = trace["families"].get(spec["family"])
    if not fam or not fam["calls"] or not fam["seconds"]:
        return None

    def rise(name):
        return run["counters1"].get(name, 0) - run["counters0"].get(name, 0)

    counter = f"fleet.batched_dispatches.{spec['dispatches']}"
    if counter not in run["counters1"] or not rise(counter):
        return None
    dispatches = rise(counter)
    jobs = rise(f"fleet.batch_jobs.{spec['dispatches']}") / dispatches
    nbytes = jobs * getattr(bytemodel_fleet, spec["job_bytes"])(
        run["config"])
    if "call_bytes" in spec:
        nbytes += getattr(bytemodel_fleet, spec["call_bytes"])(run["config"])
    floor = bytemodel.floor_seconds(nbytes, run["peak"])
    return 100.0 * fam["calls"] * floor / fam["seconds"]
