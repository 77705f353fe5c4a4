"""A program timer's reading when set-up ended: its seconds
(`total_s`) or, with `"field": "count"`, its number of readings (the
process starts at zero, so this is what set-up added).  A program
without the timer (the parent of the PR that brought its span, or a run
with the work switched off) gives nothing to read: the metric is then
left out of the line, not reported as 0."""


def read(run, spec):
    timer = run["timers0"].get(spec["timer"])
    if timer is None:
        return None
    return timer[spec.get("field", "total_s")]
