"""The share, in %, of a whole that a part leaves over, from two program
counters at the end of set-up: 100 x (1 - part / whole).  A program
without either counter (the parent of the PR that brought them) gives
nothing to read: the metric is then left out of the line, not reported
as 0."""


def read(run, spec):
    part = run["counters0"].get(spec["part"])
    whole = run["counters0"].get(spec["whole"])
    if part is None or not whole:
        return None
    return 100.0 * (1.0 - part / whole)
