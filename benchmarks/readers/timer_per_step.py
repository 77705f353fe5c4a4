"""A program timer over the window, per step: its seconds (times
`scale`) or, with `"field": "count"`, its number of readings."""


def read(run, spec):
    t1 = run["timers1"].get(spec["timer"])
    if t1 is None:
        return None
    t0 = run["timers0"].get(spec["timer"], {"count": 0, "total_s": 0.0})
    field = spec.get("field", "total_s")
    return ((t1[field] - t0[field]) * spec.get("scale", 1.0)
            / len(run["spans"]))
