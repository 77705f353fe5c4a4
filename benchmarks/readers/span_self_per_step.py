"""Program spans over the window, per step: the sum, over every registry
timer whose name matches one of the layer file's `timers` (regular
expressions), of `field` (`self_s`: the spans' seconds less their child
spans'; `total_s`; or `count`), times `scale`.

A program without such spans (the parent of the PR that brought them),
or one whose timers carry no self seconds, gives nothing to read: the
metric is then left out of the line, not reported as 0.
"""

import re


def read(run, spec):
    field = spec.get("field", "self_s")
    patterns = [re.compile(p) for p in spec["timers"]]
    total, found = 0.0, False
    for name, t1 in run["timers1"].items():
        if field not in t1 or not any(p.search(name) for p in patterns):
            continue
        found = True
        total += t1[field] - run["timers0"].get(name, {}).get(field, 0)
    if not found:
        return None
    return total * spec.get("scale", 1.0) / len(run["spans"])
