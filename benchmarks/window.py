"""The measured window: whole cycles of whole steps.

A cell's traffic cycles over `cycle` distinct steps (one, or a set of
trees of unequal work), so the window holds whole cycles only: every
kind of step is timed as often as every other.  A new cycle starts only
while the time used so far plus the longest cycle seen still fits in
the budget; the first cycle always runs.  The metric is the time from
the first step's start to the last step's end over the number of steps:
all the work and all the time, no medians of chunks.
"""

from __future__ import annotations

import time


def run_window(step, seconds: float, cycle: int = 1,
               clock=time.perf_counter, on_cycle=None):
    """Call `step(i)` for i = 0, 1, ... under the rule above.  Returns
    the list of (start, end) pairs on `clock`, relative to the first
    start.
    `on_cycle(steps done, elapsed, longest cycle)` runs between cycles:
    in no step's time but in the window's (the tracer is stopped there)."""
    spans, longest = [], 0.0
    t0 = clock()
    i = 0
    while True:
        c0 = clock()
        for _ in range(cycle):
            s = clock()
            step(i)
            e = clock()
            spans.append((s - t0, e - t0))
            i += 1
        longest = max(longest, e - c0)
        if on_cycle is not None:
            on_cycle(i, e - t0, longest)
        if (clock() - t0) + longest > seconds:
            return spans


def step_seconds(spans) -> float:
    return (spans[-1][1] - spans[0][0]) / len(spans)
