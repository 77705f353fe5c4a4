"""From a profiler trace (`*.xplane.pb`) to device numbers.

`jax.profiler.ProfileData` reads the file with nothing but JAX.  What
is taken from it:

* device planes (`/device:TPU:<n>`): the line of XLA operations gives
  the busy union (seconds in which an operation ran), the line of XLA
  modules gives each compiled program's executions, which are sorted
  into families by regular expressions on the module name;
* the host plane: the program's `engine:*` annotations
  (`jax.profiler.TraceAnnotation`, on the profiler's clock), used to
  say what the host was in during each idle gap of the device.

`python benchmarks/tracereduce.py <file>` prints what a trace holds, for
looking at one by hand.
"""

from __future__ import annotations

import bisect
import gzip
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
ANNOTATION = re.compile(r"^engine:")


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def union_seconds(intervals, lo: float, hi: float):
    """(busy seconds, gaps) of [start, end) intervals in ns clipped to
    [lo, hi]; gaps are the (start, end) stretches no interval covers."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy / 1e9, gaps


def module_family(name: str, families: dict):
    for fam, patterns in families.items():
        if any(re.search(p, name) for p in patterns):
            return fam
    return None


def short_op(name: str) -> str:
    """An XLA operation's event name is its whole HLO text; keep the
    result's name and the opcode: `%while.23 = (...) while(...)` ->
    `while.23 while`."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    m = re.search(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(", rest)
    return (head.lstrip("%") + (" " + m.group(1) if m else ""))[:80]


def _strip_id(name: str) -> str:
    """`jit_impl_eval(1234567)` -> `jit_impl_eval`."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(data, families: dict, window_annotation=None) -> dict:
    """`families` maps a family to regular expressions over XLA module
    names.  The window runs from the start of the first host event named
    `window_annotation` to the end of the last; without one, from the
    first to the last device operation.  Returns busy and
    window seconds (averaged over the device planes), each family's
    device seconds and executions, the operations and idle gaps that
    took most time."""
    planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane: "
                         + ", ".join(p.name for p in data.planes))
    host, marks = [], []
    for p in data.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                ev = _events(ln)
                host += [e for e in ev if ANNOTATION.match(e[0])]
                marks += [e for e in ev if e[0] == window_annotation]
    window = (min(s for _, s, _ in marks),
              max(s + d for _, s, d in marks)) if marks else None
    host.sort(key=lambda ev: ev[1])
    host_starts = [ev[1] for ev in host]

    busy_s, window_s = [], []
    fam = {f: {"seconds": 0.0, "calls": 0} for f in families}
    other = {}
    op_seconds = {}
    gaps_named = {}
    for p in planes:
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            raise ValueError(f"{p.name} lacks {OPS_LINE!r}/{MODULES_LINE!r}:"
                             f" {sorted(lines)}")
        ops = _events(lines[OPS_LINE])
        mods = _events(lines[MODULES_LINE])
        if not ops:
            raise ValueError(f"{p.name}: no operation ran on the device")
        lo, hi = window or (min(s for _, s, _ in ops),
                            max(s + d for _, s, d in ops))
        b, gaps = union_seconds([(s, s + d) for _, s, d in ops], lo, hi)
        busy_s.append(b)
        window_s.append((hi - lo) / 1e9)
        mods.sort(key=lambda ev: ev[1])
        mod_starts = [ev[1] for ev in mods]
        for name, s, d in ops:
            if s + d > lo and s < hi:
                i = bisect.bisect_right(mod_starts, s) - 1
                inside = i >= 0 and s < mods[i][1] + mods[i][2]
                key = ((_strip_id(mods[i][0]) if inside else "?") + "/"
                       + short_op(name))
                op_seconds[key] = op_seconds.get(key, 0.0) + d / 1e9
        for name, s, d in mods:
            if s + d <= lo or s >= hi:
                continue
            f = module_family(name, families)
            if f is None:
                key = _strip_id(name)
                other[key] = other.get(key, 0.0) + d / 1e9
            else:
                fam[f]["seconds"] += d / 1e9
                fam[f]["calls"] += 1
        for s, e in gaps:
            name = _host_during(host, host_starts, s, e)
            gaps_named[name] = gaps_named.get(name, 0.0) + (e - s) / 1e9
    n = len(planes)
    for f in fam.values():
        f["seconds"] /= n
        f["calls"] /= n

    def top(d):
        return sorted(([k, v / n] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:10]

    return {"busy_s": sum(busy_s) / n, "window_s": sum(window_s) / n,
            "families": fam, "other_modules": top(other),
            "device_ops": top(op_seconds), "idle_gaps": top(gaps_named)}


def _host_during(host, starts, s: float, e: float) -> str:
    """The `engine:*` annotation that covers most of the gap [s, e), or
    "between dispatches" where none covers any of it.  The annotations
    follow one another on one thread, so only the few that start before
    the gap's end and nearest to it can reach into it."""
    best, cover = "between dispatches", 0.0
    i = bisect.bisect_left(starts, e)
    for name, hs, hd in host[max(0, i - 8):i]:
        c = min(e, hs + hd) - max(s, hs)
        if c > cover:
            best, cover = name, c
    return best


def describe(data, top: int = 12) -> str:
    out = []
    for p in data.planes:
        out.append(f"PLANE {p.name}")
        for ln in p.lines:
            ev = _events(ln)
            tot = {}
            for name, _s, d in ev:
                c = tot.setdefault(_strip_id(name), [0, 0.0])
                c[0] += 1
                c[1] += d / 1e9
            out.append(f"  LINE {ln.name!r}: {len(ev)} events")
            for name, (cnt, sec) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {sec:10.6f} s  x{cnt:<6d} {name}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
