#!/usr/bin/env python3
"""Which `jax.named_scope` does each device operation of a trace carry?

    python tools/trace_scopes.py <file.xplane.pb[.gz]> [top]

A TPU trace names its device events by XLA's numbering (`%while.23 =
...`).  The scope a kernel was traced under (`examl/outroot`,
`examl/edge_grad`, ...: ops/kernels.py) is NOT in the device event:
`jax.profiler.ProfileData` shows three stats an event (device offset,
duration, time scale), and the event's metadata adds `hlo_category`,
`program_id`, `flops`, `bytes_accessed`, `source` (file:line) and
`source_stack`.  It sits in the plane `/host:metadata`: one event
metadata a compiled program (`jit__grad_impl(<program_id>)`) whose stat
`Hlo Proto` holds the serialized module, and there each instruction's
`metadata.op_name` (`jit(_grad_impl)/examl/outroot/while`).  The join
is the device event's `program_id` stat and its display name
(`while.23`, the instruction's name).

`ProfileData` cannot reach a plane without lines, so this reads the
protobuf with the definitions TensorFlow ships (an import of some ten
seconds: a tool for looking at a kept trace, `--keep-trace` of
benchmarks/run.py, not a part of any timed path).  Prints the device
operations that took most time, with seconds, executions, scope and
source line (a loop's body runs inside its loop's interval: seconds of
nested operations are not to be added to their loop's).
"""

from __future__ import annotations

import gzip
import re
import sys

SCOPE = re.compile(r"examl/[a-z_]+")


def load(path: str):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    opener = gzip.open if path.endswith(".gz") else open
    space = xplane_pb2.XSpace()
    with opener(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def op_names(space) -> dict:
    """{program_id: {instruction name: op_name}} from the `Hlo Proto`
    stats of the `/host:metadata` plane."""
    from tensorflow.compiler.xla.service import hlo_pb2
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for pid, md in plane.event_metadata.items():
            for st in md.stats:
                if not st.bytes_value:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(st.bytes_value)
                # the map's key is signed, the event's stat is not
                out[pid % (1 << 64)] = {ins.name: ins.metadata.op_name
                            for comp in proto.hlo_module.computations
                            for ins in comp.instructions}
    return out


def scope_of(op_name: str) -> str:
    """The outermost `examl/*` scope of an op_name: the kernel the
    operation belongs to (inner scopes split a kernel further)."""
    m = SCOPE.search(op_name or "")
    return m.group(0) if m else "-"


def device_ops(space) -> list:
    """[(seconds, executions, program, instruction, scope, op_name,
    source)] over the XLA Ops lines of the device planes."""
    names = op_names(space)
    total = {}
    for plane in space.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        keys = {}                # metadata id -> ((program, instruction), source)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if ev.metadata_id not in keys:
                    md = plane.event_metadata[ev.metadata_id]
                    st = {stat[s.metadata_id]: s for s in md.stats}
                    pid = (st["program_id"].uint64_value
                           or st["program_id"].int64_value) % (1 << 64) \
                        if "program_id" in st else 0
                    keys[ev.metadata_id] = (
                        (pid, md.display_name or md.name), st.get("source"))
                key, src = keys[ev.metadata_id]
                row = total.setdefault(key, [0.0, 0, src])
                row[0] += ev.duration_ps / 1e12
                row[1] += 1
    rows = []
    for (pid, ins), (sec, n, src) in total.items():
        op_name = names.get(pid, {}).get(ins, "")
        rows.append((sec, n, pid, ins, scope_of(op_name), op_name,
                     src.str_value if src is not None else ""))
    return sorted(rows, reverse=True)


def main(argv) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__, file=sys.stderr)
        return 2
    space = load(argv[1])
    top = int(argv[2]) if len(argv) == 3 else 16
    for sec, n, _pid, ins, scope, op_name, src in device_ops(space)[:top]:
        print(f"{sec:10.6f} s x{n:<6d} {ins:<24s} {scope:<16s} "
              f"{op_name}  [{src}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
