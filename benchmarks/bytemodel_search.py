"""Bytes the algorithm needs for one dispatch of an SPR scan program,
from what the dispatch carries.

The benchmark's own closed form, beside `bytemodel.py` and counting rows
as it does.  A scan dispatch (`search/batchscan.py`: the lazy arm's
`jit_spr_scan_impl`, the thorough arm's `jit_spr_thorough_impl`) runs a
partial traversal (orientation fixes into the arena, uppass rows into
the scan region) and scores every candidate insertion of the window:

* an entry writes one CLV row with its scaler row and reads its two
  children: a row with its scaler row for an inner child, a byte a site
  for a tip (`bytemodel.bytes_per_traversal_counts`);
* a candidate reads two rows with their scaler rows: the far end of its
  edge and the uppass row above it; a far end that is a tip is a byte a
  site;
* the pruned subtree's row is read once a dispatch (a byte a site where
  the subtree is one tip).

Each operand once, whatever the arm iterates: the thorough arm's Newton
and smoothing rounds over a candidate's three operands are the
implementation's, not the algorithm's, so both arms share one floor.
Unlike a full traversal's, these counts vary with the plan, so they are
the program's own counters over the window (`search.scan_*`, of which
`search.thorough_*` is the thorough arm's share).
"""

from __future__ import annotations

from benchmarks import bytemodel

NAMES = ("dispatches", "entries", "tip_children", "candidates",
         "tip_operands")


def scan_bytes(entries: float, tip_children: float, candidates: float,
               tip_operands: float, dispatches: float, patterns: int,
               R: int, K: int, itemsize: int) -> float:
    """Bytes of `dispatches` scan dispatches carrying these totals."""
    row = patterns * R * K * itemsize + patterns * 4
    operands = 2 * candidates + dispatches
    return (bytemodel.bytes_per_traversal_counts(
        entries, tip_children, patterns, R, K, itemsize)
        + (operands - tip_operands) * row + tip_operands * patterns)


def window_counts(counters0: dict, counters1: dict, arm: str):
    """What the arm's dispatches of the window carried, from the
    program's counters; None where the program has none of them."""
    def rise(name):
        return counters1.get(name, 0) - counters0.get(name, 0)
    if "search.scan_entries" not in counters1:
        return None
    out = {n: rise("search.thorough_" + n) for n in NAMES}
    if arm == "lazy":
        out = {n: rise("search.scan_" + n) - out[n] for n in NAMES}
    return out


def bytes_per_dispatch(counts: dict, config: dict) -> float:
    s = bytemodel.shapes(config)
    return scan_bytes(
        counts["entries"], counts["tip_children"], counts["candidates"],
        counts["tip_operands"], counts["dispatches"], s["patterns"],
        s["R"], s["K"], s["itemsize"]) / counts["dispatches"]
