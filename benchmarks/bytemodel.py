"""Bytes the algorithm needs for one call, from shapes.

The benchmark's own copy of the closed forms in
`examl_tpu/obs/traffic.py` (the program's file may change; the
yardstick may not): the same work whatever implements it.  A full
traversal of an unrooted binary tree on n taxa recomputes n - 2 inner
conditional-likelihood rows; rooted on an inner branch, n of their
2(n - 2) children are tips.
"""

from __future__ import annotations

ITEMSIZE = {"f32": 4, "f64": 8, "bf16": 2}


def bytes_per_traversal_counts(n_entries: int, n_tip_children: int,
                               patterns: int, R: int, K: int,
                               itemsize: int) -> int:
    """`n_entries` CLV rows written, `2*n_entries - n_tip_children`
    inner-child CLV rows read (each with its int32 scaler row),
    `n_tip_children` 1-byte tip code rows read."""
    clv_row = patterns * R * K * itemsize
    sc_row = patterns * 4
    inner_children = 2 * n_entries - n_tip_children
    return ((n_entries + inner_children) * (clv_row + sc_row)
            + n_tip_children * patterns)


def bytes_per_grad_pass(n_entries: int, n_tip_children: int, n_edges: int,
                        patterns: int, R: int, K: int,
                        itemsize: int) -> int:
    """One whole-tree gradient dispatch: the pre-order pass reads one
    outroot row and two child partials an entry and writes two outroot
    rows; the edge contraction reads one outroot row and one down
    partial (with its scaler row) an edge."""
    clv_row = patterns * R * K * itemsize
    sc_row = patterns * 4
    inner_children = 2 * n_entries - n_tip_children
    pre = (3 * n_entries * clv_row
           + inner_children * (clv_row + sc_row)
           + n_tip_children * patterns)
    return pre + n_edges * (2 * clv_row + sc_row)


def shapes(config: dict) -> dict:
    n = config["taxa"]
    return {"n_entries": n - 2, "n_tip_children": n, "n_edges": 2 * n - 3,
            "patterns": config["patterns"], "R": config["rate_categories"],
            "K": config["states"],
            "itemsize": ITEMSIZE[config["precision"]["clv_dtype"]]}


def traversal_bytes(config: dict) -> int:
    s = shapes(config)
    return bytes_per_traversal_counts(
        s["n_entries"], s["n_tip_children"], s["patterns"], s["R"],
        s["K"], s["itemsize"])


def gradient_bytes(config: dict) -> int:
    s = shapes(config)
    return bytes_per_grad_pass(
        s["n_entries"], s["n_tip_children"], s["n_edges"], s["patterns"],
        s["R"], s["K"], s["itemsize"])


def floor_seconds(nbytes: int, peak: dict) -> float:
    """Least time the chip could take for one call: its bytes over the
    HBM bandwidth.  HBM bounds every configuration there is (the MXU
    floor at three bf16 passes is 5-20x lower); a configuration that is
    compute-bound brings the operations floor with it."""
    return nbytes / peak["hbm_bytes_per_s"]
