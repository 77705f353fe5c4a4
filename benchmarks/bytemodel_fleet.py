"""Bytes the algorithm needs for the fleet's batched programs, per live
job and per dispatch, from shapes (the closed forms of `bytemodel.py`).

A batched program runs one tree's work per job on its own arena: the
same bytes a job whatever the batch holds, so padding jobs count for
nothing.

* eval (`fleet_eval_impl`): a full traversal of the job's tree from a
  fresh arena, then its root reduction: `traversal_bytes`;
* grad (`fleet_grad_impl`): the full traversal, then the whole-tree
  gradient pass on it: `traversal_bytes + gradient_bytes`;
* weights (`fleet_weights_impl`): the two rows either side of the root
  branch (CLV and scaler), read once a dispatch, and one weight row a
  replicate.
"""

from __future__ import annotations

from benchmarks import bytemodel


def eval_job_bytes(config: dict) -> int:
    return bytemodel.traversal_bytes(config)


def grad_job_bytes(config: dict) -> int:
    return bytemodel.traversal_bytes(config) + bytemodel.gradient_bytes(
        config)


def weights_job_bytes(config: dict) -> int:
    """One replicate's weight row, in the compute precision (f32)."""
    return config["patterns"] * bytemodel.ITEMSIZE[
        config["precision"].get("compute_dtype", "f32")]


def weights_call_bytes(config: dict) -> int:
    s = bytemodel.shapes(config)
    return 2 * s["patterns"] * (s["R"] * s["K"] * s["itemsize"] + 4)
