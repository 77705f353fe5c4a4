"""Batched many-tree evaluation: a leading TREE axis over the engines.

Three batched programs, all built from the engine's existing traced
bodies so the per-job arithmetic is IDENTICAL to one-at-a-time
evaluation (the parity contract tests/test_fleet.py pins bit-for-bit):

* FAST batch — jobs whose topologies bucket to the same fastpath
  segment profile (ops/fastpath.py: the profile IS the jit key, shared
  across topologies of similar shape) stack their per-job CLV arenas
  and packed schedule arrays and `jax.vmap` the engine's
  `_run_segments_impl` + root evaluation over the leading tree axis:
  one dispatch, J trees, zero new compiles for same-profile jobs.
* SCAN batch — the PSR / force_scan tier vmaps the engine's
  `_trav_eval_impl` over stacked wave-scheduled Traversal arrays
  (the [L, W] shape is the group key).
* WEIGHTS batch — bootstrap replicates on a FIXED topology exploit the
  fact that pattern weights enter only at the root reduction
  (`kernels.root_log_likelihood_from`): ONE ordinary CLV pass (shared
  programs, cached schedules — `engine.cache_hits` is the evidence),
  then a batched weight matrix [J, B, lane] in the lnL sum.

Job counts pad to a power of two (padding jobs replay job 0, results
discarded) so compiled variants stay O(log J) and the real/padded
ratio is the `fleet.batch_occupancy` evidence.

Every batched program here enters the engine's shared cache through
`cache_put`, which routes it through the exported program bank
(ops/export_bank.py) when EXAML_EXPORT_BANK is on: a respawned fleet
rank or autoscaled replica deserializes its fleet/fleetscan/fleetw/
fleetgrad executables instead of recompiling them, so rank-respawn
MTTR is the lease re-dispatch, not the compile phase (the jit keys
below are tuples of primitives — profile, bucketed shapes, pad counts
— which is what makes the artifact signatures stable across
processes).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from examl_tpu import obs
from examl_tpu.ops import fastpath, kernels
from examl_tpu.ops.kernels import Traversal
from examl_tpu.tree.topology import Tree
from examl_tpu.utils import bucket_len, next_pow2, z_slots


# Batch-group key for shared-topology weight replicates: the driver's
# grouping and the evaluator's compiled-pad bookkeeping must agree.
WEIGHTS_GROUP = ("weights",)


class PreparedJob:
    """One job's host-side evaluation state: the centroid-rooted flat
    traversal (rebuilt per cycle — branch lengths move), the cached
    immutable fast structure (topology-keyed, reused across cycles),
    and the batch group key."""

    __slots__ = ("tree", "p", "flat", "st", "key", "z", "gs")

    def __init__(self, tree, p, flat, st, key, z):
        self.tree = tree
        self.p = p
        self.flat = flat
        self.st = st          # FastStructure (fast mode) or None
        self.key = key        # hashable batch-group key
        self.z = z            # root-branch z [C]
        self.gs = None        # gradient GradStructure (lazily built,
                              # reused while the topology signature holds)


class PendingBatch:
    """A launched-but-uncollected batch: the per-engine device outputs
    of one `launch_eval` (jax async dispatch — the arrays are futures
    until `collect` materializes them).  XLA runtime errors surface at
    collect time; the driver maps them back through the same
    quarantine bisection a synchronous raise takes."""

    __slots__ = ("jobs", "J", "outs", "ev")

    def __init__(self, jobs, J, outs, ev):
        self.jobs = jobs
        self.J = J
        self.outs = outs      # [(engine, device-resident [jpad, L] lnl)]
        self.ev = ev          # the evaluator lane that launched it


def batch_eligible(inst) -> Optional[str]:
    """None when the instance can take the batched tier, else the
    human-readable reason it cannot (the driver degrades to sequential
    evaluation and says why).

    Sharded engines ARE eligible single-process (ISSUE 17): the job
    stacks commit over the fabric's tree axis (or replicate over a 1-D
    site mesh) and GSPMD composes them with the site-sharded engine
    constants in one dispatch.  Multi-process sharding stays out — a
    per-job stack cannot span process-local shards."""
    if getattr(inst, "save_memory", False):
        return "-S SEV pools hold one arena per instance"
    for eng in inst.engines.values():
        if eng.sharding is not None and jax.process_count() > 1:
            return "multi-process sharded arenas cannot stack per job"
    return None


class BatchEvaluator:
    """Batched evaluation over one PhyloInstance (all engines)."""

    def __init__(self, inst):
        reason = batch_eligible(inst)
        if reason is not None:
            raise ValueError(f"batched tier unavailable: {reason}")
        self.inst = inst
        self.engines = list(inst.engines.values())
        eng = self.engines[0]
        self.ntips = eng.ntips
        self.C = inst.num_branch_slots
        # Mode is instance-wide: PSR and force_scan apply to every
        # engine alike (instance.psr; EXAML_FAST_TRAVERSAL env).
        self.fast = (not eng.psr and not eng.force_scan
                     and eng.fast_slack > 0)
        self.wave_width = eng.wave_width
        self._jpads: dict = {}     # group key -> compiled pad sizes
        self._weights_pass = None  # (tree id, dispatch epoch) of the
                                   # last weights-batch CLV pass

    def _const(self, eng, name: str):
        """One engine constant (models / block_part / weights / tips /
        site_rates) as THIS evaluator's dispatches should see it.  The
        base evaluator reads the engine's live arrays (default device);
        a DeviceShard (fleet/shard.py) overrides this with its
        device-resident copies so the whole dispatch — committed
        constants pull the uncommitted batch stacks after them — runs
        on the shard's device."""
        return getattr(eng, name)

    def _pick_jpad(self, group_key, J: int) -> int:
        """Batch pad size: the smallest ALREADY-COMPILED power of two
        that fits, else the next power of two.  A tail batch (queue
        drained below the cap) replays the hot program with padding
        jobs instead of minting a fresh compile — occupancy < 1 is the
        trade the `fleet.batch_occupancy` gauge records."""
        compiled = self._jpads.setdefault(group_key, set())
        fits = [p for p in compiled if p >= J]
        if fits:
            return min(fits)
        jpad = next_pow2(J)
        # Minting a pad larger than everything already compiled is jpad
        # GROWTH — under memory pressure the governor denies it
        # (counted: the drain's shrunken cap should have kept J inside
        # the compiled pads) but the pad must still cover J, so the
        # mint proceeds: admission shrinks future occupancy via
        # `effective_cap`, it never breaks the batch in hand.
        if compiled and jpad > max(compiled):
            from examl_tpu.resilience import memgov
            if memgov.under_pressure():
                obs.inc("mem.admission_denials")
        compiled.add(jpad)
        return jpad

    # -- preparation / grouping --------------------------------------------

    def prepare(self, tree, prev: Optional[PreparedJob] = None) -> PreparedJob:
        """Host-side schedule state for one job (cheap on re-prepare:
        the immutable structure survives while the topology signature
        matches; only z refreshes)."""
        p = tree.centroid_branch()
        with obs.timer("host_schedule"):
            flat = tree.flat_full_traversal(p)
        z = np.asarray(z_slots(p.z, self.C), dtype=np.float64)
        if not self.fast:
            key = ("scan",) + self._scan_shape(flat)
            return PreparedJob(tree, p, flat, None, key, z)
        if prev is not None and prev.st is not None \
                and prev.flat.topo_key == flat.topo_key:
            st = prev.st
        else:
            with obs.timer("host_schedule"):
                # One structure serves every engine: the one-entry tail
                # only where every engine's row is that wide.
                st = fastpath.build_structure(
                    flat, self.ntips,
                    min(e.trav_row_bytes() for e in self.engines))
        pj = PreparedJob(tree, p, flat, st, ("fast", st.profile), z)
        if prev is not None and prev.gs is not None \
                and prev.flat.topo_key == flat.topo_key:
            pj.gs = prev.gs       # gradient plan survives z-only cycles
        return pj

    def _scan_shape(self, flat) -> tuple:
        """The scan tier's compiled [L, W] traversal shape — the batch
        group key for PSR/force_scan jobs (mirrors the wave chunking in
        engine._pack_traversal)."""
        sizes = np.asarray(flat.wave_sizes)
        W = min(next_pow2(int(sizes.max())), self.wave_width) if len(sizes) \
            else 1
        nwaves = int(np.sum((sizes + W - 1) // W))
        return (bucket_len(nwaves), W)

    # -- batched programs (engine shared-cache entries) ---------------------

    def _fast_fn(self, eng, profile, jpad: int):
        key = ("fleet", profile, jpad, self.C)
        fn = eng.cache_get(key)
        if fn is not None:
            return fn

        def body(clv, scaler, base, lidx, ridx, lcode, rcode, zl, zr,
                 p_idx, q_idx, zv, dm, block_part, weights, tips):
            clv, scaler = eng._run_segments_impl(
                dm, block_part, tips, clv, scaler, profile, base, lidx,
                ridx, lcode, rcode, zl, zr)
            return kernels.root_log_likelihood(
                dm, block_part, weights, tips, clv, scaler, p_idx, q_idx,
                zv, eng.num_parts, eng.scale_exp, eng.ntips, None)

        # No donation: the body returns only the lnL rows, so the
        # stacked arenas have no donatable destination (jax would warn
        # "donated buffers were not usable" on every dispatch).
        vb = jax.vmap(body, in_axes=(0,) * 12 + (None,) * 4)
        return eng.cache_put(key, jax.jit(vb))

    def _scan_fn(self, eng, shape, jpad: int):
        key = ("fleetscan", shape, jpad, self.C)
        fn = eng.cache_get(key)
        if fn is not None:
            return fn

        def body(buf, scaler, tv, p_idx, q_idx, zv, dm, block_part,
                 weights, tips, sr):
            return eng._trav_eval_impl(buf, scaler, (), tv, p_idx, q_idx,
                                       zv, dm, block_part, weights, tips,
                                       sr)

        vb = jax.vmap(body,
                      in_axes=(0, 0, Traversal(0, 0, 0, 0, 0), 0, 0, 0,
                               None, None, None, None, None))
        return eng.cache_put(key, jax.jit(vb, donate_argnums=(0, 1)))

    def _weights_fn(self, eng, jpad: int):
        key = ("fleetw", jpad)
        fn = eng.cache_get(key)
        if fn is not None:
            return fn

        def body(w, clv, scaler, p_idx, q_idx, zv, dm, block_part, tips,
                 sr):
            return kernels.root_log_likelihood(
                dm, block_part, w, tips, clv, scaler, p_idx, q_idx, zv,
                eng.num_parts, eng.scale_exp, eng.ntips, sr)

        # The engine's LIVE arena rides along un-donated (it is read by
        # every job and must survive the dispatch).
        vb = jax.vmap(body, in_axes=(0,) + (None,) * 9)
        return eng.cache_put(key, jax.jit(vb))

    # -- dispatch ------------------------------------------------------------

    @staticmethod
    def _pad_stack(arrs: Sequence, jpad: int):
        """Stack per-job leaves, padding to jpad by replaying job 0."""
        arrs = list(arrs) + [arrs[0]] * (jpad - len(arrs))
        return jnp.stack([jnp.asarray(a) for a in arrs])

    def _gidx_st(self, st, num: int) -> int:
        if num <= self.ntips:
            return num - 1
        return self.ntips + int(st.row_of[num])

    def _gidx_identity(self, num: int) -> int:
        """gather index against the INITIAL arena layout (row = node
        number - ntips - 1): the batch arenas are fresh per dispatch, so
        the identity map is always valid — and it matches a scan-tier
        engine's own never-permuted row_map, keeping the batched scan
        program's arithmetic identical to one-at-a-time."""
        if num <= self.ntips:
            return num - 1
        return self.ntips + (num - self.ntips - 1)

    def eval_batch(self, jobs: List[PreparedJob],
                   record_occupancy: bool = True) -> np.ndarray:
        """Per-job per-partition lnL [J, M] for one same-key batch, in
        ONE device dispatch per engine.

        Rows are per-job INDEPENDENT (vmap over the tree axis), so a
        poison job surfaces as exactly its own non-finite row — the
        attribution the driver's job-level quarantine ladder keys on —
        and a bisection sub-batch reuses the smallest already-compiled
        pow2 program (`_pick_jpad`) instead of minting compiles.
        Bisection probes pass `record_occupancy=False`: the operator
        gauge must reflect the scheduled batches' real/padded ratio,
        not isolation sub-dispatches."""
        return self.collect(self.launch_eval(jobs, record_occupancy))

    def launch_eval(self, jobs: List[PreparedJob],
                    record_occupancy: bool = True) -> "PendingBatch":
        """ENQUEUE one same-key batch (one dispatch per engine) without
        blocking on the result: jax dispatch is asynchronous, so a
        multi-device driver (fleet/shard.py) launches one batch per
        device and only then collects — the devices execute
        concurrently instead of serializing behind each batch's host
        sync."""
        assert jobs, "empty batch"
        assert len({j.key for j in jobs}) == 1, \
            "batch mixes job groups (driver bug)"
        J = len(jobs)
        jpad = self._pick_jpad(jobs[0].key, J)
        if record_occupancy:
            obs.gauge("fleet.batch_occupancy", J / jpad)
        outs = []
        for eng in self.engines:
            out = (self._launch_fast(eng, jobs, jpad) if self.fast
                   else self._launch_scan(eng, jobs, jpad))
            outs.append((eng, out))
        return PendingBatch(jobs, J, outs, self)

    def collect(self, pending: "PendingBatch") -> np.ndarray:
        """Materialize a launched batch's per-job per-partition lnL
        [J, M] — THE blocking seam of the batched tier (registered
        host-sync: the rows feed the results table and the fsync'd
        journal, so the sync is the product)."""
        J = pending.J
        M = len(self.inst.models)
        per_part = np.full((J, M), np.nan)
        for eng, out in pending.outs:
            vals = np.asarray(out)
            for li, gid in enumerate(eng.bucket.part_ids):
                per_part[:, gid] = vals[:J, li]
        return per_part

    def _batch_arenas(self, eng, jpad: int):
        from examl_tpu.resilience import memgov
        rows = eng.n_inner + eng.fast_slack + 1
        est = (jpad * rows * eng.B * eng.lane * eng.R * eng.K
               * np.dtype(eng.storage_dtype).itemsize)
        # Arena provisioning is an admission seam: a denial is counted
        # evidence (the drain should already have cut the batch), never
        # a block — the dispatch in hand proceeds.
        memgov.admit_bytes(est, seam="fleet.batch_arenas")
        clv = jnp.zeros((jpad, rows, eng.B, eng.lane, eng.R, eng.K),
                        eng.storage_dtype)
        scaler = jnp.zeros((jpad, rows, eng.B, eng.lane), jnp.int32)
        return clv, scaler

    def _launch_fast(self, eng, jobs: List[PreparedJob], jpad: int):
        profile = jobs[0].st.profile
        with obs.timer("host_schedule"):
            zs = [fastpath.refresh_z(j.st, j.flat, self.C, eng.dtype)
                  for j in jobs]
        fn = self._fast_fn(eng, profile, jpad)
        clv, scaler = self._batch_arenas(eng, jpad)
        pq = [(self._gidx_st(j.st, j.p.number),
               self._gidx_st(j.st, j.p.back.number)) for j in jobs]
        obs.inc("engine.dispatch_count")
        with obs.span("fleet:batch_eval",
                      args={"jobs": len(jobs), "jpad": jpad}):
            out = fn(clv, scaler,
                     self._pad_stack([j.st.base for j in jobs], jpad),
                     self._pad_stack([j.st.lidx for j in jobs], jpad),
                     self._pad_stack([j.st.ridx for j in jobs], jpad),
                     self._pad_stack([j.st.lcode for j in jobs], jpad),
                     self._pad_stack([j.st.rcode for j in jobs], jpad),
                     self._pad_stack([z[0] for z in zs], jpad),
                     self._pad_stack([z[1] for z in zs], jpad),
                     self._pad_stack([jnp.int32(p) for p, _ in pq], jpad),
                     self._pad_stack([jnp.int32(q) for _, q in pq], jpad),
                     self._pad_stack(
                         [jnp.asarray(j.z, eng.dtype) for j in jobs], jpad),
                     self._const(eng, "models"),
                     self._const(eng, "block_part"),
                     self._const(eng, "weights"),
                     self._const(eng, "tips"))
        return out

    def _launch_scan(self, eng, jobs: List[PreparedJob], jpad: int):
        tvs = []
        with obs.timer("host_schedule"):
            for j in jobs:
                entries = j.flat.to_entries()
                tvs.append(eng._pack_traversal(
                    entries,
                    lambda e: e.parent - self.ntips - 1,
                    self._gidx_identity))
        shapes = {tuple(tv.parent.shape) for tv in tvs}
        assert len(shapes) == 1, f"scan batch mixes shapes {shapes}"
        fn = self._scan_fn(eng, shapes.pop(), jpad)
        clv, scaler = self._batch_arenas(eng, jpad)
        tv = Traversal(*(self._pad_stack([getattr(t, f) for t in tvs], jpad)
                         for f in Traversal._fields))
        pq = [(self._gidx_identity(j.p.number),
               self._gidx_identity(j.p.back.number)) for j in jobs]
        obs.inc("engine.dispatch_count")
        with obs.span("fleet:batch_eval_scan",
                      args={"jobs": len(jobs), "jpad": jpad}):
            _, _, out = fn(clv, scaler, tv,
                           self._pad_stack([jnp.int32(p) for p, _ in pq],
                                           jpad),
                           self._pad_stack([jnp.int32(q) for _, q in pq],
                                           jpad),
                           self._pad_stack(
                               [jnp.asarray(j.z, eng.dtype) for j in jobs],
                               jpad),
                           self._const(eng, "models"),
                           self._const(eng, "block_part"),
                           self._const(eng, "weights"),
                           self._const(eng, "tips"),
                           self._const(eng, "site_rates"))
        return out

    # -- batched universal interpreter (mixed-profile novel jobs) ------------

    def _uni_fn(self, eng, akey, npad: int, ppad: int, jpad: int):
        """One compiled vmapped interpreter program per (alphabet,
        table bucket, slot bucket, job pad): the per-job descriptor
        TABLES are runtime data, so topologies with completely
        different profiles batch through the same executable — the
        class select is `lax.select_n` (ops/universal.py select=True),
        computing all three tip-case branches and gathering one, which
        keeps the arena writes outside any conditional under vmap."""
        key = ("unibatch", akey, npad, ppad, jpad, self.C)
        fn = eng.cache_get(key)
        if fn is not None:
            return fn
        from examl_tpu.ops import universal
        alpha = universal.alphabet(akey)

        def body(clv, scaler, cls, slot, cbase, lidx, ridx, lcode,
                 rcode, zl, zr, p_idx, q_idx, zv, dm, block_part,
                 weights, tips):
            apply = fastpath.chunk_applier(dm, block_part, tips,
                                           eng.scale_exp,
                                           eng.fast_precision)
            clv, scaler = universal.run_universal(
                alpha, cls, slot, cbase, lidx, ridx, lcode, rcode, zl,
                zr, clv, scaler, apply.values, select=True)
            return kernels.root_log_likelihood(
                dm, block_part, weights, tips, clv, scaler, p_idx,
                q_idx, zv, eng.num_parts, eng.scale_exp, eng.ntips,
                None)

        vb = jax.vmap(body, in_axes=(0,) * 14 + (None,) * 4)
        return eng.cache_put(key, jax.jit(vb))

    def launch_universal(self, jobs: List[PreparedJob], key,
                         record_occupancy: bool = True) -> "PendingBatch":
        """ENQUEUE one mixed-profile batch through the vmapped
        universal interpreter: jobs grouped only by their BUCKETED
        table/slot sizes (driver key ("uni", akey, npad, ppad)) share
        one dispatch — novel-topology serving traffic batches instead
        of dispatching solo.  Descriptor tables and padded index
        copies reuse the engine's per-topology universal cache, so a
        recurring topology ships only its two fresh z arrays."""
        from examl_tpu.ops import universal
        assert jobs
        _, akey, npad, ppad = key
        J = len(jobs)
        jpad = self._pick_jpad(key, J)
        if record_occupancy:
            obs.gauge("fleet.batch_occupancy", J / jpad)
        obs.inc("fleet.uni_batches")
        outs = []
        for eng in self.engines:
            descs, idxs, zls, zrs = [], [], [], []
            with obs.timer("host_schedule"):
                for j in jobs:
                    ent = eng._universal_entry(
                        j.st.profile, np.asarray(j.st.base),
                        (j.st.lidx, j.st.ridx, j.st.lcode, j.st.rcode),
                        cache_key=j.flat.topo_key)
                    desc = ent["desc"].get(npad)
                    if desc is None:
                        desc = ent["desc"][npad] = jax.device_put(
                            list(universal.pad_table(ent["table"],
                                                     npad)))
                    idx = ent["pads"].get(ppad)
                    if idx is None:
                        idx = ent["pads"][ppad] = jax.device_put(
                            [universal.pad_slots(np.asarray(a), ppad)
                             for a in ent["idx"]])
                    descs.append(desc)
                    idxs.append(idx)
                    zl, zr = fastpath.refresh_z(j.st, j.flat, self.C,
                                                eng.dtype,
                                                total_slots=ppad)
                    zls.append(zl)
                    zrs.append(zr)
            fn = self._uni_fn(eng, akey, npad, ppad, jpad)
            clv, scaler = self._batch_arenas(eng, jpad)
            pq = [(self._gidx_st(j.st, j.p.number),
                   self._gidx_st(j.st, j.p.back.number)) for j in jobs]
            obs.inc("engine.dispatch_count")
            with obs.span("fleet:batch_universal",
                          args={"jobs": J, "jpad": jpad, "steps": npad}):
                out = fn(clv, scaler,
                         self._pad_stack([d[0] for d in descs], jpad),
                         self._pad_stack([d[1] for d in descs], jpad),
                         self._pad_stack([d[2] for d in descs], jpad),
                         self._pad_stack([i[0] for i in idxs], jpad),
                         self._pad_stack([i[1] for i in idxs], jpad),
                         self._pad_stack([i[2] for i in idxs], jpad),
                         self._pad_stack([i[3] for i in idxs], jpad),
                         self._pad_stack(zls, jpad),
                         self._pad_stack(zrs, jpad),
                         self._pad_stack(
                             [jnp.int32(p) for p, _ in pq], jpad),
                         self._pad_stack(
                             [jnp.int32(q) for _, q in pq], jpad),
                         self._pad_stack(
                             [jnp.asarray(j.z, eng.dtype)
                              for j in jobs], jpad),
                         self._const(eng, "models"),
                         self._const(eng, "block_part"),
                         self._const(eng, "weights"),
                         self._const(eng, "tips"))
            outs.append((eng, out))
        return PendingBatch(jobs, J, outs, self)

    def unibatch_key(self, prep: PreparedJob):
        """The mixed-profile batch-group key for a novel-profile job:
        ("uni", alphabet, table_bucket, slot_bucket) — a pure function
        of the job's BUCKETED universal-table shape, so topologies
        with entirely different profiles group together.  None for a
        job without a fast-path structure or with a layout the
        interpreter cannot run — the driver falls back to solo
        routing."""
        from examl_tpu.ops import universal
        if prep.st is None:
            return None
        eng = self.engines[0]
        try:
            ent = eng._universal_entry(
                prep.st.profile, np.asarray(prep.st.base),
                (prep.st.lidx, prep.st.ridx, prep.st.lcode, prep.st.rcode),
                cache_key=prep.flat.topo_key)
        except universal.UniversalIneligible:
            return None
        table = ent["table"]
        return ("uni", universal.alphabet_key(),
                bucket_len(table.n_chunks), bucket_len(table.slots))

    # -- batched whole-tree gradient smoothing (--fleet-cycles) --------------
    # The sequential path paid the per-branch Newton loop PER JOB per
    # cycle; here one vmapped dispatch per engine per sweep runs every
    # job's post-order traversal, pre-order (outroot) pass and
    # all-edges derivative contraction at once (ops/gradient.py), and
    # the host applies the same Rprop-damped batched Newton update the
    # single-tree gradient smoother uses (optimize/branch.py).

    def _grad_fn(self, eng, profile, steps: int, width: int, chunks: int,
                 jpad: int):
        key = ("fleetgrad", profile, steps, width, chunks, jpad, self.C)
        fn = eng.cache_get(key)
        if fn is not None:
            return fn

        def body(clv, scaler, base, lidx, ridx, lcode, rcode, zl, zr,
                 p_row, q_row, p_g, q_g, tvp, ex_rows, ey_gidx, ez,
                 dm, block_part, weights, tips):
            clv, scaler = eng._run_segments_impl(
                dm, block_part, tips, clv, scaler, profile, base, lidx,
                ridx, lcode, rcode, zl, zr)
            return eng._grad_impl(clv, scaler, p_row, q_row, p_g, q_g,
                                  tvp, ex_rows, ey_gidx, ez, dm,
                                  block_part, weights, tips, None)

        vb = jax.vmap(body, in_axes=(0,) * 17 + (None,) * 4)
        return eng.cache_put(key, jax.jit(vb))

    def _grad_batch(self, jobs: List[PreparedJob], jpad: int):
        """One vmapped gradient dispatch per engine: (d1, d2) [J, E, C]
        summed across engines."""
        from examl_tpu.ops import gradient
        gss = []
        for j in jobs:
            if j.gs is None:
                with obs.timer("host_schedule"):
                    j.gs = gradient.build_structure(
                        j.flat, min(e.grad_wave_cap()
                                    for e in self.engines))
            gss.append(j.gs)
        shapes = {(g.n_steps, g.wave_w, g.n_chunks) for g in gss}
        assert len(shapes) == 1, f"grad batch mixes shapes {shapes}"
        steps, width, chunks = shapes.pop()
        E = gss[0].n_edges
        J = len(jobs)
        # Re-read branch vectors THROUGH the tree per sweep: smoothing
        # mutates z between dispatches, and flat/prep z arrays are
        # captured copies (the structural halves — st, gs — stay valid
        # while the topology signature holds).
        with obs.timer("host_schedule"):
            for j in jobs:
                j.flat = j.tree.flat_full_traversal(j.p)
        d1 = d2 = None
        for eng in self.engines:
            with obs.timer("host_schedule"):
                zs = [fastpath.refresh_z(j.st, j.flat, self.C, eng.dtype)
                      for j in jobs]
                dyn = [gradient.grad_arrays(
                           g, j.flat, np.asarray(j.st.row_of), self.C,
                           z_slots(j.p.z, self.C))
                       for g, j in zip(gss, jobs)]
            fn = self._grad_fn(eng, jobs[0].st.profile, steps, width,
                               chunks, jpad)
            clv, scaler = self._batch_arenas(eng, jpad)
            pq = [(self._gidx_st(j.st, j.p.number),
                   self._gidx_st(j.st, j.p.back.number)) for j in jobs]

            def stk(xs, dtype=None):
                return self._pad_stack(
                    [jnp.asarray(x, dtype) if dtype else jnp.asarray(x)
                     for x in xs], jpad)

            tvp = kernels.OutrootTraversal(
                up_row=stk([d[0][0] for d in dyn]),
                lrow=stk([d[0][1] for d in dyn]),
                rrow=stk([d[0][2] for d in dyn]),
                left=stk([d[0][3] for d in dyn]),
                right=stk([d[0][4] for d in dyn]),
                zu=stk([d[0][5] for d in dyn], eng.dtype),
                zl=stk([d[0][6] for d in dyn], eng.dtype),
                zr=stk([d[0][7] for d in dyn], eng.dtype))
            obs.inc("engine.dispatch_count")
            obs.inc("engine.grad_pass_dispatches")
            with obs.span("fleet:grad_smooth",
                          args={"jobs": J, "jpad": jpad}):
                e1, e2 = fn(
                    clv, scaler,
                    self._pad_stack([j.st.base for j in jobs], jpad),
                    self._pad_stack([j.st.lidx for j in jobs], jpad),
                    self._pad_stack([j.st.ridx for j in jobs], jpad),
                    self._pad_stack([j.st.lcode for j in jobs], jpad),
                    self._pad_stack([j.st.rcode for j in jobs], jpad),
                    self._pad_stack([z[0] for z in zs], jpad),
                    self._pad_stack([z[1] for z in zs], jpad),
                    stk([jnp.int32(g.roots[0] - 1) for g in gss]),
                    stk([jnp.int32(g.roots[1] - 1) for g in gss]),
                    stk([jnp.int32(self._gidx_st(j.st, g.roots[0]))
                         for j, g in zip(jobs, gss)]),
                    stk([jnp.int32(self._gidx_st(j.st, g.roots[1]))
                         for j, g in zip(jobs, gss)]),
                    tvp, stk([d[1] for d in dyn]),
                    stk([d[2] for d in dyn]),
                    stk([d[3] for d in dyn], eng.dtype),
                    eng.models, eng.block_part, eng.weights, eng.tips)
            e1 = np.asarray(e1, dtype=np.float64)[:J, :E]
            e2 = np.asarray(e2, dtype=np.float64)[:J, :E]
            d1 = e1 if d1 is None else d1 + e1
            d2 = e2 if d2 is None else d2 + e2
        return d1, d2

    def smooth_batch(self, jobs: List[PreparedJob], maxtimes: int) -> bool:
        """Whole-tree gradient smoothing for one same-profile batch:
        per sweep ONE vmapped dispatch per engine covers every job's
        gradient pass, then the batched Rprop-damped Newton update
        applies to all jobs' branches simultaneously — replacing the
        per-job sequential Newton loop `--fleet-cycles` used to pay.
        Returns False when some job's branches still moved at the
        sweep budget — the caller ACCEPTS that like the per-branch
        path accepts its own maxtimes exhaustion (counted as
        fleet.grad_smooth_unconverged); only a raise falls back to
        the per-job path."""
        import os as _os

        from examl_tpu.constants import DELTAZ, ZMAX, ZMIN
        from examl_tpu.optimize.branch import _edge_slots
        from examl_tpu.ops import gradient
        assert jobs
        assert len({j.key for j in jobs}) == 1, \
            "smooth batch mixes job groups (driver bug)"
        try:
            damping = float(_os.environ.get("EXAML_GRAD_DAMPING", "")
                            or 1.0)
        except ValueError:
            damping = 1.0
        jpad = self._pick_jpad(("fleetgrad",) + tuple(
            sorted({j.key for j in jobs})), len(jobs))
        J = len(jobs)
        slot_lists = [_edge_slots(j.tree, j.flat, j.p) for j in jobs]
        scale = prev_step = None
        done = np.zeros(J, dtype=bool)
        for _ in range(max(1, 4 * maxtimes)):
            d1, d2 = self._grad_batch(jobs, jpad)      # [J, E, C]
            z0 = np.clip(np.stack(
                [[z_slots(s.z, self.C) for s in sl] for sl in slot_lists]),
                ZMIN, ZMAX)
            znew = gradient.newton_step(z0, d1, d2)
            step = np.log(znew) - np.log(z0)
            if scale is None:
                scale = np.full_like(step, damping)
            else:
                flip = prev_step * step < 0.0
                scale = np.maximum(
                    np.where(flip, scale * 0.5,
                             np.minimum(scale * 1.2, damping)),
                    1.0 / 64)
            prev_step = step
            zapp = np.clip(z0 * np.exp(step * scale), ZMIN, ZMAX)
            zapp = np.where(done[:, None, None], z0, zapp)
            moved = np.abs(zapp - z0) > DELTAZ
            for ji, sl in enumerate(slot_lists):
                if done[ji]:
                    continue
                for i, s in enumerate(sl):
                    s.z[:] = zapp[ji, i].tolist()
            done |= ~moved.any(axis=(1, 2))
            obs.inc("fleet.grad_smooth_sweeps")
            if done.all():
                return True
        obs.inc("fleet.grad_smooth_unconverged")
        return False

    # -- weights-only batch (shared topology) --------------------------------

    def eval_weights_batch(self, tree,
                           per_job_weights: List[List[np.ndarray]],
                           record_occupancy: bool = True) -> np.ndarray:
        """Per-job per-partition lnL [J, M] of J weight replicates on
        ONE topology: a single ordinary CLV pass (shared programs — the
        schedule and jit caches hit), then one batched root reduction
        per engine."""
        from examl_tpu.fleet import bootstrap as _bs
        J = len(per_job_weights)
        assert J
        jpad = self._pick_jpad(WEIGHTS_GROUP, J)
        p = tree.centroid_branch()
        # The one CLV pass: the NORMAL evaluation path (fast tier where
        # eligible), so repeated replicate batches on the same topology
        # are pure cache hits — engine.cache_hits / sched_cache.hit are
        # the program-sharing acceptance evidence.  Consecutive weight
        # batches on the same tree skip even the traversal: the live
        # arenas are still this tree's CLVs as long as NO device
        # program ran in between (every arena-mutating path — newview,
        # newton, model grids, other fleet batches — bumps
        # engine.dispatch_count, so the epoch is conservative).
        if self._weights_pass != (id(tree),
                                  obs.counter("engine.dispatch_count")):
            self.inst.evaluate(tree, p, full=True)
        else:
            obs.inc("fleet.clv_pass_reuses")
        M = len(self.inst.models)
        per_part = np.full((J, M), np.nan)
        if record_occupancy:
            obs.gauge("fleet.batch_occupancy", J / jpad)
        for eng in self.engines:
            w = [_bs.packed_weights(eng.bucket, pj) for pj in per_job_weights]
            fn = self._weights_fn(eng, jpad)
            buf, _aux = eng._state()
            zv = jnp.asarray(z_slots(p.z, self.C), dtype=eng.dtype)
            obs.inc("engine.dispatch_count")
            with obs.span("fleet:weights_eval",
                          args={"jobs": J, "jpad": jpad}):
                out = fn(self._pad_stack(
                             [jnp.asarray(x, eng.dtype) for x in w], jpad),
                         buf, eng.scaler,
                         jnp.int32(eng._gidx(p.number)),
                         jnp.int32(eng._gidx(p.back.number)),
                         zv, eng.models, eng.block_part, eng.tips,
                         eng.site_rates)
            vals = np.asarray(out)
            for li, gid in enumerate(eng.bucket.part_ids):
                per_part[:, gid] = vals[:J, li]
        self._weights_pass = (id(tree),
                              obs.counter("engine.dispatch_count"))
        return per_part
