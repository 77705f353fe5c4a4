"""PhyloInstance: alignment + models + device engines behind one facade.

The host-side counterpart of the reference's `tree` master struct plus its
generic entry points (`evaluateGeneric`, `newviewGeneric`,
`makenewzGeneric` — ExaML `axml.h:1223-1256`): owns per-partition model
parameters, the packed site buckets (one device program per state count),
and the CLV orientation bookkeeping against a host `Tree`.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from examl_tpu.io.alignment import AlignmentData
from examl_tpu.models import protein as protein_mod
from examl_tpu.models.gtr import ModelParams, build_model
from examl_tpu.ops.engine import LikelihoodEngine
from examl_tpu.parallel.packing import pack_partitions
from examl_tpu.tree.topology import Node, Tree, TraversalEntry


def packed_site_rates(bucket, per_site_rates, rate_category) -> np.ndarray:
    """GLOBAL packed per-site rate multipliers [B, lane] for a bucket
    (padding sites keep rate 1): `perSiteRates[rateCategory]` scattered
    through the bucket's global layout.  Pure layout arithmetic — the
    same on every process of a selective-loading job because the rate
    state is host-global (each engine then materializes only its block
    window, engine._local_block_window)."""
    packed = np.ones(bucket.num_sites)
    for li, gid in enumerate(bucket.part_ids):
        packed[bucket.site_indices(li)] = \
            per_site_rates[gid][rate_category[gid]]
    return packed.reshape(bucket.num_blocks, bucket.lane)


class PhyloInstance:
    def __init__(self, alignment: AlignmentData, dtype=None,
                 ncat: int = 4, use_median: bool = False,
                 per_partition_branches: bool = False,
                 block_multiple: int = 1, sharding=None,
                 rate_model: str = "GAMMA", psr_categories: int = 25,
                 save_memory: bool = False,
                 local_window: Optional[tuple] = None):
        from examl_tpu.config import default_dtype
        if rate_model not in ("GAMMA", "PSR"):
            raise ValueError(f"unknown rate model {rate_model!r}")
        self.rate_model = rate_model
        self.psr = rate_model == "PSR"
        if self.psr:
            ncat = 1                      # one rate per site, weight 1
        self.psr_categories = psr_categories
        self.save_memory = save_memory       # SEV mode (ops/sev.py)
        self.alignment = alignment
        self.dtype = jnp.dtype(dtype if dtype is not None else default_dtype())
        self.ncat = ncat
        self.use_median = use_median
        M = len(alignment.partitions)
        self.num_parts = M
        self.per_partition_branches = per_partition_branches
        self.num_branch_slots = M if per_partition_branches else 1

        # Initial models (reference initModel `models.c:4180`): GTR rates all
        # 1.0, empirical frequencies (or the protein matrix's own), alpha 1.0.
        self.models: List[ModelParams] = []
        # AUTO partitions start from WAG (reference `models.c:4222`) until
        # autoProtein selection replaces them during modOpt.
        self.auto_prot_models: Dict[int, str] = {
            gid: "WAG" for gid, p in enumerate(alignment.partitions) if p.auto}
        self.auto_prot_freqs: Dict[int, str] = {
            gid: "fixed" for gid in self.auto_prot_models}
        for gid, part in enumerate(alignment.partitions):
            name = self.auto_prot_models.get(gid, part.model_name)
            if part.lg4:
                from examl_tpu.models.lg4 import build_lg4
                if self.psr:
                    raise ValueError(
                        "LG4 models are not supported under PSR "
                        "(the reference likewise restricts LG4 to GAMMA)")
                if ncat != 4:
                    raise ValueError("LG4 models require 4 rate categories")
                if part.optimize_freqs or part.use_empirical_freqs:
                    raise ValueError(
                        f"partition {part.name}: LG4 models carry one "
                        "frequency vector per rate category; the F/X "
                        "frequency suffixes are not applicable")
                self.models.append(build_lg4(name, alpha=1.0,
                                             use_median=use_median))
                continue
            rates, freqs = None, part.empirical_freqs
            if part.datatype.name == "AA" and name != "GTR":
                rates, model_freqs = protein_mod.get_matrix(name)
                if not part.use_empirical_freqs and not part.optimize_freqs:
                    freqs = model_freqs
            self.models.append(build_model(
                part.datatype, freqs, rates=rates, alpha=1.0, ncat=ncat,
                use_median=use_median))

        if local_window is not None:
            # Multi-host selective loading: `alignment` holds only this
            # process's site columns (io/bytefile.read_bytefile_for_process)
            # and the buckets are the matching local window of the global
            # packed axis (reference per-rank loading, byteFile.c:278-382).
            from examl_tpu.parallel.packing import pack_partitions_local
            procid, nprocs = local_window
            self.buckets = pack_partitions_local(
                alignment.partitions, procid, nprocs,
                block_multiple=block_multiple)
        else:
            self.buckets = pack_partitions(alignment.partitions,
                                           block_multiple=block_multiple)
        self.engines: Dict[int, LikelihoodEngine] = {}
        for states, bucket in self.buckets.items():
            branch_indices = ([bucket.part_ids[i] for i in range(bucket.num_parts)]
                              if per_partition_branches
                              else [0] * bucket.num_parts)
            self.engines[states] = LikelihoodEngine(
                bucket, [self.models[g] for g in bucket.part_ids],
                alignment.ntaxa, num_branch_slots=self.num_branch_slots,
                branch_indices=branch_indices, dtype=self.dtype,
                sharding=sharding, psr=self.psr, save_memory=save_memory)

        # PSR per-site rate state (reference patrat / rateCategory /
        # perSiteRates, `axml.h:585-600`): host copies per partition,
        # sized GLOBAL even under selective loading — the rate scan
        # allgathers per-site lnls to every process and the
        # categorization then runs identically everywhere (the
        # reference's Gatherv/Scatterv CAT pipeline,
        # `optimizeModel.c:2135-2254`, as one collective).
        if self.psr:
            widths = [p.global_width if p.global_width is not None
                      else p.width for p in alignment.partitions]
            self.patrat = [np.ones(w) for w in widths]
            self.site_lhs = [np.zeros(w) for w in widths]
            self.rate_category = [np.zeros(w, dtype=np.int32)
                                  for w in widths]
            self.per_site_rates = [np.ones(1) for _ in alignment.partitions]
            self.psr_invocations = 0
            self.cat_opt_rounds = 0
            self._psr_global_weights: Optional[Dict[int, np.ndarray]] = None
            self._psr_packed_weights: Dict[int, np.ndarray] = {}

        self.per_partition_lnl = np.full(M, np.nan)
        self.likelihood = np.nan
        # Smoothing state (reference partitionSmoothed/partitionConverged).
        self.partition_smoothed = np.zeros(self.num_branch_slots, dtype=bool)
        self.partition_converged = np.zeros(self.num_branch_slots, dtype=bool)

    # -- model push --------------------------------------------------------

    def push_models(self, only_states=None) -> None:
        for states, bucket in self.buckets.items():
            if only_states is not None and states not in only_states:
                continue
            self.engines[states].set_models(
                [self.models[g] for g in bucket.part_ids])

    def set_model(self, gid: int, model: ModelParams, push: bool = True) -> None:
        self.models[gid] = model
        if push:
            self.push_models()

    def push_site_rates(self) -> None:
        """Install the CATEGORIZED per-site rates into the engines' packed
        [B, lane] site-rate buffers (padding sites keep rate 1).

        Evaluation always runs under the <=25 category representatives
        (`perSiteRates[rateCategory]`); `patrat` holds each site's
        un-snapped scan optimum and only seeds the next scan (reference
        distinction between patrat and perSiteRates, `axml.h:585-600`)."""
        assert self.psr
        for states, bucket in self.buckets.items():
            self.engines[states].set_site_rates(packed_site_rates(
                bucket, self.per_site_rates, self.rate_category))

    # -- PSR global per-site state under selective loading ------------------
    # The scan/categorize pipeline is host-GLOBAL on every process (the
    # per-site lnls allgather in engine.rate_scan; the categorization is
    # deterministic), but under selective loading each process's bucket
    # holds only its window of the packed weights.  One host allgather
    # of the weight windows (contiguous, procid-ordered — they tile the
    # axis) recovers the global view every process needs for the
    # weighted crawl and the weighted-mean-rate-1 normalization — the
    # per-site-rate-state allgather replacing the reference's
    # Gatherv/Scatterv legs (`optimizeModel.c:2135-2254`).

    def psr_packed_weights(self, bucket) -> np.ndarray:
        """GLOBAL packed pattern weights [B, lane] for a bucket.
        Weights are static, so the cross-process gather runs ONCE per
        bucket and is cached — every PSR scan/normalize round reuses
        it rather than re-collecting on the search path."""
        cached = self._psr_packed_weights.get(bucket.states)
        if cached is not None:
            return cached
        w = np.asarray(bucket.weights, dtype=np.float64).reshape(
            bucket.local_num_blocks, bucket.lane)
        if bucket.is_local:
            import jax
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                w = np.asarray(
                    multihost_utils.process_allgather(w, tiled=True))
            # else: a 1-process window IS global — keep w as is
        self._psr_packed_weights[bucket.states] = w
        return w

    def psr_pattern_weights(self, gid: int) -> np.ndarray:
        """GLOBAL pattern weights of partition `gid` (== the partition's
        own weights on a full read)."""
        part = self.alignment.partitions[gid]
        if getattr(part, "global_width", None) is None:
            return np.asarray(part.weights, dtype=np.float64)
        if self._psr_global_weights is None:
            self._psr_global_weights = {}
            for states, bucket in self.buckets.items():
                flat = self.psr_packed_weights(bucket).reshape(-1)
                for li, g in enumerate(bucket.part_ids):
                    self._psr_global_weights[g] = flat[
                        bucket.site_indices(li)].copy()
        return self._psr_global_weights[gid]

    # -- tree construction -------------------------------------------------

    def tree_from_newick(self, text: str) -> Tree:
        return Tree.from_newick(text, self.alignment.taxon_names,
                                self.num_branch_slots)

    def random_tree(self, seed: int = 0) -> Tree:
        return Tree.random(self.alignment.taxon_names, seed,
                           self.num_branch_slots)

    # -- CLV orientation / traversal ---------------------------------------

    def _collect(self, tree: Tree, slot: Node, full: bool) -> List[TraversalEntry]:
        if tree.is_tip(slot.number):
            return []
        return tree.compute_traversal(slot, full)

    def new_view(self, tree: Tree, slot: Node) -> None:
        """Make slot's CLV valid (reference newviewGeneric)."""
        entries = self._collect(tree, slot, full=False)
        self.run_traversal(entries)

    def run_traversal(self, entries: List[TraversalEntry],
                      only_states=None, full: bool = False) -> None:
        if not len(entries):
            return
        for states, eng in self.engines.items():
            if only_states is not None and states not in only_states:
                continue
            eng.run_traversal(entries, full=full)

    def batch_evaluator(self):
        """The fleet tier's batched many-tree evaluator over this
        instance (examl_tpu/fleet/batch.py), or None when the instance
        is ineligible (-S SEV pools, multi-process sharded arenas) —
        one evaluator per instance so its compiled-pad bookkeeping and
        prepared-job caches persist across fleet batches.  A
        fabric-sharded instance (--mesh SxT) gets the MeshShard
        evaluator: job stacks commit over the mesh's tree axis so one
        dispatch spans every slice (fleet/shard.py)."""
        ev = getattr(self, "_batch_evaluator", None)
        if ev is None:
            from examl_tpu.fleet.batch import BatchEvaluator, batch_eligible
            if batch_eligible(self) is not None:
                return None
            sh = next(iter(self.engines.values())).sharding \
                if self.engines else None
            if sh is not None and getattr(sh, "is_fabric", False):
                from examl_tpu.fleet.shard import MeshShard
                ev = self._batch_evaluator = MeshShard(self)
            else:
                ev = self._batch_evaluator = BatchEvaluator(self)
        return ev

    def invalidate_schedules(self) -> None:
        """Drop every engine's cached schedule structures.  Called from
        the search's topology-commit seams (SPR regraft, best-tree
        recall, checkpoint restore); the signature keys already make
        staleness impossible, so this is hygiene + obs evidence
        (engine.sched_cache.invalidate)."""
        for eng in self.engines.values():
            eng.sched_cache_invalidate()

    # -- likelihood --------------------------------------------------------

    def evaluate(self, tree: Tree, p: Optional[Node] = None,
                 full: bool = False, only_states=None) -> float:
        """lnL at branch (p, p.back); reference evaluateGeneric
        (`evaluateGenericSpecial.c:897-1001`).

        only_states restricts traversal+evaluation to the named state
        buckets (the reference's executeModel masking during model
        optimization): other partitions keep their cached lnL, which stays
        valid because their parameters and the tree are unchanged.  Callers
        must finish with an unrestricted evaluate before changing topology.
        """
        if p is None:
            # Full traversals root at the topological centroid, not the
            # reference's tr->start tip edge: lnL is rooting-invariant,
            # but the centroid halves the wave-schedule depth (fewer
            # sequential newview steps on device) AND maximizes -S
            # savings — subtree windows stay small on BOTH sides, so
            # far more (node, block) cells are all-gap (measured
            # tools/sev_ratio.py: 57% vs 34% block cells saved on the
            # clade-structured fixture; the reference's own per-site
            # compaction at its tip rooting saves 49%).
            p = tree.centroid_branch() if full else tree.start
        q = p.back
        if full:
            # Array-rate full traversal (tree/topology.py): one host
            # pass + numpy scheduling, carrying the topology signature
            # the engines' schedule-structure caches key on.  Subsumes
            # invalidate_all + the two compute_traversal calls (every
            # inner node recomputed and re-oriented toward this edge).
            from examl_tpu import obs
            with obs.span("engine:tree/schedule", also="host_schedule"):
                entries = tree.flat_full_traversal(p)
        else:
            entries = (self._collect(tree, p, full)
                       + self._collect(tree, q, full))
        per_part = self.per_partition_lnl
        from examl_tpu.resilience import faults
        faults.fire("engine.dispatch")
        for states, eng in self.engines.items():
            if only_states is not None and states not in only_states:
                continue
            # Fused traversal + root evaluation: one dispatch per engine.
            vals = eng.traverse_evaluate(entries, p.number, q.number, p.z,
                                         full=full)
            if faults.fire("engine.nonfinite"):
                vals = np.full_like(np.asarray(vals, dtype=float), np.nan)
            if not np.all(np.isfinite(vals)):
                vals = self._nonfinite_retry(tree, eng, p, q)
            for li, gid in enumerate(eng.bucket.part_ids):
                per_part[gid] = vals[li]
        if only_states is not None and np.isnan(per_part).any():
            raise RuntimeError(
                "restricted evaluate before any unrestricted one: cached "
                "per-partition lnL is uninitialized for the skipped buckets")
        self.likelihood = float(per_part.sum())
        return self.likelihood

    def _nonfinite_retry(self, tree: Tree, eng, p: Node, q: Node):
        """Non-finite guard at the dispatch boundary: a NaN/−inf lnL
        from one engine means poisoned CLVs or a miscompiled fast-tier
        program (bf16 underflow past the rescaler, a bad cached kernel)
        — not a recoverable search state.  Retry ONCE on the scan tier
        with a full recompute of this engine's CLVs (the one program
        hardware-proven on every backend, the same escape hatch the
        bank pins); a second non-finite result is a hard error — a
        search step taken on a poisoned lnL silently corrupts the tree.
        Counted as engine.nonfinite_retries / .nonfinite_recovered."""
        from examl_tpu import obs
        obs.inc("engine.nonfinite_retries")
        obs.log(f"EXAML: non-finite lnL from the states={eng.bucket.states} "
                "engine; recomputing once on the scan tier")
        prior = eng.force_scan
        eng.force_scan = True
        try:
            tree.invalidate_all()
            entries = (self._collect(tree, p, True)
                       + self._collect(tree, q, True))
            vals = eng.traverse_evaluate(entries, p.number, q.number, p.z,
                                         full=True)
        finally:
            eng.force_scan = prior
        if not np.all(np.isfinite(vals)):
            raise FloatingPointError(
                "non-finite log-likelihood persists on the scan-tier "
                f"retry (states={eng.bucket.states}); refusing to search "
                "on a poisoned lnL")
        obs.inc("engine.nonfinite_recovered")
        return vals

    # -- branch-length optimization (Newton-Raphson) ------------------------

    def makenewz(self, tree: Tree, p: Node, q: Node, z0: Sequence[float],
                 maxiter: int = 1, mask_converged: bool = False) -> np.ndarray:
        """Optimize the branch (p,q) starting from z0; returns new z [C].

        Mirrors reference `topLevelMakenewz`
        (`makenewzGenericSpecial.c:1133-1349`) including curvature guards.
        """
        from examl_tpu.constants import ZMAX, ZMIN

        if len(self.engines) == 1:
            # Single state bucket (the common case): the entire operation —
            # both partial traversals, the sumtable, and the NR loop to
            # convergence — is ONE device dispatch (lax.while_loop), vs the
            # reference's one Allreduce per NR iteration
            # (`makenewzGenericSpecial.c:1241-1248`).
            from examl_tpu.utils import z_slots
            (eng,) = self.engines.values()
            entries = (self._collect(tree, p, False)
                       + self._collect(tree, q, False))
            conv = self.partition_converged if mask_converged else None
            return eng.newton_branch(entries, p.number, q.number,
                                     z_slots(z0, self.num_branch_slots),
                                     maxiter, conv)

        # Mixed state buckets: derivatives must sum across engines each NR
        # iteration, so the loop runs on host over per-engine sumtables.
        self.new_view(tree, p)
        self.new_view(tree, q)
        sts = {s: eng.make_sumtable(p.number, q.number)
               for s, eng in self.engines.items()}

        C = self.num_branch_slots
        z = np.asarray(z0, dtype=np.float64).copy()
        zprev = z.copy()
        zstep = np.zeros(C)
        maxiters = np.full(C, maxiter)
        outer_conv = np.zeros(C, dtype=bool)
        curvat_ok = np.ones(C, dtype=bool)
        if mask_converged:
            outer_conv |= self.partition_converged

        while not outer_conv.all():
            fresh = ~outer_conv & curvat_ok
            zprev = np.where(fresh, z, zprev)
            zstep = np.where(fresh, (1.0 - ZMAX) * z + ZMIN, zstep)
            curvat_ok = np.where(fresh, False, curvat_ok)

            z = np.clip(z, ZMIN, ZMAX)
            d1 = np.zeros(C)
            d2 = np.zeros(C)
            for s, eng in self.engines.items():
                e1, e2 = eng.branch_derivatives(sts[s], z)
                d1 += e1
                d2 += e2

            active = ~outer_conv & ~curvat_ok
            bad = active & (d2 >= 0.0) & (z < ZMAX)
            z = np.where(bad, 0.37 * z + 0.63, z)
            zprev = np.where(bad, z, zprev)
            curvat_ok = np.where(active & ~bad, True, curvat_ok)

            step = curvat_ok & ~outer_conv
            if step.any():
                with np.errstate(over="ignore"):
                    tantmp = np.where(d2 < 0.0, -d1 / np.where(d2 < 0, d2, 1.0),
                                      np.inf)
                    znew = np.where(tantmp < 100.0,
                                    np.clip(z * np.exp(np.minimum(tantmp, 100.0)),
                                            ZMIN, None),
                                    0.25 * zprev + 0.75)
                    znew = np.minimum(znew, 0.25 * zprev + 0.75)
                z = np.where(step & (d2 < 0.0), znew, z)
                z = np.minimum(z, ZMAX)
                maxiters = np.where(step, maxiters - 1, maxiters)
                moving = np.abs(z - zprev) > zstep
                gave_up = moving & (maxiters < -20)
                z = np.where(step & gave_up, np.asarray(z0), z)
                outer_conv = np.where(step, ~moving | gave_up, outer_conv)
        return z


def default_instance(phylip_path: str, model_path: Optional[str] = None,
                     **kwargs) -> PhyloInstance:
    from examl_tpu.io.alignment import load_alignment
    ad = load_alignment(phylip_path, model_path)
    return PhyloInstance(ad, **kwargs)
