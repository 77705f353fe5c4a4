"""LikelihoodEngine: device-resident CLV state + jitted kernel dispatch.

One engine instance manages one state-count bucket (see parallel/packing.py):
the CLV tensor `[rows, blocks, lane, rates, states]`, the per-(row, site)
scaling exponents, and jit-compiled traversal / root-evaluation / derivative
programs.  Traversal programs are compiled per wave-schedule shape [L, W]
(W a capped power of two, L a multiple of 4) so partial traversals
(typically 3-4 entries, reference `newviewGenericSpecial.c:925`) and full
traversals each reuse a handful of compiled variants.

CLV rows are indexed by tree-node number - 1 (tips 1..n hold their constant
tip indicator vectors, inner nodes n+1..2n-2 are recomputed on traversal);
the last row is scratch for padding entries.  This mirrors the reference's
one-CLV-per-inner-node memory scheme (`axml.h:533-629` xVector).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from examl_tpu import obs
from examl_tpu.obs import traffic as _traffic
from examl_tpu.models.gtr import ModelParams
from examl_tpu.ops import kernels
from examl_tpu.ops.kernels import DeviceModels, Traversal
from examl_tpu.parallel.packing import PackedBucket
from examl_tpu.tree.topology import FlatTraversal, TraversalEntry
from examl_tpu.utils import z_slots as _z_slots


def stack_models(models: Sequence[ModelParams],
                 branch_indices: Sequence[int], dtype,
                 psr: bool = False) -> DeviceModels:
    from examl_tpu.models.lg4 import LG4Params

    R = models[0].ncat
    assert all(m.ncat == R for m in models)
    arr = lambda xs: jnp.asarray(np.stack(xs), dtype=dtype)

    def per_cat(m, field_lg4, field):
        """[R, ...] per-category tensor: LG4 models supply one per
        category, plain models tile their single one."""
        if isinstance(m, LG4Params):
            return np.stack(getattr(m, field_lg4))
        return np.broadcast_to(getattr(m, field),
                               (R,) + getattr(m, field).shape)

    def weights_of(m):
        if psr:
            return np.ones(R)
        if isinstance(m, LG4Params):
            return np.asarray(m.rate_weights)
        return np.full(R, 1.0 / R)

    return DeviceModels(
        eign=arr([per_cat(m, "eign_list", "eign") for m in models]),
        ev=arr([per_cat(m, "ev_list", "ev") for m in models]),
        ei=arr([per_cat(m, "ei_list", "ei") for m in models]),
        freqs=arr([per_cat(m, "freqs_list", "freqs") for m in models]),
        gamma_rates=arr([m.gamma_rates for m in models]),
        rate_weights=arr([weights_of(m) for m in models]),
        part_branch=jnp.asarray(np.asarray(branch_indices, dtype=np.int32)),
    )


from examl_tpu.utils import next_pow2 as _next_pow2


def _bucket_len(n: int) -> int:
    """Round a traversal length up to a bucketed size (utils.bucket_len:
    multiples of 4 up to 16, then <=25% geometric buckets).  Keeps the
    number of compiled traversal variants O(log n) while a padding wave
    costs a full W-wide newview, so the waste per call stays bounded."""
    from examl_tpu.utils import bucket_len
    return bucket_len(n)


class LikelihoodEngine:
    _obs_seq = 0                 # gauge-name ordinal (see _register_obs)
    _family = "direct"           # family of the dispatch in hand (_phase);
    # "direct" until the first: a harness calling a schedule helper itself

    def __init__(self, bucket: PackedBucket, models: Sequence[ModelParams],
                 ntips: int, num_branch_slots: int = 1,
                 branch_indices: Optional[Sequence[int]] = None,
                 dtype=jnp.float64, sharding=None,
                 scale_exp: Optional[int] = None, wave_width: int = 8,
                 psr: bool = False, save_memory: bool = False):
        from examl_tpu.config import refuse_removed_switches
        refuse_removed_switches()
        self.bucket = bucket
        self.ntips = ntips
        self.psr = psr
        self.save_memory = save_memory
        self.dtype = jnp.dtype(dtype)
        self.scale_exp = (scale_exp if scale_exp is not None
                          else kernels.default_scale_exponent(self.dtype))
        self.num_branch_slots = num_branch_slots
        self.wave_width = wave_width
        self.num_parts = bucket.num_parts
        # CLV rows hold INNER nodes only plus one scratch row; tips live as
        # packed uint8 codes with an indicator lookup table, materialized on
        # the fly inside the kernels (the reference's yVector + tipVector
        # scheme, `axml.h:533-629` -- tip CLVs are never stored, which more
        # than halves likelihood-buffer memory).  Row assignment is a HOST
        # map (`row_map`): full traversals relayout rows in wave order so
        # the fast path writes contiguous slices (ops/fastpath.py); partial
        # traversals update rows in place through the map.  The arena keeps
        # `fast_slack` rows of headroom for the fast path's padded writes.
        self.n_inner = max(ntips - 2, 1)
        # EXAML_FAST_TRAVERSAL=0 forces the wave-batched scan tier for
        # full traversals too (escape hatch: the chunk pipeline is the
        # faster program, but the scan program is the one whose compile
        # is proven on every backend).
        # Runtime-togglable via `force_scan` (the arena keeps its slack).
        import os as _fos
        self.force_scan = _fos.environ.get("EXAML_FAST_TRAVERSAL",
                                           "") == "0"
        # Universal interpreter tier (ops/universal.py): topology-as-
        # data execution of the SAME bounded chunk layout through one
        # compiled lax.scan/lax.switch program whose jit key is
        # bucket sizes + the (kind, width) alphabet, not the
        # per-topology segment profile.  EXAML_UNIVERSAL=0 opts out
        # (mirroring EXAML_FAST_TRAVERSAL); "force"/"always" pins every
        # eligible full traversal to the interpreter — the supervisor's
        # chunk->universal degradation rung and the equivalence tests'
        # lever.  Default: available, taken when a serving caller sets
        # `route_novel_to_universal` and the specialized program for a
        # profile is not already compiled (zero-recompile serving).
        self._universal_env = _fos.environ.get("EXAML_UNIVERSAL", "")
        self.universal_off = self._universal_env == "0"
        self.universal_force = self._universal_env in ("force", "always")
        self.route_novel_to_universal = False
        self._last_universal = False   # the most recent fast dispatch
                                       # ran the interpreter (tier tag)
        # Slack floor: the bounded chunk layout pads narrow chunks up to
        # the width floor and points the scanned tail's padding
        # sub-chunks at the slack region, so the arena headroom follows
        # the live layout knobs (fastpath.slack_rows; the build asserts
        # max_write fits in any case).
        from examl_tpu.ops import fastpath as _fastpath
        self.fast_slack = (0 if psr or save_memory
                           else _fastpath.slack_rows(ntips))
        self.num_rows = self.n_inner + self.fast_slack + 1
        self.scratch_row = self.num_rows - 1
        self.row_map = np.full(2 * ntips - 1, -1, dtype=np.int64)
        for num in range(ntips + 1, 2 * ntips - 1):
            self.row_map[num] = num - ntips - 1
        # Precision for the fast path's CHILD CLV contractions only.  These
        # sums are all-positive (transition probabilities x likelihoods, no
        # cancellation), so 3-pass bf16 (HIGH) costs 0.016 lnL absolute on
        # testData/140 (1.2e-7 relative, NUMERICS.md) while halving MXU
        # passes vs HIGHEST; P-matrix eigen-recomposition and the root
        # evaluation stay at HIGHEST (cancellation-prone -- the measurement
        # that rejected HIGH globally was dominated by those).  CPU ignores
        # the knob (always true f32/f64).  EXAML_DOT_PRECISION overrides.
        import os as _pos
        # CLV STORAGE dtype (ROOFLINE.md lever 3): the newview kernel is
        # HBM-bandwidth-bound, so storing the arena in bf16 (compute
        # stays f32: gathers upcast after the load, stores downcast
        # before it) halves bytes/update and doubles the throughput
        # ceiling.  Opt-in via EXAML_CLV_DTYPE=bf16 — each CLV cell is
        # rounded once per node level, so the lnL bound must be
        # re-measured per analysis (see NUMERICS.md).  A non-f32 compute
        # dtype (f64 parity runs) ignores the knob: a globally-exported
        # env var must not crash unrelated jobs.
        _clv_env = _pos.environ.get("EXAML_CLV_DTYPE", "")
        if _clv_env in ("bf16", "bfloat16") and self.dtype == jnp.float32:
            self.storage_dtype = jnp.dtype(jnp.bfloat16)
        elif _clv_env in ("", "0", "same", "bf16", "bfloat16"):
            self.storage_dtype = self.dtype
        else:
            raise ValueError(f"EXAML_CLV_DTYPE={_clv_env!r}: expected "
                             "bf16/bfloat16 or unset")
        _prec = _pos.environ.get("EXAML_DOT_PRECISION", "high").upper()
        if _prec not in ("DEFAULT", "HIGH", "HIGHEST"):
            raise ValueError(
                f"EXAML_DOT_PRECISION={_prec!r}: expected one of "
                "default/high/highest")
        self.fast_precision = getattr(jax.lax.Precision, _prec)
        # LRU-bounded: topology churn during a search mints distinct
        # wave profiles without bound; evicting beyond 32 keeps
        # compiled-program memory bounded (recompiling a re-seen profile
        # costs seconds, holding hundreds costs GBs).
        from collections import OrderedDict
        self._fast_jit_cache = OrderedDict()
        self._fast_jit_cache_cap = 32
        # Schedule-STRUCTURE cache (tentpole of the host-path scale
        # work): the immutable half of a fast-path schedule — chunk
        # layout, child index/code arrays, row map — keyed by the
        # traversal's 128-bit topology signature (FlatTraversal.
        # topo_key, a function of topology + root edge only).  The
        # branch-length-only full traversals that dominate model
        # optimization and repeated evaluations hit here and skip the
        # Python schedule rebuild entirely, refreshing only z
        # (fastpath.refresh_z).  Self-validating: an SPR/NNI topology
        # change mints a different signature, so a stale structure can
        # never be served — explicit invalidation (sched_cache_
        # invalidate, called from the search's commit seams) is memory
        # hygiene plus the obs evidence, not a correctness requirement.
        self._sched_cache = OrderedDict()
        self._sched_cache_cap = 8
        # Universal-interpreter descriptor tables (host arrays derived
        # from a FastStructure: class ids, slot offsets, padded index
        # copies), keyed like the structure cache by topology signature
        # — content-keyed, so staleness is impossible and eviction is
        # only memory hygiene.
        self._universal_tables = OrderedDict()
        self._universal_tables_cap = 8
        # Whole-tree gradient plans (ops/gradient.py): the reversed
        # wave packing + edge table, a function of topology + root
        # edge only — keyed like the structure cache by topology
        # signature (content-keyed: staleness impossible, eviction is
        # hygiene).  z values and CLV gather indices refresh per
        # dispatch.
        self._grad_structs = OrderedDict()
        self._grad_structs_cap = 8
        self.sharding = sharding

        lane = bucket.lane
        B = bucket.num_blocks              # GLOBAL (jit program shapes)
        self.B, self.lane = B, lane
        self.R = models[0].ncat
        self.K = bucket.states
        if bucket.is_local:
            if sharding is None:
                raise ValueError("a local (sliced) bucket requires a "
                                 "site-axis sharding")

        if branch_indices is None:
            branch_indices = [0] * self.num_parts
        self._branch_indices = list(branch_indices)
        self.models = self._replicated(stack_models(
            models, branch_indices, self.dtype, psr=psr))
        # Per-site rate multipliers (PSR/CAT model); None selects the
        # GAMMA path in every kernel.  Placed like every per-site tensor
        # (block axis sharded) so multi-process jobs hold a global
        # array; under selective loading each process contributes only
        # its block window (reference per-rank CAT state,
        # `optimizeModel.c:2135-2254` — here the categorization itself
        # is global on every process, see optimize/psr.py).
        self.site_rates = (self._put_blocks(
            self._local_block_window(np.ones((B, lane, 1),
                                             dtype=self.dtype)),
            lambda s: s.sites)
            if psr else None)

        Bl = bucket.local_num_blocks
        self.block_part = self._put_blocks(
            bucket.block_part, lambda s: s.blocks)
        self.weights = self._put_blocks(
            np.asarray(bucket.weights.reshape(Bl, lane), dtype=self.dtype),
            lambda s: s.sites)

        self.tips = self._build_tip_state()
        if save_memory:
            from examl_tpu.ops.sev import SevState
            if sharding is not None and sharding.tree_shards > 1:
                # The CLI names this (S, T) combination precisely; this
                # is the engine-level backstop for embedded callers.
                raise ValueError(
                    f"-S cannot compose with a {sharding.site_shards}x"
                    f"{sharding.tree_shards} fabric: the SEV pool "
                    "holds one arena per instance, so per-job arenas "
                    "cannot stack along the tree axis (Sx1 only)")
            self.clv = None
            gdev = sharding.num_devices if sharding is not None else 1
            local_ndev, cap_reduce = gdev, None
            if sharding is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as _P
                from examl_tpu.parallel.sharding import SITE_AXIS as _SA
                _pool_sh = NamedSharding(sharding.mesh, _P(_SA))
                _slot_sh = NamedSharding(sharding.mesh, _P(None, _SA))

                # Born sharded: -S exists because the pool only fits
                # when split across devices, so it must never stage
                # whole on one device (reuses the dense arena's
                # born-sharded allocator).
                zeros_pool = (lambda shape, dt:
                              self._zeros_sharded(shape, dt,
                                                  lambda _: _pool_sh))

                if bucket.is_local:
                    # Multi-host selective loading: this process's
                    # bookkeeping covers its block window only; slot
                    # maps assemble globally from the local windows, and
                    # the region capacity / dirty flag agree via a tiny
                    # host allgather (the reference's per-rank data +
                    # Allreduce'd bookkeeping, byteFile.c:278-382).
                    if B % gdev:
                        raise ValueError(
                            "-S selective loading needs the GLOBAL "
                            f"block count ({B}) divisible by the mesh "
                            f"size ({gdev}); pad the instance with "
                            "block_multiple=num_devices")
                    b_per_dev = B // gdev
                    if (bucket.local_num_blocks % b_per_dev
                            or bucket.block_offset % b_per_dev):
                        raise ValueError(
                            "-S selective loading needs the process "
                            "block window aligned to whole devices "
                            f"(window {bucket.block_offset}+"
                            f"{bucket.local_num_blocks} blocks, "
                            f"{b_per_dev} blocks/device)")
                    local_ndev = bucket.local_num_blocks // b_per_dev

                    def cap_reduce(local_max, dirty):
                        from jax.experimental import multihost_utils
                        pair = multihost_utils.process_allgather(
                            np.asarray([local_max, int(dirty)],
                                       np.int64))
                        return int(pair[:, 0].max()), bool(
                            pair[:, 1].any())

                    def put_slot(arr):
                        return jax.make_array_from_process_local_data(
                            _slot_sh, np.asarray(arr))
                else:
                    put_slot = lambda x: jax.device_put(jnp.asarray(x),
                                                        _slot_sh)
            else:
                zeros_pool = put_slot = None
            self.sev = SevState(bucket.tip_codes, self._undetermined_code(),
                                self.num_rows, bucket.local_num_blocks,
                                lane, self.R, self.K,
                                self.storage_dtype, ndev=local_ndev,
                                zeros_pool=zeros_pool, put_slot=put_slot,
                                global_regions=gdev,
                                cap_reduce=cap_reduce)
        else:
            self.sev = None
            self.clv = self._zeros_sharded(
                (self.num_rows, B, lane, self.R, self.K),
                self.storage_dtype, lambda s: s.clv)
        self.scaler = self._zeros_sharded((self.num_rows, B, lane),
                                          jnp.int32, lambda s: s.scaler)

        # One jitted traversal program; jax recompiles per padded entry-count
        # shape (powers of two, so only a handful of variants exist).  The
        # CLV/scaler buffers are donated: they are replaced by the outputs,
        # never read again.  site_rates rides along as a traced argument
        # (None on the GAMMA path).
        from examl_tpu.parallel.sharding import SITE_AXIS as _SAX
        self._axis_name = (_SAX if (save_memory and sharding is not None)
                           else None)
        if self._axis_name is not None:
            self._build_sev_mapped_programs()
        else:
            self._jit_traverse = jax.jit(self._traverse_only_impl,
                                         donate_argnums=(0, 1))
            self._jit_evaluate = jax.jit(self._evaluate_impl)
            self._jit_trav_eval = jax.jit(self._trav_eval_impl,
                                          donate_argnums=(0, 1))
            self._jit_newton = jax.jit(self._newton_impl,
                                       donate_argnums=(0, 1))
            self._jit_sumtable = jax.jit(self._sumtable_impl)
            self._jit_derivs = jax.jit(self._derivs_impl)
        self._jit_rate_scan = jax.jit(self._rate_scan_impl)
        # Exported program bank (ops/export_bank.py): program-identity
        # constants that are INVISIBLE in the arg avals — two programs
        # with identical input shapes but different engine constants
        # (scale exponent, dot precision, partition count, chunk-layout
        # knobs) must never share a serialized executable.  Eligibility
        # is single-process default-device engines only: mesh-sharded
        # and -S pooled executables embed placement state the bank does
        # not relocate (ROADMAP §4 keeps counting that residual).
        # The mesh shape is part of the program family (ISSUE 17): a
        # 2x2-fabric executable partitions differently from a 4x1 or an
        # unsharded one even at identical avals, so the (S, T) term
        # keys every shared-cache entry and export-artifact signature.
        mesh_term = (None if self.sharding is None
                     else (self.sharding.site_shards,
                           self.sharding.tree_shards))
        self._export_identity = (
            "prog-v1", self.K, str(self.dtype), str(self.storage_dtype),
            int(self.scale_exp), str(self.fast_precision),
            self.num_parts, self.num_branch_slots, self.ntips,
            bool(self.psr), (_fastpath.MIN_WIDTH, _fastpath.CHUNK_CAP,
                             _fastpath.TAIL_WIDTH), self.wave_width,
            mesh_term)
        self._exportable = (self.sharding is None and not save_memory
                            and self.clv is not None
                            and next(iter(self.clv.devices()))
                            == jax.devices()[0])
        # Core programs get the same timed/watchdogged first-call monitor
        # as the shared-cache fast programs: any program family's compile
        # can stall, so every family must be able to
        # name itself from the watchdog and account its compile seconds.
        # The export-bank wrapper sits OUTSIDE the guard: a deserialized
        # executable serves the dispatch without the guard (or any
        # compile) ever firing, a miss falls through to the guarded
        # compile and serializes its result for the next cold start.
        from examl_tpu.ops import export_bank as _export_bank
        for attr, family in (("_jit_traverse", "traverse"),
                             ("_jit_evaluate", "evaluate"),
                             ("_jit_trav_eval", "trav_eval"),
                             ("_jit_newton", "newton"),
                             ("_jit_sumtable", "sumtable"),
                             ("_jit_derivs", "derivs"),
                             ("_jit_rate_scan", "rate_scan")):
            raw = getattr(self, attr)
            guarded = self._guard_first_call(raw, family)
            setattr(self, attr, _export_bank.wrap(
                raw, guarded, family, (family,) + self._export_identity,
                exportable=self._exportable,
                entry_meta={"ntips": self.ntips}))
        # In-engine traffic accounting (obs/traffic.py, the shared
        # roofline model): true (unpadded) pattern count for the bytes
        # model, per-tier windowed achieved-GB/s accumulators fed by
        # the timed blocking dispatch path (per-tier so a scan-tier
        # recompute among chunk-tier evals can never blend into the
        # wrong gauge), and the sequential-op count of the most recent
        # schedule (the launch-floor term of the regime classifier).
        self._patterns_true = int(np.sum(bucket.part_widths))
        self._traffic_win: Dict[str, _traffic.TrafficWindow] = {}
        self._traffic_led: Dict[str, float] = {}
        self._last_dispatch_ops = 1
        self._register_obs()

    # -- observability ------------------------------------------------------

    def _register_obs(self) -> None:
        """Publish this engine's gauges into the process metrics registry
        via a weakref-bound snapshot collector (ISSUE: CLV arena bytes,
        rescale counts) — zero per-dispatch cost, the device is touched
        only when a snapshot is taken."""
        import weakref

        obs.inc("engine.instances")
        # The bucket's packed site axis against its live patterns: the
        # zero-weight lanes that pad every partition to whole blocks
        # (parallel/packing.py) are 1 - patterns / lanes of every row.
        obs.inc("engine.site_lanes", self.bucket.num_sites)
        obs.inc("engine.site_patterns", self._patterns_true)
        from examl_tpu.ops import fastpath
        obs.gauge("engine.model_groups", fastpath.tail_p_models(
            self.models.eign.shape[0], self.site_shards))
        # Unique per engine: two same-state engines in one process must
        # not alias each other's gauges — the ordinal disambiguates.
        seq = LikelihoodEngine._obs_seq
        LikelihoodEngine._obs_seq += 1
        self._obs_tag = f"s{self.K}.e{seq}"
        self._update_arena_gauge()
        if self.sharding is not None:
            # Declared-mesh axis gauges (ISSUE 17): instance-wide (every
            # engine of one run shares the mesh), rendered by
            # tools/run_report.py and tools/top.py next to the fleet's
            # per-slice dispatch counters.
            obs.gauge("engine.mesh_site_shards", self.sharding.site_shards)
            obs.gauge("engine.mesh_tree_shards", self.sharding.tree_shards)
        ref = weakref.ref(self)

        def _collect():
            eng = ref()
            if eng is None:
                return False
            eng._update_arena_gauge()
            try:
                # Total accumulated scaling counts across the arena — the
                # host-visible residue of on-device rescale events.  Only
                # safe single-process: a one-sided reduction over a
                # multi-process global array would hang the job.
                if eng.sharding is None and eng.scaler is not None:
                    obs.gauge("engine.rescale_scale_counts." + eng._obs_tag,
                              int(jnp.sum(eng.scaler)))
            except Exception:
                pass
            return True

        obs.add_collector(_collect)

    def _update_arena_gauge(self) -> None:
        itemsize = np.dtype(self.storage_dtype).itemsize
        if self.clv is not None:
            nbytes = (self.num_rows * self.B * self.lane * self.R
                      * self.K * itemsize)
        elif self.sev is not None and self.sev.pool is not None:
            nbytes = int(np.prod(self.sev.pool.shape)) * itemsize
        else:
            nbytes = 0
        obs.gauge(f"engine.clv_arena_bytes.{self._obs_tag}", nbytes)

    # -- traffic accounting (shared roofline model, obs/traffic.py) ---------

    def _dispatch_tier(self, fast: bool) -> str:
        """Tier label for the traffic gauges: which program family moved
        the bytes (scan = the wave-batched fallback; chunk = the fast
        path; universal = the topology-as-data interpreter)."""
        if not fast:
            return "scan"
        return "universal" if self._last_universal else "chunk"

    def _tier_for(self, entries, full: bool) -> str:
        """Tier a traversal over `entries` will actually dispatch on
        (a full, fast-eligible FlatTraversal -> the fast tier;
        everything else — an entry list, partial, PSR, -S, force_scan
        — runs the scan tier)."""
        return self._dispatch_tier(
            full and isinstance(entries, FlatTraversal)
            and self._fast_eligible_flat(entries))

    def _traversal_traffic_bytes(self, entries) -> int:
        """Modeled HBM bytes of one traversal over `entries` (a
        TraversalEntry list or a FlatTraversal): obs/traffic.py's
        closed form."""
        itemsize = np.dtype(self.storage_dtype).itemsize
        if isinstance(entries, FlatTraversal):
            tips = int((np.asarray(entries.left) <= self.ntips).sum()
                       + (np.asarray(entries.right) <= self.ntips).sum())
            return _traffic.bytes_per_traversal_counts(
                entries.n, tips, self._patterns_true, self.R, self.K,
                itemsize)
        return _traffic.bytes_per_traversal(
            entries, self.ntips, self._patterns_true, self.R, self.K,
            itemsize)

    def _scan_plan_traffic_bytes(self, plan) -> int:
        """Modeled HBM bytes of one batched-scan dispatch: the downpass
        orientation fixes (plain TraversalEntry rows) PLUS the uppass
        entries, each writing one scan row and reading its two child
        refs (a (kind, v) ref with a non-slot kind and v <= ntips is a
        tip code row — the same tip test the shared model applies)."""
        up = plan.up_entries
        tips = sum(1 for e in up for kind, v in (e.left, e.right)
                   if kind != "slot" and v <= self.ntips)
        itemsize = np.dtype(self.storage_dtype).itemsize
        return (self._traversal_traffic_bytes(list(plan.down_entries))
                + _traffic.bytes_per_traversal_counts(
                    len(up), tips, self._patterns_true, self.R, self.K,
                    itemsize))

    def _record_traffic(self, nbytes: int, tier: str,
                        wall_s: Optional[float] = None,
                        window: bool = True) -> None:
        """Account one dispatch's modeled bytes; blocking full-traversal
        dispatches (wall_s given) additionally land in the `dispatch`
        latency histogram and — unless `window=False` — feed the
        windowed achieved-GB/s gauge with the regime verdict, so every
        metrics snapshot states WHICH regime its number came from.
        Callers pass window=False when the measured wall contains a
        first-call COMPILE: the histogram must keep it (that p99 is the
        point), but a compile-dominated window would publish a
        near-zero GB/s wrongly tagged bandwidth-meaningful.

        `nbytes` is the WHOLE alignment's (`_patterns_true`), so under a
        mesh `engine.traffic_bytes` and the achieved-GB/s gauges are sums
        over the chips: one chip's part, the figure its own roofline is
        held to (the benchmark's `*_chip_roofline`), is that over the
        gauge `engine.mesh_site_shards`."""
        obs.inc("engine.traffic_bytes", nbytes)
        # Drift gate (obs/programs.py): reconcile this dispatch's
        # analytic bytes with the serving program's XLA bytes-accessed
        # (program.model_drift_pct.<tier>) and learn which source can
        # back the tier's achieved-GB/s row.  The model stays the
        # gauge's denominator either way — the tag makes a chip-round
        # row self-describing, the gate makes model bugs evidence.
        from examl_tpu.obs import programs as _programs
        src = _programs.model_vs_xla(tier, nbytes)
        if wall_s is None:
            return
        # The `dispatch` timer: wall of one
        # BLOCKING traversal dispatch — its p99 is where a launch-floor
        # stall or surprise recompile shows up in any CLI snapshot.
        obs.observe("dispatch", wall_s)
        if not window:
            return
        win = self._traffic_win.get(tier)
        if win is None:
            win = self._traffic_win[tier] = _traffic.TrafficWindow()
        out = win.add(nbytes, wall_s, self._last_dispatch_ops)
        if out is None:
            return
        gbps, regime, n = out
        # Per-engine tagged like clv_arena_bytes/program_chunks: a
        # DNA+AA instance has two engines whose windows close
        # interleaved — untagged, the snapshot would quote whichever
        # partition's verdict landed last as the run's.
        label = f"{tier}.{self._obs_tag}"
        obs.gauge(f"engine.achieved_gbps.{label}", round(gbps, 3))
        obs.gauge(f"engine.regime_dispatch_bound.{label}",
                  1.0 if regime["regime"] == "dispatch-bound" else 0.0)
        # source: model|xla for the row's bytes figure (1.0 = an XLA
        # bytes-accessed figure exists for the serving program and the
        # drift gauge above reconciles the two).
        obs.gauge(f"engine.traffic_source_xla.{label}",
                  1.0 if src == "xla" else 0.0)
        # Live HBM telemetry rides the traffic-window cadence: one
        # rate-limited device.memory_stats() sample per closed window.
        _programs.sample_memory()
        # Ledger cadence is rate-limited per tier (the gauges above
        # always carry the LATEST verdict): a flight recorder wants
        # periodic bandwidth samples on the timeline, not one line per
        # window when tests shrink the window to a single dispatch.
        now = time.time()
        if now - self._traffic_led.get(tier, 0.0) >= \
                _traffic.LEDGER_EVENT_INTERVAL_S:
            self._traffic_led[tier] = now
            obs.ledger_event("traffic.window", tier=tier,
                             gbps=round(gbps, 3), dispatches=n,
                             source=src, **regime)

    def _site_spec_vocab(self) -> dict:
        """PartitionSpec vocabulary + shard_map wrapper for the programs
        mapped over the site axis — shared by the SEV x sharding core
        programs, the batched-scan program (search/batchscan.py) and
        the site-sharded gradient pass (`_grad_program`)."""
        from jax.sharding import PartitionSpec as P

        from examl_tpu.parallel.sharding import SITE_AXIS as AX

        mesh = self.sharding.mesh
        REP = P()

        def wrap(impl, in_specs, out_specs, donate=()):
            mapped = jax.shard_map(impl, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs)
            return jax.jit(mapped, donate_argnums=donate)

        return {
            "rep": REP,
            "pool": P(AX),                        # [ndev*cap, lane, R, K]
            "scaler": P(None, AX),                # [rows, B, lane]
            "aux": (P(None, AX), P(None, AX)),    # slot_read, slot_write
            "blocks": P(AX),                      # block_part [B]
            "sites": P(AX),                       # weights [B, lane]
            # site_rates [B, lane, 1] shards its block axis under PSR;
            # GAMMA passes the literal None leaf, whose spec must be
            # None for the pytrees to match.
            "sr": P(AX) if self.psr else None,
            "tips": kernels.TipState(codes=P(None, AX), masks=P(None, AX),
                                     table=REP),
            "models": DeviceModels(*(REP,) * len(DeviceModels._fields)),
            "traversal": Traversal(*(REP,) * len(Traversal._fields)),
            "wrap": wrap,
        }

    def _build_sev_mapped_programs(self) -> None:
        """SEV x sharding: the pooled programs run under `jax.shard_map`.

        The pool's cell axis is irregular while the mesh shards blocks,
        so GSPMD cannot prove the pool gathers local; shard_map makes
        the guarantee structural: each device's program sees ITS pool
        region [cap, lane, R, K] (cell ids are region-local,
        ops/sev.py), its block range of the slot maps / tip codes /
        weights, and runs the IDENTICAL pooled kernel — the only
        cross-device traffic is the lnL / derivative psum the kernels
        emit when axis_name is set (the reference's MPI Allreduces,
        `evaluateGenericSpecial.c:968-973`,
        `makenewzGenericSpecial.c:1241-1248`)."""
        v = self._site_spec_vocab()
        (REP, pool_s, sc_s, aux_s, b_s, bl_s, tips_s, dm_s, tv_s, sr_s,
         wrap) = (v["rep"], v["pool"], v["scaler"], v["aux"], v["blocks"],
                  v["sites"], v["tips"], v["models"], v["traversal"],
                  v["sr"], v["wrap"])

        self._jit_traverse = wrap(
            self._traverse_only_impl,
            (pool_s, sc_s, aux_s, tv_s, dm_s, b_s, tips_s, sr_s),
            (pool_s, sc_s), donate=(0, 1))
        self._jit_evaluate = wrap(
            self._evaluate_impl,
            (pool_s, sc_s, aux_s, REP, REP, REP, dm_s, b_s, bl_s,
             tips_s, sr_s),
            REP)
        self._jit_trav_eval = wrap(
            self._trav_eval_impl,
            (pool_s, sc_s, aux_s, tv_s, REP, REP, REP, dm_s, b_s, bl_s,
             tips_s, sr_s),
            (pool_s, sc_s, REP), donate=(0, 1))
        self._jit_newton = wrap(
            self._newton_impl,
            (pool_s, sc_s, aux_s, tv_s, REP, REP, REP, REP, REP, dm_s,
             b_s, bl_s, tips_s, sr_s),
            (pool_s, sc_s, REP), donate=(0, 1))
        st_s = b_s                          # sumtable [B, lane, R, K]
        self._jit_sumtable = wrap(
            self._sumtable_impl,
            (pool_s, sc_s, aux_s, REP, REP, dm_s, b_s, tips_s),
            st_s)
        self._jit_derivs = wrap(
            self._derivs_impl,
            (st_s, REP, dm_s, b_s, bl_s, sr_s),
            (REP, REP))

    # -- construction helpers ---------------------------------------------

    def _datatype(self):
        from examl_tpu import datatypes
        if self.K == 4:
            return datatypes.DNA
        if self.K == 20:
            return datatypes.AA
        return datatypes.BINARY

    def _undetermined_code(self) -> int:
        return self._datatype().undetermined_code

    def _build_tip_state(self) -> kernels.TipState:
        dt = self._datatype()
        table = self._replicated(
            jnp.asarray(dt.tip_indicator_table(), dtype=self.dtype))
        codes = self.bucket.tip_codes.astype(np.uint8).reshape(
            self.ntips, self.bucket.local_num_blocks, self.lane)
        masks = dt.code_bitmasks[codes].astype(
            kernels.tip_mask_dtype(self.K))
        return kernels.TipState(
            codes=self._put_blocks(codes, lambda s: s.scaler),
            masks=self._put_blocks(masks, lambda s: s.scaler), table=table)

    # -- tensor placement ---------------------------------------------------
    # Single-device: plain jnp arrays.  Sharded, global bucket: device_put
    # of full-width host arrays.  Sharded, LOCAL bucket (multi-host
    # selective loading): this process holds only its contiguous window of
    # the block axis, and the global array is assembled from per-process
    # shards — host memory never sees the full width (the reference's
    # per-rank site slices, `byteFile.c:278-382`).

    def _local_block_window(self, host_global: np.ndarray) -> np.ndarray:
        """This process's contiguous block window of a GLOBAL block-axis
        host array (identity on global buckets): the bridge between
        host-global state (PSR rates, rate-scan grids — identical on
        every process) and `_put_blocks`, which under selective loading
        expects only the local window."""
        if self.bucket.is_local:
            o = self.bucket.block_offset
            return host_global[o:o + self.bucket.local_num_blocks]
        return host_global

    def _put_blocks(self, host: np.ndarray, pick):
        """Place a block-axis host array (full width, or the local window
        of a local bucket) under the sharding member pick selects."""
        if self.sharding is None:
            return jnp.asarray(host)
        sh = pick(self.sharding)
        if self.bucket.is_local:
            return jax.make_array_from_process_local_data(sh, host)
        return jax.device_put(jnp.asarray(host), sh)

    def _replicated(self, tree):
        """A pytree of small arrays (models, the tip table, schedule
        structures) placed once on every device of the mesh, so that no
        sharded call re-lays them from device 0; the tree itself without
        a mesh."""
        if self.sharding is None:
            return tree
        return jax.device_put(tree, self.sharding.replicated)

    def _zeros_sharded(self, shape, dtype, pick):
        """A zero array born with its final sharding: no single-device
        (or single-process) staging of the full-size buffer — the CLV
        arena is the framework's dominant allocation."""
        if self.sharding is None:
            return jnp.zeros(shape, dtype=dtype)
        npdtype = np.dtype(dtype)

        def shard_zeros(idx):
            shard_shape = tuple(
                len(range(*sl.indices(dim))) for sl, dim in zip(idx, shape))
            return np.zeros(shard_shape, dtype=npdtype)

        return jax.make_array_from_callback(shape, pick(self.sharding),
                                            shard_zeros)

    def set_models(self, models: Sequence[ModelParams]) -> None:
        with obs.span("engine:set_models"):
            self.models = self._replicated(stack_models(
                models, self._branch_indices, self.dtype, psr=self.psr))
            obs.inc("engine.staged_arrays", len(DeviceModels._fields))

    # -- spans of the timed path (obs/trace.py) -----------------------------
    # Every blocking dispatch is one `engine:<family>` span tiled by up
    # to four phases: schedule (host structures), stage (host values to
    # device arguments), launch (the jitted call until it returns; a
    # first call's compile nests here) and wait (the blocking read-back).

    def _dispatch(self, family: str, args: Optional[dict] = None,
                  also: Optional[str] = None):
        """The span of one dispatch.  Not a profiler annotation: its
        phases are, and benchmarks/tracereduce.py gives an idle gap to
        the `engine:*` annotation covering most of it, so an annotated
        parent would shadow them."""
        self._family = family
        return obs.span("engine:" + family, args, cat="dispatch",
                        annotate=False, also=also)

    def _phase(self, phase: str):
        """A phase of the dispatch in hand.  The schedule phases also
        feed the `host_schedule` timer (the host floor, whatever the
        family)."""
        return obs.span(f"engine:{self._family}/{phase}", cat="dispatch",
                        also="host_schedule" if phase == "schedule"
                        else None)

    def _stage_root(self, sched, p_num: int, q_num: int, z):
        """(p_idx, q_idx, z): the device arguments of a root evaluation
        at branch (p, q), against `sched`'s not yet installed layout or,
        with None, the installed one."""
        if sched is None:
            p, q = self._gidx(p_num), self._gidx(q_num)
        else:
            p, q = self._gidx_of(sched, p_num), self._gidx_of(sched, q_num)
        return (self._stage(p, jnp.int32), self._stage(q, jnp.int32),
                self._stage(_z_slots(z, self.num_branch_slots), self.dtype))

    def _stage(self, value, dtype=None) -> jax.Array:
        """One host array or scalar handed to jnp as a device argument
        (`jnp.int32(v)` is `jnp.asarray(v, dtype=jnp.int32)`); counted,
        because each is a transfer and often a one-scalar convert
        program of its own."""
        obs.inc("engine.staged_arrays")
        if self.sharding is not None:
            # Born replicated over the mesh: `jnp.asarray` would commit
            # it to device 0 and every sharded call would re-lay it.
            return jax.device_put(np.asarray(value, dtype=dtype),
                                  self.sharding.replicated)
        return jnp.asarray(value, dtype=dtype)

    def invalidate_tips_changed(self) -> None:
        self.tips = self._build_tip_state()

    # -- traversal ---------------------------------------------------------

    def _pack_traversal(self, entries, parent_row, gidx) -> Traversal:
        """Wave-schedule entries into [L, W] with a capped wave width.

        Waves wider than `wave_width` are chunked over several steps (their
        entries are independent, so any split is valid); narrow waves pad to
        W.  This keeps padding waste ~W/2 entries per wave while collapsing
        the sequential step count from len(entries) to ~len(waves).  W is a
        capped power of two and L is size-bucketed (_bucket_len) so only
        O(log n) compiled variants exist.  parent_row/gidx map an entry's
        parent to its arena row and a child id to its gather index (normal
        traversals use the row_map; the batched scan targets its scratch
        region)."""
        from examl_tpu.tree.topology import Tree
        raw = Tree.schedule_waves(entries)
        cap = self.wave_width
        W = min(_next_pow2(max((len(w) for w in raw), default=1)), cap)
        waves = [w[i:i + W] for w in raw for i in range(0, len(w), W)]
        # L rounds up into geometric buckets (<=25% padding waves, O(log n)
        # compiled variants -- see _bucket_len).  An empty traversal stays
        # empty (lax.scan over length 0) so fused traverse+evaluate/newton
        # calls on already-oriented CLVs cost no newview.
        L = _bucket_len(len(waves))
        C = self.num_branch_slots
        parent = np.full((L, W), self.scratch_row, dtype=np.int32)
        left = np.zeros((L, W), dtype=np.int32)
        right = np.zeros((L, W), dtype=np.int32)
        zl = np.ones((L, W, C), dtype=np.float64)
        zr = np.ones((L, W, C), dtype=np.float64)
        for li, wave in enumerate(waves):
            for wi, e in enumerate(wave):
                parent[li, wi] = parent_row(e)
                left[li, wi] = gidx(e.left)
                right[li, wi] = gidx(e.right)
                zl[li, wi, :] = _z_slots(e.zl, C)
                zr[li, wi, :] = _z_slots(e.zr, C)
        return Traversal(parent=jnp.asarray(parent), left=jnp.asarray(left),
                         right=jnp.asarray(right),
                         zl=jnp.asarray(zl, dtype=self.dtype),
                         zr=jnp.asarray(zr, dtype=self.dtype))

    def _traversal_arrays(self, entries: List[TraversalEntry]) -> Traversal:
        with self._phase("schedule"):
            tv = self._pack_traversal(
                entries, lambda e: self.row_map[e.parent], self._gidx)
        # Sequential dependent steps of the scan-tier program = the wave
        # count L: the launch-floor term the regime classifier uses.
        self._last_dispatch_ops = int(tv.parent.shape[0])
        return tv

    def _gidx(self, num: int) -> int:
        """gather_child index of a node: tips by code slot, inner nodes by
        ntips + current arena row (see kernels.gather_child)."""
        if num <= self.ntips:
            return num - 1
        return self.ntips + int(self.row_map[num])

    def set_site_rates(self, rates: np.ndarray) -> None:
        """Install per-site rate multipliers [B, lane] (PSR model).

        `rates` is the GLOBAL array (identical on every process in a
        multi-host job); placement shards the block axis like every
        other per-site tensor, and under selective loading only this
        process's block window is materialized on its devices."""
        assert self.psr
        self.site_rates = self._put_blocks(
            self._local_block_window(
                np.asarray(rates, dtype=self.dtype).reshape(
                    self.B, self.lane, 1)), lambda s: s.sites)

    def run_traversal(self, entries: List[TraversalEntry],
                      full: bool = False) -> None:
        """Recompute CLVs for `entries`: a full, fast-eligible
        `FlatTraversal` takes the cached-structure fast path; anything
        else (a TraversalEntry list, a partial traversal, PSR, -S) the
        scan tier."""
        if not len(entries):
            return
        obs.inc("engine.dispatch_count")
        obs.inc("engine.traversal_entries", len(entries))
        # Traffic bytes only: this path does not block on the result,
        # so its wall time would measure submission, not the traversal
        # — the windowed GB/s gauge is fed by the blocking fused paths.
        self._record_traffic(self._traversal_traffic_bytes(entries),
                             self._tier_for(entries, full))
        flat = entries if isinstance(entries, FlatTraversal) else None
        # No wait phase: this path does not block on its result.
        with self._dispatch("traverse", {"entries": len(entries),
                                         "full": bool(full)}):
            if flat is not None:
                if full and self._fast_eligible_flat(flat):
                    self._run_fast_flat(flat)
                    return
                entries = flat.to_entries()
            if self.save_memory:
                self._sev_begin(entries)
            tv = self._traversal_arrays(entries)
            buf, aux = self._state()
            with self._phase("launch"):
                buf, self.scaler = self._jit_traverse(
                    buf, self.scaler, aux, tv, self.models,
                    self.block_part, self.tips, self.site_rates)
            self._set_buf(buf)

    def _guard_first_call(self, fn, family: str = "program", key=None):
        """Wrap a freshly-jitted program so its FIRST invocation (= the
        compile) runs as a timed, event-emitting compile monitor: a
        pathological compile blocks in C++ with no Python-level recourse
        (observed round 4: the chunk program never returned), so after
        the compile deadline
        (EXAML_COMPILE_TIMEOUT, the CLI's --compile-timeout; default
        180 s) a daemon thread tells the user WHICH program family is
        stuck and which escape hatch pins the hardware-proven scan tier
        — through stderr AND the run info file (obs log sink), so the
        operator need not guess.  Compile happens in C++ with the GIL
        released, so the timer thread does run while the main thread is
        stuck.  Installed at every fast-program cache miss, so
        recompiles after an LRU eviction are guarded too.  The first
        call is counted and timed into the
        registry (engine.compile_count / engine.compile_seconds
        [.family]) and emits a `compile:<family>` span — a wedged
        compile leaves the span's unmatched "B" event as the trace's
        last line.  Around it the `first_call:<family>` span (timer
        `engine.first_call`) holds the whole first call: the
        observatory's prelower before and its analysis after.

        Under `--bank` (ops/bank.py) this watchdog is the LAST line of
        defense, not the first: every family compiles ahead of time in
        a killable subprocess with a HARD deadline, and main-process
        first calls run inside the bank phase as persistent-cache hits.
        The wrapper attributes each first call accordingly
        (engine.compile_count.bank_phase vs
        engine.first_calls.banked/unbanked) so the run artifacts prove
        where compile time was actually paid."""
        # "collectives": the cross-chip collectives in the compiled
        # program's own text, read once by the observatory (deep mode;
        # else unknown and never counted: the counter stays absent);
        # every dispatch then adds them to `engine.collectives` (a
        # one-device program holds none).  The executed count while
        # none sits in a loop: the row's `collectives_in_loops`.
        state = {"first": True, "collectives": 0}

        def call(*args):
            if not state["first"]:
                if state["collectives"]:
                    obs.inc("engine.collectives", state["collectives"])
                return fn(*args)
            state["first"] = False
            # The whole first call, prelower to the program table's row,
            # is one span with three children (obs/trace.py draws the
            # tree): `compile:<family>` and `engine.compile_seconds`
            # hold the jitted call ALONE; its trace and lowering land in
            # `first_call:<family>/lower` while the observatory is deep.
            with obs.span(f"first_call:{family}", cat="compile",
                          also="engine.first_call"):
                return first_call(*args)

        def first_call(*args):
            import os as _os
            import threading

            from examl_tpu.ops import bank

            try:
                limit = float(_os.environ.get("EXAML_COMPILE_TIMEOUT")
                              or 180.0)
            except ValueError:
                limit = 180.0
            done = threading.Event()
            # Program observatory (obs/programs.py): count persistent-
            # cache hits around the compile to attribute its source,
            # and trace the lowering BEFORE the dispatch donates its
            # buffers — the registry row's cost/memory analyses come
            # from AOT-compiling this trace (a cache deserialize when
            # the persistent cache is armed), never from re-dispatching.
            from examl_tpu.obs import programs as _programs
            cache_hits0 = _programs.xla_cache_hits()
            lowered = _programs.prelower(fn, args, family)

            def bark():
                if not done.wait(limit):
                    obs.inc("engine.watchdog_barks")
                    obs.log(
                        "EXAML: a device-program compile (program family "
                        f"'{family}') has taken >{limit:.0f}s — if this "
                        "never returns, rerun with --bank (ahead-of-time "
                        "banking kills wedged compiles and degrades to "
                        "the scan tier), or pin EXAML_FAST_TRAVERSAL=0 "
                        "(scan tier) or "
                        "EXAML_BATCH_SCAN=0 (sequential SPR scans), "
                        "depending on which program is compiling.")

            threading.Thread(target=bark, daemon=True).start()
            # Ledger bracketing mirrors the trace span: a wedged compile
            # leaves the unmatched "start" as the rank's last ledger
            # event, naming the guilty family in the merged timeline.
            obs.ledger_event("compile", family=family, status="start")
            # Histogram-carrying timer alongside the counter sum: one
            # pathological compile must be visible as a p99, not
            # averaged into compile_seconds.
            compiling = obs.span(f"compile:{family}", cat="compile",
                                 also=f"engine.compile_seconds.{family}")
            try:
                with compiling:
                    # Fault seam: `compile.hang` sleeps here (default
                    # 3600 s), making the first call indistinguishable
                    # from a wedged remote compile — the watchdog bark,
                    # bank deadline-kill and supervisor paths are all
                    # exercisable on CPU through this one line.
                    from examl_tpu.resilience import faults
                    faults.fire("compile.hang")
                    return fn(*args)
            finally:
                done.set()
                dt = compiling.elapsed
                obs.ledger_event("compile", family=family, status="end",
                                 seconds=round(dt, 3))
                obs.inc("engine.compile_count")
                obs.inc("engine.compile_seconds", dt)
                obs.inc(f"engine.compile_seconds.{family}", dt)
                if bank.in_bank_phase():
                    # Banked run, bank phase: the designed place for
                    # every first call (compile time lives here, off
                    # the search's critical path).
                    obs.inc("engine.compile_count.bank_phase")
                    obs.inc("engine.compile_seconds.bank_phase", dt)
                elif bank.active():
                    # Banked run, search phase: a banked family minting
                    # a new shape variant is expected (persistent-cache
                    # hit); an UNBANKED first call means the bank's
                    # enumeration missed a family — the acceptance
                    # counter for wedge immunity.  A family the bank
                    # ATTEMPTED but had to degrade is a separate case:
                    # scan-tier families have no escape hatch ("no
                    # fallback exists for the fallback tier itself"),
                    # so when their worker loses the compile deadline
                    # on a loaded host the run legitimately compiles
                    # them in-process — that is the watchdogged path
                    # the bank's own log promises, not an enumeration
                    # gap, and it must not trip the acceptance counter.
                    if bank.is_banked(family):
                        obs.inc("engine.first_calls.banked")
                    elif family in bank.degraded():
                        obs.inc("engine.first_calls.degraded_inprocess")
                        obs.inc("engine.first_calls."
                                f"degraded_inprocess.{family}")
                    elif bank.sharded_residual(family):
                        # Multi-process run AND the bank enumerated
                        # this family: its mesh-sharded variant can
                        # only first-compile here (workers cannot join
                        # the process group — ROADMAP §4).  This is the
                        # bank's DOCUMENTED residual wedge exposure,
                        # not an enumeration gap; a family the
                        # enumeration MISSED falls through to
                        # `unbanked`, the pure acceptance counter.
                        obs.inc("engine.first_calls.inprocess_sharded")
                        obs.inc("engine.first_calls."
                                f"inprocess_sharded.{family}")
                    else:
                        obs.inc("engine.first_calls.unbanked")
                        obs.inc(f"engine.first_calls.unbanked.{family}")
                row = _programs.record(
                    family, key if key is not None else family,
                    ("xla-cache"
                     if _programs.xla_cache_hits() > cache_hits0
                     else "fresh"),
                    dt, lowered=lowered)
                state["collectives"] = (row or {}).get("collective_total", 0)
                if state["collectives"]:
                    obs.inc("engine.collectives", state["collectives"])

        return call

    @staticmethod
    def _cache_family(key) -> str:
        """Program family of a shared-cache key: external builders prefix
        their keys with a string tag ("scan"/"thscan"/...); the
        engine's own chunk-profile keys are the "fast" family."""
        if isinstance(key, tuple) and key and isinstance(key[0], str):
            return key[0]
        return "fast"

    # -- shared program cache (LRU) -----------------------------------------
    # External program builders (search/batchscan.py, quartets_batch.py)
    # share _fast_jit_cache through these two helpers so they get the
    # same move_to_end-on-hit / trim-on-insert / compile-watchdog
    # discipline as the engine's own fast programs — without it a hot
    # scan program sits at the LRU-oldest slot and wave-profile churn
    # evicts it, and its recompile runs unguarded.

    def cache_get(self, key):
        fn = self._fast_jit_cache.get(key)
        if fn is not None:
            self._fast_jit_cache.move_to_end(key)
            obs.inc("engine.cache_hits")
        else:
            obs.inc("engine.cache_misses")
        return fn

    def cache_put(self, key, fn):
        # Guard, then export-wrap: an exported-bank hit serves the
        # dispatch from a deserialized executable (the guard — and the
        # compile it monitors — never fires); a miss runs the guarded
        # compile and serializes it for the next cold start.  The cache
        # key rides into the artifact signature: two programs with
        # identical avals but different static closures (chunk profile,
        # bucket pair) must never share an artifact.
        from examl_tpu.ops import export_bank
        from examl_tpu.resilience import memgov
        family = self._cache_family(key)
        if not memgov.admit_program(family, seam="engine.cache_put"):
            # Predicted peak exceeds the remaining budget: evict cold
            # cached executables and per-topology device caches BEFORE
            # the compile mints more device memory.  Counted
            # (mem.evictions) — never a silent crash, and the put
            # proceeds either way: eviction is the reaction, admission
            # never blocks a needed program.
            memgov.evict_engine(self)
        guarded = self._guard_first_call(fn, family, key=key)
        fn = export_bank.wrap(fn, guarded, family,
                              (key,) + self._export_identity,
                              exportable=self._exportable,
                              entry_meta={"ntips": self.ntips})
        self._fast_jit_cache[key] = fn
        while len(self._fast_jit_cache) > self._fast_jit_cache_cap:
            self._fast_jit_cache.popitem(last=False)
            obs.inc("engine.cache_evictions")
        return fn

    # -- engine state: dense CLV buffer or SEV pool -------------------------
    # Every device program takes (buf, scaler, aux): dense aux = (),
    # SEV aux = (slot_read, slot_write).  buf and scaler are donated; aux
    # is not (the engine keeps the slot maps across calls).

    def _sev_begin(self, entries: List[TraversalEntry]):
        """Update gap/cell bookkeeping for a traversal and sync device."""
        self.sev.update_for_entries(entries)
        self.sev.sync()

    def _state(self):
        if self.save_memory:
            if self.sev.pool is None:
                self.sev.sync()
            return self.sev.pool, (self.sev.slot_read, self.sev.slot_write)
        return self.clv, ()

    def _set_buf(self, buf) -> None:
        if self.save_memory:
            self.sev.pool = buf
        else:
            self.clv = buf

    def _gather(self, buf, aux, scaler, idx, tips):
        if self.save_memory:
            return kernels.gather_child_pooled(tips, buf, aux[0], scaler,
                                               idx, self.ntips)
        return kernels.gather_child(tips, buf, scaler, idx, self.ntips)

    def _traverse_kernel(self, buf, aux, scaler, tv, dm, block_part, tips,
                         sr):
        if self.save_memory:
            return kernels.traverse_pooled(dm, block_part, tips, buf,
                                           aux[0], aux[1], scaler, tv,
                                           self.scale_exp, self.ntips, sr)
        return kernels.traverse(dm, block_part, tips, buf, scaler, tv,
                                self.scale_exp, self.ntips, sr)

    def _traverse_only_impl(self, buf, scaler, aux, tv, dm, block_part,
                            tips, sr):
        return self._traverse_kernel(buf, aux, scaler, tv, dm, block_part,
                                     tips, sr)

    # -- fast full-traversal path (ops/fastpath.py) ------------------------

    def _install_row_map(self, st) -> None:
        self.row_map[:st.row_of.shape[0]] = st.row_of

    # -- cached schedule structures (flat fast path) -------------------------

    def sched_cache_invalidate(self) -> None:
        """Drop cached schedule structures (search commit seams call
        this through instance.invalidate_schedules after an SPR/NNI
        topology change or a checkpoint restore).  Purely hygiene +
        evidence: the topology-signature keys already guarantee a stale
        structure can never be served."""
        if self._sched_cache:
            obs.inc("engine.sched_cache.invalidate")
            self._sched_cache.clear()
        self._universal_tables.clear()
        self._grad_structs.clear()

    def trav_row_bytes(self) -> int:
        """Bytes of a CLV-arena row in the traversal program that runs
        (blocks x lanes x R x K x the arena's itemsize; a shard's under
        the mesh): what selects the one-entry tail
        (`fastpath.ONE_ENTRY_ROW_BYTES`).  0 where the universal
        interpreter may take the dispatch (forced, or novel profiles
        routed to it): it runs uniform floor-width steps only, so its
        layouts keep the 8-wide tail."""
        if not self.universal_off and (self.universal_force
                                       or self.route_novel_to_universal):
            return 0
        return (self.B * self.lane // self.site_shards * self.R * self.K
                * np.dtype(self.storage_dtype).itemsize)

    @property
    def site_shards(self) -> int:
        """How many ways the mesh cuts the site (block) axis; 1 without
        one."""
        return 1 if self.sharding is None else self.sharding.site_shards

    def _fast_structure(self, flat):
        from examl_tpu.ops import fastpath
        # Keyed by the row too: routing to the interpreter, switched on
        # after a structure was cached, changes the layout it needs.
        row_bytes = self.trav_row_bytes()
        key = (flat.topo_key, row_bytes)
        st = self._sched_cache.get(key)
        if st is not None:
            self._sched_cache.move_to_end(key)
            obs.inc("engine.sched_cache.hit")
            return st
        obs.inc("engine.sched_cache.miss")
        st = fastpath.build_structure(flat, self.ntips, row_bytes)
        assert st.max_write <= self.num_rows - 1, \
            (st.max_write, self.num_rows)
        if self.sharding is not None:
            st = st._replace(**self._replicated(
                {f: getattr(st, f)
                 for f in ("base", "lidx", "ridx", "lcode", "rcode")}))
        self._sched_cache[key] = st
        while len(self._sched_cache) > self._sched_cache_cap:
            self._sched_cache.popitem(last=False)
            obs.inc("engine.sched_cache.evictions")
        return st

    def _fast_eligible_flat(self, flat) -> bool:
        """The fast path relayouts the whole arena, so it requires a
        traversal covering every inner node (full=True callers after
        invalidate_all) and the GAMMA kernels (PSR keeps the scan path)."""
        return (not self.psr and not self.force_scan
                and self.fast_slack > 0 and flat.n == self.n_inner)

    def _note_fast_program(self, profile) -> None:
        """Publish the bounded chunk program's size gauges: unrolled
        blocks after coalescing, scan groups, and the per-traversal
        operation count (the launch-latency floor the bounded layout
        exists to shrink) — landing in `--metrics` snapshots.  Tagged
        per engine like the other engine gauges (_register_obs): two
        engines (a DNA+AA instance) must not overwrite each other's
        program size."""
        from examl_tpu.ops import fastpath
        un, sc, total = fastpath.profile_stats(profile)
        tag = "." + self._obs_tag
        obs.gauge("engine.program_chunks" + tag, un)
        obs.gauge("engine.scan_groups" + tag, sc)
        obs.gauge("engine.dispatches_per_traversal" + tag, un + sc)
        obs.gauge("engine.chunk_blocks_total" + tag, total)
        self._last_dispatch_ops = un + sc     # regime launch-floor term

    def _fast_fn_flat(self, profile, with_eval: bool):
        """Jitted chunk program over the PACKED structure + z arrays:
        each segment's window is sliced statically from the profile
        inside the trace (scan groups reshape theirs to [glen, step]),
        so a dispatch carries 7 array leaves total instead of 7 per
        chunk.  The key IS the BUCKETED segment profile (not raw
        per-chunk widths) — two topologies of similar shape mint the
        same key and share one compiled program, which is the point of
        width bucketing (tests/test_fastpath.py asserts the cache-hit
        counters).  Key leads with "fast": the program family of the
        bank/watchdog accounting."""
        key = ("fast", profile, "flat", with_eval)
        fn = self.cache_get(key)
        if fn is not None:
            return fn

        def impl(clv, scaler, base, lidx, ridx, lcode, rcode, zl, zr,
                 dm, block_part, tips):
            return self._run_segments_impl(
                dm, block_part, tips, clv, scaler, profile, base, lidx,
                ridx, lcode, rcode, zl, zr)

        def impl_eval(clv, scaler, base, lidx, ridx, lcode, rcode, zl,
                      zr, p_idx, q_idx, z, dm, block_part, weights,
                      tips):
            clv, scaler = self._run_segments_impl(
                dm, block_part, tips, clv, scaler, profile, base, lidx,
                ridx, lcode, rcode, zl, zr)
            lnl = kernels.root_log_likelihood(
                dm, block_part, weights, tips, clv, scaler, p_idx, q_idx,
                z, self.num_parts, self.scale_exp, self.ntips, None)
            return clv, scaler, lnl

        return self.cache_put(key, jax.jit(
            impl_eval if with_eval else impl, donate_argnums=(0, 1)))

    def _run_fast_flat(self, flat, p_num=None, q_num=None, z=None):
        """Fast full traversal (and optional fused root evaluation) from
        a FlatTraversal: cached structure + fresh z only.  The universal
        interpreter (ops/universal.py) takes the dispatch when forced or
        when novel-profile routing is on and no specialized program for
        this profile exists — same layout, same chunk arithmetic, but a
        topology-independent jit key."""
        from examl_tpu.ops import fastpath, universal
        with self._phase("schedule"):
            st = self._fast_structure(flat)
        self._last_universal = False
        if self._universal_take(st.profile, p_num is not None):
            try:
                return self._run_universal_flat(flat, st, p_num, q_num, z)
            except universal.UniversalIneligible:
                obs.inc("engine.universal_ineligible")
        with self._phase("schedule"):
            zl, zr = fastpath.refresh_z(
                st, flat, self.num_branch_slots, self.dtype,
                placement=self.sharding and self.sharding.replicated)
        self._note_fast_program(st.profile)
        # Write slots the program runs (padding and replays included)
        # beside the entries they hold, as engine.grad_slots.
        obs.inc("engine.trav_slots", fastpath.profile_slots(st.profile))
        obs.inc("engine.trav_live_slots", flat.n)
        if any(seg[0] == "e" for seg in st.profile) and \
                fastpath.tail_p_models(self.models.eign.shape[0],
                                       self.site_shards):
            obs.inc("engine.grouped_dispatches")   # its tail's P built first
        if p_num is None:
            fn = self._fast_fn_flat(st.profile, with_eval=False)
            with self._phase("launch"):
                self.clv, self.scaler = fn(
                    self.clv, self.scaler, st.base, st.lidx, st.ridx,
                    st.lcode, st.rcode, zl, zr, self.models,
                    self.block_part, self.tips)
            self._install_row_map(st)
            return None
        fn = self._fast_fn_flat(st.profile, with_eval=True)
        with self._phase("stage"):
            root = self._stage_root(st, p_num, q_num, z)
        with self._phase("launch"):
            self.clv, self.scaler, out = fn(
                self.clv, self.scaler, st.base, st.lidx, st.ridx, st.lcode,
                st.rcode, zl, zr, *root, self.models, self.block_part,
                self.weights, self.tips)
        self._install_row_map(st)
        with self._phase("wait"):
            return np.asarray(out)

    # -- universal interpreter tier (ops/universal.py) ----------------------
    # Topology-as-data: the bounded layout's packed arrays ship as
    # RUNTIME data into one compiled lax.scan whose body lax.switches
    # over the fixed (kind, width) alphabet.  The jit key is
    # ("universal", alphabet, table_bucket, slot_bucket, with_eval) — a
    # tiny closed family — so any topology runs through an
    # already-banked program with zero first-call compiles.  lnL is
    # bit-identical to the specialized chunk program by construction:
    # identical chunk sequence, identical `chunk_applier` arithmetic,
    # identical order (tests/test_universal.py pins it).

    def _universal_take(self, profile, with_eval: bool) -> bool:
        """Should this full-traversal dispatch run the interpreter?
        force > routing; routing diverts only profiles whose
        specialized program is not already compiled (an already-hot
        profile keeps its ~1.3x-faster specialized dispatch)."""
        if self.universal_off:
            return False
        if self.universal_force:
            return True
        if not self.route_novel_to_universal:
            return False
        return ("fast", profile, "flat", with_eval) \
            not in self._fast_jit_cache

    def _universal_akey(self):
        """(min_width, cap): the layout-knob identity a table's step
        splitting and a program's switch alphabet must agree on."""
        from examl_tpu.ops import universal
        return universal.alphabet_key()

    def _universal_entry(self, profile, base_h, idx_h, cache_key=None):
        """Descriptor-table cache entry: the host table plus lazily
        padded per-bucket copies of the descriptor and index arrays
        (content-keyed by topology signature when available; an entry
        built under a different alphabet — env-retuned knobs, a grown
        arena — rebuilds, since class ids index the alphabet)."""
        from examl_tpu.ops import universal
        akey = self._universal_akey()
        if cache_key is not None:
            ent = self._universal_tables.get(cache_key)
            if ent is not None and ent["akey"] == akey:
                self._universal_tables.move_to_end(cache_key)
                return ent
        ent = {"table": universal.build_table(profile, base_h, akey),
               "idx": idx_h, "desc": {}, "pads": {}, "akey": akey}
        if cache_key is not None:
            self._universal_tables[cache_key] = ent
            while len(self._universal_tables) > self._universal_tables_cap:
                self._universal_tables.popitem(last=False)
        return ent

    def _universal_minted(self, akey, with_eval: bool):
        """The (table_bucket, slot_bucket) pairs whose interpreter
        program is ACTUALLY resident in the jit cache right now —
        derived from the cache keys rather than shadow state, so every
        invalidation path (LRU eviction, an env knob retune changing
        the alphabet key) keeps
        `pick_pads` honest for free."""
        return {(k[2], k[3]) for k in self._fast_jit_cache
                if isinstance(k, tuple) and len(k) == 5
                and k[0] == "universal" and k[1] == akey
                and k[4] == with_eval}

    def _universal_args(self, ent, with_eval: bool):
        """(npad, ppad, desc, idx) for one dispatch: buckets picked
        from the compiled-program set (replay padding is idempotent,
        so any larger compiled bucket serves correctly).  The padded
        descriptor and index arrays are memoized per bucket on the
        entry DEVICE-RESIDENT — like FastStructure's packed arrays, a
        cached serving dispatch ships only the two fresh z arrays."""
        from examl_tpu.ops import universal
        table = ent["table"]
        npad, ppad = universal.pick_pads(
            self._universal_minted(ent["akey"], with_eval),
            table.n_chunks, table.slots)
        desc = ent["desc"].get(npad)
        if desc is None:
            desc = ent["desc"][npad] = jax.device_put(
                list(universal.pad_table(table, npad)))
        idx = ent["pads"].get(ppad)
        if idx is None:
            idx = ent["pads"][ppad] = jax.device_put(
                [universal.pad_slots(np.asarray(a), ppad)
                 for a in ent["idx"]])
        return npad, ppad, desc, idx

    def _run_universal_flat(self, flat, st, p_num=None, q_num=None,
                            z=None):
        """Interpreter dispatch from a cached FastStructure: descriptor
        table + packed index copies are cached per topology signature;
        only the z arrays (padded to the slot bucket) are fresh."""
        from examl_tpu.ops import fastpath
        with_eval = p_num is not None
        with self._phase("schedule"):
            ent = self._universal_entry(
                st.profile, np.asarray(st.base),
                (st.lidx, st.ridx, st.lcode, st.rcode),
                cache_key=flat.topo_key)
            npad, ppad, desc, idx = self._universal_args(ent, with_eval)
            zl, zr = fastpath.refresh_z(st, flat, self.num_branch_slots,
                                        self.dtype, total_slots=ppad)
        return self._universal_dispatch(st, desc, idx, zl, zr, npad,
                                        ppad, p_num, q_num, z)

    def _universal_dispatch(self, sched, desc, idx, zl, zr, npad: int,
                            ppad: int, p_num, q_num, z):
        """Ship the padded table + packed layout as data through the
        bucketed interpreter program and install the layout's row map
        (identical post-state to the specialized dispatch)."""
        with_eval = p_num is not None
        obs.inc("engine.universal_dispatches")
        tag = "." + self._obs_tag
        obs.gauge("engine.universal_steps" + tag, npad)
        obs.gauge("engine.universal_slots" + tag, ppad)
        # The interpreter is ONE device op, but its scan walks npad
        # dependent steps — the launch-floor term the regime classifier
        # uses (same accounting as the scan tier's wave count).
        self._last_dispatch_ops = npad
        self._last_universal = True
        fn = self._universal_fn(npad, ppad, with_eval)
        cls, slot, cbase = desc
        li, ri, lc, rc = idx
        if not with_eval:
            with self._phase("launch"):
                self.clv, self.scaler = fn(
                    self.clv, self.scaler, cls, slot, cbase, li, ri, lc,
                    rc, zl, zr, self.models, self.block_part, self.tips)
            self._install_row_map(sched)
            return None
        with self._phase("stage"):
            root = self._stage_root(sched, p_num, q_num, z)
        with self._phase("launch"):
            self.clv, self.scaler, out = fn(
                self.clv, self.scaler, cls, slot, cbase, li, ri, lc, rc,
                zl, zr, *root, self.models, self.block_part, self.weights,
                self.tips)
        self._install_row_map(sched)
        with self._phase("wait"):
            return np.asarray(out)

    def _universal_fn(self, npad: int, ppad: int, with_eval: bool):
        """The ONE jitted interpreter program per (alphabet, buckets,
        with_eval) — the `("universal", ...)` cache family, with its
        own compile-watchdog label via `_cache_family`: the portability
        rung below the chunk tier (chunk -> universal -> scan)."""
        from examl_tpu.ops import fastpath, universal
        akey = self._universal_akey()
        key = ("universal", akey, npad, ppad, with_eval)
        fn = self.cache_get(key)
        if fn is not None:
            return fn
        alpha = universal.alphabet(akey)

        def run(clv, scaler, cls, slot, cbase, lidx, ridx, lcode, rcode,
                zl, zr, dm, block_part, tips):
            apply = fastpath.chunk_applier(dm, block_part, tips,
                                           self.scale_exp,
                                           self.fast_precision,
                                           self.site_shards)
            return universal.run_universal(
                alpha, cls, slot, cbase, lidx, ridx, lcode, rcode, zl,
                zr, clv, scaler, apply.values)

        def impl_eval(clv, scaler, cls, slot, cbase, lidx, ridx, lcode,
                      rcode, zl, zr, p_idx, q_idx, zv, dm, block_part,
                      weights, tips):
            clv, scaler = run(clv, scaler, cls, slot, cbase, lidx, ridx,
                              lcode, rcode, zl, zr, dm, block_part, tips)
            lnl = kernels.root_log_likelihood(
                dm, block_part, weights, tips, clv, scaler, p_idx, q_idx,
                zv, self.num_parts, self.scale_exp, self.ntips, None)
            return clv, scaler, lnl

        return self.cache_put(key, jax.jit(
            impl_eval if with_eval else run, donate_argnums=(0, 1)))

    def _run_segments_impl(self, dm, block_part, tips, clv, scaler,
                           profile, base, lidx, ridx, lcode, rcode, zl,
                           zr):
        """Bounded-program execution over the packed 7-leaf layout
        (fastpath.run_segments): O(#segments) program ops — unrolled
        hot chunks plus lax.scan long-tail groups."""
        from examl_tpu.ops import fastpath
        apply = fastpath.chunk_applier(dm, block_part, tips,
                                       self.scale_exp, self.fast_precision,
                                       self.site_shards)
        return fastpath.run_segments(profile, base, lidx, ridx, lcode,
                                     rcode, zl, zr, clv, scaler, apply)

    # -- batched SPR radius scan (search/batchscan.py) ----------------------

    def ensure_scan_rows(self, n: int) -> int:
        """Grow the arena by a scratch scan region of >= n rows (pow2
        bucketed so reallocation and recompilation stay O(log n) over a
        search); returns the region's base row.  The fast path and the
        normal traversals never touch rows above their original arena, so
        the region is free scratch between scan dispatches."""
        if self.save_memory:
            base = self.sev.ensure_scan_rows(n)
            if self.sev.num_rows > self.num_rows:
                # The scaler stays DENSE under -S ([rows, B, lane] int32,
                # ~1/64 the bytes of a CLV row): it must grow with the
                # pool's scan rows or traverse_pooled's scatter silently
                # drops scan-row scaler writes (JAX OOB scatter = drop)
                # and candidate lnLs lose their scale counts.
                grow = self.sev.num_rows - self.num_rows
                self.scaler = self._grow_rows(self.scaler, grow,
                                              self.sharding and
                                              self.sharding.scaler)
                self.num_rows = self.sev.num_rows
            return base
        if not hasattr(self, "_scan_base"):
            self._scan_base = self.num_rows
            self._scan_cap = 0
        if n > self._scan_cap:
            grow = _next_pow2(n) - self._scan_cap
            self.clv = self._grow_rows(self.clv, grow,
                                       self.sharding and self.sharding.clv)
            self.scaler = self._grow_rows(self.scaler, grow,
                                          self.sharding and
                                          self.sharding.scaler)
            self._scan_cap += grow
            self.num_rows += grow
            obs.gauge("engine.scan_rows", self._scan_cap)
        return self._scan_base

    @staticmethod
    def _grow_rows(arr, grow: int, sharding):
        """Append `grow` zero rows, keeping the array committed to its
        sharding: the pad is placed BEFORE the concatenate — eagerly
        concatenating a committed global array with an uncommitted
        process-local one is undefined in a multi-process run, and the
        row axis is never the sharded axis so concat preserves the
        operands' placement."""
        pad = jnp.zeros((grow,) + arr.shape[1:], arr.dtype)
        if sharding is not None:
            pad = jax.device_put(pad, sharding)
        return jnp.concatenate([arr, pad])

    def _scan_traversal_arrays(self, down_entries, up_entries, base: int):
        """Wave-schedule the orientation fixes AND the uppass entries into
        ONE set of Traversal arrays (one traverse, one dispatch).  Slot
        ids are encoded above the node-number range so Tree.schedule_waves
        resolves node->node, node->slot, and slot->slot dependencies
        uniformly; down entries write normal arena rows through the row
        map, up entries write the scan region."""
        from examl_tpu.tree.topology import TraversalEntry

        SLOT0 = 2 * self.ntips + 1

        def ref_id(ref):
            kind, v = ref
            return SLOT0 + v if kind == "slot" else v

        pseudo = list(down_entries) + [
            TraversalEntry(SLOT0 + e.slot, ref_id(e.left),
                           ref_id(e.right), e.zl, e.zr)
            for e in up_entries]

        def parent_row(e) -> int:
            if e.parent >= SLOT0:
                return base + (e.parent - SLOT0)
            return self.row_map[e.parent]

        def gidx(ident: int) -> int:
            if ident >= SLOT0:
                return self.ntips + base + (ident - SLOT0)
            return self._gidx(ident)

        return self._pack_traversal(pseudo, parent_row, gidx)

    def _scan_dispatch_arrays(self, plan, base: int, T: int):
        """Shared padding/chunk plumbing for the scan programs: gather
        indices for candidates and their uppass rows, padded to a pow2
        number of T-wide chunks (O(log n) compiled variants)."""
        N = len(plan.candidates)
        n_chunks = max(1, _next_pow2((N + T - 1) // T))
        npad = n_chunks * T
        qg = np.zeros(npad, np.int32)
        upg = np.zeros(npad, np.int32)
        for i, c in enumerate(plan.candidates):
            qg[i] = self._gidx(c.q_num)
            upg[i] = self.ntips + base + c.up_slot
        return n_chunks, npad, qg, upg

    def batched_scan(self, plan) -> np.ndarray:
        """Uppass traversal + all candidate insertion scores in one
        dispatch; returns this engine's per-candidate lnL sums [N].
        Works on the dense arena and on -S SEV pools alike (gap bits for
        the orientation fixes update first; the scan region is carved
        from the pool by ensure_scan_rows)."""
        from examl_tpu.search import batchscan

        obs.inc("engine.dispatch_count")
        obs.inc("engine.traversal_entries",
                len(plan.down_entries) + len(plan.up_entries))
        self._record_traffic(self._scan_plan_traffic_bytes(plan), "scan")
        if self.save_memory:
            self.sev.update_for_entries(plan.down_entries)
        base = self.ensure_scan_rows(len(plan.up_entries))
        T = batchscan.CAND_CHUNK
        C = self.num_branch_slots
        with self._dispatch("spr_scan",
                            {"candidates": len(plan.candidates)}):
            with self._phase("schedule"):
                tv = self._scan_traversal_arrays(plan.down_entries,
                                                 plan.up_entries, base)
                n_chunks, npad, qg, upg = self._scan_dispatch_arrays(
                    plan, base, T)
                zc = np.ones((npad, C), dtype=np.float64)
                for i, c in enumerate(plan.candidates):
                    zc[i] = _z_slots(c.z, C)
            fn = batchscan.scan_program(self, n_chunks)
            buf, aux = self._state()
            with self._phase("stage"):
                cand = (self._stage(qg.reshape(n_chunks, T)),
                        self._stage(upg.reshape(n_chunks, T)),
                        self._stage(zc.reshape(n_chunks, T, C), self.dtype),
                        self._stage(self._gidx(plan.s_num), jnp.int32),
                        self._stage(_z_slots(plan.zp, C), self.dtype))
            with self._phase("launch"):
                buf, self.scaler, lnls = fn(
                    buf, self.scaler, aux, tv, *cand, self.models,
                    self.block_part, self.weights, self.tips,
                    self.site_rates)
            self._set_buf(buf)
            with self._phase("wait"):
                return np.asarray(lnls)[:len(plan.candidates)]

    def batched_thorough(self, plan):
        """Thorough-arm companion of `batched_scan`: triangle Newton,
        localSmooth, and scoring per candidate in one dispatch; returns
        (lnls [N], smoothed branch triplets [N, 3]).  Works on the dense
        arena and on -S SEV pools (sharded or not) alike, like the lazy
        arm."""
        from examl_tpu.search import batchscan

        obs.inc("engine.dispatch_count")
        obs.inc("engine.traversal_entries",
                len(plan.down_entries) + len(plan.up_entries))
        self._record_traffic(self._scan_plan_traffic_bytes(plan), "scan")
        if self.save_memory:
            self.sev.update_for_entries(plan.down_entries)
        base = self.ensure_scan_rows(len(plan.up_entries))
        T = batchscan.TH_CHUNK
        N = len(plan.candidates)
        with self._dispatch("spr_thorough", {"candidates": N}):
            with self._phase("schedule"):
                tv = self._scan_traversal_arrays(plan.down_entries,
                                                 plan.up_entries, base)
                n_chunks, npad, qg, upg = self._scan_dispatch_arrays(
                    plan, base, T)
                zq0 = np.full(npad,
                              float(np.asarray(plan.zp, np.float64)[0]))
                for i, c in enumerate(plan.candidates):
                    zq0[i] = float(np.asarray(c.q_slot.z, np.float64)[0])
            fn = batchscan.thorough_program(self, n_chunks)
            buf, aux = self._state()
            with self._phase("stage"):
                cand = (self._stage(qg.reshape(n_chunks, T)),
                        self._stage(upg.reshape(n_chunks, T)),
                        self._stage(zq0.reshape(n_chunks, T), self.dtype),
                        self._stage(self._gidx(plan.s_num), jnp.int32))
            with self._phase("launch"):
                buf, self.scaler, lnls, es = fn(
                    buf, self.scaler, aux, tv, *cand, self.models,
                    self.block_part, self.weights, self.tips,
                    self.site_rates)
            self._set_buf(buf)
            with self._phase("wait"):
                return np.asarray(lnls)[:N], np.asarray(es)[:N]

    # -- evaluation --------------------------------------------------------

    def _evaluate_impl(self, buf, scaler, aux, p_idx, q_idx, z, dm,
                       block_part, weights, tips, sr):
        xp, sp = self._gather(buf, aux, scaler, p_idx, tips)
        xq, sq = self._gather(buf, aux, scaler, q_idx, tips)
        return kernels.root_log_likelihood_from(
            dm, block_part, weights, xp, sp, xq, sq, z, self.num_parts,
            self.scale_exp, sr, axis_name=self._axis_name)

    def evaluate(self, p_num: int, q_num: int, z: Sequence[float]) -> np.ndarray:
        """Per-partition lnL [M] at branch (p,q); CLVs must be current."""
        obs.inc("engine.dispatch_count")
        with self._dispatch("evaluate"):
            with self._phase("stage"):
                root = self._stage_root(None, p_num, q_num, z)
            buf, aux = self._state()
            with self._phase("launch"):
                out = self._jit_evaluate(buf, self.scaler, aux, *root,
                                         self.models, self.block_part,
                                         self.weights, self.tips,
                                         self.site_rates)
            with self._phase("wait"):
                return np.asarray(out)

    # -- fused single-dispatch entry points ---------------------------------
    # Traversal + root evaluation (resp. + sumtable + the whole NR loop) in
    # ONE device program: the reference pays one reduction round-trip per
    # evaluateGeneric and one per NR iteration (SURVEY §3.2-3.3); here each
    # search step is a single dispatch.

    def _trav_eval_impl(self, buf, scaler, aux, tv, p_idx, q_idx, z, dm,
                        block_part, weights, tips, sr):
        buf, scaler = self._traverse_kernel(buf, aux, scaler, tv, dm,
                                            block_part, tips, sr)
        lnl = self._evaluate_impl(buf, scaler, aux, p_idx, q_idx, z, dm,
                                  block_part, weights, tips, sr)
        return buf, scaler, lnl

    def traverse_evaluate(self, entries: List[TraversalEntry], p_num: int,
                          q_num: int, z: Sequence[float],
                          full: bool = False) -> np.ndarray:
        obs.inc("engine.dispatch_count")
        obs.inc("engine.traversal_entries", len(entries))
        nbytes = self._traversal_traffic_bytes(entries)
        compiles0 = obs.registry().counter("engine.compile_count")
        with self._dispatch("trav_eval", {"entries": len(entries),
                                          "full": bool(full)}) as disp:
            out = self._traverse_evaluate(entries, p_num, q_num, z, full)
        # This path BLOCKS (np.asarray on the lnL), so the elapsed wall
        # covers the whole traversal: full traversals feed the windowed
        # achieved-GB/s gauge (partial ones — a few entries around one
        # branch — only account bytes; their wall is dominated by the
        # root evaluation and would read as launch floor).  A dispatch
        # whose span contained a first-call compile keeps its histogram
        # observation but is excluded from the bandwidth window.
        self._record_traffic(
            nbytes, self._tier_for(entries, full),
            wall_s=disp.elapsed if full and len(entries) else None,
            window=(obs.registry().counter("engine.compile_count")
                    == compiles0))
        return out

    def _traverse_evaluate(self, entries: List[TraversalEntry], p_num: int,
                           q_num: int, z: Sequence[float],
                           full: bool = False) -> np.ndarray:
        if isinstance(entries, FlatTraversal):
            flat = entries
            if full and flat.n and self._fast_eligible_flat(flat):
                return self._run_fast_flat(flat, p_num, q_num, z)
            entries = flat.to_entries()
        if self.save_memory:
            self._sev_begin(entries)
        tv = self._traversal_arrays(entries)
        with self._phase("stage"):
            root = self._stage_root(None, p_num, q_num, z)
        buf, aux = self._state()
        with self._phase("launch"):
            buf, self.scaler, out = self._jit_trav_eval(
                buf, self.scaler, aux, tv, *root, self.models,
                self.block_part, self.weights, self.tips, self.site_rates)
        self._set_buf(buf)
        with self._phase("wait"):
            return np.asarray(out)

    def _gidx_of(self, sched, num: int) -> int:
        """gather_child index of a node against a schedule's NEW layout
        WITHOUT installing it: a kernel failure between schedule build
        and dispatch must not leave self.row_map pointing at rows the
        arena does not hold."""
        if num <= self.ntips:
            return num - 1
        return self.ntips + sched.row_of[num]

    def _newton_impl(self, buf, scaler, aux, tv, p_idx, q_idx, z0,
                     maxiters, conv, dm, block_part, weights, tips, sr):
        buf, scaler = self._traverse_kernel(buf, aux, scaler, tv, dm,
                                            block_part, tips, sr)
        xp, _ = self._gather(buf, aux, scaler, p_idx, tips)
        xq, _ = self._gather(buf, aux, scaler, q_idx, tips)
        st = kernels.sumtable(dm, block_part, xp, xq)
        z = kernels.newton_raphson_branch(dm, block_part, weights, st, z0,
                                          maxiters, conv,
                                          self.num_branch_slots, sr,
                                          axis_name=self._axis_name)
        return buf, scaler, z

    def newton_branch(self, entries: List[TraversalEntry], p_num: int,
                      q_num: int, z0: np.ndarray, maxiter: int,
                      conv_mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Fused traversal + sumtable + NR-to-convergence; returns new z [C]."""
        obs.inc("engine.dispatch_count")
        obs.inc("engine.newton_dispatches")
        obs.inc("engine.traversal_entries", len(entries))
        self._record_traffic(self._traversal_traffic_bytes(entries),
                             "scan")
        if self.save_memory:
            self._sev_begin(entries)
        C = self.num_branch_slots
        if conv_mask is None:
            conv_mask = np.zeros(C, dtype=bool)
        with self._dispatch("newton", {"entries": len(entries),
                                       "maxiter": int(maxiter)}):
            tv = self._traversal_arrays(entries)
            buf, aux = self._state()
            with self._phase("stage"):
                branch = (self._stage(self._gidx(p_num), jnp.int32),
                          self._stage(self._gidx(q_num), jnp.int32),
                          self._stage(z0),
                          jnp.full(C, maxiter, dtype=jnp.int32),
                          self._stage(conv_mask))
            with self._phase("launch"):
                buf, self.scaler, z = self._jit_newton(
                    buf, self.scaler, aux, tv, *branch, self.models,
                    self.block_part, self.weights, self.tips,
                    self.site_rates)
            self._set_buf(buf)
            with self._phase("wait"):
                return np.asarray(z, dtype=np.float64)

    # -- PSR rate-grid scan -------------------------------------------------

    def _rate_scan_impl(self, tips, tv, p_idx, q_idx, z, grid, dm,
                        block_part):
        """Full traversal + per-site-per-candidate root lnL for one grid
        chunk [B, lane, G]; scratch CLVs live only inside this program."""
        G = grid.shape[2]
        clv = jnp.zeros((self.num_rows, self.B, self.lane, G, self.K),
                        dtype=self.dtype)
        scaler = jnp.zeros((self.num_rows, self.B, self.lane),
                           dtype=jnp.int32)
        clv, scaler = kernels.traverse(dm, block_part, tips, clv, scaler,
                                       tv, self.scale_exp, self.ntips,
                                       grid)
        return kernels.per_rate_site_lnls(dm, block_part, tips, clv,
                                          scaler, p_idx, q_idx, z, grid,
                                          self.scale_exp, self.ntips)

    def rate_scan(self, entries: List[TraversalEntry], p_num: int,
                  q_num: int, z: Sequence[float],
                  grid: np.ndarray) -> np.ndarray:
        """Per-site lnL under each candidate rate: grid [B, lane, G] ->
        [B, lane, G].  entries must be a FULL traversal for branch (p,q).

        TPU-native replacement for the reference's per-site
        `evaluatePartialGeneric` scan (SURVEY §7.3(5)).
        """
        assert self.psr
        obs.inc("engine.dispatch_count")
        obs.inc("engine.traversal_entries", len(entries))
        with self._dispatch("rate_scan", {"grid": int(grid.shape[-1])}):
            tv = self._traversal_arrays(entries)
            with self._phase("stage"):
                root = self._stage_root(None, p_num, q_num, z)
                # `grid` is GLOBAL [B, lane, G] (every process builds the
                # same one from the host-global patrat); a selective-
                # loading process contributes only its block window to
                # the sharded device array.
                obs.inc("engine.staged_arrays")
                grid_dev = self._put_blocks(
                    self._local_block_window(
                        np.asarray(grid, dtype=self.dtype)),
                    lambda s: s.sites)
            with self._phase("launch"):
                out = self._jit_rate_scan(self.tips, tv, *root, grid_dev,
                                          self.models, self.block_part)
            with self._phase("wait"):
                if self.sharding is not None and jax.process_count() > 1:
                    # Multi-host: the per-site scan result is block-
                    # sharded across processes; the host-side PSR
                    # crawl/categorization needs the global view on EVERY
                    # process (deterministic, so all processes categorize
                    # identically — the reference gathers to rank 0 and
                    # scatters back instead, `optimizeModel.c:2135-2254`;
                    # an allgather of the same payload replaces both
                    # legs).
                    from jax.experimental import multihost_utils
                    return np.asarray(
                        multihost_utils.process_allgather(out, tiled=True))
                return np.asarray(out)

    # -- branch derivatives ------------------------------------------------

    def _sumtable_impl(self, buf, scaler, aux, p_idx, q_idx, dm,
                       block_part, tips):
        xp, _ = self._gather(buf, aux, scaler, p_idx, tips)
        xq, _ = self._gather(buf, aux, scaler, q_idx, tips)
        return kernels.sumtable(dm, block_part, xp, xq)

    def _derivs_impl(self, st, z, dm, block_part, weights, sr):
        return kernels.nr_derivatives(dm, block_part, weights,
                                      st, z, self.num_branch_slots, sr,
                                      axis_name=self._axis_name)

    def make_sumtable(self, p_num: int, q_num: int) -> jax.Array:
        obs.inc("engine.dispatch_count")
        buf, aux = self._state()
        # No wait phase: the table stays on the device.
        with self._dispatch("sumtable"):
            with self._phase("stage"):
                p_idx = self._stage(self._gidx(p_num), jnp.int32)
                q_idx = self._stage(self._gidx(q_num), jnp.int32)
            with self._phase("launch"):
                return self._jit_sumtable(buf, self.scaler, aux, p_idx,
                                          q_idx, self.models,
                                          self.block_part, self.tips)

    def branch_derivatives(self, st: jax.Array, z: Sequence[float]):
        obs.inc("engine.dispatch_count")
        with self._dispatch("derivs"):
            with self._phase("stage"):
                zv = self._stage(_z_slots(z, self.num_branch_slots),
                                 self.dtype)
            with self._phase("launch"):
                d1, d2 = self._jit_derivs(st, zv, self.models,
                                          self.block_part, self.weights,
                                          self.site_rates)
            with self._phase("wait"):
                return np.asarray(d1), np.asarray(d2)

    # -- whole-tree analytic gradients (ops/gradient.py) --------------------
    # One pre-order (outroot) pass over the reversed wave schedule plus
    # one batched edge-derivative contraction gives (d1, d2) for ALL
    # 2n-3 branches in a single dispatch — the O(n)->O(1) replacement
    # for the per-branch sumtable+Newton round trips that dominate
    # smoothTree/treeEvaluate on large trees (ROADMAP §5).

    def grad_eligible(self) -> bool:
        """The gradient pass runs on the dense CLV arena (any tier's
        post-order output); -S SEV pools keep the per-branch path."""
        return not self.save_memory

    def grad_wave_cap(self) -> int:
        """Entries an outroot step of this engine's gradient program may
        hold (`gradient.wave_cap`), from the bytes a row of the outroot
        arena holds THERE: the arena's blocks x lanes, a shard's under
        the mesh, where `_grad_impl` runs inside `shard_map`, times
        R x K values of the compute dtype."""
        from examl_tpu.ops import gradient
        return gradient.wave_cap(
            self.B * self.lane // self.site_shards * self.R * self.K
            * np.dtype(self.dtype).itemsize)

    def _grad_structure(self, flat):
        from examl_tpu.ops import gradient
        gs = self._grad_structs.get(flat.topo_key)
        if gs is not None:
            self._grad_structs.move_to_end(flat.topo_key)
            return gs
        gs = gradient.build_structure(flat, self.grad_wave_cap())
        self._grad_structs[flat.topo_key] = gs
        while len(self._grad_structs) > self._grad_structs_cap:
            self._grad_structs.popitem(last=False)
        return gs

    def _grad_impl(self, clv, scaler, p_row, q_row, p_gidx, q_gidx, tvp,
                   ex_rows, ey_gidx, ez, dm, block_part, weights, tips,
                   sr):
        """Traced gradient program: outroot-arena init at the root edge
        (out(p) = D(q), out(q) = D(p)), the reverse-wave sibling-combine
        pass, then the chunked all-edges derivative contraction.  The
        outroot arena lives only inside this program; clv/scaler are
        read-only (NOT donated — the engine keeps serving them)."""
        from examl_tpu.ops import gradient
        out = jnp.zeros((2 * self.ntips - 1,) + clv.shape[1:],
                        dtype=self.dtype)
        dq, _ = kernels.gather_child(tips, clv, scaler, q_gidx, self.ntips)
        dp, _ = kernels.gather_child(tips, clv, scaler, p_gidx, self.ntips)
        out = out.at[p_row].set(dq.astype(out.dtype))
        out = out.at[q_row].set(dp.astype(out.dtype))
        out = kernels.outroot_pass(dm, block_part, tips, clv, scaler, out,
                                   tvp, self.scale_exp, self.ntips, sr)
        return gradient.edge_gradients(
            dm, block_part, weights, tips, clv, scaler, out, ex_rows,
            ey_gidx, ez, self.num_branch_slots, self.ntips, sr)

    def _grad_program(self):
        """The jitted gradient pass.  Without a mesh, `_grad_impl` as it
        is.  Site-sharded, the same body under `jax.shard_map` over the
        site axis: every chip runs the one-chip program on its own
        blocks (its shard of `clv`, an outroot arena born at its shard's
        size, row gathers and scatters that never leave the chip) and
        the two [E, C] site sums meet in ONE `psum` after the chunk
        loop: ExaML's derivative Allreduce
        (`makenewzGenericSpecial.c:1241-1248`), once a pass.  Left to
        GSPMD the segment sums would reduce across chips inside every
        chunk body.  The mapped function keeps the name: traces and the
        benchmark find the program as `jit__grad_impl`."""
        if self.sharding is None:
            return jax.jit(self._grad_impl)
        from examl_tpu.parallel.sharding import SITE_AXIS
        v = self._site_spec_vocab()
        rep, arena = v["rep"], v["scaler"]

        def _grad_impl(*args):
            d1, d2 = self._grad_impl(*args)
            both = jax.lax.psum(jnp.stack([d1, d2]), SITE_AXIS)
            return both[0], both[1]

        return v["wrap"](
            _grad_impl,
            (arena, arena, rep, rep, rep, rep,
             kernels.OutrootTraversal(
                 *(rep,) * len(kernels.OutrootTraversal._fields)),
             rep, rep, rep, v["models"], v["blocks"], v["sites"],
             v["tips"], v["sr"]),
            (rep, rep))

    def whole_tree_gradients(self, flat, root_z):
        """(d1, d2) [E, C]: lnL gradient and curvature w.r.t. lz = log z
        for every branch of the FULL traversal `flat`, in ONE dispatch.

        Edge order: edge 0 is the traversal's root edge; edges 1+2i /
        2+2i are entry i's left / right child branches (flat order).
        PRECONDITION: the CLV arena is current for `flat` (a
        `run_traversal(flat, full=True)` — any tier — just ran);
        `root_z` is the root edge's branch vector.

        The jit key is shape-only, ("grad", steps, width, chunks), and
        topology ships as runtime data.  The chunks are
        ceil(E / GRAD_CHUNK), a constant of the engine; the width
        follows the row's bytes (`grad_wave_cap`): 1 from 0.5 MiB a row,
        where the steps are n (ONE program an engine, whatever the
        tree), 8 under it with a `bucket_len` of the packed waves (a
        few programs by topology).
        `engine.grad_slots` counts the slots both loops ran and
        `engine.grad_live_slots` those that held an entry or an edge.
        """
        from examl_tpu.ops import gradient
        from examl_tpu.ops.kernels import OutrootTraversal
        if not self.grad_eligible():
            raise RuntimeError("whole-tree gradients need the dense CLV "
                               "arena (-S SEV pools keep the per-branch "
                               "Newton path)")
        obs.inc("engine.dispatch_count")
        obs.inc("engine.grad_pass_dispatches")
        compiles0 = obs.registry().counter("engine.compile_count")
        # The dispatch's wall also feeds the `engine.grad_pass` timer,
        # whose count is the gradient passes made.
        with self._dispatch("grad_pass", also="engine.grad_pass") as disp:
            with self._phase("schedule"):
                gs = self._grad_structure(flat)
                pre, ex_rows, ey_gidx, ez = gradient.grad_arrays(
                    gs, flat, self.row_map, self.num_branch_slots, root_z)
            key = ("grad", gs.n_steps, gs.wave_w, gs.n_chunks)
            fn = self.cache_get(key)
            if fn is None:
                fn = self.cache_put(key, self._grad_program())
            p, q = gs.roots
            up_row, lrow, rrow, lg, rg, zu, zl, zr = pre
            with self._phase("stage"):
                edges = (
                    self._stage(p - 1, jnp.int32),
                    self._stage(q - 1, jnp.int32),
                    self._stage(self._gidx(p), jnp.int32),
                    self._stage(self._gidx(q), jnp.int32),
                    OutrootTraversal(
                        up_row=self._stage(up_row), lrow=self._stage(lrow),
                        rrow=self._stage(rrow), left=self._stage(lg),
                        right=self._stage(rg),
                        zu=self._stage(zu, self.dtype),
                        zl=self._stage(zl, self.dtype),
                        zr=self._stage(zr, self.dtype)),
                    self._stage(ex_rows), self._stage(ey_gidx),
                    self._stage(ez, self.dtype))
            with self._phase("launch"):
                d1, d2 = fn(self.clv, self.scaler, *edges, self.models,
                            self.block_part, self.weights, self.tips,
                            self.site_rates)
            # Blocking by contract: the host-side batched Newton update
            # consumes d1/d2 — this sync IS the gradient measurement
            # (the registered seam, like the trav-eval family).
            with self._phase("wait"):
                d1 = np.asarray(d1, dtype=np.float64)
                d2 = np.asarray(d2, dtype=np.float64)
        itemsize = np.dtype(self.storage_dtype).itemsize
        tip_children = int((np.asarray(flat.left) <= self.ntips).sum()
                           + (np.asarray(flat.right) <= self.ntips).sum())
        nbytes = _traffic.bytes_per_grad_pass(
            gs.n, tip_children, gs.n_edges, self._patterns_true, self.R,
            self.K, itemsize)
        # The gradient program is one device op whose scan walks
        # n_steps + n_chunks dependent steps — the launch-floor term.
        self._last_dispatch_ops = gs.n_steps + gs.n_chunks
        obs.inc("engine.grad_slots", gs.n_steps * gs.wave_w
                + gs.n_chunks * gradient.GRAD_CHUNK)
        obs.inc("engine.grad_live_slots", gs.n + gs.n_edges)
        self._record_traffic(
            nbytes, "grad", wall_s=disp.elapsed,
            window=(obs.registry().counter("engine.compile_count")
                    == compiles0))
        return d1[:gs.n_edges], d2[:gs.n_edges]


