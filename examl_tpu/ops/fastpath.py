"""Fast full-traversal path: case-split wave chunks, MXU-shaped dots,
with a BOUNDED program: width bucketing, chunk coalescing and a scanned
long tail keep the compiled chunk program at O(log n) operations.

The TPU-native re-architecture of the reference's newview inner loops
(ExaML `newviewGenericSpecial.c:1263-1497` dispatch over TIP_TIP /
TIP_INNER / INNER_INNER kernels, and the MIC backend's tip-product
precompute `umpX`, `mic_native_dna.c:132-165`), driven by what the MXU
and XLA actually reward:

* Waves of independent entries are split by tip case and executed as
  chunks, each chunk one batched dot over its padded width.
* The per-rate P application is folded into ONE block-diagonal
  [R*K, R*K] contraction per child — 4x fewer MXU row-streams than R
  separate [K, K] dots at identical numerics (the blocks are exact).
* Tip children never materialize CLVs: a per-chunk `ump[code, r, a] =
  sum_k P[r,a,k] * tipvec[code,k]` table is contracted against one-hot
  code vectors — tip state never touches HBM at CLV width.
* Parents of one chunk occupy CONTIGUOUS rows of a wave-ordered CLV
  arena, so every write is a `dynamic_update_slice` that XLA performs
  in place — the `.at[].set` scatter inside scan was measured to copy
  the whole CLV buffer every step (half the runtime).
* Inner children's rows and scalers are read through
  `kernels.take_rows`: `arena[idx]` while a row is at most 128 blocks
  wide, one dynamic slice a row above that, where the v5e compiler
  would cut the gather into pieces by slicing the whole arena — a copy
  of the arena a chunk (PERF.md §6, PR 32 and 34).  The values are
  `arena[idx]`'s bit for bit either way.

Program-size discipline (the BEAGLE lesson: library-scale phylogenetics
lives or dies on operation scheduling cost, not FLOPs).  A naive
schedule is one unrolled block per (wave, kind) chunk — ~1,500 blocks
at 50k taxa, which costs XLA tens of minutes of CPU compile and pays a
per-block launch-latency floor every traversal.  Three coordinated
moves bound it:

1. WIDTH BUCKETING — chunk widths quantize to a geometric ladder with a
   floor (`MIN_WIDTH`, default 8) and a cap (`CHUNK_CAP`, default 1024;
   wider chunks split into cap-width pieces).  The `(kind, width)`
   alphabet is therefore small and FIXED, so profiles — and with them
   jit keys and bank program families — are shared across topologies of
   similar shape instead of being unique per tree.
2. CHUNK COALESCING — runs of small same-kind chunks from adjacent
   waves merge into one padded chunk when a vectorized dependency check
   proves no merged entry reads a row the merged chunk itself writes
   (entries within a wave are independent, so any split is valid; the
   cross-wave merge is valid exactly when the check passes).  Arena
   rows are assigned in final emission order, so merged writes stay
   contiguous `dynamic_update_slice`s.
3. SCANNED LONG TAIL — maximal runs of chunks with an identical
   bucketed step shape (same `(kind, width)` for head runs produced by
   cap-splitting; same per-wave `((kind, width), ...)` signature for
   the narrow tail waves, absent kinds normalized to width-`MIN_WIDTH`
   padding sub-chunks) collapse into ONE `lax.scan` over stacked chunk
   arrays.  Scan lengths bucket geometrically; padding steps REPLAY the
   run's final step, which is idempotent (a chunk reads only rows
   written strictly before it and rewrites its own rows with identical
   values), so no scratch arithmetic leaks into real rows.
   Where a CLV-arena row holds `ONE_ENTRY_ROW_BYTES` or more (a shard's
   row under a mesh), the narrow tail from where its first scan group
   would start runs instead as ONE `lax.scan` of one entry a step, in
   wave order: no width-8 slots, no pad sub-chunks for absent kinds
   (the root-side waves hold one to eight entries, so an 8-wide step
   per kind was 4-7 padding slots a real row).  A step's kind is data
   (a tip child's row index is -1 and reads no row), the compute is a
   `lax.switch` over the three kinds of `chunk_applier`'s kernel at
   W = 1, and the one-row arena write stays outside it; the length
   buckets (`bucket_len`) and replays repeat the final entry.  With
   more than one model the tail's transition matrices, all its
   entries', are built before its scan and each step takes its own
   (`tail_p_models`).

The resulting `profile` is a tuple of segments — `("u", kind, width)`
for an unrolled block, `("s", glen, ((kind, width), ...))` for a scan
group, `("e", L)` for the one-entry tail — and IS the jit key: program
length is O(#segments) ~ O(log n)
(measured: 50k taxa, 1,511 raw chunks -> ~70 unrolled blocks + ~35 scan
groups), and execution order equals wave order chunk for chunk, so the
bounded program's lnL is bit-identical to the unbounded unroll.

`build_structure` (vectorized, from a `FlatTraversal`) produces the
layout; the engine caches the immutable structure per topology
signature and refreshes only the packed z arrays per call
(`refresh_z`).  `structure_chunks` + `run_chunks` execute the same
layout unrolled: the reference the tests hold `run_segments` to.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from examl_tpu.ops import kernels
from examl_tpu.utils import bucket_len, next_pow2

# -- bounded-layout knobs ----------------------------------------------------
# The ladder alphabet is {MIN_WIDTH, 2*MIN_WIDTH, ..., CHUNK_CAP}: small and
# fixed, so two topologies of similar shape produce the SAME profile and
# share one compiled program (and one bank family / persistent-cache entry).

# All three are powers of two with MIN_WIDTH <= TAIL_WIDTH, CHUNK_CAP.
MIN_WIDTH = 8        # width floor
CHUNK_CAP = 1024     # width cap; wider chunks split
TAIL_WIDTH = 64      # waves whose chunks all bucket <= this join the
                     # scanned tail
MIN_SCAN = 4         # shorter runs stay unrolled (replay padding would
                     # dominate them)

# Bytes of a CLV-arena row (blocks x lanes x R x K x itemsize, a shard's
# under a mesh) from which the scanned tail runs ONE entry a step (the
# ("e", L) segment) instead of 8-wide per-kind chunks: the narrowest row
# the chip probe measured (v5e, PERF.md section 6, PR 41).  A traversal
# call on the benchmark's 140-taxon tree, today's tail -> one entry, ms:
# 3.215 -> 2.253 at 1 MiB, 8.995 -> 4.943 at 2, 17.34 -> 10.39 at 4,
# 48.48 -> 22.18 at 8 (DNA), 25.40 -> 15.80 at 5 MiB (protein, the row
# read as two half-rows); on the 49-taxon tree 1.993 -> 1.044 at 1 MiB,
# 24.63 -> 5.04 at 8.  One entry wins by half the tail's time at 1 MiB
# (in place, in the 16,384-DNA cells, by a fifth: about 21 us a step);
# under it not measured, so the 8-wide tail stays there.  An engine that
# may hand a dispatch to the universal interpreter, whose steps are all
# floor-width, plans the 8-wide tail whatever its row
# (`Engine.trav_row_bytes`).
ONE_ENTRY_ROW_BYTES = 1 << 20


def slack_rows(ntips: int) -> int:
    """Arena slack rows the bounded layout needs: headroom for padded
    chunk writes past the real rows AND the dedicated pad region the
    scanned tail's width-MIN_WIDTH padding sub-chunks write (base = n;
    every build still asserts max_write against the arena)."""
    floor = 2 * MIN_WIDTH
    return min(max(64, floor), max(next_pow2(ntips), floor))


class FastChunk(NamedTuple):
    """One case-homogeneous batch of independent newview entries.

    kind: 0 = tip-tip, 1 = tip-inner (tip is always the left child),
    2 = inner-inner.  Arrays are device-resident, width-padded.
    """
    kind: int
    width: int
    base: jax.Array         # scalar int32: first arena row written
    lidx: jax.Array         # [W] arena row of left child (kind 2)
    ridx: jax.Array         # [W] arena row of right child (kind 1, 2)
    lcode: jax.Array        # [W] 0-based tip index of left child (kind 0, 1)
    rcode: jax.Array        # [W] 0-based tip index of right child (kind 0)
    zl: jax.Array           # [W, C]
    zr: jax.Array           # [W, C]


class FastStructure(NamedTuple):
    """The IMMUTABLE half of a fast-path schedule: everything that is a
    function of topology + traversal root only (segment profile, chunk
    widths/bases, packed child index/code arrays, the arena row map) —
    cacheable across the branch-length-only traversals that dominate
    model optimization and repeated full evaluations.  The cheap
    DYNAMIC half (packed per-slot zl/zr) is rebuilt per call by
    `refresh_z` through the stored entry->slot map.

    Child/code arrays are stored PACKED along one padded slot axis
    (device-resident, transferred once); the jitted program slices each
    segment's window statically from the profile (scan groups reshape
    theirs to [glen, step_width]), so a cached dispatch ships only the
    two fresh z arrays to the device.

    `profile` is the BUCKETED segment tuple (see module docstring), not
    raw per-chunk widths — it is the engine's jit-cache key, so two
    different topologies with the same bucketed profile share one
    compiled program (tests/test_fastpath.py proves the cache hit)."""
    profile: Tuple[tuple, ...]  # segment tuple: the jit key
    base: jax.Array             # [n_chunks] int32: first arena row written
    lidx: jax.Array             # [P] packed left-child arena rows
    ridx: jax.Array             # [P]
    lcode: jax.Array            # [P] packed 0-based tip indices
    rcode: jax.Array            # [P]
    row_of: np.ndarray          # [2*ntips-1] node number -> row (-1 tips)
    z_src: np.ndarray           # [P] flat-entry index per slot (-1 pad;
                                #     replay slots repeat their source)
    z_swap: np.ndarray          # [P] slot's children were canonicalized
    num_rows: int
    max_write: int


# -- profile helpers ---------------------------------------------------------


def iter_profile_chunks(profile):
    """Yield (kind, width) for every chunk in execution order, scan
    groups expanded step-major (incl. replay steps); a one-entry step's
    kind is data, not profile: (None, 1)."""
    for seg in profile:
        if seg[0] == "u":
            yield seg[1], seg[2]
        elif seg[0] == "e":
            for _ in range(seg[1]):
                yield None, 1
        else:
            _, glen, subs = seg
            for _ in range(glen):
                for k, w in subs:
                    yield k, w


def profile_stats(profile) -> Tuple[int, int, int]:
    """(unrolled_blocks, scan_groups, total_chunks) of a profile (a
    one-entry tail is a scan group) —
    unrolled_blocks + scan_groups is the program's operation count (the
    launch-latency floor per traversal); total_chunks counts every
    chunk incl. scan steps (the raw work-unit count)."""
    un = sum(1 for s in profile if s[0] == "u")
    sc = sum(1 for s in profile if s[0] != "u")
    total = sum(1 for _ in iter_profile_chunks(profile))
    return un, sc, total


def profile_slots(profile) -> int:
    """Total packed slot count P of a profile."""
    return sum(w for _, w in iter_profile_chunks(profile))


# -- layout planning ---------------------------------------------------------


class _Chunk:
    """Planner-internal chunk record (host only)."""

    __slots__ = ("kind", "W", "spans", "real", "pad", "replay_of",
                 "entry", "base", "slot")

    def __init__(self, kind, W, spans, pad=False, replay_of=None,
                 entry=False):
        self.kind = kind
        self.W = W
        self.spans = spans          # [(lo, hi)] into sorted-entry order
        self.real = sum(hi - lo for lo, hi in spans)
        self.pad = pad              # writes only slack rows
        self.replay_of = replay_of  # index into the final chunk list
        self.entry = entry          # a step of the one-entry tail
        self.base = -1
        self.slot = -1


class _Layout(NamedTuple):
    profile: Tuple[tuple, ...]
    chunks: List[_Chunk]        # final execution order (incl. pads/replays)
    P: int                      # total packed slots
    max_write: int


def _bucket_w(s: int, mw: int) -> int:
    return max(mw, next_pow2(s))


def _plan_layout(kinds: np.ndarray, sizes: np.ndarray, gwave: np.ndarray,
                 starts: np.ndarray, child_key: np.ndarray,
                 n: int, one_entry: bool = False) -> _Layout:
    """Plan the chunk/segment layout from the (wave, kind)-sorted group
    table.  `child_key[g]` is the max (wave*3+kind) sort key over group
    g's inner children's defining entries (-1 when all children are
    tips/external) — the vectorized dependency oracle for coalescing.
    `one_entry`: the narrow tail waves from where today's first tail
    scan group starts run as ONE ("e", L) segment, an entry a step."""
    G = len(kinds)
    mw, cap, tailw = MIN_WIDTH, CHUNK_CAP, TAIL_WIDTH

    # -- 1. coalescing: merge a small group into the newest earlier
    # same-kind group when every inner child of the candidate was
    # computed strictly before the target's position (original sort
    # keys upper-bound post-merge positions, so the check is
    # conservative-safe) and the merged chunk stays small.
    class _Rec:
        __slots__ = ("kind", "wave", "size", "spans", "key")

        def __init__(self, g):
            self.kind = int(kinds[g])
            self.wave = int(gwave[g])
            self.size = int(sizes[g])
            self.spans = [(int(starts[g]), int(starts[g] + sizes[g]))]
            self.key = self.wave * 3 + self.kind

    recs: List[_Rec] = []
    open_of: Dict[int, _Rec] = {}
    for g in range(G):
        k = int(kinds[g])
        t = open_of.get(k)
        if (t is not None and t.size + int(sizes[g]) <= tailw
                and int(child_key[g]) < t.key):
            t.size += int(sizes[g])
            t.spans.append((int(starts[g]), int(starts[g] + sizes[g])))
            continue
        r = _Rec(g)
        recs.append(r)
        open_of[k] = r

    # -- 2. per-wave emission: head waves cap-split into ladder pieces,
    # tail waves normalize to a per-wave signature with width-mw padding
    # sub-chunks for absent (previously seen) kinds.
    by_wave: Dict[int, List[_Rec]] = {}
    for r in recs:
        by_wave.setdefault(r.wave, []).append(r)

    def split_spans(spans, take):
        """Cut `take` entries off the front of a span list."""
        out, rest = [], []
        need = take
        for lo, hi in spans:
            if need <= 0:
                rest.append((lo, hi))
            elif hi - lo <= need:
                out.append((lo, hi))
                need -= hi - lo
            else:
                out.append((lo, lo + need))
                rest.append((lo + need, hi))
                need = 0
        return out, rest

    stream: List[tuple] = []    # ("h", [chunk]) | ("t", sig, [chunks])
    seen = set()
    for wave in sorted(by_wave):
        wrecs = sorted(by_wave[wave], key=lambda r: r.kind)
        tail = all(_bucket_w(r.size, mw) <= tailw for r in wrecs)
        if tail:
            step = []
            have = {r.kind: r for r in wrecs}
            for k in (0, 1, 2):
                r = have.get(k)
                if r is not None:
                    step.append(_Chunk(k, _bucket_w(r.size, mw), r.spans))
                elif k in (1, 2) and k in seen:
                    step.append(_Chunk(k, mw, [], pad=True))
            seen.update(have)
            sig = tuple((c.kind, c.W) for c in step)
            stream.append(("t", sig, step))
        else:
            out = []
            for r in wrecs:
                seen.add(r.kind)
                spans, size = r.spans, r.size
                while size > cap:
                    head, spans = split_spans(spans, cap)
                    out.append(_Chunk(r.kind, cap, head))
                    size -= cap
                out.append(_Chunk(r.kind, _bucket_w(size, mw), spans))
            stream.append(("h", out))

    # -- 3. segmentation: maximal runs of an identical step shape become
    # one lax.scan; scan lengths bucket geometrically with replay
    # padding (idempotent re-execution of the final step).
    profile: List[tuple] = []
    chunks: List[_Chunk] = []

    def emit_run(sig, steps):
        glen = len(steps)
        if glen < MIN_SCAN:
            for step in steps:
                for c in step:
                    if not c.pad:       # unrolled pads are pure waste
                        profile.append(("u", c.kind, c.W))
                        chunks.append(c)
            return
        blen = bucket_len(glen)
        profile.append(("s", blen, sig))
        for step in steps:
            chunks.extend(step)
        ns = len(sig)
        last = len(chunks) - ns
        for _ in range(blen - glen):
            for j in range(ns):
                src = last + j
                chunks.append(_Chunk(chunks[src].kind, chunks[src].W,
                                     chunks[src].spans,
                                     pad=chunks[src].pad,
                                     replay_of=src))

    def emit_entries(tail):
        # One entry a step, in wave order (any order inside a chunk:
        # its entries are independent); replays repeat the final entry.
        steps = [_Chunk(c.kind, 1, [(i, i + 1)], entry=True)
                 for c in tail for lo, hi in c.spans for i in range(lo, hi)]
        blen = bucket_len(len(steps))
        profile.append(("e", blen))
        chunks.extend(steps)
        last = len(chunks) - 1
        chunks.extend(_Chunk(chunks[last].kind, 1, chunks[last].spans,
                             replay_of=last, entry=True)
                      for _ in range(blen - len(steps)))

    run_sig: Optional[tuple] = None
    run_steps: List[List[_Chunk]] = []
    run_tail = True             # the run holds tail waves only
    entries: Optional[List[_Chunk]] = None  # the one-entry tail, gathering

    def flush():
        nonlocal run_sig, run_steps, run_tail
        if run_steps:
            emit_run(run_sig, run_steps)
        run_sig, run_steps, run_tail = None, [], True

    for item in stream:
        if entries is not None:
            if item[0] == "t":
                entries.extend(c for c in item[2] if not c.pad)
                continue
            emit_entries(entries)
            entries = None
        if item[0] == "h":
            for c in item[1]:
                sig = ((c.kind, c.W),)
                if sig != run_sig:
                    flush()
                    run_sig = sig
                run_steps.append([c])
                run_tail = False
        else:
            _, sig, step = item
            if sig != run_sig:
                flush()
                run_sig = sig
            run_steps.append(step)
            if one_entry and run_tail and len(run_steps) == MIN_SCAN:
                # Where today's first tail scan group starts, the rest
                # of the narrow tail runs one entry a step.
                entries = [c for st in run_steps for c in st if not c.pad]
                run_sig, run_steps = None, []
    flush()
    if entries is not None:
        emit_entries(entries)

    return _finish_layout(tuple(profile), chunks, n)


def _finish_layout(profile, chunks, n: int) -> _Layout:
    """Assign arena rows (final emission order; pads write the slack
    region at row n, replays rewrite their source rows) and packed slot
    offsets; compute max_write for the engine's arena-capacity check."""
    row = 0
    slot = 0
    max_write = 0
    for c in chunks:
        c.slot = slot
        slot += c.W
        if c.replay_of is not None:
            c.base = chunks[c.replay_of].base
        elif c.pad:
            c.base = n
        else:
            c.base = row
            row += c.real
        max_write = max(max_write, c.base + c.W)
    assert row == n, (row, n)
    return _Layout(profile=profile, chunks=chunks, P=slot,
                   max_write=max_write)


def _layout_from_arrays(wave_id, el, er, lt, rt, child_nodes_key, n,
                        one_entry: bool = False):
    """Shared planner front-end: group the (wave, kind)-sorted entries
    and plan.  Returns (layout, order, skey-derived group table pieces)
    where `order` is the (wave, kind) stable sort permutation."""
    if n == 0:
        return (_Layout(profile=(), chunks=[], P=0, max_write=0),
                np.empty(0, np.int64))
    kind = 2 - (lt.astype(np.int64) + rt.astype(np.int64))
    skey_all = wave_id * 3 + kind
    order = np.argsort(skey_all, kind="stable")
    skey = skey_all[order]
    starts = np.flatnonzero(np.r_[True, skey[1:] != skey[:-1]])
    sizes = np.diff(np.r_[starts, n])
    kinds = (skey[starts] % 3).astype(np.int64)
    gwave = (skey[starts] // 3).astype(np.int64)
    # Dependency oracle: per sorted entry, the max sort key over its
    # inner children's defining entries (tips/external -> -1), reduced
    # per group.
    ck = np.maximum(child_nodes_key[el[order]],
                    child_nodes_key[er[order]])
    child_key = (np.maximum.reduceat(ck, starts) if n
                 else np.empty(0, np.int64))
    layout = _plan_layout(kinds, sizes, gwave, starts, child_key, n,
                          one_entry)
    return layout, order


def _pack_structure(layout: _Layout, order, el, er, lt, rt, swap, parent,
                    row_map_size: int):
    """Fill the packed per-slot arrays from a layout: a scatter per real
    chunk span (vectorized over entries), then slot-window copies for
    the replay chunks.  Returns host arrays."""
    n = order.shape[0]
    P = layout.P
    # Final entry order: concatenation of real-chunk spans (emission
    # order) — rows 0..n-1 in exactly this order.
    spans = [(lo, hi) for c in layout.chunks if c.replay_of is None
             for (lo, hi) in c.spans]
    if spans:
        pos = np.concatenate([np.arange(lo, hi) for lo, hi in spans])
    else:
        pos = np.empty(0, np.int64)
    assert pos.shape[0] == n
    final = order[pos]                  # indices into the ORIGINAL entries
    row_of = np.full(row_map_size, -1, dtype=np.int64)
    row_of[parent[final]] = np.arange(n)
    # Destination slot of each final-order entry.
    dst = np.empty(n, np.int64)
    off = 0
    for c in layout.chunks:
        if c.replay_of is not None:
            continue
        dst[off:off + c.real] = c.slot + np.arange(c.real)
        off += c.real
    el_f = el[final]
    er_f = er[final]
    lt_f = lt[final] | rt[final]        # post-swap: left is tip (kind 0/1)
    rt_f = lt[final] & rt[final]        # post-swap: right is tip (kind 0)
    # Every inner child must be defined by some entry in the traversal:
    # a -1 row would silently gather the scratch row (the loud
    # replacement for the old per-entry builder's KeyError on partial
    # entry lists, which the fast builders do not support).
    if (((~lt_f) & (row_of[el_f] < 0))
            | ((~rt_f) & (row_of[er_f] < 0))).any():
        raise KeyError("traversal entries reference inner children no "
                       "entry computes (partial entry lists are not "
                       "supported by the fast-path schedule builders)")
    lidx = np.zeros(P, np.int32)
    ridx = np.zeros(P, np.int32)
    lcode = np.zeros(P, np.int32)
    rcode = np.zeros(P, np.int32)
    z_src = np.full(P, -1, np.int64)
    z_swap = np.zeros(P, bool)
    # A tip child of a one-entry step reads no row: -1 marks it, and
    # the step's kind is the count of its children's rows (run_segments).
    entry = [c for c in layout.chunks if c.entry]
    es = np.asarray([c.slot for c in entry], np.int64)
    no_row = np.zeros(P, np.int32)
    no_row[es] = -1
    lidx[dst] = np.where(lt_f, no_row[dst], row_of[el_f])
    ridx[dst] = np.where(rt_f, no_row[dst], row_of[er_f])
    lcode[dst] = np.where(lt_f, el_f - 1, 0)
    rcode[dst] = np.where(rt_f, er_f - 1, 0)
    z_src[dst] = final
    z_swap[dst] = swap[final]
    for c in layout.chunks:             # replay steps copy their source
        if c.replay_of is None:
            continue
        s = layout.chunks[c.replay_of].slot
        for arr in (lidx, ridx, lcode, rcode, z_src, z_swap):
            arr[c.slot:c.slot + c.W] = arr[s:s + c.W]
    # Wave order is an execution order one entry at a time: a one-entry
    # step reads only rows that earlier steps wrote.
    assert (np.maximum(lidx[es], ridx[es])
            < np.asarray([c.base for c in entry], np.int64)).all()
    base = np.asarray([c.base for c in layout.chunks], np.int32)
    return row_of, base, lidx, ridx, lcode, rcode, z_src, z_swap, dst


def build_structure(flat, ntips: int, row_bytes: int = 0) -> FastStructure:
    """Vectorized schedule-structure build from a FlatTraversal: numpy
    sort/scatter over the whole traversal (this is what makes a
    120k-taxon schedule build array-rate) into the bounded chunk layout
    of the module docstring.  `row_bytes`, the bytes of a CLV-arena row
    in the program that runs, selects the one-entry tail from
    `ONE_ENTRY_ROW_BYTES`."""
    n = flat.n
    left = flat.left
    right = flat.right
    wave_id = np.repeat(np.arange(flat.wave_sizes.shape[0], dtype=np.int64),
                        flat.wave_sizes)
    lt = left <= ntips
    rt = right <= ntips
    swap = (~lt) & rt                     # canonicalize: tip child left
    el = np.where(swap, right, left)
    er = np.where(swap, left, right)
    kind = 2 - ((left <= ntips).astype(np.int64)
                + (right <= ntips).astype(np.int64))
    node_key = np.full(2 * ntips - 1, -1, dtype=np.int64)
    node_key[flat.parent] = wave_id * 3 + kind
    layout, order = _layout_from_arrays(
        wave_id, el, er, lt, rt, node_key, n,
        row_bytes >= ONE_ENTRY_ROW_BYTES)
    (row_of, base, lidx, ridx, lcode, rcode, z_src, z_swap,
     _dst) = _pack_structure(layout, order, el, er, lt, rt, swap,
                             flat.parent, 2 * ntips - 1)
    dev = jax.device_put([base, lidx, ridx, lcode, rcode])
    return FastStructure(profile=layout.profile, base=dev[0], lidx=dev[1],
                         ridx=dev[2], lcode=dev[3], rcode=dev[4],
                         row_of=row_of, z_src=z_src, z_swap=z_swap,
                         num_rows=n, max_write=layout.max_write)


def refresh_z(st: FastStructure, flat, num_slots: int, dtype,
              total_slots: Optional[int] = None, placement=None):
    """The DYNAMIC half of a cached schedule: permute the traversal's
    branch-length vectors into packed chunk-slot order (canonical swap
    applied; padding slots at z=1, replay slots repeating their source
    entry's z) — pure numpy fancy indexing, the only per-call host work
    on a schedule-cache hit.  `total_slots` (>= the structure's packed
    slot count) pads the result with z=1 rows for the universal
    interpreter's bucketed slot axis (ops/universal.py); the padding
    rows are never read.  `placement` is the sharding the two arrays
    are born with (a mesh's replicated one; the default device
    without)."""
    zl_f = flat.zl
    zr_f = flat.zr
    if zl_f.shape[1] != num_slots:
        from examl_tpu.utils import z_slots
        zl_f = np.stack([z_slots(z, num_slots) for z in zl_f])
        zr_f = np.stack([z_slots(z, num_slots) for z in zr_f])
    P = st.z_src.shape[0]
    Pout = P if total_slots is None else total_slots
    assert Pout >= P, (Pout, P)
    ok = st.z_src >= 0
    src = st.z_src[ok]
    sw = st.z_swap[ok, None]
    zl = np.ones((Pout, num_slots))
    zr = np.ones((Pout, num_slots))
    zl[:P][ok] = np.where(sw, zr_f[src], zl_f[src])
    zr[:P][ok] = np.where(sw, zl_f[src], zr_f[src])
    return jax.device_put([np.asarray(zl, dtype), np.asarray(zr, dtype)],
                          placement)


def structure_chunks(st: FastStructure, zl, zr) -> Tuple[FastChunk, ...]:
    """Materialized per-chunk list of a structure and its `refresh_z`
    arrays, in execution order (includes the replay/padding chunks of
    scan groups, so `run_chunks` over it is bit-identical to the
    segment program).  The engine's jitted programs use the packed
    arrays instead; this feeds the unrolled reference executor."""
    base_h, li, ri, lc, rc, zl, zr = (np.asarray(a) for a in (
        st.base, st.lidx, st.ridx, st.lcode, st.rcode, zl, zr))
    views = []
    metas = []
    off = cidx = 0
    for kind, W in iter_profile_chunks(st.profile):
        if kind is None:        # one-entry step: its children's rows
            kind = int(li[off] >= 0) + int(ri[off] >= 0)
        views += [li[off:off + W], ri[off:off + W],
                  lc[off:off + W], rc[off:off + W],
                  zl[off:off + W], zr[off:off + W]]
        metas.append((kind, W, np.int32(base_h[cidx])))
        off += W
        cidx += 1
    dev = iter(jax.device_put(
        [m[2] for m in metas] + views))
    bases = [next(dev) for _ in metas]
    return tuple(
        FastChunk(kind, W, b, next(dev), next(dev), next(dev),
                  next(dev), next(dev), next(dev))
        for (kind, W, _), b in zip(metas, bases))


# -- execution ---------------------------------------------------------------


def tail_p_models(M: int, site_shards: int) -> int:
    """How many models' transition matrices a chunk program's one-entry
    tail builds before its scan (`chunk_applier`'s `tail_p`): all M
    where there are more than one and no mesh cuts the block axis, else
    0 (each step builds its own)."""
    return M if M > 1 and site_shards == 1 else 0


def chunk_applier(models: kernels.DeviceModels, block_part: jax.Array,
                  tips: kernels.TipState, scale_exp: int, precision,
                  site_shards: int = 1):
    """The single-chunk kernel body (traced): P-build + child
    contractions + product + rescale + contiguous arena write.  Shared
    by the unrolled blocks, the lax.scan group bodies, and the
    reference `run_chunks` loop, so every execution strategy performs
    the identical arithmetic.  `site_shards`: how many ways a mesh cuts
    the block axis the program sees whole (GSPMD).

    `apply.tail_p` builds the transition matrices of a one-entry tail's
    every entry ahead of its scan (`run_segments`), whose steps then
    take their own by `values(..., pl, pr)`; None where
    `tail_p_models` is 0: with one model a step builds its R matrices
    in a few small dots, and a mesh's programs stay as they were.  With
    M models a step builds M x R of them, 232 batched 20 x 20 x 20
    products a child for 58 protein genes, which the v5e runs one small
    matrix at a time; the same einsum over all the tail's entries at
    once took 3.0 ms off that cell's 38.4 ms traversal call (PERF.md
    section 6)."""
    M = models.eign.shape[0]
    C = tips.table.shape[0]
    cdt = tips.table.dtype        # COMPUTE dtype; the arena may store
    R = models.gamma_rates.shape[1]
    eyeR = jnp.eye(R, dtype=cdt)  # narrower (bf16 tier, EXAML_CLV_DTYPE)
    HI = jax.lax.Precision.HIGHEST
    minlik, two_e, _ = kernels.scale_constants(cdt, scale_exp)

    def take(arena, idx):
        """Rows `idx` of an arena: `take_rows` for a chunk; a one-entry
        step's one row by a dynamic slice, which hands the dot the
        arena's own layout.  The v5e keeps a K = 4 arena's K
        second-minor, so the dot reads R x K in place; a K = 20 arena's
        row comes by `take_row`, which keeps the compiler from copying
        the whole arena for it."""
        if idx.shape[0] > 1:
            return kernels.take_rows(arena, idx)
        if arena.ndim == 5 and arena.shape[4] > arena.shape[3] \
                and arena.shape[3] % 2 == 0:
            return kernels.take_row(arena, idx[0], site_shards)
        return jax.lax.dynamic_slice_in_dim(arena, idx[0], 1)

    def tip_child(p, code, B, RK):
        # ump[w,m,c,(r a)] = sum_k tipvec[c,k] P[w,m,r,a,k]; contracted
        # against exact one-hot code vectors (MIC umpX generalization).
        W = code.shape[0]
        ump = jnp.einsum("ck,wmrak->wmcra", tips.table, p, precision=HI)
        ump = ump.reshape(W, M, C, RK)[:, block_part]       # [W,B,C,RK]
        oh = jax.nn.one_hot(tips.codes[code], C, dtype=cdt)
        return jax.lax.dot_general(oh, ump,
                                   (((3,), (2,)), ((0, 1), (0, 1))),
                                   precision=precision)

    def inner_child(p, idx, clv, B, lane, RK):
        # block-diagonal (r,k)->(r,a) contraction: exact same arithmetic
        # as per-rate P application, one MXU-friendly [RK,RK] dot.  The
        # rows come by index (take_rows), never by a gather wider than
        # the compiler takes in one piece; a narrower arena (bf16) is
        # cast after the read.
        W = idx.shape[0]
        pb = jnp.einsum("wmrak,rs->wmrksa", p, eyeR).reshape(W, M, RK, RK)
        pb = pb[:, block_part]                              # [W,B,RK,RK]
        x = take(clv, idx).astype(cdt)
        x = x.reshape(W, B, lane, RK)
        return jax.lax.dot_general(x, pb,
                                   (((3,), (2,)), ((0, 1), (0, 1))),
                                   precision=precision)

    @jax.named_scope("examl/newview")
    def values(clv, scaler, ch: FastChunk, pl=None, pr=None):
        """The chunk's COMPUTED rows, no write: (v [W, B, lane, R, K]
        in the compute dtype, sc [W, B, lane]).  Split out of `apply`
        so the universal interpreter (ops/universal.py) can run the
        identical arithmetic inside a `lax.switch` branch while the
        arena write stays OUTSIDE the conditional — XLA copies carry
        buffers that are written inside cond branches (measured 7.6x
        on CPU), but read-only operands flow through for free.  `pl`,
        `pr`: the children's [W, M, R, K, K] transition matrices where
        they were built before the step (`tail_p`)."""
        rows, B, lane, R_, K = clv.shape
        RK = R_ * K
        if pl is None:
            pl = kernels.p_matrices_wave(models, ch.zl)     # [W,M,R,K,K]
            pr = kernels.p_matrices_wave(models, ch.zr)
        W = ch.width
        if ch.kind == 0:
            yl = tip_child(pl, ch.lcode, B, RK)
            yr = tip_child(pr, ch.rcode, B, RK)
            sc = jnp.zeros((W, B, lane), jnp.int32)
        elif ch.kind == 1:
            yl = tip_child(pl, ch.lcode, B, RK)
            yr = inner_child(pr, ch.ridx, clv, B, lane, RK)
            sc = take(scaler, ch.ridx)
        else:
            yl = inner_child(pl, ch.lidx, clv, B, lane, RK)
            yr = inner_child(pr, ch.ridx, clv, B, lane, RK)
            sc = take(scaler, ch.lidx) + take(scaler, ch.ridx)
        v = yl * yr                                         # [W,B,lane,RK]
        needs = jnp.max(jnp.abs(v), axis=3) < minlik
        v = jnp.where(needs[..., None], v * two_e, v)
        sc = sc + needs.astype(jnp.int32)
        return v.reshape(W, B, lane, R_, K), sc

    def write(clv, scaler, v, sc, base):
        """The contiguous in-place arena write of computed rows."""
        z0 = jnp.zeros((), base.dtype if hasattr(base, "dtype")
                       else jnp.int32)
        clv = jax.lax.dynamic_update_slice(
            clv, v.astype(clv.dtype), (base, z0, z0, z0, z0))
        scaler = jax.lax.dynamic_update_slice(scaler, sc, (base, z0, z0))
        return clv, scaler

    @jax.named_scope("examl/newview")
    def apply(clv, scaler, ch: FastChunk):
        v, sc = values(clv, scaler, ch)
        return write(clv, scaler, v, sc, ch.base)

    apply.values = values
    apply.write = write
    apply.tail_p = functools.partial(kernels.p_matrices_wave, models) \
        if tail_p_models(M, site_shards) else None
    return apply


def run_chunks(models: kernels.DeviceModels, block_part: jax.Array,
               tips: kernels.TipState, clv: jax.Array, scaler: jax.Array,
               chunks, scale_exp: int, precision) -> Tuple[jax.Array, jax.Array]:
    """Execute an explicit chunk list unrolled, in order (traced; shapes
    static).  The REFERENCE execution strategy: the segment program
    (`run_segments`) must match it bit for bit, and to f32 rounding
    where many models' one-entry tail builds its P before its scan
    (`chunk_applier`'s `tail_p`).

    clv is [rows, B, lane, R, K]; writes spill up to width-1 junk rows
    past each chunk's real entries — the arena reserves slack for the
    final chunk and intermediate spill is overwritten by later chunks
    before anything reads it.
    """
    apply = chunk_applier(models, block_part, tips, scale_exp, precision)
    for ch in chunks:
        clv, scaler = apply(clv, scaler, ch)
    return clv, scaler


@jax.named_scope("examl/newview")
def run_segments(profile, base, lidx, ridx, lcode, rcode, zl, zr,
                 clv, scaler, apply) -> Tuple[jax.Array, jax.Array]:
    """Execute the bounded program over the PACKED 7-leaf layout:
    unrolled segments slice their windows statically; scan segments
    reshape theirs to [glen, step] and run one `lax.scan` whose body
    executes the step's sub-chunks with the same `apply` kernel, so the
    program length is O(#segments) while the arithmetic — and execution
    order — is chunk-for-chunk identical to `run_chunks`."""
    off = 0
    coff = 0

    def window(a, o, w):
        return jax.lax.slice_in_dim(a, o, o + w)

    def entry_branch(kind):
        def branch(c, s, li, ri, lc, rc, zl_, zr_, *p):
            return apply.values(c, s, FastChunk(kind, 1, None, li, ri, lc,
                                                rc, zl_, zr_), *p)
        return branch

    entry_branches = [entry_branch(k) for k in (0, 1, 2)]

    for seg in profile:
        if seg[0] == "u":
            _, k, W = seg
            ch = FastChunk(k, W, base[coff], window(lidx, off, W),
                           window(ridx, off, W), window(lcode, off, W),
                           window(rcode, off, W), window(zl, off, W),
                           window(zr, off, W))
            clv, scaler = apply(clv, scaler, ch)
            off += W
            coff += 1
            continue
        if seg[0] == "e":
            glen, subs = seg[1], ((None, 1),)
        else:
            _, glen, subs = seg
        SW = sum(w for _, w in subs)
        ns = len(subs)
        span = glen * SW

        def reshape_xs(a):
            w = window(a, off, span)
            return w.reshape((glen, SW) + w.shape[1:])

        xs = (window(base, coff, glen * ns).reshape(glen, ns),
              reshape_xs(lidx), reshape_xs(ridx), reshape_xs(lcode),
              reshape_xs(rcode), reshape_xs(zl), reshape_xs(zr))
        if seg[0] == "e" and apply.tail_p is not None:
            # many models: every entry's P before the scan, a step's own
            # handed to it (`chunk_applier`)
            xs += tuple(apply.tail_p(window(z, off, span))[:, None]
                        for z in (zl, zr))

        def body(carry, x, subs=subs):
            c, s = carry
            b, li, ri, lc, rc, zl_, zr_ = x
            o = 0
            for j, (k, W) in enumerate(subs):
                ch = FastChunk(k, W, b[j], window(li, o, W),
                               window(ri, o, W), window(lc, o, W),
                               window(rc, o, W), window(zl_, o, W),
                               window(zr_, o, W))
                c, s = apply(c, s, ch)
                o += W
            return (c, s), None

        def entry_body(carry, x):
            # One entry a step; its kind is data: the count of its
            # children's rows (-1 marks a tip child, which reads none).
            # Only the computing is switched; the write of the one row
            # stays outside the conditional (ops/universal.py: XLA
            # copies carry buffers written inside cond branches).
            c, s = carry
            b, li, ri, lc, rc, zl_, zr_, *p = x
            kind = ((li[0] >= 0).astype(jnp.int32)
                    + (ri[0] >= 0).astype(jnp.int32))
            v, sc = jax.lax.switch(kind, entry_branches, c, s, li, ri,
                                   lc, rc, zl_, zr_, *p)
            return apply.write(c, s, v, sc, b[0]), None

        (clv, scaler), _ = jax.lax.scan(
            entry_body if seg[0] == "e" else body, (clv, scaler), xs)
        off += span
        coff += glen * ns
    return clv, scaler
