"""Topology-as-data universal interpreter: ONE compiled executable for
every topology.

The bounded chunk tier (ops/fastpath.py) already packs a traversal's
entire schedule into seven array leaves — segment descriptors, chunk
windows, kinds, child index/code arrays, per-chunk zl/zr — but its
compiled program is still SPECIALIZED: the segment profile is the jit
key, each segment's window is sliced statically inside the trace, and a
topology whose bucketed profile was never seen pays a first-call
compile.  A long-lived `--serve` process therefore keeps meeting novel
profiles forever, and the bank can only pre-compile what it can
enumerate (ROADMAP items 4-5).

This module inverts the design, the way BEAGLE's operation-queue API
does on GPUs (PAPERS.md, Ayres et al. 4.1: operations are CALL-TIME
lists, not compile-time programs) expressed XLA-natively per the
Julia->TPU lesson (PAPERS.md, 1810.09868: keep control flow structured,
feed the schedule in as data):

* The chunk sequence becomes a runtime DESCRIPTOR TABLE.  Every chunk
  is split into UNIFORM steps of the ladder floor width (`MIN_WIDTH`;
  valid because chunk entries are independent and all ladder widths
  are floor multiples — per-entry arithmetic is untouched), so the
  class alphabet collapses to the three tip cases alone and every
  step's tensor shapes are identical.
* One `lax.scan` walks the table; its body `lax.switch`es over the
  3-kind alphabet.  A branch only COMPUTES its step's rows — the
  identical `fastpath.chunk_applier` arithmetic the specialized
  program unrolls (the shared `values` half of the kernel) — and the
  arena `dynamic_update_slice` happens OUTSIDE the conditional.  This
  split is load-bearing: XLA copies carry buffers that are written
  inside cond branches (measured 7.6x on CPU), while read-only
  operands flow through for free.
* Table length and packed-slot count bucket through `utils.bucket_len`
  (<=25% padding); padding steps REPLAY the final step — PR5's
  replay-step discipline: a step reads only rows written strictly
  before it and rewrites its own rows with identical values, so replay
  is idempotent and no scratch arithmetic leaks into real rows.

The jit key collapses from the per-topology segment profile to
`("universal", (floor, cap), table_bucket, slot_bucket, with_eval)` — a
tiny CLOSED family — so any topology of any size runs through an
already-banked executable with zero first-call compiles.  That closure
is also what makes the family SERIALIZABLE: the exported program bank
(ops/export_bank.py) persists each bucket pair's compiled executable
next to the XLA cache, so a restarted or autoscaled process
deserializes the interpreter instead of compiling it — the
zero-compile property extends from "within one process" to "across
process lifetimes".  Dispatch
reuses any already-compiled bucket pair that fits (`pick_pads`,
mirroring the fleet tier's smallest-compiled-pow2 discipline), so a
serving process never compiles again after warmup.  The price is
sequential depth: the interpreter runs O(packed slots / floor) scan
steps instead of the specialized program's O(log n) fused ops — the
zero-compile tier for serving novel topologies, not a replacement for
the chunk tier on a hot profile.

The interpreter executes the chunk tier's own kernel
(`fastpath.chunk_applier`): it is the PORTABILITY tier — the escape
ladder runs chunk -> universal -> scan.  Opt out with `EXAML_UNIVERSAL=0`; force with
`EXAML_UNIVERSAL=force` (what the supervisor's degradation ladder pins
between the chunk and scan rungs).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Set, Tuple

import numpy as np

from examl_tpu.utils import bucket_len


class UniversalIneligible(ValueError):
    """This layout cannot run through the interpreter (a chunk width
    off the ladder the table is built for, or an empty traversal).
    Callers fall back to the specialized program."""


def width_ladder(mw: int, cap: int) -> Tuple[int, ...]:
    """The bucketed-width ladder {mw, 2mw, ..., cap} (fastpath's
    `_bucket_w` floor/cap ladder)."""
    widths = []
    w = mw
    while w < cap:
        widths.append(w)
        w *= 2
    widths.append(cap)
    return tuple(widths)


def alphabet_key() -> Tuple[int, int]:
    """(min_width, cap) — the layout constants that determine step
    width and table splitting; rides in every universal jit key, so
    programs of two ladders can never alias."""
    from examl_tpu.ops import fastpath
    return (fastpath.MIN_WIDTH, fastpath.CHUNK_CAP)


def alphabet(knobs: Optional[Tuple[int, int]] = None
             ) -> Tuple[Tuple[int, int], ...]:
    """The closed class alphabet: the three tip cases, all at the
    UNIFORM step width (the ladder floor).  Uniform width is what lets
    every switch branch return identically-shaped small results so the
    arena write can live outside the conditional."""
    if knobs is None:
        knobs = alphabet_key()
    mw, _cap = knobs
    return tuple((k, mw) for k in (0, 1, 2))


class UniversalTable(NamedTuple):
    """Host-side descriptor table of one layout, in execution order:
    every chunk split into uniform floor-width steps (scan-group steps
    and their replay padding already expanded by the packed layout,
    `fastpath._pack_structure`)."""
    n_chunks: int           # step count (table rows before padding)
    slots: int              # real packed slot count P
    cls: np.ndarray         # [n_chunks] int32 class id into alphabet()
    slot: np.ndarray        # [n_chunks] int32 packed-slot offset
    base: np.ndarray        # [n_chunks] int32 first arena row written


def build_table(profile, base: np.ndarray,
                knobs: Optional[Tuple[int, int]] = None) -> UniversalTable:
    """Flatten a bounded segment profile into the runtime descriptor
    table, splitting every chunk into floor-width steps.  `base` is the
    layout's per-chunk arena-base array (host).  Splitting is exact:
    ladder widths are all multiples of the floor, chunk entries are
    independent, and every per-entry op in the kernel batches over the
    width axis, so sub-steps compute bit-identical rows.  Raises
    UniversalIneligible for widths off the `knobs` ladder, a one-entry
    tail or an empty profile."""
    from examl_tpu.ops import fastpath

    if knobs is None:
        knobs = alphabet_key()
    mw, cap = knobs
    if any(seg[0] == "e" for seg in profile):
        raise UniversalIneligible(
            "one-entry tail ('e' segment): a step's kind is data and its "
            "width 1, off the interpreter's uniform-width alphabet")
    kinds_w = list(fastpath.iter_profile_chunks(profile))
    if not kinds_w:
        raise UniversalIneligible("empty traversal")
    ks = np.fromiter((k for k, _ in kinds_w), np.int64, len(kinds_w))
    ws = np.fromiter((w for _, w in kinds_w), np.int64, len(kinds_w))
    offladder = ((ws % mw) != 0) | (ws > cap) | (ws < 1)
    if offladder.any():
        bad = ws[offladder]
        raise UniversalIneligible(
            f"chunk widths {sorted(set(int(b) for b in bad))} off the "
            f"ladder (floor {mw}, cap {cap})")
    base = np.asarray(base, np.int64)
    if base.shape[0] != len(kinds_w):
        raise UniversalIneligible(
            f"base array length {base.shape[0]} != chunk count "
            f"{len(kinds_w)}")
    reps = ws // mw
    slot0 = np.concatenate([[0], np.cumsum(ws)[:-1]])
    n = int(reps.sum())
    # Sub-step index j within its chunk: 0..reps-1 per chunk.
    j = (np.arange(n, dtype=np.int64)
         - np.repeat(np.concatenate([[0], np.cumsum(reps)[:-1]]), reps))
    return UniversalTable(
        n_chunks=n, slots=int(ws.sum()),
        cls=np.repeat(ks, reps).astype(np.int32),
        slot=(np.repeat(slot0, reps) + j * mw).astype(np.int32),
        base=(np.repeat(base, reps) + j * mw).astype(np.int32))


def pad_table(table: UniversalTable, npad: int):
    """Descriptor arrays padded to `npad` rows by REPLAYING the final
    step (PR5 discipline: idempotent — the final step re-reads rows
    written strictly before it and rewrites its own rows with identical
    values), so a larger already-compiled bucket can serve a smaller
    table with no scratch arithmetic touching real rows."""
    assert npad >= table.n_chunks
    pad = npad - table.n_chunks
    if pad == 0:
        return table.cls, table.slot, table.base
    return (np.concatenate([table.cls, np.full(pad, table.cls[-1])]),
            np.concatenate([table.slot, np.full(pad, table.slot[-1])]),
            np.concatenate([table.base, np.full(pad, table.base[-1])]))


def pick_pads(minted: Set[Tuple[int, int]], n_chunks: int,
              slots: int) -> Tuple[int, int]:
    """(table_bucket, slot_bucket) for a dispatch: the least-waste
    ALREADY-COMPILED bucket pair that fits — replay padding is
    idempotent, so any larger bucket serves correctly — else the
    natural `bucket_len` pair.  Reuse is capped at 2x each axis:
    replay steps cost real chunk applies, and a 4x-padded dispatch
    would trade the compile we avoided for permanent arithmetic.
    Callers add the returned pair to `minted` (mirrors the fleet
    tier's `_pick_jpad` smallest-compiled-pow2 discipline)."""
    fits = [(tn, tp) for tn, tp in minted
            if n_chunks <= tn <= 2 * n_chunks and slots <= tp <= 2 * slots]
    if fits:
        return min(fits, key=lambda t: (t[0] + t[1], t))
    return bucket_len(n_chunks), bucket_len(slots)


def pad_slots(arr: np.ndarray, ppad: int, fill=0) -> np.ndarray:
    """A packed per-slot host array padded to the slot bucket.  Padding
    slots are never read: descriptor padding replays the final REAL
    step, whose window lies inside the real slot range."""
    P = arr.shape[0]
    assert ppad >= P
    if ppad == P:
        return arr
    out = np.full((ppad,) + arr.shape[1:], fill, dtype=arr.dtype)
    out[:P] = arr
    return out


def run_universal(alpha, cls, slot, cbase, lidx, ridx, lcode, rcode,
                  zl, zr, clv, scaler, values, select: bool = False):
    """The interpreter body (traced): one `lax.scan` over the
    descriptor table; each step `lax.switch`es to its tip-case class,
    dynamic-slices the floor-width windows out of the packed arrays at
    the step's slot offset, and COMPUTES the step's rows with the
    shared chunk kernel (`values` — the compute half of
    `fastpath.chunk_applier`).  The arena writes happen here, outside
    the conditional, so the carry is never copied through the switch.
    Program length is O(1) regardless of topology or table length —
    THE property that makes the jit key topology-independent.

    `select=True` replaces the `lax.switch` with `lax.select_n` over
    ALL THREE class branches — a gather-style select of computed
    values, bit-identical to the switch (select_n picks one branch's
    exact results; no arithmetic blending) at ~3x the per-step compute.
    This is the VMAPPED (fleet unibatch) form: under vmap a batched
    switch index degenerates to executing every branch anyway, and the
    explicit select keeps the arena writes outside any conditional
    (the GL001 cond-write hazard cannot re-enter through a batching
    rule) while letting MIXED-PROFILE job batches share one compiled
    program — the tables differ per job, the program does not."""
    import jax
    import jax.numpy as jnp

    from examl_tpu.ops.fastpath import FastChunk

    W = alpha[0][1]
    assert all(w == W for _, w in alpha), "alphabet must be uniform-width"

    def make_branch(kind):
        def branch(clv, scaler, off):
            def win(a):
                return jax.lax.dynamic_slice_in_dim(a, off, W)
            ch = FastChunk(kind, W, jnp.int32(0), win(lidx), win(ridx),
                           win(lcode), win(rcode), win(zl), win(zr))
            return values(clv, scaler, ch)
        return branch

    branches = [make_branch(k) for k, _ in alpha]

    def body(carry, x):
        c, s = carry
        ci, off, b = x
        if select:
            outs = [br(c, s, off) for br in branches]
            v = jax.lax.select_n(ci, *[v for v, _ in outs])
            sc = jax.lax.select_n(ci, *[sc for _, sc in outs])
        else:
            v, sc = jax.lax.switch(ci, branches, c, s, off)
        z0 = jnp.zeros((), b.dtype)
        c = jax.lax.dynamic_update_slice(c, v.astype(c.dtype),
                                         (b, z0, z0, z0, z0))
        s = jax.lax.dynamic_update_slice(s, sc, (b, z0, z0))
        return (c, s), None

    with jax.named_scope("examl/newview"):
        (clv, scaler), _ = jax.lax.scan(body, (clv, scaler),
                                        (cls, slot, cbase))
    return clv, scaler
