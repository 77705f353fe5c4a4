"""Ask the TPU's compiler, without a TPU, about the main path's programs.

The compiler for a v5e is installed in the CPU sandbox and compiles for a
chip that is DESCRIBED, not attached (`jax.experimental.topologies`).
Nothing runs, so these say nothing about results or times — only that the
program the chip will be handed compiles at the smoke's real shapes, fits
the device's memory and carries the collectives it should.

The engine reads `jax.devices()` and would take its CPU branch, so each
case builds a 140-taxon ONE-block f32 engine on CPU, takes the jitted
body the engine would dispatch (steering the engine from here: the jit
cache hands back the raw `jax.jit`), and
lowers it with `ShapeDtypeStruct`s on the described device with the block
axis scaled up to the real width.

This is the only file that describes a topology, and only inside
fixtures: one process at a time may load the TPU's library, so the call
must not happen at import, in conftest, or in an autouse fixture.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from examl_tpu.instance import PhyloInstance  # noqa: E402
from examl_tpu.io.alignment import build_alignment_data  # noqa: E402
from examl_tpu.obs.programs import (arena_gathers,  # noqa: E402
                                    collectives_in_loops, loop_bodies,
                                    operand_slices)
from examl_tpu.ops import fastpath  # noqa: E402

HBM_BYTES = 16 * 1000 ** 3        # one v5e chip: 16 GB (Cloud TPU docs)
NTAXA = 140
# The most temporaries the compiler may count for the gradient program,
# to the MiB the loops' index tables move: at 131,072 and a chip's
# 65,536 what it counted while both loops ran the next power of two's
# slots (PR 35's tree, this file's own compiles; PR 36 read
# 3,950,129,152 / 3,176,473,088: the peak is the edge chunk's 32 rows
# beside the outroot arena, which no step width changes at 131,072).
GRAD_TEMP_MAX = {"dna131k": 3_950_031_872 + 2 ** 20,
                 "dna65k_chip": 3_243_680_256 + 2 ** 20,
                 # the 8-entry steps PR 39 took away, ('grad', 28, 8,
                 # 9), here and on the chip; the one-entry programs read
                 # 411,315,712 and 3,403,522,048, here and on the chip
                 "dna16k": 811_964_928 + 2 ** 20,
                 "aa16k": 3_996_271_104 + 2 ** 20}
# sha256 (first 16) of `jit__grad_impl`'s lowered text at 131,072
# patterns on a CPU device, x64 off, since PR 36 (PERF.md section 6):
# one entry a step there before and after PR 39, so not a letter moved.
GRAD_TEXT_PR36 = {NTAXA: "22eeddda69dad6bf", 49: "ab1572ab8449bb08"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip_compile():
    """The chip runs f32 with x64 off; and a compile for a described
    device can be written to the persistent cache but not read back, so
    the cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    x64 = jax.config.jax_enable_x64
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", x64)
        jax.config.update("jax_enable_compilation_cache", cache)
        cc.reset_cache()


def _one_block_engine(datatype: str, ntaxa: int = NTAXA):
    """140 taxa x 128 random sites (one 128-lane block) on CPU, f32,
    with a full-traversal schedule of a random tree."""
    rng = np.random.default_rng(7)
    alphabet = {"AA": "ARNDCQEGHILKMFPSTWYV", "DNA": "ACGT"}[datatype]
    names = [f"t{i}" for i in range(ntaxa)]
    seqs = ["".join(alphabet[c] for c in rng.integers(0, len(alphabet), 128))
            for _ in names]
    inst = PhyloInstance(
        build_alignment_data(names, seqs, datatype_name=datatype),
        dtype=jnp.float32)
    (eng,) = inst.engines.values()
    assert eng.B == 1 and eng.dtype == jnp.float32
    eng.cache_put = lambda key, fn: fn       # hand back the raw jax.jit
    tree = inst.random_tree(3)
    p = tree.centroid_branch()
    flat = tree.flat_full_traversal(p)
    st = eng._fast_structure(flat)
    return inst, eng, tree, p, flat, st


def _chunk_eval_call(eng, p, flat, st):
    """(jitted chunk+evaluate program, its arguments) exactly as
    `_run_fast_flat` dispatches them."""
    zl, zr = fastpath.refresh_z(st, flat, eng.num_branch_slots, eng.dtype)
    fn = eng._fast_fn_flat(st.profile, with_eval=True)
    zv = jnp.asarray(np.asarray(p.z, dtype=np.float32))
    args = (eng.clv, eng.scaler, st.base, st.lidx, st.ridx, st.lcode,
            st.rcode, zl, zr,
            jnp.int32(eng._gidx_of(st, p.number)),
            jnp.int32(eng._gidx_of(st, p.back.number)), zv,
            eng.models, eng.block_part, eng.weights, eng.tips)
    return fn, args


def _grad_call(eng, p, flat, st, blocks: int, sharding=None):
    """(jitted gradient pass, its arguments, its structure) as
    `whole_tree_gradients` dispatches them (ops/gradient.py) on an
    engine of `blocks` blocks under `sharding`: the structure's outroot
    step width follows the row's bytes there (`Engine.grad_wave_cap`:
    one entry a step from 0.5 MiB a row)."""
    from examl_tpu.ops import gradient
    from examl_tpu.ops.kernels import OutrootTraversal
    eng._install_row_map(st)
    eng.B, eng.sharding = blocks, sharding
    gs = eng._grad_structure(flat)
    root_z = np.asarray(p.z, dtype=np.float64)
    pre, ex_rows, ey_gidx, ez = gradient.grad_arrays(
        gs, flat, eng.row_map, eng.num_branch_slots, root_z)
    up_row, lrow, rrow, lg, rg, zu, zl, zr = pre
    f32 = lambda a: jnp.asarray(a, dtype=jnp.float32)  # noqa: E731
    tvp = OutrootTraversal(
        up_row=jnp.asarray(up_row), lrow=jnp.asarray(lrow),
        rrow=jnp.asarray(rrow), left=jnp.asarray(lg),
        right=jnp.asarray(rg), zu=f32(zu), zl=f32(zl), zr=f32(zr))
    pn, qn = gs.roots
    args = (eng.clv, eng.scaler, jnp.int32(pn - 1), jnp.int32(qn - 1),
            jnp.int32(eng._gidx(pn)), jnp.int32(eng._gidx(qn)), tvp,
            jnp.asarray(ex_rows), jnp.asarray(ey_gidx), f32(ez),
            eng.models, eng.block_part, eng.weights, eng.tips, None)
    return jax.jit(eng._grad_impl), args, gs


def _as_shapes(eng, args, blocks: int, place):
    """`args` as ShapeDtypeStructs with the block axis scaled from 1 to
    `blocks` (axis 1 of clv / scaler / tips.codes / tips.masks, axis 0
    of block_part / weights); `place(kind)` gives each leaf's sharding, kind being the
    SiteSharding attribute the engine would place it with."""
    block_axis = {id(eng.clv): (1, "clv"), id(eng.scaler): (1, "scaler"),
                  id(eng.tips.codes): (1, "scaler"),
                  id(eng.tips.masks): (1, "scaler"),
                  id(eng.block_part): (0, "blocks"),
                  id(eng.weights): (0, "sites")}

    def leaf(x):
        x = x if hasattr(x, "shape") else np.asarray(x)
        shape, kind = tuple(x.shape), "replicated"
        if id(x) in block_axis:
            ax, kind = block_axis[id(x)]
            assert shape[ax] == 1
            shape = shape[:ax] + (blocks,) + shape[ax + 1:]
        dtype = jax.dtypes.canonicalize_dtype(x.dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place(kind))

    return jax.tree.map(leaf, args)


def _on(one_chip):
    """`place` for a one-chip compile, as the engine lowers: its arrays
    bring no sharding into the lowering, so the arenas carry none here
    either, and the small replicated operands carry the described
    device, which is what sends the compile there.  A `sharding=` on
    `clv` pins that parameter (`sharding={replicated}`, no propagation
    to it), and for the one-entry K = 20 gradient program this
    compiler's `memory_analysis()` then counts 4,605,826,048 B of
    temporaries where the engine's own lowering reads 3,403,522,048,
    here and on the chip, though both buffer assignments hold the same
    3.27 GB to 49 KB (PERF.md section 6, PR 39's review round)."""
    return lambda kind: one_chip if kind == "replicated" else None


def _fits(compiled) -> dict:
    m = compiled.memory_analysis()
    sizes = {"arguments": m.argument_size_in_bytes,
             "temporaries": m.temp_size_in_bytes,
             "outputs": m.output_size_in_bytes,
             "aliased": m.alias_size_in_bytes}
    print("memory_analysis:", sizes)          # shown with pytest -s
    assert sizes["arguments"] + sizes["temporaries"] < HBM_BYTES, sizes
    return sizes


def _arena_sized_in_loops(text: str, elems: int) -> list:
    """Instructions inside a `while` loop (`loop_bodies`) whose result
    holds at least `elems` elements, an arena's, other than the in-place
    row writes: a `scatter` or `dynamic-update-slice`, or a fusion whose
    computation ends in one.  Parameters, tuples and bitcasts move
    nothing."""
    import re
    looped = loop_bodies(text)
    in_place = ("scatter", "dynamic-update-slice")
    inst = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                      r"([\w\-]+)\((.*)$", re.M)

    def root_op(comp):
        return next((m.group(4) for m in inst.finditer(looped.get(comp, ""))
                     if m.group(1)), None)

    found = []
    for body in looped.values():
        for m in inst.finditer(body):
            _, name, dims, op, rest = m.groups()
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            if n < elems or op in in_place + (
                    "parameter", "get-tuple-element", "bitcast"):
                continue
            called = re.search(r"calls=%?([\w.\-]+)", rest)
            if op == "fusion" and called and root_op(called.group(1)) \
                    in in_place:
                continue
            found.append((op, name))
    return found


def _reads_rows_by_index(compiled, arena_elems: int,
                         gathers: int = 0) -> None:
    """What `kernels.take_rows` is for: the compiler was handed no
    gather of an arena (or kept `gathers` one-piece ones, where a row is
    one piece and `take_rows` hands them over), so it cut none into
    pieces by slicing the arena, and no loop holds a copy of one."""
    text = compiled.as_text()
    assert " while(" in text                     # the walk has loops to see
    assert operand_slices(text) == 0
    assert arena_gathers(text) == gathers
    assert _arena_sized_in_loops(text, arena_elems) == []


def _one_all_reduce_outside_loops(text: str) -> None:
    """The program's one cross-shard collective is an all-reduce that
    runs once a call: counted in the text, it is one a call only while
    none sits in a loop."""
    n_reduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    assert n_reduce == 1, n_reduce
    assert " while(" in text and collectives_in_loops(text) == 0
    for other in ("all-gather", "all-to-all", "collective-permute",
                  "reduce-scatter"):
        assert f" {other}(" not in text and f" {other}-start(" not in text


def test_chunk_evaluate_program_140x131072_dna(one_chip, chip_compile):
    """The fullwidth phase's first program: full traversal (XLA chunk
    tier) + root evaluation at 140 x 131,072 DNA patterns, f32."""
    _, eng, _, p, flat, st = _one_block_engine("DNA")
    fn, args = _chunk_eval_call(eng, p, flat, st)
    compiled = fn.lower(*_as_shapes(eng, args, 1024,
                                    _on(one_chip))).compile()
    sizes = _fits(compiled)
    arena = eng.num_rows * 1024 * 128 * 16 * 4
    assert sizes["arguments"] > arena            # the real-width arena
    assert "tpu_custom_call" not in compiled.as_text()    # no kernel


@pytest.mark.parametrize("ntaxa,shapes", [(NTAXA, (NTAXA - 2, 1, 9)),
                                          (49, (47, 1, 3))])
def test_gradient_pass_131072_dna(one_chip, chip_compile, ntaxa, shapes):
    """The whole-tree gradient pass (ops/gradient.py) at the same
    width, 140 taxa (cell 2) and 49 (the wide search cell): the outroot
    arena (2n-1 rows) lives inside the program.  A row is 8.39 MB, so
    one entry an outroot step: n steps whatever the tree, and
    ceil(E / 32) edge chunks, 9 for 277 edges and 3 for 95."""
    _, eng, _, p, flat, st = _one_block_engine("DNA", ntaxa)
    fn, args, gs = _grad_call(eng, p, flat, st, 1024)
    assert (gs.n_steps, gs.wave_w, gs.n_chunks) == shapes
    compiled = fn.lower(*_as_shapes(eng, args, 1024,
                                    _on(one_chip))).compile()
    sizes = _fits(compiled)
    outroot = (2 * ntaxa - 1) * 1024 * 128 * 16 * 4
    # 140 taxa: 5.19 GB while the rows were gathered (32 operand
    # slices), 3.95 GB read by index in steps of 8 entries and 16
    # chunks (PR 32 to 35)
    assert outroot <= sizes["temporaries"] <= GRAD_TEMP_MAX["dna131k"]
    _reads_rows_by_index(compiled, eng.num_rows * 1024 * 128 * 16)
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    text = fn.lower(*_as_shapes(eng, args, 1024, lambda kind: cpu)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        GRAD_TEXT_PR36[ntaxa]


@pytest.mark.parametrize("datatype,cell,states,gathers", [
    ("DNA", "dna16k", 4, 2), ("AA", "aa16k", 20, 0)])
def test_gradient_pass_140x16384(one_chip, chip_compile, datatype, cell,
                                 states, gathers):
    """128 blocks, DNA (a row is 1 MiB) and K = 20 (5 MiB): one entry an
    outroot step since PR 39, n steps whatever the tree, its one-row
    reads dynamic slices; the edge loop's `take_rows` still hands the
    compiler its two 32-row gathers (a row is one piece): at K = 20 the
    compiler lowers them to loops of dynamic slices itself, on DNA it
    keeps them, whole.  No operand slice, no arena in a loop, and
    fewer temporaries than the 8-entry step's (411,315,712 for
    811,964,928 on DNA, 3,403,522,048 for 3,996,271,104 at K = 20,
    here and on the chip)."""
    _, eng, _, p, flat, st = _one_block_engine(datatype)
    fn, args, gs = _grad_call(eng, p, flat, st, 128)
    assert (gs.n_steps, gs.wave_w, gs.n_chunks) == (NTAXA - 2, 1, 9)
    compiled = fn.lower(*_as_shapes(eng, args, 128,
                                    _on(one_chip))).compile()
    assert _fits(compiled)["temporaries"] <= GRAD_TEMP_MAX[cell]
    _reads_rows_by_index(compiled, eng.num_rows * 128 * 128 * 4 * states,
                         gathers)


def test_chunk_evaluate_program_reads_rows_by_index(one_chip, chip_compile):
    """`fastpath.chunk_applier` reads its child rows and scalers through
    `kernels.take_rows`: at 131,072 patterns the chunk+evaluate program
    holds no operand slice (56 while `clv[idx]` was a gather) and no
    copy of the arena in a loop."""
    _, eng, _, p, flat, st = _one_block_engine("DNA")
    fn, args = _chunk_eval_call(eng, p, flat, st)
    compiled = fn.lower(*_as_shapes(eng, args, 1024,
                                    _on(one_chip))).compile()
    # 1.77 GB while the rows were gathered (one copy of the arena),
    # 0.54 GB read by index
    assert _fits(compiled)["temporaries"] < 1.0e9
    _reads_rows_by_index(compiled, eng.num_rows * 1024 * 128 * 16)


def test_chunk_evaluate_program_140x16384_protein(one_chip, chip_compile):
    """K = 20: the same chunk program on 140 x 16,384 protein patterns
    (R*K = 80 minor dimension)."""
    _, eng, _, p, flat, st = _one_block_engine("AA")
    assert eng.K == 20
    fn, args = _chunk_eval_call(eng, p, flat, st)
    compiled = fn.lower(*_as_shapes(eng, args, 128,
                                    _on(one_chip))).compile()
    _fits(compiled)


def test_site_sharded_chunk_program_one_all_reduce(topo, chip_compile):
    """The default path on a four-chip host: the chunk+evaluate program
    with the block axis sharded over a 4-device mesh (140 x 16,384 DNA).
    The root lnL segment-sum is the ONE cross-shard collective — ExaML's
    single Allreduce."""
    from examl_tpu.parallel.sharding import make_mesh, site_sharding
    sh = site_sharding(make_mesh(devices=topo.devices[:4]))
    _, eng, _, p, flat, st = _one_block_engine("DNA")
    fn, args = _chunk_eval_call(eng, p, flat, st)
    compiled = fn.lower(*_as_shapes(
        eng, args, 128, lambda kind: getattr(sh, kind))).compile()
    sizes = _fits(compiled)
    # per-device bytes: a quarter of the 140 x 16,384 arena, not all
    arena = eng.num_rows * 128 * 128 * 16 * 4
    assert sizes["arguments"] < arena // 2
    _one_all_reduce_outside_loops(compiled.as_text())


def test_site_sharded_chunk_program_262144_reads_rows_by_index(
        topo, chip_compile):
    """The four-chip deployment's traversal: the chunk+evaluate program
    at 140 x 262,144 DNA with the block axis sharded four ways (GSPMD).
    The program sees the GLOBAL 2,048 blocks, so `take_rows` reads by
    index, and a shard's rows (512 blocks) are cut into no operand
    slices (28 a chip while they were gathered); the root lnL sum stays
    the one collective, outside every loop.  The 16,384 case above takes
    the gather form and cannot see this."""
    from examl_tpu.parallel.sharding import make_mesh, site_sharding
    sh = site_sharding(make_mesh(devices=topo.devices[:4]))
    _, eng, _, p, flat, st = _one_block_engine("DNA")
    fn, args = _chunk_eval_call(eng, p, flat, st)
    blocks = 262144 // 128
    compiled = fn.lower(*_as_shapes(
        eng, args, blocks, lambda kind: getattr(sh, kind))).compile()
    sizes = _fits(compiled)
    arena = eng.num_rows * blocks * 128 * 16 * 4
    assert sizes["arguments"] < arena // 2       # a quarter and the tips
    assert sizes["temporaries"] < 0.5e9          # 0.27 GB a chip by index
    _reads_rows_by_index(compiled, eng.num_rows * (blocks // 4) * 128 * 16)
    _one_all_reduce_outside_loops(compiled.as_text())


def test_site_sharded_gradient_program_262144_one_all_reduce(
        topo, chip_compile):
    """The four-chip deployment (benchmarks/configs/dna140x262k.json):
    the whole-tree gradient pass at 140 x 262,144 DNA, four site shards
    of 65,536 patterns.  One chip's compiler refuses this width; a shard
    fits.  The pass is mapped over the site axis
    (`LikelihoodEngine._grad_program`), so no arena row is gathered or
    scattered across chips, and the derivative sums (d1 and d2 stacked)
    meet in ONE all-reduce after the chunk loop, not one a chunk."""
    from examl_tpu.parallel.sharding import make_mesh, site_sharding
    sh = site_sharding(make_mesh(devices=topo.devices[:4]))
    _, eng, _, p, flat, st = _one_block_engine("DNA")
    blocks = 262144 // 128
    # sh: what select_sharding gives there; a shard's row holds 65,536
    # sites, 4 MiB, so one entry a step (two before PR 39)
    _, args, gs = _grad_call(eng, p, flat, st, blocks, sh)
    assert (gs.n_steps, gs.wave_w, gs.n_chunks) == (NTAXA - 2, 1, 9)
    compiled = eng._grad_program().lower(*_as_shapes(
        eng, args, blocks, lambda kind: getattr(sh, kind))).compile()
    sizes = _fits(compiled)
    arena = eng.num_rows * blocks * 128 * 16 * 4
    assert sizes["arguments"] < arena // 2       # a quarter and the tips
    # the outroot arena is born at the shard's size: 2n-1 rows a chip
    outroot = (2 * NTAXA - 1) * (blocks // 4) * 128 * 16 * 4
    # 3.75 GB a chip while the rows were gathered (16 operand slices),
    # 3.24 GB read by index
    assert outroot <= sizes["temporaries"] <= GRAD_TEMP_MAX["dna65k_chip"]
    _reads_rows_by_index(compiled, eng.num_rows * (blocks // 4) * 128 * 16)
    text = compiled.as_text()
    assert "jit__grad_impl" in text.split("\n", 1)[0]   # the trace's name
    # one in the text is one a pass only outside every loop: moved into
    # the chunk loop it would still count one here and run once a chunk
    _one_all_reduce_outside_loops(text)


def _one_entry_structure(eng, flat, blocks: int, sharding=None):
    """The structure the engine builds at `blocks` blocks a row under
    `sharding`: from `fastpath.ONE_ENTRY_ROW_BYTES` a row (a shard's),
    its narrow tail is one ("e", L) segment."""
    eng.sched_cache_invalidate()
    eng.B, eng.sharding = blocks, sharding
    st = eng._fast_structure(flat)
    eng.B, eng.sharding = 1, None
    return st


def _arena_copies(text: str, shape: tuple) -> int:
    """Whole-arena `copy` instructions: a change of the arena's layout."""
    head = "= f32[" + ",".join(str(d) for d in shape) + "]"
    return sum(1 for ln in text.splitlines() if head in ln and " copy(" in ln)


@pytest.mark.parametrize("datatype,blocks", [("DNA", 1024), ("AA", 128),
                                             ("DNA", 128)])
def test_one_entry_tail_program_reads_rows_by_index(one_chip, chip_compile,
                                                    datatype, blocks):
    """The chunk+evaluate program with the one-entry tail at cell 2's
    row (140 x 131,072 DNA, 8 MiB), cell 3's (140 x 16,384 protein,
    5 MiB) and the narrow cells' (140 x 16,384 DNA, 1 MiB, the
    threshold): a step reads its inner children's rows by dynamic
    slices (a protein row as its two halves of the rate axis), so the
    program holds no operand slice, no arena-sized value in a loop but
    the in-place writes, at 131,072 no gather of an arena, and no more
    whole-arena layout copies than today's tail's program (a one-row
    slice of the protein arena made the compiler add three)."""
    _, eng, _, p, flat, today = _one_block_engine(datatype)
    st = _one_entry_structure(eng, flat, blocks)
    assert st.profile[-1][0] == "e", st.profile
    assert today.profile[-1][0] == "s", today.profile
    texts = []
    for s in (today, st):
        fn, args = _chunk_eval_call(eng, p, flat, s)
        compiled = fn.lower(*_as_shapes(eng, args, blocks,
                                        _on(one_chip))).compile()
        texts.append(compiled.as_text())
    _fits(compiled)
    arena = (eng.num_rows, blocks, 128, 4, eng.K)
    _reads_rows_by_index(compiled, int(np.prod(arena)),
                         0 if blocks * 128 > 128 * 128 else
                         arena_gathers(texts[1]))
    assert _arena_copies(texts[1], arena) <= _arena_copies(texts[0], arena)


def test_site_sharded_one_entry_tail_program_262144(topo, chip_compile):
    """The four-chip deployment's traversal with the one-entry tail
    (a shard's row is 4 MiB; forced here whatever the threshold): the
    step's `lax.switch` over the sharded arena brings no collective into
    a loop, and the root lnL sum stays the one collective; rows by
    index, no operand slice."""
    from examl_tpu.parallel.sharding import make_mesh, site_sharding
    sh = site_sharding(make_mesh(devices=topo.devices[:4]))
    _, eng, _, p, flat, _ = _one_block_engine("DNA")
    blocks = 262144 // 128
    st = fastpath.build_structure(flat, NTAXA, fastpath.ONE_ENTRY_ROW_BYTES)
    assert st.profile[-1][0] == "e", st.profile
    fn, args = _chunk_eval_call(eng, p, flat, st)
    compiled = fn.lower(*_as_shapes(
        eng, args, blocks, lambda kind: getattr(sh, kind))).compile()
    assert _fits(compiled)["temporaries"] < 0.5e9
    _reads_rows_by_index(compiled, eng.num_rows * (blocks // 4) * 128 * 16)
    _one_all_reduce_outside_loops(compiled.as_text())


def test_newton_program_compiles(one_chip, chip_compile):
    """`_newton_impl` (fused partial traversal + per-branch Newton: what
    `local_smooth`, -S pools and multi-process meshes smooth with) at
    140 x 16,384 DNA."""
    inst, eng, tree, p, flat, st = _one_block_engine("DNA")
    eng._install_row_map(st)
    tv = eng._traversal_arrays(flat.to_entries()[-4:])
    C = eng.num_branch_slots
    args = (eng.clv, eng.scaler, (), tv,
            jnp.int32(eng._gidx(p.number)),
            jnp.int32(eng._gidx(p.back.number)),
            jnp.asarray(np.asarray(p.z, dtype=np.float32)),
            jnp.full(C, 16, dtype=jnp.int32), jnp.zeros(C, dtype=bool),
            eng.models, eng.block_part, eng.weights, eng.tips, None)
    compiled = jax.jit(eng._newton_impl).lower(
        *_as_shapes(eng, args, 128, _on(one_chip))).compile()
    _fits(compiled)


def _scan_call(inst, eng, tree, thorough: bool, scan_rows: int):
    """(jitted SPR scan program, its arguments) as `batched_scan` /
    `batched_thorough` dispatch them, for the first slot of the cycle's
    order with an inner node on both sides, radius 10, with the scan
    region grown to `scan_rows` rows."""
    from examl_tpu.search import batchscan, spr
    inst.evaluate(tree, full=True)
    p = next(s for s in spr.dfs_slot_order(tree)
             if not tree.is_tip(s.number)
             and not tree.is_tip(s.next.back.number)
             and not tree.is_tip(s.next.next.back.number))
    p1, p2 = p.next.back, p.next.next.back
    spr.remove_node(inst, tree, spr.SprContext(inst), p)
    plan = batchscan.plan_for_endpoints(inst, tree, p, p1, p2, 1, 10)
    assert len(plan.up_entries) <= scan_rows
    base = eng.ensure_scan_rows(scan_rows)
    assert eng.num_rows - base == scan_rows
    T = batchscan.TH_CHUNK if thorough else batchscan.CAND_CHUNK
    tv = eng._scan_traversal_arrays(plan.down_entries, plan.up_entries, base)
    n_chunks, npad, qg, upg = eng._scan_dispatch_arrays(plan, base, T)
    f32 = lambda a: jnp.asarray(a, dtype=jnp.float32)  # noqa: E731
    cand = (jnp.asarray(qg.reshape(n_chunks, T)),
            jnp.asarray(upg.reshape(n_chunks, T)))
    if thorough:
        fn = batchscan.thorough_program(eng, n_chunks)
        cand += (f32(np.ones((n_chunks, T))),
                 jnp.int32(eng._gidx(plan.s_num)))
    else:
        fn = batchscan.scan_program(eng, n_chunks)
        cand += (f32(np.ones((n_chunks, T, 1))),
                 jnp.int32(eng._gidx(plan.s_num)), f32(plan.zp))
    return fn, (eng.clv, eng.scaler, (), tv, *cand, eng.models,
                eng.block_part, eng.weights, eng.tips, None)


@pytest.mark.parametrize("thorough", [False, True],
                         ids=["spr_scan", "spr_thorough"])
@pytest.mark.parametrize("ntaxa,blocks,scan_rows", [(49, 1024, 128),
                                                    (140, 128, 512)])
def test_spr_scan_programs_at_the_search_cells_sizes(
        one_chip, chip_compile, ntaxa, blocks, scan_rows, thorough):
    """The search cells' two device programs (search/batchscan.py) at
    49 x 131,072 with a 128-row scan region and at 140 x 16,384 with
    512 (the most radius 10 can ask for: 4 + 2 x (n - 3) uppass
    entries): they fit beside the gradient program's count, hold no
    operand slice and no arena-sized value in a loop but the in-place
    writes, and carry names the full-traversal family's pattern does
    not match.  At 131,072 `take_rows` reads by index (no gather of an
    arena); at 16,384 a row is one piece and it is the gather, by design
    (kernels.ONE_PIECE_SITES), which the compiler does not cut."""
    import re
    inst, eng, tree, _, _, st = _one_block_engine("DNA", ntaxa)
    eng._install_row_map(st)
    fn, args = _scan_call(inst, eng, tree, thorough, scan_rows)
    compiled = fn.lower(*_as_shapes(eng, args, blocks,
                                    _on(one_chip))).compile()
    sizes = _fits(compiled)
    arena = eng.num_rows * blocks * 128 * 16 * 4   # with the scan region
    assert sizes["arguments"] > arena
    # beside what the gradient program holds while the scan region is
    # allocated: its outroot arena of 2n - 1 rows (PERF.md section 7)
    outroot = (2 * ntaxa - 1) * blocks * 128 * 16 * 4
    assert sizes["arguments"] + sizes["temporaries"] + outroot < HBM_BYTES
    arena_elems = (eng.num_rows - scan_rows) * blocks * 128 * 16
    text = compiled.as_text()
    if blocks * 128 > 128 * 128:
        _reads_rows_by_index(compiled, arena_elems)
    else:
        assert " while(" in text and operand_slices(text) == 0
        assert _arena_sized_in_loops(text, arena_elems) == []
    name = text.split("\n", 1)[0]
    want = "jit_spr_thorough_impl" if thorough else "jit_spr_scan_impl"
    assert want in name
    assert not re.search(r"^jit_impl(_eval)?\(", want + "(1)")
