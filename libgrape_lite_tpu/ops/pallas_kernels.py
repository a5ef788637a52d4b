"""Pallas TPU kernels for the hot ops.

The framework's compute is mostly XLA-fused gathers + segment
reductions (ops/segment.py); the ops that benefit from hand-written
kernels are the *bitmap* ones — LCC / k-clique set intersection, where
the working set is a [chunk, words] tile of packed adjacency rows and
the op is AND + population_count + row-reduce.  The reference's
analogue is its SSE/STTNI intersection kernels (`lcc_opt.h:26-41`) and
the CUDA warp intersections (`cuda/utils/dev_utils.h`).

`intersect_count` tiles the edge chunk over a (rows, words) grid; each
program ANDs two tiles resident in VMEM and reduces popcounts on the
VPU — no HBM round-trip for the intermediate AND, which is what the
`jnp` path materialises.  `row_and_popcount` takes the compiled kernel
when `use_pallas()` (the TPU backend) and the fused jnp path anywhere
else; tests exercise the kernel in interpret mode.

`vmem_gather` is the pull's `full[nbr]` from a table that stays in VMEM
for the length of the call: XLA's gather steps through its indices one
at a time, twelve bundles an element whatever the table's size, where
one vector load of a table row per index is the work.
`ops/segment.pull_gather` chooses between the two.

`vmem_row_gather` is the same loads for a table that is too long to
stay, read by indices that do not decrease: a fold's row ends out of
its scanned stream.  The table passes through VMEM a slice at a time
and each block of indices meets the slices it spans
(`ops/segment._row_end_gather` chooses).

`tile_scan` is the first level of that fold's segmented scan: the
seven steps that scan a tile of 128 places stay inside the tile's 128
lanes, so a block of tiles is read once, scanned in registers by lane
rotations and selects, and written once, where XLA's seven fusions
read and write the whole stream each (`ops/segment._first_level`
chooses).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _intersect_kernel(a_ref, b_ref, o_ref):
    # words are the inner ("arbitrary") grid axis: the [block, 1] output
    # block stays resident across it and accumulates
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    cnt = lax.population_count(a_ref[...] & b_ref[...]).astype(jnp.int32)
    # pin the accumulator dtype: under x64, sum() promotes int32 to
    # int64, which the int32 output ref rejects
    o_ref[...] += cnt.sum(axis=1, keepdims=True, dtype=jnp.int32)


def _word_block(words: int) -> int:
    """Words per grid step: the widest lane-aligned divisor up to 512
    (two double-buffered [512, 512] uint32 operands are 4 MiB of VMEM),
    or the whole axis where it does not lane-align — bitmaps of under
    4096 vertices per shard, which are narrow."""
    for wb in (512, 256, 128):
        if words % wb == 0:
            return wb
    return words


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def intersect_count(a, b, block: int = 512, interpret: bool = False):
    """Row-wise |a_i AND b_i| popcount for packed uint32 bitmaps.

    a, b: [n, words] uint32 -> [n] int32.  `n` must be a multiple of
    `block` (callers pad; edge chunks already are).  The kernel writes
    an [n, 1] column: the chip's compiler refuses a 1-D `(block,)`
    output block ("XLA layout {0:T(1024)S(1)} does not match Mosaic
    layout {0:T(512)S(1)}"), and a keepdims column needs no
    sublane->lane relayout of the row sums.
    """
    n, words = a.shape
    if n % block != 0:
        raise ValueError(f"rows {n} not a multiple of block {block}")
    wblock = _word_block(words)
    out = pl.pallas_call(
        _intersect_kernel,
        grid=(n // block, words // wblock),
        in_specs=[
            pl.BlockSpec((block, wblock), lambda i, j: (i, j)),
            pl.BlockSpec((block, wblock), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)
    return out[:, 0]


def row_and_popcount(a, b, block: int = 512):
    """Dispatcher used by the LCC/k-clique kernels: the Pallas kernel on
    TPU when the tile shape allows, the XLA-fused path otherwise."""
    n = a.shape[0]
    if use_pallas() and n % block == 0:
        return intersect_count(a, b, block=block)
    return lax.population_count(a & b).sum(axis=1, dtype=jnp.int32)


def use_pallas() -> bool:
    """Compiled Pallas kernels run on the TPU backend only; elsewhere
    callers take the fused jnp path, or interpret mode where a caller
    asks for the kernel by name."""
    return jax.default_backend() == "tpu"


# ---- the pull's gather from a table resident in VMEM ----------------------

LANES = 128
SUBLANES = 8
# A chunk is one output vreg, 8 x 128 indices, and one SMEM slot; two
# slots are two copies of the unrolled body, which is what the kernel
# costs to compile (4-5 s).  Chunk pairs per grid step: 32,768 indices,
# so that the one DMA wait a step leaves exposed, and the step itself,
# are under 2% of it
_PAIRS = 16


def gather_table_budget() -> int:
    """Bytes of table `vmem_gather` may hold in VMEM: half of what the
    device reports (`pltpu.get_tpu_info()`; 64 MiB of the v5e's 128).
    The kernel asks for the table and 4 MiB of blocks as its scoped
    limit, and the other half stays XLA's, which keeps a shard's fold
    levels and CDLP's loop operands there (PERF.md section 5).  A
    Graph500 scale-22 table is 16 MiB, scale 24 exactly the budget;
    what is larger takes XLA's gather."""
    return pltpu.get_tpu_info().vmem_capacity_bytes // 2


def _gather_chunk(tab, rows, slot: int, lanes):
    """One output vreg: the 8 x 128 table values whose rows of `tab`
    (a table, or a slice of one, in VMEM) stand in SMEM slot `slot` of
    `rows` and whose places in those rows are `lanes`.  The arithmetic
    `_gather_kernel` describes, shared by the two gather kernels."""
    lane = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    sub = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def lane_step(j, acc):
        t = jnp.zeros((SUBLANES, LANES), tab.dtype)
        for s in range(SUBLANES):
            t = jnp.where(sub == s, tab[pl.ds(rows[slot, s, j], 1), :], t)
        return jnp.where(lane == j,
                         jnp.take_along_axis(t, lanes, axis=1), acc)

    # unrolled where the kernel is lowered, not where it is traced:
    # `j` becomes a constant there, so the SMEM offsets are static,
    # and the trace holds one step, not 128 (traced out in Python
    # the body cost every process 24 s on the chip's host)
    return lax.fori_loop(0, LANES, lane_step,
                         jnp.zeros((SUBLANES, LANES), tab.dtype),
                         unroll=True)


def _gather_kernel(idx_ref, tab_hbm, out_ref, tab, tab_sem, stage, rows,
                   sems, *, v: int, pairs: int):
    """One grid step: `pairs` times two chunks of 1024 indices.

    Per index the work is one `(1, 128)` load of table row `idx >> 7`,
    replicated over the sublanes (`vld` with sublane stride 0), and one
    select that puts it in its sublane; per eight indices one in-vreg
    lane gather by `idx & 127` (a single source vreg, which the chip's
    compiler accepts) and one select into the output vreg.  The address
    comes from a scalar, and the scalar slots are what bounded the
    first formulations (two a bundle): so the row numbers are taken in
    vregs, a chunk at a time, and moved VMEM -> SMEM by a local DMA
    into one of two slots, whose offsets are static (`sld` takes one
    register, and a slot's base or a loop's counter would use it).
    What is left an index is `sld`, the table's `scalar_lea` and the
    `vld`: 1.08 bundles, the one load slot being the limit (0.80 ns at
    Graph500 scale 21 against 8.61, PERF.md section 6)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        # once per call, one buffer: the table stays for every step
        cp = pltpu.make_async_copy(tab_hbm, tab, tab_sem)
        cp.start()
        cp.wait()

    def norm(i):
        # `full[nbr]`'s own rule, so that the two agree on every int32:
        # a negative index counts from the end, what is still outside
        # is clamped.  It also keeps the rows a ragged last block reads
        # inside the table.
        i = jnp.where(i < 0, i + v, i)
        return jnp.clip(i, 0, v - 1)

    def slot_copy(slot):
        return pltpu.make_async_copy(
            stage.at[slot], rows.at[slot], sems.at[slot])

    def send(slot, r0):
        stage[slot] = norm(idx_ref[pl.ds(r0, SUBLANES), :]) >> 7
        slot_copy(slot).start()

    def chunk(slot, r0):
        lanes = norm(idx_ref[pl.ds(r0, SUBLANES), :]) & (LANES - 1)
        out_ref[pl.ds(r0, SUBLANES), :] = _gather_chunk(
            tab, rows, slot, lanes)

    send(0, 0)

    def pair(k, carry):
        ra = pl.multiple_of(k * (2 * SUBLANES), SUBLANES)
        rb = pl.multiple_of(ra + SUBLANES, SUBLANES)
        slot_copy(0).wait()
        send(1, rb)
        chunk(0, ra)
        slot_copy(1).wait()

        @pl.when(k + 1 < pairs)
        def _():
            send(0, pl.multiple_of(rb + SUBLANES, SUBLANES))

        chunk(1, rb)
        return carry

    lax.fori_loop(0, pairs, pair, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vmem_gather(full, nbr, interpret: bool = False):
    """`full[nbr]` for a 1-D 32-bit table and int32 indices, bit for
    bit, on every int32 index.

    The table, viewed `[ceil(V / 128), 128]`, is copied to VMEM once
    and stays; the indices stream through in `[256, 128]` blocks and
    the output leaves in the same blocks (2-D: the chip's compiler
    refuses 1-D ones).  The last block may be ragged; a stream that is
    not whole 128s is padded first, which a CSR's never is."""
    v, n = full.shape[0], nbr.shape[0]
    vpad, npad = -v % LANES, -n % LANES
    tab = (jnp.pad(full, (0, vpad)) if vpad else full).reshape(-1, LANES)
    idx = (jnp.pad(nbr, (0, npad)) if npad else nbr).reshape(-1, LANES)
    nrows = idx.shape[0]
    pairs = min(_PAIRS, pl.cdiv(nrows, 2 * SUBLANES))
    blk = 2 * SUBLANES * pairs
    out = pl.pallas_call(
        functools.partial(_gather_kernel, v=v, pairs=pairs),
        grid=(pl.cdiv(nrows, blk),),
        in_specs=[
            pl.BlockSpec((blk, LANES), lambda g: (g, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((blk, LANES), lambda g: (g, 0)),
        # inside a `shard_map` that checks them, the output varies over
        # the mesh axes its operands vary over
        out_shape=jax.ShapeDtypeStruct(
            (nrows, LANES), full.dtype,
            vma=jax.typeof(idx).vma | jax.typeof(tab).vma),
        scratch_shapes=[
            pltpu.VMEM(tab.shape, tab.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.VMEM((2, SUBLANES, LANES), jnp.int32),
            pltpu.SMEM((2, SUBLANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            # the table is primed at step 0 and carried
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tab.size * 4 + (4 << 20),
        ),
        interpret=interpret,
        name="vmem_gather",
    )(idx, tab)
    out = out.reshape(-1)
    return out[:n] if npad else out


# ---- the fold's row ends, from slices of the scanned stream in VMEM -------

# Rows of 128 in a slice of the stream: 4 MiB, two of which the
# pipeline holds; a Graph500 scale-21 stream is 64 of them
_SLICE_ROWS = 8192
# Rows of 128 in a block of row ends: 4,096 indices, four chunks
_END_ROWS = 32


def _row_gather_kernel(blk_ref, sl_ref, idx_ref, tab_ref, out_ref, stage,
                       rows, sem, *, slice_rows: int, chunks: int):
    """One work item: the index block `blk_ref[k]` against the slice
    `sl_ref[k]` of the table, both placed by the pipeline.  A block's
    items follow one another, so its output block stays in VMEM from
    the first to the last of them and each fills in the indices that
    lie in its slice.  `_gather_kernel`'s chunk with one SMEM slot:
    the indices are a row's width of the fold's stream, and the wait
    for the slot is what the second one would hide."""
    k = pl.program_id(0)
    before = jnp.maximum(k - 1, 0)

    # items past the real ones repeat the last: nothing moves, nothing
    # to do
    @pl.when((k == 0) | (blk_ref[k] != blk_ref[before])
             | (sl_ref[k] != sl_ref[before]))
    def _():
        span = slice_rows * LANES
        base = sl_ref[k] * span
        cp = pltpu.make_async_copy(stage.at[0], rows.at[0], sem)

        def chunk(c, carry):
            at = pl.ds(pl.multiple_of(c * SUBLANES, SUBLANES), SUBLANES)
            i = idx_ref[at, :] - base
            inside = (i >= 0) & (i < span)
            i = jnp.clip(i, 0, span - 1)
            stage[0] = i >> 7
            cp.start()
            cp.wait()
            got = _gather_chunk(tab_ref, rows, 0, i & (LANES - 1))
            out_ref[at, :] = jnp.where(inside, got, out_ref[at, :])
            return carry

        lax.fori_loop(0, chunks, chunk, 0)


@functools.partial(
    jax.jit, static_argnames=("interpret", "slice_rows", "end_rows"))
def vmem_row_gather(table, idx, interpret: bool = False,
                    slice_rows: int = _SLICE_ROWS,
                    end_rows: int = _END_ROWS):
    """`table[idx]` for a 1-D 32-bit table of any length and int32
    indices that are in bounds and do not decrease (the caller's
    promise), bit for bit.

    The table, viewed `[ceil(E / 128), 128]`, passes through VMEM a
    slice at a time; the indices come in blocks, and because they are
    sorted a block meets only the slices from its first index's to its
    last's.  The grid runs over those (block, slice) pairs, at most
    blocks + slices - 1 of them whatever the indices are, block by
    block: two scalar-prefetch arrays name each step's pair, the block
    specs read them, and a slice or an output block that stays from
    one step to the next is not copied again.  `slice_rows` and
    `end_rows` (rows of 128, whole 8s) are the tests', which cannot
    afford the real ones' slices."""
    e, n = table.shape[0], idx.shape[0]
    epad, npad = -e % LANES, -n % LANES
    tab = (jnp.pad(table, (0, epad)) if epad else table).reshape(-1, LANES)
    idx = (jnp.pad(idx, (0, npad), mode="edge") if npad else idx).reshape(
        -1, LANES)
    nrows = idx.shape[0]
    end_rows = min(end_rows, pl.cdiv(nrows, SUBLANES) * SUBLANES)
    slice_rows = min(slice_rows, tab.shape[0])
    nb, nc = pl.cdiv(nrows, end_rows), pl.cdiv(tab.shape[0], slice_rows)

    # the pairs, from each block's first and last index
    opens = jnp.arange(nb, dtype=jnp.int32) * end_rows
    closes = jnp.minimum(opens + end_rows, nrows) - 1
    lo = jnp.clip(idx[opens, 0] // (slice_rows * LANES), 0, nc - 1)
    hi = jnp.clip(idx[closes, LANES - 1] // (slice_rows * LANES), lo, nc - 1)
    upto = jnp.cumsum(hi - lo + 1, dtype=jnp.int32)
    k = jnp.minimum(jnp.arange(nb + nc, dtype=jnp.int32), upto[-1] - 1)
    blk = jnp.searchsorted(upto, k, side="right").astype(jnp.int32)
    sl = hi[blk] - (upto[blk] - 1 - k)

    out = pl.pallas_call(
        functools.partial(_row_gather_kernel, slice_rows=slice_rows,
                          chunks=end_rows // SUBLANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb + nc,),
            in_specs=[
                pl.BlockSpec((end_rows, LANES), lambda k, b, s: (b[k], 0)),
                pl.BlockSpec((slice_rows, LANES), lambda k, b, s: (s[k], 0)),
            ],
            out_specs=pl.BlockSpec((end_rows, LANES),
                                   lambda k, b, s: (b[k], 0)),
            scratch_shapes=[
                pltpu.VMEM((1, SUBLANES, LANES), jnp.int32),
                pltpu.SMEM((1, SUBLANES, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (nrows, LANES), table.dtype,
            vma=jax.typeof(idx).vma | jax.typeof(tab).vma),
        compiler_params=pltpu.CompilerParams(
            # an output block is carried over its items
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * slice_rows * LANES * 4 + (4 << 20),
        ),
        interpret=interpret,
        name="vmem_row_gather",
    )(blk, sl, idx, tab)
    out = out.reshape(-1)
    return out[:n] if npad else out


# ---- the scan's first level, in one pass through VMEM ---------------------

# Rows of 128 in a block of the stream: 1 MiB a stream, and the
# pipeline holds two each of the values, the ids and the output
_SCAN_ROWS = 2048
# Rows of 128 the seven steps are done on at a time, in registers: 16
# vregs of values and 16 of ids.  A step waits for its rotations, so a
# chunk takes about 0.59 us whatever it holds up to here (8.77 ms at
# Graph500 scale 21 with 32 rows, 2.41 with 128, 1.84 with 256, whose
# code is a third larger: PERF.md section 6, PR 47)
_SCAN_CHUNK = 128


def tile_scan_floor() -> int:
    """Bytes of a scan's three streams (the values, the ids and the
    scanned values) over which `tile_scan` takes their first level:
    what the device reports as its VMEM (`pltpu.get_tpu_info()`; 128
    MiB on the v5e).  Streams that fit there XLA keeps there between
    its seven steps, with their neighbours fused in: alone the kernel
    still wins on them (0.33 ms for 0.46 on a serving lane's 8.4M
    entries), inside a runner it does not (`serve-g500-s18.keys8`
    -0.7%, `road-like-cc.wcc` +0.4%; PERF.md section 6, PR 47), so
    they keep XLA's steps and their runners the text they had."""
    return pltpu.get_tpu_info().vmem_capacity_bytes


def _tile_scan_kernel(v_ref, i_ref, out_ref, *, combine, chunk: int,
                      chunks: int):
    """One block of tiles: `ops/segment._segmented_scan`'s first level,
    its seven distances in its order with its operands, on `chunk` rows
    at a time.  At distance d a lane takes in the lane d below it (a
    rotation along the lanes, of the values and of the ids) where the
    ids agree; a lane under d sees the id -1 there, as `_shift` fills
    it, and keeps its value.  A tile is a row, so nothing passes from
    one row to the next, and the rows a ragged last block brings along
    cost nothing but their time."""
    lane = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)

    def step(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        v, i = v_ref[at, :], i_ref[at, :]
        d = 1
        while d < LANES:
            below = jnp.where(lane >= d, pltpu.roll(i, d, 1), -1)
            v = jnp.where(i == below, combine(v, pltpu.roll(v, d, 1)), v)
            d *= 2
        out_ref[at, :] = v
        return carry

    lax.fori_loop(0, chunks, step, 0)


@functools.partial(
    jax.jit, static_argnames=("combine", "interpret", "block_rows", "chunk"))
def tile_scan(values, ids, combine, interpret: bool = False,
              block_rows: int = _SCAN_ROWS, chunk: int = _SCAN_CHUNK):
    """The first level of `ops/segment._segmented_scan`, bit for bit:
    each tile of 128 (a row of the `[E / 128, 128]` views `values`, 32
    bits wide, and `ids`, int32) scanned by `combine` (`jnp.add`,
    `jnp.minimum`, ...: the fold's own), restarting where the ids
    change.

    The two streams pass through VMEM once in blocks of `block_rows`
    tiles and the scanned block leaves once; the seven steps between
    stay in registers.  The last block may be ragged.  `block_rows`
    and `chunk` (whole 8s, the one a multiple of the other) are the
    tests', which scan a few tiles."""
    nrows = values.shape[0]
    chunk = min(chunk, pl.cdiv(nrows, SUBLANES) * SUBLANES)
    block_rows = min(block_rows, pl.cdiv(nrows, chunk) * chunk)
    spec = pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
    return pl.pallas_call(
        functools.partial(_tile_scan_kernel, combine=combine, chunk=chunk,
                          chunks=block_rows // chunk),
        grid=(pl.cdiv(nrows, block_rows),),
        in_specs=[spec, spec],
        out_specs=spec,
        # inside a `shard_map` that checks them, the output varies over
        # the mesh axes its operands vary over
        out_shape=jax.ShapeDtypeStruct(
            values.shape, values.dtype,
            vma=jax.typeof(values).vma | jax.typeof(ids).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="tile_scan",
    )(values, ids)
