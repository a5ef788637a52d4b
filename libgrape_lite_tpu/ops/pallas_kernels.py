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
