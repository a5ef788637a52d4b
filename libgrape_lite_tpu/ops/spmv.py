"""Strict-tile SpMV — the Pallas analogue of the reference's LBSTRICT
edge-balanced kernel (`grape/cuda/parallel/parallel_engine.h:847-1013`).

The framework's default SpMV is gather + XLA `segment_sum`
(ops/segment.py).  That path's TPU lowering is a sorted scatter-add;
its weakness is the scatter's serialization on hot rows.  This kernel
replaces the scatter with MXU work:

  * edges (sorted by row, as every CSR here stores them) are cut into
    fixed tiles of `tile` edges — exact edge balance, the strict
    policy's defining property;
  * each tile's row span [row_lo, row_lo + rmax) is known on the host
    (`plan_tiles`); `rmax` is the worst span over tiles;
  * a Pallas program per tile builds the one-hot indicator
    `[rmax, tile]` (edge e hits local row src[e]-row_lo) and contracts
    it with the per-edge values on the MXU — per-tile partial row sums,
    no scatter;
  * a single XLA scatter-add of `[num_tiles, rmax]` partials (≪ E
    elements) folds tile boundaries.

The tradeoff is explicit: MXU MACs per tile = tile × rmax.  On
hub-dominated tiles (power-law graphs) rmax is tiny and the kernel is
pure wins; on degree-1 tails rmax → tile and the indicator matmul
wastes FLOPs.  `segment_sum_auto` + `plan_for_app` pick per-shape: the
kernel when the planned rmax is small relative to the tile (dense
rows, `strict_worthwhile`), the XLA path otherwise — the same
adaptivity the reference gets from choosing cm/wm/strict per app.
PageRank's pull consumes this (models/pagerank.py); `GRAPE_SPMV`
(auto|strict|xla) overrides the choice for A/B runs.

A/B-measure with `scripts/spmv_ab.py` on real TPU before changing any
default (VERDICT r1 next-round item 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


LANE = 128  # vector lane width: tile row spans must lane-align


def _align_rmax(span: int) -> int:
    """Lane-align a tile row span so the kernel's [tile, rmax] matmul
    output tiles cleanly — the single sizing rule for every path
    through plan_tiles (the empty-edge case included, which used to
    hardcode the literal)."""
    return max(LANE, -(-span // LANE) * LANE)


def plan_tiles(edge_src_sorted: np.ndarray, tile: int, vp: int):
    """Host-side strict tiling of a row-sorted edge array (padding rows
    `vp` included — they land in the sliced-off overflow row).

    Returns (row_lo [num_tiles] int32, rmax int, num_tiles int).
    """
    e = len(edge_src_sorted)
    if e == 0:
        # degenerate shard: one all-pad tile at the minimal aligned
        # span (derived, not hardcoded — plan_for_app additionally
        # rejects fully-empty fragments so no indicator matmul runs
        # for zero real edges)
        return np.zeros(1, dtype=np.int32), _align_rmax(1), 1
    # span planning must ignore pad edges (src == vp): a boundary tile
    # mixing the last real row with pads would otherwise inflate rmax to
    # ~vp, and the worst span sizes EVERY tile's [tile, rmax] matmul.
    # Pad edges clamp to the last real row for planning; in the kernel
    # their one-hot row is row_lo + (vp - row_lo) >= the clamp point, so
    # they only ever credit the sliced-off overflow row.
    real = edge_src_sorted[edge_src_sorted < vp]
    last_real = int(real[-1]) if len(real) else 0
    src_plan = np.minimum(edge_src_sorted, last_real)
    num_tiles = -(-e // tile)
    starts = np.arange(num_tiles, dtype=np.int64) * tile
    ends = np.minimum(starts + tile, e) - 1
    row_lo = src_plan[starts].astype(np.int32)
    row_hi = src_plan[ends].astype(np.int32)
    rmax = _align_rmax(int((row_hi - row_lo).max()) + 1)
    return row_lo, rmax, num_tiles


def _spmv_tile_kernel(local_ref, val_ref, out_ref, *, rmax):
    local = local_ref[0]  # [1, tile] int32: edge row - the tile's row_lo
    val = val_ref[0].astype(jnp.float32)  # [1, tile]
    tile = local.shape[-1]
    # one-hot of each edge against the tile's row window, rows down the
    # sublanes: the [1, tile] edge row broadcasts along them as it lies
    rows = jax.lax.broadcasted_iota(jnp.int32, (rmax, tile), 0)
    onehot_t = (rows == local).astype(jnp.float32)
    # [1, tile] x [rmax, tile]^T on the MXU -> per-row partial sums.
    # HIGHEST: the MXU's default single bf16 pass rounds `val` to 8
    # mantissa bits (2e-3 relative on row sums of uniform values on a
    # v5e, against 1e-7 here: chip run, PR 22); the one-hot side is
    # exact in any precision.
    out_ref[0] = jax.lax.dot_general(
        val, onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("tile", "rmax", "num_tiles", "vp", "interpret")
)
def _spmv_partials(values, edge_src, row_lo, tile, rmax, num_tiles, vp,
                   interpret=False):
    e_pad = num_tiles * tile
    pad = e_pad - values.shape[0]
    if pad:
        # padded edges carry value 0 into row `vp` (overflow)
        values = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
        edge_src = jnp.concatenate(
            [edge_src, jnp.full((pad,), vp, edge_src.dtype)]
        )
    # each edge's row relative to its tile's window, taken here in XLA:
    # in the kernel `row_lo_ref[program_id]` is a dynamic scalar read of
    # a VMEM vector, which the chip's compiler refuses ("cannot
    # statically prove that index in dimension 0 is a multiple of 256")
    local = (
        edge_src.astype(jnp.int32).reshape(num_tiles, tile)
        - row_lo.astype(jnp.int32)[:, None]
    )
    # Mosaic requires the last two block dims to be (8,128)-divisible
    # or equal to the array dims — a singleton middle dim satisfies
    # that for per-tile [1, tile] blocks (tests/test_pallas_lowering.py
    # guards the lowering offline, chip_smoke.py the chip's compile)
    grid_spec = pl.GridSpec(
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rmax), lambda i: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_spmv_tile_kernel, rmax=rmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_tiles, 1, rmax), jnp.float32),
        interpret=interpret,
    )(
        local.reshape(num_tiles, 1, tile),
        values.reshape(num_tiles, 1, tile),
    )
    return out.reshape(num_tiles, rmax)


def spmv_strict(values, edge_src, row_lo, vp: int, tile: int, rmax: int,
                interpret: bool | None = None):
    """Strict-tile segment-sum of `values` by sorted `edge_src` into
    [vp] rows (drop-in for ops.segment.segment_reduce(..., "sum") on
    sorted float inputs).  `row_lo` may be host numpy or a traced
    per-shard array (shard_map callers pass their slice).
    `interpret=None` auto-selects: compiled on TPU, interpreter
    elsewhere (CPU backends can't lower Pallas)."""
    if interpret is None:
        from libgrape_lite_tpu.ops.pallas_kernels import use_pallas

        interpret = not use_pallas()
    num_tiles = row_lo.shape[0]
    with jax.named_scope("grape.pull.strict"):
        partials = _spmv_partials(
            values, edge_src, jnp.asarray(row_lo), tile, rmax, num_tiles,
            vp, interpret=interpret,
        )
        # fold tile partials: rows of tile t live at row_lo[t] + [0, rmax)
        idx = jnp.asarray(row_lo, jnp.int32)[:, None] + jnp.arange(
            rmax, dtype=jnp.int32
        )
        idx = jnp.minimum(idx, vp)  # clamp into the overflow row
        out = jnp.zeros((vp + 1,), jnp.float32)
        out = out.at[idx.reshape(-1)].add(partials.reshape(-1))
        return out[:vp]


def strict_worthwhile(rmax: int, tile: int) -> bool:
    """Adoption heuristic: the indicator matmul costs tile*rmax MACs
    for tile useful adds — accept up to 8 lanes of row window per
    128-edge MXU pass (hub-heavy tiles), reject degree-1 tails."""
    return rmax * 16 <= tile


_PLAN_CACHE: "weakref.WeakKeyDictionary" = None  # set on first use


def plan_for_app(frag, vp: int, dtype, tile: int = 2048,
                 mode: str | None = None):
    """Host-side SpMV planning for a fragment's in-edge array: returns
    (row_lo [fnum, num_tiles] int32, tile, rmax) when the strict kernel
    should serve this app's segment-sums, else None (XLA `segment_sum`).

    Selection (`GRAPE_SPMV` env: auto|strict|xla, default auto):
      * `xla` — never;
      * `strict` — always (A/B runs; interpret-mode off-TPU);
      * `auto` — only on a real TPU backend, float32 values (the MXU
        path accumulates in f32; f64 states keep XLA), and
        `strict_worthwhile` on the worst tile span.

    The cheap mode/backend/dtype rejections run BEFORE the O(E)
    device-to-host copy + tile scan, and accepted plans are cached per
    fragment — queries repeat, topology does not.
    """
    import os
    import weakref

    mode = mode or os.environ.get("GRAPE_SPMV", "auto")
    if mode == "xla":
        return None
    if mode != "strict":
        from libgrape_lite_tpu.ops.pallas_kernels import use_pallas

        if not use_pallas():
            return None
        if np.dtype(dtype) != np.float32:
            return None

    global _PLAN_CACHE
    if _PLAN_CACHE is None:
        _PLAN_CACHE = weakref.WeakKeyDictionary()
    key = (tile, vp)
    cached = _PLAN_CACHE.get(frag, {}).get(key)
    if cached is None:
        edge_src_stacked = np.asarray(frag.dev.ie.edge_src)
        fnum = edge_src_stacked.shape[0]
        if not (edge_src_stacked < vp).any():
            # zero real edges on every shard: a [tile, rmax] indicator
            # matmul for nothing — let XLA's trivial segment_sum serve
            _PLAN_CACHE.setdefault(frag, {})[key] = False
            return None
        plans = [
            plan_tiles(edge_src_stacked[f], tile, vp) for f in range(fnum)
        ]
        rmax = max(p[1] for p in plans)
        row_lo = np.stack([p[0] for p in plans]).astype(np.int32)
        cached = (row_lo, tile, rmax)
        _PLAN_CACHE.setdefault(frag, {})[key] = cached
    if cached is False:  # cached empty-fragment rejection
        return None
    row_lo, tile, rmax = cached
    if mode != "strict" and not strict_worthwhile(rmax, tile):
        return None
    return row_lo, tile, rmax


def segment_sum_auto(values, edge_src, vp: int, plan=None, row_ptr=None):
    """Sorted segment-sum routed per the host plan: the strict-tile
    Pallas kernel when `plan` is a (row_lo_local, tile, rmax) triple
    (row_lo_local = this shard's [num_tiles] slice), otherwise
    `ops/segment.segment_reduce`, by scan where the caller has the
    CSR's `row_ptr`."""
    if plan is None:
        from libgrape_lite_tpu.ops.segment import segment_reduce

        return segment_reduce(values, edge_src, vp, "sum", row_ptr=row_ptr)
    row_lo, tile, rmax = plan
    return spmv_strict(values, edge_src, row_lo, vp, tile, rmax)
