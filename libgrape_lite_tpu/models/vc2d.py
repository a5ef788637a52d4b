"""2-D vertex-cut min-fold apps — SSSP/BFS/WCC on the SUMMA mesh.

The tentpole of ROADMAP item 2 (PR 10): promote the vertex-cut seed
side-path (fragment/vertexcut.py, until now PageRankVC-only) to a
first-class execution path for the tropical-min LDBC apps, so
hub-heavy graphs stop paying the edge-cut pathology (docs/
SCALE_NOTES.md: at RMAT scale 12 a degree-correlated 1-D cut makes
99% of edges boundary edges and every shard pays the hub shard's Ep).
SparseP (arxiv 2201.05072) is the blueprint: equally-wide 2-D tiles
bound both per-tile compute and per-tile collective volume.

Layout (fragment (i, j) = mesh device (i, j), fid = i*k + j):

  * tile (i, j) holds the COO block of edges src ∈ chunk_i x
    dst ∈ chunk_j (undirected graphs are symmetrised at build, like
    the 1-D loader, so ONE dst-side pull per round covers both
    directions);
  * the master carry (dist/depth/comp) is sharded 1-D by row chunk:
    the [k*vc] leaf rides P(vcrow) — device (i, j) holds chunk i,
    replicated along the column axis.  That replication IS the
    "broadcast source values along the column axis" of the SUMMA
    round: every tile reads its source chunk locally.

Per round (inceval):

  1. local scatter-reduce: candidates over the tile's edges fold into
     [vc] row partials for chunk j via ops/segment.py;
  2. pmin along the row axis completes chunk j (column-sharded);
  3. ONE transpose ppermute ((i,j) -> (j,i)) re-aligns the completed
     fold row-sharded, and the master fold + termination vote run on
     the row copy.

Identity argument (pinned in tests/test_partition2d.py): min is
associative and commutative, and every candidate `value[src] (+ w)`
is computed from exactly the operands the 1-D pull uses — regrouping
the fold across tiles is bit-exact, so SSSP/BFS/WCC results are
byte-identical to the 1-D path.  (Sum folds — PageRankVC — regroup
float partials and are eps-identical instead.)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from libgrape_lite_tpu.app.base import GatherScatterAppBase, StepContext
from libgrape_lite_tpu.parallel.comm_spec import VC_COL_AXIS, VC_ROW_AXIS
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_INT_SENT = np.iinfo(np.int32).max
_OUT_SENTINEL = np.iinfo(np.int64).max  # BFS prints the reference's max


def vc_transpose(x, k):
    """Swap row/col sharding of a chunk-sharded per-device block:
    device (i, j) exchanges with (j, i) — one ppermute over the joint
    axis (diagonal devices self-map, so the average per-device ICI
    volume is (1 - 1/k) * |x|; the planner's byte model prices it
    that way)."""
    if k == 1:
        return x
    perm = [(i * k + j, j * k + i) for i in range(k) for j in range(k)]
    return lax.ppermute(x, (VC_ROW_AXIS, VC_COL_AXIS), perm)


def vc_source_carry(frag, source, app_name: str, fill, hit, dtype):
    """`[k*vc]` gpid-space carry seeded at `source` — or `[B, k*vc]`
    when `source` is a sequence (the batched init contract of
    `batch_query_key`, the vc2d analogue of app.base's
    source_lane_array).  Out-of-range sources leave their lane all
    `fill` (every vertex unreachable), logged like the 1-D apps."""
    batched = isinstance(source, (list, tuple, np.ndarray))
    srcs = np.asarray(
        source if batched else [source], dtype=np.int64
    ).reshape(-1)
    arr = np.full((len(srcs), frag.k * frag.vc), fill, dtype=dtype)
    for b, s in enumerate(srcs):
        if 0 <= s < frag.k * frag.chunk:
            arr[b, int(frag.oid_to_gpid(np.array([s]))[0])] = hit
        else:
            from libgrape_lite_tpu.utils import logging as glog

            glog.log_info(
                f"{app_name}: source {int(s)!r} is outside the oid "
                "space; all vertices will be unreachable"
            )
    return arr if batched else arr[0]


def vc_finalize_rows(frag, flat: np.ndarray) -> np.ndarray:
    """Compact a gpid-space [k*vc] result into [fnum, vc] rows aligned
    with inner_oids order (masters = diagonal fragments) — the Worker
    output contract shared by every vertex-cut app.  A carry leaf that
    spans non-addressable devices (jax.distributed) is gathered via
    process_allgather first — np.asarray on it would throw (the PR 18
    edgecut bug class; same idiom as worker.result_values)."""
    if not getattr(flat, "is_fully_addressable", True):
        from jax.experimental import multihost_utils

        flat = np.asarray(multihost_utils.process_allgather(flat))
    vals = np.asarray(flat).reshape(frag.k, frag.vc)
    out = np.zeros((frag.fnum, frag.vc), dtype=vals.dtype)
    for c in range(frag.k):
        oids = frag.inner_oids(c * frag.k + c)
        offs = oids % frag.chunk
        out[c * frag.k + c, : len(oids)] = vals[c, offs]
    return out


class VC2DMinAppBase(GatherScatterAppBase):
    """Shared scaffolding of the tropical-min vertex-cut apps: the
    row-sharded carry, the SUMMA round and the diagonal-master
    finalize.  Subclasses declare `state_key` and the candidate
    builder."""

    load_strategy = LoadStrategy.kNullLoadStrategy
    message_strategy = MessageStrategy.kGatherScatter
    mesh_kind = "vc2d"
    state_key = ""          # the carry leaf ("dist"/"depth"/"comp")

    def custom_specs(self):
        return {
            self.state_key: P(VC_ROW_AXIS),
            "vmask_row": P(VC_ROW_AXIS),
        }

    # ---- shared init scaffolding ----

    def _init_common(self, frag, carry: np.ndarray):
        """Carry + ephemeral leaves and the partition fingerprint facts
        that key the compiled-runner cache (a 1-D and a 2-D compile
        must never share an entry — `k` and the mode ride in trace_key
        as primitive attributes)."""
        self.check_tiles(frag)
        state = {self.state_key: carry}
        eph_entries = {"vmask_row": frag.vertex_mask()}
        self._partition = "2d"
        self._mesh_k = frag.k
        self._partition_stats = frag.tile_stats()
        # decided on the HOST fragment (the traced VCDeviceFragment
        # carries only geometry); a primitive, so it rides trace_key
        self._src_pull = self._wants_src_pull(frag)
        state.update(eph_entries)
        self.ephemeral_keys = frozenset(eph_entries)
        return state

    def _wants_src_pull(self, frag) -> bool:
        """Directed WCC pulls the src side too (weak connectivity needs
        both directions; undirected tiles are symmetrised instead)."""
        return False

    # ---- the SUMMA round ----

    def peval(self, ctx: StepContext, frag, state):
        # like the 1-D pull apps: the first pull round subsumes the
        # reference's source-only PEval
        return state, jnp.int32(1)

    def _dst_partial(self, ctx, frag, val_row, state):
        """Tile-local candidates folded into [vc] chunk-j partials
        (pull into dst)."""
        raise NotImplementedError

    def _src_partial(self, ctx, frag, val_col, state):
        """Optional src-side partials (directed WCC)."""
        raise NotImplementedError

    def inceval(self, ctx: StepContext, frag, state):
        k, vc = frag.k, frag.vc
        val = state[self.state_key]  # [vc] chunk i (row copy)
        partial = self._dst_partial(ctx, frag, val, state)
        relax_col = lax.pmin(partial, VC_ROW_AXIS)  # complete chunk j
        relax_row = vc_transpose(relax_col, k)      # re-align to chunk i
        if self._src_pull:
            val_col = vc_transpose(val, k)          # chunk j copy
            partial2 = self._src_partial(ctx, frag, val_col, state)
            relax_row = jnp.minimum(
                relax_row, lax.pmin(partial2, VC_COL_AXIS)
            )
        new = jnp.minimum(val, relax_row)
        changed = jnp.logical_and(new < val, state["vmask_row"])
        # each column of devices holds all k chunks once: the psum
        # over vcrow IS the global changed count, identical everywhere
        active = lax.psum(changed.sum().astype(jnp.int32), VC_ROW_AXIS)
        return {self.state_key: new}, active

    def finalize(self, frag, state):
        return vc_finalize_rows(frag, np.asarray(state[self.state_key]))


class SSSPVC2D(VC2DMinAppBase):
    """SSSP on the 2-D mesh: tropical relax `min(dist[src] + w)` per
    tile, completed by the row-axis pmin — byte-identical to the 1-D
    pull (same adds, min regrouping is exact)."""

    state_key = "dist"
    result_format = "sssp_infinity"
    needs_edata = True
    batch_query_key = "source"

    def init_state(self, frag, source=0):
        import jax

        if not frag.weighted:
            raise ValueError(
                "SSSP requires edge weights; build the vertex-cut "
                "fragment with weights (use bfs_vc for unit-weight "
                "traversal)"
            )
        _, _, w_arr, _ = frag._host_tiles
        dtype = w_arr.dtype
        if not jax.config.jax_enable_x64:
            dtype = np.float32
        dist = vc_source_carry(
            frag, source, "SSSPVC2D", np.inf, 0.0, dtype
        )
        return self._init_common(frag, dist)

    def _dst_partial(self, ctx, frag, val_row, state):
        vc = frag.vc
        inf = jnp.asarray(jnp.inf, val_row.dtype)
        cand = jnp.where(frag.mask, val_row[frag.src % vc] + frag.w, inf)
        return self.segment_reduce(cand, frag.dst % vc, vc, "min")

    def invariants(self, frag, state):
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("dist", lo=0.0),
            monotone_non_increasing("dist"),
        ]


class BFSVC2D(VC2DMinAppBase):
    """BFS levels on the 2-D mesh: unit-weight tropical relax
    `min(depth[src] + 1)` — byte-identical to the 1-D pull."""

    state_key = "depth"
    result_format = "int"
    batch_query_key = "source"

    def init_state(self, frag, source=0):
        depth = vc_source_carry(
            frag, source, "BFSVC2D", _INT_SENT, 0, np.int32
        )
        return self._init_common(frag, depth)

    def _dst_partial(self, ctx, frag, val_row, state):
        vc = frag.vc
        sent = jnp.int32(_INT_SENT)
        nb = val_row[frag.src % vc]
        cand = jnp.where(
            jnp.logical_and(frag.mask, nb != sent), nb + 1, sent
        )
        return self.segment_reduce(cand, frag.dst % vc, vc, "min")

    def invariants(self, frag, state):
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("depth", lo=0, hi=_INT_SENT),
            monotone_non_increasing("depth"),
        ]

    def finalize(self, frag, state):
        out = vc_finalize_rows(
            frag, np.asarray(state["depth"]).astype(np.int64)
        )
        return np.where(out == _INT_SENT, _OUT_SENTINEL, out)


class WCCVC2D(VC2DMinAppBase):
    """WCC on the 2-D mesh: min-gpid label propagation.  gpid order is
    oid order (contiguous chunks), so the converged representative is
    the min-OID member — the same vertex the 1-D map-partitioned path
    canonicalises to, making the finalized labels byte-identical.

    Directed graphs pull BOTH tile orientations per round (weak
    connectivity) from the same carry snapshot; the fixed point is the
    unique per-component min either way, but round counts can differ
    from the 1-D path's dependent second pull, so the byte-identity
    pin covers the undirected form."""

    state_key = "comp"
    result_format = "int"

    def _wants_src_pull(self, frag) -> bool:
        return bool(frag.directed) and not frag.symmetrized

    def init_state(self, frag, **_):
        gpids = np.arange(frag.k * frag.vc, dtype=np.int32)
        comp = np.where(frag.vertex_mask(), gpids, _INT_SENT).astype(
            np.int32
        )
        return self._init_common(frag, comp)

    def _label_partial(self, ctx, frag, table, rows, cols):
        vc = frag.vc
        big = jnp.int32(_INT_SENT)
        cand = jnp.where(frag.mask, table[cols % vc], big)
        return self.segment_reduce(cand, rows % vc, vc, "min")

    def _dst_partial(self, ctx, frag, val_row, state):
        return self._label_partial(
            ctx, frag, val_row, frag.dst, frag.src
        )

    def _src_partial(self, ctx, frag, val_col, state):
        return self._label_partial(
            ctx, frag, val_col, frag.src, frag.dst
        )

    def invariants(self, frag, state):
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("comp", lo=0, hi=_INT_SENT),
            monotone_non_increasing("comp"),
        ]

    def finalize(self, frag, state):
        comp = np.asarray(state["comp"]).astype(np.int64)
        out = vc_finalize_rows(frag, comp)
        # canonicalise label -> representative oid (pure arithmetic:
        # gpid encodes the oid) — matching the 1-D WCC finalize
        return np.where(
            out == _INT_SENT, -1, frag.gpid_to_oid(out)
        )
