"""PageRankVC — PageRank on vertex-cut storage via gather-scatter.

Re-design of `examples/analytical_apps/pagerank/pagerank_vc.h` +
`GatherScatterMessageManager`
(`grape/parallel/gather_scatter_message_manager.h:28-399`):

  * degree = # of appearances as src or dst (the stored edge list is
    the raw directed file; accumulation flows both directions,
    `pagerank_vc.h` IncEval),
  * per-round: every fragment adds `curr[src] -> next[dst]` and
    `curr[dst] -> next[src]` over its edge block, partial sums are
    gathered to masters (`GatherMasterVertices` with NumericSum),
  * master update `(base + d·sum)/deg` (final round: `d·sum + base`),
    then ScatterMasterVertices.

TPU formulation (SUMMA): the k x k fragment grid IS a 2-D device mesh
(`CommSpec.mesh2d`, axes vcrow/vccol; fragment (i, j) holds the edge
block src∈chunk_i x dst∈chunk_j).  Master state is SHARDED, not
replicated: device (i, j) keeps rank/deg for chunk i (row copy) and
chunk j (column copy) — O(N/k) per device, realizing the 2-D
partition's memory advantage
(`immutable_vertexcut_fragment.h:82-148`).  Per round:

  * both directions are ONE pull over the tile's CSR
    (fragment/vertexcut.py `VCPullFragment`): `pull_gather` reads the
    table `[row copy; column copy]`, `segment_reduce(row_ptr=)` folds
    2 vc rows by scan — rows 0..vc-1 the sums into the tile's
    destinations, rows vc..2 vc-1 into its sources; no E-wide scatter
    and no XLA element gather (`grape.pull.gather`, `grape.pull.fold`);
  * `grape.vc.gather_master`: the destination sums psum over `vcrow`
    → complete chunk-j sums, column-sharded; the source sums psum over
    `vccol` → row-sharded (the GatherToMaster segment-reduce);
  * `grape.vc.scatter`: ONE transpose `ppermute` ((i,j)→(j,i)) aligns
    the row-sharded sums with the column copy, and after the master
    update on the column copy (`grape.app.update`) a second one
    refreshes the row copy (ScatterToFragment).

PEval takes the degrees from the CSR's offsets.  The state is 32-bit
where x64 is off (the chip), as `PageRank`'s, so the gather is the
VMEM kernel's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from libgrape_lite_tpu.app.base import GatherScatterAppBase, StepContext
from libgrape_lite_tpu.models.vc2d import vc_transpose as _transpose
from libgrape_lite_tpu.ops.segment import pull_gather, segment_reduce
from libgrape_lite_tpu.parallel.comm_spec import VC_COL_AXIS, VC_ROW_AXIS
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy


class PageRankVC(GatherScatterAppBase):
    load_strategy = LoadStrategy.kNullLoadStrategy
    message_strategy = MessageStrategy.kGatherScatter
    result_format = "float"
    mesh_kind = "vc2d"
    tile_layout = "pull"
    replicated_keys = frozenset({"step", "dangling_sum", "total_dangling"})

    def __init__(self, delta: float = 0.85, max_round: int = 10):
        self.delta = delta
        self.max_round = max_round

    def custom_specs(self):
        return {
            "rank_col": P(VC_COL_AXIS), "rank_row": P(VC_ROW_AXIS),
            "deg_col": P(VC_COL_AXIS), "vmask_col": P(VC_COL_AXIS),
        }

    def init_state(self, frag, delta: float | None = None,
                   max_round: int | None = None):
        if delta is not None:
            self.delta = delta
        if max_round is not None:
            self.max_round = max_round
        self.check_tiles(frag)
        # partition fingerprint (r10): keys the runner cache apart
        # from any 1-D compile and feeds the obs query span's tile
        # record (trace_report's tile table)
        self._partition = "2d"
        self._mesh_k = frag.k
        self._partition_stats = frag.tile_stats()
        n_pad = frag.dev.n_pad
        vmask = frag.vertex_mask()
        # 32 bits where x64 is off, as PageRank's state: the kernels'
        # kind of value; f64 under x64 (the CPU golden lanes)
        dt = np.float64 if jax.config.jax_enable_x64 else np.float32
        return {
            # global [k*vc] leaves; placement shards them into [vc]
            # row/col chunk copies per device
            "rank_col": np.zeros(n_pad, dtype=dt),
            "rank_row": np.zeros(n_pad, dtype=dt),
            "deg_col": np.zeros(n_pad, dtype=np.int32),
            "vmask_col": vmask,
            "step": np.int32(0),
            "dangling_sum": dt(0),
            "total_dangling": dt(0),
        }

    @staticmethod
    def _gather_master(k, into_dst, into_src):
        """A tile's partial sums `[vc]` by destination and by source ->
        the complete sums of the device's column chunk."""
        with jax.named_scope("grape.vc.gather_master"):
            into_dst = lax.psum(into_dst, VC_ROW_AXIS)
            into_src = lax.psum(into_src, VC_COL_AXIS)
        with jax.named_scope("grape.vc.scatter"):
            return into_dst + _transpose(into_src, k)

    def peval(self, ctx: StepContext, frag, state):
        k, vc = frag.k, frag.vc
        dt = state["rank_col"].dtype
        vmask_col = state["vmask_col"]

        # degree: appearances as dst (rows 0..vc-1 of the tile's CSR)
        # + as src (rows vc..2 vc-1), read off the offsets
        per_row = jnp.diff(frag.pull.indptr)
        deg_col = self._gather_master(
            k, per_row[:vc], per_row[vc:]).astype(jnp.int32)

        with jax.named_scope("grape.app.update"):
            p = jnp.asarray(1.0 / max(frag.total_vnum, 1), dt)
            dangling = jnp.logical_and(vmask_col, deg_col == 0)
            total_dangling = lax.psum(
                dangling.sum(), VC_COL_AXIS).astype(dt)
            rank_col = jnp.where(
                vmask_col,
                jnp.where(deg_col > 0,
                          p / jnp.maximum(deg_col, 1).astype(dt), p),
                jnp.asarray(0, dt),
            )
        with jax.named_scope("grape.vc.scatter"):
            rank_row = _transpose(rank_col, k)
        state = dict(
            state,
            rank_col=rank_col,
            rank_row=rank_row,
            deg_col=deg_col,
            dangling_sum=p * total_dangling,
            total_dangling=total_dangling,
            step=jnp.int32(0),
        )
        return state, jnp.int32(1 if self.max_round > 0 else 0)

    def inceval(self, ctx: StepContext, frag, state):
        k, vc = frag.k, frag.vc
        dt = state["rank_col"].dtype
        zero = jnp.asarray(0, dt)
        pull = frag.pull

        # both directions in one pull: src-side ranks (row copy) flow
        # to the tile's destinations, dst-side ranks (column copy) to
        # its sources
        table = jnp.concatenate([state["rank_row"], state["rank_col"]])
        contrib = pull_gather(table, pull.edge_nbr, pull.edge_mask, zero)
        sums = segment_reduce(
            contrib, pull.edge_src, 2 * vc, "sum", row_ptr=pull.indptr
        ).astype(dt)
        gathered = self._gather_master(k, sums[:vc], sums[vc:])

        with jax.named_scope("grape.app.update"):
            n = max(frag.total_vnum, 1)
            d = self.delta
            vmask_col = state["vmask_col"]
            deg_col = state["deg_col"]
            step = state["step"] + 1
            base = (jnp.asarray((1.0 - d) / n, dt)
                    + jnp.asarray(d / n, dt) * state["dangling_sum"])
            dangling_sum = base * state["total_dangling"]
            is_last = step >= jnp.int32(self.max_round)
            iter_val = jnp.where(
                deg_col > 0,
                (base + jnp.asarray(d, dt) * gathered)
                / jnp.maximum(deg_col, 1).astype(dt),
                base,
            )
            final_val = gathered * jnp.asarray(d, dt) + base
            rank_col = jnp.where(
                vmask_col, jnp.where(is_last, final_val, iter_val), zero
            )
        with jax.named_scope("grape.vc.scatter"):
            rank_row = _transpose(rank_col, k)
        state = dict(
            state,
            rank_col=rank_col,
            rank_row=rank_row,
            step=step,
            dangling_sum=dangling_sum,
        )
        return state, jnp.where(is_last, jnp.int32(0), jnp.int32(1))

    def finalize(self, frag, state):
        # compact the gpid-space rank into [fnum, vc] rows aligned with
        # inner_oids order (masters = diagonal fragments)
        rank = np.asarray(state["rank_col"]).reshape(frag.k, frag.vc)
        out = np.zeros((frag.fnum, frag.vc), dtype=rank.dtype)
        for c in range(frag.k):
            oids = frag.inner_oids(c * frag.k + c)
            offs = oids % frag.chunk
            out[c * frag.k + c, : len(oids)] = rank[c, offs]
        return out
