"""App-framework API: the TPU PIE model.

Re-design of `grape/app/*`:
  * `ParallelAppBase` (`parallel_app_base.h:38-109`) — PEval/IncEval +
    static traits,
  * `AutoAppBase` (`auto_app_base.h:38-84`) — implicit messaging,
  * `BatchShuffleAppBase` (`batch_shuffle_app_base.h`) — whole-array sync,
  * `GatherScatterAppBase` (`gather_scatter_app_base.h:30-61`) —
    vertex-cut apps,
  * `ContextBase` / `VertexDataContext` (`context_base.h`,
    `vertex_data_context.h:24-80`).

The TPU contract: an app provides

  * `init_state(frag, **query_args)` — host-side: build the initial
    per-fragment state (numpy arrays stacked `[fnum, ...]`; leaves named
    in `replicated_keys` are mesh-replicated scalars/arrays).  This is
    the host half of PEval (e.g. placing the source distance).
  * `peval(ctx, frag, state) -> (state, active)` — traced per shard
    (inside `shard_map`); first superstep.
  * `inceval(ctx, frag, state) -> (state, active)` — traced per shard;
    repeated until the `psum`-reduced `active` vote is zero (the
    reference's termination allreduce,
    `parallel_message_manager.h:123-138`) or `max_rounds` is hit.
  * `finalize(frag, state) -> np.ndarray [fnum, vp]` — host-side
    assemble: per-vertex output values.

`ctx` is the `Communicator` namespace (psum/pmin/pmax/all_gather/
ppermute) plus the gather helper; messaging *is* collectives — there is
no buffer/archive machinery to port because XLA owns the transport.
"""

from __future__ import annotations

from typing import Dict, FrozenSet

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from libgrape_lite_tpu.fragment.edgecut import DeviceFragment
from libgrape_lite_tpu.ops.segment import table_gather
from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS
from libgrape_lite_tpu.parallel.communicator import (
    Communicator,
    collective_scope,
)
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy


class StepContext(Communicator):
    """Per-superstep toolkit handed to app code while tracing."""

    @staticmethod
    def gather_state(x_local):
        """Local per-vertex block [vp, ...] -> full pid-indexed array
        [fnum * vp, ...].  The TPU form of BatchShuffle's
        `SyncInnerVertices` + `UpdateOuterVertices`
        (`batch_shuffle_message_manager.h:237,264`): one `all_gather`
        over ICI replaces per-neighbor mirror buffers."""
        with collective_scope():
            return lax.all_gather(x_local, FRAG_AXIS, tiled=True)

    @staticmethod
    def fid():
        return lax.axis_index(FRAG_AXIS)

    @staticmethod
    def exchange_mirrors(x_local, send_idx):
        """Mirror-compressed form of `gather_state` (reference
        `batch_shuffle_message_manager.h:237-264`): exchange only the
        outer-vertex rows each neighbor shard actually references.

        x_local: this shard's [vp] state; send_idx: this shard's
        [fnum, m] send table (rows ordered by receiver, from
        `parallel/mirror.MirrorPlan`).  Returns the compact
        [vp + fnum*m] table addressed by the plan's `nbr_compact`
        columns — O(vp + mirrors) instead of O(fnum*vp).

        The send buffer is packed by `ops/segment.table_gather`: the
        VMEM gather kernel where the call shows it can read the shard's
        state (the TPU backend, a 1-D 32-bit state within the kernel's
        budget), `x_local[send_idx]` by XLA's gather everywhere else;
        both move the same bits.  The table's `m` is a whole number of
        128s, so its `[fnum * m]` stream is the kernel's `[rows, 128]`
        view as it lies."""
        with jax.named_scope("grape.exchange.pack"):
            vals = table_gather(x_local, send_idx)
        with collective_scope():
            recv = lax.all_to_all(
                vals, FRAG_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
        with jax.named_scope("grape.exchange.unpack"):
            return jnp.concatenate([x_local, recv.reshape(-1)])


def source_lane_array(frag, source, app_name: str, fill, hit, dtype):
    """(batched, arr): the serve source-vector contract's shared
    scaffolding.  `source` is one query id or a SEQUENCE of k lane ids
    (`batch_query_key`); `arr` is [k, fnum, vp] holding `hit` at each
    resolved source and `fill` everywhere else — SSSP seeds distances
    (inf/0), BFS depths (sentinel/0), personalized PageRank its
    teleport vector (0/1).  An absent or None source leaves its lane
    all-`fill` (the unreachable/zero-mass convention)."""
    batched = isinstance(source, (list, tuple, np.ndarray))
    sources = list(source) if batched else [source]
    arr = np.full((len(sources), frag.fnum, frag.vp), fill, dtype=dtype)
    for b, s in enumerate(sources):
        pid = resolve_source(frag, s, app_name) if s is not None else -1
        if pid >= 0:
            arr[b, pid // frag.vp, pid % frag.vp] = hit
    return batched, arr


def resolve_source(frag, source, app_name: str) -> int:
    """oid -> pid for a query source, logging when absent (shared by
    SSSP/BFS/BC; the reference's GetInnerVertex miss is silent, a
    warning is strictly more debuggable)."""
    pid = int(frag.oid_to_pid(np.array([source]))[0])
    if pid < 0:
        from libgrape_lite_tpu.utils import logging as glog

        glog.log_info(
            f"{app_name}: source {source!r} is not in the vertex map; "
            "all vertices will be unreachable"
        )
    return pid


class ContextBase:
    """Per-query mutable state descriptor (reference `context_base.h`).
    In the TPU build context state *is* the state pytree; this class only
    carries metadata used by the driver."""


class VertexDataContext(ContextBase):
    """Marker for apps whose result is one value per vertex
    (reference `vertex_data_context.h:24-80`)."""


class AppBase:
    # trait parity (parallel_app_base.h:42-46)
    load_strategy: LoadStrategy = LoadStrategy.kBothOutIn
    message_strategy: MessageStrategy = MessageStrategy.kSyncOnOuterVertex
    need_split_edges: bool = False

    # state keys that are mesh-replicated (everything else is sharded
    # with leading fragment dim)
    replicated_keys: FrozenSet[str] = frozenset()

    # state keys that are read-only trace INPUTS, not loop state: they
    # enter the jitted superstep sharded like normal leaves but are
    # excluded from the while_loop carry and from the outputs (the
    # mirror plan's per-shard tables ride in this way —
    # constants can't, because closing over an array under shard_map
    # replicates it to every device)
    ephemeral_keys: FrozenSet[str] = frozenset()

    # which mesh the superstep runs on: "frag" = the 1-D fragment axis
    # (default); "vc2d" = the k x k (vcrow, vccol) SUMMA mesh for
    # vertex-cut apps (CommSpec.mesh2d)
    mesh_kind: str = "frag"

    # serve/: the query arg that varies per lane of a batched
    # multi-source dispatch (e.g. "source" for SSSP/BFS).  When set,
    # `init_state` must also accept a SEQUENCE of k values for that arg
    # and return carry leaves with a leading [k] lane axis while
    # building ephemeral leaves (pack streams, mirror tables) ONCE —
    # shared across lanes.  None = no native vector support; the
    # generic `init_state_batch` stacking fallback applies.
    batch_query_key: str | None = None

    # dyn/: True when the app folds a fragment's staged delta-edge
    # overlay (frag.dyn_overlay) into its pull reduction — sound only
    # for min-fold apps, where extra candidates merge exactly.  Apps
    # without the contract must not run while an overlay holds staged
    # edges (they would silently see the stale graph); Worker.query
    # enforces this, and ServeSession repacks first.
    dyn_overlay_support: bool = False

    # dyn/: the incremental-IncEval contract (dyn/incremental.py).
    #   None            — no contract; query_incremental recomputes cold
    #   "monotone-min"  — additive deltas reuse the previous fixed
    #                     point: seeded = min(fresh_init, migrated prev)
    #                     per key in `inc_seed_keys`, byte-identical to
    #                     a cold run on the mutated graph
    #   "restart"       — declared, but the iteration has no reusable
    #                     fixed point (fixed-round PageRank): cold, counted
    inc_mode: str | None = None
    inc_seed_keys: Dict[str, str] = {}

    def inc_value_map(self, key: str, values: np.ndarray, old_frag,
                      new_frag) -> np.ndarray:
        """Remap carry VALUES across a repack (row migration is the
        framework's job; value remapping is the app's).  Default:
        identity — right for distances/depths; WCC overrides to
        re-address its pid-valued component labels."""
        return values

    def custom_specs(self) -> Dict:
        """Per-key PartitionSpec overrides for state leaves that are
        neither [fnum, ...]-sharded nor replicated (e.g. SUMMA row/col
        chunk state, P("vcrow") / P("vccol")).  These leaves pass into
        the traced step as their per-shard blocks, unsqueezed."""
        return {}

    # ---- a round that follows its frontier (worker `_frontier_loop`) ----
    #
    # A monotone min relaxation needs only the rows that improved last
    # round to propose again.  An app whose `init_state` sets
    # `frontier_budget` to (B, C) offers the fused serial loop a second
    # round, `inceval_frontier`, for a list of at most B rows whose
    # adjacency, in the CSR `frontier_csr` names, holds at most C
    # entries; the loop carries the list beside the state and takes
    # that round wherever the last vote and the list's entries fit, the
    # dense `inceval` elsewhere (ops/segment.py has the round itself).
    # Without a threshold both rounds give the same state and the same
    # vote (BFS).  An offer may carry a threshold as well (SSSP's
    # near/far): with `frontier_step` set to a bucket's width the loop
    # also carries a scalar `below`, lists only the improved rows whose
    # value (`frontier_values`) is under it, and where that list runs
    # empty moves `below` to the end of the first bucket that holds a
    # row at or over it and refills the list from the state; the vote
    # is then the list's length, 0 where no row is left at any value,
    # and the fixed point is the dense loop's, reached in another
    # order.  What the offer rests on is the app's to observe in
    # `init_state`, statically; every other runner keeps `inceval`.
    frontier_budget = None
    frontier_step = None

    def frontier_mask(self, state, new_state=None):
        """V-wide mask of the rows whose proposals no round has applied
        yet: of the state PEval returned, the rows that hold a value
        (one such row, a query's source, is the first list; more start
        with a dense round); given the state a dense round made of
        `state`, the rows that round improved, the list after it (with
        a threshold: those of them under it)."""
        raise NotImplementedError

    def frontier_values(self, state):
        """The V-wide values the round relaxes, floats, +inf where a
        row holds none: what a loop that carries a threshold
        (`frontier_step`) compares with it."""
        raise NotImplementedError

    def frontier_csr(self, frag):
        """The CSR the round pushes along.  Here and in
        `inceval_frontier` `frag` is the shard's block as the runner
        receives it, `[1, ...]` leaves unsqueezed
        (`ops/segment._block_at`); `state` is the carried leaves, so
        an app that makes the offer keeps no ephemeral ones."""
        raise NotImplementedError

    def inceval_frontier(self, frag, state, front, lo, count, below=None):
        """`inceval` from the rows `front` lists (`lo`, `count`: their
        `ops/segment.frontier_spans` in `frontier_csr`):
        `(state', active, front')`, the carried leaves alone.  Without
        a threshold state and vote are `inceval`'s own; with `below`,
        `front'` holds the improved rows under it and `active` counts
        them."""
        raise NotImplementedError

    # 0 means "run until the termination vote fires"
    max_rounds: int = 0

    # output formatting
    result_format: str = "float"  # float | int | sssp_infinity

    def init_state(self, frag, **query_args) -> Dict:
        raise NotImplementedError

    def init_state_batch(self, frag, args_list) -> Dict:
        """Initial state for k query lanes (serve/ batched dispatch):
        carry leaves gain a leading [k] lane axis; ephemeral leaves
        (read-only trace inputs) stay unbatched and shared.

        Apps with a `batch_query_key` and lane-uniform remaining args
        get the cheap path — ONE init_state call with the vector arg,
        so per-query host work (pack-plan resolve, stream builds) is
        paid once.  Everything else falls back to one init_state per
        lane with the carry leaves stacked (lane 0's ephemeral leaves
        are adopted for the batch: plans are deterministic per
        fragment, so every lane builds identical streams)."""
        if not args_list:
            raise ValueError("init_state_batch needs at least one lane")
        key = self.batch_query_key
        if key is not None:
            fixed = {k: v for k, v in args_list[0].items() if k != key}
            if all(
                {k: v for k, v in a.items() if k != key} == fixed
                for a in args_list[1:]
            ):
                return self.init_state(
                    frag, **fixed,
                    **{key: [a.get(key, 0) for a in args_list]},
                )
        states = [self.init_state(frag, **a) for a in args_list]
        eph = frozenset(getattr(self, "ephemeral_keys", ()) or ())
        return {
            k: (states[0][k] if k in eph
                else np.stack([s[k] for s in states]))
            for k in states[0]
        }

    def peval(self, ctx: StepContext, frag: DeviceFragment, state: Dict):
        raise NotImplementedError

    def inceval(self, ctx: StepContext, frag: DeviceFragment, state: Dict):
        raise NotImplementedError

    def finalize(self, frag, state: Dict):
        raise NotImplementedError

    # ---- runtime invariants (guard/) ----
    #
    # Named device-side predicates over consecutive carries, evaluated
    # by the guard monitor when GRAPE_GUARD (or Worker.query(guard=...))
    # arms it: every round in stepwise execution, at every chunk
    # boundary in the guarded-fused path.  The default is the generic
    # floor (NaN-free float carries); apps override to declare their
    # algebraic invariants (monotone distances, conserved mass, label
    # ranges).  `state` is the example carry (placed leaves) — use it
    # to inspect dtypes/keys; predicates themselves are traced.

    def invariants(self, frag, state: Dict) -> list:
        from libgrape_lite_tpu.guard.invariants import default_invariants

        return default_invariants(self, frag, state)

    # ---- MutationContext (reference grape/app/mutation_context.h) ----
    #
    # Apps that mutate the graph mid-query define `collect_mutations`;
    # the stepwise worker calls it between supersteps
    # (reference worker.h:211-222 applies staged mutations through
    # BasicFragmentMutator between rounds) and rebuilds the fragment.
    # State migrates by oid via `migrate_state` (default: aligned copy;
    # new vertices take init_state defaults).

    def migrate_state(self, old_frag, new_frag, old_state, new_state):
        """Copy per-vertex state rows across a rebuild, matching by oid."""
        from libgrape_lite_tpu.fragment.mutation import oid_row_alignment

        of, ol, nf, nl = oid_row_alignment(old_frag, new_frag)
        out = dict(new_state)
        for k, v in new_state.items():
            if k in self.replicated_keys:
                out[k] = old_state.get(k, v)
                continue
            ov = old_state.get(k)
            if (
                ov is not None
                and np.ndim(ov) >= 2
                and ov.shape[:2] == (old_frag.fnum, old_frag.vp)
                and np.ndim(v) >= 2
                and v.shape[:2] == (new_frag.fnum, new_frag.vp)
            ):
                nv = np.array(v)
                nv[nf, nl] = ov[of, ol]
                out[k] = nv
        return out

    def trace_key(self):
        """Hashable fingerprint of every hyperparameter that gets baked
        into the traced superstep (used to key the compiled-runner
        cache).  Default: all primitive instance attributes."""
        items = []
        for k, v in sorted(self.__dict__.items()):
            if isinstance(v, (int, float, str, bool, type(None), np.dtype)):
                items.append((k, v))
        return tuple(items)

    # ---- shared compute helpers ----

    @staticmethod
    def segment_reduce(values, edge_src, vp, kind="sum", row_ptr=None):
        """Reduce per-edge values into per-vertex rows; padded edges fall
        into the overflow row `vp` which is sliced off.  This is the TPU
        ForEachEdge: edge-parallel, degree-oblivious (the role of the
        reference CUDA LB kernels, `cuda/parallel/parallel_engine.h`).
        A pull over a whole CSR hands over its `indptr` as `row_ptr`
        and folds by scan; see `ops/segment.segment_reduce`."""
        from libgrape_lite_tpu.ops.segment import segment_reduce

        return segment_reduce(values, edge_src, vp, kind, row_ptr=row_ptr)

    @staticmethod
    def dyn_min_fold(relaxed, state: Dict, vp: int, prefix: str, cand):
        """Merge the staged delta-edge overlay (dyn/ingest.py) into a
        pull-mode min reduction.  `cand` is the [capacity] per-slot
        candidate vector, already masked to the fold's neutral element
        on inactive slots; rows come from the overlay's lid-sorted
        `src` plane (pad slots route to the vp overflow row).  `min`
        is associative, so the merged result is byte-identical to a
        cold query on the rebuilt mutated graph — the whole point of
        the side-path: the packed CSR, its plans, and the compiled
        runner never change."""
        extra = AppBase.segment_reduce(
            cand, state[prefix + "src"], vp, "min"
        )
        return jnp.minimum(relaxed, extra)


class ParallelAppBase(AppBase):
    """Explicit-messaging superstep app (reference ParallelAppBase)."""


class BatchShuffleAppBase(AppBase):
    """Whole-array mirror-sync app (PageRank-style)."""

    message_strategy = MessageStrategy.kSyncOnOuterVertex


class AutoAppBase(AppBase):
    """Auto-messaging app (reference `auto_app_base.h:38-84` +
    `auto_parallel_message_manager.h:47-365`): the app registers
    SyncBuffers (state-key -> aggregate op) and writes only the local
    compute; messaging is implicit.

    TPU mapping: `propose(ctx, frag, state)` returns, per synced key, a
    full pid-indexed [n_pad] proposal array (neutral element where the
    shard has nothing to say — the push-model scatter of
    generateAutoMessages); the framework all-reduces proposals with the
    buffer op (aggregateAutoMessages) and hands each shard its slice to
    `update` (default: adopt it, vote active while anything changed)."""

    sync_buffers: Dict[str, str] = {}

    def propose(self, ctx: StepContext, frag: DeviceFragment, state: Dict):
        raise NotImplementedError

    def update(self, ctx: StepContext, frag: DeviceFragment, state: Dict,
               combined: Dict):
        changed_any = jnp.int32(0)
        new_state = dict(state)
        for k in self.sync_buffers:
            new = combined[k]
            changed = jnp.logical_and(new != state[k], frag.inner_mask)
            changed_any = changed_any + changed.sum().astype(jnp.int32)
            new_state[k] = new
        return new_state, ctx.sum(changed_any)

    def peval(self, ctx, frag, state):
        return state, jnp.int32(1)

    def inceval(self, ctx, frag, state):
        from libgrape_lite_tpu.parallel.message_manager import (
            AutoParallelMessageManager,
        )

        proposals = self.propose(ctx, frag, state)
        combined = AutoParallelMessageManager.sync(
            frag, proposals, self.sync_buffers
        )
        return self.update(ctx, frag, state, combined)


class GatherScatterAppBase(AppBase):
    """Vertex-cut app (reference `gather_scatter_app_base.h:30-61`)."""

    message_strategy = MessageStrategy.kGatherScatter
    # the tiles' device form the app's round reads
    # (fragment/vertexcut.py: "coo" | "pull"); the runner loads it and
    # `check_tiles` refuses a fragment built for the other family
    tile_layout = "coo"

    def check_tiles(self, frag) -> None:
        if getattr(frag, "layout", "coo") != self.tile_layout:
            raise ValueError(
                f"{type(self).__name__} reads the vertex-cut tiles' "
                f"{self.tile_layout!r} form and this fragment holds "
                f"{frag.layout!r}: build it with "
                f"layout={self.tile_layout!r} (fragment/vertexcut.py; "
                "raw storage's default is 'pull', symmetrised "
                "storage's 'coo')")
