"""LCCBeta — scalable LCC via intersection of sorted adjacency lists.

Re-design of `examples/analytical_apps/lcc/lcc_beta.h` (the reference's
alternative LCC) with the round-2 scaling goal (ROADMAP item 3): the
packed-bitmap LCC (models/lcc.py) costs O(N/32) words per row — ideal
for LDBC-scale graphs, wrong beyond ~2^21 vertices.  This variant
intersects *sorted oriented neighbor lists* instead:

  * the degree-oriented DAG's out-adjacency is materialised as a padded
    ELL block `[vp, D] int32` (D = max oriented out-degree, bounded by
    graph degeneracy — O(sqrt(2E)) worst case), rows sorted ascending;
  * for every oriented edge (v, u): each member of N+(v) is compared
    with every member of N+(u), a chunk's edges along the lane axis
    (`_members`: equality over all pairs, no search and no address
    that depends on data), which finds the common members w — one pass
    yields all three triangle credits, so no reverse (N−) structure and
    no second intersection: v and u by the edge's count, and each w at
    its *adjacency slot* — w = ell[v, j] is named by (row v, column j),
    so an edge folds its hits as one row into the `[vp, W]` slot table
    of its walk (`_fold_rows`), and after the walk the tables are
    flushed by id: the schedule once more without targets, the edge
    (v, u) reading the slot whose id is u (`_slot_of`).  Crediting each
    w by id inside the walk is a C x W element scatter a chunk, nine
    tenths of a query on the chip (PERF.md, PR 33);
  * remote rows ride the same ring `ppermute` as the bitmap kernel;
    credits accumulate in a pid-indexed vector folded by one `psum`.

The oriented adjacency (the ELL block, its row counts, the tier
schedule) is a property of the resident graph, not of a query: it is
built once per fragment and kept on the device with it
(`_resident_adjacency`, counted in LCC_STATS), and rides every query as
read-only ephemeral leaves, out of the fused loop's carry and of the
result.  The step carries `jax.named_scope` names (metadata only):
`grape.lcc.orient`, `.rows`, `.intersect`, `.credit`, `.ring` on the
ring's `ppermute` (several fragments only), and `grape.app.update` on
the quotient (docs/OBSERVABILITY.md).

Working set is O(chunk · (W + D)) — independent of vertex count.  Exactness
matches the golden within eps like models/lcc.py: triangle enumeration
is orientation-agnostic (each triangle is found exactly once at its
DAG-minimal edge and all three credits scatter), so the kernels agree
even though this one defaults to the "lo" orientation while the bitmap
kernel keeps the reference's "hi" convention (simple-graph multiplicity
assumption documented there).
"""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats
from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

# the resident adjacency: how often it was built and how often found
# with the fragment, and the geometry of the one the last query read
# (docs/OBSERVABILITY.md)
LCC_STATS = _FedStats("lcc", {
    "builds": 0, "cache_hits": 0, "d_max": 0, "ell_bytes": 0,
    "oriented_edges": 0, "query_lanes": 0, "tiers": 0,
    "ring_passes": 0, "ring_bytes": 0, "shard_lanes": 0,
    "shard_kept_max": 0, "shard_kept_min": 0,
    "credit_rows": 0, "flush_updates": 0,
    "step_kept_max": 0, "step_kept_min": 0, "fold_runs_max": 0,
})

# fragment -> {(orientation, degree_threshold, tier request): adjacency};
# weak-keyed, so an adjacency goes with its fragment
_ADJACENCY_CACHE = weakref.WeakKeyDictionary()


def _chunk_rows(width: int) -> int:
    """Edges an intersection pass takes at once at query width `width`:
    bounded so that chunk x width stays about 4M int32 entries."""
    return max(128, min(4096, (1 << 22) // max(width, 1)))


def _untiered_chunk(ep: int, d: int) -> int:
    """Edges a chunk of the untiered pass over `ep` oe entries takes."""
    return min(_chunk_rows(d), ep)


def _untiered_entries(ep: int, d: int) -> int:
    """Padded schedule entries of the untiered pass over `ep` oe entries."""
    c_e = _untiered_chunk(ep, d)
    return max(1, -(-ep // c_e)) * c_e


def _members(q_t, t_t, qv_t, sel):
    """hit[i, c]: `q_t[i, c]` is a valid query slot (`qv_t`) of a selected
    edge (`sel[c]`) and is among `t_t[:, c]`.  `q_t`, `qv_t` [W, C],
    `t_t` [D, C]: every pair is compared and OR-ed over the target axis
    with the chunk's edges on the minor (lane) axis, so no address
    depends on data (a search costs the chip 8 dependent element gathers
    a lane, this 0.2 ns: PERF.md, PR 31), and XLA fuses
    the compare into the reduce, so nothing W x D x C is ever written.
    No order is assumed.  A valid id is below the sentinel that every
    pad holds, so only a pad of `q_t` can meet a pad of `t_t`, and
    `qv_t` drops those."""
    hit = (t_t[:, None, :] == q_t[None, :, :]).any(axis=0)
    return jnp.logical_and(jnp.logical_and(hit, qv_t), sel[None, :])


def _fold_rows(slot, sl, hit_t, runs):
    """`slot[sl[c], i] += hit_t[i, c]`: a chunk's far-end credits by
    adjacency slot, as row updates of the `[vp, W]` table where crediting
    by id is C x W element updates (8 ns each on the chip; a whole row
    costs what two to seven elements do: PERF.md, PR 33).  `sl` [C]
    ascending, rows may repeat; `hit_t` [W, C] bool; `runs` a static
    bound on the distinct rows of a chunk.  Where it is at most half of C
    the runs are summed first, by a one-hot `[runs, C] x [C, W]` product
    (0/1 in bf16, f32 sums: exact, a run is at most C long), and one row
    a run is written; a pad run adds 0 to the last row.  At half of C the
    product costs the chip what the rows it spares do (PERF.md, PR 35)."""
    upd = hit_t.T
    if 2 * runs <= hit_t.shape[1]:
        opens = jnp.concatenate([jnp.ones((1,), bool), sl[1:] != sl[:-1]])
        run = jnp.cumsum(opens, dtype=jnp.int32) - 1
        of_run = run[None, :] == jnp.arange(runs, dtype=jnp.int32)[:, None]
        upd = jnp.dot(of_run.astype(jnp.bfloat16), upd.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
        sl = jnp.where(of_run, sl[None, :], slot.shape[0] - 1).min(axis=1)
    return slot.at[sl].add(upd.astype(slot.dtype), indices_are_sorted=True)


def _slot_of(rows, q, u):
    """What the table holds for each edge (v, u) of a chunk: `rows`
    [C, W] = slot[v] beside the ids `q` [C, W] = ell[v, :W] they
    belong to; the column that holds `u` [C] is picked by equality (ids
    are distinct in a row, a pad is no id), 0 where none does."""
    return jnp.where(q == u[:, None], rows, 0).sum(axis=1, dtype=rows.dtype)


class LCCBeta(ParallelAppBase):
    load_strategy = LoadStrategy.kOnlyOut
    message_strategy = MessageStrategy.kAlongOutgoingEdgeToOuterVertex
    result_format = "float"

    # "lcc": full triple crediting + clustering-coefficient ratio.
    # "apex": apex-only triangle counts (each triangle counted once at
    # its DAG apex) — the k=3 clique-counting mode used by KClique.
    credit_mode = "lcc"
    # DAG orientation for the ELL build: "lo" = edges point to the
    # higher-(degree,id) endpoint, bounding max out-degree D by graph
    # DEGENERACY instead of hub degree.  Triangle enumeration is
    # orientation-agnostic (each triangle is found exactly once, at its
    # DAG-minimal edge, and all three credits are scattered), so this
    # is purely the scaling choice: under "hi" a RMAT-24 hub row would
    # be D = 6202+ (a ~52 GB ELL); under "lo" D stays at degeneracy
    # scale (VERDICT r4 weak #6).  Exception: degree_threshold > 0
    # switches back to "hi", because the reference's filter semantics
    # (`lcc.h:234-243`: apex and middle unfiltered, far end exempt) are
    # DEFINED on lower-degree neighbor lists — and under "lo" hub rows
    # are already degeneracy-short, so the cost cap is moot anyway.
    orientation = "lo"

    def _eff_orientation(self) -> str:
        # the threshold flip applies ONLY to lcc crediting: apex-mode
        # subclasses (ApexTriangleCount, the clique kernels) pin "lo"
        # because their per-apex attribution and hub_cap gating are
        # defined on the degeneracy-bounded orientation
        if self.credit_mode == "lcc" and getattr(
            self, "degree_threshold", 0
        ) > 0:
            return "hi"
        return self.orientation

    def init_state(self, frag, degree_threshold: int = 0, **_):
        """The query's state: the zeroed result plus the fragment's
        oriented adjacency (`ell`, `cnt`, the tier schedule `eperm`),
        which is built once per fragment and rides as read-only
        ephemeral leaves (`_resident_adjacency`).

        degree_threshold > 0 drops filtered (hub) vertices' lists — the
        reference's LCC cost cap (`lcc.h:234-243`, 0 = disabled)."""
        from libgrape_lite_tpu.ops.spgemm_pack import resolve_lcc_backend

        # GRAPE_LCC_BACKEND = spgemm/auto: the merge-intersection
        # kernel has no spgemm lowering — RECORDED decline (never
        # silent), results stay intersect-parity
        resolve_lcc_backend(
            type(self).__name__, frag, supported=False,
            unsupported_reason="merge-intersection ELL kernel has no "
            "spgemm lowering (use lcc_bitmap/lcc_opt)",
        )
        self.degree_threshold = int(degree_threshold)
        adj = self._resident_adjacency(frag)
        self._tier_info = adj["tier_info"]
        state = {
            "ell": adj["ell"],
            "cnt": adj["cnt"],
            "lcc": np.zeros((frag.fnum, frag.vp), dtype=np.float64),
        }
        if adj["eperm"] is not None:
            state["eperm"] = adj["eperm"]
        # read-only inputs, never written: out of the fused loop's
        # carry and of the result state (the ephemeral stream-table
        # convention, worker.py eph_part)
        self.ephemeral_keys = frozenset(state) - {"lcc"}
        return state

    def _resident_adjacency(self, frag) -> dict:
        """The oriented adjacency of `frag` as placed device arrays,
        built once per (fragment, effective orientation,
        degree_threshold, requested tier widths) and kept with the
        fragment, as `parallel/mirror._FRAG_MIRROR_CACHE` keeps the
        mirror plans: it is a property of the resident graph, not of
        a query, so a second query builds nothing on the host and
        copies nothing to the device (`put_global` hands a placed
        array through).  A mutated or rebuilt fragment is another
        object and so another key."""
        per_frag = _ADJACENCY_CACHE.setdefault(frag, {})
        key = (self._eff_orientation(), self.degree_threshold,
               self._tier_request())
        if key in per_frag:
            LCC_STATS["cache_hits"] += 1
        else:
            from libgrape_lite_tpu import obs
            from libgrape_lite_tpu.parallel.comm_spec import put_global

            # the miss: once per fragment and key, a set-up phase; the
            # host build is what `derived.place` leaves of it
            tr = obs.tracer()
            with tr.span("derived.lcc_adjacency", fnum=frag.fnum) as sp:
                adj = self._build_adjacency(frag, key[2])
                sp.set(d_max=adj["geometry"]["d_max"],
                       ell_bytes=adj["geometry"]["ell_bytes"])
                shard = frag.comm_spec.sharded()
                with tr.span("derived.place", what="lcc_adjacency"):
                    adj.update(jax.block_until_ready({
                        k: put_global(adj[k], shard)
                        for k in ("ell", "cnt", "eperm")
                    }))
            per_frag[key] = adj
            LCC_STATS["builds"] += 1
        # the geometry the counter shows is the last query's
        LCC_STATS.update(per_frag[key]["geometry"])
        return per_frag[key]

    def _build_adjacency(self, frag, tier_request) -> dict:
        """Host prep: dedup degree-oriented out-adjacency as sorted,
        padded ELL blocks (the analogue of lcc.h stage-1 neighbor
        filtering, done against the host CSRs), and the tier schedule
        over them."""
        fnum, vp = frag.fnum, frag.vp
        n_pad = fnum * vp
        sent = n_pad  # sorts last, never matches a valid query

        # global degree (incl multiplicity) per pid
        deg = np.zeros(n_pad, dtype=np.int64)
        for f in range(fnum):
            deg[f * vp : (f + 1) * vp] = np.diff(frag.host_oe[f].indptr)

        rows_per_frag = []
        cnts = np.zeros((fnum, vp), dtype=np.int32)
        # the oe entries the orientation rule keeps, for the schedule
        kept = np.zeros((fnum, len(frag.host_oe[0].edge_src)), dtype=bool)
        # the ring step at which an entry's target block is on its device:
        # step s of device f holds the block of fragment (f + s) % fnum
        steps = np.zeros(kept.shape, dtype=np.int32)
        d_max = 1
        for f in range(fnum):
            c = frag.host_oe[f]
            e = c.num_edges
            v = f * vp + c.edge_src[:e].astype(np.int64)
            u = c.edge_nbr[:e].astype(np.int64)
            if self._eff_orientation() == "lo":
                # low->high: out-degree bounded by degeneracy (hubs
                # keep only higher-degree neighbors — few); the k=4
                # kernel uses this to stay under hub_cap on power-law
                # graphs
                keep = (deg[u] > deg[v]) | ((deg[u] == deg[v]) & (u > v))
            else:
                keep = (deg[u] < deg[v]) | ((deg[u] == deg[v]) & (u < v))
            keep &= u != v
            if self.degree_threshold > 0:
                keep &= deg[v] <= self.degree_threshold
            kept[f, :e] = keep
            steps[f, :e] = (u // vp - f) % fnum
            # distinct kept (v, u) in (v, u) order: one packed key (v,
            # u < n_pad + 1, so the key stays far inside int64)
            packed = np.unique((v * (n_pad + 1) + u)[keep])
            v, u = packed // (n_pad + 1), packed % (n_pad + 1)
            lid = (v - f * vp).astype(np.int64)
            cnt = np.bincount(lid, minlength=vp).astype(np.int32)
            cnts[f] = cnt
            d_max = max(d_max, int(cnt.max(initial=1)))
            rows_per_frag.append((lid, u, cnt))

        est_bytes = fnum * vp * d_max * 4
        if est_bytes > 8 << 30:
            from libgrape_lite_tpu.utils import logging as glog

            # --degree_threshold switches to "hi" rows whose width is
            # bounded by the threshold itself, so only a value that
            # keeps n_pad*t*4 under budget actually helps — print it
            t_fit = (8 << 30) // max(fnum * vp * 4, 1)
            glog.log_info(
                f"LCC ELL estimate {est_bytes / (1 << 30):.1f} GiB "
                f"(n_pad={fnum * vp:,} x D={d_max}); "
                f"--degree_threshold below ~{t_fit} caps hub rows "
                "(reference FLAGS_degree_threshold, lcc.h:234-243)"
            )
        # build int32 in place: an int64 staging copy + stack + astype
        # would peak ~5x the printed estimate on the host
        stacked = np.full((fnum, vp, d_max), sent, dtype=np.int32)
        for f in range(fnum):
            lid, u, cnt = rows_per_frag[f]
            order = np.lexsort((u, lid))
            lid_s, u_s = lid[order], u[order]
            starts = np.zeros(vp, dtype=np.int64)
            np.cumsum(cnt[:-1], out=starts[1:])
            col = np.arange(len(lid_s)) - starts[lid_s]
            stacked[f, lid_s, col] = u_s  # ascending per row (lexsort)

        eperm, tier_info = self._build_tier_perm(
            frag, cnts, d_max, tier_request, kept, steps
        )
        ep = len(frag.host_oe[0].edge_src)
        # the padded entries and lanes a device walks a pass (a tiered
        # pass walks its ring step's segments only), the entries of its
        # whole schedule, and the widest tier's fold bound
        if tier_info:
            pass_entries = sum(n * c for _, n, c, _, _ in tier_info)
            shard_lanes = sum(n * c * w for _, n, c, w, _ in tier_info)
            shard_entries = fnum * pass_entries
            fold_runs = tier_info[-1][4]
        else:
            pass_entries = shard_entries = _untiered_entries(ep, d_max)
            shard_lanes = shard_entries * d_max
            fold_runs = _untiered_chunk(ep, d_max)
        # the ring sends its block once a pass, the last one included
        ring_passes = fnum if fnum > 1 else 0
        kept_per_shard = kept.sum(axis=1)
        kept_per_step = np.stack([
            np.bincount(steps[f][kept[f]], minlength=fnum)
            for f in range(fnum)
        ])
        geometry = {
            "d_max": d_max,
            "ell_bytes": int(stacked.nbytes),
            "oriented_edges": int(sum(len(r[0]) for r in rows_per_frag)),
            # the padded lanes one device's step runs, all passes
            "query_lanes": max(ring_passes, 1) * shard_lanes,
            "tiers": len(tier_info) if tier_info else 1,
            "ring_passes": ring_passes,
            # what one device sends a query: its [vp, D] int32 block
            "ring_bytes": ring_passes * vp * d_max * 4,
            "shard_lanes": shard_lanes,
            # oe entries the orientation keeps, fullest and emptiest shard
            "shard_kept_max": int(kept_per_shard.max()),
            "shard_kept_min": int(kept_per_shard.min()),
            # the same by (shard, ring step): the imbalance that the
            # schedule's uniform step segments pad away
            "step_kept_max": int(kept_per_step.max()),
            "step_kept_min": int(kept_per_step.min()),
            # the far-end credits ("lcc" mode): an entry of a pass folds
            # one row into the slot table, and the flush walks the whole
            # schedule once more, one element update an entry
            "credit_rows": max(ring_passes, 1) * pass_entries,
            "flush_updates": shard_entries,
            "fold_runs_max": fold_runs,
        }
        return {"ell": stacked, "cnt": cnts, "eperm": eperm,
                "tier_info": tier_info, "geometry": geometry}

    # width ladder for the tiered merge passes; "0" disables tiering.
    # Subclasses that override peval with their own edge walk (the
    # clique kernels) set uses_tiered_pass = False so they don't pay
    # the host bucketing pass or carry a dead schedule table.
    _TIER_WIDTHS = (64, 256)
    uses_tiered_pass = True

    def _tier_request(self):
        """The width ladder asked for (the class's, or GRAPE_LCC_TIERS'),
        None for no tiering: part of the resident adjacency's key."""
        import os

        if not self.uses_tiered_pass:
            return None
        spec = os.environ.get("GRAPE_LCC_TIERS")
        if spec == "0":
            return None
        if spec:
            try:
                return tuple(int(x) for x in spec.split(","))
            except ValueError:
                from libgrape_lite_tpu.utils import logging as glog

                glog.log_info(
                    f"GRAPE_LCC_TIERS={spec!r} is not a comma-separated "
                    "int list; using the default width ladder"
                )
        return tuple(self._TIER_WIDTHS)

    def _build_tier_perm(self, frag, cnts, d_max, req, kept, steps):
        """Tiered edge schedule (r5): the query side of the intersection
        costs W_query x D compares per edge (and a scattered credit per
        query lane), but the average oriented out-degree is far below D
        (RMAT-22: mean 16 vs D 1030 — 98% of the query lanes hold ELL
        padding, on the CPU substrate and the TPU VPU alike).  Bucket
        the oe entries the host's own
        orientation rule keeps (`kept` [fnum, Ep] bool: the ELL is
        built from that rule, so the other half could only be masked
        away inside the step) by their SOURCE row's
        ELL width and process each bucket at its own static width:
        tier t covers rows with cnt <= W_t, so its queries slice
        `ell[:, :W_t]` with zero semantic change (the sliced-off lanes
        were invalid by qvalid anyway).

        A tier is cut once more by ring step (`steps` [fnum, Ep]: the
        one pass at which the entry's target block is on the device), so
        that a pass walks its own entries and not the tier masked down
        to them: `fnum` step segments side by side, each in `oe` order
        (a chunk's source rows ascend) and each padded to the same whole
        number of chunks.  One fragment has one step.

        Returns (eperm, tier_info): eperm [fnum, L] int32 — the
        segments' oe-edge indices, sentinel Ep in the padding slots —
        and tier_info [(offset, n_chunks, chunk, W, runs)]: the tier
        starts at `offset`, step s of it at `offset + s * n_chunks *
        chunk`, `n_chunks` chunks a step; `runs` is the most distinct
        source rows one chunk holds, its pad run included (what
        `_fold_rows` may sum first: counted here, since how a row's
        entries fall over the steps is the graph's).  The geometry is
        uniform across shards and steps (the fullest segment's, padded
        to the tier's chunk size), as shard_map needs one static
        program; (None, None) where `req` (`_tier_request`) asks for no
        tiering or leaves nothing to tier."""
        if req is None:
            return None, None
        widths = [w for w in req if 0 < w < d_max]
        widths = sorted(set(widths)) + [d_max]
        if len(widths) == 1:
            return None, None  # nothing to tier

        fnum, vp = frag.fnum, frag.vp
        ep = len(frag.host_oe[0].edge_src)
        bounds = np.asarray(widths, dtype=np.int64)
        srcs = [np.asarray(frag.host_oe[f].edge_src) for f in range(fnum)]
        per_shard = []  # [fnum][tier][step] -> edge index arrays
        for f in range(fnum):
            c = np.append(cnts[f], 0)  # pad rows (src == vp) -> cnt 0
            tier = np.searchsorted(bounds, c[np.minimum(srcs[f], vp)],
                                   side="left")
            bucket = np.where(kept[f], tier * fnum + steps[f], -1)
            per_shard.append(
                [[np.flatnonzero(bucket == t * fnum + s).astype(np.int32)
                  for s in range(fnum)] for t in range(len(widths))]
            )

        info = []
        segs = [[] for _ in range(fnum)]
        offset = 0
        for t, w in enumerate(widths):
            c_t = _chunk_rows(w)
            n_t = max(len(idx) for shard in per_shard for idx in shard[t])
            n_t = -(-max(n_t, 1) // c_t) * c_t  # pad to chunk multiple
            runs = 1
            for f in range(fnum):
                seg = np.full((fnum, n_t), ep, dtype=np.int32)  # Ep = sentinel
                for s, idx in enumerate(per_shard[f][t]):
                    seg[s, : len(idx)] = idx
                segs[f].append(seg.reshape(-1))
                # the chunks' source rows as the step reads them
                sl = np.minimum(srcs[f][np.minimum(seg, ep - 1)], vp - 1)
                sl = sl.reshape(-1, c_t)
                opens = (sl[:, 1:] != sl[:, :-1]).sum(axis=1)
                runs = max(runs, 1 + int(opens.max()))
            info.append((offset, n_t // c_t, c_t, w, runs))
            offset += fnum * n_t
        return np.stack([np.concatenate(s) for s in segs]), info

    def _oriented_edge_mask(self, ctx, frag):
        """Traced oriented-dedup edge mask over frag.oe — the SAME rule
        as the host ELL build, honoring `self._eff_orientation()` (shared by
        the LCC pass and the k=4 kernel so the two can never drift)."""
        from libgrape_lite_tpu.models.lcc import LCC

        vp = frag.vp
        my_fid = lax.axis_index(FRAG_AXIS).astype(jnp.int32)
        oe = frag.oe
        deg_local = frag.out_degree
        deg_full = ctx.gather_state(deg_local)
        row_pid = my_fid * vp + jnp.minimum(oe.edge_src, vp - 1)
        d_row = deg_local[jnp.minimum(oe.edge_src, vp - 1)]
        d_nbr = deg_full[oe.edge_nbr]
        if self._eff_orientation() == "lo":
            keep = jnp.logical_or(
                d_nbr > d_row,
                jnp.logical_and(d_nbr == d_row, oe.edge_nbr > row_pid),
            )
        else:
            keep = jnp.logical_or(
                d_nbr < d_row,
                jnp.logical_and(d_nbr == d_row, oe.edge_nbr < row_pid),
            )
        keep = jnp.logical_and(LCC._dedup_mask(oe), keep)
        keep = jnp.logical_and(keep, oe.edge_nbr != row_pid)
        if self.degree_threshold > 0:
            # filtered v enumerates no oriented edges; a filtered middle
            # u's ELL row is already empty (host build dropped it)
            keep = jnp.logical_and(keep, d_row <= self.degree_threshold)
        return keep

    def peval(self, ctx: StepContext, frag, state):
        vp, fnum = frag.vp, frag.fnum
        n_pad = vp * fnum
        my_fid = lax.axis_index(FRAG_AXIS).astype(jnp.int32)

        ell, cnt = state["ell"], state["cnt"]
        d = ell.shape[-1]
        oe = frag.oe

        ep = oe.edge_src.shape[0]
        c_e = _untiered_chunk(ep, d)
        n_chunks = max(1, -(-ep // c_e))
        # the degree gather, the oriented mask and the neighbour ids
        # split into (fragment, row): E-wide, once a query
        with jax.named_scope("grape.lcc.orient"):
            keep = self._oriented_edge_mask(ctx, frag)
            nbr_fid = (oe.edge_nbr // vp).astype(jnp.int32)
            nbr_lid = (oe.edge_nbr % vp).astype(jnp.int32)

        lcc_mode = self.credit_mode == "lcc"
        cred = jnp.zeros((n_pad + 1,), dtype=jnp.int32)
        tier_info = getattr(self, "_tier_info", None)
        tiered = tier_info is not None and "eperm" in state
        # width of a walk -> a static bound on the distinct source rows
        # of one of its chunks
        runs = {d: c_e}
        if tiered:
            eperm = state["eperm"]
            # per-tier query tables: static slices of the local ELL
            # (queries always come from LOCAL rows; only the target
            # side rides the ring at full width)
            tier_ells = [ell[:, :w] for (_, _, _, w, _) in tier_info]
            # counted on the host with the schedule
            runs = {w: r for (_, _, _, w, r) in tier_info}
        # far-end credits by adjacency slot, a table a walk width (a
        # partial row costs the chip a loop over the rows: PERF.md,
        # PR 33): slots[W][v, j] belongs to the id ell[v, j].
        # Temporaries of the step; apex mode credits no far end
        slots = ({w: jnp.zeros((vp, w), dtype=jnp.int32) for w in runs}
                 if lcc_mode else None)

        def walk(carry, visit, scope, step=None):
            """Every chunk of the device's schedule, in order (of ring
            step `step`'s segments only, where one is given and the
            schedule is tiered: the untiered walk has no segments), through
            `visit(carry, sl, nfid, nlid, live, q)`: the chunk's local
            source rows `sl` (ascending: the schedule is in `oe` order and
            `oe` is a CSR by source), its neighbours as (fragment, row),
            the entries that are real oriented edges (`live`) and the
            query block q = ell[sl, :W]; the index and row gathers under
            `scope`."""
            if tiered:
                for (off, n_chunks_t, c_t, _, _), ell_t in zip(
                    tier_info, tier_ells
                ):
                    if step is None:
                        n_chunks_t *= fnum
                    else:
                        with jax.named_scope(scope):
                            off = off + step * (n_chunks_t * c_t)

                    def body(i, carry, off=off, c_t=c_t, ell_t=ell_t):
                        with jax.named_scope(scope):
                            idx = lax.dynamic_slice(
                                eperm, (off + i * c_t,), (c_t,)
                            )
                            vld = idx < ep      # Ep = padding sentinel
                            ic = jnp.minimum(idx, ep - 1)
                            sl = jnp.minimum(oe.edge_src[ic], vp - 1)
                            nfid_c = nbr_fid[ic]
                            nlid_c = nbr_lid[ic]
                            live = jnp.logical_and(vld, keep[ic])
                            # tier rows have cnt <= W_t by construction
                            q = ell_t[sl]       # [C, W_t]
                        return visit(carry, sl, nfid_c, nlid_c, live, q)

                    carry = lax.fori_loop(0, n_chunks_t, body, carry)
                return carry

            def body(i, carry):
                with jax.named_scope(scope):
                    start = jnp.minimum(i * c_e, ep - c_e)
                    pos0 = start + jnp.arange(c_e, dtype=jnp.int32)
                    fresh = pos0 >= i * c_e
                    srcs = lax.dynamic_slice(
                        oe.edge_src, (start,), (c_e,))
                    nfid = lax.dynamic_slice(nbr_fid, (start,), (c_e,))
                    nlid = lax.dynamic_slice(nbr_lid, (start,), (c_e,))
                    kept = lax.dynamic_slice(keep, (start,), (c_e,))
                    live = jnp.logical_and(kept, fresh)
                    sl = jnp.minimum(srcs, vp - 1)
                    q = ell[sl]                 # [C, D] queries (N+(v))
                return visit(carry, sl, nfid, nlid, live, q)

            return lax.fori_loop(0, n_chunks, body, carry)

        def pass_for(carry, rot_ell, cur_fid, step):
            """Ring step `step`: one walk against the target block of
            fragment `cur_fid`, over the entries whose neighbour it holds
            (the tiered schedule's own segments of the step, where the
            test on `nfid_c` drops the padding alone; all of the untiered
            one)."""

            def chunk_credit(carry, sl, nfid_c, nlid_c, live, q):
                cr, slots = carry
                w = q.shape[1]
                with jax.named_scope("grape.lcc.rows"):
                    sel = jnp.logical_and(live, nfid_c == cur_fid)
                    qv = (jnp.arange(w)[:, None]
                          < cnt[sl][None, :])   # [W, C] valid query slots
                    tgt = rot_ell[nlid_c]       # [C, D] (N+(u))
                with jax.named_scope("grape.lcc.intersect"):
                    # the chunk's edges along the minor axis
                    hit = _members(q.T, tgt.T, qv, sel)
                    c1 = hit.sum(axis=0, dtype=jnp.int32)

                v_pid = my_fid * vp + sl  # local row pid
                with jax.named_scope("grape.lcc.credit"):
                    cr = cr.at[jnp.where(sel, v_pid, n_pad)].add(
                        jnp.where(sel, c1, 0)
                    )
                    if lcc_mode:
                        u_pid = cur_fid * vp + nlid_c
                        cr = cr.at[jnp.where(sel, u_pid, n_pad)].add(
                            jnp.where(sel, c1, 0)
                        )
                        # far-end credits: +1 at the slot of each matched
                        # member
                        slots = {**slots, w: _fold_rows(
                            slots[w], sl, hit, runs[w])}
                return cr, slots

            return walk(carry, chunk_credit, "grape.lcc.rows", step)

        if fnum == 1:
            cred, slots = pass_for((cred, slots), ell, jnp.int32(0), 0)
        else:
            perm = [(i, (i - 1) % fnum) for i in range(fnum)]

            def ring_body(s, carry):
                credits, r_ell = carry
                cur_fid = (my_fid + s) % fnum
                credits = pass_for(credits, r_ell, cur_fid, s)
                with jax.named_scope("grape.lcc.ring"):
                    r_ell = lax.ppermute(r_ell, FRAG_AXIS, perm)
                return credits, r_ell

            (cred, slots), _ = lax.fori_loop(
                0, fnum, ring_body, ((cred, slots), ell))

        if lcc_mode:
            # the flush, once a query: the whole schedule once more without
            # targets.  The scheduled edge (v, u) is the slot (v, j) with
            # ell[v, j] == u, so each slot is read by exactly one edge
            def flush(cr, sl, nfid_c, nlid_c, live, q):
                with jax.named_scope("grape.lcc.credit"):
                    u_pid = nfid_c * vp + nlid_c
                    far = _slot_of(slots[q.shape[1]][sl], q, u_pid)
                    return cr.at[jnp.where(live, u_pid, n_pad)].add(
                        jnp.where(live, far, 0)
                    )

            cred = walk(cred, flush, "grape.lcc.credit")

        with jax.named_scope("grape.lcc.credit"):
            total = ctx.sum(cred[:n_pad])
            tri = lax.dynamic_slice(total, (my_fid * vp,), (vp,))

        if self.credit_mode == "apex":
            # raw per-apex triangle counts (k=3 clique counting) stay
            # integer end to end — float32 would round above 2^24
            with jax.named_scope("grape.app.update"):
                out = jnp.where(
                    frag.inner_mask, tri, 0).astype(jnp.int32)
            return dict(state, tri=out), jnp.int32(0)
        with jax.named_scope("grape.app.update"):
            dt = state["lcc"].dtype
            deg_local = frag.out_degree
            degf = deg_local.astype(dt)
            denom = degf * (degf - 1)
            lcc = jnp.where(
                jnp.logical_and(frag.inner_mask, deg_local >= 2),
                2.0 * tri.astype(dt) / jnp.maximum(denom, 1),
                jnp.asarray(0, dt),
            )
        return dict(state, lcc=lcc), jnp.int32(0)

    def inceval(self, ctx, frag, state):
        return state, jnp.int32(0)

    def finalize(self, frag, state):
        return np.asarray(state["lcc"])


class ApexTriangleCount(LCCBeta):
    """k=3 clique counting: the merge kernel in apex-only credit mode
    with integer counts (used by models/kclique.py).  Uses the same
    low->high orientation as the k=4 kernel and the host recursion, so
    per-apex attribution is consistent across every k (each clique
    credits its (degree, id)-minimal member)."""

    credit_mode = "apex"
    orientation = "lo"
    result_format = "int"

    def init_state(self, frag, **kw):
        state = super().init_state(frag, **kw)
        state["tri"] = np.zeros((frag.fnum, frag.vp), dtype=np.int32)
        return state

    def finalize(self, frag, state):
        return np.asarray(state["tri"]).astype(np.int64)
