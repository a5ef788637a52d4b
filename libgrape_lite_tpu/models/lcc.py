"""LCC — local clustering coefficient (triangle counting).

Re-design of `examples/analytical_apps/lcc/lcc.h` (+ the SIMD set
intersection of `lcc_opt.h:26-41`): orient the (deduplicated) undirected
graph into a DAG by (degree, id) — u ∈ N+(v) iff deg(u) < deg(v) or
(deg equal and id(u) < id(v)) (`lcc.h` stage-1 neighbor filter) — then
every triangle has a unique apex v with v→u, v→w, u→w and each corner
earns +1 (`lcc.h:170-180`).  lcc(v) = 2·T(v) / (deg(v)·(deg(v)−1)) with
deg the raw adjacency degree (`lcc_context.h:52-68`).

TPU formulation (validated bit-exact vs `dataset/p2p-31-LCC`):

  * N+ / N− adjacency become *packed bitmaps* `[vp, N_pad/32] uint32`;
    set intersection = `bitwise_and` + `lax.population_count` — the VPU
    replaces the reference's STTNI/AVX-512 intersection kernels.
  * Remote bitmap rows travel by ring `ppermute` (the classic systolic
    distributed-join): at step s each shard holds shard (fid+s)'s N+
    block and processes exactly the edges whose head lives there.  This
    replaces the reference's per-vertex neighbor-list messages
    (`lcc.h` stage 1→2) with dense ICI traffic.
  * Per-corner credits: apex and middle credit locally per edge
    (v, u ∈ edge), the far-end credit accumulates into a pid-indexed
    vector folded by `psum` at the end.

Three popcount passes per edge total — O(E · N/32) word-ops, chunked to
bound HBM working set (GRAPE_LCC_CHUNK, default 4096).

r11 (ops/spgemm_pack.py): the promised successor landed as the tiled
masked-SpGEMM backend — GRAPE_LCC_BACKEND = intersect | spgemm | auto
routes the triangle-credit pass through pruned [128, 128] bitmap-tile
products reduced on the MXU instead of the O(N/32)-per-row popcount
sweep; `auto` prices both static ledgers at the active rate
profile and records the decision (declines too — never silent) in
spgemm_pack.SPGEMM_STATS.  Per-vertex triangle counts are
integer-identical across backends (same 3-credit algebra over the same
oriented dedup edge set), so the lcc output is BIT-exact either way:
both backends feed the same `_emit` tail.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.pallas_kernels import row_and_popcount
from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_CHUNK_DEFAULT = 4096


def _lcc_chunk() -> int:
    """Edge-chunk size of the intersect kernel's HBM working set —
    env-tunable (GRAPE_LCC_CHUNK) instead of the r1 baked constant
    (grape-lint R1's baked-constant class: a module literal consumed
    by a traced body is invisible to every cache key; as an app
    attribute it rides `trace_key` and the intersect op model)."""
    spec = os.environ.get("GRAPE_LCC_CHUNK", "")
    if not spec:
        return _CHUNK_DEFAULT
    try:
        v = int(spec)
    except ValueError:
        raise ValueError(
            f"GRAPE_LCC_CHUNK={spec!r}: expected a positive int"
        ) from None
    if v <= 0:
        raise ValueError(f"GRAPE_LCC_CHUNK={v} must be positive")
    return v


class LCC(ParallelAppBase):
    load_strategy = LoadStrategy.kOnlyOut
    message_strategy = MessageStrategy.kAlongOutgoingEdgeToOuterVertex
    result_format = "float"
    replicated_keys = frozenset()

    def init_state(self, frag, degree_threshold: int = 0, **_):
        from libgrape_lite_tpu.ops.spgemm_pack import (
            resolve_lcc_backend,
            resolve_spgemm_dispatch,
        )

        # degree_threshold > 0 skips hub vertices' neighbor lists — the
        # reference's cost cap (`lcc.h:234-243` filterByDegree, flag
        # default INT_MAX i.e. disabled; 0 here means disabled too)
        self.degree_threshold = int(degree_threshold)
        self.lcc_chunk = _lcc_chunk()
        state = {
            "lcc": np.zeros((frag.fnum, frag.vp), dtype=np.float64),
        }
        # backend resolution (GRAPE_LCC_BACKEND; decisions + declines
        # recorded in SPGEMM_STATS).  `lcc_backend` and the plan uid
        # are primitive attrs, so they ride trace_key: the two
        # backends never share a compiled runner
        self.lcc_backend = resolve_lcc_backend(
            type(self).__name__, frag,
            degree_threshold=self.degree_threshold,
            chunk=self.lcc_chunk,
        )
        self._spgemm = None
        self._spgemm_uid = -1
        self.ephemeral_keys = frozenset()
        if self.lcc_backend == "spgemm":
            self._spgemm = resolve_spgemm_dispatch(
                frag, degree_threshold=self.degree_threshold
            )
            self._spgemm_uid = self._spgemm.uid
            entries = self._spgemm.state_entries()
            state.update(entries)
            self.ephemeral_keys = frozenset(entries)
        return state

    # ---- helpers -------------------------------------------------------

    @staticmethod
    def _dedup_mask(csr):
        """Adjacent-duplicate mask; build_csr sorts by (src, nbr) so
        multi-edges are adjacent."""
        s, n = csr.edge_src, csr.edge_nbr
        dup = jnp.zeros_like(csr.edge_mask).at[1:].set(
            jnp.logical_and(s[1:] == s[:-1], n[1:] == n[:-1])
        )
        return jnp.logical_and(csr.edge_mask, ~dup)

    @staticmethod
    def _build_bitmap(rows, cols, keep, vp, words):
        """Packed adjacency bitmap — delegates to the shared
        utils/bitset.pack_bits (kept (row, col) pairs must be unique so
        bit-add == bit-or)."""
        from libgrape_lite_tpu.utils.bitset import pack_bits

        return pack_bits(cols, keep, vp, rows, words * 32)

    # ---- the staged computation ---------------------------------------

    def peval(self, ctx: StepContext, frag, state):
        """Backend-dispatched triangle credits, one shared emit tail:
        both backends produce the SAME int32 per-vertex triangle
        counts (pinned by tests/test_spgemm.py), so every downstream
        bit is backend-independent by construction."""
        if getattr(self, "lcc_backend", "intersect") == "spgemm":
            tri = self._tri_spgemm(ctx, frag, state)
        else:
            with jax.named_scope("grape.lcc.intersect"):
                tri = self._tri_intersect(ctx, frag, state)
        return self._emit(ctx, frag, state, tri)

    def _tri_spgemm(self, ctx: StepContext, frag, state):
        """Per-vertex triangle counts via the tiled masked SpGEMM
        (ops/spgemm_pack.py): per-shard pruned tile products credit
        apex/middle/far into a pid-indexed vector, folded by one psum
        — the same credit exchange as the intersect ring."""
        vp, fnum = frag.vp, frag.fnum
        my_fid = lax.axis_index(FRAG_AXIS).astype(jnp.int32)
        cred = self._spgemm.credits(state)
        cred_all = ctx.sum(cred)
        return lax.dynamic_slice(cred_all, (my_fid * vp,), (vp,))

    def _emit(self, ctx: StepContext, frag, state, tri):
        deg_local = frag.out_degree
        deg64 = deg_local.astype(
            jnp.float64 if state["lcc"].dtype == jnp.float64
            else jnp.float32
        )
        denom = deg64 * (deg64 - 1)
        lcc = jnp.where(
            jnp.logical_and(frag.inner_mask, deg_local >= 2),
            2.0 * tri.astype(denom.dtype) / jnp.maximum(denom, 1),
            0.0,
        )
        return dict(state, lcc=lcc.astype(state["lcc"].dtype)), jnp.int32(0)

    def _tri_intersect(self, ctx: StepContext, frag, state):
        vp, fnum = frag.vp, frag.fnum
        n_pad = vp * fnum
        words = (n_pad + 31) // 32
        my_fid = lax.axis_index(FRAG_AXIS).astype(jnp.int32)
        base_pid = my_fid * vp

        deg_local = frag.out_degree  # includes multiplicity (lcc_context degree)
        deg_full = ctx.gather_state(deg_local)

        oe, ie = frag.oe, frag.ie

        def oriented(csr, toward_nbr: bool):
            """toward_nbr=True keeps edges oriented row→nbr
            (deg[nbr] < deg[row] or tie with nbr_pid < row_pid);
            False keeps nbr→row."""
            row_pid = base_pid + jnp.minimum(csr.edge_src, vp - 1)
            d_row = deg_local[jnp.minimum(csr.edge_src, vp - 1)]
            d_nbr = deg_full[csr.edge_nbr]
            if toward_nbr:
                k = jnp.logical_or(
                    d_nbr < d_row,
                    jnp.logical_and(d_nbr == d_row, csr.edge_nbr < row_pid),
                )
            else:
                k = jnp.logical_or(
                    d_row < d_nbr,
                    jnp.logical_and(d_nbr == d_row, row_pid < csr.edge_nbr),
                )
            thr = getattr(self, "degree_threshold", 0)
            if thr > 0:
                # a filtered vertex contributes no N+ list (lcc.h:98,164):
                # drop rows of filtered list owners — the list owner is
                # the row vertex when orienting row→nbr, the nbr otherwise
                owner_deg = d_row if toward_nbr else d_nbr
                k = jnp.logical_and(k, owner_deg <= thr)
            return jnp.logical_and(self._dedup_mask(csr), k)

        keep_oe = oriented(oe, True)   # v(row) → u(nbr):  u ∈ N+(v)
        keep_ie = oriented(ie, False)  # u(nbr) → w(row):  u ∈ N−(w)

        bplus = self._build_bitmap(oe.edge_src, oe.edge_nbr, keep_oe, vp, words)
        bminus = self._build_bitmap(ie.edge_src, ie.edge_nbr, keep_ie, vp, words)

        ep_oe = oe.edge_src.shape[0]
        ep_ie = ie.edge_src.shape[0]
        chunk = getattr(self, "lcc_chunk", _CHUNK_DEFAULT)
        c_oe = min(chunk, ep_oe)
        c_ie = min(chunk, ep_ie)
        tri = jnp.zeros((vp,), dtype=jnp.int32)
        cred = jnp.zeros((n_pad,), dtype=jnp.int32)

        nbr_fid_oe = (oe.edge_nbr // vp).astype(jnp.int32)
        nbr_lid_oe = (oe.edge_nbr % vp).astype(jnp.int32)
        nbr_fid_ie = (ie.edge_nbr // vp).astype(jnp.int32)
        nbr_lid_ie = (ie.edge_nbr % vp).astype(jnp.int32)

        def edge_chunks(ep, c):
            return max(1, -(-ep // c))

        def intersect_pass(carry_tri, carry_cred, brot, cur_fid):
            """One ring step: process oe edges (apex+middle credits) and
            ie edges (far-end credit) whose nbr lives on `cur_fid`."""

            def oe_body(i, acc):
                t, c = acc
                start = jnp.minimum(i * c_oe, ep_oe - c_oe)
                pos = start + jnp.arange(c_oe, dtype=jnp.int32)
                fresh = pos >= i * c_oe  # exclude clamped overlap
                srcs = lax.dynamic_slice(oe.edge_src, (start,), (c_oe,))
                nfid = lax.dynamic_slice(nbr_fid_oe, (start,), (c_oe,))
                nlid = lax.dynamic_slice(nbr_lid_oe, (start,), (c_oe,))
                kept = lax.dynamic_slice(keep_oe, (start,), (c_oe,))
                sel = jnp.logical_and(jnp.logical_and(kept, fresh), nfid == cur_fid)
                rows_v = bplus[jnp.minimum(srcs, vp - 1)]
                rows_u = brot[nlid]
                cnt = row_and_popcount(rows_v, rows_u)
                cnt = jnp.where(sel, cnt, 0)
                t = t.at[jnp.where(sel, srcs, vp - 1)].add(
                    jnp.where(sel, cnt, 0)
                )
                u_pid = cur_fid * vp + nlid
                c = c.at[jnp.where(sel, u_pid, 0)].add(jnp.where(sel, cnt, 0))
                return t, c

            def ie_body(i, t):
                start = jnp.minimum(i * c_ie, ep_ie - c_ie)
                pos = start + jnp.arange(c_ie, dtype=jnp.int32)
                fresh = pos >= i * c_ie
                srcs = lax.dynamic_slice(ie.edge_src, (start,), (c_ie,))
                nfid = lax.dynamic_slice(nbr_fid_ie, (start,), (c_ie,))
                nlid = lax.dynamic_slice(nbr_lid_ie, (start,), (c_ie,))
                kept = lax.dynamic_slice(keep_ie, (start,), (c_ie,))
                sel = jnp.logical_and(jnp.logical_and(kept, fresh), nfid == cur_fid)
                rows_w = bminus[jnp.minimum(srcs, vp - 1)]
                rows_v = brot[nlid]
                cnt = row_and_popcount(rows_w, rows_v)
                t = t.at[jnp.where(sel, srcs, vp - 1)].add(
                    jnp.where(sel, cnt, 0)
                )
                return t

            t = lax.fori_loop(
                0, edge_chunks(ep_oe, c_oe), oe_body, (carry_tri, carry_cred)
            )
            carry_tri, carry_cred = t
            carry_tri = lax.fori_loop(
                0, edge_chunks(ep_ie, c_ie), ie_body, carry_tri
            )
            return carry_tri, carry_cred

        if fnum == 1:
            tri, cred = intersect_pass(tri, cred, bplus, jnp.int32(0))
        else:
            perm = [(i, (i - 1) % fnum) for i in range(fnum)]  # shift left

            def ring_body(s, carry):
                t, c, brot = carry
                cur_fid = (my_fid + s) % fnum
                t, c = intersect_pass(t, c, brot, cur_fid)
                brot = lax.ppermute(brot, FRAG_AXIS, perm)
                return t, c, brot

            tri, cred, _ = lax.fori_loop(0, fnum, ring_body, (tri, cred, bplus))

        cred_all = ctx.sum(cred)
        return tri + lax.dynamic_slice(cred_all, (base_pid,), (vp,))

    def inceval(self, ctx: StepContext, frag, state):
        return state, jnp.int32(0)

    def invariants(self, frag, state):
        # a clustering coefficient is a triangle fraction: [0, 1] on a
        # deduplicated simple graph (in_range also rejects NaN)
        from libgrape_lite_tpu.guard.invariants import in_range

        return [in_range("lcc", lo=0.0, hi=1.0)]

    def finalize(self, frag, state):
        return np.asarray(state["lcc"])
