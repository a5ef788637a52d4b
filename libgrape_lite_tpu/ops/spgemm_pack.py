"""Tiled masked SpGEMM for triangle-style workloads on the MXU.

ROADMAP item 5a: the GraphBLAS triangle-count formulation
``B = (A · Aᵀ) ∘ A`` over the degree-oriented DAG, with all
irregularity compiled into static streams at plan time and the
per-round dataflow dense vector/matrix work.

Formulation (the output-stationary form of masked SpGEMM): with ``D``
the deduplicated degree-oriented adjacency (v → u iff (deg, id) orders
v and u; every triangle {v, u, w} has exactly one labeling v → u,
v → w, u → w), the masked product only needs entries where the MASK is
nonzero — and the mask IS the oriented edge list.  So the plan
enumerates mask edges directly and tiles the CONTRACTION dimension:

  * the w-space (list members) is COMPACTED and popularity-sorted at
    plan time, then cut into 128-lane K-tiles; D ships as a packed
    bitmap ``[rows, nK * 4] uint32`` over that compacted space — the
    [128, 128]-bit adjacency tile is the storage unit;
  * one work ITEM = (mask edge (v, u), K-tile k).  Plan-time tile
    pruning emits an item only when BOTH operand rows have bits in
    tile k (skip empty A-row × A-col tile products) — on power-law
    graphs this prunes the vast majority of the n/128 candidate tiles
    per edge (bench RMAT-16: 4.5 items/edge vs 135 K-tiles);
  * the kernel processes items in chunks of ``cfg.chunk``: gather the
    two packed rows' k-tile words, expand to dense uint8 [chunk, 128]
    blocks, AND them, and reduce the hit block to per-edge counts with
    one ``[chunk, 128] @ [128, 128]`` matmul (a VPU tree-reduce would
    work too; the matmul keeps the reduction off the vector unit);
  * credits scatter per item: ``cnt`` to the apex v and middle u pids,
    the hit VECTOR to the far-end pids of tile k (a static
    colspace → pid table row) — the same 3-credit algebra as the
    popcount kernel's oe + ie passes, so per-vertex triangle counts
    are INTEGER-IDENTICAL to the intersect backend by construction
    (triangle enumeration is orientation-agnostic; each triangle is
    found exactly once, at its unique DAG (v, u) edge).

Sharding: items are partitioned by the apex fragment; each shard ships
a sub-bitmap holding only the rows its items reference, plus its item
streams padded to the cross-shard max (shard_map needs one static
program).  Credits accumulate in a pid-indexed vector folded by one
``psum`` — exactly the popcount kernel's credit exchange.

Cost: the static op-budget ledger carries split engine columns
(``vpu_ops`` / ``mxu_ops`` / ``hbm_bytes``) under conventions mirrored
(and independently recounted) by scripts/pack_cost_model.py.  The
popcount intersect pays 3 · n_pad/32 word-ops per edge per pass —
linear in VERTEX COUNT, the six-LDBC breadth ceiling this primitive
lifts: the item count scales with the pruned tile products instead
(arxiv 2311.03826's structured-SpGEMM framing; the per-tile pricing
discipline follows SparseP, arxiv 2201.05072).

`GRAPE_LCC_BACKEND` = intersect | spgemm | auto selects the LCC
backend; `auto` prices both ledgers at the active rate profile.
Declines are RECORDED in SPGEMM_STATS — never silent.
"""

from __future__ import annotations

import itertools
import os
from dataclasses import dataclass, field

import numpy as np

C = 128          # lane width == K-tile width (one [128,128]-bit tile)
WPT = C // 32    # uint32 words per bitmap row per K-tile

# Rate assumptions for the auto backend pricing come from the shared
# RateProfile (ops/calibration.py) — the same rates every other priced
# surface reads, fitted or pinned.  Only the op-count CONVENTIONS below
# stay literal: the recount gate in scripts/pack_cost_model compares op
# counts (rates cancel in the mismatch), so sharing rates is safe while
# sharing counts would make the gate tautological.

# modeled per-item op counts (counting conventions, shared with the
# independent recount in scripts/pack_cost_model.spgemm_recount — a
# drift here must trip the 5% gate there, so do not import these from
# the recount side):
#   * expand: 6 plane-rows of 128 lanes (two operands x shift / mask /
#     lane-select of the 4 packed words into the dense uint8 block);
#   * mask_and: 2 planes (the AND and the item-validity select);
#   * far_scatter: 1 plane (the [128]-lane hit-vector scatter-add);
#   * tail: 1 plane (count cast + apex/middle scalar scatters, priced
#     at one plane per item — scalar work rides the vector epilogue);
#   * count-reduce: ONE [chunk,128] @ [128,128] matmul row per item =
#     128 MXU output elements (`mxu` column);
#   * gather_rows: 2 per item (the two packed bitmap row fetches).
_ITEM_VPU_PLANES = {"expand": 6, "mask_and": 2, "far_scatter": 1,
                    "tail": 1}
_ITEM_VPU = sum(_ITEM_VPU_PLANES.values())   # 10 planes x 128 lanes
_ITEM_MXU = C
_ITEM_GATHER_ROWS = 2

_SPGEMM_SCHEMA_VERSION = 1


@dataclass(frozen=True)
class SpGemmConfig:
    """chunk = items per kernel step (the fori_loop body's [chunk, 128]
    working set; GRAPE_SPGEMM_CHUNK overrides).  Part of the plan
    geometry: the item streams are padded to a chunk multiple."""

    chunk: int = 1024

    def __post_init__(self):
        if not (0 < self.chunk <= (1 << 20)):
            raise ValueError(
                f"chunk={self.chunk} not in (0, {1 << 20}]"
            )

    @staticmethod
    def from_env() -> "SpGemmConfig":
        spec = os.environ.get("GRAPE_SPGEMM_CHUNK", "")
        if not spec:
            return SpGemmConfig()
        try:
            return SpGemmConfig(chunk=int(spec))
        except ValueError as e:
            raise ValueError(
                f"GRAPE_SPGEMM_CHUNK={spec!r}: expected a positive int"
            ) from e


_PLAN_COUNTER = itertools.count()


@dataclass
class SpGemmPlan:
    """Static streams + ledger for one fragment's masked SpGEMM."""

    n_pad: int
    fnum: int
    vp: int
    n_ktiles: int                 # compacted-colspace tiles (K dim)
    words: int                    # uint32 words per bitmap row
    items: int                    # real work items across shards
    p_pad: int                    # per-shard padded item count
    rows_pad: int                 # per-shard padded bitmap height
    mask_edges: int               # kept oriented (dedup) edges
    orientation: str              # "lo" | "hi" (threshold forces hi)
    degree_threshold: int
    cfg: SpGemmConfig = field(default_factory=SpGemmConfig)
    # [fnum, ...] stacked device streams (None for plan_only plans)
    host_streams: dict | None = None
    ledger: dict = field(default_factory=dict)
    stats: dict = field(default_factory=dict)
    uid: int = field(default_factory=lambda: next(_PLAN_COUNTER))


# stream-name -> dtype table (fingerprinted in the disk-cache digest)
_SG_DTYPES = {
    "bm": "uint32", "vrow": "int32", "urow": "int32", "kt": "int32",
    "apex": "int32", "mid": "int32", "valid": "int8", "colpid": "int32",
}


def _ledger_from_counts(items: int, mask_edges: int, n_chunks: int,
                        hbm_bytes: int) -> dict:
    """The op-budget ledger under the conventions above: split engine
    columns, per-stage attribution, one level — the shape
    Worker.pack_ledger and the bench consume."""
    per_stage = {
        k: v * C * items for k, v in _ITEM_VPU_PLANES.items()
    }
    vpu = sum(per_stage.values())
    mxu = _ITEM_MXU * items
    gr = _ITEM_GATHER_ROWS * items
    totals = {
        "vpu_ops": vpu, "mxu_ops": mxu, "gather_rows": gr,
        "hbm_bytes": hbm_bytes, "blocks": n_chunks,
        "per_stage": per_stage,
    }
    return {
        "edges": mask_edges,
        "levels": [{
            "level": 0, "blocks": n_chunks, "has_gather": True,
            "vpu_ops": vpu, "mxu_ops": mxu, "gather_rows": gr,
            "hbm_bytes": hbm_bytes, "per_stage": per_stage,
        }],
        "totals": totals,
    }


def _oriented_mask_edges(frag, degree_threshold: int):
    """Host-side oriented dedup edge list in GLOBAL pids, matching
    models/lcc.py's traced `oriented(oe, True)` rule exactly:

      * degree = out-degree incl. multiplicity (lcc_context degree);
      * dedup + self-loop drop (build_csr sorts, np.unique here);
      * threshold > 0 keeps the reference's "hi" orientation (the
        filter semantics of lcc.h:234-243 are DEFINED on lower-degree
        neighbor lists: a filtered OWNER contributes no list) and
        drops rows of filtered owners;
      * threshold == 0 orients "lo" (toward the higher (deg, id)
        endpoint): triangle enumeration is orientation-agnostic, and
        under "lo" the compacted column space concentrates on hubs —
        fewer K-tiles, denser pruning.

    Returns (v, u, deg) with v, u int64 pid arrays row-major sorted.
    """
    fnum, vp = frag.fnum, frag.vp
    n_pad = fnum * vp
    deg = np.zeros(n_pad, dtype=np.int64)
    vs, us = [], []
    for f in range(fnum):
        h = frag.host_oe[f]
        deg[f * vp:(f + 1) * vp] = np.diff(h.indptr)
        e = h.num_edges
        vs.append(f * vp + np.asarray(h.edge_src[:e], dtype=np.int64))
        us.append(np.asarray(h.edge_nbr[:e], dtype=np.int64))
    v = np.concatenate(vs) if vs else np.zeros(0, np.int64)
    u = np.concatenate(us) if us else np.zeros(0, np.int64)
    keep = v != u
    v, u = v[keep], u[keep]
    if len(v):
        pairs = np.unique(np.stack([v, u], 1), axis=0)
        v, u = pairs[:, 0], pairs[:, 1]
    thr = int(degree_threshold)
    if thr > 0:
        k = (deg[u] < deg[v]) | ((deg[u] == deg[v]) & (u < v))
        k &= deg[v] <= thr
        orientation = "hi"
    else:
        k = (deg[u] > deg[v]) | ((deg[u] == deg[v]) & (u > v))
        orientation = "lo"
    return v[k], u[k], deg, orientation


def plan_spgemm(frag, degree_threshold: int = 0,
                cfg: SpGemmConfig | None = None,
                plan_only: bool = False) -> SpGemmPlan:
    """Build the static masked-SpGEMM plan for `frag`.

    `plan_only=True` computes geometry, item counts and the ledger
    WITHOUT materializing device streams — the bench's modeled A/B at
    full bench geometry plans this way (the executed lane geometry
    ships real streams and is recount-gated)."""
    cfg = cfg or SpGemmConfig.from_env()
    fnum, vp = frag.fnum, frag.vp
    n_pad = fnum * vp
    v, u, deg, orientation = _oriented_mask_edges(frag, degree_threshold)
    return _plan_from_oriented(
        v, u, n_pad, fnum, vp, orientation, int(degree_threshold), cfg,
        plan_only,
    )


def plan_spgemm_edges(src, dst, n_vertices: int,
                      degree_threshold: int = 0,
                      cfg: SpGemmConfig | None = None,
                      plan_only: bool = True) -> SpGemmPlan:
    """Plan from a RAW undirected edge list (no fragment build) —
    host-side harnesses: the bench's modeled A/B at full bench
    geometry plans this way (plan_only).  Symmetrizes, dedups, drops
    self-loops and orients exactly like the fragment path (degree =
    symmetrized adjacency degree incl. multiplicity)."""
    cfg = cfg or SpGemmConfig.from_env()
    vp = -(-int(n_vertices) // C) * C
    a = np.concatenate([np.asarray(src, np.int64),
                        np.asarray(dst, np.int64)])
    b = np.concatenate([np.asarray(dst, np.int64),
                        np.asarray(src, np.int64)])
    keep = a != b
    a, b = a[keep], b[keep]
    deg = np.bincount(a, minlength=vp)
    if len(a):
        pairs = np.unique(np.stack([a, b], 1), axis=0)
        a, b = pairs[:, 0], pairs[:, 1]
    thr = int(degree_threshold)
    if thr > 0:
        k = (deg[b] < deg[a]) | ((deg[b] == deg[a]) & (b < a))
        k &= deg[a] <= thr
        orientation = "hi"
    else:
        k = (deg[b] > deg[a]) | ((deg[b] == deg[a]) & (b > a))
        orientation = "lo"
    return _plan_from_oriented(
        a[k], b[k], vp, 1, vp, orientation, thr, cfg, plan_only
    )


def _plan_from_oriented(v, u, n_pad, fnum, vp, orientation, thr,
                        cfg: SpGemmConfig, plan_only: bool) -> SpGemmPlan:
    E = len(v)
    # ---- compacted, popularity-sorted column (w) space ----
    colcnt = np.bincount(u, minlength=n_pad)
    cols = np.argsort(-colcnt, kind="stable")
    cols = cols[colcnt[cols] > 0]
    colmap = np.full(n_pad, -1, dtype=np.int64)
    colmap[cols] = np.arange(len(cols))
    n_ktiles = max(1, -(-len(cols) // C))
    words = n_ktiles * WPT

    # ---- bitmap row space: vertices with oriented out-edges ----
    rowcnt = np.bincount(v, minlength=n_pad)
    rows = np.flatnonzero(rowcnt > 0)
    rowmap = np.full(n_pad, -1, dtype=np.int64)
    rowmap[rows] = np.arange(len(rows))
    n_rows = max(1, len(rows))

    # ---- per-row K-tile incidence (u64 bitset) for pruning ----
    kt_of_u = colmap[u] // C
    kwords = (n_ktiles + 63) // 64
    ktbm = np.zeros((n_rows, kwords), dtype=np.uint64)
    rk = np.unique(rowmap[v] * n_ktiles + kt_of_u)
    rr, kk = rk // n_ktiles, rk % n_ktiles
    np.bitwise_or.at(
        ktbm, (rr, kk // 64),
        np.uint64(1) << (kk % 64).astype(np.uint64),
    )

    # items: per mask edge, the K-tiles where BOTH rows have bits
    # (u ∉ rowspace has no list -> no items; the edge contributes 0)
    vr_all = rowmap[v]
    ur_all = rowmap[u]
    has_u = ur_all >= 0
    items = 0
    items_by_fid = np.zeros(fnum, dtype=np.int64)
    item_e: list = []
    item_k: list = []
    step = max(1, (1 << 24) // max(n_ktiles, 1))
    sel = np.flatnonzero(has_u)
    for lo in range(0, len(sel), step):
        s = sel[lo:lo + step]
        both = ktbm[vr_all[s]] & ktbm[ur_all[s]]
        bits = (
            (both[:, :, None] >> np.arange(64, dtype=np.uint64)) & 1
        ).astype(bool).reshape(len(s), kwords * 64)[:, :n_ktiles]
        per_edge = bits.sum(axis=1).astype(np.int64)
        np.add.at(items_by_fid, (v[s] // vp).astype(np.int64), per_edge)
        if plan_only:
            items += int(per_edge.sum())
        else:
            ei, ki = np.nonzero(bits)
            items += len(ei)
            item_e.append(s[ei])
            item_k.append(ki.astype(np.int64))

    stats = {
        "mask_edges": E, "items": items,
        "items_per_edge": round(items / max(1, E), 3),
        "n_ktiles": n_ktiles, "colspace": int(len(cols)),
        "rowspace": int(len(rows)), "orientation": orientation,
    }

    if plan_only:
        # byte model mirrors the materialized layout: item streams pad
        # to the PER-SHARD max (not the total — billing fnum x total
        # would inflate the spgemm HBM cost ~fnum-fold and bias the
        # auto decision toward intersect); the stacked sub-bitmap is
        # modeled at the full height once (a lower bound — hub rows
        # duplicate across shards in the shipped form)
        rows_pad = n_rows
        p_max = int(items_by_fid.max()) if fnum > 1 else items
        p_pad = max(cfg.chunk,
                    -(-max(1, p_max) // cfg.chunk) * cfg.chunk)
        hbm = (rows_pad * words * 4
               + fnum * p_pad * (5 * 4 + 1)
               + fnum * n_ktiles * C * 4)
        n_chunks = fnum * (p_pad // cfg.chunk)
        return SpGemmPlan(
            n_pad=n_pad, fnum=fnum, vp=vp, n_ktiles=n_ktiles,
            words=words, items=items, p_pad=p_pad, rows_pad=rows_pad,
            mask_edges=E, orientation=orientation, degree_threshold=thr,
            cfg=cfg, host_streams=None,
            ledger=_ledger_from_counts(items, E, n_chunks, hbm),
            stats=stats,
        )

    e_idx = (np.concatenate(item_e) if item_e
             else np.zeros(0, np.int64))
    k_idx = (np.concatenate(item_k) if item_k
             else np.zeros(0, np.int64))

    # ---- packed adjacency bitmap over the compacted colspace ----
    bm = np.zeros((n_rows, words), dtype=np.uint32)
    cw = colmap[u]
    np.bitwise_or.at(
        bm, (rowmap[v], (cw // 32).astype(np.int64)),
        (np.uint32(1) << (cw % 32).astype(np.uint32)),
    )

    # colspace block -> pid table (far-end credit scatter targets);
    # padding lanes hit the n_pad sink row
    colpid = np.full(n_ktiles * C, n_pad, dtype=np.int32)
    colpid[:len(cols)] = cols.astype(np.int32)

    # ---- partition items by apex fragment, build per-shard streams ----
    fid_of = (v[e_idx] // vp).astype(np.int64) if len(e_idx) else \
        np.zeros(0, np.int64)
    per_shard = [np.flatnonzero(fid_of == f) for f in range(fnum)]
    p_real = [len(s) for s in per_shard]
    p_max = max([1] + p_real)
    p_pad = -(-p_max // cfg.chunk) * cfg.chunk

    sub_rows = []
    for f in range(fnum):
        s = per_shard[f]
        need = np.unique(np.concatenate([
            vr_all[e_idx[s]], ur_all[e_idx[s]],
        ])) if len(s) else np.zeros(0, np.int64)
        sub_rows.append(need)
    rows_pad = max(1, max(len(r) for r in sub_rows))

    st = {
        "bm": np.zeros((fnum, rows_pad, words), np.uint32),
        "vrow": np.zeros((fnum, p_pad), np.int32),
        "urow": np.zeros((fnum, p_pad), np.int32),
        "kt": np.zeros((fnum, p_pad), np.int32),
        "apex": np.full((fnum, p_pad), n_pad, np.int32),
        "mid": np.full((fnum, p_pad), n_pad, np.int32),
        "valid": np.zeros((fnum, p_pad), np.int8),
        "colpid": np.tile(colpid, (fnum, 1)),
    }
    for f in range(fnum):
        s = per_shard[f]
        if not len(s):
            continue
        need = sub_rows[f]
        local = np.full(n_rows, 0, dtype=np.int64)
        local[need] = np.arange(len(need))
        st["bm"][f, :len(need)] = bm[need]
        n = len(s)
        ei = e_idx[s]
        st["vrow"][f, :n] = local[vr_all[ei]].astype(np.int32)
        st["urow"][f, :n] = local[ur_all[ei]].astype(np.int32)
        st["kt"][f, :n] = k_idx[s].astype(np.int32)
        st["apex"][f, :n] = v[ei].astype(np.int32)
        st["mid"][f, :n] = u[ei].astype(np.int32)
        st["valid"][f, :n] = 1

    hbm = sum(int(a.nbytes) for a in st.values())
    n_chunks = fnum * (p_pad // cfg.chunk)
    stats["item_imbalance"] = round(
        p_max / max(1.0, items / max(1, fnum)), 3
    )
    return SpGemmPlan(
        n_pad=n_pad, fnum=fnum, vp=vp, n_ktiles=n_ktiles, words=words,
        items=items, p_pad=p_pad, rows_pad=rows_pad, mask_edges=E,
        orientation=orientation, degree_threshold=thr, cfg=cfg,
        host_streams=st,
        ledger=_ledger_from_counts(items, E, n_chunks, hbm),
        stats=stats,
    )


# --------------------------------------------------------------------------
# device executor
# --------------------------------------------------------------------------


def spgemm_credits(state: dict, prefix: str, n_pad: int, chunk: int):
    """Traced per-shard credit pass: returns the [n_pad] int32 partial
    triangle-credit vector (apex + middle + far contributions of this
    shard's items; caller psums across shards).

    Stage per chunk: gather the two packed rows' K-tile words, expand
    to dense uint8 [chunk, 128] blocks, AND + validity-mask, count via
    the [chunk, 128] @ [128, 128] matmul (the PR 4 MXU lowering
    shape), scatter cnt to apex/middle pids and the hit vector to the
    tile's far-end pids."""
    import jax.numpy as jnp
    from jax import lax

    bm = state[prefix + "bm"]
    vrow = state[prefix + "vrow"]
    urow = state[prefix + "urow"]
    kt = state[prefix + "kt"]
    apex = state[prefix + "apex"]
    mid = state[prefix + "mid"]
    valid = state[prefix + "valid"]
    colpid = state[prefix + "colpid"]
    p = vrow.shape[0]
    n_chunks = p // chunk
    # count-reduce operand: ones in column 0 — the matmul emits the
    # row sums in lane 0 (output shape [chunk, 128], the validated
    # [B,128] @ [128,128] form)
    ones = jnp.zeros((C, C), jnp.float32).at[:, 0].set(1.0)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    wiota = jnp.arange(WPT, dtype=jnp.int32)
    liota = jnp.arange(C, dtype=jnp.int32)

    def body(i, cred):
        def sl(a):
            return lax.dynamic_slice(a, (i * chunk,), (chunk,))

        vr, ur, k, ap, md, vd = (
            sl(vrow), sl(urow), sl(kt), sl(apex), sl(mid), sl(valid)
        )
        wcol = k[:, None] * WPT + wiota[None, :]
        vw = bm[vr[:, None], wcol]                       # [c, WPT] u32
        uw = bm[ur[:, None], wcol]
        vb = ((vw[:, :, None] >> shifts) & 1).reshape(chunk, C)
        ub = ((uw[:, :, None] >> shifts) & 1).reshape(chunk, C)
        hits = (vb & ub).astype(jnp.float32)
        hits = hits * vd[:, None].astype(jnp.float32)
        cnt = jnp.dot(
            hits, ones, preferred_element_type=jnp.float32
        )[:, 0].astype(jnp.int32)
        cred = cred.at[ap].add(cnt)
        cred = cred.at[md].add(cnt)
        far = colpid[k[:, None] * C + liota[None, :]]    # [c, C] pids
        cred = cred.at[far.reshape(-1)].add(
            hits.astype(jnp.int32).reshape(-1)
        )
        return cred

    cred = jnp.zeros((n_pad + 1,), jnp.int32)
    cred = lax.fori_loop(0, n_chunks, body, cred)
    return cred[:n_pad]


# --------------------------------------------------------------------------
# dispatch resolution: per-fragment cache + persistent plan cache
# --------------------------------------------------------------------------

_FRAG_PLAN_CACHE = None


def _frag_cache(frag):
    """The plans resolved for `frag`, weak-keyed on the fragment so they
    go with it (fleet/budget.plan_stream_bytes prices them)."""
    global _FRAG_PLAN_CACHE
    import weakref

    if _FRAG_PLAN_CACHE is None:
        _FRAG_PLAN_CACHE = weakref.WeakKeyDictionary()
    return _FRAG_PLAN_CACHE.setdefault(frag, {})


class SpGemmDispatch:
    """Resolved spgemm backend for one fragment: the plan plus the
    state-entry plumbing (streams ride as ephemeral [fnum, ...] state
    leaves)."""

    def __init__(self, plan: SpGemmPlan, prefix: str = "sg_"):
        self.plan = plan
        self.prefix = prefix

    @property
    def uid(self) -> int:
        return self.plan.uid

    @property
    def chunk(self) -> int:
        return self.plan.cfg.chunk

    def state_entries(self) -> dict:
        assert self.plan.host_streams is not None, \
            "plan_only plans ship no streams"
        return {
            self.prefix + k: v for k, v in self.plan.host_streams.items()
        }

    def state_keys(self):
        return [self.prefix + k for k in _SG_DTYPES]

    def ledger(self) -> dict:
        return self.plan.ledger

    def credits(self, state: dict):
        return spgemm_credits(
            state, self.prefix, self.plan.n_pad, self.chunk
        )


def resolve_spgemm_dispatch(frag, degree_threshold: int = 0,
                            cfg: SpGemmConfig | None = None,
                            prefix: str = "sg_") -> SpGemmDispatch:
    """Resolve (and cache) the spgemm plan for `frag`: per-fragment
    memo first, then the persistent plan cache (GRAPE_PACK_PLAN_CACHE,
    `spgemmplan_*` entries), then the host planner.  SPGEMM_STATS
    counts which of the three served."""
    cfg = cfg or SpGemmConfig.from_env()
    per_frag = _frag_cache(frag)
    key = ("spgemm", cfg, int(degree_threshold))
    if key in per_frag:
        SPGEMM_STATS["frag_cache_hits"] += 1
        return SpGemmDispatch(per_frag[key], prefix)
    v, u, deg, orientation = _oriented_mask_edges(frag, degree_threshold)
    plan = _load_cached_plan(v, u, frag, degree_threshold, cfg)
    if plan is not None:
        SPGEMM_STATS["disk_cache_hits"] += 1
    else:
        SPGEMM_STATS["planned"] += 1
        plan = _plan_from_oriented(
            v, u, frag.fnum * frag.vp, frag.fnum, frag.vp, orientation,
            int(degree_threshold), cfg, plan_only=False,
        )
        _save_cached_plan(plan, v, u, frag, degree_threshold, cfg)
    per_frag[key] = plan
    return SpGemmDispatch(plan, prefix)


def _spgemm_digest(v, u, frag, thr: int, cfg: SpGemmConfig) -> str:
    """Content key for cached spgemm plans: `backend: spgemm` and the
    spgemm schema version are IN the digest."""
    import hashlib

    from libgrape_lite_tpu.ft.fingerprint import stable_config_digest

    fp = stable_config_digest({
        "backend": "spgemm",
        "schema": _SPGEMM_SCHEMA_VERSION,
        "chunk": cfg.chunk,
        "thr": int(thr),
        "fnum": frag.fnum,
        "vp": frag.vp,
        "stream_dtypes": _SG_DTYPES,
    })
    h = hashlib.sha256()
    h.update(fp.encode())
    h.update(np.ascontiguousarray(v, np.int64).tobytes())
    h.update(np.ascontiguousarray(u, np.int64).tobytes())
    return h.hexdigest()[:24]


def _plan_cache_path(v, u, frag, thr, cfg):
    root = os.environ.get("GRAPE_PACK_PLAN_CACHE")
    if not root:
        return None
    return os.path.join(
        root, f"spgemmplan_{_spgemm_digest(v, u, frag, thr, cfg)}.npz"
    )


def _save_cached_plan(plan: SpGemmPlan, v, u, frag, thr, cfg):
    import json

    path = _plan_cache_path(v, u, frag, thr, cfg)
    if path is None or plan.host_streams is None:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    meta = {
        "n_pad": plan.n_pad, "fnum": plan.fnum, "vp": plan.vp,
        "n_ktiles": plan.n_ktiles, "words": plan.words,
        "items": plan.items, "p_pad": plan.p_pad,
        "rows_pad": plan.rows_pad, "mask_edges": plan.mask_edges,
        "orientation": plan.orientation,
        "degree_threshold": plan.degree_threshold,
        "chunk": plan.cfg.chunk,
        "ledger": plan.ledger, "stats": plan.stats,
    }
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(
            f,
            __meta=np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8
            ).copy(),
            **plan.host_streams,
        )
    os.replace(tmp, path)


def _load_cached_plan(v, u, frag, thr, cfg) -> SpGemmPlan | None:
    import json

    path = _plan_cache_path(v, u, frag, thr, cfg)
    if path is None or not os.path.exists(path):
        return None
    try:
        z = np.load(path)
        meta = json.loads(bytes(z["__meta"]))
        if meta["chunk"] != cfg.chunk:
            return None
        streams = {k: z[k] for k in z.files if k != "__meta"}
        return SpGemmPlan(
            n_pad=meta["n_pad"], fnum=meta["fnum"], vp=meta["vp"],
            n_ktiles=meta["n_ktiles"], words=meta["words"],
            items=meta["items"], p_pad=meta["p_pad"],
            rows_pad=meta["rows_pad"], mask_edges=meta["mask_edges"],
            orientation=meta["orientation"],
            degree_threshold=meta["degree_threshold"], cfg=cfg,
            host_streams=streams, ledger=meta["ledger"],
            stats=meta["stats"],
        )
    except Exception:
        return None  # corrupt/stale cache entries are rebuilt


# --------------------------------------------------------------------------
# backend selection + stats
# --------------------------------------------------------------------------


# resolve-path counters + the decision/decline record.  `declines` and
# `decisions` are bounded lists of structured records — every backend
# request that does NOT engage spgemm leaves a trace here, never a
# silent fallback.  Federated as "spgemm" (obs/federation.py): a dict
# subclass, so the mutation sites below are unchanged.
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats

SPGEMM_STATS = _FedStats("spgemm", {
    "planned": 0, "frag_cache_hits": 0, "disk_cache_hits": 0,
    "auto_spgemm": 0, "auto_intersect": 0,
    "declines": [], "decisions": [],
})
_STATS_CAP = 64


def spgemm_stats() -> dict:
    """Snapshot of the spgemm resolve/decision counters (copy)."""
    out = dict(SPGEMM_STATS)
    out["declines"] = list(SPGEMM_STATS["declines"])
    out["decisions"] = list(SPGEMM_STATS["decisions"])
    return out


def _record(kind: str, rec: dict):
    lst = SPGEMM_STATS[kind]
    if len(lst) >= _STATS_CAP:
        del lst[0]
    lst.append(rec)


def record_decline(app: str, reason: str, requested: str):
    """A backend request that falls back to intersect — RECORDED, and
    vlogged, never silent."""
    from libgrape_lite_tpu.utils import logging as glog

    _record("declines", {
        "app": app, "reason": reason, "requested": requested,
    })
    glog.log_info(
        "spgemm backend declined for %s (requested %s): %s",
        app, requested, reason,
    )


def lcc_backend_mode() -> str:
    mode = os.environ.get("GRAPE_LCC_BACKEND", "intersect")
    if mode not in ("intersect", "spgemm", "auto"):
        raise ValueError(
            f"GRAPE_LCC_BACKEND={mode!r}: expected 'intersect', "
            "'spgemm' or 'auto'"
        )
    return mode


def intersect_ledger(frag, chunk: int) -> dict:
    """Modeled popcount-intersect cost for models/lcc.py's kernel on
    this fragment's geometry: per ring step (fnum of them) the kernel
    sweeps every padded oe chunk (apex + middle pass) and ie chunk
    (far-end pass), each slot paying 3 word-ops per bitmap word (AND,
    popcount, reduce) over n_pad/32 words.  Bytes: the two packed
    bitmap families resident per shard plus the rotating block
    traffic."""
    ep_oe = len(frag.host_oe[0].edge_src)
    ep_ie = len((frag.host_ie or frag.host_oe)[0].edge_src)
    return intersect_ledger_geom(
        frag.fnum * frag.vp, ep_oe, ep_ie, frag.fnum, frag.vp, chunk
    )


def intersect_ledger_geom(n_pad: int, ep_oe: int, ep_ie: int,
                          fnum: int, vp: int, chunk: int) -> dict:
    """`intersect_ledger` on raw geometry (no fragment) — the bench's
    modeled A/B at full bench geometry prices this way."""
    words = (n_pad + 31) // 32
    c_oe = max(1, min(chunk, ep_oe))
    c_ie = max(1, min(chunk, ep_ie))
    slots = (max(1, -(-ep_oe // c_oe)) * c_oe
             + max(1, -(-ep_ie // c_ie)) * c_ie)
    word_ops = fnum * fnum * slots * 3 * words
    hbm = fnum * (2 * vp * words * 4)
    return {
        "word_ops": word_ops,
        "word_ops_per_edge": round(word_ops / max(1, fnum * ep_oe), 1),
        "hbm_bytes": hbm,
        "words": words,
        "chunk": chunk,
    }


def price_backends(spgemm_ledger: dict, intersect: dict,
                   profile=None) -> dict:
    """Modeled seconds for both backends at the shared profile rates
    (the pack cost model's conventions: VPU lanes + MXU elems + gather
    rows summed, HBM concurrent).  `profile` defaults to the active
    RateProfile — a fitted profile re-prices the auto choice."""
    from libgrape_lite_tpu.ops.calibration import active_profile

    p = profile or active_profile()
    t = spgemm_ledger["totals"]
    sp = max(
        t["vpu_ops"] / p.vpu_lanes_per_cycle / p.clock_hz
        + t["mxu_ops"] * p.mxu_cyc_per_elem / p.clock_hz
        + t["gather_rows"] / p.gather_rows_per_cycle / p.clock_hz,
        t["hbm_bytes"] / p.hbm_bps,
    )
    it = max(
        intersect["word_ops"] / p.vpu_lanes_per_cycle / p.clock_hz,
        intersect["hbm_bytes"] / p.hbm_bps,
    )
    return {
        "t_spgemm_s": sp, "t_intersect_s": it,
        "spgemm_wins": bool(sp < it),
        "profile": p.label(),
    }


def resolve_lcc_backend(app_name: str, frag,
                        degree_threshold: int = 0,
                        chunk: int = 4096,
                        supported: bool = True,
                        unsupported_reason: str = "") -> str:
    """The GRAPE_LCC_BACKEND resolution an LCC-family app runs at
    init_state: returns "intersect" or "spgemm", recording every
    non-intersect request's outcome in SPGEMM_STATS.

    `supported=False` (lcc_beta's merge kernel, lcc_directed's
    direction-weighted counts) always yields intersect — with a
    RECORDED decline when the env asked for spgemm/auto."""
    mode = lcc_backend_mode()
    if mode == "intersect":
        return "intersect"
    if not supported:
        record_decline(app_name, unsupported_reason or
                       "app has no spgemm lowering", mode)
        return "intersect"
    if getattr(frag, "dyn_overlay", None) is not None:
        record_decline(
            app_name,
            "dyn overlay attached: the host-planned bitmap would go "
            "stale against staged deltas", mode,
        )
        return "intersect"
    if mode == "spgemm":
        _record("decisions", {
            "app": app_name, "mode": mode, "backend": "spgemm",
        })
        return "spgemm"
    # auto: price both from the ledgers.  The pricing plan is memoized
    # in the per-fragment cache (keyed like the engaged plan, with a
    # "price" tag) so serve-style Worker churn re-prices for free; an
    # already-engaged materialized plan is reused directly — its
    # ledger is the exact one the recount gate validates
    cfg = SpGemmConfig.from_env()
    per_frag = _frag_cache(frag)
    plan = per_frag.get(("spgemm", cfg, int(degree_threshold)))
    if plan is None:
        price_key = ("spgemm-price", cfg, int(degree_threshold))
        plan = per_frag.get(price_key)
        if plan is None:
            plan = plan_spgemm(frag, degree_threshold, cfg=cfg,
                               plan_only=True)
            per_frag[price_key] = plan
    prices = price_backends(plan.ledger, intersect_ledger(frag, chunk))
    backend = "spgemm" if prices["spgemm_wins"] else "intersect"
    SPGEMM_STATS["auto_spgemm" if prices["spgemm_wins"]
                 else "auto_intersect"] += 1
    rec = {
        "app": app_name, "mode": "auto", "backend": backend,
        "t_spgemm_s": round(prices["t_spgemm_s"], 6),
        "t_intersect_s": round(prices["t_intersect_s"], 6),
        "items": plan.items, "mask_edges": plan.mask_edges,
        "profile": prices["profile"],
    }
    _record("decisions", rec)
    if backend == "intersect":
        record_decline(
            app_name,
            f"auto: modeled intersect {prices['t_intersect_s']:.2e}s "
            f"beats spgemm {prices['t_spgemm_s']:.2e}s", mode,
        )
    return backend
