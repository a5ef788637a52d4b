"""Vertex-cut (2-D edge partition) fragments.

Re-design of `grape/fragment/immutable_vertexcut_fragment.h:40-349` +
`VCPartitioner` (`grape/vertex_map/partitioner.h:269-330`): fnum must be
k^2; edge (src, dst) lands on fragment (src_chunk * k + dst_chunk);
vertex masters are 1-D oid-range chunks (the reference specialises to
uint64 oids, i.e. the oid value space is the vertex space — same here).

TPU layout: fragment (i, j) = device (i, j) of the k x k mesh
(`CommSpec.mesh2d`, fid = i*k + j) holds the edges whose endpoints are
*global padded ids* gpid = chunk * Vc + offset (Vc = padded chunk
width), in one of two device forms, stacked [fnum, ...]:

  * `pull` (`VCPullFragment`; raw storage's default, `--vc` PageRank's):
    the tile's edges twice in ONE padded CSR of 2 Vc rows with offsets,
    by destination (rows 0..Vc-1, neighbour = source offset, read from
    the device's row copy) and by source (rows Vc..2 Vc-1, neighbour =
    Vc + destination offset, read from its column copy), so that a
    round's two directions are one `pull_gather` over the table
    `[row copy; column copy]` and one scan fold (ops/segment.py), like
    an edge-cut shard's `ie`.  No edge data is placed: the reference's
    `--vc` fragment has none.
  * `coo` (`VCDeviceFragment`; symmetrised storage's default, the
    `*_vc` min-fold apps'): four `[Ep]` COO arrays with no order, which
    those apps scatter-reduce.

The host keeps the COO tiles either way (`_host_tiles`: the per-tile
CSR views, `tile_stats`, the ft fingerprint, `restore_device`).
Master state is sharded over the mesh's two axes by the apps
(models/pagerank_vc.py, models/vc2d.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import numpy as np

from libgrape_lite_tpu.fragment.edgecut import DeviceCSR
from libgrape_lite_tpu.obs.federation import FederatedStats
from libgrape_lite_tpu.parallel.comm_spec import CommSpec, put_global


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# 2-D tile fill / pad-waste observability (OpenMetrics via the obs
# federation, namespace "vc_tiles"): the vertex-cut analogue of the
# rebalancer's before/after edge-skew record — every tile_stats() scan
# publishes the latest fill profile so 2-D skew is scrapeable
VC_TILE_STATS = FederatedStats("vc_tiles", {
    "scans": 0,
    "tiles": 0,
    "edge_slots": 0,        # padded COO slots per tile (Ep)
    "edges": 0,             # real edges across all tiles
    "pad_slots": 0,         # fnum*Ep - edges: allocated-but-dead slots
    "pad_waste_frac": 0.0,  # pad_slots / (fnum*Ep)
    "min_fill_frac": 0.0,
    "mean_fill_frac": 0.0,
    "max_fill_frac": 0.0,
    "tile_skew": 0.0,
})


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "w", "mask"],
    meta_fields=["fnum", "k", "vc", "chunk", "total_vnum"],
)
@dataclass
class VCDeviceFragment:
    """Stacked [fnum, Ep] COO blocks (or a per-shard view inside
    shard_map — the reference's GetEdgesOfBucket spans)."""

    src: jax.Array  # [fnum, Ep] int32 gpid
    dst: jax.Array  # [fnum, Ep] int32 gpid
    w: jax.Array | None  # [fnum, Ep] or None
    mask: jax.Array  # [fnum, Ep] bool
    fnum: int
    k: int
    vc: int  # padded chunk width
    chunk: int  # real chunk width (oid space / k)
    # real vertex count (guard/ monitor's active-range ceiling)
    total_vnum: int = 0

    @property
    def n_pad(self) -> int:
        return self.k * self.vc

    def local(self) -> "VCDeviceFragment":
        return VCDeviceFragment(
            src=self.src[0], dst=self.dst[0],
            w=None if self.w is None else self.w[0],
            mask=self.mask[0],
            fnum=self.fnum, k=self.k, vc=self.vc, chunk=self.chunk,
            total_vnum=self.total_vnum,
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["pull"],
    meta_fields=["fnum", "k", "vc", "chunk", "total_vnum"],
)
@dataclass
class VCPullFragment:
    """Stacked [fnum, ...] tiles as the one pull reads them (or a
    per-shard view inside shard_map): `pull` is a padded CSR of 2 vc
    rows over the table `[row copy; column copy]`, the tile's edges by
    destination then by source (the module's docstring), pads behind
    the last row (row id 2 vc, masked)."""

    pull: DeviceCSR  # indptr [fnum, 2 vc + 1], the rest [fnum, E2]
    fnum: int
    k: int
    vc: int
    chunk: int
    total_vnum: int = 0

    @property
    def n_pad(self) -> int:
        return self.k * self.vc

    def local(self) -> "VCPullFragment":
        p = self.pull
        return VCPullFragment(
            pull=DeviceCSR(p.indptr[0], p.edge_src[0], p.edge_nbr[0],
                           None, p.edge_mask[0]),
            fnum=self.fnum, k=self.k, vc=self.vc, chunk=self.chunk,
            total_vnum=self.total_vnum,
        )


def default_layout(symmetrized: bool) -> str:
    """The device form a load takes where none is asked for: the
    pull's CSRs for raw storage (`--vc` PageRank's), the COO tiles for
    symmetrised (the min-fold apps')."""
    return "coo" if symmetrized else "pull"


def pull_csr(src_g: np.ndarray, dst_g: np.ndarray, vc: int, width: int):
    """One tile's pull CSR from its real edges' gpids: (indptr
    [2 vc + 1], edge_src, edge_nbr, edge_mask [width])."""
    from libgrape_lite_tpu.graph.csr import build_csr

    s, d = src_g % vc, dst_g % vc
    c = build_csr(
        np.concatenate([d, vc + s]), np.concatenate([s, vc + d]),
        None, 2 * vc, width,
    )
    return c.indptr, c.edge_src, c.edge_nbr, c.edge_mask


class ImmutableVertexcutFragment:
    """Host descriptor for the full 2-D partitioned graph."""

    def __init__(self, comm_spec, dev, oids, k, vc, chunk, total_enum,
                 directed: bool = True, weighted: bool = False,
                 symmetrized: bool = False):
        self.comm_spec = comm_spec
        self.dev = dev
        self.k = k
        self.vc = vc
        self.chunk = chunk
        self.fnum = k * k
        self.vp = vc  # chunk width, for Worker result shapes
        self.total_enum = total_enum
        self._oids = np.asarray(oids)
        self._chunk_oids = [
            np.sort(self._oids[(self._oids // chunk) == c]) for c in range(k)
        ]
        self.total_vnum = len(self._oids)
        # traversal semantics of the stored tile blocks: `directed`
        # mirrors the loader flag; `symmetrized` says the blocks hold
        # BOTH (u,v) and (v,u) per input edge (min-fold pulls use one
        # dst-side pull per round — the 1-D undirected-CSR convention);
        # PageRankVC-style gather-scatter apps keep raw storage and
        # accumulate both directions in-app instead
        self.directed = directed
        self.weighted = weighted
        self.symmetrized = symmetrized
        self.layout = "coo"  # the device form: "coo" | "pull"
        self._host_pull = None  # the pull form's stacked host arrays
        self._host_csrs = {}
        self._vertex_mask = None  # made on first use, like the views
        self._tile_profile = None  # `tile_stats`' scan of the tiles

    def oid_to_gpid(self, oids: np.ndarray) -> np.ndarray:
        oids = np.asarray(oids)
        return (oids // self.chunk) * self.vc + (oids % self.chunk)

    def gpid_to_oid(self, gpids: np.ndarray) -> np.ndarray:
        """Inverse of `oid_to_gpid` — gpid order is oid order (chunks
        are contiguous oid ranges and offset < chunk <= vc), which is
        what makes the 2-D WCC representative the min-OID member."""
        gpids = np.asarray(gpids)
        return (gpids // self.vc) * self.chunk + (gpids % self.vc)

    def vertex_mask(self) -> np.ndarray:
        """[k * vc] bool: which gpid slots are real vertices.  Made
        once: every query's `init_state` asks, and the fragment is
        immutable (callers place it, none writes to it)."""
        if self._vertex_mask is None:
            m = np.zeros(self.k * self.vc, dtype=bool)
            m[self.oid_to_gpid(self._oids)] = True
            self._vertex_mask = m
        return self._vertex_mask

    # ---- per-tile CSR views -------------------------------------------
    #
    # The ft fingerprint reads fragments through the host_ie/host_oe
    # CSR-list protocol; the vertex-cut tiles expose the same shape:
    #   host_ie[f]: rows = dst offsets in chunk-j space, cols = src
    #               offsets in chunk-i space (the dst-side pull whose
    #               gather table is the [vc] column-broadcast chunk);
    #   host_oe[f]: the transposed orientation (src-side pull — the
    #               directed-WCC second direction).
    # Both index LOCAL [vc] tables.

    def _tile_csrs(self, orientation: str):
        if orientation in self._host_csrs:
            return self._host_csrs[orientation]
        from libgrape_lite_tpu.graph.csr import build_csr

        s_arr, d_arr, w_arr, m_arr = self._host_tiles
        rows_all, cols_all = (
            (d_arr, s_arr) if orientation == "ie" else (s_arr, d_arr)
        )
        csrs = []
        ep = s_arr.shape[1]
        for f in range(self.fnum):
            m = m_arr[f]
            csrs.append(build_csr(
                (rows_all[f][m] % self.vc).astype(np.int64),
                (cols_all[f][m] % self.vc).astype(np.int64),
                None if w_arr is None else w_arr[f][m],
                self.vc, ep,
            ))
        self._host_csrs[orientation] = csrs
        return csrs

    @property
    def host_ie(self):
        return self._tile_csrs("ie")

    @property
    def host_oe(self):
        return self._tile_csrs("oe")

    def tile_stats(self) -> dict:
        """Per-tile real edge counts + the skew summary the planner,
        the bench `partition2d` lane and trace_report all read —
        the 2-D analogue of edgecut's partition-skew warning.  HOST
        data only (`_host_tiles`): under jax.distributed the device
        tiles span non-addressable devices and cannot be fetched (the
        PR 18 edgecut.inner_vertices_num bug class).  Also publishes
        the fill / pad-waste profile into the "vc_tiles" federation
        namespace so 2-D skew is scrapeable like the rebalancer's
        edge-skew record.  The scan itself is made once
        (`_scan_tiles`); a call publishes and counts."""
        if self._tile_profile is None:
            self._tile_profile = self._scan_tiles()
        published, stats = self._tile_profile
        VC_TILE_STATS["scans"] += 1
        VC_TILE_STATS.update(published)
        return stats

    def _scan_tiles(self):
        """`tile_stats`' one pass over the tiles' masks: (what the
        federation publishes, what the call returns).  The tiles never
        change, and every query's `init_state` asks."""
        _, _, _, m_arr = self._host_tiles
        ep = int(m_arr.shape[1])
        counts = m_arr.sum(axis=1).astype(int)
        mean = max(float(counts.mean()), 1.0)
        fills = counts / max(ep, 1)
        edges = int(counts.sum())
        pad = self.fnum * ep - edges
        skew = round(float(counts.max()) / mean, 3)
        waste = round(pad / max(self.fnum * ep, 1), 4)
        published = {
            "tiles": self.fnum,
            "edge_slots": ep,
            "edges": edges,
            "pad_slots": pad,
            "pad_waste_frac": waste,
            "min_fill_frac": round(float(fills.min()), 4),
            "mean_fill_frac": round(float(fills.mean()), 4),
            "max_fill_frac": round(float(fills.max()), 4),
            "tile_skew": skew,
        }
        return published, {
            "k": self.k,
            "per_tile": [
                {"tile": f, "row": f // self.k, "col": f % self.k,
                 "edges": int(c), "fill_frac": round(float(fr), 4)}
                for f, (c, fr) in enumerate(zip(counts, fills))
            ],
            "max_tile_edges": int(counts.max()),
            "mean_tile_edges": round(mean, 1),
            "tile_skew": skew,
            "edge_slots": ep,
            "pad_slots": pad,
            "pad_waste_frac": waste,
        }

    # masters: the diagonal fragment (c, c) owns chunk c
    # (reference partitioner.h:269-330 master placement).  Both reads
    # are HOST-side (`_chunk_oids` from the build-time oid array) by
    # audit: the device tiles span non-addressable devices under
    # jax.distributed and must never back these (the bug class PR 18
    # fixed in edgecut.inner_vertices_num).
    def inner_vertices_num(self, fid: int) -> int:
        i, j = divmod(fid, self.k)
        return len(self._chunk_oids[i]) if i == j else 0

    def inner_oids(self, fid: int) -> np.ndarray:
        i, j = divmod(fid, self.k)
        return self._chunk_oids[i] if i == j else np.zeros(0, np.int64)

    # ---- device residency (fleet/ eviction, docs/FLEET.md) ----

    def _place_tiles(self):
        """Deterministic device placement of the host tile blocks —
        shared by build and restore_device, so a restored fragment's
        content is byte-identical to the evicted one: set-up phase
        `load.place`, which ends when the arrays are on the devices
        (its record's `bytes_in_use` is what the graph holds).
        put_global (not bare device_put): under jax.distributed the
        sharding spans non-addressable devices and device_put would
        throw (the same multi-process contract every 1-D placement
        site honors).  Tile f goes to device (f // k, f % k) of
        `CommSpec.mesh2d`, the mesh the apps' rounds run on."""
        from libgrape_lite_tpu import obs

        shard = self.comm_spec.sharded2d()
        meta = dict(fnum=self.fnum, k=self.k, vc=self.vc,
                    chunk=self.chunk, total_vnum=self.total_vnum)

        def put(x):
            return put_global(x, shard)

        with obs.tracer().span("load.place", fnum=self.fnum):
            if self.layout == "pull":
                indptr, rows, nbr, mask = self._host_pull
                dev = VCPullFragment(
                    pull=DeviceCSR(put(indptr), put(rows), put(nbr),
                                   None, put(mask)),
                    **meta,
                )
            else:
                s_arr, d_arr, w_arr, m_arr = self._host_tiles
                dev = VCDeviceFragment(
                    src=put(s_arr), dst=put(d_arr), w=put(w_arr),
                    mask=put(m_arr), **meta,
                )
            return jax.block_until_ready(dev)

    def release_device(self) -> bool:
        """Evict: delete the stacked COO tile buffers and drop `dev`.
        Every host artifact survives — `_host_tiles`, the cached
        per-tile CSR views, the plan caches weak-keyed on THIS
        object — so `restore_device` re-places byte-identical content
        with zero re-planning (the 1-D fleet contract).  Returns
        False when already released."""
        if self.dev is None:
            return False
        seen = set()
        for leaf in jax.tree_util.tree_leaves(self.dev):
            if leaf is None or id(leaf) in seen:
                continue
            seen.add(id(leaf))
            delete = getattr(leaf, "delete", None)
            if callable(delete):
                try:
                    delete()
                except Exception:
                    pass  # committed/donated buffers: GC frees them
        self.dev = None
        return True

    def restore_device(self) -> bool:
        """Re-admission: re-place the device tiles from `_host_tiles`
        (deterministic, byte-identical to the evicted arrays).
        Returns False when already resident."""
        if self.dev is not None:
            return False
        self.dev = self._place_tiles()
        return True

    @staticmethod
    def cut_tiles(
        fnum: int,
        oids: np.ndarray,
        src_oid: np.ndarray,
        dst_oid: np.ndarray,
        weights: np.ndarray | None = None,
        edata_dtype=np.float64,
        symmetrize: bool = False,
    ):
        """The cut itself (`VCPartitioner`): `(k, vc, chunk, tiles)`,
        `tiles` the padded `[fnum, Ep]` COO blocks `(src, dst, w, mask)`
        in gpids, a tile's edges in the order the list has them.  One
        stable sort of the edges' tile ids, then a slice a tile."""
        k = int(round(np.sqrt(fnum)))
        if k * k != fnum:
            raise ValueError(f"vertex-cut needs fnum = k^2, got {fnum}")
        space = int(np.asarray(oids).max()) + 1 if len(oids) else 1
        chunk = (space + k - 1) // k
        vc = _round_up(chunk, 128)

        src = np.asarray(src_oid)
        dst = np.asarray(dst_oid)
        if symmetrize:
            src, dst = (
                np.concatenate([src, dst]), np.concatenate([dst, src])
            )
            if weights is not None:
                weights = np.concatenate([weights, weights])
        bad = (src < 0) | (src >= space) | (dst < 0) | (dst >= space)
        if bad.any():
            ex = np.stack([src[bad], dst[bad]], 1)[:3]
            raise ValueError(
                f"edge endpoint(s) outside the vertex oid space "
                f"[0, {space}), e.g. {ex.tolist()} — the vertex-cut "
                "fragment requires dense oid ids covering all endpoints"
            )
        # space <= k*chunk, so // chunk is already < k
        sc = src // chunk
        dc = dst // chunk
        fid = sc * k + dc
        counts = np.bincount(fid, minlength=fnum)
        ep = _round_up(max(int(counts.max()), 1), 128)
        # narrow ids sort by radix: one O(E) pass for fnum <= 65,536
        order = np.argsort(
            fid.astype(np.uint16 if fnum <= 1 << 16 else np.int64),
            kind="stable",
        )
        sg = (sc * vc + src % chunk).astype(np.int32)[order]
        dg = (dc * vc + dst % chunk).astype(np.int32)[order]
        ws = None if weights is None else np.asarray(weights)[order]

        s_arr = np.zeros((fnum, ep), dtype=np.int32)
        d_arr = np.zeros((fnum, ep), dtype=np.int32)
        w_arr = None if ws is None else np.zeros((fnum, ep), edata_dtype)
        m_arr = np.zeros((fnum, ep), dtype=bool)
        lo = 0
        for f, n in enumerate(counts.tolist()):
            s_arr[f, :n] = sg[lo:lo + n]
            d_arr[f, :n] = dg[lo:lo + n]
            if w_arr is not None:
                w_arr[f, :n] = ws[lo:lo + n]
            m_arr[f, :n] = True
            lo += n
        return k, vc, chunk, (s_arr, d_arr, w_arr, m_arr)

    @classmethod
    def from_tiles(
        cls, comm_spec: CommSpec, oids, k: int, vc: int, chunk: int,
        tiles, total_enum: int, directed: bool = True,
        symmetrized: bool = False, layout: str | None = None,
        host_pull=None,
    ) -> "ImmutableVertexcutFragment":
        """The fragment of cut tiles (`cut_tiles`', or a cache's), its
        device form built where it is the pull's and not handed over
        (`host_pull`), and placed.  `layout` None: `default_layout`."""
        layout = layout or default_layout(symmetrized)
        if layout not in ("coo", "pull"):
            raise ValueError(f"vertex-cut layout {layout!r}: coo | pull")
        s_arr, d_arr, w_arr, m_arr = tiles
        out = cls(comm_spec, None, oids, k, vc, chunk, total_enum,
                  directed=directed, weighted=w_arr is not None,
                  symmetrized=symmetrized)
        # host tile blocks stay resident: the per-tile CSR views
        # (host_ie/host_oe), tile_stats, the ft content fingerprint and
        # fleet re-admission (restore_device) all read them — the
        # edge-cut fragment keeps its host CSRs the same way
        out._host_tiles = tiles
        out.layout = layout
        if layout == "pull":
            if host_pull is None:
                counts = m_arr.sum(axis=1)
                width = _round_up(max(2 * int(counts.max()), 1), 128)
                per_tile = [
                    pull_csr(s_arr[f, :n], d_arr[f, :n], vc, width)
                    for f, n in enumerate(counts.tolist())
                ]
                host_pull = tuple(np.stack(a) for a in zip(*per_tile))
            out._host_pull = host_pull
        out.dev = out._place_tiles()
        return out

    @classmethod
    def build(
        cls,
        comm_spec: CommSpec,
        oids: np.ndarray,
        src_oid: np.ndarray,
        dst_oid: np.ndarray,
        weights: np.ndarray | None = None,
        edata_dtype=np.float64,
        directed: bool = True,
        symmetrize: bool = False,
        layout: str | None = None,
    ) -> "ImmutableVertexcutFragment":
        """`symmetrize=True` stores BOTH (u,v) -> tile (cu,cv) and
        (v,u) -> tile (cv,cu) per input edge, so one dst-side pull per
        round covers the undirected traversal (the 1-D loader's
        symmetrised-CSR convention; min folds stay byte-identical).
        The default keeps raw storage — the seed contract PageRankVC's
        both-direction gather-scatter accumulation depends on.
        `layout` is the device form (`from_tiles`)."""
        k, vc, chunk, tiles = cls.cut_tiles(
            comm_spec.fnum, oids, src_oid, dst_oid, weights,
            edata_dtype, symmetrize,
        )
        return cls.from_tiles(
            comm_spec, oids, k, vc, chunk, tiles, len(src_oid),
            directed=directed, symmetrized=symmetrize, layout=layout,
        )
