"""Graph loading pipeline.

Re-design of `grape/fragment/loader.h:42-80` + `ev_fragment_loader.h:49-229`
+ `basic_fragment_loader_base.h:244-441`: read .v/.e TSV, build the
vertex map (partitioner + idxer), shuffle edges to owner fragments and
construct padded device CSRs.  The reference's MPI ring shuffle becomes
host-side numpy grouping followed by per-device placement.

Also implements the content-hash fragment serialization cache
(`basic_fragment_loader_base.h:127-242`; flags `--serialize/--deserialize`,
`flags.cc:56-59`): prefix/<hex>/part_<fnum>/frag.npz with a `sig` file.
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field

import numpy as np

from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
from libgrape_lite_tpu.io.line_parser import read_edge_file, read_vertex_file
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.utils.types import LoadStrategy
from libgrape_lite_tpu.vertex_map.partitioner import make_partitioner
from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap


@dataclass
class LoadGraphSpec:
    """Loading options (reference `LoadGraphSpec`,
    `basic_fragment_loader_base.h:30-109`)."""

    directed: bool = False
    weighted: bool = True
    load_strategy: LoadStrategy = LoadStrategy.kBothOutIn
    partitioner_type: str = "map"  # hash | map | segment (flags.cc:46-48)
    idxer_type: str = "hashmap"  # sorted_array | hashmap | pthash | local
    rebalance: bool = False
    rebalance_vertex_factor: int = 0
    string_id: bool = False  # reference --string_id (load_tests.cc:45)
    serialize: bool = False
    deserialize: bool = False
    serialization_prefix: str = ""
    vid_dtype: type = np.int32
    edata_dtype: type = np.float32
    # keep the original oid edge list on the fragment — required for
    # rebuild-on-mutate and the dyn/ repack path (deserialize-path
    # loads cannot retain it: the cache stores only the built shards)
    retain_edge_list: bool = False
    # the reference's --vc (run_app_vc.h): `LoadGraph` hands the load
    # to `LoadVertexcutGraph`, a k x k vertex cut (fnum = k^2) as an
    # `ImmutableVertexcutFragment`
    vertex_cut: bool = False


def _cache_dir(efile: str, vfile: str, spec: LoadGraphSpec, fnum: int,
               cut: dict | None = None) -> str:
    """(directory, signature) of a load's serialization cache.  `cut`
    holds what a vertex-cut load's fragment depends on besides (its
    storage and device form) and sets it apart from the edge cut's;
    an edge-cut load's signature is what it always was."""
    sig = json.dumps(
        {
            **({} if cut is None else {"vertex_cut": cut}),
            "efile": os.path.abspath(efile),
            "vfile": os.path.abspath(vfile) if vfile else "",
            "esize": os.path.getsize(efile),
            "vsize": os.path.getsize(vfile) if vfile else 0,
            "directed": spec.directed,
            "weighted": spec.weighted,
            # undirected fragments alias oe == ie (one symmetrised CSR
            # serves every strategy), so apps with different
            # load_strategy traits share one cache entry — a PageRank
            # --serialize feeds an SSSP --deserialize
            "strategy": (
                "undirected-aliased" if not spec.directed
                else spec.load_strategy.value
            ),
            "partitioner": spec.partitioner_type,
            "idxer": spec.idxer_type,
            "rebalance": spec.rebalance,
            "string_id": spec.string_id,
            "rebalance_vertex_factor": spec.rebalance_vertex_factor,
            "type": ("ShardedEdgecutFragment" if cut is None
                     else "ImmutableVertexcutFragment"),
        },
        sort_keys=True,
    )
    h = hashlib.sha256(sig.encode()).hexdigest()[:16]
    return os.path.join(spec.serialization_prefix, h, f"part_{fnum}"), sig


VALIDATE_LOAD_ENV = "GRAPE_VALIDATE_LOAD"

#: degree-weighted chunk rebalancing gate (ROADMAP item 4): "1" folds
#: `rebalance=True` into the spec BEFORE the cache signature is
#: computed, so rebalanced and oid-range caches never alias.  At
#: fnum 1 the rebalancer's single block IS the oid range — results are
#: byte-identical (pinned in tests).
REBALANCE_ENV = "GRAPE_PARTITION_REBALANCE"


def _fold_rebalance_env(spec: LoadGraphSpec) -> LoadGraphSpec:
    if spec.rebalance:
        return spec
    if os.environ.get(REBALANCE_ENV, "") in ("", "0", "off"):
        return spec
    import dataclasses

    vf = int(os.environ.get(REBALANCE_ENV + "_VF", "0") or 0)
    return dataclasses.replace(
        spec, rebalance=True, rebalance_vertex_factor=vf
    )


def _shard_skew(partitioner, dst: np.ndarray, fnum: int) -> dict:
    """Per-shard in-edge counts under one partitioner: the padded-max
    bill every SPMD shard pays (the 1d term the partition ledger
    prices).  skew = max/mean — 1.0 is a perfectly balanced cut."""
    pids = partitioner.get_partition_id(dst)
    counts = np.bincount(pids[pids >= 0], minlength=fnum)
    mean = float(counts.mean()) if fnum else 0.0
    return {
        "max_shard_edges": int(counts.max()) if fnum else 0,
        "mean_shard_edges": round(mean, 1),
        "skew": round(float(counts.max()) / mean, 4) if mean else 1.0,
    }


def _validate_load(frag: ShardedEdgecutFragment) -> ShardedEdgecutFragment:
    """GRAPE_VALIDATE_LOAD=1 gate: structural validation of every host
    CSR right after a load/deserialize (graph/csr.py `CSR.validate`).
    A malformed or tampered input — especially a hand-assembled or
    bit-rotted serialization cache — fails loudly HERE instead of
    producing wrong results three queries later."""
    if os.environ.get(VALIDATE_LOAD_ENV, "") in ("", "0"):
        return frag
    n_pad = frag.fnum * frag.vp
    aliased = frag.host_ie is frag.host_oe
    sides = [("oe", frag.host_oe)] if aliased else [
        ("oe", frag.host_oe), ("ie", frag.host_ie)
    ]
    for side, csrs in sides:
        for f, c in enumerate(csrs):
            c.validate(name=f"{side}[{f}]", n_pad=n_pad)
    from libgrape_lite_tpu.utils import logging as glog

    glog.vlog(
        1,
        "load validation: %d CSR(s) structurally sound",
        len(sides) * frag.fnum,
    )
    return frag


def read_graph_files(efile: str, vfile: str | None, spec: LoadGraphSpec):
    """(src, dst, w | None, oids) of a load's files: the `read_edges`
    phase of both loaders (the native parser where it built)."""
    src, dst, w = read_edge_file(
        efile, weighted=spec.weighted, string_id=spec.string_id
    )
    if not spec.weighted:
        w = None
    if vfile:
        oids = read_vertex_file(vfile, string_id=spec.string_id)
    else:
        # efile-only loading (basic_efile_fragment_loader.h):
        # vertex universe = the set of edge endpoints.
        # np.unique yields them in sorted oid order (NOT the
        # reference's first-appearance order); lids therefore
        # differ, but all output is oid-keyed so results are
        # unaffected.
        oids = np.unique(np.concatenate([src, dst]))
    return src, dst, w, oids


def LoadGraph(
    efile: str,
    vfile: str | None,
    comm_spec: CommSpec,
    spec: LoadGraphSpec | None = None,
) -> ShardedEdgecutFragment:
    """Entry point, mirroring `LoadGraph<FRAG_T>` (`loader.h:42-53`).

    The load is a `load_graph` span with `read_edges` / `partition` /
    `build_fragment` / `deserialize` / `serialize` children, and under
    `build_fragment` or `deserialize` the placement `load.place`
    (`ShardedEdgecutFragment._device_put`).  All are set-up phases
    (obs/tracer.py `SETUP_PHASES`): kept in the `setup` ledger armed or
    not, and with obs/ armed on the same timeline as the query the
    load delays."""
    from libgrape_lite_tpu import obs

    if spec is not None and spec.vertex_cut:
        return LoadVertexcutGraph(efile, vfile, comm_spec, spec)
    spec = _fold_rebalance_env(spec or LoadGraphSpec())
    tr = obs.tracer()

    with tr.span("load_graph", efile=efile, fnum=comm_spec.fnum) as lsp:
        cache = None
        if (spec.serialize or spec.deserialize) and spec.serialization_prefix:
            cache, sig = _cache_dir(efile, vfile or "", spec, comm_spec.fnum)

        if spec.deserialize and cache and os.path.exists(
            os.path.join(cache, "sig")
        ):
            with tr.span("deserialize", cache=cache):
                frag = _deserialize_fragment(cache, comm_spec, spec)
            lsp.set(path="deserialize")
            return _validate_load(frag)

        with tr.span("read_edges"):
            src, dst, w, oids = read_graph_files(efile, vfile, spec)
        lsp.set(edges=int(len(src)), vertices=int(len(oids)))

        with tr.span("partition", kind=spec.partitioner_type):
            if spec.rebalance:
                from libgrape_lite_tpu.fragment.rebalancer import Rebalancer

                partitioner = Rebalancer(
                    spec.rebalance_vertex_factor
                ).partition(oids, src, dst, comm_spec.fnum)
                # record the skew the rebalancer fixed (in-edge counts
                # of the pull direction, both orientations when
                # undirected) vs the oid-range cut it replaced — only
                # computed when engaged, the default path pays nothing
                from libgrape_lite_tpu.fragment.partition import (
                    PARTITION_STATS,
                )

                d_all = (dst if spec.directed
                         else np.concatenate([dst, src]))
                before = _shard_skew(
                    make_partitioner(
                        spec.partitioner_type, comm_spec.fnum, oids
                    ), d_all, comm_spec.fnum,
                )
                after = _shard_skew(partitioner, d_all, comm_spec.fnum)
                PARTITION_STATS["rebalance"] = {
                    "fnum": comm_spec.fnum,
                    "vertex_factor": spec.rebalance_vertex_factor,
                    "before": before, "after": after,
                }
            else:
                partitioner = make_partitioner(
                    spec.partitioner_type, comm_spec.fnum, oids
                )
            vm = VertexMap.build(
                oids, partitioner, idxer_type=spec.idxer_type
            )

        with tr.span("build_fragment"):
            frag = ShardedEdgecutFragment.build(
                comm_spec, vm, src, dst, w,
                directed=spec.directed,
                load_strategy=spec.load_strategy,
                vid_dtype=spec.vid_dtype,
                edata_dtype=spec.edata_dtype,
                retain_edge_list=spec.retain_edge_list,
            )
            frag.load_spec = spec  # preserved across rebuild-on-mutate

        if spec.serialize and cache:
            with tr.span("serialize", cache=cache):
                _serialize_fragment(frag, cache, sig)
        return _validate_load(frag)


def LoadVertexcutGraph(
    efile: str,
    vfile: str | None,
    comm_spec: CommSpec,
    spec: LoadGraphSpec | None = None,
    *,
    symmetrize: bool = False,
    layout: str | None = None,
    edges=None,
):
    """The vertex-cut load, mirroring `LoadVertexcutGraph<FRAG_T>`
    (`loader.h:42-53`; `run_app --vc`): the files' edges cut into
    k x k tiles (`fnum = k^2`, `VCPartitioner`) as an
    `ImmutableVertexcutFragment`.  `LoadGraph` hands a spec with
    `vertex_cut` here.

    The same set-up phases as the edge-cut load: `load_graph` with
    `read_edges` / `partition` (the cut: one stable sort of the tile
    ids) / `build_fragment` (the tiles' device form) / `deserialize` /
    `serialize` children and, under `build_fragment` or `deserialize`,
    the placement `load.place`; the same serialization cache, its
    signature set apart by the cut.

    `symmetrize` stores both orientations of every edge and `layout`
    names the device form (fragment/vertexcut.py: raw storage's
    default is the pull's CSR, PageRankVC's; symmetrised storage's the
    COO tiles the `*_vc` min-fold apps read).  `edges` is
    `read_graph_files`' tuple where the caller has read the files
    already (runner.py's partition probe)."""
    from libgrape_lite_tpu import obs
    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
        default_layout,
    )

    spec = spec or LoadGraphSpec(vertex_cut=True)
    if spec.string_id:
        raise ValueError(
            "string ids are not supported with vertex-cut storage (the "
            "reference's VC fragment is specialized to uint64 oids, "
            "immutable_vertexcut_fragment.h)"
        )
    layout = layout or default_layout(symmetrize)
    fnum = comm_spec.fnum
    tr = obs.tracer()

    with tr.span("load_graph", efile=efile, fnum=fnum, cut="vertex") as lsp:
        cache = None
        if (spec.serialize or spec.deserialize) and spec.serialization_prefix:
            cache, sig = _cache_dir(
                efile, vfile or "", spec, fnum,
                cut={"symmetrize": symmetrize, "layout": layout},
            )

        if spec.deserialize and cache and os.path.exists(
            os.path.join(cache, "sig")
        ):
            with tr.span("deserialize", cache=cache):
                frag = _deserialize_vertexcut(cache, comm_spec, spec)
            lsp.set(path="deserialize")
        else:
            with tr.span("read_edges"):
                src, dst, w, oids = (
                    edges if edges is not None
                    else read_graph_files(efile, vfile, spec)
                )
                if not spec.weighted:
                    w = None
            lsp.set(edges=int(len(src)), vertices=int(len(oids)))

            with tr.span("partition", kind="vertex_cut"):
                k, vc, chunk, tiles = ImmutableVertexcutFragment.cut_tiles(
                    fnum, oids, src, dst, w, spec.edata_dtype, symmetrize
                )

            with tr.span("build_fragment"):
                frag = ImmutableVertexcutFragment.from_tiles(
                    comm_spec, oids, k, vc, chunk, tiles, len(src),
                    directed=spec.directed, symmetrized=symmetrize,
                    layout=layout,
                )

            if spec.serialize and cache:
                with tr.span("serialize", cache=cache):
                    _serialize_vertexcut(frag, cache, sig)
        frag.load_spec = spec
        # the tiles' fill profile, published with the load
        # (VC_TILE_STATS; the apps attach it to their query spans)
        frag.tile_stats()
        return frag


# ---- archive-backed cache format (utils/archive.py) ---------------------
#
# The reference serializes fragments through InArchive/OutArchive with
# delta-varint gid compression (`basic_fragment_loader_base.h:127-242`,
# `grape/utils/varint.h`); the TPU build does the same at the host
# boundary: CSR indptr / edge_src are non-decreasing -> delta-varint
# (3-5x smaller than raw int64), edge_nbr -> plain varint, masks ->
# packed bits, weights raw.  One `frag.garc` file per partition.

_GARC_MAGIC = 0x47415243  # "GARC"

# stream encodings (flag byte per array)
# _ENC_PICKLE is write-dead since format v3: a crafted cache file must
# not reach pickle.loads at deserialize time (arbitrary code execution);
# string oids use length-prefixed UTF-8 (_ENC_STR) instead.
# _ENC_FPLANE (v3): float streams as byte planes, each plane deflated
# only when it actually compresses — the sign/exponent plane shrinks
# ~4x while mantissa planes are incompressible noise that v2's
# whole-archive deflate burned seconds failing to compress.
# _ENC_VARINT_Z/_ENC_DELTA_Z (v3): the varint payload additionally
# deflated (level 1) when that wins ≥10% — LEB128 output has a skewed
# byte alphabet, so cheap entropy coding recovers most of what v2's
# whole-archive deflate got, per-stream and only where it pays.
(_ENC_RAW, _ENC_VARINT, _ENC_DELTA, _ENC_BITS, _ENC_PICKLE, _ENC_STR,
 _ENC_FPLANE, _ENC_VARINT_Z, _ENC_DELTA_Z) = range(9)

# deflate a float byte-plane only when a cheap level-1 pass wins ≥10%
_PLANE_MIN_GAIN = 0.9
# below this element count the codec machinery costs more than it saves
_FPLANE_MIN = 4096


def _put_array(ar, a: np.ndarray) -> None:
    """Append one array: flag byte, element count, payload, dtype tag."""
    a = np.asarray(a)
    if a.dtype == object:  # string oids: varint lengths + UTF-8 payload
        from libgrape_lite_tpu.utils.archive import varint_encode

        blobs = [str(s).encode("utf-8") for s in a.tolist()]
        lens = varint_encode(
            np.array([len(b) for b in blobs], dtype=np.uint64)
        )
        payload = b"".join(blobs)
        ar.add_scalar(_ENC_STR, "<b")
        ar.add_scalar(len(a))
        ar.add_scalar(len(lens))
        ar.add_bytes(lens)
        ar.add_scalar(len(payload))
        ar.add_bytes(payload)
        return
    from libgrape_lite_tpu.utils.archive import (
        delta_varint_encode, varint_encode,
    )

    if a.dtype == np.bool_:
        ar.add_scalar(_ENC_BITS, "<b")
        ar.add_scalar(len(a))
        ar.add_bytes(np.packbits(a).tobytes())
    elif np.issubdtype(a.dtype, np.integer) and (
        len(a) == 0 or (int(a.min()) >= 0 and int(a.max()) < (1 << 62))
    ):
        monotone = len(a) > 0 and bool((np.diff(a) >= 0).all())
        enc = (delta_varint_encode if monotone else varint_encode)(
            a.astype(np.uint64)
        )
        code = _ENC_DELTA if monotone else _ENC_VARINT
        # GRAPE_GARC_COMPACT=1 trades write time for bytes: deflating
        # the LEB128 payloads recovers v2's whole-archive ratio
        # (measured RMAT-18 weighted: 4.6 s / 45 MB vs the default
        # 2.7 s / 59 MB vs v2's 7.5 s / 46 MB).  "0"/"" disable it,
        # consistent with GRAPE_LCC_TIERS (ADVICE r5)
        compact = os.environ.get("GRAPE_GARC_COMPACT", "") not in ("", "0")
        if compact and len(enc) >= 1 << 12:
            import zlib

            z = zlib.compress(enc, 1)
            if len(z) < _PLANE_MIN_GAIN * len(enc):
                code = _ENC_DELTA_Z if monotone else _ENC_VARINT_Z
                enc = z
        ar.add_scalar(code, "<b")
        ar.add_scalar(len(a))
        ar.add_scalar(len(enc))
        ar.add_bytes(enc)
    elif np.issubdtype(a.dtype, np.floating) and len(a) >= _FPLANE_MIN:
        import zlib

        from libgrape_lite_tpu.io.native import byte_split

        planes = byte_split(a)
        ar.add_scalar(_ENC_FPLANE, "<b")
        ar.add_scalar(len(a))
        ar.add_scalar(planes.shape[0], "<b")
        for p in planes:
            raw = p.tobytes()
            # probe compressibility on a 1 MiB sample first: mantissa
            # planes are noise, and paying a full-plane deflate just to
            # discover that was 60% of the serialize phase (measured:
            # 40.8 s of 67.6 s at 115M f64 weights)
            sample = raw[: 1 << 20]
            z = None
            if len(zlib.compress(sample, 1)) < _PLANE_MIN_GAIN * len(sample):
                z = zlib.compress(raw, 1)
            if z is not None and len(z) < _PLANE_MIN_GAIN * len(raw):
                ar.add_scalar(1, "<b")
                ar.add_scalar(len(z))
                ar.add_bytes(z)
            else:
                ar.add_scalar(0, "<b")
                ar.add_scalar(len(raw))
                ar.add_bytes(raw)
    else:
        ar.add_scalar(_ENC_RAW, "<b")
        ar.add_scalar(len(a))
        ar.add_array(a)
    tag = a.dtype.str.encode()
    ar.add_scalar(len(tag), "<b")
    ar.add_bytes(tag)


def _bounded_decompress(buf: bytes, max_out: int) -> bytes:
    """zlib.decompress with an output-size cap: the stream lengths in a
    frag.garc are attacker-controlled, so an unbounded decompress would
    let a small crafted cache file balloon into a huge allocation
    before any length check runs (decompression bomb, ADVICE r5).  The
    expected output size is always known to the caller; producing more
    than that is by definition a corrupt stream."""
    import zlib

    d = zlib.decompressobj()
    try:
        # never pass 0 as max_length — zlib treats it as "no limit",
        # which would reopen the bomb for streams claiming n=0; a
        # 1-byte cap makes any output at all fail the check below
        out = d.decompress(buf, max(1, max_out))
        # input left over after the output cap was reached means the
        # stream wants to produce more than the caller's bound; probe
        # with a 1-byte cap (never ballooning) to confirm
        extra = d.decompress(d.unconsumed_tail, 1) if d.unconsumed_tail else b""
    except zlib.error as e:
        raise ValueError(f"corrupt deflate stream in frag.garc: {e}") from e
    if extra or len(out) > max_out:
        raise ValueError(
            "corrupt deflate stream in frag.garc: decompressed output "
            f"exceeds the expected {max_out} bytes"
        )
    return out


def _get_array(oa) -> np.ndarray:
    from libgrape_lite_tpu.utils.archive import (
        delta_varint_decode, varint_decode,
    )

    enc = oa.get_scalar("<b")
    if enc == _ENC_PICKLE:
        raise ValueError(
            "pickle-era garc stream refused (deserializing it would run "
            "arbitrary code from the cache file); delete the cache dir "
            "and re-serialize from source"
        )
    if enc == _ENC_STR:
        n = oa.get_scalar()
        nlens = oa.get_scalar()
        lens = varint_decode(bytes(oa.get_bytes(nlens)))
        npay = oa.get_scalar()
        payload = bytes(oa.get_bytes(npay))
        # fail loudly on corrupt/crafted streams (the hardening point
        # of this format): count and payload extent must match exactly
        if len(lens) != n or int(lens.sum()) != len(payload):
            raise ValueError("corrupt string stream in frag.garc")
        out = np.empty(n, dtype=object)
        pos = 0
        for i, ln in enumerate(lens.tolist()):
            out[i] = payload[pos:pos + ln].decode("utf-8")
            pos += ln
        return out
    n = oa.get_scalar()
    if enc == _ENC_FPLANE:
        from libgrape_lite_tpu.io.native import byte_join

        itemsize = oa.get_scalar("<b")
        planes = np.empty((itemsize, n), dtype=np.uint8)
        for p in range(itemsize):
            comp = oa.get_scalar("<b")
            nbytes = oa.get_scalar()
            raw = bytes(oa.get_bytes(nbytes))
            if comp:
                # a plane is exactly n bytes; cap the inflate there
                raw = _bounded_decompress(raw, n)
            if len(raw) != n:
                raise ValueError("corrupt float plane in frag.garc")
            planes[p] = np.frombuffer(raw, dtype=np.uint8)
        tl = oa.get_scalar("<b")
        dt = np.dtype(bytes(oa.get_bytes(tl)).decode())
        if dt.itemsize != itemsize or dt.kind != "f":
            raise ValueError("corrupt float dtype tag in frag.garc")
        return byte_join(planes, dt)
    if enc == _ENC_BITS:
        vals = np.unpackbits(
            np.frombuffer(oa.get_bytes((n + 7) // 8), np.uint8)
        )[:n].astype(bool)
    elif enc in (_ENC_VARINT, _ENC_DELTA, _ENC_VARINT_Z, _ENC_DELTA_Z):
        nbytes = oa.get_scalar()
        buf = bytes(oa.get_bytes(nbytes))
        if enc in (_ENC_VARINT_Z, _ENC_DELTA_Z):
            # LEB128 uses at most 10 bytes per uint64, so n elements
            # bound the inflated payload at 10*n
            buf = _bounded_decompress(buf, 10 * n)
        vals = (
            delta_varint_decode(buf) if enc in (_ENC_DELTA, _ENC_DELTA_Z)
            else varint_decode(buf)
        )
    else:
        vals = oa.get_array(np.uint8)
    tl = oa.get_scalar("<b")
    dt = np.dtype(bytes(oa.get_bytes(tl)).decode())
    if enc == _ENC_RAW:
        return vals.view(dt).copy()
    return vals.astype(dt)


def _serialize_fragment(frag: ShardedEdgecutFragment, cache: str, sig: str):
    from libgrape_lite_tpu.utils.archive import InArchive

    os.makedirs(cache, exist_ok=True)
    vm = frag.vertex_map
    aliased = frag.host_ie is frag.host_oe
    ar = InArchive()
    ar.add_scalar(_GARC_MAGIC)
    ar.add_scalar(3)  # format version (v3: string oids are UTF-8, not pickle)
    for v in (
        frag.fnum, frag.vp, int(frag.directed), int(frag.weighted),
        int(aliased), frag.dev.total_vnum, frag.dev.total_enum,
    ):
        ar.add_scalar(int(v))
    sides = [("oe", frag.host_oe)] if aliased else [
        ("oe", frag.host_oe), ("ie", frag.host_ie)
    ]
    for f in range(frag.fnum):
        _put_array(ar, vm.inner_oids(f))
        for side, csrs in sides:
            c = csrs[f]
            _put_array(ar, c.indptr)
            _put_array(ar, c.edge_src)
            _put_array(ar, c.edge_nbr)
            _put_array(ar, c.edge_mask)
            ar.add_scalar(c.num_edges)
            ar.add_scalar(0 if c.edge_w is None else 1, "<b")
            if c.edge_w is not None:
                _put_array(ar, c.edge_w)
    # v3 container is raw: compression is per-stream now (varint for
    # ints, plane-split deflate for floats) — v2's whole-archive
    # deflate spent most of its time failing to compress float
    # mantissa noise (measured 7.9 s for a 10% saving on 80 MB of
    # weights; the plane codec gets more in < 1/3 the time)
    with open(os.path.join(cache, "frag.garc"), "wb") as fh:
        fh.write(ar.get_buffer())
    with open(os.path.join(cache, "sig"), "w") as f:
        f.write(sig)


def _read_cache_file(path: str) -> bytes:
    """Read one cache shard with the shared transient-IO retry policy
    (ft/retry.py): serialization prefixes live on shared/network
    filesystems where a stale-handle EIO is worth one more try before
    falling back to a full rebuild from source text."""
    from libgrape_lite_tpu.ft.retry import (
        CACHE_READ_POLICY, is_transient_io_error, with_retries,
    )

    def _read():
        with open(path, "rb") as fh:
            return fh.read()

    return with_retries(
        _read,
        policy=CACHE_READ_POLICY,
        retryable=is_transient_io_error,
        describe=f"garc cache read {path}",
    )


def _read_garc(cache: str):
    """Parse frag.garc -> (meta dict, per-fragment streams)."""
    import zlib

    from libgrape_lite_tpu.utils.archive import OutArchive

    blob = _read_cache_file(os.path.join(cache, "frag.garc"))
    # v3 containers start with the raw GARC magic; v2 wrapped the whole
    # archive in one deflate stream (first byte 0x78)
    if not blob.startswith((_GARC_MAGIC).to_bytes(8, "little")):
        blob = zlib.decompress(blob)
    oa = OutArchive(blob)
    if oa.get_scalar() != _GARC_MAGIC:
        raise ValueError("bad garc magic")
    version = oa.get_scalar()
    # v2 accepted for non-string-oid caches; its pickle streams (string
    # oids only) are refused stream-by-stream in _get_array
    if version not in (2, 3):
        raise ValueError(f"unsupported garc version {version}")
    (fnum, vp, directed, weighted, aliased, total_vnum,
     total_enum) = (oa.get_scalar() for _ in range(7))
    meta = dict(
        fnum=fnum, vp=vp, directed=bool(directed),
        weighted=bool(weighted), aliased=bool(aliased),
        total_vnum=total_vnum, total_enum=total_enum,
    )
    sides = ["oe"] if aliased else ["oe", "ie"]
    frags = []
    for _f in range(fnum):
        entry = {"oids": _get_array(oa)}
        for side in sides:
            indptr = _get_array(oa)
            src = _get_array(oa)
            nbr = _get_array(oa)
            mask = _get_array(oa)
            ne = oa.get_scalar()
            has_w = oa.get_scalar("<b")
            w = _get_array(oa) if has_w else None
            entry[side] = (indptr, src, nbr, mask, ne, w)
        frags.append(entry)
    if not oa.empty():  # not an assert: must survive `python -O`
        raise ValueError("trailing bytes in frag.garc")
    return meta, frags


def _rebuild_vertex_map(all_oids, fnum: int, vp: int, spec) -> VertexMap:
    """Rebuild the exact fid assignment from per-fragment oid lists
    (oids_f belongs to fragment f) — shared by both cache formats."""
    from libgrape_lite_tpu.utils.id_parser import IdParser
    from libgrape_lite_tpu.vertex_map.idxer import make_idxer
    from libgrape_lite_tpu.vertex_map.partitioner import ExplicitPartitioner

    idxers = [make_idxer(spec.idxer_type, o) for o in all_oids]
    id_parser = IdParser(fnum, vp)
    flat_oids = np.concatenate(all_oids) if all_oids else np.zeros(0, np.int64)
    flat_fids = np.concatenate(
        [np.full(len(o), f, dtype=np.int64) for f, o in enumerate(all_oids)]
    ) if all_oids else np.zeros(0, np.int64)
    part = ExplicitPartitioner(flat_oids, flat_fids)
    part.fnum = fnum
    return VertexMap(part, idxers, id_parser)


def _deserialize_fragment(
    cache: str, comm_spec: CommSpec, spec: LoadGraphSpec
) -> ShardedEdgecutFragment:
    from libgrape_lite_tpu.graph.csr import CSR

    if os.path.exists(os.path.join(cache, "frag.garc")):
        meta, frags = _read_garc(cache)
        fnum = meta["fnum"]
        if fnum != comm_spec.fnum:
            raise ValueError(
                f"serialized fnum={fnum} != requested {comm_spec.fnum}"
            )
        # the content hash normally guarantees these, but a moved or
        # hand-assembled cache must fail HERE, not as a tracer error
        # deep inside the first query
        if spec.weighted and not meta["weighted"]:
            raise ValueError(
                "serialized fragment has no edge weights but the app "
                "requires them (spec.weighted=True); re-serialize from "
                "a weighted load"
            )
        if bool(meta["directed"]) != bool(spec.directed):
            raise ValueError(
                f"serialized directed={meta['directed']} != requested "
                f"{spec.directed}"
            )
        vp = meta["vp"]
        directed, weighted = meta["directed"], meta["weighted"]
        vm = _rebuild_vertex_map(
            [e["oids"] for e in frags], fnum, vp, spec
        )

        def csr_from(e, side):
            indptr, src, nbr, mask, ne, w = e[side]
            return CSR(
                indptr=indptr, edge_src=src, edge_nbr=nbr, edge_w=w,
                edge_mask=mask, num_rows=vp, num_edges=ne,
            )

        host_oe = [csr_from(e, "oe") for e in frags]
        host_ie = (
            host_oe if meta["aliased"]
            else [csr_from(e, "ie") for e in frags]
        )
        dev = ShardedEdgecutFragment._device_put(
            comm_spec, vm, host_oe, host_ie, vp, directed,
            meta["total_vnum"], meta["total_enum"],
        )
        return ShardedEdgecutFragment(
            comm_spec, vm, dev, host_oe, host_ie, directed, weighted
        )

    # legacy npz caches written before the garc format.  Pickle is only
    # required for object (string-oid) arrays; for the common int-oid
    # case refuse pickled payloads outright so a crafted cache file
    # can't execute code.  string_id=True legacy caches therefore
    # require a trusted serialization_prefix — re-serialize to get the
    # pickle-free garc format.
    # retry only the open (where stale network-FS handles bite); the
    # file object keeps np.load's lazy per-member reads — buffering the
    # whole multi-GB archive would double peak RSS at RMAT-24 scale
    from libgrape_lite_tpu.ft.retry import (
        CACHE_READ_POLICY, is_transient_io_error, with_retries,
    )

    npz_path = os.path.join(cache, "frag.npz")
    fh = with_retries(
        lambda: open(npz_path, "rb"),
        policy=CACHE_READ_POLICY,
        retryable=is_transient_io_error,
        describe=f"npz cache open {npz_path}",
    )
    z = np.load(fh, allow_pickle=bool(spec.string_id))
    fnum = int(z["fnum"])
    if fnum != comm_spec.fnum:
        raise ValueError(
            f"serialized fnum={fnum} != requested {comm_spec.fnum}"
        )
    vp = int(z["vp"])
    directed = bool(z["directed"])
    weighted = bool(z["weighted"])
    # same moved-cache guards as the garc branch
    if spec.weighted and not weighted:
        raise ValueError(
            "serialized fragment has no edge weights but the app "
            "requires them (spec.weighted=True); re-serialize from a "
            "weighted load"
        )
    if directed != bool(spec.directed):
        raise ValueError(
            f"serialized directed={directed} != requested "
            f"{spec.directed}"
        )

    vm = _rebuild_vertex_map(
        [z[f"oids_{f}"] for f in range(fnum)], fnum, vp, spec
    )

    def csr_of(side, f):
        return CSR(
            indptr=z[f"{side}_indptr_{f}"],
            edge_src=z[f"{side}_src_{f}"],
            edge_nbr=z[f"{side}_nbr_{f}"],
            edge_w=z[f"{side}_w_{f}"] if f"{side}_w_{f}" in z else None,
            edge_mask=z[f"{side}_mask_{f}"],
            num_rows=vp,
            num_edges=int(z[f"{side}_ne_{f}"]),
        )

    aliased = bool(z["aliased"]) if "aliased" in z else False
    host_oe = [csr_of("oe", f) for f in range(fnum)]
    host_ie = host_oe if aliased else [csr_of("ie", f) for f in range(fnum)]
    dev = ShardedEdgecutFragment._device_put(
        comm_spec, vm, host_oe, host_ie, vp, directed,
        int(z["total_vnum"]), int(z["total_enum"]),
    )
    return ShardedEdgecutFragment(
        comm_spec, vm, dev, host_oe, host_ie, directed, weighted
    )


# ---- the vertex cut's cache: one `frag.garc` of tiles ------------------

_GAVC_MAGIC = 0x47415643  # "GAVC"


def _serialize_vertexcut(frag, cache: str, sig: str):
    """A vertex-cut fragment's tiles, real entries only (the padding is
    the header's `ep`), and where the device form is the pull's its
    CSRs' offsets and neighbours (row ids and masks follow from the
    offsets), so that a deserialize sorts nothing."""
    from libgrape_lite_tpu.utils.archive import InArchive

    os.makedirs(cache, exist_ok=True)
    s_arr, d_arr, w_arr, m_arr = frag._host_tiles
    ar = InArchive()
    ar.add_scalar(_GAVC_MAGIC)
    ar.add_scalar(1)  # format version
    pull = frag._host_pull
    for v in (
        frag.fnum, frag.k, frag.vc, frag.chunk, frag.total_enum,
        int(frag.directed), int(w_arr is not None),
        int(frag.symmetrized), int(pull is not None), s_arr.shape[1],
        0 if pull is None else pull[1].shape[1],
    ):
        ar.add_scalar(int(v))
    _put_array(ar, frag._oids)
    for f in range(frag.fnum):
        n = int(m_arr[f].sum())
        _put_array(ar, s_arr[f, :n])
        _put_array(ar, d_arr[f, :n])
        if w_arr is not None:
            _put_array(ar, w_arr[f, :n])
        if pull is not None:
            _put_array(ar, pull[0][f])
            _put_array(ar, pull[2][f, :2 * n])
    with open(os.path.join(cache, "frag.garc"), "wb") as fh:
        fh.write(ar.get_buffer())
    with open(os.path.join(cache, "sig"), "w") as f:
        f.write(sig)


def _deserialize_vertexcut(cache: str, comm_spec: CommSpec,
                           spec: LoadGraphSpec):
    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
    )
    from libgrape_lite_tpu.utils.archive import OutArchive

    oa = OutArchive(_read_cache_file(os.path.join(cache, "frag.garc")))
    if oa.get_scalar() != _GAVC_MAGIC:
        raise ValueError("bad vertex-cut garc magic")
    version = oa.get_scalar()
    if version != 1:
        raise ValueError(f"unsupported vertex-cut garc version {version}")
    (fnum, k, vc, chunk, total_enum, directed, weighted, symmetrized,
     has_pull, ep, width) = (oa.get_scalar() for _ in range(11))
    if fnum != comm_spec.fnum:
        raise ValueError(
            f"serialized fnum={fnum} != requested {comm_spec.fnum}"
        )
    if spec.weighted and not weighted:
        raise ValueError(
            "serialized fragment has no edge weights but the load "
            "asks for them (spec.weighted=True); re-serialize from a "
            "weighted load"
        )
    if bool(directed) != bool(spec.directed):
        raise ValueError(
            f"serialized directed={bool(directed)} != requested "
            f"{spec.directed}"
        )
    oids = _get_array(oa)
    s_arr = np.zeros((fnum, ep), dtype=np.int32)
    d_arr = np.zeros((fnum, ep), dtype=np.int32)
    w_arr = None
    m_arr = np.zeros((fnum, ep), dtype=bool)
    pull = None
    if has_pull:
        rows = 2 * vc
        pull = (
            np.zeros((fnum, rows + 1), dtype=np.int32),
            np.full((fnum, width), rows, dtype=np.int32),
            np.zeros((fnum, width), dtype=np.int32),
            np.zeros((fnum, width), dtype=bool),
        )
    for f in range(fnum):
        src = _get_array(oa)
        n = len(src)
        if n > ep:
            raise ValueError("corrupt tile in the vertex-cut garc")
        s_arr[f, :n] = src
        d_arr[f, :n] = _get_array(oa)
        if weighted:
            w = _get_array(oa)
            if w_arr is None:
                w_arr = np.zeros((fnum, ep), dtype=w.dtype)
            w_arr[f, :n] = w
        m_arr[f, :n] = True
        if has_pull:
            indptr = _get_array(oa)
            nbr = _get_array(oa)
            if (len(indptr) != rows + 1 or len(nbr) != 2 * n
                    or 2 * n > width or int(indptr[-1]) != 2 * n):
                raise ValueError("corrupt pull CSR in the vertex-cut garc")
            pull[0][f] = indptr
            pull[1][f, :2 * n] = np.repeat(
                np.arange(rows, dtype=np.int32), np.diff(indptr))
            pull[2][f, :2 * n] = nbr
            pull[3][f, :2 * n] = True
    if not oa.empty():  # not an assert: must survive `python -O`
        raise ValueError("trailing bytes in the vertex-cut frag.garc")
    return ImmutableVertexcutFragment.from_tiles(
        comm_spec, oids, k, vc, chunk, (s_arr, d_arr, w_arr, m_arr),
        total_enum, directed=bool(directed),
        symmetrized=bool(symmetrized),
        layout="pull" if has_pull else "coo", host_pull=pull,
    )
