#!/usr/bin/env python3
"""First light on the chip: LoadGraph -> Worker / ServeSession, end to end.

    python chip_smoke.py [--fnum N] [--scale S] [--seed K]

One process, which imports JAX itself and exits non-zero before any work
unless JAX's first device is a TPU.  Through the entry points a user
calls it drives:

  set-up   `make -B -C native`; the Graph500 Kronecker graph of
           `--scale` (edge factor 16, weighted, undirected) from
           `--seed` with scripts/gen_rmat.py's generator, written as a
           TSV outside the checkout and loaded ONCE through `LoadGraph`.
  Stage A  in-process `cli.main` on dataset/p2p-31 at `--fnum`: the six
           Graphalytics algorithms against the goldens (at fnum 4 also
           `pagerank --vc` on the 2x2 mesh), then `serve` plain and
           `--inflight 4`.
  Stage B  the real size, on the resident fragment: PageRank, SSSP, BFS,
           WCC through `Worker.query`, cold then warm (no compile),
           every vertex against a plain NumPy/SciPy reference; then a
           `ServeSession` answers 4 SSSP + 4 BFS point queries.
  Stage C  the Pallas kernel LCC's default path reaches on a TPU
           (bitmap intersect), compiled, on p2p-31; and the pull's
           gather (`ops/segment.pull_gather`, which every stage's pulls
           already went through) alone, compiled, against XLA's
           `full[nbr]` on a four-chip shard's shapes.

Any failure raises: nothing is caught and continued.  The walls it
prints are set-up and health readings, never a benchmark metric.
`--rehearse` permits a CPU run at a tiny `--scale` (Pallas interpreted):
a rehearsal of the control flow, never a result.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DATASET = os.path.join(REPO, "dataset")
DEFAULT_SCALE = 21  # graph500-22's generator one scale down: see REDUCED
EDGE_FACTOR = 16
PR_DELTA, PR_ROUNDS = 0.85, 10
P2P_SOURCE = 6
REHEARSAL_MAX_SCALE = 12

# app -> (golden suffix, rule, eps): the x32 rules tests/x32_check.py
# fixes — a TPU run is f32 against f64 goldens
RULES = {
    "sssp": ("SSSP", "eps", 1e-3),
    "bfs": ("BFS", "exact", None),
    "pagerank": ("PR", "eps", 1e-3),
    "wcc": ("WCC", "partition", None),
    "cdlp": ("CDLP", "exact", None),
    "lcc": ("LCC", "eps", 1e-4),
}
NOT_RUN = {
    "cdlp_at_size": "its two runs were 150-300 s of an earlier smoke at "
                    "scale 21-22; the benchmark cell g500-cdlp.cdlp-10r "
                    "runs and checks it at size (PERF.md). Exact on "
                    "p2p-31 in Stage A",
    "lcc_at_size": "the registry's lcc runs at size in the benchmark cell "
                   "g500-lcc.lcc, which checks every vertex (PERF.md); "
                   "docs/SCALE_NOTES.md sizes its ELL past one chip at "
                   "scale 22, and the cell g500-lcc-x4.lcc runs it "
                   "sharded over four chips, the target blocks on a "
                   "ring. Exact on p2p-31 in Stages A and C",
}


def require(cond, message: str) -> None:
    """A smoke check: a raise, not an `assert`, so -O cannot drop it."""
    if not cond:
        raise AssertionError(message)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, round(time.perf_counter() - t0, 2)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", file=sys.stderr, flush=True)


# ---- plain references: NumPy / SciPy only, nothing from the library ----


def symmetric_csr(n: int, src, dst, w):
    """The undirected multigraph as two SciPy CSR matrices with one
    entry per distinct (row, col): the lightest parallel weight, and the
    pair's multiplicity (a self-loop counts twice, as in a symmetrised
    edge list).  One sort of packed (row, col, weight) keys; weights
    must be integers below 256 (the generator's are 1..10, p2p-31's
    1..100)."""
    import scipy.sparse as sp

    wi = np.asarray(w).astype(np.int64)
    require((wi == w).all() and wi.min() >= 0 and wi.max() < 256,
            "reference needs integer weights in [0, 256)")
    vbits = max(1, (n - 1).bit_length())
    s = np.concatenate([src, dst]).astype(np.int64)
    t = np.concatenate([dst, src]).astype(np.int64)
    key = (s << (vbits + 8)) | (t << 8) | np.concatenate([wi, wi])
    del s, t
    key.sort()
    pair = key >> 8
    starts = np.flatnonzero(np.r_[True, pair[1:] != pair[:-1]])
    mult = np.diff(np.r_[starts, len(key)])
    key = key[starts]
    rc = (key >> (vbits + 8), (key >> 8) & ((1 << vbits) - 1))
    return (sp.csr_matrix(((key & 255).astype(np.float64), rc), shape=(n, n)),
            sp.csr_matrix((mult.astype(np.float64), rc), shape=(n, n)))


def ref_pagerank(mult, delta: float, rounds: int) -> np.ndarray:
    """LDBC PageRank: power iteration, dangling mass spread evenly."""
    n = mult.shape[0]
    deg = np.asarray(mult.sum(axis=1)).ravel()
    rank = np.full(n, 1.0 / n)
    for _ in range(rounds):
        base = (1.0 - delta) / n + delta * rank[deg == 0].sum() / n
        rank = base + delta * (mult @ (rank / np.maximum(deg, 1.0)))
    return rank


def ref_sssp(minw, source: int) -> np.ndarray:
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(minw, directed=True, indices=source)


def ref_bfs(minw, source: int) -> np.ndarray:
    """Hop depth per vertex, -1 where unreached: SciPy's BFS order and
    predecessors, depths resolved one level per pass."""
    from scipy.sparse.csgraph import breadth_first_order

    order, pred = breadth_first_order(minw, source, directed=True)
    depth = np.full(minw.shape[0], -1, dtype=np.int64)
    depth[source] = 0
    todo = order[1:]
    while len(todo):
        d = depth[pred[todo]]
        depth[todo[d >= 0]] = d[d >= 0] + 1
        todo = todo[d < 0]
    return depth


def ref_wcc(minw) -> np.ndarray:
    from scipy.sparse.csgraph import connected_components

    return connected_components(minw, directed=False)[1]


def mismatches(rule: str, got, want, eps=None) -> int:
    """tests/verifiers.py's three rules, vectorised for millions of rows."""
    got, want = np.asarray(got), np.asarray(want)
    if rule == "partition":  # same grouping, arbitrary labels
        a = np.unique(got, return_inverse=True)[1].astype(np.int64)
        b = np.unique(want, return_inverse=True)[1].astype(np.int64)
        pairs = len(np.unique(a * (b.max() + 1) + b))
        return int(not pairs == a.max() + 1 == b.max() + 1)
    if rule == "exact":
        return int((got != want).sum())
    got = got.astype(np.float64)
    inf = np.isinf(want) | np.isinf(got)
    with np.errstate(invalid="ignore"):  # inf - inf, masked out below
        close = np.where(want == 0, np.abs(got) < max(1e-12, eps * 1e-8),
                         np.abs(got - want) <= eps * np.abs(want))
    return int((~np.where(inf, got == want, close)).sum())


# ---- observation helpers ----


@contextlib.contextmanager
def pallas_spy():
    """The `interpret=` of every `pl.pallas_call` traced inside the
    block: what ran, not what a selector says should have."""
    from jax.experimental import pallas as pl

    seen, real = [], pl.pallas_call

    def spy(*args, **kwargs):
        seen.append(bool(kwargs.get("interpret", False)))
        return real(*args, **kwargs)

    pl.pallas_call = spy
    try:
        yield seen
    finally:
        pl.pallas_call = real


def spy_summary(calls: list) -> dict:
    """What `pallas_spy` saw, in a stage's terms."""
    return {"pallas_calls": len(calls),
            "interpret": any(calls) if calls else None,
            "compiled": bool(calls) and not any(calls)}


def device_memory() -> list:
    import jax

    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}  # None on the CPU
        out.append({"id": d.id, **{k: int(ms.get(k, 0)) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}})
    return out


def check_golden(app: str, result: dict) -> str:
    from tests import verifiers

    suffix, rule, eps = RULES[app]
    golden = verifiers.load_golden(os.path.join(DATASET, f"p2p-31-{suffix}"))
    if rule == "eps":
        verifiers.eps_verify(result, golden, eps=eps)
        return f"golden, eps {eps:g}"
    (verifiers.exact_verify if rule == "exact"
     else verifiers.wcc_verify)(result, golden)
    return f"golden, {rule}"


# ---- Stage A: the CLI on p2p-31 ----


def stage_a(fnum: int, workdir: str) -> dict:
    from libgrape_lite_tpu import cli
    from tests.verifiers import load_result_lines

    files = ["--efile", os.path.join(DATASET, "p2p-31.e"),
             "--vfile", os.path.join(DATASET, "p2p-31.v"),
             "--fnum", str(fnum)]
    flags = {"sssp": ["--sssp_source", str(P2P_SOURCE)],
             "bfs": ["--bfs_source", str(P2P_SOURCE)],
             "pagerank": ["--pr_d", str(PR_DELTA), "--pr_mr", str(PR_ROUNDS)],
             "cdlp": ["--cdlp_mr", "10"]}
    runs = [(app, app, []) for app in RULES]
    if fnum == 4:
        runs.append(("pagerank_vc", "pagerank", ["--vc"]))  # the 2x2 mesh
    out = {}
    for label, app, extra in runs:
        prefix = os.path.join(workdir, f"a_{label}")
        with contextlib.redirect_stdout(sys.stderr):  # the CLI's timer lines
            _, wall = timed(cli.main, [
                "--application", app, *files, *flags.get(app, []), *extra,
                "--out_prefix", prefix])
        text = ""
        for f in range(fnum):
            with open(os.path.join(prefix, f"result_frag_{f}")) as fh:
                text += fh.read()
        out[label] = {"ok": True,
                      "check": check_golden(app, load_result_lines(text)),
                      "cold_wall_s": wall}
        log(f"A {label}: {out[label]}")
    # serve: the synchronous loop, then the async pump — whose launch_cap
    # takes its non-CPU branch (serve/pipeline.py) only on a chip
    for label, extra in (("serve", []), ("serve_inflight4", ["--inflight", "4"])):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            cli.main(["serve", *files, "--num_queries", "16",
                      "--max_batch", "4", *extra])
        rec = json.loads(buf.getvalue().strip().splitlines()[-1])
        require(rec["queries"] == 16 and rec["failed"] == 0,
                f"stage A {label}: {rec}")
        out[label] = {"ok": True, "queries": 16, "failed": 0,
                      "batch_hist": rec["batch_hist"]}
        log(f"A {label}: {out[label]}")
    return out


# ---- Stage B: the real size, on the resident fragment ----


def make_graph(scale: int, seed: int, workdir: str, setup: dict):
    """The committed generator's draws, kept in memory for the plain
    references and written as the TSV + vertex file `LoadGraph` parses."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import gen_rmat
    from bench import rmat_edges

    (n, src, dst), setup["generate"] = timed(
        rmat_edges, scale, EDGE_FACTOR, seed)
    w = gen_rmat.edge_weights(len(src), seed)
    efile = os.path.join(workdir, f"graph500-{scale}.e")
    vfile = os.path.join(workdir, f"graph500-{scale}.v")

    def write():
        gen_rmat.write_edge_file(efile, src, dst, w)
        with open(vfile, "w") as f:
            f.write("\n".join(map(str, range(n))) + "\n")

    _, setup["write"] = timed(write)
    return n, src, dst, w, efile, vfile


def make_references(n, src, dst, w, seed) -> dict:
    minw, mult = symmetric_csr(n, src, dst, w)
    # four seeded sources that are in the graph (RMAT leaves about half
    # the id space isolated).  Stage B asks [0] cold and [1] warm — the
    # one held to the reference; the served stream asks all four
    has_edge = np.flatnonzero(np.diff(minw.indptr) > 0)
    sources = np.random.default_rng(seed).choice(
        has_edge, size=4, replace=False).tolist()
    return {"sources": sources,
            "pagerank": ref_pagerank(mult, PR_DELTA, PR_ROUNDS),
            "sssp": ref_sssp(minw, sources[1]),
            "bfs": ref_bfs(minw, sources[1]),
            "wcc": ref_wcc(minw)}


def check_placement(frag, fnum: int) -> dict:
    """Every device-resident leaf spans `fnum` distinct devices, and no
    device staged the whole graph on the way (device 0's allocator peak
    within 25% of the others')."""
    import jax

    arrays = [x for x in jax.tree_util.tree_leaves(frag.dev)
              if isinstance(x, jax.Array)]
    for x in arrays:
        got = len({s.device for s in x.addressable_shards})
        require(got == fnum,
                f"fragment leaf {x.shape} spans {got} devices, not {fnum}")
    out = {"leaves": len(arrays), "distinct_devices": fnum}
    peaks = [m["peak_bytes_in_use"] for m in device_memory()[:fnum]]
    if fnum > 1 and all(peaks):
        out["peak_dev0_over_others"] = round(
            peaks[0] / float(np.median(peaks[1:])), 3)
        require(abs(out["peak_dev0_over_others"] - 1.0) <= 0.25,
                f"device 0 peaked at {peaks[0]}, the others at {peaks[1:]}")
    return out


def by_vertex(frag, values) -> np.ndarray:
    """[fnum, vp] result rows -> one value per oid (oids are 0..n-1)."""
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return out


def stage_b(frag, refs: dict) -> dict:
    from libgrape_lite_tpu.analysis.artifact import compile_events
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.worker.worker import Worker

    s = refs["sources"]
    pr = {"delta": PR_DELTA, "max_round": PR_ROUNDS}
    queries = {"pagerank": (pr, pr), "wcc": ({}, {}),
               "sssp": ({"source": s[0]}, {"source": s[1]}),
               "bfs": ({"source": s[0]}, {"source": s[1]})}
    out, single = {}, {}
    for name, (cold_kw, warm_kw) in queries.items():
        worker = Worker(APP_REGISTRY[name](), frag)
        with compile_events() as cold_ev:
            _, cold = timed(worker.query, **cold_kw)
        with compile_events() as warm_ev:
            _, warm = timed(worker.query, **warm_kw)
        require(warm_ev.compiles == 0,
                f"stage B {name}: the warm query compiled: {warm_ev.events}")
        values = worker.result_values()
        single[name] = values
        got, want = by_vertex(frag, values), refs[name]
        _, rule, eps = RULES[name]
        if name == "bfs":  # unreached: the app's sentinel is no depth
            got = np.where(got >= len(got), -1, got)
        bad = mismatches(rule, got, want, eps)
        require(bad == 0, f"stage B {name}: {bad} vertices off the "
                          f"plain reference ({rule})")
        out[name] = {
            "ok": True, "rounds": int(worker.rounds),
            "check": f"every vertex vs NumPy/SciPy, {rule}"
                     + (f" {eps:g}" if eps else ""),
            "cold_wall_s": cold, "warm_wall_s": warm,
            "compiles_cold": cold_ev.compiles,
            "compiles_warm": warm_ev.compiles,
        }
        log(f"B {name}: {out[name]}")

    # the resident session: one lane per app must be byte-identical to
    # the single Worker.query above (docs/SERVING.md's per-lane identity)
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    reqs = [(app, src, sess.submit(app, {"source": src}))
            for app in ("sssp", "bfs") for src in s]
    _, wall = timed(sess.drain)
    for app, src, r in reqs:
        require(r.result is not None and r.result.ok,
                f"stage B serve: {app} from {src} failed: "
                f"{getattr(r.result, 'error', None)}")
        if src == s[1]:
            require(np.asarray(r.result.values).tobytes()
                    == single[app].tobytes(),
                    f"stage B serve: the {app} lane from {src} differs "
                    "from the single Worker.query")
    out["serve"] = {
        "ok": True, "queries": len(reqs), "failed": 0,
        "batch_hist": {str(k): v for k, v in sess.queue.batch_hist.items()},
        "byte_identical_lanes": ["sssp", "bfs"], "wall_s": wall,
    }
    sess.close()
    log(f"B serve: {out['serve']}")
    return out


# ---- Stage C: the reachable Pallas kernels, compiled ----


def stage_c(on_tpu: bool) -> dict:
    import jax

    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from tests.verifiers import collect_worker_result

    frag = LoadGraph(
        os.path.join(DATASET, "p2p-31.e"), os.path.join(DATASET, "p2p-31.v"),
        CommSpec(fnum=1),
        LoadGraphSpec(directed=False, weighted=True, edata_dtype=np.float32),
    )
    # off the TPU (a rehearsal) row_and_popcount takes the fused jnp
    # path: no kernel is expected there
    jax.clear_caches()  # an earlier stage's trace must not hide this
    with pallas_spy() as calls:
        (result, wall) = timed(
            collect_worker_result, APP_REGISTRY["lcc_bitmap"](), frag)
    if on_tpu:
        require(calls, "stage C intersect_count: no pallas_call was traced")
        require(not any(calls), "stage C intersect_count: ran interpreted")
    out = {"intersect_count": {
        "ok": True, "app": "lcc_bitmap", "check": check_golden("lcc", result),
        **spy_summary(calls), "cold_wall_s": wall,
    }}
    log(f"C intersect_count: {out['intersect_count']}")
    out["vmem_gather"] = gather_check(on_tpu)
    out["vmem_row_gather"] = row_ends_check(on_tpu)
    return out


def gather_check(on_tpu: bool) -> dict:
    """`pull_gather` as the apps call it, on the shapes of the x4
    cell's shard (17,192,832 indices, a ragged last block, into the
    mirror exchange's 1,388,544 places): on the TPU the kernel must be
    what was traced, compiled, and its output `full[nbr]` bit for bit,
    the ends of the index range and beyond included.  Off it (a
    rehearsal) the choice is XLA's gather and no kernel is expected."""
    import jax
    import jax.numpy as jnp

    from libgrape_lite_tpu.ops.segment import GATHER_STATS, pull_gather

    v, n = 1_388_544, 134_319 * 128
    rng = np.random.default_rng(27)
    nbr = rng.integers(0, v, n).astype(np.int32)
    nbr[:6] = [0, v - 1, -1, -v, v, np.iinfo(np.int32).max]
    out = {"ok": True, "indices": n, "table": v}
    before = GATHER_STATS.snapshot()
    with pallas_spy() as calls:
        for dtype in (np.float32, np.int32):
            full = jnp.asarray(rng.integers(-9, 9, v).astype(dtype))
            (got, wall) = timed(jax.jit(pull_gather), full, nbr)
            require(bool(jnp.array_equal(got, full[jnp.asarray(nbr)])),
                    f"stage C vmem_gather: {np.dtype(dtype).name} output "
                    "is not full[nbr]")
            out[f"{np.dtype(dtype).name}_cold_wall_s"] = wall
    took = GATHER_STATS.snapshot()
    out["took"] = {k: took[k] - before[k] for k in took}
    if on_tpu:
        require(out["took"] == {"kernel": 2, "xla": 0},
                f"stage C vmem_gather: the choice was {out['took']}")
        require(len(calls) == 2 and not any(calls),
                "stage C vmem_gather: not compiled")
    out.update(spy_summary(calls))
    log(f"C vmem_gather: {out}")
    return out


def row_ends_check(on_tpu: bool) -> dict:
    """`segment_reduce(row_ptr=)` as the apps call it, on the shapes of
    the x4 cell's shard (524,288 rows, a third of them empty, over
    17,192,832 places: five slices of the scanned stream, the last
    ragged, and 66 blocks of tiles, the last ragged): on the TPU the
    scan's first level and the row ends must go by the kernels that
    were traced, compiled, and the fold equal the scatter's bit for
    bit (a min: exact under any grouping).  A float sum's bits hang on
    the grouping, so `tile_scan` is also held, alone, to XLA's seven
    steps bit for bit.  Off the TPU (a rehearsal) the choice is XLA's
    steps and XLA's gather."""
    import jax
    import jax.numpy as jnp

    from libgrape_lite_tpu.ops import segment
    from libgrape_lite_tpu.ops.segment import (
        ROW_END_STATS, SCAN_STATS, segment_reduce,
    )

    rows, n = 524_288, 134_319 * 128
    rng = np.random.default_rng(45)
    deg = np.where(rng.random(rows) < 0.35, 0, rng.geometric(0.025, rows))
    deg[rows // 2] += n - 4096 - deg.sum()  # a hub takes what is left
    ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    ids = np.full(n, rows, np.int32)
    ids[:ptr[-1]] = np.repeat(np.arange(rows, dtype=np.int32), deg)
    out = {"ok": True, "rows": rows, "places": n}
    before = ROW_END_STATS.snapshot(), SCAN_STATS.snapshot()
    with pallas_spy() as calls:
        for dtype in (np.float32, np.int32):
            vals = jnp.asarray(rng.integers(-9, 9, n).astype(dtype))
            (got, wall) = timed(
                jax.jit(lambda v, i, p: segment_reduce(
                    v, i, rows, "min", row_ptr=p)), vals, ids, ptr)
            want = jax.jit(lambda v, i: segment_reduce(
                v, i, rows, "min"))(vals, ids)
            require(bool(jnp.array_equal(got, want)),
                    f"stage C vmem_row_gather: {np.dtype(dtype).name} scan "
                    "fold is not the scatter's")
            out[f"{np.dtype(dtype).name}_cold_wall_s"] = wall
    took = ROW_END_STATS.snapshot(), SCAN_STATS.snapshot()
    out["took"], out["scan_took"] = (
        {k: t[k] - b[k] for k in t} for t, b in zip(took, before))
    if on_tpu:
        require(out["took"] == {"kernel": 2, "xla": 0},
                f"stage C vmem_row_gather: the choice was {out['took']}")
        require(out["scan_took"] == {"kernel": 2, "xla": 0},
                f"stage C tile_scan: the choice was {out['scan_took']}")
        require(len(calls) == 4 and not any(calls),
                "stage C tile_scan, vmem_row_gather: not compiled")
        # the float sum, whose grouping shows: the kernel alone against
        # the steps it stands for
        from libgrape_lite_tpu.ops.pallas_kernels import tile_scan

        vals = jnp.asarray((rng.standard_normal(n) * 10.0 ** rng.integers(
            -3, 6, n)).astype(np.float32)).reshape(-1, segment.SCAN_TILE)
        tiles = jnp.asarray(ids).reshape(-1, segment.SCAN_TILE)
        got = jax.jit(lambda v, i: tile_scan(v, i, jnp.add))(vals, tiles)
        want = jax.jit(lambda v, i: segment._tile_steps(
            v, i, jnp.add, 0))(vals, tiles)
        require(np.asarray(got).tobytes() == np.asarray(want).tobytes(),
                "stage C tile_scan: a float sum's tiles are not the "
                "XLA steps' bit for bit")
        out["float_sum_bit_equal"] = True
    out.update(spy_summary(calls))
    log(f"C tile_scan, vmem_row_gather: {out}")
    return out


# ---- driver ----


def run(args, on_tpu: bool) -> dict:
    import jax
    import jaxlib
    from importlib import metadata

    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.io import native
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    dev0 = jax.devices()[0]
    fnum = args.fnum or len(jax.devices())
    summary = {
        "rehearsal": not on_tpu, "platform": dev0.platform,
        "device_kind": dev0.device_kind, "devices": len(jax.devices()),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu") if on_tpu else None},
        "fnum": fnum, "scale": args.scale, "edge_factor": EDGE_FACTOR,
        "seed": args.seed,
        "deployment": "LDBC Graphalytics, Graph500 Kronecker a=.57 b=.19 "
                      "c=.19 d=.05, undirected, weighted",
        "reduced": ([f"scale {args.scale} < 22 (graph500-22 is the "
                     "smallest Graphalytics Graph500 set): the smoke's "
                     "time limit"] if args.scale < 22 else []),
        "not_run": NOT_RUN,
    }
    setup = summary["setup_s"] = {}

    # the native parser, built in THIS run from this tree's loader.cc (a
    # copied tree may carry a stale .so); once it loads, every
    # read_edge_file in the process takes it
    _, setup["native_build"] = timed(
        subprocess.run, ["make", "-B", "-C", os.path.join(REPO, "native")],
        check=True, capture_output=True)
    require(not os.environ.get("GRAPE_TPU_NO_NATIVE") and native.available(),
            "native/loader.cc built but the parser did not load")
    summary["native"] = True

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        n, src, dst, w, efile, vfile = make_graph(
            args.scale, args.seed, workdir, setup)
        summary["graph"] = {"vertices": n, "edges": len(src),
                            "pull_entries": 2 * len(src),
                            "efile_bytes": os.path.getsize(efile)}
        refs, setup["references"] = timed(
            make_references, n, src, dst, w, args.seed)
        del src, dst, w
        frag, setup["load"] = timed(
            LoadGraph, efile, vfile, CommSpec(fnum=fnum),
            LoadGraphSpec(weighted=True, edata_dtype=np.float64))
        log(f"set-up {setup}")
        summary["placement"] = check_placement(frag, fnum)
        summary["hbm"] = {"loader_assumes_bytes": 16 << 30,
                          "after_load": device_memory()}
        summary["stage_a"] = stage_a(fnum, workdir)
        summary["stage_b"] = stage_b(frag, refs)
    del frag
    summary["stage_c"] = stage_c(on_tpu)  # kernels are per-shard: one chip

    hbm = summary["hbm"]["at_exit"] = device_memory()
    if on_tpu:
        for m in hbm[:fnum]:
            require(m["peak_bytes_in_use"] and m["bytes_limit"],
                    f"device {m['id']} reports no allocator stats: {m}")
        # CommSpec takes jax.devices()[:fnum]: a chip beyond that was
        # never handed a buffer
        for m in hbm[fnum:]:
            require(m["peak_bytes_in_use"] < 1 << 20,
                    f"device {m['id']} is outside fnum={fnum} yet peaked "
                    f"at {m['peak_bytes_in_use']} bytes")
    return summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--fnum", type=int, default=None,
                   help="fragments = chips used (default: all present)")
    p.add_argument("--scale", type=int, default=DEFAULT_SCALE)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--rehearse", action="store_true",
                   help="permit a CPU run at --scale <= "
                        f"{REHEARSAL_MAX_SCALE}: never a result")
    args = p.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    dev0 = jax.devices()[0]
    on_tpu = dev0.platform == "tpu"
    if not on_tpu and not (args.rehearse
                           and args.scale <= REHEARSAL_MAX_SCALE):
        print(f"chip_smoke: JAX found platform {dev0.platform!r}, not 'tpu': "
              "nothing was run (--rehearse permits a CPU rehearsal at "
              f"--scale <= {REHEARSAL_MAX_SCALE})", file=sys.stderr)
        return 2
    # the program itself: with this file alone in a directory the import
    # fails here, before anything reaches stdout
    from libgrape_lite_tpu.analysis.artifact import compile_events
    from libgrape_lite_tpu.utils.compile_cache import place_compile_cache

    print(f"platform: {dev0.platform}", flush=True)
    cache_dir = place_compile_cache()
    with compile_events() as ev:
        summary = run(args, on_tpu)
    names = [name for name, _ in ev.events]
    summary["compile"] = {
        # one duration per request: a fresh backend compile, or the
        # fetch of a persistent-cache hit
        "requests": names.count("/jax/core/compile/backend_compile_duration"),
        "request_s": round(ev.compile_seconds(), 2),
        "cache_dir": cache_dir,
        "cache_hits": names.count("/jax/compilation_cache/cache_hits"),
        "cache_misses": names.count("/jax/compilation_cache/cache_misses"),
    }
    summary["wall_s"] = round(time.perf_counter() - t_start, 1)
    summary["ok"] = True
    summary["claim"] = None
    print(json.dumps(summary), flush=True)
    if on_tpu:  # a rehearsal prints no verdict
        print(json.dumps({"ok": True, "device": {
            "platform": dev0.platform, "kind": dev0.device_kind,
            "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
