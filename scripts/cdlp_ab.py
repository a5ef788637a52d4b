"""CDLP wide-path A/B: dynamic label-universe compression vs the
variadic wide sort (VERDICT r4 next #2 'done' criterion).

Builds RMAT at --scale over --fnum shards (a geometry where the STATIC
packed key cannot fit: rank_bits + src_bits > 32), runs a few CDLP
rounds twice — once with the dynamic-compression path (default at this
geometry) and once with the wide sort forced — and prints per-round
wall clock plus the per-round distinct-label counts so the cond's
branch choice is visible.  Reference counterpart: the cdlp vs cdlp_opt
split (`examples/analytical_apps/cdlp/cdlp_opt.h`).

Run on CPU mesh:
  JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python scripts/cdlp_ab.py --scale 20 --fnum 8
On TPU (single chip): python scripts/cdlp_ab.py --scale 20 --fnum 1
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def planted_edges(scale: int, edge_factor: int, n_comm: int, seed: int = 11):
    """Planted-partition graph: n=2^scale vertices in n_comm communities,
    ~90% of edges intra-community — the coalescence profile of LDBC
    datagen's person-knows-person graphs (community-structured), unlike
    RMAT whose ~0.34n fragmented tail pins the live label universe at
    O(n)."""
    n = 1 << scale
    e = n * edge_factor
    rng = np.random.default_rng(seed)
    comm = rng.integers(0, n_comm, n)
    order = np.argsort(comm, kind="stable")
    # vertices grouped by community; intra edges pick endpoints within
    # the group via its contiguous index range
    starts = np.searchsorted(comm[order], np.arange(n_comm))
    ends = np.append(starts[1:], n)
    src_c = rng.integers(0, n_comm, e)
    intra = rng.random(e) < 0.9
    lo, hi = starts[src_c], np.maximum(ends[src_c], starts[src_c] + 1)
    u = order[(lo + rng.integers(0, 1 << 62, e) % (hi - lo))]
    v_in = order[(lo + rng.integers(0, 1 << 62, e) % (hi - lo))]
    v_out = rng.integers(0, n, e)
    v = np.where(intra, v_in, v_out)
    return n, u.astype(np.int64), v.astype(np.int64)


def build(scale: int, edge_factor: int, fnum: int, graph: str, n_comm: int):
    import bench
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import SegmentedPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    if graph == "planted":
        n, src, dst = planted_edges(scale, edge_factor, n_comm)
    else:
        n, src, dst = bench.rmat_edges(scale, edge_factor, seed=11)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(
        oids, SegmentedPartitioner(fnum, oids), idxer_type="sorted_array"
    )
    frag = ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, None,
        directed=False, load_strategy=LoadStrategy.kOnlyOut,
    )
    return n, frag


def run(app_factory, frag, rounds: int):
    """Compile once (untimed), then time each superstep individually
    via the stepwise building blocks (per-round wall clock is the A/B
    quantity; the fused while_loop hides it)."""
    import jax

    from libgrape_lite_tpu.worker.worker import Worker

    app = app_factory()
    w = Worker(app, frag)
    state = w._place_state(app.init_state(frag, max_round=rounds))
    peval_fn = w._compile_single_step("peval", state)
    inc_fn = w._compile_single_step("inceval", state)
    # warm both compiles out of the timed region
    st_w, _ = jax.block_until_ready(peval_fn(frag.dev, state))
    jax.block_until_ready(inc_fn(frag.dev, st_w))

    times = []
    t0 = time.perf_counter()
    st, active = jax.block_until_ready(peval_fn(frag.dev, state))
    times.append(time.perf_counter() - t0)
    r = 1
    while int(active) > 0 and r < rounds:
        t0 = time.perf_counter()
        st, active = jax.block_until_ready(inc_fn(frag.dev, st))
        times.append(time.perf_counter() - t0)
        r += 1
    w._result_state = st
    return w.result_values(), times, sum(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--edge_factor", type=int, default=16)
    ap.add_argument("--fnum", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--graph", choices=["rmat", "planted"], default="rmat")
    ap.add_argument("--n_comm", type=int, default=4096)
    args = ap.parse_args()

    from libgrape_lite_tpu.models import CDLP

    n, frag = build(args.scale, args.edge_factor, args.fnum, args.graph,
                    args.n_comm)
    rank_bits = int(np.ceil(np.log2(frag.vp * frag.fnum + 2)))
    src_bits = int(np.ceil(np.log2(frag.vp + 2)))
    assert rank_bits + src_bits > 32, (
        "geometry fits the static pack; A/B is vacuous here"
    )
    print(
        f"[cdlp_ab] n={n:,} vp={frag.vp} fnum={frag.fnum} "
        f"src_bits={src_bits} dyn_budget=2^{32 - src_bits}",
        file=sys.stderr,
    )

    report = {"scale": args.scale, "fnum": args.fnum, "graph": args.graph,
              "rounds": args.rounds, "dyn_budget": 1 << (32 - src_bits),
              "variants": {}}

    for name, force_wide in (("dynamic", False), ("wide", True)):
        def mk(fw=force_wide):
            app = CDLP()
            app._force_dynamic = True
            app._force_wide = fw
            return app

        res, times, total = run(mk, frag, args.rounds)
        report["variants"][name] = {
            "round_s": [round(t, 4) for t in times],
            "total_s": round(total, 3),
        }
        print(f"[cdlp_ab] {name}: rounds={times} total={total:.3f}s",
              file=sys.stderr)
        if name == "dynamic":
            ref = res
        else:
            assert np.array_equal(np.asarray(ref), np.asarray(res)), (
                "dynamic and wide paths diverged"
            )
            report["parity"] = True

    print(json.dumps(report))


if __name__ == "__main__":
    main()
