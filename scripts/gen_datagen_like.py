"""Generate a datagen-9_0-fb-like surrogate graph (VERDICT r4 next #6).

The baseline's north-star dataset, LDBC datagen-9_0-fb
(`/root/reference/Performance.md:23,36`), is a Facebook-like
person-knows-person graph: |V| = 12,857,672, |E| = 1,049,527,225
undirected (avg degree ~163), community-structured (persons cluster by
university/city), degree distribution lognormal-ish with a hub cutoff
in the low thousands — structurally UNLIKE RMAT/Kronecker (no o(n)
fragmented tail, no degree-correlated id space, high clustering).
The dataset itself cannot be downloaded in this sandbox (zero egress),
and the full size exceeds the box's RAM for a load anyway, so this
generator produces a structure-matched surrogate at a documented
linear scale factor:

  * vertices n = 12,857,672 / s  (s = --scale_div, default 8)
  * target avg degree kept at the FULL graph's ~163 (per-edge
    throughput is what transfers across sizes for O(E)-per-round
    algorithms; shrinking degree with n would change the compute/
    communication ratio)
  * degree sequence: lognormal(sigma=1.15) scaled to the target mean,
    clipped to [1, 2000] (datagen fb's hub cutoff scale)
  * community sizes: Zipf-like power law over ~n/1500 communities,
    clipped to [400, 50k]
  * wiring: configuration model — every vertex gets deg(v) stubs;
    80% of stubs pair WITHIN the community (sorted by (community,
    random), paired consecutively), 20% pair globally; self-loops and
    duplicate pairs dropped (sub-1% degree loss, standard for
    configuration models)
  * weights: uniform(0, 1] float64, the Graphalytics SSSP convention

Output: TSV edge file (+ optional .v), plus a JSON line of structural
properties so the mapping to the real dataset is checkable.  See
docs/DATAGEN_SURROGATE.md for the RMAT<->datagen comparison this
unblocks.

The construction itself lives in `benchmarks/graphs/datagen_like.py`
(PR 36: the benchmark's cell `datagen-like.cdlp-10r` draws its graph
with it and may depend on nothing outside `benchmarks/`); this script
hands it the published file's ratios (`published_block`) and keeps
the command line, the properties line and the `%.9f` writer.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FULL_V = 12_857_672
FULL_E = 1_049_527_225


def published_block(seed: int) -> dict:
    """The construction's parameters at datagen-9_0-fb's own ratios, as
    the `generator` block `benchmarks/graphs/datagen_like.py` reads."""
    return {
        "name": "datagen_like",
        "mean_degree": 2 * FULL_E / FULL_V,  # ~163 (undirected degree)
        "degree_sigma": 1.15,
        "degree_clip": [1, 2000],
        # mean size ~1500 keeps intra-community edge density ~10% —
        # dense enough for CDLP/LCC community behavior, sparse enough
        # that configuration-model duplicate pairs stay rare (a
        # 150-person mean with 130 intra stubs per member degenerated
        # into near-cliques and lost 25% of edges to dedup)
        "vertices_per_community": 1500,
        "community_zipf": 1.35,
        "community_size_unit": 300,
        "community_clip": [400, 50_000],
        "intra_share": 0.8,
        "weights": [1e-6, 1.0],
        "weight_dtype": "float64",
        "generator_seed": seed,
    }


def generate(scale_div: int, seed: int = 42):
    """The surrogate at 1/scale_div of the full vertex count."""
    from benchmarks.graphs import datagen_like

    n = FULL_V // scale_div
    src, dst, w, comm, deg = datagen_like.draw(published_block(seed), n)
    return n, src, dst, w, comm, deg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale_div", type=int, default=8,
                    help="linear downscale factor vs datagen-9_0-fb")
    ap.add_argument("--out", required=True, help="edge TSV path")
    ap.add_argument("--vfile", default="", help="optional vertex file")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    t0 = time.perf_counter()
    n, src, dst, w, comm, deg = generate(args.scale_div, args.seed)
    t_gen = time.perf_counter() - t0
    props = {
        "surrogate_of": "datagen-9_0-fb",
        "scale_div": args.scale_div,
        "n_vertices": int(n),
        "n_edges_undirected": int(len(src)),
        "full_dataset": {"v": FULL_V, "e": FULL_E},
        "avg_degree": round(2 * len(src) / n, 1),
        "max_degree": int(np.bincount(
            np.concatenate([src, dst])).max()),
        "n_communities": int(len(np.unique(comm))),
        "gen_s": round(t_gen, 1),
    }
    print(json.dumps(props), file=sys.stderr)

    t0 = time.perf_counter()
    import io

    with open(args.out, "w", buffering=1 << 22) as f:
        CHUNK = 4_000_000
        for i in range(0, len(src), CHUNK):
            s, d, ww = src[i:i+CHUNK], dst[i:i+CHUNK], w[i:i+CHUNK]
            buf = io.StringIO()
            np.savetxt(buf, np.column_stack([s, d, ww]),
                       fmt="%d %d %.9f")
            f.write(buf.getvalue())
    if args.vfile:
        with open(args.vfile, "w", buffering=1 << 22) as f:
            f.write("\n".join(map(str, range(n))) + "\n")
    print(f"[gen] wrote {args.out} in {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    with open(args.out + ".props.json", "w") as f:
        json.dump(props, f)


if __name__ == "__main__":
    main()
