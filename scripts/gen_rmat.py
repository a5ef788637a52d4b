#!/usr/bin/env python
"""Generate an RMAT edge file for scale runs (the LDBC datagen stand-in
for this sandbox; reference scope `/root/reference/Performance.md:21-50`).

  python scripts/gen_rmat.py --scale 24 --edge_factor 16 \
      --weighted --out /tmp/rmat24.e

Writes `src dst [w]` lines (integer weights 1..10 so the pandas C
writer stays fast).  The CSV WRITE is chunked (bounded text buffers);
generation itself materialises the full src/dst int64 arrays plus a
per-bit float64 draw, so peak memory is ~5x the edge-array bytes
(scale 24 x ef 16: ~20 GiB).

`--delta N` additionally emits a reproducible update stream of N
`a src dst [w]` lines to `--delta_out` (dyn/ docs/DYNAMIC_GRAPHS.md):
fresh RMAT draws over the SAME vertex universe with a separate seed —
additive-only, so they ride the overlay side-path; the serve CLI
ingests the file via --delta_stream and bench.py's dyn lane measures
updates/sec against exactly this distribution.

`--shuffle_ids` applies a seeded permutation (`--shuffle_seed`) to the
vertex id space before writing: raw RMAT ids are degree-correlated
(low ids are hubs — a=0.57 biases every bit toward 0), which makes
any contiguous-range partitioner put the hubs on one shard and every
shard pay that shard's padded Ep (3.2x waste at scale 24,
docs/SCALE_NOTES.md).  The shuffle breaks the correlation
reproducibly, so a 1-D baseline measured on the shuffled file is the
HONEST best-case edge-cut — the comparison the bench `partition2d`
lane runs its 2-D A/B against (docs/PARTITION2D.md).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--scale", type=int, default=24)
    p.add_argument("--edge_factor", type=int, default=16)
    p.add_argument("--weighted", action="store_true")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--out", required=True)
    p.add_argument("--delta", type=int, default=0,
                   help="also emit N additive delta ops ('a src dst "
                        "[w]' lines) to --delta_out")
    p.add_argument("--delta_out", default="",
                   help="path for the --delta update stream")
    p.add_argument("--delta_seed", type=int, default=101)
    p.add_argument("--shuffle_ids", action="store_true",
                   help="apply a seeded permutation to the vertex id "
                        "space (breaks RMAT's degree-id correlation; "
                        "the honest 1-D baseline for 2-D A/Bs)")
    p.add_argument("--shuffle_seed", type=int, default=53)
    args = p.parse_args(argv)
    if args.delta and not args.delta_out:
        p.error("--delta requires --delta_out")

    from bench import rmat_edges

    t0 = time.perf_counter()
    n, src, dst = rmat_edges(args.scale, args.edge_factor, args.seed)
    if args.shuffle_ids:
        perm = shuffle_perm(n, args.shuffle_seed)
        src, dst = perm[src], perm[dst]
        print(f"[gen_rmat] shuffled ids (seed {args.shuffle_seed})",
              flush=True)
    print(f"[gen_rmat] generated {len(src):,} edges over {n:,} vertices "
          f"in {time.perf_counter() - t0:.1f}s", flush=True)

    t0 = time.perf_counter()
    w = edge_weights(len(src), args.seed) if args.weighted else None
    write_edge_file(args.out, src, dst, w)
    print(f"[gen_rmat] wrote {args.out} "
          f"({os.path.getsize(args.out) / (1 << 30):.2f} GiB) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    if args.delta:
        t0 = time.perf_counter()
        d_src, d_dst = delta_edges(args.scale, args.delta,
                                   args.delta_seed)
        if args.shuffle_ids:
            # the update stream lives in the same (shuffled) id space
            # as the base graph it mutates
            d_src, d_dst = perm[d_src], perm[d_dst]
        rng_dw = np.random.default_rng(args.delta_seed + 1)
        with open(args.delta_out, "w") as f:
            if args.weighted:
                dw = rng_dw.integers(1, 11, args.delta)
                for s, d, x in zip(d_src.tolist(), d_dst.tolist(),
                                   dw.tolist()):
                    f.write(f"a {s} {d} {x}\n")
            else:
                for s, d in zip(d_src.tolist(), d_dst.tolist()):
                    f.write(f"a {s} {d}\n")
        print(f"[gen_rmat] wrote {args.delta} delta op(s) to "
              f"{args.delta_out} in {time.perf_counter() - t0:.1f}s",
              flush=True)
    return 0


_WRITE_CHUNK = 1 << 24


def edge_weights(n_edges: int, seed: int) -> np.ndarray:
    """The file's weight column: integers 1..10 from `seed + 1`, drawn
    in write-chunk order — the stream `--weighted` has always written,
    so the file and an in-memory twin (chip_smoke.py's plain
    references) agree edge for edge."""
    rng = np.random.default_rng(seed + 1)
    return np.concatenate(
        [rng.integers(1, 11, min(_WRITE_CHUNK, n_edges - lo))
         for lo in range(0, n_edges, _WRITE_CHUNK)]
        or [np.zeros(0, dtype=np.int64)]
    )


def write_edge_file(path: str, src, dst, w=None) -> None:
    """`src dst [w]` lines, written in bounded chunks."""
    import pandas as pd

    with open(path, "w") as f:
        for lo in range(0, len(src), _WRITE_CHUNK):
            hi = min(lo + _WRITE_CHUNK, len(src))
            cols = {"s": src[lo:hi], "d": dst[lo:hi]}
            if w is not None:
                cols["w"] = w[lo:hi]
            pd.DataFrame(cols).to_csv(
                f, sep=" ", header=False, index=False
            )


def shuffle_perm(n: int, seed: int = 53) -> np.ndarray:
    """The reproducible id permutation behind --shuffle_ids — shared
    with bench.py's partition2d lane so the benched id space IS the
    scripted one."""
    return np.random.default_rng(seed).permutation(n)


def delta_edges(scale: int, n_ops: int, seed: int):
    """Reproducible additive update stream: RMAT draws over the same
    2^scale vertex universe with an independent seed — shared with
    bench.py's dyn lane so the measured distribution IS the scripted
    one."""
    from bench import rmat_edges

    # rmat_edges draws scale*edge_factor-sized arrays; generate the
    # smallest RMAT batch covering n_ops and slice
    ef = max(1, -(-n_ops // (1 << scale)))
    _, src, dst = rmat_edges(scale, ef, seed)
    return src[:n_ops], dst[:n_ops]


if __name__ == "__main__":
    sys.exit(main())
