"""Graph500 Kronecker (R-MAT) edge lists from a seed, as files `LoadGraph` parses.

The benchmark's own copy of `bench.py::rmat_edges` and
`scripts/gen_rmat.py::edge_weights / write_edge_file` (one quadrant draw
per id bit, integer weights, `src dst w` lines), with the two steps of the
Graph500 specification's generator that the originals leave out: the
vertex labels are permuted at random and the edge tuples are shuffled.
Without the permutation id 0 is the largest hub and a vertex's degree
falls with the number of set bits of its id, so a partitioner that cuts
the id range into blocks gives its first block (a+b)^2 of all entries.
The published graph500-* files carry scrambled ids; so do these.

Every parameter comes from the configuration's `generator` block (`a`,
`b`, `c`, `d`, `edge_factor`, `generator_seed`, `weights`), so what a
configuration states is what is drawn.  The edges are drawn in fixed
chunks of `CHUNK`, each from its own `SeedSequence([seed, chunk])`, so
that chunks can be made and written by several processes: the graph
depends on the block and the scale only, never on the number of
processes.  The shuffle is within a chunk; the draws are independent and
identically distributed, so the order across chunks is exchangeable as
drawn.  Nothing here imports JAX.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
import shutil

import numpy as np

CHUNK = 1 << 21  # edges per chunk: the unit of seeding and of parallel work
MAX_PROCESSES = 12
LABELS = 1 << 30  # SeedSequence word of the label permutation: no chunk has it


def label_permutation(gen: dict, scale: int) -> np.ndarray:
    """The id each drawn label is written as: a seeded permutation of
    0..2^scale-1 (Graph500's `randperm(N)`)."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(gen["generator_seed"]), LABELS]))
    return rng.permutation(1 << scale).astype(np.int32)


def draw_chunk(gen: dict, scale: int, chunk: int, count: int, perm: np.ndarray):
    """`count` edges of chunk `chunk`: (src int32, dst int32, w uint8),
    relabelled through `perm` and shuffled."""
    a, b, c, d = (float(gen[k]) for k in "abcd")
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"generator: a + b + c + d = {a + b + c + d}, not 1")
    lo, hi = gen["weights"]
    rng = np.random.default_rng(
        np.random.SeedSequence([int(gen["generator_seed"]), chunk]))
    src = np.zeros(count, dtype=np.int32)
    dst = np.zeros(count, dtype=np.int32)
    for _ in range(scale):
        r = rng.random(count, dtype=np.float32)
        src_bit = r >= np.float32(a + b)
        dst_bit = ((r >= np.float32(a)) & ~src_bit) | (r >= np.float32(a + b + c))
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    w = rng.integers(int(lo), int(hi) + 1, count, dtype=np.uint8)
    order = rng.permutation(count)
    return perm[src[order]], perm[dst[order]], w[order]


def chunk_counts(gen: dict, scale: int) -> list:
    total = (1 << scale) * int(gen["edge_factor"])
    return [min(CHUNK, total - lo) for lo in range(0, total, CHUNK)]


def edges(gen: dict, scale: int):
    """The whole edge list in memory (for the plain references)."""
    perm = label_permutation(gen, scale)
    parts = [draw_chunk(gen, scale, i, c, perm)
             for i, c in enumerate(chunk_counts(gen, scale))]
    return tuple(np.concatenate(col) for col in zip(*parts))


def _write_chunk(gen: dict, scale: int, chunk: int, count: int, path: str) -> int:
    import pandas as pd

    src, dst, w = draw_chunk(gen, scale, chunk, count, label_permutation(gen, scale))
    pd.DataFrame({"s": src, "d": dst, "w": w}).to_csv(
        path, sep=" ", header=False, index=False)
    return count


def write_files(gen: dict, scale: int, efile: str, vfile: str) -> dict:
    """Writes `efile` (`src dst w` lines) and `vfile` (every id 0..2^scale-1,
    isolated ones included) and returns the counts.  Files appear under
    their final names only when whole."""
    counts = chunk_counts(gen, scale)
    parts = [f"{efile}.part{i}" for i in range(len(counts))]
    jobs = [(gen, scale, i, c, p) for i, (c, p) in enumerate(zip(counts, parts))]
    workers = min(len(jobs), MAX_PROCESSES, os.cpu_count() or 1)
    if workers <= 1:
        for job in jobs:
            _write_chunk(*job)
    else:
        # spawn, not fork: the parent holds JAX's threads and the chip.
        # The children import NumPy and pandas only
        ctx = multiprocessing.get_context("spawn")
        with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
            for fut in [pool.submit(_write_chunk, *job) for job in jobs]:
                fut.result()
    with open(efile + ".tmp", "wb") as out:
        for p in parts:
            with open(p, "rb") as f:
                shutil.copyfileobj(f, out, 1 << 24)
            os.remove(p)
    n = 1 << scale
    with open(vfile + ".tmp", "w") as f:
        f.write("\n".join(map(str, range(n))) + "\n")
    os.replace(vfile + ".tmp", vfile)
    os.replace(efile + ".tmp", efile)
    return {"vertices": n, "edges": sum(counts), "pull_entries": 2 * sum(counts),
            "efile_bytes": os.path.getsize(efile)}
