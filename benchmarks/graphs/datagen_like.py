"""A surrogate of the LDBC Datagen social graphs (`datagen-*-fb`): persons in
communities, as files `LoadGraph` parses.

The benchmark's own copy of `scripts/gen_datagen_like.py`'s construction
(docs/DATAGEN_SURROGATE.md), draw for draw.  The published files are not
here (no network; `datagen-9_0-fb` holds 1.05e9 edges), so what is kept is
what makes the family unlike Graph500's Kronecker graphs: lognormal-like
degrees with a hub cut-off in the low thousands instead of a power law,
planted communities that label propagation collapses onto, ids that carry
neither degree nor community, and a simple graph.

  * degrees: lognormal(`degree_sigma`) scaled to `mean_degree`, clipped to
    `degree_clip`, made even in sum;
  * communities: one per `vertices_per_community` ids, sizes Zipf(
    `community_zipf`) x `community_size_unit` clipped to `community_clip`
    and rescaled to the vertex count, membership shuffled over the ids;
  * wiring: the configuration model, every vertex `deg` stubs; a share
    `intra_share` of the stubs pairs inside the community (sorted by
    community and a random key, paired consecutively), the rest over the
    whole graph; self-loops and repeated pairs dropped, which costs about
    an eighth of the edges, most of them the hubs';
  * weights: `weights` = [low, high], integers low..high where
    `weight_dtype` is an integer type, else uniform on [low, high).

Every parameter comes from the configuration's `generator` block and none
is fixed here.  One process, whole arrays: int64 stubs, as the script draws
them, so a `generator_seed` gives the script's graph.  The edge list comes
out ordered by (smaller id, larger id).  Nothing here imports JAX.
"""

from __future__ import annotations

import os

import numpy as np


def draw(gen: dict, n: int):
    """The graph on `n` ids: (src int32, dst int32, w, community of each
    id, drawn degree of each id), `src < dst`, each pair once."""
    rng = np.random.default_rng(int(gen["generator_seed"]))
    sigma = float(gen["degree_sigma"])
    mu = np.log(float(gen["mean_degree"])) - sigma * sigma / 2
    lo, hi = gen["degree_clip"]
    deg = np.clip(rng.lognormal(mu, sigma, n), lo, hi).astype(np.int64)
    if deg.sum() % 2:  # an even stub count, so that the pairing closes
        deg[0] += 1

    n_comm = max(n // int(gen["vertices_per_community"]), 1)
    lo, hi = gen["community_clip"]
    sizes = np.clip(rng.zipf(float(gen["community_zipf"]), n_comm).astype(np.float64)
                    * float(gen["community_size_unit"]), lo, hi)
    sizes = np.maximum((sizes / sizes.sum() * n).astype(np.int64), 1)
    sizes[np.argmax(sizes)] += n - sizes.sum()  # rounding, onto the largest
    comm = np.repeat(np.arange(n_comm, dtype=np.int64), sizes)
    rng.shuffle(comm)

    stubs = np.repeat(np.arange(n, dtype=np.int64), deg)
    intra = rng.random(len(stubs)) < float(gen["intra_share"])
    ends = []
    for mask, by_community in ((intra, True), (~intra, False)):
        s = stubs[mask]
        s = s[:len(s) - len(s) % 2]
        if by_community:  # a pair may straddle two communities: an inter edge
            order = np.lexsort((rng.random(len(s)), comm[s]))
        else:
            order = rng.permutation(len(s))
        s = s[order]
        ends.append((s[0::2], s[1::2]))
    src = np.concatenate([u for u, _ in ends])
    dst = np.concatenate([v for _, v in ends])
    del stubs, intra, ends

    keep = src != dst
    lo, hi = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    first = np.unique(lo * n + hi, return_index=True)[1]
    src, dst = lo[first].astype(np.int32), hi[first].astype(np.int32)
    lo, hi = gen["weights"]
    dtype = np.dtype(gen["weight_dtype"])
    if np.issubdtype(dtype, np.integer):
        w = rng.integers(int(lo), int(hi) + 1, len(src), dtype=dtype)
    else:
        w = rng.uniform(lo, hi, len(src)).astype(dtype)
    return src, dst, w, comm, deg


def edges(gen: dict, scale: int):
    """The whole edge list in memory (for the plain references)."""
    return draw(gen, 1 << scale)[:3]


def write_files(gen: dict, scale: int, efile: str, vfile: str) -> dict:
    """Writes `efile` (`src dst w` lines) and `vfile` (every id 0..2^scale-1)
    and returns the counts.  Files appear under their final names only when
    whole."""
    import pandas as pd

    src, dst, w = edges(gen, scale)
    pd.DataFrame({"s": src, "d": dst, "w": w}).to_csv(
        efile + ".tmp", sep=" ", header=False, index=False)
    n = 1 << scale
    with open(vfile + ".tmp", "w") as f:
        f.write("\n".join(map(str, range(n))) + "\n")
    os.replace(vfile + ".tmp", vfile)
    os.replace(efile + ".tmp", efile)
    return {"vertices": n, "edges": len(src), "pull_entries": 2 * len(src),
            "efile_bytes": os.path.getsize(efile)}
