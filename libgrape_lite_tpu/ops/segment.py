"""Segment reductions — the TPU ForEachEdge.

The reference parallelises per-edge work with its CPU ParallelEngine
(`grape/parallel/parallel_engine.h:32-719`) and the CUDA load-balancing
kernel catalog (`grape/cuda/parallel/parallel_engine.h:42-1444`,
cm/wm/cta/strict policies).  On TPU the same problem — distribute
variable-degree adjacency work evenly — is solved by *edge-major*
layout: per-edge values keyed by their row id, reduced with XLA segment
ops, which lower to sorted-scatter kernels the compiler tiles evenly.
A Pallas row-blocked variant lives alongside for the hot SpMV path.

The two halves of a pull carry `jax.named_scope` names, which reach the
device trace as the operations' `tf_op` (metadata only: the compiled
program is the same with and without them): `grape.pull.gather` on the
E-wide gather, `grape.pull.fold` on the segment fold.  A fusion takes
its root's name, so where XLA fuses the gather into the fold the whole
fusion reads as the fold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.ops as jops


def pull_gather(full, nbr, mask=None, fill=None, add=None, absent=None):
    """The E-wide half of a pull: one candidate per pull entry.

    `full[nbr]`, plus `add` (a per-entry weight or a constant) where
    given, with `fill` where `mask` is false or, where `absent` is
    given, where the neighbour holds that sentinel and proposes
    nothing."""
    with jax.named_scope("grape.pull.gather"):
        vals = full[nbr]
        ok = mask
        if absent is not None:
            ok = vals != absent if ok is None else jnp.logical_and(
                ok, vals != absent)
        if add is not None:
            vals = vals + add
        return vals if ok is None else jnp.where(ok, vals, fill)


def segment_reduce(values, segment_ids, num_rows: int, kind: str = "sum",
                   sorted_ids: bool = True):
    """Reduce `values` by `segment_ids` into `num_rows` rows.

    Ids equal to `num_rows` (padding convention) land in an overflow row
    that is sliced off — mirroring the reference's convention of routing
    invalid work to a trash slot rather than branching.

    `sorted_ids` defaults True because CSR edge arrays are built sorted
    by row (graph/csr.py) — XLA lowers sorted segment reductions to a
    cheaper scan-style kernel than the general scatter.
    """
    fn = {
        "sum": jops.segment_sum,
        "min": jops.segment_min,
        "max": jops.segment_max,
        "prod": jops.segment_prod,
    }[kind]
    with jax.named_scope("grape.pull.fold"):
        out = fn(
            values, segment_ids, num_segments=num_rows + 1,
            indices_are_sorted=sorted_ids,
        )
        return out[:num_rows]
