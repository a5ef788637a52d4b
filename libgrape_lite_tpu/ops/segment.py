"""Segment reductions — the TPU ForEachEdge.

The reference parallelises per-edge work with its CPU ParallelEngine
(`grape/parallel/parallel_engine.h:32-719`) and the CUDA load-balancing
kernel catalog (`grape/cuda/parallel/parallel_engine.h:42-1444`,
cm/wm/cta/strict policies).  On TPU the same problem — distribute
variable-degree adjacency work evenly — is solved by *edge-major*
layout: per-edge values keyed by their row id and reduced per row.
A Pallas row-blocked variant lives alongside for the hot SpMV path.

`segment_reduce` has two folds.  XLA's segment ops are scatters, and
on the TPU v5e a scatter steps through its updates one at a time, 8.7
ns an element at Graph500 scale 10 as at scale 21, sorted ids or not
(PERF.md, section 5): `indices_are_sorted` does not turn it into a
scan.  So where the rows are a CSR's, sorted and with their offsets at
hand, the fold is a scan written out in dense XLA: `_segmented_scan`
over tiles of 128 places, then each row's fold from the row's last
place: V sorted reads of the E-wide scanned stream.  XLA's gather
takes 18.1 ns a row for them at scale 21 (every read misses: a third
of a dense round), so where the values are 32 bits wide on the TPU
backend `ops/pallas_kernels.vmem_row_gather` reads them from slices of
the stream that pass through VMEM (`_row_end_gather`; ROW_END_STATS
counts which of the two a call took).  Everything else keeps the scatter:
ids that are not sorted and streams without offsets (the dyn overlay,
`exchange_base`, the 2-D tiles of `vc2d`, `bc`, `kcore`, 64-bit
lanes).  Query lanes under `jax.vmap` (the batched runners) fold as
their single queries do, one lane after another, wherever those
queries' gather is the kernel below; where it is not, the lanes of an
exact fold keep the scatter, into which XLA fuses their gather, and a
float sum's lanes scan, because its bits depend on the grouping and a
lane answers with its single query's bytes.  CDLP's count is a scan as
well, and never a scatter: `run_position` and `segment_top_label` work
on the (row, label) pairs its sort has just put in order, with the
CSR's offsets, which every round has (a caller without them gets them
by a binary search of the sorted rows, and XLA's gather of the row
ends: no round today).

The module makes a third choice, in `pull_gather`: how `full[nbr]` is
read.  XLA's gather also steps through its indices one at a time (8.6
ns an index at scale 10 as at scale 21, whatever the element is and
wherever it reads), while the table is a sixteenth of the chip's VMEM
or less.  So on the TPU backend a 1-D 32-bit table that fits half of
VMEM is gathered by `ops/pallas_kernels.vmem_gather`, which keeps the
table in VMEM for the length of the call and streams the indices
through it (0.80 ns an index).  Everything else keeps `full[nbr]`:
other backends, 64-bit tables, tables of rows, tables over the budget.
The query lanes of a batched call under `jax.vmap` take what a lane's
single call takes: the kernel, once a lane, where the lanes share
their indices (every caller's do), so that a lane runs its single
query's pull, gather and fold (`_kernel_table` is the one predicate
both `vmap` rules rest on).  What the choice observes is the backend,
the arguments' shapes and dtypes and the device's VMEM size; no option
selects it.  GATHER_STATS counts which gather each call took, as
FOLD_STATS does for the fold (docs/OBSERVABILITY.md).

Beside the pull, at the end of the module, a round that touches
neither every entry nor every row: `frontier_relax` pushes from a list
of the rows that improved last round, C entries at most, for the loops
that can carry such a list (worker/worker.py `_frontier_loop`).

The two halves of a pull carry `jax.named_scope` names, which reach the
device trace as the operations' `tf_op` (metadata only: the compiled
program is the same with and without them): `grape.pull.gather` on the
E-wide gather, by kernel or by XLA, `grape.pull.fold` on the segment
fold, by scan or by scatter.  A fusion takes its root's name, so where
XLA fuses the gather into the fold the whole fusion reads as the fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.ops as jops
from jax import lax
from jax.custom_batching import custom_vmap

from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats
from libgrape_lite_tpu.ops.pallas_kernels import (
    gather_table_budget,
    use_pallas,
    vmem_gather,
    vmem_row_gather,
)


# which gather each `pull_gather` call took, counted where it is
# decided: at trace time, once per call site per traced program
GATHER_STATS = _FedStats("gather", {"kernel": 0, "xla": 0})


def _recount(stats, took: list, to: str) -> None:
    """Move a call's one entry in `stats` from `took[0]` to `to`: once,
    however often a `vmap` rule runs for the call (a loop's batching
    rule may run it again)."""
    if took[0] != to:
        stats[took[0]] -= 1
        stats[to] += 1
        took[0] = to


def _kernel_values(dtype) -> bool:
    """Whether values of `dtype` are the gather kernels' kind here:
    the TPU backend, 32 bits."""
    return use_pallas() and jnp.dtype(dtype).itemsize == 4


def _kernel_table(dtype, rows: int) -> bool:
    """Whether a 1-D table of `rows` values of `dtype` is one
    `pallas_kernels.vmem_gather` keeps in VMEM: the kernels' kind of
    value, the kernel's VMEM budget.  A single call's gather and both
    `vmap` rules (the gather's and the fold's) rest on it; everything
    in it is read off shapes, dtypes and the backend at trace time."""
    return _kernel_values(dtype) and 0 < rows * 4 <= gather_table_budget()


def _kernel_gathers(full, nbr) -> bool:
    """Whether `full[nbr]` goes through `pallas_kernels.vmem_gather`:
    a 1-D table the kernel takes (`_kernel_table`) and a 1-D int32
    stream."""
    return (
        full.ndim == 1 and nbr.ndim == 1 and nbr.shape[0] > 0
        and nbr.dtype == jnp.int32
        and _kernel_table(full.dtype, full.size)
    )


def _kernel_gather(stats, kernel):
    """The gather of one call that chose `kernel` (`vmem_gather` for a
    pull's `full[nbr]`, `vmem_row_gather` for a fold's row ends), with
    its own rule under `jax.vmap`.  Made anew for each call, because it
    carries the call's entry in `stats`."""
    took = ["kernel"]
    stats["kernel"] += 1

    @custom_vmap
    def gather(full, nbr):
        return kernel(full, nbr)

    @gather.def_vmap
    def lanes(axis_size, in_batched, full, nbr):
        if in_batched[1]:
            # lanes that bring their own indices (no caller does) keep
            # XLA's gather, lane by lane
            _recount(stats, took, "xla")
            axes = tuple(0 if b else None for b in in_batched)
            return jax.vmap(lambda f, i: f[i], in_axes=axes)(full, nbr), True
        # Query lanes over one CSR (the batched runner): the single
        # query's kernel, one lane after another.  A lane's table is
        # the 1-D one `_kernel_gathers` saw, resident in VMEM for its
        # call, and its candidates are one dense row of the
        # `[lanes, Ep]` block; XLA's own vmapped gather writes that
        # block lanes minor, padded 32-fold (PERF.md section 6, PR 25
        # (3)).  One traced body in a loop, not a copy a lane: code is
        # HBM (PR 40).  The call stays `kernel` in its stats.
        return lax.map(lambda f: gather(f, nbr), full), True

    return gather


def pull_gather(full, nbr, mask=None, fill=None, add=None, absent=None):
    """The E-wide half of a pull: one candidate per pull entry.

    `full[nbr]`, plus `add` (a per-entry weight or a constant) where
    given, with `fill` where `mask` is false or, where `absent` is
    given, where the neighbour holds that sentinel and proposes
    nothing.

    Two gathers, chosen by what the call can see (`_kernel_gathers`):
    the Pallas kernel that keeps the table in VMEM, or XLA's gather.
    Query lanes under `jax.vmap` that share `nbr` take the kernel one
    lane after another; lanes with indices of their own take XLA's.
    Both move the same bits.  GATHER_STATS counts which one a call
    took."""
    with jax.named_scope("grape.pull.gather"):
        if _kernel_gathers(full, nbr):
            vals = _kernel_gather(GATHER_STATS, vmem_gather)(full, nbr)
        else:
            GATHER_STATS["xla"] += 1
            vals = full[nbr]
        ok = mask
        if absent is not None:
            ok = vals != absent if ok is None else jnp.logical_and(
                ok, vals != absent)
        if add is not None:
            vals = vals + add
        return vals if ok is None else jnp.where(ok, vals, fill)


# places in one tile of the segmented scan: the lanes of the chip's
# vector registers, and the unit fragment/edgecut.py rounds a CSR's Ep
# up to
SCAN_TILE = 128

_FOLDS = {
    # kind: (scatter, combine, identity of an empty row as the scatter
    # gives it, given the dtype)
    "sum": (jops.segment_sum, jnp.add, lambda dt: 0),
    "prod": (jops.segment_prod, jnp.multiply, lambda dt: 1),
    "min": (jops.segment_min, jnp.minimum,
            lambda dt: jnp.inf if jnp.issubdtype(dt, jnp.floating)
            else jnp.iinfo(dt).max),
    "max": (jops.segment_max, jnp.maximum,
            lambda dt: -jnp.inf if jnp.issubdtype(dt, jnp.floating)
            else jnp.iinfo(dt).min),
}

# which fold each `segment_reduce` call took, counted where it is
# decided: at trace time, once per call site per traced program
FOLD_STATS = _FedStats("fold", {"scan": 0, "scatter": 0})


def _shift(x, d: int, fill):
    """`x` moved `d` places up its last axis, `fill` in the first `d`."""
    cfg = [(0, 0, 0)] * (x.ndim - 1) + [(d, -d, 0)]
    return lax.pad(x, jnp.asarray(fill, x.dtype), cfg)


def _segmented_scan(values, ids, combine, identity):
    """Inclusive scan of the 1-D `values` that restarts where the
    sorted, non-negative `ids` change.

    Tiles of SCAN_TILE places are scanned side by side in
    log2(SCAN_TILE) dense steps: at distance d a place takes in the
    one d below it when both hold one id (the ids are sorted, so the
    places between do too).  Each tile's last place is then a partial
    of the row that leaves the tile; the scan of those partials, one
    level up, is what every tile still lacks from the tiles before it,
    and it is folded into the places of the tile's first row."""
    n = ids.shape[0]
    pad = -n % SCAN_TILE
    if pad:
        # only above the first level: a CSR's Ep is whole tiles
        values = lax.pad(values, jnp.asarray(identity, values.dtype),
                         [(0, pad, 0)])
        ids = lax.pad(ids, ids[-1], [(0, pad, 0)])
    v = values.reshape(-1, SCAN_TILE)
    i = ids.reshape(-1, SCAN_TILE)
    d = 1
    while d < SCAN_TILE:
        v = jnp.where(i == _shift(i, d, -1),
                      combine(v, _shift(v, d, identity)), v)
        d *= 2
    if v.shape[0] > 1:
        tail_i = i[:, -1]
        above = _segmented_scan(v[:, -1], tail_i, combine, identity)
        carry_i = _shift(tail_i, 1, -1)[:, None]
        carry = _shift(above, 1, identity)[:, None]
        v = jnp.where(i == carry_i, combine(carry, v), v)
    return v.reshape(-1)[:n]


def _segmented_scan_pair(values, ids, wins, identity):
    """`_segmented_scan` of a pair of 1-D arrays under a total order:
    `wins(x, y)` says where the pair `y` beats the pair `x`, and a
    place keeps the best pair of its row so far.  `identity` is the
    pair that every other loses to.  Same tiles, same levels."""
    n = ids.shape[0]
    pad = -n % SCAN_TILE
    if pad:
        values = tuple(
            lax.pad(v, jnp.asarray(e, v.dtype), [(0, pad, 0)])
            for v, e in zip(values, identity))
        ids = lax.pad(ids, ids[-1], [(0, pad, 0)])
    v = tuple(x.reshape(-1, SCAN_TILE) for x in values)
    i = ids.reshape(-1, SCAN_TILE)

    def take(v, other, same_row):
        took = jnp.logical_and(same_row, wins(v, other))
        return tuple(jnp.where(took, y, x) for x, y in zip(v, other))

    d = 1
    while d < SCAN_TILE:
        below = tuple(_shift(x, d, e) for x, e in zip(v, identity))
        v = take(v, below, i == _shift(i, d, -1))
        d *= 2
    if i.shape[0] > 1:
        tail_i = i[:, -1]
        above = _segmented_scan_pair(
            tuple(x[:, -1] for x in v), tail_i, wins, identity)
        carry_i = _shift(tail_i, 1, -1)[:, None]
        carry = tuple(_shift(a, 1, e)[:, None]
                      for a, e in zip(above, identity))
        v = take(v, carry, i == carry_i)
    return tuple(x.reshape(-1)[:n] for x in v)


def _scatter_fold(values, segment_ids, num_rows: int, kind: str,
                  sorted_ids: bool):
    out = _FOLDS[kind][0](
        values, segment_ids, num_segments=num_rows + 1,
        indices_are_sorted=sorted_ids,
    )
    return out[:num_rows]


# which gather read each scan's row ends, counted where it is decided:
# at trace time, once per call site per traced program
ROW_END_STATS = _FedStats("row_ends", {"kernel": 0, "xla": 0})


def _row_end_gather(dtype, offsets: bool = True):
    """How one call reads its row ends out of its scanned stream of
    `dtype`, V sorted reads of an E-wide table (the module's docstring
    has the prices): `pallas_kernels.vmem_row_gather` where the values
    are the kernels' kind (`_kernel_values`), query lanes under
    `jax.vmap` one after another; XLA's gather everywhere else, and
    for a caller whose `row_ptr` is not a CSR's `offsets` but its own
    search of the ids.  Both move the same bits.  Made once for each
    call, however often its fold is traced (`_scan_fold`), because it
    carries the call's entry in ROW_END_STATS."""
    if offsets and _kernel_values(dtype):
        return _kernel_gather(ROW_END_STATS, vmem_row_gather)
    ROW_END_STATS["xla"] += 1
    return lambda scanned, at: scanned.at[at].get(
        mode="promise_in_bounds", indices_are_sorted=True)


def _row_ends(scanned, row_ptr, num_rows: int, empty, gather):
    """Each row's fold out of the scanned stream: what stands at the
    row's last place, read by `gather` (`_row_end_gather`'s), `empty`
    for a row with no place."""
    # an empty row has no last place
    last = row_ptr[1:num_rows + 1] - 1
    out = gather(scanned, jnp.maximum(last, 0))
    return jnp.where(last >= row_ptr[:num_rows], out,
                     jnp.asarray(empty, scanned.dtype))


def _scan_rows(values, segment_ids, row_ptr, num_rows: int, kind: str,
               ends):
    """The scan fold of one lane: `_segmented_scan`, then each row's
    fold from the row's last place, read by `ends(dtype)`."""
    _, combine, ident = _FOLDS[kind]
    identity = ident(values.dtype)
    scanned = _segmented_scan(values, segment_ids, combine, identity)
    return _row_ends(scanned, row_ptr, num_rows, identity,
                     ends(scanned.dtype))


def _grouping_shows(kind: str, dtype) -> bool:
    """Whether a fold's bits depend on how its operands are grouped:
    float sums and products do; integer, min and max folds are exact
    under any grouping."""
    return kind in ("sum", "prod") and jnp.issubdtype(dtype, jnp.inexact)


def _scan_fold(num_rows: int, kind: str):
    """The fold of one `segment_reduce` call that came with offsets:
    the scan, with its own rule under `jax.vmap`.  Made anew for each
    call, because it carries the call's entries in FOLD_STATS and in
    ROW_END_STATS (the latter is the single query's choice, and stays
    where the rule below sends the lanes to the scatter).

    Under `jax.vmap` (query lanes over one CSR) the rule asks the
    question the gather's rule answered, of what it can see itself:
    `_kernel_table` of the values' dtype and `num_rows`, which on one
    fragment is the length of the table the lanes' candidates were
    gathered from.  Where it holds the lanes' gather was the kernel,
    their candidates stand lanes major, and each lane folds by its
    single query's scan.  Where it does not, XLA's vmapped gather
    feeds the fold, and an exact fold keeps the scatter XLA fuses that
    gather into: ahead of a scan its `[Ep, lanes]` block would stand in
    memory lanes minor, padded 32-fold.  The edge the fold cannot see:
    on several fragments the gathered table is `fnum * vp` long (or
    the mirror exchange's compact one), so where `vp * 4 <= budget <
    full.size * 4` the gather is XLA's and the lanes scan behind it
    all the same, PR 25's measured loss (249.5 ms a four-lane pull for
    the scatter's 122.3, and 4.4 GB of block at Ep 8.4M; PERF.md
    section 6).  That takes more than 16M vertices in all on a v5e;
    no cell and no test holds such a graph."""
    took = ["scan"]
    FOLD_STATS["scan"] += 1
    # the call's one gather of row ends, however often it is traced
    ends = functools.cache(_row_end_gather)

    @custom_vmap
    def fold(values, segment_ids, row_ptr):
        return _scan_rows(values, segment_ids, row_ptr, num_rows, kind,
                          ends)

    @fold.def_vmap
    def lanes(axis_size, in_batched, values, segment_ids, row_ptr):
        if in_batched[1] or in_batched[2]:
            raise NotImplementedError(
                "segment_reduce: lanes share segment_ids and row_ptr")
        if _kernel_table(values.dtype, num_rows):
            # one lane after another, as the gather's rule wrote them:
            # each lane runs its single query's operations on a dense
            # row of the `[lanes, Ep]` block, whatever the fold's kind
            return lax.map(
                lambda v: fold(v, segment_ids, row_ptr), values), True
        if _grouping_shows(kind, values.dtype):
            # a float sum's lanes scan too, each with the arithmetic of
            # its own single query, so that a lane keeps that query's
            # bits (docs/SERVING.md)
            return jax.vmap(
                lambda v: _scan_rows(v, segment_ids, row_ptr, num_rows,
                                     kind, ends)
            )(values), True
        # no kernel for these lanes' tables (other backends, 64-bit
        # values, tables over the budget): XLA gathers all lanes of an
        # entry at once and fuses that into the scatter
        _recount(FOLD_STATS, took, "scatter")
        return jax.vmap(
            lambda v: _scatter_fold(v, segment_ids, num_rows, kind, True)
        )(values), True

    return fold


def segment_reduce(values, segment_ids, num_rows: int, kind: str = "sum",
                   sorted_ids: bool = True, row_ptr=None):
    """Reduce `values` by `segment_ids` into `num_rows` rows.

    Ids equal to `num_rows` (padding convention) land in an overflow row
    that is sliced off — mirroring the reference's convention of routing
    invalid work to a trash slot rather than branching.

    Two folds, chosen by what the caller hands over.  With `row_ptr`
    (a CSR's `indptr`: `segment_ids` sorted, row r at places
    `row_ptr[r]:row_ptr[r + 1]`, padding behind the last row) the
    fold is a tile-segmented scan and one V-wide gather of the row
    ends: dense E-wide work, no scatter.  Float sums then group by
    tile, not in stream order; integer, min and max folds are exact
    either way.  Without it the fold is `jax.ops.segment_*`, a
    scatter, for ids that are not sorted (`sorted_ids=False`) or that
    come without offsets.  Under `jax.vmap` (query lanes over one CSR)
    the lanes scan one after another where their gather was the
    kernel's; elsewhere a fold that is exact under any grouping goes
    back to the scatter and a float sum scans as its single query
    does, so a lane's answer has that query's bytes every way (see
    `_scan_fold`).  FOLD_STATS counts which fold a call took.
    """
    if row_ptr is not None and not sorted_ids:
        raise ValueError(
            "segment_reduce: row_ptr describes sorted segment_ids")
    with jax.named_scope("grape.pull.fold"):
        if row_ptr is not None:
            return _scan_fold(num_rows, kind)(values, segment_ids, row_ptr)
        FOLD_STATS["scatter"] += 1
        return _scatter_fold(values, segment_ids, num_rows, kind,
                             sorted_ids)


def run_position(segment_ids, label):
    """Each place's position, counted from 1, in its run of equal
    `(segment_ids, label)` pairs, which are in lexicographic order.

    A run opens where either half of the pair changes.  The opening
    place comes down to the run's places by a max-scan of the opening
    positions (positions only grow, so the latest opening is the
    largest); the scan restarts with the sorted `segment_ids`, which
    changes nothing, since a row's first place opens a run, and lets
    `_segmented_scan` do it.  The position is monotone inside a run
    and equals the run's length at its last place."""
    first = jnp.logical_or(segment_ids != _shift(segment_ids, 1, -1),
                           label != _shift(label, 1, 0))
    idx = jnp.arange(segment_ids.shape[0], dtype=jnp.int32)
    opened = _segmented_scan(jnp.where(first, idx, 0), segment_ids,
                             jnp.maximum, 0)
    return idx - opened + 1


def _more_then_smaller(x, y):
    """Where the pair `y = (count, label)` beats `x`: by the larger
    count, then by the smaller label."""
    (cx, lx), (cy, ly) = x, y
    return jnp.logical_or(cy > cx, jnp.logical_and(cy == cx, ly < lx))


def segment_top_label(count, label, segment_ids, num_rows: int,
                      row_ptr=None):
    """Per row, the smallest `label` among the places whose `count` is
    the row's largest; the label dtype's largest value for a row with
    no place.  `count` is positive.

    `segment_ids` are sorted, padding (`num_rows`) last.  One scan of
    the pair `(count, label)` under the order "larger count, then
    smaller label", then each row's answer from its last place: exact
    under any grouping, no scatter.  `row_ptr` is the rows' offsets
    where the caller has them (a whole CSR's `indptr`); without it
    they are found in the sorted ids by a V-wide binary search.
    FOLD_STATS counts the call as a scan."""
    FOLD_STATS["scan"] += 1
    empty = jnp.iinfo(label.dtype).max
    with jax.named_scope("grape.pull.fold"):
        offsets = row_ptr is not None
        if not offsets:
            row_ptr = jnp.searchsorted(
                segment_ids,
                jnp.arange(num_rows + 1, dtype=segment_ids.dtype))
        _, best = _segmented_scan_pair(
            (count, label), segment_ids, _more_then_smaller, (0, empty))
        return _row_ends(best, row_ptr, num_rows, empty,
                         _row_end_gather(label.dtype, offsets))


# ---- a round whose work follows its frontier -----------------------------
#
# The folds above touch every entry and every row whatever a round has
# to do.  A monotone min relaxation (BFS, SSSP, and by the same rule
# WCC) needs only the rows that improved last round to propose again:
# `frontier_spans`, `frontier_relax` and `frontier_rows` are that round
# at static shapes, a list of at most B rows whose adjacency holds at
# most C entries, for one fragment's unbatched state.  Nothing in them
# is wider than C but the update of the V-wide values in place, and
# `frontier_rows`, which a loop runs where it turns from dense rounds to
# these and where it refills the list from the state (models/bfs.py,
# models/sssp.py, worker/worker.py `_frontier_loop`).

FRONTIER_SCOPE = "grape.frontier.compact"
# the threshold's step and the refill that follows it (a loop whose
# list holds the rows under a threshold only: SSSP's near/far)
ADVANCE_SCOPE = "grape.frontier.advance"


def _at(table, idx):
    """`table[idx]` for indices the caller keeps in bounds."""
    return table.at[idx].get(mode="promise_in_bounds")


def _block_at(block, idx):
    """`_at` for a CSR's array, which may come as a shard's block
    `[1, N]`, its leading axis unsqueezed: on the chip squeezing such a
    block is a copy of it into another tiling (`reduce.33`, PERF.md
    section 6, PR 39), which a round that reads a few thousand of its
    entries must not pay."""
    if block.ndim == 2:
        return block.at[0, idx].get(mode="promise_in_bounds")
    return _at(block, idx)


def frontier_spans(front, row_ptr):
    """Where the listed rows' entries lie in the CSR `row_ptr` belongs
    to: per place of `front` (row ids, the list padded with the row
    count) the row's first entry and its entry count, 0 at a pad, and
    the count of them all.  B-wide: one gather of the offsets' pairs.
    `row_ptr` may be a shard's block `[1, V + 1]` (see `_block_at`)."""
    num_rows = row_ptr.shape[-1] - 1
    lead = row_ptr.ndim - 1
    with jax.named_scope("grape.pull.gather"):
        row = jnp.minimum(front, num_rows - 1)
        ends = jax.vmap(lambda r: lax.dynamic_slice(
            row_ptr, (jnp.zeros_like(r),) * lead + (r,),
            (1,) * lead + (2,)).reshape(2))(row)
        lo = ends[:, 0]
        count = jnp.where(front < num_rows, ends[:, 1] - lo, 0)
    with jax.named_scope(FRONTIER_SCOPE):
        return lo, count, count.sum()


def _bits(x, dtype):
    """`x`'s 32 bits as `dtype`: nothing where that is what it is."""
    return x if x.dtype == dtype else lax.bitcast_convert_type(x, dtype)


def frontier_relax(values, front, lo, count, edge_nbr, entries: int,
                   add=1, absent=None, below=None):
    """One push round of a min relaxation from the rows `front` lists:
    `(values', front', active)`.

    `lo`, `count` are `frontier_spans`' and the entries they cover are
    at most `entries` (C; the caller's to see to).  The listed rows'
    entries are laid out in C slots: each row's offset is the counts'
    running sum, the row that opens at a slot is scattered there (B
    updates) and comes down to the row's other slots by a running
    maximum, as `run_position`'s openers do.  A slot then reads its
    row's value and first entry (one gather of pairs from the B-wide
    table; two gathers where the value is 64 bits wide), its neighbour
    `edge_nbr[entry]` and what the neighbour holds; the candidate is
    the row's value plus `add`, a constant or a value an entry (an
    array over the CSR's entries, read at the C slots: a weighted
    push), nothing where the row holds `absent`; `edge_nbr` and such an
    `add` may be a shard's block `[1, Ep]` (see `_block_at`).
    Candidates that improve a neighbour are folded into
    `values` by one `.at[].min` of C updates (pads fall out of bounds
    and are dropped, the trash row of `segment_reduce`), and the
    neighbours that improved, each once and ascending, are the next
    list: a sort of the C targets, the first of each run kept, and a
    second sort that moves those to the front.  Given `below`, a scalar
    threshold, the next list keeps only the improved rows whose new
    value is under it (a row's new value is its least candidate, so it
    is under the threshold where any candidate that improves it is);
    the others are improved all the same and left to the caller, who
    finds them by their values when the threshold moves on.
    `active` counts the list: without a threshold the rows a dense
    round of the same relaxation would find changed.
    Where `active` exceeds the list's places the list is cut short and
    the caller's next round has to be a dense one."""
    rows, cap = values.shape[0], front.shape[0]
    slot = jnp.arange(entries, dtype=jnp.int32)
    with jax.named_scope(FRONTIER_SCOPE):
        upto = jnp.cumsum(count)
        first = upto - count
        opens = jnp.zeros((entries,), jnp.int32).at[
            jnp.where(count > 0, first, entries)
        ].max(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        owner = lax.cummax(opens)
        live = slot < upto[-1]
    with jax.named_scope("grape.pull.gather"):
        held = _at(values, jnp.minimum(front, rows - 1))
        # the row's first entry and its value in one gather where the
        # value is 32 bits wide (its bits ride as `int32`): what a
        # gather costs here is its indices (PERF.md section 6, PR 40)
        if held.dtype.itemsize == 4:
            pair = _at(jnp.stack([lo - first, _bits(held, jnp.int32)],
                                 axis=1), owner)
        else:
            pair = _at(lo - first, owner), _at(held, owner)

        def column(i):
            # sliced anew at each use: BFS's lowered text, which reads
            # the pairs' second column twice, stays what it was
            return pair[i] if isinstance(pair, tuple) else pair[:, i]

        entry = jnp.where(live, column(0) + slot, 0)
        nbr = _block_at(edge_nbr, entry)
        cand = _bits(column(1), held.dtype) + (
            add if jnp.ndim(add) == 0 else _block_at(add, entry))
        if absent is not None:
            live = jnp.logical_and(
                live, _bits(column(1), held.dtype) != absent)
        old = _at(values, jnp.minimum(nbr, rows - 1))
        target = jnp.where(
            jnp.logical_and(live, cand < old), nbr, rows)
    with jax.named_scope("grape.pull.fold"):
        values = values.at[target].min(cand, mode="drop")
    with jax.named_scope(FRONTIER_SCOPE):
        if below is not None:
            target = jnp.where(cand < below, target, rows)
        hit = lax.sort(target, is_stable=False)
        opener = jnp.logical_and(hit != _shift(hit, 1, -1), hit < rows)
    with jax.named_scope("grape.app.update"):
        active = opener.sum().astype(jnp.int32)
    with jax.named_scope(FRONTIER_SCOPE):
        front = lax.sort(jnp.where(opener, hit, rows), is_stable=False)[:cap]
        if entries < cap:
            front = jnp.pad(front, (0, cap - entries), constant_values=rows)
    return values, front, active


def frontier_rows(mask, cap: int, scope: str = FRONTIER_SCOPE):
    """The first `cap` set rows of the V-wide `mask`, ascending, the
    list padded with the row count: what a loop needs where it turns
    from dense rounds to `frontier_relax`, and where it refills its
    list from the state (under `scope`, so that a trace tells the two).

    No V-wide gather, scatter or sort: the set rows are counted a tile
    of SCAN_TILE rows at a time (dense), the tiles' running sum is
    searched for each place of the list (`cap` binary searches of V /
    128 sums), the tile found is read whole (one gather of `cap` rows of
    the mask) and the place's row in it is where the tile's own running
    count reaches what the tiles before it lack."""
    rows = mask.shape[0]
    with jax.named_scope(scope):
        pad = -rows % SCAN_TILE
        tiles = (jnp.pad(mask, (0, pad)) if pad else mask).reshape(
            -1, SCAN_TILE).astype(jnp.int32)
        upto = jnp.cumsum(tiles.sum(axis=1))
        want = jnp.arange(1, cap + 1, dtype=jnp.int32)
        tile = jnp.minimum(jnp.searchsorted(upto, want).astype(jnp.int32),
                           upto.shape[0] - 1)
        before = jnp.where(tile > 0, _at(upto, jnp.maximum(tile - 1, 0)), 0)
        # a tile's running count by one product with a triangle of ones
        # (exact: counts to 128 in f32); a `cumsum` along the lanes is
        # a reduce-window that costs 1.9 MB of code here, and code is
        # HBM (PERF.md section 6, PR 40)
        upper = jnp.triu(jnp.ones((SCAN_TILE, SCAN_TILE), jnp.float32))
        inside = jnp.dot(_at(tiles, tile).astype(jnp.float32), upper,
                         precision=lax.Precision.HIGHEST)
        lane = (inside < (want - before).astype(jnp.float32)[:, None]).sum(
            axis=1)
        return jnp.where(want <= upto[-1],
                         tile * SCAN_TILE + lane.astype(jnp.int32),
                         rows).astype(jnp.int32)
