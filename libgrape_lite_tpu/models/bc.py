"""BC — betweenness centrality from a single source (Brandes).

Re-design of `examples/analytical_apps/bc/bc.h` (two-stage: forward BFS
accumulating shortest-path counts, then a level-by-level backward
dependency sweep pushed along out-edges to depth-1 predecessors;
`bc.h:162-178, 199-220`).

TPU formulation: both stages are `lax.while_loop`s over depth levels
inside one traced PEval, and a level of either is one sum over the
in-edges of a V-wide table that is zero off the level (`_level_sum`).
As a pull it is the pull PageRank's round makes (`_level_pull`): one
gather of the table by the CSR's neighbour stream
(`ops/segment.pull_gather`) and one fold by the CSR's offsets
(`segment_reduce(..., row_ptr=ie.indptr)`, the scan).  The level's
mask belongs to the *source* of an entry, so it is applied before the
gather, V-wide, and nothing E-wide is compared or divided:

  forward  d -> d+1:  pn_new[v] = Σ_{(u,v) in-edges} T[u],
                      T = where(depth == d, pn, 0);
                      newly-reached vertices get depth d+1 — path
                      counting and BFS fused,
  backward d+1 -> d:  delta[u] = pn[u] · Σ_{(v,u) in-edges} T[v],
                      T = where(depth == d+1, (1+delta)/pn, 0)
                      — identical update order to the reference's
                      accum/multiply form (`bc.h:205-211`); the
                      quotient is taken under the mask, so an
                      unreached vertex's pn of 0 divides nothing.

The forward loop ends on the level that reaches nothing, L + 1 sums
for L levels; the backward sweep starts at the deepest level that holds
a vertex, L sums.  A pull costs the whole graph whatever the level
holds, and a search's first and last levels hold a handful of rows: one
fragment's unbatched query on a graph large enough for it to pay
(`_DENSE_FLOOR`) carries a second arm, a push of the level's rows along
their own entries (`ops/segment.frontier_sum`), and a `lax.cond` a level
takes it where the level's rows and entries fit `_PUSH_ROWS` and
`_PUSH_ENTRIES` (two V-wide integer counts on the device).  The state is float64 under
`jax_enable_x64` and float32 otherwise (`models/pagerank.py`'s
convention: what the chip computes in is declared, not left to the
placement's narrowing).

Output value = the dependency (the reference's `centrality_value`), the
root's own included.  BC_STATS holds what the last extracted query's
sweep found (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats
from libgrape_lite_tpu.ops.segment import (
    frontier_rows, frontier_spans, frontier_sum, pull_gather, segment_reduce,
)
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_SENT = np.iinfo(np.int32).max

# The room of a level's push (`_level_sum`): a list of B rows whose
# adjacency holds C entries, both paid in full by every push, since
# shapes are static (models/bfs.py has the same two for its rounds).  A
# pull costs 0.94 ns a padded entry, 29.6 ms at `g500-bc.bc-key1`'s
# 31.4M; a push of 8,192 x 8,192 is seven B- or C-wide element gathers
# and a scatter-add at 58-62 us each and the dense comparisons at 44 us
# (the binary searches `frontier_rows` makes by default were 0.81 ms
# more), and one of 32,768 x 32,768 took 6.5 ms alone for 1.45 (PERF.md
# section 6, PR 51).
# From that cell's key the levels hold 1, 1, 455, 238,944, 399,379,
# 7,054, 30 and 1 rows and 1, 456, 1.13M, 28.1M, 2.12M, 7,297, 31 and 1
# entries: 8,192 of each take every level but depths 2 to 4, nine of
# the query's fifteen sums (seven of thirteen at scales 19 and 21); the
# 2,048 rows of BFS's rounds would leave depth 5 a pull in both sweeps,
# and no level lies between 8,192 and 32,768, which cost 0.241 s a
# query for 0.196.  Code counts as well: a runner's code is HBM at the
# peak and the two arms are 3.6 MB of it at 8,192, 4.6 MB at 32,768, of
# the 5.4 MB that cell's 1% allows.
_PUSH_ROWS = 8192
_PUSH_ENTRIES = 8192
# Under this many times C padded pull entries a pull is the cheaper sum
# at any level and the loops are built without the other arm: a push's
# gathers cost what a pull of about a million entries does (0.94 ns an
# entry; models/bfs.py's rounds, which sort, break even at 16 times C)
_DENSE_FLOOR = 128

# what the sweep of the last extracted query found: the levels under the
# root (the deepest vertex's depth), the vertices it reached, and the
# level sums the two loops ran for them (`levels + 1` forward, the last
# of which reaches nothing, `levels` backward) by the arm they took:
# `pulls + pushes == 2 * levels + 1`
BC_STATS = _FedStats(
    "bc", {"levels": 0, "reached": 0, "pulls": 0, "pushes": 0})


def _fits(rows, entries, budget):
    """Whether a level of `rows` rows that hold `entries` entries is a
    push under `budget`: the one rule of the device's choice
    (`_level_sum`) and of the host's recount (`BC.finalize`), on exact
    integers in both."""
    return (rows <= budget[0]) & (entries <= budget[1])


def _level_pull(ctx: StepContext, frag, table):
    """Row sums of `table` over the in-edges: a level's one pull.  `table`
    is this shard's `[vp]` block, zero off the level."""
    ie = frag.ie
    full = ctx.gather_state(table)
    contrib = pull_gather(
        full, ie.edge_nbr, ie.edge_mask, jnp.asarray(0, table.dtype))
    return segment_reduce(
        contrib, ie.edge_src, frag.vp, "sum", row_ptr=ie.indptr
    ).astype(table.dtype)


def _level_sum(ctx: StepContext, frag, table, at_level, budget):
    """Row sums of `table` over the in-edges, `table` zero off the rows
    `at_level` marks: `_level_pull`, or with a `budget` of (B rows, C
    entries) a `lax.cond` between it and the same sums pushed from the
    marked rows along their out-edges (`frontier_sum`; an undirected
    fragment's `oe` is its `ie`, read under the name the pull reads),
    taken where the level fits the budget.  Both counts are V-wide and
    dense; the list of the level's rows comes from the mask each time,
    so the loops carry nothing for it."""
    if budget is None:
        return _level_pull(ctx, frag, table)
    csr = frag.oe if frag.directed else frag.ie

    def push():
        front = frontier_rows(at_level, budget[0], search="compare_all")
        lo, count, _ = frontier_spans(front, csr.indptr)
        return frontier_sum(
            table, front, lo, count, csr.edge_nbr, budget[1])

    fits = _fits(at_level.sum(),
                 jnp.where(at_level, frag.out_degree, 0).sum(), budget)
    return lax.cond(fits, push, lambda: _level_pull(ctx, frag, table))


class BC(ParallelAppBase):
    load_strategy = LoadStrategy.kBothOutIn
    message_strategy = MessageStrategy.kSyncOnOuterVertex
    result_format = "float"
    # (B rows, C entries) where a level may push (`init_state`)
    push_budget = None

    def init_state(self, frag, source=0):
        fnum, vp = frag.fnum, frag.vp
        # the chip's float, declared (models/pagerank.py): without x64
        # JAX narrows float64 state on placement anyway, and the
        # gather kernel takes 32-bit tables only
        dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        depth = np.full((fnum, vp), _SENT, dtype=np.int32)
        pn = np.zeros((fnum, vp), dtype=dtype)
        from libgrape_lite_tpu.app.base import resolve_source

        pid = resolve_source(frag, source, "BC")
        if pid >= 0:
            depth[pid // vp, pid % vp] = 0
            pn[pid // vp, pid % vp] = 1.0
        delta = np.zeros((fnum, vp), dtype=dtype)
        # a level's push (`_level_sum`) reads the state as the whole
        # table and the entries as row ids: one fragment, no staged
        # edges beside the CSR, and a graph on which a pull costs more
        # than a budget-sized push (`init_state_batch` takes the offer
        # back from query lanes)
        offered = (
            fnum == 1 and getattr(frag, "dyn_overlay", None) is None
            and frag.dev.ie.edge_nbr.shape[-1]
            >= _DENSE_FLOOR * _PUSH_ENTRIES
        )
        self.push_budget = (_PUSH_ROWS, _PUSH_ENTRIES) if offered else None
        return {"depth": depth, "pn": pn, "delta": delta}

    def init_state_batch(self, frag, args_list):
        state = super().init_state_batch(frag, args_list)
        # lanes keep the pull: a lane's `cond` is a select under
        # `jax.vmap`, both arms every level
        self.push_budget = None
        return state

    def peval(self, ctx: StepContext, frag, state):
        sent = jnp.int32(_SENT)
        dt = state["pn"].dtype
        zero, one = jnp.asarray(0, dt), jnp.asarray(1, dt)

        def forward_round(carry):
            depth, pn, d, _ = carry
            # the pull inside keeps its own scopes: the innermost wins
            with jax.named_scope("grape.bc.forward"):
                at_d = depth == d
                acc = _level_sum(ctx, frag, jnp.where(at_d, pn, zero),
                                 at_d, self.push_budget)
                newly = jnp.logical_and(depth == sent, acc > 0)
                # vertices discovered exactly now get depth d+1 and
                # pathcount; the dense pull sums all depth-d
                # predecessors at once, on every shard's frontier, so
                # acc is already total
                depth2 = jnp.where(newly, d + 1, depth)
                pn2 = jnp.where(
                    jnp.logical_and(depth2 == d + 1, frag.inner_mask), acc, pn
                )
                n_new = ctx.sum(
                    jnp.logical_and(newly, frag.inner_mask).sum().astype(jnp.int32)
                )
            return depth2, pn2, d + 1, n_new

        def forward_cond(carry):
            _, _, d, n_new = carry
            return n_new > 0

        depth, pn, max_d, _ = lax.while_loop(
            forward_cond,
            forward_round,
            (state["depth"], state["pn"], jnp.int32(0), jnp.int32(1)),
        )

        def backward_round(carry):
            delta, d = carry
            with jax.named_scope("grape.bc.backward"):
                at_d = depth == d
                # every vertex at a depth has a path to it: pn >= 1
                # under the mask, and off it nothing is divided
                acc = _level_sum(ctx, frag, jnp.where(
                    at_d, (one + delta) / jnp.where(at_d, pn, one), zero),
                    at_d, self.push_budget)
                mine = jnp.logical_and(depth == d - 1, frag.inner_mask)
                delta2 = jnp.where(mine, pn * acc, delta)
            return delta2, d - 1

        def backward_cond(carry):
            _, d = carry
            return d > 0

        # the forward loop's last level reached nothing: max_d - 1 is the
        # deepest that holds a vertex, whose dependency stays 0
        delta, _ = lax.while_loop(
            backward_cond, backward_round,
            (jnp.zeros_like(state["delta"]), max_d - 1),
        )

        return {"depth": depth, "pn": pn, "delta": delta}, jnp.int32(0)

    def invariants(self, frag, state):
        # Brandes partials: shortest-path counts and dependencies are
        # finite and nonnegative (in_range(lo=0) rejects NaN — NaN >= 0
        # is False); depth is the BFS level or the untouched sentinel
        from libgrape_lite_tpu.guard.invariants import finite, in_range

        return [
            finite("pn"),
            in_range("pn", lo=0),
            finite("delta"),
            in_range("delta", lo=0),
            in_range("depth", lo=0, hi=_SENT),
        ]

    def inceval(self, ctx, frag, state):
        return state, jnp.int32(0)

    def finalize(self, frag, state):
        # BC_STATS from the extracted state: host side, after the query
        # and outside its wall (padding rows keep the sentinel)
        depth = np.asarray(state["depth"])
        reached = depth != _SENT
        at = depth[reached]
        levels = int(at.max()) if at.size else 0
        pushes = 0
        if self.push_budget is not None and at.size:
            # the arms the loops took, by the rule they took them by
            # (`_fits`): the forward loop summed from depths 0..levels,
            # the backward one from levels..1.  Entries are counted on
            # the levels whose rows fit alone: a few thousand rows
            rows = np.bincount(at)
            thin = (rows <= self.push_budget[0])[at]
            entries = np.bincount(
                at[thin], minlength=len(rows),
                weights=frag.host_oe[0].degree[reached[0]][thin])
            fits = _fits(rows, entries.astype(np.int64), self.push_budget)
            pushes = int(fits.sum() + fits[1:].sum())
        BC_STATS.update(
            levels=levels, reached=at.size,
            pulls=2 * levels + 1 - pushes, pushes=pushes)
        return np.asarray(state["delta"])
