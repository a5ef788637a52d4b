"""BC — betweenness centrality from a single source (Brandes).

Re-design of `examples/analytical_apps/bc/bc.h` (two-stage: forward BFS
accumulating shortest-path counts, then a level-by-level backward
dependency sweep pushed along out-edges to depth-1 predecessors;
`bc.h:162-178, 199-220`).

TPU formulation: both stages are `lax.while_loop`s over depth levels
inside one traced PEval, and a level of either is one pull, the pull
PageRank's round makes (`_level_pull`): one V-wide table that is zero
off the level, one gather of it by the CSR's neighbour stream
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

The forward loop ends on the level that reaches nothing, L + 1 pulls
for L levels; the backward sweep starts at the deepest level that holds
a vertex, L pulls.  The state is float64 under `jax_enable_x64` and
float32 otherwise (`models/pagerank.py`'s convention: what the chip
computes in is declared, not left to the placement's narrowing).

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
from libgrape_lite_tpu.ops.segment import pull_gather, segment_reduce
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_SENT = np.iinfo(np.int32).max

# what the sweep of the last extracted query found: the levels under the
# root (the deepest vertex's depth), the vertices it reached, and the
# level pulls the two loops ran for them (`levels + 1` forward, the last
# of which reaches nothing, `levels` backward)
BC_STATS = _FedStats("bc", {"levels": 0, "reached": 0, "pulls": 0})


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


class BC(ParallelAppBase):
    load_strategy = LoadStrategy.kBothOutIn
    message_strategy = MessageStrategy.kSyncOnOuterVertex
    result_format = "float"

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
        return {"depth": depth, "pn": pn, "delta": delta}

    def peval(self, ctx: StepContext, frag, state):
        sent = jnp.int32(_SENT)
        dt = state["pn"].dtype
        zero, one = jnp.asarray(0, dt), jnp.asarray(1, dt)

        def forward_round(carry):
            depth, pn, d, _ = carry
            # the pull inside keeps its own scopes: the innermost wins
            with jax.named_scope("grape.bc.forward"):
                acc = _level_pull(ctx, frag, jnp.where(depth == d, pn, zero))
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
                acc = _level_pull(ctx, frag, jnp.where(
                    at_d, (one + delta) / jnp.where(at_d, pn, one), zero))
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
        levels = int(depth[reached].max()) if reached.any() else 0
        BC_STATS.update(
            levels=levels, reached=int(reached.sum()), pulls=2 * levels + 1)
        return np.asarray(state["delta"])
