"""K-hop neighborhood — the serve-routable sampling workload.

One notch of ROADMAP 5c (the reference ships `examples/gnn_sampler`):
the fleet bench needs a workload whose traffic shape looks like real
user traffic — many tiny point queries, each touching a small
neighborhood — and k-hop neighborhood extraction is exactly the
frontier expansion a GNN sampler runs before fanout subsampling
(sampler/sampler.py keeps the fixed-fanout strategies; the full GNN
driver stays a follow-on).

Formulation: the BFS unit-weight tropical relaxation with the round
budget AS the hop bound — after k `inceval` rounds the depth plane
holds exactly the <= k-hop ball around the source.  Everything BFS
earned rides along for free: the `batch_query_key="source"` contract
(serve/ coalesces k sources into one vmapped dispatch), the dyn
overlay fold (staged delta edges join the neighborhood exactly) and
the guard invariants.  `k` is a constructor hyperparameter (it is baked
into the while_loop bound, so it rides `trace_key` and two k's never
share a compile).

Result: hop distance for members of the ball, -1 outside (the
reference sampler emits empty lists for unreached frontiers).
"""

from __future__ import annotations

import numpy as np

from libgrape_lite_tpu.models.bfs import _SENTINEL, BFS


class KHopNeighborhood(BFS):
    result_format = "int"
    # bounded-round iteration: the previous fixed point is not
    # reusable under the hop cap, so incremental IncEval stays an
    # honest counted cold run (dyn overlay support is inherited — the
    # min fold is exact at any round budget)
    inc_mode = None
    inc_seed_keys: dict = {}

    def __init__(self, k: int = 2):
        k = int(k)
        if k < 1:
            raise ValueError(f"khop needs k >= 1, got {k}")
        self.k = k
        # the hop bound IS the round budget: round r relaxes depths
        # to r, so k rounds yield exactly the <= k-hop ball
        self.max_rounds = k

    def finalize(self, frag, state):
        d = np.asarray(state["depth"]).astype(np.int64)
        return np.where((d == _SENTINEL) | (d > self.k), -1, d)
