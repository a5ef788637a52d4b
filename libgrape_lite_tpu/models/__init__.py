"""The analytical-app library (reference `examples/analytical_apps`).

Each app is a PIE program: host-side `init_state` (PEval's setup),
traced `peval`/`inceval` supersteps, host-side `finalize` (Assemble).

The registry mirrors the reference's app-variant names
(`run_app.h:214-296` dispatch).  Variants that differ only by CPU-side
execution strategy (e.g. SIMD/pooled-buffer builds of the same
algorithm) map to the same TPU implementation — XLA owns those
concerns; variants with genuinely different round/communication
structure have distinct classes: `*_auto` (SyncBuffer push),
`pagerank_push`, `bfs_opt` (direction-optimizing push/pull),
`sssp_opt`/`sssp_delta` (bucketed near/far worklists).
Exceptions: cdlp_auto / lcc_auto alias the base apps — their SyncBuffer
is a plain mirror-overwrite (no aggregate op), which the gather model
performs inherently, so push and pull coincide.
"""

from libgrape_lite_tpu.models.pagerank import PageRank
from libgrape_lite_tpu.models.sssp import SSSP
from libgrape_lite_tpu.models.bfs import BFS
from libgrape_lite_tpu.models.wcc import WCC
from libgrape_lite_tpu.models.cdlp import CDLP, CDLPOpt
from libgrape_lite_tpu.models.lcc import LCC
from libgrape_lite_tpu.models.bc import BC
from libgrape_lite_tpu.models.kcore import KCore
from libgrape_lite_tpu.models.core_decomposition import CoreDecomposition
from libgrape_lite_tpu.models.pagerank_local import PageRankLocal
from libgrape_lite_tpu.models.kclique import KClique
from libgrape_lite_tpu.models.pagerank_vc import (
    PageRankVC,
)
from libgrape_lite_tpu.models.vc2d import BFSVC2D, SSSPVC2D, WCCVC2D
from libgrape_lite_tpu.models.lcc_directed import LCCDirected
from libgrape_lite_tpu.models.wcc_opt import WCCOpt
from libgrape_lite_tpu.models.sssp_msg import BFSMsg, SSSPMsg
from libgrape_lite_tpu.models.bfs_opt import BFSOpt
from libgrape_lite_tpu.models.sssp_delta import SSSPDelta
from libgrape_lite_tpu.models.lcc_beta import LCCBeta
from libgrape_lite_tpu.models.triangle_count import (
    CommonNeighbors,
    TriangleCount,
)
from libgrape_lite_tpu.models.khop import KHopNeighborhood
from libgrape_lite_tpu.models.auto_apps import (
    BFSAuto,
    PageRankAuto,
    SSSPAuto,
    WCCAuto,
)

APP_REGISTRY = {
    "sssp": SSSP,
    # probe-and-pick: host BFS hop probe chooses dense vs delta at
    # query time (models/sssp_select.py; near-far heuristic analogue)
    "sssp_select": SSSP,
    "sssp_auto": SSSPAuto,
    # sssp_opt = the reference's worklist-optimized variant
    # (cuda/sssp/sssp.h near/far): here the bucketed delta-stepping app
    "sssp_opt": SSSPDelta,
    "sssp_delta": SSSPDelta,
    "sssp_msg": SSSPMsg,
    "bfs": BFS,
    "bfs_auto": BFSAuto,
    # bfs_opt = direction-optimizing push/pull (bfs/bfs_opt.h)
    "bfs_opt": BFSOpt,
    "bfs_msg": BFSMsg,
    "wcc": WCC,
    "wcc_auto": WCCAuto,
    "wcc_opt": WCCOpt,
    "pagerank": PageRank,
    "pagerank_auto": PageRankAuto,
    "pagerank_parallel": PageRank,
    "pagerank_opt": PageRank,
    "pagerank_push": PageRankAuto,
    # the reference's push_opt differs from push only by the Opt
    # message manager (pooled buffers — compiler-managed here)
    "pagerank_push_opt": PageRankAuto,
    "cdlp": CDLP,
    "cdlp_auto": CDLP,
    "cdlp_opt": CDLPOpt,
    "cdlp_opt_ud": CDLPOpt,
    "cdlp_opt_ud_dense": CDLPOpt,
    # `lcc` = the merge-intersection variant (LCCBeta): measured 6.1s
    # warm vs 10.8s for the bitmap kernel on the p2p-31 CI config
    # (4-dev CPU mesh, scripts/run_ldbc.py, round 2); O(chunk·Dmax)
    # working set scales past the bitmap's O(N/32)-per-row.  The bitmap
    # variant stays as lcc_opt/lcc_bitmap (its VPU popcount path is the
    # analogue of the reference's SIMD lcc_opt.h) pending a TPU A/B.
    "lcc": LCCBeta,
    "lcc_auto": LCCBeta,
    "lcc_opt": LCC,
    "lcc_bitmap": LCC,
    "lcc_beta": LCCBeta,
    "lcc_directed": LCCDirected,
    # pagerank already pulls over in-edges (pagerank_parallel.h
    # semantics), which is the directed-correct formulation
    "pagerank_directed": PageRank,
    # the reference's opt-mode bc runs the staged pair
    # StagedBCBFS -> StagedBC (run_app_opt.h:471-472); here both
    # stages are fused into one PIE program (two while_loops in
    # BC.peval), so all three names resolve to it
    "bc": BC,
    "staged_bc": BC,
    "staged_bc_bfs": BC,
    "kcore": KCore,
    "kclique": KClique,
    "core_decomposition": CoreDecomposition,
    "pagerank_local": PageRankLocal,
    "pagerank_local_parallel": PageRankLocal,
    # pagerank_vc = SUMMA-sharded master state (O(N/k) per device)
    "pagerank_vc": PageRankVC,
    # 2-D vertex-cut min-fold apps (models/vc2d.py, ROADMAP item 2):
    # byte-identical to the 1-D pulls; selected by GRAPE_PARTITION
    # via fragment/partition.resolve_partition
    "sssp_vc": SSSPVC2D,
    "bfs_vc": BFSVC2D,
    "wcc_vc": WCCVC2D,
    # r11 spgemm-backed workloads (ops/spgemm_pack.py, docs/SPGEMM.md):
    # triangle counts share the LCC credit pass (both backends);
    # common_neighbors is the serve-able 2-hop point query
    "triangle_count": TriangleCount,
    "common_neighbors": CommonNeighbors,
    # k-hop neighborhood extraction (models/khop.py): the
    # serve-routable sampling workload — ROADMAP 5c one notch
    "khop": KHopNeighborhood,
}
