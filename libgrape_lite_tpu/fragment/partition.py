"""1-D edge-cut vs 2-D vertex-cut partition planner (ROADMAP item 2).

Given the host edge list, price BOTH partition layouts from a static
cost ledger and choose — `GRAPE_PARTITION`:

  * unset / "" / "0" / "1d"  — 1-D edge-cut, the serial path,
    bit-for-bit untouched (lowered-HLO pinned in
    tests/test_partition2d.py);
  * "2d"                      — force the 2-D vertex-cut path when the
    app/geometry is eligible (hard error otherwise would hide the
    reason: ineligibility DECLINES with the reason recorded, and the
    1-D path runs);
  * "auto"                    — engage 2-D only when the modeled round
    cost wins.

Cost model (rates from the active RateProfile, ops/calibration.py —
one set of modeled rates, not private copies):

  t_1d = max_shard_edges_padded * ops_per_edge / VPU_rate
         + gather_bytes / ICI          (mirror.exchange_bytes_ledger)
  t_2d = max_tile_edges_padded  * ops_per_edge / VPU_rate
         + vc2d_bytes / ICI            (mirror.vc2d_exchange_bytes)

Both compute terms are PADDED maxima: SPMD blocks are uniform, so
every shard/tile pays the most-loaded one's capacity — exactly the
hub pathology being priced (docs/SCALE_NOTES.md: a degree-correlated
1-D cut pads every shard to the hub shard's Ep; the vertex-cut splits
each hub's edges across its tile column).  Decisions and decline
reasons land in PARTITION_STATS, never silent.
"""

from __future__ import annotations

import os

import numpy as np

from libgrape_lite_tpu.parallel.mirror import (
    exchange_bytes_ledger,
    vc2d_exchange_bytes,
)
from libgrape_lite_tpu.ops.calibration import active_profile

# op COUNT per edge of a pull round (XLA gather + segment fold): a
# counting convention, not a rate; stays literal
DEFAULT_OPS_PER_EDGE = 30.0

# 1-D app name -> its registered 2-D vertex-cut twin.  min-fold apps
# are byte-identical to the 1-D pull; PageRankVC's sum fold is
# eps-identical (float partials regroup, accepted here because
# PageRank is verified by eps everywhere already).
VC2D_APPS = {
    "sssp": "sssp_vc",
    "bfs": "bfs_vc",
    "wcc": "wcc_vc",
    "pagerank": "pagerank_vc",
}

# federated as "partition" (obs/federation.py); mutation sites unchanged
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats

PARTITION_STATS = _FedStats("partition", {
    "resolved_2d": 0,     # decisions that engaged the 2-D path
    "declined": 0,        # 2d/auto requested but ineligible or priced out
    "last_decision": None,
})


# one set of padding helpers: the modeled vp/capacity terms below
# must round exactly the way the real fragment builders do, or the
# cost comparison drifts from the bill the shards actually pay
from libgrape_lite_tpu.fragment.edgecut import (  # noqa: E402
    _next_pow2,
    _round_up,
)


def partition_mode() -> str:
    """1d | 2d | auto from GRAPE_PARTITION (default 1d: the serial
    edge-cut path stays the compiled program).  Unrecognized values
    fall back to 1d WITH a log line — a typo must not silently
    downgrade a forced 2d to auto (mirror.resolve_mirror_plan
    discipline)."""
    v = (os.environ.get("GRAPE_PARTITION", "") or "1d").strip().lower()
    if v in ("", "0", "off", "1d"):
        return "1d"
    if v == "2d":
        return "2d"
    if v in ("auto", "1"):
        return "auto"
    from libgrape_lite_tpu.utils import logging as glog

    glog.log_info(
        f"GRAPE_PARTITION={v!r} is not one of 1d|2d|auto; using 1d"
    )
    return "1d"


def modeled_costs(src: np.ndarray, dst: np.ndarray, n_vertices: int,
                  fnum: int, *, directed: bool = False,
                  itemsize: int = 4,
                  ops_per_edge: float | None = None,
                  profile=None) -> dict:
    """Price one round of the pull under both layouts.  `src`/`dst`
    are the RAW oid edge list (symmetrised internally when
    undirected, matching both loaders); shard/tile assignment follows
    the contiguous-range conventions of the map partitioner and
    VCPartitioner.  `itemsize` defaults to the f32 payload convention
    BOTH byte ledgers share (mirror.exchange_bytes_ledger) — mixing
    conventions here would bias the 1-D-vs-2-D comparison.  Rates come
    from `profile` (default: the active RateProfile)."""
    p = profile or active_profile()
    ope = DEFAULT_OPS_PER_EDGE if ops_per_edge is None else ops_per_edge
    rate = p.vpu_lanes_per_cycle * p.clock_hz
    s = np.asarray(src)
    d = np.asarray(dst)
    if not directed:
        s, d = np.concatenate([s, d]), np.concatenate([d, s])

    # 1-D: contiguous oid blocks (map/segmented partitioner), in-CSR
    # rows = destination owner; every shard pays the padded max Ep
    shard_w = max(1, -(-n_vertices // fnum))
    shard_counts = np.bincount(
        np.minimum(d // shard_w, fnum - 1), minlength=fnum
    )
    max_shard = int(shard_counts.max())
    vp = _next_pow2(max(shard_w, 8))
    # fnum == 1 has NO exchange on either layout (the ledger's
    # fnum*vp convention would bill a phantom gather and bias auto
    # toward a pointless 2-D swap)
    bytes_1d = (
        exchange_bytes_ledger(fnum, vp)["gather"] if fnum > 1 else 0
    )
    t_1d = _round_up(max_shard, 128) * ope / rate + bytes_1d / p.ici_bps

    # 2-D: k x k oid-range tiles (VCPartitioner); one dst-side pull
    # per round on the symmetrised storage (two orientations when the
    # directed graph must pull both, i.e. WCC — priced by the caller
    # via `pulls` if needed; the default single pull covers
    # SSSP/BFS/undirected)
    k = int(round(np.sqrt(fnum)))
    out = {
        "1d": {
            "max_shard_edges": max_shard,
            "exchange_bytes": bytes_1d,
            "t_round_s": t_1d,
        },
    }
    if k * k == fnum and k >= 1:
        chunk = max(1, -(-n_vertices // k))
        vc = _round_up(chunk, 128)
        tile = np.minimum(s // chunk, k - 1) * k + np.minimum(
            d // chunk, k - 1
        )
        tile_counts = np.bincount(tile, minlength=k * k)
        max_tile = int(tile_counts.max())
        bytes_2d = vc2d_exchange_bytes(k, vc, itemsize=itemsize)
        t_2d = (
            _round_up(max_tile, 128) * ope / rate + bytes_2d / p.ici_bps
        )
        out["2d"] = {
            "k": k,
            "max_tile_edges": max_tile,
            "exchange_bytes": bytes_2d,
            "t_round_s": t_2d,
        }
    return out


def precheck_partition(app_name: str, fnum: int, *,
                       directed: bool = False,
                       string_id: bool = False) -> str | None:
    """The eligibility checks that need NO edge data (decline reason,
    or None = structurally eligible).  Shared by `resolve_partition`
    and the runner's probe gate, so the runner can record a cheap
    decline WITHOUT reading a possibly multi-GB edge file first."""
    if app_name not in VC2D_APPS:
        return (
            f"no 2-D vertex-cut implementation for {app_name!r} "
            f"(known: {sorted(VC2D_APPS)})"
        )
    k = int(round(np.sqrt(fnum)))
    if k * k != fnum:
        return f"fnum={fnum} is not a perfect square"
    if string_id:
        return (
            "string ids: the vertex-cut fragment is specialized to "
            "integer oids (reference immutable_vertexcut_fragment.h)"
        )
    if directed and app_name == "pagerank":
        return (
            "pagerank_vc accumulates both directions (the reference's "
            "undirected gather-scatter semantics); the directed 1-D "
            "formulation has no 2-D twin"
        )
    return None


def resolve_partition(app_name: str, fnum: int, src: np.ndarray,
                      dst: np.ndarray, oids: np.ndarray, *,
                      directed: bool = False, string_id: bool = False,
                      mode: str | None = None, eligible: bool = True,
                      reason: str = "") -> dict:
    """The partition decision for one (app, graph, fnum) — returns the
    recorded decision dict ({"mode": "1d"|"2d", "engaged": bool,
    "costs": ..., "reason": ...}); every 2d/auto request that lands on
    1-D carries its decline reason.
    `eligible=False` + `reason` lets a caller record a decline the
    planner cannot see itself (e.g. a delta-mutation load)."""
    from libgrape_lite_tpu.utils import logging as glog

    mode = partition_mode() if mode is None else mode
    prof = active_profile()
    decision = {
        "app": app_name, "requested": mode, "fnum": fnum,
        "mode": "1d", "engaged": False, "profile": prof.label(),
    }

    def declined(why: str, count: bool = True):
        decision["reason"] = why
        PARTITION_STATS["last_decision"] = decision
        if count:
            PARTITION_STATS["declined"] += 1
            glog.vlog(
                1, "partition: 2d declined for %s: %s", app_name, why
            )
        return decision

    if mode == "1d":
        return declined("GRAPE_PARTITION off (1d)", count=False)
    if not eligible:
        return declined(reason or "caller declared ineligible")
    why = precheck_partition(
        app_name, fnum, directed=directed, string_id=string_id
    )
    if why is not None:
        return declined(why)
    k = int(round(np.sqrt(fnum)))
    n_vertices = int(np.asarray(oids).max()) + 1 if len(oids) else 1
    costs = modeled_costs(src, dst, n_vertices, fnum,
                          directed=directed, profile=prof)
    decision["costs"] = costs
    if "2d" not in costs:
        return declined("cost model found no k^2 tiling")
    if mode == "auto" and costs["2d"]["t_round_s"] >= costs["1d"][
        "t_round_s"
    ]:
        return declined(
            "modeled 2-D round cost "
            f"{costs['2d']['t_round_s']:.3e}s does not beat 1-D "
            f"{costs['1d']['t_round_s']:.3e}s (balanced cut or k too "
            "small for the byte win; GRAPE_PARTITION=2d forces)"
        )
    decision["mode"] = "2d"
    decision["engaged"] = True
    PARTITION_STATS["resolved_2d"] += 1
    PARTITION_STATS["last_decision"] = decision
    glog.vlog(
        1, "partition: 2d engaged for %s (k=%d, max tile %d vs max "
        "shard %d edges, %d vs %d exchange B/round)",
        app_name, k, costs["2d"]["max_tile_edges"],
        costs["1d"]["max_shard_edges"], costs["2d"]["exchange_bytes"],
        costs["1d"]["exchange_bytes"],
    )
    return decision
