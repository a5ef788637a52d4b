"""Zero-downtime replica drain: stop routing, quiesce, work, rejoin.

The drain primitive the ROADMAP named (item 2c): take one replica out
of rotation for repack/reshard/offline ingest WITHOUT dropping
traffic, built on the async pump's quiesce barrier (PR 12) and the
router's graph-version fence:

  1. **stop routing** — the replica leaves the candidate set; new
     queries spread over its siblings (`FleetRouter.submit` routes
     least-outstanding among the remaining replicas).
  2. **quiesce** — every query ALREADY admitted to the replica runs
     to completion through its pump's drain (forced partial batches);
     zero queries are dropped, by construction.
  3. **offline work** — the caller's `offline(session)` hook runs
     against the idle replica: fold the dyn overlay into a rebuilt
     CSR (`session.dyn.fold_now`), repartition, reshard — anything
     that would have stalled the serving path.  This is the host-side
     gather/scatter + vertex-map-rebuild migration step of the
     distributed-memory permutation/assignment primitives
     (arXiv 2509.20776), run where nobody is waiting on it.
  4. **rejoin** — the catch-up log (every fence bump the replica
     missed, with its ops) replays IN ORDER, so the replica's graph
     content is identical to its siblings' (the overlay/rebuild
     byte-identity contract of dyn/ makes representation differences
     invisible); the fence versions must line up or rejoin raises
     `FenceViolationError` — a replica can never rejoin at a stale
     version.

The drain drill (tests/test_fleet.py, bench `fleet` block): R=2
serving a 64-query stream with concurrent ingest, one replica drained
mid-stream — zero dropped queries, every per-query result
byte-identical to the undrained R=1 run.
"""

from __future__ import annotations

import time

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.fleet.budget import FLEET_STATS
from libgrape_lite_tpu.fleet.router import FenceViolationError


def begin_drain(router, idx: int, *, offline=None) -> dict:
    """Phase 1-3: stop routing, quiesce (zero drops), run the offline
    work.  The replica stays OUT of rotation until `rejoin` — deltas
    ingested meanwhile accumulate in its catch-up log."""
    r = router.replicas[idx]
    if not r.routable:
        raise ValueError(f"replica {idx} is already draining")
    if len([x for x in router.replicas if x.routable]) < 2:
        raise ValueError(
            f"cannot drain replica {idx}: it is the last routable "
            "replica — traffic would drop"
        )
    t0 = time.perf_counter()
    r.routable = False
    tr = obs.tracer()
    if tr.enabled:
        tr.instant(
            "fleet_drain_begin", replica=idx,
            outstanding=r.outstanding,
            pending=r.session.queue.pending(),
        )
    # quiesce: finish everything this replica already admitted
    drained = r.pump.drain()
    router._collect()
    if offline is not None:
        offline(r.session)
    wall = time.perf_counter() - t0
    r.drains += 1
    router.stats["drains"] += 1
    report = {
        "replica": idx,
        "drained_queries": len(drained),
        "offline": offline is not None,
        "wall_s": round(wall, 4),
    }
    FLEET_STATS.record("drain", **report)
    return report


def rejoin(router, idx: int) -> dict:
    """Phase 4: replay the catch-up log in fence order, verify the
    version lines up with the fence, and return to rotation."""
    r = router.replicas[idx]
    if r.routable:
        raise ValueError(f"replica {idx} is not draining")
    applied = 0
    for fence, ops, force in r.catchup:
        r.session.ingest(ops, force_repack=force)
        r.version = fence
        applied += len(ops)
    r.catchup = []
    if r.version != router.fence:
        # the fence only moves at ingest, and every ingest while we
        # were draining logged a catch-up entry — a mismatch here
        # means the log was tampered with or a version was skipped
        raise FenceViolationError(
            f"replica {idx} rejoining at version {r.version} but the "
            f"fence is {router.fence} — catch-up log incomplete"
        )
    r.routable = True
    tr = obs.tracer()
    if tr.enabled:
        tr.instant(
            "fleet_rejoin", replica=idx, fence=router.fence,
            catchup_ops=applied,
        )
    report = {"replica": idx, "catchup_ops": applied,
              "version": r.version}
    FLEET_STATS.record("rejoin", **report)
    return report


def rejoin_lost(router, checkpoint_dir: str, *, session_factory):
    """Process-loss rejoin (docs/FAULT_TOLERANCE.md, "Distributed
    resilience"): a replica lost to a dead rank cannot drain or replay
    a catch-up log — its in-memory state is gone.  What survives is
    the last committed sharded checkpoint.  This builds a REPLACEMENT
    replica from a live sibling's fragment (`replicate_fragment`, the
    same deterministic copy the autoscaler's scale-up uses), adds it
    to rotation at the current fence, and returns `(replica, meta)`
    where `meta` is the newest sharded snapshot's metadata — the
    caller resumes interrupted checkpointed queries via
    `Worker.resume`, which is reshard-aware (the snapshot restores
    onto the replacement's mesh even when the gang shrank)."""
    from libgrape_lite_tpu.fragment.mutation import replicate_fragment
    from libgrape_lite_tpu.ft.checkpoint import latest_meta

    meta = latest_meta(checkpoint_dir)
    if meta.get("layout") != "sharded":
        raise ValueError(
            f"rejoin_lost needs a sharded (multi-process) checkpoint "
            f"lineage; {checkpoint_dir!r} holds a "
            f"{meta.get('layout', 'single-file')!r} layout — use the "
            f"ordinary resume path for single-process loss"
        )
    live = [x for x in router.replicas if x.routable]
    if not live:
        raise ValueError(
            "rejoin_lost: no live replica to replicate a fragment from"
        )
    sess = session_factory(replicate_fragment(live[0].session.fragment))
    r = router.add_replica(sess)
    FLEET_STATS.record(
        "rejoin", replica=r.idx, lost_process=True,
        ckpt_rounds=int(meta["rounds"]),
        ckpt_ranks=int(meta.get("ranks", 0)),
    )
    return r, meta


def drain_replica(router, idx: int, *, offline=None) -> dict:
    """The one-call form: begin + rejoin immediately (no ingest can
    land in between, so the catch-up log is empty and the replica
    rejoins at the unchanged fence)."""
    report = begin_drain(router, idx, offline=offline)
    report["rejoin"] = rejoin(router, idx)
    return report
