"""Guarded batched execution: per-lane monitors, breach isolation.

The unguarded batched fast path is `Worker.query_batch` (one vmapped
fused dispatch).  With guards armed a batch runs here instead: fused
chunks of `guard_cfg.every` supersteps (Worker._make_batched_chunk_
runner — the same freeze-masked vmapped body) with ONE GuardMonitor
per lane probing its slice of the carry at every chunk boundary.
Lanes are independent under vmap — state never crosses the lane axis —
so a poisoned query cannot contaminate batchmates; what breach
isolation adds is the POLICY surface: a lane whose invariants fail is
frozen (its active vote is forced to zero, pinning its carry) and its
result carries the diagnostic bundle, while every other lane keeps
running to convergence and returns byte-identical results.  This is
the serving-runtime form of the halt policy — one bad query must not
halt the dispatch it shares.

Rollback policy degrades to per-lane halt here: batched queries have
no per-lane checkpoint lineage (the monitor logs the downgrade, as the
unchunked guarded path did before PR 6 grew snapshots).

Under the async pump (serve/pipeline.py) a guarded batch still runs
this chunk loop at dispatch time — breach isolation needs the probe
verdicts, which sync at every chunk boundary by design — but the
verdict arrays are snapshot into a `BatchDispatch` handle and the
per-lane VALUES harvest lazily with everyone else's, so a guarded
batch mid-window never blocks on value extraction and batches behind
it in the window keep executing while the chunk loop probes.  Breach
semantics are pinned unchanged either way (tests/test_serve_async.py
poisons a lane with W>1 batches in flight).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu import obs

_INT32_MAX = np.iinfo(np.int32).max


def lane_slices(carry: dict, lane: int) -> dict:
    """Lane `lane`'s [fnum, ...] view of a batched carry (lazy device
    slices — the per-lane probe jits over them directly)."""
    return {k: v[lane] for k, v in carry.items()}


def run_guarded_batch(worker, args_list, mr: int, guard_cfg, *,
                      chunk_hook=None):
    """Execute a k-lane batch under per-lane guard monitors.

    Returns the batched result state (like Worker.query_batch) and
    leaves per-lane verdicts on the worker: `batch_rounds`,
    `batch_terminate`, and `batch_breaches` (one diagnostic bundle or
    None per lane — serve/session.py turns bundles into failed
    ServeResults).

    `chunk_hook(carry, rounds)` is a test seam: called after every
    chunk with the batched device carry, it may return replacement
    numpy leaves (e.g. poisoning one lane) that are re-placed before
    the probes — the breach-isolation drill in tests/test_serve.py
    injects through it."""
    from libgrape_lite_tpu.guard.monitor import GuardMonitor

    app = worker.app
    frag = worker.fragment
    batch = len(args_list)
    if mr <= 0:
        mr = _INT32_MAX
    if guard_cfg.policy == "rollback":
        from libgrape_lite_tpu.utils import logging as glog

        glog.log_info(
            "guard: batched dispatches have no per-lane checkpoint "
            "lineage — rollback degrades to per-lane halt (breach "
            "isolation)"
        )

    state = worker._place_state_batch(
        app.init_state_batch(frag, args_list)
    )
    eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
    eph_part = {k: v for k, v in state.items() if k in eph}

    def carry_of(st):
        return {k: v for k, v in st.items() if k not in eph}

    monitors = [
        GuardMonitor(app=app, frag=frag, config=guard_cfg,
                     ledger=worker.pack_ledger())
        for _ in range(batch)
    ]
    worker._guard_monitor = monitors[0] if monitors else None
    breaches = [None] * batch
    failed = np.zeros(batch, dtype=bool)

    def probe_lane(b, prev_b, cur, rounds_b, active_b, digest=None,
                   residual=None):
        """One lane's chunk-boundary probe; a non-warn breach freezes
        the lane instead of raising — batchmates keep running."""
        if active_b < 0:  # cooperative abort is the app's own verdict
            return
        breach = monitors[b].check(
            prev_b, lane_slices(cur, b), rounds_b, active_b,
            digest=digest, residual=residual,
        )
        if breach is not None:
            failed[b] = True
            breaches[b] = breach.bundle
            obs.tracer().instant(
                "serve_lane_breach", lane=b, round=rounds_b,
                kind=breach.verdict["kind"], policy=guard_cfg.policy,
            )

    tr = obs.tracer()
    try:
        with tr.span("query", mode="guarded-batched",
                     app=type(app).__name__, batch=batch) as qsp:
            peval_fn = worker._batched_step_for("peval", state, batch)
            prev = [
                lane_slices(carry_of(state), b) for b in range(batch)
            ]
            with tr.span("peval", batch=batch) as sp:
                out = worker._enqueue(
                    peval_fn, "guarded-batched", batch, frag.dev, state)
                sp.mark("dispatched")
                carry, active = jax.block_until_ready(out)
            active = np.asarray(active).copy()
            if tr.enabled:
                obs.metrics().counter(
                    "grape_supersteps_total"
                ).inc(batch)
            rounds_v = np.zeros(batch, dtype=np.int32)
            for b in range(batch):
                probe_lane(b, prev[b], carry, 0, int(active[b]))
                prev[b] = lane_slices(carry, b)
            act_eff = np.where(failed, 0, active).astype(np.int32)
            chunk_fn = worker._batched_chunk_runner_for(
                guard_cfg.every, mr, batch, state
            )
            r_global = 0
            while (act_eff > 0).any() and r_global < mr:
                live_in = act_eff > 0
                with tr.span("chunk", start_round=r_global,
                             lanes=int(live_in.sum())) as sp:
                    out = chunk_fn(
                        frag.dev, carry, eph_part,
                        jnp.asarray(act_eff), jnp.asarray(rounds_v),
                        jnp.int32(r_global),
                    )
                    sp.mark("dispatched")
                    carry, rv, act, r2, dig, res = (
                        jax.block_until_ready(out)
                    )
                    sp.set(end_round=int(r2))
                rounds_v = np.asarray(rv).copy()
                active = np.asarray(act).copy()
                dig = np.asarray(dig)
                res = np.asarray(res)
                if tr.enabled:
                    m = obs.metrics()
                    m.counter("grape_supersteps_total").inc(
                        int(r2) - r_global
                    )
                r_global = int(r2)
                if chunk_hook is not None:
                    corrupted = chunk_hook(carry, r_global)
                    if corrupted is not None:
                        carry = {
                            **carry,
                            **worker._place_state_batch(corrupted),
                        }
                        dig = res = None  # stale: re-probe fully
                for b in range(batch):
                    if not live_in[b] or failed[b]:
                        continue
                    digest = (
                        None if dig is None
                        else tuple(int(x) for x in dig[b])
                    )
                    residual = None
                    if res is not None and float(res[b]) >= 0:
                        residual = float(res[b])
                    probe_lane(
                        b, prev[b], carry, int(rounds_v[b]),
                        int(active[b]), digest=digest,
                        residual=residual,
                    )
                    prev[b] = lane_slices(carry, b)
                act_eff = np.where(failed, 0, active).astype(np.int32)
            worker.batch_rounds = rounds_v
            worker.batch_terminate = np.minimum(0, active)
            worker.batch_breaches = list(breaches)
            worker.rounds = int(rounds_v.max()) if batch else 0
            worker._terminate_code = (
                int(worker.batch_terminate.min()) if batch else 0
            )
            if tr.enabled:
                qsp.set(
                    lane_rounds=[int(x) for x in rounds_v],
                    failed_lanes=[
                        b for b in range(batch) if failed[b]
                    ],
                )
            worker._finish_query_obs(qsp)
    finally:
        if tr.enabled:
            obs.flush()
    worker._result_state = {**carry, **eph_part}
    # same provenance record as the unguarded paths: a serve repack
    # rebinds worker.fragment, and query_incremental's prev_fragment
    # default must name the fragment THIS result's rows live in
    worker._result_fragment = frag
    return worker._result_state
