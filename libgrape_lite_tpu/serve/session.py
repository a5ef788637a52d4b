"""ServeSession: a resident graph serving many queries.

The reference libgrape-lite is a library — load, query once, exit; the
ROADMAP north star is a service.  A session inverts the lifetime: the
expensive per-graph artifacts are pinned ONCE and every query reuses
them —

  * the HBM-resident sharded fragment (`frag.dev` device CSRs),
  * compiled fused runners, keyed by (app hyperparameters, state
    shape, max_rounds) in each app's resident Worker
    (`Worker._runner_cache` — the session owns the workers, so the
    cache spans queries and `runner_cache_stats` proves the second
    query of a shape compiles nothing).

Queries arrive through the AdmissionQueue (serve/queue.py), coalesce
into vmapped multi-source batches (Worker.query_batch) under the
BatchPolicy, and keep per-query observability: each lane gets its own
trace track + result record, and with guards armed each lane gets its
own monitor with breach isolation (serve/batch.py).

Typical use::

    sess = ServeSession(frag)
    reqs = [sess.submit("sssp", {"source": s}) for s in sources]
    sess.drain()                      # or pump() under a wait policy
    values = reqs[0].result.values

docs/SERVING.md is the user guide.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.serve.policy import BatchPolicy, compat_key
from libgrape_lite_tpu.serve.queue import (
    AdmissionQueue,
    QueryRequest,
    ServeResult,
)
from libgrape_lite_tpu.worker.worker import Worker


def _calibration_harvester():
    """The live-harvest hook (ops/calibration.py): when
    GRAPE_CALIBRATE_HARVEST is armed, returns the callable that joins
    a dispatch's telemetry `device_us` stamp to its worker's shipped
    ledger recount; None (the common case) costs one env read."""
    from libgrape_lite_tpu.ops import calibration

    if not calibration.harvest_armed():
        return None
    return calibration.harvest_from_worker


class ServeSession:
    def __init__(self, fragment, apps: Dict | None = None,
                 policy: BatchPolicy | None = None,
                 guard: Optional[str] = None, dyn=None):
        """`apps` maps app_key -> app factory (default: the full
        APP_REGISTRY); `guard` is the session-default guard policy
        (per-request `guard=` wins).

        `dyn` enables live ingest (dyn/, docs/DYNAMIC_GRAPHS.md):
        True (env-configured RepackPolicy), a RepackPolicy, or a
        pre-built DynGraph.  The session then accepts `ingest(ops)`
        between pumps — staged deltas ride the overlay side-path
        (zero replanning, zero recompiles) until the repack policy
        folds them, a counted recompile event.  Requires the fragment
        loaded with retain_edge_list=True for the repack path."""
        if apps is None:
            from libgrape_lite_tpu.models import APP_REGISTRY

            apps = dict(APP_REGISTRY)
        self.dyn = None
        if dyn is not None and dyn is not False:
            # the delta overlay is an edge-cut side-path: the vc2d
            # apps never read `dyn_overlay`, so a dyn vertex-cut
            # session would serve STALE results silently — refuse
            # loudly instead (docs/PARTITION2D.md "Serve + fleet")
            if getattr(fragment, "_host_tiles", None) is not None:
                raise ValueError(
                    "dyn ingest is not supported on a vertex-cut "
                    "fragment: the 2-D tile pulls do not read the "
                    "delta overlay, so staged edges would be "
                    "silently invisible; repack into a new fragment "
                    "instead"
                )
            from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy

            if isinstance(dyn, DynGraph):
                self.dyn = dyn
            else:
                self.dyn = DynGraph(
                    fragment,
                    policy=None if dyn is True else dyn,
                )
            fragment = self.dyn.fragment
        self.fragment = fragment
        self.apps = apps
        self.policy = policy or BatchPolicy()
        self.guard = guard
        self.queue = AdmissionQueue(
            self._dispatch, self.policy, self._compat_key
        )
        self._workers: Dict[str, Worker] = {}
        self._pump = None  # the attached AsyncServePump, if any
        self._closed = False
        # optional result cache (autopilot/cache.py) + its epoch
        # source; a bare session's epoch is its own ingest counter, a
        # fleet replica's is the router fence (attach_result_cache)
        self._cache = None
        self._cache_epoch = None
        self._ingest_epoch = 0
        self.stats = {
            "queries": 0, "batches": 0, "failed": 0,
            "sequential_fallbacks": 0, "cache_hits": 0,
            "ingested_ops": 0, "overlay_applies": 0, "repacks": 0,
            "forced_repacks": 0,
        }

    # ---- resident workers -------------------------------------------------

    def worker(self, app_key: str) -> Worker:
        """The resident Worker for one app: created on first use, then
        reused for every query — its runner cache is the session's
        zero-recompile guarantee."""
        w = self._workers.get(app_key)
        if w is None:
            if app_key not in self.apps:
                raise ValueError(
                    f"unknown application {app_key!r}; session serves: "
                    f"{sorted(self.apps)}"
                )
            w = Worker(self.apps[app_key](), self.fragment)
            self._workers[app_key] = w
        return w

    def cache_stats(self) -> dict:
        """Aggregated cache counters: compiled-runner hits/misses over
        every resident worker — the numbers the zero-recompile
        acceptance asserts on."""
        runner = {"hits": 0, "misses": 0}
        for w in self._workers.values():
            runner["hits"] += w.runner_cache_stats["hits"]
            runner["misses"] += w.runner_cache_stats["misses"]
        return {"runner": runner}

    # ---- lifecycle: eviction / re-admission / close (fleet/) --------------

    @property
    def resident(self) -> bool:
        """True while the fragment's device arrays are placed (a
        released/evicted session keeps every host artifact but holds
        no HBM)."""
        return self.fragment.dev is not None

    def release_device(self, *,
                       release_fragment: bool = True) -> dict:
        """Evict this session's device footprint: quiesce any attached
        pump, drop each resident worker's retained result buffers
        (`Worker.release_buffers`), and — unless the fragment is
        shared with a sibling session (`release_fragment=False`, the
        FleetManager's call) — delete the fragment's device arrays.

        Everything HOST-side stays warm: the per-fragment plan cache
        (weak-keyed on this very fragment object), the compiled-runner
        caches, the mirror plans.  `restore_device` therefore re-admits
        with ZERO re-planning and ZERO XLA recompiles — counter- and
        compile_events-pinned by tests/test_fleet.py."""
        if self._pump is not None and self._pump.inflight():
            self._pump.quiesce(reason="release_device")
        for w in self._workers.values():
            w.release_buffers()
        released = False
        if release_fragment:
            released = self.fragment.release_device()
        return {"fragment_released": released,
                "workers": len(self._workers)}

    def restore_device(self) -> bool:
        """Re-admit an evicted session: re-place the device arrays
        from the retained host CSRs (byte-identical content — the
        build is deterministic).  Returns True when a placement
        actually happened (False: already resident, e.g. a shared
        fragment restored by a sibling)."""
        if self._closed:
            raise RuntimeError("session is closed")
        return self.fragment.restore_device()

    def close(self) -> None:
        """Terminal release: drain + detach the pump, release the
        device footprint, and drop the resident workers (their
        compiled-runner caches go with them).  Further submits raise;
        close is idempotent."""
        if self._closed:
            return
        if self._pump is not None:
            self._pump.close()
        self.release_device()
        self._workers.clear()
        self._closed = True

    # ---- live ingest (dyn/) ----------------------------------------------

    def ingest(self, ops, *, force_repack: bool = False) -> dict:
        """Apply a batch of delta ops between dispatches (the host-
        pumped loop makes this a superstep boundary by construction —
        no query is ever mid-flight here).  Below the repack threshold
        the staged edges ride the overlay side-path and the next query
        of a warmed shape compiles NOTHING (runner cache hit —
        pinned by tests/test_dyn.py); at a repack the
        rebuilt fragment is adopted into every resident worker and the
        recompiles that follow are COUNTED in cache_stats, never
        silent.  Returns the DynGraph report ({mode, staged, ...})."""
        if self.dyn is None:
            raise RuntimeError(
                "session was built without dyn=; pass dyn=True (or a "
                "RepackPolicy / DynGraph) to enable live ingest"
            )
        # with an async pump attached, the superstep-boundary
        # invariant is an EXPLICIT drain, not an accident of the sync
        # loop: quiesce the dispatch window before touching the graph
        # (a no-op when nothing is in flight, e.g. when the pump's own
        # ingest barrier already drained it)
        if self._pump is not None and self._pump.inflight():
            self._pump.quiesce(reason="ingest")
        # delta from the DynGraph's own counters: one ingest can fold
        # MORE than once (staging past capacity repacks mid-batch), so
        # the final report's mode alone undercounts
        before_r = self.dyn.stats["repacks"]
        before_o = self.dyn.stats["overlay_applies"]
        report = self.dyn.ingest(ops, force_repack=force_repack)
        self.stats["ingested_ops"] += report.get("staged", 0)
        self.stats["repacks"] += self.dyn.stats["repacks"] - before_r
        self.stats["overlay_applies"] += (
            self.dyn.stats["overlay_applies"] - before_o
        )
        if self.dyn.fragment is not self.fragment:
            self._adopt_fragment()
        if report.get("staged", 0):
            # a content-changing ingest advances the cache epoch (an
            # empty forced repack preserves every answer and must NOT
            # kill the cache); a session owning its own epoch reaps
            # the stale one here — a fleet replica's router does this
            # at the fence bump instead (fleet/router.py)
            self._ingest_epoch += 1
            if self._cache is not None and self._cache_epoch is not None:
                try:
                    self._cache.invalidate_stale(self._cache_epoch())
                except Exception:
                    pass
        return report

    def _adopt_fragment(self) -> None:
        """Point the session and every resident worker at the rebuilt
        fragment.  Stale compiled runners stay in the caches but miss
        naturally: the fragment's structure closes every runner key
        (`Worker._cached_runner`) and the apps' re-resolved plan/mirror
        uids enter the trace key, so the first post-repack query of
        each shape is a counted compile."""
        self.fragment = self.dyn.fragment
        for w in self._workers.values():
            w.fragment = self.dyn.fragment

    def _ensure_dyn_view(self, app_key: str, w: Worker) -> None:
        """Apps without an overlay contract (PageRank, host-only
        loops) must see a consistent graph: fold the pending overlay
        into the CSR before dispatching them — a counted forced
        repack, not a silent stale read."""
        if self.dyn is None or self.dyn.overlay_count == 0:
            return
        if getattr(w.app, "dyn_overlay_support", False):
            return
        self.dyn.fold_now(
            reason=f"{app_key} has no dyn-overlay contract"
        )
        self.stats["repacks"] += 1
        self.stats["forced_repacks"] += 1
        self._adopt_fragment()

    # ---- admission --------------------------------------------------------

    def _compat_for(self, app_key: str, args: dict, max_rounds,
                    guard, tenant) -> tuple:
        # an unknown app must not raise here: the queue calls this
        # while PICKING the next batch, and a raise would wedge the
        # head of the queue forever — the dispatch path turns the
        # lookup failure into per-request error results instead
        if app_key not in self.apps:
            return (app_key, "?unknown", tenant)
        # batch_query_key is a CLASS attribute: read it off the
        # registered app class directly — instantiating the resident
        # Worker here (as this method once did) built state and
        # plans while the queue was merely PICKING a batch, so a bare
        # submit of a never-dispatched app paid a full worker warmup.
        # The tenant tag joins the key so requests of DIFFERENT
        # tenants never share a batched dispatch — one tenant's
        # poisoned lane can never fail a batchmate tenant (fleet/).
        return compat_key(
            app_key, args, max_rounds, guard or self.guard,
            getattr(self.apps[app_key], "batch_query_key", None),
            getattr(self.apps[app_key], "mesh_kind", "frag"),
        ) + (tenant,)

    def _compat_key(self, req: QueryRequest) -> tuple:
        return self._compat_for(req.app_key, req.args, req.max_rounds,
                                req.guard, req.tenant)

    # ---- result cache / admission control (autopilot/) --------------------

    def attach_result_cache(self, cache, epoch=None) -> None:
        """Wire a ResultCache (autopilot/cache.py) into this session:
        `submit` probes it BEFORE the request enters coalescing, and
        the queue's `deliver` stores every cacheable OK result.
        `epoch` supplies the invalidation fence (the FleetRouter
        passes its own `lambda: router.fence`); a bare session uses
        its ingest counter — any content-changing ingest bumps it and
        the stale epoch dies wholesale."""
        self._cache = cache
        self._cache_epoch = epoch or (lambda: self._ingest_epoch)
        self.queue.result_cache = cache
        self.queue.cache_meta = self._cache_meta
        self.queue.cache_epoch = self._cache_epoch

    def attach_admission(self, controller) -> None:
        """Wire an AdmissionController (autopilot/admission.py): the
        queue's pop sweep sheds/defers over-budget tenants before
        coalescing."""
        self.queue.admission = controller.review

    def _cacheable(self, app_key: str, args: dict, guard):
        """The lane source when (app_key, args, guard) is cacheable —
        a point query (batch_query_key contract) with its lane arg
        present and no guard armed (guarded runs carry verdicts a
        cache must not replay) — else None."""
        if self._cache is None or (guard or self.guard) is not None:
            return None
        app = self.apps.get(app_key)
        bq = getattr(app, "batch_query_key", None) if app else None
        if bq is None:
            return None
        return args.get(bq)

    def _cache_meta(self, req: QueryRequest):
        """(compat, source) for a cacheable request, else None — the
        queue's deliver() store hook."""
        source = self._cacheable(req.app_key, req.args, req.guard)
        if source is None:
            return None
        return (self._compat_key(req), source)

    def _deliver_cached(self, app_key: str, args: dict, entry, *,
                        max_rounds, priority, deadline_s,
                        tenant) -> QueryRequest:
        """Serve one cache hit WITHOUT dispatching: mint the request +
        result pair, stamp zeroed stages (no queue wait, no device
        time — honest, not missing), emit a `serve_query` span with
        ``cached=true``, run the SAME `slo.observe` accounting as a
        delivered result, and push it on the queue's out-of-band
        channel so every pump/drain surface returns it."""
        import time as _time

        from libgrape_lite_tpu.obs import slo

        t0_ns = _time.perf_counter_ns()
        req = QueryRequest(
            app_key=app_key, args=dict(args), max_rounds=max_rounds,
            priority=int(priority), deadline_s=deadline_s,
            tenant=tenant,
        )
        req.popped_s = req.submitted_s
        vals, rounds, code = entry
        res = ServeResult(
            request_id=req.id, app_key=app_key, ok=True, values=vals,
            rounds=rounds, terminate_code=code, batch_size=1,
            stages={"queue_wait_us": 0, "window_wait_us": 0,
                    "dispatch_us": 0, "device_us": 0, "harvest_us": 0},
        )
        res.latency_s = _time.perf_counter() - req.submitted_s
        req.result = res
        self.stats["cache_hits"] += 1
        slo.observe(app_key, tenant, res.latency_s, True)
        tr = obs.tracer()
        if tr.enabled:
            tr.emit_span_raw(
                "serve_query", t0_ns=t0_ns,
                dur_ns=_time.perf_counter_ns() - t0_ns,
                tid=tr.lane_tid(0), query_id=req.id, app=app_key,
                lane=0, rounds=rounds, ok=True, cached=True,
                tenant=tenant or "", queue_wait_us=0,
            )
        self.queue.push_oob(res)
        return req

    def submit(self, app_key: str, args: dict | None = None, *,
               max_rounds: int | None = None,
               guard: str | None = None, priority: int = 0,
               deadline_s: float | None = None,
               tenant: str | None = None) -> QueryRequest:
        if self._closed:
            raise RuntimeError("session is closed")
        args = dict(args or {})
        # result-cache probe BEFORE coalescing (autopilot/cache.py): a
        # hit never enters the queue at all — the device, the batch
        # planner, and the admission sweep all skip it
        source = self._cacheable(app_key, args, guard)
        if source is not None:
            compat = self._compat_for(app_key, args, max_rounds,
                                      guard, tenant)
            fence = self._cache_epoch()
            entry = self._cache.lookup(compat, source, fence)
            if entry is not None:
                return self._deliver_cached(
                    app_key, args, entry, max_rounds=max_rounds,
                    priority=priority, deadline_s=deadline_s,
                    tenant=tenant,
                )
        return self.queue.submit(
            app_key, args, max_rounds=max_rounds, guard=guard,
            priority=priority, deadline_s=deadline_s, tenant=tenant,
        )

    def pump(self, **kw) -> List[ServeResult]:
        return self.queue.pump(**kw)

    def drain(self) -> List[ServeResult]:
        return self.queue.drain()

    def async_pump(self, window: int | None = None):
        """An AsyncServePump over this session (serve/pipeline.py):
        up to `window` coalesced batches dispatched-but-unharvested at
        once (default: `policy.inflight`).  W=1 is byte- and
        result-order-identical to the synchronous `pump`/`drain`
        loop; the synchronous loop itself is untouched either way."""
        from libgrape_lite_tpu.serve.pipeline import AsyncServePump

        return AsyncServePump(self, window=window)

    def serve(self, stream) -> List[ServeResult]:
        """Scripted-stream convenience: submit every item, drain, and
        return results in completion order.  Items are (app_key, args)
        pairs or {"app": ..., "args": {...}, "max_rounds": ...,
        "guard": ...} dicts — the CLI `serve` subcommand's format."""
        for item in stream:
            if isinstance(item, dict):
                self.submit(
                    item["app"], item.get("args"),
                    max_rounds=item.get("max_rounds"),
                    guard=item.get("guard"),
                    priority=item.get("priority", 0),
                    deadline_s=item.get("deadline_s"),
                    tenant=item.get("tenant"),
                )
            else:
                app_key, args = item
                self.submit(app_key, args)
        return self.drain()

    # ---- dispatch ---------------------------------------------------------

    def _dispatch(self, batch: List[QueryRequest]) -> List[ServeResult]:
        """Run one coalesced batch: a single query through the plain
        fused path, several through the vmapped batched runner (guarded
        or not), with a sequential fallback for apps that cannot batch
        (host-only loops, mutation apps).  Per-request outcomes never
        raise out of the serve loop — failures become error results."""
        self.stats["batches"] += 1
        self.stats["queries"] += len(batch)
        try:
            w = self.worker(batch[0].app_key)
        except ValueError as e:
            # unknown app: fail these requests, keep the loop serving
            self.stats["failed"] += len(batch)
            return [
                ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={"error": str(e)}, lane=b,
                    batch_size=len(batch),
                )
                for b, req in enumerate(batch)
            ]
        try:
            self._ensure_dyn_view(batch[0].app_key, w)
        except Exception as e:
            # a failed forced repack (e.g. the fragment was loaded
            # without retain_edge_list) must not raise out of the
            # serve loop — the popped requests get error results
            self.stats["failed"] += len(batch)
            return [
                ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={"error": f"{type(e).__name__}: {e}"},
                    lane=b, batch_size=len(batch),
                )
                for b, req in enumerate(batch)
            ]
        guard = batch[0].guard or self.guard
        mr = batch[0].max_rounds
        tr = obs.tracer()

        if len(batch) > 1:
            try:
                w._check_batchable()
            except ValueError:
                self.stats["sequential_fallbacks"] += 1
                return [
                    r for req in batch
                    for r in [self._run_single(w, req, guard)]
                ]
            with tr.span("serve_batch", app=batch[0].app_key,
                         batch=len(batch)) as sp:
                results = self._run_batched(w, batch, mr, guard)
            if tr.enabled:
                # one track per query: the lane's interval IS the batch
                # dispatch interval, tagged with its request id so the
                # timeline stays attributable after coalescing
                for b, (req, res) in enumerate(zip(batch, results)):
                    tr.emit_span_raw(
                        "serve_query", t0_ns=sp.t0_ns,
                        dur_ns=sp.dur_ns, tid=tr.lane_tid(b),
                        query_id=req.id, app=req.app_key, lane=b,
                        rounds=res.rounds, ok=res.ok,
                        tenant=req.tenant or "",
                        queue_wait_us=self._queue_wait_us(req),
                    )
            return results

        with tr.span("serve_batch", app=batch[0].app_key, batch=1) as sp:
            res = self._run_single(w, batch[0], guard)
        if tr.enabled:
            tr.emit_span_raw(
                "serve_query", t0_ns=sp.t0_ns, dur_ns=sp.dur_ns,
                tid=tr.lane_tid(0), query_id=batch[0].id,
                app=batch[0].app_key, lane=0, rounds=res.rounds,
                ok=res.ok, tenant=batch[0].tenant or "",
                queue_wait_us=self._queue_wait_us(batch[0]),
            )
        return [res]

    @staticmethod
    def _queue_wait_us(req: QueryRequest) -> int:
        """submit->pop µs for one request (0 before the pop stamp)."""
        if not req.popped_s:
            return 0
        return int((req.popped_s - req.submitted_s) * 1e6)

    @staticmethod
    def _exec_stages(w: Worker, total_ns: int) -> dict:
        """Batch-level stage split of one synchronous dispatch, from
        the worker's host stamps when the path decomposed (fused /
        batched runners) — otherwise the whole execute is attributed
        to dispatch_us (guarded/stepwise/host paths run host work and
        device chunks interleaved; pretending to split them would be
        a made-up number, not a measurement)."""
        st = w.last_stage_ns
        if st is not None:
            return {
                "window_wait_us": 0,
                "dispatch_us": st["dispatch"] // 1000,
                "device_us": st["device"] // 1000,
            }
        return {
            "window_wait_us": 0,
            "dispatch_us": total_ns // 1000,
            "device_us": 0,
        }

    def _run_single(self, w: Worker, req: QueryRequest,
                    guard) -> ServeResult:
        import time as _time

        from libgrape_lite_tpu.guard.monitor import GuardError

        try:
            t0 = _time.perf_counter_ns()
            w.query(req.max_rounds, guard=guard, **req.args)
            t_exec = _time.perf_counter_ns()
            with obs.tracer().span("serve.harvest", batch=1):
                vals = w.result_values()
            stages = self._exec_stages(w, t_exec - t0)
            stages["harvest_us"] = (
                _time.perf_counter_ns() - t_exec
            ) // 1000
            if _calibration_harvester() is not None:
                _calibration_harvester()(w, stages, w.rounds)
            return ServeResult(
                request_id=req.id, app_key=req.app_key, ok=True,
                values=vals, rounds=w.rounds,
                terminate_code=w._terminate_code, batch_size=1,
                stages=stages,
            )
        except GuardError as e:
            self.stats["failed"] += 1
            return ServeResult(
                request_id=req.id, app_key=req.app_key, ok=False,
                error=e.bundle, rounds=w.rounds, batch_size=1,
            )
        except Exception as e:  # one bad query must not kill the loop
            self.stats["failed"] += 1
            return ServeResult(
                request_id=req.id, app_key=req.app_key, ok=False,
                error={"error": f"{type(e).__name__}: {e}"},
                batch_size=1,
            )

    def _run_batched(self, w: Worker, batch: List[QueryRequest],
                     mr, guard) -> List[ServeResult]:
        import time as _time

        try:
            t0 = _time.perf_counter_ns()
            w.query_batch(
                [req.args for req in batch], mr, guard=guard
            )
            t_exec = _time.perf_counter_ns()
        except Exception as e:  # whole-batch failure: every lane errors
            self.stats["failed"] += len(batch)
            return [
                ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={"error": f"{type(e).__name__}: {e}"},
                    lane=b, batch_size=len(batch),
                )
                for b, req in enumerate(batch)
            ]
        stages = self._exec_stages(w, t_exec - t0)
        if _calibration_harvester() is not None:
            # the vmapped batch runs every lane to the max round in
            # lockstep, so the device stamp covers rounds x lanes of
            # the per-round ledger columns
            br = w.batch_rounds
            rounds = (max(int(r) for r in br)
                      if br is not None and len(br) else w.rounds)
            _calibration_harvester()(w, stages, rounds * len(batch))
        results = []
        breaches = w.batch_breaches or [None] * len(batch)
        with obs.tracer().span("serve.harvest", batch=len(batch)):
            for b, req in enumerate(batch):
                if breaches[b] is not None:
                    self.stats["failed"] += 1
                    results.append(ServeResult(
                        request_id=req.id, app_key=req.app_key, ok=False,
                        error=breaches[b], rounds=int(w.batch_rounds[b]),
                        lane=b, batch_size=len(batch),
                        stages=dict(stages),
                    ))
                else:
                    results.append(ServeResult(
                        request_id=req.id, app_key=req.app_key, ok=True,
                        values=w.batch_result_values(b),
                        rounds=int(w.batch_rounds[b]),
                        terminate_code=int(w.batch_terminate[b]),
                        lane=b, batch_size=len(batch),
                        stages=dict(stages),
                    ))
        # per-lane extraction happened inside the loop above: the
        # batch-level harvest stage is the whole post-sync interval
        harvest_us = (_time.perf_counter_ns() - t_exec) // 1000
        for r in results:
            r.stages["harvest_us"] = harvest_us
        return results
