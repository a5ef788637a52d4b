"""Async serving pipeline: overlapped admission/dispatch/harvest.

The synchronous serve loop (`AdmissionQueue.pump` ->
`ServeSession._dispatch`) runs one coalesced batch, blocks pulling
every lane's result to host, and only then lets the queue pick the
next batch — the device idles during admission/coalescing/extraction
and the host idles while the device runs, and a live `--delta_stream`
ingest serialises against both.  JAX async dispatch makes the fix
structural: a dispatched runner returns un-synced device refs, so the
pump can keep a WINDOW of W dispatched batches in flight and harvest
lazily.  Three stages, one host thread, no background workers
(deterministic and testable, like the sync queue):

* **dispatch** (`_fill`/`_dispatch_stage`): pop ready batches with the
  queue's own policy decision (`AdmissionQueue._pop_ready` — same
  batch composition, same FIFO order) and dispatch them un-synced
  through `Worker.query_batch_dispatch` until the window holds W.
  This stage must never force a host sync — grape-lint R7
  (`sync-in-pump`) fossilizes that, judging this module's dispatch
  code against the `PUMP_HARVEST_SYNCS` contract below.
* **harvest** (`_harvest_head`): drain completed batches FIFO — the
  head batch's verdicts sync and its per-lane values extract
  (`ServeResult` deferred-values form) while batches behind it are
  still executing, so host-side extraction of batch N-1 overlaps
  device execution of batch N.  FIFO harvest makes result order
  identical to the synchronous loop by construction.
* **ingest barrier** (`ingest`): a delta apply is a barrier item — the
  pump quiesces the window (the superstep-boundary invariant the dyn
  overlay relies on is an explicit drain here, not an accident of the
  sync loop), applies the delta, and refills.

W=1 is pinned byte-identical and result-order-identical to the
synchronous loop (tests/test_serve_async.py runs the full matrix),
and the synchronous path itself is untouched when no pump is attached.
Batches the window cannot hold un-synced — host-only sequential
fallbacks, guarded single queries, dyn force-repacks (a barrier:
the fold rebuilds the fragment under every resident worker) — run
through the session's own synchronous dispatch, and EVERY such
decline is recorded in `PUMP_STATS`, never silent.

docs/SERVING.md ("The async pump") is the user guide; the CLI surface
is `--inflight W`, and bench.py's `serve_async` block A/Bs W in {1,4}
with concurrent ingest.
"""

from __future__ import annotations

import os
import time
from typing import List, Optional

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.serve.queue import QueryRequest, ServeResult

#: env override for the dispatch-window depth: set GRAPE_SERVE_INFLIGHT=1
#: to force the serial discipline on any pump without touching call
#: sites (the override is recorded in PUMP_STATS, never silent).
INFLIGHT_ENV = "GRAPE_SERVE_INFLIGHT"

#: the audited harvest contract (grape-lint R7 `sync-in-pump`): the
#: ONLY methods of this module that may force a host sync
#: (block_until_ready / device_get / np.asarray / int()/float() on a
#: device value).  R7 walks every self-call chain rooted at a
#: dispatch-stage method (`_fill*` / `_dispatch*`) and flags any sync
#: forcer reached outside these names — the defect class this module
#: exists to remove, fossilized so it cannot creep back in.
PUMP_HARVEST_SYNCS = frozenset({
    "_harvest_head",
    "_results_from_dispatch",
    "_run_declined",
    "drain",
    "harvest",
    "quiesce",
})


class PumpStats:
    """Every engage/decline of the dispatch window — the recorded-
    decision discipline the partition/backend ledgers use, applied to
    serving: a batch that could not ride the window (sequential
    fallback, dyn force-repack, guarded single) or a window forced
    narrower than asked (W=1 env) is COUNTED with its reason, so a
    pump that silently degraded to the serial discipline is visible
    in one dict instead of a wall-clock mystery."""

    #: events kept for inspection — bounded so a long-lived serving
    #: process (the module's use case) never grows it without limit
    MAX_EVENTS = 256

    def __init__(self):
        self.engaged = 0
        self.declines = {}
        self.events: List[dict] = []

    def _record(self, ev: dict) -> None:
        self.events.append(ev)
        if len(self.events) > self.MAX_EVENTS:
            del self.events[: self.MAX_EVENTS // 2]

    def engage(self, **detail) -> None:
        self.engaged += 1
        self._record({"kind": "engage", **detail})

    def decline(self, reason: str, **detail) -> None:
        self.declines[reason] = self.declines.get(reason, 0) + 1
        self._record({"kind": "decline", "reason": reason, **detail})

    def snapshot(self) -> dict:
        return {"engaged": self.engaged,
                "declines": dict(self.declines)}

    def reset(self) -> None:
        self.engaged = 0
        self.declines = {}
        self.events = []


#: module-level record shared by every pump in the process:
#: tests/bench read it, reset() between runs
PUMP_STATS = PumpStats()

# federated as "pump" (obs/federation.py): the class keeps its own
# snapshot()/reset() protocol; the federation just routes to it
from libgrape_lite_tpu.obs import federation as _federation  # noqa: E402

_federation.register("pump", PUMP_STATS.snapshot, PUMP_STATS.reset,
                     module=__name__)


class PendingBatch:
    """One admitted batch inside the dispatch window: the popped
    requests plus either ready results (a recorded decline ran the
    synchronous path) or a prepared/launched dispatch the harvest
    stage turns into results.  `prepared` is the host-side half
    (state built + placed, runner resolved); `dispatch` appears once
    the pump launches it — launches are STAGGERED so executions never
    oversubscribe the backend while preparation and extraction
    overlap whatever is executing."""

    __slots__ = ("batch", "mode", "results", "prepared", "dispatch",
                 "reason", "t0_ns", "t_admit_ns", "t_launch_ns",
                 "disp_ns")

    def __init__(self, batch: List[QueryRequest], mode: str,
                 results: Optional[List[ServeResult]] = None,
                 prepared=None, dispatch=None, reason: str = ""):
        self.batch = batch
        self.mode = mode  # "ready" | "deferred"
        self.results = results
        self.prepared = prepared
        self.dispatch = dispatch
        self.reason = reason
        self.t0_ns = 0
        # stage stamps (host perf_counter_ns): window admission time,
        # execution-launch time, and accumulated host dispatch work
        # (prepare + launch enqueue) — the harvest stage turns these
        # into each lane's window_wait/dispatch/device/harvest µs
        self.t_admit_ns = 0
        self.t_launch_ns = 0
        self.disp_ns = 0

    @property
    def launched(self) -> bool:
        return self.mode == "ready" or self.dispatch is not None

    def ready(self) -> bool:
        if self.mode == "ready":
            return True
        if self.dispatch is None:
            return False  # prepared but not yet executing
        return self.dispatch.is_ready()


class AsyncServePump:
    """Overlapped admission/dispatch/harvest over one ServeSession.

    Construction attaches the pump to the session (`session._pump`),
    which makes `session.ingest` barrier-safe no matter which surface
    calls it.  `window` defaults to `session.policy.inflight`;
    GRAPE_SERVE_INFLIGHT overrides either (recorded).  One host
    thread: `pump()` steps, `drain()` finishes, `ingest()` is the
    barrier item.  Results are delivered in dispatch order (FIFO
    harvest), so W=1 reproduces the synchronous loop exactly."""

    def __init__(self, session, window: int | None = None, *,
                 eager_values: bool = True):
        self.session = session
        w = int(window if window is not None
                else getattr(session.policy, "inflight", 1))
        env = os.environ.get(INFLIGHT_ENV, "")
        if env:
            w_env = max(1, int(env))
            if w_env != w:
                PUMP_STATS.decline(
                    "inflight_env", asked=w, forced=w_env
                )
            w = w_env
        if w < 1:
            raise ValueError(f"window must be >= 1, got {w}")
        self.window = w
        # how many batches may be EXECUTING at once.  The window holds
        # W batches admitted + prepared (host work done); the launch
        # cap staggers their enqueue: on the CPU fallback concurrent
        # XLA executions fight for the same cores (measured ~0.9x), so
        # the default serialises execution and takes the win from
        # overlapping prepare/extract with the one running batch; on a
        # real accelerator the device queue serialises programs anyway,
        # so a deeper cap just keeps the queue fed.
        cap_env = os.environ.get("GRAPE_SERVE_LAUNCH_CAP", "")
        if cap_env:
            self.launch_cap = max(1, int(cap_env))
        else:
            import jax

            self.launch_cap = (
                1 if jax.default_backend() == "cpu" else w
            )
        # True (default): the harvest stage resolves every lane's
        # values as it drains the batch; False keeps them deferred so
        # the caller pays extraction on first read (ServeResult.values)
        self.eager_values = eager_values
        self._inflight: List[PendingBatch] = []
        # queries (not batches) dispatched so far: the budget surface
        # a streaming driver pins its ingest points on (`max_dispatch`
        # below), so the batch <-> graph-version interleave is
        # identical at every window depth
        self.dispatched_queries = 0
        self.stats = {
            "dispatched": 0, "harvested": 0, "max_inflight": 0,
            "overlapped_harvests": 0, "quiesces": 0,
        }
        session._pump = self

    # ---- bookkeeping ------------------------------------------------------

    def inflight(self) -> int:
        return len(self._inflight)

    def pending(self) -> int:
        return self.session.queue.pending()

    def close(self) -> None:
        """Detach from the session (drains first — in-flight work is
        never abandoned)."""
        self.quiesce(reason="close")
        if self.session._pump is self:
            self.session._pump = None

    # ---- dispatch stage (R7: no host syncs on these paths) ----------------

    def _fill(self, now: float | None = None, *, force: bool = False,
              max_dispatch: int | None = None) -> int:
        """Dispatch stage: admit ready batches into the window until
        it is full, the queue has nothing ready, or `max_dispatch`
        total dispatched queries is reached (checked before each
        batch, like the sync streaming loop's ingest_every — batches
        stay atomic)."""
        n = 0
        while len(self._inflight) < self.window:
            if (max_dispatch is not None
                    and self.dispatched_queries >= max_dispatch):
                break
            batch = self.session.queue._pop_ready(now, force=force)
            if not batch:
                break
            self._dispatch(batch)
            n += 1
        return n

    def _dispatch(self, batch: List[QueryRequest]) -> None:
        tr = obs.tracer()
        t_admit = time.perf_counter_ns()
        with tr.span(
            "serve_dispatch", app=batch[0].app_key, batch=len(batch),
            window=self.window, inflight=len(self._inflight),
            queue_depth=self.session.queue.pending(),
        ) as sp:
            pb = self._dispatch_stage(batch)
            sp.set(mode=pb.mode, reason=pb.reason)
        pb.t_admit_ns = t_admit
        pb.disp_ns = time.perf_counter_ns() - t_admit
        if tr.enabled:
            pb.t0_ns = sp.t0_ns
        self._inflight.append(pb)
        self.dispatched_queries += len(batch)
        self.stats["dispatched"] += 1
        self.stats["max_inflight"] = max(
            self.stats["max_inflight"], len(self._inflight)
        )
        self._launch_next()
        if tr.enabled:
            m = obs.metrics()
            m.gauge("grape_serve_window_depth").set(len(self._inflight))
            m.series("grape_serve_queue_depth_series").append(
                self.session.queue.pending()
            )

    def _fail_batch(self, pb: PendingBatch, e: Exception) -> None:
        """Whole-batch failure containment, the sync loop's contract
        carried into the window: one bad batch becomes per-lane error
        results and must not kill the pump or strand its neighbours."""
        self.session.stats["failed"] += len(pb.batch)
        pb.mode = "ready"
        pb.dispatch = None
        pb.results = [
            ServeResult(
                request_id=req.id, app_key=req.app_key, ok=False,
                error={"error": f"{type(e).__name__}: {e}"},
                lane=b, batch_size=len(pb.batch),
            )
            for b, req in enumerate(pb.batch)
        ]

    def _launch_next(self) -> None:
        """Enqueue prepared batches until `launch_cap` executions are
        in flight (FIFO — the head launches first).  No host sync:
        launch() of an unguarded batch only enqueues; a guarded
        batch's chunk loop runs here whole (its probes sync inside
        the worker by design — the audited guarded path, not a
        dispatch-stage stray).  A launch that raises fails ITS batch
        only (per-lane error results), like the sync loop's
        whole-batch containment."""
        launched = sum(
            1 for p in self._inflight
            if p.mode == "deferred" and p.dispatch is not None
        )
        for p in self._inflight:
            if launched >= self.launch_cap:
                break
            if p.mode == "deferred" and p.dispatch is None:
                t_l0 = time.perf_counter_ns()
                try:
                    p.dispatch = p.prepared.launch()
                except Exception as e:
                    self._fail_batch(p, e)
                    continue
                t_l1 = time.perf_counter_ns()
                p.disp_ns += t_l1 - t_l0
                p.t_launch_ns = t_l1
                launched += 1

    def _dispatch_stage(self, batch: List[QueryRequest]) -> PendingBatch:
        """Route one popped batch: un-synced through the window when
        the batched runner can hold it, otherwise the session's own
        synchronous dispatch with the decline recorded."""
        sess = self.session
        app_key = batch[0].app_key
        if app_key not in sess.apps:
            return self._run_declined(batch, "unknown_app")
        w = sess.worker(app_key)
        guard = batch[0].guard or sess.guard
        if (
            sess.dyn is not None
            and sess.dyn.overlay_count > 0
            and not getattr(w.app, "dyn_overlay_support", False)
        ):
            # the forced fold rebuilds the fragment under every
            # resident worker — a window barrier, not a window item
            return self._run_declined(batch, "dyn_force_repack")
        try:
            w._check_batchable()
        except ValueError:
            return self._run_declined(batch, "sequential_fallback")

        from libgrape_lite_tpu.guard.config import GuardConfig

        if len(batch) == 1 and GuardConfig.resolve(guard).enabled:
            # the sync loop runs single guarded queries through the
            # plain Worker.query guard machinery (incl. checkpointed
            # rollback) — keep that path, and its breach bundles,
            # bit-for-bit
            return self._run_declined(batch, "guarded_single")
        sess.stats["batches"] += 1
        sess.stats["queries"] += len(batch)
        try:
            prepared = w.query_batch_prepare(
                [req.args for req in batch], batch[0].max_rounds,
                guard=guard,
            )
        except Exception as e:  # whole-batch failure: per-lane errors
            sess.stats["failed"] += len(batch)
            return PendingBatch(batch, "ready", results=[
                ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={"error": f"{type(e).__name__}: {e}"},
                    lane=b, batch_size=len(batch),
                )
                for b, req in enumerate(batch)
            ], reason="dispatch_error")
        PUMP_STATS.engage(app=app_key, batch=len(batch),
                          guarded=prepared.guarded)
        return PendingBatch(batch, "deferred", prepared=prepared)

    def _run_declined(self, batch: List[QueryRequest],
                      reason: str) -> PendingBatch:
        """Synchronous fallback: the session's own dispatch loop, with
        the decline recorded in PUMP_STATS.  A dyn force-repack
        additionally quiesces the window FIRST — in-flight batches
        must land on the graph view they were admitted against."""
        if reason == "dyn_force_repack":
            self.quiesce(reason=reason)
        PUMP_STATS.decline(reason, app=batch[0].app_key,
                           batch=len(batch))
        return PendingBatch(
            batch, "ready", results=self.session._dispatch(batch),
            reason=reason,
        )

    # ---- harvest stage ----------------------------------------------------

    def _harvest_head(self, *, block: bool = True) -> List[ServeResult]:
        """Harvest stage: turn the window head into delivered results
        (FIFO — result order is the synchronous loop's).  With
        `block=False` an unsettled head is left in flight and []
        returns."""
        if not self._inflight:
            return []
        pb = self._inflight[0]
        if not block and not pb.ready():
            return []
        self._inflight.pop(0)
        tr = obs.tracer()
        overlapped = bool(self._inflight)
        with tr.span(
            "serve_harvest", app=pb.batch[0].app_key,
            batch=len(pb.batch), window=self.window,
            inflight=len(self._inflight), overlapped=overlapped,
            mode=pb.mode,
        ):
            if pb.mode == "ready":
                results = pb.results
            else:
                results = self._results_from_dispatch(pb)
        delivered = self.session.queue.deliver(pb.batch, results)
        self.stats["harvested"] += 1
        if overlapped:
            self.stats["overlapped_harvests"] += 1
        if tr.enabled:
            obs.metrics().gauge("grape_serve_window_depth").set(
                len(self._inflight)
            )
        return delivered

    def _results_from_dispatch(self, pb: PendingBatch) -> List[ServeResult]:
        """One deferred batch -> ServeResults: launch if the stagger
        hasn't yet (a window behind a slow head), sync the lane
        verdicts, hand the freed execution slot to the next prepared
        batch, THEN extract values — so the extraction (the host work
        the window exists to hide) overlaps the successor's
        execution."""
        sess = self.session
        try:
            if pb.dispatch is None:
                t_l0 = time.perf_counter_ns()
                pb.dispatch = pb.prepared.launch()
                t_l1 = time.perf_counter_ns()
                pb.disp_ns += t_l1 - t_l0
                pb.t_launch_ns = t_l1
            d = pb.dispatch.wait()
            t_sync = time.perf_counter_ns()
        except Exception as e:
            # JAX async dispatch surfaces runtime failures at the
            # sync point — the same whole-batch containment the sync
            # loop's _run_batched applies (one bad batch must not
            # kill the pump or strand the rest of the window)
            self._fail_batch(pb, e)
            self._launch_next()
            return pb.results
        # the head's execution has settled: keep the backend busy
        # while we extract below
        self._launch_next()
        batch = pb.batch
        tr = obs.tracer()
        if tr.enabled and not d.supersteps_counted:
            obs.metrics().counter("grape_supersteps_total").inc(
                int(d.rounds.sum()) + len(batch)
            )
        results: List[ServeResult] = []
        for b, req in enumerate(batch):
            if d.breaches[b] is not None:
                sess.stats["failed"] += 1
                results.append(ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error=d.breaches[b], rounds=int(d.rounds[b]),
                    lane=b, batch_size=len(batch),
                ))
            else:
                results.append(ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=True,
                    values_fn=(lambda dd=d, bb=b: dd.lane_values(bb)),
                    rounds=int(d.rounds[b]),
                    terminate_code=int(d.terminate[b]),
                    lane=b, batch_size=len(batch),
                ))
        if self.eager_values:
            for r in results:
                try:
                    r.resolve()
                except Exception as e:  # one lane's extraction failing
                    sess.stats["failed"] += 1  # must not strand the rest
                    r.ok = False
                    r.values = None
                    r.error = {"error": f"{type(e).__name__}: {e}"}
        t_h1 = time.perf_counter_ns()
        # window_wait overlaps the dispatch stage (admit -> launch
        # includes host prepare time) — an attribution aid, not a
        # partition; queue_wait is stamped at delivery by the queue.
        stages = {
            "window_wait_us": max(0, pb.t_launch_ns - pb.t_admit_ns) // 1000,
            "dispatch_us": pb.disp_ns // 1000,
            "device_us": max(0, t_sync - pb.t_launch_ns) // 1000,
            "harvest_us": max(0, t_h1 - t_sync) // 1000,
        }
        for r in results:
            r.stages = dict(stages)
        if tr.enabled:
            now_ns = time.perf_counter_ns()
            for b, (req, res) in enumerate(zip(batch, results)):
                # per-query lane attribution, dispatch -> harvest
                tr.emit_span_raw(
                    "serve_query", t0_ns=pb.t0_ns,
                    dur_ns=max(0, now_ns - pb.t0_ns),
                    tid=tr.lane_tid(b), query_id=req.id,
                    app=req.app_key, lane=b, rounds=res.rounds,
                    ok=res.ok, tenant=req.tenant or "",
                    queue_wait_us=int(
                        max(0.0, req.popped_s - req.submitted_s) * 1e6
                    ),
                )
        return results

    # ---- driving ----------------------------------------------------------

    def pump(self, now: float | None = None, *, force: bool = False,
             block: bool = False,
             max_dispatch: int | None = None) -> List[ServeResult]:
        """One pump step: fill the window (dispatch stage), drain every
        batch that has already settled, and — when the window is full
        with admitted work still waiting, or the caller passed
        `block=True` — harvest the head to make room so a waiting
        batch is never starved by a full window.  `max_dispatch` caps
        the TOTAL dispatched-query count (streaming drivers pin their
        ingest points with it).  Returns the results delivered THIS
        call ([] = nothing was ready)."""
        out: List[ServeResult] = []
        self._fill(now, force=force, max_dispatch=max_dispatch)
        # deadline-expired requests fail at pop time inside the queue;
        # surface them with this step's results (never silently lost)
        out.extend(self.session.queue.take_expired())
        while True:
            got = self._harvest_head(block=False)
            if not got:
                break
            out.extend(got)
            self._fill(now, force=force, max_dispatch=max_dispatch)
        if self._inflight and (
            block
            or (len(self._inflight) >= self.window
                and self.session.queue.pending() > 0)
        ):
            out.extend(self._harvest_head(block=True))
            self._fill(now, force=force, max_dispatch=max_dispatch)
        return out

    def drain(self) -> List[ServeResult]:
        """Dispatch + harvest until the queue AND the window are empty
        (partial batches forced) — the pump analogue of queue.drain."""
        out: List[ServeResult] = []
        while self.session.queue.pending() or self._inflight:
            self._fill(force=True)
            out.extend(self.session.queue.take_expired())
            out.extend(self._harvest_head(block=True))
        out.extend(self.session.queue.take_expired())
        return out

    def quiesce(self, reason: str = "quiesce") -> List[ServeResult]:
        """Drain the window WITHOUT admitting new batches — the
        explicit superstep-boundary barrier `ingest` relies on.
        Delivered results are bound to their requests as usual."""
        if not self._inflight:
            return []
        self.stats["quiesces"] += 1
        PUMP_STATS._record({
            "kind": "quiesce", "reason": reason,
            "inflight": len(self._inflight),
        })
        out: List[ServeResult] = []
        while self._inflight:
            out.extend(self._harvest_head(block=True))
        return out

    def ingest(self, ops, *, force_repack: bool = False) -> dict:
        """The barrier item: quiesce the window, then apply the delta
        through the session (overlay-only ingests stay zero-recompile
        — pinned by tests).  The window refills on the NEXT
        pump()/drain() step, never here: an eager refill would
        dispatch past the caller's ingest cadence and batches admitted
        after this barrier must see the post-delta graph the caller
        scheduled them against (the `max_dispatch` budget pins that
        interleave across window depths)."""
        self.quiesce(reason="ingest")
        return self.session.ingest(ops, force_repack=force_repack)
