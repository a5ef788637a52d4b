"""Admission queue: accept queries, coalesce compatible ones, dispatch.

Execution model: the queue is a host-side FIFO pumped by the caller
(a scripted stream, the CLI `serve` subcommand, or bench.py's
throughput lane) — no background thread, so results are deterministic
and testable.  `submit` enqueues, `pump` ships at most one batch when
the policy says it is ready (full, or the head has waited
`max_wait_s`), `drain` pumps until empty.  FIFO order is preserved per
compatibility class; a batch is the head request plus the next
compatible requests in arrival order (requests BETWEEN them stay
queued — admission never reorders within a class, and an incompatible
head never blocks forever because `drain`/timeout forces partial
batches).

Scheduling (r13): requests carry an optional `priority` class — the
queue always serves the highest class present, FIFO within a class,
and classes never coalesce — and an optional `deadline_s`; a request
whose deadline passes before it dispatches FAILS as a ServeResult
with the recorded reason (`take_expired` returns them through every
pump/drain surface), never a silent drop.  `submit` is thread-safe
against `_pop_ready` (one lock) so the threaded admission front
(serve/feeder.py) can produce while the pump consumes.

The pop/dispatch/deliver split (`_pop_ready` / the dispatch callback /
`deliver`) exists for the async pump (serve/pipeline.py): the pump
pops ready batches with the SAME policy decision this module's own
`pump` uses, keeps up to W of them dispatched-but-unharvested, and
delivers through the same bookkeeping — so batch composition, FIFO
order, the batch-size histogram, and the admission-wait record are
one implementation regardless of how many batches are in flight.
"""

from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from libgrape_lite_tpu.serve.policy import BatchPolicy

_IDS = itertools.count()


def latency_summary_ms(latencies) -> dict:
    """{n, p50_ms, p99_ms} of a latency list (seconds in, ms out) —
    THE one percentile convention (sorted ascending, index
    `min(n-1, int(n*p))`) shared by the admission-wait record, the
    CLI global and per-app summaries, and the fleet per-replica /
    per-tenant summaries.  Five hand-rolled copies of this index
    arithmetic would drift; one helper cannot."""
    if not latencies:
        return {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0}
    lat = sorted(latencies)
    return {
        "n": len(lat),
        "p50_ms": round(1e3 * lat[len(lat) // 2], 3),
        "p99_ms": round(
            1e3 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 3
        ),
    }


@dataclass
class QueryRequest:
    """One admitted query (serve/): app + args + the limits that gate
    coalescing (policy.compat_key).

    `priority` picks the scheduling class: the queue always serves the
    highest class present, FIFO within a class, and requests of
    different classes never coalesce.  `deadline_s` (seconds from
    submission) expires a request that has not DISPATCHED in time —
    it fails as a ServeResult with the recorded reason, never a
    silent drop.  `tenant` (fleet/) tags the owning tenant; requests
    of different tenants never share a batched dispatch, so one
    tenant's poisoned lane can never fail a batchmate tenant."""

    app_key: str
    args: dict
    max_rounds: Optional[int] = None
    guard: Optional[str] = None
    priority: int = 0
    deadline_s: Optional[float] = None
    tenant: Optional[str] = None
    id: int = field(default_factory=lambda: next(_IDS))
    submitted_s: float = field(default_factory=time.perf_counter)
    # stamped by _pop_ready when the request leaves the queue: the
    # submit->pop interval is the per-request queue_wait_us stage
    popped_s: float = 0.0
    result: Optional["ServeResult"] = None

    @property
    def done(self) -> bool:
        return self.result is not None


class ServeResult:
    """Per-query outcome: either assembled values or a structured
    error (a guard breach bundle for poisoned lanes — batchmates of a
    breached query complete normally, serve/batch.py isolates lanes).

    `values` has a DEFERRED form for the async pump
    (serve/pipeline.py): constructed with `values_fn` instead of
    `values`, the [fnum, vp] assembly (device sync + finalize) runs
    the first time `values` is read — or when the harvest stage drains
    the batch, whichever comes first — so host-side extraction of
    batch N-1 overlaps device execution of batch N.  Synchronous
    construction with `values=` is unchanged, and a resolved result is
    indistinguishable from an eager one."""

    __slots__ = ("request_id", "app_key", "ok", "rounds",
                 "terminate_code", "error", "lane", "batch_size",
                 "latency_s", "stages", "_values", "_values_fn")

    def __init__(self, request_id: int, app_key: str, ok: bool,
                 values: Optional[np.ndarray] = None, rounds: int = 0,
                 terminate_code: int = 0, error: Optional[dict] = None,
                 lane: int = 0, batch_size: int = 1,
                 latency_s: float = 0.0,
                 values_fn: Optional[Callable[[], np.ndarray]] = None,
                 stages: Optional[dict] = None):
        self.request_id = request_id
        self.app_key = app_key
        self.ok = ok
        self.rounds = rounds
        self.terminate_code = terminate_code
        self.error = error  # breach bundle / failure detail
        self.lane = lane  # position inside the dispatched batch
        self.batch_size = batch_size
        self.latency_s = latency_s  # submit -> result delivery
        # stage decomposition of the latency (µs ints): queue_wait_us
        # (submit->pop, per request) + window_wait_us / dispatch_us /
        # device_us / harvest_us (batch-level, same for every lane of
        # one dispatch).  deliver() fills queue_wait_us; the dispatch
        # paths fill the rest — a failed request may carry a partial
        # dict, never a missing one after delivery.
        self.stages = stages
        self._values = values  # [fnum, vp] assembled
        self._values_fn = values_fn

    @property
    def values(self) -> Optional[np.ndarray]:
        if self._values is None and self._values_fn is not None:
            fn, self._values_fn = self._values_fn, None
            self._values = fn()
        return self._values

    @values.setter
    def values(self, v) -> None:
        self._values = v
        self._values_fn = None

    @property
    def deferred(self) -> bool:
        """True while the values are still an un-synced thunk."""
        return self._values_fn is not None

    def resolve(self) -> "ServeResult":
        """Force the deferred values now (the harvest stage's drain)."""
        self.values
        return self


class AdmissionQueue:
    """FIFO + coalescing front of a ServeSession.

    `dispatch(batch)` is the session's batched executor: it must
    return one ServeResult per request, in batch order.  The queue
    records a batch-size histogram — the serving bench's saturation
    signal (all-1 bars mean the stream never coalesced)."""

    def __init__(self, dispatch: Callable[[List[QueryRequest]],
                                          List[ServeResult]],
                 policy: BatchPolicy | None = None,
                 compat_key: Callable[[QueryRequest], tuple] | None = None):
        self._dispatch = dispatch
        self.policy = policy or BatchPolicy()
        self._compat = compat_key or (
            lambda r: (r.app_key, r.max_rounds, r.guard or "", r.tenant)
        )
        self._pending: List[QueryRequest] = []
        # guards _pending (and the expired stash) against the threaded
        # admission front (serve/feeder.py): submit may run on a feeder
        # thread while the pump thread pops — everything else stays
        # single-threaded and the scripted mode pays one uncontended
        # acquire per call
        self._lock = threading.Lock()
        self.batch_hist: Dict[int, int] = {}
        self.completed = 0
        # deadline-expired and shed requests failed (never silently
        # dropped): counted here, reason on each result, results
        # returned by the next pump/drain via take_expired()
        self.expired = 0
        self.shed = 0
        self._expired_out: List[ServeResult] = []
        # optional admission-control hook (autopilot/admission.py):
        # callable(req) -> "admit" | "defer" | "shed", consulted by
        # the _pop_ready sweep BEFORE coalescing — shed requests fail
        # loudly (reason=shed_over_budget), deferred tenants queue
        # behind in-budget ones
        self.admission = None
        # optional result cache (autopilot/cache.py): deliver() stores
        # every OK result under its full identity; cache_meta(req)
        # returns (compat, source) for cacheable requests (None
        # otherwise) and cache_epoch() the current fence epoch — both
        # wired by ServeSession.attach_result_cache
        self.result_cache = None
        self.cache_meta = None
        self.cache_epoch = None
        # per-request submit->dispatch wait (seconds), recorded at pop
        # time next to the batch-size histogram: the admission-latency
        # half of the serving story (the histogram says how well the
        # stream coalesced; this says what the coalescing COST each
        # request at the head of the queue)
        self.admission_waits: List[float] = []

    def submit(self, app_key: str, args: dict | None = None, *,
               max_rounds: int | None = None,
               guard: str | None = None, priority: int = 0,
               deadline_s: float | None = None,
               tenant: str | None = None) -> QueryRequest:
        req = QueryRequest(
            app_key=app_key, args=dict(args or {}),
            max_rounds=max_rounds, guard=guard,
            priority=int(priority), deadline_s=deadline_s,
            tenant=tenant,
        )
        with self._lock:
            self._pending.append(req)
        return req

    def pending(self) -> int:
        return len(self._pending)

    def _expire_overdue(self, now: float) -> None:
        """Fail (not drop) every pending request whose deadline passed
        before it dispatched: the request gets an error ServeResult
        with the recorded reason and rides out through take_expired().
        Caller holds the lock."""
        live: List[QueryRequest] = []
        swept: List[int] = []
        for req in self._pending:
            if (req.deadline_s is not None
                    and now - req.submitted_s > req.deadline_s):
                waited = now - req.submitted_s
                res = ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={
                        "error": "deadline expired before dispatch",
                        "reason": "deadline_expired",
                        "deadline_s": req.deadline_s,
                        "waited_s": round(waited, 6),
                    },
                    latency_s=waited,
                    stages={"queue_wait_us": int(waited * 1e6)},
                )
                req.result = res
                self._expired_out.append(res)
                self.expired += 1
                self.completed += 1
                swept.append(req.id)
                # a query that never dispatched still BURNS its
                # tenant's error budget — without this, the tenant
                # that caused a deadline storm never paid for it
                # (slo.observe never raises and takes no queue locks;
                # safe under the queue lock like the recorder below)
                from libgrape_lite_tpu.obs import slo

                slo.observe(req.app_key, req.tenant, waited, ok=False)
            else:
                live.append(req)
        self._pending = live
        if swept:
            from libgrape_lite_tpu.obs.recorder import (
                DEADLINE_STORM_THRESHOLD,
                RECORDER,
            )

            RECORDER.record("deadline_expired", n=len(swept),
                            ids=swept[:16])
            if len(swept) >= DEADLINE_STORM_THRESHOLD:
                # a deadline STORM — one sweep failing a window's
                # worth of requests — is a postmortem trigger, not
                # just a counter (recorder never raises; safe under
                # the queue lock, it takes no queue locks itself)
                RECORDER.trigger("deadline_storm", extra={
                    "expired_in_sweep": len(swept),
                    "request_ids": swept[:64],
                    "pending": len(self._pending),
                })

    def _review_admission(self) -> set:
        """Run the attached admission hook over the pending list:
        shed requests fail loudly (the deadline-expiry discipline —
        counted, reasoned, SLO-observed, returned via take_expired),
        deferred requests stay queued but their tenants are returned
        so _head_batch serves in-budget tenants first.  Caller holds
        the lock."""
        deferred: set = set()
        if self.admission is None:
            return deferred
        live: List[QueryRequest] = []
        shed_n = 0
        for req in self._pending:
            try:
                verdict = self.admission(req)
            except Exception:
                verdict = "admit"  # a broken hook must not wedge admission
            if verdict == "shed":
                waited = time.perf_counter() - req.submitted_s
                res = ServeResult(
                    request_id=req.id, app_key=req.app_key, ok=False,
                    error={
                        "error": "shed: tenant over error budget",
                        "reason": "shed_over_budget",
                        "tenant": req.tenant or "",
                        "waited_s": round(waited, 6),
                    },
                    latency_s=waited,
                    stages={"queue_wait_us": int(waited * 1e6)},
                )
                req.result = res
                self._expired_out.append(res)
                self.shed += 1
                self.completed += 1
                shed_n += 1
                # shedding burns the shed tenant's budget too — the
                # same accounting rule as deadline expiry above
                from libgrape_lite_tpu.obs import slo

                slo.observe(req.app_key, req.tenant, waited, ok=False)
            else:
                if verdict == "defer":
                    deferred.add(req.tenant)
                live.append(req)
        self._pending = live
        if shed_n:
            from libgrape_lite_tpu.obs.recorder import RECORDER

            RECORDER.record("shed_over_budget", n=shed_n)
        return deferred

    def take_expired(self) -> List[ServeResult]:
        """Drain the out-of-band results — deadline-expired and shed
        failures, plus cache-hit results that never dispatched
        (pump/drain and the async pump call this so such a request is
        always RETURNED to the driver, never silently dropped)."""
        with self._lock:
            out, self._expired_out = self._expired_out, []
        return out

    def push_oob(self, res: ServeResult) -> None:
        """Append one out-of-band result (a cache hit served without
        dispatching — serve/session.py) to the take_expired channel,
        so every pump/drain surface returns it like any other."""
        with self._lock:
            self._expired_out.append(res)
            self.completed += 1

    def _head_batch(self, deferred: set = frozenset()
                    ) -> List[QueryRequest]:
        """The head request plus the next compatible requests in FIFO
        order, up to max_batch lanes.  The head is the FIRST request
        of the HIGHEST priority class present (FIFO within a class);
        only same-class requests may join its batch, so a low-priority
        straggler never rides an urgent dispatch.  Tenants in
        `deferred` (admission control: past error budget) queue
        BEHIND everyone else: they only head a batch when nothing
        in-budget is pending, so deferral never becomes starvation."""
        cands = [r for r in self._pending if r.tenant not in deferred]
        if not cands:
            cands = self._pending
        top = max(r.priority for r in cands)
        head = next(r for r in cands if r.priority == top)
        key = self._compat(head)
        batch = [head]
        seen_head = False
        for req in self._pending:
            if req is head:
                seen_head = True
                continue
            if not seen_head:
                continue
            if len(batch) >= self.policy.max_batch:
                break
            if req.priority == top and self._compat(req) == key:
                batch.append(req)
        return batch

    def _pop_ready(self, now: float | None = None, *,
                   force: bool = False) -> List[QueryRequest]:
        """Pop at most ONE ready batch off the queue — the policy
        decision shared by the synchronous `pump` and the async pump's
        dispatch stage (serve/pipeline.py).  Ready = full, head waited
        `max_wait_s`, or `force`d.  Expires overdue deadlines and runs
        the admission hook first (failed results, via take_expired).
        Records each popped request's submit->dispatch wait.
        [] = nothing ready."""
        now = time.perf_counter() if now is None else now
        with self._lock:
            self._expire_overdue(now)
            deferred = self._review_admission()
            if not self._pending:
                return []
            batch = self._head_batch(deferred)
            if not force and len(batch) < self.policy.max_batch:
                head_wait = now - batch[0].submitted_s
                if head_wait < self.policy.max_wait_s:
                    return []
            ids = {r.id for r in batch}
            self._pending = [
                r for r in self._pending if r.id not in ids
            ]
        t_pop = time.perf_counter()
        from libgrape_lite_tpu import obs

        hist = obs.metrics().histogram(
            "grape_serve_admission_wait_seconds",
            help="per-request submit->dispatch wait in the "
                 "admission queue",
        )
        for req in batch:
            req.popped_s = t_pop
            wait = t_pop - req.submitted_s
            self.admission_waits.append(wait)
            hist.observe(wait)
        return batch

    def deliver(self, batch: List[QueryRequest],
                results: List[ServeResult]) -> List[ServeResult]:
        """Bind one dispatched batch's results to its requests
        (latency stamping, histogram/completion bookkeeping) — shared
        by the synchronous `pump` and the async pump's harvest stage,
        so the two loops account identically."""
        if len(results) != len(batch):
            raise RuntimeError(
                f"dispatch returned {len(results)} results for a "
                f"{len(batch)}-lane batch"
            )
        t_done = time.perf_counter()
        from libgrape_lite_tpu.obs import slo

        for req, res in zip(batch, results):
            res.latency_s = t_done - req.submitted_s
            st = res.stages
            if st is None:
                st = res.stages = {}
            if "queue_wait_us" not in st and req.popped_s:
                st["queue_wait_us"] = int(
                    (req.popped_s - req.submitted_s) * 1e6
                )
            req.result = res
            # the ONE bookkeeping site shared by the sync loop, the
            # async pump, and every fleet replica — so SLO accounting
            # cannot drift between serving modes (no-op when no
            # objectives are configured; never raises)
            slo.observe(req.app_key, req.tenant, res.latency_s,
                        res.ok)
            # result-cache store (autopilot/cache.py), same shared
            # site: sync loop, async pump, and fleet replicas all
            # deliver here, so every cacheable OK result is stored
            # regardless of serving mode.  The key carries the FULL
            # compat identity + source + fence epoch (grape-lint R9).
            if self.result_cache is not None and res.ok:
                meta = self.cache_meta(req) if self.cache_meta else None
                if meta is not None:
                    compat, source = meta
                    fence = self.cache_epoch() if self.cache_epoch else 0
                    self.result_cache.store(compat, source, fence, res)
        self.batch_hist[len(batch)] = (
            self.batch_hist.get(len(batch), 0) + 1
        )
        self.completed += len(batch)
        return results

    def admission_wait_summary(self) -> dict:
        """p50/p99 of the recorded submit->dispatch waits, in ms (the
        CLI `serve` summary and the bench serve_async block surface
        this next to qps)."""
        return latency_summary_ms(self.admission_waits)

    def pump(self, now: float | None = None, *,
             force: bool = False) -> List[ServeResult]:
        """Dispatch at most ONE batch: when it is full, when the head
        request has waited `max_wait_s`, or when `force`d (drain).
        Returns the delivered results, including any deadline-expired
        failures ([] = nothing was ready)."""
        from libgrape_lite_tpu import obs

        tr = obs.tracer()
        with tr.span("serve.pop"):
            batch = self._pop_ready(now, force=force)
            out = self.take_expired()
        if not batch:
            return out
        results = self._dispatch(batch)
        with tr.span("serve.deliver", batch=len(batch)):
            out.extend(self.deliver(batch, results))
        return out

    def drain(self) -> List[ServeResult]:
        """Pump until the queue is empty (partial batches forced) —
        the scripted-stream mode of the CLI `serve` subcommand."""
        out: List[ServeResult] = self.take_expired()
        while self._pending:
            out.extend(self.pump(force=True))
        return out
