"""Tracer: nested host spans with a disabled fast path.

Two design constraints rule this file:

1. **Disarmed cost is a branch, not a feature.**  Every call site in
   the worker's superstep loop runs `tracer.span(...)` unconditionally;
   with tracing off that call must cost well under a microsecond
   (pinned by tests/test_obs.py::test_disabled_span_overhead_budget),
   and the *compiled* fused path must be byte-identical to an
   obs-less build (pinned by the lowered-HLO test) — the same
   discipline guard/ established for guards-off.  A disabled tracer
   therefore returns one shared no-op span object, or under a
   profiler session the profiler mirror alone (below); no clock read,
   no buffering.

2. **Armed cost stays off the device path.**  Spans buffer into a
   `collections.deque` — append is a single GIL-atomic bytecode, so
   concurrent emitters (the superstep loop, the checkpoint writer
   thread, a retry loop) never contend on a lock — and nothing is
   serialized until `flush()`.

Timing convention (the satellite fix for `Worker.query_stepwise`):
JAX dispatch is asynchronous, so a naive `t1 - t0` around a jitted
call measures only host-side enqueue for every round except the one
that forces a host read.  A span's clock therefore stops only after
the caller has synced on the device results (`jax.block_until_ready`
on the full carry) — `dur` is honest wall time including device
execution.  Callers that want the split call `span.mark("dispatched")`
between the dispatch returning and the sync: the span then reports
`dispatched_us` (host enqueue) and `device_wait_us` (sync wait, the
device-execution estimate) in its args.  The first round after a
compile still includes trace+compile time in `dispatch_us`; spans
never try to hide that — instead the worker calls
`span.mark("compiled")` on any round whose runner came out of a jit
cache MISS, so the span carries `compiled_us` and a reader can keep
compile rounds out of a round's wall rather than silently folding
compile time into the measurement.

One span system, two sinks, one clock: every span is also a
`jax.profiler.TraceAnnotation` named `grape.<name>` with the span's
keyword arguments as its stats, armed or not.  Under a `jax.profiler`
session the interval lands in the `.xplane.pb` beside the device's
operations (benchmarks/reduce_scopes.py reads it).  An annotation
costs about 0.3 µs even with no session, which would more than double
the disarmed span, so the disarmed tracer asks the profiler first
(`TraceAnnotation.is_enabled()`, 20 ns) and makes none when nothing
records; an annotation opened outside a session is dropped by the
profiler anyway.  The JSONL sink keeps what no profiler window covers
(operators' long runs).

Set-up phases: a span whose name is in `SETUP_PHASES` is what a process
pays once (`LoadGraph` and its stages, a per-fragment structure built
on a cache miss, a runner that compiles, the native loader's build) and
is kept armed or not: on close it also appends one record to
`SETUP_LEDGER`, the federated namespace `setup`.  No name of that
vocabulary is ever opened by a warm query, so the disarmed `span()`
pays one set-membership test for it and nothing else.
"""

from __future__ import annotations

import itertools
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.obs.events import (
    FRAG_TID_BASE,
    counter_event,
    flow_event,
    instant_event,
    metadata_event,
    span_event,
)
from libgrape_lite_tpu.utils.memory import fullest_bytes_in_use


MIRROR_PREFIX = "grape."
_profiling = TraceAnnotation.is_enabled  # is a profiler session recording?

#: what a process pays once.  A span under one of these names is a
#: set-up phase: recorded in `SETUP_LEDGER` whether or not the tracer
#: is armed.  The rule that keeps the ledger off the hot path: a name
#: of this set opens only in `LoadGraph`, on a cache MISS of a
#: per-fragment structure, on a runner MISS, in the native loader's
#: build and where the compile cache is placed, never in a warm query
#: (docs/OBSERVABILITY.md has the table: site, args, bytes).
SETUP_PHASES = frozenset({
    "load_graph", "read_edges", "partition", "build_fragment",
    "deserialize", "serialize", "load.place",
    "native.build", "compile_cache", "runner.compile",
    "derived.mirror_plan", "derived.lcc_adjacency", "derived.place",
})
#: the phases that place arrays or load an executable: their records
#: carry `bytes_in_use` of the fullest local device at open and at
#: close.  All of them run with the backend up (reading the allocator
#: would start it otherwise).
SETUP_PLACING = frozenset({"load.place", "derived.place", "runner.compile"})
SETUP_LEDGER_CAP = 256


class SetupLedger:
    """The bounded record of the set-up phases this process closed, in
    closing order (a child before its parent).  One record: `name`,
    `parent` (the enclosing phase on the same thread, or None), `t0_ns`
    and `dur_ns` on `time.perf_counter_ns`, the span's `args`, and for
    a placing phase `bytes_in_use` `{"open", "close"}` (absent on a
    backend without allocator statistics, never 0 for unknown).  Past
    `cap` records nothing is kept and `dropped` counts."""

    def __init__(self, cap: int = SETUP_LEDGER_CAP):
        self.cap = cap
        self._records: list = []
        self.dropped = 0
        self._lock = threading.Lock()

    def append(self, record: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._records) < self.cap:
                self._records.append(record)
            else:
                self.dropped += 1

    def __len__(self) -> int:
        return len(self._records)

    def snapshot(self) -> Dict[str, Any]:
        """`records` for /federation and the postmortem bundle;
        `count`, `dropped`, `cap` and `seconds` (total by phase name)
        are what /metrics can show."""
        with self._lock:
            records = [dict(r) for r in self._records]
            dropped = self.dropped
        seconds: Dict[str, float] = {}
        for r in records:
            seconds[r["name"]] = (
                seconds.get(r["name"], 0.0) + r["dur_ns"] / 1e9
            )
        return {"records": records, "count": len(records),
                "dropped": dropped, "cap": self.cap, "seconds": seconds}

    def reset(self) -> None:
        with self._lock:
            self._records = []
            self.dropped = 0


SETUP_LEDGER = SetupLedger()
federation.register("setup", SETUP_LEDGER.snapshot, SETUP_LEDGER.reset,
                    module=__name__)
_open_phases = threading.local()


def _phase_stack() -> list:
    """This thread's open set-up phases, outermost first."""
    try:
        return _open_phases.stack
    except AttributeError:
        _open_phases.stack = []
        return _open_phases.stack


def _plain(v):
    """A span argument as the ledger keeps it: JSON scalars as they
    are, anything else by its `str`."""
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


class _NullSpan:
    """Shared no-op span: the disarmed surface while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def mark(self, label: str) -> None:
        pass

    def set(self, **args) -> None:
        pass

    def close(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _MirrorSpan(TraceAnnotation):
    """The disarmed span under a profiler session: the mirror alone.
    `set` reaches it too (an argument known only inside the span, as
    `worker.runner`'s `miss`), `mark` and `close` do nothing."""

    __slots__ = ()

    def mark(self, label: str) -> None:
        pass

    def set(self, **args) -> None:
        self.set_metadata(**args)

    def close(self) -> None:
        pass


class Span:
    """One armed span; created by `Tracer.span` and closed by the
    context manager (or an explicit `close()`)."""

    __slots__ = ("_tracer", "name", "args", "tid", "t0_ns", "dur_ns",
                 "_marks", "_mirror")

    def __init__(self, tracer: "Tracer", name: str, tid: int,
                 args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.tid = tid
        # the mirror opens and closes beside the span's own clock
        # reads, so both sinks hold the same interval
        self._mirror = TraceAnnotation(MIRROR_PREFIX + name, **args)
        self._mirror.__enter__()
        self.t0_ns = time.perf_counter_ns()
        self.dur_ns = 0
        self._marks = None

    def mark(self, label: str) -> None:
        """Record a named intermediate timestamp (µs offsets land in
        args as `<label>_us`); `dispatched` additionally yields
        `device_wait_us` = close - mark, the device-execution estimate
        under the sync-before-close convention."""
        if self._marks is None:
            self._marks = []
        self._marks.append((label, time.perf_counter_ns()))

    def set(self, **args) -> None:
        """Attach/overwrite args (visible in the exported event, and
        among the mirror's stats)."""
        self.args.update(args)
        self._mirror.set_metadata(**args)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.close()
        return False

    def close(self) -> None:
        end = time.perf_counter_ns()
        self._mirror.__exit__(None, None, None)
        self.dur_ns = end - self.t0_ns
        if self._marks:
            for label, t in self._marks:
                self.args[f"{label}_us"] = round((t - self.t0_ns) / 1000.0, 3)
            last_label, last_t = self._marks[-1]
            if last_label == "dispatched":
                self.args["device_wait_us"] = round((end - last_t) / 1000.0, 3)
        self._tracer._emit_span(self)


class PhaseSpan(Span):
    """A span of the set-up vocabulary: a `Span` (both sinks when
    armed, the profiler mirror either way) that also leaves its record
    in `SETUP_LEDGER` when it closes."""

    __slots__ = ("parent", "_bytes_open")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        stack = _phase_stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._bytes_open = (
            fullest_bytes_in_use() if name in SETUP_PLACING else None
        )
        super().__init__(
            tracer, name, tracer._tid() if tracer.enabled else 0, args
        )

    def close(self) -> None:
        super().close()
        stack = _phase_stack()
        if self in stack:
            stack.remove(self)
        record = {
            "name": self.name, "parent": self.parent,
            "t0_ns": self.t0_ns, "dur_ns": self.dur_ns,
            "args": {k: _plain(v) for k, v in self.args.items()},
        }
        if self._bytes_open is not None:
            closed = fullest_bytes_in_use()
            if closed is not None:
                record["bytes_in_use"] = {
                    "open": self._bytes_open, "close": closed,
                }
        SETUP_LEDGER.append(record)


class Tracer:
    """Buffered per-process span/instant/counter recorder.

    `enabled` is fixed at construction: the global disarmed tracer is a
    singleton whose `instant()`/`counter()` are two-branch no-ops and
    whose `span()` is a shared no-op (the profiler mirror alone while a
    profiler session records), and arming (obs.configure) swaps in a
    fresh enabled instance — call sites hold no state, they re-read
    the global through `obs.tracer()` per query."""

    def __init__(self, enabled: bool = True, *, rank: int | None = None,
                 nprocs: int | None = None):
        self.enabled = enabled
        self._rank_fallback = int(rank or 0)
        self._nprocs_fallback = int(nprocs or 1)
        self.trace_id = uuid.uuid4().hex if enabled else None
        self._buf = deque()  # lock-free: deque.append is GIL-atomic
        self._meta_rows: list = []  # (tid, name) thread rows
        self._tids: Dict[int, int] = {}
        self._tid_counter = itertools.count()
        self._lock = threading.Lock()  # tid registry only, never the hot path
        self._t_anchor_ns = time.perf_counter_ns()
        self._wall_anchor = time.time()

    @property
    def pid(self) -> int:
        """The process rank, read LIVE on every use: the tracer can be
        armed before `jax.distributed.initialize` lands (the runner
        arms obs before CommSpec), and this jax build's pre-init
        `process_id` default is 0 — indistinguishable from a final
        single-host rank — so caching would freeze every multi-host
        process at rank 0.  Events emitted before init carry pid 0;
        everything from the first collective onward (all query spans)
        carries the real rank."""
        try:
            from jax._src import distributed

            st = distributed.global_state
            if getattr(st, "client", None) is None:
                # jax.distributed not initialized: the pre-init
                # process_id default (0) is indistinguishable from a
                # real rank, so the constructor fallback wins — tests
                # build fake rank-r tracers this way
                return self._rank_fallback
            pid = st.process_id
            return int(pid) if pid is not None else self._rank_fallback
        except Exception:
            return self._rank_fallback

    @property
    def nprocs(self) -> int:
        """Gang size, read live like `pid` (same pre-init caveat); the
        constructor fallback lets tests build a fake rank-r-of-n tracer
        without touching jax.distributed."""
        try:
            from jax._src import distributed

            st = distributed.global_state
            if getattr(st, "client", None) is None:
                return self._nprocs_fallback
            n = getattr(st, "num_processes", None)
            return int(n) if n else self._nprocs_fallback
        except Exception:
            return self._nprocs_fallback

    # ---- track bookkeeping ----------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, next(self._tid_counter))
            name = threading.current_thread().name
            self._meta_rows.append(
                (tid, "host" if tid == 0 else name)
            )
        return tid

    def frag_tid(self, fid: int) -> int:
        """The per-fragment track row (named lazily on first use)."""
        tid = FRAG_TID_BASE + int(fid)
        if tid not in self._tids:
            with self._lock:
                if tid not in self._tids:
                    self._tids[tid] = tid
                    self._meta_rows.append((tid, f"frag/{fid}"))
        return tid

    def lane_tid(self, lane: int) -> int:
        """The per-lane track row for serve/ batched dispatches: each
        query of a batch renders as its own Perfetto row (the lane's
        interval IS the batch dispatch interval — attribution, not
        measurement).  Like frag rows, lane rows restate host
        intervals, so the span rollup excludes them."""
        from libgrape_lite_tpu.obs.events import LANE_TID_BASE

        tid = LANE_TID_BASE + int(lane)
        if tid not in self._tids:
            with self._lock:
                if tid not in self._tids:
                    self._tids[tid] = tid
                    self._meta_rows.append((tid, f"lane/{lane}"))
        return tid

    def replica_tid(self, replica: int) -> int:
        """The per-replica track row for fleet/ routing: each
        replica's dispatch intervals render as their own Perfetto row
        (attribution across the replica set, like lane rows across a
        batch; excluded from the span rollup the same way)."""
        from libgrape_lite_tpu.obs.events import REPLICA_TID_BASE

        tid = REPLICA_TID_BASE + int(replica)
        if tid not in self._tids:
            with self._lock:
                if tid not in self._tids:
                    self._tids[tid] = tid
                    self._meta_rows.append((tid, f"replica/{replica}"))
        return tid

    # ---- emitters --------------------------------------------------------

    def _push(self, ev: Dict[str, Any]) -> None:
        """Buffer one event, stamping `rank`/`nprocs` when the process
        is part of a real gang.  Single-process exports (nprocs == 1)
        are untouched so rank-0 solo output stays byte-identical to
        the pre-gang schema."""
        n = self.nprocs
        if n > 1:
            ev["rank"] = ev["pid"]
            ev["nprocs"] = n
        self._buf.append(ev)

    def span(self, name: str, **args):
        if name in SETUP_PHASES:
            return PhaseSpan(self, name, args)
        if not self.enabled:
            if not _profiling():
                return _NULL_SPAN
            return _MirrorSpan(MIRROR_PREFIX + name, **args)
        return Span(self, name, self._tid(), args)

    def _emit_span(self, span: Span) -> None:
        if not self.enabled:
            return  # a disarmed set-up phase: the ledger alone keeps it
        self._push(span_event(
            span.name, ts_ns=span.t0_ns, dur_ns=span.dur_ns,
            pid=self.pid, tid=span.tid,
            args=span.args or None,
        ))

    def emit_span_raw(self, name: str, *, t0_ns: int, dur_ns: int,
                      tid: int, **args) -> None:
        """Re-emit a span interval on another track (the worker mirrors
        superstep spans onto per-fragment rows: SPMD execution is
        lockstep across the mesh, so the host wall interval IS each
        fragment's interval)."""
        if not self.enabled:
            return
        self._push(span_event(
            name, ts_ns=t0_ns, dur_ns=dur_ns, pid=self.pid, tid=tid,
            args=args or None,
        ))

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self._push(instant_event(
            name, ts_ns=time.perf_counter_ns(), pid=self.pid,
            tid=self._tid(), args=args or None,
        ))

    def counter(self, name: str, **values) -> None:
        if not self.enabled:
            return
        self._push(counter_event(
            name, ts_ns=time.perf_counter_ns(), pid=self.pid,
            tid=self._tid(), values=values,
        ))

    def flow(self, name: str, *, flow_id: int, phase: str,
             cat: str = "gang", **args) -> None:
        """Emit one leg of a cross-rank flow arrow (ph s/t/f).  Every
        rank participating in one logical edge (a breach vote, a 2PC
        stage→commit) emits its own leg with the SAME `(cat, flow_id)`;
        the gang assembler merges them and Perfetto draws the arrow
        across process tracks."""
        if not self.enabled:
            return
        self._push(flow_event(
            name, ts_ns=time.perf_counter_ns(), pid=self.pid,
            tid=self._tid(), flow_id=flow_id, phase=phase, cat=cat,
            args=args or None,
        ))

    # ---- draining --------------------------------------------------------

    def drain(self) -> list:
        """Pop every buffered event (metadata rows stay; they re-export
        with every flush so partial files stay loadable)."""
        out = []
        while True:
            try:
                out.append(self._buf.popleft())
            except IndexError:
                return out

    def events(self) -> list:
        """Non-destructive snapshot: metadata + buffered events (test
        and rollup surface; flush() is the draining exporter)."""
        return self.metadata() + list(self._buf)

    def metadata(self) -> list:
        """Process/thread-name rows, built at export time so they
        carry the CURRENT rank (see the `pid` property)."""
        if not self.enabled:
            return []
        pid = self.pid
        rows = [metadata_event(
            "process_name", pid=pid, name=f"grape/r{pid}"
        )]
        rows += [
            metadata_event("thread_name", pid=pid, tid=tid, name=name)
            for tid, name in list(self._meta_rows)
        ]
        n = self.nprocs
        if n > 1:
            for ev in rows:
                ev["rank"] = pid
                ev["nprocs"] = n
        return rows

    def wall_anchor(self) -> Dict[str, float]:
        """Monotonic→wall-clock correlation for the export metadata."""
        return {
            "perf_counter_ns": self._t_anchor_ns,
            "unix_time": self._wall_anchor,
        }


#: the module-level disarmed singleton (obs/config.py swaps the global
#: reference; this instance is what every call site sees by default)
DISABLED = Tracer(enabled=False)
