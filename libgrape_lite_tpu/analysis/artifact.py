"""Layer 2 of grape-lint: audits on actually-lowered/compiled runners.

The AST rules prove the source can't *express* a defect class; these
audits recount from the shipped artifact — the lowered StableHLO
module and the live XLA compile stream — and fail on drift, the same
two-sided discipline the pack ledger applies to op counts (model from
the plan, recount from the arrays; cf. SparseP's cost-model
validation).  Three audits:

* **A1 constant-bloat** — scan the fused runner's lowered module for
  literal constants above a byte threshold.  Catches every R1 escape
  (closure paths the AST pattern missed, library code, future
  refactors) end-to-end: a baked fragment array WILL show up as a
  multi-MB `stablehlo.constant`.
* **A2 donation** — the fused runner must donate its carry (the
  `tf.aliasing_output` markers in the lowered module): losing
  `donate_argnums` silently doubles peak HBM for the loop carry.
* **A3 surprise-compile** — run the canonical warm query matrix
  (sssp/bfs x fused/guarded/batched/incremental) twice and pin ZERO
  XLA compiles on the second pass, counted by `compile_events()`
  (the real `/jax/core/compile` stream, not cache counters — PR 6's
  per-batch re-jit was invisible to the counters, never to this).

`compile_events()` is also the public counter the zero-recompile
tests (tests/test_serve.py, tests/test_dyn.py) pin on.
"""

from __future__ import annotations

import re
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from libgrape_lite_tpu.analysis.report import Finding

DEFAULT_CONSTANT_THRESHOLD = 64 * 1024  # bytes

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a persistent-compilation-cache hit (JAX_COMPILATION_CACHE_DIR — the
# recommended TPU-pod configuration) satisfies a compile REQUEST
# without ever invoking backend_compile: a per-dispatch fresh jit
# wrapper still retraces and re-requests every batch, so a warmed
# zero-compile pin must count these too or the exact defect class A3
# exists to catch hides behind the disk cache
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENTS = (_BACKEND_COMPILE_EVENT, _CACHE_HIT_EVENT)
# what a runner costs the host before the backend sees it, and what the
# persistent cache answers: the `runner.compile` set-up phase's args
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileEvents:
    """Events captured while a `compile_events()` block was active.
    `.compiles` counts XLA compile requests that reached the backend —
    fresh backend_compile calls AND persistent-cache hits (both mean a
    new executable was requested, i.e. something retraced); `.events`
    keeps the raw (event, seconds) stream for diagnostics."""

    def __init__(self):
        self.events: List[tuple] = []
        self.arrived: List[float] = []  # perf_counter at each event

    def _covered_seconds(self, event: str) -> float:
        """Seconds the intervals of `event` cover.  A duration arrives
        when its interval ends, and a jit traced inside another reports
        its own trace inside the outer one's, so the intervals are
        merged, not summed."""
        spans = sorted(
            (end - dur, end)
            for (name, dur), end in zip(self.events, self.arrived)
            if name == event
        )
        covered, reach = 0.0, float("-inf")
        for start, end in spans:
            if end > reach:
                covered += end - max(start, reach)
                reach = end
        return covered

    def phase_seconds(self) -> Dict[str, float]:
        """What the compile requests inside the block cost, by phase:
        tracing and lowering (host work the executable cache does not
        spare), the backend (a compile, or on a hit the fetch), the
        persistent cache's retrieval, and its hits and misses."""
        names = [name for name, _ in self.events]
        return {
            "trace_s": self._covered_seconds(_TRACE_EVENT),
            "lower_s": self._covered_seconds(_LOWER_EVENT),
            "backend_s": self.compile_seconds(),
            "cache_retrieval_s": sum(
                dur for name, dur in self.events
                if name == _CACHE_RETRIEVAL_EVENT
            ),
            "cache_hits": names.count(_CACHE_HIT_EVENT),
            "cache_misses": names.count(_CACHE_MISS_EVENT),
        }

    @property
    def compiles(self) -> int:
        return sum(
            1 for name, _ in self.events
            if name in _COMPILE_EVENTS
        )

    def compile_seconds(self) -> float:
        return sum(
            dur for name, dur in self.events
            if name == _BACKEND_COMPILE_EVENT
        )


@contextmanager
def compile_events():
    """Count real XLA compiles inside the block::

        with compile_events() as ev:
            worker.query(source=0)      # warmed: expect ev.compiles == 0

    Counts the backend_compile monitoring event AND persistent-cache
    hits, so it sees EVERY compile request in the process — including
    ones invisible to the runner/plan cache counters (a fresh jit
    wrapper per dispatch compiles identical HLO through a brand-new
    cache entry; the counters stay flat, this does not — the PR 6
    guarded-serve incident) and ones invisible to backend_compile
    alone (the same fresh wrapper under JAX_COMPILATION_CACHE_DIR
    hits the disk cache instead of the compiler)."""
    from jax import monitoring

    rec = CompileEvents()

    def _listen(event, duration, **kw):
        rec.events.append((event, duration))
        rec.arrived.append(time.perf_counter())

    def _listen_plain(event, **kw):
        # record_event stream (no duration): persistent-cache hits
        rec.events.append((event, 0.0))
        rec.arrived.append(time.perf_counter())

    monitoring.register_event_duration_secs_listener(_listen)
    monitoring.register_event_listener(_listen_plain)
    try:
        yield rec
    finally:
        monitoring.unregister_event_duration_listener(_listen)
        monitoring.unregister_event_listener(_listen_plain)


# ---------------------------------------------------------------------------
# lowered-module scanning (A1 constant bloat, A2 donation)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i4": 1, "i1": 1,
    "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1, "ui4": 1,
    "c64": 8, "c128": 16,
}

_CONST_RE = re.compile(
    r"(?:stablehlo|mhlo)\.constant[^\n]*?:\s*tensor<([^>]*)>"
)
_ALIAS_RE = re.compile(r"tf\.aliasing_output")


def tensor_type_bytes(type_str: str) -> int:
    """Byte size of a `tensor<...>` element spec like '4x128xf32'."""
    parts = type_str.strip().split("x")
    dtype = parts[-1]
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        return 0  # opaque/quantized types: not a bloat candidate
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0  # dynamic dim: size unknowable, skip
        n *= int(d)
    return n * width


def scan_constants(lowered_text: str,
                   threshold: int = DEFAULT_CONSTANT_THRESHOLD):
    """(offenders, total_bytes, n_constants): every literal constant
    in the lowered module at/above `threshold` bytes."""
    offenders = []
    total = 0
    count = 0
    for m in _CONST_RE.finditer(lowered_text):
        nbytes = tensor_type_bytes(m.group(1))
        count += 1
        total += nbytes
        if nbytes >= threshold:
            offenders.append(
                {"tensor": m.group(1), "bytes": nbytes}
            )
    return offenders, total, count


def donation_info(lowered_text: str) -> dict:
    return {"donated_args": len(_ALIAS_RE.findall(lowered_text))}


def lower_fused(worker, max_rounds: Optional[int] = None,
                **query_args):
    """The fused runner's jax Lowered object for this worker+args —
    the exact artifact `Worker.query` would dispatch (same cache, so
    auditing does not add a compile the next query wouldn't hit)."""
    app = worker.app
    frag = worker.fragment
    mr = app.max_rounds if max_rounds is None else max_rounds
    state = worker._place_state(app.init_state(frag, **query_args))
    runner = worker._runner_for(mr, state)
    eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    return runner.lower(frag.dev, carry, eph_part)


def audit_fused_runner(worker, *, threshold: int =
                       DEFAULT_CONSTANT_THRESHOLD,
                       expect_donation: bool = True,
                       **query_args):
    """A1 + A2 on one worker's fused runner.  Returns (findings,
    info): findings use rules A1/A2; info carries the raw numbers for
    the report."""
    app_name = type(worker.app).__name__
    text = lower_fused(worker, **query_args).as_text()
    offenders, total, count = scan_constants(text, threshold)
    don = donation_info(text)
    findings: List[Finding] = []
    for off in offenders:
        findings.append(Finding(
            "A1", f"<lowered:{app_name}>", 0, f"{app_name}.fused",
            f"lowered module holds a {off['bytes']}-byte literal "
            f"constant (tensor<{off['tensor']}>) above the "
            f"{threshold}-byte threshold — a closure-captured array "
            "was baked in (R1 class)",
        ))
    if expect_donation and don["donated_args"] == 0:
        findings.append(Finding(
            "A2", f"<lowered:{app_name}>", 0, f"{app_name}.fused",
            "fused runner donates no input buffer — the carry is "
            "double-buffered in HBM instead of aliased into the loop",
        ))
    info = {
        "app": app_name,
        "constants": count,
        "constant_bytes": total,
        "offenders": offenders,
        "threshold": threshold,
        **don,
    }
    return findings, info


# ---------------------------------------------------------------------------
# A3 — the canonical warm query matrix under the compile counter
# ---------------------------------------------------------------------------

MATRIX_APPS = ("sssp", "bfs")
MATRIX_MODES = ("fused", "guarded", "batched", "incremental")


def _additive_delta():
    """A minimal additive delta description: enough for
    incremental_plan to pick the seeded path (the audit does not
    mutate the graph — it pins the seeded machinery's compile
    behavior, which is what serving exercises after every overlay
    ingest)."""
    from libgrape_lite_tpu.dyn.delta import DeltaBuffer

    buf = DeltaBuffer(capacity=4)
    buf.stage([("a", 0, 1, 1.0)])
    return buf.summary()


def _run_cell(worker, mode: str, sources):
    if mode == "fused":
        worker.query(source=sources[0])
    elif mode == "guarded":
        worker.query(source=sources[0], guard="halt")
    elif mode == "batched":
        worker.query_batch([{"source": s} for s in sources])
    elif mode == "incremental":
        prev = worker.query(source=sources[0])
        worker.query_incremental(
            prev, delta=_additive_delta(), source=sources[0]
        )
    else:
        raise ValueError(f"unknown matrix mode {mode!r}")


def warm_matrix_audit(frag, apps=MATRIX_APPS, modes=MATRIX_MODES,
                      sources=(0, 1)):
    """A3: run every (app, mode) cell once to warm, then re-run the
    whole matrix under `compile_events()` and pin zero compiles.
    Returns (findings, info); info["cells"] carries per-cell compile
    counts for the report."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    workers = {a: Worker(APP_REGISTRY[a](), frag) for a in apps}
    for a in apps:
        for mode in modes:
            _run_cell(workers[a], mode, sources)

    findings: List[Finding] = []
    cells = []
    total = 0
    for a in apps:
        for mode in modes:
            with compile_events() as ev:
                _run_cell(workers[a], mode, sources)
            cells.append(
                {"app": a, "mode": mode, "compiles": ev.compiles}
            )
            total += ev.compiles
            if ev.compiles:
                findings.append(Finding(
                    "A3", f"<warm:{a}>", 0, f"{a}.{mode}",
                    f"warmed {mode} query compiled {ev.compiles} "
                    "module(s) — a runner/probe cache is leaking "
                    "(R2 class)",
                ))
    info = {
        "cells": cells,
        "unexpected_compiles": total,
        "apps": list(apps),
        "modes": list(modes),
    }
    return findings, info


# ---------------------------------------------------------------------------
# the full artifact audit (CLI --artifact, tpu_first_light.sh)
# ---------------------------------------------------------------------------


def _default_fragment(n: int = 400, e: int = 3200, fnum: int = 1):
    """A small weighted random graph — big enough to make a baked CSR
    obvious against the 64 KiB constant threshold, small enough to
    audit in seconds on the CPU fallback."""
    import numpy as np

    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(8)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, w, directed=False,
    )


def run_artifact_audit(frag=None, *, threshold: int =
                       DEFAULT_CONSTANT_THRESHOLD,
                       apps=MATRIX_APPS, modes=MATRIX_MODES):
    """Everything Layer 2 knows how to prove, as (findings, report):
    constant-bloat + donation on each app's fused runner, then the
    zero-compile warm matrix.  `frag=None` builds the small canonical
    fragment (the CLI/tpu_first_light path); pass a real loaded
    fragment to audit production geometry."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    if frag is None:
        frag = _default_fragment()
    findings: List[Finding] = []
    lowered: Dict[str, dict] = {}
    for a in apps:
        w = Worker(APP_REGISTRY[a](), frag)
        fs, info = audit_fused_runner(w, threshold=threshold, source=0)
        findings.extend(fs)
        lowered[a] = info
    mfs, matrix = warm_matrix_audit(frag, apps=apps, modes=modes)
    findings.extend(mfs)
    report = {
        "findings": [f.to_dict(False) for f in findings],
        "constant_bloat": {
            a: {
                "constants": i["constants"],
                "constant_bytes": i["constant_bytes"],
                "offenders": len(i["offenders"]),
            }
            for a, i in lowered.items()
        },
        "donation": {
            a: {"donated_args": i["donated_args"]}
            for a, i in lowered.items()
        },
        "compile_audit": matrix,
    }
    return findings, report
