"""serve/ — the multi-query serving runtime (ROADMAP item 1).

A `ServeSession` pins one loaded graph — HBM-resident CSR shards and
compiled fused runners — and serves many queries against it
with zero re-planning and zero recompilation after the first hit of
each (app, state-shape, max_rounds).  An `AdmissionQueue` coalesces
compatible point queries into vmapped multi-source batches
(`Worker.query_batch`: k SSSP/BFS sources per dispatch, per-lane
active masks, byte-identical per-lane results) under a `BatchPolicy`
(max batch / max wait), with per-query obs spans and — when guards are
armed — per-lane invariant monitors whose breaches freeze ONE lane
instead of halting the batch (serve/batch.py).

The async pump (serve/pipeline.py) overlaps the three stages the
host-pumped loop serialises: up to `BatchPolicy.inflight` coalesced
batches dispatched-but-unharvested at once (JAX async dispatch), lazy
FIFO harvest with deferred per-lane values, and `ingest` as an
explicit window barrier — W=1 pinned byte- and result-order-identical
to the synchronous loop.

docs/SERVING.md is the user guide; the CLI surface is
`python -m libgrape_lite_tpu.cli serve ...` (`--inflight W` arms the
pump), and bench.py's `serve` / `serve_async` blocks report
queries/sec at fixed p99 next to MTEPS.
"""

from libgrape_lite_tpu.serve.batch import run_guarded_batch
from libgrape_lite_tpu.serve.feeder import ArrivalFeeder
from libgrape_lite_tpu.serve.pipeline import (
    PUMP_STATS,
    AsyncServePump,
)
from libgrape_lite_tpu.serve.policy import BatchPolicy, compat_key
from libgrape_lite_tpu.serve.queue import (
    AdmissionQueue,
    QueryRequest,
    ServeResult,
)
from libgrape_lite_tpu.serve.session import ServeSession

__all__ = [
    "AdmissionQueue",
    "ArrivalFeeder",
    "AsyncServePump",
    "BatchPolicy",
    "PUMP_STATS",
    "QueryRequest",
    "ServeResult",
    "ServeSession",
    "compat_key",
    "run_guarded_batch",
]
