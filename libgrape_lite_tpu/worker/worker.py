"""Superstep driver.

Re-design of `grape/worker/worker.h:48-232`: `Init` prepares the
fragment + message plumbing, `Query` runs PEval then iterates IncEval
until the termination vote fires, `Output` assembles results.

TPU mapping of the reference loop (`worker.h:104-146`):

  * the whole PEval + IncEval loop is ONE jitted function: a
    `lax.while_loop` whose carry is the app state pytree, executed under
    `shard_map` over the frag mesh axis;
  * `messages_.ToTerminate()`'s 2-int MPI_Allreduce
    (`parallel_message_manager.h:123-138`) is the `psum`-reduced
    `active` scalar the app returns each round;
  * per-round host logging (`worker.h:120-139`) is unavailable inside
    the fused loop by design — XLA owns the schedule; a debug mode
    (`fused=False`) drives rounds from the host instead, one jitted
    superstep per round, for parity with the reference's observable
    behavior.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from libgrape_lite_tpu import compat, obs
from libgrape_lite_tpu.app.base import AppBase, StepContext
from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats
from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS
from libgrape_lite_tpu.utils.types import state_struct

_INT32_MAX = np.iinfo(np.int32).max
# the device-trace name of every runner's loop condition (metadata only)
_TERMINATE_SCOPE = "grape.worker.terminate"

# What the rounds of the last extracted query had to do
# (docs/OBSERVABILITY.md): the fused serial runner carries a record of
# the `active` each IncEval voted, a few words beside the state, and
# `Worker.result_values` reads it out with the answer, outside the
# query's wall.  `active_bits[b]` counts the rounds whose `active` had
# bit length b (0: nothing left; b: 2^(b-1) .. 2^b - 1; 32: a negative
# vote, a terminate code), so the buckets sum to `rounds`;
# `active_max` and `active_sum` are the widest round and all of them
# (for BFS: the widest level, and the reached vertices less the
# source).  `frontier_rounds` counts the rounds that followed their
# frontier (`_frontier_loop`; 0 where the app offers no such round).
# Where that loop carries a threshold (SSSP's near/far) a round's vote
# is the length of the list it leaves, the pending rows under the
# threshold, `advances` counts the threshold's steps and `pushed_sum`
# the rows the rounds started from, a row as often as it pushed (over
# the vertices: 1 is label-setting, and a hop-synchronous Bellman-Ford
# on the road graph's weights reads 39); both are 0 where no threshold
# is carried (BFS, where a reached row pushes once: `active_sum` + 1).
# A query through any other runner (batched, chunked, stepwise, host)
# leaves the initial values.
ROUND_STATS = _FedStats("rounds", {
    "app": "", "rounds": 0, "active_bits": [], "active_max": 0,
    "active_sum": 0, "frontier_rounds": 0, "advances": 0, "pushed_sum": 0,
})
_RECORD_SCOPE = "grape.worker.record"
_RECORD_BITS = 33  # bit lengths 0..32, one bucket each
# behind the buckets: the largest vote, the sum's low and high words
# (x32 has no 64-bit integer; a V-wide vote a round outgrows one word)
_RECORD_MAX, _RECORD_LO, _RECORD_HI, _RECORD_WORDS = range(
    _RECORD_BITS, _RECORD_BITS + 4)


def _note_round(record, active):
    """`record` after a round that voted `active`.  The record is
    `_RECORD_WORDS` uint32 scalars and the step scalar arithmetic: an
    array in the carry is fetched into VMEM and written back every
    round, two asynchronous copies whose slots move every other
    buffer of the loop (PERF.md section 6, PR 39)."""
    with jax.named_scope(_RECORD_SCOPE):
        bits = jnp.uint32(32) - lax.clz(active.astype(jnp.uint32))
        a = jnp.maximum(active, 0).astype(jnp.uint32)
        lo = record[_RECORD_LO] + a
        return (
            *(n + (bits == b).astype(jnp.uint32)
              for b, n in enumerate(record[:_RECORD_BITS])),
            jnp.maximum(record[_RECORD_MAX], a),
            lo,
            record[_RECORD_HI] + (lo < a).astype(jnp.uint32),
        )


def _frontier_loop(app, frag_stacked, inceval, cond, st, active):
    """`_make_runner`'s loop for an app that offers a round that follows
    its frontier (app/base.py): `(state, active, rounds, record)`, the
    record one word longer, the rounds that took that round (with a
    threshold four: behind it the threshold's steps and the rows
    pushed, in two words); `cond` is the plain loop's own.

    The carry holds, beside the state, the list of the rows whose
    proposals are pending (`int32[B]`, padded with `vp`) and its length;
    after a round the length is the round's vote, and a list is whole
    only where its length fits B.  A round reads where the listed rows'
    entries lie (B-wide) and takes `inceval_frontier` where the length
    fits B and the entries C; else `inceval(frag, state)`, today's dense
    round, and after it, where the vote fits B, one V-wide compaction
    of the rows it improved into the list.  The arms of a `cond` share
    their temporaries' room; what is carried is B words and scalars.
    The dense arm squeezes the fragment's blocks for itself and the
    other round reads them as they come, `[1, Ep]`: on the chip the
    squeeze is a copy into another tiling, and squeezed once before the
    loop the copies a dense round reads stand in HBM for the whole loop
    beside rounds that read a few thousand entries of them.

    With a threshold (`app.frontier_step`, a bucket's width: near/far)
    the carry gains the scalar `below` and the list holds the pending
    rows under it alone.  Rows at or over it wait: such a row has never
    pushed the value it holds (a row pushes only while under the
    threshold of its time, and values only fall), so where a push
    leaves the list empty the next round is the conditional's third
    arm: it finds the least value at or over `below`, moves `below` to
    the end of that value's bucket and lists every row between the two
    (`grape.frontier.advance`; a bucket that outgrows B makes the next
    round a dense one, which pushes from every row and so is a correct
    step at any threshold).  A round's vote is the length of the list
    it leaves, and the loop goes on past an empty list until that arm
    finds no row left at any value: the dense loop's fixed point.
    `rounds` counts every iteration, the threshold's steps and the last
    look among them."""
    from libgrape_lite_tpu.ops.segment import (
        ADVANCE_SCOPE, FRONTIER_SCOPE, frontier_rows, frontier_spans,
    )

    rows, entries = app.frontier_budget
    step = app.frontier_step
    row_ptr = app.frontier_csr(frag_stacked).indptr
    # the first list, from the state PEval returned: a query's source,
    # the one row that holds a value.  A state with more pending rows
    # (an incremental query's seeds) starts with a dense round, whose
    # compaction is the loop's one copy of that code, and code counts:
    # a runner's megabytes are HBM at the peak like its state (PERF.md
    # section 6, PR 37 and PR 40)
    pending = app.frontier_mask(st)
    with jax.named_scope(FRONTIER_SCOPE):
        held = pending.sum().astype(jnp.int32)
        # more than B says "a dense round first"; with a threshold the
        # true count, which `pushed_sum` adds up
        many = (jnp.int32(rows + 1) if step is None
                else jnp.maximum(held, rows + 1))
        n0 = jnp.where(held <= 1, held, many)
        front0 = jnp.full((rows,), pending.shape[0], jnp.int32).at[0].set(
            jnp.where(held == 1, jnp.argmax(pending).astype(jnp.int32),
                      pending.shape[0]))

    def bucket_end(least):
        """The end of the bucket `least` lies in; past `least` even
        where a bucket is narrower than the values' spacing there."""
        end = jnp.floor(least / step) * step + step
        return jnp.maximum(end, jnp.nextafter(least, jnp.inf))

    def sparse(s, front, lo, count, *below):
        return app.inceval_frontier(
            frag_stacked, s, front, lo, count, *below)

    def dense(s, front, lo, count, *below):
        s2, a2 = inceval(frag_stacked.local(), s)

        def improved():
            mask = app.frontier_mask(s, s2)
            if step is None:
                return mask
            # the list after it holds the improved rows under the
            # threshold alone, and the vote counts those
            return jnp.logical_and(mask, app.frontier_values(s2) < below[0])

        if step is not None:
            # the mask is made here and again inside the `cond` below,
            # not made once and handed in: a V-wide operand of that
            # `cond` moves the loop's values and the edge blocks out of
            # VMEM for every arm, and a push then costs 725 us for 385
            # (PERF.md section 6, PR 43;
            # tests/test_lanes_compile_v5e.py holds the placement)
            with jax.named_scope("grape.app.update"):
                a2 = improved().sum().astype(jnp.int32)
        front2 = lax.cond(
            a2 <= rows,
            lambda: frontier_rows(improved(), rows),
            lambda: front,
        )
        return s2, a2, front2

    def advance(s, front, lo, count, below):
        with jax.named_scope(ADVANCE_SCOPE):
            values = app.frontier_values(s)
            far = values >= below
            least = jnp.min(jnp.where(far, values, jnp.inf))
            some = least < jnp.inf
            upto = jnp.where(some, bucket_end(least), below)
            bucket = jnp.logical_and(far, values < upto)
            n = bucket.sum().astype(jnp.int32)
        front2 = lax.cond(
            n <= rows,
            lambda: frontier_rows(bucket, rows, ADVANCE_SCOPE),
            lambda: front,
        )
        return s, n, front2, upto, some.astype(jnp.uint32)

    def body(carry):
        s, _, r, rec, front, n, took, *far = carry
        lo, count, total = frontier_spans(front, row_ptr)
        fits = jnp.logical_and(n <= rows, total <= entries)
        if step is None:
            s2, a2, front2 = lax.cond(fits, sparse, dense, s, front, lo, count)
            with jax.named_scope(_RECORD_SCOPE):
                took = took + fits.astype(jnp.uint32)
            return (s2, a2, r + jnp.int32(1), _note_round(rec, a2), front2,
                    a2, took)
        below, stepped, lo_word, hi_word = far

        def push(arm):
            return lambda *operands: (*arm(*operands), operands[-1],
                                      jnp.uint32(0))

        # one conditional a round, the threshold's step its third arm:
        # an iteration is a push, a dense round or a step, and `rounds`
        # counts each
        which = jnp.where(n == 0, 2, jnp.where(fits, 0, 1))
        s2, a2, front2, below, moved = lax.switch(
            which, [push(sparse), push(dense), advance],
            s, front, lo, count, below)
        with jax.named_scope(_RECORD_SCOPE):
            took = took + (which == 0).astype(jnp.uint32)
            pushed = n.astype(jnp.uint32)
            lo_word = lo_word + pushed
            hi_word = hi_word + (lo_word < pushed).astype(jnp.uint32)
            # a push that leaves its list empty says nothing of the rows
            # over the threshold: the next round looks
            active = jnp.where(n == 0, a2, jnp.maximum(a2, 1))
        return (s2, active, r + jnp.int32(1), _note_round(rec, a2), front2,
                a2, took, below, stepped + moved, lo_word, hi_word)

    far0 = ()
    if step is not None:
        with jax.named_scope(ADVANCE_SCOPE):
            values = app.frontier_values(st)
            far0 = (bucket_end(jnp.min(jnp.where(pending, values, jnp.inf))),
                    jnp.uint32(0), jnp.uint32(0), jnp.uint32(0))
    st, active, rounds, record, _, _, took, *far = lax.while_loop(
        cond, body,
        (st, jnp.int32(active), jnp.int32(0),
         (jnp.uint32(0),) * _RECORD_WORDS, front0, n0, jnp.uint32(0), *far0),
    )
    return st, active, rounds, (*record, took, *far[1:])


def _squeeze_state(state, squeezed):
    return {
        k: (v[0] if k in squeezed else v) for k, v in state.items()
    }


def _unsqueeze_state(state, squeezed):
    return {
        k: (v[None] if k in squeezed else v) for k, v in state.items()
    }


def _squeeze_lane_state(state, squeezed):
    """Per-shard view of batched carry leaves: the lane axis leads, so
    sharded keys arrive as [B, 1, ...] blocks and squeeze axis 1."""
    return {
        k: (v[:, 0] if k in squeezed else v) for k, v in state.items()
    }


def _jit_with_chunk_digest(sm, state, eph):
    """Wrap a compiled guarded-chunk shard_map so the watchdog digest
    (and the stagnation residual) ride out as extra outputs of the
    SAME jitted dispatch — computed on the global post-collective
    carry, so they are value-identical to the monitor's own probe
    (same carry_digest function, same masked-residual rule) and the
    guarded-fused path pays no extra device dispatch for them (ROADMAP
    "Watchdog on device")."""
    from libgrape_lite_tpu.guard.watchdog import carry_digest

    float_keys = sorted(
        k for k, v in state.items()
        if k not in eph and np.dtype(v.dtype).kind == "f"
    )

    def with_digest(frag_stacked, st, eph_state, active0, r0):
        out, rounds, active = sm(
            frag_stacked, st, eph_state, active0, r0
        )
        dig = carry_digest(out)
        if float_keys:
            diffs = [
                jnp.max(jnp.where(
                    jnp.isfinite(d), d, jnp.float32(0)
                ))
                for k in float_keys
                for d in [jnp.abs(
                    out[k].astype(jnp.float32)
                    - st[k].astype(jnp.float32)
                )]
            ]
            res = jnp.max(jnp.stack(diffs))
        else:
            res = jnp.float32(-1)
        return out, rounds, active, dig, res

    return jax.jit(with_digest)


class BatchDispatch:
    """One dispatched (possibly still in-flight) batched query: the
    un-synced outputs of a `Worker.query_batch_dispatch` call, held
    SELF-CONTAINED so a window of W dispatches can coexist without
    clobbering the worker's per-query result fields (`batch_rounds`,
    `_result_state`, ...) — the deferred batch-result surface the
    async serve pump (serve/pipeline.py) harvests from.

    Nothing here forces a host sync until asked: `is_ready()` polls,
    `wait()` syncs the per-lane verdicts (rounds / terminate codes —
    a few int32s), and `lane_values(b)` does the per-lane extraction
    (device_get + finalize) the harvest stage overlaps with the next
    batch's device execution."""

    __slots__ = ("app", "fragment", "eph", "state", "rounds_v",
                 "active_v", "breaches", "batch", "guarded",
                 "supersteps_counted", "_rounds", "_active")

    def __init__(self, *, app, fragment, eph, state, rounds_v,
                 active_v, batch, breaches=None, guarded=False,
                 supersteps_counted=False):
        self.app = app
        self.fragment = fragment
        self.eph = frozenset(eph)
        self.state = state  # {**carry, **eph} — device (or synced) refs
        self.rounds_v = rounds_v
        self.active_v = active_v
        self.batch = batch
        self.breaches = (
            list(breaches) if breaches is not None else [None] * batch
        )
        self.guarded = guarded
        # guarded dispatches count supersteps inside their chunk loop;
        # unguarded ones are counted by whoever harvests (the rounds
        # are not known until the dispatch settles)
        self.supersteps_counted = supersteps_counted
        self._rounds = None
        self._active = None

    def is_ready(self) -> bool:
        """True when the dispatch has settled (no sync forced); a
        backend without `jax.Array.is_ready` reports True and the
        first harvest simply blocks."""
        probe = getattr(self.rounds_v, "is_ready", None)
        return bool(probe()) if callable(probe) else True

    def wait(self) -> "BatchDispatch":
        """Sync the per-lane verdicts; values stay deferred per lane."""
        if self._rounds is None:
            self._rounds = np.asarray(self.rounds_v)
            self._active = np.asarray(self.active_v)
        return self

    @property
    def rounds(self) -> np.ndarray:
        return self.wait()._rounds

    @property
    def terminate(self) -> np.ndarray:
        return np.minimum(0, self.wait()._active)

    def lane_state(self, lane: int):
        """Lane `lane`'s carry view (ephemeral leaves are shared)."""
        return {
            k: (v if k in self.eph else v[lane])
            for k, v in self.state.items()
        }

    def lane_values(self, lane: int) -> np.ndarray:
        """Per-vertex assembled values for one lane, [fnum, vp] numpy —
        the host-sync the harvest stage pays lazily."""
        with obs.tracer().span("worker.extract", lane=lane):
            host = jax.device_get(self.lane_state(lane))
            return self.app.finalize(self.fragment, host)


class PreparedBatch:
    """A batched query with its host-side work DONE (state built and
    placed, runner resolved through the cache) but its execution not
    yet enqueued.  The async pump prepares ahead under the window and
    staggers `launch()` calls so executions never oversubscribe the
    backend (on the CPU fallback two concurrent XLA executions fight
    for the same cores; on a real accelerator the device queue
    serialises them anyway) while preparation and result extraction
    overlap whatever IS executing.  Guarded batches carry their args
    instead: the chunked monitor loop cannot split, so launch() runs
    it whole (serve/batch.py)."""

    __slots__ = ("worker", "app", "fragment", "eph", "runner", "carry",
                 "eph_part", "batch", "guarded", "_guard_args")

    def __init__(self, *, worker, app, fragment, eph=None, runner=None,
                 carry=None, eph_part=None, batch=0, guarded=False,
                 guard_args=None):
        self.worker = worker
        self.app = app
        self.fragment = fragment
        self.eph = eph
        self.runner = runner
        self.carry = carry
        self.eph_part = eph_part
        self.batch = batch
        self.guarded = guarded
        self._guard_args = guard_args

    def launch(self) -> "BatchDispatch":
        """Enqueue the execution (no host sync for unguarded batches —
        the refs ride back un-synced; guarded batches run their chunk
        loop here, which probes at boundaries by design)."""
        if self.guarded:
            from libgrape_lite_tpu.serve.batch import run_guarded_batch

            args_list, mr, guard_cfg = self._guard_args
            w = self.worker
            run_guarded_batch(w, args_list, mr, guard_cfg)
            return BatchDispatch(
                app=self.app, fragment=self.fragment,
                eph=frozenset(
                    getattr(self.app, "ephemeral_keys", ()) or ()
                ),
                state=w._result_state,
                rounds_v=np.asarray(w.batch_rounds).copy(),
                active_v=np.asarray(w.batch_terminate).copy(),
                batch=self.batch, breaches=w.batch_breaches,
                guarded=True, supersteps_counted=True,
            )
        out_state, rounds_v, active_v = self.runner(
            self.fragment.dev, self.carry, self.eph_part
        )
        return BatchDispatch(
            app=self.app, fragment=self.fragment, eph=self.eph,
            state={**out_state, **self.eph_part},
            rounds_v=rounds_v, active_v=active_v, batch=self.batch,
        )


def _unsqueeze_lane_state(state, squeezed):
    return {
        k: (v[:, None] if k in squeezed else v) for k, v in state.items()
    }


class Worker:
    """Binds an app to a sharded fragment and runs queries
    (reference `Worker<APP_T, MESSAGE_MANAGER_T>`).

    Failure handling follows the reference's cooperative-abort scope
    (`default_message_manager.h:156-166`, `ForceTerminate` +
    `TerminateInfo`): an app votes a NEGATIVE active value to abort;
    the psum carries it to every shard, the loop stops, and
    `get_terminate_info()` reports the failure.

    Checkpoint-restart (ft/): `query(..., checkpoint_every=K,
    checkpoint_dir=...)` degrades the fused loop to stepwise execution
    and snapshots the carry pytree + round counter every K supersteps
    (a superstep boundary is a consistent cut); `resume(dir)` validates
    the config fingerprint and continues from the last complete
    superstep with byte-identical results.  With checkpointing off
    (the default) the fused `shard_map(while_loop)` path is untouched —
    fail-fast, like the reference."""

    def __init__(self, app: AppBase, fragment: ShardedEdgecutFragment):
        self.app = app
        self.fragment = fragment
        self.comm_spec = fragment.comm_spec
        self._runner_cache = {}
        # hit/miss counters over the compiled-runner cache: serve/ pins
        # "a session's second query triggers zero XLA compilation" on
        # the miss count staying flat (tests/test_serve.py)
        self.runner_cache_stats = {"hits": 0, "misses": 0}
        self.rounds = 0
        self._result_state = None
        # (result state, the runner's record of its rounds' votes): the
        # fused serial runner's, paired so that a result of another
        # path is never read with this one's record (ROUND_STATS)
        self._round_record = None
        # the fragment each result was computed on: query_incremental's
        # safe prev_fragment default — a serve repack rebinds
        # self.fragment, but the PREVIOUS result's rows still live in
        # the old layout and must migrate by oid
        self._result_fragment = None
        self._terminate_code = 0
        self._guard_monitor = None  # guard/: set only while guards are armed
        self.batch_rounds = None  # per-lane rounds of the last query_batch
        self.batch_terminate = None  # per-lane terminate codes (min(0, v))
        self.batch_breaches = None  # per-lane guard bundles (serve/batch)
        # host-side stage decomposition of the last fused/batched
        # query: {"dispatch": ns, "device": ns} — perf_counter_ns
        # stamps around the runner enqueue and the result sync, so the
        # serve stage report (queue.ServeResult.stages) can split host
        # dispatch from device wait without touching the jitted
        # program (None on paths that do not decompose: guarded,
        # stepwise, host-only)
        self.last_stage_ns = None
        # dyn/: incremental-IncEval accounting — seeded vs (counted,
        # never silent) cold fallbacks, and the last query's plan
        self.inc_stats = {"seeded": 0, "cold": 0}
        self.inc_report = None
        self._seed_fn = None  # set only inside query_incremental

    @property
    def guard_report(self):
        """The last query's guard statistics (probes, breaches,
        rollbacks) or None when guards were off."""
        return (
            None if self._guard_monitor is None
            else self._guard_monitor.report()
        )

    def _seeded(self, state_np):
        """Apply the incremental-IncEval seed overrides (dyn/) to a
        freshly-built init state — identity outside query_incremental.
        The hook sits at every init_state call site, so the seeded
        query runs the SAME fused/stepwise/guarded machinery as a cold
        one (and a checkpoint resume restores over the fresh init the
        usual way: the restored carry came from the seeded run)."""
        if self._seed_fn is None:
            return state_np
        return self._seed_fn(state_np)

    def _check_dyn_view(self):
        """An app without a dyn-overlay contract must not run while the
        fragment holds staged delta edges — it would silently compute
        on the stale base graph.  ServeSession repacks automatically
        before dispatching such apps; bare Workers fail loudly."""
        ov = getattr(self.fragment, "dyn_overlay", None)
        if (
            ov is not None and ov.count > 0
            and not getattr(self.app, "dyn_overlay_support", False)
        ):
            raise ValueError(
                f"{type(self.app).__name__} has no dyn-overlay "
                f"contract and the fragment carries {ov.count} staged "
                "delta edge(s); fold them first (DynGraph.fold_now — "
                "ServeSession.ingest handles this automatically)"
            )

    def release_buffers(self) -> None:
        """Drop this worker's device-resident references — the last
        query's result carry, its fragment provenance and the guard
        monitor — so a fleet eviction (ServeSession.release_device)
        actually frees the HBM.  The compiled-runner cache is KEPT:
        re-admission must compile nothing (tests/test_fleet.py pins
        it)."""
        self._result_state = None
        self._round_record = None
        self._result_fragment = None
        self._guard_monitor = None
        self.batch_rounds = None
        self.batch_terminate = None
        self.batch_breaches = None

    def get_terminate_info(self):
        """(success, info) — reference `Worker::GetTerminateInfo`
        (worker.h:150-152)."""
        if self._terminate_code >= 0:
            return True, ""
        return False, (
            f"query force-terminated with code {self._terminate_code} "
            f"after {self.rounds} rounds"
        )

    # ---- Init (reference worker.h:82-100) is construction above ----

    def _mesh_layout(self):
        """(mesh, frag/dim0 spec) for the app's mesh kind: the 1-D frag
        axis by default, the k x k SUMMA mesh for vc2d apps."""
        if self.app.mesh_kind == "vc2d":
            from libgrape_lite_tpu.parallel.comm_spec import (
                VC_COL_AXIS, VC_ROW_AXIS,
            )

            return self.comm_spec.mesh2d(), P((VC_ROW_AXIS, VC_COL_AXIS))
        return self.comm_spec.mesh, P(FRAG_AXIS)

    def _key_specs(self, state):
        """(spec per state key, keys squeezed of their leading frag
        dim).  Custom-spec leaves pass through as raw per-shard blocks."""
        app = self.app
        custom = app.custom_specs()
        replicated = set(app.replicated_keys)
        _, shard0 = self._mesh_layout()
        specs = {
            k: custom.get(k, P() if k in replicated else shard0)
            for k in state
        }
        squeezed = {
            k for k in state if k not in custom and k not in replicated
        }
        return specs, squeezed

    def _shard_mapped(self, stepper, key_specs, state, extra_in=(),
                      out_tail=()):
        """`stepper` under `shard_map` for a state of this structure:
        every runner's `compile_for` but its wrap.  `stepper` takes the
        fragment, the carry, the ephemeral leaves (apart, so that no
        wrap donates them: they are stripped from the outputs and
        could never alias) and one argument for each of `extra_in`,
        and returns the carry and one value for each of `out_tail`;
        `key_specs` is `_key_specs` or its batched twin."""
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())
        mesh, frag_spec = self._mesh_layout()
        specs, squeezed = key_specs(state)
        carry_specs = {k: v for k, v in specs.items() if k not in eph}
        eph_specs = {k: v for k, v in specs.items() if k in eph}
        return compat.shard_map(
            partial(stepper, squeezed=squeezed),
            mesh=mesh,
            in_specs=(frag_spec, carry_specs, eph_specs, *extra_in),
            out_specs=(carry_specs, *out_tail),
            check_vma=False,
        )

    def _stepper_parts(self, max_rounds, frag_stacked, state, eph_state,
                       squeezed):
        """What the fused and the chunk runner's steppers share, in the
        traced shard: `(frag, st, peval, inceval, limit, running)`.
        `st` is the squeezed carry; `peval(frag, s)` and `inceval(frag,
        s)` are `_lane_stepper_parts`' closures, a round of a carry
        without its ephemeral leaves; `running(bound)` is the loop
        condition of a carry `(state, active, round, ...)` that stops
        at `bound`."""
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())
        frag = frag_stacked.local()
        st_all = _squeeze_state({**state, **eph_state}, squeezed)
        peval, inceval = self._lane_stepper_parts(
            {k: st_all[k] for k in eph})
        st = {k: v for k, v in st_all.items() if k not in eph}
        limit = jnp.int32(max_rounds if max_rounds > 0 else _INT32_MAX)

        def running(bound):
            def cond(carry):
                _, act, r, *_ = carry
                with jax.named_scope(_TERMINATE_SCOPE):
                    return jnp.logical_and(act > 0, r < bound)

            return cond

        return frag, st, peval, inceval, limit, running

    def _make_runner(self, max_rounds: int):
        """The fused runner: PEval, then IncEval to the fixed point (or
        `max_rounds`) in one `shard_map(while_loop)`.  It alone carries
        the round record, offers the loop that follows a frontier, and
        donates its carry."""
        app = self.app

        def stepper(frag_stacked, state, eph_state, squeezed):
            frag, st, peval, inceval, limit, running = (
                self._stepper_parts(max_rounds, frag_stacked, state,
                                    eph_state, squeezed))
            st, active = peval(frag, st)
            cond = running(limit)

            def body(carry):
                s, _, r, rec = carry
                s2, a2 = inceval(frag, s)
                return s2, a2, r + jnp.int32(1), _note_round(rec, a2)

            # the record of the rounds' votes (ROUND_STATS) rides in the
            # loop's carry, not in an app's state: any app's `active`
            # is recorded, and the apps' own states, which the batched,
            # incremental and checkpointed paths share, stay as they
            # were
            budget = app.frontier_budget
            if budget is None:
                st, active, rounds, record = lax.while_loop(
                    cond, body,
                    (st, jnp.int32(active), jnp.int32(0),
                     (jnp.uint32(0),) * _RECORD_WORDS),
                )
            else:
                st, active, rounds, record = _frontier_loop(
                    app, frag_stacked, inceval, cond, st, active)
            return (_unsqueeze_state(st, squeezed), rounds, active,
                    jnp.stack(record))

        def compile_for(state):
            sm = self._shard_mapped(stepper, self._key_specs, state,
                                    out_tail=(P(), P(), P()))
            # donate the placed carry state: every query places fresh
            # buffers (query -> _place_state), so XLA may alias them
            # into the loop carry instead of holding input + output
            # copies in HBM (fragment CSRs and ephemeral tables are
            # reused / output-less and stay un-donated)
            return jax.jit(sm, donate_argnums=(1,))

        return compile_for

    def _make_chunk_runner(self, chunk: int, max_rounds: int):
        """Fused IncEval segment for the guarded path: runs up to
        `chunk` supersteps of the SAME `shard_map(while_loop)` body as
        `_make_runner`, but (a) skips PEval (the caller drives it once),
        (b) enters/exits at an arbitrary (round, active) so segments
        compose, and (c) does NOT donate the carry — the guard probe
        reads the pre-chunk carry for the consecutive-carry invariants
        (monotone distances etc.), so guarded execution holds two carry
        generations in HBM by design.  The dispatch emits the carry's
        digest and residual beside it (`_jit_with_chunk_digest`)."""
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())

        def stepper(frag_stacked, state, eph_state, active0, r0, squeezed):
            frag, st, _, inceval, limit, running = (
                self._stepper_parts(max_rounds, frag_stacked, state,
                                    eph_state, squeezed))
            stop = jnp.minimum(jnp.int32(r0) + jnp.int32(chunk), limit)

            def body(carry):
                s, _, r = carry
                s2, a2 = inceval(frag, s)
                return s2, a2, r + jnp.int32(1)

            st, active, rounds = lax.while_loop(
                running(stop), body,
                (st, jnp.int32(active0), jnp.int32(r0)),
            )
            return _unsqueeze_state(st, squeezed), rounds, active

        def compile_for(state):
            sm = self._shard_mapped(stepper, self._key_specs, state,
                                    extra_in=(P(), P()),
                                    out_tail=(P(), P()))
            return _jit_with_chunk_digest(sm, state, eph)

        return compile_for

    def _cached_runner(self, key, build):
        """One compiled-runner cache lookup with hit/miss accounting
        (serve/ asserts zero-recompile reuse through these counters).
        A runner is traced against the fragment it is handed as much
        as against the state, so the fragment's structure (its static
        sizes, its leaves' shapes and dtypes) closes every key: a
        worker handed a fragment rebuilt to other shapes (a dyn
        repack) misses here and its compile is a counted one, where
        the cached `jit` would trace anew and tell no counter."""
        leaves, treedef = jtu.tree_flatten(self.fragment.dev)
        key = (*key, treedef, tuple((x.shape, x.dtype) for x in leaves))
        hit = key in self._runner_cache
        self.runner_cache_stats["hits" if hit else "misses"] += 1
        # a miss means the first dispatch traces and compiles: the
        # span sites read this flag right after it and stamp
        # `mark("compiled")`, and `_enqueue` opens `runner.compile`
        self._last_runner_miss = not hit
        if not hit:
            self._runner_cache[key] = build()
        return self._runner_cache[key]

    def _state_struct(self, state):
        return state_struct(state)

    def _chunk_runner_for(self, chunk: int, max_rounds: int, state):
        key = (
            "chunk", chunk, max_rounds,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key, lambda: self._make_chunk_runner(chunk, max_rounds)(state)
        )

    def _runner_for(self, max_rounds: int, state):
        """Cache the jitted runner per (max_rounds, app hyperparameters,
        state structure) so repeated queries don't re-trace but changed
        query params (which are baked into the trace) do.  `max_rounds`
        is part of the key because the round limit is baked into the
        while_loop cond — a second query with a different limit must
        not silently reuse the first compile (pinned by
        tests/test_worker.py::test_runner_cache_keys_max_rounds)."""
        key = (
            max_rounds,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key, lambda: self._make_runner(max_rounds)(state)
        )

    def _staged(self, make_state, place, runner_for):
        """The host half of a fused or batched dispatch under its three
        spans: build the state, place it, look the runner up (`miss=1`
        when it had to be traced: the enqueue that follows compiles).
        Returns (runner, carry, eph_part, eph)."""
        tr = obs.tracer()
        with tr.span("worker.init_state"):
            state = make_state()
        with tr.span("worker.place_state"):
            state = place(state)
        with tr.span("worker.runner") as sp:
            runner = runner_for(state)
            if self._last_runner_miss:
                sp.set(miss=1)
        # AFTER init_state: overlay-contracted apps extend their
        # ephemeral set there (dyn edge streams ride as shared eph
        # leaves)
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())
        carry = {k: v for k, v in state.items() if k not in eph}
        eph_part = {k: v for k, v in state.items() if k in eph}
        return runner, carry, eph_part, eph

    def _enqueue(self, runner, mode: str, batch: int, *operands):
        """The runner's dispatch.  On a runner miss the call traces,
        lowers and compiles (or fetches) before it enqueues: that one
        is set-up phase `runner.compile`, with what JAX's monitoring
        events give for it as args (the listener lives for this call
        only); a hit opens nothing.

        The miss compiles ahead of its call, so that the phase can say
        what the executable holds on one device (`executable_bytes`:
        code, temporaries, arguments, outputs, aliased; left out where
        no analysis is given) and what the state operands handed to it
        do (`state_bytes`: every operand after the fragment).  The call
        that follows finds that executable and compiles nothing
        (tests/test_setup_ledger.py pins one backend compile a miss)."""
        if not self._last_runner_miss:
            return runner(*operands)
        from libgrape_lite_tpu.analysis.artifact import compile_events
        from libgrape_lite_tpu.utils.memory import (
            executable_bytes, shard_bytes,
        )

        with obs.tracer().span(
            "runner.compile", app=type(self.app).__name__, mode=mode,
            batch=batch,
        ) as sp, compile_events() as ev:
            compiled = runner.lower(*operands).compile()
            sp.set(**executable_bytes(compiled),
                   state_bytes=shard_bytes(operands[1:]))
            out = runner(*operands)
            sp.set(**ev.phase_seconds())
        return out

    # ---- batched multi-source execution (serve/) -------------------------

    def _check_batchable(self):
        """Batched dispatch covers superstep apps on the 1-D frag mesh
        and the 2-D vc2d mesh; everything else fails loudly BEFORE a
        cryptic trace error."""
        app = self.app
        if getattr(app, "host_only", False):
            raise ValueError(
                f"{type(app).__name__} is a host-only app: its "
                "data-dependent host loop has no superstep carry to vmap"
            )
        if hasattr(app, "collect_mutations"):
            raise ValueError(
                "MutationContext apps rebuild the fragment between "
                "rounds and cannot share one batched dispatch"
            )
        if app.mesh_kind not in ("frag", "vc2d"):
            raise ValueError(
                f"batched dispatch supports the frag and vc2d meshes "
                f"only (app mesh_kind={app.mesh_kind!r})"
            )
        if app.custom_specs() and app.mesh_kind != "vc2d":
            # vc2d's custom row-sharded specs are handled by
            # _key_specs_batch; any OTHER custom layout is unaudited
            raise ValueError(
                "batched dispatch does not support custom-spec state "
                "leaves outside the vc2d mesh"
            )

    def _key_specs_batch(self, state):
        """(spec per key, keys squeezed of their axis-1 frag dim) for a
        batched carry: sharded leaves are [B, fnum, ...] split on axis
        1, replicated leaves [B, ...] everywhere, ephemeral leaves stay
        unbatched [fnum, ...] (shared streams).  Custom-spec leaves
        (vc2d): ephemeral ones keep their per-shard layout unbatched,
        carry ones gain the leading lane axis with the custom spec
        shifted one dim right ([B, k*vc] rides P(None, vcrow)) — and
        are NOT squeezed, since their local block has no unit frag
        dim."""
        app = self.app
        custom = app.custom_specs()
        replicated = set(app.replicated_keys)
        eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
        _, shard0 = self._mesh_layout()
        specs, squeezed = {}, set()
        for k in state:
            if k in eph:
                specs[k] = custom.get(k, shard0)
            elif k in replicated:
                specs[k] = P()
            elif k in custom:
                specs[k] = P(None, *custom[k])
            else:
                specs[k] = P(None, FRAG_AXIS)
                squeezed.add(k)
        return specs, squeezed

    def _place_state_batch(self, state_np):
        from libgrape_lite_tpu.parallel.comm_spec import put_global

        mesh, _ = self._mesh_layout()
        specs, _ = self._key_specs_batch(state_np)
        return {
            k: put_global(v, NamedSharding(mesh, specs[k]))
            for k, v in state_np.items()
        }

    def _lane_stepper_parts(self, eph_vals):
        """(lane_peval, lane_inc): one query's superstep closures over
        the shared per-shard fragment + ephemeral streams: the bodies
        of every runner, the batched ones' under vmap."""
        app = self.app
        eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
        ctx = StepContext()

        def strip(s):
            return {k: v for k, v in s.items() if k not in eph}

        def lane_peval(frag, s):
            s2, a = app.peval(ctx, frag, {**s, **eph_vals})
            return strip(s2), jnp.int32(a)

        def lane_inc(frag, s):
            s2, a = app.inceval(ctx, frag, {**s, **eph_vals})
            return strip(s2), jnp.int32(a)

        return lane_peval, lane_inc

    @staticmethod
    def _lane_body(lane_inc, frag, batch: int):
        """One batched IncEval round with the per-lane freeze mask:
        lanes whose vote has reached zero (or negative: cooperative
        abort) keep their carry PINNED, so each lane executes exactly
        the inceval sequence of its own sequential query and the
        per-lane result is byte-identical to k separate Worker.query
        runs — convergence raggedness costs masked (discarded) compute
        on finished lanes, never a value change."""
        def body(carry):
            s, act, rv, r = carry
            s2, a2 = jax.vmap(lambda st: lane_inc(frag, st))(s)
            with jax.named_scope("grape.worker.freeze"):
                live = act > 0

                def sel(new, old):
                    mask = live.reshape((batch,) + (1,) * (new.ndim - 1))
                    return jnp.where(mask, new, old)

                s3 = jtu.tree_map(sel, s2, s)
                a3 = jnp.where(live, a2, act)
                r2 = r + jnp.int32(1)
                return s3, a3, jnp.where(live, r2, rv), r2

        return body

    def _lane_loop_parts(self, max_rounds, batch, frag_stacked, state,
                         eph_state, squeezed):
        """What the batched and the batched chunk runner's steppers
        share, in the traced shard: `(frag, st, lane_peval, limit,
        running, body)`.  `st` is the squeezed lanes' carry;
        `running(bound)` is the loop condition of a carry `(state,
        active[B], rounds[B], round)` that stops at `bound` or when
        every lane's vote has settled; `body` is `_lane_body`'s."""
        custom = frozenset(self.app.custom_specs())
        frag = frag_stacked.local()
        # custom-spec ephemeral leaves (vc2d vmask_row) arrive as
        # their raw per-shard block — no unit frag dim to strip
        eph_vals = {
            k: (v if k in custom else v[0])
            for k, v in eph_state.items()
        }
        st = _squeeze_lane_state(state, squeezed)
        lane_peval, lane_inc = self._lane_stepper_parts(eph_vals)
        limit = jnp.int32(max_rounds if max_rounds > 0 else _INT32_MAX)

        def running(bound):
            def cond(carry):
                _, act, _, r = carry
                with jax.named_scope(_TERMINATE_SCOPE):
                    return jnp.logical_and(jnp.any(act > 0), r < bound)

            return cond

        return (frag, st, lane_peval, limit, running,
                self._lane_body(lane_inc, frag, batch))

    def _make_batched_runner(self, max_rounds: int, batch: int):
        """Fused multi-source runner: the SAME PEval+IncEval loop as
        _make_runner, vmapped over a leading lane axis of the carry.
        Each lane is an independent query against the shared HBM-
        resident fragment and ephemeral streams (plan tables, mirror
        send tables, an overlay's staged edges ride once, not per lane); the
        while_loop runs until EVERY lane's active vote has settled, and
        the freeze mask (see _lane_body) keeps finished lanes pinned so
        raggedness never perturbs results."""

        def stepper(frag_stacked, state, eph_state, squeezed):
            frag, st, lane_peval, limit, running, body = (
                self._lane_loop_parts(max_rounds, batch, frag_stacked,
                                      state, eph_state, squeezed))
            st, active = jax.vmap(lambda s: lane_peval(frag, s))(st)
            st, active, rounds_v, _ = lax.while_loop(
                running(limit), body,
                (st, active, jnp.zeros((batch,), jnp.int32),
                 jnp.int32(0)),
            )
            return _unsqueeze_lane_state(st, squeezed), rounds_v, active

        def compile_for(state):
            sm = self._shard_mapped(stepper, self._key_specs_batch, state,
                                    out_tail=(P(), P()))
            return jax.jit(sm, donate_argnums=(1,))

        return compile_for

    def _make_batched_chunk_runner(self, chunk: int, max_rounds: int,
                                   batch: int):
        """Batched analogue of _make_chunk_runner for the guarded serve
        path: runs up to `chunk` global supersteps from an arbitrary
        (per-lane active, per-lane rounds, global round) entry point,
        emitting a per-lane carry digest + masked residual as extra
        outputs of the same dispatch.  No carry donation — the per-lane
        guard probes read the pre-chunk carry."""
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())

        def stepper(frag_stacked, state, eph_state, active0, rv0, r0,
                    squeezed):
            _, st, _, limit, running, body = self._lane_loop_parts(
                max_rounds, batch, frag_stacked, state, eph_state,
                squeezed)
            stop = jnp.minimum(jnp.int32(r0) + jnp.int32(chunk), limit)
            st, active, rv, r = lax.while_loop(
                running(stop), body,
                (st, jnp.asarray(active0, jnp.int32),
                 jnp.asarray(rv0, jnp.int32), jnp.int32(r0)),
            )
            return _unsqueeze_lane_state(st, squeezed), rv, active, r

        def compile_for(state):
            sm = self._shard_mapped(stepper, self._key_specs_batch, state,
                                    extra_in=(P(), P(), P()),
                                    out_tail=(P(), P(), P()))

            from libgrape_lite_tpu.guard.watchdog import carry_digest

            float_keys = sorted(
                k for k, v in state.items()
                if k not in eph and np.dtype(v.dtype).kind == "f"
            )

            def lane_residual(out_f, st_f):
                diffs = [
                    jnp.max(jnp.where(
                        jnp.isfinite(d), d, jnp.float32(0)
                    ))
                    for k in float_keys
                    for d in [jnp.abs(
                        out_f[k].astype(jnp.float32)
                        - st_f[k].astype(jnp.float32)
                    )]
                ]
                return jnp.max(jnp.stack(diffs))

            def with_digest(frag_stacked, st, eph_state, active0, rv0, r0):
                out, rv, active, r = sm(
                    frag_stacked, st, eph_state, active0, rv0, r0
                )
                dig = jax.vmap(carry_digest)(out)  # [B, 2]
                if float_keys:
                    res = jax.vmap(lane_residual)(
                        {k: out[k] for k in float_keys},
                        {k: st[k] for k in float_keys},
                    )
                else:
                    res = jnp.full((batch,), jnp.float32(-1))
                return out, rv, active, r, dig, res

            return jax.jit(with_digest)

        return compile_for

    def _batched_runner_for(self, max_rounds: int, batch: int, state):
        key = (
            "batched", batch, max_rounds,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key,
            lambda: self._make_batched_runner(max_rounds, batch)(state),
        )

    def _batched_chunk_runner_for(self, chunk: int, max_rounds: int,
                                  batch: int, state):
        key = (
            "batched-chunk", chunk, batch, max_rounds,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key,
            lambda: self._make_batched_chunk_runner(
                chunk, max_rounds, batch
            )(state),
        )

    def query_batch(self, args_list, max_rounds: int | None = None, *,
                    guard=None):
        """Run k point queries as ONE vmapped dispatch over the shared
        fragment (serve/, ROADMAP item 1): `args_list` carries one
        query-arg dict per lane (e.g. [{"source": 3}, {"source": 9}]).
        Per-lane results are byte-identical to k sequential
        `Worker.query` runs (freeze-masked lanes, pinned by
        tests/test_serve.py); per-lane round counts land in
        `batch_rounds`, per-lane terminate codes in `batch_terminate`,
        and lane b's carry is `batch_lane_state(b)`.

        Guarded batched execution (per-lane monitors, breach isolation)
        is driven by serve/batch.py — `guard` here routes there."""
        self._check_batchable()
        # BEFORE the guard routing: the guarded batch path must reject
        # a stale dyn view exactly like the plain one
        self._check_dyn_view()
        app = self.app
        frag = self.fragment
        mr = app.max_rounds if max_rounds is None else max_rounds
        self._guard_monitor = None
        self.last_stage_ns = None

        from libgrape_lite_tpu.guard.config import GuardConfig

        guard_cfg = GuardConfig.resolve(guard)
        if guard_cfg.enabled:
            from libgrape_lite_tpu.serve.batch import run_guarded_batch

            return run_guarded_batch(self, args_list, mr, guard_cfg)

        import time as _time

        t_host0 = _time.perf_counter_ns()
        batch = len(args_list)
        tr = obs.tracer()
        try:
            with tr.span("query", mode="batched",
                         app=type(app).__name__, batch=batch) as sp:
                def place(state):
                    # as `query` does: the last batch's result goes
                    # once this one has a state to place, or every
                    # batch holds two batched states in HBM at its peak
                    self._result_state = None
                    return self._place_state_batch(state)

                runner, carry, eph_part, _ = self._staged(
                    lambda: app.init_state_batch(frag, args_list),
                    place,
                    lambda st: self._batched_runner_for(mr, batch, st),
                )
                with tr.span("worker.enqueue"):
                    out_state, rounds_v, active_v = self._enqueue(
                        runner, "batched", batch,
                        frag.dev, carry, eph_part,
                    )
                t_enq = _time.perf_counter_ns()
                sp.mark("dispatched")
                with tr.span("worker.wait"):
                    out_state = jax.block_until_ready(out_state)
                with tr.span("worker.readback"):
                    rv = np.asarray(rounds_v)
                    av = np.asarray(active_v)
                self.last_stage_ns = {
                    "dispatch": t_enq - t_host0,
                    "device": _time.perf_counter_ns() - t_enq,
                }
                self.batch_rounds = rv
                self.batch_terminate = np.minimum(0, av)
                self.batch_breaches = [None] * batch
                self.rounds = int(rv.max()) if batch else 0
                self._terminate_code = (
                    int(self.batch_terminate.min()) if batch else 0
                )
                if tr.enabled:
                    # each lane pays PEval + its own counted IncEvals,
                    # all inside the single batched dispatch (frozen-
                    # lane recomputes are discarded, not counted)
                    obs.metrics().counter(
                        "grape_supersteps_total"
                    ).inc(int(rv.sum()) + batch)
                    sp.set(lane_rounds=[int(x) for x in rv])
                self._finish_query_obs(sp)
        finally:
            if tr.enabled:
                obs.flush()
        self._result_state = {**out_state, **eph_part}
        self._result_fragment = self.fragment
        return self._result_state

    def batch_lane_state(self, lane: int):
        """Lane `lane`'s carry view of the last query_batch result
        (ephemeral leaves are shared, not sliced)."""
        if self._result_state is None or self.batch_rounds is None:
            raise RuntimeError("query_batch() first")
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())
        return {
            k: (v if k in eph else v[lane])
            for k, v in self._result_state.items()
        }

    def batch_result_values(self, lane: int) -> np.ndarray:
        """Per-vertex assembled values for one lane, [fnum, vp] numpy."""
        with obs.tracer().span("worker.extract", lane=lane):
            host = jax.device_get(self.batch_lane_state(lane))
            return self.app.finalize(self.fragment, host)

    def query_batch_prepare(self, args_list,
                            max_rounds: int | None = None, *,
                            guard=None) -> PreparedBatch:
        """Do the HOST half of a batched dispatch — same checks, same
        state build/placement, same cached runner as `query_batch`
        (so a W=1 pump is byte-identical to the synchronous loop) —
        and return a PreparedBatch whose `launch()` enqueues the
        execution.  The async serve pump (serve/pipeline.py) prepares
        ahead under its window and staggers launches; the worker's own
        per-query result fields are left untouched, so W dispatches
        can coexist.

        Guarded batches defer the whole chunked per-lane monitor loop
        (serve/batch.py) to launch() — breach isolation needs probe
        verdicts, which sync at every chunk boundary by design — and
        their verdict arrays are SNAPSHOT into the launched handle, so
        a guarded batch mid-window never clobbers a neighbour's
        verdicts and its per-lane values still harvest lazily."""
        self._check_batchable()
        self._check_dyn_view()
        app = self.app
        frag = self.fragment
        mr = app.max_rounds if max_rounds is None else max_rounds

        from libgrape_lite_tpu.guard.config import GuardConfig

        guard_cfg = GuardConfig.resolve(guard)
        batch = len(args_list)
        if guard_cfg.enabled:
            return PreparedBatch(
                worker=self, app=app, fragment=frag, batch=batch,
                guarded=True,
                guard_args=(list(args_list), mr, guard_cfg),
            )

        runner, carry, eph_part, eph = self._staged(
            lambda: app.init_state_batch(frag, args_list),
            self._place_state_batch,
            lambda st: self._batched_runner_for(mr, batch, st),
        )
        return PreparedBatch(
            worker=self, app=app, fragment=frag, eph=eph,
            runner=runner, carry=carry, eph_part=eph_part, batch=batch,
        )

    def query_batch_dispatch(self, args_list,
                             max_rounds: int | None = None, *,
                             guard=None) -> BatchDispatch:
        """Prepare AND launch in one call: k point queries dispatched
        without waiting, outputs riding back un-synced in a
        self-contained BatchDispatch (JAX async dispatch).  The
        one-shot surface for callers that do not stagger launches."""
        return self.query_batch_prepare(
            args_list, max_rounds, guard=guard
        ).launch()

    def query(self, max_rounds: int | None = None, *,
              checkpoint_every: int | None = None,
              checkpoint_dir: str | None = None,
              fault_plan=None, guard=None, **query_args):
        """Run one query (reference `Worker::Query`, worker.h:104-146).

        `checkpoint_every=K` + `checkpoint_dir` degrade the fused loop
        to stepwise execution with a carry snapshot every K supersteps
        (ft/checkpoint.py); `checkpoint_every=None` (default) leaves
        the fused `shard_map(while_loop)` fast path untouched.

        `guard` arms the runtime invariant monitor (guard/):
        GuardConfig, a policy string ("warn"|"halt"|"rollback"), or
        None to read GRAPE_GUARD from the env.  With guards off (the
        default) this method compiles exactly the trace it always has —
        the guard decision is a host-side env read, so the fused fast
        path is byte-identical and zero-overhead.  Guards on: the loop
        runs in fused chunks of GRAPE_GUARD_EVERY supersteps with an
        invariant probe + watchdog digest at every boundary.

        Guards + checkpointing compose WITHOUT the stepwise degrade
        when `checkpoint_every` is a multiple of the guard chunk size:
        chunk boundaries are consistent cuts, so snapshots come
        straight from the chunk outputs (probed first — a state that
        fails its invariants never becomes a rollback target) and the
        inner loop stays the fused while_loop.  Misaligned cadences,
        and checkpointing without guards, keep the stepwise path."""
        from libgrape_lite_tpu.guard.config import GuardConfig

        app = self.app
        self._check_dyn_view()
        self.last_stage_ns = None
        if checkpoint_every is not None or checkpoint_dir is not None:
            guard_cfg = GuardConfig.resolve(guard)
            if (
                guard_cfg.enabled
                and checkpoint_every and checkpoint_dir
                and checkpoint_every % guard_cfg.every == 0
                and not getattr(app, "host_only", False)
                and not hasattr(app, "collect_mutations")
                and jax.process_count() == 1
            ):
                mr = app.max_rounds if max_rounds is None else max_rounds
                return self._query_guarded(
                    mr, guard_cfg,
                    checkpoint_every=checkpoint_every,
                    checkpoint_dir=checkpoint_dir,
                    fault_plan=fault_plan, **query_args,
                )
            return self.query_stepwise(
                max_rounds, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
                guard=guard, **query_args,
            )
        frag = self.fragment
        mr = app.max_rounds if max_rounds is None else max_rounds
        self._guard_monitor = None

        guard_cfg = GuardConfig.resolve(guard)
        if guard_cfg.enabled:
            if getattr(app, "host_only", False):
                if not getattr(app, "host_guard", False):
                    from libgrape_lite_tpu.utils import logging as glog

                    glog.log_info(
                        "guard: host-only apps have no superstep carry "
                        "to monitor; guards are inert for "
                        f"{type(app).__name__}"
                    )
            elif hasattr(app, "collect_mutations"):
                # MutationContext apps run stepwise with a mutation-
                # aware monitor (digest history resets at boundaries)
                return self.query_stepwise(
                    max_rounds, guard=guard, **query_args
                )
            else:
                return self._query_guarded(
                    mr, guard_cfg, fault_plan=fault_plan, **query_args
                )

        tr = obs.tracer()
        if getattr(app, "host_only", False):
            # host-engine apps (irregular recursion, e.g. kclique) skip
            # the traced superstep loop entirely; iterative ones honor
            # the same round bound as everyone else
            import inspect

            kwargs = dict(query_args)
            if "max_rounds" in inspect.signature(app.host_compute).parameters:
                kwargs["max_rounds"] = mr
            if getattr(app, "host_guard", False):
                # guard-capable host loops (exchange apps) run their
                # own round-boundary probes; hand them THIS query's
                # RESOLVED config — enabled or not — so
                # Worker.query(guard=...) arms them like any superstep
                # app AND an explicit guard="off" genuinely disarms an
                # env-armed GRAPE_GUARD (the hooks fall back to the
                # env only when no worker handed them a config)
                app._host_guard_cfg = guard_cfg
            try:
                with tr.span("query", mode="host",
                             app=type(app).__name__) as sp:
                    self._result_state = app.host_compute(frag, **kwargs)
                    self._result_fragment = self.fragment
                    self.rounds = getattr(app, "rounds", 0)
                    self._finish_query_obs(sp)
            finally:
                # a breach raise must still surface the monitor (for
                # guard_report) and land its spans in the file sinks
                self._guard_monitor = getattr(
                    app, "_host_guard_monitor", None
                )
                if tr.enabled:
                    obs.flush()
            return self._result_state

        if hasattr(app, "collect_mutations"):
            # MutationContext apps need the host between supersteps;
            # the fused while_loop cannot rebuild the fragment mid-loop
            return self.query_stepwise(max_rounds, **query_args)

        import time as _time

        t_host0 = _time.perf_counter_ns()
        # the whole PEval+IncEval loop is one dispatch: the span's
        # dispatch/device split is the honest granularity here (per-
        # superstep spans need the stepwise or guarded-chunked paths);
        # the host's own stages are its child spans, which reach the
        # profiler's trace too (obs/tracer.py)
        try:
            with tr.span("query", mode="fused",
                         app=type(app).__name__) as sp:
                def place(state):
                    # a worker answers for its newest query: once this
                    # one has a state to place, the last one's result
                    # goes, or every query holds two states in HBM at
                    # its peak.  A query refused before that, in
                    # `init_state`, leaves the last answer standing;
                    # one that fails from here on leaves none
                    self._result_state = self._round_record = None
                    return self._place_state(state)

                runner, carry, eph_part, _ = self._staged(
                    lambda: self._seeded(app.init_state(frag, **query_args)),
                    place,
                    lambda st: self._runner_for(mr, st),
                )
                with tr.span("worker.enqueue"):
                    out_state, rounds, active, record = self._enqueue(
                        runner, "fused", 1, frag.dev, carry, eph_part,
                    )
                t_enq = _time.perf_counter_ns()
                if self._last_runner_miss:
                    # fresh compile rode inside this enqueue: stamp it,
                    # so a reader can keep it out of a round's wall
                    sp.mark("compiled")
                sp.mark("dispatched")
                with tr.span("worker.wait"):
                    out_state = jax.block_until_ready(out_state)
                with tr.span("worker.readback"):
                    self.rounds = int(rounds)
                    self._terminate_code = min(0, int(active))
                self.last_stage_ns = {
                    "dispatch": t_enq - t_host0,
                    "device": _time.perf_counter_ns() - t_enq,
                }
                if tr.enabled:
                    # PEval + one IncEval per counted round, all
                    # inside the single fused dispatch
                    obs.metrics().counter(
                        "grape_supersteps_total"
                    ).inc(self.rounds + 1)
                self._finish_query_obs(sp)
        finally:
            if tr.enabled:
                obs.flush()
        self._result_state = out_state
        self._round_record = (out_state, record)
        self._result_fragment = self.fragment
        return out_state

    def query_incremental(self, prev_result, delta=None,
                          max_rounds: int | None = None, *,
                          prev_fragment=None, guard=None,
                          checkpoint_every: int | None = None,
                          checkpoint_dir: str | None = None,
                          fault_plan=None, **query_args):
        """Incremental IncEval (dyn/, PIE's headline capability): run
        this query seeded from `prev_result` — the state dict a
        previous `query` of the SAME app and args returned on the
        pre-delta graph — re-converging only the region the delta
        touched instead of recomputing from scratch.

        `delta` describes the staged change (a dyn.DeltaBuffer or its
        `summary()`); the app's `inc_mode` contract decides the path:

          * "monotone-min" + additive delta -> the carry is seeded with
            `min(fresh_init, migrated prev)` per `inc_seed_keys` key —
            EXACT, byte-identical to a cold full query on the mutated
            graph (the monotone-operator argument lives in
            dyn/incremental.py), typically in a fraction of the rounds;
          * anything else -> a cold full query through the same API,
            counted in `inc_stats["cold"]` — an honest fallback, never
            a silent wrong answer.

        `prev_fragment` names the fragment `prev_result` was computed
        on when a repack replaced it (rows migrate by oid, values remap
        via the app's `inc_value_map`).  Default: the fragment THIS
        worker's last query ran on (`_result_fragment`) — so the
        resident-worker pattern (query, session repack rebinds
        `self.fragment`, query_incremental) migrates correctly without
        the caller naming the old fragment; a prev_result imported
        from a DIFFERENT worker across a repack must pass it
        explicitly (falling back to the current fragment would attach
        old rows to renumbered vertices).  Composes with guard/ and
        ft/ exactly like `query` — the seeded run is an ordinary query
        with a different starting carry, so checkpoints taken inside
        it resume byte-identically through the mutation boundary."""
        from libgrape_lite_tpu.dyn.incremental import (
            incremental_plan,
            reseed_fold,
        )
        from libgrape_lite_tpu.utils import logging as glog

        app = self.app
        mode, reason = incremental_plan(app, delta)
        self.inc_report = {"mode": mode, "reason": reason}
        self.inc_stats[mode] += 1
        if mode == "cold":
            glog.vlog(
                1, "query_incremental: cold recompute (%s)", reason
            )
            return self.query(
                max_rounds, guard=guard,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
                **query_args,
            )
        prev_frag = (
            prev_fragment or self._result_fragment or self.fragment
        )
        host_prev = {
            k: np.asarray(jax.device_get(prev_result[k]))
            for k in app.inc_seed_keys
            if k in prev_result
        }
        self._seed_fn = lambda fresh: {
            **fresh,
            **reseed_fold(app, self.fragment, fresh, prev_frag,
                          host_prev),
        }
        try:
            return self.query(
                max_rounds, guard=guard,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
                **query_args,
            )
        finally:
            self._seed_fn = None

    def _ledger_brief(self):
        """Scalar totals of the engaged plan ledger (the query span's
        modeled-cost attachment: modeled ops/bytes sit next to the
        measured wall/device time in ONE record — the side-by-side the
        SparseP-style roofline accounting needs)."""
        led = self.pack_ledger()
        if not led:
            return None
        t = led["totals"]
        return {
            "edges": led["edges"],
            "vpu_ops": t["vpu_ops"],
            "mxu_ops": t["mxu_ops"],
            "gather_rows": t["gather_rows"],
            "hbm_bytes": t["hbm_bytes"],
            "blocks": t["blocks"],
        }

    def _finish_query_obs(self, sp):
        """Armed-query close-out: ledger totals + round count onto the
        query span, registry roll-ups.  A no-op when obs/ is disarmed
        (the caller passed the shared null span)."""
        if not obs.armed():
            return
        sp.set(rounds=self.rounds, terminate_code=self._terminate_code)
        led = self._ledger_brief()
        m = obs.metrics()
        m.counter("grape_queries_total").inc()
        m.gauge("grape_query_rounds").set(self.rounds)
        if led is not None:
            sp.set(pack_ledger=led)
            m.gauge("grape_pack_edges").set(led["edges"])
            m.gauge("grape_pack_hbm_bytes").set(led["hbm_bytes"])
            m.gauge("grape_pack_vpu_ops").set(led["vpu_ops"])
            m.gauge("grape_pack_mxu_ops").set(led["mxu_ops"])
        # 2-D vertex-cut queries attach their tile layout to the query
        # span (r10): trace_report renders per-tile rows + the
        # max-tile-skew column from exactly this record
        part = getattr(self.app, "_partition_stats", None)
        if part is not None:
            record = {
                "mode": getattr(self.app, "_partition", "2d"),
                "k": part["k"],
                "max_tile_edges": part["max_tile_edges"],
                "mean_tile_edges": part["mean_tile_edges"],
                "tile_skew": part["tile_skew"],
                "per_tile": part["per_tile"],
            }
            sp.set(partition=record)
        # guard probe/breach/rollback counts live in the counters the
        # monitor itself maintains at the event sites — no duplicate
        # gauges here that could disagree after an aborted query

    def _mirror_superstep(self, tr, sp, rounds: int, name: str) -> None:
        """Re-emit a closed superstep span on every per-fragment track:
        SPMD execution is lockstep across the mesh, so the host wall
        interval IS each fragment's interval — multi-frag meshes render
        as parallel rows in Perfetto."""
        if self.fragment.fnum <= 1:
            return
        for f in range(self.fragment.fnum):
            tr.emit_span_raw(
                name, t0_ns=sp.t0_ns, dur_ns=sp.dur_ns,
                tid=tr.frag_tid(f), round=rounds, frag=f,
            )

    def _query_guarded(self, mr: int, guard_cfg, *,
                       checkpoint_every: int | None = None,
                       checkpoint_dir: str | None = None,
                       fault_plan=None, **query_args):
        """Guarded-fused query: PEval once, then fused IncEval chunks
        of `guard_cfg.every` supersteps with an invariant probe +
        watchdog digest at every chunk boundary — a breach is detected
        within one cadence while the inner loop stays the fused
        `shard_map(while_loop)`.  Policies: warn logs and continues,
        halt raises with the diagnostic bundle.

        With `checkpoint_every` (a multiple of the chunk size — query()
        enforces the alignment) snapshots are taken straight from the
        chunk outputs at matching boundaries, AFTER the probe (a state
        that fails its invariants never becomes the rollback target),
        and the rollback policy self-heals in place: restore the last
        good snapshot, rewind (rounds, active), and replay in paranoid
        mode (chunk size 1, so a recurring deterministic fault is
        localized to its exact superstep) — no stepwise degrade.
        Fault-injection hooks (GRAPE_FT_FAULTS / `fault_plan`) fire at
        chunk boundaries, the guarded path's consistent cuts."""
        from libgrape_lite_tpu.guard.monitor import GuardMonitor
        from libgrape_lite_tpu.utils import logging as glog

        app = self.app
        frag = self.fragment
        if mr <= 0:  # 0 = run until the termination vote fires
            mr = _INT32_MAX

        if fault_plan is None:
            from libgrape_lite_tpu.ft.faults import active_plan

            fault_plan = active_plan()
        if fault_plan.is_noop():
            fault_plan = None

        state = self._place_state(
            self._seeded(app.init_state(frag, **query_args))
        )
        eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
        eph_part = {k: v for k, v in state.items() if k in eph}

        def carry_of(st):
            return {k: v for k, v in st.items() if k not in eph}

        ckpt = None
        if checkpoint_every:
            from libgrape_lite_tpu.ft.checkpoint import CheckpointManager
            from libgrape_lite_tpu.ft.fingerprint import (
                canonical_query_args, compute_fingerprint,
            )

            ckpt = CheckpointManager(
                checkpoint_dir,
                fingerprint=compute_fingerprint(app, frag, query_args),
                query_args=canonical_query_args(query_args),
                checkpoint_every=checkpoint_every,
                fresh_start=True,
            )

        monitor = GuardMonitor(
            app=app, frag=frag, config=guard_cfg, ckpt=ckpt,
            ledger=self.pack_ledger(),
        )
        self._guard_monitor = monitor
        glog.vlog(
            1, "guard: fused chunks of %d supersteps (policy=%s%s)",
            guard_cfg.every, guard_cfg.policy,
            f", snapshots every {checkpoint_every}" if ckpt else "",
        )

        tr = obs.tracer()
        try:
            with tr.span("query", mode="guarded-fused",
                         app=type(app).__name__) as qsp:
                peval_fn = self._single_step_for("peval", state)
                prev = carry_of(state)
                with tr.span("peval") as sp:
                    out = self._enqueue(
                        peval_fn, "guarded-fused", 1, frag.dev, state)
                    sp.mark("dispatched")
                    carry, active = jax.block_until_ready(out)
                    sp.set(active=int(active))
                if tr.enabled:
                    obs.metrics().counter(
                        "grape_supersteps_total"
                    ).inc()
                rounds = 0
                if fault_plan is not None:
                    corrupted = fault_plan.maybe_corrupt_carry(carry, 0)
                    if corrupted is not None:
                        carry = {**carry, **self._place_state(corrupted)}
                if int(active) >= 0:
                    # a PEval breach has no snapshot to restore — any
                    # non-warn verdict halts
                    breach = monitor.check(prev, carry, 0, int(active))
                    if breach is not None:
                        monitor.raise_breach(breach)
                if ckpt is not None:
                    # a superstep-0 snapshot always exists, so a breach
                    # at any later chunk has something to fall back to
                    ckpt.save_async(carry, 0, int(active))
                if fault_plan is not None:
                    fault_plan.on_superstep(0, ckpt)
                chunk_fn = self._chunk_runner_for(
                    guard_cfg.every, mr, state
                )
                chunk1_fn = None  # paranoid replay compiles lazily
                prev = carry
                while int(active) > 0 and rounds < mr:
                    cf = chunk_fn
                    if monitor.paranoid:
                        if chunk1_fn is None:
                            chunk1_fn = self._chunk_runner_for(
                                1, mr, state
                            )
                        cf = chunk1_fn
                    r0 = rounds
                    with tr.span("chunk", start_round=r0) as sp:
                        out = cf(frag.dev, carry, eph_part,
                                 jnp.int32(int(active)),
                                 jnp.int32(rounds))
                        sp.mark("dispatched")
                        new_carry, r2, new_active, dig, res = (
                            jax.block_until_ready(out)
                        )
                        sp.set(end_round=int(r2), active=int(new_active))
                    rounds = int(r2)
                    if tr.enabled:
                        tr.counter("active_vertices",
                                   value=int(new_active))
                        m = obs.metrics()
                        # every superstep inside the chunk counts; the
                        # active series only has chunk-BOUNDARY samples
                        # here (the in-chunk votes never reach the
                        # host) — documented in docs/OBSERVABILITY.md
                        m.counter("grape_supersteps_total").inc(
                            rounds - r0
                        )
                        m.series("grape_active_per_round").append(
                            int(new_active)
                        )
                    carry, active = new_carry, new_active
                    # injected corruption lands BEFORE the probe (same-
                    # round detection) and before the save; a corrupted
                    # carry invalidates the in-dispatch digest/residual,
                    # so the monitor re-probes fully
                    digest = tuple(int(x) for x in np.asarray(dig))
                    res_f = float(res)
                    residual = None if res_f < 0 else res_f
                    if fault_plan is not None:
                        corrupted = fault_plan.maybe_corrupt_carry(
                            carry, rounds
                        )
                        if corrupted is not None:
                            carry = {
                                **carry, **self._place_state(corrupted)
                            }
                            digest = residual = None
                    if int(active) >= 0:
                        breach = monitor.check(
                            prev, carry, rounds, int(active),
                            digest=digest, residual=residual,
                        )
                        if breach is not None:
                            if breach.action == "rollback":
                                restored, meta = monitor.rollback(breach)
                                carry = self._place_state(restored)
                                rounds = int(meta["rounds"])
                                active = np.int32(meta["active"])
                                prev = carry
                                # the rollback rewinds past this
                                # boundary's save and injection hooks
                                continue
                            monitor.raise_breach(breach)
                    prev = carry
                    if (
                        ckpt is not None
                        and rounds % checkpoint_every == 0
                        and rounds > 0
                    ):
                        ckpt.save_async(carry, rounds, int(active))
                    if fault_plan is not None:
                        fault_plan.on_superstep(rounds, ckpt)
                self.rounds = rounds
                self._terminate_code = min(0, int(active))
                self._finish_query_obs(qsp)
        finally:
            # flush in finally: a halt-policy breach raises out of the
            # span context, and its guard_breach instant must still
            # land in the file sinks, not wait for the atexit hook;
            # the in-flight snapshot must land durable the same way
            if ckpt is not None:
                ckpt.close()
            if tr.enabled:
                obs.flush()
        self._result_state = {**carry, **eph_part}
        self._result_fragment = self.fragment
        return self._result_state

    def _place_state(self, state_np):
        """Place the init state: sharded leaves over the frag axis,
        declared-replicated leaves everywhere, custom-spec leaves per
        their declared PartitionSpec.  Multi-process meshes go through
        `put_global` (every process holds the same host arrays)."""
        from libgrape_lite_tpu.parallel.comm_spec import put_global

        mesh, _ = self._mesh_layout()
        specs, _ = self._key_specs(state_np)
        return {
            k: put_global(v, NamedSharding(mesh, specs[k]))
            for k, v in state_np.items()
        }

    def _compile_single_step(self, kind: str, state):
        """One jitted (PEval | IncEval) superstep — the unfused building
        block shared by query_stepwise; `query` fuses the whole loop via
        _make_runner instead."""
        app = self.app
        mesh, frag_spec = self._mesh_layout()
        specs, squeezed = self._key_specs(state)
        eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
        out_specs = {k: v for k, v in specs.items() if k not in eph}

        def fn(frag_stacked, st):
            lf = frag_stacked.local()
            s = _squeeze_state(st, squeezed)
            from libgrape_lite_tpu.app.base import StepContext

            ctx = StepContext()
            s2, active = (
                app.peval(ctx, lf, s) if kind == "peval"
                else app.inceval(ctx, lf, s)
            )
            s2 = {k: v for k, v in s2.items() if k not in eph}
            return _unsqueeze_state(s2, squeezed), jnp.int32(active)

        return jax.jit(
            compat.shard_map(
                fn, mesh=mesh, in_specs=(frag_spec, specs),
                out_specs=(out_specs, P()), check_vma=False,
            )
        )

    def _compile_batched_step(self, kind: str, state, batch: int):
        """One jitted vmapped (PEval | IncEval) superstep over the lane
        axis — the guarded serve path's building block (serve/batch.py
        drives PEval once, then batched chunks)."""
        mesh, frag_spec = self._mesh_layout()
        specs, squeezed = self._key_specs_batch(state)
        eph = frozenset(getattr(self.app, "ephemeral_keys", ()) or ())
        out_specs = {k: v for k, v in specs.items() if k not in eph}

        def fn(frag_stacked, st):
            lf = frag_stacked.local()
            eph_state = {k: st[k] for k in eph}
            eph_vals = {k: v[0] for k, v in eph_state.items()}
            s = _squeeze_lane_state(
                {k: v for k, v in st.items() if k not in eph}, squeezed
            )
            lane_peval, lane_inc = self._lane_stepper_parts(eph_vals)
            lane = lane_peval if kind == "peval" else lane_inc
            s2, active = jax.vmap(lambda x: lane(lf, x))(s)
            return _unsqueeze_lane_state(s2, squeezed), active

        return jax.jit(
            compat.shard_map(
                fn, mesh=mesh, in_specs=(frag_spec, specs),
                out_specs=(out_specs, P()), check_vma=False,
            )
        )

    def _single_step_for(self, kind: str, state):
        """Cached _compile_single_step: the stepwise and guarded
        paths previously minted a fresh jit wrapper per query, so
        every stepwise profile run and every guarded query re-traced
        and re-compiled its PEval/IncEval step — invisible to
        runner_cache_stats, visible to analysis.compile_events()
        (grape-lint R2; the same class as PR 6's guarded-serve
        per-batch re-jit)."""
        key = (
            "step", kind,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key, lambda: self._compile_single_step(kind, state)
        )

    def _batched_step_for(self, kind: str, state, batch: int):
        """Cached _compile_batched_step: a serve session dispatches
        many guarded batches of the same shape, and each fresh jit
        wrapper would retrace + recompile the identical vmapped PEval
        (invisible to runner_cache_stats — the zero-recompile
        accounting must see it)."""
        key = (
            "batched-step", kind, batch,
            self.app.trace_key(),
            self._state_struct(state),
        )
        return self._cached_runner(
            key,
            lambda: self._compile_batched_step(kind, state, batch),
        )

    def query_stepwise(self, max_rounds: int | None = None, *,
                       checkpoint_every: int | None = None,
                       checkpoint_dir: str | None = None,
                       fault_plan=None, guard=None, _resume: bool = False,
                       **query_args):
        """Host-driven query: one jitted superstep per round with
        per-round wall time + termination-vote logs — the observable
        behavior of the reference's coordinator logs (`worker.h:120-139`)
        and -DPROFILING timers.  Also the execution mode for
        MutationContext apps (`query` routes them here), since the graph
        can be rebuilt between rounds, and for checkpointed queries
        (`checkpoint_every=K` snapshots the carry pytree every K
        supersteps via ft/checkpoint.py).  Slower than the fused `query`
        (host sync per round); results are identical for mutation-free
        apps.

        With obs/ armed, every round emits a `superstep` span.  Timing
        convention (documented on tracer.Span): the clock stops only
        AFTER `jax.block_until_ready` on the round's full carry, so
        `dur` is honest wall time; the `dispatched` mark splits it
        into `dispatched_us` (host enqueue — inflated by trace+compile
        on the first round) and `device_wait_us` (the device-execution
        estimate).  Reported vlog times follow the same synced
        interval."""
        # public entry point too (profiling surface): an uncontracted
        # app must fail loudly on a staged dyn view here as well
        self._check_dyn_view()
        tr = obs.tracer()
        if not tr.enabled:
            return self._query_stepwise_impl(
                max_rounds, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
                guard=guard, _resume=_resume, **query_args,
            )
        try:
            with tr.span("query", mode="stepwise",
                         app=type(self.app).__name__) as sp:
                out = self._query_stepwise_impl(
                    max_rounds, checkpoint_every=checkpoint_every,
                    checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
                    guard=guard, _resume=_resume, **query_args,
                )
                self._finish_query_obs(sp)
        finally:
            # flush in finally: a breach/fault raising out of the loop
            # must still land its spans + instants in the file sinks
            obs.flush()
        return out

    def _query_stepwise_impl(self, max_rounds: int | None = None, *,
                             checkpoint_every: int | None = None,
                             checkpoint_dir: str | None = None,
                             fault_plan=None, guard=None,
                             _resume: bool = False, **query_args):
        import time

        from libgrape_lite_tpu.utils import logging as glog

        tr = obs.tracer()

        app = self.app
        frag = self.fragment
        has_mutations = hasattr(app, "collect_mutations")
        if checkpoint_dir and checkpoint_every is None and not _resume:
            raise ValueError(
                "checkpoint_dir requires checkpoint_every (a dir alone "
                "would run stepwise while writing no snapshots); to "
                "continue a previous run use Worker.resume"
            )
        checkpointing = checkpoint_every is not None or _resume
        if checkpointing:
            if getattr(app, "host_only", False):
                raise ValueError(
                    "checkpointing requires the superstep path; "
                    f"{type(app).__name__} is a host-only app"
                )
            if has_mutations:
                raise ValueError(
                    "checkpointing MutationContext apps is not supported "
                    "(the fragment itself changes between rounds)"
                )
            if not checkpoint_dir:
                raise ValueError("checkpoint_every requires checkpoint_dir")
            if checkpoint_every is not None and checkpoint_every <= 0:
                raise ValueError(
                    f"checkpoint_every must be >= 1, got {checkpoint_every}"
                )
        if getattr(app, "host_only", False):
            return self.query(max_rounds, **query_args)
        mr = app.max_rounds if max_rounds is None else max_rounds
        if mr <= 0:
            mr = _INT32_MAX

        if fault_plan is None:
            from libgrape_lite_tpu.ft.faults import active_plan

            fault_plan = active_plan()
        if fault_plan.is_noop():
            fault_plan = None

        from libgrape_lite_tpu.guard.config import GuardConfig

        guard_cfg = GuardConfig.resolve(guard)
        self._guard_monitor = None

        state_np = self._seeded(app.init_state(frag, **query_args))
        eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
        ckpt = None
        resume_meta = None
        if checkpointing:
            from libgrape_lite_tpu.ft.checkpoint import (
                CheckpointManager, CheckpointMismatchError, latest_meta,
                restore_latest,
            )
            from libgrape_lite_tpu.ft.fingerprint import (
                canonical_query_args, compute_fingerprint,
            )

            distributed = jax.process_count() > 1
            fingerprint = compute_fingerprint(app, frag, query_args)
            if _resume:
                meta0 = latest_meta(checkpoint_dir)
                fp0 = meta0.get("fingerprint", {})
                from libgrape_lite_tpu.ft.distributed import (
                    GEOMETRY_KEYS,
                )

                if meta0.get("layout") == "sharded" and any(
                    fp0.get(k) != fingerprint.get(k)
                    for k in GEOMETRY_KEYS
                ):
                    # reshard-on-loss: the snapshot was written by a
                    # different mesh — a lost rank, a changed fnum, or
                    # the same shape cut differently (fragment_hash);
                    # gather the surviving shard files and scatter the
                    # carry onto THIS mesh's layout
                    from libgrape_lite_tpu.ft.distributed import (
                        restore_resharded,
                    )

                    restored, resume_meta = restore_resharded(
                        checkpoint_dir, frag, fingerprint,
                        base_state={
                            k: v for k, v in state_np.items()
                            if k not in eph
                        },
                    )
                else:
                    restored, resume_meta = restore_latest(
                        checkpoint_dir, fingerprint
                    )
                carry_keys = {k for k in state_np if k not in eph}
                if set(restored) != carry_keys:
                    raise CheckpointMismatchError(
                        f"checkpoint carry keys {sorted(restored)} != "
                        f"this query's carry keys {sorted(carry_keys)}"
                    )
                state_np = {**state_np, **restored}
                if checkpoint_every is None:
                    checkpoint_every = (
                        resume_meta.get("checkpoint_every") or None
                    )
            if checkpoint_every is not None and distributed:
                # the carry spans non-addressable devices: each process
                # writes only its local shards, committed under the
                # two-phase barrier (ft/distributed.py)
                from libgrape_lite_tpu.ft.distributed import (
                    ShardedCheckpointManager,
                )

                ckpt = ShardedCheckpointManager(
                    checkpoint_dir,
                    fingerprint=fingerprint,
                    query_args=canonical_query_args(query_args),
                    checkpoint_every=checkpoint_every,
                    frag=frag,
                    fresh_start=not _resume,
                )
            elif checkpoint_every is not None:
                ckpt = CheckpointManager(
                    checkpoint_dir,
                    fingerprint=fingerprint,
                    query_args=canonical_query_args(query_args),
                    checkpoint_every=checkpoint_every,
                    # a new query starts a new lineage; stale
                    # checkpoints in a reused dir must not shadow it
                    fresh_start=not _resume,
                )

        state = self._place_state(state_np)
        led = self.pack_ledger() if glog.vlog_level() >= 1 else None
        if led:
            # per-stage ALU attribution for the engaged spgemm plan — the
            # stepwise profile's wall-clock lines read against these
            # modeled shares (first-light playbook step 3); the whole
            # block is gated on the level so a silent run never pays
            # the ledger merge + string build
            t = led["totals"]
            e = max(1, led["edges"])
            stages = ", ".join(
                f"{k}={v / e:.1f}"
                for k, v in sorted(t.get("per_stage", {}).items())
            )
            glog.vlog(
                1,
                f"pack op-budget: {t['vpu_ops'] / e:.1f} VPU ops/edge, "
                f"{t['mxu_ops'] / e:.1f} MXU elems/edge, "
                f"{t['gather_rows'] / e:.2f} gather rows/edge over "
                f"{t['blocks']} blocks / {len(led['levels'])} levels "
                f"(per-stage VPU ops/edge: {stages})",
            )
        inc_fn = self._single_step_for("inceval", state)
        # a fresh-compiled inc_fn means the FIRST superstep dispatch
        # below includes trace+compile: that round's span gets a
        # `compiled` mark
        inc_fresh = getattr(self, "_last_runner_miss", False)
        # ephemeral leaves drop out of each step's outputs; re-merge the
        # placed originals so the next step's inputs stay complete
        eph_vals = {k: state[k] for k in eph}

        def carry_of(st):
            return {k: v for k, v in st.items() if k not in eph}

        monitor = None
        if guard_cfg.enabled:
            from libgrape_lite_tpu.guard.monitor import GuardMonitor

            monitor = GuardMonitor(
                app=app, frag=frag, config=guard_cfg, ckpt=ckpt,
                ledger=self.pack_ledger(),
            )
            self._guard_monitor = monitor
            glog.vlog(
                1, "guard: stepwise probes every %d round(s) "
                "(policy=%s)", guard_cfg.every, guard_cfg.policy,
            )
            if has_mutations:
                # MutationContext apps guard too (dyn/): each mutation
                # boundary resets the watchdog digest history and
                # re-resolves the probe — a pre-mutation digest match
                # proves nothing about the REBUILT graph's operator
                glog.vlog(
                    1, "guard: mutation-aware — digest history resets "
                    "at every mutation boundary",
                )

        # cross-rank breach vote (guard/vote.py): armed only under
        # jax.distributed AND only when a hazard hook exists — guard,
        # checkpointing, or an injected fault plan, all of which are
        # env/flag-symmetric across the gang.  Single-process `vote`
        # stays None and voted_hooks degenerates to a plain call, so
        # this path's behavior is bit-identical to the pre-vote code.
        vote = None
        if jax.process_count() > 1 and (
            monitor is not None or ckpt is not None
            or fault_plan is not None
        ):
            from libgrape_lite_tpu.guard.vote import BreachVote

            vote = BreachVote.for_current_process()

        # gang trace federation (obs/gang.py): anchor the clock
        # handshake and land the first per-rank sidecar BEFORE the
        # first vote collective, so even a round-0 halt leaves a
        # mergeable file for the rank-0 assembler.  Symmetric by the
        # same contract as the vote itself: GRAPE_TRACE is documented
        # env-symmetric across the gang.
        gang_armed = vote is not None and tr.enabled
        if gang_armed:
            obs.gang.ensure_handshake()
            obs.gang.write_sidecar()

        def voted_hooks(vote_rounds, hooks):
            """Run one superstep boundary's host-side hazard hooks
            (probe / snapshot / fault injection) under the breach
            vote: every rank exchanges a verdict at this same cut, so
            a one-rank halt (InvariantBreachError, DivergenceError,
            InjectedFault, an IO error in a hook) halts EVERY rank
            instead of stranding siblings in the next collective.  A
            halt raised by the vote (local err re-raise or
            RemoteBreachError) first triggers the distributed flight
            recorder: every rank dumps its postmortem shard under the
            shared incident id the vote derived (obs/gang.py)."""
            if vote is None:
                return hooks()
            err = None
            out = None
            try:
                out = hooks()
            except Exception as e:
                err = e
            try:
                vote.round_vote(vote_rounds, err)  # re-raises err
            except BaseException as halt:
                if gang_armed:
                    obs.gang.on_breach_halt(halt, vote_rounds)
                raise
            return out

        # the monotone invariants compare against the carry of the LAST
        # probe (not the last round): with a probe cadence > 1 an
        # in-gap increase that settles into a new fixed point would
        # otherwise slip past round-to-round comparison
        guard_prev = None
        if resume_meta is not None:
            rounds = int(resume_meta["rounds"])
            active = np.int32(resume_meta["active"])
            guard_prev = carry_of(state) if monitor is not None else None
            glog.vlog(
                1, "resumed from superstep %d (active=%d, dir=%s)",
                rounds, int(active), checkpoint_dir,
            )
            tr.instant("resume", round=rounds, active=int(active))
        else:
            peval_fn = self._single_step_for("peval", state)
            prev_carry = carry_of(state) if monitor is not None else None
            t0 = time.perf_counter()
            # timing convention: the clock stops only after the sync on
            # the full carry (block_until_ready), so PEval's reported
            # time is wall including device execution — not the async
            # dispatch-only time a naive t1-t0 around the call measures
            with tr.span("peval", round=0) as sp:
                out = self._enqueue(
                    peval_fn, "stepwise", 1, frag.dev, state)
                if getattr(self, "_last_runner_miss", False):
                    sp.mark("compiled")
                sp.mark("dispatched")
                state, active = jax.block_until_ready(out)
                sp.set(active=int(active))
            state = {**state, **eph_vals}
            glog.vlog(
                1, "PEval: %.6fs active=%d",
                time.perf_counter() - t0, int(active),
            )
            if tr.enabled:
                self._mirror_superstep(tr, sp, 0, "peval")
                tr.counter("active_vertices", value=int(active))
                m = obs.metrics()
                m.series("grape_active_per_round").append(int(active))
                m.counter("grape_supersteps_total").inc()
            rounds = 0
            if fault_plan is not None:
                # injected device-state corruption lands BEFORE the
                # probe (so detection is same-round) and before the
                # save (warn-policy runs aside, a corrupt state never
                # becomes the snapshot a rollback would restore)
                corrupted = fault_plan.maybe_corrupt_carry(
                    carry_of(state), 0
                )
                if corrupted is not None:
                    state = {**state, **self._place_state(corrupted)}
            def peval_hooks():
                if (
                    monitor is not None and int(active) >= 0
                    and monitor.due(0)
                ):
                    # a PEval breach has no snapshot to restore — any
                    # non-warn verdict halts
                    breach = monitor.check(
                        prev_carry, carry_of(state), 0, int(active)
                    )
                    if breach is not None:
                        monitor.raise_breach(breach)
                if ckpt is not None:
                    # a superstep-0 snapshot always exists, so a kill
                    # at any later round has something to fall back to
                    ckpt.save_async(carry_of(state), 0, int(active))
                if fault_plan is not None:
                    fault_plan.on_superstep(0, ckpt)

            voted_hooks(0, peval_hooks)
            if gang_armed:
                # drain this rank's spans so the merged gang timeline
                # survives a kill at any later round
                obs.gang.write_sidecar()
            if monitor is not None and int(active) >= 0 and monitor.due(0):
                guard_prev = carry_of(state)

        def apply_mutations_if_any(state, frag, inc_fn, rounds):
            host_state = {
                k: np.asarray(v) for k, v in jax.device_get(state).items()
            }
            mutator = app.collect_mutations(frag, host_state, rounds)
            if mutator is None:
                return state, frag, inc_fn, False
            old_frag = frag
            frag = mutator.mutate(frag)
            self.fragment = frag
            fresh = app.init_state(frag, **query_args)
            migrated = app.migrate_state(old_frag, frag, host_state, fresh)
            state = self._place_state(migrated)
            # cached too: an unchanged post-mutation state struct
            # re-uses the compiled step (the fragment rides as an
            # argument, so reuse is sound); a changed struct misses
            inc_fn = self._single_step_for("inceval", state)
            glog.vlog(1, "applied mutations after round %d", rounds)
            tr.instant("apply_mutations", round=rounds)
            return state, frag, inc_fn, True

        if has_mutations:
            # mutations staged during PEval apply even when the query
            # would otherwise converge immediately (worker.h:211-222
            # applies them every round boundary); a ForceTerminate vote
            # (negative active) still wins
            state, frag, inc_fn, changed = apply_mutations_if_any(
                state, frag, inc_fn, 0
            )
            if changed:
                # the rebuilt state carries fresh ephemeral leaves
                eph_vals = {k: state[k] for k in eph}
                inc_fresh = (inc_fresh
                             or getattr(self, "_last_runner_miss", False))
                if monitor is not None:
                    monitor.on_mutation(frag, self.pack_ledger())
                    guard_prev = carry_of(state)
            if changed and int(active) >= 0:
                active = 1
        try:
            while int(active) > 0 and rounds < mr:
                t0 = time.perf_counter()
                # same sync-before-clock-stop convention as PEval: the
                # span (and the vlog line) cover dispatch + device wait
                with tr.span("superstep", round=rounds + 1) as sp:
                    out = inc_fn(frag.dev, state)
                    if inc_fresh:
                        # first dispatch since (re)compile
                        sp.mark("compiled")
                        inc_fresh = False
                    sp.mark("dispatched")
                    state, active = jax.block_until_ready(out)
                    sp.set(active=int(active))
                state = {**state, **eph_vals}
                rounds += 1
                glog.vlog(
                    1, "IncEval round %d: %.6fs active=%d",
                    rounds, time.perf_counter() - t0, int(active),
                )
                if tr.enabled:
                    self._mirror_superstep(tr, sp, rounds, "superstep")
                    tr.counter("active_vertices", value=int(active))
                    m = obs.metrics()
                    m.series("grape_active_per_round").append(int(active))
                    m.counter("grape_supersteps_total").inc()
                if fault_plan is not None:
                    # corruption lands BEFORE the probe: detection is
                    # same-round even for carries a further superstep
                    # would wash clean (CDLP mode adoption)
                    corrupted = fault_plan.maybe_corrupt_carry(
                        carry_of(state), rounds
                    )
                    if corrupted is not None:
                        state = {**state, **self._place_state(corrupted)}
                # the probe runs BEFORE the cadence save — and is
                # FORCED on checkpoint rounds even when the guard
                # cadence would skip them: a state that fails its
                # invariants must never become the snapshot a later
                # rollback restores (a rollback `continue` also skips
                # this round's save and injection hooks)
                ckpt_round = (
                    ckpt is not None and rounds % checkpoint_every == 0
                )

                def round_hooks(rounds=rounds, active=active,
                                ckpt_round=ckpt_round):
                    # probe / snapshot / injection for this superstep;
                    # returns a (restored, meta) rollback payload or
                    # None.  The rollback decision is driven by jitted
                    # GLOBAL probes, so it is symmetric across ranks —
                    # every rank returns the same payload and the
                    # lockstep vote in voted_hooks holds.
                    if (
                        monitor is not None and int(active) >= 0
                        and (monitor.due(rounds) or ckpt_round)
                    ):
                        breach = monitor.check(
                            guard_prev, carry_of(state), rounds,
                            int(active)
                        )
                        if breach is not None:
                            if breach.action == "rollback":
                                return monitor.rollback(breach)
                            monitor.raise_breach(breach)
                    if (
                        ckpt is not None
                        and rounds % checkpoint_every == 0
                    ):
                        ckpt.save_async(
                            carry_of(state), rounds, int(active)
                        )
                    if fault_plan is not None:
                        fault_plan.on_superstep(rounds, ckpt)
                    return None

                rolled = voted_hooks(rounds, round_hooks)
                if gang_armed:
                    obs.gang.write_sidecar()
                if rolled is not None:
                    restored, meta = rolled
                    state = {**state, **self._place_state(restored)}
                    rounds = int(meta["rounds"])
                    active = np.int32(meta["active"])
                    guard_prev = carry_of(state)
                    continue
                if (
                    monitor is not None and int(active) >= 0
                    and (monitor.due(rounds) or ckpt_round)
                ):
                    guard_prev = carry_of(state)
                if has_mutations:
                    # MutationContext path (reference worker.h:211-222);
                    # never overrides a ForceTerminate vote
                    state, frag, inc_fn, changed = apply_mutations_if_any(
                        state, frag, inc_fn, rounds
                    )
                    if changed:
                        eph_vals = {k: state[k] for k in eph}
                        inc_fresh = (
                            inc_fresh
                            or getattr(self, "_last_runner_miss", False)
                        )
                        if monitor is not None:
                            # the graph (and its superstep operator)
                            # changed: digest history no longer proves
                            # cycles, monotone comparisons must not
                            # span the rebuild
                            monitor.on_mutation(frag, self.pack_ledger())
                            guard_prev = carry_of(state)
                    if changed and int(active) >= 0:
                        active = 1  # the new topology must be re-evaluated
                        if rounds >= mr:
                            glog.log_info(
                                "mutation applied on the final permitted "
                                "round; the rebuilt topology was NOT "
                                "re-evaluated — raise max_rounds"
                            )
        finally:
            # flush the in-flight snapshot even on an exception (an
            # injected raise-mode kill must leave a durable checkpoint)
            if ckpt is not None:
                ckpt.close()
        self.rounds = rounds
        self._terminate_code = min(0, int(active))
        self._result_state = state
        self._result_fragment = self.fragment
        return state

    def pack_ledger(self):
        """The engaged spgemm plan's static op-budget ledger
        (ops/spgemm_pack `_ledger_from_counts` form), or None when the
        app resolved none — the stepwise profiling hook, guard/ and
        obs/ read per-stage ALU attribution from here."""
        d = getattr(self.app, "_spgemm", None)
        return (d.ledger() or None) if d is not None else None

    def resume(self, checkpoint_dir: str, max_rounds: int | None = None, *,
               checkpoint_every: int | None = None, fault_plan=None,
               guard=None):
        """Continue a checkpointed query from the last complete
        superstep.  The config fingerprint (app, fragment content, mesh
        shape, query args, numeric config) is validated before any
        state is adopted — a mismatch raises `CheckpointMismatchError`;
        a corrupt newest shard falls back to the previous complete
        superstep.  Query args are replayed from checkpoint metadata,
        so the resumed run finishes with byte-identical results to an
        uninterrupted one.  Checkpointing continues at the recorded
        cadence unless `checkpoint_every` overrides it."""
        from libgrape_lite_tpu.ft.checkpoint import (
            CheckpointMismatchError, latest_meta,
        )
        from libgrape_lite_tpu.ft.fingerprint import app_registry_name

        meta = latest_meta(checkpoint_dir)
        # reject a wrong-app resume BEFORE replaying its query args into
        # this app's init_state (which would fail with an opaque
        # TypeError instead of the fingerprint diagnosis)
        recorded = (meta.get("fingerprint") or {}).get("app")
        mine = app_registry_name(self.app)
        if recorded is not None and recorded != mine:
            raise CheckpointMismatchError(
                f"checkpoint {checkpoint_dir!r} does not match this "
                f"query: app: checkpoint has {recorded!r}, query has "
                f"{mine!r}"
            )
        query_args = meta.get("query_args") or {}
        return self.query_stepwise(
            max_rounds, checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, fault_plan=fault_plan,
            guard=guard, _resume=True, **query_args,
        )

    # ---- Output / Assemble (reference worker.h:148-154, ctx.Output) ----

    def result_values(self) -> np.ndarray:
        """Per-vertex assembled values, [fnum, vp] numpy."""
        if self._result_state is None:
            raise RuntimeError("query() first")
        with obs.tracer().span("worker.extract"):
            if jax.process_count() > 1:
                # the carry spans non-addressable devices in a
                # jax.distributed run; gather each sharded leaf to a full
                # host copy so finalize sees the same [fnum, vp] view a
                # single-process run would
                from jax.experimental import multihost_utils

                host_state = {}
                for k, v in self._result_state.items():
                    if getattr(v, "is_fully_addressable", True):
                        host_state[k] = np.asarray(jax.device_get(v))
                    else:
                        host_state[k] = np.asarray(
                            multihost_utils.process_allgather(v)
                        )
            else:
                host_state = jax.device_get(self._result_state)
            self._record_round_stats()
            return self.app.finalize(self.fragment, host_state)

    def _record_round_stats(self) -> None:
        """ROUND_STATS from the record the extracted result's runner
        carried: host side, after the query and outside its wall."""
        ROUND_STATS.reset()
        rec = self._round_record
        if rec is None or rec[0] is not self._result_state:
            return
        # replicated: every device holds all of it
        words = np.asarray(rec[1].addressable_data(0)).astype(np.int64)
        # behind the plain loop's words one from the loop that can
        # follow its frontier, and three more where it carries a
        # threshold
        took, advances, pushed_lo, pushed_hi = (
            words[_RECORD_WORDS:].tolist() + [0] * 4)[:4]
        ROUND_STATS.update(
            app=type(self.app).__name__, rounds=int(self.rounds),
            active_bits=words[:_RECORD_BITS].tolist(),
            active_max=int(words[_RECORD_MAX]),
            active_sum=int(words[_RECORD_HI] << 32 | words[_RECORD_LO]),
            frontier_rounds=took, advances=advances,
            pushed_sum=pushed_hi << 32 | pushed_lo,
        )

    def output(self, prefix: str) -> None:
        """Write per-fragment result files `result_frag_<fid>` with
        `oid value` lines (reference `GetResultFilename` + ctx Output)."""
        import os

        # result_values() runs a process_allgather on non-fully-
        # addressable leaves — a collective EVERY process must join, so
        # all ranks gather before the single-writer early return below
        values = self.result_values()
        if jax.process_count() > 1 and jax.process_index() != 0:
            # every process now holds the full gathered result; one
            # writer keeps a shared output dir race-free
            return
        os.makedirs(prefix, exist_ok=True)
        fmt = self.app.result_format
        for f in range(self.fragment.fnum):
            n = self.fragment.inner_vertices_num(f)
            oids = self.fragment.inner_oids(f)
            vals = values[f, :n]
            path = os.path.join(prefix, f"result_frag_{f}")
            with open(path, "w") as out:
                out.write(format_result_lines(oids, vals, fmt))


def format_result_lines(oids, vals, fmt: str) -> str:
    if len(oids) == 0:
        return ""
    lines = []
    if fmt == "int":
        for o, v in zip(oids.tolist(), np.asarray(vals).tolist()):
            # string-keyed graphs carry str component/community ids
            lines.append(f"{o} {v if isinstance(v, str) else int(v)}")
    elif fmt == "sssp_infinity":
        for o, v in zip(oids.tolist(), np.asarray(vals).tolist()):
            if not np.isfinite(v):
                lines.append(f"{o} infinity")
            else:
                lines.append(f"{o} {v:.15e}")
    else:
        for o, v in zip(oids.tolist(), np.asarray(vals).tolist()):
            lines.append(f"{o} {v:.15e}")
    return "\n".join(lines) + "\n"
