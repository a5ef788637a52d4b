"""Message managers: collective communication strategies.

Re-design of the reference message-manager family
(`grape/parallel/*message_manager*.h`).  The reference moves explicit
byte archives between MPI ranks; on TPU a "message" is a position in a
dense or fixed-capacity tensor and the transport is an XLA collective.
The managers here are *strategy namespaces* used inside traced superstep
code:

* batch-shuffle / sync-on-outer-vertex  → `StepContext.gather_state`
  (one `all_gather`; see app/base.py) — reference
  `batch_shuffle_message_manager.h`.
* auto messaging (SyncBuffer)           → `AutoParallelMessageManager`:
  per-vertex *proposal* arrays all-reduced with the buffer's aggregate
  op — reference `auto_parallel_message_manager.h:47-365`
  (generateAutoMessages / aggregateAutoMessages become one
  `psum`/`pmin`/`pmax` over pid-indexed proposals).
* point-to-point message tensors        → `AllToAllMessageManager`:
  fixed-capacity per-destination (lid, payload) tensors exchanged with
  `all_to_all` — reference `default_message_manager.h` /
  `parallel_message_manager.h` (the per-destination InArchives + length
  allgather + isend/irecv become one static-shape collective; the
  length sync disappears because capacity is static, and overflow is
  detected with a `psum` vote so the caller can retry with a larger
  capacity — the role of `EstimateMessageSize`, worker.h:157-170).

Termination (`ToTerminate`, `parallel_message_manager.h:123-138`): all
managers express the 2-int MPI_Allreduce as a `psum` of the per-shard
active count; `ForceContinue` is returning a nonzero vote.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS
from libgrape_lite_tpu.parallel.communicator import collective_scope


class MessageManagerBase:
    """Protocol documentation holder (reference
    `message_manager_base.h`): Init/Start/StartARound/FinishARound are
    trace-time no-ops on TPU (XLA owns scheduling); ToTerminate is the
    psum vote computed by the app; Finalize is garbage collection."""


class AutoParallelMessageManager(MessageManagerBase):
    """SyncBuffer aggregation: proposals are [n_pad] arrays (neutral
    element everywhere a shard has nothing to say); the aggregate op
    runs as one all-reduce collective and each shard keeps its slice."""

    _REDUCERS = {
        "min": lambda x: lax.pmin(x, FRAG_AXIS),
        "max": lambda x: lax.pmax(x, FRAG_AXIS),
        "sum": lambda x: lax.psum(x, FRAG_AXIS),
    }

    @classmethod
    def sync(cls, frag, proposals: Dict[str, jnp.ndarray], ops: Dict[str, str]):
        """Aggregate proposals across shards; return own-slice dict."""
        vp = frag.vp
        fid = lax.axis_index(FRAG_AXIS)
        out = {}
        for k, prop in proposals.items():
            with collective_scope():
                combined = cls._REDUCERS[ops[k]](prop)
            with jax.named_scope("grape.exchange.unpack"):
                out[k] = lax.dynamic_slice(combined, (fid * vp,), (vp,))
        return out


class AllToAllMessageManager(MessageManagerBase):
    """Fixed-capacity point-to-point message tensors.

    `exchange` routes per-message payloads to destination shards:
    messages are sorted by destination, packed into a [fnum, capacity]
    tensor (sliced per destination), exchanged with one `all_to_all`,
    and returned as flat receive buffers plus a global overflow flag.
    """

    @staticmethod
    def exchange(dest_fid, lid, payload, valid, capacity: int, fnum: int):
        """All inputs are per-shard flat arrays of equal length M.

        Returns (recv_lid [fnum*capacity], recv_payload, recv_valid,
        overflowed_scalar).  Messages beyond `capacity` for any single
        destination are dropped and flagged (callers retry with a
        bigger capacity or fall back to the dense path).
        """
        with jax.named_scope("grape.exchange.pack"):
            m = dest_fid.shape[0]
            big = jnp.int32(fnum)
            d = jnp.where(valid, dest_fid.astype(jnp.int32), big)
            order = jnp.argsort(d)  # stable: groups by destination
            d_s = d[order]
            lid_s = lid[order]
            pay_s = payload[order]

            # rank within destination group
            idx = jnp.arange(m, dtype=jnp.int32)
            first_of_group = jnp.zeros(m, jnp.int32).at[1:].set(
                (d_s[1:] != d_s[:-1]).astype(jnp.int32)
            )
            # start index of each message's group (running max of group heads)
            starts = jnp.where(first_of_group > 0, idx, 0)
            starts = lax.associative_scan(jnp.maximum, starts)
            rank = idx - starts

            ok = jnp.logical_and(d_s < big, rank < capacity)
            slot_d = jnp.where(ok, d_s, big)
            slot_r = jnp.where(ok, rank, 0)

            send_lid = jnp.zeros((fnum + 1, capacity), lid.dtype)
            send_pay = jnp.zeros((fnum + 1, capacity), payload.dtype)
            send_val = jnp.zeros((fnum + 1, capacity), jnp.bool_)
            send_lid = send_lid.at[slot_d, slot_r].set(
                jnp.where(ok, lid_s, 0)
            )[:fnum]
            send_pay = send_pay.at[slot_d, slot_r].set(
                jnp.where(ok, pay_s, 0)
            )[:fnum]
            send_val = send_val.at[slot_d, slot_r].set(ok)[:fnum]

            overflow_local = jnp.logical_and(
                d_s < big, rank >= capacity
            ).any().astype(jnp.int32)
        with collective_scope():
            overflowed = lax.psum(overflow_local, FRAG_AXIS)
            recv_lid = lax.all_to_all(
                send_lid, FRAG_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            recv_pay = lax.all_to_all(
                send_pay, FRAG_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
            recv_val = lax.all_to_all(
                send_val, FRAG_AXIS, split_axis=0, concat_axis=0, tiled=True
            )
        with jax.named_scope("grape.exchange.unpack"):
            return (
                recv_lid.reshape(-1),
                recv_pay.reshape(-1),
                recv_val.reshape(-1),
                overflowed,
            )


def plan_initial_capacity(frag, requested: int | None, learned) -> int:
    """Initial per-destination message capacity for the exchange path —
    the role of the reference's `EstimateMessageSize` priming
    (`parallel_message_manager_opt.h`): `requested` wins; else the
    capacity a previous query on this fragment settled at (`learned` is
    the app's per-fragment WeakKeyDictionary); else a graph-informed
    floor — the densest vertex must be able to push all its edges to a
    single destination shard without overflowing round one.

    An armed fault plan (GRAPE_FT_FAULTS=capacity=N, ft/faults.py)
    clamps the result so the overflow vote + retry ladder actually
    executes in drills instead of being dead code on real graphs."""
    from libgrape_lite_tpu.ft.faults import active_plan

    if requested:
        return active_plan().clamp_capacity(max(1, requested))
    if frag in learned:
        return active_plan().clamp_capacity(learned[frag])
    max_deg = max(
        int(np.diff(c.indptr).max(initial=1)) for c in frag.host_oe
    )
    cap = 1024
    while cap < 2 * max_deg:
        cap *= 2
    return active_plan().clamp_capacity(cap)
