"""App-facing collective aggregates.

Re-design of `grape/communication/communicator.h:35-127` (MPI gather +
bcast on rank 0) and `grape/cuda/communication/communicator.h:29-216`
(ncclAllReduce): on TPU these are single XLA collectives over the frag
mesh axis, usable *inside* jitted superstep code.
"""

from __future__ import annotations

import jax
from jax import lax

from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS


def collective_scope():
    """The device-trace name of every collective of the exchange layer
    (metadata only).  A new scope object per use: one instance keeps
    the context it replaced and is not safe to nest or to share."""
    return jax.named_scope("grape.exchange.collective")


class Communicator:
    """Mixin/namespace of in-step collectives. Methods must be called
    inside `shard_map` tracing over the frag axis."""

    axis = FRAG_AXIS

    @staticmethod
    def sum(x):
        with collective_scope():
            return lax.psum(x, FRAG_AXIS)

    @staticmethod
    def min(x):
        with collective_scope():
            return lax.pmin(x, FRAG_AXIS)

    @staticmethod
    def max(x):
        with collective_scope():
            return lax.pmax(x, FRAG_AXIS)

    @staticmethod
    def all_gather(x, tiled: bool = True):
        """Gather per-shard blocks into the full array (the analogue of
        BatchShuffle's whole-array sync, `batch_shuffle_message_manager.h:237`)."""
        with collective_scope():
            return lax.all_gather(x, FRAG_AXIS, tiled=tiled)

    @staticmethod
    def all_to_all(x, split_axis=0, concat_axis=0):
        with collective_scope():
            return lax.all_to_all(
                x, FRAG_AXIS, split_axis=split_axis,
                concat_axis=concat_axis, tiled=True,
            )

    @staticmethod
    def ppermute(x, perm):
        with collective_scope():
            return lax.ppermute(x, FRAG_AXIS, perm)

    @staticmethod
    def axis_index():
        return lax.axis_index(FRAG_AXIS)

    @staticmethod
    def axis_size():
        return lax.axis_size(FRAG_AXIS)
