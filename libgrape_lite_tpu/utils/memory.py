"""Device/host memory accounting.

Re-design of the reference's `MemoryTracker` (`grape/utils/memory_tracker.h:26-43`)
and `GetMemoryUsage` (`grape/util.h:51-69`): instead of interposing on
malloc, we read live/peak bytes from the JAX device allocator and RSS
from /proc.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass

import jax
import numpy as np


@dataclass
class MemoryStats:
    device_bytes_in_use: int
    device_peak_bytes: int
    host_rss_bytes: int

    def __str__(self):
        gb = 1 << 30
        return (
            f"device in-use {self.device_bytes_in_use / gb:.3f} GiB, "
            f"device peak {self.device_peak_bytes / gb:.3f} GiB, "
            f"host rss {self.host_rss_bytes / gb:.3f} GiB"
        )


def get_host_rss() -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def get_memory_stats(device=None) -> MemoryStats:
    in_use = peak = 0
    devs = [device] if device is not None else jax.local_devices()
    for d in devs:
        # None on a backend without allocator stats (the CPU); a call
        # that fails on an accelerator raises — zeros there would read
        # as "nothing resident"
        ms = d.memory_stats()
        if ms:
            in_use += ms.get("bytes_in_use", 0)
            peak += ms.get("peak_bytes_in_use", 0)
    return MemoryStats(in_use, peak, get_host_rss())


def fullest_bytes_in_use() -> int | None:
    """`bytes_in_use` of the fullest local device, as `hbm_peak_bytes`
    takes its peak; None on a backend without allocator statistics
    (the CPU), where 0 would read as "nothing resident"."""
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.local_devices()
    ]
    if not in_use or any(b is None for b in in_use):
        return None
    return max(in_use)


#: a compiled executable's memory analysis under the set-up ledger's
#: names (phase `runner.compile`): name -> `CompiledMemoryStats` field
EXECUTABLE_BYTES = {
    "code_bytes": "generated_code_size_in_bytes",
    "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes",
    "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
}


def executable_bytes(compiled) -> dict:
    """What one device holds for `compiled` (a `jax.stages.Compiled`):
    the program's code, its temporaries, the arguments it reads, its
    outputs and the part of them that lies in donated arguments, by
    `memory_analysis()`.  Empty where the backend or the executable
    gives no analysis: an unknown size is left out, never 0."""
    analysis = compiled.memory_analysis()
    if analysis is None:
        return {}
    return {
        name: int(getattr(analysis, field))
        for name, field in EXECUTABLE_BYTES.items()
    }


def shard_bytes(tree) -> int:
    """Bytes one device holds of the placed arrays of `tree`, from
    shapes: a sharded leaf's shard, a replicated leaf whole."""
    return sum(
        math.prod(x.sharding.shard_shape(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )
