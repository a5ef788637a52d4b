"""The one spelling of `shard_map` the SPMD call sites share: the
public `jax.shard_map` with its `check_vma=` keyword (every call site
disables the checker — collectives like `all_to_all` have no rule)."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
