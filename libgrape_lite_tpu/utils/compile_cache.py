"""Where JAX's persistent compilation cache lives.

Every entry point (`cli.main` / `serve_main`, `chip_smoke.py`,
`bench.py`, `scripts/run_ldbc.py`) calls `place_compile_cache()` before
its first compile, so processes that run from one checkout share their
executables.  The directory never moves between them: no temp name, pid
or time.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Returns the directory this process caches executables in.

    `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and whoever
    placed the cache owns its policy — nothing is set in code.  Unset:
    `<checkout>/.jax_cache`, with JAX's 1 s compile-time floor dropped
    to 0 so the sub-second fused runners are kept too."""
    from libgrape_lite_tpu import obs

    env = os.environ.get(CACHE_ENV)
    where = env or DEFAULT_CACHE_DIR
    # one set-up record: where the cache is and whether the floor was set
    with obs.tracer().span("compile_cache", dir=where, floor_set=not env):
        if not env:
            import jax

            jax.config.update("jax_compilation_cache_dir", where)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
    return where
