#!/usr/bin/env python
"""Pre-seed the persistent pack-plan cache for bench.py's exact
geometry (host-side O(E log E) planning is hardware-independent, so
doing it ahead of a live-TPU window means `GRAPE_SPMV=pack bench.py`
loads the plan instead of spending live minutes building it).

The fragments come from bench.build_bench_fragment /
build_bench_weighted_fragment — the SAME code bench runs — so the
content-addressed digests match by construction.  Exits nonzero when
either plan fails to build (a silent MISS would only be discovered
during the live window)."""
import os
import sys

# host-side planning never needs the TPU: pin CPU before any jax import
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax

jax.config.update("jax_platforms", "cpu")

from bench import PLAN_CACHE_DIR, build_bench_fragment, \
    build_bench_weighted_fragment

os.environ.setdefault("GRAPE_PACK_PLAN_CACHE", PLAN_CACHE_DIR)
from libgrape_lite_tpu.ops.spmv_pack import resolve_pack_dispatch

n, src, dst, comm_spec, vm, frag = build_bench_fragment()
frag_w = build_bench_weighted_fragment(src, dst, comm_spec, vm)

# seed BOTH scan modes: the live-window A/B (GRAPE_PACK_SCAN=mxu vs
# shift, tpu_first_light step 2b) must not burn live minutes on the
# O(E log E) planner; the cache digest fingerprints the mode, so each
# seeds its own entry
ok = True
for mode in ("mxu", "shift"):
    os.environ["GRAPE_PACK_SCAN"] = mode
    d = resolve_pack_dispatch(frag)
    print(f"pagerank plan [{mode}]:",
          "ok" if d is not None else "MISSED", flush=True)
    dw = resolve_pack_dispatch(frag_w, with_weights=True)
    print(f"sssp plan [{mode}]:",
          "ok" if dw is not None else "MISSED", flush=True)
    ok = ok and d is not None and dw is not None

sys.exit(0 if ok else 1)
