"""Offline TPU-lowering regression for every shipped Pallas kernel.

Round 1 shipped a kernel whose block shapes violated Mosaic's (8, 128)
rule — interpret-mode tests passed, and the failure only surfaced on
real hardware (docs/PERF_NOTES.md).  Mosaic lowering runs client-side,
so `.trace(...).lower(lowering_platforms=('tpu',))` validates kernels
with no TPU attached, in a child that runs x32 as a chip run does
(this lane's conftest enables x64).  What lowering cannot see — the
chip's backend compile and a compiled run — is chip_smoke.py's, on the
chip.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_offline(script: str, **env):
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=850,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp

import sys
sys.path.insert(0, %(repo)r)

from libgrape_lite_tpu.ops.spmv_pack import (
    PackConfig, plan_pack, segment_sum_pack,
)

# production geometry (the shipped default config): at vp = 2^20 the
# column space spans 4 gather passes, plus fold/final levels
cfg = PackConfig()
rng = np.random.default_rng(0)
vp = 8192 * 128            # 2^20 rows: the bench shard size
e = 200_000
rows = np.sort(rng.integers(0, vp, e))
cols = rng.integers(0, vp, e)
plan = plan_pack(rows, cols, vp, vp, cfg)

x = jax.ShapeDtypeStruct((vp,), jnp.float32)
traced = jax.jit(
    lambda x: segment_sum_pack(x, plan, interpret=False)
).trace(x)
low = traced.lower(lowering_platforms=('tpu',))
print("SPMV_PACK_LOWERED", len(low.as_text()))

# tropical min with baked weight stream (the SSSP relaxation)
from libgrape_lite_tpu.ops.spmv_pack import segment_reduce_pack
w = rng.uniform(0.1, 5.0, e).astype(np.float32)
plan_w = plan_pack(rows, cols, vp, vp, cfg, edge_w=w)
low = jax.jit(
    lambda x: segment_reduce_pack(x, plan_w, "min", interpret=False)
).trace(x).lower(lowering_platforms=('tpu',))
print("SPMV_PACK_MIN_LOWERED", len(low.as_text()))
"""


@pytest.mark.parametrize("scan", ["mxu", "shift"])
def test_spmv_pack_lowers_for_tpu(scan):
    r = _run_offline(SCRIPT % {"repo": REPO}, GRAPE_PACK_SCAN=scan)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SPMV_PACK_LOWERED" in r.stdout
    assert "SPMV_PACK_MIN_LOWERED" in r.stdout


# the MXU scan's matmul core (triangular lane cumsum, exclusive form,
# per-group tail broadcast + exclusive tail prefix with the chained
# base) in isolation, so a refusal of the full kernel above can be
# told apart from one of the scan's matmul math
MXU_SCRIPT = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUB, C, GR = 2048, 128, 128

def kernel(v_ref, o_ref):
    v = v_ref[...]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
           ).astype(v.dtype)
    rowcum = jnp.dot(v, tri, preferred_element_type=v.dtype)
    rseg = rowcum - v  # exclusive form (restore gather probed apart)
    e_last = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
              == (C - 1)).astype(v.dtype)
    lexc = (jax.lax.broadcasted_iota(jnp.int32, (GR, GR), 1)
            < jax.lax.broadcasted_iota(jnp.int32, (GR, GR), 0)
            ).astype(v.dtype)
    parts = []
    base = jnp.zeros((1, C), v.dtype)
    for g in range(SUB // GR):
        rg = rseg[g * GR:(g + 1) * GR]
        tail_g = jnp.dot(rg, e_last, preferred_element_type=v.dtype)
        s_exc_g = jnp.dot(lexc, tail_g, preferred_element_type=v.dtype)
        parts.append(s_exc_g + base)
        base = base + (s_exc_g[GR - 1:GR] + tail_g[GR - 1:GR])
    o_ref[...] = rseg + jnp.concatenate(parts, axis=0)

low = jax.jit(lambda v: pl.pallas_call(
    kernel,
    out_shape=jax.ShapeDtypeStruct((SUB, C), jnp.float32),
)(v)).trace(
    jax.ShapeDtypeStruct((SUB, C), jnp.float32),
).lower(lowering_platforms=('tpu',))
print("MXU_ROWCUM_LOWERED", len(low.as_text()))
"""


def test_mxu_scan_rowcum_lowers_for_tpu():
    r = _run_offline(MXU_SCRIPT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "MXU_ROWCUM_LOWERED" in r.stdout


SCRIPT2 = r"""
import numpy as np
import jax
import jax.numpy as jnp

import sys
sys.path.insert(0, %(repo)r)

# strict-tile SpMV at bench-like shapes
from libgrape_lite_tpu.ops.spmv import plan_tiles, spmv_strict

rng = np.random.default_rng(0)
vp = 1 << 18
src = np.sort(rng.integers(0, vp, 1 << 20)).astype(np.int32)
row_lo, rmax, num_tiles = plan_tiles(src, 2048, vp)
vals = jax.ShapeDtypeStruct((len(src),), jnp.float32)
srcs = jax.ShapeDtypeStruct((len(src),), jnp.int32)
low = jax.jit(
    lambda v, s: spmv_strict(v, s, row_lo, vp, 2048, rmax,
                             interpret=False)
).trace(vals, srcs).lower(lowering_platforms=('tpu',))
print("SPMV_STRICT_LOWERED", len(low.as_text()))

# LCC bitmap intersect kernel (both aligned and full-dim word counts)
from libgrape_lite_tpu.ops.pallas_kernels import intersect_count

for words in (128, 197):
    a = jax.ShapeDtypeStruct((4096, words), jnp.uint32)
    low = jax.jit(
        lambda a: intersect_count(a, a, block=512, interpret=False)
    ).trace(a).lower(lowering_platforms=('tpu',))
    print(f"INTERSECT_LOWERED_{words}", len(low.as_text()))
"""


def test_legacy_kernels_lower_for_tpu():
    r = _run_offline(SCRIPT2 % {"repo": REPO})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "SPMV_STRICT_LOWERED" in r.stdout
    assert "INTERSECT_LOWERED_128" in r.stdout
    assert "INTERSECT_LOWERED_197" in r.stdout


# the pull's gather at the cells' shapes: Graph500 scale 21 on one
# chip, and a four-chip shard, whose stream is whole 128s but not whole
# blocks and whose table is the mirror exchange's compact one
SCRIPT3 = r"""
import jax
import jax.numpy as jnp

import sys
sys.path.insert(0, %(repo)r)

from libgrape_lite_tpu.ops.pallas_kernels import vmem_gather

for name, v, n in (("S21", 2097152, 67108864),
                   ("X4", 1388544, 17192832)):
    for dt in (jnp.float32, jnp.int32):
        low = jax.jit(vmem_gather).trace(
            jax.ShapeDtypeStruct((v,), dt),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ).lower(lowering_platforms=('tpu',))
        assert "tpu_custom_call" in low.as_text()
        print(f"VMEM_GATHER_LOWERED_{name}_{jnp.dtype(dt).name}",
              len(low.as_text()))

# per shard inside a shard_map that checks varying axes: the kernel's
# output has to say over which it varies
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()[:4]), ("f",))
for check in (True, False):
    low = jax.jit(jax.shard_map(
        lambda f, i: vmem_gather(f[0], i[0])[None], mesh=mesh,
        in_specs=(P("f"), P("f")), out_specs=P("f"), check_vma=check,
    )).trace(
        jax.ShapeDtypeStruct((4, 1388544), jnp.float32),
        jax.ShapeDtypeStruct((4, 17192832), jnp.int32),
    ).lower(lowering_platforms=('tpu',))
    assert "tpu_custom_call" in low.as_text()
    print(f"VMEM_GATHER_LOWERED_SHARD_MAP_{check}")
"""


def test_vmem_gather_lowers_for_tpu():
    r = _run_offline(
        SCRIPT3 % {"repo": REPO},
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "VMEM_GATHER_LOWERED_SHARD_MAP_True" in r.stdout
    assert "VMEM_GATHER_LOWERED_SHARD_MAP_False" in r.stdout
    for name in ("S21", "X4"):
        for dt in ("float32", "int32"):
            assert f"VMEM_GATHER_LOWERED_{name}_{dt}" in r.stdout
