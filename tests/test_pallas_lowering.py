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


SCRIPT2 = r"""
import numpy as np
import jax
import jax.numpy as jnp

import sys
sys.path.insert(0, %(repo)r)

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


# the fold's row ends at the cells' shapes: a Graph500 scale-21 stream
# (64 slices), a four-chip shard's (ragged last slice, and inside a
# shard_map that checks varying axes), the serving cell's
SCRIPT4 = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import sys
sys.path.insert(0, %(repo)r)

from libgrape_lite_tpu.ops.pallas_kernels import vmem_row_gather

for name, v, e in (("S21", 2097152, 67108864), ("X4", 524288, 17192832),
                   ("S18", 262144, 8388608)):
    for dt in (jnp.float32, jnp.int32):
        low = jax.jit(vmem_row_gather).trace(
            jax.ShapeDtypeStruct((e,), dt),
            jax.ShapeDtypeStruct((v,), jnp.int32),
        ).lower(lowering_platforms=('tpu',))
        assert "tpu_custom_call" in low.as_text()
        print(f"VMEM_ROW_GATHER_LOWERED_{name}_{jnp.dtype(dt).name}")

mesh = Mesh(np.array(jax.devices()[:4]), ("f",))
low = jax.jit(jax.shard_map(
    lambda t, i: vmem_row_gather(t[0], i[0])[None], mesh=mesh,
    in_specs=(P("f"), P("f")), out_specs=P("f"),
)).trace(
    jax.ShapeDtypeStruct((4, 17192832), jnp.float32),
    jax.ShapeDtypeStruct((4, 524288), jnp.int32),
).lower(lowering_platforms=('tpu',))
assert "tpu_custom_call" in low.as_text()
print("VMEM_ROW_GATHER_LOWERED_SHARD_MAP")
"""


def test_vmem_row_gather_lowers_for_tpu():
    r = _run_offline(
        SCRIPT4 % {"repo": REPO},
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "VMEM_ROW_GATHER_LOWERED_SHARD_MAP" in r.stdout
    for name in ("S21", "X4", "S18"):
        for dt in ("float32", "int32"):
            assert f"VMEM_ROW_GATHER_LOWERED_{name}_{dt}" in r.stdout


# the scan's first level at the cells' five shapes (Graph500 scale 21,
# its four-chip shard with a ragged last block, the two CDLP cells, a
# serving lane, the road graph), and inside a shard_map that checks
# varying axes
SCRIPT5 = r"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import sys
sys.path.insert(0, %(repo)r)

from libgrape_lite_tpu.ops.pallas_kernels import tile_scan

for name, e in (("S21", 67108864), ("X4", 17192832), ("CDLP19", 16777216),
                ("DATAGEN", 18950272), ("S18", 8388608), ("ROAD", 2517504)):
    for kind, combine, dt in (("sum", jnp.add, jnp.float32),
                              ("min", jnp.minimum, jnp.int32),
                              ("min", jnp.minimum, jnp.float32),
                              ("max", jnp.maximum, jnp.int32)):
        low = jax.jit(lambda v, i: tile_scan(v, i, combine)).trace(
            jax.ShapeDtypeStruct((e // 128, 128), dt),
            jax.ShapeDtypeStruct((e // 128, 128), jnp.int32),
        ).lower(lowering_platforms=('tpu',))
        assert "tpu_custom_call" in low.as_text()
        print(f"TILE_SCAN_LOWERED_{name}_{kind}_{jnp.dtype(dt).name}")

mesh = Mesh(np.array(jax.devices()[:4]), ("f",))
low = jax.jit(jax.shard_map(
    lambda v, i: tile_scan(v[0], i[0], jnp.add)[None], mesh=mesh,
    in_specs=(P("f"), P("f")), out_specs=P("f"),
)).trace(
    jax.ShapeDtypeStruct((4, 134319, 128), jnp.float32),
    jax.ShapeDtypeStruct((4, 134319, 128), jnp.int32),
).lower(lowering_platforms=('tpu',))
assert "tpu_custom_call" in low.as_text()
print("TILE_SCAN_LOWERED_SHARD_MAP")
"""


def test_tile_scan_lowers_for_tpu():
    r = _run_offline(
        SCRIPT5 % {"repo": REPO},
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "TILE_SCAN_LOWERED_SHARD_MAP" in r.stdout
    for name in ("S21", "X4", "CDLP19", "DATAGEN", "S18", "ROAD"):
        for fold in ("sum_float32", "min_int32", "min_float32", "max_int32"):
            assert f"TILE_SCAN_LOWERED_{name}_{fold}" in r.stdout
