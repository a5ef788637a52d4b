"""The lanes' pull compiled by the chip's own compiler, no chip attached.

`ops/segment.py`'s `vmap` rules put the Pallas gather kernel in a loop
over the query lanes and the tile scan in a second one.  Interpret mode
says nothing about what Mosaic and XLA:TPU accept, so this file compiles
the four-lane pull for a described v5e (tests here never run it: no
time, no bytes), at the serving cell's table size, and reads the
compiled program: the kernel is there, no scatter is, and the parent's
program (the choice unarmed) is the fused scatter it was.  The topology
is described inside a fixture, in this one file, so that only the worker
that is handed the file loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from libgrape_lite_tpu.ops import segment
from libgrape_lite_tpu.ops.segment import pull_gather, segment_reduce
from tests.conftest import GATHER_BUDGET

LANES, V, EP = 4, 262144, 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(one_chip, dtype) -> str:
    def shaped(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    big = (jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
           else jnp.inf)

    def lanes(full, nbr, mask, ids, ptr):
        def one(f):
            cand = pull_gather(f, nbr, mask, jnp.asarray(big, f.dtype),
                               add=1)
            return segment_reduce(cand, ids, V, "min", row_ptr=ptr)
        return jax.vmap(one)(full)

    # x32, as the chip runs (this lane's x64 is for the CPU's goldens)
    with jax.enable_x64(False):
        return jax.jit(lanes).lower(
            shaped((LANES, V), dtype), shaped((EP,), "int32"),
            shaped((EP,), "bool"), shaped((EP,), "int32"),
            shaped((V + 1,), "int32")).compile().as_text()


@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_armed_lanes_compile_to_kernel_and_scan(dtype, one_chip,
                                                monkeypatch):
    # as the TPU backend steers the choice (the process itself is a CPU's)
    monkeypatch.setattr(segment, "use_pallas", lambda: True)
    monkeypatch.setattr(segment, "gather_table_budget",
                        lambda: GATHER_BUDGET)
    text = _compiled(one_chip, jnp.dtype(dtype))
    assert "tpu_custom_call" in text and "vmem_gather" in text
    assert " scatter(" not in text
    assert "while(" in text  # the lanes are a loop, not four copies
    assert text.count("tpu_custom_call") == 1


def test_unarmed_lanes_compile_to_the_fused_scatter(one_chip):
    text = _compiled(one_chip, jnp.dtype("float32"))
    assert "tpu_custom_call" not in text
    assert " scatter(" in text
