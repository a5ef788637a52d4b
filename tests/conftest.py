"""Test config: emulate an 8-chip mesh on CPU.

The reference tests every app under `mpirun -n {1,2,4,6,8}`
(`misc/app_tests.sh:231-238`); here the analogue is a virtual 8-device
CPU platform (`xla_force_host_platform_device_count`) and fragment
counts {1,2,4,8} over sub-meshes.  x64 is enabled so float results are
bit-comparable with the reference's doubles.
"""

import os

# force CPU regardless of ambient JAX_PLATFORMS (the test matrix needs 8
# virtual devices; real-TPU runs use bench.py / the CLI instead).  jax may
# already be imported by a pytest plugin, so go through jax.config, which
# takes effect until the backend is actually initialised; XLA_FLAGS is
# read at CPU client creation, so setting it here still works.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# the entry points place a persistent compile cache in the checkout
# (utils/compile_cache.py) and tests call them in-process and in
# children: this lane compiles everything itself, as it always has, so
# its pinned compile counts never depend on an earlier run's disk
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)

assert len(jax.devices()) == 8, (
    "tests need the 8-device virtual CPU mesh; jax backend was initialised "
    "before conftest could configure it"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

DATASET = os.path.join(os.path.dirname(__file__), "..", "dataset")


def dataset_path(name: str) -> str:
    return os.path.join(DATASET, name)


@pytest.fixture(scope="session")
def graph_cache():
    """Session cache of loaded fragments keyed by (fnum, directed)."""
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    cache = {}

    def get(fnum: int, directed: bool = False):
        key = (fnum, directed)
        if key not in cache:
            spec = LoadGraphSpec(
                directed=directed, weighted=True, edata_dtype=np.float64
            )
            cs = CommSpec(fnum=fnum)
            cache[key] = LoadGraph(
                dataset_path("p2p-31.e"), dataset_path("p2p-31.v"), cs, spec
            )
        return cache[key]

    return get


# half of a v5e's VMEM, as `gather_table_budget` reads it on the chip
GATHER_BUDGET = 64 << 20


@pytest.fixture
def pull_kernel(monkeypatch):
    """Arms `ops/segment.pull_gather` as it chooses on the TPU backend.

    `pull_kernel("interpreted")` puts `pallas_kernels.vmem_gather` in
    interpret mode behind the choice (XLA:CPU compiles its unrolled
    body for 30-75 s wherever it stands in a runner, whatever the
    graph's size); `pull_kernel("stand_in")` puts `full[nbr]` there
    (the choice and what the kernel is handed are under test, not the
    kernel's bits: tests/test_pull_gather.py pins those).  Returns the
    list the kernel's calls are noted in, as (table dtype, table
    shape, stream shape); either way a call refuses what the real
    kernel cannot take.  The fold's row ends follow the same choice
    (`segment._row_end_gather`): `rows="interpreted"` puts
    `pallas_kernels.vmem_row_gather` in interpret mode there, at
    small slices (10 s of XLA:CPU compile an instance); the default is
    its plain stand-in, so that the cases that were here before it pay
    for one interpreted kernel, as they did.  So does the scan's first
    level (`segment._first_level`): `scan="interpreted"` puts
    `pallas_kernels.tile_scan` in interpret mode there, at blocks of
    16 tiles; the default is XLA's seven steps.  The stream size from
    which the kernel is chosen (`tile_scan_floor`) is brought down to
    nothing, so that a test's few tiles take it."""
    from libgrape_lite_tpu.ops import pallas_kernels, segment

    def arm(kind: str, rows: str = "stand_in",
            scan: str = "stand_in") -> list:
        calls = []

        def scan_kernel(values, ids, combine):
            assert values.ndim == 2 and values.dtype.itemsize == 4, values
            assert ids.shape == values.shape and ids.dtype == np.int32, ids
            assert values.shape[1] == segment.SCAN_TILE, values
            if scan == "interpreted":
                return pallas_kernels.tile_scan(
                    values, ids, combine, interpret=True, block_rows=16,
                    chunk=8)
            assert scan == "stand_in", scan
            # the fill a step shifts in never meets a place's own id
            return segment._tile_steps(values, ids, combine, 0)

        def row_kernel(table, idx):
            assert table.ndim == 1 and table.dtype.itemsize == 4, table
            assert idx.ndim == 1 and idx.dtype == np.int32, idx
            if rows == "interpreted":
                # slices of 2,048 places and blocks of 1,024 ends, so
                # that a test's CSR spans several of each
                return pallas_kernels.vmem_row_gather(
                    table, idx, interpret=True, slice_rows=16, end_rows=8)
            assert rows == "stand_in", rows
            return table.at[idx].get(mode="promise_in_bounds",
                                     indices_are_sorted=True)

        def kernel(full, nbr):
            assert full.ndim == 1 and full.dtype.itemsize == 4, full
            assert nbr.ndim == 1 and nbr.dtype == np.int32, nbr
            calls.append((str(full.dtype), full.shape, nbr.shape))
            if kind == "interpreted":
                return pallas_kernels.vmem_gather(full, nbr, interpret=True)
            assert kind == "stand_in", kind
            return full[nbr]

        monkeypatch.setattr(segment, "use_pallas", lambda: True)
        monkeypatch.setattr(segment, "gather_table_budget",
                            lambda: GATHER_BUDGET)
        monkeypatch.setattr(segment, "vmem_gather", kernel)
        monkeypatch.setattr(segment, "vmem_row_gather", row_kernel)
        monkeypatch.setattr(segment, "tile_scan", scan_kernel)
        monkeypatch.setattr(segment, "tile_scan_floor", lambda: 0)
        return calls

    return arm


def gather_took(fn) -> dict:
    """What `fn()` moved GATHER_STATS by."""
    from libgrape_lite_tpu.ops.segment import GATHER_STATS

    before = GATHER_STATS.snapshot()
    fn()
    return {k: v - before[k] for k, v in GATHER_STATS.snapshot().items()}


def rand_frag(fnum, n=900, e=7000, seed=11, weighted=True, directed=False):
    """A random multigraph with f32 weights (so SSSP's and PageRank's
    state is 32-bit under this lane's x64), cut over `fnum` fragments."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = (
        rng.uniform(0.5, 4.0, e).astype(np.float32)
        if weighted
        else np.ones(e, dtype=np.float32)
    )
    oids = np.arange(n, dtype=np.int64)
    comm = CommSpec(fnum=fnum)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        comm, vm, src, dst, w, directed=directed,
        load_strategy=LoadStrategy.kBothOutIn,
    )
