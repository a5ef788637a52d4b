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
