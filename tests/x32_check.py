"""Standalone x64-OFF parity check (run as a subprocess by
test_x32_lane.py, outside the conftest's jax_enable_x64=True session).

On real TPU configs x64 is off and float64 app state silently becomes
float32; this lane verifies the LDBC eps tolerances still hold in
float32 — the deployment-mode check the x64 CPU matrix can't provide
(reference runs doubles everywhere, `misc/app_tests.sh`).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the whole point of this lane: ensure x64 is OFF even if the ambient
# shell exported JAX_ENABLE_X64
jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.verifiers import (  # noqa: E402
    collect_worker_result as run_worker,
    eps_verify,
    exact_verify,
    load_golden,
)

DATASET = os.path.join(os.path.dirname(__file__), "..", "dataset")


def dataset_path(name):
    return os.path.join(DATASET, name)


def main():
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.models import LCC, SSSP, BFS, PageRank
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    for fnum in (1, 4):
        spec = LoadGraphSpec(
            directed=False, weighted=True, edata_dtype=np.float32
        )
        frag = LoadGraph(
            dataset_path("p2p-31.e"), dataset_path("p2p-31.v"),
            CommSpec(fnum=fnum), spec,
        )

        res = run_worker(SSSP(), frag, source=6)
        # float32 path sums: golden is float64; p2p-31 depths are ~20
        # hops of O(100) weights, so 1e-3 relative absorbs the rounding
        eps_verify(res, load_golden(dataset_path("p2p-31-SSSP")), eps=1e-3)

        res = run_worker(BFS(), frag, source=6)
        exact_verify(res, load_golden(dataset_path("p2p-31-BFS")))

        res = run_worker(PageRank(), frag, delta=0.85, max_round=10)
        eps_verify(res, load_golden(dataset_path("p2p-31-PR")), eps=1e-3)

        res = run_worker(LCC(), frag)
        eps_verify(res, load_golden(dataset_path("p2p-31-LCC")), eps=1e-4)

    print("X32-LANE-OK")


if __name__ == "__main__":
    main()
