"""chip_smoke.py and the no-hiding repairs it rests on (PR 22), on the
CPU: the smoke refuses a CPU unless asked to rehearse, its rehearsal
runs every stage, its plain references are pinned to the p2p-31
goldens, and the helpers it trusts (`place_compile_cache`,
`use_pallas`) do what they say.  A pass on the chip is the chip's."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tests import verifiers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATASET = os.path.join(REPO, "dataset")


def _smoke(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )


def test_smoke_refuses_a_cpu():
    r = _smoke()
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and r.stdout == ""
    # the rehearsal flag does not unlock a real size either
    assert _smoke("--rehearse", "--scale", "16").returncode != 0


def test_smoke_rehearsal_runs_every_stage():
    r = _smoke("--rehearse", "--scale", "10")
    assert r.returncode == 0, r.stderr[-4000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "platform: cpu"
    s = json.loads(lines[-1])  # the summary; a rehearsal prints no verdict
    assert s["rehearsal"] and s["platform"] == "cpu" and s["ok"]
    assert s["claim"] is None and s["native"] is True and s["reduced"]
    assert set(s["stage_a"]) == {"sssp", "bfs", "pagerank", "wcc", "cdlp",
                                 "lcc", "serve", "serve_inflight4"}
    assert set(s["stage_b"]) == {"pagerank", "sssp", "bfs", "wcc", "serve"}
    assert all(v["ok"] for st in ("stage_a", "stage_b", "stage_c")
               for v in s[st].values())
    assert all(s["stage_b"][a]["compiles_warm"] == 0
               for a in ("pagerank", "sssp", "bfs", "wcc"))
    assert s["stage_b"]["serve"]["queries"] == 8
    assert set(s["stage_c"]) == {"intersect_count", "vmem_gather",
                                 "vmem_row_gather"}
    # off the TPU the intersect kernel's dispatcher takes the jnp path
    assert s["stage_c"]["intersect_count"]["pallas_calls"] == 0
    # and the pull's gather is XLA's, checked against itself
    assert s["stage_c"]["vmem_gather"]["pallas_calls"] == 0
    assert s["stage_c"]["vmem_gather"]["took"] == {"kernel": 0, "xla": 2}
    # as are the fold's row ends, under a fold checked against the scatter
    assert s["stage_c"]["vmem_row_gather"]["pallas_calls"] == 0
    assert s["stage_c"]["vmem_row_gather"]["took"] == {"kernel": 0, "xla": 2}
    assert not any(v["compiled"] for v in s["stage_c"].values())


def test_plain_references_match_the_goldens():
    """What Stage B trusts at size is itself pinned: the smoke's NumPy/
    SciPy references reproduce the LDBC validation outputs on p2p-31."""
    import chip_smoke as cs

    oids = np.loadtxt(os.path.join(DATASET, "p2p-31.v"), dtype=np.int64)
    oids = np.sort(oids.reshape(len(oids), -1)[:, 0])
    e = np.loadtxt(os.path.join(DATASET, "p2p-31.e"))
    src = np.searchsorted(oids, e[:, 0].astype(np.int64))
    dst = np.searchsorted(oids, e[:, 1].astype(np.int64))
    minw, mult = cs.symmetric_csr(len(oids), src, dst, e[:, 2])
    source = int(np.searchsorted(oids, cs.P2P_SOURCE))

    def golden(suffix):
        g = verifiers.load_golden(os.path.join(DATASET, f"p2p-31-{suffix}"))
        return np.array([float(g[int(o)]) for o in oids])

    pr = cs.ref_pagerank(mult, cs.PR_DELTA, cs.PR_ROUNDS)
    assert cs.mismatches("eps", pr, golden("PR"), 1e-6) == 0
    assert cs.mismatches("eps", cs.ref_sssp(minw, source),
                         golden("SSSP"), 1e-9) == 0
    bfs, want = cs.ref_bfs(minw, source), golden("BFS")
    assert cs.mismatches(
        "exact", bfs, np.where(want >= len(oids), -1, want)) == 0
    wcc = cs.ref_wcc(minw)
    assert cs.mismatches("partition", wcc, golden("WCC")) == 0
    # and the comparators do see a difference
    assert cs.mismatches("eps", pr * (1 + 2e-3), golden("PR"), 1e-3) == len(pr)
    assert cs.mismatches("exact", bfs + (np.arange(len(bfs)) == 3), bfs) == 1
    merged = np.where(wcc == 1, 0, wcc)
    assert cs.mismatches("partition", merged, golden("WCC")) == 1


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from libgrape_lite_tpu.utils.compile_cache import place_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert place_compile_cache() == "/somewhere/else" and calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = place_compile_cache(), place_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert calls[:2] == [
        ("jax_compilation_cache_dir", first),
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
    ]


def test_use_pallas_is_the_backend_and_swallows_nothing(monkeypatch):
    import jax

    from libgrape_lite_tpu.ops.pallas_kernels import use_pallas

    assert use_pallas() is False  # this lane is the CPU

    def boom():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError):
        use_pallas()
