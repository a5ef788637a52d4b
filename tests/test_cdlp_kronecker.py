"""CDLP on the benchmark's own graph, against the benchmark's plain reference.

The Graph500 Kronecker graph of `benchmarks/configs/g500-cdlp.json` has what
p2p-31 lacks: multi-edges (a doubled edge counts twice in a vertex's label
counts), self-loops, isolated vertices and ids permuted at random.  At the
configuration's `rehearse_scale` (10) the shapes take the packed single-key
sort; the dynamic-universe branch and the two-key `lax.sort` are forced
there.  Scale 16 is the smallest whose shapes take the cell's branch
unforced (17 + 17 bits > 32: the `lax.cond` of the dynamic universe, whose
first pass, all labels distinct, takes the two-key sort).

The lowered-text cases pin that the `grape.cdlp.*` scopes are there and that
they, and the move of CDLP's gather into `ops/segment.pull_gather`, left
the pull apps' programs alone.
"""

import contextlib
import json
import os
import time
import types

import jax
import numpy as np
import pytest

from benchmarks.graphs import kronecker
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import cdlp as cdlp_reference
from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.worker.worker import Worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "g500-cdlp.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "traffic", "cdlp-10r.json")) as f:
    ROUNDS = int(json.load(f)["jobs"][0]["params"]["max_round"])  # 10

# scale 16, 3 passes, graph files, load and reference included: 6 s alone on
# this sandbox's CPU, so the budget leaves room for five other workers
SCALE16_BUDGET_S = 120


@pytest.fixture(scope="module")
def kron(tmp_path_factory):
    """scale -> (fragment through LoadGraph, the references' graph)."""
    made = {}

    def get(scale: int):
        if scale not in made:
            d = tmp_path_factory.mktemp(f"kron{scale}")
            efile, vfile = str(d / "graph.e"), str(d / "graph.v")
            gen = CONFIG["generator"]
            kronecker.write_files(gen, scale, efile, vfile)
            spec = dict(CONFIG["load_graph_spec"])
            spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
            frag = LoadGraph(efile, vfile, CommSpec(fnum=1), LoadGraphSpec(**spec))
            n = 1 << scale
            minw, mult = symmetric_csr(n, *kronecker.edges(gen, scale))
            made[scale] = frag, types.SimpleNamespace(n=n, minw=minw, mult=mult)
        return made[scale]

    return get


def labels_by_id(frag, app, rounds: int) -> np.ndarray:
    w = Worker(app, frag)
    w.query(max_round=rounds)
    values = np.asarray(w.result_values())
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    out[frag.inner_oids(0)] = values[0, :frag.inner_vertices_num(0)]
    return cdlp_reference.to_reference_form(out)


@pytest.mark.parametrize("branch", ["packed", "dynamic", "wide"])
def test_rehearse_scale_is_exact_on_every_vertex(kron, branch):
    frag, graph = kron(int(CONFIG["rehearse_scale"]))
    assert graph.mult.diagonal().any() and graph.mult.data.max() >= 2, (
        "the graph should hold self-loops and multi-edges")
    app = APP_REGISTRY["cdlp"]()
    app._force_dynamic = branch == "dynamic"
    app._force_wide = branch == "wide"
    got = labels_by_id(frag, app, ROUNDS)
    want = cdlp_reference.reference(graph, {"max_round": ROUNDS})
    assert (got != want).sum() == 0


def test_scale_16_takes_the_cells_branch_unforced(kron):
    t0 = time.perf_counter()
    frag, graph = kron(16)
    rank_bits = int(np.ceil(np.log2(frag.vp + 2)))
    assert 2 * rank_bits > 32 and 32 - rank_bits >= 10  # the lax.cond branch
    got = labels_by_id(frag, APP_REGISTRY["cdlp"](), 3)
    want = cdlp_reference.reference(graph, {"max_round": 3})
    assert (got != want).sum() == 0
    assert len(np.unique(got)) < graph.n  # labels did propagate
    assert time.perf_counter() - t0 < SCALE16_BUDGET_S


# ---- the scopes, and what they left alone --------------------------------


def lowered(app, frag, debug_info: bool, **params) -> str:
    w = Worker(app, frag)
    state = w._place_state(app.init_state(frag, **params))
    eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    return w._runner_for(0, state).lower(frag.dev, carry, eph_part).as_text(
        debug_info=debug_info)


@pytest.mark.parametrize("force", [None, "_force_dynamic", "_force_wide"])
def test_cdlp_names_its_round_in_every_branch(graph_cache, force):
    app = APP_REGISTRY["cdlp"]()
    if force:
        setattr(app, force, True)
    text = lowered(app, graph_cache(1), True)
    for scope in ("grape.pull.gather", "grape.pull.fold", "grape.cdlp.sort",
                  "grape.cdlp.count", "grape.app.update"):
        assert scope in text, f"{force}: no {scope} in CDLP's lowered runner"
    # the distinct-label predicate belongs to the dynamic branch alone
    if force:
        assert ("grape.cdlp.universe" in text) == (force == "_force_dynamic")


@pytest.mark.parametrize("app", ["pagerank", "bfs", "cdlp"])
def test_scopes_leave_the_lowered_program_alone(app, graph_cache, monkeypatch):
    """The program the compiler sees is the same with the scopes in place
    and with `jax.named_scope` a null context: for the pull apps, whose
    cached executables the benchmark's existing cells fetch, and for CDLP."""
    frag = graph_cache(1)
    params = {"bfs": {"source": 6}}.get(app, {})
    scoped = lowered(APP_REGISTRY[app](), frag, False, **params)
    assert "grape." not in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert "grape." not in lowered(APP_REGISTRY[app](), frag, True, **params)
    assert lowered(APP_REGISTRY[app](), frag, False, **params) == scoped
