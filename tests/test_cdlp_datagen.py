"""CDLP on the Datagen-like surrogate, against the benchmark's plain reference.

The graph of `benchmarks/configs/datagen-like.json` has what Kronecker lacks:
planted communities that label propagation collapses onto, so the distinct
labels fall under the dynamic branch's budget after a pass or two and the
`lax.cond` takes its packed arm, which on a Graph500 graph it never does
(since PR 37 both arms sort ranks in the live universe: `grape.cdlp.live` and
`grape.cdlp.rank` name what every pass of the branch pays for them).  `CDLP_STATS` says which way each pass
went; the state's `universe` leaf is where it reads that.

Under 2^16 padded ids the shapes pack against the initial universe (`static`)
and no predicate is computed, so the small cases force the dynamic branch
with a budget under the first passes' universe: both arms run in one query.
The unforced case takes the surrogate's construction at 2^16 ids and a tenth
of the published degree, the smallest shapes whose default is the `cond`.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.graphs import datagen_like
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import cdlp as cdlp_reference
from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.models.cdlp import CDLP_STATS
from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.worker.worker import Worker
from tests.test_cdlp_kronecker import ROUNDS, labels_by_id, lowered

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "datagen-like.json")) as f:
    CONFIG = json.load(f)

SMALL = 12  # 4,096 ids, two communities: packs statically unless forced
BUDGET = 64  # the forced dynamic branch's: under the first passes' universe
# 65,536 ids at mean degree 16: the shapes of the `cond`, a CPU's seconds
UNFORCED = 16, dict(CONFIG["generator"], mean_degree=16.0)


@pytest.fixture(scope="module")
def surrogate(tmp_path_factory):
    """(scale, generator block) -> (fragment through LoadGraph, the
    references' graph)."""
    made = {}

    def get(scale: int, gen: dict = CONFIG["generator"]):
        key = scale, json.dumps(gen, sort_keys=True)
        if key not in made:
            d = tmp_path_factory.mktemp(f"datagen{scale}")
            efile, vfile = str(d / "graph.e"), str(d / "graph.v")
            datagen_like.write_files(gen, scale, efile, vfile)
            spec = dict(CONFIG["load_graph_spec"])
            spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
            frag = LoadGraph(efile, vfile, CommSpec(fnum=1), LoadGraphSpec(**spec))
            n = 1 << scale
            minw, mult = symmetric_csr(n, *datagen_like.edges(gen, scale))
            made[key] = frag, types.SimpleNamespace(n=n, minw=minw, mult=mult)
        return made[key]

    return get


def query(frag, rounds: int = ROUNDS, **hooks):
    """(labels by id in the reference's form, CDLP_STATS after the query)."""
    app = APP_REGISTRY["cdlp"]()
    for hook, value in hooks.items():
        setattr(app, hook, value)
    return labels_by_id(frag, app, rounds), CDLP_STATS.snapshot()


def distinct_before_each_pass(graph, rounds: int) -> list:
    """Distinct labels in the state each of `rounds` passes starts from, by
    the reference's algorithm."""
    return [graph.n] + [
        len(np.unique(cdlp_reference.reference(graph, {"max_round": r})))
        for r in range(1, rounds)]


BRANCHES = {
    "static": {},
    "dynamic": {"_force_dynamic": True, "_u_budget_override": BUDGET},
    "wide": {"_force_wide": True},
}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_surrogate_is_exact_on_every_vertex(surrogate, branch):
    frag, graph = surrogate(SMALL)
    assert not graph.mult.diagonal().any() and graph.mult.data.max() == 1, (
        "the surrogate is a simple graph")
    got, stats = query(frag, **BRANCHES[branch])
    want = cdlp_reference.reference(graph, {"max_round": ROUNDS})
    assert (got != want).sum() == 0
    assert len(np.unique(got)) <= 4  # collapsed onto the planted communities
    assert stats["branch"] == branch and stats["passes"] == ROUNDS
    if branch != "dynamic":
        # no predicate under 2^16 padded ids, none on the two-key path
        assert stats["universe"] == [-1] * ROUNDS
        assert stats["packed_passes"] == 0 and stats["u_budget"] == 0
        return
    seen = distinct_before_each_pass(graph, ROUNDS)
    assert stats["universe"] == seen and stats["u_budget"] == BUDGET
    # both arms ran: the first passes' universe is over the budget, the last under
    assert seen[0] > BUDGET >= seen[-1]
    assert stats["packed_passes"] == sum(s <= BUDGET for s in seen)
    assert 1 <= stats["packed_passes"] < ROUNDS


def test_the_three_sorts_agree(surrogate):
    frag, _ = surrogate(SMALL)
    answers = [query(frag, **hooks)[0] for hooks in BRANCHES.values()]
    assert all((a == answers[0]).all() for a in answers[1:])


def test_the_default_app_takes_the_packed_arm_unforced(surrogate):
    scale, gen = UNFORCED
    frag, graph = surrogate(scale, gen)
    src_bits = int(np.ceil(np.log2(frag.vp + 2)))
    assert 2 * src_bits > 32 and 32 - src_bits >= 10  # the lax.cond's shapes
    rounds = 5
    got, stats = query(frag, rounds)
    want = cdlp_reference.reference(graph, {"max_round": rounds})
    assert (got != want).sum() == 0
    assert stats["branch"] == "dynamic" and stats["u_budget"] == 1 << (32 - src_bits)
    seen = stats["universe"]
    assert seen == distinct_before_each_pass(graph, rounds)
    assert seen[0] == graph.n > stats["u_budget"]  # the first pass sorts two keys
    assert all(a >= b for a, b in zip(seen[1:], seen[2:]))  # then it only falls
    assert stats["packed_passes"] == sum(s <= stats["u_budget"] for s in seen) >= 1
    # the two-key sort alone gives the same labels
    assert (query(frag, rounds, _force_wide=True)[0] == got).all()


def test_stats_are_federated_and_keep_the_last_extracted_query(surrogate):
    frag, _ = surrogate(SMALL)
    query(frag, 3, **BRANCHES["dynamic"])
    assert federation.EXPECTED["cdlp"] == "libgrape_lite_tpu.models.cdlp"
    fed = federation.snapshot("cdlp")
    assert fed == CDLP_STATS.snapshot() and fed["passes"] == 3
    assert len(fed["universe"]) == 3 and fed["branch"] == "dynamic"
    json.dumps(fed)
    query(frag, 2)  # the next extracted answer replaces the record
    assert CDLP_STATS["passes"] == 2 and CDLP_STATS["branch"] == "static"


@pytest.mark.parametrize("name", ["cdlp", "cdlp_opt"])
def test_opt_and_plain_write_the_same_record_but_the_first_pass(surrogate, name):
    frag, _ = surrogate(SMALL)
    app = APP_REGISTRY[name]()
    app._force_dynamic, app._u_budget_override = True, BUDGET
    w = Worker(app, frag)
    w.query(max_round=4)
    w.result_values()
    seen = CDLP_STATS["universe"]
    assert len(seen) == 4 and all(s > 0 for s in seen[1:])
    # cdlp_opt's first pass is a neighbour minimum: it sorts nothing
    assert (seen[0] == -1) == (name == "cdlp_opt")


# ---- the scopes of the dynamic branch ---------------------------------------


@pytest.mark.parametrize("force", [None, "_force_wide"])
def test_packed_arm_is_named_in_the_lowered_runner(surrogate, force):
    scale, gen = UNFORCED
    frag, _ = surrogate(scale, gen)
    app = APP_REGISTRY["cdlp"]()
    if force:
        setattr(app, force, True)
    text = lowered(app, frag, True)
    for scope in ("grape.cdlp.live", "grape.cdlp.rank", "grape.cdlp.universe"):
        assert (scope in text) == (force is None), f"{force}: {scope}"
    assert "grape.cdlp.sort" in text and "grape.cdlp.count" in text
    # metadata only: the program the compiler sees carries no name
    assert "grape." not in lowered(app, frag, False)


def test_static_pack_names_no_packed_arm(surrogate):
    frag, _ = surrogate(SMALL)
    text = lowered(APP_REGISTRY["cdlp"](), frag, True)
    assert "grape.cdlp.sort" in text
    for scope in ("grape.cdlp.live", "grape.cdlp.rank", "grape.cdlp.universe"):
        assert scope not in text
