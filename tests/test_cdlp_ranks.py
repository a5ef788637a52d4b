"""CDLP's dynamic branch on ranks (`models/cdlp.CDLP._live_labels`).

Since PR 37 a pass of the dynamic branch sorts the gathered state once,
V-wide, and everything that searched reads that sort: the runs counted are
the `lax.cond`'s predicate, the runs before a value its rank, the pull's
gather fetches ranks, both arms sort, count and fold them, and the fold's
answer is decoded a row at a time.  Pinned here: whole queries through
`LoadGraph -> Worker.query` against `benchmarks/references/cdlp.py` on the
two families' graphs, on one fragment, two and four, with
the budget under, inside and over the universe's fall (the two-key arm
alone, both arms in one query, the packed arm alone), `cdlp` and `cdlp_opt`;
the `universe` leaf against the reference's distinct labels pass by pass;
and that the lowered runner holds no search and one E-wide gather a pass.
"""

import re
import types
from collections import Counter

import numpy as np
import pytest

from benchmarks.graphs import datagen_like, kronecker
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import cdlp as cdlp_reference
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.models.cdlp import CDLP_STATS
from tests import test_cdlp_datagen as datagen
from tests import test_cdlp_kronecker as kron
from tests.test_cdlp_datagen import surrogate  # noqa: F401  (a fixture)
from tests.test_cdlp_count import RUNS, labels_by_id, load_as_the_cell_does

ROUNDS = 5
# name -> (generator module, its block of the cell's configuration, scale)
FAMILIES = {
    # a simple graph whose universe falls onto its planted communities
    "surrogate": (datagen_like, datagen.CONFIG["generator"], 10),
    # multi-edges, self-loops, isolated vertices; the universe hardly falls
    "kronecker": (kronecker, kron.CONFIG["generator"], 8),
}
# the forced dynamic branch's budget: no pass fits, some do, every one does
BUDGETS = {"two_key_arm": 0, "both_arms": None, "packed_arm": 1 << 20}


@pytest.fixture(scope="module")
def family(tmp_path_factory):
    """(family, fnum) -> (fragment through LoadGraph as the cells load it,
    the reference's graph, the distinct labels before each pass)."""
    made, seen = {}, {}

    def get(name: str, fnum: int):
        if (name, fnum) not in made:
            gen_module, gen, scale = FAMILIES[name]
            d = tmp_path_factory.mktemp(f"{name}{fnum}")
            efile, vfile = str(d / "graph.e"), str(d / "graph.v")
            gen_module.write_files(gen, scale, efile, vfile)
            n = 1 << scale
            _, mult = symmetric_csr(n, *gen_module.edges(gen, scale))
            graph = types.SimpleNamespace(n=n, mult=mult)
            if name not in seen:
                seen[name] = datagen.distinct_before_each_pass(graph, ROUNDS)
            made[name, fnum] = (
                load_as_the_cell_does(efile, vfile, fnum), graph, seen[name])
        return made[name, fnum]

    return get


def _app(name: str, budget):
    app = APP_REGISTRY[name]()
    app._force_dynamic, app._u_budget_override = True, budget
    return app


@pytest.mark.parametrize("fnum", RUNS, ids=["1", "4", "2"])
@pytest.mark.parametrize("arms", sorted(BUDGETS))
@pytest.mark.parametrize("graph,name", [
    ("surrogate", "cdlp"), ("surrogate", "cdlp_opt"), ("kronecker", "cdlp")])
def test_ranks_are_exact_in_both_arms(graph, name, arms, fnum, family):
    frag, ref_graph, seen = family(graph, fnum)
    budget = BUDGETS[arms]
    if budget is None:
        # between the second pass's universe and the last's: both arms run
        budget = (seen[1] + seen[-1]) // 2
    app = _app(name, budget)
    got = labels_by_id(frag, app, ROUNDS)
    want = cdlp_reference.reference(ref_graph, {"max_round": ROUNDS})
    assert (got != want).sum() == 0
    stats = CDLP_STATS.snapshot()
    assert stats["branch"] == "dynamic" and stats["u_budget"] == budget
    # the padded vertices of a fragment hold the pad label: one more value
    pad = int(frag.fnum * frag.vp > ref_graph.n)
    counted = [s + pad for s in seen]
    if name == "cdlp_opt":
        counted[0] = -1  # its first pass is a neighbour minimum: no predicate
    assert stats["universe"] == counted
    packed = sum(0 <= c <= budget for c in counted)
    assert stats["packed_passes"] == packed
    predicates = sum(c >= 0 for c in counted)
    assert {"two_key_arm": packed == 0, "both_arms": 0 < packed < predicates,
            "packed_arm": packed == predicates}[arms]


# ---- the program ------------------------------------------------------------

def _results_by_width(text: str, op: str) -> Counter:
    """{leading dimension of the first result: how many `op`s of the lowered
    text give it}.  The type follows the op, behind its region where it has
    one (a sort's comparator, which holds scalars only)."""
    result = re.compile(r"->\s*\(?tensor<(\d+)x")
    return Counter(int(result.search(text, found.end()).group(1))
                   for found in re.finditer(rf'stablehlo\.{op}"?\(', text))


@pytest.fixture(scope="module")
def runners(family, surrogate):
    """name -> (fragment, app factory) of the two default-shaped runners:
    the surrogate at the shapes whose default is the `cond`, and
    kronecker-1 with the dynamic branch forced."""
    scale, gen = datagen.UNFORCED
    return {
        "surrogate": (surrogate(scale, gen)[0], lambda: APP_REGISTRY["cdlp"]()),
        "kronecker-1": (family("kronecker", 1)[0], lambda: _app("cdlp", None)),
    }


@pytest.mark.parametrize("which", ["surrogate", "kronecker-1"])
def test_dynamic_branch_lowers_without_a_search(which, runners):
    """No `searchsorted` (a `while` of dependent gathers) and no scatter is
    left in the runner, and its only E-wide gather is the pull's, one a
    pass: PEval's and the loop's.  The two-key path's runner is the
    yardstick: it never held a search."""
    frag, make = runners[which]
    app = make()
    assert app._sort_plan(frag.fnum * frag.vp, frag.vp)[0] == "dynamic"
    text = kron.lowered(app, frag, True)
    wide = make()
    wide._force_wide = True
    text_wide = kron.lowered(wide, frag, True)
    # the superstep loop is the program's only `while`
    assert text.count("stablehlo.while") == text_wide.count("stablehlo.while") == 1
    assert "stablehlo.scatter" not in text
    ep = frag.host_oe[0].edge_src.shape[0]
    gathers = _results_by_width(text, "gather")
    assert ep > frag.fnum * frag.vp and gathers[ep] == 2
    assert gathers[ep] == _results_by_width(text_wide, "gather")[ep]
    assert all(width <= frag.fnum * frag.vp for width in gathers if width != ep)
    # what the branch adds to a pass is V-wide: the state's sort, the ranks'
    # way back, the table, against the E-wide sort of either arm
    sorts = _results_by_width(text, "sort")
    assert set(sorts) == {frag.fnum * frag.vp, ep}
    assert set(_results_by_width(text_wide, "sort")) == {ep}
    # and none of them is stable: every operand is a key or ties cannot
    # show, and a stable sort carries an iota as wide as its operands
    assert "is_stable = true" not in text + text_wide
    assert "is_stable = false" in text and "is_stable = false" in text_wide
    for scope in ("grape.cdlp.universe", "grape.cdlp.live", "grape.cdlp.rank",
                  "grape.cdlp.sort", "grape.cdlp.count", "grape.pull.gather",
                  "grape.pull.fold"):
        assert scope in text, f"{which}: no {scope} in the lowered runner"
        if scope in ("grape.cdlp.universe", "grape.cdlp.live", "grape.cdlp.rank"):
            assert scope not in text_wide
