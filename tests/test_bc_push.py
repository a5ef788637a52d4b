"""BC's thin levels as pushes: `ops/segment.frontier_sum` gives the sums
the pull gives, on every level of both sweeps, and `models/bc.py` takes it
where a level fits the budgets and nowhere else.

The graphs here lie under the dense floor, so the app builds no push arm for
them: the budgets and the floor are brought down on the module (as
tests/test_sssp_frontier.py does), never through a parameter of the app.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import libgrape_lite_tpu.models.bc as bc_module
from benchmarks.compare import mismatches
from benchmarks.references import bc as bc_reference
from benchmarks.run import by_vertex as by_id
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.models.bc import BC_STATS
from libgrape_lite_tpu.ops import segment
from libgrape_lite_tpu.worker.worker import Worker
from tests.test_bc_pull import EPS, SENT, drawn, narrow_bc
from tests.test_worker import build_fragment

# only `gather_state` is asked of a level's context, and one fragment's is
# the table itself
ONE_FRAGMENT = types.SimpleNamespace(gather_state=lambda table: table)
WIDE = (1 << 12, 1 << 14)  # every level of the graphs drawn here fits


def directed_edges():
    """A directed multigraph: 300 ids, 1,200 drawn pairs (some twice over),
    no edge back unless drawn."""
    rng = np.random.default_rng(11)
    src, dst = rng.integers(0, 300, 1200), rng.integers(0, 300, 1200)
    return 300, np.r_[src, src[:40]], np.r_[dst, dst[:40]]


@pytest.fixture(scope="module")
def swept():
    """{kind: (fragment, the state the app's pulls leave)}: the levels and
    the tables of both sweeps come from the program itself, which builds no
    push arm at these sizes."""
    out = {}
    for kind in ("simple", "parallel", "directed"):
        if kind == "directed":
            n, src, dst = directed_edges()
            frag, root = build_fragment(src, dst, None, n, 1, directed=True), int(src[0])
        else:
            n, src, dst, _, roots = drawn(kind)
            frag, root = build_fragment(src, dst, None, n, 1), roots["edge"]
        w = Worker(APP_REGISTRY["bc"](), frag)
        state = w.query(source=root)
        assert w.app.push_budget is None
        out[kind] = frag, {k: np.asarray(v)[0] for k, v in state.items()}
    return out


def tables(state):
    """(depth d, sweep, the level's mask, its table) for every sum of both
    sweeps, as `BC.peval` builds them."""
    depth, pn, delta = state["depth"], state["pn"], state["delta"]
    deep = int(depth[depth != SENT].max())
    for d in range(deep + 1):
        at = depth == d
        yield d, "forward", at, np.where(at, pn, 0)
    # the tables of the backward sweep from its finished dependencies: what
    # level d's sum read when it ran (deeper levels are final by then)
    for d in range(deep, 0, -1):
        at = depth == d
        yield d, "backward", at, np.where(at, (1 + delta) / np.where(at, pn, 1), 0)


def counting(monkeypatch):
    """`frontier_sum` as the app sees it, noting every execution of a push
    arm on the device (a `cond`'s arm runs only where it is taken)."""
    hits = []
    plain = segment.frontier_sum

    def noted(*args):
        jax.debug.callback(lambda: hits.append(1))
        return plain(*args)

    monkeypatch.setattr(bc_module, "frontier_sum", noted)
    return hits


@pytest.mark.parametrize("dtype", [np.float64, np.float32], ids=["f64", "f32"])
@pytest.mark.parametrize("kind", ["simple", "parallel", "directed"])
def test_a_push_sums_what_the_pull_sums(kind, dtype, swept, monkeypatch):
    """Every level of both sweeps: the push from the level's rows along `oe`
    against the pull along `ie`.  Path counts are integers, equal bit for
    bit in any order; the backward tables differ by the order of a float
    sum.  A multigraph's repeated entry counts twice in both, and the
    directed fragment's in-edge sums are its sources' out-edge pushes."""
    frag, state = swept[kind]
    local = frag.dev.local()
    hits = counting(monkeypatch)
    pull = jax.jit(lambda t: bc_module._level_pull(ONE_FRAGMENT, local, t))
    push = jax.jit(lambda t, at: bc_module._level_sum(
        ONE_FRAGMENT, local, t, at, WIDE))
    seen = 0
    for d, sweep, at, table in tables(state):
        table = jnp.asarray(table.astype(dtype))
        want, got = np.asarray(pull(table)), np.asarray(push(table, jnp.asarray(at)))
        assert got.dtype == want.dtype == dtype
        if sweep == "forward":
            assert (got == want).all(), (d, sweep)
        else:
            np.testing.assert_allclose(
                got, want, rtol=1e-12 if dtype == np.float64 else 1e-6, atol=0)
        assert ((got == 0) == (want == 0)).all()
        seen += 1
    jax.effects_barrier()
    assert seen >= 7 and len(hits) == seen  # each took the push arm
    if kind == "parallel":  # an entry drawn twice is in a span twice
        csr = frag.host_oe[0]
        rows = np.repeat(np.arange(csr.num_rows), csr.degree)
        pairs = rows * frag.vp + csr.edge_nbr[:csr.num_edges]
        assert len(np.unique(pairs)) < len(pairs)


@pytest.mark.parametrize("kind", ["simple", "parallel", "directed"])
def test_no_masked_entry_lies_in_a_rows_span(kind, swept):
    """Why the push reads no `edge_mask`: the mask is false on the padding
    behind the last row's entries and nowhere else (graph/csr.py), so the
    spans `frontier_spans` reads off `indptr` hold real entries alone."""
    frag, _ = swept[kind]
    for csr in (frag.host_oe[0], frag.host_ie[0]):
        csr.validate()
        assert csr.indptr[-1] == csr.num_edges == csr.edge_mask.sum()
        assert csr.edge_mask[:csr.num_edges].all()
        assert not csr.edge_mask[csr.num_edges:].any()
        assert len(csr.edge_mask) > csr.num_edges  # there is padding to mask


@pytest.mark.parametrize("budget,why", [((4, 1 << 14), "rows"), ((1 << 12, 64), "entries")])
def test_a_level_over_a_budget_is_a_pull(budget, why, swept, monkeypatch):
    """A level whose rows outgrow B or whose entries outgrow C takes the
    pull arm, bit for bit the pull's floats, and the levels that fit still
    push: the choice is a level's, by `_fits` on two exact counts."""
    frag, state = swept["simple"]
    local = frag.dev.local()
    hits = counting(monkeypatch)
    pull = jax.jit(lambda t: bc_module._level_pull(ONE_FRAGMENT, local, t))
    either = jax.jit(lambda t, at: bc_module._level_sum(
        ONE_FRAGMENT, local, t, at, budget))
    degree = frag.host_oe[0].degree
    pushed = pulled = 0
    for d, sweep, at, table in tables(state):
        fits = bool(bc_module._fits(at.sum(), degree[at].sum(), budget))
        before = len(hits)
        got = np.asarray(either(jnp.asarray(table), jnp.asarray(at)))
        jax.effects_barrier()
        assert len(hits) - before == int(fits), (d, sweep, why)
        want = np.asarray(pull(jnp.asarray(table)))
        if fits:
            np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
        else:
            assert got.tobytes() == want.tobytes()
        pushed, pulled = pushed + fits, pulled + (not fits)
    assert pushed >= 2 and pulled >= 2


@pytest.fixture
def budgets(monkeypatch):
    def set_to(rows, entries, floor=0):
        monkeypatch.setattr(bc_module, "_PUSH_ROWS", rows)
        monkeypatch.setattr(bc_module, "_PUSH_ENTRIES", entries)
        monkeypatch.setattr(bc_module, "_DENSE_FLOOR", floor)

    return set_to


def expected_pushes(graph, levels, budget):
    """The sums a query's two loops push, counted from the reference's
    levels: forward from depths 0..L, backward from L..1."""
    degree = np.asarray(graph.mult.sum(axis=1)).ravel()
    fits = [bool(bc_module._fits(len(level), int(degree[level].sum()), budget))
            for level in levels]
    return sum(fits) + sum(fits[1:])


@pytest.mark.parametrize("narrow", [False, True], ids=["f64", "f32"])
@pytest.mark.parametrize("root", ["edge", "isolated", "small"])
@pytest.mark.parametrize("kind", ["simple", "parallel"])
def test_the_app_with_a_push_arm_answers_as_the_reference(
        kind, root, narrow, budgets, monkeypatch):
    """The registry's `bc` with the arm forced to exist (64 rows, 256
    entries: the first and last levels push, the wide ones pull) against
    the plain reference, in float64 and from a float32 state; `BC_STATS`
    counts the arms the device took, which the host recounts by the same
    rule."""
    n, src, dst, graph, roots = drawn(kind)
    budgets(64, 256)
    hits = counting(monkeypatch)
    frag = build_fragment(src, dst, None, n, 1)

    w = Worker((narrow_bc() if narrow else APP_REGISTRY["bc"])(), frag)
    state = w.query(source=roots[root])
    assert w.app.push_budget == (64, 256) and int(w.rounds) == 0
    got = by_id(frag, w.result_values())
    jax.effects_barrier()
    delta, sigma, depth, levels = bc_reference.brandes(graph, roots[root])
    got_depth = by_id(frag, np.asarray(state["depth"]))
    assert (np.where(got_depth == SENT, -1, got_depth) == depth).all()
    got_pn = by_id(frag, np.asarray(state["pn"]))
    if narrow:
        assert mismatches("eps", got, delta, EPS) == 0
        assert (got_pn == sigma).all()  # integers under 2^24: exact in any order
    else:
        np.testing.assert_allclose(got, delta, rtol=1e-9, atol=1e-12)
        assert (got_pn == sigma).all()
    assert (got[delta == 0] == 0).all()
    deep = len(levels) - 1
    stats = BC_STATS.snapshot()
    assert stats["pushes"] == len(hits) == expected_pushes(graph, levels, (64, 256))
    assert stats["pulls"] + stats["pushes"] == 2 * deep + 1
    assert (stats["levels"], stats["reached"]) == (deep, int((depth >= 0).sum()))
    if root == "edge":  # thin at both ends, wide in the middle
        assert stats["pushes"] >= 3 and stats["pulls"] >= 3
    else:  # 1 row, or 5 on a path: every sum a push
        assert stats["pulls"] == 0


def test_a_path_pushes_every_level_and_a_star_none(budgets):
    """Known thin levels: on a path every level is one row of at most two
    entries, `2L + 1` pushes and no pull; a star's hub holds more entries
    than C and its leaves are more rows than B, so from a leaf only the
    first sum (one row, one entry) and the last two of the forward sweep's
    mirror fit."""
    budgets(8, 16)
    n = 40
    path = build_fragment(np.arange(n - 1), np.arange(1, n), None, n, 1)
    w = Worker(APP_REGISTRY["bc"](), path)
    w.query(source=0)
    got = by_id(path, w.result_values())
    assert (got == np.arange(n - 1, -1, -1)).all()  # all it reaches, behind it
    assert BC_STATS.snapshot() == {
        "levels": n - 1, "reached": n, "pulls": 0, "pushes": 2 * (n - 1) + 1}
    star = build_fragment(np.zeros(n - 1, int), np.arange(1, n), None, n, 1)
    w = Worker(APP_REGISTRY["bc"](), star)
    w.query(source=1)
    got = by_id(star, w.result_values())
    assert got[0] == n - 2 and got[1] == n - 1 and (got[2:] == 0).all()
    # levels: {1} 1 row 1 entry, {0} 1 row 39 entries, the other 38 leaves:
    # forward sums from depths 0, 1, 2 push, pull, pull; backward from 2, 1
    # pull, pull
    assert BC_STATS.snapshot() == {
        "levels": 2, "reached": n, "pulls": 4, "pushes": 1}


@pytest.mark.parametrize("how", ["two_fragments", "lanes", "under_the_floor"])
def test_every_other_query_keeps_the_pull(how, budgets, monkeypatch):
    """Several fragments, query lanes and a graph under the dense floor are
    built without the arm: no push runs, `pushes` reads 0 and the answer is
    the reference's."""
    n, src, dst, graph, roots = drawn("simple")
    budgets(64, 256, floor=1 << 20 if how == "under_the_floor" else 0)
    hits = counting(monkeypatch)
    delta, _, depth, levels = bc_reference.brandes(graph, roots["edge"])
    frag = build_fragment(src, dst, None, n, 2 if how == "two_fragments" else 1)
    w = Worker(APP_REGISTRY["bc"](), frag)
    if how == "lanes":
        w.query_batch([{"source": roots["edge"]}, {"source": roots["small"]}])
        got = by_id(frag, w.batch_result_values(0))
    else:
        w.query(source=roots["edge"])
        got = by_id(frag, w.result_values())
    jax.effects_barrier()
    assert w.app.push_budget is None and not hits
    np.testing.assert_allclose(got, delta, rtol=1e-9, atol=1e-12)
    deep = len(levels) - 1
    assert BC_STATS.snapshot() == {
        "levels": deep, "reached": int((depth >= 0).sum()),
        "pulls": 2 * deep + 1, "pushes": 0}
    if how == "lanes":  # the same worker asked a single query takes the offer again
        w.query(source=roots["edge"])
        assert w.app.push_budget == (64, 256)
        assert by_id(frag, w.result_values()) == pytest.approx(delta, rel=1e-9)
        assert BC_STATS.snapshot()["pushes"] == expected_pushes(graph, levels, (64, 256))


def test_the_push_arm_holds_nothing_as_wide_as_the_graph(budgets):
    """The traced program with the arm: a `cond` in each loop; in its push
    branch every gather reads B or C places and the one scatter adds C
    values (the V-wide table of zeros it adds into aside), and nothing
    sorts.  Without the arm the program is the parent's: no `cond` at
    all."""
    n, src, dst, _, roots = drawn("simple")
    frag = build_fragment(src, dst, None, n, 1)
    rows, entries = 64, 256
    vp, ep = frag.vp, frag.dev.ie.edge_nbr.shape[1]
    assert len({rows, entries, vp, vp + 1, ep}) == 5

    def lowered(**how):
        w = Worker(APP_REGISTRY["bc"](), frag)
        state = w.app.init_state(frag, source=roots["edge"])
        return w._make_runner(w.app.max_rounds)(state).lower(
            frag.dev, state, {}).as_text(**how)

    plain = lowered()
    assert "stablehlo.case" not in plain and "stablehlo.scatter" not in plain
    budgets(rows, entries)
    text = lowered()
    assert text.count("stablehlo.case") == 2 and "stablehlo.sort" not in text
    named = lowered(debug_info=True)
    for scope in ("grape.frontier.compact", "grape.pull.gather", "grape.pull.fold",
                  "grape.bc.forward", "grape.bc.backward"):
        assert scope in named, scope

    local = frag.dev.local()
    arm = jax.jit(lambda t, at: bc_module._level_sum(
        ONE_FRAGMENT, local, t, at, (rows, entries)))
    text = arm.lower(jnp.zeros(vp), jnp.zeros(vp, bool)).as_text()
    # the pull arm of this one-fragment program is the scan fold: no scatter
    found = []
    for m in re.finditer(r'stablehlo\.(gather|scatter)"?\(', text):
        sig = text[m.start():text.index("->", text.index(" : (", m.start()))]
        operands = re.findall(r"tensor<([0-9x]*)x?[a-z0-9]+>", sig[sig.rindex(" : ("):])
        found.append((m.group(1), int(operands[1].split("x")[0])))
    wide = [f for f in found if f[1] not in (rows, entries)]
    # the pull arm's own: the E-wide gather of the table, the V-wide one of
    # the scanned stream at the rows' ends
    assert sorted(wide) == [("gather", vp), ("gather", ep)], found
    assert [f for f in found if f[0] == "scatter"] == [("scatter", entries)]
    assert ("gather", rows) in found and ("gather", entries) in found


def test_every_counter_of_the_namespace_is_in_the_inventory():
    """docs/OBSERVABILITY.md's row of the `bc` namespace names each field."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "OBSERVABILITY.md")) as f:
        row, = [ln for ln in f if ln.startswith("| federated counter | `bc` namespace")]
    for field in BC_STATS.snapshot():
        assert f"`{field}`" in row, field
