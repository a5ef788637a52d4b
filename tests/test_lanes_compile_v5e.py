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

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from libgrape_lite_tpu.ops import segment
from libgrape_lite_tpu.ops.segment import pull_gather, segment_reduce
from libgrape_lite_tpu.utils.memory import executable_bytes
from tests.conftest import GATHER_BUDGET

LANES, V, EP = 4, 262144, 1 << 20


def _steer(monkeypatch, budget: int = 64 << 20):
    """The choices as the TPU backend steers them on a v5e (the process
    itself is a CPU's): the kernels' backend, half of the chip's 128
    MiB of VMEM for a gathered table, all of it as the size from which
    a scan's streams go through `tile_scan`."""
    monkeypatch.setattr(segment, "use_pallas", lambda: True)
    monkeypatch.setattr(segment, "gather_table_budget", lambda: budget)
    monkeypatch.setattr(segment, "tile_scan_floor", lambda: 128 << 20)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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
    _steer(monkeypatch, GATHER_BUDGET)
    text = _compiled(one_chip, jnp.dtype(dtype))
    assert "tpu_custom_call" in text and "vmem_gather" in text
    assert " scatter(" not in text
    assert "while(" in text  # the lanes are a loop, not four copies
    # one instance of each kernel: the pull's gather and, since PR 45,
    # the fold's row ends; XLA's V-wide gather of them is gone.  The
    # lanes' streams fit the VMEM, so their scan keeps XLA's steps
    assert text.count("tpu_custom_call") == 2
    assert "vmem_row_gather" in text and "tile_scan" not in text
    assert not re.search(rf"= \w+\[{V}\]\S* fusion\(.*kind=kCustom", text)


def test_unarmed_lanes_compile_to_the_fused_scatter(one_chip):
    text = _compiled(one_chip, jnp.dtype("float32"))
    assert "tpu_custom_call" not in text
    assert " scatter(" in text


# ---- the round that follows its frontier, at the road cells' shapes ----

ROAD_V, ROAD_EP, ROWS, ENTRIES = 1 << 20, 2517504, 2048, 8192


@pytest.mark.parametrize("values,weighted", [("int32", False),
                                             ("float32", True)])
def test_the_frontier_round_compiles(values, weighted, one_chip):
    """BFS's push (a constant, no threshold) and SSSP's (a weight an
    entry read from the shard's `[1, Ep]` block, the next list cut at a
    threshold), with the refill of the list from the state: the chip's
    compiler takes both, and nothing in the push is as wide as the
    graph but the update of the values in place."""
    from libgrape_lite_tpu.ops.segment import (
        ADVANCE_SCOPE, frontier_relax, frontier_rows, frontier_spans,
    )

    def shaped(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    def push(dist, front, ptr, nbr, w, below):
        lo, count, total = frontier_spans(front, ptr)
        return (*frontier_relax(
            dist, front, lo, count, nbr, ENTRIES,
            add=w if weighted else 1, below=below if weighted else None,
            absent=None if weighted else jnp.iinfo(jnp.int32).max), total)

    def refill(dist, below):
        return frontier_rows(dist >= below, ROWS, ADVANCE_SCOPE)

    with jax.enable_x64(False):
        text = jax.jit(push).lower(
            shaped((ROAD_V,), values), shaped((ROWS,), "int32"),
            shaped((1, ROAD_V + 1), "int32"), shaped((1, ROAD_EP), "int32"),
            shaped((1, ROAD_EP), values), shaped((), values),
        ).compile().as_text()
        jax.jit(refill).lower(
            shaped((ROAD_V,), values), shaped((), values)).compile()
    assert " sort(" in text and " scatter(" in text
    # no copy of an E-wide block: the slots read it where it lies
    assert f"[{ROAD_EP}]" not in text.replace(f"[1,{ROAD_EP}]", "")


# ---- the whole fused runner at the road cells' size: where the loop's
# buffers live ----


@pytest.fixture(scope="module")
def road20():
    """The road cells' graph, as one float32 fragment (the chip's x32)."""
    import numpy as np

    from tests.test_sssp_frontier import fragment, road

    n, src, dst, w = road(20)
    return fragment(n, src, dst, w.astype(np.float32))


# Generated code of PR 46's runners, compiled here as below (bytes;
# `scratch/runner_code47.py` on the parent's export, PR 47): a later
# change may add `CODE_ROOM` and no more, because code is HBM and
# `hbm_peak_bytes` is bounded at 1%.  (PR 47's tile-scan kernel does not
# enter these runners: their streams fit the VMEM, `tile_scan_floor`.
# Where it does it takes the place of seven E-wide fusions, 0.9 MB of
# code for 0.07.)
PARENT_CODE = {"road.bfs": 6_772_224, "road.sssp": 7_943_168,
               "lanes.bfs": 3_704_320, "lanes.sssp": 3_923_968,
               # PR 51's `bc` on the Kronecker scale-18 fragment, both
               # arms (3,374,592 before the push arms: the two cost
               # 2.82 MB here, 3.58 MB at `g500-bc.bc-key1`'s shapes,
               # which has 5.42 MB of room)
               "kron18.bc": 6_194_688}
CODE_ROOM = 250_000


def _within(compiled, parent: int, room: int):
    # through the helper that stamps `runner.compile`'s `code_bytes`:
    # what the worker records on the chip is what is bounded here
    code = executable_bytes(compiled)["code_bytes"]
    assert code <= parent + room, (code, parent)


def _described(w, frag, state, key_specs, one_chip, devices=None):
    """`(dev, carried)`: the fragment and a host state as shapes on the
    described chip (or on `devices`, a fragment each), for
    `runner.lower`; the worker's mesh becomes theirs."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS

    mesh = Mesh(np.array(devices or [one_chip._device]), (FRAG_AXIS,))
    w.comm_spec.mesh = mesh
    specs, _ = key_specs(state)

    def shaped(x, spec):
        x = np.asarray(x) if not hasattr(x, "dtype") else x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec))

    _, frag_spec = w._mesh_layout()
    dev = jax.tree_util.tree_map(lambda x: shaped(x, frag_spec), frag.dev)
    return dev, {k: shaped(v, specs[k]) for k, v in state.items()}


@pytest.mark.parametrize("name,values", [("bfs", "s32"), ("sssp", "f32")])
def test_the_road_runner_keeps_its_values_in_vmem(name, values, one_chip,
                                                  road20, monkeypatch):
    """A frontier round's C-wide gathers cost 7 ns an index from a
    table the compiler keeps in VMEM and 14 to 25 from HBM, and which
    it is hangs on the whole loop's shape, not on the round's lines: a
    V-wide operand handed to the dense arm's inner `cond` moved SSSP's
    distances and edge blocks out, 725 us a round for 385 (my chip
    runs, PR 43; PR 40 met the same with BFS).  So the runner of each
    road cell is compiled here as the chip compiles it, and the loop's
    values (memory space 1) and the fetches of the edge blocks into it
    have to be there."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    _steer(monkeypatch)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY[name](), road20)
        state = w.app.init_state(road20, source=5)
        assert w.app.frontier_budget == (ROWS, ENTRIES)
        assert not w.app.ephemeral_keys
        dev, carried = _described(w, road20, state, w._key_specs, one_chip)
        ends = segment.ROW_END_STATS.snapshot()
        scans = segment.SCAN_STATS.snapshot()
        compiled = w._make_runner(w.app.max_rounds)(state).lower(
            dev, carried, {}).compile()
    text = compiled.as_text()
    # the dense arm reads its row ends by the kernel (it never runs in
    # the road cells, but its code is HBM there: `hbm_peak_bytes` is
    # bounded at 1% of 67.7 / 68.8 MB) and scans by XLA's steps: the
    # road graph's 2.5M entries are 30 MB of streams
    assert segment.ROW_END_STATS.snapshot() == {
        **ends, "kernel": ends["kernel"] + 1}
    assert segment.SCAN_STATS.snapshot() == {
        **scans, "xla": scans["xla"] + 1}
    assert "vmem_row_gather" in text and "tile_scan" not in text
    _within(compiled, PARENT_CODE["road." + name], CODE_ROOM)
    loops = [line for line in text.splitlines()
             if re.search(r" while\(", line) and f"{values}[{ROAD_V}]" in line]
    assert loops and all(
        re.search(rf"= \({values}\[{ROAD_V}\]\{{0:T\(1024\)S\(1\)\}}", line)
        for line in loops)
    # the push's update writes them where they live
    assert re.search(
        rf"= {values}\[{ROAD_V}\]\{{0:T\(1024\)S\(1\)\}} fusion\(.*scatter-min",
        text)


# ---- the default `wcc` at the road cell's size: what the hook round
# adds to the label round's runner ----

# `road-like-cc.wcc` on the parent commit (the label round) peaks at
# 65,226,752 bytes (my chip run, PR 46); the driver holds the change to
# 1% over it in that cell, 652,267 bytes, and what the change adds is
# code: the peaks differed by the compiled runners' difference to the
# byte (807,424 both, call A's tree)
WCC_ROOM = 652_267


def test_the_hook_round_fits_the_road_cells_room(one_chip, road20,
                                                 monkeypatch):
    """The two jumps' kernel instances, the hook's scatter and nothing
    that fetches the fragment's `inner_mask` into the loop: the hook
    round's runner within 0.55 MB of the label round's (0.2 MB less
    than the cell's bound allows; 522,752 bytes as written)."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.models.wcc import WCC
    from libgrape_lite_tpu.worker.worker import Worker

    class LabelWCC(WCC):
        hook_round = False

    _steer(monkeypatch)

    def runner(app):
        with jax.enable_x64(False):
            w = Worker(app, road20)
            state = w.app.init_state(road20)
            assert set(state) == {"comp"} and not w.app.ephemeral_keys
            dev, carried = _described(w, road20, state, w._key_specs,
                                      one_chip)
            return w._make_runner(w.app.max_rounds)(state).lower(
                dev, carried, {}).compile()

    label, hook = runner(LabelWCC()), runner(APP_REGISTRY["wcc"]())
    text = hook.as_text()
    # the pull's gather and the row ends as the label round has them,
    # and the two jumps; one scatter, the hook; no sort
    assert label.as_text().count("tpu_custom_call") == 2
    assert text.count("tpu_custom_call") == 4
    assert text.count(" scatter(") == 1 and " sort(" not in text
    assert " scatter(" not in label.as_text()
    assert f"pred[1,{ROAD_V}]" not in text  # the mask stays where it lies
    code = [executable_bytes(c)["code_bytes"] for c in (label, hook)]
    assert code[1] - code[0] <= 550_000 < WCC_ROOM, code


# ---- the batched SSSP runner at the serving cell's size: what it is
# handed, and which pull it takes ----

SERVE_V, SERVE_EP = 1 << 18, 1 << 23


@pytest.fixture(scope="module")
def kron18():
    """`serve-g500-s18`'s graph, as one float32 fragment (the chip's x32)."""
    import numpy as np

    from benchmarks.graphs import kronecker
    from tests.test_sssp_frontier import KRON, fragment

    src, dst, w = kronecker.edges(KRON, 18)
    return fragment(SERVE_V, src, dst, w.astype(np.float32))


def test_the_serving_sssp_lanes_read_the_fragments_own_weights(
        one_chip, kron18, monkeypatch):
    """Four SSSP lanes at `serve-g500-s18.keys8`'s shapes, compiled as the
    chip compiles them: the kernel a lane and the tile scan (PR 41), and
    of E-wide float blocks the runner is handed one, the fragment's
    `edge_w`: a batch builds and places its lanes and no stream beside
    them (a pre-masked copy of the weights was 33.5 MB and 0.18 s of
    host time a batch; PERF.md section 6, PR 44)."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.segment import FOLD_STATS, GATHER_STATS
    from libgrape_lite_tpu.worker.worker import Worker

    _steer(monkeypatch)
    assert kron18.vp == SERVE_V
    assert kron18.dev.ie.edge_nbr.shape == (1, SERVE_EP)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY["sssp"](), kron18)
        state = w.app.init_state(kron18, source=[5, 6, 7, 9])
        assert set(state) == {"dist"} and not w.app.ephemeral_keys
        assert state["dist"].shape == (LANES, 1, SERVE_V)
        dev, carried = _described(w, kron18, state, w._key_specs_batch,
                                  one_chip)
        gathers, folds = GATHER_STATS.snapshot(), FOLD_STATS.snapshot()
        compiled = w._make_batched_runner(
            w.app.max_rounds, LANES)(state).lower(dev, carried, {}).compile()
    text = compiled.as_text()
    _within(compiled, PARENT_CODE["lanes.sssp"], CODE_ROOM)
    took = {k: v - gathers[k] for k, v in GATHER_STATS.snapshot().items()}
    assert took == {"kernel": 1, "xla": 0}
    took = {k: v - folds[k] for k, v in FOLD_STATS.snapshot().items()}
    assert took == {"scan": 1, "scatter": 0}
    assert "vmem_gather" in text and " scatter(" not in text
    entry = text[text.index("\nENTRY "):]
    handed = re.findall(r"= (\w+\[[0-9,]*\])\S* parameter\(", entry)
    assert f"f32[{LANES},1,{SERVE_V}]" in handed
    assert handed.count(f"f32[1,{SERVE_EP}]") == 1


def test_the_serving_bfs_lanes_hold_their_code(one_chip, kron18,
                                               monkeypatch):
    """The serving cell's other batched runner: one instance of each
    gather kernel in the lanes' loops, the row ends counted once as
    `kernel`, the scan's first level once as `xla` (a lane's 8.4M
    entries are 101 MB of streams, under the VMEM line), and its code
    within the parent's + 0.25 MB (the cell has 1.56 MB of room for its
    two runners; PERF.md section 6, PR 45 and PR 47)."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    _steer(monkeypatch)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY["bfs"](), kron18)
        state = w.app.init_state(kron18, source=[5, 6, 7, 9])
        dev, carried = _described(w, kron18, state, w._key_specs_batch,
                                  one_chip)
        eph = frozenset(w.app.ephemeral_keys or ())
        ends = segment.ROW_END_STATS.snapshot()
        scans = segment.SCAN_STATS.snapshot()
        compiled = w._make_batched_runner(
            w.app.max_rounds, LANES)(state).lower(
                dev, {k: v for k, v in carried.items() if k not in eph},
                {k: v for k, v in carried.items() if k in eph}).compile()
    assert segment.ROW_END_STATS.snapshot() == {
        **ends, "kernel": ends["kernel"] + 1}
    assert segment.SCAN_STATS.snapshot() == {
        **scans, "xla": scans["xla"] + 1}
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and " scatter(" not in text
    assert "vmem_gather" in text and "vmem_row_gather" in text
    assert "tile_scan" not in text
    _within(compiled, PARENT_CODE["lanes.bfs"], CODE_ROOM)


# ---- BC's two sweeps on a Kronecker graph: a level is PageRank's pull ----


def test_a_bc_level_is_the_gather_kernel_and_the_scan_fold(
        one_chip, kron18, monkeypatch):
    """The registry's `bc` on a float32 Kronecker fragment, compiled as
    the chip compiles it: each of PEval's two loops holds one instance
    of the gather kernel, of the tile scan and of the row ends' kernel
    (the cell's streams are over the VMEM line at every scale of its
    list, 15.5M entries and more; this graph's 8.4M are brought over it
    by lowering the line), and no XLA gather as wide as the graph or as
    its vertices: the level's mask, the quotient and the updates are
    V-wide element work.  Since PR 51 each loop's level is a
    conditional between that pull and a push (`models/bc._level_sum`;
    this graph is over the dense floor): one scatter a loop, the push's
    C-wide add into a V-wide table of zeros, none as wide as the graph,
    no sort, and the runner's code under a stated line."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.models.bc import _PUSH_ENTRIES, _PUSH_ROWS
    from libgrape_lite_tpu.worker.worker import Worker

    def choices():
        return [s.snapshot() for s in (
            segment.GATHER_STATS, segment.FOLD_STATS, segment.SCAN_STATS,
            segment.ROW_END_STATS)]

    _steer(monkeypatch)
    monkeypatch.setattr(segment, "tile_scan_floor", lambda: 64 << 20)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY["bc"](), kron18)
        state = w.app.init_state(kron18, source=5)
        assert w.app.push_budget == (_PUSH_ROWS, _PUSH_ENTRIES)
        assert {k: str(v.dtype) for k, v in state.items()} == {
            "depth": "int32", "pn": "float32", "delta": "float32"}
        dev, carried = _described(w, kron18, state, w._key_specs, one_chip)
        before = choices()
        compiled = w._make_runner(w.app.max_rounds)(state).lower(
            dev, carried, {}).compile()
    # one call site a loop, each the kernels' kind
    assert choices() == [
        {**before[0], "kernel": before[0]["kernel"] + 2},
        {**before[1], "scan": before[1]["scan"] + 2},
        {**before[2], "kernel": before[2]["kernel"] + 2},
        {**before[3], "kernel": before[3]["kernel"] + 2}]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6
    assert all(k in text for k in ("vmem_gather", "tile_scan",
                                   "vmem_row_gather"))
    assert len(re.findall(r" while\(", text)) >= 2
    assert len(re.findall(r" conditional\(", text)) == 2
    assert " sort(" not in text
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert len(scatters) == 2, scatters
    for line in scatters:  # the add of C values into the V-wide zeros
        assert re.search(rf"scatter-add\S* = f32\[{SERVE_V}\]", line), line
    updates = re.findall(rf"f32\[{_PUSH_ENTRIES}\]", text)
    assert updates and f"f32[{SERVE_EP}]" in text  # the pull's candidates
    wide = [line for line in text.splitlines()
            if re.search(rf"\[({SERVE_EP}|{SERVE_V})\]\S* gather\(", line)]
    assert not wide, wide[:2]
    _within(compiled, PARENT_CODE["kron18.bc"], CODE_ROOM)


# ---- four fragments with a mirror plan: which gather packs the send
# buffer ----


@pytest.fixture(scope="module")
def cut4():
    """A random graph cut over four fragments (the chip's x32 state)."""
    from tests.conftest import rand_frag

    return rand_frag(4, n=20000, e=200000, seed=5, weighted=False)


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "unarmed"])
def test_the_exchange_packs_by_the_gather_it_can_see(armed, topo, one_chip,
                                                     cut4, monkeypatch):
    """PageRank's fused runner on four fragments under the mirror
    exchange, compiled for the described 2x2 as the chip compiles it:
    steered as the TPU backend steers it, `grape.exchange.pack` holds a
    `vmem_gather` instance (the shard's `[vp]` rank read by the
    `[fnum * m]` send stream, 7.1 ns an index by XLA's gather, 0.8 by
    the kernel: PERF.md section 6, PR 49) and no gather fusion as wide
    as the stream; unsteered it is the fusion it was."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.segment import GATHER_STATS
    from libgrape_lite_tpu.worker.worker import Worker

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    if armed:
        _steer(monkeypatch)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY["pagerank"](), cut4)
        state = w.app.init_state(cut4, max_round=3)
        plan, eph = w.app._mx, frozenset(w.app.ephemeral_keys)
        assert plan is not None and eph == {"mx_send", "mx_nbr"}
        dev, carried = _described(w, cut4, state, w._key_specs, one_chip,
                                  devices=topo.devices)
        gathers = GATHER_STATS.snapshot()
        text = w._make_runner(w.app.max_rounds)(state).lower(
            dev, {k: v for k, v in carried.items() if k not in eph},
            {k: v for k, v in carried.items() if k in eph},
        ).compile().as_text()
    took = {k: v - gathers[k] for k, v in GATHER_STATS.snapshot().items()}
    stream = plan.fnum * plan.m
    packs = [line for line in text.splitlines()
             if "grape.exchange.pack" in line]
    fused = [line for line in packs if re.search(
        rf"= f32\[{stream}\]\S* fusion\(.*kind=kCustom", line)]
    kernels = [line for line in packs
               if "tpu_custom_call" in line and "vmem_gather" in line]
    assert " all-to-all(" in text
    if armed:
        # the pull's gather and the pack's
        assert took == {"kernel": 2, "xla": 0}
        assert len(kernels) == 1 and not fused, (kernels, fused)
        assert f"f32[{stream // 128},128]" in kernels[0]
    else:
        assert took == {"kernel": 0, "xla": 2}
        assert "tpu_custom_call" not in text
        assert len(fused) == 1 and not kernels


# ---- the row-end kernel alone, at the cells' shapes ----

ROW_END_SHAPES = {
    # name: (dtype, rows, entries, devices)
    "g500_s21": ("float32", 1 << 21, 1 << 26, 1),
    "g500_s21_bfs": ("int32", 1 << 21, 1 << 26, 1),
    "cdlp_s19": ("int32", 1 << 19, 1 << 24, 1),
    # a shard of four, inside the `shard_map`: a ragged last slice
    "g500_s21_x4": ("float32", 1 << 19, 17192832, 4),
}


@pytest.mark.parametrize("name", sorted(ROW_END_SHAPES))
def test_the_row_end_kernel_compiles_at_the_cells_shapes(name, topo,
                                                         one_chip):
    """`vmem_row_gather` through the chip's own compiler at the streams
    the cells hold (two 4 MiB slices in VMEM, 64 of them at scale 21),
    on one chip and per shard of a four-chip mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from libgrape_lite_tpu.ops.pallas_kernels import vmem_row_gather

    dtype, rows, entries, devices = ROW_END_SHAPES[name]
    if devices == 1:
        fn = vmem_row_gather
        args = (jax.ShapeDtypeStruct((entries,), dtype, sharding=one_chip),
                jax.ShapeDtypeStruct((rows,), "int32", sharding=one_chip))
    else:
        mesh = Mesh(np.array(topo.devices), ("f",))
        fn = jax.shard_map(
            lambda t, i: vmem_row_gather(t[0], i[0])[None], mesh=mesh,
            in_specs=(P("f"), P("f")), out_specs=P("f"))
        over = NamedSharding(mesh, P("f"))
        args = (jax.ShapeDtypeStruct((devices, entries), dtype,
                                     sharding=over),
                jax.ShapeDtypeStruct((devices, rows), "int32",
                                     sharding=over))
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_row_gather" in text
    # the stream is read where it lies: no copy of it beside the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# ---- the tile-scan kernel alone, at the cells' shapes ----

TILE_SCAN_SHAPES = {
    # name: (kind, dtype, entries, devices)
    "g500_s21": ("sum", "float32", 1 << 26, 1),
    "g500_s21_bfs": ("min", "int32", 1 << 26, 1),
    "cdlp_s19_count": ("max", "int32", 1 << 24, 1),
    "datagen_count": ("max", "int32", 18950272, 1),
    "serving_lane_sssp": ("min", "float32", 1 << 23, 1),
    "road": ("min", "int32", 2517504, 1),
    # (the two above are under the VMEM line: the choice leaves them
    # to XLA, and the kernel takes them where a caller hands them over)
    # a shard of four, inside the `shard_map`: 134,319 tiles, a ragged
    # last block
    "g500_s21_x4": ("sum", "float32", 17192832, 4),
}


@pytest.mark.parametrize("name", sorted(TILE_SCAN_SHAPES))
def test_the_tile_scan_kernel_compiles_at_the_cells_shapes(name, topo,
                                                           one_chip):
    """`tile_scan` through the chip's own compiler at the streams the
    cells hold (blocks of 2,048 tiles, 256 of them at scale 21), on one
    chip and per shard of a four-chip mesh: the streams are read where
    they lie, and the kernel's code is a twentieth of the seven
    fusions' it stands for."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from libgrape_lite_tpu.ops.pallas_kernels import tile_scan

    kind, dtype, entries, devices = TILE_SCAN_SHAPES[name]

    def scan(v, i):
        # the 1-D streams in the views `_segmented_scan` hands over
        return tile_scan(v.reshape(-1, segment.SCAN_TILE),
                         i.reshape(-1, segment.SCAN_TILE),
                         segment._FOLDS[kind][1]).reshape(-1)

    if devices == 1:
        fn = scan
        args = tuple(
            jax.ShapeDtypeStruct((entries,), dt, sharding=one_chip)
            for dt in (dtype, "int32"))
    else:
        mesh = Mesh(np.array(topo.devices), ("f",))
        fn = jax.shard_map(
            lambda v, i: scan(v[0], i[0])[None], mesh=mesh,
            in_specs=(P("f"), P("f")), out_specs=P("f"))
        over = NamedSharding(mesh, P("f"))
        args = tuple(
            jax.ShapeDtypeStruct((devices, entries), dt, sharding=over)
            for dt in (dtype, "int32"))
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "tile_scan" in text
    if devices == 1:
        # no copy of a stream beside the kernel.  (A shard's `[1, Ep]`
        # blocks are squeezed by a copy here, as the ids' block is in
        # every four-chip runner: `reduce.37`, ROADMAP S7.)
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < 1 << 20
        assert memory.generated_code_size_in_bytes < 100_000


# ---- the vertex cut's round on the 2 x 2 mesh, at its cell's shapes ----

VC_CHUNK, VC_WIDTH = 1 << 20, 16_793_600  # g500-s21-vc2x2: vc, the stream


def test_the_vertex_cut_round_is_one_pull(topo, monkeypatch):
    """`pagerank_vc`'s fused runner compiled for the described 2 x 2 as
    the chip compiles it, at the shapes of `g500-s21-vc2x2.pagerank`
    (grown from a small fragment's: nothing is placed): a round's two
    directions are one `vmem_gather` over the 8 MiB table `[row copy;
    column copy]` and one scan fold whose 16.8M-entry stream lies over
    `tile_scan`'s floor, the row ends by their kernel; no scatter and
    no XLA gather as wide as the stream; the two axis sums and the
    transposes are the round's collectives."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
    )
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.segment import (
        FOLD_STATS, GATHER_STATS, ROW_END_STATS, SCAN_STATS,
    )
    from libgrape_lite_tpu.parallel.comm_spec import (
        CommSpec, VC_COL_AXIS, VC_ROW_AXIS,
    )
    from libgrape_lite_tpu.worker.worker import Worker

    _steer(monkeypatch)
    rng = np.random.default_rng(3)
    n, e = 4000, 30000
    frag = ImmutableVertexcutFragment.build(
        CommSpec(fnum=4), np.arange(n), rng.integers(0, n, e),
        rng.integers(0, n, e))
    small = frag.vc
    counted = (GATHER_STATS, FOLD_STATS, SCAN_STATS, ROW_END_STATS)
    with jax.enable_x64(False):
        w = Worker(APP_REGISTRY["pagerank_vc"](), frag)
        state = w.app.init_state(frag, max_round=10)
        mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                    (VC_ROW_AXIS, VC_COL_AXIS))
        w.comm_spec._mesh2d = mesh
        specs, _ = w._key_specs(state)
        _, frag_spec = w._mesh_layout()

        def grown(x, spec):
            # a chunk-wide axis becomes the cell's, the stream's too
            x = np.asarray(x) if not hasattr(x, "dtype") else x
            shape = tuple(
                {2 * small + 1: 2 * VC_CHUNK + 1, 2 * small: 2 * VC_CHUNK,
                 frag.dev.pull.edge_src.shape[1]: VC_WIDTH}.get(d, d)
                for d in x.shape)
            return jax.ShapeDtypeStruct(
                shape, x.dtype, sharding=NamedSharding(mesh, spec))

        dev = jax.tree_util.tree_map(lambda x: grown(x, frag_spec), frag.dev)
        dev = type(dev)(pull=dev.pull, fnum=4, k=2, vc=VC_CHUNK,
                        chunk=VC_CHUNK, total_vnum=2 * VC_CHUNK)
        before = [s.snapshot() for s in counted]
        compiled = w._make_runner(w.app.max_rounds)(state).lower(
            dev, {k: grown(v, specs[k]) for k, v in state.items()}, {},
        ).compile()
    text = compiled.as_text()
    took = [{k: v - b[k] for k, v in s.snapshot().items()}
            for s, b in zip(counted, before)]
    assert took == [{"kernel": 1, "xla": 0}, {"scan": 1, "scatter": 0},
                    {"kernel": 1, "xla": 0}, {"kernel": 1, "xla": 0}], took
    assert " scatter(" not in text
    for kernel in ("vmem_gather", "tile_scan", "vmem_row_gather"):
        assert kernel in text, kernel
    wide = [line for line in text.splitlines()
            if re.search(rf"\[{VC_WIDTH}\]\S* gather\(", line)]
    assert not wide, wide[:2]
    assert " all-reduce(" in text or " all-reduce-start(" in text
    assert " collective-permute" in text
    for scope in ("grape.vc.gather_master", "grape.vc.scatter",
                  "grape.pull.gather", "grape.pull.fold",
                  "grape.app.update"):
        assert scope in text, scope
    # what a chip holds beside the graph: 4.7 MB of code here (4.72 at
    # PR 53), and a state of O(N / k)
    mem = executable_bytes(compiled)
    print(mem)
    assert mem["code_bytes"] < 4_720_640 + CODE_ROOM, mem
