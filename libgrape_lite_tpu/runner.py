"""run_app equivalent: dispatch by app name, load, query, output.

Re-design of `examples/analytical_apps/run_app.{cc,h}`
(`run_app.h:103-323`: CreateAndQuery / DoQuery) and `utils.h` (DoQuery
writes per-fragment results via `GetResultFilename`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict

import numpy as np

from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.utils import timer
from libgrape_lite_tpu.utils.types import LoadStrategy
from libgrape_lite_tpu.worker.worker import Worker




@dataclass
class QueryArgs:
    """Flag bag (reference `examples/analytical_apps/flags.cc:23-69`)."""

    application: str = "sssp"
    efile: str = ""
    vfile: str = ""
    out_prefix: str = ""
    directed: bool = False
    sssp_source: int | str = 0
    bfs_source: int | str = 0
    bc_source: int | str = 0
    kcore_k: int = 0
    kclique_k: int = 3
    khop_k: int = 2  # k-hop neighborhood hop bound (models/khop.py)
    cn_source: int | str = 0  # common_neighbors 2-hop query source
    pr_d: float = 0.85
    pr_mr: int = 10
    cdlp_mr: int = 10
    degree_threshold: int = 0
    fnum: int | None = None
    # jax.distributed gang membership (parallel/comm_spec.py:
    # init_distributed runs before any backend use when
    # num_processes > 1); 0/unset = single-process
    coordinator: str = ""
    num_processes: int = 0
    process_id: int = -1
    partitioner_type: str = "map"
    idxer_type: str = "hashmap"
    rebalance: bool = False
    rebalance_vertex_factor: int = 0
    string_id: bool = False
    memory_stats: bool = False
    checkpoint_every: int = 0  # ft/: superstep checkpoint cadence (0 = off)
    checkpoint_dir: str = ""
    resume: bool = False  # continue from the last complete checkpoint
    guard: str = ""  # guard/: breach policy ("" reads GRAPE_GUARD)
    profile: bool = False
    trace: str = ""  # obs/: Chrome-trace output path ("" reads GRAPE_TRACE)
    metrics: str = ""  # obs/: metrics snapshot basename (GRAPE_METRICS)
    serialize: bool = False
    deserialize: bool = False
    serialization_prefix: str = ""
    vc: bool = False  # vertex-cut storage (reference --vc, run_app_vc.h)
    delta_efile: str = ""
    delta_vfile: str = ""
    extra: Dict[str, Any] = field(default_factory=dict)


def _coerce_source(v, string_id: bool):
    if string_id or isinstance(v, int):
        return v
    try:
        return int(v)
    except (TypeError, ValueError):
        return v


def build_query_kwargs(app_name: str, args: QueryArgs) -> dict:
    if app_name.startswith("sssp"):
        return {"source": _coerce_source(args.sssp_source, args.string_id)}
    if app_name.startswith("bfs"):
        return {"source": _coerce_source(args.bfs_source, args.string_id)}
    if app_name == "bc":
        return {"source": _coerce_source(args.bc_source, args.string_id)}
    if app_name == "kcore":
        return {"k": args.kcore_k}
    if app_name == "kclique":
        return {"k": args.kclique_k}
    if app_name.startswith("pagerank"):
        return {"delta": args.pr_d, "max_round": args.pr_mr}
    if app_name.startswith("lcc") or app_name == "triangle_count":
        # hub cost cap (reference FLAGS_degree_threshold, lcc.h:234-243);
        # 0 = disabled (the reference's INT_MAX default);
        # triangle_count shares the LCC credit pass and its filter
        return {"degree_threshold": args.degree_threshold}
    if app_name == "common_neighbors":
        return {"source": _coerce_source(args.cn_source, args.string_id)}
    if app_name == "khop":
        # the hop bound is a constructor hyperparameter (run_app bakes
        # it into the app); the per-query arg is the source alone
        return {"source": _coerce_source(args.bfs_source, args.string_id)}
    if app_name.startswith("cdlp"):
        return {"max_round": args.cdlp_mr}
    return {}


def run_app(args: QueryArgs, comm_spec: CommSpec | None = None) -> Worker:
    # flag-consistency checks fail in milliseconds, BEFORE the (possibly
    # minutes-long) graph load
    if (args.checkpoint_every or args.resume) and not args.checkpoint_dir:
        raise ValueError(
            "--checkpoint_every/--resume require --checkpoint_dir"
        )
    if args.checkpoint_dir and not (args.checkpoint_every or args.resume):
        raise ValueError(
            "--checkpoint_dir requires --checkpoint_every (or --resume)"
        )
    if args.num_processes and args.num_processes > 1:
        if args.process_id < 0 or not args.coordinator:
            raise ValueError(
                "--num_processes > 1 requires --coordinator and "
                "--process_id (every member of the gang names itself)"
            )
        if comm_spec is not None:
            raise ValueError(
                "pass EITHER a prebuilt comm_spec or the "
                "--coordinator/--num_processes/--process_id flags, "
                "not both"
            )
        # must run before the partition probe or load touch a backend
        comm_spec = CommSpec.init_distributed(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
            fnum=args.fnum,
        )
    if args.trace or args.metrics:
        # arm obs/ BEFORE the load so the load_graph span is captured;
        # flags win over env (configure replaces any env-armed tracer)
        from libgrape_lite_tpu import obs

        obs.configure(
            trace_path=args.trace or None,
            metrics_path=args.metrics or None,
        )
    name = args.application
    if args.vc and name == "pagerank":
        name = "pagerank_vc"  # reference run_app_vc.h:82-89
    if name not in APP_REGISTRY:
        raise ValueError(
            f"unknown application {name!r}; known: {sorted(APP_REGISTRY)}"
        )
    app_cls = APP_REGISTRY[name]
    # khop's hop bound is a trace-key hyperparameter, not a query arg
    app = app_cls(k=args.khop_k) if name == "khop" else app_cls()

    if comm_spec is None:
        comm_spec = CommSpec(fnum=args.fnum)

    weighted = getattr(app_cls, "needs_edata", False)
    spec = LoadGraphSpec(
        directed=args.directed,
        weighted=weighted,
        load_strategy=app_cls.load_strategy,
        partitioner_type=args.partitioner_type,
        idxer_type=args.idxer_type,
        rebalance=args.rebalance,
        rebalance_vertex_factor=args.rebalance_vertex_factor,
        string_id=args.string_id,
        serialize=args.serialize,
        deserialize=args.deserialize,
        serialization_prefix=args.serialization_prefix,
        edata_dtype=np.float64,
    )

    from libgrape_lite_tpu.utils.types import MessageStrategy

    # 1-D vs 2-D partition choice (fragment/partition.py, ROADMAP
    # item 2): consulted ONLY when GRAPE_PARTITION asks — the default
    # path stays byte-for-byte the program it always was.  An engaged
    # decision swaps in the registered 2-D twin and the vertex-cut
    # fragment; EVERY declined request records its reason (never
    # silent), and the structurally-cheap declines (wrong app, non-
    # square fnum, string ids, delta load) are recorded WITHOUT
    # reading the edge file.
    vc2d_inputs = None
    if not args.vc:
        from libgrape_lite_tpu.fragment.partition import (
            VC2D_APPS,
            partition_mode,
            precheck_partition,
            resolve_partition,
        )

        if partition_mode() != "1d":
            empty = np.zeros(0, dtype=np.int64)
            if args.delta_efile or args.delta_vfile:
                resolve_partition(
                    name, comm_spec.fnum, empty, empty, empty,
                    directed=args.directed, string_id=args.string_id,
                    eligible=False,
                    reason="delta-mutation load has no vertex-cut path",
                )
            elif not args.efile:
                # a deserialize run may carry no edge file at all, and
                # the probe prices the cut from the edge list; decline
                # with the reason recorded rather than crash
                resolve_partition(
                    name, comm_spec.fnum, empty, empty, empty,
                    directed=args.directed, string_id=args.string_id,
                    eligible=False,
                    reason="no edge file: the partition probe reads "
                           "the edge list",
                )
            elif precheck_partition(
                name, comm_spec.fnum, directed=args.directed,
                string_id=args.string_id,
            ) is not None:
                # structurally ineligible: record the decline cheaply
                # (resolve_partition re-derives the same reason before
                # touching the arrays)
                resolve_partition(
                    name, comm_spec.fnum, empty, empty, empty,
                    directed=args.directed, string_id=args.string_id,
                )
            else:
                from libgrape_lite_tpu.fragment.loader import (
                    read_graph_files,
                )

                with timer.phase("partition probe"):
                    p_src, p_dst, p_w, p_oids = read_graph_files(
                        args.efile, args.vfile or None, spec
                    )
                    decision = resolve_partition(
                        name, comm_spec.fnum, p_src, p_dst, p_oids,
                        directed=args.directed,
                    )
                if decision["engaged"]:
                    name = VC2D_APPS[name]
                    app = APP_REGISTRY[name]()
                    vc2d_inputs = (p_src, p_dst, p_w, p_oids)
                # an auto decline on modeled cost falls through to the
                # 1-D loader, which re-reads the file — the probe is
                # opt-in (GRAPE_PARTITION set) and the arrays cannot
                # seed LoadGraph's partitioner/idxer pipeline without
                # replicating it here

    is_vc = app_cls.message_strategy == MessageStrategy.kGatherScatter
    if args.vc and not is_vc:
        raise ValueError(
            f"--vc has no vertex-cut implementation for {name!r} "
            "(the reference's --vc path supports pagerank only, "
            "run_app_vc.h:82-89)"
        )
    if is_vc and (args.delta_efile or args.delta_vfile):
        raise ValueError("--delta_efile/--delta_vfile are not supported "
                         "with vertex-cut storage")
    if is_vc and args.string_id:
        raise ValueError(
            "--string_id is not supported with vertex-cut storage (the "
            "reference's VC fragment is specialized to uint64 oids, "
            "immutable_vertexcut_fragment.h)"
        )

    with timer.phase("load graph"):
        if vc2d_inputs is not None or is_vc:
            from libgrape_lite_tpu.fragment.loader import (
                LoadVertexcutGraph,
            )

            # one builder for --vc and GRAPE_PARTITION=2d.  Min-fold
            # pulls get symmetrised COO tiles (the 1-D loader's
            # undirected-CSR convention; WCC symmetrises even when
            # directed — weak connectivity IS the undirected
            # traversal) and keep COO tiles on raw directed storage;
            # pagerank_vc keeps raw storage, accumulates both
            # directions in-app and reads the tiles' pull CSRs
            layout = type(app).tile_layout
            sym = layout == "coo" and (
                name == "wcc_vc" or not args.directed)
            frag = LoadVertexcutGraph(
                args.efile, args.vfile or None, comm_spec,
                dataclasses.replace(spec, vertex_cut=True),
                symmetrize=sym, layout=layout, edges=vc2d_inputs,
            )
        elif args.delta_efile or args.delta_vfile:
            from libgrape_lite_tpu.fragment.mutation import LoadGraphAndMutate

            frag = LoadGraphAndMutate(
                args.efile, args.vfile or None,
                args.delta_efile or None, args.delta_vfile or None,
                comm_spec, spec,
            )
        else:
            frag = LoadGraph(args.efile, args.vfile or None, comm_spec, spec)

    if args.memory_stats:
        from libgrape_lite_tpu.utils.memory import get_memory_stats

        print(f"[memory] after load: {get_memory_stats()}")

    if name == "sssp_select":
        # per-(graph, source) dense-vs-delta decision on evidence
        # (models/sssp_select.py); the probe runs on the host CSRs the
        # load just produced, before any device compile
        from libgrape_lite_tpu.models.sssp_select import select_sssp_variant
        from libgrape_lite_tpu.utils import logging as glog

        with timer.phase("sssp variant probe"):
            picked, reason = select_sssp_variant(
                frag, _coerce_source(args.sssp_source, args.string_id)
            )
        glog.log_info(f"sssp_select -> {picked}: {reason}")
        app = APP_REGISTRY[picked]()

    with timer.phase("load application"):
        worker = Worker(app, frag)

    with timer.phase("run algorithm"):
        kw = build_query_kwargs(name, args)
        if args.profile and not getattr(app, "host_only", False):
            from libgrape_lite_tpu.utils import logging as glog

            if glog._level < 1:
                glog.set_vlog_level(1)  # --profile exists to show timings
        guard = args.guard or None  # None -> GRAPE_GUARD env
        if args.resume:
            # query args replay from the checkpoint metadata (the
            # fingerprint guarantees they match this invocation's app +
            # fragment); a fresh cadence flag overrides the recorded one
            worker.resume(
                args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every or None,
                guard=guard,
            )
        elif args.checkpoint_every:
            worker.query(
                checkpoint_every=args.checkpoint_every,
                checkpoint_dir=args.checkpoint_dir,
                guard=guard,
                **kw,
            )
        elif args.profile and not getattr(app, "host_only", False):
            worker.query_stepwise(guard=guard, **kw)
        else:
            worker.query(guard=guard, **kw)

    if args.memory_stats:
        from libgrape_lite_tpu.utils.memory import get_memory_stats

        print(f"[memory] after query: {get_memory_stats()}")

    if args.out_prefix:
        with timer.phase("print output"):
            worker.output(args.out_prefix)

    from libgrape_lite_tpu import obs

    if obs.armed():
        # final flush: the worker flushes per query, but the output
        # phase above and any post-query spans must land too
        flushed = obs.flush()
        from libgrape_lite_tpu.utils import logging as glog

        if flushed["trace"]:
            glog.log_info(
                f"obs: trace -> {flushed['trace']} (JSONL twin "
                f"{flushed['jsonl']}); open via https://ui.perfetto.dev"
            )
        if flushed["metrics"]:
            glog.log_info(
                f"obs: metrics -> {flushed['metrics']}.json / .prom"
            )
    return worker
