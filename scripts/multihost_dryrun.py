#!/usr/bin/env python
"""Two-process jax.distributed dryrun — the multi-host (DCN) analogue
of the reference's `mpirun -n 2` CI lane (`misc/app_tests.sh:231-238`).

Exercises `CommSpec.init_distributed` (parallel/comm_spec.py): each
process brings up the distributed runtime, contributes its local CPU
devices to the global frag mesh, and the two run a psum + ring
ppermute over a globally-sharded array — the collective patterns every
app uses, now crossing a process boundary (the reference's
PROCESS BOUNDARY marks in SURVEY.md §3.1).

Usage:
  python scripts/multihost_dryrun.py                  # parent: spawns 2 workers
  python scripts/multihost_dryrun.py --worker I ADDR  # child process I
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NPROC = 2
LOCAL_DEVICES = 2  # per process -> 4 global


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker(pid: int, coord: str) -> None:
    import jax

    # pin CPU before any backend init: a child never takes the chip
    jax.config.update("jax_platforms", "cpu")

    from libgrape_lite_tpu import compat
    from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS, CommSpec

    comm_spec = CommSpec.init_distributed(
        coordinator_address=coord, num_processes=NPROC, process_id=pid
    )
    assert comm_spec.fnum == NPROC * LOCAL_DEVICES, (
        f"expected {NPROC * LOCAL_DEVICES} global devices, got "
        f"{comm_spec.fnum}"
    )
    assert comm_spec.worker_id == pid

    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    fnum = comm_spec.fnum
    vp = 8
    sharding = NamedSharding(comm_spec.mesh, P(FRAG_AXIS))

    # each process materialises only its addressable shards
    def make(cb):
        return jax.make_array_from_callback((fnum, vp), sharding, cb)

    x = make(lambda idx: np.full(
        (1, vp), float(idx[0].start if idx[0].start else 0), np.float32
    ))

    def step(xs):
        local = xs[0]
        total = lax.psum(local.sum(), FRAG_AXIS)  # termination-vote shape
        fid = lax.axis_index(FRAG_AXIS)
        ring = [(i, (i + 1) % fnum) for i in range(fnum)]
        passed = lax.ppermute(local, FRAG_AXIS, ring)  # mirror exchange
        return (passed + total)[None], total

    fn = jax.jit(
        compat.shard_map(
            step, mesh=comm_spec.mesh, in_specs=(P(FRAG_AXIS),),
            out_specs=(P(FRAG_AXIS), P()), check_vma=False,
        )
    )
    out, total = fn(x)
    got = float(np.asarray(total))
    want = float(sum(f * vp for f in range(fnum)))
    assert got == want, f"psum across processes: got {got}, want {want}"
    # every shard received its ring predecessor's block: shard j was
    # filled with the constant j, so after the ring ppermute + psum it
    # must hold ((j-1) mod fnum) + want exactly
    for s in out.addressable_shards:
        j = s.index[0].start or 0
        expect = ((j - 1) % fnum) + want
        block = np.asarray(s.data)
        assert (block == expect).all(), (
            f"shard {j}: expected predecessor value {expect}, got {block}"
        )
    # ---- full app query across the process boundary (VERDICT r3 next
    # #10): PageRank on p2p-31 through the real loader + Worker, each
    # process verifying its addressable shards against the golden ----
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    jax.config.update("jax_enable_x64", True)  # f64 golden comparison

    spec = LoadGraphSpec(
        directed=False, weighted=True, edata_dtype=np.float64
    )
    frag = LoadGraph(
        os.path.join(REPO, "dataset", "p2p-31.e"),
        os.path.join(REPO, "dataset", "p2p-31.v"),
        comm_spec, spec,
    )
    app = PageRank()
    wk = Worker(app, frag)
    rank = wk.query(delta=0.85, max_round=10)["rank"]

    golden = {}
    with open(os.path.join(REPO, "dataset", "p2p-31-PR")) as f:
        for line in f:
            k, v = line.split()
            golden[int(k)] = float(v)

    checked = 0
    for shard in rank.addressable_shards:
        f = shard.index[0].start or 0
        vals = np.asarray(shard.data)[0]
        oids = frag.vertex_map.inner_oids(f)
        for i, o in enumerate(np.asarray(oids).tolist()):
            g = golden[int(o)]
            r = float(vals[i])
            assert abs(r - g) <= 1e-4 * max(abs(g), 1e-12), (
                f"shard {f} oid {o}: {r} vs golden {g}"
            )
            checked += 1
    assert checked > 0

    # ---- checkpointed query lane (docs/FAULT_TOLERANCE.md,
    # "Distributed resilience"): each process writes only its own
    # rank_<r>.npz shards under the two-phase commit barrier, then
    # both verify the committed snapshot's manifest ----
    ckpt_dir = os.environ.get("GRAPE_DRYRUN_CKPT_DIR", "")
    ckpt_note = ""
    if ckpt_dir:
        from libgrape_lite_tpu.ft.checkpoint import (
            list_checkpoints, read_meta,
        )
        from libgrape_lite_tpu.models import SSSP

        swk = Worker(SSSP(), frag)
        swk.query_stepwise(
            checkpoint_every=2, checkpoint_dir=ckpt_dir, source=6
        )
        steps = list_checkpoints(ckpt_dir)
        assert steps, f"no committed checkpoint in {ckpt_dir}"
        newest = steps[-1][1]
        meta = read_meta(newest)
        assert meta.get("layout") == "sharded", meta.get("layout")
        assert meta.get("ranks") == NPROC, meta
        for r in range(NPROC):
            shard = os.path.join(newest, f"rank_{r}.npz")
            assert os.path.exists(shard), f"missing {shard}"
        # output() on EVERY rank: the result gather inside it is a
        # process_allgather all processes must join (a rank-0-only
        # call deadlocks the gang); rank 0 alone then writes the files
        out_dir = os.path.join(os.path.dirname(ckpt_dir), "out")
        swk.output(out_dir)
        if pid == 0:
            for f in range(frag.fnum):
                rf = os.path.join(out_dir, f"result_frag_{f}")
                assert os.path.getsize(rf) > 0, f"empty {rf}"
        ckpt_note = (
            f", sharded ckpt rounds={meta['rounds']} ranks={meta['ranks']}"
            f", output files={frag.fnum}"
        )

    print(
        f"[worker {pid}] ok: fnum={fnum}, psum={got}, "
        f"pagerank golden rows checked={checked} rounds={wk.rounds}"
        f"{ckpt_note}",
        flush=True,
    )


def main() -> int:
    if "--worker" in sys.argv:
        i = sys.argv.index("--worker")
        worker(int(sys.argv[i + 1]), sys.argv[i + 2])
        return 0

    import tempfile
    import time

    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={LOCAL_DEVICES}"
    ).strip()
    # shared dir for the sharded-checkpoint lane; both workers write
    # their rank shards here and verify the committed manifest
    ckpt_tmp = tempfile.TemporaryDirectory(prefix="dryrun_ckpt_")
    env["GRAPE_DRYRUN_CKPT_DIR"] = os.path.join(ckpt_tmp.name, "ck")
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", str(i), coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for i in range(NPROC)
    ]
    # one shared deadline for ALL workers (not 180s each): callers wrap
    # this script in their own timeout, and sequential per-worker waits
    # would overshoot it while orphaning the rest of the gang
    deadline = time.monotonic() + 180
    ok = True
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:  # a hung gang must die together
                if q.poll() is None:
                    q.kill()
            out, _ = p.communicate()
            ok = False
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        text = out.decode(errors="replace")
        print(f"--- worker {i} (rc={p.returncode}) ---\n{text}")
        ok = ok and p.returncode == 0 and "ok:" in text
    print("multihost_dryrun:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
