"""The ring that carries LCC's sharded adjacency, from the trace and a count.

On several fragments every device holds the oriented lists of its own rows
as one block `[vp, D] int32` and intersects its local query rows with each
fragment's block in turn; the blocks go round a ring, one `ppermute` a pass.
What one device sends a query is therefore the block once per pass the
program makes, reckoned here from the fragment's rows and the list width and
held against the program's own count (`LCC_STATS["ring_bytes"]`).

The time is the time a collective is in flight on a device (from its start
to its done, the union over the query), not self time under a scope: a
permute that XLA overlaps with the pass is in flight for as long as the pass
beside it runs, so bytes over that time cannot read over 100%.  It holds the
query's other two collectives too, the all-gather of the degrees and the
all-reduce of the credits; both are small beside the blocks.

The chip's trace records the start-to-done spans (the line `Async XLA Ops`)
on the first device's plane only, while `reduce_xplane`'s `collective_s` is a
mean over all devices: on four chips it reads a quarter of what the one
device that shows the journey reads (PERF.md, PR 32).  So the mean here is
over the devices whose plane has that line; where none has (collectives that
are not asynchronous, a rehearsal) it is `collective_s` as it stands.
"""

import glob
import os

from benchmarks import reduce_scopes, reduce_xplane
from benchmarks.layer_metrics.lcc_scope import lcc_stats, traced_queries

ID_BYTES = 4  # the ELL holds int32 ids
SCOPE = "grape.lcc.ring"


def ring_bytes(passes: int, rows: int, width: int) -> int:
    """Bytes one device sends a query: its `[rows, width]` block of 32-bit
    ids once per ring pass."""
    return passes * rows * width * ID_BYTES


def sent_bytes(run) -> int | None:
    """`ring_bytes` of the run's fragment at the passes and the list width the
    program states; None from a program that counts no ring.  The program's
    own `ring_bytes` has to agree."""
    stats = lcc_stats(run)
    if not stats or not stats.get("ring_passes"):
        return None
    want = ring_bytes(stats["ring_passes"], run.frag.vp, stats["d_max"])
    if want != stats["ring_bytes"]:
        raise RuntimeError(f"LCC_STATS['ring_bytes'] is {stats['ring_bytes']}, "
                           f"{stats['ring_passes']} passes of a [{run.frag.vp}, "
                           f"{stats['d_max']}] int32 block are {want}")
    return want


def trace_file(run) -> str | None:
    """The traced pass's `.xplane.pb`, where `run.trace_pass` wrote it."""
    if "xplane" in run.__dict__:
        return run.xplane
    files = sorted(glob.glob(os.path.join(
        os.path.dirname(reduce_xplane.__file__), "cache", "traces",
        f"{run.cell['name']}-seed{run.seed}", "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def async_in_flight(path: str) -> dict:
    """{device: seconds a collective is in flight inside the traced window}
    for the devices whose plane records the asynchronous operations' spans."""
    spans, devices, asyncs = reduce_xplane.read_planes(path, "/device:TPU:")
    w0, w1 = next(((s, e) for s, e, n in spans if n == reduce_xplane.WINDOW_SPAN),
                  (float("-inf"), float("inf")))
    out = {}
    for dev, journeys in asyncs.items():
        covered = [(max(s, w0), min(e, w1))
                   for s, e, n in journeys + devices.get(dev, [])
                   if reduce_xplane.COLLECTIVE.search(n) and e > w0 and s < w1]
        if any(reduce_xplane.COLLECTIVE.search(n) for _, _, n in journeys):
            out[dev] = reduce_xplane.length(reduce_xplane.union(covered)) / 1e9
    return out


def in_flight_s(run) -> float | None:
    """Seconds a collective is in flight on a device during the traced pass,
    mean over the devices that record it; None where the trace has none.
    Found once a run, with an earlier line that sets the journey beside its
    two ends (the self time of the permute's start and done under the
    program's own scope) and beside `collective_s`."""
    t = run.trace
    if not t or not t["collective_s"]:
        return None
    if "lcc_ring_in_flight" not in run.__dict__:
        path = trace_file(run) if run.devices[0].platform == "tpu" else None
        shown = async_in_flight(path) if path else {}
        run.lcc_ring_in_flight = (sum(shown.values()) / len(shown) if shown
                                  else t["collective_s"])
        ends = ((reduce_scopes.for_run(run) or {}).get("scope_s") or {}).get(SCOPE)
        run.log(f"LCC ring: collectives in flight {run.lcc_ring_in_flight:.6f} s of the "
                f"traced pass (asynchronous spans on {len(shown)} of "
                f"{len(t['devices'])} device planes; collective_s, the mean over "
                f"all, {t['collective_s']:.6f} s); self time under {SCOPE} "
                + ("not in the trace" if ends is None else f"{ends:.6f} s"))
    return run.lcc_ring_in_flight


def read(run, spec):
    """`lcc_ring_ms`: time a collective is in flight per traced query, in ms; with
    `roofline`, the share of that time the chip's interconnect would need for
    the bytes one device sends."""
    in_flight = in_flight_s(run)
    if in_flight is None:
        return None
    in_flight /= traced_queries(run)
    if not spec.get("roofline"):
        return 1e3 * in_flight
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    sent = sent_bytes(run)
    if peaks is None or sent is None:
        return None  # no table of peaks for this device, or no ring in the program
    return 100.0 * sent / (peaks["ici_bits_per_s"] / 8) / in_flight
