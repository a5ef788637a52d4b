"""From a profiler trace (`.xplane.pb`) to numbers: the only code that reads one.

    python benchmarks/reduce_xplane.py <file.xplane.pb> [--dump]

`reduce()` reads the trace with `jax.profiler.ProfileData` and returns

  window_s        the traced window: the `bench.trace` span, else the extent
                  of the device operations
  busy_s          seconds in which an operation ran on the device (the union
                  of operation intervals inside the window), mean over devices
  idle_share      1 - busy / window, of the idlest device
  device_ops      [[name, seconds]]: operations by self time (a `while` or a
                  `call` does not count what runs inside it), mean over devices,
                  largest first, under the names the trace prints, shortened
                  by `short_name`
  collective_s / collective_exposed_s
                  union of collective operations' intervals, and the part of
                  it during which no other operation ran on that device
  idle_gaps       [[span, seconds]]: the idle time of the idlest device, by
                  the innermost `bench.*` host span that covers each gap
  spans           {name: [count, seconds]} of the `bench.*` spans themselves

Device planes are those whose name starts with `device_plane_prefix`
(`/device:TPU:`), and their operations the events of the line `XLA Ops`.  With
`device_plane_prefix=None` (a rehearsal without the chip) the host threads'
events that carry an `hlo_op` stat stand in for one device: that exercises the
code and gives no device number.
"""

from __future__ import annotations

import json
import re
import sys
import warnings
from collections import defaultdict

OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous operations
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast|all_gather|all_reduce|all_to_all|ppermute|psum", re.I)


def short_name(op: str) -> str:
    """'fusion.16 f32[2097153] kCustom' from the whole HLO instruction the
    chip's trace gives as an operation's name: its own name, its output type
    without the layout, and a fusion's kind."""
    m = re.match(r"%?([^\s=]+) = (\S+)", op)
    if not m:
        return op[:96]
    out = re.sub(r"\{[^}]*\}", "", m.group(2)).rstrip(",")
    kind = re.search(r"kind=(k\w+)", op)
    return (m.group(1) + ("" if out.startswith("(") else " " + out)
            + (" " + kind.group(1) if kind else ""))


def _stats(event) -> dict:
    try:
        with warnings.catch_warnings():  # jaxlib's stats type lacks __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            return dict(event.stats)
    except Exception:  # an event without readable stats has none
        return {}


def union(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Disjoint sorted `a` minus disjoint sorted `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(ops: list) -> list:
    """[name, self_ns] per operation: its duration less that of the
    operations nested directly inside it on the same line."""
    ops = sorted(ops, key=lambda o: (o[0], -(o[1] - o[0])))
    out, stack = [], []  # stack of [end, index into out]
    for s, e, name in ops:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([name, e - s])
        stack.append([e, len(out) - 1])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def read_planes(path: str, device_plane_prefix):
    """(host spans, {device: [(start, end, name)]}, the same for the
    asynchronous operations' start-to-done spans)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices, asyncs = [], {}, {}
    stand_in = []
    for plane in data.planes:
        is_device = (device_plane_prefix is not None
                     and plane.name.startswith(device_plane_prefix))
        for line in plane.lines:
            if is_device and line.name not in (OP_LINE, ASYNC_LINE):
                continue
            into = asyncs if line.name == ASYNC_LINE else devices
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((s, e, ev.name))
                elif is_device:
                    into.setdefault(plane.name, []).append(
                        (s, e, short_name(ev.name)))
                elif device_plane_prefix is None and "hlo_op" in _stats(ev):
                    stand_in.append((s, e, ev.name))
    if device_plane_prefix is None and stand_in:
        devices["host XLA ops (rehearsal stand-in)"] = stand_in
    return spans, devices, asyncs


def reduce(path: str, device_plane_prefix="/device:TPU:", n_devices: int | None = None) -> dict:
    spans, devices, asyncs = read_planes(path, device_plane_prefix)
    devices = {k: v for k, v in sorted(devices.items()) if v}
    if not devices:
        raise RuntimeError(f"{path}: no device operation in the trace")
    if n_devices is not None and device_plane_prefix is not None:
        if len(devices) < n_devices:
            raise RuntimeError(f"{path}: operations on {len(devices)} devices, "
                               f"{n_devices} expected")
    window = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
    if window:
        w0, w1 = window[0]
    else:
        w0 = min(s for ops in devices.values() for s, *_ in ops)
        w1 = max(e for ops in devices.values() for _, e, *_ in ops)
    op_s = defaultdict(float)
    busy, coll, exposed, per_device = [], [], [], {}
    for dev, ops in devices.items():
        ops = [(*_clip(s, e, w0, w1), n) for s, e, n in ops if e > w0 and s < w1]
        cover = union([(s, e) for s, e, _ in ops])
        busy.append(length(cover))
        per_device[dev] = cover
        for name, ns in self_times(ops):
            op_s[name] += ns
        # a collective is in flight from its start to its done: the
        # asynchronous line has that span, the operations' line the two ends
        cu = union([(s, e) for s, e, n in ops if COLLECTIVE.search(n)]
                   + [_clip(s, e, w0, w1) for s, e, n in asyncs.get(dev, ())
                      if COLLECTIVE.search(n) and e > w0 and s < w1])
        # what else ran: every non-collective operation that nests nothing
        # collective (a `while` around the whole round would hide the lot)
        others = union([(s, e) for s, e, n in ops if not COLLECTIVE.search(n)
                        and not any(s <= cs and ce <= e for cs, ce in cu)])
        coll.append(length(cu))
        exposed.append(length(subtract(cu, others)))
    nd = len(devices)
    window_ns = w1 - w0
    idlest = min(per_device, key=lambda d: length(per_device[d]))
    gaps = subtract([[w0, w1]], per_device[idlest])
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    gap_s = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [(se - ss, n) for ss, se, n in inner if ss <= mid <= se]
        gap_s[min(covering)[1] if covering else WINDOW_SPAN] += e - s
    span_s = defaultdict(lambda: [0, 0.0])
    for s, e, n in spans:
        span_s[n][0] += 1
        span_s[n][1] += (e - s) / 1e9
    rank = lambda d: sorted(([k, v / 1e9] for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / nd / 1e9,
        "idle_share": 1.0 - min(busy) / window_ns,
        "devices": list(devices),
        "device_ops": [[k, v / nd] for k, v in rank(op_s)],
        "collective_s": sum(coll) / nd / 1e9,
        "collective_exposed_s": sum(exposed) / nd / 1e9,
        "idle_gaps": rank(gap_s),
        "spans": dict(span_s),
    }


def dump(path: str, limit: int = 12) -> None:
    """What a trace holds, for reading one by hand: planes, lines, and the
    longest events of each line with their stats."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in sorted(events, key=lambda e: -e.duration_ns)[:limit]:
                print(f"    {ev.name!r} start {ev.start_ns:.0f} dur "
                      f"{ev.duration_ns:.0f} {_stats(ev)}")


if __name__ == "__main__":
    if "--dump" in sys.argv:
        dump(sys.argv[1])
    else:
        out = reduce(sys.argv[1], None if "--host" in sys.argv else "/device:TPU:")
        out["device_ops"] = out["device_ops"][:20]
        print(json.dumps(out, indent=1))
