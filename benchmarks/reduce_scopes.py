"""From a traced pass's `.xplane.pb` to what the program's own names say.

    python benchmarks/reduce_scopes.py <file.xplane.pb> [--metadata]

`reduce_xplane.py` names device operations as the compiler did
(`fusion.16 f32[2097153] kCustom`) and host time by the benchmark's own
`bench.*` spans.  This reads the same file by the names the program gives:

* **device scopes.**  The library wraps its work in `jax.named_scope`s named
  `grape.<layer>.<what>`; they reach the trace as each HLO instruction's
  `tf_op` (JAX's name stack), in the plane's *event metadata*, which
  `jax.profiler.ProfileData` does not expose.  `event_metadata()` reads it
  from the protobuf wire format (no `tensorflow` import).  An operation's
  scope is the innermost `grape.*` component of its `tf_op`; a fusion carries
  its root's.
* **host spans.**  Every `obs` span of the library is also a
  `TraceAnnotation` named `grape.<name>`, with its keyword arguments as the
  event's stats.

`reduce()` returns

  scope_s        {scope: seconds} of operation self time inside the traced
                 window, mean over devices; "" holds what carries no scope.
                 None when no operation carries one (a CPU trace, a program
                 without the scopes, an executable cached by such a program)
  scoped_share   scoped self time over all self time, or None
  spans          [[name, start_ns, end_ns, {argument: value}]] of the
                 `grape.*` host spans inside the window
  idle_by_span   [[span, seconds]]: the idle time of the idlest device by the
                 innermost `grape.*` span that covers it, else the innermost
                 `bench.*` span; a gap is cut where spans begin and end
  idle_s         that device's idle time
  batches        [{app, lanes, span_s, busy_s}] per `grape.serve_batch` span:
                 the time an operation ran on the device inside it, mean
                 over devices
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import reduce_xplane as rx  # noqa: E402

SCOPE = re.compile(r"grape\.[A-Za-z0-9_.]+")
SPAN_PREFIX = "grape."

# ---- the protobuf wire format, as far as xplane.proto needs it ----
# XSpace.planes=1; XPlane.name=2 .event_metadata=4 .stat_metadata=5 (maps:
# entry.key=1, entry.value=2); XEventMetadata.name=2 .display_name=4
# .stats=5; XStatMetadata.name=2; XStat.metadata_id=1 .str_value=5
# .ref_value=7 (the id of a stat metadata whose name is the value)


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes
    for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key = value = None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def event_metadata(path: str) -> dict:
    """{plane name: {event name: {"display_name", <stat name>: text, ...}}}
    for the stats of the event metadata that hold text (`tf_op`,
    `hlo_category`, `source`, ...)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, events, stat_names = "", [], {}
        for pno, v in _fields(plane):
            if pno == 2:
                name = _text(v)
            elif pno == 4:
                events.append(_map_entry(v)[1])
            elif pno == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (_text(x) for mno, x in _fields(meta) if mno == 2), "")
        table = {}
        for meta in events:
            entry, ev_name = {}, ""
            for mno, v in _fields(meta):
                if mno == 2:
                    ev_name = _text(v)
                elif mno == 4:
                    entry["display_name"] = _text(v)
                elif mno == 5:
                    stat = dict(_fields(v))
                    if 5 in stat or 7 in stat:  # text, or a reference to one
                        entry[stat_names.get(stat.get(1), "")] = (
                            _text(stat[5]) if 5 in stat else stat_names.get(stat[7], ""))
            table[ev_name] = entry
        out[name] = table
    return out


def scope_of(tf_op: str | None) -> str:
    """'grape.exchange.collective' from
    'jit(stepper)/while/body/grape.app.update/grape.exchange.collective/psum:'."""
    found = SCOPE.findall(tf_op or "")
    return found[-1].rstrip(".") if found else ""


# ---- the reduction ----


def _read(path: str, device_plane_prefix: str):
    """(host spans [(start, end, name, stats)], {device: [(start, end,
    whole event name)]})."""
    from jax.profiler import ProfileData

    spans, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith(device_plane_prefix)
        for line in plane.lines:
            if is_device and line.name != rx.OP_LINE:
                continue
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if is_device:
                    devices.setdefault(plane.name, []).append((s, e, ev.name))
                elif ev.name.startswith((SPAN_PREFIX, rx.SPAN_PREFIX)):
                    spans.append((s, e, ev.name, rx._stats(ev)))
    return spans, {k: v for k, v in sorted(devices.items()) if v}


def _innermost(spans: list, at: float, prefix: str) -> str | None:
    covering = [(e - s, name) for s, e, name, _ in spans
                if name.startswith(prefix) and s <= at <= e]
    return min(covering)[1] if covering else None


def idle_by_span(gaps: list, spans: list) -> dict:
    """Seconds of `gaps` by the innermost `grape.*` span over each piece
    (else the innermost `bench.*` one, else the window), a gap being cut
    wherever a span begins or ends inside it."""
    out = defaultdict(float)
    cuts = sorted({t for s, e, _, _ in spans for t in (s, e)})
    for g0, g1 in gaps:
        edges = [g0] + [t for t in cuts if g0 < t < g1] + [g1]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            owner = (_innermost(spans, mid, SPAN_PREFIX)
                     or _innermost(spans, mid, rx.SPAN_PREFIX) or rx.WINDOW_SPAN)
            out[owner] += (b - a) / 1e9
    return dict(out)


def reduce(path: str, device_plane_prefix: str = "/device:TPU:") -> dict:
    spans, devices = _read(path, device_plane_prefix)
    if not devices:
        raise RuntimeError(f"{path}: no device operation in the trace")
    window = [(s, e) for s, e, name, _ in spans if name == rx.WINDOW_SPAN]
    if window:
        w0, w1 = window[0]
    else:
        w0 = min(s for ops in devices.values() for s, *_ in ops)
        w1 = max(e for ops in devices.values() for _, e, *_ in ops)
    spans = [sp for sp in spans if sp[1] > w0 and sp[0] < w1 and sp[2] != rx.WINDOW_SPAN]
    meta = event_metadata(path)
    scope_ns, cover = defaultdict(float), {}
    for dev, ops in devices.items():
        names = meta.get(dev, {})
        ops = [(max(s, w0), min(e, w1), scope_of(names.get(n, {}).get("tf_op")))
               for s, e, n in ops if e > w0 and s < w1]
        cover[dev] = rx.union([(s, e) for s, e, _ in ops])
        for scope, ns in rx.self_times(ops):
            scope_ns[scope] += ns
    nd = len(devices)
    total = sum(scope_ns.values())
    scoped = total - scope_ns.get("", 0.0)
    idlest = min(cover, key=lambda d: rx.length(cover[d]))
    gaps = rx.subtract([[w0, w1]], cover[idlest])
    batches = []
    for s, e, name, stats in spans:
        if name != SPAN_PREFIX + "serve_batch":
            continue
        busy = sum((e - s) - rx.length(rx.subtract([[s, e]], c))
                   for c in cover.values()) / nd
        batches.append({"app": stats.get("app"), "lanes": int(stats.get("batch", 0)),
                        "span_s": (e - s) / 1e9, "busy_s": busy / 1e9})
    by_span = idle_by_span(gaps, spans)
    return {
        "scope_s": ({k: v / nd / 1e9 for k, v in scope_ns.items()} if scoped else None),
        "scoped_share": scoped / total if scoped else None,
        "spans": [[name, s, e, stats] for s, e, name, stats in sorted(spans)
                  if name.startswith(SPAN_PREFIX)],
        "idle_by_span": sorted(([k, v] for k, v in by_span.items()), key=lambda kv: -kv[1]),
        "idle_s": rx.length(gaps) / 1e9,
        "batches": batches,
    }


# ---- what the metric readers call ----


def for_run(run) -> dict | None:
    """The reduction of this run's traced pass, found where `run.trace_pass`
    wrote it; made once per run.  None without a traced pass on the chip."""
    if "scopes" not in run.__dict__:
        run.scopes = None
        files = sorted(glob.glob(os.path.join(
            HERE, "cache", "traces", f"{run.cell['name']}-seed{run.seed}",
            "plugins", "profile", "*", "*.xplane.pb")))
        if run.trace and files and run.devices[0].platform == "tpu":
            run.scopes = reduce(files[-1])
            run.log("idle by grape.* span (s): " + ", ".join(
                f"{k} {v:.4f}" for k, v in run.scopes["idle_by_span"][:12])
                + f"; of {run.scopes['idle_s']:.4f} idle")
    return run.scopes


def scope_seconds(run, scopes) -> float | None:
    """Self time under the given scopes, mean over devices; None when the
    trace's operations carry no scope at all."""
    red = for_run(run)
    if not red or red["scope_s"] is None:
        return None
    return sum(red["scope_s"].get(s, 0.0) for s in scopes)


def span_seconds(run, names) -> list | None:
    """Durations of the host spans of these names, in trace order; None when
    the program emitted none of them."""
    red = for_run(run)
    found = [(e - s) / 1e9 for name, s, e, _ in (red["spans"] if red else ())
             if name in names]
    return found or None


def idle_seconds(run, names) -> float | None:
    """Idle time of the idlest device under the given host spans; None when
    the program emitted no `grape.*` span."""
    red = for_run(run)
    if not red or not red["spans"]:
        return None
    return sum(v for k, v in red["idle_by_span"] if k in names)


if __name__ == "__main__":
    if "--metadata" in sys.argv:
        for plane, table in event_metadata(sys.argv[1]).items():
            for name, entry in table.items():
                if "tf_op" in entry:
                    print(plane, "|", entry.get("display_name"), "|",
                          entry.get("hlo_category"), "|", entry["tf_op"], "|",
                          entry.get("source"))
    else:
        print(json.dumps(reduce(sys.argv[1]), indent=1))
