"""Flight recorder — an always-cheap ring of recent events that dumps
a correlated postmortem bundle when something goes wrong.

The ring (``deque(maxlen=…)``, default 512) costs one append per
``record()`` whether or not anything ever breaks; there is no arming
step, so the events leading INTO a failure are already captured when
the failure fires.  Three triggers dump:

* a guard breach (guard/monitor.py ``_policy`` — carries the guard's
  forensic bundle),
* a fleet fence violation (fleet/router.py ``_check_fence``),
* a deadline storm (serve/queue.py — more than
  ``DEADLINE_STORM_THRESHOLD`` queries expired in one sweep).

A dump is written only when a sink is configured
(``GRAPE_POSTMORTEM=<dir>`` or ``set_sink()``); triggers without a
sink still count in the federated ``recorder`` namespace, so a scrape
shows that postmortem-worthy moments happened even when nobody kept
the bundles.  Triggers never raise: the recorder is a measurement
plane, not a control path.

Bundle schema (``grape-postmortem-v1``, rendered by the CLI
``postmortem`` subcommand):

* ``reason`` / ``detail`` — what tripped the dump,
* ``trace_id`` / ``wall_anchor`` — correlation to the Chrome trace,
* ``events`` — the recorder's own ring (admission/dispatch/…
  breadcrumbs),
* ``spans`` / ``instants`` — the last-N buffered tracer events,
  VERBATIM: tracer buffers hold final export-form dicts (µs
  timestamps), so each bundle span row is byte-identical to the same
  row in the flushed Chrome trace's ``traceEvents`` — the postmortem
  and the timeline can be joined row-for-row,
* ``federation`` — the full stats-federation snapshot (plan/spgemm/
  partition/pump/fleet/slo/recorder ledgers),
* ``guard`` — the guard bundle when the trigger was a breach,
* ``extra`` — trigger-specific context (fence versions, expired ids).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from libgrape_lite_tpu.obs.federation import FederatedStats

POSTMORTEM_ENV = "GRAPE_POSTMORTEM"
RING_CAPACITY = 512
BUNDLE_SPANS = 256
DEADLINE_STORM_THRESHOLD = 8
BUNDLE_SCHEMA = "grape-postmortem-v1"

REC_STATS = FederatedStats("recorder", {
    "recorded": 0,
    "dropped": 0,
    "triggers": 0,
    "dumps": 0,
    "last_reason": None,
})


class FlightRecorder:
    """Bounded ring of breadcrumbs + the postmortem dump path."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._sink: Optional[str] = None
        self._seq = 0

    # ---- always-cheap side ----------------------------------------------

    def record(self, kind: str, **detail) -> None:
        """One breadcrumb: a dict append into a bounded deque.  The
        deque drops the oldest entry itself; the drop counter keeps
        the loss visible on a scrape."""
        if len(self._ring) == self._ring.maxlen:
            REC_STATS["dropped"] += 1
        self._ring.append({
            "kind": kind, "t_ns": time.perf_counter_ns(), **detail,
        })
        REC_STATS["recorded"] += 1

    def events(self) -> List[dict]:
        return list(self._ring)

    # ---- dump side -------------------------------------------------------

    def set_sink(self, path: Optional[str]) -> None:
        """Directory bundles are written to (None → env only)."""
        self._sink = path

    def sink(self) -> Optional[str]:
        return self._sink or os.environ.get(POSTMORTEM_ENV) or None

    def build_bundle(self, reason: str,
                     extra: Optional[Dict[str, Any]] = None,
                     guard: Optional[Dict[str, Any]] = None) -> dict:
        from libgrape_lite_tpu import obs
        from libgrape_lite_tpu.obs import federation

        spans: List[dict] = []
        instants: List[dict] = []
        trace_id = None
        wall_anchor = None
        try:
            if obs.armed():
                trace_id = obs.trace_id()
                tr = obs.tracer()
                wall_anchor = tr.wall_anchor()
                # history events are the final export-form dicts —
                # copied by reference so a bundle row serializes
                # byte-identically to the same traceEvents row
                for ev in obs.history():
                    ph = ev.get("ph")
                    if ph == "X":
                        spans.append(ev)
                    elif ph == "i":
                        instants.append(ev)
                spans = spans[-BUNDLE_SPANS:]
                instants = instants[-BUNDLE_SPANS:]
        except Exception:  # never let forensics kill the patient
            pass
        try:
            fed = federation.snapshot()
        except Exception:
            fed = {}
        bundle = {
            "schema": BUNDLE_SCHEMA,
            "reason": reason,
            "trace_id": trace_id,
            "wall_anchor": wall_anchor,
            "events": self.events(),
            "spans": spans,
            "instants": instants,
            "federation": fed,
            "guard": guard,
            "extra": extra or {},
        }
        try:
            from libgrape_lite_tpu.obs.metrics import gang_identity

            rank, nprocs = gang_identity()
            if nprocs > 1:
                # who dumped this shard; single-process manifests stay
                # byte-identical to the pre-gang schema
                bundle["rank"] = rank
                bundle["nprocs"] = nprocs
        except Exception:
            pass
        return bundle

    def trigger(self, reason: str,
                extra: Optional[Dict[str, Any]] = None,
                guard: Optional[Dict[str, Any]] = None,
                incident: Optional[str] = None,
                filename: Optional[str] = None,
                ) -> Optional[str]:
        """Count the postmortem-worthy moment; dump a bundle when a
        sink is configured.  Returns the bundle path or None.  Never
        raises.

        `incident` stamps a gang-shared incident id into the bundle;
        `filename` overrides the default `postmortem_<reason>_<seq>`
        name (relative to the sink — obs/gang.py uses
        `incident_<id>/rank_<r>.json` so every rank's shard of one
        incident lands in one directory)."""
        try:
            REC_STATS["triggers"] += 1
            REC_STATS["last_reason"] = reason
            sink = self.sink()
            if not sink:
                return None
            bundle = self.build_bundle(reason, extra=extra,
                                       guard=guard)
            if incident:
                bundle["incident"] = incident
            with self._lock:
                self._seq += 1
                seq = self._seq
            os.makedirs(sink, exist_ok=True)
            if filename:
                path = os.path.join(sink, filename)
                os.makedirs(os.path.dirname(path), exist_ok=True)
            else:
                safe = "".join(
                    c if c.isalnum() or c in "-_" else "_"
                    for c in reason
                )
                path = os.path.join(
                    sink, f"postmortem_{safe}_{seq:03d}.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(bundle, fh, indent=1, sort_keys=False,
                          default=str)
                fh.write("\n")
            os.replace(tmp, path)
            REC_STATS["dumps"] += 1
            try:
                from libgrape_lite_tpu import obs

                obs.tracer().instant(
                    "postmortem", reason=reason, path=path,
                )
            except Exception:
                pass
            return path
        except Exception:
            return None


RECORDER = FlightRecorder()
