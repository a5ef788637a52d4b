"""Fleet budget: price resident sessions, decide admission/eviction.

A serving fleet multiplexes N resident (graph x app) sessions over ONE
HBM budget.  This module is the only place the tenancy trade-off
lives, and it prices footprints from the ledgers that already exist
rather than inventing a new byte model:

  * **fragment bytes** — the stacked device CSRs + per-vertex planes,
    priced from their HOST twins (`ShardedEdgecutFragment.host_oe/ie`,
    the same geometry `_check_hbm_budget` bills at load time), so an
    EVICTED session prices identically to a resident one;
  * **plan-stream bytes** — every spgemm plan resolved for the
    fragment (`spgemm_pack._frag_cache`), the `host_streams` tables
    the dispatch ships as ephemeral state;
  * **overlay bytes** — the dyn delta overlay's dense
    [fnum, capacity] side planes (dyn/ingest.py);
  * **runner bytes** — the resident workers' retained result carries
    (`Worker._result_state`), the buffers `Worker.release_buffers`
    drops on eviction.

Admission is SparseP-style cost-model-driven, not a hand-tuned
watermark: `FleetBudget.admit` fits the priced footprint under the
capacity (GRAPE_FLEET_HBM_BYTES, default GRAPE_HBM_BYTES, default one
v5e chip's 16 GiB; 0 disables like the loader's gate) and, when it
does not fit, evicts **cost-weighted LRU** victims — the resident
maximizing `idle_seconds * freeable_bytes / weight` goes first, so
cold, large, low-priority tenants pay before hot or heavy-weighted
ones.  Fragments SHARED between residents are billed once and are
only freeable when their last resident leaves.  Every decision —
admit, evict, re-admit, reject — is recorded in `FLEET_STATS` with
its prices, in the PARTITION_STATS/PUMP_STATS recorded-decision
style: a fleet that silently thrashed or refused a tenant is visible
in one dict instead of a wall-clock mystery.

docs/FLEET.md is the user guide.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from libgrape_lite_tpu.ops.calibration import default_profile

#: capacity env knob; falls back to the loader's GRAPE_HBM_BYTES gate
FLEET_HBM_ENV = "GRAPE_FLEET_HBM_BYTES"
#: one chip's HBM, from the shared RateProfile (pinned: one v5e)
DEFAULT_HBM_BYTES = default_profile().hbm_capacity_bytes


class FleetStats:
    """Every fleet decision, counted and bounded (the PUMP_STATS
    discipline applied to tenancy/routing): admissions, evictions,
    re-admissions, rejections, drains — each with the prices/reasons
    that drove it."""

    MAX_EVENTS = 256

    def __init__(self):
        self.admits = 0
        self.evictions = 0
        self.readmits = 0
        self.rejects = 0
        self.drains = 0
        self.rejoins = 0
        self.events: List[dict] = []

    def _record(self, ev: dict) -> None:
        self.events.append(ev)
        if len(self.events) > self.MAX_EVENTS:
            del self.events[: self.MAX_EVENTS // 2]

    def record(self, kind: str, **detail) -> None:
        if kind == "admit":
            self.admits += 1
        elif kind == "evict":
            self.evictions += 1
        elif kind == "readmit":
            self.readmits += 1
        elif kind == "reject":
            self.rejects += 1
        elif kind == "drain":
            self.drains += 1
        elif kind == "rejoin":
            self.rejoins += 1
        self._record({"kind": kind, **detail})

    def snapshot(self) -> dict:
        return {
            "admits": self.admits, "evictions": self.evictions,
            "readmits": self.readmits, "rejects": self.rejects,
            "drains": self.drains, "rejoins": self.rejoins,
        }

    def reset(self) -> None:
        self.__init__()


#: module-level record shared by every budget/manager/router in the
#: process (like PUMP_STATS): tests/bench read it, reset() between runs
FLEET_STATS = FleetStats()

# federated as "fleet" (obs/federation.py): the class keeps its own
# snapshot()/reset() protocol; the federation just routes to it
from libgrape_lite_tpu.obs import federation as _federation  # noqa: E402

_federation.register("fleet", FLEET_STATS.snapshot, FLEET_STATS.reset,
                     module=__name__)


# ---- footprint pricing ----------------------------------------------------


def fragment_bytes(frag) -> int:
    """Device bytes of one sharded fragment, priced from the host CSR
    twins (identical shapes/dtypes to the stacked device arrays), so
    the price is the same whether the fragment is currently resident
    or evicted.  Undirected fragments alias ie onto oe and pay once,
    like the device build.

    A vertex-cut (2-D SUMMA) fragment is priced from its host tile
    buffers instead: its `host_ie`/`host_oe` are DERIVED per-tile COO
    views that never ship to the device, so pricing them would charge
    the fleet for bytes that are never placed."""
    tiles = getattr(frag, "_host_tiles", None)
    if tiles is not None:
        # what is placed: the tiles' pull CSRs where that is the
        # device form, else the COO tiles themselves
        placed = getattr(frag, "_host_pull", None) or tiles
        total = sum(a.nbytes for a in placed if a is not None)
        # per-device vertex planes: carry mask [k*vc] (bool) on the
        # row axis + oid plane (i64) + ivnum scalar per tile
        k, vc = frag.k, frag.vc
        total += k * k * (k * vc) * 1 + frag.fnum * (8 * frag.vp + 4)
        return int(total)

    def csr(csrs):
        b = 0
        for c in csrs:
            b += c.indptr.nbytes + c.edge_src.nbytes
            b += c.edge_nbr.nbytes + c.edge_mask.nbytes
            if c.edge_w is not None:
                b += c.edge_w.nbytes
        return b

    total = csr(frag.host_oe)
    aliased = frag.host_ie is frag.host_oe
    if not aliased:
        total += csr(frag.host_ie)
    # ivnum + inner_mask + oids(i64) + degree plane(s)
    fnum, vp = frag.fnum, frag.vp
    total += fnum * 4 + fnum * vp * (1 + 8 + 4 + (0 if aliased else 4))
    return total


def plan_stream_bytes(frag) -> int:
    """Bytes of every spgemm plan resolved for `frag` — the
    `host_streams` tables the dispatch ships as ephemeral state
    leaves (`SpGemmPlan` entries of the per-fragment cache)."""
    from libgrape_lite_tpu.ops.spgemm_pack import _frag_cache

    seen, total = set(), 0
    for plan in _frag_cache(frag).values():
        streams = getattr(plan, "host_streams", None)
        if not isinstance(streams, dict) or id(plan) in seen:
            continue
        seen.add(id(plan))
        total += sum(
            v.nbytes for v in streams.values() if hasattr(v, "nbytes")
        )
    return total


def overlay_bytes(frag) -> int:
    """Bytes of the attached dyn delta overlay's dense side planes."""
    ov = getattr(frag, "dyn_overlay", None)
    if ov is None:
        return 0
    sides = [ov.ie] if ov.oe is ov.ie else [ov.ie, ov.oe]
    return sum(
        s.src.nbytes + s.nbr.nbytes + s.w.nbytes + s.mask.nbytes
        for s in sides
    )


def runner_bytes(session) -> int:
    """Device bytes retained by the session's resident workers — the
    last result carries `Worker.release_buffers` drops on eviction."""
    total = 0
    for w in getattr(session, "_workers", {}).values():
        st = getattr(w, "_result_state", None)
        if isinstance(st, dict):
            total += sum(
                v.nbytes for v in st.values() if hasattr(v, "nbytes")
            )
    return total


@dataclass
class Footprint:
    """One resident target's priced device footprint.  `frag_keys`
    identifies the fragment objects so the budget can bill a SHARED
    fragment once across tenants (and refuse to free it while a
    sibling still serves from it)."""

    frag_bytes: int = 0
    plan_bytes: int = 0
    overlay_bytes: int = 0
    runner_bytes: int = 0
    frag_keys: Dict[int, int] = field(default_factory=dict)  # id -> bytes

    @property
    def total(self) -> int:
        return (self.frag_bytes + self.plan_bytes
                + self.overlay_bytes + self.runner_bytes)

    @property
    def private_bytes(self) -> int:
        """Everything except the (possibly shared) fragment arrays."""
        return self.total - self.frag_bytes

    def as_dict(self) -> dict:
        return {
            "frag_bytes": self.frag_bytes,
            "plan_bytes": self.plan_bytes,
            "overlay_bytes": self.overlay_bytes,
            "runner_bytes": self.runner_bytes,
            "total": self.total,
        }


def session_footprint(session) -> Footprint:
    """Price one ServeSession from the existing ledgers (see module
    docstring for the four components)."""
    frag = session.fragment
    fb = fragment_bytes(frag)
    return Footprint(
        frag_bytes=fb,
        plan_bytes=plan_stream_bytes(frag),
        overlay_bytes=overlay_bytes(frag),
        runner_bytes=runner_bytes(session),
        frag_keys={id(frag): fb},
    )


def target_footprint(target) -> Footprint:
    """Price a tenancy target: a ServeSession, or a FleetRouter whose
    replicas are priced per replica session (each replica holds its
    own fragment copy, so nothing dedupes here unless replicas share)."""
    replicas = getattr(target, "replicas", None)
    if replicas is None:
        return session_footprint(target)
    out = Footprint()
    for r in replicas:
        fp = session_footprint(r.session)
        out.plan_bytes += fp.plan_bytes
        out.overlay_bytes += fp.overlay_bytes
        out.runner_bytes += fp.runner_bytes
        for k, b in fp.frag_keys.items():
            if k not in out.frag_keys:
                out.frag_keys[k] = b
                out.frag_bytes += b
    return out


# ---- the budget -----------------------------------------------------------


@dataclass
class _Resident:
    footprint: Footprint
    weight: float
    last_use: float
    evictable: bool


class FleetBudget:
    """Admission/eviction under one HBM byte budget (see module
    docstring for the policy).  The budget only DECIDES; releasing the
    actual device buffers is the caller's job via the `evict` callback
    (FleetManager points it at `ServeSession.release_device`)."""

    def __init__(self, capacity_bytes: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        if capacity_bytes is None:
            capacity_bytes = int(os.environ.get(
                FLEET_HBM_ENV,
                os.environ.get("GRAPE_HBM_BYTES", DEFAULT_HBM_BYTES),
            ))
        self.capacity = int(capacity_bytes)  # 0 = unlimited
        self._clock = clock
        self.residents: Dict[str, _Resident] = {}

    # ---- accounting -------------------------------------------------------

    def used_bytes(self) -> int:
        """Total resident bytes with shared fragments billed once."""
        total, seen = 0, set()
        for r in self.residents.values():
            total += r.footprint.private_bytes
            for k, b in r.footprint.frag_keys.items():
                if k not in seen:
                    seen.add(k)
                    total += b
        return total

    def _freeable_bytes(self, name: str) -> int:
        """Bytes actually recovered by evicting `name`: its private
        bytes plus any of its fragments no OTHER resident shares."""
        r = self.residents[name]
        freeable = r.footprint.private_bytes
        for k, b in r.footprint.frag_keys.items():
            shared = any(
                k in o.footprint.frag_keys
                for n, o in self.residents.items() if n != name
            )
            if not shared:
                freeable += b
        return freeable

    def _marginal_bytes(self, footprint: Footprint) -> int:
        """Admission cost of a footprint given what is already
        resident (shared fragments are already paid for)."""
        cost = footprint.private_bytes
        for k, b in footprint.frag_keys.items():
            shared = any(
                k in r.footprint.frag_keys
                for r in self.residents.values()
            )
            if not shared:
                cost += b
        return cost

    def touch(self, name: str) -> None:
        if name in self.residents:
            self.residents[name].last_use = self._clock()

    # ---- decisions --------------------------------------------------------

    def _pick_victim(self) -> Optional[str]:
        """Cost-weighted LRU: the evictable resident maximizing
        idle_seconds * freeable_bytes / weight (ties: insertion
        order).  None when nothing can be evicted."""
        now = self._clock()
        best, best_score = None, -1.0
        for name, r in self.residents.items():
            if not r.evictable:
                continue
            idle = max(now - r.last_use, 1e-9)
            score = idle * self._freeable_bytes(name) / max(r.weight, 1e-9)
            if score > best_score:
                best, best_score = name, score
        return best

    def admit(self, name: str, footprint: Footprint, *,
              weight: float = 1.0, evictable: bool = True,
              evict: Optional[Callable[[str], None]] = None) -> dict:
        """Admit `name` under the budget, evicting cost-weighted-LRU
        victims as needed (each via the `evict` callback, then
        released here).  Returns the recorded decision dict; a reject
        (nothing left to evict and still over budget) is recorded AND
        returned with admitted=False — never silent, the caller
        decides whether to raise."""
        # re-pricing an already-resident tenant: pop the old entry so
        # the marginal cost computes fresh, but KEEP it around — a
        # reject must put it back (the tenant is still resident at
        # its old footprint; dropping it would under-count used_bytes
        # forever after)
        prior = self.residents.pop(name, None)
        readmit = prior is not None
        evicted: List[dict] = []
        while (self.capacity
               and self.used_bytes() + self._marginal_bytes(footprint)
               > self.capacity):
            victim = self._pick_victim()
            if victim is None:
                if prior is not None:
                    self.residents[name] = prior
                decision = {
                    "admitted": False, "name": name,
                    "asked_bytes": footprint.total,
                    "used_bytes": self.used_bytes(),
                    "capacity": self.capacity,
                    "evicted": evicted,
                    "reason": "over budget with no evictable resident",
                }
                FLEET_STATS.record("reject", **decision)
                return decision
            freed = self._freeable_bytes(victim)
            if evict is not None:
                evict(victim)
            del self.residents[victim]
            ev = {"name": victim, "freed_bytes": freed,
                  "for": name}
            evicted.append(ev)
            FLEET_STATS.record("evict", **ev)
        self.residents[name] = _Resident(
            footprint=footprint, weight=float(weight),
            last_use=self._clock(), evictable=evictable,
        )
        decision = {
            "admitted": True, "name": name,
            "bytes": footprint.total,
            "used_bytes": self.used_bytes(),
            "capacity": self.capacity,
            "evicted": evicted,
        }
        FLEET_STATS.record("readmit" if readmit else "admit", **decision)
        return decision

    def release(self, name: str, reason: str = "release") -> None:
        if name in self.residents:
            freed = self._freeable_bytes(name)
            del self.residents[name]
            FLEET_STATS.record(
                "evict", name=name, freed_bytes=freed, reason=reason,
            )

    def snapshot(self) -> dict:
        return {
            "capacity": self.capacity,
            "used_bytes": self.used_bytes(),
            "residents": {
                n: {**r.footprint.as_dict(), "weight": r.weight,
                    "evictable": r.evictable}
                for n, r in self.residents.items()
            },
        }
