"""Priced per-query admission: shed or defer tenants past budget.

`fleet/budget.py` already prices SESSIONS from the plan ledgers
(SparseP discipline: price from a cost model, never hand-tune a
watermark).  This module extends the same ledger geometry to
INDIVIDUAL queries: one round of a point query costs what the
fragment's resolved plans say they move/compute (their `ledger`
totals), scaled by the round limit — so the
admission controller knows what a request will cost BEFORE the fleet
pays for it.

The decide step is a pure function over (tenant burn, priced cost):

  * burn below `defer_burn`      -> admit;
  * past budget but under
    `shed_burn` (and affordable) -> **defer**: the request stays
    queued, but `AdmissionQueue._head_batch` serves in-budget tenants
    first — deferred work re-queues BEHIND them, never starves
    (an all-deferred queue still drains);
  * at/over `shed_burn`, or an
    over-budget tenant's request
    pricier than `max_cost`      -> **shed**: a loud failed
    ServeResult with ``reason=shed_over_budget``, counted and
    returned through `take_expired` exactly like `deadline_expired`
    — and it burns the tenant's error budget via `slo.observe`, like
    every other failure (the PR's queue.py bugfix).

Every decision is recorded in the federated ``autopilot`` namespace
(signals.record_decision), never silent.  docs/AUTOPILOT.md covers
the pricing model and tuning.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from libgrape_lite_tpu.autopilot.signals import (
    AUTOPILOT_STATS,
    record_decision,
)

#: rounds assumed for an unbounded request (max_rounds=None) — the
#: pricing must stay finite; callers with a real limit are priced
#: exactly
DEFAULT_PRICED_ROUNDS = 16


def _plan_totals(fragment) -> list:
    """Ledger totals of every plan resolved for `fragment` (the
    per-fragment cache of ops/spgemm_pack.py)."""
    from libgrape_lite_tpu.ops.spgemm_pack import _frag_cache

    out = []
    for plan in _frag_cache(fragment).values():
        led = getattr(plan, "ledger", None)
        if isinstance(led, dict) and isinstance(led.get("totals"), dict):
            out.append(led["totals"])
    return out


def query_cost(fragment, max_rounds: Optional[int] = None) -> float:
    """Estimated cost of one point query on `fragment`, in
    HBM-bytes-per-query: the resolved plans' per-round ledger bytes
    times the round limit.  Falls back to the fragment's CSR byte size
    per round when no plan has been resolved (the default pull
    resolves none)."""
    rounds = int(max_rounds) if max_rounds else DEFAULT_PRICED_ROUNDS
    per_round = max(
        (float(t.get("hbm_bytes", 0)) for t in _plan_totals(fragment)),
        default=0.0,
    )
    if per_round <= 0.0:
        from libgrape_lite_tpu.fleet.budget import fragment_bytes

        per_round = float(fragment_bytes(fragment))
    AUTOPILOT_STATS["priced"] += 1
    return per_round * rounds


def query_wall_s(fragment, max_rounds: Optional[int] = None,
                 profile=None) -> float:
    """Estimated WALL seconds of one point query on `fragment` under
    `profile` (default: the active RateProfile) — the widest resolved
    plan's full ledger columns priced through the profile's additive
    wall model, times the round limit.  0.0 when no plan has been
    resolved (the byte fallback has no op columns to price);
    byte-based `query_cost` stays the load-shaped metric, this is the
    latency-shaped one a fitted profile keeps honest."""
    from libgrape_lite_tpu.ops.calibration import active_profile

    p = profile or active_profile()
    rounds = int(max_rounds) if max_rounds else DEFAULT_PRICED_ROUNDS
    best = max((p.wall_s(t) for t in _plan_totals(fragment)),
               default=0.0)
    return best * rounds


@dataclass(frozen=True)
class AdmissionConfig:
    """Thresholds of the shed/defer policy (docs/AUTOPILOT.md)."""

    # burn >= 1.0 means the error budget is spent; defer starts there
    defer_burn: float = 1.0
    # a tenant burning at 2x budget no longer gets device time at all
    shed_burn: float = 2.0
    # optional absolute cost ceiling (HBM bytes/query): an OVER-BUDGET
    # tenant's request pricier than this sheds instead of deferring —
    # in-budget tenants are never cost-gated (None disables)
    max_cost: Optional[float] = None
    # optional absolute WALL ceiling (seconds/query, priced from the
    # active RateProfile via `query_wall_s`): same over-budget-only
    # semantics as max_cost (None disables — the shipped default)
    max_cost_s: Optional[float] = None

    def __post_init__(self):
        if self.defer_burn <= 0:
            raise ValueError(
                f"defer_burn must be > 0, got {self.defer_burn}"
            )
        if self.shed_burn < self.defer_burn:
            raise ValueError(
                f"shed_burn ({self.shed_burn}) must be >= defer_burn "
                f"({self.defer_burn})"
            )


def decide_admission(burn: float, cost: float,
                     cfg: AdmissionConfig,
                     cost_s: float = 0.0) -> str:
    """Pure decide: 'admit' | 'defer' | 'shed' for one request of a
    tenant burning `burn` with priced cost `cost` (HBM bytes) and
    modeled wall `cost_s` (seconds, 0.0 = unpriced)."""
    if burn < cfg.defer_burn:
        return "admit"
    if burn >= cfg.shed_burn:
        return "shed"
    if cfg.max_cost is not None and cost > cfg.max_cost:
        return "shed"
    if cfg.max_cost_s is not None and cost_s > cfg.max_cost_s:
        return "shed"
    return "defer"


class AdmissionController:
    """The queue-side hook: `review(req)` prices one pending request,
    reads its tenant's burn from the SLO surface, and returns the
    decide verdict.  Wire with ``queue.admission = ctl.review`` (the
    ServeSession/FleetRouter attach helpers do this) — the queue's
    `_pop_ready` sweep then sheds/defers before coalescing.

    `cost_of` defaults to `query_cost` over `fragment`; pass a
    callable for tests (pure decide tables need no fragment)."""

    def __init__(self, config: Optional[AdmissionConfig] = None,
                 fragment=None,
                 cost_of: Optional[Callable] = None):
        self.config = config or AdmissionConfig()
        self._fragment = fragment
        self._cost_of = cost_of

    def burn_of(self, tenant: Optional[str]) -> float:
        """Current burn of one tenant's objective key (0.0 when the
        tenant has no objective or nothing was observed yet)."""
        from libgrape_lite_tpu.obs.slo import SLO_STATS

        if tenant is None:
            return 0.0
        burn = SLO_STATS.get("burn_by_key") or {}
        return float(burn.get(f"tenant:{tenant}", 0.0))

    def cost_of(self, req) -> float:
        if self._cost_of is not None:
            return float(self._cost_of(req))
        if self._fragment is None:
            return 0.0
        return query_cost(self._fragment, req.max_rounds)

    def wall_of(self, req, profile) -> float:
        if self._cost_of is not None or self._fragment is None:
            return 0.0
        return query_wall_s(self._fragment, req.max_rounds,
                            profile=profile)

    def review(self, req) -> str:
        """'admit' | 'defer' | 'shed' for one queued request.  Records
        shed/defer decisions (admits are the steady state and only
        counted implicitly); never raises — an admission failure must
        not wedge the queue head."""
        from libgrape_lite_tpu.ops.calibration import active_profile

        try:
            prof = active_profile()
            burn = self.burn_of(req.tenant)
            cost = self.cost_of(req)
            cost_s = self.wall_of(req, prof)
            verdict = decide_admission(burn, cost, self.config,
                                       cost_s=cost_s)
        except Exception:
            return "admit"
        if verdict != "admit":
            record_decision(
                verdict, tenant=req.tenant or "", app=req.app_key,
                burn=round(burn, 4), cost=round(cost, 1),
                cost_s=round(cost_s, 6), profile=prof.label(),
            )
        return verdict
