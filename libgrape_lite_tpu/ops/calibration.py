"""Self-calibrating cost ledger (r17, ROADMAP item 3).

Every auto-selector in the stack prices its decision from a cost
model — the 1-D/2-D partition ledger (fragment/partition.py), the
``GRAPE_LCC_BACKEND=auto`` intersect-vs-spgemm choice
(ops/spgemm_pack.py), autopilot admission (autopilot/admission.py)
and the fleet HBM budget (fleet/budget.py).
Until r17 each carried its own private copy of the hand-pinned v5e
rates; this module makes ONE :class:`RateProfile` the single source of pricing
constants, and adds the machinery to *fit* those rates from measured
device walls instead of faith (the SparseP discipline: measured-rate-
driven selection, applied to the whole selector family):

* :func:`default_profile` — the ``"v5e-pinned"`` profile, bit-for-bit
  the constants every consumer shipped with through r16.  With no
  profile configured nothing changes: every decision and every
  byte-identity pin is unchanged by construction.
* :func:`active_profile` — the profile consumers price from:
  ``GRAPE_RATE_PROFILE=<path>`` loads a schema-validated JSON profile
  (a bad file is a LOUD error, never a silent fallback to pinned).
* :func:`fit_rates` — weighted least squares over measured samples:
  the ledger recount columns (``vpu_ops`` / ``mxu_ops`` /
  ``gather_rows`` / ``hbm_bytes``) are the regressors, the
  sync-before-close wall is the response.  The recount discipline
  means the design matrix is *exact* — the fit's only noise is the
  wall measurement.  Ill-conditioned sample sets FAIL loudly
  (:class:`CalibrationError`); the fitter never silently
  extrapolates a rate the samples cannot identify.
* :func:`microbench_samples` — the seeded sweep: real jitted
  masked-SpGEMM dispatches across a small geometry grid, walls taken
  sync-before-close (``block_until_ready``), regressors read from each plan's shipped
  op-budget ledger.
* :func:`harvest_dispatch` / :func:`harvested_samples` — live
  harvest: the telemetry plane's per-dispatch ``device_us`` stage
  stamp (serve/session.py) joined to the dispatching worker's
  already-shipped ledger recount.  Armed via
  ``GRAPE_CALIBRATE_HARVEST=1``; disarmed it is one cached env read.
* :func:`drift_report` — modeled-vs-measured drift per priced
  surface under a profile; the bench ``calibration`` lane and
  ``calibrate --check`` exit 2 past :data:`DRIFT_TOLERANCE`,
  turning "the model is stale" from silent mispricing of every
  auto-selector into a failed gate.

The calibration wall model is the ADDITIVE form

    wall = dispatch_overhead + vpu/(lanes*clock) + mxu*cyc/clock
         + gather/(rows_per_cycle*clock) + hbm_bytes/hbm_bps

— conservative (no compute/HBM overlap assumed), linear in the
regressors, and therefore exactly fittable.  docs/CALIBRATION.md is
the user guide.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

PROFILE_ENV = "GRAPE_RATE_PROFILE"
HARVEST_ENV = "GRAPE_CALIBRATE_HARVEST"
PROFILE_SCHEMA_VERSION = 1

#: modeled-vs-measured drift past this fraction fails the gate
#: (the same 5% the op-budget ledger recount gates at)
DRIFT_TOLERANCE = 0.05

#: column-normalized design matrices worse than this are refused —
#: the samples cannot separate the requested rates
COND_LIMIT = 1e6

#: the regressor columns a sample may carry, in fit order
REGRESSORS = ("const", "vpu_ops", "mxu_ops", "gather_rows",
              "hbm_bytes")


class CalibrationError(RuntimeError):
    """A sample set that cannot honestly identify the requested rates
    (rank-deficient, ill-conditioned, or yielding a non-positive
    rate) or a profile file that fails schema validation."""


@dataclass(frozen=True)
class RateProfile:
    """THE pricing constants — one source, every consumer.

    The default instance IS the hand-pinned v5e model every module
    shipped with through r16; a fitted instance carries the backend
    fingerprint it was measured on plus fit provenance.  `unfitted`
    names rate fields a fit inherited from its base profile instead
    of identifying from samples (recorded, never silent)."""

    name: str = "v5e-pinned"
    clock_hz: float = 940e6            # v5e core clock
    vpu_lanes_per_cycle: float = 1024.0  # one (8,128) vreg op/cycle
    mxu_cyc_per_elem: float = 0.008    # verified tri-matmul cumsum rate
    hbm_bps: float = 819e9             # v5e HBM bandwidth
    ici_bps: float = 9e10              # ~2x45 GB/s v5e ICI links
    gather_rows_per_cycle: float = 128.0  # sublane gather, "row" point
    #: the probe's gather-rate bracket (slots/cycle): vreg = a full
    #: (8,128) vector per cycle, row = one 128-lane row per cycle,
    #: unroll = Mosaic ~8-way select fallback
    gather_rates: Dict[str, float] = field(default_factory=lambda: {
        "vreg": 1024.0, "row": 128.0, "unroll": 16.0,
    })
    #: per-exchange-mode byte rates (all ICI on the pinned profile;
    #: a fitted profile may separate them)
    exchange_bps: Dict[str, float] = field(default_factory=lambda: {
        "gather": 9e10, "mirror": 9e10, "vc2d": 9e10,
    })
    hbm_capacity_bytes: int = 16 << 30  # one v5e chip
    dispatch_overhead_s: float = 0.0   # per-dispatch fixed cost (fit)
    fingerprint: str = "pinned"        # backend it was fitted on
    fitted: bool = False
    source: str = "pinned"             # pinned | microbench | harvest
    residual: float = 0.0              # fit RMS relative error
    unfitted: Tuple[str, ...] = ()

    # ---- pricing ---------------------------------------------------------

    def wall_s(self, sample: dict) -> float:
        """The additive calibration wall model for one sample of
        ledger-recount columns (absent columns price as zero)."""
        clk = self.clock_hz
        return (
            self.dispatch_overhead_s * float(sample.get("const", 1))
            + float(sample.get("vpu_ops", 0))
            / self.vpu_lanes_per_cycle / clk
            + float(sample.get("mxu_ops", 0))
            * self.mxu_cyc_per_elem / clk
            + float(sample.get("gather_rows", 0))
            / self.gather_rows_per_cycle / clk
            + float(sample.get("hbm_bytes", 0)) / self.hbm_bps
        )

    def label(self) -> str:
        """The fingerprint label decision records carry — a decision
        made under a stale profile is attributable in
        PARTITION_STATS / SPGEMM_STATS / autopilot records."""
        return f"{self.name}@{self.fingerprint}"

    # ---- (de)serialization ----------------------------------------------

    def as_dict(self) -> dict:
        return {
            "schema": PROFILE_SCHEMA_VERSION,
            "name": self.name,
            "clock_hz": self.clock_hz,
            "vpu_lanes_per_cycle": self.vpu_lanes_per_cycle,
            "mxu_cyc_per_elem": self.mxu_cyc_per_elem,
            "hbm_bps": self.hbm_bps,
            "ici_bps": self.ici_bps,
            "gather_rows_per_cycle": self.gather_rows_per_cycle,
            "gather_rates": dict(self.gather_rates),
            "exchange_bps": dict(self.exchange_bps),
            "hbm_capacity_bytes": int(self.hbm_capacity_bytes),
            "dispatch_overhead_s": self.dispatch_overhead_s,
            "fingerprint": self.fingerprint,
            "fitted": self.fitted,
            "source": self.source,
            "residual": self.residual,
            "unfitted": list(self.unfitted),
        }

    @staticmethod
    def from_dict(d: dict) -> "RateProfile":
        errors = validate_profile(d)
        if errors:
            raise CalibrationError(
                "invalid rate profile: " + "; ".join(errors)
            )
        return RateProfile(
            name=d["name"],
            clock_hz=float(d["clock_hz"]),
            vpu_lanes_per_cycle=float(d["vpu_lanes_per_cycle"]),
            mxu_cyc_per_elem=float(d["mxu_cyc_per_elem"]),
            hbm_bps=float(d["hbm_bps"]),
            ici_bps=float(d["ici_bps"]),
            gather_rows_per_cycle=float(d["gather_rows_per_cycle"]),
            gather_rates={k: float(v)
                          for k, v in d["gather_rates"].items()},
            exchange_bps={k: float(v)
                          for k, v in d["exchange_bps"].items()},
            hbm_capacity_bytes=int(d["hbm_capacity_bytes"]),
            dispatch_overhead_s=float(d["dispatch_overhead_s"]),
            fingerprint=d["fingerprint"],
            fitted=bool(d["fitted"]),
            source=d["source"],
            residual=float(d["residual"]),
            unfitted=tuple(d.get("unfitted", [])),
        )


#: profile schema: field -> (type tuple, positivity required).  bool
#: is an int subclass and is REJECTED in every numeric field (the
#: check_bench_schema discipline).
_NUM = (int, float)
_PROFILE_FIELDS = {
    "schema": (int, False),
    "name": (str, False),
    "clock_hz": (_NUM, True),
    "vpu_lanes_per_cycle": (_NUM, True),
    "mxu_cyc_per_elem": (_NUM, True),
    "hbm_bps": (_NUM, True),
    "ici_bps": (_NUM, True),
    "gather_rows_per_cycle": (_NUM, True),
    "gather_rates": (dict, False),
    "exchange_bps": (dict, False),
    "hbm_capacity_bytes": (_NUM, True),
    "dispatch_overhead_s": (_NUM, False),  # zero is legal
    "fingerprint": (str, False),
    "fitted": (bool, False),
    "source": (str, False),
    "residual": (_NUM, False),
    "unfitted": (list, False),
}
_EXCHANGE_MODES = ("gather", "mirror", "vc2d")


def validate_profile(d) -> List[str]:
    """Schema errors for one profile dict (empty = valid): required
    fields, numeric types with bool rejected, positive rates, the
    exchange-mode keys, unknown keys are errors."""
    errors: List[str] = []
    if not isinstance(d, dict):
        return [f"profile must be a dict, got {type(d).__name__}"]
    for key, (typ, positive) in _PROFILE_FIELDS.items():
        if key not in d:
            errors.append(f"missing field {key!r}")
            continue
        v = d[key]
        if typ is not bool and isinstance(v, bool):
            errors.append(f"{key}: bool is not a number")
            continue
        if not isinstance(v, typ):
            errors.append(
                f"{key}: expected {getattr(typ, '__name__', typ)}, "
                f"got {type(v).__name__}"
            )
            continue
        if positive and not (isinstance(v, _NUM) and v > 0
                             and np.isfinite(v)):
            errors.append(f"{key}: must be a positive finite number")
    for key in d:
        if key not in _PROFILE_FIELDS:
            errors.append(f"unknown field {key!r}")
    if isinstance(d.get("schema"), int) and not isinstance(
            d.get("schema"), bool) and d["schema"] != \
            PROFILE_SCHEMA_VERSION:
        errors.append(
            f"schema {d['schema']} != {PROFILE_SCHEMA_VERSION}"
        )
    for dk in ("gather_rates", "exchange_bps"):
        sub = d.get(dk)
        if not isinstance(sub, dict):
            continue
        for k, v in sub.items():
            if isinstance(v, bool) or not isinstance(v, _NUM) \
                    or not (v > 0 and np.isfinite(v)):
                errors.append(
                    f"{dk}[{k!r}]: must be a positive finite number"
                )
        if dk == "exchange_bps":
            for mode in _EXCHANGE_MODES:
                if mode not in sub:
                    errors.append(f"exchange_bps missing mode {mode!r}")
    uf = d.get("unfitted")
    if isinstance(uf, list):
        for x in uf:
            if not isinstance(x, str):
                errors.append("unfitted entries must be strings")
                break
    return errors


_DEFAULT = RateProfile()


def default_profile() -> RateProfile:
    """The ``"v5e-pinned"`` profile — bit-for-bit the constants every
    pricing consumer shipped with through r16."""
    return _DEFAULT


def backend_fingerprint() -> str:
    """``platform:device_kind`` of device 0 — the key a persisted
    profile is valid for.  Falls back to ``unknown:unknown`` when no
    backend is reachable (a profile fitted there says so)."""
    try:
        import jax

        dev = jax.devices()[0]
        return f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    except Exception:
        return "unknown:unknown"


def save_profile(profile: RateProfile, path: str) -> str:
    """Write one schema-validated profile JSON (atomic replace)."""
    d = profile.as_dict()
    errors = validate_profile(d)
    if errors:
        raise CalibrationError(
            "refusing to save an invalid profile: " + "; ".join(errors)
        )
    dirpath = os.path.dirname(os.path.abspath(path))
    os.makedirs(dirpath, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(d, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_profile(path: str) -> RateProfile:
    """Load + schema-validate one profile JSON.  Errors are LOUD
    (CalibrationError) — a configured-but-broken profile must never
    silently downgrade every auto-selector to the pinned rates."""
    try:
        with open(path) as f:
            d = json.load(f)
    except OSError as e:
        raise CalibrationError(
            f"cannot read rate profile {path!r}: {e}"
        ) from e
    except json.JSONDecodeError as e:
        raise CalibrationError(
            f"rate profile {path!r} is not valid JSON: {e}"
        ) from e
    return RateProfile.from_dict(d)


_ACTIVE_CACHE: Dict[Tuple[str, float], RateProfile] = {}


def active_profile() -> RateProfile:
    """The profile every consumer prices from: the file named by
    ``GRAPE_RATE_PROFILE`` (mtime-memoized), else the pinned default.
    Read LIVE at every call — arming/swapping a profile mid-process
    (tests, the serve loop) must take effect on the next decision."""
    path = os.environ.get(PROFILE_ENV, "")
    if not path:
        return _DEFAULT
    try:
        key = (os.path.abspath(path), os.path.getmtime(path))
    except OSError as e:
        raise CalibrationError(
            f"GRAPE_RATE_PROFILE={path!r} is not readable: {e}"
        ) from e
    prof = _ACTIVE_CACHE.get(key)
    if prof is None:
        prof = load_profile(path)
        _ACTIVE_CACHE.clear()  # one live file; old mtimes are dead
        _ACTIVE_CACHE[key] = prof
    return prof


def profile_label(profile: Optional[RateProfile] = None) -> str:
    """Label of `profile` (default: the active one) for decision
    records."""
    return (profile or active_profile()).label()


# ---- fitting -------------------------------------------------------------

#: coefficient of regressor r, under profile p
_COEFF_OF = {
    "const": lambda p: p.dispatch_overhead_s,
    "vpu_ops": lambda p: 1.0 / (p.vpu_lanes_per_cycle * p.clock_hz),
    "mxu_ops": lambda p: p.mxu_cyc_per_elem / p.clock_hz,
    "gather_rows": lambda p: 1.0 / (p.gather_rows_per_cycle
                                    * p.clock_hz),
    "hbm_bytes": lambda p: 1.0 / p.hbm_bps,
}


def _profile_with_coeff(profile: RateProfile, reg: str,
                        coeff: float) -> RateProfile:
    clk = profile.clock_hz
    if reg == "const":
        return replace(profile, dispatch_overhead_s=coeff)
    if reg == "vpu_ops":
        return replace(profile, vpu_lanes_per_cycle=1.0 / (coeff * clk))
    if reg == "mxu_ops":
        return replace(profile, mxu_cyc_per_elem=coeff * clk)
    if reg == "gather_rows":
        rate = 1.0 / (coeff * clk)
        return replace(profile, gather_rows_per_cycle=rate,
                       gather_rates={**profile.gather_rates,
                                     "row": rate})
    if reg == "hbm_bytes":
        return replace(profile, hbm_bps=1.0 / coeff)
    raise ValueError(f"unknown regressor {reg!r}")


@dataclass(frozen=True)
class FitResult:
    profile: RateProfile
    regressors: Tuple[str, ...]
    coefficients: Dict[str, float]
    residual: float          # RMS relative error over the samples
    cond: float              # condition of the normalized design
    samples: int


def fit_rates(samples: Sequence[dict],
              regressors: Sequence[str] = ("const", "vpu_ops",
                                           "mxu_ops", "hbm_bytes"),
              base: Optional[RateProfile] = None,
              name: str = "fitted",
              source: str = "microbench") -> FitResult:
    """Weighted least squares of measured walls over ledger columns.

    Each sample: ``{"wall_s": measured, "surface": str, <columns>}``.
    Rows are weighted by ``1/wall`` so the fit minimizes RELATIVE
    error (an absolute fit lets the largest dispatch dominate and the
    small ones drift past the gate).  Columns NOT in `regressors`
    (and requested columns with no variation in the samples) are
    priced at the `base` profile's rates and subtracted from the
    response first — those rates are inherited and RECORDED in
    ``profile.unfitted``, never silently invented.

    Raises :class:`CalibrationError` when the sample set cannot
    identify the requested rates: fewer samples than live columns,
    rank deficiency / condition past :data:`COND_LIMIT`, or a fitted
    rate that comes out non-positive (collinear columns pushing mass
    onto each other).  The fitter must fail loudly, never silently
    extrapolate."""
    base = base or default_profile()
    for r in regressors:
        if r not in REGRESSORS:
            raise ValueError(f"unknown regressor {r!r}")
    samples = list(samples)
    if not samples:
        raise CalibrationError("no samples to fit")
    y = np.array([float(s["wall_s"]) for s in samples])
    if not np.all(np.isfinite(y)) or np.any(y <= 0):
        raise CalibrationError(
            "measured walls must be positive finite seconds"
        )

    def col(reg: str) -> np.ndarray:
        if reg == "const":
            return np.ones(len(samples))
        return np.array([float(s.get(reg, 0)) for s in samples])

    live = [r for r in regressors if np.any(col(r) != 0)]
    dead = [r for r in regressors if r not in live]
    inherited = [r for r in REGRESSORS
                 if r not in live and np.any(col(r) != 0)]
    if not live:
        raise CalibrationError("every requested column is zero")
    if len(samples) < len(live):
        raise CalibrationError(
            f"{len(samples)} samples cannot identify {len(live)} "
            f"rates ({', '.join(live)}) — extend the sweep"
        )
    # response minus the base-priced contribution of inherited columns
    y_adj = y.copy()
    for r in inherited:
        y_adj -= col(r) * _COEFF_OF[r](base)
    if np.any(y_adj <= 0):
        raise CalibrationError(
            "inherited-rate contributions exceed the measured walls "
            f"(inherited: {', '.join(inherited)}) — the base profile "
            "overprices these samples; fit those columns too"
        )
    A = np.stack([col(r) for r in live], axis=1)
    w = 1.0 / y  # relative-error weighting
    Aw = A * w[:, None]
    yw = y_adj * w
    norms = np.linalg.norm(Aw, axis=0)
    if np.any(norms == 0):
        raise CalibrationError("degenerate design column")
    cond = float(np.linalg.cond(Aw / norms))
    if not np.isfinite(cond) or cond > COND_LIMIT:
        raise CalibrationError(
            f"design matrix condition {cond:.3g} past {COND_LIMIT:g} "
            f"— the samples cannot separate ({', '.join(live)}); "
            "vary the geometry mix (scan modes, spgemm, sizes)"
        )
    coef_n, _, rank, _ = np.linalg.lstsq(Aw / norms, yw, rcond=None)
    if rank < len(live):
        raise CalibrationError(
            f"rank-deficient design ({rank} < {len(live)})"
        )
    coef = coef_n / norms
    for r, c in zip(live, coef):
        if r != "const" and c <= 0:
            raise CalibrationError(
                f"fitted coefficient for {r} is non-positive "
                f"({c:.3g}) — collinear samples; extend the sweep or "
                f"drop {r} from the regressors"
            )
    if "const" in live and coef[live.index("const")] <= 0:
        # a (slightly) negative intercept is measurement noise, but a
        # negative overhead must never ship in a profile — and just
        # clamping it to zero leaves the OTHER coefficients fit
        # against an intercept that no longer exists (every modeled
        # wall then overshoots by the absorbed mass), so refit the
        # model without the const column instead
        return fit_rates(
            samples,
            regressors=[r for r in regressors if r != "const"],
            base=base, name=name, source=source,
        )
    profile = base
    coeffs = {}
    for r, c in zip(live, coef):
        coeffs[r] = float(c)
        profile = _profile_with_coeff(profile, r, float(c))
    modeled = np.array([profile.wall_s(s) for s in samples])
    residual = float(np.sqrt(np.mean(((modeled - y) / y) ** 2)))
    profile = replace(
        profile, name=name, source=source, fitted=True,
        fingerprint=backend_fingerprint(), residual=residual,
        unfitted=tuple(sorted(
            r for r in set(inherited) | set(dead) if r != "const")),
    )
    return FitResult(
        profile=profile, regressors=tuple(live),
        coefficients=coeffs, residual=residual, cond=cond,
        samples=len(samples),
    )


#: the driver's regressor fallback chain: richest model first, each
#: step drops the column CPU walls most often cannot identify (HBM —
#: cached; gather — collinear with vpu on the padded plans; MXU — a
#: fixed fraction of vpu on the spgemm surface).  Dropped columns are
#: inherited + recorded, never silent.
REGRESSOR_FALLBACK: Tuple[Tuple[str, ...], ...] = (
    ("const", "vpu_ops", "mxu_ops", "gather_rows", "hbm_bytes"),
    ("const", "vpu_ops", "mxu_ops", "hbm_bytes"),
    ("const", "vpu_ops", "mxu_ops"),
    ("const", "vpu_ops"),
)


def fit_rates_auto(samples: Sequence[dict],
                   base: Optional[RateProfile] = None,
                   name: str = "fitted",
                   source: str = "microbench") -> Tuple[FitResult,
                                                        List[str]]:
    """`fit_rates` down the :data:`REGRESSOR_FALLBACK` chain: the
    richest rate set the samples can honestly identify wins.  Returns
    (fit, notes) where notes records every rejected step and why —
    the driver prints them, so a degraded fit is visible.  Raises the
    LAST step's CalibrationError when even (const, vpu) cannot fit."""
    notes: List[str] = []
    last: Optional[CalibrationError] = None
    for regs in REGRESSOR_FALLBACK:
        try:
            fit = fit_rates(samples, regressors=regs, base=base,
                            name=name, source=source)
            return fit, notes
        except CalibrationError as e:
            notes.append(f"{'+'.join(regs)}: {e}")
            last = e
    raise last  # type: ignore[misc]


def default_min_wall_s() -> float:
    """Samples with walls under this are excluded from a fit: on the
    CPU backend a sub-20ms jitted dispatch is scheduler noise, not a
    rate measurement (the padded SpMV plans land there); on real
    accelerators hardware walls are deterministic down to µs, so
    nothing is dropped."""
    try:
        import jax

        if jax.devices()[0].platform == "cpu":
            return 0.020
    except Exception:
        pass
    return 0.0


SAMPLES_SCHEMA_VERSION = 1


def save_samples(samples: Sequence[dict], path: str) -> str:
    """Persist one measured sample set (the sweep the profile was
    fitted from) — `calibrate --check --samples` and the bench
    `calibration` lane evaluate drift against the RECORDED
    measurement instead of re-racing a noisy scheduler in CI."""
    doc = {"schema": SAMPLES_SCHEMA_VERSION,
           "fingerprint": backend_fingerprint(),
           "samples": [dict(s) for s in samples]}
    dirpath = os.path.dirname(os.path.abspath(path))
    os.makedirs(dirpath, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_samples(path: str) -> List[dict]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CalibrationError(
            f"cannot read calibration samples {path!r}: {e}"
        ) from e
    if not isinstance(doc, dict) or not isinstance(
            doc.get("samples"), list):
        raise CalibrationError(
            f"calibration samples {path!r}: expected "
            "{schema, fingerprint, samples: [...]}"
        )
    out = []
    for i, s in enumerate(doc["samples"]):
        if not isinstance(s, dict) or "wall_s" not in s:
            raise CalibrationError(
                f"calibration samples {path!r}: entry {i} has no "
                "wall_s"
            )
        w = s["wall_s"]
        if isinstance(w, bool) or not isinstance(w, _NUM) or w <= 0:
            raise CalibrationError(
                f"calibration samples {path!r}: entry {i} wall_s "
                "must be a positive number"
            )
        out.append(dict(s))
    return out


def drift_report(profile: RateProfile,
                 samples: Sequence[dict]) -> dict:
    """Modeled-vs-measured drift of `profile` over `samples`, per
    priced surface (the ``surface`` tag each sample carries) and
    overall.  Per surface the drift is the AGGREGATE
    ``|sum(modeled) - sum(measured)| / sum(measured)`` — the bias the
    auto-selectors would price with; ``max_sample_drift_pct`` is
    reported for forensics but the gate rides the aggregate."""
    by: Dict[str, Dict[str, float]] = {}
    worst_sample = 0.0
    for s in samples:
        surf = s.get("surface", "unknown")
        m = profile.wall_s(s)
        t = float(s["wall_s"])
        e = by.setdefault(surf, {"modeled_s": 0.0, "measured_s": 0.0,
                                 "samples": 0})
        e["modeled_s"] += m
        e["measured_s"] += t
        e["samples"] += 1
        worst_sample = max(worst_sample, abs(m - t) / t)
    max_drift = 0.0
    for surf, e in by.items():
        drift = (abs(e["modeled_s"] - e["measured_s"])
                 / max(e["measured_s"], 1e-12))
        e["drift_pct"] = round(drift * 100.0, 3)
        max_drift = max(max_drift, drift)
    return {
        "profile": profile.label(),
        "surfaces": by,
        "drift_pct": round(max_drift * 100.0, 3),
        "max_sample_drift_pct": round(worst_sample * 100.0, 3),
        "drift_ok": bool(max_drift <= DRIFT_TOLERANCE),
        "tolerance_pct": DRIFT_TOLERANCE * 100.0,
    }


# ---- seeded micro-bench sweep --------------------------------------------


def _bench_fragment(scale: int, ef: int, seed: int):
    """A tiny fnum=1 edge-cut fragment for one RMAT-ish draw (the
    test-suite construction: CommSpec + MapPartitioner + build)."""
    from libgrape_lite_tpu.fragment.edgecut import (
        ShardedEdgecutFragment,
    )
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(seed)
    n = 1 << scale
    e = n * ef
    # hub-skewed draw so the oriented DAG has dense and sparse tiles
    src = np.minimum(
        rng.integers(0, n, e),
        rng.integers(0, n, e),
    ).astype(np.int64)
    dst = rng.integers(0, n, e).astype(np.int64)
    oids = np.arange(n, dtype=np.int64)
    comm = CommSpec(fnum=1)
    vm = VertexMap.build(oids, MapPartitioner(1, oids))
    return ShardedEdgecutFragment.build(
        comm, vm, src, dst, None, directed=False,
    )


def _timed_call(fn, args, repeats: int) -> float:
    """Best-of-`repeats` sync-before-close wall of one jitted call
    (first call compiles and is discarded)."""
    import time

    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _spgemm_sample(scale: int, ef: int, seed: int,
                   repeats: int) -> Optional[dict]:
    import jax

    from libgrape_lite_tpu.ops.spgemm_pack import (
        resolve_spgemm_dispatch,
    )

    frag = _bench_fragment(scale, ef, seed)
    try:
        disp = resolve_spgemm_dispatch(frag)
    except Exception:
        return None
    led = disp.ledger()
    if not led or not disp.plan.items:
        return None
    # host_streams entries carry a leading [fnum] shard axis; the
    # credits pass is the traced PER-SHARD program (fnum=1 here)
    entries = {k: np.asarray(v)[0]
               for k, v in disp.state_entries().items()}

    def run(state):
        return disp.credits(state)

    fn = jax.jit(run)
    wall = _timed_call(fn, (entries,), repeats)
    t = led["totals"]
    return {
        "surface": "spgemm",
        "geometry": f"s{scale}ef{ef}",
        "wall_s": wall,
        "vpu_ops": int(t["vpu_ops"]),
        "mxu_ops": int(t["mxu_ops"]),
        "gather_rows": int(t["gather_rows"]),
        "hbm_bytes": int(t["hbm_bytes"]),
    }


def microbench_samples(scales: Sequence[int] = (8, 9, 10),
                       ef: int = 8, seed: int = 7,
                       repeats: int = 3) -> List[dict]:
    """The seeded sweep: masked-SpGEMM dispatches across a small
    geometry grid.  Exchange dispatches need a >1 device mesh; on a
    1-device backend the exchange rates stay inherited (recorded in
    ``profile.unfitted`` by the fit)."""
    samples: List[dict] = []
    for i, scale in enumerate(scales):
        s = _spgemm_sample(scale, ef, seed + 13 * i, repeats)
        if s is not None:
            samples.append(s)
    return samples


# ---- live harvest --------------------------------------------------------

_HARVEST: List[dict] = []
_HARVEST_MAX = 4096


def harvest_armed() -> bool:
    return os.environ.get(HARVEST_ENV, "") in ("1", "true", "on")


def harvest_dispatch(stages: Optional[dict], totals: Optional[dict],
                     rounds: int) -> Optional[dict]:
    """Join one dispatch's telemetry stage stamp (``device_us``) to
    its worker's shipped ledger recount: the ledger totals are
    per ROUND, the device stamp covers the whole fused while_loop, so
    the regressor columns scale by `rounds`.  Returns the sample (and
    appends it to the harvest buffer), or None when the dispatch
    carries no usable stamp/ledger."""
    if not stages or not totals or rounds <= 0:
        return None
    device_us = stages.get("device_us", 0)
    if not device_us or device_us <= 0:
        return None
    sample = {
        "surface": "harvest",
        "wall_s": device_us / 1e6,
        "vpu_ops": int(totals.get("vpu_ops", 0)) * rounds,
        "mxu_ops": int(totals.get("mxu_ops", 0)) * rounds,
        "gather_rows": int(totals.get("gather_rows", 0)) * rounds,
        "hbm_bytes": int(totals.get("hbm_bytes", 0)) * rounds,
    }
    if sample["vpu_ops"] == 0 and sample["hbm_bytes"] == 0:
        return None
    _HARVEST.append(sample)
    if len(_HARVEST) > _HARVEST_MAX:
        del _HARVEST[: _HARVEST_MAX // 2]
    return sample


def harvest_from_worker(worker, stages: Optional[dict],
                        rounds: int) -> Optional[dict]:
    """The serve-session hook: pull the dispatching worker's merged
    ledger totals and harvest the stamp (no-op when the worker
    has no ledger — the pull apps ship no recount columns)."""
    try:
        led = worker.pack_ledger()
    except Exception:
        return None
    totals = (led or {}).get("totals")
    if not totals:
        return None
    return harvest_dispatch(stages, totals, rounds)


def harvested_samples() -> List[dict]:
    return list(_HARVEST)


def reset_harvest() -> None:
    del _HARVEST[:]


# federated as "calibration" (obs/federation.py): harvest depth +
# the active profile label, visible to the live exporter
from libgrape_lite_tpu.obs import federation as _federation  # noqa: E402


def _calibration_snapshot() -> dict:
    return {
        "harvested": len(_HARVEST),
        "armed": harvest_armed(),
        "profile": os.environ.get(PROFILE_ENV, "") or "v5e-pinned",
    }


_federation.register("calibration", _calibration_snapshot,
                     reset_harvest, module=__name__)
