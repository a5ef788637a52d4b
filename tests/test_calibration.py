"""Self-calibrating cost ledger (ops/calibration.py, r17).

The contract surface:
  * ONE source of pricing constants: the default RateProfile IS the
    pinned v5e rates, and every consumer (pack_cost_model, spgemm
    price_backends, the partition ledger, autopilot admission)
    prices from the same profile object — the
    dedupe regression pins that two call sites cannot drift apart;
  * the fitter: synthetic round-trip within 1%, ill-conditioned or
    under-determined sample sets FAIL loudly, a negative intercept is
    refit without the const column (never clamped), the fallback
    chain records every rejected step;
  * profile/sample persistence: schema-validated JSON, loud load
    errors, GRAPE_RATE_PROFILE env loading;
  * the drift gate: modeled-vs-measured per surface, trip and pass;
  * decision records: every auto-selector decision names the profile
    label it priced from, and a swapped profile demonstrably flips
    the LCC intersect/spgemm auto choice at a geometry where the
    ledgers disagree;
  * satellites: degree-weighted rebalancing behind
    GRAPE_PARTITION_REBALANCE (skew recorded, byte-identical at
    fnum 1), the grape-lint R10 pinned-rate-constant rule, the bench
    schema `calibration` block, the bench_compare absolute drift
    gate, and the calibrate CLI.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import replace
from types import SimpleNamespace

import numpy as np
import pytest

from libgrape_lite_tpu.ops import calibration as calib
from tests.test_worker import build_fragment


# ---- fixtures / helpers ---------------------------------------------------


def _truth_profile() -> calib.RateProfile:
    """A profile with rates deliberately DIFFERENT from the pinned
    defaults in every fitted field — a round-trip that accidentally
    read the default would miss by far more than 1%."""
    return replace(
        calib.default_profile(), name="truth",
        clock_hz=1.0e9, vpu_lanes_per_cycle=512.0,
        mxu_cyc_per_elem=0.02, gather_rows_per_cycle=64.0,
        hbm_bps=4.0e11, dispatch_overhead_s=2.0e-3,
    )


def _synthetic_samples(profile, n=14, seed=5, surface="spmv"):
    """Samples whose walls are EXACTLY the profile's additive model
    over independently drawn columns — the fit's only job is to read
    the coefficients back."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        s = {
            "surface": surface,
            "vpu_ops": int(rng.integers(1 << 20, 1 << 29)),
            "mxu_ops": int(rng.integers(1 << 16, 1 << 24)),
            "gather_rows": int(rng.integers(1 << 14, 1 << 22)),
            "hbm_bytes": int(rng.integers(1 << 22, 1 << 30)),
        }
        s["wall_s"] = profile.wall_s(s)
        out.append(s)
    return out


def _ring_frag(n, chords=64, seed=3, fnum=1):
    """Sparse ring + a few chords: the intersect bitmap sweep pays for
    the whole n_pad word range while spgemm touches few tile products
    — the geometry where the two LCC ledgers genuinely disagree."""
    src = np.arange(n, dtype=np.int64)
    dst = (src + 1) % n
    rng = np.random.default_rng(seed)
    s = np.concatenate([src, rng.integers(0, n, chords)])
    d = np.concatenate([dst, rng.integers(0, n, chords)])
    return build_fragment(s, d, None, n, fnum)


@pytest.fixture
def scripts_path():
    p = os.path.join(os.path.dirname(__file__), "..", "scripts")
    sys.path.insert(0, p)
    try:
        yield
    finally:
        sys.path.remove(p)


# ---- the one source of pricing constants ----------------------------------


def test_default_profile_is_the_pinned_v5e_rates():
    p = calib.default_profile()
    assert p.name == "v5e-pinned"
    assert p.clock_hz == 940e6
    assert p.vpu_lanes_per_cycle == 1024.0
    assert p.mxu_cyc_per_elem == 0.008
    assert p.hbm_bps == 819e9
    assert p.ici_bps == 9e10
    assert p.gather_rows_per_cycle == 128.0
    assert p.gather_rates == {"vreg": 1024.0, "row": 128.0,
                              "unroll": 16.0}
    assert p.exchange_bps == {"gather": 9e10, "mirror": 9e10,
                              "vc2d": 9e10}
    assert p.hbm_capacity_bytes == 16 << 30
    assert p.dispatch_overhead_s == 0.0
    assert not p.fitted
    assert p.label() == "v5e-pinned@pinned"


def test_dedupe_both_call_sites_price_identically(scripts_path):
    """Satellite (a): pack_cost_model.price and spgemm price_backends
    deduped their private rate copies onto the shared profile — for
    the same ledger columns both must produce the SAME per-column
    seconds, pinned here against the profile's own coefficients."""
    import pack_cost_model as pcm

    p = _truth_profile()  # non-default rates: a stale copy would miss
    totals = {"vpu_ops": 1 << 24, "mxu_ops": 1 << 18,
              "gather_rows": 1 << 14, "hbm_bytes": 1 << 26}
    vpu_s = totals["vpu_ops"] / p.vpu_lanes_per_cycle / p.clock_hz
    mxu_s = totals["mxu_ops"] * p.mxu_cyc_per_elem / p.clock_hz
    hbm_s = totals["hbm_bytes"] / p.hbm_bps
    row_s = totals["gather_rows"] / p.gather_rows_per_cycle / p.clock_hz

    priced = pcm.price(totals, profile=p)
    assert priced["t_vpu_ms"] == round(vpu_s * 1e3, 2)
    assert priced["t_mxu_ms"] == round(mxu_s * 1e3, 2)
    assert priced["t_hbm_ms"] == round(hbm_s * 1e3, 2)

    from libgrape_lite_tpu.ops.spgemm_pack import price_backends

    it = {"word_ops": 1 << 22, "hbm_bytes": 1 << 20}
    pb = price_backends({"totals": totals}, it, profile=p)
    assert pb["t_spgemm_s"] == pytest.approx(
        max(vpu_s + mxu_s + row_s, hbm_s), rel=1e-12
    )
    assert pb["t_intersect_s"] == pytest.approx(
        max(it["word_ops"] / p.vpu_lanes_per_cycle / p.clock_hz,
            it["hbm_bytes"] / p.hbm_bps),
        rel=1e-12,
    )
    assert pb["profile"] == p.label()


# ---- the fitter -----------------------------------------------------------


def test_fit_round_trip_within_one_percent():
    truth = _truth_profile()
    samples = _synthetic_samples(truth)
    fit = calib.fit_rates(
        samples,
        regressors=("const", "vpu_ops", "mxu_ops", "gather_rows",
                    "hbm_bytes"),
    )
    got = fit.profile
    assert got.fitted and got.source == "microbench"
    # each fitted COEFFICIENT must land within 1% of the truth's
    for reg in fit.regressors:
        want = calib._COEFF_OF[reg](truth)
        assert fit.coefficients[reg] == pytest.approx(want, rel=0.01)
    assert fit.residual < 0.01
    # and the profile's wall model reproduces held-out samples
    held = _synthetic_samples(truth, n=4, seed=99)
    for s in held:
        assert got.wall_s(s) == pytest.approx(s["wall_s"], rel=0.01)
    rep = calib.drift_report(got, held)
    assert rep["drift_ok"]


def test_fit_ill_conditioned_fails_loudly():
    """Perfectly collinear columns (mxu = 3*vpu in every sample)
    cannot be separated — the fitter must refuse, not invent rates."""
    rng = np.random.default_rng(2)
    samples = []
    for _ in range(8):
        v = int(rng.integers(1 << 20, 1 << 28))
        samples.append({"surface": "x", "vpu_ops": v, "mxu_ops": 3 * v,
                        "wall_s": v * 1e-12 + 1e-3})
    with pytest.raises(calib.CalibrationError):
        calib.fit_rates(samples, regressors=("vpu_ops", "mxu_ops"))


def test_fit_underdetermined_fails_loudly():
    truth = _truth_profile()
    samples = _synthetic_samples(truth, n=2)
    with pytest.raises(calib.CalibrationError, match="cannot identify"):
        calib.fit_rates(
            samples,
            regressors=("const", "vpu_ops", "mxu_ops", "hbm_bytes"),
        )
    with pytest.raises(calib.CalibrationError, match="no samples"):
        calib.fit_rates([])
    with pytest.raises(calib.CalibrationError, match="positive finite"):
        calib.fit_rates([{"surface": "x", "vpu_ops": 10,
                          "wall_s": -1.0}])


def test_fit_negative_intercept_refits_without_const():
    """Regression for the const-clamp bug: when the LSQ optimum's
    intercept comes out negative, the fitter must DROP the const
    column and refit — clamping it to zero leaves the other
    coefficients fit against an intercept that no longer exists, so
    every modeled wall overshoots."""
    rng = np.random.default_rng(4)
    coeff = 2.0e-12
    samples = []
    for _ in range(10):
        v = int(rng.integers(1 << 28, 1 << 31))
        # wall = coeff*vpu - delta: the exact optimum has a negative
        # intercept; walls stay comfortably positive
        samples.append({"surface": "x", "vpu_ops": v,
                        "wall_s": coeff * v - 2e-5})
    fit = calib.fit_rates(samples, regressors=("const", "vpu_ops"))
    assert fit.profile.dispatch_overhead_s == 0.0
    assert "const" not in fit.regressors
    assert "const" not in fit.profile.unfitted
    assert fit.coefficients["vpu_ops"] == pytest.approx(coeff, rel=0.01)
    # the clamp bug's signature was systematic overshoot: the refit
    # must stay within the drift gate on its own samples
    assert calib.drift_report(fit.profile, samples)["drift_ok"]


def test_fit_rates_auto_records_fallback_notes():
    """Collinear vpu/mxu columns walk the fallback chain: every
    rejected step is a note, the inherited column is recorded in
    profile.unfitted — degraded fits are visible, never silent."""
    rng = np.random.default_rng(6)
    base = calib.default_profile()
    samples = []
    for _ in range(9):
        v = int(rng.integers(1 << 24, 1 << 29))
        s = {"surface": "x", "vpu_ops": v, "mxu_ops": 3 * v}
        # true wall prices mxu at the BASE rate so the inherited
        # subtraction leaves a cleanly fittable vpu response
        s["wall_s"] = base.wall_s(s) * 1.7
        samples.append(s)
    fit, notes = calib.fit_rates_auto(samples, base=base)
    assert notes, "rejected fallback steps must be recorded"
    assert all("vpu_ops" in n for n in notes)
    assert "mxu_ops" in fit.profile.unfitted
    assert "mxu_ops" not in fit.regressors
    assert calib.drift_report(fit.profile, samples)["drift_ok"]


# ---- persistence + env loading -------------------------------------------


def test_profile_save_load_round_trip(tmp_path):
    truth = replace(_truth_profile(), fitted=True, source="microbench",
                    fingerprint="cpu:test", residual=0.004,
                    unfitted=("gather_rows",))
    path = str(tmp_path / "rates.json")
    calib.save_profile(truth, path)
    got = calib.load_profile(path)
    assert got == truth


def test_validate_profile_rejections():
    good = _truth_profile().as_dict()
    assert calib.validate_profile(good) == []

    bad = dict(good)
    bad["clock_hz"] = True  # bool is an int subclass: must be refused
    assert any("bool" in e for e in calib.validate_profile(bad))

    bad = dict(good)
    bad["surprise_rate"] = 1.0
    assert any("unknown field" in e for e in calib.validate_profile(bad))

    bad = dict(good)
    bad["exchange_bps"] = {"gather": 9e10, "mirror": 9e10}
    assert any("vc2d" in e for e in calib.validate_profile(bad))

    bad = dict(good)
    bad["gather_rates"] = {"row": -5.0}
    assert any("gather_rates" in e for e in calib.validate_profile(bad))

    bad = dict(good)
    bad["hbm_bps"] = 0
    assert any("hbm_bps" in e for e in calib.validate_profile(bad))


def test_load_profile_errors_are_loud(tmp_path):
    with pytest.raises(calib.CalibrationError, match="cannot read"):
        calib.load_profile(str(tmp_path / "absent.json"))
    p = tmp_path / "corrupt.json"
    p.write_text("{not json")
    with pytest.raises(calib.CalibrationError, match="not valid JSON"):
        calib.load_profile(str(p))
    q = tmp_path / "invalid.json"
    q.write_text(json.dumps({"name": "x"}))
    with pytest.raises(calib.CalibrationError, match="invalid rate"):
        calib.load_profile(str(q))


def test_active_profile_env(tmp_path, monkeypatch):
    monkeypatch.delenv(calib.PROFILE_ENV, raising=False)
    assert calib.active_profile() is calib.default_profile()

    prof = replace(_truth_profile(), name="installed")
    path = str(tmp_path / "rates.json")
    calib.save_profile(prof, path)
    monkeypatch.setenv(calib.PROFILE_ENV, path)
    assert calib.active_profile() == prof
    assert calib.profile_label().startswith("installed@")

    # a configured-but-broken profile must never silently downgrade
    # every auto-selector to the pinned rates
    monkeypatch.setenv(calib.PROFILE_ENV, str(tmp_path / "gone.json"))
    with pytest.raises(calib.CalibrationError):
        calib.active_profile()


def test_samples_save_load_round_trip(tmp_path):
    samples = _synthetic_samples(_truth_profile(), n=3)
    path = str(tmp_path / "samples.json")
    calib.save_samples(samples, path)
    got = calib.load_samples(path)
    assert got == samples

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": 1, "fingerprint": "x",
                               "samples": [{"vpu_ops": 3}]}))
    with pytest.raises(calib.CalibrationError, match="no\n?.*wall_s"):
        calib.load_samples(str(bad))
    bad.write_text(json.dumps({"schema": 1, "fingerprint": "x",
                               "samples": [{"wall_s": True}]}))
    with pytest.raises(calib.CalibrationError, match="positive"):
        calib.load_samples(str(bad))
    with pytest.raises(calib.CalibrationError, match="cannot read"):
        calib.load_samples(str(tmp_path / "absent.json"))


# ---- the drift gate -------------------------------------------------------


def test_drift_report_trip_and_pass():
    truth = _truth_profile()
    samples = (_synthetic_samples(truth, n=6, surface="spmv")
               + _synthetic_samples(truth, n=4, seed=8,
                                    surface="spgemm"))
    rep = calib.drift_report(truth, samples)
    assert rep["drift_ok"]
    assert rep["drift_pct"] == 0.0
    assert set(rep["surfaces"]) == {"spmv", "spgemm"}
    assert rep["surfaces"]["spmv"]["samples"] == 6
    assert rep["profile"] == truth.label()

    corrupt = replace(truth,
                      vpu_lanes_per_cycle=truth.vpu_lanes_per_cycle
                      / 20.0)
    rep = calib.drift_report(corrupt, samples)
    assert not rep["drift_ok"]
    assert rep["drift_pct"] > rep["tolerance_pct"]
    assert rep["max_sample_drift_pct"] >= rep["drift_pct"]


# ---- live harvest ---------------------------------------------------------


def test_harvest_dispatch_scales_ledger_by_rounds(monkeypatch):
    calib.reset_harvest()
    monkeypatch.delenv(calib.HARVEST_ENV, raising=False)
    assert not calib.harvest_armed()
    monkeypatch.setenv(calib.HARVEST_ENV, "1")
    assert calib.harvest_armed()

    totals = {"vpu_ops": 100, "mxu_ops": 10, "gather_rows": 4,
              "hbm_bytes": 2048}
    # no device stamp -> no sample (never a zero-wall row)
    assert calib.harvest_dispatch({}, totals, 5) is None
    assert calib.harvest_dispatch({"device_us": 0}, totals, 5) is None
    s = calib.harvest_dispatch({"device_us": 1500.0}, totals, 5)
    assert s is not None
    assert s["wall_s"] == pytest.approx(1.5e-3)
    assert s["vpu_ops"] == 500 and s["hbm_bytes"] == 10240
    assert s["surface"] == "harvest"
    assert calib.harvested_samples() == [s]
    calib.reset_harvest()
    assert calib.harvested_samples() == []


# ---- decision records name the profile ------------------------------------


def test_partition_decision_carries_profile_label():
    from libgrape_lite_tpu.fragment.partition import resolve_partition

    rng = np.random.default_rng(1)
    n = 256
    src = rng.integers(0, n, 2048)
    dst = rng.integers(0, n, 2048)
    oids = np.arange(n, dtype=np.int64)
    dec = resolve_partition("sssp", 4, src, dst, oids, mode="auto")
    assert dec["profile"] == "v5e-pinned@pinned"
    assert "costs" in dec  # auto mode actually priced


def test_admission_shed_record_carries_profile(monkeypatch):
    from libgrape_lite_tpu.autopilot.admission import (
        AdmissionConfig,
        AdmissionController,
        decide_admission,
        query_wall_s,
    )
    from libgrape_lite_tpu.autopilot.signals import AUTOPILOT_STATS
    from libgrape_lite_tpu.obs.slo import SLO_STATS
    from libgrape_lite_tpu.ops.spgemm_pack import resolve_spgemm_dispatch

    # the pure decide: an over-budget tenant's request whose modeled
    # WALL exceeds max_cost_s sheds
    cfg = AdmissionConfig(max_cost_s=0.5)
    assert decide_admission(1.5, 0.0, cfg, cost_s=0.6) == "shed"
    assert decide_admission(1.5, 0.0, cfg, cost_s=0.4) == "defer"
    assert decide_admission(0.5, 0.0, cfg, cost_s=9.9) == "admit"

    frag = _ring_frag(512, chords=32, fnum=1)
    # no plan resolved: no op columns to price
    assert query_wall_s(frag, max_rounds=8) == 0.0
    assert resolve_spgemm_dispatch(frag).plan.items > 0
    wall = query_wall_s(frag, max_rounds=8)
    assert wall > 0.0
    # a 1000x slower VPU re-prices the SAME plan 1000x up
    slow = replace(calib.default_profile(),
                   vpu_lanes_per_cycle=1024.0 / 1000.0)
    assert query_wall_s(frag, max_rounds=8, profile=slow) > 100 * wall

    monkeypatch.setitem(SLO_STATS, "burn_by_key", {"tenant:t9": 1.5})
    ctl = AdmissionController(
        config=AdmissionConfig(max_cost_s=wall / 2.0), fragment=frag
    )
    req = SimpleNamespace(tenant="t9", app_key="sssp", max_rounds=8)
    assert ctl.review(req) == "shed"
    rec = AUTOPILOT_STATS["decisions"][-1]
    assert rec["kind"] == "shed"
    assert rec["profile"] == "v5e-pinned@pinned"
    assert rec["cost_s"] > 0


# ---- swapped profile flips the LCC auto choice ----------------------------


def test_lcc_auto_flips_under_swapped_profile(tmp_path, monkeypatch):
    """Acceptance pin: at the sparse-ring geometry the two LCC
    ledgers disagree — spgemm wins under the pinned rates, and a
    profile with the MXU rate inverted (1000x slower per element)
    flips the auto choice to intersect, both via direct pricing and
    via the GRAPE_RATE_PROFILE file the resolver loads."""
    from libgrape_lite_tpu.ops.spgemm_pack import (
        SPGEMM_STATS,
        intersect_ledger,
        plan_spgemm,
        price_backends,
        resolve_lcc_backend,
    )

    frag = _ring_frag(4096)
    plan = plan_spgemm(frag, 0, plan_only=True)
    it = intersect_ledger(frag, 4096)
    pinned = calib.default_profile()
    base = price_backends(plan.ledger, it, profile=pinned)
    assert base["spgemm_wins"], "geometry must favor spgemm at pinned"

    slow_mxu = replace(pinned, name="slow-mxu",
                       mxu_cyc_per_elem=pinned.mxu_cyc_per_elem * 1e3)
    swapped = price_backends(plan.ledger, it, profile=slow_mxu)
    assert not swapped["spgemm_wins"]
    assert swapped["t_spgemm_s"] > base["t_spgemm_s"]
    assert swapped["t_intersect_s"] == base["t_intersect_s"]

    # the resolver end to end: same fragment, same env mode, only the
    # installed profile differs -> the decision flips and each
    # decision record names the profile it priced from
    monkeypatch.setenv("GRAPE_LCC_BACKEND", "auto")
    monkeypatch.delenv(calib.PROFILE_ENV, raising=False)
    assert resolve_lcc_backend("lcc", frag) == "spgemm"
    dec = SPGEMM_STATS["decisions"][-1]
    assert dec["backend"] == "spgemm"
    assert dec["profile"] == "v5e-pinned@pinned"

    path = str(tmp_path / "slow_mxu.json")
    calib.save_profile(slow_mxu, path)
    monkeypatch.setenv(calib.PROFILE_ENV, path)
    assert resolve_lcc_backend("lcc", frag) == "intersect"
    dec = SPGEMM_STATS["decisions"][-1]
    assert dec["backend"] == "intersect"
    assert dec["profile"].startswith("slow-mxu@")


def test_partition_and_overlap_reprice_under_profile():
    from libgrape_lite_tpu.fragment.partition import modeled_costs

    rng = np.random.default_rng(7)
    n = 1024
    src = rng.integers(0, n, 8192)
    dst = rng.integers(0, n, 8192)
    pinned = calib.default_profile()
    slow_ici = replace(pinned, ici_bps=pinned.ici_bps / 1e4)

    base = modeled_costs(src, dst, n, 4, profile=pinned)
    slow = modeled_costs(src, dst, n, 4, profile=slow_ici)
    # the exchange term re-prices; edge counts (conventions) do not
    assert slow["1d"]["t_round_s"] > base["1d"]["t_round_s"]
    assert slow["2d"]["t_round_s"] > base["2d"]["t_round_s"]
    assert slow["1d"]["max_shard_edges"] == base["1d"]["max_shard_edges"]


# ---- degree-weighted rebalancing (satellite c) ----------------------------


def _write_skewed_graph(tmp_path, n=64, hub_edges=40):
    """Hub-heavy TSV: vertices 0..3 soak up most in-edges, so the
    oid-range cut dumps the whole hot tier into shard 0."""
    rng = np.random.default_rng(9)
    lines = []
    for hub in range(4):
        for _ in range(hub_edges):
            lines.append((int(rng.integers(4, n)), hub))
    for v in range(4, n):
        lines.append((v, int((v + 1) % n) or 4))
    efile = tmp_path / "skew.e"
    efile.write_text("".join(f"{s}\t{d}\t1.0\n" for s, d in lines))
    vfile = tmp_path / "skew.v"
    vfile.write_text("".join(f"{v}\n" for v in range(n)))
    return str(efile), str(vfile)


def test_rebalance_env_gate_records_skew(tmp_path, monkeypatch):
    from libgrape_lite_tpu.fragment.loader import (
        REBALANCE_ENV,
        LoadGraph,
        LoadGraphSpec,
    )
    from libgrape_lite_tpu.fragment.partition import PARTITION_STATS
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    efile, vfile = _write_skewed_graph(tmp_path)
    PARTITION_STATS["rebalance"] = None

    # env off: oid-range cut, nothing recorded
    monkeypatch.delenv(REBALANCE_ENV, raising=False)
    LoadGraph(efile, vfile, CommSpec(fnum=4), LoadGraphSpec())
    assert PARTITION_STATS["rebalance"] is None

    monkeypatch.setenv(REBALANCE_ENV, "1")
    LoadGraph(efile, vfile, CommSpec(fnum=4), LoadGraphSpec())
    rec = PARTITION_STATS["rebalance"]
    assert rec is not None and rec["fnum"] == 4
    # the hub-heavy cut is what the rebalancer exists to fix
    assert rec["before"]["skew"] > 1.5
    assert rec["after"]["skew"] <= rec["before"]["skew"]
    assert rec["after"]["max_shard_edges"] <= \
        rec["before"]["max_shard_edges"]


def test_rebalance_fnum1_is_byte_identical(tmp_path, monkeypatch):
    """At fnum 1 the rebalancer's single block IS the oid range — the
    built fragment must be bit-for-bit the env-off one."""
    from libgrape_lite_tpu.fragment.loader import (
        REBALANCE_ENV,
        LoadGraph,
        LoadGraphSpec,
    )
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    efile, vfile = _write_skewed_graph(tmp_path)

    def load():
        return LoadGraph(efile, vfile, CommSpec(fnum=1),
                         LoadGraphSpec())

    monkeypatch.delenv(REBALANCE_ENV, raising=False)
    off = load()
    monkeypatch.setenv(REBALANCE_ENV, "1")
    on = load()
    for side in ("host_oe", "host_ie"):
        a, b = getattr(off, side)[0], getattr(on, side)[0]
        assert a.indptr.tobytes() == b.indptr.tobytes()
        assert a.edge_src.tobytes() == b.edge_src.tobytes()
        assert a.edge_nbr.tobytes() == b.edge_nbr.tobytes()
        assert a.edge_mask.tobytes() == b.edge_mask.tobytes()
        assert a.edge_w.tobytes() == b.edge_w.tobytes()
    assert (off.vertex_map.inner_oids(0).tobytes()
            == on.vertex_map.inner_oids(0).tobytes())


# ---- grape-lint R10 (satellite b) -----------------------------------------


def test_r10_flags_pinned_rate_literals():
    from libgrape_lite_tpu.analysis.astlint import lint_source

    src = "HBM_BPS = 819e9\n"
    found = lint_source(src, "libgrape_lite_tpu/some/module.py")
    assert [f.rule for f in found] == ["R10"]
    assert "HBM_BPS" in found[0].message

    # dict rate tables and annotated assigns trip too
    src = ("_GATHER_RATES = {'row': 128.0}\n"
           "CLOCK_HZ: float = 940e6\n")
    found = lint_source(src, "libgrape_lite_tpu/m.py")
    assert sorted(f.symbol for f in found
                  if f.rule == "R10") == ["CLOCK_HZ", "_GATHER_RATES"]

    # expressions of literals are still literals
    found = lint_source("ICI_BPS = 2 * 45e9\n", "libgrape_lite_tpu/m.py")
    assert [f.rule for f in found] == ["R10"]


def test_r10_sanctioned_forms_pass():
    from libgrape_lite_tpu.analysis.astlint import lint_source

    # reading the shared profile is THE sanctioned form
    src = ("from libgrape_lite_tpu.ops.calibration import "
           "default_profile\n"
           "HBM_BPS = default_profile().hbm_bps\n"
           "CLOCK_HZ = default_profile().clock_hz\n")
    assert lint_source(src, "libgrape_lite_tpu/m.py") == []

    # op-count conventions are NOT rates; the recount gates must stay
    # independent of the planners they audit
    src = "DEFAULT_OPS_PER_EDGE = 30.0\n_ITEM_VPU_PLANES = 6\n"
    assert lint_source(src, "libgrape_lite_tpu/m.py") == []

    # ops/calibration.py is the one home pinned literals belong in
    src = "HBM_BPS = 819e9\n"
    assert lint_source(src, "libgrape_lite_tpu/ops/calibration.py") == []


def test_r10_zero_findings_in_migrated_modules():
    """The migrated consumers carry no private rate copies, and the
    suppression baseline holds no R10 entries (zero-entry rule)."""
    from libgrape_lite_tpu.analysis.astlint import lint_source

    root = os.path.join(os.path.dirname(__file__), "..")
    for rel in (
        "libgrape_lite_tpu/fragment/partition.py",
        "libgrape_lite_tpu/ops/spgemm_pack.py",
        "libgrape_lite_tpu/autopilot/admission.py",
        "libgrape_lite_tpu/fleet/budget.py",
        "scripts/pack_cost_model.py",
    ):
        with open(os.path.join(root, rel)) as f:
            src = f.read()
        r10 = [f for f in lint_source(src, rel) if f.rule == "R10"]
        assert r10 == [], f"{rel} carries a pinned rate copy: {r10}"

    with open(os.path.join(
            root, "libgrape_lite_tpu/analysis/baseline.json")) as f:
        baseline = json.load(f)
    assert not [e for e in baseline.get("suppressions", [])
                if e.get("rule") == "R10"]


# ---- CI plumbing: bench schema, bench_compare, the calibrate CLI ----------


def _good_calibration_block():
    return {
        "profile": "bench-fit@cpu:test", "fingerprint": "cpu:test",
        "source": "microbench", "fitted": True, "samples": 7,
        "residual_pct": 1.2, "drift_pct": 2.4,
        "max_sample_drift_pct": 4.0, "drift_ok": True,
        "rates": {"clock_hz": 940e6, "vpu_lanes_per_cycle": 1024.0},
        "unfitted": ["gather_rows"],
        "fallback_notes": ["const+vpu_ops+mxu_ops: x"],
        "surfaces": {"spmv": {"modeled_s": 0.1, "measured_s": 0.11,
                              "samples": 5, "drift_pct": 2.4}},
    }


def test_bench_schema_calibration_block(scripts_path):
    from check_bench_schema import self_check, validate_record

    assert self_check() == []

    def errs(block):
        rec = {"metric": "x", "value": 1, "unit": "u",
               "vs_baseline": 1.0, "calibration": block}
        return [e for e in validate_record(rec)
                if e.startswith("calibration")]

    assert errs(_good_calibration_block()) == []

    bad = _good_calibration_block()
    bad["drift_pct"] = True  # bool-in-numeric must be rejected
    assert any("drift_pct" in e for e in errs(bad))

    bad = _good_calibration_block()
    bad["rates"]["hbm_bps"] = False
    assert any("rates" in e for e in errs(bad))

    bad = _good_calibration_block()
    bad["fallback_notes"] = [3]
    assert any("fallback_notes" in e for e in errs(bad))

    bad = _good_calibration_block()
    bad["surfaces"]["spmv"].pop("modeled_s")
    assert any("surfaces" in e and "modeled_s" in e for e in errs(bad))

    bad = _good_calibration_block()
    bad["surprise"] = 1
    assert any("unknown field" in e for e in errs(bad))

    bad = _good_calibration_block()
    bad.pop("drift_ok")
    assert any("drift_ok" in e for e in errs(bad))


def test_bench_compare_absolute_drift_gate(scripts_path):
    """The candidate's recorded drift gates ABSOLUTELY at 5% — a
    drifting baseline is no excuse (unlike the relative perf gates)."""
    from bench_compare import calibration_drift_failure

    assert calibration_drift_failure({}) is None
    ok = {"calibration": {"drift_ok": True, "drift_pct": 2.0,
                          "profile": "p@f"}}
    assert calibration_drift_failure(ok) is None

    tripped = {"calibration": {"drift_ok": False, "drift_pct": 9.3,
                               "profile": "p@f"}}
    msg = calibration_drift_failure(tripped)
    assert msg and "9.3" in msg and "p@f" in msg

    # drift_pct past 5 trips even if the producer claimed drift_ok
    lied = {"calibration": {"drift_ok": True, "drift_pct": 7.5,
                            "profile": "p@f"}}
    assert calibration_drift_failure(lied) is not None


def test_calibrate_cli_fit_check_and_corrupt_gate(tmp_path, capsys,
                                                  monkeypatch):
    from libgrape_lite_tpu.cli import calibrate_main

    monkeypatch.delenv(calib.PROFILE_ENV, raising=False)
    truth = _truth_profile()
    sp = str(tmp_path / "samples.json")
    calib.save_samples(_synthetic_samples(truth), sp)
    out = str(tmp_path / "rates.json")

    assert calibrate_main(["--samples", sp, "--out", out,
                           "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    blk = rec["calibration"]
    assert blk["fitted"] and blk["drift_ok"]
    assert blk["source"] == "samples"
    assert rec["out"] == out
    # the CLI block is the bench block's shape: one schema pins both
    fitted = calib.load_profile(out)
    assert blk["rates"]["vpu_lanes_per_cycle"] == pytest.approx(
        fitted.vpu_lanes_per_cycle
    )

    # --check under the fitted profile passes...
    assert calibrate_main(["--check", "--samples", sp,
                           "--profile", out, "--json"]) == 0
    capsys.readouterr()
    # ...and a corrupted profile (20x the VPU rate) trips the gate
    d = json.loads(open(out).read())
    d["vpu_lanes_per_cycle"] *= 20.0
    bad = str(tmp_path / "rates_bad.json")
    with open(bad, "w") as f:
        json.dump(d, f)
    assert calibrate_main(["--check", "--samples", sp,
                           "--profile", bad, "--json"]) == 2
    blk = json.loads(capsys.readouterr().out)["calibration"]
    assert not blk["drift_ok"]

    # an unreadable samples file is a loud exit 2, not a crash
    assert calibrate_main(["--samples",
                           str(tmp_path / "absent.json")]) == 2
