"""analysis/ — grape-lint: static contract linter + artifact auditor
(ISSUE 8 acceptance).

Pins: each AST rule R1-R9 trips on a known-bad fixture snippet and
stays silent on the matching known-good one; the suppression baseline
round-trips and is keyed by line-stable fingerprints; the artifact
audits run on a REAL compiled SSSP runner (constant-bloat clean,
donation present, zero compiles across the warmed canonical query
matrix); `compile_events()` counts real XLA compiles; the lint-report
JSON validates against its declared schema; and the self-lint gate —
grape-lint over the shipped libgrape_lite_tpu/ tree returns zero
unsuppressed findings.
"""

import json
import textwrap

import numpy as np
import pytest

from libgrape_lite_tpu import analysis
from libgrape_lite_tpu.analysis.astlint import lint_source


def _rules(src, path="fixture.py"):
    return sorted(
        {f.rule for f in lint_source(textwrap.dedent(src), path)}
    )


# ---- R1: baked constants --------------------------------------------------


def test_r1_trips_on_closure_captured_array():
    src = """
    import jax, numpy as np
    table = np.zeros((1024, 128))

    def make():
        def stepper(x):
            return x + table
        return jax.jit(stepper)
    """
    assert "R1" in _rules(src)


def test_r1_trips_on_closure_captured_dev():
    src = """
    import jax

    def make(frag):
        def stepper(x):
            return x + frag.dev.deg
        return jax.jit(stepper)
    """
    assert "R1" in _rules(src)


def test_r1_passes_when_array_is_a_parameter():
    src = """
    import jax, numpy as np
    table = np.zeros((1024, 128))

    def make():
        def stepper(frag_stacked, x, table):
            frag = frag_stacked.local()
            return x + table + frag.deg
        return jax.jit(stepper)

    def run(fn):
        return fn(None, 0, table)
    """
    assert "R1" not in _rules(src)


def test_r1_allows_scalar_dtype_constants():
    # jnp.int32(sentinel) closures are harmless scalars, not baked
    # MB-scale arrays (the bfs_opt sentinel pattern)
    src = """
    import jax, jax.numpy as jnp

    def make():
        sent = jnp.int32(2**30)
        def stepper(x):
            return jnp.minimum(x, sent)
        return jax.jit(stepper)
    """
    assert "R1" not in _rules(src)


# ---- R2: per-dispatch jit -------------------------------------------------


def test_r2_trips_on_jit_in_query_path():
    src = """
    import jax

    class Worker:
        def query(self, state):
            fn = jax.jit(lambda x: x + 1)
            return fn(state)
    """
    assert "R2" in _rules(src)


def test_r2_trips_on_builder_called_per_dispatch():
    src = """
    class Worker:
        def _compile_single_step(self, kind, state):
            return kind

        def query_stepwise(self, state):
            fn = self._compile_single_step("peval", state)
            return fn
    """
    assert "R2" in _rules(src)


def test_r2_passes_inside_builders_and_caches():
    src = """
    import jax

    class Worker:
        def _make_runner(self, mr):
            def compile_for(state):
                return jax.jit(lambda s: s)
            return compile_for

        def _runner_for(self, mr, state):
            key = (mr, self._struct(state))
            return self._cached_runner(
                key, lambda: self._make_runner(mr)(state))

        def host_compute(self, frag, cap):
            per_frag = self._cache.setdefault(frag, {})
            if cap not in per_frag:
                fn = jax.jit(lambda x: x + cap)
                per_frag[cap] = fn
            return per_frag[cap]
    """
    assert "R2" not in _rules(src)


# ---- R3: cache-key completeness ------------------------------------------


def test_r3_trips_on_missing_key_field():
    src = """
    class Worker:
        def _runner_for(self, max_rounds, state):
            key = (self._state_struct(state),)
            return self._cached_runner(key, lambda: None)
    """
    assert "R3" in _rules(src)


def test_r3_passes_when_every_param_is_keyed():
    src = """
    class Worker:
        def _runner_for(self, max_rounds, state):
            key = (max_rounds, self._state_struct(state))
            return self._cached_runner(key, lambda: None)
    """
    assert "R3" not in _rules(src)


# ---- R4: query-path parity ------------------------------------------------


def test_r4_trips_on_entrypoint_skipping_dyn_view():
    src = """
    class Worker:
        def _check_dyn_view(self):
            pass

        def query(self, source=0):
            from libgrape_lite_tpu.guard.config import GuardConfig
            cfg = GuardConfig.resolve(None)
            return cfg
    """
    assert "R4" in _rules(src)


def test_r4_passes_via_transitive_self_calls():
    src = """
    class Worker:
        def _check_dyn_view(self):
            pass

        def query(self, source=0):
            from libgrape_lite_tpu.guard.config import GuardConfig
            self._check_dyn_view()
            cfg = GuardConfig.resolve(None)
            return cfg

        def query_incremental(self, prev):
            return self.query()
    """
    assert "R4" not in _rules(src)


def test_r4_trips_on_dispatch_skipping_ensure_dyn_view():
    src = """
    class Session:
        def _ensure_dyn_view(self, app_key, w):
            pass

        def _dispatch(self, batch):
            return [w.query() for w in batch]
    """
    assert "R4" in _rules(src)


# ---- R5: eager logging + bool-in-schema ----------------------------------


def test_r5_trips_on_eager_vlog():
    src = """
    from libgrape_lite_tpu.utils import logging as glog

    def run(r, dt):
        glog.vlog(1, f"round {r}: {dt:.6f}s")
    """
    assert "R5" in _rules(src)


def test_r5_trips_on_concat_vlog():
    # "round " + str(r) is not literal folding: it pays str() + an
    # allocation per call at disabled levels, like the f-string form
    src = """
    from libgrape_lite_tpu.utils import logging as glog

    def run(r):
        glog.vlog(1, "round " + str(r))
    """
    assert "R5" in _rules(src)


def test_r5_passes_on_lazy_vlog():
    src = """
    from libgrape_lite_tpu.utils import logging as glog

    def run(r, dt):
        glog.vlog(1, "round %d: %.6fs", r, dt)
    """
    assert "R5" not in _rules(src)


def test_r5_trips_on_bool_blind_schema_check():
    src = """
    def validate_record(record):
        errors = []
        for k, v in record.items():
            if not isinstance(v, (int, float)):
                errors.append(k)
        return errors
    """
    assert "R5" in _rules(src)


def test_r5_passes_with_explicit_bool_rejection():
    src = """
    def validate_record(record):
        errors = []
        for k, v in record.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                errors.append(k)
        return errors
    """
    assert "R5" not in _rules(src)


# ---- R7: host syncs on the async pump's dispatch stage --------------------

_PUMP_PATH = "libgrape_lite_tpu/serve/pipeline.py"


def test_r7_trips_on_asarray_in_dispatch_stage():
    # np.asarray on the dispatch path materialises the device buffer —
    # the sync re-serialises the window the pump exists to keep full
    src = """
    import numpy as np

    class Pump:
        def _fill(self, force=False):
            self._dispatch(self.queue.pop())

        def _dispatch(self, batch):
            out, rounds, active = self.runner(batch)
            return np.asarray(rounds)
    """
    assert "R7" in _rules(src, _PUMP_PATH)


def test_r7_trips_on_int_of_device_value_in_dispatch_stage():
    src = """
    class Pump:
        def _dispatch_stage(self, batch):
            d = self.worker.dispatch(batch)
            return int(d.rounds[0])
    """
    assert "R7" in _rules(src, _PUMP_PATH)


def test_r7_is_path_scoped_to_the_pump_module():
    # the synchronous session/queue loop is ALLOWED to sync — the
    # contract binds only serve/pipeline.py dispatch-stage code
    src = """
    import numpy as np

    class Session:
        def _dispatch(self, batch):
            return np.asarray(self.runner(batch))
    """
    assert "R7" not in _rules(
        src, "libgrape_lite_tpu/serve/session.py"
    )
    assert "R7" in _rules(src, _PUMP_PATH)


def test_r7_passes_when_sync_lives_in_the_harvest_contract():
    # _harvest_head / _run_declined are named in PUMP_HARVEST_SYNCS:
    # syncs there are the audited harvest stage, and a dispatch chain
    # that routes THROUGH a contract method stops being audited at it
    src = """
    import jax
    import numpy as np

    class Pump:
        def _fill(self, force=False):
            self._dispatch_stage(self.queue.pop())

        def _dispatch_stage(self, batch):
            return self._run_declined(batch)

        def _run_declined(self, batch):
            return jax.block_until_ready(self.session._dispatch(batch))

        def _harvest_head(self, pb):
            return np.asarray(pb.rounds)
    """
    assert "R7" not in _rules(src, _PUMP_PATH)


def test_r7_nested_thunks_are_harvest_time():
    # a deferred thunk BUILT at dispatch time runs at harvest time —
    # the lazy-values form, not a dispatch-stage sync
    src = """
    class Pump:
        def _dispatch_stage(self, batch):
            d = self.worker.dispatch(batch)
            return lambda: int(d.rounds[0])
    """
    assert "R7" not in _rules(src, _PUMP_PATH)


def test_r7_shipped_pump_is_clean():
    # zero-entry baseline: the shipped dispatch stage holds no syncs
    import os

    import libgrape_lite_tpu

    root = os.path.dirname(libgrape_lite_tpu.__file__)
    with open(os.path.join(root, "serve", "pipeline.py")) as fh:
        src = fh.read()
    r7 = [f for f in lint_source(src, _PUMP_PATH) if f.rule == "R7"]
    assert not r7, [f.message for f in r7]


# ---- R8: module-level *_STATS surfaces must federate ----------------------


def test_r8_trips_on_hand_rolled_stats_dict():
    # the retired idiom: a raw module dict is invisible to
    # federation.snapshot(), the live exporter, and every bundle
    src = """
    THING_STATS = {"planned": 0, "declines": []}

    def plan():
        THING_STATS["planned"] += 1
    """
    assert "R8" in _rules(src, "libgrape_lite_tpu/ops/thing.py")


def test_r8_trips_on_ad_hoc_stats_class_instance():
    src = """
    class _Stats:
        def snapshot(self):
            return {}

    THING_STATS = _Stats()
    """
    assert "R8" in _rules(src, "libgrape_lite_tpu/ops/thing.py")


def test_r8_passes_federated_stats_ctor_under_alias():
    src = """
    from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats

    THING_STATS = _FedStats("thing", {"planned": 0})
    """
    assert "R8" not in _rules(src, "libgrape_lite_tpu/ops/thing.py")


def test_r8_passes_explicit_register_via_module_alias():
    # the PumpStats/FleetStats form: a class instance is fine as long
    # as its defining module registers it with the federation
    src = """
    from libgrape_lite_tpu.obs import federation as _federation

    class _Stats:
        def snapshot(self):
            return {}

    THING_STATS = _Stats()
    _federation.register("thing", THING_STATS.snapshot, None,
                         module=__name__)
    """
    assert "R8" not in _rules(src, "libgrape_lite_tpu/ops/thing.py")


def test_r8_passes_lazy_function_level_register():
    # registration behind a function-level import still counts — the
    # rule asks WHETHER the module wires in, not where the import sits
    src = """
    THING_STATS = {"planned": 0}

    def _wire():
        from libgrape_lite_tpu.obs.federation import register
        register("thing", lambda: dict(THING_STATS), None)

    _wire()
    """
    assert "R8" not in _rules(src, "libgrape_lite_tpu/ops/thing.py")


def test_r8_exempts_the_federation_module_itself():
    src = """
    SLO_STATS = {"observed": 0}
    """
    assert "R8" not in _rules(
        src, "libgrape_lite_tpu/obs/federation.py")
    assert "R8" in _rules(src, "libgrape_lite_tpu/obs/other.py")


def test_r8_shipped_stats_surfaces_are_clean():
    # zero-entry baseline over the real owners of every EXPECTED
    # namespace: each *_STATS surface in the shipped tree federates
    import os

    import libgrape_lite_tpu
    from libgrape_lite_tpu.obs.federation import EXPECTED

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(libgrape_lite_tpu.__file__)))
    for owner in EXPECTED.values():
        rel = owner.replace(".", "/") + ".py"
        with open(os.path.join(root, rel)) as fh:
            src = fh.read()
        r8 = [f for f in lint_source(src, rel) if f.rule == "R8"]
        assert not r8, (owner, [f.message for f in r8])


# ---- R9: result-cache call sites must name the full key -------------------


def test_r9_trips_on_incomplete_lookup_key():
    # the R3 shape on the result cache: a call site that drops a key
    # field silently shares one cached answer across identities
    src = """
    def probe(cache, compat, src_id):
        return cache.lookup(compat, src_id, 0)
    """
    assert "R9" in _rules(src, "libgrape_lite_tpu/serve/session.py")


def test_r9_trips_on_store_missing_fence():
    src = """
    def deliver(self, compat, source, res):
        self.result_cache.store(compat, source, res)
    """
    assert "R9" in _rules(src, "libgrape_lite_tpu/serve/queue.py")


def test_r9_passes_full_positional_key():
    src = """
    def probe(cache, compat, source, fence):
        return cache.lookup(compat, source, fence)
    """
    assert "R9" not in _rules(src, "libgrape_lite_tpu/serve/session.py")


def test_r9_passes_keyword_and_synonym_spellings():
    # keyword names count as naming the field; the fence may be spelt
    # epoch/version (the session's ingest-counter idiom)
    src = """
    def deliver(self, ck, s, res):
        self.result_cache.store(compat=ck, source=s,
                                fence=self.epoch(), result=res)

    def probe(self, cache, compat, source):
        return cache.lookup(compat, source, self._ingest_epoch)
    """
    assert "R9" not in _rules(src, "libgrape_lite_tpu/serve/queue.py")


def test_r9_ignores_non_cache_receivers():
    # lookup()/store() on something that is not a result cache (a
    # registry, a dict wrapper) is out of scope
    src = """
    def resolve(registry, compat, src_id):
        return registry.lookup(compat, src_id)
    """
    assert "R9" not in _rules(src, "libgrape_lite_tpu/serve/session.py")


def test_r9_exempts_the_cache_module_itself():
    src = """
    def _evict(self, compat, src_id):
        self._entries.cache.lookup(compat, src_id, 0)
    """
    assert "R9" in _rules(src, "libgrape_lite_tpu/serve/other.py")
    assert "R9" not in _rules(
        src, "libgrape_lite_tpu/autopilot/cache.py")


def test_r9_shipped_call_sites_are_clean():
    # zero-entry baseline: the two shipped call sites (the session's
    # submit probe, the queue's deliver store) name the full key
    import os

    import libgrape_lite_tpu

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(libgrape_lite_tpu.__file__)))
    for rel in ("libgrape_lite_tpu/serve/session.py",
                "libgrape_lite_tpu/serve/queue.py"):
        with open(os.path.join(root, rel)) as fh:
            src = fh.read()
        r9 = [f for f in lint_source(src, rel) if f.rule == "R9"]
        assert not r9, (rel, [f.message for f in r9])


# ---- R11: no raw SUMMA axis names in models/ ------------------------------


def test_r11_trips_on_raw_axis_literal():
    src = """
    from jax import lax

    def fold(partial):
        return lax.pmin(partial, 'vcrow')
    """
    assert "R11" in _rules(src, "libgrape_lite_tpu/models/vc2d.py")


def test_r11_trips_on_axis_tuple_literal():
    src = """
    SPEC = ('vcrow', 'vccol')
    """
    assert "R11" in _rules(src, "libgrape_lite_tpu/models/custom.py")


def test_r11_passes_on_imported_constants():
    src = """
    from jax import lax

    from libgrape_lite_tpu.parallel.comm_spec import (
        VC_COL_AXIS,
        VC_ROW_AXIS,
    )

    def fold(partial):
        return lax.pmin(partial, VC_ROW_AXIS)

    def fold_col(partial):
        return lax.pmin(partial, VC_COL_AXIS)
    """
    assert "R11" not in _rules(src, "libgrape_lite_tpu/models/vc2d.py")


def test_r11_is_scoped_to_models():
    # the defining module and non-model layers (worker, bench) never
    # open a collective over the axis by name — out of scope
    src = """
    VC_ROW_AXIS = 'vcrow'
    VC_COL_AXIS = 'vccol'
    """
    assert "R11" not in _rules(
        src, "libgrape_lite_tpu/parallel/comm_spec.py")
    assert "R11" not in _rules(src, "libgrape_lite_tpu/worker/worker.py")
    assert "R11" in _rules(src, "libgrape_lite_tpu/models/evil.py")


def test_r11_shipped_models_are_clean():
    # zero-entry baseline over the whole models/ tree
    import glob
    import os

    import libgrape_lite_tpu

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(libgrape_lite_tpu.__file__)))
    for path in glob.glob(
        os.path.join(root, "libgrape_lite_tpu", "models", "*.py")
    ):
        rel = os.path.relpath(path, root)
        with open(path) as fh:
            src = fh.read()
        r11 = [f for f in lint_source(src, rel) if f.rule == "R11"]
        assert not r11, (rel, [f.message for f in r11])


# ---- baseline round-trip --------------------------------------------------


def test_baseline_suppression_roundtrip(tmp_path):
    src = """
    import jax

    class Worker:
        def query(self, state):
            return jax.jit(lambda x: x)(state)
    """
    findings = lint_source(textwrap.dedent(src), "mod.py")
    assert findings, "fixture must produce a finding"
    f = findings[0]

    bl_path = str(tmp_path / "baseline.json")
    bl = analysis.Baseline(entries={}, path=bl_path)
    with pytest.raises(ValueError):
        bl.add(f, "")  # reasons are mandatory
    bl.add(f, "test exception")
    bl.save()

    loaded = analysis.Baseline.load(bl_path)
    assert loaded.suppresses(f)
    live, quiet = analysis.split_by_baseline(findings, loaded)
    assert f not in live and f in quiet

    # the fingerprint is line-stable: shifting the snippet down two
    # lines must not invalidate the suppression
    shifted = lint_source("\n\n" + textwrap.dedent(src), "mod.py")
    assert loaded.suppresses(shifted[0])
    assert shifted[0].line != f.line

    # ...but a different rule id under the same fingerprint must not
    # suppress (entries pin their rule)
    clone = analysis.Finding("R9", f.path, f.line, f.symbol, f.message)
    assert not loaded.suppresses(clone)


def test_baseline_budget_blocks_new_identical_finding(tmp_path):
    """A suppression covers at most its `count` (default 1) matching
    findings: fingerprints are line-blind, so a SECOND eager vlog
    with the identical message added to the same function collides
    with the shipped entry — it must surface, not ride the old
    exception (code-review finding on the v1 fingerprint scheme)."""
    one = """
    from libgrape_lite_tpu.utils import logging as glog

    def run(r):
        glog.vlog(1, f"round {r}")
    """
    two = """
    from libgrape_lite_tpu.utils import logging as glog

    def run(r):
        glog.vlog(1, f"round {r}")
        glog.vlog(1, f"round again {r}")
    """
    f1 = lint_source(textwrap.dedent(one), "mod.py")
    assert len(f1) == 1
    bl = analysis.Baseline(entries={}, path=str(tmp_path / "b.json"))
    bl.add(f1[0], "known exception")

    f2 = lint_source(textwrap.dedent(two), "mod.py")
    assert len(f2) == 2
    assert f2[0].fingerprint == f2[1].fingerprint  # line-blind collision
    live, quiet = analysis.split_by_baseline(f2, bl)
    assert len(quiet) == 1 and len(live) == 1, (live, quiet)

    # explicitly suppressing the second instance raises the budget
    # AND records its reason — every instance stays named
    bl.add(f2[1], "second instance, also fine")
    live2, quiet2 = analysis.split_by_baseline(f2, bl)
    assert live2 == [] and len(quiet2) == 2
    entry = bl.entries[f2[0].fingerprint]
    assert entry["count"] == 2
    assert "second instance, also fine" in entry["reason"]
    assert "known exception" in entry["reason"]


def test_stale_baseline_entry_fails_default_scope_gate(tmp_path):
    """A fixed finding must retire its baseline entry: on the default
    full-tree scope, an entry (or raised budget unit) that matched no
    finding fails the gate — else the stale suppression green-gates a
    later reintroduction of the exact defect it names (code-review
    finding on the v1 staleness-blind split)."""
    # a faithful copy of the shipped baseline stays clean...
    shipped = analysis.Baseline.load(None)
    bl_path = str(tmp_path / "b.json")
    shipped.path = bl_path
    shipped.save()
    report, rc = analysis.run_lint(baseline_path=bl_path)
    assert rc == 0 and report["stale"] == []

    # ...adding an entry for a defect nobody ships flips the gate
    ghost = analysis.Finding(
        "R2", "libgrape_lite_tpu/worker/worker.py", 1,
        "Worker.query", "ghost defect that was fixed long ago",
    )
    shipped.add(ghost, "entry for a finding that no longer exists")
    shipped.save()
    report, rc = analysis.run_lint(baseline_path=bl_path)
    assert rc == 1 and not report["ok"]
    assert [s["fingerprint"] for s in report["stale"]] == [
        ghost.fingerprint
    ]
    assert report["stale"][0]["unused"] == 1
    assert analysis.validate_lint_report(report) == []
    # the stale entry surfaces in the text rendering too
    txt = analysis.render_text([], [], report["stale"])
    assert "stale baseline entry" in txt and ghost.fingerprint in txt

    # an explicit sub-tree scope proves nothing about tree-wide
    # entries — staleness is only judged on the default scope
    scoped, rc2 = analysis.run_lint(
        [str(tmp_path)], baseline_path=bl_path
    )
    assert rc2 == 0 and scoped["stale"] == []


def test_baseline_rejects_unnamed_entries(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(
        {"version": 1, "suppressions": [{"fingerprint": "abc"}]}
    ))
    with pytest.raises(ValueError, match="named"):
        analysis.Baseline.load(str(p))


# ---- report schema --------------------------------------------------------


def test_lint_report_schema_valid_and_drift_detected():
    report, rc = analysis.run_lint()
    assert analysis.validate_lint_report(report) == []
    # unknown field = error; bool in a numeric field = error
    bad = dict(report)
    bad["surprise"] = 1
    assert any("surprise" in e for e in
               analysis.validate_lint_report(bad))
    bad2 = dict(report)
    bad2["suppressed"] = True
    assert any("bool" in e for e in
               analysis.validate_lint_report(bad2))


# ---- self-lint gate -------------------------------------------------------


def test_self_lint_gate_zero_unsuppressed_findings():
    """THE acceptance pin: grape-lint over the shipped tree is clean —
    every rule's historical bug class is un-shippable from here on,
    and every intentional exception is named in the baseline."""
    report, rc = analysis.run_lint()
    live = [f for f in report["findings"] if not f["suppressed"]]
    assert rc == 0 and live == [], live


# ---- compile_events -------------------------------------------------------


def test_compile_events_counts_real_compiles():
    import jax
    import jax.numpy as jnp

    fresh = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(17.0)
    with analysis.compile_events() as ev:
        fresh(x).block_until_ready()
    assert ev.compiles >= 1
    assert ev.compile_seconds() > 0
    # warmed call: the same wrapper compiles nothing
    with analysis.compile_events() as ev2:
        fresh(x).block_until_ready()
    assert ev2.compiles == 0
    # and the listener unregistered: events stop accumulating
    n = len(ev2.events)
    fresh(jnp.arange(18.0)).block_until_ready()
    assert len(ev2.events) == n


def test_compile_events_counts_persistent_cache_hits():
    """Under JAX_COMPILATION_CACHE_DIR (the recommended TPU-pod
    setup) a re-requested executable hits the disk cache and
    backend_compile never fires — but the re-request still means
    something retraced, which is exactly what a warmed zero-compile
    pin exists to catch.  The counter must see the cache-hit event
    stream too (code-review finding on the v1 backend-only counter)."""
    from jax._src import monitoring

    with analysis.compile_events() as ev:
        monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert ev.compiles == 1
    # and the plain-event listener unregistered with the block
    n = len(ev.events)
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert len(ev.events) == n


def test_state_struct_shared_between_worker_and_probe_cache():
    """The runner cache and the guard probe cache key on ONE
    structural-identity helper (utils/types.state_struct) — two
    private copies could drift and disagree on 'same structure'."""
    import libgrape_lite_tpu.guard.monitor as gm
    from libgrape_lite_tpu.utils.types import state_struct
    from libgrape_lite_tpu.worker.worker import Worker

    assert gm.state_struct is state_struct
    state = {"dist": np.zeros((4, 8), np.float32),
             "active": np.zeros((4,), np.int32)}
    assert Worker._state_struct(None, state) == state_struct(state)


# ---- artifact audits on a real compiled runner ----------------------------


def _small_fragment(fnum=1):
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(13)
    n, e = 220, 1600
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, w, directed=False,
    )


def test_artifact_audit_real_sssp_runner_clean():
    """A1+A2 on the actually-lowered fused SSSP runner: no literal
    constant above the threshold (the fragment rides as an argument,
    never baked — the PR 3 incident stays fixed) and the carry is
    donated."""
    from libgrape_lite_tpu.analysis.artifact import audit_fused_runner
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    w = Worker(SSSP(), _small_fragment())
    findings, info = audit_fused_runner(w, source=0)
    assert findings == [], [f.message for f in findings]
    assert info["offenders"] == []
    assert info["donated_args"] >= 1
    assert info["constants"] > 0  # the scan genuinely saw the module


def test_artifact_audit_catches_a_baked_constant():
    """Seed the R1 bug on purpose: a runner whose closure bakes a
    >64 KiB array must be flagged by the constant-bloat scan — the
    audit is live, not vacuously green."""
    import jax
    import jax.numpy as jnp

    from libgrape_lite_tpu.analysis.artifact import scan_constants

    baked = np.arange(50000, dtype=np.float32)  # ~195 KiB

    def bad(x):
        return x + jnp.asarray(baked)

    text = jax.jit(bad).lower(
        jax.ShapeDtypeStruct((50000,), np.float32)
    ).as_text()
    offenders, total, count = scan_constants(text)
    assert offenders, "baked 195KiB constant not detected"
    assert offenders[0]["bytes"] == 50000 * 4


def test_warm_matrix_zero_compiles():
    """A3 on a real fragment: after one warming pass, the whole
    canonical matrix (sssp/bfs x fused/guarded/batched/incremental)
    compiles NOTHING — counted on the real XLA compile stream, which
    is exactly where the PR 6 guarded re-jit and the pre-PR 8
    stepwise/probe re-jits were invisible to cache counters."""
    from libgrape_lite_tpu.analysis.artifact import warm_matrix_audit

    findings, info = warm_matrix_audit(_small_fragment())
    assert findings == [], [f.message for f in findings]
    assert info["unexpected_compiles"] == 0
    assert len(info["cells"]) == 8


def test_artifact_block_findings_respect_baseline(tmp_path, monkeypatch):
    """One defect must not render live in artifact.findings while the
    top-level record marks it suppressed: run_lint rewrites the
    artifact block's verdicts from the same baseline split."""
    from libgrape_lite_tpu import analysis as an

    fake = an.Finding("A2", "<lowered:SSSP>", 0, "SSSP.fused",
                      "fused runner donates no input buffer")

    def fake_audit(*a, **k):
        return [fake], {"findings": [fake.to_dict(False)]}

    monkeypatch.setattr(
        "libgrape_lite_tpu.analysis.run_artifact_audit", fake_audit
    )
    bl = an.Baseline(entries={}, path=str(tmp_path / "b.json"))
    bl.add(fake, "backend where donation legitimately does not lower")
    bl.save()
    # AST scope is an empty scratch dir: this pin is about the
    # artifact block's verdicts, and the custom baseline does not
    # carry the shipped tree's named exceptions
    scope = tmp_path / "empty_scope"
    scope.mkdir()
    report, rc = an.run_lint(
        [str(scope)],
        baseline_path=str(tmp_path / "b.json"), artifact=True,
    )
    assert rc == 0 and report["ok"]
    art = report["artifact"]["findings"]
    assert len(art) == 1 and art[0]["suppressed"] is True
    top = [f for f in report["findings"]
           if f["fingerprint"] == fake.fingerprint]
    assert top and top[0]["suppressed"] is True


def test_guarded_probe_shared_across_monitors():
    """The R2 fix behind the matrix pin: two guarded queries (two
    GuardMonitors) share one compiled probe through the fragment-
    keyed cache instead of re-jitting per query."""
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    frag = _small_fragment()
    w = Worker(SSSP(), frag)
    w.query(source=0, guard="halt")
    probe1 = w._guard_monitor._probe
    with analysis.compile_events() as ev:
        w.query(source=1, guard="halt")
    assert w._guard_monitor._probe is probe1
    assert ev.compiles == 0


# ---- CLI surface ----------------------------------------------------------


def test_cli_lint_seeded_violation_and_clean_tree(tmp_path):
    """Acceptance: `cli lint` exits nonzero on a seeded R1-R4
    violation in a scratch module and 0 on the shipped tree."""
    from libgrape_lite_tpu.cli import lint_main

    bad = tmp_path / "seeded.py"
    bad.write_text(textwrap.dedent("""
        import jax
        import numpy as np

        big = np.zeros((512, 512))

        class Worker:
            def _check_dyn_view(self):
                pass

            def _cached_runner(self, key, build):
                return build()

            def _runner_for(self, max_rounds, state):
                key = (id(state),)
                return self._cached_runner(key, lambda: None)

            def query(self, source=0):
                def stepper(x):
                    return x + big
                return jax.jit(stepper)(source)
    """))
    assert lint_main([str(bad)]) == 1
    assert lint_main([]) == 0
    assert lint_main(["--json"]) == 0
    # a mistyped path fails the gate (exit 2), never lints zero
    # files and reports clean
    assert lint_main([str(tmp_path / "no_such_dir")]) == 2
    # an EMPTY --update-baseline reason (an unset shell variable) is
    # a usage error, not a silent fall-through to a plain lint run
    assert lint_main(["--update-baseline", ""]) == 2


# ---- the options PR 28 removed are gone, not hidden -----------------------

REMOVED_OPTIONS = [
    "GRAPE_SPMV", "GRAPE_SPMV_STRICT", "GRAPE_PACK_SCAN",
    "GRAPE_PACK_COMPOSE", "GRAPE_PACK_CFG", "GRAPE_PACK_VMEM_BUDGET",
    "GRAPE_SSSP_FUSE",
]


@pytest.mark.parametrize("name", REMOVED_OPTIONS)
def test_removed_option_is_spelled_nowhere(name):
    """The pull has one path (ops/segment.py): no file of the library,
    the scripts, the smoke or the README reads or offers an option that
    selected another."""
    import os
    import re

    root = os.path.join(os.path.dirname(__file__), "..")
    files = [os.path.join(root, f) for f in ("chip_smoke.py", "README.md")]
    for top in ("libgrape_lite_tpu", "scripts"):
        for d, _, names in os.walk(os.path.join(root, top)):
            files += [os.path.join(d, n) for n in names
                      if n.endswith((".py", ".sh", ".md"))]
    word = re.compile(rf"\b{name}\b")
    hits = [os.path.relpath(f, root) for f in files
            if word.search(open(f, encoding="utf-8").read())]
    assert hits == [], f"{name} is still spelled in {hits}"


def test_setting_a_removed_option_changes_nothing(monkeypatch):
    """`GRAPE_SPMV=pack` in the environment: BFS answers with the same
    bytes and a checkpoint's fingerprint is the same, so one written
    under the default by an earlier tree still restores."""
    from libgrape_lite_tpu.ft.fingerprint import compute_fingerprint
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.conftest import rand_frag

    frag = rand_frag(2, weighted=False)

    def run():
        w = Worker(APP_REGISTRY["bfs"](), frag)
        w.query(source=0)
        return (w.result_values().tobytes(),
                compute_fingerprint(w.app, frag, {"source": 0}))

    monkeypatch.delenv("GRAPE_SPMV", raising=False)
    values, fingerprint = run()
    assert fingerprint["spmv_mode"] == "auto"
    monkeypatch.setenv("GRAPE_SPMV", "pack")
    assert run() == (values, fingerprint)
