"""grape-lint rule catalogue.

Every rule fossilizes a bug this repo actually shipped (and caught by
hand in a review pass, per CHANGES.md) — the linter's job is to make
each class un-shippable instead of re-findable.  The rule ids are
stable contract: findings, baselines, and commit messages cite them.

The catalogue is data (id -> Rule); the checkers live in
analysis/astlint.py (R1-R8, pure AST) and analysis/artifact.py
(A1-A3, audits on actually-lowered/compiled runners).  Layer 1 proves
the source can't express the defect; Layer 2 recounts from the
shipped artifact — the same two-sided discipline the pack op ledger
applies to op counts (model from the plan, recount from the arrays,
fail on drift).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Rule:
    id: str
    slug: str
    summary: str   # what the rule forbids
    history: str   # the shipped bug it would have caught


RULES: Dict[str, Rule] = {
    r.id: r
    for r in [
        Rule(
            "R1", "baked-constant",
            "a jit/shard_map/pallas_call-traced body references a "
            "closure-captured np/jnp array or a frag/.dev attribute "
            "that is not a parameter — XLA bakes it into the "
            "executable as a literal constant",
            "PR 3: the guard probe closed over frag.dev, baking "
            "MB-scale fragment CSRs into the probe executable as XLA "
            "constants; fixed by passing dev as a jit argument",
        ),
        Rule(
            "R2", "uncached-jit",
            "jax.jit (or a _make_*/_compile_* runner builder) is "
            "invoked inside a per-query/per-dispatch code path "
            "instead of behind the runner cache — every dispatch "
            "silently retraces and recompiles",
            "PR 6: the guarded serve path's batched PEval minted a "
            "fresh jax.jit wrapper per batch, so steady guarded "
            "streams re-jitted every dispatch, invisibly to the "
            "zero-recompile counters",
        ),
        Rule(
            "R3", "cache-key-field",
            "a runner-builder argument does not appear in the "
            "_cached_runner cache key — two queries differing only "
            "in that argument silently share one compile",
            "PR 6 (pinned at HEAD): the fused-runner cache key "
            "initially omitted max_rounds, so a second query with a "
            "different round limit reused the first compile's baked "
            "while_loop bound",
        ),
        Rule(
            "R4", "dyn-view-parity",
            "a public query entrypoint does not reach the dyn "
            "stale-view check (_check_dyn_view / _ensure_dyn_view) "
            "and guard-config resolution — an uncontracted app can "
            "silently compute on the pre-delta graph",
            "PR 7 (post-hoc review): GUARDED query_batch ran the "
            "stale-view check after the guard routing, and "
            "query_stepwise skipped it entirely — both silently "
            "served the pre-delta graph on a staged dyn view",
        ),
        Rule(
            "R5", "eager-log-bool-schema",
            "a level-gated vlog call formats its message eagerly "
            "(f-string/%/.format/concat), or a numeric schema "
            "validator accepts bool through isinstance(x, int)",
            "PR 5: hot-loop f-strings were formatted-then-dropped at "
            "disabled vlog levels (measurable per round), and the "
            "bench schema checker accepted bools in numeric fields "
            "(bool is an int subclass)",
        ),
        Rule(
            "R7", "sync-in-pump",
            "a host-sync forcer (block_until_ready, jax.device_get, "
            "np/jnp.asarray, or int()/float() on a non-literal value) "
            "is reached from serve/pipeline.py dispatch-stage code "
            "(_dispatch*/_fill* self-call chains) outside the audited "
            "harvest contract (serve/pipeline.PUMP_HARVEST_SYNCS) — "
            "one stray sync re-serialises the whole dispatch window",
            "PR 12 (preventive): the synchronous serve loop blocked "
            "pulling every lane's result to host before the next "
            "batch could dispatch — the exact defect class the async "
            "pump removes; fossilized so it cannot creep back into "
            "the dispatch stage (zero-entry baseline)",
        ),
        Rule(
            "R8", "unfederated-stats",
            "a module-level *_STATS surface is neither constructed as "
            "obs.federation.FederatedStats nor registered with "
            "obs.federation.register in its defining module — the "
            "ledger is invisible to federation.snapshot(), the live "
            "/metrics exporter, and every postmortem bundle",
            "PR 15: PLAN/SPGEMM/PARTITION_STATS and the superstep "
            "pipeline's ledger (gone since PR 42) were four "
            "hand-rolled module dicts and PUMP/FLEET_STATS two ad-hoc "
            "classes, each with its own snapshot/reset idiom; a "
            "scrape could not see them and a new one would have "
            "drifted the same way (zero-entry baseline)",
        ),
        Rule(
            "R9", "cache-key-completeness",
            "a call into the autopilot result cache "
            "(autopilot/cache.py lookup()/store()) does not name "
            "every field of the result identity — the compat key, "
            "the lane source, and the fence epoch "
            "(cache.CACHE_KEY_FIELDS) — so two structurally "
            "different queries (or two graph versions) could share "
            "one cached answer",
            "PR 16 (preventive): the result cache is sound only "
            "because its key carries the FULL compat_key plus the "
            "router fence; the R3 incident (a cache key missing "
            "max_rounds silently shared one compile) shows exactly "
            "how a dropped key field ships — fossilized here for the "
            "result cache before it can recur (zero-entry baseline)",
        ),
        Rule(
            "R10", "pinned-rate-constant",
            "a module-level float-literal pricing RATE (a *_BPS / "
            "*_HZ / *_CYC_PER_ELEM / *_PER_CYCLE constant or a "
            "GATHER_RATES table) is defined outside "
            "ops/calibration.py — a private rate copy that the "
            "calibration pass cannot fit and the drift gate cannot "
            "see, so the surface it prices silently diverges from "
            "measured truth",
            "PR 17: _MXU_CYC_PER_ELEM = 0.008 lived in BOTH "
            "ops/spgemm_pack.py and scripts/pack_cost_model.py, and "
            "pipeline/partition carried their own VPU/ICI copies — "
            "five pricing surfaces, four rate tables, none of them "
            "fittable; collapsed onto the RateProfile (zero-entry "
            "baseline over the migrated tree)",
        ),
        Rule(
            "R11", "raw-axis-name",
            "a models/ module spells a SUMMA mesh axis name as a raw "
            "string literal ('vcrow'/'vccol') instead of importing "
            "VC_ROW_AXIS/VC_COL_AXIS from parallel/comm_spec.py — a "
            "private copy of the mesh contract that a rename (or a "
            "third axis) silently misses, turning a compile-time "
            "import error into a wrong-axis collective at runtime",
            "PR 19 (preventive): the pipelined SUMMA round put the "
            "row-axis psum on the hot path of three apps at once; "
            "every collective's correctness now hangs on the axis "
            "names matching mesh2d()'s, so the string form is "
            "fossilized out of models/ (zero-entry baseline)",
        ),
        Rule(
            "A1", "constant-bloat",
            "the lowered HLO of a fused runner holds a literal "
            "constant above the byte threshold — an R1 escape "
            "caught end-to-end on the shipped artifact",
            "PR 3: same baked-constant incident as R1, audited here "
            "from the lowered module instead of the source",
        ),
        Rule(
            "A2", "donation",
            "the fused runner's lowered module donates no input "
            "buffer — the carry is double-buffered in HBM instead "
            "of aliased into the loop",
            "PR 6 era: the fused runner relies on donate_argnums "
            "aliasing the placed carry; losing it would silently "
            "double peak HBM at scale",
        ),
        Rule(
            "A3", "surprise-compile",
            "a warmed query of the canonical matrix (sssp/bfs x "
            "fused/guarded/batched/incremental) triggers an XLA "
            "compile — the runner/probe caches leak",
            "PR 6: per-batch re-jit of the guarded batched PEval; "
            "PR 8 first run: the stepwise/guarded single-step runner "
            "and the guard probe were rebuilt per query (fixed under "
            "R2 in this PR)",
        ),
    ]
}


def describe(rule_id: str) -> str:
    r = RULES[rule_id]
    return f"[{r.id} {r.slug}] {r.summary}"
