"""GuardMonitor: one probe per cadence, breach policy, rollback state.

The monitor owns detection and policy; the Worker owns execution (it
re-places restored state and rewinds its loop counters).  Flow per
probe:

  1. host check: the psum'd active vote must lie in [0, total_vnum]
     (negative active is the app's own cooperative abort, not a
     breach — the loop exits before the monitor ever sees it);
  2. ONE jitted device dispatch evaluates every applicable invariant,
     the carry digest, and the float residual;
  3. invariant failures -> breach verdict; otherwise, while the run is
     still voting active, the watchdog checks the digest history;
  4. policy: warn logs and continues; halt raises with the diagnostic
     bundle; rollback asks the Worker to restore the last good
     snapshot (requires a CheckpointManager) and flips the monitor
     into paranoid mode (probe every round) so a deterministic fault
     is localized to its exact superstep on replay.

Watchdog verdicts never roll back: the cycle/stagnation proof is a
property of the healthy deterministic loop, so a replay would diverge
identically — they halt (or warn) instead.
"""

from __future__ import annotations

import weakref
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.guard.config import GuardConfig
from libgrape_lite_tpu.guard.watchdog import (
    DivergenceWatchdog,
    carry_digest,
    digest_hex,
)
from libgrape_lite_tpu.utils import logging as glog
from libgrape_lite_tpu.utils.types import state_struct

_HISTORY = 64  # rounds of digest/active context kept for the bundle

# compiled probes shared across monitors: a GuardMonitor is created
# per query — and per LANE per batch in serve/batch.py — so holding
# the jitted probe on the instance re-traced and re-compiled it for
# every guarded dispatch (jit caches by wrapper identity; the wrapper
# was new each time).  The cache is keyed weakly on the fragment
# (probes bind invariants resolved against it) and strongly on (app
# class, app.trace_key(), carry structure) — the same identity the
# worker's runner cache uses; a repack/mutation swaps the fragment
# and naturally starts a fresh entry.  Found by grape-lint R2, the
# PR 6 guarded-serve re-jit class (analysis/rules.py).
_PROBE_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class GuardError(RuntimeError):
    """A guard breach under the halt policy (or an exhausted rollback
    budget).  `.bundle` carries the structured diagnostic."""

    def __init__(self, msg: str, bundle: dict):
        super().__init__(msg)
        self.bundle = bundle


class InvariantBreachError(GuardError):
    """An app-declared invariant failed on the live carry."""


class DivergenceError(GuardError):
    """The watchdog proved an oscillation cycle or flagged residual
    stagnation."""


@dataclass
class Breach:
    action: str  # "halt" | "rollback"
    verdict: dict
    bundle: dict
    message: str


class GuardMonitor:
    def __init__(self, app, frag, config: GuardConfig, *,
                 ckpt=None, ledger=None):
        self.app = app
        self.frag = frag
        self.config = config
        self.ckpt = ckpt
        self.watchdog = DivergenceWatchdog(config.stagnation_window)
        self.paranoid = False
        self.rollbacks = 0
        self.probes = 0
        self.mutations = 0  # mutation boundaries crossed (dyn/)
        self.breaches: List[dict] = []
        self._invariants = None
        self._probe = None
        self._ledger = ledger
        self._digest_hist: List = []
        self._active_hist: List = []
        self._last_breach = None

    # ---- probe construction ---------------------------------------------

    def due(self, rounds: int) -> bool:
        return (
            self.paranoid
            or self.config.every <= 1
            or rounds % self.config.every == 0
        )

    def can_rollback(self) -> bool:
        return self.ckpt is not None

    def on_mutation(self, new_frag, ledger=None) -> None:
        """Mutation-boundary reset (dyn/): after a delta apply the
        graph — and with it the deterministic superstep operator —
        changed.  A digest match against a pre-mutation round no
        longer proves a cycle (the same carry under a DIFFERENT
        operator evolves differently), so the watchdog history must
        clear or a legitimately re-visited state raises a
        false-positive DivergenceError.  The compiled probe is also
        dropped: state shapes and the fragment arrays it binds may
        have been rebuilt.  `ledger` is the re-resolved plan ledger
        for post-mutation breach bundles — the pre-mutation snapshot
        would misattribute modeled cost, so absent a fresh one it is
        nulled rather than left stale."""
        self.frag = new_frag
        self._ledger = ledger
        self.mutations += 1
        self.watchdog.reset()
        self._probe = None
        self._probe_inv = None
        self._invariants = None
        # a pre-mutation snapshot is NOT a valid rollback target for
        # the rebuilt graph (shapes/pids may differ, and replaying
        # would re-run already-applied mutations) — drop it so a later
        # rollback verdict degrades to halt.  Unreachable today
        # (checkpointing MutationContext apps is rejected up front),
        # but cheap insurance against that restriction loosening.
        self.ckpt = None
        glog.vlog(
            1, "guard: mutation boundary — watchdog history reset, "
            "probe re-resolves against the mutated fragment",
        )

    def _resolve(self, carry: Dict) -> None:
        cache = _PROBE_CACHE.setdefault(self.frag, {})
        key = (
            type(self.app).__qualname__,
            self.app.trace_key(),
            state_struct(carry),
        )
        hit = cache.get(key)
        if hit is None:
            hit = self._build_probe(carry)
            cache[key] = hit
        self._invariants, self._probe, self._probe_inv = hit

    def _build_probe(self, carry: Dict):
        """(kept invariants, jitted probe, jitted invariants-only
        probe or None) — built once per (fragment, app class +
        hyperparameters, carry structure) and shared through
        _PROBE_CACHE across every monitor of that identity."""
        declared = self.app.invariants(self.frag, carry)
        kept, dropped = [], []
        for inv in declared:
            (kept if set(inv.requires) <= set(carry) else dropped).append(inv)
        if dropped:
            glog.log_info(
                "guard: dropped invariants whose carry keys are absent: "
                + ", ".join(i.name for i in dropped)
            )
        float_keys = sorted(
            k for k in carry if np.dtype(carry[k].dtype).kind == "f"
        )

        # `dev` rides as a jit ARGUMENT (DeviceFragment is a pytree):
        # closing over it would bake multi-MB fragment arrays into the
        # probe executable as XLA constants
        def inv_part(dev, prev, cur):
            oks, vals = [], []
            for inv in kept:
                ok, val = inv.check(dev, prev, cur)
                oks.append(ok)
                vals.append(val)
            oks = (
                jnp.stack(oks) if oks else jnp.zeros((0,), jnp.bool_)
            )
            vals = (
                jnp.stack(vals) if vals else jnp.zeros((0,), jnp.float32)
            )
            return oks, vals

        def probe(dev, prev, cur):
            oks, vals = inv_part(dev, prev, cur)
            digest = carry_digest(cur)
            residual = None
            if float_keys:
                # non-finite deltas (inf sentinels present in BOTH
                # carries give inf - inf = NaN; a newly-reached inf ->
                # finite transition gives inf) carry no usable
                # magnitude — mask them so one padded +inf row cannot
                # poison the stagnation metric with NaN forever
                diffs = []
                for k in float_keys:
                    d = jnp.abs(
                        cur[k].astype(jnp.float32)
                        - prev[k].astype(jnp.float32)
                    )
                    diffs.append(jnp.max(
                        jnp.where(jnp.isfinite(d), d, jnp.float32(0))
                    ))
                residual = jnp.max(jnp.stack(diffs))
            return oks, vals, digest, residual

        # invariants-only probe for callers that already hold the
        # digest/residual (the guarded-fused chunk runner emits them
        # as extra loop outputs); apps with no invariants then skip
        # the probe dispatch entirely
        return kept, jax.jit(probe), (jax.jit(inv_part) if kept else None)

    # ---- per-probe entry point ------------------------------------------

    def check(self, prev: Dict, cur: Dict, rounds: int,
              active: int, *, digest=None,
              residual=None) -> Optional[Breach]:
        """One probe.  `digest`/`residual` may be supplied by a caller
        that computed them inside its own dispatch (the guarded-fused
        chunk runner emits the carry digest and masked residual as
        extra loop outputs — value-identical to the probe's, same
        functions on the same global carry); the monitor then runs
        only the invariants-only probe, or nothing at all when the app
        declares no invariants."""
        self.probes += 1
        obs.metrics().counter("grape_guard_probes_total").inc()
        if self._probe is None:
            self._resolve(cur)
        vnum = self.frag.dev.total_vnum
        if active > vnum:
            verdict = {
                "kind": "active_range",
                "round": rounds,
                "active": int(active),
                "detail": (
                    f"active vote {int(active)} exceeds the vertex count "
                    f"{vnum} — the termination allreduce is corrupt"
                ),
            }
            return self._policy(verdict, rounds, active, failed=None)

        if digest is None:
            oks, vals, digest_words, residual = self._probe(
                self.frag.dev, prev, cur
            )
            digest = tuple(int(x) for x in np.asarray(digest_words))
            if residual is not None:
                residual = float(residual)
        elif self._probe_inv is not None:
            oks, vals = self._probe_inv(self.frag.dev, prev, cur)
        else:
            oks = vals = np.zeros((0,))
        oks = np.asarray(oks)
        vals = np.asarray(vals)
        self._digest_hist.append((rounds, digest_hex(digest)[:16]))
        self._active_hist.append((rounds, int(active)))
        del self._digest_hist[:-_HISTORY], self._active_hist[:-_HISTORY]

        failed = [
            (inv, float(v))
            for inv, ok, v in zip(self._invariants, oks, vals)
            if not bool(ok)
        ]
        if failed:
            verdict = {
                "kind": "invariant",
                "round": rounds,
                "failed": {inv.name: v for inv, v in failed},
                "detail": "; ".join(
                    f"{inv.name}: {inv.description} (measure={v:g})"
                    for inv, v in failed
                ),
            }
            return self._policy(
                verdict, rounds, active,
                failed=tuple(inv.name for inv, _ in failed),
            )
        if active > 0:
            # a converged final round legitimately repeats the previous
            # digest (nothing changed, active==0) — only a still-active
            # loop can be diagnosed as cycling/stagnating
            verdict = self.watchdog.observe(
                rounds, digest,
                None if residual is None else float(residual),
            )
            if verdict is not None:
                return self._policy(verdict, rounds, active, failed=None)
        return None

    # ---- policy ----------------------------------------------------------

    def _policy(self, verdict: dict, rounds: int, active: int,
                failed) -> Optional[Breach]:
        bundle = self._bundle(verdict, rounds, active)
        self.breaches.append(bundle)
        # the breach lands on the trace timeline as an instant event,
        # so a Perfetto view shows WHICH superstep span it interrupted;
        # the bundle carries the trace id for the reverse lookup
        obs.metrics().counter("grape_guard_breaches_total").inc()
        obs.tracer().instant(
            "guard_breach", kind=verdict["kind"], round=rounds,
            policy=self.config.policy,
            detail=verdict.get("detail", ""),
        )
        from libgrape_lite_tpu.obs.recorder import RECORDER

        RECORDER.trigger(
            "guard_breach",
            extra={"kind": verdict["kind"], "round": rounds,
                   "policy": self.config.policy},
            guard=bundle,
        )
        msg = (
            f"guard: {verdict['kind']} breach at superstep {rounds} "
            f"(policy={self.config.policy}): {verdict['detail']}"
        )
        if self.config.policy == "warn":
            glog.log_info(msg + " — continuing (warn policy)")
            return None
        action = "halt"
        if self.config.policy == "rollback" and verdict["kind"] == "invariant":
            if not self.can_rollback():
                glog.log_info(
                    "guard: rollback policy without a checkpoint manager "
                    "(no checkpoint_every/checkpoint_dir) — halting instead"
                )
            elif (
                self.rollbacks > 0
                and self._last_breach == (rounds, failed)
            ):
                # the paranoid replay reproduced the exact breach: the
                # fault is a deterministic property of this superstep,
                # not transient state damage — localized, stop retrying
                glog.log_info(
                    f"guard: breach recurred at superstep {rounds} after a "
                    "rollback — the fault is deterministic; localized, "
                    "halting"
                )
                bundle["localized_round"] = rounds
            elif self.rollbacks >= self.config.max_rollbacks:
                glog.log_info(
                    f"guard: rollback budget ({self.config.max_rollbacks}) "
                    "exhausted — halting"
                )
            else:
                action = "rollback"
        elif self.config.policy == "rollback":
            # oscillation/stagnation replay identically — never roll back
            glog.log_info(
                f"guard: {verdict['kind']} verdicts are deterministic "
                "under replay — halting instead of rolling back"
            )
        self._last_breach = (rounds, failed)
        glog.log_info(msg)
        return Breach(action=action, verdict=verdict, bundle=bundle, message=msg)

    def raise_breach(self, breach: Breach):
        cls = (
            InvariantBreachError
            if breach.verdict["kind"] in ("invariant", "active_range")
            else DivergenceError
        )
        raise cls(breach.message, breach.bundle)

    # ---- rollback --------------------------------------------------------

    def rollback(self, breach: Breach):
        """(restored_state, meta) of the last good snapshot; flips the
        monitor paranoid and resets the watchdog history (replayed
        rounds must not re-match their own old digests)."""
        from libgrape_lite_tpu.ft.checkpoint import restore_latest

        self.ckpt.wait()  # an in-flight write must land before listing
        with obs.tracer().span(
            "rollback", breach_round=breach.verdict["round"]
        ):
            state, meta = restore_latest(
                self.ckpt.directory, self.ckpt.fingerprint
            )
        self.rollbacks += 1
        obs.metrics().counter("grape_guard_rollbacks_total").inc()
        self.paranoid = True
        self.watchdog.reset()
        glog.log_info(
            f"guard: rolled back to superstep {int(meta['rounds'])} "
            f"(breach at superstep {breach.verdict['round']}, "
            f"rollback {self.rollbacks}/{self.config.max_rollbacks}); "
            "replaying in paranoid mode"
        )
        return state, meta

    # ---- diagnostics -----------------------------------------------------

    def _bundle(self, verdict: dict, rounds: int, active: int) -> dict:
        try:
            from libgrape_lite_tpu.ft.fingerprint import (
                app_registry_name,
                fragment_content_hash,
            )

            fingerprint = (
                dict(self.ckpt.fingerprint) if self.ckpt is not None else {
                    "app": app_registry_name(self.app),
                    "fragment_hash": fragment_content_hash(self.frag),
                    "fnum": self.frag.fnum,
                    "vp": self.frag.vp,
                }
            )
        except Exception as e:  # diagnostics must never mask the breach
            fingerprint = {"error": f"{type(e).__name__}: {e}"}
        ledger = None
        if self._ledger:
            ledger = {
                "edges": self._ledger.get("edges"),
                "totals": {
                    k: v
                    for k, v in self._ledger.get("totals", {}).items()
                    if not isinstance(v, dict)
                },
            }
        return {
            "verdict": dict(verdict),
            "round": rounds,
            "active": int(active),
            # None when obs/ is disarmed; with tracing on, the id ties
            # this bundle to the trace file's metadata block
            "trace_id": obs.trace_id(),
            "policy": self.config.policy,
            "paranoid": self.paranoid,
            "rollbacks": self.rollbacks,
            "recent_digests": list(self._digest_hist),
            "active_history": list(self._active_hist),
            "invariants": [i.name for i in (self._invariants or [])],
            "op_ledger": ledger,
            "config_fingerprint": fingerprint,
            "guard_config": asdict(self.config),
        }

    def report(self) -> dict:
        return {
            "policy": self.config.policy,
            "every": self.config.every,
            "probes": self.probes,
            "paranoid": self.paranoid,
            "rollbacks": self.rollbacks,
            "mutations": self.mutations,
            "breaches": list(self.breaches),
            "invariants": [i.name for i in (self._invariants or [])],
        }
