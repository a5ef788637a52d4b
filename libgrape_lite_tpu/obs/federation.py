"""Stats federation — one registry for every ``*_STATS`` surface.

Before this module, operational truth was scattered over six
module-level registries, each with a private snapshot convention:
``PLAN_STATS`` / ``SPGEMM_STATS`` / ``PARTITION_STATS`` and the
superstep pipeline's (gone since) were raw mutable dicts copied ad
hoc, while ``PUMP_STATS`` / ``FLEET_STATS`` were classes with
``snapshot()``.
The federation gives them one namespace-keyed ``snapshot()`` /
``reset()`` API, and ``self_check()`` kills declared-but-unwired
namespaces the same way ``check_bench_schema.self_check()`` kills
declared-but-unwired bench blocks: ``EXPECTED`` names every namespace
the tree is supposed to register and the module that owns it, and the
check imports each owner and demands a live, JSON-serializable
registration.  grape-lint R8 (``unfederated-stats``) fossilizes the
retired class: a module-level ``*_STATS`` registry that never
registers here is a finding.

Registration happens at import of the owning module — the federation
itself imports nothing outside the stdlib, so any module (ops/,
fragment/, parallel/, serve/, fleet/) can register without a cycle.

``FederatedStats`` is the drop-in for the raw-dict registries: a
``dict`` subclass, so every existing ``STATS["k"] += 1`` hot-path
call site keeps working unchanged, but snapshots are taken under the
federation lock with per-value list/dict copies — callers can no
longer read a half-updated dict.
"""

from __future__ import annotations

import copy
import json
import threading
from typing import Any, Callable, Dict, List, Optional

# namespace -> {"snapshot": fn, "reset": fn|None, "module": str}
_REGISTRY: Dict[str, Dict[str, Any]] = {}
_LOCK = threading.Lock()

# The wiring contract: every namespace the shipped tree must register,
# and the module whose import performs the registration.  self_check()
# imports each owner — a namespace declared here but never registered
# (or registered with a broken snapshot) is an error, exactly the
# check_bench_schema discipline for bench blocks.
EXPECTED: Dict[str, str] = {
    "spgemm": "libgrape_lite_tpu.ops.spgemm_pack",
    "partition": "libgrape_lite_tpu.fragment.partition",
    "pump": "libgrape_lite_tpu.serve.pipeline",
    "fleet": "libgrape_lite_tpu.fleet.budget",
    "slo": "libgrape_lite_tpu.obs.slo",
    "recorder": "libgrape_lite_tpu.obs.recorder",
    "autopilot": "libgrape_lite_tpu.autopilot.signals",
    "vc_tiles": "libgrape_lite_tpu.fragment.vertexcut",
    "gang": "libgrape_lite_tpu.obs.gang",
    "fold": "libgrape_lite_tpu.ops.segment",
    "gather": "libgrape_lite_tpu.ops.segment",
    "row_ends": "libgrape_lite_tpu.ops.segment",
    "scan": "libgrape_lite_tpu.ops.segment",
    "lcc": "libgrape_lite_tpu.models.lcc_beta",
    "cdlp": "libgrape_lite_tpu.models.cdlp",
    "setup": "libgrape_lite_tpu.obs.tracer",
    "rounds": "libgrape_lite_tpu.worker.worker",
}


def register(
    namespace: str,
    snapshot: Callable[[], Dict[str, Any]],
    reset: Optional[Callable[[], None]] = None,
    module: str = "",
) -> None:
    """Register one stats surface under `namespace`.

    Re-registration of the same namespace overwrites (module reloads
    in tests re-run the module body); two DIFFERENT modules claiming
    one namespace is a wiring bug and raises.
    """
    if not namespace or not namespace.replace("_", "").isalnum():
        raise ValueError(f"bad federation namespace: {namespace!r}")
    with _LOCK:
        prev = _REGISTRY.get(namespace)
        if prev is not None and module and prev["module"] and \
                prev["module"] != module:
            raise ValueError(
                f"federation namespace {namespace!r} already "
                f"registered by {prev['module']} (now: {module})"
            )
        _REGISTRY[namespace] = {
            "snapshot": snapshot, "reset": reset, "module": module,
        }


def registered() -> List[str]:
    """Sorted namespaces currently registered."""
    with _LOCK:
        return sorted(_REGISTRY)


def snapshot(namespace: Optional[str] = None) -> Dict[str, Any]:
    """One coherent read of every registered surface (or just one).

    Returns ``{namespace: {field: value, ...}, ...}`` — with a
    namespace argument, that namespace's fields directly.
    """
    with _LOCK:
        if namespace is not None:
            ent = _REGISTRY.get(namespace)
            if ent is None:
                raise KeyError(
                    f"unregistered federation namespace: {namespace!r}"
                )
            return dict(ent["snapshot"]())
        return {ns: dict(ent["snapshot"]())
                for ns, ent in sorted(_REGISTRY.items())}


def reset(namespace: Optional[str] = None) -> None:
    """Reset one namespace, or every namespace that supports reset."""
    with _LOCK:
        if namespace is not None:
            ent = _REGISTRY.get(namespace)
            if ent is None:
                raise KeyError(
                    f"unregistered federation namespace: {namespace!r}"
                )
            ents = [ent]
        else:
            ents = list(_REGISTRY.values())
    for ent in ents:
        if ent["reset"] is not None:
            ent["reset"]()


def self_check() -> List[str]:
    """Errors when the wiring contract is broken, [] when clean.

    Imports every EXPECTED owner module (import performs the
    registration), then demands: the namespace is registered, its
    registered module matches the declaration, and its snapshot is a
    JSON-serializable dict.  Mirrors check_bench_schema.self_check():
    a declared-but-unwired namespace can never report clean.
    """
    import importlib

    errors: List[str] = []
    for ns, owner in sorted(EXPECTED.items()):
        try:
            importlib.import_module(owner)
        except Exception as e:  # pragma: no cover — partial checkouts
            errors.append(f"{ns}: owner module {owner} failed to "
                          f"import: {type(e).__name__}: {e}")
            continue
        with _LOCK:
            ent = _REGISTRY.get(ns)
        if ent is None:
            errors.append(
                f"{ns}: declared in federation.EXPECTED but never "
                f"registered by {owner} — declared-but-unwired"
            )
            continue
        if ent["module"] and ent["module"] != owner:
            errors.append(
                f"{ns}: registered by {ent['module']}, declared "
                f"owner is {owner}"
            )
        try:
            snap = ent["snapshot"]()
        except Exception as e:
            errors.append(f"{ns}: snapshot() raised "
                          f"{type(e).__name__}: {e}")
            continue
        if not isinstance(snap, dict):
            errors.append(f"{ns}: snapshot() returned "
                          f"{type(snap).__name__}, want dict")
            continue
        try:
            json.dumps(snap)
        except (TypeError, ValueError) as e:
            errors.append(f"{ns}: snapshot() not JSON-serializable: "
                          f"{e}")
    return errors


class FederatedStats(dict):
    """A module-level stats dict that self-registers at construction.

    Drop-in for the raw-dict registries: mutation sites keep the plain
    ``STATS["planned"] += 1`` / ``STATS["declines"].append(...)``
    idiom, but ``snapshot()`` copies under the federation lock (lists
    and dicts value-copied) and ``reset()`` restores the construction-
    time initial state — the snapshot protocol PumpStats/FleetStats
    already had, now shared by every registry.
    """

    def __init__(self, namespace: str, initial: Dict[str, Any],
                 register_: bool = True):
        super().__init__(copy.deepcopy(initial))
        self.namespace = namespace
        self._initial = copy.deepcopy(initial)
        if register_:
            register(namespace, self.snapshot, self.reset,
                     module=self.__class__.__module__
                     if type(self) is not FederatedStats
                     else _caller_module())

    def snapshot(self) -> Dict[str, Any]:
        out = {}
        for k, v in self.items():
            if isinstance(v, list):
                out[k] = list(v)
            elif isinstance(v, dict):
                out[k] = dict(v)
            else:
                out[k] = v
        return out

    def reset(self) -> None:
        self.clear()
        self.update(copy.deepcopy(self._initial))


def _caller_module() -> str:
    """Module name of the frame constructing a FederatedStats — the
    registry's owner for self_check's module-match."""
    import inspect

    frame = inspect.currentframe()
    try:
        # _caller_module <- __init__ <- owning module body
        f = frame.f_back.f_back
        while f is not None:
            mod = f.f_globals.get("__name__", "")
            if mod != __name__:
                return mod
            f = f.f_back
        return ""
    finally:
        del frame
