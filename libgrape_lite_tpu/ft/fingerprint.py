"""Query identity fingerprint for checkpoint validation.

A checkpoint is resumable only against the *same* computation: same
app, same fragment content, same mesh shape, same query arguments, and
the same numeric configuration (x64 and SpMV-path selection change
float reduction dtypes/order, which would break the byte-identical
resume contract).  The fingerprint captures exactly that set — and
deliberately NOT process-local identities like compiled-runner cache
keys or mirror-plan uids, which differ between the killed process and
the resuming one even for identical configs.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict

import numpy as np

FINGERPRINT_FORMAT = 1


def stable_config_digest(obj: Any) -> str:
    """sha256 hex of a canonical-JSON rendering of `obj` — the shared
    config-fingerprint primitive for cache keys (the spgemm plan cache
    keys its entries through this).  Non-JSON leaves
    fall back to str(), so dataclass asdict() payloads with numpy
    scalars stay stable across processes."""
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()


def app_registry_name(app) -> str:
    """The APP_REGISTRY name for this app instance (first registered
    alias, sorted for determinism), falling back to the class name for
    unregistered app classes (tests, user subclasses)."""
    from libgrape_lite_tpu.models import APP_REGISTRY

    names = sorted(k for k, v in APP_REGISTRY.items() if v is type(app))
    return names[0] if names else type(app).__name__


def _hash_array(h, a) -> None:
    a = np.asarray(a)
    if a.dtype == object:  # string oids
        for s in a.tolist():
            h.update(str(s).encode("utf-8"))
            h.update(b"\x00")
    else:
        h.update(np.ascontiguousarray(a).tobytes())


def fragment_content_hash(frag) -> str:
    """sha256 over the fragment's host CSR content (topology, weights,
    oid assignment) + shape metadata.  Cached on the fragment — the
    arrays are immutable after build, and a rebuild-on-mutate produces
    a fresh fragment object."""
    cached = getattr(frag, "_ft_content_hash", None)
    if cached is not None:
        return cached
    h = hashlib.sha256()
    h.update(
        json.dumps(
            {
                "fnum": frag.fnum,
                "vp": frag.vp,
                "directed": bool(frag.directed),
                "weighted": bool(frag.weighted),
            },
            sort_keys=True,
        ).encode()
    )
    aliased = frag.host_ie is frag.host_oe
    sides = [frag.host_oe] if aliased else [frag.host_oe, frag.host_ie]
    for f in range(frag.fnum):
        _hash_array(h, frag.inner_oids(f))
        for csrs in sides:
            c = csrs[f]
            _hash_array(h, c.indptr)
            _hash_array(h, c.edge_nbr)
            _hash_array(h, c.edge_mask)
            if c.edge_w is not None:
                _hash_array(h, c.edge_w)
    digest = h.hexdigest()
    frag._ft_content_hash = digest
    return digest


def canonical_query_args(query_args: Dict[str, Any]) -> Dict[str, Any]:
    """JSON-roundtrippable form of the query kwargs: numpy scalars
    become Python numbers, everything else must already be a JSON
    primitive (the resume path replays these through `init_state`)."""
    out = {}
    for k, v in sorted(query_args.items()):
        if isinstance(v, (np.integer,)):
            v = int(v)
        elif isinstance(v, (np.floating,)):
            v = float(v)
        elif isinstance(v, (np.bool_,)):
            v = bool(v)
        if not isinstance(v, (int, float, str, bool, type(None))):
            raise TypeError(
                f"query arg {k!r}={v!r} is not checkpointable (must be a "
                "JSON primitive so resume can replay it through init_state)"
            )
        out[k] = v
    return out


def compute_fingerprint(app, frag, query_args: Dict[str, Any]) -> Dict[str, Any]:
    """The identity a checkpoint must match to be resumed."""
    import jax

    return {
        "format": FINGERPRINT_FORMAT,
        "app": app_registry_name(app),
        "app_class": type(app).__name__,
        "fragment_hash": fragment_content_hash(frag),
        "fnum": frag.fnum,
        "vp": frag.vp,
        "query_args": canonical_query_args(query_args),
        # numeric config that changes result bytes
        "x64": bool(jax.config.jax_enable_x64),
        # a constant since the one pull: a checkpoint an earlier tree
        # wrote under its default still restores, one it wrote under
        # another mode is still refused
        "spmv_mode": "auto",
        # mesh geometry beyond fnum/vp: the partition layout and the
        # process topology.  A 2-D-partition snapshot must never
        # silently restore into a 1-D worker (the carry layouts
        # differ), and a reshard restore must KNOW it is crossing a
        # process-count change (ft/distributed.py GEOMETRY_KEYS) —
        # both are loud CheckpointMismatchErrors, never guesses.
        "partition_mode": _partition_mode(),
        "processes": jax.process_count(),
    }


def _partition_mode() -> str:
    # local import: fragment/ pulls in the parallel stack; the
    # fingerprint module must stay importable standalone
    from libgrape_lite_tpu.fragment.partition import partition_mode

    return partition_mode()


def fingerprint_mismatch(expected: Dict, found: Dict) -> list[str]:
    """Human-readable list of differing fingerprint fields."""
    keys = sorted(set(expected) | set(found))
    return [
        f"{k}: checkpoint has {found.get(k)!r}, query has {expected.get(k)!r}"
        for k in keys
        if expected.get(k) != found.get(k)
    ]
