"""What every driver does the same way: a job's keys and a median."""

from __future__ import annotations

import statistics

import numpy as np


def job_keys(run, job: dict) -> list | None:
    """The keys a job's callers ask, in this run's order: the graph's pool of
    search keys (the configuration's), permuted by `--seed`.  None for a job
    that takes no key."""
    rule = job.get("keys")
    if rule is None:
        return None
    pool = run.dataset.key_pool(int(rule["pool"]))
    order = np.random.default_rng(run.seed).permutation(len(pool))
    return [int(pool[i]) for i in order]


def median(xs):
    return statistics.median(xs) if xs else None


def spread_line(name: str, xs: list) -> str:
    if not xs:
        return f"{name}: no sample"
    return (f"{name}: {len(xs)} samples, min {min(xs):.6g}, "
            f"median {median(xs):.6g}, max {max(xs):.6g}")
