"""Coordinator-only phase timer.

Re-design of `examples/analytical_apps/timer.h:43-75`: a stack of named
phases, printed by the coordinator (process index 0).  JAX devices are
asynchronous, so `timer_end` blocks on outstanding device work before
reading the clock (the analogue of the reference's implicit MPI barrier).
The printed lines are the operator's output; the spans of the same
intervals are obs/'s (`load_graph`, `query`), opened where the work is.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax

_stack: List[Tuple[str, float]] = []
_is_coordinator = True


def set_coordinator(flag: bool) -> None:
    global _is_coordinator
    _is_coordinator = flag


def timer_start(name: str) -> None:
    jax.effects_barrier()
    _stack.append((name, time.perf_counter()))


def timer_end() -> float:
    jax.effects_barrier()
    name, t0 = _stack.pop()
    dt = time.perf_counter() - t0
    if _is_coordinator:
        print(f"[timer] {name}: {dt:.6f} s")
    return dt


class phase:
    """Context-manager sugar: `with phase("run algorithm"): ...`"""

    def __init__(self, name: str):
        self.name = name
        self.seconds = None

    def __enter__(self):
        timer_start(self.name)
        return self

    def __exit__(self, *exc):
        self.seconds = timer_end()
        return False
