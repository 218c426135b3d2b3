"""Slope-based timing of chained device work.

Dispatch is asynchronous and every host sync costs a fixed host<->device
round trip plus the fetch itself, so timing one call measures the
enqueue and the sync as much as the device. `slope_time` times K1 and K2
chained executions with a single tiny fetch each and returns
(t(K2) - t(K1)) / (K2 - K1): per-execution device time, with the fixed
costs (sync, fetch, first-dispatch overhead) cancelled.
"""

from __future__ import annotations

import time
from typing import Callable

import jax


def _run_chain(step: Callable, state, k: int):
    t0 = time.perf_counter()
    for _ in range(k):
        state = step(state)
    # Fetch something tiny that depends on the chain.
    leaf = jax.tree.leaves(state)[0]
    _ = jax.device_get(jax.numpy.ravel(leaf)[:1])
    return time.perf_counter() - t0, state


def slope_time(step: Callable, state, k1: int = 2, k2: int = 10):
    """step: state -> state (chained device work). Returns (seconds per
    execution, final state)."""
    # Warm: compile + one round trip.
    _, state = _run_chain(step, state, 1)
    t1, state = _run_chain(step, state, k1)
    t2, state = _run_chain(step, state, k2)
    return (t2 - t1) / (k2 - k1), state
