"""Dispatch-count-differencing wall timer.

Every forced completion costs a per-call constant (the dispatch and the
wait for the device to drain) that can drown millisecond-scale per-step
signals. JAX dispatches are async and pipeline on the device, so timing
n1 vs n2 back-to-back dispatches — forcing completion only at the end —
pays that constant once each, and the difference isolates device time
per call.

Completion is forced with jax.block_until_ready on the last result.

Used by distributed.fleet.pipeline.PipelineParallel (store-vs-remat
auto-pick).
"""
from __future__ import annotations

import time

__all__ = ["timed_dispatch_diff"]


def timed_dispatch_diff(fn, args, calls=(1, 3), repeats=2,
                        per_call: int = 1) -> float:
    """Seconds per unit of work, with per-call constants cancelled:
    (T(n2 calls) - T(n1 calls)) / ((n2 - n1) * per_call).

    fn(*args) must return a pytree of jax arrays;
    per_call is the number of work units one call performs (e.g. the
    scan length inside fn). The caller is responsible for having
    compiled/warmed fn (the first invocation here blocks once before
    timing, which also absorbs any remaining warm-up)."""
    import jax

    jax.block_until_ready(fn(*args))
    n1, n2 = calls
    ts = {}
    for n in (n1, n2):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = None
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        ts[n] = best
    return max(ts[n2] - ts[n1], 1e-9) / ((n2 - n1) * per_call)
