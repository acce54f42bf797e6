"""Profiling hooks.

Capability parity with the reference's two profiling layers (SURVEY §5):
(a) ``--profiling`` per-kernel cudaEvent timing prints → here per-step
wall-time with host-readback fencing, and (b) Legion Prof traces →
here the XLA/jax profiler (``jax.profiler.trace``) whose output loads in
TensorBoard / Perfetto.

Measurement protocol: a timed region ends in a fence. ``device_fence``
reads one element of every output back to the host, which cannot complete
before the producing program has; on a local chip
``jax.block_until_ready`` is a fence too. A single-call timing includes
the call's fixed dispatch cost; ``slope_time`` cancels it by running T1
and T2 iterations inside ONE device program and taking the slope.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


def device_fence(out):
    """Block until ``out`` has actually been computed, by reading one
    element of every array leaf back to the host.

    A host readback of an output buffer cannot complete until the
    producing program has finished, whatever the runtime's notion of
    "ready" is. Only a single element per leaf is copied. Returns ``out``.
    """
    import jax.numpy as jnp

    # index the first element; flattening first (jnp.ravel) copies the whole
    # leaf on a TPU, where a reshape of a tiled array is a relayout: 2.1 GB
    # for each of a KV cache's two leaves, alive together until the readback
    scalars = [jnp.asarray(leaf[(0,) * leaf.ndim], jnp.float32)
               for leaf in jax.tree_util.tree_leaves(out)
               if hasattr(leaf, "dtype") and getattr(leaf, "size", 0)]
    if scalars:
        # the element extractions dispatch asynchronously; ONE stacked
        # readback fences them all (N synchronous readbacks would each
        # stall the host inside a timed window)
        np.asarray(jnp.stack(scalars))
    return out


def timed_call(fn, *args, **kwargs):
    """Run fn, fence its outputs via host readback, return (result, s)."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    device_fence(out)
    return out, time.perf_counter() - t0


def slope_time(run: Callable[[int], object], t1: int = 1, t2: int = 5,
               reps: int = 2) -> float:
    """Per-iteration time of ``run(T)`` via the T-slope protocol.

    ``run(T)`` must execute T iterations of the workload inside ONE
    device program (e.g. a jitted ``lax.fori_loop`` with a traced trip
    count) and block until done (readback-fence its result).  The slope
    ``(time(t2) - time(t1)) / (t2 - t1)`` cancels the per-dispatch
    latency and any other fixed per-call cost.  Each trip count is timed
    ``reps`` times and the best
    (minimum) is used.  Returns seconds per iteration; may be <= 0
    under jitter — callers should treat that as "too fast to resolve"
    and fall back.
    """
    best = {}
    for t in (t1, t2):
        best[t] = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(t)
            best[t] = min(best[t], time.perf_counter() - t0)
    return (best[t2] - best[t1]) / (t2 - t1)


def adaptive_slope_time(run: Callable[[int], object], cap: int = 4096,
                        reps: int = 3, min_resolve_s: float = 5e-3) -> float:
    """T-slope with an adaptively chosen upper trip count.

    Per-call jitter scales with the fixed dispatch+readback cost, so a
    fixed small T2 cannot resolve micro/millisecond ops.  This grows the
    trip count by 4x
    until the extra compute clears a noise floor of
    ``max(0.5 * fixed_cost, min_resolve_s)``, then returns the slope
    against the T=1 baseline.  Each level is timed ``reps`` times, best
    (minimum) kept.  Returns 0.0 when the workload is too fast to
    resolve even at ``cap`` trips (the delta there is indistinguishable
    from jitter) — callers must fall back to an analytic estimate
    rather than rank on noise.
    """
    def best_of(t):
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            run(t)
            b = min(b, time.perf_counter() - t0)
        return b

    t_fix = best_of(1)
    thresh = max(0.5 * t_fix, min_resolve_s)
    t = 8
    while True:
        t_hi = best_of(t)
        if t_hi - t_fix >= thresh:
            return (t_hi - t_fix) / (t - 1)
        if t >= cap:
            return 0.0          # never resolved above the noise floor
        t = min(t * 4, cap)


class StepTimer:
    """Accumulates per-step device-fenced wall times (the --profiling
    print path, reference linear_kernels.cu:159-225 style)."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.times: Dict[str, List[float]] = {}

    def record(self, name: str, seconds: float):
        if self.enabled:
            self.times.setdefault(name, []).append(seconds)

    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for name, ts in self.times.items():
            out[name] = {"count": len(ts), "total_s": sum(ts),
                         "mean_ms": 1e3 * sum(ts) / max(1, len(ts)),
                         "last_ms": 1e3 * ts[-1]}
        return out

    def report(self) -> str:
        return " ".join(f"{k}={v['mean_ms']:.2f}ms(x{v['count']})"
                        for k, v in self.summary().items())


def _mark_telemetry():
    from flexflow_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    if tel is not None:
        tel.tracer.profiler_mark()


@contextlib.contextmanager
def profiler_trace(logdir: str):
    """XLA device trace (the Legion Prof equivalent): view with
    TensorBoard's profile plugin or Perfetto. With telemetry on, the
    session holds the program's batch-level spans on its host plane and
    a clock mark at both ends (``SpanTracer.profiler_mark``), by which
    ``tools/profile_trace.spans_on_profiler_clock`` aligns the rest."""
    jax.profiler.start_trace(logdir)
    try:
        _mark_telemetry()
        yield
    finally:
        _mark_telemetry()
        jax.profiler.stop_trace()
