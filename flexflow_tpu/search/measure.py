"""Measured searched-vs-DP A/B — the wall-clock check on the Unity search.

The search's "advantage" numbers are analytic (its own cost model grading
its own homework). This module closes the loop the way the reference's
headline does (Unity OSDI'22 reports MEASURED speedup, README.md:68): it
compiles the SAME model under (a) the searched strategy, (b) forced pure
data-parallelism, and (c) a sequence-only search (nonsequence splits
disabled), runs real train steps on the live mesh, and reports wall-clock
seconds per step next to the analytic costs.

Timing: ``train_one_batch`` returns ``float(loss)`` — a host readback,
which is the honest fence on this runtime (utils/profiling.device_fence).
After warmup the variants' timed blocks of steps take turns, and a
variant's per-step time is the median of its blocks.
"""

from __future__ import annotations

import statistics
import time
from typing import Callable, Dict, Optional, Tuple


def _compile_forced(build_model: Callable[[], object], strategy, xs, ys):
    """``build_model()`` compiled under a FORCED ``strategy`` (no search)
    and warmed by two train steps. ``strategy=None`` compiles whatever
    the model's config dictates (plain GSPMD defaults)."""
    import flexflow_tpu as ff

    model = build_model()
    model.config.auto_parallel = False   # the strategy is given, not searched
    model.strategy = strategy            # compile adopts strategy.axis_degrees
    model.compile(
        optimizer=ff.SGDOptimizer(model, 0.01),
        loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    for _ in range(2):                   # compile + warm
        model.train_one_batch([x for x in xs], ys)
    return model


def _seconds_per_step(model, xs, ys, steps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(steps):
        model.train_one_batch([x for x in xs], ys)
    return (time.perf_counter() - t0) / steps


def searched_vs_dp_wallclock(build_model: Callable[[], object], xs, ys,
                             chip: str = "v5e",
                             num_devices: Optional[int] = None,
                             steps: int = 6, reps: int = 3,
                             variants: Tuple[str, ...] = ("searched", "dp",
                                                          "seq_only")
                             ) -> Dict[str, Dict[str, float]]:
    """The A/B: analytic cost AND measured wall-clock for each variant.

    Variants:
      searched — the full Unity search (nonsequence splits included)
      dp       — forced canonical pure data-parallelism over ALL devices
      seq_only — the search with nonsequence (branch) splits disabled

    Returns {variant: {"analytic": s, "wallclock": s}}. The strategies
    are chosen under the ``chip`` analytic machine model but EXECUTED on
    whatever mesh the current jax backend provides — on the virtual CPU
    mesh the ratio is a structural sanity check (does the searched
    placement actually run no slower than DP?), not TPU physics.

    Every variant is compiled first and the timed blocks then take turns,
    one of each variant a repetition, so that whatever else loads the host
    loads all variants alike; a variant's time is the median of its
    ``reps`` blocks (the best block of a few is an extreme: beside busy
    neighbours it moved by 25% between equal programs, the median by 2%)."""
    from flexflow_tpu.search.graph_search import (
        data_parallel_model_strategy, optimize_model)

    out: Dict[str, Dict[str, float]] = {}
    models = {}
    for variant in variants:
        probe = build_model()
        n = (num_devices if num_devices is not None
             else probe.config.resolve_num_devices())
        if variant == "dp":
            strat = data_parallel_model_strategy(probe, chip=chip,
                                                 num_devices=n)
            if strat is None:
                raise ValueError(
                    f"no canonical DP strategy for this model over {n} "
                    "devices (batch dim not divisible) — the A/B has no "
                    "meaningful DP baseline")

            def build_dp():
                m = build_model()
                # pure DP uses the whole device set on the data axis
                m.config.data_parallelism_degree = n
                m.config.tensor_parallelism_degree = 1
                m.config.expert_parallelism_degree = 1
                return m

            builder = build_dp
        else:
            # the searched variant gets the FULL Unity space, including
            # the mesh factorization (so it can pick pure DP when DP is
            # genuinely best instead of losing inside a pinned dp x tp)
            strat = optimize_model(
                probe, chip=chip, num_devices=n,
                enable_nonsequence=(variant == "searched"),
                search_mesh=True)
            builder = build_model
        models[variant] = _compile_forced(builder, strat, xs, ys)
        out[variant] = {"analytic": float(strat.cost) if strat else -1.0}
    blocks = {variant: [] for variant in models}
    for _ in range(reps):
        for variant, model in models.items():
            blocks[variant].append(_seconds_per_step(model, xs, ys, steps))
    for variant in models:
        out[variant]["wallclock"] = statistics.median(blocks[variant])
    return out


def format_ab(name: str, res: Dict[str, Dict[str, float]]) -> str:
    """One printable line: measured ratios next to analytic ones."""
    parts = [name]
    for v, d in res.items():
        parts.append(f"{v}: analytic={d['analytic']:.3e}s "
                     f"wallclock={d['wallclock'] * 1e3:.1f}ms")
    if "dp" in res and "searched" in res:
        aa = res["dp"]["analytic"] / max(res["searched"]["analytic"], 1e-30)
        ww = res["dp"]["wallclock"] / max(res["searched"]["wallclock"], 1e-30)
        parts.append(f"advantage analytic={aa:.2f}x MEASURED={ww:.2f}x")
    return " | ".join(parts)
