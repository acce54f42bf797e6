"""Pallas TPU kernels for the serving hot path.

The reference implements its serving hot ops as hand-written CUDA
(reference src/ops/inc_multihead_self_attention.cu,
spec_inc_multihead_self_attention.cu, tree_inc_multihead_self_attention.cu —
~2.8K LoC — plus sampling/top-k kernels under src/ops/kernels/). The TPU
equivalents live here as Pallas kernels; every kernel has a pure-jnp
reference path used on CPU (tests) and as a numerics oracle.

Dispatch: ``use_pallas(config)`` returns True on a real TPU backend (or when
FF_PALLAS_INTERPRET=1 forces interpreter-mode kernels on CPU, which the
kernel unit tests use to exercise the Pallas code path everywhere). The
variable is a CPU-only switch: set on a TPU backend it raises, because the
interpreter would silently stand in for the compiled kernels.
"""

from __future__ import annotations

import os


def pallas_interpret_forced() -> bool:
    return os.environ.get("FF_PALLAS_INTERPRET", "") not in ("", "0")


# ----------------------------------------------------------------------
# Fast-path observability (r1 VERDICT: a silent jnp fallback "pays for
# max_seq" with no signal). Counters are per-process; the first fallback
# of each distinct reason logs a warning once.
# ----------------------------------------------------------------------
fallback_counts: dict = {}
fast_path_count: int = 0
# How a step with no slot map (a decode step, a slot-grid prefill) put its
# new keys and values into the cache, in traces by the run's width in
# positions a row: inside the attention kernel (``fused``), or by a
# row-granular scatter in front of it (``scatter``: about 75 ns an index
# row of the scalar unit, R x KH x width of them a cache a layer).
fused_append_counts: dict = {}
scatter_append_counts: dict = {}
# Which form of the latent kernel a trace took, by (form, mode): "block" (a
# DMA block's scores in one pass, its softmax partitions unrolled: a decode
# step's few query rows) or "partition" (the partition loop: a prefill
# segment's thousands), of the shapes alone
# (``kernels/attention.latent_form``); mode "append" (a decode step, the
# append fused), "rows" (the compact prefill batch) or "grid" (a slot-grid
# step). ``latent_summary()`` prints it; read by no metric.
latent_form_counts: dict = {}
# Which form of the k/v kernel's stream a trace took, by (form, mode, DMA
# block in positions): "block" (a DMA block of several softmax partitions,
# its scores in one pass and its partitions unrolled: the plain stream where
# a partition's descriptor is small) or "loop" (a partition a block), of the
# shapes alone (``kernels/attention.stream_block``); mode "append" (a decode
# step, its one position a row appended by the kernel), "run" (a run of
# positions appended), "rows" (the compact prefill batch) or "grid" (a
# slot-grid step). ``stream_summary()`` prints it; read by no metric.
stream_form_counts: dict = {}
_warned: set = set()


def record_fast_path(append=None, latent=None, stream=None):
    """Count a trace of the attention kernel; ``append``: the width of the
    run of positions it appends to the cache itself; ``latent``: the (form,
    mode) of a latent kernel's trace; ``stream``: the (form, mode, DMA
    block) of a k/v kernel's."""
    global fast_path_count
    fast_path_count += 1
    if append is not None:
        fused_append_counts[append] = fused_append_counts.get(append, 0) + 1
    if latent is not None:
        latent_form_counts[latent] = latent_form_counts.get(latent, 0) + 1
    if stream is not None:
        stream_form_counts[stream] = stream_form_counts.get(stream, 0) + 1


def record_scatter_append(width: int):
    """Count a trace of a row-granular scatter append of ``width`` positions
    a row in a step with no slot map."""
    scatter_append_counts[width] = scatter_append_counts.get(width, 0) + 1


def append_summary() -> str:
    """The two append counters in one line, as a run prints them: "fused
    appends of width 8: 12 traces; scatter appends of width 4: 2 traces"."""
    def part(kind, counts):
        return "; ".join(f"{kind} appends of width {w}: {n} traces"
                         for w, n in sorted(counts.items())) or (
                             f"{kind} appends: 0")
    return (part("fused", fused_append_counts) + "; "
            + part("scatter", scatter_append_counts))


def latent_summary() -> str:
    """The latent kernel's traces by form and mode in one line: "latent
    kernel: block form, append: 8 traces; partition form, rows: 8 traces"."""
    return "latent kernel: " + ("; ".join(
        f"{form} form, {mode}: {n} traces"
        for (form, mode), n in sorted(latent_form_counts.items()))
        or "0 traces")


def stream_summary() -> str:
    """The k/v kernel's traces by form, mode and DMA block in one line: "k/v
    kernel: block form of 1024, append: 10 traces; loop form of 128, rows:
    10 traces"."""
    return "k/v kernel: " + ("; ".join(
        f"{form} form of {block}, {mode}: {n} traces"
        for (form, mode, block), n in sorted(stream_form_counts.items()))
        or "0 traces")


def record_fallback(reason: str):
    """Count (and warn once per reason) a serving-attention jnp fallback."""
    fallback_counts[reason] = fallback_counts.get(reason, 0) + 1
    if reason not in _warned:
        _warned.add(reason)
        import warnings

        warnings.warn(
            f"serving attention fell back to the jnp path ({reason}); "
            "this pays O(max_seq) per step instead of streaming the "
            "valid cache prefix", stacklevel=3)


def reset_dispatch_stats():
    global fast_path_count
    fallback_counts.clear()
    fused_append_counts.clear()
    scatter_append_counts.clear()
    latent_form_counts.clear()
    stream_form_counts.clear()
    _warned.clear()
    fast_path_count = 0
    from flexflow_tpu.kernels import moe

    moe.reset_dispatch_stats()


def use_pallas(config=None) -> bool:
    """Should serving ops run their Pallas kernels?"""
    if config is not None and not getattr(config, "use_pallas", True):
        return False
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if pallas_interpret_forced():
        if on_tpu:
            raise RuntimeError(
                "FF_PALLAS_INTERPRET is set on a TPU backend: the Pallas "
                "kernels would run interpreted instead of compiled. Unset "
                "it (it exists for CPU tests only).")
        return True
    return on_tpu


from flexflow_tpu.kernels.attention import flash_attend  # noqa: E402,F401
