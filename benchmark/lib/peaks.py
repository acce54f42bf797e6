"""Published peaks of the chips the benchmark may run on, and the bytes a
decode step has to read.

Keyed by ``jax.devices()[0].device_kind``. A kind that is not here is an
error, never a default: a roofline share against a guessed peak is worth
nothing. The program has its own table (``search/machine_model.TPU_CHIPS``);
this one is the benchmark's, so that the program cannot move the yardstick.
"""

from __future__ import annotations

from typing import Iterable, Tuple

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmark/lib/peaks.py") from None


def decode_step_bytes(weights: Iterable[Tuple[str, int, int, float]],
                      cache_bytes_per_token: float,
                      live_cache_tokens: float) -> float:
    """Bytes one decode step must read from HBM: every weight matrix the
    step multiplies by, once, plus the keys and values of every live
    position. ``weights`` is the family's list of
    (name, rows, cols, bytes_per_element) for one decode step; the embedding
    table is not in it (a step reads one row per request, not the table).
    Activations and the written cache row are left out: at these widths they
    are under a thousandth of the weights."""
    w = sum(rows * cols * b for _, rows, cols, b in weights)
    return w + cache_bytes_per_token * live_cache_tokens
