"""Arithmetic shared by the per-layer readers of a model whose layers keep a
recurrent state a row (a gated delta rule, ops/kda_attention.py) beside
gated GQA layers' plain k/v caches, over a held range of routed experts
(Solar-Open2): ``kda_state_hbm_roofline``, ``decode_kda_hbm_roofline``,
``linear_attn_share``, ``kv_recurrent_share``.

``ctx`` is what ``lib/readers.py`` documents. The recurrent kernel is the
device operations whose name contains ``kda_state_step`` (the Pallas call's
name: kernels/linear_attention.py); the chunked form of a prefill step is
plain XLA fusions with no name of their own, and no reader here sees it.
The counters are the program's: ``ffsv_kda_state_steps_total`` (live rows x
recurrent layers x steps of the decode blocks: one state read and written
each), ``ffsv_attn_positions_read_total{kind="full"}`` (layer-positions the
decode steps' rows had to attend in the GQA layers),
``ffsv_decode_steps_total`` (row-steps), ``ffsv_moe_experts_touched
{phase="decode"}`` and the gauges ``ffsv_kv_cache_bytes{kind}``; the
``decode_block`` spans carry ``steps`` and ``rows``. The shapes and the
counts of bytes come from the cell's family (``families/solar_open2.py``:
``state_step_bytes``, ``decode_step_must_read``). Every count is of bytes
that MUST be moved: a share over 100 would mean a count too high. A program
without the series (any commit before PR 54, any other model) gives every
reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.cca_readers import (_positions_a_row_step,
                                                 _traced_row_steps)
from benchmark.layer_metrics.window_readers import _gained, _kernel_ns_in
from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R
from benchmark.lib import trace as TR

KERNEL = "kda_state_step"
STATE_STEPS = "ffsv_kda_state_steps_total"
TOUCHED = 'ffsv_moe_experts_touched{phase="decode"}'
BYTES = 'ffsv_kv_cache_bytes{kind="%s"}'


def _has_state(ctx) -> bool:
    """The program counted state steps in the window and the family knows
    the state's shapes."""
    return bool(_gained(ctx, STATE_STEPS)) and hasattr(
        ctx["family"], "state_step_bytes")


def kda_state_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the bytes the recurrent kernel had to move in the traced
    decode blocks (their own rows x steps, times the recurrent layers, at
    one live row's state in and out and its q, k, g, v, beta, o rows) over
    the chip's HBM bandwidth, as a share of the kernel's time inside those
    blocks."""
    hit = _kernel_ns_in(ctx, KERNEL, "decode_block")
    if hit is None or not _has_state(ctx):
        return None
    spans, ns = hit
    row_steps = _traced_row_steps(spans)
    if not row_steps:
        return None
    fam, cfg = ctx["family"], ctx["cfg"]
    need = fam.state_step_bytes(
        cfg, row_steps * fam.layers_of(cfg, "recurrent"))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def decode_kda_hbm_roofline(ctx) -> Optional[float]:
    """The WHOLE decode step: the bytes it must move (everything outside
    the experts once; the experts a layer-step touched, the window's decode
    mean, in each layer; the cache positions the traced blocks' rows had to
    read in the GQA layers; the recurrent state of the traced blocks' rows,
    read and written once a KDA layer) over the chip's HBM bandwidth, as a
    share of ``decode_step_ms``."""
    step = R.decode_step_ms(ctx)
    touched = R.hist_mean(ctx, TOUCHED)
    per_row = _positions_a_row_step(ctx)
    fam = ctx["family"]
    if (step is None or touched is None or per_row is None
            or not _has_state(ctx)
            or not hasattr(fam, "decode_step_must_read")):
        return None
    spans = PR.spans_inside(ctx, ("decode_block",))
    steps = sum(s[3].get("steps", 0) for s in spans)
    row_steps = _traced_row_steps(spans)
    if not steps or row_steps is None:
        return None
    rows = row_steps / steps
    need = fam.decode_step_must_read(ctx["cfg"], touched, rows * per_row,
                                     rows)
    return 100.0 * (1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]) / step


def linear_attn_share(ctx) -> Optional[float]:
    """The recurrent kernel's share of the traced device time. The chunked
    form (a prefill step's) is unnamed XLA fusions and is NOT in it."""
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], KERNEL)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None


def kv_recurrent_share(ctx) -> Optional[float]:
    """Of the cache bytes the program holds, the share that is recurrent
    state and convolution tails (the rest: the GQA layers' k/v caches)."""
    tel = ctx.get("tel")
    if not tel:
        return None
    got = {k: tel["after"].get(BYTES % k, {}).get("value")
           for k in ("recurrent", "full")}
    if None in got.values() or sum(got.values()) <= 0:
        return None
    return 100.0 * got["recurrent"] / sum(got.values())
