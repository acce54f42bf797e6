"""Arithmetic shared by the per-layer readers of a model most of whose layers
are state-space mixers that keep a recurrent state a row
(ops/ssd_mixer.py) beside a few plain GQA layers' k/v caches, every
feed-forward dense (Granite-4.0-H): ``ssd_state_hbm_roofline``,
``decode_ssd_hbm_roofline``, ``ssd_state_share``.

``ctx`` is what ``lib/readers.py`` documents. The recurrent kernel is the
device operations whose name contains ``ssd_state_step`` (the Pallas call's
name: kernels/linear_attention.py; the gated delta rule's is
``kda_state_step``, and neither name contains the other); the chunked form
of a prefill step is plain XLA fusions with no name of their own, and no
reader here sees it. The counters are the program's:
``ffsv_kda_state_steps_total`` (live rows x recurrent layers x steps of the
decode blocks, whatever the recurrent op: the name's ``kda`` is historical),
``ffsv_attn_positions_read_total{kind="full"}`` (layer-positions the decode
steps' rows had to attend in the attention layers) and
``ffsv_decode_steps_total`` (row-steps); the ``decode_block`` spans carry
``steps`` and ``rows``. The shapes and the counts of bytes come from the
cell's family (``families/granite_hybrid.py``: ``state_step_bytes``,
``decode_step_must_read``). Every count is of bytes that MUST be moved: a
share over 100 would mean a count too high. A program without the kernel
(any commit before PR 56, any other model) gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.cca_readers import (_positions_a_row_step,
                                                 _traced_row_steps)
from benchmark.layer_metrics.window_readers import _gained, _kernel_ns_in
from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R
from benchmark.lib import trace as TR

KERNEL = "ssd_state_step"
STATE_STEPS = "ffsv_kda_state_steps_total"


def _kernel_ns(ctx) -> float:
    """The recurrent kernel's self time in the traced stretch (0 without
    a trace or without the kernel)."""
    tr = ctx.get("trace")
    return TR.time_of(tr["ops"], KERNEL) if tr and tr["busy_s"] > 0 else 0.0


def ssd_state_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the bytes the recurrent kernel had to move in the traced
    decode blocks (their own rows x steps, times the recurrent layers, at
    one live row's state in and out and its dt * x, decay, B, C and y rows)
    over the chip's HBM bandwidth, as a share of the kernel's time inside
    those blocks."""
    hit = _kernel_ns_in(ctx, KERNEL, "decode_block")
    if hit is None or not _gained(ctx, STATE_STEPS):
        return None
    spans, ns = hit
    row_steps = _traced_row_steps(spans)
    if not row_steps:
        return None
    fam, cfg = ctx["family"], ctx["cfg"]
    need = fam.state_step_bytes(
        cfg, row_steps * fam.layers_of(cfg, "recurrent"))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def decode_ssd_hbm_roofline(ctx) -> Optional[float]:
    """The WHOLE decode step: the bytes it must move (every weight once; the
    cache positions the traced blocks' rows had to read in the attention
    layers; the recurrent state and tail of the traced blocks' rows, read
    and written once a mixer) over the chip's HBM bandwidth, as a share of
    ``decode_step_ms``."""
    step = R.decode_step_ms(ctx)
    per_row = _positions_a_row_step(ctx)
    if (step is None or per_row is None or _kernel_ns(ctx) <= 0
            or not _gained(ctx, STATE_STEPS)):
        return None
    spans = PR.spans_inside(ctx, ("decode_block",))
    steps = sum(s[3].get("steps", 0) for s in spans)
    row_steps = _traced_row_steps(spans)
    if not steps or row_steps is None:
        return None
    rows = row_steps / steps
    need = ctx["family"].decode_step_must_read(ctx["cfg"], rows * per_row,
                                               rows)
    return 100.0 * (1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]) / step


def ssd_state_share(ctx) -> Optional[float]:
    """The recurrent kernel's share of the traced device time. The chunked
    form (a prefill step's) is unnamed XLA fusions and is NOT in it."""
    ns = _kernel_ns(ctx)
    return 100.0 * ns / TR.total(ctx["trace"]["merged"]) if ns > 0 else None
