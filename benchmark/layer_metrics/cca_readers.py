"""Arithmetic shared by the per-layer readers of a model whose attention
layers carry a row's tail beside a plain grouped k/v cache and whose router
picks one expert or none (ZAYA1): ``decode_cca_hbm_roofline``,
``attn_kv_hbm_roofline``, ``cca_tail_step_share``.

``ctx`` is what ``lib/readers.py`` documents. The attention kernel is the
device operations whose name contains ``flash_attend`` (the plain k/v
kernel: this model has no other). The counters are the program's:
``ffsv_attn_positions_read_total{kind="full"}`` (layer-positions the decode
steps' rows had to attend), ``ffsv_decode_steps_total`` (row-steps),
``ffsv_moe_experts_touched{phase="decode"}`` (distinct experts a layer-step
read) and ``ffsv_cca_tails_total{phase="prefill",source}`` (a prefill step's
segments by where their tail came from: ``start``, ``step``, ``state``); the
``decode_block`` spans carry ``steps`` and ``rows``. The shapes and the
count of bytes come from the cell's family (``families/zaya.py``). Every
count is of bytes that MUST be read: a share over 100 would mean a count too
high. A program without the series (any commit before PR 50, any other
model) gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.window_readers import _gained, _kernel_ns_in
from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R

KERNEL = "flash_attend"
READ = 'ffsv_attn_positions_read_total{kind="full"}'
TOUCHED = 'ffsv_moe_experts_touched{phase="decode"}'
TAILS = 'ffsv_cca_tails_total{phase="prefill",source="%s"}'
SOURCES = ("start", "step", "state")


def _positions_a_row_step(ctx) -> Optional[float]:
    """Layer-positions a row of a decode step had to read, the window's
    mean (all the layers together)."""
    pos = _gained(ctx, READ)
    row_steps = _gained(ctx, "ffsv_decode_steps_total")
    return pos / row_steps if pos and row_steps else None


def _traced_row_steps(spans) -> Optional[float]:
    """Row-steps of the traced ``decode_block`` spans, from their own
    ``rows`` and ``steps``."""
    rows = [s[3].get("rows") for s in spans]
    if None in rows:
        return None
    return float(sum(r * s[3].get("steps", 0) for r, s in zip(rows, spans)))


def decode_cca_hbm_roofline(ctx) -> Optional[float]:
    """The WHOLE decode step: the bytes it must read (everything outside
    the experts once, the table once; the experts a layer-step touched, the
    window's decode mean, in each layer; the cache positions the traced
    blocks' rows had to read: their own rows a step times the window's
    layer-positions a row-step, at a position's bytes) over the chip's HBM
    bandwidth, as a share of ``decode_step_ms``."""
    step = R.decode_step_ms(ctx)
    touched = R.hist_mean(ctx, TOUCHED)
    per_row = _positions_a_row_step(ctx)
    fam = ctx["family"]
    if (step is None or touched is None or per_row is None
            or not hasattr(fam, "decode_step_must_read")):
        return None
    spans = PR.spans_inside(ctx, ("decode_block",))
    steps = sum(s[3].get("steps", 0) for s in spans)
    row_steps = _traced_row_steps(spans)
    if not steps or row_steps is None:
        return None
    need = fam.decode_step_must_read(ctx["cfg"], touched,
                                     row_steps / steps * per_row)
    return 100.0 * (1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]) / step


def attn_kv_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the k/v bytes the plain attention kernel had to read in the
    traced decode blocks (their own row-steps, times the window's
    layer-positions a row-step, times a position's bytes) over the chip's
    HBM bandwidth, as a share of the kernel's time inside those blocks."""
    hit = _kernel_ns_in(ctx, KERNEL, "decode_block")
    per_row = _positions_a_row_step(ctx)
    fam = ctx["family"]
    if hit is None or per_row is None or not hasattr(
            fam, "cache_position_bytes"):
        return None
    spans, ns = hit
    row_steps = _traced_row_steps(spans)
    if not row_steps:
        return None
    need = row_steps * per_row * fam.cache_position_bytes(ctx["cfg"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def cca_tail_step_share(ctx) -> Optional[float]:
    """Of the window's prefill segments, those whose tail came from another
    segment of the same step: what the consecutive segments carry. On a
    ``# `` line the three counts."""
    got = {s: _gained(ctx, TAILS % s) for s in SOURCES}
    if None in got.values() or sum(got.values()) <= 0:
        return None
    PR.say("prefill segments by where their tail came from: " + ", ".join(
        "%s %d" % (s, got[s]) for s in SOURCES))
    return 100.0 * got["step"] / sum(got.values())
