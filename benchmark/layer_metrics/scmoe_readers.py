"""Arithmetic shared by the per-layer readers of a model whose router has
outputs that are no expert and whose layer holds two latent attentions
beside a routed branch on a shortcut (LongCat-Flash): ``zero_expert_share``,
``decode_scmoe_hbm_roofline``.

``ctx`` is what ``lib/readers.py`` documents. The counters are the
program's: ``ffsv_moe_zero_pairs_total{phase}`` (picks of a router index that
names no expert: they add ``w * x``), ``ffsv_moe_tokens_total{phase}`` (real
tokens the routed layers saw, a layer each), ``ffsv_moe_experts_touched
{phase="decode"}`` (distinct held experts a layer-step read),
``ffsv_attn_positions_read_total{kind="latent"}`` (layer-positions the decode
steps' rows had to attend) and ``ffsv_decode_steps_total`` (row-steps); the
``decode_block`` spans carry ``steps`` and ``rows``. The shapes and the count
of bytes come from the cell's family (``families/longcat_flash.py``). Every
count is of bytes that MUST be read: a share over 100 would mean a count too
high. A program without the series (any commit before PR 47, any other model)
gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.window_readers import _gained
from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R

PHASES = ("decode", "prefill", "verify")
ZERO = 'ffsv_moe_zero_pairs_total{phase="%s"}'
TOKENS = 'ffsv_moe_tokens_total{phase="%s"}'
TOUCHED = 'ffsv_moe_experts_touched{phase="decode"}'
READ = 'ffsv_attn_positions_read_total{kind="latent"}'


def zero_expert_share(ctx) -> Optional[float]:
    """Of the picks the window's routers made (real tokens a layer, times
    ``moe_topk``), those that cost no expert's arithmetic. On a ``# `` line
    the split by phase."""
    zero = {ph: _gained(ctx, ZERO % ph) for ph in PHASES}
    tokens = {ph: _gained(ctx, TOKENS % ph) for ph in PHASES}
    if None in zero.values() or None in tokens.values():
        return None
    k = ctx["cfg"]["moe_topk"]
    if sum(tokens.values()) <= 0:
        return None
    PR.say("picks that were no expert, of all picks: " + ", ".join(
        "%s %d of %d" % (ph, zero[ph], tokens[ph] * k) for ph in PHASES))
    return 100.0 * sum(zero.values()) / (sum(tokens.values()) * k)


def decode_scmoe_hbm_roofline(ctx) -> Optional[float]:
    """The WHOLE decode step: the bytes it must read (every matrix outside
    the experts once; the held experts a layer-step touched, the window's
    decode mean, in each sparse layer; the cache positions the traced
    blocks' rows had to read: their own rows and steps times the window's
    layer-positions a row-step, at the bytes a position must cost) over the
    chip's HBM bandwidth, as a share of ``decode_step_ms``."""
    step = R.decode_step_ms(ctx)
    touched = R.hist_mean(ctx, TOUCHED)
    pos = _gained(ctx, READ)
    row_steps = _gained(ctx, "ffsv_decode_steps_total")
    fam = ctx["family"]
    if (step is None or touched is None or not pos or not row_steps
            or not hasattr(fam, "decode_step_must_read")):
        return None
    spans = PR.spans_inside(ctx, ("decode_block",))
    steps = sum(s[3].get("steps", 0) for s in spans)
    rows = [s[3].get("rows") for s in spans]
    if not steps or None in rows:
        return None
    rows_a_step = sum(r * s[3].get("steps", 0)
                      for r, s in zip(rows, spans)) / steps
    need = fam.decode_step_must_read(ctx["cfg"], touched,
                                     rows_a_step * pos / row_steps)
    return 100.0 * (1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]) / step
