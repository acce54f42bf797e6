"""Arithmetic shared by the per-layer readers of the routed-expert layer
(ops/moe.py, kernels/moe.py): ``moe_share``, ``experts_touched``,
``expert_load_skew``, ``moe_hbm_roofline``, ``moe_prefill_mxu_roofline``.

``ctx`` is what ``lib/readers.py`` documents. The kernel is the device
operations whose name contains ``moe_experts`` (the Pallas call's name; the
sort, gather and combine around it are XLA fusions and count elsewhere). The
counters are the program's ``ffsv_moe_*`` series, which it keeps on the
device and brings over when a snapshot is taken. A program without the
kernel or the series (any commit before PR 26, any model without experts)
gives every reader here None.
"""

from __future__ import annotations

import bisect
from typing import Optional

from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R
from benchmark.lib import trace as TR

KERNEL = "moe_experts"
TOUCHED = 'ffsv_moe_experts_touched{phase="decode"}'


def kernel_ns_in(ctx, span_name: str):
    """(spans, self nanoseconds of the kernel inside them) for the program
    spans ``span_name`` that lie wholly inside the traced stretch: a device
    call's operations run between its span's ends."""
    spans = PR.spans_inside(ctx, (span_name,))       # by start time
    if not spans:
        return None
    starts = [s[1] for s in spans]
    ns = 0.0
    for name, start, dur in ctx["trace"]["ops"]:
        if KERNEL in name:      # a custom call: no operation inside it
            i = bisect.bisect_right(starts, start + 0.5 * dur) - 1
            if i >= 0 and start + 0.5 * dur < spans[i][2]:
                ns += dur
    return (spans, ns) if ns > 0 else None


def moe_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], KERNEL)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None


def experts_touched(ctx) -> Optional[float]:
    """Distinct experts one expert-layer call of a decode step read, the
    mean over the window's layer-steps."""
    return R.hist_mean(ctx, TOUCHED)


def expert_load_skew(ctx) -> Optional[float]:
    """Routed pairs of the busiest expert over the mean expert's, in the
    window, all layers and phases together: 1.0 is an even load."""
    tel = ctx.get("tel")
    if not tel:
        return None
    gained = []
    for e in range(ctx["cfg"]["num_experts"]):
        name = f'ffsv_moe_expert_pairs_total{{expert="{e}"}}'
        if name not in tel["after"]:
            return None
        gained.append(tel["after"][name]["value"]
                      - tel["before"].get(name, {}).get("value", 0.0))
    mean = sum(gained) / len(gained)
    return max(gained) / mean if mean > 0 else None


def moe_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the bytes of the experts the kernel had to read (the mean
    distinct experts of a layer-step in the window, times the traced decode
    blocks' layer-steps, times one expert's bytes) over the chip's HBM
    bandwidth, as a share of the kernel's time inside those blocks."""
    hit = kernel_ns_in(ctx, "decode_block")
    touched = experts_touched(ctx)
    if hit is None or touched is None:
        return None
    spans, ns = hit
    layer_steps = (sum(s[3].get("steps", 0) for s in spans)
                   * ctx["cfg"]["num_hidden_layers"])
    need = layer_steps * touched * ctx["family"].expert_bytes(ctx["cfg"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def moe_prefill_mxu_roofline(ctx) -> Optional[float]:
    """Prefill: the arithmetic of the routed pairs (the traced prefill
    steps' real tokens, times the experts a token goes to, times the
    layers, times one pair's operations) over the chip's bf16 peak, as a
    share of the kernel's time inside those steps."""
    hit = kernel_ns_in(ctx, "prefill")
    if hit is None:
        return None
    spans, ns = hit
    cfg = ctx["cfg"]
    pairs = (sum(s[3].get("n_tokens", 0) for s in spans)
             * cfg["num_experts_per_tok"] * cfg["num_hidden_layers"])
    need = pairs * ctx["family"].pair_flops(cfg)
    return 100.0 * (need / ctx["peaks"]["bf16_flops"]) / (ns / 1e9)
