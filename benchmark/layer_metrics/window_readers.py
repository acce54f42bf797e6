"""Arithmetic shared by the per-layer readers of a model whose attention
layers are of two kinds and whose expert layers hold a share of the router's
experts (K-EXAONE): ``window_attn_share``, ``attn_window_hbm_roofline``,
``kv_window_share``, ``moe_local_hbm_roofline``, ``moe_local_mxu_roofline``.

``ctx`` is what ``lib/readers.py`` documents. The windowed attention kernel
is the device operations whose name contains ``flash_attend_window`` (the
Pallas call's name on a windowed layer; a full layer's is ``flash_attend``
alone), the expert kernel those that contain ``moe_experts``. The counters
are the program's ``ffsv_attn_positions_read_total{kind}`` (layer-positions
the decode steps' rows had to attend) and ``ffsv_moe_*`` (computed pairs and
held experts only). The shapes come from the cell's family
(``families/exaone_moe.py``). Every count is of bytes or operations that
MUST be read or done: a share over 100 would mean a count too high. A
program without the kernel names or the series (any commit before PR 31, any
model with one kind of layer) gives every reader here None.
"""

from __future__ import annotations

import bisect
from typing import Optional

from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R
from benchmark.lib import trace as TR

WINDOW_KERNEL = "flash_attend_window"
EXPERT_KERNEL = "moe_experts"


def _gained(ctx, name) -> Optional[float]:
    """What a counter gained inside the window, or None without it."""
    tel = ctx.get("tel")
    if not tel or name not in tel["after"]:
        return None
    return (tel["after"][name]["value"]
            - tel["before"].get(name, {}).get("value", 0.0))


def _kernel_ns_in(ctx, needle: str, span_name: str):
    """(spans, self nanoseconds of the kernel ``needle`` inside them) for
    the program spans ``span_name`` wholly inside the traced stretch (a
    custom call holds no other operation, so its time is its own)."""
    if not ctx.get("trace"):
        return None
    spans = PR.spans_inside(ctx, (span_name,))       # by start time
    if not spans:
        return None
    starts = [s[1] for s in spans]
    ns = 0.0
    for name, start, dur in ctx["trace"]["ops"]:
        if needle in name:
            mid = start + 0.5 * dur
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < spans[i][2]:
                ns += dur
    return (spans, ns) if ns > 0 else None


def _positions(ctx):
    """(window, full) layer-positions the window's decode steps read."""
    got = [_gained(ctx, f'ffsv_attn_positions_read_total{{kind="{k}"}}')
           for k in ("window", "full")]
    return None if None in got or sum(got) <= 0 else got


def window_attn_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], WINDOW_KERNEL)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None


def kv_window_share(ctx) -> Optional[float]:
    """Of the cache bytes the window's decode steps had to read, the share
    the windowed layers read (a position costs every layer the same)."""
    pos = _positions(ctx)
    return None if pos is None else 100.0 * pos[0] / (pos[0] + pos[1])


def attn_window_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the cache bytes the windowed layers had to read in the
    traced decode blocks (the window's mean layer-positions a row-step,
    times the traced blocks' steps, times the mean live rows, times the
    bytes a position costs a layer) over the chip's HBM bandwidth, as a
    share of the windowed kernel's time inside those blocks."""
    hit = _kernel_ns_in(ctx, WINDOW_KERNEL, "decode_block")
    pos = _positions(ctx)
    row_steps = _gained(ctx, "ffsv_decode_steps_total")
    occ = R.hist_mean(ctx, "ffsv_batch_occupancy")
    if hit is None or pos is None or not row_steps or occ is None:
        return None
    spans, ns = hit
    rows = occ * ctx["cfg"]["assumed"]["max_requests_per_batch"]
    steps = sum(s[3].get("steps", 0) for s in spans)
    need = (steps * rows * pos[0] / row_steps
            * ctx["family"].cache_position_bytes(ctx["cfg"]))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def moe_local_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the bytes of the held experts the kernel had to read (the
    mean distinct held experts of a layer-step in the window, times the
    traced decode blocks' steps, times the family's SPARSE layers, times one
    expert's bytes) over the chip's HBM bandwidth, as a share of the
    kernel's time inside those blocks."""
    hit = _kernel_ns_in(ctx, EXPERT_KERNEL, "decode_block")
    touched = R.hist_mean(ctx, 'ffsv_moe_experts_touched{phase="decode"}')
    if hit is None or touched is None:
        return None
    spans, ns = hit
    fam, cfg = ctx["family"], ctx["cfg"]
    layer_steps = (sum(s[3].get("steps", 0) for s in spans)
                   * fam.layers_of(cfg, "sparse"))
    need = layer_steps * touched * fam.expert_bytes(cfg)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def moe_local_mxu_roofline(ctx) -> Optional[float]:
    """Prefill: the arithmetic of the COMPUTED pairs (the traced prefill
    steps' real tokens, times the family's sparse layers, times the pairs
    the held experts computed per token and layer in the window's prefill
    steps: ``routed`` over ``tokens``, about one of a token's eight) over
    the chip's bf16 peak, as a share of the kernel's time in those steps."""
    hit = _kernel_ns_in(ctx, EXPERT_KERNEL, "prefill")
    routed = _gained(ctx, 'ffsv_moe_routed_pairs_total{phase="prefill"}')
    tokens = _gained(ctx, 'ffsv_moe_tokens_total{phase="prefill"}')
    if hit is None or not routed or not tokens:
        return None
    spans, ns = hit
    fam, cfg = ctx["family"], ctx["cfg"]
    pairs = (sum(s[3].get("n_tokens", 0) for s in spans)
             * fam.layers_of(cfg, "sparse") * routed / tokens)
    need = pairs * fam.pair_flops(cfg)
    return 100.0 * (need / ctx["peaks"]["bf16_flops"]) / (ns / 1e9)
