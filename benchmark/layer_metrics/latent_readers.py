"""Arithmetic shared by the per-layer readers of a model whose attention
layers keep a latent cache (Mistral-4): ``latent_attn_share``,
``attn_latent_hbm_roofline``, ``attn_latent_mxu_roofline``,
``kv_latent_bytes_per_pos``.

``ctx`` is what ``lib/readers.py`` documents. The latent attention kernel is
the device operations whose name contains ``flash_attend_latent`` (the
Pallas call's name). The counters are the program's
``ffsv_attn_positions_read_total{kind="latent"}`` (layer-positions the decode
steps' rows had to attend), ``ffsv_prefill_attended_pairs_total`` ((query,
key) pairs the prefill steps' rows had to attend, causal, a layer) and the
gauge ``ffsv_kv_cache_bytes{kind="latent"}`` (what compile allocated). The
shapes come from the cell's family (``families/mistral4.py``). Every count
is of bytes or operations that MUST be read or done, by the PUBLISHED
description of the layer: a share over 100 would mean a count too high. A
program without the kernel name or the series (any commit before PR 35, any
model without latent layers) gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.lib import readers as R
from benchmark.lib import trace as TR
from benchmark.layer_metrics.window_readers import _gained, _kernel_ns_in

LATENT_KERNEL = "flash_attend_latent"
READ = 'ffsv_attn_positions_read_total{kind="latent"}'
BYTES = 'ffsv_kv_cache_bytes{kind="latent"}'
PAIRS = "ffsv_prefill_attended_pairs_total"


def latent_attn_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], LATENT_KERNEL)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None


def kv_latent_bytes_per_pos(ctx) -> Optional[float]:
    """What a cache position costs a latent layer AS STORED: the bytes
    compile allocated for the kind over slots x positions x latent layers."""
    tel = ctx.get("tel")
    if not tel or BYTES not in tel["after"]:
        return None
    cfg = ctx["cfg"]
    a = cfg["assumed"]
    layers = ctx["family"].layers_of(cfg, "latent")
    return tel["after"][BYTES]["value"] / (
        a["max_requests_per_batch"] * a["max_sequence_length"] * layers)


def attn_latent_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the cache bytes the latent layers had to read in the traced
    decode blocks (the window's mean layer-positions a row-step, times the
    traced blocks' steps, times the mean live rows, times the bytes of a
    position that must be read) over the chip's HBM bandwidth, as a share
    of the latent kernel's time inside those blocks."""
    hit = _kernel_ns_in(ctx, LATENT_KERNEL, "decode_block")
    pos = _gained(ctx, READ)
    row_steps = _gained(ctx, "ffsv_decode_steps_total")
    occ = R.hist_mean(ctx, "ffsv_batch_occupancy")
    if hit is None or not pos or not row_steps or occ is None:
        return None
    spans, ns = hit
    rows = occ * ctx["cfg"]["assumed"]["max_requests_per_batch"]
    steps = sum(s[3].get("steps", 0) for s in spans)
    need = (steps * rows * pos / row_steps
            * ctx["family"].cache_position_bytes(ctx["cfg"]))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (ns / 1e9)


def attn_latent_mxu_roofline(ctx) -> Optional[float]:
    """Prefill: the arithmetic of the (query, key) pairs the traced prefill
    steps had to attend (their real tokens, times the pairs a prefilled
    token attended in the window's prefill steps, times the latent layers,
    times the published arithmetic of one pair) over the chip's bf16 peak,
    as a share of the latent kernel's time inside those steps."""
    hit = _kernel_ns_in(ctx, LATENT_KERNEL, "prefill")
    pairs = _gained(ctx, PAIRS)
    tokens = _gained(ctx, "ffsv_prefill_tokens_total")
    if hit is None or not pairs or not tokens:
        return None
    spans, ns = hit
    fam, cfg = ctx["family"], ctx["cfg"]
    traced = (sum(s[3].get("n_tokens", 0) for s in spans) * pairs / tokens
              * fam.layers_of(cfg, "latent"))
    need = traced * fam.latent_pair_flops(cfg)
    return 100.0 * (need / ctx["peaks"]["bf16_flops"]) / (ns / 1e9)
