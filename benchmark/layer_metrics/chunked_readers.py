"""Arithmetic shared by the per-layer readers of a model whose attention
layers keep an exact window and one summary pair a chunk of the positions
before it (EvaByte): ``chunked_attn_share``, ``attn_chunked_hbm_roofline``,
``decode_chunked_hbm_roofline``, ``kv_chunked_read_share``,
``summarise_share``.

``ctx`` is what ``lib/readers.py`` documents. The attention kernel is the
device operations whose name contains ``flash_attend_chunked`` (the Pallas
call's name on a chunked layer), the summariser those that contain
``eva_summarise`` (the decode step's kernel; a prefill step pools its fresh
keys in XLA fusions that carry no name of their own and are not in it). The
counters are the program's, counted on the host from the batch's lengths:
``ffsv_attn_positions_read_total{kind="summary"}`` and
``{kind="chunk_window"}`` (layer-entries the decode steps' rows had to
read), ``ffsv_attn_positions_held_total`` (layer-positions those rows held),
``ffsv_attn_prefill_entries_total`` (layer-entries the prefill steps'
segments had to read); and, on every ``decode_block`` span, ``entries``: the
layer-entries that block's rows had to read, so that a share of the traced
blocks' time is set against the traced blocks' own bytes. The
shapes come from the cell's family (``families/evabyte.py``). Every count is
of bytes that MUST be read: a share over 100 would mean a count too high. A
program without the kernel names or the series (any commit before PR 42,
any other model) gives every reader here None.
"""

from __future__ import annotations

from typing import Optional

from benchmark.layer_metrics.window_readers import _gained, _kernel_ns_in
from benchmark.lib import phase_readers as PR
from benchmark.lib import readers as R
from benchmark.lib import trace as TR

ATTEND_KERNEL = "flash_attend_chunked"
SUMMARISE_KERNEL = "eva_summarise"
READ = 'ffsv_attn_positions_read_total{kind="%s"}'


def _share_of_busy(ctx, needle: str) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], needle)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None


def chunked_attn_share(ctx) -> Optional[float]:
    return _share_of_busy(ctx, ATTEND_KERNEL)


def summarise_share(ctx) -> Optional[float]:
    return _share_of_busy(ctx, SUMMARISE_KERNEL)


def kv_chunked_read_share(ctx) -> Optional[float]:
    """Of the positions the decode steps' rows held, the entries they had
    to read (a full cache reads every position: 100)."""
    got = [_gained(ctx, READ % k) for k in ("summary", "chunk_window")]
    held = _gained(ctx, "ffsv_attn_positions_held_total")
    if None in got or not held:
        return None
    return 100.0 * sum(got) / held


def _traced_entries(spans) -> Optional[float]:
    """Layer-entries the rows of the decode blocks ``spans`` had to read:
    the program's own count of each block, from its rows' lengths, on the
    span (``entries``)."""
    got = [s[3].get("entries") for s in spans]
    return None if not got or None in got else float(sum(got))


def attn_chunked_hbm_roofline(ctx) -> Optional[float]:
    """Decode: the cache bytes the traced decode blocks' rows had to read
    (the blocks' own ``entries``, times the bytes of an entry of a layer)
    over the chip's HBM bandwidth, as a share of the kernel's time inside
    those blocks. On a ``# `` line the prefill form's: the layer-entries the
    window's prefill segments had to read a prefilled token, times the
    traced steps' tokens, over the kernel's time inside those steps (a
    segment's 128 queries share one read, so this form is bound by
    arithmetic before bytes)."""
    hit = _kernel_ns_in(ctx, ATTEND_KERNEL, "decode_block")
    entries = None if hit is None else _traced_entries(hit[0])
    if entries is None:
        return None
    spans, ns = hit
    entry = ctx["family"].cache_position_bytes(ctx["cfg"])
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    pre = _kernel_ns_in(ctx, ATTEND_KERNEL, "prefill")
    read = _gained(ctx, "ffsv_attn_prefill_entries_total")
    tokens = _gained(ctx, "ffsv_prefill_tokens_total")
    if pre is not None and read and tokens:
        traced = sum(s[3].get("n_tokens", 0) for s in pre[0])
        PR.say("chunked attention, prefill form: %.1f%% of its HBM bound "
               "(%d traced tokens, %.3f ms of kernel a step)"
               % (100.0 * (traced * read / tokens * entry / bw)
                  / (pre[1] / 1e9), traced, pre[1] / 1e6 / len(pre[0])))
    return 100.0 * (entries * entry / bw) / (ns / 1e9)


def decode_chunked_hbm_roofline(ctx) -> Optional[float]:
    """The WHOLE decode step: the bytes it must read (every weight matrix
    once, lib/peaks.decode_step_bytes, and the entries its rows had to
    read: the traced blocks' own ``entries`` over their steps) over the
    chip's HBM bandwidth, as a share of ``decode_step_ms``."""
    step = R.decode_step_ms(ctx)
    spans = PR.spans_inside(ctx, ("decode_block",))
    steps = sum(s[3].get("steps", 0) for s in spans)
    entries = _traced_entries(spans)
    if step is None or entries is None or not steps:
        return None
    from benchmark.lib import peaks as P

    fam, cfg = ctx["family"], ctx["cfg"]
    need = P.decode_step_bytes(fam.decode_weights(cfg),
                               fam.cache_position_bytes(cfg),
                               entries / steps)
    return 100.0 * (1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]) / step
