"""Arithmetic shared by the per-layer readers in ``benchmark/layer_metrics``.

A reader gets one ``ctx`` dict and returns a number or None:

    records     the requests that count for this cell's window (dicts, see
                lib/window.py), failed ones included
    w0, w1      the window on the benchmark's clock; seconds = w1 - w0
    tel         {"before": snapshot, "after": snapshot} of the program's
                metrics registry at the window's edges, or None
    trace       lib/trace.reduce_trace()'s dict for the traced stretch, or None
    cfg, traffic, family (module), peaks, memory_peak_bytes
"""

from __future__ import annotations

from typing import Optional

from . import trace as TR
from . import window as W


def hist_delta(ctx, name):
    """(count, sum) a histogram gained inside the window, or None."""
    tel = ctx.get("tel")
    if not tel or name not in tel["after"]:
        return None
    a, b = tel["after"][name], tel["before"].get(name, {})
    n = a.get("count", 0) - b.get("count", 0)
    if n <= 0:
        return None
    return n, a.get("sum", 0.0) - b.get("sum", 0.0)


def hist_mean(ctx, name) -> Optional[float]:
    d = hist_delta(ctx, name)
    return None if d is None else d[1] / d[0]


def record_percentile(ctx, fn, q: float = 50.0) -> Optional[float]:
    """Percentile of ``fn`` over the window's requests that resolved ok."""
    return W.tail(ctx["records"], fn, q)[0]


def batch_occupancy(ctx) -> Optional[float]:
    m = hist_mean(ctx, "ffsv_batch_occupancy")
    return None if m is None else 100.0 * m


def rounds_per_s(ctx) -> Optional[float]:
    n = 0
    for name in ("ffsv_decode_block_seconds", "ffsv_spec_block_seconds"):
        d = hist_delta(ctx, name)
        n += d[0] if d else 0
    return n / (ctx["w1"] - ctx["w0"]) if n else None


def _span_busy(ctx, name):
    """(spans, device-busy ns inside them) for the program spans ``name``
    that lie wholly inside the traced stretch."""
    tr = ctx.get("trace")
    if not tr:
        return None
    spans = [s for s in tr["spans"] if s[0] == name
             and s[1] >= tr["t0_ns"] and s[2] <= tr["t1_ns"]]
    if not spans:
        return None
    return spans, TR.busy_in(tr["merged"], [(s[1], s[2]) for s in spans])


def decode_step_ms(ctx) -> Optional[float]:
    """Device time of one decode step: device-busy time inside the
    program's decode-block spans over the steps those blocks ran."""
    sb = _span_busy(ctx, "decode_block")
    if sb is None:
        return None
    spans, busy_ns = sb
    steps = sum(s[3].get("steps", 0) for s in spans)
    return busy_ns / 1e6 / steps if steps else None


def prefill_tok_s(ctx) -> Optional[float]:
    sb = _span_busy(ctx, "prefill")
    if sb is None:
        return None
    spans, busy_ns = sb
    toks = sum(s[3].get("n_tokens", 0) for s in spans)
    return toks / (busy_ns / 1e9) if busy_ns > 0 and toks else None


def decode_hbm_roofline(ctx) -> Optional[float]:
    step = decode_step_ms(ctx)
    occ = hist_mean(ctx, "ffsv_batch_occupancy")
    kv = hist_mean(ctx, "ffsv_kv_cache_utilization")
    if step is None or occ is None or kv is None:
        return None
    from . import peaks as P

    a = ctx["cfg"]["assumed"]
    live_tokens = (occ * a["max_requests_per_batch"]
                   * kv * a["max_sequence_length"])
    fam = ctx["family"]
    need = P.decode_step_bytes(fam.decode_weights(ctx["cfg"]),
                               fam.cache_bytes_per_token(ctx["cfg"]),
                               live_tokens)
    bound_ms = 1e3 * need / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * bound_ms / step


def attn_share(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * TR.time_of(tr["ops"], "flash_attend") / 1e9 / (
        TR.total(tr["merged"]) / 1e9)


def device_idle(ctx) -> Optional[float]:
    tr = ctx.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
