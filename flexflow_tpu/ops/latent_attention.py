"""Serving attention over a latent cache (multi-head latent attention).

The layer caches, a position, ONE entry shared by all its query heads: the
normed low-rank latent ``c_kv`` and the rotated key part ``k_rope``
(ops/kv_layout.py ``latent_*`` owns how it is stored). Per-head keys and
values are ``c_kv`` carried up through ``W_kvb = [W_k | W_v]``; the op never
builds them. It takes the ABSORBED form, in prefill as in decode:

    c_q = RMSNorm(x W_qa);  [q_nope | q_rope]_h = c_q W_qb
    [c | k_r] = x W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r, p)
    q_lat_h = q_nope_h W_k,h^T                  (the key half, absorbed)
    score_h = s(p) * scale * [q_lat_h | RoPE(q_rope_h, p)] . [c_kv | k_rope]
    o_lat_h = softmax(score_h) . c_kv           (one stream: keys AND values)
    out = concat_h(o_lat_h W_v,h) W_o           (the value half, carried out)

equal to attending the expanded keys ``[c_kv W_k,h | k_rope]`` and values
``c_kv W_v,h`` in exact arithmetic. ``RoPE`` rotates halves
(``apply_rotary``): a checkpoint that pairs ADJACENT columns has the rope
columns of ``W_qb`` and ``W_kva`` permuted when it is loaded
(models/mistral4.py), which leaves every score as it was. Its frequencies are
a table in the op's attrs (YaRN is data, ``rotary_cos_sin``); ``s(p) = 1 +
beta * ln(1 + floor(p / period))`` scales a query by its own position.

A latent layer is served by incremental decoding: what stages, moves or
shares cache positions as a k/v pair refuses it (``refuse_windowed``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.core.initializer import (ConstantInitializer,
                                           default_kernel_initializer)
from flexflow_tpu.core.layer import WeightSpec
from flexflow_tpu.ffconst import OpType
from flexflow_tpu.ops import kv_layout as kvl
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.ops.inc_attention import (LATENT_STACK, _append_by_slot,
                                            append_kv_stacked, apply_rotary,
                                            rotary_cos_sin)


def _dims(attrs):
    return (attrs["num_q_heads"], attrs["q_lora_rank"],
            attrs["kv_lora_rank"], attrs["qk_nope_head_dim"],
            attrs["qk_rope_head_dim"], attrs["v_head_dim"])


def _want_pallas(attrs) -> bool:
    from flexflow_tpu import kernels as ffk

    return attrs.get("use_pallas", True) and ffk.use_pallas()


def _weight_specs(attrs, input_specs):
    (shape, d) = input_specs[0]
    E = shape[-1]
    H, qr, rank, dn, dr, dv = _dims(attrs)
    dt = attrs.get("data_type") or d
    init = attrs.get("kernel_initializer") or default_kernel_initializer()
    one = ConstantInitializer(1.0)
    return [
        WeightSpec("wq_a", (E, qr), dt, init),
        WeightSpec("q_norm", (qr,), dt, one),
        WeightSpec("wq_b", (qr, H * (dn + dr)), dt, init),
        WeightSpec("wkv_a", (E, rank + dr), dt, init),
        WeightSpec("kv_norm", (rank,), dt, one),
        # the up-projection's key and value halves, a head apart: [H, rank,
        # dn] and [H, rank, dv], so that a quantised half keeps the scales
        # of its own columns (quant.py: one scale per (head, column))
        WeightSpec("wk_b", (H, rank, dn), dt, init),
        WeightSpec("wv_b", (H, rank, dv), dt, init),
        WeightSpec("wo", (H * dv, E), dt, init),
    ]


def _init_state(attrs, input_specs):
    _, _, rank, _, dr, _ = _dims(attrs)
    S = attrs["max_seq_length"]
    width = kvl.latent_width(rank, dr, _want_pallas(attrs))
    return {"c_cache": jnp.zeros(
        kvl.latent_cache_shape(attrs["max_requests"], S, width),
        jnp.dtype(attrs.get("cache_dtype", "bfloat16")))}


def _payload(w):
    """(array, per-(head, column) scale or None) of a stacked weight."""
    from flexflow_tpu.quant import is_quantized

    if is_quantized(w):
        assert w.qtype == "int8", w.qtype
        return w.q, w.scale
    return w, None


def absorb_queries(q_nope, wk_b):
    """q_nope [R, Q, H, dn] -> q_lat [R, Q, H, rank] float32: the key half
    of the up-projection applied to the QUERY (a quantised half: its column
    scales go onto the query first, which is exact)."""
    w, scale = _payload(wk_b)
    if scale is not None:
        q_nope = (q_nope.astype(jnp.float32) * scale).astype(q_nope.dtype)
    return jnp.einsum("rqhd,hcd->rqhc", q_nope, w.astype(q_nope.dtype),
                      preferred_element_type=jnp.float32)


def carry_out(o_lat, wv_b, dtype):
    """o_lat [R, Q, H, rank] -> [R, Q, H * dv]: the value half of the
    up-projection applied to the attended latents."""
    w, scale = _payload(wv_b)
    R, Q, H, rank = o_lat.shape
    # heads leading on both sides: a batched gemm as every backend has it
    out = jax.lax.dot_general(
        jnp.swapaxes(o_lat.reshape(R * Q, H, rank), 0, 1),
        w.astype(o_lat.dtype), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)             # [H, R*Q, dv]
    if scale is not None:
        out = out * scale[:, None, :]
    return jnp.swapaxes(out.astype(dtype), 0, 1).reshape(R, Q, -1)


def position_scale(positions, beta: float, period: int):
    """s(p) [R, Q] float32 of the QUERY positions."""
    return 1.0 + beta * jnp.log1p(
        (positions // period).astype(jnp.float32))


def _attend(attrs, q, entry, cache, layer_idx: int, meta, ctx):
    """Append this step's entries ``entry [R, Q, 1, W]`` to layer
    ``layer_idx`` of the stack ``cache`` and attend ``q [R, Q, H, W]`` over
    it: ``([R, Q, H, rank], the new stack)``. A decode step (one token a
    row on the slot grid) fuses its append into the kernel; every other
    step appends first (``append_latent``)."""
    from flexflow_tpu import kernels as ffk
    from flexflow_tpu.kernels.attention import (flash_attend_latent,
                                                latent_form,
                                                latent_head_groups,
                                                reference_attend_latent,
                                                supports_latent)

    rank, S = attrs["kv_lora_rank"], attrs["max_seq_length"]
    Q, rows = q.shape[1], meta.slots
    qpos = meta.start_pos[:, None] + jnp.arange(Q)[None, :]
    lengths = jnp.where(meta.active, meta.start_pos + meta.num_tokens, 0)
    attend = functools.partial(
        flash_attend_latent, q, lengths=lengths, qpos=qpos, rank=rank,
        qk_scale=attrs["softmax_scale"], layer_idx=layer_idx,
        interpret=ffk.pallas_interpret_forced())
    kernel = False
    fused = Q == 1 and rows is None     # a decode step: the kernel appends
    if ffk.use_pallas(ctx.config if ctx is not None else None):
        kernel = supports_latent(S, cache.shape[-1], rank)
        groups = kernel and latent_head_groups(
            q.shape[2], Q, cache.shape[-1], rank, S, q.dtype.itemsize,
            cache.dtype.itemsize)
        if not kernel:
            ffk.record_fallback(
                f"latent cache S={S} width={cache.shape[-1]} rank={rank} "
                "not tileable")
        elif not groups:
            kernel = False
            ffk.record_fallback(
                f"latent queries of {Q} tokens: not one head's rows fit VMEM")
        else:
            ffk.record_fast_path(latent=(
                latent_form(q.shape[2] // groups * Q, S),
                "append" if fused else "grid" if rows is None else "rows"))
    if kernel and fused:
        appos = jnp.where(
            meta.active & (meta.num_tokens > 0) & (meta.start_pos < S),
            meta.start_pos, -1)
        return attend(cache=cache, append=(entry, appos))
    cache = append_latent(cache, layer_idx, entry, meta.start_pos,
                          meta.num_tokens, meta.active, rows)
    if kernel:
        return attend(cache=cache, rows=rows), cache
    c = cache[layer_idx] if rows is None else cache[layer_idx][rows]
    return reference_attend_latent(
        q, c, lengths, qpos, rank=rank,
        qk_scale=attrs["softmax_scale"]), cache


@register_op
class IncMultiHeadLatentAttention(OpImpl):
    """Incremental-decoding attention over a per-slot latent cache."""

    op_type = OpType.INC_MULTIHEAD_LATENT_ATTENTION
    quant_aware = True

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        (shape, d) = input_specs[0]
        return [(tuple(shape[:-1]) + (attrs["embed_dim"],),
                 attrs.get("data_type") or d)]

    weight_specs = staticmethod(_weight_specs)
    init_state = staticmethod(_init_state)

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        from flexflow_tpu.ops.norm import _rms_norm
        from flexflow_tpu.quant import qmatmul

        x = inputs[0]
        meta = ctx.batch_config
        assert meta is not None, "serving ops need ctx.batch_config"
        if hasattr(meta, "ancestor") or getattr(ctx, "kv_override",
                                                None) is not None:
            raise NotImplementedError(
                "a latent attention layer is served by incremental decoding "
                "on one chip only: tree verification, beam drafting and a "
                "pipeline stage stage and move cache positions as a k/v pair")
        H, _, rank, dn, dr, _ = _dims(attrs)
        eps = attrs["norm_eps"]
        R, Q = x.shape[0], x.shape[1]
        cq = _rms_norm(qmatmul(x, params["wq_a"]), params["q_norm"], eps)
        q = qmatmul(cq, params["wq_b"]).reshape(R, Q, H, dn + dr)
        ckr = qmatmul(x, params["wkv_a"])
        c_kv = _rms_norm(ckr[..., :rank], params["kv_norm"], eps)
        cos, sin = rotary_cos_sin(meta.positions, dr, attrs["rope_theta"],
                                  q.dtype, inv_freq=attrs["rope_inv_freq"])
        if attrs["rope_factor"] != 1.0:
            cos, sin = cos * attrs["rope_factor"], sin * attrs["rope_factor"]
        q_rope = apply_rotary(q[..., dn:], cos, sin)
        k_rope = apply_rotary(ckr[..., None, rank:], cos, sin)[:, :, 0]
        q_lat = absorb_queries(q[..., :dn], params["wk_b"])
        beta = attrs.get("pos_scale_beta", 0.0)
        if beta:
            s_p = position_scale(meta.positions, beta,
                                 attrs["pos_scale_period"])[..., None, None]
            q_lat = q_lat * s_p
            q_rope = (q_rope.astype(jnp.float32) * s_p).astype(q.dtype)
        # (compile stacks the latent layers' caches, however many)
        cache = (ctx.state_out.get(LATENT_STACK)
                 or ctx.state_in[LATENT_STACK])["c"]
        width = cache.shape[-1]
        o_lat, cache = _attend(
            attrs, kvl.latent_query(q_lat.astype(q.dtype), q_rope, width),
            kvl.latent_entry(c_kv, k_rope, width), cache,
            attrs["cache_layer_idx"], meta, ctx)
        ctx.state_out[LATENT_STACK] = {"c": cache}
        out = carry_out(o_lat, params["wv_b"], x.dtype)
        return [qmatmul(out, params["wo"])]


def append_latent(cache, layer_idx: int, entry, start_pos, num_tokens, active,
                  slots):
    """Write this step's entries ``[R, Q, 1, W]`` into layer ``layer_idx``
    of the stack in place, through the appends of the other kinds
    (ops/inc_attention.py): by slot for the compact prefill batch, the row
    scatter for a step on the slot grid. A decode step on the kernel's path
    does not come here: its append is fused (``_attend``)."""
    if slots is not None:
        return _append_by_slot(cache, jnp.int32(layer_idx), entry, start_pos,
                               active, slots, num_tokens, pack=1, ring=False)
    return append_kv_stacked(cache, layer_idx, entry, start_pos, num_tokens,
                             active)
