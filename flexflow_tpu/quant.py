"""Weight-only int8/int4 quantization for serving.

Capability parity with the reference's 4/8-bit weight compression
(src/ops/kernels/decompress_kernels.cu, inference/utils/
compress_llama_weights.py, flags config.h:161-163). TPU-idiomatic design:
weights are stored on device as int8 (int4 packs two nibbles per byte) with
a per-output-channel float scale; the jitted step dequantizes on the fly so
the HBM read of each weight is 1/4 or 1/8 the bytes — on
bandwidth-bound decode steps that is the win; XLA fuses the dequant
multiply into the consumer.

Symmetric per-column scheme (the reference's decompress path is also
scale-only): q = round(w / s), s = max|w_col| / qmax.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """Pytree leaf-pair: int8 payload + per-column scale, with static
    metadata (qtype, original rows, original dtype) so it passes through
    jit boundaries. A stacked ``[E, in, out]`` weight (the routed experts,
    ops/moe.py) keeps one scale per (expert, output column): ``[E, out]``."""

    def __init__(self, qtype: str, q, scale, rows: int, dtype: str):
        self.qtype = qtype
        self.q = q
        self.scale = scale
        self.rows = rows
        self.dtype = dtype

    def tree_flatten(self):
        return (self.q, self.scale), (self.qtype, self.rows, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], children[0], children[1], aux[1], aux[2])

    @property
    def nbytes(self) -> int:
        return getattr(self.q, "nbytes", 0) + getattr(self.scale, "nbytes", 0)

    @property
    def shape(self):
        return tuple(self.q.shape[:-2]) + (self.rows, self.q.shape[-1])

    def __repr__(self):
        return (f"QuantizedWeight({self.qtype}, shape={self.shape}, "
                f"dtype={self.dtype})")


_QTYPE_ALIASES = {"int8": "int8", "8": "int8", "q8": "int8",
                  "int4": "int4", "4": "int4", "q4": "int4"}


def normalize_qtype(qtype) -> Optional[str]:
    """Canonicalize a user-facing quantization spec (spec-JSON ``quantize``
    key, CLI flags) to ``"int8"``/``"int4"``/``None``. Unknown values fail
    loudly — a typo silently serving fp weights would defeat the point."""
    if qtype is None or qtype is False:
        return None
    q = str(qtype).strip().lower()
    if q in ("", "none", "fp", "float", "fp32", "bf16", "off"):
        return None
    if q not in _QTYPE_ALIASES:
        raise ValueError(
            f"unknown quantization type {qtype!r}; expected int8/int4/none")
    return _QTYPE_ALIASES[q]


def quantize_array(w, qtype: str) -> QuantizedWeight:
    """Quantize a 2-D ``[in, out]`` float array (int4 packs two rows per
    byte) or, int8 only, a stack ``[E, in, out]`` of them."""
    w = jnp.asarray(w)
    assert w.ndim in (2, 3), w.shape
    if w.ndim == 3 and qtype != "int8":
        raise NotImplementedError(
            f"{qtype} for a stacked [E, in, out] weight {w.shape}: only int8 "
            "is implemented (int4's row packing is 2-D)")
    # (qmax an operand: XLA turns a division by a constant into a
    # multiplication by its reciprocal, which is another last bit)
    q, scale = _quantize(w, jnp.float32(127.0 if qtype == "int8" else 7.0),
                         qtype)
    return QuantizedWeight(qtype, q, scale, int(w.shape[-2]), str(w.dtype))


@functools.partial(jax.jit, static_argnames=("qtype",))
def _quantize(w, qmax, qtype: str):
    """(payload, scale) of ``quantize_array``: ONE program a weight shape.
    As eager operations these were eight programs a shape, most of the
    programs a cold build compiles (PERF.md section 6, PR 35). The numbers
    are the eager ones to the bit: the scale is ``max|w| / qmax`` ROUNDED TO
    ``w``'s OWN TYPE, as the eager division of a bfloat16 weight rounded
    it; inside one fused program XLA would keep the quotient's float32
    bits (``xla_allow_excess_precision``), so the rounding is spelled out
    with ``reduce_precision``, which no pass removes."""
    fi = jnp.finfo(w.dtype)
    scale = jax.lax.reduce_precision(
        jnp.max(jnp.abs(w), axis=-2).astype(jnp.float32) / qmax,
        exponent_bits=fi.nexp, mantissa_bits=fi.nmant)    # [out] | [E, out]
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[..., None, :]),
                 -qmax, qmax).astype(jnp.int8)
    if qtype == "int4":
        if q.shape[0] % 2:
            q = jnp.pad(q, ((0, 1), (0, 0)))
        lo = q[0::2] & 0x0F
        hi = (q[1::2] & 0x0F) << 4
        q = (lo | hi).astype(jnp.int8)                    # [ceil(in/2), out]
    return q, scale


def _unpack_int4(q, rows: int):
    lo = (q << 4).astype(jnp.int8) >> 4                   # sign-extend nibble
    hi = q >> 4                                           # arithmetic shift
    full = jnp.stack([lo, hi], axis=1).reshape(-1, q.shape[1])
    return full[:rows]


def dequantize_array(leaf: QuantizedWeight, dtype=None):
    q = leaf.q
    if leaf.qtype == "int4":
        q = _unpack_int4(q, leaf.rows)
    out_dtype = dtype or jnp.dtype(leaf.dtype)
    return (q.astype(jnp.float32)
            * leaf.scale[..., None, :]).astype(out_dtype)


def is_quantized(leaf) -> bool:
    return isinstance(leaf, QuantizedWeight)


# weights eligible for quantization: the serving matmul weights
_QUANT_NAMES = {"kernel", "wq", "wk", "wv", "wo", "weight",
                "w1", "w2", "w3", "gate", "up", "down",
                # a latent attention layer's (ops/latent_attention.py)
                "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b",
                # the joined projection of ops/cca_attention.py
                "wqkv",
                # a plain attention layer's output gate (``output_gate``)
                "wg",
                # ops/kda_attention.py: the joined first halves of its
                # low-rank gates with beta's projection, and their second
                "wlow", "wfb", "wgb",
                # ops/ssd_mixer.py: the mixer's two matrices
                "win", "wout"}
# ... and of those, the ones that may be a stack [E, in, out] (ops/moe.py;
# a latent layer's up-projection halves, a head apart)
_STACKED_NAMES = {"gate", "up", "down", "wk_b", "wv_b"}


def quantize_params(params: Dict[str, Dict[str, Any]], qtype: str,
                    min_dim: int = 64) -> Dict[str, Dict[str, Any]]:
    """Quantize every eligible 2-D weight in a model params tree, and the
    stacked expert weights ``[E, in, out]`` (per expert and column)."""
    assert qtype in ("int8", "int4"), qtype
    out: Dict[str, Dict[str, Any]] = {}
    for layer, ws in params.items():
        new_ws = {}
        for name, w in ws.items():
            arr = jnp.asarray(w) if not is_quantized(w) else None
            if (arr is not None and name in _QUANT_NAMES
                    and (arr.ndim == 2
                         or arr.ndim == 3 and name in _STACKED_NAMES)
                    and min(arr.shape[-2:]) >= min_dim
                    and jnp.issubdtype(arr.dtype, jnp.floating)):
                new_ws[name] = quantize_array(arr, qtype)
            else:
                new_ws[name] = w
        out[layer] = new_ws
    return out


def qmatmul(x, w, compute_dtype=None, out_dtype=None):
    """``x @ w`` for a possibly-quantized 2-D weight, with the per-column
    scale factored OUT of the gemm: y = (x @ q) * scale.

    ``out_dtype`` overrides only the RESULT dtype (the gemm operands stay
    in ``compute_dtype``): logits heads use out_dtype=float32 to keep the
    f32 accumulator without paying for an f32-operand gemm.

    Exact for the symmetric per-column scheme (diag-scale commutes with the
    contraction), and crucial for bandwidth: the gemm fusion then reads the
    int8 payload straight from HBM with an on-the-fly convert, instead of
    XLA materializing a dequantized bf16 copy of the weight (int8 read +
    bf16 write + bf16 read = 3x the traffic — measured ~25% of a 7B int8
    decode step before this path existed)."""
    cd = compute_dtype or x.dtype
    od = out_dtype or cd
    if not is_quantized(w):
        y = jax.lax.dot_general(
            x.astype(cd), jnp.asarray(w).astype(cd),
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return y.astype(od)
    payload = w.q
    if w.qtype == "int4":
        payload = _unpack_int4(payload, w.rows)
    y = jax.lax.dot_general(
        x.astype(cd), payload.astype(cd),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (y * w.scale).astype(od)


def qmatmul_t(x, table, compute_dtype=None, out_dtype=None):
    """``x @ table.T`` for a possibly-quantized ``[rows, in]`` table (an
    embedding's, read as a tied head). Its scale is per COLUMN of the table,
    the contracted dim here, so it goes onto ``x`` first, which is exact
    for the same reason ``qmatmul``'s goes onto the result; the gemm reads
    the int8 payload as stored."""
    cd = compute_dtype or x.dtype
    payload = table
    if is_quantized(table):
        if table.qtype != "int8":
            raise NotImplementedError(
                f"a tied head over an {table.qtype} table: its rows pack in "
                "pairs along the dim the head keeps")
        x = x.astype(jnp.float32) * table.scale
        payload = table.q
    y = jax.lax.dot_general(
        x.astype(cd), jnp.asarray(payload).astype(cd),
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return y.astype(out_dtype or cd)


def qtake(table, ids):
    """Embedding-row gather for a possibly-quantized table: gather the
    packed rows first, dequantize only the gathered rows (the eager path
    would materialize the whole dequantized table per step)."""
    if not is_quantized(table):
        return jnp.take(table, ids, axis=0)
    if table.qtype == "int4":
        # rows pack in pairs: entry r lives in packed row r//2, nibble r%2
        packed = jnp.take(table.q, ids // 2, axis=0)
        lo = (packed << 4).astype(jnp.int8) >> 4
        hi = packed >> 4
        rows = jnp.where((ids % 2 == 0)[..., None], lo, hi)
    else:
        rows = jnp.take(table.q, ids, axis=0)
    out_dtype = jnp.dtype(table.dtype)
    return (rows.astype(jnp.float32) * table.scale).astype(out_dtype)


def dequantize_layer_params(ws: Optional[Dict[str, Any]], dtype=None):
    """Lazily dequantize one layer's weights (called inside the jitted
    step; XLA fuses the scale-multiply into the consumer matmul)."""
    if not ws:
        return ws
    if not any(is_quantized(v) for v in ws.values()):
        return ws
    return {k: dequantize_array(v, dtype) if is_quantized(v) else v
            for k, v in ws.items()}


def quantized_nbytes(params) -> int:
    """Device bytes of the (possibly quantized) params tree."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += getattr(leaf, "nbytes", 0)
    return total
