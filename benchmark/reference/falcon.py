"""Falcon (7B architecture) in plain float32 ``jax.numpy``: no kernels, no
cache, no batching, matmul precision "highest".

Follows the published description (tiiuae/falcon-7b ``modeling_falcon.py``,
``new_decoder_architecture=false``, ``parallel_attn=true``,
``multi_query=true``): one input layer norm feeds attention and the MLP in
parallel, one key/value head shared by all query heads, rotary embedding in
the rotate-half form over the whole head, GELU (exact, erf) MLP of 4x width,
no biases, final layer norm, untied-in-memory ``lm_head``.

Departures: none in the mathematics. Weights arrive as a dict of float32
arrays (the served weights, dequantised), not a checkpoint.

    weights["emb"] [V, H]; weights["layers"][i] = {ln_g, ln_b, wq [H, nh*hd],
    wk [H, hd], wv [H, hd], wo [nh*hd, H], up [H, 4H], down [4H, H]};
    weights["lnf_g"], ["lnf_b"], ["head"] [H, V]
"""

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)          # [T, hd]
    return x * jnp.cos(ang)[:, None, :] + _rotate_half(x) * jnp.sin(ang)[:, None, :]


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32, full causal forward."""
    nh = cfg["num_attention_heads"]
    H = cfg["hidden_size"]
    hd = H // nh
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    theta = cfg.get("rope_theta", 10000.0)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    with jax.default_matmul_precision("highest"):
        x = weights["emb"][tokens]
        for lw in weights["layers"]:
            h = _ln(x, lw["ln_g"], lw["ln_b"], eps)
            q = (h @ lw["wq"]).reshape(T, nh, hd)
            k = (h @ lw["wk"]).reshape(T, 1, hd)
            v = (h @ lw["wv"]).reshape(T, hd)
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)[:, 0]
            s = jnp.einsum("qnd,kd->nqk", q, k) / jnp.sqrt(jnp.float32(hd))
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("nqk,kd->qnd", p, v).reshape(T, nh * hd)
            mlp = jax.nn.gelu(h @ lw["up"], approximate=False) @ lw["down"]
            x = x + a @ lw["wo"] + mlp
        x = _ln(x, weights["lnf_g"], weights["lnf_b"], eps)
        return x @ weights["head"]
