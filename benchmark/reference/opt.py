"""OPT in plain float32 ``jax.numpy``: no kernels, no cache, no batching,
matmul precision "highest".

Follows the published description (facebook/opt-6.7b ``modeling_opt.py``,
``do_layer_norm_before=true``): token embedding plus LEARNED positions looked
up at position + 2 (the offset OPT keeps from its fairseq origin),
pre-layer-norm blocks, multi-head attention with biases and the query scaled
by head_dim**-0.5 before the product, ReLU feed-forward with biases, final
layer norm, ``lm_head`` without bias.

Departures: none in the mathematics. ``word_embed_proj_dim`` equals the
hidden size at 6.7B, so there is no projection in or out. Weights arrive as
a dict of float32 arrays (the served weights, dequantised).

    weights["emb"] [V, H]; ["pos"] [P+2, H]; weights["layers"][i] =
    {ln1_g, ln1_b, wq, bq, wk, bk, wv, bv, wo, bo, ln2_g, ln2_b,
     fc1 [H, F], b1, fc2 [F, H], b2}; ["lnf_g"], ["lnf_b"], ["head"] [H, V]
"""

import jax
import jax.numpy as jnp


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32, full causal forward."""
    nh = cfg["num_attention_heads"]
    H = cfg["hidden_size"]
    hd = H // nh
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    with jax.default_matmul_precision("highest"):
        x = weights["emb"][tokens] + weights["pos"][pos + 2]
        for lw in weights["layers"]:
            h = _ln(x, lw["ln1_g"], lw["ln1_b"])
            q = ((h @ lw["wq"] + lw["bq"]) * hd ** -0.5).reshape(T, nh, hd)
            k = (h @ lw["wk"] + lw["bk"]).reshape(T, nh, hd)
            v = (h @ lw["wv"] + lw["bv"]).reshape(T, nh, hd)
            s = jnp.einsum("qnd,knd->nqk", q, k)
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("nqk,knd->qnd", p, v).reshape(T, H)
            x = x + a @ lw["wo"] + lw["bo"]
            h = _ln(x, lw["ln2_g"], lw["ln2_b"])
            x = x + jax.nn.relu(h @ lw["fc1"] + lw["b1"]) @ lw["fc2"] + lw["b2"]
        x = _ln(x, weights["lnf_g"], weights["lnf_b"])
        return x @ weights["head"]
