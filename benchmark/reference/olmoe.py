"""OLMoE (allenai/OLMoE-1B-7B architecture) in plain float32 ``jax.numpy``:
no kernels, no cache, no batching, matmul precision "highest".

Follows the published description (HF ``modeling_olmoe.py``): RMSNorm
(float32) -> attention whose q and k are RMS-normalised over their WHOLE
projection, before the split into heads and before the rotary embedding
(rotate-half form over the whole head), no biases, no clipping (``clip_qkv``
null) -> residual -> RMSNorm -> router -> softmax in float32 over ALL
experts -> the ``num_experts_per_tok`` largest, NOT renormalised
(``norm_topk_prob`` false; renormalised if the configuration says true) ->
``sum_e p_e * W_down,e (silu(W_gate,e m) * W_up,e m)`` -> residual; final
RMSNorm; untied ``lm_head``. No shared expert, no capacity, no dropped token.

The experts are a loop over all of them: every position is multiplied by
every expert and the result weighted by ``p_e`` where the expert was chosen
and by 0 where not.

Departures: none in the mathematics. Weights arrive as a dict of float32
arrays (the served weights, dequantised), not a checkpoint.

    weights["emb"] [V, H]; weights["layers"][i] = {ln1 [H], wq/wk/wv [H, nh*hd],
    wo [nh*hd, H], q_norm/k_norm [nh*hd], ln2 [H], router [H, E],
    gate/up [E, H, I], down [E, I, H]}; weights["norm"] [H]; ["head"] [H, V]

``forward_routed`` also returns every layer's router probabilities and can be
told which experts to use (``routes``: per layer ``[T, k]`` indices): it then
weights those experts with its OWN float32 probabilities for them. That is
how the program's routing is checked (families/olmoe.py): rounding moves a
router logit by more than the 8th and 9th largest are apart at a few
positions, and one different expert moves a position's logits by far more
than any rounding does.
"""

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)          # [T, hd]
    return x * jnp.cos(ang)[:, None, :] + _rotate_half(x) * jnp.sin(ang)[:, None, :]


def _experts(m, lw, probs, chosen, renormalise):
    """m [T, H], probs [T, E], chosen [T, k] -> [T, H]."""
    E = probs.shape[-1]
    picked = (chosen[..., None] == jnp.arange(E)).any(-2)        # [T, E]
    p = jnp.where(picked, probs, 0.0)
    if renormalise:
        p = p / p.sum(-1, keepdims=True)
    y = jnp.zeros_like(m)
    for e in range(E):
        a = jax.nn.silu(m @ lw["gate"][e]) * (m @ lw["up"][e])
        y = y + p[:, e:e + 1] * (a @ lw["down"][e])
    return y


def forward_routed(weights, tokens, cfg, routes=None):
    """tokens [T] int -> (logits [T, V] float32, [probs [T, E]] per layer).
    Full causal forward. ``routes`` None: each layer uses its own top-k."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    k = cfg["num_experts_per_tok"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = cfg.get("rope_theta", 10000.0)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    all_probs = []
    with jax.default_matmul_precision("highest"):
        x = weights["emb"][tokens]
        for i, lw in enumerate(weights["layers"]):
            n = _rms(x, lw["ln1"], eps)
            q = _rms(n @ lw["wq"], lw["q_norm"], eps).reshape(T, nh, hd)
            kk = _rms(n @ lw["wk"], lw["k_norm"], eps).reshape(T, nkv, hd)
            v = (n @ lw["wv"]).reshape(T, nkv, hd)
            q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
            kk = jnp.repeat(kk, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            s = jnp.einsum("qnd,knd->nqk", q, kk) / jnp.sqrt(jnp.float32(hd))
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
            h = x + a.reshape(T, nh * hd) @ lw["wo"]
            m = _rms(h, lw["ln2"], eps)
            probs = jax.nn.softmax(m @ lw["router"], axis=-1)
            chosen = (jax.lax.top_k(probs, k)[1] if routes is None
                      else jnp.asarray(routes[i]))
            x = h + _experts(m, lw, probs, chosen,
                             cfg.get("norm_topk_prob", False))
            all_probs.append(probs)
        return _rms(x, weights["norm"], eps) @ weights["head"], all_probs


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg)[0]
