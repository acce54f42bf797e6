"""EXAONE-MoE (LGAI-EXAONE/K-EXAONE-236B-A23B, ``model_type`` ``exaone_moe``)
in plain float32 ``jax.numpy``: no kernels, no cache, no batching, matmul
precision "highest". x is the residual stream.

    x = x + Attn_l(RMSNorm(x));  x = x + F_l(RMSNorm(x))          (pre-norm)

``Attn_l``: q = x Wq, k = x Wk, v = x Wv in 64 / 8 / 8 heads of 128; RMSNorm
over EACH head's 128 with a learned [128] weight on q and on k, before any
rotation. ``layer_types[l] == "sliding_attention"``: rotary embedding
(rotate-half, theta from ``rope_parameters``), and the query at position i
sees keys j with ``i - sliding_window < j <= i``. ``"full_attention"``: causal
over all j <= i and NO rotary embedding. Scale 1/sqrt(128), softmax in
float32, 8 query heads a key head, then Wo.

``F_l``, ``mlp_layer_types[l] == "dense"``: Wdown(silu(Wgate x) * Wup x).
``"sparse"``: s = sigmoid(x Wr) over all experts; chosen = the
``num_experts_per_tok`` largest of s + b (b the checkpoint's per-expert
selection bias); g = s[chosen], g = routed_scaling_factor * g / (sum g +
1e-20) over ALL the chosen, held here or not; F_l(x) = sum over the chosen
experts e that are HELD of g_e Expert_e(x), plus Shared(x); experts and the
shared expert are SwiGLU. With every expert held that is the whole layer;
with ``held = (first, count)`` it is one chip's share of an expert-parallel
layer (what the absent experts would add is left out, here as in the
program, and the partial result goes on to the next layer).

Head: RMSNorm, then the rows of the head that the weights hold.

Assumed, for ``config.json`` has no key that says so: the pre-norm block
(the DeepSeek-V3-style block whose parameter names ``exaone_moe`` uses;
EXAONE 4.0 normed each sublayer's OUTPUT instead), no rotary embedding on
full layers (the family's model card: "global attention: NoPE"), and the
selection bias (DeepSeek-V3's ``e_score_correction_bias``, from
``scoring_func`` sigmoid, ``n_group``, ``topk_group``,
``routed_scaling_factor``). ``n_group == topk_group == 1``: no group limit,
as the family asserts. The multi-token-prediction layer is no part of the
next-token forward and is absent.

Departures: none in the mathematics. Weights arrive as float32 arrays (the
served weights, dequantised), and ``weights["layers"]`` may be any iterable:
it is consumed one layer at a time, so a generator keeps one layer's float32
copy alive at once (a sparse layer of 16 held experts is 3 GB).

    weights["emb"] [V, H]; each layer {ln1 [H], wq [H, nh*hd], wk/wv
    [H, nkv*hd], wo [nh*hd, H], q_norm/k_norm [hd], ln2 [H]} and, dense:
    {gate/up [H, I], down [I, H]}; sparse: {router [H, E], bias [E],
    gate/up [count, H, Ie], down [count, Ie, H], s_gate/s_up [H, Is],
    s_down [Is, H]}; weights["norm"] [H]; ["head"] [H, V]

``forward_routed`` also returns every sparse layer's biased scores s + b
(what the choice is made on) and can be told which experts to use
(``routes``: per sparse layer ``[T, k]`` indices over all experts): it then
weights those with its OWN float32 scores. That is how the program's routing
is checked (families/exaone_moe.py, as families/olmoe.py).
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
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return x * cos + _rotate_half(x) * sin


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _sparse(m, lw, cfg, chosen, held):
    """m [T, H] -> (F(m) [T, H], biased scores [T, E])."""
    s = jax.nn.sigmoid(m @ lw["router"])                        # [T, E]
    biased = s + lw["bias"]
    if chosen is None:
        chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])[1]
    E = s.shape[-1]
    picked = (chosen[..., None] == jnp.arange(E)).any(-2)       # [T, E]
    g = jnp.where(picked, s, 0.0)
    g = cfg["routed_scaling_factor"] * g / (g.sum(-1, keepdims=True) + 1e-20)
    first, count = held
    y = _swiglu(m, lw["s_gate"], lw["s_up"], lw["s_down"])
    for e in range(count):
        y = y + g[:, first + e:first + e + 1] * _swiglu(
            m, lw["gate"][e], lw["up"][e], lw["down"][e])
    return y, biased


def forward_routed(weights, tokens, cfg, routes=None, held=None):
    """tokens [T] int -> (logits [T, V] float32, [biased scores [T, E]] per
    sparse layer). Full causal forward, no cache. ``routes`` None: each
    sparse layer uses its own top-k. ``held`` None: every expert."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    theta = cfg["rope_parameters"]["rope_theta"]
    window = cfg["sliding_window"]
    if held is None:
        held = (0, cfg["num_experts"])
    T = tokens.shape[0]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    near = pos[:, None] - pos[None, :] < window
    all_scores = []
    with jax.default_matmul_precision("highest"):
        x = weights["emb"][tokens]
        for i, lw in enumerate(weights["layers"]):
            sliding = cfg["layer_types"][i] == "sliding_attention"
            n = _rms(x, lw["ln1"], eps)
            q = _rms((n @ lw["wq"]).reshape(T, nh, hd), lw["q_norm"], eps)
            kk = _rms((n @ lw["wk"]).reshape(T, nkv, hd), lw["k_norm"], eps)
            v = (n @ lw["wv"]).reshape(T, nkv, hd)
            if sliding:
                q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
            kk = jnp.repeat(kk, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            s = jnp.einsum("qnd,knd->nqk", q, kk) / jnp.sqrt(jnp.float32(hd))
            seen = causal & near if sliding else causal
            s = jnp.where(seen[None], s, -jnp.inf)
            a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
            h = x + a.reshape(T, nh * hd) @ lw["wo"]
            m = _rms(h, lw["ln2"], eps)
            if cfg["mlp_layer_types"][i] == "dense":
                x = h + _swiglu(m, lw["gate"], lw["up"], lw["down"])
                continue
            chosen = (None if routes is None
                      else jnp.asarray(routes[len(all_scores)]))
            y, biased = _sparse(m, lw, cfg, chosen, held)
            x = h + y
            all_scores.append(biased)
        return _rms(x, weights["norm"], eps) @ weights["head"], all_scores


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg,
                          held=cfg.get("held_experts"))[0]
