"""LongCat-Flash (meituan-longcat/LongCat-Flash-Omni's language model,
``model_type`` ``longcat_flash``) in plain float32 ``jax.numpy``: no kernels,
no cache, no batching, matmul precision "highest", the EXPANDED form of its
latent attention. x is the residual stream, p a token's absolute position.

One layer (``N`` an RMSNorm of its own each time it appears):

    h1 = h  + A0(N(h));   x = N(h1);   m = M(x)
    h2 = h1 + F0(x)
    h3 = h2 + A1(N(h2))
    h' = h3 + F1(N(h3)) + m

``A``: c_q = RMSNorm(n W_qa); q = c_q W_qb in heads of [q_nope | q_rope];
``mla_scale_q_lora``: q = q * sqrt(hidden_size / q_lora_rank), both parts;
[c | k_r] = n W_kva; c_kv = RMSNorm(c); ``mla_scale_kv_lora``: c_kv = c_kv *
sqrt(hidden_size / kv_lora_rank); k_rope = RoPE(k_r, p), ONE for all heads;
[k_nope | v]_h = c_kv W_kvb (a head's columns: qk_nope then v); q_rope =
RoPE(q_rope, p); score = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 *
(q_nope . k_nope + q_rope . k_rope), causal, softmax in float32; out =
softmax . v, then W_o. ``RoPE`` rotates ADJACENT pairs (x_2i, x_2i+1) by
p * rope_theta^(-2i/d). Every position's keys and values are built and
nothing is cached.

``F``: W_down(silu(W_gate x) * W_up x) of ``ffn_hidden_size``.

``M(x)``: s = softmax(x W_r) in float32 over ``n_routed_experts +
zero_expert_num`` outputs; chosen = the ``moe_topk`` largest of s + b (b the
checkpoint's ``e_score_correction_bias``); w_j = routed_scaling_factor *
s[j] for the chosen, NOT renormalised; M(x) = sum over the chosen j of w_j
E_j(x), with E_j a SwiGLU of ``expert_ffn_hidden_size`` for j <
n_routed_experts and E_j(x) = x (``zero_expert_type`` identity) for the
others. With ``held = (first, count)`` only the held SwiGLU experts' part is
added (one chip's share of an expert-parallel layer: what the absent experts
would add is left out, here as in the program); the zero experts are held
nowhere and cost nothing, so every chip adds their part for its own tokens
and they are always in.

Head: RMSNorm, then the rows of the head that the weights hold.

Assumed, for ``config.json`` has no key that says so: SiLU; the pre-norm
block; no bias; adjacent rotary pairs; no renormalising of the chosen
weights (no ``norm_topk_prob``); the two ``mla_scale_*`` factors are
``sqrt(hidden_size / rank)`` and sit where the lines above put them. The
audio and vision encoders and the codec decoder are no part of this forward.

Departures, none in the mathematics:
* weights arrive as float32 arrays (the served weights, dequantised, in the
  PUBLISHED form: the program's load-time permutation of the rope columns
  and its folding of ``mla_scale_kv_lora`` into the latent norm are undone
  by whoever hands them over), ``W_kvb`` as its key and value halves a head
  apart, and ``weights["layers"]`` may be any iterable (consumed one layer
  at a time);
* an expert is run on the ROWS that chose it, padded to a multiple of 16
  with rows of weight exactly zero (a row that did not choose it has weight
  zero, so leaving it out changes no number; the padding keeps the number
  of shapes small, each of which is a program to compile);
* ``matmul_dtype`` (None: float32) rounds every matmul's INPUTS to that
  type first, the accumulation staying float32: how the nearest precision
  below the served one is read. ``without`` names terms left out ON PURPOSE,
  to show that the comparison sees them (families/longcat_flash.py):
  "zero" (the zero experts' term), "routed" (the SwiGLU experts' term),
  "q_scale", "kv_scale".

    weights["emb"] [V, H]; each layer {"attn": two of {ln [H], wq_a [H, qr],
    q_norm [qr], wq_b [qr, nh*(dn+dr)], wkv_a [H, rank+dr], kv_norm [rank],
    wk_b [nh, rank, dn], wv_b [nh, rank, dv], wo [nh*dv, H]}, "ffn": two of
    {ln [H], gate/up [H, I], down [I, H]}, router [H, E+Z], bias [E+Z],
    gate/up [count, H, Ie], down [count, Ie, H]}; weights["norm"] [H];
    ["head"] [H, V]

``forward_routed`` also returns every layer's biased scores and can be told
which indices to use (``routes``), as reference/exaone_moe.py's.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

PAD_ROWS = 16


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [T, heads, d] rotated in ADJACENT pairs by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _matmul(matmul_dtype):
    def rnd(a):
        a = jnp.asarray(a, jnp.float32)
        return a if matmul_dtype is None else a.astype(matmul_dtype).astype(
            jnp.float32)

    return lambda a, b: jnp.matmul(rnd(a), rnd(b))


def _swiglu(mm, m, gate, up, down):
    return mm(jax.nn.silu(mm(m, gate)) * mm(m, up), down)


def _attention(mm, n, aw, cfg, pos, causal, without):
    nh = cfg["num_attention_heads"]
    rank, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, H = cfg.get("rms_norm_eps", 1e-5), cfg["hidden_size"]
    T = n.shape[0]
    q = mm(_rms(mm(n, aw["wq_a"]), aw["q_norm"], eps), aw["wq_b"])
    q = q.reshape(T, nh, dn + dr)
    if cfg.get("mla_scale_q_lora", True) and "q_scale" not in without:
        q = q * math.sqrt(H / cfg["q_lora_rank"])
    ckr = mm(n, aw["wkv_a"])
    c_kv = _rms(ckr[:, :rank], aw["kv_norm"], eps)
    if cfg.get("mla_scale_kv_lora", True) and "kv_scale" not in without:
        c_kv = c_kv * math.sqrt(H / rank)
    theta = float(cfg["rope_theta"])
    k_rope = _rope(ckr[:, None, rank:], pos, theta)[:, 0]       # [T, dr]
    q_rope = _rope(q[..., dn:], pos, theta)
    # the expanded keys and values of every position: [nh, T, dn], [nh, T, dv]
    k_nope = mm(c_kv[None], aw["wk_b"])
    v = mm(c_kv[None], aw["wv_b"])
    s = (mm(q[..., :dn].transpose(1, 0, 2), k_nope.transpose(0, 2, 1))
         + mm(q_rope.transpose(1, 0, 2), k_rope.T[None]))       # [nh, T, T]
    s = jnp.where(causal[None], s * (dn + dr) ** -0.5, -jnp.inf)
    a = mm(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2)    # [T, nh, dv]
    return mm(a.reshape(T, nh * dv), aw["wo"])


def _routed(mm, m, lw, cfg, chosen, held, without=()):
    """m [T, H] -> (M(m) [T, H], biased scores [T, E + Z])."""
    E = cfg["n_routed_experts"]
    s = jax.nn.softmax(mm(m, lw["router"]), axis=-1)            # [T, E+Z]
    biased = s + lw["bias"]
    if chosen is None:
        chosen = jax.lax.top_k(biased, cfg["moe_topk"])[1]
    chosen = jnp.asarray(chosen)
    w = cfg["routed_scaling_factor"] * jnp.take_along_axis(s, chosen, -1)
    y = jnp.zeros_like(m)
    if "zero" not in without:       # E_j(x) = x for j >= E
        y = y + m * jnp.sum(jnp.where(chosen >= E, w, 0.0), -1,
                            keepdims=True)
    if "routed" in without:
        return y, biased
    first, count = held
    chosen_np, w_np = np.asarray(chosen), np.asarray(w)
    for e in range(count):          # a loop over the chosen, by expert
        rows, slot = np.nonzero(chosen_np == first + e)
        if not rows.size:
            continue
        pad = (-rows.size) % PAD_ROWS
        at = jnp.asarray(np.concatenate([rows, np.zeros(pad, rows.dtype)]))
        we = jnp.asarray(np.concatenate(
            [w_np[rows, slot], np.zeros(pad, w_np.dtype)]))
        y = y.at[at].add(we[:, None] * _swiglu(
            mm, m[at], lw["gate"][e], lw["up"][e], lw["down"][e]))
    return y, biased


def forward_routed(weights, tokens, cfg, routes=None, held=None,
                   matmul_dtype=None, without=()):
    """tokens [T] int -> (logits [T, V] float32, [biased scores [T, E + Z]]
    per layer). Full causal forward, no cache. ``routes`` None: each layer
    uses its own top-k. ``held`` None: every SwiGLU expert."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    if held is None:
        held = (0, cfg["n_routed_experts"])
    tokens = jnp.asarray(tokens)
    pos = jnp.arange(tokens.shape[0])
    causal = pos[:, None] >= pos[None, :]
    mm = _matmul(matmul_dtype)
    all_scores = []
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(weights["emb"], jnp.float32)[tokens]
        for lw in weights["layers"]:
            routed = None
            for s in (0, 1):
                aw, fw = lw["attn"][s], lw["ffn"][s]
                h = h + _attention(mm, _rms(h, aw["ln"], eps), aw, cfg, pos,
                                   causal, without)
                x = _rms(h, fw["ln"], eps)
                if s == 0:          # the shortcut leaves here
                    chosen = (None if routes is None
                              else routes[len(all_scores)])
                    routed, biased = _routed(mm, x, lw, cfg, chosen, held,
                                             without)
                    all_scores.append(biased)
                h = h + _swiglu(mm, x, fw["gate"], fw["up"], fw["down"])
            h = h + routed          # and rejoins here
        return mm(_rms(h, weights["norm"], eps), weights["head"]), all_scores


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg,
                          held=cfg.get("held_experts"))[0]
