"""Mistral-4 (mistralai/Mistral-Small-4-119B-2603, ``model_type``
``mistral4``) in plain float32 ``numpy``: no kernels, no cache, no batching,
the EXPANDED form of its latent attention. x is the residual stream, p a
token's absolute position. (``numpy`` and not ``jax.numpy`` as the other
families' references: on the chip's host every eager ``jax.numpy`` operation
is a program to compile, a second each, and XLA's CPU matmul runs on one of
its thirteen cores; the same lines in ``jax.numpy`` took this check 210-535 s
of a cold run, PERF.md section 6, PR 35. Nothing here is jax.)

    x = x + Attn(RMSNorm(x));  x = x + Routed(m) + Shared(m),  m = RMSNorm(x)

``Attn``: c_q = RMSNorm(n W_qa); q = c_q W_qb in heads of [q_nope | q_rope];
[c | k_r] = n W_kva; c_kv = RMSNorm(c); k_rope = RoPE(k_r, p), ONE for all
heads; [k_nope | v]_h = c_kv W_kvb (a head's columns: qk_nope then v);
q_rope = RoPE(q_rope, p); score = s(p) * scale * (q_nope . k_nope + q_rope .
k_rope), causal over all positions, softmax in float32; out = softmax . v,
then W_o. What a serving cache holds a position is c_kv and k_rope; here
every position's keys and values are built and nothing is cached.

``RoPE`` rotates ADJACENT pairs (x_2i, x_2i+1) (``rope_interleave``), by
p * f_i with YaRN's f_i over the rope dims: f_i = (1 - r_i) theta^(-2i/d) +
r_i theta^(-2i/d) / factor, r_i = clip((i - low) / (high - low), 0, 1), low =
floor(c(beta_fast)), high = ceil(c(beta_slow)), c(b) = d ln(L0 / (2 pi b)) /
(2 ln theta), L0 = ``original_max_position_embeddings``; cos and sin times
m(mscale) / m(mscale_all_dim), m(t) = 0.1 t ln(factor) + 1. scale =
qk_head_dim^-0.5 * m(mscale_all_dim)^2. s(p) = 1 + llama_4_scaling_beta *
ln(1 + floor(p / L0)), by the QUERY's position.

``Routed``, ``Shared``: as reference/exaone_moe.py's sparse layer (sigmoid
scores, the ``num_experts_per_tok`` largest of score + bias, their own
scores normalised over ALL the chosen, times ``routed_scaling_factor``,
SwiGLU experts; with ``held = (first, count)`` only the held experts' part
is added: one chip's share of an expert-parallel layer).

Head: RMSNorm, then the rows of the head that the weights hold.

Assumed, for ``config.json`` has no key that says so, from the family whose
keys it uses (DeepseekV3Config: ``n_group``, ``topk_group``,
``norm_topk_prob``, ``routed_scaling_factor``, ``rope_interleave``,
``mscale_all_dim``): sigmoid scores with a selection bias; ``mscale^2`` in
the softmax scale; the pre-norm block; s(p) multiplies the query AFTER its
rotation (as the HF ``ministral3`` code does; a scalar, so before or after is
the same number). ``n_group == topk_group == 1``. The vision tower is no part
of the text forward and is absent.

Departures: none in the mathematics. Weights arrive as float32 arrays (the
served weights, dequantised, in the PUBLISHED column order: the program's
load-time permutation of the rope columns is undone by whoever hands them
over), ``W_kvb`` as its key and value halves a head apart, and
``weights["layers"]`` may be any iterable (consumed one layer at a time).
``matmul_dtype`` (None: float32; else an ``ml_dtypes`` type such as
``jnp.float8_e4m3fn``) rounds every matmul's INPUTS to that type first, the
accumulation staying float32: how the nearest precision below the served one
is read (families/mistral4.py).

    weights["emb"] [V, H]; each layer {ln1 [H], wq_a [H, qr], q_norm [qr],
    wq_b [qr, nh*(dn+dr)], wkv_a [H, rank+dr], kv_norm [rank], wk_b [nh,
    rank, dn], wv_b [nh, rank, dv], wo [nh*dv, H], ln2 [H], router [H, E],
    bias [E], gate/up [count, H, Ie], down [count, Ie, H], s_gate/s_up [H,
    Is], s_down [Is, H]}; weights["norm"] [H]; ["head"] [H, V]

``forward_routed`` also returns every sparse layer's biased scores and can
be told which experts to use (``routes``), as reference/exaone_moe.py's.
"""

import math

import numpy as np

F32 = np.float32


def _rms(x, g, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + F32(eps)) * g


def _silu(x):
    return x / (1 + np.exp(-x))


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _mscale(factor, t):
    return 0.1 * t * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(d, rope):
    """The d/2 rotary frequencies of ``rope_parameters`` (float32)."""
    theta = float(rope["rope_theta"])
    i = np.arange(d // 2, dtype=F32)
    base = (theta ** (-2.0 * i / d)).astype(F32)
    if rope.get("rope_type", "default") != "yarn":
        return base
    L0 = rope["original_max_position_embeddings"]

    def c(b):
        return d * math.log(L0 / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), d - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0).astype(F32)
    return (1 - r) * base + r * base / F32(rope["factor"])


def _rope(x, pos, freqs, factor):
    """x [T, heads, d] rotated in ADJACENT pairs by pos * freqs."""
    ang = pos[:, None].astype(F32) * freqs[None, :]             # [T, d/2]
    cos = (np.cos(ang) * F32(factor))[:, None, :]
    sin = (np.sin(ang) * F32(factor))[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return np.stack([a * cos - b * sin, b * cos + a * sin],
                    axis=-1).reshape(x.shape)


def _swiglu(mm, m, gate, up, down):
    return mm(_silu(mm(m, gate)) * mm(m, up), down)


def _matmul(matmul_dtype):
    """a @ b in float32, the inputs first rounded to ``matmul_dtype``."""
    def rnd(a):
        a = np.asarray(a, F32)
        return a if matmul_dtype is None else a.astype(matmul_dtype).astype(
            F32)

    return (lambda a, b: np.matmul(rnd(a), rnd(b))), rnd


def _sparse(mm, m, lw, cfg, chosen, held):
    """m [T, H] -> (Routed(m) + Shared(m) [T, H], biased scores [T, E])."""
    m = np.asarray(m, F32)
    s = 1 / (1 + np.exp(-mm(m, lw["router"])))                  # [T, E]
    biased = s + np.asarray(lw["bias"], F32)
    k = cfg["num_experts_per_tok"]
    if chosen is None:          # the k largest, ties to the lower index
        chosen = np.argsort(-biased, axis=-1, kind="stable")[:, :k]
    E = s.shape[-1]
    picked = (np.asarray(chosen)[..., None] == np.arange(E)).any(-2)
    g = np.where(picked, s, F32(0))
    g = F32(cfg["routed_scaling_factor"]) * g / (g.sum(-1, keepdims=True)
                                                  + F32(1e-20))
    first, count = held
    y = _swiglu(mm, m, lw["s_gate"], lw["s_up"], lw["s_down"])
    for e in range(count):
        # an expert's rows are the tokens that chose it: every other row's
        # weight is exactly zero, so leaving them out changes no number
        rows = np.nonzero(picked[:, first + e])[0]
        if rows.size:
            y[rows] += g[rows, first + e, None] * _swiglu(
                mm, m[rows], lw["gate"][e], lw["up"][e], lw["down"][e])
    return y, biased


def forward_routed(weights, tokens, cfg, routes=None, held=None,
                   matmul_dtype=None, last=None):
    """tokens [T] int -> (logits [T, V] float32, [biased scores [T, E]] per
    sparse layer). Full causal forward, no cache. ``routes`` None: each
    sparse layer uses its own top-k. ``held`` None: every expert.
    ``last`` (the BLOCKED form, for a long sequence through ONE layer, which
    is a whole period): only the last ``last`` positions' queries, experts
    and logits are computed, [last, ..] each; the positions before them
    need only their keys and values, which one layer makes from the
    embeddings alone."""
    nh = cfg["num_attention_heads"]
    rank, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg.get("rms_norm_eps", 1e-6)
    rope = cfg["rope_parameters"]
    yarn = rope.get("rope_type", "default") == "yarn"
    factor = rope["factor"] if yarn else 1.0
    m_all = _mscale(factor, rope.get("mscale_all_dim", 0.0))
    rope_factor = _mscale(factor, rope.get("mscale", 1.0)) / m_all
    scale = (dn + dr) ** -0.5 * m_all ** 2
    freqs = inv_freq(dr, rope)
    if held is None:
        held = (0, cfg["n_routed_experts"])
    tokens = np.asarray(tokens)
    T = tokens.shape[0]
    pos = np.arange(T)
    tail = slice(None) if last is None else slice(T - last, T)
    causal = pos[tail, None] >= pos[None, :]
    layers = weights["layers"]
    if last is not None:
        layers = list(layers)
        assert len(layers) == 1, "the blocked form is one layer's"
    beta = rope.get("llama_4_scaling_beta", 0.0)
    s_p = (1.0 + beta * np.log1p(np.floor(
        pos / rope.get("original_max_position_embeddings", 1)))).astype(F32)
    mm, rnd = _matmul(matmul_dtype)
    all_scores = []
    x = np.asarray(weights["emb"], F32)[tokens]
    for lw in layers:
        n = _rms(x, lw["ln1"], eps)
        q = mm(_rms(mm(n[tail], lw["wq_a"]), lw["q_norm"], eps), lw["wq_b"])
        q = q.reshape(q.shape[0], nh, dn + dr)
        ckr = mm(n, lw["wkv_a"])
        c_kv = _rms(ckr[:, :rank], lw["kv_norm"], eps)
        k_rope = _rope(ckr[:, None, rank:], pos, freqs, rope_factor)[:, 0]
        q_rope = _rope(q[..., dn:], pos[tail], freqs, rope_factor)
        # the expanded keys and values of every position, head by head:
        # [nh, T, dn] and [nh, T, dv]
        k_nope = np.stack([mm(c_kv, lw["wk_b"][h]) for h in range(nh)])
        v = np.stack([mm(c_kv, lw["wv_b"][h]) for h in range(nh)])
        qn = q[..., :dn].transpose(1, 0, 2)                     # [nh, Tq, dn]
        qr = q_rope.transpose(1, 0, 2)
        s = (mm(qn, k_nope.transpose(0, 2, 1))
             + mm(qr, k_rope.T[None]))                          # [nh, Tq, T]
        s = s * (F32(scale) * s_p[tail])[None, :, None]
        p = _softmax(np.where(causal[None], s, -np.inf))
        a = mm(p, v).transpose(1, 0, 2)                         # [Tq, nh, dv]
        h = x[tail] + mm(a.reshape(-1, nh * dv), lw["wo"])
        m = _rms(h, lw["ln2"], eps)
        chosen = None if routes is None else routes[len(all_scores)]
        y, biased = _sparse(mm, m, lw, cfg, chosen, held)
        x = h + y
        all_scores.append(biased)
    return mm(_rms(x, weights["norm"], eps), weights["head"]), all_scores


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg,
                          held=cfg.get("held_experts"))[0]
