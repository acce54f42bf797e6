"""ZAYA1 (Zyphra/ZAYA1-8B, ``model_type`` ``zaya``) in plain float32 numpy:
no kernels, no cache, no state, no batching, no jax (numpy's float32 matmul
is a true one, and its BLAS uses all the host's cores where an eager
``jax.numpy`` forward of 469 positions over 262272 logits ran 106 s on one:
PERF.md section 6, PR 50; reference/mistral4.py has the precedent). The
whole sequence at once: what the program carries from step to
step as a row's tail is here a shift along the sequence. x is the residual
stream, t a token's position, ``x_{-1} = x_{-2} = 0`` at the start.

With E the hidden size, H query and G key/value heads of D, ``g(h) = h //
(H / G)``, R the router's width, and ``N`` an RMSNorm of its own each time:

    h1 = (a1 * h  + b1) + (c1 * CCA(N(h))         + d1)
    h' = (a2 * h1 + b2) + (c2 * MoE(N(h1), r_prev) + d2)     # also yields r

``CCA(x)``:

    qt = Wq x_t [H D]      kt = Wk x_t [G D]      u_t = [qt ; kt]
    v_t = [ Wv1 x_t ; Wv2 x_{t-1} ]              # head 0 this token's, head 1 the token's before
    c0_t = w0[0] * u_{t-1} + w0[1] * u_t + b0    # depthwise, kernel 2
    c1_t[j] = B[j,0] c0_{t-1}[j] + B[j,1] c0_t[j] + b1[j]     # grouped, kernel 2, j over the H + G heads
    q_t[h] = c1_t[h]     + (qt[h] + kt[g(h)]) / 2
    k_t[g] = c1_t[H + g] + (mean_{h in g} qt[h] + kt[g]) / 2
    q_t[h] <- sqrt(D) q_t[h] / |q_t[h]|     k_t[g] <- tau_g sqrt(D) k_t[g] / |k_t[g]|
    rotate the FIRST ``partial_rotary_factor`` of each head's dims of q_t, k_t at t (rotate-half)
    o_t[h] = sum_{s <= t} softmax_s(q_t[h] . k_s[g(h)] / sqrt(D)) v_s[g(h)]
    CCA(x)_t = Wo [o_t[0] ; .. ; o_t[H-1]]

(``c0_{-1}`` is the same equation a position earlier, on zeros: ``b0``.)

``MoE(x, r_prev)``:

    r = Wd x + gamma * r_prev                    # layer 0 has no r_prev
    z = W3 gelu(W2 gelu(W1 N_r(r)))  [num_experts + 1]      # exact (erf) GELU
    p = softmax(z);  e = argmax(p + bias)
    MoE = p_e * Wdown_e(silu(Wgate_e x) * (Wup_e x))  if e < num_experts,  0 otherwise

Head: ``logits = emb^T N(h)``, the embedding's own table.

Everything ``config.json`` has no key for is as ISSUE 50 states it
(benchmark/configs/zaya1-8b.json ``assumed``); not checked against the
published code.

Departures, none in the mathematics:
* weights arrive as float32 arrays (the served weights, dequantised), and
  ``weights["layers"]`` may be any iterable (consumed one layer at a time);
* an expert is run on the ROWS that chose it (a row that did not choose it
  has weight zero, so leaving it out changes no number);
* ``matmul_dtype`` (None: float32; else an ``ml_dtypes`` type such as
  ``jax.numpy.bfloat16`` or ``float8_e4m3fn``) rounds every matmul's INPUTS
  to that type first, the accumulation staying float32: how the nearest precision below
  the served one is read. ``without`` names terms left out ON PURPOSE, to
  show that the comparison sees them (families/zaya.py): "conv_tap" (``B[j,0]
  c0_{t-1}``), "value_shift" (the second value half unshifted: ``Wv2 x_t``),
  "eda" (``gamma * r_prev``), "routed" (the experts' term).

    weights["emb"] [V, E]; ["norm"] [E]; each layer {ln1, ln2 [E], wq [E, H D],
    wk [E, G D], wv1, wv2 [E, G D / 2], wo [H D, E], conv0_w [2, C], conv0_b
    [C], conv1_w [H + G, 2, D, D], conv1_b [C], tau [G], res_attn, res_mlp
    [4, E] (a, b, c, d), wd [E, R], gamma (a number; absent in layer 0), rn
    [R], w1, w2 [R, R], w3 [R, num_experts + 1], bias [num_experts + 1],
    gate, up [num_experts, E, I], down [num_experts, I, E]}

``forward_routed`` also returns every layer's biased scores and can be told
which indices to use (``routes``), as reference/longcat_flash.py's.
"""

import math

import numpy as np

F32 = np.float32
_erf = np.vectorize(math.erf, otypes=[F32])


def _rms(x, g, eps):
    return (x / np.sqrt((x * x).mean(-1, keepdims=True) + F32(eps))
            * np.asarray(g, F32))


def _matmul(matmul_dtype):
    def rnd(a):
        a = np.asarray(a, F32)
        return a if matmul_dtype is None else a.astype(matmul_dtype).astype(
            F32)

    return lambda a, b: np.matmul(rnd(a), rnd(b))


def _gelu(x):
    """Exact (erf) GELU."""
    return (0.5 * x * (1.0 + _erf(x / math.sqrt(2.0)))).astype(F32)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _before(a):
    """``a_{t-1}`` at every t: zeros before the start."""
    return np.concatenate([np.zeros_like(a[:1]), a[:-1]], axis=0)


def _rope_first(x, pos, rot, theta):
    """x [T, heads, D]: the first ``rot`` dims of each head rotated
    (rotate-half inside them) by pos * theta^(-2i/rot); the rest pass."""
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=F32) / rot))
    ang = pos[:, None].astype(F32) * inv[None, :]               # [T, rot/2]
    ang = np.concatenate([ang, ang], axis=-1)[:, None, :]
    a = x[..., :rot]
    half = np.concatenate([-a[..., rot // 2:], a[..., :rot // 2]], axis=-1)
    return np.concatenate(
        [a * np.cos(ang) + half * np.sin(ang), x[..., rot:]],
        axis=-1).astype(F32)


def _cca(mm, x, lw, cfg, pos, causal, without):
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    T = x.shape[0]
    u = np.concatenate([mm(x, lw["wq"]), mm(x, lw["wk"])], axis=-1)
    w0, b0 = np.asarray(lw["conv0_w"], F32), np.asarray(lw["conv0_b"], F32)
    B, b1 = np.asarray(lw["conv1_w"], F32), np.asarray(lw["conv1_b"], F32)

    def c0_of(u_t, u_before):
        return (w0[0] * u_before + w0[1] * u_t + b0).reshape(-1, H + G, D)

    c0 = c0_of(u, _before(u))
    # c0_{t-1}: the same equation a position earlier (at t = 0: on zeros)
    c0_before = c0_of(_before(u), _before(_before(u)))
    c1 = mm(c0.transpose(1, 0, 2), B[:, 1]) + b1.reshape(H + G, 1, D)
    if "conv_tap" not in without:
        c1 = c1 + mm(c0_before.transpose(1, 0, 2), B[:, 0])
    c1 = c1.transpose(1, 0, 2)                                  # [T, H+G, D]
    ut = u.reshape(T, H + G, D)
    qt, kt = ut[:, :H], ut[:, H:]
    grouped = qt.reshape(T, G, H // G, D)
    q = c1[:, :H] + 0.5 * (grouped + kt[:, :, None]).reshape(T, H, D)
    k = c1[:, H:] + 0.5 * (grouped.mean(axis=2) + kt)

    def unit(a):
        return a * (math.sqrt(D) / np.linalg.norm(a, axis=-1, keepdims=True))

    rot = int(D * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    q = _rope_first(unit(q), pos, rot, theta)
    k = _rope_first(unit(k) * np.asarray(lw["tau"], F32)[:, None], pos, rot,
                    theta)
    v2 = mm(x, lw["wv2"])
    v = np.concatenate(
        [mm(x, lw["wv1"]), v2 if "value_shift" in without else _before(v2)],
        axis=-1).reshape(T, G, D)
    qg = q.reshape(T, G, H // G, D).transpose(1, 2, 0, 3)      # [G, H/G, T, D]
    s = mm(qg, k.transpose(1, 2, 0)[:, None]) / math.sqrt(D)   # [G, H/G, T, T]
    s = np.where(causal[None, None], s, -np.inf)
    o = mm(_softmax(s), v.transpose(1, 0, 2)[:, None])
    return mm(o.transpose(2, 0, 1, 3).reshape(T, H * D), lw["wo"])


def _routed(mm, m, r_prev, lw, cfg, chosen, without=()):
    """m [T, E] -> (MoE(m) [T, E], r [T, R], biased scores [T, n + 1])."""
    n = cfg["num_experts"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    r = mm(m, lw["wd"])
    if r_prev is not None and "eda" not in without:
        r = r + F32(lw["gamma"]) * r_prev
    z = _gelu(mm(_rms(r, lw["rn"], eps), lw["w1"]))
    z = mm(_gelu(mm(z, lw["w2"])), lw["w3"])
    p = _softmax(z)
    biased = p + np.asarray(lw["bias"], F32)
    if chosen is None:
        chosen = np.argmax(biased, axis=-1)[:, None]
    chosen = np.asarray(chosen)
    w = np.take_along_axis(p, chosen, -1)[:, 0]
    y = np.zeros_like(m)
    if "routed" in without:
        return y, r, biased
    for e in range(n):              # the last output names no expert
        rows, = np.nonzero(chosen[:, 0] == e)
        if rows.size:
            x = m[rows]
            y[rows] += w[rows, None] * mm(
                _silu(mm(x, lw["gate"][e])) * mm(x, lw["up"][e]),
                lw["down"][e])
    return y, r, biased


def _rescaled(h, out, v):
    a, b, c, d = np.asarray(v, F32)
    return (a * h + b) + (c * out + d)


def forward_routed(weights, tokens, cfg, routes=None, matmul_dtype=None,
                   without=()):
    """tokens [T] int -> (logits [T, V] float32, [biased scores [T,
    num_experts + 1]] per layer). Full causal forward over the whole
    sequence. ``routes`` None: each layer uses its own pick."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    tokens = np.asarray(tokens)
    pos = np.arange(tokens.shape[0])
    causal = pos[:, None] >= pos[None, :]
    mm = _matmul(matmul_dtype)
    all_scores, r = [], None
    emb = np.asarray(weights["emb"], F32)
    h = emb[tokens]
    for lw in weights["layers"]:
        h = _rescaled(h, _cca(mm, _rms(h, lw["ln1"], eps), lw, cfg, pos,
                              causal, without), lw["res_attn"])
        chosen = None if routes is None else routes[len(all_scores)]
        y, r, biased = _routed(mm, _rms(h, lw["ln2"], eps), r, lw, cfg,
                               chosen, without)
        all_scores.append(biased)
        h = _rescaled(h, y, lw["res_mlp"])
    return mm(_rms(h, weights["norm"], eps), emb.T), all_scores


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg)[0]
