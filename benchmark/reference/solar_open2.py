"""Solar-Open2 (upstage/Solar-Open2-250B, ``model_type`` ``solar_open2``) in
plain float32 ``jax.numpy``: no kernels, no cache, no batching, no chunks,
matmul precision "highest". x is the residual stream, ``N`` an RMSNorm
(``rms_norm_eps``) with its own weight each time.

    h = x + Mix_l(N(x));  x = h + MoE_l(N(h))                     (pre-norm)

``Mix_l`` for ``l`` in ``gqa_layers``, the gated GQA layer: q = n Wq, k = n
Wk, v = n Wv in 64 / 8 / 8 heads of 128, NO position embedding and no q/k
norm, scores q . k / sqrt(128), causal softmax in float32, 8 query heads a
key head; ``y = (sigmoid(n Wg) * Attn) Wo``, the gate as wide as the heads.

``Mix_l`` otherwise, the KDA layer (a gated delta rule with one decay a key
channel; ``H`` heads, ``K = V`` = 128), the literal recurrence, a token at a
time (``lax.scan`` over positions):

    for s in (q, k, v):  u^s_t = n_t W_s;  s_t = silu(sum_{j<4} c^s[j] * u^s_{t-3+j}),  u^s_{<0} = 0
    q_t[h] <- q_t[h] / sqrt(|q_t[h]|^2 + 1e-6) / sqrt(K);  k_t[h] <- k_t[h] / sqrt(|k_t[h]|^2 + 1e-6)
    g_t[h] = -exp(A_log[h]) * softplus((n_t W_fa) W_fb + dt_bias)[h]          [K]
    beta_t[h] = 2 sigmoid(n_t w_b[h])
    S' = exp(g_t)[:, None] * S;  d = beta_t (v_t - S'^T k_t);  S = S' + k_t d^T;  o_t = S^T q_t     S_{-1} = 0
    y_t = (N_V(o_t[h]) * w_n * sigmoid(((n_t W_ga) W_gb)[h])) W_o

``MoE_l``: s = sigmoid(m Wr) over all experts; chosen = the
``num_experts_per_tok`` largest of s + b; g = s[chosen], g =
routed_scaling_factor * g / (sum g + 1e-20) over ALL the chosen, held here
or not; MoE_l(m) = sum over the chosen experts e that are HELD of g_e
Expert_e(m), plus Shared(m); experts and the shared expert are SwiGLU. With
``held = (first, count)`` it is one chip's share of an expert-parallel layer
(what the absent experts would add is left out, here as in the program).

Head: N, then the rows of the head that the weights hold.

Assumed, for ``config.json`` has no key that says so (as ISSUE 54 states it;
not checked against the published code): the pre-norm block; the GQA
layer's gate, its form and width (``use_gqa_gate`` gives the flag only); no
q/k norm there; the router's sigmoid scores, selection bias and
normalisation (the DeepSeek-V3 key names it uses); float32 for ``S`` and the
decay.

Departures: none in the mathematics. Weights arrive as float32 arrays (the
served weights, dequantised), and ``weights["layers"]`` may be any iterable,
consumed a layer at a time. Each layer is one jitted function of its kind
(the chip machine's host compiles an eager operation a shape, a second
each), and the held experts are a ``lax.scan`` over their stack.

    weights["emb"] [V, E]; a layer {ln1, ln2 [E], router [E, n], bias [n],
    gate/up [count, E, I], down [count, I, E], s_gate/s_up [E, Is], s_down
    [Is, E]} and, GQA: {wq [E, nh*hd], wk/wv [E, nkv*hd], wg [E, nh*hd], wo
    [nh*hd, E]}; KDA: {wq/wk/wv [E, H*K], conv [taps, 3*H*K], wfa/wga [E, r],
    wfb/wgb [r, H*K], wb [E, H], A_log [H], dt_bias [H*K], o_norm [V], wo
    [H*V, E]}; weights["norm"] [E]; ["head"] [E, V]

``forward_routed`` also returns every layer's biased scores s + b and can be
told which experts to use (``routes``: per layer ``[T, k]`` indices over all
experts). ``without`` leaves a term out ON PURPOSE, to show that the
comparison has teeth: ``"beta2"`` (the factor 2 on beta), ``"decay"``
(``g = 0``), ``"conv_tap"`` (the tap of the position three before),
``"o_gate"`` (the KDA layer's output gate), ``"gqa_gate"``; ``state_dtype``
rounds ``S`` to that type after every token, ``matmul_dtype`` every matmul's
inputs.
"""

import functools

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _unit(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _mm(a, b, dt):
    if dt is not None:
        a, b = (a.astype(dt).astype(jnp.float32),
                b.astype(dt).astype(jnp.float32))
    return a @ b


def _swiglu(m, gate, up, down, dt):
    return _mm(jax.nn.silu(_mm(m, gate, dt)) * _mm(m, up, dt), down, dt)


def _gqa(n, lw, nh, nkv, hd, without, dt):
    T = n.shape[0]
    q = _mm(n, lw["wq"], dt).reshape(T, nh, hd)
    k = jnp.repeat(_mm(n, lw["wk"], dt).reshape(T, nkv, hd), nh // nkv, 1)
    v = jnp.repeat(_mm(n, lw["wv"], dt).reshape(T, nkv, hd), nh // nkv, 1)
    s = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(T)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
    a = a.reshape(T, nh * hd)
    if "gqa_gate" not in without and "wg" in lw:
        a = a * jax.nn.sigmoid(_mm(n, lw["wg"], dt))
    return _mm(a, lw["wo"], dt)


def _kda(n, lw, H, K, eps, without, dt, state_dtype):
    """n [T, E], the layer's normed input -> (y [T, E], the state the last
    token leaves [H, K, V])."""
    T = n.shape[0]
    taps = lw["conv"].shape[0]
    u = jnp.concatenate([_mm(n, lw[w], dt) for w in ("wq", "wk", "wv")], -1)
    ext = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    first = 1 if "conv_tap" in without else 0
    mixed = jax.nn.silu(sum(lw["conv"][j] * ext[j:j + T]
                            for j in range(first, taps)))
    q, k, v = (mixed[:, i * H * K:(i + 1) * H * K].reshape(T, H, K)
               for i in range(3))
    q, k = _unit(q) / jnp.sqrt(jnp.float32(K)), _unit(k)
    a = _mm(_mm(n, lw["wfa"], dt), lw["wfb"], dt) + lw["dt_bias"]
    g = -jnp.exp(lw["A_log"])[:, None] * jax.nn.softplus(a).reshape(T, H, K)
    if "decay" in without:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm(n, lw["wb"], dt))             # [T, H]
    if "beta2" not in without:
        beta = 2.0 * beta

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * d[:, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(token, jnp.zeros((H, K, K), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, lw["o_norm"], eps)
    if "o_gate" not in without:
        o = o * jax.nn.sigmoid(_mm(_mm(n, lw["wga"], dt), lw["wgb"],
                                   dt)).reshape(T, H, K)
    return _mm(o.reshape(T, H * K), lw["wo"], dt), S


@functools.partial(jax.jit, static_argnames=("H", "K", "eps", "state_dtype"))
def kda_state(lw, n, *, H, K, eps=1e-5, state_dtype=None):
    """The recurrent state ``[H, K, V]`` a KDA layer holds after the tokens
    whose normed inputs are ``n [T, E]``: the literal recurrence on GIVEN
    inputs, which is how the precision of a program's state is read apart
    from the noise of the layers before it (families/solar_open2.py)."""
    with jax.default_matmul_precision("highest"):
        return _kda(n, lw, H, K, eps, (), None, state_dtype)[1]


def _moe(m, lw, chosen, top_k, scaling, held, dt):
    """m [T, E] -> (MoE(m) [T, E], biased scores [T, n])."""
    s = jax.nn.sigmoid(_mm(m, lw["router"], dt))
    biased = s + lw["bias"]
    if chosen is None:
        chosen = jax.lax.top_k(biased, top_k)[1]
    n = s.shape[-1]
    picked = (chosen[..., None] == jnp.arange(n)).any(-2)
    g = jnp.where(picked, s, 0.0)
    g = scaling * g / (g.sum(-1, keepdims=True) + 1e-20)
    first, count = held
    mine = jax.lax.dynamic_slice_in_dim(g, first, count, axis=1)

    def expert(y, xs):
        w_e, gate, up, down = xs
        return y + w_e[:, None] * _swiglu(m, gate, up, down, dt), None

    y, _ = jax.lax.scan(
        expert, _swiglu(m, lw["s_gate"], lw["s_up"], lw["s_down"], dt),
        (mine.T, lw["gate"], lw["up"], lw["down"]))
    return y, biased


@functools.partial(jax.jit, static_argnames=(
    "kind", "dims", "eps", "top_k", "scaling", "held", "without", "dt",
    "state_dtype"))
def _layer(x, lw, chosen, *, kind, dims, eps, top_k, scaling, held,
           without, dt, state_dtype):
    with jax.default_matmul_precision("highest"):
        n = _rms(x, lw["ln1"], eps)
        if kind == "gqa":
            h = x + _gqa(n, lw, *dims, without, dt)
        else:
            h = x + _kda(n, lw, *dims, eps, without, dt, state_dtype)[0]
        y, biased = _moe(_rms(h, lw["ln2"], eps), lw, chosen, top_k,
                         scaling, held, dt)
        return h + y, biased


def forward_routed(weights, tokens, cfg, routes=None, held=None,
                   without=(), matmul_dtype=None, state_dtype=None):
    """tokens [T] int -> (logits [T, V] float32, [biased scores [T, n]] per
    layer). Full causal forward, no cache. ``routes`` None: each layer uses
    its own top-k. ``held`` None: every expert of the router's width."""
    lin = cfg["linear_attn_config"]
    eps = cfg.get("rms_norm_eps", 1e-5)
    if held is None:
        held = (0, cfg["n_routed_experts"])
    kw = dict(eps=eps, top_k=cfg["num_experts_per_tok"],
              scaling=float(cfg.get("routed_scaling_factor", 1.0)),
              held=tuple(int(h) for h in held), without=tuple(without),
              dt=matmul_dtype, state_dtype=state_dtype)
    all_scores = []
    x = jnp.asarray(weights["emb"])[jnp.asarray(tokens)]
    for i, lw in enumerate(weights["layers"]):
        if i in cfg["gqa_layers"]:
            kind, dims = "gqa", (cfg["num_attention_heads"],
                                 cfg["num_key_value_heads"], cfg["head_dim"])
        else:
            kind, dims = "kda", (lin["num_heads"], lin["head_dim"])
        chosen = None if routes is None else jnp.asarray(routes[i])
        x, biased = _layer(x, lw, chosen, kind=kind, dims=dims, **kw)
        all_scores.append(biased)
    with jax.default_matmul_precision("highest"):
        logits = _mm(_rms(x, weights["norm"], eps), weights["head"],
                     matmul_dtype)
    return logits, all_scores


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg,
                          held=cfg.get("held_experts"))[0]
