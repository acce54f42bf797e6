"""Granite-4.0-H (ibm-granite/granite-4.0-h-micro, ``model_type``
``granitemoehybrid``) in plain float32 ``jax.numpy``: no kernels, no cache,
no batching, no chunks, matmul precision "highest". x is the residual
stream, ``N`` an RMSNorm (``rms_norm_eps``) with its own weight each time,
``m`` the ``residual_multiplier``.

    x = embedding_multiplier * Embed[token]
    h = x + m * Mix_l(N(x));  x = h + m * (silu(g) * u) W_out,  [g | u] = N(h) W_in     (pre-norm)
    logits = (N(x) Embed^T) / logits_scaling                                            (a tied head)

``Mix_l`` for ``layer_types[l] == "attention"``: q = n Wq, k = n Wk, v = n
Wv in 32 / 8 / 8 heads of 64, NO position embedding and no q/k norm, scores
``attention_multiplier * q . k`` (0.015625: the softmax scale itself),
causal softmax in float32, 4 query heads a key head; ``y = Attn Wo``.

``Mix_l`` for ``"mamba"``, the state-space mixer (Mamba-2; ``H`` heads of
``P`` channels, ``N`` state dims, one group), the literal recurrence, a
token at a time (``lax.scan`` over positions):

    [z | xBC | dt] = n_t W_in                          (H P | H P + 2 N | H)
    xBC_t <- silu(b + sum_{j<4} c[j] * xBC_{t-3+j}),   xBC_{<0} = 0
    [x | B | C] = xBC_t                                x [H, P], B [N], C [N]
    dt_t[h] = softplus(dt_t[h] + dt_bias[h]);  a_t[h] = exp(-exp(A_log[h]) * dt_t[h])
    S[h] = a_t[h] * S[h] + (dt_t[h] * x_t[h]) B_t^T;   y_t[h] = S[h] C_t + D[h] * x_t[h]      S_{-1} = 0
    out = (N_{H P}(y_t * silu(z_t)) * w_n) W_out       the gate FIRST, then one norm over all H P

Assumed, for ``config.json`` has no key that says so (as ISSUE 56 states it;
not checked against the published code): the pre-norm block and where the
multipliers sit; the mixer's split order; the gate before the mixer's norm;
no q/k norm; float32 for ``S`` and the decay.

Departures: none in the mathematics. Weights arrive as float32 arrays (the
served weights, dequantised), and ``weights["layers"]`` may be any iterable,
consumed a layer at a time. Each layer is one jitted function of its kind
(the chip machine's host compiles an eager operation a shape, a second
each).

    weights["emb"] [V, E]; a layer {ln1, ln2 [E], w_in [E, 2 I], w_out [I, E]}
    and, attention: {wq [E, nh*hd], wk/wv [E, nkv*hd], wo [nh*hd, E]}; mamba:
    {win [E, 2 H P + 2 N + H], conv [taps, H P + 2 N], conv_bias [H P + 2 N],
    A_log, dt_bias, D [H], norm [H P], wout [H P, E]}; weights["norm"] [E]

``without`` leaves a term out ON PURPOSE, to show that the comparison has
teeth: ``"D"`` (the skip term), ``"conv_bias"``, ``"conv_tap"`` (the tap of
the position three before), ``"dt_bias"``, ``"z_gate"``, ``"norm_order"``
(the norm FIRST, then the gate), ``"residual_multiplier"`` (1),
``"attention_multiplier"`` (``1 / sqrt(head_dim)``),
``"embedding_multiplier"`` (1); ``state_dtype`` rounds ``S`` to that type
after every token, ``matmul_dtype`` every matmul's inputs.
"""

import functools

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _mm(a, b, dt):
    if dt is not None:
        a, b = (a.astype(dt).astype(jnp.float32),
                b.astype(dt).astype(jnp.float32))
    return a @ b


def _attention(n, lw, nh, nkv, scale, without, dt):
    T = n.shape[0]
    hd = lw["wq"].shape[1] // nh
    q = _mm(n, lw["wq"], dt).reshape(T, nh, hd)
    k = jnp.repeat(_mm(n, lw["wk"], dt).reshape(T, nkv, hd), nh // nkv, 1)
    v = jnp.repeat(_mm(n, lw["wv"], dt).reshape(T, nkv, hd), nh // nkv, 1)
    if "attention_multiplier" in without:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("qnd,knd->nqk", q, k) * scale
    pos = jnp.arange(T)
    s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
    a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
    return _mm(a.reshape(T, nh * hd), lw["wo"], dt)


def _mamba(n, lw, H, P, N, eps, without, dt, state_dtype):
    """n [T, E], the layer's normed input -> (out [T, E], the state the last
    token leaves [H, P, N])."""
    T = n.shape[0]
    d = H * P
    taps = lw["conv"].shape[0]
    zxd = _mm(n, lw["win"], dt)
    z, u, dtr = zxd[:, :d], zxd[:, d:2 * d + 2 * N], zxd[:, 2 * d + 2 * N:]
    ext = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    first = 1 if "conv_tap" in without else 0
    mixed = sum(lw["conv"][j] * ext[j:j + T] for j in range(first, taps))
    if "conv_bias" not in without:
        mixed = mixed + lw["conv_bias"]
    mixed = jax.nn.silu(mixed)
    x = mixed[:, :d].reshape(T, H, P)
    B, C = mixed[:, d:d + N], mixed[:, d + N:]
    if "dt_bias" not in without:
        dtr = dtr + lw["dt_bias"]
    dtv = jax.nn.softplus(dtr)                              # [T, H]
    a = jnp.exp(-jnp.exp(lw["A_log"]) * dtv)

    def token(S, xs):
        x_t, B_t, C_t, dt_t, a_t = xs
        S = (a_t[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(jnp.float32)
        return S, jnp.einsum("hpn,n->hp", S, C_t)

    S, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, B, C, dtv, a))
    if "D" not in without:
        y = y + lw["D"][:, None] * x
    y = y.reshape(T, d)
    gate = 1.0 if "z_gate" in without else jax.nn.silu(z)
    if "norm_order" in without:
        y = _rms(y, lw["norm"], eps) * gate
    else:
        y = _rms(y * gate, lw["norm"], eps)
    return _mm(y, lw["wout"], dt), S


@functools.partial(jax.jit, static_argnames=("H", "P", "N", "eps",
                                             "state_dtype"))
def ssd_state(lw, n, *, H, P, N, eps=1e-5, state_dtype=None):
    """The recurrent state ``[H, P, N]`` a mixer layer holds after the
    tokens whose normed inputs are ``n [T, E]``: the literal recurrence on
    GIVEN inputs, which is how the precision of a program's state is read
    apart from the noise of the layers before it
    (families/granite_hybrid.py)."""
    with jax.default_matmul_precision("highest"):
        return _mamba(n, lw, H, P, N, eps, (), None, state_dtype)[1]


@functools.partial(jax.jit, static_argnames=(
    "kind", "dims", "eps", "residual", "without", "dt", "state_dtype"))
def _layer(x, lw, *, kind, dims, eps, residual, without, dt, state_dtype):
    with jax.default_matmul_precision("highest"):
        m = 1.0 if "residual_multiplier" in without else residual
        n = _rms(x, lw["ln1"], eps)
        if kind == "attention":
            h = x + m * _attention(n, lw, *dims, without, dt)
        else:
            h = x + m * _mamba(n, lw, *dims, eps, without, dt,
                               state_dtype)[0]
        gu = _mm(_rms(h, lw["ln2"], eps), lw["w_in"], dt)
        half = gu.shape[1] // 2
        return h + m * _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:],
                           lw["w_out"], dt)


def forward(weights, tokens, cfg, without=(), matmul_dtype=None,
            state_dtype=None):
    """tokens [T] int -> logits [T, V] float32. Full causal forward, no
    cache (benchmark/README.md)."""
    eps = cfg.get("rms_norm_eps", 1e-5)
    without = tuple(without)
    kw = dict(eps=eps, residual=float(cfg["residual_multiplier"]),
              without=without, dt=matmul_dtype, state_dtype=state_dtype)
    emb = jnp.asarray(weights["emb"])
    x = emb[jnp.asarray(tokens)]
    if "embedding_multiplier" not in without:
        x = x * float(cfg["embedding_multiplier"])
    for i, lw in enumerate(weights["layers"]):
        if cfg["layer_types"][i] == "attention":
            kind, dims = "attention", (cfg["num_attention_heads"],
                                       cfg["num_key_value_heads"],
                                       float(cfg["attention_multiplier"]))
        else:
            kind, dims = "mamba", (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                                   cfg["mamba_d_state"])
        x = _layer(x, lw, kind=kind, dims=dims, **kw)
    with jax.default_matmul_precision("highest"):
        logits = _mm(_rms(x, weights["norm"], eps), emb.T, matmul_dtype)
    return logits / float(cfg["logits_scaling"])
