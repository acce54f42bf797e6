"""EvaByte (6.5B architecture) in plain float32 ``jax.numpy``: no kernels, no
cache, no batching, matmul precision "highest".

The sizes are the published ``config.json``'s (EvaByte/EvaByte); the formulas
are the published modeling code's (``eva.py``: ``_generate_feature_map``,
``_calculate_chunk_rfa_cache`` and the joint softmax of its aggregation
kernel) as the author of this file holds them, with no network to read them
again. What is ASSUMED, here and in ``benchmark/configs/evabyte-6.5b.json``:
which vector pools keys and which pools values (``adaptive_mu_k`` the keys,
``adaptive_phi`` the values, both scoring the KEYS); that the pooled keys are
the ROTATED ones; that a window's summaries become visible when the window
is complete, all at once, and not chunk by chunk; the rotate-half rotary
convention over all of a head's dims; the head's layout (``num_pred_heads``
blocks of ``vocab_size`` columns, the first the next byte's).

With ``W = window_size``, ``c = chunk_size``, ``D`` the head size, ``s =
D^-1/2``, a layer at a time:

    norm(x)  = x / sqrt(mean(x^2) + eps) * (1 + g)      norm_add_unit_offset
    h'       = h + Attn(norm1(h))                       adds in float32
    h''      = h' + W_down(silu(W_gate y) * W_up y),  y = norm2(h')
    q_i, k_i, v_i = the three projections of norm1(h_i), H heads of D;
             rotary on q and k at the true position i (rotate-half, all D
             dims, theta). A head at a time, on rotated keys:
    chunk n  = positions [c n, c n + c):
             kbar_n = sum_m softmax_m(mu . k_m) k_m
             vbar_n = sum_m softmax_m(phi . k_m) v_m        m over the chunk
    query i in window w = i // W sees the exact pairs
             E_i = {j : W w <= j <= i} and the summaries
             C_i = {n : n < (W / c) w}, in ONE softmax:
    o_i      = (sum_E e^{s q_i.k_j} v_j + sum_C e^{s q_i.kbar_n} vbar_n)
               / (sum_E e^{s q_i.k_j} + sum_C e^{s q_i.kbar_n}),  then W_o
    logits   = norm(h) W_head[:, :V]                    float32

Computed a window at a time (a window's queries see that window's keys and
the summaries before it, nothing else), so the scores of 4130 positions at
the published widths are three blocks and not one 4130 x 4130 a head, and
jitted whole: an eager ``jax.numpy`` program costs a compile an operation on
the chip machine's host (PERF.md section 6, PR 35).

Departures: none in the mathematics. Weights arrive as a dict of float32
arrays (the served weights, dequantised), not a checkpoint:

    weights["emb"] [V, E]; weights["layers"][i] = {ln1 [E], wq/wk/wv
    [E, H*D], wo [H*D, E], mu/phi [H, D], ln2 [E], gate/up [E, I], down
    [I, E]}; ["norm"] [E]; ["head"] [E, V]

``matmul_dtype`` (None: float32; else an ``ml_dtypes`` type such as
``jnp.float8_e4m3fn``) rounds every matmul's INPUTS to that type first, the
accumulation staying float32: how the nearest precision below the served one
is read (families/evabyte.py). ``wrong`` (the checks' and the tests' only)
computes one of three things the model is NOT, each of which the comparison
has to tell from it: ``"early"`` makes a window's summaries visible one
window early (a query sees its own window's chunks as summaries too),
``"mean"`` pools a chunk by its plain mean, ``"swapped"`` exchanges ``mu``
and ``phi``.
"""

import functools

import jax
import jax.numpy as jnp


def _norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)          # [T, hd]
    return x * jnp.cos(ang)[:, None, :] + _rotate_half(x) * jnp.sin(ang)[:, None, :]


def _rounded(dt):
    """x -> x rounded to ``dt`` and back to float32 (None: as it is): what a
    matmul's input goes through."""
    if dt is None:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


def summaries(k, v, mu, phi, c, wrong=None):
    """k, v [T, H, D] (keys rotated), mu, phi [H, D] -> (kbar, vbar)
    [T // c, H, D]: the learned softmax pools of every whole chunk."""
    T, H, D = k.shape
    n = T // c
    kc, vc = k[:n * c].reshape(n, c, H, D), v[:n * c].reshape(n, c, H, D)
    if wrong == "mean":
        return kc.mean(1), vc.mean(1)
    if wrong == "swapped":
        mu, phi = phi, mu
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu), axis=1)
    b = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", a, kc),
            jnp.einsum("nch,nchd->nhd", b, vc))


def attention(q, k, v, mu, phi, W, c, rnd=lambda x: x, wrong=None):
    """q, k, v [T, H, D] (q, k rotated) -> [T, H, D], a window at a time."""
    T, H, D = q.shape
    kbar, vbar = summaries(k, v, mu, phi, c, wrong)
    out = []
    for w in range(-(-T // W)):
        lo, hi = w * W, min(T, (w + 1) * W)
        # every chunk of every window before this one, none of its own
        n = (W // c) * (w + 1 if wrong == "early" else w)
        n = min(n, kbar.shape[0])
        keys = jnp.concatenate([kbar[:n], k[lo:hi]], axis=0)
        vals = jnp.concatenate([vbar[:n], v[lo:hi]], axis=0)
        s = jnp.einsum("qhd,jhd->hqj", rnd(q[lo:hi]), rnd(keys)) * D ** -0.5
        j = jnp.arange(n + hi - lo)[None, :]
        seen = (j < n) | (j - n <= jnp.arange(hi - lo)[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqj,jhd->qhd", rnd(p), rnd(vals)))
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("H", "eps", "theta", "W", "c",
                                             "matmul_dtype", "wrong"))
def _forward(weights, tokens, *, H, eps, theta, W, c, matmul_dtype, wrong):
    rnd = _rounded(matmul_dtype)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b))
    T = tokens.shape[0]
    pos = jnp.arange(T)
    h = weights["emb"][tokens]
    for lw in weights["layers"]:
        y = _norm(h, lw["ln1"], eps)
        q, k, v = (mm(y, lw[n]).reshape(T, H, -1) for n in ("wq", "wk", "wv"))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        a = attention(q, k, v, lw["mu"], lw["phi"], W, c, rnd, wrong)
        h = h + mm(a.reshape(T, -1), lw["wo"])
        y = _norm(h, lw["ln2"], eps)
        h = h + mm(jax.nn.silu(mm(y, lw["gate"])) * mm(y, lw["up"]),
                   lw["down"])
    return mm(_norm(h, weights["norm"], eps), weights["head"])


def forward(weights, tokens, cfg, matmul_dtype=None, wrong=None):
    """tokens [T] int -> logits [T, V] float32 of the next-byte head."""
    with jax.default_matmul_precision("highest"):
        return _forward(
            weights, jnp.asarray(tokens), H=cfg["num_attention_heads"],
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
            W=cfg["window_size"], c=cfg["chunk_size"],
            matmul_dtype=matmul_dtype, wrong=wrong)
