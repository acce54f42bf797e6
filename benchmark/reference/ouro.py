"""Ouro (ByteDance/Ouro-2.6B, ``model_type`` ``ouro``) in plain float32
``jax.numpy``: no kernels, no cache, no batching, matmul precision
"highest". ONE stack of ``L`` blocks is applied ``T = total_ut_steps`` times
to the whole sequence with the SAME weights, the final norm between passes;
``N_x`` is an RMSNorm (``rms_norm_eps``) with its own weight.

    block_l(h):  a  = Attn_l(N_in(h))          causal softmax(q . k / sqrt(hd)) v, rotary (theta, whole head,
                 h1 = h + N_in2(a)             rotate-half), no bias; the sublayer's OUTPUT is normed before the add
                 m  = (silu(N_post(h1) W_gate) * N_post(h1) W_up) W_down
                 h' = h1 + N_post2(m)
    h^0 = Embed[token]
    for t in 0..T-1:  x = h^t;  for l in 0..L-1: x = block_l(x);   h^{t+1} = N_f(x);   g_t = h^{t+1} . w_g + b_g
    lambda_t = sigmoid(g_t);  p_t = lambda_t * prod_{j<t}(1 - lambda_j) for t < T-1,  p_{T-1} = prod_{j<T-1}(1 - lambda_j)
    exit = the first t with p_0 + .. + p_t >= early_exit_threshold, else T-1;   logits = h^{exit+1} W_head

Keys and values of pass ``t`` are computed from pass ``t``'s own states: a
serving program must keep them apart a pass (a cache plane a pass).

Assumed, for ``config.json`` has no key that says so (as ISSUE 60 states it;
not checked against the published code): the sandwich block and where its
four norms sit; the final norm between passes; the gate on the normed state,
with a bias; the exit rule; the rotary convention.

Departures: none in the mathematics. Weights arrive as float32 arrays (the
served weights, dequantised). A block is one jitted function (the chip
machine's host compiles an eager operation a shape, a second each), called
``T x L`` times.

    weights["emb"] [V, E]; a layer {n_in, n_in2, n_post, n_post2 [E], wq, wk,
    wv [E, nh*hd], wo [nh*hd, E], gate, up [E, I], down [I, E]};
    weights["norm"] [E]; weights["gate_w"] [E, 1]; weights["gate_b"] [1];
    weights["head"] [E, V]

Wrong ON PURPOSE, to show that the comparison has teeth: ``passes`` (run
that many instead of ``total_ut_steps``); ``without``: ``"own_planes"``
(every pass attends pass 0's keys and values: one cache for all passes),
``"norm_between"`` (no final norm between passes: applied once, behind the
last), ``"post_norm"`` (no ``N_post2``), ``"exit_state"`` (the logits off
the state BEFORE the pass the rule picks); ``rope_theta``;
``matmul_dtype`` rounds every matmul's inputs to that type.
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


def _rotate(x, theta):
    """x [T, heads, hd], position = row: rotate-half over the whole head."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "eps", "theta",
                                             "without", "dt"))
def _block(x, lw, kv, *, nh, nkv, eps, theta, without, dt):
    """One block over the whole sequence. ``kv``: None, or the (k, v) the
    block attends in place of its own (``"own_planes"`` left out). Returns
    (h', (k, v))."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        n = _rms(x, lw["n_in"], eps)
        hd = lw["wq"].shape[1] // nh
        q = _rotate(_mm(n, lw["wq"], dt).reshape(T, nh, hd), theta)
        k = _rotate(_mm(n, lw["wk"], dt).reshape(T, nkv, hd), theta)
        v = _mm(n, lw["wv"], dt).reshape(T, nkv, hd)
        own = (k, v)
        if kv is not None:
            k, v = kv
        k, v = jnp.repeat(k, nh // nkv, 1), jnp.repeat(v, nh // nkv, 1)
        s = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(hd))
        pos = jnp.arange(T)
        s = jnp.where((pos[:, None] >= pos[None, :])[None], s, -jnp.inf)
        a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
        a = _mm(a.reshape(T, nh * hd), lw["wo"], dt)
        h = x + _rms(a, lw["n_in2"], eps)
        n = _rms(h, lw["n_post"], eps)
        m = _mm(jax.nn.silu(_mm(n, lw["gate"], dt)) * _mm(n, lw["up"], dt),
                lw["down"], dt)
        if "post_norm" not in without:
            m = _rms(m, lw["n_post2"], eps)
        return h + m, own


def exit_passes(gates, threshold: float):
    """``gates`` [T, n] logits -> (p [T, n], exit [n]): the rule above,
    literally."""
    lam = jax.nn.sigmoid(jnp.asarray(gates, jnp.float32))
    T = lam.shape[0]
    ps, stay = [], jnp.ones_like(lam[0])
    for t in range(T):
        ps.append(stay if t == T - 1 else lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    p = jnp.stack(ps)
    at = jnp.full(lam[0].shape, T - 1)
    for t in reversed(range(T)):
        at = jnp.where(jnp.cumsum(p, axis=0)[t] >= threshold, t, at)
    return p, at


def forward(weights, tokens, cfg, without=(), passes=None, rope_theta=None,
            matmul_dtype=None, return_exit=False):
    """tokens [n] int -> logits [n, V] float32 (``return_exit``: and the
    exit probabilities [T, n] and the pass a token exits at). Full causal
    forward, no cache (benchmark/README.md)."""
    without = tuple(without)
    steps = int(cfg["total_ut_steps"] if passes is None else passes)
    kw = dict(nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]),
              theta=float(cfg["rope_theta"] if rope_theta is None
                          else rope_theta),
              without=without, dt=matmul_dtype)
    eps = kw["eps"]
    layers = list(weights["layers"])
    x = jnp.asarray(weights["emb"])[jnp.asarray(tokens)]
    states, gates, first = [x], [], [None] * len(layers)
    for t in range(steps):
        for i, lw in enumerate(layers):
            shared = first[i] if "own_planes" in without else None
            x, own = _block(x, lw, shared, **kw)
            if t == 0:
                first[i] = own
        with jax.default_matmul_precision("highest"):
            last = t == steps - 1
            if "norm_between" not in without or last:
                x = _rms(x, weights["norm"], eps)
            states.append(x)
            gates.append((_mm(x, weights["gate_w"], matmul_dtype)
                          + weights["gate_b"])[:, 0])
    p, at = exit_passes(jnp.stack(gates), float(cfg["early_exit_threshold"]))
    pick = at if "exit_state" in without else at + 1
    h = jnp.take_along_axis(jnp.stack(states), pick[None, :, None],
                            axis=0)[0]
    with jax.default_matmul_precision("highest"):
        logits = _mm(h, weights["head"], matmul_dtype)
    return (logits, p, at) if return_exit else logits
