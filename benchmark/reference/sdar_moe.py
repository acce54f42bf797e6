"""SDAR-MoE (JetLM/SDAR-30B-A3B-Chat: a Qwen3-MoE body generating by
diffusion over blocks) in plain float32 ``jax.numpy``/numpy: no kernels, no
cache, no batching, matmul precision "highest".

``forward`` follows the published ``modeling_sdar_moe.py``: RMSNorm
(float32) -> GQA attention whose q and k are RMS-normalised over each HEAD
(one ``head_dim``-wide weight), before the rotary embedding (rotate-half over
the whole head) at the TRUE position, no bias, scale ``head_dim ** -0.5`` ->
residual -> RMSNorm -> router -> softmax in float32 over ALL experts -> the
``num_experts_per_tok`` largest, renormalised over the chosen
(``norm_topk_prob`` true) -> ``sum_e w_e W_down,e (silu(W_gate,e m) * W_up,e
m)`` -> residual; final RMSNorm; untied ``lm_head``; logits unshifted (at a
masked position they are the distribution of the token AT it). With ``B``
the block length, key ``j`` is visible to query ``i`` iff ``j // B <= i //
B``: causal across blocks, both ways inside one. A trailing part block sees
itself whole.

``generate`` follows the published ``generate.py`` (``block_diffusion_
generate``, remasking ``low_confidence_dynamic``): the prompt's whole blocks
are context; then block by block, the block starts as the prompt's remainder
followed by mask tokens; a pass with a mask left picks ``x0 = argmax`` and
its confidence ``softmax_float32(logits)[x0]`` at each masked position and
unmasks every one above the threshold, or the ``block_length //
denoising_steps`` most confident where fewer clear it; a pass that begins
with no mask left keeps the block and the next begins. Output is cut at
``new_tokens``.

Departures from the published code, each on purpose:
  * every pass here is a full forward over the sequence so far (prefix +
    block): the published code keeps a key/value cache of the earlier blocks
    and stores the block on the commit pass. The mathematics is the same;
    the cache is what the system under test is checked for;
  * greedy: the published sampler's temperature, top-k and top-p are off
    (``assumed`` in the configuration file);
  * no end-of-sequence stop (``eos`` None): the benchmark's requests run to
    their asked length; a test may give an id, and the output is then cut
    after its first occurrence in a committed block;
  * which positions are masked is carried, not re-read from the ids, so a
    pick or a prompt token equal to the mask id is a token;
  * weights arrive as a dict of float32 arrays (the served weights,
    dequantised), not a checkpoint; every position goes through every
    expert (one einsum over the stacks) and an expert that was not chosen
    is weighted by 0.

    weights["emb"] [V, H]; weights["layers"][i] = {ln1 [H], wq [H, nh*hd],
    wk/wv [H, nkv*hd], wo [nh*hd, H], q_norm/k_norm [hd], ln2 [H],
    router [H, E], gate/up [E, H, I], down [E, I, H]}; ["norm"] [H];
    ["head"] [H, V]

``forward_routed`` also returns every layer's router probabilities and can be
told which experts to use (``routes``), as reference/olmoe.py's: it then
weights those experts with its OWN probabilities for them, renormalised over
them. ``one_way`` (tests only) turns the mask inside a block causal: what a
program that ignored the block would compute. ``matmul_dtype`` (None:
float32; else an ``ml_dtypes`` type such as ``jnp.float8_e4m3fn``) rounds
every matmul's INPUTS to that type first, the accumulation staying float32:
how the nearest precision below the served one is read (families/sdar_moe.py).
"""

import numpy as np

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


def _rounded(dt):
    """x -> x rounded to ``dt`` and back to float32 (None: as it is): what a
    matmul's input goes through."""
    if dt is None:
        return lambda x: x
    return lambda x: x.astype(dt).astype(jnp.float32)


def _experts(m, lw, probs, chosen, rnd):
    """m [T, H], probs [T, E], chosen [T, k] -> [T, H]: every position
    through every expert, weighted by the chosen experts' probabilities
    renormalised over them and by 0 where the expert was not chosen."""
    E = probs.shape[-1]
    picked = (chosen[..., None] == jnp.arange(E)).any(-2)        # [T, E]
    p = jnp.where(picked, probs, 0.0)
    p = p / p.sum(-1, keepdims=True)
    m = rnd(m)
    a = (jax.nn.silu(jnp.einsum("th,ehi->eti", m, rnd(lw["gate"])))
         * jnp.einsum("th,ehi->eti", m, rnd(lw["up"])))
    y = jnp.einsum("eti,eih->eth", rnd(a), rnd(lw["down"]))
    return (y * p.T[:, :, None]).sum(0)


def block_length(cfg) -> int:
    return cfg["assumed"]["block_length"]


def forward_routed(weights, tokens, cfg, routes=None, one_way=False,
                   matmul_dtype=None):
    """tokens [T] int -> (logits [T, V] float32, [probs [T, E]] per layer),
    under the block mask. ``routes`` None: each layer uses its own top-k."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    k, eps, theta = (cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
                     cfg["rope_theta"])
    B = block_length(cfg)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    visible = (pos[None, :] // B <= pos[:, None] // B)           # [q, key]
    if one_way:
        visible = pos[None, :] <= pos[:, None]
    all_probs, rnd = [], _rounded(matmul_dtype)
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b))
    with jax.default_matmul_precision("highest"):
        x = weights["emb"][tokens]
        for i, lw in enumerate(weights["layers"]):
            n = _rms(x, lw["ln1"], eps)
            q = _rms(mm(n, lw["wq"]).reshape(T, nh, hd), lw["q_norm"], eps)
            kk = _rms(mm(n, lw["wk"]).reshape(T, nkv, hd), lw["k_norm"], eps)
            v = mm(n, lw["wv"]).reshape(T, nkv, hd)
            q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
            kk = jnp.repeat(kk, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            s = jnp.einsum("qnd,knd->nqk", q, kk) / jnp.sqrt(jnp.float32(hd))
            s = jnp.where(visible[None], s, -jnp.inf)
            a = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v)
            h = x + mm(a.reshape(T, nh * hd), lw["wo"])
            m = _rms(h, lw["ln2"], eps)
            probs = jax.nn.softmax(mm(m, lw["router"]), axis=-1)
            chosen = (jax.lax.top_k(probs, k)[1] if routes is None
                      else jnp.asarray(routes[i]))
            x = h + _experts(m, lw, probs, chosen, rnd)
            all_probs.append(probs)
        return mm(_rms(x, weights["norm"], eps), weights["head"]), all_probs


def forward(weights, tokens, cfg):
    """tokens [T] int -> logits [T, V] float32 (benchmark/README.md)."""
    return forward_routed(weights, tokens, cfg)[0]


def unmask(block, masked, logits, cfg):
    """One denoise pass's decision, in place: ``block``/``masked`` [B],
    ``logits`` [B, V] float32 of the block's positions. Returns how many
    positions it unmasked and whether the threshold (not the floor) chose
    them."""
    a = cfg["assumed"]
    floor = a["block_length"] // a["denoising_steps"]
    z = np.asarray(logits, np.float32)
    z = z - z.max(-1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(-1, keepdims=True, dtype=np.float32)
    x0 = p.argmax(-1)
    conf = np.where(masked, p[np.arange(len(x0)), x0], -np.inf)
    high = conf > a["confidence_threshold"]
    cleared = high.sum() >= floor
    if cleared:
        take = high
    else:
        take = np.zeros_like(masked)
        take[np.argsort(-conf, kind="stable")[:floor]] = True
        take &= masked
    block[take] = x0[take]
    masked[take] = False
    return int(take.sum()), bool(cleared)


def generate(weights, prompt, new_tokens, cfg, eos=None, trace=None):
    """The published loop, every pass a full forward over the sequence so
    far. Returns the output tokens (at most ``new_tokens``). ``trace`` (a
    list; tests) gets one ``(kind, unmasked, by_threshold)`` a pass."""
    a = cfg["assumed"]
    B, mask_id = a["block_length"], a["mask_token_id"]
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // B * B
    seq, out = prompt[:whole], []
    known = prompt[whole:]
    while len(out) < new_tokens:
        block = np.array(known + [0] * (B - len(known)), np.int64)
        masked = np.arange(B) >= len(known)
        while masked.any():
            toks = np.where(masked, mask_id, block)
            logits = forward(weights, jnp.asarray(seq + toks.tolist()),
                             cfg)[len(seq):]
            n, by_thr = unmask(block, masked, np.asarray(logits), cfg)
            if trace is not None:
                trace.append(("denoise", n, by_thr))
        if trace is not None:
            trace.append(("commit", 0, False))   # the cache's pass: no pick
        seq += block.tolist()
        out += block[len(known):].tolist()
        known = []
        if eos is not None and eos in out:
            return out[:out.index(eos) + 1][:new_tokens]
    return out[:new_tokens]
