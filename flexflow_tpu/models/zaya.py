"""ZAYA1 decoder for serving (HF ``model_type`` ``zaya``: Zyphra/ZAYA1-8B):
attention in a compressed latent whose queries, keys and values reach back
along the sequence, and a top-1 router that is an MLP fed by the layer
before.

One layer, ``N`` an RMSNorm of its own each time, ``a, b, c, d`` learned
vectors of the hidden size a sublayer (residual scaling):

    h1 = (a1 * h  + b1) + (c1 * CCA(N(h))         + d1)
    h' = (a2 * h1 + b2) + (c2 * MoE(N(h1), r_prev) + d2)     # also yields r

* ``CCA`` (ops/cca_attention.py has the equations): queries and keys
  projected into ``num_attention_heads + num_key_value_heads`` heads of
  ``head_dim``, half the hidden size at the published widths; two causal
  convolutions of kernel ``cca_time0`` = ``cca_time1`` = 2 along the
  sequence; a value that is half the token's before; rotary over the first
  ``partial_rotary_factor`` of a head's dims. A slot keeps the row's tail
  (the last two positions' unmixed latents, the last position's shifted
  value half) beside a plain grouped k/v cache.
* ``MoE(x, r_prev)``: ``r = Wd x + gamma * r_prev`` (``router_hidden_size``
  wide; layer 0 has no ``r_prev``), ``z = W3 gelu(W2 gelu(W1 N_r(r)))``
  over ``num_experts + 1`` outputs in float32, ``p = softmax(z)``, ``e =
  argmax(p + bias)``; the token's result is ``p_e`` times SwiGLU expert
  ``e`` of ``moe_intermediate_size``, and NOTHING where ``e`` is the last
  output, which names no expert (the token skips the layer's experts).
  ``r`` is a second value threaded through the graph beside the hidden
  state. The router is graph ops, as the other expert families'; the skip
  is ``moe_experts(held=(0, num_experts))`` over a router one wider: a pair
  routed past the held experts is no work and no row, counted as a token
  without a routed pair.
* ``logits = E^T N(h)`` on the embedding's own table
  (``tie_word_embeddings``; ``FFModel.dense(tied_to=)``): one array.

``config.json`` has keys for the sizes only. What it has none for (the
residual scaling, the convolutions' grouping, the value shift, the q-k mean,
the norms and ``tau``, ``gamma``, the router MLP's shape, GELU's exact form,
the bias and the skip output) is as ISSUE 50 states it, not checked against
the published ``modeling_zaya.py``; benchmark/configs/zaya1-8b.json lists
each under ``assumed``, and the checkpoint names below (``HF_KEYS``) with
them.

Tree verification, beam drafting, the prefix pool, a mesh that divides the
model and a pipeline plan cannot carry a row's tail and refuse this model
(``ops/inc_attention.refuse_windowed``); preemption can (the victim is
prefilled again from position 0, which rebuilds the tail).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from flexflow_tpu.ffconst import ActiMode, DataType, InferenceMode, OpType
from flexflow_tpu.models.exaone_moe import (_EXPERT_PROJ, _experts_key,
                                            stack_held_experts)
from flexflow_tpu.ops.base import OpImpl, register_op
from flexflow_tpu.serve.batch_config import GenerationConfig

# the four vectors of a sublayer's residual scaling, in the equations' order
_SCALING = ("a", "b", "c", "d")
# Checkpoint names below ``model.layers.{i}.`` -> (layer below
# ``layers.{i}.``, weight, transpose): assumed, like everything config.json
# has no key for. The convolutions' two entries are torch Conv1d weights,
# ``conv0`` depthwise ``[C, 1, 2]``, ``conv1`` grouped ``[C, D, 2]``
# (``preprocess_hf_state_dict`` lays them out as the op holds them).
HF_KEYS = {
    "input_layernorm.weight": ("input_layernorm", "weight", False),
    "post_attention_layernorm.weight":
        ("post_attention_layernorm", "weight", False),
    # ``{q,k,v1,v2}_proj.weight`` in the checkpoint, joined ``[C + 2 Dv,
    # E]`` (``preprocess_hf_state_dict``): one array, one gemm
    "self_attn.qkv_proj.weight": ("self_attn", "wqkv", True),
    "self_attn.o_proj.weight": ("self_attn", "wo", True),
    # ``conv0.weight``, ``conv0.bias``, ``conv1.bias``, joined ``[4, C]``
    "self_attn.conv_vec": ("self_attn", "conv_vec", False),
    "self_attn.conv1.weight": ("self_attn", "conv1_w", False),
    "self_attn.temperature": ("self_attn", "tau", False),
    "mlp.router.down_proj.weight": ("mlp.router.down_proj", "kernel", True),
    "mlp.router.norm.weight": ("mlp.router.norm", "weight", False),
    "mlp.router.fc1.weight": ("mlp.router.fc1", "kernel", True),
    "mlp.router.fc2.weight": ("mlp.router.fc2", "kernel", True),
    "mlp.router.fc3.weight": ("mlp.router.fc3", "kernel", True),
    "mlp.router.balancing_bias": ("mlp.router.balancing_bias", "weight",
                                  False),
    "mlp.router.eda_gamma": ("mlp.router.eda_gamma", "weight", False),
    # a sublayer's four vectors, ``res_scale.{a,b,c,d}`` in the checkpoint,
    # stacked ``[4, E]`` (``preprocess_hf_state_dict``): one array to fetch
    "self_attn.res_scale": ("self_attn.res_scale", "weight", False),
    "mlp.res_scale": ("mlp.res_scale", "weight", False),
}


@dataclasses.dataclass
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    cca_time0: int = 2
    cca_time1: int = 2
    moe_intermediate_size: int = 2048       # ONE expert's width
    num_experts: int = 16
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # seeded weights only (no key of the source): the standard deviation of
    # the router's last matrix (None: the program's default initialiser)
    router_init_std: Optional[float] = None

    @classmethod
    def from_hf_config(cls, hf) -> "ZayaConfig":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        for key, want in (("cca_time0", 2), ("cca_time1", 2),
                          ("num_experts_per_tok", 1), ("hidden_act", "silu"),
                          ("attention_bias", False), ("lm_head_bias", False),
                          ("sliding_window", None),
                          ("tie_word_embeddings", True)):
            if get(key, want) != want:
                raise NotImplementedError(
                    f"zaya with {key} = {get(key)!r}: only {want!r} is built "
                    "(two taps a convolution, so a tail of two positions; "
                    "one pick a token; SiLU experts; no bias; no window; a "
                    "head on the embedding's table)")
        kinds = set(get("layer_types") or ["hybrid"])
        if kinds != {"hybrid"}:
            raise NotImplementedError(
                f"zaya layer_types {sorted(kinds)}: only 'hybrid' (an "
                "attention sublayer then a routed one) is built")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        rope = (get("rope_parameters") or {}).get("hybrid") or {}
        for key in ("rope_theta", "partial_rotary_factor"):
            if rope.get(key) is not None:
                kw[key] = rope[key]
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"zaya with rope_type {rope['rope_type']!r}")
        return cls(**{k: v for k, v in kw.items() if v is not None})

    @property
    def router_width(self) -> int:
        """The router's outputs: the experts, then the skip."""
        return self.num_experts + 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def bias_std(self) -> float:
        """The seeded selection bias's spread: a quarter of the mean softmax
        score over the router's width (models/longcat_flash.py has why)."""
        return 0.25 / self.router_width


@register_op
class RouterBarrier(OpImpl):
    """A graph step that computes nothing: its output is its input behind an
    optimisation barrier, across which the compiler fuses nothing.

    Why it exists: left to itself XLA:TPU fuses the router's four small
    gemms (``down_proj`` .. ``fc3``, 256 wide) into one another as
    producers, and its cost model then overflows its stack on the prefill
    step's ``[4, 128, .]`` shapes: libtpu 0.0.34 segfaults while COMPILING,
    on the chip and for a described one. One barrier in the middle of the
    chain leaves two pairs. When it can go: ROADMAP R7 (e) has the command
    (``tools/compile_zaya_for_v5e.py --unfenced``) that says whether the
    compiler at hand still needs it."""

    op_type = OpType.NOOP

    @staticmethod
    def infer_output_specs(attrs, input_specs):
        return [input_specs[0]]

    @staticmethod
    def forward(attrs, params, inputs, ctx):
        import jax

        return [jax.lax.optimization_barrier(inputs[0])]


class _ScalingInitializer:
    """Rows a, b, c, d of a sublayer's residual scaling: a = c = 1, b = d =
    0, each plus N(0, 0.02), so that a missing one is seen."""

    def __call__(self, key, shape, dtype):
        import jax
        import jax.numpy as jnp

        return (jnp.asarray([1.0, 0.0, 1.0, 0.0], dtype)[:, None]
                + 0.02 * jax.random.normal(key, shape, dtype))


def _rescaled(model, h, out, c: ZayaConfig, p: str, data_type):
    """``(a * h + b) + (c * out + d)``, the four vectors the rows of ONE
    parameter ``p.res_scale`` ``[4, E]`` (one array for a step to fetch,
    where four were four)."""
    a, b, c_, d = model.split(
        model.parameter([len(_SCALING), c.hidden_size], data_type,
                        initializer=_ScalingInitializer(),
                        name=f"{p}.res_scale"),
        len(_SCALING), axis=0, name=f"{p}.res_scale.rows")
    return model.add(model.add(model.multiply(h, a), b),
                     model.add(model.multiply(out, c_), d))


def routed_branch(model, x, r_prev, p: str, c: ZayaConfig, data_type):
    """``MoE(x, r_prev)`` recorded under the checkpoint's names below ``p``
    (``layers.{i}.mlp``): ``(the experts' result, r)``."""
    from flexflow_tpu.core.initializer import (ConstantInitializer,
                                               NormInitializer)

    W = c.router_hidden_size

    def fc(t, width, name, **kw):
        return model.dense(t, width, use_bias=False, datatype=data_type,
                           name=f"{p}.router.{name}", **kw)

    r = fc(x, W, "down_proj")
    if r_prev is not None:
        gamma = model.parameter([1], data_type,
                                initializer=ConstantInitializer(0.5),
                                name=f"{p}.router.eda_gamma")
        r = model.add(r, model.multiply(r_prev, gamma))
    z = model.rms_norm(r, eps=c.rms_norm_eps, dim=W, name=f"{p}.router.norm")
    z = fc(z, W, "fc1", activation=ActiMode.AC_MODE_GELU)
    z = fc(z, W, "fc2", activation=ActiMode.AC_MODE_GELU)
    z = model._add_layer(RouterBarrier.op_type, [z], {},
                         f"{p}.router.barrier")
    # float32 logits (the gemm's accumulator): the scores, the choice and
    # the weight are made in float32, as the other expert families'
    logits = fc(z, c.router_width, "fc3", keep_f32_logits=True,
                kernel_initializer=(
                    None if c.router_init_std is None
                    else NormInitializer(stddev=c.router_init_std)))
    scores = model.softmax(logits, name=f"{p}.router.scores")
    # the bias steers the choice only, never the weight. Seeded non-zero,
    # so that a test sees it
    bias = model.parameter([c.router_width], DataType.DT_FLOAT,
                           initializer=NormInitializer(stddev=c.bias_std),
                           name=f"{p}.router.balancing_bias")
    _, chosen = model.top_k(model.add(scores, bias), 1,
                            name=f"{p}.router.top_k")
    # the chosen probability itself: top-1, renormalised it would be 1
    weight = model.gather(scores, chosen, dim=2, name=f"{p}.router.picked")
    # the router's last output names no expert: a pick of it is a pair
    # routed past the held experts, no work and no row (ops/moe.py)
    return model.moe_experts(
        x, chosen, weight, c.router_width, c.moe_intermediate_size,
        data_type=data_type, held=(0, c.num_experts),
        name=f"{p}.experts"), r


def create_zaya_model(model, config: ZayaConfig,
                      mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                      generation_config: Optional[GenerationConfig] = None,
                      data_type: DataType = DataType.DT_FLOAT):
    """Record the ZAYA1 decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"zaya is served by incremental decoding only, not {mode}: tree "
            "verification (speculation) and beam drafting stage tokens that "
            "may be rejected, and an attention layer that carries a tail "
            "(ops/cca_attention.py) has overwritten it by then")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")

    def norm(t, name):
        return model.rms_norm(t, eps=c.rms_norm_eps, dim=c.hidden_size,
                              name=name)

    r = None
    for i in range(c.num_hidden_layers):
        ly = f"layers.{i}"
        attn = model.inc_cca_attention(
            norm(h, f"{ly}.input_layernorm"), c.hidden_size,
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.rotary_dim, rope_theta=c.rope_theta, data_type=data_type,
            name=f"{ly}.self_attn")
        h = _rescaled(model, h, attn, c, f"{ly}.self_attn", data_type)
        routed, r = routed_branch(
            model, norm(h, f"{ly}.post_attention_layernorm"), r,
            f"{ly}.mlp", c, data_type)
        h = _rescaled(model, h, routed, c, f"{ly}.mlp", data_type)

    logits = model.dense(norm(h, "norm"), c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         tied_to="embed_tokens", name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


def preprocess_hf_state_dict(sd, config: ZayaConfig):
    """Stack the experts' ``[out, in]`` Linears into ``[E, in, out]``; lay
    the two Conv1d weights out as the op holds them (``conv0`` ``[C, 1, 2]``
    -> rows 0-1 of ``conv_vec`` ``[4, C]``, the two biases its rows 2-3;
    ``conv1`` ``[C, D, 2]``, output channel ``j * D + o`` of group ``j`` ->
    ``[H + G, 2, D in, D out]``); join the four projections into
    ``qkv_proj``; drop a tied head's copy."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    c = config
    D = c.head_dim
    sd.pop("lm_head.weight", None)
    for i in range(c.num_hidden_layers):
        p = f"model.layers.{i}.self_attn"
        proj = [f"{p}.{n}_proj.weight" for n in ("q", "k", "v1", "v2")]
        if all(k in sd for k in proj):
            sd[f"{p}.qkv_proj.weight"] = np.concatenate(
                [_to_numpy(sd.pop(k)) for k in proj], axis=0)
        vec = [f"{p}.conv0.weight", f"{p}.conv0.bias", f"{p}.conv1.bias"]
        if all(k in sd for k in vec):
            w0, b0, b1 = (_to_numpy(sd.pop(k)) for k in vec)
            sd[f"{p}.conv_vec"] = np.concatenate(
                [w0.reshape(w0.shape[0], 2).T, b0[None], b1[None]], axis=0)
        if f"{p}.conv1.weight" in sd:
            w1 = _to_numpy(sd[f"{p}.conv1.weight"])        # [C, D, 2]
            sd[f"{p}.conv1.weight"] = np.ascontiguousarray(
                w1.reshape(-1, D, D, 2).transpose(0, 3, 2, 1))
        for sub in ("self_attn", "mlp"):
            keys = [f"model.layers.{i}.{sub}.res_scale.{v}" for v in _SCALING]
            if all(k in sd for k in keys):
                sd[f"model.layers.{i}.{sub}.res_scale"] = np.stack(
                    [_to_numpy(sd.pop(k)) for k in keys])
        stack_held_experts(sd, i, c.num_experts, 0, c.num_experts)


def hf_weight_map(config: ZayaConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has prepared. No entry for
    a head: it reads the embedding's table."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False)}
    for i in range(config.num_hidden_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for key, (layer, weight, transpose) in HF_KEYS.items():
            if i == 0 and key == "mlp.router.eda_gamma":
                continue            # layer 0 has no router state before it
            m[f"{hf}.{key}"] = (f"{ff}.{layer}", weight, transpose)
        for proj, w in _EXPERT_PROJ:
            m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
    return m
