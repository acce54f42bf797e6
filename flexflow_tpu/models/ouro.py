"""Ouro (LoopLM) decoder for serving: ONE stack of blocks applied
``total_ut_steps`` times to every token with the SAME weights.

Beyond the reference model zoo. ``model_type`` ``ouro`` (ByteDance
Ouro-1.4B / 2.6B). The published modeling file is not on this machine: the
equations below are ISSUE 60's, "as ISSUE 60 states it; not checked against
the published code" (benchmark/configs/ouro-2.6b.json ``assumed``; the plain
reference is benchmark/reference/ouro.py).

    N_x: an RMSNorm with its own weight; four a block, one final.
    block_l(h, t):  a  = Attn_l(N_in(h); cache plane t * L + l)
                    h1 = h + N_in2(a)              the sublayer's OUTPUT is
                    m  = W_down (silu(W_gate N_post(h1)) * W_up N_post(h1))
                    h' = h1 + N_post2(m)           normed before the add
    h^0 = Embed[token]
    for t in 0..T-1:  x = h^t; for l in 0..L-1: x = block_l(x, t)
                      h^{t+1} = N_f(x);  g_t = w_g . h^{t+1} + b_g
    the head reads h^{exit+1}, exit = the first t at which the gate's exit
    probabilities sum to early_exit_threshold, else T - 1 (ops/loop.py)

Attention is full MHA/GQA with whole-head rotate-half rotary, no bias. The
span of L blocks, the final norm and the gate are ONE loop region of the
graph (``FFModel.loop_begin`` / ``loop_end``): the graph, the parameters,
``quant.quantize_params`` and ``hf_weight_map`` see L layers, a compiled
program holds the span once inside one device loop, and every pass keeps a
k/v cache plane of its own (pass ``t`` of layer ``l``: plane ``t * L + l``
of one stack), because a later token attends every pass's keys at every
earlier position: no pass is skipped for any token whatever the gate says.

What cannot serve such a model says so (``ops/loop.refuse_looped``, and
``core/model.LoopRegion.check`` for what a span may hold): speculation
(drafting, tree verification, beam), a pipeline plan, a mesh that divides
the model, ``inference_debugging``. The shared-prefix pool and preemption
work over the planes: the stack is one array, a plane a layer of it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig


@dataclasses.dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 65536
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    tie_word_embeddings: bool = False

    @classmethod
    def from_hf_config(cls, hf) -> "OuroConfig":
        """Accepts a transformers config or a plain dict. Refuses what the
        builder would otherwise ignore: a layer that is not full attention,
        a sliding window, a rotary scaling, another activation."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        layers = get("num_hidden_layers", 48)
        types = get("layer_types") or ["full_attention"] * layers
        if set(types[:layers]) - {"full_attention"}:
            raise NotImplementedError(
                "an Ouro model's layers are all full_attention here; "
                f"layer_types has {sorted(set(types))}")
        if get("use_sliding_window", False):
            raise NotImplementedError(
                "use_sliding_window: a windowed layer inside the loop "
                "region is not served (sliding_window "
                f"{get('sliding_window')})")
        if get("rope_scaling") is not None:
            raise NotImplementedError(
                f"rope_scaling {get('rope_scaling')}: only the plain rotary "
                "embedding is built")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError(
                f"hidden_act {get('hidden_act')!r}: the feed-forward is "
                "SwiGLU")
        heads = get("num_attention_heads", 16)
        return cls(
            vocab_size=get("vocab_size", 49152),
            hidden_size=get("hidden_size", 2048),
            intermediate_size=get("intermediate_size", 5632),
            num_hidden_layers=layers,
            num_attention_heads=heads,
            num_key_value_heads=get("num_key_value_heads") or heads,
            head_dim=get("head_dim") or get("hidden_size", 2048) // heads,
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            rope_theta=float(get("rope_theta", 1000000.0)),
            max_position_embeddings=get("max_position_embeddings", 65536),
            total_ut_steps=get("total_ut_steps", 4),
            early_exit_threshold=float(get("early_exit_threshold", 1.0)),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        )


# A block's four norms, by the checkpoint's names (assumed: ISSUE 60).
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")
# seeded so that a missing norm or gate is seen: the norms ones plus
# N(0, NORM_STD), the gate's vector N(0, GATE_STD), its bias 0
NORM_STD = GATE_STD = 0.02


class _NearOnes:
    def __call__(self, key, shape, dtype):
        import jax
        import jax.numpy as jnp

        return (1.0 + NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)


def create_ouro_model(model, config: OuroConfig,
                      mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                      generation_config: Optional[GenerationConfig] = None,
                      data_type: DataType = DataType.DT_FLOAT):
    """Record the Ouro decoder graph into ``model`` (an FFModel)."""
    from flexflow_tpu.core.initializer import NormInitializer

    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"{mode.name} over a looped model: tree verification and beam "
            "drafting stage positions in ONE cache a layer and commit or "
            "roll them back there; a loop region keeps a cache plane a pass "
            "(ops/loop.py). It is served by incremental decoding")
    if c.hidden_size != c.num_attention_heads * c.head_dim:
        raise NotImplementedError(
            f"head_dim {c.head_dim} x {c.num_attention_heads} heads is not "
            f"hidden_size {c.hidden_size}: the attention op's heads are "
            "hidden_size / num_attention_heads wide")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic

    def norm(x, name):
        return model.rms_norm(x, eps=c.rms_norm_eps, dim=c.hidden_size,
                              initializer=_NearOnes(), name=name)

    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    h = model.loop_begin(h, c.total_ut_steps, name="ut_steps")
    for i in range(c.num_hidden_layers):
        p = f"layers.{i}"
        attn = model.inc_multiquery_self_attention(
            norm(h, f"{p}.{NORMS[0]}"), c.hidden_size,
            c.num_attention_heads, c.num_key_value_heads,
            data_type=data_type, apply_rotary_embedding=True,
            rope_theta=c.rope_theta, name=f"{p}.self_attn")
        h = model.add(h, norm(attn, f"{p}.{NORMS[1]}"))
        x = norm(h, f"{p}.{NORMS[2]}")
        gate = model.dense(x, c.intermediate_size, use_bias=False,
                           datatype=data_type, name=f"{p}.mlp.gate_proj")
        up = model.dense(x, c.intermediate_size, use_bias=False,
                         datatype=data_type, name=f"{p}.mlp.up_proj")
        down = model.dense(model.sigmoid_silu_multi(gate, up), c.hidden_size,
                           use_bias=False, datatype=data_type,
                           name=f"{p}.mlp.down_proj")
        h = model.add(h, norm(down, f"{p}.{NORMS[3]}"))
    h = norm(h, "norm")
    # float32 gate logits (the gemm's accumulator): the exit rule is made
    # in float32
    g = model.dense(h, 1, use_bias=True, datatype=data_type,
                    keep_f32_logits=True,
                    kernel_initializer=NormInitializer(stddev=GATE_STD),
                    name="early_exit_gate")
    _, states, gates = model.loop_end(h, collect=[h, g], name="ut_steps.end")
    h = model.loop_exit(states, gates, c.early_exit_threshold,
                        name="early_exit")
    logits = model.dense(h, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head",
                         **({"tied_to": "embed_tokens"}
                            if c.tie_word_embeddings else {}))
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


# checkpoint keys (assumed, after the block's names above: ISSUE 60)
HF_KEYS = {
    "embed": "model.embed_tokens.weight",
    "norm": "model.norm.weight",
    "gate_w": "model.early_exit_gate.weight",
    "gate_b": "model.early_exit_gate.bias",
    "head": "lm_head.weight",
    "layer": "model.layers.{i}",
}


def preprocess_hf_state_dict(sd, config: "OuroConfig" = None):
    from flexflow_tpu.models.hf_utils import tie_lm_head

    tie_lm_head(sd, HF_KEYS["embed"])


def hf_weight_map(config: OuroConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?): the
    ``num_hidden_layers`` blocks ONCE, whatever ``total_ut_steps``."""
    m = {HF_KEYS["embed"]: ("embed_tokens", "weight", False),
         HF_KEYS["norm"]: ("norm", "weight", False),
         HF_KEYS["gate_w"]: ("early_exit_gate", "kernel", True),
         HF_KEYS["gate_b"]: ("early_exit_gate", "bias", False)}
    if not config.tie_word_embeddings:
        m[HF_KEYS["head"]] = ("lm_head", "kernel", True)
    for i in range(config.num_hidden_layers):
        hf, ff = HF_KEYS["layer"].format(i=i), f"layers.{i}"
        for p, w in (("q_proj", "wq"), ("k_proj", "wk"),
                     ("v_proj", "wv"), ("o_proj", "wo")):
            m[f"{hf}.self_attn.{p}.weight"] = (f"{ff}.self_attn", w, True)
        for p in ("gate_proj", "up_proj", "down_proj"):
            m[f"{hf}.mlp.{p}.weight"] = (f"{ff}.mlp.{p}", "kernel", True)
        for n in NORMS:
            m[f"{hf}.{n}.weight"] = (f"{ff}.{n}", "weight", False)
    return m
