"""Solar-Open2 decoder for serving (HF ``model_type`` ``solar_open2``:
upstage/Solar-Open2-250B): three layers in four keep a RECURRENT STATE a row
(a gated delta rule with one decay a key channel, ops/kda_attention.py), the
fourth a plain grouped k/v cache with no position embedding and an output
gate, and every layer routes over sparse experts beside a shared one.

One layer ``i``, ``N`` an RMSNorm of its own each time (pre-norm):

    h1 = h  + Mix_i(N(h))        Mix_i: the gated GQA layer for i in
    h' = h1 + MoE(N(h1))         ``gqa_layers``, the KDA layer otherwise

* KDA layer (``linear_attn_config``: ``num_heads`` heads of ``head_dim``,
  ``short_conv_kernel_size`` taps; ops/kda_attention.py has the equations):
  a slot keeps ``[heads, head_dim, head_dim]`` float32 and the
  convolutions' tails a layer, overwritten by every step.
* Gated GQA layer: ``num_attention_heads`` query and ``num_key_value_heads``
  key/value heads of ``head_dim``, no rotary embedding (``use_rope`` false),
  no q/k norm, ``y = W_o (sigmoid(W_g x) * Attn)`` (``use_gqa_gate``): the
  plain attention op with ``output_gate``, through the flash kernel on the
  k/v cache every other model keeps.
* ``MoE``: ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``,
  top ``num_experts_per_tok`` of sigmoid scores, a per-expert selection bias
  for the choice only, the chosen scores normalised (``norm_topk_prob``)
  times ``routed_scaling_factor``, plus ``n_shared_experts`` shared experts
  on every token: ``models/exaone_moe.sparse_layer``. ``held_experts =
  (first, count)`` builds one chip's share of an expert-parallel layer.
* Untied head over a final ``N``.

``gqa_layers`` decides a layer's kind, not a period. ``config.json`` has
keys for the sizes and the flags only. What it has none for (the pre-norm
block, the gate's form and width, the router's scoring, the seeded decay)
is as ISSUE 54 states it, not checked against the published code;
benchmark/configs/solar-open2-250b.json lists each under ``assumed``, and the
checkpoint names below (``HF_KEYS``) with them.

Tree verification, beam drafting, the prefix pool, a mesh that divides the
model and a pipeline plan cannot carry a recurrent state and refuse this
model (``ops/inc_attention.refuse_windowed``); preemption can (the victim is
prefilled again from position 0, which rebuilds its state).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.models.exaone_moe import (_EXPERT_PROJ, _experts_key,
                                            sparse_layer, stack_held_experts)
from flexflow_tpu.serve.batch_config import GenerationConfig

_QKV = ("q", "k", "v")
# Checkpoint names below ``model.layers.{i}.self_attn.`` -> (weight,
# transpose) of a KDA layer: assumed (the published layer's names), like
# everything config.json has no key for. ``preprocess_hf_state_dict`` joins
# ``{q,k,v}_proj`` into ``qkv_proj``, ``{f_a,g_a,b}_proj`` into ``low_proj``
# and the three depthwise Conv1d weights ``[C, 1, taps]`` into ``conv``
# ``[taps, 3 C]``.
HF_KEYS_KDA = {
    "qkv_proj.weight": ("wqkv", True),
    "low_proj.weight": ("wlow", True),
    "f_b_proj.weight": ("wfb", True),
    "g_b_proj.weight": ("wgb", True),
    "conv": ("conv", False),
    "A_log": ("A_log", False),
    "dt_bias": ("dt_bias", False),
    "o_norm.weight": ("o_norm", False),
    "o_proj.weight": ("wo", True),
}
# ... and of a gated GQA layer
HF_KEYS_GQA = {
    "q_proj.weight": ("wq", True),
    "k_proj.weight": ("wk", True),
    "v_proj.weight": ("wv", True),
    "g_proj.weight": ("wg", True),
    "o_proj.weight": ("wo", True),
}
HF_KEYS = {"kda": HF_KEYS_KDA, "gqa": HF_KEYS_GQA}


@dataclasses.dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64           # the GQA layers'
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_num_heads: int = 64              # the KDA layers'
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    moe_intermediate_size: int = 1280       # ONE expert's width
    n_routed_experts: int = 320             # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    # this chip's routed experts (first, count); None: all of them
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.gqa_layers is None:         # the family's 1:3 pattern
            self.gqa_layers = tuple(range(0, L, 4))
        # a cut in depth keeps the leading layers
        self.gqa_layers = tuple(i for i in self.gqa_layers if i < L)

    @classmethod
    def from_hf_config(cls, hf) -> "SolarOpen2Config":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        for key, want, why in (
                ("use_rope", False, "the GQA layers rotate nothing"),
                ("kda_use_full_proj", False,
                 "the decay and the gate go through a low rank"),
                ("kda_allow_neg_eigval", True, "beta = 2 sigmoid(.)"),
                ("first_k_dense_replace", 0, "every layer is sparse"),
                ("norm_topk_prob", True,
                 "the chosen scores are normalised"),
                ("tie_word_embeddings", False, "an untied head")):
            if get(key, want) != want:
                raise NotImplementedError(
                    f"solar_open2 with {key} = {get(key)!r}: only {want!r} "
                    f"is built ({why})")
        lin = dict(get("linear_attn_config") or {})
        if lin.get("num_kv_heads") is not None:
            raise NotImplementedError(
                f"solar_open2 with linear_attn_config.num_kv_heads = "
                f"{lin['num_kv_heads']!r}: the KDA layer's keys and values "
                "have as many heads as its queries")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        kw.update(linear_num_heads=lin.get("num_heads"),
                  linear_head_dim=lin.get("head_dim"),
                  short_conv_kernel_size=lin.get("short_conv_kernel_size"))
        for key in ("gqa_layers", "held_experts"):
            if kw.get(key) is not None:
                kw[key] = tuple(kw[key])
        return cls(**{k: v for k, v in kw.items() if v is not None})

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    def kind(self, i: int) -> str:
        return "gqa" if i in self.gqa_layers else "kda"


def create_solar_open2_model(
        model, config: SolarOpen2Config,
        mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
        generation_config: Optional[GenerationConfig] = None,
        data_type: DataType = DataType.DT_FLOAT):
    """Record the Solar-Open2 decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"solar_open2 is served by incremental decoding only, not "
            f"{mode}: tree verification (speculation) and beam drafting "
            "stage tokens that may be rejected, and a layer that keeps a "
            "recurrent state (ops/kda_attention.py) has folded them into "
            "it by then")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")

    def norm(t, name):
        return model.rms_norm(t, eps=c.rms_norm_eps, dim=c.hidden_size,
                              name=name)

    qdim = c.num_attention_heads * c.head_dim
    for i in range(c.num_hidden_layers):
        ly = f"layers.{i}"
        x = norm(h, f"{ly}.input_layernorm")
        if c.kind(i) == "gqa":
            mix = model.inc_multiquery_self_attention(
                x, c.hidden_size, c.num_attention_heads,
                c.num_key_value_heads, kdim=qdim, vdim=qdim,
                data_type=data_type, apply_rotary_embedding=False,
                output_gate=c.use_gqa_gate, name=f"{ly}.self_attn")
        else:
            mix = model.inc_kda_attention(
                x, c.hidden_size, c.linear_num_heads, c.linear_head_dim,
                conv_kernel=c.short_conv_kernel_size, norm_eps=c.rms_norm_eps,
                data_type=data_type, name=f"{ly}.self_attn")
        h = model.add(h, mix)
        h = model.add(h, sparse_layer(
            model, norm(h, f"{ly}.post_attention_layernorm"), f"{ly}.mlp",
            c.n_routed_experts, c.num_experts_per_tok,
            c.routed_scaling_factor, c.moe_intermediate_size,
            c.n_shared_experts, c.hidden_size, c.held_experts, data_type))

    logits = model.dense(norm(h, "norm"), c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


def preprocess_hf_state_dict(sd, config: SolarOpen2Config):
    """Stack the HELD experts' ``[out, in]`` Linears into ``[count, in,
    out]`` (the others are dropped unread); join a KDA layer's three
    projections, its three first-half projections and its three Conv1d
    weights into the arrays the op holds."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    first, count = config.held
    for i in range(config.num_hidden_layers):
        stack_held_experts(sd, i, config.n_routed_experts, first, count)
        if config.kind(i) != "kda":
            continue
        p = f"model.layers.{i}.self_attn"
        for joined, parts in (("qkv_proj", [f"{s}_proj" for s in _QKV]),
                              ("low_proj", ["f_a_proj", "g_a_proj",
                                            "b_proj"])):
            keys = [f"{p}.{n}.weight" for n in parts]
            if all(k in sd for k in keys):
                sd[f"{p}.{joined}.weight"] = np.concatenate(
                    [_to_numpy(sd.pop(k)) for k in keys], axis=0)
        keys = [f"{p}.{s}_conv1d.weight" for s in _QKV]
        if all(k in sd for k in keys):      # [C, 1, taps] each
            sd[f"{p}.conv"] = np.concatenate(
                [_to_numpy(sd.pop(k))[:, 0, :].T for k in keys], axis=1)


def hf_weight_map(config: SolarOpen2Config):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has prepared."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i in range(config.num_hidden_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for key, (weight, transpose) in HF_KEYS[config.kind(i)].items():
            if key == "g_proj.weight" and not config.use_gqa_gate:
                continue
            m[f"{hf}.self_attn.{key}"] = (f"{ff}.self_attn", weight,
                                          transpose)
        for p in ("input_layernorm", "post_attention_layernorm"):
            m[f"{hf}.{p}.weight"] = (f"{ff}.{p}", "weight", False)
        m[f"{hf}.mlp.gate.weight"] = (f"{ff}.mlp.gate", "kernel", True)
        m[f"{hf}.mlp.gate.e_score_correction_bias"] = (
            f"{ff}.mlp.gate.e_score_correction_bias", "weight", False)
        for proj, w in _EXPERT_PROJ:
            m[f"{hf}.mlp.shared_experts.{proj}.weight"] = (
                f"{ff}.mlp.shared_experts.{proj}", "kernel", True)
            m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
    return m
