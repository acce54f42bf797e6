"""Mistral-4 decoder for serving (HF ``model_type`` ``mistral4``:
mistralai/Mistral-Small-4-119B-2603): multi-head latent attention in every
layer, then a sparse layer with a shared expert.

* Attention (ops/latent_attention.py): queries through a low-rank pair
  ``q_a_proj`` -> RMSNorm -> ``q_b_proj``; keys and values through
  ``kv_a_proj_with_mqa`` -> RMSNorm over the ``kv_lora_rank`` latent ->
  ``kv_b_proj``. A head's query and key are ``qk_nope_head_dim`` values that
  are never rotated and ``qk_rope_head_dim`` that are; the rotated key part
  is ONE for all heads. The layer caches the normed latent and the rotated
  key part and attends in the absorbed form; ``kv_b_proj`` is split into its
  key and value halves when it is loaded.
* Rotary: YaRN over the rope dims (``yarn_inv_freq``: a frequency table,
  data to the op), a checkpoint's ADJACENT pairing turned into the op's
  rotate-half by permuting the rope columns of ``q_b_proj`` and
  ``kv_a_proj_with_mqa`` at load (``rope_permutation``). The softmax scale
  is ``qk_head_dim ** -0.5 * m(mscale_all_dim) ** 2`` with ``m(t) = 0.1 t
  ln(factor) + 1``; cos and sin are times ``m(mscale) / m(mscale_all_dim)``.
  ``llama_4_scaling_beta``: a query is scaled by ``1 + beta * ln(1 +
  floor(p / original_max_position_embeddings))`` of its own position.
* Experts: as models/exaone_moe.py (whose router graph and shared expert
  this builder reuses): float32 router logits, sigmoid, the
  ``num_experts_per_tok`` largest of ``score + e_score_correction_bias``,
  their own scores normalised over the chosen, times
  ``routed_scaling_factor``; routed SwiGLU experts of
  ``moe_intermediate_size`` plus ``n_shared_experts`` shared ones.
  ``first_k_dense_replace`` leading layers are a dense SwiGLU instead.
* Pre-norm block; no bias anywhere.

Assumed, where ``config.json`` has no key (benchmark/reference/mistral4.py
has the same list), from the family whose keys it uses (DeepseekV3Config):
sigmoid scores with a selection bias, the softmax scale's ``mscale ** 2``,
the pre-norm block, and the position scale multiplying the query after its
rotation. ``n_group == topk_group == 1`` is required. The vision tower is no
part of the text forward and is neither built nor loaded.

``held_experts = (first, count)`` builds one chip's share of an
expert-parallel deployment, as in models/exaone_moe.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.models.exaone_moe import (_EXPERT_PROJ, _experts_key,
                                            _swiglu, sparse_layer,
                                            stack_held_experts)
from flexflow_tpu.serve.batch_config import GenerationConfig


def _mscale(factor: float, t: float) -> float:
    return 0.1 * t * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, rope: dict) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of ``rope_parameters``: theta's
    own (``rope_type`` default), or YaRN's blend of them with theirs over
    ``factor``, by where each lies between the ``beta_fast`` and
    ``beta_slow`` rotations of the original context."""
    theta = float(rope.get("rope_theta", 10000.0))
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", rope.get("type", "default")) == "default":
        return base
    factor = float(rope["factor"])
    L0 = rope["original_max_position_embeddings"]

    def turns(b):       # the dim whose wave turns b times over L0 positions
        return dim * math.log(L0 / (b * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(turns(rope.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (1 - ramp) * base + ramp * base / factor


def rope_permutation(dim: int) -> np.ndarray:
    """Column order that turns ADJACENT rotary pairs ``(2i, 2i+1)`` into
    the halves ``(i, i + dim/2)`` that ``apply_rotary`` rotates."""
    return np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])


@dataclasses.dataclass
class Mistral4Config:
    vocab_size: int = 131072
    hidden_size: int = 4096
    intermediate_size: int = 12288          # a dense layer's MLP
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128             # the router's width
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_interleave: bool = True
    max_position_embeddings: int = 1048576
    rope_parameters: Optional[dict] = None
    # this chip's routed experts (first, count); None: all of them
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.rope_parameters is None:
            self.rope_parameters = {"rope_type": "default",
                                    "rope_theta": 10000.0}

    @classmethod
    def from_hf_config(cls, hf) -> "Mistral4Config":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        if get("n_group", 1) != 1 or get("topk_group", 1) != 1:
            raise NotImplementedError(
                "mistral4 with n_group or topk_group other than 1: no "
                "group-limited routing is built")
        if not get("norm_topk_prob", True):
            raise NotImplementedError(
                "mistral4 routes on sigmoid scores normalised over the "
                "chosen experts; got norm_topk_prob false")
        if get("q_lora_rank") is None:
            raise NotImplementedError(
                "mistral4 without q_lora_rank: the full-rank query "
                "projection is not built")
        rope = dict(get("rope_parameters") or {})
        kind = rope.get("rope_type", rope.get("type", "default"))
        if kind not in ("yarn", "default"):
            raise NotImplementedError(
                f"rope_type {kind!r}: mistral4 builds 'yarn' and 'default'")
        rope.setdefault("rope_theta", get("rope_theta", 10000.0))
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        kw["rope_parameters"] = rope
        kw = {k: v for k, v in kw.items() if v is not None}
        if kw.get("held_experts") is not None:
            kw["held_experts"] = tuple(kw["held_experts"])
        return cls(**kw)

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    def rotary(self) -> dict:
        """What the attention op is told of ``rope_parameters``: the
        frequency table, the factor on cos and sin, the softmax scale, the
        position scale."""
        rope = self.rope_parameters
        yarn = rope.get("rope_type", rope.get("type", "default")) == "yarn"
        factor = float(rope.get("factor", 1.0)) if yarn else 1.0
        m_all = _mscale(factor, rope.get("mscale_all_dim", 0.0) or 0.0)
        return dict(
            rope_inv_freq=yarn_inv_freq(self.qk_rope_head_dim, rope),
            rope_theta=float(rope["rope_theta"]),
            rope_factor=_mscale(factor, rope.get("mscale", 1.0)) / m_all,
            softmax_scale=(self.qk_nope_head_dim + self.qk_rope_head_dim)
            ** -0.5 * m_all ** 2,
            pos_scale_beta=float(rope.get("llama_4_scaling_beta", 0.0)),
            pos_scale_period=int(rope.get(
                "original_max_position_embeddings",
                self.max_position_embeddings)))


def create_mistral4_model(model, config: Mistral4Config,
                          mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                          generation_config: Optional[GenerationConfig] = None,
                          data_type: DataType = DataType.DT_FLOAT):
    """Record the Mistral-4 decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"mistral4 is served by incremental decoding only, not {mode}: "
            "tree verification and beam drafting stage and move cache "
            "positions as a k/v pair, and a latent layer "
            "(ops/latent_attention.py) keeps one shared entry a position")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    rotary = c.rotary()
    for i in range(c.num_hidden_layers):
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.input_layernorm")
        attn = model.inc_multihead_latent_attention(
            x, c.hidden_size, c.num_attention_heads, c.q_lora_rank,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, norm_eps=c.rms_norm_eps, data_type=data_type,
            name=f"layers.{i}.self_attn", **rotary)
        h = model.add(h, attn)
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.post_attention_layernorm")
        p = f"layers.{i}.mlp"
        if i < c.first_k_dense_replace:
            h = model.add(h, _swiglu(model, x, c.intermediate_size,
                                     c.hidden_size, data_type, p))
            continue
        h = model.add(h, sparse_layer(
            model, x, p, c.n_routed_experts, c.num_experts_per_tok,
            c.routed_scaling_factor, c.moe_intermediate_size,
            c.n_shared_experts, c.hidden_size, c.held_experts, data_type))

    x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size, name="norm")
    logits = model.dense(x, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


_TEXT = "language_model."   # a multimodal checkpoint's text tower


def preprocess_hf_state_dict(sd, config: Mistral4Config):
    """Drop the vision tower unread; stack the HELD experts' ``[out, in]``
    Linears into ``[count, in, out]`` (the others dropped unread); split
    ``kv_b_proj`` into its key and value halves, a head apart; permute the
    rope columns of ``q_b_proj`` and ``kv_a_proj_with_mqa`` from the
    checkpoint's adjacent pairing to the op's halves."""
    c = config
    for k in [k for k in sd if "vision_tower" in k or "vision_encoder" in k
              or "multi_modal_projector" in k or "patch_merger" in k]:
        del sd[k]
    for k in [k for k in sd if _TEXT in k]:
        sd[k.replace(_TEXT, "")] = sd.pop(k)
    first, count = c.held
    perm = (rope_permutation(c.qk_rope_head_dim) if c.rope_interleave
            else np.arange(c.qk_rope_head_dim))
    for i in range(c.num_hidden_layers):
        prepare_latent_attention(sd, f"model.layers.{i}.self_attn", c, perm)
        if i < c.first_k_dense_replace:
            continue
        stack_held_experts(sd, i, c.n_routed_experts, first, count)


def prepare_latent_attention(sd, a: str, c, perm, latent_scale: float = 1.0):
    """One latent attention's entries under the checkpoint prefix ``a``
    (``c`` has the six MLA sizes): ``kv_b_proj`` split into its key and
    value halves, a head apart; the rope columns of ``q_b_proj`` and
    ``kv_a_proj_with_mqa`` reordered by ``perm``; ``kv_a_layernorm`` times
    ``latent_scale`` (a model that multiplies the normed latent by a
    constant before ``kv_b_proj``: ``RMSNorm(c) * w * s`` exactly). Also
    what models/longcat_flash.py prepares, twice a layer."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                     c.qk_rope_head_dim, c.v_head_dim)
    rank = c.kv_lora_rank
    if f"{a}.kv_b_proj.weight" in sd:       # [H * (dn + dv), rank]
        w = _to_numpy(sd.pop(f"{a}.kv_b_proj.weight")).reshape(
            H, dn + dv, rank)
        sd[f"{a}.kv_b_proj.key"] = w[:, :dn].transpose(0, 2, 1)
        sd[f"{a}.kv_b_proj.value"] = w[:, dn:].transpose(0, 2, 1)
    if f"{a}.q_b_proj.weight" in sd:        # [H * (dn + dr), q_rank]
        w = _to_numpy(sd[f"{a}.q_b_proj.weight"])
        w = w.reshape(H, dn + dr, -1)
        sd[f"{a}.q_b_proj.weight"] = np.concatenate(
            [w[:, :dn], w[:, dn:][:, perm]], axis=1).reshape(
                H * (dn + dr), -1)
    if f"{a}.kv_a_proj_with_mqa.weight" in sd:   # [rank + dr, hidden]
        w = _to_numpy(sd[f"{a}.kv_a_proj_with_mqa.weight"])
        sd[f"{a}.kv_a_proj_with_mqa.weight"] = np.concatenate(
            [w[:rank], w[rank:][perm]], axis=0)
    if latent_scale != 1.0 and f"{a}.kv_a_layernorm.weight" in sd:
        sd[f"{a}.kv_a_layernorm.weight"] = _to_numpy(
            sd[f"{a}.kv_a_layernorm.weight"]) * np.float32(latent_scale)


def latent_attention_map(hf: str, ff: str) -> dict:
    """HF key -> (layer_name, weight_name, transpose?) of one latent
    attention whose checkpoint prefix is ``hf`` and whose layer is ``ff``,
    over entries ``prepare_latent_attention`` has prepared."""
    m = {f"{hf}.{p}.weight": (ff, w, True)
         for p, w in (("q_a_proj", "wq_a"), ("q_b_proj", "wq_b"),
                      ("kv_a_proj_with_mqa", "wkv_a"), ("o_proj", "wo"))}
    m[f"{hf}.q_a_layernorm.weight"] = (ff, "q_norm", False)
    m[f"{hf}.kv_a_layernorm.weight"] = (ff, "kv_norm", False)
    m[f"{hf}.kv_b_proj.key"] = (ff, "wk_b", False)
    m[f"{hf}.kv_b_proj.value"] = (ff, "wv_b", False)
    return m


def hf_weight_map(config: Mistral4Config):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has prepared."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i in range(config.num_hidden_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        m.update(latent_attention_map(f"{hf}.self_attn", f"{ff}.self_attn"))
        for p in ("input_layernorm", "post_attention_layernorm"):
            m[f"{hf}.{p}.weight"] = (f"{ff}.{p}", "weight", False)
        dense = i < config.first_k_dense_replace
        sub = "" if dense else ".shared_experts"
        for proj, _ in _EXPERT_PROJ:
            m[f"{hf}.mlp{sub}.{proj}.weight"] = (
                f"{ff}.mlp{sub}.{proj}", "kernel", True)
        if not dense:
            m[f"{hf}.mlp.gate.weight"] = (f"{ff}.mlp.gate", "kernel", True)
            m[f"{hf}.mlp.gate.e_score_correction_bias"] = (
                f"{ff}.mlp.gate.e_score_correction_bias", "weight", False)
            for proj, w in _EXPERT_PROJ:
                m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
    return m
