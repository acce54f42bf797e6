"""OLMoE decoder for serving: sparse experts on the normal path.

The reference zoo (inference/models/) has no expert model; this family
follows HF ``modeling_olmoe.py`` (allenai/OLMoE-1B-7B): embedding -> N x
(RMSNorm -> attention with q/k RMSNorm over the whole projection and rotary
embedding -> residual -> RMSNorm -> router -> float32 softmax over all
experts -> top-k, not renormalised (``norm_topk_prob`` false) -> routed
SwiGLU experts -> residual) -> final RMSNorm -> untied lm_head. No shared
expert, no capacity, no dropped token.

The expert layer is ops in the model graph (router ``dense``, ``softmax``,
``top_k``, ``moe_experts``) where the LLaMA builder has its MLP, so every
generate loop and engine runs it as it runs any model; the top-k values and
indices are graph values.

Layer names follow the HF checkpoint (``layers.{i}.mlp.gate`` is the
router). HF keeps one ``nn.Linear`` per expert and projection;
``preprocess_hf_state_dict`` stacks them into the three ``[E, in, out]``
tensors the expert op holds, and ``unstack_hf_experts`` is the inverse for
``checkpoint_store.export_hf_state_dict``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig

_EXPERT_PROJ = (("gate_proj", "gate"), ("up_proj", "up"),
                ("down_proj", "down"))


@dataclasses.dataclass
class OLMoEConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024       # ONE expert's width (HF OlmoeMLP)
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096

    @classmethod
    def from_hf_config(cls, hf) -> "OLMoEConfig":
        """Accepts a transformers OlmoeConfig or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        if get("clip_qkv") is not None or get("norm_topk_prob"):
            raise NotImplementedError(
                "OLMoE with clip_qkv or norm_topk_prob set: the graph below "
                "neither clamps q/k/v nor renormalises the chosen experts' "
                "weights (OLMoE-1B-7B publishes null and false; "
                "models/sdar_moe.py is the family that renormalises)")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in kw.items() if v is not None}
        kw.setdefault("num_key_value_heads",
                      kw.get("num_attention_heads", cls.num_attention_heads))
        return cls(**kw)


def create_olmoe_model(model, config: OLMoEConfig,
                       mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                       generation_config: Optional[GenerationConfig] = None,
                       data_type: DataType = DataType.DT_FLOAT):
    """Record the OLMoE decoder graph into ``model`` (an FFModel)."""
    c = config
    ffc = model.config
    R = ffc.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic

    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    if mode == InferenceMode.TREE_VERIFY_MODE:
        attn_builder = model.tree_inc_multiquery_self_attention
    elif mode == InferenceMode.BEAM_SEARCH_MODE:
        attn_builder = model.spec_inc_multiquery_self_attention
    else:
        attn_builder = model.inc_multiquery_self_attention

    for i in range(c.num_hidden_layers):
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.input_layernorm")
        attn = attn_builder(
            x, c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            data_type=data_type, apply_rotary_embedding=True,
            rope_theta=c.rope_theta, qk_norm_eps=c.rms_norm_eps,
            name=f"layers.{i}.self_attn")
        h = model.add(h, attn)
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.post_attention_layernorm")
        # float32 router logits (the head's keep_f32_logits): the softmax
        # and the choice of experts are made on the gemm's accumulator
        router = model.dense(x, c.num_experts, use_bias=False,
                             datatype=data_type, keep_f32_logits=True,
                             name=f"layers.{i}.mlp.gate")
        probs = model.softmax(router, name=f"layers.{i}.mlp.softmax")
        weights, chosen = model.top_k(probs, c.num_experts_per_tok,
                                      name=f"layers.{i}.mlp.top_k")
        experts = model.moe_experts(
            x, chosen, weights, c.num_experts, c.intermediate_size,
            data_type=data_type, name=f"layers.{i}.mlp.experts")
        h = model.add(h, experts)

    x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size, name="norm")
    logits = model.dense(x, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample and mode == InferenceMode.INC_DECODING_MODE:
        out = model.sampling(logits, top_p=gen.topp, temperature=gen.temperature)
    elif (mode == InferenceMode.BEAM_SEARCH_MODE
          and ffc.max_beam_width > 1):
        # per-node top-k (prob, id) pairs in ONE tensor, as the LLaMA
        # builder packs them
        w = ffc.max_beam_width
        probs, ids = model.arg_top_k(logits, k=w, speculative_decoding=True)
        out = model.concat([probs, model.cast(ids, DataType.DT_FLOAT)],
                           axis=-1)
    else:
        out = model.argmax(logits)
    return out


def _experts_key(i: int, proj: str) -> str:
    """The stacked tensor's name in a preprocessed state dict (no such key
    exists in an HF checkpoint)."""
    return f"model.layers.{i}.mlp.experts.{proj}.weight"


def preprocess_hf_state_dict(sd, config: OLMoEConfig):
    """Stack HF's per-expert ``[out, in]`` Linears into ``[E, in, out]``."""
    from flexflow_tpu.models.hf_utils import _to_numpy, tie_lm_head

    tie_lm_head(sd, "model.embed_tokens.weight")
    for i in range(config.num_hidden_layers):
        for proj, _ in _EXPERT_PROJ:
            keys = [f"model.layers.{i}.mlp.experts.{e}.{proj}.weight"
                    for e in range(config.num_experts)]
            if all(k in sd for k in keys):
                sd[_experts_key(i, proj)] = np.stack(
                    [_to_numpy(sd.pop(k)).T for k in keys])


def unstack_hf_experts(sd, config: OLMoEConfig):
    """Inverse of the stacking above, for the checkpoint writer."""
    for i in range(config.num_hidden_layers):
        for proj, _ in _EXPERT_PROJ:
            stack = sd.pop(_experts_key(i, proj), None)
            if stack is None:
                continue
            for e in range(config.num_experts):
                sd[f"model.layers.{i}.mlp.experts.{e}.{proj}.weight"] = \
                    np.ascontiguousarray(stack[e].T)


def hf_weight_map(config: OLMoEConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has stacked."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i in range(config.num_hidden_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for p, w in (("q_proj", "wq"), ("k_proj", "wk"),
                     ("v_proj", "wv"), ("o_proj", "wo")):
            m[f"{hf}.self_attn.{p}.weight"] = (f"{ff}.self_attn", w, True)
        for p in ("q_norm", "k_norm"):
            m[f"{hf}.self_attn.{p}.weight"] = (f"{ff}.self_attn", p, False)
        m[f"{hf}.mlp.gate.weight"] = (f"{ff}.mlp.gate", "kernel", True)
        for proj, w in _EXPERT_PROJ:
            m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
        m[f"{hf}.input_layernorm.weight"] = (
            f"{ff}.input_layernorm", "weight", False)
        m[f"{hf}.post_attention_layernorm.weight"] = (
            f"{ff}.post_attention_layernorm", "weight", False)
    return m
