"""SDAR-MoE decoder for serving: sparse experts, generation by diffusion
over blocks.

Follows the published ``modeling_sdar_moe.py`` and ``generate.py`` of
JetLM/SDAR-30B-A3B-Chat (a Qwen3-MoE body): embedding -> N x (RMSNorm ->
GQA attention whose q and k are RMS-normalised over each HEAD before the
rotary embedding -> residual -> RMSNorm -> router -> float32 softmax over
all experts -> top-k, renormalised over the chosen (``norm_topk_prob``) ->
routed SwiGLU experts -> residual) -> final RMSNorm -> untied lm_head. No
shared expert, no capacity, no dropped token, no bias.

What sets the family apart is how tokens come out. With ``B`` the block
length, key ``j`` is visible to query ``i`` iff ``j // B <= i // B``: causal
across blocks, both ways inside one (``block_length`` on the attention
layers). The head hands back the greedy pick AND its float32 probability at
every position, unshifted: at a masked position they are the distribution of
the token AT it. The serving stack fills a row's next block from them
(serve/batch_config.BlockDiffusion; serve/engine._diffusion_block): the
block length, the schedule, the threshold and the mask id are the model's
own, live on this config and reach the stack as ``FFModel.block_diffusion``.

The expert layer is graph ops as in models/olmoe.py, plus the
renormalisation of the chosen experts' weights. Layer names follow the HF
checkpoint; the per-expert Linears are stacked as OLMoE's are.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.models import olmoe as _olmoe
from flexflow_tpu.serve.batch_config import BlockDiffusion, GenerationConfig


@dataclasses.dataclass
class SDARMoEConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768    # ONE expert's width
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    # generation (generate.py's arguments; the released -Chat checkpoint's
    # defaults): not in config.json, so a caller sets them here
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9       # remasking low_confidence_dynamic
    mask_token_id: int = 151669

    @classmethod
    def from_hf_config(cls, hf) -> "SDARMoEConfig":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        refused = {
            "a sliding window": (get("use_sliding_window")
                                 or get("sliding_window") is not None),
            "mlp_only_layers": bool(get("mlp_only_layers")),
            "decoder_sparse_step other than 1":
                get("decoder_sparse_step", 1) != 1,
            "rope_scaling": get("rope_scaling") is not None,
            "norm_topk_prob false": not get("norm_topk_prob", True),
            "attention_bias": bool(get("attention_bias")),
        }
        if any(refused.values()):
            raise NotImplementedError(
                "sdar_moe with " + ", ".join(k for k, v in refused.items()
                                             if v)
                + ": the graph below computes every layer sparse, full "
                "attention at the true rotary positions without bias, and "
                "the chosen experts' weights renormalised (SDAR-30B-A3B-"
                "Chat publishes none of these)")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in kw.items() if v is not None}
        if "head_dim" not in kw and "hidden_size" in kw:
            kw["head_dim"] = kw["hidden_size"] // kw.get(
                "num_attention_heads", cls.num_attention_heads)
        return cls(**kw)

    @property
    def diffusion(self) -> BlockDiffusion:
        return BlockDiffusion(self.block_length, self.denoising_steps,
                              float(self.confidence_threshold),
                              self.mask_token_id)

    @property
    def intermediate_size(self) -> int:
        """One expert's width under OLMoE's name for it (the shared
        checkpoint maps below)."""
        return self.moe_intermediate_size


def create_sdar_moe_model(model, config: SDARMoEConfig,
                          mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                          generation_config: Optional[GenerationConfig] = None,
                          data_type: DataType = DataType.DT_FLOAT):
    """Record the SDAR-MoE decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"sdar_moe is served by incremental decoding only, not {mode}: "
            "tree verification and beam drafting read a cache position as "
            "causal and a step as one token, and a block-diffusion model's "
            "step fills a block that sees itself both ways")
    if (generation_config or GenerationConfig()).do_sample:
        raise NotImplementedError(
            "sdar_moe unmasks greedily (the pick and its probability); "
            "sampling the picks is not built")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    qdim = c.num_attention_heads * c.head_dim
    for i in range(c.num_hidden_layers):
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.input_layernorm")
        attn = model.inc_multiquery_self_attention(
            x, c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            kdim=qdim, vdim=qdim, data_type=data_type,
            apply_rotary_embedding=True, rope_theta=c.rope_theta,
            qk_norm_eps=c.rms_norm_eps, qk_norm_per_head=True,
            block_length=c.block_length, name=f"layers.{i}.self_attn")
        h = model.add(h, attn)
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.post_attention_layernorm")
        p = f"layers.{i}.mlp"
        # float32 router logits (the gemm's accumulator), as OLMoE's
        router = model.dense(x, c.num_experts, use_bias=False,
                             datatype=data_type, keep_f32_logits=True,
                             name=f"{p}.gate")
        probs = model.softmax(router, name=f"{p}.softmax")
        picked, chosen = model.top_k(probs, c.num_experts_per_tok,
                                     name=f"{p}.top_k")
        weights = model.divide(
            picked, model.reduce_sum(picked, [-1], keepdims=True),
            name=f"{p}.weights")
        experts = model.moe_experts(
            x, chosen, weights, c.num_experts, c.moe_intermediate_size,
            data_type=data_type, name=f"{p}.experts")
        h = model.add(h, experts)

    x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size, name="norm")
    logits = model.dense(x, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    return model.unmasking_head(logits, c.diffusion)


# The checkpoint's names are OLMoE's (model.layers.{i}.self_attn.{q,k}_norm,
# mlp.gate, mlp.experts.{e}.{gate,up,down}_proj): its maps serve, the q/k
# norms being one head wide here, which a map does not see.
preprocess_hf_state_dict = _olmoe.preprocess_hf_state_dict
unstack_hf_experts = _olmoe.unstack_hf_experts
hf_weight_map = _olmoe.hf_weight_map
