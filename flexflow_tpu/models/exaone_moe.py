"""EXAONE-MoE decoder for serving (HF ``model_type`` ``exaone_moe``:
LGAI-EXAONE/K-EXAONE-236B-A23B): windowed and full attention layers in one
model, a leading dense layer, then sparse layers with a shared expert.

The first family here whose layers are not all alike. What differs comes
from the configuration's per-layer lists, and each layer is built from what
its own entries say:

* ``layer_types[i]``: ``sliding_attention`` (rotary embedding, a query sees
  the last ``sliding_window`` positions, the layer keeps a ring of them:
  ops/kv_layout.py) or ``full_attention`` (causal over everything, NO
  rotary embedding). Both: grouped-query heads, RMSNorm over each head's
  ``head_dim`` on q and on k with a learned ``[head_dim]`` weight, before
  any rotation.
* ``mlp_layer_types[i]``: ``dense`` (SwiGLU of ``intermediate_size``) or
  ``sparse``: router over ``num_experts`` -> float32 sigmoid scores ->
  the ``num_experts_per_tok`` largest of ``score + e_score_correction_bias``
  -> the chosen experts' own scores, normalised over the chosen
  (``norm_topk_prob``) and times ``routed_scaling_factor`` -> routed SwiGLU
  experts of ``moe_intermediate_size``, plus ``num_shared_experts`` shared
  experts (one SwiGLU of their joint width) that every token goes through.
  The router is graph ops (``dense``, ``sigmoid``, ``parameter``, ``add``,
  ``top_k``, ``gather``, ``reduce_sum``, ``divide``, ``scalar_multiply``);
  the chosen indices and weights are graph values, as OLMoE's.
* The block is pre-norm: ``x += Attn(RMSNorm(x))``, ``x += F(RMSNorm(x))``.

Assumed, where ``config.json`` has no key (benchmark/reference/exaone_moe.py
has the same list): the pre-norm block (the DeepSeek-V3-style block whose
parameter names this family uses; EXAONE 4.0 normed each sublayer's output
instead), no rotary embedding on full layers (the family's model card:
"global attention: NoPE"), and the selection bias (DeepSeek-V3's
``e_score_correction_bias``, from ``scoring_func`` sigmoid with ``n_group``,
``topk_group`` and ``routed_scaling_factor``). ``n_group == topk_group ==
1`` is required: no group-limited routing is implemented, as in the family.
The multi-token-prediction layer (``num_nextn_predict_layers``) is no part
of the next-token forward and is not built or loaded.

``held_experts = (first, count)`` builds one chip's share of an
expert-parallel deployment: the sparse layers hold those routed experts of
the router's ``num_experts`` (ops/moe.MoeExperts) and add their part; the
shared expert, attention, router and dense layer are whole. A sliced
vocabulary is a smaller ``vocab_size``.

Layer names follow the HF checkpoint's (``layers.{i}.mlp.gate`` is the
router, ``layers.{i}.mlp.shared_experts.*`` the shared expert). HF keeps one
``nn.Linear`` per expert and projection; ``preprocess_hf_state_dict``
stacks the held range into the three ``[count, in, out]`` tensors the op
holds and reads no other expert.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig

_EXPERT_PROJ = (("gate_proj", "gate"), ("up_proj", "up"),
                ("down_proj", "down"))
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class ExaoneMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432          # the dense layers' MLP
    moe_intermediate_size: int = 2048       # one expert's width
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128                  # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    sliding_window: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    layer_types: Optional[List[str]] = None
    mlp_layer_types: Optional[List[str]] = None
    # this chip's routed experts (first, count); None: all of them
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:        # the family's "LLLG" period
            self.layer_types = [FULL if i % 4 == 3 else SLIDING
                                for i in range(L)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["dense"] + ["sparse"] * (L - 1)
        # a cut in depth keeps the leading layers
        self.layer_types = list(self.layer_types)[:L]
        self.mlp_layer_types = list(self.mlp_layer_types)[:L]
        assert len(self.layer_types) == len(self.mlp_layer_types) == L
        assert set(self.layer_types) <= {SLIDING, FULL}, self.layer_types
        assert set(self.mlp_layer_types) <= {"dense", "sparse"}

    @classmethod
    def from_hf_config(cls, hf) -> "ExaoneMoEConfig":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        if get("n_group", 1) != 1 or get("topk_group", 1) != 1:
            raise NotImplementedError(
                "exaone_moe with n_group or topk_group other than 1: no "
                "group-limited routing is built (the family asserts 1)")
        if get("scoring_func", "sigmoid") != "sigmoid" \
                or not get("norm_topk_prob", True):
            raise NotImplementedError(
                "exaone_moe routes on sigmoid scores normalised over the "
                "chosen experts; got scoring_func "
                f"{get('scoring_func')!r}, norm_topk_prob "
                f"{get('norm_topk_prob')!r}")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        rope = get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(f"rope_type {rope.get('rope_type')!r}")
        kw["rope_theta"] = rope.get("rope_theta", get("rope_theta"))
        kw = {k: v for k, v in kw.items() if v is not None}
        if "head_dim" not in kw and "hidden_size" in kw:
            kw["head_dim"] = kw["hidden_size"] // kw.get(
                "num_attention_heads", cls.num_attention_heads)
        if kw.get("held_experts") is not None:
            kw["held_experts"] = tuple(kw["held_experts"])
        return cls(**kw)

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.num_experts)


def _swiglu(model, x, width: int, hidden: int, data_type, prefix: str):
    gate = model.dense(x, width, use_bias=False, datatype=data_type,
                       name=f"{prefix}.gate_proj")
    up = model.dense(x, width, use_bias=False, datatype=data_type,
                     name=f"{prefix}.up_proj")
    return model.dense(model.sigmoid_silu_multi(gate, up), hidden,
                       use_bias=False, datatype=data_type,
                       name=f"{prefix}.down_proj")


def sparse_layer(model, x, p: str, num_experts: int, top_k: int,
                 scaling: float, expert_width: int, shared_experts: int,
                 hidden: int, held, data_type):
    """``Routed(x) + Shared(x)`` of the family's sparse layer, recorded
    under the checkpoint's names below ``p`` (``layers.{i}.mlp``): the
    router's graph ops, the held routed experts, the shared expert. Also
    what models/mistral4.py builds."""
    from flexflow_tpu.core.initializer import NormInitializer

    # float32 router logits (the gemm's accumulator), as OLMoE's: the
    # scores, the choice and the weights are made in float32
    logits = model.dense(x, num_experts, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name=f"{p}.gate")
    scores = model.sigmoid(logits, name=f"{p}.scores")
    # the checkpoint's per-expert selection bias: it moves the choice
    # and never the weight. Seeded non-zero, so that a test sees it
    bias = model.parameter(
        [num_experts], DataType.DT_FLOAT,
        initializer=NormInitializer(stddev=0.05),
        name=f"{p}.gate.e_score_correction_bias")
    _, chosen = model.top_k(model.add(scores, bias), top_k,
                            name=f"{p}.top_k")
    picked = model.gather(scores, chosen, dim=2, name=f"{p}.picked")
    total = model.scalar_add(
        model.reduce_sum(picked, [-1], keepdims=True), 1e-20)
    weights = model.scalar_multiply(model.divide(picked, total), scaling,
                                    name=f"{p}.weights")
    routed = model.moe_experts(
        x, chosen, weights, num_experts, expert_width,
        data_type=data_type, held=held, name=f"{p}.experts")
    shared = _swiglu(model, x, shared_experts * expert_width, hidden,
                     data_type, f"{p}.shared_experts")
    return model.add(routed, shared)


def create_exaone_moe_model(model, config: ExaoneMoEConfig,
                            mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                            generation_config: Optional[GenerationConfig] = None,
                            data_type: DataType = DataType.DT_FLOAT):
    """Record the EXAONE-MoE decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"exaone_moe is served by incremental decoding only, not {mode}: "
            "tree verification and beam drafting stage and move cache "
            "positions, which a windowed layer's ring (ops/kv_layout.py) "
            "does not keep")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    qdim = c.num_attention_heads * c.head_dim
    for i in range(c.num_hidden_layers):
        sliding = c.layer_types[i] == SLIDING
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.input_layernorm")
        attn = model.inc_multiquery_self_attention(
            x, c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            kdim=qdim, vdim=qdim, data_type=data_type,
            apply_rotary_embedding=sliding, rope_theta=c.rope_theta,
            qk_norm_eps=c.rms_norm_eps, qk_norm_per_head=True,
            sliding_window=c.sliding_window if sliding else None,
            name=f"layers.{i}.self_attn")
        h = model.add(h, attn)
        x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                           name=f"layers.{i}.post_attention_layernorm")
        p = f"layers.{i}.mlp"
        if c.mlp_layer_types[i] == "dense":
            h = model.add(h, _swiglu(model, x, c.intermediate_size,
                                     c.hidden_size, data_type, p))
            continue
        h = model.add(h, sparse_layer(
            model, x, p, c.num_experts, c.num_experts_per_tok,
            c.routed_scaling_factor, c.moe_intermediate_size,
            c.num_shared_experts, c.hidden_size, c.held_experts, data_type))

    x = model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size, name="norm")
    logits = model.dense(x, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


def _experts_key(i: int, proj: str) -> str:
    """The stacked tensor's name in a preprocessed state dict (no such key
    exists in an HF checkpoint)."""
    return f"model.layers.{i}.mlp.experts.{proj}.weight"


def stack_held_experts(sd, i: int, num_experts: int, first: int, count: int):
    """Layer ``i``'s HELD experts' ``[out, in]`` Linears stacked into
    ``[count, in, out]`` under ``_experts_key``; every expert's own entries
    are dropped, the others' unread."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    for proj, _ in _EXPERT_PROJ:
        keys = [f"model.layers.{i}.mlp.experts.{e}.{proj}.weight"
                for e in range(num_experts)]
        held = keys[first:first + count]
        if all(k in sd for k in held):
            sd[_experts_key(i, proj)] = np.stack(
                [_to_numpy(sd[k]).T for k in held])
        for k in keys:
            sd.pop(k, None)


def preprocess_hf_state_dict(sd, config: ExaoneMoEConfig):
    """Stack the HELD experts' ``[out, in]`` Linears into ``[count, in,
    out]``; the other experts' entries are dropped unread."""
    first, count = config.held
    for i, kind in enumerate(config.mlp_layer_types):
        if kind != "sparse":
            continue
        stack_held_experts(sd, i, config.num_experts, first, count)
    for k in [k for k in sd if k.startswith("mtp.") or ".mtp." in k]:
        del sd[k]                   # the prediction head is not loaded


def hf_weight_map(config: ExaoneMoEConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has stacked."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i, kind in enumerate(config.mlp_layer_types):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for p, w in (("q_proj", "wq"), ("k_proj", "wk"),
                     ("v_proj", "wv"), ("o_proj", "wo")):
            m[f"{hf}.self_attn.{p}.weight"] = (f"{ff}.self_attn", w, True)
        for p in ("q_norm", "k_norm"):
            m[f"{hf}.self_attn.{p}.weight"] = (f"{ff}.self_attn", p, False)
        for p in ("input_layernorm", "post_attention_layernorm"):
            m[f"{hf}.{p}.weight"] = (f"{ff}.{p}", "weight", False)
        sub = "" if kind == "dense" else ".shared_experts"
        for proj, _ in _EXPERT_PROJ:
            m[f"{hf}.mlp{sub}.{proj}.weight"] = (
                f"{ff}.mlp{sub}.{proj}", "kernel", True)
        if kind == "sparse":
            m[f"{hf}.mlp.gate.weight"] = (f"{ff}.mlp.gate", "kernel", True)
            m[f"{hf}.mlp.gate.e_score_correction_bias"] = (
                f"{ff}.mlp.gate.e_score_correction_bias", "weight", False)
            for proj, w in _EXPERT_PROJ:
                m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
    return m
