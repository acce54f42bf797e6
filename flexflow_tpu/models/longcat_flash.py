"""LongCat-Flash decoder for serving (HF ``model_type`` ``longcat_flash``:
meituan-longcat/LongCat-Flash-Omni's language model): two latent-attention
sublayers a layer, the routed experts on a shortcut beside them, and a
router some of whose outputs are experts that cost nothing.

One layer, with ``N`` an RMSNorm of its own, ``A0``/``A1`` two latent
attentions, ``F0``/``F1`` two dense SwiGLU of ``ffn_hidden_size`` and ``M``
the routed layer:

    h1 = h  + A0(N(h));   x = N(h1);   m = M(x)
    h2 = h1 + F0(x)
    h3 = h2 + A1(N(h2))
    h' = h3 + F1(N(h3)) + m

The routed branch reads the FIRST sublayer's post-attention norm and rejoins
after the SECOND sublayer's dense FFN (the shortcut: in a deployment its
exchange between chips overlaps the second sublayer).

* ``M(x)``: float32 logits ``x W_r`` over ``n_routed_experts +
  zero_expert_num`` outputs, softmax over all of them, the ``moe_topk``
  largest of ``score + e_score_correction_bias``, weights = the chosen
  ones' own softmax scores times ``routed_scaling_factor``, NOT
  renormalised; ``sum_j w_j E_j(x)`` with ``E_j`` a SwiGLU of
  ``expert_ffn_hidden_size`` for ``j < n_routed_experts`` and ``E_j(x) = x``
  (``zero_expert_type`` identity) for the others (ops/moe.MoeExperts
  ``zero_experts``). The router is graph ops, as the other expert
  families': ``dense`` -> ``softmax`` -> ``add(bias)`` -> ``top_k`` ->
  ``gather`` -> ``scalar_multiply``.
* Attention (ops/latent_attention.py), as models/mistral4.py's without YaRN
  or a position scale, plus ``mla_scale_q_lora``: both query parts times
  ``sqrt(hidden_size / q_lora_rank)``, and ``mla_scale_kv_lora``: the normed
  latent times ``sqrt(hidden_size / kv_lora_rank)`` before ``kv_b_proj``.
  Neither needs arithmetic of its own: the query's factor multiplies every
  score, so it is in the op's ``softmax_scale``; the latent's is in the
  ``kv_a_layernorm`` weight (``RMSNorm(c) * w * s``), where
  ``preprocess_hf_state_dict`` folds it. A SEEDED latent norm starts at 1
  in that folded form (a published weight of ``1 / s``): the factor is
  there to undo what a fixed-width initialiser loses through the low-rank
  pair, which the program's variance-keeping initialisers do not lose, and
  ``s`` times a unit latent gives scores only an arg-max survives.
* Pre-norm block; no bias anywhere; SiLU.

Assumed, where ``config.json`` has no key (benchmark/reference/
longcat_flash.py has the same list): SiLU, the pre-norm block, no bias,
rotary over ADJACENT pairs (turned into the op's halves by permuting the
rope columns at load, models/mistral4.rope_permutation), no renormalising of
the chosen weights (there is no ``norm_topk_prob``), the two ``mla_scale_*``
factors being ``sqrt(hidden_size / rank)`` and sitting where the equations
above put them. The audio and vision encoders and the codec decoder of the
Omni model are no part of the language model's forward and are neither
built nor loaded.

``held_experts = (first, count)`` builds one chip's share of an
expert-parallel deployment, as in models/mistral4.py: the routed SwiGLU
experts ``[first, first + count)``. The zero experts live on no chip and on
every chip: each adds ``w * x`` for its own tokens.

Layer names follow the checkpoint's: ``layers.{i}.self_attn.{0,1}``,
``layers.{i}.mlps.{0,1}``, ``layers.{i}.input_layernorm.{0,1}``,
``layers.{i}.post_attention_layernorm.{0,1}``,
``layers.{i}.mlp.router.classifier`` (the router),
``layers.{i}.mlp.experts``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.models.exaone_moe import (_EXPERT_PROJ, _experts_key,
                                            _swiglu, stack_held_experts)
from flexflow_tpu.models.mistral4 import (latent_attention_map,
                                          prepare_latent_attention,
                                          rope_permutation, yarn_inv_freq)
from flexflow_tpu.serve.batch_config import GenerationConfig


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288            # a dense sublayer's MLP
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28                    # each of two sublayers
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512             # the SwiGLU experts
    zero_expert_num: int = 256              # router outputs past them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    # this chip's routed experts (first, count); None: all of them
    held_experts: Optional[Tuple[int, int]] = None
    # seeded weights only (no key of the source): the router's initialiser
    # (None: the program's default)
    router_init_std: Optional[float] = None

    @classmethod
    def from_hf_config(cls, hf) -> "LongcatFlashConfig":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        if get("zero_expert_type", "identity") != "identity":
            raise NotImplementedError(
                f"zero_expert_type {get('zero_expert_type')!r}: only the "
                "identity is built (a zero expert adds w * x)")
        if get("attention_method", "MLA") != "MLA":
            raise NotImplementedError(
                f"attention_method {get('attention_method')!r}: longcat_flash "
                "is built over latent attention (MLA) only")
        if get("router_bias", False):
            raise NotImplementedError(
                "longcat_flash with router_bias: a bias on the router's "
                "logits is not built (the selection bias "
                "e_score_correction_bias is)")
        if get("attention_bias", False):
            raise NotImplementedError("longcat_flash with attention_bias")
        if get("q_lora_rank") is None:
            raise NotImplementedError(
                "longcat_flash without q_lora_rank: the full-rank query "
                "projection is not built")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in kw.items() if v is not None}
        if kw.get("held_experts") is not None:
            kw["held_experts"] = tuple(kw["held_experts"])
        return cls(**kw)

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def latent_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)

    @property
    def bias_std(self) -> float:
        """The seeded selection bias's spread: a quarter of the mean softmax
        score over the router's width, so that it moves some picks and not
        most (the other families' N(0, 0.05) is forty times a score here and
        would choose alone)."""
        return 0.25 / self.router_width


def routed_branch(model, x, p: str, c: LongcatFlashConfig, data_type):
    """``M(x)`` recorded under the checkpoint's names below ``p``
    (``layers.{i}.mlp``)."""
    from flexflow_tpu.core.initializer import NormInitializer

    width = c.router_width
    # float32 router logits (the gemm's accumulator), as the other expert
    # families': the scores, the choice and the weights are made in float32
    logits = model.dense(
        x, width, use_bias=False, datatype=data_type, keep_f32_logits=True,
        kernel_initializer=(None if c.router_init_std is None
                            else NormInitializer(stddev=c.router_init_std)),
        name=f"{p}.router.classifier")
    scores = model.softmax(logits, name=f"{p}.router.scores")
    # the checkpoint's selection bias: it moves the choice and never the
    # weight. Seeded non-zero, so that a test sees it
    bias = model.parameter(
        [width], DataType.DT_FLOAT,
        initializer=NormInitializer(stddev=c.bias_std),
        name=f"{p}.router.e_score_correction_bias")
    _, chosen = model.top_k(model.add(scores, bias), c.moe_topk,
                            name=f"{p}.router.top_k")
    picked = model.gather(scores, chosen, dim=2, name=f"{p}.router.picked")
    weights = model.scalar_multiply(picked, c.routed_scaling_factor,
                                    name=f"{p}.router.weights")
    return model.moe_experts(
        x, chosen, weights, width, c.expert_ffn_hidden_size,
        data_type=data_type, held=c.held,
        zero_experts=((c.n_routed_experts, c.zero_expert_num)
                      if c.zero_expert_num else None),
        name=f"{p}.experts")


def create_longcat_flash_model(
        model, config: LongcatFlashConfig,
        mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
        generation_config: Optional[GenerationConfig] = None,
        data_type: DataType = DataType.DT_FLOAT):
    """Record the LongCat-Flash decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"longcat_flash is served by incremental decoding only, not "
            f"{mode}: tree verification and beam drafting stage and move "
            "cache positions as a k/v pair, and a latent layer "
            "(ops/latent_attention.py) keeps one shared entry a position")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    attention = dict(
        rope_inv_freq=yarn_inv_freq(
            c.qk_rope_head_dim, {"rope_theta": c.rope_theta}),
        rope_theta=float(c.rope_theta),
        # mla_scale_q_lora: both query parts times q_scale, so every score
        softmax_scale=(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        * c.q_scale,
        # (mla_scale_kv_lora is in the latent norm's weight)
        norm_eps=c.rms_norm_eps, data_type=data_type)

    def norm(t, name):
        return model.rms_norm(t, eps=c.rms_norm_eps, dim=c.hidden_size,
                              name=name)

    for i in range(c.num_layers):
        ly = f"layers.{i}"
        routed = None
        for s in (0, 1):    # cache layers 2i and 2i + 1, in this order
            attn = model.inc_multihead_latent_attention(
                norm(h, f"{ly}.input_layernorm.{s}"), c.hidden_size,
                c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                name=f"{ly}.self_attn.{s}", **attention)
            h = model.add(h, attn)
            x = norm(h, f"{ly}.post_attention_layernorm.{s}")
            if s == 0:      # the shortcut leaves here
                routed = routed_branch(model, x, f"{ly}.mlp", c, data_type)
            h = model.add(h, _swiglu(model, x, c.ffn_hidden_size,
                                     c.hidden_size, data_type,
                                     f"{ly}.mlps.{s}"))
        h = model.add(h, routed)    # and rejoins here

    x = norm(h, "norm")
    logits = model.dense(x, c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


_DROPPED = ("visual", "vision", "audio", "codec", "talker", "mtp.")


def preprocess_hf_state_dict(sd, config: LongcatFlashConfig):
    """Drop the encoders, the codec decoder and the prediction head unread;
    stack the HELD experts' ``[out, in]`` Linears into ``[count, in, out]``
    (the others dropped unread); split ``kv_b_proj`` into its key and value
    halves, a head apart; permute the rope columns of ``q_b_proj`` and
    ``kv_a_proj_with_mqa`` from the checkpoint's adjacent pairing to the
    op's halves; fold ``mla_scale_kv_lora``'s factor into
    ``kv_a_layernorm`` (``mla_scale_q_lora``'s is the op's softmax
    scale)."""
    c = config
    for k in [k for k in sd if any(d in k for d in _DROPPED)]:
        del sd[k]
    first, count = c.held
    perm = rope_permutation(c.qk_rope_head_dim)
    for i in range(c.num_layers):
        for s in (0, 1):
            prepare_latent_attention(sd, f"model.layers.{i}.self_attn.{s}",
                                     c, perm, c.latent_scale)
        stack_held_experts(sd, i, c.n_routed_experts, first, count)


def hf_weight_map(config: LongcatFlashConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has prepared."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i in range(config.num_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for s in (0, 1):
            m.update(latent_attention_map(f"{hf}.self_attn.{s}",
                                          f"{ff}.self_attn.{s}"))
            for p in ("input_layernorm", "post_attention_layernorm"):
                m[f"{hf}.{p}.{s}.weight"] = (f"{ff}.{p}.{s}", "weight",
                                             False)
            for proj, _ in _EXPERT_PROJ:
                m[f"{hf}.mlps.{s}.{proj}.weight"] = (
                    f"{ff}.mlps.{s}.{proj}", "kernel", True)
        m[f"{hf}.mlp.router.classifier.weight"] = (
            f"{ff}.mlp.router.classifier", "kernel", True)
        m[f"{hf}.mlp.router.e_score_correction_bias"] = (
            f"{ff}.mlp.router.e_score_correction_bias", "weight", False)
        for proj, w in _EXPERT_PROJ:
            m[_experts_key(i, proj)] = (f"{ff}.mlp.experts", w, False)
    return m
