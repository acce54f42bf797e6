"""Granite-4.0-H decoder for serving (HF ``model_type`` ``granitemoehybrid``:
ibm-granite/granite-4.0-h-micro): most layers are state-space mixers that
keep a RECURRENT STATE a row (Mamba-2, ops/ssd_mixer.py), a few are plain
grouped-query attention layers with no position embedding, and every layer's
feed-forward is one dense SwiGLU (the ``shared_mlp``).

One layer ``i``, ``N`` an RMSNorm of its own each time (pre-norm), ``m`` the
``residual_multiplier``:

    h_0 = embedding_multiplier * Embed[token]
    h1  = h  + m * Mix_i(N(h))        Mix_i by ``layer_types[i]``
    h'  = h1 + m * W_out (silu(g) * u),  [g | u] = W_in N(h1)
    logits = (Embed . N(h_L)) / logits_scaling                 a tied head

* ``"mamba"``: ``mamba_n_heads`` heads of ``mamba_d_head`` channels over one
  group of ``mamba_d_state`` state dims, a depthwise causal convolution of
  ``mamba_d_conv`` taps with a bias (ops/ssd_mixer.py has the equations): a
  slot keeps ``[heads, d_head, d_state]`` float32 and the convolution's tail
  a layer, overwritten by every step.
* ``"attention"``: ``num_attention_heads`` query and ``num_key_value_heads``
  key/value heads of ``hidden_size / num_attention_heads``, no bias, NO
  position embedding (``position_embedding_type`` ``nope``), no q/k norm,
  scores ``attention_multiplier * q . k`` (the softmax scale itself, not a
  factor on ``1 / sqrt(d)``): the plain attention op through the flash
  kernel on the k/v cache every other model keeps.

The four multipliers are graph ``scalar_multiply`` steps (the residual one
INSIDE the residual add, on the sublayer's output), but the attention one,
which is the attention op's ``scaling_factor``. ``layer_types`` decides a
layer's kind, not a period. ``config.json`` has keys for the sizes and the
multipliers only. What it has none for (the pre-norm block, the mixer's
split order ``[z | xBC | dt]``, the gate before the mixer's norm, the
seeded decay) is as ISSUE 56 states it, not checked against the published
code; benchmark/configs/granite-4.0-h-micro.json lists each under
``assumed``, and the checkpoint names below (``HF_KEYS``) with them. The
larger siblings route over experts beside the ``shared_mlp``
(``num_local_experts > 0``): not built here, and refused.

Tree verification, beam drafting, the prefix pool, a mesh that divides the
model and a pipeline plan cannot carry a recurrent state and refuse this
model (``ops/inc_attention.refuse_windowed``); preemption can (the victim is
prefilled again from position 0, which rebuilds its state).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig

# Checkpoint names below ``model.layers.{i}.`` -> (weight, transpose) of a
# mixer layer: assumed (the published layer's names), like everything
# config.json has no key for. ``preprocess_hf_state_dict`` lays the
# depthwise Conv1d weight ``[C, 1, taps]`` out as ``conv`` ``[taps, C]``.
HF_KEYS_MAMBA = {
    "mamba.in_proj.weight": ("win", True),
    "mamba.conv": ("conv", False),
    "mamba.conv1d.bias": ("conv_bias", False),
    "mamba.A_log": ("A_log", False),
    "mamba.dt_bias": ("dt_bias", False),
    "mamba.D": ("D", False),
    "mamba.norm.weight": ("norm", False),
    "mamba.out_proj.weight": ("wout", True),
}
# ... and of an attention layer
HF_KEYS_ATTENTION = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
}
HF_KEYS = {"mamba": HF_KEYS_MAMBA, "attention": HF_KEYS_ATTENTION}


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    layer_types: Optional[Tuple[str, ...]] = None
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:    # the family's pattern: one in ten
            self.layer_types = tuple(
                "attention" if i % 10 == 5 else "mamba" for i in range(L))
        # a cut in depth keeps the leading layers
        self.layer_types = tuple(self.layer_types)[:L]
        unknown = set(self.layer_types) - set(HF_KEYS)
        if len(self.layer_types) != L or unknown:
            raise ValueError(
                f"granitemoehybrid: layer_types names {len(self.layer_types)}"
                f" layers for num_hidden_layers {L}, kinds "
                f"{sorted(set(self.layer_types))}; known: {sorted(HF_KEYS)}")

    @classmethod
    def from_hf_config(cls, hf) -> "GraniteHybridConfig":
        """Accepts a transformers config or a plain dict."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        if get("num_local_experts", 0):
            raise NotImplementedError(
                f"granitemoehybrid with num_local_experts = "
                f"{get('num_local_experts')!r}: only the dense sibling is "
                "built, whose every layer's feed-forward is the shared_mlp "
                "alone; the larger ones route over experts beside it")
        for key, want, why in (
                ("position_embedding_type", "nope",
                 "the attention layers rotate nothing"),
                ("mamba_n_groups", 1,
                 "B and C are every head's (kernels/linear_attention."
                 "ssd_state_step shares them)"),
                ("mamba_conv_bias", True, "the convolution has a bias"),
                ("mamba_proj_bias", False, "the mixer's gemms have none"),
                ("attention_bias", False, "the attention gemms have none"),
                ("tie_word_embeddings", True, "a tied head")):
            if get(key, want) != want:
                raise NotImplementedError(
                    f"granitemoehybrid with {key} = {get(key)!r}: only "
                    f"{want!r} is built ({why})")
        kw = {f.name: get(f.name) for f in dataclasses.fields(cls)}
        c = cls(**{k: v for k, v in kw.items() if v is not None})
        inner = get("mamba_expand", 2) * c.hidden_size
        if c.mamba_n_heads * c.mamba_d_head != inner:
            raise NotImplementedError(
                f"granitemoehybrid with mamba_n_heads x mamba_d_head = "
                f"{c.mamba_n_heads * c.mamba_d_head} and mamba_expand x "
                f"hidden_size = {inner}: the mixer's inner width is both")
        return c

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def kind(self, i: int) -> str:
        return self.layer_types[i]


def create_granite_hybrid_model(
        model, config: GraniteHybridConfig,
        mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
        generation_config: Optional[GenerationConfig] = None,
        data_type: DataType = DataType.DT_FLOAT):
    """Record the Granite-4.0-H decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"granitemoehybrid is served by incremental decoding only, not "
            f"{mode}: tree verification (speculation) and beam drafting "
            "stage tokens that may be rejected, and a layer that keeps a "
            "recurrent state (ops/ssd_mixer.py) has folded them into it by "
            "then")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic
    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="embed_tokens")
    h = model.scalar_multiply(h, c.embedding_multiplier,
                              name="embedding_multiplier")

    def norm(t, name):
        return model.rms_norm(t, eps=c.rms_norm_eps, dim=c.hidden_size,
                              name=name)

    def residual(h, branch, name):
        return model.add(h, model.scalar_multiply(
            branch, c.residual_multiplier, name=f"{name}.residual"))

    for i in range(c.num_hidden_layers):
        ly = f"layers.{i}"
        x = norm(h, f"{ly}.input_layernorm")
        if c.kind(i) == "attention":
            mix = model.inc_multiquery_self_attention(
                x, c.hidden_size, c.num_attention_heads,
                c.num_key_value_heads, data_type=data_type,
                apply_rotary_embedding=False, scaling_query=True,
                scaling_factor=c.attention_multiplier,
                qk_prod_scaling=False, name=f"{ly}.self_attn")
        else:
            mix = model.inc_ssd_mixer(
                x, c.hidden_size, c.mamba_n_heads, c.mamba_d_head,
                c.mamba_d_state, conv_kernel=c.mamba_d_conv,
                norm_eps=c.rms_norm_eps, data_type=data_type,
                name=f"{ly}.mamba")
        h = residual(h, mix, f"{ly}.mixer")
        p = f"{ly}.shared_mlp"
        gate, up = model.split(
            model.dense(norm(h, f"{ly}.post_attention_layernorm"),
                        2 * c.shared_intermediate_size, use_bias=False,
                        datatype=data_type, name=f"{p}.input_linear"),
            2, axis=-1, name=f"{p}.halves")
        h = residual(h, model.dense(
            model.sigmoid_silu_multi(gate, up), c.hidden_size,
            use_bias=False, datatype=data_type, name=f"{p}.output_linear"),
            p)

    logits = model.dense(norm(h, "norm"), c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         tied_to="embed_tokens", name="lm_head")
    logits = model.scalar_multiply(logits, 1.0 / c.logits_scaling,
                                   name="logits_scaling")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


def preprocess_hf_state_dict(sd, config: GraniteHybridConfig):
    """Lay a mixer's depthwise Conv1d weight ``[C, 1, taps]`` out as the op
    holds it, ``conv`` ``[taps, C]``."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    for i in range(config.num_hidden_layers):
        key = f"model.layers.{i}.mamba.conv1d.weight"
        if key in sd:
            sd[f"model.layers.{i}.mamba.conv"] = _to_numpy(
                sd.pop(key))[:, 0, :].T


def hf_weight_map(config: GraniteHybridConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?), over a
    state dict that ``preprocess_hf_state_dict`` has prepared. The head is
    the embedding's table (``tie_word_embeddings``): no key of its own."""
    m = {"model.embed_tokens.weight": ("embed_tokens", "weight", False),
         "model.norm.weight": ("norm", "weight", False)}
    for i in range(config.num_hidden_layers):
        hf, ff = f"model.layers.{i}", f"layers.{i}"
        for key, (weight, transpose) in HF_KEYS[config.kind(i)].items():
            m[f"{hf}.{key}"] = (f"{ff}.{key.split('.')[0]}", weight,
                                transpose)
        for p in ("input_layernorm", "post_attention_layernorm"):
            m[f"{hf}.{p}.weight"] = (f"{ff}.{p}", "weight", False)
        for p in ("input_linear", "output_linear"):
            m[f"{hf}.shared_mlp.{p}.weight"] = (f"{ff}.shared_mlp.{p}",
                                                "kernel", True)
    return m
