"""EvaByte decoder for serving: a byte-level model whose attention (EVA)
reads an exact window and one learned summary for every chunk before it.

Follows the published ``config.json`` of EvaByte/EvaByte (6.5B) for the
sizes and its modeling code (``eva.py``) for the formulas, as
benchmark/reference/evabyte.py writes them down line for line: embedding ->
N x (RMSNorm with a unit offset -> attention -> residual -> RMSNorm ->
SwiGLU MLP -> residual) -> final RMSNorm -> head, no bias anywhere.

* ``norm_add_unit_offset``: ``x / sqrt(mean(x^2) + eps) * (1 + g)``; the
  stored weight is ``g`` (a loader that folded ``1 + g`` would store that
  instead; this one does not).
* ``fp32_skip_add``: the residual stream is float32; a norm reads it and
  hands the compute dtype to the projections.
* Attention: rotary (rotate-half, every dim, at the TRUE position) on q
  and k; query ``i`` in window ``w = i // window_size`` sees the exact pairs
  of its own window up to itself and, of every window before, one pair
  ``(kbar_n, vbar_n)`` a chunk of ``chunk_size`` positions, pooled from the
  chunk's rotated keys by the head's two learned vectors (``adaptive_mu_k``
  scores the keys' pool, ``adaptive_phi`` the values'), all in ONE softmax.
  The layer keeps both in one cache stream (ops/kv_layout.py ``chunked_*``)
  and attends through ``flash_attend_chunked``.
* The head is ``num_pred_heads`` x ``vocab_size`` columns wide as
  published; columns ``[0, vocab_size)`` are the next byte's, and the only
  ones this builder holds and serves (``num_pred_heads`` 1). The model's
  own multi-byte decoding, which drafts from the other heads and verifies
  itself, is speculation over a cache that refuses speculation
  (ops/inc_attention.refuse_windowed), and is not served.

Layer names follow the HF checkpoint (``model.layers.{i}.self_attn`` ...).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from flexflow_tpu.ffconst import DataType, InferenceMode
from flexflow_tpu.serve.batch_config import GenerationConfig


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320           # 256 bytes and 64 special ids
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    max_position_embeddings: int = 32768
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8         # published; the builder holds the first

    # config.json keys this builder reads, and the values of the others it
    # was written for: anything else is refused, not ignored
    EXPECTED = {"attention_class": "eva", "attention_bias": False,
                "hidden_act": "silu", "norm_add_unit_offset": True,
                "fp32_skip_add": True, "fp32_logits": True,
                "mixedp_attn": True, "fp32_ln": False,
                "tie_word_embeddings": False, "rope_scaling": None,
                "num_chunks": None}
    IGNORED = ("model_type", "init_cutoff_factor", "init_fn", "init_std",
               "lazy_init", "max_seq_length", "architectures", "auto_map",
               "torch_dtype", "transformers_version", "use_cache",
               "bos_token_id", "eos_token_id", "pad_token_id",
               "initializer_range")

    @classmethod
    def from_hf_config(cls, hf, strict: bool = False) -> "EvaByteConfig":
        """Accepts a transformers config or a plain dict. ``strict`` (a
        dict): a key that is neither read, nor expected at its value, nor
        known to say nothing of the shape, is refused."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        wrong = {k: get(k, v) for k, v in cls.EXPECTED.items()
                 if get(k, v) != v}
        if wrong:
            raise NotImplementedError(
                f"evabyte with {wrong}: the graph below is EVA attention "
                "without bias, SiLU-gated MLPs, norms with a unit offset, "
                "float32 residual adds and logits, an untied head, plain "
                f"rotary positions (expected {cls.EXPECTED})")
        fields = {f.name for f in dataclasses.fields(cls)}
        if strict:
            unknown = sorted(set(hf) - fields - set(cls.EXPECTED)
                             - set(cls.IGNORED))
            if unknown:
                raise KeyError(f"evabyte config keys not understood: "
                               f"{unknown}")
        c = cls(**{k: get(k) for k in fields if get(k) is not None})
        if c.num_key_value_heads != c.num_attention_heads:
            raise NotImplementedError(
                "evabyte with grouped key/value heads: the pooling vectors "
                "are one pair a head, and the published model has none")
        return c


def create_evabyte_model(model, config: EvaByteConfig,
                         mode: InferenceMode = InferenceMode.INC_DECODING_MODE,
                         generation_config: Optional[GenerationConfig] = None,
                         data_type: DataType = DataType.DT_FLOAT):
    """Record the EvaByte decoder graph into ``model`` (an FFModel)."""
    c = config
    if mode != InferenceMode.INC_DECODING_MODE:
        raise NotImplementedError(
            f"evabyte in {mode.name}: a chunked attention layer is served "
            "by incremental decoding only (tree verification and beam "
            "drafting move and roll back cache positions that its cache "
            "keeps only inside a chunk's summary)")
    R = model.config.max_requests_per_batch
    tokens = model.create_tensor([R, 1], DataType.DT_INT32)  # Q is dynamic

    def norm(h, name):
        return model.rms_norm(h, eps=c.rms_norm_eps, dim=c.hidden_size,
                              unit_offset=True, data_type=data_type,
                              name=name)

    h = model.embedding(tokens, c.vocab_size, c.hidden_size,
                        dtype=data_type, name="model.embed_tokens")
    h = model.cast(h, DataType.DT_FLOAT)        # fp32_skip_add: the stream
    for i in range(c.num_hidden_layers):
        p = f"model.layers.{i}"
        attn = model.inc_multihead_self_attention(
            norm(h, f"{p}.input_layernorm"), c.hidden_size,
            c.num_attention_heads, data_type=data_type,
            apply_rotary_embedding=True, rope_theta=c.rope_theta,
            eva_window=c.window_size, chunk_size=c.chunk_size,
            name=f"{p}.self_attn")
        h = model.add(h, attn)
        x = norm(h, f"{p}.post_attention_layernorm")
        gate = model.dense(x, c.intermediate_size, use_bias=False,
                           datatype=data_type, name=f"{p}.mlp.gate_proj")
        up = model.dense(x, c.intermediate_size, use_bias=False,
                         datatype=data_type, name=f"{p}.mlp.up_proj")
        down = model.dense(model.sigmoid_silu_multi(gate, up), c.hidden_size,
                           use_bias=False, datatype=data_type,
                           name=f"{p}.mlp.down_proj")
        h = model.add(h, down)
    logits = model.dense(norm(h, "model.norm"), c.vocab_size, use_bias=False,
                         datatype=data_type, keep_f32_logits=True,
                         name="lm_head")
    gen = generation_config or GenerationConfig()
    if gen.do_sample:
        return model.sampling(logits, top_p=gen.topp,
                              temperature=gen.temperature)
    return model.argmax(logits)


def preprocess_hf_state_dict(sd, config: EvaByteConfig = None):
    """The head's first ``vocab_size`` rows (the next byte's; the other
    heads' are not loaded) and the pooling vectors as ``[heads, head_dim]``
    (published ``[1, heads, 1, 1, head_dim]``)."""
    from flexflow_tpu.models.hf_utils import _to_numpy

    if "lm_head.weight" in sd:
        sd["lm_head.weight"] = _to_numpy(sd["lm_head.weight"])[
            :config.vocab_size]
    for k in list(sd):
        if k.endswith(("adaptive_mu_k", "adaptive_phi")):
            v = _to_numpy(sd[k])
            sd[k] = v.reshape(v.shape[1], v.shape[-1])


def hf_weight_map(config: EvaByteConfig):
    """HF state-dict key -> (layer_name, weight_name, transpose?)."""
    m = {"model.embed_tokens.weight": ("model.embed_tokens", "weight", False),
         "model.norm.weight": ("model.norm", "weight", False),
         "lm_head.weight": ("lm_head", "kernel", True)}
    for i in range(config.num_hidden_layers):
        p = f"model.layers.{i}"
        for hf, w in (("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
                      ("o_proj", "wo")):
            m[f"{p}.self_attn.{hf}.weight"] = (f"{p}.self_attn", w, True)
        for w in ("adaptive_mu_k", "adaptive_phi"):
            m[f"{p}.self_attn.{w}"] = (f"{p}.self_attn", w, False)
        for hf in ("gate_proj", "up_proj", "down_proj"):
            m[f"{p}.mlp.{hf}.weight"] = (f"{p}.mlp.{hf}", "kernel", True)
        for n in ("input_layernorm", "post_attention_layernorm"):
            m[f"{p}.{n}.weight"] = (f"{p}.{n}", "weight", False)
    return m
