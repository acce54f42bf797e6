"""int8/int4 weight-only quantization tests (reference
decompress_kernels.cu + compress_llama_weights.py capability)."""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.quant import (
    dequantize_array,
    is_quantized,
    quantize_array,
    quantize_params,
    quantized_nbytes,
)


def test_quantize_roundtrip_int8():
    rng = np.random.RandomState(0)
    w = rng.randn(128, 96).astype(np.float32)
    leaf = quantize_array(w, "int8")
    assert leaf.q.dtype == np.int8 and leaf.q.shape == (128, 96)
    back = np.asarray(dequantize_array(leaf))
    # int8 symmetric: error bounded by scale/2 per element
    scale = np.abs(w).max(axis=0) / 127.0
    assert np.all(np.abs(back - w) <= scale[None, :] * 0.5 + 1e-7)


def test_quantize_roundtrip_int4_packing():
    rng = np.random.RandomState(1)
    for rows in (128, 127):      # even and odd (padded) row counts
        w = rng.randn(rows, 64).astype(np.float32)
        leaf = quantize_array(w, "int4")
        assert leaf.q.shape == ((rows + 1) // 2, 64)
        back = np.asarray(dequantize_array(leaf))
        assert back.shape == w.shape
        scale = np.abs(w).max(axis=0) / 7.0
        assert np.all(np.abs(back - w) <= scale[None, :] * 0.5 + 1e-6)


def test_quantize_params_selects_eligible():
    rng = np.random.RandomState(2)
    params = {
        "dense_0": {"kernel": rng.randn(128, 128).astype(np.float32),
                    "bias": rng.randn(128).astype(np.float32)},
        "norm_0": {"gamma": rng.randn(128).astype(np.float32)},
        "small": {"kernel": rng.randn(4, 4).astype(np.float32)},
    }
    q = quantize_params(params, "int8")
    assert is_quantized(q["dense_0"]["kernel"])
    assert not is_quantized(q["dense_0"]["bias"])
    assert not is_quantized(q["norm_0"]["gamma"])
    assert not is_quantized(q["small"]["kernel"])     # below min_dim
    assert quantized_nbytes(q) < quantized_nbytes(params)


@pytest.mark.parametrize("qtype,tol", [("int8", 0.02), ("int4", 0.2)])
def test_quantized_model_predict_close(qtype, tol):
    rng = np.random.RandomState(3)
    model = ff.FFModel(ff.FFConfig(batch_size=16))
    t = model.create_tensor([16, 128], ff.DataType.DT_FLOAT)
    x = model.dense(t, 128, ff.ActiMode.AC_MODE_RELU)
    x = model.dense(x, 64)
    model.compile()

    xin = rng.randn(16, 128).astype(np.float32)
    full = model.predict(xin)
    model.quantize_weights(qtype)
    quant = model.predict(xin)
    rel = (np.abs(quant - full).max()
           / max(1e-6, np.abs(full).max()))
    assert rel < tol, rel


def test_quantized_serving_generates():
    """Full serving loop with int8 weights (reference --8bit-quantization)."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from flexflow_tpu import serve as ff_serve

    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False))
    hf.eval()

    llm = ff_serve.LLM(hf)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32",
                quantization_type="int8")
    res = llm.generate([5, 9, 23, 44], max_new_tokens=8)
    assert len(res.output_tokens) == 8

    # int8 weight-only: greedy tokens should match full precision for a
    # well-conditioned tiny model
    llm_full = ff_serve.LLM(hf)
    llm_full.compile(max_requests_per_batch=2, max_seq_length=64,
                     max_tokens_per_batch=16, kv_cache_dtype="float32")
    full = llm_full.generate([5, 9, 23, 44], max_new_tokens=8)
    matches = sum(a == b for a, b in
                  zip(res.output_tokens, full.output_tokens))
    assert matches >= 6, (res.output_tokens, full.output_tokens)


def test_qtake_matches_dequantized_gather():
    """qtake (packed-row gather, int4 nibble select) must equal gathering
    from the fully dequantized table."""
    import numpy as np
    import jax.numpy as jnp

    from flexflow_tpu.quant import dequantize_array, qtake, quantize_array

    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(31, 16).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, 31, size=(4, 5)).astype(np.int32))
    for qtype in ("int8", "int4"):
        qt = quantize_array(table, qtype)
        got = qtake(qt, ids)
        want = jnp.take(dequantize_array(qt), ids, axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def _tiny_llama(quant):
    from flexflow_tpu.ffconst import CompMode, InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, kv_cache_dtype="float32",
                      quantization_type=quant, seed=3)
    m = ff.FFModel(cfg)
    create_llama_model(
        m,
        LLAMAConfig(vocab_size=128, hidden_size=128, intermediate_size=96,
                    num_hidden_layers=1, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=64),
        InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=CompMode.COMP_MODE_INFERENCE)
    return m


@pytest.mark.parametrize("quant", [None, "int8"])
def test_param_accessors_roundtrip(quant):
    """get/set_parameter_by_key on an unstacked model: a write to a
    quantized weight re-quantizes it, and its neighbours stay as they
    were."""
    m = _tiny_llama(quant)
    tol = dict(rtol=0.02, atol=1e-4) if quant else dict(rtol=1e-6)
    for key, shape, neighbour in (
            (("layers.0.self_attn", "wq"), (128, 128),
             ("layers.0.self_attn", "wk")),
            (("layers.0.mlp.gate_proj", "kernel"), (128, 96),
             ("layers.0.mlp.up_proj", "kernel"))):
        assert is_quantized(m.params[key[0]][key[1]]) == bool(quant)
        w = m.get_parameter_by_key(key)
        assert w.shape == shape
        before = m.get_parameter_by_key(neighbour)
        new = np.full_like(w, 0.01)
        m.set_parameter_by_key(key, new)
        assert is_quantized(m.params[key[0]][key[1]]) == bool(quant)
        np.testing.assert_allclose(m.get_parameter_by_key(key), new, **tol)
        np.testing.assert_array_equal(m.get_parameter_by_key(neighbour),
                                      before)


def test_param_set_rejects_wrong_shape():
    m = _tiny_llama("int8")
    with pytest.raises(AssertionError):
        m.set_parameter_by_key(("layers.0.self_attn", "wq"),
                               np.zeros(128, np.float32))
    with pytest.raises(KeyError):
        m.get_parameter_by_key(("layers.0.self_attn", "no_such_weight"))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape,qtype", [((192, 80), "int8"),
                                         ((3, 192, 80), "int8"),
                                         ((191, 80), "int4")])
def test_the_one_program_quantiser_gives_the_eager_numbers_to_the_bit(
        dtype, shape, qtype):
    """``quantize_array`` is one jitted program a shape; its payload and its
    scale are what the eager operations it replaced gave (a fused bfloat16
    quotient would keep float32 bits, a division by a constant become a
    multiplication), so no served weight moved with it."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.quant import _unpack_int4

    w = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.dtype(dtype)) * .02
    w = w.at[..., 1].set(0)                     # a column of zeros: scale 1
    got = quantize_array(w, qtype)
    qmax = 127.0 if qtype == "int8" else 7.0
    scale = jnp.max(jnp.abs(w), axis=-2) / qmax
    scale = jnp.where(scale == 0, 1.0, scale).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale[..., None, :]), -qmax,
                 qmax).astype(jnp.int8)
    payload = got.q if qtype == "int8" else _unpack_int4(got.q, got.rows)
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(got.scale), np.asarray(scale))
    assert got.scale.dtype == jnp.float32 and got.shape == shape
