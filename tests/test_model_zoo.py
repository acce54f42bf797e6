"""Model-zoo alignment vs HuggingFace transformers.

Reference test strategy (reference tests/inference/huggingface_inference.py
+ the config matrix in tests/inference/python_test_configs/): every serving
model family must decode token-identically to the HF implementation. Here
each family gets a tiny randomly-initialized HF model (no downloads) whose
weights load into our graph; greedy decoding must match exactly and prefill
logits must be allclose in fp32.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import flexflow_tpu as ff
from flexflow_tpu.models import FAMILIES, family_for_hf_config
from flexflow_tpu.serve.request_manager import RequestManager


def _hf_llama():
    return transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False))


def _hf_opt():
    return transformers.OPTForCausalLM(transformers.OPTConfig(
        vocab_size=256, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        word_embed_proj_dim=64, do_layer_norm_before=True))


def _hf_falcon():
    return transformers.FalconForCausalLM(transformers.FalconConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, alibi=False))


def _hf_falcon40b_style():
    return transformers.FalconForCausalLM(transformers.FalconConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2, multi_query=False,
        parallel_attn=True, new_decoder_architecture=True, bias=False,
        alibi=False))


def _hf_mpt():
    # expansion_ratio stays at the default 4: HF's MptMLP hard-codes
    # 4*hidden_size regardless of the config field.
    return transformers.MptForCausalLM(transformers.MptConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, max_seq_len=128))


def _hf_starcoder():
    return transformers.GPTBigCodeForCausalLM(transformers.GPTBigCodeConfig(
        vocab_size=256, n_embd=64, n_inner=128, n_layer=2, n_head=4,
        n_positions=128, multi_query=True))


def _hf_starcoder_mha():
    # multi_query=False: HF fuses c_attn per-head interleaved [q|k|v] rows
    return transformers.GPTBigCodeForCausalLM(transformers.GPTBigCodeConfig(
        vocab_size=256, n_embd=64, n_inner=128, n_layer=2, n_head=4,
        n_positions=128, multi_query=False))


def _hf_olmoe():
    # sparse experts: 8 experts, top-2, not renormalised; q/k RMSNorm
    return transformers.OlmoeForCausalLM(transformers.OlmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=128, tie_word_embeddings=False))


CASES = {
    "llama": _hf_llama,
    "opt": _hf_opt,
    "falcon": _hf_falcon,
    "falcon-new-arch": _hf_falcon40b_style,
    "mpt": _hf_mpt,
    "olmoe": _hf_olmoe,
    "starcoder": _hf_starcoder,
    "starcoder-mha": _hf_starcoder_mha,
}


@pytest.fixture(params=sorted(CASES), scope="module")
def hf_case(request):
    torch.manual_seed(0)
    m = CASES[request.param]()
    m.eval()
    return m


def build_ff_from_hf(hf_model, max_requests=2, max_seq=64):
    family = family_for_hf_config(hf_model.config)
    config = family.config_cls.from_hf_config(hf_model.config)
    ffc = ff.FFConfig(max_requests_per_batch=max_requests,
                      max_sequence_length=max_seq, max_tokens_per_batch=16,
                      kv_cache_dtype="float32")
    model = ff.FFModel(ffc)
    family.build(model, config)
    model.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    n = family.load_hf(model, config, hf_model.state_dict())
    assert n == len(family.hf_weight_map(config))
    return model


def test_greedy_decode_matches_hf(hf_case):
    prompt = [3, 17, 42, 99, 7]
    new_tokens = 10
    with torch.no_grad():
        out = hf_case.generate(
            torch.tensor([prompt]), max_new_tokens=new_tokens,
            do_sample=False, pad_token_id=0)
    hf_tokens = out[0, len(prompt):].tolist()

    model = build_ff_from_hf(hf_case)
    rm = RequestManager()
    rm.register_new_request(prompt, max_new_tokens=new_tokens)
    (res,) = rm.generate_incr_decoding(model)
    assert res.output_tokens == hf_tokens


def test_prefill_logits_close_to_hf(hf_case):
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.serve.batch_config import make_batch_meta

    prompt = [3, 17, 42, 99, 7, 55]
    with torch.no_grad():
        hf_logits = hf_case(torch.tensor([prompt])).logits[0].numpy()

    model = build_ff_from_hf(hf_case)
    R, Q = model.config.max_requests_per_batch, len(prompt)
    tokens = np.zeros((R, Q), np.int32)
    tokens[0] = prompt
    meta = make_batch_meta(
        R, Q, tokens=tokens,
        positions=np.broadcast_to(np.arange(Q, dtype=np.int32),
                                  (R, Q)).copy(),
        num_tokens=np.array([Q] + [0] * (R - 1), np.int32),
        active=np.array([True] + [False] * (R - 1)))
    ctx = OpContext(training=False, compute_dtype=jnp.float32,
                    batch_config=meta, config=model.config)
    feeds = {model.input_tensors[0].tensor_id: meta.tokens}
    if model.position_input_tensor is not None:
        feeds[model.position_input_tensor.tensor_id] = (
            np.asarray(meta.positions) + model.position_offset)
    values, _ = model._run_graph(model.params, feeds, ctx, model.op_state)
    logits_t = model.layers[-1].inputs[0]
    ours = np.asarray(values[logits_t.tensor_id])[0]
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)
