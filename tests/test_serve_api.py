"""serve.LLM / serve.SSM API tests (reference serve/serve.py surface).

Mirrors the reference inference CI (tests/inference/python_inference_tests.sh):
(a) LLM.generate through the public API matches HF greedy decoding,
(b) spec-infer (LLM + SSM) token-matches incremental decoding,
(c) init() maps reference config keys onto FFConfig fields.
"""

import dataclasses
import json
import os
import re
import sys

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from flexflow_tpu import serve as ff_serve


@pytest.fixture(scope="module")
def hf_llama():
    torch.manual_seed(0)
    m = transformers.LlamaForCausalLM(transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=False))
    m.eval()
    return m


def test_llm_generate_matches_hf(hf_llama):
    prompt = [5, 9, 23, 44]
    with torch.no_grad():
        out = hf_llama.generate(torch.tensor([prompt]), max_new_tokens=8,
                                do_sample=False, pad_token_id=0)
    hf_tokens = out[0, len(prompt):].tolist()

    llm = ff_serve.LLM(hf_llama)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32")
    res = llm.generate(prompt, max_new_tokens=8)
    assert res.output_tokens == hf_tokens


def test_llm_with_ssm_spec_infer(hf_llama):
    prompt = [5, 9, 23, 44]
    llm_incr = ff_serve.LLM(hf_llama)
    llm_incr.compile(max_requests_per_batch=2, max_seq_length=64,
                     max_tokens_per_batch=16, kv_cache_dtype="float32")
    incr = llm_incr.generate(prompt, max_new_tokens=8)

    llm = ff_serve.LLM(hf_llama)
    ssm = ff_serve.SSM(hf_llama)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, ssms=[ssm],
                kv_cache_dtype="float32")
    spec = llm.generate(prompt, max_new_tokens=8)
    # reference CI gate: spec infer output token-matches incr decoding
    assert spec.output_tokens == incr.output_tokens


def test_cli_main_incr_and_spec(capsys):
    """python -m flexflow_tpu.serve (launcher parity): incremental and
    speculative paths run end-to-end from argv."""
    from flexflow_tpu.serve.__main__ import main

    assert main(["--max-new-tokens", "6", "--max-seq-length", "64",
                 "--max-tokens-per-batch", "16"]) == 0
    out = capsys.readouterr().out
    assert "tok/s" in out and "guid=" in out

    # '--ssm-model builtin' with no --model uses the built-in draft pair;
    # a real path without --model is rejected up front
    assert main(["--max-new-tokens", "6", "--max-seq-length", "64",
                 "--max-tokens-per-batch", "16",
                 "--ssm-model", "builtin"]) == 0
    out = capsys.readouterr().out
    assert "[speculative]" in out
    with pytest.raises(SystemExit):
        main(["--ssm-model", "/some/real/draft"])


def test_init_maps_reference_keys():
    out = ff_serve.init(num_gpus=4, memory_per_gpu=14000,
                        zero_copy_memory_per_node=30000,
                        tensor_parallelism_degree=2, fusion=True,
                        use_8bit_quantization=True)
    assert out["num_devices"] == 4
    assert out["tensor_parallelism_degree"] == 2
    assert out["enable_fusion"] is True
    assert out["quantization_type"] == "int8"
    assert "memory_per_gpu" not in out
    ff_serve.init()  # reset globals for other tests


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", ["falcon-7b", "opt-6.7b-spec",
                                  "olmoe-1b-7b"])
def test_cells_measure_the_default_path(name):
    """A benchmark configuration switches no path away from the one a
    default user gets: each boolean FFConfig field it sets is at
    FFConfig's default."""
    from flexflow_tpu.config import FFConfig

    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        assumed = json.load(f)["assumed"]
    defaults = {f.name: f.default for f in dataclasses.fields(FFConfig)
                if isinstance(f.default, bool)}
    switches = {k: v for k, v in assumed.items() if k in defaults}
    assert switches                 # the test reads what it thinks it reads
    assert switches == {k: defaults[k] for k in switches}


@pytest.mark.parametrize("cls", ["FFConfig", "GenerationConfig"])
def test_every_option_is_read(cls):
    """Every field of the two option objects is named by a module of the
    program other than the one that declares it."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.serve.batch_config import GenerationConfig

    klass = {"FFConfig": FFConfig, "GenerationConfig": GenerationConfig}[cls]
    # read by nothing until a benchmark PR stops passing it (config.py)
    exempt = {"use_native_scheduler"}
    declaring = os.path.abspath(sys.modules[klass.__module__].__file__)
    text = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "flexflow_tpu")):
        for fn in files:
            path = os.path.join(root, fn)
            if fn.endswith(".py") and path != declaring:
                with open(path) as f:
                    text.append(f.read())
    words = set(re.findall(r"\w+", "\n".join(text)))
    unread = {f.name for f in dataclasses.fields(klass)} - words - exempt
    assert not unread
    assert exempt <= {f.name for f in dataclasses.fields(FFConfig)}


def test_output_file(tmp_path, hf_llama):
    path = str(tmp_path / "out.txt")
    llm = ff_serve.LLM(hf_llama, output_file=path)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32")
    llm.generate([3, 1, 2], max_new_tokens=4)
    text = open(path).read()
    assert "guid(" in text and "output:" in text


def test_start_server_concurrent_submitters_token_identical(hf_llama):
    """VERDICT r3 item 6: start_server runs a background step loop with a
    thread-safe submission queue; two CONCURRENT submitters interleave
    into one running batch, and every request's tokens are identical to a
    sequential (inline) run."""
    import threading

    prompts = {"a": [5, 9, 23, 44], "b": [7, 3], "c": [1, 2, 3],
               "d": [11, 13, 17, 19, 23]}
    # sequential reference, fresh model
    llm_seq = ff_serve.LLM(hf_llama)
    llm_seq.compile(max_requests_per_batch=2, max_seq_length=64,
                    max_tokens_per_batch=16, kv_cache_dtype="float32")
    want = {k: llm_seq.generate(p, max_new_tokens=8).output_tokens
            for k, p in prompts.items()}

    llm = ff_serve.LLM(hf_llama)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32")
    llm.start_server()
    try:
        got = {}
        errs = []

        def worker(keys):
            try:
                for k in keys:
                    got[k] = llm.generate(
                        prompts[k], max_new_tokens=8).output_tokens
            except Exception as e:          # pragma: no cover
                errs.append(e)

        t1 = threading.Thread(target=worker, args=(["a", "c"],))
        t2 = threading.Thread(target=worker, args=(["b", "d"],))
        t1.start(); t2.start()
        t1.join(timeout=120); t2.join(timeout=120)
        assert not t1.is_alive() and not t2.is_alive(), "server hung"
        assert not errs, errs
        assert got == want
    finally:
        llm.stop_server()
    assert llm._server is None
    # after stop, inline generate still works and matches
    again = llm.generate(prompts["a"], max_new_tokens=8).output_tokens
    assert again == want["a"]


def test_start_server_requires_compile(hf_llama):
    llm = ff_serve.LLM(hf_llama)
    with pytest.raises(RuntimeError, match="compile"):
        llm.start_server()


def test_server_empty_prompt_list_returns_immediately(hf_llama):
    """generate([]) in server mode must return [] instead of enqueueing
    a waiter no generation round ever releases (a permanent hang)."""
    import threading

    llm = ff_serve.LLM(hf_llama)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32")
    llm.start_server()
    try:
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault("r", llm.generate([])))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "empty submission hung the server path"
        assert out["r"] == []
    finally:
        llm.stop_server()
