"""Speculative-inference serving entry (reference inference/python/
spec_infer.py, C++ main inference/spec_infer/spec_infer.cc:274): a verifier
LLM + small draft SSMs with token-tree verification.

Zero-egress default: random-init verifier whose 2-layer truncation is the
draft.
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(__file__), *[_os.pardir] * 2)))

import argparse
import time

import flexflow_tpu.serve as ff_serve


def make_models(path, ssm_path):
    import torch
    import transformers

    if path:
        return path, (ssm_path or path)
    torch.manual_seed(0)
    cfg = dict(vocab_size=1024, hidden_size=256, intermediate_size=688,
               num_attention_heads=8, num_key_value_heads=4,
               max_position_embeddings=512, tie_word_embeddings=False)
    llm = transformers.LlamaForCausalLM(
        transformers.LlamaConfig(num_hidden_layers=4, **cfg))
    ssm = transformers.LlamaForCausalLM(
        transformers.LlamaConfig(num_hidden_layers=2, **cfg))
    # draft = truncation of the verifier (shared lower layers)
    sd = {k: v for k, v in llm.state_dict().items()
          if "layers.2." not in k and "layers.3." not in k}
    ssm.load_state_dict(sd, strict=False)
    return llm, ssm


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="", help="verifier HF dir (optional)")
    p.add_argument("--ssm-model", default="", help="draft HF dir (optional)")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--max-requests-per-batch", type=int, default=4)
    p.add_argument("--max-seq-length", type=int, default=256)
    p.add_argument("--max-tokens-per-batch", type=int, default=64)
    args = p.parse_args()

    ff_serve.init()
    llm_src, ssm_src = make_models(args.model, args.ssm_model)
    llm = ff_serve.LLM(llm_src)
    ssm = ff_serve.SSM(ssm_src)
    llm.compile(max_requests_per_batch=args.max_requests_per_batch,
                max_seq_length=args.max_seq_length,
                max_tokens_per_batch=args.max_tokens_per_batch,
                ssms=[ssm])

    prompts = [[1, 5, 9, 23], [1, 44, 17], [1, 3, 3, 7, 11]] \
        if llm.tokenizer is None else ["Hello, my name is"]
    t0 = time.time()
    results = llm.generate(prompts, max_new_tokens=args.max_new_tokens)
    dt = time.time() - t0
    total = sum(len(r.output_tokens) for r in results)
    for r in results:
        print(f"guid={r.guid} output_tokens={r.output_tokens}")
    print(f"speculative decoding: {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
