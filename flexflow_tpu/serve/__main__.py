"""CLI serving entry: ``python -m flexflow_tpu.serve`` (the launcher-parity
surface of the reference's flexflow_python / inference mains).

Examples:
  python -m flexflow_tpu.serve --model <hf-dir> --prompt "Hello" \
      --max-new-tokens 64
  python -m flexflow_tpu.serve --model <hf-dir> --ssm-model <draft-dir> \
      --prompt "Hello"                       # speculative decoding
With no --model, serves a randomly-initialized LLaMA-class model (zero-
egress default) so the full stack can be exercised anywhere.
"""

from __future__ import annotations

import argparse
import time


def _default_models(with_ssm: bool):
    import torch
    import transformers

    torch.manual_seed(0)
    kw = dict(vocab_size=1024, hidden_size=256, intermediate_size=688,
              num_attention_heads=8, num_key_value_heads=4,
              max_position_embeddings=512, tie_word_embeddings=False)
    llm = transformers.LlamaForCausalLM(
        transformers.LlamaConfig(num_hidden_layers=4, **kw))
    if not with_ssm:
        return llm, None
    ssm = transformers.LlamaForCausalLM(
        transformers.LlamaConfig(num_hidden_layers=2, **kw))
    sd = {k: v for k, v in llm.state_dict().items()
          if "layers.2." not in k and "layers.3." not in k}
    ssm.load_state_dict(sd, strict=False)
    return llm, ssm


def main(argv=None):
    from flexflow_tpu import serve as ff_serve

    p = argparse.ArgumentParser(prog="python -m flexflow_tpu.serve")
    p.add_argument("--model", default="", help="HF checkpoint dir")
    p.add_argument("--ssm-model", default="",
                   help="draft model dir (enables speculative decoding)")
    p.add_argument("--prompt", action="append", default=None)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--max-requests-per-batch", type=int, default=4)
    p.add_argument("--max-seq-length", type=int, default=256)
    p.add_argument("--max-tokens-per-batch", type=int, default=64)
    p.add_argument("--tensor-parallelism-degree", type=int, default=1)
    p.add_argument("--8bit-quantization", dest="q8", action="store_true")
    p.add_argument("--4bit-quantization", dest="q4", action="store_true")
    p.add_argument("--offload", action="store_true")
    p.add_argument("--output-file", default="")
    args = p.parse_args(argv)

    ff_serve.init()
    if args.model:
        llm_src = args.model
        ssm_src = args.ssm_model or None
    else:
        if args.ssm_model and args.ssm_model != "builtin":
            p.error("--ssm-model <dir> requires --model (a real draft "
                    "cannot speculate for the built-in random verifier); "
                    "use '--ssm-model builtin' for the demo draft pair")
        llm_src, ssm_src = _default_models(with_ssm=bool(args.ssm_model))

    llm = ff_serve.LLM(llm_src, output_file=args.output_file)
    ssms = [ff_serve.SSM(ssm_src)] if ssm_src is not None else []
    quant = "int4" if args.q4 else ("int8" if args.q8 else None)
    llm.compile(
        max_requests_per_batch=args.max_requests_per_batch,
        max_seq_length=args.max_seq_length,
        max_tokens_per_batch=args.max_tokens_per_batch,
        model_specific_tensor_parallelism_degree=args.tensor_parallelism_degree,
        ssms=ssms,
        **({"quantization_type": quant} if quant else {}),
        **({"cpu_offload": True} if args.offload else {}))

    prompts = args.prompt
    if not prompts:
        prompts = (["Hello, my name is"] if llm.tokenizer is not None
                   else [[1, 5, 9, 23], [1, 44, 17]])
    t0 = time.time()
    results = llm.generate(prompts, max_new_tokens=args.max_new_tokens)
    dt = time.time() - t0
    total = sum(len(r.output_tokens) for r in results)
    for r in results:
        print(f"guid={r.guid} output={r.output_text or r.output_tokens}")
    print(f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s)"
          + (" [speculative]" if ssms else ""))
    return 0


if __name__ == "__main__":
    # here, not in main(): tests call main() and must not arm the cache
    from flexflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
