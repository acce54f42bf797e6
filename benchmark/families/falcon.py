"""Family "falcon": builds a serving handle for a Falcon configuration file
(incremental decoding), and holds what the yardstick needs to know about the
family's shapes."""

from __future__ import annotations

from . import _common as C

# Reference check: relative L2 error of the logits, worst position. The
# program computes in bfloat16 (8 bits of mantissa, relative rounding 2**-8
# = 0.4% per operation, accumulating over two layers and the head); the
# reference in float32 on the same dequantised int8 weights. Measured on the
# chip at the published widths: see PERF.md section 6. The bound is about
# three times that, and well under what 8-bit float arithmetic (6%) or a
# dropped term would give.
REFERENCE_TOL = 0.03
REFERENCE_LAYERS = 2


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.falcon import FalconConfig

    c = FalconConfig.from_hf_config(cfg)
    if layers is not None:
        c.num_hidden_layers = layers
    return c


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.falcon import create_falcon_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    llm = C.build_model(C.ffconfig(cfg, telemetry), create_falcon_model,
                        _model_cfg(cfg), InferenceMode.INC_DECODING_MODE)
    return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}


def warm_and_check(built: dict, cfg: dict) -> dict:
    """Reach every program the loop uses, before the clock of the window."""
    new = 24
    res = C.serve_pass(built["handle"],
                       C.warm_prompts(cfg, cfg["vocab_size"]), new)
    return {"ok": C.all_ok(res, new),
            "ttft_attributed": all(r.ttft_s > 0 for r in res),
            "scheduler_loop": built["handle"].rm.scheduler_loop}


def decode_weights(cfg: dict):
    """(name, rows, cols, bytes per element) of every matrix one decode
    step multiplies by, int8 payload plus the float32 scale per column."""
    H, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    hd = H // cfg["num_attention_heads"]
    kv = 1 if cfg.get("multi_query", True) else cfg["num_attention_heads"]
    b = C.weight_element_bytes(cfg)
    per_layer = [("wq", H, H, b), ("wk", H, kv * hd, b), ("wv", H, kv * hd, b),
                 ("wo", H, H, b), ("up", H, 4 * H, b), ("down", 4 * H, H, b),
                 ("scales", 1, 2 * H + 2 * kv * hd + 5 * H, 4.0),
                 ("ln", 1, 2 * H, 2.0)]
    out = [(f"h.{i}.{n}", r, c, e) for i in range(L) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("ln_f", 1, 2 * H, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = 1 if cfg.get("multi_query", True) else cfg["num_attention_heads"]
    return 2.0 * kv * hd * 2 * cfg["num_hidden_layers"]     # k and v, bf16


def _reference_weights(m, L):
    p = m.params
    layers = []
    for i in range(L):
        a = p[f"h.{i}.self_attention"]
        ln = p[f"h.{i}.input_layernorm"]
        layers.append({
            "ln_g": C.dense(ln["gamma"]), "ln_b": C.dense(ln["beta"]),
            "wq": C.dense(a["wq"]), "wk": C.dense(a["wk"]),
            "wv": C.dense(a["wv"]), "wo": C.dense(a["wo"]),
            "up": C.dense(p[f"h.{i}.mlp.dense_h_to_4h"]["kernel"]),
            "down": C.dense(p[f"h.{i}.mlp.dense_4h_to_h"]["kernel"])})
    return {"emb": C.dense(p["word_embeddings"]["weight"]), "layers": layers,
            "lnf_g": C.dense(p["ln_f"]["gamma"]),
            "lnf_b": C.dense(p["ln_f"]["beta"]),
            "head": C.dense(p["lm_head"]["kernel"])}


def reference_check(cfg: dict, reference) -> dict:
    from flexflow_tpu.models.falcon import create_falcon_model

    return C.reference_check(cfg, create_falcon_model,
                             _model_cfg(cfg, REFERENCE_LAYERS),
                             _reference_weights, reference, REFERENCE_TOL)
