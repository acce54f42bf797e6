"""Family "opt": builds a serving handle for an OPT configuration file.
With a ``speculative`` group in the file the handle serves SpecInfer: the
verifier in tree-verify mode and a draft that is the verifier's first
``draft_layers`` layers."""

from __future__ import annotations

from . import _common as C

# See families/falcon.py for the reasoning; OPT's biases and learned
# positions add nothing to the rounding. Measured: PERF.md section 6.
REFERENCE_TOL = 0.03
REFERENCE_LAYERS = 2


def _model_cfg(cfg: dict, layers=None):
    from flexflow_tpu.models.opt import OPTConfig

    c = OPTConfig.from_hf_config(cfg)
    if layers is not None:
        c.num_hidden_layers = layers
    return c


def build(cfg: dict, telemetry: bool) -> dict:
    from flexflow_tpu.ffconst import InferenceMode
    from flexflow_tpu.models.opt import create_opt_model
    from flexflow_tpu.serve.loadgen import EngineHandle

    ffc = C.ffconfig(cfg, telemetry)
    spec = cfg.get("speculative")
    if not spec:
        llm = C.build_model(ffc, create_opt_model, _model_cfg(cfg),
                            InferenceMode.INC_DECODING_MODE)
        return {"handle": EngineHandle(llm), "llm": llm, "models": [llm]}
    llm = C.build_model(ffc, create_opt_model, _model_cfg(cfg),
                        InferenceMode.TREE_VERIFY_MODE)
    nd, L, eps = spec["draft_layers"], cfg["num_hidden_layers"], spec["verifier_damp"]
    # Seeded random weights give an independent draft no acceptance. So the
    # draft is the verifier's first layers, and the residual writes of the
    # verifier's deeper layers are damped: a stated high-acceptance regime.
    for i in range(nd, L):
        for lname, names in ((f"layers.{i}.self_attn", ("wo", "bo")),
                             (f"layers.{i}.fc2", ("kernel", "bias"))):
            for w in names:
                if w in llm.params[lname]:
                    llm.params[lname][w] = C.scale_leaf(llm.params[lname][w], eps)
    ssm = C.build_model(ffc, create_opt_model, _model_cfg(cfg, nd),
                        InferenceMode.BEAM_SEARCH_MODE)
    for lname, lp in ssm.params.items():
        for w in lp:
            lp[w] = llm.params[lname][w]
    return {"handle": EngineHandle(llm, ssms=[ssm], spec_depth=spec["spec_depth"]),
            "incremental": EngineHandle(llm),
            "llm": llm, "models": [llm, ssm]}


def warm_and_check(built: dict, cfg: dict) -> dict:
    """Every program of the loop, and greedy SpecInfer against incremental
    decoding on the same replayed requests."""
    new = 32
    prompts = C.warm_prompts(cfg, cfg["vocab_size"])
    out = {}
    if "incremental" in built:
        incr = C.serve_pass(built["incremental"], prompts, new)
        built["incremental"].stop_server()
        out["incremental_ok"] = C.all_ok(incr, new)
    res = C.serve_pass(built["handle"], prompts, new)
    out["ok"] = C.all_ok(res, new) and out.get("incremental_ok", True)
    out["ttft_attributed"] = all(r.ttft_s > 0 for r in res)
    out["scheduler_loop"] = built["handle"].rm.scheduler_loop
    if "incremental" in built:
        n = sum(a.output_tokens == b.output_tokens for a, b in zip(incr, res))
        out["spec_equals_incremental"] = f"{n}/{len(res)}"
        out["ok"] = out["ok"] and n == len(res)
    return out


def decode_weights(cfg: dict):
    H, F, V, L = (cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"],
                  cfg["num_hidden_layers"])
    b = C.weight_element_bytes(cfg)
    per_layer = [("wq", H, H, b), ("wk", H, H, b), ("wv", H, H, b),
                 ("wo", H, H, b), ("fc1", H, F, b), ("fc2", F, H, b),
                 ("scales", 1, 5 * H + F, 4.0), ("biases", 1, 5 * H + F, 2.0),
                 ("ln", 1, 4 * H, 2.0)]
    out = [(f"layers.{i}.{n}", r, c, e) for i in range(L) for n, r, c, e in per_layer]
    return out + [("lm_head", H, V, b), ("lm_head.scale", 1, V, 4.0),
                  ("final_layer_norm", 1, 2 * H, 2.0)]


def cache_bytes_per_token(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * 2 * cfg["num_hidden_layers"]


def _reference_weights(m, L):
    p = m.params
    layers = []
    for i in range(L):
        a = p[f"layers.{i}.self_attn"]
        l1, l2 = p[f"layers.{i}.self_attn_layer_norm"], p[f"layers.{i}.final_layer_norm"]
        f1, f2 = p[f"layers.{i}.fc1"], p[f"layers.{i}.fc2"]
        layers.append({
            "ln1_g": C.dense(l1["gamma"]), "ln1_b": C.dense(l1["beta"]),
            "ln2_g": C.dense(l2["gamma"]), "ln2_b": C.dense(l2["beta"]),
            **{k: C.dense(a[k]) for k in ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo")},
            "fc1": C.dense(f1["kernel"]), "b1": C.dense(f1["bias"]),
            "fc2": C.dense(f2["kernel"]), "b2": C.dense(f2["bias"])})
    return {"emb": C.dense(p["embed_tokens"]["weight"]),
            "pos": C.dense(p["embed_positions"]["weight"]), "layers": layers,
            "lnf_g": C.dense(p["final_layer_norm"]["gamma"]),
            "lnf_b": C.dense(p["final_layer_norm"]["beta"]),
            "head": C.dense(p["lm_head"]["kernel"])}


def reference_check(cfg: dict, reference) -> dict:
    from flexflow_tpu.models.opt import create_opt_model

    return C.reference_check(cfg, create_opt_model,
                             _model_cfg(cfg, REFERENCE_LAYERS),
                             _reference_weights, reference, REFERENCE_TOL)
