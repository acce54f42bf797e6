/* Flat C API for the native runtime components of flexflow_tpu.
 *
 * Capability parity with the reference's native layer: the GPT-2 byte-level
 * BPE tokenizer (reference src/runtime/gpt_tokenizer.cc, 324 LoC), the
 * SentencePiece tokenizer, the C graph builder and the ffsv_* serving ABI.
 * The Python runtime binds the first three via ctypes (reference used a
 * cffi C API, src/c/flexflow_c.cc); scheduling is the Python serving loop
 * and device compute stays in XLA/Pallas.
 */

#ifndef FLEXFLOW_TPU_C_H
#define FLEXFLOW_TPU_C_H

#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ---------------- GPT-2 byte-level BPE tokenizer ---------------- */

/* Create from vocab.json ({"token": id, ...}) and merges.txt file paths.
 * Returns NULL on error. */
void *ffbpe_create(const char *vocab_json_path, const char *merges_path);

/* Create from in-memory buffers (NUL-terminated). */
void *ffbpe_create_from_buffers(const char *vocab_json, const char *merges);

void ffbpe_destroy(void *handle);

int ffbpe_vocab_size(void *handle);

/* Encode UTF-8 text (explicit length — embedded NULs are data, not
 * terminators) into ids. Returns the number of ids produced, or a negative
 * value whose magnitude is the required capacity if cap is too small. */
int ffbpe_encode(void *handle, const char *text, int text_len,
                 int32_t *out_ids, int cap);

/* Decode ids to UTF-8. Returns bytes written (excluding NUL), or negative
 * required capacity. */
int ffbpe_decode(void *handle, const int32_t *ids, int n, char *out, int cap);

/* ---------------- SentencePiece tokenizer (LLaMA family) ----------------
 * Reference: tokenizers-cpp selected by ModelType in
 * request_manager.cc:109; here native/src/sp_tokenizer.cpp. */
void *ffsp_create(const char *model_path);
void *ffsp_create_from_buffer(const uint8_t *data, int n);
void ffsp_destroy(void *handle);
int ffsp_vocab_size(void *handle);
int ffsp_model_type(void *handle);           /* 1=unigram 2=bpe */
int ffsp_bos_id(void *handle);
int ffsp_eos_id(void *handle);
int ffsp_unk_id(void *handle);
int ffsp_encode(void *handle, const char *text, int text_len,
                int32_t *out_ids, int cap);  /* returns total ids */
int ffsp_decode(void *handle, const int32_t *ids, int n, char *out,
                int cap);                    /* returns total bytes */
int ffsp_piece_to_id(void *handle, const char *piece);


/* ---------------- model graph builder ----------------
 * Reference: the model-builder half of the C ABI (src/c/flexflow_c.cc
 * flexflow_model_create + per-op wrappers). A C host constructs the graph
 * and serializes it as the frontend IR (JSON lines); the runtime loads it
 * with flexflow_tpu.torch.model.file_to_ff and compiles/trains. Node ids
 * are >= 0; every function returns a negative value on error. */
void *ffgb_create(void);
void ffgb_destroy(void *handle);
int ffgb_input(void *handle, int index, const char *name);
int ffgb_dense(void *handle, int in, int out_dim, int use_bias,
               const char *name);
int ffgb_conv2d(void *handle, int in, int out_channels, int kh, int kw,
                int sh, int sw, int ph, int pw, int groups, int use_bias,
                const char *name);
int ffgb_pool2d(void *handle, int in, int kh, int kw, int sh, int sw,
                int ph, int pw, int is_max, const char *name);
int ffgb_unary(void *handle, int in, const char *op, const char *name);
int ffgb_binary(void *handle, int a, int b, const char *op,
                const char *name);
int ffgb_concat(void *handle, const int *ins, int n, int axis,
                const char *name);
int ffgb_softmax(void *handle, int in, int axis, const char *name);
int ffgb_dropout(void *handle, int in, double rate, const char *name);
int ffgb_embedding(void *handle, int in, int num_entries, int out_dim,
                   const char *name);
int ffgb_reshape(void *handle, int in, const int *shape, int ndims,
                 const char *name);
/* Normalize over the LAST ndims dims (sizes in normalized_shape). */
int ffgb_layer_norm(void *handle, int in, const int *normalized_shape,
                    int ndims, int affine, double eps, const char *name);
int ffgb_batch_norm(void *handle, int in, const char *name);
/* dim <= 0 -> default (input's last-dim size). */
int ffgb_rms_norm(void *handle, int in, double eps, int dim,
                  const char *name);
/* Training MHA; pass the same id for q/k/v for self-attention. */
int ffgb_multihead_attention(void *handle, int q, int k, int v,
                             int embed_dim, int num_heads, double dropout,
                             const char *name);
/* op: add subtract multiply divide; reverse != 0 -> (scalar OP x). */
int ffgb_scalar(void *handle, int in, const char *op, double scalar,
                int reverse, const char *name);
int ffgb_transpose(void *handle, int in, const int *perm, int ndims,
                   const char *name);
/* Reduction dims must be unique and in [0, FFGB_MAX_DIMS); exact-rank
 * validation happens at IR load. */
#define FFGB_MAX_DIMS 8
int ffgb_mean(void *handle, int in, const int *dims, int ndims,
              int keepdims, const char *name);
/* dtype name per flexflow_tpu.ffconst.DataType values, e.g. "float32". */
int ffgb_cast(void *handle, int in, const char *dtype, const char *name);
int ffgb_output(void *handle, const int *ids, int n);
int ffgb_save(void *handle, const char *path);
int ffgb_serialize(void *handle, char *out, int cap);

/* ---------------- serving ABI (libflexflow_tpu_serve.so) -----------
 * Config create/parse, model build, weight load, request registration
 * and generate — the reference's full-surface C API role
 * (src/c/flexflow_c.cc; flexflow_model_generate :1584), letting a
 * non-Python host run serving end-to-end (the reference's C++ mains,
 * inference/incr_decoding/incr_decoding.cc:118). Implemented in
 * native/src/serve_c.cpp over an embedded CPython runtime (the role
 * Legion plays in the reference); link -lflexflow_tpu_serve AND the
 * matching -lpython3.x. Handles are opaque; release with ffsv_release.
 * Not thread-safe (like the reference C API). */

/* Init the embedded runtime; repo_root = dir containing flexflow_tpu
 * (NULL if already importable). 0 on success. */
int ffsv_init(const char *repo_root);
const char *ffsv_last_error(void);
void ffsv_release(void *handle);
/* Call last, before returning from main: stops the runtime's threads so
 * the host's exit() does not race them. No ffsv_* call may follow. */
void ffsv_shutdown(void);

void *ffsv_config_create(void);
/* Reference flexflow_config_parse_args (same flag set as FFConfig.from_args). */
void *ffsv_config_parse_args(int argc, const char **argv);
int ffsv_config_set(void *cfg, const char *key, const char *value);
char *ffsv_config_get(void *cfg, const char *key);   /* caller frees */

/* Build + compile a serving model. spec_json:
 * {"family":"llama|opt|falcon|mpt|starcoder",
 *  "model_config":{...family Config kwargs...},
 *  "mode":"inc|spec|tree", "weights_npz":"path" (optional),
 *  "checkpoint_dir":"path" (optional), "quantize":"int8|int4" (optional),
 *  "generation_config":{...} (optional)}
 *
 * "checkpoint_dir" cold-starts the model from an HF-layout disk
 * checkpoint (config.json + model.safetensors or pytorch_model.bin, as
 * written by flexflow_tpu.models.checkpoint_store): the family and
 * model_config are read from config.json — supplying "model_config" or
 * "weights_npz" alongside it is an error, and an explicit "family" must
 * agree with the checkpoint. "quantize" compresses the weights to int8
 * or int4 on load (quantize-on-load; works with either weight source),
 * token-identical to quantizing the same weights in memory.
 *
 * generation_config keys (all optional; defaults in parentheses) drive
 * the adaptive speculation controller — the same per-request depth
 * tuning + incremental fallback the Python serving stack runs, so an
 * embedded C host's spec decoding never loses to plain decoding:
 *   "adaptive": bool        (true)  controller on/off
 *   "spec_depth": int       (0)     max draft depth; 0 = caller's depth
 *   "min_spec_depth": int   (1)     shrink floor
 *   "fallback_margin": f    (0.95)  park below this est. speedup
 *   "recover_margin": f     (1.05)  un-park above this (hysteresis)
 *   "probe_every": int      (4)     fallback blocks between probe rounds
 *   "ewma_alpha": f         (0.4)   acceptance-EWMA smoothing
 *   "draft_cost_ratio": f   (0)     0 = estimate from parameter bytes
 * plus the shared-prefix KV cache (serve/prefix_cache.py — requests
 * whose prompts share a prefix with an earlier prompt skip those
 * prefill FLOPs; token-identical to the no-reuse path):
 *   "prefix_cache": bool        (false)  arm the refcounted radix pool
 *   "prefix_cache_tokens": int  (0)      pool budget in KV tokens;
 *                                        0 = library default (65536)
 * Unknown keys fail the create (ffsv_last_error) rather than running
 * with silently-defaulted policy. Controller state is observable via
 * ffsv_metrics_dump: ffsv_spec_effective_depth / _fallback_total /
 * _fallback_active / _acceptance_ewma; prefix-cache state via
 * ffsv_prefix_cache_hits_total / _misses_total / _evictions_total,
 * ffsv_prefix_shared_tokens_total and the ffsv_prefix_pool_tokens
 * occupancy gauge. */
void *ffsv_llm_create(void *cfg, const char *spec_json);

/* Speculative-decoding pair: verifier (tree-verify) + draft SSM
 * (beam-search) — the reference's spec_infer main
 * (inference/spec_infer/spec_infer.cc:201). Same JSON schema; the
 * VERIFIER spec's generation_config carries the pair-level adaptive
 * policy. draft_json is either one model spec or {"ssms":[spec, ...]}
 * for multi-SSM drafting (all SSMs propose into one merged token tree
 * per round). */
void *ffsv_spec_create(void *cfg, const char *verifier_json,
                       const char *draft_json);
/* spec_depth: draft-chain depth per round, must be >= 1 (returns -1
 * otherwise; there is no 0-means-default). The verifier spec's
 * generation_config.spec_depth, when set, overrides this argument;
 * with the adaptive controller on (default) the value is the COMPILED
 * maximum and the effective per-request depth adapts below it. */
int ffsv_generate_spec(void *llm, int spec_depth);

/* Register a tokenized prompt; returns the request guid, or -1. When
 * the spec JSON's generation_config sets "timeout_s" > 0, that default
 * wall-clock bound applies to every request registered this way. */
long ffsv_register_request(void *llm, const int32_t *tokens, int n_tokens,
                           int max_new_tokens);
/* Register with an explicit per-request wall-clock timeout (seconds;
 * <= 0 = none, overriding any spec-JSON default). A request past its
 * deadline is cancelled between decode rounds: its slot is freed, the
 * partial output stays readable via ffsv_get_output, and
 * ffsv_request_status reports 1 (timed_out). Returns the guid, or -1. */
long ffsv_register_request_timeout(void *llm, const int32_t *tokens,
                                   int n_tokens, int max_new_tokens,
                                   double timeout_s);
/* Flag a registered request for cancellation; the next
 * ffsv_generate/ffsv_generate_spec round reaps it (slot freed, partial
 * output kept, status -> 2 cancelled). Works on all scheduler paths
 * (incremental and fused speculative).
 * Returns 1 if cancelled, 0 if unknown or already finished, -1 error. */
int ffsv_request_cancel(void *llm, long guid);
/* Resolution status of a request guid: -1 unknown, 0 ok (completed),
 * 1 timed_out, 2 cancelled, 3 error, 4 registered-but-unfinished.
 * Timed-out/cancelled requests still expose their partial tokens via
 * ffsv_get_output / ffsv_get_output_text. */
int ffsv_request_status(void *llm, long guid);
/* Decode every pending request to completion (reference
 * flexflow_model_generate). Returns finished count, or -1. Requests
 * whose deadline expires mid-run, or that were cancelled, count toward
 * the finished total (they RESOLVED — check ffsv_request_status). */
int ffsv_generate(void *llm);
/* Fetch a finished request's output tokens; returns the full count
 * (recall with more room if it exceeds cap), or -1. */
int ffsv_get_output(void *llm, long guid, int32_t *out, int cap);

/* Text surface (reference flexflow_model_generate takes text): attach
 * the GPT-2 BPE tokenizer (returns vocab size or -1), register text
 * prompts, fetch decoded text (malloc'd; caller frees). An unknown or
 * unfinished guid returns NULL (see ffsv_last_error), so empty text is
 * always a real, finished result. */
int ffsv_register_bpe_tokenizer(void *llm, const char *vocab_json_path,
                                const char *merges_path);
long ffsv_register_request_text(void *llm, const char *text,
                                int max_new_tokens);
char *ffsv_get_output_text(void *llm, long guid);

/* Snapshot the serving telemetry registry (flexflow_tpu/telemetry):
 * acceptance/latency histograms, batch occupancy, per-round counters.
 * format: "json" (structured, incl. exact p50/p90/p99 per histogram) or
 * "prometheus" (text exposition). Enable by setting the config field
 * "telemetry" to "true" before ffsv_llm_create (optionally
 * "telemetry_trace_path" for the JSONL span trace); disabled telemetry
 * dumps an empty snapshot ("{}" / "").
 *
 * When the process also runs a replica fleet (FleetTelemetry /
 * ReplicaPool on the Python side), the dump is the AGGREGATE across the
 * global registry plus every live per-replica registry — counters sum,
 * histograms merge bucket-exactly — so one call sees the whole fleet.
 * Per-replica breakdowns (replica="N" labels in prometheus, a
 * "replicas" map in json) are available via FleetTelemetry.snapshot /
 * to_prometheus in-process; the C surface exposes the pooled view.
 * Unknown format strings fail (NULL + ffsv_last_error) rather than
 * guessing. Returns a malloc'd string the caller frees, or NULL on
 * error (see ffsv_last_error). */
char *ffsv_metrics_dump(const char *format);

#ifdef __cplusplus
}
#endif

#endif /* FLEXFLOW_TPU_C_H */
