/* Speculative decoding driven end-to-end from C through the ffsv_* ABI
 * — the role of the reference's C++ spec_infer main
 * (reference inference/spec_infer/spec_infer.cc:201: build LLM in tree
 * -verify mode + SSMs in beam-search mode, register requests,
 * generate). The drafts here are 1- and 2-layer truncations of the
 * verifier — the same seeded per-layer-name init makes the shallow
 * weights match automatically, so acceptance is non-trivial even
 * without real checkpoints (weights load via the spec's "weights_npz"
 * in production).
 *
 * Exercises the full spec-JSON surface: a multi-SSM draft set
 * ({"ssms": [...]}) and a "generation_config" adaptive-speculation
 * policy (depth bounds + fallback threshold) on the verifier — the
 * per-request depth controller that keeps spec decoding from ever
 * losing to plain incremental decoding, engaged identically for
 * embedded C hosts and the Python stack. The same object arms the
 * shared-prefix KV cache ("prefix_cache"/"prefix_cache_tokens"): a
 * second request reusing the first one's prompt as its prefix skips
 * those prefill FLOPs, observable below via the ffsv_prefix_* metrics.
 *
 * With a second argument — a directory holding an HF-layout checkpoint
 * (config.json + model.safetensors, as written by
 * flexflow_tpu.models.checkpoint_store / save_tiny_checkpoint) — the
 * example also cold-starts an incremental engine from disk through the
 * spec-JSON "checkpoint_dir" key with "quantize":"int8"
 * quantize-on-load: family and model config come from config.json, not
 * the JSON, which is exactly how a C replica host rejoins a fleet after
 * a crash.
 *
 *   cc spec_infer.c -L../../native/build -lflexflow_tpu_serve \
 *      -lpython3.12 -o spec_infer
 *   ./spec_infer /path/to/repo [/path/to/checkpoint_dir]
 */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include "../../native/include/flexflow_tpu_c.h"

#define MODEL_CORE(layers)                                              \
  "\"family\": \"llama\", \"model_config\": {"                          \
  "\"vocab_size\": 128, \"hidden_size\": 64, "                          \
  "\"intermediate_size\": 128, \"num_hidden_layers\": " #layers ", "    \
  "\"num_attention_heads\": 4, \"num_key_value_heads\": 2, "            \
  "\"max_position_embeddings\": 64}"

/* verifier: 4 layers + the adaptive-speculation policy + the
 * shared-prefix KV pool (4096-token budget) */
#define VERIFIER_JSON                                                   \
  "{" MODEL_CORE(4) ", \"generation_config\": {"                        \
  "\"adaptive\": true, \"spec_depth\": 3, \"min_spec_depth\": 1, "      \
  "\"fallback_margin\": 0.95, \"recover_margin\": 1.05, "               \
  "\"probe_every\": 4, "                                                \
  "\"prefix_cache\": true, \"prefix_cache_tokens\": 4096}}"

/* drafts: two truncations proposing into one merged token tree */
#define DRAFTS_JSON                                                     \
  "{\"ssms\": [{" MODEL_CORE(2) "}, {" MODEL_CORE(1) "}]}"

int main(int argc, char **argv) {
  const char *repo_root = argc > 1 ? argv[1] : NULL;
  if (ffsv_init(repo_root) != 0) {
    fprintf(stderr, "init failed: %s\n", ffsv_last_error());
    return 1;
  }
  void *cfg = ffsv_config_create();
  ffsv_config_set(cfg, "max_requests_per_batch", "2");
  ffsv_config_set(cfg, "max_sequence_length", "64");
  ffsv_config_set(cfg, "max_tokens_per_batch", "16");
  ffsv_config_set(cfg, "kv_cache_dtype", "float32");
  /* observe the controller through ffsv_metrics_dump below */
  ffsv_config_set(cfg, "telemetry", "true");

  void *pair = ffsv_spec_create(cfg, VERIFIER_JSON, DRAFTS_JSON);
  if (!pair) {
    fprintf(stderr, "spec create failed: %s\n", ffsv_last_error());
    return 1;
  }

  int32_t prompt[] = {5, 9, 23, 7};
  long g = ffsv_register_request(pair, prompt, 4, 6);
  /* depth argument 3 = compiled max; generation_config.spec_depth
   * matches, and the controller adapts each request's depth below it */
  if (g < 0 || ffsv_generate_spec(pair, 3) != 1) {
    fprintf(stderr, "spec generate failed: %s\n", ffsv_last_error());
    return 1;
  }
  int32_t out[64];
  int n = ffsv_get_output(pair, g, out, 64);
  if (n <= 0) {
    fprintf(stderr, "no output: %s\n", ffsv_last_error());
    return 1;
  }
  printf("spec request %ld ->", g);
  for (int i = 0; i < n && i < 64; i++) printf(" %d", out[i]);
  printf("\n");
  /* the controller's depth/fallback state is part of the metrics
   * surface — a C host can watch acceptance health without Python */
  char *snap = ffsv_metrics_dump("json");
  if (!snap || !strstr(snap, "ffsv_spec_effective_depth")) {
    fprintf(stderr, "controller metrics missing: %s\n", ffsv_last_error());
    return 1;
  }
  printf("controller metrics present (ffsv_spec_effective_depth)\n");
  free(snap);

  /* Shared-prefix KV reuse: the finished request's prompt is now in the
   * radix pool, so a request extending it matches at admission and
   * skips the shared prefill. The pool's behavior is part of the
   * metrics surface (hits/misses/evictions, shared tokens, occupancy);
   * the exact-token-identity contract is asserted by the Python tests. */
  int32_t p_reuse[] = {5, 9, 23, 7, 40, 41};
  long g_reuse = ffsv_register_request(pair, p_reuse, 6, 4);
  if (g_reuse < 0 || ffsv_generate_spec(pair, 3) != 1 ||
      ffsv_request_status(pair, g_reuse) != 0) {
    fprintf(stderr, "prefix-reuse generate failed: %s\n", ffsv_last_error());
    return 1;
  }
  snap = ffsv_metrics_dump("json");
  if (!snap || !strstr(snap, "ffsv_prefix_cache_hits_total") ||
      !strstr(snap, "ffsv_prefix_shared_tokens_total") ||
      !strstr(snap, "ffsv_prefix_pool_tokens")) {
    fprintf(stderr, "prefix-cache metrics missing: %s\n", ffsv_last_error());
    return 1;
  }
  printf("prefix cache engaged (ffsv_prefix_* metrics present)\n");
  free(snap);

  /* Overload-safety surface: cancellation + per-request timeouts.
   * A request cancelled BEFORE its generate round resolves as
   * status 2 (cancelled); one registered with a microscopic timeout
   * resolves as status 1 (timed_out). Both keep partial output
   * readable, and the finished request above reports status 0. */
  if (ffsv_request_status(pair, g) != 0) {
    fprintf(stderr, "finished request should report status 0\n");
    return 1;
  }
  int32_t p2[] = {11, 3, 19};
  long g_cancel = ffsv_register_request(pair, p2, 3, 6);
  long g_timeout = ffsv_register_request_timeout(pair, p2, 3, 6, 1e-6);
  if (g_cancel < 0 || g_timeout < 0) {
    fprintf(stderr, "register failed: %s\n", ffsv_last_error());
    return 1;
  }
  if (ffsv_request_status(pair, g_cancel) != 4) {
    fprintf(stderr, "pending request should report status 4\n");
    return 1;
  }
  if (ffsv_request_cancel(pair, g_cancel) != 1 ||
      ffsv_request_cancel(pair, g_cancel) != 1) {
    /* second call: flagging an already-flagged (still unfinished)
     * request is still a successful cancel */
    fprintf(stderr, "cancel failed: %s\n", ffsv_last_error());
    return 1;
  }
  if (ffsv_generate_spec(pair, 3) != 2) {
    fprintf(stderr, "generate after cancel/timeout failed: %s\n",
            ffsv_last_error());
    return 1;
  }
  if (ffsv_request_status(pair, g_cancel) != 2) {
    fprintf(stderr, "cancelled request should report status 2, got %d\n",
            ffsv_request_status(pair, g_cancel));
    return 1;
  }
  if (ffsv_request_status(pair, g_timeout) != 1) {
    fprintf(stderr, "timed-out request should report status 1, got %d\n",
            ffsv_request_status(pair, g_timeout));
    return 1;
  }
  if (ffsv_request_cancel(pair, g_cancel) != 0 ||
      ffsv_request_status(pair, 424242) != -1) {
    fprintf(stderr, "finished/unknown guid handling wrong\n");
    return 1;
  }
  printf("cancel + timeout statuses OK\n");

  /* checkpoint cold start: build from disk, config read from the
   * checkpoint's config.json, weights int8-quantized on load */
  if (argc > 2) {
    char ckpt_json[1024];
    snprintf(ckpt_json, sizeof ckpt_json,
             "{\"checkpoint_dir\": \"%s\", \"quantize\": \"int8\"}",
             argv[2]);
    void *llm = ffsv_llm_create(cfg, ckpt_json);
    if (!llm) {
      fprintf(stderr, "checkpoint create failed: %s\n", ffsv_last_error());
      return 1;
    }
    long gc = ffsv_register_request(llm, prompt, 4, 6);
    if (gc < 0 || ffsv_generate(llm) != 1) {
      fprintf(stderr, "checkpoint generate failed: %s\n",
              ffsv_last_error());
      return 1;
    }
    int nc = ffsv_get_output(llm, gc, out, 64);
    if (nc <= 0) {
      fprintf(stderr, "checkpoint output missing: %s\n", ffsv_last_error());
      return 1;
    }
    printf("checkpoint request %ld ->", gc);
    for (int i = 0; i < nc && i < 64; i++) printf(" %d", out[i]);
    printf("\ncheckpoint cold start OK (int8 quantize-on-load)\n");
    ffsv_release(llm);
  }

  ffsv_release(pair);
  ffsv_release(cfg);
  ffsv_shutdown();
  printf("C spec_infer OK\n");
  return 0;
}
