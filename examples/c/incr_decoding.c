/* Incremental-decoding serving driven END-TO-END from C through the
 * ffsv_* ABI — the role of the reference's C++ serving main
 * (reference inference/incr_decoding/incr_decoding.cc:118, which drives
 * src/c/flexflow_c.cc flexflow_model_generate:1584). Config creation,
 * model build+compile, request registration and generation all happen
 * through the C surface; the embedded Python+XLA runtime plays the part
 * Legion plays in the reference.
 *
 *   cc incr_decoding.c -L../../native/build -lflexflow_tpu_serve \
 *      -lpython3.12 -o incr_decoding
 *   ./incr_decoding /path/to/repo
 *
 * Weights are seeded-random (real checkpoints load via the spec's
 * "weights_npz"); the point is the full C-driven serving round trip.
 */
#include <stdio.h>
#include <stdlib.h>

#include "../../native/include/flexflow_tpu_c.h"

int main(int argc, char **argv) {
  const char *repo_root = argc > 1 ? argv[1] : NULL;
  if (ffsv_init(repo_root) != 0) {
    fprintf(stderr, "init failed: %s\n", ffsv_last_error());
    return 1;
  }

  /* reference-style flag parsing (subset of flexflow_config_parse_args) */
  const char *flags[] = {"--max-requests-per-batch", "4"};
  void *cfg = ffsv_config_parse_args(2, flags);
  if (!cfg) {
    fprintf(stderr, "config failed: %s\n", ffsv_last_error());
    return 1;
  }
  ffsv_config_set(cfg, "max_sequence_length", "64");
  ffsv_config_set(cfg, "max_tokens_per_batch", "16");
  ffsv_config_set(cfg, "kv_cache_dtype", "float32");

  void *llm = ffsv_llm_create(
      cfg,
      "{\"family\": \"llama\", \"mode\": \"inc\", \"model_config\": {"
      "\"vocab_size\": 128, \"hidden_size\": 64, "
      "\"intermediate_size\": 128, \"num_hidden_layers\": 2, "
      "\"num_attention_heads\": 4, \"num_key_value_heads\": 2, "
      "\"max_position_embeddings\": 64}}");
  if (!llm) {
    fprintf(stderr, "llm create failed: %s\n", ffsv_last_error());
    return 1;
  }

  int32_t prompt_a[] = {5, 9, 23, 7};
  int32_t prompt_b[] = {11, 42, 3};
  long ga = ffsv_register_request(llm, prompt_a, 4, 6);
  long gb = ffsv_register_request(llm, prompt_b, 3, 6);
  if (ga < 0 || gb < 0) {
    fprintf(stderr, "register failed: %s\n", ffsv_last_error());
    return 1;
  }

  int finished = ffsv_generate(llm);
  if (finished != 2) {
    fprintf(stderr, "generate failed (%d): %s\n", finished,
            ffsv_last_error());
    return 1;
  }

  long guids[] = {ga, gb};
  for (int r = 0; r < 2; r++) {
    int32_t out[64];
    int n = ffsv_get_output(llm, guids[r], out, 64);
    if (n <= 0) {
      fprintf(stderr, "no output for %ld: %s\n", guids[r],
              ffsv_last_error());
      return 1;
    }
    printf("request %ld ->", guids[r]);
    for (int i = 0; i < n && i < 64; i++) printf(" %d", out[i]);
    printf("\n");
  }

  ffsv_release(llm);
  ffsv_release(cfg);
  ffsv_shutdown();
  printf("C incr_decoding OK\n");
  return 0;
}
