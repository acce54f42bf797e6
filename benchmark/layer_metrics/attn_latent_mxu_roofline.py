"""attn_latent_mxu_roofline - layer: Pallas kernel (kernels/attention.py flash_attend).

Arithmetic of the (query, key) pairs the traced prefill steps attended (their tokens x ffsv_prefill_attended_pairs_total over ffsv_prefill_tokens_total in the window x latent layers x families/mistral4.latent_pair_flops: the PUBLISHED arithmetic of a pair, whatever form computes it) over the chip's bf16 peak, divided by flash_attend_latent's self time inside those steps.
Returns None when its source is not there; the harness then leaves the
metric out of the line.
"""

from benchmark.layer_metrics import latent_readers as L


def read(ctx):
    return L.attn_latent_mxu_roofline(ctx)
