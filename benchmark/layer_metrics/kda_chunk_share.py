"""kda_chunk_share - layer: Pallas kernel (kernels/linear_attention.py kda_chunk).

The chunked form's kernel's (kda_chunk: a prefill step's rows through the recurrent state, chunks of 64 tokens by matrix products) share of the traced device time: self time of the device operations whose name contains ``kda_chunk`` over device-busy time of the traced stretch.
``linear_attn_share`` still reads the RECURRENT form only (``kda_state_step``, a decode step's kernel; neither name contains the other), as ``kda_state_hbm_roofline`` does; since PR 55 the chunked form is no longer unnamed XLA fusions (what ``kda_readers.py`` and ``linear_attn_share.py`` still say of it), and this share is where its time is read.
Returns None when its source is not there (a program without the kernel: any commit before PR 55; any other model; a process that takes the jnp form); the harness then
leaves the metric out of the line.
"""

from benchmark.lib import trace as TR

KERNEL = "kda_chunk"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    ns = TR.time_of(tr["ops"], KERNEL)
    return 100.0 * ns / TR.total(tr["merged"]) if ns > 0 else None
