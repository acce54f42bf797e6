"""Batch descriptors for serving steps.

Capability parity with the reference BatchConfig family (reference
include/flexflow/batch_config.h: BatchConfig :39 with MAX_NUM_REQUESTS=64
:57 / MAX_NUM_TOKENS=1024 :58, BeamSearchBatchConfig with MAX_BEAM_WIDTH=1
:125 / MAX_BEAM_DEPTH=8 :126, TreeVerifyBatchConfig with committed_tokens
:92-98), which are POD structs shipped by-value to every Legion task.

TPU-first redesign: the reference flattens all in-flight tokens into one
[MAX_NUM_TOKENS] list because Legion tasks are dynamically shaped. Under XLA
everything must be static-shaped, so the batch is **request-slot major**:
``tokens[max_requests, q]`` where ``q`` is the per-step token width (1 for
incremental decoding, the prefill chunk for prompt processing, the tree size
for verification). Each distinct ``q`` compiles one program; the scheduler
buckets steps so there is no recompile storm. Inactive slots and padding
positions are masked, never branched on — the step program is identical for
every batch composition (the moral equivalent of the reference's Legion
trace replay, request_manager.cc:1841-1856, is XLA's compiled-once step).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

# Reference include/flexflow/batch_config.h:57-58
MAX_NUM_REQUESTS = 64
MAX_NUM_TOKENS = 1024
# Reference include/flexflow/batch_config.h:125-126
MAX_BEAM_WIDTH = 1
MAX_BEAM_DEPTH = 8
# Reference request_manager.cc:1829 (depth-4 in-flight batch pipeline)
DEFAULT_PIPELINE_DEPTH = 4


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """How a block-diffusion model generates (a model's property, set by
    its builder's ``FFModel.unmasking_head`` and read off the compiled model
    as ``FFModel.block_diffusion``; never a serving option). A row's next
    ``block_length`` positions start as mask tokens after what is known of
    them; a denoise pass runs the block against the cache and the block
    itself, stores nothing that lasts, and unmasks every masked position
    whose pick is more probable than ``threshold``, or the ``floor`` most
    confident where fewer clear it; the pass that leaves no mask emits the
    block, and the row's next pass carries it in front of the next block:
    its keys and values are what the cache keeps, and the row's stored
    length grows by the block (serve/engine._diffusion_block)."""

    block_length: int
    denoising_steps: int
    threshold: float
    mask_token_id: int

    def __post_init__(self):
        if self.block_length % self.denoising_steps:
            raise NotImplementedError(
                f"a schedule of {self.denoising_steps} denoising steps over "
                f"a block of {self.block_length}: the floor of a pass is "
                "one number, block_length / denoising_steps")

    @property
    def floor(self) -> int:
        """Positions a denoise pass unmasks at least (the schedule)."""
        return self.block_length // self.denoising_steps

    def passes_for(self, tokens: int) -> int:
        """The most passes ``tokens`` more tokens of a row can take: every
        block at the floor (the pass that completes a block emits it)."""
        return -(-tokens // self.block_length) * self.denoising_steps

    def emitted_most(self, passes: int) -> int:
        """The most tokens a row can emit in ``passes``: a block a pass,
        where every pick clears the threshold."""
        return self.block_length * passes


@dataclasses.dataclass
class GenerationConfig:
    """Sampling + speculation-policy configuration (reference
    include/flexflow/inference.h:23-33 covers the sampling half; the
    adaptive-speculation knobs drive serve/spec_controller.py and are
    settable from embedded C hosts through the ``ffsv`` spec JSON's
    ``generation_config`` object — see capi_host.llm_create)."""

    do_sample: bool = False
    temperature: float = 0.8
    topp: float = 0.6
    # --- adaptive speculation controller (serve/spec_controller.py) ---
    # On by default: spec decoding must never lose to incremental — the
    # controller tunes per-request draft depth from observed acceptance
    # and parks hopeless requests on the fused incremental decode block
    # (token-identical output either way; greedy acceptance commits the
    # verifier's own argmax sequence).
    adaptive_spec: bool = True
    # default per-request wall-clock bound (seconds); 0 = no timeout.
    # Applied at registration by embedded C hosts (capi_host) — a
    # request past its deadline is cancelled between decode rounds and
    # resolves with timed_out status and its partial output.
    timeout_s: float = 0.0
    spec_depth: int = 0             # 0 = caller's depth / engine max
    min_spec_depth: int = 1
    spec_fallback_margin: float = 0.95   # park below this est. speedup
    spec_recover_margin: float = 1.05    # un-park above this (hysteresis)
    spec_probe_every: int = 4            # fallback blocks between probes
    spec_ewma_alpha: float = 0.4
    spec_draft_cost_ratio: float = 0.0   # 0 = estimate from param bytes
    # --- shared-prefix KV cache (serve/prefix_cache.py, ISSUE 19) ---
    # Off by default: arming it attaches a refcounted radix pool to the
    # RequestManager — admission-time longest-prefix match, grant-time
    # KV install (those prefill FLOPs skipped), insert-on-finish of
    # newly seen prompts. Token-identical to the no-reuse path (greedy
    # decode depends only on the token prefix). With the cache on, the
    # incremental path runs the host scheduler loop (the pool lives
    # host-side). prefix_cache_tokens is the pool budget in tokens
    # (0 = prefix_cache.DEFAULT_POOL_TOKENS).
    prefix_cache: bool = False
    prefix_cache_tokens: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchMeta:
    """Per-step metadata, a pytree of device arrays (all static shapes).

    tokens:    int32[R, Q]  token ids to run this step
    positions: int32[R, Q]  absolute sequence position of each token
    start_pos: int32[R]     KV-cache depth of each slot before this step
    num_tokens:int32[R]     how many of the Q tokens are real (rest padding)
    active:    bool[R]      slot currently holds a request
    slots:     int32[R]     the cache row each batch row reads and appends
                            to; None: row i is slot i. With it the batch is
                            segment major (the compact prefill batch of
                            RequestManager._meta_from_segments): R is the
                            number of segments, two rows may be consecutive
                            chunks of one slot, and every row's K/V is
                            appended before any row attends
    """

    tokens: jnp.ndarray
    positions: jnp.ndarray
    start_pos: jnp.ndarray
    num_tokens: jnp.ndarray
    active: jnp.ndarray
    slots: Optional[jnp.ndarray] = None

    @property
    def q_width(self) -> int:
        return self.tokens.shape[1]

    @property
    def max_requests(self) -> int:
        return self.tokens.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TreeBatchMeta:
    """Verification-step metadata (reference TreeVerifyBatchConfig).

    Queries are the nodes of a token tree, flattened per request slot. Node 0
    is the root (the last committed token re-fed for its logits); node i's
    parent is ``parent[r, i] < i``. Attention for node i sees the committed
    prefix plus its own ancestor chain (the reference's causal tree mask,
    tree_inc_multihead_self_attention.cu).

    tokens:    int32[R, T]  tree node token ids
    positions: int32[R, T]  absolute position = start_pos + depth_in_tree
    parent:    int32[R, T]  parent node index within the tree (root: -1)
    ancestor:  bool[R, T, T] ancestor[r, i, j] = node j is an ancestor of i
                             (or j == i); computed host-side in numpy
    start_pos: int32[R]     committed KV depth before this step
    num_nodes: int32[R]     real tree nodes (rest padding)
    active:    bool[R]
    """

    tokens: jnp.ndarray
    positions: jnp.ndarray
    parent: jnp.ndarray
    ancestor: jnp.ndarray
    start_pos: jnp.ndarray
    num_nodes: jnp.ndarray
    active: jnp.ndarray

    @property
    def q_width(self) -> int:
        return self.tokens.shape[1]

    @property
    def max_requests(self) -> int:
        return self.tokens.shape[0]


def make_batch_meta(max_requests: int, q_width: int,
                    tokens: Optional[np.ndarray] = None,
                    positions: Optional[np.ndarray] = None,
                    start_pos: Optional[np.ndarray] = None,
                    num_tokens: Optional[np.ndarray] = None,
                    active: Optional[np.ndarray] = None) -> BatchMeta:
    """Host-side constructor with zero-filled defaults."""
    R, Q = max_requests, q_width
    z = lambda shape, dt: np.zeros(shape, dtype=dt)
    return BatchMeta(
        tokens=jnp.asarray(tokens if tokens is not None else z((R, Q), np.int32)),
        positions=jnp.asarray(
            positions if positions is not None else z((R, Q), np.int32)),
        start_pos=jnp.asarray(
            start_pos if start_pos is not None else z((R,), np.int32)),
        num_tokens=jnp.asarray(
            num_tokens if num_tokens is not None else z((R,), np.int32)),
        active=jnp.asarray(active if active is not None else z((R,), bool)),
    )


def ancestor_mask_from_parents(parent: np.ndarray) -> np.ndarray:
    """[R, T] parent indices -> [R, T, T] ancestor-or-self boolean mask.

    Host-side numpy; T is small (<= speculation tree size), so the O(T^2)
    walk is negligible next to a device step.
    """
    R, T = parent.shape
    mask = np.zeros((R, T, T), dtype=bool)
    for r in range(R):
        for i in range(T):
            j = i
            while j >= 0:
                mask[r, i, j] = True
                j = parent[r, j]
    return mask
