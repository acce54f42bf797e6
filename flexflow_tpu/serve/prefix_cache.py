"""Shared-prefix KV cache: radix trie over token ids + pooled KV segments.

At million-user scale most traffic shares system prompts / few-shot
prefixes (ISSUE 19; SpecInfer's cache-as-prefix-store view generalized
across requests). This module gives the RequestManager a process-level
pool of finished prompts' KV:

* ``PrefixCache`` — a trie over token ids. ``match(tokens)`` walks the
  trie for the longest stored path agreeing with ``tokens`` (capped at
  ``len(tokens) - 1``: the last prompt token must still be fed to emit
  the first output logits) and returns ``(shared_len, entry)``, bumping
  the entry's refcount. Entries are inserted on request finish
  (``insert``) with their slot's actual KV; eviction is LRU by a
  token-count budget on an injectable clock, and an entry with live
  references is never evicted (the eviction-under-pressure safety the
  tests pin).

* KV segment helpers — ``extract_prefix_kv`` / ``install_prefix_kv``
  copy the first N cache positions of a slot out to host memory and
  back into another slot, handling both op_state layouts
  (per-layer ``{"k_cache","v_cache"}`` of ``[R, KH, S, Dp]`` and the
  stacked ``op_state["kv_cache"] = {"k","v"}`` of ``[L, R, KH, S, Dp]``,
  see ops/inc_attention.py), and a cache stored packed
  (``[.., S/2, 128]`` at D=64: ops/kv_layout.py, which reads the layout
  from the cache's shape against the model's ``max_seq``). A segment is
  position-major ``[.., P, Dp]`` on the host whatever the cache's layout;
  ``_PAD`` is even, so a segment is whole stored rows. Segments are padded
  to a sublane multiple
  of positions so the jitted installer compiles per LENGTH BUCKET, not
  per prefix length; the pad positions hold stale KV but sit beyond the
  slot's valid extent (``flash_attend`` masks ``s_ids < length``) and
  are overwritten by the suffix prefill before the extent reaches them.

Token identity: KV at position p depends only on tokens[0..p] (per-token
projections + rotary at the absolute position), so a pooled segment is
bit-for-bit what re-prefilling the same prefix would produce — reuse
changes wall clock, never tokens. The manager still prefills the
(non-shared) suffix through the normal chunked path.

Copy, not alias: JAX arrays are functional, so "pointing" a slot at a
pooled page means one contiguous dynamic_update_slice per model at grant
time (the same idiom as ops/inc_attention.append_kv_contiguous); the
refcounts exist so the POOL entry backing an in-flight request cannot be
evicted and re-used mid-flight.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from flexflow_tpu.ops import kv_layout as kvl

# position-count granularity for stored/installed segments (matches
# kernels/attention.SUBLANE, imported lazily nowhere: the value is a
# layout constant, not a kernel knob)
_PAD = 8

# default pool budget in TOKENS (sum of entry lengths); ~a few hundred
# chat system prompts. GenerationConfig.prefix_cache_tokens overrides.
DEFAULT_POOL_TOKENS = 65536


def _round_up(n: int, m: int = _PAD) -> int:
    return -(-n // m) * m


# ----------------------------------------------------------------------
# KV segment extract/install (both op_state layouts)
# ----------------------------------------------------------------------
def _kv_slots(op_state) -> List[Tuple[str, str, str, bool]]:
    """KV-cache entries of an op_state: (name, k_key, v_key, stacked)."""
    from flexflow_tpu.ops.inc_attention import refuse_windowed

    refuse_windowed(op_state, "the shared-prefix pool")
    out = []
    for name, st in op_state.items():
        if not isinstance(st, dict):
            continue
        if "k_cache" in st and "v_cache" in st:
            out.append((name, "k_cache", "v_cache", False))
        elif name == "kv_cache" and "k" in st and "v" in st:
            out.append((name, "k", "v", True))
    return out


def extract_prefix_kv(op_state, slot: int, length: int,
                      max_seq: int) -> Optional[Dict]:
    """Copy the first ``length`` positions of ``slot``'s KV to host numpy,
    position-major, padded up to a ``_PAD`` multiple of positions.
    ``max_seq`` is the caches' length in positions. Returns None when the
    cache is too short to hold the padded segment."""
    P = _round_up(length)
    if P > max_seq:
        return None
    segs: Dict[str, Dict[str, np.ndarray]] = {}
    for name, kk, vk, stacked in _kv_slots(op_state):
        at = (slice(None), slot) if stacked else (slot,)
        # [L, R, KH, S, Dp] or [R, KH, S, Dp], as stored
        segs[name] = {
            c: np.asarray(kvl.read_positions(
                cache, 0, P, kvl.pack_of(cache, max_seq), at))
            for c, cache in (("k", op_state[name][kk]),
                             ("v", op_state[name][vk]))}
    return segs or None


def prefix_compatible(op_state, segs: Dict, length: int,
                      max_seq: int) -> bool:
    """True when ``segs`` (one model's stored segment dict) can be
    installed into ``op_state`` for ``length`` shared tokens — every KV
    cache present, geometry matching, padded length within the cache."""
    slots = _kv_slots(op_state)
    if not slots:
        return False
    P = _round_up(length)
    for name, kk, vk, stacked in slots:
        seg = segs.get(name)
        if seg is None:
            return False
        cache, k = op_state[name][kk], seg["k"]
        if P > max_seq or k.shape[-2] < P:
            return False
        Dp = cache.shape[-1] // kvl.pack_of(cache, max_seq)
        want = ((cache.shape[0], cache.shape[2], Dp)
                if stacked else (cache.shape[1], Dp))
        got = ((k.shape[0], k.shape[1], k.shape[3])
               if stacked else (k.shape[0], k.shape[2]))
        if want != got:
            return False
    return True


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _install_fn(op_state, segs, slot, max_seq):
    out = dict(op_state)
    for name, kk, vk, stacked in _kv_slots(op_state):
        seg = segs.get(name)
        if seg is None:
            continue
        k_cache, v_cache = op_state[name][kk], op_state[name][vk]
        # a prefix starts at position 0, so it is whole stored rows
        pack = kvl.pack_of(k_cache, max_seq)
        k = kvl.to_rows(seg["k"].astype(k_cache.dtype), pack)
        v = kvl.to_rows(seg["v"].astype(v_cache.dtype), pack)
        if stacked:      # seg [L, KH, P, Dp] -> cache [L, R, KH, S, Dp]
            kc = jax.lax.dynamic_update_slice(
                k_cache, k[:, None], (0, slot, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_cache, v[:, None], (0, slot, 0, 0, 0))
        else:            # seg [KH, P, Dp] -> cache [R, KH, S, Dp]
            kc = jax.lax.dynamic_update_slice(
                k_cache, k[None], (slot, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_cache, v[None], (slot, 0, 0, 0))
        out[name] = {**op_state[name], kk: kc, vk: vc}
    return out


def install_prefix_kv(op_state, slot: int, segs: Dict, length: int,
                      max_seq: int):
    """Write the first ``length`` shared positions of a stored segment
    into ``slot``, returning the new (donated-in) op_state. One fused
    dynamic_update_slice per cache; compiles per length BUCKET (``_PAD``
    multiple), with the bucket tail's stale positions masked off by the
    slot's valid extent until the suffix prefill overwrites them."""
    P = _round_up(length)
    cut = {name: {"k": s["k"][..., :P, :], "v": s["v"][..., :P, :]}
           for name, s in segs.items()}
    return _install_fn(op_state, cut, jnp.int32(slot), max_seq)


# ----------------------------------------------------------------------
# Radix trie + refcounted pool
# ----------------------------------------------------------------------
class _Node:
    __slots__ = ("children", "entry", "parent", "token")

    def __init__(self, parent=None, token=None):
        self.children: Dict[int, "_Node"] = {}
        self.entry: Optional["PrefixEntry"] = None
        self.parent = parent
        self.token = token


class PrefixEntry:
    """One pooled prefix: its token ids, per-model host KV segments
    (keyed "llm", "ssm0", ... — a model absent at insert time simply
    prefills cold on reuse), a refcount, and an LRU stamp."""

    __slots__ = ("tokens", "length", "segments", "refs", "last_used",
                 "_node")

    def __init__(self, tokens: Tuple[int, ...], segments: Dict[str, Any],
                 now: float):
        self.tokens = tokens
        self.length = len(tokens)
        self.segments = segments
        self.refs = 0
        self.last_used = now
        self._node: Optional[_Node] = None


class PrefixCache:
    """Refcounted shared-prefix KV pool (see module docstring).

    Thread-safe for the serving split of duties: ``match`` runs on
    submitter threads (register_new_request) while ``insert``/eviction
    run on the engine loop thread."""

    def __init__(self, max_tokens: int = 0, min_tokens: int = 2,
                 clock=None):
        self.max_tokens = max_tokens or DEFAULT_POOL_TOKENS
        self.min_tokens = max(1, min_tokens)
        self._clock = clock or time.monotonic
        self._root = _Node()
        self._entries: List[PrefixEntry] = []
        self._lock = threading.Lock()
        # counters (telemetry mirrors these through the manager hooks)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.shared_tokens_total = 0
        self.pool_tokens = 0

    def __len__(self):
        return len(self._entries)

    # -- lookup --------------------------------------------------------
    def match(self, tokens: Sequence[int]
              ) -> Tuple[int, Optional[PrefixEntry]]:
        """Longest-prefix lookup, capped at ``len(tokens) - 1``. On a hit
        the entry's refcount is taken (caller MUST ``release``). The
        returned ``shared_len`` may be shorter than the entry (radix
        partial match: the entry's first ``shared_len`` positions are
        what the caller installs)."""
        with self._lock:
            node, depth = self._root, 0
            for t in tokens[:max(0, len(tokens) - 1)]:
                child = node.children.get(int(t))
                if child is None:
                    break
                node, depth = child, depth + 1
            if depth < self.min_tokens:
                self.misses += 1
                return 0, None
            entry = self._subtree_entry(node)
            if entry is None:       # pruning keeps this unreachable in
                self.misses += 1    # steady state; belt and braces
                return 0, None
            entry.refs += 1
            entry.last_used = self._clock()
            self.hits += 1
            self.shared_tokens_total += depth
            return depth, entry

    @staticmethod
    def _subtree_entry(node: _Node) -> Optional[PrefixEntry]:
        """Any entry at or below ``node`` — every path in the trie was
        written by an insert, and eviction prunes entry-less leaves, so
        the first descent finds one."""
        seen = 0
        while node is not None and seen < 4096:
            if node.entry is not None:
                return node.entry
            node = next(iter(node.children.values()), None)
            seen += 1
        return None

    def release(self, entry: PrefixEntry):
        with self._lock:
            entry.refs = max(0, entry.refs - 1)

    def acquire(self, entry: PrefixEntry):
        with self._lock:
            entry.refs += 1

    # -- insert / evict ------------------------------------------------
    def would_store(self, tokens: Sequence[int]) -> bool:
        """True when ``insert(tokens, ...)`` would add a new entry — the
        cheap pre-check before paying the device->host KV readback."""
        n = len(tokens)
        if n < self.min_tokens or n > self.max_tokens:
            return False
        with self._lock:
            node = self._root
            for t in tokens:
                node = node.children.get(int(t))
                if node is None:
                    return True
            return node.entry is None

    def insert(self, tokens: Sequence[int], segments: Dict[str, Any]
               ) -> Tuple[Optional[PrefixEntry], int]:
        """Pool a finished prompt's KV. Returns (entry, n_evicted);
        entry is None when the prompt is out of bounds or already
        stored (the existing entry just gets an LRU touch)."""
        toks = tuple(int(t) for t in tokens)
        n = len(toks)
        if n < self.min_tokens or n > self.max_tokens:
            return None, 0
        with self._lock:
            node = self._root
            for t in toks:
                child = node.children.get(t)
                if child is None:
                    child = node.children[t] = _Node(node, t)
                node = child
            now = self._clock()
            if node.entry is not None:
                node.entry.last_used = now
                return None, 0
            entry = PrefixEntry(toks, segments, now)
            entry._node = node
            node.entry = entry
            self._entries.append(entry)
            self.pool_tokens += n
            return entry, self._evict_to_budget(keep=entry)

    def _evict_to_budget(self, keep: Optional[PrefixEntry] = None) -> int:
        """LRU-evict unreferenced entries until the pool fits the token
        budget (lock held). Entries with live refs — a request between
        match and finish — are NEVER evicted, so the pool may run over
        budget transiently under pressure."""
        n_evicted = 0
        while self.pool_tokens > self.max_tokens:
            victims = [e for e in self._entries
                       if e.refs == 0 and e is not keep]
            if not victims:
                break
            victim = min(victims, key=lambda e: e.last_used)
            self._remove(victim)
            n_evicted += 1
        self.evictions += n_evicted
        return n_evicted

    def _remove(self, entry: PrefixEntry):
        self._entries.remove(entry)
        self.pool_tokens -= entry.length
        node = entry._node
        entry._node = None
        if node is None:
            return
        node.entry = None
        # prune the now entry-less tail so _subtree_entry never descends
        # into a dead branch
        while (node.parent is not None and not node.children
               and node.entry is None):
            parent = node.parent
            parent.children.pop(node.token, None)
            node.parent = None
            node = parent
