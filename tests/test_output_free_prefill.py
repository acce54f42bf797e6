"""The output-free step (``InferenceManager.step(want_output=False)``, every
prefill step of every loop) is a program of its own that ends at the last
layer's hidden state: ``_prefill_impl``, the graph without its tail (final
norm, head, pick).

Over the families the suite builds, at tiny sizes on the CPU: the caches it
leaves are those of the step whose pick is read, bit for bit, step after
step of ragged segments chosen as the scheduler chooses them; a served
batch's tokens are those of a manager whose output-free step still runs the
whole program (the parent's); and the lowered program has nothing of the
vocabulary's extent but the embedding's gather, with every kernel call and
every other gemm of the whole program, the last layer's too.
"""

import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.serve.engine import _tail_of
from flexflow_tpu.serve.inference_manager import InferenceManager
from flexflow_tpu.serve.request_manager import Request, RequestManager
from flexflow_tpu.serve.step_costs import GivenCosts


def _dense():
    from test_serving_pp import make_model

    return make_model()


def _pipelined():
    from test_serving_pp import make_model

    return make_model(pp=2)


def _routed_experts():      # K-EXAONE: held experts, rings beside full caches
    from test_exaone_moe import _build

    return _build()[0]


def _latent_cache():        # Mistral-4
    from test_mistral4 import _build

    return _build()[0]


def _chunked_cache():       # EvaByte
    from test_evabyte import _build

    return _build()


def _tail_carrying(**tiny):     # ZAYA1
    from test_zaya import TINY, _build

    return _build(tiny=dict(TINY, **tiny))[0]


def _block_diffusion():     # SDAR-MoE
    from test_sdar_moe import _build

    return _build()[0]


FAMILIES = {"dense": _dense, "routed_experts": _routed_experts,
            "latent_cache": _latent_cache, "chunked_cache": _chunked_cache,
            "tail_carrying": _tail_carrying,
            "block_diffusion": _block_diffusion, "pipelined": _pipelined}


def _prompts(m, n):
    """``n`` prompts of ragged lengths: one of several chunks, so that a
    compact step holds consecutive segments of one slot, the others short."""
    chunk, _ = RequestManager._prefill_shape(m.config)
    vocab = m.layers[0].weights[0].shape[0]
    rng = np.random.default_rng(7)
    lengths = [2 * chunk + 5, chunk - 3, 3, chunk + 1][:n]
    return [[int(t) for t in rng.integers(1, min(vocab, 250), size=k)]
            for k in lengths]


def _next_step(ifm, active):
    """(meta, rows) of the prefill step ``RequestManager._prefill`` would
    run for ``active`` now; rows empty: nothing is filling."""
    m = ifm.model
    chunk, segments = RequestManager._prefill_shape(m.config)
    compact = RequestManager._compact_prefill(ifm)
    chunked = (getattr(m, "attention_kinds", None) or {}).get("chunked")
    rows = RequestManager._prefill_rows(
        active, chunk, lambda r: r.cache_depth, segments,
        consecutive=compact, hold=RequestManager._held_back(m),
        window=chunked and chunked["window"])
    if not rows:
        return None, rows
    return (RequestManager._meta_from_segments(segments, chunk, rows)
            if compact else
            RequestManager._meta_from_rows(len(active), chunk, rows)), rows


def _leaves(state):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(state)}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_output_free_step_leaves_the_caches_of_the_whole_step(family):
    m = FAMILIES[family]()
    ifm = InferenceManager(m)
    R = m.config.max_requests_per_batch
    active = [Request(guid=i, prompt_tokens=p, tokens=list(p), slot=i,
                      prefill_start_s=float(i))
              for i, p in enumerate(_prompts(m, R))]
    hidden = m.layers[0].outputs[0].shape[-1]
    rng = jax.random.PRNGKey(5)
    steps = 0
    while True:
        meta, rows = _next_step(ifm, active)
        if not rows:
            break
        # both programs donate their state: a copy each, of the same bits
        state = _leaves(m.op_state)
        picks, whole = ifm._step(
            m.params, jax.tree.map(jnp.copy, m.op_state), meta, rng)
        out, m.op_state = ifm._prefill(m.params, m.op_state, meta, rng)
        whole, free = _leaves(whole), _leaves(m.op_state)
        assert whole.keys() == free.keys() == state.keys()
        for name in whole:
            assert np.array_equal(whole[name], free[name],
                                  equal_nan=True), (steps, name)
        assert any(not np.array_equal(state[name], free[name],
                                      equal_nan=True) for name in free)
        # handed back whole: every row and position of the step, where the
        # whole program hands back their picks
        (h,) = out
        assert h.shape == tuple(meta.tokens.shape) + (hidden,)
        assert np.asarray(picks).shape[:2] == tuple(meta.tokens.shape)
        for slot, toks, start in rows:
            active[slot].cache_depth = start + len(toks)
        steps += 1
    assert steps >= 2       # the later steps start from caches that hold


def _serve(m, prompts, whole_program: bool):
    rm = RequestManager()
    ifm = rm._manager_of(m)
    ifm.step_costs = GivenCosts(2.0, 1.0)       # the same rounds every run
    calls = []
    program = ifm._step if whole_program else ifm._prefill
    ifm._prefill = lambda *a: calls.append(1) or program(*a)
    guids = [rm.register_new_request(p, max_new_tokens=6) for p in prompts]
    rm.generate_incr_decoding(m)
    del m._inference_manager
    return [rm.results[g].output_tokens for g in guids], len(calls)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_served_batch_has_the_tokens_of_the_whole_program(family):
    m = FAMILIES[family]()
    prompts = _prompts(m, m.config.max_requests_per_batch + 1)
    want, steps = _serve(m, prompts, whole_program=True)
    got, again = _serve(m, prompts, whole_program=False)
    assert got == want and all(len(t) == 6 for t in got)
    assert again == steps >= 2      # every prefill step took the program


def _calls(text):
    return collections.Counter(re.findall(r"call @(\w+)", text))


def _gemms(text):
    return collections.Counter(
        re.sub(r"^.*?\) : ", "", line.strip()) for line in text.splitlines()
        if "stablehlo.dot_general" in line)


def test_the_program_ends_at_the_last_layers_hidden_state(monkeypatch):
    """ZAYA1's cut with the kernels on and a vocabulary no other extent
    equals: nothing of the tail, all of the last layer."""
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    vocab = 331
    m = _tail_carrying(vocab_size=vocab)
    ifm = InferenceManager(m)
    tail = _tail_of(m)
    assert [t.shape[-1] for t in tail.inputs] == [128]
    chunk, segments = RequestManager._prefill_shape(m.config)
    meta = RequestManager._meta_from_segments(
        segments, chunk, [(0, list(range(1, chunk + 1)), 0),
                          (0, [3, 4, 5], chunk), (2, [7] * 9, 0)])
    args = (m.params, m.op_state, meta, jax.random.PRNGKey(0))
    whole = ifm._step.lower(*args).as_text()
    free = ifm._prefill.lower(*args).as_text()

    def of_the_vocabulary(text):
        return [line.strip() for line in text.splitlines()
                if re.search(r"[<x]%dx|dense<%d>" % (vocab, vocab), line)]

    # the table as an argument, its hand-over to the gather and the gather
    assert len(of_the_vocabulary(whole)) > len(of_the_vocabulary(free)) > 0
    assert all("func.func" in line or "call @_take" in line
               or "gather" in line or "constant" in line
               for line in of_the_vocabulary(free)), of_the_vocabulary(free)
    # every kernel call of the whole program, the last layer's among them
    kernels = {name: n for name, n in _calls(whole).items()
               if re.match(r"flash_attend|moe_experts", name)}
    assert sum(kernels.values()) == 2 * 2       # attention, experts; 2 layers
    assert {name: _calls(free)[name] for name in kernels} == kernels
    assert _calls(whole) - _calls(free) == {"argmax": 1}
    # and every gemm but the head's
    head = _gemms(whole) - _gemms(free)
    assert sum(head.values()) == 1 and not _gemms(free) - _gemms(whole)
    assert re.search(r"[<x]%dx" % vocab, next(iter(head)))
