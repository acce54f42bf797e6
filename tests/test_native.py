"""Native (C++) layer tests: BPE and SentencePiece tokenizer
parity/round-trip (reference tests/gpt_tokenizer.cpp), the C graph
builder and the ffsv_* serving ABI."""

import random
import string

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.native import native_available
from flexflow_tpu.native.tokenizer import (
    BPETokenizer,
    PyBPETokenizer,
    _bytes_to_unicode,
    pretokenize,
)

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native toolchain unavailable")


def _toy_vocab():
    bu = _bytes_to_unicode()
    vocab = {ch: i for i, ch in enumerate(bu.values())}
    merges = []

    def add(a, b):
        merges.append((a, b))
        m = a + b
        if m not in vocab:
            vocab[m] = len(vocab)

    sp = bu[ord(" ")]
    add("h", "e")
    add("l", "l")
    add("he", "ll")
    add("hell", "o")
    add("w", "o")
    add("r", "l")
    add("wo", "rl")
    add("worl", "d")
    add(sp, "w")
    add(sp + "w", "orld")  # never formed (worl+d wins) — exercises no-op rule
    add("t", "h")
    add("th", "e")
    add(sp, "the")
    vocab["<|endoftext|>"] = len(vocab)
    return vocab, merges


def test_pretokenize_rules():
    assert pretokenize("hello world") == ["hello", " world"]
    assert pretokenize("it's fine") == ["it", "'s", " fine"]
    assert pretokenize("a  b") == ["a", " ", " b"]
    assert pretokenize("ab12cd") == ["ab", "12", "cd"]
    assert pretokenize("x!?y") == ["x", "!?", "y"]
    assert pretokenize("  ") == ["  "]
    assert pretokenize("") == []


def test_python_bpe_merge_order():
    vocab, merges = _toy_vocab()
    tok = PyBPETokenizer(vocab, merges)
    ids = tok.encode("hello")
    assert [tok.id_to_token[i] for i in ids] == ["hello"]
    ids = tok.encode("the world")
    # (h,e) has the lowest rank, so "the" -> 't' + 'he' (not the 'th'+'e'
    # path): rank order decides, not left-to-right greediness
    assert [tok.id_to_token[i] for i in ids][:2] == ["t", "he"]
    assert tok.decode(ids) == "the world"


@needs_native
def test_native_python_parity_fuzz():
    vocab, merges = _toy_vocab()
    tok = BPETokenizer(vocab=vocab, merges=merges)
    assert tok.is_native
    py = PyBPETokenizer(vocab, merges)
    rng = random.Random(42)
    cases = ["hello world", "it's the world's 'test'", "tab\tnewline\n",
             "unicode: café 日本語 emoji \U0001F600", "  x  ", "'''", "123abc",
             "hello" * 50]
    for _ in range(300):
        n = rng.randint(0, 60)
        cases.append("".join(rng.choice(string.printable) for _ in range(n)))
    for text in cases:
        a, b = tok.encode(text), py.encode(text)
        assert a == b, (text, a, b)
        assert tok.decode(a) == py.decode(b) == text


@needs_native
def test_native_tokenizer_decode_utf8():
    vocab, merges = _toy_vocab()
    tok = BPETokenizer(vocab=vocab, merges=merges)
    text = "héllo wörld 你好"
    assert tok.decode(tok.encode(text)) == text


# ======================================================================
# SentencePiece tokenizer (native/src/sp_tokenizer.cpp vs Python twin)
# ======================================================================
def _make_sp_model(model_type: int, byte_fallback: bool = True,
                   seed: int = 0) -> bytes:
    """Synthetic but structurally-faithful SentencePiece model: control
    pieces, a vocabulary of ▁-prefixed words/subwords with descending
    scores, and the 256 byte pieces (zero egress: no real tokenizer.model
    exists in this environment, so tests build their own)."""
    import numpy as np

    from flexflow_tpu.native.sp_tokenizer import (BYTE, CONTROL, NORMAL,
                                                  UNKNOWN, build_model_proto)

    rng = np.random.RandomState(seed)
    pieces = [("<unk>", 0.0, UNKNOWN), ("<s>", 0.0, CONTROL),
              ("</s>", 0.0, CONTROL)]
    words = ["the", "quick", "brown", "fox", "jumps", "over", "lazy", "dog",
             "hello", "world", "token", "model", "serve", "très", "bien",
             "日本", "語"]
    subs = ["qu", "ick", "th", "e", "br", "own", "fo", "x", "ju", "mp", "s",
            "o", "ver", "la", "zy", "do", "g", "he", "llo", "wor", "ld",
            "to", "ken", "mo", "del", "ser", "ve", "a", "b", "c", "d", "t",
            "h", "i", "n", "r", "u", "w", "l", "▁"]
    vocab = []
    for w in words:
        vocab.append("▁" + w)
        vocab.append(w)
    vocab.extend(subs)
    seen = set()
    for v in vocab:
        if v in seen:
            continue
        seen.add(v)
        pieces.append((v, -float(rng.uniform(0.5, 12.0)), NORMAL))
    for b in range(256):
        pieces.append((f"<0x{b:02X}>", -100.0, BYTE))
    return build_model_proto(pieces, model_type=model_type,
                             byte_fallback=byte_fallback)


@pytest.mark.parametrize("model_type", [1, 2])  # unigram, bpe
def test_sp_native_matches_python_oracle(model_type):
    """The C++ SentencePiece tokenizer must agree token-for-token with the
    Python twin on fuzzed strings (the reference ships tokenizers-cpp for
    LLaMA; parity here is native-vs-oracle because the environment has
    neither the sentencepiece library nor a real checkpoint)."""
    import numpy as np

    from flexflow_tpu.native.sp_tokenizer import SentencePieceTokenizer

    tok = SentencePieceTokenizer(_make_sp_model(model_type))
    if tok._native is None:
        pytest.skip("native library unavailable")
    rng = np.random.RandomState(42)
    corpus = ["the quick brown fox jumps over the lazy dog",
              "hello world", "  spaced   out  text ", "", " ", "très bien",
              "日本語 model", "emoji 🦙 fallback", "a\nb\tc",
              "serve the token model"]
    # plus random mixtures of vocab words and arbitrary unicode
    glyphs = list("abcdefgh xyz…éß中πλ🙂")
    for _ in range(40):
        n = rng.randint(1, 14)
        parts = []
        for _ in range(n):
            if rng.rand() < 0.6:
                parts.append(str(rng.choice(
                    ["the", "quick", "fox", "model", "très", "日本"])))
            else:
                parts.append("".join(rng.choice(glyphs)
                                     for _ in range(rng.randint(1, 6))))
        corpus.append(" ".join(parts))
    for text in corpus:
        native = tok._encode_raw(text)
        oracle = tok.model.encode_ids(text)
        assert native == oracle, (text, native, oracle)
        assert tok.decode(native) == tok.model.decode_ids(oracle)


def test_sp_roundtrip_and_llama_conventions():
    """Byte-fallback round trip + HF-LlamaTokenizer-style surface: leading
    BOS, ▁ whitespace escaping, dummy prefix stripped on decode."""
    from flexflow_tpu.native.sp_tokenizer import SentencePieceTokenizer

    tok = SentencePieceTokenizer(_make_sp_model(1))
    text = "the quick fox 🦙 says ωmega"
    ids = tok.encode(text)
    assert ids[0] == tok.bos_token_id
    # byte-fallback keeps arbitrary unicode lossless through decode
    assert tok.decode(ids[1:]) == "the quick fox 🦙 says ωmega"
    assert tok.eos_token_id == 2
    # whitespace normalization: runs collapse, SP parity
    assert tok.decode(tok.encode("  the   fox ")[1:]) == "the fox"


def test_sp_bpe_differs_from_unigram_but_roundtrips():
    from flexflow_tpu.native.sp_tokenizer import SentencePieceTokenizer

    uni = SentencePieceTokenizer(_make_sp_model(1, seed=3))
    bpe = SentencePieceTokenizer(_make_sp_model(2, seed=3))
    text = "the quick brown fox"
    assert uni.decode(uni.encode(text)[1:]) == text
    assert bpe.decode(bpe.encode(text)[1:]) == text


# ---------------------------------------------------------------------------
# Native C graph-builder ABI (reference src/c/flexflow_c.cc model-builder
# wrappers; here the C host serializes the frontend IR)
# ---------------------------------------------------------------------------
def test_native_graph_builder_builds_and_trains():
    from flexflow_tpu.native.graph_builder import NativeGraphBuilder

    try:
        gb = NativeGraphBuilder()
    except RuntimeError:
        pytest.skip("native toolchain unavailable")
    x = gb.input(0)
    h = gb.unary(gb.dense(x, 32, name="fc1"), "relu")
    h2 = gb.dense(h, 32, name="fc2")
    s = gb.binary(h, h2, "add")          # residual
    out = gb.softmax(gb.dense(s, 4, name="head"))
    gb.output([out])

    model = ff.FFModel(ff.FFConfig(batch_size=8))
    t = model.create_tensor([8, 16], ff.DataType.DT_FLOAT)
    outs = gb.build_on(model, [t])
    assert outs[0].dims == (8, 4)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.05),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(0)
    xs = rng.randn(8, 16).astype(np.float32)
    ys = rng.randint(0, 4, size=(8, 1)).astype(np.int32)
    losses = [model.train_one_batch([xs], ys) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]        # separably-fittable random batch


def test_native_graph_builder_save_roundtrip(tmp_path):
    from flexflow_tpu.native.graph_builder import NativeGraphBuilder
    from flexflow_tpu.torch.model import file_to_ff

    try:
        gb = NativeGraphBuilder()
    except RuntimeError:
        pytest.skip("native toolchain unavailable")
    x = gb.input(0)
    c = gb.conv2d(x, 8, 3, 3, 1, 1, 1, 1, name="conv")
    p = gb.pool2d(gb.unary(c, "relu"), 2, 2, 2, 2)
    f = gb.unary(p, "flat")
    out = gb.softmax(gb.dense(f, 10))
    gb.output([out])
    path = tmp_path / "cnet.ir"
    gb.save(str(path))

    model = ff.FFModel(ff.FFConfig(batch_size=4))
    t = model.create_tensor([4, 3, 8, 8], ff.DataType.DT_FLOAT)
    outs = file_to_ff(str(path), model, [t])
    assert outs[0].dims == (4, 10)


def test_native_graph_builder_rejects_bad_ids():
    from flexflow_tpu.native.graph_builder import NativeGraphBuilder

    try:
        gb = NativeGraphBuilder()
    except RuntimeError:
        pytest.skip("native toolchain unavailable")
    with pytest.raises(ValueError):
        gb.dense(99, 8)                  # unknown node id
    x = gb.input(0)
    with pytest.raises(ValueError):
        gb.unary(x, "not_an_op")


def test_native_graph_builder_transformer_block():
    """Round-4 ABI breadth: a transformer encoder block described
    entirely from C (embedding -> MHA -> residual layer_norm -> MLP ->
    rms_norm -> mean -> head) builds, trains, and the scalar/transpose/
    mean/cast wrappers lower through the same IR the torch frontend
    uses."""
    from flexflow_tpu.native.graph_builder import NativeGraphBuilder

    try:
        gb = NativeGraphBuilder()
    except RuntimeError:
        pytest.skip("native toolchain unavailable")
    toks = gb.input(0)
    h = gb.embedding(toks, 64, 32, name="embed")
    a = gb.multihead_attention(h, h, h, 32, 4, name="attn")
    h = gb.layer_norm(gb.binary(h, a, "add"), [32], name="ln1")
    f = gb.unary(gb.dense(h, 64, name="up"), "gelu")
    h = gb.rms_norm(gb.binary(h, gb.dense(f, 32, name="down"), "add"),
                    eps=1e-6, name="rn")
    h = gb.scalar(h, "multiply", 0.5, name="halve")
    h = gb.mean(h, [1], name="pool")
    out = gb.softmax(gb.dense(h, 4, name="head"))
    gb.output([out])

    model = ff.FFModel(ff.FFConfig(batch_size=8))
    t = model.create_tensor([8, 6], ff.DataType.DT_INT32)
    outs = gb.build_on(model, [t])
    assert outs[0].dims == (8, 4)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.05),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(0)
    xs = rng.randint(0, 64, size=(8, 6)).astype(np.int32)
    ys = rng.randint(0, 4, size=(8, 1)).astype(np.int32)
    losses = [model.train_one_batch([xs], ys) for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_native_graph_builder_new_op_validation():
    from flexflow_tpu.native.graph_builder import NativeGraphBuilder

    try:
        gb = NativeGraphBuilder()
    except RuntimeError:
        pytest.skip("native toolchain unavailable")
    x = gb.input(0)
    with pytest.raises(ValueError):
        gb.multihead_attention(x, x, x, 33, 4)     # embed % heads != 0
    with pytest.raises(ValueError):
        gb.scalar(x, "power", 2.0)                 # unknown scalar op
    with pytest.raises(ValueError):
        gb.transpose(x, [0, 0])                    # not a permutation
    with pytest.raises(ValueError):
        gb.cast(x, "complex64")                    # unsupported dtype
    y = gb.transpose(x, [1, 0])
    z = gb.cast(y, "float32")
    assert z >= 0


@needs_native
def test_ffsv_serving_abi_in_process():
    """The ffsv_* serving ABI (reference flexflow_c.cc surface: config
    parse/set, model build, request registration, generate) driven
    through ctypes. ffsv_init sees an already-initialized interpreter
    and imports capi_host into it, so the whole round trip runs
    in-process — the embedded-host path is covered by the
    examples/c/run_incr_decoding.py smoke test."""
    import ctypes
    import os

    import pytest

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib_path = os.path.join(root, "native", "build",
                            "libflexflow_tpu_serve.so")
    import subprocess

    r = subprocess.run(["make", "-C", os.path.join(root, "native")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-500:]
    if not os.path.exists(lib_path):
        pytest.skip("serve library not built (no python dev files)")
    lib = ctypes.PyDLL(lib_path)     # PyDLL: calls hold the GIL
    c = ctypes
    lib.ffsv_init.restype = c.c_int
    lib.ffsv_init.argtypes = [c.c_char_p]
    lib.ffsv_last_error.restype = c.c_char_p
    lib.ffsv_config_create.restype = c.c_void_p
    lib.ffsv_config_set.restype = c.c_int
    lib.ffsv_config_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
    lib.ffsv_llm_create.restype = c.c_void_p
    lib.ffsv_llm_create.argtypes = [c.c_void_p, c.c_char_p]
    lib.ffsv_register_request.restype = c.c_long
    lib.ffsv_register_request.argtypes = [c.c_void_p,
                                          c.POINTER(c.c_int32),
                                          c.c_int, c.c_int]
    lib.ffsv_generate.restype = c.c_int
    lib.ffsv_generate.argtypes = [c.c_void_p]
    lib.ffsv_get_output.restype = c.c_int
    lib.ffsv_get_output.argtypes = [c.c_void_p, c.c_long,
                                    c.POINTER(c.c_int32), c.c_int]
    lib.ffsv_release.argtypes = [c.c_void_p]

    assert lib.ffsv_init(root.encode()) == 0, lib.ffsv_last_error()
    cfg = lib.ffsv_config_create()
    assert cfg
    for k, v in (("max_requests_per_batch", "2"),
                 ("max_sequence_length", "64"),
                 ("max_tokens_per_batch", "16"),
                 ("kv_cache_dtype", "float32")):
        assert lib.ffsv_config_set(cfg, k.encode(), v.encode()) == 0
    # a typo'd boolean must be rejected, not silently stored as False
    assert lib.ffsv_config_set(cfg, b"enable_fusion", b"ture") == -1

    spec = (b'{"family": "llama", "mode": "inc", "model_config": {'
            b'"vocab_size": 128, "hidden_size": 64, '
            b'"intermediate_size": 128, "num_hidden_layers": 2, '
            b'"num_attention_heads": 4, "num_key_value_heads": 2, '
            b'"max_position_embeddings": 64}}')
    llm = lib.ffsv_llm_create(cfg, spec)
    assert llm, lib.ffsv_last_error()
    prompt = (c.c_int32 * 3)(5, 9, 23)
    guid = lib.ffsv_register_request(llm, prompt, 3, 4)
    assert guid >= 0
    assert lib.ffsv_generate(llm) == 1, lib.ffsv_last_error()
    out = (c.c_int32 * 16)()
    n = lib.ffsv_get_output(llm, guid, out, 16)
    assert n >= 4, lib.ffsv_last_error()
    # cross-check against the pure-Python path: same config/spec/seed
    # must produce the same tokens
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import CompMode, InferenceMode
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model
    from flexflow_tpu.serve.request_manager import RequestManager

    m = ff.FFModel(ff.FFConfig(max_requests_per_batch=2,
                               max_sequence_length=64,
                               max_tokens_per_batch=16,
                               kv_cache_dtype="float32"))
    create_llama_model(m, LLAMAConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64), InferenceMode.INC_DECODING_MODE)
    m.compile(comp_mode=CompMode.COMP_MODE_INFERENCE)
    rm = RequestManager()
    rm.register_new_request([5, 9, 23], max_new_tokens=4)
    ref = rm.generate_incr_decoding(m)[0].output_tokens
    assert list(out[:n]) == [int(t) for t in ref]

    # spec surface: depth < 1 must be rejected (falsy would silently
    # mean "maximum depth" in the Python layer)
    lib.ffsv_spec_create.restype = c.c_void_p
    lib.ffsv_spec_create.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
    lib.ffsv_generate_spec.restype = c.c_int
    lib.ffsv_generate_spec.argtypes = [c.c_void_p, c.c_int]
    pair = lib.ffsv_spec_create(cfg, spec, spec)
    assert pair, lib.ffsv_last_error()
    assert lib.ffsv_generate_spec(pair, 0) == -1
    assert b"spec_depth" in lib.ffsv_last_error()
    prompt2 = (c.c_int32 * 3)(5, 9, 23)
    g2 = lib.ffsv_register_request(pair, prompt2, 3, 4)
    assert g2 >= 0 and lib.ffsv_generate_spec(pair, 2) == 1, \
        lib.ffsv_last_error()
    n2 = lib.ffsv_get_output(pair, g2, out, 16)
    assert n2 >= 4
    lib.ffsv_release(pair)

    # telemetry surface (ffsv_metrics_dump): disabled -> empty snapshot;
    # enabled -> the generate above the dump shows up in the registry
    # (in-process, so the Python side can flip the global switch without
    # building another model through the C path)
    import json as _mjson

    from flexflow_tpu.telemetry import disable_telemetry, enable_telemetry

    lib.ffsv_metrics_dump.restype = c.c_void_p
    lib.ffsv_metrics_dump.argtypes = [c.c_char_p]
    libc_m = ctypes.CDLL(None)
    libc_m.free.argtypes = [ctypes.c_void_p]
    # "disabled" is this process's state, not what a test file before this
    # one on the same worker left: the switch off, dead fleets collected
    import gc

    disable_telemetry()
    gc.collect()
    ptr = lib.ffsv_metrics_dump(b"json")
    assert ptr, lib.ffsv_last_error()
    assert ctypes.string_at(ptr) == b"{}"
    libc_m.free(ptr)
    enable_telemetry()
    try:
        prompt3 = (c.c_int32 * 3)(5, 9, 23)
        g3 = lib.ffsv_register_request(llm, prompt3, 3, 2)
        assert g3 >= 0 and lib.ffsv_generate(llm) == 1, lib.ffsv_last_error()
        ptr = lib.ffsv_metrics_dump(b"prometheus")
        assert ptr, lib.ffsv_last_error()
        prom = ctypes.string_at(ptr).decode()
        libc_m.free(ptr)
        assert "ffsv_requests_total 1" in prom
        ptr = lib.ffsv_metrics_dump(b"json")
        assert ptr, lib.ffsv_last_error()
        snap = _mjson.loads(ctypes.string_at(ptr).decode())
        libc_m.free(ptr)
        assert snap["ffsv_tokens_generated_total"]["value"] == 2
        # unknown format: NULL with ffsv_last_error set, not a crash
        assert not lib.ffsv_metrics_dump(b"bogus")
        assert b"metrics format" in lib.ffsv_last_error()
    finally:
        disable_telemetry()

    # --- adaptive speculation through the C ABI: generation_config +
    # multi-SSM {"ssms": [...]} spec JSON (the embedded-host face of
    # serve/spec_controller.py) ---
    from flexflow_tpu.telemetry import (disable_telemetry as _dis,
                                        enable_telemetry as _en)

    gcfg_spec = (b'{"family": "llama", "model_config": {'
                 b'"vocab_size": 128, "hidden_size": 64, '
                 b'"intermediate_size": 128, "num_hidden_layers": 4, '
                 b'"num_attention_heads": 4, "num_key_value_heads": 2, '
                 b'"max_position_embeddings": 64}, '
                 b'"generation_config": {"adaptive": true, '
                 b'"spec_depth": 3, "min_spec_depth": 1, '
                 b'"fallback_margin": 0.95, "probe_every": 4, '
                 b'"draft_cost_ratio": 0.2}}')
    drafts_spec = (b'{"ssms": [{"family": "llama", "model_config": {'
                   b'"vocab_size": 128, "hidden_size": 64, '
                   b'"intermediate_size": 128, "num_hidden_layers": 2, '
                   b'"num_attention_heads": 4, "num_key_value_heads": 2, '
                   b'"max_position_embeddings": 64}}, '
                   b'{"family": "llama", "model_config": {'
                   b'"vocab_size": 128, "hidden_size": 64, '
                   b'"intermediate_size": 128, "num_hidden_layers": 1, '
                   b'"num_attention_heads": 4, "num_key_value_heads": 2, '
                   b'"max_position_embeddings": 64}}]}')
    apair = lib.ffsv_spec_create(cfg, gcfg_spec, drafts_spec)
    assert apair, lib.ffsv_last_error()
    # in-process: the opaque handle IS the _SpecHost — pin the parsed
    # policy and the multi-SSM build directly
    host = ctypes.cast(ctypes.c_void_p(apair), ctypes.py_object).value
    assert len(host.ssms) == 2
    assert host.gen_cfg is not None and host.gen_cfg.adaptive_spec
    assert host.gen_cfg.spec_depth == 3
    assert host.gen_cfg.spec_fallback_margin == pytest.approx(0.95)
    _en()
    try:
        ap = (c.c_int32 * 3)(5, 9, 23)
        ag = lib.ffsv_register_request(apair, ap, 3, 8)
        # depth arg 2: generation_config.spec_depth=3 must override it
        assert ag >= 0 and lib.ffsv_generate_spec(apair, 2) == 1, \
            lib.ffsv_last_error()
        an = lib.ffsv_get_output(apair, ag, out, 16)
        assert an == 8, lib.ffsv_last_error()
        ptr = lib.ffsv_metrics_dump(b"json")
        assert ptr, lib.ffsv_last_error()
        snap = _mjson.loads(ctypes.string_at(ptr).decode())
        libc_m.free(ptr)
        # the depth controller ENGAGED on the C-host path: effective
        # depths were recorded (and never above the JSON's spec_depth),
        # and the fallback/EWMA gauges exist for host dashboards
        eff = snap["ffsv_spec_effective_depth"]
        assert eff["count"] >= 1
        assert eff["percentiles"]["p99"] <= 3     # JSON spec_depth bound
        assert "ffsv_spec_fallback_active" in snap
        assert "ffsv_spec_acceptance_ewma" in snap
    finally:
        _dis()
    lib.ffsv_release(apair)
    # a typo'd generation_config key must fail the create loudly
    bad = gcfg_spec.replace(b'"adaptive"', b'"adaptve"')
    assert not lib.ffsv_llm_create(cfg, bad)
    assert b"generation_config" in lib.ffsv_last_error()

    # text surface (reference flexflow_model_generate takes TEXT): a
    # toy byte-level vocab round-trips prompt -> tokens -> text
    import json as _json
    import tempfile

    from flexflow_tpu.native.tokenizer import _bytes_to_unicode

    lib.ffsv_register_bpe_tokenizer.restype = c.c_int
    lib.ffsv_register_bpe_tokenizer.argtypes = [c.c_void_p, c.c_char_p,
                                                c.c_char_p]
    lib.ffsv_register_request_text.restype = c.c_long
    lib.ffsv_register_request_text.argtypes = [c.c_void_p, c.c_char_p,
                                               c.c_int]
    lib.ffsv_get_output_text.restype = c.c_void_p
    lib.ffsv_get_output_text.argtypes = [c.c_void_p, c.c_long]
    bu = _bytes_to_unicode()
    vocab = {ch: i for i, ch in enumerate(bu.values())}
    vocab["<|endoftext|>"] = len(vocab)
    with tempfile.TemporaryDirectory() as td:
        vp = os.path.join(td, "vocab.json")
        mp = os.path.join(td, "merges.txt")
        with open(vp, "w") as f:
            _json.dump(vocab, f)
        open(mp, "w").write("")
        spec_t = _json.dumps({
            "family": "llama", "mode": "inc", "model_config": {
                "vocab_size": len(vocab), "hidden_size": 64,
                "intermediate_size": 128, "num_hidden_layers": 2,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "max_position_embeddings": 64}}).encode()
        tl = lib.ffsv_llm_create(cfg, spec_t)
        assert tl, lib.ffsv_last_error()
        assert lib.ffsv_register_bpe_tokenizer(
            tl, vp.encode(), mp.encode()) == len(vocab)
        tg = lib.ffsv_register_request_text(tl, b"hello tpu", 4)
        assert tg >= 0, lib.ffsv_last_error()
        assert lib.ffsv_generate(tl) == 1, lib.ffsv_last_error()
        # unknown guid must be a NULL error, not an empty string
        assert not lib.ffsv_get_output_text(tl, 999999)
        ptr = lib.ffsv_get_output_text(tl, tg)
        assert ptr, lib.ffsv_last_error()
        assert len(ctypes.string_at(ptr).decode()) > 0
        libc = ctypes.CDLL(None)
        libc.free.argtypes = [ctypes.c_void_p]
        libc.free(ptr)                  # header contract: caller frees
        lib.ffsv_release(tl)
    lib.ffsv_release(llm)
    lib.ffsv_release(cfg)
    # ffsv_shutdown finalizes only an interpreter ffsv_init started; in
    # this one it found Python running and must leave it so
    lib.ffsv_shutdown.restype = None
    lib.ffsv_shutdown()
    assert lib.ffsv_config_create()
