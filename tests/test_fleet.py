"""Fleet elasticity & replica failover tests (ISSUE 17).

Gates, in dependency order: the HF-layout checkpoint store roundtrips
every model family token-identically (export inverts the per-family qkv
fusion bit-for-bit); quantize-on-load from disk matches quantizing the
same weights in memory; the C-API spec JSON's ``checkpoint_dir`` /
``quantize`` keys cold-start an engine; the replica pool survives a
seeded mid-run crash with token-identical failover and a respawn that
rejoins from disk; and the autoscaler spins a replica up under a spike.

Kept lean on purpose (tier-1 budget): every engine here is the TINY
geometry from models/checkpoint_store.TINY_CONFIGS, and the file is
hoisted to the front of the run by conftest._EARLY_FILES.
"""

import json
import time

import numpy as np
import pytest

from flexflow_tpu.models.checkpoint_store import (
    TINY_CONFIGS, export_hf_state_dict, load_checkpoint,
    load_checkpoint_into, read_checkpoint_config, save_checkpoint,
    save_tiny_checkpoint)

PROMPT = [3, 5, 7]
NEW_TOKENS = 8


def _build_tiny(family_name, seed=0, max_seq=64, slots=2):
    """Same build recipe as save_tiny_checkpoint: seeded init is
    deterministic given the layer names, so seed=0 reproduces the
    checkpoint's weights exactly and seed=123 gives provably different
    ones."""
    import flexflow_tpu as ff
    from flexflow_tpu.ffconst import CompMode, InferenceMode
    from flexflow_tpu.models import FAMILIES

    fam = FAMILIES[family_name]
    mcfg = fam.config_cls(**TINY_CONFIGS[fam.name])
    cfg = ff.FFConfig(max_requests_per_batch=slots,
                      max_sequence_length=max_seq,
                      max_tokens_per_batch=16, seed=seed,
                      kv_cache_dtype="float32")
    model = ff.FFModel(cfg)
    fam.build(model, mcfg, mode=InferenceMode.INC_DECODING_MODE)
    model.compile(comp_mode=CompMode.COMP_MODE_INFERENCE)
    return model, mcfg


def _gen(model, prompts=(PROMPT,), new_tokens=NEW_TOKENS):
    from flexflow_tpu.serve.request_manager import RequestManager

    rm = RequestManager()
    guids = [rm.register_new_request(list(p), max_new_tokens=new_tokens)
             for p in prompts]
    rm.generate_incr_decoding(model)
    return [list(rm.results[g].output_tokens) for g in guids]


@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fleet_ckpt"))
    save_tiny_checkpoint("llama", d, seed=0)
    return d


# ---------------------------------------------------------------------------
# checkpoint store: all-families roundtrip + format/layout details
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", sorted(TINY_CONFIGS))
def test_checkpoint_roundtrip_token_identical(tmp_path, family):
    from flexflow_tpu.models import FAMILIES, family_for_hf_config

    model, mcfg = _build_tiny(family)
    ref = _gen(model)[0]
    assert len(ref) == NEW_TOKENS
    save_checkpoint(model, family, mcfg, str(tmp_path))

    # the on-disk state dict is a bit-exact image of the export
    sd_mem = export_hf_state_dict(model, family, mcfg)
    cfg_dict, sd_disk = load_checkpoint(str(tmp_path))
    assert sorted(sd_disk) == sorted(sd_mem)
    for k in sd_mem:
        assert np.array_equal(sd_disk[k], np.asarray(sd_mem[k],
                                                     np.float32)), k
    # config.json roundtrips through from_hf_config to the same dataclass
    fam = family_for_hf_config(cfg_dict)
    assert fam is FAMILIES[family]
    assert fam.config_cls.from_hf_config(cfg_dict) == mcfg

    # trash the live weights, reload from disk, regenerate: token-equal
    for lp in model.params.values():
        for w in list(lp):
            lp[w] = lp[w] * 0
    # n counts params loaded AFTER the preprocess: fused-qkv families load
    # MORE tensors than the file stores (the split), OLMoE fewer (one
    # Linear per expert in the file, three stacks in the model)
    n = load_checkpoint_into(model, str(tmp_path))
    assert n == len(fam.hf_weight_map(mcfg))
    assert _gen(model)[0] == ref


@pytest.mark.parametrize("layout", ["falcon-mq", "falcon-mha",
                                    "falcon-gqa-newarch",
                                    "starcoder-mq", "starcoder-mha"])
def test_qkv_refuse_inverts_preprocess(layout):
    """The export-side re-fuse must be the numeric inverse of the
    load-side split for every genuine HF fused-qkv layout (falcon's
    three, starcoder's two) — pure numpy, no model build."""
    rng = np.random.RandomState(0)
    H, hd = 4, 16
    hidden = H * hd
    if layout.startswith("falcon"):
        from flexflow_tpu.models.checkpoint_store import \
            _refuse_falcon as refuse
        from flexflow_tpu.models.falcon import (FalconConfig,
                                                preprocess_hf_state_dict)

        kv = {"falcon-mq": 1, "falcon-mha": H, "falcon-gqa-newarch": 2}
        c = FalconConfig(vocab_size=32, hidden_size=hidden,
                         num_hidden_layers=1, num_attention_heads=H,
                         num_kv_heads=kv[layout], bias=True,
                         new_decoder_architecture=("newarch" in layout))
        base, KH = "transformer.h.0.self_attention", c.num_kv_heads
    else:
        from flexflow_tpu.models.checkpoint_store import \
            _refuse_starcoder as refuse
        from flexflow_tpu.models.starcoder import (STARCODERConfig,
                                                   preprocess_hf_state_dict)

        c = STARCODERConfig(vocab_size=32, hidden_size=hidden,
                            intermediate_size=128, num_hidden_layers=1,
                            num_attention_heads=H,
                            multi_query=(layout == "starcoder-mq"))
        base, KH = "transformer.h.0.attn", (1 if c.multi_query else H)
    sd = {}
    for p, rows in (("q_proj", H * hd), ("k_proj", KH * hd),
                    ("v_proj", KH * hd)):
        sd[f"{base}.{p}.weight"] = rng.randn(rows, hidden).astype(
            np.float32)
        sd[f"{base}.{p}.bias"] = rng.randn(rows).astype(np.float32)
    want = {k: v.copy() for k, v in sd.items()}
    refuse(sd, c)
    assert not [k for k in sd if ".q_proj." in k]    # fully fused
    preprocess_hf_state_dict(sd, c)
    for k, v in want.items():
        assert np.array_equal(sd[k], v), k


@pytest.mark.parametrize("qtype", ["int8", "int4"])
def test_quantize_on_load_token_identical(llama_ckpt, qtype):
    """Disk cold start with quantize-on-load == in-memory build + same
    quantization, even when the loading model started from DIFFERENT
    random weights (seed 123) — only the checkpoint decides tokens."""
    ref_model, _ = _build_tiny("llama", seed=0)
    ref_model.quantize_weights(qtype)
    ref = _gen(ref_model)[0]

    other, _ = _build_tiny("llama", seed=123)
    load_checkpoint_into(other, llama_ckpt, quantize=qtype)
    assert _gen(other)[0] == ref


def test_pytorch_bin_format_matches_safetensors(tmp_path, llama_ckpt):
    pytest.importorskip("torch")
    model, mcfg = _build_tiny("llama", seed=0)
    save_checkpoint(model, "llama", mcfg, str(tmp_path), fmt="pytorch-bin")
    cfg_pt, sd_pt = load_checkpoint(str(tmp_path))
    cfg_st, sd_st = load_checkpoint(llama_ckpt)
    assert cfg_pt == cfg_st
    assert sorted(sd_pt) == sorted(sd_st)
    for k in sd_st:
        assert np.array_equal(sd_pt[k], sd_st[k]), k


def test_checkpoint_store_cli(tmp_path, capsys):
    from flexflow_tpu.models import checkpoint_store as cs

    out = str(tmp_path / "ckpt")
    assert cs.main(["save", "--family", "llama", "--out", out]) == 0
    assert cs.main(["info", out]) == 0
    saved, info = [json.loads(line)
                   for line in capsys.readouterr().out.splitlines()]
    assert saved["model_type"] == info["model_type"] == "llama"
    assert info["n_tensors"] == saved["n_tensors"] > 0


# ---------------------------------------------------------------------------
# front doors: LLM.from_checkpoint and the C-API spec JSON
# ---------------------------------------------------------------------------

def test_llm_from_checkpoint_token_identical(llama_ckpt):
    from flexflow_tpu.serve.api import LLM

    ref_model, _ = _build_tiny("llama", seed=0)
    ref = _gen(ref_model)[0]

    llm = LLM.from_checkpoint(llama_ckpt)
    llm.compile(max_requests_per_batch=2, max_seq_length=64,
                max_tokens_per_batch=16, kv_cache_dtype="float32")
    res = llm.generate(PROMPT, max_new_tokens=NEW_TOKENS)
    assert list(res.output_tokens) == ref
    assert llm.checkpoint_dir == llama_ckpt


def test_capi_checkpoint_dir_cold_start(llama_ckpt):
    import flexflow_tpu as ff
    from flexflow_tpu.serve import capi_host

    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=4,
                      kv_cache_dtype="float32")
    # spec keys are validated BEFORE the (expensive) build
    with pytest.raises(ValueError, match="mutually exclusive"):
        capi_host.llm_create(cfg, json.dumps(
            {"checkpoint_dir": llama_ckpt,
             "model_config": {"vocab_size": 128}}))
    with pytest.raises(ValueError, match="mutually exclusive"):
        capi_host.llm_create(cfg, json.dumps(
            {"checkpoint_dir": llama_ckpt, "weights_npz": "w.npz"}))
    with pytest.raises(ValueError, match="does not match"):
        capi_host.llm_create(cfg, json.dumps(
            {"checkpoint_dir": llama_ckpt, "family": "opt"}))
    with pytest.raises(ValueError):
        capi_host.llm_create(cfg, json.dumps(
            {"checkpoint_dir": llama_ckpt, "quantize": "int7"}))

    host = capi_host.llm_create(cfg, json.dumps(
        {"checkpoint_dir": llama_ckpt, "quantize": "int8"}))
    g = capi_host.register_request(host, PROMPT, NEW_TOKENS)
    assert capi_host.generate(host) == 1
    out = capi_host.get_output(host, g)
    assert len(out) == NEW_TOKENS

    # same tokens as the in-memory int8 path (quantize-on-load contract)
    ref_model, _ = _build_tiny("llama", seed=0)
    ref_model.quantize_weights("int8")
    assert out == _gen(ref_model)[0]


# ---------------------------------------------------------------------------
# replica pool: crash failover + respawn + autoscaling spike
# ---------------------------------------------------------------------------

def test_pool_failover_and_autoscale(llama_ckpt):
    from flexflow_tpu.serve.faultinject import (FaultInjector,
                                                check_invariants)
    from flexflow_tpu.serve.loadgen import TenantSpec, WorkloadSpec
    from flexflow_tpu.serve.replica import (ReplicaPool,
                                            checkpoint_replica_factory,
                                            spike_run)

    factory = checkpoint_replica_factory(llama_ckpt, slots=2, max_seq=64)
    prompts = [[2 + i, 9, 4 + i] for i in range(6)]

    # reference tokens from a single standalone engine off the same
    # checkpoint (different FFConfig seed — weights come from disk)
    ref_handle = factory(99)
    refs = _gen(ref_handle.ffmodel, prompts)

    pool = ReplicaPool(factory, n_replicas=2)
    pool.start_server()
    try:
        # crash replica 0 mid-run: its 3rd engine step raises
        injector = FaultInjector(error_every=3, max_errors=1)
        injector.install(pool.replicas[0].handle.ffmodel)
        try:
            guids, ev = pool.submit(prompts, NEW_TOKENS, 0)
            assert ev.wait(timeout=180)
        finally:
            injector.uninstall()
        results = [pool.rm.results[g] for g in guids]
        # every future resolved ok — the crash never surfaces as an error
        assert [r.status for r in results] == ["ok"] * len(prompts)
        # ...with token-identical output (failed-over requests re-prefill
        # on a survivor built from the same checkpoint)
        assert [list(r.output_tokens) for r in results] == refs
        assert sum(r.failovers for r in results) >= 1
        assert pool.replicas[0].crashes == 1

        # the respawned replica rejoins from disk with a measured cold
        # start
        deadline = time.monotonic() + 120
        while pool.n_alive() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert pool.n_alive() == 2
        stats = pool.stats()
        assert stats["failovers_total"] >= 1
        assert stats["failover_recovery_s"] is not None
        assert len(stats["cold_starts_s"]) == 3     # 2 initial + respawn
        assert stats["cold_start_s"] > 0

        # autoscale under a spike: outstanding >= slots+1 triggers a
        # scale_up at the measured cold-start delay
        spec = WorkloadSpec(prompt_lens=(4, 8), output_lens=(24, 32),
                            vocab_size=128,
                            tenants=(TenantSpec("default", 1.0,
                                                deadline_s=2.0),))
        sp = spike_run(pool, spec, base_rps=4.0, spike_multiple=16.0,
                       n_base=6, n_spike=12, seed=1, timeout_s=180)
        assert sp["scaled_up"]
        assert sp["cold_start_s"] > 0
        assert sp["n_replicas_after"] == 3
        assert sp["base"]["resolved_fraction"] == 1.0
        assert sp["spike"]["resolved_fraction"] == 1.0
        assert sp["slo_violation_s"] >= 0.0

        # pool-aware leak audit: live replicas' slot tables + the pool's
        # own entry/waiter tables are clean
        assert check_invariants(pool) == []
    finally:
        pool.stop_server(flush_timeout_s=30)


# ---------------------------------------------------------------------------
# loadgen: failover wait attribution + summarize accounting
# ---------------------------------------------------------------------------

def test_attribute_failover_wait_fake_clock():
    from flexflow_tpu.serve.loadgen import attribute_failover_wait

    # fake clock: submitted t=0, crashed engine held it until t=3.5,
    # survivor then queued it 0.5s, prefilled 0.2s, decoded 1.3s
    # (final engine: latency 2.0, queue_wait 0.5) — pool saw 5.5s total
    qw, ttft = attribute_failover_wait(pool_latency_s=5.5,
                                       final_latency_s=2.0,
                                       final_queue_wait_s=0.5,
                                       final_prefill_s=0.2)
    # service time stays the survivor's 1.5s; ALL dead time (3.5 lost on
    # the crashed replica + 0.5 requeued) lands in queue wait
    assert qw == pytest.approx(4.0)
    assert ttft == pytest.approx(4.2)
    # degenerate clocks never go negative
    qw, ttft = attribute_failover_wait(1.0, 2.0, 0.1)
    assert qw >= 0.0 and ttft >= qw


def test_summarize_counts_failovers():
    from flexflow_tpu.serve.loadgen import RequestRecord, summarize

    def rec(i, failovers=0, queue_wait=0.0):
        return RequestRecord(idx=i, tenant="default", scheduled_s=0.0,
                             submitted_s=0.0, prompt_tokens=4,
                             output_tokens=8, latency_s=1.0 + queue_wait,
                             ttft_s=queue_wait, queue_wait_s=queue_wait,
                             prefill_s=0.0, failovers=failovers)

    rep = summarize([rec(0), rec(1, failovers=1, queue_wait=3.0),
                     rec(2, failovers=2, queue_wait=5.0)],
                    offered_rps=1.0, n_scheduled=3)
    assert rep["n_failed_over"] == 2
    assert rep["failovers_total"] == 3
    assert rep["resolved_fraction"] == 1.0
    # the re-dispatch wait shows up as queue wait, not service time
    assert rep["queue_wait_p99_s"] >= 3.0
