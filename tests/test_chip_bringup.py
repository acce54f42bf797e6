"""What the chip bring-up (chip_smoke.py) leans on, checked on the CPU.

Lean by design (tier-1 budget): lowering-only kernel exports at the
smoke's shapes, and the small decisions that keep a later number from
lying about where it ran — which chip's peaks, which devices, which
compile cache, which native library, interpreted or compiled kernels.
The smoke's own tiny CPU rehearsal is ``slow``.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the Pallas kernel lowers to Mosaic at the shapes the smoke reaches
# ---------------------------------------------------------------------------

def _export_tpu(fn, *avals):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    assert "tpu_custom_call" in exported.mlir_module()


def test_flash_attend_exports_for_tpu_at_smoke_shapes():
    from flexflow_tpu.kernels.attention import flash_attend

    # LLaMA-2-7B widths; 2 slots x 2 layers keep the lowering light
    L, R, H, KH, S, D, W, chunk = 2, 2, 32, 32, 1024, 128, 8, 128
    bf, i32 = jnp.bfloat16, jnp.int32
    sds = jax.ShapeDtypeStruct
    stack = sds((L, R, KH, S, D), bf)
    _export_tpu(                      # width-8 decode, fused in-place append
        lambda q, k, v, n, qp, kn, vn, ap: flash_attend(
            q, k, v, n, qp, append_kv=(kn, vn, ap), causal=True,
            layer_idx=1),
        sds((R, W, H, D), bf), stack, stack, sds((R,), i32),
        sds((R, W), i32), sds((R, 1, KH, D), bf), sds((R, 1, KH, D), bf),
        sds((R,), i32))
    _export_tpu(                      # width-8 tree verify with a bias
        lambda q, k, v, n, qp, b: flash_attend(
            q, k, v, n, qp, bias=b, causal=False, layer_idx=1),
        sds((R, W, H, D), bf), stack, stack, sds((R,), i32),
        sds((R, W), i32), sds((R, W, S), jnp.float32))
    _export_tpu(                      # one prefill chunk
        lambda q, k, v, n, qp: flash_attend(q, k, v, n, qp, causal=True,
                                            layer_idx=0),
        sds((R, chunk, H, D), bf), stack, stack, sds((R,), i32),
        sds((R, chunk), i32))
    _export_tpu(                      # the compact prefill batch: 4 segments
        lambda q, k, v, n, qp, rows: flash_attend(      # read by row map
            q, k, v, n, qp, rows=rows, causal=True, layer_idx=0),
        sds((4, chunk, H, D), bf), stack, stack, sds((4,), i32),
        sds((4, chunk), i32), sds((4,), i32))


def test_flash_attend_run_append_exports_for_tpu_at_sdars_pass():
    """The fused append of a run at SDAR-30B-A3B's pass: 32 rows x 8
    positions (two blocks of four, a count a row), 32 query and 4 key/value
    heads of 128 over the bfloat16 stack of 1024 positions (2 layers keep
    the lowering light)."""
    from flexflow_tpu.kernels.attention import flash_attend

    L, R, H, KH, S, D, W = 2, 32, 32, 4, 1024, 128, 8
    bf, i32 = jnp.bfloat16, jnp.int32
    sds = jax.ShapeDtypeStruct
    stack = sds((L, R, KH, S, D), bf)
    _export_tpu(
        lambda q, k, v, n, qp, kn, vn, ap, cnt: flash_attend(
            q, k, v, n, qp, append_kv=(kn, vn, ap, cnt), causal=True,
            layer_idx=1),
        sds((R, W, H, D), bf), stack, stack, sds((R,), i32),
        sds((R, W), i32), sds((R, W, KH, D), bf), sds((R, W, KH, D), bf),
        sds((R,), i32), sds((R,), i32))


# (tokens, experts held, router width or None, hidden, expert width, top-k)
MOE_STEPS = {
    "decode_32_rows": (32, 64, None, 2048, 1024, 8),
    "prefill_512_tokens": (512, 64, None, 2048, 1024, 8),
    "sdar_pass_256_tokens": (256, 128, None, 2048, 768, 8),
    "k_exaone_prefill_staged": (512, 16, 128, 6144, 2048, 8),
}


@pytest.mark.parametrize("step", MOE_STEPS)
def test_moe_experts_exports_for_tpu_at_the_cells_shapes(step):
    """The routed-expert kernel at OLMoE-1B-7B's widths (64 int8 experts of
    2048 x 1024, top-8): a decode step of 32 rows and a prefill step of 512
    real tokens; SDAR's pass of 32 rows x 8 over 128 experts of 768; all
    three keep their rows in VMEM. K-EXAONE's prefill step (16 held experts
    of 6144 x 2048) does not fit there and exports the staged form. A
    Mosaic rejection shows here and not on the chip."""
    from flexflow_tpu.kernels import moe as K
    from flexflow_tpu.quant import QuantizedWeight

    tokens, E, width, H, inter, k = MOE_STEPS[step]
    sds = jax.ShapeDtypeStruct
    assert K.rows_fit(tokens, H, inter, 1, 2) is (width is None)

    def q(rows, cols):
        return QuantizedWeight("int8", sds((E, rows, cols), jnp.int8),
                               sds((E, cols), jnp.float32), rows, "bfloat16")

    _export_tpu(
        lambda x, idx, w, valid, g, u, d: K.moe_experts(
            x, idx, w, valid, g, u, d, pallas=True,
            held=None if width is None else (0, width))[0],
        sds((tokens, H), jnp.bfloat16), sds((tokens, k), jnp.int32),
        sds((tokens, k), jnp.float32), sds((tokens,), jnp.bool_),
        q(H, inter), q(H, inter), q(inter, H))


@pytest.mark.parametrize("shape", [(32, 8, 128), (4, 128, 128)],
                         ids=["sdar_pass_256_rows", "prefill_512_rows"])
def test_router_top_k_exports_for_tpu_at_sdars_shapes(shape):
    """The router's ``TopK`` at SDAR-30B-A3B's shapes, 8 of 128 float32
    scores for a pass of 32 rows x 8 positions and for a prefill step of
    4 x 128 tokens: the module for the TPU sorts ``[rows, 128]``, never
    the three-dimensional scores (75 us a layer there, PR 59)."""
    from flexflow_tpu.ops.base import OpContext
    from flexflow_tpu.ops.reduction_ops import TopK

    rows = shape[0] * shape[1]
    text = jax.export.export(
        jax.jit(lambda x: TopK.forward({"k": 8}, {}, [x], OpContext())),
        platforms=["tpu"])(
            jax.ShapeDtypeStruct(shape, jnp.float32)).mlir_module()
    sorts = [line for line in text.splitlines()
             if "mhlo.topk" in line or "stablehlo.sort" in line]
    assert len(sorts) == 1, sorts
    assert f"(tensor<{rows}x128xf32>) ->" in sorts[0]


def test_interpret_switch_on_a_tpu_backend_raises(monkeypatch):
    from flexflow_tpu import kernels as ffk

    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    assert ffk.use_pallas()           # CPU: the tests' interpreter switch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="FF_PALLAS_INTERPRET"):
        ffk.use_pallas()
    monkeypatch.delenv("FF_PALLAS_INTERPRET")
    assert ffk.use_pallas()           # TPU without the switch: compiled


# ---------------------------------------------------------------------------
# which chip, which devices
# ---------------------------------------------------------------------------

def test_chip_for_device(monkeypatch):
    from flexflow_tpu.search.machine_model import (MachineModel,
                                                   chip_for_device)

    assert chip_for_device() == "cpu-sim"         # the test backend
    dev = lambda kind: types.SimpleNamespace(platform="tpu",
                                             device_kind=kind)
    assert chip_for_device(dev("TPU v5 lite")) == "v5e"
    assert chip_for_device(dev("TPU v4")) == "v4"
    with pytest.raises(ValueError, match="TPU v9"):
        chip_for_device(dev("TPU v9"))
    # tpu_chip=None reaches the search and the cost export through here
    monkeypatch.setattr(jax, "devices", lambda *a: [dev("TPU v9")])
    with pytest.raises(ValueError, match="TPU v9"):
        MachineModel.from_name(None, 1)


def test_make_mesh_uses_num_devices():
    import flexflow_tpu as ff
    from flexflow_tpu.parallel.mesh import make_mesh

    n = len(jax.devices())
    assert n > 1                                  # conftest's virtual mesh
    assert make_mesh(ff.FFConfig()).devices.size == n    # absorbed into dp
    one = make_mesh(ff.FFConfig(num_devices=1))
    assert one.devices.size == 1
    assert one.devices.flat[0] == jax.devices()[0]
    tp = make_mesh(ff.FFConfig(num_devices=2, tensor_parallelism_degree=2))
    assert dict(tp.shape) == {"model": 2}
    with pytest.raises(ValueError, match="num_devices"):
        make_mesh(ff.FFConfig(num_devices=n + 1))


# ---------------------------------------------------------------------------
# compile cache: placeable, fixed, never set by the package or the tests
# ---------------------------------------------------------------------------

def test_compile_cache_dir(monkeypatch):
    from flexflow_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    # JAX reads the variable itself: no directory is set in code
    assert "jax_compilation_cache_dir" not in dict(updates)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert dict(updates)["jax_compilation_cache_dir"] == fixed


# ---------------------------------------------------------------------------
# native library: keyed on what it was built from, not on mtimes
# ---------------------------------------------------------------------------

def test_native_build_is_keyed_on_source_content(tmp_path, monkeypatch):
    from flexflow_tpu import native

    if not native.native_available():
        pytest.skip("no native toolchain")
    assert not native._needs_build()
    stamp = tmp_path / "stamp"
    monkeypatch.setattr(native, "_STAMP_PATH", str(stamp))
    assert native._needs_build()                  # no stamp: unknown origin
    stamp.write_text("0" * 64 + "\n")
    assert native._needs_build()                  # built from other sources
    stamp.write_text(native._source_hash() + "\n")
    # a newer source mtime alone changes nothing; content decides
    assert not native._needs_build()


# ---------------------------------------------------------------------------
# chip_smoke.py itself
# ---------------------------------------------------------------------------

def _run_smoke(*args, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *args], capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_chip_smoke_refuses_without_a_tpu(monkeypatch, capsys):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    assert chip_smoke.main([]) != 0               # in-process: conftest's CPU
    out = capsys.readouterr()
    assert out.out == ""                          # no result of any kind
    assert "no TPU" in out.err


def test_chip_smoke_result_line_has_exactly_the_contract_keys(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    out = json.loads(chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
    assert out == {"ok": True, "device": {"platform": "tpu",
                                          "kind": "TPU v5 lite", "count": 1}}
    assert list(out) == ["ok", "device"]
    assert list(out["device"]) == ["platform", "kind", "count"]


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal():
    r = _run_smoke("--rehearse-cpu", timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    summary, result = map(json.loads, r.stdout.strip().splitlines()[-2:])
    # the last line is the result alone; the summary is the line before it
    assert result == {"ok": True, "device": summary["device"]}
    assert set(result["device"]) == {"platform", "kind", "count"}
    out = summary
    assert out["ok"] and out["rehearsal"] and out["claim"] is None
    assert list(out)[-1] == "claim"
    assert out["device"]["platform"] == "cpu"
    assert out["phases"]["serving"]["specinfer"]["match_first30"] == "4/4"
    assert out["phases"]["serving"]["attention"]["fallback_traces"] == {}
