"""Test configuration: run everything on a virtual 8-device CPU mesh.

The reference can only test multi-node behavior on real 2-node CI clusters
(reference tests/multinode_helpers/, .github/workflows/multinode-test.yml);
on TPU/JAX we get a faithful multi-device SPMD simulation for free via
--xla_force_host_platform_device_count (SURVEY §4 "Implication").
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual 8-device CPU mesh whatever the machine holds:
# pin the platform after import but before any backend initialises (the
# setdefault above loses to a JAX_PLATFORMS the caller exported).
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The tier-1 suite saturates its 870 s wall-clock budget, and pytest's
# alphabetical collection put the newest (lean) subsystems — telemetry,
# loadgen — BEHIND the cutoff, so their dots never counted. Hoist them to
# the front of the run: they share one tiny session-scoped spec pair and
# finish in seconds, so the reordering costs the heavier files nothing.
_EARLY_FILES = ("test_loadgen.py", "test_telemetry.py",
                "test_spec_controller.py", "test_overload.py",
                "test_fleet.py", "test_observability.py",
                "test_prefix_cache.py", "test_seq_parallel.py")


def pytest_collection_modifyitems(session, config, items):
    def rank(item):
        name = item.fspath.basename
        return _EARLY_FILES.index(name) if name in _EARLY_FILES \
            else len(_EARLY_FILES)

    items.sort(key=rank)        # stable: preserves order within files


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _reset_layer_naming():
    from flexflow_tpu.core.layer import Layer

    Layer.reset_naming()
    yield


def _tiny_llama(mode, beam=1):
    import flexflow_tpu as ff
    from flexflow_tpu.models.llama import LLAMAConfig, create_llama_model

    tiny = LLAMAConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=128)
    cfg = ff.FFConfig(max_requests_per_batch=2, max_sequence_length=64,
                      max_tokens_per_batch=16, seed=0,
                      kv_cache_dtype="float32", max_beam_width=beam)
    m = ff.FFModel(cfg)
    create_llama_model(m, tiny, mode=mode)
    m.compile(comp_mode=ff.CompMode.COMP_MODE_INFERENCE)
    return m


@pytest.fixture(scope="session")
def tiny_spec_pair():
    """One TINY llama verify/draft pair shared across the telemetry and
    loadgen test files (tier-1 budget: these files must stay lean, so
    they build models ONCE per session, on the geometry test_serving
    proved out)."""
    from flexflow_tpu.ffconst import InferenceMode

    return (_tiny_llama(InferenceMode.TREE_VERIFY_MODE),
            _tiny_llama(InferenceMode.BEAM_SEARCH_MODE))


@pytest.fixture(scope="session")
def tiny_beam_draft():
    """``tiny_spec_pair``'s draft compiled at beam width 2, which sends it
    to the beam engine (``_spec_route`` refuses a width the draft was not
    compiled with): the same weights, so its best beam is the verifier's
    own greedy continuation."""
    from flexflow_tpu.ffconst import InferenceMode

    return _tiny_llama(InferenceMode.BEAM_SEARCH_MODE, beam=2)
