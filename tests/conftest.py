"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Model/parallelism tests exercise real tp/dp/sp shardings on a virtual mesh
(jax.sharding.Mesh over 8 host CPU devices), so multi-chip code paths are
covered without TPU hardware.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Force the virtual CPU platform; set RUN_TESTS_ON_TPU=1 to run against real
# hardware instead. The env var decides when jax is first imported here;
# the config update also covers a plugin that imported jax before this file.
if not os.environ.get("RUN_TESTS_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

import pathlib
import sys

# Make the repo root importable regardless of the pytest invocation cwd.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import pytest

# Modules whose tests compile jitted engines, shard_map programs over the
# 8-device mesh, execute notebooks, or build transformers golden models —
# minutes each, so they form the `slow` tier (pytest.ini defaults to
# `-m "not slow"`; run them with `pytest -m slow`, or everything with
# `pytest -m ""`). Auto-marked here so new tests in these files inherit
# the tier without per-test decorators.
SLOW_MODULES = {
    "test_engine",
    "test_engine_tp",
    "test_flash_attention",
    "test_hf_golden",
    "test_hf_streaming",
    "test_int8",
    "test_llama",
    "test_loadgen_e2e",
    "test_lora",
    "test_notebooks",
    "test_paged_kv",
    "test_parallel",
    "test_preempt_restore_matrix",
    "test_pipeline_parallel",
    "test_prefix_cache",
    "test_quality_smoke",
    "test_retrieval_tier_e2e",
    "test_router_fleet",
    "test_scheduler_disagg",
    "test_spec_decode",
    "test_spec_draft",
    "test_spec_pipeline",
    "test_server_tp_e2e",
    "test_tp_kernels",
}


def pytest_collection_modifyitems(config, items):
    # A renamed/split slow module must not silently fall into the fast
    # tier: every listed name has to resolve to a real test file.
    here = pathlib.Path(__file__).parent
    missing = [m for m in SLOW_MODULES if not (here / f"{m}.py").exists()]
    assert not missing, f"SLOW_MODULES entries without a test file: {missing}"
    for item in items:
        if item.module.__name__ in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _isolate_echo_chain_docs():
    """EchoChain.documents is class-level (it must survive per-request
    instantiation, like the reference's vector store does), so scrub it
    between tests to keep them order-independent."""
    from generativeaiexamples_tpu.chains.echo import EchoChain

    EchoChain.documents.clear()
    yield
    EchoChain.documents.clear()


@pytest.fixture()
def clean_app_env(monkeypatch):
    """Scrub APP_* env vars so config tests see only what they set."""
    for key in list(os.environ):
        if key.startswith("APP_"):
            monkeypatch.delenv(key, raising=False)
    return monkeypatch
