"""The one compile-cache / host-platform rule (utils/jax_env.py)."""
import os
import re
import tempfile

import pytest

from generativeaiexamples_tpu.utils import jax_env


@pytest.fixture()
def cache_config_restored():
    """configure_compile_cache hands its directory to the imported jax;
    put the test session's setting back afterwards."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_set_is_left_untouched(monkeypatch, tmp_path):
    target = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(jax_env.CACHE_ENV, target)
    assert jax_env.compile_cache_dir() == target
    assert jax_env.configure_compile_cache() == target
    assert os.environ[jax_env.CACHE_ENV] == target
    # set nothing in code, create nothing: the deployment owns the place
    assert not os.path.exists(target)


def test_unset_resolves_to_checkout_jax_cache(monkeypatch, cache_config_restored):
    monkeypatch.delenv(jax_env.CACHE_ENV, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert jax_env.compile_cache_dir() == want
    assert jax_env.configure_compile_cache() == want
    # exported for children, handed to the already-imported jax
    assert os.environ[jax_env.CACHE_ENV] == want
    import jax

    assert jax.config.jax_compilation_cache_dir == want


def test_default_path_is_stable_not_temporary(monkeypatch):
    """The directory is part of the cache key: no temporary, per-user,
    per-process or per-time component."""
    monkeypatch.delenv(jax_env.CACHE_ENV, raising=False)
    path = jax_env.compile_cache_dir()
    assert path == jax_env.compile_cache_dir()
    assert not path.startswith(tempfile.gettempdir() + os.sep)
    rel = os.path.relpath(path, jax_env.checkout_root())
    assert rel == ".jax_cache"
    for token in (str(os.getpid()), str(os.getuid()) if os.getuid() else None):
        if token:
            assert not re.search(rf"(^|\D){token}(\D|$)", rel)
    assert not re.search(r"\d{4,}", rel)  # no timestamp / counter


@pytest.mark.parametrize(
    "given,want",
    [
        ("tpu", "tpu,cpu"),
        ("tpu,cpu", "tpu,cpu"),
        ("cpu", "cpu"),
        ("", ""),
        (" tpu ", "tpu,cpu"),
    ],
)
def test_host_platform_stays_reachable(monkeypatch, given, want):
    import jax

    prev = jax.config.jax_platforms
    monkeypatch.setenv(jax_env.PLATFORMS_ENV, given)
    try:
        assert jax_env.ensure_host_platform() == want
        assert os.environ[jax_env.PLATFORMS_ENV] == want
    finally:
        jax.config.update("jax_platforms", prev)


def test_host_device_is_the_cpu():
    assert jax_env.host_device().platform == "cpu"


def test_entry_points_share_the_helper():
    """No entry point carries cache logic of its own."""
    repo = jax_env.checkout_root()
    for rel in (
        "tools/precompile.py", "chip_smoke.py",
        "generativeaiexamples_tpu/server/__main__.py",
        "generativeaiexamples_tpu/engine/server.py",
        "generativeaiexamples_tpu/router/__main__.py",
    ):
        with open(os.path.join(repo, rel), encoding="utf-8") as fh:
            src = fh.read()
        assert "jax_env.bootstrap()" in src, rel
        assert 'JAX_COMPILATION_CACHE_DIR"' not in src.replace(
            'os.environ.get("JAX_COMPILATION_CACHE_DIR")', ""
        ), rel


@pytest.mark.parametrize(
    "platforms,preset,want",
    [
        ("tpu,cpu", None, "0"),  # the chip machine: sub-second programs are kept
        ("tpu", None, "0"),
        ("", None, "0"),  # jax's own order: the accelerator comes first
        ("cpu", None, None),  # the test suite keeps jax's default
        (" cpu ,tpu", None, None),
        ("tpu,cpu", "2.5", "2.5"),  # the deployment's own choice stands
    ],
)
def test_small_executables_are_persisted_on_an_accelerator(monkeypatch, platforms, preset, want):
    import jax

    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv(jax_env.PLATFORMS_ENV, platforms)
    monkeypatch.delenv(jax_env.MIN_COMPILE_ENV, raising=False)
    if preset is not None:
        monkeypatch.setenv(jax_env.MIN_COMPILE_ENV, preset)
    try:
        jax_env.persist_small_executables()
        assert os.environ.get(jax_env.MIN_COMPILE_ENV) == want
        changed = want == "0"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == (0.0 if changed else prev)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
