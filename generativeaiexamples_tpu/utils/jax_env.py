"""Process-level JAX environment rules, in ONE place.

Every entry point that may compile (chain-server, engine server,
router, tools/precompile.py, chip_smoke.py) calls
:func:`bootstrap` before jax initializes a backend. Three rules:

- **Compile cache.** Where ``JAX_COMPILATION_CACHE_DIR`` is set the
  deployment owns the location and nothing here touches it; where it is
  not, the cache lives at ``<checkout>/.jax_cache`` (gitignored). The
  directory is part of the cache key, so it is never a temporary,
  per-user, per-process or per-time path — a directory that moves never
  hits.
- **Small executables are kept too.** jax persists only what took a
  second or more to compile; a serving start compiles ~300 programs
  under that (one page-table scatter per wave size, the eager fills
  and casts of engine build and warm-up), ~20 s of every start with an
  otherwise warm cache. On an accelerator the threshold is set to 0
  unless the deployment set ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
  itself; a CPU-only process (the test suite: thousands of cheap
  programs) keeps jax's default.
- **Host staging.** Weights are initialized/quantized on the host and
  device-put once (8B in bf16 would not fit a 16 GB chip), which needs
  jax's ``cpu`` backend. A ``JAX_PLATFORMS`` list restricted to the
  accelerator (``tpu``) removes it and ``jax.devices("cpu")`` raises, so
  the list gets ``,cpu`` appended; the accelerator stays first and
  therefore the default backend.

No jax import at module level: parents that launch a chip-holding child
(tools/loadgen, chip_smoke.py, perfbench/run.py) import this freely.
"""
from __future__ import annotations

import os
import sys

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
PLATFORMS_ENV = "JAX_PLATFORMS"
MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


def checkout_root() -> str:
    """The directory holding the ``generativeaiexamples_tpu`` package."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def compile_cache_dir() -> str:
    """The one rule: the environment's directory, else the checkout's."""
    return os.environ.get(CACHE_ENV) or os.path.join(
        checkout_root(), ".jax_cache"
    )


def configure_compile_cache() -> str:
    """Apply the rule and return the directory in force. A set
    environment variable is left exactly as found (jax reads it itself);
    otherwise the checkout default is exported for this process and its
    children, and handed to an already-imported jax."""
    path = compile_cache_dir()
    if os.environ.get(CACHE_ENV):
        return path
    os.makedirs(path, exist_ok=True)
    os.environ[CACHE_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def persist_small_executables() -> None:
    """Keep executables that compiled in under a second as well (see
    module docstring); a set variable or a CPU-first platform list is
    left as found."""
    names = [p.strip() for p in os.environ.get(PLATFORMS_ENV, "").split(",")]
    if MIN_COMPILE_ENV in os.environ or names[0] == "cpu":
        return
    os.environ[MIN_COMPILE_ENV] = "0"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def ensure_host_platform() -> str:
    """Keep the ``cpu`` backend reachable next to a restricted platform
    list (see module docstring). Returns the list in force ('' = jax's
    own default order, which always includes cpu)."""
    platforms = os.environ.get(PLATFORMS_ENV, "")
    names = [p.strip() for p in platforms.split(",") if p.strip()]
    if not names or "cpu" in names:
        return platforms
    platforms = ",".join(names + ["cpu"])
    os.environ[PLATFORMS_ENV] = platforms
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", platforms)
    return platforms


def bootstrap() -> str:
    """Entry-point preamble; returns the compile-cache directory."""
    persist_small_executables()
    ensure_host_platform()
    return configure_compile_cache()


def host_device():
    """The host (cpu) device weights are staged on before their one
    transfer to the accelerator."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            f"host staging needs jax's cpu backend, but {PLATFORMS_ENV}="
            f"{os.environ.get(PLATFORMS_ENV, '')!r} excludes it; start "
            "through an entry point (utils/jax_env.bootstrap appends it) "
            f"or set {PLATFORMS_ENV}=tpu,cpu"
        ) from exc
