"""The tests' reference for what an engine serves: a cache-free greedy
decode through ``llama.forward`` in float32 (no page pool, no kernel,
no chunking, no step program), on the weights the engine draws itself
(``init_params_fast``, seed 0)."""
import contextlib
import dataclasses
import functools

import numpy as np


@contextlib.contextmanager
def rectangles():
    """llama as a family WITHOUT a packed walk: an engine built inside
    sends its waves as ``[rows, width]`` rectangles, the dispatch of
    every family that registers none (and llama's own before the packed
    axis): the walk the packed one is held to."""
    from generativeaiexamples_tpu.models import registry

    llama = registry.families()["llama"]
    registry._FAMILIES["llama"] = dataclasses.replace(llama, extend_packed=None)
    try:
        yield
    finally:
        registry._FAMILIES["llama"] = llama


def build_engine(kind: str, **cfg):
    """An engine whose waves go out ``kind``: 'packed' (llama as
    registered) or 'rect' (``rectangles``)."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    with (rectangles() if kind == "rect" else contextlib.nullcontext()):
        eng = LLMEngine(EngineConfig(**cfg))
    assert eng._packed == (kind == "packed")
    return eng


@functools.lru_cache(maxsize=None)
def reference_params(preset: str = "debug"):
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.utils import jax_env

    with jax.default_device(jax_env.host_device()):
        return llama.init_params_fast(llama.PRESETS[preset], 0, jnp.float32)


def reference_greedy(prompt, n, preset: str = "debug"):
    """The ``n`` tokens a greedy decode of ``prompt`` yields. Each step
    is one forward over prompt + answer so far, padded to the final
    length (causal attention: position t's logits are those of the
    prefix ending at t, whatever follows), so every step has one shape."""
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama

    cfg, params = llama.PRESETS[preset], reference_params(preset)
    ids = list(prompt)
    total = len(prompt) + n
    positions = jnp.arange(total, dtype=jnp.int32)[None]
    while len(ids) < total:
        tokens = jnp.asarray([ids + [0] * (total - len(ids))], jnp.int32)
        logits, _ = llama.forward(params, cfg, tokens, positions)
        ids.append(int(np.argmax(np.asarray(logits[0, len(ids) - 1]))))
    return ids[len(prompt):]


def served_walk_logits(params, cfg, tokens, prompt_len, page_size=8, dtype=None):
    """Logits of the SERVED llama walks over ``tokens`` [B, T] (stacked
    ``params``): a monolithic ``prefill_paged`` of the first
    ``prompt_len`` tokens, then one ``decode_paged`` step a token, each
    row on its own pages of a fresh pool. Returns (prefill's last-token
    logits [B, V], the decode steps' logits [T - prompt_len, B, V])."""
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama, registry

    fam = registry.family_of(cfg)
    B, T = tokens.shape
    per_row = -(-max(T, 1) // page_size) + 1
    layered = llama.consume_split_params_layers(dict(params, layers=dict(params["layers"])))
    caches = fam.init_paged_cache(
        cfg, 1 + B * per_row, page_size, B, dtype or params["embed"].dtype
    )
    tables = 1 + jnp.arange(B * per_row, dtype=jnp.int32).reshape(B, per_row)
    slots = jnp.arange(B, dtype=jnp.int32)
    lengths = jnp.full((B,), prompt_len, jnp.int32)
    last, caches = fam.prefill_paged(
        layered, cfg, caches, tokens[:, :prompt_len], lengths, slots, tables, page_size,
        use_flash=False,
    )
    live = jnp.ones((B,), bool)
    steps = []
    for t in range(prompt_len, T):
        logits, caches = fam.decode_paged(
            layered, cfg, caches, tokens[:, t], jnp.full((B,), t, jnp.int32), live, tables,
            per_row * page_size, page_size,
        )
        steps.append(logits)
    return last, steps
