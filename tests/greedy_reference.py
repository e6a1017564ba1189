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
    assert eng.shapes.packed == (kind == "packed")
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


def reference_walk_greedy(eng, prompt, n, pad_to=64):
    """The ``n`` greedy tokens of ``prompt`` by the family's REFERENCE
    walks on the engine's own weights: one ``prefill_paged`` of the
    whole prompt (the walk no engine program calls: models/registry.py;
    padded to whole ``pad_to``s, the longest block any family's
    recurrence walks), then one ``decode_paged`` step a token, on a
    fresh pool of the engine's page size. No scheduler, no chunk, no
    step program: what a served prompt of any length is held to, for
    every family."""
    import jax
    import jax.numpy as jnp

    fam, cfg, page = eng._family, eng.model_config, eng.engine_config.page_size
    width = -(-len(prompt) // pad_to) * pad_to
    per_row = -(-(width + n) // page) + 1
    walks = eng.__dict__.setdefault("_reference_walks", {})  # jitted once an engine and shape
    if per_row not in walks:
        walks[per_row] = (
            jax.jit(lambda params, caches, tokens, lengths, tables: fam.prefill_paged(
                params, cfg, caches, tokens, lengths, jnp.zeros((1,), jnp.int32), tables, page,
                use_flash=False)),
            jax.jit(lambda params, caches, token, position, tables: fam.decode_paged(
                params, cfg, caches, token, position, jnp.ones((1,), bool), tables,
                per_row * page, page)),
        )
    prefill, decode = walks[per_row]
    caches = fam.init_paged_cache(cfg, 1 + per_row, page, 1, jnp.float32)
    tables = 1 + jnp.arange(per_row, dtype=jnp.int32).reshape(1, per_row)
    tokens = jnp.asarray([list(prompt) + [0] * (width - len(prompt))], jnp.int32)
    logits, caches = prefill(eng.params, caches, tokens, jnp.asarray([len(prompt)], jnp.int32), tables)
    out = [int(np.argmax(np.asarray(logits[0, : eng._sample_vocab])))]
    while len(out) < n:
        position = jnp.full((1,), len(prompt) + len(out) - 1, jnp.int32)
        logits, caches = decode(eng.params, caches, jnp.asarray(out[-1:], jnp.int32), position, tables)
        out.append(int(np.argmax(np.asarray(logits[0, : eng._sample_vocab]))))
    return out
