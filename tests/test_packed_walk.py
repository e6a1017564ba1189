"""The dense family's chunk walk over a PACKED token axis
(``models/llama.py`` ``extend_layers_packed``), held to the walk over
``[rows, width]`` rectangles it replaces in serving
(``_chunk_layers_paged``, which stays spec verify's) and to the
cache-free forward: the benchmark's chunk (512 over pages of 128, four
rows a wave) on the debug model, every prompt length of the deck and
around it in waves of 1-4 rows, as the engine packs them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import llama

C, PAGE, R, PMAX = 512, 128, 4, 16
LADDER = [128, 256, 384, 512, 768, 1024, 1536, 2048]
WINDOWS = (512, 1024, 2048)
LENGTHS = [1, 17, 71, 330, 458, 512, 583, 1100]
CFG = llama.PRESETS["debug-1k"]
POOL = 1 + R * PMAX


@functools.lru_cache(maxsize=None)
def _params():
    stacked = llama.init_params_fast(CFG, 0, jnp.float32)
    return stacked, llama.consume_split_params_layers(dict(stacked, layers=dict(stacked["layers"])))


def _tables():
    # slot s holds pages 1 + s * PMAX ..; page 0 is the scratch page
    return jnp.asarray(1 + np.arange(R * PMAX).reshape(R, PMAX), jnp.int32)


def _prompt(n, salt):
    return [(i * salt + 3) % 250 + 1 for i in range(n)]


def _wave(rows, first):
    at = LENGTHS.index(first)
    return [_prompt(LENGTHS[(at + j) % len(LENGTHS)], 7 + 2 * j) for j in range(rows)]


@functools.partial(jax.jit, static_argnames=("seg",))
def _packed(params, caches, tokens, rows, pick, seg):
    starts, counts, offsets, slots = rows
    return llama.extend_layers_packed(
        params, CFG, tokens, starts, counts, offsets, slots, _tables(), caches, PAGE,
        seg=seg, windows=WINDOWS, window_index=pick[1], n_rows=pick[0],
    )


@functools.partial(jax.jit, static_argnames=("window",))
def _rect(params, caches, tokens, offsets, valid, slots, window):
    h, caches = llama._chunk_layers_paged(
        params, CFG, tokens, offsets, valid, slots, _tables(), caches, window, PAGE,
    )
    last = jnp.take_along_axis(h, (jnp.clip(valid, 1, C) - 1)[:, None, None], axis=1)[:, 0]
    return last, caches


def _serve(prompts, caches, packed):
    """The engine's chunk loop over one wave (rows in slots 0..):
    every row's last-token hidden state and the pools."""
    lengths = np.array([len(p) for p in prompts])
    last = np.zeros((len(prompts), CFG.hidden_size), np.float32)
    for k in range(-(-int(lengths.max()) // C)):
        valid = np.clip(lengths - k * C, 0, C)
        live = [i for i in range(len(prompts)) if valid[i] > 0]
        window = next(w for w in WINDOWS if w >= (k + 1) * C)
        if packed:
            T = next(t for t in LADDER if t >= valid.sum())
            tokens, rows, at = np.zeros((T,), np.int32), np.zeros((4, R), np.int32), 0
            for j, i in enumerate(live):
                tokens[at:at + valid[i]] = prompts[i][k * C:k * C + valid[i]]
                rows[:, j] = (at, valid[i], k * C, i)
                at += valid[i]
            rows[0, len(live):] = at
            pick = np.array([len(live), WINDOWS.index(window)], np.int32)
            h, caches = _packed(_params()[1], caches, jnp.asarray(tokens), jnp.asarray(rows), jnp.asarray(pick), seg=min(T, C))
            last[live] = np.asarray(h)[:len(live)]
        else:
            tokens = np.zeros((R, C), np.int32)
            for i in live:
                tokens[i, :valid[i]] = prompts[i][k * C:k * C + valid[i]]
            valid_r = np.zeros((R,), np.int32)
            valid_r[:len(prompts)] = valid
            h, caches = _rect(_params()[1], caches, jnp.asarray(tokens), jnp.full((R,), k * C, jnp.int32),
                              jnp.asarray(valid_r), jnp.arange(R, dtype=jnp.int32), window=window)
            last[live] = np.asarray(h)[live]
    return last, caches


def _live_rows(cache, prompts):
    """Each pool array's rows at the wave's live positions."""
    out = {}
    for key, buf in cache.items():
        # (a scale plane holds a page's scales in the flat order t * Hkv + h, in either layout)
        buf = np.asarray(buf)[1:].reshape((R, PMAX * PAGE) + (buf.shape[2:] if buf.ndim == 4 else (-1,)))
        out[key] = [buf[i, :len(p)] for i, p in enumerate(prompts)]
    return out


@pytest.mark.parametrize("rows", [1, 2, 3, 4])
@pytest.mark.parametrize("first", LENGTHS)
@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_packed_wave_equals_the_rectangle_walk(kv, first, rows):
    prompts = _wave(rows, first)
    fresh = lambda: llama.init_kv_pool(CFG, POOL, PAGE, jnp.float32, quantized=kv == "int8")  # noqa: E731
    got, got_caches = _serve(prompts, fresh(), packed=True)
    want, want_caches = _serve(prompts, fresh(), packed=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    head = lambda h: np.asarray(llama._head(_params()[1], jnp.asarray(h)[:, None], CFG))[:, 0]  # noqa: E731
    # first tokens equal
    assert np.array_equal(head(got).argmax(-1), head(want).argmax(-1))
    for a, b in zip(got_caches, want_caches):
        a, b = _live_rows(a, prompts), _live_rows(b, prompts)
        for key in a:
            for x, y in zip(a[key], b[key]):
                if key in ("k", "v") and kv == "int8":
                    # the written pool is bit-equal but where a value sat on a
                    # rounding edge of its scale (products of [1, T] against
                    # [rows, 512] round differently in float32): one step
                    assert np.abs(x.astype(np.int32) - y.astype(np.int32)).max() <= 1
                    assert (x != y).mean() < 1e-3
                else:
                    np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)
    if kv == "float32":
        # and the cache-free forward's last-position logits
        stacked = _params()[0]
        for i, p in enumerate(prompts):
            logits, _ = llama.forward(stacked, CFG, jnp.asarray([p], jnp.int32),
                                      jnp.arange(len(p), dtype=jnp.int32)[None])
            np.testing.assert_allclose(head(got[i:i + 1])[0], np.asarray(logits[0, -1]), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("walk", ["packed", "rectangle_wrapper"])
def test_a_dead_token_never_writes_a_live_page(walk, kv):
    """Whatever the axis holds beyond its rows' live tokens (the gap
    behind a row, the padded end, a dead row) lands on the scratch page:
    every other page keeps every value but at the live tokens' own
    coordinates."""
    caches = llama.init_kv_pool(CFG, POOL, PAGE, jnp.float32, quantized=kv == "int8")
    caches = [{k: jnp.full_like(v, 5) for k, v in c.items()} for c in caches]
    params = _params()[1]
    # rows: slot 2 extends 71 tokens from 512, slot 0 holds 330 from 0, one dead row, slot 3 one token from 1024
    counts, offsets, slots = [71, 330, 0, 1], [512, 0, 128, 1024], [2, 0, 1, 3]
    if walk == "packed":
        starts = [0, 71, 401, 401]
        _, out = llama.extend_layers_packed(
            params, CFG, jnp.arange(512, dtype=jnp.int32) % 200 + 1, jnp.asarray(starts), jnp.asarray(counts),
            jnp.asarray(offsets), jnp.asarray(slots), _tables(), caches, PAGE, seg=512, windows=WINDOWS,
            window_index=jnp.int32(2), n_rows=jnp.int32(4),
        )
    else:
        _, out = llama.extend_layers_paged(
            params, CFG, (jnp.arange(4 * 384, dtype=jnp.int32) % 200 + 1).reshape(4, 384), jnp.asarray(offsets),
            jnp.asarray(counts), jnp.asarray(slots), _tables(), caches, 2048, PAGE,
        )
    written = np.zeros((POOL, PAGE), bool)
    for n, at, slot in zip(counts, offsets, slots):
        pos = at + np.arange(n)
        written[1 + slot * PMAX + pos // PAGE, pos % PAGE] = True
    for c in out:
        for key, buf in c.items():
            buf = np.asarray(buf)
            changed = (buf != 5).reshape(POOL, PAGE, -1).any(-1)
            assert not changed[1:][~written[1:]].any(), key
            if key in ("ks", "vs") or kv == "float32":
                assert changed[written].all(), key  # and every live token did land


def test_the_rectangle_wrapper_is_the_rectangle_walk():
    """``extend_layers_paged`` ([rows, width], what every family's
    ``extend_paged`` takes: the rehearsal compile and the benchmark's
    tests call it so) packs its rectangle and equals the old walk."""
    params = _params()[1]
    caches = llama.init_kv_pool(CFG, POOL, PAGE, jnp.float32)
    tokens = (jnp.arange(4 * 128, dtype=jnp.int32) * 7 % 200 + 1).reshape(4, 128)
    offsets, valid, slots = jnp.asarray([0, 128, 0, 256]), jnp.asarray([128, 5, 0, 77]), jnp.asarray([1, 0, 2, 3])
    got, got_c = llama.extend_layers_paged(params, CFG, tokens, offsets, valid, slots, _tables(), caches, 512, PAGE)
    h, want_c = llama._chunk_layers_paged(params, CFG, tokens, offsets, valid, slots, _tables(), caches, 512, PAGE)
    want = jnp.take_along_axis(h, (jnp.clip(valid, 1, 128) - 1)[:, None, None], axis=1)[:, 0]
    live = np.asarray(valid) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], rtol=1e-5, atol=1e-6)
    for a, b in zip(got_c, want_c):
        for key in a:
            np.testing.assert_allclose(np.asarray(a[key])[1:], np.asarray(b[key])[1:], rtol=1e-5, atol=1e-6)
