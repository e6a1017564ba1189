"""Token sampling: temperature + nucleus (top-p), jit-safe.

Implements the generation controls the reference exposes through its
/generate API (reference: common/server.py:83-88 — temperature, top_p,
max_tokens, stop) as pure JAX ops that live inside the compiled decode step.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Nucleus sampling only considers the top-K logits (see sample_tokens).
NUCLEUS_TOP_K = 64

# The grouped top-K (top_k_scaled) shrinks a row to K groups of these
# sizes, widest first (a lane row of the float32 tile, then an eighth of
# one), each wherever the row still holds at least `ratio` times the
# K x size candidates it would keep; lax.top_k does the rest. From v5e
# timings of a head product and this sampler, 64 rows, K = 64 (ms a step:
# lax.top_k alone / the first rung alone / both; CHANGES.md, PR 36):
# 200,064: 4.24 / 2.07 / 1.87; 32,768: 0.89 / 0.69 / 0.50; 19,360: 0.65 /
# 0.57 / 0.37; 16,384 (2 x K x 128): 0.59 / 0.56 / 0.36, where the second
# rung alone reads 0.36 too; 4,096 (4 x K x 16): 0.22 against 0.17 by
# the second rung; 2,048 (2 x): 0.12 against 0.15, so that rung asks 4.
TOP_K_RUNGS = ((128, 2), (16, 4))  # (group size, ratio)


def sample_keys(base: jax.Array, seeds: jax.Array, positions: jax.Array) -> jax.Array:
    """Per-row sampling keys that depend ONLY on (seed, position).

    Because the key for the token at position q is a pure function of the
    request's seed and q — not of the decode step count or of which other
    requests share the batch — a request's sampled stream is reproducible
    across batch compositions and engine restarts.
    """
    return jax.vmap(lambda s, p: jax.random.fold_in(jax.random.fold_in(base, s), p))(
        seeds, positions
    )


def _top_k(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """lax.top_k behind barriers. XLA turns a top-k into its TopK call
    only while the k-wide slices of the sort are the sort's only users;
    a caller that slices the result again (its first column, say) has the
    two slices merged into one of another width, and the whole row is
    sorted instead: 18.7 ms a step against 4.2 for [64, 200064] on a
    v5e. One barrier an output: the SPMD partitioner aborts on a barrier
    that takes the top-k's tuple whole (a sharded vocabulary, TP)."""
    vals, idx = jax.lax.top_k(x, k)
    return jax.lax.optimization_barrier(vals), jax.lax.optimization_barrier(idx)


def top_k_groups(n: int, k: int) -> Tuple[int, ...]:
    """The group sizes top_k_scaled shrinks a row of n entries by: a
    function of (n, k) alone."""
    groups = []
    for group, ratio in TOP_K_RUNGS:
        if n >= ratio * k * group:
            groups.append(group)
            n = k * group
    return tuple(groups)


def top_k_scaled(
    logits: jax.Array,  # [B, V]
    scale: jax.Array,  # [B], > 0
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """``lax.top_k(logits / scale[:, None], k)``, values and indices alike
    (ties included), without a scaled copy of the vocabulary.

    The top k of a row lie in the k groups with the largest maxima: an
    element of the top k in any other group would have k groups ahead of
    it that each hold an element at least as large. So one max-pass to
    [B, V / group], a top-k over the group maxima, a gather of k x group
    candidates a row, and the same again on the candidates with a
    narrower group until lax.top_k is cheapest (top_k_groups). Division
    by a positive scale keeps the order, so the maxima are taken on the
    raw logits and divided after; the candidates are divided before
    their top-k, in ascending group order, so that ties the rounding of
    the division makes break towards the lowest index exactly as they do
    for lax.top_k on the scaled vocabulary (groups are contiguous, so
    the group order is the index order).
    """
    B, V = logits.shape
    groups = top_k_groups(V, k)
    if not groups:
        return _top_k(logits / scale[:, None], k)
    group = groups[0]
    G = -(-V // group)
    if G * group != V:
        # a padding entry is never among the top k: it ranks after every
        # real one, -inf ones included (highest indices)
        logits = jnp.pad(logits, ((0, 0), (0, G * group - V)), constant_values=-jnp.inf)
    # rows by eights: with a lane-row group this view is the float32
    # tiled layout itself, so neither the maxima nor the gather first
    # copy the vocabulary ([B, G, group] cost two copies of it on a v5e)
    sub = 8 if B % 8 == 0 else 1
    tiles = logits.reshape(B // sub, sub, G, group).transpose(0, 2, 1, 3)
    maxima = jnp.max(tiles, axis=-1).transpose(0, 2, 1).reshape(B, G)
    _, gids = _top_k(maxima / scale[:, None], k)
    gids = jnp.sort(gids, axis=-1)
    row = jnp.arange(B, dtype=jnp.int32)[:, None]
    cand = tiles[row // sub, gids, row % sub].reshape(B, k * group)
    vals, pos = top_k_scaled(cand, scale, k)
    # gids[b, pos // group] by comparison: a [B, k] gather of single
    # elements costs 34 us on a v5e, this nothing
    gid = jnp.sum(
        jnp.where((pos // group)[:, :, None] == jnp.arange(k), gids[:, None, :], 0),
        axis=-1,
    )
    return vals, gid * group + pos % group


def sample_tokens(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,  # single key, or per-row keys [B, ...] from sample_keys
    temperature: jax.Array,  # [B] or scalar
    top_p: jax.Array,  # [B] or scalar
    live: Optional[jax.Array] = None,  # [B] bool; None: every row counts
) -> jax.Array:
    """Sample next tokens. temperature <= 0 selects greedy argmax.

    Nucleus filtering keeps the smallest prefix of the descending-sorted
    distribution whose cumulative mass reaches top_p (the top token is
    always kept), restricted to the top NUCLEUS_TOP_K logits: mass beyond
    them is negligible for trained LLMs, the standard serving trade
    (HF/TRT-LLM combine top-k with top-p the same way). The mass is the
    true softmax's: the log-sum-exp runs over the whole vocabulary.

    The vocabulary is read for what the LIVE rows asked: the top-K and
    the log-sum-exp only if one of them has 0 < temperature and
    top_p < 1, the full-vocabulary draw only if one has top_p >= 1, and a
    batch of greedy rows does neither. A dead row (a slot never used
    holds temperature 1, top_p 1) gets some in-vocabulary token, which
    its caller discards.
    """
    B = logits.shape[0]
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    sampling = temperature > 0 if live is None else live & (temperature > 0)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)

    # key is either one key for the whole batch or per-row keys ([B, 2]
    # legacy / [B] typed) produced by sample_keys.
    per_row = key.ndim == jax.random.PRNGKey(0).ndim + 1

    def draw(k, lg):
        if per_row:
            return jax.vmap(lambda kk, row: jax.random.categorical(kk, row))(k, lg)
        return jax.random.categorical(k, lg, axis=-1)

    def nucleus(_):
        K = min(NUCLEUS_TOP_K, logits.shape[-1])
        top_vals, top_idx = top_k_scaled(logits, safe_t, K)  # descending
        # log-sum-exp of logits / temperature as jax.scipy's computes it,
        # its maximum read off the top-K, the scaled vocabulary never
        # written: the division fuses into the one reduction
        amax = top_vals[:, :1]
        amax = jnp.where(jnp.isfinite(amax), amax, 0.0)
        lse = amax + jnp.log(
            jnp.sum(jnp.exp(logits / safe_t[:, None] - amax), axis=-1, keepdims=True)
        )
        top_probs = jnp.exp(top_vals - lse)  # true softmax probs
        # Probability mass strictly before each slot; keep while < top_p
        # (the top token is always kept).
        mass_before = jnp.cumsum(top_probs, axis=-1) - top_probs
        keep = mass_before < top_p[:, None]
        masked = jnp.where(keep, top_vals, -jnp.inf)
        choice = draw(key, masked)  # [B] in K
        pick = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
        # a greedy row's scale is 1: its first top-K index is its argmax
        return pick.astype(jnp.int32), top_idx[:, 0].astype(jnp.int32)

    def no_nucleus(_):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy, greedy

    need_nucleus = jnp.any(sampling & (top_p < 1.0))
    pick, greedy = jax.lax.cond(need_nucleus, nucleus, no_nucleus, None)
    # Full-vocab draw serves rows with top_p >= 1 (pure temperature).
    need_full = jnp.any(sampling & (top_p >= 1.0))
    full = jax.lax.cond(
        need_full,
        lambda _: draw(key, logits / safe_t[:, None]).astype(jnp.int32),
        lambda _: greedy,
        None,
    )
    sampled = jnp.where(top_p < 1.0, pick, full)
    return jnp.where(temperature > 0, sampled, greedy)
