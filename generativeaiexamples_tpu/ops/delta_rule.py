"""Pallas TPU kernel: one decode step of the gated delta rule.

A delta-rule layer (KDA of models/glm5next.py, Gated DeltaNet of
models/gigachat35.py) keeps a ``[Dk, Dv]`` float32 state a value head
and slot, and a decode step advances every one of them:

    ``S <- Diag(exp g) S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q``

The step is bound by moving the state: 64 slots x 64 heads x 64 KB is
268 MB a layer, which the recurrence has to read once and write once.
Written in ``jax.numpy`` XLA runs it as two passes (a reduction over the
state cannot fuse with an update that needs the reduction's result), so
the state crosses HBM three times. Here a block of ``hb`` heads' states
is held in VMEM: one DMA in, both products and the rank-one update on
the VPU in float32, one DMA out, and the output aliases the input so no
second state-sized buffer exists.

**Orientation.** A state tile has ``Dk`` on sublanes and ``Dv`` on
lanes, so ``v``, ``u`` and ``o`` are rows as they arrive, while ``k``,
``q`` and the decay multiply ALONG ``Dk`` and are needed as columns
broadcast over lanes. Each ``[heads, Dk]`` operand block is transposed
once a grid step and a head's column is broadcast from it when used.

**One kernel for both families.** The decay arrives per channel,
``g [N, Hv, Dk]`` (a family with one decay a head broadcasts it: 2 MB
beside 536 MB of state). ``q`` and ``k`` arrive at the ``Hk`` key heads
the layer has; value head ``j`` reads key head ``j // (Hv / Hk)``, which
the block index map and a static index resolve, so no repeated copy of
``q`` and ``k`` is made. A dead row (``live`` 0, by scalar prefetch)
gets its state back as it came and a zero output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM one state block may take; in and out are each double-buffered, so
# the kernel holds four of them (PERF.md section 6, PR 39: timed on the chip)
_STATE_BLOCK_BYTES = 1024 * 1024
_SUBLANE = 8


def head_block(value_heads: int, key_heads: int, state_bytes: int) -> int:
    """Value heads a grid step advances: a divisor of ``value_heads``
    whose key heads are whole sublane tiles (or all of them), the largest
    whose states fit ``_STATE_BLOCK_BYTES``, else the smallest there is."""
    ratio = value_heads // key_heads
    fits = [hb for hb in range(ratio, value_heads + 1, ratio)
            if value_heads % hb == 0 and ((hb // ratio) % _SUBLANE == 0 or hb == value_heads)]
    under = [hb for hb in fits if hb * state_bytes <= _STATE_BLOCK_BYTES]
    return max(under) if under else min(fits)


def _column(t, j: int, width: int):
    """Column ``j`` of ``t [Dk, heads]`` broadcast over ``width`` lanes."""
    return jnp.broadcast_to(t[:, j:j + 1], (t.shape[0], width))


def _kernel(live_ref, s_ref, q_ref, k_ref, v_ref, beta_ref, g_ref, o_ref, s_out_ref, *, ratio: int):
    hb, _, Dv = s_ref.shape[1:]

    @pl.when(live_ref[pl.program_id(0)] != 0)
    def _advance():
        kT, qT = k_ref[0].T, q_ref[0].T  # [Dk, hb / ratio]
        aT = jnp.exp(g_ref[0]).T  # [Dk, hb]
        beta, v = beta_ref[0], v_ref[0]  # [hb, 1], [hb, Dv]
        for j in range(hb):
            S = _column(aT, j, Dv) * s_ref[0, j]
            k = _column(kT, j // ratio, Dv)
            u = beta[j:j + 1] * (v[j:j + 1] - jnp.sum(S * k, axis=0, keepdims=True))  # [1, Dv]
            S = S + k * u
            s_out_ref[0, j] = S
            o_ref[0, j:j + 1, :] = jnp.sum(S * _column(qT, j // ratio, Dv), axis=0, keepdims=True)

    @pl.when(live_ref[pl.program_id(0)] == 0)
    def _keep():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_step(S, q, k, v, beta, g, live, *, interpret: bool = False):
    """One token a row. S [R, Hv, Dk, Dv] float32, R >= N; q, k
    [N, Hk, Dk]; v [N, Hv, Dv]; beta [N, Hv]; g [N, Hv, Dk] (log decay,
    <= 0); live [N] bool. Returns (o [N, Hv, Dv] float32, S), the new
    state in the buffer of the old one where the caller donates it. The
    grid walks the first N rows of S: rows past them (a prefix store's,
    engine/prefix_cache.py) are never fetched and, the output being the
    input's buffer, stay as they are."""
    _, Hv, Dk, Dv = S.shape
    N = q.shape[0]
    Hk = q.shape[1]
    ratio = Hv // Hk
    hb = head_block(Hv, Hk, Dk * Dv * S.dtype.itemsize)
    kb = hb // ratio
    f32 = lambda x: x.astype(jnp.float32)

    def heads(width, last):
        return pl.BlockSpec((1, width, last), lambda n, h, live: (n, h, 0))

    state = pl.BlockSpec((1, hb, Dk, Dv), lambda n, h, live: (n, h, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_kernel, ratio=ratio),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, Hv // hb),
            in_specs=[state, heads(kb, Dk), heads(kb, Dk), heads(hb, Dv), heads(hb, 1), heads(hb, Dk)],
            out_specs=[heads(hb, Dv), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((N, Hv, Dv), jnp.float32), jax.ShapeDtypeStruct(S.shape, S.dtype)],
        input_output_aliases={1: 1},  # the state, counted with the scalar-prefetch operand
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="delta_rule_step",
    )(live.astype(jnp.int32), S, f32(q), f32(k), f32(v), f32(beta)[..., None], f32(g))
    return o, S
