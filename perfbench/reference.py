"""What decides ``correct`` for a served model, whatever its architecture.

The engine's prefill logits and its greedy decode through the cache are
held against a plain float32 forward pass on the engine's OWN weights.
That forward pass belongs to the configuration's adapter
(``perfbench/arch/``: ``reference_logits``, with its ``TOLERANCE`` and
the readings the tolerance was set from); this module keeps what every
adapter shares: the seeded prompts, the comparison, and the
dequantisation of an ``(int8, scale)`` pair.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np


def dense(w):
    """A weight as float32: given either as an array or as an int8 matrix
    with its float32 scale per output channel, ``(q, scale)``."""
    import jax.numpy as jnp

    if isinstance(w, (tuple, list)):
        q, scale = w
        return q.astype(jnp.float32) * scale.astype(jnp.float32)
    return w.astype(jnp.float32)


def seeded_prompts(lengths: Sequence[int], vocab: int, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab, size=n)] for n in lengths]


def compare(prompts: Sequence[Sequence[int]], engine_logits: Sequence[np.ndarray],
            engine_tokens: Sequence[Sequence[int]], ref_logits: Sequence[np.ndarray],
            tolerance: float) -> Dict[str, Any]:
    """``ref_logits[i]`` covers prompt i followed by the engine's tokens.
    (i) last-prompt-position logits: max|diff|/max|ref| (a prompt whose
    ``engine_logits`` entry is None is served only and skips this);
    (ii) every engine token's reference logit lies within the same
    margin of the reference's maximum at its position (greedy decode
    through the cache chose a token the reference also ranks at the top,
    up to rounding). Every prompt must have produced a token: a served
    path that delivers nothing does not pass. ``tolerance`` is the
    adapter's."""
    prefill_err, decode_margin = [], []
    for prompt, eng, toks, ref in zip(prompts, engine_logits, engine_tokens, ref_logits):
        T = len(prompt)
        if eng is not None:
            last = ref[T - 1]
            scale = max(float(np.max(np.abs(last))), 1e-6)
            eng = np.asarray(eng, np.float32)[: last.shape[0]]
            prefill_err.append(float(np.max(np.abs(eng - last)) / scale) if np.all(np.isfinite(eng)) else float("inf"))
        for j, tok in enumerate(toks):
            row = ref[T - 1 + j]
            s = max(float(np.max(np.abs(row))), 1e-6)
            decode_margin.append(float((np.max(row) - row[tok]) / s))
    ok = (
        bool(prefill_err) and max(prefill_err) <= tolerance
        and all(len(t) > 0 for t in engine_tokens)
        and bool(decode_margin) and max(decode_margin) <= tolerance
    )
    return {
        "ok": bool(ok), "tolerance": tolerance,
        "prefill_rel_err": prefill_err,
        "decode_margin_max": max(decode_margin) if decode_margin else None,
        "decode_tokens_checked": len(decode_margin),
    }
