"""The sampler reads the vocabulary for what the live rows asked
(models/sampling.py): tokens equal to the plain reference kept below,
bit for bit, at every vocabulary size the cells have and around the
shape rule; the grouped top-K equal to lax.top_k, ties included; dead
rows do not switch the full-vocabulary draw on; and a tiny engine
reports how often the draw engages."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import sampling
from generativeaiexamples_tpu.models.sampling import (
    NUCLEUS_TOP_K,
    sample_keys,
    sample_tokens,
    top_k_scaled,
)


# --------------------------------------------------------------------------- #
# the plain reference: sample_tokens as it stood before PR 36, verbatim


def reference_sample_tokens(logits, key, temperature, top_p):
    temperature = jnp.asarray(temperature, jnp.float32)
    top_p = jnp.asarray(top_p, jnp.float32)
    if temperature.ndim == 0:
        temperature = jnp.broadcast_to(temperature, logits.shape[:1])
    if top_p.ndim == 0:
        top_p = jnp.broadcast_to(top_p, logits.shape[:1])

    greedy = jnp.argmax(logits, axis=-1)

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]

    per_row = key.ndim == jax.random.PRNGKey(0).ndim + 1

    def draw(k, lg):
        if per_row:
            return jax.vmap(lambda kk, row: jax.random.categorical(kk, row))(k, lg)
        return jax.random.categorical(k, lg, axis=-1)

    def sample_path(scaled):
        full = draw(key, scaled)

        def nucleus(operand):
            scaled, full = operand
            K = min(NUCLEUS_TOP_K, scaled.shape[-1])
            top_vals, top_idx = jax.lax.top_k(scaled, K)  # descending
            lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
            top_probs = jnp.exp(top_vals - lse)  # true softmax probs
            mass_before = jnp.cumsum(top_probs, axis=-1) - top_probs
            keep = mass_before < top_p[:, None]
            masked = jnp.where(keep, top_vals, -jnp.inf)
            choice = draw(key, masked)  # [B] in K
            pick = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
            return jnp.where(top_p < 1.0, pick, full)

        need_nucleus = jnp.any((temperature > 0) & (top_p < 1.0))
        return jax.lax.cond(need_nucleus, nucleus, lambda op: op[1], (scaled, full))

    any_sampling = jnp.any(temperature > 0)
    sampled = jax.lax.cond(any_sampling, sample_path, lambda s: greedy, scaled)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# tokens equal the reference's

B = 8
# below every rung of the shape rule (40, 130), one rung (8193, a padded
# last group), and the three cells' vocabularies: two rungs (19,360 pads
# its last group; 32,768 and 200,064 are whole groups)
VOCABS = (40, 130, 8193, 19360, 32768, 200064)
MIXES = {
    "nucleus_0.1_0.1": (np.full(B, 0.1), np.full(B, 0.1)),
    "nucleus_1_0.5": (np.full(B, 1.0), np.full(B, 0.5)),
    "full_1_1": (np.full(B, 1.0), np.full(B, 1.0)),
    "greedy_0_1": (np.full(B, 0.0), np.full(B, 1.0)),
    "mixed": (
        np.array([0.0, 0.1, 1.0, 0.7, 0.0, 1.3, 0.1, 1.0]),
        np.array([1.0, 0.1, 1.0, 0.5, 0.3, 1.0, 0.9, 0.95]),
    ),
}
_REFERENCE = jax.jit(reference_sample_tokens)
_SAMPLE = jax.jit(sample_tokens)


@functools.lru_cache(maxsize=None)
def _logits(V):
    return jnp.asarray(np.random.default_rng(V).standard_normal((B, V)).astype(np.float32) * 3.0)


def _keys(per_row):
    key = jax.random.PRNGKey(36)
    if per_row:
        return sample_keys(key, jnp.arange(B, dtype=jnp.int32) * 7919, jnp.arange(B, dtype=jnp.int32) + 5)
    return key


@pytest.mark.parametrize("keys", ["one_key", "row_keys"])
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("V", VOCABS)
def test_tokens_equal_the_reference(V, mix, keys):
    t, p = (jnp.asarray(a, jnp.float32) for a in MIXES[mix])
    key = _keys(keys == "row_keys")
    want = np.asarray(_REFERENCE(_logits(V), key, t, p))
    got = np.asarray(_SAMPLE(_logits(V), key, t, p))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    # and with every row declared live
    np.testing.assert_array_equal(np.asarray(_SAMPLE(_logits(V), key, t, p, jnp.ones(B, bool))), want)


def test_scalar_parameters_broadcast():
    lg = _logits(130)
    for t, p in ((0.0, 1.0), (0.8, 0.6), (1.0, 1.0)):
        np.testing.assert_array_equal(
            np.asarray(sample_tokens(lg, _keys(False), t, p)),
            np.asarray(reference_sample_tokens(lg, _keys(False), t, p)),
        )


# --------------------------------------------------------------------------- #
# the grouped top-K equals lax.top_k, values and indices


def _tie_rows(kind, V, rng):
    K = min(NUCLEUS_TOP_K, V)
    if kind == "random":
        return rng.standard_normal((B, V)).astype(np.float32) * 3.0
    if kind == "ties_everywhere":  # five distinct values: every cut falls inside a run
        return rng.integers(0, 5, (B, V)).astype(np.float32)
    if kind == "ties_at_the_cut":  # K - 3 clear winners, then a plateau the cut divides
        x = rng.standard_normal((B, V)).astype(np.float32)
        for b in range(B):
            order = rng.permutation(V)
            x[b, order[: K - 3]] = 50.0 + np.arange(K - 3, dtype=np.float32)
            x[b, order[K - 3 : K - 3 + min(V - K + 3, 200)]] = 20.0
        return x
    if kind == "ties_the_division_makes":  # neighbouring floats that 1 / 0.1 rounds together
        x = rng.standard_normal((B, V)).astype(np.float32)
        base = np.float32(1.7)
        steps = np.nextafter(base, np.float32(2), dtype=np.float32) - base
        for b in range(B):
            where = rng.permutation(V)[: min(V, 3 * K)]
            x[b, where] = base + steps * rng.integers(0, 6, where.size).astype(np.float32)
        return x
    if kind == "minus_inf":  # a masked vocabulary: fewer finite entries than K
        x = np.full((B, V), -np.inf, np.float32)
        x[:, :: max(1, V // 20)] = rng.standard_normal(x[:, :: max(1, V // 20)].shape).astype(np.float32)
        return x
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["random", "ties_everywhere", "ties_at_the_cut",
                                  "ties_the_division_makes", "minus_inf"])
@pytest.mark.parametrize("V", VOCABS)
def test_top_k_scaled_equals_lax_top_k(V, kind):
    K = min(NUCLEUS_TOP_K, V)
    x = jnp.asarray(_tie_rows(kind, V, np.random.default_rng(V + len(kind))))
    for scale in (1.0, 0.1, 0.7):
        s = jnp.full(B, scale, jnp.float32)
        want_v, want_i = jax.lax.top_k(x / s[:, None], K)
        got_v, got_i = jax.jit(top_k_scaled, static_argnums=2)(x, s, K)
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("rows", [1, 3, 12, 16])
def test_top_k_scaled_at_any_row_count(rows):
    """Rows go by eights where they can (the float32 tile) and one by one
    where they cannot (a prefill wave of 1-4 rows, B x (K + 1) verify rows)."""
    x = jnp.asarray(np.random.default_rng(rows).integers(0, 50, (rows, 19360)).astype(np.float32))
    s = jnp.linspace(0.1, 1.3, rows, dtype=jnp.float32)
    want_v, want_i = jax.lax.top_k(x / s[:, None], NUCLEUS_TOP_K)
    got_v, got_i = top_k_scaled(x, s, NUCLEUS_TOP_K)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_the_division_does_make_ties():
    """The case above is not vacuous: at 0.1 distinct neighbouring floats
    meet, which is why the candidates are divided BEFORE their top-k."""
    x = _tie_rows("ties_the_division_makes", 8193, np.random.default_rng(1))
    row = x[0][np.isclose(x[0], 1.7, atol=1e-5)]
    assert len(np.unique(row / np.float32(0.1))) < len(np.unique(row))


@pytest.mark.parametrize("V,groups", [(40, ()), (130, ()), (4095, ()), (4096, (16,)), (8193, (16,)),
                                      (16383, (16,)), (16384, (128, 16)), (19360, (128, 16)),
                                      (32768, (128, 16)), (200064, (128, 16))])
def test_the_shape_rule_reads_v_and_k_alone(V, groups):
    """A row is shrunk to K groups of a rung's size where it holds at
    least the rung's ratio times the K x size candidates kept: the trace
    then holds one top-k and one gather a rung, and lax.top_k last."""
    K = min(NUCLEUS_TOP_K, V)
    assert sampling.top_k_groups(V, K) == groups
    jaxpr = jax.make_jaxpr(lambda x, s: top_k_scaled(x, s, K))(
        jax.ShapeDtypeStruct((B, V), jnp.float32), jax.ShapeDtypeStruct((B,), jnp.float32))
    names = _primitives(jaxpr.jaxpr, [])
    assert names.count("top_k") == len(groups) + 1
    assert names.count("gather") == len(groups)


# --------------------------------------------------------------------------- #
# what the trace holds: conditions over live rows, no top-k for greedy rows


def _primitives(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, acc)
    return acc


def _cond_predicates(t, p, live, V=300):
    """(top-k and log-sum-exp run, full-vocabulary draw runs): the two
    cond predicates of the traced sampler, evaluated."""
    args = (_logits(V), _keys(True), jnp.asarray(t, jnp.float32), jnp.asarray(p, jnp.float32))
    if live is not None:
        args += (jnp.asarray(live),)
    closed = jax.make_jaxpr(sample_tokens)(*args)
    conds = [e for e in closed.jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2
    holds_top_k = ["top_k" in _primitives(e.params["branches"][1].jaxpr, []) for e in conds]
    assert holds_top_k == [True, False]  # the nucleus cond, then the full draw's
    preds = jax.core.eval_jaxpr(closed.jaxpr.replace(outvars=[e.invars[0] for e in conds]), closed.consts, *args)
    return tuple(bool(x) for x in preds)


NUC, FULL, GREEDY = (0.1, 0.1), (1.0, 1.0), (0.0, 1.0)
LIVE_CASES = {
    # rows (temperature, top_p), live mask -> (nucleus runs, full draw runs)
    "nucleus_rows_beside_dead_unused_slots": ([NUC] * 4 + [FULL] * 4, [1] * 4 + [0] * 4, (True, False)),
    "the_same_without_a_mask": ([NUC] * 4 + [FULL] * 4, None, (True, True)),
    "one_live_full_row": ([NUC] * 3 + [FULL] + [FULL] * 4, [1] * 4 + [0] * 4, (True, True)),
    "greedy_rows_beside_dead_sampling_slots": ([GREEDY] * 4 + [NUC, NUC, FULL, FULL], [1] * 4 + [0] * 4, (False, False)),
    "only_full_rows_live": ([FULL] * 2 + [NUC] * 6, [1] * 2 + [0] * 6, (False, True)),
    "nothing_live": ([NUC] * 4 + [FULL] * 4, [0] * 8, (False, False)),
    "greedy_with_top_p_below_one": ([(0.0, 0.3)] * 8, [1] * 8, (False, False)),
}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_the_conditions_are_over_live_rows(case):
    rows, live, want = LIVE_CASES[case]
    t, p = zip(*rows)
    assert _cond_predicates(t, p, None if live is None else np.asarray(live, bool)) == want


@pytest.mark.parametrize("V", [300, 19360])
def test_dead_rows_change_no_live_token(V):
    """Live rows read the tokens they would read alone, whatever the dead
    slots hold, and a dead row still gets an id of the vocabulary."""
    t = jnp.asarray([0.1, 0.0, 0.9, 1.0, 1.0, 1.0, 0.1, 0.0], jnp.float32)
    p = jnp.asarray([0.1, 1.0, 0.8, 1.0, 1.0, 1.0, 0.1, 1.0], jnp.float32)
    live = np.array([1, 1, 1, 0, 0, 0, 1, 1], bool)
    want = np.asarray(_REFERENCE(_logits(V), _keys(True), t, p))
    got = np.asarray(_SAMPLE(_logits(V), _keys(True), t, p, jnp.asarray(live)))
    np.testing.assert_array_equal(got[live], want[live])
    assert ((got >= 0) & (got < V)).all()


def test_a_greedy_batch_traces_no_top_k_outside_a_cond_branch():
    closed = jax.make_jaxpr(sample_tokens)(_logits(19360), _keys(True), jnp.zeros(B), jnp.ones(B))
    top_level = [e.primitive.name for e in closed.jaxpr.eqns]
    assert not {"top_k", "sort", "random_bits", "exp", "argmax"} & set(top_level)
    assert "top_k" in _primitives(closed.jaxpr, [])  # it is there, inside its branch
    # and the greedy branch of each cond holds neither a top-k nor a draw
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "cond":
            off = _primitives(eqn.params["branches"][0].jaxpr, [])
            assert not {"top_k", "random_bits", "exp"} & set(off)


def test_a_top_k_stays_a_top_k_call_when_its_result_is_sliced():
    """XLA rewrites sort + slice into its TopK call only while the k-wide
    slices are the sort's only users; sample_tokens slices the result
    again (the maximum, the greedy id). Without the barrier in _top_k
    the whole vocabulary is sorted (18.7 ms a step at 200,064 on a v5e
    against 2.2): the compiled program must hold no wide sort."""
    import re

    for V in (8193, 19360):
        t = jnp.full(B, 0.1)
        text = _SAMPLE.lower(_logits(V), _keys(True), t, t).compile().as_text()
        widths = [int(w) for w in re.findall(r"= \(?[a-z0-9]+\[\d+,(\d+)\]\S* (?:[^=]*)sort\(", text)]
        assert all(w <= NUCLEUS_TOP_K for w in widths), widths
        assert "TopK" in text


@pytest.mark.parametrize("V", [512, 8192, 19360])
def test_a_vocabulary_sharded_over_a_mesh_still_lowers(V):
    """TP serving hands the sampler logits whose vocabulary axis is
    sharded; every rung count must partition and give the same tokens
    (a barrier over lax.top_k's outputs as ONE tuple aborts the SPMD
    partitioner: _top_k takes them one by one)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    t = jnp.asarray([0.5, 0.1, 0.0, 1.0, 0.9, 0.1, 1.0, 0.0], jnp.float32)
    p = jnp.asarray([0.5, 0.1, 1.0, 1.0, 0.95, 0.9, 1.0, 0.3], jnp.float32)
    want = np.asarray(_SAMPLE(_logits(V), _keys(True), t, p))
    sharded = jax.device_put(_logits(V), NamedSharding(mesh, P(None, "model")))
    with mesh:
        got = np.asarray(jax.jit(sample_tokens)(sharded, _keys(True), t, p))
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------- #
# the engine: the counter that says how often the draw engages, and the
# parent's stream


@pytest.fixture(scope="module")
def engines():
    """A tiny engine, and its twin built over the reference sampler."""
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    def build():
        return LLMEngine(EngineConfig(
            model_config_name="debug", max_batch_size=4, max_seq_len=128, prefill_chunk=16,
            decode_block=2, dtype="float32", tensor_parallelism=1,
            page_size=8, decode_runahead=1, watchdog_stall_s=0.0,
        ))

    eng = build()
    real = sampling.sample_tokens
    sampling.sample_tokens = lambda lg, key, t, p, live=None: reference_sample_tokens(lg, key, t, p)
    try:
        twin = build()  # _build_steps binds the sampler it finds at build time
    finally:
        sampling.sample_tokens = real
    yield eng, twin
    eng.shutdown()
    twin.shutdown()


def _full_dispatches():
    from generativeaiexamples_tpu.engine import llm_engine

    return llm_engine._M_SAMPLER_FULL.value


STREAMS = {"nucleus": (0.8, 0.9), "sharp_nucleus": (0.1, 0.1), "full": (1.0, 1.0), "greedy": (0.0, 1.0)}


@pytest.mark.parametrize("kind", sorted(STREAMS))
def test_a_stream_is_the_one_the_reference_sampler_gives(engines, kind):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    eng, twin = engines
    t, p = STREAMS[kind]
    prompt = [3, 5, 8, 13, 21, 34, len(kind)]
    params = SamplingParams(temperature=t, top_p=p, max_tokens=24, seed=4321)
    got = list(eng.iter_ids(prompt, params, timeout=300))
    assert len(got) >= 2 and got == list(twin.iter_ids(prompt, params, timeout=300))


def test_a_half_empty_engine_reports_when_the_full_draw_engages(engines):
    """Two of four slots decode nucleus traffic; the other two were never
    used and hold (1.0, 1.0): no decode span counts a full row and the
    counter stands. One live top_p = 1 request raises both."""
    from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    eng, _ = engines
    dtl.configure(enable=True)

    def run(*param_sets):
        since, before = dtl.cursor(), _full_dispatches()
        reqs = [eng.submit([7, 11, 13 + i], ps) for i, ps in enumerate(param_sets)]
        for r in reqs:
            while r.out_queue.get(timeout=300) is not None:
                pass
        spans = [s for s in dtl.spans_since(since)[0] if s["kind"] == "decode"]
        assert spans and all("sampler_full_rows" in s for s in spans)
        return [s["sampler_full_rows"] for s in spans], _full_dispatches() - before

    nucleus = SamplingParams(temperature=0.1, top_p=0.1, max_tokens=12, seed=1)
    rows, grew = run(nucleus, nucleus)
    assert max(rows) == 0 and grew == 0
    rows, grew = run(nucleus, SamplingParams(temperature=0.7, top_p=1.0, max_tokens=12, seed=2))
    assert max(rows) == 1 and grew == sum(1 for r in rows if r)
    # a greedy request with top_p = 1 asks for no draw
    rows, grew = run(SamplingParams(temperature=0.0, top_p=1.0, max_tokens=6))
    assert max(rows) == 0 and grew == 0
