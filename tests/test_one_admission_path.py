"""One admission path (ISSUE 48): every prompt of every registered
family enters through the chunk walk. An engine has no prefill program
beside its extends, a prompt of at most one chunk is chunk 0 of the walk
whatever waits beside it in the queue, and the one warm walk covers the
mix. Six engines, one a family's debug preset (llama's at 1k of capacity), float32
on the gather."""
import numpy as np
import pytest

from greedy_reference import reference_walk_greedy

PRESETS = ["debug-1k", "phi4flash-debug", "glm5next-debug", "gigachat35-debug", "afmoe-debug", "solaropen2-debug"]
CHUNK = 64


@pytest.fixture(scope="module", params=PRESETS)
def engine(request):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    eng = LLMEngine(EngineConfig(
        model_config_name=request.param, max_batch_size=3, max_seq_len=192, prefill_chunk=CHUNK, page_size=16,
        tensor_parallelism=1, decode_block=2, dtype="float32", prefix_cache_enable="off",
    ))
    eng.warmup()
    yield eng
    eng.shutdown()


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 250, size=n)]


def _serve(engine, prompts, n=4):
    """The prompts admitted from ONE backlog, oldest first: (tokens of
    each, the prefill span kinds that name each request)."""
    from generativeaiexamples_tpu.engine import dispatch_timeline
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    cursor = dispatch_timeline.spans_since(0)[1]
    with engine.hold_admissions():
        reqs = [engine.submit(p, SamplingParams(temperature=0.0, max_tokens=n)) for p in prompts]
    outs = []
    for req in reqs:
        outs.append([])
        while (tok := req.out_queue.get(timeout=600)) is not None:
            outs[-1].append(tok)
    spans = [s for s in dispatch_timeline.spans_since(cursor)[0] if s["kind"].startswith("prefill")]
    kinds = [sorted({s["kind"] for s in spans if req.rid in s.get("rids", ())}) for req in reqs]
    return outs, kinds


def test_no_engine_has_a_prefill_program(engine):
    """Four step programs (extend, finish, decode and, where the family
    has a verify walk, spec-verify) beside the small ones: warm-up
    compiled no program of kind ``prefill`` and the engine holds none."""
    snap = engine._compile_watch.snapshot()
    kinds = {k[len("compile_executables_"):] for k in snap if k.startswith("compile_executables_")}
    assert {"extend", "finish", "decode"} <= kinds | {"update_slots"}
    assert kinds <= {"extend", "finish", "decode", "spec_verify", "put_rows", "update_slots", "page_tables",
                     "prefix_state_copy"}
    assert not hasattr(engine, "_prefill_fn")
    assert snap["compile_executables_extend"] == len(engine.shapes.extend_signatures())


@pytest.mark.parametrize("n", [1, 9, CHUNK])
def test_a_short_prompt_is_chunk_zero_whatever_waits_beside_it(engine, n):
    """A prompt of at most one chunk: the reference walks' greedy tokens,
    through ``prefill_chunk`` dispatches alone, with the queue to itself
    and with a prompt of three chunks claimable beside it (on the parent
    the neighbour chose the short prompt's program)."""
    short, long = _prompt(n, seed=n), _prompt(2 * CHUNK + 5, seed=100 + n)
    want = reference_walk_greedy(engine, short, 4)
    (alone,), (alone_kinds,) = _serve(engine, [short])
    (beside, _), (beside_kinds, long_kinds) = _serve(engine, [short, long])
    assert alone == beside == want
    assert alone_kinds == beside_kinds == long_kinds == ["prefill_chunk"]


def test_a_mix_of_short_and_long_prompts_compiles_nothing(engine):
    """More requests than slots, lengths on both sides of a chunk and at
    its edges, admitted from one backlog: every one the reference walks'
    tokens and no executable added after warm-up."""
    before = engine._compile_watch.snapshot()
    prompts = [_prompt(n, seed=7 * n) for n in (3, CHUNK + 1, CHUNK, 2 * CHUNK + 17, 30, CHUNK - 1, 100)]
    outs, kinds = _serve(engine, prompts)
    for prompt, out in zip(prompts, outs):
        assert out == reference_walk_greedy(engine, prompt, 4)
    assert all(k == ["prefill_chunk"] for k in kinds)
    after = engine._compile_watch.snapshot()
    assert after["compile_hot_path_total"] == 0
    assert after["compile_executables"] == before["compile_executables"]
