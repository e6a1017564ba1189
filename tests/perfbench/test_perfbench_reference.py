"""The plain float32 reference against the engine at the tiny preset:
prefill logits and greedy decode through the cache, as the launcher
compares them on the chip at the published widths. The tolerance and
its reason are in the adapter, perfbench/arch/mistral.py; the comparison
itself (perfbench/reference.py) is shared by every adapter."""
import json
import os

import numpy as np
import pytest

from perfbench import reference
from perfbench.arch import mistral
from perfbench.tokenizer_file import CHAT_MARKERS, write_tokenizer

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "perfbench")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "debug-tiny.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def engine(cfg):
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    mistral.register(cfg)
    eng = LLMEngine(EngineConfig(
        model_config_name=cfg["name"], quantization="int8", kv_cache_dtype="int8",
        tensor_parallelism=1, max_batch_size=4, max_seq_len=256, prefill_chunk=64,
        page_size=16, decode_block=4, paged_kernel="interpret",
    ))
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def compared(cfg, engine):
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    # the third prompt is longer than prefill_chunk (64): it reaches the
    # extend program and is compared through the served path only
    prompts = reference.seeded_prompts([24, 40, 80], 250, seed=5)
    eng_logits = list(mistral.engine_prefill_logits(engine, prompts[:2], on_tpu=False)) + [None]
    greedy = SamplingParams(temperature=0.0, max_tokens=4)
    tokens = [list(engine.iter_ids(p, greedy, timeout=300)) for p in prompts]
    params = engine.params
    weights = lambda i: mistral.engine_layer_weights(params, cfg, i)  # noqa: E731
    head = mistral.unpack(params["lm_head"], cfg["hidden_size"], cfg["vocab_size"])
    args = (np.asarray(params["embed"], np.float32), weights,
            np.asarray(params["final_norm"], np.float32), head)
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full, args


def test_prefill_logits_and_decode_through_the_cache_agree_with_the_reference(cfg, compared):
    prompts, eng_logits, tokens, full, args = compared
    ref = mistral.forward(full, cfg, *args)
    out = reference.compare(prompts, list(eng_logits), tokens, ref, mistral.TOLERANCE)
    assert out["ok"], out
    assert max(out["prefill_rel_err"]) <= mistral.TOLERANCE
    assert len(out["prefill_rel_err"]) == 2  # the served-only prompt has no logits to compare
    assert out["decode_tokens_checked"] == 12 and out["decode_margin_max"] <= mistral.TOLERANCE
    assert all(len(t) == 4 for t in tokens)


def test_a_served_path_that_delivers_no_token_does_not_pass(cfg, compared):
    prompts, eng_logits, tokens, full, args = compared
    ref = mistral.forward(full, cfg, *args)
    assert not reference.compare(prompts, list(eng_logits), [[] for _ in tokens], ref, mistral.TOLERANCE)["ok"]
    assert not reference.compare(prompts, list(eng_logits), tokens[:2] + [[]], ref, mistral.TOLERANCE)["ok"]


@pytest.mark.parametrize("fault", ["dropped_layer", "wrong_rotary_base", "int4_weights"])
def test_the_tolerance_is_tight_enough_to_catch_a_different_model(cfg, compared, fault):
    prompts, eng_logits, tokens, full, (embed, weights, final_norm, head) = compared
    bad_cfg, bad_weights = dict(cfg), weights
    if fault == "dropped_layer":
        bad_cfg["num_hidden_layers"] = cfg["num_hidden_layers"] - 1
    elif fault == "wrong_rotary_base":
        bad_cfg["rope_theta"] = 10000.0
    else:  # keep only the 4 high bits of every int8 weight
        def bad_weights(i):
            w = weights(i)
            return {k: ((v[0] // 16 * 16).astype(np.int8), v[1]) if isinstance(v, tuple) else v
                    for k, v in w.items()}
    ref = mistral.forward(full, bad_cfg, embed, bad_weights, final_norm, head)
    out = reference.compare(prompts, list(eng_logits), tokens, ref, mistral.TOLERANCE)
    assert not out["ok"], out


def test_causal_mask_and_grouped_heads_by_hand():
    """One layer, identity-free check: the logits at position t do not
    depend on tokens after t, and repeating KV heads equals the grouped form."""
    rng = np.random.default_rng(0)
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "hidden_size": 16,
           "intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 1e6, "num_hidden_layers": 1}
    w = {"attn_norm": np.ones(16, np.float32), "mlp_norm": np.ones(16, np.float32),
         "wq": rng.normal(size=(16, 32)).astype(np.float32) * 0.2,
         "wk": rng.normal(size=(16, 16)).astype(np.float32) * 0.2,
         "wv": rng.normal(size=(16, 16)).astype(np.float32) * 0.2,
         "wo": rng.normal(size=(32, 16)).astype(np.float32) * 0.2,
         "w_gate": rng.normal(size=(16, 32)).astype(np.float32) * 0.2,
         "w_up": rng.normal(size=(16, 32)).astype(np.float32) * 0.2,
         "w_down": rng.normal(size=(32, 16)).astype(np.float32) * 0.2}
    embed = rng.normal(size=(10, 16)).astype(np.float32)
    head = rng.normal(size=(16, 10)).astype(np.float32)
    a, b = mistral.forward([[1, 2, 3, 4], [1, 2, 3, 9]], cfg, embed, lambda i: w, np.ones(16, np.float32), head)
    np.testing.assert_allclose(a[:3], b[:3], rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(a[3] - b[3])) > 1e-3


def test_tokenizer_file_gives_one_visible_frame_per_generated_id(tmp_path):
    from generativeaiexamples_tpu.engine.tokenizer import HFTokenizer

    path = str(tmp_path / "tokenizer.json")
    write_tokenizer(path, 32768)
    tok = HFTokenizer(path)
    assert tok.vocab_size == 32768 and tok.supports_split_render
    assert len(set(tok.stop_ids())) == 2
    text = "Context: cooling loop\n\nQuestion: what is it?\n"
    assert len(tok.encode(text)) == len(text)  # one character, one token
    assert tok.decode(tok.encode(text)) == text
    for i in (0, 94, 97, 98, 102, 103, 5000, 32767):
        assert tok.decode([i]) != ""
    ids = tok.render_chat([("system", "s"), ("user", "u")])
    assert ids.count(tok.token_to_id(CHAT_MARKERS[4]) if hasattr(tok, "token_to_id") else tok.eot_id) == 2
    with pytest.raises(ValueError):
        write_tokenizer(path, 50)


@pytest.mark.parametrize("tp,kind", [(1, "column"), (4, "column"), (1, "row"), (4, "row")])
def test_unpack_undoes_the_engines_kernel_layout(tp, kind):
    """The program pads int8 packs for its kernels, per shard under
    tensor parallelism; the reference must read back the plain matrix."""
    from generativeaiexamples_tpu.ops import quant

    rng = np.random.default_rng(tp)
    k, f = 256, 1024  # per shard 256 columns (padded to 512) or 64 rows (padded to 128)
    plain = rng.integers(-127, 128, (k, f)).astype(np.int8)
    scale = rng.uniform(0.5, 1.5, (1, f)).astype(np.float32)
    pack = {"q": np.asarray(quant._layout(plain, tp, kind)), "scale": scale}
    q, s = mistral.unpack(pack, k, f, tp=tp, kind=kind)
    np.testing.assert_array_equal(q, plain)
    np.testing.assert_array_equal(s, scale)
    q2, s2 = mistral.unpack(pack, k, 100, lo=50, tp=tp, kind=kind)
    np.testing.assert_array_equal(q2, plain[:, 50:150])
