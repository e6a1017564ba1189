"""The Trinity-Mini adapter (perfbench/arch/afmoe.py): its plain float32
reference against the engine at a tiny size that keeps the three kinds
of layer served and wraps the ring inside a prompt, the control one
precision down, the injected faults that must each fail ``TOLERANCE``,
its byte and operation counts against hand values, its readers, its
configuration file and its manifest entries (found by name: entries a
later PR appends are none of this file's business)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import afmoe as adapter
from tests.perfbench.manifest_entries import assert_cell_holds, entries_of
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "trinity-mini-26b-a3b-bf16.json")
CELL = "doc_reason_trinitymini"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# one sliding dense, one sliding expert and one full expert layer at widths a CPU test can walk
TINY = dict(
    CFG, name="afmoe-tiny-test", vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    layer_types=["sliding_attention", "sliding_attention", "full_attention"], num_dense_layers=1,
    layers_served=[0, 1, 2], layers=3, num_experts=8, num_experts_per_tok=2, num_experts_held=8,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16, sliding_window=8,
    engine=dict(CFG["engine"], max_seq_len=256), reference=dict(CFG["reference"], decode_tokens=5),
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    adapter.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=2, max_seq_len=256,
        prefill_chunk=64, page_size=16, decode_block=4, prefix_cache_enable="off",
        dtype="float32", paged_kernel="off",
    ))
    # a selection bias wide enough that the top-k of (score + bias) and of the score differ at this size
    eng.params = dict(eng.params, layers=[
        dict(lp, e_bias=lp["e_bias"] * 30.0) if "e_bias" in lp else lp for lp in eng.params["layers"]])
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def compared(engine):
    """As the launcher compares on the chip: last-position logits of the
    served walks (one chunk; 39 tokens and one decode step over a wrapped
    ring; 150 tokens in three extend chunks, each wider than the window of
    8), greedy tokens through the engine, and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = reference.seeded_prompts([9, 40, 150], 250, seed=11)
    eng_logits = adapter.engine_prefill_logits(engine, prompts, on_tpu=False)
    greedy = SamplingParams(temperature=0.0, max_tokens=5)
    tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full, adapter.reference_logits(engine, TINY, full)


def test_engine_agrees_with_the_reference_through_prefill_extend_and_decode(compared):
    prompts, eng_logits, tokens, _, ref = compared
    out = reference.compare(prompts, list(eng_logits), tokens, ref, adapter.TOLERANCE)
    assert out["ok"], out
    # float32 walks against a float32 reference: rounding alone
    assert len(out["prefill_rel_err"]) == 3 and max(out["prefill_rel_err"]) < 1e-4
    assert out["decode_tokens_checked"] == 15 and out["decode_margin_max"] < 1e-4
    # only the compared positions carry logits: the head is not computed for the rest
    assert not ref[2][:140].any() and ref[2][149].any()


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    calls = []
    real = adapter._served_logits
    try:
        adapter._served_logits = lambda eng, prompts: calls.append(1) or real(eng, prompts)
        rows = adapter.engine_prefill_logits(engine, compared[0][:2], on_tpu=False)
        assert not calls and all(isinstance(r, adapter.Deferred) for r in rows)
        first = np.asarray(rows[0], np.float32)
        np.asarray(rows[1])
        assert calls == [1] and first.shape == (256,)
        np.testing.assert_allclose(first, np.asarray(compared[1][0]), rtol=1e-5, atol=1e-5)
    finally:
        adapter._served_logits = real
        adapter._PENDING.clear()


def _model():
    from generativeaiexamples_tpu.models import afmoe as m

    return m


def _window(delta):
    def fault(eng, mp):
        cfg = eng.model_config
        mp.setattr(eng, "model_config", dataclasses.replace(cfg, sliding_window=cfg.sliding_window + delta))
    return fault


def _rope_on_the_full_layer(eng, mp):
    m = _model()
    real = m._project
    mp.setattr(m, "_project", lambda u, positions, lp, cfg, mixer, dtype: real(u, positions, lp, cfg, "window", dtype))


def _no_rope_on_a_window_layer(eng, mp):
    mp.setattr(_model(), "rope", lambda x, positions, theta: x)


def _ring_read_after_the_chunk_wrote_it(eng, mp):
    """A chunk that writes its keys into the ring BEFORE it reads it: the
    rows it wrapped over are gone, its own are seen twice."""
    real = eng._family.extend_paged

    def twice(params, cfg, caches, *rest, **kw):
        _, written = real(params, cfg, caches, *rest, **kw)
        return real(params, cfg, dict(caches, win=written["win"]), *rest, **kw)

    mp.setattr(eng, "_family", dataclasses.replace(eng._family, extend_paged=twice))


def _no_output_gate(eng, mp):
    import jax.numpy as jnp

    m = _model()
    real = m._attn_output
    mp.setattr(m, "_attn_output", lambda o, gate, lp: real(o, jnp.ones_like(gate), lp))


def _no_head_norms(eng, mp):
    """q and k go to the scores as projected: no RMSNorm per head."""
    m = _model()
    real = m.rms_norm
    mp.setattr(m, "rms_norm", lambda x, w, eps, out_dtype=None: (
        x.astype(out_dtype) if w.shape[-1] == eng.model_config.head_dim else real(x, w, eps, out_dtype)))


def _no_route_scale(eng, mp):
    mp.setattr(eng, "model_config", dataclasses.replace(eng.model_config, routed_scaling_factor=1.0))


def _route(normalise: bool, bias: bool):
    def fault(eng, mp):
        import jax
        import jax.numpy as jnp
        from generativeaiexamples_tpu.models import glm5next

        def route(x, lp, cfg):
            s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), lp["router"], precision=jax.lax.Precision.HIGHEST))
            _, top = jax.lax.top_k(s + lp["e_bias"] if bias else s, cfg.num_experts_per_tok)
            chosen = jnp.take_along_axis(s, top, axis=-1)
            gates = cfg.routed_scaling_factor * (chosen / jnp.sum(chosen, axis=-1, keepdims=True) if normalise else chosen)
            return top.astype(jnp.int32), gates

        mp.setattr(glm5next, "route", route)
    return fault


def _no_mup_multiplier(eng, mp):
    mp.setattr(eng, "model_config", dataclasses.replace(eng.model_config, mup_enabled=False))


def _no_post_norms(eng, mp):
    """Two of the four norms dropped: the sublayers' outputs join the residual as they come."""
    m = _model()
    mp.setattr(m, "sublayer", lambda x, lp, sub, cfg, fn: x + fn(m.rms_norm(x, lp[f"n_{sub}_in"], cfg.norm_eps, x.dtype)))


FAULTS = {
    "a_window_of_2049_keys": _window(+1),
    "a_window_of_2047_keys": _window(-1),
    "rope_on_the_full_layer": _rope_on_the_full_layer,
    "no_rope_on_a_window_layer": _no_rope_on_a_window_layer,
    "the_ring_read_after_the_chunk_wrote_it": _ring_read_after_the_chunk_wrote_it,
    "a_missing_output_gate": _no_output_gate,
    "missing_head_norms": _no_head_norms,
    "a_missing_route_scale": _no_route_scale,
    "gates_not_normalised": _route(normalise=False, bias=True),
    "top_k_over_the_scores_without_the_bias": _route(normalise=True, bias=False),
    "a_missing_mup_multiplier": _no_mup_multiplier,
    "missing_post_norms": _no_post_norms,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_injected_fault_fails_the_logits_comparison(engine, compared, fault, monkeypatch):
    """Each fault in the SERVED walks takes a compared logit past ``TOLERANCE``."""
    prompts, _, tokens, _, ref = compared
    FAULTS[fault](engine, monkeypatch)
    faulty = adapter.engine_prefill_logits(engine, prompts, on_tpu=False)
    out = reference.compare(prompts, faulty, tokens, ref, adapter.TOLERANCE)
    adapter._PENDING.clear()
    assert not out["ok"] and max(out["prefill_rel_err"]) > adapter.TOLERANCE, (fault, out)


def test_the_control_one_precision_down_fails(engine, compared):
    """All-bfloat16 products, sums, norms and residual: past ``TOLERANCE``."""
    prompts, _, _, full, ref = compared
    low = adapter.reference_logits(engine, TINY, full, precision="bfloat16")
    err = [float(np.max(np.abs(a[len(p) - 1] - b[len(p) - 1])) / np.max(np.abs(b[len(p) - 1])))
           for a, b, p in zip(low, ref, prompts)]
    assert max(err) > adapter.TOLERANCE, err


# --------------------------------------------------------------------------- #
# The adapter's contract, its bytes and its readers (no jax)


def test_adapter_contract_and_no_jax_at_import():
    import subprocess
    import sys

    assert arch.load(CFG, [os.path.join(ROOT, p) for p in ("perfbench", "tests/perfbench")]) is adapter
    code = "import sys; import perfbench.arch.afmoe; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    with open(adapter.__file__, encoding="utf-8") as fh:
        assert "models.afmoe import AfmoeConfig" in fh.read()  # the registration alone touches the program's model


def test_layers_served_and_the_model_configuration():
    assert adapter.layer_kinds(CFG) == [(True, "dense"), (True, "sparse"), (True, "sparse"), (True, "sparse"), (False, "sparse")]
    from generativeaiexamples_tpu.models import afmoe as m

    assert adapter.model_config(CFG) == m.PRESETS["trinity-mini"]
    assert adapter.model_config(TINY) == dataclasses.replace(
        m.PRESETS["afmoe-debug"], max_seq_len=256, layers_served=(0, 1, 2))
    assert adapter.expert_keys(CFG) == {"swiglu_limit": float("inf"), "num_experts_per_tok": 8,
                                        "routed_scaling_factor": 2.826, "experts_first": 0, "n_routed_experts_held": 128}


def test_byte_and_operation_counts_against_hand_values():
    D, V = 2048, 200192
    assert adapter.expert_bytes(CFG) == 3 * D * 1024 * 2 == 12_582_912
    attn = D * 9216 + 4096 * D
    assert attn + 2 * 128 == 27_263_232  # ISSUE 42: attention parameters a layer, the two head norms among them
    fixed_bf16 = 5 * attn + 3 * D * 6144 + 4 * 3 * D * 1024 + D * V
    fixed_f32 = 5 * (4 * D + 2 * 128) + D + 4 * (D * 128 + 128)
    assert adapter.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    # everything a layer holds outside the embedding, twice over: the plan's weights less the embedding
    assert adapter.fixed_weight_bytes(CFG) + 4 * 128 * 12_582_912 == pytest.approx(
        CFG["memory_plan"]["weights_bytes"] - 2 * D * V + 2 * fixed_f32)
    rows, ctx, hit = 64.0, 4800.0, 500.0
    token = 2 * 2 * 512  # K and V of 4 heads of 128, bfloat16
    want = (adapter.fixed_weight_bytes(CFG) + hit * 12_582_912 + rows * (4 * 2048 + 4801) * token
            + rows * (5 * token + 2 * D))
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit) == pytest.approx(want)
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit, 64 * 4 * 2048, 64 * 4801) == pytest.approx(want)
    assert 8.5e9 < want < 9.6e9  # ISSUE 42: ~9.2 GB a step
    assert adapter.expected_experts_hit(CFG, 64) == pytest.approx(4 * 128 * (1 - (1 - 8 / 128) ** 64))
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = adapter.decode_step_floor_s(CFG, peaks, rows, ctx, hit)
    assert floor == pytest.approx(want / 819e9) and 0.0105 < floor < 0.0118  # bytes bind, not operations
    assert adapter.decode_step_flops(CFG, rows, ctx) / 197e12 < floor / 3


def _ctx(spans, trace=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": adapter}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 14.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 60, "state_rows": 60, "moe_pairs_held": 1920, "moe_pairs_absent": 0,
          "moe_experts_hit": 480, "moe_experts_held": 512, "window_tokens_read": 60 * 4 * 2048,
          "full_tokens_read": 60 * 5000, "kv_pages_walked": 2400}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "moe_experts_hit": 512, "moe_experts_held": 512,
         "window_tokens_read": 4 * 512 * 2048, "full_tokens_read": 512 * 3000}
PARENT_SPANS = [{"kind": "decode", "rows": 60}]


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=500, moe_pairs_held=2000), CHUNK])
    assert _read("moe_experts_hit_share", ctx) == pytest.approx(100 * 980 / 1024)
    assert _read("moe_pairs_per_expert_mean", ctx) == pytest.approx(3920 / 980)
    assert _read("window_tokens_read_mean", ctx) == pytest.approx(60 * 4 * 2048)
    assert _read("state_rows_mean", ctx) == 60
    assert _read("window_read_share.trinity", ctx) == pytest.approx(100 * 4 * 2048 / (4 * 2048 + 5000))
    share = _read("decode_step_roofline_share.trinity", ctx)
    want = adapter.decode_step_floor_s(CFG, ctx["peaks"], 60, 5000, 490, 60 * 4 * 2048, 60 * 5000) / 0.014 * 100
    assert share == pytest.approx(want) and 60 < share < 100
    parent = _ctx(PARENT_SPANS)
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean", "window_tokens_read_mean",
                 "window_read_share.trinity", "decode_step_roofline_share.trinity"):
        assert _read(name, parent) is None


def test_the_grouped_matmul_roofline_counts_the_experts_hit_and_stays_under_the_peak():
    trace = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
             "ops_self_s": {"grouped_matmul_gate_up": 0.6, "grouped_matmul_down": 0.35, "paged_attention": 0.05, "fusion": 1.0},
             "modules": {"jit_decode_paged": {"count": 60, "total_s": 1.8}, "jit_extend_batch_paged": {"count": 8, "total_s": 0.5}}}
    ctx = _ctx([DECODE, CHUNK], trace)
    block = CFG["engine"]["decode_block"]  # steps a decode program
    hits = 60 * block * 480 + 8 * 512
    got = _read("grouped_matmul_roofline_share.trinity", ctx)
    assert got == pytest.approx(100 * hits * 12_582_912 / 819e9 / 0.95) and got < 100
    assert _read("grouped_matmul_busy_share", ctx) == pytest.approx(100 * 0.95 / 2.4)
    assert _read("page_attn_busy_share", ctx) == pytest.approx(100 * 0.05 / 2.4)
    assert _read("grouped_matmul_roofline_share.trinity", _ctx([DECODE], None)) is None  # an untraced run
    assert _read("grouped_matmul_roofline_share.trinity",
                 _ctx(PARENT_SPANS, dict(trace, ops_self_s={"fusion": 1.0}))) is None  # the parent


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Trinity-Mini")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 2048, "intermediate_size": 6144, "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "num_hidden_layers": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1, "num_dense_layers": 2, "sliding_window": 2048,
        "rms_norm_eps": 1e-5, "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "mup_enabled": True, "rope_theta": 10000, "rope_scaling": None, "vocab_size": 200192,
        "max_position_embeddings": 131072, "tie_word_embeddings": False, "model_type": "afmoe",
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["layer_types"] == ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 8
    # the cut: depth alone
    assert CFG["reduced"] == ["layers"] and set(CFG["reduced_how"]) == {"layers"}
    assert CFG["layers"] == len(CFG["layers_served"]) == 5 and CFG["layers_served"] == [0, 4, 5, 6, 7]
    assert CFG["num_experts_held"] == CFG["num_experts"] == 128 and CFG["experts_first"] == 0
    assert CFG["chips_sharing_a_layer"] == 1 and "pipeline stage" in CFG["deployment"]
    assumed = " ".join(CFG["assumed"])
    for item in ("per head", "output gate", "sliding_attention layers ONLY", "four RMSNorms", "muP", "buffer",
                 "N(0, 0.01)", "own position"):
        assert item in assumed, item


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] == 64 * 64 + 1
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 64
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 8192 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512 and env["APP_ENGINE_PREFIXCACHEENABLE"] == "off"
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == eng["decode_block"] and eng["decode_block"] in (2, 3, 4)
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    assert CFG["reference"]["prompt_tokens"] == [64, 96, 640, 2560] and CFG["reference"]["decode_tokens"] == 8
    assert CFG["correct"]["kernel_paths"] == {"grouped_matmul": "compiled"}
    grow = {(c["metric"], c.get("labels", {}).get("held")) for c in CFG["correct"]["counters_must_grow"]}
    assert grow == {("genai_engine_moe_pairs_total", "true"), ("genai_engine_window_read_tokens_total", None),
                    ("genai_engine_state_slot_resets_total", None)}
    # all experts are here: an absent pair is a bug
    assert CFG["correct"]["counters_must_not_grow"] == [{"metric": "genai_engine_moe_pairs_total", "labels": {"held": "false"}}]
    from generativeaiexamples_tpu.models import afmoe as m

    mc, plan = adapter.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc) == 8_483_069_440
    assert plan["fixed_state_bytes"] == 64 * m.fixed_state_bytes_per_slot(mc) and eng["fixed_state_bytes_per_slot"] == 16_777_216
    assert plan["page_pool_bytes"] == 4097 * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 2048
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9


# the per-layer entries ISSUE 42 names for the cell; a later PR may append more
NAMED = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share",
    "stream_backlog_tokens_mean", "state_rows_mean", "extend_dispatch_dev_ms",
    "page_attn_busy_share", "page_attn_pages_walked_mean", "window_tokens_read_mean",
    "moe_experts_hit_share", "moe_pairs_per_expert_mean", "grouped_matmul_busy_share",
    "grouped_matmul_roofline_share.trinity", "decode_step_roofline_share.trinity", "window_read_share.trinity",
)
JOINED = ("decode_step_done_ms", "extend_wide_done_ms", "extend_device_share", "device_starved_share", "device_hold_max_ms")




def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("trinity-mini-26b-a3b-bf16", "doc_reason", 1)
    assert len(cell["why"]) <= 200 and "4 window : 1 full" in cell["why"]
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    # found by name and cell: neither their count nor their place is pinned
    mine = assert_cell_holds(manifest, CELL, NAMED + JOINED + ("moe_tiles_used_share",))
    assert "extend_narrow_done_ms" not in mine  # one chunk width


def test_manifest_entries_of_the_cell_found_by_name():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert_manifest_entries_of_the_cell(manifest)
    traffic = load(os.path.join(BENCH, "traffic", "doc_reason.json"))
    assert traffic["clients"] == CFG["engine"]["max_batch_size"] and traffic["question_bytes"] == [2048, 3072, 4096]
    assert sum(1 for w in manifest["workloads"] if w["traffic"] == "doc_reason") >= 3  # three expert configurations, one traffic file


EARLIER = {
    "doc_reason_glm53flash": ("glm-5.3-flash-ep8-bf16", "decode_step_roofline_share.glm53"),
    "doc_reason_gigachat35": ("gigachat3.5-432b-a28b-ep16-bf16", "decode_step_roofline_share.gigachat35"),
    "reason_decode_phi4flash": ("phi-4-mini-flash-reasoning-bf16", "decode_step_roofline_share"),
}


@pytest.mark.parametrize("cell", sorted(EARLIER))
def test_the_earlier_cells_entries_are_untouched(cell):
    """The three cells before this one keep their configuration, their
    place before it and the whole step's share each of them reads."""
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    config, step_share = EARLIER[cell]
    (w,) = [x for x in manifest["workloads"] if x["name"] == cell]
    assert w["config"] == config and w["chips"] == 1
    mine = entries_of(manifest, cell)
    assert step_share in mine and {"decode_rows_mean", "device_idle_share"} <= set(mine)
    assert not any(n.startswith("decode_step_roofline_share") for n in mine if n != step_share)  # one adapter's floor a cell
    assert manifest["workloads"].index(w) < [x["name"] for x in manifest["workloads"]].index(CELL)
