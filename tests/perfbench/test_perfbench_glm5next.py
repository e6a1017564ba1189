"""The GLM-5.3-Flash adapter (perfbench/arch/glm5next.py): its plain
float32 reference against the engine at a tiny size that keeps the five
layers of the share, the control one precision down, the injected faults
that must each fail ``TOLERANCE`` (or the selection's limit), its byte
counts against hand values, its readers and its configuration file."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import glm5next as glm
from tests.perfbench.manifest_entries import assert_cell_holds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "glm-5.3-flash-ep8-bf16.json")


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# the same five layers and the same share at widths a CPU test can walk
TINY = dict(
    CFG, name="glm5next-tiny-test", vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, n_routed_experts_held=2,
    num_attention_heads=4, kda_low_rank=8, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    v_head_dim=16, index_n_heads=2, index_head_dim=16, index_rope_dims=8, index_topk=32,
    linear_attn_config=dict(CFG["linear_attn_config"], num_heads=4, head_dim=16),
    engine=dict(CFG["engine"], max_seq_len=256),
    reference=dict(CFG["reference"], decode_tokens=5),
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    glm.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=2, max_seq_len=256,
        prefill_chunk=64, page_size=16, decode_block=4, prefix_cache_enable="off",
        dtype="float32", paged_kernel="off",
    ))
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def compared(engine):
    """As the launcher compares on the chip: last-position logits of the
    served walks (prefill alone; prefill and one decode step; chunked
    extend of 150 tokens, past the selection's reach of 32), greedy
    tokens through the engine, and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = reference.seeded_prompts([9, 40, 150], 250, seed=11)
    eng_logits = glm.engine_prefill_logits(engine, prompts, on_tpu=False)
    greedy = SamplingParams(temperature=0.0, max_tokens=5)
    tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full, glm.reference_logits(engine, TINY, full)


def test_engine_agrees_with_the_reference_through_prefill_extend_and_decode(compared):
    prompts, eng_logits, tokens, _, ref = compared
    out = reference.compare(prompts, list(eng_logits), tokens, ref, glm.TOLERANCE)
    assert out["ok"], out
    assert len(out["prefill_rel_err"]) == 3 and max(out["prefill_rel_err"]) < 1e-4
    assert out["decode_tokens_checked"] == 15 and out["decode_margin_max"] < 1e-4
    # only the compared positions carry logits: the head is not computed for the rest
    assert not ref[2][:140].any() and ref[2][149].any()


def test_the_engines_selection_is_held_against_the_references(engine, compared, monkeypatch):
    kept = dict(glm._LAST_SELECTION)
    assert len(kept["tokens"]) == 150 and kept["groups"].sum() == 8  # 32 tokens of the 148 before the open group
    assert kept["reference_groups"].sum() == 8 and kept["share"] == 1.0
    ref_sel = kept["reference_groups"]
    assert glm.selection_overlap(np.roll(kept["groups"], 1), ref_sel) < glm.SELECTION_OVERLAP_MIN
    assert glm.selection_overlap(np.ones_like(ref_sel), ref_sel) == 1.0  # "selection off" is caught by the logits, below
    # a selection that went elsewhere makes the comparison raise: the run is then not correct
    monkeypatch.setitem(glm._LAST_SELECTION, "groups", np.roll(kept["groups"], 3))
    full = compared[3]
    with pytest.raises(RuntimeError, match="selection overlap"):
        glm.check_selection(full, [[], [], [np.tile(ref_sel, (155, 1))]])


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    """The launcher asks for the rows, then sends its greedy requests,
    then compares: the served walks run at the first read, and once."""
    calls = []
    real = glm._served_logits
    try:
        glm._served_logits = lambda eng, prompts: calls.append(1) or real(eng, prompts)
        rows = glm.engine_prefill_logits(engine, compared[0][:2], on_tpu=False)
        assert not calls and all(isinstance(r, glm.Deferred) for r in rows)
        first = np.asarray(rows[0], np.float32)
        np.asarray(rows[1])
        assert calls == [1] and first.dtype == np.float32 and first.shape == (256,)
        np.testing.assert_allclose(first, np.asarray(compared[1][0]), rtol=1e-5, atol=1e-5)
    finally:
        glm._served_logits = real
        glm._PENDING.clear()


def _with(engine, monkeypatch, **model_cfg):
    monkeypatch.setattr(engine, "model_config", dataclasses.replace(engine.model_config, **model_cfg))


def _cast_state(real):
    import jax.numpy as jnp

    def init(*args, **kw):
        caches = real(*args, **kw)
        caches["kda"] = [s.astype(jnp.bfloat16) for s in caches["kda"]]
        return caches
    return init


def _stale_extend(real):
    import jax.numpy as jnp

    # every chunk is told it is not a row's first: the former tenant's state is carried on
    return lambda params, cfg, caches, tokens, offsets, *rest, **kw: real(
        params, cfg, caches, tokens, jnp.maximum(offsets, 64), *rest, **kw)


FAULTS = {
    "a_dropped_expert": lambda eng, mp: mp.setattr(eng, "params", dict(eng.params, layers=[
        dict(lp, we_down=lp["we_down"].at[0].set(0.0)) if "we_down" in lp else lp for lp in eng.params["layers"]])),
    "a_wrong_gate_scale": lambda eng, mp: _with(eng, mp, routed_scaling_factor=1.0),
    "selection_off": lambda eng, mp: _with(eng, mp, index_topk=4096),
    "a_stale_state": lambda eng, mp: mp.setattr(eng, "_family", dataclasses.replace(
        eng._family, extend_paged=_stale_extend(eng._family.extend_paged))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_injected_fault_fails_the_logits_comparison(engine, compared, fault, monkeypatch):
    """Each fault in the SERVED walks (experts, gates, selection, state)
    takes a compared logit past ``TOLERANCE``."""
    prompts, _, tokens, _, ref = compared
    FAULTS[fault](engine, monkeypatch)
    faulty = glm.engine_prefill_logits(engine, prompts, on_tpu=False)
    out = reference.compare(prompts, faulty, tokens, ref, glm.TOLERANCE)
    assert not out["ok"] and max(out["prefill_rel_err"]) > glm.TOLERANCE, (fault, out)


def test_a_bfloat16_state_alone_shows_but_does_not_pass_a_limit_that_admits_bfloat16_products(engine, compared, monkeypatch):
    """The KDA state kept in bfloat16 (nothing else changed) moves the
    compared logits a thousand times further than the float32 walks'
    rounding, and stays under ``TOLERANCE``: a limit that has to admit
    bfloat16 matrix products cannot refuse a bfloat16 state by itself.
    What refuses lower precision is the control below (PERF.md section 7)."""
    prompts, clean, tokens, _, ref = compared
    monkeypatch.setattr(engine, "_family", dataclasses.replace(
        engine._family, init_paged_cache=_cast_state(engine._family.init_paged_cache)))
    faulty = glm.engine_prefill_logits(engine, prompts, on_tpu=False)
    out = reference.compare(prompts, faulty, tokens, ref, glm.TOLERANCE)
    base = reference.compare(prompts, list(clean), tokens, ref, glm.TOLERANCE)
    assert max(out["prefill_rel_err"]) > 100 * max(base["prefill_rel_err"]) and max(out["prefill_rel_err"]) < glm.TOLERANCE


def test_the_control_one_precision_down_fails(engine, compared):
    prompts, eng_logits, tokens, full, ref = compared
    low = glm.reference_logits(engine, TINY, full, precision="bfloat16")
    err = [float(np.max(np.abs(a[len(p) - 1] - b[len(p) - 1])) / np.max(np.abs(b[len(p) - 1])))
           for a, b, p in zip(low, ref, prompts)]
    assert max(err) > glm.TOLERANCE, err


# --------------------------------------------------------------------------- #
# The adapter's contract, its bytes and its readers (no jax)


def test_adapter_contract_and_no_jax_at_import():
    import subprocess
    import sys

    assert arch.load(CFG, [os.path.join(ROOT, p) for p in ("perfbench", "tests/perfbench")]) is glm
    code = "import sys; import perfbench.arch.glm5next; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))


def test_layers_served_and_the_model_configuration():
    assert glm.layer_kinds(CFG) == [("kda", "dense"), ("dsa", "sparse"), ("kda", "sparse"), ("kda", "sparse"), ("kda", "sparse")]
    from generativeaiexamples_tpu.models import glm5next as m

    assert glm.model_config(CFG) == m.PRESETS["glm-5.3-flash-ep8"]
    assert glm.model_config(TINY) == dataclasses.replace(m.PRESETS["glm5next-debug"], max_seq_len=256)


def test_byte_counts_against_hand_values():
    D, Fm, V = 4096, 2048, 19360
    assert glm.expert_bytes(CFG) == 3 * D * Fm * 2 == 50_331_648
    kda = D * 24576 + D * (64 + 256) + 2 * 128 * 8192 + 8192 * D
    dsa = D * (1536 + 512 + 128 + 32) + 1536 * (64 * 256 + 32 * 128) + 2 * 64 * 256 * 512 + 64 * 256 * D
    fixed_bf16 = 4 * kda + dsa + 3 * D * 12288 + 4 * 3 * D * Fm + D * V
    fixed_f32 = 4 * (4 * 24576 + 8192 + 64) + 5 * 2 * (4 * D * 24 + 4 * D) + 4 * D * 288
    assert glm.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    assert 1.95e9 < glm.fixed_weight_bytes(CFG) < 2.15e9  # ISSUE 35: 1.87 GB outside the experts + 0.16 GB of head
    state = 64 * 128 * 128 * 4 + 3 * 24576 * 2
    rows, ctx, hit, sel = 64.0, 4500.0, 120.4, 2050.0
    want = (glm.fixed_weight_bytes(CFG) + hit * 50_331_648
            + rows * (2 * 4 * state + sel * 512 * 2 + ctx / 4 * 128 * 2 + 512 * 2 + 2 * D))
    assert glm.decode_step_bytes(CFG, rows, ctx, hit, sel) == pytest.approx(want)
    assert 10.0e9 < want < 10.8e9  # ISSUE 35 counts 10.4 GB at 64 rows
    # without the spans: the uniform router's expectation and the selection's own size
    assert glm.expected_experts_hit(CFG, 64) == pytest.approx(4 * 36 * (1 - (1 - 8 / 288) ** 64))
    assert glm.selected_tokens(CFG, 1000) == 1000 and glm.selected_tokens(CFG, 4500) == 2048 + 2.5
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = glm.decode_step_floor_s(CFG, peaks, rows, ctx, hit, sel)
    assert floor == pytest.approx(want / 819e9) and 0.012 < floor < 0.0135  # bytes bind, not operations
    assert glm.decode_step_flops(CFG, rows, ctx, sel) / 197e12 < floor / 3


def _ctx(spans, trace=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": glm}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 20.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 60, "state_rows": 60, "moe_pairs_held": 230, "moe_experts_hit": 115,
          "moe_experts_held": 144, "dsa_tokens_selected": 60 * 2050, "dsa_context_tokens": 60 * 4500,
          "kv_pages_walked": 2200}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "moe_experts_hit": 144, "moe_experts_held": 144}


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=125, moe_pairs_held=250), CHUNK])
    read = lambda name: (lambda spec: arch.module_under("perfbench.arch.glm5next", [BENCH]) and getattr(  # noqa: E731
        glm, spec["reader"].split(":")[1])(ctx, spec["params"]))(load(os.path.join(BENCH, "layer_metrics", name + ".json")))
    assert read("moe_experts_hit_share") == pytest.approx(100 * 240 / 288)
    assert read("moe_pairs_per_expert_mean") == pytest.approx(480 / 240)
    assert read("dsa_selected_share") == pytest.approx(100 * 2050 / 4500)
    share = read("decode_step_roofline_share.glm53")
    want = glm.decode_step_floor_s(CFG, ctx["peaks"], 60, 4500, 120, 2050) / 0.020 * 100
    assert share == pytest.approx(want) and 50 < share < 70
    parent = _ctx([{"kind": "decode", "rows": 60}])
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean", "dsa_selected_share", "decode_step_roofline_share.glm53"):
        spec = load(os.path.join(BENCH, "layer_metrics", name + ".json"))
        assert getattr(glm, spec["reader"].split(":")[1])(parent, spec["params"]) is None


def test_kernel_roofline_readers_count_the_bytes_the_trace_saw():
    trace = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
             "ops_self_s": {"grouped_matmul_gate_up": 0.5, "grouped_matmul_down": 0.3, "latent_attention": 0.06, "fusion": 1.0},
             "modules": {"jit_decode_paged": {"count": 10, "total_s": 1.8}, "jit_extend_batch_paged": {"count": 8, "total_s": 0.5}}}
    ctx = _ctx([DECODE, CHUNK], trace)
    spec = load(os.path.join(BENCH, "layer_metrics", "grouped_matmul_roofline_share.json"))
    block = CFG["engine"]["decode_block"]  # steps a decode program
    hits = 10 * block * 115 + 8 * 144
    assert glm.grouped_matmul_roofline_share(ctx, spec["params"]) == pytest.approx(100 * hits * 50_331_648 / 819e9 / 0.8)
    spec = load(os.path.join(BENCH, "layer_metrics", "latent_attn_roofline_share.json"))
    assert glm.latent_attention_roofline_share(ctx, spec["params"]) == pytest.approx(
        100 * 10 * block * 2200 * 128 * 512 * 2 / 819e9 / 0.06)
    for reader, name in ((glm.grouped_matmul_roofline_share, "grouped_matmul_roofline_share"),
                         (glm.latent_attention_roofline_share, "latent_attn_roofline_share")):
        params = load(os.path.join(BENCH, "layer_metrics", name + ".json"))["params"]
        assert reader(_ctx([DECODE], None), params) is None  # an untraced run
        assert reader(_ctx([{"kind": "decode", "rows": 3}], dict(trace, ops_self_s={"fusion": 1.0})), params) is None  # the parent


# --------------------------------------------------------------------------- #
# The configuration file, the traffic file and the manifest's entries


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 4096, "intermediate_size": 12288, "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "num_key_value_heads": 64, "num_hidden_layers": 45, "n_routed_experts": 288, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 256, "qk_head_dim": 256,
        "qk_rope_head_dim": 0, "v_head_dim": 256, "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
        "index_kpool": 4, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "rms_norm_eps": 1e-5,
        "routed_scaling_factor": 2.5, "swiglu_limit": 10, "first_k_dense_replace": 3, "n_group": 1, "topk_group": 1,
        "max_position_embeddings": 1048576, "head_dim": 0,
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["linear_attn_config"]["num_heads"] == 64 and CFG["linear_attn_config"]["head_dim"] == 128
    assert CFG["linear_attn_config"]["gate_lower_bound"] == -5 and CFG["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert len(CFG["layer_types"]) == len(CFG["mlp_layer_types"]) == len(CFG["indexer_types"]) == 45
    # the cut: depth, experts held, vocabulary, MTP; no width among them
    assert CFG["reduced"] == ["layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    width_words = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "experts_per")
    for key in CFG["reduced"]:
        assert not any(w in key for w in width_words) and not key.endswith(("_dim", "_rank")) and key in CFG["reduced_how"]
    assert CFG["layers"] == len(CFG["layers_served"]) == 5 and CFG["layers_served"] == [0, 3, 4, 5, 6]
    assert CFG["vocab_size"] == 154880 // 8 and CFG["vocab_size_published"] == 154880
    assert CFG["n_routed_experts_held"] == 288 // 8 and CFG["experts_first"] == 0 and CFG["num_nextn_predict_layers"] == 0
    assert "8 chips share each layer" in CFG["deployment"] and len(CFG["assumed"]) >= 8


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] == 64 * 64 + 1
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 64
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 8192 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512 and env["APP_ENGINE_PREFIXCACHEENABLE"] == "off"
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    assert CFG["reference"]["prompt_tokens"] == [64, 96, 640, 2560] and CFG["reference"]["decode_tokens"] == 8
    assert max(CFG["reference"]["prompt_tokens"]) > CFG["index_topk"]  # the selection discards keys on the chip
    assert CFG["correct"]["kernel_paths"] == {"grouped_matmul": "compiled"}
    grow = {c["metric"] for c in CFG["correct"]["counters_must_grow"]}
    assert {"genai_engine_moe_pairs_total", "genai_engine_dsa_selected_tokens_total",
            "genai_engine_dsa_context_tokens_total", "genai_engine_state_slot_resets_total"} <= grow
    from generativeaiexamples_tpu.models import glm5next as m

    mc, plan = glm.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc)
    assert plan["fixed_state_bytes"] == 64 * m.fixed_state_bytes_per_slot(mc) and eng["fixed_state_bytes_per_slot"] == 17_367_552
    assert plan["page_pool_bytes"] == 4097 * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 1088
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9


CELL = "doc_reason_glm53flash"
# the cell's per-layer metrics as PR 35 brought them (base names since PR 56: a generic metric is ONE entry with the list of its cells)
PER_LAYER_15 = (
    "decode_rows_mean", "decode_step_dev_ms", "decode_step_roofline_share.glm53",
    "tpot_chat_p50_ms", "device_idle_share", "stream_backlog_tokens_mean",
    "state_rows_mean", "extend_dispatch_dev_ms", "moe_experts_hit_share",
    "moe_pairs_per_expert_mean", "dsa_selected_share", "grouped_matmul_busy_share",
    "grouped_matmul_roofline_share", "latent_attn_busy_share", "latent_attn_roofline_share",
)


def assert_manifest_entries_of_the_cell(manifest):
    """The cell, its configuration and its entries, found by NAME and
    CELL: the cell's set of names contains its 15, wherever they stand and
    whatever other cells their lists hold."""
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("glm-5.3-flash-ep8-bf16", "doc_reason", 1)
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG))
    assert_cell_holds(manifest, CELL, PER_LAYER_15)


def test_traffic_and_manifest_entries_are_as_the_issue_gives_them():
    traffic = load(os.path.join(BENCH, "traffic", "doc_reason.json"))
    assert traffic["kind"] == "closed" and traffic["clients"] == 64
    assert traffic["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1}
    assert traffic["question_bytes"] == [2048, 3072, 4096] and traffic["max_tokens"] == [1024, 2048, 3072]
    assert traffic["ramp"] == {"expected_request_s": 50.0, "cap_s": 60.0}
    assert traffic["traced_run_window_s"] == 20.0 and traffic["trace_window_s"] == 2.5
    assert_manifest_entries_of_the_cell(load(os.path.join(ROOT, "BENCHMARK.json")))
