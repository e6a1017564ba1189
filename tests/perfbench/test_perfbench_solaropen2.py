"""The Solar-Open2 adapter (perfbench/arch/solaropen2.py): its plain
float32 reference against the engine at a tiny size that keeps the
period served, with the prefix store ON so that the ``served_only``
prompts enter through a restored state, the control one precision down
and the faults that must each fail ``TOLERANCE``, its byte and operation
counts against hand values, its readers, its configuration file and its
manifest entries (found by name: entries a later PR appends are none of
this file's business)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import solaropen2 as adapter
from tests.perfbench.manifest_entries import assert_cell_holds
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "solar-open2-250b-ep8-bf16.json")
CELL = "chat_sessions_solaropen2"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# the period served (softmax, KDA, KDA, KDA) at widths a CPU test can walk: the sizes of ``solaropen2-debug``
TINY = dict(
    CFG, name="solaropen2-tiny-test", vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=4, gqa_layers=[0], layers_served=[0, 1, 2, 3], layers=4, n_routed_experts=16,
    num_experts_per_tok=4, n_routed_experts_held=2, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    linear_attn_config=dict(CFG["linear_attn_config"], num_heads=4, head_dim=16), kda_low_rank=8,
    engine=dict(CFG["engine"], max_seq_len=512),
    reference=dict(CFG["reference"], prompt_tokens=[9, 40, 150], served_only_prompt_tokens=[200, 330], decode_tokens=5),
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    adapter.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=2, max_seq_len=512,
        prefill_chunk=64, page_size=16, decode_block=4, prefix_cache_enable="auto", prefix_cache_slots=4,
        dtype="float32", paged_kernel="off",
    ))
    yield eng
    eng.shutdown()


def harness_prompts(engine):
    """The launcher's prompts (perfbench/launcher.py ``reference_check``)."""
    ref = TINY["reference"]
    lengths = list(ref["prompt_tokens"]) + list(ref["served_only_prompt_tokens"])
    usable = min(TINY["vocab_size"], getattr(engine.tokenizer, "vocab_size", TINY["vocab_size"]))
    stops = set(engine.tokenizer.stop_ids())
    return [[t if t not in stops else 0 for t in p]
            for p in reference.seeded_prompts(lengths, usable, seed=adapter.HARNESS_PROMPT_SEED)]


def counters():
    from generativeaiexamples_tpu.utils import metrics as metrics_mod

    out = {}
    for line in metrics_mod.get_registry().render().splitlines():
        if line.startswith("genai_engine_") and " " in line:
            k, v = line.rsplit(" ", 1)
            out[k] = float(v)
    return out


@pytest.fixture(scope="module")
def compared(engine):
    """As the launcher compares on the chip: last-position logits of the
    served walks (one chunk; 39 tokens and one decode step; 150 tokens in
    three extend chunks), greedy tokens through the engine for those AND
    for the two served-only prompts, which the adapter served once
    before, and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = harness_prompts(engine)
    before = counters()
    eng_logits = list(adapter.engine_prefill_logits(engine, prompts[:3], on_tpu=False)) + [None, None]
    primed = counters()
    greedy = SamplingParams(temperature=0.0, max_tokens=5)
    tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
    after = counters()
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    grew = lambda a, b, k: b.get(k, 0.0) - a.get(k, 0.0)  # noqa: E731
    return (prompts, eng_logits, tokens, full, adapter.reference_logits(engine, TINY, full),
            {"primed": {k: grew(before, primed, k) for k in primed}, "served": {k: grew(primed, after, k) for k in after}})


def test_engine_agrees_with_the_reference_through_prefill_extend_decode_and_a_hit(compared):
    prompts, eng_logits, tokens, _, ref, _ = compared
    out = reference.compare(prompts, eng_logits, tokens, ref, adapter.TOLERANCE)
    assert out["ok"] and max(out["prefill_rel_err"]) < 1e-4 and out["decode_margin_max"] < 1e-4, out
    assert out["decode_tokens_checked"] == 25 and all(len(t) == 5 for t in tokens)


def test_the_served_only_prompts_enter_through_a_restored_state(compared):
    """The adapter's priming saved a state at 192 and 320 tokens (the
    deepest chunk boundary under each prompt's last token); the harness's
    own decode of the two prompts restored them and prefilled the tail."""
    _, _, _, _, _, grew = compared
    primed, served = grew["primed"], grew["served"]
    assert primed["genai_engine_prefix_state_saves_total"] == 2 and primed.get("genai_engine_prefix_state_restores_total", 0) == 0
    assert served["genai_engine_prefix_state_restores_total"] == 2
    assert served["genai_engine_prefix_cache_hits_total"] == 2
    assert served["genai_engine_prefix_cache_tokens_reused_total"] == 192 + 320
    assert served["genai_engine_prefix_state_saves_total"] == 1  # the 150-token prompt's, at 128; the two are there already
    assert served["genai_engine_state_slot_resets_total"] == 3  # the three prompts that entered cold


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    prompts = compared[0]
    rows = adapter.engine_prefill_logits(engine, prompts[:1], on_tpu=False)
    assert adapter._PENDING and isinstance(rows[0], adapter.Deferred)
    assert np.asarray(rows[0]).shape == (TINY["vocab_size"],)
    adapter._PENDING.clear()


@pytest.mark.parametrize("fault", ["bfloat16_state", "bfloat16_router_input"])
def test_one_thing_rounded_to_bfloat16_shows_and_fails_the_float32_limit(engine, compared, fault):
    """The float32 engine against a reference whose recurrent state
    alone, or whose experts' (and router's) input alone, is rounded to
    bfloat16: the reading is dozens of times the clean one and fails the
    limit a float32 engine is held to here (1e-4). It does NOT pass
    ``TOLERANCE``: that limit admits bfloat16 PRODUCTS (the chip's
    served walks read a few percent), of which one rounded tensor is a
    part; the all-bfloat16 control below is what must fail it."""
    import jax.numpy as jnp

    prompts, eng_logits, tokens, full, clean_ref, _ = compared
    faults = {"bfloat16_state": dict(state_dtype=jnp.bfloat16), "bfloat16_router_input": dict(router_dtype=jnp.bfloat16)}
    ref = adapter.reference_logits(engine, TINY, full, **faults[fault])
    clean = reference.compare(prompts, eng_logits, tokens, clean_ref, 1e-4)
    out = reference.compare(prompts, eng_logits, tokens, ref, 1e-4)
    assert clean["ok"] and not out["ok"], (fault, out)
    assert max(out["prefill_rel_err"]) > 30 * max(clean["prefill_rel_err"]), (fault, out, clean)


def test_the_control_one_precision_down_fails(engine, compared):
    """All-bfloat16 products, sums, norms, state and residual: past ``TOLERANCE``."""
    prompts, _, _, full, ref, _ = compared
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
    code = "import sys; import perfbench.arch.solaropen2; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    with open(adapter.__file__, encoding="utf-8") as fh:
        text = fh.read()
    # the registration alone touches the program's model; the reference imports nothing of it
    assert text.count("generativeaiexamples_tpu.models") == 2 and "models.solaropen2 import SolarOpen2Config" in text


def test_layers_served_and_the_model_configuration():
    assert adapter.layer_kinds(CFG) == ["full", "kda", "kda", "kda"]
    from generativeaiexamples_tpu.models import solaropen2 as m

    assert adapter.model_config(CFG) == m.PRESETS["solar-open2-250b-ep8"]
    assert adapter.model_config(TINY) == dataclasses.replace(m.PRESETS["solaropen2-debug"], max_seq_len=512,
                                                             layers_served=(0, 1, 2, 3))
    assert adapter.expert_keys(CFG) == {"swiglu_limit": float("inf"), "num_experts_per_tok": 8,
                                        "routed_scaling_factor": 1.0, "experts_first": 0, "n_routed_experts_held": 40}


def test_byte_and_operation_counts_against_hand_values():
    D, V, K = 4096, 24576, 8192
    assert adapter.expert_bytes(CFG) == 3 * D * 1280 * 2 == 31_457_280
    assert adapter.state_row_bytes(CFG) == 3 * (64 * 128 * 128 * 4 + 3 * 3 * K * 2) == 13_025_280
    assert adapter.page_bytes(CFG) == 128 * 4096 == 524_288
    attn, kda = D * 18432 + K * D, D * 3 * K + D * 320 + 2 * 128 * K + K * D
    assert attn == 109_051_904 and kda + 4 * 3 * K + K + 64 + 128 == 137_732_288  # ISSUE 44's mixers
    fixed_bf16 = attn + 3 * kda + 4 * 3 * D * 1280 + D * V
    fixed_f32 = 3 * (4 * 3 * K + K + 64 + 128) + 4 * (2 * D + D * 320 + 320) + D
    assert adapter.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    # everything the plan holds outside the routed experts and the embedding, the float32 leaves at their width
    assert adapter.fixed_weight_bytes(CFG) + 4 * 40 * 31_457_280 == pytest.approx(
        CFG["memory_plan"]["weights_bytes"] - 2 * D * V + 2 * fixed_f32)
    rows, ctx, hit = 64.0, 4500.0, 128.0
    want = (adapter.fixed_weight_bytes(CFG) + hit * 31_457_280 + rows * 4501 * 4096
            + rows * (2 * 13_025_280 + 4096 + 2 * D))
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit) == pytest.approx(want)
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit, 64 * 4501) == pytest.approx(want)
    assert 7.5e9 < want < 9.0e9  # ISSUE 44: ~8.2 GB a step
    assert adapter.expected_experts_hit(CFG, 64) == pytest.approx(4 * 40 * (1 - (1 - 8 / 320) ** 64))
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = adapter.decode_step_floor_s(CFG, peaks, rows, ctx, hit)
    assert floor == pytest.approx(want / 819e9) and 0.009 < floor < 0.011  # bytes bind, not operations
    assert adapter.decode_step_flops(CFG, rows, ctx) / 197e12 < floor / 3


def _ctx(spans, trace=None, before=None, after=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": adapter,
           "metrics_before": before or {}, "metrics_after": after or {}}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 13.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 60, "state_rows": 60, "state_kernel_rows": 60, "moe_pairs_held": 240,
          "moe_pairs_absent": 1680, "moe_experts_hit": 120, "moe_experts_held": 160, "full_tokens_read": 60 * 4500,
          "kv_pages_walked": 2160}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "moe_experts_hit": 160, "moe_experts_held": 160, "full_tokens_read": 512 * 3000,
         "state_kernel_rows": 0}
PARENT_SPANS = [{"kind": "decode", "rows": 60}]
TRACE = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
         "ops_self_s": {"grouped_matmul_gate_up": 0.5, "grouped_matmul_down": 0.3, "paged_attention": 0.3,
                        "delta_rule_step": 0.35, "fusion": 1.0},
         "modules": {"jit_decode_paged": {"count": 80, "total_s": 1.7}, "jit_extend_batch_paged": {"count": 10, "total_s": 0.6},
                     "jit_prefix_state_copy": {"count": 12, "total_s": 0.0006}}}


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=100, moe_pairs_held=200), CHUNK])
    assert _read("moe_experts_hit_share", ctx) == pytest.approx(100 * 220 / 320)
    assert _read("moe_pairs_per_expert_mean", ctx) == pytest.approx(440 / 220)
    assert _read("state_rows_mean", ctx) == 60
    share = _read("decode_step_roofline_share.solaropen2", ctx)
    want = adapter.decode_step_floor_s(CFG, ctx["peaks"], 60, 4500, 110, 60 * 4500) / 0.013 * 100
    assert share == pytest.approx(want) and 50 < share < 100
    parent = _ctx(PARENT_SPANS)
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean",
                 "decode_step_roofline_share.solaropen2"):
        assert _read(name, parent) is None


def test_kernel_roofline_readers_count_the_bytes_the_trace_saw_and_stay_under_the_peak():
    ctx = _ctx([DECODE, CHUNK], TRACE)
    steps = 80 * CFG["engine"]["decode_block"]
    got = _read("grouped_matmul_roofline_share.solaropen2", ctx)
    assert got == pytest.approx(100 * (steps * 120 + 10 * 160) * 31_457_280 / 819e9 / 0.8) and got < 100
    got = _read("delta_step_roofline_share.solaropen2", ctx)
    assert got == pytest.approx(100 * steps * 60 * 2 * 64 * 65536 * 3 / 819e9 / 0.35) and got < 100
    got = _read("page_attn_roofline_share.solaropen2", ctx)
    assert got == pytest.approx(100 * steps * 2160 * 524_288 / 819e9 / 0.3) and got < 100
    got = _read("prefix_state_copy_roofline_share.solaropen2", ctx)
    assert got == pytest.approx(100 * 12 * 2 * 13_025_280 / 819e9 / 0.0006) and got < 100
    assert _read("prefix_state_copy_device_share.solaropen2", ctx) == pytest.approx(100 * 0.0006 / 2.4)
    assert _read("grouped_matmul_busy_share", ctx) == pytest.approx(100 * 0.8 / 2.4)
    assert _read("page_attn_busy_share", ctx) == pytest.approx(100 * 0.3 / 2.4)
    bare = dict(TRACE, ops_self_s={"fusion": 1.0}, modules={"jit_decode_paged": {"count": 80, "total_s": 1.7}})
    for name in ("grouped_matmul_roofline_share", "delta_step_roofline_share", "page_attn_roofline_share",
                 "prefix_state_copy_roofline_share", "prefix_state_copy_device_share"):
        assert _read(name + ".solaropen2", _ctx([DECODE], None)) is None  # an untraced run
        assert _read(name + ".solaropen2", _ctx(PARENT_SPANS, bare)) is None  # a program without the kernels


def test_the_reused_share_reads_the_stores_counters():
    key = lambda name: (name, frozenset())  # noqa: E731
    before = {key("genai_engine_prefix_cache_tokens_reused_total"): 1000.0, key("genai_engine_prefill_tokens_total"): 5000.0,
              key("genai_engine_prefix_state_restores_total"): 2.0}
    after = {key("genai_engine_prefix_cache_tokens_reused_total"): 56000.0, key("genai_engine_prefill_tokens_total"): 50000.0,
             key("genai_engine_prefix_state_restores_total"): 40.0}
    assert _read("prefix_reused_token_share.solaropen2", _ctx([], None, before, after)) == pytest.approx(55.0)
    # a program without the counter (the parent), and a window in which nothing was submitted
    parent = {k: v for k, v in after.items() if "state_restores" not in k[0]}
    assert _read("prefix_reused_token_share.solaropen2", _ctx([], None, before, parent)) is None
    assert _read("prefix_reused_token_share.solaropen2", _ctx([], None, after, after)) is None


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Solar-Open2-250B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "vocab_size":  # reduced: the published number stands beside it
            assert CFG["vocab_size_published"] == value and key in CFG["reduced"]
        else:
            assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 4096, "intermediate_size": 10240, "moe_intermediate_size": 1280, "num_attention_heads": 64,
        "num_key_value_heads": 8, "head_dim": 128, "num_hidden_layers": 48, "n_routed_experts": 320,
        "num_experts_per_tok": 8, "n_shared_experts": 1, "first_k_dense_replace": 0, "rms_norm_eps": 1e-5,
        "routed_scaling_factor": 1, "norm_topk_prob": True, "use_rope": False, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "gqa_interval": 3, "model_type": "solar_open2",
        "max_position_embeddings": 1048576, "tie_word_embeddings": False, "vocab_size_published": 196608,
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["gqa_layers"] == list(range(0, 48, 4))
    assert CFG["linear_attn_config"] == {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert "gate_lower_bound" not in CFG["linear_attn_config"]  # no clamp: the block-wise form must not need one
    # the cut: depth, the experts held, the vocabulary's share; no width
    assert CFG["reduced"] == ["layers", "n_routed_experts", "vocab_size"] and set(CFG["reduced_how"]) == set(CFG["reduced"])
    assert CFG["layers"] == len(CFG["layers_served"]) == 4 and CFG["layers_served"] == [0, 1, 2, 3]
    assert CFG["n_routed_experts_held"] == 40 and CFG["experts_first"] == 0 and CFG["vocab_size"] == 196608 // 8
    assert CFG["chips_sharing_a_layer"] == 8 and "8 chips share each layer" in CFG["deployment"]
    assumed = " ".join(CFG["assumed"])
    for item in ("ELEMENTWISE", "2 sigmoid", "NO lower bound", "rank of 128", "scoring function is assumed",
                 "no per-head norm", "N(0, 0.01)", "used by no layer"):
        assert item in assumed, item


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] and 4097 <= eng["kv_pool_pages"] <= 6145
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 64
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 16384 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512
    assert int(env["APP_ENGINE_PREFILLWAVETOKENS"]) == eng["prefill_wave_tokens"] == 512
    assert env["APP_ENGINE_PREFIXCACHEENABLE"] == "auto"  # the first cell with the store on
    assert int(env["APP_ENGINE_PREFIXCACHESLOTS"]) == eng["prefix_cache_slots"] and 64 <= eng["prefix_cache_slots"] <= 96
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == eng["decode_block"] and eng["decode_block"] in (2, 3, 4)
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    ref = CFG["reference"]
    assert ref["prompt_tokens"] == [64, 96, 640, 2560] and ref["served_only_prompt_tokens"] == [1600, 3200]
    assert ref["decode_tokens"] == 8
    assert CFG["correct"]["kernel_paths"] == {"grouped_matmul": "compiled", "delta_step": "compiled"}
    grow = {(c["metric"], c.get("labels", {}).get("held")) for c in CFG["correct"]["counters_must_grow"]}
    assert grow == {("genai_engine_moe_pairs_total", "true"), ("genai_engine_state_slot_resets_total", None),
                    ("genai_engine_prefix_cache_hits_total", None), ("genai_engine_prefix_cache_tokens_reused_total", None),
                    ("genai_engine_prefix_state_restores_total", None)}
    from generativeaiexamples_tpu.models import solaropen2 as m

    mc, plan = adapter.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc) == 6_616_706_688
    rows = eng["max_batch_size"] + eng["prefix_cache_slots"]
    assert plan["fixed_state_bytes"] == rows * m.fixed_state_bytes_per_slot(mc) and eng["fixed_state_bytes_per_slot"] == 13_025_280
    assert plan["page_pool_bytes"] == eng["kv_pool_pages"] * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 4096
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9


# the per-layer entries ISSUE 44 names for the cell; a later PR may append more
GENERIC = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share", "extend_dispatch_dev_ms",
    "state_rows_mean", "page_attn_busy_share", "page_attn_pages_walked_mean", "moe_experts_hit_share",
    "moe_pairs_per_expert_mean", "grouped_matmul_busy_share", "stream_backlog_tokens_mean",
)
OWN = (
    "prefix_reused_token_share", "prefix_state_copy_roofline_share", "prefix_state_copy_device_share",
    "decode_step_roofline_share", "grouped_matmul_roofline_share", "delta_step_roofline_share", "page_attn_roofline_share",
)
# PR 40's six whole-window span metrics; the narrow one too: this family's ladder has two chunk widths (128, 512)
JOINED = ("decode_step_done_ms", "extend_wide_done_ms", "extend_narrow_done_ms", "extend_device_share",
          "device_starved_share", "device_hold_max_ms")




def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar-open2-250b-ep8-bf16", "chat_sessions", 1)
    assert len(cell["why"]) <= 200 and "saved KDA state" in cell["why"]
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    assert len(cfg["why"]) <= 200
    own = tuple(base + ".solaropen2" for base in OWN)  # an adapter's reader: a file under the suffixed name
    # found by name and cell: neither their count nor their place is pinned
    assert_cell_holds(manifest, CELL, GENERIC + own + JOINED + ("moe_tiles_used_share",))
    for name in GENERIC + own:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".json"))
    # every metric that moves what the cell reports lists its cells: none is left to every cell by default
    assert all("workloads" in e for e in manifest["per_layer"] if e["moves"] in ("out_tok_s", "itl_p995_ms"))


def test_manifest_entries_of_the_cell_found_by_name():
    assert_manifest_entries_of_the_cell(load(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_traffic_file_is_as_the_issue_gives_it():
    traffic = load(os.path.join(BENCH, "traffic", "chat_sessions.json"))
    assert (traffic["kind"], traffic["clients"], traffic["turns"]) == ("sessions", CFG["engine"]["max_batch_size"], 4)
    assert traffic["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1}
    assert traffic["question_bytes"] == [512, 1024, 1536] and traffic["max_tokens"] == [256, 512, 768]
    assert traffic["ramp"] == {"expected_request_s": 60.0, "cap_s": 60.0}
    assert traffic["trace_window_s"] == 2.5 and traffic["traced_run_window_s"] == 20.0
    # the worst case fits what a request may reserve: turn 4's prompt and its answer under max_seq_len
    assert 4 * (1536 + 256) + 600 + 768 < CFG["engine"]["max_seq_len"]


EARLIER = ("chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash", "doc_reason_gigachat35",
           "doc_reason_trinitymini")


@pytest.mark.parametrize("cell", EARLIER)
def test_the_earlier_cells_entries_are_untouched(cell):
    """What this PR appended changed no entry of the cells before it:
    against the parent commit where git has one, every entry is the
    parent's but for the cell's name at the END of a ``workloads`` list."""
    import subprocess

    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(cell) < names.index(CELL)
    shown = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, capture_output=True, text=True)
    if shown.returncode != 0 or CELL in shown.stdout:
        return
    parent = json.loads(shown.stdout)
    assert next(w for w in manifest["workloads"] if w["name"] == cell) == next(w for w in parent["workloads"] if w["name"] == cell)
    for group in ("per_layer", "end_to_end", "configs"):
        for e, now in zip(parent[group], manifest[group]):  # the parent's entries lead, in the parent's order
            assert now.get("workloads", [])[: len(e.get("workloads", []))] == e.get("workloads", [])
            assert set(now.get("workloads", [])) - set(e.get("workloads", [])) <= {CELL}
            assert {k: v for k, v in now.items() if k != "workloads"} == {k: v for k, v in e.items() if k != "workloads"}
