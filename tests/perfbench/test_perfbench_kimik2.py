"""The Kimi-K2.5 adapter (perfbench/arch/kimik2.py): its plain float32
reference against the engine at a tiny size that keeps the dense layer
and two expert layers, with the prefix store ON so that the
``served_only`` prompt enters through shared latent pages, the control
one precision down that must fail ``TOLERANCE``, its byte and operation
counts against hand values, its readers on synthetic spans, its
configuration file and its manifest entries (found by name: entries a
later PR appends are none of this file's business)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import kimik2 as adapter
from tests.perfbench.manifest_entries import assert_cell_holds
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "kimi-k2.5-ep32-bf16.json")
CELL = "agent_sessions_kimik25"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# one dense and two expert layers at widths a CPU test can walk: the sizes of ``kimik2-debug``;
# YaRN's original context is 64, so every prompt but the first reaches the slowed pairs
TINY = dict(
    CFG, name="kimik2-tiny-test", vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=6, layers_served=[0, 1, 2], layers=3, n_routed_experts=16, num_experts_per_tok=4,
    n_routed_experts_held=2, num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling=dict(CFG["rope_scaling"], original_max_position_embeddings=64),
    engine=dict(CFG["engine"], max_seq_len=512, kv_bytes_per_token=3 * 128 * 2),
    reference=dict(CFG["reference"], prompt_tokens=[9, 40, 150], served_only_prompt_tokens=[330], decode_tokens=5),
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
    for the served-only prompt, which the adapter served once before,
    and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = harness_prompts(engine)
    before = counters()
    eng_logits = list(adapter.engine_prefill_logits(engine, prompts[:3], on_tpu=False)) + [None]
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
    assert out["decode_tokens_checked"] == 20 and all(len(t) == 5 for t in tokens)


def test_the_served_only_prompt_enters_through_shared_latent_pages(compared):
    """The adapter's priming left an entry at 320 tokens (the deepest
    chunk boundary under the prompt's last token) that holds 20 pages of
    every pool; the harness's own decode of the prompt mapped them and
    prefilled the ten-token tail. No state row is saved or restored:
    the family has pages only."""
    _, _, _, _, _, grew = compared
    primed, served = grew["primed"], grew["served"]
    assert primed.get("genai_engine_prefix_cache_hits_total", 0) == 0
    assert served["genai_engine_prefix_cache_hits_total"] == 1
    assert served["genai_engine_prefix_cache_tokens_reused_total"] == 320
    assert served["genai_engine_kv_prefix_pages_mapped_total"] == 320 // 16
    for name in ("genai_engine_prefix_state_saves_total", "genai_engine_prefix_state_restores_total",
                 "genai_engine_state_slot_resets_total"):
        assert primed.get(name, 0) == 0 and served.get(name, 0) == 0
    assert "genai_engine_prefix_shared_pages_in_use" in counters()


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    prompts = compared[0]
    rows = adapter.engine_prefill_logits(engine, prompts[:1], on_tpu=False)
    assert adapter._PENDING and isinstance(rows[0], adapter.Deferred)
    assert np.asarray(rows[0]).shape == (TINY["vocab_size"],)
    adapter._PENDING.clear()


def test_the_control_one_precision_down_fails(engine, compared):
    """All-bfloat16 products, sums, norms, softmax and residual: past ``TOLERANCE``."""
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
    code = "import sys; import perfbench.arch.kimik2; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    with open(adapter.__file__, encoding="utf-8") as fh:
        text = fh.read()
    # the registration alone touches the program's model; the reference imports nothing of it
    assert text.count("generativeaiexamples_tpu.models") == 2 and "models.kimik2 import KimiK2Config" in text


def test_layers_served_and_the_model_configuration():
    assert adapter.layer_kinds(CFG) == ["dense", "sparse", "sparse", "sparse", "sparse"]
    from generativeaiexamples_tpu.models import kimik2 as m

    assert adapter.model_config(CFG) == m.PRESETS["kimi-k2.5-ep32"]
    assert adapter.model_config(TINY) == dataclasses.replace(m.PRESETS["kimik2-debug"], max_seq_len=512)
    assert adapter.expert_keys(CFG) == {"swiglu_limit": float("inf"), "num_experts_per_tok": 8,
                                        "routed_scaling_factor": 2.827, "experts_first": 0, "n_routed_experts_held": 12}
    assert adapter.softmax_scale(CFG) == pytest.approx(192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_byte_and_operation_counts_against_hand_values():
    D, V = 7168, 20480
    assert adapter.expert_bytes(CFG) == 3 * D * 2048 * 2 == 88_080_384
    assert adapter.latent_row(CFG) == 640 and adapter.latent_page_bytes(CFG) == 163_840
    mla = D * 2112 + 1536 * 64 * 192 + 2 * 64 * 128 * 512 + 8192 * D
    assert mla + 1536 + 512 == 101_124_096  # ISSUE 49's attention, its two inner norms included
    fixed_bf16 = 5 * mla + 3 * D * 18432 + 4 * 3 * D * 2048 + D * V
    fixed_f32 = 5 * (1536 + 512 + 2 * D) + D + 4 * (D * 384 + 384)
    assert adapter.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    # everything the plan holds outside the routed experts and the embedding, the float32 leaves at their width
    assert adapter.fixed_weight_bytes(CFG) + 4 * 12 * 88_080_384 == pytest.approx(
        CFG["memory_plan"]["weights_bytes"] - 2 * D * V + 2 * fixed_f32)
    rows, ctx, hit = 32.0, 8000.0, 23.0
    want = adapter.fixed_weight_bytes(CFG) + hit * 88_080_384 + rows * (5 * 8001 * 1280 + 2 * D)
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit) == pytest.approx(want)
    assert 6.0e9 < want < 6.3e9  # ISSUE 49: ~4.5 GB of weights and 1.6 GB of latent pages a step
    assert adapter.expected_experts_hit(CFG, 32) == pytest.approx(4 * 12 * (1 - (383 / 384) ** 256))
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = adapter.decode_step_floor_s(CFG, peaks, rows, ctx, hit)
    assert floor == pytest.approx(want / 819e9) and 0.007 < floor < 0.008  # bytes bind, not operations
    assert adapter.decode_step_flops(CFG, rows, ctx) / 197e12 < floor / 3
    nbytes, flops = adapter.latent_read_bytes_and_flops(CFG, 10, 1000)
    assert nbytes == 10 * 163_840 and flops == 2 * 64 * 1000 * (576 + 512)


def _ctx(spans, trace=None, before=None, after=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": adapter,
           "metrics_before": before or {}, "metrics_after": after or {}}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 14.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 30, "latent_layers": 5, "moe_pairs_held": 30, "moe_pairs_absent": 930,
          "moe_experts_hit": 23, "moe_experts_held": 48, "latent_tokens_read": 30 * 8001, "kv_pages_walked": 30 * 63 + 2}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "latent_layers": 5, "moe_experts_hit": 48, "moe_experts_held": 48,
         "latent_tokens_read": 512 * 9000, "prefix_depth_tokens": 9216}
PARENT_SPANS = [{"kind": "decode", "rows": 30}]
TRACE = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
         "ops_self_s": {"grouped_matmul_gate_up": 0.3, "grouped_matmul_down": 0.2, "dense_latent_attention": 0.45,
                        "fusion": 1.0},
         "modules": {"jit_decode_paged": {"count": 40, "total_s": 1.1}, "jit_extend_batch_paged": {"count": 12, "total_s": 1.2}}}


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=25, moe_pairs_held=34), CHUNK, dict(CHUNK, prefix_depth_tokens=4096),
                {k: v for k, v in CHUNK.items() if k != "prefix_depth_tokens"}])
    assert _read("moe_experts_hit_share", ctx) == pytest.approx(100 * 48 / 96)
    assert _read("moe_pairs_per_expert_mean", ctx) == pytest.approx(64 / 48)
    assert _read("latent_tokens_read_mean", ctx) == 30 * 8001
    assert _read("decode_rows_mean", ctx) == 30
    assert _read("extend_prefix_depth_mean", ctx) == pytest.approx((9216 + 4096) / 2)  # the chunks after a hit alone
    share = _read("decode_step_roofline_share.kimik25", ctx)
    want = adapter.decode_step_floor_s(CFG, ctx["peaks"], 30, 8000, 24) / 0.014 * 100
    assert share == pytest.approx(want) and 40 < share < 100
    parent = _ctx(PARENT_SPANS)
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean", "latent_tokens_read_mean",
                 "decode_step_roofline_share.kimik25", "extend_prefix_depth_mean"):
        assert _read(name, parent) is None


def test_kernel_roofline_readers_count_what_the_trace_saw_and_stay_under_the_peak():
    ctx = _ctx([DECODE, CHUNK], TRACE)
    steps = 40 * CFG["engine"]["decode_block"]
    got = _read("grouped_matmul_roofline_share.kimik25", ctx)
    assert got == pytest.approx(100 * (steps * 23 + 12 * 48) * 88_080_384 / 819e9 / 0.5) and got < 100
    got = _read("latent_attn_roofline_share.kimik25", ctx)
    by_bytes = steps * (30 * 63 + 2) * 5 * 163_840 / 819e9
    by_ops = 2 * 64 * steps * 30 * 8001 * 5 * 1088 / 197e12
    assert by_bytes > by_ops and got == pytest.approx(100 * by_bytes / 0.45) and got < 100
    assert _read("latent_attn_busy_share", ctx) == pytest.approx(100 * 0.45 / 2.4)
    assert _read("grouped_matmul_busy_share", ctx) == pytest.approx(100 * 0.5 / 2.4)
    bare = dict(TRACE, ops_self_s={"fusion": 1.0}, modules={"jit_decode_paged": {"count": 40, "total_s": 1.1}})
    for name in ("grouped_matmul_roofline_share", "latent_attn_roofline_share"):
        assert _read(name + ".kimik25", _ctx([DECODE], None)) is None  # an untraced run
        assert _read(name + ".kimik25", _ctx(PARENT_SPANS, bare)) is None  # a program without the kernels or the fields
        assert _read(name + ".kimik25", _ctx(PARENT_SPANS, TRACE)) is None  # kernels but no span field of this family


def test_the_reused_share_reads_the_stores_counters():
    key = lambda name: (name, frozenset())  # noqa: E731
    before = {key("genai_engine_prefix_cache_tokens_reused_total"): 1000.0, key("genai_engine_prefill_tokens_total"): 5000.0,
              key("genai_engine_prefix_shared_pages_in_use"): 0.0}
    after = {key("genai_engine_prefix_cache_tokens_reused_total"): 71000.0, key("genai_engine_prefill_tokens_total"): 35000.0,
             key("genai_engine_prefix_shared_pages_in_use"): 900.0}
    assert _read("prefix_reused_token_share.kimik25", _ctx([], None, before, after)) == pytest.approx(70.0)
    # a program without the gauge (the parent), and a window in which nothing was reused (the store off)
    parent = {k: v for k, v in after.items() if "shared_pages" not in k[0]}
    assert _read("prefix_reused_token_share.kimik25", _ctx([], None, before, parent)) is None
    assert _read("prefix_reused_token_share.kimik25", _ctx([], None, after, after)) is None


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Kimi-K2.5")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "vocab_size":  # reduced: the published number stands beside it
            assert CFG["vocab_size_published"] == value and key in CFG["reduced"]
        else:
            assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "num_key_value_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "num_hidden_layers": 61, "n_routed_experts": 384,
        "num_experts_per_tok": 8, "n_shared_experts": 1, "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.827, "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "rope_theta": 50000, "model_type": "kimi_k2",
        "max_position_embeddings": 262144, "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "attention_bias": False, "hidden_act": "silu", "vocab_size_published": 163840,
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 4096, "type": "yarn"}
    # the cut: depth, the experts held, the vocabulary's share; no width
    assert CFG["reduced"] == ["layers", "n_routed_experts", "vocab_size"] and set(CFG["reduced_how"]) == set(CFG["reduced"])
    widths = ("hidden", "intermediate", "_dim", "_rank", "head", "per_tok")
    assert not any(w in key for key in CFG["reduced"] for w in widths)
    assert CFG["layers"] == len(CFG["layers_served"]) == 5 and CFG["layers_served"] == [0, 1, 2, 3, 4]
    assert CFG["n_routed_experts_held"] == 12 and CFG["experts_first"] == 0 and CFG["vocab_size"] == 163840 // 8
    assert CFG["chips_sharing_a_layer"] == 32 and "32 chips share each layer" in CFG["deployment"]
    assert "21.3 pairs" in CFG["deployment"] and "0.67" in CFG["deployment"]
    assumed = " ".join(CFG["assumed"])
    for item in ("vision tower", "NOT served", "pairs 2i, 2i+1", "N(0, 0.1)", "no clamp", "no output gate",
                 "character-level tokenizer", "no verify walk", "0.14468"):
        assert item in assumed, item


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] and 4097 <= eng["kv_pool_pages"] <= 6145
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 32
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 24576 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512
    assert int(env["APP_ENGINE_PREFILLWAVETOKENS"]) == eng["prefill_wave_tokens"] == 512  # one row a wave
    assert env["APP_ENGINE_PREFIXCACHEENABLE"] == "auto"
    assert int(env["APP_ENGINE_PREFIXCACHESLOTS"]) == eng["prefix_cache_slots"] == 96
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == eng["decode_block"] and eng["decode_block"] in (2, 3, 4)
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    ref = CFG["reference"]
    assert ref["prompt_tokens"] == [64, 96, 640, 2560] and ref["served_only_prompt_tokens"] == [4352]
    assert ref["decode_tokens"] == 8
    assert CFG["correct"]["kernel_paths"] == {"grouped_matmul": "compiled"}
    grow = {(c["metric"], c.get("labels", {}).get("held")) for c in CFG["correct"]["counters_must_grow"]}
    assert grow == {("genai_engine_moe_pairs_total", "true"), ("genai_engine_latent_read_tokens_total", None),
                    ("genai_engine_prefix_cache_hits_total", None), ("genai_engine_prefix_cache_tokens_reused_total", None)}
    from generativeaiexamples_tpu.models import kimik2 as m

    mc, plan = adapter.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc) == 2 * 3_496_763_904
    assert plan["fixed_state_bytes"] == 0 == eng["fixed_state_bytes_per_slot"]
    assert plan["page_pool_bytes"] == eng["kv_pool_pages"] * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 6400
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9
    # 32 rows at the traffic's worst case fit the pool with room for what the store retains
    worst = -(-(6 * 3072 + 5 * 256 + 700 + 384) // 128) + 1
    assert 32 * worst < eng["kv_pool_pages"] and worst * 128 < eng["max_seq_len"]
    for key in ("rehearsal_compile", "measured_peak"):
        assert "TO BE FILLED" not in plan[key]
    assert "TO BE FILLED" not in CFG["engine_how"]


# the per-layer entries ISSUE 49 names for the cell; a later PR may append more
GENERIC = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share", "extend_dispatch_dev_ms",
    "stream_backlog_tokens_mean", "moe_experts_hit_share", "moe_pairs_per_expert_mean", "grouped_matmul_busy_share",
    "latent_attn_busy_share", "latent_tokens_read_mean",
)
OWN = ("latent_attn_roofline_share", "grouped_matmul_roofline_share", "decode_step_roofline_share",
       "prefix_reused_token_share")
DATA = ("extend_prefix_depth_mean",)  # a data file under its base name: reader ``span_mean``
JOINED = ("decode_step_done_ms", "extend_wide_done_ms", "extend_narrow_done_ms", "extend_device_share",
          "device_starved_share", "device_hold_max_ms")




def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("kimi-k2.5-ep32-bf16", "agent_sessions", 1)
    assert len(cell["why"]) <= 200 and "shared latent pages" in cell["why"] and "deployment 21.3" in cell["why"]
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    assert len(cfg["why"]) <= 200
    own = tuple(base + ".kimik25" for base in OWN)  # an adapter's reader: a file under the suffixed name
    # found by name and cell: neither their count nor their place is pinned
    assert_cell_holds(manifest, CELL, GENERIC + own + DATA + JOINED + ("moe_tiles_used_share",))
    for name in GENERIC + own + DATA:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".json"))
    assert _metric("extend_prefix_depth_mean") == {
        "name": "extend_prefix_depth_mean", "reader": "span_mean",
        "params": {"kind": "prefill_chunk", "field": "prefix_depth_tokens"}}
    assert len(manifest["workloads"]) >= 7 and all(w["chips"] == 1 for w in manifest["workloads"])
    # every metric that moves what the cell reports lists its cells: none is left to every cell by default
    assert all("workloads" in e for e in manifest["per_layer"] if e["moves"] in ("out_tok_s", "itl_p995_ms"))


def test_manifest_entries_of_the_cell_found_by_name():
    assert_manifest_entries_of_the_cell(load(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_traffic_file_is_as_the_issue_gives_it():
    traffic = load(os.path.join(BENCH, "traffic", "agent_sessions.json"))
    assert (traffic["kind"], traffic["clients"], traffic["turns"]) == ("sessions", CFG["engine"]["max_batch_size"], 6)
    assert traffic["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1}
    assert traffic["question_bytes"] == [1024, 2048, 3072] and traffic["max_tokens"] == [128, 256, 384]
    assert traffic["ramp"]["cap_s"] == 60.0 and 40.0 <= traffic["ramp"]["expected_request_s"] <= 60.0
    assert traffic["trace_window_s"] == 2.5 and traffic["traced_run_window_s"] == 20.0


EARLIER = ("chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash", "doc_reason_gigachat35",
           "doc_reason_trinitymini", "chat_sessions_solaropen2")


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
            if "workloads" in e and now.get("workloads") != e["workloads"]:
                assert now["workloads"] == e["workloads"] + [CELL] and {k: v for k, v in now.items() if k != "workloads"} == \
                    {k: v for k, v in e.items() if k != "workloads"}
            else:
                assert now == e
