"""The GigaChat3.5 adapter (perfbench/arch/gigachat35.py): its plain
float32 reference against the engine at a tiny size that keeps the five
layers of the share, the control one precision down, the injected faults
that must each fail ``TOLERANCE``, its byte and operation counts against
hand values, its readers, its configuration file and its manifest entries."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import gigachat35 as giga
from tests.perfbench.manifest_entries import assert_cell_holds
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "gigachat3.5-432b-a28b-ep16-bf16.json")
CELL = "doc_reason_gigachat35"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# the same five layers and the same share at widths a CPU test can walk
TINY = dict(
    CFG, name="gigachat35-tiny-test", vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, n_routed_experts_held=2,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, rope_scaling=dict(CFG["rope_scaling"], original_max_position_embeddings=64),
    engine=dict(CFG["engine"], max_seq_len=256, kv_bytes_per_token=256),
    reference=dict(CFG["reference"], decode_tokens=5),
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    giga.register(TINY)
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
    served walks (one chunk; 39 tokens and one decode step; 150 tokens in
    three extend chunks, YaRN positions past the original context of
    64), greedy tokens through the engine, and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = reference.seeded_prompts([9, 40, 150], 250, seed=11)
    eng_logits = giga.engine_prefill_logits(engine, prompts, on_tpu=False)
    greedy = SamplingParams(temperature=0.0, max_tokens=5)
    tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full, giga.reference_logits(engine, TINY, full)


def test_engine_agrees_with_the_reference_through_prefill_extend_and_decode(compared):
    prompts, eng_logits, tokens, _, ref = compared
    out = reference.compare(prompts, list(eng_logits), tokens, ref, giga.TOLERANCE)
    assert out["ok"], out
    assert len(out["prefill_rel_err"]) == 3 and max(out["prefill_rel_err"]) < 1e-4
    assert out["decode_tokens_checked"] == 15 and out["decode_margin_max"] < 1e-4
    # only the compared positions carry logits: the head is not computed for the rest
    assert not ref[2][:140].any() and ref[2][149].any()


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    calls = []
    real = giga._served_logits
    try:
        giga._served_logits = lambda eng, prompts: calls.append(1) or real(eng, prompts)
        rows = giga.engine_prefill_logits(engine, compared[0][:2], on_tpu=False)
        assert not calls and all(isinstance(r, giga.Deferred) for r in rows)
        first = np.asarray(rows[0], np.float32)
        np.asarray(rows[1])
        assert calls == [1] and first.shape == (256,)
        np.testing.assert_allclose(first, np.asarray(compared[1][0]), rtol=1e-5, atol=1e-5)
    finally:
        giga._served_logits = real
        giga._PENDING.clear()


def _model():
    from generativeaiexamples_tpu.models import gigachat35 as m

    return m


def _drop_an_expert(eng, mp):
    mp.setattr(eng, "params", dict(eng.params, layers=[
        dict(lp, we_down=lp["we_down"].at[0].set(0.0)) if "we_down" in lp else lp for lp in eng.params["layers"]]))


def _no_rope_key(eng, mp):
    import jax.numpy as jnp

    mp.setattr(_model(), "rope", lambda x, positions, cfg: jnp.zeros_like(x))


def _value_off_its_columns(eng, mp):
    """The cached row's latent columns shifted by the RoPE key's width:
    what a value slice taken at the key's width reads."""
    import jax.numpy as jnp

    m = _model()
    real = m._mla_project

    def shifted(x, positions, lp, cfg):
        q_nope, q_rope, gate, row = real(x, positions, lp, cfg)
        R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        return q_nope, q_rope, gate, jnp.concatenate([jnp.roll(row[..., :R], dr, axis=-1), row[..., R:]], axis=-1)

    mp.setattr(m, "_mla_project", shifted)


def _no_output_gate(eng, mp):
    import jax.numpy as jnp

    m = _model()
    real = m._mla_output
    mp.setattr(m, "_mla_output", lambda o, gate, lp, cfg: real(o, jnp.ones_like(gate), lp, cfg))


def _w_for_one_plus_w(eng, mp):
    import jax
    import jax.numpy as jnp

    def gated_norm(x, w, g, eps, gate_scale):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return y * (w.astype(jnp.float32) * (gate_scale * jax.nn.sigmoid(g.astype(jnp.float32))))

    mp.setattr(_model(), "gated_norm", gated_norm)


def _decay_from_the_wrong_axis(eng, mp):
    """One scalar a head, read along the head axis backwards (head j
    decays as head 63 - j): the decay of another head's channel."""
    m = _model()
    real = m._gdn_inputs

    def flipped(x, conv_cat, lp, cfg):
        q, k, v, beta, g, z = real(x, conv_cat, lp, cfg)
        return q, k, v, beta, g[..., ::-1], z

    mp.setattr(m, "_gdn_inputs", flipped)


def _stale_state(eng, mp):
    import jax.numpy as jnp

    real = eng._family.extend_paged
    # every chunk is told it is not a row's first: the former tenant's state is carried on
    mp.setattr(eng, "_family", dataclasses.replace(
        eng._family, extend_paged=lambda params, cfg, caches, tokens, offsets, *rest, **kw: real(
            params, cfg, caches, tokens, jnp.maximum(offsets, 64), *rest, **kw)))


def _wrong_gate_scale(eng, mp):
    mp.setattr(eng, "model_config", dataclasses.replace(eng.model_config, routed_scaling_factor=1.0))


FAULTS = {
    "a_dropped_expert": _drop_an_expert,
    "a_wrong_gate_scale": _wrong_gate_scale,
    "the_rope_key_left_out_of_the_score": _no_rope_key,
    "the_value_read_off_its_columns": _value_off_its_columns,
    "a_missing_output_gate": _no_output_gate,
    "one_plus_w_read_as_w": _w_for_one_plus_w,
    "the_scalar_decay_from_the_wrong_axis": _decay_from_the_wrong_axis,
    "a_stale_state": _stale_state,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_injected_fault_fails_the_logits_comparison(engine, compared, fault, monkeypatch):
    """Each fault in the SERVED walks takes a compared logit past ``TOLERANCE``."""
    prompts, _, tokens, _, ref = compared
    FAULTS[fault](engine, monkeypatch)
    faulty = giga.engine_prefill_logits(engine, prompts, on_tpu=False)
    out = reference.compare(prompts, faulty, tokens, ref, giga.TOLERANCE)
    assert not out["ok"] and max(out["prefill_rel_err"]) > giga.TOLERANCE, (fault, out)


def test_the_control_one_precision_down_fails(engine, compared):
    prompts, _, _, full, ref = compared
    low = giga.reference_logits(engine, TINY, full, precision="bfloat16")
    err = [float(np.max(np.abs(a[len(p) - 1] - b[len(p) - 1])) / np.max(np.abs(b[len(p) - 1])))
           for a, b, p in zip(low, ref, prompts)]
    assert max(err) > giga.TOLERANCE, err


# --------------------------------------------------------------------------- #
# The adapter's contract, its bytes and its readers (no jax)


def test_adapter_contract_and_no_jax_at_import():
    import subprocess
    import sys

    assert arch.load(CFG, [os.path.join(ROOT, p) for p in ("perfbench", "tests/perfbench")]) is giga
    code = "import sys; import perfbench.arch.gigachat35; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))


def test_layers_served_and_the_model_configuration():
    assert giga.layer_kinds(CFG) == [("gdn", "dense"), ("mla", "sparse"), ("gdn", "sparse"), ("gdn", "sparse"), ("gdn", "sparse")]
    from generativeaiexamples_tpu.models import gigachat35 as m

    assert giga.model_config(CFG) == m.PRESETS["gigachat3.5-432b-a28b-ep16"]
    assert giga.model_config(TINY) == dataclasses.replace(m.PRESETS["gigachat35-debug"], max_seq_len=256)
    assert giga.softmax_scale(CFG) == pytest.approx(192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    assert giga.softmax_scale(CFG) == pytest.approx(giga.model_config(CFG).softmax_scale)
    np.testing.assert_allclose(giga.yarn_inv_freq(CFG), np.asarray(m.yarn_inv_freq(giga.model_config(CFG))), rtol=1e-6)
    inv, plain = giga.yarn_inv_freq(CFG), 100000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:15], plain[:15], rtol=1e-6)  # pairs that turn 32+ times in 32768 keep theirs
    np.testing.assert_allclose(inv[24:], plain[24:] / 8, rtol=1e-6)  # pairs that turn less than once: / factor
    assert np.all(np.diff(inv) < 0)


def test_byte_and_operation_counts_against_hand_values():
    D, Fm, V = 7168, 2048, 16032
    assert giga.expert_bytes(CFG) == 3 * D * Fm * 2 == 88_080_384
    assert giga.latent_row(CFG) == 640  # 512 + 64 padded to five lane tiles: what the pool allocates
    gdn = D * 16384 + D * (8192 + 128) + 8192 * D
    mla = D * (1536 + 8192 + 512 + 64) + 1536 * 64 * 192 + 2 * 64 * 128 * 512 + 8192 * D
    assert gdn + 4 * 16384 == 235_864_064 and mla == 159_842_304  # ISSUE 38: 235.9 M (with the taps) and 159.8 M
    fixed_bf16 = 4 * gdn + mla + 3 * D * 18432 + 4 * 3 * D * Fm + D * V
    fixed_f32 = 4 * (4 * 16384 + 128 + 128) + (1536 + 512) + 5 * 8 * D + 2 * D + 4 * (D * 256 + 256)
    assert giga.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    assert 3.5e9 < giga.fixed_weight_bytes(CFG) < 3.7e9  # ISSUE 38: 3.6 GB of mixer, dense-MLP, shared-expert and head weights
    state = 64 * 128 * 128 * 4 + 3 * 16384 * 2
    rows, ctx, hit = 64.0, 5000.0, 40.0
    want = giga.fixed_weight_bytes(CFG) + hit * 88_080_384 + rows * (2 * 4 * state + 5001 * 1280 + 2 * D)
    assert giga.decode_step_bytes(CFG, rows, ctx, hit) == pytest.approx(want)
    assert 9.5e9 < want < 10.0e9
    assert giga.expected_experts_hit(CFG, 64) == pytest.approx(4 * 16 * (1 - (1 - 8 / 256) ** 64))
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = giga.decode_step_floor_s(CFG, peaks, rows, ctx, hit)
    assert floor == pytest.approx(want / 819e9) and 0.0115 < floor < 0.0125  # bytes bind, not operations
    assert giga.decode_step_flops(CFG, rows, ctx) / 197e12 < floor / 3
    # the latent read of one step at 64 rows x 5,000 tokens (40 pages a row)
    nbytes, flops = giga.latent_read_bytes_and_flops(CFG, 64 * 40, 64 * 5000)
    assert nbytes == 64 * 40 * 128 * 1280 and flops == 2 * 64 * 64 * 5000 * (576 + 512)
    assert flops / 197e12 < nbytes / 819e9  # 121 FLOP a byte asked for, 106 as allocated: under the ridge of 240


def _ctx(spans, trace=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": giga}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 20.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 60, "state_rows": 60, "moe_pairs_held": 120, "moe_experts_hit": 40,
          "moe_experts_held": 64, "latent_tokens_read": 60 * 5000, "kv_pages_walked": 2400}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "moe_experts_hit": 64, "moe_experts_held": 64, "latent_tokens_read": 300000}
PARENT_SPANS = [{"kind": "decode", "rows": 60}]


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=50, moe_pairs_held=150), CHUNK])
    assert _read("moe_experts_hit_share", ctx) == pytest.approx(100 * 90 / 128)
    assert _read("moe_pairs_per_expert_mean", ctx) == pytest.approx(270 / 90)
    assert _read("latent_tokens_read_mean", ctx) == pytest.approx(300000)
    assert _read("state_rows_mean", ctx) == 60
    share = _read("decode_step_roofline_share.gigachat35", ctx)
    want = giga.decode_step_floor_s(CFG, ctx["peaks"], 60, 5000, 45) / 0.020 * 100
    assert share == pytest.approx(want) and 50 < share < 70
    parent = _ctx(PARENT_SPANS)
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean", "latent_tokens_read_mean",
                 "decode_step_roofline_share.gigachat35"):
        assert _read(name, parent) is None


def test_kernel_roofline_readers_count_what_the_trace_saw_and_stay_under_the_peak():
    trace = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
             "ops_self_s": {"grouped_matmul_gate_up": 0.25, "grouped_matmul_down": 0.15, "latent_attention_dense": 0.04, "fusion": 1.0},
             "modules": {"jit_decode_paged": {"count": 30, "total_s": 1.8}, "jit_extend_batch_paged": {"count": 8, "total_s": 0.5}}}
    ctx = _ctx([DECODE, CHUNK], trace)
    block = CFG["engine"]["decode_block"]  # steps a decode program
    hits = 30 * block * 40 + 8 * 64
    got = _read("grouped_matmul_roofline_share.gigachat35", ctx)
    assert got == pytest.approx(100 * hits * 88_080_384 / 819e9 / 0.4) and got < 100
    # the latent read: the LARGER of the byte time and the operation time, the row as allocated (640 wide)
    steps = 30 * block
    byte_s = steps * 2400 * 128 * 1280 / 819e9
    flop_s = steps * 2 * 64 * 300000 * 1088 / 197e12
    got = _read("latent_attn_roofline_share.gigachat35", ctx)
    assert byte_s > flop_s and got == pytest.approx(100 * byte_s / 0.04) and got < 100
    fast = dict(ctx, peaks=dict(ctx["peaks"], hbm_bytes_per_s=1e15))  # where bytes cost nothing, operations bind
    assert _read("latent_attn_roofline_share.gigachat35", fast) == pytest.approx(100 * flop_s / 0.04)
    assert _read("latent_attn_busy_share", ctx) == pytest.approx(100 * 0.04 / 2.4)
    assert _read("grouped_matmul_busy_share", ctx) == pytest.approx(100 * 0.4 / 2.4)
    for name in ("grouped_matmul_roofline_share.gigachat35", "latent_attn_roofline_share.gigachat35"):
        assert _read(name, _ctx([DECODE], None)) is None  # an untraced run
        assert _read(name, _ctx(PARENT_SPANS, dict(trace, ops_self_s={"fusion": 1.0}))) is None  # the parent


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "GigaChat3.5-432B-A28B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in ("vocab_size", "num_nextn_predict_layers"):
            assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "num_key_value_heads": 64, "num_hidden_layers": 40, "n_routed_experts": 256, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "linear_num_key_heads": 32, "linear_num_value_heads": 64,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6,
        "routed_scaling_factor": 2.5, "swiglu_limit": 10, "first_k_dense_replace": 3, "n_group": 1, "topk_group": 1,
        "max_position_embeddings": 262144, "rope_theta": 100000, "layernorm_gating_weight": 2,
        "linear_sigmoid_gate_scale": 2,
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                                   "original_max_position_embeddings": 32768, "type": "yarn"}
    assert CFG["full_attention_layers"] == list(range(3, 40, 4))
    # the cut: depth, experts held, vocabulary, MTP; no width among them
    assert CFG["reduced"] == ["layers", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    width_words = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "experts_per")
    for key in CFG["reduced"]:
        assert not any(w in key for w in width_words) and not key.endswith(("_dim", "_rank")) and key in CFG["reduced_how"]
    assert CFG["layers"] == len(CFG["layers_served"]) == 5 and CFG["layers_served"] == [0, 3, 4, 5, 6]
    assert CFG["vocab_size"] == 128256 // 8 and CFG["vocab_size_published"] == 128256
    assert CFG["n_routed_experts_held"] == 256 // 16 and CFG["experts_first"] == 0 and CFG["num_nextn_predict_layers"] == 0
    assert CFG["chips_sharing_a_layer"] == 16 and "16 chips share each layer" in CFG["deployment"] and len(CFG["assumed"]) >= 8


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
    grow = {c["metric"] for c in CFG["correct"]["counters_must_grow"]}
    assert grow == {"genai_engine_moe_pairs_total", "genai_engine_latent_read_tokens_total",
                    "genai_engine_state_slot_resets_total"}
    from generativeaiexamples_tpu.models import gigachat35 as m

    mc, plan = giga.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc)
    assert plan["fixed_state_bytes"] == 64 * m.fixed_state_bytes_per_slot(mc) and eng["fixed_state_bytes_per_slot"] == 17_170_432
    assert plan["page_pool_bytes"] == 4097 * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 1280
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9


# the cell's per-layer metrics as PR 38 brought them (base names since PR 56), and what PR 56 added to the cell
PER_LAYER = (
    "decode_rows_mean", "decode_step_dev_ms", "decode_step_roofline_share.gigachat35",
    "tpot_chat_p50_ms", "device_idle_share", "stream_backlog_tokens_mean",
    "state_rows_mean", "extend_dispatch_dev_ms", "moe_experts_hit_share",
    "moe_pairs_per_expert_mean", "grouped_matmul_busy_share",
    "grouped_matmul_roofline_share.gigachat35", "latent_attn_busy_share",
    "latent_attn_roofline_share.gigachat35", "latent_tokens_read_mean",
)
# PR 40's whole-window span metrics (not the narrow one: one chunk width) and the share of planned tiles used
JOINED = ("decode_step_done_ms", "extend_wide_done_ms", "extend_device_share", "device_starved_share",
          "device_hold_max_ms", "moe_tiles_used_share")


def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("gigachat3.5-432b-a28b-ep16-bf16", "doc_reason", 1)
    assert len(cell["why"]) <= 200
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    assert_cell_holds(manifest, CELL, PER_LAYER + JOINED)


def test_manifest_entries_of_the_cell_found_by_name():
    assert_manifest_entries_of_the_cell(load(os.path.join(ROOT, "BENCHMARK.json")))
    traffic = load(os.path.join(BENCH, "traffic", "doc_reason.json"))
    assert traffic["clients"] == CFG["engine"]["max_batch_size"] and traffic["question_bytes"] == [2048, 3072, 4096]
