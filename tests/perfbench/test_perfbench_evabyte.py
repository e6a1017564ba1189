"""The EvaByte adapter (perfbench/arch/evabyte.py): its plain float32
reference against the engine at a tiny size that closes windows inside a
prompt and while decoding, the served rows over ALL output heads held
beside the harness's own comparison, the wrong forms that must each fail
``TOLERANCE``, its byte and operation counts against hand values, its
readers on fixture spans and a fixture trace, its configuration file and
its manifest entries (found by name and cell: entries a later PR appends
are none of this file's business)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import evabyte as adapter
from tests.perfbench.manifest_entries import assert_cell_holds
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "evabyte-6.5b-pp4-bf16.json")
CELL = "doc_bytes_evabyte"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# two layers at widths a CPU test can walk: 4 heads of 16, a window of 32, chunks of 4, 40 ids, 2 output heads
TINY = dict(
    CFG, name="evabyte-tiny-test", vocab_size=40, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
    layers_served=[0, 1], layers=2, num_attention_heads=4, num_key_value_heads=4, chunk_size=4, window_size=32,
    num_pred_heads=2, engine=dict(CFG["engine"], max_seq_len=256),
    reference=dict(CFG["reference"], prompt_tokens=[9, 40, 62, 150], served_only_prompt_tokens=[], decode_tokens=5),
)
PEAKS = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    adapter.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=2, max_seq_len=256,
        prefill_chunk=32, page_size=8, decode_block=4, prefix_cache_enable="off",
        dtype="float32", paged_kernel="interpret",
    ))
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def compared(engine):
    """As the launcher compares on the chip: last-position logits of the
    served walks (9 tokens in one chunk; 39 and a decode step inside the
    second window; 61 and a decode step whose greedy tokens then CLOSE the
    second window; 149 in five chunks and a step four windows deep),
    greedy tokens through the engine, and the reference's logits."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = reference.seeded_prompts(TINY["reference"]["prompt_tokens"], 40, seed=11)
    eng_logits = adapter.engine_prefill_logits(engine, prompts, on_tpu=False)
    greedy = SamplingParams(temperature=0.0, max_tokens=5)
    tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full, adapter.reference_logits(engine, TINY, full)


def test_engine_agrees_with_the_reference_through_extend_decode_and_a_closing_window(engine, compared):
    prompts, eng_logits, tokens, _, ref = compared
    assert engine._family_kernels == {"eva_read": "interpret"}
    out = reference.compare(prompts, list(eng_logits), tokens, ref, adapter.TOLERANCE)
    assert out["ok"], out
    # float32 walks against a float32 reference, summaries kept in bfloat16 (tests/test_evabyte.py TOL)
    assert len(out["prefill_rel_err"]) == 4 and max(out["prefill_rel_err"]) < 4e-3
    assert out["decode_tokens_checked"] == 20 and out["decode_margin_max"] < 4e-3
    # what the harness compares is the next byte's head; all the heads were held inside the adapter
    assert all(r.shape[1] == 40 for r in ref) and all(np.asarray(e).shape == (40,) for e in eng_logits)
    assert len(adapter._SERVED_ALL) == 4 and all(row.shape == (80,) for row in adapter._SERVED_ALL)
    # only the compared positions carry logits: the head is not computed for the rest
    assert not ref[3][:140].any() and ref[3][149].any()


def test_the_other_output_heads_are_held_to_the_same_limit_inside_the_adapter(engine, compared, monkeypatch):
    """``reference.compare`` sees 320 logits a row; a served row whose SECOND head is off must still end the run
    ``correct: false``: ``reference_logits`` raises, which the launcher reports as the comparison's error."""
    prompts, _, _, full, _ = compared
    real = adapter._served_logits

    def second_head_off(eng, ps):
        rows = real(eng, ps)
        rows[2] = rows[2].copy()
        rows[2][40:] += 0.5
        return rows

    monkeypatch.setattr(adapter, "_served_logits", second_head_off)
    rows = adapter.engine_prefill_logits(engine, prompts, on_tpu=False)
    with pytest.raises(RuntimeError, match="all output heads"):
        adapter.reference_logits(engine, TINY, full)
    np.testing.assert_allclose(np.asarray(rows[2]), np.asarray(compared[1][2]), atol=1e-5)  # head 0 was sound
    assert adapter.all_heads_rel_err([np.ones((3, 4))], [3], [np.array([1, 1, 1, 1.5])]) == [0.5]


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    calls = []
    real = adapter._served_logits
    try:
        adapter._served_logits = lambda eng, prompts: calls.append(1) or real(eng, prompts)
        rows = adapter.engine_prefill_logits(engine, compared[0][:2], on_tpu=False)
        assert not calls and all(isinstance(r, adapter.Deferred) for r in rows)
        first = np.asarray(rows[0], np.float32)
        np.asarray(rows[1])
        assert calls == [1] and first.shape == (40,)
        np.testing.assert_allclose(first, np.asarray(compared[1][0]), rtol=1e-5, atol=1e-5)
    finally:
        adapter._served_logits = real
        adapter._PENDING.clear()


@pytest.mark.parametrize("fault", ["no_ksq", "two_softmax"])
def test_a_wrong_form_of_the_layer_fails_the_comparison(engine, compared, fault):
    """The reference with a wrong form planted is not what the engine serves: the comparison fails, by the
    prompts past one window and not by the one inside it."""
    prompts, eng_logits, tokens, full, _ = compared
    host = adapter._shared._host
    params = engine.params
    wrong = adapter.forward(full, TINY, host(params["embed"]), lambda l: host(params["layers"][l]),
                            (host(params["final_norm"]), host(params["head"])), positions=6, fault=fault)
    out = reference.compare(prompts, list(eng_logits), tokens, [r[:, :40] for r in wrong], adapter.TOLERANCE)
    assert not out["ok"] and out["prefill_rel_err"][0] < 4e-3 and max(out["prefill_rel_err"][1:]) > adapter.TOLERANCE


def test_adapter_contract_and_no_jax_at_import():
    import subprocess
    import sys

    assert arch.load(CFG, [os.path.join(ROOT, p) for p in ("perfbench", "tests/perfbench")]) is adapter
    code = "import sys; import perfbench.arch.evabyte; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    with open(adapter.__file__, encoding="utf-8") as fh:
        text = fh.read()
    # the registration and the served walks alone touch the program's model; the reference imports none of it
    assert "models.evabyte import EvaByteConfig" in text and "TODO" not in text
    assert 0 < adapter.TOLERANCE < 0.1


def test_the_model_configuration_is_the_published_stage():
    from generativeaiexamples_tpu.models import evabyte as m

    assert adapter.model_config(CFG) == m.PRESETS["evabyte-6.5b-pp4"]
    assert adapter.model_config(TINY) == dataclasses.replace(
        m.PRESETS["evabyte-debug"], max_seq_len=256, layers_served=(0, 1))


def test_byte_and_operation_counts_against_hand_values():
    D, F = 4096, 11008
    layer = 4 * D * D + 3 * D * F
    assert layer + 2 * D + 2 * 32 * 128 == 202_391_552  # ISSUE 57: a layer's parameters
    weights = 2.0 * (8 * layer + D * 320) + 4.0 * (8 * 4 * D + D)
    assert adapter.weight_bytes(CFG) == weights and 3.23e9 < weights < 3.25e9  # less the 7 heads a step does not read
    row = 2 * 2 * D  # a K and a V row over 32 heads of 128, bfloat16: 16,384 B
    assert adapter.rows_read(CFG, 12000) == (12000 % 2048 + 1, 5 * 128) == (1761, 640)
    assert adapter.read_bytes_and_flops(CFG, 1000, 500) == (1500 * row, 2.0 * 2.0 * 1500 * D)
    # point one: 24 rows at 12,000 cached tokens (five closed windows behind, 1,761 exact keys)
    want = weights + 24 * 8 * (1761 + 640) * row + 24 * (8 * row + 2 * D)
    assert adapter.decode_step_bytes(CFG, 24, 12000) == pytest.approx(want)
    assert adapter.decode_step_bytes(CFG, 24, 0.0, 24 * 8 * 1761, 24 * 8 * 640) == pytest.approx(want)
    assert adapter.decode_step_floor_s(CFG, PEAKS, 24, 12000) == pytest.approx(want / 819e9)
    assert 0.0130 < want / 819e9 < 0.0134  # bytes bind: 10.8 GB a step
    flops = 2.0 * 24 * (8 * layer + D * 320) + 4.0 * 24 * 8 * 2401 * D
    assert adapter.decode_step_flops(CFG, 24, 12000) == pytest.approx(flops) and flops / 197e12 < want / 819e9 / 20
    # point two: ISSUE 57's mean row (1,000 exact keys, 800 summaries a layer): the read is ~63 % of the bytes
    want = weights + 24 * 8 * 1800 * row + 24 * (8 * row + 2 * D)
    assert adapter.decode_step_floor_s(CFG, PEAKS, 24, 0.0, 24 * 8 * 1000, 24 * 8 * 800) == pytest.approx(want / 819e9)
    assert 0.0108 < want / 819e9 < 0.0112 and 0.62 < 24 * 8 * 1800 * row / want < 0.65
    # one row at position 0: the weights and one key a layer
    assert adapter.decode_step_bytes(CFG, 1, 0) == pytest.approx(weights + 8 * row + 8 * row + 2 * D)


DECODE = {"kind": "decode", "rows": 24, "state_rows": 24, "eva_layers": 8, "eva_window": 2048, "eva_chunk": 16,
          "eva_window_tokens_read": 24 * 8 * 1000, "eva_summaries_read": 24 * 8 * 768,
          "eva_summaries_written": 16, "eva_windows_closed": 0}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "eva_layers": 8, "eva_window_tokens_read": 8 * 512 * 800,
         "eva_summaries_read": 8 * 512 * 512, "eva_summaries_written": 8 * 32, "eva_windows_closed": 0}
PARENT_SPANS = [{"kind": "decode", "rows": 24}]
TRACE = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
         "ops_self_s": {"eva_decode_read": 0.9, "fusion": 1.0, "convolution_fusion": 0.5},
         "modules": {"jit_decode_paged": {"count": 70, "total_s": 2.0}, "jit_extend_batch_paged": {"count": 8, "total_s": 0.4}}}


def _ctx(spans, trace=None):
    ctx = {"spans": spans, "config": CFG, "peaks": PEAKS, "trace": trace, "adapter": adapter}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 14.0}[name]
    return ctx


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


OWN = ("eva_window_tokens_read_mean", "eva_summaries_read_mean", "eva_summary_rows_share", "eva_read_busy_share",
       "eva_read_roofline_share", "decode_step_roofline_share.evabyte")


def test_the_six_metric_files_read_a_fixture_span_and_trace_and_nothing_on_a_parent():
    half = dict(DECODE, rows=12, eva_window_tokens_read=12 * 8 * 500, eva_summaries_read=12 * 8 * 256)
    ctx = _ctx([DECODE, half, CHUNK], TRACE)
    # a decode row's reads a step a layer (the chunk's span is not a decode row's)
    assert _read("eva_window_tokens_read_mean", ctx) == pytest.approx((1000 + 500) / 2)
    assert _read("eva_summaries_read_mean", ctx) == pytest.approx((768 + 256) / 2)
    window, summaries = 24 * 8 * 1000 + 12 * 8 * 500, 24 * 8 * 768 + 12 * 8 * 256
    assert _read("eva_summary_rows_share", ctx) == pytest.approx(100 * summaries / (window + summaries))
    assert _read("eva_read_busy_share", ctx) == pytest.approx(100 * 0.9 / 2.4)
    # the rows the spans counted a step x the steps the trace counted, over the kernel's self time
    steps = 70 * CFG["engine"]["decode_block"]
    rows_a_step = (window + summaries) / 2
    share = _read("eva_read_roofline_share", ctx)
    assert share == pytest.approx(100 * steps * rows_a_step * 16384 / 819e9 / 0.9) and 0 < share < 100
    step = _read("decode_step_roofline_share.evabyte", ctx)
    want = adapter.decode_step_floor_s(CFG, PEAKS, 18, 0.0, window / 2, summaries / 2) / 0.014 * 100
    assert step == pytest.approx(want) and 0 < step < 100
    # the same work whatever implements the read: another operation's name reads nothing, not a wrong number
    assert _read("eva_read_roofline_share", _ctx([DECODE], dict(TRACE, ops_self_s={"fusion": 1.0}))) is None
    assert _read("eva_read_busy_share", _ctx([DECODE], None)) is None  # an untraced run
    parent = _ctx(PARENT_SPANS, TRACE)  # a program without the stats
    for name in OWN:
        if name != "eva_read_busy_share":  # a share of the busy time is 0 there, and the parent cannot run the cell
            assert _read(name, parent) is None, name


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "EvaByte")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 4096, "intermediate_size": 11008, "num_attention_heads": 32, "num_key_value_heads": 32,
        "num_hidden_layers": 32, "vocab_size": 320, "num_pred_heads": 8, "chunk_size": 16, "window_size": 2048,
        "rope_theta": 100000, "rope_scaling": None, "rms_norm_eps": 1e-5, "attention_class": "eva",
        "norm_add_unit_offset": True, "fp32_logits": True, "fp32_skip_add": True, "mixedp_attn": True,
        "attention_bias": False, "tie_word_embeddings": False, "max_position_embeddings": 32768,
        "hidden_act": "silu", "model_type": "evabyte",
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    # the cut: depth alone
    assert CFG["reduced"] == ["layers"] and set(CFG["reduced_how"]) == {"layers"}
    assert CFG["layers"] == len(CFG["layers_served"]) == 8 and CFG["layers_served"] == list(range(8))
    assert CFG["chips_sharing_a_layer"] == 1 and "four-stage pipeline" in CFG["deployment"]
    assumed = " ".join(CFG["assumed"])
    for item in ("mu_h", "phi_h", "|k_m|^2 / 2", "NO scale on mu . k", "ROTATED keys", "do NOT overlap",
                 "ONE softmax", "no QK norm", "ONE matrix W_head", "head 0", "320 ids", "seed 0", "ignore_eos"):
        assert item in assumed, item


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    # 24 x 128 + the scratch page: 768 pages under ISSUE 57's 24 x 160, given back after the measured peak (the plan says why)
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] == 24 * 128 + 1
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 24
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 20480 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512 and env["APP_ENGINE_PREFIXCACHEENABLE"] == "off"
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == eng["decode_block"] and eng["decode_block"] in (2, 3, 4)
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    ref = CFG["reference"]
    assert ref["prompt_tokens"] == [64, 1040, 2044, 4090] and ref["served_only_prompt_tokens"] == [12288]
    # 1040 = 65 x 16: the served walk extends 1,039 of them (an OPEN chunk of 15) and its decode step completes the chunk
    assert ref["decode_tokens"] == 8 and 1039 % 16 == 15 and 2044 + 8 > 2048 > 2044 and 4090 + 8 > 4096 > 4090
    assert CFG["correct"]["kernel_paths"] == {"eva_read": "compiled"}
    assert [c["metric"] for c in CFG["correct"]["counters_must_grow"]] == [
        f"genai_engine_eva_{n}_total" for n in ("window_tokens_read", "summaries_read", "summaries_written", "windows_closed")]
    from generativeaiexamples_tpu.models import evabyte as m

    mc, plan = adapter.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc) == 3_261_865_984
    assert plan["fixed_state_bytes"] == 24 * m.fixed_state_bytes_per_slot(mc) == 24 * 8 * 33_554_432
    assert eng["fixed_state_bytes_per_slot"] == 8 * 33_554_432
    assert plan["page_pool_bytes"] == 3073 * 128 * m.kv_bytes_per_token(mc) and eng["kv_bytes_per_token"] == 8 * 1024
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < 0.78 * 16.9e9 < plan["resident_bytes"] < 0.9 * 16.9e9  # 80 % of the chip before temporaries
    # the mean request's reservation fits 24 times over; the largest alone does for every slot it can take
    assert 24 * -(-(12288 + 400 + 2048) // 128) < eng["kv_pool_pages"] - 1 and -(-20480 // 128) < eng["kv_pool_pages"]
    assert "TODO" not in json.dumps(CFG)


# the per-layer entries ISSUE 57 names for the cell; a later PR may append more
GENERIC = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share", "extend_dispatch_dev_ms",
    "state_rows_mean", "stream_backlog_tokens_mean", "decode_step_done_ms", "extend_wide_done_ms",
    "extend_device_share", "device_starved_share", "device_hold_max_ms", "gap_tail_extend_share",
)


def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("evabyte-6.5b-pp4-bf16", "doc_bytes", 1)
    assert len(cell["why"]) <= 200 and "BYTES/s" in cell["why"] and "ignore_eos" in cell["why"]
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    # found by name and cell: neither their count nor their place is pinned
    mine = assert_cell_holds(manifest, CELL, GENERIC + OWN)
    own = [e for e in manifest["per_layer"] if e["workloads"] == [CELL]]
    assert sorted(e["name"] for e in own) == sorted(OWN) and len(own) <= 6
    return mine


def test_manifest_entries_of_the_cell_found_by_name():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert_manifest_entries_of_the_cell(manifest)
    assert len(manifest["workloads"]) >= 9 and len(manifest["per_layer"]) <= 128
    traffic = load(os.path.join(BENCH, "traffic", "doc_bytes.json"))
    assert traffic["kind"] == "closed" and traffic["clients"] == CFG["engine"]["max_batch_size"] == 24
    assert traffic["question_bytes"] == [8192, 12288, 16384] and traffic["max_tokens"] == [1024, 2048, 3072]
    assert traffic["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1, "ignore_eos": True}
    # the longest request fits a slot, and every decoding row stands past the first window
    assert 16384 + 400 + 3072 <= CFG["engine"]["max_seq_len"] and min(traffic["question_bytes"]) > 2 * 2048
    assert traffic["ramp"]["cap_s"] == 60.0 and "TODO" not in json.dumps(traffic)


def test_the_cells_entries_are_found_behind_a_later_cell():
    """The append rehearsal with THIS cell in the manifest: a tenth cell joins the lists behind it and the
    cell's own predicate still holds; every earlier cell's does (``test_perfbench_append.py`` runs them)."""
    from tests.perfbench import test_perfbench_append as rehearsal

    m = rehearsal.appended()
    assert [w["name"] for w in m["workloads"]][-2:] == [CELL, rehearsal.NINTH]
    assert_manifest_entries_of_the_cell(m)
