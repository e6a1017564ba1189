"""The MiniMax-M3 adapter (perfbench/arch/minimaxm3.py): its plain float32
reference against the engine at a tiny size that keeps the dense layer
and two expert layers (a block of 8 tokens, top 2), with the prefix store
ON so that the ``served_only`` prompt enters through shared pages and
their summaries, the engine's selection held to the reference's, the
control one precision down that must fail ``TOLERANCE``, its byte and
operation counts against hand values, its readers on synthetic spans,
its configuration file and its manifest entries (found by name: entries
a later PR appends are none of this file's business)."""
import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import arch, reference
from perfbench.arch import minimaxm3 as adapter
from tests.perfbench.manifest_entries import assert_cell_holds
from tests.perfbench.manifest_entries import metric_spec as _metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "minimax-m3-ep8-bf16.json")
CELL = "agent_files_minimaxm3"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


CFG = load(CONFIG)
# one dense and two expert layers at widths a CPU test can walk: the sizes of ``minimaxm3-debug``
TINY = dict(
    CFG, name="minimaxm3-tiny-test", vocab_size=256, hidden_size=64, dense_intermediate_size=96, intermediate_size=32,
    shared_intermediate_size=32, num_hidden_layers=6, moe_layer_freq=[0, 1, 1, 1, 1, 1], layers_served=[0, 1, 2], layers=3,
    num_local_experts=16, num_experts_per_tok=4, num_local_experts_held=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rotary_dim=8, msa={"block": 8, "topk": 2, "index_heads": 2, "index_head_dim": 16},
    engine=dict(CFG["engine"], max_seq_len=512, page_size=8),
    reference=dict(CFG["reference"], prompt_tokens=[9, 30, 150], served_only_prompt_tokens=[330], decode_tokens=5),
)


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    adapter.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=2, max_seq_len=512,
        prefill_chunk=32, page_size=8, decode_block=4, prefix_cache_enable="auto", prefix_cache_slots=4,
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
    served walks (one chunk; 29 tokens and one decode step; 150 tokens in
    five extend chunks, the selection biting from 24 tokens on), greedy
    tokens through the engine for those AND for the served-only prompt,
    which the adapter served once before, and the reference's logits."""
    import jax

    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    with jax.default_matmul_precision("highest"):
        prompts = harness_prompts(engine)
        before = counters()
        eng_logits = list(adapter.engine_prefill_logits(engine, prompts[:3], on_tpu=False)) + [None]
        primed = counters()
        greedy = SamplingParams(temperature=0.0, max_tokens=5)
        tokens = [list(engine.iter_ids(p, greedy, timeout=600)) for p in prompts]
        after = counters()
        full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
        grew = lambda a, b, k: b.get(k, 0.0) - a.get(k, 0.0)  # noqa: E731
        ref = adapter.reference_logits(engine, TINY, full)
        eng_logits = [None if e is None else np.asarray(e) for e in eng_logits]
    return (prompts, eng_logits, tokens, full, ref,
            {"primed": {k: grew(before, primed, k) for k in primed}, "served": {k: grew(primed, after, k) for k in after}})


def test_engine_agrees_with_the_reference_through_prefill_extend_decode_and_a_hit(compared):
    prompts, eng_logits, tokens, _, ref, _ = compared
    out = reference.compare(prompts, eng_logits, tokens, ref, adapter.TOLERANCE)
    assert out["ok"] and max(out["prefill_rel_err"]) < 1e-4 and out["decode_margin_max"] < 1e-4, out
    assert out["decode_tokens_checked"] == 20 and all(len(t) == 5 for t in tokens)


def test_the_engines_selection_is_held_to_the_references_layer_by_layer(compared):
    """The served walks kept every layer's selection at every position
    of the longest compared prompt (150 tokens: 149 through five extend
    chunks and the last through ONE decode step, whose selection at
    block 18 is first + two of the sixteen candidates + local + open a
    KV head); the reference's own selection is the same in every layer
    (no block differs: every gap is 0), so its rows attended under their
    own. A layer with too many (position, KV head) pairs that differ
    past a tie, or that shares too few of the reference's blocks, fails
    the run; and the chunk walk's own
    row at position 149 (the last chunk walked again with the last token
    in it) selected the decode step's blocks and was held to the
    reference's row there."""
    kept = dict(adapter._LAST_SELECTION)
    assert kept["shares"] == [1.0, 1.0, 1.0] and kept["share"] == kept["last_share"] == 1.0 and len(kept["tokens"]) == 150
    assert [g.shape for g in kept["gaps"]] == [(150, 2)] * 3 and all(float(np.max(g)) == 0.0 for g in kept["gaps"])
    assert kept["blocks"].shape == (3, 150, 2, 19) and kept["blocks"].dtype == bool
    assert [np.nonzero(row)[0].tolist()[0::4] + np.nonzero(row)[0].tolist()[-2:] for row in kept["blocks"][-1, 149]] == \
        [[0, 18, 17, 18]] * 2 and all(int(row.sum()) == 5 for row in kept["blocks"][-1, 149])
    assert np.array_equal(kept["reference_blocks"][:, :19], kept["blocks"][-1, 149])
    assert kept["chunk_logits"].shape == (TINY["vocab_size"],) and kept["chunk_rel_err"] < 1e-4
    assert kept["chunk_blocks_apart"] == 0 and np.array_equal(kept["chunk_blocks"], kept["blocks"][:, 149])
    masks = adapter.selection_masks(np.asarray([[[[0, 3, 2, 0]]]]), np.asarray([[[[True, True, False, False]]]]), 4)
    assert masks.tolist() == [[[[True, False, False, True]]]]
    own = np.asarray([[1, 0, 1], [1, 1, 0]], bool)
    eps, most = adapter.SELECTION_TIE_EPS, adapter.SELECTION_DECIDED_SHARE_MAX
    tied = np.asarray([[0.0, eps * 0.9]] * 100)  # differs in ties alone
    few = np.where(np.arange(200).reshape(100, 2) < int(200 * most), eps * 1.1, 0.0)  # as many decided pairs as may be
    many = np.where(np.arange(200).reshape(100, 2) <= int(200 * most), np.inf, 0.0)  # one more
    with pytest.raises(RuntimeError, match="selection overlap 0.5000 under 0.9"):
        adapter.check_selection({"shares": [1.0, 0.5], "last": (own, own), "gaps": [tied, tied]})
    with pytest.raises(RuntimeError, match=r"blocks its scores decide: \[0.0, 0.0, 0.025\] of a layer's"):
        adapter.check_selection({"shares": [1.0, 1.0, 1.0], "last": (own, own), "gaps": [tied, few * 0, many]})
    assert adapter.check_selection({"shares": [1.0, 0.95], "last": (own, own), "gaps": [tied, few]}) == int(200 * most) / 200
    last = (kept["reference_blocks"][:, :19], kept["blocks"][-1, 149])
    assert adapter.check_selection({"shares": kept["shares"], "last": last, "gaps": kept["gaps"]}) == 0.0
    assert adapter.check_selection({}) is None
    try:
        adapter._LAST_SELECTION["chunk_logits"] = kept["chunk_logits"] + 1.0
        with pytest.raises(RuntimeError, match="the chunk walk's logits at position 149"):
            adapter.check_chunk_walk_row(compared[4], compared[3])
        adapter._LAST_SELECTION["chunk_blocks"] = ~kept["chunk_blocks"]
        with pytest.raises(RuntimeError, match="select different blocks at position 149: 114 apart"):
            adapter.check_chunk_walk_row(compared[4], compared[3])
    finally:
        adapter._LAST_SELECTION.update(kept)


def test_tie_gaps_by_hand():
    """One query, one KV head, six blocks, candidates 1..4 for two
    places: the reference keeps blocks 1 and 2 (scores 5 and 4). A
    selection that holds 3 (3.9) for 2 differs in a tie; one that holds
    4 (1) for 2 differs by two spreads; one that differs in a block no
    score decides reads inf, as does any difference where there are no
    more candidates than places."""
    import jax.numpy as jnp

    scores = jnp.asarray([[[9.0, 5.0, 4.0, 3.9, 1.0, 9.0]]])
    cand = jnp.asarray([[[False, True, True, True, True, False]]])
    pick = lambda *blocks: jnp.asarray([[[j in blocks for j in range(6)]]])  # noqa: E731
    own = pick(0, 1, 2, 5)
    sd = float(np.std([5.0, 4.0, 3.9, 1.0]))
    gap = lambda other, topk=2: float(adapter.tie_gaps(scores, cand, own, other, topk)[0, 0])  # noqa: E731
    assert gap(own) == 0.0
    assert gap(pick(0, 1, 3, 5)) == pytest.approx(0.1 / sd, rel=1e-4)
    assert gap(pick(0, 1, 4, 5)) == pytest.approx(3.0 / sd, rel=1e-4)
    assert gap(pick(0, 1, 5)) == 0.0 and gap(pick(0, 1, 2)) == np.inf  # block 2 dropped: it IS the last place; block 5: undecided
    assert gap(pick(0, 1, 3, 5), topk=4) == np.inf
    got, _, _, late = adapter.block_selection(jnp.ones((32, 1, 4)), jnp.ones((32, 1, 4)), TINY, late_chunk=16)
    # all scores equal: ties go to the lower index; the late-block fault loses the blocks of the query's own chunk
    assert np.nonzero(got[31, 0])[0].tolist() == [0, 1, 2, 3] and np.nonzero(late[31, 0])[0].tolist() == [0, 1, 2, 3]
    got, _, _, late = adapter.block_selection(jnp.ones((48, 1, 4)), jnp.ones((48, 1, 4)).at[16:24].set(2.0), TINY, late_chunk=32)
    assert np.nonzero(got[47, 0])[0].tolist() == [0, 1, 2, 4, 5] and np.nonzero(late[47, 0])[0].tolist() == [0, 1, 2, 4, 5]
    got, _, _, late = adapter.block_selection(jnp.ones((64, 1, 4)), jnp.ones((64, 1, 4)).at[32:40].set(2.0), TINY, late_chunk=32)
    assert np.nonzero(got[63, 0])[0].tolist() == [0, 1, 4, 6, 7] and np.nonzero(late[63, 0])[0].tolist() == [0, 1, 2, 6, 7]


def test_a_walk_that_loses_its_summaries_comes_out_not_correct(engine, compared, monkeypatch):
    """The planted fault (``PERFBENCH_MINIMAXM3_FAULT=stale_summary``):
    the longest prompt's chunk walk loses every summary it wrote, so
    later chunks score stale ones. Its selection differs from the
    reference's in blocks the scores decide, and the comparison raises:
    the run is not correct. The readings' tool reports the same fault,
    the planted late-block fault from the reference's own scores, and a
    sound walk's gaps (none)."""
    prompts, _, _, full, _, _ = compared
    kept = dict(adapter._LAST_SELECTION)
    try:
        monkeypatch.setenv(adapter.FAULT_ENV, "stale_summary")
        rows = adapter.engine_prefill_logits(engine, prompts[:3], on_tpu=False)
        assert isinstance(rows[0], adapter.Deferred)
        with pytest.raises(RuntimeError, match="differs from the reference's in blocks its scores decide"):
            adapter.reference_logits(engine, TINY, full)
        monkeypatch.delenv(adapter.FAULT_ENV)
        with pytest.raises(ValueError, match="the one planted fault"):
            adapter._served_logits(engine, prompts[:3], "other")
        sound = adapter.tolerance_readings(engine, TINY, control=False)
        assert sound["gaps"]["differ"] == 0 and sound["gaps"]["largest"] is None and sound["chunk_walk_rel_err"] < 1e-4
        assert max(sound["served_prefill_rel_err"]) < 1e-4 and sound["selection_shares"] == [1.0, 1.0, 1.0]
        late = sound["late_block_gaps"]
        assert min(late["past_eps_share_by_layer"]) > 5 * adapter.SELECTION_DECIDED_SHARE_MAX
        faulty = adapter.tolerance_readings(engine, TINY, fault="stale_summary", control=False)
        assert min(faulty["gaps"]["past_eps_share_by_layer"]) > 5 * adapter.SELECTION_DECIDED_SHARE_MAX
        assert faulty["gaps"]["past_marks"]["0.5"] >= faulty["gaps"]["past_marks"]["2.0"] > 0
    finally:
        adapter._PENDING.clear()
        adapter._LAST_SELECTION.clear()
        adapter._LAST_SELECTION.update(kept)


def test_the_served_only_prompt_enters_through_shared_pages_and_their_summaries(compared):
    """The adapter's priming left an entry at 320 tokens (the deepest
    chunk boundary under the prompt's last token) that holds 40 pages of
    every pool; the harness's own decode of the prompt mapped them and
    prefilled the ten-token tail under the selection. No state row is
    saved or restored: the family has pages only."""
    _, _, _, _, _, grew = compared
    primed, served = grew["primed"], grew["served"]
    assert primed.get("genai_engine_prefix_cache_hits_total", 0) == 0
    assert served["genai_engine_prefix_cache_hits_total"] == 1
    assert served["genai_engine_prefix_cache_tokens_reused_total"] == 320
    assert served["genai_engine_kv_prefix_pages_mapped_total"] == 320 // 8
    for name in ("genai_engine_prefix_state_saves_total", "genai_engine_prefix_state_restores_total",
                 "genai_engine_state_slot_resets_total"):
        assert primed.get(name, 0) == 0 and served.get(name, 0) == 0
    for name in ("pages_selected", "pages_live", "blocks_scored", "pages_pooled"):
        assert served[f"genai_engine_msa_{name}_total"] > 0, name
    assert served["genai_engine_msa_pages_selected_total"] < served["genai_engine_msa_pages_live_total"]


def test_the_compared_rows_are_deferred_until_read(engine, compared):
    prompts = compared[0]
    rows = adapter.engine_prefill_logits(engine, prompts[:1], on_tpu=False)
    assert adapter._PENDING and isinstance(rows[0], adapter.Deferred)
    assert np.asarray(rows[0]).shape == (TINY["vocab_size"],)
    adapter._PENDING.clear()


def test_the_control_one_precision_down_fails(engine, compared):
    """All-bfloat16 products, sums, norms, softmax, indexer and residual: past ``TOLERANCE``."""
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
    code = "import sys; import perfbench.arch.minimaxm3; assert 'jax' not in sys.modules"
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT))
    with open(adapter.__file__, encoding="utf-8") as fh:
        text = fh.read()
    # the registration alone touches the program's model; the reference imports nothing of it
    assert text.count("generativeaiexamples_tpu.models") == 2 and "models.minimaxm3 import MiniMaxM3Config" in text


def test_layers_served_and_the_model_configuration():
    assert adapter.layer_kinds(CFG) == ["dense", "sparse", "sparse", "sparse", "sparse"]
    from generativeaiexamples_tpu.models import minimaxm3 as m

    assert adapter.model_config(CFG) == m.PRESETS["minimax-m3-ep8"]
    assert adapter.model_config(TINY) == dataclasses.replace(m.PRESETS["minimaxm3-debug"], max_seq_len=512)


def test_byte_and_operation_counts_against_hand_values():
    D, V = 6144, 25008
    assert adapter.expert_bytes(CFG) == 3 * D * 3072 * 2 == 113_246_208
    assert adapter.strip_bytes(CFG) == 2 * 128 * 128 * 2 == 65_536 and adapter.summary_bytes(CFG) == 1024
    attn = D * (64 + 8 + 16) * 128 + 8192 * D
    assert attn == 119_537_664  # ISSUE 52's attention and indexer
    fixed_bf16 = 5 * attn + 3 * D * 12288 + 4 * 3 * D * 3072 + D * V
    fixed_f32 = 5 * (2 * D + 68 * 128) + D + 4 * (D * 128 + 128)
    assert adapter.fixed_weight_bytes(CFG) == 2.0 * fixed_bf16 + 4.0 * fixed_f32
    # everything the plan holds outside the routed experts and the embedding, the float32 leaves at their width
    assert adapter.fixed_weight_bytes(CFG) + 4 * 16 * 113_246_208 == pytest.approx(
        CFG["memory_plan"]["weights_bytes"] - 2 * D * V + 2 * fixed_f32)
    rows, ctx, hit = 16.0, 16000.0, 25.0
    assert adapter.selected_strips(CFG, ctx) == 4 * 19 and adapter.selected_strips(CFG, 1000.0) == 4 * 8
    strips = rows * 5 * 4 * 19
    want = (adapter.fixed_weight_bytes(CFG) + hit * 113_246_208 + strips * 65_536
            + rows * (5 * 126 * 1024 + 2 * D))
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit) == pytest.approx(want)
    assert strips * 65_536 == pytest.approx(0.398e9, rel=0.01)  # ISSUE 52: 0.39 GB of selected pages a step
    assert adapter.decode_step_bytes(CFG, rows, ctx, hit, selected=1000.0) == pytest.approx(want - (strips - 1000) * 65_536)
    assert adapter.expected_experts_hit(CFG, 16) == pytest.approx(4 * 16 * (1 - (127 / 128) ** 64))
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    floor = adapter.decode_step_floor_s(CFG, peaks, rows, ctx, hit)
    assert floor == pytest.approx(want / 819e9) and 0.005 < floor < 0.008  # bytes bind, not operations
    assert adapter.decode_step_flops(CFG, rows, ctx) / 197e12 < floor / 3
    nbytes, flops = adapter.selected_read_bytes_and_flops(CFG, 10)
    assert nbytes == 10 * 65_536 and flops == 10 * 128 * 16 * 128 * 2 * 2


def _ctx(spans, trace=None, before=None, after=None):
    peaks = load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    ctx = {"spans": spans, "config": CFG, "peaks": peaks, "trace": trace, "adapter": adapter,
           "metrics_before": before or {}, "metrics_after": after or {}}
    ctx["read"] = lambda name: {"decode_step_dev_ms": 10.0}[name]
    return ctx


DECODE = {"kind": "decode", "rows": 15, "kv_readers": 5, "moe_pairs_held": 8, "moe_pairs_absent": 232,
          "moe_experts_hit": 24, "moe_experts_held": 64, "msa_pages_selected": 15 * 5 * 4 * 19,
          "msa_pages_live": 15 * 5 * 4 * 120, "msa_blocks_scored": 15 * 5 * 4 * 117, "msa_pages_pooled": 0}
CHUNK = {"kind": "prefill_chunk", "rows": 1, "kv_readers": 5, "moe_experts_hit": 64, "moe_experts_held": 64,
         "msa_pages_selected": 512 * 5 * 4 * 19, "msa_pages_live": 512 * 5 * 4 * 100, "prefix_depth_tokens": 9216}
PARENT_SPANS = [{"kind": "decode", "rows": 15}]
TRACE = {"devices": 1, "busy_s": 2.4, "window_s": 2.5,
         "ops_self_s": {"grouped_matmul_gate_up": 0.3, "grouped_matmul_down": 0.2, "selected_page_attention": 0.2,
                        "fusion": 1.0},
         "modules": {"jit_decode_paged": {"count": 40, "total_s": 1.1}, "jit_extend_batch_paged": {"count": 12, "total_s": 1.2}}}


def _read(name, ctx):
    from perfbench import readers

    spec = _metric(name)
    return readers.resolve(spec["reader"], [BENCH])(ctx, spec["params"])


def test_span_readers_and_what_a_parent_without_the_fields_gives():
    ctx = _ctx([DECODE, dict(DECODE, moe_experts_hit=26, moe_pairs_held=12), CHUNK, dict(CHUNK, prefix_depth_tokens=4096),
                {k: v for k, v in CHUNK.items() if k != "prefix_depth_tokens"}])
    assert _read("moe_experts_hit_share", ctx) == pytest.approx(100 * 50 / 128)
    assert _read("moe_pairs_per_expert_mean", ctx) == pytest.approx(20 / 50)
    assert _read("msa_selected_page_share", ctx) == pytest.approx(100 * 19 / 120)  # decode spans alone
    assert _read("decode_rows_mean", ctx) == 15
    assert _read("extend_prefix_depth_mean", ctx) == pytest.approx((9216 + 4096) / 2)
    share = _read("decode_step_roofline_share.minimaxm3", ctx)
    want = adapter.decode_step_floor_s(CFG, ctx["peaks"], 15, 119.5 * 128, 25, 15 * 5 * 4 * 19) / 0.010 * 100
    assert share == pytest.approx(want) and 40 < share < 100
    parent = _ctx(PARENT_SPANS)
    for name in ("moe_experts_hit_share", "moe_pairs_per_expert_mean", "msa_selected_page_share",
                 "decode_step_roofline_share.minimaxm3", "extend_prefix_depth_mean"):
        assert _read(name, parent) is None


def test_kernel_roofline_readers_count_what_the_trace_saw_and_stay_under_the_peak():
    ctx = _ctx([DECODE, CHUNK], TRACE)
    steps = 40 * CFG["engine"]["decode_block"]
    got = _read("grouped_matmul_roofline_share.minimaxm3", ctx)
    assert got == pytest.approx(100 * (steps * 24 + 12 * 64) * 113_246_208 / 819e9 / 0.5) and got < 100
    got = _read("msa_read_roofline_share", ctx)
    strips = steps * 15 * 5 * 4 * 19
    by_bytes, by_ops = strips * 65_536 / 819e9, strips * 128 * 16 * 128 * 4 / 197e12
    assert by_bytes > by_ops and got == pytest.approx(100 * by_bytes / 0.2) and got < 100
    assert _read("msa_read_busy_share", ctx) == pytest.approx(100 * 0.2 / 2.4)
    assert _read("grouped_matmul_busy_share", ctx) == pytest.approx(100 * 0.5 / 2.4)
    bare = dict(TRACE, ops_self_s={"fusion": 1.0}, modules={"jit_decode_paged": {"count": 40, "total_s": 1.1}})
    for name in ("grouped_matmul_roofline_share.minimaxm3", "msa_read_roofline_share"):
        assert _read(name, _ctx([DECODE], None)) is None  # an untraced run
        assert _read(name, _ctx(PARENT_SPANS, bare)) is None  # a program without the kernels or the fields
        assert _read(name, _ctx(PARENT_SPANS, TRACE)) is None  # kernels but no span field of this family


def test_the_reused_share_reads_the_stores_counters():
    key = lambda name: (name, frozenset())  # noqa: E731
    before = {key("genai_engine_prefix_cache_tokens_reused_total"): 1000.0, key("genai_engine_prefill_tokens_total"): 5000.0,
              key("genai_engine_prefix_shared_pages_in_use"): 0.0}
    after = {key("genai_engine_prefix_cache_tokens_reused_total"): 61000.0, key("genai_engine_prefill_tokens_total"): 45000.0,
             key("genai_engine_prefix_shared_pages_in_use"): 900.0}
    assert _read("prefix_reused_token_share.minimaxm3", _ctx([], None, before, after)) == pytest.approx(60.0)
    parent = {k: v for k, v in after.items() if "shared_pages" not in k[0]}
    assert _read("prefix_reused_token_share.minimaxm3", _ctx([], None, before, parent)) is None
    assert _read("prefix_reused_token_share.minimaxm3", _ctx([], None, after, after)) is None


# --------------------------------------------------------------------------- #
# The configuration file and the manifest's entries


def test_configuration_holds_every_number_of_the_catalogs_config():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "MiniMax-M3")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in ("vocab_size", "num_mtp_modules", "num_nextn_predict_layers"):  # reduced: the published number beside it
            assert CFG[key + "_published"] == value and key in CFG["reduced"]
        else:
            assert CFG[key] == value, key


def test_configuration_holds_the_published_sizes_and_reduces_no_width():
    want = {
        "hidden_size": 6144, "intermediate_size": 3072, "dense_intermediate_size": 12288, "shared_intermediate_size": 3072,
        "num_attention_heads": 64, "num_key_value_heads": 4, "head_dim": 128, "rotary_dim": 64, "num_hidden_layers": 60,
        "num_local_experts": 128, "num_experts_per_tok": 4, "n_shared_experts": 1, "rms_norm_eps": 1e-6,
        "routed_scaling_factor": 2, "scoring_func": "sigmoid", "use_routing_bias": True, "rope_theta": 5000000,
        "max_position_embeddings": 1048576, "tie_word_embeddings": False, "hidden_act": "swigluoai", "swiglu_alpha": 1.702,
        "swiglu_limit": 7, "use_gemma_norm": True, "attention_output_gate": False, "qk_norm_type": "per_head",
        "vocab_size_published": 200064,
    }
    for key, value in want.items():
        assert CFG[key] == value, key
    assert CFG["moe_layer_freq"] == [0, 0, 0] + [1] * 57
    assert CFG["msa"] == {"block": 128, "topk": 16, "index_heads": 4, "index_head_dim": 128}
    # the cut: depth, the experts held, the vocabulary's share, the prediction modules; no width
    assert CFG["reduced"] == ["layers", "num_local_experts", "vocab_size", "num_mtp_modules", "num_nextn_predict_layers"]
    assert set(CFG["reduced_how"]) == set(CFG["reduced"])
    widths = ("hidden", "intermediate", "_dim", "_rank", "head", "per_tok")
    assert not any(w in key for key in CFG["reduced"] for w in widths)
    assert CFG["layers"] == len(CFG["layers_served"]) == 5 and CFG["layers_served"] == [0, 3, 4, 5, 6]
    assert CFG["num_local_experts_held"] == 16 and CFG["experts_first"] == 0 and CFG["vocab_size"] == 200064 // 8
    assert CFG["num_mtp_modules"] == 0 and CFG["num_nextn_predict_layers"] == 0
    assert CFG["chips_sharing_a_layer"] == 8 and "8 chips share each layer" in CFG["deployment"]
    assert "4 pairs" in CFG["deployment"] and "0.5" in CFG["deployment"] and "6.3 of 16" in CFG["deployment"]
    assumed = " ".join(CFG["assumed"])
    for item in ("vision tower", "NOT served", "pairs (i, i + 32)", "N(0, 0.1)", "no output gate", "EVERY layer is sparse",
                 "summed unweighted", "OWN cached keys", "ties to the lower index", "1.702", "character-level tokenizer",
                 "no verify walk"):
        assert item in assumed, item


def test_configuration_engine_reference_and_memory_plan():
    env, eng = CFG["server_env"], CFG["engine"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] and 3000 <= eng["kv_pool_pages"] <= 3700
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 16
    assert int(env["APP_ENGINE_MAXSEQLEN"]) == eng["max_seq_len"] == 36864 and int(env["APP_ENGINE_PAGESIZE"]) == 128
    assert eng["page_size"] == CFG["msa"]["block"]  # the page IS the block
    assert int(env["APP_ENGINE_PREFILLCHUNK"]) == eng["prefill_chunk"] == 512
    assert int(env["APP_ENGINE_PREFILLWAVETOKENS"]) == eng["prefill_wave_tokens"] == 512  # one row a wave
    assert env["APP_ENGINE_PREFIXCACHEENABLE"] == "auto"
    assert int(env["APP_ENGINE_PREFIXCACHESLOTS"]) == eng["prefix_cache_slots"]
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == eng["decode_block"] and eng["decode_block"] in (2, 3, 4)
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_KVCACHEDTYPE"] == "bfloat16"
    ref = CFG["reference"]
    assert max(ref["prompt_tokens"]) >= 5120 and ref["served_only_prompt_tokens"] == [3072] and ref["decode_tokens"] == 8
    assert CFG["correct"]["kernel_paths"] == {"grouped_matmul": "compiled", "selected_read": "compiled"}
    grow = {(c["metric"], c.get("labels", {}).get("held")) for c in CFG["correct"]["counters_must_grow"]}
    assert grow == {("genai_engine_moe_pairs_total", "true"), ("genai_engine_msa_pages_selected_total", None),
                    ("genai_engine_msa_pages_live_total", None), ("genai_engine_msa_blocks_scored_total", None),
                    ("genai_engine_msa_pages_pooled_total", None), ("genai_engine_prefix_cache_hits_total", None),
                    ("genai_engine_prefix_cache_tokens_reused_total", None)}
    from generativeaiexamples_tpu.models import minimaxm3 as m

    mc, plan = adapter.model_config(CFG), CFG["memory_plan"]
    assert plan["weights_bytes"] == 2 * m.count_logical_params(mc) == 2 * 4_985_107_456
    assert plan["fixed_state_bytes"] == 0 == eng["fixed_state_bytes_per_slot"]
    assert plan["page_pool_bytes"] == eng["kv_pool_pages"] * m.page_bytes(mc) and eng["kv_bytes_per_token"] == 10_280
    assert plan["resident_bytes"] == sum(plan[k] for k in ("weights_bytes", "fixed_state_bytes", "page_pool_bytes", "embedder_bytes"))
    assert 0.25 * 16.9e9 < plan["resident_bytes"] < 16.9e9
    # a row at the traffic's worst case fits max_seq_len
    worst = -(-(4 * 8192 + 3 * 256 + 700 + 384) // 128) + 1
    assert worst * 128 <= eng["max_seq_len"]
    for key in ("page_pool", "rehearsal_compile", "measured_peak"):
        assert "TBD" not in plan[key]
    assert "TBD" not in CFG["engine_how"]


# the per-layer entries ISSUE 52 names for the cell; a later PR may append more
GENERIC = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "device_idle_share", "extend_dispatch_dev_ms",
    "stream_backlog_tokens_mean", "moe_experts_hit_share", "moe_pairs_per_expert_mean", "grouped_matmul_busy_share",
    "extend_prefix_depth_mean",
)
OWN = ("grouped_matmul_roofline_share", "decode_step_roofline_share", "prefix_reused_token_share")
NEW = ("msa_selected_page_share", "msa_read_busy_share", "msa_index_busy_share", "msa_read_roofline_share")


# PR 40's whole-window span metrics (both chunk widths) and the share of planned tiles used: joined in PR 56
JOINED = ("decode_step_done_ms", "extend_wide_done_ms", "extend_narrow_done_ms", "extend_device_share",
          "device_starved_share", "device_hold_max_ms", "moe_tiles_used_share")


def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("minimax-m3-ep8-bf16", "agent_files", 1)
    assert len(cell["why"]) <= 200 and "more host" in cell["why"] and "attention sees more than its share" in cell["why"]
    (cfg,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    assert cfg["reduced"] == CFG["reduced"] and cfg["file"].endswith(os.path.basename(CONFIG)) and cfg["source"] == CFG["source"]
    assert len(cfg["why"]) <= 200
    own = tuple(base + ".minimaxm3" for base in OWN)  # an adapter's reader: a file under the suffixed name
    # found by name and cell: neither count nor place is pinned
    assert_cell_holds(manifest, CELL, GENERIC + own + NEW + JOINED)
    for name in GENERIC + own + NEW:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".json"))
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert all("workloads" in e for e in manifest["per_layer"] if e["moves"] in ("out_tok_s", "itl_p995_ms"))
    assert len(json.dumps(manifest)) < 64 * 1024


def test_manifest_entries_of_the_cell_found_by_name():
    assert_manifest_entries_of_the_cell(load(os.path.join(ROOT, "BENCHMARK.json")))


def test_the_traffic_file_is_as_the_issue_gives_it():
    traffic = load(os.path.join(BENCH, "traffic", "agent_files.json"))
    assert (traffic["kind"], traffic["clients"], traffic["turns"]) == ("sessions", CFG["engine"]["max_batch_size"], 4)
    assert traffic["request"] == {"use_knowledge_base": False, "temperature": 0.1, "top_p": 0.1}
    assert traffic["question_bytes"] == [4096, 6144, 8192] and traffic["max_tokens"] == [128, 256, 384]
    assert traffic["ramp"] == {"expected_request_s": 60.0, "cap_s": 60.0}
    assert traffic["trace_window_s"] == 2.5 and traffic["traced_run_window_s"] == 20.0


EARLIER = ("chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash", "doc_reason_gigachat35",
           "doc_reason_trinitymini", "chat_sessions_solaropen2", "agent_sessions_kimik25")


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
