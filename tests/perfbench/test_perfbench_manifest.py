"""BENCHMARK.json and every data file it names load and cross-reference."""
import json
import os
import re

import pytest

from tests.perfbench.manifest_entries import assert_cell_holds

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "experts_per")


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


MANIFESTS = {
    "benchmark": os.path.join(ROOT, "BENCHMARK.json"),
    "rehearsal": os.path.join(BENCH, "rehearsal", "manifest.json"),
}
M = load(MANIFESTS["benchmark"])
CELLS = [w["name"] for w in M["workloads"]]
E2E = [m["name"] for m in M["end_to_end"]]
PAIRS = [(m["name"], cell) for m in M["per_layer"] for cell in m.get("workloads", CELLS)]


def cells_of(metric, manifest=M):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


@pytest.mark.parametrize("which", sorted(MANIFESTS))
def test_manifest_has_exactly_the_contract_keys(which):
    m = load(MANIFESTS[which])
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert m["paths"] == ["perfbench", "tests/perfbench"]
    assert m["command"][:2] == ["python3", "perfbench/run.py"]
    assert os.path.getsize(MANIFESTS[which]) < 64 * 1024


@pytest.mark.parametrize("which", sorted(MANIFESTS))
def test_names_units_and_lines_use_only_admitted_characters(which):
    m = load(MANIFESTS[which])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[group]]
        assert len(set(names)) == len(names), group
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(e["layer"]) <= 200 and "\n" not in e["layer"]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_a_configuration_and_a_traffic_file_that_exist(cell):
    w = next(x for x in M["workloads"] if x["name"] == cell)
    cfg = next(c for c in M["configs"] if c["name"] == w["config"])
    assert cfg["file"].startswith("perfbench/")
    body = load(os.path.join(ROOT, cfg["file"]))
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    traffic = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    assert traffic["kind"] in ("closed", "poisson", "sessions")
    assert body["chips"] == w["chips"]
    assert body["adapter"].startswith("perfbench.arch.")  # a cell's adapter lives with the benchmark


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(cell):
    e2e = [m["name"] for m in M["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in cells_of(m) for m in M["per_layer"])


def test_every_configuration_is_used_and_at_most_a_quarter_of_cells_take_four_chips():
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("metric,cell", PAIRS, ids=[f"{n}-{c}" for n, c in PAIRS])
def test_per_layer_metric_has_a_reader_file_and_moves_a_metric_its_cells_report(metric, cell):
    """One case a (metric, cell) READING, so that merging the copies of a
    metric into one entry with the list of its cells loses no case."""
    from perfbench import readers
    from perfbench.run import layer_metric_file

    m = next(x for x in M["per_layer"] if x["name"] == metric)
    assert "workloads" in m  # no list would mean every cell, the next PR's too
    spec = load(layer_metric_file(metric))
    assert spec["name"] in (metric, metric.rsplit(".", 1)[0])
    # a key of READERS, or module:function of a module under the manifest's paths
    assert callable(readers.resolve(spec["reader"], [os.path.join(ROOT, p) for p in M["paths"]]))
    target = next(x for x in M["end_to_end"] if x["name"] == m["moves"])
    assert cell in CELLS and cell in cells_of(target)  # the cell reports the end-to-end metric the entry moves
    assert m["workloads"] == sorted(set(m["workloads"]), key=CELLS.index)  # each cell once, in the manifest's order
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


CHAT, PHI, GLM, GIGA, TRINITY, SOLAR, KIMI, MINIMAX = ALL_EIGHT = (
    "chat_decode_7b", "reason_decode_phi4flash", "doc_reason_glm53flash", "doc_reason_gigachat35",
    "doc_reason_trinitymini", "chat_sessions_solaropen2", "agent_sessions_kimik25", "agent_files_minimaxm3")
# the 163 (metric, cell) readings of the manifest before PR 56 merged its 128 entries into one a metric
# (a copy `<base>.<suffix>` without a file of its own stands under its base name): a merge may lose none of them
PAIRS_BEFORE_THE_MERGE = {
    "decode_rows_mean": ALL_EIGHT,
    "decode_step_dev_ms": ALL_EIGHT,
    "tpot_chat_p50_ms": ALL_EIGHT,
    "page_attn_busy_share": (CHAT, PHI, TRINITY, SOLAR),
    "int8_matmul_busy_share": (CHAT,),
    "decode_step_roofline_share": (CHAT, PHI),
    "device_idle_share": ALL_EIGHT,
    "extend_dispatch_dev_ms": (CHAT, GLM, GIGA, TRINITY, SOLAR, KIMI, MINIMAX),
    "page_attn_pages_walked_mean": (CHAT, PHI, TRINITY, SOLAR),
    "itl_p99_ms": (CHAT,),
    "state_rows_mean": (PHI, GLM, GIGA, TRINITY, SOLAR),
    "window_tokens_read_mean": (PHI, TRINITY),
    "prefill_cross_skipped_share": (PHI,),
    "stream_backlog_tokens_mean": ALL_EIGHT,
    "extend_pad_tokens_mean": (CHAT,),
    "decode_step_roofline_share.glm53": (GLM,),
    "moe_experts_hit_share": (GLM, GIGA, TRINITY, SOLAR, KIMI, MINIMAX),
    "moe_pairs_per_expert_mean": (GLM, GIGA, TRINITY, SOLAR, KIMI, MINIMAX),
    "dsa_selected_share": (GLM,),
    "grouped_matmul_busy_share": (GLM, GIGA, TRINITY, SOLAR, KIMI, MINIMAX),
    "grouped_matmul_roofline_share": (GLM,),
    "latent_attn_busy_share": (GLM, GIGA, KIMI),
    "latent_attn_roofline_share": (GLM,),
    "decode_step_roofline_share.gigachat35": (GIGA,),
    "grouped_matmul_roofline_share.gigachat35": (GIGA,),
    "latent_attn_roofline_share.gigachat35": (GIGA,),
    "latent_tokens_read_mean": (GIGA, KIMI),
    "decode_step_done_ms": (CHAT, PHI, GLM, TRINITY, SOLAR, KIMI),
    "extend_wide_done_ms": (CHAT, PHI, GLM, TRINITY, SOLAR, KIMI),
    "extend_narrow_done_ms": (CHAT, PHI, SOLAR, KIMI),
    "extend_device_share": (CHAT, PHI, GLM, TRINITY, SOLAR, KIMI),
    "device_starved_share": (CHAT, PHI, GLM, TRINITY, SOLAR, KIMI),
    "device_hold_max_ms": (CHAT, PHI, GLM, TRINITY, SOLAR, KIMI),
    "grouped_matmul_roofline_share.trinity": (TRINITY,),
    "decode_step_roofline_share.trinity": (TRINITY,),
    "window_read_share.trinity": (TRINITY,),
    "prefix_reused_token_share.solaropen2": (SOLAR,),
    "prefix_state_copy_roofline_share.solaropen2": (SOLAR,),
    "prefix_state_copy_device_share.solaropen2": (SOLAR,),
    "decode_step_roofline_share.solaropen2": (SOLAR,),
    "grouped_matmul_roofline_share.solaropen2": (SOLAR,),
    "delta_step_roofline_share.solaropen2": (SOLAR,),
    "page_attn_roofline_share.solaropen2": (SOLAR,),
    "latent_attn_roofline_share.kimik25": (KIMI,),
    "grouped_matmul_roofline_share.kimik25": (KIMI,),
    "decode_step_roofline_share.kimik25": (KIMI,),
    "prefix_reused_token_share.kimik25": (KIMI,),
    "extend_prefix_depth_mean": (KIMI, MINIMAX),
    "grouped_matmul_roofline_share.minimaxm3": (MINIMAX,),
    "decode_step_roofline_share.minimaxm3": (MINIMAX,),
    "prefix_reused_token_share.minimaxm3": (MINIMAX,),
    "msa_selected_page_share": (MINIMAX,),
    "msa_read_busy_share": (MINIMAX,),
    "msa_index_busy_share": (MINIMAX,),
    "msa_read_roofline_share": (MINIMAX,),
    "gap_tail_extend_share": ALL_EIGHT,
}


def test_the_manifest_still_holds_every_reading_it_held_before_the_merge():
    assert sum(len(cells) for cells in PAIRS_BEFORE_THE_MERGE.values()) == 163
    held = set(PAIRS)
    lost = [(name, cell) for name, cells in PAIRS_BEFORE_THE_MERGE.items() for cell in cells if (name, cell) not in held]
    assert not lost
    assert len(M["per_layer"]) <= 128 and len(PAIRS) >= 163


# the dense family's cell has no adapter test of its own: what PRs 24-41 brought it stands here
FIRST_CELL = (
    "decode_rows_mean", "decode_step_dev_ms", "tpot_chat_p50_ms", "page_attn_busy_share", "int8_matmul_busy_share",
    "decode_step_roofline_share", "device_idle_share", "extend_dispatch_dev_ms", "page_attn_pages_walked_mean",
    "itl_p99_ms", "stream_backlog_tokens_mean", "extend_pad_tokens_mean", "decode_step_done_ms", "extend_wide_done_ms",
    "extend_narrow_done_ms", "extend_device_share", "device_starved_share", "device_hold_max_ms", "gap_tail_extend_share",
)


def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CHAT]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mistral-7b-v0.3-int8", "chat_decode", 1)
    mine = assert_cell_holds(manifest, CHAT, FIRST_CELL)
    assert not any(n.startswith(("moe_", "grouped_matmul", "latent_", "state_rows")) for n in mine)  # no expert, no state


def test_the_first_cells_entries_are_found_by_name_and_cell():
    assert_manifest_entries_of_the_cell(M)


def test_a_suffixed_metric_name_reads_the_file_of_its_base_name():
    from perfbench.run import layer_metric_file

    own = layer_metric_file("device_idle_share")
    assert own.endswith("device_idle_share.json") and os.path.exists(own)
    assert layer_metric_file("device_idle_share.serve") == own  # no copy of the file is needed
    assert layer_metric_file("decode_rows_mean") != own


@pytest.mark.parametrize("name", sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))))
def test_every_layer_metric_file_is_read_by_a_cell_of_some_manifest(name):
    used = {m["name"].rsplit(".", 1)[0] for path in MANIFESTS.values() for m in load(path)["per_layer"]}
    used |= {m["name"] for path in MANIFESTS.values() for m in load(path)["per_layer"]}
    assert name in used


@pytest.mark.parametrize("name", sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))))
def test_every_traffic_file_is_read_by_a_cell_of_some_manifest(name):
    assert name in {w["traffic"] for path in MANIFESTS.values() for w in load(path)["workloads"]}


@pytest.mark.parametrize("metric", E2E)
def test_end_to_end_metric_is_computed_by_the_harness(metric):
    from perfbench import reduce

    reqs = [{"frames_s": [0.5, 1.0, 1.5], "send_s": 0.1, "due_s": None, "end_s": 1.6, "status": "ok"}]
    values = dict(reduce.end_to_end(reqs, 0.0, 2.0), setup_s=1.0)
    assert values[metric] is not None and values[metric] > 0


def test_configuration_file_holds_the_published_sizes_and_reduces_no_width():
    cfg = load(os.path.join(BENCH, "configs", "mistral-7b-v0.3-int8.json"))
    published = {
        "hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "vocab_size": 32768, "rope_theta": 1000000.0, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 32768, "sliding_window": None, "tie_word_embeddings": False,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == []
    for key in cfg["reduced"]:
        assert not any(w in key for w in WIDTH_WORDS) and not key.endswith(("_dim", "_rank"))
    env = cfg["server_env"]
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == cfg["engine"]["kv_pool_pages"]
    assert int(env["APP_ENGINE_DECODEBLOCK"]) == cfg["engine"]["decode_block"]
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == cfg["engine"]["max_batch_size"]
    # the engine's default wave (16384 tokens) asks for extend programs that do not fit this chip
    assert int(env["APP_ENGINE_PREFILLWAVETOKENS"]) == cfg["engine"]["prefill_wave_tokens"] == 2048
    assert max(cfg["reference"]["served_only_prompt_tokens"]) > cfg["engine"]["prefill_chunk"]


def test_files_under_paths_are_named_from_admitted_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in M["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    peaks = load(os.path.join(BENCH, "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["_source"]
    assert "cpu" not in peaks  # an unknown kind is an error, not a default
