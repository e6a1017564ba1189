"""BENCHMARK.json and every data file it names load and cross-reference."""
import json
import os
import re

import pytest

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
LAYER = [m["name"] for m in M["per_layer"]]


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


@pytest.mark.parametrize("metric", LAYER)
def test_per_layer_metric_has_a_reader_file_and_moves_a_metric_its_cells_report(metric):
    from perfbench import readers

    m = next(x for x in M["per_layer"] if x["name"] == metric)
    from perfbench.run import layer_metric_file

    spec = load(layer_metric_file(metric))
    assert spec["name"] in (metric, metric.rsplit(".", 1)[0])
    # a key of READERS, or module:function of a module under the manifest's paths
    assert callable(readers.resolve(spec["reader"], [os.path.join(ROOT, p) for p in M["paths"]]))
    target = next(x for x in M["end_to_end"] if x["name"] == m["moves"])
    assert set(cells_of(m)) <= set(cells_of(target))
    same_layer = {x["layer"] for x in M["per_layer"]}
    assert m["layer"] in same_layer
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


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
