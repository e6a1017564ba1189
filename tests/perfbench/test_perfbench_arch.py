"""The harness's four seams: the adapter a configuration names, readers
named ``module:function``, the kernel-path and counter requirements a
configuration can add to, and the traced run's own window — and a
rehearsal run of a configuration whose adapter lives in this test tree
(``other_arch.py``), which no file of ``perfbench/`` knows."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import arch, readers, run, trace_reduce
from perfbench.arch import mistral

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")
ROOTS = [os.path.join(ROOT, "perfbench"), os.path.join(ROOT, "tests", "perfbench")]
OTHER = "tests.perfbench.other_arch"


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


MANIFESTS = [os.path.join(ROOT, "BENCHMARK.json"), os.path.join(BENCH, "rehearsal", "manifest.json")]
CONFIG_FILES = sorted({c["file"] for m in MANIFESTS for c in load(m)["configs"]})
MISTRAL = load(os.path.join(BENCH, "configs", "mistral-7b-v0.3-int8.json"))
TINY = load(os.path.join(BENCH, "configs", "debug-tiny.json"))


# --------------------------------------------------------------------------- #
# 1. the adapter


@pytest.mark.parametrize("file", CONFIG_FILES)
def test_every_configuration_names_an_importable_adapter_with_the_five_names(file):
    cfg = load(os.path.join(ROOT, file))
    module = arch.load(cfg, ROOTS)  # from under the manifest's paths
    assert module.__name__ == cfg["adapter"]
    for name in ("register", "engine_prefill_logits", "reference_logits", "decode_step_floor_s"):
        assert callable(getattr(module, name)), name
    assert 0 < module.TOLERANCE < 1 and "TOLERANCE" in module.__doc__  # the limit and where it came from


@pytest.mark.parametrize("cfg, error", [
    ({"name": "x"}, ValueError),                                  # no adapter key
    ({"name": "x", "adapter": "perfbench.reduce"}, ValueError),   # a module without the contract
    ({"name": "x", "adapter": "perfbench.arch.no_such"}, ImportError),
    ({"name": "x", "adapter": "generativeaiexamples_tpu.utils.slo"}, ValueError),  # the program is not the yardstick
])
def test_a_configuration_without_a_sound_adapter_fails_loudly(cfg, error):
    with pytest.raises(error):
        arch.load(cfg, ROOTS)


@pytest.mark.parametrize("file", ["launcher.py", "run.py", "readers.py", "reference.py"])
def test_the_harness_names_no_architecture(file):
    with open(os.path.join(BENCH, file), encoding="utf-8") as fh:
        text = fh.read().lower()
    for word in ("llama", "mistral", "rope", "rms_norm", "rotary", "swiglu"):
        assert word not in text, (file, word)


def test_importing_an_adapter_or_the_parent_does_not_import_jax():
    code = "import sys; import perfbench.run, perfbench.arch.mistral, tests.perfbench.other_arch; " \
           "sys.exit(1 if 'jax' in sys.modules else 0)"
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=dict(os.environ, PYTHONPATH=ROOT), timeout=120)
    assert proc.returncode == 0


# --------------------------------------------------------------------------- #
# 2. readers by name


def test_a_reader_resolves_by_key_or_as_a_function_of_a_benchmark_module():
    assert readers.resolve("span_mean", ROOTS) is readers.READERS["span_mean"]
    from tests.perfbench import other_arch

    assert readers.resolve(OTHER + ":decode_step_bytes_mean", ROOTS) is other_arch.decode_step_bytes_mean
    assert readers.resolve("perfbench.readers:device_idle_share", ROOTS) is readers.device_idle_share


@pytest.mark.parametrize("name, error", [
    ("no_such_reader", ValueError),                       # neither a key nor module:function
    ("perfbench.readers:", ValueError),
    ("perfbench.readers:no_such_function", ValueError),
    ("perfbench.readers:READERS", ValueError),            # there, but not callable
    ("json:loads", ValueError),                           # callable, but outside the benchmark's paths
    ("generativeaiexamples_tpu.utils.slo:anything", ValueError),  # the program is not the yardstick
    ("perfbench.arch.no_such_module:reader", ImportError),
])
def test_a_bad_reader_name_fails_loudly(name, error):
    with pytest.raises(error):
        readers.resolve(name, ROOTS)


def test_every_metric_file_names_a_reader_that_resolves_under_the_manifests_paths():
    for f in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
        spec = load(os.path.join(BENCH, "layer_metrics", f))
        assert callable(readers.resolve(spec["reader"], ROOTS)), f


def test_a_metric_file_beside_the_manifest_is_found_before_the_benchmarks_own(tmp_path):
    os.makedirs(tmp_path / "layer_metrics")
    mine = tmp_path / "layer_metrics" / "my_metric.json"
    mine.write_text('{"name": "my_metric", "reader": "span_mean", "params": {}}')
    assert run.layer_metric_file("my_metric", str(tmp_path)) == str(mine)
    assert run.layer_metric_file("my_metric.serve", str(tmp_path)) == str(mine)
    own = os.path.join(BENCH, "layer_metrics", "device_idle_share.json")
    assert run.layer_metric_file("device_idle_share", str(tmp_path)) == own
    assert run.layer_metric_file("device_idle_share") == own


# --------------------------------------------------------------------------- #
# 3. the roofline share through the adapter equals what the old function read


def old_decode_roofline_share(ctx, p):
    """``readers.decode_roofline_share`` with ``mean_live_tokens`` and the
    ``shapes`` calls as they stood before the adapters (PR 27), verbatim."""
    def mean_live_tokens(ctx):
        rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
        ctxs = []
        for tl in ctx["flight"]:
            prompt = readers.event_attr(tl, "submit", "prompt_tokens")
            gen = readers.event_attr(tl, "engine_finish", "generated")
            if prompt is not None and gen is not None:
                ctxs.append(prompt + gen / 2.0)
        if rows is None or not ctxs:
            return None
        return rows * sum(ctxs) / len(ctxs)

    step_ms = ctx["read"](p["time_metric"])
    rows = readers.span_mean(ctx, {"kind": "decode", "field": "rows"})
    live = mean_live_tokens(ctx)
    if not step_ms or rows is None or live is None:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    t_bytes = mistral.decode_step_bytes(cfg, rows, live) / peaks["hbm_bytes_per_s"]
    t_flops = mistral.decode_step_flops(cfg, rows, live) / peaks["int8_ops_per_s"]
    return 100.0 * max(t_bytes, t_flops) / (step_ms / 1000.0)


def roofline_ctx(rows, requests, step_ms, peaks=None):
    return {
        "config": MISTRAL, "adapter": mistral, "read": lambda name: step_ms,
        "peaks": peaks or load(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"],
        "spans": [{"kind": "decode", "category": "dispatch", "rows": r} for r in rows],
        "flight": [{"timeline": [{"event": "submit", "t_s": 0, "prompt_tokens": p},
                                 {"event": "engine_finish", "t_s": 1, "generated": g}]} for p, g in requests],
    }


HAND_INPUTS = [
    ([64], [(258, 384)], 38.0),
    ([43, 44, 41, 47, 39], [(330, 256), (455, 384), (580, 512), (331, 512)], 20.4),
    ([1, 2, 64], [(4000, 96)], 12.0),
    ([7] * 13, [(333 + 7 * i, 256 + i) for i in range(29)], 17.9),
    ([64], [(300, 256)], 0.9),  # so many operations in so short a step that they, not the bytes, bound it
]


@pytest.mark.parametrize("rows, requests, step_ms", HAND_INPUTS)
def test_roofline_share_through_the_adapter_equals_the_old_function_on_hand_inputs(rows, requests, step_ms):
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12} if step_ms > 1 else \
        {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e9}
    ctx = roofline_ctx(rows, requests, step_ms, peaks)
    new = readers.resolve("decode_roofline_share", ROOTS)(ctx, {"time_metric": "decode_step_dev_ms"})
    assert new == old_decode_roofline_share(ctx, {"time_metric": "decode_step_dev_ms"})  # to the last digit
    assert new > 0


def test_roofline_share_through_the_adapter_equals_what_the_chip_run_of_pr_27_printed():
    fx = load(os.path.join(BENCH, "fixtures", "chip_roofline_inputs.json"))
    ctx = roofline_ctx(fx["decode_span_rows"], fx["finished_requests_prompt_and_generated"], None)
    ctx["trace"] = {"devices": 1, "modules": fx["decode_modules"]}
    ctx["config"] = dict(MISTRAL, engine=dict(MISTRAL["engine"], decode_block=fx["decode_block"]))
    spec = load(os.path.join(BENCH, "layer_metrics", "decode_step_dev_ms.json"))
    ctx["read"] = lambda name: readers.resolve(spec["reader"], ROOTS)(ctx, spec["params"])
    assert ctx["read"]("decode_step_dev_ms") == fx["recorded_decode_step_dev_ms"]
    spec2 = load(os.path.join(BENCH, "layer_metrics", "decode_step_roofline_share.json"))
    new = readers.resolve(spec2["reader"], ROOTS)(ctx, spec2["params"])
    assert new == fx["recorded_decode_step_roofline_share"] == old_decode_roofline_share(ctx, spec2["params"])


def test_roofline_share_on_the_recorded_trace_fixture():
    with open(os.path.join(BENCH, "fixtures", "chip_trace_events.json"), encoding="utf-8") as fh:
        summary = trace_reduce.reduce_events([tuple(e) for e in json.load(fh)["events"]])
    ctx = roofline_ctx([56, 57], [(400, 384), (520, 256)], None)
    ctx["trace"] = summary
    ctx["read"] = lambda name: readers.device_module_ms(ctx, {"match": "^jit_decode", "divide_by_engine": "decode_block"})
    new = readers.decode_roofline_share(ctx, {"time_metric": "decode_step_dev_ms"})
    assert new is not None and new == old_decode_roofline_share(ctx, {"time_metric": "decode_step_dev_ms"})


def test_the_mistral_floor_is_the_larger_of_bytes_and_int8_operations():
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    rows, context = 64, 450.0
    floor = mistral.decode_step_floor_s(MISTRAL, peaks, rows, context)
    assert floor == mistral.decode_step_bytes(MISTRAL, rows, rows * context) / 819e9  # HBM-bound at these shapes
    slow_mxu = dict(peaks, int8_ops_per_s=1e12)
    assert mistral.decode_step_floor_s(MISTRAL, slow_mxu, rows, context) \
        == mistral.decode_step_flops(MISTRAL, rows, rows * context) / 1e12


def test_a_reader_that_finds_nothing_returns_nothing_not_zero():
    ctx = roofline_ctx([], [], None)
    assert readers.decode_roofline_share(ctx, {"time_metric": "decode_step_dev_ms"}) is None
    assert readers.mean_decode_context(ctx) is None


# --------------------------------------------------------------------------- #
# 4. what `correct` requires, and what a configuration can add to it

LINE = ("INFO resolved kernel paths: quant_kernel={q} kv_kernel=False paged_kernel={p} "
        "paged_verify_kernel=compiled tp_kernels=None (backend=tpu, devices=1)\n")
BF16 = dict(MISTRAL, server_env=dict(MISTRAL["server_env"], APP_ENGINE_QUANTIZATION="none"))
NO_KEY = dict(MISTRAL, server_env={k: v for k, v in MISTRAL["server_env"].items() if k != "APP_ENGINE_QUANTIZATION"})
W8A8 = dict(MISTRAL, server_env=dict(MISTRAL["server_env"], APP_ENGINE_QUANTIZATION="w8a8"))
SCAN = dict(BF16, correct={"kernel_paths": {"scan_kernel": "compiled"}})
WEAKER = dict(MISTRAL, correct={"kernel_paths": {"paged_kernel": "interpret", "quant_kernel": "False"}})


@pytest.mark.parametrize("cfg, q, p, extra, on_tpu, n_problems", [
    (MISTRAL, "True", "compiled", "", True, 0),          # the int8 file, as before
    (MISTRAL, "False", "compiled", "", True, 1),         # int8 weights on the XLA path
    (MISTRAL, "True", "interpret", "", True, 1),
    (MISTRAL, "False", "False", "", True, 1),            # one problem names both
    (MISTRAL, "False", "interpret", "", False, 0),       # a CPU rehearsal is not asked for compiled kernels
    (BF16, "False", "compiled", "", True, 0),            # bfloat16 weights: no int8 kernel to ask for
    (NO_KEY, "False", "compiled", "", True, 0),          # the engine's default is unquantised
    (BF16, "False", "False", "", True, 1),               # the page kernel is asked of every cell
    (W8A8, "w8a8", "compiled", "", True, 0),             # the engine prints that format's own name
    (W8A8, "True", "compiled", "", True, 1),
    (SCAN, "False", "compiled", " scan_kernel=compiled", True, 0),
    (SCAN, "False", "compiled", "", True, 1),            # an added requirement missing from the line
    (SCAN, "False", "compiled", " scan_kernel=xla", True, 1),
    (SCAN, "False", "interpret", "", False, 1),          # what a configuration adds holds wherever it runs
    (WEAKER, "True", "compiled", "", True, 1),           # a configuration cannot turn a default around
    (WEAKER, "False", "interpret", "", True, 1),
])
def test_kernel_path_requirements(cfg, q, p, extra, on_tpu, n_problems):
    text = LINE.format(q=q, p=p).replace(" tp_kernels", extra + " tp_kernels")
    problems = run.check_server_log(text, on_tpu, cfg)
    assert len(problems) == n_problems, problems


def test_server_log_faults_are_still_raised():
    ok = LINE.format(q="True", p="compiled")
    assert run.check_server_log("INFO nothing resolved\n", True, MISTRAL) == ["server log has no 'resolved kernel paths' line"]
    assert run.check_server_log(ok + "Traceback (most recent call last):\n", True, MISTRAL) == ["server log holds a traceback"]
    assert run.check_server_log(ok + "WARNING COMPILE ON HOT PATH jit_x\n", True, MISTRAL) \
        == ["server log reports a compile on the hot path"]
    assert "quant_kernel=False (want True)" in run.check_server_log(LINE.format(q="False", p="compiled"), True, MISTRAL)[0]


def scrape(kernel=0.0, gather=0.0, hot=0.0, scan=0.0, fallback=0.0):
    return readers.parse_metrics(
        f'genai_engine_paged_attn_dispatches_total{{path="kernel"}} {kernel}\n'
        f'genai_engine_paged_attn_dispatches_total{{path="gather"}} {gather}\n'
        f'genai_engine_hot_path_compiles_total {hot}\n'
        f'genai_engine_scan_dispatches_total{{path="kernel"}} {scan}\n'
        f'genai_engine_scan_dispatches_total{{path="xla"}} {fallback}\n')


ADDED = dict(MISTRAL, correct={
    "counters_must_grow": [{"metric": "genai_engine_scan_dispatches_total", "labels": {"path": "kernel"}}],
    "counters_must_not_grow": [{"metric": "genai_engine_scan_dispatches_total", "labels": {"path": "xla"}}],
})


@pytest.mark.parametrize("cfg, before, after, want", [
    (MISTRAL, scrape(kernel=5), scrape(kernel=9), []),
    (MISTRAL, scrape(kernel=5), scrape(kernel=5), ["kernel"]),            # no kernel dispatch in the window
    (MISTRAL, scrape(kernel=5), scrape(kernel=9, gather=1), ["gather"]),
    (MISTRAL, scrape(kernel=5, hot=2), scrape(kernel=9, hot=3), ["hot_path"]),
    (MISTRAL, scrape(kernel=5, hot=2), scrape(kernel=9, hot=2), []),      # a compile before the window is set-up
    (ADDED, scrape(kernel=5, scan=1), scrape(kernel=9, scan=4), []),
    (ADDED, scrape(kernel=5, scan=1), scrape(kernel=9, scan=1), ["scan"]),   # an added counter that did not grow
    (ADDED, scrape(kernel=5), scrape(kernel=9, scan=4, fallback=2), ["xla"]),
    (ADDED, scrape(scan=1), scrape(scan=4), ["kernel"]),                  # the defaults stay for every cell
    (dict(MISTRAL, correct={"counters_must_grow": [], "counters_must_not_grow": []}),
     scrape(), scrape(gather=1, hot=1), ["kernel", "gather", "hot_path"]),  # empty lists drop nothing
])
def test_counter_requirements(cfg, before, after, want):
    problems, readings = run.check_counters(before, after, cfg)
    assert len(problems) == len(want), problems
    for word, problem in zip(want, problems):
        assert word in problem
    n_specs = 3 + sum(len(v) for v in cfg.get("correct", {}).values())
    assert len(readings) == n_specs and all("grew by" in r for r in readings)  # each number beside its limit


# --------------------------------------------------------------------------- #
# 5. the traced run's window


@pytest.mark.parametrize("traffic, seconds, trace, want", [
    ({"traced_run_window_s": 20.0}, 51, 1, 20.0),
    ({"traced_run_window_s": 20.0}, 51, 0, 51.0),   # an untraced run ignores it
    ({}, 51, 1, 51.0),                               # a mix that says nothing traces a whole-length run
    ({}, 4, 0, 4.0),
])
def test_traced_window_comes_from_the_traffic_file_and_an_untraced_run_ignores_it(traffic, seconds, trace, want):
    assert run.window_seconds(traffic, seconds, trace) == want


def test_spans_recorded_after_the_window_are_not_the_windows():
    """A traced run serves on while the profiler writes its capture (40-60 s
    on the chip, the server starved beside it: rows per dispatch fall from
    ~44 to ~12); those spans must not reach the span readers."""
    spans = [{"kind": "decode", "rows": 44, "t_wall": 1000.0 + t} for t in (0.0, 5.0, 19.9)]
    late = [{"kind": "decode", "rows": 12, "t_wall": 1000.0 + t} for t in (20.0, 35.0)]
    early = [{"kind": "decode", "rows": 17, "t_wall": 999.9}]
    unclocked = [{"kind": "decode", "rows": 40}]
    kept = run.spans_in_window(early + spans + late + unclocked, 1000.0, 20.0)
    assert kept == spans + unclocked
    assert readers.span_mean({"spans": kept}, {"kind": "decode", "field": "rows"}) == 43.0


@pytest.mark.parametrize("n_items", [0, 499, 500, 501, 1234])
def test_a_scrape_follows_full_pages_from_the_last_item_not_from_the_newest_cursor(monkeypatch, n_items):
    """Both ``?since=`` endpoints of the program cap a page and return the
    NEWEST cursor of the process; following that lost every span past the
    500th (the last ~8 s of a 51 s window)."""
    store = [{"seq": 100 + i, "rows": i} for i in range(n_items)]
    calls = []

    def fake(host, port, method, path, **kw):
        since = int(path.split("since=")[1].split("&")[0])
        calls.append(since)
        page = [it for it in store if it["seq"] > since][:500]
        return 200, json.dumps({"spans": page, "cursor": 100 + n_items - 1 if store else 7}).encode()

    monkeypatch.setattr(run.loadgen, "http_call", fake)
    items, cursor = run.scrape_all("h", 1, "/internal/timeline", "spans", 7)
    assert items == store and len(calls) == n_items // 500 + 1
    assert cursor == (100 + n_items - 1 if store else 7)


def test_the_heartbeat_keeps_the_longest_gap_of_its_own_wake_ups():
    hb = run.Heartbeat()
    hb.start()
    import time
    time.sleep(0.15)
    gap = hb.stop()
    assert 0.015 < gap < 0.15 and not hb.is_alive()


def test_the_chat_mix_keeps_its_untraced_run_and_shortens_only_the_traced_one():
    t = load(os.path.join(BENCH, "traffic", "chat_decode.json"))
    assert t["traced_run_window_s"] == 20.0 and t["trace_window_s"] == 2.5
    assert t["ramp"] == {"expected_request_s": 24.0, "cap_s": 60.0} and t["clients"] == 64
    assert run.window_seconds(t, load(MANIFESTS[0])["run_seconds"], 0) == 51.0


# --------------------------------------------------------------------------- #
# 6. two references written differently agree; a rehearsal run on the test-tree adapter


def test_the_test_tree_reference_agrees_with_the_mistral_reference_on_hand_weights():
    from tests.perfbench import other_arch

    rng = np.random.default_rng(3)
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "hidden_size": 16,
           "intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 1e4, "num_hidden_layers": 2}
    shapes = {"wq": (16, 32), "wk": (16, 16), "wv": (16, 16), "wo": (32, 16),
              "w_gate": (16, 32), "w_up": (16, 32), "w_down": (32, 16)}
    layers = [dict({k: rng.normal(size=s).astype(np.float32) * 0.3 for k, s in shapes.items()},
                   attn_norm=rng.uniform(0.5, 1.5, 16).astype(np.float32),
                   mlp_norm=rng.uniform(0.5, 1.5, 16).astype(np.float32)) for _ in range(2)]
    embed = rng.normal(size=(11, 16)).astype(np.float32)
    head = rng.normal(size=(16, 11)).astype(np.float32)
    final = rng.uniform(0.5, 1.5, 16).astype(np.float32)
    seqs = [[1, 2, 3, 4, 5, 6, 7], [10, 0, 9]]
    a = mistral.forward(seqs, cfg, embed, lambda i: layers[i], final, head)
    b = [other_arch.reference_sequence(s, cfg, embed, layers, final, head.astype(np.float64)) for s in seqs]
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def other_run(tmp_path_factory):
    """A manifest, a configuration and a metric file in a temporary
    directory: debug-tiny's sizes under another name, with the adapter of
    this test tree, a reader of that adapter and two added counters."""
    tmp = tmp_path_factory.mktemp("other_arch")
    cfg = dict(TINY, name="other-tiny", adapter=OTHER, correct={
        "kernel_paths": {"paged_kernel": "interpret"},
        "counters_must_grow": [{"metric": "genai_engine_timeline_spans_total"}],
        "counters_must_not_grow": [{"metric": "genai_engine_handoff_recompute_total"}],
    })
    (tmp / "other-tiny.json").write_text(json.dumps(cfg))
    os.makedirs(tmp / "layer_metrics")
    (tmp / "layer_metrics" / "other_decode_step_bytes.json").write_text(json.dumps(
        {"name": "other_decode_step_bytes", "reader": OTHER + ":decode_step_bytes_mean", "params": {"scale": 1.0}}))
    rehearsal = load(MANIFESTS[1])
    manifest = dict(
        rehearsal,
        configs=[{"name": "other-tiny", "source": cfg["source"], "file": str(tmp / "other-tiny.json"),
                  "reduced": [], "why": "the seam, not an architecture"}],
        workloads=[{"name": "other_closed", "config": "other-tiny", "traffic": "rehearsal_closed", "chips": 1,
                    "why": "the rehearsal's closed loop on a configuration of the test-tree adapter"}],
        per_layer=[m for m in rehearsal["per_layer"] if m["name"] == "decode_rows_mean"] + [
            {"name": "other_decode_step_bytes", "unit": "bytes", "better": "lower", "source": "program_counter",
             "layer": "kernels", "moves": "out_tok_s"}],
    )
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest", str(tmp / "manifest.json"),
         "--workload", "other_closed", "--seed", "2147484001", "--seconds", "4", "--trace", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)


def lines_of(proc, prefix):
    return [ln for ln in proc.stdout.splitlines() if ln.startswith(prefix)]


def test_a_configuration_of_the_test_tree_adapter_runs_through_the_rehearsal(other_run):
    assert other_run.returncode == 1, other_run.stdout[-3000:] + other_run.stderr[-2000:]
    line = json.loads(other_run.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {} and line["attempted"] > 0 and line["failed"] == 0
    # registered, served, compared with ITS reference; the only fault of a rehearsal is the platform
    assert lines_of(other_run, "not correct:") == ["not correct: platform is 'cpu', not 'tpu'"]
    ref = json.loads(lines_of(other_run, "reference: ")[0][len("reference: "):])
    assert ref["ok"] is True and ref["decode_tokens_checked"] == 12 and len(ref["prefill_rel_err"]) == 2


def test_the_rehearsal_read_the_adapters_own_reader_and_checked_its_added_counters(other_run):
    values = json.loads(lines_of(other_run, "rehearsal values")[0].split(": ", 1)[1])
    assert values["other_decode_step_bytes"] > 0 and values["decode_rows_mean"] >= 1
    counters = lines_of(other_run, "counters: ")[0]
    assert "genai_engine_timeline_spans_total grew by" in counters
    assert "genai_engine_handoff_recompute_total grew by 0" in counters
    assert 'genai_engine_paged_attn_dispatches_total{path="kernel"} grew by' in counters  # the defaults stay
    # every number compared stands beside its limit at the end of stderr too
    tail = other_run.stderr.strip().splitlines()[-12:]
    assert any(ln.startswith("reference prefill_rel_err") and "limit 0.04" in ln for ln in tail), tail
    assert tail[-1] == "not correct: platform is 'cpu', not 'tpu'"


def test_the_traced_rehearsal_took_its_window_from_the_traffic_file(other_run):
    window = json.loads(lines_of(other_run, "window: ")[0][len("window: "):])
    assert 1.9 < window["seconds"] < 2.6  # rehearsal_closed.json: traced_run_window_s 2, while --seconds said 4


def test_no_file_of_the_benchmark_names_the_test_tree_adapter():
    for d, _, files in os.walk(BENCH):
        for f in files:
            if f.endswith((".py", ".json", ".sh")):
                with open(os.path.join(d, f), encoding="utf-8") as fh:
                    assert "other_arch" not in fh.read(), os.path.join(d, f)
