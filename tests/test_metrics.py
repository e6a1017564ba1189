"""Metrics registry: exposition-format round-trip, trace exemplars, the
/metrics endpoints on both servers, and the profiler-capture endpoints.

Covers the observability acceptance contract:
- /metrics on the chain-server serves valid 0.0.4 exposition text with
  Counter+Gauge+Histogram families from the engine, server-middleware
  and retrieval layers — parsed and validated, not just substring-matched;
- a scrape with no engine built never constructs one;
- engine scheduling histograms carry trace-id exemplars when tracing is
  enabled (memory exporter).
"""
import asyncio
import math
import queue
import re
import threading
import time
import types

import numpy as np

from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.chains.echo import EchoChain
from generativeaiexamples_tpu.utils import tracing
from generativeaiexamples_tpu.utils.metrics import (
    CONTENT_TYPE_LATEST,
    MetricsRegistry,
    current_trace_id_hex,
    get_registry,
)


# --------------------------------------------------------------------------- #
# A small exposition-format parser (the acceptance criterion asks for
# parser-verified output, not substring checks).

_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)(?: .*)?$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(value: str) -> str:
    out, i = [], 0
    while i < len(value):
        if value[i] == "\\" and i + 1 < len(value):
            out.append({"n": "\n", "\\": "\\", '"': '"'}.get(value[i + 1], value[i + 1]))
            i += 2
        else:
            out.append(value[i])
            i += 1
    return "".join(out)


def _parse_value(raw: str) -> float:
    if raw == "+Inf":
        return math.inf
    if raw == "-Inf":
        return -math.inf
    return float(raw)


def parse_exposition(text: str):
    """Parse 0.0.4 text into {family: {"type", "help", "samples"}} where
    samples are (sample_name, labels_dict, value). Raises on malformed
    lines, samples without TYPE metadata, or duplicate TYPE lines."""
    families = {}
    for line in text.splitlines():
        if not line or line == "# EOF":
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            families.setdefault(name, {"samples": []})["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, typ = rest.partition(" ")
            fam = families.setdefault(name, {"samples": []})
            assert "type" not in fam, f"duplicate TYPE for {name}"
            fam["type"] = typ
            continue
        assert not line.startswith("#"), f"unexpected comment line: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        sample_name, raw_labels, raw_value = m.groups()
        family = re.sub(r"_(bucket|sum|count)$", "", sample_name)
        if family not in families:
            family = sample_name
        if family not in families and sample_name.endswith("_total"):
            # OpenMetrics counters: the family declares the bare name,
            # samples append _total
            family = sample_name[: -len("_total")]
        assert family in families, f"sample {sample_name} has no TYPE metadata"
        labels = {
            k: _unescape(v) for k, v in _LABEL_RE.findall(raw_labels or "")
        }
        families[family]["samples"].append(
            (sample_name, labels, _parse_value(raw_value))
        )
    return families


def validate_histograms(families) -> None:
    """Bucket monotonicity and _sum/_count consistency for every
    histogram family in a parsed exposition."""
    for name, fam in families.items():
        if fam.get("type") != "histogram":
            continue
        series = {}
        for sample_name, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
            entry = series.setdefault(key, {"buckets": [], "sum": None, "count": None})
            if sample_name == name + "_bucket":
                entry["buckets"].append((_parse_value(labels["le"]), value))
            elif sample_name == name + "_sum":
                entry["sum"] = value
            elif sample_name == name + "_count":
                entry["count"] = value
        for key, entry in series.items():
            assert entry["sum"] is not None, f"{name}{key}: missing _sum"
            assert entry["count"] is not None, f"{name}{key}: missing _count"
            buckets = sorted(entry["buckets"])
            assert buckets, f"{name}{key}: no buckets"
            assert buckets[-1][0] == math.inf, f"{name}{key}: no +Inf bucket"
            counts = [c for _, c in buckets]
            assert counts == sorted(counts), f"{name}{key}: buckets not monotone"
            assert counts[-1] == entry["count"], f"{name}{key}: +Inf != _count"
            if entry["count"] == 0:
                assert entry["sum"] == 0.0


# --------------------------------------------------------------------------- #
# Registry unit tests


def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("genai_test_ops_total", "ops", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    g = reg.gauge("genai_test_depth", "depth")
    g.set(4)
    g.dec()
    h = reg.histogram("genai_test_wait_seconds", "wait", buckets=(0.1, 1.0))
    h.observe(0.05, trace_id=None)
    h.observe(0.5, trace_id=None)
    h.observe(99.0, trace_id=None)

    families = parse_exposition(reg.render())
    validate_histograms(families)
    assert families["genai_test_ops_total"]["type"] == "counter"
    (sample,) = families["genai_test_ops_total"]["samples"]
    assert sample == ("genai_test_ops_total", {"kind": "a"}, 3.0)
    (gauge_sample,) = families["genai_test_depth"]["samples"]
    assert gauge_sample[2] == 3.0
    hist = {
        s[0]: s for s in families["genai_test_wait_seconds"]["samples"]
        if s[0].endswith(("_sum", "_count"))
    }
    assert hist["genai_test_wait_seconds_count"][2] == 3
    assert abs(hist["genai_test_wait_seconds_sum"][2] - 99.55) < 1e-9


def test_openmetrics_counter_family_name_drops_total():
    """OpenMetrics HELP/TYPE declare the bare counter family name and
    only samples carry ``_total`` (strict OM parsers reject suffixed
    declarations); the 0.0.4 rendering keeps the legacy full name."""
    reg = MetricsRegistry()
    c = reg.counter("genai_test_sent_total", "sent", ("kind",))
    c.labels(kind="x").inc(2)

    om = parse_exposition(reg.render(openmetrics=True))
    assert "genai_test_sent_total" not in om  # no suffixed declaration
    fam = om["genai_test_sent"]
    assert fam["type"] == "counter"
    (sample,) = fam["samples"]
    assert sample == ("genai_test_sent_total", {"kind": "x"}, 2.0)

    legacy = parse_exposition(reg.render())
    assert legacy["genai_test_sent_total"]["type"] == "counter"


def test_label_escaping_roundtrip():
    reg = MetricsRegistry()
    nasty = 'quote " backslash \\ newline \n done'
    reg.counter("genai_test_escape_total", "escapes", ("path",)).labels(
        path=nasty
    ).inc()
    families = parse_exposition(reg.render())
    (sample,) = families["genai_test_escape_total"]["samples"]
    assert sample[1]["path"] == nasty


def test_counter_rejects_negative_and_type_conflicts():
    import pytest

    reg = MetricsRegistry()
    c = reg.counter("genai_test_neg_total", "x")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        reg.gauge("genai_test_neg_total", "same name, different type")
    with pytest.raises(ValueError):
        reg.counter("genai_test_neg_total", "same type, different labels", ("a",))
    # identical re-registration is idempotent
    assert reg.counter("genai_test_neg_total", "x") is c


def test_histogram_exemplar_attached_under_active_span():
    exporter = tracing.InMemorySpanExporter()
    tracer = tracing.Tracer(exporter=exporter, flush_interval=0.1)
    tracing.set_tracer(tracer)
    try:
        reg = MetricsRegistry()
        h = reg.histogram("genai_test_exemplar_seconds", "x", buckets=(1.0,))
        with tracer.span("work") as span:
            trace_hex = f"{span.context.trace_id:032x}"
            h.observe(0.5)  # auto-resolves the active trace
        tracer.force_flush()
        (exemplar,) = h.exemplars()
        assert exemplar.trace_id == trace_hex
        assert exemplar.value == 0.5
        # exported span carries the SAME trace id — the exemplar links
        (exported,) = exporter.spans
        assert f"{exported.context.trace_id:032x}" == trace_hex
        # 0.0.4 output omits exemplars; OpenMetrics output carries them
        assert "trace_id" not in reg.render()
        om = reg.render(openmetrics=True)
        assert f'# {{trace_id="{trace_hex}"}} 0.5' in om
        assert om.rstrip().endswith("# EOF")
    finally:
        tracing.reset_tracer()


def test_no_exemplar_without_tracing():
    reg = MetricsRegistry()
    h = reg.histogram("genai_test_noexemplar_seconds", "x", buckets=(1.0,))
    h.observe(0.5)
    assert h.exemplars() == []


# --------------------------------------------------------------------------- #
# Engine-layer exemplars (acceptance: queue_wait/ttft/per-token latency
# carry trace ids when ENABLE_TRACING=true, via the memory exporter).
# The engine cannot build on this environment's jax, so the test drives
# the REAL submit-capture and _emit accounting paths on a stub engine.


def test_engine_histograms_carry_trace_exemplars():
    from generativeaiexamples_tpu.engine import llm_engine

    exporter = tracing.InMemorySpanExporter()
    tracer = tracing.Tracer(exporter=exporter, flush_interval=0.1)
    tracing.set_tracer(tracer)
    try:
        with tracer.span("POST /generate") as span:
            trace_hex = f"{span.context.trace_id:032x}"
            # submit()'s capture line: the active trace rides the request
            req = llm_engine._Request(
                rid=999999,
                prompt_ids=[1, 2],
                params=llm_engine.SamplingParams(max_tokens=8),
                t_submit=time.time(),
                trace_hex=current_trace_id_hex(),
            )
        assert req.trace_hex == trace_hex
        req.t_admit = time.time()
        # _admit()'s queue-wait observation
        llm_engine._M_QUEUE_WAIT.observe(
            req.t_admit - req.t_submit, trace_id=req.trace_hex
        )
        # reader-thread emissions: first token -> TTFT + prefill wait;
        # later tokens -> inter-token latency. _emit is the real method,
        # driven on a stub engine (no device needed for accounting).
        stub = types.SimpleNamespace(
            _stop_ids=set(),
            max_seq_len=64,
            _release_q=queue.Queue(),
            _lock=threading.Condition(),
        )
        llm_engine.LLMEngine._emit(stub, req, np.array([5]))
        llm_engine.LLMEngine._emit(stub, req, np.array([6]))
        for hist in (
            llm_engine._M_QUEUE_WAIT,
            llm_engine._M_TTFT,
            llm_engine._M_PREFILL_WAIT,
            llm_engine._M_TOKEN_LATENCY,
        ):
            assert any(
                e.trace_id == trace_hex for e in hist.exemplars()
            ), f"no exemplar with the request's trace id on {hist.name}"
        tracer.force_flush()
        assert any(
            f"{s.context.trace_id:032x}" == trace_hex for s in exporter.spans
        )
    finally:
        tracing.reset_tracer()


def test_legacy_metrics_dict_keys_derive_from_registry():
    """The tools and /internal/metrics read the flat dict view;
    its keys must track the registry families."""
    from generativeaiexamples_tpu.engine import llm_engine

    stub = types.SimpleNamespace()
    m = llm_engine.LLMEngine.metrics.fget(stub)
    for key in (
        "generated_tokens", "requests", "decode_steps", "admission_waves",
        "prefill_chunks", "queue_wait_sum", "queue_wait_n", "ttft_sum",
        "ttft_n", "prefill_wait_sum", "decode_dispatches",
        "spec_drafted_tokens", "spec_accepted_tokens",
        "spec_acceptance_rate", "spec_tokens_per_step",
    ):
        assert key in m
    before = m["generated_tokens"]
    llm_engine._M_TOKENS.inc()
    assert llm_engine.LLMEngine.metrics.fget(stub)["generated_tokens"] == before + 1
    # the spec-decode derived rates track the registry families too
    from generativeaiexamples_tpu.engine import spec_decode

    d0 = m["spec_drafted_tokens"]
    a0 = m["spec_accepted_tokens"]
    spec_decode.record_dispatch(drafted=4, accepted=2)
    m2 = llm_engine.LLMEngine.metrics.fget(stub)
    assert m2["spec_drafted_tokens"] == d0 + 4
    assert m2["spec_accepted_tokens"] == a0 + 2
    assert 0.0 < m2["spec_acceptance_rate"] <= 1.0
    assert m2["spec_tokens_per_step"] >= 1.0


# --------------------------------------------------------------------------- #
# HTTP endpoints


def _run(coro_fn, app_factory):
    async def _go():
        app = app_factory()
        async with TestClient(TestServer(app)) as client:
            return await coro_fn(client)

    return asyncio.run(_go())


def test_chain_server_metrics_scrape_without_building_engine(tmp_path):
    """GET /metrics serves 0.0.4 exposition with families from three
    layers (engine, http middleware, retrieval) — and never builds an
    engine."""
    import numpy as np

    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.retrieval.store import Chunk
    from generativeaiexamples_tpu.retrieval.tpu_store import TPUVectorStore
    from generativeaiexamples_tpu.server.api import create_app

    # retrieval-layer samples (store add + search) without any engine
    store = TPUVectorStore(4, persist_dir=str(tmp_path), collection="m")
    store.add([Chunk(text="alpha", source="d.txt")], np.eye(1, 4, dtype=np.float32))
    store.search(np.ones(4, np.float32), top_k=1)

    saved = llm_engine._ENGINE
    llm_engine._ENGINE = None
    try:
        async def scenario(client):
            await client.get("/health")
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
            body = await resp.text()
            om = await client.get(
                "/metrics", headers={"Accept": "application/openmetrics-text"}
            )
            assert om.headers["Content-Type"].startswith("application/openmetrics-text")
            assert (await om.text()).rstrip().endswith("# EOF")
            return body

        body = _run(scenario, lambda: create_app(EchoChain))
        assert llm_engine._ENGINE is None, "a metrics scrape built the engine!"
    finally:
        llm_engine._ENGINE = saved

    families = parse_exposition(body)
    validate_histograms(families)
    # engine layer: counter + gauge + histogram
    assert families["genai_engine_requests_total"]["type"] == "counter"
    assert families["genai_engine_batch_slots_in_use"]["type"] == "gauge"
    assert families["genai_engine_ttft_seconds"]["type"] == "histogram"
    # server middleware layer: the /health request left a labelled sample
    http = families["genai_http_requests_total"]
    assert http["type"] == "counter"
    assert any(
        labels.get("route") == "/health" and labels.get("status") == "200"
        for _, labels, _ in http["samples"]
    )
    assert families["genai_http_requests_in_flight"]["type"] == "gauge"
    assert families["genai_http_request_duration_seconds"]["type"] == "histogram"
    # retrieval layer: the store ops above produced samples
    search = families["genai_vectorstore_search_seconds"]
    assert search["type"] == "histogram"
    assert any(
        labels.get("store") == "tpu" for _, labels, _ in search["samples"]
    )
    chunks = families["genai_vectorstore_chunks"]
    assert chunks["type"] == "gauge"
    assert any(
        labels == {"store": "tpu", "collection": "m"} and value == 1.0
        for _, labels, value in chunks["samples"]
    )


def test_engine_server_metrics_scrape_without_building_engine():
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.engine.server import ModelServer

    saved = llm_engine._ENGINE
    llm_engine._ENGINE = None
    try:
        async def scenario(client):
            resp = await client.get("/metrics")
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
            return await resp.text()

        server = ModelServer()
        body = _run(scenario, server.build_app)
        assert server._engine is None, "the engine-server scrape built the engine!"
        assert llm_engine._ENGINE is None
    finally:
        llm_engine._ENGINE = saved
    families = parse_exposition(body)
    validate_histograms(families)
    assert "genai_engine_ttft_seconds" in families


def test_internal_metrics_json_view_backward_compatible():
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.server.api import create_app

    saved = llm_engine._ENGINE
    llm_engine._ENGINE = None
    try:
        async def scenario(client):
            resp = await client.get("/internal/metrics")
            assert resp.status == 200
            return await resp.json()

        body = _run(scenario, lambda: create_app(EchoChain))
        assert llm_engine._ENGINE is None
    finally:
        llm_engine._ENGINE = saved
    assert body["engine"] is None  # legacy shape preserved
    assert "genai_http_requests_total" in body["metrics"]  # registry view


def test_internal_metrics_json_view_parity_with_exposition():
    """Every family visible in the Prometheus exposition must appear in
    the /internal/metrics JSON dump (and vice versa) — including the
    telemetry/flight-recorder/SLO families: the JSON view is the same
    registry, so a family missing from either side is a rendering bug."""
    # Import every registering module the exposition would show.
    from tools.check_metric_names import REGISTRY_MODULES

    import importlib

    for module in REGISTRY_MODULES:
        importlib.import_module(module)
    registry = get_registry()
    exposed = set()
    for line in registry.render().splitlines():
        if line.startswith("# TYPE "):
            exposed.add(line.split(" ", 3)[2])
    collected = set(registry.collect().keys())
    assert exposed, "exposition rendered no families"
    assert exposed == collected
    for family in (
        "genai_engine_dispatch_device_seconds",
        "genai_engine_device_starved_seconds_total",
        "genai_slo_attainment_ratio",
        "genai_flight_recorder_events_total",
    ):
        assert family in collected


# --------------------------------------------------------------------------- #
# Profiler capture endpoints


def _reset_profiling_state():
    from generativeaiexamples_tpu.utils import profiling

    with profiling._LOCK:
        profiling._ACTIVE_DIR = profiling._STARTED_AT = None


def test_profile_endpoints_gated_off_by_default(monkeypatch):
    from generativeaiexamples_tpu.server.api import create_app

    monkeypatch.delenv("ENABLE_PROFILING", raising=False)
    _reset_profiling_state()

    async def scenario(client):
        start = await client.post("/internal/profile/start")
        stop = await client.post("/internal/profile/stop")
        return start.status, (await start.json()), stop.status

    start_status, body, stop_status = _run(scenario, lambda: create_app(EchoChain))
    assert start_status == 403 and stop_status == 403
    assert "ENABLE_PROFILING" in body["error"]


def test_profile_start_stop_lifecycle(monkeypatch, tmp_path):
    from generativeaiexamples_tpu.server.api import create_app
    from generativeaiexamples_tpu.utils import profiling

    calls = []
    fake = types.SimpleNamespace(
        start_trace=lambda log_dir: calls.append(("start", log_dir)),
        stop_trace=lambda: calls.append(("stop",)),
    )
    monkeypatch.setenv("ENABLE_PROFILING", "true")
    monkeypatch.setattr(profiling, "_profiler", lambda: fake)
    _reset_profiling_state()
    log_dir = str(tmp_path / "prof")

    async def scenario(client):
        first = await client.post(
            "/internal/profile/start", json={"log_dir": log_dir}
        )
        dup = await client.post("/internal/profile/start")
        stop = await client.post("/internal/profile/stop")
        idle = await client.post("/internal/profile/stop")
        return (
            first.status, await first.json(), dup.status,
            stop.status, await stop.json(), idle.status,
        )

    first_status, first_body, dup_status, stop_status, stop_body, idle_status = _run(
        scenario, lambda: create_app(EchoChain)
    )
    assert first_status == 200 and first_body == {"ok": True, "log_dir": log_dir}
    assert dup_status == 409  # one capture at a time
    assert stop_status == 200 and stop_body["log_dir"] == log_dir
    assert idle_status == 409  # nothing to stop
    assert calls == [("start", log_dir), ("stop",)]


def test_profile_stop_failure_keeps_session_stoppable(monkeypatch, tmp_path):
    """A failed stop_trace (e.g. disk full) must NOT clear the active
    session — otherwise jax's profiler stays running with start 500ing
    and stop 409ing forever. The operator retries stop instead."""
    from generativeaiexamples_tpu.utils import profiling

    monkeypatch.setenv("ENABLE_PROFILING", "true")
    state = {"fail_next_stop": True}

    def stop_trace():
        if state["fail_next_stop"]:
            state["fail_next_stop"] = False
            raise RuntimeError("disk full")

    fake = types.SimpleNamespace(start_trace=lambda d: None, stop_trace=stop_trace)
    monkeypatch.setattr(profiling, "_profiler", lambda: fake)
    _reset_profiling_state()
    status, _ = profiling.start_profile(str(tmp_path))
    assert status == 200
    status, body = profiling.stop_profile()
    assert status == 500 and "disk full" in body["error"]
    assert profiling.capture_active()  # still stoppable
    status, _ = profiling.stop_profile()
    assert status == 200
    assert not profiling.capture_active()


def test_profile_graceful_when_profiler_unavailable(monkeypatch):
    from generativeaiexamples_tpu.utils import profiling

    monkeypatch.setenv("ENABLE_PROFILING", "true")
    monkeypatch.setattr(profiling, "_profiler", lambda: None)
    _reset_profiling_state()
    status, body = profiling.start_profile()
    assert status == 501
    assert "unavailable" in body["error"]


def test_annotation_scope_noop_when_disabled(monkeypatch):
    from generativeaiexamples_tpu.utils import profiling

    monkeypatch.delenv("ENABLE_PROFILING", raising=False)
    scope = profiling.annotation_scope()
    with scope("engine.decode_block"):
        pass  # must be a free nullcontext


# --------------------------------------------------------------------------- #
# Histogram bucket audit (PR 16): every registered distribution must be
# strictly increasing, +Inf-terminated, and — for the _seconds families —
# span enough decades that a p95 read off the cumulative buckets is
# meaningful at both the fast (lock-wait/gap) and slow (queue-wait)
# scales. Pins the audit that extended the saturated step-time top edge
# and moved queue-wait onto SLOW_SECONDS_BUCKETS.


def test_registered_histogram_buckets_monotone_and_covering():
    import importlib

    from tools.check_metric_names import REGISTRY_MODULES

    from generativeaiexamples_tpu.utils.metrics import Histogram

    for module in REGISTRY_MODULES:
        importlib.import_module(module)

    histograms = [f for f in get_registry().families() if isinstance(f, Histogram)]
    assert histograms, "registry has no histogram families — imports broke?"
    for family in histograms:
        uppers = list(family._buckets)
        assert uppers == sorted(uppers), f"{family.name}: buckets not sorted"
        assert len(set(uppers)) == len(uppers), (
            f"{family.name}: duplicate bucket edges"
        )
        assert uppers[-1] == math.inf, f"{family.name}: missing +Inf bucket"
        finite = [u for u in uppers if u != math.inf]
        # A p95 estimated from cumulative buckets needs resolution:
        # too few edges and every answer collapses to the same bound.
        assert len(finite) >= 6, f"{family.name}: too few buckets ({len(finite)})"
        if family.name.endswith("_seconds"):
            assert finite[0] > 0, f"{family.name}: non-positive first edge"
            assert finite[-1] / finite[0] >= 100, (
                f"{family.name}: _seconds buckets span under two decades "
                f"({finite[0]}..{finite[-1]})"
            )


def test_seconds_bucket_presets_cover_their_scales():
    from generativeaiexamples_tpu.utils.metrics import (
        FAST_SECONDS_BUCKETS,
        SLOW_SECONDS_BUCKETS,
    )

    # FAST resolves lock-wait/dispatch-gap scales: sub-100µs first edge
    # so an uncontended lock acquisition doesn't land in one giant
    # lowest bucket, finite top ≥ 1s so a pathological stall still
    # resolves below +Inf.
    fast_finite = [u for u in FAST_SECONDS_BUCKETS if u != math.inf]
    assert fast_finite[0] <= 1e-4 and fast_finite[-1] >= 1.0
    # SLOW resolves queue-wait under shed/backpressure: top edge beyond
    # the old saturated 5s ceiling so p95 under load is a real number.
    slow_finite = [u for u in SLOW_SECONDS_BUCKETS if u != math.inf]
    assert slow_finite[-1] >= 60.0
    for preset in (FAST_SECONDS_BUCKETS, SLOW_SECONDS_BUCKETS):
        assert preset[-1] == math.inf
        assert list(preset) == sorted(set(preset))


def test_histogram_rejects_non_increasing_bucket_edges():
    import pytest

    registry = MetricsRegistry()
    with pytest.raises(ValueError):
        registry.histogram(
            "genai_test_dup_edge_seconds", "dup", buckets=(0.1, 0.1, 1.0)
        )
    with pytest.raises(ValueError):
        registry.histogram(
            "genai_test_backward_edge_seconds", "backward", buckets=(1.0, 0.5)
        )
