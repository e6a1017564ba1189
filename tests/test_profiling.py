"""POST /internal/profile/start|stop coverage (utils/profiling.py):
env-gate off -> 403, double-start -> 409, stop-without-start -> 409,
profiler-unavailable -> 501, and the annotation-scope no-op path when
jax.profiler is unavailable."""
import asyncio
import contextlib
import threading

import pytest

from generativeaiexamples_tpu.utils import profiling


class _FakeProfiler:
    """Stands in for jax.profiler (profiling only touches start_trace /
    stop_trace / TraceAnnotation)."""

    def __init__(self, fail_start=False, fail_stop=False):
        self.started = []
        self.threads = []
        self.stopped = 0
        self._fail_start = fail_start
        self._fail_stop = fail_stop

    def start_trace(self, log_dir):
        self.threads.append(threading.current_thread())
        if self._fail_start:
            raise RuntimeError("no backend")
        self.started.append(log_dir)

    def stop_trace(self):
        self.threads.append(threading.current_thread())
        if self._fail_stop:
            raise RuntimeError("trace write failed")
        self.stopped += 1

    TraceAnnotation = staticmethod(contextlib.nullcontext)


@pytest.fixture(autouse=True)
def _clean_session(monkeypatch):
    """Profiling session state is process-global; every test starts
    with no active capture and the env gate unset."""
    monkeypatch.delenv("ENABLE_PROFILING", raising=False)
    monkeypatch.setattr(profiling, "_ACTIVE_DIR", None)
    monkeypatch.setattr(profiling, "_STARTED_AT", None)
    yield


def _enable(monkeypatch, profiler):
    monkeypatch.setenv("ENABLE_PROFILING", "true")
    monkeypatch.setattr(profiling, "_profiler", lambda: profiler)


# --------------------------------------------------------------------------- #
# function-level contract


def test_env_gate_off_is_403_for_both_endpoints():
    status, body = profiling.start_profile()
    assert status == 403 and "disabled" in body["error"]
    status, body = profiling.stop_profile()
    assert status == 403


def test_profiler_unavailable_is_501(monkeypatch):
    monkeypatch.setenv("ENABLE_PROFILING", "1")
    monkeypatch.setattr(profiling, "_profiler", lambda: None)
    assert profiling.start_profile()[0] == 501
    assert profiling.stop_profile()[0] == 501


def test_start_stop_roundtrip_and_double_start(monkeypatch, tmp_path):
    fake = _FakeProfiler()
    _enable(monkeypatch, fake)
    log_dir = str(tmp_path / "prof")
    status, body = profiling.start_profile(log_dir)
    assert status == 200 and body["log_dir"] == log_dir
    assert profiling.capture_active()
    # double start: 409 with the active dir, profiler untouched
    status, body = profiling.start_profile(str(tmp_path / "other"))
    assert status == 409 and body["log_dir"] == log_dir
    assert fake.started == [log_dir]
    status, body = profiling.stop_profile()
    assert status == 200 and body["log_dir"] == log_dir
    assert body["duration_s"] is not None
    assert not profiling.capture_active()


def test_stop_without_start_is_409(monkeypatch):
    _enable(monkeypatch, _FakeProfiler())
    status, body = profiling.stop_profile()
    assert status == 409 and "no profile capture" in body["error"]


def test_failed_stop_keeps_session_active_for_retry(monkeypatch, tmp_path):
    fake = _FakeProfiler(fail_stop=True)
    _enable(monkeypatch, fake)
    assert profiling.start_profile(str(tmp_path))[0] == 200
    assert profiling.stop_profile()[0] == 500
    # the session stays active: the operator can retry stop, and start
    # keeps refusing (jax's profiler may still be running)
    assert profiling.capture_active()
    assert profiling.start_profile(str(tmp_path))[0] == 409
    fake._fail_stop = False
    assert profiling.stop_profile()[0] == 200


def test_capture_is_the_benchmarks_kind_python_tracer_off(monkeypatch, tmp_path):
    """With ``ProfileOptions`` (this image's jax) the capture starts as
    perfbench/launcher.py starts its own: Python tracer off, host
    tracer at 1; a profiler without it is started as before."""

    class Options:
        python_tracer_level = 1
        host_tracer_level = 2

    class WithOptions(_FakeProfiler):
        ProfileOptions = Options

        def start_trace(self, log_dir, profiler_options=None):
            self.started.append((log_dir, profiler_options))

    fake = WithOptions()
    _enable(monkeypatch, fake)
    assert profiling.start_profile(str(tmp_path))[0] == 200
    (log_dir, opts), = fake.started
    assert log_dir == str(tmp_path) and isinstance(opts, Options)
    assert opts.python_tracer_level == 0 and opts.host_tracer_level == 1
    assert profiling.stop_profile()[0] == 200
    assert profiling._capture_options(_FakeProfiler()) == {}

    import jax

    real = profiling._capture_options(jax.profiler)["profiler_options"]
    assert real.python_tracer_level == 0


# --------------------------------------------------------------------------- #
# annotation scope


def test_annotation_scope_noop_when_disabled():
    scope = profiling.annotation_scope()
    with scope("engine.decode_block"):  # must be directly usable
        pass


def test_annotation_scope_noop_when_profiler_unavailable(monkeypatch):
    monkeypatch.setenv("ENABLE_PROFILING", "true")
    monkeypatch.setattr(profiling, "_profiler", lambda: None)
    scope = profiling.annotation_scope()
    with scope("engine.prefill_chunk"):
        pass


def test_annotation_scope_uses_trace_annotation_when_available(monkeypatch):
    fake = _FakeProfiler()
    _enable(monkeypatch, fake)
    assert profiling.annotation_scope() is _FakeProfiler.TraceAnnotation


# --------------------------------------------------------------------------- #
# endpoint wiring (server/observability.py handlers)


def test_profile_endpoints_gate_and_conflict(monkeypatch, tmp_path):
    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.server.observability import (
        add_observability_routes,
    )

    async def scenario():
        app = web.Application()
        add_observability_routes(app)
        async with TestClient(TestServer(app)) as client:
            # env gate off: 403 on both
            assert (await client.post("/internal/profile/start")).status == 403
            assert (await client.post("/internal/profile/stop")).status == 403
            fake = _FakeProfiler()
            _enable(monkeypatch, fake)
            # stop without start
            assert (await client.post("/internal/profile/stop")).status == 409
            # start honors the JSON body's log_dir override
            resp = await client.post(
                "/internal/profile/start",
                json={"log_dir": str(tmp_path / "캡처")},
            )
            assert resp.status == 200
            assert (await resp.json())["log_dir"] == str(tmp_path / "캡처")
            # double start
            assert (await client.post("/internal/profile/start")).status == 409
            assert (await client.post("/internal/profile/stop")).status == 200
            # both calls ran OFF the event loop's thread: a capture's
            # start and its write-out must not stall the streams
            assert fake.threads and loop_thread not in fake.threads

    loop_thread = None

    async def on_loop():
        nonlocal loop_thread
        loop_thread = threading.current_thread()
        await scenario()

    asyncio.run(on_loop())
