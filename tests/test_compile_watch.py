"""Compile-path observability (engine/compile_watch.py): jit's own
monitoring events attributed to the engine program whose wrapped call
they were raised in, warmup phases, hot-path detection with what jit
did (and flight-event stamping), the stamp on the dispatch span, and
what the watch must NOT count: jit work outside any wrapped call."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine import dispatch_timeline as dtl
from generativeaiexamples_tpu.engine.compile_watch import CompileWatch
from generativeaiexamples_tpu.utils import flight_recorder as fr
from generativeaiexamples_tpu.utils import metrics as metrics_mod


@pytest.fixture(autouse=True)
def _fresh_recorder():
    fr.reset()
    dtl.reset()
    yield
    fr.reset()
    dtl.reset()


def _program():
    """A fresh jitted function (its own executable cache) of an array
    and a static python value."""
    return jax.jit(lambda x, k: x * 2 + k, static_argnums=(1,))


def _hot(program: str) -> float:
    family = metrics_mod.get_registry().get("genai_engine_hot_path_compiles_total")
    return sum(child.value for labels, child in family._items() if labels[0] == program)


# --------------------------------------------------------------------------- #
# wrap + phases


def test_jit_events_inside_a_wrapped_call_count_one_executable_each():
    watch = CompileWatch()
    wrapped = watch.wrap("decode", _program())
    x = np.zeros((4,), np.int32)
    assert int(wrapped(x, 64)[0]) == 64  # transparent passthrough
    wrapped(np.ones((4,), np.int32), 64)  # values never recompile
    wrapped(x, 128)  # new static value: new executable
    wrapped(np.zeros((5,), np.int32), 128)  # new shape: new executable
    snap = watch.snapshot()
    assert snap["compile_executables"] == 3.0
    assert snap["compile_executables_decode"] == 3.0
    assert snap["compile_seconds_total"] > 0
    assert snap["compile_hot_path_total"] == 0.0  # warmup never finished


def test_hot_path_compile_fires_after_warmup_and_stamps_inflight():
    watch = CompileWatch()
    wrapped = watch.wrap("decode", _program())
    wrapped(np.zeros((4,), np.int32), 64)
    watch.finish_warmup()
    live = fr.start(request_id="stalled-1")
    # pre-warmed: silent
    wrapped(np.ones((4,), np.int32), 64)
    assert watch.snapshot()["compile_hot_path_total"] == 0.0
    # jit compiles AFTER warmup: loud
    wrapped(np.zeros((4,), np.int32), 128)
    snap = watch.snapshot()
    assert snap["compile_hot_path_total"] == 1.0
    stamped = [attrs for _, name, attrs in live.events if name == "hot_path_compile"]
    assert stamped and stamped[0]["program"] == "decode"
    assert stamped[0]["what"] in ("compile", "cache_load")
    # coverage: 2 calls served post-warmup, 1 found everything warm
    assert snap["compile_rungs_hit"] == 2.0
    assert snap["compile_warmup_coverage"] == 0.5


def test_warmup_scope_after_finish_counts_as_warmup():
    watch = CompileWatch()
    wrapped = watch.wrap("spec_verify", _program())
    wrapped(np.zeros((2,), np.int32), 16)
    watch.finish_warmup()
    with watch.warmup_scope():  # bench re-warm / runtime spec toggle
        wrapped(np.zeros((2,), np.int32), 32)
    snap = watch.snapshot()
    assert snap["compile_hot_path_total"] == 0.0
    assert snap["compile_executables"] == 2.0
    # and the late rung is warm from here on
    wrapped(np.zeros((2,), np.int32), 32)
    assert watch.snapshot()["compile_warmup_coverage"] == 1.0


def test_snapshot_keys_ride_utilization_namespace():
    """Every snapshot key is compile_-prefixed and flat, so the loadgen
    schema's single-level utilization.* claim covers them all."""
    watch = CompileWatch()
    watch.wrap("prefill", _program())(np.zeros((1,)), 1)
    snap = watch.snapshot()
    assert all(k.startswith("compile_") for k in snap)
    assert all(isinstance(v, float) for v in snap.values())


def test_committed_then_uncommitted_operand_of_one_shape_is_a_hot_path_load():
    """jit keys an executable on more than shapes: the case a shadow of
    its key missed (four multi-second loads at the start of every ramp,
    PERF.md section 6, PR 32)."""
    watch = CompileWatch()
    fn = _program()
    wrapped = watch.wrap("extend", fn)
    committed = jax.device_put(jnp.zeros((4,), jnp.float32), jax.devices()[0])
    wrapped(committed, 1)
    watch.finish_warmup()
    before = _hot("extend")
    wrapped(committed, 1)
    assert watch.snapshot()["compile_hot_path_total"] == 0.0
    wrapped(jnp.zeros((4,), jnp.float32), 1)  # same shape and dtype, not committed
    assert fn._cache_size() == 2
    assert watch.snapshot()["compile_hot_path_total"] == 1.0
    assert _hot("extend") - before == 1.0


def test_compile_on_another_thread_outside_any_wrapped_call_is_nobodys():
    watch = CompileWatch()
    wrapped = watch.wrap("decode", _program())
    wrapped(np.zeros((4,), np.int32), 1)
    watch.finish_warmup()
    inside, compiled = threading.Event(), threading.Event()

    def beside():  # the harness's reference thread compiles beside the ramp
        inside.wait(10)
        _program()(np.zeros((7,), np.int32), 3)
        compiled.set()

    slow = watch.wrap("finish", lambda: (inside.set(), compiled.wait(30)))
    worker = threading.Thread(target=beside)
    worker.start()
    slow()  # a wrapped call is open on THIS thread while the other compiles
    worker.join()
    _program()(np.zeros((3,), np.int32), 2)  # and this thread, outside any
    snap = watch.snapshot()
    assert snap["compile_hot_path_total"] == 0.0
    assert snap["compile_executables"] == 1.0


def test_jit_work_is_stamped_on_the_span_it_happened_in():
    dtl.configure(enable=True)
    watch = CompileWatch()
    wrapped = watch.wrap("decode", _program())
    wrapped(np.zeros((4,), np.int32), 1)
    dtl.record_span("decode", t_wall=0.0, lock_wait_s=0.0, run_s=0.5)
    wrapped(np.zeros((4,), np.int32), 1)  # warm: nothing to stamp
    dtl.record_span("decode", t_wall=1.0, lock_wait_s=0.0, run_s=0.001)
    spans = dtl.spans_since(0)[0]
    overlay, first, second = spans
    assert overlay["kind"] == "compile:decode" and overlay["category"] == "compile"
    assert first["jit_what"] in ("compile", "cache_load") and first["jit_s"] > 0
    assert abs(first["jit_s"] - overlay["duration_s"]) < 1e-6
    assert "jit_what" not in second and "jit_s" not in second


# --------------------------------------------------------------------------- #
# engine integration: the tiny CPU engine's warmup covers serving, and
# the utilization snapshot carries the stats (slow-free smoke: reuses
# the debug config the flight-recorder acceptance test runs tier-1).

TINY = dict(
    model_config_name="debug",
    max_batch_size=2,
    max_seq_len=64,
    prefill_chunk=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    page_size=16,
    watchdog_stall_s=0.0,
)


@pytest.fixture(scope="module")
def eng():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    engine = LLMEngine(EngineConfig(**TINY))
    engine.warmup()
    yield engine
    engine.shutdown()


def test_engine_warmup_covers_serving_no_hot_compiles(eng):
    from generativeaiexamples_tpu.engine.llm_engine import (
        _END,
        SamplingParams,
    )

    snap = eng.utilization_snapshot()
    assert snap["compile_warmup_done"] == 1.0
    assert snap["compile_executables"] > 0
    executables = snap["compile_executables"]
    for prompt in ([7] * 10, [9] * 30):  # single-chunk and chunked
        req = eng.submit(prompt, SamplingParams(temperature=0.0, max_tokens=4))
        while req.out_queue.get() is not _END:
            pass
    snap = eng.utilization_snapshot()
    assert snap["compile_hot_path_total"] == 0.0
    assert snap["compile_executables"] == executables
    assert snap["compile_warmup_coverage"] == 1.0
    assert snap["compile_rungs_hit"] > 0
