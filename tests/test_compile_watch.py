"""Compile-path observability (engine/compile_watch.py): signature
derivation, first-dispatch compile accounting, warmup phases,
hot-path detection with flight-event stamping, and coverage math.
Pure host — wrapped callables are plain functions over numpy arrays."""
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.compile_watch import (
    CompileWatch,
    _signature,
)
from generativeaiexamples_tpu.utils import flight_recorder as fr


@pytest.fixture(autouse=True)
def _fresh_recorder():
    fr.reset()
    yield
    fr.reset()


# --------------------------------------------------------------------------- #
# signature derivation: jit's recompile key, observably


def test_signature_arrays_by_shape_dtype_not_value():
    a = np.zeros((4, 8), np.float32)
    b = np.ones((4, 8), np.float32)
    c = np.zeros((4, 9), np.float32)
    d = np.zeros((4, 8), np.int32)
    assert _signature(a) == _signature(b)  # values never recompile
    assert _signature(a) != _signature(c)  # shapes do
    assert _signature(a) != _signature(d)  # dtypes do


def test_signature_scalars_by_value_and_containers_recurse():
    assert _signature(64) != _signature(128)  # static args select execs
    assert _signature(True) != _signature(1.0)
    caches_a = [{"k": np.zeros((2, 4)), "v": np.zeros((2, 4))}]
    caches_b = [{"k": np.ones((2, 4)), "v": np.ones((2, 4))}]
    caches_c = [{"k": np.zeros((2, 8)), "v": np.zeros((2, 4))}]
    assert _signature(caches_a) == _signature(caches_b)
    assert _signature(caches_a) != _signature(caches_c)


# --------------------------------------------------------------------------- #
# wrap + phases


def _counting_fn():
    calls = []

    def fn(*args, **kwargs):
        calls.append(args)
        return len(calls)

    return fn, calls


def test_first_dispatch_per_signature_counts_one_compile():
    watch = CompileWatch()
    fn, calls = _counting_fn()
    wrapped = watch.wrap("decode", fn)
    x = np.zeros((4,), np.int32)
    assert wrapped(x, 64) == 1  # transparent passthrough
    wrapped(np.ones((4,), np.int32), 64)  # same signature: no new exec
    wrapped(x, 128)  # new static value: new executable
    snap = watch.snapshot()
    assert snap["compile_executables"] == 2.0
    assert snap["compile_executables_decode"] == 2.0
    assert snap["compile_hot_path_total"] == 0.0  # warmup never finished
    assert len(calls) == 3


def test_hot_path_compile_fires_after_warmup_and_stamps_inflight():
    watch = CompileWatch()
    wrapped = watch.wrap("decode", _counting_fn()[0])
    wrapped(np.zeros((4,), np.int32), 64)
    watch.finish_warmup()
    live = fr.start(request_id="stalled-1")
    # pre-warmed signature: silent
    wrapped(np.ones((4,), np.int32), 64)
    assert watch.snapshot()["compile_hot_path_total"] == 0.0
    # first-seen signature AFTER warmup: loud
    wrapped(np.zeros((4,), np.int32), 128)
    snap = watch.snapshot()
    assert snap["compile_hot_path_total"] == 1.0
    assert any(
        name == "hot_path_compile" and attrs["program"] == "decode"
        for _, name, attrs in live.events
    )
    # coverage: 2 distinct rungs served post-warmup, 1 pre-warmed
    assert snap["compile_rungs_hit"] == 2.0
    assert snap["compile_warmup_coverage"] == 0.5


def test_warmup_scope_after_finish_counts_as_warmup():
    watch = CompileWatch()
    wrapped = watch.wrap("spec_verify", _counting_fn()[0])
    wrapped(np.zeros((2,), np.int32), 16)
    watch.finish_warmup()
    with watch.warmup_scope():  # bench re-warm / runtime spec toggle
        wrapped(np.zeros((2,), np.int32), 32)
    snap = watch.snapshot()
    assert snap["compile_hot_path_total"] == 0.0
    assert snap["compile_executables"] == 2.0
    # and the late rung joined the pre-warmed set
    wrapped(np.zeros((2,), np.int32), 32)
    assert watch.snapshot()["compile_warmup_coverage"] == 1.0


def test_snapshot_keys_ride_utilization_namespace():
    """Every snapshot key is compile_-prefixed and flat, so the loadgen
    schema's single-level utilization.* claim covers them all."""
    watch = CompileWatch()
    watch.wrap("prefill", _counting_fn()[0])(np.zeros((1,)))
    snap = watch.snapshot()
    assert all(k.startswith("compile_") for k in snap)
    assert all(isinstance(v, float) for v in snap.values())


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
    engine.warmup(prompt_lengths=[16])
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
