"""Kill/restore token-identity matrix (slow tier).

The ISSUE 19 acceptance gate: a live engine killed mid-decode and
restored FROM ITS SPOOL on a fresh engine process continues the stream
token-identically to an uninterrupted run — across greedy and
seeded-sampled requests, bf16 and int8 KV caches, and spec decode
on/off. The "kill" is a drain (the graceful spot-VM window) followed by
a hard shutdown of the first engine; the second engine shares only the
on-disk spool, exactly like a replacement replica on the same host.
"""
import time

import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine.llm_engine import (
    LLMEngine,
    SamplingParams,
)
from generativeaiexamples_tpu.utils import faults
from generativeaiexamples_tpu.utils.resilience import RequestPreempted

TINY = dict(
    model_config_name="debug",
    max_batch_size=2,
    max_seq_len=128,
    prefill_chunk=16,
    decode_block=4,
    dtype="float32",
    tensor_parallelism=1,
    page_size=8,
    watchdog_stall_s=0.0,
    drain_timeout_s=30.0,
)

PROMPT = [7 + i for i in range(10)]


def _pull(req, n, timeout=120.0):
    out = []
    while len(out) < n:
        item = req.out_queue.get(timeout=timeout)
        assert item is not None, "stream ended before the kill point"
        out.append(item)
    return out


def _rest(req, timeout=120.0):
    out = []
    while True:
        item = req.out_queue.get(timeout=timeout)
        if item is None:
            return out
        out.append(item)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("spec", ["off", "on"])
@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
def test_killed_engine_restores_token_identically(
    tmp_path, kv_dtype, spec, sampling
):
    spool = str(tmp_path / "spool")
    cfg = dict(TINY, kv_cache_dtype=kv_dtype, spec_decode_enable=spec)
    params = (
        SamplingParams(temperature=0.0, max_tokens=24, seed=5)
        if sampling == "greedy"
        else SamplingParams(temperature=0.8, max_tokens=24, seed=987654)
    )

    # --- engine A: the replica that will be preempted -------------------
    eng_a = LLMEngine(EngineConfig(snapshot_spool_dir=spool, **cfg))
    try:
        baseline = list(eng_a.iter_ids(PROMPT, params, timeout=120))
        assert len(baseline) >= 12, (
            "matrix leg needs a long enough uninterrupted stream to cut "
            f"mid-decode, got {len(baseline)} tokens"
        )
        # Throttle dispatch so the victim is still mid-decode at the
        # kill point (an unthrottled debug engine finishes 24 tokens in
        # a handful of milliseconds).
        faults.reset()
        faults.configure("engine.dispatch", "delay", at=1, count=0,
                         value=0.05)
        try:
            req = eng_a.submit(PROMPT, params)
            got = _pull(req, 4)
            summary = eng_a.drain()
        finally:
            faults.reset()
        tail = _rest(req)
        assert isinstance(req.error, RequestPreempted)
        sid = req.error.snapshot_id
        assert sid, "the kill point must leave a restorable snapshot"
        assert sid in summary["snapshots"]
        emitted = got + tail
        assert emitted == baseline[: len(emitted)]
        assert len(emitted) < len(baseline), "nothing left to restore"
    finally:
        eng_a.shutdown()  # the kill: engine A is gone for good

    # --- engine B: the replacement, sharing only the on-disk spool ------
    t0 = time.time()
    eng_b = LLMEngine(EngineConfig(snapshot_spool_dir=spool, **cfg))
    try:
        snap = eng_b.snapshot_spool.load(sid)
        req2, _params2, prior, mode = eng_b.restore_snapshot(snap)
        assert mode == "restore", (
            "cross-engine restore must resume from the KV payload, "
            f"got mode={mode!r}"
        )
        assert prior == emitted
        continuation = _rest(req2)
        assert prior + continuation == baseline, (
            f"restored stream diverged for {sampling}/{kv_dtype}/"
            f"spec={spec}: {prior + continuation} != {baseline}"
        )
    finally:
        eng_b.shutdown()
    assert time.time() - t0 < 120
