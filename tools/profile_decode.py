"""Profile steady-state decode on the real TPU (VERDICT r2 next #2).

Builds an engine from BENCH_* env knobs (model, batch, quantization,
page kernel, speculation, scheduler policy), fills every slot, then
wraps ~PROFILE_SECONDS of steady-state decode in ``jax.profiler.trace``
and attributes device time across the decode step: Pallas
weight-streaming calls, XLA fusions, cache scatters, copies/transposes,
sampling, and inter-dispatch idle. Device-side timings only: a host
clock sees enqueue and readback, the xplane device track is measured
on-chip. The trace parsing itself lives in
``generativeaiexamples_tpu/utils/xplane.py``, shared with the dispatch
timeline's Perfetto device track
(``GET /internal/timeline?format=perfetto&xplane=<logdir>``).

Usage (defaults mirror the 8B headline config):
  BENCH_MODEL=llama3-8b BENCH_BATCH=96 BENCH_KV=bfloat16 \
  python tools/profile_decode.py
Writes the per-category breakdown to stdout and keeps the raw trace
directory for deeper inspection.
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

os.environ.setdefault("LOGLEVEL", "WARNING")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from generativeaiexamples_tpu.utils.xplane import (  # noqa: E402
    categorize,
    parse_trace,
)


def build_engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    cfg = EngineConfig(
        model_config_name=os.environ.get("BENCH_MODEL", "llama3-8b"),
        max_batch_size=int(os.environ.get("BENCH_BATCH", "96")),
        max_seq_len=int(os.environ.get("BENCH_SEQ", "512")),
        prefill_chunk=128,
        tensor_parallelism=int(os.environ.get("BENCH_TP", "-1")),
        dtype="bfloat16",
        decode_block=int(os.environ.get("BENCH_BLOCK", "8")),
        quantization=os.environ.get("BENCH_QUANT", "int8"),
        kv_cache_dtype=os.environ.get("BENCH_KV", "bfloat16"),
        # Profile the attention server and policy actually deployed.
        paged_kernel=os.environ.get("BENCH_PAGED_KERNEL", "auto"),
        spec_decode_enable=os.environ.get("BENCH_SPEC", "off"),
        scheduler_policy=os.environ.get("BENCH_SCHED", "unified"),
    )
    return LLMEngine(cfg)


def main() -> None:
    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()
    import jax

    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    engine = build_engine()
    B = engine.num_slots
    prompt_tokens = int(os.environ.get("BENCH_PROMPT", "128"))
    prompt = list(range(5, 5 + prompt_tokens - 1))
    seconds = float(os.environ.get("PROFILE_SECONDS", "1.0"))

    # Warm the exact serving shapes, then refill every slot with
    # long-budget requests so the traced window is pure steady-state
    # decode (no prefill admissions mid-trace).
    list(
        engine.stream_text(
            prompt, SamplingParams(temperature=0.0, max_tokens=8), timeout=900
        )
    )
    engine.warmup()
    # Full remaining cache budget per request, and a second wave queued
    # behind the first, so decode slots stay saturated through the whole
    # traced window (a too-small budget drains before the trace starts —
    # the trace then shows zero decode steps).
    gen_budget = engine.max_seq_len - prompt_tokens - 2
    params = SamplingParams(temperature=0.0, max_tokens=gen_budget)
    with engine.hold_admissions():
        reqs = [engine.submit([7 + i] + prompt, params) for i in range(2 * B)]
    # let prefill waves drain and decode reach steady state
    deadline = time.time() + 120
    while time.time() < deadline:
        with engine._lock:
            if len(engine._slot_req) == B:
                break
        time.sleep(0.2)
    time.sleep(0.5)

    logdir = os.environ.get(
        "PROFILE_DIR", tempfile.mkdtemp(prefix="decode_profile_")
    )
    steps0 = engine.metrics["decode_steps"]
    with jax.profiler.trace(logdir):
        time.sleep(seconds)
    steps = engine.metrics["decode_steps"] - steps0

    for req in reqs:
        req.cancelled = True
    if steps == 0:
        print(
            "WARNING: zero decode steps in the traced window — the engine "
            "drained before tracing; raise BENCH_SEQ or request count.",
            file=sys.stderr,
        )
    report = parse_trace(logdir)

    wall_ms = report["wall_us"] / 1e3
    print(f"trace: {logdir}")
    print(
        f"traced {wall_ms:.1f} ms of device activity, ~{steps} decode steps "
        f"(block={engine.shapes.decode_block})"
    )
    print("\n== executables (device time) ==")
    for name, us in sorted(report["executables"].items(), key=lambda x: -x[1]):
        print(
            f"  {name:<40} {us / 1e3:9.2f} ms  x{report['exe_counts'][name]:<5}"
            f" ({us / max(report['wall_us'], 1) * 100:5.1f}% of traced wall)"
        )
    print("\n== op categories (within executables) ==")
    total_ops = sum(report["categories"].values())
    for cat, us in sorted(report["categories"].items(), key=lambda x: -x[1]):
        print(
            f"  {cat:<16} {us / 1e3:9.2f} ms ({us / max(total_ops, 1) * 100:5.1f}%)"
        )
    exe_total = sum(report["executables"].values())
    print(
        f"\nops-total {total_ops / 1e3:.2f} ms vs exe-total {exe_total / 1e3:.2f} ms"
        f" vs traced wall {wall_ms:.2f} ms"
        f" -> inter-dispatch idle ~{max(0.0, report['wall_us'] - exe_total) / 1e3:.2f} ms"
    )
    print("\n== top 25 ops ==")
    for name, us in sorted(report["ops"].items(), key=lambda x: -x[1])[:25]:
        print(
            f"  {us / 1e3:9.2f} ms x{report['op_counts'][name]:<6} "
            f"[{categorize(name):<14}] {name[:90]}"
        )
    engine.shutdown()


if __name__ == "__main__":
    main()
