"""The server child of the benchmark: the program's own chain server,
started unchanged, on a configuration read from a file.

The configuration file names its adapter (``"adapter"``, a module under
``perfbench/arch/``; the contract is in ``perfbench/arch/__init__.py``).
This launcher imports it, has it register the file's published sizes
with the engine under the configuration's name (in its own process) and
then calls ``generativeaiexamples_tpu.server.__main__.main()``.
Everything else — engine settings, chain, embedder, store — arrives as
the ``APP_*`` environment the parent built from the same file. No model
is named here: what is particular to an architecture is the adapter's.

Beside the server it runs two threads. One, once the engine's warm-up
is done, compares the engine with the adapter's plain float32 reference
on the host CPU device and writes ``reference.json`` into the work
directory (the clients ramp up meanwhile; the parent opens no window
before that file exists). The other brackets the traced interval: the
parent creates ``trace.start`` and ``trace.stop`` in the work directory
and this process starts and stops ``jax.profiler`` with the Python
tracer OFF (the program's own ``POST /internal/profile/start`` leaves
it on and runs inside the server's event loop: on the chip that stalled
every stream for the length of the capture — PERF.md section 6). On the
way out (SIGTERM) the launcher writes the device's peak memory.

This process holds the chip; the parent never imports jax.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(obj, fh)
    os.replace(tmp, path)


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without memory_stats reports 0
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def reference_check(cfg: dict, adapter, work: str, tp: int = 1) -> None:
    """The protocol of the comparison; the three steps that know the
    model are the adapter's. Runs on its own thread; never raises (a
    failure is a result)."""
    out = {"ok": False}
    t0 = time.time()
    try:
        from generativeaiexamples_tpu.engine import llm_engine

        while llm_engine._ENGINE is None or not llm_engine.WARMUP_DONE.is_set():
            time.sleep(0.5)
        eng = llm_engine._ENGINE
        t0 = time.time()
        from generativeaiexamples_tpu.engine.llm_engine import SamplingParams
        from generativeaiexamples_tpu.utils import jax_env
        from perfbench import reference

        ref_cfg = cfg["reference"]
        on_tpu = device_facts()["platform"] == "tpu"
        usable = min(cfg["vocab_size"], getattr(eng.tokenizer, "vocab_size", cfg["vocab_size"]))
        stops = set(eng.tokenizer.stop_ids())
        n_logits = len(ref_cfg["prompt_tokens"])
        lengths = list(ref_cfg["prompt_tokens"]) + list(ref_cfg.get("served_only_prompt_tokens", []))
        prompts = [
            [t if t not in stops else 0 for t in p]
            for p in reference.seeded_prompts(lengths, usable, seed=20240924)
        ]
        # (i) logits of the short prompts from the engine's prefill forward;
        # (ii) every prompt, the one longer than prefill_chunk included, is
        # decoded by the SERVED programs (prefill, extend, int8 paged KV, decode)
        eng_logits = list(adapter.engine_prefill_logits(eng, prompts[:n_logits], on_tpu))
        eng_logits += [None] * (len(prompts) - n_logits)
        greedy = SamplingParams(temperature=0.0, max_tokens=int(ref_cfg["decode_tokens"]))
        eng_tokens = [list(eng.iter_ids(p, greedy, timeout=900)) for p in prompts]
        ref_logits = adapter.reference_logits(
            eng, cfg, [list(p) + list(t) for p, t in zip(prompts, eng_tokens)], tp,
            jax_env.host_device(),
        )
        out = reference.compare(prompts, eng_logits, eng_tokens, ref_logits, adapter.TOLERANCE)
        out["decode_tokens"] = [len(t) for t in eng_tokens]
    except Exception:  # noqa: BLE001 - reported to the parent, which fails the run
        out["error"] = traceback.format_exc()
    out["seconds"] = round(time.time() - t0, 3)
    write_json(os.path.join(work, "reference.json"), out)


def trace_on_request(work: str) -> None:
    """Start the profiler when ``trace.start`` appears, stop it when
    ``trace.stop`` does, then write ``trace.done``. Never raises."""
    start, stop = os.path.join(work, "trace.start"), os.path.join(work, "trace.stop")
    out = {"ok": False}
    try:
        while not os.path.exists(start):
            time.sleep(0.02)
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t0 = time.time()
        jax.profiler.start_trace(os.path.join(work, "trace"), profiler_options=opts)
        t1 = time.time()
        while not os.path.exists(stop):
            time.sleep(0.02)
        t2 = time.time()
        jax.profiler.stop_trace()
        out = {"ok": True, "start_call_s": t1 - t0, "traced_s": t2 - t1, "stop_call_s": time.time() - t2,
               "capture_bytes": sum(os.path.getsize(os.path.join(d, f))
                                    for d, _, fs in os.walk(os.path.join(work, "trace")) for f in fs)}
    except Exception:  # noqa: BLE001 - reported to the parent
        out["error"] = traceback.format_exc()
    write_json(os.path.join(work, "trace.done"), out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--work", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    with open(args.config, encoding="utf-8") as fh:
        cfg = json.load(fh)

    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()
    facts = device_facts()
    write_json(os.path.join(args.work, "device.json"), facts)
    if facts["platform"] != "tpu" and not cfg.get("rehearsal"):
        print(f"no accelerator: jax reports {facts}", flush=True)
        return 3
    if facts["count"] < args.chips:
        print(f"the cell needs {args.chips} chips, jax reports {facts}", flush=True)
        return 3

    from perfbench import arch

    adapter = arch.load(cfg)
    adapter.register(cfg)
    threading.Thread(
        target=reference_check, args=(cfg, adapter, args.work, args.chips), daemon=True,
        name="perfbench-reference",
    ).start()

    if args.trace:
        threading.Thread(
            target=trace_on_request, args=(args.work,), daemon=True, name="perfbench-trace",
        ).start()

    from generativeaiexamples_tpu.server import __main__ as server_main

    sys.argv = [sys.argv[0], "--host", "127.0.0.1", "--port", str(args.port)]
    server_main.main()  # returns on SIGTERM
    facts["memory_peak_bytes"] = memory_peak_bytes()
    write_json(os.path.join(args.work, "device_final.json"), facts)
    from generativeaiexamples_tpu.engine import llm_engine

    if llm_engine._ENGINE is not None:
        llm_engine._ENGINE.shutdown()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # daemon threads (warm-up, reference) must not hold the exit
