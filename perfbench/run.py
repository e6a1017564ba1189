#!/usr/bin/env python3
"""perfbench/run.py — run ONE cell of the benchmark ONCE.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: one
configuration (``perfbench/configs/<name>.json``) under one traffic mix
(``perfbench/traffic/<name>.json``). This process never imports jax: it
starts the program's chain server as a child (``perfbench/launcher.py``,
which holds the chip), waits for it to be ready, ingests the mix's
corpus, ramps the clients up while the child compares the engine with
the plain reference, and opens the measured window after both. Everything before the window is
``setup_s``. ``--trace 0`` reports the cell's end-to-end metrics from
the client's frame log; ``--trace 1`` has the launcher's profiler thread capture the
last few seconds of the window and reports the cell's per-layer metrics.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced).
Without a TPU, or with fewer chips than the cell asks for, nothing is
printed and the exit code is not 0 — except for the rehearsal manifest
(``--manifest perfbench/rehearsal/manifest.json``), whose tiny
configuration runs the whole flow on the CPU, prints ``correct: false``
with NO metric values, and exits 1.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time

T_PROCESS_START = time.time()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import loadgen, readers, reduce, trace_reduce  # noqa: E402
from perfbench.tokenizer_file import write_tokenizer  # noqa: E402

READY_TIMEOUT_S = 1100.0  # launch -> ready, cold compile included


def canned_answers() -> tuple:
    """The texts the chain and the server answer with, inside a 200, when
    something went wrong (imported lazily; neither module imports jax)."""
    from generativeaiexamples_tpu.chains.developer_rag import NO_CONTEXT_MSG, NO_DOCS_MSG
    from generativeaiexamples_tpu.server.api import GENERIC_ERROR_MSG, VECTOR_STORE_ERROR_MSG

    return (NO_CONTEXT_MSG, NO_DOCS_MSG, GENERIC_ERROR_MSG, VECTOR_STORE_ERROR_MSG)


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class RunFailure(Exception):
    pass


def server_env(cfg: dict, cell: dict, work: str, trace: bool) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)  # the driver's own; the benchmark takes no notice of it
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(cfg["server_env"])
    tok = os.path.join(work, "tokenizer.json")
    write_tokenizer(tok, cfg["vocab_size"])
    env.update({
        "APP_ENGINE_MODELCONFIGNAME": cfg["name"],
        "APP_ENGINE_TOKENIZERPATH": tok,
        "APP_ENGINE_TENSORPARALLELISM": str(cell["chips"]),
        "APP_VECTORSTORE_PERSISTDIR": os.path.join(work, "vs"),
        "DOC_UPLOAD_DIR": os.path.join(work, "uploads"),
        "APP_ENGINE_SNAPSHOTSPOOLDIR": os.path.join(work, "snapshots"),
        "APP_BLACKBOX_DIR": os.path.join(work, "blackbox"),
    })
    return env


def wait_until(pred, what: str, timeout: float, alive) -> float:
    t0 = time.time()
    while not pred():
        if not alive():
            raise RunFailure(f"server exited while waiting for {what}")
        if time.time() - t0 > timeout:
            raise RunFailure(f"{what} not reached in {timeout:.0f} s")
        time.sleep(0.5)
    return time.time() - t0


def scrape_all(host: str, port: int, path: str, key: str, since: int):
    """Follow a ``?since=<cursor>`` endpoint to its end."""
    items, cursor = [], since
    while True:
        status, payload = loadgen.http_call(host, port, "GET", f"{path}?since={cursor}&limit=500")
        if status != 200:
            raise RunFailure(f"GET {path}: HTTP {status}: {payload[:200]!r}")
        doc = json.loads(payload)
        batch = doc.get(key, [])
        items.extend(batch)
        new_cursor = int(doc.get("cursor", cursor))
        if len(batch) < 500 or new_cursor == cursor:
            return items, new_cursor
        cursor = new_cursor


def cursor_of(host: str, port: int, path: str) -> int:
    status, payload = loadgen.http_call(host, port, "GET", f"{path}?since=999999999&limit=1")
    if status != 200:
        raise RunFailure(f"GET {path}: HTTP {status}")
    return int(json.loads(payload).get("cursor", 0))


def check_server_log(text: str, on_tpu: bool) -> list:
    problems = []
    if "Traceback (most recent call last)" in text:
        problems.append("server log holds a traceback")
    if "COMPILE ON HOT PATH" in text:
        problems.append("server log reports a compile on the hot path")
    m = re.search(r"resolved kernel paths: quant_kernel=(\S+) kv_kernel=\S+ paged_kernel=(\S+)", text)
    if not m:
        problems.append("server log has no 'resolved kernel paths' line")
    elif on_tpu and (m.group(1) != "True" or m.group(2) != "compiled"):
        problems.append(f"kernels not compiled: quant_kernel={m.group(1)} paged_kernel={m.group(2)}")
    return problems


def layer_metric_file(name: str) -> str:
    """``layer_metrics/<name>.json``; a manifest name ``<base>.<suffix>``
    without a file of its own reads ``<base>.json``, so one reader file
    serves the entries that differ only in ``moves`` and ``workloads``."""
    path = os.path.join(BENCH, "layer_metrics", name + ".json")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "layer_metrics", name.rsplit(".", 1)[0] + ".json")
    return path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()

    manifest = load_json(args.manifest)
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    peaks_table = load_json(os.path.join(BENCH, "peaks.json"))
    rehearsal = bool(cfg.get("rehearsal"))

    def in_cell(metric: dict) -> bool:
        return "workloads" not in metric or cell["name"] in metric["workloads"]

    tag = f"{cell['name']}-seed{args.seed}-trace{args.trace}"
    work = os.path.join(ROOT, ".perfbench_work", cell["name"])
    out_dir = os.path.join(ROOT, "chiprun_out", "perfbench", tag)
    again = 1
    while os.path.exists(out_dir):  # a second run of the same cell and seed keeps its own logs
        again += 1
        out_dir = os.path.join(ROOT, "chiprun_out", "perfbench", f"{tag}-{again}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, "server.log")
    host, port = "127.0.0.1", free_port()

    say(f"perfbench: cell={cell['name']} config={cfg['name']} traffic={cell['traffic']} "
        f"seed={args.seed} seconds={args.seconds:g} trace={args.trace}")
    env = server_env(cfg, cell, work, bool(args.trace))
    client = None
    proc = None
    result = None
    with open(log_path, "w", encoding="utf-8") as log_fh:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "launcher.py"),
             "--config", os.path.join(ROOT, cfg_entry["file"]), "--port", str(port),
             "--work", work, "--chips", str(cell["chips"]), "--trace", str(args.trace)],
            env=env, stdout=log_fh, stderr=subprocess.STDOUT, cwd=ROOT,
        )

        def alive() -> bool:
            return proc.poll() is None

        def log_text() -> str:
            with open(log_path, encoding="utf-8", errors="replace") as fh:
                return fh.read()

        def get_ok(path: str) -> bool:
            return loadgen.http_call(host, port, "GET", path, timeout=10)[0] == 200

        try:
            # ---- set-up: everything before the measured window ---------- #
            wait_until(lambda: os.path.exists(os.path.join(work, "device.json")),
                       "the device report", 180, alive)
            device = load_json(os.path.join(work, "device.json"))
            on_tpu = device["platform"] == "tpu"
            wait_until(lambda: get_ok("/health"), "/health", 180, alive)
            ready_s = wait_until(lambda: get_ok("/internal/ready"), "/internal/ready",
                                 READY_TIMEOUT_S, alive)
            say(f"setup: ready {time.time() - T_PROCESS_START:.1f} s after process start "
                f"(waited {ready_s:.1f} s for ready)")

            docs = loadgen.build_corpus(traffic, args.seed)
            if docs:
                t0 = time.time()
                failures = loadgen.ingest_corpus(
                    host, port, docs, int(traffic["corpus"].get("ingest_threads", 4)))
                if failures:
                    raise RunFailure(f"ingest failed: {failures[:3]}")
                hist = {}
                for _, text in docs:
                    hist[len(text)] = hist.get(len(text), 0) + 1
                say(f"ingest: {len(docs)} documents in {time.time() - t0:.1f} s; "
                    f"chunk bytes histogram {dict(sorted(hist.items()))}")

            deck = loadgen.build_deck(traffic, args.seed)
            client = loadgen.Client(host, port, traffic, deck, canned=canned_answers())
            cap = float(traffic.get("ramp", {}).get("cap_s", 40.0))
            t_ramp = time.monotonic()
            need = client.start(args.seed, horizon_s=args.seconds + cap + 30)
            got = 0
            while got < need and time.monotonic() - t_ramp < cap:
                if client.first_done.acquire(timeout=0.25):
                    got += 1
                if not alive():
                    raise RunFailure("server exited during the ramp")
            say(f"ramp: {got}/{need} clients finished a request after "
                f"{time.monotonic() - t_ramp:.1f} s (cap {cap:g} s)")

            # The comparison with the plain reference runs in the server child
            # (host CPU) while the clients ramp up; the window opens after both.
            ref_path = os.path.join(work, "reference.json")
            wait_until(lambda: os.path.exists(ref_path), "the reference comparison", 300, alive)
            ref = load_json(ref_path)
            say("reference: " + json.dumps({k: v for k, v in ref.items() if k != "error"}))
            if ref.get("error"):
                say("reference error:\n" + ref["error"])

            flight_cursor = cursor_of(host, port, "/internal/requests")
            span_cursor = cursor_of(host, port, "/internal/timeline")
            status, payload = loadgen.http_call(host, port, "GET", "/metrics")
            metrics_before = readers.parse_metrics(payload.decode(errors="replace")) if status == 200 else {}

            # ---- the measured window ------------------------------------ #
            t_open = time.monotonic()
            setup_s = time.time() - T_PROCESS_START
            t_close = t_open + args.seconds
            trace_dir = os.path.join(work, "trace")
            if args.trace:
                # the LAST seconds of the window are traced, so that writing
                # the capture out falls after the window has closed
                span = min(float(traffic.get("trace_window_s", 5.0)), args.seconds * 0.8)
                time.sleep(max(0.0, t_close - span - time.monotonic()))
                open(os.path.join(work, "trace.start"), "w").close()
            time.sleep(max(0.0, t_close - time.monotonic()))
            t_close = time.monotonic()
            if args.trace:
                open(os.path.join(work, "trace.stop"), "w").close()
            status, payload = loadgen.http_call(host, port, "GET", "/metrics")
            metrics_after = readers.parse_metrics(payload.decode(errors="replace")) if status == 200 else {}
            if args.trace:
                done = os.path.join(work, "trace.done")
                wait_until(lambda: os.path.exists(done), "the profiler to write its capture", 300, alive)
                say("profiler: " + json.dumps(load_json(done)))
            log_len = len(log_text())  # cutting the open streams below makes the server log tracebacks
            client.stop()
            flight, _ = scrape_all(host, port, "/internal/requests", "timelines", flight_cursor)
            spans, _ = scrape_all(host, port, "/internal/timeline", "spans", span_cursor)

            # ---- reduce -------------------------------------------------- #
            w0, w1 = 0.0, t_close - t_open
            reqs = [r.to_json(t_open) for r in client.logs]
            with open(os.path.join(out_dir, "frames.jsonl"), "w", encoding="utf-8") as fh:
                fh.write(json.dumps({"window_s": [w0, w1], "setup_s": setup_s, "seed": args.seed}) + "\n")
                for r in reqs:
                    fh.write(json.dumps(r) + "\n")
            n = reduce.counts(reqs, w0, w1)
            e2e = reduce.end_to_end(reqs, w0, w1)
            e2e["setup_s"] = setup_s
            tenths = reduce.sub_window_rates(reqs, w0, w1, 10)
            seconds = reduce.sub_window_rates(reqs, w0, w1, max(1, int(w1)))
            say("window: " + json.dumps({
                "seconds": w1, "tokens": reduce.window_tokens(reqs, w0, w1),
                "requests_finished": n["attempted"], "failed": n["failed"],
                "first_frames": len(reduce.ttfts_ms(reqs, w0, w1)),
                "gaps": len(reduce.gaps_ms(reqs, w0, w1)),
                "out_tok_s_median_of_tenths": reduce.percentile(tenths, 50),
                "tok_s_per_second_bin": [round(x) for x in seconds],
                "generator_lateness_p99_ms": reduce.percentile([x * 1000 for x in client.lateness_s], 99),
            }))

            # the server's view of the requests that finished in the window
            t_open_wall = time.time() - (time.monotonic() - t_open)
            fin = []
            for tl in flight:
                if not any(e.get("event") == "http_request" and e.get("path") == "/generate"
                           for e in tl.get("timeline", [])):
                    continue
                end_wall = tl.get("started_at", 0.0) + (tl.get("total_s") or 0.0)
                if t_open_wall <= end_wall < t_open_wall + w1:
                    fin.append(tl)
            prompt_tokens = [v for v in (readers.event_attr(t, "submit", "prompt_tokens") for t in fin) if v]
            generated = sorted(int(v) for v in (readers.event_attr(t, "engine_finish", "generated") for t in fin)
                               if v is not None)
            stops = {}
            for tl in fin:
                for e in tl.get("timeline", []):
                    if e.get("event") == "engine_finish":
                        stops[e.get("stop")] = stops.get(e.get("stop"), 0) + 1
            with open(os.path.join(out_dir, "server_view.json"), "w", encoding="utf-8") as fh:
                json.dump({"t_open_wall": t_open_wall, "flight": fin, "spans": spans}, fh)
            say("server: " + json.dumps({
                "timelines_finished_in_window": len(fin),
                "prompt_tokens_p10_p50_p90": [reduce.percentile(prompt_tokens, q) for q in (10, 50, 90)],
                "generated_p10_p50_p90": [reduce.percentile(generated, q) for q in (10, 50, 90)],
                "stop_reasons": stops,
            }))

            # ---- correct ------------------------------------------------- #
            problems = []
            if not on_tpu:
                problems.append(f"platform is {device['platform']!r}, not 'tpu'")
            peaks = peaks_table.get(device["kind"])
            if on_tpu and peaks is None:
                raise RunFailure(f"device kind {device['kind']!r} is not in peaks.json")
            if not ref.get("ok"):
                problems.append("the plain reference disagrees with the engine")
            done_ok = [r for r in reduce.finished_in(reqs, w0, w1) if r["status"] == "ok"]
            delivered = sorted(len(r["frames_s"]) for r in done_ok)
            # the same requests seen from both ends, compared as multisets
            # (no request id crosses the wire). A request that ends at an
            # edge of the window may be counted by one side only: those are
            # dropped, and every other count must find its partner.
            edge = abs(len(delivered) - len(generated))
            lonely = reduce.unpaired_counts(delivered, generated)
            if not delivered or edge > max(2, len(delivered) // 10):
                problems.append(f"client finished {len(delivered)} requests, server {len(generated)}")
            elif lonely:
                problems.append("delivered tokens differ from the engine's generated counts: "
                                f"no partner for {lonely[:8]}")
            if any(len(r["frames_s"]) > r["max_tokens"] for r in done_ok):
                problems.append("a stream delivered more tokens than asked")

            def grew(metric: str, **labels: str) -> float:
                return (readers.metric_sum(metrics_after, metric, **labels)
                        - readers.metric_sum(metrics_before, metric, **labels))

            kernel = grew("genai_engine_paged_attn_dispatches_total", path="kernel")
            gather = grew("genai_engine_paged_attn_dispatches_total", path="gather")
            hot = grew("genai_engine_hot_path_compiles_total")
            if kernel <= 0:
                problems.append("no page-attention kernel dispatch in the window")
            if gather > 0:
                problems.append(f"{gather:g} paged dispatches took the XLA gather path")
            if hot > 0:
                problems.append(f"{hot:g} compiles on the hot path inside the window")
            if n["attempted"] == 0:
                problems.append("no request finished inside the window")
            say(f"counters: paged_attn kernel={kernel:g} gather={gather:g} hot_path_compiles={hot:g}")
            result = dict(n=n, e2e=e2e, problems=problems, device=device, on_tpu=on_tpu, peaks=peaks,
                          reqs=reqs, fin=fin, spans=spans, metrics_before=metrics_before,
                          metrics_after=metrics_after, window=(w0, w1), log_len=log_len,
                          trace_dir=trace_dir)
        except RunFailure as exc:
            say(f"FAIL: {exc}")
            for ln in log_text().splitlines()[-40:]:
                say(f"  [server log] {ln}")
        finally:
            if client is not None:
                client.stop()
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=30)
    if result is None:
        return proc.returncode if proc.returncode not in (0, None, -15) else 1

    problems = result["problems"] + check_server_log(log_text()[: result["log_len"]], result["on_tpu"])
    device = dict(result["device"])
    final_path = os.path.join(work, "device_final.json")
    device["memory_peak_bytes"] = load_json(final_path).get("memory_peak_bytes", 0) if os.path.exists(final_path) else 0
    if result["on_tpu"] and not device["memory_peak_bytes"]:
        problems.append("the server child reported no peak memory")

    # ---- the traced interval, reduced in a child that may import jax ---- #
    trace_summary = None
    if args.trace:
        env2 = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
        red = subprocess.run(
            [sys.executable, os.path.join(BENCH, "trace_reduce.py"), result["trace_dir"],
             os.path.join(out_dir, "trace_events_sample.json"), "6000"],
            env=env2, capture_output=True, text=True, timeout=600, cwd=ROOT,
        )
        if red.returncode == 0:
            trace_summary = json.loads(red.stdout.strip().splitlines()[-1])
            with open(os.path.join(out_dir, "trace_summary.json"), "w", encoding="utf-8") as fh:
                json.dump(trace_summary, fh, indent=1)
            say("trace: " + json.dumps({
                "devices": trace_summary.get("devices"), "window_s": trace_summary.get("window_s"),
                "busy_s": trace_summary.get("busy_s"),
                "modules": {k: v for k, v in sorted((trace_summary.get("modules") or {}).items(),
                                                    key=lambda kv: -kv[1]["total_s"])[:8]},
            }))
        else:
            say("trace reduction failed: " + red.stderr[-800:])
        if result["on_tpu"] and not (trace_summary and trace_summary.get("busy_s", 0) > 0):
            problems.append("the traced interval shows no operation on the device")

    # ---- metrics of this cell ------------------------------------------- #
    metrics = {}
    if args.trace:
        ctx = {
            "requests": result["reqs"], "window": result["window"], "flight": result["fin"],
            "pairs": readers.join_in_order(
                reduce.finished_in(result["reqs"], *result["window"]), result["fin"]),
            "spans": result["spans"], "metrics_before": result["metrics_before"],
            "metrics_after": result["metrics_after"], "trace": trace_summary,
            "config": cfg, "peaks": result["peaks"] or {},
        }
        cache = {}

        def read(name: str):
            if name not in cache:
                spec = load_json(layer_metric_file(name))
                cache[name] = readers.READERS[spec["reader"]](ctx, spec.get("params", {}))
            return cache[name]

        ctx["read"] = read
        for m in manifest["per_layer"]:
            if in_cell(m):
                value = read(m["name"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if in_cell(m):
                value = result["e2e"].get(m["name"])
                if value is None:
                    problems.append(f"end-to-end metric {m['name']} has no sample in the window")
                else:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = not problems
    for p in problems:
        say(f"not correct: {p}")
    if not result["on_tpu"]:
        # a CPU run yields counts, never a time or a rate: keep its numbers
        # off the metric names
        say("rehearsal values (CPU; NOT measurements): " + json.dumps(
            {k: v["value"] for k, v in metrics.items()}))
        metrics = {}
    line = {
        "correct": bool(correct), "attempted": result["n"]["attempted"],
        "failed": result["n"]["failed"], "metrics": metrics, "device": device,
    }
    if args.trace and trace_summary and trace_summary.get("devices"):
        line["device"]["busy_s"] = trace_summary["busy_s"]
        line["device"]["window_s"] = trace_summary["window_s"]
        line["breakdown"] = trace_reduce.breakdown(trace_summary)
    say(json.dumps(line))
    if not rehearsal:
        shutil.rmtree(work, ignore_errors=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
