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
A traced run reports no end-to-end metric, so its window is as long as
the traffic file's ``traced_run_window_s`` says (``--seconds`` where it
says nothing): it has to end inside the driver's limit for one run.

Nothing here knows a model. The configuration file names its adapter
(``perfbench/arch/``), a per-layer metric file names its reader
(``readers.resolve``), and the configuration may ADD to what ``correct``
requires of the kernel-path line and of the counters (``"correct"``).

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
import threading
import time

T_PROCESS_START = time.time()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import arch, loadgen, readers, reduce, trace_reduce  # noqa: E402
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
    """Follow a ``?since=<cursor>`` endpoint to its end. The cursor the
    server returns is the NEWEST in the process, not the last it sent, so
    a full page is followed from the ``seq`` of its last item (followed
    from the returned cursor it lost every span past the 500th: the last
    ~8 s of a 51 s window, until PR 28)."""
    items, cursor = [], since
    while True:
        status, payload = loadgen.http_call(host, port, "GET", f"{path}?since={cursor}&limit=500")
        if status != 200:
            raise RunFailure(f"GET {path}: HTTP {status}: {payload[:200]!r}")
        doc = json.loads(payload)
        batch = doc.get(key, [])
        items.extend(batch)
        newest = int(doc.get("cursor", cursor))
        new_cursor = max((int(it["seq"]) for it in batch if "seq" in it), default=newest)
        if len(batch) < 500 or new_cursor == cursor:
            return items, newest
        cursor = new_cursor


def cursor_of(host: str, port: int, path: str) -> int:
    status, payload = loadgen.http_call(host, port, "GET", f"{path}?since=999999999&limit=1")
    if status != 200:
        raise RunFailure(f"GET {path}: HTTP {status}")
    return int(json.loads(payload).get("cursor", 0))


# What every cell's counters have to show between the two ``/metrics``
# scrapes around the window. A configuration adds to these lists under
# ``"correct"``; it cannot take one away.
MUST_GROW = [{"metric": "genai_engine_paged_attn_dispatches_total", "labels": {"path": "kernel"}}]
MUST_NOT_GROW = [{"metric": "genai_engine_paged_attn_dispatches_total", "labels": {"path": "gather"}},
                 {"metric": "genai_engine_hot_path_compiles_total"}]


def required_kernel_paths(cfg: dict, on_tpu: bool) -> list:
    """``(key, value)`` pairs the engine's ``resolved kernel paths:`` line
    has to show. On a TPU every cell needs the compiled page kernel, and a
    configuration served in a quantised format the matmul kernel of that
    format (the engine prints True for int8 and the format's own name
    otherwise); the configuration's ``correct.kernel_paths`` come on top."""
    need = []
    if on_tpu:
        need.append(("paged_kernel", "compiled"))
        fmt = cfg.get("server_env", {}).get("APP_ENGINE_QUANTIZATION", "none")
        if fmt not in ("", "none"):
            need.append(("quant_kernel", "True" if fmt == "int8" else fmt))
    need += sorted((k, str(v)) for k, v in cfg.get("correct", {}).get("kernel_paths", {}).items())
    return need


def check_server_log(text: str, on_tpu: bool, cfg: dict) -> list:
    problems = []
    if "Traceback (most recent call last)" in text:
        problems.append("server log holds a traceback")
    if "COMPILE ON HOT PATH" in text:
        problems.append("server log reports a compile on the hot path")
    m = re.search(r"resolved kernel paths: (.*)", text)
    if not m:
        problems.append("server log has no 'resolved kernel paths' line")
        return problems
    resolved = dict(re.findall(r"(\w+)=([^\s,()]+)", m.group(1)))
    wrong = [f"{k}={resolved.get(k, '<absent>')} (want {v})"
             for k, v in required_kernel_paths(cfg, on_tpu) if resolved.get(k) != v]
    if wrong:
        problems.append("kernel paths not as required: " + " ".join(wrong))
    return problems


def counter_label(spec: dict) -> str:
    labels = ",".join(f'{k}="{v}"' for k, v in sorted(spec.get("labels", {}).items()))
    return spec["metric"] + (f"{{{labels}}}" if labels else "")


def check_counters(before: dict, after: dict, cfg: dict):
    """Problems and the readings behind them: every counter of MUST_GROW
    and of the configuration's ``counters_must_grow`` grew between the
    two scrapes, none of MUST_NOT_GROW and ``counters_must_not_grow`` did."""
    extra = cfg.get("correct", {})
    problems, readings = [], []
    for specs, must_grow in ((MUST_GROW + list(extra.get("counters_must_grow", [])), True),
                             (MUST_NOT_GROW + list(extra.get("counters_must_not_grow", [])), False)):
        for spec in specs:
            labels = spec.get("labels", {})
            grew = (readers.metric_sum(after, spec["metric"], **labels)
                    - readers.metric_sum(before, spec["metric"], **labels))
            readings.append(f"{counter_label(spec)} grew by {grew:g} ({'more than' if must_grow else 'limit'} 0)")
            if must_grow and grew <= 0:
                problems.append(f"{counter_label(spec)} did not grow in the window")
            elif not must_grow and grew > 0:
                problems.append(f"{counter_label(spec)} grew by {grew:g} in the window")
    return problems, readings


class Heartbeat(threading.Thread):
    """Sleeps 20 ms at a time and keeps its longest gap. A window in which
    the HOST stood still shows here (and the clients, threads of this
    process, stood still with it); a stall of the server child or of the
    device does not. Run-to-run stalls of several seconds have been seen
    (PERF.md section 6); this says whose they are."""

    def __init__(self):
        super().__init__(daemon=True, name="perfbench-heartbeat")
        self.max_gap_s = 0.0
        self._halt = threading.Event()

    def run(self):
        last = time.monotonic()
        while not self._halt.wait(0.02):
            now = time.monotonic()
            self.max_gap_s = max(self.max_gap_s, now - last)
            last = now

    def stop(self) -> float:
        self._halt.set()
        self.join(timeout=5)
        return self.max_gap_s


def window_seconds(traffic: dict, seconds: float, trace: int) -> float:
    """The measured window of an untraced run is ``--seconds``. A traced
    run reports no end-to-end metric: the traffic file may give it a
    shorter window (``traced_run_window_s``), of which the last
    ``trace_window_s`` are traced."""
    return float(traffic.get("traced_run_window_s", seconds)) if trace else float(seconds)


def spans_in_window(spans: list, t_open_wall: float, seconds: float) -> list:
    """Dispatch spans recorded inside the window (``t_wall`` is the
    server's wall clock; a span without one is kept)."""
    return [sp for sp in spans if t_open_wall <= sp.get("t_wall", t_open_wall) < t_open_wall + seconds]


def layer_metric_file(name: str, beside: str = "") -> str:
    """``layer_metrics/<name>.json``, looked for beside the manifest
    first (``beside``) and then in ``perfbench/``; a manifest name
    ``<base>.<suffix>`` without a file of its own reads ``<base>.json``,
    so one reader file serves the entries that differ only in ``moves``
    and ``workloads``."""
    names = [name] + ([name.rsplit(".", 1)[0]] if "." in name else [])
    dirs = ([os.path.join(beside, "layer_metrics")] if beside else []) + [os.path.join(BENCH, "layer_metrics")]
    for d in dirs:
        for n in names:
            path = os.path.join(d, n + ".json")
            if os.path.exists(path):
                return path
    return os.path.join(dirs[-1], name + ".json")


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
    roots = [os.path.join(ROOT, p) for p in manifest["paths"]]
    adapter = arch.load(cfg, roots)  # a configuration without a sound adapter fails before anything starts
    window_s = window_seconds(traffic, args.seconds, args.trace)

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
        f"seed={args.seed} seconds={args.seconds:g} window={window_s:g} trace={args.trace}")
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
            heartbeat = Heartbeat()
            heartbeat.start()
            t_open = time.monotonic()
            setup_s = time.time() - T_PROCESS_START
            t_close = t_open + window_s
            trace_dir = os.path.join(work, "trace")
            if args.trace:
                # the LAST seconds of the window are traced, so that writing
                # the capture out falls after the window has closed
                span = min(float(traffic.get("trace_window_s", 5.0)), window_s * 0.8)
                time.sleep(max(0.0, t_close - span - time.monotonic()))
                open(os.path.join(work, "trace.start"), "w").close()
            time.sleep(max(0.0, t_close - time.monotonic()))
            t_close = time.monotonic()
            heartbeat_gap_s = heartbeat.stop()
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
                "host_heartbeat_max_gap_ms": heartbeat_gap_s * 1000.0,
            }))

            # the server's view of the requests that finished in the window, and
            # of the dispatches made in it (a traced run goes on serving while the
            # profiler writes its capture, and that starves the server: those
            # spans are not the window's)
            t_open_wall = time.time() - (time.monotonic() - t_open)
            spans = spans_in_window(spans, t_open_wall, w1)
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

            counter_problems, counter_readings = check_counters(metrics_before, metrics_after, cfg)
            problems += counter_problems
            if n["attempted"] == 0:
                problems.append("no request finished inside the window")
            say("counters: " + "; ".join(counter_readings))
            compared = [
                f"reference prefill_rel_err {ref.get('prefill_rel_err')} (limit {ref.get('tolerance')})",
                f"reference decode_margin_max {ref.get('decode_margin_max')} over "
                f"{ref.get('decode_tokens_checked')} tokens (limit {ref.get('tolerance')})",
                f"requests finished in the window: client {len(delivered)}, server {len(generated)}, "
                f"without a partner {len(lonely)} (limit 0)",
            ] + counter_readings
            result = dict(n=n, e2e=e2e, problems=problems, device=device, on_tpu=on_tpu, peaks=peaks,
                          reqs=reqs, fin=fin, spans=spans, metrics_before=metrics_before,
                          metrics_after=metrics_after, window=(w0, w1), log_len=log_len,
                          trace_dir=trace_dir, compared=compared)
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

    problems = result["problems"] + check_server_log(log_text()[: result["log_len"]], result["on_tpu"], cfg)
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
            "config": cfg, "adapter": adapter, "peaks": result["peaks"] or {},
        }
        cache = {}
        beside = os.path.dirname(os.path.abspath(args.manifest))

        def read(name: str):
            if name not in cache:
                spec = load_json(layer_metric_file(name, beside))
                cache[name] = readers.resolve(spec["reader"], roots)(ctx, spec.get("params", {}))
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
    # each number compared beside its limit, as the last lines of stderr
    print("\n".join(["compared:"] + result["compared"] + [f"not correct: {p}" for p in problems]),
          file=sys.stderr, flush=True)
    if not rehearsal:
        shutil.rmtree(work, ignore_errors=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
