#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run with no arguments on a machine with ONE TPU chip. It drives the
north-star path end to end through the entry points a user would call:

  1. **kernels** (child process, holds the chip, exits): each of the four
     Pallas kernels of the main path, compiled (``interpret=False``) at
     Llama-3-8B widths on data from ``--seed``, against the repo's own
     XLA/jnp path within a stated tolerance. Its first line names the
     device as jax reports it.
  2. **server** (child process ``python -m generativeaiexamples_tpu.server``,
     holds the chip): ``EXAMPLE_NAME=developer_rag`` on full-width
     ``llama3-8b`` (int8 weights, int8 paged KV), the default
     ``arctic-embed-l`` embedder and the ``tpu`` vector store, random
     weights from a seed. This process is its only client: ready →
     ingest a multi-chunk document → /search → concurrent /generate
     (four with the knowledge base, one without) → delete the document.
     Then it reads /metrics, the flight-recorder timelines and the
     server log and asserts that the compiled kernels served.

``--chips 4`` runs ONLY the tensor-parallel path on four chips and what
it is compared with (one child process that owns all four chips): a
``tensor_parallelism=1`` engine on one device first, torn down, then the
same server entry with ``APP_ENGINE_TENSORPARALLELISM=4``.

This parent process NEVER imports jax: a process that has touched jax
holds the chip, and the child that needs it would fail or hang. Device
facts come from the child that held the chip, after it exited.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Anything but a TPU platform, or any failed check, prints ``"ok": false``
and exits non-zero. ``--preset debug`` rehearses every phase at a tiny
size on the CPU (Pallas interpret mode) — and still ends ``"ok": false``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:  # loaded from elsewhere (tests): the repo's package must resolve
    sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
RESULT_TAG = "CHIP_SMOKE_RESULT "  # child -> parent, one JSON per line
READY_TIMEOUT_S = 1000.0  # launch -> /internal/ready, cold compile included

# Serving sizes. Widths and depth are the model's own and are not cut;
# slots, context and the warm-up ladder are small so that a COLD run
# (every program compiled from nothing) fits the 1200 s budget: each
# (wave rung x window rung) chunked-prefill program is a whole unrolled
# 32-layer compile.
PRESETS = {
    "full": {
        "model": "llama3-8b",
        "embed_model": "",  # config default: snowflake/arctic-embed-l
        "max_batch": 4,
        "max_seq_len": 4096,
        "prefill_chunk": 512,
        "page_size": 128,
        "decode_block": 8,
        "warmup": "512",
        "max_tokens": 32,
        "paged_kernel": "auto",
        "tp_kernels": "auto",
        "tp_model": "llama3-8b",
        # the TP comparison needs no long context; fewer window rungs
        # keep the four-chip call (charged four times) short
        "tp_max_seq_len": 1024,
    },
    # CPU rehearsal: same phases, tiny model, Pallas interpret mode.
    "debug": {
        "model": "debug-1k",
        "embed_model": "debug",
        "max_batch": 4,
        "max_seq_len": 256,
        "prefill_chunk": 64,
        "page_size": 16,
        "decode_block": 4,
        "warmup": "64",
        "max_tokens": 8,
        "paged_kernel": "interpret",
        "tp_kernels": "interpret",
        "tp_model": "kernel-8dev",
        "tp_max_seq_len": 256,
    },
}

TOPICS = [
    "thermal design of the cooling loop", "scheduler admission waves",
    "interconnect topology and routing", "checkpoint resume semantics",
    "vector index compaction", "tokenizer byte fallback rules",
    "tracing span export batching", "quantization scale layout",
]


def say(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def corpus() -> str:
    """A multi-chunk document with distinctive per-section keywords."""
    lines = []
    for i, t in enumerate(TOPICS):
        lines.append(f"Section {i}: {t.title()}.")
        for j in range(30):
            lines.append(
                f"Paragraph {j} of section {i} discusses {t} in detail, "
                f"including parameter {i * 100 + j} and its operational limits."
            )
    return "\n\n".join(lines)


# --------------------------------------------------------------------------- #
# HTTP (stdlib only: the parent stays light and off jax)


def http(method: str, url: str, body=None, headers=None, timeout: float = 60.0):
    """(status, bytes). Non-2xx statuses are returned, not raised."""
    data = None
    headers = dict(headers or {})
    if body is not None and not isinstance(body, bytes):
        data = json.dumps(body).encode()
        headers.setdefault("Content-Type", "application/json")
    elif body is not None:
        data = body
    req = urllib.request.Request(url, data=data, method=method, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read()


def http_ok(method: str, url: str, what: str, **kw) -> bytes:
    status, payload = http(method, url, **kw)
    check(status == 200, f"{what}: HTTP {status}: {payload[:300]!r}")
    return payload


def multipart(filename: str, content: bytes):
    boundary = uuid.uuid4().hex
    body = (
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
        f"filename=\"{filename}\"\r\nContent-Type: text/plain\r\n\r\n"
    ).encode() + content + f"\r\n--{boundary}--\r\n".encode()
    return body, {"Content-Type": f"multipart/form-data; boundary={boundary}"}


def parse_sse(payload: bytes):
    frames = []
    for block in payload.decode("utf-8", errors="replace").split("\n\n"):
        block = block.strip()
        if block.startswith("data: "):
            frames.append(json.loads(block[len("data: "):]))
    return frames


def parse_metrics(text: str):
    """Prometheus text exposition -> {(name, frozenset(labels)): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)", line)
        if not m:
            continue
        labels = frozenset(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            pass
    return out


def metric_sum(metrics, name: str, **labels) -> float:
    want = set(labels.items())
    return sum(
        v for (n, ls), v in metrics.items() if n == name and want <= set(ls)
    )


# --------------------------------------------------------------------------- #
# Checks on recorded text (pure functions: tier-1 tests feed them fixtures)


def check_metrics_text(text: str, gather_explained: bool = False, before=None) -> dict:
    """The kernels served: kernel-path page-attention dispatches > 0, any
    gather-path dispatch accounted for by a start-up line, and no compile
    on the hot path after ready. The families are process-global:
    ``before`` holds what an EARLIER engine of the same process had
    already counted (the tp=1 reference of ``--chips 4``) and is
    subtracted."""
    m = parse_metrics(text)
    before = before or {}
    kernel = metric_sum(m, "genai_engine_paged_attn_dispatches_total", path="kernel") - before.get("kernel", 0)
    gather = metric_sum(m, "genai_engine_paged_attn_dispatches_total", path="gather") - before.get("gather", 0)
    hot = metric_sum(m, "genai_engine_hot_path_compiles_total")
    tokens = metric_sum(m, "genai_engine_generated_tokens_total") - before.get("tokens", 0)
    check(
        kernel > 0,
        "genai_engine_paged_attn_dispatches_total{path=\"kernel\"} is "
        f"{kernel:g}: the page-attention kernel did not serve "
        f"(gather dispatches: {gather:g})",
    )
    check(
        gather == 0 or gather_explained,
        f"{gather:g} paged dispatches took the XLA gather path and no "
        "start-up line accounts for them",
    )
    check(hot == 0, f"genai_engine_hot_path_compiles_total is {hot:g} after ready")
    return {
        "kernel_dispatches": kernel, "gather_dispatches": gather,
        "hot_path_compiles": hot, "generated_tokens_total": tokens,
    }


_KERNEL_LINE = re.compile(
    r"resolved kernel paths: quant_kernel=(\S+) "
    r"paged_kernel=(\S+) paged_verify_kernel=(\S+) "
    r"(?:paged_extend_kernel=\S+ )?tp_kernels=(\S+) (?:kv_scales=(\S+) )?"
    r"\(backend=(\w+), devices=(\d+)\)"
)
_WARMUP_LINE = re.compile(
    r"Engine warmup complete "
    r"\(engine build ([\d.]+) s, warmup ([\d.]+) s; (device memory[^)]*)\)"
)


def check_server_log(text: str, want_compiled: bool, tp: int = 1) -> dict:
    """No traceback, no REFUSED geometry, and the resolved kernel paths
    are the compiled ones (``want_compiled`` is False only on the CPU
    rehearsal, where interpret mode stands in)."""
    check("Traceback (most recent call last)" not in text, "server log holds a traceback")
    check("REFUSED" not in text, "server log holds a kernel REFUSED line")
    check("COMPILE ON HOT PATH" not in text, "server log reports a hot-path compile")
    m = _KERNEL_LINE.search(text)
    check(m is not None, "server log has no 'resolved kernel paths' line")
    quant, paged, verify, tpk, kv_scales, backend, devices = m.groups()
    paths = {
        "quant_kernel": quant, "paged_kernel": paged,
        "paged_verify_kernel": verify, "tp_kernels": tpk,
        "kv_scales": kv_scales, "backend": backend, "devices": int(devices),
    }
    if want_compiled:
        check(backend == "tpu", f"engine resolved on backend {backend}, not tpu")
        check(quant == "True", f"int8 matmul kernel not resolved (quant_kernel={quant})")
        check(paged == "compiled", f"page-attention kernel not compiled (paged_kernel={paged})")
        # the full preset's int8 pool (128-token pages, 8 KV heads) tiles the
        # lanes; only a pool whose heads are sharded keeps them token-major
        want_scales = "lane_dense" if tp == 1 else "token_major"
        check(kv_scales == want_scales, f"int8 pool's scale planes are {kv_scales}, not {want_scales}")
    else:
        check(paged in ("compiled", "interpret"), f"paged_kernel={paged}")
    if tp > 1:
        check(tpk == f"{tp}-way", f"TP kernel path not engaged (tp_kernels={tpk})")
    w = _WARMUP_LINE.search(text)
    check(w is not None, "server log has no 'Engine warmup complete' line")
    paths.update(
        engine_build_s=float(w.group(1)), warmup_s=float(w.group(2)),
        device_memory=w.group(3),
    )
    return paths


def gather_explained_by_log(text: str) -> bool:
    """The engine says so at start-up when spec-verify chunks stay on the
    gather (engine/llm_engine.py _resolve_paged_kernel)."""
    return "verify dispatches stay on" in text


def final_line(ok: bool, device: dict) -> str:
    return json.dumps({"ok": bool(ok), "device": device})


# --------------------------------------------------------------------------- #
# Child processes


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra or {})
    return env


def run_child(phase: str, args, log_name: str, timeout: float, extra_env=None):
    """Run ``chip_smoke.py --phase`` to its end; relay its lines; return
    (exit code, [result dicts])."""
    cmd = [
        sys.executable, os.path.abspath(__file__), "--phase", phase,
        "--preset", args.preset, "--seed", str(args.seed),
        "--port", str(args.port),
    ]
    log_path = os.path.join(OUT, log_name)
    results = []
    with open(log_path, "w", encoding="utf-8") as log_fh:
        proc = subprocess.Popen(
            cmd, env=child_env(extra_env), stdout=subprocess.PIPE,
            stderr=log_fh, text=True, cwd=ROOT,
        )
        killer = threading.Timer(timeout, proc.kill)
        killer.start()
        try:
            for line in proc.stdout:
                line = line.rstrip("\n")
                if line.startswith(RESULT_TAG):
                    results.append(json.loads(line[len(RESULT_TAG):]))
                else:
                    say(f"  [{phase}] {line}")
            rc = proc.wait()
        finally:
            killer.cancel()
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if rc != 0:
        with open(log_path, encoding="utf-8", errors="replace") as fh:
            tail = fh.readlines()[-25:]
        for ln in tail:
            say(f"  [{phase} stderr] {ln.rstrip()}")
    return rc, results


def emit(obj: dict) -> None:
    """Child side of RESULT_TAG."""
    print(RESULT_TAG + json.dumps(obj), flush=True)


def cache_state():
    """(dir, entry count) under the one compile-cache rule."""
    from generativeaiexamples_tpu.utils import jax_env

    path = jax_env.compile_cache_dir()
    try:
        n = len([f for f in os.listdir(path) if not f.startswith(".")])
    except OSError:
        n = 0
    return path, n


# --------------------------------------------------------------------------- #
# Phase: kernels (child; imports jax)


def _device_facts():
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def phase_kernels(args) -> int:
    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = _device_facts()
    emit({"device": device})
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and args.preset == "full":
        print("no TPU: the full preset has nothing to run on", flush=True)
        return 3
    interpret = not on_tpu
    full = args.preset == "full"

    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops import (
        decode_attention as da, flash_attention as fa, int8_matmul as mm,
        page_attention as pa, quant,
    )

    rng = np.random.default_rng(args.seed)
    # Stated tolerances, as max|kernel - plain| / max|plain|: the plain
    # paths round dequantized weights to bf16 before the dot and XLA's
    # default f32 einsum precision on the TPU is a bf16 pass, so a few
    # bf16 ulps (2^-8) of the output range is agreement.
    TOL_MM, TOL_ATTN = 0.02, 0.05
    failures = []

    def rel(out, ref):
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        if not np.all(np.isfinite(out)):
            return float("inf")
        return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-6))

    def report(name, err, tol, seconds):
        ok = err <= tol
        print(
            f"kernel {name}: rel_err={err:.4g} tol={tol} "
            f"{'ok' if ok else 'FAIL'} ({seconds:.1f}s incl. compile)",
            flush=True,
        )
        if not ok:
            failures.append(name)

    # 1. weight-streaming int8 matmul, every (K, F) of the 8B step
    shapes = (
        [(4096, 6144), (4096, 28672), (14336, 4096), (4096, 128256)]
        if full else [(256, 512), (512, 200)]
    )
    for K, F in shapes:
        t0 = time.time()
        x = jnp.asarray(rng.standard_normal((16, K)), jnp.bfloat16)
        # padded into the kernel layout exactly as the packer does
        q = quant._layout(jnp.asarray(rng.integers(-127, 128, (K, F)), jnp.int8), 1, "column")
        scale = jnp.asarray(rng.uniform(0.5, 1.5, (1, F)) / (73.0 * K ** 0.5), jnp.float32)
        out = mm.int8_matmul(x, q, scale, interpret=interpret)
        ref = mm.int8_matmul_xla(x, q, scale)
        report(f"int8_matmul M=16 K={K} F={F}", rel(out, ref), TOL_MM, time.time() - t0)

    # 2. ragged page attention over an int8 pool (the served cache; its
    #    scale planes lane-dense, as init_kv_pool stores them on one
    #    device), against the XLA gather the engine falls back to, and
    #    against the same kernel over token-major planes of the same
    #    values (what a head-sharded pool keeps): the same bits
    B, Hq, Hkv, Dh, S = (16, 32, 8, 128, 4096) if full else (2, 8, 8, 128, 256)
    page = 128 if full else 16
    Pmax = S // page
    t0 = time.time()
    pos = jnp.asarray(rng.integers(1, S, (B,)), jnp.int32)
    n_pages = B * Pmax + 1
    pk = jnp.asarray(rng.integers(-127, 128, (n_pages, page, Hkv, Dh)), jnp.int8)
    pv = jnp.asarray(rng.integers(-127, 128, (n_pages, page, Hkv, Dh)), jnp.int8)
    pks = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, page, Hkv)), jnp.float32)
    pvs = jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, page, Hkv)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(B, Pmax), jnp.int32
    )
    qp = jnp.asarray(rng.standard_normal((B, 1, Hq, Dh)), jnp.bfloat16)
    dense = (n_pages,) + llama.kv_scale_plane_shape(page, Hkv)
    check(dense[2] == 128 and dense != pks.shape, f"scale planes of {dense} are not lane-dense")
    dks, dvs = pks.reshape(dense), pvs.reshape(dense)
    out = pa.paged_attention(qp, pk, pv, tables, pos, dks, dvs, interpret=interpret)

    def gathered(buf):
        return jnp.swapaxes(llama._gather_page_window(buf, tables, Pmax, page), 1, 2)

    def gathered_scales(plane):
        return jnp.swapaxes(llama.gather_kv_scales(plane, tables, Pmax, page), 1, 2)[:, :, None, :]

    ref = da.decode_attention_xla(
        qp, gathered(pk), gathered_scales(dks), gathered(pv), gathered_scales(dvs), pos[:, None],
    )
    report(
        f"paged_attention int8 B={B} page={page} Pmax={Pmax}, softmax over {pa.score_rows(Hq, Hkv)} "
        f"of {Hq} score rows a page", rel(out, ref), TOL_ATTN, time.time() - t0,
    )
    t0 = time.time()
    token_major = pa.paged_attention(qp, pk, pv, tables, pos, pks, pvs, interpret=interpret)
    same = np.array_equal(np.asarray(out, np.float32), np.asarray(token_major, np.float32))
    report(
        f"paged_attention int8 lane-dense scales {dense[1:]} x{pa.pages_per_step(pk, dks)} pages a step "
        f"== token-major scales {pks.shape[1:]} x{pa.pages_per_step(pk, pks)}, bit for bit on {B} live rows",
        0.0 if same else float("inf"), 0.0, time.time() - t0,
    )

    # 3. flash prefill against the einsum attention
    T = 2048 if full else 128
    t0 = time.time()
    fq = jnp.asarray(rng.standard_normal((1, T, Hq, Dh)), jnp.bfloat16)
    fk = jnp.asarray(rng.standard_normal((1, T, Hkv, Dh)), jnp.bfloat16)
    fv = jnp.asarray(rng.standard_normal((1, T, Hkv, Dh)), jnp.bfloat16)
    out = fa.flash_attention_causal(fq, fk, fv, interpret=interpret)
    tpos = jnp.arange(T, dtype=jnp.int32)[None]
    ref = llama._attention(fq, fk, fv, tpos[:, :, None] >= tpos[:, None, :])
    report(f"flash_attention_causal T={T}", rel(out, ref), TOL_ATTN, time.time() - t0)

    emit({"kernels_failed": failures})
    return 1 if failures else 0


# --------------------------------------------------------------------------- #
# Phase: server (parent side — no jax here)


def server_env(preset: dict, port: int, work: str, tp: int) -> dict:
    env = {
        "EXAMPLE_NAME": "developer_rag",
        "APP_LLM_MODELENGINE": "tpu",
        "APP_VECTORSTORE_NAME": "tpu",
        "APP_VECTORSTORE_PERSISTDIR": os.path.join(work, "vs"),
        "DOC_UPLOAD_DIR": os.path.join(work, "uploads"),
        "APP_ENGINE_SNAPSHOTSPOOLDIR": os.path.join(work, "snapshots"),
        "APP_BLACKBOX_DIR": os.path.join(work, "blackbox"),
        # random-init embeddings have ~0 cosine similarity: no threshold,
        # so retrieval still fills the context window
        "APP_RETRIEVER_SCORETHRESHOLD": "0",
        "APP_ENGINE_MODELCONFIGNAME": preset["tp_model"] if tp > 1 else preset["model"],
        "APP_ENGINE_QUANTIZATION": "int8",
        "APP_ENGINE_KVCACHEDTYPE": "int8",
        "APP_ENGINE_TENSORPARALLELISM": str(tp),
        "APP_ENGINE_MAXBATCHSIZE": str(preset["max_batch"]),
        "APP_ENGINE_MAXSEQLEN": str(preset["tp_max_seq_len"] if tp > 1 else preset["max_seq_len"]),
        "APP_ENGINE_PREFILLCHUNK": str(preset["prefill_chunk"]),
        "APP_ENGINE_PAGESIZE": str(preset["page_size"]),
        "APP_ENGINE_DECODEBLOCK": str(preset["decode_block"]),
        "APP_ENGINE_WARMUPPROMPTLENGTHS": preset["warmup"],
        "APP_ENGINE_PAGEDKERNEL": preset["paged_kernel"],
        "GENAI_TPU_TP_KERNELS": preset["tp_kernels"],
        "LOGLEVEL": "INFO",
    }
    if preset["embed_model"]:
        env["APP_EMBEDDINGS_MODELNAME"] = preset["embed_model"]
    return env


def wait_for(url: str, what: str, timeout: float, alive) -> float:
    t0 = time.time()
    while True:
        check(alive(), f"server exited while waiting for {what}")
        try:
            status, _ = http("GET", url, timeout=10)
            if status == 200:
                return time.time() - t0
        except (urllib.error.URLError, OSError):
            pass
        check(time.time() - t0 < timeout, f"{what} not reached in {timeout:.0f} s")
        time.sleep(1.0)


def generate(base: str, question: str, use_kb: bool, max_tokens: int, out: dict) -> None:
    t0 = time.time()
    try:
        status, payload = http(
            "POST", base + "/generate",
            body={
                "messages": [{"role": "user", "content": question}],
                "use_knowledge_base": use_kb,
                "max_tokens": max_tokens,
                "temperature": 0.2,
            },
            timeout=600,
        )
        out.update(status=status, payload=payload, seconds=time.time() - t0)
    except Exception as exc:  # noqa: BLE001 - reported by the caller's checks
        out.update(status=-1, payload=repr(exc).encode(), seconds=time.time() - t0)


def drive_server(base: str, preset: dict, n_kb: int, log_text, max_seq_len: int,
                 metrics_before=None) -> dict:
    """Everything a user does against a ready server, with the checks.
    ``log_text()`` returns the server log so far. Runs in the jax-free
    parent (one chip) or on a client thread of the TP child (four)."""
    from generativeaiexamples_tpu.chains.developer_rag import (
        NO_CONTEXT_MSG, NO_DOCS_MSG,
    )
    from generativeaiexamples_tpu.server.api import (
        GENERIC_ERROR_MSG, VECTOR_STORE_ERROR_MSG,
    )

    canned = {NO_CONTEXT_MSG, NO_DOCS_MSG, GENERIC_ERROR_MSG, VECTOR_STORE_ERROR_MSG}
    max_tokens = preset["max_tokens"]
    doc_name = "chip_smoke_corpus.txt"

    body, headers = multipart(doc_name, corpus().encode())
    t0 = time.time()
    http_ok("POST", base + "/documents", "POST /documents", body=body, headers=headers, timeout=600)
    say(f"ingest: {doc_name} uploaded in {time.time() - t0:.1f} s")
    docs = json.loads(http_ok("GET", base + "/documents", "GET /documents"))
    check(doc_name in docs.get("documents", []), f"{doc_name} not listed after upload: {docs}")

    res = json.loads(http_ok(
        "POST", base + "/search", "POST /search",
        body={"query": f"What is said about {TOPICS[2]}?", "top_k": 4}, timeout=300,
    ))
    chunks = res.get("chunks", [])
    check(len(chunks) >= 2, f"/search returned {len(chunks)} chunks of a multi-chunk document")
    check(all(c.get("filename") == doc_name and c.get("content") for c in chunks),
          "/search returned chunks without content or from another document")
    say(f"search: {len(chunks)} chunks, top score {chunks[0].get('score'):.4f}")

    keep = max_seq_len - 1 - min(64, max_tokens)
    say(
        f"note: max_seq_len={max_seq_len}; a prompt longer than "
        f"{keep} tokens is served by its TAIL (engine submit() clamp); with the "
        "byte-level tokenizer the RAG prompts here are several thousand tokens"
    )
    questions = [
        (f"What does section {i} say about {TOPICS[i]} and parameter {i * 100 + 7}?", True)
        for i in range(n_kb)
    ] + [("Say something about TPUs in one sentence.", False)]
    outs = [dict() for _ in questions]
    threads = [
        threading.Thread(
            target=generate, args=(base, q, kb, max_tokens, o), name=f"smoke-gen-{i}",
        )
        for i, ((q, kb), o) in enumerate(zip(questions, outs))
    ]
    t_first = time.time()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    say(f"generate: {len(questions)} concurrent requests in {time.time() - t_first:.1f} s "
        f"(first answer after {min(o['seconds'] for o in outs):.1f} s)")
    for (q, kb), o in zip(questions, outs):
        tag = f"/generate kb={kb} {q[:40]!r}"
        check(o["status"] == 200, f"{tag}: HTTP {o['status']}: {o['payload'][:200]!r}")
        frames = parse_sse(o["payload"])
        check(frames, f"{tag}: no SSE frames")
        last = frames[-1]
        check(
            last.get("choices") and last["choices"][0].get("finish_reason") == "[DONE]",
            f"{tag}: stream does not end in finish_reason [DONE]: {last}",
        )
        warnings = [w for f in frames for w in (f.get("warnings") or [])]
        check(not warnings, f"{tag}: stream carries warnings {warnings}")
        text = "".join(
            c.get("message", {}).get("content", "")
            for f in frames for c in f.get("choices", [])
        )
        check(text.strip() not in canned, f"{tag}: canned error answer {text.strip()[:80]!r}")
        o["chars"] = len(text)
        o["frames"] = len(frames)

    # Token counts come from the server's own flight recorder, not from
    # the client's reading of the stream: a prefill that could not
    # compile once showed up as silently EMPTY answers with status 200.
    listing = json.loads(http_ok(
        "GET", base + "/internal/requests?since=0&limit=500", "GET /internal/requests",
    ))
    answered = []
    for detail in listing.get("timelines", []):
        events = detail.get("timeline", [])
        if not any(e.get("event") == "http_request" and e.get("path") == "/generate" for e in events):
            continue
        submit = next((e for e in events if e.get("event") == "submit"), None)
        done = next((e for e in events if e.get("event") == "engine_finish"), None)
        rid = detail["request_id"]
        check(submit is not None and done is not None,
              f"request {rid}: no submit/engine_finish in its timeline "
              f"({[e.get('event') for e in events]})")
        generated = int(done.get("generated", 0))
        prompt_tokens = int(submit.get("prompt_tokens", 0))
        check(detail.get("outcome") == "finish" and done.get("outcome") == "finish",
              f"request {rid}: outcome {detail.get('outcome')!r}/{done.get('outcome')!r}")
        check(generated > 0, f"request {rid}: EMPTY answer (0 tokens)")
        ended_by = done.get("stop")
        # Shorter than asked for is right only when the engine says an
        # end-of-sequence token ended the stream.
        check(generated == max_tokens or ended_by == "eos",
              f"request {rid}: {generated}/{max_tokens} tokens, ended by {ended_by!r}")
        answered.append((prompt_tokens, generated, ended_by, detail.get("ttft_s")))
    check(len(answered) == len(questions),
          f"{len(answered)} /generate timelines for {len(questions)} requests")
    for prompt_tokens, generated, ended_by, ttft in answered:
        say(f"answer: prompt_tokens={prompt_tokens}"
            f"{' (tail-clamped)' if prompt_tokens >= keep else ''} "
            f"generated={generated}/{max_tokens} ended_by={ended_by} ttft_s={ttft}")
    check(
        sum(1 for a in answered if a[0] > preset["prefill_chunk"]) >= n_kb,
        "fewer knowledge-base prompts than asked went through chunked prefill "
        f"(prompt tokens: {[a[0] for a in answered]})",
    )

    metrics_text = http_ok("GET", base + "/metrics", "GET /metrics").decode()
    with open(os.path.join(OUT, "metrics.txt"), "w", encoding="utf-8") as fh:
        fh.write(metrics_text)
    stats = check_metrics_text(metrics_text, gather_explained_by_log(log_text()), metrics_before)
    check(stats["generated_tokens_total"] >= sum(a[1] for a in answered),
          "engine token counter is below the timelines' sum")
    say(f"metrics: paged_attn kernel={stats['kernel_dispatches']:g} "
        f"gather={stats['gather_dispatches']:g} "
        f"hot_path_compiles={stats['hot_path_compiles']:g} "
        f"generated_tokens_total={stats['generated_tokens_total']:g}")

    http_ok("DELETE", base + f"/documents?filename={doc_name}", "DELETE /documents", timeout=120)
    docs = json.loads(http_ok("GET", base + "/documents", "GET /documents"))
    check(doc_name not in docs.get("documents", []), f"{doc_name} still listed after delete")
    say("delete: document removed")
    return {"answers": len(answered), **stats}


def phase_server(args, preset: dict) -> dict:
    work = os.path.join(OUT, "work")
    subprocess.run(["rm", "-rf", work], check=False)
    os.makedirs(work, exist_ok=True)
    log_path = os.path.join(OUT, "server.log")
    base = f"http://127.0.0.1:{args.port}"
    env = child_env(server_env(preset, args.port, work, tp=1))
    t_launch = time.time()
    with open(log_path, "w", encoding="utf-8") as log_fh:
        proc = subprocess.Popen(
            [sys.executable, "-m", "generativeaiexamples_tpu.server", "--port", str(args.port)],
            env=env, stdout=log_fh, stderr=subprocess.STDOUT, cwd=ROOT,
        )

        def log_text() -> str:
            with open(log_path, encoding="utf-8", errors="replace") as fh:
                return fh.read()

        try:
            up_s = wait_for(base + "/health", "/health", 180, lambda: proc.poll() is None)
            ready_s = wait_for(base + "/internal/ready", "/internal/ready", READY_TIMEOUT_S,
                               lambda: proc.poll() is None)
            say(f"server: up in {up_s:.1f} s, ready {ready_s:.1f} s later "
                f"({time.time() - t_launch:.1f} s from launch)")
            stats = drive_server(base, preset, 4, log_text, preset["max_seq_len"])
        except SmokeFailure:
            for ln in log_text().splitlines()[-30:]:
                say(f"  [server log] {ln}")
            raise
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=30)
        paths = check_server_log(log_text(), want_compiled=args.preset == "full")
    stats.update(paths, ready_s=ready_s)
    return stats


# --------------------------------------------------------------------------- #
# Phase: tensor parallelism over four chips (child; owns every chip)


def phase_tp(args) -> int:
    preset = PRESETS[args.preset]
    n = 4
    from generativeaiexamples_tpu.utils import jax_env

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    jax_env.bootstrap()
    work = os.path.join(OUT, "work_tp")
    subprocess.run(["rm", "-rf", work], check=False)
    os.makedirs(work, exist_ok=True)
    os.environ.update(server_env(preset, args.port, work, tp=n))

    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = _device_facts()
    emit({"device": device})
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and args.preset == "full":
        print("no TPU: the full preset has nothing to run on", flush=True)
        return 3
    check(device["count"] == n, f"--chips {n} needs {n} devices, jax reports {device['count']}")

    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine import llm_engine
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine, SamplingParams
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.parallel import tp_kernels
    from generativeaiexamples_tpu.parallel.mesh import mesh_context

    def engine_cfg(tp: int) -> EngineConfig:
        return EngineConfig(
            model_config_name=preset["tp_model"], quantization="int8",
            kv_cache_dtype="int8", tensor_parallelism=tp,
            max_batch_size=preset["max_batch"], max_seq_len=preset["tp_max_seq_len"],
            prefill_chunk=preset["prefill_chunk"], page_size=preset["page_size"],
            decode_block=preset["decode_block"], paged_kernel=preset["paged_kernel"],
        )

    T = min(128, preset["prefill_chunk"])
    prompts = [
        [5 + ((7 * i + 13 * j) % 200) for j in range(T - 3 * i)] for i in range(3)
    ]
    greedy = SamplingParams(temperature=0.0, max_tokens=preset["max_tokens"])

    def first_logits(eng):
        """Last-prompt-token logits through the engine's own prefill
        forward, flags as the engine resolved them."""
        cfg = eng.model_config
        tok = np.zeros((len(prompts), T), np.int32)
        for i, p in enumerate(prompts):
            tok[i, : len(p)] = p
        lengths = np.asarray([len(p) for p in prompts], np.int32)
        use_flash = None if (eng._mesh.size == 1 or eng._tp is not None) else False

        def fwd(params, tokens, lens):
            return llama.prefill_layers(
                params, cfg, tokens, lens, use_flash=use_flash,
                quant_kernel=eng._quant_kernel, tp=eng._tp,
                interpret=not on_tpu,
            )[0]

        with mesh_context(eng._mesh):
            return np.asarray(jax.jit(fwd)(eng.params, jnp.asarray(tok), jnp.asarray(lengths)), np.float32)

    def streams(eng):
        return [list(eng.iter_ids(p, greedy, timeout=900)) for p in prompts]

    # -- what it is compared with: tp=1 on one device, then torn down ----
    t0 = time.time()
    ref_eng = LLMEngine(engine_cfg(1))
    print(f"tp=1 reference engine built in {time.time() - t0:.1f} s on "
          f"{[str(d) for d in ref_eng._mesh.devices.reshape(-1)]}; "
          f"quant_kernel={ref_eng._quant_kernel} paged_kernel={ref_eng._paged_kernel}", flush=True)
    ref_logits = first_logits(ref_eng)
    ref_streams = streams(ref_eng)
    print(f"tp=1 reference: {[len(s) for s in ref_streams]} tokens per prompt, "
          f"{ref_eng.device_memory_line()}", flush=True)
    ref_m = ref_eng.metrics
    metrics_before = {
        "kernel": ref_m["paged_attn_kernel_dispatches"],
        "gather": ref_m["paged_attn_gather_dispatches"],
        "tokens": ref_m["generated_tokens"],
    }
    print(f"tp=1 reference counted (subtracted from the server's /metrics): {metrics_before}", flush=True)
    ref_eng.shutdown()
    ref_eng.params = ref_eng._cache = None
    del ref_eng
    jax.clear_caches()
    gc.collect()

    # -- the same server entry, tensor_parallelism=4, in THIS process ----
    result = {}

    failures = []

    def section(name, fn) -> None:
        """One group of checks; a failure is recorded and the next
        group still runs (a four-chip call is too dear to stop at the
        first finding)."""
        try:
            fn()
        except Exception as exc:  # noqa: BLE001 - every failure is reported
            failures.append(name)
            print(f"FAIL [{name}]: {type(exc).__name__}: {exc}", flush=True)

    def serve() -> None:
        base = f"http://127.0.0.1:{args.port}"
        stats = drive_server(
            base, preset, 2, lambda: "", preset["tp_max_seq_len"], metrics_before,
        )
        result.update(stats)

    def placement() -> None:
        eng = llama_engine()
        mesh_shape = dict(eng._mesh.shape)
        check(mesh_shape.get("model") == n, f"engine mesh is {mesh_shape}, not model={n}")
        check(eng._tp is not None, "TP kernel path (shard_map tiles) not engaged")
        print(f"tp={n} engine: mesh={mesh_shape} quant_kernel={eng._quant_kernel} "
              f"paged_kernel={eng._paged_kernel} tp_kernels={eng._tp.shards}-way", flush=True)
        if on_tpu:
            check(eng._paged_kernel == "compiled", f"paged_kernel={eng._paged_kernel}")
        # shards of one weight and of the KV pool on distinct devices
        layer0 = eng.params["layers"][0]
        wname = "wq" if "wq" in layer0 else "wqkv"
        weight, pool = layer0[wname]["q"], eng._cache[0]["k"]
        wdevs = {sh.device for sh in weight.addressable_shards}
        kdevs = {sh.device for sh in pool.addressable_shards}
        print(f"sharding: {wname}.q {weight.shape} -> {len(wdevs)} x "
              f"{weight.addressable_shards[0].data.shape}; kv pool {pool.shape} -> "
              f"{len(kdevs)} x {pool.addressable_shards[0].data.shape}", flush=True)
        check(len(wdevs) == n, f"{wname} shards sit on {len(wdevs)} devices: {wdevs}")
        check(len(kdevs) == n, f"KV pool shards sit on {len(kdevs)} devices: {kdevs}")
        print(eng.device_memory_line(), flush=True)
        if on_tpu:
            in_use = [d.memory_stats()["bytes_in_use"] for d in eng._mesh.devices.reshape(-1)]
            check(max(in_use) <= 1.5 * min(in_use),
                  f"devices do not hold a like share: bytes_in_use={in_use}")

    def collectives() -> None:
        # row-parallel matmul: an all-reduce, around kernel tiles
        eng = llama_engine()
        x = jnp.zeros((8, eng.model_config.q_dim), jnp.bfloat16)
        with mesh_context(eng._mesh):
            text = jax.jit(
                lambda a, p: tp_kernels.packed_matmul_tp(a, p, eng._tp, "row")
            ).lower(x, eng.params["layers"][0]["wo"]).compile().as_text()
        print(f"row-parallel matmul: all-reduce={'all-reduce' in text} "
              f"tpu_custom_call={'tpu_custom_call' in text}", flush=True)
        check("all-reduce" in text, "row-parallel matmul compiled without an all-reduce")
        if on_tpu:
            check("tpu_custom_call" in text, "TP matmul tile is not a Pallas custom call")

    def agreement() -> None:
        # first-token logits and greedy streams against tp=1
        eng = llama_engine()
        logits = first_logits(eng)
        check(np.all(np.isfinite(logits)), "TP logits are not finite")
        err = float(np.max(np.abs(logits - ref_logits)) / max(np.max(np.abs(ref_logits)), 1e-6))
        tol = 0.05
        print(f"first-token logits tp={n} vs tp=1: max|diff|/max|ref|={err:.4g} (tol {tol}); "
              f"argmax equal on {int(np.sum(logits.argmax(-1) == ref_logits.argmax(-1)))}/{len(prompts)} prompts",
              flush=True)
        check(err <= tol, f"first-token logits differ by {err:.4g} > {tol}")
        for i, (a, b) in enumerate(zip(streams(eng), ref_streams)):
            common = next((k for k, (u, v) in enumerate(zip(a, b)) if u != v), min(len(a), len(b)))
            print(f"greedy stream {i}: common prefix {common}/{len(b)} tokens "
                  "(random weights flip near-ties)", flush=True)
            check(len(a) > 0, f"TP stream {i} is empty")

    def client() -> None:
        try:
            base = f"http://127.0.0.1:{args.port}"
            wait_for(base + "/health", "/health", 180, lambda: True)
            ready_s = wait_for(base + "/internal/ready", "/internal/ready",
                               READY_TIMEOUT_S, lambda: True)
            print(f"tp={n} server ready after {ready_s:.1f} s", flush=True)
            for name, fn in (("serve", serve), ("placement", placement),
                             ("collectives", collectives), ("agreement", agreement)):
                section(name, fn)
            result.update(ok=not failures)
        except BaseException as exc:  # noqa: BLE001 - reported, then the server is stopped
            print(f"FAIL: {type(exc).__name__}: {exc}", flush=True)
            result.update(ok=False)
        finally:
            os.kill(os.getpid(), signal.SIGTERM)  # run_app exits gracefully

    def llama_engine():
        eng = llm_engine._ENGINE
        check(eng is not None, "the server never built its engine")
        return eng

    th = threading.Thread(target=client, name="smoke-tp-client")
    th.start()
    from generativeaiexamples_tpu.server import __main__ as server_main

    sys.argv = [sys.argv[0], "--host", "127.0.0.1", "--port", str(args.port)]
    server_main.main()  # returns on SIGTERM
    th.join(timeout=60)
    if llm_engine._ENGINE is not None:
        llm_engine._ENGINE.shutdown()
    emit({"tp_ok": bool(result.get("ok"))})
    return 0 if result.get("ok") else 1


# --------------------------------------------------------------------------- #


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--preset", default="full", choices=sorted(PRESETS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--port", type=int, default=8089)
    ap.add_argument("--phase", default="", choices=("", "kernels", "tp"),
                    help="internal: run one chip-holding phase in this process")
    args = ap.parse_args()
    if args.phase == "kernels":
        return phase_kernels(args)
    if args.phase == "tp":
        try:
            return phase_tp(args)
        except SmokeFailure as exc:
            print(f"FAIL: {exc}", flush=True)
            return 1

    t_start = time.time()
    os.makedirs(OUT, exist_ok=True)
    preset = PRESETS[args.preset]
    device = {"platform": "none", "kind": "none", "count": 0}
    ok = False
    try:
        cache_dir, entries_before = cache_state()
        env_set = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        say(f"chip_smoke: preset={args.preset} chips={args.chips} seed={args.seed}")
        say(f"compile cache: dir={cache_dir} ({'from JAX_COMPILATION_CACHE_DIR' if env_set else 'checkout default'}), "
            f"{entries_before} entries before -> {'warm' if entries_before else 'cold'}")
        if args.chips == 4:
            say(f"model: {preset['tp_model']} tensor_parallelism=4, int8 weights, int8 paged KV; "
                "compared with tensor_parallelism=1 on one device")
            rc, results = run_child("tp", args, "tp.log", timeout=3000)
            for r in results:
                device = r.get("device", device)
            check(rc == 0, f"tensor-parallel phase failed (exit {rc})")
        else:
            say(f"model: {preset['model']} (llama3-8b = 32 layers, 4096 hidden, 14336 MLP, "
                "32/8 heads of 128, vocabulary 128256), int8 weights, int8 paged KV; "
                f"embedder {preset['embed_model'] or 'snowflake/arctic-embed-l (config default)'}; "
                f"slots={preset['max_batch']} max_seq_len={preset['max_seq_len']} "
                f"prefill_chunk={preset['prefill_chunk']} page_size={preset['page_size']}")
            t0 = time.time()
            rc, results = run_child("kernels", args, "kernels.log", timeout=600)
            for r in results:
                device = r.get("device", device)
            say(f"kernels phase: exit {rc} in {time.time() - t0:.1f} s; device {device}")
            check(rc == 0, f"kernel-vs-plain phase failed (exit {rc})")
            stats = phase_server(args, preset)
            _, entries_after = cache_state()
            say(f"server phase: {stats['answers']} answered /generate; "
                f"engine build {stats['engine_build_s']:.1f} s, warm-up/compile {stats['warmup_s']:.1f} s "
                f"({'warm' if entries_before else 'cold'} cache, {entries_before} -> {entries_after} entries)")
            say(f"resolved kernel paths: quant_kernel={stats['quant_kernel']} "
                f"paged_kernel={stats['paged_kernel']} paged_verify_kernel={stats['paged_verify_kernel']} "
                f"kv_scales={stats['kv_scales']} (backend={stats['backend']}, devices={stats['devices']})")
            say(f"peak HBM after warm-up, from memory_stats(): {stats['device_memory']}")
            history = os.path.join(OUT, "history.jsonl")
            prior = []
            if os.path.exists(history):
                with open(history, encoding="utf-8") as fh:
                    prior = [json.loads(ln) for ln in fh if ln.strip()]
            for p in prior[-3:]:
                say(f"earlier run in this directory: {p['cache']} cache, warm-up/compile {p['warmup_s']:.1f} s")
            with open(history, "a", encoding="utf-8") as fh:
                fh.write(json.dumps({
                    "cache": "warm" if entries_before else "cold",
                    "warmup_s": stats["warmup_s"], "engine_build_s": stats["engine_build_s"],
                }) + "\n")
        ok = device.get("platform") == "tpu"
        if not ok:
            say(f"every phase ran, but the platform is {device.get('platform')!r}, not 'tpu': not ok")
    except SmokeFailure as exc:
        say(f"FAIL: {exc}")
    say(f"total {time.time() - t_start:.1f} s")
    say(final_line(ok, device))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
