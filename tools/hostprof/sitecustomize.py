"""Host profiler of the token's way from the reader to the socket
(PERF.md section 5, PR 30): not part of the program. Put this directory
on PYTHONPATH and set HOSTPROF_OUT=<file.json>, then run a benchmark
cell (``PYTHONPATH=tools/hostprof HOSTPROF_OUT=chiprun_out/hostprof/x.json
python3 perfbench/run.py --workload ... --trace 0``); reduce the file
with ``python3 tools/hostprof/reduce.py <file.json>``. It slows what it
measures (PERF.md has by how much). In the benchmark's server
child (argv names launcher.py) a thread waits until the reference
comparison is done and ~60 SSE streams are live (so the sampled stretch
lies in the window), then for HOSTPROF_SECONDS s: (1) reads per-thread
CPU time from /proc, (2) times named functions with thread CPU time
(wrappers, switched on only for the stretch), (3) samples
sys._current_frames() at 50 Hz."""
import os
import sys

if os.environ.get("HOSTPROF_OUT") and any("launcher.py" in a for a in sys.argv):
    import collections
    import functools
    import json
    import threading
    import time

    ON = [False]
    CALLS = collections.defaultdict(lambda: [0, 0.0])  # name -> [calls, thread cpu s]

    def _timed(name, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not ON[0]:
                return fn(*a, **kw)
            t0 = time.thread_time()
            try:
                return fn(*a, **kw)
            finally:
                rec = CALLS[name]
                rec[0] += 1
                rec[1] += time.thread_time() - t0
        return wrapper

    def _timed_async(name, fn):
        @functools.wraps(fn)
        async def wrapper(*a, **kw):
            if not ON[0]:
                return await fn(*a, **kw)
            t0 = time.thread_time()
            try:
                return await fn(*a, **kw)
            finally:
                rec = CALLS[name]
                rec[0] += 1
                rec[1] += time.thread_time() - t0
        return wrapper

    def _patch():
        import asyncio.base_events
        import queue

        from aiohttp import web

        from generativeaiexamples_tpu.engine import llm_engine, tokenizer
        from generativeaiexamples_tpu.server import api
        from generativeaiexamples_tpu.utils import tracing

        if hasattr(api, "_chunk_frame"):  # the program before PR 30
            api._chunk_frame = _timed("_chunk_frame", api._chunk_frame)
        if hasattr(api, "_chunk_frames"):
            make = api._chunk_frames
            api._chunk_frames = lambda rid: _timed("frames(chunk)", make(rid))
        tokenizer.HFTokenizer.decode = _timed("tokenizer.decode", tokenizer.HFTokenizer.decode)
        llm_engine.LLMEngine._emit = _timed("_emit", llm_engine.LLMEngine._emit)
        web.StreamResponse.write = _timed_async("resp.write", web.StreamResponse.write)
        asyncio.base_events.BaseEventLoop.run_in_executor = _timed(
            "run_in_executor (submit side)", asyncio.base_events.BaseEventLoop.run_in_executor)
        asyncio.base_events.BaseEventLoop.call_soon_threadsafe = _timed(
            "call_soon_threadsafe", asyncio.base_events.BaseEventLoop.call_soon_threadsafe)
        queue.Queue.put = _timed("queue.Queue.put", queue.Queue.put)
        queue.Queue.get = _timed("queue.Queue.get", queue.Queue.get)
        if hasattr(llm_engine, "_TokenQueue"):
            tq = llm_engine._TokenQueue
            tq.put_many = _timed("_TokenQueue.put_many", tq.put_many)
            tq.take_all = _timed("_TokenQueue.take_all", tq.take_all)
        for cls_name in ("Span", "_NoopSpan"):
            cls = getattr(tracing, cls_name, None)
            if cls is not None and hasattr(cls, "add_event"):
                cls.add_event = _timed(f"{cls_name}.add_event", cls.add_event)
        if hasattr(llm_engine.LLMEngine, "_stream_from"):
            pass  # a generator: its cost is the sse-producer thread's remainder

    def _cpu_by_thread():
        out = {}
        for t in threading.enumerate():
            tid = t.native_id
            try:
                with open(f"/proc/self/task/{tid}/stat") as fh:
                    parts = fh.read().rsplit(")", 1)[1].split()
                out[(tid, t.name)] = (int(parts[11]) + int(parts[12])) / os.sysconf("SC_CLK_TCK")
            except OSError:
                pass
        return out

    def _cls(name):
        for p in ("sse-producer", "llm-reader", "llm-decode", "MainThread", "asyncio_", "ThreadPoolExecutor"):
            if name.startswith(p):
                return p
        return name.split("-")[0].split("_")[0]

    def _run():
        out = os.environ["HOSTPROF_OUT"]
        delay = float(os.environ.get("HOSTPROF_DELAY", "8"))
        seconds = float(os.environ.get("HOSTPROF_SECONDS", "20"))
        hz = float(os.environ.get("HOSTPROF_HZ", "50"))
        work = sys.argv[sys.argv.index("--work") + 1] if "--work" in sys.argv else None
        while "generativeaiexamples_tpu.server.api" not in sys.modules or \
                "generativeaiexamples_tpu.engine.llm_engine" not in sys.modules:
            time.sleep(0.5)
        time.sleep(2.0)
        patched = True
        try:
            _patch()
        except Exception as exc:  # noqa: BLE001
            patched = repr(exc)
        need = int(os.environ.get("HOSTPROF_MIN_STREAMS", "56"))
        while sum(t.name.startswith("sse-producer") for t in threading.enumerate()) < need or (
                work and os.environ.get("HOSTPROF_WAIT_REFERENCE", "1") == "1"
                and not os.path.exists(os.path.join(work, "reference.json"))):
            time.sleep(1.0)
        time.sleep(delay)
        from generativeaiexamples_tpu.engine import llm_engine
        me = threading.get_ident()
        stacks = collections.Counter()
        cpu0 = _cpu_by_thread()
        seen = dict(cpu0)
        proc0 = time.process_time()
        tokens0 = llm_engine._M_TOKENS.value
        ON[0] = True
        t0 = time.time()
        n = 0
        nthreads = []
        while time.time() - t0 < seconds:
            tick = time.time()
            names = {t.ident: t.name for t in threading.enumerate()}
            frames = sys._current_frames()
            nthreads.append(len(frames))
            for ident, f in frames.items():
                if ident == me:
                    continue
                st = []
                d = 0
                while f is not None and d < 10:
                    co = f.f_code
                    st.append(f"{os.path.basename(os.path.dirname(co.co_filename))}/{os.path.basename(co.co_filename)}:{co.co_name}:{f.f_lineno}")
                    f = f.f_back
                    d += 1
                stacks[(_cls(names.get(ident, "?")), tuple(st))] += 1
            del frames
            n += 1
            if n % int(hz) == 0:
                seen.update(_cpu_by_thread())  # threads that end inside the stretch keep their CPU
            rest = 1.0 / hz - (time.time() - tick)
            if rest > 0:
                time.sleep(rest)
        ON[0] = False
        wall = time.time() - t0
        seen.update(_cpu_by_thread())
        tokens1 = llm_engine._M_TOKENS.value
        by_cls = collections.Counter()
        for key, v in seen.items():
            by_cls[_cls(key[1])] += v - cpu0.get(key, 0.0)
        handoffs = {}
        for name in ("_M_HANDOFFS", "_M_HANDOFF_TOKENS"):
            m = getattr(llm_engine, name, None)
            if m is not None:
                handoffs[name] = m.value
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as fh:
            json.dump({
                "samples": n, "wall_s": wall, "process_cpu_s": time.process_time() - proc0,
                "threads_mean": sum(nthreads) / max(1, len(nthreads)),
                "cpu_s_by_thread_class": dict(by_cls),
                "tokens": tokens1 - tokens0, "patched": patched,
                "calls": {k: v for k, v in CALLS.items()}, "handoffs_total": handoffs,
                "stacks": [[cls, list(st), c] for (cls, st), c in stacks.most_common()],
            }, fh)

    threading.Thread(target=_run, daemon=True, name="hostprof").start()
