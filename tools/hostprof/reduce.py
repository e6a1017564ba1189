"""Reduce a file written by tools/hostprof/sitecustomize.py: CPU by
thread class a token, the timed functions, and the sampled stacks by
category (PERF.md section 5, PR 30)."""
import json, sys, collections

BLOCKED = (
    ("threading.py", "wait"), ("selectors.py", "select"), ("threading.py", "acquire"),
    ("threading.py", "_wait_for_tstate_lock"), ("threading.py", "join"),
)

def is_blocked(stack):
    top = stack[0]
    f, fn, ln = top.rsplit(":", 2)
    base = f.split("/")[-1]
    if (base, fn) in BLOCKED:
        return True
    if base == "thread.py" and fn == "_worker":  # work_queue.get(block=True)
        return True
    return False

CATS = [
    ("bleach/html5lib (Message validator)", lambda s: any("bleach" in x or "html5lib" in x for x in s)),
    ("_chunk_frame: pydantic build+dump", lambda s: any(":_chunk_frame:" in x or ":_sse_frame:" in x or "pydantic" in x for x in s)),
    ("tokenizer.decode", lambda s: any("tokenizer.py:decode:" in x for x in s)),
    ("span.add_event", lambda s: any(":add_event:" in x for x in s)),
    ("resp.write / aiohttp writer", lambda s: any("web_response.py:write" in x or "http_writer.py" in x or "selector_events.py:write" in x for x in s)),
    ("run_in_executor / futures hop", lambda s: any(":run_in_executor:" in x or "futures/thread.py" in x or "futures/_base.py" in x or "asyncio/futures.py" in x for x in s)),
    ("_emit: histograms/slo/flight", lambda s: any(":_emit:" in x for x in s) and any("metrics.py" in x or "slo.py" in x or "flight_recorder.py" in x for x in s)),
    ("_emit: rest (incl. queue put)", lambda s: any(":_emit:" in x for x in s)),
    ("_reader_loop rest (np.asarray wait incl.)", lambda s: any(":_reader_loop:" in x or ":_emit_slab:" in x for x in s)),
    ("queue get/put (not blocked)", lambda s: s[0].split(":")[0].endswith("queue.py") or (len(s) > 1 and s[1].split(":")[0].endswith("queue.py"))),
    ("_stream_from rest (stop search, concat)", lambda s: any(":_stream_from:" in x or ":_next_stream_item" in x or ":cut:" in x or "tokenizer.py:" in x for x in s)),
    ("_aiter_threaded / _produce / _put", lambda s: any(":_aiter_threaded:" in x or ":_produce:" in x or ":_put:" in x for x in s)),
    ("/generate handler rest", lambda s: any(":_generate_admitted:" in x or ":generate_answer:" in x or ":frames:" in x for x in s)),
    ("asyncio loop internals", lambda s: any("asyncio/" in x for x in s)),
    ("aiohttp other", lambda s: any("aiohttp/" in x for x in s)),
    ("engine dispatch thread", lambda s: any(":_decode_once:" in x or ":_loop:" in x or "_dispatch" in x for x in s)),
]

def main(path):
    d = json.load(open(path))
    n = d["samples"]
    print(f"samples {n} wall {d['wall_s']:.1f}s process_cpu {d['process_cpu_s']:.2f}s threads {d['threads_mean']:.0f} tokens {d.get('tokens')}")
    if d.get("tokens"):
        print(f"  tokens/s in the sampled stretch {d['tokens']/d['wall_s']:.0f}; process CPU per token {1e3*d['process_cpu_s']/d['tokens']:.3f} ms")
    print("CPU seconds by thread class (from /proc):")
    for k, v in sorted(d["cpu_s_by_thread_class"].items(), key=lambda kv: -kv[1]):
        if v > 0.005:
            print(f"  {k:22s} {v:7.2f}s  {100*v/d['wall_s']:5.1f}% of a core" + (f"  {1e6*v/d['tokens']:.0f} us/token" if d.get("tokens") else ""))
    print("named functions (thread CPU inside the call, nested calls included):")
    for k, (calls, cpu) in sorted(d["calls"].items(), key=lambda kv: -kv[1][1]):
        print(f"  {k:32s} {calls / max(1, d['tokens']):5.2f} calls/token  {1e6 * cpu / max(calls, 1):7.1f} us/call  "
              f"{1e6 * cpu / max(1, d['tokens']):7.1f} us/token")
    print("tokens a hand-off:", d.get("handoffs_total"))
    run = collections.Counter(); blocked = collections.Counter(); by_thread = collections.Counter()
    other = collections.Counter()
    for cls, st, c in d["stacks"]:
        if is_blocked(st):
            blocked[cls] += c
            continue
        by_thread[cls] += c
        for name, pred in CATS:
            if pred(st):
                run[name] += c
                break
        else:
            run["other"] += c
            other[(cls, st[0], st[1] if len(st) > 1 else "")] += c
    tot = sum(run.values())
    print(f"runnable (holding or wanting the GIL) thread-samples: {tot} = {tot/n:.2f} threads a sample; blocked: {sum(blocked.values())/n:.1f} threads a sample")
    for k, v in run.most_common():
        print(f"  {100*v/tot:5.1f}%  {k}")
    print("runnable by thread class:", {k: round(100*v/tot, 1) for k, v in by_thread.most_common()})
    print("top 'other':")
    for k, v in other.most_common(12):
        print("   ", v, k)

main(sys.argv[1])
