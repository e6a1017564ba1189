"""The command itself: last-line format, and what a run without a TPU
does. These start the real server child on the CPU at the tiny
rehearsal configuration."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
REHEARSAL = os.path.join(ROOT, "perfbench", "rehearsal", "manifest.json")


def run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, RUN, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def rehearsal_runs():
    out = {}
    for trace in (0, 1):
        out[trace] = run("--manifest", REHEARSAL, "--workload", "rehearsal_closed",
                         "--seed", "2147483777", "--seconds", "4", "--trace", str(trace))
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_run_ends_correct_false_non_zero_with_no_number_under_a_metric_name(rehearsal_runs, trace):
    proc = rehearsal_runs[trace]
    assert proc.returncode == 1, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["metrics"] == {}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert "memory_peak_bytes" in line["device"]
    # the only fault of a rehearsal is the platform
    faults = [ln for ln in proc.stdout.splitlines() if ln.startswith("not correct:")]
    assert faults == ["not correct: platform is 'cpu', not 'tpu'"], faults
    assert '"ok": true' in next(ln for ln in proc.stdout.splitlines() if ln.startswith("reference:"))


def test_an_untraced_run_measures_for_seconds_and_a_traced_one_for_the_traffic_files_window(rehearsal_runs):
    seconds = {}
    for trace, proc in rehearsal_runs.items():
        line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("window: "))
        seconds[trace] = json.loads(line[len("window: "):])["seconds"]
    assert 3.9 < seconds[0] < 4.6   # --seconds 4, whatever rehearsal_closed.json says of traced runs
    assert 1.9 < seconds[1] < 2.6   # traced_run_window_s 2


def test_frame_log_is_written_with_every_arrival(rehearsal_runs):
    assert rehearsal_runs[0].returncode == 1
    import glob

    runs = glob.glob(os.path.join(ROOT, "chiprun_out", "perfbench", "rehearsal_closed-seed2147483777-trace0*"))
    path = os.path.join(max(runs, key=os.path.getmtime), "frames.jsonl")
    with open(path, encoding="utf-8") as fh:
        head, *reqs = [json.loads(ln) for ln in fh]
    assert head["window_s"][0] == 0.0 and head["setup_s"] > 0
    assert reqs and all({"send_s", "frames_s", "status", "max_tokens"} <= set(r) for r in reqs)
    done = [r for r in reqs if r["status"] == "ok"]
    assert done and all(len(r["frames_s"]) == r["max_tokens"] for r in done)  # one frame per token


def test_real_manifest_without_a_tpu_prints_no_result_and_fails():
    proc = run("--workload", "chat_decode_7b", "--seed", "1", "--seconds", "1", "--trace", "0", timeout=300)
    assert proc.returncode not in (0, None)
    assert not any(ln.startswith('{"correct"') for ln in proc.stdout.splitlines())


def test_final_line_shape_of_a_chip_run():
    """The last line a chip run printed (kept verbatim from PR 28's second
    call, seed 1123581321) has the keys the driver reads."""
    line = json.loads(
        '{"correct": true, "attempted": 160, "failed": 0, "metrics": {"out_tok_s": {"value": 1202.2140676700647, '
        '"unit": "tokens/s"}, "itl_p995_ms": {"value": 458.63495000000177, "unit": "ms"}, "setup_s": {"value": '
        '226.0498538017273, "unit": "s"}}, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, '
        '"memory_peak_bytes": 13325876224}}'
    )
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        m = json.load(fh)
    want = {e["name"] for e in m["end_to_end"] if "chat_decode_7b" in e.get("workloads", ["chat_decode_7b"])}
    assert set(line["metrics"]) == want
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
