"""chip_smoke.py's own logic, without a chip: the parent stays off jax,
the last-line contract, and the checks it makes on recorded text."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


METRICS_KERNEL = textwrap.dedent(
    """\
    # HELP genai_engine_paged_attn_dispatches_total Paged dispatches by path.
    # TYPE genai_engine_paged_attn_dispatches_total counter
    genai_engine_paged_attn_dispatches_total{path="kernel"} 37
    genai_engine_generated_tokens_total 160
    genai_engine_compile_seconds_count{program="decode"} 1
    """
)
# APP_ENGINE_PAGEDKERNEL=off: every dispatch is charged to the gather.
METRICS_GATHER = textwrap.dedent(
    """\
    genai_engine_paged_attn_dispatches_total{path="gather"} 37
    genai_engine_generated_tokens_total 160
    """
)
METRICS_HOT = METRICS_KERNEL + 'genai_engine_hot_path_compiles_total{program="extend"} 1\n'

LOG_OK = textwrap.dedent(
    """\
    2026-09-26 INFO x: ragged page-attention kernel serving paged decode (compiled, page_size=128)
    2026-09-26 INFO x: resolved kernel paths: quant_kernel=True paged_kernel=compiled paged_verify_kernel=compiled tp_kernels=None kv_scales=lane_dense (backend=tpu, devices=1)
    2026-09-26 INFO x: Engine warmup complete (engine build 61.5 s, warmup 244.0 s; device memory: dev0 in_use=9.90GB peak=11.20GB limit=16.91GB)
    """
)


def test_kernel_served_metrics_pass(smoke):
    stats = smoke.check_metrics_text(METRICS_KERNEL)
    assert stats["kernel_dispatches"] == 37
    assert stats["gather_dispatches"] == 0
    assert stats["hot_path_compiles"] == 0


def test_gather_served_engine_fails_the_metrics_check(smoke):
    with pytest.raises(smoke.SmokeFailure, match="kernel did not serve"):
        smoke.check_metrics_text(METRICS_GATHER)


def test_unexplained_gather_dispatches_fail(smoke):
    mixed = METRICS_KERNEL + 'genai_engine_paged_attn_dispatches_total{path="gather"} 2\n'
    with pytest.raises(smoke.SmokeFailure, match="gather"):
        smoke.check_metrics_text(mixed)
    assert smoke.check_metrics_text(mixed, gather_explained=True)["gather_dispatches"] == 2


def test_hot_path_compile_fails_the_metrics_check(smoke):
    with pytest.raises(smoke.SmokeFailure, match="hot_path_compiles"):
        smoke.check_metrics_text(METRICS_HOT)


def test_server_log_check_reads_resolved_paths(smoke):
    paths = smoke.check_server_log(LOG_OK, want_compiled=True)
    assert paths["paged_kernel"] == "compiled" and paths["kv_scales"] == "lane_dense"
    assert paths["engine_build_s"] == 61.5 and paths["warmup_s"] == 244.0
    assert "peak=11.20GB" in paths["device_memory"]


@pytest.mark.parametrize(
    "mutate,match",
    [
        (lambda s: s.replace("paged_kernel=compiled", "paged_kernel=None"), "not compiled"),
        (lambda s: s.replace("quant_kernel=True", "quant_kernel=False"), "int8 matmul"),
        (lambda s: s.replace("backend=tpu", "backend=cpu"), "not tpu"),
        (lambda s: s.replace("kv_scales=lane_dense", "kv_scales=token_major"), "scale planes"),
        (lambda s: s.replace("kv_scales=lane_dense ", ""), "scale planes"),  # a program from before the layout had a name
        (lambda s: s + "Traceback (most recent call last):\n", "traceback"),
        (lambda s: s + "WARNING ragged page-attention kernel REFUSED this geometry\n", "REFUSED"),
        (lambda s: s + "ERROR COMPILE ON HOT PATH: extend\n", "hot-path"),
    ],
    ids=["gather-resolved", "xla-matmul", "cpu-backend", "token-major-scales", "unnamed-scales", "traceback", "refused",
         "hot-compile"],
)
def test_server_log_check_fails_on(smoke, mutate, match):
    with pytest.raises(smoke.SmokeFailure, match=match):
        smoke.check_server_log(mutate(LOG_OK), want_compiled=True)


def test_last_line_format(smoke):
    line = smoke.final_line(True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert line == '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}'
    assert json.loads(smoke.final_line(False, {"platform": "cpu", "kind": "cpu", "count": 1}))["ok"] is False


# The parent launches chip-holding children; a parent that has touched
# jax holds the chip itself. Run main() in a fresh interpreter with the
# child launches stubbed and look at sys.modules.
_PARENT_PROBE = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {script!r})
m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)
device = {device!r}
m.run_child = lambda phase, args, log, timeout, extra_env=None: (0, [{{"device": device}}])
m.phase_server = lambda args, preset: dict(
    answers=5, engine_build_s=1.0, warmup_s=2.0, quant_kernel="True",
    paged_kernel="compiled", paged_verify_kernel="compiled", kv_scales="lane_dense",
    backend=device["platform"], devices=1, device_memory="device memory: stub",
)
m.OUT = {out!r}
sys.argv = ["chip_smoke.py"] + {argv!r}
rc = m.main()
print("JAX_IMPORTED=" + str(any(k == "jax" or k.startswith("jax.") for k in sys.modules)))
print("RC=" + str(rc))
"""


def _run_parent(tmp_path, device, argv=()):
    code = _PARENT_PROBE.format(
        script=SCRIPT, device=device, out=str(tmp_path / "out"), argv=list(argv)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    return lines


@pytest.mark.parametrize("argv", [(), ("--chips", "4")], ids=["one-chip", "four-chips"])
def test_parent_never_imports_jax(tmp_path, argv):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4 if argv else 1}
    lines = _run_parent(tmp_path, device, argv)
    assert "JAX_IMPORTED=False" in lines
    assert "RC=0" in lines
    # the JSON object is the LAST line main() printed, and carries only
    # ok + device
    last = json.loads(lines[-3])
    assert last == {"ok": True, "device": device}


def test_cpu_platform_is_not_ok(tmp_path):
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    lines = _run_parent(tmp_path, device, ("--preset", "debug"))
    assert "RC=1" in lines
    assert json.loads(lines[-3]) == {"ok": False, "device": device}
    assert not any('"ok": true' in ln for ln in lines)


def test_sandbox_run_without_arguments_fails_fast(tmp_path):
    """What the driver does first: no arguments, no accelerator — a
    non-zero exit, ``"ok": false``, and no server ever started."""
    proc = subprocess.run(
        [sys.executable, SCRIPT], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "server:" not in proc.stdout


@pytest.mark.parametrize(
    "module",
    ["tools.loadgen.runner", "tools.loadgen.fleet", "tools.loadgen.chaos",
     "generativeaiexamples_tpu.router.__main__"],
)
def test_server_launching_parents_import_without_jax(module):
    """The loadgen launchers start the server as
    a child: importing them must not touch jax (one process per chip)."""
    code = (
        "import importlib, sys; importlib.import_module(%r); "
        "sys.exit(1 if any(k == 'jax' or k.startswith('jax.') for k in sys.modules) else 0)"
        % module
    )
    env = {k: v for k, v in os.environ.items() if k != "BENCH_FORCE_CPU"}
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
