"""Hardware peak constants + roofline/MFU arithmetic, in ONE place.

The engine's fit planner reads this math. Peaks are
PUBLISHED per-chip numbers in one table keyed by jax's ``device_kind``,
each with its source; the engine resolves the attached device against
it at start-up (:func:`configure_peaks`). On the ``tpu`` backend a kind
that is not in the table is an error, not a default — add the part with
its source, or state the peaks explicitly through the
``BENCH_PEAK_TFLOPS`` / ``BENCH_PEAK_HBM_GBPS`` overrides. A non-TPU
backend (CPU rehearsals, tests) keeps the reference part's numbers so
the arithmetic stays defined; what it yields there is a count, never a
device metric.

Everything here is pure host arithmetic — no jax import, so the
metric-name linter and pure-host tests can load it freely.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_tflops: float
    hbm_gbps: float
    hbm_bytes: float
    source: str


# Keyed by ``jax.devices()[0].device_kind``.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_tflops=197.0,
        hbm_gbps=819.0,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM2e at 819 GB/s per chip",
    ),
}
REFERENCE_KIND = "TPU v5 lite"

PEAK_TFLOPS = float(
    os.environ.get("BENCH_PEAK_TFLOPS", DEVICE_PEAKS[REFERENCE_KIND].bf16_tflops)
)
PEAK_HBM_GBPS = float(
    os.environ.get("BENCH_PEAK_HBM_GBPS", DEVICE_PEAKS[REFERENCE_KIND].hbm_gbps)
)


def peaks_for(platform: str, device_kind: str) -> DevicePeaks:
    """The table row for an attached device. Unknown TPU kinds raise;
    non-TPU platforms get the reference part (see module docstring)."""
    row = DEVICE_PEAKS.get(device_kind)
    if row is not None:
        return row
    if platform == "tpu":
        raise ValueError(
            f"no published peaks for TPU device_kind {device_kind!r} in "
            f"utils/hardware.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); "
            "add the part with its source"
        )
    return DEVICE_PEAKS[REFERENCE_KIND]


def configure_peaks(platform: str, device_kind: str) -> None:
    """Point the module-level peaks at the attached device (the engine
    calls this once at start-up). A peak stated through its env override
    stays as stated; with both stated the table is not consulted, so an
    unlisted part can still be served."""
    global PEAK_TFLOPS, PEAK_HBM_GBPS
    want_flops = "BENCH_PEAK_TFLOPS" not in os.environ
    want_hbm = "BENCH_PEAK_HBM_GBPS" not in os.environ
    if not (want_flops or want_hbm):
        return
    row = peaks_for(platform, device_kind)
    if want_flops:
        PEAK_TFLOPS = row.bf16_tflops
    if want_hbm:
        PEAK_HBM_GBPS = row.hbm_gbps


def device_hbm_bytes(device) -> float:
    """Per-device memory the allocator may use. ``GENAI_TPU_HBM_BYTES``
    overrides (tests / fit-planning for another part). On the ``tpu``
    backend the allocator's own ``bytes_limit`` is the only source and
    its absence is an error; other backends report no limit, so fit
    plans rehearsed there use the reference part's published size."""
    env = os.environ.get("GENAI_TPU_HBM_BYTES")
    if env:
        return float(env)
    if device.platform == "tpu":
        stats = device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{device} reports no memory_stats()['bytes_limit']; refusing "
                "to plan against an assumed HBM size (set GENAI_TPU_HBM_BYTES "
                "to state it)"
            )
        return float(stats["bytes_limit"])
    return DEVICE_PEAKS[REFERENCE_KIND].hbm_bytes


def matmul_params(model_cfg) -> int:
    """Parameters that actually hit the MXU per generated token: every
    logical parameter except the embedding table, which is a per-token
    GATHER at decode, not a matmul — counting it would inflate MFU ~20%
    on the 1B proxy (untied 128k-vocab table ≈ lm_head size)."""
    from generativeaiexamples_tpu.models.llama import count_logical_params

    return count_logical_params(model_cfg) - model_cfg.vocab_size * model_cfg.hidden_size


def mfu_ratio(tokens_per_sec: float, n_matmul_params: int,
              devices: int = 1) -> float:
    """Model FLOPs utilization: a forward pass costs ~2 FLOPs per matmul
    parameter per token (prefill and decode alike), against the mesh's
    aggregate peak."""
    peak = PEAK_TFLOPS * 1e12 * max(1, devices)
    return tokens_per_sec * 2.0 * n_matmul_params / peak


def hbm_ratio(bytes_per_sec: float, devices: int = 1) -> float:
    """Achieved HBM bandwidth as a fraction of the mesh's aggregate
    roofline."""
    peak = PEAK_HBM_GBPS * 1e9 * max(1, devices)
    return bytes_per_sec / peak


# Bytes each stored KV element occupies in the cache, by configured
# dtype. int4 packs two elements per byte (split-halves codec in
# models/llama.py), so the honest per-element width is fractional —
# every roofline/fit-plan consumer shares this ONE table instead of
# re-hardcoding "int8 means 1".
_KV_BYTES_PER_ELEMENT = {"bfloat16": 2.0, "int8": 1.0, "int4": 0.5}


def kv_bytes_per_element(kv_cache_dtype: str) -> float:
    """Per-element KV cache width in bytes for a configured dtype
    string. Raises on unknown dtypes so accounting can never silently
    default to the wrong width."""
    try:
        return _KV_BYTES_PER_ELEMENT[kv_cache_dtype]
    except KeyError:
        raise ValueError(
            f"unknown kv_cache_dtype {kv_cache_dtype!r}; expected one of "
            f"{sorted(_KV_BYTES_PER_ELEMENT)}"
        ) from None


def kv_read_bytes_per_step(model_cfg, batch: int, window: int,
                           kv_bytes: float) -> int:
    """Attention cache traffic for ONE decode step over the whole batch:
    every step reads ``window`` rows of K and V per layer per slot.
    Comparable to — and for small models larger than — weight
    streaming. ``kv_bytes`` is per-element and may be fractional
    (int4 = 0.5, see :func:`kv_bytes_per_element`)."""
    return int(
        2 * batch * window * model_cfg.num_kv_heads * model_cfg.head_dim
        * kv_bytes * model_cfg.num_layers
    )


def kv_read_bytes_ragged(model_cfg, live_tokens: int, kv_bytes: float) -> int:
    """Attention cache traffic for ONE ragged decode step: only each
    row's live (page-rounded) K and V rows, summed over the batch as
    ``live_tokens`` — the paged layout's replacement for the
    batch x padded-window product above. This is what the paged engine
    feeds the utilization estimator, so the roofline gauges charge the
    bytes the ragged kernel actually reads instead of phantom
    padded-window traffic. The kernel's TIME follows the same sum: it
    walks the live pages alone (ops/page_attention.page_work_list), one
    or two of a row a grid step, not a dense slots x max_pages grid, so
    bytes and steps move together. The kernel fetches exactly these
    pages: a dead place of a row's last group names the page its place
    already holds, which moves nothing. An int8 pool's scales are in
    ``kv_bytes`` at their logical 4 bytes a (token, head); the kernel's
    scale DMAs move 16 times that (a ``[page, 8]`` float32 block pads to
    128 lanes), about half again of a page's int8 bytes: the pool's
    layout, PERF.md §7."""
    # exactly the per-step formula at batch=1 x live_tokens "window" —
    # one expression, so the fixed and paged accounting cannot drift
    return kv_read_bytes_per_step(model_cfg, 1, live_tokens, kv_bytes)


def streamed_weight_bytes(params) -> int:
    """Bytes the decode step streams from HBM for weights each step:
    every param leaf except the embedding table (gathered rows only).
    When no top-level ``embed`` leaf exists the total is returned."""
    import jax

    tree = params
    if isinstance(params, dict) and "embed" in params:
        tree = dict(params)
        tree.pop("embed", None)
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))
