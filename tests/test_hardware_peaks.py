"""Device peaks are a table keyed by device_kind; a TPU that is not in
it is an error, and HBM limits are not assumed on the tpu backend."""
import pytest

from generativeaiexamples_tpu.utils import hardware


class _Dev:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_v5e_row_carries_the_published_peaks_and_their_source():
    row = hardware.peaks_for("tpu", "TPU v5 lite")
    assert (row.bf16_tflops, row.hbm_gbps) == (197.0, 819.0)
    assert "Google Cloud" in row.source


def test_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        hardware.peaks_for("tpu", "TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        hardware.configure_peaks("tpu", "TPU v99")


def test_cpu_rehearsal_keeps_the_reference_part():
    assert hardware.peaks_for("cpu", "cpu") is hardware.DEVICE_PEAKS[hardware.REFERENCE_KIND]
    before = (hardware.PEAK_TFLOPS, hardware.PEAK_HBM_GBPS)
    hardware.configure_peaks("cpu", "cpu")
    assert (hardware.PEAK_TFLOPS, hardware.PEAK_HBM_GBPS) == before


def test_explicit_overrides_state_the_peaks_for_an_unlisted_part(monkeypatch):
    monkeypatch.setenv("BENCH_PEAK_TFLOPS", "1")
    monkeypatch.setenv("BENCH_PEAK_HBM_GBPS", "1")
    hardware.configure_peaks("tpu", "TPU v99")  # does not raise


def test_tpu_hbm_limit_comes_from_the_allocator_or_fails(monkeypatch):
    monkeypatch.delenv("GENAI_TPU_HBM_BYTES", raising=False)
    assert hardware.device_hbm_bytes(_Dev("tpu", {"bytes_limit": 123})) == 123.0
    for stats in (None, {}, {"bytes_in_use": 1}):
        with pytest.raises(RuntimeError, match="bytes_limit"):
            hardware.device_hbm_bytes(_Dev("tpu", stats))
    # other backends report no limit: fit plans rehearse against the
    # reference part's published size
    assert hardware.device_hbm_bytes(_Dev("cpu", None)) == 16e9
    monkeypatch.setenv("GENAI_TPU_HBM_BYTES", "5e9")
    assert hardware.device_hbm_bytes(_Dev("tpu", None)) == 5e9


def test_devices_scale_peaks():
    one = hardware.mfu_ratio(1000.0, 10**9, devices=1)
    eight = hardware.mfu_ratio(1000.0, 10**9, devices=8)
    assert abs(one / eight - 8.0) < 1e-6
    # the kv-read formula matches bench's inline version
    class _Cfg:
        num_kv_heads, head_dim, num_layers = 4, 64, 8

    assert hardware.kv_read_bytes_per_step(_Cfg, 16, 256, 2) == (
        2 * 16 * 256 * 4 * 64 * 2 * 8
    )
