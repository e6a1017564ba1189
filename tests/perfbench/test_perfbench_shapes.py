"""The Mistral adapter's counts of bytes and operations against hand counts for Mistral-7B-v0.3."""
import json
import os

import pytest

from perfbench import readers
from perfbench.arch import mistral as shapes

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "perfbench")
with open(os.path.join(BENCH, "configs", "mistral-7b-v0.3-int8.json"), encoding="utf-8") as fh:
    CFG = json.load(fh)


def test_layer_elements_by_hand():
    # Q|K|V 4096 x (4096 + 2 x 1024), O 4096 x 4096, gate|up 4096 x 28672, down 14336 x 4096
    assert shapes.layer_weight_elements(CFG) == 25_165_824 + 16_777_216 + 117_440_512 + 58_720_256 == 218_103_808


def test_decode_weight_bytes_by_hand():
    matrices = 32 * 218_103_808 + 4096 * 32768                       # int8: one byte each
    scales = 4 * (32 * (6144 + 4096 + 28672 + 4096) + 32768)        # float32 per output channel
    norms = 2 * (2 * 32 * 4096 + 4096)                               # bf16 vectors
    assert shapes.decode_weight_bytes(CFG) == matrices + scales + norms == 7_119_708_160


def test_kv_bytes_per_token_by_hand():
    assert shapes.kv_bytes_per_token(CFG) == 32 * 2 * 8 * 128 + 32 * 2 * 8 * 4 == 67_584
    assert CFG["engine"]["kv_bytes_per_token"] == 67_584
    pool = CFG["engine"]["kv_pool_pages"] * CFG["engine"]["page_size"] * 67_584
    assert pool == 4_982_833_152  # the 4.98 GB of the configuration's `assumed`


def test_decode_step_bytes_and_roofline_share():
    rows, live = 64, 64 * 450
    total = shapes.decode_step_bytes(CFG, rows, live)
    assert total == 7_119_708_160 + live * 67_584 + rows * (2 * 4096 + 67_584)
    flops = shapes.decode_step_flops(CFG, rows, live)
    assert flops == 2.0 * (rows * (32 * 218_103_808 + 4096 * 32768) + 2 * 32 * 32 * 128 * live)
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    ctx = {
        "config": CFG, "adapter": shapes, "peaks": peaks, "read": lambda name: 38.0,
        "spans": [{"kind": "decode", "category": "dispatch", "rows": rows}],
        "flight": [{"timeline": [{"event": "submit", "t_s": 0, "prompt_tokens": 258},
                                 {"event": "engine_finish", "t_s": 1, "generated": 384}]}],
    }
    share = readers.READERS["decode_roofline_share"](ctx, {"time_metric": "decode_step_dev_ms"})
    # bytes bound it (11.1 ms against 2.4 ms of int8 operations)
    assert share == pytest.approx(100 * (total / 819e9) / 0.038)
    assert total / 819e9 > flops / 393e12
    assert 25 < share < 35
