"""Random-init int8 packs describe ONE model whatever the TP width."""
import numpy as np

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import quant


def _dense(pack, tp_shards, kind, k_features=None):
    return np.asarray(
        quant.dequantize_int8(
            pack, tp_shards=tp_shards, kind=kind, k_features=k_features
        ),
        np.float32,
    )


def test_random_init_is_the_same_model_at_tp1_and_tp4():
    cfg = llama.PRESETS["kernel-8dev"]
    p1 = quant.init_packed_params_int8(cfg, seed=3, tp_shards=1)["layers"]
    p4 = quant.init_packed_params_int8(cfg, seed=3, tp_shards=4)["layers"]
    qkv = np.concatenate(
        [_dense(p4[n], 4, quant.PACK_KINDS[n]) for n in ("wq", "wk", "wv")], axis=-1
    )
    np.testing.assert_array_equal(_dense(p1["wqkv"], 1, "column"), qkv)
    gateup = np.concatenate(
        [_dense(p4[n], 4, "column") for n in ("w_gate", "w_up")], axis=-1
    )
    np.testing.assert_array_equal(_dense(p1["w_gateup"], 1, "column"), gateup)
    for name, k in (("wo", cfg.q_dim), ("w_down", cfg.intermediate_size)):
        np.testing.assert_array_equal(
            _dense(p1[name], 1, "row", k), _dense(p4[name], 4, "row", k)
        )


def test_random_init_draws_one_int16_stream_per_named_weight():
    """The packs hold the draws of ONE generator, one call per named
    weight in the unfused order (the model a seed names must not move
    when the packer changes how it copies)."""
    cfg = llama.PRESETS["kernel-8dev"]
    spec = llama.init_spec(cfg)
    layers = quant.init_packed_params_int8(cfg, seed=3, tp_shards=1)["layers"]
    rng = np.random.default_rng(3)
    for pack, names in (
        ("wqkv", ("wq", "wk", "wv")), ("w_gateup", ("w_gate", "w_up")),
        ("wo", ("wo",)), ("w_down", ("w_down",)),
    ):
        want = np.concatenate(
            [rng.integers(-127, 128, size=spec[n][0], dtype=np.int16) for n in names], axis=-1
        )
        q = np.asarray(layers[pack]["q"])
        K, F = want.shape[-2:]
        np.testing.assert_array_equal(q[..., :K, :F], want)
        assert not q[..., K:, :].any() and not q[..., :, F:].any()
