"""The Phi-4-mini-flash-reasoning adapter (perfbench/arch/phi4flash.py):
its plain float32 reference by hand and against the engine at a tiny
size that keeps the layer rule, the control one precision down, the
injected faults that must each fail ``TOLERANCE``, its byte count, its
reader and its configuration file."""
import json
import math
import os

import numpy as np
import pytest

from perfbench import reference
from perfbench.arch import phi4flash as phi
from tests.perfbench.manifest_entries import assert_cell_holds, real

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "perfbench")

# Mamba 0,2,4 (4 publishes the memory); window 1,3; full 5; GMU 6; cross 7
TINY = {
    "name": "phi4flash-tiny-test", "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4, "sliding_window": 8, "layer_norm_eps": 1e-5,
    "vocab_size": 512, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "max_position_embeddings": 1024, "tie_word_embeddings": True,
}


@pytest.fixture(scope="module")
def engine():
    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    phi.register(TINY)
    eng = LLMEngine(EngineConfig(
        model_config_name=TINY["name"], tensor_parallelism=1, max_batch_size=3, max_seq_len=128,
        prefill_chunk=16, page_size=8, decode_block=4, prefix_cache_enable="off",
        dtype="float32", paged_kernel="off",
    ))
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def compared(engine):
    """As the launcher compares on the chip: last-position logits of the
    served walks (prefill alone; prefill and one decode step; chunked
    extend past the window AND the chunk: 40 tokens, chunks of 16, 16
    and 8), and greedy tokens through the engine for the same prompts."""
    from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

    prompts = reference.seeded_prompts([6, 13, 40], 500, seed=7)  # (seed 5 draws a stop id first)
    eng_logits = phi.engine_prefill_logits(engine, prompts, on_tpu=False)
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    tokens = [list(engine.iter_ids(p, greedy, timeout=300)) for p in prompts]
    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    return prompts, eng_logits, tokens, full


def compare(engine, compared, cfg=TINY, **kw):
    prompts, eng_logits, tokens, full = compared
    ref = phi.reference_logits(engine, cfg, full, **kw)
    return reference.compare(prompts, list(eng_logits), tokens, ref, phi.TOLERANCE)


def test_engine_agrees_with_the_reference_through_prefill_extend_and_decode(engine, compared):
    out = compare(engine, compared)
    assert out["ok"], out
    assert len(out["prefill_rel_err"]) == 3 and max(out["prefill_rel_err"]) < 1e-4
    assert out["decode_tokens_checked"] == 18 and out["decode_margin_max"] < 1e-4


def test_compared_logits_are_the_served_walks_in_the_engines_shapes(engine, compared, monkeypatch):
    """Which walks compute the compared logits, and in what shapes: a
    chunk of ``prefill_chunk`` tokens for one row, a scratch cache of one
    slot."""
    calls = []
    fam = engine._family

    def spy(name):
        real = getattr(fam, name)

        def walk(params, cfg, caches, tokens, *rest, **kw):
            calls.append((name, tuple(tokens.shape), caches["ssm"][0].shape[0]))
            return real(params, cfg, caches, tokens, *rest, **kw)
        return walk

    import dataclasses

    monkeypatch.setattr(engine, "_family", dataclasses.replace(
        fam, **{n: spy(n) for n in ("prefill_paged", "extend_paged", "decode_paged")}))
    again = phi.engine_prefill_logits(engine, compared[0], on_tpu=False)
    # (a walk is traced once a shape: the second prefill and the later chunks reuse theirs)
    assert [c[0] for c in calls] == ["prefill_paged", "decode_paged", "extend_paged"]
    assert [c[1] for c in calls] == [(1, 16), (1,), (1, 16)]
    assert {c[2] for c in calls} == {1}
    for a, b in zip(again, compared[1]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


SERVED_FAULTS = {
    # the scan hands back the state it was given: nothing is carried on
    "scan_state_not_carried": ("selective_scan", lambda real: lambda *a, **k: (real(*a, **k)[0], a[6])),
    # ring and page writes are lost: a decode step reads what the slot held before
    "cache_writes_lost": ("_write_rows", lambda real: lambda buf, lead, row, values: buf),
    # the pages of the shared K/V read back as zeros
    "shared_kv_pages_not_read": ("_gather_window", lambda real: lambda pool, pages: 0 * real(pool, pages)),
}


@pytest.mark.parametrize("fault", sorted(SERVED_FAULTS))
def test_a_fault_in_the_served_walks_fails_the_logits_comparison(engine, compared, fault, monkeypatch):
    """The limit on ``prefill_rel_err`` holds the SERVED path (caches,
    carried state, page reads), not a cache-free forward beside it."""
    from generativeaiexamples_tpu.models import phi4flash as model

    name, make = SERVED_FAULTS[fault]
    monkeypatch.setattr(model, name, make(getattr(model, name)))
    prompts, _, tokens, full = compared
    faulty = phi.engine_prefill_logits(engine, prompts, on_tpu=False)
    out = reference.compare(prompts, faulty, tokens, phi.reference_logits(engine, TINY, full), phi.TOLERANCE)
    assert not out["ok"] and max(out["prefill_rel_err"]) > phi.TOLERANCE, out


def test_the_control_one_precision_down_fails_the_tolerance():
    """The reference with NOTHING in float32 (bfloat16 residual stream,
    scan state, normalisation and softmax) is not correct: at the
    published depth (32 layers; tiny widths) over eight prompts, held
    against the float32 reference standing in for a faultless engine.
    At the published widths it reads 0.0757 / 0.0744 (the adapter)."""
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import phi4flash as model

    class Weights:
        pass

    deep = dict(TINY, num_hidden_layers=32)
    held = Weights()
    held.params = model.init_params_fast(phi.model_config(deep), 0, jnp.float32)
    prompts = reference.seeded_prompts([6, 13, 40, 24, 17, 33, 9, 28], 500, seed=7)
    exact = phi.reference_logits(held, deep, prompts)
    tokens = [[int(np.argmax(r[-1]))] for r in exact]
    full = [list(p) + t for p, t in zip(prompts, tokens)]
    exact = phi.reference_logits(held, deep, full)
    perfect = [r[len(p) - 1] for r, p in zip(exact, prompts)]
    assert reference.compare(prompts, perfect, tokens, exact, phi.TOLERANCE)["ok"]
    out = reference.compare(prompts, perfect, tokens,
                            phi.reference_logits(held, deep, full, precision="bfloat16"), phi.TOLERANCE)
    assert not out["ok"], out
    assert max(out["prefill_rel_err"]) > phi.TOLERANCE


def lam_free_weights(w, l):
    """Lambda vectors for which lam = exp(-50) - exp(log lam0) + lam0 = 0."""
    w = dict(w)
    if "lq1" in w:
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)
        unit = np.zeros_like(np.asarray(w["lk1"]))
        unit[0] = 1.0
        w.update(lq1=-50.0 * unit, lk1=unit, lq2=math.log(lam0) * unit, lk2=unit)
    return w


FAULTS = ["no_lam_term", "memory_from_the_wrong_layer", "window_off_by_one", "state_not_reset",
          "positional_encoding_added"]


@pytest.mark.parametrize("fault", FAULTS)
def test_every_injected_fault_fails_the_tolerance(engine, compared, fault, monkeypatch):
    import jax.numpy as jnp

    cfg = dict(TINY)
    real_mixer, real_weights, real_embed = phi.mamba_mixer, phi.engine_layer_weights, phi.embed_tokens
    if fault == "no_lam_term":
        monkeypatch.setattr(phi, "engine_layer_weights", lambda params, l: lam_free_weights(real_weights(params, l), l))
    elif fault == "memory_from_the_wrong_layer":  # an earlier Mamba layer's scan output
        monkeypatch.setattr(phi, "memory_layer", lambda n: n // 2 - 2)
    elif fault == "window_off_by_one":
        cfg["sliding_window"] = TINY["sliding_window"] + 1
    elif fault == "state_not_reset":
        def mixer(x, w, cfg_):  # the scan starts from a former tenant's state, not from zero
            out, y = real_mixer(jnp.concatenate([jnp.ones_like(x[:4]), x], axis=0), w, cfg_)
            return out[4:], y[4:]
        monkeypatch.setattr(phi, "mamba_mixer", mixer)
    else:
        def embed(emb, tokens):  # sinusoids on the embeddings: this model has none
            h = real_embed(emb, tokens)
            pos = jnp.arange(h.shape[0], dtype=h.dtype)[:, None]
            freq = jnp.exp(-jnp.arange(h.shape[1], dtype=h.dtype)[None, :] / h.shape[1] * 4.0)
            return h + 0.1 * jnp.sin(pos * freq)
        monkeypatch.setattr(phi, "embed_tokens", embed)
    out = compare(engine, compared, cfg=cfg)
    assert not out["ok"], out


def test_reference_by_hand_mamba_scan_and_differential_attention():
    """The two new mixers on cases small enough to compute with loops."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    cfg = {"hidden_size": 32, "mamba_d_state": 2, "mamba_d_conv": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "layer_norm_eps": 1e-5}
    di, r, ds, T = 4, 2, 2, 3
    w = {"in_proj": rng.normal(size=(32, 2 * di)) * 0.3, "conv_w": rng.normal(size=(2, di)), "conv_b": rng.normal(size=(di,)),
         "x_proj": rng.normal(size=(di, r + 2 * ds)), "dt_proj": rng.normal(size=(r, di)), "dt_bias": rng.normal(size=(di,)),
         "A_log": rng.normal(size=(ds, di)), "D": rng.normal(size=(di,)), "out_proj": rng.normal(size=(di, 32))}
    w = {k: v.astype(np.float32) for k, v in w.items()}
    x = rng.normal(size=(T, 32)).astype(np.float32)
    out, y = phi.mamba_mixer(jnp.asarray(x), {k: jnp.asarray(v) for k, v in w.items()}, cfg)
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    xz = x @ w["in_proj"]
    xin, z = xz[:, :di], xz[:, di:]
    state, want = np.zeros((ds, di)), []
    for t in range(T):
        conv = w["conv_w"][1] * xin[t] + (w["conv_w"][0] * xin[t - 1] if t else 0) + w["conv_b"]
        u = silu(conv)
        dbc = u @ w["x_proj"]
        dt = np.log1p(np.exp(dbc[:r] @ w["dt_proj"] + w["dt_bias"]))
        for c in range(di):
            for s in range(ds):
                state[s, c] = math.exp(dt[c] * -math.exp(w["A_log"][s, c])) * state[s, c] + dt[c] * u[c] * dbc[r + s]
        want.append(state.T @ dbc[r + ds:] + w["D"] * u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(out), (np.asarray(want) * silu(z)) @ w["out_proj"], rtol=2e-5, atol=2e-5)

    # differential attention: 2 diff-heads over ONE diff-KV-head, head size 8, layer 3
    Dh, S = 8, 4
    q, k, v = (rng.normal(size=s).astype(np.float32) for s in ((S, 4, Dh), (S, 2, Dh), (S, 2, Dh)))
    aw = {"lq1": rng.normal(size=Dh) * 0.3, "lk1": rng.normal(size=Dh) * 0.3, "lq2": rng.normal(size=Dh) * 0.3,
          "lk2": rng.normal(size=Dh) * 0.3, "subln": rng.normal(size=2 * Dh), "wo": np.eye(32), "bo": np.zeros(32)}
    aw = {kk: vv.astype(np.float32) for kk, vv in aw.items()}
    causal = np.tril(np.ones((S, S), bool))
    got = phi.diff_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             {kk: jnp.asarray(vv) for kk, vv in aw.items()}, cfg, phi.lambda_init(3), jnp.asarray(causal))
    lam0 = 0.8 - 0.6 * math.exp(-0.9)
    lam = math.exp(aw["lq1"] @ aw["lk1"]) - math.exp(aw["lq2"] @ aw["lk2"]) + lam0
    vcat = np.concatenate([v[:, 0], v[:, 1]], axis=-1)
    want = np.zeros((S, 32), np.float32)
    for i in range(2):
        a = []
        for half in range(2):
            sc = q[:, 2 * i + half] @ k[:, half].T / math.sqrt(Dh)
            sc = np.where(causal, sc, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            a.append(p / p.sum(-1, keepdims=True) @ vcat)
        d = a[0] - lam * a[1]
        d = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) * aw["subln"]
        want[:, 16 * i:16 * (i + 1)] = (1 - lam0) * d
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_reference_sees_no_future_and_no_further_back_than_the_window():
    rng = np.random.default_rng(3)

    def weights(l):
        kind = phi.layer_kind(l, 8)
        h, di = 64, 128
        w = {"ln1_w": np.ones(h), "ln1_b": np.zeros(h), "ln2_w": np.ones(h), "ln2_b": np.zeros(h),
             "w_gate_up": rng.normal(size=(h, 256)) * 0.1, "w_down": rng.normal(size=(128, h)) * 0.1}
        if kind == "mamba":
            w.update(in_proj=rng.normal(size=(h, 2 * di)) * 0.1, conv_w=rng.normal(size=(4, di)), conv_b=np.zeros(di),
                     x_proj=rng.normal(size=(di, 4 + 32)) * 0.1, dt_proj=rng.normal(size=(4, di)), dt_bias=np.zeros(di),
                     A_log=np.zeros((16, di)), D=np.ones(di), out_proj=rng.normal(size=(di, h)) * 0.1)
        elif kind == "gmu":
            w.update(w1=rng.normal(size=(h, di)) * 0.1, w2=rng.normal(size=(di, h)) * 0.1)
        else:
            width = 64 + (64 if kind != "cross" else 0)
            name = "wqkv" if kind != "cross" else "wq"
            w.update({name: rng.normal(size=(h, width)) * 0.2, "b" + name[1:]: np.zeros(width),
                      "wo": rng.normal(size=(64, h)) * 0.1, "bo": np.zeros(h), "subln": np.ones(16),
                      "lq1": np.zeros(8), "lk1": np.zeros(8), "lq2": np.zeros(8), "lk2": np.zeros(8)})
        return {k: np.asarray(v, np.float32) for k, v in w.items()}

    layers = [weights(l) for l in range(8)]
    embed = rng.normal(size=(20, 64)).astype(np.float32)
    norm = (np.ones(64, np.float32), np.zeros(64, np.float32))
    a, b = phi.forward([[1, 2, 3, 4, 5], [1, 2, 3, 4, 9]], TINY, embed, lambda l: layers[l], norm)
    np.testing.assert_allclose(a[:4], b[:4], rtol=1e-5, atol=1e-6)  # causal
    assert np.max(np.abs(a[4] - b[4])) > 1e-3
    assert [phi.layer_kind(l, 32) for l in (0, 1, 16, 17, 18, 19, 31)] == \
        ["mamba", "window", "mamba", "full", "gmu", "cross", "cross"]


def test_decode_step_floor_counts_this_models_bytes():
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning-bf16.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    assert abs(phi.decode_weight_bytes(cfg) - 7.71e9) < 0.02e9
    assert phi.kv_bytes_per_token(cfg) == cfg["engine"]["kv_bytes_per_token"] == 5120
    assert phi.state_bytes_per_row(cfg) == 2 * 9 * (327_680 + 30_720)
    # ISSUE 29's case: 64 rows at a mean context of 1.6k: ~17 ms, bound by bytes
    floor = phi.decode_step_floor_s(cfg, peaks, 64, 1600)
    per_row = 8 * 1600 * 5120 + 8 * 512 * 5120 + phi.state_bytes_per_row(cfg)
    assert abs(floor - (phi.decode_weight_bytes(cfg) + 64 * per_row) / 819e9) < 2e-5
    assert 0.015 < floor < 0.019
    assert phi.decode_step_flops(cfg, 64, 1600) / peaks["bf16_flops_per_s"] < floor
    assert phi.decode_step_floor_s(cfg, peaks, 64, 300) < floor  # a window not yet full reads less


def test_prefill_cross_skipped_share_reads_two_counters_or_nothing():
    skipped, tokens = "genai_engine_prefill_cross_skipped_tokens_total", "genai_engine_prefill_tokens_total"
    before = {(skipped, frozenset()): 100.0, (tokens, frozenset()): 110.0}
    after = {(skipped, frozenset()): 1090.0, (tokens, frozenset()): 1110.0}
    ctx = {"metrics_before": before, "metrics_after": after}
    assert phi.prefill_cross_skipped_share(ctx, {}) == pytest.approx(99.0)
    # a program without the counters (the parent): nothing to read, no exception
    assert phi.prefill_cross_skipped_share({"metrics_before": {}, "metrics_after": {}}, {}) is None
    assert phi.prefill_cross_skipped_share({"metrics_before": before, "metrics_after": before}, {}) is None


def test_configuration_file_holds_the_catalogs_keys_and_cuts_nothing():
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning-bf16.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
        "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
        "vocab_size": 200064,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == [] and cfg["adapter"] == "perfbench.arch.phi4flash"
    mc = phi.model_config(cfg)
    assert (mc.num_layers, mc.d_inner, mc.dt_rank, mc.head_dim) == (32, 5120, cfg["mamba_dt_rank"], 64)
    env, eng = cfg["server_env"], cfg["engine"]
    assert env["APP_ENGINE_QUANTIZATION"] == "none" and env["APP_ENGINE_PREFIXCACHEENABLE"] == "off"
    assert int(env["APP_ENGINE_KVPOOLPAGES"]) == eng["kv_pool_pages"] == 64 * 4096 // 128 + 1
    assert int(env["APP_ENGINE_MAXBATCHSIZE"]) == eng["max_batch_size"] == 64
    assert int(env["APP_ENGINE_PREFILLWAVETOKENS"]) == eng["prefill_wave_tokens"]
    from generativeaiexamples_tpu.models import phi4flash as model

    assert eng["fixed_state_bytes_per_slot"] == model.fixed_state_bytes_per_slot(mc)
    assert eng["kv_bytes_per_token"] == model.kv_bytes_per_token(mc)
    ref = cfg["reference"]  # logits AND tokens of every prompt; one past the window AND the chunk
    assert max(ref["prompt_tokens"]) > max(eng["prefill_chunk"], cfg["sliding_window"])
    assert not ref.get("served_only_prompt_tokens") and len(ref["prompt_tokens"]) >= 3
    assert "APP_ENGINE_IGNOREEOS" not in env
    grow = {c["metric"] for c in cfg["correct"]["counters_must_grow"]}
    assert grow == {"genai_engine_state_slot_resets_total", "genai_engine_prefill_cross_skipped_tokens_total"}


CELL = "reason_decode_phi4flash"
# the cell's per-layer metrics as PRs 29 and 30 brought them (base names since PR 56) and PR 40's whole-window span metrics
PER_LAYER = (
    "decode_rows_mean", "decode_step_dev_ms", "decode_step_roofline_share", "tpot_chat_p50_ms", "device_idle_share",
    "page_attn_busy_share", "page_attn_pages_walked_mean", "state_rows_mean", "window_tokens_read_mean",
    "prefill_cross_skipped_share", "stream_backlog_tokens_mean",
    "decode_step_done_ms", "extend_wide_done_ms", "extend_narrow_done_ms", "extend_device_share",
    "device_starved_share", "device_hold_max_ms",
)


def assert_manifest_entries_of_the_cell(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi-4-mini-flash-reasoning-bf16", "reason_decode", 1)
    mine = assert_cell_holds(manifest, CELL, PER_LAYER)
    assert "extend_dispatch_dev_ms" not in mine  # the family's extend programs are not named jit_extend*


def test_manifest_entries_of_the_cell_found_by_name():
    assert_manifest_entries_of_the_cell(real())
