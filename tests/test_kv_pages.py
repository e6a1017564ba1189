"""Paged-KV page allocator: pure-host tier-1 coverage (no engine build).

The engine-level paged==fixed token-identity contract lives in the slow
tier (tests/test_paged_kv.py); everything here is host arithmetic —
alloc/free/refcount semantics, OOM backpressure, fragmentation bounds,
config validation, and the fit-planner invariant that admission-time
page reservations can never over-commit the configured pool.
"""
import random

import pytest

from generativeaiexamples_tpu.config import EngineConfig
from generativeaiexamples_tpu.engine import kv_pages


def make_alloc(pool=17, page=8):
    return kv_pages.PageAllocator(pool, page)


# --------------------------------------------------------------------- #
# alloc / free basics
def test_alloc_free_roundtrip():
    a = make_alloc()
    assert a.capacity == 16  # scratch page excluded
    pages = a.alloc(4)
    assert len(pages) == 4
    assert kv_pages.SCRATCH_PAGE not in pages
    assert a.used_pages() == 4 and a.free_pages() == 12
    assert a.release(pages) == 4
    assert a.used_pages() == 0 and a.free_pages() == 16


def test_alloc_zero_is_empty():
    a = make_alloc()
    assert a.alloc(0) == []
    assert a.used_pages() == 0


def test_scratch_page_never_issued():
    a = make_alloc(pool=5)
    pages = a.alloc(4)  # the whole pool
    assert sorted(pages) == [1, 2, 3, 4]


def test_oom_backpressure_leaves_state_intact():
    a = make_alloc(pool=5)
    held = a.alloc(3)
    before = (a.used_pages(), a.free_pages())
    assert a.alloc(2) is None  # only 1 free
    assert (a.used_pages(), a.free_pages()) == before
    # and the failure was counted
    assert kv_pages.metrics_snapshot()["kv_page_alloc_failures"] >= 1
    a.release(held)
    assert len(a.alloc(4)) == 4


# --------------------------------------------------------------------- #
# refcount sharing (zero-copy prefix)
def test_refcount_sharing():
    a = make_alloc()
    pages = a.alloc(2)
    a.retain(pages)  # prefix-cache entry donates
    assert a.refcount(pages[0]) == 2
    assert a.release(pages) == 0  # request leaves; entry still holds
    assert a.used_pages() == 2
    assert a.release(pages) == 2  # entry evicted
    assert a.used_pages() == 0


def test_retain_release_unallocated_raise():
    a = make_alloc()
    with pytest.raises(ValueError):
        a.retain([3])
    with pytest.raises(ValueError):
        a.release([3])


def test_stats_shared_count():
    a = make_alloc()
    own = a.alloc(2)
    shared = a.alloc(2)
    a.retain(shared)
    st = a.stats()
    assert st["pages_in_use"] == 4
    assert st["pages_shared"] == 2
    assert st["utilization"] == pytest.approx(4 / 16)
    a.release(own + shared + shared)


# --------------------------------------------------------------------- #
# sizing arithmetic
def test_pages_needed_caps_at_capacity():
    # prompt + budget + slack beyond capacity clamps to the per-slot max
    assert kv_pages.pages_needed(100, 1000, 8, 64, 5) == 8
    assert kv_pages.pages_needed(10, 6, 8, 64, 0) == 2
    # slack covers in-flight overrun writes
    assert kv_pages.pages_needed(10, 6, 8, 64, 9) == 4


def test_pool_pages_auto_parity():
    cfg = EngineConfig(max_batch_size=4, page_size=8, kv_pool_pages=0)
    # HBM parity: B + prefix slots full strips, plus the scratch page
    assert kv_pages.pool_pages(cfg, 64, prefix_slots=2) == 1 + 6 * 8
    cfg2 = EngineConfig(kv_pool_pages=33)
    assert kv_pages.pool_pages(cfg2, 64) == 33


def test_fit_planner_never_overcommits_pool():
    """Satellite invariant: worst-case admission reservations for a full
    batch always fit the auto-sized pool, and the allocator can never
    hand out more pages than exist — simulated over random request
    mixes with the exact arithmetic the engine's funding step uses."""
    rng = random.Random(7)
    S, page, B, slack = 128, 16, 6, 9
    cfg = EngineConfig(max_batch_size=B, page_size=page, kv_pool_pages=0)
    pool = kv_pages.pool_pages(cfg, S, prefix_slots=0)
    per_slot = kv_pages.pages_for_tokens(S, page)
    # (a) static bound: B concurrent worst-case requests always fundable
    assert pool - 1 >= B * per_slot
    # (b) dynamic: random admit/release churn never over-commits
    a = kv_pages.PageAllocator(pool, page)
    live = []
    for _ in range(300):
        if live and rng.random() < 0.45:
            a.release(live.pop(rng.randrange(len(live))))
        else:
            need = kv_pages.pages_needed(
                rng.randrange(1, S), rng.randrange(1, S), page, S, slack
            )
            assert need <= per_slot
            got = a.alloc(need)
            if got is None:
                assert len(live) >= B  # only a full batch can exhaust it
                continue
            live.append(got)
        assert a.used_pages() + a.free_pages() == a.capacity
        # no page issued twice
        flat = [p for pages in live for p in pages]
        assert len(flat) == len(set(flat))


def test_spec_draft_k_funding_agreement():
    """ISSUE 13 satellite fix: ``cap_draft_len`` and the paged admission
    funding must agree on the EFFECTIVE draft K — a draft-model K
    override (``spec_draft_model_len``) may never let a verify chunk
    write past the funded page reservation. Simulated with the exact
    engine arithmetic: ``slack = decode_block + effective_draft_len + 1``
    (the ``_page_slack`` rule), a budget ledger mirroring
    ``_slot_budget``, and the verify chunk writing rows
    ``[pos, pos + k]`` (draft + bonus) every round."""
    from generativeaiexamples_tpu.engine import spec_decode

    S, page = 128, 16
    for draft_len, model_len, proposer in [
        (8, 0, "lookup"),        # lookup ignores the override
        (4, 12, "draft_model"),  # override WIDER than spec_draft_len
        (2, 9, "combined"),
        (8, 3, "draft_model"),   # override narrower
    ]:
        cfg = EngineConfig(
            spec_draft_len=draft_len,
            spec_draft_model_len=model_len,
            spec_proposer=proposer,
            spec_draft_model="debug",
            decode_block=4,
            page_size=page,
        )
        K = spec_decode.effective_draft_len(cfg)
        if proposer == "lookup":
            assert K == draft_len
        elif model_len:
            assert K == model_len
        slack = cfg.decode_block + K + 1  # llm_engine._page_slack
        for T in (1, 17, 100):
            for M in (1, 8, 64):
                funded_tokens = kv_pages.pages_needed(
                    T, M, page, S, slack
                ) * page
                budget = min(M - 1, S - 1 - T)
                pos = T
                while budget > 0:
                    k = spec_decode.cap_draft_len(K, pos, budget, S)
                    assert 0 <= k <= K
                    # every row the verify chunk writes sits inside the
                    # funded reservation (and the cache)
                    assert pos + k < min(funded_tokens, S)
                    emitted = k + 1
                    pos += emitted
                    budget -= emitted


def test_fragmentation_bound():
    """Internal fragmentation per request is bounded by one partial page
    plus the reserved generation budget — with the whole batch live, the
    wasted fraction stays under (slack + budget + page) / live size."""
    S, page, slack = 256, 16, 9
    a = kv_pages.PageAllocator(1 + 8 * kv_pages.pages_for_tokens(S, page), page)
    waste = 0
    live_tokens = 0
    for prompt, budget, generated in [(100, 64, 64), (37, 16, 3), (5, 8, 8)]:
        need = kv_pages.pages_needed(prompt, budget, page, S, slack)
        pages = a.alloc(need)
        live = prompt + generated
        live_tokens += live
        waste += need * page - live
        # per-request bound: reservation slack + page rounding
        assert need * page - live <= (budget - generated) + slack + page
    frag = waste / (waste + live_tokens)
    assert 0.0 <= frag < 1.0


def test_occupancy_basis_mean_and_peak():
    """The allocator's transition-sampled occupancy accessor — the ONE
    mean-live basis bench's fixed-vs-paged bytes/token comparison
    evaluates both layouts at."""
    a = make_alloc()
    a.occupancy(reset=True)
    p1 = a.alloc(4)   # sample: 4 in use
    p2 = a.alloc(8)   # sample: 12 in use
    a.release(p2)     # sample: 4 in use
    occ = a.occupancy()
    assert occ["peak_live_pages"] == 12
    assert occ["occupancy_samples"] == 3
    assert occ["mean_live_pages"] == pytest.approx((4 + 12 + 4) / 3)
    st = a.stats()
    assert st["peak_live_pages"] == 12
    assert st["mean_live_pages"] == occ["mean_live_pages"]
    # reset=True starts a fresh window (a tool brackets its measured wave)
    a.occupancy(reset=True)
    a.release(p1)
    occ2 = a.occupancy()
    assert occ2["occupancy_samples"] == 1
    assert occ2["mean_live_pages"] == 0.0


# --------------------------------------------------------------------- #
# config validation
def _paged_cfg(**kw):
    base = dict(page_size=16, prefill_chunk=64)
    base.update(kw)
    return EngineConfig(**base)


def test_validate_config_accepts_the_defaults():
    kv_pages.validate_config(EngineConfig())  # 128-token pages tile a 512-token chunk
    kv_pages.validate_config(_paged_cfg())


def test_validate_config_paged_kernel_knob():
    for mode in ("auto", "off", "interpret"):
        kv_pages.validate_config(_paged_cfg(paged_kernel=mode))
    with pytest.raises(ValueError, match="paged_kernel"):
        kv_pages.validate_config(_paged_cfg(paged_kernel="always"))


@pytest.mark.parametrize(
    "kw,match",
    [
        (dict(kv_pool_pages=-1), "kv_pool_pages"),
        (dict(page_size=0), "power of two"),
        (dict(page_size=24), "power of two"),
        (dict(page_size=256, prefill_chunk=256), "128"),
        (dict(page_size=32, prefill_chunk=48), "multiple of"),
    ],
)
def test_validate_config_rejections(kw, match):
    with pytest.raises(ValueError, match=match):
        kv_pages.validate_config(_paged_cfg(**kw))


def test_validate_runtime():
    kv_pages.validate_runtime(16, 128, 1 + 8)
    with pytest.raises(ValueError, match="multiple"):
        kv_pages.validate_runtime(16, 120, 100)
    with pytest.raises(ValueError, match="rung"):
        kv_pages.validate_runtime(256, 512, 100)
    with pytest.raises(ValueError, match="full-length"):
        kv_pages.validate_runtime(16, 128, 8)


# --------------------------------------------------------------------- #
# metrics plumbing
def test_metrics_snapshot_moves():
    m0 = kv_pages.metrics_snapshot()
    a = make_alloc()
    pages = a.alloc(3)
    a.release(pages)
    kv_pages.record_prefix_mapped(5)
    m1 = kv_pages.metrics_snapshot()
    assert m1["kv_page_allocs"] - m0["kv_page_allocs"] == 3
    assert m1["kv_page_frees"] - m0["kv_page_frees"] == 3
    assert m1["kv_prefix_pages_mapped"] - m0["kv_prefix_pages_mapped"] == 5
    assert set(m1) >= {
        "kv_page_allocs", "kv_page_frees", "kv_page_alloc_failures",
        "kv_prefix_pages_mapped", "kv_pages_in_use", "kv_page_utilization",
    }


# --------------------------------------------------------------------- #
# how a quantised pool stores a page's scales (models/llama.py
# kv_scale_plane_shape), and every writer of them against the reader


@pytest.mark.parametrize(
    "page,hkv,head_sharded,plane",
    [
        (128, 8, False, (8, 128)),  # the benchmark's int8 cell: one 4 KB tile a page
        (16, 8, False, (1, 128)),
        (64, 2, False, (1, 128)),
        (128, 8, True, (128, 8)),  # heads sharded over a mesh: the head dimension stays
        (8, 8, False, (8, 8)),  # 64 scales a page: no whole 128-lane row
        (16, 2, False, (16, 2)),  # the debug engines
        (128, 10, False, (128, 10)),  # 10 heads: a token's scales would straddle two rows
        (128, 128, False, (128, 128)),  # the two layouts coincide
    ],
)
def test_scale_plane_shape_follows_the_geometry(page, hkv, head_sharded, plane):
    import jax

    from generativeaiexamples_tpu.models import llama

    assert llama.kv_scale_plane_shape(page, hkv, head_sharded) == plane
    cfg = llama.LlamaConfig(vocab_size=32, hidden_size=16, intermediate_size=16, num_layers=1,
                            num_heads=hkv, num_kv_heads=hkv, head_dim=2, max_seq_len=page)
    for packed in (False, True):
        pool = jax.eval_shape(lambda: llama.init_kv_pool(
            cfg, 5, page, quantized=True, packed=packed, head_sharded=head_sharded))[0]
        assert pool["ks"].shape == pool["vs"].shape == (5,) + plane
        assert pool["k"].shape[:3] == (5, page, hkv)  # the values stay token-major


WRITER_PAGE, WRITER_PMAX, WRITER_SLOTS = 32, 4, 3


def _writer_run(writer, caches, params, cfg):
    """One walk that writes K/V scales; returns (what it computed, pools)."""
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.models import llama

    page, pmax = WRITER_PAGE, WRITER_PMAX
    rng = np.random.default_rng(11)
    tables = jnp.asarray(1 + np.arange(WRITER_SLOTS * pmax).reshape(WRITER_SLOTS, pmax), jnp.int32)
    tok = lambda *shape: jnp.asarray(rng.integers(1, cfg.vocab_size, shape), jnp.int32)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    if writer == "reference_prefill":  # (the reference walk's writer, no engine program's) rows of 40 tokens: a page boundary inside every row
        kvs = [tuple(jnp.asarray(rng.standard_normal((2, 40, cfg.num_kv_heads, cfg.head_dim)), jnp.float32)
                     for _ in "kv") for _ in caches]
        return None, llama.write_prefill_pages(caches, kvs, tables[i32([2, 0])], page)
    if writer == "rectangular_chunk":  # the verify walk: a row across a page boundary, a half-dead row, a dead row
        return llama.verify_layers_paged(
            params, cfg, tok(3, 8), i32([page - 3, 5, 80]), i32([8, 4, 0]), i32([1, 0, 2]), tables, caches,
            pmax * page, page)
    if writer == "packed_wave":  # 32 tokens: 19 of slot 2 from 29 (into its second page), a gap, 6 of slot 0 from 0
        return llama.extend_layers_packed(
            params, cfg, tok(32), i32([0, 24]), i32([19, 6]), i32([29, 0]), i32([2, 0]), tables, caches, page,
            seg=32, windows=(pmax * page,))
    assert writer == "decode_step"  # a page's last and first token, and a dead row on the scratch page
    return llama.decode_layers_paged(
        params, cfg, tok(3), i32([page - 1, page, 0]), jnp.asarray([True, True, False]), tables, caches,
        window=pmax * page, page_size=page)


@pytest.mark.parametrize("kv", ["int8", "int4"])
@pytest.mark.parametrize("writer", ["reference_prefill", "rectangular_chunk", "packed_wave", "decode_step"])
def test_every_writer_of_scales_and_the_gather_reader_agree_across_layouts(writer, kv):
    """Each walk that writes a quantised pool, over a LANE-DENSE pool and
    over a token-major one (what a head-sharded pool keeps): the gather
    reader returns the same scales from both on every page a request can
    hold (rows across a page boundary, a half-dead row, a dead row),
    nonzero exactly at the live tokens, and what the walk computed
    through the gather read (the int4 pool's only read at this head
    size) is the same to the bit."""
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.models import llama

    cfg = llama.PRESETS["debug-8dev"]  # 8 KV heads x 32-token pages: two 128-lane rows a page
    params = llama.consume_split_params_layers(llama.init_params_fast(cfg, 0, jnp.float32))
    pool = 1 + WRITER_SLOTS * WRITER_PMAX
    outs = {}
    for layout in ("lane_dense", "token_major"):
        caches = llama.init_kv_pool(cfg, pool, WRITER_PAGE, jnp.float32, quantized=True, packed=kv == "int4",
                                    head_sharded=layout == "token_major")
        assert caches[0]["ks"].shape[1:] == ((2, 128) if layout == "lane_dense" else (WRITER_PAGE, 8))
        outs[layout] = _writer_run(writer, caches, params, cfg)
    (got, dense), (want, token_major) = outs["lane_dense"], outs["token_major"]
    if want is not None:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    every_page = jnp.arange(pool, dtype=jnp.int32)[None]
    live_tokens = {"reference_prefill": 2 * 40, "rectangular_chunk": 8 + 4, "packed_wave": 19 + 6, "decode_step": 2}[writer]
    for a, b in zip(dense, token_major):
        for key in ("ks", "vs"):
            rows = np.asarray(llama.gather_kv_scales(a[key], every_page, pool, WRITER_PAGE))[0]
            assert rows.shape == (pool * WRITER_PAGE, 8)
            # every page a request can hold, to the bit; the scratch page
            # takes the token-major walk's dead tokens and none of the
            # lane-dense walk's, which rebuilds its live tokens' rows only
            np.testing.assert_array_equal(rows[WRITER_PAGE:], np.asarray(b[key]).reshape(rows.shape)[WRITER_PAGE:])
            assert not rows[:WRITER_PAGE].any(), key
            assert (rows != 0).all(-1).sum() == (rows != 0).any(-1).sum() == live_tokens, key
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))
