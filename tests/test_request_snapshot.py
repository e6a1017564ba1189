"""Request snapshot substrate (engine/request_snapshot.py), tier-1
pure host — no engine build: the array codec (bf16/int8 included), the
versioned document round-trip, seed pinning in sampling_params, and
the bounded on-disk spool (eviction, fingerprint refusal, missing
entries)."""
import json
import os

import numpy as np
import pytest

from generativeaiexamples_tpu.engine import request_snapshot as snap_mod
from generativeaiexamples_tpu.engine.request_snapshot import (
    RequestSnapshot,
    SnapshotError,
    SnapshotMismatch,
    SnapshotSpool,
    decode_kv_payload,
    encode_kv_payload,
)


def _snap(sid="snap-1-abc", **over):
    kwargs = dict(
        snapshot_id=sid,
        rid=1,
        prompt_ids=[5, 6, 7],
        emitted=[11, 12],
        position=5,
        sampling_seed=42,
        params={"temperature": 0.0, "top_p": 0.7, "max_tokens": 8,
                "stop": [], "seed": 0, "prefix_hint": None,
                "spec_decode": None},
        created_at=123.0,
    )
    kwargs.update(over)
    return RequestSnapshot(**kwargs)


# --------------------------------------------------------------------------- #
# codec


@pytest.mark.parametrize(
    "dtype", ["float32", "int8", "int32", "bfloat16", "uint8"]
)
def test_kv_payload_codec_roundtrip_bitexact(dtype):
    import ml_dtypes

    np_dtype = (
        np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16"
        else np.dtype(dtype)
    )
    rng = np.random.default_rng(7)
    arr = rng.standard_normal((2, 4, 3)).astype(np_dtype)
    layers = [{"k": arr, "v": arr * 2}, {"k": arr + 1, "v": arr - 1}]
    doc = encode_kv_payload(layers)
    # the payload document must survive a JSON wire trip (the router
    # relays it verbatim between replicas)
    doc = json.loads(json.dumps(doc))
    back = decode_kv_payload(doc)
    assert len(back) == 2
    for orig, got in zip(layers, back):
        for key in orig:
            assert got[key].dtype == orig[key].dtype
            assert got[key].shape == orig[key].shape
            assert np.array_equal(
                got[key].view(np.uint8), orig[key].view(np.uint8)
            )


@pytest.mark.parametrize("kv", ["int8", "int4"])
@pytest.mark.parametrize("source", ["lane_dense", "token_major"])
def test_a_payload_of_either_scale_layout_restores_into_a_pool_of_the_other(source, kv):
    """A payload's scale planes are page gathers of the pool that took
    it: lane-dense [pages, page * Hkv / 128, 128] from a single-device
    engine, token-major [pages, page, Hkv] from a head-sharded one. The
    two are the same bytes a page, so the upload restores either into
    either (the payload's own shapes record which it holds)."""
    import jax.numpy as jnp

    from generativeaiexamples_tpu.models import llama

    cfg, page, pool = llama.PRESETS["debug-8dev"], 16, 7  # 16 x 8 heads: one 128-lane row a page
    rng = np.random.default_rng(3)

    def build(layout):
        caches = llama.init_kv_pool(
            cfg, pool, page, quantized=True, packed=kv == "int4", head_sharded=layout == "token_major")
        want = (page, cfg.num_kv_heads) if layout == "token_major" else (1, 128)
        assert caches[0]["ks"].shape == caches[0]["vs"].shape == (pool,) + want
        return caches

    src = build(source)
    for layer in src:
        for key in ("ks", "vs"):
            layer[key] = jnp.asarray(rng.uniform(1, 2, layer[key].shape), jnp.float32)
        for key in ("k", "v"):
            layer[key] = jnp.asarray(rng.integers(0, 100, layer[key].shape), layer[key].dtype)
    taken = np.asarray([2, 5], np.int32)
    doc = json.loads(json.dumps(encode_kv_payload(
        [{key: np.asarray(buf[taken]) for key, buf in layer.items()} for layer in src])))
    assert doc["layers"][0]["ks"]["shape"] == [2, *src[0]["ks"].shape[1:]]
    dst = build("token_major" if source == "lane_dense" else "lane_dense")
    into = jnp.asarray([4, 1], jnp.int32)
    snap_mod.upload_kv_payload(dst, into, decode_kv_payload(doc))
    tables = lambda pages: jnp.asarray([list(pages)], jnp.int32)
    for a, b in zip(src, dst):
        for key in ("ks", "vs"):
            got = np.asarray(llama.gather_kv_scales(b[key], tables(into), 2, page))
            np.testing.assert_array_equal(got, np.asarray(llama.gather_kv_scales(a[key], tables(taken), 2, page)))
            assert got.all() and not np.asarray(b[key])[0].any()
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(b[key][into]), np.asarray(a[key][taken]))


def test_snapshot_doc_roundtrip_and_provenance_stamp():
    snap = _snap(kv=encode_kv_payload([{"k": np.zeros((1, 2), np.int8)}]),
                 geometry={"page_size": 8, "pages": 1})
    doc = json.loads(json.dumps(snap.to_doc()))
    assert doc["version"] == snap_mod.SNAPSHOT_VERSION
    assert "git_sha" in doc["provenance"]
    back = RequestSnapshot.from_doc(doc)
    assert back.snapshot_id == snap.snapshot_id
    assert back.prompt_ids == snap.prompt_ids
    assert back.emitted == snap.emitted
    assert back.position == snap.position
    assert back.sampling_seed == snap.sampling_seed
    assert back.restorable and back.geometry == snap.geometry


def test_version_drift_refused():
    doc = _snap().to_doc()
    doc["version"] = snap_mod.SNAPSHOT_VERSION + 1
    with pytest.raises(SnapshotMismatch, match="version"):
        RequestSnapshot.from_doc(doc)


def test_sampling_params_pin_the_spooled_seed():
    """An unseeded request drew its effective seed at original submit
    time; the rebuilt params must pin THAT seed, never re-draw."""
    snap = _snap(sampling_seed=987654)
    assert snap.params["seed"] == 0  # the client never sent one
    params = snap.sampling_params()
    assert params.seed == 987654
    assert params.temperature == 0.0 and params.max_tokens == 8


def test_replay_only_snapshot_has_no_payload():
    snap = _snap()
    assert not snap.restorable
    back = RequestSnapshot.from_doc(json.loads(json.dumps(snap.to_doc())))
    assert back.kv is None and not back.restorable


# --------------------------------------------------------------------------- #
# spool


def test_spool_save_load_list_and_load_doc(tmp_path):
    spool = SnapshotSpool(str(tmp_path / "spool"), max_entries=8,
                          fingerprint="fp-a")
    snap = _snap(kv=encode_kv_payload([{"k": np.ones((1, 2), np.int8)}]),
                 geometry={"page_size": 8})
    path = spool.save(snap)
    assert os.path.exists(path)
    assert snap.config_fingerprint == "fp-a"  # stamped on save
    back = spool.load(snap.snapshot_id)
    assert back.emitted == snap.emitted
    assert back.config_fingerprint == "fp-a"
    doc = spool.load_doc(snap.snapshot_id)
    assert doc["snapshot_id"] == snap.snapshot_id
    inv = spool.list()
    assert len(inv) == 1
    assert inv[0]["snapshot_id"] == snap.snapshot_id
    assert inv[0]["restorable"] is True
    assert inv[0]["bytes"] > 0


def test_spool_missing_and_traversal_safe(tmp_path):
    spool = SnapshotSpool(str(tmp_path / "spool"), max_entries=2)
    with pytest.raises(SnapshotError, match="not in spool"):
        spool.load("snap-nope")
    with pytest.raises(SnapshotError):
        spool.load_doc("../../etc/passwd")


def test_spool_bounded_oldest_evicted(tmp_path):
    spool = SnapshotSpool(str(tmp_path / "spool"), max_entries=2)
    ids = []
    for i in range(4):
        sid = f"snap-{i}-x"
        spool.save(_snap(sid=sid, created_at=float(i)))
        # mtime granularity: make eviction order unambiguous
        os.utime(spool._path(sid), (i, i))
        ids.append(sid)
    names = sorted(os.listdir(spool.directory))
    assert len(names) == 2
    assert f"{ids[0]}.json" not in names and f"{ids[1]}.json" not in names
    assert spool.list()[0]["snapshot_id"] == ids[3]  # newest first


def test_spool_fingerprint_refusal(tmp_path):
    spool = SnapshotSpool(str(tmp_path / "spool"), max_entries=2,
                          fingerprint="fp-engine")
    snap = _snap(config_fingerprint="fp-other")
    with pytest.raises(SnapshotMismatch, match="fingerprint"):
        spool.check_fingerprint(snap)
    # an unstamped snapshot (or an unfingerprinted spool) passes: old
    # documents must not brick a restore
    spool.check_fingerprint(_snap(config_fingerprint=None))
    SnapshotSpool(str(tmp_path / "s2")).check_fingerprint(snap)


def test_preempt_frame_carries_snapshot_id_for_the_router():
    """Cross-layer contract: the server's PREEMPTED terminator frame
    must advertise the snapshot id in exactly the shape the router's
    bridge parses back out."""
    from generativeaiexamples_tpu.router.app import (
        _frame_finish,
        _frame_snapshot_id,
        _parse_frame,
    )
    from generativeaiexamples_tpu.server.api import _preempt_frame
    from generativeaiexamples_tpu.utils.resilience import RequestPreempted

    frame = _preempt_frame(
        "resp-1", RequestPreempted("drained", snapshot_id="snap-9-ff")
    )
    doc = _parse_frame(frame.encode())
    assert doc is not None
    assert _frame_finish(doc) == "PREEMPTED"
    assert _frame_snapshot_id(doc) == "snap-9-ff"
    # replay-only preemption: empty id on the wire
    doc = _parse_frame(
        _preempt_frame("resp-2", RequestPreempted("drained")).encode()
    )
    assert _frame_snapshot_id(doc) == ""


# --------------------------------------------------------------------------- #
# kv_dtype geometry: cross-dtype restores refuse


class _GeoEngine:
    """Just enough engine surface for check_geometry."""

    def __init__(self, kv_quant, kv_packed):
        from types import SimpleNamespace

        self._kv_quant = kv_quant
        self._kv_packed = kv_packed
        self.engine_config = SimpleNamespace(page_size=8)
        self.model_config = SimpleNamespace(
            num_layers=2, num_kv_heads=2, head_dim=16
        )


def _geo(**over):
    geo = {
        "page_size": 8, "pages": 1, "quantized": True, "kv_dtype": "int8",
        "num_layers": 2, "num_kv_heads": 2, "head_dim": 16,
    }
    geo.update(over)
    drop = [k for k, v in geo.items() if v is _ABSENT]
    for k in drop:
        del geo[k]
    return geo


_ABSENT = object()


def test_check_geometry_kv_dtype_matrix():
    from generativeaiexamples_tpu.engine.request_snapshot import (
        SnapshotMismatch, check_geometry)

    int8_eng = _GeoEngine(kv_quant=True, kv_packed=False)
    int4_eng = _GeoEngine(kv_quant=True, kv_packed=True)
    snap8 = _snap(kv={"layers": []}, geometry=_geo(kv_dtype="int8"))
    snap4 = _snap(kv={"layers": []}, geometry=_geo(kv_dtype="int4"))
    check_geometry(int8_eng, snap8)  # matching dtypes restore
    check_geometry(int4_eng, snap4)
    # int4 nibbles are not int8 bytes — both cross directions refuse
    with pytest.raises(SnapshotMismatch, match="kv_dtype"):
        check_geometry(int8_eng, snap4)
    with pytest.raises(SnapshotMismatch, match="kv_dtype"):
        check_geometry(int4_eng, snap8)


def test_check_geometry_legacy_snapshot_back_compat():
    """Pre-kv_dtype snapshots (no key) stay restorable on bf16/int8
    engines — the quantized flag already disambiguates those — but an
    int4 engine must refuse them."""
    from generativeaiexamples_tpu.engine.request_snapshot import (
        SnapshotMismatch, check_geometry)

    legacy = _snap(kv={"layers": []}, geometry=_geo(kv_dtype=_ABSENT))
    check_geometry(_GeoEngine(kv_quant=True, kv_packed=False), legacy)
    with pytest.raises(SnapshotMismatch, match="kv_dtype"):
        check_geometry(_GeoEngine(kv_quant=True, kv_packed=True), legacy)
