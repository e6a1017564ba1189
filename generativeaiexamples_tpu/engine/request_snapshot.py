"""Live-request checkpoint/restore: the preemption-tolerance substrate.

A replica death used to lose every in-flight request — the exact
failure mode that makes spot/preemptible TPUs unusable for serving.
This module generalizes the P/D handoff record
(engine/scheduler/handoff.py): where a ``KVHandoff`` describes a
request crossing the prefill→decode tier boundary *inside* one engine,
a :class:`RequestSnapshot` describes the same request crossing an
*engine* boundary — emitted tokens, pinned sampling seed, decode
position, prefix hint, spec-proposer context, plus the KV page payload
read back page-granularly from the paged pool. Restoring it on a fresh
engine re-admits through the existing handoff import seam
(``LLMEngine._import_handoff``) and resumes the stream
token-identically to an uninterrupted run (the slow identity suite
pins greedy + seeded-sampled, bf16 + int8 KV, spec on/off): sampling
keys derive from (seed, position) against a constant base key, so a
continuation at position P samples exactly what the dead engine would
have.

Snapshots spool to a bounded on-disk directory (oldest-first eviction,
like the anomaly black box's bundle dir) stamped with run provenance
(utils/provenance.py). Restore REFUSES a snapshot whose config
fingerprint differs from the serving engine's — resuming a bf16
snapshot on an int8 engine would be silent garbage, the same
refuse-to-compare discipline the perf trajectory tooling applies.

Lifecycle (docs/resilience.md "Preemption and drain lifecycle"):

    serving --drain--> draining --checkpoint--> spooled
    spooled --POST /internal/restore--> restored (KV payload upload)
    spooled --replay-from-prompt-----> replayed (no payload / no room)

Import-light at module level (numpy only, no jax): the spool and codec
run on router/CI hosts that never build an engine.
"""
from __future__ import annotations

import base64
import dataclasses
import json
import os
import secrets
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from generativeaiexamples_tpu.utils import get_logger
from generativeaiexamples_tpu.utils import metrics as metrics_mod
from generativeaiexamples_tpu.utils import provenance

logger = get_logger(__name__)

_REG = metrics_mod.get_registry()
_M_PREEMPTED = _REG.counter(
    "genai_engine_preempted_total",
    "Live requests checkpointed off a draining engine, by mode: "
    "mode='snapshot' (KV payload spooled — restorable mid-stream) vs "
    "mode='replay' (no KV to spool — prompt + pinned seed only, the "
    "sibling replays from the prompt).",
    ("mode",),
)
_M_RESTORED = _REG.counter(
    "genai_engine_restored_total",
    "Snapshots re-admitted on this engine, by mode: mode='restore' "
    "(KV payload uploaded, decode resumed at the spooled position) vs "
    "mode='replay' (no payload or no slot/pages — full re-prefill "
    "from the prompt with the pinned seed).",
    ("mode",),
)
_M_RESTORE_LATENCY = _REG.histogram(
    "genai_engine_restore_seconds",
    "Snapshot re-admission latency: restore_snapshot() entry to the "
    "request registered into the decode batch (KV upload included).",
)
_M_SNAPSHOT_BYTES = _REG.counter(
    "genai_engine_snapshot_bytes_total",
    "KV payload bytes captured into request snapshots (what a drain "
    "reads back from the paged pool and spools to disk).",
)

SNAPSHOT_VERSION = 1


def record_preempted(mode: str) -> None:
    """Count one preempted live request (mode 'snapshot' | 'replay')."""
    _M_PREEMPTED.labels(mode=mode).inc()


def record_restored(mode: str, latency_s: Optional[float] = None) -> None:
    """Count one re-admission (mode 'restore' | 'replay'); restore-path
    callers pass the end-to-end re-admission latency."""
    _M_RESTORED.labels(mode=mode).inc()
    if latency_s is not None:
        _M_RESTORE_LATENCY.observe(latency_s)


class SnapshotError(RuntimeError):
    """Base error for snapshot capture/spool/restore failures."""


class SnapshotMismatch(SnapshotError):
    """The snapshot's config fingerprint or KV geometry does not match
    the engine asked to restore it (mapped to HTTP 409)."""


# --------------------------------------------------------------------------- #
# Codec: numpy arrays <-> JSON-safe documents


def require_paged_state(engine, what: str) -> None:
    """A snapshot's payload is a request's K and V pages, layer by
    layer. A model with fixed per-slot state (models/registry.py) has
    more than that, and a restore from pages alone would resume it on
    another tenant's recurrent state; a family whose pools are not such
    pages (a latent row a token) has nothing the payload could carry.
    Refuse to take or restore one, with the engine's own reason."""
    reason = getattr(engine, "_snapshot_refusal", None)
    if reason:
        raise SnapshotError(
            f"{what} refused: model {engine.engine_config.model_config_name!r} {reason}"
        )


def _encode_array(arr: np.ndarray) -> Dict[str, Any]:
    return {
        "dtype": arr.dtype.name,
        "shape": list(arr.shape),
        "data": base64.b64encode(np.ascontiguousarray(arr).tobytes()).decode("ascii"),
    }


def _decode_array(doc: Dict[str, Any]) -> np.ndarray:
    name = doc["dtype"]
    if name == "bfloat16":
        # numpy has no native bf16; ml_dtypes ships with jax and is
        # how jax arrays surface bf16 to the host.
        import ml_dtypes

        dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        dtype = np.dtype(name)
    return np.frombuffer(
        base64.b64decode(doc["data"]), dtype=dtype
    ).reshape(doc["shape"])


def encode_kv_payload(layers: List[Dict[str, np.ndarray]]) -> Dict[str, Any]:
    """Per-layer page gathers (k/v of shape [pages, page_size, Hkv, Dh],
    ks/vs as the pool stores a page's scales: [pages, page_size * Hkv /
    128, 128] or [pages, page_size, Hkv]) -> JSON-safe payload doc."""
    return {
        "layers": [
            {key: _encode_array(arr) for key, arr in layer.items()}
            for layer in layers
        ]
    }


def decode_kv_payload(doc: Dict[str, Any]) -> List[Dict[str, np.ndarray]]:
    return [
        {key: _decode_array(arr) for key, arr in layer.items()}
        for layer in doc["layers"]
    ]


def upload_kv_payload(cache: List[Dict[str, Any]], idx_dev, payload) -> None:
    """Write a decoded payload's pages into the per-layer pools ``cache``
    at pool pages ``idx_dev``, in place of the dict entries (eager ops:
    see ``_apply_restore``). A payload's scale planes may be of the other
    layout than the pool's (a head-sharded engine keeps [page, Hkv], a
    single device [page * Hkv / 128, 128]: models/llama.py
    ``kv_scale_plane_shape``); the two hold a page's scales in the same
    flat order, so a reshape to the pool's page restores either."""
    import jax.numpy as jnp

    for layer, pages in zip(cache, payload):
        for key, arr in pages.items():
            layer[key] = layer[key].at[idx_dev].set(
                jnp.asarray(arr).reshape(arr.shape[:1] + layer[key].shape[1:])
            )


def params_doc(params: Any) -> Dict[str, Any]:
    """SamplingParams -> plain dict (stop tuple becomes a list)."""
    return {
        "temperature": params.temperature,
        "top_p": params.top_p,
        "max_tokens": params.max_tokens,
        "stop": list(params.stop),
        "seed": params.seed,
        "prefix_hint": params.prefix_hint,
        "spec_decode": params.spec_decode,
    }


@dataclasses.dataclass
class RequestSnapshot:
    """One preempted request, engine-portable.

    ``position`` is the request's next absolute decode position: KV
    rows [0, position) are live (prompt + all-but-last emitted token),
    ``emitted[-1]`` is the next decode input (its KV row is written by
    the first restored decode step — the engine's standing invariant).
    ``kv`` is the page-granular pool payload covering those rows, or
    None for a replay-only snapshot (request never admitted). ``sampling_seed`` pins the device RNG stream:
    sampling keys derive from (seed, position), so the continuation
    is token-identical for sampled requests too.
    """

    snapshot_id: str
    rid: int
    prompt_ids: List[int]
    emitted: List[int]
    position: int
    sampling_seed: int
    params: Dict[str, Any]
    geometry: Optional[Dict[str, Any]] = None
    kv: Optional[Dict[str, Any]] = None
    config_fingerprint: Optional[str] = None
    created_at: float = 0.0

    @property
    def restorable(self) -> bool:
        """Whether a KV payload travels with this snapshot (restore
        path) vs prompt-only (replay path)."""
        return self.kv is not None

    def to_doc(self) -> Dict[str, Any]:
        return {
            "version": SNAPSHOT_VERSION,
            "snapshot_id": self.snapshot_id,
            "rid": self.rid,
            "prompt_ids": list(self.prompt_ids),
            "emitted": list(self.emitted),
            "position": self.position,
            "sampling_seed": self.sampling_seed,
            "params": dict(self.params),
            "geometry": dict(self.geometry) if self.geometry else None,
            "kv": self.kv,
            "config_fingerprint": self.config_fingerprint,
            "created_at": self.created_at,
            "provenance": {
                "git_sha": provenance.git_sha(),
                "git_dirty": provenance.git_dirty(),
            },
        }

    @classmethod
    def from_doc(cls, doc: Dict[str, Any]) -> "RequestSnapshot":
        if doc.get("version") != SNAPSHOT_VERSION:
            raise SnapshotMismatch(
                f"snapshot version {doc.get('version')!r} is not "
                f"{SNAPSHOT_VERSION} — refusing to restore"
            )
        return cls(
            snapshot_id=doc["snapshot_id"],
            rid=int(doc["rid"]),
            prompt_ids=[int(t) for t in doc["prompt_ids"]],
            emitted=[int(t) for t in doc["emitted"]],
            position=int(doc["position"]),
            sampling_seed=int(doc["sampling_seed"]),
            params=dict(doc["params"]),
            geometry=doc.get("geometry"),
            kv=doc.get("kv"),
            config_fingerprint=doc.get("config_fingerprint"),
            created_at=float(doc.get("created_at") or 0.0),
        )

    def sampling_params(self):
        """Rebuild SamplingParams with the seed PINNED to the spooled
        effective seed — an unseeded request's random draw at original
        submit time must not be re-drawn, or the sampled continuation
        diverges."""
        from generativeaiexamples_tpu.engine.llm_engine import SamplingParams

        p = self.params
        return SamplingParams(
            temperature=float(p.get("temperature", 0.2)),
            top_p=float(p.get("top_p", 0.7)),
            max_tokens=int(p.get("max_tokens", 1024)),
            stop=tuple(p.get("stop") or ()),
            seed=self.sampling_seed,
            prefix_hint=p.get("prefix_hint"),
            spec_decode=p.get("spec_decode"),
        )


# --------------------------------------------------------------------------- #
# Engine-side capture


def capture(engine, req, position: int, pages: Tuple[int, ...]) -> RequestSnapshot:
    """Serialize one quiesced live request on ``engine`` into a
    RequestSnapshot, reading its KV rows [0, position) back from the
    paged pool page-granularly.

    MUST run with the engine's dispatch loop parked and its prefill
    tier quiesced (the drain workflow's contract): the page gathers
    read the live cache chain, and a concurrent donated-buffer
    dispatch would be a use-after-free. Runs on the drain (HTTP)
    thread — never reachable from the dispatch loop, so the blocking
    device readback below is outside the dispatch-readback lint's
    scope by construction."""
    snap_id = f"snap-{req.rid}-{secrets.token_hex(6)}"
    emitted = list(getattr(req, "emitted", ()) or ())
    kv_doc = None
    geometry = None
    if pages and position > 0:
        page = engine.engine_config.page_size
        n_payload = (position + page - 1) // page
        n_payload = min(n_payload, len(pages))
        idx = np.asarray(pages[:n_payload], np.int32)
        import jax.numpy as jnp

        idx_dev = jnp.asarray(idx)
        staged: List[Dict[str, Any]] = []
        with engine._dispatch_lock:
            # Gather enqueue only (new arrays — nothing donated); the
            # host sync happens after the lock drops.
            for layer in engine._cache:
                staged.append({key: buf[idx_dev] for key, buf in layer.items()})
        host_layers = [
            {key: np.asarray(arr) for key, arr in layer.items()}
            for layer in staged
        ]
        nbytes = sum(
            arr.nbytes for layer in host_layers for arr in layer.values()
        )
        _M_SNAPSHOT_BYTES.inc(nbytes)
        kv_doc = encode_kv_payload(host_layers)
        mc = engine.model_config
        geometry = {
            "page_size": page,
            "pages": int(n_payload),
            "quantized": bool(getattr(engine, "_kv_quant", False)),
            # Storage dtype of the pool rows: int4 payloads are packed
            # uint8 bytes whose nibble layout an int8 engine cannot
            # read — restore must refuse a cross-dtype snapshot, not
            # silently dequantize garbage.
            "kv_dtype": _engine_kv_dtype(engine),
            # Which layout the payload's scale planes hold (lane_dense /
            # token_major, models/llama.py kv_scale_plane_shape; None:
            # no scales). A record, not a condition: the two are the
            # same bytes a page and restore into each other.
            "kv_scales": engine._kv_scale_layout(),
            "num_layers": mc.num_layers,
            "num_kv_heads": mc.num_kv_heads,
            "head_dim": mc.head_dim,
        }
    return RequestSnapshot(
        snapshot_id=snap_id,
        rid=req.rid,
        prompt_ids=list(req.prompt_ids),
        emitted=emitted,
        position=int(position),
        sampling_seed=int(req.sampling_seed),
        params=params_doc(req.params),
        geometry=geometry,
        kv=kv_doc,
        created_at=time.time(),
    )


def _engine_kv_dtype(engine) -> str:
    """Storage dtype string of this engine's KV pool rows."""
    if not getattr(engine, "_kv_quant", False):
        return "bfloat16"
    return "int4" if getattr(engine, "_kv_packed", False) else "int8"


def check_geometry(engine, snap: RequestSnapshot) -> None:
    """Refuse a KV payload whose pool geometry does not match this
    engine (fingerprint refusal catches config drift; this catches a
    hand-edited or cross-build snapshot with a matching fingerprint
    but incompatible arrays)."""
    if snap.kv is None:
        return
    geo = snap.geometry or {}
    mc = engine.model_config
    expect = {
        "page_size": engine.engine_config.page_size,
        "quantized": bool(getattr(engine, "_kv_quant", False)),
        "kv_dtype": _engine_kv_dtype(engine),
        "num_layers": mc.num_layers,
        "num_kv_heads": mc.num_kv_heads,
        "head_dim": mc.head_dim,
    }
    for key, want in expect.items():
        got = geo.get(key)
        if key == "kv_dtype" and got is None:
            # Pre-kv_dtype snapshots carried only the quantized flag;
            # that flag (checked above) disambiguates bf16 vs int8, and
            # no such snapshot can hold int4 bytes — so legacy docs
            # remain restorable everywhere EXCEPT an int4 engine, where
            # a missing dtype must refuse (int8 bytes are not nibbles).
            if want != "int4":
                continue
        if got != want:
            raise SnapshotMismatch(
                f"snapshot {snap.snapshot_id} KV geometry mismatch: "
                f"{key} is {got!r}, engine wants {want!r}"
            )


# --------------------------------------------------------------------------- #
# The bounded on-disk spool


class SnapshotSpool:
    """Bounded snapshot directory: one ``<snapshot_id>.json`` per
    preempted request, provenance-stamped, oldest-first eviction past
    ``max_entries`` (the black box's bundle-dir discipline). Restore
    refuses on config-fingerprint mismatch."""

    def __init__(self, directory: str, max_entries: int = 64,
                 fingerprint: Optional[str] = None) -> None:
        self.directory = directory
        self.max_entries = max(1, int(max_entries))
        self.fingerprint = fingerprint

    def _path(self, snapshot_id: str) -> str:
        safe = os.path.basename(snapshot_id)
        return os.path.join(self.directory, f"{safe}.json")

    def save(self, snap: RequestSnapshot) -> str:
        os.makedirs(self.directory, exist_ok=True)
        snap.config_fingerprint = self.fingerprint
        doc = snap.to_doc()
        path = self._path(snap.snapshot_id)
        tmp = f"{path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        os.replace(tmp, path)
        self._evict_old()
        logger.info(
            "spooled snapshot %s (rid %d, position %d, %s)",
            snap.snapshot_id, snap.rid, snap.position,
            "kv payload" if snap.restorable else "replay-only",
        )
        return path

    def load(self, snapshot_id: str) -> RequestSnapshot:
        path = self._path(snapshot_id)
        try:
            with open(path, "r", encoding="utf-8") as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            raise SnapshotError(f"snapshot {snapshot_id!r} not in spool")
        except (OSError, ValueError) as exc:
            raise SnapshotError(
                f"snapshot {snapshot_id!r} unreadable: {exc}"
            ) from exc
        return RequestSnapshot.from_doc(doc)

    def load_doc(self, snapshot_id: str) -> Dict[str, Any]:
        """The raw spool document (the router ships this verbatim to a
        sibling's /internal/restore — no engine needed to relay it)."""
        path = self._path(snapshot_id)
        try:
            with open(path, "r", encoding="utf-8") as fh:
                return json.load(fh)
        except FileNotFoundError:
            raise SnapshotError(f"snapshot {snapshot_id!r} not in spool")
        except (OSError, ValueError) as exc:
            raise SnapshotError(
                f"snapshot {snapshot_id!r} unreadable: {exc}"
            ) from exc

    def check_fingerprint(self, snap: RequestSnapshot) -> None:
        """Config-fingerprint refusal: a snapshot captured under a
        different engine configuration must not resume here."""
        if self.fingerprint is None or snap.config_fingerprint is None:
            return
        if snap.config_fingerprint != self.fingerprint:
            raise SnapshotMismatch(
                f"snapshot {snap.snapshot_id} was captured under config "
                f"fingerprint {snap.config_fingerprint} but this engine "
                f"runs {self.fingerprint} — refusing to restore"
            )

    def list(self) -> List[Dict[str, Any]]:
        """Spool inventory, newest first (the router's restore path
        lists a dead replica's spool through GET /internal/snapshots)."""
        try:
            names = [
                n for n in os.listdir(self.directory) if n.endswith(".json")
            ]
        except OSError:
            return []
        out: List[Dict[str, Any]] = []
        for name in names:
            path = os.path.join(self.directory, name)
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    doc = json.load(fh)
                out.append({
                    "snapshot_id": doc.get("snapshot_id"),
                    "rid": doc.get("rid"),
                    "position": doc.get("position"),
                    "emitted": len(doc.get("emitted") or ()),
                    "restorable": doc.get("kv") is not None,
                    "created_at": doc.get("created_at"),
                    "config_fingerprint": doc.get("config_fingerprint"),
                    "bytes": os.path.getsize(path),
                })
            except (OSError, ValueError):
                continue
        out.sort(key=lambda d: d.get("created_at") or 0.0, reverse=True)
        return out

    def _evict_old(self) -> None:
        try:
            names = [
                n for n in os.listdir(self.directory) if n.endswith(".json")
            ]
        except OSError:
            return
        if len(names) <= self.max_entries:
            return
        paths = [os.path.join(self.directory, n) for n in names]
        paths.sort(key=lambda p: os.path.getmtime(p))
        for path in paths[: len(paths) - self.max_entries]:
            try:
                os.remove(path)
                logger.warning(
                    "snapshot spool over %d entries — evicted %s",
                    self.max_entries, os.path.basename(path),
                )
            except OSError:
                pass
