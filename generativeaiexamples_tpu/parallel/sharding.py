"""GSPMD sharding rules for the Llama parameter/cache pytrees.

Tensor parallelism the XLA way: annotate every leaf with a
``NamedSharding`` over the mesh and let the compiler insert the ICI
collectives (allreduce after the row-parallel ``wo``/``w_down`` matmuls,
allgather where layouts change) — replacing the NCCL allreduce the
reference inherits from TRT-LLM/Megatron (SURVEY §2.6).

Megatron-style layout on the ``model`` axis:
- column-parallel: ``wq``/``wk``/``wv``/``w_gate``/``w_up`` shard their
  output feature dim;
- row-parallel: ``wo``/``w_down`` shard their input feature dim;
- ``embed``/``lm_head`` shard the vocab dim; norms are replicated;
- KV cache shards heads on ``model`` and batch on ``data``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from generativeaiexamples_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def param_specs() -> Dict[str, Any]:
    """PartitionSpec pytree matching models/llama.py's param pytree."""
    return {
        "embed": P(MODEL_AXIS, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, MODEL_AXIS),
            "wk": P(None, None, MODEL_AXIS),
            "wv": P(None, None, MODEL_AXIS),
            # int8-fused serving layouts (ops/quant.py): GSPMD keeps the
            # global-view semantics of the later Q|K|V (gate|up) split
            # correct under any sharding of the fused axis (at worst extra
            # collectives; TP int8 runs the XLA dequant path anyway).
            "wqkv": P(None, None, MODEL_AXIS),
            "w_gateup": P(None, None, MODEL_AXIS),
            "wo": P(None, MODEL_AXIS, None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, MODEL_AXIS),
            "w_up": P(None, None, MODEL_AXIS),
            "w_down": P(None, MODEL_AXIS, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, MODEL_AXIS),  # packed: handled by _prune_to
    }


def activation_spec(seq_sharded: bool = False) -> P:
    """[B, T, D] activations: batch on data, optionally sequence on seq."""
    return P(DATA_AXIS, SEQ_AXIS if seq_sharded else None, None)


def token_spec(seq_sharded: bool = False) -> P:
    return P(DATA_AXIS, SEQ_AXIS if seq_sharded else None)


def _int8_pack_specs(spec: P) -> Dict[str, P]:
    """Specs for an int8 pack {"q": [..., K_pad, F_pad], "scale":
    [..., 1, F]}: q shards like the dense matrix; the per-output-channel
    scale follows the output (last) axis only. Single rule site for the
    stacked (_prune_to) and layered (shard_params_layered) layouts."""
    return {"q": spec, "scale": P(*([None] * (len(spec) - 1)), spec[-1])}


def _prune_to(tree: Dict[str, Any], like: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for key, val in like.items():
        spec = tree[key]
        if isinstance(val, dict) and isinstance(spec, P):
            out[key] = _int8_pack_specs(spec)
        elif isinstance(val, dict):
            out[key] = _prune_to(spec, val)
        else:
            out[key] = spec
    return out


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Device-put a param pytree according to param_specs()."""
    specs = _prune_to(param_specs(), params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


# ------------------------------------------------------------------ //
# Layered (per-layer pytree) serving layout under TP — the unrolled
# engine path (models/llama.py consume_split_params_layers /
# init_kv_cache_layers) sharded the same Megatron way as the stacked
# tree, minus the leading L axis.


def _drop_lead(spec: P) -> P:
    return P(*spec[1:])


def layer_param_specs() -> Dict[str, Any]:
    """Per-layer specs: param_specs()['layers'] with the L axis dropped."""
    return {k: _drop_lead(s) for k, s in param_specs()["layers"].items()}


def shard_params_layered(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Shard a split (per-layer-list) param tree over the mesh.

    Slicing a GSPMD-sharded stacked array already yields sharded
    per-layer views, but the inferred output sharding is XLA's choice;
    this re-puts every leaf with the explicit Megatron spec so the
    layout is deterministic regardless of how the tree was built.
    """
    lspecs = layer_param_specs()

    def put(x, spec):
        if isinstance(x, dict):  # int8 pack {"q","scale"}
            packs = _int8_pack_specs(spec)
            return {
                k: jax.device_put(v, NamedSharding(mesh, packs[k]))
                for k, v in x.items()
            }
        return jax.device_put(x, NamedSharding(mesh, spec))

    out = {
        "embed": put(params["embed"], param_specs()["embed"]),
        "final_norm": jax.device_put(
            params["final_norm"], NamedSharding(mesh, param_specs()["final_norm"])
        ),
        "layers": [
            {k: put(v, lspecs[k]) for k, v in layer.items()}
            for layer in params["layers"]
        ],
    }
    if "lm_head" in params:
        out["lm_head"] = put(params["lm_head"], param_specs()["lm_head"])
    return out


def kv_cache_layer_specs(quantized: bool) -> Dict[str, P]:
    """One layer's leaf specs of the resident DRAFT model's private
    cache (engine/spec_draft.py; init_kv_cache_layers layouts): bf16
    [B, S, Hkv, Dh]; int8 head-major [B, Hkv, S, Dh] with
    [B, Hkv, 1, S] scales. KV heads ride the model axis, slots the
    data axis, as the target's pool shards its heads — so draft
    dispatches ride the same mesh collectives as the target's."""
    if quantized:
        qspec = P(DATA_AXIS, MODEL_AXIS, None, None)
        return {"k": qspec, "v": qspec, "ks": qspec, "vs": qspec}
    spec = P(DATA_AXIS, None, MODEL_AXIS, None)
    return {"k": spec, "v": spec}


def shard_draft_kv_cache(caches, mesh: Mesh, quantized: bool):
    """Device-put the draft model's per-layer caches with
    :func:`kv_cache_layer_specs`."""
    specs = kv_cache_layer_specs(quantized)
    return [
        {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in layer.items()
        }
        for layer in caches
    ]


def kv_pool_specs(quantized: bool) -> Dict[str, P]:
    """One layer's PAGE-POOL leaf specs (init_kv_pool layouts):
    [P, page, Hkv, Dh] token-major, scales [P, page, Hkv] (the engine
    builds a pool it will shard with ``head_sharded=True``, so its scale
    planes keep the head dimension these specs shard). KV heads ride
    the model axis (the per-page gather is position-only, so every shard
    gathers its own heads' rows); pages are replicated over data —
    any slot's table may reference any page."""
    if quantized:
        return {
            "k": P(None, None, MODEL_AXIS, None),
            "v": P(None, None, MODEL_AXIS, None),
            "ks": P(None, None, MODEL_AXIS),
            "vs": P(None, None, MODEL_AXIS),
        }
    spec = P(None, None, MODEL_AXIS, None)
    return {"k": spec, "v": spec}


def shard_kv_pool(pools, mesh: Mesh, quantized: bool):
    specs = kv_pool_specs(quantized)
    return [
        {
            k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in layer.items()
        }
        for layer in pools
    ]


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
