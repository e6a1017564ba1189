#!/usr/bin/env python3
"""Rehearsal 3 for a configuration of the Mistral adapter: compile its decode program and its
longest chunked-prefill (extend) rung for a DESCRIBED v5e chip, without
a chip, and print ``memory_analysis()``. A compile, never a run.

    JAX_PLATFORMS=cpu python3 perfbench/tools/rehearse_compile.py perfbench/configs/<name>.json

The programs are the model functions the engine jits
(``llama.decode_layers_paged`` in a scan of ``decode_block`` steps,
``llama.extend_layers_paged`` at ``prefill_wave_tokens / prefill_chunk``
rows against the full window) on shapes built from the configuration
file; sampling is replaced by an argmax, which adds no memory to speak of.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from generativeaiexamples_tpu.models import llama  # noqa: E402
from perfbench.arch.mistral import llama_config  # noqa: E402


def main(path: str) -> None:
    cfg = json.load(open(path, encoding="utf-8"))
    env, eng = cfg["server_env"], cfg["engine"]
    mc = llama_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)  # noqa: E731
    pad = lambda n, m: -(-n // m) * m  # noqa: E731
    pack = lambda k, f: {"q": S((pad(k, 128), pad(f, 512)), jnp.int8), "scale": S((1, f), jnp.float32)}  # noqa: E731
    h, m, q, kv = mc.hidden_size, mc.intermediate_size, mc.q_dim, mc.kv_dim
    layer = {"attn_norm": S((h,), jnp.bfloat16), "mlp_norm": S((h,), jnp.bfloat16),
             "wqkv": pack(h, q + 2 * kv), "wo": pack(q, h), "w_gateup": pack(h, 2 * m), "w_down": pack(m, h)}
    params = {"embed": S((mc.vocab_size, h), jnp.bfloat16), "layers": [layer] * mc.num_layers,
              "final_norm": S((h,), jnp.bfloat16), "lm_head": pack(h, mc.vocab_size)}
    page, pages = eng["page_size"], eng["kv_pool_pages"] + 1
    B, seq, C = eng["max_batch_size"], eng["max_seq_len"], eng["prefill_chunk"]
    cache = [{"k": S((pages, page, mc.num_kv_heads, mc.head_dim), jnp.int8),
              "v": S((pages, page, mc.num_kv_heads, mc.head_dim), jnp.int8),
              "ks": S((pages, page, mc.num_kv_heads), jnp.float32),
              "vs": S((pages, page, mc.num_kv_heads), jnp.float32)}] * mc.num_layers
    tables = S((B, seq // page), jnp.int32)
    block = eng["decode_block"]

    def decode(params, caches, tokens, positions, live, tables):
        def body(carry, _):
            tokens, positions, caches = carry
            logits, caches = llama.decode_layers_paged(
                params, mc, tokens, positions, live, tables, caches, window=seq,
                page_size=page, quant_kernel=True, page_kernel="compiled")
            return (jnp.argmax(logits, -1).astype(jnp.int32), positions + 1, caches), tokens
        (tokens, positions, caches), slab = jax.lax.scan(body, (tokens, positions, caches), None, length=block)
        return tokens, positions, caches, slab

    rows = max(1, int(env.get("APP_ENGINE_PREFILLWAVETOKENS", 16384)) // C)

    def extend(params, caches, tokens, offsets, valid, slots, tables):
        return llama.extend_layers_paged(params, mc, tokens, offsets, valid, slots, tables, caches,
                                         seq, page, quant_kernel=True)

    i32 = jnp.int32
    jobs = {
        f"decode rows={B} block={block} window={seq}": (
            jax.jit(decode, donate_argnums=(1,)),
            (params, cache, S((B,), i32), S((B,), i32), S((B,), jnp.bool_), tables)),
        f"extend rows={rows} chunk={C} window={seq}": (
            jax.jit(extend, donate_argnums=(1,)),
            (params, cache, S((rows, C), i32), S((rows,), i32), S((rows,), i32), S((rows,), i32), tables)),
    }
    for name, (fn, args) in jobs.items():
        t0 = time.time()
        compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, output {ma.output_size_in_bytes / 1e9:.2f} GB, "
              f"aliased {ma.alias_size_in_bytes / 1e9:.2f} GB; tpu_custom_call x{text.count('tpu_custom_call')}",
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
