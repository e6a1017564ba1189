#!/usr/bin/env python3
"""Rehearsal 3 for any configuration of the benchmark: compile its decode
program and its widest chunked-prefill (extend) dispatch for a DESCRIBED
v5e chip, without a chip, and print ``memory_analysis()``. A compile,
never a run.

    JAX_PLATFORMS=cpu python3 perfbench/tools/rehearse_compile.py perfbench/configs/<name>.json

The model comes in as it does in a run (``perfbench/launcher.py``): the
configuration file names its adapter, the adapter's ``register`` makes
the program's registry resolve the configuration's name, and the
programs are the FAMILY's walks (``models/registry.py``: ``decode_paged``
in a scan of ``decode_block`` steps, ``extend_paged`` at
``prefill_wave_tokens / prefill_chunk`` rows, one row for a fixed-state
family, against the full window) on shapes taken from the family's own
``init_params`` / ``place_params`` / ``init_paged_cache`` under
``jax.eval_shape``, so a new configuration is rehearsed without an edit
here. Sampling is replaced by an argmax, which adds no memory to speak
of. A family that draws its weights with numpy draws them for real
(minutes and the weights' size in host memory at 4-7 B parameters).
"""
import functools
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

from generativeaiexamples_tpu.models import registry  # noqa: E402
from perfbench import arch  # noqa: E402


def main(path: str) -> None:
    cfg = json.load(open(path, encoding="utf-8"))
    env, eng = cfg["server_env"], cfg["engine"]
    paths = json.load(open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8"))["paths"]
    arch.load(cfg, [os.path.join(ROOT, p) for p in paths]).register(cfg)
    family, mc = registry.resolve(cfg["name"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda x: S(x.shape, x.dtype), tree)  # noqa: E731

    dtype = jnp.bfloat16
    quant = env.get("APP_ENGINE_QUANTIZATION", "none")
    if quant in ("int8", "w8a8"):  # the engine draws packed int8 weights for a llama-family model
        from generativeaiexamples_tpu.ops.quant import init_packed_params_int8

        draw = functools.partial(init_packed_params_int8, mc, 0, dtype, tp_shards=1)
    else:
        draw = functools.partial(family.init_params, mc, 0, dtype)
    params = on_chip(jax.eval_shape(lambda: family.place_params(draw())))
    page, B, seq, C = eng["page_size"], eng["max_batch_size"], eng["max_seq_len"], eng["prefill_chunk"]
    kv = env.get("APP_ENGINE_KVCACHEDTYPE", "bfloat16")
    cache = on_chip(jax.eval_shape(functools.partial(
        family.init_paged_cache, mc, eng["kv_pool_pages"], page, B, dtype,
        quantized=kv in ("int8", "int4"), packed=kv == "int4")))
    # the kernel paths an engine on ONE TPU device resolves
    kernels = dict(quant_kernel={"int8": True, "w8a8": "w8a8"}.get(quant, False), tp=None,
                   **family.resolve_kernels(mc, "compiled"))
    tables = S((B, seq // page), jnp.int32)
    block = eng["decode_block"]

    def decode(params, caches, tokens, positions, live, tables):
        def body(carry, _):
            tokens, positions, caches = carry
            logits, caches = family.decode_paged(
                params, mc, caches, tokens, positions, live, tables, seq, page,
                page_kernel="compiled", **kernels)
            return (jnp.argmax(logits, -1).astype(jnp.int32), positions + 1, caches), tokens
        (tokens, positions, caches), slab = jax.lax.scan(body, (tokens, positions, caches), None, length=block)
        return tokens, positions, caches, slab

    # a fixed-state family is sent one row a wave whatever the wave's tokens (engine/llm_engine.py _max_wave_rows)
    rows = 1 if family.fixed_state else max(1, int(env.get("APP_ENGINE_PREFILLWAVETOKENS", 16384)) // C)

    def extend(params, caches, tokens, offsets, valid, slots, tables):
        return family.extend_paged(params, mc, caches, tokens, offsets, valid, slots, tables, seq, page,
                                   page_kernel=None, **kernels)

    i32 = jnp.int32
    jobs = {
        f"decode rows={B} block={block} window={seq}": (
            jax.jit(decode, donate_argnums=(1,)),
            (params, cache, S((B,), i32), S((B,), i32), S((B,), jnp.bool_), tables)),
        f"extend rows={rows} chunk={C} window={seq}": (
            jax.jit(extend, donate_argnums=(1,)),
            (params, cache, S((rows, C), i32), S((rows,), i32), S((rows,), i32), S((rows,), i32), tables)),
    }
    print(f"{cfg['name']}: adapter {cfg['adapter']}, family {family.name}", flush=True)
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
