"""Offline AOT pre-compilation of a serving config's executable set.

The reference's NIM containers ship a model cache volume so engines
start serving without a build step (reference:
deploy/compose/docker-compose-nim-ms.yaml:5-6 NIM_CACHE). The TPU
analogue is the persistent XLA compile cache: every serving executable
(prefill-chunk extends, decode windows, finish/sample)
is a pure function of SHAPES, so this tool boots the engine with
random-init weights, runs the full warmup walk, and leaves the compiled
artifacts in ``JAX_COMPILATION_CACHE_DIR`` — after which a real
deployment of the same config reaches serving-ready in seconds instead
of minutes (an 8B bucket compile is ~40 s; an 80-layer 70B-shard bucket
far longer).

Usage (flags mirror the APP_ENGINE_* config fields):

    python -m tools.precompile --model llama3-8b --quantization int8 \
        --kv-cache-dtype int8 --max-batch-size 16 --max-seq-len 4096 \
        --prefill-chunk 512

Run it in the image build / cache-warm job; print timings twice to see
the cold vs warm difference.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="llama3-8b", help="preset name (models/llama.py PRESETS)")
    ap.add_argument("--quantization", default="int8", choices=["none", "int8", "w8a8"])
    ap.add_argument("--kv-cache-dtype", default="int8", choices=["bfloat16", "int8"])
    ap.add_argument("--max-batch-size", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=4096)
    ap.add_argument("--prefill-chunk", type=int, default=512)
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--tensor-parallelism", type=int, default=-1)
    args = ap.parse_args(argv)

    # The one compile-cache rule (utils/jax_env.py): the environment's
    # JAX_COMPILATION_CACHE_DIR where set, <checkout>/.jax_cache otherwise.
    from generativeaiexamples_tpu.utils import jax_env

    args.cache_dir = jax_env.bootstrap()

    from generativeaiexamples_tpu.config import EngineConfig
    from generativeaiexamples_tpu.engine.llm_engine import LLMEngine

    t0 = time.time()
    engine = LLMEngine(
        EngineConfig(
            model_config_name=args.model,
            quantization=args.quantization,
            kv_cache_dtype=args.kv_cache_dtype,
            max_batch_size=args.max_batch_size,
            max_seq_len=args.max_seq_len,
            prefill_chunk=args.prefill_chunk,
            decode_block=args.decode_block,
            tensor_parallelism=args.tensor_parallelism,
        )
    )
    t_boot = time.time() - t0
    try:
        t1 = time.time()
        engine.warmup()
        t_warm = time.time() - t1
    finally:
        engine.shutdown()
    n_entries = len(os.listdir(args.cache_dir))
    print(
        f"precompile {args.model} q={args.quantization} kv={args.kv_cache_dtype} "
        f"bs={args.max_batch_size} seq={args.max_seq_len} chunk={args.prefill_chunk}: "
        f"boot {t_boot:.1f}s + warmup {t_warm:.1f}s; "
        f"{n_entries} cache entries in {args.cache_dir}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
