"""Instruction bundles of the compiled page kernel, read WITHOUT a chip.

The TPU compiler installed here compiles for a DESCRIBED device
(``v5e:2x2``, as tests/test_chip_compile.py does) and, asked to, writes
every pass of its low-level scheduler as text. The last pass of the
Pallas kernel (``*-paged_attention*-final_bundles.txt``) is the VLIW
program the chip runs: one line a bundle, regions marked where control
can enter. A page's arithmetic is one straight-line region, so its
bundle count is a lower bound of its cycles, and at two pages a grid
step it PREDICTED the chip's reading (PERF.md §6, PR 47: 422 -> 378
bundles a page, -10.4 %; the chip read -10.7 %). What it cannot see is
waiting: a rotate or a lane reduce whose result is needed at once stalls
the bundle that pops it (~50 cycles), so a change that trades vector
operations for dependent rotates can count fewer bundles and run slower.
Count here first, then measure on the chip.

    JAX_PLATFORMS=cpu python -m tools.kernel_bundles --heads 32 8 --kv int8 --pages-a-step 2
    JAX_PLATFORMS=cpu python -m tools.kernel_bundles --heads 64 8 --kv bf16 --head-major --file other/page_attention.py
    JAX_PLATFORMS=cpu python -m tools.kernel_bundles --selected-chunk 512   # ops/selected_chunk_read.py (PR 53)
    JAX_PLATFORMS=cpu python -m tools.kernel_bundles --eva-chunk 512        # ops/eva_read.py eva_chunk_read (PR 58)

Prints the kernel's total bundles, the size of every region between
control targets (the largest is the page arithmetic; at N pages a step
it holds all N) and the operations of that region by name. The compile
runs in a CHILD process: with the dump on, this libtpu aborts after the
last pass is written (its HTML memory report wants a template that is
not installed), which costs nothing that is read here. Only one process
may load libtpu at a time unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.
"""
from __future__ import annotations

import argparse
import collections
import glob
import importlib.util
import os
import re
import subprocess
import sys
import tempfile


def _parse() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, nargs=2, default=(32, 8), metavar=("HQ", "HKV"))
    ap.add_argument("--kv", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--head-major", action="store_true")
    ap.add_argument("--query-len", type=int, default=1)
    ap.add_argument("--pages-a-step", type=int, default=0, help="0: the kernel's own rule")
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--file", default=None, help="another page_attention.py to compile (a parent's copy)")
    ap.add_argument("--latent-chunk", type=int, default=0, metavar="T",
                    help="compile ops/latent_attention.py latent_chunk_read at Kimi-K2.5's widths and a chunk of T "
                         "queries instead (one row, 192 pages); --heads-a-step 0: the kernel's own rule")
    ap.add_argument("--heads-a-step", type=int, default=0)
    ap.add_argument("--latent-decode", type=int, default=0, metavar="H",
                    help="compile ops/latent_attention.py dense_latent_attention instead, H heads over rows of 640 "
                         "columns whose first 512 are the value (Kimi-K2.5's and GigaChat3.5's: 32 rows x 192 pages); "
                         "--pages-a-step 0: the kernel's own rule")
    ap.add_argument("--bias", action="store_true",
                    help="with --latent-decode: latent_attention, rows of 512 columns and a bias (GLM-5.3-Flash's: "
                         "64 rows x 64 pages)")
    ap.add_argument("--selected-chunk", type=int, default=0, metavar="T",
                    help="compile ops/selected_chunk_read.py instead, at MiniMax-M3's widths (64/4 heads of 128, one "
                         "row, 288 pages, 19 places a query) and a chunk of T queries")
    ap.add_argument("--eva-chunk", type=int, default=0, metavar="T",
                    help="compile ops/eva_read.py eva_chunk_read instead, at EvaByte's widths (32 heads of 128, 24 "
                         "buffers of 2,048 rows, 3,073 pages of 8 summary rows, one row, a table of 160 pages) and a "
                         "chunk of T queries")
    ap.add_argument("--keep", default=None, help="directory to keep the dump in")
    ap.add_argument("--compile-into", default=None, help=argparse.SUPPRESS)  # the child's job
    return ap.parse_args()


def _compile(args: argparse.Namespace, dump: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true --xla_mosaic_dump_to={dump}"
    )
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    if args.file:
        spec = importlib.util.spec_from_file_location("page_attention_under_test", args.file)
        pa = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = pa
        spec.loader.exec_module(pa)
    else:
        from generativeaiexamples_tpu.ops import page_attention as pa

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if args.latent_chunk:
        from generativeaiexamples_tpu.ops import latent_attention as la

        H, T, dn, dr, Dv, R, row, page, pmax = 64, args.latent_chunk, 128, 64, 128, 512, 640, args.page, 192
        bf16 = jnp.bfloat16
        jax.jit(lambda *a: la.latent_chunk_read(*a, scale=0.1, heads_per_step=args.heads_a_step or None)).lower(
            s((1, H, T, dn), bf16), s((1, H, T, dr), bf16), s((6145, page, row), bf16), s((1, pmax), jnp.int32),
            s((1, T), jnp.int32), s((1,), jnp.int32), s((H, dn, R), bf16), s((H, R, Dv), bf16),
        ).compile()
        return

    if args.selected_chunk:
        from generativeaiexamples_tpu.ops import selected_chunk_read as scr

        T, Hq, Hk, Dh, page, pmax, P, K, bf16 = args.selected_chunk, 64, 4, 128, args.page, 288, 3457, 19, jnp.bfloat16

        def read(q, k, v, tables, pos, n_tokens, pages, valid):
            work = scr.chunk_work_list(tables, pos, n_tokens, page, P)
            return scr.selected_chunk_read(q, k, v, pos, pages, valid, work, scr.chunk_live_steps(work, pages, valid)[0])

        jax.jit(read).lower(
            s((1, T, Hq, Dh), bf16), s((P, Hk, page, Dh), bf16), s((P, Hk, page, Dh), bf16), s((1, pmax), jnp.int32),
            s((1, T), jnp.int32), s((1,), jnp.int32), s((1, T, Hk, K), jnp.int32), s((1, T, Hk, K), jnp.bool_),
        ).compile()
        return

    if args.eva_chunk:
        from generativeaiexamples_tpu.ops import eva_read

        T, H, Dh, W, slots, P, pmax, bf16 = args.eva_chunk, 32, 128, 2048, 24, 3073, 160, jnp.bfloat16

        def read(q, wk, wv, sk, sv, tables, slot, offsets, valid):
            work = eva_read.chunk_work_list(tables, slot, offsets, valid, T, W, args.page, slots, P)
            return eva_read.eva_chunk_read(q, wk, wv, sk, sv, work, num_heads=H)

        jax.jit(read).lower(
            s((1, T, H * Dh), bf16), s((slots, W, H * Dh), bf16), s((slots, W, H * Dh), bf16),
            s((P, 8, H * Dh // 2), jnp.uint32), s((P, 8, H * Dh // 2), jnp.uint32), s((slots, pmax), jnp.int32),
            s((1,), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32),
        ).compile()
        return

    if args.latent_decode:
        from generativeaiexamples_tpu.ops import latent_attention as la

        H, page, bf16 = args.latent_decode, args.page, jnp.bfloat16
        B, pmax, W = (64, 64, 512) if args.bias else (32, 192, 640)

        def read(q, pool, tables, pos, *bias):
            work = pa.page_work_list(tables, pos, 1, page, args.pages_a_step) if args.pages_a_step else None
            if bias:
                return la.latent_attention(q, pool, *bias, tables, pos, scale=0.1, work=work)
            return la.dense_latent_attention(q, pool, tables, pos, value_dim=512, scale=0.1, work=work)

        jax.jit(read).lower(
            s((B, H, W), bf16), s((B * pmax + 1, page, W), bf16), s((B, pmax), jnp.int32), s((B,), jnp.int32),
            *([s((B, pmax * page), jnp.float32)] if args.bias else []),
        ).compile()
        return

    hq, hkv = args.heads
    B, pmax, P, page, dh = 64, 32, 577, args.page, args.head_dim
    dtype = jnp.int8 if args.kv == "int8" else jnp.bfloat16
    pool = s((P, hkv, page, dh) if args.head_major else (P, page, hkv, dh), dtype)
    scales = []
    if args.kv == "int8":
        from generativeaiexamples_tpu.models import llama

        scales = [s((P,) + llama.kv_scale_plane_shape(page, hkv), jnp.float32)] * 2

    def read(q, k, v, tables, pos, *sc):
        return pa.paged_attention(
            q, k, v, tables, pos, *sc, head_major=args.head_major, group=args.pages_a_step or None
        )

    jax.jit(read).lower(
        s((B, args.query_len, hq, dh), jnp.bfloat16), pool, pool,
        s((B, pmax), jnp.int32), s((B,), jnp.int32), *scales,
    ).compile()


def main() -> int:
    args = _parse()
    if args.compile_into:
        _compile(args, args.compile_into)
        return 0
    kernel = ("latent_chunk_read" if args.latent_chunk else "selected_chunk_read" if args.selected_chunk
              else "eva_chunk_read" if args.eva_chunk else "latent_attention" if args.latent_decode else "paged_attention")
    if not args.keep:  # ~1,700 files of passes: read, then thrown away
        with tempfile.TemporaryDirectory(prefix="kernel_bundles_") as scratch:
            return _report(scratch, False, kernel)
    os.makedirs(args.keep, exist_ok=True)
    return _report(args.keep, True, kernel)


def _report(dump: str, keep: bool, kernel: str) -> int:
    child = subprocess.run(
        [sys.executable, "-m", "tools.kernel_bundles", *sys.argv[1:], "--compile-into", dump],
        capture_output=True, text=True,
    )
    found = sorted(glob.glob(os.path.join(dump, f"*{kernel}*final_bundles.txt")))
    found = [f for f in found if "schedule-analysis" not in f]
    if not found:
        print(child.stderr[-2000:], file=sys.stderr)
        print(f"no final_bundles file of the kernel under {dump}", file=sys.stderr)
        return 1
    lines = [l for l in open(found[-1]) if re.match(r"\s*(0x[0-9a-f]+|\d+)\s", l)]
    marks = [i for i, l in enumerate(lines) if re.match(r"\s*\S+\s+(LH|LB|LE|PB|PF|CT):", l)]
    edges = [0] + marks + [len(lines)]
    regions = [(b - a, a, b) for a, b in zip(edges, edges[1:]) if b > a]
    print(f"kernel: {len(lines)} bundles; regions between control targets: {[r[0] for r in regions]}")
    size, a, b = max(regions)
    ops = collections.Counter(
        re.sub(r"\.(xlu|mxu)\d|\.msr[ab]", "", op)
        for l in lines[a:b] for op in re.findall(r"= (v[a-z0-9._]+)", l)
    )
    print(f"largest region: {size} bundles, {sum(ops.values())} vector operations:")
    print("  " + "  ".join(f"{k}:{v}" for k, v in ops.most_common(40)))
    if keep:
        print(f"dump kept in {dump} ({found[-1]})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
