"""Fine-tuning CLI: SFT and LoRA on the sharded Llama decoder.

TPU-native replacement for the reference's fine-tuning story, which is
NeMo/Megatron notebooks executed inside an external `nvcr.io/nvidia/nemo`
container — Gemma/CodeGemma/StarCoder2 LoRA + SFT with
``tensor_model_parallel_size=4`` and `.nemo` checkpoints (reference:
models/Gemma/sft.ipynb, models/StarCoder2/lora.ipynb, models/NeMo/slm/
slm_pretraining_sft.ipynb; SURVEY §2.3). Here the whole loop is in-repo:

    python -m tools.finetune --model debug --data data.jsonl \
        --mode lora --rank 8 --steps 100 --ckpt-dir ckpts/

- data: JSONL with {"prompt", "response"} (loss on response tokens only)
  or {"text"} (loss everywhere);
- parallelism: (data, seq, model) mesh, same GSPMD shardings as serving
  (parallel/sharding.py); TP count set by --tp (-1 = all chips);
- checkpoint/resume: orbax, step-numbered, --resume picks up the latest;
- LoRA: --merge-out writes base+adapter merged weights the engine serves
  with zero adapter overhead.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np


def parse_args(argv: List[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="SFT / LoRA fine-tuning on TPU")
    p.add_argument("--model", default="debug", help="preset name or HF checkpoint dir")
    p.add_argument("--data", required=True, help="JSONL training data")
    p.add_argument("--mode", choices=["sft", "lora"], default="lora")
    p.add_argument("--tokenizer", default=None, help="tokenizer.json path (default: bytes)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--rank", type=int, default=16, help="LoRA rank")
    p.add_argument("--alpha", type=float, default=32.0, help="LoRA alpha")
    p.add_argument(
        "--targets", default="wq,wk,wv,wo", help="comma-separated LoRA target projections"
    )
    p.add_argument("--tp", type=int, default=-1, help="tensor parallelism (-1 = all devices)")
    p.add_argument("--dp", type=int, default=1, help="data parallelism")
    p.add_argument("--sp", type=int, default=1, help="sequence parallelism")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--save-every", type=int, default=50)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--merge-out", default=None, help="write merged LoRA weights here (npz)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def load_examples(path: str) -> List[Dict[str, str]]:
    out = []
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    if not out:
        raise ValueError(f"No examples in {path}")
    return out


def tokenize_examples(
    examples: List[Dict[str, str]], tokenizer, seq_len: int
) -> List[Dict[str, np.ndarray]]:
    """Fixed-length rows: tokens [T] and loss_mask [T] (1.0 on supervised
    positions — response tokens for prompt/response pairs, all for text)."""
    rows = []
    pad = tokenizer.pad_id
    for ex in examples:
        if "text" in ex:
            ids = tokenizer.encode(ex["text"], add_bos=True)
            mask_from = 1  # supervise everything after BOS
        else:
            prompt_ids = tokenizer.encode(ex["prompt"], add_bos=True)
            full_ids = prompt_ids + tokenizer.encode(ex["response"])
            ids, mask_from = full_ids, len(prompt_ids)
        ids = ids[:seq_len]
        mask = np.zeros(seq_len, np.float32)
        mask[min(mask_from, seq_len): len(ids)] = 1.0
        tokens = np.full(seq_len, pad, np.int32)
        tokens[: len(ids)] = ids
        if mask.sum() == 0:
            continue
        rows.append({"tokens": tokens, "loss_mask": mask})
    if not rows:
        raise ValueError("All examples were empty after tokenization")
    return rows


def batches(
    rows: List[Dict[str, np.ndarray]], batch_size: int, seed: int
) -> Iterator[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    while True:
        idx = rng.permutation(len(rows))
        for start in range(0, len(rows) - batch_size + 1, batch_size):
            chosen = [rows[i] for i in idx[start: start + batch_size]]
            yield {
                "tokens": np.stack([r["tokens"] for r in chosen]),
                "loss_mask": np.stack([r["loss_mask"] for r in chosen]),
            }
        if len(rows) < batch_size:  # tiny datasets: sample with replacement
            chosen = [rows[i] for i in rng.integers(0, len(rows), batch_size)]
            yield {
                "tokens": np.stack([r["tokens"] for r in chosen]),
                "loss_mask": np.stack([r["loss_mask"] for r in chosen]),
            }


def main(argv: List[str] | None = None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)

    from generativeaiexamples_tpu.utils import jax_env

    jax_env.bootstrap()
    from generativeaiexamples_tpu.engine.tokenizer import load_tokenizer
    from generativeaiexamples_tpu.models import hf_loader, llama, lora
    from generativeaiexamples_tpu.models.checkpoint import CheckpointManager
    from generativeaiexamples_tpu.models.train import (
        TrainState,
        make_lora_train_step,
        make_optimizer,
        make_train_step,
    )
    from generativeaiexamples_tpu.parallel.mesh import create_mesh
    from generativeaiexamples_tpu.parallel.sharding import shard_params

    tokenizer = load_tokenizer(args.tokenizer)
    rows = tokenize_examples(load_examples(args.data), tokenizer, args.seq_len)
    print(f"dataset: {len(rows)} usable rows", file=sys.stderr)

    if args.model in llama.PRESETS:
        cfg, params_src = llama.PRESETS[args.model], None
    else:
        cfg = hf_loader.config_from_hf(args.model)
        if cfg is None:
            raise SystemExit(f"--model {args.model!r} is neither a preset nor a HF dir")
        params_src = args.model

    mesh = create_mesh(args.tp, args.dp, args.sp)
    optimizer = make_optimizer(learning_rate=args.lr)
    key = jax.random.PRNGKey(args.seed)

    with jax.set_mesh(mesh):
        if params_src:
            base_params = shard_params(hf_loader.load_params(params_src, cfg), mesh)
        else:
            base_params = shard_params(llama.init_params(cfg, key), mesh)

        if args.mode == "lora":
            lora_cfg = lora.LoRAConfig(
                rank=args.rank, alpha=args.alpha,
                targets=tuple(t.strip() for t in args.targets.split(",") if t.strip()),
            )
            trainable = lora.shard_lora_params(
                lora.init_lora_params(cfg, lora_cfg, key), lora_cfg, mesh
            )
            step_fn = jax.jit(make_lora_train_step(cfg, lora_cfg, optimizer, args.sp > 1))
            print(
                f"LoRA r={lora_cfg.rank} targets={lora_cfg.targets}: "
                f"{lora.count_lora_params(trainable):,} trainable / "
                f"{llama.count_params(base_params):,} total",
                file=sys.stderr,
            )
        else:
            trainable = base_params
            step_fn = jax.jit(make_train_step(cfg, optimizer, args.sp > 1))

        state = TrainState(
            params=trainable,
            opt_state=optimizer.init(trainable),
            step=jnp.zeros((), jnp.int32),
        )

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start_step = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            state = ckpt.restore(state)
            start_step = int(state.step)
            print(f"resumed from step {start_step}", file=sys.stderr)

        it = batches(rows, args.batch_size, args.seed)
        t0 = time.time()
        loss = None
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            if args.mode == "lora":
                state, loss = step_fn(state, base_params, batch)
            else:
                state, loss = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
                dt = time.time() - t0
                print(
                    f"step {step + 1}/{args.steps} loss={float(loss):.4f} "
                    f"({(step + 1 - start_step) / dt:.2f} steps/s)",
                    file=sys.stderr,
                )
            if ckpt and (step + 1) % args.save_every == 0:
                ckpt.save(step + 1, state)

        if ckpt:
            ckpt.save(args.steps, state, wait=True)
            ckpt.close()

        if args.mode == "lora" and args.merge_out:
            merged = lora.merge(base_params, state.params, lora_cfg)
            save_merged(args.merge_out, merged)
            print(f"merged weights written to {args.merge_out}", file=sys.stderr)

    if loss is not None:
        print(json.dumps({"final_loss": float(loss), "steps": args.steps}))
    return 0


def save_merged(path: str, params) -> None:
    """Flatten the merged param pytree to an npz the engine can reload."""
    flat = {}

    def walk(prefix: str, node) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}/{k}" if prefix else k, v)
        else:
            flat[prefix] = np.asarray(jax.device_get(node)).astype(np.float32)

    walk("", params)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez(path, **flat)


def load_merged(path: str, dtype=jnp.bfloat16):
    """Inverse of save_merged: npz → nested param pytree."""
    out: Dict = {}
    with np.load(path) as data:
        for name in data.files:
            node = out
            parts = name.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = jnp.asarray(data[name], dtype)
    return out


if __name__ == "__main__":
    raise SystemExit(main())
