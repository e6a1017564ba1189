"""Adapters: what the harness has to know about one architecture.

The harness (``run.py``, ``launcher.py``, ``readers.py``,
``reference.py``) names no model. A configuration file names its adapter
— ``"adapter": "perfbench.arch.mistral"`` — and ``load`` imports that
module. A ``model_config`` PR adds a model by adding an adapter file
here (and the configuration, traffic and metric files that name it); it
edits nothing that is there.

An adapter is ONE module with these five names:

``register(cfg) -> None``
    Make the engine able to resolve ``cfg["name"]`` (the value of
    ``APP_ENGINE_MODELCONFIGNAME``). Called once in the server child
    before the server's ``main()``; ``cfg`` is the configuration file.

``engine_prefill_logits(eng, prompts, on_tpu) -> float32 [n, vocab]``
    Last-prompt-position logits of ``prompts`` (lists of token ids)
    from the engine's own prefill forward, with the engine's weights
    and the kernel paths it resolved.

``reference_logits(eng, cfg, sequences, tp, device) -> list of float32 [T, vocab]``
    The plain float32 forward over every position of every sequence, on
    the engine's OWN weights read back as integers or floats (nothing
    else of the program: no kernel, no cache, no batching), in blocks
    — layer by layer — so that it fits beside the engine. ``tp`` is the
    number of shards the engine's packs are laid out for, ``device``
    the host CPU device to compute on.

``TOLERANCE``
    The limit ``reference.compare`` holds both readings to, with the
    readings it was set from in the module's docstring.

``decode_step_floor_s(cfg, peaks, rows, mean_context) -> seconds``
    The least time one decode step of ``rows`` sequences, each with
    ``mean_context`` cached tokens, can take on the chip whose row of
    ``peaks.json`` is ``peaks``: the adapter's own count of bytes and
    operations over the peaks that match what the configuration serves
    in, the larger of the two. ``decode_step_roofline_share`` divides
    it by the measured device time of a step.

A module may hold more: readers of its own, which a per-layer metric
file names as ``"reader": "perfbench.arch.<module>:<function>"`` (the
``(ctx, params)`` signature of ``readers.py``; ``ctx["adapter"]`` is the
configuration's adapter). Importing an adapter must not import jax: the
parent process (``run.py``) imports it too and never touches the chip.
"""
from __future__ import annotations

import importlib
import os
from typing import Any, Dict, Sequence

CONTRACT = ("register", "engine_prefill_logits", "reference_logits", "TOLERANCE", "decode_step_floor_s")


def module_under(name: str, roots: Sequence[str]):
    """Import ``name`` and insist that its file lies under one of
    ``roots`` (the manifest's ``paths``): adapters and readers are the
    yardstick, and stay where a PR that claims a gain cannot change them."""
    module = importlib.import_module(name)
    path = os.path.realpath(getattr(module, "__file__", None) or "")
    if not any(path.startswith(os.path.realpath(r) + os.sep) for r in roots):
        raise ValueError(f"{name} ({path or 'no file'}) is not under the benchmark's paths {list(roots)}")
    return module


def load(cfg: Dict[str, Any], roots: Sequence[str] = ()):
    """The adapter module a configuration file names; with ``roots``,
    only from under them."""
    name = cfg.get("adapter")
    if not name:
        raise ValueError(f"configuration {cfg.get('name')!r} names no \"adapter\" module")
    module = module_under(name, roots) if roots else importlib.import_module(name)
    missing = [n for n in CONTRACT if not hasattr(module, n)]
    if missing:
        raise ValueError(f"adapter {name} lacks {missing} (the contract is in perfbench/arch/__init__.py)")
    return module
