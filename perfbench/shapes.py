"""Bytes and operations a step needs, from the configuration's shapes.

Kept with the benchmark so that no PR which claims a gain can change the
count. Every function takes the configuration file's dict (the
published ``config.json`` keys at its top level plus ``engine``).
"""
from __future__ import annotations

from typing import Any, Dict


def layer_weight_elements(cfg: Dict[str, Any]) -> int:
    """Matrix elements of one decoder layer: Q|K|V, O, gate|up, down."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * (q + 2 * kv) + q * h + h * 2 * m + m * h


def decode_weight_bytes(cfg: Dict[str, Any]) -> int:
    """Bytes of weights one decode step streams from HBM: the int8
    matrices of every layer and of the untied head (1 byte an element),
    their float32 per-output-channel scales, and the bf16 norm vectors.
    The embedding table is read one row per sequence and is counted in
    ``decode_step_bytes``."""
    h, m, L, v = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    matrices = L * layer_weight_elements(cfg) + h * v
    scales = 4 * (L * ((q + 2 * kv) + h + 2 * m + h) + v)
    norms = 2 * (2 * L * h + h)
    return matrices + scales + norms


def kv_bytes_per_token(cfg: Dict[str, Any]) -> int:
    """int8 K and V of every layer plus one float32 scale per head,
    layer and K/V for one cached token."""
    L, kvh, d = cfg["num_hidden_layers"], cfg["num_key_value_heads"], cfg["head_dim"]
    return L * 2 * kvh * d + L * 2 * kvh * 4


def decode_step_bytes(cfg: Dict[str, Any], rows: float, live_tokens: float) -> float:
    """HBM bytes one decode step of ``rows`` sequences must move when
    ``live_tokens`` tokens of context are cached in all: the weights
    once, the live KV once, one embedding row and one new KV entry per
    sequence."""
    per_row = 2 * cfg["hidden_size"] + kv_bytes_per_token(cfg)
    return decode_weight_bytes(cfg) + live_tokens * kv_bytes_per_token(cfg) + rows * per_row


def decode_step_flops(cfg: Dict[str, Any], rows: float, live_tokens: float) -> float:
    """Multiply-adds x 2 of one decode step: every matrix once per row,
    and attention's two products over the live context."""
    matrices = cfg["num_hidden_layers"] * layer_weight_elements(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
    attn = 2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * live_tokens
    return 2.0 * (rows * matrices + attn)
