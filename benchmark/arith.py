"""The yardstick's arithmetic: percentiles, spreads, and the operations and
bytes a step needs, worked out from shapes. Nothing here imports the program.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of the raw samples, by linear
    interpolation between order statistics."""
    if not len(values):
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(values, float), q))


def median(values) -> float:
    return percentile(values, 50.0)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind`; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json ({sorted(table)})")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    """Matmul weights of one decoder layer: q, k, v, o and the SwiGLU MLP."""
    h, f, dh = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return h * dh * (2 * nh + 2 * nkv) + 3 * h * f


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs one trained token needs, forward + backward, nothing
    recomputed: 6 per matmul weight (layers and the head; the embedding
    lookup is no matmul) and causal attention's two [S, S] products at half
    their square: 2 * 2 * dh * nh * S/2 forward, three times that with the
    backward pass."""
    layers = m["num_hidden_layers"]
    matmul = layers * layer_matmul_params(m) \
        + m["hidden_size"] * m["vocab_size"]
    attn_fwd = layers * 2 * 2 * m["head_dim"] * m["num_attention_heads"] \
        * seq / 2
    return 6.0 * matmul + 3.0 * attn_fwd


def decode_step_bytes(m: dict, live_kv_tokens: float,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step (one token for every slot) has to read: every
    layer's matmul weights and norm scales, the head (the embedding is a
    lookup of a few rows), and the K and V of every live cached token."""
    h = m["hidden_size"]
    layers = m["num_hidden_layers"]
    weights = layers * (layer_matmul_params(m) + 2 * h) \
        + h * m["vocab_size"] + h
    kv = 2 * layers * m["num_key_value_heads"] * m["head_dim"] \
        * live_kv_tokens
    return weights * weight_bytes + kv * kv_bytes


def kv_bytes_per_token(m: dict, kv_bytes: int = 2) -> int:
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"] * kv_bytes


def serve_weight_bytes(m: dict, weight_bytes: int = 2) -> int:
    h = m["hidden_size"]
    tied = 1 if m.get("tie_word_embeddings") else 2
    return weight_bytes * (
        m["num_hidden_layers"] * (layer_matmul_params(m) + 2 * h)
        + tied * h * m["vocab_size"] + h)
