"""Operations and bytes of the `mellum` block as it is served, worked out from
the configuration's published keys. Nothing here imports the program.

A layer holds: q [h, nh*dh], k and v [h, nkv*dh], o [nh*dh, h], a router
[h, E], E experts of three [h, f] / [f, h] matrices, two norm scales. A decode
step reads the attention, router and norm weights of every layer, the head,
the weights of the experts that got at least one row — as counted, never all E
by assumption — and the K and V of the tokens each layer attends: every live
token on a full-attention layer, at most `sliding_window` a sequence on a
sliding-window layer.
"""
from __future__ import annotations

WEIGHT_BYTES = KV_BYTES = 2          # bfloat16


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def layer_counts(m: dict):
    """(full-attention layers, sliding-window layers) among those run."""
    kinds = m["layer_types"][:depth(m)]
    n_window = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - n_window, n_window


def attention_params(m: dict) -> int:
    """q, k, v, o, the router and the two norm scales of one layer."""
    h, dh = m["hidden_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    return h * dh * (2 * nh + 2 * nkv) + h * m["num_experts"] + 2 * h


def expert_params(m: dict) -> int:
    """One expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params(m: dict) -> int:
    return attention_params(m) + m["num_experts"] * expert_params(m)


def serve_weight_bytes(m: dict) -> int:
    """Every weight the deployment holds: the layers, the final norm, the
    embedding and the untied head."""
    h = m["hidden_size"]
    tied = 1 if m.get("tie_word_embeddings") else 2
    return WEIGHT_BYTES * (depth(m) * layer_params(m) + h
                           + tied * h * m["vocab_size"])


def kv_bytes_per_token_layer(m: dict) -> int:
    return 2 * m["num_key_value_heads"] * m["head_dim"] * KV_BYTES


def decode_step_bytes(m: dict, experts_hit: float, tokens_full: float,
                      tokens_window: float) -> float:
    """Bytes one decode step (one token for every slot) has to read.
    `experts_hit`: experts with at least one row, a routed layer, as the
    program counted them; `tokens_full`: cached tokens of the live sequences;
    `tokens_window`: those of them inside a sliding window (min(len, W) a
    sequence)."""
    n_full, n_window = layer_counts(m)
    h = m["hidden_size"]
    weights = depth(m) * (attention_params(m) + experts_hit * expert_params(m)) \
        + h * m["vocab_size"] + h
    kv = kv_bytes_per_token_layer(m) * (n_full * tokens_full
                                        + n_window * tokens_window)
    return weights * WEIGHT_BYTES + kv


def expert_mm_bytes(m: dict, experts_hit: float, rows: float) -> float:
    """Bytes the three grouped products of one routed layer must move: the
    weights of the experts hit, and the (token, choice) rows in and out of
    each product (gate and up read [rows, h] and write [rows, f], down the
    other way round)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return WEIGHT_BYTES * (experts_hit * expert_params(m)
                           + rows * 3 * (h + f))


def window_attn_bytes(m: dict, tokens_window: float) -> float:
    """K and V one sliding-window layer's decode attention must read for a
    step: `tokens_window` = the sum over live sequences of min(len, W)."""
    return kv_bytes_per_token_layer(m) * tokens_window


def window_kv_share(m: dict, pages_full: float, pages_window: float) -> float:
    """KV bytes held — the full layers' pages and the window layers' rings in
    use — over what the same reservations would hold were every layer full."""
    n_full, n_window = layer_counts(m)
    return (pages_full * n_full + pages_window * n_window) \
        / (pages_full * (n_full + n_window))
