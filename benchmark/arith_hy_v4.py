"""Operations and bytes of the `hy_v4` block as it is served, worked out from
the configuration's published keys. Nothing here imports the program.

A layer holds gated latent attention (q_a [h, q_rank], q_b [q_rank, H x
qk_head_dim], kv_a [h, kv_rank + rope], kv_b [kv_rank, H x (nope + v)], the
gate and o [h, H x v] each, a sink a head), an indexer on a `full` layer
(wq_b [q_rank, nI x dI], wk [h, dI], weights_proj [h, nI]), two
hyper-connection maps ([hc x h, hc x (hc + 2)] each), and an MLP: `dense` a
SwiGLU of `intermediate_size`, `sparse` a router [h, E] beside the held
experts and the shared one. A decode step reads every weight it multiplies:
every layer's, every HELD expert's (the grouped matmul fetches each held
expert's weight blocks, rows or none), the head in f32; and from the cache the
index keys each `full` layer scores and the latent rows each layer attends, at
the model's own widths (a latent row 576 bf16 values, an index key 128).
"""
from __future__ import annotations

WEIGHT_BYTES = CACHE_BYTES = 2       # bfloat16
HEAD_BYTES = 4                       # enable_lm_head_fp32


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def kinds(m: dict):
    """(indexer kind, mlp kind) of each layer run."""
    n = depth(m)
    return list(zip(m["indexer_types"][:n], m["mlp_layer_types"][:n]))


def full_layers(m: dict) -> int:
    return sum(k == "full" for k, _ in kinds(m))


def routed_layers(m: dict) -> int:
    return sum(k == "sparse" for _, k in kinds(m))


def latent_row_bytes(m: dict) -> int:
    return CACHE_BYTES * (m["kv_lora_rank"] + m["qk_rope_head_dim"])


def index_key_bytes(m: dict) -> int:
    return CACHE_BYTES * m["index_head_dim"]


def attention_params(m: dict, full: bool) -> int:
    h, nh = m["hidden_size"], m["num_attention_heads"]
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    rope, nope, v = (m["qk_rope_head_dim"], m["qk_nope_head_dim"],
                     m["v_head_dim"])
    n = h * qr + qr * nh * (nope + rope) + h * (kr + rope) \
        + kr * nh * (nope + v) + 2 * h * nh * v + qr + kr + nh
    if full:
        n += qr * m["index_n_heads"] * m["index_head_dim"] \
            + h * m["index_head_dim"] + h * m["index_n_heads"] \
            + 2 * m["index_head_dim"]
    return n


def hc_params(m: dict) -> int:
    n = m["hc_mult"]
    return 2 * (n * m["hidden_size"] * n * (n + 2) + 3 + n * (n + 2))


def swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden_size"] * width


def mlp_params(m: dict, kind: str) -> int:
    """What one decode step multiplies of a layer's MLP: the dense SwiGLU,
    or the router, every held expert and the shared expert(s)."""
    if kind == "dense":
        return swiglu_params(m, m["intermediate_size"])
    f = m["moe_intermediate_size"]
    return (m["hidden_size"] + 1) * m["published"]["n_routed_experts"] \
        + len(m["deployment"]["held"]) * swiglu_params(m, f) \
        + swiglu_params(m, f * m["n_shared_experts"])


def decode_weight_bytes(m: dict) -> int:
    """Weight bytes one decode step reads: every layer, the final norm, the
    head in f32 (the embedding is a gather of a row a token)."""
    h = m["hidden_size"]
    layers = sum(attention_params(m, dsa == "full") + hc_params(m)
                 + mlp_params(m, mlp) + 2 * h for dsa, mlp in kinds(m))
    return WEIGHT_BYTES * (layers + h) + HEAD_BYTES * h * m["vocab_size"]


def decode_step_bytes(m: dict, rows: int, scored: float,
                      attended: float) -> float:
    """Bytes one decode step of `rows` slots must read: its weights, the
    index keys its `full` layers score (`scored`: keys a step, summed over
    rows and full layers) and the latent rows its layers attend (`attended`:
    summed over rows and layers), with the absorbed queries in and the
    attention's latent outputs out."""
    return decode_weight_bytes(m) + scored * index_key_bytes(m) \
        + sparse_decode_bytes(m, rows, attended)


def sparse_decode_bytes(m: dict, rows: int, attended: float) -> float:
    """What the decode lane's sparse attention must move in a step: the
    selected latent rows, and a query [H, 576] in and an output [H, 512]
    out a row and layer."""
    nh = m["num_attention_heads"]
    io = rows * depth(m) * nh * CACHE_BYTES * (
        m["kv_lora_rank"] + m["qk_rope_head_dim"] + m["kv_lora_rank"])
    return attended * latent_row_bytes(m) + io


def sparse_attn_flops(m: dict, attended: float) -> float:
    """Operations of latent attention over `attended` (query row, selected
    key) pairs of a layer, summed: scores over 576 values and the weighted
    sum of 512, every head."""
    return 2.0 * attended * m["num_attention_heads"] * (
        2 * m["kv_lora_rank"] + m["qk_rope_head_dim"])


def index_flops(m: dict, scored: float) -> float:
    """Operations of the indexer over `scored` (query row, key) pairs."""
    return 2.0 * scored * m["index_n_heads"] * m["index_head_dim"]


def expert_mm_bytes(m: dict, experts_hit: float, rows: float) -> float:
    """Bytes the three grouped products of one routed layer must move: the
    weights of the held experts hit, and the (token, choice) rows routed to
    them in and out of each product (gate and up read [rows, h] and write
    [rows, f], down the other way round)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return WEIGHT_BYTES * (experts_hit * swiglu_params(m, f)
                           + rows * 3 * (h + f))
