"""Operations and bytes of one training step of a `glm4_moe_lite`
configuration, worked out from its published keys and from the rows the
held experts were counted to compute. Nothing here imports the program.
"""
from __future__ import annotations


def attention_params(m: dict) -> int:
    """Matmul weights of one latent-attention block."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
            + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * nh * (m["qk_nope_head_dim"]
                                        + m["v_head_dim"])
            + nh * m["v_head_dim"] * h)


def expert_params(m: dict) -> int:
    """One expert's (or the shared expert's) three SwiGLU matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_width(m: dict) -> int:
    return m.get("published", {}).get("n_routed_experts",
                                      m["n_routed_experts"])


def blocks(m: dict):
    """(dense blocks, expert blocks, MTP modules) that run in a step; each
    module holds one expert block."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    return dense, m["num_hidden_layers"] - dense, \
        m["num_nextn_predict_layers"]


def dense_params_per_token(m: dict) -> int:
    """Matmul weights every token multiplies: attention of every block, the
    dense MLP, each expert block's shared expert and router, each module's
    eh_proj, and the head once for every set of logits. The embedding is a
    lookup; the routed experts are counted by their rows."""
    h = m["hidden_size"]
    dense, expert, mtp = blocks(m)
    expert += mtp
    return ((dense + expert) * attention_params(m)
            + dense * 3 * h * m["intermediate_size"]
            + expert * (m["n_shared_experts"] * expert_params(m)
                        + h * router_width(m))
            + mtp * 2 * h * h
            + (1 + mtp) * h * m["vocab_size"])


def attention_flops_per_token(m: dict, seq: int) -> float:
    """Causal attention's two [S, S] products of every block at half their
    square, forward: q k^T over nope + rope, p v over the value head."""
    dense, expert, mtp = blocks(m)
    widths = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return (dense + expert + mtp) * 2 * widths \
        * m["num_attention_heads"] * seq / 2


def train_flops_per_step(m: dict, batch: int, seq: int,
                         rows_held: float) -> float:
    """Model FLOPs of one step, forward + backward, nothing recomputed: 6 a
    matmul weight a token (or a routed row: `rows_held` is the step's count
    of (token, held expert) assignments over all expert blocks) and three
    times causal attention's forward products."""
    tokens = batch * seq
    return (6.0 * tokens * dense_params_per_token(m)
            + 6.0 * rows_held * expert_params(m)
            + 3.0 * tokens * attention_flops_per_token(m, seq))


# launches of the grouped matmul a step makes for each expert block: gate, up
# and down, each forward, d lhs and d rhs
PRODUCTS_PER_BLOCK = 9


def grouped_matmul_floor_s(m: dict, rows_multiplied: float, launches: float,
                           peaks: dict, itemsize: int = 2) -> float:
    """The least time the chip could take for the grouped products behind
    `rows_multiplied` buffer rows (padding included, summed over blocks and
    steps; each row goes through PRODUCTS_PER_BLOCK products of 2 H F FLOPs)
    in `launches` kernel launches: the larger of FLOPs over the peak and
    bytes over the bandwidth. Each launch reads or writes a [rows, H] and a
    [rows, F] operand and the held experts' [G, H, F] once."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = PRODUCTS_PER_BLOCK * 2.0 * rows_multiplied * h * f
    data = itemsize * (PRODUCTS_PER_BLOCK * rows_multiplied * (h + f)
                       + launches * m["n_routed_experts"] * h * f)
    return max(flops / peaks["bf16_flops_per_s"],
               data / peaks["hbm_bytes_per_s"])
