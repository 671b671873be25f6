"""Operations and bytes of one training step of an `afmoe` configuration
(Trinity-Mini), worked out from its published keys, from the rows the held
experts were counted to compute, and — for the window layers — from the
(query, key) pairs INSIDE the mask, whatever a kernel sweeps. Nothing here
imports the program.
"""
from __future__ import annotations

from benchmark import arith_glm4_moe_lite as glm_arith


def attention_params(m: dict) -> int:
    """Matmul weights of one attention block: q, the output gate and o at
    heads x head_dim, k and v at kv heads x head_dim."""
    h, d = m["hidden_size"], m["head_dim"]
    return h * d * (3 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"])


def expert_params(m: dict) -> int:
    """One expert's (or the shared expert's) three SwiGLU matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_width(m: dict) -> int:
    return m.get("published", {}).get("num_experts", m["num_experts"])


def blocks(m: dict):
    """(dense blocks, expert blocks) that run in a step."""
    dense = min(m["num_dense_layers"], m["num_hidden_layers"])
    return dense, m["num_hidden_layers"] - dense


def layer_kinds(m: dict, seq: int):
    """(window layers, full layers) at `seq` rows: a window no row outgrows
    is a full layer."""
    kinds = m["layer_types"][:m["num_hidden_layers"]]
    window = sum(k == "sliding_attention" for k in kinds) \
        if m["sliding_window"] < seq else 0
    return window, len(kinds) - window


def parameters(m: dict) -> dict:
    """Matmul weights held here, by part: one attention block, one dense
    layer, one expert layer (attention, shared expert, router, the held
    experts), embedding + head over the vocabulary slice, and the whole."""
    h = m["hidden_size"]
    attn, expert = attention_params(m), expert_params(m)
    dense_layer = attn + 3 * h * m["intermediate_size"]
    expert_layer = attn + m["num_shared_experts"] * expert \
        + h * router_width(m) + m["num_experts"] * expert
    vocab = 2 * h * m["vocab_size"]
    dense, experts = blocks(m)
    return {"attention": attn, "expert": expert, "dense_layer": dense_layer,
            "expert_layer": expert_layer, "vocabulary": vocab,
            "total": dense * dense_layer + experts * expert_layer + vocab}


def dense_params_per_token(m: dict) -> int:
    """Matmul weights every token multiplies: attention of every block, the
    dense MLP, each expert block's shared expert and router, the head. The
    embedding is a lookup; the routed experts are counted by their rows."""
    h = m["hidden_size"]
    dense, experts = blocks(m)
    return ((dense + experts) * attention_params(m)
            + dense * 3 * h * m["intermediate_size"]
            + experts * (m["num_shared_experts"] * expert_params(m)
                         + h * router_width(m))
            + h * m["vocab_size"])


def window_pairs(m: dict, seq: int) -> int:
    """(query, key) pairs inside a window layer's mask, a head: row t sees
    the keys s with 0 <= t - s < window — W*S - W*(W-1)/2."""
    w = min(m["sliding_window"], seq)
    return w * seq - w * (w - 1) // 2


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def attention_flops_per_row(m: dict, seq: int) -> float:
    """Forward FLOPs of the two score products (q k^T and p v, 2 x head_dim
    each a pair) of every layer over one sequence, the pairs counted AS THE
    MASK HAS THEM: a window layer's band, a full layer's triangle."""
    window, full = layer_kinds(m, seq)
    pairs = window * window_pairs(m, seq) + full * causal_pairs(seq)
    return 2 * 2.0 * m["head_dim"] * m["num_attention_heads"] * pairs


def train_flops_per_step(m: dict, batch: int, seq: int,
                         rows_held: float) -> float:
    """Model FLOPs of one step, forward + backward, nothing recomputed: 6 a
    matmul weight a token (or a routed row: `rows_held` is the step's count
    of (token, held expert) assignments over all expert blocks) and three
    times attention's forward products."""
    tokens = batch * seq
    return (6.0 * tokens * dense_params_per_token(m)
            + 6.0 * rows_held * expert_params(m)
            + 3.0 * batch * attention_flops_per_row(m, seq))


# launches of the grouped matmul a step makes for each expert block: gate, up
# and down, each forward, d lhs and d rhs
PRODUCTS_PER_BLOCK = glm_arith.PRODUCTS_PER_BLOCK


def grouped_matmul_floor_s(m: dict, rows_multiplied: float, launches: float,
                           peaks: dict, itemsize: int = 2) -> float:
    """The least time the chip could take for the grouped products behind
    `rows_multiplied` buffer rows in `launches` kernel launches: the accepted
    `kernel.grouped_mm_roofline_share`'s rule itself
    (`arith_glm4_moe_lite.grouped_matmul_floor_s`), the held experts counted
    under this family's key."""
    return glm_arith.grouped_matmul_floor_s(
        dict(m, n_routed_experts=m["num_experts"]), rows_multiplied,
        launches, peaks, itemsize)


# products of [rows, head_dim]-sized operands a pair costs: q k^T and p v
# forward; s, dp, dv, dk and dq backward
WINDOW_PRODUCTS = (2, 5)


def window_kernels_floor_s(m: dict, batch: int, seq: int, steps: float,
                           peaks: dict, itemsize: int = 2) -> float:
    """The least time the chip could take for the window layers' forward and
    backward kernels over `steps` steps, for the pairs INSIDE the mask: the
    larger of their FLOPs (2 head_dim a pair and product, 2 products forward,
    5 backward) over the peak and their bytes over the bandwidth (forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes dq
    and a dk and dv a q head). A kernel that sweeps blocks outside the mask
    reads below 100% for it."""
    window, _ = layer_kinds(m, seq)
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    launches = window * batch * steps
    flops = launches * sum(WINDOW_PRODUCTS) * 2.0 * d * nh \
        * window_pairs(m, seq)
    data = launches * itemsize * seq * d * ((2 * nh + 2 * nkv)
                                            + (6 * nh + 2 * nkv))
    return max(flops / peaks["bf16_flops_per_s"],
               data / peaks["hbm_bytes_per_s"])
