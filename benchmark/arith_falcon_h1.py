"""Operations and bytes of the `falcon_h1` block as it is served, worked out
from the configuration's published keys. Nothing here imports the program.

A layer holds an attention block (q [h, H x d], k and v [h, Hkv x d], o
[H x d, h]), a Mamba-2 mixer (W_in [h, 2 d_ssm + 2 G N + heads], a depthwise
conv of `mamba_d_conv` taps over d_ssm + 2 G N channels with its bias, three
scalars a head, the gated norm's d_ssm scales, out [d_ssm, h]), a dense SwiGLU
of `intermediate_size` and two norms. A decode step reads every weight it
multiplies, the head in bfloat16, the live tokens' K and V, and reads and
writes each live sequence's state: S of every head (d_ssm x d_state float32)
and the conv's last taps - 1 inputs (float32), a layer.
"""
from __future__ import annotations

WEIGHT_BYTES = KV_BYTES = 2          # bfloat16
STATE_BYTES = 4                      # float32


def depth(m: dict) -> int:
    return int(m["num_hidden_layers"])


def conv_dim(m: dict) -> int:
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return 2 * h * d * (m["num_attention_heads"] + m["num_key_value_heads"])


def mixer_params(m: dict) -> int:
    h, d, heads = m["hidden_size"], m["mamba_d_ssm"], m["mamba_n_heads"]
    c = conv_dim(m)
    return h * (d + c + heads) + (m["mamba_d_conv"] + 1) * c + 3 * heads \
        + d + d * h


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m: dict) -> int:
    return attention_params(m) + mixer_params(m) + mlp_params(m) \
        + 2 * m["hidden_size"]


def decode_weight_bytes(m: dict) -> int:
    """Weight bytes one decode step reads: every layer, the final norm, the
    head (the embedding is a gather of a row a token)."""
    h = m["hidden_size"]
    return WEIGHT_BYTES * (depth(m) * layer_params(m) + h
                           + h * m["vocab_size"])


def ssm_state_bytes(m: dict) -> int:
    """A sequence's S of one layer: every head's d_head x d_state."""
    return STATE_BYTES * m["mamba_d_ssm"] * m["mamba_d_state"]


def state_row_bytes(m: dict) -> int:
    """A sequence's whole state of one layer: S and the conv's inputs."""
    return ssm_state_bytes(m) \
        + STATE_BYTES * (m["mamba_d_conv"] - 1) * conv_dim(m)


def kv_token_bytes(m: dict) -> int:
    """K and V of one token over every layer."""
    return 2 * KV_BYTES * m["num_key_value_heads"] * m["head_dim"] * depth(m)


def ssd_decode_bytes(m: dict, slot_steps: float) -> float:
    """What the decode step's recurrence must move for `slot_steps` (a
    sequence's step of one layer each): its S read and written, Δ x in and
    y out (float32 a head's d_head), B and C in (float32 a group's
    d_state) and exp(Δ A) in (one a head)."""
    heads, p = m["mamba_n_heads"], m["mamba_d_head"]
    per = 2 * ssm_state_bytes(m) + STATE_BYTES * (
        2 * heads * p + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
        + heads)
    return slot_steps * per


def ssd_scan_flops(m: dict, tokens: float) -> float:
    """Operations of the chunked scan over `tokens` (summed over layers),
    chunks of `mamba_chunk_size` Q: a token and head meets the chunk's
    C Bᵀ (2 Q N) and the masked product with Δ x (2 Q P), and its share of
    C Sᵀ and of the state's update (2 N P each)."""
    q, n, p = m["mamba_chunk_size"], m["mamba_d_state"], m["mamba_d_head"]
    return tokens * m["mamba_n_heads"] * (2 * q * (n + p) + 4 * n * p)


def ssd_scan_bytes(m: dict, tokens: float, segments: float) -> float:
    """What the chunked scan must move: a segment's S of every head read
    and written once, and a token's Δ x in and y out, its B and C in and
    its Δ A in."""
    heads, p = m["mamba_n_heads"], m["mamba_d_head"]
    g, n = m["mamba_n_groups"], m["mamba_d_state"]
    return segments * 2 * ssm_state_bytes(m) \
        + tokens * STATE_BYTES * (2 * heads * p + 2 * g * n + heads)


def decode_step_bytes(m: dict, rows: float, kv_tokens: float) -> float:
    """Bytes one decode step of `rows` live sequences holding `kv_tokens`
    cached tokens must move: its weights, their K and V, and each
    sequence's state of every layer read and written."""
    return decode_weight_bytes(m) + kv_tokens * kv_token_bytes(m) \
        + 2 * rows * depth(m) * state_row_bytes(m)
