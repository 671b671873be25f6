"""Hy4-preview (`model_type: hy_v4`) as the serving engine runs it: every layer
gated latent attention whose keys an indexer selects, hyper-connection
residual streams, a leading dense layer, then layers of sigmoid-routed experts
beside a shared one, an f32 head.

The source (`tencent/Hy4-preview`, config.json) gives the shapes; the layer
equations are written from its keys and the public descriptions of each part.
X is the residual, `hc_mult` = n streams of `hidden_size` = C; x a token's
normed layer input; t its position.

- Hyper-connections (`enable_ihc`, read as mHC, arXiv:2512.24880). Around each
  sub-block F (attention, MLP), from x~ = RMSNorm(vec X) over n C values:
      H_pre  = sigmoid(a_pre  x~ phi_pre  + b_pre)             [1 x n]
      H_post = hc_magnitude sigmoid(a_post x~ phi_post + b_post) [1 x n]
      H_res  = Sinkhorn-Knopp(exp(a_res mat(x~ phi_res) + b_res)) [n x n]
      X <- H_res X + H_post^T F(H_pre X)
  The embedding enters as n copies; the streams are summed before the final
  norm.
- Gated latent attention (`use_mla`, `gated_mla`, `gating_type:
  elementwise`, `learnable_sink`):
      c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads of [nope | rope]
      [c_kv ; k_r] = x W_kva;  c_kv RMSNorm'd;  k_r rotary, one a token
      score(t, s) = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope),
      k_nope / v = c_kv W_kvb split by head; a learned sink logit a head in
      the softmax's denominator;  o = (sigmoid(x W_g) * attn) W_o.
  Served absorbed: q_nope W_uk against the cached [c_kv ; k_r] row, the
  weighted sum of c_kv rows times W_uv.
- Learned sparse attention (`use_dsa`, DeepSeek-V3.2's indexer):
      q^I = c_q W_Iq (`index_n_heads` x `index_head_dim`),
      k^I = LayerNorm(x W_Ik),  w = x W_Iw,
      I(t, s) = sum_j w_j relu(q^I_j . k^I_s) for s <= t;
  attention sees the top min(`index_topk`, t + 1) positions of I (the lower
  position wins a tie). A `shared` entry of `indexer_types` reuses the
  selection of the nearest `full` layer above it and has no indexer.
- Experts (`noaux_tc`, one group): s = sigmoid(x W_r) over `n_routed_experts`;
  the top `num_experts_per_tok` of s + b (b a bias); gates s / sum s x
  `routed_scaling_factor`; plus `n_shared_experts` shared expert(s). Layer
  kinds by `mlp_layer_types`: `dense` a SwiGLU of `intermediate_size`.
- Every SwiGLU clamps (`swiglu_limit` L): silu(min(g, L)) * clip(u, -L, L).
- The head is f32 (`enable_lm_head_fp32`).

`assumed` (no key fixes these; program and reference use the same):
`enable_ihc` is mHC; 20 Sinkhorn iterations (rows, then columns), `hc_eps` in
their denominators; RMSNorm of x~ without a learned scale; the gate reads the
normed layer input; rotary pairs are halves, over the first
`qk_rope_head_dim` dims of q^I and k^I too; the indexer's LayerNorm has a
weight and a bias and takes `rms_norm_eps`; sigmoid router scores; the
indexer's positive scale factors and Hadamard rotation are left out (they keep
the order of I).

Not built, and refused by name: the MTP module (`num_nextn_predict_layers`:
its drafter serves speculation, which the engine refuses for routed models),
and any key value other than the published model's where the code path would
differ. The model exists as `served_model()` — the contract of
`models/llama.py`'s program builders — and seeded serving parameters.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.rms_norm import rms_norm as _rms
from ..kernels.rope import apply_rotary_emb
from .llama import DSA_COUNTS, MOE_COUNTS, ServedLayer, ServedModel

SINKHORN_ITERS = 20
# keys that must hold these values: another value would be another model
_FIXED = {"model_type": "hy_v4", "hidden_act": "silu", "use_mla": True,
          "use_dsa": True, "gated_mla": True, "gating_type": "elementwise",
          "enable_ihc": True, "learnable_sink": True,
          "enable_lm_head_fp32": True, "attention_bias": False,
          "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "num_nextn_predict_layers": 0}
# keys read by nothing the served model does
_UNUSED = ("bitwise_backward_align", "mtp_loss_factor", "learnable_sink_init",
           "head_dim", "max_position_embeddings")
# keys of a benchmark configuration's file that are no model key
_FILE_KEYS = ("source", "published", "reduced", "assumed", "deployment",
              "notes")
_DSA = ("full", "shared")


@dataclasses.dataclass
class HyV4Config:
    """The source's keys, under their names. `held`: the routed experts this
    chip holds (all by default); the router keeps `n_routed_experts`."""
    vocab_size: int = 120832
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    num_key_value_heads: int = 8        # read by nothing: MLA has no kv heads
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 256
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = ("full",) + ("full", "shared", "shared",
                                                  "shared") * 19 + ("full",)
    layer_types: Tuple[str, ...] = ("deepseek_sparse_attention",) * 78
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 77
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    swiglu_limit: float = 10.0
    hc_mult: int = 4
    hc_magnitude: float = 2.0
    hc_eps: float = 1e-6
    rope_parameters: dict = dataclasses.field(
        default_factory=lambda: {"rope_theta": 10000000,
                                 "rope_type": "default"})
    rms_norm_eps: float = 1e-5
    held: Optional[Tuple[int, ...]] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("indexer_types", "layer_types", "mlp_layer_types"):
            kinds = tuple(getattr(self, name))
            if len(kinds) < n:
                raise ValueError(f"{name} names {len(kinds)} layers, "
                                 f"num_hidden_layers is {n}")
            setattr(self, name, kinds[:n])
        if set(self.layer_types) != {"deepseek_sparse_attention"}:
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}: "
                             "only deepseek_sparse_attention is built")
        if set(self.indexer_types) - set(_DSA) \
                or self.indexer_types[0] != "full":
            raise ValueError(f"indexer_types: only {_DSA} are built, and the "
                             "first layer must select its own keys")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}: "
                             "only dense and sparse are built")
        if self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("qk_head_dim must be qk_nope_head_dim + "
                             "qk_rope_head_dim")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError(f"rope_parameters.rope_type "
                             f"{self.rope_parameters['rope_type']!r} is not "
                             "built")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the indexer's rotary dims exceed its head")
        if self.held is None:
            self.held = tuple(range(self.n_routed_experts))
        self.held = tuple(int(e) for e in self.held)
        if not all(0 <= e < self.n_routed_experts for e in self.held) \
                or len(set(self.held)) != len(self.held):
            raise ValueError(f"held experts {self.held} are not distinct "
                             f"indices below {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, d: dict, **over) -> "HyV4Config":
        """From a config.json's keys. A key that fixes a code path this
        module does not build is refused by name with its value; keys that
        change nothing served are let through; any other key is refused."""
        names = {f.name for f in dataclasses.fields(cls)}
        for k, v in d.items():
            if k in _FIXED and v != _FIXED[k]:
                raise ValueError(f"{k}={v!r} is not built (only "
                                 f"{_FIXED[k]!r})")
            if k not in names and k not in _FIXED and k not in _UNUSED \
                    and k not in _FILE_KEYS:
                raise ValueError(f"{k}={v!r}: no such key is built")
        return cls(**{**{k: v for k, v in d.items() if k in names}, **over})

    @staticmethod
    def tiny(**over) -> "HyV4Config":
        """Three layers (dense + full, sparse + full, sparse + shared) at
        CPU-test sizes: selection binds past 8 tokens."""
        return HyV4Config(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=12, qk_rope_head_dim=4, qk_head_dim=16,
            v_head_dim=16, index_n_heads=2, index_head_dim=8, index_topk=8,
            indexer_types=("full", "full", "shared"),
            layer_types=("deepseek_sparse_attention",) * 3,
            mlp_layer_types=("dense", "sparse", "sparse"),
            n_routed_experts=8, num_experts_per_tok=2, held=(0, 1, 2, 3),
            rope_parameters={"rope_theta": 10000, "rope_type": "default"},
            dtype="float32"), **over})

    @property
    def latent_width(self) -> int:
        """Values of a cached latent row: c_kv, then the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "full")

    def served_model(self) -> ServedModel:
        layers = []
        for i, (dsa, mlp) in enumerate(zip(self.indexer_types,
                                           self.mlp_layer_types)):
            layers.append(ServedLayer(
                f"model.layers.{i}.", None,
                float(self.rope_parameters["rope_theta"]), None,
                _routed(self) if mlp == "sparse" else _dense(self),
                attn=SparseLatentAttention(self, dsa == "full"),
                residual=_hyper_connection(self)))
        return ServedModel(
            embed="model.embed_tokens.weight", norm="model.norm.weight",
            head="lm_head.weight", head_dim=self.qk_head_dim,
            layers=tuple(layers), streams=self.hc_mult, head_f32=True,
            counts=MOE_COUNTS + DSA_COUNTS)


# ---- hyper-connections ---------------------------------------------------

def sinkhorn(m, iters: int, eps: float):
    """Alternate row and column normalisation of positive m [..., n, n]."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_maps(cfg: HyV4Config, xf, phi, alpha, bias):
    """(H_pre [..., n], H_post [..., n], H_res [..., n, n]) of streams xf
    [..., n, C] (f32)."""
    n = cfg.hc_mult
    flat = xf.reshape(*xf.shape[:-2], -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                              + cfg.rms_norm_eps)
    z = jnp.dot(xt.astype(phi.dtype), phi,
                preferred_element_type=jnp.float32)
    a, b = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = cfg.hc_magnitude * jax.nn.sigmoid(a[1] * z[..., n:2 * n]
                                             + b[n:2 * n])
    res = jnp.exp(a[2] * z[..., 2 * n:] + b[2 * n:]).reshape(
        *z.shape[:-1], n, n)
    return pre, post, sinkhorn(res, SINKHORN_ITERS, cfg.hc_eps)


def _hyper_connection(cfg: HyV4Config):
    """The contract's residual rule: (X [..., n, C], F, p, the layer's
    prefix, "attn" | "mlp") -> (X', what F returned beside its output); the
    sub-block's maps are `<prefix><attn|mlp>_hc.{phi,alpha,bias}`."""

    def residual(h, block, p, prefix, which):
        pre = f"{prefix}{which}_hc."
        with jax.named_scope("hc.mix"):
            xf = h.astype(jnp.float32)
            h_pre, h_post, h_res = hc_maps(cfg, xf, p[pre + "phi"],
                                           p[pre + "alpha"], p[pre + "bias"])
            u = jnp.einsum("...i,...ic->...c", h_pre, xf).astype(h.dtype)
        y, aux = block(u)
        with jax.named_scope("hc.mix"):
            out = jnp.einsum("...ij,...jc->...ic", h_res, xf) \
                + h_post[..., None] * y.astype(jnp.float32)[..., None, :]
        return out.astype(h.dtype), aux

    return residual


# ---- attention -----------------------------------------------------------

class SparseLatentAttention:
    """The contract's attention of one layer: its latent rows (every layer)
    and index keys (`full` layers) in pools of its own, written here. The
    selection rides between layers in the builders' carry."""

    def __init__(self, cfg: HyV4Config, full: bool):
        self.cfg, self.full = cfg, full

    # pools: [pages * block, 1, W] int32 packed latent rows; [pages, 1,
    # block, index_head_dim] index keys on a `full` layer, else None
    def pools(self, max_pages: int, block_size: int, dtype):
        from ..kernels.sparse_attention import latent_words

        w = latent_words(self.cfg.latent_width)
        latent = jnp.zeros((max_pages * block_size, 1, w), jnp.int32)
        index = jnp.zeros((max_pages, 1, block_size,
                           self.cfg.index_head_dim), dtype) \
            if self.full else None
        return latent, index

    def row_bytes(self, dtype):
        """(latent, index-key) bytes a token of this layer takes, the index
        keys in `dtype` (the pools')."""
        from ..kernels.sparse_attention import latent_words

        return (4 * latent_words(self.cfg.latent_width),
                jnp.dtype(dtype).itemsize * self.cfg.index_head_dim
                if self.full else 0)

    def _project(self, x, p, pre, pos_ids):
        """x [B, S, C] -> absorbed queries [B, S, H, width], latent rows
        [B, S, width], c_q [B, S, q_rank], the gate [B, S, H, v]."""
        c = self.cfg
        h, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)
        a = pre + "self_attn."
        c_q = _rms(x @ p[a + "q_a_proj.weight"], p[a + "q_a_layernorm.weight"],
                   c.rms_norm_eps)
        q = (c_q @ p[a + "q_b_proj.weight"]).reshape(*x.shape[:2], h,
                                                      nope + rope)
        kv = x @ p[a + "kv_a_proj_with_mqa.weight"]
        c_kv = _rms(kv[..., :c.kv_lora_rank], p[a + "kv_a_layernorm.weight"],
                    c.rms_norm_eps)
        q_rope, k_rope = apply_rotary_emb(
            q[..., nope:], kv[..., None, c.kv_lora_rank:],
            position_ids=pos_ids, base=float(c.rope_parameters["rope_theta"]))
        w_uk = p[a + "kv_b_proj.weight"].reshape(
            c.kv_lora_rank, h, nope + c.v_head_dim)[..., :nope]
        q_lat = jnp.einsum("bshn,khn->bshk", q[..., :nope], w_uk)
        q_abs = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1)
        rows = jnp.concatenate([c_kv, k_rope[..., 0, :].astype(c_kv.dtype)],
                               -1)
        gate = jax.nn.sigmoid((x @ p[a + "g_proj.weight"]).astype(
            jnp.float32)).reshape(*x.shape[:2], h, c.v_head_dim)
        return q_abs, rows, c_q, gate

    def _index(self, x, c_q, p, pre, pos_ids):
        """q^I [B, S, nI, dI], w [B, S, nI] (f32), k^I [B, S, dI]."""
        c = self.cfg
        a = pre + "self_attn.indexer."
        n, d, r = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
        qi = (c_q @ p[a + "wq_b.weight"]).reshape(*x.shape[:2], n, d)
        ki = _layer_norm(x @ p[a + "wk.weight"], p[a + "k_norm.weight"],
                         p[a + "k_norm.bias"], c.rms_norm_eps)[..., None, :]
        qr, kr = apply_rotary_emb(qi[..., :r], ki[..., :r],
                                  position_ids=pos_ids,
                                  base=float(c.rope_parameters["rope_theta"]))
        qi = jnp.concatenate([qr.astype(qi.dtype), qi[..., r:]], -1)
        ki = jnp.concatenate([kr.astype(ki.dtype), ki[..., r:]], -1)[..., 0, :]
        w = (x @ p[a + "weights_proj.weight"]).astype(jnp.float32)
        return qi, w, ki

    def _out(self, o_lat, gate, p, pre):
        """o_lat [..., H, kv_rank] -> the block's output [..., C]."""
        c = self.cfg
        a = pre + "self_attn."
        w_uv = p[a + "kv_b_proj.weight"].reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)[..., c.qk_nope_head_dim:]
        attn = jnp.einsum("...hk,khv->...hv", o_lat.astype(w_uv.dtype), w_uv)
        o = (gate * attn.astype(jnp.float32)).astype(attn.dtype)
        return o.reshape(*o.shape[:-2], -1) @ p[a + "o_proj.weight"]

    def _count(self, scored, attended):
        c = jnp.zeros((len(MOE_COUNTS) + len(DSA_COUNTS),), jnp.int32)
        return c.at[len(MOE_COUNTS):].set(jnp.stack([
            jnp.asarray(int(self.full), jnp.int32),
            jnp.asarray(scored, jnp.int32), jnp.asarray(attended, jnp.int32)]))

    def decode(self, x, p, pre, latent, index, lane, carry):
        """One token a row: x [B, 1, C] (normed), lane {"tables" [B, W],
        "pos" [B] (the token's position), "block_size"}. Returns (y [B, 1,
        C], latent, index, carry, counts)."""
        from ..kernels.sparse_attention import (decode_plan,
                                                dsa_index_scores,
                                                latent_commit, pack_rows,
                                                select_topk,
                                                sparse_mla_decode)

        c = self.cfg
        tables, pos, bs = lane["tables"], lane["pos"], lane["block_size"]
        b = x.shape[0]
        rows_b = jnp.arange(b)
        with jax.named_scope("attn.mla"):
            q_abs, rows, c_q, gate = self._project(x, p, pre, pos[:, None])
            page = tables[rows_b, pos // bs]
            latent = latent_commit(latent, pack_rows(rows[:, 0]),
                                   page * bs + pos % bs)
        k = min(c.index_topk, tables.shape[1] * bs)
        n_sel = jnp.minimum(pos + 1, c.index_topk)
        scored = 0
        if self.full:
            with jax.named_scope("attn.indexer"):
                qi, w, ki = self._index(x, c_q, p, pre, pos[:, None])
                index = index.at[page, 0, pos % bs].set(ki[:, 0].astype(
                    index.dtype))
                scores = dsa_index_scores(qi, w, index, tables, pos[:, None])
                _, idx = select_topk(scores[:, 0], k)
                plan = decode_plan(idx, n_sel, tables, bs)
            carry = dict(carry, decode=plan)
            scored = jnp.sum(pos + 1)
        plan = carry["decode"]
        with jax.named_scope("attn.mla"):
            o_lat = sparse_mla_decode(
                q_abs[:, 0], latent, plan, p[pre + "self_attn.sinks"],
                scale=1.0 / math.sqrt(c.qk_head_dim),
                value_width=c.kv_lora_rank,
                block_size=bs)[..., :c.kv_lora_rank]
            y = self._out(o_lat[:, None], gate, p, pre)
        return y, latent, index, carry, self._count(scored, jnp.sum(n_sel))

    def window(self, x, p, pre, latent, index, lane, carry):
        """A prefill window: x [1, T, C] (normed), lane {"table" [W],
        "pages" [T / block] (the window's pages, pad ones the scratch
        page), "cached", "new_len", "pos" [T], "block_size"}."""
        from ..kernels.sparse_attention import (dsa_index_scores, pack_rows,
                                                sparse_mla_window,
                                                window_commit,
                                                window_selection)

        c = self.cfg
        table, pages, pos, bs = (lane["table"], lane["pages"], lane["pos"],
                                 lane["block_size"])
        t = x.shape[1]
        live = jnp.arange(t) < lane["new_len"]
        with jax.named_scope("attn.mla"):
            q_abs, rows, c_q, gate = self._project(x, p, pre, pos[None])
            latent = window_commit(latent, pack_rows(rows[0]), pages, bs)
        scored = 0
        if self.full:
            from ..kernels.sparse_attention import INDEX_ROWS

            with jax.named_scope("attn.indexer"):
                qi, w, ki = self._index(x, c_q, p, pre, pos[None])
                index = index.at[pages].set(ki[0].reshape(
                    -1, 1, bs, ki.shape[-1]).astype(index.dtype))
                tq = min(INDEX_ROWS, t)
                g = t // tq
                scores = dsa_index_scores(
                    qi.reshape(g, tq, *qi.shape[2:]), w.reshape(g, tq, -1),
                    index, jnp.broadcast_to(table[None], (g, table.shape[0])),
                    pos.reshape(g, tq)).reshape(t, -1)
                thr, tie = window_selection(scores, c.index_topk)
            carry = dict(carry, window=(scores, thr, tie))
            scored = jnp.sum(jnp.where(live, pos + 1, 0))
        with jax.named_scope("attn.mla"):
            scores, thr, tie = carry["window"]
            o_lat = sparse_mla_window(
                q_abs[0], latent, table, pos, scores, thr, tie,
                p[pre + "self_attn.sinks"],
                scale=1.0 / math.sqrt(c.qk_head_dim),
                value_width=c.kv_lora_rank, block_size=bs)
            y = self._out(o_lat[None, ..., :c.kv_lora_rank], gate, p, pre)
        attended = jnp.sum(jnp.where(live, jnp.minimum(pos + 1, c.index_topk),
                                     0))
        return y, latent, index, carry, self._count(scored, attended)


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


# ---- MLPs ----------------------------------------------------------------

def clamped_swiglu(x, w_gate, w_up, w_down, limit: float):
    g = jnp.minimum(x @ w_gate, limit)
    u = jnp.clip(x @ w_up, -limit, limit)
    return (jax.nn.silu(g) * u) @ w_down


def _dense(cfg: HyV4Config):
    def mlp(x, p, pre):
        m = pre + "mlp."
        return clamped_swiglu(x, p[m + "gate_proj.weight"],
                              p[m + "up_proj.weight"],
                              p[m + "down_proj.weight"],
                              cfg.swiglu_limit), None

    return mlp


def route(cfg: HyV4Config, x, w_router, bias):
    """x [T, C] -> (experts [T, k] int32, gates [T, k] f32): sigmoid scores
    in f32, the top k of score + bias (the lower index wins a tie), gates
    the scores alone, renormalised and scaled."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                           cfg.num_experts_per_tok)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    return idx.astype(jnp.int32), gates * cfg.routed_scaling_factor


def _routed(cfg: HyV4Config):
    """The held routed experts (the dropless layer, the clamp) beside the
    shared expert. Counts what the layer did (MOE_COUNTS, then zeros for
    DSA_COUNTS) over the experts this chip holds: the rows routed to them,
    how many of them got a row, the busiest one's rows."""
    from ..parallel.moe import dropless_experts

    n = cfg.n_routed_experts
    held = jnp.asarray(cfg.held, jnp.int32)

    def mlp(x, p, pre):
        m = pre + "mlp."
        rows = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe.route"):
            idx, gates = route(cfg, rows, p[m + "gate.weight"],
                               p[m + "gate.e_score_correction_bias"])
        with jax.named_scope("moe.experts"):
            y, c = dropless_experts(
                rows, idx, gates, p[m + "experts.gate_proj"],
                p[m + "experts.up_proj"], p[m + "experts.down_proj"],
                cfg.held, n, limit=cfg.swiglu_limit)
        with jax.named_scope("moe.shared"):
            y = y + clamped_swiglu(
                rows, p[m + "shared_experts.gate_proj.weight"],
                p[m + "shared_experts.up_proj.weight"],
                p[m + "shared_experts.down_proj.weight"],
                cfg.swiglu_limit).astype(y.dtype)
        counted = {"layer_steps": 1, "rows_routed": c["moe.rows_held"],
                   "experts_hit": jnp.sum(c["moe.load"][held] > 0),
                   "load_max": c["moe.load_max"],
                   "rows_multiplied": c["moe.rows_multiplied"]}
        return y.reshape(x.shape), jnp.concatenate([jnp.stack(
            [jnp.asarray(counted[k], jnp.int32) for k in MOE_COUNTS]),
            jnp.zeros((len(DSA_COUNTS),), jnp.int32)])

    mlp.routed = True
    return mlp


# ---- parameters ----------------------------------------------------------

def serving_param_shapes(cfg: HyV4Config) -> dict:
    """Name -> shape of every serving parameter."""
    c = cfg
    hd, h = c.hidden_size, c.num_attention_heads
    out = {"model.embed_tokens.weight": (c.vocab_size, hd)}
    for i, (dsa, mlp) in enumerate(zip(c.indexer_types, c.mlp_layer_types)):
        pre = f"model.layers.{i}."
        a = pre + "self_attn."
        out.update({
            pre + "input_layernorm.weight": (hd,),
            pre + "post_attention_layernorm.weight": (hd,),
            a + "q_a_proj.weight": (hd, c.q_lora_rank),
            a + "q_a_layernorm.weight": (c.q_lora_rank,),
            a + "q_b_proj.weight": (c.q_lora_rank, h * c.qk_head_dim),
            a + "kv_a_proj_with_mqa.weight": (hd, c.latent_width),
            a + "kv_a_layernorm.weight": (c.kv_lora_rank,),
            a + "kv_b_proj.weight": (c.kv_lora_rank, h * (
                c.qk_nope_head_dim + c.v_head_dim)),
            a + "g_proj.weight": (hd, h * c.v_head_dim),
            a + "o_proj.weight": (h * c.v_head_dim, hd),
            a + "sinks": (h,)})
        if dsa == "full":
            x = a + "indexer."
            out.update({
                x + "wq_b.weight": (c.q_lora_rank,
                                    c.index_n_heads * c.index_head_dim),
                x + "wk.weight": (hd, c.index_head_dim),
                x + "k_norm.weight": (c.index_head_dim,),
                x + "k_norm.bias": (c.index_head_dim,),
                x + "weights_proj.weight": (hd, c.index_n_heads)})
        for sub in ("attn_hc.", "mlp_hc."):
            out.update({pre + sub + "phi": (c.hc_mult * hd,
                                            c.hc_mult * (c.hc_mult + 2)),
                        pre + sub + "alpha": (3,),
                        pre + sub + "bias": (c.hc_mult * (c.hc_mult + 2),)})
        m = pre + "mlp."
        if mlp == "dense":
            d = c.intermediate_size
            out.update({m + "gate_proj.weight": (hd, d),
                        m + "up_proj.weight": (hd, d),
                        m + "down_proj.weight": (d, hd)})
        else:
            f, g = c.moe_intermediate_size, len(c.held)
            fs = f * c.n_shared_experts
            out.update({
                m + "gate.weight": (hd, c.n_routed_experts),
                m + "gate.e_score_correction_bias": (c.n_routed_experts,),
                m + "experts.gate_proj": (g, hd, f),
                m + "experts.up_proj": (g, hd, f),
                m + "experts.down_proj": (g, f, hd),
                m + "shared_experts.gate_proj.weight": (hd, fs),
                m + "shared_experts.up_proj.weight": (hd, fs),
                m + "shared_experts.down_proj.weight": (fs, hd)})
    out["model.norm.weight"] = (hd,)
    out["lm_head.weight"] = (hd, c.vocab_size)
    return out


# parameters kept in f32 whatever the served type: the head
# (`enable_lm_head_fp32`), the sinks, the router's bias and the
# hyper-connections' scalars
F32_PARAMS = ("lm_head.weight", "sinks", "e_score_correction_bias", "alpha",
              "bias")


def param_dtype(name: str, dtype):
    return jnp.float32 if name.endswith(F32_PARAMS) else jnp.dtype(dtype)


# a SwiGLU's products reach past `swiglu_limit` where its inputs have this
# deviation (g > 10 on 2.3% of them, |u| > 10 on 4.6%): the clamp does work
SWIGLU_IN_STD = 5.0


def _init_value(name: str, shape, key, dtype):
    """Seeded value of one parameter: Xavier-normal matrices (an expert's
    fans are its own [d, f]), norm scales 1, and the parameters that would
    be no-ops at zero drawn too: sinks N(0, 1), the router's bias
    N(0, 0.05), the indexer norm's bias N(0, 0.1), the hyper-connections'
    scales 0.05 (1 + 0.1 N) and biases N(0, 0.2). A SwiGLU's gate and up
    weights give its products a deviation of SWIGLU_IN_STD, so that the
    clamp binds, and its down weight N(0, 1 / (16^2 f)) brings the output
    back to about unit scale."""
    dt = param_dtype(name, dtype)
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(("gate_proj.weight", "up_proj.weight", "gate_proj",
                      "up_proj")):
        return (SWIGLU_IN_STD / math.sqrt(shape[-2]) * normal).astype(dt)
    if name.endswith(("down_proj.weight", "down_proj")):
        return (normal / (16.0 * math.sqrt(shape[-2]))).astype(dt)
    if name.endswith("sinks"):
        return normal.astype(dt)
    if name.endswith("e_score_correction_bias"):
        return (0.05 * normal).astype(dt)
    if name.endswith("k_norm.bias"):
        return (0.1 * normal).astype(dt)
    if name.endswith("alpha"):
        return (0.05 * (1.0 + 0.1 * normal)).astype(dt)
    if name.endswith("hc.bias"):
        return (0.2 * normal).astype(dt)
    if len(shape) == 1:
        return jnp.ones(shape, dt)
    std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
    return (std * normal).astype(dt)


def init_serving_params(cfg: HyV4Config, seed: int = 0,
                        dtype: Optional[str] = None) -> dict:
    """Every serving parameter made on the device from the seed, in the type
    it is served in (`param_dtype`). One jitted program makes a layer kind
    and is called once a layer; another makes the rest."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    shapes = serving_param_shapes(cfg)

    def fill(group, key):
        return {name: _init_value(name, shape, jax.random.fold_in(key, j),
                                  dtype)
                for j, (name, shape) in enumerate(group.items())}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF, impl="rbg"),
                             seed >> 32)
    rest = {k: v for k, v in shapes.items()
            if not k.startswith("model.layers.")}
    out = jax.jit(lambda key: fill(rest, key))(jax.random.fold_in(key, 0))
    makers = {}
    for i, kind in enumerate(zip(cfg.indexer_types, cfg.mlp_layer_types)):
        pre = f"model.layers.{i}."
        if kind not in makers:
            group = {k[len(pre):]: v for k, v in shapes.items()
                     if k.startswith(pre)}
            makers[kind] = jax.jit(lambda key, group=group: fill(group, key))
        made = makers[kind](jax.random.fold_in(key, i + 1))
        out.update({pre + k: v for k, v in made.items()})
    return out
