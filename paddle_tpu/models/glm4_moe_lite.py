"""The `glm4_moe_lite` decoder family (GLM-4.7-Flash; the block is
DeepSeek-V3's, arXiv:2412.19437 sections 2.1-2.2), on the training path.

- multi-head latent attention: low-rank q and kv projections with an RMSNorm
  on each latent, a decoupled rotary part of `qk_rope_head_dim` that the keys
  of all heads share, through the same flash-attention kernels as Llama (the
  head size is nope + rope = the value head's);
- a leading dense SwiGLU layer, then expert layers: `parallel.moe`'s dropless
  layer over the experts HELD here (one chip's share of an expert-parallel
  group, `config.held`; all by default), routed over all `n_routed_experts`
  by the bias-balanced sigmoid gate, plus a shared expert every token takes;
- multi-token-prediction modules: module k joins the stack's last hidden
  state with the embedding of the token k + 1 ahead, runs one expert block
  and the shared head, and predicts the token k + 2 ahead.

`Glm4MoeLiteForCausalLM` goes through `parallel.make_train_step` like Llama:
its forward takes the token rows with their look-ahead columns and returns
both heads' logits and the routers' counters; the criterion returns the loss
with a report (`loss.main`, `loss.mtp`, `moe.*`) that the step hands to the
host beside the loss; `state_updates` is the routers' bias rule, applied
inside the step, outside the optimizer.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, dispatch, unwrap
from ..core import tape as _tape
from ..kernels.rms_norm import rms_norm as _k_rms
from ..kernels.rope import apply_rotary_emb, rope_freqs
from ..nn import functional as F
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..parallel.moe import BiasBalancedSigmoidGate, DroplessMoELayer


@dataclasses.dataclass
class Glm4MoeLiteConfig:
    """Field names are the published config.json's; defaults are
    GLM-4.7-Flash's. `held`, the two rates and `recompute` are the
    deployment's."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240          # the leading dense layers' MLP
    moe_intermediate_size: int = 1536       # one expert's, and the shared's
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64              # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # global indices of the routed experts this chip holds; None = all
    held: Optional[Tuple[int, ...]] = None
    # bias += rate * sign(mean load - load) after each step
    router_bias_update_rate: float = 0.001
    # loss = main + weight * mean of the modules' losses
    mtp_loss_weight: float = 0.3
    recompute: bool = False                 # per-block rematerialisation
    dtype: str = "float32"

    def __post_init__(self):
        if self.held is not None:
            self.held = tuple(int(e) for e in self.held)
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise NotImplementedError(
                "flash attention takes one head size: qk_nope_head_dim + "
                f"qk_rope_head_dim = {self.qk_head_dim} is not v_head_dim "
                f"{self.v_head_dim}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @classmethod
    def from_dict(cls, m: dict, **over) -> "Glm4MoeLiteConfig":
        """From a config.json-like dict: the keys that are fields, the rest
        (rope_scaling null, hidden_act silu, ... ) left where they are."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{**{k: v for k, v in m.items() if k in names}, **over})


class Glm4MoeLiteRMSNorm(Layer):
    def __init__(self, dim: int, eps: float):
        super().__init__()
        from ..nn.initializer import Constant

        self.eps = eps
        self.weight = self.create_parameter(
            [dim], default_initializer=Constant(1.0))

    def forward(self, x):
        return dispatch("rms_norm", lambda a, w: _k_rms(a, w, self.eps),
                        (x, self.weight))


def _rope_join(q, kv, k_rope, cos, sin, dn: int):
    """q [B, S, H, dn + dr], kv [B, S, H, dn + dv], k_rope [B, S, 1, dr] ->
    (q, k, v) of full heads: rotary on the rope part alone, and the keys of
    all heads share the one rotated k_rope."""
    q_r, k_r = apply_rotary_emb(q[..., dn:], k_rope, cos=cos, sin=sin)
    k_r = jnp.broadcast_to(k_r, q_r.shape)
    return (jnp.concatenate([q[..., :dn], q_r], axis=-1),
            jnp.concatenate([kv[..., :dn], k_r], axis=-1), kv[..., dn:])


class Glm4MoeLiteAttention(Layer):
    """Multi-head latent attention."""

    def __init__(self, c: Glm4MoeLiteConfig):
        super().__init__()
        self.config = c
        nh = c.num_attention_heads
        self.q_a_proj = Linear(c.hidden_size, c.q_lora_rank, bias_attr=False)
        self.q_a_layernorm = Glm4MoeLiteRMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = Linear(c.q_lora_rank, nh * c.qk_head_dim,
                               bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(
            c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = Glm4MoeLiteRMSNorm(c.kv_lora_rank,
                                                 c.rms_norm_eps)
        self.kv_b_proj = Linear(
            c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.o_proj = Linear(nh * c.v_head_dim, c.hidden_size,
                             bias_attr=False)

    def forward(self, hidden, cos, sin):
        c = self.config
        b, s, _ = hidden.shape
        nh, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(hidden)))
        q = q.reshape([b, s, nh, dn + dr])
        kva = self.kv_a_proj_with_mqa(hidden)
        kv = self.kv_b_proj(self.kv_a_layernorm(kva[..., :c.kv_lora_rank]))
        kv = kv.reshape([b, s, nh, dn + c.v_head_dim])
        k_rope = kva[..., c.kv_lora_rank:].reshape([b, s, 1, dr])
        q, k, v = dispatch(
            "mla_rope_join",
            lambda q_, kv_, kr: _rope_join(q_, kv_, kr, cos, sin, dn),
            (q, kv, k_rope))
        out, _ = F.flash_attention(q, k, v, causal=True)
        return self.o_proj(out.reshape([b, s, nh * c.v_head_dim]))


class Glm4MoeLiteMLP(Layer):
    def __init__(self, hidden: int, inter: int):
        super().__init__()
        self.gate_proj = Linear(hidden, inter, bias_attr=False)
        self.up_proj = Linear(hidden, inter, bias_attr=False)
        self.down_proj = Linear(inter, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class Glm4MoeLiteMoE(DroplessMoELayer):
    """The held routed experts' part plus the shared expert."""

    def __init__(self, c: Glm4MoeLiteConfig):
        super().__init__(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, held=c.held,
            norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor)
        self.shared_experts = Glm4MoeLiteMLP(
            c.hidden_size, c.moe_intermediate_size * c.n_shared_experts)

    def forward(self, x):
        routed, counters = super().forward(x)
        with jax.named_scope("moe.shared"):
            shared = self.shared_experts(x)
        return shared + routed, counters


class Glm4MoeLiteDecoderLayer(Layer):
    def __init__(self, c: Glm4MoeLiteConfig, dense: bool):
        super().__init__()
        self.dense = dense
        self.self_attn = Glm4MoeLiteAttention(c)
        self.mlp = Glm4MoeLiteMLP(c.hidden_size, c.intermediate_size) \
            if dense else Glm4MoeLiteMoE(c)
        self.input_layernorm = Glm4MoeLiteRMSNorm(c.hidden_size,
                                                  c.rms_norm_eps)
        self.post_attention_layernorm = Glm4MoeLiteRMSNorm(c.hidden_size,
                                                           c.rms_norm_eps)

    def forward(self, hidden, cos, sin):
        """-> (hidden, the router's counters; None for a dense layer)."""
        with jax.named_scope("mla.attn"):
            hidden = hidden + self.self_attn(self.input_layernorm(hidden),
                                             cos, sin)
        x = self.post_attention_layernorm(hidden)
        if self.dense:
            return hidden + self.mlp(x), None
        y, counters = self.mlp(x)
        return hidden + y, counters


def _run_block(layer, hidden, cos, sin, remat: bool):
    if not remat:
        return layer(hidden, cos, sin)

    def run(h):
        out, counters = layer(Tensor(h), cos, sin)
        return unwrap(out), counters

    out, counters = jax.checkpoint(run)(unwrap(hidden))
    return Tensor(out), counters


class Glm4MoeLiteMTP(Layer):
    """One multi-token-prediction module (DeepSeek-V3 section 2.2): the
    embedding and the head are the main model's."""

    def __init__(self, c: Glm4MoeLiteConfig):
        super().__init__()
        self.hnorm = Glm4MoeLiteRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.enorm = Glm4MoeLiteRMSNorm(c.hidden_size, c.rms_norm_eps)
        self.eh_proj = Linear(2 * c.hidden_size, c.hidden_size,
                              bias_attr=False)
        self.block = Glm4MoeLiteDecoderLayer(c, dense=False)
        self.norm = Glm4MoeLiteRMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, hidden, next_emb, cos, sin, remat: bool):
        """(the module's hidden state before its norm — the next module's
        input —, its normed state for the head, its router's counters)."""
        from .. import ops

        joined = ops.concat([self.hnorm(hidden), self.enorm(next_emb)],
                            axis=-1)
        hidden, counters = _run_block(self.block, self.eh_proj(joined), cos,
                                      sin, remat)
        return hidden, self.norm(hidden), counters


class Glm4MoeLiteModel(Layer):
    def __init__(self, c: Glm4MoeLiteConfig):
        super().__init__()
        from ..nn.layer.container import LayerList

        self.config = c
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.layers = LayerList([
            Glm4MoeLiteDecoderLayer(c, dense=i < c.first_k_dense_replace)
            for i in range(c.num_hidden_layers)])
        self.norm = Glm4MoeLiteRMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, input_ids, cos, sin, remat: bool):
        """-> (last block's output before the final norm, the expert
        blocks' counters in order)."""
        hidden = self.embed_tokens(input_ids)
        counted = []
        for layer in self.layers:
            hidden, counters = _run_block(layer, hidden, cos, sin, remat)
            if counters is not None:
                counted.append(counters)
        return hidden, counted


class Glm4MoeLiteOutput(NamedTuple):
    logits: Tensor                  # [B, S, V] of the main head
    mtp_logits: Tuple[Tensor, ...]  # module k's [B, S, V]
    counters: dict                  # each router's, stacked [blocks, ...]


class Glm4MoeLiteForCausalLM(Layer):
    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        from ..framework import dtype as dtypes
        from ..nn.layer.container import LayerList

        self.config = config
        # every parameter is born in config.dtype (see LlamaModel)
        with dtypes.default_dtype(config.dtype):
            self.model = Glm4MoeLiteModel(config)
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            self.mtp = LayerList([
                Glm4MoeLiteMTP(config)
                for _ in range(config.num_nextn_predict_layers)])

    def forward(self, tokens):
        """Training: `tokens` [B, S + D] with D = `num_nextn_predict_layers`
        look-ahead columns — the inputs are tokens[:, :S], module k embeds
        tokens[:, 1+k : S+1+k] — and the result is a `Glm4MoeLiteOutput`.
        Evaluation (`eval()`): every column is an input, no module runs, and
        the result is the main head's logits."""
        c = self.config
        depth = len(self.mtp) if self.training else 0
        s = tokens.shape[1] - depth
        cos, sin = rope_freqs(s, c.qk_rope_head_dim, base=c.rope_theta)
        remat = c.recompute and not _tape.grad_enabled()
        hidden, counted = self.model(tokens[:, :s], cos, sin, remat)
        logits = self.lm_head(self.model.norm(hidden))
        if not self.training:
            return logits
        mtp_logits = []
        for k, module in enumerate(self.mtp):
            with jax.named_scope("mtp"):
                emb = self.model.embed_tokens(tokens[:, 1 + k:s + 1 + k])
                hidden, normed, counters = module(hidden, emb, cos, sin,
                                                  remat)
                mtp_logits.append(self.lm_head(normed))
            counted.append(counters)
        stacked = {k: jnp.stack([c_[k] for c_ in counted])
                   for k in counted[0]} if counted else {}
        return Glm4MoeLiteOutput(logits, tuple(mtp_logits), stacked)

    def routers(self):
        """[(the bias buffer's name in `raw_state()`, its gate)] in the order
        of the counters' rows."""
        return [(name + ".e_score_correction_bias", layer)
                for name, layer in self.named_sublayers()
                if isinstance(layer, BiasBalancedSigmoidGate)]

    def state_updates(self, state: dict, report: dict) -> dict:
        """New values of the leaves that are state, not parameters — each
        router's bias, moved by its balancing rule from the loads this step
        counted. `make_train_step` applies it inside the step, after the
        optimizer."""
        rate = self.config.router_bias_update_rate
        return {name: gate.updated_bias(state[name], report["moe.load"][i],
                                        rate)
                for i, (name, gate) in enumerate(self.routers())}


def _cross_entropy(logits, labels):
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, labels.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


class Glm4MoeLitePretrainingCriterion(Layer):
    """loss = CE(main head, next token) + `mtp_loss_weight` * mean over the
    modules of CE(module k, token k + 2 ahead). Returns (loss, report): the
    two parts and the routers' counters, summed over the expert blocks
    (`moe.load_max` their largest, `moe.load_mean` their mean, `moe.load`
    [blocks, experts] as counted)."""

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        self.config = config

    def forward(self, out: Glm4MoeLiteOutput, labels, *mtp_labels):
        def impl(lg, lb, *rest):
            n = len(rest) // 2
            main = _cross_entropy(lg, lb)
            parts = [_cross_entropy(a, b)
                     for a, b in zip(rest[:n], rest[n:])]
            mtp = sum(parts) / n if n else jnp.zeros((), jnp.float32)
            return main + self.config.mtp_loss_weight * mtp, main, mtp

        loss, main, mtp = dispatch(
            "glm4_moe_lite_ce", impl,
            (out.logits, labels) + tuple(out.mtp_logits) + tuple(mtp_labels))
        report = {"loss.main": unwrap(main), "loss.mtp": unwrap(mtp)}
        for k, v in out.counters.items():
            if k == "moe.choice":       # the routing itself: no counter
                continue
            if k == "moe.load":
                report[k] = v
            elif k == "moe.load_max":
                report[k] = jnp.max(v)
            elif k == "moe.load_mean":
                report[k] = jnp.mean(v)
            else:
                report[k] = jnp.sum(v)
        return loss, report
