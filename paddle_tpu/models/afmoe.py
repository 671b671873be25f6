"""The `afmoe` decoder family (Arcee Trinity-Mini, 26B-A3B), on the training
path. What the source's config.json states is taken under its keys; what it
is silent on is the public reference implementation of `model_type: afmoe`
(listed under `assumed` in `benchmark/configs/trinity-mini.json`).

- embedding scaled by sqrt(hidden_size) (`mup_enabled`);
- a layer is `a = h + N2(Attn(N1(h)))`, `h' = a + N4(MLP(N3(a)))`: four
  RMSNorms, one before and one after each sub-block;
- attention: grouped heads of `head_dim` (q, and the gate, are
  `num_attention_heads * head_dim` wide), an RMSNorm over the head on q and
  on k, the output multiplied by `sigmoid(Wg x)` before `Wo`.
  `layer_types[l]` picks the mask and the table: `sliding_attention` turns q
  and k by the rotary table at `rope_theta` and sees the keys s with
  0 <= t - s < `sliding_window` (the flash kernels' window: the blocks
  behind it are skipped); `full_attention` is causal and NOT rotated;
- the first `num_dense_layers` MLPs are SwiGLU at `intermediate_size`; the
  others are `parallel.moe`'s dropless layer over the experts HELD here (one
  chip's share of an expert-parallel group, `config.held`; all by default),
  routed over all `num_experts` by the bias-balanced sigmoid gate
  (`route_norm`, `route_scale`), plus `num_shared_experts` shared ones.

`AfmoeForCausalLM` goes through `parallel.make_train_step` as
`Glm4MoeLiteForCausalLM` does: the criterion returns the loss with a report
(`moe.*`, `attn.window_pairs_*`), `state_updates` is the routers' bias rule
at `load_balance_coeff`, applied inside the step, outside the optimizer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, dispatch
from ..kernels.rms_norm import rms_norm as _k_rms
from ..kernels.rope import apply_rotary_emb, rope_freqs
from ..nn import functional as F
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..parallel.moe import BiasBalancedSigmoidGate, DroplessMoELayer

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass
class AfmoeConfig:
    """Field names are the published config.json's; defaults are
    Trinity-Mini's. `held` is the deployment's."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144           # the leading dense layers' MLP
    moe_intermediate_size: int = 1024       # one expert's, and the shared's
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128                  # the router's width
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    route_norm: bool = True
    route_scale: float = 2.826
    sliding_window: int = 2048
    # longer lists are cut to `num_hidden_layers` (a configuration cut in
    # depth keeps the source's list whole)
    layer_types: Tuple[str, ...] = _PERIOD * 8
    mup_enabled: bool = True
    # the routers' bias moves by this much a step: sign(mean load - load)
    load_balance_coeff: float = 0.001
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # global indices of the routed experts this chip holds; None = all
    held: Optional[Tuple[int, ...]] = None
    dtype: str = "float32"

    def __post_init__(self):
        if self.held is not None:
            self.held = tuple(int(e) for e in self.held)
        kinds = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers or \
                set(kinds) - {"sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types {kinds} does not name "
                             f"{self.num_hidden_layers} sliding_attention / "
                             "full_attention layers")
        self.layer_types = kinds

    @classmethod
    def from_dict(cls, m: dict, **over) -> "AfmoeConfig":
        """From a config.json-like dict: the keys that are fields, the rest
        left where they are. Where the dict is a cut configuration, the
        router keeps the width under `published.num_experts` (`num_experts`
        then counts the experts held) and `deployment.held` names them."""
        names = {f.name for f in dataclasses.fields(cls)}
        picked = {k: v for k, v in m.items() if k in names}
        if "num_experts" in m.get("published", {}):
            picked["num_experts"] = m["published"]["num_experts"]
        if "held" in m.get("deployment", {}):
            picked["held"] = m["deployment"]["held"]
        return cls(**{**picked, **over})


class AfmoeRMSNorm(Layer):
    def __init__(self, dim: int, eps: float):
        super().__init__()
        from ..nn.initializer import Constant

        self.eps = eps
        self.weight = self.create_parameter(
            [dim], default_initializer=Constant(1.0))

    def forward(self, x):
        return dispatch("rms_norm", lambda a, w: _k_rms(a, w, self.eps),
                        (x, self.weight))


def window_of(c: AfmoeConfig, layer: int, seq: int) -> Optional[int]:
    """The window layer `layer` runs under at `seq` rows: None for a full
    layer, and for a window that no row of the sequence outgrows."""
    if c.layer_types[layer] == "sliding_attention" and c.sliding_window < seq:
        return c.sliding_window
    return None


class AfmoeAttention(Layer):
    """Grouped attention with q/k norms and an output gate; rotary and a
    window on the `sliding_attention` layers alone."""

    def __init__(self, c: AfmoeConfig, layer: int):
        super().__init__()
        self.config, self.layer = c, layer
        self.rotated = c.layer_types[layer] == "sliding_attention"
        nh, nkv, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.q_proj = Linear(c.hidden_size, nh * dh, bias_attr=False)
        self.k_proj = Linear(c.hidden_size, nkv * dh, bias_attr=False)
        self.v_proj = Linear(c.hidden_size, nkv * dh, bias_attr=False)
        self.gate_proj = Linear(c.hidden_size, nh * dh, bias_attr=False)
        self.o_proj = Linear(nh * dh, c.hidden_size, bias_attr=False)
        self.q_norm = AfmoeRMSNorm(dh, c.rms_norm_eps)
        self.k_norm = AfmoeRMSNorm(dh, c.rms_norm_eps)

    def forward(self, hidden, cos, sin):
        c = self.config
        b, s, _ = hidden.shape
        nh, nkv, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = self.q_norm(self.q_proj(hidden).reshape([b, s, nh, dh]))
        k = self.k_norm(self.k_proj(hidden).reshape([b, s, nkv, dh]))
        v = self.v_proj(hidden).reshape([b, s, nkv, dh])
        if self.rotated:
            q, k = dispatch(
                "afmoe_rope",
                lambda q_, k_: apply_rotary_emb(q_, k_, cos=cos, sin=sin),
                (q, k), n_outs=2)
        with jax.named_scope("attn.window" if self.rotated else "attn.full"):
            out, _ = F.flash_attention(q, k, v, causal=True,
                                       window=window_of(c, self.layer, s))
        with jax.named_scope("attn.gate"):
            out = out.reshape([b, s, nh * dh]) \
                * F.sigmoid(self.gate_proj(hidden))
        return self.o_proj(out)


class AfmoeMLP(Layer):
    def __init__(self, hidden: int, inter: int):
        super().__init__()
        self.gate_proj = Linear(hidden, inter, bias_attr=False)
        self.up_proj = Linear(hidden, inter, bias_attr=False)
        self.down_proj = Linear(inter, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class AfmoeMoE(DroplessMoELayer):
    """The held routed experts' part plus the shared expert."""

    def __init__(self, c: AfmoeConfig):
        super().__init__(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, held=c.held, norm_topk_prob=c.route_norm,
            routed_scaling_factor=c.route_scale)
        self.shared_experts = AfmoeMLP(
            c.hidden_size, c.moe_intermediate_size * c.num_shared_experts)

    def forward(self, x):
        routed, counters = super().forward(x)
        with jax.named_scope("moe.shared"):
            shared = self.shared_experts(x)
        return shared + routed, counters


class AfmoeDecoderLayer(Layer):
    def __init__(self, c: AfmoeConfig, layer: int):
        super().__init__()
        self.dense = layer < c.num_dense_layers
        self.self_attn = AfmoeAttention(c, layer)
        self.mlp = AfmoeMLP(c.hidden_size, c.intermediate_size) \
            if self.dense else AfmoeMoE(c)
        for name in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm"):
            setattr(self, name, AfmoeRMSNorm(c.hidden_size, c.rms_norm_eps))

    def forward(self, hidden, cos, sin):
        """-> (hidden, the router's counters; None for a dense layer)."""
        hidden = hidden + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(hidden), cos, sin))
        x = self.pre_mlp_layernorm(hidden)
        y, counters = (self.mlp(x), None) if self.dense else self.mlp(x)
        return hidden + self.post_mlp_layernorm(y), counters


class AfmoeModel(Layer):
    def __init__(self, c: AfmoeConfig):
        super().__init__()
        from ..nn.layer.container import LayerList

        self.config = c
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size)
        self.layers = LayerList([AfmoeDecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = AfmoeRMSNorm(c.hidden_size, c.rms_norm_eps)

    def forward(self, input_ids):
        """-> (the final norm's output, the expert blocks' counters in
        order)."""
        c = self.config
        hidden = self.embed_tokens(input_ids)
        if c.mup_enabled:
            hidden = hidden * math.sqrt(c.hidden_size)
        cos, sin = rope_freqs(input_ids.shape[1], c.head_dim,
                              base=c.rope_theta)
        counted = []
        for layer in self.layers:
            hidden, counters = layer(hidden, cos, sin)
            if counters is not None:
                counted.append(counters)
        return self.norm(hidden), counted


def window_pairs(c: AfmoeConfig, batch: int, seq: int) -> dict:
    """What the window layers' kernels do in a step (forward and backward),
    a head: the (query, key) pairs of the score blocks they sweep and the
    pairs inside the mask. Constants of the shapes."""
    from ..kernels.flash_attention import window_pairs as pairs

    layers = sum(window_of(c, i, seq) is not None
                 for i in range(c.num_hidden_layers))
    fwd, bwd, mask = pairs(seq, c.num_attention_heads, c.num_key_value_heads,
                           c.head_dim, c.sliding_window,
                           jax.default_backend() == "tpu")
    return {"attn.window_pairs_swept": float(layers * batch * (fwd + bwd)),
            "attn.window_pairs_in_mask": float(layers * batch * 2 * mask)}


class AfmoeOutput(NamedTuple):
    logits: Tensor                  # [B, S, V]
    counters: dict                  # each router's, stacked [blocks, ...]


class AfmoeForCausalLM(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        from ..framework import dtype as dtypes

        self.config = config
        # every parameter is born in config.dtype (see LlamaModel)
        with dtypes.default_dtype(config.dtype):
            self.model = AfmoeModel(config)
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, tokens):
        """Training: an `AfmoeOutput` of the logits of `tokens` [B, S] and
        what the step counted. Evaluation (`eval()`): the logits."""
        hidden, counted = self.model(tokens)
        logits = self.lm_head(hidden)
        if not self.training:
            return logits
        stacked = {k: jnp.stack([c_[k] for c_ in counted])
                   for k in counted[0]} if counted else {}
        stacked.update({k: jnp.asarray(v, jnp.float32) for k, v in
                        window_pairs(self.config, *tokens.shape).items()})
        return AfmoeOutput(logits, stacked)

    def routers(self):
        """[(the bias buffer's name in `raw_state()`, its gate)] in the order
        of the counters' rows."""
        return [(name + ".e_score_correction_bias", layer)
                for name, layer in self.named_sublayers()
                if isinstance(layer, BiasBalancedSigmoidGate)]

    def state_updates(self, state: dict, report: dict) -> dict:
        """New values of the leaves that are state, not parameters — each
        router's bias, moved by `load_balance_coeff` from the loads this
        step counted. `make_train_step` applies it inside the step, after
        the optimizer."""
        rate = self.config.load_balance_coeff
        return {name: gate.updated_bias(state[name], report["moe.load"][i],
                                        rate)
                for i, (name, gate) in enumerate(self.routers())}


class AfmoePretrainingCriterion(Layer):
    """Mean next-token cross-entropy (no auxiliary term: the routers balance
    by their bias). Returns (loss, report): the routers' counters summed over
    the expert blocks (`moe.load_max` their largest, `moe.load_mean` their
    mean, `moe.load` [blocks, experts] as counted) and the window kernels'
    pair counts."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config

    def forward(self, out: AfmoeOutput, labels):
        from .glm4_moe_lite import _cross_entropy

        loss = dispatch("afmoe_ce", _cross_entropy, (out.logits, labels))
        report = {}
        for k, v in out.counters.items():
            if k == "moe.choice":       # the routing itself: no counter
                continue
            if k == "moe.load" or k.startswith("attn."):
                report[k] = v
            elif k == "moe.load_max":
                report[k] = jnp.max(v)
            elif k == "moe.load_mean":
                report[k] = jnp.mean(v)
            else:
                report[k] = jnp.sum(v)
        return loss, report
