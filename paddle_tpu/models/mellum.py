"""Mellum 2 (`model_type: mellum`) as the serving engine runs it: a decoder
whose layers alternate sliding-window and full attention (three window layers,
then a full one) and whose every MLP is a layer of routed experts.

The source (`JetBrains/Mellum2-12B-A2.5B-Instruct`, config.json) gives the
shapes; the layer equations are written from its keys:

- attention: q / k / v / o without bias, heads of `head_dim` (q is
  `num_attention_heads * head_dim` wide, NOT `hidden_size`), grouped, rotary
  over the whole head in rotate-half pairs. `layer_types[i]` picks the
  layer's mask and its table from `rope_parameters`: `sliding_attention`
  sees positions (t - sliding_window, t] and turns by the plain table;
  `full_attention` sees everything before it and turns by YaRN frequencies
  with `attention_factor` on cos and sin (kernels/rope.py).
- MLP: `softmax(x W_r)` over `num_experts` in f32, the `num_experts_per_tok`
  largest, renormalised to sum to one (`norm_topk_prob`), SwiGLU experts of
  width `moe_intermediate_size` through the dropless expert layer
  (parallel/moe.py) with every expert held; no shared expert.

No q / k norm and no MTP head are built: `config.json` has no key for
either. `intermediate_size` is the dense width of `mlp_layer_types: dense`
layers, of which the published model has none: a `dense` entry is refused.

There is no trainer here: the model exists as `served_model()` — the
contract of `models/llama.py`'s program builders — and seeded serving
parameters. A window mask in the trainer's flash kernels is ROADMAP M2.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.rope import YarnScaling
from .llama import MOE_COUNTS, ServedLayer, ServedModel

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


@dataclasses.dataclass
class MellumConfig:
    """The source's keys, under their names."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    # longer lists are cut to `num_hidden_layers` (a configuration cut in
    # depth keeps the source's lists whole)
    layer_types: Tuple[str, ...] = _PERIOD * 7
    mlp_layer_types: Tuple[str, ...] = ("sparse",) * 28
    rope_parameters: dict = dataclasses.field(
        default_factory=lambda: {k: dict(v) for k, v in _ROPE.items()})
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types"):
            kinds = tuple(getattr(self, name))
            if len(kinds) < n:
                raise ValueError(f"{name} names {len(kinds)} layers, "
                                 f"num_hidden_layers is {n}")
            setattr(self, name, kinds[:n])
        bad = set(self.layer_types) - set(_ROPE)
        if bad or set(self.layer_types) - set(self.rope_parameters):
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}: "
                             "each needs a group in rope_parameters, and "
                             f"only {sorted(_ROPE)} are built")
        if set(self.mlp_layer_types) != {"sparse"}:
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}: "
                             "only `sparse` layers are built")
        if self.attention_bias:
            raise ValueError("attention_bias is not built")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("heads must group evenly over kv heads, and "
                             "head_dim be even")

    @classmethod
    def from_dict(cls, d: dict, **over) -> "MellumConfig":
        """From a config.json's keys; the rest of the file is ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{**{k: v for k, v in d.items() if k in names}, **over})

    @staticmethod
    def tiny(**over) -> "MellumConfig":
        """One period of four layers at CPU-test sizes: a window of two
        pages of 8, eight experts, two a token."""
        return MellumConfig(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=8, num_experts_per_tok=2, sliding_window=16,
            max_position_embeddings=512, dtype="float32"), **over})

    def rope_of(self, kind: str):
        """(base, YarnScaling or None) of a layer kind's rotary table."""
        g = self.rope_parameters[kind]
        if g.get("rope_type", "default") == "default":
            return float(g["rope_theta"]), None
        if g["rope_type"] != "yarn":
            raise ValueError(f"rope_type {g['rope_type']!r} is not built")
        return float(g["rope_theta"]), YarnScaling(
            float(g["factor"]), int(g["original_max_position_embeddings"]),
            float(g.get("beta_fast", 32)), float(g.get("beta_slow", 1)),
            float(g["attention_factor"]))

    def served_model(self) -> ServedModel:
        routed = _routed_experts(self)
        layers = []
        for i, kind in enumerate(self.layer_types):
            base, scaling = self.rope_of(kind)
            layers.append(ServedLayer(
                f"model.layers.{i}.",
                self.sliding_window if kind == "sliding_attention" else None,
                base, scaling, routed))
        return ServedModel(
            embed="model.embed_tokens.weight", norm="model.norm.weight",
            head=None if self.tie_word_embeddings else "lm_head.weight",
            head_dim=self.head_dim, layers=tuple(layers))


def route(x, w_router, topk: int, norm_topk_prob: bool = True):
    """x [T, d] -> (experts [T, k] int32, gates [T, k] f32): softmax over all
    experts in f32, the `topk` largest (the lower index wins a tie),
    renormalised to sum to one."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    gates, idx = jax.lax.top_k(probs, topk)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates


def _routed_experts(cfg: MellumConfig):
    """The contract's MLP of a `sparse` layer: route, then the dropless
    expert layer with every expert held. Counts what the layer did
    (MOE_COUNTS): rows are (token, choice) pairs of every row of the call,
    pad rows and idle slots included — they are multiplied like any other."""
    from ..parallel.moe import dropless_experts

    n, k = cfg.num_experts, cfg.num_experts_per_tok
    held = tuple(range(n))

    def mlp(x, p, pre):
        rows = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe.route"):
            idx, gates = route(rows, p[pre + "mlp.gate.weight"], k,
                               cfg.norm_topk_prob)
        with jax.named_scope("moe.experts"):
            y, c = dropless_experts(
                rows, idx, gates, p[pre + "mlp.experts.gate_proj"],
                p[pre + "mlp.experts.up_proj"],
                p[pre + "mlp.experts.down_proj"], held, n)
        counted = {"layer_steps": 1, "rows_routed": c["moe.rows_routed"],
                   "experts_hit": jnp.sum(c["moe.load"] > 0),
                   "load_max": c["moe.load_max"],
                   "rows_multiplied": c["moe.rows_multiplied"]}
        return y.reshape(x.shape), jnp.stack(
            [jnp.asarray(counted[k], jnp.int32) for k in MOE_COUNTS])

    mlp.routed = True
    return mlp


def serving_param_shapes(cfg: MellumConfig) -> dict:
    """Name -> shape of every serving parameter."""
    h, dh = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
    f, e = cfg.moe_intermediate_size, cfg.num_experts
    out = {"model.embed_tokens.weight": (cfg.vocab_size, h)}
    for i, mlp in enumerate(cfg.mlp_layer_types):
        pre = f"model.layers.{i}."
        out.update({
            pre + "input_layernorm.weight": (h,),
            pre + "self_attn.q_proj.weight": (h, q),
            pre + "self_attn.k_proj.weight": (h, kv),
            pre + "self_attn.v_proj.weight": (h, kv),
            pre + "self_attn.o_proj.weight": (q, h),
            pre + "post_attention_layernorm.weight": (h,)})
        if mlp == "sparse":
            out.update({
                pre + "mlp.gate.weight": (h, e),
                pre + "mlp.experts.gate_proj": (e, h, f),
                pre + "mlp.experts.up_proj": (e, h, f),
                pre + "mlp.experts.down_proj": (e, f, h)})
        else:
            d = cfg.intermediate_size
            out.update({pre + "mlp.gate_proj.weight": (h, d),
                        pre + "mlp.up_proj.weight": (h, d),
                        pre + "mlp.down_proj.weight": (d, h)})
    out["model.norm.weight"] = (h,)
    if not cfg.tie_word_embeddings:
        out["lm_head.weight"] = (h, cfg.vocab_size)
    return out


def init_serving_params(cfg: MellumConfig, seed: int = 0,
                        dtype: Optional[str] = None) -> dict:
    """Every serving parameter made on the device from the seed, in the type
    it is served in: Xavier-normal matrices (an expert's fans are its own
    [d, f], not the stack's), norm scales 1. One jitted program makes a
    layer and is called once a layer; a second makes the rest."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    shapes = serving_param_shapes(cfg)

    def fill(group, key):
        out = {}
        for j, (name, shape) in enumerate(group.items()):
            if len(shape) == 1:
                out[name] = jnp.ones(shape, dtype)
                continue
            std = math.sqrt(2.0 / (shape[-2] + shape[-1]))
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, j), shape, jnp.float32)).astype(dtype)
        return out

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF, impl="rbg"),
                             seed >> 32)
    rest = {k: v for k, v in shapes.items()
            if not k.startswith("model.layers.")}
    out = jax.jit(lambda key: fill(rest, key))(jax.random.fold_in(key, 0))
    makers = {}
    for i, mlp in enumerate(cfg.mlp_layer_types):
        pre = f"model.layers.{i}."
        if mlp not in makers:
            group = {k[len(pre):]: v for k, v in shapes.items()
                     if k.startswith(pre)}
            makers[mlp] = jax.jit(lambda key, group=group: fill(group, key))
        made = makers[mlp](jax.random.fold_in(key, i + 1))
        out.update({pre + k: v for k, v in made.items()})
    return out
