"""Falcon-H1 (`model_type: falcon_h1`) as the serving engine runs it: in every
layer an attention block and a Mamba-2 mixer side by side on the same normed
input, then a dense SwiGLU, with the μP multipliers as published.

The source (`tiiuae/Falcon-H1-34B-Instruct`, config.json) gives the shapes and
the multipliers; the equations follow the public reference implementation of
`model_type: falcon_h1`. h is the residual, x a token's normed layer input.

    x = RMSNorm(h)                                  (input_layernorm)
    attention: grouped causal attention over every earlier position, rotary
      over the whole head (rope_theta), no bias, reading x *
      attention_in_multiplier; k = (x W_k) * key_multiplier;
      a = o_proj(attn) * attention_out_multiplier
    Mamba-2 mixer (mamba_d_ssm = heads x mamba_d_head, mamba_n_groups
      groups of mamba_d_state, head h reads group h // (heads / groups)):
      u = x * ssm_in_multiplier
      [z | x_s | B | C | dt] = (u W_in) * mup, mup = ssm_multipliers by part
      [x_s | B | C] = silu(causal depthwise conv1d, mamba_d_conv taps, + bias)
      Δ = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ;  y_t = S_t C_t + D x_t
      y = RMSNorm_grouped(y * silu(z)) w  (mamba_n_groups groups; the gate
      first: mamba_norm_before_gate false)
      m = out_proj(y) * ssm_out_multiplier
    h <- h + a + m
    h <- h + down(silu(gate(x') * mlp_multipliers[0]) * up(x'))
              * mlp_multipliers[1],   x' = RMSNorm(h)   (pre_ff_layernorm)
    the embedding times embedding_multiplier; the final norm before an
    untied head, the logits times lm_head_multiplier.

Served: the attention block is the program builders' own (paged K/V, the
decode and ragged attention kernels) with the layer's multipliers
(`ServedLayer.attn_scales`); the mixer (`ServedLayer.mixer`) keeps a state of
fixed size a sequence, in float32: S of every head (`kernels/ssd.py`'s
layout) and the conv's last mamba_d_conv - 1 inputs. The decode lane runs one
step of it a token (`ssd_decode`), a prefill window the chunked scan
(`ssd_scan`, chunks of mamba_chunk_size) from the sequence's carried state,
or from zero at its first position. The second norm is read under the
builders' name (`post_attention_layernorm`).

`assumed` (no key fixes these; program and reference use the same): the state
is float32; the conv's weight is stored as [taps, channels].

Any key value other than the published model's where the code path would
differ is refused by name.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ssd import ssd_decode, ssd_scan
from .llama import ServedLayer, ServedModel

# what the mixers count, summed by the programs: rows the decode lane
# stepped (idle rows left out) and, of a prefill window, the tokens scanned,
# the segments and those that began from a carried state
SSM_COUNTS = ("ssm_decode_slot_steps", "ssm_scan_tokens", "ssm_scan_segments",
              "ssm_scan_carried")
# keys that must hold these values: another value would be another model
_FIXED = {"model_type": "falcon_h1", "hidden_act": "silu",
          "attention_bias": False, "mlp_bias": False,
          "projectors_bias": False, "mamba_proj_bias": False,
          "mamba_conv_bias": True, "mamba_norm_before_gate": False,
          "mamba_rms_norm": True, "mamba_use_mlp": True,
          "attn_layer_indices": None, "rope_scaling": None,
          "tie_word_embeddings": False}
# keys read by nothing the served model does: the context the positions may
# reach, the logits a forward keeps, and the factors the given widths were
# derived from (mamba_d_ssm and intermediate_size are given)
_UNUSED = ("max_position_embeddings", "num_logits_to_keep", "mamba_expand",
           "mlp_expansion_factor")
# keys of a benchmark configuration's file that are no model key
_FILE_KEYS = ("source", "published", "reduced", "assumed", "deployment",
              "notes")


@dataclasses.dataclass
class FalconH1Config:
    """The source's keys that shape the model, under their names."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (0.3535533905932738, 0.25,
                                          0.1767766952966369, 0.5,
                                          0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.mlp_multipliers = tuple(float(v) for v in self.mlp_multipliers)
        self.ssm_multipliers = tuple(float(v) for v in self.ssm_multipliers)
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError("mlp_multipliers takes 2 values (gate, down), "
                             "ssm_multipliers 5 (z, x, B, C, dt)")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError("the mixer's heads must split evenly over "
                             "mamba_n_groups")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the q heads must split evenly over the kv "
                             "heads")

    @classmethod
    def from_dict(cls, d: dict, **over) -> "FalconH1Config":
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
    def tiny(**over) -> "FalconH1Config":
        """Two layers at CPU-test sizes, chunks of 8 tokens."""
        return FalconH1Config(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, rope_theta=1e4,
            mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
            mamba_n_groups=2, mamba_d_state=16, mamba_chunk_size=8,
            dtype="float32"), **over})

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: x_s, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def proj_dim(self) -> int:
        """Columns of W_in: z, x_s, B, C and dt."""
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    def served_model(self) -> ServedModel:
        mixer = Mamba2Mixer(self)
        scales = (float(self.attention_in_multiplier),
                  float(self.key_multiplier),
                  float(self.attention_out_multiplier))
        layers = tuple(ServedLayer(
            f"model.layers.{i}.", None, float(self.rope_theta), None,
            _mlp(self), mixer=mixer, attn_scales=scales)
            for i in range(self.num_hidden_layers))
        return ServedModel(
            embed="model.embed_tokens.weight",
            norm="model.final_layernorm.weight", head="lm_head.weight",
            head_dim=self.head_dim, layers=layers, counts=SSM_COUNTS,
            embed_scale=float(self.embedding_multiplier),
            logit_scale=float(self.lm_head_multiplier))


def _mlp(cfg: FalconH1Config):
    gate_mult, down_mult = cfg.mlp_multipliers

    def mlp(x, p, pre):
        f = pre + "feed_forward."
        gate = jnp.dot(x, p[f + "gate_proj.weight"]) * gate_mult
        up = jnp.dot(x, p[f + "up_proj.weight"])
        return jnp.dot(jax.nn.silu(gate) * up,
                       p[f + "down_proj.weight"]) * down_mult, None
    return mlp


# ---- the mixer ---------------------------------------------------------------

def mup_vector(cfg: FalconH1Config):
    """W_in's column multipliers: ssm_multipliers over z, x_s, B, C, dt."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


class Mamba2Mixer:
    """The layer's Mamba-2 mixer over a state of fixed size a sequence
    (`ServedLayer.mixer`): a row of `state(rows)` is a sequence's S
    [heads, d_state, d_head] (`kernels/ssd.py`'s layout) and its conv's last
    d_conv - 1 inputs, oldest first, flat ([(d_conv - 1) x conv_dim]: a row
    is then one slab of whole tiles, which a row's gather and scatter read
    and write in the array's own layout), both float32."""

    def __init__(self, cfg: FalconH1Config):
        self.cfg = cfg
        self.mup = mup_vector(cfg)

    def state(self, rows: int):
        c = self.cfg
        return (jnp.zeros((rows, c.mamba_n_heads, c.mamba_d_state,
                           c.mamba_d_head), jnp.float32),
                jnp.zeros((rows, (c.mamba_d_conv - 1) * c.conv_dim),
                          jnp.float32))

    def row_bytes(self) -> int:
        c = self.cfg
        return 4 * (c.mamba_d_ssm * c.mamba_d_state
                    + (c.mamba_d_conv - 1) * c.conv_dim)

    def _project(self, x, p, pre):
        """x [..., hidden] -> (z, [x_s | B | C] before the conv, dt raw),
        f32."""
        c = self.cfg
        u = x * c.ssm_in_multiplier
        zxbcdt = jnp.dot(u, p[pre + "mamba.in_proj.weight"],
                         preferred_element_type=jnp.float32) * self.mup
        d = c.mamba_d_ssm
        return (zxbcdt[..., :d], zxbcdt[..., d:d + c.conv_dim],
                zxbcdt[..., d + c.conv_dim:])

    def _split(self, xbc):
        """The conv's output [..., conv_dim] -> x_s [..., heads, d_head],
        B and C [..., groups, d_state]."""
        c = self.cfg
        d, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
        lead = xbc.shape[:-1]
        return (xbc[..., :d].reshape(*lead, c.mamba_n_heads, c.mamba_d_head),
                xbc[..., d:d + gn].reshape(*lead, c.mamba_n_groups,
                                           c.mamba_d_state),
                xbc[..., d + gn:].reshape(*lead, c.mamba_n_groups,
                                          c.mamba_d_state))

    def _dt(self, dt, p, pre):
        """(Δ, A) of every head: softplus(dt + dt_bias), -exp(A_log)."""
        return (jax.nn.softplus(dt + p[pre + "mamba.dt_bias"]),
                -jnp.exp(p[pre + "mamba.A_log"]))

    def _out(self, y, z, xs, p, pre, dtype):
        """y [..., heads, d_head] (S C) -> the mixer's output [..., hidden]:
        the skip term, the gate, the grouped norm, out_proj, the
        multiplier."""
        c = self.cfg
        y = y + p[pre + "mamba.D"][:, None] * xs
        y = y.reshape(*y.shape[:-2], c.mamba_d_ssm) * jax.nn.silu(z)
        g = y.reshape(*y.shape[:-1], c.mamba_n_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + c.rms_norm_eps)
        y = g.reshape(y.shape) * p[pre + "mamba.norm.weight"]
        return jnp.dot(y.astype(dtype), p[pre + "mamba.out_proj.weight"]) \
            * c.ssm_out_multiplier

    def _conv(self, window, p, pre):
        """silu of the causal conv: window [..., taps, conv_dim]."""
        w = p[pre + "mamba.conv1d.weight"]               # [taps, conv_dim]
        return jax.nn.silu(jnp.einsum("...kc,kc->...c", window, w)
                           + p[pre + "mamba.conv1d.bias"])

    def decode(self, x, p, pre, state, lane):
        """One token of every batch row: x [b, 1, hidden]; the rows'
        states at `lane["state_rows"]` (idle rows: the sink row 0)."""
        ssm, conv = state
        rows = lane["state_rows"]
        with jax.named_scope("ssm.mixer"):
            z, xbc, dt = self._project(x[:, 0], p, pre)
            window = jnp.concatenate(
                [conv[rows].reshape(len(rows), -1, xbc.shape[-1]),
                 xbc[:, None]], axis=1)
            conv = conv.at[rows].set(window[:, 1:].reshape(len(rows), -1))
            xs, b, c = self._split(self._conv(window, p, pre))
            delta, a = self._dt(dt, p, pre)
            y, ssm = ssd_decode(ssm, rows, delta[..., None] * xs,
                                jnp.exp(delta * a), b, c)
            m = self._out(y, z, xs, p, pre, x.dtype)
        live = jnp.sum(rows != 0).astype(jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        return m[:, None], (ssm, conv), jnp.stack([live, zero, zero, zero])

    def window(self, x, p, pre, state, lane):
        """A prefill window of one sequence: x [1, T, hidden], its first
        `lane["new_len"]` rows real; the sequence's state at
        `lane["state_row"]`, from zero where `lane["fresh"]`."""
        cfg = self.cfg
        ssm, conv = state
        row, fresh, n = lane["state_row"], lane["fresh"], lane["new_len"]
        t = x.shape[1]
        taps = cfg.mamba_d_conv
        with jax.named_scope("ssm.mixer"):
            z, xbc, dt = self._project(x[0], p, pre)
            prev = jnp.where(fresh, 0.0, conv[row]).reshape(taps - 1, -1)
            ext = jnp.concatenate([prev, xbc])           # [taps - 1 + T, C]
            conv = conv.at[row].set(
                jax.lax.dynamic_slice_in_dim(ext, n, taps - 1).reshape(-1))
            window = jnp.stack([ext[k:k + t] for k in range(taps)], axis=1)
            xs, b, c = self._split(self._conv(window, p, pre))
            delta, a = self._dt(dt, p, pre)
            delta = jnp.where((jnp.arange(t) < n)[:, None], delta, 0.0)
            y, ssm = ssd_scan(
                ssm, row[None], fresh[None].astype(jnp.int32),
                jnp.swapaxes(delta[..., None] * xs, 0, 1)[None],
                (delta * a).T[None], jnp.swapaxes(b, 0, 1)[None],
                jnp.swapaxes(c, 0, 1)[None], chunk=cfg.mamba_chunk_size)
            m = self._out(jnp.swapaxes(y[0], 0, 1), z, xs, p, pre, x.dtype)
        some = (n > 0).astype(jnp.int32)
        return m[None], (ssm, conv), jnp.stack([
            jnp.zeros((), jnp.int32), n.astype(jnp.int32), some,
            some * (~fresh).astype(jnp.int32)])


# ---- parameters ----------------------------------------------------------

def serving_param_shapes(cfg: FalconH1Config) -> dict:
    """Name -> shape of every serving parameter."""
    c = cfg
    hd, f = c.hidden_size, c.intermediate_size
    q, kv = c.num_attention_heads * c.head_dim, \
        c.num_key_value_heads * c.head_dim
    out = {"model.embed_tokens.weight": (c.vocab_size, hd)}
    for i in range(c.num_hidden_layers):
        pre = f"model.layers.{i}."
        out.update({
            pre + "input_layernorm.weight": (hd,),
            pre + "post_attention_layernorm.weight": (hd,),
            pre + "self_attn.q_proj.weight": (hd, q),
            pre + "self_attn.k_proj.weight": (hd, kv),
            pre + "self_attn.v_proj.weight": (hd, kv),
            pre + "self_attn.o_proj.weight": (q, hd),
            pre + "mamba.in_proj.weight": (hd, c.proj_dim),
            pre + "mamba.conv1d.weight": (c.mamba_d_conv, c.conv_dim),
            pre + "mamba.conv1d.bias": (c.conv_dim,),
            pre + "mamba.dt_bias": (c.mamba_n_heads,),
            pre + "mamba.A_log": (c.mamba_n_heads,),
            pre + "mamba.D": (c.mamba_n_heads,),
            pre + "mamba.norm.weight": (c.mamba_d_ssm,),
            pre + "mamba.out_proj.weight": (c.mamba_d_ssm, hd),
            pre + "feed_forward.gate_proj.weight": (hd, f),
            pre + "feed_forward.up_proj.weight": (hd, f),
            pre + "feed_forward.down_proj.weight": (f, hd)})
    out["model.final_layernorm.weight"] = (hd,)
    out["lm_head.weight"] = (hd, c.vocab_size)
    return out


# parameters kept in f32 whatever the served type: the mixer's per-head
# scalars and its conv
F32_PARAMS = ("dt_bias", "A_log", "mamba.D", "conv1d.weight", "conv1d.bias")


def param_dtype(name: str, dtype):
    return jnp.float32 if name.endswith(F32_PARAMS) else jnp.dtype(dtype)


def out_multipliers(cfg: FalconH1Config, name: str) -> float:
    """The product of the multipliers a matrix's output passes through
    before it joins what follows: its columns' share of `mup` for W_in is
    left to `init_serving_params`."""
    c = cfg
    if name.endswith("k_proj.weight"):
        return c.attention_in_multiplier * c.key_multiplier
    if name.endswith(("q_proj.weight", "v_proj.weight")):
        return c.attention_in_multiplier
    if name.endswith("o_proj.weight"):
        return c.attention_out_multiplier
    if name.endswith("out_proj.weight"):
        return c.ssm_out_multiplier
    if name.endswith("gate_proj.weight"):
        return c.mlp_multipliers[0]
    if name.endswith("down_proj.weight"):
        return c.mlp_multipliers[1]
    if name.endswith("lm_head.weight"):
        return c.lm_head_multiplier
    return 1.0


# the range of Δ the dt bias is drawn for (Mamba-2's own initializer)
DT_RANGE = (1e-3, 1e-1)


def _init_value(cfg: FalconH1Config, name: str, shape, key, dtype):
    """Seeded value of one parameter. A matrix is N(0, 1 / (fan_in m^2)),
    m the product of the multipliers its output passes through (W_in's
    columns: ssm_in_multiplier times their part of `mup`), so that every
    projection's scaled output has unit deviation at a unit-RMS input and
    the μP-scaled logits do not collapse; the embedding's rows N(0, 1).
    A_log = log U(1, 16); dt_bias the inverse softplus of a Δ log-uniform
    over DT_RANGE; D = 1; the conv's taps and bias U(-1/2, 1/2) (its fan-in
    of 4 taps); norm scales 1."""
    dt = param_dtype(name, dtype)
    if name.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name.endswith("dt_bias"):
        lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
        d = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return d + jnp.log(-jnp.expm1(-d))
    if name.endswith("mamba.D") or len(shape) == 1 \
            and not name.endswith("conv1d.bias"):
        return jnp.ones(shape, dt)
    if name.endswith(("conv1d.weight", "conv1d.bias")):
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    normal = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("embed_tokens.weight"):
        return normal.astype(dt)
    if name.endswith("in_proj.weight"):
        m = cfg.ssm_in_multiplier * mup_vector(cfg)
        return (normal / (math.sqrt(shape[0]) * m)).astype(dt)
    m = out_multipliers(cfg, name)
    return (normal / (math.sqrt(shape[0]) * m)).astype(dt)


def init_serving_params(cfg: FalconH1Config, seed: int = 0,
                        dtype: Optional[str] = None) -> dict:
    """Every serving parameter made on the device from the seed, in the type
    it is served in (`param_dtype`). One jitted program makes a layer and is
    called once a layer; another makes the rest."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    shapes = serving_param_shapes(cfg)

    def fill(group, key):
        return {name: _init_value(cfg, name, shape,
                                  jax.random.fold_in(key, j), dtype)
                for j, (name, shape) in enumerate(group.items())}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF, impl="rbg"),
                             seed >> 32)
    rest = {k: v for k, v in shapes.items()
            if not k.startswith("model.layers.")}
    out = jax.jit(lambda key: fill(rest, key))(jax.random.fold_in(key, 0))
    pre0 = "model.layers.0."
    group = {k[len(pre0):]: v for k, v in shapes.items()
             if k.startswith(pre0)}
    make_layer = jax.jit(lambda key: fill(group, key))
    for i in range(cfg.num_hidden_layers):
        made = make_layer(jax.random.fold_in(key, i + 1))
        out.update({f"model.layers.{i}.{k}": v for k, v in made.items()})
    return out
