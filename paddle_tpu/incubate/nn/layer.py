"""Fused transformer layers (reference:
python/paddle/incubate/nn/layer/{fused_linear,fused_transformer,
fused_dropout_add}.py).

TPU-native form: "fused" here means one traced region XLA compiles into
fused kernels — packed qkv projection, pre/post-norm residual blocks —
rather than hand-written fused CUDA kernels. Parameter layout follows the
reference (qkv_weight [3, num_heads, head_dim, embed_dim]) so state_dicts
line up. Dropout placement follows the reference: attention-probability
dropout (attn_dropout_rate), branch dropout before the residual add
(dropout_rate), and activation dropout in the FFN (act_dropout_rate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import dispatch
from ...nn.layer.layers import Layer
from ...nn.initializer import Constant
from ...nn import functional as NF
from . import functional as IF

__all__ = ["FusedLinear", "FusedMultiHeadAttention", "FusedFeedForward",
           "FusedTransformerEncoderLayer", "FusedMultiTransformer",
           "FusedDropoutAdd", "FusedBiasDropoutResidualLayerNorm",
           "FusedEcMoe"]


class FusedLinear(Layer):
    """reference: layer/fused_linear.py FusedLinear — gemm with fused bias
    epilogue (XLA does this fusion natively)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, transpose_weight=False, name=None):
        super().__init__()
        shape = (out_features, in_features) if transpose_weight else \
            (in_features, out_features)
        self.weight = self.create_parameter(shape, attr=weight_attr)
        self.bias = None if bias_attr is False else self.create_parameter(
            (out_features,), attr=bias_attr, is_bias=True)
        self.transpose_weight = transpose_weight

    def forward(self, x):
        return IF.fused_linear(x, self.weight, self.bias,
                               transpose_weight=self.transpose_weight)


class FusedDropoutAdd(Layer):
    """reference: layer/fused_dropout_add.py — dropout(x) + y in one
    region."""

    def __init__(self, p=0.5, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.mode = mode

    def forward(self, x, y):
        out = NF.dropout(x, p=self.p, training=self.training,
                         mode=self.mode)
        return out + y


class FusedMultiHeadAttention(Layer):
    """reference: layer/fused_transformer.py:189 — packed-qkv attention
    with fused pre/post layer-norm and residual."""

    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None,
                 normalize_before=False, need_weights=False,
                 qkv_weight_attr=None, qkv_bias_attr=None,
                 linear_weight_attr=None, linear_bias_attr=None,
                 pre_ln_scale_attr=None, pre_ln_bias_attr=None,
                 ln_scale_attr=None, ln_bias_attr=None, epsilon=1e-5,
                 nranks=1, ring_id=-1, name=None):
        super().__init__()
        if embed_dim % num_heads:
            raise ValueError("embed_dim must divide num_heads")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.attn_dropout_rate = attn_dropout_rate
        self.epsilon = epsilon
        # reference layout: [3, num_heads, head_dim, embed_dim]
        qkv_shape = (3, num_heads, self.head_dim, embed_dim)
        self.qkv_weight = self.create_parameter(qkv_shape,
                                                attr=qkv_weight_attr)
        self.qkv_bias = None if qkv_bias_attr is False else \
            self.create_parameter((3, num_heads, self.head_dim),
                                  attr=qkv_bias_attr, is_bias=True)
        self.linear_weight = self.create_parameter(
            (embed_dim, embed_dim), attr=linear_weight_attr)
        self.linear_bias = None if linear_bias_attr is False else \
            self.create_parameter((embed_dim,), attr=linear_bias_attr,
                                  is_bias=True)
        one = Constant(1.0)
        self.pre_ln_scale = self.create_parameter(
            (embed_dim,), attr=pre_ln_scale_attr, default_initializer=one)
        self.pre_ln_bias = self.create_parameter(
            (embed_dim,), attr=pre_ln_bias_attr, is_bias=True)
        self.ln_scale = self.create_parameter(
            (embed_dim,), attr=ln_scale_attr, default_initializer=one)
        self.ln_bias = self.create_parameter(
            (embed_dim,), attr=ln_bias_attr, is_bias=True)

    def _ln(self, x, scale, bias):
        return NF.layer_norm(x, (self.embed_dim,), weight=scale,
                             bias=bias, epsilon=self.epsilon)

    def _attn_branch(self, x, attn_mask, probs_mask):
        """Everything between the (optional) pre-norm and the branch
        dropout: packed qkv -> softmax(+ prob dropout) -> out proj."""
        args = [a for a in (x, self.qkv_weight, self.qkv_bias,
                            self.linear_weight, self.linear_bias,
                            attn_mask, probs_mask) if a is not None]

        def impl(*arrs):
            it = iter(arrs)
            xa = next(it)
            qkv_w = next(it)
            qkv_b = next(it) if self.qkv_bias is not None else None
            lw = next(it)
            lb = next(it) if self.linear_bias is not None else None
            mask = next(it) if attn_mask is not None else None
            u = next(it) if probs_mask is not None else None
            qkv = jnp.einsum("bse,nhde->nbshd", xa, qkv_w)
            if qkv_b is not None:
                qkv = qkv + qkv_b[:, None, None]
            q, k, v = qkv[0], qkv[1], qkv[2]
            scale = 1.0 / jnp.sqrt(jnp.asarray(self.head_dim, jnp.float32))
            logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * scale
            if mask is not None:
                logits = logits + mask.astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
            if u is not None:
                keep = (u >= self.attn_dropout_rate).astype(probs.dtype)
                probs = probs * keep / (1.0 - self.attn_dropout_rate)
            ctx = jnp.einsum("bhst,bthd->bshd", probs,
                             v.astype(jnp.float32)).astype(xa.dtype)
            ctx = ctx.reshape(*ctx.shape[:2], self.embed_dim)
            out = ctx @ lw
            if lb is not None:
                out = out + lb
            return out

        return dispatch("fused_multi_head_attention", impl, tuple(args))

    def forward(self, query, key=None, value=None, attn_mask=None,
                cache=None):
        import paddle_tpu as _p

        residual = query
        x = self._ln(query, self.pre_ln_scale, self.pre_ln_bias) \
            if self.normalize_before else query
        probs_mask = None
        if self.training and self.attn_dropout_rate:
            b, s = x.shape[0], x.shape[1]
            probs_mask = _p.rand([b, self.num_heads, s, s])
        out = self._attn_branch(x, attn_mask, probs_mask)
        out = NF.dropout(out, p=self.dropout_rate, training=self.training)
        out = residual + out
        if not self.normalize_before:
            out = self._ln(out, self.ln_scale, self.ln_bias)
        return out

    def decode_step(self, x, cache, sequence_lengths):
        """One cached decode token: x [B, 1, E], cache [2, B, H, MAX, D].
        Routes through incubate.nn.functional.masked_multihead_attention.
        Returns (out [B, 1, E], updated cache)."""
        residual = x
        h = self._ln(x, self.pre_ln_scale, self.pre_ln_bias) \
            if self.normalize_before else x
        # pack qkv for mmha's [B, 3*H*D] layout
        w = self.qkv_weight.reshape(
            [3 * self.num_heads * self.head_dim, self.embed_dim])
        packed = NF.linear(h[:, 0], w.t(),
                           None if self.qkv_bias is None
                           else self.qkv_bias.reshape([-1]))
        attn, new_cache = IF.masked_multihead_attention(
            packed, cache_kv=cache, sequence_lengths=sequence_lengths)
        out = NF.linear(attn, self.linear_weight, self.linear_bias)
        out = residual + out[:, None]
        if not self.normalize_before:
            out = self._ln(out, self.ln_scale, self.ln_bias)
        return out, new_cache


class FusedFeedForward(Layer):
    """reference: layer/fused_transformer.py FusedFeedForward — pre/post-
    norm MLP with fused residual."""

    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1,
                 epsilon=1e-5, activation="relu", act_dropout_rate=None,
                 normalize_before=False, linear1_weight_attr=None,
                 linear1_bias_attr=None, linear2_weight_attr=None,
                 linear2_bias_attr=None, ln1_scale_attr=None,
                 ln1_bias_attr=None, ln2_scale_attr=None,
                 ln2_bias_attr=None, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.d_model = d_model
        self.normalize_before = normalize_before
        self.activation = activation
        self.dropout_rate = dropout_rate
        self.act_dropout_rate = (dropout_rate if act_dropout_rate is None
                                 else act_dropout_rate)
        self.epsilon = epsilon
        self.linear1_weight = self.create_parameter(
            (d_model, dim_feedforward), attr=linear1_weight_attr)
        self.linear1_bias = None if linear1_bias_attr is False else \
            self.create_parameter((dim_feedforward,),
                                  attr=linear1_bias_attr, is_bias=True)
        self.linear2_weight = self.create_parameter(
            (dim_feedforward, d_model), attr=linear2_weight_attr)
        self.linear2_bias = None if linear2_bias_attr is False else \
            self.create_parameter((d_model,), attr=linear2_bias_attr,
                                  is_bias=True)
        one = Constant(1.0)
        self.ln1_scale = self.create_parameter(
            (d_model,), attr=ln1_scale_attr, default_initializer=one)
        self.ln1_bias = self.create_parameter((d_model,),
                                              attr=ln1_bias_attr,
                                              is_bias=True)
        self.ln2_scale = self.create_parameter(
            (d_model,), attr=ln2_scale_attr, default_initializer=one)
        self.ln2_bias = self.create_parameter((d_model,),
                                              attr=ln2_bias_attr,
                                              is_bias=True)

    def forward(self, src):
        residual = src
        x = NF.layer_norm(src, (self.d_model,), weight=self.ln1_scale,
                          bias=self.ln1_bias, epsilon=self.epsilon) \
            if self.normalize_before else src
        h = NF.linear(x, self.linear1_weight, self.linear1_bias)
        h = {"relu": NF.relu, "gelu": NF.gelu}[self.activation](h)
        h = NF.dropout(h, p=self.act_dropout_rate, training=self.training)
        out = NF.linear(h, self.linear2_weight, self.linear2_bias)
        out = NF.dropout(out, p=self.dropout_rate, training=self.training)
        out = residual + out
        if not self.normalize_before:
            out = NF.layer_norm(out, (self.d_model,), weight=self.ln2_scale,
                                bias=self.ln2_bias, epsilon=self.epsilon)
        return out


class FusedTransformerEncoderLayer(Layer):
    """reference: layer/fused_transformer.py FusedTransformerEncoderLayer
    — FusedMultiHeadAttention + FusedFeedForward."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False, name=None):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead,
            dropout_rate=dropout_rate,
            attn_dropout_rate=(dropout_rate if attn_dropout_rate is None
                               else attn_dropout_rate),
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation, act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before)

    def forward(self, src, src_mask=None, cache=None, seq_lens=None):
        if cache is not None:
            out, new_cache = self.fused_attn.decode_step(src, cache,
                                                         seq_lens)
            return self.ffn(out), new_cache
        out = self.fused_attn(src, attn_mask=src_mask)
        return self.ffn(out)


class FusedMultiTransformer(Layer):
    """reference: layer/fused_transformer.py FusedMultiTransformer — the
    serving-path stacked decoder (one Layer holding every block's
    parameters). With `caches` given, each token routes through
    incubate.nn.functional.masked_multihead_attention over the per-layer
    contiguous cache and the updated caches are returned."""

    def __init__(self, embed_dim, num_heads, dim_feedforward,
                 dropout_rate=0.0, activation="gelu", normalize_before=True,
                 num_layers=1, epsilon=1e-5, name=None):
        super().__init__()
        self.num_layers = num_layers
        self.layers = []
        for i in range(num_layers):
            blk = FusedTransformerEncoderLayer(
                embed_dim, num_heads, dim_feedforward,
                dropout_rate=dropout_rate, activation=activation,
                normalize_before=normalize_before)
            self.add_sublayer(f"blk{i}", blk)
            self.layers.append(blk)

    def forward(self, src, attn_mask=None, caches=None, seq_lens=None,
                **kwargs):
        h = src
        if caches is not None:
            if seq_lens is None:
                raise ValueError("decode with caches requires seq_lens")
            new_caches = []
            for blk, cache in zip(self.layers, caches):
                h, c = blk(h, cache=cache, seq_lens=seq_lens)
                new_caches.append(c)
            return h, new_caches
        for blk in self.layers:
            h = blk(h, src_mask=attn_mask)
        return h


class FusedBiasDropoutResidualLayerNorm(Layer):
    """reference: layer/fused_transformer.py
    FusedBiasDropoutResidualLayerNorm — LN(residual + dropout(x + bias))."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None,
                 bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.dropout_rate = dropout_rate
        self.epsilon = epsilon
        from ...nn.initializer import Constant
        self.linear_bias = self.create_parameter((embed_dim,),
                                                 attr=bias_attr,
                                                 is_bias=True)
        self.ln_scale = self.create_parameter(
            (embed_dim,), attr=weight_attr,
            default_initializer=Constant(1.0))
        self.ln_bias = self.create_parameter((embed_dim,), is_bias=True)

    def forward(self, x, residual):
        from . import functional as _F

        return _F.fused_bias_dropout_residual_layer_norm(
            x, residual, bias=self.linear_bias, ln_scale=self.ln_scale,
            ln_bias=self.ln_bias, dropout_rate=self.dropout_rate,
            ln_epsilon=self.epsilon, training=self.training)


class FusedEcMoe(Layer):
    """reference: layer/fused_ec_moe.py FusedEcMoe — expert-choice MoE
    block over stacked expert gemms."""

    def __init__(self, hidden_size, inter_size, num_experts, act_type,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        if act_type not in ("gelu", "relu"):
            raise ValueError("act_type must be gelu or relu")
        self.act_type = act_type
        self.bmm0_weight = self.create_parameter(
            (num_experts, hidden_size, inter_size), attr=weight_attr)
        self.bmm0_bias = self.create_parameter(
            (num_experts, inter_size), attr=bias_attr, is_bias=True)
        self.bmm1_weight = self.create_parameter(
            (num_experts, inter_size, hidden_size), attr=weight_attr)
        self.bmm1_bias = self.create_parameter(
            (num_experts, hidden_size), attr=bias_attr, is_bias=True)

    def forward(self, x, gate):
        from . import functional as _F

        return _F.fused_ec_moe(x, gate, self.bmm0_weight, self.bmm0_bias,
                               self.bmm1_weight, self.bmm1_bias,
                               act_type=self.act_type)
