"""Llama decoder family — the flagship benchmark model.

Reference anchor: test/auto_parallel/hybrid_strategy/
semi_auto_parallel_llama_model.py (the reference's own Llama used for hybrid
dp/mp/pp accuracy tests) and the fused-op family it rides
(fused_rotary_position_embedding, swiglu, rms_norm).

TPU-first design:
- weights are plain Layer parameters annotated with NamedSharding via
  logical-axis rules (`shard_llama`) — TP (mp), FSDP (sharding), and
  sequence/context parallel (sep) all come from ONE mesh; XLA SPMD inserts
  the collectives.
- attention runs the Pallas flash-attention kernel; norm runs the fused
  RMSNorm kernel; RoPE/swiglu are XLA-fused elementwise ops.
- optional per-layer rematerialisation (jax.checkpoint) trades FLOPs for
  HBM, replacing the reference's RecomputeFunction PyLayer
  (fleet/recompute/recompute.py:109).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor, dispatch, unwrap
from ..core import tape as _tape
from ..nn import functional as F
from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding
from ..kernels.rms_norm import rms_norm as _k_rms
from ..kernels.rope import YarnScaling, rope_freqs, apply_rotary_emb
from ..parallel import mesh as mesh_mod


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False          # per-layer remat
    # skip remat for the last K layers: their saved activations live the
    # shortest (backward frees them first), so exempting them buys back
    # recompute FLOPs at minimal peak-memory cost (analog of the
    # reference's selective recompute_interval in fleet pp_layers)
    recompute_skip: int = 0
    # remat policy: "none" saves only layer boundaries (recompute all);
    # "save_attn" additionally keeps attention outputs, skipping the flash
    # forward re-run in the backward pass (reference analog: selective
    # recompute in fleet recompute_hybrid);
    # "dots_saveable" / "dots_with_no_batch_dims_saveable" save matmul
    # outputs (jax.checkpoint_policies; measured: OOM at the bench config)
    remat_policy: str = "none"
    # remat granularity (reference: fleet/recompute/recompute.py:109 is
    # op-level, not layer-level): "layer" wraps the whole decoder layer;
    # "attn" / "mlp" checkpoint only the NAMED sub-block — that block's
    # interior activations are dropped and recomputed in backward while
    # the OTHER block's are saved — a finer memory/FLOPs point than
    # whole-layer skip counts
    remat_scope: str = "layer"
    # MLP via the fused Pallas swiglu kernel (kernels/swiglu.py): ~18%
    # slower per-op than XLA's dual-matmul at the bench shape, but its
    # custom vjp recomputes per-tile, so the two [B,S,F] gate/up
    # intermediates are never saved — an activation-memory lever that
    # can buy whole no-remat layers (single-chip knob: the pallas call
    # has no SPMD partition rule)
    fused_swiglu: bool = False
    # attention over the sep axis: "ulysses" (all-to-all seq->head reshard)
    # or "ring" (ring attention — k/v rotate with ppermute, exact blockwise
    # softmax; the long-context leapfrog the reference lacks)
    attention_impl: str = "ulysses"
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    # ------ stock sizes (any field overridable, e.g.
    # llama2_13b(num_hidden_layers=2) for a dims-faithful smoke) ------
    @staticmethod
    def _stock(defaults: dict, over: dict) -> "LlamaConfig":
        return LlamaConfig(**{**defaults, **over})

    @staticmethod
    def llama2_7b(**over) -> "LlamaConfig":
        return LlamaConfig._stock(
            dict(hidden_size=4096, intermediate_size=11008,
                 num_hidden_layers=32, num_attention_heads=32), over)

    @staticmethod
    def llama2_13b(**over) -> "LlamaConfig":
        return LlamaConfig._stock(
            dict(hidden_size=5120, intermediate_size=13824,
                 num_hidden_layers=40, num_attention_heads=40), over)

    @staticmethod
    def llama3_8b(**over) -> "LlamaConfig":
        # the modern GQA ratio (32:8) + 128k vocab + long-rope base
        return LlamaConfig._stock(
            dict(vocab_size=128256, hidden_size=4096,
                 intermediate_size=14336, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=8,
                 rope_theta=500000.0), over)

    @staticmethod
    def llama_1b(**over) -> "LlamaConfig":
        return LlamaConfig._stock(
            dict(hidden_size=2048, intermediate_size=5504,
                 num_hidden_layers=16, num_attention_heads=16), over)

    @staticmethod
    def tiny(**over) -> "LlamaConfig":
        return LlamaConfig._stock(
            dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=64), over)


# ---------------------------------------------------------------------------
# activation sharding helper
# ---------------------------------------------------------------------------

def _act_spec(mesh: Optional[Mesh], shape, *dims) -> Optional[NamedSharding]:
    """Build a NamedSharding keeping only axes present in the mesh whose size
    divides the tensor dim. Each dim is None, an axis name, or a tuple of
    axis names."""
    if mesh is None:
        return None
    from ..parallel.mesh import divisible_prefix

    out = []
    for i, d in enumerate(dims):
        if d is None:
            out.append(None)
            continue
        names = (d,) if isinstance(d, str) else d
        kept = divisible_prefix(mesh, shape[i], names)
        out.append(kept if kept else None)
    return NamedSharding(mesh, P(*out))


def _per_shard(fn, mesh, in_specs, out_specs):
    """`fn` run once per shard of `mesh` (identity without a multi-device
    mesh). The Pallas-backed ops of the training path need it: GSPMD
    cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" — what
    the sharded train step died of when it was first compiled for four
    chips, PR 22), so each kernel call states its own row/head layout and
    runs on the local block. check_vma=False: jax's Pallas interpreter —
    the CPU tests' form of the same kernels — cannot run under the
    check."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _constrain(x, mesh, *dims):
    sh = _act_spec(mesh, list(x.shape), *dims)
    if sh is None:
        return x
    return dispatch("shard_constraint",
                    lambda a: jax.lax.with_sharding_constraint(a, sh), (x,))


# batch dim is data-parallel over both dp and the ZeRO axis; seq dim is
# context-parallel over sep (reference: 5-D topo [data,pipe,sharding,sep,model],
# fleet/base/topology.py:188)
from ..parallel.mesh import (BATCH_AXES,  # noqa: E402 (single topology source)
                             CP_AXIS, MP_AXIS)

SEQ_AXIS = "sep"


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class LlamaRMSNorm(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.hidden_size = config.hidden_size
        self.variance_epsilon = config.rms_norm_eps
        from ..nn.initializer import Constant

        self.weight = self.create_parameter(
            [config.hidden_size], default_initializer=Constant(1.0),
            dtype=config.dtype)

    def forward(self, x, mesh=None):
        norm = lambda a, w: _k_rms(a, w, self.variance_epsilon)
        if mesh is not None and len(x.shape) == 3:
            # row-wise: any batch/seq split of the rows is exact
            rows = _act_spec(mesh, x.shape, BATCH_AXES, SEQ_AXIS, None).spec
            norm = _per_shard(norm, mesh, (rows, P()), rows)
        return dispatch("rms_norm", norm, (x, self.weight))


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        nh, nkv, dh = (config.num_attention_heads, config.num_key_value_heads,
                       config.head_dim)
        self.num_heads, self.num_kv_heads, self.head_dim = nh, nkv, dh
        self.q_proj = Linear(h, nh * dh, bias_attr=False)
        self.k_proj = Linear(h, nkv * dh, bias_attr=False)
        self.v_proj = Linear(h, nkv * dh, bias_attr=False)
        self.o_proj = Linear(nh * dh, h, bias_attr=False)

    def forward(self, hidden, cos, sin, cache: Optional[Tuple] = None,
                mesh=None):
        b, s, _ = hidden.shape
        q = self.q_proj(hidden).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(hidden).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(hidden).reshape([b, s, self.num_kv_heads, self.head_dim])
        q, k = dispatch(
            "fused_rope",
            lambda qa, ka: apply_rotary_emb(qa, ka, cos=cos, sin=sin), (q, k))
        new_cache = None
        if cache is not None:
            pk, pv = cache
            if pk is not None:
                k = Tensor(jnp.concatenate([unwrap(pk), unwrap(k)], axis=1))
                v = Tensor(jnp.concatenate([unwrap(pv), unwrap(v)], axis=1))
            new_cache = (k, v)
        causal = cache is None or k.shape[1] == s
        use_ring = (self.config.attention_impl == "ring" and cache is None
                    and mesh is not None and SEQ_AXIS in mesh.axis_names
                    and int(mesh.shape[SEQ_AXIS]) > 1)
        if use_ring:
            from ..parallel.ring_attention import ring_attention

            # GQA handled inside the ring by grouped einsum — no repeat
            out = dispatch(
                "ring_attention",
                lambda qa, ka, va: ring_attention(
                    qa, ka, va, mesh=mesh, axis=SEQ_AXIS, causal=causal),
                (q, k, v))
        else:
            from ..parallel.ulysses import seq_to_head, ulysses_available

            ulysses = (cache is None and mesh is not None
                       and ulysses_available(mesh, self.num_heads, s))
            if ulysses:
                # Ulysses: explicit all-to-all over the sep group swaps seq
                # shards for head shards (GSPMD's re-constraint lowering of
                # this swap replicates — "involuntary full remat" — so the
                # swap is a shard_map'd lax.all_to_all riding ICI; reference
                # analog: SegmentParallel sep groups,
                # fleet/base/topology.py:224)
                a2a = lambda a: seq_to_head(a, mesh)
                q = dispatch("ulysses_a2a", a2a, (q,))
                if ulysses_available(mesh, self.num_kv_heads, s):
                    k = dispatch("ulysses_a2a", a2a, (k,))
                    v = dispatch("ulysses_a2a", a2a, (v,))
                else:
                    # GQA with too few kv heads to split over mp*sep:
                    # replicate kv groups just enough to split evenly —
                    # the repeat multiplies a2a bytes, so use the minimal
                    # factor whose result still block-aligns with q's
                    # contiguous (mp, sep) head shards (kv'[j] = kv[j//r]
                    # puts q head t with kv group t*nkv/nh on each device)
                    from ..parallel.ulysses import minimal_kv_repeat

                    rep = minimal_kv_repeat(mesh, self.num_heads,
                                            self.num_kv_heads)
                    grow = lambda a: seq_to_head(
                        jnp.repeat(a, rep, axis=2), mesh)
                    k = dispatch("ulysses_a2a", grow, (k,))
                    v = dispatch("ulysses_a2a", grow, (v,))
            else:
                # heads sharded over mp (and sep when divisible): GSPMD
                # inserts the reshard from the constraint
                q = _constrain(q, mesh, BATCH_AXES, None,
                               (MP_AXIS, SEQ_AXIS), None)
                k = _constrain(k, mesh, BATCH_AXES, None,
                               (MP_AXIS, SEQ_AXIS), None)
                v = _constrain(v, mesh, BATCH_AXES, None,
                               (MP_AXIS, SEQ_AXIS), None)
            if mesh is None or mesh.size == 1:
                out, _ = F.flash_attention(q, k, v, causal=causal)
            else:
                # batch over (dp, sharding), heads over (mp, sep) — the
                # layout both branches above leave q/k/v in. kv heads
                # that cannot split the way q heads do keep every head
                # whole on each shard (the GQA group map is by position)
                lay = [BATCH_AXES, None, (MP_AXIS, SEQ_AXIS), None]
                qs = _act_spec(mesh, q.shape, *lay).spec
                if _act_spec(mesh, k.shape, *lay).spec != qs:
                    lay[2] = None
                    qs = _act_spec(mesh, q.shape, *lay).spec

                def attend(qa, ka, va):
                    return unwrap(F.flash_attention(
                        Tensor(qa), Tensor(ka), Tensor(va),
                        causal=causal)[0])

                out = dispatch("flash_attn_per_shard",
                               _per_shard(attend, mesh, (qs, qs, qs), qs),
                               (q, k, v))
            if ulysses:
                from ..parallel.ulysses import head_to_seq

                out = dispatch("ulysses_a2a_back",
                               lambda a: head_to_seq(a, mesh), (out,))
        if self.config.remat_policy == "save_attn":
            from jax.ad_checkpoint import checkpoint_name

            out = dispatch("ckpt_name",
                           lambda a: checkpoint_name(a, "attn_out"), (out,))
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if cache is not None:
            return out, new_cache
        return out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self._fused = config.fused_swiglu
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = Linear(h, i, bias_attr=False)
        self.up_proj = Linear(h, i, bias_attr=False)
        self.down_proj = Linear(i, h, bias_attr=False)

    def forward(self, x):
        if self._fused:
            from ..kernels.swiglu import swiglu_matmul

            act = dispatch(
                "fused_swiglu",
                lambda a, g, u: swiglu_matmul(a, g, u, fused=True),
                (x, self.gate_proj.weight, self.up_proj.weight))
            return self.down_proj(act)
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, hidden, cos, sin, cache=None, mesh=None, remat=None):
        """remat: None, or "attn"/"mlp" — checkpoint ONLY that sub-block
        (sub-layer recompute granularity; the reference's recompute is
        op-level too, fleet/recompute/recompute.py:109)."""
        residual = hidden
        h = self.input_layernorm(hidden, mesh=mesh)
        if cache is not None:
            attn, new_cache = self.self_attn(h, cos, sin, cache=cache, mesh=mesh)
        else:
            new_cache = None
            if remat == "attn":
                def attn_fn(h_):
                    return unwrap(self.self_attn(Tensor(h_), cos, sin,
                                                 mesh=mesh))

                attn = Tensor(jax.checkpoint(attn_fn)(unwrap(h)))
            else:
                attn = self.self_attn(h, cos, sin, mesh=mesh)
        hidden = residual + attn
        residual = hidden
        h = self.post_attention_layernorm(hidden, mesh=mesh)
        if remat == "mlp" and cache is None:
            def mlp_fn(h_):
                return unwrap(self.mlp(Tensor(h_)))

            hidden = residual + Tensor(jax.checkpoint(mlp_fn)(unwrap(h)))
        else:
            hidden = residual + self.mlp(h)
        hidden = _constrain(hidden, mesh, BATCH_AXES, SEQ_AXIS, None)
        if cache is not None:
            return hidden, new_cache
        return hidden


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        from ..framework import dtype as dtypes
        from ..nn.layer.container import LayerList

        # every parameter is BORN in config.dtype: building in f32 and
        # casting at the end holds the whole model twice over — at
        # llama3-8B widths that alone overran one 16 GB chip (PR 22)
        with dtypes.default_dtype(config.dtype):
            self.embed_tokens = Embedding(config.vocab_size,
                                          config.hidden_size)
            self.layers = LayerList(
                [LlamaDecoderLayer(config)
                 for _ in range(config.num_hidden_layers)])
            self.norm = LlamaRMSNorm(config)

    def forward(self, input_ids, caches=None, position_offset: int = 0):
        mesh = mesh_mod.get_global_mesh()
        s = input_ids.shape[1]
        pos = jnp.arange(position_offset, position_offset + s)
        cos, sin = rope_freqs(s, self.config.head_dim,
                              base=self.config.rope_theta, position_ids=pos)
        hidden = self.embed_tokens(input_ids)
        hidden = _constrain(hidden, mesh, BATCH_AXES, SEQ_AXIS, None)
        use_ckpt = (self.config.recompute and not _tape.grad_enabled()
                    and caches is None)
        new_caches = [] if caches is not None else None
        for li, layer in enumerate(self.layers):
            if caches is not None:
                hidden, c = layer(hidden, cos, sin, cache=caches[li], mesh=mesh)
                new_caches.append(c)
            elif use_ckpt and li < len(self.layers) - \
                    self.config.recompute_skip:
                if self.config.remat_scope in ("attn", "mlp"):
                    # sub-layer granularity: the layer itself wraps just
                    # that block; no outer whole-layer checkpoint
                    hidden = layer(hidden, cos, sin, mesh=mesh,
                                   remat=self.config.remat_scope)
                    continue

                def run(h, l=layer):
                    return unwrap(l(Tensor(h), cos, sin, mesh=mesh))

                policy = None
                if self.config.remat_policy == "save_attn":
                    policy = jax.checkpoint_policies.save_only_these_names(
                        "attn_out")
                elif self.config.remat_policy in (
                        "dots_saveable", "dots_with_no_batch_dims_saveable"):
                    policy = getattr(jax.checkpoint_policies,
                                     self.config.remat_policy)
                hidden = Tensor(jax.checkpoint(run, policy=policy)(
                    unwrap(hidden)))
            else:
                hidden = layer(hidden, cos, sin, mesh=mesh)
        hidden = self.norm(hidden, mesh=mesh)
        if caches is not None:
            return hidden, new_caches
        return hidden


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            from ..framework import dtype as dtypes

            with dtypes.default_dtype(config.dtype):
                self.lm_head = Linear(config.hidden_size,
                                      config.vocab_size, bias_attr=False)

    def forward(self, input_ids, caches=None, position_offset: int = 0):
        out = self.llama(input_ids, caches=caches,
                         position_offset=position_offset)
        hidden = out[0] if caches is not None else out
        if self.config.tie_word_embeddings:
            w = self.llama.embed_tokens.weight
            logits = dispatch("tied_lm_head",
                              lambda h, e: jnp.matmul(h, e.T), (hidden, w))
        else:
            logits = self.lm_head(hidden)
        if caches is not None:
            return logits, out[1]
        return logits

    # --------------------------------------------------------------
    def jit_generate(self, input_ids, max_new_tokens: int = 32,
                     eos_token_id: Optional[int] = None,
                     do_sample: bool = False, temperature: float = 1.0,
                     top_k: int = 0, top_p: float = 1.0,
                     seed: Optional[int] = None, bucket_size: int = 128,
                     quant: Optional[str] = None,
                     prefill_with_quant: bool = False,
                     cache_layout: str = "contiguous",
                     kv_block_size: int = 64, seq_lens=None):
        """Decode as ONE jitted program: prefill, then a lax.scan over
        decode steps against fixed-layout per-layer KV caches (reference
        analog: the fused serving generation path over
        masked_multihead_attention + top_p_sampling,
        python/paddle/tensor/search.py:1354).

        Serving features:
        - **prompt bucketing**: prompts are right-padded to a multiple of
          ``bucket_size`` and the true length enters the program as a
          traced scalar, so every prompt length in a bucket shares ONE
          compile (pad K/V slots are masked out of decode attention until
          overwritten, and the first token reads the logits at the true
          last position).
        - **sampling**: ``do_sample=True`` enables temperature / top-k /
          top-p with a threaded PRNG key; ``seed`` makes it deterministic.
          temperature and top_p are traced (no recompile when they change);
          top_k is static (it sizes a lax.top_k).
        - **weight-only int8/int4 decode** (``quant="weight_only_int8"``
          or ``"weight_only_int4"``): the decode scan reads per-channel-
          scaled int8 (or nibble-packed int4) projection weights
          (nn.quant.weight_quantize layout) — half / quarter the HBM
          traffic on the weight-bound decode path.
        - **quant-only serving** (``prefill_with_quant=True``, requires
          ``quant``): prefill ALSO reads the quantized weights
          (build_quant_generate) so no full-precision parameter set is
          ever put on device — this is how 7B-class models fit one chip.
        - **paged KV cache** (``cache_layout="paged"``): K/V live in
          [max_pages, Hkv, kv_block_size, D] pools addressed through a
          block table allocated by PagedKVManager at prefill
          (build_paged_generate; reference:
          block_multihead_attention.py:25). ``seq_lens`` (per-row true
          prompt lengths) serves a ragged batch in one program; rows
          must be right-padded to the input rectangle.
        """
        cfg = self.config
        ids_arr = unwrap(input_ids) if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if max_new_tokens <= 0:
            return Tensor(ids_arr)
        b, s0 = ids_arr.shape
        sb = -(-s0 // bucket_size) * bucket_size  # bucketed prompt length
        padded = jnp.pad(ids_arr, ((0, 0), (0, sb - s0)))
        total = sb + max_new_tokens
        max_seq = total if total < 512 else ((total + 511) // 512) * 512
        if prefill_with_quant and quant is None:
            raise ValueError("prefill_with_quant=True requires quant=")
        if cache_layout not in ("contiguous", "paged"):
            raise ValueError(f"cache_layout must be 'contiguous' or "
                             f"'paged', got {cache_layout!r}")
        if seq_lens is not None and cache_layout != "paged":
            raise ValueError("per-row seq_lens (ragged batch) requires "
                             "cache_layout='paged'")
        params = dict(self.raw_state())
        dec_params = self._decode_params(params, quant)
        # the paged program bakes the pool dtype in at build time, so
        # the flag joins the cache key (flipping it must not serve a
        # stale compiled program)
        kv_dtype = resolve_kv_cache_dtype() if cache_layout == "paged" \
            else None
        serving_mp = resolve_serving_mp() if cache_layout == "paged" \
            else None
        if cache_layout == "paged":
            from ..parallel.collectives import \
                resolve_quantized_collectives

            qcoll = resolve_quantized_collectives()
        else:
            qcoll = None
        sig = (b, sb, max_new_tokens, eos_token_id, do_sample, int(top_k),
               quant, prefill_with_quant, cache_layout, kv_block_size,
               kv_dtype, qcoll, serving_mp)
        cache = getattr(self, "_jit_gen_cache", None)
        if cache is None:
            cache = self._jit_gen_cache = {}
        if sig not in cache:  # keep every compiled shape variant
            if cache_layout == "paged":
                fn = build_paged_generate(cfg, b, sb, max_new_tokens,
                                          kv_block_size, eos_token_id,
                                          do_sample, int(top_k),
                                          serving_mp=serving_mp)
            elif prefill_with_quant:
                fn = build_quant_generate(cfg, b, sb, max_new_tokens,
                                          max_seq, eos_token_id, do_sample,
                                          int(top_k))
            else:
                fn = _build_jit_generate(self, cfg, b, sb, max_new_tokens,
                                         max_seq, eos_token_id, do_sample,
                                         int(top_k))
            cache[sig] = jax.jit(fn)
        if seed is not None:
            key = jax.random.PRNGKey(int(seed))
        else:
            from ..framework.random import next_key

            key = next_key()
        if cache_layout == "paged":
            if seq_lens is None:
                s0_vec = jnp.full((b,), s0, jnp.int32)
            else:
                lens_np = np.asarray(seq_lens, np.int32).reshape(-1)
                if lens_np.shape[0] != b:
                    raise ValueError(f"seq_lens has {lens_np.shape[0]} "
                                     f"entries for a batch of {b}")
                if (lens_np < 1).any() or (lens_np > s0).any():
                    # out-of-range lengths would be silently clamped by
                    # the XLA gathers and decode over pad garbage
                    raise ValueError(
                        f"seq_lens must lie in [1, {s0}] (the input "
                        f"rectangle width); got {lens_np.tolist()}")
                s0_vec = jnp.asarray(lens_np)
            total = sb + max_new_tokens
            mgr = PagedKVManager(
                b * -(-total // kv_block_size), kv_block_size)
            tables, _ = mgr.tables_for_batch([total] * b)
            new_tokens = cache[sig](
                dec_params, padded, s0_vec, tables, key,
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(top_p, jnp.float32))
        else:
            args = (jnp.asarray(s0, jnp.int32), key,
                    jnp.asarray(temperature, jnp.float32),
                    jnp.asarray(top_p, jnp.float32))
            if prefill_with_quant:
                new_tokens = cache[sig](dec_params, padded, *args)
            else:
                new_tokens = cache[sig](params, dec_params, padded, *args)
        out = jnp.concatenate([ids_arr, new_tokens], axis=1)
        if eos_token_id is not None:
            # host-side trim: cut after every row has hit EOS
            toks = np.asarray(new_tokens)
            hit = (toks == eos_token_id)
            if hit.any(axis=1).all():
                last = int(hit.argmax(axis=1).max())
                out = out[:, :s0 + last + 1]
        return Tensor(out)

    def _decode_params(self, params, quant):
        """Decode-path parameter dict; with quant, the 2-D projection
        weights become (int8 [N,K], scale [N]) pairs. Quantized entries are
        cached per source array (jax arrays are immutable, so identity
        tracks staleness): a weight updated by training or set_state_dict
        is requantized on the next call, never served stale."""
        if quant is None:
            return params
        if quant not in ("weight_only_int8", "weight_only_int4"):
            raise ValueError(
                "quant must be None, 'weight_only_int8' or "
                f"'weight_only_int4', got {quant!r}")
        from ..nn.quant import weight_quantize

        qcache = getattr(self, "_decode_quant_cache", None)
        if qcache is None:
            qcache = self._decode_quant_cache = {}
        out = dict(params)
        names = [n for n in params
                 if n.endswith("_proj.weight") or n == "lm_head.weight"]
        for n in names:
            src = params[n]
            hit = qcache.get((n, quant))
            if hit is None or hit[0] is not src:
                wq, sc = weight_quantize(Tensor(src.astype(jnp.float32)),
                                         algo=quant)
                hit = (src, (unwrap(wq), unwrap(sc)))
                qcache[(n, quant)] = hit
            out[n] = hit[1]
        return out

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 seed: Optional[int] = None):
        """Eager decode with a KV cache (reference analog: PaddleNLP
        generation; kernel family masked_multihead_attention). Supports the
        same greedy/sampled selection as jit_generate."""
        ids = input_ids if isinstance(input_ids, Tensor) else Tensor(input_ids)
        if seed is not None:
            key = jax.random.PRNGKey(int(seed))
        else:
            from ..framework.random import next_key

            key = next_key()

        def pick(logits_slice, key):
            return _sample_next(
                logits_slice.astype(jnp.float32), key, do_sample,
                jnp.asarray(temperature, jnp.float32), int(top_k),
                jnp.asarray(top_p, jnp.float32))[:, None]

        caches = [(None, None)] * self.config.num_hidden_layers
        logits, caches = self(ids, caches=caches)
        out = [ids]
        key, k0 = jax.random.split(key)
        last = pick(unwrap(logits)[:, -1], k0)
        offset = ids.shape[1]
        for step in range(max_new_tokens):
            out.append(Tensor(last))
            if eos_token_id is not None and bool(
                    jnp.all(last == eos_token_id)):
                break
            if step == max_new_tokens - 1:
                break  # the last appended token needs no further forward
            logits, caches = self(Tensor(last), caches=caches,
                                  position_offset=offset)
            offset += 1
            key, ks = jax.random.split(key)
            last = pick(unwrap(logits)[:, -1], ks)
        return Tensor(jnp.concatenate([unwrap(t) for t in out], axis=1))


def _mm(x, w):
    """Matmul against a decode weight: dense [K, N], or a
    nn.quant.weight_quantize pair — int8 [N, K] or packed int4 [N, K//2]
    (detected by the stored K) with per-channel scales [N]. The
    int→bf16 convert (and the int4 unpack) fuse into the dot, so HBM
    reads stay at the quantized width."""
    if isinstance(w, tuple):
        wq, sc = w
        if wq.shape[1] != x.shape[-1]:  # packed int4: two nibbles/byte
            # in-register Pallas dequant-matmul: the packed bytes stay
            # packed all the way into VMEM (kernels/int4_matmul.py) —
            # end-to-end decode 1.68 ms/step vs 2.79 for the XLA shift
            # form (int8 remains fastest at ~1.1-1.3) — older record,
            # removed in PR 22; not measured on this machine
            from ..kernels.int4_matmul import int4_matmul

            lead = x.shape[:-1]
            out = int4_matmul(x.reshape(-1, x.shape[-1]), wq, sc)
            return out.reshape(*lead, wq.shape[0]).astype(x.dtype)
        out = jnp.einsum("...k,nk->...n", x, wq.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        return (out * sc).astype(x.dtype)
    return x @ w


def _sample_next(logits, key, do_sample, temperature, top_k, top_p):
    """Pick the next token from [B, V] logits: greedy, or nucleus sampling
    (the jit-safe form of ops/search.py top_p_sampling — sort, cumulative
    mass cut, categorical draw)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    srt = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p
    keep = keep.at[:, 0].set(True)  # the argmax survives even top_p<=0
    threshold = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    logits = jnp.where(logits < threshold, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


# what a routed layer counts each time it runs (summed by the programs);
# rows_multiplied: the buffer rows its grouped matmuls walked, padding
# included (row tiles in use x the layout's tile)
MOE_COUNTS = ("layer_steps", "rows_routed", "experts_hit", "load_max",
              "rows_multiplied")
# what a layer of learned sparse attention counts beside them: `full`
# layer-steps (layers that scored their keys), keys scored (a full layer's
# rows' context lengths) and keys attended (a row's selection, every layer)
DSA_COUNTS = ("dsa_full_layer_steps", "dsa_keys_scored",
              "dsa_keys_attended")


class ServedLayer(NamedTuple):
    """What the serving program builders know of one decoder layer. The
    attention block is the same everywhere unless `attn` says otherwise —
    rms_norm, q / k / v without bias, rotary over the whole head, grouped
    causal attention, o — read under `prefix` + `input_layernorm.weight`,
    `self_attn.{q,k,v,o}_proj.weight`, `post_attention_layernorm.weight`;
    what differs is here."""
    prefix: str
    # None: every earlier position; W: positions (t - W, t], kept in a
    # per-sequence ring by the engine
    window: Optional[int]
    rope_base: float
    rope_scaling: Optional[YarnScaling]
    # (x [..., hidden] after the second norm, p, prefix) -> (y, counts):
    # counts None, or for a routed layer the model's `counts` as an int32
    # vector
    mlp: Callable
    # None: the block above. Else the layer's own attention over pools of
    # its own (models/hy_v4.py): `pools(max_pages, block_size, dtype)` ->
    # (kc, vc), either may be None; `row_bytes(dtype)` -> (bytes a token of
    # kc, of vc); `decode` / `window` (x after the first norm, p, prefix, kc,
    # vc, lane, carry) -> (y, kc, vc, carry, counts), writing the layer's
    # rows themselves. `carry` is a dict the layers of one program hand
    # down; `lane` holds the lane's tables and positions
    attn: Optional[object] = None
    # None: h + F(h). Else (h, F, p, prefix, "attn" | "mlp") -> (h', aux),
    # F: u -> (y, aux), the sub-block around which the rule joins the
    # residual (a model of `streams` residual streams has one)
    residual: Optional[Callable] = None
    # None: the attention block alone. Else a mixer beside the block above,
    # on the same normed input, its output added to the block's, that keeps
    # a state of fixed size a sequence (models/falcon_h1.py's Mamba-2):
    # `state(rows)` -> the layer's state arrays, a row a sequence, row 0
    # the sink of idle rows; `row_bytes()` -> the bytes of one row;
    # `decode` / `window` (x after the first norm, p, prefix, state, lane)
    # -> (y, state, counts), updating the rows `lane` names in place. A row
    # starts from zero at its sequence's first position and is carried from
    # one window to the next
    mixer: Optional[object] = None
    # None, or the block above's (input, key, output) multipliers: q, k and
    # v read input * x, k is multiplied by key, o's output by output
    attn_scales: Optional[Tuple[float, float, float]] = None


class ServedModel(NamedTuple):
    """A model as `_make_chunk_prefill`, `_make_decode_step` and
    `_make_head_logits` read it: parameter names, head size, layers."""
    embed: str
    norm: str
    head: Optional[str]         # None: the embedding, transposed
    head_dim: int
    layers: Tuple[ServedLayer, ...]
    # residual streams: the embedding enters as this many copies, summed
    # before the final norm
    streams: int = 1
    # the head in f32 (true f32 products) whatever the served type
    head_f32: bool = False
    # what a routed model's layers count, in order (summed by the programs)
    counts: Tuple[str, ...] = MOE_COUNTS
    # the multipliers of the embedding's rows and of the logits
    embed_scale: float = 1.0
    logit_scale: float = 1.0

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, l in enumerate(self.layers)
                     if l.window is not None)

    @property
    def latent(self) -> bool:
        """Whether the layers keep their own pools (`ServedLayer.attn`)."""
        return any(l.attn is not None for l in self.layers)

    @property
    def routed(self) -> bool:
        return any(getattr(l.mlp, "routed", False) for l in self.layers)

    @property
    def stateful(self) -> bool:
        """Whether a layer keeps state a sequence (`ServedLayer.mixer`)."""
        return any(l.mixer is not None for l in self.layers)

    @property
    def counted(self) -> bool:
        """Whether the layers count what they did (`counts`)."""
        return self.routed or self.stateful


def _dense_swiglu(x, p, pre):
    gate = _mm(x, p[pre + "mlp.gate_proj.weight"])
    up = _mm(x, p[pre + "mlp.up_proj.weight"])
    return _mm(jax.nn.silu(gate) * up, p[pre + "mlp.down_proj.weight"]), None


def served_model(cfg) -> ServedModel:
    """The contract of `cfg`'s model: its own `served_model()` where the
    config has one, else the Llama block (one table, dense SwiGLU)."""
    own = getattr(cfg, "served_model", None)
    if own is not None:
        return own()
    return ServedModel(
        embed="llama.embed_tokens.weight", norm="llama.norm.weight",
        head=None if cfg.tie_word_embeddings else "lm_head.weight",
        head_dim=cfg.head_dim,
        layers=tuple(ServedLayer(f"llama.layers.{i}.", None, cfg.rope_theta,
                                 None, _dense_swiglu)
                     for i in range(cfg.num_hidden_layers)))


def _attn_scope(layer: ServedLayer):
    return jax.named_scope(
        "attn.window" if layer.window is not None else "attn.full")


def _attn_in(layer: ServedLayer, x):
    """What the attention block's projections read: the normed input, times
    the layer's input multiplier where it has one."""
    return x if layer.attn_scales is None else x * layer.attn_scales[0]


def _attn_out(layer: ServedLayer, y):
    """The attention block's output `y` (o's) as the residual takes it:
    times the layer's output multiplier where it has one."""
    return y if layer.attn_scales is None else y * layer.attn_scales[2]


def _make_head_logits(cfg):
    """LM-head logits over the decode-params dict (quant-aware via _mm;
    tied embeddings stay a dense transpose-matmul)."""
    model = served_model(cfg)

    def head_logits(h, p):
        if model.head is None:
            logits = h @ p[model.embed].T
        elif model.head_f32:
            logits = jnp.dot(h.astype(jnp.float32),
                             p[model.head].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        else:
            logits = _mm(h, p[model.head])
        if model.logit_scale != 1.0:
            logits = logits * model.logit_scale
        return logits
    return head_logits


def _embed(model: ServedModel, h):
    """The residual entering the first layer: the embedding (times the
    model's multiplier), as `streams` copies where the model has several."""
    if model.embed_scale != 1.0:
        h = h * model.embed_scale
    if model.streams == 1:
        return h
    return jnp.broadcast_to(h[..., None, :],
                            h.shape[:-1] + (model.streams, h.shape[-1]))


def _streams_out(model: ServedModel, h):
    """The last layer's residual as the final norm takes it."""
    if model.streams == 1:
        return h
    return jnp.sum(h.astype(jnp.float32), axis=-2).astype(h.dtype)


def _own_layer(layer: ServedLayer, h, p, eps, attend, kc, vc, carry):
    """A layer with its own attention (`ServedLayer.attn`): `attend(x, kc,
    vc, carry)` is its lane's call. Returns (h, kc, vc, carry, counts)."""
    pre = layer.prefix

    def join(h, block, which):
        if layer.residual is None:
            y, aux = block(h)
            return h + y, aux
        return layer.residual(h, block, p, pre, which)

    def attn_block(u):
        y, *rest = attend(_k_rms(u, p[pre + "input_layernorm.weight"], eps),
                          kc, vc, carry)
        return y, rest

    h, (kc, vc, carry, c_attn) = join(h, attn_block, "attn")
    h, c_mlp = join(h, lambda u: layer.mlp(
        _k_rms(u, p[pre + "post_attention_layernorm.weight"], eps), p, pre),
        "mlp")
    return h, kc, vc, carry, [c for c in (c_attn, c_mlp) if c is not None]


def _make_prefill(cfg, b, sb, tp=None):
    """Shared per-layer prefill over the `_decode_params` layout (dense
    OR quantized projections, via _mm): embed -> L x (rms/attn/mlp) ->
    final rms. Returns (h_final, [(k_i, v_i)]) with rotary-applied K/V
    [b, sb, nkv, dh] per layer — the caller owns the cache layout
    (contiguous slices or page scatter).

    With `tp` (ServingTP, inside a shard_map body) the q/k/v weights
    arrive column-sharded so each shard computes only its local heads;
    the flash attention runs shard-local and the per-shard outputs
    all-gather along the head axis before the (replicated) o-proj —
    the one cross-chip collective per layer. The returned K/V carry
    the LOCAL kv heads (callers scatter into the local pool shard)."""
    from ..kernels.flash_attention import flash_attention as _flash

    nh, nkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    # head counts the projections reshape at: the LOCAL shard's under
    # tp, the full model's otherwise (never the config's alone)
    nh_l = tp.nh_local if tp is not None else nh
    nkv_l = tp.nkv_local if tp is not None else nkv
    n_layers = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps

    def prefill(p, ids):
        h = p["llama.embed_tokens.weight"][ids]          # [b, sb, h]
        pos_ids = jnp.arange(sb)
        kvs = []
        for i in range(n_layers):
            pre = f"llama.layers.{i}."
            x = _k_rms(h, p[pre + "input_layernorm.weight"], eps)
            q = _mm(x, p[pre + "self_attn.q_proj.weight"]).reshape(
                b, sb, nh_l, dh)
            k = _mm(x, p[pre + "self_attn.k_proj.weight"]).reshape(
                b, sb, nkv_l, dh)
            v = _mm(x, p[pre + "self_attn.v_proj.weight"]).reshape(
                b, sb, nkv_l, dh)
            q, k = apply_rotary_emb(q, k, position_ids=pos_ids,
                                    base=cfg.rope_theta)
            kvs.append((k, v))
            attn = _flash(q, k, v, causal=True)        # [b, sb, nh_l, dh]
            if tp is not None:
                attn = tp.gather_heads(attn)           # [b, sb, nh, dh]
            h = h + _mm(attn.reshape(b, sb, nh * dh),
                        p[pre + "self_attn.o_proj.weight"])
            x2 = _k_rms(h, p[pre + "post_attention_layernorm.weight"], eps)
            gate = _mm(x2, p[pre + "mlp.gate_proj.weight"])
            up = _mm(x2, p[pre + "mlp.up_proj.weight"])
            h = h + _mm(jax.nn.silu(gate) * up,
                        p[pre + "mlp.down_proj.weight"])
        h = _k_rms(h, p["llama.norm.weight"], eps)
        return h, kvs

    return prefill


def _make_prefill_with_prefix(cfg, b, sb, w_pre, block_size, tp=None):
    """Suffix prefill over a cached block-aligned prefix: compute hidden
    states for the `sb` UNCACHED suffix tokens only, attending over the
    prefix K/V gathered from the paged pools (already rotary-encoded at
    their absolute positions when they were first cached) plus the
    suffix itself, causally. This is the compute the prefix cache
    exists to elide — a request whose first `prefix_lens[row]` tokens
    hit the cache pays O(suffix) prefill instead of O(prompt).

    Per-row state is traced, so ONE compiled program serves any mix of
    prefix lengths (including 0) at this (suffix bucket, batch) shape:
    `prefix_tables` [b, w_pre] maps the prefix's logical blocks to pool
    pages (rows shorter than w_pre blocks pad with any valid page id —
    masked), `prefix_lens` [b] is the cached token count (a multiple of
    block_size), and suffix positions/rope offsets follow from it.

    The mixed prefix+suffix attention has two implementations:

    - **Pallas kernel** (FLAGS_prefix_prefill_kernel, default on): the
      ragged paged prefix-prefill grid (kernels/prefix_prefill.py) —
      one (kv head, page) tile streamed from the pools per step with
      online-softmax carry, like the paged decode kernel (PAPERS.md:
      Ragged Paged Attention). Bandwidth-bound: the gathered
      [b, w_pre, nkv, bs, dh] prefix tensor never exists.
    - **masked jnp softmax fallback**: exact but gather-bound — kept
      for unsupported shapes (suffix bucket not a whole number of KV
      pages, or an empty prefix table) and as the numerics oracle.

    The flag is read when this factory runs (program-build time), so a
    jitted program keeps the path it was compiled with.

    Returns prefill(p, kcs, vcs, ids, prefix_tables, prefix_lens,
    suffix_lens=None) -> (h_final [b, sb, h], [(k_i, v_i)]) with
    rotary-applied suffix K/V [b, sb, nkv, dh] per layer — the caller
    owns the page scatter. `suffix_lens` [b] (true suffix lengths) lets
    the kernel skip and zero pad query rows; the fallback ignores it
    (pad rows beyond it are don't-care either way: their K/V land past
    the decode watermark and are masked until overwritten).

    int8 pools (FLAGS_kv_cache_dtype): pass kcs/vcs entries as
    (int8 pool, f32 scale [max_pages, nkv]) tuples — both the kernel
    and the fallback dequantize against the scales (the fallback in
    f32 at the gather, the kernel inside its accumulation).

    With `tp` (ServingTP, inside a shard_map body): q/k/v weights and
    the pools arrive shard-local, the mixed prefix+suffix attention
    (kernel or fallback — both derive head counts from their OPERAND
    shapes) streams only the local kv heads' pages, and the per-shard
    outputs all-gather along the head axis before the replicated
    o-proj — same single collective per layer as the decode step."""
    nh, nkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    nh_l = tp.nh_local if tp is not None else nh
    nkv_l = tp.nkv_local if tp is not None else nkv
    n_layers = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    scale = 1.0 / math.sqrt(dh)
    from ..framework.flags import flag as _flag

    use_kernel = (bool(_flag("prefix_prefill_kernel"))
                  and sb % block_size == 0 and w_pre >= 1)

    def prefill(p, kcs, vcs, ids, prefix_tables, prefix_lens,
                suffix_lens=None):
        h = p["llama.embed_tokens.weight"][ids]          # [b, sb, h]
        pos_ids = prefix_lens[:, None] + jnp.arange(sb)[None, :]  # [b, sb]
        kvs = []
        for i in range(n_layers):
            pre = f"llama.layers.{i}."
            x = _k_rms(h, p[pre + "input_layernorm.weight"], eps)
            q = _mm(x, p[pre + "self_attn.q_proj.weight"]).reshape(
                b, sb, nh_l, dh)
            k = _mm(x, p[pre + "self_attn.k_proj.weight"]).reshape(
                b, sb, nkv_l, dh)
            v = _mm(x, p[pre + "self_attn.v_proj.weight"]).reshape(
                b, sb, nkv_l, dh)
            q, k = apply_rotary_emb(q, k, position_ids=pos_ids,
                                    base=cfg.rope_theta)
            kvs.append((k, v))
            kc_i, ksc_i = kcs[i] if isinstance(kcs[i], tuple) \
                else (kcs[i], None)
            vc_i, vsc_i = vcs[i] if isinstance(vcs[i], tuple) \
                else (vcs[i], None)
            if tp is not None and tp.cp > 1:
                # context parallelism (ISSUE 18): prefix-phase partials
                # over the LOCAL pool pages, merged cross-chip; the
                # causal suffix phase is replicated (fresh K/V derive
                # from replicated activations) and folds in once
                from ..kernels.partial_attention import (
                    causal_window_partials, combine_partials,
                    cp_local_view, finalize_partials, paged_partials)

                loc, owned = cp_local_view(prefix_tables,
                                           kc_i.shape[0], tp.cp_axis)
                page = kc_i.shape[2]
                pos_ok = jnp.arange(loc.shape[1] * page)[None, :] \
                    < prefix_lens[:, None]
                valid = pos_ok & jnp.repeat(owned, page, axis=1)
                part = paged_partials(q, kc_i, vc_i, loc, valid,
                                      scale=scale, k_scale=ksc_i,
                                      v_scale=vsc_i)
                part = tp.merge_attn_partials(*part)
                suf = causal_window_partials(q, k, v, scale=scale)
                attn = finalize_partials(
                    *combine_partials(part, suf)).astype(h.dtype)
            elif use_kernel:
                from ..kernels.prefix_prefill import \
                    prefix_prefill_attention

                attn = prefix_prefill_attention(
                    q, k, v, kc_i, vc_i, prefix_tables, prefix_lens,
                    suffix_lens, scale=scale, k_scale=ksc_i,
                    v_scale=vsc_i).astype(h.dtype)
            else:
                from ..kernels.prefix_prefill import \
                    prefix_prefill_reference

                attn = prefix_prefill_reference(
                    q, k, v, kc_i, vc_i, prefix_tables, prefix_lens,
                    scale=scale, k_scale=ksc_i,
                    v_scale=vsc_i).astype(h.dtype)
            if tp is not None:
                attn = tp.gather_heads(attn)
            h = h + _mm(attn.reshape(b, sb, nh * dh),
                        p[pre + "self_attn.o_proj.weight"])
            x2 = _k_rms(h, p[pre + "post_attention_layernorm.weight"], eps)
            gate = _mm(x2, p[pre + "mlp.gate_proj.weight"])
            up = _mm(x2, p[pre + "mlp.up_proj.weight"])
            h = h + _mm(jax.nn.silu(gate) * up,
                        p[pre + "mlp.down_proj.weight"])
        h = _k_rms(h, p["llama.norm.weight"], eps)
        return h, kvs

    return prefill


def _make_chunk_prefill(cfg, tn, tp=None, block_size=None):
    """Chunk-lane transformer body of the UNIFIED serving step (ISSUE
    14): one ragged prefill WINDOW of `tn` tokens for ONE request,
    attending its already-committed tokens (earlier chunks, or a cached
    prefix — both are just pool pages named by the row's block table)
    plus the window itself causally, through `ragged_paged_attention`.
    A cold prompt is a window with ``cached_len 0``; a long prompt is
    several windows across engine steps (chunked prefill — the thing
    that stops a 100k-token prompt head-of-line-blocking decode).

    Per-window state is traced, so ONE compiled program serves every
    (cached_len, new_len) mix at this window shape: `chunk_table`
    [1, w] names the request's pages, `cached_len` [1] is the
    committed token count (page-aligned by the engine's chunking, but
    the kernel accepts arbitrary), `new_len` [1] the true chunk length
    (window rows beyond it are pad — zeroed by the kernel and scattered
    at the scratch page by the caller).

    Attention follows FLAGS_prefix_prefill_kernel at program-build
    time exactly like `_make_prefill_with_prefix`: the Pallas
    `ragged_paged_attention` grid by default, the
    `ragged_paged_attention_reference` masked softmax as fallback and
    oracle. int8 pools (FLAGS_kv_cache_dtype) pass kcs/vcs entries as
    (int8 pool, f32 scale) tuples — both paths dequantize against the
    scales.

    With `tp` (ServingTP, inside a shard_map body): shard-local q/k/v
    heads + pool shards, per-shard outputs all-gather (bf16 payload)
    before the replicated o-proj — the same one collective per layer
    as every other serving program.

    The layers are `served_model(cfg)`'s: each says whether it attends a
    window (then `chunk_table` is a pair and the layer reads its ring's
    table), which rotary table it turns by, and what its MLP is.

    Returns prefill(p, kcs, vcs, ids, chunk_table, cached_len,
    new_len, chunk_pages=None) -> (h_final [1, tn, hidden], [(k_i, v_i)])
    with rotary-applied window K/V [1, tn, nkv_l, dh] per layer — the
    caller owns the page scatter — and, where a layer is routed, the
    layers' summed counters as a third result. A layer with its own
    attention (`ServedLayer.attn`) writes its rows itself, at
    `chunk_pages` [1, tn / block_size] (the window's pages, pad pages the
    scratch page): its entries of the lists `kcs` / `vcs` are replaced in
    place and its entry of the K/V list is None. Its window starts on a
    page's edge (no prefix cache; windows of whole pages). A model whose
    layers keep state a sequence (`ServedLayer.mixer`) passes the layers'
    `states` and the window's sequence's row `state_row` [1]; the window
    starts from that row's carried state, or from zero where `cached_len`
    is 0, and the states come back after the K/V list."""
    model = served_model(cfg)
    nh, nkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   model.head_dim)
    nh_l = tp.nh_local if tp is not None else nh
    nkv_l = tp.nkv_local if tp is not None else nkv
    eps = cfg.rms_norm_eps
    scale = 1.0 / math.sqrt(dh)
    from ..framework.flags import flag as _flag

    use_kernel = bool(_flag("prefix_prefill_kernel"))

    def prefill(p, kcs, vcs, ids, chunk_table, cached_len, new_len,
                chunk_pages=None, states=None, state_row=None):
        from ..kernels.ragged_attention import (
            ragged_paged_attention, ragged_paged_attention_reference)

        h = _embed(model, p[model.embed][ids])           # [1, tn, h]
        pos_ids = cached_len[:, None] + jnp.arange(tn)[None, :]
        kvs, counts, carry = [], [], {}
        lane = {"table": chunk_table[0], "pos": pos_ids[0],
                "new_len": new_len[0], "block_size": block_size,
                "pages": None if chunk_pages is None else chunk_pages[0]}
        if states is not None:
            states = list(states)
            lane.update(state_row=state_row[0], fresh=cached_len[0] == 0)
        for i, layer in enumerate(model.layers):
            pre = layer.prefix
            if layer.attn is not None:
                h, kcs[i], vcs[i], carry, cs = _own_layer(
                    layer, h, p, eps, lambda x, kc, vc, carry: layer.attn
                    .window(x, p, pre, kc, vc, lane, carry), kcs[i], vcs[i],
                    carry)
                kvs.append(None)
                counts += cs
                continue
            # a model with window layers hands every table in twice:
            # (the full layers', the window layers' rings)
            table_i = chunk_table if not model.window_layers \
                else chunk_table[layer.window is not None]
            x = _k_rms(h, p[pre + "input_layernorm.weight"], eps)
            xa = _attn_in(layer, x)
            q = _mm(xa, p[pre + "self_attn.q_proj.weight"]).reshape(
                1, tn, nh_l, dh)
            k = _mm(xa, p[pre + "self_attn.k_proj.weight"]).reshape(
                1, tn, nkv_l, dh)
            v = _mm(xa, p[pre + "self_attn.v_proj.weight"]).reshape(
                1, tn, nkv_l, dh)
            if layer.attn_scales is not None:
                k = k * layer.attn_scales[1]
            q, k = apply_rotary_emb(q, k, position_ids=pos_ids,
                                    base=layer.rope_base,
                                    scaling=layer.rope_scaling)
            kvs.append((k, v))
            kc_i, ksc_i = kcs[i] if isinstance(kcs[i], tuple) \
                else (kcs[i], None)
            vc_i, vsc_i = vcs[i] if isinstance(vcs[i], tuple) \
                else (vcs[i], None)
            if tp is not None and tp.cp > 1:
                # context parallelism (ISSUE 18): this shard holds only
                # 1/cp of the pool pages — stream the LOCAL pages as
                # online-softmax partials (position-valid AND owned),
                # merge the stats cross-chip (never the KV), then fold
                # in the replicated causal window exactly once
                from ..kernels.partial_attention import (
                    causal_window_partials, combine_partials,
                    cp_local_view, finalize_partials, paged_partials)

                loc, owned = cp_local_view(table_i, kc_i.shape[0],
                                           tp.cp_axis)
                page = kc_i.shape[2]
                pos_ok = jnp.arange(loc.shape[1] * page)[None, :] \
                    < cached_len[:, None]
                valid = pos_ok & jnp.repeat(owned, page, axis=1)
                part = paged_partials(q, kc_i, vc_i, loc, valid,
                                      scale=scale, k_scale=ksc_i,
                                      v_scale=vsc_i)
                part = tp.merge_attn_partials(*part)
                win = causal_window_partials(q, k, v, new_len,
                                             scale=scale)
                mm_, ll_, aa_ = combine_partials(part, win)
                live = jnp.arange(tn)[None, :] < new_len[:, None]
                attn = finalize_partials(
                    mm_, ll_, aa_, live[..., None]).astype(h.dtype)
            else:
                attn_fn = ragged_paged_attention if use_kernel \
                    else ragged_paged_attention_reference
                with _attn_scope(layer):
                    attn = attn_fn(q, k, v, kc_i, vc_i, table_i,
                                   cached_len, new_len, scale=scale,
                                   k_scale=ksc_i, v_scale=vsc_i,
                                   window=layer.window).astype(h.dtype)
            if tp is not None:
                attn = tp.gather_heads(attn)
            y = _attn_out(layer, _mm(attn.reshape(1, tn, nh * dh),
                                     p[pre + "self_attn.o_proj.weight"]))
            if layer.mixer is not None:
                # the mixer beside attention, on the same normed input
                m, states[i], count = layer.mixer.window(
                    x, p, pre, states[i], lane)
                y = y + m
                counts.append(count)
            h = h + y
            x2 = _k_rms(h, p[pre + "post_attention_layernorm.weight"], eps)
            y, count = layer.mlp(x2, p, pre)
            h = h + y
            if count is not None:
                counts.append(count)
        h = _k_rms(_streams_out(model, h), p[model.norm], eps)
        out = (h, kvs) if states is None else (h, kvs, states)
        if counts:
            return out + (sum(counts),)
        return out

    return prefill


def _make_verify_window(cfg, b, w, tp=None):
    """Speculative-verify transformer body (ISSUE 19): the chunk lane of
    `_make_chunk_prefill`, batched over `b` slots at a FIXED window of
    `w = spec_k + 1` tokens — the slot's pending token plus its k
    drafts — through the same `ragged_paged_attention` kernel the
    unified step runs. The only new ask of the model is that logits
    come back for ALL w rows instead of the last: row j scores the
    token the target would emit AFTER window token j, which is exactly
    what greedy acceptance compares draft j+1 against.

    Per-slot state is traced so ONE compiled program serves every
    (cached_len, new_len) mix: `tables` [b, tw] are the slots' block
    tables, `cached_lens` [b] the committed counts (arbitrary, token
    granular), `new_lens` [b] the true window lengths (1 = no drafts =
    plain decode semantics; rows past new_len are pad — the kernel
    zeroes them and the caller scatters their K/V at the scratch page).

    With `tp` (ServingTP, inside a shard_map body): shard-local q/k/v
    heads + pool shards, per-shard outputs all-gather before the
    replicated o-proj — same one collective per layer as the decode
    chunk. Context parallelism (tp.cp > 1) is a follow-up; the engine
    gates it.

    Returns verify(p, kcs, vcs, ids, tables, cached_lens, new_lens) ->
    (h_final [b, w, hidden], [(k_i, v_i)]) with rotary-applied window
    K/V [b, w, nkv_l, dh] per layer — the caller owns the per-column
    page scatter and the head projection."""
    nh, nkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    nh_l = tp.nh_local if tp is not None else nh
    nkv_l = tp.nkv_local if tp is not None else nkv
    if tp is not None and tp.cp > 1:
        raise NotImplementedError(
            "speculative verify windows do not compose with serving_cp "
            "yet (page-sharded partial-attention merge of a multi-row "
            "window is a ROADMAP follow-up)")
    n_layers = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    scale = 1.0 / math.sqrt(dh)
    from ..framework.flags import flag as _flag

    use_kernel = bool(_flag("prefix_prefill_kernel"))

    def verify(p, kcs, vcs, ids, tables, cached_lens, new_lens):
        from ..kernels.ragged_attention import (
            ragged_paged_attention, ragged_paged_attention_reference)

        h = p["llama.embed_tokens.weight"][ids]          # [b, w, h]
        pos_ids = cached_lens[:, None] + jnp.arange(w)[None, :]
        kvs = []
        for i in range(n_layers):
            pre = f"llama.layers.{i}."
            x = _k_rms(h, p[pre + "input_layernorm.weight"], eps)
            q = _mm(x, p[pre + "self_attn.q_proj.weight"]).reshape(
                b, w, nh_l, dh)
            k = _mm(x, p[pre + "self_attn.k_proj.weight"]).reshape(
                b, w, nkv_l, dh)
            v = _mm(x, p[pre + "self_attn.v_proj.weight"]).reshape(
                b, w, nkv_l, dh)
            q, k = apply_rotary_emb(q, k, position_ids=pos_ids,
                                    base=cfg.rope_theta)
            kvs.append((k, v))
            kc_i, ksc_i = kcs[i] if isinstance(kcs[i], tuple) \
                else (kcs[i], None)
            vc_i, vsc_i = vcs[i] if isinstance(vcs[i], tuple) \
                else (vcs[i], None)
            attn_fn = ragged_paged_attention if use_kernel \
                else ragged_paged_attention_reference
            attn = attn_fn(q, k, v, kc_i, vc_i, tables,
                           cached_lens, new_lens, scale=scale,
                           k_scale=ksc_i, v_scale=vsc_i).astype(h.dtype)
            if tp is not None:
                attn = tp.gather_heads(attn)
            h = h + _mm(attn.reshape(b, w, nh * dh),
                        p[pre + "self_attn.o_proj.weight"])
            x2 = _k_rms(h, p[pre + "post_attention_layernorm.weight"], eps)
            gate = _mm(x2, p[pre + "mlp.gate_proj.weight"])
            up = _mm(x2, p[pre + "mlp.up_proj.weight"])
            h = h + _mm(jax.nn.silu(gate) * up,
                        p[pre + "mlp.down_proj.weight"])
        h = _k_rms(h, p["llama.norm.weight"], eps)
        return h, kvs

    return verify


def build_quant_generate(cfg, b, sb, max_new, max_seq=None,
                         eos_token_id=None, do_sample=False, top_k=0):
    """Model-free serving program over QUANTIZED weights only: prefill AND
    decode read the nn.quant weight layout (int8 [N,K] / packed int4
    [N,K//2] + per-channel scales), dequantizing on the fly inside each
    matmul — no full-precision parameter set ever exists on device.

    This is what makes 7B-class serving fit one 16 GB chip: bf16 weights
    (13.5 GB) + an int8 copy cannot coexist, so the fp prefill path of
    `_build_jit_generate` is replaced by the same per-layer loop batched
    over the prompt (flash attention for the causal part). Prefill is
    compute-bound, so the dequant adds bandwidth it doesn't miss; decode
    stays weight-read-bound at the quantized width.

    Reference analog: the weight-only serving path of
    python/paddle/nn/quant/quantized_linear.py:180 (weight_only_linear)
    under the fused_multi_transformer generation loop
    (incubate/nn/functional/fused_multi_transformer.py).

    Returns run(dec_params, ids_padded, s0, key, temperature, top_p) ->
    new_tokens; jit it once per shape. `dec_params` is the
    `_decode_params` dict: quantized projections + fp embed/norm weights.
    """
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    if max_seq is None:
        total = sb + max_new
        max_seq = total if total < 512 else ((total + 511) // 512) * 512

    head_logits = _make_head_logits(cfg)
    prefill = _make_prefill(cfg, b, sb)
    decode_step = _make_decode_step(cfg, b, max_seq)

    def run(p_dec, ids, s0, key, temperature, top_p):
        h, kvs = prefill(p_dec, ids)
        kcs, vcs = [], []
        for k, v in kvs:
            kc = jnp.zeros((b, nkv, max_seq, dh), h.dtype)
            kcs.append(jax.lax.dynamic_update_slice(
                kc, jnp.swapaxes(k, 1, 2).astype(h.dtype), (0, 0, 0, 0)))
            vc = jnp.zeros((b, nkv, max_seq, dh), h.dtype)
            vcs.append(jax.lax.dynamic_update_slice(
                vc, jnp.swapaxes(v, 1, 2).astype(h.dtype), (0, 0, 0, 0)))
        # logits at the TRUE last prompt position, not the padded end
        h_last = jax.lax.dynamic_index_in_dim(h, s0 - 1, axis=1,
                                              keepdims=True)
        last_logits = head_logits(h_last, p_dec)[:, -1]
        return _decode_tail(decode_step, p_dec, kcs, vcs,
                            last_logits, s0, key, temperature, top_p,
                            ids.dtype, max_new, eos_token_id, do_sample,
                            top_k, b)

    return run


def make_paged_kv_helpers(b, n_pre, nkv, dh, block_size, tables):
    """The two paged-cache plumbing pieces shared by every paged program
    (build_paged_generate and serving.engine): prefill page transpose and
    the per-token page/slot commit, closed over the traced block table.
    The commit updates the pools in place in the layout the decode kernel
    reads (`kernels/kv_commit.py`) where their shape allows — pages of whole
    row tiles, heads of whole lane tiles, bf16 or f32 — and is XLA's
    scatter otherwise."""
    from ..kernels.kv_commit import commit_ok, kv_commit

    def to_pages(kv):
        """[b, n_pre*block_size, nkv, dh] -> [b, n_pre, nkv, block_size, dh]"""
        return jnp.transpose(
            kv.reshape(b, n_pre, block_size, nkv, dh), (0, 1, 3, 2, 4))

    def kv_write(kc, vc, k, v, lens):
        page = tables[jnp.arange(b), lens // block_size]
        slot = lens % block_size
        if commit_ok(kc, vc):
            return kv_commit(kc, vc, k[:, 0], v[:, 0], page, slot)
        return (kc.at[page, :, slot, :].set(k[:, 0].astype(kc.dtype)),
                vc.at[page, :, slot, :].set(v[:, 0].astype(vc.dtype)))

    return to_pages, kv_write


# ---------------------------------------------------------------------------
# int8 KV cache (FLAGS_kv_cache_dtype): symmetric per-(page, kv-head)
# absmax quantization of the paged pools — quantize on the K/V page
# scatter, dequantize inside the Pallas kernels (decode_attention /
# prefix_prefill stream the int8 tiles + their scale rows)
# ---------------------------------------------------------------------------

KV_CACHE_DTYPES = ("bf16", "int8")


def resolve_kv_cache_dtype(kv_cache_dtype: Optional[str] = None) -> str:
    """'bf16' | 'int8', from the argument or FLAGS_kv_cache_dtype /
    PADDLE_TPU_KV_CACHE_DTYPE. Read at program-BUILD time (like
    FLAGS_prefix_prefill_kernel): flip it before constructing or
    warming an engine."""
    if kv_cache_dtype is None:
        from ..framework.flags import flag as _flag

        kv_cache_dtype = str(_flag("kv_cache_dtype"))
    if kv_cache_dtype not in KV_CACHE_DTYPES:
        raise ValueError(
            f"kv_cache_dtype must be one of {KV_CACHE_DTYPES}, got "
            f"{kv_cache_dtype!r}")
    return kv_cache_dtype


def resolve_unified_step(unified_step=None) -> bool:
    """Whether the serving engine runs the UNIFIED ragged step (ISSUE
    14) — one chunked-prefill+decode program over
    `ragged_paged_attention` instead of the split cold/prefix-prefill
    program zoo — from the argument or FLAGS_unified_step /
    PADDLE_TPU_UNIFIED_STEP. 'auto' (the default) resolves ON, on the
    chip as off it: a default that depended on the backend meant the
    chip served the step the CPU tests exercised least. Which step is
    FASTER on the chip is not measured (benchmark PR); the split oracle
    stays one flag away. Read at engine-BUILD time like every other
    serving flag."""
    if unified_step is None:
        from ..framework.flags import flag as _flag

        unified_step = _flag("unified_step")
    if isinstance(unified_step, str):
        s = unified_step.strip().lower()
        if s in ("auto", "", "1", "true", "on", "yes"):
            return True
        if s in ("0", "false", "off", "no"):
            return False
        raise ValueError(
            f"unified_step must be 'auto'/'1'/'0', got {unified_step!r}")
    return bool(unified_step)


def serving_block_size_candidates(cfg, *, prompt_bucket: int,
                                  kv_cache_dtype: str = "bf16",
                                  max_candidates: int = 2) -> list:
    """KV page sizes (``block_size``) a serving engine could be built
    at for this model, ascending: the divisors of `prompt_bucket`
    (whole pages per bucket — the engine's admission invariant) whose
    per-token K+V row keeps double-buffered page blocks under the
    streaming kernels' scoped-VMEM cap. Candidates come from
    `kernels.constraints.vmem_block_candidates` — the SAME
    `fit_vmem_block` rule the decode / prefix-prefill kernels size
    their blocks with — so the static autotuner (analysis/tuner.py)
    can only propose pages the kernels would actually tile at.
    `max_candidates` keeps the largest few (big pages amortize block
    tables and scatter launches; a deep small-page tail is never
    competitive)."""
    itemsize = 1 if resolve_kv_cache_dtype(kv_cache_dtype) == "int8" \
        else 2
    row = 2 * cfg.num_key_value_heads * cfg.head_dim * itemsize
    from ..kernels.constraints import vmem_block_candidates

    return vmem_block_candidates(int(prompt_bucket), row,
                                 max_candidates=max_candidates)


SERVING_MP_FALLBACK_MSG = (
    "kv heads not divisible by serving_mp; falling back to "
    "replicated-KV head-sharded-Q (each shard streams the FULL kv "
    "pools — no per-chip KV memory win, query compute still shards)")


def resolve_serving_mp(serving_mp: Optional[int] = None) -> int:
    """Tensor-parallel degree of the paged serving stack, from the
    argument or FLAGS_serving_mp / PADDLE_TPU_SERVING_MP. Read at
    program-BUILD time (like FLAGS_kv_cache_dtype): flip it before
    constructing or warming an engine. 1 (default) = the single-chip
    path, byte-identical to a build without the flag."""
    if serving_mp is None:
        from ..framework.flags import flag as _flag

        serving_mp = int(_flag("serving_mp"))
    serving_mp = int(serving_mp)
    if serving_mp < 1:
        raise ValueError(f"serving_mp must be >= 1, got {serving_mp}")
    return serving_mp


def resolve_serving_cp(serving_cp: Optional[int] = None) -> int:
    """Context-parallel degree of the paged serving stack (pools shard
    by PAGE), from the argument or FLAGS_serving_cp /
    PADDLE_TPU_SERVING_CP. Read at program-BUILD time (like
    FLAGS_serving_mp): flip it before constructing or warming an
    engine. 1 (default) = the page-replicated path, byte-identical to
    a build without the flag."""
    if serving_cp is None:
        from ..framework.flags import flag as _flag

        serving_cp = int(_flag("serving_cp"))
    serving_cp = int(serving_cp)
    if serving_cp < 1:
        raise ValueError(f"serving_cp must be >= 1, got {serving_cp}")
    return serving_cp


class PageShardingError(ValueError):
    """A paged-pool geometry cannot shard along the PAGE axis as asked:
    the fleet page count does not split evenly across the `cp` shards.
    Named (rather than a bare ValueError) so admission / tuner /
    engine-build callers can distinguish 'this cp degree is
    geometrically impossible here' from argument typos."""


class ServingTP:
    """Head-sharding geometry of a tensor-parallel serving program.

    The sharding layout (ROADMAP: "pools+scales sharded; decode
    all-gathers only the o-proj activations"):

    - q/k/v projections COLUMN-shard by head over `mp`: shard i owns
      contiguous q heads [i*nh_local, (i+1)*nh_local) and kv heads
      [i*nkv_local, (i+1)*nkv_local) — the same contiguous blocks a
      `NamedSharding(P(..., 'mp'))` device_put produces, so GQA group
      membership is preserved per shard (group = nh/nkv is invariant).
    - the paged K/V pools (and their int8 scale sidecars) shard on the
      kv-head axis; block tables, lengths and budgets stay replicated,
      so page ids mean the same thing on every chip and "KV transfer"
      between workers is table bookkeeping, not data movement.
    - attention runs entirely shard-local (each shard streams only its
      local kv heads); the per-shard attention outputs — the o-proj
      ACTIVATIONS — are all-gathered along the head axis, and the
      o-proj itself plus everything outside the attention block (embed,
      norms, MLP, lm head, sampling) is computed replicated. That makes
      the all-gather the ONE cross-chip collective per layer, and every
      per-element computation identical to the single-chip program
      (token identity, not just closeness).

    MQA fallback (`kv heads % mp != 0`, e.g. nkv=1): kv heads cannot
    shard, so k/v projections and the pools stay REPLICATED while q
    heads still shard — each shard streams the full pools against its
    query group (`group_local = nh_local // nkv`), commits identical
    K/V on every chip, and the o-proj all-gather is unchanged. A
    build-time warning names the fallback (the per-chip KV-memory win
    is gone; the grid is still correct — satellite of ISSUE 7: group
    math derives from LOCAL head counts, never the full-model config).
    """

    def __init__(self, cfg, mp: int, axis: str = MP_AXIS,
                 quantized: Optional[bool] = None, cp: int = 1,
                 cp_axis: str = CP_AXIS):
        # quantized collectives (ISSUE 15): resolved HERE at geometry-
        # build time like every serving flag — the engine threads its
        # own resolution through so the flag joins its program keys
        from ..parallel.collectives import resolve_quantized_collectives

        self.quantized = resolve_quantized_collectives(quantized)
        self.cp = int(cp)
        self.cp_axis = cp_axis
        if self.cp < 1:
            raise ValueError(f"serving_cp must be >= 1, got {cp}")
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        if nh % mp:
            raise ValueError(
                f"serving_mp={mp} does not divide num_attention_heads "
                f"{nh}; query heads must shard evenly")
        self.mp = int(mp)
        self.axis = axis
        self.nh_local = nh // mp
        self.kv_sharded = nkv % mp == 0
        self.nkv_local = nkv // mp if self.kv_sharded else nkv
        if self.kv_sharded and self.nh_local % self.nkv_local:
            raise ValueError(
                f"serving_mp={mp} breaks the GQA grouping: {nh} q heads "
                f"/ {nkv} kv heads shard to {self.nh_local}/"
                f"{self.nkv_local} per chip")
        if not self.kv_sharded:
            if self.nh_local % nkv:
                raise ValueError(
                    f"serving_mp={mp} with {nkv} kv heads leaves "
                    f"{self.nh_local} q heads per chip — not a whole "
                    "number of kv groups; no valid replicated-KV grid")
            import warnings

            warnings.warn(
                f"serving_mp={mp} with {nkv} kv heads: "
                + SERVING_MP_FALLBACK_MSG, stacklevel=3)

    def gather_heads(self, ctx):
        """All-gather the per-shard attention outputs along the head
        axis — THE one cross-chip collective per layer (the o-proj
        activations; shard i's block lands at head offset i*nh_local,
        matching the column-sharded q projection). The payload is cast
        to bf16 BEFORE the gather (ISSUE 14 satellite: PR 11's comms
        auditor proved an f32 activation stream shipped f32 here, with
        the downcast landing at the o-proj AFTER the wire — the
        pre-cast halves the mp seam's bytes; a bf16 stream is
        untouched, so production serving numerics don't move and every
        shard applies the same rounding, keeping mp token-identical to
        itself across degrees).

        With FLAGS_quantized_collectives (ISSUE 15, the cashed EQuARX
        follow-up) the payload ships as absmax-scaled int8 blocks with
        an f32 scale sidecar (`parallel.collectives.
        quantized_all_gather` — the int8 KV pools' proven scheme):
        ~0.5x the bf16 wire bytes again, at quantization-noise
        accuracy (the serving gate is the int8-KV token-match bar, not
        identity). TPU803 goes silent on the rewritten seam by design
        (int8 payloads never fire); the comms auditor prices payload
        AND sidecar."""
        if self.mp <= 1:
            # cp-only geometry: every chip already holds all heads —
            # no head seam to gather (and no dtype cast: byte-identity
            # with the single-chip path is per-element)
            return ctx
        if ctx.dtype == jnp.float32:
            ctx = ctx.astype(jnp.bfloat16)
        if self.quantized:
            from ..parallel.collectives import quantized_all_gather

            return quantized_all_gather(ctx, self.axis,
                                        axis=ctx.ndim - 2, tiled=True)
        return jax.lax.all_gather(ctx, self.axis, axis=ctx.ndim - 2,
                                  tiled=True)

    def merge_attn_partials(self, m, l, acc):
        """Merge per-cp-shard online-softmax partials into the global
        attention state — the context-parallel seam next to
        `gather_heads` (ISSUE 18). Each cp shard streams only its LOCAL
        pages and emits (m [rows...], l [rows...], acc [rows..., dh])
        f32 partials; this applies the SAME rescale recurrence the
        paged kernels run between page tiles, lifted one level to run
        between CHIPS:

            M     = pmax(m, cp)             # global running max
            w     = exp(m - M)              # per-shard rescale
            l_g   = psum(l * w, cp)
            acc_g = psum(acc * w[..., None], cp)

        Only the stats + weighted accumulator cross the wire — never
        the KV pages — so the merge costs O(rows * nh * dh) f32 per
        layer against the O(ctx * nkv * dh) pool stream it shards.
        Rows with no valid key anywhere carry the finite _NEG_INF
        sentinel (never true -inf), so w = exp(0) = 1 and l_g = 0 —
        the caller's finalize zeros them, and no NaN can form.

        With FLAGS_quantized_collectives the weighted accumulator —
        the only payload with real width — ships via the int8
        two-hop psum (`parallel.collectives.quantized_psum`); the
        scalar m/l stats always merge exact."""
        if self.cp <= 1:
            return m, l, acc
        m_g = jax.lax.pmax(m, self.cp_axis)
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, self.cp_axis)
        acc_w = acc * w[..., None]
        if self.quantized:
            from ..parallel.collectives import quantized_psum

            acc_g = quantized_psum(acc_w, self.cp_axis)
        else:
            acc_g = jax.lax.psum(acc_w, self.cp_axis)
        return m_g, l_g, acc_g


def make_serving_tp(cfg, serving_mp: Optional[int] = None,
                    quantized_collectives: Optional[bool] = None,
                    serving_cp: Optional[int] = None) \
        -> Optional[ServingTP]:
    """ServingTP geometry for the resolved (mp, cp) degrees, or None at
    mp=1 and cp=1 (the single-chip path takes no TP plumbing at all).
    `quantized_collectives` (default: the flag) sends the head seam's
    all-gather (`gather_heads`) and the cp merge's accumulator psum
    (`merge_attn_partials`) as int8 (ISSUE 15); `serving_cp` (default: the
    flag) the page-sharded context-parallel geometry (ISSUE 18) —
    at cp > 1 with mp == 1 the head seam (`gather_heads`) is identity
    and only `merge_attn_partials` crosses chips."""
    mp = resolve_serving_mp(serving_mp)
    cp = resolve_serving_cp(serving_cp)
    if mp <= 1 and cp <= 1:
        return None
    return ServingTP(cfg, mp, quantized=quantized_collectives, cp=cp)


def _tp_weight_spec(name: str, w, tp: ServingTP):
    """PartitionSpec(s) for one serving weight under ServingTP: q (and,
    when kv shards, k/v) projections shard on their OUTPUT-head axis —
    dense [in, out] on axis 1; nn.quant pairs (int8/int4-packed
    [out, in_packed], per-channel scale [out]) on axis 0 of both — and
    EVERYTHING else (o-proj included: it consumes the all-gathered
    activations) replicates. Mirrors `shard_serving_params`; both feed
    shard_map in_specs."""
    from jax.sharding import PartitionSpec as _P

    sharded = name.endswith("q_proj.weight") or (
        tp.kv_sharded and (name.endswith("k_proj.weight")
                           or name.endswith("v_proj.weight")))
    if isinstance(w, tuple):
        if sharded:
            return (_P(tp.axis, None), _P(tp.axis))
        return tuple(_P(*([None] * getattr(a, "ndim", 0))) for a in w)
    if sharded:
        return _P(None, tp.axis)
    return _P(*([None] * getattr(w, "ndim", 0)))


def serving_param_specs(params: dict, tp: ServingTP) -> dict:
    """{name: PartitionSpec | (spec, spec)} mirroring a `_decode_params`
    dict under ServingTP — the in_specs tree every sharded serving
    program passes to shard_map."""
    return {name: _tp_weight_spec(name, w, tp)
            for name, w in params.items()}


def shard_serving_params(params: dict, mesh, tp: ServingTP) -> dict:
    """Lay a `_decode_params` dict out on the serving mesh per
    `serving_param_specs` (one device_put per weight; sharded q/k/v
    columns, everything else replicated across the mp devices)."""
    specs = serving_param_specs(params, tp)
    out = {}
    for name, w in params.items():
        sp = specs[name]
        if isinstance(w, tuple):
            out[name] = tuple(
                jax.device_put(a, NamedSharding(mesh, s))
                for a, s in zip(w, sp))
        else:
            out[name] = jax.device_put(w, NamedSharding(mesh, sp))
    return out


def quantize_kv_pages(kv):
    """Symmetric absmax int8 quantization of whole K/V pages.

    kv: [..., block_size, dh] with the per-(page, kv-head) reduction
    over the trailing two axes (callers pass [b, n_pre, nkv, block, dh]
    page stacks). The absmax is computed in f32 BEFORE any bf16
    round-trip. Returns (int8 same shape, scale [...] f32) with
    scale = absmax / 127 — dequant is q * scale; an all-zero page keeps
    scale 0 (dequantizes to exact zeros)."""
    kf = kv.astype(jnp.float32)
    amax = jnp.max(jnp.abs(kf), axis=(-2, -1)) / 127.0
    safe = jnp.where(amax > 0, amax, 1.0)
    q = jnp.round(kf / safe[..., None, None]).astype(jnp.int8)
    return q, amax


def make_paged_kv_q8_helpers(b, n_pre, nkv, dh, block_size, tables):
    """int8 twins of `make_paged_kv_helpers`, operating on
    (pool int8 [max_pages, nkv, block, dh], scale f32 [max_pages, nkv])
    pairs:

    - `to_pages_q8(kv)` -> (int8 pages, scales): the prefill transpose
      fused with quantize-on-scatter;
    - `kv_write_q8(kct, vct, k, v, lens)` with kct/vct = (pool, scale)
      tuples: the per-token decode commit. The page's absmax scale is
      monotone — a token louder than the page's current absmax grows the
      scale and the already-stored rows rescale in the same read-modify-
      write (one page per token per layer, noise next to the full-cache
      stream each decode step already pays); `slot == 0` resets the
      scale, so a recycled page can never poison its new owner with a
      stale (possibly huge) absmax."""
    to_pages, _ = make_paged_kv_helpers(b, n_pre, nkv, dh, block_size,
                                        tables)

    def to_pages_q8(kv):
        return quantize_kv_pages(to_pages(kv))

    def _commit_token(pool, scales, tok, page, slot):
        tokf = tok.astype(jnp.float32)                     # [b, nkv, dh]
        tok_amax = jnp.max(jnp.abs(tokf), axis=-1) / 127.0  # [b, nkv]
        # fresh page (slot 0): whatever scale the page's previous owner
        # left behind is dead — start the absmax chain from this token
        old = jnp.where((slot == 0)[:, None], 0.0, scales[page])
        new = jnp.maximum(old, tok_amax)
        safe = jnp.where(new > 0, new, 1.0)
        ratio = old / safe                                  # <= 1
        pg = jnp.round(pool[page].astype(jnp.float32)
                       * ratio[..., None, None])
        q = jnp.round(tokf / safe[..., None])
        pg = pg.at[jnp.arange(b), :, slot, :].set(q)
        pool = pool.at[page].set(
            jnp.clip(pg, -127, 127).astype(jnp.int8))
        return pool, scales.at[page].set(new)

    def kv_write_q8(kct, vct, k, v, lens):
        page = tables[jnp.arange(b), lens // block_size]
        slot = lens % block_size
        kc, ksc = _commit_token(*kct, k[:, 0], page, slot)
        vc, vsc = _commit_token(*vct, v[:, 0], page, slot)
        return (kc, ksc), (vc, vsc)

    return to_pages_q8, kv_write_q8


def hash_prefix_blocks(tokens, block_size: int):
    """Chained per-block prompt hashes: hash i covers tokens
    [0, (i+1)*block_size) — a hit on hash i therefore implies the WHOLE
    prefix through block i matches, so a cached-prefix walk can stop at
    the first miss (the vLLM prefix-cache keying scheme)."""
    hashes = []
    h = block_size  # seed the chain with the geometry
    for i in range(len(tokens) // block_size):
        h = hash((h, tuple(tokens[i * block_size:(i + 1) * block_size])))
        hashes.append(h)
    return hashes


class PagedKVManager:
    """Host-side KV page allocator for the paged generation path
    (reference: the block-table management serving engines drive above
    block_multihead_attention.py:25 — allocate pages at prefill, free at
    sequence end, reuse freed pages for new requests).

    Pages are identified by integer ids into the [max_pages, H,
    block_size, D] cache pool; `alloc` hands out the lowest free ids
    (freed pages are reused before fresh ones), `free` returns them.

    Block-aligned prefix cache (refcounted): a page holding one FULL
    block of a prompt's K/V may be registered under the chained hash of
    that prefix (`insert_prefix`); later requests whose prompt starts
    with the same blocks map the cached pages into their block tables
    (`acquire_prefix`) instead of recomputing them. Every live mapping
    holds a reference; `free` is refcount-aware — it releases the
    reference and only makes the page reusable once no request maps it,
    parking refcount-0 cached pages on an LRU list that `alloc_pages`
    evicts (oldest first) when the strictly-free list runs short. A
    referenced cached page is therefore never recycled, which is what
    keeps a hung-slot retire from pulling a shared prefix out from
    under the surviving slots."""

    def __init__(self, max_pages: int, block_size: int = 64):
        self.max_pages = int(max_pages)
        self.block_size = int(block_size)
        self._free = list(range(self.max_pages - 1, -1, -1))  # pop() = min
        # prefix cache state: hash -> page; page -> [hash, refcount];
        # refcount-0 cached pages in least-recently-released order
        self._hash_to_page = {}
        self._cached = {}
        self._lru = OrderedDict()
        self.prefix_evictions = 0
        self._geometry = None  # set_pool_geometry
        # the second kind of pool (set_window_rings): none by default
        self.ring_pages = self.n_rings = self._ring_layers = 0
        self._free_rings = []
        # the fourth kind (set_state_rows): none by default
        self.n_state_rows = self.state_row_bytes = 0
        self._free_states = []

    # ---- pool byte accounting -------------------------------------------

    @staticmethod
    def page_bytes(block_size: int, *, n_layers: int, num_kv_heads: int,
                   head_dim: int, kv_cache_dtype: str = "bf16",
                   mp: int = 1, latent_bytes: int = 0,
                   index_bytes: int = 0) -> int:
        """PER-CHIP device bytes ONE page costs across all layers: K + V
        pools (2 x nkv x block x dh x itemsize per layer) plus, for
        int8, the per-(page, kv-head) f32 absmax scale rows
        (2 x nkv x 4). Under kv-head sharding (`mp` — ServingTP with
        nkv % mp == 0) each chip holds only nkv/mp heads of every page,
        so a page costs 1/mp of the replicated bytes per chip; page ids
        and page COUNTS stay global (every chip maps the same ids).
        The third pool kind — layers that keep their own rows (latent
        attention) — adds `latent_bytes` + `index_bytes` a token: a
        token's latent rows and index keys summed over those layers."""
        mp = int(mp)
        if mp > 1:
            if num_kv_heads % mp:
                raise ValueError(
                    f"per-shard geometry needs kv heads {num_kv_heads} "
                    f"divisible by mp {mp} (the MQA fallback replicates "
                    "the pools — pass mp=1)")
            num_kv_heads //= mp
        itemsize = 1 if kv_cache_dtype == "int8" else 2
        per_layer = 2 * num_kv_heads * block_size * head_dim * itemsize
        if kv_cache_dtype == "int8":
            per_layer += 2 * num_kv_heads * 4
        return per_layer * n_layers \
            + block_size * (int(latent_bytes) + int(index_bytes))

    @classmethod
    def pages_for_bytes(cls, budget_bytes: int, block_size: int, *,
                        n_layers: int, num_kv_heads: int, head_dim: int,
                        kv_cache_dtype: str = "bf16", mp: int = 1,
                        cp: int = 1, latent_bytes: int = 0,
                        index_bytes: int = 0) -> int:
        """FLEET pages a PER-CHIP device byte budget holds — the
        capacity side of the int8 win (at the same budget an int8 pool
        holds ~2x the pages) AND of both sharding axes: at mp shards a
        per-chip budget buys ~mp x the aggregate cacheable pages
        (each chip stores only its 1/mp head slice of every page), and
        at cp shards it buys cp x the PAGE COUNT outright (each chip
        stores only its 1/cp of the fleet's pages — the context-
        parallel axis, ISSUE 18). The result is divisible by cp by
        construction (per-chip count x cp), satisfying
        `set_pool_geometry`'s sharding invariant."""
        per_page = cls.page_bytes(block_size, n_layers=n_layers,
                                  num_kv_heads=num_kv_heads,
                                  head_dim=head_dim,
                                  kv_cache_dtype=kv_cache_dtype, mp=mp,
                                  latent_bytes=latent_bytes,
                                  index_bytes=index_bytes)
        return max(0, int(budget_bytes) // per_page) * max(1, int(cp))

    def set_pool_geometry(self, *, n_layers: int, num_kv_heads: int,
                          head_dim: int, kv_cache_dtype: str = "bf16",
                          mp: int = 1, cp: int = 1, latent_bytes: int = 0,
                          index_bytes: int = 0):
        """Record the pool geometry this manager's page ids index into,
        enabling `kv_pool_bytes()` (benches attribute capacity-driven
        hit-rate changes with it). `mp` is the kv-head shard count (1
        when the pools are replicated — including the MQA fallback) and
        `cp` the PAGE shard count (ISSUE 18: global page id g lives on
        cp shard g // (max_pages // cp)), so byte accounting reports
        PER-CHIP cost while page ids / capacity math stay fleet-wide.
        A fleet page count that does not split evenly across the cp
        shards raises `PageShardingError` — silent remainder pages
        would desynchronize the contiguous owner map every chip
        derives locally."""
        resolve_kv_cache_dtype(kv_cache_dtype)
        if mp > 1 and num_kv_heads % mp:
            raise ValueError(
                f"kv heads {num_kv_heads} not divisible by mp {mp}; "
                "replicated pools record mp=1")
        cp = int(cp)
        if cp < 1:
            raise ValueError(f"cp must be >= 1, got {cp}")
        if self.max_pages % cp:
            raise PageShardingError(
                f"fleet page count {self.max_pages} not divisible by "
                f"cp {cp}: the page axis shards contiguously "
                f"({self.max_pages} % {cp} == {self.max_pages % cp} "
                "pages would have no owner)")
        self._geometry = dict(n_layers=int(n_layers),
                              num_kv_heads=int(num_kv_heads),
                              head_dim=int(head_dim),
                              kv_cache_dtype=kv_cache_dtype,
                              mp=int(mp), cp=cp,
                              latent_bytes=int(latent_bytes),
                              index_bytes=int(index_bytes))

    # ---- the second pool kind: per-sequence rings of window layers ------

    def set_window_rings(self, n_rings: int, ring_pages: int,
                         n_layers: int) -> None:
        """Sliding-window layers keep a sequence's last positions only, so
        their K/V live in pools of their own: `n_rings` rings of
        `ring_pages` pages (one a live sequence), page 0 the sink of idle
        rows. A ring is handed out whole at admission and never grows;
        position t of its sequence lives at ring page (t // block) %
        ring_pages. `n_layers` window layers share the page ids (as the
        full layers share the first kind's), which `kv_pool_bytes` counts.
        The prefix cache knows nothing of rings."""
        self.ring_pages, self.n_rings = int(ring_pages), int(n_rings)
        self._ring_layers = int(n_layers)
        self._free_rings = list(range(self.n_rings - 1, -1, -1))

    @property
    def window_pool_pages(self) -> int:
        """Pages of a window layer's pool: the rings and the sink."""
        return self.n_rings * self.ring_pages + 1 if self.n_rings else 0

    @property
    def n_rings_free(self) -> int:
        return len(self._free_rings)

    def alloc_ring(self) -> list:
        if not self._free_rings:
            raise RuntimeError(
                f"all {self.n_rings} window rings are in use (one a live "
                "sequence: admission holds to that)")
        r = self._free_rings.pop()
        return list(range(1 + r * self.ring_pages,
                          1 + (r + 1) * self.ring_pages))

    def free_ring(self, ring) -> None:
        if ring:
            self._free_rings.append((ring[0] - 1) // self.ring_pages)

    # ---- the fourth kind: a row of state a sequence ---------------------

    def set_state_rows(self, n_rows: int, row_bytes: int) -> None:
        """Layers that keep a state of fixed size a sequence (a recurrent
        mixer beside attention) hold it in arrays of `n_rows` rows, one a
        live sequence, row 0 the sink of idle rows; `row_bytes` is a row's
        bytes over those layers. A row is handed out whole at admission and
        never grows; the programs zero it at its sequence's first position.
        The prefix cache knows nothing of rows."""
        self.n_state_rows, self.state_row_bytes = int(n_rows), int(row_bytes)
        self._free_states = list(range(self.n_state_rows - 1, 0, -1))

    @property
    def n_states_free(self) -> int:
        return len(self._free_states)

    def alloc_state(self) -> int:
        if not self._free_states:
            raise RuntimeError(
                f"all {self.n_state_rows - 1} state rows are in use (one a "
                "live sequence: admission holds to that)")
        return self._free_states.pop()

    def free_state(self, row) -> None:
        if row:
            self._free_states.append(row)

    def kv_pool_bytes(self, aggregate: bool = False) -> int:
        """Device bytes of the K/V pools of both kinds (+ int8 scale
        arrays) this manager allocates pages of, and of the state rows —
        PER CHIP by default (the number an
        HBM budget constrains; at cp > 1 each chip holds only
        max_pages/cp of the fleet's pages); `aggregate=True` multiplies
        both shard counts back in (the whole-fleet footprint). Requires
        `set_pool_geometry`."""
        if self._geometry is None:
            raise RuntimeError(
                "kv_pool_bytes() needs set_pool_geometry(...) first")
        geo = dict(self._geometry)
        cp = geo.pop("cp", 1)
        per_chip = (self.max_pages // cp) \
            * self.page_bytes(self.block_size, **geo)
        if self.n_rings:   # window layers: never sharded (the engine refuses)
            per_chip += self.window_pool_pages * self.page_bytes(
                self.block_size, **dict(geo, n_layers=self._ring_layers,
                                        latent_bytes=0, index_bytes=0))
        # state rows: never sharded (the engine refuses)
        per_chip += self.n_state_rows * self.state_row_bytes
        return per_chip * geo["mp"] * cp if aggregate else per_chip

    @property
    def n_free(self) -> int:
        """Strictly free pages (no eviction needed)."""
        return len(self._free)

    @property
    def n_available(self) -> int:
        """Pages allocatable right now: free + evictable (refcount-0
        cached). The admission bound — a referenced cached page is NOT
        available."""
        return len(self._free) + len(self._lru)

    @property
    def n_cached(self) -> int:
        """Pages currently registered in the prefix cache (any refcount)."""
        return len(self._cached)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.block_size)

    def alloc(self, n_tokens: int):
        return self.alloc_pages(self.pages_needed(n_tokens))

    def alloc_pages(self, n: int):
        # pool tight: evict refcount-0 cached pages, least recently
        # released first, dropping their hash mapping (future lookups
        # miss and recompute)
        evicted = False
        while len(self._free) < n and self._lru:
            page, _ = self._lru.popitem(last=False)
            h, refs = self._cached.pop(page)
            assert refs == 0, f"page {page} on LRU with refs {refs}"
            del self._hash_to_page[h]
            self._free.append(page)
            self.prefix_evictions += 1
            evicted = True
        if n > len(self._free):
            raise RuntimeError(
                f"paged KV pool exhausted: need {n} pages, "
                f"{len(self._free)} free of {self.max_pages} "
                f"({len(self._cached)} cached, {len(self._lru)} evictable)")
        if evicted:
            # only evictions append out-of-order ids (free() re-sorts)
            self._free.sort(reverse=True)
        return [self._free.pop() for _ in range(n)]

    def free(self, pages) -> None:
        """Refcount-aware release. Cached pages drop one reference and
        park on the LRU at zero (still mapped — a future prefix hit
        revives them); private pages return to the free list.

        Pages are processed in REVERSE order: a request's page list is
        block-ordered, so its deepest prefix blocks land oldest on the
        LRU and evict first — evicting block 0 before block 1 would
        orphan block 1's mapping (the chained-hash walk stops at the
        first miss and could never reach it again)."""
        for p in reversed(list(pages)):
            if not 0 <= p < self.max_pages:
                raise ValueError(f"page id {p} out of range")
            meta = self._cached.get(p)
            if meta is not None:
                if meta[1] <= 0:
                    raise ValueError(
                        f"over-release of cached page {p} (refcount 0)")
                meta[1] -= 1
                if meta[1] == 0:
                    self._lru[p] = None
                continue
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)
        self._free.sort(reverse=True)

    # ---- prefix cache ---------------------------------------------------

    def prefix_lookup(self, tokens, max_blocks: Optional[int] = None,
                      hashes=None):
        """Longest cached block-aligned prefix of `tokens` WITHOUT taking
        references. Returns (n_blocks_hit, n_lru_hits) — the second
        counts hits currently refcount-0, i.e. pages that will leave the
        available pool when acquired (admission must budget for them).
        `hashes` (from hash_prefix_blocks) skips re-hashing a prompt the
        caller already hashed — the scheduler plans every waiting
        request each step, so this sits on the admission hot path."""
        hits = lru = 0
        if hashes is None:
            hashes = hash_prefix_blocks(tokens, self.block_size)
        if max_blocks is not None:
            hashes = hashes[:max_blocks]
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None:
                break
            hits += 1
            if self._cached[page][1] == 0:
                lru += 1
        return hits, lru

    def acquire_prefix(self, tokens, max_blocks: Optional[int] = None,
                       hashes=None):
        """Walk the chained block hashes of `tokens`, taking a reference
        on every hit (pinning the page against eviction). Returns the
        cached page ids, in block order; release each with free()."""
        pages = []
        if hashes is None:
            hashes = hash_prefix_blocks(tokens, self.block_size)
        if max_blocks is not None:
            hashes = hashes[:max_blocks]
        for h in hashes:
            page = self._hash_to_page.get(h)
            if page is None:
                break
            meta = self._cached[page]
            if meta[1] == 0:
                del self._lru[page]
            meta[1] += 1
            pages.append(page)
        return pages

    def insert_prefix(self, tokens, pages, start_block: int = 0,
                      hashes=None) -> int:
        """Register `pages` — one per full block of `tokens`, starting at
        block `start_block`, already holding that block's K/V — under the
        chained prefix hashes. A hash that is already mapped is SKIPPED
        (first writer wins; the caller keeps its page as a private copy),
        so two same-prefix requests prefilled in one batch never
        double-insert. Each inserted page gains one reference owned by
        the caller — release it with free(). Returns the insert count."""
        if hashes is None:
            hashes = hash_prefix_blocks(tokens, self.block_size)
        inserted = 0
        for h, page in zip(hashes[start_block:], pages):
            if h in self._hash_to_page:
                continue
            if page in self._cached:
                raise ValueError(
                    f"page {page} already registered in the prefix cache")
            if page in self._free:
                raise ValueError(f"cannot insert free page {page}")
            self._hash_to_page[h] = page
            self._cached[page] = [h, 1]
            inserted += 1
        return inserted

    def tables_for_batch(self, seq_capacities):
        """Allocate per-sequence page lists and return (tables [B, max_n]
        int32 array, page_lists) — rows padded with their own last page
        id (never read past capacity)."""
        lists = [self.alloc(c) for c in seq_capacities]
        width = max(len(l) for l in lists)
        tbl = np.asarray([l + [l[-1]] * (width - len(l)) for l in lists],
                         np.int32)
        return jnp.asarray(tbl), lists


def build_paged_generate(cfg, b, sb, max_new, block_size: int = 64,
                         eos_token_id=None, do_sample=False, top_k=0,
                         serving_mp=None):
    """Generation over a PAGED KV cache with block tables — the vLLM-class
    serving core (reference: block_multihead_attention.py:25 + the paged
    decode kernels in paddle/phi/kernels/fusion/gpu/block_attn.h).

    Layout: per layer, key/value pools [max_pages, Hkv, block_size, D];
    a traced block table [B, pages_per_seq] maps each sequence's logical
    blocks to pool pages (any permutation — the allocator decides).
    Per-sequence true prompt lengths arrive as a traced VECTOR, so one
    compiled program serves a varying-length (ragged) batch: prefill is
    computed over the padded rectangle, per-sequence watermarks mask the
    garbage slots until overwritten, and each row's first sampled token
    reads its own last-position logits.

    Decode attention: the Pallas paged kernel
    (kernels/decode_attention.paged_decode_attention) for equal heads AND
    grouped queries — the grouped kernel copies a slot's live pages,
    every kv head of each, and scores all query heads in VMEM, so no
    path ever gathers pages at query width (the round-4 jnp fallback is
    gone).

    Weights are read through `_mm`, so the dec_params dict may hold
    dense OR nn.quant-quantized projections (int8/int4 serving composes
    with paging for free). With FLAGS_kv_cache_dtype=int8 (read when
    this factory runs — program-BUILD time) the pools are int8 +
    per-(page, kv-head) f32 absmax scales: prefill quantizes on the
    page scatter, decode commits re-quantize per token, and the Pallas
    kernels dequantize in-kernel. Returns
    run(dec_params, ids, s0_vec, tables, key, temperature, top_p).

    With FLAGS_serving_mp > 1 (or `serving_mp=`, likewise read at
    BUILD time) the whole program runs under shard_map on the serving
    mesh: pools (created inside the body) hold only the shard's local
    kv heads, q/k/v weights arrive column-sharded per
    `serving_param_specs`, and the per-layer o-proj activation
    all-gather is the one cross-chip collective. Tokens out are
    replicated — byte-identical to the single-chip program.
    """
    from ..kernels.decode_attention import paged_decode_attention

    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    n_layers = cfg.num_hidden_layers
    eps = cfg.rms_norm_eps
    if sb % block_size:
        raise ValueError(f"bucketed prompt length {sb} must be a multiple "
                         f"of block_size {block_size}")
    total = sb + max_new
    pages_per_seq = -(-total // block_size)
    n_pre = sb // block_size
    quant_kv = resolve_kv_cache_dtype() == "int8"
    tp = make_serving_tp(cfg, serving_mp)
    # the kv-head count of the pools the BODY sees (local under tp;
    # full when replicated — including the MQA fallback)
    nkv_eff = tp.nkv_local if tp is not None else nkv

    head_logits = _make_head_logits(cfg)
    base_prefill = _make_prefill(cfg, b, sb, tp=tp)

    def prefill(p, ids, tables, pools):
        to_pages, _ = make_paged_kv_helpers(b, n_pre, nkv_eff, dh,
                                            block_size, tables)
        to_pages_q8, _ = make_paged_kv_q8_helpers(b, n_pre, nkv_eff, dh,
                                                  block_size, tables)
        h, kvs = base_prefill(p, ids)
        for i, (k, v) in enumerate(kvs):
            kc, vc = pools[i]
            # scatter this layer's prefill K/V into the allocated pages
            if quant_kv:
                (kcp, ksc), (vcp, vsc) = kc, vc
                qk, sk_ = to_pages_q8(k)
                qv, sv_ = to_pages_q8(v)
                pools[i] = (
                    (kcp.at[tables[:, :n_pre]].set(qk),
                     ksc.at[tables[:, :n_pre]].set(sk_)),
                    (vcp.at[tables[:, :n_pre]].set(qv),
                     vsc.at[tables[:, :n_pre]].set(sv_)))
            else:
                pools[i] = (
                    kc.at[tables[:, :n_pre]].set(
                        to_pages(k).astype(kc.dtype)),
                    vc.at[tables[:, :n_pre]].set(
                        to_pages(v).astype(vc.dtype)))
        return h, pools

    def paged_attn(q1, kc, vc, tables, lens):
        """q1 [b, nh, dh]; lens [b] = cached positions (current token
        already written at lens[b]). The Pallas kernel covers both equal
        and grouped heads (a loop over each slot's live pages).
        int8 pools arrive as (pool, scale) tuples."""
        if isinstance(kc, tuple):
            (kcp, ksc), (vcp, vsc) = kc, vc
            return paged_decode_attention(q1, kcp, vcp, tables, lens,
                                          k_scale=ksc, v_scale=vsc)
        return paged_decode_attention(q1, kc, vc, tables, lens)

    def make_decode_step(tables):
        """The shared per-layer decode body (_make_decode_step) with the
        KV store swapped for page/slot scatter + table-indirect attention;
        `pos` is the per-sequence [b] length vector (ragged batch)."""
        _, kv_write = make_paged_kv_helpers(b, n_pre, nkv_eff, dh,
                                            block_size, tables)
        if quant_kv:
            _, kv_write = make_paged_kv_q8_helpers(b, n_pre, nkv_eff, dh,
                                                   block_size, tables)

        def kv_attend(q1, kc, vc, lens):
            return paged_attn(q1, kc, vc, tables, lens)

        return _make_decode_step(cfg, b, kv_write=kv_write,
                                 kv_attend=kv_attend, tp=tp)

    def run(p_dec, ids, s0_vec, tables, key, temperature, top_p):
        dtype = p_dec["llama.embed_tokens.weight"].dtype
        max_pages = b * pages_per_seq
        if quant_kv:
            def pool():
                return (jnp.zeros((max_pages, nkv_eff, block_size, dh),
                                  jnp.int8),
                        jnp.zeros((max_pages, nkv_eff), jnp.float32))
            pools = [(pool(), pool()) for _ in range(n_layers)]
        else:
            pools = [(jnp.zeros((max_pages, nkv_eff, block_size, dh),
                                dtype),
                      jnp.zeros((max_pages, nkv_eff, block_size, dh),
                                dtype))
                     for _ in range(n_layers)]
        h, pools = prefill(p_dec, ids, tables, pools)
        # each row's own last-position logits (ragged batch)
        h_last = h[jnp.arange(b), s0_vec - 1][:, None, :]
        last_logits = head_logits(h_last, p_dec)[:, -1]
        kcs = [kv[0] for kv in pools]
        vcs = [kv[1] for kv in pools]
        return _decode_tail(make_decode_step(tables), p_dec,
                            kcs, vcs, last_logits, s0_vec, key,
                            temperature, top_p, ids.dtype, max_new,
                            eos_token_id, do_sample, top_k, b)

    if tp is None:
        return run

    from ..parallel.mesh import serving_mesh
    from jax import shard_map

    mesh = serving_mesh(tp.mp)

    def run_sharded(p_dec, ids, s0_vec, tables, key, temperature, top_p):
        # in_specs are derived from the params structure at trace time
        # (quant pairs vs dense); pools never cross the boundary — they
        # are born local inside the body
        specs = serving_param_specs(p_dec, tp)
        fn = shard_map(run, mesh=mesh,
                       in_specs=(specs, P(), P(), P(), P(), P(), P()),
                       out_specs=P(), check_vma=False)
        return fn(p_dec, ids, s0_vec, tables, key, temperature, top_p)

    return run_sharded


def init_quant_serving_params(cfg, quant, seed: int = 0,
                              dtype=jnp.bfloat16):
    """Random-initialised quantized serving parameter dict in the
    `_decode_params` layout (quantized projections + fp embed/norms),
    built weight-by-weight ON DEVICE so the full-precision model never
    exists anywhere — host RAM or HBM — at once (peak transient = one
    fp32 weight). This is the 7B-on-one-16GB-chip bootstrap for serving
    benches and shape tests; real checkpoints reach the same layout via
    set_state_dict + jit_generate(..., quant=..., prefill_with_quant=True).

    Reference analog: the weight_only checkpoint conversion feeding
    python/paddle/nn/quant/quantized_linear.py weight_only_linear."""
    from ..nn.quant import weight_quantize

    key = jax.random.PRNGKey(seed)
    h, dh = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    im = cfg.intermediate_size

    def nxt():
        nonlocal key
        key, k = jax.random.split(key)
        return k

    def quantized(shape):
        w = jax.random.normal(nxt(), shape, jnp.float32) * 0.02
        wq, sc = weight_quantize(Tensor(w), algo=quant)
        return (unwrap(wq), unwrap(sc))

    p = {"llama.embed_tokens.weight": (
        jax.random.normal(nxt(), (cfg.vocab_size, h), jnp.float32)
        * 0.02).astype(dtype)}
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        p[pre + "input_layernorm.weight"] = jnp.ones((h,), dtype)
        p[pre + "post_attention_layernorm.weight"] = jnp.ones((h,), dtype)
        p[pre + "self_attn.q_proj.weight"] = quantized((h, nh * dh))
        p[pre + "self_attn.k_proj.weight"] = quantized((h, nkv * dh))
        p[pre + "self_attn.v_proj.weight"] = quantized((h, nkv * dh))
        p[pre + "self_attn.o_proj.weight"] = quantized((nh * dh, h))
        p[pre + "mlp.gate_proj.weight"] = quantized((h, im))
        p[pre + "mlp.up_proj.weight"] = quantized((h, im))
        p[pre + "mlp.down_proj.weight"] = quantized((im, h))
    p["llama.norm.weight"] = jnp.ones((h,), dtype)
    if not cfg.tie_word_embeddings:
        p["lm_head.weight"] = quantized((h, cfg.vocab_size))
    return p


def _decode_tail(decode_step, p_dec, kcs, vcs, last_logits,
                 s0, key, temperature, top_p, ids_dtype, max_new,
                 eos_token_id, do_sample, top_k, b):
    """Shared post-prefill decode loop: sample the first token from the
    prompt's last logits, then scan single-token decode steps."""
    key, k0 = jax.random.split(key)
    first = _sample_next(last_logits.astype(jnp.float32), k0, do_sample,
                         temperature, top_k, top_p)
    done0 = (first == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((b,), bool)

    def step(carry, _):
        tok, pos, kcs, vcs, done, key = carry
        logits, kcs, vcs = decode_step(p_dec, kcs, vcs, tok[:, None], pos)
        key, ks = jax.random.split(key)
        nxt = _sample_next(logits.astype(jnp.float32), ks, do_sample,
                           temperature, top_k, top_p)
        if eos_token_id is not None:
            nxt = jnp.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
        return (nxt, pos + 1, kcs, vcs, done, key), nxt

    toks = None
    if max_new > 1:
        _, toks = jax.lax.scan(
            step, (first, s0.astype(jnp.int32), kcs, vcs, done0, key),
            None, length=max_new - 1)
    pieces = [first[:, None]]
    if toks is not None:
        pieces.append(jnp.swapaxes(toks, 0, 1))
    return jnp.concatenate(pieces, axis=1).astype(ids_dtype)


def _make_decode_step(cfg, b, max_seq=None, kv_write=None, kv_attend=None,
                      tp=None, kv_window=None, tables=None, block_size=None,
                      state_rows=None):
    """Single-token decode step — the per-layer transformer math shared
    by EVERY generation program (fp, quant-only, paged); only the KV
    store differs, injected via two callbacks:

      kv_write(kc, vc, k, v, pos)  -> (kc, vc)   store the token's K/V
          (k/v [B, 1, Hkv, D]; pos scalar or [B] vector of cached counts)
      kv_attend(q1, kc, vc, pos)   -> ctx [B, Hq, D]

    Defaults (both None, requires max_seq): contiguous [B, Hkv, max_seq,
    D] caches with the grouped masked softmax — the
    masked_multihead_attention math.

    With `tp` (ServingTP, inside a shard_map body) the projections
    compute only the local shard's heads, kv_write/kv_attend operate on
    the local pool shard, and the per-shard context all-gathers along
    the head axis before the replicated o-proj — the ONE cross-chip
    collective per decode step per layer (the o-proj activations).

    The layers are `served_model(cfg)`'s. `kv_window` is the (kv_write,
    kv_attend) pair of the window layers, over their rings. A layer with
    its own attention (`ServedLayer.attn`) reads and writes its pools
    itself through the paged `tables` [B, W] of `block_size`-token pages.
    A model whose layers keep state a sequence (`ServedLayer.mixer`)
    decodes only through `decode_step(..., states)`: `states` the layers'
    state arrays (None where a layer has no mixer), each batch row's at its
    row of `state_rows` [B] (idle rows: the sink row 0); the step then
    returns them as a fourth result. Where a layer is routed or has a mixer
    the step returns the layers' summed counters last."""
    model = served_model(cfg)
    nh, nkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   model.head_dim)
    nh_l = tp.nh_local if tp is not None else nh
    nkv_l = tp.nkv_local if tp is not None else nkv
    # GQA group from the LOCAL shard's head counts, never the full
    # model config (nh//nkv) — under the replicated-KV MQA fallback the
    # local group is nh_l // nkv, not nh // nkv (ISSUE 7 satellite)
    group = nh_l // nkv_l
    eps = cfg.rms_norm_eps
    head_logits = _make_head_logits(cfg)
    if model.window_layers and kv_window is None:
        raise ValueError(
            "a model with sliding-window layers decodes over per-sequence "
            "rings: pass their (kv_write, kv_attend) as kv_window (the "
            "serving engine does)")

    if kv_write is None:
        def kv_write(kc, vc, k, v, pos):
            kc = jax.lax.dynamic_update_slice(
                kc, jnp.swapaxes(k, 1, 2).astype(kc.dtype), (0, 0, pos, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, jnp.swapaxes(v, 1, 2).astype(vc.dtype), (0, 0, pos, 0))
            return kc, vc

    if kv_attend is None:
        # Pallas fused decode attention (round-5 roofline finding: the
        # old jnp einsum+softmax path read the KV cache at ~450 GB/s
        # effective and was the whole 17-20% residual above the serving
        # weight-read bound; the kernels stream it near peak)
        from ..kernels.decode_attention import (decode_attention,
                                                gqa_decode_attention)

        def kv_attend(q1, kc, vc, pos):
            lens = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
            if group == 1:
                return decode_attention(q1, kc, vc, lens)
            return gqa_decode_attention(q1, kc, vc, lens)

    def decode_step(p, kcs, vcs, tok, pos, states=None):
        """tok [B, 1] int32; pos: tokens already cached — a traced scalar
        (contiguous) or a per-sequence [B] vector (paged ragged batch;
        the [B, 1] position_ids broadcast per-example through the rope
        tables)."""
        if model.stateful and states is None:
            raise ValueError(
                "the model's layers keep state a sequence: decode through "
                "decode_step(..., states) (the serving engine does)")
        # the embedding stays dense (it's a gather, not a matmul)
        h = _embed(model, p[model.embed][tok[:, 0]][:, None, :])
        pos_ids = pos[:, None] if getattr(pos, "ndim", 0) == 1 \
            else jnp.reshape(pos, (1,))
        new_kcs, new_vcs, counts, carry = [], [], [], {}
        lane = {"tables": tables, "pos": pos, "block_size": block_size,
                "state_rows": state_rows}
        if states is not None:
            states = list(states)
        for i, layer in enumerate(model.layers):
            pre = layer.prefix
            if layer.attn is not None:
                h, kc, vc, carry, cs = _own_layer(
                    layer, h, p, eps, lambda x, kc, vc, carry: layer.attn
                    .decode(x, p, pre, kc, vc, lane, carry), kcs[i], vcs[i],
                    carry)
                new_kcs.append(kc)
                new_vcs.append(vc)
                counts += cs
                continue
            write, attend = (kv_write, kv_attend) if layer.window is None \
                else kv_window
            x = _k_rms(h, p[pre + "input_layernorm.weight"], eps)
            xa = _attn_in(layer, x)
            q = _mm(xa, p[pre + "self_attn.q_proj.weight"]).reshape(
                b, 1, nh_l, dh)
            k = _mm(xa, p[pre + "self_attn.k_proj.weight"]).reshape(
                b, 1, nkv_l, dh)
            v = _mm(xa, p[pre + "self_attn.v_proj.weight"]).reshape(
                b, 1, nkv_l, dh)
            if layer.attn_scales is not None:
                k = k * layer.attn_scales[1]
            q, k = apply_rotary_emb(q, k, position_ids=pos_ids,
                                    base=layer.rope_base,
                                    scaling=layer.rope_scaling)
            kc, vc = write(kcs[i], vcs[i], k, v, pos)
            new_kcs.append(kc)
            new_vcs.append(vc)
            with _attn_scope(layer):
                ctx = attend(q[:, 0], kc, vc, pos)      # [b, nh_l, dh]
            if tp is not None:
                ctx = tp.gather_heads(ctx)              # [b, nh, dh]
            y = _attn_out(layer, _mm(ctx.reshape(b, 1, nh * dh),
                                     p[pre + "self_attn.o_proj.weight"]))
            if layer.mixer is not None:
                # the mixer beside attention, on the same normed input
                m, states[i], count = layer.mixer.decode(
                    x, p, pre, states[i], lane)
                y = y + m
                counts.append(count)
            h = h + y
            x2 = _k_rms(h, p[pre + "post_attention_layernorm.weight"], eps)
            y, count = layer.mlp(x2, p, pre)
            h = h + y
            if count is not None:
                counts.append(count)
        h = _k_rms(_streams_out(model, h), p[model.norm], eps)
        logits = head_logits(h, p)[:, -1]
        out = (logits, new_kcs, new_vcs) if states is None \
            else (logits, new_kcs, new_vcs, states)
        if counts:
            return out + (sum(counts),)
        return out

    return decode_step


def _build_jit_generate(model, cfg, b, sb, max_new, max_seq, eos_token_id,
                        do_sample, top_k):
    """Assemble the pure (params, dec_params, ids, s0, key, temperature,
    top_p) -> new_tokens generation program: prefill through the model's
    own forward (flash attention) on the bucket-padded prompt, then a scan
    of single-token decode steps over padded [B, Hkv, max_seq, D] caches
    with grouped-GQA attention (one pass over the cache per token, the
    masked_multihead_attention math). ``s0`` (true prompt length) is a
    traced scalar: pad K/V slots at [s0, sb) sit above the `pos` watermark
    so decode attention never sees them before they are overwritten."""
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    n_layers = cfg.num_hidden_layers
    head_logits = _make_head_logits(cfg)
    decode_step = _make_decode_step(cfg, b, max_seq)

    def run(p, p_dec, ids, s0, key, temperature, top_p):
        with _tape.no_grad():
            out = model.func_call(
                p, Tensor(ids), caches=[(None, None)] * n_layers)
        logits, prefill = unwrap(out[0]), out[1]
        kcs, vcs = [], []
        for (k, v) in prefill:
            kc = jnp.zeros((b, nkv, max_seq, dh), unwrap(k).dtype)
            kcs.append(jax.lax.dynamic_update_slice(
                kc, jnp.swapaxes(unwrap(k), 1, 2), (0, 0, 0, 0)))
            vc = jnp.zeros((b, nkv, max_seq, dh), unwrap(v).dtype)
            vcs.append(jax.lax.dynamic_update_slice(
                vc, jnp.swapaxes(unwrap(v), 1, 2), (0, 0, 0, 0)))
        # logits at the TRUE last prompt position, not the padded end
        last_logits = jax.lax.dynamic_index_in_dim(
            logits, s0 - 1, axis=1, keepdims=False)
        return _decode_tail(decode_step, p_dec, kcs, vcs,
                            last_logits, s0, key, temperature, top_p,
                            ids.dtype, max_new, eos_token_id, do_sample,
                            top_k, b)

    return run


class LlamaPretrainingCriterion(Layer):
    """Shifted-token cross entropy (reference:
    semi_auto_parallel_llama_model.py LlamaPretrainingCriterion)."""

    def __init__(self, config: Optional[LlamaConfig] = None):
        super().__init__()

    def forward(self, logits, labels):
        def impl(lg, lb):
            lg32 = lg.astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lg32, axis=-1)
            picked = jnp.take_along_axis(
                lg32, lb.astype(jnp.int32)[..., None], axis=-1)[..., 0]
            return jnp.mean(lse - picked)

        return dispatch("llama_ce", impl, (logits, labels))


# ---------------------------------------------------------------------------
# sharding rules (logical-axis table; reference analog: per-op spmd_rules +
# the mp/sharding placements the fleet wrappers assign)
# ---------------------------------------------------------------------------

def llama_sharding_rules():
    """(param-name-suffix, partition dims) table. Weight layout is
    [in, out] (nn.Linear convention)."""
    return [
        # vocab over the ZeRO axis, h over mp: the lookup's gather output
        # then lands h-sharded-over-mp, which GSPMD reshards cleanly to the
        # (batch, sep)-sharded activation layout (vocab-over-mp made it log
        # "involuntary full rematerialization" on every embedding lookup)
        ("embed_tokens.weight", ("sharding", MP_AXIS)),     # [vocab, h]
        ("q_proj.weight", ("sharding", MP_AXIS)),           # [h, nh*dh]
        ("k_proj.weight", ("sharding", MP_AXIS)),
        ("v_proj.weight", ("sharding", MP_AXIS)),
        ("o_proj.weight", (MP_AXIS, "sharding")),           # [nh*dh, h]
        ("gate_proj.weight", ("sharding", MP_AXIS)),
        ("up_proj.weight", ("sharding", MP_AXIS)),
        ("down_proj.weight", (MP_AXIS, "sharding")),
        ("lm_head.weight", ("sharding", MP_AXIS)),          # [h, vocab]
        ("layernorm.weight", (None,)),
        ("norm.weight", (None,)),
    ]


def _param_sharding(mesh: Mesh, name: str, ndim: int,
                    shape) -> NamedSharding:
    for suffix, dims in llama_sharding_rules():
        if name.endswith(suffix):
            spec = []
            for i in range(ndim):
                d = dims[i] if i < len(dims) else None
                if d is not None and d in mesh.axis_names \
                        and shape[i] % int(mesh.shape[d]) == 0:
                    spec.append(d)
                else:
                    spec.append(None)
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P(*([None] * ndim)))


def shard_llama(model: Layer, mesh: Optional[Mesh] = None) -> Layer:
    """Lay every parameter out per the logical-axis rules: TP over `mp`,
    ZeRO-3/FSDP over `sharding` — one device_put per param, then XLA SPMD
    owns all collectives."""
    mesh = mesh or mesh_mod.get_global_mesh()
    if mesh is None:
        return model
    for name, p in model.named_parameters():
        sh = _param_sharding(mesh, name, p.ndim, p.shape)
        if isinstance(p._array, jax.core.Tracer):
            p._array = jax.lax.with_sharding_constraint(p._array, sh)
        else:
            p._array = jax.device_put(p._array, sh)
    return model
