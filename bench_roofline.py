"""Decompose the 7B int8 decode step against its weight-read roofline.

An older review (removed in PR 22) quoted 10.09 ms/step vs an 8.39 ms
weight-read bound (83%) and never explains the ~1.7 ms residual. This
bench isolates the non-weight terms by ablation on a DECODE-ONLY
program (a fori_loop of _make_decode_step with a traced trip count —
one compile per ablation, prefill excluded entirely):

- full:        the serving decode step (head + attention + KV r/w)
- head128:     lm_head swapped for a 128-col quantized head
               -> full - head128 = the real head's cost
- no_attn:     kv_attend returns q (KV writes stay, reads vanish)
               -> full - no_attn = attention read+compute cost
- kv_long:     same program at max_seq 2048 instead of 256
               -> (kv_long - full) / extra_bytes = measured KV-read
               bandwidth, scaled back to the serving max_seq

Each value is a (t_hi - t_lo)/(hi - lo) slope, median of 5 pairs.
Usage: python bench_roofline.py [7b_int8|1b_int8]
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import LlamaConfig, init_quant_serving_params
from paddle_tpu.models.llama import _make_decode_step
from paddle_tpu.nn.quant import weight_quantize
from paddle_tpu.core.tensor import Tensor, unwrap

from paddle_tpu.analysis.device_specs import DEVICE_SPECS

CONFIGS = {"7b_int8": "llama2_7b", "1b_int8": "llama_1b"}
B = 4
# ONE spec table (analysis/device_specs.py) owns the hardware numbers
# (ISSUE 13 hoist; value unchanged: v5e 819e9)
HBM_GBS = DEVICE_SPECS["tpu-v5e"].hbm_gbs


def build_decode_loop(cfg, b, max_seq=None, kv_attend=None,
                      kv_write=None):
    """(p, kcs, vcs, tok0, pos0, n) -> checksum: n chained decode steps
    with traced n (one compile serves every trip count)."""
    decode_step = _make_decode_step(cfg, b, max_seq, kv_write=kv_write,
                                    kv_attend=kv_attend)

    def run(p, kcs, vcs, tok0, pos0, n):
        def body(i, carry):
            tok, pos, kcs_, vcs_ = carry
            logits, kcs_, vcs_ = decode_step(p, kcs_, vcs_, tok[:, None],
                                             pos)
            return (jnp.argmax(logits, -1).astype(tok.dtype), pos + 1,
                    kcs_, vcs_)
        tok, pos, _, _ = jax.lax.fori_loop(
            0, n, body, (tok0, pos0, kcs, vcs))
        return jnp.sum(tok)

    return jax.jit(run)


def slope_ms(fn, args_lo, args_hi, span):
    from bench_util import paired_slope_ms

    np.asarray(fn(*args_lo))  # warm both legs (trip count traced)
    np.asarray(fn(*args_hi))

    def run(which):
        np.asarray(fn(*(args_hi if which else args_lo)))

    return paired_slope_ms(run, 0, 1, pairs=5) / span


def measure(name):
    cfg = getattr(LlamaConfig, CONFIGS[name])(dtype="bfloat16")
    quant = "weight_only_int8"
    p = init_quant_serving_params(cfg, quant, seed=0)
    np.asarray(jax.tree.leaves(p)[-1])
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    L = cfg.num_hidden_layers

    # 128-col head: same layout class (int8 + scales), 1/250th the bytes
    key = jax.random.PRNGKey(1)
    w128 = jax.random.normal(key, (cfg.hidden_size, 128), jnp.float32)
    wq, sc = weight_quantize(Tensor(w128), algo=quant)
    p_head128 = dict(p)
    p_head128["lm_head.weight"] = (unwrap(wq), unwrap(sc))

    def caches(max_seq):
        kcs = [jnp.zeros((B, nkv, max_seq, dh), jnp.bfloat16)
               for _ in range(L)]
        return kcs, [c for c in kcs]

    tok0 = jnp.ones((B,), jnp.int32)
    pos0 = jnp.asarray(128, jnp.int32)
    lo, hi = jnp.asarray(2), jnp.asarray(66)
    span = 64

    out = {"config": name, "batch": B}
    runs = [
        ("full", build_decode_loop(cfg, B, 256), p, 256),
        ("head128", build_decode_loop(cfg, B, 256), p_head128, 256),
        ("no_attn", build_decode_loop(
            cfg, B, 256, kv_attend=lambda q1, kc, vc, pos: q1), p, 256),
        ("kv_long", build_decode_loop(cfg, B, 2048), p, 2048),
    ]
    for nm, fn, pp, ms in runs:
        kcs, vcs = caches(ms)
        val = slope_ms(fn, (pp, kcs, vcs, tok0, pos0, lo),
                       (pp, kcs, vcs, tok0, pos0, hi), span)
        out[nm + "_ms"] = round(val, 3)

    # derived terms
    head_ms = out["full_ms"] - out["head128_ms"]
    attn_ms = out["full_ms"] - out["no_attn_ms"]
    extra_bytes = 2 * L * B * nkv * (2048 - 256) * dh * 2  # k+v bf16
    kv_bw = extra_bytes / ((out["kv_long_ms"] - out["full_ms"]) / 1e3) \
        if out["kv_long_ms"] > out["full_ms"] else float("nan")
    kv_at_256 = 2 * L * B * nkv * 256 * dh * 2 / kv_bw * 1e3 \
        if kv_bw == kv_bw else float("nan")
    out.update({
        "head_ms": round(head_ms, 3),
        "attn_read_compute_ms": round(attn_ms, 3),
        "kv_read_bw_gbs": round(kv_bw / 1e9, 1) if kv_bw == kv_bw else None,
        "kv_read_at_max_seq256_ms": round(kv_at_256, 3)
        if kv_at_256 == kv_at_256 else None,
    })
    print(json.dumps(out), flush=True)
    return out


def measure_paged(name, block_size: int = 64):
    """Split the paged-vs-contiguous gap into its two mechanisms: the
    per-token page/slot scatter write vs the table-indirect attend.
    Same decode-only loop; identity tables at max_seq 256."""
    from paddle_tpu.kernels.decode_attention import paged_decode_attention
    from paddle_tpu.models.llama import make_paged_kv_helpers

    cfg = getattr(LlamaConfig, CONFIGS[name])(dtype="bfloat16")
    quant = "weight_only_int8"
    p = init_quant_serving_params(cfg, quant, seed=0)
    np.asarray(jax.tree.leaves(p)[-1])
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    L = cfg.num_hidden_layers
    max_seq = 256
    n_blk = max_seq // block_size
    tables = jnp.asarray(
        np.arange(B * n_blk, dtype=np.int32).reshape(B, n_blk))
    _, kv_write = make_paged_kv_helpers(B, n_blk, nkv, dh, block_size,
                                        tables)

    def kv_attend(q1, kc, vc, lens):
        return paged_decode_attention(q1, kc, vc, tables, lens)

    def pools():
        ks = [jnp.zeros((B * n_blk, nkv, block_size, dh), jnp.bfloat16)
              for _ in range(L)]
        return ks, list(ks)

    tok0 = jnp.ones((B,), jnp.int32)
    pos0 = jnp.full((B,), 128, jnp.int32)  # paged path takes [B] lens
    lo, hi = jnp.asarray(2), jnp.asarray(66)

    out = {"config": name + "_paged_decomp", "batch": B,
           "kv_block_size": block_size}
    runs = [
        ("paged_full", build_decode_loop(cfg, B, kv_write=kv_write,
                                         kv_attend=kv_attend)),
        ("paged_write_only", build_decode_loop(
            cfg, B, kv_write=kv_write,
            kv_attend=lambda q1, kc, vc, lens: q1)),
    ]
    for nm, fn in runs:
        kcs, vcs = pools()
        val = slope_ms(fn, (p, kcs, vcs, tok0, pos0, lo),
                       (p, kcs, vcs, tok0, pos0, hi), 64)
        out[nm + "_ms"] = round(val, 3)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    from paddle_tpu.serving.compile_cache import enable_compile_cache

    enable_compile_cache()   # the one decision where the cache lives
    for nm in (sys.argv[1:] or ["7b_int8"]):
        if nm.endswith("_paged"):
            measure_paged(nm[:-len("_paged")])
        else:
            measure(nm)
