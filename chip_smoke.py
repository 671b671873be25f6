"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, no children. Drives the two main paths through the entry points a
user would call — `serving.ContinuousBatchingEngine` at llama3-8B widths and
the Llama trainer at `bench.py`'s 1B configuration — and checks what comes out
against references written here. Every phase is fatal: a failure raises, the
process exits non-zero and the result line is never printed.

    python chip_smoke.py            # one chip: device, kernels, serve, train
    python chip_smoke.py --chips 4  # ONLY the cross-chip phase and what it
                                    # is compared with (mp=4 vs mp=1 serving,
                                    # sharded vs one-device train step)

The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
Without an accelerator the device phase exits non-zero before anything runs.

Phases are plain functions taking their sizes as arguments, so
tests/test_chip_smoke_cpu.py runs the same code at `LlamaConfig.tiny()` sizes
on the CPU's virtual devices (rehearsals 1 and 2 of the on-chip-measurement
guide, kept as tests).
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# first generated token vs the f32 reference: a disagreement is accepted only
# where the reference itself is a near-tie — its top logit leads the engine's
# choice by less than this fraction of the logits' standard deviation. bf16
# rounding through the stack moves a logit by a few hundredths of that
# deviation; a wrong rotary table or mask moves the argmax by several of them.
BF16_TIE_TOL = 0.1

# device memory kept clear of weights and pools when the serve phase picks its depth:
# the f32 reference's per-layer weight copies (~0.9 GB at 8B widths) and vocab
# chunk, the engine's activations and logits, XLA's workspace
SERVE_HEADROOM_BYTES = int(2.5 * 2**30)


def say(msg: str = "") -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def device_phase(chips: int):
    """Fail at once unless the process sees TPU devices; print what it sees
    and the spec-table row the device kind selects."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator — jax.devices()[0].platform is "
            f"{d0.platform!r}, need 'tpu'")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, jax sees "
            f"{len(devs)}")
    from paddle_tpu.analysis.device_specs import spec_for_device_kind

    spec = spec_for_device_kind(d0.device_kind)   # unknown kind raises
    say(f"device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__}")
    say(f"device: spec row {spec.name}: bf16 peak "
        f"{spec.peak_for('bfloat16') / 1e12:.0f} TFLOP/s, HBM "
        f"{spec.hbm_gbs / 1e9:.0f} GB/s, {spec.hbm_bytes / 2**30:.0f} GiB")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _peak_bytes(device=None) -> int:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# ---------------------------------------------------------------------------
# phase: kernels — Mosaic-compiled forms against f32 oracles computed on the
# same device. Each check returns None or a failure string.
# ---------------------------------------------------------------------------

def _attn_oracle(q, k, v, causal=True):
    """fp32 grouped attention oracle on-device."""
    b, s, hq, d = q.shape
    hk = k.shape[2]
    g = hq // hk
    qf = q.astype(jnp.float32).reshape(b, s, hk, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return out.reshape(b, s, hq, d)


def check_flash_fwd_bwd():
    from paddle_tpu.kernels.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, HQ, HK, D = 2, 512, 8, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.bfloat16)

    out = jax.jit(lambda a: flash_attention(a, k, v, causal=True))(q)
    ref = jax.jit(lambda a: _attn_oracle(a, k, v))(q)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    if err > 5e-2:
        return f"flash fwd max err {err:.4f} > 5e-2"

    def loss_k(a):
        return jnp.sum(flash_attention(a, k, v,
                                       causal=True).astype(jnp.float32)
                       * jnp.cos(jnp.arange(D, dtype=jnp.float32)))

    def loss_o(a):
        return jnp.sum(_attn_oracle(a, k, v)
                       * jnp.cos(jnp.arange(D, dtype=jnp.float32)))

    gk = jax.jit(jax.grad(loss_k))(q).astype(jnp.float32)
    go = jax.jit(jax.grad(loss_o))(q).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(go))) or 1.0
    gerr = float(jnp.max(jnp.abs(gk - go))) / scale
    if gerr > 8e-2:
        return f"flash bwd rel err {gerr:.4f} > 8e-2"
    return None


def check_decode_contiguous():
    from paddle_tpu.kernels.decode_attention import decode_attention

    rng = np.random.default_rng(1)
    B, H, S, D = 4, 8, 256, 128
    kc = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    lens = jnp.asarray([100, 255, 17, 200], jnp.int32)
    out = jax.jit(lambda a: decode_attention(a, kc, vc, lens))(q)

    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", qf,
                   kc.astype(jnp.float32)) / math.sqrt(D)
    valid = jnp.arange(S)[None, None, :] <= lens[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    ref = jnp.einsum("bhs,bhsd->bhd", p, vc.astype(jnp.float32))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    if err > 5e-2:
        return f"decode max err {err:.4f} > 5e-2"

    # narrow head dim (D=32): routes through the GQA grid's dot form —
    # the equal-heads broadcast fails to lower below D=128
    Dn = 32
    kcn = jnp.asarray(rng.normal(size=(B, H, 64, Dn)), jnp.bfloat16)
    vcn = jnp.asarray(rng.normal(size=(B, H, 64, Dn)), jnp.bfloat16)
    qn = jnp.asarray(rng.normal(size=(B, H, Dn)), jnp.bfloat16)
    lensn = jnp.asarray([10, 63, 1, 30], jnp.int32)
    outn = jax.jit(lambda a: decode_attention(a, kcn, vcn, lensn))(qn)
    sn = jnp.einsum("bhd,bhsd->bhs", qn.astype(jnp.float32),
                    kcn.astype(jnp.float32)) / math.sqrt(Dn)
    validn = jnp.arange(64)[None, None, :] <= lensn[:, None, None]
    pn = jax.nn.softmax(jnp.where(validn, sn, -1e30), axis=-1)
    refn = jnp.einsum("bhs,bhsd->bhd", pn, vcn.astype(jnp.float32))
    errn = float(jnp.max(jnp.abs(outn.astype(jnp.float32) - refn)))
    return f"narrow-d decode max err {errn:.4f} > 5e-2" \
        if errn > 5e-2 else None


def check_decode_paged():
    from paddle_tpu.kernels.decode_attention import paged_decode_attention

    rng = np.random.default_rng(2)
    B, H, D, BS, NBLK = 4, 8, 128, 64, 4
    max_pages = B * NBLK
    kc = jnp.asarray(rng.normal(size=(max_pages, H, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(max_pages, H, BS, D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    # striped, non-identity table: proves the page indirection
    tables = jnp.asarray([[j * B + i for j in range(NBLK)]
                          for i in range(B)], jnp.int32)
    lens = jnp.asarray([60, 255, 128, 200], jnp.int32)
    out = jax.jit(
        lambda a: paged_decode_attention(a, kc, vc, tables, lens))(q)

    # oracle: gather pages into a contiguous view, masked softmax
    kl = jnp.transpose(kc[tables], (0, 2, 1, 3, 4)).reshape(
        B, H, NBLK * BS, D).astype(jnp.float32)
    vl = jnp.transpose(vc[tables], (0, 2, 1, 3, 4)).reshape(
        B, H, NBLK * BS, D).astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   kl) / math.sqrt(D)
    valid = jnp.arange(NBLK * BS)[None, None, :] <= lens[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    ref = jnp.einsum("bhs,bhsd->bhd", p, vl)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    return f"paged decode max err {err:.4f} > 5e-2" if err > 5e-2 else None


def check_decode_paged_gqa():
    """Grouped-heads paged decode on silicon — the grid real GQA serving
    configs take."""
    from paddle_tpu.kernels.decode_attention import paged_decode_attention

    rng = np.random.default_rng(6)
    B, HQ, HK, D, BS, NBLK = 4, 16, 4, 128, 64, 4
    max_pages = B * NBLK
    kc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.bfloat16)
    tables = jnp.asarray([[j * B + i for j in range(NBLK)]
                          for i in range(B)], jnp.int32)
    lens = jnp.asarray([60, 255, 128, 200], jnp.int32)
    out = jax.jit(
        lambda a: paged_decode_attention(a, kc, vc, tables, lens))(q)

    g = HQ // HK
    kl = jnp.transpose(kc[tables], (0, 2, 1, 3, 4)).reshape(
        B, HK, NBLK * BS, D).astype(jnp.float32)
    vl = jnp.transpose(vc[tables], (0, 2, 1, 3, 4)).reshape(
        B, HK, NBLK * BS, D).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(B, HK, g, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kl) / math.sqrt(D)
    valid = jnp.arange(NBLK * BS)[None, None, None, :] <= \
        lens[:, None, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    ref = jnp.einsum("bkgs,bksd->bkgd", p, vl).reshape(B, HQ, D)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    return (f"paged GQA decode max err {err:.4f} > 5e-2"
            if err > 5e-2 else None)


def check_prefix_prefill():
    """Ragged paged prefix-prefill on silicon (ISSUE 4): suffix queries
    over a scattered 4-page cached prefix + causal suffix, ragged
    per-row prefix AND suffix lengths, GQA 16:4 — against the gathered
    masked-softmax oracle the jnp fallback path uses."""
    from paddle_tpu.kernels.prefix_prefill import prefix_prefill_attention

    rng = np.random.default_rng(7)
    B, SB, HQ, HK, D, BS, W = 2, 128, 16, 4, 128, 64, 4
    max_pages = B * W + 1
    q = jnp.asarray(rng.normal(size=(B, SB, HQ, D)), jnp.bfloat16)
    ks = jnp.asarray(rng.normal(size=(B, SB, HK, D)), jnp.bfloat16)
    vs = jnp.asarray(rng.normal(size=(B, SB, HK, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                     jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                     jnp.bfloat16)
    tables = jnp.asarray([[j * B + i + 1 for j in range(W)]
                          for i in range(B)], jnp.int32)
    plens = jnp.asarray([4 * BS, 1 * BS], jnp.int32)   # ragged depths
    slens = jnp.asarray([SB, 70], jnp.int32)           # pad q rows row 1
    out = jax.jit(lambda a: prefix_prefill_attention(
        a, ks, vs, kc, vc, tables, plens, slens))(q)
    if not bool(jnp.isfinite(out.astype(jnp.float32)).all()):
        return "prefix prefill emitted non-finite values"

    # the shared masked-softmax oracle, compiled on the same device
    from paddle_tpu.kernels.prefix_prefill import prefix_prefill_reference

    ref = jax.jit(lambda a: prefix_prefill_reference(
        a, ks, vs, kc, vc, tables, plens))(q)
    err = 0.0
    for row, sl in enumerate([SB, 70]):
        err = max(err, float(jnp.max(jnp.abs(
            out[row, :sl].astype(jnp.float32) - ref[row, :sl]))))
    return (f"prefix prefill max err {err:.4f} > 5e-2"
            if err > 5e-2 else None)


def check_ragged_step():
    """Unified ragged paged attention on silicon (ISSUE 14): decode
    rows (new_len=1), a cold prefill row, and a chunked row whose
    cached length ends MID-PAGE coexist in ONE grid at the serving GQA
    ratio — against the gathered masked-softmax oracle. Runs the bf16 pools; the int8 variant
    rides check_kv_quant's scale plumbing, so here the bf16 grid is
    the contract."""
    from paddle_tpu.kernels.ragged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference)

    rng = np.random.default_rng(9)
    B, TN, HQ, HK, D, BS, W = 4, 128, 16, 4, 128, 64, 4
    max_pages = B * W + 1
    q = jnp.asarray(rng.normal(size=(B, TN, HQ, D)), jnp.bfloat16)
    kn = jnp.asarray(rng.normal(size=(B, TN, HK, D)), jnp.bfloat16)
    vn = jnp.asarray(rng.normal(size=(B, TN, HK, D)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                     jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)),
                     jnp.bfloat16)
    tables = jnp.asarray([[j * B + i + 1 for j in range(W)]
                          for i in range(B)], jnp.int32)
    # decode row / decode row mid-page / cold prefill / chunked partial
    clens = jnp.asarray([4 * BS, 2 * BS + 17, 0, BS + 5], jnp.int32)
    nlens = jnp.asarray([1, 1, TN, 70], jnp.int32)
    out = jax.jit(lambda a: ragged_paged_attention(
        a, kn, vn, kc, vc, tables, clens, nlens))(q)
    if not bool(jnp.isfinite(out.astype(jnp.float32)).all()):
        return "ragged step emitted non-finite values"
    ref = jax.jit(lambda a: ragged_paged_attention_reference(
        a, kn, vn, kc, vc, tables, clens, nlens))(q)
    err = 0.0
    for row, nl in enumerate([1, 1, TN, 70]):
        err = max(err, float(jnp.max(jnp.abs(
            out[row, :nl].astype(jnp.float32) - ref[row, :nl]))))
    if err > 5e-2:
        return f"ragged step max err {err:.4f} > 5e-2"
    # pad rows beyond new_lens must be exact zeros on chip too
    for row, nl in enumerate([1, 1, TN, 70]):
        if nl < TN and float(jnp.max(jnp.abs(
                out[row, nl:].astype(jnp.float32)))) != 0.0:
            return f"ragged step row {row} pad positions not zero"
    return None


def check_kv_quant():
    """int8 paged KV cache on silicon (ISSUE 5): the dequantize-in-kernel
    paged GQA decode, prefix-prefill and ragged-step paths against (a) the same math
    over explicitly dequantized pools (kernel-roundoff tight) and (b)
    the original bf16 pools (absmax-quantization tolerance) — so a
    Mosaic lowering bug in the scale plumbing can't hide inside the
    quant tolerance."""
    from paddle_tpu.kernels.decode_attention import paged_decode_attention
    from paddle_tpu.kernels.prefix_prefill import (
        prefix_prefill_attention, prefix_prefill_reference)
    from paddle_tpu.models import quantize_kv_pages

    rng = np.random.default_rng(8)
    B, HQ, HK, D, BS, NBLK = 4, 16, 4, 128, 64, 4
    max_pages = B * NBLK + 1
    kc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.normal(size=(max_pages, HK, BS, D)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.bfloat16)
    tables = jnp.asarray([[j * B + i + 1 for j in range(NBLK)]
                          for i in range(B)], jnp.int32)
    lens = jnp.asarray([60, 255, 128, 200], jnp.int32)
    kq, ks = quantize_kv_pages(kc)
    vq, vs = quantize_kv_pages(vc)
    out = jax.jit(lambda a: paged_decode_attention(
        a, kq, vq, tables, lens, k_scale=ks, v_scale=vs))(q)

    g = HQ // HK
    kd = kq.astype(jnp.float32) * ks[:, :, None, None]
    vd = vq.astype(jnp.float32) * vs[:, :, None, None]

    def oracle(kl_src, vl_src):
        kl = jnp.transpose(kl_src[tables], (0, 2, 1, 3, 4)).reshape(
            B, HK, NBLK * BS, D).astype(jnp.float32)
        vl = jnp.transpose(vl_src[tables], (0, 2, 1, 3, 4)).reshape(
            B, HK, NBLK * BS, D).astype(jnp.float32)
        qg = q.astype(jnp.float32).reshape(B, HK, g, D)
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kl) / math.sqrt(D)
        valid = jnp.arange(NBLK * BS)[None, None, None, :] <= \
            lens[:, None, None, None]
        p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
        return jnp.einsum("bkgs,bksd->bkgd", p, vl).reshape(B, HQ, D)

    ref_dq = jax.jit(lambda: oracle(kd, vd))()
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_dq)))
    if err > 5e-2:
        return f"int8 paged decode vs dequant oracle err {err:.4f} > 5e-2"
    ref = jax.jit(lambda: oracle(kc, vc))()
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    if err > 1e-1:
        return f"int8 paged decode quant err {err:.4f} > 1e-1"

    # prefix prefill: int8 kernel vs the int8-aware reference
    SB, W = 128, 4
    qs = jnp.asarray(rng.normal(size=(B, SB, HQ, D)), jnp.bfloat16)
    ksuf = jnp.asarray(rng.normal(size=(B, SB, HK, D)), jnp.bfloat16)
    vsuf = jnp.asarray(rng.normal(size=(B, SB, HK, D)), jnp.bfloat16)
    ptbl = jnp.asarray([[j * B + i + 1 for j in range(W)]
                        for i in range(B)], jnp.int32)
    plens = jnp.asarray([4 * BS, 1 * BS, 0, 2 * BS], jnp.int32)
    slens = jnp.asarray([SB, 70, 40, SB], jnp.int32)
    outp = jax.jit(lambda a: prefix_prefill_attention(
        a, ksuf, vsuf, kq, vq, ptbl, plens, slens,
        k_scale=ks, v_scale=vs))(qs)
    if not bool(jnp.isfinite(outp.astype(jnp.float32)).all()):
        return "int8 prefix prefill emitted non-finite values"
    refp = jax.jit(lambda a: prefix_prefill_reference(
        a, ksuf, vsuf, kq, vq, ptbl, plens,
        k_scale=ks, v_scale=vs))(qs)
    err = 0.0
    for row, sl in enumerate([SB, 70, 40, SB]):
        err = max(err, float(jnp.max(jnp.abs(
            outp[row, :sl].astype(jnp.float32) - refp[row, :sl]))))
    if err > 5e-2:
        return f"int8 prefix prefill max err {err:.4f} > 5e-2"

    # unified ragged step over the same int8 pools (the engine's default
    # step when kv_cache_dtype=int8): kernel vs the int8-aware reference
    from paddle_tpu.kernels.ragged_attention import (
        ragged_paged_attention, ragged_paged_attention_reference)

    clens = jnp.asarray([4 * BS, 2 * BS + 17, 0, BS + 5], jnp.int32)
    nlens = jnp.asarray([1, 1, SB, 70], jnp.int32)
    outr = jax.jit(lambda a: ragged_paged_attention(
        a, ksuf, vsuf, kq, vq, ptbl, clens, nlens,
        k_scale=ks, v_scale=vs))(qs)
    refr = jax.jit(lambda a: ragged_paged_attention_reference(
        a, ksuf, vsuf, kq, vq, ptbl, clens, nlens,
        k_scale=ks, v_scale=vs))(qs)
    err = 0.0
    for row, nl in enumerate([1, 1, SB, 70]):
        err = max(err, float(jnp.max(jnp.abs(
            outr[row, :nl].astype(jnp.float32) - refr[row, :nl]))))
    return (f"int8 ragged step max err {err:.4f} > 5e-2"
            if err > 5e-2 else None)


def check_int4_matmul():
    from paddle_tpu.kernels.int4_matmul import _xla_fallback, int4_matmul

    rng = np.random.default_rng(3)
    M, K, N = 4, 2048, 2048
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    w = jnp.asarray(rng.integers(-128, 128, (N, K // 2)), jnp.int8)
    sc = jnp.asarray(np.abs(rng.normal(size=(N,))) * 0.01, jnp.float32)
    out = jax.jit(lambda a: int4_matmul(a, w, sc))(x).astype(jnp.float32)
    ref = jax.jit(lambda a: _xla_fallback(
        a.astype(jnp.float32), w, sc))(x).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(ref))) or 1.0
    err = float(jnp.max(jnp.abs(out - ref))) / scale
    return f"int4 matmul rel err {err:.4f} > 3e-2" if err > 3e-2 else None


def check_rms_norm():
    from paddle_tpu.kernels.rms_norm import rms_norm

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(4, 128, 2048)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(2048,)) * 0.1 + 1.0, jnp.bfloat16)
    out = jax.jit(lambda a: rms_norm(a, w, 1e-6))(x).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    ref = xf * jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6) \
        * w.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out - ref)))
    return f"rms_norm max err {err:.4f} > 3e-2" if err > 3e-2 else None


def check_jit_generate():
    """One bucketed jit_generate on chip: deterministic, and the paged
    path agrees with the contiguous path on silicon."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(7)
    cfg = LlamaConfig.tiny(dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    x = paddle.to_tensor(
        np.random.default_rng(5).integers(1, cfg.vocab_size, (2, 9)))
    a = model.jit_generate(x, max_new_tokens=6).numpy()
    b = model.jit_generate(x, max_new_tokens=6).numpy()
    if not (a == b).all():
        return "jit_generate not deterministic across calls"
    c = model.jit_generate(x, max_new_tokens=6, cache_layout="paged",
                           kv_block_size=8).numpy()
    agree = (a == c).mean()
    if agree < 0.9:
        return f"paged vs contiguous agreement {agree:.2f} < 0.9 on chip"
    return None


# default path: a failure or a compiler error here fails the run
DEFAULT_CHECKS = [
    ("flash_fwd_bwd", check_flash_fwd_bwd),
    ("decode_contiguous", check_decode_contiguous),
    ("decode_paged", check_decode_paged),
    ("decode_paged_gqa", check_decode_paged_gqa),
    ("prefix_prefill", check_prefix_prefill),
    ("ragged_step", check_ragged_step),
    ("rms_norm", check_rms_norm),
    ("jit_generate", check_jit_generate),
]
# non-default mechanisms: a compiler refusal is printed, not hidden, and does
# not by itself fail the run; wrong numbers do
OPTIONAL_CHECKS = [
    ("kv_quant (int8 KV)", check_kv_quant),
    ("int4_matmul", check_int4_matmul),
]


def run_kernel_checks():
    """Run every default-path check; returns the list of numerics failures
    (empty = green). A check that cannot run raises — nothing is caught.
    bench.py gates on this."""
    failures = []
    for name, fn in DEFAULT_CHECKS:
        msg = fn()
        if msg:
            failures.append(f"{name}: {msg}")
        say(f"kernels: {name}: {'FAIL — ' + msg if msg else 'pass'}")
    return failures


def kernel_phase():
    failures = run_kernel_checks()
    for name, fn in OPTIONAL_CHECKS:
        try:
            msg = fn()
        except Exception as e:  # reported on its own line, see above
            first = (str(e).strip().splitlines() or [""])[0]
            say(f"kernels: {name}: refused: {type(e).__name__}: {first}")
            continue
        if msg:
            failures.append(f"{name}: {msg}")
        say(f"kernels: {name}: {'FAIL — ' + msg if msg else 'pass'}")
    if failures:
        raise SystemExit("chip_smoke: kernel numerics failed:\n  "
                         + "\n  ".join(failures))


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.dot(x, w.astype(jnp.float32), precision=_HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("nh", "nkv", "dh", "eps",
                                             "theta"))
def _reference_layer(h, w, *, nh, nkv, dh, eps, theta):
    """One decoder layer of the reference, [S, H] f32 in and out: rms,
    rotary (rotate-half), causal GQA softmax attention, SwiGLU."""
    s = h.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    x = _rms(h, w["input_layernorm.weight"], eps)
    q = rope(_mm(x, w["self_attn.q_proj.weight"]).reshape(s, nh, dh))
    k = rope(_mm(x, w["self_attn.k_proj.weight"]).reshape(s, nkv, dh))
    v = _mm(x, w["self_attn.v_proj.weight"]).reshape(s, nkv, dh)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", pr, v, precision=_HI)
    h = h + _mm(a.reshape(s, nh * dh), w["self_attn.o_proj.weight"])
    x = _rms(h, w["post_attention_layernorm.weight"], eps)
    gate = _mm(x, w["mlp.gate_proj.weight"])
    return h + _mm(jax.nn.silu(gate) * _mm(x, w["mlp.up_proj.weight"]),
                   w["mlp.down_proj.weight"])


def reference_last_logits(cfg, p, ids, n_real: int):
    """Plain f32 jax.numpy Llama forward of the weights in `p` over the
    (right-padded) token ids; returns the logits at position n_real - 1.
    Independent of paddle_tpu's model code: embedding, `_reference_layer`
    per layer, final rms, head — true f32 matmuls (precision HIGHEST),
    weights upcast one layer at a time."""
    geom = dict(nh=cfg.num_attention_heads, nkv=cfg.num_key_value_heads,
                dh=cfg.head_dim, eps=cfg.rms_norm_eps, theta=cfg.rope_theta)
    emb = p["llama.embed_tokens.weight"]
    h = emb[jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        h = _reference_layer(h, {k[len(pre):]: v for k, v in p.items()
                                 if k.startswith(pre)}, **geom)
    h = _rms(h[n_real - 1][None], p["llama.norm.weight"], geom["eps"])
    head = p["lm_head.weight"] if "lm_head.weight" in p else emb.T
    # the head in vocab chunks: the whole [H, V] matrix in f32 would be a
    # 2 GiB transient at the 128k vocab
    step = -(-head.shape[1] // 8)
    return jnp.concatenate([_mm(h, head[:, a:a + step])[0]
                            for a in range(0, head.shape[1], step)])


def serve_depth(cfg, bytes_limit: int, pool_tokens: int) -> int:
    """Deepest stack of `cfg`'s layers whose bf16 weights and KV pool pages
    (`pool_tokens` cached tokens per layer), next to the embedding and head,
    leave SERVE_HEADROOM_BYTES of `bytes_limit` free — depth is cut only as
    far as the device's memory forces."""
    h, im = cfg.hidden_size, cfg.intermediate_size
    qkvo = h * cfg.head_dim * 2 * (cfg.num_attention_heads
                                   + cfg.num_key_value_heads)
    pool = 2 * pool_tokens * cfg.num_key_value_heads * cfg.head_dim
    per_layer = 2 * (qkvo + 3 * h * im + 2 * h + pool)
    fixed = 2 * cfg.vocab_size * h * (1 if cfg.tie_word_embeddings else 2)
    fit = (bytes_limit - SERVE_HEADROOM_BYTES - fixed) // per_layer
    return int(max(1, min(cfg.num_hidden_layers, fit)))


def make_prompts(vocab: int, lens, shared: int, seed: int):
    """One random prompt per length; the LAST shares its first `shared`
    tokens with the FIRST, so by the time it is admitted the first has
    registered those blocks and the prefix cache serves them."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, (n,)).tolist() for n in lens]
    prompts[-1][:shared] = prompts[0][:shared]
    return prompts


def serve_requests(cfg, p, prompts, shared: int, *, max_prompt_len: int,
                   max_new: int, **engine_kw):
    """Build an engine over `p` (flags at their defaults unless engine_kw
    says otherwise), warm it for these prompts, serve them. Returns
    (engine, per-request token lists in request order, timings)."""
    from paddle_tpu.serving import ContinuousBatchingEngine

    t0 = time.perf_counter()
    eng = ContinuousBatchingEngine(cfg, p, max_prompt_len=max_prompt_len,
                                   max_new_tokens=max_new, **engine_kw)
    # the split path compiles one program per (suffix bucket, batch, prefix
    # rung): name the buckets these prompts — and the last one's suffix
    # behind its cached prefix blocks — land in. The unified path has one
    # program and ignores them.
    pb, bs = eng.prompt_bucket, eng.block_size
    suffix = len(prompts[-1]) - shared // bs * bs
    buckets = sorted({-(-n // pb) * pb
                      for n in [len(q) for q in prompts] + [suffix]})
    eng.warm(buckets)
    t_warm = time.perf_counter() - t0
    before = eng.compile_stats()
    t0 = time.perf_counter()
    reqs = [eng.add_request(q, max_new=max_new) for q in prompts]
    eng.run()
    t_run = time.perf_counter() - t0
    after = eng.compile_stats()
    bad = [r.req_id for r in reqs
           if r.failed or not r.done or len(r.tokens) != max_new]
    if bad:
        raise SystemExit(f"chip_smoke: requests {bad} did not finish with "
                         f"their {max_new} tokens")
    grown = {k: (before.get(k, 0), v) for k, v in after.items()
             if v != before.get(k, 0)}
    if grown:
        raise SystemExit(f"chip_smoke: programs compiled after warm(): "
                         f"{grown}")
    return eng, [list(r.tokens) for r in reqs], {
        "build_warm_s": t_warm, "run_s": t_run}


def serve_phase(cfg, prompts, shared: int, *, seed: int,
                max_prompt_len: int, max_new: int, **engine_kw):
    """Serve `prompts` through a default-flag engine over a random-weight
    `cfg` model and hold every first token to the f32 reference."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    t0 = time.perf_counter()
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    p = dict(model.raw_state())
    jax.block_until_ready(p)
    n_params = sum(int(np.prod(v.shape)) for v in p.values())
    say(f"serve: {cfg.num_hidden_layers} layers at hidden "
        f"{cfg.hidden_size}, ffn {cfg.intermediate_size}, "
        f"{cfg.num_attention_heads}q/{cfg.num_key_value_heads}kv heads, "
        f"dh {cfg.head_dim}, vocab {cfg.vocab_size}, {cfg.dtype}: "
        f"{n_params / 1e9:.2f}B params from seed {seed}, init "
        f"{time.perf_counter() - t0:.1f}s")

    eng, tokens, tm = serve_requests(cfg, p, prompts, shared,
                                     max_prompt_len=max_prompt_len,
                                     max_new=max_new, **engine_kw)
    m = eng.metrics()
    wc = m["warm_compile_stats"]
    say(f"serve: {len(prompts)} requests, prompt lengths "
        f"{[len(q) for q in prompts]}, max_new {max_new}: all finished "
        f"with their full token count")
    say(f"serve: build+warm (compile) {tm['build_warm_s']:.1f}s, run "
        f"{tm['run_s']:.2f}s; compiles after warm(): 0")
    say(f"serve: step={'unified' if m['unified_step'] else 'split'} "
        f"token_budget={m['token_budget']} "
        f"kv_dtype={m['kv_cache_dtype']} mp={m['serving_mp']} "
        f"prefix_hit_tokens={m['prefix_hit_tokens']}/{m['prompt_tokens']} "
        f"prefill_chunks={m['prefill_chunks']} "
        f"prefill_calls={m['prefill_calls']} "
        f"device_steps={m['device_steps']}")
    say(f"serve: compile cache dir={wc['persistent_cache_dir']} "
        f"requests={wc['compile_requests']} hits={wc['cache_hits']} "
        f"misses={wc['cache_misses']}")
    if shared and not m["prefix_hit_tokens"]:
        raise SystemExit("chip_smoke: the shared prefix was not served "
                         "from the prefix cache")

    # first generated token of every request vs the f32 reference
    pb = eng.prompt_bucket
    worst = 0.0
    for i, (q, toks) in enumerate(zip(prompts, tokens)):
        padded = q + [0] * (-len(q) % pb)
        ref = np.asarray(reference_last_logits(cfg, p, padded, len(q)))
        if not np.isfinite(ref).all():
            raise SystemExit(f"chip_smoke: reference logits of request {i} "
                             "are not finite")
        top = int(ref.argmax())
        gap = float(ref[top] - ref[toks[0]]) / float(ref.std())
        worst = max(worst, gap)
        top2 = float(np.partition(ref, -2)[-2])
        say(f"serve: request {i} (prompt {len(q)}): first token {toks[0]}, "
            f"reference argmax {top}, reference top-two gap "
            f"{(float(ref[top]) - top2) / float(ref.std()):.3f} std"
            + ("" if toks[0] == top else
               f" — engine's choice trails by {gap:.3f} std"))
        if gap >= BF16_TIE_TOL:
            raise SystemExit(
                f"chip_smoke: request {i}: first token {toks[0]} trails the "
                f"f32 reference's argmax {top} by {gap:.3f} of the logits' "
                f"std (bf16 tie tolerance {BF16_TIE_TOL})")
    say(f"serve: first tokens agree with the f32 reference (worst "
        f"disagreement {worst:.3f} std, tolerance {BF16_TIE_TOL}); "
        f"peak_bytes_in_use {_peak_bytes() / 2**30:.2f} GiB")
    return eng, tokens


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def _batch(cfg, batch: int, seq: int, seed: int):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq))),
            jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq))))


def train_phase(cfg, batch: int, seq: int, *, steps: int, seed: int):
    """`steps` real AdamW steps of bench.py's trainer on one repeated batch,
    each ended by block_until_ready; finite, falling loss. Also settles
    whether block_until_ready is a true barrier here: after it returns, a
    device_get of the loss must cost next to nothing."""
    from bench import build_train_step
    from paddle_tpu.serving import compile_cache

    snap = compile_cache.snapshot()
    step, params, opt, model = build_train_step(cfg, None, seed)
    x, y = _batch(cfg, batch, seq, seed)
    losses, secs, gets = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, params, opt = step(params, opt, x, y)
        jax.block_until_ready((loss, params, opt))
        t1 = time.perf_counter()
        losses.append(float(loss))
        gets.append(time.perf_counter() - t1)
        secs.append(t1 - t0)
    cc = compile_cache.stats_since(snap)
    steady = sorted(secs[1:])[len(secs[1:]) // 2]
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    say(f"train: {cfg.num_hidden_layers} layers at hidden {cfg.hidden_size}, "
        f"{n_params / 1e9:.2f}B params, batch {batch} x seq {seq}, AdamW: "
        f"losses {[round(v, 4) for v in losses]}")
    say(f"train: first step (compile + run) {secs[0]:.1f}s, compile "
        f"~{secs[0] - steady:.1f}s, later steps "
        f"{[round(v, 4) for v in secs[1:]]} s; compile cache "
        f"requests={cc['compile_requests']} hits={cc['cache_hits']} "
        f"misses={cc['cache_misses']}; peak_bytes_in_use (process so far) "
        f"{_peak_bytes() / 2**30:.2f} GiB")
    barrier = max(gets[1:]) < 0.1 * steady
    say(f"train: block_until_ready waited {steady:.4f}s per step; "
        f"device_get of the loss after it took {max(gets[1:]):.5f}s at "
        f"most — block_until_ready "
        f"{'IS' if barrier else 'is NOT'} a true barrier here")
    if not all(np.isfinite(losses)):
        raise SystemExit(f"chip_smoke: non-finite train loss {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"chip_smoke: train loss did not fall: {losses}")
    return losses


# ---------------------------------------------------------------------------
# phase: four chips (runs alone, behind --chips 4)
# ---------------------------------------------------------------------------

def _per_device_bytes(arrays, devices):
    """Bytes of `arrays`' shards resident on each of `devices`."""
    held = {d: 0 for d in devices}
    for a in jax.tree.leaves(arrays):
        for sh in a.addressable_shards:
            held[sh.device] = held.get(sh.device, 0) + sh.data.nbytes
    return [held[d] for d in devices]


def _assert_quartered(what: str, arrays, devices, total_bytes: int):
    per = _per_device_bytes(arrays, devices)
    share = [b / total_bytes for b in per]
    say(f"chips: {what}: {total_bytes / 2**20:.1f} MiB in all, per device "
        f"{[round(b / 2**20, 1) for b in per]} MiB "
        f"(shares {[round(v, 3) for v in share]})")
    n = len(devices)
    if not all(abs(v - 1.0 / n) < 0.1 / n for v in share):
        raise SystemExit(f"chip_smoke: {what} is not spread 1/{n} per "
                         f"device: shares {share}")


def multichip_serve(cfg, prompts, shared: int, *, seed: int,
                    max_prompt_len: int, max_new: int, n: int, **engine_kw):
    """The engine at serving_mp=n against serving_mp=1 on the same
    requests: token-identical (or parting only where the f32 reference
    calls a near-tie), pools and the sharded projections spread 1/n per
    device."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(seed)
    p = dict(LlamaForCausalLM(cfg).raw_state())
    devices = jax.devices()[:n]
    kw = dict(max_prompt_len=max_prompt_len, max_new=max_new, **engine_kw)
    eng1, tok1, tm1 = serve_requests(cfg, p, prompts, shared,
                                      serving_mp=1, **kw)
    say(f"chips: serve mp=1: build+warm {tm1['build_warm_s']:.1f}s, run "
        f"{tm1['run_s']:.2f}s")
    del eng1
    gc.collect()
    engn, tokn, tmn = serve_requests(cfg, p, prompts, shared,
                                      serving_mp=n, **kw)
    say(f"chips: serve mp={n}: build+warm {tmn['build_warm_s']:.1f}s, run "
        f"{tmn['run_s']:.2f}s, step="
        f"{'unified' if engn.unified else 'split'}")
    diff = [i for i, (a, b) in enumerate(zip(tok1, tokn)) if a != b]
    for i in diff:
        # after a first differing token the two continuations are different
        # texts; the only question is whether that first difference is a
        # coin the f32 reference also calls a near-tie
        j = next(k for k, (a, b) in enumerate(zip(tok1[i], tokn[i]))
                 if a != b)
        ids = prompts[i] + tok1[i][:j]
        ref = np.asarray(reference_last_logits(cfg, p, ids, len(ids)))
        gaps = [float(ref.max() - ref[t]) / float(ref.std())
                for t in (tok1[i][j], tokn[i][j])]
        say(f"chips: request {i} diverges at generated token {j}: mp=1 chose "
            f"{tok1[i][j]}, mp={n} chose {tokn[i][j]}; they trail the f32 "
            f"reference's argmax by {gaps[0]:.3f} / {gaps[1]:.3f} std")
        if max(gaps) >= BF16_TIE_TOL:
            raise SystemExit(
                f"chip_smoke: mp={n} tokens differ from mp=1 for request "
                f"{i} away from a reference near-tie")
    say(f"chips: serve mp={n} vs mp=1, {len(prompts)} requests x {max_new} "
        f"tokens: " + ("token-identical" if not diff else
                       f"{len(prompts) - len(diff)} identical, {len(diff)} "
                       f"diverge at a reference near-tie (tolerance "
                       f"{BF16_TIE_TOL} std)"))
    pools = [engn.kcs, engn.vcs]
    _assert_quartered("KV pools", pools, devices,
                      sum(a.nbytes for a in jax.tree.leaves(pools)))
    qkv = {k: v for k, v in engn.p.items()
           if k.endswith(("q_proj.weight", "k_proj.weight",
                          "v_proj.weight"))}
    _assert_quartered("q/k/v projections", qkv, devices,
                      sum(a.nbytes for a in qkv.values()))
    # by design (models/llama._tp_weight_spec) serving_mp shards attention
    # only: o-proj, the MLP, embedding and head are replicated on every chip
    rest = {k: v for k, v in engn.p.items() if k not in qkv}
    per = _per_device_bytes(rest, devices)
    say(f"chips: replicated by design (o-proj, MLP, embedding, head): "
        f"{[round(b / 2**20, 1) for b in per]} MiB per device")
    for d in devices:
        st = d.memory_stats() or {}
        say(f"chips: device {d.id}: bytes_in_use "
            f"{st.get('bytes_in_use', 0) / 2**30:.2f} GiB")
    del engn
    gc.collect()


def multichip_train(cfg, batch: int, seq: int, *, seed: int, n: int,
                    rel_tol: float = 2e-2):
    """One train step on a {"sharding": n} mesh against the one-device
    loss; parameters and optimizer state spread 1/n per device."""
    from bench import build_train_step
    from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh

    x, y = _batch(cfg, batch, seq, seed)
    step, params, opt, model = build_train_step(cfg, None, seed)
    loss1, params, opt = step(params, opt, x, y)
    loss1 = float(loss1)
    del step, params, opt, model
    gc.collect()
    devices = jax.devices()[:n]
    mesh = build_mesh({"dp": 1, "sharding": n, "mp": 1, "sep": 1},
                      devices=devices)
    set_global_mesh(mesh)
    try:
        step, params, opt, model = build_train_step(cfg, mesh, seed)
        total = sum(a.nbytes for a in jax.tree.leaves(params))
        _assert_quartered("train parameters", params, devices, total)
        lossn, params, opt = step(params, opt, x, y)
        lossn = float(lossn)
        _assert_quartered("train parameters after the step", params,
                          devices, total)
        _assert_quartered("optimizer state", opt, devices,
                          sum(a.nbytes for a in jax.tree.leaves(opt)))
    finally:
        set_global_mesh(None)
    rel = abs(lossn - loss1) / abs(loss1)
    say(f"chips: train step loss one device {loss1:.5f}, sharding={n} "
        f"{lossn:.5f} (rel diff {rel:.2e}, tolerance {rel_tol})")
    if not (np.isfinite(lossn) and rel < rel_tol):
        raise SystemExit("chip_smoke: sharded train step loss does not "
                         "match the one-device loss")


# ---------------------------------------------------------------------------

# prompts: three length buckets between ~100 and ~900 tokens; the last
# request shares SHARED_PREFIX tokens with the first
PROMPT_LENS = (420, 104, 131, 396, 451, 868, 897, 389)
SHARED_PREFIX = 320
MAX_PROMPT_LEN = 1024
MAX_NEW = 32
SLOTS = 8                 # the engine's default
MULTICHIP_DEPTH = 8


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run ONLY the cross-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    device = device_phase(args.chips)

    from bench import train_config
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.serving import compile_cache

    say(f"cache: {compile_cache.enable_compile_cache()}")
    wide = dict(dtype="bfloat16", max_position_embeddings=2048)
    tcfg, batch, seq = train_config()
    if args.chips == 4:
        cfg = LlamaConfig.llama3_8b(num_hidden_layers=MULTICHIP_DEPTH, **wide)
        prompts = make_prompts(cfg.vocab_size, PROMPT_LENS, SHARED_PREFIX,
                               args.seed)
        multichip_serve(cfg, prompts, SHARED_PREFIX, seed=args.seed,
                        max_prompt_len=MAX_PROMPT_LEN, max_new=MAX_NEW, n=4)
        multichip_train(tcfg, batch, seq, seed=args.seed, n=4)
    else:
        kernel_phase()
        limit = int((jax.devices()[0].memory_stats() or {})["bytes_limit"])
        full = LlamaConfig.llama3_8b(**wide)
        # the engine's default pool: every slot full-length, whole pages
        pool_tokens = SLOTS * (-(-(MAX_PROMPT_LEN + MAX_NEW) // 64) * 64 + 64)
        depth = serve_depth(full, limit, pool_tokens)
        say(f"serve: device memory limit {limit / 2**30:.2f} GiB -> depth "
            f"{depth} of {full.num_hidden_layers} layers "
            f"({SERVE_HEADROOM_BYTES / 2**30:.1f} GiB kept clear of weights "
            f"and KV pools)")
        cfg = LlamaConfig.llama3_8b(num_hidden_layers=depth, **wide)
        prompts = make_prompts(cfg.vocab_size, PROMPT_LENS, SHARED_PREFIX,
                               args.seed)
        serve_phase(cfg, prompts, SHARED_PREFIX, seed=args.seed,
                    max_prompt_len=MAX_PROMPT_LEN, max_new=MAX_NEW)
        gc.collect()   # the engine, pools and weights go before the trainer
        train_phase(tcfg, batch, seq, steps=5, seed=args.seed)
    say(f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
