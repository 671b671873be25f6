"""Decode megakernel: the per-token serving decode step as ONE Pallas
TPU kernel — a fusion LADDER of three rungs behind one flag:

  attn  `decode_layer_megakernel`   — the attention block of one layer
                                      fused (the original rung below)
  full  `decode_layer_megakernel_full` — attention block + MLP half of
                                      one layer fused (post-attn rms,
                                      blocked gate/up/down, silu-mul,
                                      residual) — one launch per layer
  scan  `decode_layers_megakernel`  — the full-layer kernel with the
                                      LAYER as the outermost grid
                                      axis: every decoder layer in ONE
                                      launch, stacked weights streamed
                                      per layer step, the residual
                                      stream carried across layers in
                                      VMEM scratch, per-layer KV
                                      commits aliased into a stacked
                                      pool

Why (OPBENCH): `decode_attention` costs 0.21 ms but `decode_step_1b_int8`
costs 1.9 ms — the decode hot path is dominated by inter-kernel dispatch
and the HBM round-trips between tiny per-token ops (a [B, 1, H] tensor
bounces through HBM between every projection), not by attention math.
MPK (mega-kernelizing tensor programs) and the XLA operator-fusion
analysis in PAPERS.md both show this overhead class is recoverable by
fusing the layer step; this kernel is that fusion for the paged serving
decode path.

Fusion boundary (one kernel per decoder layer — the attention block):

    rms_norm -> QKV projection (dense or weight-only-int8) -> rotary
    -> paged GQA attention over the bf16/int8 pools
    -> paged-KV commit (the int8 quantize-on-scatter read-modify-write
       of ONE page per token from the q8 helpers, as an in-kernel
       epilogue with the same monotone per-(page, kv-head) scale update)
    -> o-proj + residual add

On the ATTN rung the MLP half of the layer stays with XLA: its three
[1, H] x [H, F] matmuls are weight-read-bound and XLA schedules them
well (an older record, removed in PR 22, had this for swiglu); the dispatch overhead that
rung recovers lives in the many tiny attention-block ops. The FULL and
SCAN rungs pull the MLP in too (the `_swiglu` math at M=1, weights
streamed per block), and SCAN then removes the per-layer launch
entirely — `kernels_per_step` drops from 2 + 3·n_layers (attn) to 3
(one megakernel + final norm + lm head).

Grid: (b, nkv, 2 + n_inner) with the last axis "arbitrary":

  j == 0            rms_norm (computed once per row at kv head 0, kept
                    in scratch), QKV projection for this kv head's query
                    group, rotary (cos/sin tables precomputed per row
                    outside — position-only math), q/k/v parked in VMEM
                    scratch; online-softmax scratch re-inits.
  1 <= j <= n_inner the paged attention phase: each step streams
                    `pages_per_step` (kv head, page) tiles straight from
                    the pools via the block table — the PR 4 follow-up
                    multi-page inner step — with the `_paged_gqa_kernel`
                    online-softmax recurrence, f32 accumulation, pad
                    pages masked AND pinned out of the DMA stream.
                    Positions are masked STRICTLY below `lens[b]`: the
                    current token never round-trips through the pool.
  j == n_inner + 1  the current token's k/v (still in scratch) joins the
                    softmax, the context finalizes, o-proj accumulates
                    into a per-row scratch across kv heads (residual add
                    + store at the last kv head), and the commit
                    epilogue writes the token's K/V page in place
                    (`input_output_aliases`: every pool page NOT
                    committed this step is untouched HBM).

Commit correctness: a slot's commit page is always one of its private
pages (the engine admits at least one suffix token past any cached
prefix), so distinct live rows never write the same page; retired rows
all aim at the engine's scratch page, whose content is never read
(their lens is 0, masking every streamed position).

Numerics: matches the multi-kernel path op-for-op (f32 statistics and
accumulation, bf16 rounding at the same seams), but not bitwise —
parity is asserted to tolerance in tests/test_decode_megakernel.py and
token identity is asserted end-to-end through the engine.

Wired behind the tri-state FLAGS_decode_megakernel /
PADDLE_TPU_DECODE_MEGAKERNEL = off|attn|full|scan (default OFF — the
multi-kernel path remains the oracle; legacy booleans map to
off/attn), read at program-BUILD time like the prefix-prefill flag.
Unsupported shapes step DOWN the ladder one rung at a time with a
build-time warning; see models/llama.py `resolve_decode_megakernel`
and serving/README.md.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, VMEM_BUDGET_BYTES,
                          dtype_itemsize, fit_vmem_block,
                          missing_scale_finding, register_constraint)
from .decode_attention import _on_tpu
from .rope import rope_freqs

_NEG_INF = -1e30

# maximum pages the attention phase streams per inner grid step (the
# multi-page inner step); the actual factor is the largest of
# (PAGES_PER_STEP, ..., 1) dividing the table width that fits VMEM
PAGES_PER_STEP = 4


def _check_megakernel_shapes(shapes, dtypes):
    """Checker for the megakernel pallas call. The rank-3 operand tail
    is the streamed/committed pool tiles [pages*nkv, block, dh] — the
    LAST rank-3 operand is always a pool commit ref (the dense-weight
    layout puts the reshaped [nkv, group*dh, H] o-proj weight first, so
    the head must not be read); the head-dim lane check and the
    int8-pool-without-scales check are both shape-decidable here."""
    out = []
    arr = [s for s in shapes if len(s) == 3]
    if not arr:
        return out
    d = arr[-1][-1]
    if d % LANE:
        out.append(("warning",
                    f"head_dim {d} is not a multiple of the {LANE}-lane "
                    "tile; every fused projection and streamed page tile "
                    f"pads to {-(-d // LANE) * LANE} lanes"))
    finding = missing_scale_finding(shapes, dtypes)
    if finding is not None:
        out.append(finding)
    return out


CONSTRAINT = register_constraint(KernelConstraint(
    name="decode_megakernel",
    kernel_fns=("_decode_megakernel_kernel",),
    blocks={"pages_per_step": PAGES_PER_STEP},
    note="fused per-layer decode step (rms + qkv + rope + paged "
         "attention + commit + o-proj); streams whole (kv head, page) "
         "tiles, so the table width should admit a pages_per_step "
         "divisor and head_dim should be lane-aligned",
    checker=_check_megakernel_shapes,
    source="decode_megakernel.py",
))


def _unpack_weight(w, n_out, n_in):
    """(array, scale_or_None, is_quant) for a decode weight: dense
    [n_in, n_out], or the nn.quant weight-only pair (int8 [n_out, n_in],
    per-channel scale [n_out]). Packed int4 (K//2 columns) returns
    is_quant=None — the caller must fall back to the multi-kernel
    path."""
    if isinstance(w, tuple):
        wq, sc = w
        if wq.shape != (n_out, n_in):
            return None, None, None  # packed int4 or foreign layout
        return wq, sc.reshape(1, n_out).astype(jnp.float32), True
    if w.shape != (n_in, n_out):
        return None, None, None
    return w, None, False


def megakernel_supported(h, w_in, wq, wk, wv, wo, k_cache, v_cache,
                         tables, *, k_scale=None, v_scale=None) -> str | None:
    """None when `decode_layer_megakernel` can serve these operands, a
    human-readable reason otherwise (the builders fall back to the
    multi-kernel oracle path on any reason)."""
    if h.ndim != 3 or h.shape[1] != 1:
        return f"hidden states must be [b, 1, H], got {h.shape}"
    b, _, H = h.shape
    if k_cache.ndim != 4:
        return f"paged pools required, got cache rank {k_cache.ndim}"
    max_pages, nkv, bs, dh = k_cache.shape
    if dh % 2:
        return f"head_dim {dh} is odd (rotary needs paired halves)"
    quant_kv = k_cache.dtype == jnp.int8
    if quant_kv and (k_scale is None or v_scale is None):
        return "int8 pools need k_scale/v_scale"
    qs = []
    for w, (no, ni) in ((wq, (None, H)), (wk, (nkv * dh, H)),
                        (wv, (nkv * dh, H)), (wo, (H, None))):
        if isinstance(w, tuple):
            shp = w[0].shape
        else:
            shp = w.shape[::-1]
        n_out = shp[0] if no is None else no
        n_in = shp[1] if ni is None else ni
        _, _, q = _unpack_weight(w, n_out, n_in)
        if q is None:
            return "unsupported weight layout (packed int4?)"
        qs.append(q)
    if len(set(qs)) != 1:
        return "mixed dense/quantized projection weights"
    nh = (wq[0].shape[0] if isinstance(wq, tuple) else wq.shape[1]) // dh
    if nh % nkv:
        return f"Hq {nh} not a multiple of Hkv {nkv}"
    group = nh // nkv
    # resident VMEM estimate: the four weight blocks (double-buffered
    # across kv-head transitions) + page tiles + the [1, H] rows
    itw = 1 if qs[0] else jnp.dtype(h.dtype).itemsize
    kv_it = 1 if quant_kv else jnp.dtype(k_cache.dtype).itemsize
    wbytes = H * group * dh * itw * 2 + H * dh * itw * 2  # wq+wo, wk+wv
    pbytes = 2 * PAGES_PER_STEP * bs * dh * kv_it
    if 2 * (wbytes + pbytes) > VMEM_BUDGET_BYTES:
        return (f"weight blocks ({2 * (wbytes + pbytes)} bytes "
                "double-buffered) exceed the VMEM budget")
    return None


def _fit_pages_per_step(w_tbl: int) -> int:
    """Largest factor of the table width <= PAGES_PER_STEP — the
    multi-page inner step streams this many pages per grid step."""
    mp = min(PAGES_PER_STEP, w_tbl)
    while w_tbl % mp:
        mp -= 1
    return mp


def _make_kernel(*, H, nkv, group, dh, bs, n_inner, mp, scale, eps,
                 quant_w, quant_kv, residual=True, quantize_out=False):
    """Build the fused layer-step kernel body. Refs are parsed
    positionally from the static (quant_w, quant_kv, mp) layout the
    wrapper constructs. With `residual=False` the final store emits the
    f32 o-proj PARTIAL sum only (no h add) — the tensor-parallel
    serving path psums the per-shard partials outside the kernel and
    adds the residual once, after the collective. With `quantize_out`
    (implies residual=False) the partial leaves the kernel ALREADY
    absmax-int8-quantized in the quantized-collectives wire layout
    (per-128-lane blocks, scale = absmax/127, exactly
    `parallel.collectives.quantize_blocks`), so the TP seam never
    round-trips an f32 partial through HBM before the psum."""
    dh2 = dh // 2
    f32 = jnp.float32

    def _decode_megakernel_kernel(*refs):
        tbl_ref, len_ref = refs[0], refs[1]
        h_ref, win_ref, cos_ref, sin_ref = refs[2:6]
        i = 6
        if quant_w:
            (wq_ref, wqs_ref, wk_ref, wks_ref, wv_ref, wvs_ref,
             wo_ref, wos_ref) = refs[i:i + 8]
            i += 8
        else:
            wq_ref, wk_ref, wv_ref, wo_ref = refs[i:i + 4]
            i += 4
        kp_refs = refs[i:i + mp]; i += mp
        vp_refs = refs[i:i + mp]; i += mp
        ksc_refs = vsc_refs = ()
        if quant_kv:
            ksc_refs = refs[i:i + mp]; i += mp
            vsc_refs = refs[i:i + mp]; i += mp
        kcom_ref, vcom_ref = refs[i], refs[i + 1]; i += 2
        kscom_ref = vscom_ref = None
        if quant_kv:
            kscom_ref, vscom_ref = refs[i], refs[i + 1]; i += 2
        oh_ref, ok_ref, ov_ref = refs[i:i + 3]; i += 3
        oks_ref = ovs_ref = None
        if quant_kv:
            oks_ref, ovs_ref = refs[i], refs[i + 1]; i += 2
        oqs_ref = None
        if quantize_out:
            oqs_ref = refs[i]; i += 1
        (x_scr, q_scr, k_scr, v_scr, m_scr, l_scr, acc_scr,
         out_scr) = refs[i:]

        b = pl.program_id(0)
        h_id = pl.program_id(1)
        j = pl.program_id(2)
        nj = pl.num_programs(2)
        valid_until = len_ref[b]

        @pl.when((j == 0) & (h_id == 0))
        def _row_init():
            # rms_norm once per row (f32 statistics, like _k_rms), and
            # the o-proj accumulator this row's kv heads sum into
            xr = h_ref[...].astype(f32)
            var = jnp.mean(xr * xr, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + eps)
            x_scr[...] = (xr * inv
                          * win_ref[...].astype(f32)).astype(x_scr.dtype)
            out_scr[...] = jnp.zeros_like(out_scr)

        @pl.when(j == 0)
        def _qkv():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
            x = x_scr[...]
            if quant_w:
                xf = x.astype(f32)
                qf = jax.lax.dot_general(
                    xf, wq_ref[...].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wqs_ref[...]
                kf = jax.lax.dot_general(
                    xf, wk_ref[...].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wks_ref[...]
                vf = jax.lax.dot_general(
                    xf, wv_ref[...].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wvs_ref[...]
            else:
                qf = jax.lax.dot_general(
                    x, wq_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                kf = jax.lax.dot_general(
                    x, wk_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                vf = jax.lax.dot_general(
                    x, wv_ref[...], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
            cdt = x_scr.dtype
            qv, kv_, vv = qf.astype(cdt), kf.astype(cdt), vf.astype(cdt)
            # rotary: the [b, dh] cos/sin rows are position-only tables
            # (values duplicated over the halves); application is the
            # neox rotate-half, at the multi-kernel path's dtype
            c = cos_ref[0:1, :dh2].astype(cdt)
            s = sin_ref[0:1, :dh2].astype(cdt)
            for g in range(group):
                x1 = qv[:, g * dh:g * dh + dh2]
                x2 = qv[:, g * dh + dh2:(g + 1) * dh]
                q_scr[g:g + 1, :dh2] = x1 * c - x2 * s
                q_scr[g:g + 1, dh2:] = x2 * c + x1 * s
            k1, k2 = kv_[:, :dh2], kv_[:, dh2:]
            k_scr[:, :dh2] = k1 * c - k2 * s
            k_scr[:, dh2:] = k2 * c + k1 * s
            v_scr[...] = vv

        def _accum(s, v):
            """One online-softmax step (the `_gqa_grid_body`
            recurrence) over masked scores s [group, T], values
            v [T, dh]."""
            m_prev = m_scr[...]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev[:, :1], m_cur)
            corr = jnp.exp(m_prev[:, :1] - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)
            acc_scr[...] = acc_scr[...] * corr + pv
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        # ---- attention phase: mp (kv head, page) tiles per inner step,
        # positions masked STRICTLY below lens (the current token never
        # round-trips through the pool — it joins from scratch below)
        for m in range(mp):
            col = (j - 1) * mp + m

            @pl.when((j >= 1) & (j <= n_inner)
                     & (col * bs < valid_until))
            def _page(m=m, col=col):
                q = q_scr[...].astype(f32)
                k = kp_refs[m][0].astype(f32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale
                if quant_kv:
                    s = s * ksc_refs[m][0, 0]
                pos = col * bs + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(pos < valid_until, s, _NEG_INF)
                v = vp_refs[m][0].astype(f32)
                if quant_kv:
                    v = v * vsc_refs[m][0, 0]
                _accum(s, v)

        # ---- final step: current token joins, context finalizes,
        # o-proj accumulates, commit epilogue writes the page in place
        @pl.when(j == nj - 1)
        def _final():
            q = q_scr[...].astype(f32)
            kcur = k_scr[...].astype(f32)                # [1, dh]
            s = jax.lax.dot_general(
                q, kcur, (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale      # [group, 1]
            _accum(s, v_scr[...].astype(f32))
            l = l_scr[:, :1]
            ctx = (acc_scr[...]
                   / jnp.where(l > 0.0, l, 1.0)).astype(x_scr.dtype)
            contrib = jnp.zeros((1, H), f32)
            for g in range(group):
                cg = ctx[g:g + 1, :]
                if quant_w:
                    wslice = wo_ref[:, g * dh:(g + 1) * dh]   # [H, dh]
                    contrib += jax.lax.dot_general(
                        cg.astype(f32), wslice.astype(f32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
                else:
                    wslice = wo_ref[0, g * dh:(g + 1) * dh, :]  # [dh, H]
                    contrib += jax.lax.dot_general(
                        cg, wslice, (((1,), (0,)), ((), ())),
                        preferred_element_type=f32)
            out_scr[...] += contrib

            # commit epilogue: the q8 helpers' monotone-scale
            # read-modify-write (slot 0 resets a recycled page's absmax
            # chain), or the plain bf16 slot write — whole page stored,
            # aliased in place
            slot = valid_until % bs
            rows = jax.lax.broadcasted_iota(jnp.int32, (bs, dh), 0)
            if quant_kv:
                for tok_ref, com_ref, scom_ref, o_ref, os_ref in (
                        (k_scr, kcom_ref, kscom_ref, ok_ref, oks_ref),
                        (v_scr, vcom_ref, vscom_ref, ov_ref, ovs_ref)):
                    tokf = tok_ref[...].astype(f32)          # [1, dh]
                    amax = jnp.max(jnp.abs(tokf), axis=-1,
                                   keepdims=True) / 127.0    # [1, 1]
                    old = jnp.where(slot == 0, 0.0, scom_ref[0, 0])
                    new = jnp.maximum(old, amax)
                    safe = jnp.where(new > 0.0, new, 1.0)
                    ratio = old / safe
                    pg = jnp.round(com_ref[0].astype(f32) * ratio)
                    qtok = jnp.round(tokf / safe)
                    pg = jnp.where(rows == slot,
                                   jnp.broadcast_to(qtok, (bs, dh)), pg)
                    o_ref[0] = jnp.clip(pg, -127, 127).astype(jnp.int8)
                    os_ref[...] = new
            else:
                ok_ref[0] = jnp.where(
                    rows == slot,
                    jnp.broadcast_to(k_scr[...], (bs, dh)),
                    kcom_ref[0]).astype(ok_ref.dtype)
                ov_ref[0] = jnp.where(
                    rows == slot,
                    jnp.broadcast_to(v_scr[...], (bs, dh)),
                    vcom_ref[0]).astype(ov_ref.dtype)

        @pl.when((j == nj - 1) & (h_id == nkv - 1))
        def _residual():
            proj = out_scr[...]
            if quant_w:
                proj = proj * wos_ref[...]
            if residual:
                oh_ref[...] = (h_ref[...].astype(f32)
                               + proj).astype(oh_ref.dtype)
            elif quantize_out:
                # quantized-partial output: absmax-int8 per 128-lane
                # block, the quantize_blocks wire layout op-for-op
                # (scale = absmax/127, zero block -> scale 0, round, no
                # clip) — the psum's hop-0 quantization, fused
                nb = H // LANE
                p2 = proj.reshape(nb, LANE)
                sc = jnp.max(jnp.abs(p2), axis=1,
                             keepdims=True) / 127.0
                safe = jnp.where(sc > 0.0, sc, 1.0)
                oh_ref[...] = jnp.round(p2 / safe).reshape(
                    1, H).astype(jnp.int8)
                oqs_ref[...] = sc.reshape(1, nb)
            else:
                # partial-sum output: the caller owns residual + psum
                oh_ref[...] = proj.astype(oh_ref.dtype)

    return _decode_megakernel_kernel


def decode_layer_megakernel(h, lens, tables, w_in, wq, wk, wv, wo,
                            k_cache, v_cache, *, rope_base: float = 10000.0,
                            eps: float = 1e-6, scale: float | None = None,
                            k_scale=None, v_scale=None,
                            residual: bool = True,
                            quantize_out: bool = False):
    """One decoder layer's fused decode step.

    h: [b, 1, H] residual stream; lens: [b] int32 cached token counts
    (the current token's position); tables: [b, W] block table;
    w_in: [H] rms weight; wq/wk/wv/wo: dense [K, N] arrays or
    nn.quant weight-only pairs (int8 [N, K], scale [N]) — all four must
    agree; k_cache/v_cache: [max_pages, nkv, block, dh] paged pools
    (bf16/f32, or int8 with `k_scale`/`v_scale` [max_pages, nkv]).

    Head counts derive from the OPERANDS (nkv from the pool shape, nh
    from wq, group = nh // nkv) — under tensor-parallel serving these
    are the LOCAL shard's counts, so the grid is correct for any
    head sharding the caller arranged (ISSUE 7 satellite: never the
    full-model config's nq // nkv).

    Returns (h_out [b, 1, H], k_cache', v_cache') — or, for int8 pools,
    (h_out, (k_cache', k_scale'), (v_cache', v_scale')) — with exactly
    one page per (row, kv head) rewritten (the commit) and every other
    page byte-identical (aliased in place). With ``residual=False``
    h_out is instead the f32 o-proj PARTIAL sum (no residual add) —
    the TP serving path psums partials across shards and adds the
    residual after the collective. With ``quantize_out=True`` (requires
    ``residual=False`` and lane-aligned H) the partial is emitted
    ALREADY absmax-int8-quantized per 128-lane block — h_out becomes
    the pair (q [b, H] int8, scale [b, H // 128] f32), byte-compatible
    with `parallel.collectives.quantize_blocks`, for
    `quantized_psum_prequant` to put straight on the wire.
    """
    reason = megakernel_supported(h, w_in, wq, wk, wv, wo, k_cache,
                                  v_cache, tables, k_scale=k_scale,
                                  v_scale=v_scale)
    if reason is not None:
        raise ValueError(f"decode megakernel unsupported here: {reason}")
    if quantize_out:
        if residual:
            raise ValueError("quantize_out emits a PARTIAL (the psum "
                             "payload); it requires residual=False")
        if h.shape[-1] % LANE:
            raise ValueError(
                f"quantize_out needs lane-aligned H, got {h.shape[-1]}")
    b, _, H = h.shape
    max_pages, nkv, bs, dh = k_cache.shape
    w_tbl = tables.shape[1]
    quant_kv = k_cache.dtype == jnp.int8
    nh = (wq[0].shape[0] if isinstance(wq, tuple) else wq.shape[1]) // dh
    group = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp = _fit_pages_per_step(w_tbl)
    n_inner = w_tbl // mp
    nj = n_inner + 2
    gdh = group * dh
    cdt = h.dtype

    h2d = h.reshape(b, H)
    win2 = w_in.reshape(1, H)
    # position-only rotary tables from the one shared rope_freqs,
    # duplicated over the halves so the kernel block stays lane-aligned
    # at dh (the kernel reads only [:dh/2])
    cos_h, sin_h = rope_freqs(0, dh, rope_base,
                              position_ids=lens)         # [b, dh/2] f32
    cos_t = jnp.concatenate([cos_h, cos_h], axis=-1)
    sin_t = jnp.concatenate([sin_h, sin_h], axis=-1)

    wq_a, wq_s, quant_w = _unpack_weight(wq, nh * dh, H)
    wk_a, wk_s, _ = _unpack_weight(wk, nkv * dh, H)
    wv_a, wv_s, _ = _unpack_weight(wv, nkv * dh, H)
    wo_a, wo_s, _ = _unpack_weight(wo, H, nh * dh)

    # pools collapse (page, kv head) -> one row axis, like the paged GQA
    # decode kernel: page selection is tbl[b, i]*nkv + h
    kc2 = k_cache.reshape(max_pages * nkv, bs, dh)
    vc2 = v_cache.reshape(max_pages * nkv, bs, dh)
    if quant_kv:
        ksc2 = k_scale.astype(jnp.float32).reshape(max_pages * nkv, 1)
        vsc2 = v_scale.astype(jnp.float32).reshape(max_pages * nkv, 1)

    def row_map(b_, h_, j_, tbl, lens_):
        return (b_, 0)

    def const_map(b_, h_, j_, tbl, lens_):
        return (0, 0)

    def stream_map_m(m):
        def _map(b_, h_, j_, tbl, lens_):
            # pin pad pages (and the non-attention steps) to the row's
            # last live page so skipped tiles are never DMA'd
            col = jnp.clip((j_ - 1) * mp + m, 0, w_tbl - 1)
            last = jnp.maximum((lens_[b_] - 1) // bs, 0)
            col = jnp.minimum(col, last)
            return (tbl[b_, col] * nkv + h_, 0, 0)
        return _map

    def stream_scale_map_m(m):
        def _map(b_, h_, j_, tbl, lens_):
            col = jnp.clip((j_ - 1) * mp + m, 0, w_tbl - 1)
            last = jnp.maximum((lens_[b_] - 1) // bs, 0)
            col = jnp.minimum(col, last)
            return (tbl[b_, col] * nkv + h_, 0)
        return _map

    def commit_map(b_, h_, j_, tbl, lens_):
        # the page the current token lands in (clamped like the XLA
        # gather for frozen rows whose lens sits at the budget edge)
        i = jnp.minimum(lens_[b_] // bs, w_tbl - 1)
        return (tbl[b_, i] * nkv + h_, 0, 0)

    def commit_scale_map(b_, h_, j_, tbl, lens_):
        i = jnp.minimum(lens_[b_] // bs, w_tbl - 1)
        return (tbl[b_, i] * nkv + h_, 0)

    in_specs = [
        pl.BlockSpec((1, H), row_map),          # h
        pl.BlockSpec((1, H), const_map),        # w_in
        pl.BlockSpec((1, dh), row_map),         # cos
        pl.BlockSpec((1, dh), row_map),         # sin
    ]
    operands = [h2d, win2, cos_t, sin_t]
    if quant_w:
        in_specs += [
            pl.BlockSpec((gdh, H), lambda b_, h_, j_, t, l: (h_, 0)),
            pl.BlockSpec((1, gdh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((dh, H), lambda b_, h_, j_, t, l: (h_, 0)),
            pl.BlockSpec((1, dh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((dh, H), lambda b_, h_, j_, t, l: (h_, 0)),
            pl.BlockSpec((1, dh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((H, gdh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((1, H), const_map),
        ]
        operands += [wq_a, wq_s, wk_a, wk_s, wv_a, wv_s, wo_a, wo_s]
    else:
        wo3 = wo_a.reshape(nkv, gdh, H)
        in_specs += [
            pl.BlockSpec((H, gdh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((H, dh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((H, dh), lambda b_, h_, j_, t, l: (0, h_)),
            pl.BlockSpec((1, gdh, H),
                         lambda b_, h_, j_, t, l: (h_, 0, 0)),
        ]
        operands += [wq_a, wk_a, wv_a, wo3]
    for m in range(mp):
        in_specs.append(pl.BlockSpec((1, bs, dh), stream_map_m(m)))
        operands.append(kc2)
    for m in range(mp):
        in_specs.append(pl.BlockSpec((1, bs, dh), stream_map_m(m)))
        operands.append(vc2)
    if quant_kv:
        for m in range(mp):
            in_specs.append(pl.BlockSpec((1, 1), stream_scale_map_m(m)))
            operands.append(ksc2)
        for m in range(mp):
            in_specs.append(pl.BlockSpec((1, 1), stream_scale_map_m(m)))
            operands.append(vsc2)
    # commit refs (the aliased read-modify-write operands)
    commit_base = 2 + len(operands)  # call-arg index incl. the 2 prefetch
    in_specs += [pl.BlockSpec((1, bs, dh), commit_map),
                 pl.BlockSpec((1, bs, dh), commit_map)]
    operands += [kc2, vc2]
    if quant_kv:
        in_specs += [pl.BlockSpec((1, 1), commit_scale_map),
                     pl.BlockSpec((1, 1), commit_scale_map)]
        operands += [ksc2, vsc2]

    if quantize_out:
        oh_dtype = jnp.int8
    else:
        oh_dtype = cdt if residual else jnp.float32
    out_specs = [
        pl.BlockSpec((1, H), row_map),
        pl.BlockSpec((1, bs, dh), commit_map),
        pl.BlockSpec((1, bs, dh), commit_map),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, H), oh_dtype),
        jax.ShapeDtypeStruct(kc2.shape, kc2.dtype),
        jax.ShapeDtypeStruct(vc2.shape, vc2.dtype),
    ]
    aliases = {commit_base: 1, commit_base + 1: 2}
    if quant_kv:
        out_specs += [pl.BlockSpec((1, 1), commit_scale_map),
                      pl.BlockSpec((1, 1), commit_scale_map)]
        out_shape += [jax.ShapeDtypeStruct(ksc2.shape, jnp.float32),
                      jax.ShapeDtypeStruct(vsc2.shape, jnp.float32)]
        aliases[commit_base + 2] = 3
        aliases[commit_base + 3] = 4
    if quantize_out:
        # wire-layout scales ride as one more (un-aliased) output AFTER
        # the commit outputs, so the alias indices above never move
        out_specs.append(pl.BlockSpec((1, H // LANE), row_map))
        out_shape.append(jax.ShapeDtypeStruct((b, H // LANE),
                                              jnp.float32))

    kernel = _make_kernel(H=H, nkv=nkv, group=group, dh=dh, bs=bs,
                          n_inner=n_inner, mp=mp, scale=scale, eps=eps,
                          quant_w=quant_w, quant_kv=quant_kv,
                          residual=residual, quantize_out=quantize_out)
    out = pl.pallas_call(
        kernel,
        name=CONSTRAINT.name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nkv, nj),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((1, H), cdt),        # x (post-rms)
                pltpu.VMEM((group, dh), cdt),   # q (rotary-applied)
                pltpu.VMEM((1, dh), cdt),       # k current token
                pltpu.VMEM((1, dh), cdt),       # v current token
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dh), jnp.float32),
                pltpu.VMEM((1, H), jnp.float32),  # o-proj accumulator
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=not _on_tpu(),
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), *operands)

    if quantize_out:
        h_out = (out[0], out[-1])  # (q [b, H] int8, scale [b, H/128])
    else:
        h_out = out[0].reshape(b, 1, H)
    kc_new = out[1].reshape(max_pages, nkv, bs, dh)
    vc_new = out[2].reshape(max_pages, nkv, bs, dh)
    if quant_kv:
        ksc_new = out[3].reshape(max_pages, nkv)
        vsc_new = out[4].reshape(max_pages, nkv)
        return h_out, (kc_new, ksc_new), (vc_new, vsc_new)
    return h_out, kc_new, vc_new


# ---------------------------------------------------------------------------
# full-layer + layer-scanned rungs (ISSUE 20): the MLP half joins the
# fusion, then ONE pallas_call walks every decoder layer
# ---------------------------------------------------------------------------

# requested MLP inner-dim block: gate/up/down stream F in chunks of the
# largest divisor <= this that fits VMEM next to the attention blocks
MLP_BLOCK = 512


def _fit_mlp_block(F: int, H: int, itw: int,
                   reserve_bytes: int = 0) -> int:
    """Largest divisor of the MLP inner dim <= MLP_BLOCK whose three
    weight blocks (gate + up + down, double-buffered) fit the VMEM
    budget next to `reserve_bytes` of attention-phase state."""
    return fit_vmem_block(MLP_BLOCK, F, 3 * H * itw, n_buffers=2,
                          reserve_bytes=reserve_bytes)


class _S:
    """Shape/dtype view standing in for an array in the shape-only
    support checks (the scan check delegates per-layer geometry to
    `megakernel_full_supported` without materializing layer slices)."""

    def __init__(self, shape, dtype):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = jnp.dtype(dtype)

    @property
    def ndim(self):
        return len(self.shape)

    def reshape(self, *s):
        if len(s) == 1 and isinstance(s[0], (tuple, list)):
            s = tuple(s[0])
        return _S(s, self.dtype)

    def astype(self, dt):
        return _S(self.shape, dt)


def _drop_lead(w):
    """Per-layer shape view of a stacked weight (or quant pair)."""
    if isinstance(w, tuple):
        return (_S(w[0].shape[1:], w[0].dtype),
                _S(w[1].shape[1:], w[1].dtype))
    return _S(w.shape[1:], w.dtype)


def _attn_resident_bytes(b, H, group, dh, bs, quant_w, quant_kv, cdt):
    """The attention phase's double-buffered VMEM estimate (the
    `megakernel_supported` formula) + the residual-carry scratch."""
    itw = 1 if quant_w else jnp.dtype(cdt).itemsize
    kv_it = 1 if quant_kv else jnp.dtype(cdt).itemsize
    wbytes = H * group * dh * itw * 2 + H * dh * itw * 2
    pbytes = 2 * PAGES_PER_STEP * bs * dh * kv_it
    return 2 * (wbytes + pbytes) + b * H * jnp.dtype(cdt).itemsize


def megakernel_full_supported(h, w_in, w_post, wq, wk, wv, wo, wg, wu,
                              wd, k_cache, v_cache, tables, *,
                              k_scale=None, v_scale=None) -> str | None:
    """None when the FULL-LAYER rung (attention + MLP fused) can serve
    these per-layer operands, a reason otherwise. Strictly stronger
    than `megakernel_supported`: a reason here still permits the attn
    rung (the ladder steps down one fusion level at a time)."""
    reason = megakernel_supported(h, w_in, wq, wk, wv, wo, k_cache,
                                  v_cache, tables, k_scale=k_scale,
                                  v_scale=v_scale)
    if reason is not None:
        return reason
    b, _, H = h.shape
    _, _, bs, dh = k_cache.shape
    if isinstance(wg, tuple):
        F = wg[0].shape[0]
    else:
        F = wg.shape[1]
    qs = []
    for w, (no, ni) in ((wg, (F, H)), (wu, (F, H)), (wd, (H, F))):
        _, _, q = _unpack_weight(w, no, ni)
        if q is None:
            return "unsupported MLP weight layout (packed int4?)"
        qs.append(q)
    if len(set(qs)) != 1:
        return "mixed dense/quantized MLP weights"
    if qs[0] != isinstance(wq, tuple):
        return "attention and MLP weights disagree on quantization"
    nkv = k_cache.shape[1]
    nh = (wq[0].shape[0] if isinstance(wq, tuple) else wq.shape[1]) // dh
    itw = 1 if qs[0] else jnp.dtype(h.dtype).itemsize
    reserve = _attn_resident_bytes(b, H, nh // nkv, dh, bs,
                                   qs[0], k_cache.dtype == jnp.int8,
                                   h.dtype)
    bf = _fit_mlp_block(F, H, itw, reserve_bytes=reserve)
    if reserve + 2 * 3 * bf * H * itw > VMEM_BUDGET_BYTES:
        return ("attention + MLP weight blocks exceed the VMEM budget "
                f"even at mlp block {bf}")
    return None


def megakernel_scan_supported(h, w_in, w_post, wq, wk, wv, wo, wg, wu,
                              wd, k_cache, v_cache, tables, *,
                              n_layers, k_scale=None,
                              v_scale=None) -> str | None:
    """None when the LAYER-SCANNED rung can serve these STACKED
    operands (leading layer axis on every weight, layer-major page
    axis on the pools), a reason otherwise. A reason here still
    permits the full rung on per-layer operands."""
    L = int(n_layers)
    if L < 1:
        return f"need at least one layer, got {n_layers}"
    stacked = (("input_layernorm", w_in),
               ("post_attention_layernorm", w_post),
               ("q_proj", wq), ("k_proj", wk), ("v_proj", wv),
               ("o_proj", wo), ("gate_proj", wg), ("up_proj", wu),
               ("down_proj", wd))
    for name, w in stacked:
        arrs = w if isinstance(w, tuple) else (w,)
        for a in arrs:
            if a.ndim < 2 or a.shape[0] != L:
                return (f"{name} is not stacked along a leading "
                        f"{L}-layer axis (shape {a.shape})")
    if k_cache.ndim != 4:
        return f"paged pools required, got cache rank {k_cache.ndim}"
    if k_cache.shape[0] % L:
        return (f"pool page axis {k_cache.shape[0]} not divisible by "
                f"{L} layers")
    pool_view = _S((k_cache.shape[0] // L,) + k_cache.shape[1:],
                   k_cache.dtype)
    sc_view = None
    if k_scale is not None:
        if k_scale.shape[0] % L:
            return "pool scale page axis not divisible by layer count"
        sc_view = _S((k_scale.shape[0] // L,) + k_scale.shape[1:],
                     k_scale.dtype)
    return megakernel_full_supported(
        h, _drop_lead(w_in), _drop_lead(w_post), _drop_lead(wq),
        _drop_lead(wk), _drop_lead(wv), _drop_lead(wo), _drop_lead(wg),
        _drop_lead(wu), _drop_lead(wd), pool_view, pool_view, tables,
        k_scale=sc_view, v_scale=sc_view)


def _make_scan_kernel(*, H, F, nkv, group, dh, bs, n_inner, n_fb, mp,
                      n_layers, scale, eps, quant_w, quant_kv):
    """Build the layer-scanned fused decode-step kernel body: grid
    (L, b, nkv, n_inner + 2 + n_fb), residual stream carried across
    layers in a [b, H] VMEM scratch (never HBM between layers). The
    last grid axis adds the MLP phase to the attention schedule:

      j == 0               pre-attn rms (over the CARRIED residual),
                           QKV + rotary
      1 <= j <= n_inner    paged attention page stream
      j == n_inner + 1     attention finalize + o-proj + KV commit;
                           at the last kv head: residual add,
                           post-attn rms, MLP accumulator reset
      j >= n_inner + 2     one gate/up/down block of the MLP per step
                           (silu-mul at the oracle's bf16 seam, f32
                           down-proj accumulation); the last step adds
                           the residual and, at the last layer, emits
                           the row
    """
    dh2 = dh // 2
    f32 = jnp.float32
    ja = n_inner + 1
    jm0 = n_inner + 2
    L = n_layers

    def _decode_megakernel_scan_kernel(*refs):
        tbl_ref, len_ref = refs[0], refs[1]
        h_ref, win_ref, wpost_ref, cos_ref, sin_ref = refs[2:7]
        i = 7
        if quant_w:
            (wq_ref, wqs_ref, wk_ref, wks_ref, wv_ref, wvs_ref,
             wo_ref, wos_ref, wg_ref, wgs_ref, wu_ref, wus_ref,
             wd_ref, wds_ref) = refs[i:i + 14]
            i += 14
        else:
            (wq_ref, wk_ref, wv_ref, wo_ref, wg_ref, wu_ref,
             wd_ref) = refs[i:i + 7]
            i += 7
        kp_refs = refs[i:i + mp]; i += mp
        vp_refs = refs[i:i + mp]; i += mp
        ksc_refs = vsc_refs = ()
        if quant_kv:
            ksc_refs = refs[i:i + mp]; i += mp
            vsc_refs = refs[i:i + mp]; i += mp
        kcom_ref, vcom_ref = refs[i], refs[i + 1]; i += 2
        kscom_ref = vscom_ref = None
        if quant_kv:
            kscom_ref, vscom_ref = refs[i], refs[i + 1]; i += 2
        oh_ref, ok_ref, ov_ref = refs[i:i + 3]; i += 3
        oks_ref = ovs_ref = None
        if quant_kv:
            oks_ref, ovs_ref = refs[i], refs[i + 1]; i += 2
        (x_scr, q_scr, k_scr, v_scr, m_scr, l_scr, acc_scr, out_scr,
         hres_scr) = refs[i:]

        l_id = pl.program_id(0)
        b = pl.program_id(1)
        h_id = pl.program_id(2)
        j = pl.program_id(3)
        valid_until = len_ref[b]
        row = pl.ds(b, 1)

        @pl.when((l_id == 0) & (h_id == 0) & (j == 0))
        def _seed():
            # the residual stream enters VMEM once; every later layer
            # reads/writes the carried copy
            hres_scr[row, :] = h_ref[...]

        @pl.when((h_id == 0) & (j == 0))
        def _row_init():
            xr = hres_scr[row, :].astype(f32)
            var = jnp.mean(xr * xr, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + eps)
            x_scr[...] = (xr * inv
                          * win_ref[...].astype(f32)).astype(x_scr.dtype)
            out_scr[...] = jnp.zeros_like(out_scr)

        @pl.when(j == 0)
        def _qkv():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
            x = x_scr[...]
            if quant_w:
                xf = x.astype(f32)
                qf = jax.lax.dot_general(
                    xf, wq_ref[0].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wqs_ref[...]
                kf = jax.lax.dot_general(
                    xf, wk_ref[0].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wks_ref[...]
                vf = jax.lax.dot_general(
                    xf, wv_ref[0].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wvs_ref[...]
            else:
                qf = jax.lax.dot_general(
                    x, wq_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                kf = jax.lax.dot_general(
                    x, wk_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                vf = jax.lax.dot_general(
                    x, wv_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
            cdt = x_scr.dtype
            qv, kv_, vv = qf.astype(cdt), kf.astype(cdt), vf.astype(cdt)
            c = cos_ref[0:1, :dh2].astype(cdt)
            s = sin_ref[0:1, :dh2].astype(cdt)
            for g in range(group):
                x1 = qv[:, g * dh:g * dh + dh2]
                x2 = qv[:, g * dh + dh2:(g + 1) * dh]
                q_scr[g:g + 1, :dh2] = x1 * c - x2 * s
                q_scr[g:g + 1, dh2:] = x2 * c + x1 * s
            k1, k2 = kv_[:, :dh2], kv_[:, dh2:]
            k_scr[:, :dh2] = k1 * c - k2 * s
            k_scr[:, dh2:] = k2 * c + k1 * s
            v_scr[...] = vv

        def _accum(s, v):
            m_prev = m_scr[...]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev[:, :1], m_cur)
            corr = jnp.exp(m_prev[:, :1] - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)
            acc_scr[...] = acc_scr[...] * corr + pv
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        for m in range(mp):
            col = (j - 1) * mp + m

            @pl.when((j >= 1) & (j <= n_inner)
                     & (col * bs < valid_until))
            def _page(m=m, col=col):
                q = q_scr[...].astype(f32)
                k = kp_refs[m][0].astype(f32)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale
                if quant_kv:
                    s = s * ksc_refs[m][0, 0]
                pos = col * bs + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(pos < valid_until, s, _NEG_INF)
                v = vp_refs[m][0].astype(f32)
                if quant_kv:
                    v = v * vsc_refs[m][0, 0]
                _accum(s, v)

        @pl.when(j == ja)
        def _final():
            q = q_scr[...].astype(f32)
            kcur = k_scr[...].astype(f32)
            s = jax.lax.dot_general(
                q, kcur, (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale
            _accum(s, v_scr[...].astype(f32))
            l = l_scr[:, :1]
            ctx = (acc_scr[...]
                   / jnp.where(l > 0.0, l, 1.0)).astype(x_scr.dtype)
            contrib = jnp.zeros((1, H), f32)
            for g in range(group):
                cg = ctx[g:g + 1, :]
                if quant_w:
                    wslice = wo_ref[0][:, g * dh:(g + 1) * dh]  # [H, dh]
                    contrib += jax.lax.dot_general(
                        cg.astype(f32), wslice.astype(f32),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
                else:
                    wslice = wo_ref[0, 0, g * dh:(g + 1) * dh, :]
                    contrib += jax.lax.dot_general(
                        cg, wslice, (((1,), (0,)), ((), ())),
                        preferred_element_type=f32)
            out_scr[...] += contrib

            slot = valid_until % bs
            rows = jax.lax.broadcasted_iota(jnp.int32, (bs, dh), 0)
            if quant_kv:
                for tok_ref, com_ref, scom_ref, o_ref, os_ref in (
                        (k_scr, kcom_ref, kscom_ref, ok_ref, oks_ref),
                        (v_scr, vcom_ref, vscom_ref, ov_ref, ovs_ref)):
                    tokf = tok_ref[...].astype(f32)
                    amax = jnp.max(jnp.abs(tokf), axis=-1,
                                   keepdims=True) / 127.0
                    old = jnp.where(slot == 0, 0.0, scom_ref[0, 0])
                    new = jnp.maximum(old, amax)
                    safe = jnp.where(new > 0.0, new, 1.0)
                    ratio = old / safe
                    pg = jnp.round(com_ref[0].astype(f32) * ratio)
                    qtok = jnp.round(tokf / safe)
                    pg = jnp.where(rows == slot,
                                   jnp.broadcast_to(qtok, (bs, dh)), pg)
                    o_ref[0] = jnp.clip(pg, -127, 127).astype(jnp.int8)
                    os_ref[...] = new
            else:
                ok_ref[0] = jnp.where(
                    rows == slot,
                    jnp.broadcast_to(k_scr[...], (bs, dh)),
                    kcom_ref[0]).astype(ok_ref.dtype)
                ov_ref[0] = jnp.where(
                    rows == slot,
                    jnp.broadcast_to(v_scr[...], (bs, dh)),
                    vcom_ref[0]).astype(ov_ref.dtype)

        @pl.when((j == ja) & (h_id == nkv - 1))
        def _post_attn():
            # residual add (the attn-rung `_residual` seam), then the
            # post-attention rms feeds the MLP phase through the SAME
            # x scratch; the o-proj accumulator becomes the down-proj
            # accumulator
            proj = out_scr[...]
            if quant_w:
                proj = proj * wos_ref[...]
            hat = (hres_scr[row, :].astype(f32)
                   + proj).astype(x_scr.dtype)
            hres_scr[row, :] = hat
            xr = hat.astype(f32)
            var = jnp.mean(xr * xr, axis=-1, keepdims=True)
            inv = jax.lax.rsqrt(var + eps)
            x_scr[...] = (xr * inv
                          * wpost_ref[...].astype(f32)).astype(
                              x_scr.dtype)
            out_scr[...] = jnp.zeros_like(out_scr)

        @pl.when((j >= jm0) & (h_id == nkv - 1))
        def _mlp():
            # one [bf] block of gate/up/down per step: gate and up
            # round to the compute dtype BEFORE silu-mul (the oracle's
            # `_mm(...).astype` seam), the down projection accumulates
            # in f32 and rounds once at the end
            x2 = x_scr[...]
            if quant_w:
                x2f = x2.astype(f32)
                gf = jax.lax.dot_general(
                    x2f, wg_ref[0].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wgs_ref[...]
                uf = jax.lax.dot_general(
                    x2f, wu_ref[0].astype(f32), (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * wus_ref[...]
            else:
                gf = jax.lax.dot_general(
                    x2, wg_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
                uf = jax.lax.dot_general(
                    x2, wu_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)
            cdt = x_scr.dtype
            y = jax.nn.silu(gf.astype(cdt)) * uf.astype(cdt)
            if quant_w:
                out_scr[...] += jax.lax.dot_general(
                    y.astype(f32), wd_ref[0].astype(f32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)
            else:
                out_scr[...] += jax.lax.dot_general(
                    y, wd_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32)

        @pl.when((j == n_inner + 1 + n_fb) & (h_id == nkv - 1))
        def _mlp_final():
            down = out_scr[...]
            if quant_w:
                down = down * wds_ref[...]
            hnew = (hres_scr[row, :].astype(f32)
                    + down).astype(x_scr.dtype)
            hres_scr[row, :] = hnew
            # write the row every layer; only the last layer's flush
            # reaches HBM as the final value
            oh_ref[...] = hnew

    return _decode_megakernel_scan_kernel


def decode_layers_megakernel(h, lens, tables, w_in, w_post, wq, wk, wv,
                             wo, wg, wu, wd, k_cache, v_cache, *,
                             n_layers: int, rope_base: float = 10000.0,
                             eps: float = 1e-6,
                             scale: float | None = None,
                             k_scale=None, v_scale=None):
    """The layer-scanned FULL-LAYER fused decode step: every decoder
    layer's attention block AND MLP half in ONE pallas_call whose
    outermost grid axis walks the layers.

    Stacked operands: every per-layer weight gains a leading
    `n_layers` axis (`models/llama.py stack_decode_layer_params`
    builds the re-layout once at engine build); the paged pools stack
    layer-major along the page axis — k_cache/v_cache are
    [n_layers * max_pages, nkv, block, dh] where layer i owns pages
    [i * max_pages, (i+1) * max_pages) and `tables` stays the ONE
    per-layer block table (page ids are per-layer; the kernel adds
    the layer offset). `n_layers=1` with `w[None]`-stacked weights is
    the FULL rung: one layer per call, MLP fused, multi-kernel launch
    count already halved.

    Returns (h_out [b, 1, H], k_cache', v_cache') in the stacked pool
    layout — or the (pool, scale) pairs for int8 pools — with exactly
    one page per (layer, row, kv head) rewritten.
    """
    reason = megakernel_scan_supported(
        h, w_in, w_post, wq, wk, wv, wo, wg, wu, wd, k_cache, v_cache,
        tables, n_layers=n_layers, k_scale=k_scale, v_scale=v_scale)
    if reason is not None:
        raise ValueError(f"decode scan megakernel unsupported here: "
                         f"{reason}")
    L = int(n_layers)
    b, _, H = h.shape
    lp, nkv, bs, dh = k_cache.shape
    max_pages = lp // L
    w_tbl = tables.shape[1]
    quant_kv = k_cache.dtype == jnp.int8
    quant_w = isinstance(wq, tuple)
    nh = (wq[0].shape[1] if quant_w else wq.shape[2]) // dh
    group = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    mp = _fit_pages_per_step(w_tbl)
    n_inner = w_tbl // mp
    gdh = group * dh
    cdt = h.dtype
    F = wg[0].shape[1] if quant_w else wg.shape[2]
    itw = 1 if quant_w else jnp.dtype(cdt).itemsize
    reserve = _attn_resident_bytes(b, H, group, dh, bs, quant_w,
                                   quant_kv, cdt)
    bf = _fit_mlp_block(F, H, itw, reserve_bytes=reserve)
    n_fb = F // bf
    nj = n_inner + 2 + n_fb

    h2d = h.reshape(b, H)
    cos_h, sin_h = rope_freqs(0, dh, rope_base, position_ids=lens)
    cos_t = jnp.concatenate([cos_h, cos_h], axis=-1)
    sin_t = jnp.concatenate([sin_h, sin_h], axis=-1)

    def _split(w):
        if isinstance(w, tuple):
            return w[0], w[1].astype(jnp.float32)
        return w, None

    wq_a, wq_s = _split(wq)
    wk_a, wk_s = _split(wk)
    wv_a, wv_s = _split(wv)
    wo_a, wo_s = _split(wo)
    wg_a, wg_s = _split(wg)
    wu_a, wu_s = _split(wu)
    wd_a, wd_s = _split(wd)

    kc3 = k_cache.reshape(lp * nkv, bs, dh)
    vc3 = v_cache.reshape(lp * nkv, bs, dh)
    if quant_kv:
        ksc3 = k_scale.astype(jnp.float32).reshape(lp * nkv, 1)
        vsc3 = v_scale.astype(jnp.float32).reshape(lp * nkv, 1)

    jm0 = n_inner + 2

    def row_map(l_, b_, h_, j_, tbl, lens_):
        return (b_, 0)

    def lrow_map(l_, b_, h_, j_, tbl, lens_):
        return (l_, 0)

    def _fbm(h_, j_):
        # the MLP block walk happens ONCE, at the last kv head; other
        # kv heads pin block 0 so no redundant weight streaming occurs
        return jnp.where(h_ == nkv - 1,
                         jnp.clip(j_ - jm0, 0, n_fb - 1), 0)

    def stream_map_m(m):
        def _map(l_, b_, h_, j_, tbl, lens_):
            col = jnp.clip((j_ - 1) * mp + m, 0, w_tbl - 1)
            last = jnp.maximum((lens_[b_] - 1) // bs, 0)
            col = jnp.minimum(col, last)
            return ((l_ * max_pages + tbl[b_, col]) * nkv + h_, 0, 0)
        return _map

    def stream_scale_map_m(m):
        def _map(l_, b_, h_, j_, tbl, lens_):
            col = jnp.clip((j_ - 1) * mp + m, 0, w_tbl - 1)
            last = jnp.maximum((lens_[b_] - 1) // bs, 0)
            col = jnp.minimum(col, last)
            return ((l_ * max_pages + tbl[b_, col]) * nkv + h_, 0)
        return _map

    def commit_map(l_, b_, h_, j_, tbl, lens_):
        i = jnp.minimum(lens_[b_] // bs, w_tbl - 1)
        return ((l_ * max_pages + tbl[b_, i]) * nkv + h_, 0, 0)

    def commit_scale_map(l_, b_, h_, j_, tbl, lens_):
        i = jnp.minimum(lens_[b_] // bs, w_tbl - 1)
        return ((l_ * max_pages + tbl[b_, i]) * nkv + h_, 0)

    in_specs = [
        pl.BlockSpec((1, H), row_map),          # h (seed)
        pl.BlockSpec((1, H), lrow_map),         # w_in (stacked)
        pl.BlockSpec((1, H), lrow_map),         # w_post (stacked)
        pl.BlockSpec((1, dh), row_map),         # cos
        pl.BlockSpec((1, dh), row_map),         # sin
    ]
    operands = [h2d, w_in, w_post, cos_t, sin_t]
    if quant_w:
        in_specs += [
            pl.BlockSpec((1, gdh, H),
                         lambda l_, b_, h_, j_, t, le: (l_, h_, 0)),
            pl.BlockSpec((1, gdh),
                         lambda l_, b_, h_, j_, t, le: (l_, h_)),
            pl.BlockSpec((1, dh, H),
                         lambda l_, b_, h_, j_, t, le: (l_, h_, 0)),
            pl.BlockSpec((1, dh),
                         lambda l_, b_, h_, j_, t, le: (l_, h_)),
            pl.BlockSpec((1, dh, H),
                         lambda l_, b_, h_, j_, t, le: (l_, h_, 0)),
            pl.BlockSpec((1, dh),
                         lambda l_, b_, h_, j_, t, le: (l_, h_)),
            pl.BlockSpec((1, H, gdh),
                         lambda l_, b_, h_, j_, t, le: (l_, 0, h_)),
            pl.BlockSpec((1, H), lrow_map),
            pl.BlockSpec((1, bf, H),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, _fbm(h_, j_), 0)),
            pl.BlockSpec((1, bf),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, _fbm(h_, j_))),
            pl.BlockSpec((1, bf, H),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, _fbm(h_, j_), 0)),
            pl.BlockSpec((1, bf),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, _fbm(h_, j_))),
            pl.BlockSpec((1, H, bf),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, 0, _fbm(h_, j_))),
            pl.BlockSpec((1, H), lrow_map),
        ]
        operands += [wq_a, wq_s, wk_a, wk_s, wv_a, wv_s, wo_a, wo_s,
                     wg_a, wg_s, wu_a, wu_s, wd_a, wd_s]
    else:
        wo4 = wo_a.reshape(L, nkv, gdh, H)
        in_specs += [
            pl.BlockSpec((1, H, gdh),
                         lambda l_, b_, h_, j_, t, le: (l_, 0, h_)),
            pl.BlockSpec((1, H, dh),
                         lambda l_, b_, h_, j_, t, le: (l_, 0, h_)),
            pl.BlockSpec((1, H, dh),
                         lambda l_, b_, h_, j_, t, le: (l_, 0, h_)),
            pl.BlockSpec((1, 1, gdh, H),
                         lambda l_, b_, h_, j_, t, le: (l_, h_, 0, 0)),
            pl.BlockSpec((1, H, bf),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, 0, _fbm(h_, j_))),
            pl.BlockSpec((1, H, bf),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, 0, _fbm(h_, j_))),
            pl.BlockSpec((1, bf, H),
                         lambda l_, b_, h_, j_, t, le:
                         (l_, _fbm(h_, j_), 0)),
        ]
        operands += [wq_a, wk_a, wv_a, wo4, wg_a, wu_a, wd_a]
    for m in range(mp):
        in_specs.append(pl.BlockSpec((1, bs, dh), stream_map_m(m)))
        operands.append(kc3)
    for m in range(mp):
        in_specs.append(pl.BlockSpec((1, bs, dh), stream_map_m(m)))
        operands.append(vc3)
    if quant_kv:
        for m in range(mp):
            in_specs.append(pl.BlockSpec((1, 1), stream_scale_map_m(m)))
            operands.append(ksc3)
        for m in range(mp):
            in_specs.append(pl.BlockSpec((1, 1), stream_scale_map_m(m)))
            operands.append(vsc3)
    commit_base = 2 + len(operands)
    in_specs += [pl.BlockSpec((1, bs, dh), commit_map),
                 pl.BlockSpec((1, bs, dh), commit_map)]
    operands += [kc3, vc3]
    if quant_kv:
        in_specs += [pl.BlockSpec((1, 1), commit_scale_map),
                     pl.BlockSpec((1, 1), commit_scale_map)]
        operands += [ksc3, vsc3]

    out_specs = [
        pl.BlockSpec((1, H), row_map),
        pl.BlockSpec((1, bs, dh), commit_map),
        pl.BlockSpec((1, bs, dh), commit_map),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, H), cdt),
        jax.ShapeDtypeStruct(kc3.shape, kc3.dtype),
        jax.ShapeDtypeStruct(vc3.shape, vc3.dtype),
    ]
    aliases = {commit_base: 1, commit_base + 1: 2}
    if quant_kv:
        out_specs += [pl.BlockSpec((1, 1), commit_scale_map),
                      pl.BlockSpec((1, 1), commit_scale_map)]
        out_shape += [jax.ShapeDtypeStruct(ksc3.shape, jnp.float32),
                      jax.ShapeDtypeStruct(vsc3.shape, jnp.float32)]
        aliases[commit_base + 2] = 3
        aliases[commit_base + 3] = 4

    kernel = _make_scan_kernel(H=H, F=F, nkv=nkv, group=group, dh=dh,
                               bs=bs, n_inner=n_inner, n_fb=n_fb, mp=mp,
                               n_layers=L, scale=scale, eps=eps,
                               quant_w=quant_w, quant_kv=quant_kv)
    out = pl.pallas_call(
        kernel,
        # the FULL rung is the scan kernel at one layer; its own name
        # lets the KernelConstraint registry (and the roofline auditor)
        # tell the rungs apart
        name=(FULL_CONSTRAINT if L == 1 else SCAN_CONSTRAINT).name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, b, nkv, nj),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((1, H), cdt),        # x (post-rms)
                pltpu.VMEM((group, dh), cdt),   # q (rotary-applied)
                pltpu.VMEM((1, dh), cdt),       # k current token
                pltpu.VMEM((1, dh), cdt),       # v current token
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, 128), jnp.float32),
                pltpu.VMEM((group, dh), jnp.float32),
                pltpu.VMEM((1, H), jnp.float32),  # o/down accumulator
                pltpu.VMEM((b, H), cdt),        # carried residual
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=not _on_tpu(),
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), *operands)

    h_out = out[0].reshape(b, 1, H)
    kc_new = out[1].reshape(lp, nkv, bs, dh)
    vc_new = out[2].reshape(lp, nkv, bs, dh)
    if quant_kv:
        ksc_new = out[3].reshape(lp, nkv)
        vsc_new = out[4].reshape(lp, nkv)
        return h_out, (kc_new, ksc_new), (vc_new, vsc_new)
    return h_out, kc_new, vc_new


def _stack_one(w):
    """[None]-stack one per-layer weight (or quant pair) for the
    one-layer scan call — the FULL rung."""
    if isinstance(w, tuple):
        return (w[0][None], w[1][None])
    return w[None]


def decode_layer_megakernel_full(h, lens, tables, w_in, w_post, wq, wk,
                                 wv, wo, wg, wu, wd, k_cache, v_cache,
                                 *, rope_base: float = 10000.0,
                                 eps: float = 1e-6,
                                 scale: float | None = None,
                                 k_scale=None, v_scale=None):
    """The FULL rung: one decoder layer's attention block AND MLP half
    fused in one pallas_call — `decode_layers_megakernel` at
    n_layers=1 over [None]-stacked per-layer weights. Pools keep their
    per-layer [max_pages, nkv, block, dh] layout."""
    return decode_layers_megakernel(
        h, lens, tables, _stack_one(w_in), _stack_one(w_post),
        _stack_one(wq), _stack_one(wk), _stack_one(wv), _stack_one(wo),
        _stack_one(wg), _stack_one(wu), _stack_one(wd), k_cache,
        v_cache, n_layers=1, rope_base=rope_base, eps=eps, scale=scale,
        k_scale=k_scale, v_scale=v_scale)


def _megakernel_fused_roofline(shapes, dtypes):
    """Closed-form cost of one full/scan megakernel launch (pure shape
    math — `KernelConstraint.roofline` contract). Operand layout is
    the `decode_layers_megakernel` call order: [tables, lens, h, w_in,
    w_post, cos, sin, <weights>, <pool streams>, <commits>]. Stacked
    weight bytes count ONCE per layer step; pool bytes count the
    TABLE-NAMED pages (b * w_tbl per layer), not the whole pool."""
    try:
        if len(shapes) < 8 or len(shapes[0]) != 2:
            return None
        b, w_tbl = shapes[0]
        if shapes[1] != (b,) or len(shapes[2]) != 2:
            return None
        H = shapes[2][1]
        if len(shapes[3]) != 2:
            return None
        L = shapes[3][0]
        quant_w = dtypes[7] == "int8"
        n_w = 14 if quant_w else 7
        w_lo, w_hi = 7, 7 + n_w
        if len(shapes) <= w_hi:
            return None
        weight_bytes = sum(
            math.prod(shapes[k]) * dtype_itemsize(dtypes[k])
            for k in range(w_lo, w_hi))
        # wq/wg expose the head and MLP extents
        if quant_w:
            N = shapes[w_lo][1]          # [L, nh*dh, H]
            F = shapes[w_lo + 8][1]      # [L, F, H]
        else:
            N = shapes[w_lo][2]          # [L, H, nh*dh]
            F = shapes[w_lo + 4][2]      # [L, H, F]
        pool = shapes[w_hi]              # [L*max_pages*nkv, bs, dh]
        if len(pool) != 3:
            return None
        _, bs, dh = pool
        kv_it = dtype_itemsize(dtypes[w_hi])
        # wk exposes the kv-head extent: quant [L, nkv*dh, H] at
        # offset 2, dense [L, H, nkv*dh] at offset 1
        nkv = max(1, (shapes[w_lo + 2][1] if quant_w
                      else shapes[w_lo + 1][2]) // dh)
        nh = N // dh
        ctx = w_tbl * bs
        # bytes: stacked weights once + streamed pages per layer +
        # row traffic (h in/out per layer boundary collapses to once)
        kv_bytes = 2 * L * b * ctx * dh * kv_it
        row_bytes = 2 * b * H * dtype_itemsize(dtypes[2])
        commit_bytes = 2 * L * b * nkv * bs * dh * kv_it
        # flops: projections (q,k,v,o + gate,up,down) + attention
        proj_flops = 2 * b * L * H * (nh * dh + 2 * nkv * dh
                                      + nh * dh + 3 * F)
        attn_flops = 4 * b * L * nh * dh * ctx
        return {"flops": int(proj_flops + attn_flops),
                "hbm_bytes": int(weight_bytes + kv_bytes + row_bytes
                                 + commit_bytes)}
    except Exception:
        return None


FULL_CONSTRAINT = register_constraint(KernelConstraint(
    name="decode_megakernel_full",
    kernel_fns=(),      # the scan kernel at L == 1, told apart by `name=`
    blocks={"pages_per_step": PAGES_PER_STEP, "mlp_block": MLP_BLOCK},
    note="full-layer fused decode step (attention block + MLP half in "
         "one launch): the attn-rung schedule plus post-attention rms, "
         "blocked gate/up/down with in-kernel silu-mul, and the final "
         "residual add; MLP weights stream in mlp_block columns",
    checker=_check_megakernel_shapes,
    roofline=_megakernel_fused_roofline,
    source="decode_megakernel.py",
))

SCAN_CONSTRAINT = register_constraint(KernelConstraint(
    name="decode_megakernel_scan",
    kernel_fns=("_decode_megakernel_scan_kernel",),
    blocks={"pages_per_step": PAGES_PER_STEP, "mlp_block": MLP_BLOCK},
    note="layer-scanned fused decode step: ONE launch walks every "
         "decoder layer (outermost grid axis), stacked weights stream "
         "per layer step, the residual stream lives in VMEM scratch "
         "between layers, per-layer KV commits alias the stacked pool "
         "in place",
    checker=_check_megakernel_shapes,
    roofline=_megakernel_fused_roofline,
    source="decode_megakernel.py",
))
