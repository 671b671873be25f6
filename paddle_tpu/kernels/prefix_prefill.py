"""Ragged paged prefix-prefill attention as a Pallas TPU kernel.

The serving hot path this exists for: a request whose prompt head hit
the block-aligned prefix cache prefills only its bucketed suffix, with
the suffix queries attending over (a) the cached prefix K/V living in
the paged pools and (b) the suffix itself, causally
(models/llama._make_prefill_with_prefix). The jnp reference computes
that as a masked softmax over the prefix GATHERED to query width — a
[b, w_pre, nkv, page, dh] intermediate the XLA fusion study (PAPERS.md:
Operator Fusion in XLA) shows cannot fuse away: deep prefixes make the
prefill gather-bound.

This kernel is the Ragged Paged Attention treatment (PAPERS.md): a grid
streaming ONE (kv head, page) tile per step straight from the pools via
the per-row block table — no gathered prefix tensor ever exists — with
flash-style online-softmax m/l scratch carried across the kv axis, the
same recurrence as `_paged_gqa_kernel` in decode_attention.py. The kv
axis covers the prefix pages first, then the in-suffix blocks (causal);
each (batch row, kv head, q tile) owns one scratch pass.

Ragged handling is per-row and traced (ONE compile per shape):
`prefix_lens` masks pad pages (and pins their index maps so skipped
pages are never re-fetched), `suffix_lens` masks pad query rows and pad
suffix keys. bf16 inputs accumulate in f32, matching the reference.
Off-TPU the kernel runs in interpret mode so CPU tests exercise the
real grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, fit_vmem_block,
                          is_scale_operand, missing_scale_finding,
                          register_constraint, tensor_operands,
                          vmem_row_cap)
from .decode_attention import _on_tpu

_NEG_INF = -1e30

# default query-position block each (batch row, kv head, q tile) grid
# cell owns; rows inside a tile are (q position, head-in-group) pairs
BLOCK_Q = 128
# default (maximum) suffix kv block streamed per suffix-phase step; the
# fitting helper rounds it DOWN to a whole number of KV pages dividing
# the suffix bucket, so both phases stream page-granular tiles
BLOCK_S = 512


def fit_blocks(sb: int, page: int, group: int, dh: int, *,
               kv_itemsize: int = 2):
    """(block_q, block_s) for a bucketed suffix of length `sb` over KV
    pages of `page` tokens — the shared `constraints.fit_vmem_block`
    logic applied to both axes: block_q is the largest divisor of `sb`
    under the double-buffered cap at query-group width; block_s is the
    largest whole-page multiple dividing `sb` under the same cap (the
    prefix phase is pinned at one page per step by the pool layout).
    `kv_itemsize` is the POOL element size: int8 pools halve the bytes
    per streamed row, so the cap admits 2x the rows — minus a small
    reserve for the (1, 1) f32 scale tiles that ride each int8 step."""
    bq = fit_vmem_block(BLOCK_Q, sb, group * dh * 2)
    reserve = 0 if kv_itemsize >= 2 else 4096  # scale refs + padding
    cap = vmem_row_cap(dh * kv_itemsize, reserve_bytes=reserve)
    m = max(1, sb // page)
    k = max(1, min(BLOCK_S, cap) // page)
    k = min(k, m)
    while m % k:
        k -= 1
    return bq, k * page


def _check_prefix_prefill_shapes(shapes, dtypes):
    """Checker for the prefix-prefill pallas call. Operands lead with
    the scalar-prefetch args (tables, prefix lens, suffix lens); the
    rank-3 tail is q [b*nkv*nq, block_q*group, dh], the k/v pools
    [pages*nkv, page, dh], then the suffix k/v [b*nkv*n_suf, block_s,
    dh] — so the page size and the suffix streaming block are both
    shape-decidable here."""
    out = []
    arr = [s for s in shapes if len(s) == 3]
    if len(arr) < 5:
        return out
    d = arr[0][-1]
    if d % LANE:
        out.append(("warning",
                    f"head_dim {d} is not a multiple of the {LANE}-lane "
                    "tile; every streamed tile pads to "
                    f"{-(-d // LANE) * LANE} lanes"))
    page, blk_s = arr[1][1], arr[3][1]
    if page and blk_s % page:
        out.append(("warning",
                    f"suffix BLOCK_S {blk_s} is not a multiple of the "
                    f"KV page size {page}; the (kv head, page) streaming "
                    "grid degrades to sub-page suffix tiles"))
    return out


def _prefix_prefill_roofline(shapes, dtypes):
    """Roofline model for one prefix-prefill launch. The kernel's
    collapsed rank-3 layout (q [b·nkv·nq, bq·g, dh], suffix k/v
    [b·nkv·n_suf, bs, dh], pools [P·nkv, page, dh], tables [b, w])
    hides nkv/nq individually, but the PRODUCTS cancel: the prefix
    phase streams one (page x kv head) tile per (b, h, q-tile, page)
    grid step, so prefix bytes = q_rows · w · page · dh · itemsize per
    cache — the POOL PAGES the table names, exact. The causal suffix
    terms use the one-block-per-tile shape of the short-suffix regime
    this kernel targets (the prefix stream dominates there). Pure
    shape math; None when the layout doesn't resolve."""
    from .constraints import dtype_itemsize

    arrs = tensor_operands(shapes, dtypes)
    tables = next((s for s, dt in zip(shapes, dtypes)
                   if len(s) == 2 and dt.startswith("int")), None)
    if len(arrs) < 5 or tables is None:
        return None
    # operand order (see the pallas_call below): q, k_pool, v_pool,
    # [scales rank-2], k_suf, v_suf — suffix k/v are the LAST two
    (q_s, q_d), (pool_s, pool_d) = arrs[0], arrs[1]
    (ks_s, ks_d) = arrs[-2]
    q_rows, dh = q_s[0], q_s[-1]
    w, page = tables[1], pool_s[-2]
    q_elems = math.prod(q_s)
    prefix_ctx = w * page
    kv_item = dtype_itemsize(pool_d)
    prefix_bytes = 2 * q_rows * w * page * dh * kv_item
    n_scales = sum(1 for s, dt in zip(shapes, dtypes)
                   if is_scale_operand(s, dt))
    if n_scales:
        prefix_bytes += n_scales * q_rows * w * 4
    suffix_bytes = 2 * math.prod(ks_s) * dtype_itemsize(ks_d)
    q_bytes = 2 * q_elems * dtype_itemsize(q_d)
    flops = 4 * q_elems * (prefix_ctx + ks_s[1])
    return {"flops": flops,
            "hbm_bytes": q_bytes + prefix_bytes + suffix_bytes}


CONSTRAINT = register_constraint(KernelConstraint(
    name="prefix_prefill",
    kernel_fns=("_prefix_prefill_kernel",),
    blocks={"block_q": BLOCK_Q, "block_s": BLOCK_S},
    note="bandwidth-bound cached-prefix suffix prefill; suffix tiles "
         "should stay whole-page multiples so the kv streaming axis "
         "never issues sub-page DMAs",
    checker=_check_prefix_prefill_shapes,
    source="prefix_prefill.py",
    roofline=_prefix_prefill_roofline,
))


def _check_q8_prefix_prefill_shapes(shapes, dtypes):
    """int8 variant: the rank-3 tail reads identically (the rank-2 f32
    scale operands drop out of the filter), plus the quantized pools
    must travel with two scale operands (the shared
    `constraints.missing_scale_finding` check)."""
    out = list(_check_prefix_prefill_shapes(shapes, dtypes))
    finding = missing_scale_finding(shapes, dtypes)
    if finding is not None:
        out.append(finding)
    return out


CONSTRAINT_Q8 = register_constraint(KernelConstraint(
    name="prefix_prefill_q8",
    kernel_fns=("_prefix_prefill_q8_kernel",),
    blocks={"block_q": BLOCK_Q, "block_s": BLOCK_S},
    note="int8-pool prefix prefill streams quantized (kv head, page) "
         "tiles + their f32 absmax scales; suffix tiles stay "
         "whole-page multiples like the bf16 grid",
    checker=_check_q8_prefix_prefill_shapes,
    source="prefix_prefill.py",
    roofline=_prefix_prefill_roofline,
))


def prefix_prefill_reference(q: jax.Array, k_suf: jax.Array,
                             v_suf: jax.Array, key_cache: jax.Array,
                             value_cache: jax.Array,
                             prefix_tables: jax.Array,
                             prefix_lens: jax.Array, *,
                             scale: float | None = None,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None) -> jax.Array:
    """The exact masked-softmax math the Pallas kernel replaces — and
    the SINGLE source of it: models.llama._make_prefill_with_prefix
    calls this per layer on its fallback path, and the kernel parity
    tests, OPBENCH's `prefix_prefill_ref` row and chip_smoke all oracle
    against it. Gathers the whole padded prefix to query width
    ([b, w_pre, nkv, page, dh]) — exact, gather-bound. Same operand
    layout as `prefix_prefill_attention` (minus suffix_lens: every
    query row is computed; pad rows are don't-care garbage here where
    the kernel emits zeros). int8 pools dequantize in f32 against their
    per-(page, kv head) ``k_scale``/``v_scale`` [max_pages, nkv] before
    the gather's transpose — the oracle covers both pool dtypes.
    Returns [b, sb, nh, dh] in f32."""
    b, sb, nh, dh = q.shape
    nkv, page = key_cache.shape[1], key_cache.shape[2]
    P = prefix_tables.shape[1] * page
    group = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    quant = key_cache.dtype == jnp.int8
    gk = key_cache[prefix_tables]       # [b, w_pre, nkv, page, dh]
    gv = value_cache[prefix_tables]
    if quant:
        if k_scale is None or v_scale is None:
            raise ValueError(
                "int8 KV pools need k_scale/v_scale (TPU103 lints a "
                "quantized pool consumed without its scales)")
        gk = gk.astype(jnp.float32) \
            * k_scale[prefix_tables][..., None, None]
        gv = gv.astype(jnp.float32) \
            * v_scale[prefix_tables][..., None, None]
    pk = jnp.transpose(gk, (0, 1, 3, 2, 4)).reshape(b, P, nkv, dh)
    pv = jnp.transpose(gv, (0, 1, 3, 2, 4)).reshape(b, P, nkv, dh)
    # dequantized int8 pages stay f32 all the way into the einsum — a
    # bf16 round-trip here (q.dtype) would diverge from the kernel,
    # whose dequant lives INSIDE the f32 accumulation, and break the
    # kernel-on-vs-off token-identity contract at bf16 serving dtypes
    cat_dtype = jnp.float32 if quant else q.dtype
    keys = jnp.concatenate([pk.astype(cat_dtype),
                            k_suf.astype(cat_dtype)], axis=1)
    vals = jnp.concatenate([pv.astype(cat_dtype),
                            v_suf.astype(cat_dtype)], axis=1)
    # prefix column t is real iff t < prefix_lens[row]; suffix column
    # t is visible to suffix query s iff t <= s
    pref_valid = jnp.arange(P)[None, :] < prefix_lens[:, None]
    causal = jnp.arange(sb)[None, :] <= jnp.arange(sb)[:, None]
    mask = jnp.concatenate(
        [jnp.broadcast_to(pref_valid[:, None, :], (b, sb, P)),
         jnp.broadcast_to(causal[None], (b, sb, sb))], axis=-1)
    q5 = q.reshape(b, sb, nkv, group, dh)
    s = jnp.einsum("bsngd,btnd->bsngt", q5.astype(jnp.float32),
                   keys.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, :, None, None, :], s,
                  jnp.asarray(_NEG_INF, jnp.float32))
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bsngt,btnd->bsngd", probs,
                     vals.astype(jnp.float32))
    return ctx.reshape(b, sb, nh, dh)


def _prefix_prefill_q8_kernel(tbl_ref, plen_ref, slen_ref, q_ref, kp_ref,
                              vp_ref, ksc_ref, vsc_ref, ks_ref, vs_ref,
                              o_ref, m_scr, l_scr, acc_scr, *, page: int,
                              block_q: int, block_s: int, group: int,
                              w_pre: int, scale: float, window=None):
    """int8-pool prefix prefill: `_prefix_prefill_kernel`'s grid where
    each prefix-phase step streams the int8 (kv head, page) tile PLUS
    its (1, 1) f32 absmax scale, rescaling scores and weighted values
    inside the f32 accumulation — the dequantized bf16 pool never
    materializes. The suffix phase (fresh bf16 K/V, not from the pool)
    is untouched."""
    _prefix_prefill_kernel(tbl_ref, plen_ref, slen_ref, q_ref, kp_ref,
                           vp_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
                           acc_scr, page=page, block_q=block_q,
                           block_s=block_s, group=group, w_pre=w_pre,
                           scale=scale, ksc_ref=ksc_ref, vsc_ref=vsc_ref,
                           window=window)


def _prefix_prefill_kernel(tbl_ref, plen_ref, slen_ref, q_ref, kp_ref,
                           vp_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
                           acc_scr, *, page: int, block_q: int,
                           block_s: int, group: int, w_pre: int,
                           scale: float, ksc_ref=None, vsc_ref=None,
                           window=None):
    """Grid (b, nkv, nq, j) with j the kv streaming axis: j < w_pre
    streams prefix page tbl[b, j] from the pool, j >= w_pre streams
    in-suffix block j - w_pre. Blocks: q/out [block_q*group, dh]
    (row r = query position q_start + r // group, head h*group +
    r % group), pool tiles [page, dh], suffix tiles [block_s, dh].
    Online softmax carries across j; scratch re-inits at j == 0.
    `ksc_ref`/`vsc_ref` (int8 pools, via `_prefix_prefill_q8_kernel`)
    carry the streamed page's f32 absmax scale. Under a `window`
    (kernels/ragged_attention.py) query position t sees key positions
    (t - window, t]: prefix step j streams table column `first + j`, the
    page of the oldest position the row's FIRST query sees, so the prefix
    axis is only as long as a window's pages and no column behind the
    window is read."""
    b = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    plen = plen_ref[b]
    slen = slen_ref[b]
    q_start = qi * block_q
    first = 0 if window is None \
        else jnp.maximum(plen - (window - 1), 0) // page

    def qpos(t):
        # row r of the tile is query position q_start + r // group
        r = jax.lax.broadcasted_iota(jnp.int32, (block_q * group, t), 0)
        return q_start + r // group

    def accum(s, v):
        """One online-softmax step over masked scores s [bq*g, T] and
        values v [T, dh] — the `_gqa_grid_body` recurrence."""
        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        corr = jnp.exp(m_prev[:, :1] - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # ---- prefix phase: one pool page per step, masked by prefix_lens
    @pl.when((j < w_pre) & ((first + j) * page < plen) & (q_start < slen))
    def _prefix():
        q = q_ref[0].astype(jnp.float32)
        k = kp_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if ksc_ref is not None:
            # int8 page tile: one scalar multiply folds the page's
            # absmax scale into the scores (uniform over the tile)
            s = s * ksc_ref[0, 0]
        kpos = (first + j) * page \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qp = qpos(s.shape[1])
        seen = (kpos < plen) & (qp < slen)
        if window is not None:
            seen &= plen + qp - kpos < window
        s = jnp.where(seen, s, _NEG_INF)
        v = vp_ref[0].astype(jnp.float32)
        if vsc_ref is not None:
            v = v * vsc_ref[0, 0]
        accum(s, v)

    # ---- suffix phase: causal over the suffix itself, masked by
    # suffix_lens; blocks fully beyond this q tile's causal reach (or
    # the row's real suffix) are skipped
    @pl.when((j >= w_pre) & (q_start < slen)
             & ((j - w_pre) * block_s
                < jnp.minimum(slen, q_start + block_q)))
    def _suffix():
        q = q_ref[0].astype(jnp.float32)
        k = ks_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = (j - w_pre) * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        qp = qpos(s.shape[1])
        seen = (kpos <= qp) & (kpos < slen) & (qp < slen)
        if window is not None:
            seen &= qp - kpos < window
        s = jnp.where(seen, s, _NEG_INF)
        accum(s, vs_ref[0].astype(jnp.float32))

    @pl.when(j == nj - 1)
    def _final():
        # pad query rows emit exact ZEROS: a fully-skipped tile leaves
        # l at 0 (divide by 1), and a pad row inside a live tile
        # accumulates exp(-inf - -inf) = 1 garbage mass — the qpos mask
        # zeroes both. Never NaN: a NaN in a pad position would poison
        # later layers' K/V pages (decode attention's 0 * NaN is NaN).
        l = l_scr[:, :1]
        out = acc_scr[...] / jnp.where(l > 0.0, l, 1.0)
        rows = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        o_ref[0] = jnp.where(q_start + rows // group < slen,
                             out, 0.0).astype(o_ref.dtype)


def prefix_prefill_attention(q: jax.Array, k_suf: jax.Array,
                             v_suf: jax.Array, key_cache: jax.Array,
                             value_cache: jax.Array,
                             prefix_tables: jax.Array,
                             prefix_lens: jax.Array,
                             suffix_lens: jax.Array | None = None, *,
                             scale: float | None = None,
                             block_q: int | None = None,
                             block_s: int | None = None,
                             k_scale: jax.Array | None = None,
                             v_scale: jax.Array | None = None) -> jax.Array:
    """Suffix-query attention over a cached paged prefix + the causal
    suffix, without materializing the gathered prefix.

    q: [b, sb, nh, dh] rotary-applied suffix queries; k_suf/v_suf:
    [b, sb, nkv, dh] rotary-applied suffix K/V; key_cache/value_cache:
    [max_pages, nkv, page, dh] pools; prefix_tables: [b, w_pre] page
    ids (rows shorter than w_pre pad with any valid page id — masked
    AND pinned out of the DMA stream); prefix_lens: [b] cached token
    counts (multiples of the page size); suffix_lens: [b] true suffix
    lengths in [1, sb] (None = all rows full). Returns [b, sb, nh, dh]
    in q's dtype; rows at positions >= suffix_lens[b] are zeros.

    int8 pools (``FLAGS_kv_cache_dtype=int8``): pass the per-(page, kv
    head) f32 absmax scales as ``k_scale``/``v_scale`` [max_pages, nkv];
    each prefix-phase step then streams the int8 page tile plus its
    (1, 1) scale and dequantizes inside the f32 accumulation.

    Explicit `block_q`/`block_s` override the `fit_blocks` choice (they
    must divide sb); a block_s that is not a whole number of pages
    still computes correctly but breaks the page-granular streaming
    contract — TPU102 lint flags it via the registered constraint.
    """
    b, sb, nh, dh = q.shape
    nkv, page = key_cache.shape[1], key_cache.shape[2]
    w_pre = prefix_tables.shape[1]
    if nh % nkv:
        raise ValueError(f"Hq {nh} not a multiple of Hkv {nkv}")
    if sb % page:
        raise ValueError(
            f"suffix bucket {sb} is not a whole number of {page}-token "
            "KV pages; use the masked-softmax fallback for this shape")
    if w_pre < 1:
        raise ValueError("prefix_tables must be at least one page wide "
                         "(pad with the scratch page and prefix_lens 0)")
    quant = key_cache.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError(
            "int8 KV pools need their per-(page, kv head) k_scale / "
            "v_scale arrays — a quantized pool without scales decodes "
            "garbage (TPU103 lints this)")
    if not quant and (k_scale is not None or v_scale is not None):
        raise ValueError("k_scale/v_scale only apply to int8 KV pools")
    group = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    fit_q, fit_s = fit_blocks(sb, page, group, dh,
                              kv_itemsize=1 if quant else 2)
    block_q = fit_q if block_q is None else block_q
    block_s = fit_s if block_s is None else block_s
    if sb % block_q or sb % block_s:
        raise ValueError(f"blocks ({block_q}, {block_s}) must divide "
                         f"the suffix bucket {sb}")
    if suffix_lens is None:
        suffix_lens = jnp.full((b,), sb, jnp.int32)
    nq = sb // block_q
    n_suf = sb // block_s
    bqg = block_q * group
    # free row-major collapses — refs stay rank-3 (Mosaic cannot
    # shape-cast higher-rank blocks, see decode_attention's paged GQA):
    # q/out [b*nkv*nq, block_q*group, dh]; suffix k/v
    # [b*nkv*n_suf, block_s, dh]; pools [max_pages*nkv, page, dh] with
    # page selection tbl[b, j]*nkv + h
    qg = jnp.transpose(q.reshape(b, sb, nkv, group, dh),
                       (0, 2, 1, 3, 4)).reshape(b * nkv * nq, bqg, dh)
    ks = jnp.transpose(k_suf, (0, 2, 1, 3)).reshape(
        b * nkv * n_suf, block_s, dh)
    vs = jnp.transpose(v_suf, (0, 2, 1, 3)).reshape(
        b * nkv * n_suf, block_s, dh)
    kp = key_cache.reshape(key_cache.shape[0] * nkv, page, dh)
    vp = value_cache.reshape(value_cache.shape[0] * nkv, page, dh)

    def q_map(b_, h, qi, j, tbl, plens, slens):
        return ((b_ * nkv + h) * nq + qi, 0, 0)

    def pool_map(b_, h, qi, j, tbl, plens, slens):
        # pad pages — and the whole suffix phase — pin to the row's
        # last valid page, so the pipeline never DMAs a block the body
        # will skip (plen 0 pins to table column 0)
        jp = jnp.minimum(j, jnp.maximum(plens[b_] // page - 1, 0))
        return (tbl[b_, jp] * nkv + h, 0, 0)

    def suf_map(b_, h, qi, j, tbl, plens, slens):
        # prefix phase pins at block 0; blocks beyond this q tile's
        # causal reach — or past the row's real suffix — pin at the
        # last block the body will actually run, so skipped blocks are
        # never DMA'd (the short-suffix regime this kernel targets)
        js = jnp.clip(j - w_pre, 0, n_suf - 1)
        js = jnp.minimum(js, (qi * block_q + block_q - 1) // block_s)
        js = jnp.minimum(js, jnp.maximum((slens[b_] - 1) // block_s, 0))
        return ((b_ * nkv + h) * n_suf + js, 0, 0)

    pool_specs = [pl.BlockSpec((1, page, dh), pool_map),
                  pl.BlockSpec((1, page, dh), pool_map)]
    pool_operands = [kp, vp]
    if quant:
        # the [pages*nkv, 1, 1] scale tile rides the same pinned page
        # row (same index map) as the int8 pool tile it dequantizes
        pool_specs += [pl.BlockSpec((1, 1, 1), pool_map),
                       pl.BlockSpec((1, 1, 1), pool_map)]
        pool_operands += [k_scale.astype(jnp.float32).reshape(-1, 1, 1),
                          v_scale.astype(jnp.float32).reshape(-1, 1, 1)]
        kernel = functools.partial(
            _prefix_prefill_q8_kernel, page=page, block_q=block_q,
            block_s=block_s, group=group, w_pre=w_pre, scale=scale)
    else:
        kernel = functools.partial(
            _prefix_prefill_kernel, page=page, block_q=block_q,
            block_s=block_s, group=group, w_pre=w_pre, scale=scale)
    out = pl.pallas_call(
        kernel,
        name=(CONSTRAINT_Q8 if quant else CONSTRAINT).name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nkv, nq, w_pre + n_suf),
            in_specs=[pl.BlockSpec((1, bqg, dh), q_map)] + pool_specs + [
                pl.BlockSpec((1, block_s, dh), suf_map),
                pl.BlockSpec((1, block_s, dh), suf_map),
            ],
            out_specs=pl.BlockSpec((1, bqg, dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((bqg, 128), jnp.float32),
                pltpu.VMEM((bqg, 128), jnp.float32),
                pltpu.VMEM((bqg, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * nkv * nq, bqg, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=not _on_tpu(),
    )(prefix_tables.astype(jnp.int32), prefix_lens.astype(jnp.int32),
      suffix_lens.astype(jnp.int32), qg, *pool_operands, ks, vs)
    out = out.reshape(b, nkv, sb, group, dh)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, sb, nh, dh)
