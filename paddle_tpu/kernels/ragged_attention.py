"""Ragged paged attention — ONE grid for mixed decode + prefill rows.

The unified-serving kernel (PAPERS.md: Ragged Paged Attention; ISSUE
14): every batch row is just ``(cached_len, new_len)`` — a decode row
is ``new_len=1``, a cold prefill row is ``new_len=prompt``, a CHUNKED
prefill row is ``new_len=chunk`` with ``cached_len`` pointing at the
chunks already committed — all streaming pages from the same paged
pools through the same online-softmax recurrence. This is the
generalization of `kernels/prefix_prefill.py` to per-row ragged q
lengths and ARBITRARY cached lengths:

- `prefix_prefill` required ``prefix_lens`` to be whole pages (its
  pin maps floor-divide); here ``cached_lens`` is token-granular — the
  last cached page may be partial (a decode row mid-page), masked by
  ``kpos < cached_len`` and pinned with CEIL page counts so the
  partial page is still streamed;
- ``new_lens`` plays `prefix_prefill`'s ``suffix_lens`` role per row:
  pad query rows are skipped, pinned out of the DMA stream, and emit
  exact ZEROS (the l==0 guard — a pad-row NaN would poison later
  layers' K/V pages through 0*NaN);
- the new-token window need not be a whole number of KV pages (the
  window K/V are fresh tensors, not pool pages — only the CACHED
  phase is page-granular).

The kernel BODY is shared with `prefix_prefill` (the masks already
read raw token counts); what changes is the index-map algebra around
it. bf16 + int8-scale pool variants, both registered as
`KernelConstraint`s with a roofline model; the jnp
`ragged_paged_attention_reference` is the exact oracle (and the
engine's fallback path under FLAGS_prefix_prefill_kernel=0).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .constraints import (KernelConstraint, LANE, fit_vmem_block,
                          missing_scale_finding, register_constraint,
                          vmem_row_cap)
from .decode_attention import _on_tpu
from .prefix_prefill import (_NEG_INF, _prefix_prefill_kernel,
                             _prefix_prefill_q8_kernel)

# default query block per (row, kv head, q tile) cell — rows inside a
# tile are (new-token position, head-in-group) pairs
BLOCK_Q = 128
# default kv block streamed per new-window step (fresh K/V, so page
# granularity is NOT required here — only the cached phase is paged)
BLOCK_N = 512


def fit_blocks(tn: int, group: int, dh: int, *, kv_itemsize: int = 2):
    """(block_q, block_n) for a new-token window of `tn` tokens: both
    are the largest divisors of `tn` under the shared VMEM cap
    (`constraints.fit_vmem_block`); int8 pools reserve scale-tile bytes
    exactly like `prefix_prefill.fit_blocks` — the cap only governs the
    CACHED phase's page stream, but a shared bound keeps both phases'
    tiles resident together."""
    bq = fit_vmem_block(BLOCK_Q, tn, group * dh * 2)
    reserve = 0 if kv_itemsize >= 2 else 4096
    cap = vmem_row_cap(dh * kv_itemsize, reserve_bytes=reserve)
    bn = fit_vmem_block(min(BLOCK_N, cap), tn, dh * 2)
    return bq, bn


def _ragged_attention_kernel(tbl_ref, clen_ref, nlen_ref, q_ref, kp_ref,
                             vp_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
                             acc_scr, *, page: int, block_q: int,
                             block_s: int, group: int, w_pre: int,
                             scale: float, window=None):
    """The `_prefix_prefill_kernel` grid verbatim — its masks already
    compare raw token counts (``kpos < cached_len`` handles a partial
    last page; ``new_lens`` is positionally `suffix_lens`), so the
    ragged generalization lives entirely in the WRAPPER's index maps
    (ceil page pinning). A distinct kernel name keeps the
    KernelConstraint registry's fn->constraint map unambiguous."""
    _prefix_prefill_kernel(tbl_ref, clen_ref, nlen_ref, q_ref, kp_ref,
                           vp_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
                           acc_scr, page=page, block_q=block_q,
                           block_s=block_s, group=group, w_pre=w_pre,
                           scale=scale, window=window)


def _ragged_attention_q8_kernel(tbl_ref, clen_ref, nlen_ref, q_ref,
                                kp_ref, vp_ref, ksc_ref, vsc_ref, ks_ref,
                                vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                                page: int, block_q: int, block_s: int,
                                group: int, w_pre: int, scale: float,
                                window=None):
    """int8-pool variant: each cached-phase step streams the int8
    (kv head, page) tile plus its (1, 1) f32 absmax scale (the
    `_prefix_prefill_q8_kernel` recurrence)."""
    _prefix_prefill_q8_kernel(tbl_ref, clen_ref, nlen_ref, q_ref, kp_ref,
                              vp_ref, ksc_ref, vsc_ref, ks_ref, vs_ref,
                              o_ref, m_scr, l_scr, acc_scr, page=page,
                              block_q=block_q, block_s=block_s,
                              group=group, w_pre=w_pre, scale=scale,
                              window=window)


def _check_ragged_attention_shapes(shapes, dtypes):
    """Checker for the ragged pallas call: rank-3 tail is q
    [b*nkv*nq, block_q*group, dh], pools [pages*nkv, page, dh], then
    the new-window k/v [b*nkv*n_new, block_n, dh]. Lane alignment of
    dh matters for every streamed tile; the cached phase is pinned at
    one page per step by construction (nothing sub-page to lint)."""
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
    return out


def _check_q8_ragged_attention_shapes(shapes, dtypes):
    out = list(_check_ragged_attention_shapes(shapes, dtypes))
    finding = missing_scale_finding(shapes, dtypes)
    if finding is not None:
        out.append(finding)
    return out


# roofline: the prefix_prefill model applies VERBATIM — the operand
# layout is identical (q/pools/window-kv rank-3 tail + int table) and
# its product cancellation already prices exactly the POOL PAGES the
# table names plus the fresh window tiles. ONE model, two registries:
# a fix there propagates to the ragged constraints' predicted numbers.
from .prefix_prefill import \
    _prefix_prefill_roofline as _ragged_attention_roofline


CONSTRAINT = register_constraint(KernelConstraint(
    name="ragged_attention",
    kernel_fns=("_ragged_attention_kernel",),
    blocks={"block_q": BLOCK_Q, "block_n": BLOCK_N},
    note="unified mixed prefill+decode attention; every row is "
         "(cached_len, new_len) over the paged pools — decode is "
         "new_len=1, a prefill chunk is new_len=chunk; cached pages "
         "stream one (kv head, page) tile per step",
    checker=_check_ragged_attention_shapes,
    source="ragged_attention.py",
    roofline=_ragged_attention_roofline,
))

CONSTRAINT_Q8 = register_constraint(KernelConstraint(
    name="ragged_attention_q8",
    kernel_fns=("_ragged_attention_q8_kernel",),
    blocks={"block_q": BLOCK_Q, "block_n": BLOCK_N},
    note="int8-pool unified attention streams quantized (kv head, "
         "page) tiles + their f32 absmax scales through the same "
         "ragged (cached_len, new_len) grid",
    checker=_check_q8_ragged_attention_shapes,
    source="ragged_attention.py",
    roofline=_ragged_attention_roofline,
))


def ragged_paged_attention_reference(q: jax.Array, k_new: jax.Array,
                                     v_new: jax.Array,
                                     key_cache: jax.Array,
                                     value_cache: jax.Array,
                                     block_tables: jax.Array,
                                     cached_lens: jax.Array,
                                     new_lens: jax.Array | None = None, *,
                                     scale: float | None = None,
                                     k_scale: jax.Array | None = None,
                                     v_scale: jax.Array | None = None,
                                     window: int | None = None
                                     ) -> jax.Array:
    """The exact masked-softmax math the ragged kernel replaces — and
    the SINGLE source of it: the unified-step fallback path
    (FLAGS_prefix_prefill_kernel=0) calls this per layer, and the
    kernel parity tests / OPBENCH / chip_smoke oracle against it.

    q/k_new/v_new: [b, tn, nh/nkv, dh] rotary-applied new-token window;
    key_cache/value_cache: [max_pages, nkv, page, dh] pools (int8 with
    ``k_scale``/``v_scale`` [max_pages, nkv] dequantizes in f32 before
    the gather); block_tables: [b, w] page ids covering each row's
    cached tokens; cached_lens: [b] ARBITRARY token counts (the last
    page may be partial); new_lens: [b] true new-token counts in
    [0, tn] (None = all rows full). New token i of row b sits at
    absolute position cached_lens[b] + i: it sees every cached token
    and the window causally. Rows at window positions >= new_lens[b]
    return exact ZEROS (matching the kernel — finite, never NaN). Under
    a `window` position t sees positions (t - window, t] only.
    Returns [b, tn, nh, dh] in f32."""
    b, tn, nh, dh = q.shape
    nkv, page = key_cache.shape[1], key_cache.shape[2]
    P = block_tables.shape[1] * page
    group = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    if new_lens is None:
        new_lens = jnp.full((b,), tn, jnp.int32)
    quant = key_cache.dtype == jnp.int8
    gk = key_cache[block_tables]        # [b, w, nkv, page, dh]
    gv = value_cache[block_tables]
    if quant:
        if k_scale is None or v_scale is None:
            raise ValueError(
                "int8 KV pools need k_scale/v_scale (TPU103 lints a "
                "quantized pool consumed without its scales)")
        gk = gk.astype(jnp.float32) \
            * k_scale[block_tables][..., None, None]
        gv = gv.astype(jnp.float32) \
            * v_scale[block_tables][..., None, None]
    pk = jnp.transpose(gk, (0, 1, 3, 2, 4)).reshape(b, P, nkv, dh)
    pv = jnp.transpose(gv, (0, 1, 3, 2, 4)).reshape(b, P, nkv, dh)
    cat_dtype = jnp.float32 if quant else q.dtype
    keys = jnp.concatenate([pk.astype(cat_dtype),
                            k_new.astype(cat_dtype)], axis=1)
    vals = jnp.concatenate([pv.astype(cat_dtype),
                            v_new.astype(cat_dtype)], axis=1)
    # cached column t is real iff t < cached_lens[row] (token-granular:
    # a partial last page masks mid-page); window column j is visible
    # to window row i iff j <= i AND j < new_lens[row]
    cache_valid = jnp.arange(P)[None, :] < cached_lens[:, None]
    causal = jnp.arange(tn)[None, :] <= jnp.arange(tn)[:, None]
    win_valid = causal[None] \
        & (jnp.arange(tn)[None, None, :] < new_lens[:, None, None])
    mask = jnp.concatenate(
        [jnp.broadcast_to(cache_valid[:, None, :], (b, tn, P)),
         jnp.broadcast_to(win_valid, (b, tn, tn))], axis=-1)
    if window is not None:
        kpos = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(P)[None], (b, P)),
             cached_lens[:, None] + jnp.arange(tn)[None]], axis=1)
        qpos = cached_lens[:, None] + jnp.arange(tn)[None]
        mask &= qpos[:, :, None] - kpos[:, None, :] < window
    q5 = q.reshape(b, tn, nkv, group, dh)
    s = jnp.einsum("bsngd,btnd->bsngt", q5.astype(jnp.float32),
                   keys.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, :, None, None, :], s,
                  jnp.asarray(_NEG_INF, jnp.float32))
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bsngt,btnd->bsngd", probs,
                     vals.astype(jnp.float32))
    # pad window rows emit exact zeros, matching the kernel's l==0
    # guard (the _NEG_INF masking is finite, so probs are a garbage
    # uniform there, never NaN — zeroing makes them exact)
    live = jnp.arange(tn)[None, :] < new_lens[:, None]
    return jnp.where(live[:, :, None, None, None], ctx,
                     0.0).reshape(b, tn, nh, dh)


def ragged_paged_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, key_cache: jax.Array,
                           value_cache: jax.Array,
                           block_tables: jax.Array,
                           cached_lens: jax.Array,
                           new_lens: jax.Array | None = None, *,
                           scale: float | None = None,
                           block_q: int | None = None,
                           block_n: int | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           window: int | None = None) -> jax.Array:
    """Mixed decode/prefill attention over the paged pools in ONE grid.

    Each row attends its `cached_lens[b]` pooled tokens (streamed page
    by page via `block_tables[b]`) plus its own new-token window
    causally — decode rows are ``new_len=1``, prefill rows
    ``new_len=prompt``, chunked prefill rows ``new_len=chunk`` with
    ``cached_lens`` at the already-committed token count (ARBITRARY,
    unlike `prefix_prefill_attention`'s whole-page contract: the ceil
    pin maps stream the partial last page and `kpos < cached_len`
    masks inside it). Operand layout matches the reference above;
    returns [b, tn, nh, dh] in q's dtype, rows >= new_lens[b] exact
    zeros. int8 pools pass ``k_scale``/``v_scale`` [max_pages, nkv].

    Explicit `block_q`/`block_n` override `fit_blocks` (must divide
    tn). The window need not be page-granular — only the cached phase
    streams pool pages.

    `window` (a layer of sliding-window attention): position t sees
    positions (t - window, t]. The cached phase then starts at the page of
    the oldest position the row's first query sees and is only as long as
    a window's pages: a table column behind the window is never read (it
    may name a page since given to a later position — a ring)."""
    b, tn, nh, dh = q.shape
    nkv, page = key_cache.shape[1], key_cache.shape[2]
    w = block_tables.shape[1]
    if nh % nkv:
        raise ValueError(f"Hq {nh} not a multiple of Hkv {nkv}")
    if w < 1:
        raise ValueError("block_tables must be at least one page wide "
                         "(pad with the scratch page and cached_lens 0)")
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
    fit_q, fit_n = fit_blocks(tn, group, dh,
                              kv_itemsize=1 if quant else 2)
    block_q = fit_q if block_q is None else block_q
    block_n = fit_n if block_n is None else block_n
    if tn % block_q or tn % block_n:
        raise ValueError(f"blocks ({block_q}, {block_n}) must divide "
                         f"the new-token window {tn}")
    if new_lens is None:
        new_lens = jnp.full((b,), tn, jnp.int32)
    if window is not None:
        if window < 1:
            raise ValueError(f"window {window} must be at least 1")
        # the pages positions [c - window + 1, c) can lie in
        w = min(w, -(-max(window - 1, 1) // page) + 1)
    nq = tn // block_q
    n_new = tn // block_n
    bqg = block_q * group
    # rank-3 collapses, as in prefix_prefill (Mosaic cannot shape-cast
    # higher-rank blocks): q/out [b*nkv*nq, block_q*group, dh], window
    # k/v [b*nkv*n_new, block_n, dh], pools [max_pages*nkv, page, dh]
    qg = jnp.transpose(q.reshape(b, tn, nkv, group, dh),
                       (0, 2, 1, 3, 4)).reshape(b * nkv * nq, bqg, dh)
    kn = jnp.transpose(k_new, (0, 2, 1, 3)).reshape(
        b * nkv * n_new, block_n, dh)
    vn = jnp.transpose(v_new, (0, 2, 1, 3)).reshape(
        b * nkv * n_new, block_n, dh)
    kp = key_cache.reshape(key_cache.shape[0] * nkv, page, dh)
    vp = value_cache.reshape(value_cache.shape[0] * nkv, page, dh)

    def q_map(b_, h, qi, j, tbl, clens, nlens):
        return ((b_ * nkv + h) * nq + qi, 0, 0)

    def _last_page(clens, b_):
        # CEIL page count: a partial last page must still be streamed
        # (prefix_prefill floor-divides here — its lens are whole
        # pages; ragged cached_lens are token-granular)
        return jnp.maximum((clens[b_] + page - 1) // page - 1, 0)

    def pool_map(b_, h, qi, j, tbl, clens, nlens):
        # pad pages — and the whole window phase — pin to the row's
        # last valid page so skipped blocks are never DMA'd
        first = 0 if window is None \
            else jnp.maximum(clens[b_] - (window - 1), 0) // page
        jp = jnp.minimum(first + j, _last_page(clens, b_))
        return (tbl[b_, jp] * nkv + h, 0, 0)

    def win_map(b_, h, qi, j, tbl, clens, nlens):
        # cached phase pins at block 0; blocks beyond this q tile's
        # causal reach — or past the row's real window — pin at the
        # last block the body will run
        js = jnp.clip(j - w, 0, n_new - 1)
        js = jnp.minimum(js, (qi * block_q + block_q - 1) // block_n)
        js = jnp.minimum(js, jnp.maximum((nlens[b_] - 1) // block_n, 0))
        return ((b_ * nkv + h) * n_new + js, 0, 0)

    pool_specs = [pl.BlockSpec((1, page, dh), pool_map),
                  pl.BlockSpec((1, page, dh), pool_map)]
    pool_operands = [kp, vp]
    if quant:
        pool_specs += [pl.BlockSpec((1, 1, 1), pool_map),
                       pl.BlockSpec((1, 1, 1), pool_map)]
        pool_operands += [k_scale.astype(jnp.float32).reshape(-1, 1, 1),
                          v_scale.astype(jnp.float32).reshape(-1, 1, 1)]
        kernel = functools.partial(
            _ragged_attention_q8_kernel, page=page, block_q=block_q,
            block_s=block_n, group=group, w_pre=w, scale=scale,
            window=window)
    else:
        kernel = functools.partial(
            _ragged_attention_kernel, page=page, block_q=block_q,
            block_s=block_n, group=group, w_pre=w, scale=scale,
            window=window)
    out = pl.pallas_call(
        kernel,
        # a windowed layer's call has its own label in the device trace
        name=(CONSTRAINT_Q8 if quant else CONSTRAINT).name
        + ("_window" if window is not None else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nkv, nq, w + n_new),
            in_specs=[pl.BlockSpec((1, bqg, dh), q_map)] + pool_specs + [
                pl.BlockSpec((1, block_n, dh), win_map),
                pl.BlockSpec((1, block_n, dh), win_map),
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
    )(block_tables.astype(jnp.int32), cached_lens.astype(jnp.int32),
      new_lens.astype(jnp.int32), qg, *pool_operands, kn, vn)
    out = out.reshape(b, nkv, tn, group, dh)
    return jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(b, tn, nh, dh)
