"""Declarative tile/block constraints for the Pallas kernel pack.

Single source of truth shared by two consumers:

- the kernels themselves read the named constants (``BLOCK_Q`` etc. live in
  each kernel module and are registered here) instead of scattering magic
  numbers through block-spec math;
- ``paddle_tpu.analysis`` reads the registry to lint traced graphs: a
  ``pallas_call`` equation whose kernel function matches a registered
  constraint gets its operand shapes checked against the declared blocks
  *before* the program ever reaches Mosaic.

Hardware facts (see /opt guides and "Ragged Paged Attention"'s tiling
discussion): every VMEM tile is (sublane x 128 lanes) with the sublane
count set by dtype width — fp32 packs 8 rows per tile, bf16 16, int8/fp8
32. A dimension that is not a multiple of its tile is silently padded in
VMEM and wastes MXU/VPU issue slots.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

# minor-most (lane) dimension of every TPU vector register / VMEM tile
LANE = 128

# shared scoped-VMEM budget the streaming kernels size their blocks
# against: pairs of k+v blocks must double-buffer inside scoped VMEM, so
# keep a safety margin under the ~16 MB budget (measured: h=32, block
# 512, d=128 OOMs scoped vmem by 48 KB at max_seq 2048 without it)
VMEM_BUDGET_BYTES = 12 << 20


def vmem_row_cap(row_bytes: int, *, n_buffers: int = 4,
                 reserve_bytes: int = 0,
                 budget: int = VMEM_BUDGET_BYTES) -> int:
    """Rows of `row_bytes` bytes that fit `n_buffers`-way buffered under
    the scoped-VMEM budget (minus `reserve_bytes` of fixed kernel
    state) — the cap side of `fit_vmem_block` for callers with their own
    granularity rule (e.g. whole-page multiples)."""
    return max(1, (budget - reserve_bytes) // (n_buffers * row_bytes))


def fit_vmem_block(block: int, extent: int, row_bytes: int, *,
                   n_buffers: int = 4, reserve_bytes: int = 0,
                   budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest divisor of `extent` that is <= the requested `block` AND
    keeps `n_buffers` resident copies of a [bs, row_bytes] tile under
    the scoped-VMEM budget — the one block-fitting rule every streaming
    kernel shares (decode attention, prefix prefill, flash fast path).

    `row_bytes` is bytes per block ROW (trailing dims x element size),
    which is how the int8 paths halve their footprint relative to bf16:
    pass the pool dtype's itemsize, not a hardcoded 2. `n_buffers`
    defaults to 4 (2 operands x 2 double-buffered copies).
    `reserve_bytes` carves out fixed VMEM the kernel also holds (scale
    rows, scratch). `row_bytes=0` disables the cap (pure
    largest-divisor clamp)."""
    if row_bytes > 0:
        cap = vmem_row_cap(row_bytes, n_buffers=n_buffers,
                           reserve_bytes=reserve_bytes, budget=budget)
    else:
        cap = extent
    bs = max(1, min(block, extent, cap))
    while extent % bs:
        bs -= 1
    return bs

def vmem_block_candidates(extent: int, row_bytes: int, *,
                          n_buffers: int = 4, reserve_bytes: int = 0,
                          budget: int = VMEM_BUDGET_BYTES,
                          max_candidates: int = 0) -> list:
    """Every distinct block size `fit_vmem_block` can return for this
    `extent` as the requested block sweeps upward: the divisors of
    `extent` that keep `n_buffers` resident [bs, row_bytes] copies
    under the scoped-VMEM budget, ascending. This is the kernel-side
    block axis the static autotuner (analysis/tuner.py) enumerates —
    candidates come from the SAME cap rule the kernels size against,
    so a tuned block can never be one `fit_vmem_block` would clamp.
    `max_candidates` > 0 keeps only the largest that many (larger
    blocks amortize grid overhead; the small tail is rarely worth
    scoring). `row_bytes=0` disables the cap (all divisors)."""
    if extent < 1:
        return []
    if row_bytes > 0:
        cap = vmem_row_cap(row_bytes, n_buffers=n_buffers,
                           reserve_bytes=reserve_bytes, budget=budget)
    else:
        cap = extent
    out = [d for d in range(1, extent + 1)
           if extent % d == 0 and d <= cap]
    if not out:
        out = [fit_vmem_block(extent, extent, row_bytes,
                              n_buffers=n_buffers,
                              reserve_bytes=reserve_bytes, budget=budget)]
    if max_candidates > 0:
        out = out[-max_candidates:]
    return out


# dtype-name -> bytes per element, for the pure-shape roofline models
# (no numpy/jax in checker context by contract)
_ITEMSIZE: Dict[str, int] = {
    "int8": 1, "uint8": 1, "int4": 1, "uint4": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "float32": 4, "int32": 4, "uint32": 4,
    "float64": 8, "int64": 8, "uint64": 8,
}


def dtype_itemsize(name, default: int = 2) -> int:
    """Bytes per element of a dtype NAME string (pure lookup — the
    roofline models run under the same no-jax purity contract as the
    checkers)."""
    return _ITEMSIZE.get(str(name), default)


# second-minor (sublane) tile dimension by dtype
SUBLANE: Dict[str, int] = {
    "float32": 8,
    "bfloat16": 16,
    "float16": 16,
    "int8": 32,
    "uint8": 32,
    "int4": 32,
    "uint4": 32,
    "float8_e4m3fn": 32,
    "float8_e5m2": 32,
}


def min_tile(dtype) -> Tuple[int, int]:
    """(sublane, lane) minimum tile for `dtype`; unknown dtypes get the
    fp32 tile (the most permissive)."""
    return SUBLANE.get(str(np.dtype(dtype)), 8), LANE


def is_scale_operand(shape, dtype) -> bool:
    """Whether a pallas_call operand is an int8 pool's absmax scale
    sidecar: a small f32 array — rank <= 2, or the [pages*nkv, 1, 1]
    layout the q8 attention kernels stream (PR 22: the trailing (1, 1)
    is what lets a one-row block through the Mosaic lowering)."""
    return dtype == "float32" and (
        1 <= len(shape) <= 2 or tuple(shape[1:]) == (1, 1))


def tensor_operands(shapes, dtypes):
    """The (shape, dtype) pairs of a kernel's rank>=3 TENSOR operands —
    q and the streamed caches — scale sidecars excluded."""
    return [(s, d) for s, d in zip(shapes, dtypes)
            if len(s) >= 3 and not is_scale_operand(s, d)]


def missing_scale_finding(shapes, dtypes):
    """The ONE int8-pool-without-scales check (shared by the q8 kernel
    checkers in decode_attention/prefix_prefill and the TPU103 lint
    rule — a scale-layout change edits exactly here): quantized pools
    are the rank>=3 int8 operands, their absmax scales the
    `is_scale_operand` f32 ones; an int8 pool travelling with fewer than two
    scale operands (one each for K and V) is consumed scale-less.
    Returns a ("warning", message) finding or None."""
    n_pools = sum(1 for s, dt in zip(shapes, dtypes)
                  if len(s) >= 3 and dt == "int8")
    n_scales = sum(1 for s, dt in zip(shapes, dtypes)
                   if is_scale_operand(s, dt))
    if n_pools and n_scales < 2:
        return ("warning",
                f"{n_pools} int8 KV pool operand(s) but only "
                f"{n_scales} f32 scale operand(s): a quantized pool "
                "consumed without its per-(page, kv-head) absmax "
                "scales dequantizes to garbage")
    return None


@dataclasses.dataclass(frozen=True)
class KernelConstraint:
    """One kernel's declared TPU layout contract.

    `name` is the kernel's ONE name: every `pl.pallas_call` of the
    kernel passes it as `name=` (with a role suffix where one entry
    covers several calls: `flash_attention_bwd`), so it is the name
    of the operation in the compiled program and in a profiler's trace,
    and the `func_name` of the traced equation's kernel jaxpr.
    `kernel_fns` are the Pallas kernel *function* names this constraint
    covers, which is what a call without `name=` shows there. `blocks`
    are the named block-size constants the kernel tiles with.
    `checker(shapes, dtypes)` receives the pallas_call operand aval
    shapes/dtype-names and returns violations: plain strings (severity
    decided by the lint rule) or ("error"|"warning", message) pairs —
    "error" for shapes the kernel rejects outright, "warning" for silent
    perf hazards (padding, fallback routes). Checkers must be pure shape
    math (no jax calls) so the lint can run on CPU against any graph.

    `roofline(shapes, dtypes)` is the kernel's closed-form cost model
    for the static roofline auditor (analysis/roofline.py): a
    ``{"flops": int, "hbm_bytes": int}`` dict for one launch, or None
    when the shapes don't resolve (the auditor then falls back to its
    generic operand/result accounting). It lives HERE — next to the
    kernel whose streaming pattern it describes — so paged attention
    can count the pool PAGES its block table names rather than the
    whole gathered pool, and can never drift from the block math. Same
    purity contract as `checker`.
    """

    name: str
    kernel_fns: Tuple[str, ...]
    blocks: Dict[str, int]
    note: str = ""
    checker: Optional[
        Callable[[Sequence[Tuple[int, ...]], Sequence[str]], Sequence[str]]
    ] = None
    # source-file hint disambiguating generic kernel fn names (several
    # kernels use `_fwd_kernel`/`_kernel`): matched against the traced
    # pallas name_and_src_info string, e.g. "flash_attention.py"
    source: str = ""
    # optional roofline cost model (see class docstring)
    roofline: Optional[
        Callable[[Sequence[Tuple[int, ...]], Sequence[str]],
                 Optional[dict]]
    ] = None

    def check(self, shapes: Sequence[Tuple[int, ...]],
              dtypes: Sequence[str]) -> list:
        if self.checker is None:
            return []
        return list(self.checker(shapes, dtypes))


KERNEL_CONSTRAINTS: Dict[str, KernelConstraint] = {}
_BY_TRACED_NAME: Dict[str, KernelConstraint] = {}


def register_constraint(c: KernelConstraint) -> KernelConstraint:
    KERNEL_CONSTRAINTS[c.name] = c
    for fn in (c.name,) + tuple(c.kernel_fns):
        _BY_TRACED_NAME[fn] = c
    return c


def constraint_for_kernel_fn(fn_name: str,
                             src: str = "") -> Optional[KernelConstraint]:
    """Look up the constraint covering a traced `pallas_call`'s kernel
    name: the `name=` the call passed (the registry's own, or that
    plus a role suffix) or, without one, its kernel function's name.
    `src` is the full traced name-and-source string (when available) —
    constraints with a `source` hint only match when it appears there,
    so generic names like `_fwd_kernel` cannot cross-match kernels."""

    def source_ok(c: KernelConstraint) -> bool:
        return not c.source or not src or c.source in src

    c = _BY_TRACED_NAME.get(fn_name)
    if c is not None and source_ok(c):
        return c
    # prefix match: name_and_src_info may append wrapper suffixes
    for k, cand in _BY_TRACED_NAME.items():
        if fn_name.startswith(k) and source_ok(cand):
            return cand
    return None
