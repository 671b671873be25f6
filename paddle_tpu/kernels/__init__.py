"""Pallas TPU kernel pack.

TPU-native counterpart of the reference's hand-written fused CUDA kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, fusion/ cutlass kernels,
incubate fused op family). Each kernel ships:
  - a Pallas TPU implementation (MXU/VMEM-tiled), used on TPU backends;
  - a jnp reference path (XLA-fusable) used on CPU and as the numerics oracle.
"""
from .constraints import (  # noqa: F401
    KERNEL_CONSTRAINTS, KernelConstraint, LANE, SUBLANE,
    VMEM_BUDGET_BYTES, constraint_for_kernel_fn, fit_vmem_block,
    min_tile, register_constraint, vmem_row_cap,
)
from .flash_attention import flash_attention_fwd, flash_attention  # noqa: F401
from .rms_norm import rms_norm as fused_rms_norm  # noqa: F401
from .rope import apply_rotary_emb  # noqa: F401

# importing the kernel modules populates KERNEL_CONSTRAINTS; decode,
# prefix-prefill, int4, rope, swiglu, the grouped matmul and the expert
# layer's row movements register theirs on import too
from . import decode_attention as _decode_attention  # noqa: F401
from . import int4_matmul as _int4_matmul  # noqa: F401
from .prefix_prefill import prefix_prefill_attention  # noqa: F401
from .ragged_attention import ragged_paged_attention  # noqa: F401
from . import swiglu as _swiglu  # noqa: F401
from . import grouped_matmul as _grouped_matmul  # noqa: F401
from . import moe_rows as _moe_rows  # noqa: F401
