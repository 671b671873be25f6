"""Static roofline auditor: jaxpr FLOPs/bytes pass -> predicted step
latency + MFU (ISSUE 13).

The third leg of pre-silicon auditing: `analysis/memory.py` bounds
bytes-RESIDENT, `analysis/comms.py` prices bytes-ON-WIRE, and this pass
prices COMPUTE TIME — every equation gets FLOPs and HBM traffic, and a
program gets a predicted step time, a bound class, and an MFU, against
the `analysis/device_specs.py` table ("Operator Fusion in XLA"
PAPERS.md does exactly this per-op intensity analysis to predict fusion
wins; the launch-overhead term is counted statically too).

- **FLOPs**: `dot_general` / `conv_general_dilated` contraction math
  (2·B·M·N·K), reductions count input elements, elementwise ops count
  output elements. Registered Pallas kernels (flash / decode / prefix
  attention) get closed-form models via the `KernelConstraint`
  registry's ``roofline`` field — so paged-attention streaming counts
  the POOL PAGES the block table names, not gathered full tensors.
- **HBM traffic, fusion-aware**: XLA fuses elementwise chains, so a
  naive operand+result sum over-counts the very dequant chains the
  int8 serving path lives on. The model: elementwise / view / convert
  equations are FUSIBLE (zero traffic; their operands' *materialized
  roots* flow through), while matmuls, kernels, reductions, sorts and
  slices MATERIALIZE — each materializing equation reads the
  deduplicated root buffers feeding its operand chains and writes its
  results. `w_int8 -> convert -> mul -> dot` therefore costs exactly
  one int8 weight read, which is the weight-read bound
  `bench_serving.py` measures against. In-place updates
  (dynamic_update_slice / scatter — the KV page commit) move only the
  update's bytes; gathers/slices move their RESULT's bytes (an
  embedding lookup reads B rows, not the table).
- **Loop amplification + per-chip math**: a scan body pays per
  iteration (``count = prod(enclosing scan lengths)``, exactly like
  the comms pass); inside `shard_map` every aval is the LOCAL shard's,
  so sharded eqns count 1/mp per chip by construction.
- **Predicted step time** =
  ``max(compute, bandwidth, wire) + launch_overhead x kernels_per_step``
  with wire time from the comms pass (ICI bytes / `ici_gbs`) and the
  launch term from the ONE kernel-launch walker (`KERNEL_LAUNCH_PRIMS`)
  shared with the OPBENCH `kernels_per_step` counter and TPU105.

Three rules ride the one (memoized per device row) pass:

  TPU901 bandwidth-bound-   WARNING: an amplified eqn in a hot loop
         in-loop            whose intensity sits below the device's
                            ridge point for >= `min_amplified_ms` of
                            bandwidth time — the fusion-candidate
                            feeder (quantized streams).
  TPU902 padding-waste      WARNING: the program spends more than
                            `min_fraction` of its padded MXU FLOPs on
                            (8|16|32)x128 tile padding — quantifying
                            what TPU101 only flags per site.
  TPU903 launch-overhead-   WARNING: predicted launch overhead is
         bound              >= `max_fraction` of the predicted step —
                            the static twin of TPU105.

Use it three ways::

    from paddle_tpu.analysis import roofline
    rep = roofline.audit_roofline(fn, *example_args, device="tpu-v5e")
    rep.predicted_step_ms;  rep.predicted_mfu;  rep.bound
    print(rep.format())

    eng.warm(...);  eng.audit_roofline()   # fleet report + gauges

    python -m paddle_tpu.analysis --roofline --format json
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from .device_specs import DEVICE_SPECS, DeviceSpec, get_spec
from .diagnostics import Diagnostic, Severity
from .graph import Graph
from .rules import Rule, register_rule

# ---------------------------------------------------------------------------
# THE kernel-launch inventory — one walker, three consumers: the OPBENCH
# kernels_per_step counter (bench.py delegates here), the TPU105
# fusion-miss budget, and this pass's launch-overhead term.
# ---------------------------------------------------------------------------

KERNEL_LAUNCH_PRIMS = frozenset({"pallas_call", "dot_general"})


def count_kernel_launches(jaxpr) -> int:
    """Kernel-launch count of ONE execution of a jaxpr: pallas_call +
    dot_general equations, sub-jaxprs included, UN-amplified (a scan
    body counts once). Kernel bodies are not separate launches, so
    pallas_call params are never descended."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in KERNEL_LAUNCH_PRIMS:
            n += 1
            continue
        for v in eqn.params.values():
            vals = v if isinstance(v, (tuple, list)) else (v,)
            for item in vals:
                sub = getattr(item, "jaxpr", item)
                if hasattr(sub, "eqns"):
                    n += count_kernel_launches(sub)
    return n


def count_step_kernels(step_fn, *args) -> int:
    """Trace + count in one call (the OPBENCH `kernels_per_step`
    entry point)."""
    import jax

    return count_kernel_launches(jax.make_jaxpr(step_fn)(*args).jaxpr)


# ---------------------------------------------------------------------------
# primitive classification (see module docstring for the fusion model)
# ---------------------------------------------------------------------------

# byte-preserving views / layout ops: fused bitcasts, zero traffic
_VIEW_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim", "transpose",
    "convert_element_type", "bitcast_convert_type", "copy",
    "stop_gradient", "rev",
})
# read/write only the RESULT's bytes (indexed reads)
_SLICE_PRIMS = frozenset({"gather", "slice", "dynamic_slice"})
# in-place updates: move only the update's bytes; the target buffer's
# storage flows through (the paged-KV commit contract)
_UPDATE_PRIMS = frozenset({
    "dynamic_update_slice", "scatter", "scatter-add", "scatter-mul",
    "scatter-min", "scatter-max",
})
_UPDATE_OPERAND_IDX = {"dynamic_update_slice": 1}   # scatter updates: 2
# reductions: FLOPs = input elements, result materializes
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
})
# other materializing ops (results too irregular to fuse)
_MATERIALIZE_PRIMS = frozenset({
    "sort", "top_k", "cumsum", "cumprod", "cumlogsumexp", "cummax",
    "cummin", "concatenate", "pad", "rng_bit_generator", "threefry2x32",
})
# pure generators: fused into their consumer, no operands to read
_GENERATOR_PRIMS = frozenset({"iota"})

_MATMUL_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 8
    return int(np.prod(shape, dtype=np.int64)) * itemsize


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64))


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _dot_flops(eqn) -> Tuple[int, int]:
    """(flops, padded_flops) of a dot_general: 2·B·M·N·K, and the same
    with M rounded to the dtype sublane tile and N/K to the 128-lane
    tile — the MXU pays the padded number (TPU101's per-dim check,
    aggregated to FLOPs)."""
    from ..kernels.constraints import min_tile

    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    B = int(np.prod([lhs.shape[d] for d in lb], dtype=np.int64)) \
        if lb else 1
    K = int(np.prod([lhs.shape[d] for d in lc], dtype=np.int64)) \
        if lc else 1
    M = int(np.prod([lhs.shape[d] for d in range(len(lhs.shape))
                     if d not in lc and d not in lb], dtype=np.int64))
    N = int(np.prod([rhs.shape[d] for d in range(len(rhs.shape))
                     if d not in rc and d not in rb], dtype=np.int64))
    sub, lane = min_tile(lhs.dtype)

    def up(x, m):
        return -(-x // m) * m if x else x

    flops = 2 * B * M * N * K
    padded = 2 * B * up(M, sub) * up(N, lane) * up(K, lane)
    return flops, max(padded, flops)


def _conv_flops(eqn) -> int:
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    per_out = int(np.prod(rhs.shape[1:], dtype=np.int64)) \
        if len(rhs.shape) > 1 else 1
    return 2 * _aval_elems(out) * per_out


def _kernel_roofline_model(eqn):
    """Closed-form (flops, hbm_bytes) for a registered Pallas kernel via
    the KernelConstraint registry's `roofline` field; None when the
    kernel has no model (default operand/result accounting applies)."""
    try:
        from ..kernels.constraints import constraint_for_kernel_fn
        from .rules import _pallas_kernel_name

        kernel_name, kernel_src = _pallas_kernel_name(eqn)
        constraint = constraint_for_kernel_fn(kernel_name, kernel_src)
        model = getattr(constraint, "roofline", None)
        if constraint is None or model is None:
            return None
        shapes = [tuple(getattr(v.aval, "shape", ()))
                  for v in eqn.invars]
        dtypes = [str(getattr(v.aval, "dtype", "?")) for v in eqn.invars]
        return model(shapes, dtypes)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# events + report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EqnCost:
    """Cost of ONE occurrence of one materializing equation; `count` is
    the loop amplification (product of enclosing scan lengths). Bytes
    are PER-CHIP (shard_map bodies carry local avals)."""

    path: str
    prim: str
    dtype: str              # compute dtype (matmul lhs / result)
    shape: tuple            # result shape
    flops: int
    hbm_bytes: int
    padded_flops: int       # >= flops; == flops off the MXU
    count: int
    in_loop: bool

    @property
    def total_flops(self) -> int:
        return self.flops * max(self.count, 1)

    @property
    def total_bytes(self) -> int:
        return self.hbm_bytes * max(self.count, 1)

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, FLOPs per HBM byte."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else \
            float("inf") if self.flops else 0.0

    def bandwidth_s(self, spec: DeviceSpec) -> float:
        return self.total_bytes / spec.hbm_gbs

    def compute_s(self, spec: DeviceSpec) -> float:
        return self.total_flops / spec.peak_for(self.dtype)

    def to_dict(self) -> dict:
        return {
            "path": self.path, "prim": self.prim, "dtype": self.dtype,
            "shape": list(self.shape), "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "padded_flops": self.padded_flops, "count": self.count,
            "total_flops": self.total_flops,
            "total_bytes": self.total_bytes,
            "intensity": round(self.intensity, 3)
            if self.intensity != float("inf") else None,
            "in_loop": self.in_loop,
        }


class RooflineReport:
    """Result of the FLOPs/bytes pass against one device row: the
    roofline terms, the bound class, predicted step time + MFU, and the
    per-eqn bottleneck breakdown."""

    def __init__(self, name: str, events: List[EqnCost],
                 spec: DeviceSpec, wire_bytes: int, launches: int,
                 mp: int, n_eqns: int):
        self.name = name
        self.events = events
        self.spec = spec
        self.wire_bytes = wire_bytes       # per chip, amplified (PR 11)
        self.kernel_launches = launches    # amplified launch count
        self.mp = mp
        self.n_eqns = n_eqns

    # -- totals --------------------------------------------------------
    @property
    def total_flops(self) -> int:
        return sum(e.total_flops for e in self.events)

    @property
    def total_hbm_bytes(self) -> int:
        return sum(e.total_bytes for e in self.events)

    def flops_by_dtype(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            if e.flops:
                out[e.dtype] = out.get(e.dtype, 0) + e.total_flops
        return out

    @property
    def padding_waste_flops(self) -> int:
        return sum((e.padded_flops - e.flops) * max(e.count, 1)
                   for e in self.events)

    @property
    def total_padded_flops(self) -> int:
        return sum(e.padded_flops * max(e.count, 1) for e in self.events)

    @property
    def padding_waste_fraction(self) -> float:
        padded = self.total_padded_flops
        return self.padding_waste_flops / padded if padded else 0.0

    # -- roofline terms ------------------------------------------------
    @property
    def compute_s(self) -> float:
        return sum(f / self.spec.peak_for(d)
                   for d, f in self.flops_by_dtype().items())

    @property
    def bandwidth_s(self) -> float:
        return self.total_hbm_bytes / self.spec.hbm_gbs

    @property
    def wire_s(self) -> float:
        return self.wire_bytes / self.spec.ici_gbs

    @property
    def launch_overhead_s(self) -> float:
        return self.kernel_launches * self.spec.launch_overhead_s

    @property
    def bound(self) -> str:
        """Which roofline term dominates: 'compute' | 'bandwidth' |
        'wire'. Launch overhead is additive, not a bound class — TPU903
        flags it when it dominates the sum."""
        terms = {"compute": self.compute_s,
                 "bandwidth": self.bandwidth_s, "wire": self.wire_s}
        return max(terms, key=terms.get)

    @property
    def predicted_step_s(self) -> float:
        return max(self.compute_s, self.bandwidth_s, self.wire_s) \
            + self.launch_overhead_s

    @property
    def predicted_step_ms(self) -> float:
        return self.predicted_step_s * 1e3

    @property
    def predicted_mfu(self) -> float:
        """Model FLOPs / (predicted time x peak at the dominant compute
        dtype) — the number `bench.py`/`bench_mfu.py` measure."""
        by_dtype = self.flops_by_dtype()
        if not by_dtype or self.predicted_step_s <= 0:
            return 0.0
        dominant = max(by_dtype, key=by_dtype.get)
        return self.total_flops / (self.predicted_step_s
                                   * self.spec.peak_for(dominant))

    def bottlenecks(self, top: int = 8) -> List[EqnCost]:
        """Costliest equations: ranked by each one's own roofline time
        (max of its compute/bandwidth terms, amplified)."""
        return sorted(
            self.events,
            key=lambda e: -max(e.compute_s(self.spec),
                               e.bandwidth_s(self.spec)))[:top]

    # -- output --------------------------------------------------------
    def to_dict(self, max_events: int = 16) -> dict:
        return {
            "target": self.name,
            "device": self.spec.name,
            "per_chip": True,
            "mp": self.mp,
            "n_eqns": self.n_eqns,
            "flops": self.total_flops,
            "flops_by_dtype": self.flops_by_dtype(),
            "hbm_bytes": self.total_hbm_bytes,
            "wire_bytes": self.wire_bytes,
            "kernel_launches": self.kernel_launches,
            "compute_ms": self.compute_s * 1e3,
            "bandwidth_ms": self.bandwidth_s * 1e3,
            "wire_ms": self.wire_s * 1e3,
            "launch_overhead_ms": self.launch_overhead_s * 1e3,
            "predicted_step_ms": self.predicted_step_ms,
            "predicted_mfu": round(self.predicted_mfu, 4),
            "bound": self.bound,
            "padding_waste_flops": self.padding_waste_flops,
            "padding_waste_fraction": round(self.padding_waste_fraction,
                                            4),
            "bottlenecks": [e.to_dict()
                            for e in self.bottlenecks(max_events)],
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent)

    def format(self, top: int = 8) -> str:
        lines = [
            f"roofline audit {self.name} on {self.spec.name}: "
            f"predicted {self.predicted_step_ms:.4f} ms per execution, "
            f"{self.bound}-bound, mfu {self.predicted_mfu:.3f} "
            f"(mp={self.mp}, {self.n_eqns} eqns)",
            f"  compute {self.compute_s * 1e3:.4f} ms "
            f"({self.total_flops / 1e9:.3f} GFLOP) | "
            f"bandwidth {self.bandwidth_s * 1e3:.4f} ms "
            f"({self.total_hbm_bytes / (1 << 20):.2f} MiB) | "
            f"wire {self.wire_s * 1e3:.4f} ms | "
            f"launch {self.launch_overhead_s * 1e3:.4f} ms "
            f"({self.kernel_launches} launches)",
        ]
        if self.padding_waste_flops:
            lines.append(
                f"  tile padding: "
                f"{self.padding_waste_fraction * 100:.1f}% of padded "
                f"MXU FLOPs ({self.padding_waste_flops / 1e6:.2f} "
                "MFLOP wasted)")
        ridge = self.spec.ridge_point("bfloat16")
        for e in self.bottlenecks(top):
            amp = f" x{e.count}" if e.count > 1 else ""
            inten = ("inf" if e.intensity == float("inf")
                     else f"{e.intensity:.1f}")
            side = "bw" if e.intensity < ridge else "compute"
            t = max(e.compute_s(self.spec), e.bandwidth_s(self.spec))
            lines.append(
                f"    {t * 1e3:9.4f} ms  {e.prim} {e.dtype}"
                f"{list(e.shape)}{amp}  intensity {inten} ({side})"
                f"  {e.path}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Root:
    """One materialized HBM buffer feeding an operand chain: a program
    input/const, or a materializing equation's result."""

    rid: int
    bytes: int


@dataclasses.dataclass
class _Val:
    """What the walker knows about a traced var: the materialized roots
    its value flows from, and whether its own definition materialized
    (a fused chain's program output still pays its write)."""

    roots: Tuple[_Root, ...]
    materialized: bool


class _RooflineAuditor:
    """One walk over a closed jaxpr (same inlined traversal family as
    memory.py/comms.py): per-eqn FLOPs + fusion-aware HBM bytes with
    scan amplification and shard_map-local (per-chip) avals."""

    def __init__(self, closed_jaxpr, name: str):
        self.closed = closed_jaxpr
        self.name = name
        self.events: List[EqnCost] = []
        self.launches = 0          # amplified
        self.mp = 1
        self.n_eqns = 0
        self._next_rid = 0

    # -- helpers -------------------------------------------------------
    def _root(self, nbytes: int) -> _Root:
        self._next_rid += 1
        return _Root(self._next_rid, int(nbytes))

    def _fresh(self, aval) -> _Val:
        return _Val((self._root(_aval_bytes(aval)),), True)

    def _read_bytes(self, vals: List[Optional[_Val]]) -> int:
        seen, total = set(), 0
        for val in vals:
            if val is None:
                continue
            for r in val.roots:
                if r.rid not in seen:
                    seen.add(r.rid)
                    total += r.bytes
        return total

    def _lookup(self, env, v) -> Optional[_Val]:
        if _is_literal(v):
            return None
        return env.get(v)

    # -- entry ---------------------------------------------------------
    def run(self) -> Tuple[List[EqnCost], int, int, int]:
        jaxpr = self.closed.jaxpr
        env: Dict[Any, _Val] = {}
        for v in jaxpr.constvars:
            env[v] = self._fresh(v.aval)
        for v in jaxpr.invars:
            env[v] = self._fresh(v.aval)
        self._walk(jaxpr, env, self.name, 1, False)
        # a program output produced by a fused chain still writes HBM
        out_bytes = 0
        for v in jaxpr.outvars:
            val = self._lookup(env, v)
            if val is not None and not val.materialized:
                out_bytes += _aval_bytes(v.aval)
        if out_bytes:
            self.events.append(EqnCost(
                path=f"{self.name}/<outputs>", prim="outputs", dtype="?",
                shape=(), flops=0, hbm_bytes=out_bytes, padded_flops=0,
                count=1, in_loop=False))
        return self.events, self.launches, self.mp, self.n_eqns

    # -- traversal -----------------------------------------------------
    def _walk(self, jaxpr, env: Dict[Any, _Val], path: str, trip: int,
              in_loop: bool):
        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            where = f"{path}/eqn[{i}]:{prim}"
            if prim == "jit":
                self._inline(eqn, eqn.params["jaxpr"], env, where, trip,
                             in_loop)
            elif prim in ("remat", "remat2", "checkpoint"):
                self._inline(eqn, eqn.params["jaxpr"], env, where, trip,
                             in_loop)
            elif prim == "scan":
                self._scan(eqn, env, where, trip)
            elif prim == "while":
                self._while(eqn, env, where, trip)
            elif prim == "cond":
                self._cond(eqn, env, where, trip, in_loop)
            elif prim == "shard_map":
                self._shard_map(eqn, env, where, trip, in_loop)
            elif prim == "pallas_call":
                self._leaf(eqn, env, where, trip, in_loop)
            else:
                # THE sub-jaxpr discovery helper lives in memory.py —
                # the three passes must agree on what they descend
                from .memory import _eqn_sub_jaxprs

                subs = _eqn_sub_jaxprs(eqn)
                if subs:
                    # custom_vjp/jvp and friends: inline the FIRST
                    # sub-jaxpr (the forward) — walking fwd+bwd would
                    # double-count the primal math
                    self._inline(eqn, subs[0], env, where, trip,
                                 in_loop)
                else:
                    self._leaf(eqn, env, where, trip, in_loop)

    def _bind_sub(self, jxp, in_vals):
        """Sub-jaxpr env: captured consts and unmatched invars become
        fresh roots (their outer-aval bytes), matched invars alias
        through."""
        sub_env: Dict[Any, _Val] = {}
        for cv in jxp.constvars:
            sub_env[cv] = self._fresh(cv.aval)
        for k, bv in enumerate(jxp.invars):
            val = in_vals[k] if k < len(in_vals) else None
            sub_env[bv] = val if val is not None else self._fresh(bv.aval)
        return sub_env

    def _inline(self, eqn, sub, env, where, trip, in_loop):
        jxp = getattr(sub, "jaxpr", sub)
        in_vals = [self._lookup(env, v) for v in eqn.invars]
        aligned = len(jxp.invars) == len(in_vals)
        sub_env = self._bind_sub(jxp, in_vals if aligned else [])
        name = eqn.params.get("name")
        tag = f"{where}[{name}]" if name else where
        self._walk(jxp, sub_env, tag, trip, in_loop)
        out_aligned = len(jxp.outvars) == len(eqn.outvars)
        for k, ov in enumerate(eqn.outvars):
            val = self._lookup(sub_env, jxp.outvars[k]) if out_aligned \
                else None
            env[ov] = val if val is not None else self._fresh(ov.aval)

    def _scan(self, eqn, env, where, trip):
        sub = eqn.params["jaxpr"]
        jxp = getattr(sub, "jaxpr", sub)
        length = int(eqn.params.get("length") or 1)
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        in_vals = [self._lookup(env, v) for v in eqn.invars]
        sub_env: Dict[Any, _Val] = {}
        for k, cv in enumerate(jxp.constvars):
            sub_env[cv] = self._fresh(cv.aval)
        for k, bv in enumerate(jxp.invars):
            if k < n_consts + n_carry and k < len(in_vals) \
                    and in_vals[k] is not None:
                # consts + carries: the operand buffer threads through
                # (its bytes are re-read per iteration by the body's
                # consumers — the weight-read-per-step accounting)
                sub_env[bv] = in_vals[k]
            else:
                # per-iteration xs slice: a fresh small buffer; slice
                # bytes x trip = the full stacked array, once
                sub_env[bv] = self._fresh(bv.aval)
        self._walk(jxp, sub_env, f"{where}[jaxpr]",
                   trip * max(length, 1), True)
        for k, ov in enumerate(eqn.outvars):
            if k < n_carry and k < len(jxp.outvars):
                val = self._lookup(sub_env, jxp.outvars[k])
                env[ov] = val if val is not None else self._fresh(ov.aval)
            else:
                env[ov] = self._fresh(ov.aval)

    def _while(self, eqn, env, where, trip):
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        in_vals = [self._lookup(env, v) for v in eqn.invars]
        carry = in_vals[cn + bn:]
        for key, ops in (("cond_jaxpr", in_vals[:cn] + carry),
                         ("body_jaxpr", in_vals[cn:cn + bn] + carry)):
            sub = eqn.params.get(key)
            if sub is None:
                continue
            jxp = getattr(sub, "jaxpr", sub)
            sub_env = self._bind_sub(jxp, ops)
            # no static trip count: events keep the outer count but are
            # marked in_loop (same contract as the comms pass)
            self._walk(jxp, sub_env, f"{where}[{key}]", trip, True)
        for ov in eqn.outvars:
            env[ov] = self._fresh(ov.aval)

    def _cond(self, eqn, env, where, trip, in_loop):
        # upper bound: every branch is walked (only one executes)
        in_vals = [self._lookup(env, v) for v in eqn.invars[1:]]
        for bi, sub in enumerate(eqn.params.get("branches") or ()):
            jxp = getattr(sub, "jaxpr", sub)
            sub_env = self._bind_sub(jxp, in_vals)
            self._walk(jxp, sub_env, f"{where}[branch{bi}]", trip,
                       in_loop)
        for ov in eqn.outvars:
            env[ov] = self._fresh(ov.aval)

    def _shard_map(self, eqn, env, where, trip, in_loop):
        sub = eqn.params["jaxpr"]
        jxp = getattr(sub, "jaxpr", sub)
        mesh = eqn.params.get("mesh")
        try:
            self.mp = max(self.mp, int(mesh.size))
        except Exception:
            pass
        sub_env: Dict[Any, _Val] = {}
        for bv in jxp.invars:
            # per-chip accounting: the body reads its LOCAL shard, so a
            # boundary operand becomes a fresh root of the body aval's
            # (local) bytes — sharded pools/params count 1/mp per chip,
            # replicated operands count whole
            sub_env[bv] = self._fresh(bv.aval)
        self._walk(jxp, sub_env, f"{where}[jaxpr]", trip, in_loop)
        for ov, bv in zip(eqn.outvars, jxp.outvars):
            val = self._lookup(sub_env, bv)
            env[ov] = val if val is not None else self._fresh(ov.aval)

    # -- leaves --------------------------------------------------------
    def _emit(self, where, prim, dtype, shape, flops, nbytes, padded,
              trip, in_loop):
        self.events.append(EqnCost(
            path=where, prim=prim, dtype=str(dtype), shape=tuple(shape),
            flops=int(flops), hbm_bytes=int(nbytes),
            padded_flops=int(max(padded, flops)), count=max(trip, 1),
            in_loop=in_loop))

    def _leaf(self, eqn, env, where, trip, in_loop):
        prim = eqn.primitive.name
        self.n_eqns += 1
        in_vals = [self._lookup(env, v) for v in eqn.invars]
        out_aval = eqn.outvars[0].aval if eqn.outvars else None
        out_bytes = sum(_aval_bytes(ov.aval) for ov in eqn.outvars)
        out_elems = sum(_aval_elems(ov.aval) for ov in eqn.outvars)

        if prim in KERNEL_LAUNCH_PRIMS:
            self.launches += max(trip, 1)

        if prim in _VIEW_PRIMS:
            # fused layout/convert: roots flow through untouched
            src = in_vals[0] if in_vals else None
            for ov in eqn.outvars:
                env[ov] = _Val(src.roots if src is not None else (),
                               False)
            return
        if prim in _GENERATOR_PRIMS:
            for ov in eqn.outvars:
                env[ov] = _Val((), False)
            return

        if prim == "dot_general":
            flops, padded = _dot_flops(eqn)
            nbytes = self._read_bytes(in_vals) + out_bytes
            dtype = getattr(eqn.invars[0].aval, "dtype", "?")
            self._emit(where, prim, dtype,
                       getattr(out_aval, "shape", ()), flops, nbytes,
                       padded, trip, in_loop)
        elif prim == "conv_general_dilated":
            flops = _conv_flops(eqn)
            nbytes = self._read_bytes(in_vals) + out_bytes
            dtype = getattr(eqn.invars[0].aval, "dtype", "?")
            self._emit(where, prim, dtype,
                       getattr(out_aval, "shape", ()), flops, nbytes,
                       flops, trip, in_loop)
        elif prim == "pallas_call":
            model = _kernel_roofline_model(eqn)
            if model is not None:
                flops = int(model.get("flops", 0))
                nbytes = int(model.get("hbm_bytes", 0))
            else:
                flops = 0
                nbytes = self._read_bytes(in_vals) + out_bytes
            # compute dtype = the LARGEST operand's (the streamed
            # pool/tensor) — the last operand would pick the f32 scale
            # rows on the int8 kernels and misprice the quantized path
            # at the f32 MXU rate
            biggest = max(eqn.invars,
                          key=lambda v: _aval_bytes(
                              getattr(v, "aval", None)), default=None)
            dtype = getattr(getattr(biggest, "aval", None), "dtype",
                            "?")
            self._emit(where, prim, dtype,
                       getattr(out_aval, "shape", ()), flops, nbytes,
                       flops, trip, in_loop)
        elif prim in _SLICE_PRIMS:
            self._emit(where, prim,
                       getattr(out_aval, "dtype", "?"),
                       getattr(out_aval, "shape", ()), 0, 2 * out_bytes,
                       0, trip, in_loop)
        elif prim in _UPDATE_PRIMS:
            idx = _UPDATE_OPERAND_IDX.get(prim, 2)
            upd = eqn.invars[idx].aval if idx < len(eqn.invars) \
                else out_aval
            ub = _aval_bytes(upd)
            self._emit(where, prim, getattr(upd, "dtype", "?"),
                       getattr(upd, "shape", ()), 0, 2 * ub, 0, trip,
                       in_loop)
            # the updated buffer's storage flows through (paged pools)
            src = in_vals[0] if in_vals else None
            for ov in eqn.outvars:
                env[ov] = _Val(src.roots if src is not None else (),
                               True) if src is not None \
                    else self._fresh(ov.aval)
            return
        elif prim in _REDUCE_PRIMS:
            in_elems = sum(_aval_elems(getattr(v, "aval", None))
                           for v in eqn.invars if not _is_literal(v))
            nbytes = self._read_bytes(in_vals) + out_bytes
            self._emit(where, prim, getattr(out_aval, "dtype", "?"),
                       getattr(out_aval, "shape", ()), in_elems, nbytes,
                       in_elems, trip, in_loop)
        elif prim in _MATERIALIZE_PRIMS:
            nbytes = self._read_bytes(in_vals) + out_bytes
            self._emit(where, prim, getattr(out_aval, "dtype", "?"),
                       getattr(out_aval, "shape", ()), out_elems,
                       nbytes, out_elems, trip, in_loop)
        else:
            # default: a fusible elementwise op — FLOPs count, traffic
            # rides the consumer (roots flow through)
            roots: List[_Root] = []
            seen = set()
            for val in in_vals:
                if val is None:
                    continue
                for r in val.roots:
                    if r.rid not in seen:
                        seen.add(r.rid)
                        roots.append(r)
            if out_elems:
                self._emit(where, prim, getattr(out_aval, "dtype", "?"),
                           getattr(out_aval, "shape", ()), out_elems, 0,
                           out_elems, trip, in_loop)
            for ov in eqn.outvars:
                env[ov] = _Val(tuple(roots), False)
            return
        for ov in eqn.outvars:
            env[ov] = self._fresh(ov.aval)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def audit_graph(graph: Graph, device=None) -> RooflineReport:
    """Run the roofline pass over an already-traced `Graph` against one
    device row (memoized per row — the three TPU90x rules share one
    pass; the FLOPs/bytes walk runs once and re-prices per device)."""
    spec = get_spec(device)
    cache = getattr(graph, "_roofline_reports", None)
    if cache is None:
        cache = graph._roofline_reports = {}
    # only REGISTERED rows cache by name — a caller-built DeviceSpec
    # sharing a row's name (a test overriding launch_overhead_s) must
    # not collide with the table row's cached report
    registered = DEVICE_SPECS.get(spec.name) is spec
    rep = cache.get(spec.name) if registered else None
    if rep is None:
        raw = getattr(graph, "_roofline_raw", None)
        if raw is None:
            raw = _RooflineAuditor(graph.closed_jaxpr, graph.name).run()
            graph._roofline_raw = raw
        events, launches, mp, n_eqns = raw
        from . import comms as _comms

        wire = _comms.audit_graph(graph).total_wire_bytes
        rep = RooflineReport(graph.name, events, spec, wire, launches,
                             mp, n_eqns)
        if registered:
            cache[spec.name] = rep
    return rep


def audit_roofline(fn, *args, device=None, name: Optional[str] = None,
                   **kwargs) -> RooflineReport:
    """Trace + audit in one call. Accepts jitted functions, plain
    callables, and framework `Layer`s / Tensor arguments (same
    dispatching tracer as the other auditors — nothing executes on
    device). `device` is a spec-table row name, a `DeviceSpec`, or None
    (detect live TPU, else the v5e baseline)."""
    from .memory import trace_auto

    return audit_graph(trace_auto(fn, *args, name=name, **kwargs),
                       device=device)


def resolve_audit_roofline(audit_roofline_param: Optional[bool]) -> bool:
    """Hook default resolution: an explicit True/False wins; None
    follows FLAGS_audit_roofline (PADDLE_TPU_AUDIT_ROOFLINE) OR the
    composable PADDLE_TPU_LINT switch — turning the linter on turns
    the roofline audit on with it."""
    if audit_roofline_param is not None:
        return bool(audit_roofline_param)
    from ..framework.flags import flag

    return bool(flag("audit_roofline")) or bool(flag("tpu_lint"))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

@register_rule
class BandwidthBoundLoopRule(Rule):
    """TPU901: an equation in a hot loop whose arithmetic intensity
    sits below the device's ridge point while its AMPLIFIED bandwidth
    time exceeds the budget — memory-bound work executed over and over,
    the direct feeder for fusion and for quantized streams (half the
    bytes, double the intensity).

    Config: `min_amplified_ms` (default 0.5 ms of amplified HBM time
    per program execution; 0 disables), `device` (spec row; default
    auto)."""

    id = "TPU901"
    name = "bandwidth-bound-in-loop"
    default_severity = Severity.WARNING
    MIN_AMPLIFIED_MS = 0.5
    MAX_REPORTS = 4

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        min_ms = float(self.config.get("min_amplified_ms",
                                       self.MIN_AMPLIFIED_MS) or 0)
        if min_ms <= 0:
            return
        rep = audit_graph(graph, self.config.get("device"))
        spec = rep.spec
        found = []
        for e in rep.events:
            if not (e.in_loop or e.count > 1) or not e.hbm_bytes:
                continue
            ridge = spec.ridge_point(e.dtype)
            if e.intensity >= ridge:
                continue
            bw_ms = e.bandwidth_s(spec) * 1e3
            if bw_ms < min_ms:
                continue
            found.append((bw_ms, ridge, e))
        found.sort(key=lambda x: -x[0])
        for bw_ms, ridge, e in found[:self.MAX_REPORTS]:
            amp = f" x {e.count} iterations" if e.count > 1 else ""
            yield self.diag(
                f"{e.prim} {e.dtype}{list(e.shape)} in a hot loop runs "
                f"at intensity {e.intensity:.1f} FLOP/byte — below the "
                f"{spec.name} ridge point {ridge:.0f} — and streams "
                f"{e.hbm_bytes} bytes{amp} = {bw_ms:.2f} ms of HBM "
                "time per execution",
                where=e.path,
                hint="fuse it into its neighbours, quantize the "
                     "streamed bytes (int8 pools/weights), or batch "
                     "wider to "
                     "raise intensity; raise TPU901.min_amplified_ms "
                     "if this stream is already at the roofline")
        if len(found) > self.MAX_REPORTS:
            yield self.diag(
                f"{len(found) - self.MAX_REPORTS} more bandwidth-bound "
                f"loop eqn(s) elided (first {self.MAX_REPORTS} shown)",
                where=graph.name)


@register_rule
class PaddingWasteRule(Rule):
    """TPU902: the program spends a meaningful fraction of its padded
    MXU FLOPs on (8|16|32)x128 tile padding. TPU101 flags each ragged
    matmul; this rule QUANTIFIES the aggregate bill — a b=1 decode
    matmul pads its 1-row operand to a full 8-row sublane tile and pays
    8x the issued FLOPs.

    Config: `min_fraction` (default 0.2 of the padded total),
    `min_waste_flops` (default 1e7 amplified — toys stay quiet)."""

    id = "TPU902"
    name = "padding-waste"
    default_severity = Severity.WARNING
    MIN_FRACTION = 0.2
    MIN_WASTE_FLOPS = 10_000_000

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        min_frac = float(self.config.get("min_fraction",
                                         self.MIN_FRACTION))
        min_waste = float(self.config.get("min_waste_flops",
                                          self.MIN_WASTE_FLOPS))
        rep = audit_graph(graph, self.config.get("device"))
        waste = rep.padding_waste_flops
        frac = rep.padding_waste_fraction
        if waste < min_waste or frac < min_frac:
            return
        worst = max(
            (e for e in rep.events if e.padded_flops > e.flops),
            key=lambda e: (e.padded_flops - e.flops) * e.count,
            default=None)
        detail = ""
        if worst is not None:
            detail = (f"; worst: {worst.prim} {worst.dtype}"
                      f"{list(worst.shape)} at {worst.path}")
        yield self.diag(
            f"{frac * 100:.0f}% of the program's padded MXU FLOPs "
            f"({waste / 1e6:.1f} MFLOP per execution) are spent on "
            f"tile padding{detail}",
            where=graph.name,
            hint="pad dims to the (8|16|32)x128 tile (TPU101 names "
                 "each site), fold ragged dims into the batch, or "
                 "batch wider; raise TPU902.min_fraction if the "
                 "padding is accepted")


@register_rule
class LaunchOverheadBoundRule(Rule):
    """TPU903: predicted kernel-launch overhead is a dominant fraction
    of the predicted step time — the step is dispatch-bound, not
    compute- or bandwidth-bound. The static twin of TPU105 (which
    counts distinct launches in loop bodies): fusing N launches
    into one recovers ~(N-1) x launch_overhead per step.

    Config: `max_fraction` (default 0.25), `min_overhead_ms` (default
    0.2 — microsecond-scale toy programs stay quiet), `device`."""

    id = "TPU903"
    name = "launch-overhead-bound"
    default_severity = Severity.WARNING
    MAX_FRACTION = 0.25
    MIN_OVERHEAD_MS = 0.2

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        max_frac = float(self.config.get("max_fraction",
                                         self.MAX_FRACTION))
        min_ms = float(self.config.get("min_overhead_ms",
                                       self.MIN_OVERHEAD_MS))
        rep = audit_graph(graph, self.config.get("device"))
        overhead_ms = rep.launch_overhead_s * 1e3
        step_ms = rep.predicted_step_ms
        if overhead_ms < min_ms or step_ms <= 0 \
                or overhead_ms < max_frac * step_ms:
            return
        yield self.diag(
            f"{rep.kernel_launches} kernel launches per execution cost "
            f"a predicted {overhead_ms:.2f} ms of dispatch — "
            f"{overhead_ms / step_ms * 100:.0f}% of the "
            f"{step_ms:.2f} ms predicted step on {rep.spec.name}",
            where=graph.name,
            hint="fuse neighbouring launches into one kernel, or give "
                 "each launch more work (serving decode: more slots "
                 "per engine); TPU105 names the loop bodies")
