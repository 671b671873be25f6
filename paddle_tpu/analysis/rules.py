"""Built-in lint rules: TPU correctness/perf hazards visible in a jaxpr.

Rule catalog (see analysis/README.md for the long-form docs):

  TPU101 tile-alignment       matmul operand dims vs the dtype tile
  TPU102 kernel-constraints   pallas_call shapes vs the declared
                              KernelConstraint registry in kernels/
  TPU103 kv-cache-dtype       KV-cache pools streamed in f32 (2x the
                              bf16 bytes on the bandwidth-bound decode
                              path), or an int8 pool consumed without
                              its absmax scale operands
  TPU105 fusion-miss          a scan/while body lowering to more
                              distinct small-output Pallas/dot launches
                              than the fusion budget (dispatch-bound
                              decode steps)
  TPU201 recompile-risk       weak-typed python scalars baked into the
                              graph as literals (every new value retraces)
  TPU202 const-bloat          large arrays captured as compile-time
                              constants (recompile + HBM duplication)
  TPU301 dtype-promotion      silent bf16→f32 upcasts feeding compute
  TPU401 collectives          dead/duplicate collectives; psum over axes
                              not in the declared mesh
  TPU501 host-sync            host callbacks inside traced code (ERROR
                              when inside a scan/while hot loop)
  TPU601 ckpt-in-jit          checkpoint saves / block_until_ready
                              smuggled into a jitted region via a host
                              callback (the save serializes the device)
  TPU602 trace-in-jit         trace/metrics emitters (span, instant,
                              record_event, perfetto export) compiled
                              into a jitted program via a host callback
                              (a host round-trip per execution; the
                              observability recorder raises the same
                              way at trace time)

Custom rules: subclass `Rule`, decorate with `@register_rule`, and pass
the id in `rules=` (or nothing — registered rules run by default).

The fusion-boundary sensitivity of all of these is the subject of
"Operator Fusion in XLA: Analysis and Evaluation" (PAPERS.md); the tile
numbers come from the Pallas TPU tiling contract ((8|16|32) x 128 by
dtype) that "Ragged Paged Attention" §2 works around at the kernel level.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional, Type

import numpy as np

from ..kernels.constraints import (
    LANE, constraint_for_kernel_fn, min_tile, missing_scale_finding,
    tensor_operands,
)
from .diagnostics import Diagnostic, Severity
from .graph import EqnCtx, Graph

RULES: Dict[str, Type["Rule"]] = {}


def register_rule(cls: Type["Rule"]) -> Type["Rule"]:
    """Class decorator: adds the rule to the default pipeline set."""
    RULES[cls.id] = cls
    return cls


class Rule:
    """Base lint rule. Subclasses set `id`/`name`/`default_severity` and
    implement `check(graph)` yielding Diagnostics. `self.severity` is
    the effective severity (pipeline applies per-run overrides)."""

    id: str = "TPU000"
    name: str = "base"
    description: str = ""
    default_severity: Severity = Severity.WARNING

    def __init__(self, severity: Optional[Severity] = None, **config):
        self.severity = self.default_severity if severity is None \
            else severity
        self.config = config

    def diag(self, message: str, where: str = "",
             hint: Optional[str] = None,
             severity: Optional[Severity] = None) -> Diagnostic:
        return Diagnostic(rule=self.id,
                          severity=self.severity if severity is None
                          else severity,
                          message=message, where=where, hint=hint)

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# TPU101: MXU tile alignment of matmuls
# ---------------------------------------------------------------------------

@register_rule
class TileAlignmentRule(Rule):
    """dot_general operands whose dims are not multiples of the
    dtype-dependent TPU tile ((8|16|32) sublanes x 128 lanes). The MXU
    pads such operands; a 100-wide contraction runs at 100/128 of the
    paid FLOPs — invisible in profiles because the padding is inside the
    fusion."""

    id = "TPU101"
    name = "tile-alignment"
    default_severity = Severity.WARNING

    # dims this small are scalar-ish glue (loss reductions etc.), not
    # MXU work worth flagging
    MIN_DIM = 8

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        # dedupe: a stacked model repeats the same misaligned matmul per
        # layer — report each unique (role, size, shapes) once + count
        found: Dict[tuple, list] = {}
        for ctx in graph.eqns():
            if ctx.primitive != "dot_general":
                continue
            lhs, rhs = ctx.eqn.invars[0], ctx.eqn.invars[1]
            l_aval, r_aval = lhs.aval, rhs.aval
            (l_contract, r_contract), (l_batch, r_batch) = \
                ctx.params["dimension_numbers"]
            sub, lane = min_tile(l_aval.dtype)
            checks = []  # (role, size, multiple)
            for d in range(len(l_aval.shape)):
                if d in l_batch:
                    continue
                size = l_aval.shape[d]
                if d in l_contract:
                    checks.append(("lhs contracting", size, lane))
                else:
                    checks.append(("lhs non-contracting", size, sub))
            for d in range(len(r_aval.shape)):
                if d in r_batch:
                    continue
                size = r_aval.shape[d]
                if d in r_contract:
                    checks.append(("rhs contracting", size, lane))
                else:
                    checks.append(("rhs non-contracting", size, lane))
            for role, size, multiple in checks:
                if size >= self.MIN_DIM and size % multiple:
                    key = (role, size, multiple, str(l_aval.dtype),
                           tuple(l_aval.shape), tuple(r_aval.shape))
                    found.setdefault(key, []).append(ctx.path)
        for (role, size, multiple, dtype, ls, rs), paths in found.items():
            sites = "" if len(paths) == 1 else f" ({len(paths)} sites)"
            yield self.diag(
                f"{role} dim {size} is not a multiple of the "
                f"{multiple}-wide tile for {dtype} "
                f"(lhs {ls} x rhs {rs}){sites}",
                where=paths[0],
                hint=f"pad to {-(-size // multiple) * multiple} "
                     "or fold the ragged dim into the batch")


# ---------------------------------------------------------------------------
# TPU102: pallas_call shapes vs the kernel constraint registry
# ---------------------------------------------------------------------------

@register_rule
class KernelConstraintRule(Rule):
    """pallas_call equations checked against the `KernelConstraint`
    registry that kernels/ declares — the kernels' own block constants
    are the single source of truth, so this can never drift from the
    implementation."""

    id = "TPU102"
    name = "kernel-constraints"
    default_severity = Severity.ERROR

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        found: Dict[tuple, list] = {}
        hints: Dict[tuple, Optional[str]] = {}
        for ctx in graph.eqns():
            if ctx.primitive != "pallas_call":
                continue
            kernel_name, kernel_src = _pallas_kernel_name(ctx.eqn)
            constraint = constraint_for_kernel_fn(kernel_name, kernel_src)
            if constraint is None:
                continue
            shapes = [tuple(v.aval.shape) for v in ctx.eqn.invars]
            dtypes = [str(v.aval.dtype) for v in ctx.eqn.invars]
            for violation in constraint.check(shapes, dtypes):
                sev = None
                if isinstance(violation, tuple):
                    sev_name, violation = violation
                    sev = Severity[sev_name.upper()]
                key = (constraint.name, kernel_name, violation, sev)
                found.setdefault(key, []).append(ctx.path)
                hints[key] = constraint.note or None
        for (cname, kname, violation, sev), paths in found.items():
            sites = "" if len(paths) == 1 else f" ({len(paths)} sites)"
            yield self.diag(
                f"{cname} ({kname}): {violation}{sites}",
                where=paths[0], hint=hints[(cname, kname, violation, sev)],
                severity=sev)


def _pallas_kernel_name(eqn):
    """(name, "name at file:line") of a pallas_call's kernel — the
    kernel jaxpr's debug info: the call's `name=` where it passed one
    (every kernel in `kernels/` passes its registry name), else the
    kernel function's name (functools.partial wrappers are already
    unwrapped there). The file is the kernel function's either way."""
    info = eqn.params["jaxpr"].debug_info
    return str(info.func_name), str(info.func_src_info)


# ---------------------------------------------------------------------------
# TPU103: KV-cache pool dtype hygiene
# ---------------------------------------------------------------------------

def _kv_pool_findings(shapes, dtypes):
    """(severity, message) findings for one KV-streaming pallas_call's
    operand shapes/dtypes — module-level so tests can probe the shape
    logic directly. The rank>=3 operand tail is q followed by the
    streamed caches (the layout every registered KV kernel shares);
    the scale-presence check is the SAME
    `kernels.constraints.missing_scale_finding` the q8 kernel checkers
    run, so lint and kernels can never disagree about the layout."""
    arrs = tensor_operands(shapes, dtypes)
    if len(arrs) < 3:
        return []
    out = []
    pools = arrs[1:]
    n_f32 = sum(1 for s, d in pools if d == "float32")
    if n_f32 >= 2:
        sz = max(int(np.prod(s)) for s, d in pools if d == "float32")
        out.append(("warning",
                    f"KV cache pools streamed in float32 ({n_f32} "
                    f"operands, largest {sz} elements): decode / "
                    "prefix-prefill are bandwidth-bound, so f32 pools "
                    "pay 2x the bf16 bytes (4x int8) every step"))
    finding = missing_scale_finding(shapes, dtypes)
    if finding is not None:
        out.append(finding)
    return out


@register_rule
class KVCacheDtypeRule(Rule):
    """KV-cache pool dtype hygiene at the streaming kernels (the paged
    decode / prefix-prefill pallas calls registered in the
    KernelConstraint registry):

    - pools streamed in f32: the serving hot loops are HBM-bandwidth
      bound on KV bytes, so an f32 pool silently doubles the bf16 cost
      (quadruples int8) of EVERY decode step — serve bf16, or int8 via
      FLAGS_kv_cache_dtype=int8;
    - an int8 (quantized) pool consumed without its f32 scale operands:
      symmetric-absmax values without their scales are garbage.
    """

    id = "TPU103"
    name = "kv-cache-dtype"
    default_severity = Severity.WARNING
    KV_KERNELS = ("decode_attention", "prefix_prefill")

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        found: Dict[tuple, list] = {}
        for ctx in graph.eqns():
            if ctx.primitive != "pallas_call":
                continue
            kernel_name, kernel_src = _pallas_kernel_name(ctx.eqn)
            constraint = constraint_for_kernel_fn(kernel_name, kernel_src)
            if constraint is None \
                    or not constraint.name.startswith(self.KV_KERNELS):
                continue
            shapes = [tuple(v.aval.shape) for v in ctx.eqn.invars]
            dtypes = [str(v.aval.dtype) for v in ctx.eqn.invars]
            for sev_name, msg in _kv_pool_findings(shapes, dtypes):
                key = (kernel_name, msg, Severity[sev_name.upper()])
                found.setdefault(key, []).append(ctx.path)
        for (kname, msg, sev), paths in found.items():
            sites = "" if len(paths) == 1 else f" ({len(paths)} sites)"
            yield self.diag(
                f"{kname}: {msg}{sites}", where=paths[0],
                hint="allocate serving KV pools in bfloat16, or int8 + "
                     "scales via FLAGS_kv_cache_dtype=int8 "
                     "(PADDLE_TPU_KV_CACHE_DTYPE)",
                severity=sev)


# ---------------------------------------------------------------------------
# TPU105: fusion-miss — dispatch-bound loop bodies
# ---------------------------------------------------------------------------

@register_rule
class FusionMissRule(Rule):
    """A jitted hot loop (scan/while — the decode-step shape) whose body
    lowers to many DISTINCT kernel launches (pallas_call / dot_general)
    with only small intermediates between them is dispatch-bound, not
    compute-bound: each launch pays fixed issue overhead and the tiny
    [B, 1, H] tensors round-trip through HBM between launches (OPBENCH:
    decode_attention 0.21 ms inside a 1.9 ms decode step). Distinctness
    is by (primitive, operand/result shapes), so a 32-layer stack of
    identical layers counts its per-layer shapes once — the number this
    rule reports is the per-iteration fusion-boundary count.

    Config: `max_kernels` (default 6) — the distinct-call budget;
    `small_bytes` (default 1 MiB) — calls whose every result is under
    this are counted (bigger results mean the launch does real
    bandwidth work and is not a fusion miss)."""

    id = "TPU105"
    name = "fusion-miss"
    default_severity = Severity.WARNING
    MAX_KERNELS = 6
    SMALL_BYTES = 1 << 20

    @property
    def KERNEL_PRIMS(self):
        # THE kernel-launch inventory lives in roofline.py
        # (KERNEL_LAUNCH_PRIMS) — one walker/prim-set shared by this
        # rule, the OPBENCH kernels_per_step counter, and the roofline
        # launch-overhead term. Lazy: roofline.py subclasses Rule, so
        # it imports this module at load time.
        from .roofline import KERNEL_LAUNCH_PRIMS

        return KERNEL_LAUNCH_PRIMS

    @staticmethod
    def _loop_key(path: str) -> Optional[str]:
        """The enclosing loop's path prefix ("main/.../scan[jaxpr]"),
        None when the eqn is not inside a scan/while body."""
        parts = path.split("/")
        for i in range(len(parts) - 1, -1, -1):
            if parts[i].startswith(("scan[", "while[")):
                return "/".join(parts[:i + 1])
        return None

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        max_kernels = self.config.get("max_kernels", self.MAX_KERNELS)
        small = self.config.get("small_bytes", self.SMALL_BYTES)
        # loop path -> {distinct signature -> first ctx}
        loops: Dict[str, Dict[tuple, EqnCtx]] = {}
        totals: Dict[str, int] = {}
        for ctx in graph.eqns():
            if not ctx.in_loop or ctx.primitive not in self.KERNEL_PRIMS:
                continue
            out_bytes = max(
                (int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                 for v in ctx.eqn.outvars), default=0)
            if out_bytes >= small:
                continue
            key = self._loop_key(ctx.path)
            if key is None:
                continue
            sig = (ctx.primitive,
                   tuple(tuple(v.aval.shape) for v in ctx.eqn.invars),
                   tuple(tuple(v.aval.shape) for v in ctx.eqn.outvars))
            loops.setdefault(key, {}).setdefault(sig, ctx)
            totals[key] = totals.get(key, 0) + 1
        for key, sigs in loops.items():
            n = len(sigs)
            if n <= max_kernels:
                continue
            n_pallas = sum(1 for s in sigs if s[0] == "pallas_call")
            first = next(iter(sigs.values()))
            yield self.diag(
                f"loop body lowers to {n} distinct small-output kernel "
                f"launches ({n_pallas} pallas, {n - n_pallas} dot; "
                f"{totals[key]} total sites) — more than the "
                f"{max_kernels}-launch fusion budget: per-iteration "
                "dispatch and HBM round-trips between tiny ops dominate",
                where=first.path,
                hint="fuse neighbouring small ops into one kernel, or "
                     "give each launch more work (serving decode: more "
                     "slots per engine)")


# ---------------------------------------------------------------------------
# TPU201: weak-typed scalars -> recompilation risk
# ---------------------------------------------------------------------------

@register_rule
class RecompileRiskRule(Rule):
    """Python scalars captured into the graph trace as weakly-typed 0-d
    literals. Under jit each new VALUE is a new cache key: a loss scale
    or step count threaded as a plain float retraces (and recompiles)
    every time it changes. Shape-dependent python branches have the same
    signature — the branch outcome is frozen into the trace."""

    id = "TPU201"
    name = "recompile-risk"
    default_severity = Severity.WARNING

    # literals consumed by these primitives are structural (slicing
    # bounds, pad values, axis sizes), not data the user threads through
    STRUCTURAL = frozenset({
        "slice", "dynamic_slice", "dynamic_update_slice", "pad", "iota",
        "broadcast_in_dim", "reshape", "gather", "scatter", "concatenate",
        "rev", "transpose", "squeeze", "reduce_sum", "reduce_max",
        "reduce_min", "convert_element_type", "expand_dims",
    })
    # values overwhelmingly used as fixed algebraic identities
    BENIGN_VALUES = (0, 1, -1, 2, 0.5, -0.5, 1e-6, 1e-5, 1e-12)
    MAX_REPORTS = 8

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        # When the tracer recorded the python-scalar call arguments we
        # hunt for exactly those values among the captured literals — a
        # closure constant (rope theta, eps) is stable across calls, but
        # an ARGUMENT baked in as a literal means every new value is a
        # fresh trace. Literals are stored cast to the consuming dtype
        # (weak types are erased at jaxpr level), so the comparison
        # casts the argument the same way. The generic branch below the
        # argument match serves graphs built WITHOUT the tracer
        # (Graph(closed_jaxpr) + Pipeline.run), where scalar_args is
        # None and no argument information exists.
        arg_vals = graph.scalar_args
        seen_vals = set()
        n = 0
        for lit, ctx in graph.scalar_literals():
            if ctx.primitive in self.STRUCTURAL:
                continue
            try:
                val = np.asarray(lit.val).item()
            except Exception:
                continue
            if arg_vals is not None:
                lit_is_float = np.issubdtype(lit.val.dtype, np.floating)
                label = None
                for a, lbl in arg_vals:
                    # a FLOAT argument must not match an INT literal:
                    # the cast truncates (2.5 -> 2) and would mislabel
                    # an unrelated constant. An int argument stored as
                    # a float literal is exact and must still match.
                    if isinstance(a, float) and not lit_is_float:
                        continue
                    try:
                        if np.asarray(
                                a, dtype=lit.val.dtype).item() == val:
                            label = lbl
                            break
                    except (TypeError, ValueError, OverflowError):
                        continue
                if label is None or val in seen_vals:
                    continue
                seen_vals.add(val)
                yield self.diag(
                    f"python scalar argument {label} (= {val!r}) is "
                    f"baked into `{ctx.primitive}` as a trace constant; "
                    "every new value retraces and recompiles",
                    where=ctx.path,
                    hint="pass it as a jnp array (jnp.asarray(x)) so it "
                         "becomes a device input, or mark it static on "
                         "purpose")
                continue
            if val in self.BENIGN_VALUES or val in seen_vals:
                continue
            seen_vals.add(val)
            n += 1
            if n > self.MAX_REPORTS:
                yield self.diag(
                    "more scalar captures elided "
                    f"(first {self.MAX_REPORTS} shown)", where=graph.name)
                return
            yield self.diag(
                f"python scalar {val!r} is baked into `{ctx.primitive}` "
                "as a trace constant; a different value at the next "
                "call retraces and recompiles",
                where=ctx.path,
                hint="pass it as a jnp array argument (or mark it static "
                     "on purpose)")


# ---------------------------------------------------------------------------
# TPU202: large captured constants
# ---------------------------------------------------------------------------

@register_rule
class ConstBloatRule(Rule):
    """Arrays captured from the python closure are burned into the
    executable: they duplicate in HBM per compilation and defeat donation.
    Model weights threaded as closure constants (instead of arguments)
    are the classic cause."""

    id = "TPU202"
    name = "const-bloat"
    default_severity = Severity.INFO
    THRESHOLD_BYTES = 1 << 20  # 1 MiB

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        threshold = self.config.get("threshold_bytes",
                                    self.THRESHOLD_BYTES)
        total = 0
        worst = None
        for var, val in graph.captured_consts():
            nbytes = int(np.prod(var.aval.shape)) * var.aval.dtype.itemsize
            total += nbytes
            if worst is None or nbytes > worst[0]:
                worst = (nbytes, tuple(var.aval.shape),
                         str(var.aval.dtype))
        if total >= threshold and worst is not None:
            yield self.diag(
                f"{total / (1 << 20):.1f} MiB of arrays captured as "
                f"compile-time constants (largest: {worst[1]} "
                f"{worst[2]}, {worst[0] / (1 << 20):.1f} MiB)",
                where=graph.name,
                hint="thread weights/buffers as function arguments so "
                     "XLA can donate and share them")


# ---------------------------------------------------------------------------
# TPU301: silent dtype promotion
# ---------------------------------------------------------------------------

@register_rule
class DtypePromotionRule(Rule):
    """f32 upcasts inside bf16 compute paths. Two shapes:

    - a `convert_element_type` bf16→f32 whose result feeds elementwise
      compute or further converts: the tensor silently doubles its HBM
      traffic (jnp type promotion from a stray f32 operand is the usual
      source);
    - a `dot_general` with one bf16 and one f32 operand: the MXU runs it
      at the f32 rate — 8x slower than the bf16 path the author thought
      they wrote.

    Deliberate fp32 accumulation (`preferred_element_type`) does not
    trip this rule: it never materialises a converted operand."""

    id = "TPU301"
    name = "dtype-promotion"
    default_severity = Severity.WARNING

    LOW = ("bfloat16", "float16")
    # consumers for which an upcast is deliberate numerics, not drift
    SINK_OK = frozenset({
        "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
        "argmax", "argmin", "reduce_precision", "stop_gradient",
        "convert_element_type", "custom_jvp_call", "custom_vjp_call",
        "pallas_call", "erf_inv", "cumsum", "cumlogsumexp", "rsqrt",
    })
    MAX_REPORTS = 8

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        # the report cap applies to the (noisy) upcast findings only —
        # mixed-precision matmuls are always reported in full
        n = 0
        elided = 0
        for ctx in graph.eqns():
            if ctx.primitive == "dot_general":
                l, r = (str(v.aval.dtype) for v in ctx.eqn.invars[:2])
                if {l, r} & set(self.LOW) and "float32" in (l, r):
                    yield self.diag(
                        f"mixed-precision matmul {l} x {r}: the low-"
                        "precision operand is upcast and the MXU runs "
                        "at the f32 rate",
                        where=ctx.path,
                        hint="cast both operands to bfloat16 and use "
                             "preferred_element_type=float32 for the "
                             "accumulator")
                continue
            if ctx.primitive != "convert_element_type":
                continue
            src = str(ctx.eqn.invars[0].aval.dtype)
            dst = str(ctx.params.get("new_dtype"))
            if src not in self.LOW or dst != "float32":
                continue
            out_var = ctx.eqn.outvars[0]
            compute = [c for c in graph.consumers(out_var)
                       if c.primitive not in self.SINK_OK]
            if not compute:
                continue
            n += 1
            if n > self.MAX_REPORTS:
                elided += 1
                continue
            ops = sorted({c.primitive for c in compute})
            yield self.diag(
                f"{src}→float32 upcast of {tuple(out_var.aval.shape)} "
                f"feeds compute ({', '.join(ops[:4])}); the path pays "
                "f32 bandwidth from here on",
                where=ctx.path,
                hint="check for a stray f32 operand promoting the whole "
                     "expression; cast it down once at the source")
        if elided:
            yield self.diag(
                f"{elided} more upcast finding(s) elided "
                f"(first {self.MAX_REPORTS} shown)", where=graph.name)


# ---------------------------------------------------------------------------
# TPU401: collective hygiene
# ---------------------------------------------------------------------------

@register_rule
class CollectiveRule(Rule):
    """Four checks over collective equations (psum/all_gather/
    all_to_all/ppermute/reduce_scatter):

    - dead: the collective's result is never consumed — it still pays
      full ICI latency because XLA cannot DCE effectful comms it kept;
    - duplicate: two identical collectives over the same operand+axes
      (fold into one);
    - unknown axis: the axis name is not in the mesh axes the caller
      declared via `mesh_axes=` (skipped when not declared);
    - unquantized large payload: a collective moving more than
      `max_collective_bytes` (config; default 0 = off — see below) of
      floating-point data per equation. EQuARX (PAPERS.md) shows
      block-quantized int8 collectives inside XLA recover most of that
      wire time at negligible numerics cost — an absmax-int8 payload +
      f32 scale sidecar is the exact scheme the int8 paged KV pools
      already use. First customer: the tensor-parallel serving decode
      path's per-layer o-proj activation all-gather (FLAGS_serving_mp)
      — small at decode (b x 1 x H), but the same rule watches prefill
      all-gathers and dp gradient psums, where payloads are MBs.
      int8/int32 payloads (already-quantized or index traffic) never
      fire. Note scans AMPLIFY the cost: the size check compares the
      AMPLIFIED payload (bytes x scan trip count, via the shared
      `analysis/comms.py` inventory), and in-loop findings report at
      WARNING even when a top-level one would be INFO. OFF unless
      `max_collective_bytes=` is set explicitly: in the default
      pipeline TPU803 (quantizable-collective, default 1 MiB) owns
      the size check — two rules reporting the same site with the
      same hint at the same threshold would double every finding.

    The collective primitive list and the float-payload byte math live
    in `analysis/comms.py` (the bytes-on-wire pass) — ONE inventory
    serves this rule and TPU801/802/803.
    """

    id = "TPU401"
    name = "collectives"
    default_severity = Severity.WARNING

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        # lazy import: comms.py subclasses Rule, so it imports this
        # module at load time — the shared inventory resolves at check
        from . import comms as _comms

        mesh_axes = self.config.get("mesh_axes")
        # no default: TPU803 carries the default-threshold size check
        # (same inventory, same hint) — this knob re-arms the legacy
        # TPU401 channel at an explicit threshold
        max_bytes = self.config.get("max_collective_bytes", 0)
        # unquantized large payloads (EQuARX candidates) ride the
        # bytes-on-wire inventory so scan amplification is counted —
        # a per-layer collective inside a decode chunk pays per
        # iteration, and comparing one occurrence under-reported it
        if max_bytes:
            for ev in _comms.audit_graph(graph).collectives:
                payload = ev.float_payload_bytes
                amplified = ev.total_float_payload_bytes
                if not payload or amplified <= max_bytes:
                    continue
                if ev.in_loop:
                    amp = (f" x {ev.count} iterations = {amplified} "
                           f"bytes" if ev.count > 1 else "")
                    desc = (f"per iteration inside a loop body"
                            f"{amp} (> {max_bytes})")
                else:
                    desc = f"(> {max_bytes}) per call"
                # loop bodies AMPLIFY the cost — those escalate to the
                # rule's severity; a one-shot top-level collective is
                # an INFO-grade EQuARX candidate
                yield self.diag(
                    f"{ev.kind} over {ev.axes} moves {payload} "
                    f"bytes of float payload " + desc,
                    where=ev.path,
                    severity=None if ev.in_loop else Severity.INFO,
                    hint="quantize the payload (absmax int8 + f32 "
                         "scale sidecar, EQuARX-style — the int8 "
                         "KV pools' exact scheme) or shrink it; "
                         "raise max_collective_bytes= if this "
                         "size is intended")
        seen: Dict[tuple, EqnCtx] = {}
        for ctx in graph.eqns():
            if ctx.primitive not in _comms.COLLECTIVE_PRIMS:
                continue
            axes = _comms.collective_axes(ctx.eqn)
            # unknown axis
            if mesh_axes is not None:
                for a in axes:
                    if a not in mesh_axes:
                        yield self.diag(
                            f"{ctx.primitive} over axis {a!r} which is "
                            f"not in the mesh axes {tuple(mesh_axes)}",
                            where=ctx.path,
                            hint="collectives outside any mesh axis "
                                 "fail at run time or silently no-op",
                            severity=Severity.ERROR)
            # dead result
            if all(graph.use_count(v) == 0 for v in ctx.eqn.outvars):
                yield self.diag(
                    f"result of {ctx.primitive} over {axes} is never "
                    "used (dead collective still pays ICI latency)",
                    where=ctx.path,
                    hint="delete it, or consume its result")
                continue
            # duplicate
            key = (ctx.primitive, axes,
                   tuple(id(v) for v in ctx.eqn.invars))
            prev = seen.get(key)
            if prev is not None:
                yield self.diag(
                    f"duplicate {ctx.primitive} over {axes} on the same "
                    f"operand (first at {prev.path})",
                    where=ctx.path,
                    hint="reuse the first result; each copy is a full "
                         "ICI round")
            else:
                seen[key] = ctx


# ---------------------------------------------------------------------------
# TPU501: host sync inside traced code
# ---------------------------------------------------------------------------

@register_rule
class HostSyncRule(Rule):
    """Host callbacks (`io_callback`, `pure_callback`, `debug_callback`
    / jax.debug.print) compiled into the program stall the TPU on a
    host round-trip. Inside a scan/while hot loop that is a per-step
    barrier — ERROR; elsewhere a WARNING."""

    id = "TPU501"
    name = "host-sync"
    default_severity = Severity.WARNING

    CALLBACKS = frozenset({
        "io_callback", "pure_callback", "debug_callback", "debug_print",
        "python_callback", "outside_call",
    })

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        for ctx in graph.eqns():
            if ctx.primitive not in self.CALLBACKS:
                continue
            if ctx.in_loop:
                yield self.diag(
                    f"host callback `{ctx.primitive}` inside a traced "
                    "loop body: the device blocks on the host every "
                    "iteration",
                    where=ctx.path,
                    hint="hoist it out of the loop, or accumulate on "
                         "device and read back once",
                    severity=Severity.ERROR)
            else:
                yield self.diag(
                    f"host callback `{ctx.primitive}` compiled into the "
                    "program (host round-trip at every execution)",
                    where=ctx.path,
                    hint="drop debug prints from production traces")


# ---------------------------------------------------------------------------
# TPU601: checkpoint I/O / host barriers inside a jitted region
# ---------------------------------------------------------------------------

@register_rule
class CheckpointInJitRule(Rule):
    """Checkpoint saves (or explicit `jax.block_until_ready` barriers)
    wrapped into a jitted program through a host callback. TPU501 flags
    callbacks generically; THIS pattern is worse and deserves its own
    id: a checkpoint write is seconds of host I/O, and inside a jit it
    serializes the device for the whole write — the async-save design
    (`resilience/checkpoint.py`: snapshot at the step boundary, write
    on a background thread) exists precisely so this never happens.

    Detection is by callback identity: the callback's function name /
    module (jax stores it in the eqn params) matching save/checkpoint/
    serialize/block_until_ready. Calling `resilience.checkpoint.save`
    directly under trace does not reach the jaxpr at all — it raises at
    trace time with a message pointing here."""

    id = "TPU601"
    name = "ckpt-in-jit"
    default_severity = Severity.ERROR

    CALLBACKS = HostSyncRule.CALLBACKS
    import re as _re
    # matched against the callback's bare __name__ (or, when no name is
    # recoverable, its repr) — see _callback_identity
    # (?:\b|_) around `save` so snake_case names (save_weights,
    # shard_save) match — underscores are word chars, \b alone misses
    PATTERN = _re.compile(
        r"block_until_ready|checkpoint|ckpt|(?:\b|_)save(?:\b|_)"
        r"|serialize|state_dict", _re.IGNORECASE)

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        for ctx in graph.eqns():
            if ctx.primitive not in self.CALLBACKS:
                continue
            ident, match_target = _callback_identity(ctx.eqn)
            if not self.PATTERN.search(match_target):
                continue
            yield self.diag(
                f"host callback `{ident}` looks like checkpoint/"
                "serialization I/O compiled into the jitted program: "
                "the device stalls for the entire write"
                + (" EVERY loop iteration" if ctx.in_loop else ""),
                where=ctx.path,
                hint="checkpoint at step boundaries on the host; use "
                     "resilience.CheckpointManager.save(blocking=False) "
                     "so the step never waits on storage")


# ---------------------------------------------------------------------------
# TPU602: trace/metrics emitters inside a jitted region
# ---------------------------------------------------------------------------

@register_rule
class TraceEmitterInJitRule(Rule):
    """Observability emitters (`observability.trace` spans/instants,
    `record_event`, metric observes, chrome-trace exporters) wrapped
    into a jitted program through a host callback. Same failure shape
    as TPU601 but a different budget: a trace emit is microseconds, so
    it hides in profiles — yet inside a compiled program it is a host
    round-trip serialized into EVERY execution (and inside a scan,
    every iteration), precisely the per-step stall the bounded
    host-side recorder exists to avoid. Tracing belongs on the host
    BETWEEN dispatches.

    Detection is the TPU601 callback-identity mechanism
    (`_callback_identity`: the callback's bare ``__name__``). The
    dynamic half of the guard lives in `observability.trace`, which
    raises `TraceUnderJitError` when a span/instant is emitted at
    trace time — this rule catches the emitters that reach the jaxpr
    as explicit `pure_callback`/`io_callback` wrappers instead."""

    id = "TPU602"
    name = "trace-in-jit"
    default_severity = Severity.ERROR

    CALLBACKS = HostSyncRule.CALLBACKS
    import re as _re
    # bare-__name__ matching, (?:\b|_) around the short tokens so
    # snake_case emitters (emit_span, trace_step, record_instant)
    # match; 'log_metrics'-style benign logging stays TPU501's
    # business — only names that identify a TRACE/SPAN emitter fire
    PATTERN = _re.compile(
        r"(?:\b|_)spans?(?:\b|_)|(?:\b|_)traces?(?:\b|_)"
        r"|(?:\b|_)instant(?:\b|_)|(?:\b|_)tracer(?:\b|_)"
        r"|record_event|emit_event|perfetto|observability"
        r"|chrome_trac", _re.IGNORECASE)

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        for ctx in graph.eqns():
            if ctx.primitive not in self.CALLBACKS:
                continue
            ident, match_target = _callback_identity(ctx.eqn)
            if not self.PATTERN.search(match_target):
                continue
            yield self.diag(
                f"host callback `{ident}` looks like a trace/metrics "
                "emitter compiled into the jitted program: a host "
                "round-trip serializes the device every execution"
                + (" EVERY loop iteration" if ctx.in_loop else ""),
                where=ctx.path,
                hint="emit spans on the host between dispatches; the "
                     "observability recorder raises TraceUnderJitError "
                     "at trace time for exactly this reason")


def _callback_identity(eqn) -> tuple:
    """(display, match_target) for a callback eqn's python function.
    The match target is the bare __name__ only — matching the module
    path or qualname would flag every benign callback merely DEFINED in
    a checkpoint-related module or test class."""
    cb = eqn.params.get("callback")
    for attr in ("callback_func", "func", "callback", "__wrapped__"):
        inner = getattr(cb, attr, None)
        if inner is not None:
            cb = inner
    name = getattr(cb, "__name__", None)
    if name:
        mod = getattr(cb, "__module__", "") or ""
        display = getattr(cb, "__qualname__", None) or name
        if mod:
            display = f"{mod}.{display}"
        return display, str(name)
    rep = repr(cb) if cb is not None else repr(eqn.params)
    return rep, rep


def rule_config_for(rule_id: str, config: Dict) -> Dict:
    """Split a mixed rule_config into THIS rule's knobs: unprefixed
    keys go to every rule (legacy behaviour — rules read only the keys
    they know), and `TPUxxx.key` keys route to rule TPUxxx alone, so
    `{'TPU401.max_collective_bytes': 65536,
    'TPU702.hbm_budget_bytes': 2 << 30}` tunes two rules from one dict
    (the CLI's repeatable `--rule-config KEY=VALUE` builds exactly
    this)."""
    out = {k: v for k, v in config.items() if "." not in k}
    prefix = rule_id + "."
    for k, v in config.items():
        if k.startswith(prefix):
            out[k[len(prefix):]] = v
    return out


def default_rules(severity_overrides: Optional[Dict[str, Severity]] = None,
                  **config) -> List[Rule]:
    """Instantiate every registered rule, applying per-rule severity
    overrides ({'TPU501': Severity.ERROR} or {'TPU202': None} to
    disable) and routing `TPUxxx.`-prefixed config keys to their
    rule."""
    overrides = severity_overrides or {}
    out = []
    for rule_id, cls in sorted(RULES.items()):
        if rule_id in overrides and overrides[rule_id] is None:
            continue
        out.append(cls(severity=overrides.get(rule_id),
                       **rule_config_for(rule_id, config)))
    return out
