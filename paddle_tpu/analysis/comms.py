"""Static communication auditor: jaxpr bytes-on-wire pass + per-chip
collective cost model (ISSUE 11).

The wire-side twin of `analysis/memory.py`: where the liveness pass
bounds bytes-RESIDENT, this pass inventories every communication
equation a program executes and bounds bytes-ON-WIRE per chip — the
number that decides whether a quantized collective (EQuARX, PAPERS.md),
a reduce-scatter rewrite, or prefill/decode disaggregation pays.

- **Inventory**: psum/psum2/pmax/pmin, all_gather/pgather,
  reduce_scatter, all_to_all, ppermute — plus IMPLICIT resharding at
  pjit / shard_map boundaries where a value's known sharding disagrees
  with the consumer's declared one (communication the author never
  wrote). The primitive list and the float-payload byte math live HERE,
  once; TPU401's collective-hygiene rule consumes the same inventory.
- **Cost model** (ring algorithms, per chip): all-reduce moves
  ``2*(n-1)/n * bytes``, all-gather / reduce-scatter / all-to-all move
  ``(n-1)/n`` of the full payload, ppermute one hop. Inside
  `shard_map` every aval is already the LOCAL shard's, so operand
  bytes are per-chip by construction; axis sizes resolve from the
  enclosing mesh.
- **Loop amplification**: a collective inside a `scan` body pays per
  iteration — its event carries ``count = prod(enclosing scan
  lengths)``, so "1 all-gather per layer x 32 layers x 16 steps" is
  first-class. `while` bodies have no static trip count: their events
  keep ``count`` as-is but are marked ``in_loop``.
- **Quantized-collective recognition** (ISSUE 15): the pass marks the
  packed int8 buffers `parallel/collectives.py` emits (the f32 scale
  sidecar rides bitcast-int8 INSIDE the payload since ISSUE 18, one
  collective per hop) — priced as ``quantized_wire_bytes`` /
  ``n_quantized_sites`` in the report. int8 payloads never fire
  TPU803 by design, so a site rewritten through
  `quantized_all_gather` / `quantized_psum` goes silent at the
  DEFAULT threshold.

Three rules ride the one (memoized) pass:

  TPU801 collective-in-loop  WARNING: one collective's AMPLIFIED wire
                             bytes per program execution exceed
                             `max_step_wire_bytes` (default 32 MiB);
                             the loop trip count is in the message.
  TPU802 implicit-reshard    WARNING: a pjit/shard_map boundary whose
                             in-sharding disagrees with the value's
                             known sharding — XLA inserts the
                             collective silently. `min_bytes`
                             (default 64 KiB) floors out scalars.
  TPU803 quantizable-        WARNING: a float-payload collective
         collective          moving >= `min_bytes` (default 1 MiB,
                             amplified) — the absmax-int8 + f32-scale
                             rewrite the int8 KV pools already prove
                             recovers most of the wire time (EQuARX).
                             The direct feeder for the ROADMAP
                             quantized-collectives item. int8/int32
                             payloads never fire.

Use it three ways::

    from paddle_tpu.analysis import comms
    rep = comms.audit_comms(fn, *example_args)
    rep.total_wire_bytes            # per chip, loop-amplified
    print(rep.format())

    eng.warm(...);  eng.audit_comms()    # fleet report over the cache
    # -> metrics()["comms_audit"], predicted_bytes_on_wire_per_token

    python -m paddle_tpu.analysis --comms --format json
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .diagnostics import Diagnostic, Severity
from .graph import Graph
from .rules import Rule, register_rule

# THE communication primitive inventory — shared with TPU401 (rules.py
# imports these lazily so eqn-name lists and byte math exist once).
# pbroadcast is shard_map replication bookkeeping, not a comm op.
ALL_REDUCE_PRIMS = frozenset({"psum", "psum2", "pmax", "pmin"})
GATHER_PRIMS = frozenset({"all_gather", "pgather"})
COLLECTIVE_PRIMS = ALL_REDUCE_PRIMS | GATHER_PRIMS | frozenset({
    "all_to_all", "ppermute", "reduce_scatter",
})


def collective_axes(eqn) -> tuple:
    """Mesh-axis names a collective equation runs over (named axes
    only; positional ints from vmapped collectives are dropped)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _operand_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    try:
        itemsize = np.dtype(aval.dtype).itemsize
    except TypeError:
        itemsize = 8
    return int(np.prod(aval.shape, dtype=np.int64)) * itemsize


def float_payload_bytes(eqn) -> int:
    """Float bytes one execution of this collective moves (sum of
    floating-point operand sizes; int payloads don't count — they are
    either already quantized or index traffic). jnp.issubdtype, NOT
    np.issubdtype: bfloat16 is an ml_dtypes extension type (numpy kind
    'V') that numpy does not class as floating — and bf16 activations /
    gradients are exactly the payloads the quantization checks exist
    for."""
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        dt = np.dtype(aval.dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            continue
        total += int(np.prod(aval.shape, dtype=np.int64)) * dt.itemsize
    return total


def _wire_factor(kind: str, n: int) -> float:
    """Per-chip ring-cost factor applied to the payload base (operand
    bytes for reduce-class ops, gathered/full bytes for gather-class).
    n == 1 is a single chip (no wire); n == 0 means the axis size is
    unknown (no enclosing binder) — the n->inf limit is used so the
    estimate stays an upper bound."""
    if n == 1:
        return 0.0
    if kind in ALL_REDUCE_PRIMS:
        return 2.0 * (n - 1) / n if n else 2.0
    if kind == "ppermute":
        return 1.0
    return (n - 1) / n if n else 1.0


@dataclasses.dataclass
class CommEvent:
    """One communication site: a collective equation, or an implicit
    reshard at a pjit/shard_map boundary. `wire_bytes` is the PER-CHIP
    cost-model estimate for ONE occurrence; `count` is the loop
    amplification (product of enclosing scan lengths)."""

    kind: str               # primitive name, or 'reshard'
    path: str
    axes: tuple             # mesh axis names ('' entries never occur)
    n_devices: int          # axis-size product; 0 = unknown binder
    payload_bytes: int      # all-operand bytes, one occurrence
    float_payload_bytes: int
    wire_bytes: int         # per-chip bytes on wire, one occurrence
    count: int              # loop amplification
    shape: tuple            # largest operand's shape
    dtype: str
    in_loop: bool
    implicit: bool = False  # reshard the author never wrote
    detail: str = ""        # reshard: "P(src) -> P(dst)"
    # a recognized quantized collective (ISSUE 15): an int8 payload
    # with the f32 scale sidecar packed bitcast-int8 into the same
    # buffer (ISSUE 18 shrank the old payload+sidecar pair to one
    # collective) — the parallel/collectives.py emission. The full
    # packed bytes are priced; TPU803 never fires on int8 by design.
    quantized: bool = False

    @property
    def total_wire_bytes(self) -> int:
        return self.wire_bytes * max(self.count, 1)

    @property
    def total_float_payload_bytes(self) -> int:
        return self.float_payload_bytes * max(self.count, 1)

    def to_dict(self) -> dict:
        return {
            "kind": self.kind, "path": self.path,
            "axes": list(self.axes), "n_devices": self.n_devices,
            "payload_bytes": self.payload_bytes,
            "float_payload_bytes": self.float_payload_bytes,
            "wire_bytes": self.wire_bytes, "count": self.count,
            "total_wire_bytes": self.total_wire_bytes,
            "shape": list(self.shape), "dtype": self.dtype,
            "in_loop": self.in_loop, "implicit": self.implicit,
            "detail": self.detail, "quantized": self.quantized,
        }


class CommsReport:
    """Result of the bytes-on-wire pass: every communication event,
    loop-amplified per-chip totals, and the per-axis/per-kind splits."""

    def __init__(self, name: str, events: List[CommEvent], mp: int):
        self.name = name
        self.events = events
        # max mesh size seen across shard_map / sharding boundaries
        # (1 = no mesh anywhere); wire bytes are per chip either way
        self.mp = mp

    # -- views ---------------------------------------------------------
    @property
    def collectives(self) -> List[CommEvent]:
        return [e for e in self.events if e.kind != "reshard"]

    @property
    def reshards(self) -> List[CommEvent]:
        return [e for e in self.events if e.kind == "reshard"]

    @property
    def total_wire_bytes(self) -> int:
        """Per-chip bytes on wire for ONE execution of the program,
        loop amplification folded in."""
        return sum(e.total_wire_bytes for e in self.events)

    @property
    def total_float_payload_bytes(self) -> int:
        return sum(e.total_float_payload_bytes for e in self.collectives)

    @property
    def implicit_reshard_bytes(self) -> int:
        return sum(e.total_wire_bytes for e in self.reshards)

    @property
    def n_collective_sites(self) -> int:
        return len(self.collectives)

    @property
    def n_collectives(self) -> int:
        """Amplified occurrence count: a per-layer gather in a 16-step
        scan counts 16 per site."""
        return sum(max(e.count, 1) for e in self.collectives)

    @property
    def quantized_events(self) -> List[CommEvent]:
        return [e for e in self.events if e.quantized]

    @property
    def quantized_wire_bytes(self) -> int:
        """Per-chip amplified wire bytes of recognized quantized
        collectives — the packed int8 buffers carry payload AND
        bitcast scale sidecar, so this prices both."""
        return sum(e.total_wire_bytes for e in self.quantized_events)

    @property
    def n_quantized_sites(self) -> int:
        """Recognized quantized-collective hops (one packed int8
        buffer each since the ISSUE 18 sidecar packing)."""
        return sum(1 for e in self.quantized_events
                   if "int" in e.dtype)

    def per_axis(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            key = ",".join(e.axes) if e.axes else "<unknown>"
            out[key] = out.get(key, 0) + e.total_wire_bytes
        return out

    def per_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + e.total_wire_bytes
        return out

    def top_talkers(self, top: int = 8) -> List[CommEvent]:
        return sorted(self.events,
                      key=lambda e: -e.total_wire_bytes)[:top]

    # -- output --------------------------------------------------------
    def to_dict(self, max_events: int = 16) -> dict:
        return {
            "target": self.name,
            "per_chip": True,
            "mp": self.mp,
            "n_collective_sites": self.n_collective_sites,
            "n_collectives": self.n_collectives,
            "n_implicit_reshards": len(self.reshards),
            "bytes_on_wire": self.total_wire_bytes,
            "float_payload_bytes": self.total_float_payload_bytes,
            "implicit_reshard_bytes": self.implicit_reshard_bytes,
            "quantized_wire_bytes": self.quantized_wire_bytes,
            "n_quantized_sites": self.n_quantized_sites,
            "per_axis": self.per_axis(),
            "per_kind": self.per_kind(),
            "top_talkers": [e.to_dict()
                            for e in self.top_talkers(max_events)],
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent)

    def format(self, top: int = 8) -> str:
        kb = 1 / 1024
        lines = [
            f"comms audit {self.name}: {self.total_wire_bytes * kb:.2f} "
            f"KiB on wire per chip per execution "
            f"(mp={self.mp}, {self.n_collective_sites} site(s), "
            f"{self.n_collectives} amplified occurrence(s), "
            f"{len(self.reshards)} implicit reshard(s))",
        ]
        for axis, b in sorted(self.per_axis().items()):
            lines.append(f"  axis {axis}: {b * kb:.2f} KiB")
        if self.n_quantized_sites:
            lines.append(
                f"  quantized (int8+scale) sites: "
                f"{self.n_quantized_sites}, "
                f"{self.quantized_wire_bytes * kb:.2f} KiB on wire")
        for e in self.top_talkers(top):
            amp = f" x{e.count}" if e.count > 1 else ""
            imp = "  IMPLICIT " + e.detail if e.implicit else ""
            q = "  [q8]" if e.quantized else ""
            lines.append(
                f"    {e.total_wire_bytes * kb:9.2f} KiB  {e.kind}"
                f"[{','.join(e.axes)}] {e.dtype}{list(e.shape)}{amp}"
                f"  {e.path}{imp}{q}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# sharding-spec normalisation (for implicit-reshard detection)
# ---------------------------------------------------------------------------

def _trim(spec: Tuple[tuple, ...]) -> Tuple[tuple, ...]:
    spec = tuple(spec)
    while spec and spec[-1] == ():
        spec = spec[:-1]
    return spec


def _norm_entry(e) -> tuple:
    if e is None:
        return ()
    if isinstance(e, str):
        return (e,)
    return tuple(e)


def _norm_named_sharding(s, ndim: int):
    """(spec, axis_sizes) from a NamedSharding; None for
    UnspecifiedValue / non-mesh shardings (nothing to compare)."""
    spec = getattr(s, "spec", None)
    mesh = getattr(s, "mesh", None)
    if spec is None or mesh is None:
        return None
    entries = [_norm_entry(e) for e in tuple(spec)]
    entries += [()] * (ndim - len(entries))
    try:
        sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        return None
    return _trim(tuple(entries[:max(ndim, len(entries))])), sizes


def _norm_partition_spec(spec, ndim: int, sizes: Dict[str, int]):
    """(spec, axis_sizes) from a shard_map in_specs/out_specs entry (a
    PartitionSpec)."""
    entries = [_norm_entry(e) for e in tuple(spec)][:ndim]
    return _trim(tuple(entries)), sizes


def _reshard_wire_bytes(global_bytes: int, src, dst) -> int:
    """Per-chip wire estimate of an implicit reshard: each chip must
    fetch the (n-1)/n of its DESTINATION shard it does not already
    hold. A fully-replicated source costs nothing (the new layout is a
    local slice); sharded -> replicated is exactly the all-gather
    model."""
    src_spec, _ = src
    dst_spec, dst_sizes = dst
    if not any(src_spec):
        return 0
    axes = {a for e in src_spec for a in e} | {a for e in dst_spec
                                              for a in e}
    sizes = dict(src[1])
    sizes.update(dst_sizes)
    n = 1
    for a in axes:
        n *= int(sizes.get(a, 1))
    if n <= 1:
        return 0
    dst_shards = 1
    for e in dst_spec:
        for a in e:
            dst_shards *= int(sizes.get(a, 1))
    local_dst = global_bytes // max(dst_shards, 1)
    return int(local_dst * (n - 1) / n)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

class _CommsAuditor:
    """One walk over a closed jaxpr: collect communication events with
    axis-size resolution (shard_map meshes), loop amplification (scan
    lengths), and boundary-sharding tracking (pjit in/out_shardings,
    shard_map in/out_specs) for implicit-reshard detection."""

    def __init__(self, closed_jaxpr, name: str):
        self.closed = closed_jaxpr
        self.name = name
        self.events: List[CommEvent] = []
        self.mp = 1
        # id(var) -> (normalized spec, axis sizes) where a producer
        # declared the sharding (pjit out_shardings / shard_map
        # out_specs); program inputs are unknown, so the engine's
        # jit(shard_map(...)) top level never false-positives
        self._specs: Dict[int, tuple] = {}

    def run(self) -> CommsReport:
        self._walk(self.closed.jaxpr, self.name, {}, 1, False)
        _mark_quantized(self.events)
        return CommsReport(self.name, self.events, self.mp)

    # -- walk ----------------------------------------------------------
    def _walk(self, jaxpr, path: str, axes: Dict[str, int], trip: int,
              in_loop: bool):
        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            where = f"{path}/eqn[{i}]:{prim}"
            if prim in COLLECTIVE_PRIMS:
                self._collective(eqn, where, axes, trip, in_loop)
            elif prim == "jit":
                self._pjit(eqn, path, where, axes, trip, in_loop)
            elif prim == "scan":
                sub = eqn.params["jaxpr"]
                length = int(eqn.params.get("length") or 1)
                self._walk(getattr(sub, "jaxpr", sub),
                           f"{path}/scan[jaxpr]", axes,
                           trip * max(length, 1), True)
            elif prim == "while":
                # no static trip count: events keep the outer count but
                # are marked in_loop (TPU801 still sees them)
                for key in ("cond_jaxpr", "body_jaxpr"):
                    sub = eqn.params.get(key)
                    if sub is not None:
                        self._walk(getattr(sub, "jaxpr", sub),
                                   f"{path}/while[{key}]", axes, trip,
                                   True)
            elif prim == "shard_map":
                self._shard_map(eqn, path, where, axes, trip, in_loop)
            elif prim == "pallas_call":
                continue  # kernel bodies have Ref semantics; no comms
            else:
                for label, sub in _eqn_sub_jaxprs(eqn):
                    self._walk(sub, f"{path}/{prim}[{label}]", axes,
                               trip, in_loop)

    def _collective(self, eqn, where, axes, trip, in_loop):
        names = collective_axes(eqn)
        n = 0
        if names and all(a in axes for a in names):
            n = 1
            for a in names:
                n *= int(axes[a])
        in_bytes = sum(_operand_bytes(v) for v in eqn.invars)
        out_bytes = sum(_operand_bytes(v) for v in eqn.outvars)
        kind = eqn.primitive.name
        base = out_bytes if kind in GATHER_PRIMS else in_bytes
        wire = int(base * _wire_factor(kind, n))
        biggest = max(eqn.invars, key=_operand_bytes, default=None)
        aval = getattr(biggest, "aval", None)
        self.events.append(CommEvent(
            kind=kind, path=where, axes=names, n_devices=n,
            payload_bytes=in_bytes,
            float_payload_bytes=float_payload_bytes(eqn),
            wire_bytes=wire, count=max(trip, 1),
            shape=tuple(getattr(aval, "shape", ())),
            dtype=str(getattr(aval, "dtype", "?")),
            in_loop=in_loop))

    def _boundary(self, v, dst, where, trip, in_loop):
        """A consumer declared `dst` sharding for `v`: when the value's
        known sharding disagrees, XLA inserts a reshard collective the
        author never wrote."""
        if dst is None:
            return
        src = self._specs.get(id(v))
        if src is None or src[0] == dst[0]:
            return
        wire = _reshard_wire_bytes(_operand_bytes(v), src, dst)
        if wire <= 0:
            return  # replicated source: the new layout is a local slice
        sizes = dict(src[1])
        sizes.update(dst[1])
        axes = tuple(sorted({a for e in src[0] + dst[0] for a in e}))
        n = 1
        for a in axes:
            n *= int(sizes.get(a, 1))
        aval = getattr(v, "aval", None)
        self.events.append(CommEvent(
            kind="reshard", path=where, axes=axes, n_devices=n,
            payload_bytes=_operand_bytes(v), float_payload_bytes=0,
            wire_bytes=wire, count=max(trip, 1),
            shape=tuple(getattr(aval, "shape", ())),
            dtype=str(getattr(aval, "dtype", "?")),
            in_loop=in_loop, implicit=True,
            detail=f"{_fmt_spec(src[0])} -> {_fmt_spec(dst[0])}"))

    def _pjit(self, eqn, path, where, axes, trip, in_loop):
        ndims = [len(getattr(getattr(v, "aval", None), "shape", ()))
                 for v in eqn.invars]
        for v, s, nd in zip(eqn.invars,
                            eqn.params.get("in_shardings") or (), ndims):
            self._track_mesh(s)
            self._boundary(v, _norm_named_sharding(s, nd), where, trip,
                           in_loop)
        sub = eqn.params["jaxpr"]
        name = eqn.params.get("name")
        tag = f"pjit:{name}" if name else "pjit"
        self._walk(getattr(sub, "jaxpr", sub), f"{path}/{tag}[jaxpr]",
                   axes, trip, in_loop)
        for v, s in zip(eqn.outvars,
                        eqn.params.get("out_shardings") or ()):
            self._track_mesh(s)
            norm = _norm_named_sharding(
                s, len(getattr(getattr(v, "aval", None), "shape", ())))
            if norm is not None:
                self._specs[id(v)] = norm

    def _shard_map(self, eqn, path, where, axes, trip, in_loop):
        mesh = eqn.params.get("mesh")
        try:
            sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
            self.mp = max(self.mp, int(mesh.size))
        except Exception:
            sizes = {}
        for v, spec in zip(eqn.invars, eqn.params["in_specs"]):
            nd = len(getattr(getattr(v, "aval", None), "shape", ()))
            self._boundary(v, _norm_partition_spec(spec, nd, sizes),
                           where, trip, in_loop)
        sub = eqn.params["jaxpr"]
        self._walk(getattr(sub, "jaxpr", sub),
                   f"{path}/shard_map[jaxpr]", {**axes, **sizes}, trip,
                   in_loop)
        for v, spec in zip(eqn.outvars, eqn.params["out_specs"]):
            nd = len(getattr(getattr(v, "aval", None), "shape", ()))
            self._specs[id(v)] = _norm_partition_spec(spec, nd, sizes)

    def _track_mesh(self, sharding):
        mesh = getattr(sharding, "mesh", None)
        try:
            self.mp = max(self.mp, int(mesh.size))
        except Exception:
            pass


def _mark_quantized(events: List[CommEvent]) -> None:
    """Recognize the quantized-collective emission of
    `parallel/collectives.py` (ISSUE 15; packed single-buffer form
    since ISSUE 18): each quantized hop ships ONE int8 collective
    whose payload carries the f32 scale sidecar bitcast-int8 and
    concatenated onto the payload's last axis. Nothing else in the
    stack puts int8 on a collective — pools are sharded in place,
    activations/grads/partials travel float — so any non-reshard
    int8-dtype collective IS the rewrite's wire. Marked events
    attribute their full (payload + packed sidecar) bytes to the
    rewrite; an int8 payload never fires TPU803 by design."""
    for e in events:
        if e.kind == "reshard" or "int8" not in e.dtype:
            continue
        e.quantized = True
        e.detail = e.detail or "int8 payload + packed f32 scales"


def _fmt_spec(spec: Tuple[tuple, ...]) -> str:
    inner = ", ".join("None" if not e
                      else (repr(e[0]) if len(e) == 1 else repr(e))
                      for e in spec)
    return f"P({inner})"


def _eqn_sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vals):
            jxp = getattr(item, "jaxpr", item)
            if hasattr(jxp, "eqns") and hasattr(jxp, "invars"):
                label = k if len(vals) == 1 else f"{k}[{i}]"
                out.append((label, jxp))
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def audit_graph(graph: Graph) -> CommsReport:
    """Run the bytes-on-wire pass over an already-traced `Graph`
    (memoized on the graph — TPU401 and the three TPU80x rules share
    one pass)."""
    rep = getattr(graph, "_comms_report", None)
    if rep is None:
        rep = _CommsAuditor(graph.closed_jaxpr, graph.name).run()
        graph._comms_report = rep
    return rep


def audit_comms(fn, *args, name: Optional[str] = None,
                **kwargs) -> CommsReport:
    """Trace + audit in one call. Accepts jitted functions, plain
    callables, and framework `Layer`s / Tensor arguments (same
    dispatching tracer as the memory auditor — nothing executes on
    device)."""
    from .memory import trace_auto

    return audit_graph(trace_auto(fn, *args, name=name, **kwargs))


def resolve_audit_comms(audit_comms_param: Optional[bool]) -> bool:
    """Hook default resolution: an explicit True/False wins; None
    follows FLAGS_audit_comms (PADDLE_TPU_AUDIT_COMMS) OR the
    composable PADDLE_TPU_LINT switch — turning the linter on turns
    the communication audit on with it."""
    if audit_comms_param is not None:
        return bool(audit_comms_param)
    from ..framework.flags import flag

    return bool(flag("audit_comms")) or bool(flag("tpu_lint"))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

@register_rule
class CollectiveLoopAmplificationRule(Rule):
    """TPU801: a collective inside a loop body whose AMPLIFIED wire
    bytes (cost-model per-chip bytes x scan trip count) exceed the
    per-execution budget. The failure shape the static pass exists
    for: a per-layer all-gather reads as tiny per equation, but
    "1 per layer x 32 layers x 16 steps per chunk" is the number the
    ICI actually carries — and the one a reduce-scatter rewrite,
    chunk-size change, or quantized payload (TPU803) must beat.

    Config: `max_step_wire_bytes` (default 32 MiB; 0 disables)."""

    id = "TPU801"
    name = "collective-in-loop"
    default_severity = Severity.WARNING
    MAX_STEP_WIRE_BYTES = 1 << 25

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        budget = int(self.config.get("max_step_wire_bytes",
                                     self.MAX_STEP_WIRE_BYTES) or 0)
        if budget <= 0:
            return
        rep = audit_graph(graph)
        for e in rep.collectives:
            if not e.in_loop and e.count <= 1:
                continue
            total = e.total_wire_bytes
            if total <= budget:
                continue
            yield self.diag(
                f"{e.kind} over {e.axes} moves {e.wire_bytes} bytes "
                f"per iteration x {e.count} loop iterations = {total} "
                f"bytes on wire per chip per step "
                f"(> {budget} budget)",
                where=e.path,
                hint="hoist the collective out of the loop, rewrite as "
                     "reduce-scatter + gather at the boundary, shrink "
                     "the chunk, or quantize the payload (TPU803); "
                     "raise TPU801.max_step_wire_bytes if the budget "
                     "is wrong for this program")


@register_rule
class ImplicitReshardRule(Rule):
    """TPU802: a value crosses a pjit / shard_map boundary whose
    declared in-sharding disagrees with the sharding the value is
    known to carry — XLA silently inserts the reshard collective, so
    the program pays communication the author never wrote. The usual
    causes: an inner jit with different `in_shardings`, or a
    shard_map whose `in_specs` don't match the producer's layout.

    Config: `min_bytes` (default 64 KiB) floors out scheduling
    scalars."""

    id = "TPU802"
    name = "implicit-reshard"
    default_severity = Severity.WARNING
    MIN_BYTES = 1 << 16

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        min_bytes = int(self.config.get("min_bytes", self.MIN_BYTES))
        rep = audit_graph(graph)
        for e in rep.reshards:
            if e.total_wire_bytes < min_bytes:
                continue
            amp = (f" x {e.count} loop iterations" if e.count > 1
                   else "")
            yield self.diag(
                f"implicit reshard {e.detail} of {e.dtype}"
                f"{list(e.shape)} at a jit/shard_map boundary moves "
                f"~{e.wire_bytes} bytes per chip{amp} — communication "
                "the author never wrote",
                where=e.path,
                hint="make the boundary shardings agree (match the "
                     "producer's out_shardings / out_specs to the "
                     "consumer's in_shardings / in_specs), or reshard "
                     "explicitly where the cost is intended")


@register_rule
class QuantizableCollectiveRule(Rule):
    """TPU803: a float-payload collective moving >= `min_bytes`
    (amplified) — the EQuARX candidate. The absmax-int8 payload +
    f32-scale-sidecar rewrite is the exact scheme the int8 paged KV
    pools already prove at negligible numerics cost; this rule is the
    direct feeder for the ROADMAP quantized-collectives item
    (`parallel/collectives.py`): every site it names is a candidate
    for the quantized psum/all-gather variants. int8/int32 payloads
    (already quantized, or index traffic) never fire.

    Config: `min_bytes` (default 1 MiB, compared against the
    loop-amplified float payload)."""

    # A site rewritten through parallel/collectives.py (ISSUE 15) goes
    # SILENT here by design: the payload is int8 (never fires) and the
    # f32 scale sidecar is ~payload/32 — far under any sane min_bytes.
    id = "TPU803"
    name = "quantizable-collective"
    default_severity = Severity.WARNING
    MIN_BYTES = 1 << 20

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        min_bytes = int(self.config.get("min_bytes", self.MIN_BYTES))
        if min_bytes <= 0:
            return
        rep = audit_graph(graph)
        for e in rep.collectives:
            total = e.total_float_payload_bytes
            if not total or total < min_bytes:
                continue
            amp = (f" x {e.count} iterations = {total} bytes"
                   if e.count > 1 else "")
            yield self.diag(
                f"{e.kind} over {e.axes} moves "
                f"{e.float_payload_bytes} bytes of float payload per "
                f"occurrence{amp} on a hot path — an int8 payload "
                f"would cut the wire bytes ~{_quant_ratio(e.dtype)}x",
                where=e.path,
                hint="quantize the payload: absmax int8 + f32 scale "
                     "sidecar (EQuARX-style — the int8 paged KV "
                     "pools' exact scheme, see the ROADMAP "
                     "quantized-collectives item); raise "
                     "TPU803.min_bytes if this payload must stay "
                     "float")


def _quant_ratio(dtype: str) -> int:
    try:
        return max(int(np.dtype(dtype).itemsize), 1)
    except TypeError:
        return 2
