"""Static memory auditor: jaxpr liveness → peak-HBM estimate + donation
analysis (ISSUE 10).

The serving stack compiles dozens of jitted programs per engine
(prefill buckets x prefix-width rungs x kv dtype x mp), each
threading donated multi-GB paged pools — and the only OOM
signal at runtime is the device crashing. This pass bounds peak HBM
*statically*, from the IR, before a program ever touches silicon
("Operator Fusion in XLA: Analysis and Evaluation", PAPERS.md: buffer
liveness across fused programs is statically analyzable):

- **Liveness**: every buffer (input, captured const, equation result)
  gets a live range over a linearized equation order — sub-jaxprs
  (pjit, scan, while, cond, remat, custom_vjp, shard_map) are inlined
  with boundary variables aliased through, so a value threaded through
  a loop carry or a nested jit is ONE buffer, not many.
- **Donation / aliasing**: `jax.jit(donate_argnums=...)` masks are
  recovered from the traced pjit equation (`donated_invars`), Pallas
  `input_output_aliases` pairs merge buffers, and in-place update
  primitives (scatter / dynamic_update_slice) whose operand dies at
  the update reuse the operand's buffer — the paged-KV
  write-in-place contract. A NON-donated input is pinned live for the
  whole program (the caller still owns it; XLA may not overwrite it),
  which is exactly how a donation miss doubles residency.
- **mp-aware per-chip math**: descending into `shard_map` switches a
  buffer's accounting to its LOCAL (per-shard) aval bytes — sharded
  pools/params count 1/mp per chip, replicated buffers count whole —
  so the report's peak is the PER-CHIP number an HBM budget constrains.

Three rules ride the pass (registered into the default pipeline):

  TPU701 donation-miss     ERROR: a program output aliasable to a
                           same-shape/dtype dead input that was NOT
                           donated (e.g. a KV pool threaded through a
                           decode step without donate_argnums doubles
                           its residency). Only fires on graphs traced
                           WITH donation info (`trace_for_memory` /
                           the engine + CLI audit paths) — a generic
                           lint trace can't know the jit options.
  TPU702 hbm-over-budget   WARNING: predicted peak exceeds
                           `hbm_budget_bytes` (rule_config; default
                           off). The serving engine passes a
                           `kv_pool_bytes`-derived budget.
  TPU703 live-range-bloat  WARNING: an intermediate ≥ `min_bytes` held
                           live across ≥ `max_live_eqns` equations —
                           rematerialization / earlier-free candidates,
                           the double-buffer overlap cost made visible.

Use it three ways::

    from paddle_tpu.analysis import memory
    rep = memory.audit_memory(fn, *example_args, donate_argnums=(1, 2))
    print(rep.format());  rep.peak_bytes  # per chip

    eng.warm(...);  eng.audit_memory()   # fleet report over the cache

    python -m paddle_tpu.analysis --memory --format json
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .diagnostics import Diagnostic, Severity
from .graph import Graph
from .rules import Rule, register_rule

# sub-jaxpr primitives with bespoke boundary semantics; anything else
# carrying a jaxpr param falls back to positional aliasing
_LOOP_PRIMS = frozenset({"scan", "while"})
# primitives XLA updates in place when the operand's last use is the
# update itself (and the operand is not a non-donated input): the
# result reuses the operand buffer instead of allocating a copy
_INPLACE_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "dynamic_update_slice",
})
# byte-preserving views XLA lowers to bitcasts — the result IS the
# operand's storage (a reshaped multi-MB KV pool must not double-count)
_VIEW_PRIMS = frozenset({"reshape", "squeeze", "expand_dims"})


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys: key<fry> = 2 x uint32) — size
        # them by their trace-level representation, default one word
        itemsize = getattr(getattr(dtype, "_impl", None), "key_shape",
                           None)
        itemsize = 4 * int(np.prod(itemsize)) if itemsize else 8
    return int(np.prod(shape, dtype=np.int64)) * itemsize


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


@dataclasses.dataclass
class Buffer:
    """One HBM allocation in the audited program. `bytes` is PER-CHIP
    (shard_map descent rewrites it to the local shard's size); `def_t`
    / `last_use_t` index the linearized equation order (-1 = before the
    first equation: inputs and captured consts)."""

    bid: int
    label: str
    shape: tuple
    dtype: str
    bytes: int
    kind: str              # 'input' | 'const' | 'intermediate'
    def_t: int
    last_use_t: int = -1
    donated: bool = False   # meaningful for kind == 'input'
    is_output: bool = False
    input_index: Optional[int] = None
    # last COMPUTATIONAL use — for pinned buffers (consts, non-donated
    # inputs) `last_use_t` is forced to program end, so donation
    # analysis keeps the true last read here
    content_last_use_t: int = -1

    def to_dict(self) -> dict:
        return {
            "label": self.label, "shape": list(self.shape),
            "dtype": self.dtype, "bytes": self.bytes, "kind": self.kind,
            "def_t": self.def_t, "last_use_t": self.last_use_t,
            "donated": self.donated, "is_output": self.is_output,
        }


class MemoryReport:
    """Result of the liveness pass: peak-HBM estimate (per chip), the
    per-buffer timeline, and the donation analysis."""

    def __init__(self, name: str, buffers: List[Buffer], n_eqns: int,
                 mp: int, peak_bytes: int, peak_t: int, peak_where: str,
                 live_bytes: List[int], eqn_paths: List[str],
                 donation: dict):
        self.name = name
        self.buffers = buffers
        self.n_eqns = n_eqns
        # max mesh size seen across shard_map equations (1 = unsharded);
        # peak_bytes is per chip either way
        self.mp = mp
        self.peak_bytes = peak_bytes
        self.peak_t = peak_t
        self.peak_where = peak_where
        self._live_bytes = live_bytes     # live bytes after each t
        self._eqn_paths = eqn_paths
        self.donation = donation

    # -- views ---------------------------------------------------------
    @property
    def input_bytes(self) -> int:
        return sum(b.bytes for b in self.buffers if b.kind == "input")

    @property
    def const_bytes(self) -> int:
        return sum(b.bytes for b in self.buffers if b.kind == "const")

    @property
    def output_bytes(self) -> int:
        return sum(b.bytes for b in self.buffers if b.is_output)

    def live_at(self, t: int) -> List[Buffer]:
        return [b for b in self.buffers if b.def_t <= t <= b.last_use_t]

    def peak_buffers(self, top: int = 8) -> List[Buffer]:
        """Largest buffers live at the peak instant."""
        live = sorted(self.live_at(self.peak_t),
                      key=lambda b: -b.bytes)
        return live[:top]

    def timeline(self, max_points: int = 64) -> List[dict]:
        """Downsampled (t, where, live_bytes) — always includes the
        peak instant."""
        n = len(self._live_bytes)
        if n == 0:
            return []
        stride = max(1, n // max_points)
        idx = sorted(set(range(0, n, stride)) | {self.peak_t, n - 1})
        return [{"t": t, "where": self._eqn_paths[t],
                 "live_bytes": self._live_bytes[t]} for t in idx
                if 0 <= t < n]

    # -- output --------------------------------------------------------
    def to_dict(self, max_buffers: int = 16) -> dict:
        return {
            "target": self.name,
            "peak_hbm_bytes": self.peak_bytes,
            "peak_at": {"t": self.peak_t, "where": self.peak_where},
            "per_chip": True,
            "mp": self.mp,
            "n_eqns": self.n_eqns,
            "n_buffers": len(self.buffers),
            "input_bytes": self.input_bytes,
            "const_bytes": self.const_bytes,
            "output_bytes": self.output_bytes,
            "donation": self.donation,
            "peak_buffers": [b.to_dict()
                             for b in self.peak_buffers(max_buffers)],
            "timeline": self.timeline(),
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent)

    def format(self, top: int = 8) -> str:
        mb = 1 / (1 << 20)
        lines = [
            f"memory audit {self.name}: predicted peak "
            f"{self.peak_bytes * mb:.2f} MiB per chip "
            f"(mp={self.mp}, {self.n_eqns} eqns, "
            f"{len(self.buffers)} buffers)",
            f"  peak at [{self.peak_t}] {self.peak_where}",
        ]
        for b in self.peak_buffers(top):
            flags = []
            if b.kind == "input":
                flags.append("donated" if b.donated else "input")
            if b.kind == "const":
                flags.append("const")
            if b.is_output:
                flags.append("output")
            lines.append(
                f"    {b.bytes * mb:9.3f} MiB  {b.dtype}{list(b.shape)}"
                f"  live [{b.def_t}..{b.last_use_t}]"
                f"  {b.label}" + (f"  ({', '.join(flags)})"
                                  if flags else ""))
        d = self.donation
        lines.append(
            f"  donation: {d['donated_bytes'] * mb:.2f} MiB donated, "
            f"{d['missed_bytes'] * mb:.2f} MiB in "
            f"{len(d['misses'])} miss(es)")
        for m in d["misses"]:
            lines.append(
                f"    MISS {m['bytes'] * mb:.3f} MiB "
                f"{m['dtype']}{m['shape']}: output {m['output']} "
                f"could reuse un-donated input {m['input']}")
        return "\n".join(lines)


class _Auditor:
    """One pass over a closed jaxpr: linearize, build buffers, alias."""

    def __init__(self, closed_jaxpr, name: str,
                 donated_invars: Optional[Sequence[bool]]):
        self.closed = closed_jaxpr
        self.name = name
        self.donated = donated_invars
        self.buffers: List[Buffer] = []
        self.paths: List[str] = []      # label per linearized eqn
        self.t = 0
        self.mp = 1
        # union-find over buffer ids (aliasing merges)
        self._parent: List[int] = []
        # in-place candidates recorded during the walk:
        # (t, operand_bid, out_bid)
        self._inplace: List[Tuple[int, int, int]] = []
        # (input_bid, out_bid) pairs whose merge was refused ONLY
        # because the input was not donated — the structural
        # donation-miss channel (loop carries, in-place updates of
        # un-donated inputs)
        self._donation_candidates: List[Tuple[int, int]] = []

    # -- buffer / union-find helpers ----------------------------------
    def _new_buffer(self, aval, label: str, kind: str, def_t: int,
                    donated: bool = False,
                    input_index: Optional[int] = None) -> Buffer:
        bid = len(self.buffers)
        buf = Buffer(bid=bid, label=label,
                     shape=tuple(getattr(aval, "shape", ())),
                     dtype=str(getattr(aval, "dtype", "?")),
                     bytes=_aval_bytes(aval), kind=kind, def_t=def_t,
                     last_use_t=def_t, donated=donated,
                     input_index=input_index)
        self.buffers.append(buf)
        self._parent.append(bid)
        return buf

    def _find(self, bid: int) -> int:
        while self._parent[bid] != bid:
            self._parent[bid] = self._parent[self._parent[bid]]
            bid = self._parent[bid]
        return bid

    def _merge(self, into: int, other: int) -> int:
        """Alias two buffers into one allocation: union live ranges,
        keep the stronger kind (input/const beats intermediate — the
        merged storage IS the input's), OR the flags."""
        a, b = self._find(into), self._find(other)
        if a == b:
            return a
        ba, bb = self.buffers[a], self.buffers[b]
        # inputs/consts own their storage; an intermediate merged into
        # one inherits it
        if bb.kind != "intermediate" and ba.kind == "intermediate":
            a, b = b, a
            ba, bb = bb, ba
        ba.def_t = min(ba.def_t, bb.def_t)
        ba.last_use_t = max(ba.last_use_t, bb.last_use_t)
        ba.is_output = ba.is_output or bb.is_output
        ba.donated = ba.donated or bb.donated
        ba.bytes = max(ba.bytes, bb.bytes)
        self._parent[b] = a
        return a

    def _use(self, bid: int, t: int):
        buf = self.buffers[self._find(bid)]
        buf.last_use_t = max(buf.last_use_t, t)

    def _overwritable(self, bid: int) -> bool:
        """May this buffer legally be updated in place? Captured consts
        and NON-donated inputs are owned by the executable / caller —
        XLA must copy before writing; everything else may alias."""
        buf = self.buffers[self._find(bid)]
        if buf.kind == "const":
            return False
        return not (buf.kind == "input" and not buf.donated)

    def _is_undonated_input(self, bid: int) -> bool:
        buf = self.buffers[self._find(bid)]
        return buf.kind == "input" and not buf.donated

    # -- walk ----------------------------------------------------------
    def run(self) -> MemoryReport:
        jaxpr = self.closed.jaxpr
        env: Dict[Any, int] = {}
        for i, v in enumerate(jaxpr.constvars):
            env[v] = self._new_buffer(v.aval, f"const[{i}]", "const",
                                      -1).bid
        donated = self.donated
        for i, v in enumerate(jaxpr.invars):
            d = bool(donated[i]) if donated is not None \
                and i < len(donated) else False
            env[v] = self._new_buffer(v.aval, f"in[{i}]", "input", -1,
                                      donated=d, input_index=i).bid
        self._walk(jaxpr, env, self.name)
        T = self.t  # one past the last equation
        # program outputs stay resident at the end
        out_bids = []
        for v in jaxpr.outvars:
            bid = self._lookup(env, v)
            if bid is None:
                continue
            out_bids.append(self._find(bid))
            buf = self.buffers[self._find(bid)]
            buf.is_output = True
            buf.last_use_t = T
        # a NON-donated input (or a captured const) is owned by the
        # caller / executable for the whole run — XLA cannot reuse it.
        # The TRUE last read is kept for donation analysis: "would
        # donating this input have let an output reuse it?"
        for buf in self.buffers:
            if buf.bid != self._find(buf.bid):
                continue
            if buf.kind == "const" or (buf.kind == "input"
                                       and not buf.donated):
                buf.content_last_use_t = buf.last_use_t
                buf.last_use_t = T
        self._apply_inplace()
        self._fold_donation(out_bids, T)
        return self._sweep(T)

    def _lookup(self, env, v) -> Optional[int]:
        if _is_literal(v):
            return None
        return env.get(v)

    def _read(self, env, v, t) -> Optional[int]:
        bid = self._lookup(env, v)
        if bid is not None:
            self._use(bid, t)
        return bid

    def _walk(self, jaxpr, env: Dict[Any, int], path: str):
        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            where = f"{path}/eqn[{i}]:{prim}"
            handler = getattr(self, f"_h_{prim.replace('-', '_')}", None)
            if handler is not None:
                handler(eqn, env, where)
            elif prim == "jit":
                self._h_pjit(eqn, env, where)
            else:
                subs = _eqn_sub_jaxprs(eqn)
                if subs and prim != "pallas_call":
                    self._generic_sub(eqn, subs, env, where)
                else:
                    self._leaf(eqn, env, where)

    def _tick(self, where: str) -> int:
        t = self.t
        self.paths.append(where)
        self.t += 1
        return t

    def _leaf(self, eqn, env, where):
        """A plain equation: operands used now, results allocated now.
        pallas_call `input_output_aliases` and in-place updates reuse
        their operand's buffer."""
        t = self._tick(where)
        in_bids = [self._read(env, v, t) for v in eqn.invars]
        out_bids = []
        for k, v in enumerate(eqn.outvars):
            buf = self._new_buffer(v.aval, f"{where}#o{k}",
                                   "intermediate", t)
            out_bids.append(buf.bid)
            if not _is_dropvar(v):
                env[v] = buf.bid
        prim = eqn.primitive.name
        if prim in _VIEW_PRIMS and in_bids and in_bids[0] is not None \
                and out_bids:
            self._merge(in_bids[0], out_bids[0])
        elif eqn.primitive.name == "pallas_call":
            for pair in (eqn.params.get("input_output_aliases") or ()):
                try:
                    i_in, i_out = int(pair[0]), int(pair[1])
                except (TypeError, ValueError, IndexError):
                    continue
                if 0 <= i_out < len(out_bids) and 0 <= i_in < len(in_bids) \
                        and in_bids[i_in] is not None:
                    self._merge(in_bids[i_in], out_bids[i_out])
        elif eqn.primitive.name in _INPLACE_PRIMS and in_bids \
                and in_bids[0] is not None and out_bids:
            # candidate only — legality (operand dead, not a pinned
            # input) is decided in _apply_inplace once all uses are in
            self._inplace.append((t, in_bids[0], out_bids[0]))

    # -- sub-jaxpr handlers -------------------------------------------
    def _h_pjit(self, eqn, env, where):
        sub = eqn.params["jaxpr"]
        jxp = getattr(sub, "jaxpr", sub)
        consts = list(getattr(sub, "consts", ()))
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        sub_env: Dict[Any, int] = {}
        for k, cv in enumerate(jxp.constvars):
            if k < len(consts):
                sub_env[cv] = self._new_buffer(
                    cv.aval, f"{where}/const[{k}]", "const", t0).bid
        for bv, bid in zip(jxp.invars, in_bids):
            if bid is not None:
                sub_env[bv] = bid
            else:
                sub_env[bv] = self._new_buffer(
                    bv.aval, f"{where}/lit", "intermediate", t0).bid
        name = eqn.params.get("name")
        self._walk(jxp, sub_env, f"{where}" + (f"[{name}]" if name else ""))
        t1 = max(self.t - 1, t0)
        # a NON-donated pjit operand must survive the whole nested
        # program (the outer scope still owns it)
        donated = eqn.params.get("donated_invars")
        for k, bid in enumerate(in_bids):
            if bid is None:
                continue
            if donated is None or k >= len(donated) or not donated[k]:
                self._use(bid, t1)
        for ov, bv in zip(eqn.outvars, jxp.outvars):
            bid = self._lookup(sub_env, bv)
            if bid is None:
                bid = self._new_buffer(ov.aval, f"{where}#out",
                                       "intermediate", t1).bid
            if not _is_dropvar(ov):
                env[ov] = bid

    def _h_remat2(self, eqn, env, where):
        # jax names the checkpoint primitive "remat2"; its
        # params["jaxpr"] is an OPEN jaxpr, which _h_pjit's
        # getattr(sub, "jaxpr", sub) normalisation already handles
        self._h_pjit(eqn, env, where)

    def _h_remat(self, eqn, env, where):
        self._h_pjit(eqn, env, where)

    def _h_checkpoint(self, eqn, env, where):
        self._h_pjit(eqn, env, where)

    def _h_custom_jvp_call(self, eqn, env, where):
        self._custom_call(eqn, env, where, "call_jaxpr")

    def _h_custom_vjp_call(self, eqn, env, where):
        self._custom_call(eqn, env, where, "call_jaxpr")

    def _h_custom_vjp_call_jaxpr(self, eqn, env, where):
        self._custom_call(eqn, env, where, "fun_jaxpr")

    def _custom_call(self, eqn, env, where, key):
        sub = eqn.params.get(key)
        if sub is None:
            subs = _eqn_sub_jaxprs(eqn)
            if not subs:
                return self._leaf(eqn, env, where)
            sub = subs[0]
        self._generic_sub(eqn, [sub], env, where)

    def _generic_sub(self, eqn, subs, env, where):
        """Fallback for unknown higher-order primitives: inline the
        first sub-jaxpr with positional aliasing when arities line up,
        fresh buffers otherwise. Conservative but never wrong about
        WHICH allocations exist inside."""
        sub = subs[0]
        jxp = getattr(sub, "jaxpr", sub)
        consts = list(getattr(sub, "consts", ()))
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        sub_env: Dict[Any, int] = {}
        for k, cv in enumerate(jxp.constvars):
            if k < len(consts):
                sub_env[cv] = self._new_buffer(
                    cv.aval, f"{where}/const[{k}]", "const", t0).bid
        aligned = len(jxp.invars) == len(in_bids)
        for k, bv in enumerate(jxp.invars):
            bid = in_bids[k] if aligned else None
            if bid is None:
                bid = self._new_buffer(bv.aval, f"{where}/in[{k}]",
                                       "intermediate", t0).bid
            sub_env[bv] = bid
        self._walk(jxp, sub_env, where)
        t1 = max(self.t - 1, t0)
        out_aligned = len(jxp.outvars) == len(eqn.outvars)
        for k, ov in enumerate(eqn.outvars):
            bid = self._lookup(sub_env, jxp.outvars[k]) if out_aligned \
                else None
            if bid is None:
                bid = self._new_buffer(ov.aval, f"{where}#o{k}",
                                       "intermediate", t1).bid
            if not _is_dropvar(ov):
                env[ov] = bid

    def _h_scan(self, eqn, env, where):
        """Inline the body ONCE (the loop reuses the same buffers every
        iteration): consts/carries alias the operands, per-iteration xs
        slices are fresh small buffers, stacked ys outputs materialize
        for the whole loop, and the final-carry outputs MERGE with the
        carry operands — XLA threads carries in place, which is what
        lets a donated pool ride a decode scan at 1x residency."""
        sub = eqn.params["jaxpr"]
        jxp = getattr(sub, "jaxpr", sub)
        consts = list(getattr(sub, "consts", ()))
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        sub_env: Dict[Any, int] = {}
        for k, cv in enumerate(jxp.constvars):
            if k < len(consts):
                sub_env[cv] = self._new_buffer(
                    cv.aval, f"{where}/const[{k}]", "const", t0).bid
        for k, bv in enumerate(jxp.invars):
            if k < n_consts + n_carry and k < len(in_bids) \
                    and in_bids[k] is not None:
                sub_env[bv] = in_bids[k]
            else:
                sub_env[bv] = self._new_buffer(
                    bv.aval, f"{where}/iter_in[{k}]", "intermediate",
                    t0).bid
        # stacked ys exist from loop entry to their last use
        ys_bids = []
        for k, ov in enumerate(eqn.outvars[n_carry:]):
            ys_bids.append(self._new_buffer(
                ov.aval, f"{where}#ys[{k}]", "intermediate", t0).bid)
        self._walk(jxp, sub_env, where)
        t1 = max(self.t - 1, t0)
        # operands feed every iteration: alive through the body
        for bid in in_bids:
            if bid is not None:
                self._use(bid, t1)
        for k, ov in enumerate(eqn.outvars):
            if k < n_carry:
                bid = self._lookup(sub_env, jxp.outvars[k])
                if bid is None:
                    bid = self._new_buffer(ov.aval, f"{where}#carry[{k}]",
                                           "intermediate", t1).bid
                # final carry == the threaded operand buffer — but only
                # when the operand may be overwritten; a NON-donated
                # input carried through a loop is copied first, and the
                # double residency is exactly what the audit must show
                op_bid = in_bids[n_consts + k] \
                    if n_consts + k < len(in_bids) else None
                if op_bid is not None:
                    if self._overwritable(op_bid):
                        bid = self._merge(op_bid, bid)
                    elif self._is_undonated_input(op_bid):
                        # had it been donated, the carry would thread
                        # in place — the classic pool donation miss
                        self._donation_candidates.append((op_bid, bid))
            else:
                bid = ys_bids[k - n_carry]
                self._use(bid, t1)
            if not _is_dropvar(ov):
                env[ov] = bid

    def _h_while(self, eqn, env, where):
        sub_cond = eqn.params["cond_jaxpr"]
        sub_body = eqn.params["body_jaxpr"]
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        carry_bids = in_bids[cn + bn:]

        def inline(sub, operand_bids, tag):
            jxp = getattr(sub, "jaxpr", sub)
            consts = list(getattr(sub, "consts", ()))
            sub_env: Dict[Any, int] = {}
            for k, cv in enumerate(jxp.constvars):
                if k < len(consts):
                    sub_env[cv] = self._new_buffer(
                        cv.aval, f"{where}/{tag}/const[{k}]", "const",
                        t0).bid
            for k, bv in enumerate(jxp.invars):
                bid = operand_bids[k] if k < len(operand_bids) else None
                if bid is None:
                    bid = self._new_buffer(
                        bv.aval, f"{where}/{tag}/in[{k}]",
                        "intermediate", t0).bid
                sub_env[bv] = bid
            self._walk(jxp, sub_env, f"{where}/{tag}")
            return jxp, sub_env

        inline(sub_cond, in_bids[:cn] + carry_bids, "cond")
        body_jxp, body_env = inline(sub_body,
                                    in_bids[cn:cn + bn] + carry_bids,
                                    "body")
        t1 = max(self.t - 1, t0)
        for bid in in_bids:
            if bid is not None:
                self._use(bid, t1)
        for k, ov in enumerate(eqn.outvars):
            bid = self._lookup(body_env, body_jxp.outvars[k]) \
                if k < len(body_jxp.outvars) else None
            if bid is None:
                bid = self._new_buffer(ov.aval, f"{where}#carry[{k}]",
                                       "intermediate", t1).bid
            if k < len(carry_bids) and carry_bids[k] is not None:
                if self._overwritable(carry_bids[k]):
                    bid = self._merge(carry_bids[k], bid)
                elif self._is_undonated_input(carry_bids[k]):
                    self._donation_candidates.append((carry_bids[k],
                                                      bid))
            if not _is_dropvar(ov):
                env[ov] = bid

    def _h_cond(self, eqn, env, where):
        branches = eqn.params.get("branches") or ()
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        op_bids = in_bids[1:]  # invars[0] is the branch index
        for bi, sub in enumerate(branches):
            jxp = getattr(sub, "jaxpr", sub)
            consts = list(getattr(sub, "consts", ()))
            sub_env: Dict[Any, int] = {}
            for k, cv in enumerate(jxp.constvars):
                if k < len(consts):
                    sub_env[cv] = self._new_buffer(
                        cv.aval, f"{where}/b{bi}/const[{k}]", "const",
                        t0).bid
            for k, bv in enumerate(jxp.invars):
                bid = op_bids[k] if k < len(op_bids) else None
                if bid is None:
                    bid = self._new_buffer(
                        bv.aval, f"{where}/b{bi}/in[{k}]",
                        "intermediate", t0).bid
                sub_env[bv] = bid
            self._walk(jxp, sub_env, f"{where}/branch[{bi}]")
        t1 = max(self.t - 1, t0)
        for k, ov in enumerate(eqn.outvars):
            bid = self._new_buffer(ov.aval, f"{where}#o{k}",
                                   "intermediate", t1).bid
            if not _is_dropvar(ov):
                env[ov] = bid

    def _h_shard_map(self, eqn, env, where):
        """Per-chip accounting: inside the body every aval is the LOCAL
        shard's, so buffers created there are already per-chip; boundary
        operands are rewritten to their local (per-shard) byte size —
        a sharded pool counts 1/mp per chip, a replicated block table
        counts whole."""
        sub = eqn.params["jaxpr"]
        jxp = getattr(sub, "jaxpr", sub)
        mesh = eqn.params.get("mesh")
        try:
            self.mp = max(self.mp, int(mesh.size))
        except Exception:
            pass
        t0 = self.t
        in_bids = [self._read(env, v, t0) for v in eqn.invars]
        sub_env: Dict[Any, int] = {}
        for bv, bid in zip(jxp.invars, in_bids):
            if bid is None:
                bid = self._new_buffer(bv.aval, f"{where}/lit",
                                       "intermediate", t0).bid
            else:
                buf = self.buffers[self._find(bid)]
                local = _aval_bytes(bv.aval)
                if local:
                    buf.bytes = min(buf.bytes, local)
            sub_env[bv] = bid
        self._walk(jxp, sub_env, where)
        t1 = max(self.t - 1, t0)
        # NO blanket operand-lifetime extension here (unlike scan): the
        # body executes ONCE, so an operand dies at its last body use —
        # which is what lets a donated pool's in-place page scatter
        # inside the sharded prefill body reuse its storage
        for ov, bv in zip(eqn.outvars, jxp.outvars):
            bid = self._lookup(sub_env, bv)
            if bid is None:
                bid = self._new_buffer(ov.aval, f"{where}#out",
                                       "intermediate", t1).bid
            # keep the body-local (per-chip) size for sharded outputs
            if not _is_dropvar(ov):
                env[ov] = bid

    # -- post passes ---------------------------------------------------
    def _apply_inplace(self):
        """Grant in-place reuse to update ops whose operand's last use
        IS the update and whose operand buffer may legally be
        overwritten (donated input, const-free intermediate — never a
        non-donated input or a captured const)."""
        for t, op_bid, out_bid in self._inplace:
            a = self._find(op_bid)
            if self._find(out_bid) == a:
                continue
            buf = self.buffers[a]
            if not self._overwritable(op_bid):
                # an un-donated input whose true last read is this very
                # update would have merged had it been donated — the
                # structural donation-miss channel (prefill page
                # scatters into un-donated pools)
                if self._is_undonated_input(op_bid) \
                        and 0 <= buf.content_last_use_t <= t:
                    self._donation_candidates.append((op_bid, out_bid))
                continue
            if buf.last_use_t > t:
                continue  # operand read later: the copy is real
            self._merge(a, out_bid)

    def _fold_donation(self, out_bids: List[int], T: int):
        """XLA input-output aliasing: an output not already sharing
        storage with an input may reuse a DONATED input of identical
        shape/dtype that is dead by the time the output materializes.
        Also records the donation summary + miss candidates (TPU701)."""
        donated_pool: Dict[tuple, List[Buffer]] = {}
        for buf in self.buffers:
            if buf.bid == self._find(buf.bid) and buf.kind == "input" \
                    and buf.donated and not buf.is_output:
                donated_pool.setdefault(
                    (buf.shape, buf.dtype), []).append(buf)
        for bid in out_bids:
            buf = self.buffers[self._find(bid)]
            if buf.kind == "input":
                continue  # already aliased through
            pool = donated_pool.get((buf.shape, buf.dtype), [])
            cand = next((c for c in pool if c.last_use_t < buf.def_t
                         and self._find(c.bid) != self._find(buf.bid)),
                        None)
            if cand is not None:
                pool.remove(cand)
                self._merge(cand.bid, buf.bid)
        # donation summary over ROOT buffers
        roots = [b for b in self.buffers if b.bid == self._find(b.bid)]
        donated_bytes = sum(b.bytes for b in roots
                            if b.kind == "input" and b.donated)
        # donation misses, two channels:
        # 1. STRUCTURAL: merges the walk refused only because the
        #    input was not donated (loop carries threading an
        #    un-donated buffer, in-place updates of un-donated
        #    inputs whose true last read is the update) — precise,
        #    and robust to the loop-lifetime extension;
        # 2. GENERIC: a pure-output buffer whose aval matches a
        #    non-donated, non-output input STRICTLY dead before the
        #    output materializes (content_last_use_t < def). An input
        #    still read at/after the output's defining equation is
        #    NOT claimed — whether XLA could alias there depends on
        #    the op (the in-place-capable cases ride channel 1), and
        #    an advisory ERROR must not guess.
        misses = []
        used_inputs, used_outputs = set(), set()

        def add_miss(cand: Buffer, out_buf: Buffer):
            used_inputs.add(cand.bid)
            used_outputs.add(out_buf.bid)
            misses.append({
                "shape": list(out_buf.shape), "dtype": out_buf.dtype,
                "bytes": out_buf.bytes, "output": out_buf.label,
                "input": cand.label,
                "input_index": cand.input_index,
            })

        for in_bid, out_bid in self._donation_candidates:
            cand = self.buffers[self._find(in_bid)]
            out_buf = self.buffers[self._find(out_bid)]
            if cand.bid == out_buf.bid or cand.bid in used_inputs \
                    or out_buf.bid in used_outputs:
                continue
            if not (cand.kind == "input" and not cand.donated
                    and not cand.is_output):
                continue
            if not out_buf.is_output:
                continue  # internal double-buffering, not 2x residency
            add_miss(cand, out_buf)
        free_inputs: Dict[tuple, List[Buffer]] = {}
        for b in roots:
            if b.kind == "input" and not b.donated and not b.is_output \
                    and b.bid not in used_inputs:
                free_inputs.setdefault((b.shape, b.dtype), []).append(b)
        for b in roots:
            if not b.is_output or b.kind != "intermediate" \
                    or b.bid in used_outputs:
                continue
            pool = free_inputs.get((b.shape, b.dtype), [])
            cand = next((c for c in pool
                         if c.content_last_use_t < b.def_t), None)
            if cand is None:
                continue
            pool.remove(cand)
            add_miss(cand, b)
        self.donation = {
            "donated_bytes": donated_bytes,
            "missed_bytes": sum(m["bytes"] for m in misses),
            "misses": misses,
        }

    def _sweep(self, T: int) -> MemoryReport:
        """Event sweep over [0, T]: live bytes at t = sum of root
        buffers with def_t <= t <= last_use_t."""
        n = max(T, 1)
        delta = np.zeros(n + 2, np.int64)
        for b in self.buffers:
            if b.bid != self._find(b.bid):
                continue
            lo = max(b.def_t, 0)
            hi = min(b.last_use_t, T)
            if hi < lo:
                hi = lo
            delta[lo] += b.bytes
            delta[hi + 1] -= b.bytes
        live = np.cumsum(delta)[:n]
        peak_t = int(np.argmax(live)) if n else 0
        peak = int(live[peak_t]) if n else 0
        paths = self.paths or [self.name]
        where = paths[min(peak_t, len(paths) - 1)]
        roots = [b for b in self.buffers if b.bid == self._find(b.bid)]
        return MemoryReport(
            name=self.name, buffers=roots, n_eqns=self.t, mp=self.mp,
            peak_bytes=peak, peak_t=peak_t, peak_where=where,
            live_bytes=[int(x) for x in live],
            eqn_paths=paths, donation=self.donation)


def _eqn_sub_jaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            jxp = getattr(item, "jaxpr", item)
            if hasattr(jxp, "eqns") and hasattr(jxp, "invars"):
                out.append(item)
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _unwrap_trivial_pjit(closed, donated):
    """Peel `jit`-wrapper jaxprs: a top level that is exactly one pjit
    consuming every invar IS the program — descend and take (OR in) its
    `donated_invars`, so auditing a jitted function sees the donation
    the executable will actually perform."""
    while True:
        jaxpr = closed.jaxpr
        if len(jaxpr.eqns) != 1 or jaxpr.eqns[0].primitive.name != "jit":
            return closed, donated
        eqn = jaxpr.eqns[0]
        if len(eqn.invars) != len(jaxpr.invars) or any(
                a is not b for a, b in zip(eqn.invars, jaxpr.invars)):
            return closed, donated
        if len(eqn.outvars) != len(jaxpr.outvars) or any(
                a is not b for a, b in zip(eqn.outvars, jaxpr.outvars)):
            return closed, donated
        inner = eqn.params["jaxpr"]
        inner_don = eqn.params.get("donated_invars")
        if inner_don is None:
            inner_don = (False,) * len(inner.jaxpr.invars)
        if donated is not None:
            inner_don = tuple(a or b for a, b in zip(donated, inner_don))
        closed, donated = inner, tuple(inner_don)


def trace_for_memory(fn, *args, donate_argnums=(), name: Optional[str]
                     = None, **kwargs) -> Graph:
    """Trace `fn(*args)` for the memory auditor: a `Graph` whose
    `donated_invars` reflect the jit donation the compiled program
    would perform. `fn` may already be jitted (its own
    `donate_argnums` are recovered from the traced pjit equation) or a
    plain callable (pass `donate_argnums=` here). Array leaves may be
    jax arrays, numpy arrays, or `ShapeDtypeStruct`s — nothing runs on
    device."""
    import jax

    if kwargs:
        fn = functools.partial(fn, **kwargs)
    target = fn
    if donate_argnums:
        target = jax.jit(fn, donate_argnums=tuple(donate_argnums))
    closed = jax.make_jaxpr(target)(*args)
    closed, donated = _unwrap_trivial_pjit(closed, None)
    if donated is None:
        donated = (False,) * len(closed.jaxpr.invars)
    if name is None:
        name = getattr(fn, "__name__", None) or type(fn).__name__
    return Graph(closed, name=name, donated_invars=tuple(donated))


def audit_graph(graph: Graph) -> MemoryReport:
    """Run the liveness pass over an already-traced `Graph` (memoized
    on the graph — the three memory rules share one pass)."""
    rep = getattr(graph, "_memory_report", None)
    if rep is None:
        rep = _Auditor(graph.closed_jaxpr, graph.name,
                       getattr(graph, "donated_invars", None)).run()
        graph._memory_report = rep
    return rep


def trace_auto(fn, *args, donate_argnums=(),
               name: Optional[str] = None, **kwargs) -> Graph:
    """Dispatching tracer for the audit entry points: framework
    `Layer`s / Tensor arguments go through the lint tracer (which
    threads Layer state as inputs; donation is then unknown, so TPU701
    stays quiet), everything else through the donation-aware
    `trace_for_memory`."""
    try:
        from ..core.tensor import Tensor
        from ..nn.layer.layers import Layer

        framework = isinstance(fn, Layer) or isinstance(
            getattr(fn, "__self__", None), Layer) or any(
            isinstance(a, Tensor) for a in args)
    except Exception:
        framework = False
    if framework:
        from .graph import trace_graph

        return trace_graph(fn, *args, name=name, **kwargs)
    return trace_for_memory(fn, *args, donate_argnums=donate_argnums,
                            name=name, **kwargs)


def audit_memory(fn, *args, donate_argnums=(),
                 name: Optional[str] = None, **kwargs) -> MemoryReport:
    """Trace + audit in one call. Accepts jitted functions, plain
    callables (+ `donate_argnums=`), and framework `Layer`s / Tensor
    arguments (those trace via the lint tracer; donation is then
    unknown, so TPU701 stays quiet but the peak estimate stands)."""
    return audit_graph(trace_auto(fn, *args,
                                  donate_argnums=donate_argnums,
                                  name=name, **kwargs))


def pytree_local_bytes(tree) -> int:
    """PER-CHIP bytes of a pytree of arrays: sharded jax Arrays count
    one addressable shard, replicated / host arrays count whole. The
    engine's audit uses it to derive an HBM budget (params + pool
    budget) in the same per-chip units the liveness pass reports."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is None:
            continue
        try:
            shards = leaf.addressable_shards
            if shards:
                nb = shards[0].data.nbytes
        except Exception:
            pass
        total += int(nb)
    return total


def resolve_audit_memory(audit_memory_param: Optional[bool]) -> bool:
    """Hook default resolution: an explicit True/False wins; None
    follows FLAGS_audit_memory (PADDLE_TPU_AUDIT_MEMORY) OR the
    composable PADDLE_TPU_LINT switch — turning the linter on turns
    the memory audit on with it."""
    if audit_memory_param is not None:
        return bool(audit_memory_param)
    from ..framework.flags import flag

    return bool(flag("audit_memory")) or bool(flag("tpu_lint"))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

@register_rule
class DonationMissRule(Rule):
    """TPU701: a program output that could alias a same-shape/dtype
    dead input which was NOT donated. The classic serving shape: a KV
    pool threaded through a decode step without `donate_argnums` keeps
    BOTH the stale and the updated pool resident — 2x the multi-GB
    buffer, invisible until the device OOMs. Only fires when the graph
    carries donation info (`trace_for_memory` / the engine + CLI audit
    paths): a generic lint trace cannot know the jit options, and
    guessing would flag every pure elementwise function.

    Config: `min_bytes` (default 64 KiB) — pairs smaller than this are
    scheduling-vector noise (lengths, done flags), not pools."""

    id = "TPU701"
    name = "donation-miss"
    default_severity = Severity.ERROR
    MIN_BYTES = 1 << 16

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        if getattr(graph, "donated_invars", None) is None:
            return
        rep = audit_graph(graph)
        min_bytes = int(self.config.get("min_bytes", self.MIN_BYTES))
        for m in rep.donation["misses"]:
            if m["bytes"] < min_bytes:
                continue
            yield self.diag(
                f"output {m['output']} ({m['dtype']}{m['shape']}, "
                f"{m['bytes'] / (1 << 20):.2f} MiB) could reuse input "
                f"{m['input']} which is dead but NOT donated: both "
                "stay resident and the buffer's footprint doubles",
                where=graph.name,
                hint="pass donate_argnums= for the threaded buffer "
                     "(jax.jit(fn, donate_argnums=...)); the engine "
                     "donates its KV pools through every program")


@register_rule
class HBMBudgetRule(Rule):
    """TPU702: the liveness pass predicts a peak over the HBM budget.
    The budget AUTO-ARMS from the device row's capacity minus a
    headroom fraction (`device_specs.auto_hbm_budget` — the same
    derivation the autotuner's feasibility gate uses): by default the
    `TPU702.device` row (or the detected/default device) caps every
    audited program at ~90% of its HBM. An explicit
    `rule_config={'TPU702.hbm_budget_bytes': ...}` overrides it (the
    serving engine's audit derives one from its `kv_pool_bytes=`
    sizing; CI passes one via `--rule-config`); an explicit 0 disables
    the rule outright."""

    id = "TPU702"
    name = "hbm-over-budget"
    default_severity = Severity.WARNING

    def __init__(self, severity: Optional[Severity] = None, **config):
        super().__init__(severity, **config)
        self._auto = "hbm_budget_bytes" not in self.config
        if self._auto:
            from .device_specs import auto_hbm_budget

            self._budget = auto_hbm_budget(self.config.get("device"))
            return
        raw = self.config.get("hbm_budget_bytes", 0)
        try:
            self._budget = int(raw or 0)
        except (TypeError, ValueError):
            # a mis-typed budget must fail LOUDLY at configuration
            # time — inside check() the pipeline's rule-crash catch
            # would demote an armed budget to a silent INFO
            raise ValueError(
                f"TPU702.hbm_budget_bytes must be an integer byte "
                f"count, got {raw!r}")

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        budget = self._budget
        if budget <= 0:
            return
        rep = audit_graph(graph)
        if rep.peak_bytes <= budget:
            return
        top = ", ".join(
            f"{b.label} {b.bytes / (1 << 20):.1f} MiB"
            for b in rep.peak_buffers(3))
        src = "auto device-row" if self._auto else "configured"
        yield self.diag(
            f"predicted peak HBM {rep.peak_bytes / (1 << 20):.2f} MiB "
            f"per chip exceeds the {src} "
            f"{budget / (1 << 20):.2f} MiB budget "
            f"(peak at {rep.peak_where}; largest: {top})",
            where=graph.name,
            hint="shrink the pool budget / batch, donate threaded "
                 "buffers, shard with FLAGS_serving_mp, or raise "
                 "TPU702.hbm_budget_bytes if the headroom is real")


@register_rule
class LiveRangeBloatRule(Rule):
    """TPU703: an intermediate buffer held live across many equations.
    Long-lived big intermediates are what double-buffered/overlapped
    schedules pay for twice — and the usual remat / free-earlier
    candidates (an activation kept for one late consumer, a gather
    result outliving the loop that produced it).

    Config: `min_bytes` (default 1 MiB), `max_live_eqns` (default
    150)."""

    id = "TPU703"
    name = "live-range-bloat"
    default_severity = Severity.WARNING
    MIN_BYTES = 1 << 20
    MAX_LIVE_EQNS = 150
    MAX_REPORTS = 4

    def check(self, graph: Graph) -> Iterator[Diagnostic]:
        min_bytes = int(self.config.get("min_bytes", self.MIN_BYTES))
        span_cap = int(self.config.get("max_live_eqns",
                                       self.MAX_LIVE_EQNS))
        rep = audit_graph(graph)
        found = []
        for b in rep.buffers:
            if b.kind != "intermediate" or b.is_output:
                continue
            if b.bytes < min_bytes:
                continue
            span = b.last_use_t - max(b.def_t, 0)
            if span >= span_cap:
                found.append((span, b))
        found.sort(key=lambda x: -x[0] * x[1].bytes)
        for span, b in found[:self.MAX_REPORTS]:
            yield self.diag(
                f"{b.dtype}{list(b.shape)} "
                f"({b.bytes / (1 << 20):.2f} MiB) stays live across "
                f"{span} equations (defined at t={b.def_t}, last used "
                f"t={b.last_use_t}) — rematerialize or free it earlier",
                where=b.label,
                hint="recompute at the late consumer (jax.checkpoint) "
                     "or restructure so the value is consumed near its "
                     "definition; raise TPU703.max_live_eqns if the "
                     "overlap is deliberate (double buffering)")
        if len(found) > self.MAX_REPORTS:
            yield self.diag(
                f"{len(found) - self.MAX_REPORTS} more long-lived "
                f"buffer(s) elided (first {self.MAX_REPORTS} shown)",
                where=graph.name)
