"""Graph capture + traversal for the lint pipeline.

`trace_graph` turns any framework callable (plain jnp function, Tensor
function, or a `Layer`) into a `Graph`: the `jax.make_jaxpr` closed jaxpr
plus the traversal/indexing helpers the rules share — recursive equation
walking through sub-jaxprs (pjit, scan, while, cond, custom_vjp,
shard_map, remat), a def/use map, and literal/constant inventories.

This is the TPU analog of the reference's PIR program view that its pass
pipeline walks (pir::Program + Block walkers); jaxpr is our IR, so the
walkers speak jaxpr.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# primitives whose sub-jaxprs execute repeatedly (hot loops)
LOOP_PRIMITIVES = frozenset({"scan", "while"})
# primitives whose sub-jaxprs we do NOT descend into: a pallas kernel
# body has Ref/memory-space semantics the array-level rules would
# misread; rules inspect the pallas_call equation itself instead
OPAQUE_PRIMITIVES = frozenset({"pallas_call"})


@dataclasses.dataclass(frozen=True)
class EqnCtx:
    """One equation in context: the eqn, where it lives, and whether it
    sits inside a loop body (scan/while at any enclosing depth)."""

    eqn: Any                 # jax JaxprEqn
    path: str                # "main/pjit[f]/eqn[3]:dot_general"
    depth: int
    in_loop: bool

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    @property
    def params(self) -> dict:
        return self.eqn.params


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(label, jaxpr) pairs for every sub-jaxpr hanging off `eqn`'s
    params, normalised to open Jaxprs."""
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vals):
            jxp = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
            if hasattr(jxp, "eqns") and hasattr(jxp, "invars"):
                label = k if len(vals) == 1 else f"{k}[{i}]"
                out.append((label, jxp))
    return out


class Graph:
    """A traced program plus the shared indexes rules consume."""

    def __init__(self, closed_jaxpr, name: str = "main",
                 example_args: Optional[tuple] = None,
                 scalar_args: Optional[List[Tuple[Any, str]]] = None,
                 donated_invars: Optional[Tuple[bool, ...]] = None):
        self.closed_jaxpr = closed_jaxpr
        self.jaxpr = closed_jaxpr.jaxpr
        self.consts = list(closed_jaxpr.consts)
        self.name = name
        self.example_args = example_args
        # per-invar jit donation mask, known only on the memory-audit
        # trace path (`memory.trace_for_memory`); None means "jit
        # options unknown" — the donation-miss rule (TPU701) then stays
        # quiet rather than guessing
        self.donated_invars = donated_invars
        # python-scalar call arguments as (value, label) pairs — a list,
        # not a dict: 2 and 2.0 hash equal and must stay distinct. The
        # recompile-risk rule hunts for these values among the captured
        # literals; None means "not traced by us, no argument info".
        self.scalar_args = scalar_args
        self._eqns: Optional[List[EqnCtx]] = None
        self._use_counts: Optional[Dict[int, int]] = None
        self._var_uses: Optional[Dict[int, List[EqnCtx]]] = None

    # -- traversal -----------------------------------------------------
    def eqns(self) -> List[EqnCtx]:
        """Every equation in the program, sub-jaxprs included, in
        execution order."""
        if self._eqns is None:
            acc: List[EqnCtx] = []
            self._walk(self.jaxpr, self.name, 0, False, acc)
            self._eqns = acc
        return self._eqns

    def _walk(self, jaxpr, path: str, depth: int, in_loop: bool,
              acc: List[EqnCtx]):
        for i, eqn in enumerate(jaxpr.eqns):
            prim = eqn.primitive.name
            acc.append(EqnCtx(eqn=eqn, path=f"{path}/eqn[{i}]:{prim}",
                              depth=depth, in_loop=in_loop))
            if prim in OPAQUE_PRIMITIVES:
                continue
            child_in_loop = in_loop or prim in LOOP_PRIMITIVES
            for label, sub in _sub_jaxprs(eqn):
                tag = prim if prim != "jit" else _pjit_name(eqn)
                self._walk(sub, f"{path}/{tag}[{label}]", depth + 1,
                           child_in_loop, acc)

    # -- def/use indexes ----------------------------------------------
    def _build_uses(self):
        self._use_counts = {}
        self._var_uses = {}
        for ctx in self.eqns():
            for v in ctx.eqn.invars:
                if _is_var(v):
                    self._use_counts[id(v)] = \
                        self._use_counts.get(id(v), 0) + 1
                    self._var_uses.setdefault(id(v), []).append(ctx)

        def mark_outputs(jaxpr):
            for v in jaxpr.outvars:
                if _is_var(v):
                    self._use_counts[id(v)] = \
                        self._use_counts.get(id(v), 0) + 1
            for eqn in jaxpr.eqns:
                if eqn.primitive.name in OPAQUE_PRIMITIVES:
                    continue
                for _, sub in _sub_jaxprs(eqn):
                    mark_outputs(sub)

        mark_outputs(self.jaxpr)

    def use_count(self, var) -> int:
        """How many times `var` is consumed (by later eqns or as an
        output of its jaxpr)."""
        if self._use_counts is None:
            self._build_uses()
        return self._use_counts.get(id(var), 0)

    def consumers(self, var) -> List[EqnCtx]:
        if self._var_uses is None:
            self._build_uses()
        return self._var_uses.get(id(var), [])

    # -- constants / literals -----------------------------------------
    def scalar_literals(self) -> List[Tuple[Any, EqnCtx]]:
        """(Literal, ctx) for every scalar literal operand."""
        out = []
        for ctx in self.eqns():
            for v in ctx.eqn.invars:
                if not _is_var(v) and getattr(v, "aval", None) is not None \
                        and v.aval.shape == ():
                    out.append((v, ctx))
        return out

    def captured_consts(self) -> List[Tuple[Any, Any]]:
        """(constvar, value) pairs captured from the python closure."""
        return list(zip(self.jaxpr.constvars, self.consts))


def _is_var(v) -> bool:
    # Literals carry .val; Vars do not
    return not hasattr(v, "val")


def _pjit_name(eqn) -> str:
    name = eqn.params.get("name")
    return f"pjit:{name}" if name else "pjit"


def trace_graph(fn: Callable, *args, name: Optional[str] = None,
                scalar_args: Optional[List[Tuple[Any, str]]] = None,
                **kwargs) -> Graph:
    """Trace `fn(*args, **kwargs)` to a `Graph` without executing it on
    device. Accepts Tensors, jax arrays, numpy arrays, and
    `jax.ShapeDtypeStruct` placeholders as array leaves. When `fn` is a
    `Layer` (or a bound Layer method) its parameters and buffers are
    threaded as inputs — matching how `jit/api.py` compiles it, so
    weights do not read as captured constants. Python scalars stay in
    the closure, exactly as `jax.jit` would treat them — which is what
    the recompile-risk rule wants to inspect.
    """
    from ..core.tensor import Tensor, unwrap
    from ..core import tape as _tape

    def is_leaf(x):
        return isinstance(x, Tensor)

    flat, treedef = jax.tree.flatten((args, kwargs), is_leaf=is_leaf)
    arr_pos = [i for i, a in enumerate(flat)
               if isinstance(a, (Tensor, jax.Array, np.ndarray,
                                 jax.ShapeDtypeStruct))]
    # callers that wrap the real user function (jit/api.py) pass the
    # user-level python scalars explicitly; otherwise collect them from
    # this call's own non-array leaves
    if scalar_args is None:
        scalar_args = []
        for i, a in enumerate(flat):
            if i not in arr_pos and isinstance(a, (int, float)) \
                    and not isinstance(a, bool):
                scalar_args.append((a, f"arg[{i}]"))
    else:
        scalar_args = list(scalar_args)

    # Layer state rides as inputs, like StaticFunction._trace
    layer = None
    try:
        from ..nn.layer.layers import Layer

        if isinstance(fn, Layer):
            layer = fn
        elif isinstance(getattr(fn, "__self__", None), Layer):
            layer = fn.__self__
    except Exception:
        pass
    state: List[Any] = []
    if layer is not None:
        state = list(layer.parameters(include_sublayers=True)) \
            + [b for _, b in layer.named_buffers()]

    def pure(*arrays):
        s_arr, in_arr = arrays[:len(state)], arrays[len(state):]
        saved = [t._array for t in state]
        for t, a in zip(state, s_arr):
            t._array = a
        try:
            flat2 = list(flat)
            for pos, a in zip(arr_pos, in_arr):
                flat2[pos] = Tensor(a) if isinstance(flat[pos], Tensor) \
                    else a
            call_args, call_kwargs = jax.tree.unflatten(treedef, flat2)
            with _tape.no_grad():
                out = fn(*call_args, **call_kwargs)
        finally:
            for t, a in zip(state, saved):
                t._array = a
        leaves = jax.tree.leaves(out, is_leaf=is_leaf)
        return tuple(unwrap(o) if isinstance(o, Tensor) else o
                     for o in leaves)

    def spec(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        a = unwrap(x) if isinstance(x, Tensor) else x
        a = jnp.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    in_specs = [spec(t) for t in state] + [spec(flat[i]) for i in arr_pos]
    closed = jax.make_jaxpr(pure)(*in_specs)
    if name is None:
        name = getattr(fn, "__name__", None) or type(fn).__name__
    return Graph(closed, name=name,
                 example_args=tuple(spec(flat[i]) for i in arr_pos),
                 scalar_args=scalar_args)
