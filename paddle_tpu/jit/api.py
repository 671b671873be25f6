"""paddle.jit.to_static — the TPU-native jit story.

Reference: python/paddle/jit/api.py:182 (`to_static`) with two front-ends:
AST transform (dy2static/program_translator.py:783) and the SOT bytecode
tracer (jit/sot/). On TPU neither is needed: because *every* op funnels
through the pure-jnp dispatch layer, plain `jax.jit` tracing of the user
function is the graph capture. What we keep from SOT is its *contract* —
guard-based re-specialisation and a compiled-program cache
(jit/sot/opcode_translator/executor/guard.py, executor_cache.py): the cache
key ("guard") is the treedef + shape/dtype of tensor args plus the values of
plain-Python args, and a miss re-traces instead of graph-breaking.

Training is supported: the traced callable is routed through core dispatch,
so `jax.vjp` of the jitted function records on the eager tape and
`loss.backward()` works across a to_static boundary. Layer buffers (e.g.
BatchNorm running stats) are threaded as extra outputs and written back.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, dispatch, unwrap
from ..core import tape as _tape
from ..framework import random as _random


def _guard_key(args, kwargs):
    """Build the specialisation key (SOT guard analog)."""

    def leaf_key(x):
        if isinstance(x, Tensor):
            return ("T", tuple(x.shape), str(x.dtype), x.stop_gradient)
        if isinstance(x, (jax.Array, np.ndarray)):
            return ("A", tuple(x.shape), str(x.dtype))
        if isinstance(x, (int, float, bool, str, bytes, type(None))):
            return ("P", x)
        if isinstance(x, (list, tuple)):
            return (type(x).__name__, tuple(leaf_key(i) for i in x))
        if isinstance(x, dict):
            return ("D", tuple(sorted((k, leaf_key(v)) for k, v in x.items())))
        return ("O", id(type(x)))

    return (tuple(leaf_key(a) for a in args), leaf_key(kwargs))


_TO_STATIC_ENABLED = True


def enable_to_static(enable: bool = True):
    """Global switch (reference: jit/api.py `enable_to_static`): when off,
    every StaticFunction runs its original eager python body."""
    global _TO_STATIC_ENABLED
    _TO_STATIC_ENABLED = bool(enable)


class StaticFunction:
    """Compiled-function wrapper (reference:
    python/paddle/jit/dy2static/program_translator.py:711
    `SymbolicStaticFunction.__call__`)."""

    def __init__(self, fn: Callable, input_spec=None, build_strategy=None,
                 full_graph=True, layer=None, lint=None):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._full_graph = full_graph
        # None = follow FLAGS_tpu_lint / PADDLE_TPU_LINT; True/False force
        self._lint = lint
        self._fallback_keys = set()  # guard keys that stay eager
        self._break_keys = set()     # guard keys that cannot trace whole
        self._cache = {}  # guard key -> (jitted, n_params, n_buffers, out_treedef)
        # guard key -> list of compiled PATHS (SOT sub-graph analog):
        # each entry replays one recorded control-flow path with value
        # guards re-checked on device outputs
        self._paths = {}
        self._capture_counts = {}
        functools.update_wrapper(self, fn)

    _MAX_PATHS = 8

    @property
    def layer(self):
        if self._layer is not None:
            return self._layer
        # bound method of a Layer?
        self_obj = getattr(self._fn, "__self__", None)
        from ..nn.layer.layers import Layer

        if isinstance(self_obj, Layer):
            return self_obj
        return None

    def _collect_state(self):
        layer = self.layer
        if layer is None:
            return [], []
        params = list(layer.parameters(include_sublayers=True))
        buffers = [b for _, b in layer.named_buffers()]
        return params, buffers

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            return self._fn(*args, **kwargs)
        params, buffers = self._collect_state()
        key = _guard_key(args, kwargs)
        if key in self._fallback_keys:
            return self._fn(*args, **kwargs)
        if key in self._break_keys:
            return self._path_call(key, params, buffers, args, kwargs,
                                   None)
        entry = self._cache.get(key)
        if entry is None:
            try:
                entry = self._trace(params, buffers, args, kwargs)
            except (jax.errors.TracerBoolConversionError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerArrayConversionError,
                    jax.errors.TracerIntegerConversionError) as e:
                # SOT graph-break contract: data-dependent control flow
                # can't trace whole. Instead of staying eager, compile
                # per-PATH: record the executed op sequence + the scalar
                # values that steered python, replay it jitted, and
                # re-validate those values on every call (value guards).
                if self._full_graph:
                    raise
                if self._lint_enabled():
                    import warnings
                    warnings.warn(
                        f"to_static lint: {self._fn.__name__} "
                        "graph-breaks (data-dependent control flow); "
                        "path-compiled specialisations are NOT linted")
                self._break_keys.add(key)
                return self._path_call(key, params, buffers, args, kwargs,
                                       e)
            self._cache[key] = entry
        jitted, out_treedef, n_out = entry

        flat_args, _ = jax.tree.flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
        )
        tensor_args = [a for a in flat_args if isinstance(a, Tensor)]

        # thread a fresh PRNG key so dropout etc. varies between calls without
        # retracing (keys-as-generator; see framework/random.py)
        all_inputs = [_random.next_key()] + params + tensor_args + buffers

        try:
            outs = dispatch(f"to_static:{self._fn.__name__}", jitted,
                            tuple(all_inputs))
        except jax.errors.JaxRuntimeError as e:
            # some PJRT runtimes reject host callbacks inside compiled programs; treat that as
            # a graph break rather than a hard failure
            if "does not support host send/recv" not in str(e):
                raise
            if self._full_graph:
                raise
            import warnings
            warnings.warn(
                f"to_static: graph break in {self._fn.__name__} (backend "
                "does not support host callbacks under jit); running this "
                "specialisation eagerly")
            self._fallback_keys.add(key)
            self._cache.pop(key, None)
            return self._fn(*args, **kwargs)
        if not isinstance(outs, tuple):
            outs = (outs,)
        # write back updated buffers
        new_buf = outs[n_out:]
        for b, nb in zip(buffers, new_buf):
            b._replace(nb._array)
        result = jax.tree.unflatten(out_treedef, list(outs[:n_out]))
        return result

    # ------------------------------------------------------------------
    # path specialisation (the SOT sub-graph analog): one compiled replay
    # per executed control-flow path, guarded by the scalar values that
    # steered python during capture
    # ------------------------------------------------------------------
    def _flat_feed(self, params, buffers, args, kwargs):
        """Tensor leaves of the call, in stable order. Raw ndarray leaves
        are rejected (None): the capture keys placeholders by array object
        identity, which dispatch only preserves for Tensor._array."""
        flat_args, _ = jax.tree.flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        tensors = []
        for a in flat_args:
            if isinstance(a, Tensor):
                tensors.append(a)
            elif isinstance(a, (jax.Array, np.ndarray)):
                return None
        return tensors + list(params) + list(buffers)

    def _run_entry(self, entry, feed, buffers):
        """Run one compiled path; returns the unflattened result when its
        value guards hold, else None."""
        (replay, ctrl_vals, out_treedef, n_out, n_buf, extra_refs, _,
         mut_spec) = entry
        extra = []
        for ref in extra_refs:
            t = ref()
            if t is None:
                return None  # a closure tensor died; path unusable
            extra.append(t)
        try:
            outs = dispatch(f"to_static_path:{self._fn.__name__}", replay,
                            tuple(feed) + tuple(extra))
        except Exception:
            return None  # backend rejected the replay; falls to capture
        outs = outs if isinstance(outs, tuple) else (outs,)
        n_mut = len(mut_spec)
        got = [np.asarray(unwrap(o)).reshape(()).item()
               for o in outs[n_out + n_buf + n_mut:]]
        if got != ctrl_vals:
            return None
        for b, nb in zip(buffers, outs[n_out:n_out + n_buf]):
            b._replace(unwrap(nb))
        for (kind, idx), nv in zip(mut_spec,
                                   outs[n_out + n_buf:
                                        n_out + n_buf + n_mut]):
            tgt = feed[idx] if kind == "feed" else extra[idx]
            tgt._replace(unwrap(nv))
        return (jax.tree.unflatten(out_treedef, list(outs[:n_out])),)

    def _path_call(self, key, params, buffers, args, kwargs, err=None):
        if key in self._fallback_keys:
            return self._fn(*args, **kwargs)
        feed = self._flat_feed(params, buffers, args, kwargs)
        if feed is None:
            self._fallback_keys.add(key)
            return self._fn(*args, **kwargs)
        paths = self._paths.setdefault(key, [])
        # speculative replay, most-recently-hit first: run the compiled
        # path, then check its recorded control values still hold
        for i, entry in enumerate(paths):
            hit = self._run_entry(entry, feed, buffers)
            if hit is not None:
                if i:
                    paths.insert(0, paths.pop(i))
                return hit[0]
        # re-capture churn cap: exact-value guards (item()/float() reads
        # that change every batch, e.g. loss logging) would otherwise pay
        # capture + compile on EVERY call
        n_cap = self._capture_counts.get(key, 0)
        if n_cap >= self._MAX_PATHS:
            import warnings

            warnings.warn(
                f"to_static: {self._fn.__name__} keeps taking new paths "
                "(value guards never stabilize); this specialisation "
                "stays eager")
            self._fallback_keys.add(key)
            self._paths.pop(key, None)
            return self._fn(*args, **kwargs)
        self._capture_counts[key] = n_cap + 1
        # snapshot feed arrays: the capture run applies any in-place
        # effects, and the replay below must start from PRE-call state or
        # those effects double-apply on this call
        pre = [t._array for t in feed]
        entry, result = self._capture_path(key, params, buffers, args,
                                           kwargs, feed)
        if entry is None:
            # impure capture: the capture run itself was a valid eager
            # execution (with tape) — return it, do NOT run fn twice
            return result
        for t, a in zip(feed, pre):
            t._array = a
        for ref, a in zip(entry[5], entry[6]):
            if ref() is not None:
                ref()._array = a
        paths.insert(0, entry)
        if len(paths) > self._MAX_PATHS:
            paths.pop()
        hit = self._run_entry(entry, feed, buffers)
        if hit is None:  # pragma: no cover — replay must match itself
            self._fallback_keys.add(key)
            return result
        return hit[0]

    def _capture_path(self, key, params, buffers, args, kwargs, feed):
        """Run the fn eagerly under a Program capture; build a jitted
        replay of (outputs, new buffers, control scalars). Returns
        (path entry or None, this run's result) — the capture run keeps
        the tape, so when the capture turns out impure its result is a
        full eager execution the caller can return directly."""
        from ..core import tensor as _ct
        from ..static import Program

        prog = Program()
        pre_feed = [t._array for t in feed]  # pre-capture values
        for i, t in enumerate(feed):
            prog._register_placeholder(f"in{i}", t._array)
        prev = _ct._static_capture[0]
        _ct._static_capture[0] = prog
        try:
            result = self._fn(*args, **kwargs)
        finally:
            _ct._static_capture[0] = prev

        out_leaves, out_treedef = jax.tree.flatten(
            result, is_leaf=lambda x: isinstance(x, Tensor))
        out_keys = []
        for leaf in out_leaves:
            arr = unwrap(leaf) if isinstance(leaf, Tensor) else leaf
            k = prog.key_of(arr) if hasattr(arr, "shape") else None
            if k is None:
                prog._mark_impure("output produced outside dispatch")
                break
            out_keys.append(k)
        buf_keys = [prog.key_of(b._array) for b in buffers]
        if any(k is None for k in buf_keys):
            prog._mark_impure("buffer updated outside dispatch")
        if prog._impure is not None:
            import warnings

            warnings.warn(
                f"to_static: graph break in {self._fn.__name__} is not "
                f"path-compilable ({prog._impure}); this specialisation "
                "stays eager")
            self._fallback_keys.add(key)
            return None, result

        ctrl_keys = [k for k, _ in prog._controls]
        feed_keys = [prog._placeholders[f"in{i}"] for i in range(len(feed))]
        nodes = list(prog._nodes)
        literals = dict(prog._literals)
        # promote Tensor-owned literals (closure-layer params/buffers the
        # guard's layer introspection didn't see) to LIVE-fed inputs:
        # frozen copies would go stale after optimizer updates and block
        # autograd
        extra_refs = []
        extra_pre = []
        for k, ref in prog._literal_owner.items():
            if ref() is not None and k in literals:
                extra_pre.append(literals.pop(k))
                feed_keys.append(k)
                extra_refs.append(ref)
        # in-place mutations: any fed/closure tensor whose array changed
        # during the capture must have its NEW value among the replay
        # outputs, written back per call (counter.add_() and friends)
        mut_spec = []
        mut_keys = []
        for i, (t, a) in enumerate(zip(feed, pre_feed)):
            if t._array is not a:
                k = prog.key_of(t._array)
                if k is None:
                    prog._mark_impure("input mutated outside dispatch")
                    break
                mut_spec.append(("feed", i))
                mut_keys.append(k)
        for j, (ref, a) in enumerate(zip(extra_refs, extra_pre)):
            t = ref()
            if t is not None and t._array is not a:
                k = prog.key_of(t._array)
                if k is None:
                    prog._mark_impure("closure tensor mutated outside "
                                      "dispatch")
                    break
                mut_spec.append(("extra", j))
                mut_keys.append(k)
        if prog._impure is not None:
            import warnings

            warnings.warn(
                f"to_static: graph break in {self._fn.__name__} is not "
                f"path-compilable ({prog._impure}); this specialisation "
                "stays eager")
            self._fallback_keys.add(key)
            return None, result
        all_out = out_keys + buf_keys + mut_keys + ctrl_keys

        def replay(*vals):
            env = dict(literals)
            for k, v in zip(feed_keys, vals):
                env[k] = v
            for fn_, in_keys, out_ks in nodes:
                res = fn_(*[None if k is None else env[k]
                            for k in in_keys])
                if not isinstance(res, tuple):
                    res = (res,)
                for k, o in zip(out_ks, res):
                    env[k] = o
            return tuple(env[k] for k in all_out)

        replay = jax.jit(replay)
        # guard values must come from the COMPILED replay (fusion can
        # shift float scalars a ulp vs the eager capture; an eager-valued
        # guard would miss forever and re-capture every call). The feed
        # uses PRE-capture arrays: the capture run may have mutated them.
        try:
            outs0 = replay(*(pre_feed + extra_pre))
        except Exception as e:
            import warnings

            warnings.warn(
                f"to_static: compiled path for {self._fn.__name__} failed "
                f"({type(e).__name__}: {str(e)[:120]}); this "
                "specialisation stays eager")
            self._fallback_keys.add(key)
            return None, result
        ctrl_vals = [np.asarray(o).reshape(()).item()
                     for o in outs0[len(out_keys) + len(buf_keys)
                                    + len(mut_keys):]]
        return (replay, ctrl_vals, out_treedef, len(out_keys),
                len(buf_keys), extra_refs, extra_pre, mut_spec), result

    def _trace(self, params, buffers, args, kwargs):
        fn = self._fn
        n_p, n_b = len(params), len(buffers)
        flat_args, args_treedef = jax.tree.flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor)
        )
        tensor_pos = [i for i, a in enumerate(flat_args) if isinstance(a, Tensor)]
        const_args = [a if not isinstance(a, Tensor) else None for a in flat_args]

        out_info = {}

        def pure(key, *arrays):
            p_arr = arrays[:n_p]
            t_arr = arrays[n_p : n_p + len(tensor_pos)]
            b_arr = arrays[n_p + len(tensor_pos) :]
            # bind state
            saved_p = [p._array for p in params]
            saved_b = [b._array for b in buffers]
            for p, a in zip(params, p_arr):
                p._array = a
            for b, a in zip(buffers, b_arr):
                b._array = a
            flat = list(const_args)
            for pos, a in zip(tensor_pos, t_arr):
                t = Tensor(a)
                t.stop_gradient = flat_args[pos].stop_gradient
                flat[pos] = t
            call_args, call_kwargs = jax.tree.unflatten(args_treedef, flat)
            try:
                with _tape.no_grad(), _random.rng_scope(key):
                    out = fn(*call_args, **call_kwargs)
            finally:
                new_b = [b._array for b in buffers]
                for p, a in zip(params, saved_p):
                    p._array = a
                for b, a in zip(buffers, saved_b):
                    b._array = a
            out_leaves, out_treedef = jax.tree.flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor)
            )
            out_info["treedef"] = out_treedef
            out_info["n"] = len(out_leaves)
            return tuple(unwrap(o) for o in out_leaves) + tuple(new_b)

        jitted = jax.jit(pure)
        # prime: trace once at aval level (no execution) to learn out structure
        jax.eval_shape(
            pure,
            _random.next_key(),
            *[unwrap(p) for p in params],
            *[unwrap(flat_args[i]) for i in tensor_pos],
            *[unwrap(b) for b in buffers],
        )
        self._maybe_lint(pure, params, buffers, flat_args, tensor_pos)
        return jitted, out_info["treedef"], out_info["n"]

    def _lint_enabled(self) -> bool:
        """lint=True/False forces; None follows FLAGS_tpu_lint
        (PADDLE_TPU_LINT)."""
        if self._lint is not None:
            return bool(self._lint)
        from ..framework import flags as _flags

        try:
            return bool(_flags.flag("tpu_lint"))
        except KeyError:  # pragma: no cover
            return False

    def _maybe_lint(self, pure, params, buffers, flat_args, tensor_pos):
        """Opt-in trace-time lint (paddle_tpu.analysis): runs the rule
        pipeline over the SAME pure function jax.jit compiles, so what
        is linted is exactly what runs. Enabled per-function with
        `to_static(fn, lint=True)` or globally with PADDLE_TPU_LINT=1;
        severity policy from FLAGS_tpu_lint_fail_on."""
        from ..framework import flags as _flags

        if not self._lint_enabled():
            return
        from ..analysis import Severity, analyze

        # the user-level python scalars are baked into `pure`'s closure
        # (they are part of the guard key): hand them to the recompile
        # rule explicitly, labelled by their position in the call
        scalar_args = []
        for i, a in enumerate(flat_args):
            if isinstance(a, (int, float)) and not isinstance(a, bool):
                scalar_args.append((a, f"arg[{i}]"))
        # spec of the PRNG key WITHOUT consuming one: lint must not
        # shift the global key stream (seed-for-seed reproducibility)
        key_state = _random.get_rng_state()
        key_spec = jax.ShapeDtypeStruct(key_state.shape, key_state.dtype)
        report = analyze(
            pure,
            key_spec,
            *[unwrap(p) for p in params],
            *[unwrap(flat_args[i]) for i in tensor_pos],
            *[unwrap(b) for b in buffers],
            name=f"to_static:{self._fn.__name__}",
            scalar_args=scalar_args,
        )
        fail_on = str(_flags.flag("tpu_lint_fail_on")).lower()
        if fail_on == "never":
            fail = Severity.ERROR + 1  # nothing reaches it
        else:
            try:
                fail = Severity[fail_on.upper()]
            except KeyError:
                raise ValueError(
                    f"invalid FLAGS_tpu_lint_fail_on {fail_on!r}; "
                    "expected error|warning|info|never") from None
        report.raise_or_warn(fail_on=fail)

    # paddle parity helpers
    @property
    def code(self):
        return inspect.getsource(self._fn)

    def concrete_program_specify_input_spec(self, *a, **k):
        return None


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """paddle.jit.to_static (ref: python/paddle/jit/api.py:182).
    `full_graph=False` (the default, like the reference's SOT front-end)
    permits graph breaks: specialisations that cannot trace run eagerly.
    `lint=True` runs the paddle_tpu.analysis rule pipeline at trace time
    (default: follow the PADDLE_TPU_LINT env flag)."""
    full_graph = kwargs.pop("full_graph", False)
    lint = kwargs.pop("lint", None)

    def decorate(fn):
        from ..nn.layer.layers import Layer

        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, input_spec=input_spec,
                                full_graph=full_graph, layer=layer,
                                lint=lint)
            layer.forward = sf
            return layer
        return StaticFunction(fn, input_spec=input_spec,
                              full_graph=full_graph, lint=lint)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None


# ---------------------------------------------------------------------------
# jit.save / jit.load — deployment artifacts via StableHLO export
# ---------------------------------------------------------------------------


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save (ref: python/paddle/jit/api.py, TranslatedLayer
    artifacts). Serialises params (pickle) + a StableHLO export of the
    forward function when input_spec is given."""
    import pickle
    from ..framework.io import save as fsave

    fsave(layer.state_dict(), path + ".pdiparams")
    meta = {"class": type(layer).__name__}
    if input_spec:
        try:
            from jax import export as jexport

            params = [unwrap(p) for p in layer.parameters()]

            def pure(params_arr, *xs):
                saved = [p._array for p in layer.parameters()]
                for p, a in zip(layer.parameters(), params_arr):
                    p._array = a
                try:
                    with _tape.no_grad():
                        out = layer(*[Tensor(x) for x in xs])
                finally:
                    for p, a in zip(layer.parameters(), saved):
                        p._array = a
                return unwrap(out)

            specs = [
                jax.ShapeDtypeStruct(tuple(s.shape), s.dtype) for s in input_spec
            ]
            # multi-platform artifact: the deployment shell (native/
            # predictor_capi.cpp) may serve on a different backend than
            # the one that exported. A trace that took a TPU-only Pallas
            # fast path (Mosaic custom calls) cannot lower for "cpu" —
            # fall back to a single-platform export rather than failing
            # the save outright.
            pspecs = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params]
            try:
                exported = jexport.export(
                    jax.jit(pure), platforms=("cpu", "tpu"))(pspecs, *specs)
                meta["platforms"] = ["cpu", "tpu"]
            except Exception:
                exported = jexport.export(jax.jit(pure))(pspecs, *specs)
                meta["platforms"] = [jax.default_backend()]
            with open(path + ".pdmodel", "wb") as f:
                f.write(exported.serialize())
            meta["stablehlo"] = True
        except Exception as e:  # pragma: no cover
            meta["stablehlo"] = False
            meta["export_error"] = repr(e)
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """Loaded inference artifact (ref: python/paddle/jit/translated_layer.py)."""

    def __init__(self, exported, state_dict):
        self._exported = exported
        self._state = state_dict

    def __call__(self, *xs):
        params = [unwrap(v) for v in self._state.values()]
        out = self._exported.call(params, *[unwrap(x) for x in xs])
        return Tensor(out) if not isinstance(out, (tuple, list)) else tuple(Tensor(o) for o in out)

    def state_dict(self):
        return self._state


def load(path, **configs):
    import pickle
    from ..framework.io import load as fload

    state = fload(path + ".pdiparams")
    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    if meta.get("stablehlo"):
        from jax import export as jexport

        with open(path + ".pdmodel", "rb") as f:
            exported = jexport.deserialize(f.read())
        return TranslatedLayer(exported, state)
    raise ValueError(f"no serialized program at {path}.pdmodel; re-save with input_spec")
