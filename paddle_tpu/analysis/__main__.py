"""CLI: lint (and memory-audit) a model factory from the command line.

    python -m paddle_tpu.analysis                       # bundled llama demo
    python -m paddle_tpu.analysis mypkg.models:factory  # your factory
    python -m paddle_tpu.analysis mypkg.models:Net --shape 1,128:int32
    python -m paddle_tpu.analysis --memory --format json   # CI schema
    python -m paddle_tpu.analysis --comms --format json    # wire-side twin
    python -m paddle_tpu.analysis --roofline --format json  # compute-time leg
    python -m paddle_tpu.analysis --roofline --device tpu-v5p
    python -m paddle_tpu.analysis --tune --format json      # autotuner demo
    python -m paddle_tpu.analysis --tune --device tpu-v5e --budget-candidates 8
    python -m paddle_tpu.analysis --rule-config TPU401.max_collective_bytes=65536
    python -m paddle_tpu.analysis --comms --rule-config TPU801.max_step_wire_bytes=1048576

A factory is any zero-arg callable in an importable module. It may
return:
  - ``(fn, args)`` or ``(fn, args, kwargs)``: `fn` is linted called with
    those example arguments (arrays, Tensors, or ShapeDtypeStructs);
  - a bare callable / `Layer`: example inputs then come from ``--shape``
    (repeatable, ``dims:dtype``).

``--memory`` additionally runs the static memory auditor
(`analysis/memory.py`): the target is traced donation-aware (a jitted
factory's `donate_argnums` are recovered from the pjit equation), the
TPU701/702/703 rules see real donation info, and the output gains the
peak-HBM estimate + per-buffer breakdown. With no target, ``--memory``
audits the bundled tiny-llama PAGED DECODE program (the serving
engine's donated decode chunk) instead of the plain forward — the
program whose donation/pool accounting the auditor exists for.

``--comms`` runs the static COMMUNICATION auditor (`analysis/comms.py`):
bytes-on-wire per chip with the ring cost model, loop amplification,
and the TPU801/802/803 rules riding the same trace. With no target it
audits the bundled tiny-llama SHARDED decode program at mp=2 — the
one-all-gather-per-layer program the wire accounting exists for; on a
single-device host it notes the downgrade and audits the mp=1 decode
program instead (zero collectives, still valid output + exit 0).

``--roofline`` runs the static ROOFLINE auditor (`analysis/roofline.py`):
per-eqn FLOPs/HBM-bytes against the ``--device`` spec row
(`analysis/device_specs.py`; default: detect a live TPU, else the v5e
baseline), predicted step latency + MFU + bound class, and the
TPU901/902/903 rules riding the same trace. With no target it audits
the bundled tiny-llama PAGED DECODE program (same demo as ``--memory``
— the bandwidth-bound program the roofline exists to classify).

``--tune`` runs the auditor-driven static autotuner (`analysis/
tuner.py`) over the bundled tiny-llama serving demo: enumerate the
engine config space (block size, kv dtype, unified step,
quantized collectives, token budget), prune over-HBM candidates
against a demo budget chosen to exercise BOTH feasibility gates
(static params+pool bound before tracing, traced liveness peak
after), rank the rest by predicted step time then wire bytes, then
lint the decode program of an engine rebuilt THROUGH the winning
`TunedConfig` artifact. ``--tune-out PATH`` saves the artifact;
``--budget-candidates N`` caps the scored set. Exit status follows
``--fail-on`` against the winner's lint findings.

``--rule-config KEY=VALUE`` (repeatable) passes rule knobs: bare keys
reach every rule (``max_collective_bytes=65536``), ``TPUxxx.``-prefixed
keys reach one rule (``TPU702.hbm_budget_bytes=2147483648``,
``TPU801.max_step_wire_bytes=...``, ``TPU803.min_bytes=...``). Values
parse as int, float, true/false, or string.

``--format json`` prints one machine-readable object
(`Report.to_json()` schema, plus a ``memory`` key under ``--memory``
and a ``comms`` key under ``--comms``) so CI can gate on exit status
AND diff the findings. Exit status is 1 when any diagnostic reaches
``--fail-on`` (default: error) — the scriptable gate.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys


def _parse_shape(spec: str):
    import jax
    import jax.numpy as jnp

    dims, _, dtype = spec.partition(":")
    shape = tuple(int(d) for d in dims.split(",") if d)
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype or "float32"))


def _parse_rule_config(pairs):
    """KEY=VALUE strings -> a rule_config dict with typed values."""
    out = {}
    for pair in pairs:
        key, sep, raw = pair.partition("=")
        if not sep or not key:
            raise SystemExit(
                f"--rule-config expects KEY=VALUE, got {pair!r}")
        val: object = raw
        low = raw.lower()
        if low in ("true", "false"):
            val = low == "true"
        else:
            for cast in (int, float):
                try:
                    val = cast(raw)
                    break
                except ValueError:
                    continue
        out[key] = val
    return out


def _llama_demo():
    """Default lint target: the bundled tiny-llama forward pass."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig.tiny())
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    return model, (ids,), {}


def _tiny_serving_setup(**overrides):
    """ONE builder behind every serving-side demo target (--memory,
    --comms, --roofline, --tune): the tiny-llama config, its params,
    and the shared demo engine geometry. Overrides layer demo-specific
    knobs (serving_mp for the comms demo, block_size/unified_step for
    the tune demo) on top of the ONE base, so the demos audit the same
    engine instead of four drifting copies of its kwargs."""
    from ..models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    kw = dict(slots=2, prompt_bucket=16, max_prompt_len=32,
              max_new_tokens=8, block_size=16, steps_per_sync=4)
    kw.update(overrides)
    return cfg, dict(model.raw_state()), kw


def _tiny_engine(**overrides):
    from ..serving import ContinuousBatchingEngine

    cfg, params, kw = _tiny_serving_setup(**overrides)
    return ContinuousBatchingEngine(cfg, params, **kw)


def _decode_demo():
    """Default --memory target: the tiny-llama PAGED DECODE program —
    the serving engine's jitted decode chunk with its donated KV pools,
    exactly what the donation/peak-HBM audit exists to check."""
    eng = _tiny_engine()
    return eng._decode, eng._decode_example_args(), {}


def _sharded_decode_demo(quantized=False):
    """Default --comms target: the tiny-llama paged decode program
    SHARDED at mp=2 — one o-proj activation all-gather per layer inside
    the decode scan, the program the bytes-on-wire accounting exists
    for. Single-device hosts cannot build an mp=2 mesh: note the
    downgrade and audit the mp=1 program (zero collectives) so the
    schema + exit-status gate stay scriptable everywhere.
    `quantized=True` builds the FLAGS_quantized_collectives twin
    (ISSUE 15: int8 payload + f32 scale sidecar on the gather) — the
    CLI audits both and reports the wire-bytes ratio."""
    import jax

    mp = 2 if len(jax.devices()) >= 2 else 1
    if mp == 1 and not quantized:
        print("note: single-device host — auditing the mp=1 decode "
              "program (zero collectives); run with >= 2 devices "
              "(e.g. XLA_FLAGS=--xla_force_host_platform_device_count"
              "=2) for the sharded mp=2 demo", file=sys.stderr)
    eng = _tiny_engine(serving_mp=mp, quantized_collectives=quantized)
    tag = "+int8coll" if quantized else ""
    return (eng._decode, eng._decode_example_args(), {},
            f"models.llama tiny sharded decode (mp={mp}){tag}")


def _tune_demo(device=None, budget_candidates=None):
    """--tune target: autotune the tiny-llama engine (ISSUE 16). The
    demo baseline shrinks block_size to 8 (so a larger candidate class
    exists above it) and takes the split decode path (the unified
    step's chunk-prefill activations dwarf the tiny pools); the HBM
    budget is set just UNDER the largest candidate's static
    params+pool bound, so the demo provably exercises both gates:
    the top block-size class prunes BEFORE tracing on static bounds
    alone, the unified candidates prune on traced liveness peaks, and
    the all-defaults baseline stays feasible for the speedup
    comparison."""
    from . import tuner

    cfg, params, kw = _tiny_serving_setup(block_size=8,
                                          unified_step=False)
    space = tuner.default_space(cfg, kw)
    geo = tuner._engine_geometry(dict(kw))
    bounds = [tuner.static_candidate_bound(cfg, params, c, kw)
              for c in tuner.enumerate_candidates(space, geo)]
    report = tuner.autotune(
        cfg, params, engine_kwargs=kw, device=device,
        hbm_budget_bytes=max(bounds) - 1,
        budget_candidates=budget_candidates)
    return report, cfg, params, kw


def _resolve_target(spec, shapes, memory_mode=False, comms_mode=False,
                    roofline_mode=False):
    if spec is None:
        if comms_mode:
            return _sharded_decode_demo()
        if memory_mode or roofline_mode:
            return _decode_demo() + ("models.llama tiny paged decode",)
        return _llama_demo() + ("models.llama tiny forward",)
    mod_name, _, attr = spec.partition(":")
    if not attr:
        raise SystemExit(
            f"target {spec!r} must be module.path:factory_name")
    sys.path.insert(0, "")
    mod = importlib.import_module(mod_name)
    factory = getattr(mod, attr)
    obj = factory() if callable(factory) else factory
    if isinstance(obj, tuple):
        fn = obj[0]
        args = tuple(obj[1]) if len(obj) > 1 else ()
        kwargs = dict(obj[2]) if len(obj) > 2 else {}
    else:
        fn = obj
        args = tuple(_parse_shape(s) for s in shapes)
        kwargs = {}
    return fn, args, kwargs, spec


def _run_tune(args, rules, mesh_axes, rule_config) -> int:
    """--tune mode: autotune the bundled serving demo, lint the
    winner's decode program (the --fail-on gate prices the config the
    tuner actually recommends, not the default one), and emit the
    ranked TuningReport."""
    from . import Severity, analyze
    from .memory import trace_auto
    from .tuner import KNOBS

    if args.target is not None:
        raise SystemExit(
            "--tune runs the bundled tiny-llama serving demo; it does "
            "not take a target (tune your own model via "
            "paddle_tpu.analysis.autotune(cfg, params, ...))")
    report, cfg, params, kw = _tune_demo(
        device=args.device, budget_candidates=args.budget_candidates)
    tuned = report.tuned_config()
    if args.tune_out:
        path = tuned.save(args.tune_out)
        print(f"tuned config -> {path}", file=sys.stderr)
    # rebuild the engine THROUGH the artifact — the lint target is the
    # exact program `ContinuousBatchingEngine(config=...)` would serve
    from ..serving import ContinuousBatchingEngine

    geometry = {k: v for k, v in kw.items() if k not in KNOBS}
    eng = ContinuousBatchingEngine(cfg, dict(params), config=tuned,
                                   **geometry)
    label = "models.llama tiny paged decode (tuned)"
    graph = trace_auto(eng._decode, *eng._decode_example_args(),
                       name=label)
    lint = analyze(None, graph=graph, rules=rules, mesh_axes=mesh_axes,
                   rule_config=rule_config)
    if args.format == "json":
        out = lint.to_dict()
        out["tuning"] = report.to_dict()
        print(json.dumps(out, sort_keys=True, indent=2))
    else:
        print(report.format())
        print(lint.format(
            min_severity=Severity[args.min_severity.upper()]))
    if args.fail_on != "never" and \
            lint.at_least(Severity[args.fail_on.upper()]):
        return 1
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="jaxpr-level TPU lint + static memory audit for "
                    "paddle_tpu programs")
    parser.add_argument(
        "target", nargs="?", default=None,
        help="module.path:factory (default: bundled tiny-llama demo; "
             "with --memory: the tiny-llama paged decode program)")
    parser.add_argument(
        "--shape", action="append", default=[], metavar="DIMS[:DTYPE]",
        help="example input when the factory returns a bare callable, "
             "e.g. --shape 1,128:int32 (repeatable)")
    parser.add_argument(
        "--rules", default=None,
        help="comma-separated rule ids to run (default: all registered)")
    parser.add_argument(
        "--mesh-axes", default=None,
        help="comma-separated mesh axis names collectives may use")
    parser.add_argument(
        "--rule-config", action="append", default=[], metavar="KEY=VALUE",
        help="rule knob, repeatable: bare keys reach every rule "
             "(max_collective_bytes=65536), TPUxxx.-prefixed keys reach "
             "one rule (TPU702.hbm_budget_bytes=2147483648)")
    parser.add_argument(
        "--memory", action="store_true",
        help="also run the static memory auditor: donation-aware trace "
             "(TPU701 sees real donate_argnums), peak-HBM estimate + "
             "buffer breakdown in the output")
    parser.add_argument(
        "--comms", action="store_true",
        help="also run the static communication auditor: per-chip "
             "bytes-on-wire (ring cost model, loop amplification) + "
             "per-axis/per-kind splits in the output; with no target, "
             "audits the mp=2 tiny-llama sharded decode demo "
             "(single-device hosts note the downgrade and audit mp=1)")
    parser.add_argument(
        "--roofline", action="store_true",
        help="also run the static roofline auditor: per-eqn FLOPs/HBM "
             "bytes against the --device spec row, predicted step "
             "latency + MFU + bound class in the output; with no "
             "target, audits the tiny-llama paged decode demo")
    parser.add_argument(
        "--tune", action="store_true",
        help="run the auditor-driven static autotuner over the "
             "tiny-llama serving demo: enumerate the engine config "
             "space, prune over-HBM candidates, rank the rest by "
             "predicted step time (roofline) then wire bytes (comms), "
             "and lint the winning config's decode program; json "
             "output gains a 'tuning' key (TuningReport schema)")
    parser.add_argument(
        "--budget-candidates", type=int, default=None, metavar="N",
        help="with --tune: score at most N candidates (the all-"
             "defaults baseline always rides along for the speedup "
             "comparison)")
    parser.add_argument(
        "--tune-out", default=None, metavar="PATH",
        help="with --tune: save the winning TunedConfig artifact to "
             "PATH (a directory gets " + "'.paddle_tpu_tune.json'"
             + "; load it with ContinuousBatchingEngine(config=...) "
             "or FLAGS_tuned_config)")
    from .device_specs import DEVICE_SPECS

    parser.add_argument(
        "--device", default=None, choices=sorted(DEVICE_SPECS),
        help="device-spec row for --roofline / --tune (analysis/"
             "device_specs.py; default: detect a live TPU, else "
             "tpu-v5e)")
    parser.add_argument(
        "--format", default="text", choices=["text", "json"],
        help="output format; json prints one stable machine-readable "
             "object (Report.to_json schema + a 'memory' key under "
             "--memory, a 'comms' key under --comms, a 'roofline' key "
             "under --roofline)")
    parser.add_argument(
        "--fail-on", default="error",
        choices=["info", "warning", "error", "never"],
        help="exit 1 when a diagnostic reaches this severity")
    parser.add_argument(
        "--min-severity", default="info",
        choices=["info", "warning", "error"],
        help="hide diagnostics below this severity (text output)")
    args = parser.parse_args(argv)

    from . import Severity, analyze

    rules = args.rules.split(",") if args.rules else None
    mesh_axes = args.mesh_axes.split(",") if args.mesh_axes else None
    rule_config = _parse_rule_config(args.rule_config) or None
    if args.device:
        # the TPU90x rules run in EVERY mode (registered defaults), so
        # an explicit --device must price them against the requested
        # row even without --roofline; TPU702's auto-armed budget
        # likewise derives from the requested device row
        rule_config = dict(rule_config or {})
        for rid in ("TPU901", "TPU902", "TPU903", "TPU702"):
            rule_config.setdefault(f"{rid}.device", args.device)

    if args.tune:
        return _run_tune(args, rules, mesh_axes, rule_config)

    fn, call_args, call_kwargs, label = _resolve_target(
        args.target, args.shape, memory_mode=args.memory,
        comms_mode=args.comms, roofline_mode=args.roofline)

    mem_report = comms_report = roofline_report = None
    quantized_decode = None
    if args.memory or args.comms or args.roofline:
        # trace_auto, not trace_for_memory: a factory may return a
        # framework Layer, which only the lint tracer can thread. ONE
        # trace serves the lint rules AND every auditor.
        from .memory import audit_graph, trace_auto

        graph = trace_auto(fn, *call_args, name=label, **call_kwargs)
        report = analyze(None, graph=graph, rules=rules,
                         mesh_axes=mesh_axes, rule_config=rule_config)
        if args.memory:
            mem_report = audit_graph(graph)
        if args.comms:
            from .comms import audit_graph as comms_audit_graph

            comms_report = comms_audit_graph(graph)
            if args.target is None and comms_report.total_wire_bytes:
                # quantized-collectives twin (ISSUE 15): re-audit the
                # same demo decode with FLAGS_quantized_collectives ON
                # (int8 payload + f32 scale sidecar on the o-proj
                # gather) and record the wire-bytes ratio — the CI
                # gate asserts ~0.5x of the bf16 baseline via this
                # stable schema
                fq, aq, kq, lq = _sharded_decode_demo(quantized=True)
                qrep = comms_audit_graph(
                    trace_auto(fq, *aq, name=lq, **kq))
                quantized_decode = {
                    "target": lq,
                    "bytes_on_wire": qrep.total_wire_bytes,
                    "quantized_wire_bytes": qrep.quantized_wire_bytes,
                    "n_quantized_sites": qrep.n_quantized_sites,
                    "wire_bytes_ratio_vs_unquantized": round(
                        qrep.total_wire_bytes
                        / comms_report.total_wire_bytes, 4),
                }
        if args.roofline:
            from .roofline import audit_graph as roofline_audit_graph

            roofline_report = roofline_audit_graph(graph,
                                                   device=args.device)
    else:
        report = analyze(fn, *call_args, rules=rules, mesh_axes=mesh_axes,
                         rule_config=rule_config, name=label,
                         **call_kwargs)

    if args.format == "json":
        out = report.to_dict()
        if mem_report is not None:
            out["memory"] = mem_report.to_dict()
        if comms_report is not None:
            out["comms"] = comms_report.to_dict()
            if quantized_decode is not None:
                out["comms"]["quantized_decode"] = quantized_decode
        if roofline_report is not None:
            out["roofline"] = roofline_report.to_dict()
        print(json.dumps(out, sort_keys=True, indent=2))
    else:
        print(report.format(
            min_severity=Severity[args.min_severity.upper()]))
        if mem_report is not None:
            print(mem_report.format())
        if comms_report is not None:
            print(comms_report.format())
            if quantized_decode is not None:
                print(f"  int8coll twin: "
                      f"{quantized_decode['bytes_on_wire'] / 1024:.2f} "
                      f"KiB on wire = "
                      f"{quantized_decode['wire_bytes_ratio_vs_unquantized']}"
                      f"x the unquantized demo")
        if roofline_report is not None:
            print(roofline_report.format())
    if args.fail_on != "never" and \
            report.at_least(Severity[args.fail_on.upper()]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
