"""What the `mellum2-reason-long` readers share: the window's deltas of the
engine's routed-layer counters (`raw["moe"]`), the pools' occupancy sampled
once a scheduling step (`raw["gauges"]`: the window's mean;
`raw["gauges_traced"]`: the mean over the steps the trace holds, for the
readers that divide by the trace's seconds), and the traced scheduling steps.
A program that counts none of it (the parent of the PR that brought the
cell) gives these readers nothing to read."""
import importlib.util
import os

from benchmark import arith_mellum as arith


def counters(ctx):
    c = ctx.get("moe") or {}
    return c if c.get("moe_layer_steps") else None


def traced_steps(ctx) -> int:
    """Scheduling steps the trace holds (one `bench.step` host span each)."""
    return ctx["trace"]["host_spans"].get("step", 0)


def traced_mixed_steps(ctx) -> int:
    """Of them, the steps that carried a prefill window (the mixed step is
    a program of its own)."""
    return sum(len(ds) for name, ds in ctx["trace"]["programs"].items()
               if "serve_unified_step" in name)


def label_seconds(ctx, names) -> float:
    """Summed device time of the trace's labels whose first word (the
    kernel's name) is one of `names`."""
    return sum(s for label, s in ctx["trace"]["device_op_s"].items()
               if label.split()[0] in names)


def hit_per_layer_step(c, lane: str = ""):
    """Experts with at least one row, a routed layer each time it ran."""
    steps = c["moe_layer_steps" + lane]
    return c["moe_experts_hit" + lane] / steps if steps else None


def layers(ctx) -> int:
    return arith.depth(ctx["config"])


def same_as(metric: str):
    """The `read` of the accepted reader `readers/<metric>.py`, for a metric
    of this cell that reads the same thing. The accepted entries
    `sched.{host,commit,admit}_ms.sat` and `step.{decode,mixed}_dev_ms.sat`
    cannot take the cell's name at the end of their `workloads`:
    `tests/benchmark/test_benchmark_phase_readers.py` holds those lists to
    their one cell, and a PR that adds a cell may edit no accepted file. So
    the cell has entries of its own, and their readers are these aliases."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.readers." + metric.replace(".", "_"),
        os.path.join(os.path.dirname(__file__), metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
