"""What the `falconh1-chat-sat` readers share: the mixers' counts over the
steps the trace holds (`raw["ssm_traced"]`: the engine's `ssm_*` counters,
sampled once a scheduling step, their change across the traced steps), the
gauges of those steps (`raw["gauges_traced"]`) and the kernels' device
time. A program that counts no `ssm_*` (the parent of the PR that brought the
cell) gives these readers nothing to read."""
from benchmark import arith_falcon_h1 as arith
from benchmark.readers import _mellum

DECODE = ("ssd_decode",)
SCAN = ("ssd_scan",)

label_seconds = _mellum.label_seconds


def traced_counts(ctx):
    c = ctx.get("ssm_traced") or {}
    return c if c.get("ssm_decode_slot_steps") else None


def decode_steps(ctx) -> int:
    """Decode steps the trace holds: every scheduling step runs
    `steps_per_sync`."""
    return _mellum.traced_steps(ctx) * ctx["steps_per_sync"]


def rows_per_step(ctx):
    """Live sequences a decode step stepped, from the counts: slot-steps
    over the traced decode steps of every layer."""
    c, steps = traced_counts(ctx), decode_steps(ctx)
    if not c or not steps:
        return None
    return c["ssm_decode_slot_steps"] / (steps * arith.depth(ctx["config"]))
