"""The decode-attention kernel's share of its read bound: the time the chip's
memory would need for the K and V the traced decode steps must read, over the
device time of the `decode_attention...` operations in the trace.

Bytes: the KV part of `arith.decode_step_bytes` at the window's mean live
tokens, once for each of the `steps_per_sync` decode steps of every scheduling
step in the trace (one `bench.step` host span each) — from shapes and live
tokens only, so it reads the same work whatever kernel does it. Memory-bound
is the bound that holds at one query token a slot; it cannot pass 100%.

Seconds: `trace["device_op_s"]`, the summed device time of every label of the
trace, uncut (`tracing.reduce_events`), so the kernel is found whatever its
rank among the operations and under however many labels it ran.
"""
from benchmark import arith


def read(ctx):
    tr = ctx["trace"]
    n_steps = tr["host_spans"].get("step", 0)
    secs = sum(s for label, s in tr.get("device_op_s", {}).items()
               if label.startswith("decode_attention"))
    if not n_steps or not secs or not ctx.get("live_kv_tokens"):
        return None
    m = ctx["config"]
    kv_bytes = arith.decode_step_bytes(m, ctx["live_kv_tokens"]) \
        - arith.decode_step_bytes(m, 0)
    need = kv_bytes * n_steps * ctx["steps_per_sync"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
