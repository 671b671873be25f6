"""The time the chip's memory would need for the bytes the traced steps must
read, over the time the device was busy in the trace.

Bytes: every scheduling step in the trace (one `bench.step` host span each)
runs `steps_per_sync` decode steps, each reading every weight once and the
K and V of every live cached token; a mixed step reads the weights once more
for its prefill window. The mixed share and the mean live KV tokens are the
window's. Memory-bound is the bound that holds for decoding, so this is the
decode step's share of its roofline; it cannot pass 100%.
"""
from benchmark import arith
from benchmark.readers import _spans


def read(ctx):
    tr = ctx["trace"]
    n_steps = tr["host_spans"].get("step", 0)
    disp = _spans.in_window(ctx, "decode.dispatch")
    if not n_steps or not disp or not ctx.get("live_kv_tokens"):
        return None
    mixed = sum(bool(e["args"].get("prefill_window")) for e in disp) / len(disp)
    m = ctx["config"]
    per_decode = arith.decode_step_bytes(m, ctx["live_kv_tokens"])
    weights_once = arith.decode_step_bytes(m, 0)
    need = n_steps * (ctx["steps_per_sync"] * per_decode + mixed * weights_once)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / tr["busy_s"]
