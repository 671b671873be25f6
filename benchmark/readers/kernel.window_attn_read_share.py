"""The windowed decode kernel's share of its read bound: the K and V of
min(len, W) tokens a live sequence, a window layer and a decode step
(`arith_mellum.window_attn_bytes` at the engine's `kv_tokens_window` gauge,
its mean over the steps the trace holds, times the decode steps and window
layers the trace holds), over 819 GB/s, over the device time of the label
`decode_attention_window`."""
from benchmark import arith_mellum as arith
from benchmark.readers import _mellum


def read(ctx):
    n, g = _mellum.traced_steps(ctx), ctx.get("gauges_traced")
    secs = _mellum.label_seconds(ctx, ("decode_attention_window",))
    if not n or not g or not secs:
        return None
    _, n_window = arith.layer_counts(ctx["config"])
    need = n * ctx["steps_per_sync"] * n_window * arith.window_attn_bytes(
        ctx["config"], g["kv_tokens_window"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
