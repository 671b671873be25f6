"""The grouped matmul inside the served programs, as a share of its read
bound: the weights of the experts hit and the rows moved in and out of the
three products (`arith_mellum.expert_mm_bytes`, from the program's own counts:
both lanes' means over the window, times the routed layer-steps of each lane
the trace holds), over 819 GB/s, over the summed device time of the kernel's
label."""
from benchmark import arith_mellum as arith
from benchmark.readers import _mellum


def read(ctx):
    c, n = _mellum.counters(ctx), _mellum.traced_steps(ctx)
    secs = _mellum.label_seconds(ctx, ("grouped_matmul",))
    if not c or not n or not secs:
        return None
    layers, steps = _mellum.layers(ctx), ctx["steps_per_sync"]
    dec, chunk = c["moe_layer_steps_decode"], \
        c["moe_layer_steps"] - c["moe_layer_steps_decode"]
    need = 0.0
    if dec:
        need += n * steps * layers * arith.expert_mm_bytes(
            ctx["config"], c["moe_experts_hit_decode"] / dec,
            c["moe_rows_routed_decode"] / dec)
    if chunk:
        need += _mellum.traced_mixed_steps(ctx) * layers * arith.expert_mm_bytes(
            ctx["config"],
            (c["moe_experts_hit"] - c["moe_experts_hit_decode"]) / chunk,
            (c["moe_rows_routed"] - c["moe_rows_routed_decode"]) / chunk)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
