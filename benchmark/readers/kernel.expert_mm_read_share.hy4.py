"""`kernel.expert_mm_read_share`'s yardstick in `hy4-longctx-reason`: the
weights of the HELD experts hit and the rows routed to them, moved in and out
of the three grouped products (`arith_hy_v4.expert_mm_bytes`, from the
program's own counts: each lane's means a routed layer-step over the window,
times the routed layer-steps of that lane the trace holds), over 819 GB/s,
over the summed device time of the label `grouped_matmul`. The count knows no
row tile and no held expert's block that no row hit, so it cannot pass 100."""
from benchmark import arith_hy_v4 as arith
from benchmark.readers import _mellum


def read(ctx):
    c, n = _mellum.counters(ctx), _mellum.traced_steps(ctx)
    secs = _mellum.label_seconds(ctx, ("grouped_matmul",))
    if not c or not n or not secs:
        return None
    m, routed = ctx["config"], arith.routed_layers(ctx["config"])
    dec = c["moe_layer_steps_decode"]
    win = c["moe_layer_steps"] - dec
    need = 0.0
    if dec:
        need += n * ctx["steps_per_sync"] * routed * arith.expert_mm_bytes(
            m, c["moe_experts_hit_decode"] / dec,
            c["moe_rows_routed_decode"] / dec)
    if win:
        need += _mellum.traced_mixed_steps(ctx) * routed \
            * arith.expert_mm_bytes(
                m, (c["moe_experts_hit"] - c["moe_experts_hit_decode"]) / win,
                (c["moe_rows_routed"] - c["moe_rows_routed_decode"]) / win)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
