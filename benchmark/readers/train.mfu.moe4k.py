"""Model FLOPs of one step of the expert trainer (6 a matmul weight a token,
the routed experts by the rows counted as held — `moe.rows_held`, the mean of
the traced steps —, causal latent attention; forward + backward, nothing
recomputed: `arith_glm4_moe_lite`) over the step's device time in the trace,
as a share of the chip's published bf16 peak."""
from benchmark import arith_glm4_moe_lite as arith
from benchmark.readers import _moe, _programs


def read(ctx):
    ms = _programs.step_ms(ctx)
    rows = _moe.mean(ctx, "moe.rows_held", traced_only=True)
    if ms is None or rows is None:
        return None
    flops = arith.train_flops_per_step(ctx["config"], ctx["batch"],
                                       ctx["seq"], rows)
    return 100.0 * flops / (ms * 1e-3) / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
