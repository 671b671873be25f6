"""Model FLOPs of one step of the `afmoe` trainer (6 a matmul weight a token,
the routed experts by the rows counted as held — `moe.rows_held`, the mean of
the traced steps —, attention by the pairs inside each layer's mask, window or
causal; forward + backward, nothing recomputed: `arith_afmoe`) over the step's
device time in the trace, as a share of the chip's published bf16 peak: the
share of the whole step."""
from benchmark import arith_afmoe as arith
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
