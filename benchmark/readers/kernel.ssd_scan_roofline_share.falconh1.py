"""The prefill windows' chunked scan against its roofline: for the tokens and
segments the traced windows counted, the larger of their operations
(`arith_falcon_h1.ssd_scan_flops`) over the bf16 peak and their bytes
(`ssd_scan_bytes`: each segment's S read and written once, each token's
inputs and output) over 819 GB/s, over the device time of `ssd_scan`. The
counts are the model's work — real tokens, not the window's pad rows — so the
share cannot pass 100 whatever implements the scan."""
from benchmark import arith_falcon_h1 as arith
from benchmark.readers import _falconh1


def read(ctx):
    c = _falconh1.traced_counts(ctx)
    secs = _falconh1.label_seconds(ctx, _falconh1.SCAN)
    if not c or not secs or not c["ssm_scan_tokens"]:
        return None
    m, peaks = ctx["config"], ctx["peaks"]
    need = max(arith.ssd_scan_flops(m, c["ssm_scan_tokens"])
               / peaks["bf16_flops_per_s"],
               arith.ssd_scan_bytes(m, c["ssm_scan_tokens"],
                                    c["ssm_scan_segments"])
               / peaks["hbm_bytes_per_s"])
    return 100.0 * need / secs
