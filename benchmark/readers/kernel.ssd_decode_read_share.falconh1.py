"""The decode step's recurrence against its floor: the bytes the traced
steps' counted slot-steps must move (each live sequence's S of a layer read
and written, its Δ x, B, C, exp(Δ A) in and y out:
`arith_falcon_h1.ssd_decode_bytes`) over 819 GB/s, over the device time of
`ssd_decode`. Idle slots are not counted, so the share cannot pass 100
whatever implements the step."""
from benchmark import arith_falcon_h1 as arith
from benchmark.readers import _falconh1


def read(ctx):
    c = _falconh1.traced_counts(ctx)
    secs = _falconh1.label_seconds(ctx, _falconh1.DECODE)
    if not c or not secs:
        return None
    need = arith.ssd_decode_bytes(ctx["config"], c["ssm_decode_slot_steps"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
