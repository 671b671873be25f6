"""The window kernels' share of their roofline over the traced steps: the
least time the chip could take for the (query, key) pairs INSIDE the window
layers' masks — 2 products a pair forward, 5 backward, a head; the larger of
those FLOPs over the bf16 peak and the kernels' operands over the HBM
bandwidth (`arith_afmoe.window_kernels_floor_s`) — over the summed device
time of the labels that hold `flash` and `window`. The pairs are the mask's,
whatever the kernels sweep: blocks swept in vain lower the share, and it can
read at most 100 however the kernel is written."""
from benchmark import arith_afmoe as arith
from benchmark.readers import _afmoe


def read(ctx):
    seconds = _afmoe.window_kernel_seconds(ctx)
    steps = _afmoe.traced_steps(ctx)
    if seconds is None or not steps:
        return None
    floor = arith.window_kernels_floor_s(ctx["config"], ctx["batch"],
                                         ctx["seq"], steps, ctx["peaks"])
    return 100.0 * floor / seconds
