"""What the `afmoe` trainer's readers share: the window kernels' device time
in the trace and the steps the trace holds."""


def window_kernel_seconds(ctx):
    """Summed device time of the operations whose name holds `flash` and
    `window` (`flash_attention_window_fwd`, `flash_attention_window_bwd`; a
    label is the kernel's name and its result's shape), or None where the
    trace has none: the CPU's trace, or a program without the kernels."""
    total = sum(s for label, s in ctx["trace"]["device_op_s"].items()
                if "flash" in label.split()[0]
                and "window" in label.split()[0])
    return total or None


def traced_steps(ctx) -> int:
    """Runs, in the trace, of the program that takes most of the device's
    time: the training step."""
    return len(max(ctx["trace"]["programs"].values(), key=sum, default=[]))
