"""The flash-attention kernels' device time over the device's busy time in
the trace: every operation whose name holds `flash` — `flash_attention_fwd`
and `flash_attention_bwd` since PR 30; before it JAX's bundled
`flash_attention`, `flash_mha_bwd_dkv...` and `flash_mha_bwd_dq...` — so the
same reader reads parent and change. The row terms the bundled backward
broadcast before its kernels (`broadcast_in_dim f32[4,16,2048,1024]`) were
attention's time under another name and are NOT counted: the share reads the
kernels alone."""


def read(ctx):
    trace = ctx["trace"]
    seconds = sum(s for label, s in trace["device_op_s"].items()
                  if "flash" in label.split()[0])
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
