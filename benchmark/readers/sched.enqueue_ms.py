"""Handing a program to the device: the `decode.enqueue` span (the jitted
call, from entering it to its return), median a program, ms."""
from benchmark.readers import _host_path


def read(ctx):
    return _host_path.per_program_ms(ctx, "decode.enqueue")
