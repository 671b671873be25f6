"""Making a program's arguments and putting the host's on the device (the
key split, the chain's selects, every host array's transfer): the
`decode.stage` span, median a program, ms."""
from benchmark.readers import _host_path


def read(ctx):
    return _host_path.per_program_ms(ctx, "decode.stage")
