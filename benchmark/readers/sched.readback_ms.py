"""Copying a finished program's host-visible outputs (tokens, lengths,
done flags, the routed layers' counts, log-probabilities, a mixed step's
first token): the summed `decode.readback` spans of a program, median over
the window's programs, ms."""
from benchmark.readers import _host_path


def read(ctx):
    return _host_path.per_program_ms(ctx, "decode.readback")
