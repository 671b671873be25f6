"""Rows the expert layer's forward pass moved (tokens -> buffer and buffer ->
tokens, as its kernels' chunk lists count them) over twice the rows that
exist, over the steps read back: 1 is a layer that moves each live row once
each way, the buffer's worst case over the live rows is what a layer reads
that walks the whole buffer. A program that counts no moved rows gives
nothing."""
from benchmark.readers import _moe


def read(ctx):
    moved, held = (_moe.mean(ctx, k) for k in ("moe.rows_moved",
                                               "moe.rows_held"))
    if moved is None or not held:
        return None
    return moved / (2.0 * held)
