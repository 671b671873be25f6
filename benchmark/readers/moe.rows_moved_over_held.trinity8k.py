"""Rows the expert layer's forward pass moved over twice the rows that exist,
over the steps read back: `moe.rows_moved_over_held`'s rule, as an entry of
this cell's own (that entry's list is pinned to its cell by
`tests/benchmark/test_benchmark_rows_moved.py`)."""
from benchmark.readers import _moe


def read(ctx):
    moved, held = (_moe.mean(ctx, k) for k in ("moe.rows_moved",
                                               "moe.rows_held"))
    if moved is None or not held:
        return None
    return moved / (2.0 * held)
