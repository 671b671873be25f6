"""`moe.rows_moved_over_held` (PR 32): the step's report with and without the
counter — a number, and nothing (the parent's program counts no moved rows)."""
import pytest
from test_benchmark_contract import load_bench

from benchmark import run


def _ctx(reports):
    return {"reports": reports, "traced": (2.0, 6.0),
            "trace": {"busy_s": 1.0, "window_s": 1.25, "chips": 1,
                      "programs": {}, "device_op_s": {}}}


def _read(ctx):
    return run.load_module("readers", "moe.rows_moved_over_held").read(ctx)


@pytest.mark.parametrize("moved,held,want", [
    (46000.0, 20000.0, 1.15),           # live tiles + chunks, five blocks
    (332800.0, 21215.0, 332800.0 / 42430.0),    # a walk of the whole buffer
])
def test_rows_moved_over_twice_the_rows_held(moved, held, want):
    step = {"t": 3.0, "moe.rows_held": held, "moe.rows_routed": 163840.0,
            "moe.rows_moved": moved}
    assert _read(_ctx([step, dict(step, t=4.0)])) == pytest.approx(want)


@pytest.mark.parametrize("reports", [
    [],                                                     # no report at all
    [{"t": 3.0, "moe.rows_held": 20480.0}],                 # the parent's
    [{"t": 3.0, "moe.rows_held": 0.0, "moe.rows_moved": 640.0}],
])
def test_nothing_to_read_gives_none(reports):
    assert _read(_ctx(reports)) is None


def check_entries(bench, root):
    """The entry of `bench`, and the cell as `run`, pointed at `root`,
    loads it."""
    entry = [m for m in bench["per_layer"]
             if m["name"] == "moe.rows_moved_over_held"]
    assert entry == [{"name": "moe.rows_moved_over_held", "unit": "x",
                      "better": "lower", "source": "program_counter",
                      "layer": "expert layer", "moves": "train_tok_s",
                      "workloads": ["glm47flash-train-4k"]}]
    cell = run.load_cell("glm47flash-train-4k")
    assert entry[0] in cell["per_layer"]


def test_the_metric_is_an_entry_of_the_expert_cell():
    check_entries(load_bench(run.ROOT), run.ROOT)
