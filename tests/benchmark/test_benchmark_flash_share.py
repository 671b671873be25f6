"""`kernel.flash_dev_share` (ISSUE 30) on canned `device_op_s`: the same
reader reads the parent's program — JAX's bundled kernels under their own
names — and the change's, and finds nothing where no flash kernel ran."""
import os
import sys

import pytest
from test_benchmark_contract import load_bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark import tracing  # noqa: E402

METRIC = "kernel.flash_dev_share"

# labels as `tracing.op_label` cuts them from the profiler's HLO lines; the
# seconds are train-2k's (ledger, PR 28 and PR 29's lines; busy 4.2 s)
PARENT = {
    "flash_mha_bwd_dkv_block_q_major_1024_block_q_1024_block_k_major_1024_"
    "block_k_1024 bf16[4,16,2048,128]": 0.244,
    "flash_mha_bwd_dq_block_q_major_1024_block_k_major_1024_block_k_1024 "
    "bf16[4,16,2048,128]": 0.180,
    "flash_attention bf16[4,16,2048,128]": 0.127,
    "broadcast_in_dim f32[4,16,2048,1024]": 0.137,
    "fusion bf16[2048,5504]": 0.68}
CHANGE = {
    "flash_attention_fwd bf16[1,64,2048,128]": 0.127,
    "flash_attention_bwd bf16[64,2048,128]": 0.250,
    "fusion bf16[2048,5504]": 0.68}


def _read(ops, busy_s=4.2):
    return run.load_module("readers", METRIC).read(
        {"trace": {"busy_s": busy_s, "device_op_s": ops}})


@pytest.mark.parametrize("ops,seconds", [(PARENT, 0.551), (CHANGE, 0.377)],
                         ids=["parent", "change"])
def test_share_is_the_flash_kernels_time_over_busy_time(ops, seconds):
    assert _read(ops) == pytest.approx(100.0 * seconds / 4.2)


def test_the_label_is_what_the_profiler_line_gives():
    line = ("%flash_attention_bwd.7 = (bf16[64,2048,128]{2,1,0}, "
            "bf16[64,2048,128]{2,1,0}) custom-call(...)")
    assert tracing.op_label(line) == "flash_attention_bwd bf16[64,2048,128]"
    assert _read({tracing.op_label(line): 1.0}, busy_s=4.0) == 25.0


def test_nothing_to_read_gives_nothing():
    """The serving cell's trace, or the CPU's: no flash label. A fusion that
    merely CONSUMES a flash result has another first word and is not read."""
    assert _read({"fusion bf16[32,4096]": 1.0,
                  "copy bf16[576,8,64,128]": 0.3}) is None


def check_entries(bench, root):
    """The entry of `bench`, found by name: a later PR appends its entries
    after this one, and its cells to this one's list. Each cell it lists
    reports it as `run`, pointed at `root`, loads the cell."""
    entry = dict(next(m for m in bench["per_layer"] if m["name"] == METRIC))
    cells = entry.pop("workloads")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tok_s"}
    assert cells[:2] == ["dscoder1p3b-train-2k", "glm47flash-train-4k"]
    for cell in cells:
        assert METRIC in [m["name"] for m in
                          run.load_cell(cell)["per_layer"]]


def test_the_entry_lists_the_train_cells_and_moves_their_metric():
    check_entries(load_bench(ROOT), ROOT)
