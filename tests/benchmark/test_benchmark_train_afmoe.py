"""The `afmoe` trainer's driver (`benchmark/drivers/train_afmoe.py`): the
comparison that decides `correct` has teeth — each fault the reference can
plant fails at least one of its checks at the tiny size, and so does the
reference computed one precision lower —, and the arithmetic and the readers
behind the cell's per-layer metrics count what the issue's table counts.
"""
import json
import os
import sys

import pytest
from test_benchmark_cells import _tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith_afmoe as arith  # noqa: E402
from benchmark import reference_afmoe as reference  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.drivers import train_afmoe  # noqa: E402

CELL = "trinitymini-train-8k"


@pytest.fixture(scope="module")
def tiny():
    """(the tiny cell's configuration, its seeded float32 model)."""
    cell, _ = _tiny(CELL)
    m = cell["config"]
    model, make_step = train_afmoe.build_model(
        train_afmoe.model_config(m, "float32"), 5)
    return m, model, make_step


def test_the_sound_program_passes_the_comparison(tiny, capsys):
    m, model, make_step = tiny
    *_, bad = train_afmoe.probe(m, model, make_step, 5,
                                train_afmoe.LIMITS["float32"], print)
    text = capsys.readouterr().out
    assert bad == [] and "check: loss" in text and "check: grad" in text
    assert "check: flips expert block 3" in text


def test_the_faults_are_the_issues_nine():
    assert set(reference.FAULTS) == {
        "window_layers_full", "full_layers_rotated", "attn_gate_dropped",
        "qk_norm_dropped", "mup_scale_dropped", "sandwich_norms_dropped",
        "route_scale_dropped", "shared_expert_dropped", "top_k_less_one"}
    with pytest.raises(ValueError, match="no fault"):
        with reference.planted("nope"):
            pass


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_fails_the_comparison(tiny, fault):
    """The reference with one piece of the block's mathematics left out, held
    to the sound reference under the limits a sound run passes."""
    m, model, _ = tiny
    readings = train_afmoe.control(m, model, 5, "float32", fault=fault)
    over = [what for what, reading, limit in readings if not reading < limit]
    assert over and "moe.rows_dropped" not in over, (fault, readings)


def test_the_reference_one_precision_lower_fails(tiny):
    """The control of the limits: float32's, held against the reference
    computed with bfloat16 operands."""
    m, model, _ = tiny
    readings = train_afmoe.control(m, model, 6, "float32")
    over = [what for what, reading, limit in readings if not reading < limit]
    assert over and "moe.rows_dropped" not in over
    assert {what.split()[0] for what, _, _ in readings} >= {
        "loss", "logits", "grad", "flips"}


def test_an_incorrect_probe_fails_the_run(monkeypatch, capsys):
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    cell, kw = _tiny(CELL)
    cell["config"]["route_scale"] = 1.0             # the reference's alone
    monkeypatch.setattr(
        train_afmoe, "model_config",
        lambda m, dtype, real=train_afmoe.model_config: real(
            dict(m, route_scale=2.826), dtype))
    out = run.run_cell(cell, 3, 0.3, False, **kw)
    assert out["correct"] is False
    assert "check: FAILED: " in capsys.readouterr().out


def test_the_faults_script_reads_the_control_and_a_fault(monkeypatch, capsys):
    """`benchmark/train_afmoe_faults.py`, the chip's way to the readings
    that must fail, through its own `main` at the tiny size."""
    from benchmark import train_afmoe_faults

    cell, _ = _tiny(CELL)
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "check_device", lambda chips: None)
    train_afmoe_faults.main(["--workload", CELL, "--seed", str(2**31 + 5),
                             "--faults", "attn_gate_dropped"], "float32")
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["passes"] == [] and out["seed"] == 2**31 + 5
    assert set(out["over"]) == {"control", "attn_gate_dropped"}


def test_the_deployment_states_the_bias_rate_the_cell_runs():
    """The published `load_balance_coeff` stays in the file; the rule's rate
    is the deployment's where it states one (PERF.md section 6: 0.01)."""
    cell, _ = _tiny(CELL)
    m = cell["config"]
    assert m["load_balance_coeff"] == 0.001
    assert m["deployment"]["router_bias_update_rate"] == 0.01
    assert train_afmoe.model_config(m, "float32").load_balance_coeff == 0.01
    del m["deployment"]["router_bias_update_rate"]
    assert train_afmoe.model_config(m, "float32").load_balance_coeff == 0.001


# ---------------------------------------------------------------------------
# arithmetic and readers
# ---------------------------------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues_table():
    m = _config()
    p = arith.parameters(m)
    assert p["attention"] == 27_262_976                     # 27.26M
    assert p["expert"] == 6_291_456                         # 6.29M
    assert arith.router_width(m) == 128 and arith.blocks(m) == (1, 4)
    assert round(p["expert_layer"] / 1e6, 1) == 134.5
    assert round(p["dense_layer"] / 1e6, 1) == 65.0
    assert round(p["vocabulary"] / 1e6, 1) == 102.5
    # the issue adds its rounded parts to 705.5M; unrounded they are 705.43M
    assert abs(p["total"] / 1e6 - 705.5) < 0.1
    assert p["total"] == p["dense_layer"] + 4 * p["expert_layer"] \
        + p["vocabulary"]
    # the built model's leaves: these and the norm scales and router biases
    from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM
    import jax

    cfg = AfmoeConfig.from_dict(m, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: AfmoeForCausalLM(cfg).raw_state())
    built = sum(int(v.size) for v in shapes.values())
    small = 5 * (4 * 2048 + 2 * 128) + 2048 + 4 * 128
    assert built == p["total"] + small


def test_window_pairs_and_flops_are_counted_as_the_mask_has_them():
    m = _config()
    s, w = 8192, 2048
    assert arith.window_pairs(m, s) == w * s - w * (w - 1) // 2
    assert arith.window_pairs(m, 1024) == arith.causal_pairs(1024)
    assert arith.layer_kinds(m, s) == (4, 1)
    assert arith.layer_kinds(m, 2048) == (0, 5)
    fwd = arith.attention_flops_per_row(m, s)
    assert fwd == 4 * 128 * 32 * (4 * arith.window_pairs(m, s)
                                  + arith.causal_pairs(s))
    # the issue's prediction: 1 full layer at 0.55 + 4 window layers at 0.24
    assert round(4 * 128 * 32 * arith.causal_pairs(s) / 1e12, 2) == 0.55
    assert round(4 * 128 * 32 * arith.window_pairs(m, s) / 1e12, 2) == 0.24
    even = s * 8 * 4 / 8                 # 1/8 of the assignments, 4 blocks
    step = arith.train_flops_per_step(m, 1, s, even)
    assert 17.5e12 < step < 18.5e12      # the issue's ~18 TFLOP a step
    assert arith.train_flops_per_step(m, 1, s, even + 1000) - step \
        == 6.0 * 1000 * arith.expert_params(m)
    assert arith.train_flops_per_step(m, 1, s, 0) \
        == 6.0 * s * arith.dense_params_per_token(m) + 3.0 * fwd


def test_the_kernels_floors():
    m = _config()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows, launches = 4 * 10240.0, 36.0
    flops = 9 * 2.0 * rows * 2048 * 1024
    data = 2 * (9 * rows * (2048 + 1024) + launches * 16 * 2048 * 1024)
    assert arith.grouped_matmul_floor_s(m, rows, launches, peaks) \
        == max(flops / 197e12, data / 819e9)
    pairs = arith.window_pairs(m, 8192)
    floor = arith.window_kernels_floor_s(m, 1, 8192, 3, peaks)
    assert floor == 4 * 3 * 7 * 2.0 * 128 * 32 * pairs / 197e12
    # ~17 ms a step for the four window layers, forward and backward
    assert 0.016 < floor / 3 < 0.018


def _ctx(seconds, reports, steps=2):
    return {"config": _config(), "batch": 1, "seq": 8192, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "reports": reports, "traced": (2.0, 6.0),
            "memory_peak_bytes": 3 * 2**30,
            "trace": {"busy_s": 1.0, "window_s": 1.25, "chips": 1,
                      "programs": {"jit_train_step": [0.25] * steps,
                                   "jit_heads": [0.01]},
                      "device_op_s": seconds}}


def _reader(name):
    return run.load_module("readers", name).read


NEW = ["train.mfu.trinity8k", "train.step_ms.trinity8k",
       "train.peak_hbm_gib.trinity8k", "kernel.flash_window_roofline_share",
       "kernel.flash_window_dev_share", "attn.window_swept_over_mask",
       "kernel.grouped_mm_roofline_share.trinity8k",
       "moe.rows_moved_over_held.trinity8k"]
APPENDED = ["kernel.flash_dev_share", "moe.expert_dev_share",
            "moe.rows_held_share", "moe.load_max_over_mean"]


def test_readers_on_a_synthetic_trace():
    inside = {"t": 3.0, "moe.rows_held": 32768.0, "moe.rows_routed": 262144.0,
              "moe.rows_multiplied": 40960.0, "moe.rows_moved": 98304.0,
              "moe.load_max": 900.0, "moe.load_mean": 512.0,
              "attn.window_pairs_swept": 4 * 40370176.0,
              "attn.window_pairs_in_mask": 4 * 2 * 14681088.0}
    outside = dict(inside, t=9.0, **{"moe.rows_multiplied": 1e9,
                                     "moe.rows_held": 0.0})
    ops = {"flash_attention_window_fwd bf16[32,8192,128]": 0.02,
           "flash_attention_window_bwd bf16[32,8192,128]": 0.06,
           "flash_attention_fwd bf16[32,8192,128]": 0.03,
           "flash_attention_bwd bf16[32,8192,128]": 0.09,
           "grouped_matmul bf16[67584,1024]": 0.03,
           "grouped_matmul_dlhs bf16[67584,2048]": 0.03,
           "grouped_matmul_drhs bf16[16,2048,1024]": 0.04,
           "fusion bf16[8192,2048]": 0.5}
    ctx = _ctx(ops, [inside, outside])
    m, peaks = ctx["config"], ctx["peaks"]
    assert _reader("kernel.flash_window_dev_share")(ctx) \
        == pytest.approx(8.0)
    assert _reader("kernel.flash_dev_share")(ctx) == pytest.approx(20.0)
    assert _reader("kernel.flash_window_roofline_share")(ctx) \
        == pytest.approx(100.0 * arith.window_kernels_floor_s(
            m, 1, 8192, 2, peaks) / 0.08)
    assert _reader("attn.window_swept_over_mask")(ctx) \
        == pytest.approx(40370176 / (2 * 14681088))
    assert 1.3 < _reader("attn.window_swept_over_mask")(ctx) < 1.45
    assert _reader("moe.expert_dev_share")(ctx) == pytest.approx(10.0)
    assert _reader("moe.rows_held_share")(ctx) == pytest.approx(6.25)
    assert _reader("moe.load_max_over_mean")(ctx) \
        == pytest.approx(900 / 512)
    assert _reader("train.step_ms.trinity8k")(ctx) == pytest.approx(250.0)
    assert _reader("train.peak_hbm_gib.trinity8k")(ctx) == 3.0
    # the traced steps' rows alone, times the steps the trace holds
    floor = arith.grouped_matmul_floor_s(m, 40960.0 * 2, 9 * 4 * 2, peaks)
    assert _reader("kernel.grouped_mm_roofline_share.trinity8k")(ctx) \
        == pytest.approx(100.0 * floor / 0.1)
    flops = arith.train_flops_per_step(m, 1, 8192, 32768.0)
    assert _reader("train.mfu.trinity8k")(ctx) \
        == pytest.approx(100.0 * flops / 0.25 / 197e12)
    # both reports: (98304 + 98304) / (2 * (32768 + 0))
    assert _reader("moe.rows_moved_over_held.trinity8k")(ctx) \
        == pytest.approx(3.0)


def test_readers_find_nothing_where_the_program_has_no_such_label():
    """The parent's program, or the CPU's trace: no label, no counter — the
    readers return None and the line leaves the metric out."""
    ctx = _ctx({"fusion bf16[8192,2048]": 0.5,
                "flash_attention_fwd bf16[32,8192,128]": 0.1}, [])
    for name in NEW:
        if name in ("train.step_ms.trinity8k",
                    "train.peak_hbm_gib.trinity8k"):
            continue
        assert _reader(name)(ctx) is None, name
    ctx["trace"]["programs"] = {}
    assert _reader("train.step_ms.trinity8k")(ctx) is None


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == [dict(cell[0], config="trinity-mini",
                         traffic="train-moe-8k", chips=1)]
    config, = (c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_tok_s"
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL
    loaded = {m["name"] for m in run.load_cell(CELL)["per_layer"]}
    assert loaded == set(NEW) | set(APPENDED)
    body = _config()
    assert body["load_balance_coeff"] == 0.001
    assert body["published"] == {"num_hidden_layers": 32,
                                 "num_dense_layers": 2, "num_experts": 128,
                                 "vocab_size": 200192}
    assert body["deployment"]["held"] == list(range(16))
    assert body["deployment"]["group_chips"] == 8
    for key, why in body["assumed"].items():
        assert isinstance(why, str) and why, key
