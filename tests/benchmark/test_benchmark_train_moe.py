"""The expert trainer's driver (`benchmark/drivers/train_moe.py`): the
comparison that decides `correct` has teeth — each planted fault fails at
least one of its checks at the tiny size, and so does the reference itself
computed one precision lower —, and the arithmetic and the readers behind the
cell's per-layer metrics count what the issue's table counts.
"""
import dataclasses
import json
import os
import sys

import jax.numpy as jnp
import pytest
from test_benchmark_cells import _tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith_glm4_moe_lite as arith  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.drivers import train_moe  # noqa: E402

CELL = "glm47flash-train-4k"


def _rope_on_the_nope_part(q, kv, k_rope, cos, sin, dn):
    """`_rope_join` with the rotary table on the first dims of the nope part
    instead of the rope part."""
    from paddle_tpu.kernels.rope import apply_rotary_emb

    dr = k_rope.shape[-1]
    q_r, k_r = apply_rotary_emb(q[..., :dr], kv[..., :dr], cos=cos, sin=sin)
    return (jnp.concatenate([q_r, q[..., dr:]], -1),
            jnp.concatenate([k_r, kv[..., dr:dn],
                             jnp.broadcast_to(k_rope, q_r.shape)], -1),
            kv[..., dn:])


def _plant(fault, monkeypatch, cfg):
    """The model's configuration with `fault` planted (in it, or in the
    model's code through `monkeypatch`), and the checks that must fail."""
    from paddle_tpu.models import glm4_moe_lite as glm
    from paddle_tpu.parallel.moe import DroplessMoELayer

    if fault == "float32_computed_in_bfloat16":
        return dataclasses.replace(cfg, dtype="bfloat16"), "logits.main"
    if fault == "routed_scaling_factor_dropped":
        return dataclasses.replace(cfg, routed_scaling_factor=1.0), "grad"
    if fault == "held_set_shifted_by_one":
        return dataclasses.replace(
            cfg, held=tuple(e + 1 for e in cfg.held)), "grad"
    if fault == "no_shared_expert":
        monkeypatch.setattr(glm.Glm4MoeLiteMoE, "forward",
                            DroplessMoELayer.forward)
        return cfg, "logits.main"
    if fault == "rotary_table_on_the_nope_part":
        monkeypatch.setattr(glm, "_rope_join", _rope_on_the_nope_part)
        return cfg, "grad"
    if fault == "mask_not_causal":
        real = glm.F.flash_attention
        monkeypatch.setattr(
            glm.F, "flash_attention",
            lambda q, k, v, causal=True: real(q, k, v, causal=False))
        return cfg, "logits.main"
    assert fault == "none"
    return cfg, None


FAULTS = ["none", "float32_computed_in_bfloat16",
          "routed_scaling_factor_dropped", "no_shared_expert",
          "rotary_table_on_the_nope_part", "mask_not_causal",
          "held_set_shifted_by_one"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(fault, monkeypatch, capsys):
    cell, _ = _tiny(CELL)
    m = cell["config"]
    cfg, must_fail = _plant(fault, monkeypatch,
                            train_moe.model_config(m, "float32"))
    model, make_step = train_moe.build_model(cfg, 5)
    *_, bad = train_moe.probe(m, model, make_step, 5,
                              train_moe.LIMITS["float32"], print)
    text = capsys.readouterr().out
    if must_fail is None:
        assert bad == [] and "check: loss.main" in text
        return
    assert any(line.startswith(must_fail) for line in bad), bad


def test_the_reference_one_precision_lower_fails(capsys):
    """The control of the limits: float32's, held against the reference
    computed with bfloat16 operands."""
    cell, _ = _tiny(CELL)
    m = cell["config"]
    model, _ = train_moe.build_model(train_moe.model_config(m, "float32"), 6)
    readings = train_moe.control(m, model, 6, "float32")
    over = [what for what, reading, limit in readings if not reading < limit]
    assert over and "moe.rows_dropped" not in over
    assert {what.split()[0] for what, _, _ in readings} >= {
        "loss.main", "loss.mtp", "logits.main", "logits.mtp", "grad",
        "flips"}


def test_an_incorrect_probe_fails_the_run(monkeypatch, capsys):
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    cell, kw = _tiny(CELL)
    cell["config"]["routed_scaling_factor"] = 1.0   # the reference's alone
    monkeypatch.setattr(
        train_moe, "model_config",
        lambda m, dtype, real=train_moe.model_config: dataclasses.replace(
            real(m, dtype), routed_scaling_factor=1.8))
    out = run.run_cell(cell, 3, 0.3, False, **kw)
    assert out["correct"] is False
    assert "check: FAILED: grad" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# arithmetic and readers
# ---------------------------------------------------------------------------

def _config():
    with open(os.path.join(ROOT, "benchmark/configs/glm-4.7-flash.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues_table():
    m = _config()
    assert arith.attention_params(m) == 21_757_952          # 21.76M
    assert arith.expert_params(m) == 9_437_184              # 9.44M
    assert arith.router_width(m) == 64
    assert arith.blocks(m) == (1, 4, 1)
    attn, exp = arith.attention_params(m), arith.expert_params(m)
    h = m["hidden_size"]
    dense_layer = attn + 3 * h * m["intermediate_size"]
    expert_layer = attn + exp + h * 64 + 8 * exp
    mtp = 2 * h * h + expert_layer
    vocab = 2 * h * m["vocab_size"]
    assert round(dense_layer / 1e6, 2) == 84.67
    assert round(expert_layer / 1e6, 2) == 106.82
    assert round(vocab / 1e6, 2) == 79.30
    assert round(mtp / 1e6, 2) == 115.21
    # norm scales and router biases are the 0.03M the table leaves out
    assert round((dense_layer + 4 * expert_layer + mtp + vocab) / 1e6, 1) \
        == 706.5


def test_flops_of_a_step_count_the_rows_held():
    m = _config()
    tokens = 2 * 4096
    even = tokens * 4 * 5 / 8           # 1/8 of the assignments, 5 blocks
    step = arith.train_flops_per_step(m, 2, 4096, even)
    per_token = step / tokens
    assert 2.7e9 < per_token < 3.0e9     # the issue's ~2.87 GFLOP a token
    more = arith.train_flops_per_step(m, 2, 4096, even + 1000)
    assert more - step == 6.0 * 1000 * arith.expert_params(m)
    none = arith.train_flops_per_step(m, 2, 4096, 0)
    dense = 6.0 * tokens * arith.dense_params_per_token(m)
    attn = 3.0 * tokens * arith.attention_flops_per_token(m, 4096)
    assert none == dense + attn
    assert arith.attention_flops_per_token(m, 4096) \
        == 6 * 2 * (192 + 64 + 256) * 20 * 4096 / 2


def test_grouped_matmul_floor_is_the_larger_of_flops_and_bytes():
    m = _config()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows, launches = 5 * 5120.0, 45.0
    floor = arith.grouped_matmul_floor_s(m, rows, launches, peaks)
    flops = 9 * 2.0 * rows * 2048 * 1536
    data = 2 * (9 * rows * (2048 + 1536) + launches * 8 * 2048 * 1536)
    assert floor == max(flops / 197e12, data / 819e9)
    # at ~512 rows an expert (512 FLOPs a weight byte against the chip's 240)
    # the FLOPs set it already, as at a deployment's ~4,096
    assert floor == flops / 197e12
    # at 64 rows an expert the weights' bytes would
    rows = 5 * 8 * 64.0
    assert arith.grouped_matmul_floor_s(m, rows, launches, peaks) \
        == 2 * (9 * rows * (2048 + 1536) + launches * 8 * 2048 * 1536) / 819e9


def _ctx(seconds, reports, steps=2):
    m = _config()
    return {"config": m, "batch": 2, "seq": 4096, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "reports": reports, "traced": (2.0, 6.0),
            "memory_peak_bytes": 3 * 2**30,
            "trace": {"busy_s": 1.0, "window_s": 1.25, "chips": 1,
                      "programs": {"jit_train_step": [0.25] * steps,
                                   "jit_heads": [0.01]},
                      "device_op_s": seconds}}


def _reader(name):
    return run.load_module("readers", name).read


def test_readers_of_the_expert_layer():
    inside = {"t": 3.0, "moe.rows_held": 20480.0, "moe.rows_routed": 163840.0,
              "moe.rows_multiplied": 25600.0, "moe.load_max": 600.0,
              "moe.load_mean": 512.0}
    outside = dict(inside, t=9.0, **{"moe.rows_multiplied": 1e9,
                                     "moe.rows_held": 0.0})
    ops = {"grouped_matmul bf16[33792,1536]": 0.02,
           "grouped_matmul bf16[33792,2048]": 0.01,
           "grouped_matmul_dlhs bf16[33792,2048]": 0.03,
           "grouped_matmul_drhs bf16[8,2048,1536]": 0.04,
           "fusion bf16[8192,2048]": 0.5}
    ctx = _ctx(ops, [inside, outside])
    assert _reader("moe.expert_dev_share")(ctx) == pytest.approx(10.0)
    assert _reader("moe.rows_held_share")(ctx) == pytest.approx(6.25)
    assert _reader("moe.load_max_over_mean")(ctx) == pytest.approx(600 / 512)
    assert _reader("train.step_ms.moe4k")(ctx) == pytest.approx(250.0)
    assert _reader("train.peak_hbm_gib.moe4k")(ctx) == 3.0
    # the traced steps' rows alone, times the steps the trace holds
    floor = arith.grouped_matmul_floor_s(ctx["config"], 25600.0 * 2,
                                         9 * 5 * 2, ctx["peaks"])
    assert _reader("kernel.grouped_mm_roofline_share")(ctx) \
        == pytest.approx(100.0 * floor / 0.1)
    flops = arith.train_flops_per_step(ctx["config"], 2, 4096, 20480.0)
    assert _reader("train.mfu.moe4k")(ctx) \
        == pytest.approx(100.0 * flops / 0.25 / 197e12)


def test_trace_readers_find_nothing_where_the_program_has_no_such_kernel():
    """The parent's program, or the CPU's trace: no label, no counter — the
    readers return None and the line leaves the metric out."""
    ctx = _ctx({"fusion bf16[8192,2048]": 0.5}, [])
    for name in ("kernel.grouped_mm_roofline_share", "moe.expert_dev_share",
                 "moe.rows_held_share", "moe.load_max_over_mean",
                 "train.mfu.moe4k"):
        assert _reader(name)(ctx) is None, name
    ctx["trace"]["programs"] = {}
    assert _reader("train.step_ms.moe4k")(ctx) is None
