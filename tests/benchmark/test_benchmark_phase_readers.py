"""The readers of ISSUE 25's per-layer metrics, by hand on built spans and a
built `trace` dict: the engine's own step-phase spans (`sched.step` and its
children), the programs' role names, and the decode-attention kernel's labels
among the trace's uncut per-label sums. A program without them gives every
reader `None`."""
import json
import os

import pytest
from test_benchmark_contract import load_bench
from test_benchmark_tracing import _reader

from benchmark import arith

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("kernel.decode_attn_read_share", "step.decode_dev_ms.sat",
       "step.mixed_dev_ms.sat", "sched.host_ms.sat", "sched.commit_ms.sat",
       "sched.admit_ms.sat")


def _x(name, t0_s, t1_s, tid=1, **args):
    return {"name": name, "ph": "X", "ts": t0_s * 1e6,
            "dur": (t1_s - t0_s) * 1e6, "tid": tid, "args": args}


def _iteration(t, *, admit, build, wait, commit, tail=0.0):
    """The spans of one scheduling iteration that starts at `t`: admit,
    build, a 2 ms dispatch, the sync wait, the commit(s), 1 ms of nothing."""
    out, at = [], t
    for name, dur in (("sched.admit", admit), ("sched.build", build),
                      ("decode.dispatch", 0.002), ("decode.sync_wait", wait),
                      ("sched.commit", commit), ("sched.commit", tail)):
        if dur:
            out.append(_x(name, at, at + dur))
            at += dur
    out.append(_x("sched.step", t, at + 0.001, iter=int(t * 10)))
    return out


def _ctx():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b.json")) as f:
        m = json.load(f)
    spans = (
        # before the window, and far too slow: must not be counted
        _iteration(9.0, admit=0.5, build=0.1, wait=0.1, commit=0.2)
        + _iteration(10.0, admit=0.001, build=0.0, wait=0.350, commit=0.004)
        + _iteration(10.5, admit=0.003, build=0.002, wait=0.340,
                     commit=0.006, tail=0.002)
        + _iteration(11.0, admit=0.002, build=0.0, wait=0.360, commit=0.005)
        # another thread's span inside the first iteration's interval
        + [_x("sched.commit", 10.1, 10.2, tid=2),
           # not nested in any step, and an instant of the same name
           _x("sched.commit", 10.45, 10.46),
           {"name": "sched.step", "ph": "i", "ts": 10.2e6, "tid": 1},
           _x("spec.verify", 9.5, 10.5, chunk=3)])
    return dict(
        t0=10.0, t1=20.0, spans=spans, config=m, steps_per_sync=8,
        live_kv_tokens=11000.0, peaks=arith.peaks("TPU v5 lite"),
        trace={"busy_s": 3.9, "host_spans": {"step": 11},
               # the breakdown's rows are not what the reader reads: here
               # they do not hold the kernel at all
               "device_ops": [["fusion bf16[32,4096]", 0.6]],
               "device_op_s": {"decode_attention bf16[256,4,128]": 1.8,
                               "fusion bf16[32,4096]": 0.6,
                               "decode_attention_q8 bf16[256,4,128]": 0.2,
                               "copy bf16[576,8,64,128]": 0.2},
               "programs": {"jit_serve_decode_chunk(7)": [0.35, 0.36, 0.37,
                                                          0.34],
                            "jit_serve_unified_step(9)": [0.36, 0.38, 0.37],
                            "jit_serve_prefill_s64_b1(3)": [0.02],
                            "jit_fill(2)": [1e-5] * 9}})


def test_scheduler_phase_readers_by_hand():
    ctx = _ctx()
    # per iteration: step = phases + 2 ms dispatch + 1 ms of nothing
    # host = step - wait:  1+2+4+1 = 8;  3+2+2+6+2+1 = 16;  2+2+5+1 = 10
    assert _reader("sched.host_ms.sat")(ctx) == pytest.approx(10.0)
    assert _reader("sched.commit_ms.sat")(ctx) == pytest.approx(5.0)
    assert _reader("sched.admit_ms.sat")(ctx) == pytest.approx(2.0)
    # no admission or build span at all is 0 ms, not nothing to read
    ctx["spans"] = [e for e in ctx["spans"]
                    if e["name"] not in ("sched.admit", "sched.build")]
    assert _reader("sched.admit_ms.sat")(ctx) == 0.0


def test_step_program_readers_by_hand():
    ctx = _ctx()
    assert _reader("step.decode_dev_ms.sat")(ctx) == pytest.approx(355.0)
    assert _reader("step.mixed_dev_ms.sat")(ctx) == pytest.approx(370.0)


def test_decode_attention_read_share_by_hand():
    ctx = _ctx()
    m = ctx["config"]
    # K and V of 11,000 live tokens, every layer, 2 bytes an element
    kv = 2 * m["num_hidden_layers"] * m["num_key_value_heads"] \
        * m["head_dim"] * 11000.0 * 2
    assert arith.decode_step_bytes(m, 11000.0) \
        - arith.decode_step_bytes(m, 0) == pytest.approx(kv)
    share = _reader("kernel.decode_attn_read_share")(ctx)
    assert share == pytest.approx(100 * kv * 11 * 8 / 819e9 / 2.0)
    assert 0 < share < 100
    # the kernel at its read bound reads 100%: the share cannot pass it
    ctx["trace"]["device_op_s"] = {
        "decode_attention bf16[256,4,128]": kv * 11 * 8 / 819e9}
    assert _reader("kernel.decode_attn_read_share")(ctx) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_nothing(name):
    """The parent commit's program: `jit_run` programs, an unnamed kernel,
    only the three old spans. The driver runs these readers on it too."""
    ctx = _ctx()
    ctx["spans"] = [e for e in ctx["spans"]
                    if not e["name"].startswith("sched.")]
    ctx["trace"]["device_op_s"] = {"closed_call bf16[256,4,128]": 1.8,
                                   "fusion bf16[32,4096]": 0.6}
    ctx["trace"]["programs"] = {"jit_run(7)": [0.35], "jit_run(9)": [0.36]}
    assert _reader(name)(ctx) is None
    empty = dict(ctx, spans=[], live_kv_tokens=0.0,
                 trace={"busy_s": 1.0, "host_spans": {}, "programs": {}})
    assert _reader(name)(empty) is None


def check_entries(bench, root):
    """The three that read the engine's spans and the two that read a
    program's device time are entries of `bench`, appended behind what was
    there in the order they came (PR 25, PR 27).
    `kernel.decode_attn_read_share` stays a reader file read by hand: its
    bytes are the whole window's mean live tokens and its seconds the
    trace's, so it can read over 100% (PERF.md 7c)."""
    listed = [n for n in NEW if n.startswith("sched.")] \
        + [n for n in NEW if n.startswith("step.")]
    tail = [m for m in bench["per_layer"] if m["name"] in listed]
    assert [m["name"] for m in tail] == listed
    assert [m["name"] for m in bench["per_layer"][:6]] == [
        "sched.live_slots_mean", "step.chunk_ms.sat",
        "kernel.decode_read_share", "train.mfu", "train.step_ms",
        "train.peak_hbm_gib"]      # appended behind what was there
    assert [m["name"] for m in bench["per_layer"][6:11]] == listed
    assert "kernel.decode_attn_read_share" not in {
        m["name"] for m in bench["per_layer"]}
    for m in tail:
        assert m["workloads"] == ["mistral7b-reason-sat"]
        assert m["moves"] == "output_tok_s"
        assert m["source"] == ("device_trace" if m["name"].startswith("step.")
                               else "program_span")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_benchmark_lists_the_span_metrics_for_the_sat_cell_only():
    check_entries(load_bench(ROOT), ROOT)
