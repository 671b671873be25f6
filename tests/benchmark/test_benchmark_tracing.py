"""The reduction from the profiler's trace to numbers, on hand-built events
and on a small hand-built `.xplane.pb`; every per-layer reader on a
hand-built run."""
import importlib.util
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith, run, tracing  # noqa: E402

MS = 1e6    # nanoseconds


def _events():
    """10 ms window of host spans; the device runs 2-4, 5-8 (two overlapping
    ops) and 9-9.5 ms."""
    ops = [("fusion.1", 2 * MS, 2 * MS), ("fusion.2", 5 * MS, 2 * MS),
           ("custom-call.7", 6 * MS, 2 * MS), ("fusion.1", 9 * MS, .5 * MS),
           ("before.window", 0, .5 * MS),
           ("%while.3 = s32[]{:T(128)} while(s32[] %x), body=%b", 5 * MS,
            3 * MS)]
    progs = [("jit_run(1)", 2 * MS, 2 * MS), ("jit_run(2)", 5 * MS, 3 * MS),
             ("jit_run(1)", 9 * MS, .5 * MS)]
    host = [("step", 1 * MS, 3.5 * MS), ("add_request", 4.5 * MS, .4 * MS),
            ("step", 4.9 * MS, 3.6 * MS), ("wait_for_arrival", 8.5 * MS,
                                           .4 * MS),
            ("step", 8.9 * MS, 2.1 * MS)]
    return {"device": {"/device:TPU:0": {"XLA Ops": ops,
                                         "XLA Modules": progs,
                                         "Steps": []}},
            "host": host}


def test_op_label():
    hlo = ("%fusion.17 = (bf16[2048,32256]{1,0:T(8,128)(2,1)}, "
           "f32[2048,32256]{1,0}) fusion(bf16[2048,32256]{1,0} %p.1), "
           "kind=kOutput")
    assert tracing.op_label(hlo) == "fusion bf16[2048,32256]"
    assert tracing.op_label("%custom-call.3 = bf16[4,8]{1,0} custom-call()") \
        == "custom-call bf16[4,8]"
    assert tracing.op_label("fusion.1") == "fusion.1"
    assert len(tracing.op_label("x" * 500)) == 80


def test_union():
    assert tracing.union_ns([(5, 7), (1, 3), (2, 4), (7, 8)]) == \
        [[1, 4], [5, 8]]


def test_reduce_by_hand():
    red = tracing.reduce_events(_events())
    # the window is the benchmark's own spans: 1 ms .. 11 ms
    assert red["window_s"] == pytest.approx(10e-3)
    assert red["busy_s"] == pytest.approx(5.5e-3)      # 2 + 3 + 0.5
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2.5e-3)
    assert ops["custom-call.7"] == pytest.approx(2e-3)
    assert "before.window" not in ops and "while s32[]" not in ops
    assert red["device_ops"][0][0] == "fusion.1"
    gaps = dict(red["idle_gaps"])
    # idle: 1-2 and 8.9-9, 9.5-11 under `step`; 4-5 split by its middle
    # (4.5: add_request); 8-9 by its middle (8.5: wait_for_arrival)
    assert gaps["step"] == pytest.approx((1 + 1.5) * 1e-3)
    assert gaps["add_request"] == pytest.approx(1e-3)
    assert gaps["wait_for_arrival"] == pytest.approx(1e-3)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert red["host_spans"] == {"step": 3, "add_request": 1,
                                 "wait_for_arrival": 1}
    assert red["programs"]["jit_run(1)"] == pytest.approx([2e-3, .5e-3])
    assert red["chips"] == 1


def test_reduce_keeps_every_label_uncut_beside_the_top_ten():
    """`device_ops` is what the last line's `breakdown` prints: the ten
    longest labels. `device_op_s` is for the readers: every label's sum."""
    ev = _events()
    ev["device"]["/device:TPU:0"]["XLA Ops"] += [
        (f"%kernel_{i}.{j} = bf16[8,{i}]{{1,0}} custom-call()",
         (2 + j) * MS, (i + 1) * 1e3) for i in range(12) for j in range(2)]
    red = tracing.reduce_events(ev)
    assert len(red["device_ops"]) == tracing.TOP == 10
    sums = red["device_op_s"]
    assert len(sums) == 3 + 12 and "while s32[]" not in sums
    # the shortest label, twelfth of fifteen, with both its calls summed
    assert sums["kernel_0 bf16[8,0]"] == pytest.approx(2e-6)
    assert "kernel_0 bf16[8,0]" not in dict(red["device_ops"])
    assert red["device_ops"] == [[k, v] for k, v in sorted(
        sums.items(), key=lambda kv: -kv[1])[:10]]
    assert sums["fusion.1"] == pytest.approx(2.5e-3)


def test_reduce_averages_over_chips_and_falls_back_to_modules():
    ev = _events()
    ev["device"]["/device:TPU:1"] = {
        "XLA Modules": [("jit_run(1)", 1 * MS, 10 * MS)]}
    red = tracing.reduce_events(ev)
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx((5.5e-3 + 10e-3) / 2)


def test_a_trace_without_device_ops_is_refused():
    ev = _events()
    ev["device"] = {"/device:TPU:0": {"Steps": []}}
    with pytest.raises(SystemExit, match="no device operation"):
        tracing.reduce_events(ev)


XPLANE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run(9)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(run)" } }
}
planes { id: 3 name: "/host:metadata" }
"""


def test_read_a_hand_built_xplane(tmp_path):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XPLANE))
    ev = tracing.read_xplane(str(tmp_path))
    assert ev["host"] == [("step", 500.0, 6000.0)]
    assert ev["device"]["/device:TPU:0"]["XLA Ops"] == [
        ("fusion.1", 1000.0, 2000.0), ("custom-call.2", 4000.0, 1000.0)]
    red = tracing.reduce_events(ev)
    assert red["window_s"] == pytest.approx(6e-6)
    assert red["busy_s"] == pytest.approx(3e-6)
    assert dict(red["idle_gaps"]) == {"step": pytest.approx(3e-6)}
    assert red["programs"] == {"jit_run(9)": [pytest.approx(4e-6)]}
    with pytest.raises(SystemExit, match="expected one"):
        tracing.read_xplane(str(tmp_path / "plugins"))


def test_profile_off_does_nothing():
    p = tracing.Profile(False)
    p.tick(100.0)
    p.close()
    assert p.events is None and p.state == "off"


# ---- readers -------------------------------------------------------------

def _reader(name):
    path = os.path.join(ROOT, "benchmark", "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("r", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _x(name, t0_s, t1_s, **args):
    return {"name": name, "ph": "X", "ts": t0_s * 1e6,
            "dur": (t1_s - t0_s) * 1e6, "args": args}


def _serve_ctx():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b.json")) as f:
        m = json.load(f)
    spans = [
        _x("decode.dispatch", 9.0, 9.01, live=1),       # before the window
        _x("decode.sync_wait", 9.01, 9.1),
        _x("decode.dispatch", 10.0, 10.01, live=30),
        _x("decode.sync_wait", 10.01, 10.15),           # 150 ms pure
        _x("decode.dispatch", 10.2, 10.21, live=32, prefill_window=True,
           req_id=4),
        _x("decode.sync_wait", 10.21, 10.45),           # 250 ms mixed
        _x("prefill.dispatch", 10.2, 10.45, req_ids=[4]),
        _x("decode.dispatch", 10.5, 10.51, live=31, prefill_window=True,
           req_id=4),
        _x("decode.sync_wait", 10.51, 10.8),            # 300 ms mixed
        _x("prefill.dispatch", 10.5, 10.8, req_ids=[4]),
        _x("decode.dispatch", 10.8, 10.81, live=31),
        _x("decode.sync_wait", 10.81, 10.97),           # 170 ms pure
        {"name": "req.admit", "ph": "i", "ts": 10.8e6},
    ]
    req = SimpleNamespace(req_id=4, arrival_time=10.05)
    other = SimpleNamespace(req_id=5, arrival_time=10.9)   # never dispatched
    return dict(
        t0=10.0, t1=20.0, spans=spans, measured=[(req, 8), (other, 8)],
        counters={"prompt_tokens": 400, "prefix_hit_tokens": 256},
        config=m, steps_per_sync=8, live_kv_tokens=20000.0,
        peaks=arith.peaks("TPU v5 lite"),
        trace={"busy_s": 0.6, "host_spans": {"step": 4}, "programs": {}})


def test_serve_readers_by_hand():
    ctx = _serve_ctx()
    assert _reader("sched.queue_wait_p50_ms")(ctx) == pytest.approx(150.0)
    assert _reader("sched.prefix_hit_share")(ctx) == pytest.approx(64.0)
    assert _reader("sched.live_slots_mean")(ctx) == pytest.approx(31.0)
    assert _reader("step.chunk_ms.sat")(ctx) == pytest.approx(160.0)
    assert _reader("step.mixed_ms.chat")(ctx) == pytest.approx(275.0)
    m = ctx["config"]
    per = arith.decode_step_bytes(m, 20000.0)
    need = 4 * (8 * per + 0.5 * arith.decode_step_bytes(m, 0))
    share = _reader("kernel.decode_read_share")(ctx)
    assert share == pytest.approx(100 * need / 819e9 / 0.6)
    assert 0 < share < 100


def test_readers_with_nothing_to_read_return_nothing():
    ctx = dict(_serve_ctx(), spans=[],
               counters={"prompt_tokens": 0, "prefix_hit_tokens": 0},
               trace={"busy_s": 1.0, "host_spans": {}, "programs": {}})
    for name in ("sched.queue_wait_p50_ms", "sched.prefix_hit_share",
                 "sched.live_slots_mean", "step.chunk_ms.sat",
                 "step.mixed_ms.chat", "kernel.decode_read_share",
                 "train.step_ms", "train.mfu"):
        assert _reader(name)(ctx) is None, name


def test_train_readers_by_hand():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-coder-1.3b.json")) as f:
        m = json.load(f)
    ctx = dict(config=m, seq=2048, batch=4, chips=1,
               peaks=arith.peaks("TPU v5 lite"),
               memory_peak_bytes=12 * 2**30,
               trace={"programs": {"jit_step(1)": [0.40, 0.41, 0.39],
                                   "jit_split(2)": [1e-5] * 50}})
    flops = arith.train_flops_per_token(m, 2048)
    assert _reader("train.mfu")(ctx) == pytest.approx(
        100 * flops * 4 * 2048 / 0.4 / 197e12)
    assert 30 < _reader("train.mfu")(ctx) < 100
    assert _reader("train.step_ms")(ctx) == pytest.approx(400.0)
    assert _reader("train.peak_hbm_gib")(ctx) == 12.0


def check_entries(bench, root):
    """Every cell of `bench` as `run`, pointed at `root`, loads it: its
    sizes, its end-to-end metrics, a reader a per-layer metric and its
    driver, each found by name."""
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config"]["hidden_size"] >= 2048
        names = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        for m in cell["per_layer"]:
            assert callable(run.load_module("readers", m["name"]).read)
        assert run.load_module("drivers", cell["mix"]["driver"]).run


def test_load_cell_finds_every_file_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_entries(json.load(f), ROOT)
    with pytest.raises(SystemExit, match="no workload"):
        run.load_cell("nope")
    with pytest.raises(SystemExit, match="no readers"):
        run.load_module("readers", "nope")
