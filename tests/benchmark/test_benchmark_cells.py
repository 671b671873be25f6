"""Every cell rehearsed end to end on the CPU through run.py's own functions,
at tiny sizes: the traffic, the driver, the correctness checks, the trace and
its readers, and the keys of the last line. Sizes are steered here, in the
test — run.py has no option for it — from the presets under
`tests/benchmark/tiny/`, found by the cell's configuration, traffic mix and
driver kind (`test_benchmark_contract.tiny_overlays`): a new cell brings its
own. No number from these runs means anything: the CPU is not the device.
"""
import copy
import json
import os
import sys

import pytest
from test_benchmark_contract import (cpu_line_metrics, load_bench, overlay,
                                     tiny_overlays)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, tracing  # noqa: E402

# device-trace metrics that a CPU line holds all the same, kept as pins
IN_THE_CPU_LINE = {"mistral7b-reason-sat": {"kernel.decode_read_share"}}


@pytest.fixture
def on_cpu(monkeypatch):
    """The device check says yes, and the CPU's own op events (there is no
    device plane off the chip) stand in for the device's."""
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(tracing, "is_device_plane",
                        lambda name: name == "/host:CPU")
    monkeypatch.setattr(tracing, "op_events", lambda lines: [
        e for ln, evs in lines.items()
        if ln.startswith("tf_XLAPjRtCpuClient") for e in evs
        if not e[0].startswith(("Threadpool", "end:", "Slinky"))])
    monkeypatch.setattr(tracing, "TRACE_START_S", 0.3)
    monkeypatch.setattr(tracing, "TRACE_SECONDS", 0.5)


def _tiny(workload):
    """The cell as run.py loads it from `run.ROOT`, its tiny presets written
    over its sizes and its mix, and the keyword arguments its driver's
    rehearsal takes."""
    cell = copy.deepcopy(run.load_cell(workload))
    config, mix, driver_kw = tiny_overlays(load_bench(run.ROOT), run.ROOT,
                                           workload)
    overlay(cell["config"], config, "config")
    overlay(cell["mix"], mix, "mix")
    return cell, driver_kw


def _cells():
    return [w["name"] for w in load_bench(ROOT)["workloads"]]


def _check_line(out, cell, trace):
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"} | ({"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    group = cell["per_layer" if trace else "end_to_end"]
    units = {m["name"]: m["unit"] for m in group}
    assert out["metrics"] and set(out["metrics"]) <= set(units)
    for name, v in out["metrics"].items():
        assert set(v) == {"value", "unit"} and v["unit"] == units[name]
        assert v["value"] == v["value"] and v["value"] >= 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])
    json.dumps(out)


@pytest.mark.parametrize("workload", _cells())
def test_cell_end_to_end_run(on_cpu, workload, capsys):
    cell, kw = _tiny(workload)
    out = run.run_cell(cell, 2**31 + 5, 1.5, False, **kw)
    _check_line(out, cell, trace=False)
    # every end-to-end metric of the cell is there, set-up among them
    assert set(out["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert out["metrics"]["setup_s"]["value"] > 0
    text = capsys.readouterr().out
    assert "check: FAILED" not in text and "check:" in text


@pytest.mark.parametrize("workload", _cells())
def test_cell_traced_run(on_cpu, workload):
    cell, kw = _tiny(workload)
    out = run.run_cell(cell, 11, 1.5, True, **kw)
    _check_line(out, cell, trace=True)
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    bd = out["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    must, may = cpu_line_metrics(cell["per_layer"])
    must |= IN_THE_CPU_LINE.get(workload, set())
    assert must <= set(out["metrics"]) <= may


def test_sharded_train_cell_on_four_virtual_devices(on_cpu):
    """The four-chip cell's path — the trainer on a {"sharding": 4} mesh,
    batch split over it — on the conftest's virtual devices."""
    cell, kw = _tiny("dscoder1p3b-train-2k")
    cell["chips"] = 4
    cell["config"]["deployment"].update(
        batch=4, mesh={"dp": 1, "sharding": 4, "mp": 1, "sep": 1})
    out = run.run_cell(cell, 3, 1.0, False, **kw)
    _check_line(out, cell, trace=False)
    assert out["device"]["count"] == 4


def test_a_wrong_token_fails_the_run(on_cpu, monkeypatch, capsys):
    """The reference comparison has teeth."""
    from benchmark import reference

    real = reference.reference_last_logits
    monkeypatch.setattr(reference, "reference_last_logits",
                        lambda *a: -real(*a))
    cell, kw = _tiny("mistral7b-reason-sat")
    out = run.run_cell(cell, 1, 0.5, False, **kw)
    assert out["correct"] is False
    assert "trails the f32 reference" in capsys.readouterr().out


def test_no_accelerator_no_result():
    with pytest.raises(SystemExit, match="no accelerator"):
        run.check_device(1)
