"""The comparison that decides `hy4-longctx-reason`'s `correct`
(`benchmark/drivers/serve_hy4.py`), on a tiny window of the cell through a tiny
engine: made on the longest requests the window finished, the sound run
passes at every position, every planted fault of `benchmark/reference_hy_v4.py`
and the lower-precision control fail. (Kept apart from `tests/benchmark/`,
whose window rehearsals of the other serving cells run first; the cell's own
tiny runs through run.py are `tests/benchmark/test_benchmark_cells.py`'s.)
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from test_benchmark_cells import _tiny  # noqa: E402

from benchmark import reference_hy_v4 as reference  # noqa: E402
from benchmark.drivers import serve_hy4 as drv  # noqa: E402

CELL = "hy4-longctx-reason"


@pytest.fixture(scope="module")
def served():
    """One window of the tiny cell through a tiny engine, and the requests
    of it that decide `correct`."""
    cell, kw = _tiny(CELL)
    m, said = cell["config"], []
    raw, profile, bad, p, dtype, pad_to = drv.serve(
        cell, 5, 1.5, False, said.append, kw["engine_kw"])
    samples = drv.sample_requests(raw["measured"])
    return dict(m=m, p=p, dtype=dtype, bad=bad, raw=raw, samples=samples,
                pad_to=pad_to, said=said)


def _reading(served, samples=None, faults=()):
    return drv.readings(*drv.position_readings(
        served["m"], served["p"], samples or served["samples"],
        served["pad_to"], faults))


def test_the_sound_run_passes_at_every_position(served):
    assert served["dtype"] == "float32" and served["bad"] == []
    m, samples, raw = served["m"], served["samples"], served["raw"]
    limits, said = drv.LIMITS["float32"], []
    bad, got = drv.check(m, served["p"], samples, served["pad_to"], limits,
                         said.append)
    assert bad == [] and not drv.over(got, limits)
    positions = sum(len(t) for _, t, _ in samples)
    assert got["positions"] == positions
    assert len(said) == 1 and f"{positions} positions: tie" in said[0]
    # the samples are the longest requests THE WINDOW finished, whole
    whole = [r for r, n in raw["measured"] if len(r.tokens) == n]
    assert len(samples) == drv.SAMPLE_REQUESTS
    longest = max(len(r.prompt) + len(r.tokens) for r in whole)
    assert len(samples[0][0]) + len(samples[0][1]) == longest
    for prompt, tokens, logprobs in samples:
        assert len(logprobs) == len(tokens) and max(logprobs) < 0
        # selection binds at every generated position
        assert len(prompt) > m["index_topk"]


def test_a_window_that_finished_nothing_fails_the_run(served):
    bad, _ = drv.check(served["m"], served["p"], [], served["pad_to"],
                       drv.LIMITS["float32"], print)
    assert bad and "finished no request" in bad[0]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_fails_the_run(served, fault):
    reading = _reading(served, faults=(fault,))
    assert drv.over(reading, drv.LIMITS["float32"]), (fault, reading)


def test_the_lower_precision_control_fails_the_run(served):
    with reference.lower_precision(drv.BELOW[served["dtype"]]):
        reading = _reading(served)
    assert drv.over(reading, drv.LIMITS["float32"]), reading
    assert drv.BELOW == {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def test_the_faults_script_reads_every_reference(served):
    from benchmark import serve_hy4_faults

    at = serve_hy4_faults.fault_readings(
        drv, served["m"], served["p"], served["samples"][:1],
        served["pad_to"], served["dtype"])
    assert list(at) == ["sound", "control", *reference.FAULTS]
    limits = drv.LIMITS["float32"]
    passes = [k for k, pair in at.items()
              if not drv.over(drv.readings(*pair), limits)]
    assert passes == ["sound"]
