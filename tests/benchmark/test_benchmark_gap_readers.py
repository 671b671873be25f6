"""The readers of ISSUE 37's per-layer metrics, by hand on built spans: the
engine's host path between two programs — `decode.stage` and
`decode.enqueue` inside `decode.dispatch`, `decode.device_wait` and
`decode.readback` inside `decode.sync_wait`, each carrying the program's
`chunk`. A program without them (the parent) gives every reader `None`."""
import json
import os

import numpy as np
import pytest
from test_benchmark_phase_readers import _x
from test_benchmark_tracing import _reader

from benchmark import arith

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = ("sched.gap_ms", "sched.stage_ms", "sched.enqueue_ms",
       "sched.readback_ms")
CELLS = ["mellum2-reason-long", "mistral7b-reason-sat"]


def _dispatch(k, t, stage, enqueue):
    """Program k's `decode.dispatch` at `t` (seconds): its stage, then its
    enqueue. Returns (spans, end)."""
    end = t + stage + enqueue
    return [_x("decode.stage", t, t + stage, chunk=k, h2d=7, h2d_bytes=900),
            _x("decode.enqueue", t + stage, end, chunk=k),
            _x("decode.dispatch", t, end, chunk=k, live=32)], end


def _wait(k, t, wait, readback):
    """Program k's `decode.sync_wait` at `t`: the device wait, then one
    readback a duration in `readback`. Returns (spans, end)."""
    out = [_x("decode.device_wait", t, t + wait, chunk=k)]
    at = t + wait
    for r in readback:
        out.append(_x("decode.readback", at, at + r, chunk=k, d2h=3,
                      d2h_bytes=4096))
        at += r
    return out + [_x("decode.sync_wait", t, at, chunk=k, stalled=False)], at


# the synchronous engine, ms: (stage, enqueue, device wait, readbacks, host
# work after the readback: commit, the driver, admission and build)
SYNC = [(2.0, 1.0, 100.0, (0.5, 0.3), 4.0),
        (3.0, 1.0, 100.0, (1.0,), 2.0),
        (2.0, 2.0, 100.0, (0.4, 0.4, 0.2), 6.0),
        (2.0, 1.0, 100.0, (0.5,), 1.0)]


def _sync(t=10.0, first=1, programs=SYNC):
    spans = []
    for k, (st, en, wt, rb, host) in enumerate(programs, first):
        d, t = _dispatch(k, t, st / 1e3, en / 1e3)
        w, t = _wait(k, t, wt / 1e3, [r / 1e3 for r in rb])
        spans += d + w
        t += host / 1e3
    return spans


def _ctx(spans):
    return {"t0": 10.0, "t1": 20.0, "spans": spans}


def _before_the_window():
    """A program before t0, far too slow: must not be counted."""
    return _sync(t=9.0, first=0, programs=[(50.0, 50.0, 1.0, (50.0,), 0.0)])


def test_the_synchronous_engine_by_hand():
    ctx = _ctx(_before_the_window() + _sync())
    # the gap after program k: its readbacks, the host's work, then program
    # k+1's stage and enqueue; the last program has no successor
    gaps = [0.8 + 4.0 + 3.0 + 1.0, 1.0 + 2.0 + 2.0 + 2.0,
            1.0 + 6.0 + 2.0 + 1.0]
    assert _reader("sched.gap_ms")(ctx) == pytest.approx(arith.median(gaps))
    assert _reader("sched.stage_ms")(ctx) == pytest.approx(2.0)
    assert _reader("sched.enqueue_ms")(ctx) == pytest.approx(1.0)
    # summed over a program's readbacks: 0.8, 1.0, 1.0, 0.5
    assert _reader("sched.readback_ms")(ctx) == pytest.approx(0.9)
    # the sync engine's parts lie inside its gap
    parts = sum(_reader(n)(ctx) for n in NEW[1:])
    assert parts <= _reader("sched.gap_ms")(ctx)


def test_spans_before_the_window_are_left_out():
    inside = _ctx(_sync())
    both = _ctx(_before_the_window() + _sync())
    for name in NEW:
        assert _reader(name)(both) == pytest.approx(_reader(name)(inside))
    # and the window's end: a window that closes after the first program
    # reads that program alone (its successor's enqueue may lie past t1)
    short = dict(inside, t1=10.05)
    assert _reader("sched.gap_ms")(short) == pytest.approx(8.8)
    assert _reader("sched.stage_ms")(short) == pytest.approx(2.0)


def test_spans_pair_by_chunk_not_by_order():
    spans = _sync()
    want = {name: _reader(name)(_ctx(spans)) for name in NEW}
    rng = np.random.default_rng(37)
    for _ in range(3):
        mixed = [spans[i] for i in rng.permutation(len(spans))]
        for name in NEW:
            assert _reader(name)(_ctx(mixed)) == pytest.approx(want[name])
    # a program between two that never enqueued on this path (a verify
    # step's chunk number): k = 2 has no k + 1, so its gap is not read
    gone = [e for e in spans if e["args"].get("chunk") != 3]
    assert _reader("sched.gap_ms")(_ctx(gone)) == pytest.approx(8.8)


def test_a_pipelined_engine_reads_a_gap_of_zero():
    """Double-buffered: program k+1 is enqueued before the host waits on k,
    so every gap is negative before the floor."""
    spans, t = [], 10.0
    d, t = _dispatch(1, t, 0.002, 0.001)
    spans += d
    for k in range(1, 5):
        d, t = _dispatch(k + 1, t + 0.001, 0.002, 0.001)
        w, t = _wait(k, t, 0.095, [0.0008])
        spans += d + w
    ctx = _ctx(spans)
    assert _reader("sched.gap_ms")(ctx) == 0.0
    assert _reader("sched.stage_ms")(ctx) == pytest.approx(2.0)
    assert _reader("sched.readback_ms")(ctx) == pytest.approx(0.8)


@pytest.mark.parametrize("name", NEW)
def test_a_parent_gives_nothing(name):
    """The parent's spans: `decode.dispatch` and `decode.sync_wait` with no
    children and no `chunk` on the wait; and no spans at all."""
    parent = []
    for e in _sync():
        if e["name"] in ("decode.dispatch", "decode.sync_wait"):
            e = dict(e, args={k: v for k, v in e["args"].items()
                              if e["name"] == "decode.dispatch"
                              or k != "chunk"})
            parent.append(e)
    assert _reader(name)(_ctx(parent)) is None
    assert _reader(name)(_ctx([])) is None


@pytest.mark.parametrize("name", NEW)
def test_the_entries_are_appended_for_both_serving_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [x["name"] for x in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    (entry,) = [x for x in bench["per_layer"] if x["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "engine scheduler",
                     "moves": "output_tok_s", "workloads": CELLS}
    assert not name.endswith("mellum2")
