"""Op-latency regression gate logic (reference:
tools/check_op_benchmark_result.py — compare current vs baseline op
latencies, flag >threshold regressions; round 3: the gate enforces —
unacknowledged regressions fail the bench run)."""
import json
import sys


def test_regression_detection(tmp_path, capsys, monkeypatch):
    sys.path.insert(0, "/root/repo")
    import bench

    monkeypatch.setattr(bench, "ACKNOWLEDGED_REGRESSIONS", {})
    path = str(tmp_path / "OPBENCH.json")
    # first run: records, no warnings
    warned = bench._op_regressions({"matmul": 10.0, "rms": 2.0}, path=path)
    assert warned == []
    with open(path) as f:
        assert json.load(f)["ops"]["matmul"] == 10.0
    # second run: 50% slower matmul flags; 5% slower rms does not
    warned = bench._op_regressions({"matmul": 15.0, "rms": 2.1}, path=path)
    assert len(warned) == 1 and "matmul" in warned[0]
    err = capsys.readouterr().err
    assert "OP REGRESSION" in err
    # the baseline is the rolling BEST: a persistent regression keeps
    # flagging (a noisy slow run can never inflate the bar)
    warned = bench._op_regressions({"matmul": 15.5, "rms": 2.1}, path=path)
    assert len(warned) == 1 and "matmul" in warned[0]
    # a recovered run re-arms cleanly
    warned = bench._op_regressions({"matmul": 10.2, "rms": 2.1}, path=path)
    assert warned == []
    # the absolute floor: >10% relative but <=0.1 ms delta is jitter on a
    # very short op, not a regression
    warned = bench._op_regressions({"matmul": 10.2, "rms": 2.1,
                                    "tiny": 0.5}, path=path)
    assert warned == []
    warned = bench._op_regressions({"matmul": 10.2, "rms": 2.1,
                                    "tiny": 0.58}, path=path)
    assert warned == []  # +16% but only +0.08 ms
    # crossing BOTH thresholds trips the gate
    warned = bench._op_regressions({"matmul": 10.2, "rms": 2.5}, path=path)
    assert len(warned) == 1 and "rms" in warned[0]


def test_acknowledged_regression_is_silenced(tmp_path, monkeypatch):
    sys.path.insert(0, "/root/repo")
    import bench

    path = str(tmp_path / "OPBENCH.json")
    monkeypatch.setattr(bench, "ACKNOWLEDGED_REGRESSIONS", {})
    bench._op_regressions({"matmul": 10.0}, path=path)
    monkeypatch.setattr(
        bench, "ACKNOWLEDGED_REGRESSIONS",
        {"matmul": "2026-07-31: known, documented in PERF.md"})
    warned = bench._op_regressions({"matmul": 20.0}, path=path)
    assert warned == []
    with open(path) as f:
        assert "matmul" in json.load(f)["acknowledged"]


def test_rebaseline_marker_skips_one_comparison(tmp_path, monkeypatch):
    sys.path.insert(0, "/root/repo")
    import bench

    path = str(tmp_path / "OPBENCH.json")
    monkeypatch.setattr(bench, "ACKNOWLEDGED_REGRESSIONS", {})
    bench._op_regressions({"matmul": 10.0}, path=path)
    monkeypatch.setattr(bench, "ACKNOWLEDGED_REGRESSIONS",
                        {"__rebaseline_test__": "timer change"})
    # marker absent from the previous table -> comparisons skipped once
    warned = bench._op_regressions({"matmul": 20.0}, path=path)
    assert warned == []
    # marker now recorded: the gate is re-armed against the new best
    warned = bench._op_regressions({"matmul": 30.0}, path=path)
    assert len(warned) == 1


def test_serving_decode_chunk_entry_is_gated(tmp_path, monkeypatch):
    """The engine's decode hot loop rides the op gate (ISSUE 3): a
    `serving_decode_chunk` row records into OPBENCH.json, is NOT
    informational (a chunk regression must flag), and goes through the
    re-measure-before-fail pass like every other gated op."""
    sys.path.insert(0, "/root/repo")
    import bench

    path = str(tmp_path / "OPBENCH.json")
    monkeypatch.setattr(bench, "ACKNOWLEDGED_REGRESSIONS", {})
    assert "serving_decode_chunk" not in bench.INFORMATIONAL_OPS
    # first run records the row
    assert bench._op_regressions({"serving_decode_chunk": 30.0},
                                 path=path) == []
    with open(path) as f:
        assert json.load(f)["ops"]["serving_decode_chunk"] == 30.0
    # a 33% chunk regression re-measures ONLY the suspect (never the
    # whole table) and, still slow, fails the gate
    measured = []
    monkeypatch.setattr(bench, "_op_bench",
                        lambda only=None: (measured.append(set(only)),
                                           {"serving_decode_chunk":
                                            39.5})[1])
    warned = bench._op_regressions({"serving_decode_chunk": 40.0},
                                   path=path)
    assert measured == [{"serving_decode_chunk"}]
    assert len(warned) == 1 and "serving_decode_chunk" in warned[0]
    with open(path) as f:
        # the better of the two measurements is what lands in the table
        assert json.load(f)["ops"]["serving_decode_chunk"] == 39.5
    # a re-measure that comes back healthy clears the flag
    monkeypatch.setattr(bench, "_op_bench",
                        lambda only=None: {"serving_decode_chunk": 30.1})
    assert bench._op_regressions({"serving_decode_chunk": 40.0},
                                 path=path) == []


def test_corrupt_previous_file_tolerated(tmp_path):
    sys.path.insert(0, "/root/repo")
    import bench

    path = str(tmp_path / "OPBENCH.json")
    with open(path, "w") as f:
        f.write("not json")
    assert bench._op_regressions({"matmul": 1.0}, path=path) == []
