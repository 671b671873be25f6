"""BENCHMARK.json against the contract's static rules, and every file a name
in it leads to."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_line(w) for w in bench["command"])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])


def test_cells_and_configs(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        mix = os.path.join(ROOT, "benchmark", "workloads",
                           w["traffic"] + ".json")
        with open(mix) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "drivers",
                                           driver + ".py"))
    assert used == set(configs), "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        # no width may be cut
        assert not [k for k in c["reduced"]
                    if k.endswith(("_dim", "_rank", "_size"))]
        assert body["hidden_size"] == \
            body["head_dim"] * body["num_attention_heads"]


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    cells = [w["name"] for w in bench["workloads"]]

    def cells_of(m):
        assert set(m.get("workloads", cells)) <= set(cells)
        return set(m.get("workloads", cells))

    for cell in cells:
        assert [m for m in bench["end_to_end"]
                if m["name"] != "setup_s" and cell in cells_of(m)], cell
        assert [m for m in bench["per_layer"] if cell in cells_of(m)], cell
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), m["name"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "readers", m["name"] + ".py")), m["name"]
    # layers of one name are spelled alike; PERF.md lists them
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, layer


def test_configs_hold_the_published_widths(bench):
    published = {
        "mistral-7b": dict(hidden_size=4096, intermediate_size=14336,
                           num_attention_heads=32, num_key_value_heads=8,
                           head_dim=128, vocab_size=32768,
                           rope_theta=1e6, rms_norm_eps=1e-5),
        "deepseek-coder-1.3b": dict(hidden_size=2048, intermediate_size=5504,
                                    num_attention_heads=16,
                                    num_key_value_heads=16, head_dim=128,
                                    vocab_size=32256, rope_theta=1e5,
                                    rms_norm_eps=1e-6),
    }
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        want = published[c["name"].replace("-x4", "")]
        assert {k: body[k] for k in want} == want
