"""Proof of admission: a configuration of another family — sparse experts and
latent attention, a third driver kind, a cell and a device-trace metric of
its own — joins a copy of the benchmark by new files and appended entries
alone, and every rule of `test_benchmark_contract.py` and the rehearsal of
`test_benchmark_cells.py` take it as they stand, with no test file changed.
Then what the rules refuse, each failure naming the key or the file.

The third family's numbers are the `model-configs` catalog's row for
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json, cut as
section 4 of that guide cuts an expert model to one of 8 chips that share each
layer. The driver here is a stub: this is the harness's room, not the model.
"""
import copy
import filecmp
import json
import os
import shutil

import pytest
from test_benchmark_cells import _check_line, _tiny, on_cpu  # noqa: F401
from test_benchmark_contract import (
    CHECKS, ROOT, TINY, check_configs_hold_the_published_widths,
    cpu_line_metrics, load_bench, load_json, source_file)

from benchmark import run

SOURCE = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
URL = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}
CONFIG, TRAFFIC, DRIVER = "expert-latent", "train-experts-4k", "train-experts"
CELL, METRIC = "expertlatent-train-4k", "train.mfu.active"
CONFIG_FILE = f"benchmark/configs/{CONFIG}.json"

STUB_DRIVER = '''"""A third driver kind, as far as run.py knows one: `run`."""
import time


def run(cell, seed, seconds, trace, say, steps=1):
    dep = cell["config"]["deployment"]
    say(f"check: {steps} step(s) of {dep['batch']} x {dep['seq']} tokens")
    return {"correct": True, "attempted": steps, "failed": 0,
            "end_to_end": {"train_tok_s": dep["batch"] * dep["seq"] / 1.0},
            "raw": {"t0": time.perf_counter()}}
'''
STUB_READER = '''"""Active-parameter FLOPs of a step over its device time."""


def read(ctx):
    return None
'''


def _write(root, path, content):
    full = os.path.join(root, path)
    os.makedirs(os.path.dirname(full), exist_ok=True)
    with open(full, "w") as f:
        if isinstance(content, str):
            f.write(content)
        else:
            json.dump(content, f, indent=1)


@pytest.fixture
def third(tmp_path):
    """(benchmark object, root) of a copy of the benchmark's data with the
    third family added. Nothing that was there is edited but the one
    `workloads` list the contract says a new cell extends."""
    root = str(tmp_path)
    for name in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(os.path.join(ROOT, name), root)
    for d in ("benchmark", TINY):
        shutil.copytree(os.path.join(ROOT, d), os.path.join(root, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = load_bench(root)
    _write(root, source_file(CONFIG_FILE), SOURCE)
    _write(root, CONFIG_FILE, dict(
        SOURCE, **CUT, source=URL, reduced=list(CUT),
        published={k: SOURCE[k] for k in CUT},
        deployment={"chips": 1, "batch": 1, "seq": 4096,
                    "stands_for": "one of 8 chips that share each layer"}))
    _write(root, f"benchmark/workloads/{TRAFFIC}.json",
           {"driver": DRIVER, "users": "pre-training jobs", "population": 64})
    _write(root, f"benchmark/drivers/{DRIVER}.py", STUB_DRIVER)
    _write(root, f"benchmark/readers/{METRIC}.py", STUB_READER)
    _write(root, f"{TINY}/configs/{CONFIG}.json", {
        "hidden_size": 64, "moe_intermediate_size": 32, "q_lora_rank": 24,
        "kv_lora_rank": 16, "n_routed_experts": 4, "vocab_size": 128,
        "num_hidden_layers": 3, "deployment": {"seq": 32}})
    _write(root, f"{TINY}/workloads/{TRAFFIC}.json", {"population": 4})
    _write(root, f"{TINY}/drivers/{DRIVER}.json", {"steps": 3})
    bench = load_bench(root)
    bench["configs"].append({
        "name": CONFIG, "source": URL, "file": CONFIG_FILE,
        "reduced": list(CUT), "why": "latent attention, sparse experts"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "1 x 4096-token batches back to back: experts and MLA"})
    bench["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "trainer",
        "moves": "train_tok_s", "workloads": [CELL]})
    {m["name"]: m for m in bench["end_to_end"]}["train_tok_s"][
        "workloads"].append(CELL)
    _write(root, "BENCHMARK.json", bench)
    _only_added(before, bench, root)
    return bench, root


def _only_added(before, after, root):
    """Every file the benchmark had is byte for byte what it was, and every
    entry it had is where it was; only lists grew, at their ends."""
    for d in ("benchmark", TINY):
        cmp = filecmp.dircmp(os.path.join(ROOT, d), os.path.join(root, d),
                             ignore=["__pycache__"])
        stack = [cmp]
        while stack:
            c = stack.pop()
            assert not c.left_only and not c.diff_files and not c.funny_files
            stack += c.subdirs.values()
    for group in ("configs", "workloads", "per_layer"):
        assert after[group][:len(before[group])] == before[group]
        assert len(after[group]) == len(before[group]) + 1
    for old, new in zip(before["end_to_end"], after["end_to_end"]):
        grown = dict(new)
        if "workloads" in old:
            grown["workloads"] = new["workloads"][:len(old["workloads"])]
        assert grown == old
    assert {k: after[k] for k in ("command", "paths", "run_seconds")} \
        == {k: before[k] for k in ("command", "paths", "run_seconds")}


def _run_checks(bench, root):
    for check in CHECKS:
        check(bench, root)


def test_a_third_family_clears_every_contract_check(third):
    bench, root = third
    body = load_json(root, CONFIG_FILE)
    # the shape the rules used to refuse: no head_dim, and heads x any head
    # size of the file is not the hidden size
    assert "head_dim" not in body
    assert body["hidden_size"] != body["num_attention_heads"] \
        * body["v_head_dim"]
    assert body["vocab_size"] * 8 == body["published"]["vocab_size"]
    _run_checks(bench, root)
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    assert cpu_line_metrics(mine) == (set(), {METRIC})


def test_a_third_driver_kind_is_rehearsed_from_its_tiny_presets(
        third, on_cpu, monkeypatch, capsys):  # noqa: F811
    """run.py's own functions, pointed at the copy, and the cells test's own
    `_tiny`: the cell is loaded, shrunk and driven with no name known here."""
    _, root = third
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "HERE", os.path.join(root, "benchmark"))
    cell, kw = _tiny(CELL)
    assert kw == {"steps": 3}
    assert cell["config"]["deployment"] == {
        "chips": 1, "batch": 1, "seq": 32,
        "stands_for": "one of 8 chips that share each layer"}
    assert cell["config"]["qk_rope_head_dim"] == 64     # not overlaid
    assert cell["mix"] == {"driver": DRIVER, "users": "pre-training jobs",
                           "population": 4}
    out = run.run_cell(cell, 2**31 + 5, 0.1, False, **kw)
    _check_line(out, cell, trace=False)
    assert out["attempted"] == 3
    assert out["metrics"]["train_tok_s"]["value"] == 32.0
    assert "check: 3 step(s) of 1 x 32 tokens" in capsys.readouterr().out


def _cut_a_width(bench, root):
    body = load_json(root, CONFIG_FILE)
    body["reduced"].append("moe_intermediate_size")
    body["published"]["moe_intermediate_size"] = 1536
    body["moe_intermediate_size"] = 768
    _write(root, CONFIG_FILE, body)
    bench["configs"][-1]["reduced"].append("moe_intermediate_size")


def _change_a_value(bench, root):
    body = load_json(root, CONFIG_FILE)
    body["kv_lora_rank"] = 256
    _write(root, CONFIG_FILE, body)


def _drop_a_published_value(bench, root):
    body = load_json(root, CONFIG_FILE)
    del body["published"]["n_routed_experts"]
    _write(root, CONFIG_FILE, body)


def _drop_the_source_file(bench, root):
    os.remove(os.path.join(root, source_file(CONFIG_FILE)))


def _drop_a_tiny_file(bench, root):
    os.remove(os.path.join(root, TINY, "drivers", DRIVER + ".json"))


@pytest.mark.parametrize("fault, names", [
    (_cut_a_width, r"no width may be cut.*moe_intermediate_size"),
    (_change_a_value, r"kv_lora_rank is 256 in .*expert-latent\.json, 512"),
    (_drop_a_published_value, r"`published` of .*expert-latent\.json holds "
                              r"\['num_hidden_layers', 'vocab_size'\]"),
    (_drop_the_source_file, r"no source file .*expert-latent\.source\.json"),
    (_drop_a_tiny_file, r"no tiny preset .*drivers/train-experts\.json"),
], ids=lambda x: x.__name__ if callable(x) else "")
def test_what_the_rules_refuse_is_named(third, fault, names):
    bench, root = third
    fault(bench, root)
    with pytest.raises(AssertionError, match=names):
        _run_checks(bench, root)


def _source_keys():
    return [(c["name"], k) for c in load_bench(ROOT)["configs"]
            for k in load_json(ROOT, source_file(c["file"]))]


@pytest.mark.parametrize("config, key", _source_keys())
def test_every_published_value_is_held(tmp_path, config, key):
    """Each configuration the benchmark has, held to each key of its source
    file: a file without the value, or with another, fails with the key."""
    root, bench = str(tmp_path), load_bench(ROOT)
    entry = {c["name"]: c for c in bench["configs"]}[config]
    only = dict(bench, configs=[entry])
    body = load_json(ROOT, entry["file"])
    _write(root, source_file(entry["file"]),
           load_json(ROOT, source_file(entry["file"])))
    _write(root, entry["file"], body)
    check_configs_hold_the_published_widths(only, root)
    for change in ("remove", "alter"):
        broken = copy.deepcopy(body)
        where = broken["published"] if key in entry["reduced"] else broken
        if change == "remove":
            del where[key]
        else:
            where[key] = [where[key]]
        _write(root, entry["file"], broken)
        with pytest.raises(AssertionError, match=rf"{config}: .*{key}"):
            check_configs_hold_the_published_widths(only, root)
