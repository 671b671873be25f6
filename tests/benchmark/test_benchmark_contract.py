"""BENCHMARK.json against the contract's static rules, and every file a name
in it leads to.

Each rule is a function of (the benchmark object, the root directory it was
read from) and finds what it checks by the names the data gives, so the same
rules hold a configuration of another family that a later PR adds by new
files and appended entries (`test_benchmark_admission.py` runs them over such
a copy). No configuration, cell or driver kind is named here.
"""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# the tiny presets of the CPU rehearsals, kept with the tests
TINY = os.path.join("tests", "benchmark", "tiny")
# what every decoder's published config.json states: a source file without
# them holds the configuration to nothing
SOURCE_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
               "vocab_size")


def load_json(root, *path):
    with open(os.path.join(root, *path)) as f:
        return json.load(f)


def load_bench(root):
    return load_json(root, "BENCHMARK.json")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def is_width(key: str) -> bool:
    """Whether `reduced` may never list `key`. A width is a hidden,
    intermediate, latent or projection size, a head size, a rank: every key
    that ends in `_dim`, `_rank` or `_size` — with one exception,
    `vocab_size`, which counts the rows of the embedding and the head and is
    sliced like the experts a chip holds. Experts per token is the router's
    width. Counts of layers, heads, experts held and vocabulary rows are
    what `reduced` may list."""
    if key == "vocab_size":
        return False
    return key.endswith(("_dim", "_rank", "_size")) \
        or key == "num_experts_per_tok"


def source_file(config_file: str) -> str:
    """`<name>.source.json` beside the configuration's `<name>.json`: the
    source's own shape keys, verbatim."""
    assert config_file.endswith(".json"), config_file
    return config_file[:-len(".json")] + ".source.json"


def overlay(target: dict, patch: dict, where: str) -> None:
    """Write `patch` over `target` in place, group by group. A key the target
    lacks is a misspelling: a tiny preset changes sizes, it adds none."""
    for k, v in patch.items():
        assert k in target, f"{where}: no key {k!r} to overlay"
        if isinstance(v, dict) and isinstance(target[k], dict):
            overlay(target[k], v, f"{where}.{k}")
        else:
            target[k] = v


def tiny_overlays(bench: dict, root: str, workload: str):
    """(configuration overlay, mix overlay, the driver's keyword arguments) of
    one cell's CPU rehearsal: `tiny/configs/<config>.json`,
    `tiny/workloads/<traffic>.json` and `tiny/drivers/<driver>.json`. A
    missing file fails with its name."""
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    mix = load_json(root, bench["paths"][0], "workloads",
                    cell["traffic"] + ".json")
    out = []
    for kind, name in (("configs", cell["config"]),
                       ("workloads", cell["traffic"]),
                       ("drivers", mix["driver"])):
        path = os.path.join(TINY, kind, name + ".json")
        assert os.path.isfile(os.path.join(root, path)), \
            f"{workload}: no tiny preset {path}"
        out.append(load_json(root, path))
    return tuple(out)


def cpu_line_metrics(per_layer: list):
    """(the metrics a CPU rehearsal's traced line must hold, those it may
    hold) of one cell's per-layer entries. A metric whose entry says
    `"source": "device_trace"` reads whole programs or kernels off the device
    plane, which the CPU's trace does not have: its reader finds nothing and
    the line leaves it out."""
    return ({m["name"] for m in per_layer if m["source"] != "device_trace"},
            {m["name"] for m in per_layer})


# ---- the rules -----------------------------------------------------------

def check_top_level_keys(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_line(w) for w in bench["command"])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(root, p))


def check_names_and_units(bench, root):
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


def check_cells_and_configs(bench, root):
    home = bench["paths"][0]
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
        driver = load_json(root, home, "workloads",
                           w["traffic"] + ".json")["driver"]
        assert NAME.match(driver), driver
        assert os.path.isfile(os.path.join(root, home, "drivers",
                                           driver + ".py")), driver
    assert used == set(configs), "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(home + "/")
        body = load_json(root, c["file"])
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        cut = [k for k in c["reduced"] if is_width(k)]
        assert not cut, f"{c['name']}: no width may be cut, reduced " \
                        f"lists {cut}"


def check_every_cell_reports_what_it_must(bench, root):
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
            root, bench["paths"][0], "readers", m["name"] + ".py")), m["name"]
    # layers of one name are spelled alike; PERF.md lists them
    with open(os.path.join(root, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, layer


def check_configs_hold_the_published_widths(bench, root):
    """Every key of the source is in the configuration's file with the
    source's value, unless `reduced` lists it; then the file's `published`
    holds the source's value beside the one that is run."""
    for c in bench["configs"]:
        who, path = c["name"], source_file(c["file"])
        assert os.path.isfile(os.path.join(root, path)), \
            f"{who}: no source file {path}"
        src, body = load_json(root, path), load_json(root, c["file"])
        lacks = [k for k in SOURCE_KEYS + tuple(c["reduced"])
                 if k not in src]
        assert not lacks, f"{who}: {path} lacks {lacks}"
        published = body.get("published", {})
        assert set(published) == set(c["reduced"]), \
            f"{who}: `published` of {c['file']} holds {sorted(published)}, " \
            f"`reduced` lists {c['reduced']}"
        for k, v in src.items():
            where = published if k in c["reduced"] else body
            assert k in where, f"{who}: {k} of {path} is not in {c['file']}"
            assert where[k] == v, \
                f"{who}: {k} is {where[k]!r} in {c['file']}, {v!r} in {path}"


def check_every_cell_has_its_tiny_presets(bench, root):
    home = bench["paths"][0]
    for w in bench["workloads"]:
        config, mix, driver_kw = tiny_overlays(bench, root, w["name"])
        cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
        overlay(load_json(root, cfg["file"]), config,
                f"{TINY}/configs/{w['config']}.json")
        overlay(load_json(root, home, "workloads", w["traffic"] + ".json"),
                mix, f"{TINY}/workloads/{w['traffic']}.json")
        assert isinstance(driver_kw, dict)


CHECKS = (check_top_level_keys, check_names_and_units,
          check_cells_and_configs, check_every_cell_reports_what_it_must,
          check_configs_hold_the_published_widths,
          check_every_cell_has_its_tiny_presets)


@pytest.mark.parametrize("check", CHECKS, ids=lambda f: f.__name__[6:])
def test_contract(check):
    check(load_bench(ROOT), ROOT)


def test_is_width():
    for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "qk_nope_head_dim", "v_head_dim", "q_lora_rank",
              "kv_lora_rank", "num_experts_per_tok"):
        assert is_width(k), k
    for k in ("vocab_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "n_routed_experts", "rope_scaling"):
        assert not is_width(k), k


def test_overlay_merges_groups_and_refuses_a_new_key():
    target = {"a": 1, "g": {"x": 1, "y": 2}}
    overlay(target, {"a": 3, "g": {"y": 5}}, "t")
    assert target == {"a": 3, "g": {"x": 1, "y": 5}}
    with pytest.raises(AssertionError, match=r"t\.g: no key 'z'"):
        overlay(target, {"g": {"z": 1}}, "t")
