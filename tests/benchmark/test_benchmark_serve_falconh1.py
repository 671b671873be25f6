"""The `falconh1-chat-sat` cell's own parts: its configuration against the
source, its mix, `arith_falcon_h1`'s counts against a hand count, its readers
on made-up runs, and its entries. The comparison on a tiny window of the cell
— the sound run passes, every planted fault and the lower-precision control
fail — is `tests/test_falcon_h1.py`'s; the cell's tiny runs through run.py
are `test_benchmark_cells.py`'s.
"""
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest
from test_benchmark_contract import load_bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith_falcon_h1 as arith  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.drivers import serve_falconh1 as drv  # noqa: E402

CELL = "falconh1-chat-sat"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "falcon-h1-34b")
CUT = {"num_hidden_layers": (6, 72)}


def reader(name):
    return run.load_module("readers", name).read


@pytest.fixture(scope="module")
def published():
    with open(CONFIG + ".json") as f:
        return json.load(f)


# ---- the configuration --------------------------------------------------------

def test_the_configuration_is_the_catalog_row_cut_as_listed(published):
    with open(CONFIG + ".source.json") as f:
        src = json.load(f)
    assert published["reduced"] == list(CUT)
    assert published["published"] == {k: v[1] for k, v in CUT.items()}
    for k, v in src.items():
        assert published[k] == CUT[k][0] if k in CUT else published[k] == v
    for k in ("assumed", "deployment", "notes", "source"):
        assert published[k]
    assert "state_dtype" in published["assumed"]
    assert "initializer" in published["assumed"]
    dep = published["deployment"]
    assert dep["chips"] == 1 and dep["pipeline_stages"] == 12
    assert dep["slots"] == 64 and dep["token_budget"] == 512
    assert dep["token_budget"] % dep["block_size"] == 0
    assert dep["token_budget"] % published["mamba_chunk_size"] == 0
    assert published["num_hidden_layers"] * dep["pipeline_stages"] \
        == CUT["num_hidden_layers"][1]
    # MODEL_CATALOG names a JSONL catalog of published model configurations.
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.isfile(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["source_url"] == published["source"]]
        assert row and row[0]["config"] == src


def test_the_model_is_the_files_every_multiplier(published):
    cfg = drv.model_config(published, "bfloat16")
    assert cfg.num_hidden_layers == 6 and cfg.vocab_size == 261120
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups) == (32, 128, 256, 2)
    assert cfg.proj_dim == 9248 and cfg.conv_dim == 5120
    model = cfg.served_model()
    assert model.embed_scale == published["embedding_multiplier"]
    assert model.logit_scale == published["lm_head_multiplier"]
    layer = model.layers[0]
    assert layer.attn_scales == (published["attention_in_multiplier"],
                                 published["key_multiplier"],
                                 published["attention_out_multiplier"])
    assert all(l.mixer is not None and l.window is None
               for l in model.layers)
    assert cfg.ssm_multipliers == tuple(published["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(published["mlp_multipliers"])


def test_the_mix_fits_the_deployment_and_is_the_cells(published):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "chat-sat.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "serve_falconh1"
    assert mix["arrivals"] == {"kind": "closed", "clients": 96}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.7, "min": 64, "max": 1536}
    assert mix["output_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.5, "min": 128, "max": 1024}
    assert mix["shared_prefix_tokens"] == 0 and mix["order"] == "golden"
    assert mix["window_opens"] == "all_slots_live"
    dep = published["deployment"]
    assert mix["prompt_len"]["max"] <= dep["max_prompt_len"]
    assert mix["output_len"]["max"] <= dep["max_new_tokens"]
    from benchmark.traffic import lengths_block

    # about half the prompts pass what a window leaves beside the decode
    # rows: their state is carried from window to window
    prompts = lengths_block(mix["prompt_len"])
    assert 0.3 < np.mean(prompts > dep["token_budget"] - dep["slots"]) < 0.6


# ---- arithmetic ----------------------------------------------------------------

def test_counts_against_a_hand_count(published):
    m = published
    assert arith.attention_params(m) == 31_457_280
    assert arith.mixer_params(m) == 68_351_072
    assert arith.mlp_params(m) == 330_301_440
    assert arith.layer_params(m) == 430_120_032
    from paddle_tpu.models.falcon_h1 import Mamba2Mixer, serving_param_shapes

    cfg = drv.model_config(m, "bfloat16")
    n = sum(int(np.prod(s)) for s in serving_param_shapes(cfg).values())
    emb = 261120 * 5120
    assert n == 5_254_594_112 == 6 * 430_120_032 + 2 * emb + 5120
    assert arith.decode_weight_bytes(m) == 2 * (n - emb)
    assert arith.state_row_bytes(m) == 4_255_744 \
        == Mamba2Mixer(cfg).row_bytes()
    assert arith.ssm_state_bytes(m) == 4 * 32 * 128 * 256
    assert arith.kv_token_bytes(m) == 12_288
    # the issue's table: 64 live sequences at ~780 tokens of context
    step = arith.decode_step_bytes(m, 64, 64 * 780)
    assert 2 * 64 * 6 * 4_255_744 == 3_268_411_392
    assert step == arith.decode_weight_bytes(m) + 64 * 780 * 12_288 \
        + 3_268_411_392
    assert 11.5e9 < step < 11.9e9
    assert arith.ssd_decode_bytes(m, 1) \
        == 2 * 4 * 32 * 128 * 256 + 4 * (2 * 32 * 128 + 2 * 2 * 256 + 32)
    assert arith.ssd_scan_flops(m, 1) == 32 * (2 * 128 * 384 + 4 * 256 * 128)
    assert arith.ssd_scan_bytes(m, 10, 2) == 2 * 2 * 4 * 32 * 128 * 256 \
        + 10 * 4 * (2 * 32 * 128 + 2 * 2 * 256 + 32)


# ---- readers on a made-up run ---------------------------------------------------

def _ctx(m, **over):
    """A trace of 10 scheduling steps (8 decode steps each, 60 live rows, 6
    layers) and 3 prefill windows of 400 tokens, 2 of them carried."""
    ctx = {
        "config": m, "spans": [], "t0": 0.0, "t1": 50.0,
        "steps_per_sync": 8, "slots": 64,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "ssm_traced": {"ssm_decode_slot_steps": 10 * 8 * 6 * 60,
                       "ssm_scan_tokens": 3 * 6 * 400,
                       "ssm_scan_segments": 3 * 6,
                       "ssm_scan_carried": 2 * 6},
        "gauges_traced": {"kv_tokens_live": 50_000.0,
                          "requests_active": 60.0},
        "trace": {"busy_s": 2.0, "window_s": 2.1,
                  "host_spans": {"step": 10},
                  "programs": {"jit_serve_decode_chunk": [0.15, 0.16, 0.17],
                               "jit_serve_unified_step": [0.2, 0.22, 0.24]},
                  "device_op_s": {
                      "ssd_decode f32[66,32,256,128]": 0.6,
                      "ssd_scan f32[66,32,256,128]": 0.02,
                      "decode_attention bf16[64,20,128]": 0.05,
                      "fusion bf16[64,5120]": 1.0}}}
    ctx.update(over)
    return ctx


def test_every_reader_on_a_made_up_run(published):
    m, ctx = published, _ctx(published)
    slot_steps = 10 * 8 * 6 * 60
    assert reader("kernel.ssd_decode_read_share.falconh1")(ctx) \
        == pytest.approx(100 * arith.ssd_decode_bytes(m, slot_steps)
                         / 819e9 / 0.6)
    need = max(arith.ssd_scan_flops(m, 7200) / 197e12,
               arith.ssd_scan_bytes(m, 7200, 18) / 819e9)
    assert reader("kernel.ssd_scan_roofline_share.falconh1")(ctx) \
        == pytest.approx(100 * need / 0.02)
    assert reader("ssm.dev_share.falconh1")(ctx) == pytest.approx(
        100 * 0.62 / 2.0)
    step = arith.decode_step_bytes(m, 60, 50_000)
    assert reader("kernel.decode_read_share.falconh1")(ctx) \
        == pytest.approx(100 * 80 * step / 819e9 / 2.0)
    assert reader("step.mixed_dev_ms.falconh1")(ctx) == pytest.approx(220.0)
    for name in NEW_READERS[:4]:
        assert 0 < reader(name)(ctx) < 100, name


def _host_spans():
    """Four programs' engine spans, 10 ms apart (microseconds)."""
    out = []
    for k in range(4):
        t = 1e6 + 1e4 * k
        for name, at, dur in (("decode.dispatch", 0, 900), ("decode.stage",
                              0, 300), ("decode.enqueue", 300, 600),
                              ("decode.device_wait", 1000, 7000 + 100 * k),
                              ("decode.readback", 8100, 500)):
            out.append({"name": name, "ph": "X", "ts": t + at, "dur": dur,
                        "args": {"chunk": k, "live": 46 + k}})
    return out


def test_the_aliases_read_as_their_namesakes(published):
    ctx = _ctx(published, spans=_host_spans())
    said = {}
    for name in NEW_READERS:
        with open(os.path.join(ROOT, "benchmark", "readers",
                               name + ".py")) as f:
            found = re.findall(r'same_as\("([^"]+)"\)', f.read())
        if found:
            said[name] = found[0]
    assert said == {"step.mixed_dev_ms.falconh1": "step.mixed_dev_ms.sat",
                    **{f"sched.{m}.falconh1": f"sched.{m}" for m in (
                        "gap_ms", "stage_ms", "enqueue_ms", "readback_ms",
                        "live_slots_mean")}}
    for name, namesake in said.items():
        got = reader(name)(ctx)
        assert got is not None and got == reader(namesake)(ctx), name


# the cell's per-layer entries, in their order in BENCHMARK.json
NEW_READERS = [
    "kernel.ssd_decode_read_share.falconh1",
    "kernel.ssd_scan_roofline_share.falconh1", "ssm.dev_share.falconh1",
    "kernel.decode_read_share.falconh1", "step.mixed_dev_ms.falconh1",
    "sched.gap_ms.falconh1", "sched.stage_ms.falconh1",
    "sched.enqueue_ms.falconh1", "sched.readback_ms.falconh1",
    "sched.live_slots_mean.falconh1"]


@pytest.mark.parametrize("name", NEW_READERS[:4])
def test_a_reader_finds_nothing_on_a_program_that_records_nothing(
        published, name):
    """The parent's run: no `ssm_*` counters, none of the labels — every
    reader returns None and does not raise."""
    ctx = _ctx(published, ssm_traced=None,
               trace={"busy_s": 2.0, "window_s": 2.1, "host_spans": {},
                      "programs": {}, "device_op_s": {
                          "fusion bf16[64,5120]": 0.4}})
    assert reader(name)(ctx) is None


def check_entries(bench, root):
    """The cell's entries of `bench` and their readers under `root`: its own
    per-layer entries exactly, the end-to-end list by membership (a later
    serving cell is appended to it). A benchmark without the cell names it
    nowhere."""
    if CELL not in {w["name"] for w in bench["workloads"]}:
        assert not [x for x in bench["per_layer"] + bench["end_to_end"]
                    if CELL in x.get("workloads", [])]
        assert "falcon-h1-34b" not in {c["name"] for c in bench["configs"]}
        return
    mine = [x for x in bench["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in mine] == NEW_READERS
    assert all(x["moves"] == "output_tok_s" for x in mine)
    by = {x["name"]: x for x in bench["per_layer"]}
    for namesake in ("step.mixed_dev_ms.sat", "sched.gap_ms",
                     "sched.stage_ms", "sched.enqueue_ms", "sched.readback_ms",
                     "sched.live_slots_mean"):
        own = by[namesake.replace(".sat", "") + ".falconh1"]
        assert {**own, "name": 0, "workloads": 0} \
            == {**by[namesake], "name": 0, "workloads": 0}
    readers = os.path.join(root, "benchmark", "readers")
    for name in NEW_READERS:
        assert os.path.isfile(os.path.join(readers, name + ".py"))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "falcon-h1-34b"
    assert cells[CELL]["chips"] == 1 and cells[CELL]["traffic"] == "chat-sat"
    out = [x for x in bench["end_to_end"] if x["name"] == "output_tok_s"][0]
    assert CELL in out["workloads"]


def test_the_new_readers_are_the_cells_entries():
    check_entries(load_bench(ROOT), ROOT)


def test_the_cell_loads_with_its_own_metrics():
    cell = run.load_cell(CELL)
    assert [x["name"] for x in cell["per_layer"]] == NEW_READERS
    assert [x["name"] for x in cell["end_to_end"]] == ["output_tok_s",
                                                        "setup_s"]
    assert cell["mix"]["driver"] == "serve_falconh1"


def test_the_trace_opens_as_every_cells():
    """The cell never holds all 64 slots live (the prefill lane admits one
    request a step), so its trace waits for nothing: `tracing.Profile`,
    TRACE_START_S into the window."""
    import inspect

    assert not hasattr(drv, "Profile")
    assert "tracing.Profile(trace)" in inspect.getsource(drv.serve)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_falcon_h1", "arith_falcon_h1"):
        with open(os.path.join(ROOT, "benchmark", name + ".py")) as f:
            text = f.read()
        assert "import paddle_tpu" not in text \
            and "from paddle_tpu" not in text
    assert importlib.util.find_spec("benchmark.reference_falcon_h1") \
        is not None
