"""The `hy4-longctx-reason` cell's own parts: its configuration against the
source, its mix and the fixed order of its lengths, `arith_hy_v4`'s counts
against a hand count, its readers on made-up runs, and its entries. The
comparison on a tiny window of the cell — the sound run passes, every planted
fault and the lower-precision control fail — is
`tests/test_serve_hy4_correct.py`, which the test run reaches after the other
serving cells' own window rehearsals; the cell's tiny runs through run.py are
`test_benchmark_cells.py`'s.
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

from benchmark import arith_hy_v4 as arith  # noqa: E402
from benchmark import run, traffic, traffic_golden  # noqa: E402
from benchmark.drivers import serve_hy4 as drv  # noqa: E402

CELL = "hy4-longctx-reason"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "hy4-preview")
CUT = {"num_hidden_layers": (5, 78), "n_routed_experts": (16, 256),
       "vocab_size": (15104, 120832), "num_nextn_predict_layers": (0, 1)}


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
    dep = published["deployment"]
    assert dep["chips"] == 1 and dep["slots"] == 24
    assert dep["held"] == list(range(16)) and dep["group_chips"] == 16
    assert dep["token_budget"] % dep["block_size"] == 0
    n = published["num_hidden_layers"]
    assert published["indexer_types"][:n] == ["full", "full", "shared",
                                              "shared", "shared"]
    assert published["mlp_layer_types"][:n] == ["dense"] + ["sparse"] * 4
    assert published["vocab_size"] * 8 == src["vocab_size"]
    # MODEL_CATALOG names a JSONL catalog of published model configurations.
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.isfile(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["source_url"] == published["source"]]
        assert row and row[0]["config"] == src


def test_the_model_refuses_what_it_does_not_build(published):
    from paddle_tpu.models import HyV4Config

    cfg = drv.model_config(published, "bfloat16")
    assert cfg.n_routed_experts == 256 and len(cfg.held) == 16
    assert cfg.full_layers == (0, 1)
    for key, value in (("use_dsa", False), ("enable_ihc", False),
                       ("gating_type", "headwise"), ("n_group", 8),
                       ("num_nextn_predict_layers", 1),
                       ("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("some_new_key", 1)):
        with pytest.raises(ValueError, match=key):
            HyV4Config.from_dict({**published, key: value})
    with pytest.raises(ValueError, match="indexer_types"):
        HyV4Config.from_dict({**published,
                              "indexer_types": ["shared"] * 78})


def test_the_mix_fits_the_deployment_and_is_the_cells(published):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "reason-longctx.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "serve_hy4"
    assert mix["arrivals"] == {"kind": "closed", "clients": 36}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.3, "min": 6144, "max": 24576}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.4, "min": 768, "max": 3072}
    assert mix["shared_prefix_tokens"] == 0 and mix["order"] == "golden"
    dep = published["deployment"]
    assert mix["prompt_len"]["max"] <= dep["max_prompt_len"]
    assert mix["output_len"]["max"] <= dep["max_new_tokens"]
    from benchmark.traffic import lengths_block

    # every context passes the selection's width by 3-12 x
    prompts = lengths_block(mix["prompt_len"])
    assert prompts.min() > 3 * published["index_topk"]
    assert prompts.max() + mix["output_len"]["max"] \
        < 13 * published["index_topk"]


# ---- arithmetic ----------------------------------------------------------------

def test_counts_against_a_hand_count(published):
    m = published
    full = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 \
        + 2 * 6144 * 64 * 256 + 2048 + 512 + 64
    index = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 + 2 * 128
    assert arith.attention_params(m, False) == full == 265_685_568
    assert arith.attention_params(m, True) == full + index
    assert index == 9_371_904
    assert arith.hc_params(m) == 2 * (4 * 6144 * 24 + 3 + 24) == 1_179_702
    expert = 3 * 6144 * 2048
    assert arith.mlp_params(m, "dense") == 3 * 6144 * 18432
    assert arith.mlp_params(m, "sparse") \
        == 6145 * 256 + 16 * expert + expert
    assert arith.latent_row_bytes(m) == 1152 and arith.index_key_bytes(m) == 256
    assert arith.full_layers(m) == 2 and arith.depth(m) == 5
    w = arith.decode_weight_bytes(m)
    # the served parameters less the embedding, the head counted in f32
    from paddle_tpu.models.hy_v4 import serving_param_shapes

    cfg = drv.model_config(m, "bfloat16")
    n = sum(int(np.prod(s)) for s in serving_param_shapes(cfg).values())
    emb = 15104 * 6144
    assert n == 4_451_680_846
    assert w == 2 * (n - 2 * emb) + 4 * emb
    assert arith.sparse_decode_bytes(m, 24, 1000) \
        == 1000 * 1152 + 24 * 5 * 64 * 2 * (512 + 64 + 512)
    assert arith.decode_step_bytes(m, 24, 10, 1000) \
        == w + 10 * 256 + arith.sparse_decode_bytes(m, 24, 1000)
    assert arith.sparse_attn_flops(m, 1) == 2 * 64 * (1024 + 64)
    assert arith.index_flops(m, 1) == 2 * 32 * 128


# ---- readers on a made-up run ---------------------------------------------------

def _ctx(m, **over):
    """A window of 100 scheduling steps (8 decode steps each, 24 rows) and
    40 prefill windows; a trace of 10 steps, 3 of them mixed."""
    dec = 100 * 8
    ctx = {
        "config": m, "spans": [], "t0": 0.0, "t1": 50.0,
        "steps_per_sync": 8, "slots": 24,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "moe": {"dsa_full_layer_steps": 2 * dec + 2 * 40,
                "dsa_keys_scored": 2 * dec * 300_000 + 2 * 40 * 20_000_000,
                "dsa_keys_attended": 5 * dec * 49_000 + 5 * 40 * 4_000_000,
                "dsa_full_layer_steps_decode": 2 * dec,
                "dsa_keys_scored_decode": 2 * dec * 300_000,
                "dsa_keys_attended_decode": 5 * dec * 49_000,
                # 4 routed layers; decode rows 24 x 8 choices, 1/16 held
                "moe_layer_steps": 4 * dec + 4 * 40,
                "moe_rows_routed": 4 * dec * 12 + 4 * 40 * 1024,
                "moe_experts_hit": 4 * dec * 9 + 4 * 40 * 16,
                "moe_load_max": 4 * dec * 3 + 4 * 40 * 90,
                "moe_layer_steps_decode": 4 * dec,
                "moe_rows_routed_decode": 4 * dec * 12,
                "moe_experts_hit_decode": 4 * dec * 9},
        "trace": {"busy_s": 2.0, "window_s": 2.1,
                  "host_spans": {"step": 10},
                  "programs": {"jit_serve_decode_chunk": [0.19, 0.2, 0.21],
                               "jit_serve_unified_step": [0.5, 0.6, 0.7]},
                  "device_op_s": {
                      "dsa_index_scores f32[24,1,27648]": 0.1,
                      "sparse_mla_decode bf16[24,64,512]": 0.2,
                      "sparse_mla_window bf16[256,512,512]": 0.3,
                      "top-k f32[24,27648]": 0.05,
                      "grouped_matmul bf16[704,2048]": 0.5,
                      "moe_rows_in bf16[704,6144]": 0.02,
                      "fusion bf16[24,6144]": 1.0}}}
    ctx.update(over)
    return ctx


def test_every_reader_on_a_made_up_run(published):
    m, ctx = published, _ctx(published)
    steps, windows = 10 * 8, 3
    dec_bytes = arith.sparse_decode_bytes(m, 24, 5 * 49_000)
    win_flops = arith.sparse_attn_flops(m, 5 * 4_000_000)
    assert reader("kernel.sparse_attn_read_share.hy4")(ctx) == pytest.approx(
        100 * (steps * dec_bytes / 819e9 + windows * win_flops / 197e12)
        / 0.5)
    idx = max(2 * 300_000 * 256 / 819e9,
              arith.index_flops(m, 2 * 300_000) / 197e12)
    assert reader("kernel.indexer_read_share.hy4")(ctx) == pytest.approx(
        100 * (steps * idx + windows * arith.index_flops(
            m, 2 * 20_000_000) / 197e12) / 0.1)
    assert reader("attn.dsa_dev_share.hy4")(ctx) == pytest.approx(
        100 * 0.65 / 2.0)
    step = arith.decode_step_bytes(m, 24, 2 * 300_000, 5 * 49_000)
    assert reader("kernel.decode_read_share.hy4")(ctx) == pytest.approx(
        100 * steps * step / 819e9 / 2.0)
    assert reader("step.decode_dev_ms.hy4")(ctx) == pytest.approx(200.0)
    assert reader("step.mixed_dev_ms.hy4")(ctx) == pytest.approx(600.0)
    expert = 3 * 6144 * 2048
    want = steps * 4 * 2 * (9 * expert + 12 * 3 * (6144 + 2048)) \
        + windows * 4 * 2 * (16 * expert + 1024 * 3 * (6144 + 2048))
    assert reader("kernel.expert_mm_read_share.hy4")(ctx) == pytest.approx(
        100 * want / 819e9 / 0.5)
    c = ctx["moe"]
    assert reader("moe.experts_hit_share.hy4")(ctx) == pytest.approx(
        100 * c["moe_experts_hit"] / (16 * c["moe_layer_steps"]))
    assert reader("moe.load_max_over_mean.hy4")(ctx) == pytest.approx(
        c["moe_load_max"] * 16 / c["moe_rows_routed"])
    assert reader("moe.expert_dev_share.hy4")(ctx) == pytest.approx(
        100 * 0.52 / 2.0)
    for name in ("kernel.sparse_attn_read_share.hy4",
                 "kernel.indexer_read_share.hy4",
                 "kernel.decode_read_share.hy4",
                 "kernel.expert_mm_read_share.hy4",
                 "moe.experts_hit_share.hy4"):
        assert 0 < reader(name)(ctx) < 100


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
                        "args": {"chunk": k, "live": 20 + k}})
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
    assert {k[:-len(".hy4")] for k in said} == {
        "step.decode_dev_ms", "step.mixed_dev_ms", "moe.expert_dev_share",
        "sched.gap_ms", "sched.stage_ms", "sched.enqueue_ms",
        "sched.readback_ms", "sched.live_slots_mean"}
    for name, namesake in said.items():
        got = reader(name)(ctx)
        assert got is not None and got == reader(namesake)(ctx), name


# the cell's per-layer entries, in their order in BENCHMARK.json
NEW_READERS = [
    "kernel.sparse_attn_read_share.hy4", "kernel.indexer_read_share.hy4",
    "attn.dsa_dev_share.hy4", "kernel.decode_read_share.hy4",
    "step.decode_dev_ms.hy4", "step.mixed_dev_ms.hy4",
    "kernel.expert_mm_read_share.hy4", "moe.experts_hit_share.hy4",
    "moe.expert_dev_share.hy4", "moe.load_max_over_mean.hy4",
    "sched.gap_ms.hy4", "sched.stage_ms.hy4", "sched.enqueue_ms.hy4",
    "sched.readback_ms.hy4", "sched.live_slots_mean.hy4"]


@pytest.mark.parametrize("name", NEW_READERS[:4] + NEW_READERS[6:10])
def test_a_reader_finds_nothing_on_a_program_that_records_nothing(
        published, name):
    """The parent's run: no `dsa_*` counters, none of the labels — every
    reader returns None and does not raise."""
    ctx = _ctx(published, moe={"moe_layer_steps": 0},
               trace={"busy_s": 2.0, "window_s": 2.1, "host_spans": {},
                      "programs": {}, "device_op_s": {
                          "fusion bf16[24,6144]": 0.4}})
    assert reader(name)(ctx) is None
    del ctx["moe"]
    assert reader(name)(ctx) is None


# the serving cells `output_tok_s` lists, in the order they were accepted
SERVED = ["mistral7b-reason-sat", "mellum2-reason-long", CELL]


def check_entries(bench, root):
    """The cell's entries of `bench` and their readers under `root`: its own
    per-layer entries exactly, the end-to-end list by membership and order
    (a later serving cell is appended to it). A benchmark without the cell
    names it nowhere."""
    if CELL not in {w["name"] for w in bench["workloads"]}:
        assert not [x for x in bench["per_layer"] + bench["end_to_end"]
                    if CELL in x.get("workloads", [])]
        assert "hy4-preview" not in {c["name"] for c in bench["configs"]}
        return
    mine = [x for x in bench["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in mine] == NEW_READERS
    assert all(x["moves"] == "output_tok_s" for x in mine)
    by = {x["name"]: x for x in bench["per_layer"]}
    for stem in ("step.decode_dev_ms", "step.mixed_dev_ms"):
        sat, own = by[stem + ".sat"], by[stem + ".hy4"]
        assert {**own, "name": 0, "workloads": 0} \
            == {**sat, "name": 0, "workloads": 0}
    readers = os.path.join(root, "benchmark", "readers")
    for name in NEW_READERS:
        assert os.path.isfile(os.path.join(readers, name + ".py"))
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "hy4-preview" and cells[CELL]["chips"] == 1
    out = [x for x in bench["end_to_end"] if x["name"] == "output_tok_s"][0]
    assert [c for c in out["workloads"] if c in SERVED] == SERVED


def test_the_new_readers_are_the_cells_entries():
    check_entries(load_bench(ROOT), ROOT)


def test_the_cell_loads_with_its_own_metrics():
    cell = run.load_cell(CELL)
    assert [x["name"] for x in cell["per_layer"]] == NEW_READERS
    assert [x["name"] for x in cell["end_to_end"]] == ["output_tok_s",
                                                        "setup_s"]
    assert cell["mix"]["driver"] == "serve_hy4"


# ---- the order of the lengths ----------------------------------------------------

def _mix():
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "reason-longctx.json")) as f:
        return json.load(f)


def test_the_seed_draws_the_ids_and_never_the_lengths():
    mix, big = _mix(), 2**31 + 12345
    a, b, c = (traffic_golden.make(mix, 15104, s) for s in (big, big, 7))
    assert isinstance(a, traffic_golden.GoldenTraffic)
    got = [[a.request(i), b.request(i), c.request(i)] for i in range(40)]
    assert all(x == y for x, y, _ in got)
    assert all(len(x[0]) == len(z[0]) and x[1] == z[1] and x[0] != z[0]
               for x, _, z in got)
    for p, n in (x for x, _, _ in got):
        assert 6144 <= len(p) <= 24576 and 768 <= n <= 3072
        assert min(p) >= 1 and max(p) < 15104


def test_every_stretch_of_requests_carries_the_mix():
    """Any 24 consecutive requests (a window finishes ~15 and holds 24)
    have mean lengths within 6% of the distribution's; the seeded shuffle of
    `traffic.Traffic` strays further, and over 320 requests each quantile is
    taken 10 +- 1 times."""
    mix = _mix()
    reqs = [traffic_golden.make(mix, 15104, 3).request(i) for i in range(512)]
    shuffled = [traffic.Traffic(mix, 15104, 3).request(i) for i in range(512)]
    for k, spec in ((0, "prompt_len"), (1, "output_len")):
        mean = traffic.lengths_block(mix[spec]).mean()

        def worst(rs):
            x = np.asarray([len(r[0]) if k == 0 else r[1] for r in rs], float)
            runs = np.convolve(x, np.ones(24) / 24, mode="valid")
            return np.abs(runs / mean - 1).max()

        assert worst(reqs) < 0.06 < worst(shuffled)
    for step in (traffic_golden.PHI, traffic_golden.SILVER):
        counts = np.bincount([traffic_golden.quantile(i, step)
                              for i in range(320)], minlength=traffic.BLOCK)
        assert counts.min() >= 9 and counts.max() <= 11


def test_a_mix_without_an_order_is_the_seeded_generator():
    mix = {k: v for k, v in _mix().items() if k != "order"}
    t = traffic_golden.make(mix, 100, 5)
    assert type(t) is traffic.Traffic
    assert [t.request(i) for i in range(8)] \
        == [traffic.Traffic(mix, 100, 5).request(i) for i in range(8)]
    with pytest.raises(ValueError, match="order"):
        traffic_golden.make(dict(mix, order="sorted"), 100, 5)
    with pytest.raises(ValueError, match="closed"):
        traffic_golden.make(dict(mix, order="golden", arrivals={
            "kind": "poisson", "rate_per_s": 1.0}), 100, 5)


def test_the_trace_opens_where_every_slot_is_live(monkeypatch):
    class Eng:
        slots, n_active = 24, 23

    started = []
    monkeypatch.setattr(drv.tracing.Profile, "tick",
                        lambda self, t: started.append(t))
    prof = drv.Profile(True, Eng, seconds=50.0)
    for t in (0.5, 3.0, 9.0):
        prof.tick(t)                 # a slot is free: a prompt may prefill
    assert started == [] and prof.wait_cap_s == 20.0
    Eng.n_active = 24
    prof.tick(9.5)
    assert started == [9.5]
    # the slots never all live: opened at the cap, half of a short window
    Eng.n_active, started[:] = 3, []
    prof = drv.Profile(True, Eng, seconds=1.5)
    prof.tick(0.5)
    prof.tick(0.8)
    assert started == [0.8] and prof.wait_cap_s == 0.75


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_hy_v4", "arith_hy_v4"):
        with open(os.path.join(ROOT, "benchmark", name + ".py")) as f:
            text = f.read()
        assert "import paddle_tpu" not in text \
            and "from paddle_tpu" not in text
    assert importlib.util.find_spec("benchmark.reference_hy_v4") is not None
