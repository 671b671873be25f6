"""The `mellum2-reason-long` cell's own parts: its readers on made-up runs,
`arith_mellum`'s bytes against a hand count, its configuration against the
source, and the comparison that decides `correct` — made on requests the
window itself finished: the sound tiny run passes, every planted fault and
the lower-precision control fail.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest
from test_benchmark_cells import _tiny
from test_benchmark_contract import load_bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import arith_mellum as arith  # noqa: E402
from benchmark import reference_mellum as reference  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark.drivers import serve_moe as drv  # noqa: E402

CELL = "mellum2-reason-long"
CONFIG = os.path.join(ROOT, "benchmark", "configs", "mellum2-12b-a2.5b")


def reader(name):
    return run.load_module("readers", name).read


@pytest.fixture(scope="module")
def published():
    with open(CONFIG + ".json") as f:
        return json.load(f)


# ---- the configuration --------------------------------------------------------

def test_the_configuration_is_the_catalog_row_cut_in_depth_alone(published):
    with open(CONFIG + ".source.json") as f:
        src = json.load(f)
    assert published["reduced"] == ["num_hidden_layers"]
    assert published["published"] == {"num_hidden_layers": 28}
    assert src["num_hidden_layers"] == 28
    assert published["num_hidden_layers"] in (12, 8)     # whole periods
    for k, v in src.items():
        if k != "num_hidden_layers":
            assert published[k] == v, k
    for k in ("assumed", "deployment", "notes", "source"):
        assert published[k]
    dep = published["deployment"]
    assert dep["chips"] == 1 and dep["slots"] == 32
    ring = -(-(published["sliding_window"] + dep["token_budget"]) // 64) + 1
    assert dep["ring_tokens"] == ring * 64 == 1600
    kinds = published["layer_types"][:published["num_hidden_layers"]]
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) \
        * (len(kinds) // 4)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows if r["source_url"] == published["source"]]
        assert row and row[0]["config"] == src


def test_the_mix_fits_the_deployment_and_is_the_issues(published):
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "reason-long.json")) as f:
        mix = json.load(f)
    assert mix["driver"] == "serve_moe"
    assert mix["arrivals"] == {"kind": "closed", "clients": 48}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "min": 64, "max": 4096}
    assert mix["output_len"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.5, "min": 512, "max": 3072}
    dep = published["deployment"]
    assert mix["prompt_len"]["max"] <= dep["max_prompt_len"]
    assert mix["output_len"]["max"] <= dep["max_new_tokens"]
    from benchmark.traffic import lengths_block

    prompts = lengths_block(mix["prompt_len"])
    # about one prompt in five passes the window inside prefill
    assert 4 <= (prompts > published["sliding_window"]).sum() <= 8
    assert prompts.max() == 2868
    # an output alone outgrows the window in four requests of five
    outs = lengths_block(mix["output_len"])
    assert (outs > published["sliding_window"]).mean() > 0.75


# ---- arithmetic ----------------------------------------------------------------

def test_bytes_against_a_hand_count(published):
    m = published
    attn = 2304 * 4096 * 2 + 2 * 2304 * 512 + 2304 * 64 + 2 * 2304
    expert = 3 * 2304 * 896
    assert arith.attention_params(m) == attn == 21_385_728
    assert arith.expert_params(m) == expert == 6_193_152
    assert arith.layer_params(m) == attn + 64 * expert == 417_747_456
    depth = m["num_hidden_layers"]
    assert arith.serve_weight_bytes(m) == 2 * (
        depth * 417_747_456 + 2304 + 2 * 98304 * 2304)
    assert arith.layer_counts(m) == (depth // 4, 3 * depth // 4)
    assert arith.kv_bytes_per_token_layer(m) == 2 * 4 * 128 * 2 == 2048
    n_full, n_win = arith.layer_counts(m)
    got = arith.decode_step_bytes(m, 60.0, 50_000, 30_000)
    want = 2 * (depth * (attn + 60 * expert) + 2304 * 98304 + 2304) \
        + 2048 * (n_full * 50_000 + n_win * 30_000)
    assert got == want
    # every expert hit and nothing cached: the weights but the embedding
    assert arith.decode_step_bytes(m, 64, 0, 0) \
        == arith.serve_weight_bytes(m) - 2 * 98304 * 2304
    assert arith.expert_mm_bytes(m, 10, 100) \
        == 2 * (10 * expert + 100 * 3 * (2304 + 896))
    assert arith.window_attn_bytes(m, 1000) == 2_048_000
    assert arith.window_kv_share(m, 1000, 1000) == 1.0
    assert arith.window_kv_share(m, 1200, 825) == pytest.approx(
        (1200 * n_full + 825 * n_win) / (1200 * depth))


# ---- readers on a made-up run ---------------------------------------------------

def _ctx(m, **over):
    """A run of 10 traced scheduling steps, 2 of them mixed."""
    disp = [{"name": "decode.dispatch", "ph": "X", "ts": 1e6 * (1 + i),
             "dur": 10.0, "args": {"live": 30 + i % 3,
                                   "prefill_window": i % 10 < 3}}
            for i in range(20)]
    depth = m["num_hidden_layers"]
    dec = depth * 8 * 100            # 100 scheduling steps of 8 decode steps
    ctx = {
        "config": m, "spans": disp, "t0": 0.0, "t1": 100.0,
        "steps_per_sync": 8, "slots": 32,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "moe": {"moe_layer_steps": dec + depth * 30,
                "moe_rows_routed": dec * 256 + depth * 30 * 4096,
                "moe_experts_hit": dec * 62 + depth * 30 * 64,
                "moe_load_max": dec * 12 + depth * 30 * 100,
                "moe_layer_steps_decode": dec,
                "moe_rows_routed_decode": dec * 256,
                "moe_experts_hit_decode": dec * 62,
                "window_tokens_dropped": 1000},
        "gauges": {"kv_pages_full": 1200.0, "kv_pages_window": 825.0,
                   "kv_tokens_live": 60_000.0, "kv_tokens_window": 31_000.0},
        # the steps the trace holds: contexts shorter than the window's mean
        "gauges_traced": {"kv_pages_full": 1100.0, "kv_pages_window": 825.0,
                          "kv_tokens_live": 50_000.0,
                          "kv_tokens_window": 30_000.0},
        "trace": {"busy_s": 2.0, "window_s": 2.1,
                  "host_spans": {"step": 10},
                  "programs": {"jit_serve_decode_chunk": [0.19, 0.2, 0.21],
                               "jit_serve_unified_step": [0.25, 0.26]},
                  "device_op_s": {"grouped_matmul bf16[8448,896]": 0.7,
                                  "grouped_matmul bf16[8448,2304]": 0.5,
                                  "moe_rows_in bf16[12288,2304]": 0.02,
                                  "moe_rows_out bf16[512,2304]": 0.03,
                                  "decode_attention_window bf16[32,32,128]":
                                  0.06,
                                  "decode_attention bf16[32,32,128]": 0.04,
                                  "fusion bf16[32,2304]": 0.4}}}
    ctx.update(over)
    return ctx


def test_every_reader_on_a_made_up_run(published):
    m, ctx = published, _ctx(published)
    depth = m["num_hidden_layers"]
    n_win = 3 * depth // 4
    step = arith.decode_step_bytes(m, 62, 50_000, 30_000)
    assert reader("kernel.decode_read_share.mellum2")(ctx) == pytest.approx(
        100 * 10 * 8 * step / 819e9 / 2.0)
    mm = 10 * 8 * depth * arith.expert_mm_bytes(m, 62, 256) \
        + 2 * depth * arith.expert_mm_bytes(m, 64, 4096)
    assert reader("kernel.expert_mm_read_share")(ctx) == pytest.approx(
        100 * mm / 819e9 / 1.2)
    assert reader("kernel.window_attn_read_share")(ctx) == pytest.approx(
        100 * 10 * 8 * n_win * 2048 * 30_000 / 819e9 / 0.06)
    assert reader("moe.expert_dev_share.mellum2")(ctx) == pytest.approx(
        100 * 1.25 / 2.0)
    c = ctx["moe"]
    assert reader("moe.experts_hit_share")(ctx) == pytest.approx(
        100 * c["moe_experts_hit"] / (64 * c["moe_layer_steps"]))
    assert reader("moe.load_max_over_mean.mellum2")(ctx) == pytest.approx(
        c["moe_load_max"] * 64 / c["moe_rows_routed"])
    assert reader("cache.window_kv_share")(ctx) == pytest.approx(
        arith.window_kv_share(m, 1200, 825))
    # the accepted readers: by the cell's name at the end of their lists,
    # or through an alias where an accepted test pins the list
    assert reader("step.decode_dev_ms.mellum2")(ctx) == pytest.approx(200.0)
    assert reader("step.mixed_dev_ms.mellum2")(ctx) == pytest.approx(255.0)
    assert reader("sched.live_slots_mean")(ctx) == pytest.approx(
        np.mean([30 + i % 3 for i in range(20)]))
    for stem in ALIASES:
        assert reader(stem + ".mellum2")(ctx) == reader(stem + ".sat")(ctx)
    # no share of a bound passes it on this run
    for name in ("kernel.decode_read_share.mellum2",
                 "kernel.expert_mm_read_share",
                 "kernel.window_attn_read_share"):
        assert 0 < reader(name)(ctx) < 100


NEW_READERS = [
    "kernel.decode_read_share.mellum2", "kernel.expert_mm_read_share",
    "kernel.window_attn_read_share", "moe.expert_dev_share.mellum2",
    "moe.experts_hit_share", "moe.load_max_over_mean.mellum2",
    "cache.window_kv_share"]
# accepted readers under the cell's own entries (`_mellum.same_as`)
ALIASES = ["sched.host_ms", "sched.commit_ms", "sched.admit_ms",
           "step.decode_dev_ms", "step.mixed_dev_ms"]
NEW_READERS += [stem + ".mellum2" for stem in ALIASES]
# accepted entries whose `workloads` the cell's name was appended to
SHARED_READERS = ["sched.live_slots_mean", "step.chunk_ms.sat"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_on_a_program_that_records_nothing(
        published, name):
    """The parent's run: no counters, no gauges, no spans, none of the
    labels or programs — every reader returns None and does not raise."""
    ctx = _ctx(published, moe={}, gauges=None, gauges_traced=None, spans=[],
               trace={"busy_s": 2.0, "window_s": 2.1, "host_spans": {},
                      "programs": {}, "device_op_s": {
                          "fusion bf16[32,2304]": 0.4}})
    assert reader(name)(ctx) is None
    del ctx["moe"], ctx["gauges"], ctx["gauges_traced"]
    assert reader(name)(ctx) is None


# the serving cells `output_tok_s` lists, in the order they were accepted
SERVED = ["mistral7b-reason-sat", CELL]


def check_entries(bench, root):
    """The cell's entries of `bench` and their readers under `root`. The
    end-to-end list is held by membership and order: a later serving cell
    is appended to it."""
    mine = [x for x in bench["per_layer"] if x.get("workloads") == [CELL]]
    assert [x["name"] for x in mine] == NEW_READERS
    assert all(x["moves"] == "output_tok_s" for x in mine)
    # what the cell shares with `mistral7b-reason-sat` it reads through the
    # accepted readers, its name appended to their lists
    shared = [x["name"] for x in bench["per_layer"]
              if x.get("workloads") == ["mistral7b-reason-sat", CELL]]
    assert shared == SHARED_READERS
    by = {x["name"]: x for x in bench["per_layer"]}
    for stem in ALIASES:
        sat, own = by[stem + ".sat"], by[stem + ".mellum2"]
        assert {**own, "name": 0, "workloads": 0} \
            == {**sat, "name": 0, "workloads": 0}
    readers = os.path.join(root, "benchmark", "readers")
    for name in NEW_READERS + SHARED_READERS:
        assert os.path.isfile(os.path.join(readers, name + ".py"))
    assert not [n for n in os.listdir(readers) if n.endswith("mellum2.py")
                and n[:-3] not in NEW_READERS]
    out = [x for x in bench["end_to_end"] if x["name"] == "output_tok_s"][0]
    assert [c for c in out["workloads"] if c in SERVED] == SERVED


def test_the_new_readers_are_the_cells_entries():
    check_entries(load_bench(ROOT), ROOT)


# ---- the comparison that decides `correct` --------------------------------------

@pytest.fixture(scope="module")
def served():
    """One window of the tiny cell through a tiny engine, and the requests
    of it that decide `correct`."""
    cell, kw = _tiny(CELL)
    m, seed, said = cell["config"], 5, []
    raw, profile, bad, p, dtype, pad_to = drv.serve(
        cell, seed, 1.5, False, said.append, kw["engine_kw"])
    samples = drv.sample_requests(raw["measured"], seed)
    return dict(m=m, p=p, dtype=dtype, bad=bad, raw=raw, samples=samples,
                pad_to=pad_to, said=said, seed=seed)


def _reading(served, samples=None, faults=()):
    return drv.readings(*drv.position_readings(
        served["m"], served["p"], samples or served["samples"],
        served["pad_to"], faults))


def test_the_sound_run_passes_at_every_position(served):
    assert served["dtype"] == "float32" and served["bad"] == []
    m, samples, raw = served["m"], served["samples"], served["raw"]
    limits, said = drv.LIMITS["float32"], []
    assert drv.check(m, served["p"], samples, served["pad_to"], limits,
                     said.append) == []
    positions = sum(len(t) for _, t, _ in samples)
    assert len(said) == 1 and f"{positions} positions: tie" in said[0]
    for k in limits:
        assert f"{k} " in said[0]
    got = _reading(served)
    assert got["positions"] == positions and not drv.over(got, limits)
    # the samples are requests THE WINDOW finished, whole, the one with the
    # longest prompt first, and every token came with its log-probability
    whole = {tuple(r.prompt): r for r, n in raw["measured"]
             if len(r.tokens) == n}
    assert 1 <= len(samples) <= drv.SAMPLE_REQUESTS
    assert len(samples[0][0]) == max(len(q) for q in whole)
    for prompt, tokens, logprobs in samples:
        r = whole[tuple(prompt)]
        assert r.finish_time > raw["t0"] and tokens == r.tokens
        assert len(logprobs) == len(tokens) and max(logprobs) < 0
    # some of them outgrew the window and wrapped the ring while they decoded
    w, ring = m["sliding_window"], m["deployment"]["ring_tokens"]
    assert any(len(q) < w < len(q) + len(t) for q, t, _ in samples)
    assert any(len(q) + len(t) > ring for q, t, _ in samples)
    assert drv.sample_requests(raw["measured"], served["seed"]) == samples
    assert drv.sample_requests([], 1) == []


def test_a_window_that_finished_nothing_fails_the_run(served):
    bad = drv.check(served["m"], served["p"], [], served["pad_to"],
                    drv.LIMITS["float32"], print)
    assert bad and "finished no request" in bad[0]


def test_contexts_are_padded_to_one_length_a_cell(published):
    assert drv.padded(108) == 128 and drv.padded(512) == 512
    assert drv.padded(513) == 1024
    # the cell's: the longest prompt and the longest output of its mix
    assert drv.padded(2868 + 3072) == 6144
    assert reference.QUERY_BLOCK == 512


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_fails_the_run(served, fault):
    reading = _reading(served, faults=(fault,))
    assert drv.over(reading, drv.LIMITS["float32"]), (fault, reading)
    assert reading["logprob"] > 10 * drv.LIMITS["float32"]["logprob"]


def test_the_lower_precision_control_fails_the_run(served):
    with reference.lower_precision(drv.BELOW[served["dtype"]]):
        reading = _reading(served)
    assert drv.over(reading, drv.LIMITS["float32"]), reading
    assert reading["logprob"] > 10 * drv.LIMITS["float32"]["logprob"]
    assert drv.BELOW == {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def test_the_faults_script_reads_every_reference(served):
    from benchmark import serve_moe_faults

    at = serve_moe_faults.fault_readings(
        drv, served["m"], served["p"], served["samples"][:1],
        served["pad_to"], served["dtype"])
    assert list(at) == ["sound", "control", *reference.FAULTS]
    limits = drv.LIMITS["float32"]
    passes = [k for k, pair in at.items()
              if not drv.over(drv.readings(*pair), limits)]
    assert passes == ["sound"]


def test_a_wrong_token_or_logprob_from_the_engine_fails_the_run(served):
    """The check reads the engine's tokens and their log-probabilities: a
    token swapped shows as its gap, a log-probability moved as its distance."""
    limits = drv.LIMITS["float32"]
    prompt, tokens, logprobs = served["samples"][0]
    swapped = list(tokens)
    swapped[7] = (swapped[7] + 1) % served["m"]["vocab_size"]
    got = _reading(served, [(prompt, swapped, logprobs)])
    # (the tokens after it were chosen under another context: they flip too)
    assert "tie" in drv.over(got, limits) and got["flips"] >= 1 / len(tokens)
    moved = [lp + 0.05 for lp in logprobs]
    got = _reading(served, [(prompt, tokens, moved)])
    assert drv.over(got, limits) == ["logprob"]
    assert drv.readings(np.array([0.0, np.nan]), np.zeros(2))["tie"] \
        == float("inf")


def test_the_trace_opens_at_the_windows_first_retirement(monkeypatch):
    class Eng:
        finished = []

    started = []
    monkeypatch.setattr(drv.tracing.Profile, "tick",
                        lambda self, t: started.append(t))
    prof = drv.Profile(True, Eng, seconds=50.0)
    for t in (0.5, 3.0, 9.0):
        prof.tick(t)                 # nothing retired yet: not opened
    assert started == [] and prof.wait_cap_s == drv.TRACE_WAIT_CAP_S
    Eng.finished = [object()]
    prof.tick(9.5)
    assert started == [9.5]
    # nothing retires at all: opened at the cap, half of a short window
    Eng.finished, started[:] = [], []
    prof = drv.Profile(True, Eng, seconds=1.5)
    prof.tick(0.5)
    prof.tick(0.8)
    assert started == [0.8] and prof.wait_cap_s == 0.75


def test_the_traced_steps_gauges_are_kept_apart(served):
    raw = served["raw"]
    assert set(raw["gauges"]) == set(drv.GAUGES)
    assert raw["gauges_traced"] is None      # this run was not traced
    assert raw["gauges"]["kv_pages_window"] > 0


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_mellum", "arith_mellum"):
        with open(os.path.join(ROOT, "benchmark", name + ".py")) as f:
            text = f.read()
        assert "import paddle_tpu" not in text \
            and "from paddle_tpu" not in text
    spec = importlib.util.find_spec("benchmark.reference_mellum")
    assert spec is not None
