"""`models/falcon_h1.py` served through `ContinuousBatchingEngine` at CPU-test
sizes, against the plain reference (`benchmark/reference_falcon_h1.py`, the
mixer's recurrence token by token): the engine's prefill windows and decode
steps, the SSD kernels (interpret mode) against the recurrence, a prompt split
over windows, a reused slot, the fourth kind of state's accounting, what the
engine refuses, and the comparison that decides `falconh1-chat-sat`'s
`correct` (`benchmark/drivers/serve_falconh1.py`) with every planted fault and
the lower-precision control failing it.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from benchmark import reference_falcon_h1 as reference  # noqa: E402
from paddle_tpu.kernels import ssd  # noqa: E402
from paddle_tpu.models import FalconH1Config  # noqa: E402
from paddle_tpu.models import falcon_h1  # noqa: E402
from paddle_tpu.models.llama import PagedKVManager  # noqa: E402
from paddle_tpu.serving import ContinuousBatchingEngine  # noqa: E402

# prompts that take one window, two and three (windows of 16), and one that
# ends a token short of a window's edge
PROMPTS = ((5, 9), (37, 12), (20, 4), (15, 16))
BUDGET = 16


def _file_dict(cfg: FalconH1Config) -> dict:
    """`cfg` as a benchmark configuration file states it (the reference's
    input)."""
    d = {k: list(v) if isinstance(v, tuple) else v
         for k, v in dataclasses.asdict(cfg).items()}
    d["deployment"] = {"token_budget": BUDGET}
    return d


def _engine(cfg, p, slots=3, token_budget=BUDGET, **kw):
    return ContinuousBatchingEngine(
        cfg, p, slots=slots, max_prompt_len=48, max_new_tokens=16,
        block_size=8, prompt_bucket=8, token_budget=token_budget,
        steps_per_sync=4, logprobs=True, dtype=jnp.float32, **kw)


def _serve(cfg, p, prompts, **kw):
    eng = _engine(cfg, p, **kw)
    eng.warm()
    rng = np.random.default_rng(0)
    reqs = [eng.add_request(list(rng.integers(1, cfg.vocab_size, n)),
                            max_new=k) for n, k in prompts]
    eng.run()
    return eng, reqs


@pytest.fixture(scope="module")
def tiny():
    cfg = FalconH1Config.tiny()
    return cfg, falcon_h1.init_serving_params(cfg, 3)


@pytest.fixture(scope="module")
def served(tiny):
    cfg, p = tiny
    eng, reqs = _serve(cfg, p, PROMPTS)
    return eng, reqs


def _against_reference(cfg, p, req):
    """(gap, logprob error) at every generated position of `req`, in the
    reference's logits' deviations."""
    ids = list(req.prompt) + list(req.tokens[:-1])
    pad = -(-len(ids) // reference.QUERY_BLOCK) * reference.QUERY_BLOCK
    got = reference.token_scores(
        _file_dict(cfg), jax.device_get(p), ids + [0] * (pad - len(ids)),
        len(req.prompt) - 1, req.tokens)
    return got["gap"], np.abs(np.asarray(req.logprobs) - got["logprob"]) \
        / got["std"]


# ---- the model against the reference -------------------------------------------

def test_prefill_and_decode_match_the_reference(tiny, served):
    """The first token (the prefill window's logits) and every decoded one
    (the state carried token to token) against one reference pass: the same
    argmax, log-probabilities within f32 rounding."""
    cfg, p = tiny
    _, reqs = served
    for req in reqs:
        assert not req.failed and len(req.tokens) == req.max_new
        gap, err = _against_reference(cfg, p, req)
        assert gap.max() == 0.0, (len(req.prompt), gap)
        assert err.max() < 1e-5, (len(req.prompt), err.max())


def test_the_counters_count_the_mixers_work(tiny, served):
    cfg, _ = tiny
    eng, _ = served
    m, layers = eng.metrics(), cfg.num_hidden_layers
    windows = sum(-(-n // BUDGET) for n, _ in PROMPTS)
    carried = sum(-(-n // BUDGET) - 1 for n, _ in PROMPTS)
    assert m["ssm_scan_tokens"] == layers * sum(n for n, _ in PROMPTS)
    assert m["ssm_scan_segments"] == layers * windows
    assert m["ssm_scan_carried"] == layers * carried
    # every token after the first is a decode step of a live row, and a
    # chunk's frozen rows are stepped too: at least that many
    assert m["ssm_decode_slot_steps"] >= layers * sum(
        k - 1 for _, k in PROMPTS)
    assert m["ssm_scan_tokens_decode"] == 0
    assert m["state_rows"] == 3 + 2
    assert m["state_row_bytes"] == layers * 4 * (
        cfg.mamba_d_ssm * cfg.mamba_d_state
        + (cfg.mamba_d_conv - 1) * cfg.conv_dim)


def test_a_prompt_split_over_windows_is_the_prompt_in_one(tiny):
    """37 tokens in windows of 8 (five windows, the state carried four
    times) against windows of 40 (one): the same tokens, the same
    log-probabilities."""
    cfg, p = tiny
    _, (narrow,) = _serve(cfg, p, [(37, 12)], token_budget=8)
    _, (wide,) = _serve(cfg, p, [(37, 12)], token_budget=40)
    assert narrow.tokens == wide.tokens
    np.testing.assert_allclose(narrow.logprobs, wide.logprobs, atol=2e-5)


def test_a_reused_slot_gives_a_fresh_engines_logits(tiny):
    """One slot: the second request takes the row the first left its state
    in, and must start from zero all the same."""
    cfg, p = tiny
    rng = np.random.default_rng(5)
    first, second = (list(rng.integers(1, cfg.vocab_size, n))
                     for n in (30, 21))
    eng = _engine(cfg, p, slots=1)
    eng.warm()
    rows, alloc = [], eng.mgr.alloc_state
    eng.mgr.alloc_state = lambda: rows.append(alloc()) or rows[-1]
    eng.add_request(first, max_new=9)
    reused = eng.add_request(second, max_new=10)
    eng.run()
    assert rows == [1, 1]
    fresh = _engine(cfg, p, slots=1)
    fresh.warm()
    alone = fresh.add_request(second, max_new=10)
    fresh.run()
    assert reused.tokens == alone.tokens
    np.testing.assert_allclose(reused.logprobs, alone.logprobs, atol=2e-5)


# ---- the kernels against the recurrence --------------------------------------

def _scan_case(rng, lens, starts_fresh, chunk):
    r, h, n, p, g, t = 6, 4, 16, 8, 2, 4 * chunk
    s = len(lens)
    state = jnp.asarray(rng.normal(size=(r, h, n, p)), jnp.float32)
    valid = np.arange(t)[None] < np.asarray(lens)[:, None]
    dt = rng.uniform(0.01, 0.4, size=(s, h, t)) * valid[:, None]
    a_h = -rng.uniform(1, 8, size=(h,))
    x = rng.normal(size=(s, h, t, p))
    args = (jnp.asarray([4, 1, 2][:s], jnp.int32),
            jnp.asarray(starts_fresh, jnp.int32),
            jnp.asarray(dt[..., None] * x, jnp.float32),
            jnp.asarray(dt * a_h[None, :, None], jnp.float32),
            jnp.asarray(rng.normal(size=(s, g, t, n)), jnp.float32),
            jnp.asarray(rng.normal(size=(s, g, t, n)), jnp.float32))
    return state, args


def test_ssd_scan_is_the_recurrence_on_ragged_segments():
    """Three segments of 29, 32 and 7 tokens in windows of four chunks of 8:
    two carry their row's state, one starts from zero; two end short of a
    chunk's edge. The kernel's chunked form (interpret
    mode) against the token-by-token recurrence."""
    rng = np.random.default_rng(1)
    state, args = _scan_case(rng, (29, 32, 7), (0, 1, 0), 8)
    y, st = ssd.ssd_scan(state, *args, chunk=8)
    y_ref, st_ref = ssd.ssd_scan_reference(state, *args)
    lens = np.asarray((29, 32, 7))
    real = (np.arange(32)[None] < lens[:, None])[:, None, :, None]
    np.testing.assert_allclose(np.where(real, y, 0), np.where(real, y_ref, 0),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(st, st_ref, rtol=2e-5, atol=2e-5)
    # rows no segment names keep their state
    np.testing.assert_array_equal(np.asarray(st)[[0, 3, 5]],
                                  np.asarray(state)[[0, 3, 5]])


def test_ssd_scan_carries_the_state_across_chunk_edges():
    """A segment of 20 tokens run whole against the same tokens in two
    calls of 13 and 7 (the second from the first's state): the chunks of
    the second call start 13 tokens into the first's, not at an edge."""
    rng = np.random.default_rng(2)
    state, (rows, fresh, xdt, a, b, c) = _scan_case(rng, (20,), (1,), 8)
    y, whole = ssd.ssd_scan(state, rows, fresh, xdt, a, b, c, chunk=8)

    def part(lo, hi, st, first):
        keep = (np.arange(32) < hi - lo)
        sl = lambda z, ax: jnp.asarray(np.where(  # noqa: E731
            keep.reshape((-1,) + (1,) * (z.ndim - ax - 1)),
            np.roll(np.asarray(z), -lo, axis=ax), 0))
        return ssd.ssd_scan(st, rows, jnp.asarray([first], jnp.int32),
                            sl(xdt, 2), sl(a, 2), sl(b, 2), sl(c, 2),
                            chunk=8)

    y1, st1 = part(0, 13, state, 1)
    y2, st2 = part(13, 20, st1, 0)
    np.testing.assert_allclose(st2, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y2[0, :, :7], y[0, :, 13:20], rtol=2e-5,
                               atol=2e-5)


def test_ssd_decode_is_one_step_of_the_recurrence():
    rng = np.random.default_rng(3)
    r, h, n, p, g, b = 5, 4, 16, 8, 2, 3
    state = jnp.asarray(rng.normal(size=(r, h, n, p)), jnp.float32)
    rows = jnp.asarray([2, 0, 4], jnp.int32)
    args = (jnp.asarray(rng.normal(size=(b, h, p)), jnp.float32),
            jnp.asarray(rng.uniform(0.3, 1, size=(b, h)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32))
    y, st = ssd.ssd_decode(state, rows, *args)
    y_ref, st_ref = ssd.ssd_decode_reference(state, rows, *args)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st, st_ref, rtol=1e-5, atol=1e-5)
    # by hand, head 3 (group 1) of batch row 0
    s0 = np.asarray(state[2, 3]).T                      # [P, N]
    x, da, bb, cc = (np.asarray(a)[0] for a in args)
    s1 = da[3] * s0 + np.outer(x[3], bb[1])
    np.testing.assert_allclose(y[0, 3], s1 @ cc[1], rtol=1e-5, atol=1e-5)


def test_the_kernels_refuse_a_state_in_another_layout():
    state = jnp.zeros((3, 4, 16, 8), jnp.bfloat16)
    with pytest.raises(ValueError, match="ssd_decode"):
        ssd.ssd_decode(state, jnp.zeros((1,), jnp.int32),
                       jnp.zeros((1, 4, 8)), jnp.zeros((1, 4)),
                       jnp.zeros((1, 2, 16)), jnp.zeros((1, 2, 16)))
    with pytest.raises(ValueError, match="ssd_scan"):
        ssd.ssd_scan(state.astype(jnp.float32), jnp.zeros((1,), jnp.int32),
                     jnp.zeros((1,), jnp.int32), jnp.zeros((1, 4, 12, 8)),
                     jnp.zeros((1, 4, 12)), jnp.zeros((1, 2, 12, 16)),
                     jnp.zeros((1, 2, 12, 16)), chunk=8)


# ---- the fourth kind of state -------------------------------------------------

def test_the_state_rows_come_off_the_pool_budget(tiny):
    cfg, p = tiny
    row = falcon_h1.Mamba2Mixer(cfg).row_bytes() * cfg.num_hidden_layers
    page = PagedKVManager.page_bytes(8, n_layers=cfg.num_hidden_layers,
                                     num_kv_heads=cfg.num_key_value_heads,
                                     head_dim=cfg.head_dim)
    budget = 5 * row + 40 * page
    eng = ContinuousBatchingEngine(
        cfg, p, slots=3, max_prompt_len=48, max_new_tokens=16, block_size=8,
        prompt_bucket=8, token_budget=16, kv_pool_bytes=budget,
        dtype=jnp.float32)
    assert eng.mgr.max_pages == 40
    assert eng.mgr.kv_pool_bytes() == budget
    assert [s.shape for s in eng.states[0]] == [
        (5, cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_head),
        (5, (cfg.mamba_d_conv - 1) * cfg.conv_dim)]


def test_the_manager_hands_out_state_rows_whole():
    mgr = PagedKVManager(8, 4)
    mgr.set_state_rows(3, 100)
    assert mgr.n_states_free == 2
    a, b = mgr.alloc_state(), mgr.alloc_state()
    assert {a, b} == {1, 2}
    with pytest.raises(RuntimeError, match="state rows"):
        mgr.alloc_state()
    mgr.free_state(a)
    mgr.free_state(None)
    assert mgr.alloc_state() == a


@pytest.mark.parametrize("kw, name", [
    (dict(speculative="ngram", spec_k=2), "speculative='ngram'"),
    (dict(serving_mp=2), "serving_mp=2"),
    (dict(serving_cp=2), "serving_cp=2"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype='int8'"),
    (dict(unified_step=False), "split prefill programs"),
    (dict(disaggregated=True), "disaggregated=True"),
])
def test_the_engine_refuses_what_cannot_hold_a_state(tiny, kw, name):
    cfg, p = tiny
    with pytest.raises(ValueError, match="recurrent-state") as e:
        _engine(cfg, p, **kw)
    assert name in str(e.value)


def test_the_prefix_cache_is_off_with_its_reason(tiny, served):
    eng, _ = served
    assert eng.prefix_cache is False
    assert "recurrent state" in eng.metrics()["prefix_cache_off"]


def test_a_model_of_state_decodes_only_through_its_states(tiny):
    from paddle_tpu.models.llama import _make_decode_step

    cfg, p = tiny
    step = _make_decode_step(cfg, 1, max_seq=8)
    with pytest.raises(ValueError, match="keep state"):
        step(p, [None] * 2, [None] * 2, jnp.zeros((1, 1), jnp.int32),
             jnp.zeros((), jnp.int32))


def test_the_config_refuses_what_it_does_not_build():
    FalconH1Config.from_dict({"mamba_expand": 2, "num_logits_to_keep": 1})
    for key, value in (("mamba_norm_before_gate", True),
                       ("attn_layer_indices", [0, 1]),
                       ("mamba_rms_norm", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            FalconH1Config.from_dict({key: value})
    with pytest.raises(ValueError, match="no such key"):
        FalconH1Config.from_dict({"sliding_window": 4096})
    with pytest.raises(ValueError, match="mamba_d_head"):
        FalconH1Config(mamba_d_ssm=4000)


def test_the_initializer_gives_unit_scaled_projections():
    """A unit-RMS input through a matrix and its multipliers has about unit
    deviation: the logits do not collapse under lm_head_multiplier."""
    cfg = FalconH1Config.tiny(hidden_size=256, vocab_size=4096)
    p = falcon_h1.init_serving_params(cfg, 11)
    x = jax.random.normal(jax.random.key(0), (64, cfg.hidden_size))
    logits = (x @ p["lm_head.weight"]) * cfg.lm_head_multiplier
    assert 0.8 < float(jnp.std(logits)) < 1.25
    k = x @ p["model.layers.0.self_attn.k_proj.weight"] * cfg.key_multiplier
    assert 0.8 < float(jnp.std(k)) < 1.25
    a = -jnp.exp(p["model.layers.0.mamba.A_log"])
    assert bool(jnp.all((a <= -1) & (a >= -16)))
    dt = jax.nn.softplus(p["model.layers.0.mamba.dt_bias"])
    assert bool(jnp.all((dt > 0.9e-3) & (dt < 0.11)))


# ---- the cell's `correct` at the tiny size -------------------------------------

@pytest.fixture(scope="module")
def window():
    """One window of the tiny cell through a tiny engine, and the requests
    of it that decide `correct`."""
    from test_benchmark_cells import _tiny

    from benchmark.drivers import serve_falconh1 as drv

    cell, kw = _tiny("falconh1-chat-sat")
    said = []
    raw, profile, bad, p, dtype, pad_to = drv.serve(
        cell, 2**31 + 9, 1.5, False, said.append, kw["engine_kw"])
    m = cell["config"]
    return dict(drv=drv, m=m, p=p, dtype=dtype, bad=bad, raw=raw,
                samples=drv.sample_requests(
                    raw["measured"], m["deployment"]["token_budget"]),
                rows=raw["held_rows"], pad_to=pad_to)


def _reading(w, faults=()):
    return w["drv"].reading(w["m"], w["p"], w["samples"], w["rows"],
                            w["pad_to"], faults)


def test_the_sound_run_passes(window):
    drv, limits = window["drv"], window["drv"].LIMITS["float32"]
    budget = window["m"]["deployment"]["token_budget"]
    assert window["dtype"] == "float32" and window["bad"] == []
    assert len(window["samples"]) == drv.SAMPLE_REQUESTS
    # the longest prompt spans windows: the state is carried
    assert max(len(q) for q, _, _ in window["samples"]) > budget
    # the held rows: the shortest context first
    rows = window["rows"]
    assert 1 <= len(rows) <= 2
    assert len(rows[0][0]) == min(len(ids) for ids, _, _ in rows)
    assert not drv.over(_reading(window), limits)


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_fails_the_run(window, fault):
    drv = window["drv"]
    reading = _reading(window, (fault,))
    assert drv.over(reading, drv.LIMITS["float32"]), (fault, reading)


def test_the_lower_precision_control_fails_the_run(window):
    drv = window["drv"]
    with reference.lower_precision(drv.BELOW[window["dtype"]]):
        reading = _reading(window)
    assert drv.over(reading, drv.LIMITS["float32"]), reading


def test_the_faults_script_reads_every_reference(window):
    from benchmark import serve_falconh1_faults

    drv = window["drv"]
    at = serve_falconh1_faults.fault_readings(
        drv, window["m"], window["p"], window["samples"], window["rows"],
        window["pad_to"], window["dtype"])
    assert list(at) == ["sound", "control", *reference.FAULTS]
    limits = drv.LIMITS["float32"]
    assert [k for k, got in at.items()
            if not drv.over(got, limits)] == ["sound"]
