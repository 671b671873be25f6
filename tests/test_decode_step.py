"""The engine's one decode step (`models.llama._make_decode_step` over
paged pools, wired as `serving/engine.py` wires it at mp = cp = 1) held
to a float32 `jax.numpy` decoder written here, sharing no code with the
step: logits and the committed K/V rows at every head grouping, weight
dtype and pool dtype the engine serves; pages no row writes stay
bit-identical; a page recycled at slot 0 restarts its int8 scale. And
the option that used to select another step is refused by name."""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.analysis import tuner
from paddle_tpu.core.tensor import unwrap
from paddle_tpu.kernels.decode_attention import paged_decode_attention
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama import (_make_decode_step,
                                     make_paged_kv_helpers,
                                     make_paged_kv_q8_helpers,
                                     quantize_kv_pages)
from paddle_tpu.nn.quant import weight_quantize
from paddle_tpu.serving import ContinuousBatchingEngine

B, BS, VOCAB, HIDDEN, NH, FFN = 4, 8, 96, 64, 4, 128
F32 = jnp.float32


def _cfg(nkv, n_layers):
    return LlamaConfig(vocab_size=VOCAB, hidden_size=HIDDEN,
                       intermediate_size=FFN, num_hidden_layers=n_layers,
                       num_attention_heads=NH, num_key_value_heads=nkv)


def _params(cfg, rng, dtype, quant_w):
    """(the step's params, the same weights as dense f32 [K, N])."""
    dh, nkv = cfg.head_dim, cfg.num_key_value_heads
    shapes = {"self_attn.q_proj.weight": (HIDDEN, NH * dh),
              "self_attn.k_proj.weight": (HIDDEN, nkv * dh),
              "self_attn.v_proj.weight": (HIDDEN, nkv * dh),
              "self_attn.o_proj.weight": (NH * dh, HIDDEN),
              "mlp.gate_proj.weight": (HIDDEN, FFN),
              "mlp.up_proj.weight": (HIDDEN, FFN),
              "mlp.down_proj.weight": (FFN, HIDDEN)}
    p, ref = {}, {}

    def dense(name, arr):
        p[name] = jnp.asarray(arr, dtype)
        ref[name] = p[name].astype(F32)

    dense("llama.embed_tokens.weight", rng.normal(size=(VOCAB, HIDDEN)))
    dense("llama.norm.weight", rng.normal(size=(HIDDEN,)) * 0.1 + 1.0)
    dense("lm_head.weight", rng.normal(size=(HIDDEN, VOCAB)) * 0.1)
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        for norm in ("input_layernorm.weight",
                     "post_attention_layernorm.weight"):
            dense(pre + norm, rng.normal(size=(HIDDEN,)) * 0.1 + 1.0)
        for name, shape in shapes.items():
            w = rng.normal(size=shape) * 0.08
            if quant_w:
                # int8 [N, K] + scale [N], as the serving params hold it
                q, sc = p[pre + name] = tuple(
                    unwrap(t) for t in weight_quantize(jnp.asarray(w, F32)))
                ref[pre + name] = q.astype(F32).T * sc[None, :]
            else:
                dense(pre + name, w)
    return p, ref


def _pools(cfg, rng, dtype, quant_kv, n_pages):
    """Per-layer K and V pools, and what each holds as f32."""
    shape = (n_pages, cfg.num_key_value_heads, BS, cfg.head_dim)
    pools, held = [], []
    for _ in range(cfg.num_hidden_layers):
        pool = jnp.asarray(rng.normal(size=shape), dtype)
        if quant_kv:
            q, sc = quantize_kv_pages(pool)
            pools.append((q, sc))
            held.append(q.astype(F32) * sc[:, :, None, None])
        else:
            pools.append(pool)
            held.append(pool.astype(F32))
    return pools, held


def _paged_step(cfg, tables, quant_kv):
    """`_make_decode_step` with the engine's paged callbacks."""
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    if quant_kv:
        _, kv_write = make_paged_kv_q8_helpers(B, 0, nkv, dh, BS, tables)

        def kv_attend(q1, kct, vct, lens):
            (kc, ksc), (vc, vsc) = kct, vct
            return paged_decode_attention(q1, kc, vc, tables, lens,
                                          k_scale=ksc, v_scale=vsc)
    else:
        _, kv_write = make_paged_kv_helpers(B, 0, nkv, dh, BS, tables)

        def kv_attend(q1, kc, vc, lens):
            return paged_decode_attention(q1, kc, vc, tables, lens)

    return jax.jit(_make_decode_step(cfg, B, kv_write=kv_write,
                                     kv_attend=kv_attend))


# ---- the reference: float32 jax.numpy, nothing from paddle_tpu ------------

def _ref_rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ref_rope(x, pos, theta):
    """x [b, heads, dh] rotated (rotate-half pairs) at positions pos."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * d2, 2, dtype=F32) / (2 * d2))
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_step(cfg, w, kheld, vheld, tok, lens, tables):
    """(logits [b, vocab], [(k_new, v_new)] per layer, each [b, nkv, dh])
    for one token per row; row r attends positions 0..lens[r]-1 of its
    pages and the new token."""
    nkv, dh = cfg.num_key_value_heads, cfg.head_dim
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    cached = jnp.arange(tables.shape[1] * BS)[None, :] < lens[:, None]
    seen = jnp.concatenate([cached, jnp.ones((B, 1), bool)], 1)

    def context(held, new):
        """[pages, nkv, BS, dh] and the new row -> [b, T + 1, NH, dh]"""
        c = jnp.transpose(held[tables], (0, 1, 3, 2, 4)).reshape(
            B, -1, nkv, dh)
        return jnp.repeat(jnp.concatenate([c, new[:, None]], 1),
                          NH // nkv, axis=2)

    h = w["llama.embed_tokens.weight"][tok]
    committed = []
    for i in range(cfg.num_hidden_layers):
        pre = f"llama.layers.{i}."
        x = _ref_rms(h, w[pre + "input_layernorm.weight"], eps)
        q = (x @ w[pre + "self_attn.q_proj.weight"]).reshape(B, NH, dh)
        k = (x @ w[pre + "self_attn.k_proj.weight"]).reshape(B, nkv, dh)
        v = (x @ w[pre + "self_attn.v_proj.weight"]).reshape(B, nkv, dh)
        q, k = _ref_rope(q, lens, theta), _ref_rope(k, lens, theta)
        committed.append((np.asarray(k), np.asarray(v)))
        s = jnp.einsum("bhd,bthd->bht", q, context(kheld[i], k)) \
            / jnp.sqrt(jnp.asarray(dh, F32))
        s = jnp.where(seen[:, None, :], s, -jnp.inf)
        ctx = jnp.einsum("bht,bthd->bhd", jax.nn.softmax(s, -1),
                         context(vheld[i], v))
        h = h + ctx.reshape(B, NH * dh) \
            @ w[pre + "self_attn.o_proj.weight"]
        x2 = _ref_rms(h, w[pre + "post_attention_layernorm.weight"], eps)
        gate = x2 @ w[pre + "mlp.gate_proj.weight"]
        up = x2 @ w[pre + "mlp.up_proj.weight"]
        h = h + (gate * jax.nn.sigmoid(gate) * up) \
            @ w[pre + "mlp.down_proj.weight"]
    h = _ref_rms(h, w["llama.norm.weight"], eps)
    return np.asarray(h @ w["lm_head.weight"]), committed


# ---- the cases -------------------------------------------------------------

_RAGGED = (3, BS * 4 - 1, 0, 17)    # partial page, a table's last slot,
#                                     a retired row, mid-cache


@dataclasses.dataclass(frozen=True)
class Case:
    nkv: int
    dtype: object = F32
    quant_w: bool = False
    quant_kv: bool = False
    n_layers: int = 1
    width: int = 4
    lens: tuple = _RAGGED
    seed: int = 0

    def run(self):
        """One decode step beside the reference on the same operands."""
        cfg = _cfg(self.nkv, self.n_layers)
        rng = np.random.default_rng(self.seed)
        n_pages = B * self.width + 1
        p, w = _params(cfg, rng, self.dtype, self.quant_w)
        kcs, kheld = _pools(cfg, rng, self.dtype, self.quant_kv, n_pages)
        vcs, vheld = _pools(cfg, rng, self.dtype, self.quant_kv, n_pages)
        tables = rng.permutation(n_pages - 1)[:B * self.width].reshape(
            B, self.width).astype(np.int32) + 1
        tok = rng.integers(0, VOCAB, (B,)).astype(np.int32)
        lens = np.asarray(self.lens, np.int32)
        want, committed = _ref_step(cfg, w, kheld, vheld,
                                    jnp.asarray(tok), jnp.asarray(lens),
                                    jnp.asarray(tables))
        page, slot = tables[np.arange(B), lens // BS], lens % BS
        step = _paged_step(cfg, jnp.asarray(tables), self.quant_kv)
        logits, kcs2, vcs2 = step(p, kcs, vcs, jnp.asarray(tok)[:, None],
                                  jnp.asarray(lens))
        rows = [[_f32_pool(c)[page, :, slot, :] for c in kv]
                for kv in zip(kcs2, vcs2)]
        return dict(logits=np.asarray(logits.astype(F32)), want=want,
                    committed=committed, rows=rows, page=page, slot=slot,
                    before=(kcs, vcs), after=(kcs2, vcs2))


def _f32_pool(pool):
    if isinstance(pool, tuple):
        return np.asarray(pool[0], np.float32) \
            * np.asarray(pool[1])[:, :, None, None]
    return np.asarray(pool.astype(F32))


# tolerance of (logits, committed K/V rows): f32 is rounding only; bf16
# rounds every activation to 8 bits; an int8 pool holds a row to half a
# step of its page's absmax / 127
PARITY = {
    "gqa_group2_f32_two_layers": (Case(nkv=2, n_layers=2), 2e-4, 1e-5),
    "equal_heads_f32": (Case(nkv=4), 2e-4, 1e-5),
    "mqa_one_kv_head_f32": (Case(nkv=1), 2e-4, 1e-5),
    "gqa_bf16_two_layers": (Case(nkv=2, dtype=jnp.bfloat16, n_layers=2),
                            6e-2, 3e-2),
    "int8_weight_pairs_bf16": (Case(nkv=2, dtype=jnp.bfloat16,
                                    quant_w=True), 6e-2, 3e-2),
    "rows_span_pages_width8": (Case(nkv=2, width=8,
                                    lens=(3, BS * 8 - 1, 0, 40)),
                               2e-4, 1e-5),
    "int8_pools_gqa_two_layers": (Case(nkv=2, dtype=jnp.bfloat16,
                                       quant_kv=True, n_layers=2),
                                  1e-1, 6e-2),
    "int8_pools_equal_heads_int8_weights": (
        Case(nkv=4, dtype=jnp.bfloat16, quant_w=True, quant_kv=True),
        1e-1, 6e-2),
    "int8_pools_mqa": (Case(nkv=1, dtype=jnp.bfloat16, quant_kv=True),
                       1e-1, 6e-2),
    "int8_pools_rows_span_pages_width8": (
        Case(nkv=2, dtype=jnp.bfloat16, quant_kv=True, width=8,
             lens=(3, BS * 8 - 1, 0, 40)), 1e-1, 6e-2),
    "int8_pools_gqa_int8_weights_two_layers": (
        Case(nkv=2, dtype=jnp.bfloat16, quant_w=True, quant_kv=True,
             n_layers=2), 1e-1, 6e-2),
}


@pytest.mark.parametrize("name", sorted(PARITY))
def test_decode_step_matches_f32_reference(name):
    case, tol_logits, tol_kv = PARITY[name]
    got = case.run()
    assert np.max(np.abs(got["logits"] - got["want"])) < tol_logits
    for held, want in zip(got["rows"], got["committed"]):
        for h, w in zip(held, want):
            assert h.shape == w.shape == (B, case.nkv, 16)
            assert np.max(np.abs(h - w)) < tol_kv


@pytest.mark.parametrize("quant_kv", [False, True],
                         ids=["bf16_pools", "int8_pools"])
def test_pages_no_row_writes_are_bit_identical(quant_kv):
    got = Case(nkv=2, dtype=jnp.bfloat16, quant_kv=quant_kv,
               n_layers=2).run()
    untouched = np.setdiff1d(np.arange(B * 4 + 1), got["page"])
    assert len(untouched) == B * 4 + 1 - B
    for before, after in zip(jax.tree.leaves(got["before"]),
                             jax.tree.leaves(got["after"])):
        np.testing.assert_array_equal(np.asarray(after)[untouched],
                                      np.asarray(before)[untouched])
        assert not np.array_equal(np.asarray(after)[got["page"]],
                                  np.asarray(before)[got["page"]])


def test_recycled_page_written_at_slot0_resets_its_scale():
    """Every row commits at slot 0 of a page whose previous owner left a
    scale behind (the pools are full of random rows): the new scale is
    the new row's own absmax / 127, however loud the page used to be."""
    got = Case(nkv=2, dtype=jnp.bfloat16, quant_kv=True,
               lens=(BS, 2 * BS, 0, 3 * BS), seed=3).run()
    assert (got["slot"] == 0).all()
    for i, (k, v) in enumerate(got["committed"]):
        for (_, before), (_, after), row in (
                (got["before"][0][i], got["after"][0][i], k),
                (got["before"][1][i], got["after"][1][i], v)):
            own = np.abs(row).max(-1) / 127.0            # [b, nkv]
            new = np.asarray(after)[got["page"]]
            old = np.asarray(before)[got["page"]]
            np.testing.assert_allclose(new, own, rtol=2e-2)
            # where the previous owner was louder, a chain that had not
            # restarted would have kept its scale
            assert (old > 1.05 * own).any()


# ---- the retired option ----------------------------------------------------

def _tiny_engine(**kw):
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(21)
    params = dict(LlamaForCausalLM(cfg).raw_state())
    return cfg, ContinuousBatchingEngine(
        cfg, params, slots=2, prompt_bucket=8, max_prompt_len=16,
        max_new_tokens=4, block_size=8, steps_per_sync=2, **kw)


def test_engine_kwarg_is_a_type_error():
    with pytest.raises(TypeError, match="decode_megakernel"):
        _tiny_engine(decode_megakernel="attn")


def test_flag_is_unknown():
    with pytest.raises(KeyError, match="unknown flag FLAGS_decode_mega"):
        paddle.set_flags({"decode_megakernel": "attn"})
    with pytest.raises(KeyError, match="decode_megakernel"):
        paddle.get_flags("decode_megakernel")


@pytest.mark.parametrize("route", ["config_kwarg", "flag"])
@pytest.mark.parametrize("version,knobs,named", [
    (1, {"kv_cache_dtype": "bf16"}, "schema_version 1"),
    (tuner.SCHEMA_VERSION, {"decode_megakernel": "scan"},
     "decode_megakernel"),
], ids=["old_schema_version", "knob_outside_KNOBS"])
def test_tuned_config_file_is_refused_by_name(tmp_path, version, knobs,
                                              named, route):
    """A file the operator names (config=) raises; one a fleet-wide flag
    names warns and is left out — either way the message says why."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    path = tmp_path / tuner.TUNE_FILENAME
    path.write_text(json.dumps({
        "schema_version": version, "device": "tpu-v5e",
        "model": tuner.model_signature(cfg), "space_hash": "x",
        "knobs": knobs, "predicted": {}}))
    assert named in tuner.TunedConfig.load(str(path)).stale_reason(cfg=cfg)
    if route == "config_kwarg":
        with pytest.raises(ValueError, match=named):
            _tiny_engine(config=str(path))
        return
    paddle.set_flags({"tuned_config": str(path)})
    try:
        with pytest.warns(UserWarning, match=named):
            _, eng = _tiny_engine()
    finally:
        paddle.set_flags({"tuned_config": ""})
    assert eng.tuned_config is None and eng.kv_dtype == "bf16"


def test_no_rung_in_the_tuner_space():
    cfg = LlamaConfig.tiny()
    assert "decode_megakernel" not in tuner.KNOBS
    assert "decode_megakernel" not in tuner.default_space(cfg)
    assert "decode_megakernel" not in tuner.baseline_config(cfg)


def test_no_rung_in_any_program_key():
    _, eng = _tiny_engine(unified_step=False)
    eng._get_prefill(8, 1)
    eng._get_prefix_prefill(8, 1, eng._prefix_width_ladder()[0])
    rungs = {"off", "attn", "full", "scan"}
    assert len(eng._prefill_cache) == 2
    for key in eng._prefill_cache:
        assert not rungs & set(key)
    assert not any(r in name.split(":") for name in eng.compile_stats()
                   for r in rungs)
    assert "decode_megakernel" not in eng.metrics()
    assert eng.metrics()["megakernel_rung"] == "off"
