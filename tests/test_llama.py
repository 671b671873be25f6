"""Flagship Llama tests: kernels vs oracle, hybrid-mesh training,
parallel-vs-serial loss alignment (reference strategy:
test/auto_parallel/hybrid_strategy/semi_auto_llama_acc_align.py — parallel
losses must match single-device losses).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion, shard_llama)
from paddle_tpu.parallel import make_train_step
from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    set_global_mesh(None)


def _data(cfg, b=4, s=32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
    return x, y


class TestFlashAttentionKernel:
    def test_matches_reference_causal_gqa(self):
        from paddle_tpu.kernels.flash_attention import (_fwd_ref,
                                                        flash_attention)

        rng = np.random.default_rng(0)
        B, S, H, D = 2, 256, 4, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal)
            qc = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
            kc = jnp.swapaxes(k, 1, 2).reshape(B * 2, S, D)
            vc = jnp.swapaxes(v, 1, 2).reshape(B * 2, S, D)
            ref = _fwd_ref(qc, kc, vc, causal, 1.0 / np.sqrt(D))
            ref = jnp.swapaxes(ref.reshape(B, H, S, D), 1, 2)
            np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_gradients_match_reference(self):
        from paddle_tpu.kernels.flash_attention import (_fwd_ref,
                                                        flash_attention)

        rng = np.random.default_rng(1)
        B, S, H, D = 1, 128, 2, 32
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

        def loss_fa(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            qc = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
            kc = jnp.swapaxes(k, 1, 2).reshape(B * H, S, D)
            vc = jnp.swapaxes(v, 1, 2).reshape(B * H, S, D)
            o = _fwd_ref(qc, kc, vc, True, 1.0 / np.sqrt(D))
            return jnp.sum(o ** 2)

        g1 = jax.grad(loss_fa, (0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)


class TestRMSNormKernel:
    def test_fwd_bwd(self):
        from paddle_tpu.kernels.rms_norm import _rms_ref, rms_norm

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 64, 256)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
        np.testing.assert_allclose(rms_norm(x, w), _rms_ref(x, w, 1e-6),
                                   atol=1e-6)
        ga = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w) * jnp.cos(x)),
                      (0, 1))(x, w)
        gb = jax.grad(lambda x, w: jnp.sum(_rms_ref(x, w, 1e-6) * jnp.cos(x)),
                      (0, 1))(x, w)
        np.testing.assert_allclose(ga[0], gb[0], atol=1e-5)
        np.testing.assert_allclose(ga[1], gb[1], atol=1e-5)


class TestLlama:
    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_train_loss_decreases_hybrid_mesh(self):
        mesh = build_mesh({"dp": 2, "sharding": 2, "mp": 2, "sep": 1})
        set_global_mesh(mesh)
        cfg = LlamaConfig.tiny(recompute=True)
        model = shard_llama(LlamaForCausalLM(cfg), mesh)
        crit = LlamaPretrainingCriterion(cfg)
        step, p, o = make_train_step(model, lambda lg, lb: crit(lg, lb),
                                     mesh, lr=1e-3)
        x, y = _data(cfg)
        losses = []
        for _ in range(3):
            loss, p, o = step(p, o, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_parallel_matches_serial(self):
        cfg = LlamaConfig.tiny()
        crit = LlamaPretrainingCriterion(cfg)
        x, y = _data(cfg)

        paddle.seed(7)
        m1 = LlamaForCausalLM(cfg)
        s1, p, o = make_train_step(m1, lambda lg, lb: crit(lg, lb), None,
                                   lr=1e-3)
        serial = []
        for _ in range(3):
            l, p, o = s1(p, o, x, y)
            serial.append(float(l))

        mesh = build_mesh({"dp": 2, "sharding": 2, "mp": 2, "sep": 1})
        set_global_mesh(mesh)
        paddle.seed(7)
        m2 = shard_llama(LlamaForCausalLM(cfg), mesh)
        s2, p, o = make_train_step(m2, lambda lg, lb: crit(lg, lb), mesh,
                                   lr=1e-3)
        par = []
        for _ in range(3):
            l, p, o = s2(p, o, x, y)
            par.append(float(l))
        np.testing.assert_allclose(serial, par, atol=2e-3)

    def test_eager_forward_backward(self):
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        x, y = _data(cfg, b=2, s=16)
        loss = crit(model(paddle.to_tensor(x)), paddle.to_tensor(y))
        loss.backward()
        g = model.llama.layers[0].self_attn.q_proj.weight.grad
        assert g is not None and float((g * g).sum().numpy()) > 0

    def test_generate_kv_cache_matches_full_forward(self):
        cfg = LlamaConfig.tiny()
        paddle.seed(3)
        model = LlamaForCausalLM(cfg)
        x, _ = _data(cfg, b=2, s=8)
        out = model.generate(paddle.to_tensor(x), max_new_tokens=4)
        assert out.shape == [2, 12]
        # single-token incremental LOGITS must match the full forward (an
        # argmax-only check once hid a decode-position rope bug)
        caches = [(None, None)] * cfg.num_hidden_layers
        lg, caches = model(paddle.to_tensor(out.numpy()[:, :-1]),
                           caches=caches)
        last = out.numpy()[:, -1:]
        lg_inc, _ = model(paddle.to_tensor(last), caches=caches,
                          position_offset=11)
        full = model(paddle.to_tensor(out.numpy()))
        np.testing.assert_allclose(lg_inc.numpy()[:, -1],
                                   full.numpy()[:, -1], atol=2e-5)

    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_jit_generate_matches_eager(self):
        """The single-program decode loop (prefill + lax.scan over the
        fixed cache) must reproduce eager generate token for token."""
        cfg = LlamaConfig.tiny()
        paddle.seed(5)
        model = LlamaForCausalLM(cfg)
        x, _ = _data(cfg, b=2, s=8)
        a = model.generate(paddle.to_tensor(x), max_new_tokens=6)
        b = model.jit_generate(paddle.to_tensor(x), max_new_tokens=6)
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        # eos: single row whose SECOND generated token is declared eos —
        # the output must trim right after it, and the finished tail is
        # eos-padded up to the cut
        row = x[:1]
        a1 = model.generate(paddle.to_tensor(row), max_new_tokens=6)
        gen = a1.numpy()[0, 8:]
        eos = int(gen[1])  # 2nd generated token declared eos
        first_hit = int(np.argmax(gen == eos))  # may also be token 0
        c = model.jit_generate(paddle.to_tensor(row), max_new_tokens=6,
                               eos_token_id=eos)
        assert c.shape[1] == 8 + first_hit + 1, (c.shape, first_hit)
        assert int(c.numpy()[0, -1]) == eos
        # max_new_tokens=0 returns the prompt unchanged, like generate()
        z = model.jit_generate(paddle.to_tensor(row), max_new_tokens=0)
        np.testing.assert_array_equal(z.numpy(), row)

    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_jit_generate_prompt_bucketing_one_compile(self):
        """Two prompt lengths inside one 128-token bucket must share ONE
        compiled program, and padded decode must match the unbucketed
        (eager) result (round-2 VERDICT item 8)."""
        cfg = LlamaConfig.tiny()
        paddle.seed(6)
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(1)
        ids17 = rng.integers(1, cfg.vocab_size, (2, 17))
        ids30 = rng.integers(1, cfg.vocab_size, (2, 30))
        out17 = model.jit_generate(paddle.to_tensor(ids17), max_new_tokens=5)
        n = len(model._jit_gen_cache)
        out30 = model.jit_generate(paddle.to_tensor(ids30), max_new_tokens=5)
        assert len(model._jit_gen_cache) == n, "second length recompiled"
        # numerics match the unbucketed eager path
        e17 = model.generate(paddle.to_tensor(ids17), max_new_tokens=5)
        e30 = model.generate(paddle.to_tensor(ids30), max_new_tokens=5)
        np.testing.assert_array_equal(out17.numpy(), e17.numpy())
        np.testing.assert_array_equal(out30.numpy(), e30.numpy())

    def test_jit_generate_sampling(self):
        """Sampled decoding in the jitted loop (round-2 VERDICT item 5):
        seeded determinism, temp→0 == greedy, and no recompile when
        temperature/top_p change (they are traced scalars)."""
        cfg = LlamaConfig.tiny()
        paddle.seed(7)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(2).integers(1, cfg.vocab_size, (2, 9))
        xt = paddle.to_tensor(x)
        greedy = model.jit_generate(xt, max_new_tokens=6)
        s1 = model.jit_generate(xt, max_new_tokens=6, do_sample=True,
                                temperature=1.0, top_p=0.9, seed=42)
        s2 = model.jit_generate(xt, max_new_tokens=6, do_sample=True,
                                temperature=1.0, top_p=0.9, seed=42)
        np.testing.assert_array_equal(s1.numpy(), s2.numpy())
        cold = model.jit_generate(xt, max_new_tokens=6, do_sample=True,
                                  temperature=1e-4, seed=3)
        np.testing.assert_array_equal(cold.numpy(), greedy.numpy())
        n = len(model._jit_gen_cache)
        model.jit_generate(xt, max_new_tokens=6, do_sample=True,
                           temperature=0.7, top_p=0.5, seed=4)
        assert len(model._jit_gen_cache) == n, "temperature/top_p recompiled"
        # high temperature spreads mass: over many draws, the first sampled
        # token should not be constant across seeds
        firsts = {int(model.jit_generate(
            xt[:1], max_new_tokens=1, do_sample=True, temperature=50.0,
            seed=s).numpy()[0, -1]) for s in range(8)}
        assert len(firsts) > 1, "high-temperature sampling is degenerate"

    def test_jit_generate_top_k_restricts_support(self):
        cfg = LlamaConfig.tiny()
        paddle.seed(8)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(3).integers(1, cfg.vocab_size, (1, 9))
        xt = paddle.to_tensor(x)
        greedy_tok = int(model.jit_generate(xt, max_new_tokens=1).numpy()[0, -1])
        # top_k=1 == greedy regardless of temperature/seed
        for s in range(4):
            t = model.jit_generate(xt, max_new_tokens=1, do_sample=True,
                                   top_k=1, temperature=5.0, seed=s)
            assert int(t.numpy()[0, -1]) == greedy_tok

    @pytest.mark.slow  # tier-1 budget: int8-weight serving stays
    # covered by test_quant_serving_params_and_program and
    # test_quant_only_prefill_generation_matches
    def test_jit_generate_int8_weight_only_decode(self):
        """quant='weight_only_int8' decode (round-2 VERDICT item 3): the
        int8 per-channel path must track the fp greedy path."""
        cfg = LlamaConfig.tiny()
        paddle.seed(9)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(4).integers(1, cfg.vocab_size, (2, 9))
        xt = paddle.to_tensor(x)
        fp = model.jit_generate(xt, max_new_tokens=6)
        q = model.jit_generate(xt, max_new_tokens=6, quant="weight_only_int8")
        agree = (fp.numpy() == q.numpy()).mean()
        assert agree > 0.7, f"int8 decode diverged: agreement {agree}"
        q4 = model.jit_generate(xt, max_new_tokens=6,
                                quant="weight_only_int4")
        agree4 = (fp.numpy() == q4.numpy()).mean()
        assert agree4 > 0.5, f"int4 decode diverged: agreement {agree4}"
        with pytest.raises(ValueError):
            model.jit_generate(xt, max_new_tokens=2, quant="int3")


    def test_quant_only_prefill_generation_matches(self):
        """prefill_with_quant=True (the 7B-on-one-chip serving mode: no fp
        params on device) must track the fp-prefill quantized path —
        round-4 VERDICT item 2."""
        cfg = LlamaConfig.tiny()
        paddle.seed(12)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(7).integers(1, cfg.vocab_size, (2, 9))
        xt = paddle.to_tensor(x)
        ref = model.jit_generate(xt, max_new_tokens=6,
                                 quant="weight_only_int8")
        qo = model.jit_generate(xt, max_new_tokens=6,
                                quant="weight_only_int8",
                                prefill_with_quant=True)
        agree = (ref.numpy() == qo.numpy()).mean()
        assert agree > 0.7, f"quant-only prefill diverged: {agree}"
        with pytest.raises(ValueError):
            model.jit_generate(xt, max_new_tokens=2,
                               prefill_with_quant=True)

    def test_quant_serving_params_and_program(self):
        """init_quant_serving_params + build_quant_generate run standalone
        (no Layer model object) — the exact path the 7B serving bench
        takes; int4 packing halves the stored K dim."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models import (build_quant_generate,
                                       init_quant_serving_params)

        cfg = LlamaConfig.tiny()
        for quant, kdiv in (("weight_only_int8", 1),
                            ("weight_only_int4", 2)):
            p = init_quant_serving_params(cfg, quant, seed=3)
            wq, sc = p["llama.layers.0.self_attn.q_proj.weight"]
            assert wq.shape == (cfg.hidden_size, cfg.hidden_size // kdiv)
            assert sc.shape == (cfg.hidden_size,)
            fn = jax.jit(build_quant_generate(cfg, b=2, sb=16, max_new=4))
            ids = jnp.asarray(np.random.default_rng(8).integers(
                1, cfg.vocab_size, (2, 16)))
            toks = fn(p, ids, jnp.asarray(9, jnp.int32),
                      jax.random.PRNGKey(0), jnp.asarray(1.0, jnp.float32),
                      jnp.asarray(1.0, jnp.float32))
            assert toks.shape == (2, 4)
            assert (np.asarray(toks) >= 0).all()

    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_remat_scope_and_fused_swiglu_match_baseline(self):
        """Sub-layer remat granularity (remat_scope='attn'/'mlp') is
        numerics-preserving: same loss trajectory as the plain config
        (reference: fleet/recompute/recompute.py:109 — op-level
        recompute)."""
        from paddle_tpu.models import LlamaPretrainingCriterion
        from paddle_tpu.parallel import make_train_step

        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.integers(0, 128, (4, 32)))
        y = jnp.asarray(rng.integers(0, 128, (4, 32)))

        def losses(**over):
            cfg = LlamaConfig.tiny(**over)
            paddle.seed(15)
            m = LlamaForCausalLM(cfg)
            crit = LlamaPretrainingCriterion(cfg)
            step, p, o = make_train_step(m, lambda lg, lb: crit(lg, lb),
                                         None, lr=1e-3)
            out = []
            for _ in range(3):
                l, p, o = step(p, o, x, y)
                out.append(float(l))
            return out

        base = losses(recompute=True)
        for over in ({"recompute": True, "remat_scope": "attn"},
                     {"recompute": True, "remat_scope": "mlp"}):
            np.testing.assert_allclose(losses(**over), base, atol=2e-5,
                                       err_msg=str(over))
        # tiny()'s MLP is not 512-tileable: asking for the fused kernel
        # there is an error, not a silent XLA answer
        with pytest.raises(ValueError, match="fused=True"):
            losses(recompute=True, fused_swiglu=True)

    def test_paged_generation_matches_contiguous(self):
        """cache_layout='paged' (block tables + paged pools) must produce
        the same greedy tokens as the contiguous cache — round-4 VERDICT
        item 3 oracle bar. Covers both Pallas grids (interpret mode on
        CPU): grouped queries (nkv=2) and equal heads (nkv=4... tiny()
        has nh=4)."""
        for nkv in (2, 4):   # tiny() has nh=4: GQA + equal-heads grids
            cfg = dataclasses.replace(LlamaConfig.tiny(),
                                      num_key_value_heads=nkv)
            paddle.seed(13)
            model = LlamaForCausalLM(cfg)
            x = np.random.default_rng(9).integers(1, cfg.vocab_size, (2, 9))
            xt = paddle.to_tensor(x)
            ref = model.jit_generate(xt, max_new_tokens=6)
            paged = model.jit_generate(xt, max_new_tokens=6,
                                       cache_layout="paged",
                                       kv_block_size=8)
            np.testing.assert_array_equal(ref.numpy(), paged.numpy(),
                                          err_msg=f"nkv={nkv}")
        # paging composes with weight-only quant (no fp params needed)
        q8 = model.jit_generate(xt, max_new_tokens=6, cache_layout="paged",
                                kv_block_size=8, quant="weight_only_int8")
        agree = (ref.numpy() == q8.numpy()).mean()
        assert agree > 0.7, f"paged int8 diverged: {agree}"

    def test_paged_ragged_batch_matches_per_row(self):
        """One paged program serves rows of different prompt lengths
        (seq_lens): each row's tokens must match generating that prompt
        alone (reference: the varying-length batch contract of
        block_multihead_attention.py:25)."""
        cfg = LlamaConfig.tiny()
        paddle.seed(14)
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(10)
        p1 = rng.integers(1, cfg.vocab_size, (1, 5))
        p2 = rng.integers(1, cfg.vocab_size, (1, 9))
        rect = np.zeros((2, 9), np.int64)
        rect[0, :5], rect[1] = p1[0], p2[0]
        ragged = model.jit_generate(paddle.to_tensor(rect),
                                    max_new_tokens=5, cache_layout="paged",
                                    kv_block_size=8, seq_lens=[5, 9])
        solo1 = model.jit_generate(paddle.to_tensor(p1), max_new_tokens=5,
                                   cache_layout="paged", kv_block_size=8)
        solo2 = model.jit_generate(paddle.to_tensor(p2), max_new_tokens=5,
                                   cache_layout="paged", kv_block_size=8)
        # new tokens are appended after the input rectangle (width 9)
        np.testing.assert_array_equal(ragged.numpy()[0, 9:],
                                      solo1.numpy()[0, 5:])
        np.testing.assert_array_equal(ragged.numpy()[1, 9:],
                                      solo2.numpy()[0, 9:])

    def test_paged_kv_manager_alloc_free_reuse(self):
        """Block allocation: freed pages are reused, double-free and pool
        exhaustion raise (round-4 VERDICT item 3 'block reuse/free')."""
        from paddle_tpu.models import PagedKVManager

        m = PagedKVManager(max_pages=8, block_size=16)
        a = m.alloc(40)          # 3 pages
        assert len(a) == 3 and m.n_free == 5
        b = m.alloc(64)          # 4 pages
        assert m.n_free == 1
        m.free(a)
        assert m.n_free == 4
        c = m.alloc(33)          # 3 pages — must reuse freed ids
        assert set(c) <= set(a) | {7}
        with pytest.raises(RuntimeError):
            m.alloc(1000)
        with pytest.raises(ValueError):
            m.free(b + [b[0]])   # double free
        tbl, lists = PagedKVManager(8, 16).tables_for_batch([40, 16])
        assert tbl.shape == (2, 3)
        assert int(tbl[1, 1]) == int(tbl[1, 0])  # padded with own last id

    def test_llama2_7b_config_construction(self):
        """BASELINE config 3 (Llama-2-7B) constructs with the published
        dimensions and the quantized-weight memory math that fits one
        16 GB chip (round-4 VERDICT item 2 'Done' bar)."""
        cfg = LlamaConfig.llama2_7b(dtype="bfloat16")
        assert (cfg.hidden_size, cfg.num_hidden_layers,
                cfg.num_attention_heads,
                cfg.num_key_value_heads) == (4096, 32, 32, 32)
        assert cfg.intermediate_size == 11008
        h, im, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        L = cfg.num_hidden_layers
        proj = L * (4 * h * h + 3 * h * im) + h * v   # quantized matmuls
        rest = v * h + (2 * L + 1) * h                # embed + norms (bf16)
        n_params = proj + rest
        assert 6.5e9 < n_params < 7.0e9, n_params
        int8_gb = (proj + 2 * rest) / 2**30
        int4_gb = (proj / 2 + 2 * rest) / 2**30
        assert int8_gb < 7.0, int8_gb    # fits 16 GB with KV cache
        assert int4_gb < 3.7, int4_gb

    def test_jit_generate_top_p_zero_is_greedy(self):
        cfg = LlamaConfig.tiny()
        paddle.seed(10)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(5).integers(1, cfg.vocab_size, (1, 9))
        xt = paddle.to_tensor(x)
        greedy = model.jit_generate(xt, max_new_tokens=4)
        for s in range(3):
            t = model.jit_generate(xt, max_new_tokens=4, do_sample=True,
                                   top_p=0.0, seed=s)
            np.testing.assert_array_equal(t.numpy(), greedy.numpy())

    def test_int8_decode_requantizes_after_weight_update(self):
        """The quant cache keys on source-array identity: updating a weight
        must be reflected in the next quantized generation."""
        import jax.numpy as jnp

        cfg = LlamaConfig.tiny()
        paddle.seed(11)
        model = LlamaForCausalLM(cfg)
        x = np.random.default_rng(6).integers(1, cfg.vocab_size, (1, 9))
        xt = paddle.to_tensor(x)
        model.jit_generate(xt, max_new_tokens=2, quant="weight_only_int8")
        cache = model._decode_quant_cache
        key = next(iter(cache))     # (param name, algo)
        name = key[0]
        old_q = cache[key][1][0]
        # perturb that weight through the raw-state path
        state = model.raw_state()
        state[name] = state[name] + 1.0
        model.load_raw_state(state)
        model.jit_generate(xt, max_new_tokens=2, quant="weight_only_int8")
        new_q = model._decode_quant_cache[key][1][0]
        assert not np.array_equal(np.asarray(old_q), np.asarray(new_q))

    @pytest.mark.slow  # over tier-1 budget; run explicitly with -m slow
    def test_sep_matches_serial(self):
        """Ulysses SEP must be numerically equivalent to serial training,
        same bar as TP/DP/sharding (reference:
        semi_auto_llama_acc_align.py). Covers the divisible-kv a2a path
        (mp=1, sep=2: nkv=2 splits evenly), the kv-repeat GQA path
        (mp*sep=4 > nkv), the mp*sep composition, and the minimal-repeat
        case (nh=8, nkv=2, mp*sep=4: kv repeats 2x not 4x)."""
        cases = [
            ({"dp": 4, "sharding": 1, "mp": 1, "sep": 2}, {}),
            ({"dp": 2, "sharding": 1, "mp": 1, "sep": 4}, {}),
            ({"dp": 2, "sharding": 1, "mp": 2, "sep": 2}, {}),
            ({"dp": 2, "sharding": 1, "mp": 2, "sep": 2},
             dict(num_attention_heads=8, num_key_value_heads=2)),
        ]
        for axes, over in cases:
            set_global_mesh(None)
            cfg = dataclasses.replace(LlamaConfig.tiny(), **over)
            crit = LlamaPretrainingCriterion(cfg)
            x, y = _data(cfg)

            paddle.seed(11)
            m1 = LlamaForCausalLM(cfg)
            s1, p, o = make_train_step(m1, lambda lg, lb: crit(lg, lb),
                                       None, lr=1e-3)
            serial = []
            for _ in range(3):
                l, p, o = s1(p, o, x, y)
                serial.append(float(l))

            mesh = build_mesh(axes)
            set_global_mesh(mesh)
            paddle.seed(11)
            m2 = shard_llama(LlamaForCausalLM(cfg), mesh)
            s2, p, o = make_train_step(m2, lambda lg, lb: crit(lg, lb),
                                       mesh, lr=1e-3)
            par = []
            for _ in range(3):
                l, p, o = s2(p, o, x, y)
                par.append(float(l))
            np.testing.assert_allclose(serial, par, atol=2e-3,
                                       err_msg=f"SEP diverged on {axes}")

    def test_sep_context_parallel_runs(self):
        mesh = build_mesh({"dp": 2, "sharding": 1, "mp": 2, "sep": 2})
        set_global_mesh(mesh)
        cfg = LlamaConfig.tiny()
        model = shard_llama(LlamaForCausalLM(cfg), mesh)
        crit = LlamaPretrainingCriterion(cfg)
        step, p, o = make_train_step(model, lambda lg, lb: crit(lg, lb),
                                     mesh, lr=1e-3)
        x, y = _data(cfg)
        l1, p, o = step(p, o, x, y)
        l2, p, o = step(p, o, x, y)
        assert float(l2) < float(l1)


class TestSwigluKernel:
    def test_ref_path_matches_closed_form(self):
        from paddle_tpu.kernels import swiglu as K

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
        wg = jnp.asarray(rng.standard_normal((64, 128)) * 0.1, jnp.float32)
        wu = jnp.asarray(rng.standard_normal((64, 128)) * 0.1, jnp.float32)
        out = K.swiglu_matmul(x, wg, wu)
        ref = jax.nn.silu(x @ wg) * (x @ wu)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda a, b, c: K.swiglu_matmul(a, b, c).sum(),
                     argnums=(0, 1, 2))(x, wg, wu)
        gr = jax.grad(lambda a, b, c: (jax.nn.silu(a @ b) * (a @ c)).sum(),
                      argnums=(0, 1, 2))(x, wg, wu)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-5)

    def test_fused_matches_xla_fwd_and_bwd(self):
        """The Pallas path (interpret mode off-TPU) must match XLA fwd AND
        backward — the hand-derived dsilu and the vjp matmuls included."""
        from paddle_tpu.kernels import swiglu as K

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((1024, 512)), jnp.float32)
        wg = jnp.asarray(rng.standard_normal((512, 512)) * 0.05, jnp.float32)
        wu = jnp.asarray(rng.standard_normal((512, 512)) * 0.05, jnp.float32)
        a = K.swiglu_matmul(x, wg, wu, fused=True)
        b = K.swiglu_matmul(x, wg, wu, fused=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)
        gf = jax.grad(lambda *t: K.swiglu_matmul(*t, fused=True).sum(),
                      argnums=(0, 1, 2))(x, wg, wu)
        gx = jax.grad(lambda *t: K.swiglu_matmul(*t, fused=False).sum(),
                      argnums=(0, 1, 2))(x, wg, wu)
        for got, want, nm in zip(gf, gx, ("x", "wg", "wu")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3, err_msg=nm)


class TestInt4MatmulKernel:
    def test_matches_dequant_oracle(self):
        from paddle_tpu.kernels.int4_matmul import int4_matmul
        from paddle_tpu.nn.quant import weight_dequantize, weight_quantize

        rng = np.random.default_rng(0)
        K, N = 256, 512
        w = rng.standard_normal((K, N)).astype("float32")
        wq, sc = paddle.nn.quant.weight_quantize(
            paddle.to_tensor(w), algo="weight_only_int4")
        wd = np.asarray(weight_dequantize(
            wq, sc, algo="weight_only_int4", out_dtype="float32")._array)
        x = rng.standard_normal((4, K)).astype("float32")
        out = int4_matmul(jnp.asarray(x), wq._array, sc._array)
        np.testing.assert_allclose(np.asarray(out), x @ wd,
                                   rtol=2e-3, atol=2e-3)

    def test_misaligned_falls_back(self):
        from paddle_tpu.kernels.int4_matmul import int4_matmul
        from paddle_tpu.nn.quant import weight_dequantize, weight_quantize

        rng = np.random.default_rng(1)
        K, N = 64, 96  # N not a multiple of the block
        w = rng.standard_normal((K, N)).astype("float32")
        wq, sc = paddle.nn.quant.weight_quantize(
            paddle.to_tensor(w), algo="weight_only_int4")
        wd = np.asarray(weight_dequantize(
            wq, sc, algo="weight_only_int4", out_dtype="float32")._array)
        x = rng.standard_normal((2, K)).astype("float32")
        out = int4_matmul(jnp.asarray(x), wq._array, sc._array)
        np.testing.assert_allclose(np.asarray(out), x @ wd,
                                   rtol=2e-3, atol=2e-3)
