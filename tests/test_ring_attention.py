"""Ring attention tests: exactness vs full attention, gradients, and the
Llama integration over the sep axis (reference gap: the reference snapshot
has no ring attention — SURVEY.md §5.7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle


from paddle_tpu.parallel.mesh import build_mesh, set_global_mesh
from paddle_tpu.parallel.ring_attention import _block_attn, ring_attention


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    set_global_mesh(None)


def _full(q, k, v, causal, d):
    num, m, l = _block_attn(q, k, v, 1 / np.sqrt(d), 0, 0, causal)
    return (num / l).astype(q.dtype)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        mesh = build_mesh({"dp": 2, "sep": 4})
        set_global_mesh(mesh)
        rng = np.random.default_rng(0)
        B, S, H, D = 2, 64, 4, 16
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_full(q, k, v, causal, D)),
                                   atol=2e-5)

    def test_gradients_match(self):
        mesh = build_mesh({"dp": 1, "sep": 8})
        set_global_mesh(mesh)
        rng = np.random.default_rng(1)
        B, S, H, D = 1, 64, 2, 8
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

        def loss_ring(q, k, v):
            return jnp.sum(
                ring_attention(q, k, v, mesh=mesh, causal=True)
                * jnp.cos(q))

        def loss_ref(q, k, v):
            return jnp.sum(_full(q, k, v, True, D) * jnp.cos(q))

        g1 = jax.jit(jax.grad(loss_ring, (0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_matches_full_attention_at_8k(self):
        """Ring numerics at the LONG-CONTEXT shape (seq 8192, sep 8 —
        1024-token chunks rotating the ring), the round-4 VERDICT item 8
        CPU assertion backing the single-chip 8k bench
        (bench_longcontext.py). Small head count keeps the fp32 oracle's
        S^2 score affordable on CPU."""
        mesh = build_mesh({"dp": 1, "sep": 8})
        set_global_mesh(mesh)
        rng = np.random.default_rng(3)
        B, S, HQ, HK, D = 1, 8192, 2, 1, 64
        q = jnp.asarray(rng.normal(size=(B, S, HQ, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, HK, D)), jnp.float32)
        out = jax.jit(lambda a, b_, c: ring_attention(
            a, b_, c, mesh=mesh, causal=True))(q, k, v)
        ref = _full(q, k, v, True, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)

    def test_no_mesh_fallback(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(1, 16, 2, 8)), jnp.float32)
        out = ring_attention(q, q, q, mesh=None, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_full(q, q, q, True, 8)),
                                   atol=1e-6)


class TestLlamaRing:
    def test_ring_matches_ulysses_losses(self):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion,
                                       shard_llama)
        from paddle_tpu.parallel import make_train_step

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 128, (4, 32)))
        y = jnp.asarray(rng.integers(0, 128, (4, 32)))

        losses = {}
        for impl in ("ulysses", "ring"):
            mesh = build_mesh({"dp": 2, "sharding": 1, "mp": 2, "sep": 2})
            set_global_mesh(mesh)
            paddle.seed(7)
            cfg = LlamaConfig.tiny(attention_impl=impl)
            model = shard_llama(LlamaForCausalLM(cfg), mesh)
            crit = LlamaPretrainingCriterion(cfg)
            step, p, o = make_train_step(
                model, lambda lg, lb: crit(lg, lb), mesh, lr=1e-3)
            ls = []
            for _ in range(2):
                l, p, o = step(p, o, x, y)
                ls.append(float(l))
            losses[impl] = ls
            set_global_mesh(None)
        np.testing.assert_allclose(losses["ring"], losses["ulysses"],
                                   atol=2e-3)
