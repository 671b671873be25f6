"""Distributed stack tests on the virtual 8-device CPU mesh.

Mirrors the reference's strategy (SURVEY.md §4.3): pure-logic SPMD checks +
small-world collective semantics + parallel-vs-serial numerical alignment,
all without real multi-chip hardware.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


@pytest.fixture(autouse=True)
def _mesh():
    mesh = dist.build_mesh({"dp": 2, "mp": 2, "pp": 2})
    dist.set_global_mesh(mesh)
    yield mesh
    dist.set_global_mesh(None)


def rand(*shape):
    return np.random.uniform(-1, 1, shape).astype(np.float32)


class TestMesh:
    def test_build(self, _mesh):
        assert jax.device_count() == 8
        assert dict(_mesh.shape) == {"dp": 2, "mp": 2, "pp": 2}

    def test_hcg_accessors(self, _mesh):
        hcg = dist.HybridCommunicateGroup(_mesh)
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 1
        assert hcg.nranks == 8

    def test_auto_mesh_infers_dp(self):
        mesh = dist.auto_mesh(mp=4)
        assert dict(mesh.shape) == {"dp": 2, "mp": 4}


class TestShardTensor:
    def test_shard_and_placements(self, _mesh):
        x = paddle.to_tensor(rand(8, 4))
        d = dist.shard_tensor(x, _mesh, [dist.Shard(0)])  # shard dim0 over dp
        assert d.shape == [8, 4]  # global shape preserved
        np.testing.assert_allclose(d.numpy(), x.numpy())
        pl = dist.get_placements(d, _mesh)
        assert pl[0] == dist.Shard(0)
        assert pl[1] == dist.Replicate()

    def test_reshard(self, _mesh):
        x = dist.shard_tensor(paddle.to_tensor(rand(8, 8)), _mesh,
                              [dist.Shard(0)])
        y = dist.reshard(x, _mesh, [dist.Replicate(), dist.Shard(1)])
        np.testing.assert_allclose(y.numpy(), x.numpy())
        pl = dist.get_placements(y, _mesh)
        assert pl[1] == dist.Shard(1)

    def test_shard_layer(self, _mesh):
        layer = nn.Linear(8, 8)
        dist.shard_layer(layer, dist.ProcessMesh(_mesh))
        for p in layer.parameters():
            assert p._array.sharding is not None

    def test_process_mesh(self):
        pm = dist.ProcessMesh([[0, 1], [2, 3]], dim_names=["x", "y"])
        assert pm.shape == [2, 2]
        assert pm.dim_names == ["x", "y"]
        assert pm.ndim == 2

    def test_sharded_matmul_matches_serial(self, _mesh):
        """Parallel-vs-serial alignment (reference:
        semi_auto_llama_acc_align.py strategy)."""
        a, b = rand(8, 16), rand(16, 8)
        ta = dist.shard_tensor(paddle.to_tensor(a), _mesh, [dist.Shard(0)])
        tb = dist.shard_tensor(paddle.to_tensor(b), _mesh,
                               [dist.Replicate(), dist.Shard(1)])
        out = paddle.matmul(ta, tb)
        np.testing.assert_allclose(out.numpy(), a @ b, rtol=1e-4, atol=1e-5)


class TestCollectivesInShardMap:
    """Collectives lower to lax ops inside shard_map over the mesh axis."""

    def test_all_reduce(self, _mesh):
        from jax import shard_map

        def f(x):
            t = paddle.Tensor(x)
            out = dist.all_reduce(t, group=dist.Group("dp", _mesh))
            return out._array

        x = jnp.arange(8.0).reshape(2, 2, 2)  # [dp, mp, pp] worth of data
        g = shard_map(f, mesh=_mesh, in_specs=PartitionSpec("dp"),
                      out_specs=PartitionSpec("dp"), check_vma=False)
        out = g(x)
        ref = np.asarray(x).sum(0, keepdims=True).repeat(2, 0)
        np.testing.assert_allclose(np.asarray(out), ref)

    def test_all_gather(self, _mesh):
        from jax import shard_map

        def f(x):
            out = dist.all_gather(paddle.Tensor(x), group="mp")
            return out._array

        x = jnp.arange(4.0).reshape(4, 1)
        g = shard_map(f, mesh=_mesh, in_specs=PartitionSpec(("mp",)),
                      out_specs=PartitionSpec(None, "mp"), check_vma=False)
        out = np.asarray(g(x))
        # gathered stack: [mp_size, local_rows, 1] per shard
        assert out.shape == (2, 4, 1)
        np.testing.assert_allclose(np.sort(out.ravel()), [0, 0, 1, 1, 2, 2, 3, 3])

    def test_reduce_scatter(self, _mesh):
        from jax import shard_map

        def f(x):
            out = dist.reduce_scatter(paddle.Tensor(x), group="dp")
            return out._array

        x = jnp.ones((8, 4))
        g = shard_map(f, mesh=_mesh, in_specs=PartitionSpec(),
                      out_specs=PartitionSpec("dp"), check_vma=False)
        out = np.asarray(g(x))
        assert out.shape == (8, 4)
        np.testing.assert_allclose(out, 2.0)  # each row summed over 2 dp ranks

    def test_eager_collectives_are_identity(self, _mesh):
        t = paddle.to_tensor(rand(4))
        before = t.numpy().copy()
        dist.all_reduce(t)
        np.testing.assert_allclose(t.numpy(), before)
        got = []
        dist.all_gather(got, t)
        assert len(got) == 1
        dist.barrier()


class TestTPLayers:
    def test_column_parallel_linear(self, _mesh):
        l = dist.mpu.ColumnParallelLinear(8, 16, gather_output=True)
        x = rand(4, 8)
        ref = x @ l.weight.numpy() + l.bias.numpy()
        np.testing.assert_allclose(l(paddle.to_tensor(x)).numpy(), ref,
                                   rtol=1e-4, atol=1e-5)
        # weight is sharded over mp on dim 1
        pl = dist.get_placements(l.weight, _mesh)
        assert pl[list(_mesh.axis_names).index("mp")] == dist.Shard(1)

    def test_row_parallel_linear(self, _mesh):
        l = dist.mpu.RowParallelLinear(16, 8, input_is_parallel=False)
        x = rand(4, 16)
        ref = x @ l.weight.numpy() + l.bias.numpy()
        np.testing.assert_allclose(l(paddle.to_tensor(x)).numpy(), ref,
                                   rtol=1e-4, atol=1e-5)

    def test_vocab_parallel_embedding(self, _mesh):
        emb = dist.mpu.VocabParallelEmbedding(16, 8)
        idx = paddle.to_tensor(np.array([0, 5, 15]))
        np.testing.assert_allclose(emb(idx).numpy(), emb.weight.numpy()[[0, 5, 15]],
                                   rtol=1e-6)

    def test_parallel_cross_entropy(self, _mesh):
        ce = dist.mpu.ParallelCrossEntropy()
        logits = rand(4, 10)
        labels = np.array([1, 2, 3, 4])
        out = ce(paddle.to_tensor(logits), paddle.to_tensor(labels))
        s = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        ref = -np.log(s[np.arange(4), labels])
        np.testing.assert_allclose(out.numpy()[:, 0], ref, rtol=1e-5)

    def test_tp_mlp_grad_matches_serial(self, _mesh):
        """Column->Row parallel MLP forward/backward == serial."""
        paddle.seed(3)
        col = dist.mpu.ColumnParallelLinear(8, 16, gather_output=False)
        row = dist.mpu.RowParallelLinear(16, 8, input_is_parallel=True)
        x = paddle.to_tensor(rand(4, 8))
        out = row(F.relu(col(x)))
        loss = (out * out).sum()
        loss.backward()
        # serial reference
        w1, b1 = col.weight.numpy(), col.bias.numpy()
        w2, b2 = row.weight.numpy(), row.bias.numpy()
        h = np.maximum(x.numpy() @ w1 + b1, 0)
        ref_out = h @ w2 + b2
        np.testing.assert_allclose(out.numpy(), ref_out, rtol=1e-4, atol=1e-4)
        assert col.weight.grad is not None and row.weight.grad is not None


class TestSharding:
    def test_group_sharded_levels(self, _mesh):
        mesh = dist.build_mesh({"sharding": 8})
        dist.set_global_mesh(mesh)
        import paddle_tpu.optimizer as opt

        model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 16))
        o = opt.AdamW(learning_rate=0.01, parameters=model.parameters())
        model, o = dist.group_sharded_parallel(model, o, level="p_g_os")
        # params now sharded over sharding axis on dim0 (when divisible)
        p0 = model[0].weight
        spec = p0._array.sharding.spec
        assert spec[0] == "sharding"
        # a step still works and matches densely-computed update direction
        x = paddle.to_tensor(rand(4, 16))
        loss = (model(x) ** 2).sum()
        loss.backward()
        o.step()
        o.clear_grad()
        # accumulators inherited the sharding
        st = o._accumulators[id(p0)]
        assert any(getattr(v, "sharding", None) is not None
                   and v.sharding.spec == spec for v in st.values()
                   if hasattr(v, "ndim") and v.ndim == 2)

    def test_stage1_only_shards_states(self, _mesh):
        mesh = dist.build_mesh({"sharding": 8})
        dist.set_global_mesh(mesh)
        import paddle_tpu.optimizer as opt

        model = nn.Linear(16, 16)
        o = opt.Adam(learning_rate=0.01, parameters=model.parameters())
        model, o = dist.group_sharded_parallel(model, o, level="os")
        # params NOT sharded at stage 1
        sh = model.weight._array.sharding
        spec = getattr(sh, "spec", None)
        assert spec is None or len(spec) == 0 or spec[0] is None


class TestDataParallel:
    def test_wrapper_forward(self, _mesh):
        m = nn.Linear(4, 2)
        dp = dist.DataParallel(m)
        x = rand(8, 4)
        np.testing.assert_allclose(dp(paddle.to_tensor(x)).numpy(),
                                   x @ m.weight.numpy() + m.bias.numpy(),
                                   rtol=1e-4, atol=1e-5)
        with dp.no_sync():
            dp(paddle.to_tensor(x))
        assert len(dp.state_dict()) == 2

    def test_dp_training_matches_serial(self, _mesh):
        """DP over the mesh == serial single-device training."""
        import paddle_tpu.optimizer as opt

        def run(parallel):
            paddle.seed(11)
            m = nn.Sequential(nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 2))
            if parallel:
                m_run = dist.DataParallel(m)
            else:
                m_run = m
            o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
            np.random.seed(5)
            for _ in range(3):
                x = paddle.to_tensor(rand(8, 8))
                y = paddle.to_tensor(np.random.randint(0, 2, 8))
                loss = F.cross_entropy(m_run(x), y)
                loss.backward()
                o.step(); o.clear_grad()
            return m[0].weight.numpy()

        np.testing.assert_allclose(run(True), run(False), rtol=1e-4, atol=1e-5)


class TestPipeline:
    def test_pipeline_apply_matches_serial(self, _mesh):
        """shard_map+ppermute GPipe == serial layer stack."""
        n_stages = 2
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (n_stages, 16, 16)) * 0.1

        def block(params, x):
            return jnp.tanh(x @ params["w"])

        params = {"w": w}
        x = np.random.randn(8, 16).astype(np.float32)
        mesh = dist.build_mesh({"pp": 2, "rest": 4})
        dist.set_global_mesh(mesh)
        y = dist.pipeline_apply(block, params, jnp.asarray(x),
                                n_microbatches=4, mesh=mesh, axis="pp")
        ref = x
        for s in range(n_stages):
            ref = np.tanh(ref @ np.asarray(w[s]))
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)

    def test_pipeline_apply_differentiable(self, _mesh):
        mesh = dist.build_mesh({"pp": 2, "rest": 4})
        dist.set_global_mesh(mesh)
        w = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8)) * 0.1
        x = jnp.ones((4, 8))

        def loss_fn(w_):
            y = dist.pipeline_apply(lambda p, a: jnp.tanh(a @ p["w"]),
                                    {"w": w_}, x, n_microbatches=2,
                                    mesh=mesh, axis="pp")
            return (y ** 2).sum()

        g = jax.grad(loss_fn)(w)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).sum() > 0

    def test_pipeline_parallel_train_batch(self, _mesh):
        import paddle_tpu.optimizer as opt

        model = dist.PipelineLayer(
            layers=[dist.LayerDesc(nn.Linear, 8, 8),
                    dist.LayerDesc(nn.ReLU),
                    dist.LayerDesc(nn.Linear, 8, 4)],
            num_stages=1)
        strategy = dist.DistributedStrategy()
        strategy.pipeline_configs["accumulate_steps"] = 2
        pp = dist.PipelineParallel(model, strategy=strategy)
        o = opt.SGD(learning_rate=0.05, parameters=model.parameters())
        x = paddle.to_tensor(rand(8, 8))
        y = paddle.to_tensor(np.random.randint(0, 4, 8))
        l0 = float(pp.train_batch([x, y], o).numpy())
        l1 = float(pp.train_batch([x, y], o).numpy())
        assert l1 < l0


class TestSequenceParallel:
    def test_split_gather_roundtrip(self, _mesh):
        mesh = dist.build_mesh({"sep": 2, "rest": 4})
        dist.set_global_mesh(mesh)
        x = paddle.to_tensor(rand(2, 8, 4))
        s = dist.split_seq(x)
        assert s._array.sharding.spec[1] == "sep"
        g = dist.gather_seq(s)
        np.testing.assert_allclose(g.numpy(), x.numpy())

    def test_ulysses_alltoall_annotation(self, _mesh):
        mesh = dist.build_mesh({"sep": 2, "rest": 4})
        dist.set_global_mesh(mesh)
        q = paddle.to_tensor(rand(2, 8, 4, 16))  # [b, s, h, d]
        q2, k2, v2 = dist.sep_attention_context(q, q, q)
        np.testing.assert_allclose(q2.numpy(), q.numpy())
        assert q2._array.sharding.spec[2] == "sep"  # heads now sharded


class TestMoE:
    def test_moe_forward_and_aux(self, _mesh):
        moe = dist.MoELayer(d_model=8, num_experts=4, d_hidden=16, topk=2)
        x = paddle.to_tensor(rand(2, 6, 8))
        y = moe(x)
        assert y.shape == [2, 6, 8]
        assert moe.aux_loss is not None
        assert float(moe.aux_loss.numpy()) > 0

    def test_moe_expert_list_path(self, _mesh):
        experts = [nn.Linear(8, 8) for _ in range(2)]
        moe = dist.MoELayer(d_model=8, experts=experts, topk=1,
                            gate=dist.SwitchGate(8, 2))
        y = moe(paddle.to_tensor(rand(4, 8)))
        assert y.shape == [4, 8]

    def test_moe_grad(self, _mesh):
        moe = dist.MoELayer(d_model=8, num_experts=2, d_hidden=8, topk=1)
        x = paddle.to_tensor(rand(4, 8))
        loss = (moe(x) ** 2).sum() + moe.aux_loss
        loss.backward()
        assert moe.w1.grad is not None
        assert moe.gate.gate_weight.grad is not None

    def test_sorted_dispatch_matches_dense(self, _mesh):
        """Round-2 VERDICT item 9: the sort-based dispatch must reproduce
        the dense [T,E,C] one-hot form exactly — expert inputs, combine,
        capacity drops, and aux loss."""
        from paddle_tpu.parallel.moe import (moe_combine_sorted,
                                             moe_dispatch,
                                             moe_dispatch_sorted)

        rng = np.random.default_rng(3)
        T, D, E, K = 32, 8, 4, 2
        h = paddle.to_tensor(rng.standard_normal((T, D)).astype("float32"))
        logits = rng.standard_normal((T, E)).astype("float32")
        probs = paddle.to_tensor(
            np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))
        # capacity_factor 0.5 forces real drops, exercising arrival order
        for cf in (1.25, 0.5):
            disp, combine, aux_d = moe_dispatch(h, probs, E, K, cf)
            ein_dense = np.einsum("tec,td->ecd", np.asarray(disp._array),
                                  np.asarray(h._array))
            ein, dst, w, aux_s = moe_dispatch_sorted(h, probs, E, K, cf)
            np.testing.assert_allclose(np.asarray(ein._array), ein_dense,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(aux_d._array),
                                       float(aux_s._array), rtol=1e-5)
            out_dense = np.einsum("tec,ecd->td",
                                  np.asarray(combine._array), ein_dense)
            y = moe_combine_sorted(ein, dst, w, T, K)
            np.testing.assert_allclose(np.asarray(y._array), out_dense,
                                       rtol=1e-5, atol=1e-6)

    def test_sorted_dispatch_compiled_memory(self, _mesh):
        """At a shape where the dense slot one-hot alone would be ~335 MB,
        the sorted dispatch's whole compiled temp footprint must stay an
        order of magnitude under it."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.parallel.moe import moe_dispatch_sorted

        T, E, D, K = 4096, 64, 64, 2
        cap = int(1.25 * T * K / E)
        dense_slot_bytes = T * K * E * cap * 4

        def run(hh, pp):
            ein, dst, w, aux = moe_dispatch_sorted(
                paddle.Tensor(hh), paddle.Tensor(pp), E, K, 1.25)
            return ein._array.sum()

        mem = jax.jit(run).lower(
            jnp.zeros((T, D)), jnp.ones((T, E)) / E
        ).compile().memory_analysis().temp_size_in_bytes
        assert mem < dense_slot_bytes / 10, (mem, dense_slot_bytes)


class TestFleet:
    def test_fleet_init_and_wrap(self):
        strategy = dist.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 1, "sharding_degree": 2,
                                   "sep_degree": 1}
        dist.fleet.init(is_collective=True, strategy=strategy)
        hcg = dist.fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_sharding_parallel_world_size() == 2
        model = nn.Linear(4, 4)
        wrapped = dist.fleet.distributed_model(model)
        import paddle_tpu.optimizer as opt

        o = dist.fleet.distributed_optimizer(
            opt.Adam(learning_rate=0.01, parameters=model.parameters()))
        x = paddle.to_tensor(rand(8, 4))
        loss = (wrapped(x) ** 2).sum()
        loss.backward()
        o.step()
        assert dist.fleet.worker_index() == 0
