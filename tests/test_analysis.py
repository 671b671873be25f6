"""paddle_tpu.analysis: the jaxpr lint pipeline.

Positive AND negative cases per rule: each hazard is exercised with a
graph that fires the rule and a near-identical clean graph that must not.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.analysis as analysis
from paddle_tpu.analysis import LintError, Severity


def diags(report, rule):
    return [d for d in report if d.rule == rule]


# ---------------------------------------------------------------------------
# TPU101: tile alignment
# ---------------------------------------------------------------------------

class TestTileAlignment:
    def test_misaligned_matmul_flagged(self):
        def f(x, w):
            return x @ w

        r = analysis.analyze(f, jnp.ones((100, 100), jnp.float32),
                             jnp.ones((100, 100), jnp.float32),
                             rules=["TPU101"])
        found = diags(r, "TPU101")
        assert found, "misaligned 100x100 matmul must be flagged"
        assert any("contracting" in d.message for d in found)

    def test_aligned_matmul_clean(self):
        def f(x, w):
            return x @ w

        r = analysis.analyze(f, jnp.ones((128, 256), jnp.float32),
                             jnp.ones((256, 512), jnp.float32),
                             rules=["TPU101"])
        assert not diags(r, "TPU101")

    def test_bf16_uses_16_row_tile(self):
        def f(x, w):
            return x @ w

        # 8 rows is fine for f32 but HALF a bf16 sublane tile
        r = analysis.analyze(f, jnp.ones((24, 128), jnp.bfloat16),
                             jnp.ones((128, 128), jnp.bfloat16),
                             rules=["TPU101"])
        found = diags(r, "TPU101")
        assert any("16-wide" in d.message for d in found)

    def test_repeated_sites_deduped(self):
        def f(x, w):
            for _ in range(3):
                x = x @ w
            return x

        r = analysis.analyze(f, jnp.ones((100, 100)), jnp.ones((100, 100)),
                             rules=["TPU101"])
        per_msg = {}
        for d in diags(r, "TPU101"):
            per_msg[d.message] = per_msg.get(d.message, 0) + 1
        assert all(c == 1 for c in per_msg.values())
        assert any("3 sites" in m for m in per_msg)


# ---------------------------------------------------------------------------
# TPU102: kernel constraint registry
# ---------------------------------------------------------------------------

class TestKernelConstraints:
    def _fa(self):
        import importlib

        return importlib.import_module(
            "paddle_tpu.kernels.flash_attention")

    def test_misaligned_head_dim_flagged(self):
        fa = self._fa()

        def att(q, k, v):
            return fa._fwd_pallas(q, k, v, False, 1.0)[0]

        q = jax.ShapeDtypeStruct((4, 64, 96), jnp.float32)
        r = analysis.analyze(att, q, q, q, rules=["TPU102"])
        found = diags(r, "TPU102")
        assert found and "head_dim 96" in found[0].message
        assert found[0].severity == Severity.WARNING

    def test_gqa_mismatch_is_error(self):
        fa = self._fa()

        def att(q, k, v):
            return fa._fwd_pallas(q, k, v, False, 1.0)[0]

        q = jax.ShapeDtypeStruct((3, 64, 128), jnp.float32)
        kv = jax.ShapeDtypeStruct((2, 64, 128), jnp.float32)
        r = analysis.analyze(att, q, kv, kv, rules=["TPU102"])
        errs = [d for d in diags(r, "TPU102")
                if d.severity == Severity.ERROR]
        assert errs and "Hq % Hkv" in errs[0].message

    def test_aligned_kernel_clean(self):
        fa = self._fa()

        def att(q, k, v):
            return fa._fwd_pallas(q, k, v, False, 1.0)[0]

        q = jax.ShapeDtypeStruct((4, 64, 128), jnp.float32)
        r = analysis.analyze(att, q, q, q, rules=["TPU102"])
        assert not diags(r, "TPU102")

    def test_generic_kernel_name_needs_matching_source(self):
        # a foreign module reusing the generic `_fwd_kernel` name (as
        # swiglu did before joining the registry under unique names)
        # must not inherit flash_attention's checker: the source hint
        # gates the match
        from paddle_tpu.kernels.constraints import constraint_for_kernel_fn

        assert constraint_for_kernel_fn(
            "_fwd_kernel",
            "_fwd_kernel at .../kernels/swiglu.py:20") is None
        c = constraint_for_kernel_fn(
            "_fwd_kernel",
            "_fwd_kernel at .../kernels/flash_attention.py:98")
        assert c is not None and c.name == "flash_attention"

    def test_registry_is_shared_source_of_truth(self):
        from paddle_tpu import kernels
        from paddle_tpu.kernels import flash_attention as _  # noqa: F401

        c = kernels.KERNEL_CONSTRAINTS["flash_attention"]
        import importlib

        fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
        assert c.blocks["block_q"] == fa.BLOCK_Q
        assert c.blocks["block_k"] == fa.BLOCK_K
        assert kernels.constraint_for_kernel_fn("_fwd_kernel") is c

    def test_rope_and_swiglu_constraints_registered(self):
        """swiglu joins the TPU102 registry with its real kernel fns,
        rope as the documented (pure-jnp) layout contract."""
        from paddle_tpu.kernels import swiglu
        from paddle_tpu.kernels.constraints import (
            KERNEL_CONSTRAINTS, constraint_for_kernel_fn)

        assert "rope" in KERNEL_CONSTRAINTS
        assert "swiglu" in KERNEL_CONSTRAINTS
        c = constraint_for_kernel_fn("_swiglu_fwd_kernel", "swiglu.py")
        assert c.name == "swiglu"
        assert c.blocks["block"] == swiglu._BLOCK
        # misaligned K fires the swiglu checker
        warn = c.check([(256, 100), (100, 512), (100, 512)],
                       ["bfloat16"] * 3)
        assert any("K=100" in m for _, m in warn)


class TestPrefixPrefillConstraint:
    """TPU102 self-check for the ragged paged prefix-prefill kernel
    (ISSUE 4): the registered KernelConstraint must fire on a BLOCK_S
    that is not a whole number of KV pages — the shape the wrapper's
    fitting helper never produces, but an explicit override can."""

    def _trace(self, block_s):
        from paddle_tpu.kernels import prefix_prefill as pp

        def att(q, ks, vs, kc, vc, tbl, plens, slens):
            return pp.prefix_prefill_attention(
                q, ks, vs, kc, vc, tbl, plens, slens, block_s=block_s)

        f32 = jnp.float32
        return analysis.analyze(
            att,
            jax.ShapeDtypeStruct((1, 16, 2, 128), f32),   # q
            jax.ShapeDtypeStruct((1, 16, 1, 128), f32),   # k_suf
            jax.ShapeDtypeStruct((1, 16, 1, 128), f32),   # v_suf
            jax.ShapeDtypeStruct((4, 1, 8, 128), f32),    # key pool
            jax.ShapeDtypeStruct((4, 1, 8, 128), f32),    # value pool
            jax.ShapeDtypeStruct((1, 2), jnp.int32),      # tables
            jax.ShapeDtypeStruct((1,), jnp.int32),        # prefix lens
            jax.ShapeDtypeStruct((1,), jnp.int32),        # suffix lens
            rules=["TPU102"])

    def test_misaligned_block_s_flagged(self):
        # block_s=4 divides the 16-token suffix but is HALF a KV page:
        # the streaming grid degrades to sub-page DMAs
        found = diags(self._trace(block_s=4), "TPU102")
        assert found and any("BLOCK_S 4" in d.message for d in found)
        assert all(d.severity == Severity.WARNING for d in found)

    def test_page_granular_block_s_clean(self):
        assert not diags(self._trace(block_s=8), "TPU102")

    def test_registry_blocks_match_module(self):
        from paddle_tpu import kernels
        from paddle_tpu.kernels import prefix_prefill as pp

        c = kernels.KERNEL_CONSTRAINTS["prefix_prefill"]
        assert c.blocks["block_q"] == pp.BLOCK_Q
        assert c.blocks["block_s"] == pp.BLOCK_S
        assert "_prefix_prefill_kernel" in c.kernel_fns


# ---------------------------------------------------------------------------
# TPU105: fusion-miss (dispatch-bound loop bodies)
# ---------------------------------------------------------------------------

class TestFusionMiss:
    """TPU105: a scan body lowering to more distinct small-output
    pallas/dot launches than the fusion budget is dispatch-bound (the
    decode-step shape)."""

    @staticmethod
    def _scan_body_graph(n_dots, size=8):
        # n_dots dots of DISTINCT shapes, each with a tiny output,
        # inside a scan — a synthetic dispatch-bound decode step
        ws = [jnp.ones((size + i, size + i), jnp.float32)
              for i in range(n_dots)]

        def f(x):
            def body(c, _):
                out = 0.0
                for i, w in enumerate(ws):
                    v = jnp.ones((1, size + i), jnp.float32) * c
                    out = out + jnp.sum(v @ w)
                return out, out

            c, _ = jax.lax.scan(body, x, None, length=4)
            return c

        return analysis.analyze(f, jnp.asarray(1.0, jnp.float32),
                                rules=["TPU105"])

    def test_many_distinct_small_launches_flagged(self):
        found = diags(self._scan_body_graph(9), "TPU105")
        assert found and found[0].severity == Severity.WARNING
        assert "distinct small-output kernel launches" in found[0].message
        assert "fuse" in (found[0].hint or "")

    def test_within_budget_clean(self):
        assert not diags(self._scan_body_graph(3), "TPU105")

    def test_repeated_layers_count_once(self):
        """A 32-layer stack of IDENTICAL shapes is one distinct launch
        per op, not 32 — depth must not fire the rule."""
        w = jnp.ones((8, 8), jnp.float32)

        def f(x):
            def body(c, _):
                out = c
                for _ in range(32):   # same shapes every "layer"
                    out = jnp.sum(jnp.ones((1, 8), jnp.float32) * out @ w)
                return out, out

            c, _ = jax.lax.scan(body, x, None, length=4)
            return c

        r = analysis.analyze(f, jnp.asarray(1.0, jnp.float32),
                             rules=["TPU105"])
        assert not diags(r, "TPU105")

    def test_big_outputs_not_counted(self):
        """Launches whose results are large do real bandwidth work —
        they are not fusion misses."""
        ws = [jnp.ones((512, 600 + 8 * i), jnp.float32)
              for i in range(9)]

        def f(x):
            def body(c, _):
                out = 0.0
                for w in ws:  # each output ~1.2 MiB
                    out = out + jnp.sum(
                        (jnp.ones((512, 512), jnp.float32) * c) @ w)
                return out, out

            c, _ = jax.lax.scan(body, x, None, length=2)
            return c

        r = analysis.analyze(f, jnp.asarray(1.0, jnp.float32),
                             rules=["TPU105"])
        assert not diags(r, "TPU105")

    def test_outside_loop_not_flagged(self):
        ws = [jnp.ones((8 + i, 8 + i), jnp.float32) for i in range(9)]

        def f(x):
            out = 0.0
            for i, w in enumerate(ws):
                out = out + jnp.sum(jnp.ones((1, 8 + i),
                                             jnp.float32) * x @ w)
            return out

        r = analysis.analyze(f, jnp.asarray(1.0, jnp.float32),
                             rules=["TPU105"])
        assert not diags(r, "TPU105")

    def test_decode_step_shape_fires(self):
        """The real thing: a tiny paged decode step inside a scan trips
        TPU105."""
        import dataclasses

        from paddle_tpu.kernels.decode_attention import (
            paged_decode_attention)
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama import (_make_decode_step,
                                             make_paged_kv_helpers)

        # intermediate != vocab so the gate/up dot shape stays DISTINCT
        # from the lm-head dot: TPU105 counts by (primitive, shapes),
        # and since rope builds its tables with a broadcast multiply
        # (no dot_general) the tiny() default would land exactly on the
        # 6-launch budget instead of over it
        cfg = dataclasses.replace(LlamaConfig.tiny(),
                                  num_key_value_heads=2,
                                  intermediate_size=96)
        paddle.seed(3)
        params = dict(LlamaForCausalLM(cfg).raw_state())
        b, bs, W = 2, 8, 2
        nkv, dh = cfg.num_key_value_heads, cfg.head_dim
        tables = jnp.asarray(np.arange(b * W).reshape(b, W) + 1,
                             jnp.int32)
        pools = lambda: [jnp.zeros((b * W + 1, nkv, bs, dh),
                                   jnp.float32)
                         for _ in range(cfg.num_hidden_layers)]
        _, kv_write = make_paged_kv_helpers(b, 0, nkv, dh, bs, tables)
        step = _make_decode_step(
            cfg, b, kv_write=kv_write,
            kv_attend=lambda q1, kc, vc, lens: paged_decode_attention(
                q1, kc, vc, tables, lens))

        def chunk(tok, lens, kcs, vcs):
            def body(carry, _):
                tok, lens, kcs, vcs = carry
                logits, kcs, vcs = step(params, kcs, vcs,
                                        tok[:, None], lens)
                return (jnp.argmax(logits, -1).astype(tok.dtype),
                        lens + 1, kcs, vcs), ()

            carry, _ = jax.lax.scan(
                body, (tok, lens, kcs, vcs), None, length=2)
            return carry[0]

        tok = jnp.ones((b,), jnp.int32)
        lens = jnp.full((b,), 3, jnp.int32)
        r = analysis.analyze(chunk, tok, lens, pools(), pools(),
                             rules=["TPU105"])
        assert diags(r, "TPU105")


# ---------------------------------------------------------------------------
# TPU201: recompilation risk
# ---------------------------------------------------------------------------

class TestRecompileRisk:
    def test_python_scalar_arg_flagged(self):
        def f(x, lr):
            return x * lr

        r = analysis.analyze(f, jnp.ones((8, 128)), 0.77,
                             rules=["TPU201"])
        found = diags(r, "TPU201")
        assert found and "retraces" in found[0].message

    def test_array_scalar_clean(self):
        def f(x, lr):
            return x * lr

        r = analysis.analyze(f, jnp.ones((8, 128)), jnp.asarray(0.77),
                             rules=["TPU201"])
        assert not diags(r, "TPU201")

    def test_int_scalar_arg_flagged_in_float_math(self):
        # step counters are the classic recompile key: an int argument
        # lands in the graph as a float literal and must still match
        def f(x, step):
            return x * step

        r = analysis.analyze(f, jnp.ones((8, 128), jnp.float32), 3,
                             rules=["TPU201"])
        assert diags(r, "TPU201")

    def test_float_arg_does_not_match_int_literal(self):
        # 2.5 truncating into the unrelated int literal 2 would be a
        # false positive
        def f(x, s):
            return (x * 2).astype(jnp.int32)

        r = analysis.analyze(f, jnp.ones((8, 128), jnp.int32), 2.5,
                             rules=["TPU201"])
        assert not diags(r, "TPU201")

    def test_direct_graph_generic_literal_scan(self):
        # Graph built WITHOUT the tracer has no argument info; the rule
        # falls back to flagging suspicious scalar literals generically
        from paddle_tpu.analysis import Graph, Pipeline

        jxp = jax.make_jaxpr(lambda x: x * 0.77)(
            jax.ShapeDtypeStruct((8, 128), jnp.float32))
        report = Pipeline(rules=[analysis.RULES["TPU201"]()]).run(
            Graph(jxp, name="direct"))
        assert diags(report, "TPU201")

    def test_closure_constant_not_flagged(self):
        # rope-theta-style derived constants are stable across calls —
        # only call ARGUMENTS are recompile keys
        theta = 1.0 / 10000.0 ** 0.3

        def f(x):
            return x * theta

        r = analysis.analyze(f, jnp.ones((8, 128)), rules=["TPU201"])
        assert not diags(r, "TPU201")


# ---------------------------------------------------------------------------
# TPU202: const bloat
# ---------------------------------------------------------------------------

class TestConstBloat:
    def test_large_closure_const_flagged(self):
        big = jnp.ones((512, 600), jnp.float32)  # 1.2 MiB

        def f(x):
            return x @ big

        r = analysis.analyze(f, jnp.ones((8, 512)), rules=["TPU202"])
        found = diags(r, "TPU202")
        assert found and "captured" in found[0].message

    def test_layer_params_ride_as_inputs(self):
        # a Layer's weights must NOT read as captured constants: the
        # tracer threads them as inputs like jit/api.py does
        lin = paddle.nn.Linear(512, 600)
        r = analysis.analyze(lin, paddle.ones([8, 512]), rules=["TPU202"])
        assert not diags(r, "TPU202")


# ---------------------------------------------------------------------------
# TPU301: silent dtype promotion
# ---------------------------------------------------------------------------

class TestDtypePromotion:
    def test_upcast_feeding_compute_flagged(self):
        def f(x):
            return x.astype(jnp.float32) * 2.0 + 1.0

        r = analysis.analyze(f, jnp.ones((16, 128), jnp.bfloat16),
                             rules=["TPU301"])
        found = diags(r, "TPU301")
        assert found and "float32 upcast" in found[0].message

    def test_mixed_precision_matmul_flagged(self):
        def f(x, w):
            return jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        r = analysis.analyze(f, jnp.ones((16, 128), jnp.bfloat16),
                             jnp.ones((128, 128), jnp.float32),
                             rules=["TPU301"])
        found = diags(r, "TPU301")
        assert found and "mixed-precision matmul" in found[0].message

    def test_pure_bf16_clean(self):
        def f(x, w):
            y = jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return y.astype(jnp.bfloat16)

        r = analysis.analyze(f, jnp.ones((16, 128), jnp.bfloat16),
                             jnp.ones((128, 128), jnp.bfloat16),
                             rules=["TPU301"])
        assert not diags(r, "TPU301")

    def test_upcast_into_reduction_clean(self):
        # fp32 accumulation of a reduction is deliberate numerics
        def f(x):
            return jnp.sum(x.astype(jnp.float32))

        r = analysis.analyze(f, jnp.ones((16, 128), jnp.bfloat16),
                             rules=["TPU301"])
        assert not diags(r, "TPU301")


# ---------------------------------------------------------------------------
# TPU401: collective hygiene (virtual 8-device CPU mesh from conftest)
# ---------------------------------------------------------------------------

class TestCollectives:
    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()), ("dp",))

    def _smap(self, fn, mesh):
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        return shard_map(fn, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"), check_vma=False)

    def test_dead_collective_flagged(self):
        mesh = self._mesh()

        def f(x):
            _dead = jax.lax.psum(x * 3.0, "dp")
            return x * 2.0

        r = analysis.analyze(self._smap(f, mesh), jnp.ones((8, 128)),
                             rules=["TPU401"], mesh_axes=("dp",))
        found = diags(r, "TPU401")
        assert found and "never used" in found[0].message

    def test_duplicate_collective_flagged(self):
        mesh = self._mesh()

        def f(x):
            y = x * 2.0
            return jax.lax.psum(y, "dp") + jax.lax.psum(y, "dp")

        r = analysis.analyze(self._smap(f, mesh), jnp.ones((8, 128)),
                             rules=["TPU401"], mesh_axes=("dp",))
        found = diags(r, "TPU401")
        assert any("duplicate" in d.message for d in found)

    def test_axis_outside_mesh_is_error(self):
        mesh = self._mesh()

        def f(x):
            return jax.lax.psum(x * 1.0, "dp")

        r = analysis.analyze(self._smap(f, mesh), jnp.ones((8, 128)),
                             rules=["TPU401"], mesh_axes=("tp", "pp"))
        errs = [d for d in diags(r, "TPU401")
                if d.severity == Severity.ERROR]
        assert errs and "not in the mesh axes" in errs[0].message

    def test_used_collective_on_declared_axis_clean(self):
        mesh = self._mesh()

        def f(x):
            return jax.lax.psum(x * 1.0, "dp")

        r = analysis.analyze(self._smap(f, mesh), jnp.ones((8, 128)),
                             rules=["TPU401"], mesh_axes=("dp",))
        assert not diags(r, "TPU401")

    # -- unquantized large-collective payloads (EQuARX candidates) ------

    def test_large_unquantized_collective_flagged(self):
        """A float psum over > max_collective_bytes fires with the
        quantize hint; the same payload under the threshold is clean."""
        mesh = self._mesh()

        def f(x):
            return jax.lax.psum(x * 1.0, "dp")

        # per-SHARD payload is what the traced jaxpr sees: (1, 64, 128)
        # f32 = 32 KiB on each of the 8 dp shards
        big = jnp.ones((8, 64, 128), jnp.float32)
        r = analysis.analyze(
            self._smap(f, mesh), big, rules=["TPU401"],
            mesh_axes=("dp",),
            rule_config={"max_collective_bytes": 1 << 14})
        found = [d for d in diags(r, "TPU401")
                 if "float payload" in d.message]
        assert found and "EQuARX" in (found[0].hint or "")
        # default threshold (1 MiB) does not fire at this size
        r2 = analysis.analyze(self._smap(f, mesh), big,
                              rules=["TPU401"], mesh_axes=("dp",))
        assert not [d for d in diags(r2, "TPU401")
                    if "float payload" in d.message]

    def test_bf16_payload_counts_as_float(self):
        """bfloat16 is an ml_dtypes extension type numpy does NOT class
        as floating — but bf16 activations/gradients are exactly the
        payloads this check exists for (the serving o-proj all-gather
        is bf16). Regression: the size check must fire on bf16."""
        mesh = self._mesh()

        def f(x):
            return jax.lax.psum(x * jnp.bfloat16(1.0), "dp")

        big = jnp.ones((8, 64, 128), jnp.bfloat16)   # 16 KiB/shard
        r = analysis.analyze(
            self._smap(f, mesh), big, rules=["TPU401"],
            mesh_axes=("dp",),
            rule_config={"max_collective_bytes": 1 << 13})
        found = [d for d in diags(r, "TPU401")
                 if "float payload" in d.message]
        assert found, "bf16 payload must count as float bytes"
        # a one-shot top-level collective is an INFO-grade candidate;
        # loop bodies (per-iteration cost) escalate to WARNING — the
        # serving-decode test below asserts the escalated side
        assert found[0].severity is Severity.INFO

    def test_int8_collective_payload_never_fires(self):
        """Already-quantized payloads are the lint's GOAL state: an int8
        all-gather of any size passes (its f32 scale sidecar is tiny)."""
        mesh = self._mesh()

        def f(q, sc):
            g = jax.lax.all_gather(q, "dp", axis=0, tiled=True)
            s = jax.lax.all_gather(sc, "dp", axis=0, tiled=True)
            return g.astype(jnp.float32) * s[:, None]

        r = analysis.analyze(
            self._smap2(f, mesh),
            jnp.ones((8, 4096), jnp.int8), jnp.ones((8,), jnp.float32),
            rules=["TPU401"], mesh_axes=("dp",),
            rule_config={"max_collective_bytes": 1 << 10})
        assert not [d for d in diags(r, "TPU401")
                    if "float payload" in d.message]

    def test_zero_threshold_disables_size_check(self):
        mesh = self._mesh()

        def f(x):
            return jax.lax.psum(x * 1.0, "dp")

        r = analysis.analyze(
            self._smap(f, mesh), jnp.ones((8, 1024, 128), jnp.float32),
            rules=["TPU401"], mesh_axes=("dp",),
            rule_config={"max_collective_bytes": 0})
        assert not [d for d in diags(r, "TPU401")
                    if "float payload" in d.message]

    def test_serving_decode_all_gather_is_first_customer(self):
        """The tensor-parallel serving decode step's per-layer o-proj
        activation all-gather (ISSUE 7) is visible to the size lint: at
        a tightened threshold the collective inside the decode scan
        fires WITH the loop-amplification note — the EQuARX follow-up's
        target. At the default 1 MiB threshold the tiny-model decode
        program stays clean (a [b, 1, H] bf16 gather is small)."""
        import dataclasses as _dc

        from jax.sharding import Mesh

        from paddle_tpu.models import LlamaConfig
        from paddle_tpu.models.llama import build_paged_generate

        cfg = _dc.replace(LlamaConfig.tiny(), num_key_value_heads=2)
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        del mesh  # build_paged_generate makes its own serving mesh
        fn = build_paged_generate(cfg, 2, 8, 4, 8, serving_mp=2)
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaForCausalLM

        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        p = dict(model.raw_state())
        tables = jnp.zeros((2, 2), jnp.int32)
        args = (p, jnp.ones((2, 8), jnp.int32),
                jnp.full((2,), 8, jnp.int32), tables,
                jax.random.PRNGKey(0), jnp.float32(1.0), jnp.float32(1.0))
        r = analysis.analyze(fn, *args, rules=["TPU401"],
                             mesh_axes=("mp",),
                             rule_config={"max_collective_bytes": 1})
        loud = [d for d in diags(r, "TPU401")
                if "float payload" in d.message]
        assert loud, "the o-proj all-gather must be visible to TPU401"
        assert any("loop body" in d.message for d in loud)
        # per-iteration cost escalates: in-loop findings carry the
        # rule's WARNING severity, not the top-level INFO grade
        assert all(d.severity is Severity.WARNING for d in loud
                   if "loop body" in d.message)
        r2 = analysis.analyze(fn, *args, rules=["TPU401"],
                              mesh_axes=("mp",))
        assert not [d for d in diags(r2, "TPU401")
                    if "float payload" in d.message]

    def _smap2(self, fn, mesh):
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        return shard_map(fn, mesh=mesh, in_specs=(P("dp"), P("dp")),
                         out_specs=P("dp"), check_vma=False)


# ---------------------------------------------------------------------------
# TPU501: host sync
# ---------------------------------------------------------------------------

class TestHostSync:
    def test_callback_in_loop_is_error(self):
        def f(xs):
            def body(c, x):
                jax.debug.print("c={c}", c=c)
                return c + x, c

            return jax.lax.scan(body, jnp.float32(0), xs)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU501"])
        found = diags(r, "TPU501")
        assert found and found[0].severity == Severity.ERROR
        assert "loop" in found[0].message

    def test_callback_outside_loop_is_warning(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU501"])
        found = diags(r, "TPU501")
        assert found and found[0].severity == Severity.WARNING

    def test_no_callbacks_clean(self):
        def f(xs):
            return jax.lax.scan(lambda c, x: (c + x, c),
                                jnp.float32(0), xs)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU501"])
        assert not diags(r, "TPU501")


# ---------------------------------------------------------------------------
# TPU601: checkpoint I/O smuggled into a jitted region
# ---------------------------------------------------------------------------

class TestCheckpointInJit:
    def test_checkpoint_callback_is_error(self):
        def save_checkpoint_shard(x):
            return np.asarray(x)  # stand-in for a host-side ckpt write

        def f(x):
            return jax.pure_callback(
                save_checkpoint_shard,
                jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU601"])
        found = diags(r, "TPU601")
        assert found and found[0].severity == Severity.ERROR
        assert "save_checkpoint_shard" in found[0].message

    def test_block_until_ready_callback_is_error(self):
        def block_until_ready_barrier(x):
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                block_until_ready_barrier,
                jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU601"])
        assert diags(r, "TPU601")

    def test_snake_case_save_name_flagged(self):
        def save_weights(x):  # \b alone would miss the underscore
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                save_weights, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU601"])
        assert diags(r, "TPU601")

    def test_innocent_callback_not_flagged(self):
        def log_metrics(x):  # host logging: TPU501's business, not 601's
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                log_metrics, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU601"])
        assert not diags(r, "TPU601")

    def test_direct_save_under_trace_raises_at_trace_time(self):
        import tempfile

        from paddle_tpu.resilience import (CheckpointError,
                                           CheckpointManager)

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)

            def f(x):
                mgr.save({"x": x})
                return x

            with pytest.raises(CheckpointError, match="TPU601"):
                analysis.analyze(f, jnp.ones((4,)))


# ---------------------------------------------------------------------------
# TPU602: trace/metrics emitters smuggled into a jitted region
# ---------------------------------------------------------------------------

class TestTraceEmitterInJit:
    def test_span_emitter_callback_is_error(self):
        def emit_span(x):  # stand-in for a host-side trace emit
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                emit_span, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU602"])
        found = diags(r, "TPU602")
        assert found and found[0].severity == Severity.ERROR
        assert "emit_span" in found[0].message

    def test_record_event_callback_is_error(self):
        def record_event(x):
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                record_event, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU602"])
        assert diags(r, "TPU602")

    def test_snake_case_trace_name_flagged(self):
        def trace_step(x):  # (?:\b|_) so snake_case matches
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                trace_step, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU602"])
        assert diags(r, "TPU602")

    def test_innocent_callback_not_flagged(self):
        def fetch_tokens(x):  # a host fetch: TPU501's business, not 602's
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                fetch_tokens, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU602"])
        assert not diags(r, "TPU602")

    def test_log_metrics_stays_501_business(self):
        # TPU601's negative case must stay negative for 602 too: plain
        # host logging is flagged generically by TPU501, not as a
        # trace-emitter error
        def log_metrics(x):
            return np.asarray(x)

        def f(x):
            return jax.pure_callback(
                log_metrics, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        r = analysis.analyze(f, jnp.ones((4,)), rules=["TPU602"])
        assert not diags(r, "TPU602")

    def test_live_span_under_trace_raises_at_trace_time(self):
        # the dynamic half of the guard: the recorder itself refuses to
        # emit while jax is tracing (message points at TPU602)
        from paddle_tpu.observability import Tracer, TraceUnderJitError

        tr = Tracer()

        def f(x):
            with tr.span("inside.jit"):
                return x + 1

        with pytest.raises(TraceUnderJitError, match="TPU602"):
            jax.jit(f)(jnp.ones((4,)))


# ---------------------------------------------------------------------------
# pipeline plumbing: severity policy, custom rules, jit integration
# ---------------------------------------------------------------------------

class TestPipeline:
    def test_report_raise_on_error(self):
        def f(xs):
            def body(c, x):
                jax.debug.print("c={c}", c=c)
                return c + x, c

            return jax.lax.scan(body, jnp.float32(0), xs)

        report = analysis.analyze(f, jnp.ones((4,)))
        with pytest.raises(LintError) as ei:
            report.raise_or_warn()
        assert ei.value.report.errors

    def test_severity_override_disables_rule(self):
        def f(x, w):
            return x @ w

        r = analysis.analyze(f, jnp.ones((100, 100)), jnp.ones((100, 100)),
                             severity_overrides={"TPU101": None})
        assert not diags(r, "TPU101")

    def test_severity_override_promotes_rule(self):
        def f(x, w):
            return x @ w

        r = analysis.analyze(
            f, jnp.ones((100, 100)), jnp.ones((100, 100)),
            severity_overrides={"TPU101": Severity.ERROR})
        assert any(d.severity == Severity.ERROR
                   for d in diags(r, "TPU101"))

    def test_custom_rule_registration(self):
        from paddle_tpu.analysis import Rule, register_rule
        from paddle_tpu.analysis.rules import RULES

        @register_rule
        class NoTanhRule(Rule):
            id = "TST901"
            name = "no-tanh"
            default_severity = Severity.WARNING

            def check(self, graph):
                for ctx in graph.eqns():
                    if ctx.primitive == "tanh":
                        yield self.diag("tanh spotted", where=ctx.path)

        try:
            r = analysis.analyze(lambda x: jnp.tanh(x), jnp.ones((4,)),
                                 rules=["TST901"])
            assert diags(r, "TST901")
        finally:
            RULES.pop("TST901", None)

    def test_jit_lint_true_raises_on_error(self):
        @paddle.jit.to_static(lint=True, full_graph=True)
        def noisy(x):
            def body(c, v):
                jax.debug.print("c={c}", c=c)
                return c + v, c

            out, _ = jax.lax.scan(body, jnp.float32(0), x._array)
            return paddle.Tensor(out)

        with pytest.raises(LintError):
            noisy(paddle.ones([4]))

    def test_jit_lint_warns_below_error(self):
        @paddle.jit.to_static(lint=True, full_graph=True)
        def ragged(x):
            return paddle.matmul(x, x)

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ragged(paddle.ones([100, 100]))
        assert any("TPU101" in str(x.message) for x in w)

    def test_jit_lint_fail_on_never(self):
        paddle.set_flags({"FLAGS_tpu_lint_fail_on": "never"})
        try:
            @paddle.jit.to_static(lint=True, full_graph=True)
            def noisy(x):
                jax.debug.print("x={x}", x=x._array)
                return x * 2

            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                noisy(paddle.ones([4]))
            assert any("TPU501" in str(x.message) for x in w)
        finally:
            paddle.set_flags({"FLAGS_tpu_lint_fail_on": "error"})

    def test_jit_lint_flags_scalar_arg(self):
        # the recompile rule must see USER-level python scalar args
        # through the jit hook, where they are part of the guard key
        @paddle.jit.to_static(lint=True, full_graph=True)
        def scaled(x, alpha):
            return x * alpha

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            scaled(paddle.ones([8, 128]), 3.14159)
        assert any("TPU201" in str(x.message) for x in w)

    def test_jit_lint_preserves_rng_stream(self):
        from paddle_tpu.framework import random as _random

        paddle.seed(123)
        @paddle.jit.to_static(lint=True, full_graph=True)
        def f(x):
            return x * 2

        f(paddle.ones([8, 128]))
        after_lint = np.asarray(jax.random.key_data(
            _random.get_rng_state()))

        paddle.seed(123)
        @paddle.jit.to_static(full_graph=True)
        def g(x):
            return x * 2

        g(paddle.ones([8, 128]))
        after_plain = np.asarray(jax.random.key_data(
            _random.get_rng_state()))
        assert (after_lint == after_plain).all()

    def test_jit_lint_default_off(self):
        @paddle.jit.to_static(full_graph=True)
        def noisy(x):
            jax.debug.print("x={x}", x=x._array)
            return x * 2

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            noisy(paddle.ones([4]))
        assert not any("TPU501" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# lint-self: our own bundled model must stay error-clean
# ---------------------------------------------------------------------------

@pytest.mark.fast
class TestLintSelf:
    def test_llama_forward_error_clean(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        model = LlamaForCausalLM(LlamaConfig.tiny())
        ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
        report = analysis.analyze(model, ids,
                                  name="models.llama tiny forward")
        assert not report.errors, report.format(Severity.ERROR)

    def test_cli_default_demo(self, capsys):
        from paddle_tpu.analysis.__main__ import main

        assert main([]) == 0
        out = capsys.readouterr().out
        assert "lint models.llama tiny forward" in out
