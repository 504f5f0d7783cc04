"""Fused reduce+compress fast path (PR 4).

Covers the acceptance criteria of the fused hierarchical reduction:

* interpret-mode Pallas kernels vs their jnp oracles — bitwise;
* ``grad`` through ``hierarchical_reduce_mean(compress_fn=int8_roundtrip)``
  identical fused vs unfused (straight-through roundtrip semantics);
* plan IR: the fused program still stages as ``REDUCE@clients`` →
  ``REDUCE@pods`` and its communication stages match the unfused
  composition stage for stage;
* the flat-packing utility round-trips pytrees bitwise;
* cross-placement ``map_fn`` fusion is bitwise-identical to the nested form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as drjax
from repro import compression
from repro.compression import int8_roundtrip
from repro.core import interpreter
from repro.kernels import ops, ref
from repro.kernels import reduce_compress as rc


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------


class TestKernelsVsOracle:
    def test_reduce_compress_bitwise(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 10, 256))
        q, s = rc.reduce_compress(x, interpret=True)
        # The interpreted kernel runs compiled; so does the oracle, as XLA
        # may round the partial mean apart from an op-by-op evaluation.
        qr, sr = jax.jit(ref.reduce_compress_ref)(x)
        assert q.dtype == jnp.int8 and s.shape == (1, 10)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))

    def test_reduce_compress_row_padding_bitwise(self):
        # Lane-dense scales block rows by 128 lanes: 300 rows leave a
        # partial last block.
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 300, 128))
        q, s = rc.reduce_compress(x, row_block=128, interpret=True)
        qr, sr = jax.jit(ref.reduce_compress_ref)(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))

    def test_roundtrip_kernel_bitwise(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, 256))
        back, q, s = rc.reduce_compress_roundtrip(x, interpret=True)
        br, qr, _ = ref.reduce_compress_roundtrip_ref(x)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(br))
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))

    def test_dequant_accumulate_bitwise(self):
        k = jax.random.PRNGKey(3)
        x = jax.random.normal(k, (4, 8, 9, 128))  # (P, G, R, C)
        q, s = jax.vmap(lambda p: rc.reduce_compress(p, interpret=True))(x)
        out = rc.dequant_accumulate(q, s[:, 0], interpret=True)
        # The interpreted kernel runs compiled, where XLA fuses the dequant
        # multiply into the pod sum; the oracle is compiled too and adds
        # the pods in the kernel's order, so both stay bitwise equal.
        outr = jax.jit(ref.dequant_accumulate_ref)(q, s[:, 0])
        np.testing.assert_array_equal(np.asarray(out), np.asarray(outr))

    def test_lane_dense_scales(self):
        """The cross-pod leg's layout: ``reduce_compress`` writes its scales
        as ``(1, R)``, bitwise the ``(R, 1)`` column of the roundtrip
        kernel, and ``dequant_accumulate`` reads them as ``(P, R)``, over a
        partial last block of 128 rows, bitwise its oracle."""
        x = jax.random.normal(jax.random.PRNGKey(9), (3, 1, 300, 256))
        x = x * jnp.exp(jax.random.normal(jax.random.PRNGKey(10),
                                           (3, 1, 300, 1)))
        q, s = jax.vmap(lambda p: rc.reduce_compress(
            p, row_block=128, interpret=True))(x)
        _, q_rt, s_rt = jax.vmap(
            lambda p: rc.reduce_compress_roundtrip(p, interpret=True))(x)
        assert s.shape == (3, 1, 300)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_rt))
        np.testing.assert_array_equal(np.asarray(s[:, 0]),
                                      np.asarray(s_rt[..., 0]))
        out = rc.dequant_accumulate(q, s[:, 0], row_block=128,
                                    interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(jax.jit(ref.dequant_accumulate_ref)(q, s[:, 0])))

    def test_pair_equals_roundtrip_then_mean(self):
        """reduce_compress → dequant_accumulate (the backend's two-kernel
        execution) computes the same value as the straight-through roundtrip
        partials followed by the plain cross-pod mean."""
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 4, 256))
        q, s = jax.vmap(lambda p: rc.reduce_compress(p, interpret=True))(x)
        s = s[:, 0]                                 # (P, R), lane-dense
        pair = rc.dequant_accumulate(q, s, interpret=True)
        backs, q_rt, s_rt = jax.vmap(
            lambda p: rc.reduce_compress_roundtrip(p, interpret=True)
        )(x)
        # Both kernels put the same payload on the wire, and the roundtrip
        # partial is that payload dequantized.
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_rt))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_rt[..., 0]))
        np.testing.assert_array_equal(
            np.asarray(backs), np.asarray(q, np.float32) * np.asarray(s_rt)
        )
        # The pod mean of those partials, with the dequantize fused into the
        # sum as in the kernel (a mean of the materialized partials rounds
        # each product first, and may differ in the last bit of a summand).
        np.testing.assert_array_equal(
            np.asarray(pair), np.asarray(jax.jit(ref.dequant_accumulate_ref)(q, s))
        )


class TestOpsDispatch:
    def test_jnp_fast_path_matches_kernel(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 6, 10, 256))
        fast = ops.reduce_compress_roundtrip(x, axis=1, backend="jnp")
        kern = ops.reduce_compress_roundtrip(
            x, axis=1, backend="pallas", interpret=True
        )
        assert fast.shape == (4, 10, 256)
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(kern), atol=1e-5
        )

    def test_bf16_jnp_path_quantizes_the_f32_mean_like_the_kernel(self):
        x = jax.random.normal(jax.random.PRNGKey(8), (2, 4, 10, 256))
        x = x.astype(jnp.bfloat16)
        fast = ops.reduce_compress_roundtrip(x, axis=1, backend="jnp")
        kern = ops.reduce_compress_roundtrip(
            x, axis=1, backend="pallas", interpret=True
        )
        assert fast.dtype == kern.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(fast, np.float32), np.asarray(kern, np.float32)
        )

    def test_gemm_and_plain_mean_agree(self):
        # bf16 input takes the plain-mean branch; compare against the f32
        # gemm branch on the same values.
        x32 = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 4, 256))
        gemm = ops.reduce_compress_roundtrip(x32, axis=1, backend="jnp")
        plain = ops.reduce_compress_roundtrip(
            x32.astype(jnp.bfloat16), axis=1, backend="jnp"
        )
        np.testing.assert_allclose(
            np.asarray(gemm), np.asarray(plain, dtype=np.float32),
            atol=0.05,
        )

    def test_axis_zero_no_lead(self):
        x = jax.random.normal(jax.random.PRNGKey(7), (8, 5, 256))
        out = ops.reduce_compress_roundtrip(x, axis=0, backend="jnp")
        ref_back, _, _ = ref.reduce_compress_roundtrip_ref(x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref_back), atol=1e-5
        )


# ---------------------------------------------------------------------------
# flat packing
# ---------------------------------------------------------------------------


class TestFlatPack:
    def test_roundtrip_bitwise_mixed_dtypes(self):
        tree = {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.linspace(-1, 1, 5, dtype=jnp.float32),
            "step": jnp.arange(3, dtype=jnp.int32),
            "h": jnp.ones((2, 2), jnp.bfloat16),
            "scalar": jnp.float32(3.5),
        }
        bufs, spec = compression.flat_pack(tree, lead_ndim=0)
        assert set(bufs) == {"float32", "int32", "bfloat16"}
        for buf in bufs.values():
            assert buf.shape[-1] == compression.PACK_COLS
        back = compression.flat_unpack(bufs, spec, lead_ndim=0)
        for k in tree:
            assert back[k].dtype == jnp.asarray(tree[k]).dtype
            np.testing.assert_array_equal(
                np.asarray(back[k], np.float32),
                np.asarray(tree[k], np.float32),
            )

    def test_lead_axes_preserved_and_reducible(self):
        tree = {"a": jnp.ones((2, 4, 3)), "b": jnp.zeros((2, 4, 5, 2))}
        bufs, spec = compression.flat_pack(tree, lead_ndim=2)
        (buf,) = bufs.values()
        assert buf.shape[:2] == (2, 4)
        # reduce both group axes away, then unpack at lead_ndim=0
        reduced = {k: v.mean(axis=(0, 1)) for k, v in bufs.items()}
        out = compression.flat_unpack(reduced, spec, lead_ndim=0)
        assert out["a"].shape == (3,) and out["b"].shape == (5, 2)

    def test_mismatched_lead_raises(self):
        with pytest.raises(ValueError, match="lead axes"):
            compression.flat_pack(
                {"a": jnp.ones((2, 3)), "b": jnp.ones((4, 3))}, lead_ndim=1
            )

    def test_scale_blocks_never_span_leaves(self):
        """Regression: a small-magnitude leaf packed next to a huge one must
        keep its own quantization scale — sharing the huge leaf's 256-block
        scale would dequantize the small leaf to exactly zero."""
        tree = {
            "big": jnp.full((10,), 1e4, jnp.float32),
            "small": jnp.full((10,), 1e-3, jnp.float32),
        }
        back = int8_roundtrip(tree)
        np.testing.assert_allclose(
            np.asarray(back["small"]), np.asarray(tree["small"]), rtol=0.01
        )
        np.testing.assert_allclose(
            np.asarray(back["big"]), np.asarray(tree["big"]), rtol=0.01
        )

    def test_fused_reduce_preserves_small_leaf(self):
        """Same property through the fused hierarchical path."""

        @drjax.program(partition_size=4)
        def f(tree):
            return drjax.hierarchical_reduce_mean(
                tree, num_supergroups=2, compress_fn=int8_roundtrip
            )

        tree = {
            "big": jnp.full((4, 10), 1e4, jnp.float32),
            "small": jnp.full((4, 10), 1e-3, jnp.float32),
        }
        out = f(tree)
        np.testing.assert_allclose(
            np.asarray(out["small"]), np.full(10, 1e-3), rtol=0.01
        )


# ---------------------------------------------------------------------------
# fused hierarchical reduction
# ---------------------------------------------------------------------------


def _programs(n, num_pods):
    @drjax.program(partition_size=n)
    def fused(xs):
        return drjax.hierarchical_reduce_mean(
            xs, num_supergroups=num_pods, compress_fn=int8_roundtrip
        )

    @drjax.program(partition_size=n)
    def unfused(xs):
        return drjax.hierarchical_reduce_mean(
            xs, num_supergroups=num_pods, compress_fn=int8_roundtrip,
            use_fused=False,
        )

    @drjax.program(partition_size=n)
    def plain(xs):
        return drjax.hierarchical_reduce_mean(xs, num_supergroups=num_pods)

    return fused, unfused, plain


class TestFusedHierarchical:
    def test_forward_close_to_true_mean(self):
        fused, unfused, _ = _programs(8, 2)
        xs = jax.random.normal(jax.random.PRNGKey(0), (8, 300))
        f, u = fused(xs), unfused(xs)
        scale = float(jnp.max(jnp.abs(xs)))
        assert float(jnp.max(jnp.abs(f - xs.mean(0)))) < 0.02 * scale
        # fused and unfused share the wire format; they differ only in scale
        # block boundaries (packed 256-cols vs per-leaf rows)
        assert float(jnp.max(jnp.abs(f - u))) < 0.02 * scale

    def test_grad_fused_equals_unfused(self):
        """Acceptance: grad through the fused program == unfused composition
        (and both == the uncompressed hierarchical mean — straight-through)."""
        fused, unfused, plain = _programs(8, 2)
        xs = jax.random.normal(jax.random.PRNGKey(1), (8, 70))
        gf = jax.grad(lambda x: fused(x).sum())(xs)
        gu = jax.grad(lambda x: unfused(x).sum())(xs)
        gp = jax.grad(lambda x: plain(x).sum())(xs)
        np.testing.assert_array_equal(np.asarray(gf), np.asarray(gu))
        np.testing.assert_array_equal(np.asarray(gf), np.asarray(gp))

    def test_grad_under_jit(self):
        fused, unfused, _ = _programs(8, 4)
        xs = jax.random.normal(jax.random.PRNGKey(2), (8, 33))
        gf = jax.jit(jax.grad(lambda x: fused(x).sum()))(xs)
        gu = jax.jit(jax.grad(lambda x: unfused(x).sum()))(xs)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gu), rtol=1e-6)

    def test_nested_stack_pytree(self):
        @drjax.program(placements={"pods": 2, "clients": 4})
        def nested(tree):
            return drjax.hierarchical_reduce_mean(
                tree, compress_fn=int8_roundtrip
            )

        @drjax.program(placements={"pods": 2, "clients": 4})
        def nested_ref(tree):
            return drjax.hierarchical_reduce_mean(
                tree, compress_fn=int8_roundtrip, use_fused=False
            )

        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        tree = {
            "w": jax.random.normal(k1, (2, 4, 40)),
            "b": jax.random.normal(k2, (2, 4)),
        }
        out, outr = nested(tree), nested_ref(tree)
        assert out["w"].shape == (40,) and out["b"].shape == ()
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(out[k]), np.asarray(outr[k]), atol=0.05
            )
        g = jax.grad(lambda t: nested(t)["w"].sum())(tree)
        gr = jax.grad(lambda t: nested_ref(t)["w"].sum())(tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(gr[k]))

    def test_non_float_leaf_falls_back(self):
        @drjax.program(partition_size=4)
        def f(tree):
            return drjax.hierarchical_reduce_mean(
                tree, num_supergroups=2, compress_fn=int8_roundtrip
            )

        tree = {"w": jnp.ones((4, 8)), "count": jnp.ones((4,), jnp.int32)}
        out = f(tree)  # must not raise; generic path handles the int leaf
        np.testing.assert_allclose(np.asarray(out["w"]), np.ones(8), atol=0.02)

    def test_use_fused_true_requires_recognized_compressor(self):
        @drjax.program(partition_size=4)
        def f(xs):
            return drjax.hierarchical_reduce_mean(
                xs, num_supergroups=2, compress_fn=lambda t: t, use_fused=True
            )

        with pytest.raises(ValueError, match="use_fused=True"):
            f(jnp.ones((4, 8)))

    def test_fused_eqn_in_trace(self):
        fused, _, _ = _programs(4, 2)
        xs = jax.random.normal(jax.random.PRNGKey(5), (4, 16))
        assert "compress=int8" in str(jax.make_jaxpr(fused)(xs))

    def test_vmap_over_fused_program(self):
        """Outer-loop transforms survive the fused eqn (batch rule shifts
        the quantization axis with the appended batch dim)."""
        fused, unfused, _ = _programs(4, 2)
        xs = jax.random.normal(jax.random.PRNGKey(6), (3, 4, 32))
        vf = jax.vmap(fused)(xs)
        vu = jax.vmap(unfused)(xs)
        assert vf.shape == (3, 32)
        np.testing.assert_allclose(np.asarray(vf), np.asarray(vu), atol=0.05)


# ---------------------------------------------------------------------------
# plan IR (§5 interpreter)
# ---------------------------------------------------------------------------


def _comm_signature(plan):
    sig = []
    for s in plan.stages:
        if isinstance(s, interpreter.Reduce):
            sig.append(("REDUCE", s.op, s.placement, s.dest))
        elif isinstance(s, interpreter.Broadcast):
            sig.append(("BROADCAST", s.placement, s.source))
    return sig


class TestFusedPlanIR:
    def test_two_tagged_reduce_stages(self):
        """Acceptance: the fused program still stages REDUCE@clients →
        REDUCE@pods, and its communication structure is stage-for-stage
        identical to the unfused composition's."""
        fused, unfused, plain = _programs(8, 2)
        xs = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        plans = {
            name: drjax.build_plan(jax.make_jaxpr(p)(xs), 8)
            for name, p in [("fused", fused), ("unfused", unfused),
                            ("plain", plain)]
        }
        expected = [
            ("REDUCE", "reduce_mean", "clients", "pods"),
            ("REDUCE", "reduce_mean", "pods", "server"),
        ]
        assert _comm_signature(plans["fused"]) == expected
        assert (_comm_signature(plans["fused"])
                == _comm_signature(plans["unfused"])
                == _comm_signature(plans["plain"]))

    def test_fused_plan_kinds_match_uncompressed(self):
        """Modulo the quantization math riding inside existing stages, the
        fused plan has the same stage skeleton as the uncompressed program:
        no extra communication stages appear."""
        fused, _, plain = _programs(8, 4)
        xs = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        kinds_fused = [
            s.kind for s in drjax.build_plan(jax.make_jaxpr(fused)(xs), 8).stages
            if s.kind in ("BROADCAST", "REDUCE", "LOOP", "COND")
        ]
        kinds_plain = [
            s.kind for s in drjax.build_plan(jax.make_jaxpr(plain)(xs), 8).stages
            if s.kind in ("BROADCAST", "REDUCE", "LOOP", "COND")
        ]
        assert kinds_fused == kinds_plain == ["REDUCE", "REDUCE"]

    def test_run_plan_matches_direct(self):
        fused, _, _ = _programs(8, 2)
        xs = jax.random.normal(jax.random.PRNGKey(2), (8, 48))
        plan = drjax.build_plan(jax.make_jaxpr(fused)(xs), 8)
        (out,) = drjax.run_plan(plan, xs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(fused(xs)))

    def test_nested_stack_plan(self):
        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(xs):
            return drjax.hierarchical_reduce_mean(
                xs, compress_fn=int8_roundtrip
            )

        xs = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 16))
        plan = drjax.build_plan(
            jax.make_jaxpr(prog)(xs), {"pods": 2, "clients": 3}
        )
        assert _comm_signature(plan) == [
            ("REDUCE", "reduce_mean", "clients", "pods"),
            ("REDUCE", "reduce_mean", "pods", "server"),
        ]
        (out,) = drjax.run_plan(plan, xs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(prog(xs)))

    def test_to_beam_emits(self):
        fused, _, _ = _programs(8, 2)
        xs = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
        plan = drjax.build_plan(jax.make_jaxpr(fused)(xs), 8)
        beam = plan.to_beam()
        assert "CombinePerKey" in beam or "REDUCE" in beam.upper()


# ---------------------------------------------------------------------------
# cross-placement map_fn fusion
# ---------------------------------------------------------------------------


class TestMapFnFusion:
    def _x(self, key=0, shape=(2, 3, 7)):
        return jax.random.normal(jax.random.PRNGKey(key), shape)

    def test_fused_bitwise_equals_nested(self):
        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(x, fuse):
            return drjax.map_fn(lambda v: jnp.sin(v) * 2.0 + v.sum(), x,
                                fuse=fuse)

        x = self._x()
        np.testing.assert_array_equal(
            np.asarray(prog(x, None)), np.asarray(prog(x, False))
        )

    def test_fused_tuple_args_bitwise(self):
        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(m, t, fuse):
            return drjax.map_fn(
                lambda mm, tt: (mm * tt, (mm - tt) ** 2), (m, t), fuse=fuse
            )

        m, t = self._x(1, (2, 3, 5)), self._x(2, (2, 3, 5))
        for a, b in zip(prog(m, t, None), prog(m, t, False)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fused_grad_bitwise(self):
        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(x, fuse):
            return drjax.map_fn(jnp.tanh, x, fuse=fuse)

        x = self._x(3)
        g = jax.grad(lambda v: prog(v, None).sum())(x)
        gn = jax.grad(lambda v: prog(v, False).sum())(x)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(gn))

    def test_single_vmap_in_fused_trace(self):
        """The fused default-span map collapses both group axes into ONE
        mapped axis: the traced fn sees rank-1 slices of a rank-3 operand."""
        seen = []

        def probe(v):
            seen.append(v.ndim)
            return v * 2

        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(x, fuse):
            return drjax.map_fn(probe, x, fuse=fuse)

        x = self._x(4)
        prog(x, None)
        assert seen and seen[-1] == 1  # one vmap: per-group slice directly

    def test_mixed_axis_annotations_fall_back(self):
        ctx = drjax.make_context(
            None,
            placements={"pods": 2, "clients": 3},
            partition_axes={"pods": None, "clients": "data"},
        )
        from repro.core.api import _fused_spmd_names

        ok, _ = _fused_spmd_names(ctx)
        assert not ok
        both = drjax.make_context(
            None,
            placements={"pods": 2, "clients": 3},
            partition_axes={"pods": "pod", "clients": "data"},
        )
        ok, names = _fused_spmd_names(both)
        assert ok and names == ("pod", "data")

    def test_flat_single_placement_unchanged(self):
        @drjax.program(partition_size=5)
        def prog(x, fuse):
            return drjax.map_fn(lambda v: v + 1, x, fuse=fuse)

        x = self._x(5, (5, 4))
        np.testing.assert_array_equal(
            np.asarray(prog(x, None)), np.asarray(prog(x, False))
        )

    def test_malformed_leaf_raises(self):
        @drjax.program(placements={"pods": 2, "clients": 3})
        def prog(x):
            return drjax.map_fn(lambda v: v, x)

        with pytest.raises(ValueError, match="group axes"):
            prog(jnp.ones((3, 2, 4)))  # axes transposed
