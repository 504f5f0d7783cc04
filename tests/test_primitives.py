"""Unit tests for the DrJAX building-block primitives (paper §2/§3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import core as drjax


def _ctxd(n, **kw):
    return dict(partition_size=n, **kw)


class TestBroadcast:
    def test_scalar(self):
        @drjax.program(partition_size=4)
        def f(x):
            return drjax.broadcast(x)

        out = f(jnp.float32(2.5))
        np.testing.assert_array_equal(out, np.full((4,), 2.5, np.float32))

    def test_array(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.broadcast(x)

        x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
        out = f(x)
        assert out.shape == (3, 2, 3)
        for i in range(3):
            np.testing.assert_array_equal(out[i], x)

    def test_pytree(self):
        @drjax.program(partition_size=2)
        def f(tree):
            return drjax.broadcast(tree)

        tree = {"w": jnp.ones((3,)), "b": jnp.zeros(())}
        out = f(tree)
        assert out["w"].shape == (2, 3)
        assert out["b"].shape == (2,)

    def test_jit(self):
        @drjax.program(partition_size=5)
        def f(x):
            return drjax.broadcast(x)

        np.testing.assert_array_equal(jax.jit(f)(jnp.float32(1.0)), np.ones(5))


class TestReduceSum:
    def test_basic(self):
        @drjax.program(partition_size=4)
        def f(x):
            return drjax.reduce_sum(x)

        x = jnp.arange(4, dtype=jnp.float32)
        assert f(x) == 6.0

    def test_matrix(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(x)

        x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        np.testing.assert_allclose(f(x), x.sum(0))

    def test_wrong_partition_size_raises(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(x)

        with pytest.raises(ValueError, match="does not match"):
            jax.jit(f)(jnp.ones((4,)))

    def test_scalar_operand_raises(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(x)

        with pytest.raises(ValueError, match="scalar"):
            jax.jit(f)(jnp.float32(1.0))


class TestReduceMeanMax:
    def test_mean(self):
        @drjax.program(partition_size=4)
        def f(x):
            return drjax.reduce_mean(x)

        assert f(jnp.array([1.0, 2.0, 3.0, 6.0])) == 3.0

    def test_max(self):
        @drjax.program(partition_size=4)
        def f(x):
            return drjax.reduce_max(x)

        assert f(jnp.array([1.0, 7.0, 3.0, 6.0])) == 7.0

    def test_weighted_mean(self):
        @drjax.program(partition_size=3)
        def f(x, w):
            return drjax.reduce_weighted_mean(x, w)

        x = jnp.array([1.0, 2.0, 4.0])
        w = jnp.array([1.0, 1.0, 2.0])
        np.testing.assert_allclose(f(x, w), (1 + 2 + 8) / 4.0)

    def test_masked_mean_drops_stragglers(self):
        @drjax.program(partition_size=4)
        def f(x, mask):
            return drjax.masked_reduce_mean(x, mask)

        x = jnp.array([1.0, 2.0, 3.0, 100.0])
        mask = jnp.array([1.0, 1.0, 1.0, 0.0])  # group 3 missed the deadline
        np.testing.assert_allclose(f(x, mask), 2.0)


class TestMapFn:
    def test_single_arg(self):
        @drjax.program(partition_size=4)
        def f(x):
            return drjax.map_fn(lambda a: a * a, x)

        x = jnp.arange(4, dtype=jnp.float32)
        np.testing.assert_allclose(f(x), x * x)

    def test_tuple_args_paper_snippet4(self):
        @drjax.program(partition_size=3)
        def f(a, b):
            ab = drjax.broadcast(a)
            return drjax.map_fn(lambda u, v: u + v, (ab, b))

        out = f(jnp.float32(10.0), jnp.arange(3, dtype=jnp.float32))
        np.testing.assert_allclose(out, [10.0, 11.0, 12.0])

    def test_pytree_output(self):
        @drjax.program(partition_size=2)
        def f(x):
            return drjax.map_fn(lambda a: {"sq": a * a, "neg": -a}, x)

        out = f(jnp.array([2.0, 3.0]))
        np.testing.assert_allclose(out["sq"], [4.0, 9.0])
        np.testing.assert_allclose(out["neg"], [-2.0, -3.0])

    def test_composition_broadcast_map_reduce(self):
        # paper Snippet 2: should return 2 * n * x
        @drjax.program(partition_size=3)
        def f(x):
            y = drjax.broadcast(x)
            z = drjax.map_fn(lambda a: 2 * a, y)
            return drjax.reduce_sum(z)

        assert f(jnp.float32(1.0)) == 6.0
        assert jax.jit(f)(jnp.float32(2.0)) == 12.0


class TestTransforms:
    def test_vmap_over_program(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(drjax.broadcast(x))

        out = jax.vmap(f)(jnp.arange(5, dtype=jnp.float32))
        np.testing.assert_allclose(out, 3 * np.arange(5))

    def test_vmap_over_partitioned_arg(self):
        @drjax.program(partition_size=3)
        def f(xs):
            return drjax.reduce_sum(xs)

        xs = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)  # batch of 4
        out = jax.vmap(f)(xs)
        np.testing.assert_allclose(out, xs.sum(-1))

    def test_nested_jit_grad(self):
        @drjax.program(partition_size=4)
        def f(x):
            y = drjax.broadcast(x)
            return drjax.reduce_mean(drjax.map_fn(lambda a: a**3, y))

        g = jax.jit(jax.grad(f))(jnp.float32(2.0))
        np.testing.assert_allclose(g, 3 * 2.0**2, rtol=1e-6)

    def test_no_context_raises(self):
        with pytest.raises(RuntimeError, match="placement context"):
            drjax.broadcast(jnp.float32(1.0))

    def test_batch_rules_handle_not_mapped(self):
        """Batching rules must pass batching.not_mapped through untouched
        (an unbatched operand inside a vmap must not get its dim shifted)."""
        from jax.interpreters import batching
        from repro.core import placement as placement_lib
        from repro.core import primitives as prims

        ctx = placement_lib.make_context(3, partition_axes=None)
        x = jnp.float32(2.0)
        out, d = prims._broadcast_batch(
            (x,), (batching.not_mapped,), pctx=ctx
        )
        assert d is batching.not_mapped
        np.testing.assert_array_equal(out, np.full((3,), 2.0, np.float32))

        xs = jnp.arange(3, dtype=jnp.float32)
        # The registry holds the rule wrapped; the wrapper takes axis data
        # first and binds the primitive itself when no operand is batched.
        reducer = batching.fancy_primitive_batchers[prims.reduce_sum_p]
        out, d = reducer(None, (xs,), (batching.not_mapped,), pctx=ctx)
        assert d is batching.not_mapped
        np.testing.assert_allclose(out, 3.0)

    def test_vmap_unbatched_broadcast_operand(self):
        """A broadcast whose operand does not carry the vmap axis composes
        with a batched reduction (mixed in_axes)."""

        @drjax.program(partition_size=3)
        def f(scale, xs):
            y = drjax.broadcast(scale)  # unbatched under the outer vmap
            return drjax.reduce_sum(drjax.map_fn(lambda a, b: a * b, (y, xs)))

        xs = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
        out = jax.vmap(f, in_axes=(None, 0))(jnp.float32(2.0), xs)
        np.testing.assert_allclose(out, 2.0 * xs.sum(-1))


class TestProperties:
    """Hypothesis property tests on algebraic invariants of the primitives."""

    @given(
        n=st.integers(1, 16),
        x=st.floats(-1e3, 1e3, allow_nan=False, width=32),
    )
    @settings(max_examples=25, deadline=None)
    def test_broadcast_then_mean_is_identity(self, n, x):
        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_mean(drjax.broadcast(v))

        np.testing.assert_allclose(f(jnp.float32(x)), x, rtol=1e-5, atol=1e-5)

    @given(
        n=st.integers(1, 16),
        x=st.floats(-100, 100, allow_nan=False, width=32),
    )
    @settings(max_examples=25, deadline=None)
    def test_broadcast_then_sum_scales_by_n(self, n, x):
        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_sum(drjax.broadcast(v))

        np.testing.assert_allclose(f(jnp.float32(x)), n * x, rtol=1e-4, atol=1e-4)

    @given(st.lists(st.floats(-100, 100, allow_nan=False, width=32), min_size=1, max_size=16))
    @settings(max_examples=25, deadline=None)
    def test_reduce_sum_linear(self, xs):
        n = len(xs)
        x = jnp.array(xs, jnp.float32)

        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_sum(v)

        np.testing.assert_allclose(
            f(2.0 * x), 2.0 * f(x), rtol=1e-4, atol=1e-3
        )

    @given(st.lists(st.floats(-50, 50, allow_nan=False, width=32), min_size=1, max_size=16))
    @settings(max_examples=25, deadline=None)
    def test_map_reduce_equals_numpy(self, xs):
        n = len(xs)
        x = jnp.array(xs, jnp.float32)

        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_sum(drjax.map_fn(lambda a: a * a + 1.0, v))

        np.testing.assert_allclose(
            f(x), np.sum(np.float32(xs) ** 2 + 1.0), rtol=1e-4, atol=1e-3
        )


class TestPropertySmoke:
    """Deterministic slices of the algebraic invariants above — these run
    even when hypothesis is not installed."""

    @pytest.mark.parametrize("n,x", [(1, 3.5), (5, -41.0), (16, 987.25)])
    def test_broadcast_then_mean_is_identity(self, n, x):
        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_mean(drjax.broadcast(v))

        np.testing.assert_allclose(f(jnp.float32(x)), x, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n,x", [(2, 7.0), (16, -31.5)])
    def test_broadcast_then_sum_scales_by_n(self, n, x):
        @drjax.program(partition_size=n)
        def f(v):
            return drjax.reduce_sum(drjax.broadcast(v))

        np.testing.assert_allclose(f(jnp.float32(x)), n * x, rtol=1e-4, atol=1e-4)

    def test_reduce_sum_linear(self):
        xs = [1.0, -2.5, 17.0, 0.0, 93.5]
        x = jnp.array(xs, jnp.float32)

        @drjax.program(partition_size=len(xs))
        def f(v):
            return drjax.reduce_sum(v)

        np.testing.assert_allclose(
            f(2.0 * x), 2.0 * f(x), rtol=1e-4, atol=1e-3
        )

    def test_map_reduce_equals_numpy(self):
        xs = [0.5, -12.0, 33.25, 4.0]
        x = jnp.array(xs, jnp.float32)

        @drjax.program(partition_size=len(xs))
        def f(v):
            return drjax.reduce_sum(drjax.map_fn(lambda a: a * a + 1.0, v))

        np.testing.assert_allclose(
            f(x), np.sum(np.float32(xs) ** 2 + 1.0), rtol=1e-4, atol=1e-3
        )
