"""Compat-layer tests.

Two halves: (a) the layer works against the installed JAX, which has every
surface compat calls, and (b) monkeypatched ``jax`` surfaces pin what
compat forwards to them, so a JAX upgrade that moves one fails here.
"""

import contextlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.compat import jaxpr as compat_jaxpr


def _params(fn):
    return tuple(inspect.signature(fn).parameters)


# The JAX surfaces compat calls with no fallback, each checked where it is
# declared so a release that moved one names it here.
_SURFACES = {
    "make_mesh_axis_types": lambda: (
        "axis_types" in _params(jax.make_mesh)
        and all(hasattr(jax.sharding.AxisType, m)
                for m in ("Auto", "Explicit", "Manual"))
    ),
    "set_mesh": lambda: callable(jax.set_mesh),
    "shard_map_check_vma": lambda: "check_vma" in _params(jax.shard_map),
    "var_takes_aval": lambda: (
        compat_jaxpr.fresh_var(jax.core.ShapedArray((2,), jnp.float32)).aval
        == jax.core.ShapedArray((2,), jnp.float32)
    ),
}


class TestOnInstalledJax:
    @pytest.mark.parametrize("surface", sorted(_SURFACES))
    def test_installed_jax_has_surface(self, surface):
        assert _SURFACES[surface](), f"installed JAX lacks {surface}"

    def test_jaxpr_internals(self):
        closed = jax.make_jaxpr(lambda x: (x * 2.0, x + 1.0))(jnp.ones((3,)))
        aval = compat_jaxpr.get_aval(jnp.ones((3,)))
        assert aval.shape == (3,) and aval.dtype == jnp.float32
        out = compat_jaxpr.eval_jaxpr(closed.jaxpr, closed.consts,
                                      jnp.arange(3.0))
        np.testing.assert_allclose(out[0], [0.0, 2.0, 4.0])
        assert not any(compat_jaxpr.is_dropvar(v)
                       for v in closed.jaxpr.outvars)

    def test_make_mesh_single_device(self):
        mesh = compat.make_mesh((1,), ("data",))
        assert mesh.axis_names == ("data",)
        assert mesh.devices.shape == (1,)

    def test_make_mesh_axis_type_request_is_portable(self):
        mesh = compat.make_mesh((1,), ("data",), axis_types="auto")
        assert mesh.shape["data"] == 1
        assert mesh.axis_types == (compat.axis_type("auto"),)

    def test_axis_type_rejects_unknown_kind(self):
        with pytest.raises(ValueError, match="unknown axis type"):
            compat.axis_type("automatic")

    def test_set_mesh_usable_as_ambient_context(self):
        mesh = compat.make_mesh((1,), ("data",))
        with compat.set_mesh(mesh) as active:
            assert active is mesh
            out = jax.jit(lambda x: x * 2)(jnp.ones((4,)))
        np.testing.assert_allclose(out, 2.0 * np.ones(4))

    def test_set_mesh_none_is_noop(self):
        with compat.set_mesh(None) as active:
            assert active is None

    def test_cost_analysis_normalized_to_dict(self):
        compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
        cost = compat.cost_analysis(compiled)
        assert isinstance(cost, dict)
        assert cost.get("flops", 0) > 0
        assert compat.cost_flops(compiled) == pytest.approx(cost["flops"])
        # None (no cost model) is a legal answer, distinct from a real 0.0
        bytes_acc = compat.cost_bytes_accessed(compiled)
        assert bytes_acc is None or bytes_acc >= 0.0

    def test_cost_bytes_accessed_none_when_no_cost_model(self):
        class _NoCosts:
            def cost_analysis(self):
                raise NotImplementedError("backend reports no costs")

        assert compat.cost_bytes_accessed(_NoCosts()) is None

    def test_named_sharding_accepts_spec_or_axes(self):
        mesh = compat.make_mesh((1,), ("data",))
        from jax.sharding import PartitionSpec as P

        a = compat.named_sharding(mesh, P("data", None))
        b = compat.named_sharding(mesh, ("data", None))
        assert a.spec == b.spec == P("data", None)
        assert compat.replicated_sharding(mesh).spec == P()
        assert compat.named_sharding(mesh).spec == P()

    def test_shard_map_runs_on_installed_jax(self):
        from jax.sharding import PartitionSpec as P

        mesh = compat.make_mesh((1,), ("data",))
        fn = compat.shard_map(
            lambda x: x * 2,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=P(),
            check=False,
        )
        np.testing.assert_allclose(fn(jnp.ones((4,))), 2.0 * np.ones(4))


class TestCostNormalization:
    class _Compiled:
        def __init__(self, raw):
            self._raw = raw

        def cost_analysis(self):
            if isinstance(self._raw, Exception):
                raise self._raw
            return self._raw

    def test_dict_passthrough(self):
        assert compat.normalize_cost_analysis({"flops": 3.0}) == {"flops": 3.0}

    def test_single_element_list(self):
        assert compat.cost_analysis(
            self._Compiled([{"flops": 5.0, "bytes accessed": 7.0}])
        ) == {"flops": 5.0, "bytes accessed": 7.0}

    def test_multi_module_list_sums_numeric(self):
        cost = compat.normalize_cost_analysis(
            [{"flops": 1.0, "note": "a"}, {"flops": 2.0, "bytes accessed": 4.0}]
        )
        assert cost["flops"] == 3.0
        assert cost["bytes accessed"] == 4.0
        assert cost["note"] == "a"

    def test_empty_and_none(self):
        assert compat.normalize_cost_analysis([]) == {}
        assert compat.normalize_cost_analysis(None) == {}
        assert compat.normalize_cost_analysis("garbage") == {}

    def test_raising_backend_yields_empty(self):
        assert compat.cost_analysis(
            self._Compiled(NotImplementedError("no costs on this backend"))
        ) == {}


class _FakeAxisType:
    Auto = "AUTO"
    Explicit = "EXPLICIT"
    Manual = "MANUAL"


class TestProbeMatrix:
    """Pin what compat forwards to ``jax`` by monkeypatching it."""

    def test_new_api_axis_types_forwarded(self, monkeypatch):
        recorded = {}

        def fake_make_mesh(shape, axes, *, devices=None, axis_types=None):
            recorded.update(shape=shape, axes=axes, axis_types=axis_types)
            return "NEW-MESH"

        monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
        monkeypatch.setattr(
            jax.sharding, "AxisType", _FakeAxisType, raising=False
        )
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        assert mesh == "NEW-MESH"
        assert recorded["axis_types"] == ("AUTO", "AUTO")
        assert compat.axis_type("explicit") == "EXPLICIT"

    def test_set_mesh_prefers_jax_set_mesh(self, monkeypatch):
        seen = []

        @contextlib.contextmanager
        def fake_set_mesh(mesh):
            seen.append(mesh)
            yield

        monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
        with compat.set_mesh("the-mesh"):
            pass
        assert seen == ["the-mesh"]
