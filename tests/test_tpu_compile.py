"""Compile the main path's Mosaic kernels for a described TPU v5e.

Nothing runs: ``jax.experimental.topologies`` describes a v5e:2x2 host that
is not attached, and each kernel is lowered and compiled for one of its
chips at the payload widths a round hands it (flat-packed deltas are
``(..., R, 256)`` f32 buffers; 4096 rows is a 1M-entry pod partial). The
TPU compiler refuses here what the chip would refuse: unaligned blocks, or
more VMEM than a kernel may use (the fixed 256-row block did, from 32
groups on). Interpret-mode tests (``test_kernels.py``,
``test_fused_reduce.py``) cannot see either.

Each kernel's custom call carries the kernel's ``name`` in its ``op_name``
(the scope ``<name>/pallas_call``), which is how a profile finds it: the
custom call target ``tpu_custom_call`` is shared by every Mosaic kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

ROWS, LANES = 4096, 256
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile_hlo(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_named_kernel(hlo, name):
    """The HLO holds the Mosaic kernel, and each of its custom calls is
    named ``name`` (``vmap(name)`` under a vmap)."""
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        (op_name,) = OP_NAME.findall(line)
        assert op_name.split("/")[-2] in (name, f"vmap({name})"), op_name


@pytest.mark.parametrize("groups", [8, 64])
def test_reduce_compress(one_chip, groups):
    hlo = _compile_hlo(
        lambda x: ops.reduce_compress(x, interpret=False),
        one_chip, ((groups, ROWS, LANES), jnp.float32),
    )
    _assert_named_kernel(hlo, "reduce_compress")


@pytest.mark.parametrize("groups", [8, 64])
def test_reduce_compress_roundtrip(one_chip, groups):
    hlo = _compile_hlo(
        lambda x: ops.reduce_compress_roundtrip(
            x, backend="pallas", interpret=False
        ),
        one_chip, ((groups, ROWS, LANES), jnp.float32),
    )
    _assert_named_kernel(hlo, "reduce_compress_roundtrip")


@pytest.mark.parametrize("pods", [8, 64])
def test_dequant_accumulate(one_chip, pods):
    hlo = _compile_hlo(
        lambda q, s: ops.dequant_accumulate(q, s, interpret=False),
        one_chip,
        ((pods, ROWS, LANES), jnp.int8),
        ((pods, ROWS, 1), jnp.float32),
    )
    _assert_named_kernel(hlo, "dequant_accumulate")


def test_quantize(one_chip):
    hlo = _compile_hlo(
        lambda x: ops.quantize(x, interpret=False),
        one_chip, ((4096, 1024), jnp.float32),
    )
    _assert_named_kernel(hlo, "quantize")


def test_dequantize(one_chip):
    hlo = _compile_hlo(
        lambda q, s: ops.dequantize(q, s, interpret=False),
        one_chip, ((4096, 1024), jnp.int8), ((4096, 1), jnp.float32),
    )
    _assert_named_kernel(hlo, "dequantize")
