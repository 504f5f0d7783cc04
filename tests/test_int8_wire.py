"""The nested int8 reduce's cross-pod leg on a (pod 2, data 2) mesh.

``hierarchical_reduce_mean`` with ``int8_roundtrip`` binds its outer
``reduce_mean@pods`` tagged ``wire="int8"``: on a mesh whose pods level is
sharded, the pod partials (already ``q * s``, the roundtrip's output) cross
the pod axis as their int8 values and one f32 scale a row, and
``dequant_accumulate`` takes their mean. The tag changes the encoding only:

* the mean is the plain one's to 2 f32 ulps (the requantized scale may
  come back an ulp or two off on a chip; the CPU oracle recovers it
  exactly);
* three rounds of the nested round agree with the same rounds whose outer
  mean is the plain one, to the same ulps;
* ``grad`` through the tagged reduce is bitwise the unfused composition's;
* where no wire exists or the buffer is off the int8 grid (one device, a
  bf16 buffer), the tag is dropped and the plain mean runs.

Everything multi-device runs in the device-pool worker (``device_pool``).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS)
MAXULP = 2
PODS, CLIENTS, WIDTH = 2, 4, 3 * 256 + 40


def _run(device_pool, call: str) -> dict:
    return device_pool.run(f"""
        import json, sys
        sys.path[:0] = [{REPO!r}, {TESTS!r}]
        import test_int8_wire

        print(json.dumps(test_int8_wire.{call}))
    """)


def _mesh(devices):
    from repro import compat

    return compat.make_mesh((PODS, 2), ("pod", "data"), devices=devices[:4])


def _program(form: str, mesh):
    """The int8 hierarchical mean as a nested program, or through the flat
    API, on ``mesh`` (None: one device)."""
    from repro import core as drjax
    from repro.compression import int8_roundtrip

    if form == "nested":
        axes = {"pods": "pod", "clients": "data"} if mesh is not None else None

        @drjax.program(placements={"pods": PODS, "clients": CLIENTS},
                       partition_axes=axes, mesh=mesh)
        def f(xs, use_fused=None):
            return drjax.hierarchical_reduce_mean(
                xs, compress_fn=int8_roundtrip, use_fused=use_fused)

        return f
    axes = ("pod", "data") if mesh is not None else None

    @drjax.program(partition_size=PODS * CLIENTS, partition_axes=axes,
                   mesh=mesh)
    def g(xs, use_fused=None):
        return drjax.hierarchical_reduce_mean(
            xs.reshape((PODS * CLIENTS,) + xs.shape[2:]),
            num_supergroups=PODS, compress_fn=int8_roundtrip,
            use_fused=use_fused)

    return g


def _clients(dtype=jnp.float32):
    """Each client's delta: multiples of 2**-12 below 2**8, scaled by 8**-j
    in its j-th row of 256, so that rows differ in scale while every
    intra-pod sum stays exact in any order: both executions hand the
    cross-pod leg the same partials."""
    rng = np.random.default_rng(2**32 + 17)
    ints = rng.integers(-2**20, 2**20, (PODS, CLIENTS, WIDTH))
    rows = 8.0 ** -(np.arange(WIDTH) // 256)
    return jnp.asarray(ints * 2.0**-12 * rows, dtype)


def _placed(xs, mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(xs, NamedSharding(mesh,
                                            PartitionSpec("pod", "data")))


def _pod_terms(xs):
    """The largest magnitude each column of the cross-pod mean sums: the
    larger of the two pods' int8 roundtrip partials."""
    from repro.kernels import ops

    pad = -xs.shape[-1] % 256
    packed = jnp.pad(xs, ((0, 0), (0, 0), (0, pad)))
    packed = packed.reshape(PODS, CLIENTS, -1, 256)
    back = ops.reduce_compress_roundtrip(packed, axis=1, backend="jnp")
    return np.abs(np.asarray(back)).reshape(PODS, -1)[:, :WIDTH].max(0)


def reduce_gap(form: str) -> dict:
    """The mean on the mesh (wire engaged) against the same call on one
    device (no wire); raises unless they agree to ``MAXULP`` ulps of the
    larger pod partial."""
    from _ulp import assert_within_ulp_of

    devices = jax.devices()
    mesh = _mesh(devices)
    xs = _clients()
    wired = jax.jit(_program(form, mesh))
    plain = jax.jit(_program(form, None))
    with jax.default_device(devices[0]):
        want = np.asarray(plain(xs))
        terms = _pod_terms(xs)
    got = np.asarray(wired(_placed(xs, mesh)))
    assert_within_ulp_of(got, want, terms, maxulp=MAXULP)
    text = wired.lower(_placed(xs, mesh)).as_text()
    return {"engaged": "dequant_accumulate" in text}


def plain_means(form: str) -> dict:
    """Where the tag must be dropped: on one device, and for a bf16 buffer
    on the mesh. Whether ``dequant_accumulate`` is in each lowered text."""
    mesh = _mesh(jax.devices())
    f32, bf16 = _clients(), _clients(jnp.bfloat16)
    return {
        "one_device": "dequant_accumulate" in jax.jit(
            _program(form, None)).lower(f32).as_text(),
        "bf16": "dequant_accumulate" in jax.jit(
            _program(form, mesh)).lower(_placed(bf16, mesh)).as_text(),
        "f32": "dequant_accumulate" in jax.jit(
            _program(form, mesh)).lower(_placed(f32, mesh)).as_text(),
    }


def grad_gap() -> dict:
    """``grad`` of a weighted sum of the mean on the mesh: the fused path
    (wire engaged) against the unfused composition."""
    mesh = _mesh(jax.devices())
    xs = _placed(_clients(), mesh)
    f = _program("nested", mesh)
    w = jnp.linspace(-1.0, 1.0, WIDTH)

    def loss(xs, use_fused):
        return jnp.sum(w * f(xs, use_fused=use_fused))

    fused = jax.jit(jax.grad(lambda xs: loss(xs, None)))
    unfused = jax.jit(jax.grad(lambda xs: loss(xs, False)))
    text = jax.jit(lambda xs: loss(xs, None)).lower(xs).as_text()
    return {"engaged": "dequant_accumulate" in text,
            "bitwise": bool(np.array_equal(np.asarray(fused(xs)),
                                           np.asarray(unfused(xs))))}


def plan_on_the_mesh() -> dict:
    """The nested program's REDUCE stages on the mesh: placement, where the
    value goes, and the ``wire`` tag of the stage's eqn."""
    from repro import core as drjax
    from repro.core import interpreter

    mesh = _mesh(jax.devices())
    xs = _placed(_clients(), mesh)
    plan = drjax.build_plan(jax.make_jaxpr(_program("nested", mesh))(xs),
                            {"pods": PODS, "clients": CLIENTS})
    return {"stages": [[s.placement, s.dest, s.eqn.params.get("wire")]
                       for s in plan.stages
                       if isinstance(s, interpreter.Reduce)]}


ROUNDS = 3


def _round_step(c, t, devices, wire: bool):
    """``launch.train``'s nested int8 round, compiled for ``devices``;
    ``wire=False`` drops the tag, so the cross-pod mean is the plain one.
    The compiled step, the layout, and whether it holds the wire."""
    from benchmarks.chip import program, run, weights
    from benchmarks.chip import reference_hier as ref
    from repro.core import primitives as prims
    from repro.launch import train as train_lib
    import test_hier_entry as entry

    args = train_lib.parse_args(entry._argv(t))
    layout = train_lib.round_layout(args, devices)
    engages = prims._wire_engages
    if not wire:
        prims._wire_engages = lambda *_: False
    try:
        step, server_opt = train_lib.build_round_fn(
            program.model_config(c), args, layout.mesh)
        params = ref.init(ref.frozen(c), weights.seed_array(entry.SEED))
        sstate = server_opt.init(params)
        batch = run.round_batch(run.sampler(t, c["vocab_size"], entry.SEED),
                                t, 0)
        lowered = step.lower(layout.state(params), layout.state(sstate),
                             layout.batch(batch))
    finally:
        prims._wire_engages = engages
    return (lowered.compile(), layout,
            "dequant_accumulate" in lowered.as_text(),
            jax.device_get((params, sstate)))


def round_gap() -> dict:
    """Three rounds of the nested round on the mesh, with the wire and with
    the plain cross-pod mean, each round from the same parameters and
    server state (the wire's of the round before); raises unless every new
    parameter agrees to ``MAXULP`` ulps of the larger of its value and the
    largest pod partial of its row (``127`` of the row's int8 step)."""
    from _ulp import assert_within_ulp_of
    from benchmarks.chip import run
    from benchmarks.chip import reference_hier as ref
    import test_hier_entry as entry

    c, t = entry._cell()
    devices = jax.devices()[:4]
    wired, layout, engaged, (params, sstate) = _round_step(c, t, devices, True)
    plain, _, dropped, _ = _round_step(c, t, devices, False)
    sample = run.sampler(t, c["vocab_size"], entry.SEED)
    for r in range(ROUNDS):
        batch = run.round_batch(sample, t, r)
        steps = [jax.tree_util.tree_map(ref.int8_step, partial) for partial, _
                 in ref.pod_partials(c, t, jax.device_put(params), batch)]
        a, b = ((jax.device_get(f(layout.state(params), layout.state(sstate),
                                   layout.batch(batch))[:2]))
                for f in (wired, plain))
        for x, y, s0, s1 in zip(*map(jax.tree_util.tree_leaves,
                                     (a[0], b[0], *steps))):
            assert_within_ulp_of(x, y, np.maximum(
                np.abs(y), 127 * np.maximum(s0, s1)), maxulp=MAXULP)
        params, sstate = a
    return {"engaged": engaged, "dropped": not dropped}


@pytest.mark.parametrize("form", ["nested", "flat"])
def test_wire_mean_is_the_plain_mean_to_two_ulps(device_pool, form):
    got = _run(device_pool, f"reduce_gap({form!r})")
    assert got["engaged"], got


@pytest.mark.parametrize("form", ["nested", "flat"])
def test_one_device_and_bf16_keep_the_plain_mean(device_pool, form):
    got = _run(device_pool, f"plain_means({form!r})")
    assert got == {"one_device": False, "bf16": False, "f32": True}, got


def test_grad_through_the_tagged_reduce_is_the_unfused_grad(device_pool):
    got = _run(device_pool, "grad_gap()")
    assert got == {"engaged": True, "bitwise": True}, got


def test_nested_round_with_the_wire_is_the_plain_round(device_pool):
    got = _run(device_pool, "round_gap()")
    assert got == {"engaged": True, "dropped": True}, got


def test_the_plan_on_the_mesh_keeps_two_reduce_stages(device_pool):
    got = _run(device_pool, "plan_on_the_mesh()")
    assert got == {"stages": [["clients", "pods", None],
                              ["pods", "server", "int8"]]}, got
