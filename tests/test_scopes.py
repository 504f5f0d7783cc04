"""The program names every leg of a round: its ``jax.named_scope``s reach the
compiled HLO as ``op_name`` metadata, where a profile's reader finds them.

The building blocks bind under ``drjax.<op>[<placement>]``; the round's own
legs under ``client_step``, ``clip``, ``client_opt``, ``client_delta`` and
``server_update``. JAX adds ``transpose(`` (the backward pass) and
``rematted_computation`` (the recomputed forward under full remat).
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import core as drjax
from repro.launch import train as train_lib
from repro.models import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(fn, *args) -> set:
    return set(OP_NAME.findall(jax.jit(fn).lower(*args).compile().as_text()))


def _has(names, scope) -> bool:
    return any(scope in n for n in names)


@functools.lru_cache(maxsize=None)
def _local_sgd_names(compression=None) -> frozenset:
    """The op_names of the reduced local-SGD round of ``launch.train``,
    full remat, with ``compression`` of the clients' deltas."""
    argv = ["--arch", "lm_350m", "--reduced", "--cohort", "2",
            "--local-steps", "2", "--batch", "2", "--seq", "16"]
    if compression:
        argv += ["--compression", compression]
    args = train_lib.parse_args(argv)
    cfg = dataclasses.replace(registry.get_config("lm_350m").reduced(),
                              remat="full")
    step, server_opt = train_lib.build_round_fn(cfg, args)
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    batch = {k: jax.ShapeDtypeStruct((2, 2, 2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    text = step.lower(params, jax.eval_shape(server_opt.init, params),
                      batch).compile().as_text()
    return frozenset(OP_NAME.findall(text))


@pytest.mark.parametrize("scope", [
    "drjax.broadcast[clients]", "drjax.map[clients]",
    "drjax.reduce_mean[clients]", "client_step", "clip", "client_opt",
    "client_delta", "server_update", "transpose(", "rematted_computation",
])
def test_local_sgd_round_names_its_legs(scope):
    # Only a compressed round forms per-client deltas (``client_delta``);
    # the uncompressed one forms its mean delta under ``server_update``.
    names = _local_sgd_names("int8" if scope == "client_delta" else None)
    assert _has(names, scope), sorted(names)[:20]


def test_uncompressed_round_forms_its_delta_on_the_server():
    """The uncompressed round's clients hand the reduction their parameters:
    no client forms a delta, and the server subtracts the global from the
    mean."""
    names = _local_sgd_names()
    assert not _has(names, "client_delta"), sorted(names)[:20]
    assert _has(names, "server_update/sub"), sorted(names)[:20]


def _flat(body):
    return drjax.program(partition_size=3)(body)


def _nested(body):
    return drjax.program(placements={"pods": 2, "clients": 3})(body)


def _staged(body):
    return drjax.program(placements={"stages": 2, "clients": 3},
                         placement_kinds={"stages": "stages"})(body)


X = jnp.arange(3 * 4, dtype=jnp.float32).reshape(3, 4)
XX = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)


def _mul(a, b):
    return jnp.sin(a * b)


@pytest.mark.parametrize("fn,args,scopes", [
    (_flat(lambda x, y: drjax.reduce_sum(drjax.map_fn(
        _mul, (drjax.broadcast(x), y)))), (X[0], X),
     ["drjax.broadcast[clients]", "drjax.map[clients]",
      "drjax.reduce_sum[clients]"]),
    (_flat(lambda x: drjax.reduce_max(jnp.sin(x))), (X,),
     ["drjax.reduce_max[clients]"]),
    (_flat(lambda x, w: drjax.reduce_weighted_mean(jnp.sin(x), w)),
     (X, X[:, 0]), ["drjax.reduce_weighted_mean[clients]"]),
    (_flat(lambda x, m: drjax.masked_reduce_mean(jnp.sin(x), m)),
     (X, X[:, 1]), ["drjax.masked_reduce_mean[clients]"]),
    (_nested(lambda x, y: drjax.reduce_mean(
        drjax.map_fn(_mul, (drjax.broadcast(x), y)))), (X[0], XX),
     ["drjax.map[pods+clients]", "drjax.reduce_mean[clients]",
      "drjax.reduce_mean[pods]"]),
    (_nested(lambda x, y: drjax.reduce_mean(drjax.map_fn(
        _mul, (drjax.broadcast(x, placement="pods"), y), placement="pods"),
        placement="pods")), (X[0], XX[:, 0]),
     ["drjax.broadcast[pods]", "drjax.map[pods]", "drjax.reduce_mean[pods]"]),
    (_nested(lambda x: drjax.reduce_mean(
        drjax.map_fn(jnp.sin, x, placement="clients"), placement="clients")),
     (XX,), ["drjax.map[clients]", "drjax.reduce_mean[clients]"]),
    (_staged(lambda x: drjax.stage_transfer(jnp.sin(x))), (XX,),
     ["drjax.stage_transfer[stages]"]),
    (_staged(lambda x: drjax.stage_map([jnp.sin, jnp.cos], x)), (XX,),
     ["drjax.stage_map[stages]"]),
])
def test_building_blocks_bind_under_their_scope(fn, args, scopes):
    names = _op_names(fn, *args)
    for scope in scopes:
        assert _has(names, scope), (scope, sorted(names))


def test_hierarchical_int8_round_names_both_reduce_levels(device_pool):
    """The pod-hierarchical int8 round on a (pod 2, data 2) mesh of the
    pool's virtual devices: the intra-pod reduce with its compression and
    the cross-pod reduce each carry their level."""
    out = device_pool.run(f"""
        import dataclasses, json, re, sys
        sys.path.insert(0, {REPO!r})
        import jax, jax.numpy as jnp
        import chip_smoke
        from repro import compat
        from repro.launch import train as train_lib
        from repro.models import registry

        args = train_lib.parse_args(
            ["--arch", "lm_350m", "--reduced", "--cohort", "4",
             "--local-steps", "1", "--batch", "2", "--seq", "16"])
        cfg = registry.get_config("lm_350m").reduced()
        step, layout, server_opt, _ = chip_smoke.hier_round(
            cfg, args, jax.devices()[:4])
        mesh = layout.mesh
        rep = compat.replicated_sharding(mesh)
        described = lambda t, sh: jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), t)
        params = jax.eval_shape(
            lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
        sstate = jax.eval_shape(server_opt.init, params)
        shape = (chip_smoke.PODS, 2, 1, 2, 16)
        data = compat.named_sharding(mesh, ("pod", "data"))
        batch = {{k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=data)
                 for k in ("tokens", "labels")}}
        text = step.lower(
            described(params, rep), described(sstate, rep), batch
        ).compile().as_text()
        print(json.dumps(sorted(set(re.findall(r'op_name="([^"]*)"', text)))))
    """)
    names = set(out)
    assert (_has(names, "drjax.reduce_compress[clients]")
            or _has(names, "drjax.compress[clients]"))
    assert _has(names, "drjax.reduce_mean[pods]")
    assert _has(names, "client_step") and _has(names, "server_update")


# Opcodes of the instructions that move data between devices.
COLLECTIVE = re.compile(
    r"= [^=]*? (all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def test_every_collective_of_the_nested_round_names_its_primitive(
        device_pool):
    """The nested int8 round of ``launch.train --pods 2`` on a (pod 2,
    data 2) mesh of the pool's virtual devices: every collective the
    compiler put in, and every op of the fused reduce+compress (here the
    jnp oracle's fusion), binds under a ``drjax.`` scope, where the leg
    readers find them."""
    lines = device_pool.run(f"""
        import json, re
        import jax, jax.numpy as jnp
        from repro.launch import train as train_lib
        from repro.models import registry

        args = train_lib.parse_args(
            ["--arch", "lm_350m", "--pods", "2", "--cohort", "8",
             "--local-steps", "1", "--batch", "2", "--seq", "16",
             "--compression", "int8"])
        cfg = registry.get_config("lm_350m").reduced()
        devices = jax.devices()[:4]
        layout = train_lib.round_layout(args, devices)
        step, server_opt = train_lib.build_round_fn(cfg, args, layout.mesh)
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        params, sstate = layout.state((params, server_opt.init(params)))
        shape = (8, 1, 2, 16)
        batch = layout.batch({{k: jnp.zeros(shape, jnp.int32)
                              for k in ("tokens", "labels")}})
        text = step.lower(params, sstate, batch).compile().as_text()
        print(json.dumps([l for l in text.splitlines() if " = " in l]))
    """)
    collectives = [line for line in lines if COLLECTIVE.search(line)]
    # A reducer's parameters carry the op_name of the reduce they serve,
    # without the enclosing scopes; they are no op of their own.
    kernel = [line for line in lines if "reduce_compress_roundtrip" in line
              and " parameter(" not in line]
    assert len(collectives) >= 2 and kernel
    for line in collectives + kernel:
        (op_name,) = OP_NAME.findall(line)
        assert "drjax." in op_name, line


def test_train_profiles_the_rounds_it_is_asked_for(tmp_path):
    """``--profile-dir`` with ``--profile-rounds 0:3`` over a failure at
    round 2: the trace's host plane holds each round as a step, its four
    spans, the checkpoint saves and the restore."""
    from jax.profiler import ProfileData

    log_dir = tmp_path / "profile"
    args = train_lib.parse_args(
        ["--arch", "lm_350m", "--reduced", "--rounds", "3", "--cohort", "2",
         "--local-steps", "1", "--batch", "2", "--seq", "16",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "1",
         "--fail-at", "2", "--profile-dir", str(log_dir),
         "--profile-rounds", "0:3"])
    result = train_lib.train(args)
    assert len(result["history"]) == 3 and result["stats"]["restarts"] == 1
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"round", "sample", "dispatch", "wait", "readback",
            "checkpoint_save", "restore"} <= names


def test_profile_rounds_takes_start_colon_end():
    assert train_lib._round_range("2:5") == range(2, 5)
    with pytest.raises(SystemExit):
        train_lib.parse_args(["--profile-rounds", "5"])
