"""Tests for the compiled plan executor (PR-5).

Acceptance bar: ``plan.compile(...)(*args)`` is BITWISE-equal to
``run_plan`` on CPU for every control-flow program class; executables are
cached by (fingerprint, mesh, avals) so hot loops trigger exactly one trace
across N rounds — and across an elastic pod-count shrink the per-client leg
never recompiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as drjax
from repro import optim
from repro.algorithms.async_rounds import make_async_local_sgd_round
from repro.algorithms.rounds import (
    LocalSGDConfig,
    make_hierarchical_local_sgd_round,
    make_local_sgd_round,
    make_multi_round,
)
from repro.core import interpreter as interp
from repro.runtime import executor as executor_lib
from repro.runtime.elastic import make_elastic_hierarchical_round
from repro.runtime.executor import TraceCounter, compile_plan, fuse_stages
from _ulp import assert_within_ulp


def assert_compiled_bitwise(plan, args, maxulp=0, **compile_kwargs):
    compiled = plan.compile(**compile_kwargs)
    outs = compiled(*args)
    ref = drjax.run_plan(plan, *args)
    assert len(outs) == len(ref)
    for a, b in zip(outs, ref):
        if maxulp:
            assert_within_ulp(a, b, maxulp=maxulp)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return compiled


def quadratic_setup(n=4, steps=2, dim=3):
    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (dim,)),
        "b": jnp.float32(0.0),
    }
    data = {
        "x": jax.random.normal(jax.random.PRNGKey(1), (n, steps, 8, dim)),
        "y": jax.random.normal(jax.random.PRNGKey(2), (n, steps, 8)),
    }
    return loss_fn, params, data


# ---------------------------------------------------------------------------
# bitwise parity with run_plan (the §5 oracle), per control-flow class
# ---------------------------------------------------------------------------


class TestCompiledBitwise:
    def test_flat_broadcast_reduce(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: a * b + 1.0, (xb, ys))
            return drjax.reduce_mean(z)

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        assert_compiled_bitwise(plan, args)

    def test_gradient_program(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: (a - b) ** 2, (xb, ys))
            return drjax.reduce_mean(z)

        args = (jnp.float32(0.5), jnp.array([1.0, 2.0, 3.0]))
        gf = jax.grad(f)
        plan = drjax.build_plan(jax.make_jaxpr(jax.jit(gf))(*args), 3)
        assert_compiled_bitwise(plan, args)

    @pytest.mark.parametrize("loops", ["native", "unroll", "auto"])
    def test_scan_loop_stage(self, loops):
        @drjax.program(partition_size=3)
        def prog(m, ys):
            def body(m, _):
                g = drjax.reduce_mean(
                    drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), ys))
                )
                return m - 0.5 * g, g

            m, gs = jax.lax.scan(body, m, None, length=2)
            return m, gs

        args = (jnp.float32(0.3), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        assert_compiled_bitwise(plan, args, loops=loops)

    def test_scan_with_xs_and_consumed_ys(self):
        @drjax.program(partition_size=3)
        def prog(m, all_data):
            def body(m, data):
                g = drjax.reduce_mean(
                    drjax.map_fn(
                        lambda a, b: a - b, (drjax.broadcast(m), data)
                    )
                )
                return m - 0.5 * g, g

            m, gs = jax.lax.scan(body, m, all_data)
            return m + jnp.sum(gs), gs

        args = (
            jnp.float32(0.3),
            jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        )
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        # This is the interpreter oracle's documented last-ulp case (see
        # test_interpreter_controlflow.test_loop_xs_and_ys_emission): XLA's
        # fusion of the post-scan consumption reassociates one add chain, so
        # op-by-op and fused execution differ in the final ulp. The same
        # 1-ulp bar applies to the compiled executor; every program the
        # oracle holds bitwise stays bitwise here too (tests above/below).
        compiled = plan.compile()
        ref = drjax.run_plan(plan, *args)
        for a, b in zip(compiled(*args), ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-7
            )

    def test_reverse_scan(self):
        @drjax.program(partition_size=3)
        def prog(m, ys):
            def body(m, _):
                g = drjax.reduce_mean(
                    drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), ys))
                )
                return m - 0.5 * g, g

            m, gs = jax.lax.scan(body, m, None, length=2, reverse=True)
            return m, gs

        args = (jnp.float32(0.3), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        assert_compiled_bitwise(plan, args)

    def test_while_with_comm(self):
        @drjax.program(partition_size=4)
        def prog(x, ys):
            def cond_fn(c):
                i, acc = c
                return i < 3

            def body_fn(c):
                i, acc = c
                contrib = drjax.reduce_sum(
                    drjax.map_fn(
                        lambda a, b: a * b, (drjax.broadcast(acc), ys)
                    )
                )
                return i + 1, acc + 0.1 * contrib

            i, acc = jax.lax.while_loop(cond_fn, body_fn, (0, x))
            return acc

        # same args as the controlflow oracle test (the bitwise bar is
        # defined over the oracle suite's programs)
        args = (jnp.float32(0.5), jnp.array([1.0, 2.0, 3.0, 4.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 4)
        assert_compiled_bitwise(plan, args)

    def test_while_with_comm_in_predicate(self):
        @drjax.program(partition_size=4)
        def adaptive(x, ys):
            def cond_fn(c):
                i, acc = c
                spread = drjax.reduce_max(
                    drjax.map_fn(
                        lambda a, b: a * b, (drjax.broadcast(acc), ys)
                    )
                )
                return (spread < 10.0) & (i < 10)

            def body_fn(c):
                i, acc = c
                g = drjax.reduce_mean(
                    drjax.map_fn(
                        lambda a, b: a + b, (drjax.broadcast(acc), ys)
                    )
                )
                return i + 1, acc + 0.5 * g

            i, acc = jax.lax.while_loop(cond_fn, body_fn, (0, x))
            return acc

        args = (jnp.float32(0.5), jnp.array([1.0, 2.0, 3.0, 4.0]))
        plan = drjax.build_plan(jax.make_jaxpr(adaptive)(*args), 4)
        assert_compiled_bitwise(plan, args)

    def test_cond_with_comm_both_branches(self):
        @drjax.program(partition_size=4)
        def prog(flag, x, ys):
            def comm(ops):
                x, ys = ops
                return drjax.reduce_sum(
                    drjax.map_fn(
                        lambda a, b: a * b, (drjax.broadcast(x), ys)
                    )
                )

            def local(ops):
                x, ys = ops
                return x * 2.0

            return jax.lax.cond(flag, comm, local, (x, ys))

        ys = jnp.array([1.0, 2.0, 3.0, 4.0])
        plan = drjax.build_plan(
            jax.make_jaxpr(prog)(True, jnp.float32(2.0), ys), 4
        )
        for flag in (True, False):
            assert_compiled_bitwise(
                plan, (jnp.asarray(flag), jnp.float32(2.0), ys)
            )

    def test_local_sgd_round(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn = make_local_sgd_round(loss_fn, optim.sgd(0.05), server, cfg)
        sstate = server.init(params)
        plan = drjax.build_plan(
            jax.make_jaxpr(jax.jit(round_fn))(params, sstate, data), 4
        )
        flat = jax.tree_util.tree_leaves((params, sstate, data))
        # 1 ulp, not bitwise: the round's loss is a fused reduction whose
        # accumulation order differs between op-by-op and compiled (_ulp.py).
        assert_compiled_bitwise(plan, flat, maxulp=1)

    def test_async_round(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn, init_pending = make_async_local_sgd_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        pending = init_pending(params)
        sstate = server.init(params)
        plan = drjax.build_plan(
            jax.make_jaxpr(jax.jit(round_fn))(params, pending, sstate, data),
            4,
        )
        flat = jax.tree_util.tree_leaves((params, pending, sstate, data))
        # 1 ulp: fused loss reduction, as in test_local_sgd_round.
        assert_compiled_bitwise(plan, flat, maxulp=1)

    def test_multi_round_trainer(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn = make_local_sgd_round(loss_fn, optim.sgd(0.05), server, cfg)
        sstate = server.init(params)
        trainer = make_multi_round(round_fn, 3)
        all_data = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * 3), data
        )
        plan = drjax.build_plan(
            jax.make_jaxpr(jax.jit(trainer))(params, sstate, all_data), 4
        )
        flat = jax.tree_util.tree_leaves((params, sstate, all_data))
        for loops in ("native", "unroll"):
            # 1 ulp: fused loss reduction, as in test_local_sgd_round.
            assert_compiled_bitwise(plan, flat, maxulp=1, loops=loops)

    def test_hierarchical_two_level_reduce(self):
        @drjax.program(placements={"pods": 2, "clients": 4})
        def pod_round(model, tasks):
            model_b = drjax.broadcast(model)
            grads = drjax.map_fn(
                lambda m, t: 2.0 * (m - t), (model_b, tasks)
            )
            pod_partials = drjax.reduce_mean(grads, placement="clients")
            return drjax.reduce_mean(pod_partials, placement="pods")

        tasks = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
        args = (jnp.float32(0.5), tasks)
        plan = drjax.build_plan(
            jax.make_jaxpr(pod_round)(*args), {"pods": 2, "clients": 4}
        )
        assert_compiled_bitwise(plan, args)

    def test_repeated_inline_of_cached_jaxpr(self):
        summarize = jax.jit(lambda xs: drjax.reduce_mean(xs))

        @drjax.program(partition_size=3)
        def f(a, b):
            return (
                summarize(drjax.broadcast(a)),
                summarize(drjax.broadcast(b)),
            )

        args = (jnp.float32(1.0), jnp.float32(5.0))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        assert_compiled_bitwise(plan, args)


# ---------------------------------------------------------------------------
# executable cache + no-retrace invariants
# ---------------------------------------------------------------------------


class TestExecutableCache:
    def _plan_and_args(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            return drjax.reduce_sum(
                drjax.map_fn(lambda a, b: a * b, (drjax.broadcast(x), ys))
            )

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        return (
            lambda: drjax.build_plan(jax.make_jaxpr(f)(*args), 3),
            args,
        )

    def test_one_trace_across_rounds(self):
        build, args = self._plan_and_args()
        compiled = build().compile()
        for _ in range(10):
            compiled(*args)
        assert compiled.trace_count == 1

    def test_replan_hits_cache(self):
        """A structurally identical re-built plan shares the executable:
        same fingerprint, zero new traces."""
        build, args = self._plan_and_args()
        c1 = build().compile()
        c1(*args)
        c2 = build().compile()
        c2(*args)
        assert c2.fingerprint == c1.fingerprint
        assert c2.trace_count == 1  # the SAME entry, not a second trace

    def test_different_consts_different_fingerprint(self):
        """Captured const VALUES are part of the fingerprint — two programs
        differing only in a closed-over constant must not share."""

        def build(cval):
            const = jnp.array([cval, 2.0, 3.0])

            @drjax.program(partition_size=3)
            def f(x):
                return drjax.reduce_sum(drjax.broadcast(x) * const)

            return drjax.build_plan(jax.make_jaxpr(f)(jnp.float32(1.0)), 3)

        f1 = executor_lib.plan_fingerprint(build(1.0))
        f2 = executor_lib.plan_fingerprint(build(7.0))
        assert f1 != f2

    def test_new_shapes_are_a_new_entry(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            return drjax.reduce_sum(
                drjax.map_fn(lambda a, b: a * b, (drjax.broadcast(x), ys))
            )

        a1 = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        a2 = (
            jnp.float32(2.0),
            jnp.stack([jnp.array([1.0, 2.0, 3.0])] * 2, axis=1),
        )
        plan = drjax.build_plan(jax.make_jaxpr(f)(*a1), 3)
        compiled = plan.compile()
        compiled(*a1)
        # second aval set: separate cache entry, each traced exactly once
        plan2 = drjax.build_plan(jax.make_jaxpr(f)(*a2), 3)
        c2 = plan2.compile()
        c2(*a2)
        assert compiled.trace_count == 1
        assert c2.trace_count == 1

    def test_donation_frees_carried_args(self):
        build, args = self._plan_and_args()
        compiled = build().compile(donate_argnums=(0,))
        x = jnp.float32(5.0)
        compiled(x, args[1])
        assert x.is_deleted()

    def test_multi_round_trainer_one_trace(self):
        """make_multi_round(jit=True): N rounds + repeated meta-calls are
        exactly ONE trace; carries donated into the executable."""
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn = make_local_sgd_round(loss_fn, optim.sgd(0.05), server, cfg)
        num_rounds = 3
        counter = TraceCounter()
        trainer = make_multi_round(
            counter.wrap(round_fn), num_rounds, jit=True
        )
        all_data = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * num_rounds), data
        )
        params_i, sstate_i = params, server.init(params)
        for _ in range(4):  # 4 meta-calls x 3 rounds each
            params_i, sstate_i, _ = trainer(params_i, sstate_i, all_data)
        assert counter.count == 1  # one trace total, not one per round/call
        # donated carry: the pre-call buffers are gone
        assert all(
            l.is_deleted()
            for l in jax.tree_util.tree_leaves(params)
        )

    def test_donated_round_builder(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        ref_round = make_local_sgd_round(loss_fn, optim.sgd(0.05), server, cfg)
        hot_round = make_local_sgd_round(
            loss_fn, optim.sgd(0.05), server, cfg, donate=True
        )
        sstate = server.init(params)
        ref = ref_round(params, sstate, data)
        out = hot_round(params, sstate, data)
        for a, b in zip(
            jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(out)
        ):
            # 1 ulp: the eager round and the jitted one reduce the loss in
            # different orders (_ulp.py).
            assert_within_ulp(b, a)
        # the donated form consumed its inputs
        assert all(
            l.is_deleted() for l in jax.tree_util.tree_leaves(params)
        )


# ---------------------------------------------------------------------------
# stage fusion
# ---------------------------------------------------------------------------


class TestStageFusion:
    def test_adjacent_local_stages_fuse(self):
        """Interleaved server/group compute: run_plan sees alternating
        GROUP/SERVER stages, the executor one fused unit per local run."""

        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: a * b, (xb, ys))  # group
            s = x * 3.0  # server, adjacent to group compute
            z2 = drjax.map_fn(lambda a: a + 1.0, z)  # group again
            return drjax.reduce_sum(z2) + s

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        kinds = [s.kind for s in plan.stages]
        locals_ = [k for k in kinds if k in ("GROUP_COMPUTE", "SERVER_COMPUTE")]
        fused = fuse_stages(plan.stages)
        fused_locals = [s for s in fused if s.kind == "FUSED_COMPUTE"]
        assert len(fused_locals) < len(locals_) or len(locals_) == 1
        assert len(fused) <= len(plan.stages)
        # and fusion does not change results
        assert_compiled_bitwise(plan, args)

    def test_compiled_plan_reports_stage_units(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(drjax.broadcast(x) * 2.0)

        plan = drjax.build_plan(jax.make_jaxpr(f)(jnp.float32(1.0)), 3)
        compiled = plan.compile()
        assert compiled.num_stage_units <= len(plan.stages)


# ---------------------------------------------------------------------------
# elastic per-placement-level split
# ---------------------------------------------------------------------------


class TestElasticSplit:
    def _setup(self, num_pods=4, clients_per_pod=2, steps=2):
        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"] + params["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        params = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (3,)),
            "b": jnp.float32(0.0),
        }
        data = {
            "x": jax.random.normal(
                jax.random.PRNGKey(1), (num_pods, clients_per_pod, steps, 8, 3)
            ),
            "y": jax.random.normal(
                jax.random.PRNGKey(2), (num_pods, clients_per_pod, steps, 8)
            ),
        }
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(
            partition_size=clients_per_pod,
            num_local_steps=steps,
            num_pods=num_pods,
        )
        return loss_fn, params, data, server, cfg

    def test_matches_hierarchical_round(self):
        loss_fn, params, data, server, cfg = self._setup()
        hier = make_hierarchical_local_sgd_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        elastic = make_elastic_hierarchical_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        sstate = server.init(params)
        ref = hier(params, sstate, data)
        out = elastic.step(params, sstate, data)
        for a, b in zip(
            jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(out)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6
            )

    def test_pod_shrink_never_recompiles_client_leg(self):
        """Elastic pod dropout: the per-client executable is reused (ZERO
        new traces); only the cross-pod leg compiles for the new pod count."""
        loss_fn, params, data, server, cfg = self._setup(num_pods=4)
        elastic = make_elastic_hierarchical_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        sstate = server.init(params)
        elastic.step(params, sstate, data)
        assert elastic.client_trace_count == 1
        assert elastic.cross_compile_count == 1

        # a pod drops out: 4 -> 3
        data3 = jax.tree_util.tree_map(lambda x: x[:3], data)
        out3 = elastic.step(params, sstate, data3)
        assert elastic.client_trace_count == 1  # NEVER recompiled
        assert elastic.cross_compile_count == 2  # only the cross-pod leg

        # and the shrunken round is still the hierarchical round at P=3
        import dataclasses as _dc

        hier3 = make_hierarchical_local_sgd_round(
            loss_fn, optim.sgd(0.05), server, _dc.replace(cfg, num_pods=3)
        )
        ref3 = hier3(params, sstate, data3)
        for a, b in zip(
            jax.tree_util.tree_leaves(ref3), jax.tree_util.tree_leaves(out3)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6
            )

    def test_pod_regrow_reuses_both_legs(self):
        loss_fn, params, data, server, cfg = self._setup(num_pods=4)
        elastic = make_elastic_hierarchical_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        sstate = server.init(params)
        elastic.step(params, sstate, data)
        data3 = jax.tree_util.tree_map(lambda x: x[:3], data)
        elastic.step(params, sstate, data3)
        elastic.step(params, sstate, data)  # pod comes back
        assert elastic.client_trace_count == 1
        assert elastic.cross_compile_count == 2  # P=4 leg was cached


# ---------------------------------------------------------------------------
# serve scheduler: compiled prefill/decode (satellite)
# ---------------------------------------------------------------------------


class TestServeCompiled:
    def test_prefill_traces_once_per_shape(self):
        from repro.launch.serve import BatchScheduler, Request, chunk_schedule
        from repro.models import registry

        cfg = registry.get_config("stablelm_3b").reduced()
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        max_new = 3
        sched = BatchScheduler(cfg, params, batch=2, max_len=6 + max_new)

        def wave():
            reqs = [
                Request(
                    rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, (6,)).astype(
                        np.int32
                    ),
                    max_new=max_new,
                )
                for i in range(2)
            ]
            return sched.run_wave(reqs)

        wave()
        # chunked prefill traces one executable per power-of-two bucket the
        # prompt decomposes into (6 -> [4, 2]), not one per prompt shape
        assert sched.prefill_traces == len(chunk_schedule(6, sched.chunk))
        wave()  # same prompt shape: no retrace
        assert sched.prefill_traces == len(chunk_schedule(6, sched.chunk))


# ---------------------------------------------------------------------------
# weak scaling: compiled executor vs jitted program under a real device mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_compiled_plan_weak_scales_with_group_count(device_pool):
    """The compiled executor under a device mesh matches the jitted-program
    baseline bitwise at every group count of the weak-scaling sweep (groups
    per device held constant as REPRO_HOST_DEVICES grows), traces once per
    shape, and keeps the partitioned intermediates sharded (per-device temp
    bytes stay flat as groups double — the Fig. 6 property the sharding
    constraints exist to deliver)."""
    import textwrap

    res = device_pool.run(textwrap.dedent(
        """
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro import compat
        from repro import core as drjax
        from repro.core import interpreter as interp
        from repro.launch.mesh import mesh_for_placements, placement_axes_for
        from repro.runtime.executor import compile_plan

        n_dev = jax.device_count()
        mesh = mesh_for_placements({"clients": n_dev})
        D = 100  # differs from every swept group count

        def build(groups, ann):
            spec = {"clients": groups}
            paxes = placement_axes_for(mesh, spec)

            @drjax.program(placements=spec, partition_axes=paxes, mesh=mesh,
                           use_sharding_annotations=ann)
            def f(x):
                y = drjax.broadcast(x)
                z = drjax.map_fn(lambda a: jnp.tanh(a @ a), y)
                return drjax.reduce_mean(z)

            return f, paxes

        x = jnp.eye(D, dtype=jnp.float32) * 0.5
        out = {"bitwise": [], "traces": [], "temps": {}}
        for groups in (n_dev, 2 * n_dev):
            f, paxes = build(groups, True)
            plan = interp.build_plan(interp.trace(f, x), f.drjax_context)
            compiled = compile_plan(plan, mesh=mesh, placement_axes=paxes)
            with compat.set_mesh(mesh):
                got = compiled(x)
                got = compiled(x)  # second call: no retrace
                ref = jax.jit(f)(x)  # no-donate: bitwise baseline reuses x
            out["traces"].append(compiled.trace_count)
            out["bitwise"].append(bool(
                np.array_equal(np.asarray(got[0]), np.asarray(ref))
            ))
        # Fig. 6 property at the largest count: with annotations the (2n, D,
        # D) partitioned temps live sharded 1/n per device; without, at
        # least one fully-replicated copy materializes.
        for name, ann in (("drjax", True), ("ns", False)):
            f, _ = build(2 * n_dev, ann)
            with compat.set_mesh(mesh):
                c = jax.jit(f).lower(x).compile()  # no-donate: measurement
            out["temps"][name] = int(c.memory_analysis().temp_size_in_bytes)
        print(json.dumps(out))
        """
    ))
    assert all(res["bitwise"]), res
    assert res["traces"] == [1, 1], res
    assert res["temps"]["drjax"] < res["temps"]["ns"], res
