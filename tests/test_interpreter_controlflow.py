"""Tests for the control-flow-aware plan builder (interpreter v2).

Covers the §5 acceptance bar: jitted programs yield the same plan as unjitted
ones; scans/whiles/conds whose bodies communicate become explicit
LOOP/COND stages with sub-plans; `run_plan` matches direct execution bitwise
on CPU for the shipped round functions; and `to_beam()` output contains no
undefined names.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as drjax
from repro import optim
from repro.algorithms.async_rounds import make_async_local_sgd_round
from repro.algorithms.rounds import (
    LocalSGDConfig,
    make_local_sgd_round,
    make_multi_round,
)
from repro.core import interpreter as interp
from _ulp import assert_within_ulp


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def stage_kinds(plan):
    return [s.kind for s in plan.stages]


def assert_bitwise(plan, fn, args, maxulp=0):
    """run_plan output == direct execution, bitwise on CPU (or within
    ``maxulp`` where the caller says why)."""
    flat = jax.tree_util.tree_leaves(args)
    outs = drjax.run_plan(plan, *flat)
    direct = jax.tree_util.tree_leaves(fn(*args))
    assert len(outs) == len(direct)
    for a, b in zip(outs, direct):
        if maxulp:
            assert_within_ulp(a, b, maxulp=maxulp)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_GENERATED_NAME = re.compile(
    r"\b(?:t|o|r|bc|g|s|c|lit|x|undef|i|in_)\d+\b"
    r"|\b(?:carry|ys)[\d_]+\b|\bnum_iters_[\w]+\b"
)


def assert_no_undefined_names(beam_text):
    """Every generated identifier in to_beam() is assigned before use."""
    compile(beam_text, "<to_beam>", "exec")  # must at least be valid Python
    assert "undef" not in beam_text and "(bug?)" not in beam_text
    defined = set()
    for lineno, line in enumerate(beam_text.splitlines()):
        code = line.split("#")[0]
        m = re.match(r"\s*(?:for\s+(\w+)\s+in\b|([A-Za-z_]\w*)\s*=[^=])", code)
        lhs = (m.group(1) or m.group(2)) if m else None
        for tok_m in _GENERATED_NAME.finditer(code):
            tok = tok_m.group(0)
            if tok == lhs or tok in defined:
                continue
            raise AssertionError(
                f"undefined name {tok!r} used on line {lineno}: {line!r}"
            )
        if lhs:
            defined.add(lhs)


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
# jit transparency
# ---------------------------------------------------------------------------


class TestJitTransparency:
    def test_jit_plan_equals_unjitted_plan(self):
        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(drjax.broadcast(x) * 2.0)

        x = jnp.float32(1.5)
        plain = drjax.build_plan(jax.make_jaxpr(f)(x), 3)
        jitted = drjax.build_plan(jax.make_jaxpr(jax.jit(f))(x), 3)
        assert stage_kinds(jitted) == stage_kinds(plain)
        assert stage_kinds(jitted) == ["BROADCAST", "GROUP_COMPUTE", "REDUCE"]
        assert_bitwise(jitted, f, (x,))

    def test_nested_jit(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: a * b + 1.0, (xb, ys))
            return drjax.reduce_mean(z)

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        jitted = drjax.build_plan(
            jax.make_jaxpr(jax.jit(jax.jit(f)))(*args), 3
        )
        assert stage_kinds(jitted) == ["BROADCAST", "GROUP_COMPUTE", "REDUCE"]
        assert_bitwise(jitted, f, args)

    def test_jit_with_gradient(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: (a - b) ** 2, (xb, ys))
            return drjax.reduce_mean(z)

        args = (jnp.float32(0.5), jnp.array([1.0, 2.0, 3.0]))
        gf = jax.grad(f)
        plan = drjax.build_plan(jax.make_jaxpr(jax.jit(gf))(*args), 3)
        ops = [s.op for s in plan.stages if isinstance(s, interp.Reduce)]
        assert "reduce_sum" in ops  # transpose of broadcast
        assert_bitwise(plan, gf, args)


# ---------------------------------------------------------------------------
# loops / conds with in-loop communication
# ---------------------------------------------------------------------------


class TestLoopStages:
    def _two_round_prog(self):
        @drjax.program(partition_size=3)
        def two_rounds(m, ys):
            def body(m, _):
                grads = drjax.map_fn(
                    lambda mm, y: mm - y, (drjax.broadcast(m), ys)
                )
                g = drjax.reduce_mean(grads)
                return m - 0.5 * g, g

            m, gs = jax.lax.scan(body, m, None, length=2)
            return m, gs

        return two_rounds, (jnp.float32(0.3), jnp.array([1.0, 2.0, 3.0]))

    def test_scan_with_comm_becomes_loop_stage(self):
        prog, args = self._two_round_prog()
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        assert stage_kinds(plan) == ["LOOP"]
        loop = plan.stages[0]
        assert loop.loop_kind == "scan"
        assert loop.trip_count == 2
        assert stage_kinds(loop.body_plan) == [
            "BROADCAST",
            "GROUP_COMPUTE",
            "REDUCE",
            "SERVER_COMPUTE",
        ]

    def test_loop_stage_executes_bitwise(self):
        prog, args = self._two_round_prog()
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        assert_bitwise(plan, prog, args)

    def test_jitted_scan_same_plan(self):
        prog, args = self._two_round_prog()
        plain = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        jitted = drjax.build_plan(jax.make_jaxpr(jax.jit(prog))(*args), 3)
        assert stage_kinds(jitted) == stage_kinds(plain)
        assert stage_kinds(jitted.stages[0].body_plan) == stage_kinds(
            plain.stages[0].body_plan
        )
        assert_bitwise(jitted, prog, args)

    def test_in_loop_communication_is_explicit(self):
        prog, args = self._two_round_prog()
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        comm = plan.communication_stages(recursive=True)
        kinds = [s.kind for s in comm]
        assert "BROADCAST" in kinds and "REDUCE" in kinds
        # top-level has none: all communication lives inside the loop
        assert plan.communication_stages(recursive=False) == []
        txt = plan.to_text()
        assert "LOOP[scan] trip_count=2" in txt
        assert "BROADCAST server->groups" in txt

    def test_scan_without_comm_stays_local(self):
        """A purely local client loop must NOT become a LoopStage."""

        @drjax.program(partition_size=3)
        def f(x, ys):
            def client(y):
                def step(c, _):
                    return c * 0.5 + y, c

                out, _ = jax.lax.scan(step, y, None, length=3)
                return out

            z = drjax.map_fn(client, ys)
            return drjax.reduce_sum(z)

        args = (jnp.float32(0.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        assert "LOOP" not in stage_kinds(plan)
        assert_bitwise(plan, f, args)

    def test_repeated_inline_of_cached_jaxpr(self):
        """jit caches one jaxpr per function; inlining it at two call sites
        must alpha-rename, not alias the second call's values over the
        first's."""
        summarize = jax.jit(lambda xs: drjax.reduce_mean(xs))

        @drjax.program(partition_size=3)
        def f(a, b):
            return (
                summarize(drjax.broadcast(a)),
                summarize(drjax.broadcast(b)),
            )

        args = (jnp.float32(1.0), jnp.float32(5.0))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        outs = drjax.run_plan(plan, *args)
        assert [float(o) for o in outs] == [1.0, 5.0]

    def test_while_cond_communication_is_explicit(self):
        """Communication inside the while predicate (adaptive stopping) must
        appear in the plan, not vanish into an opaque cond_jaxpr."""

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
        (loop,) = [s for s in plan.stages if isinstance(s, interp.LoopStage)]
        assert loop.cond_plan is not None
        ops = [
            getattr(s, "op", "")
            for s in plan.communication_stages(recursive=True)
        ]
        assert "reduce_max" in ops  # the per-iteration predicate reduce
        assert "cond:" in plan.to_text()
        assert_bitwise(plan, adaptive, args)

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

        args = (jnp.float32(0.5), jnp.array([1.0, 2.0, 3.0, 4.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 4)
        loops = [s for s in plan.stages if isinstance(s, interp.LoopStage)]
        assert len(loops) == 1
        assert loops[0].loop_kind == "while"
        assert loops[0].trip_count is None
        assert_bitwise(plan, prog, args)

    def test_cond_with_comm(self):
        @drjax.program(partition_size=4)
        def prog(flag, x, ys):
            def comm(ops):
                x, ys = ops
                return drjax.reduce_sum(
                    drjax.map_fn(lambda a, b: a * b, (drjax.broadcast(x), ys))
                )

            def local(ops):
                x, ys = ops
                return x * 2.0

            return jax.lax.cond(flag, comm, local, (x, ys))

        ys = jnp.array([1.0, 2.0, 3.0, 4.0])
        plan = drjax.build_plan(
            jax.make_jaxpr(prog)(True, jnp.float32(2.0), ys), 4
        )
        conds = [s for s in plan.stages if isinstance(s, interp.CondStage)]
        assert len(conds) == 1
        assert len(conds[0].branch_plans) == 2
        for flag in (True, False):
            assert_bitwise(plan, prog, (flag, jnp.float32(2.0), ys))


# ---------------------------------------------------------------------------
# plans of the shipped algorithms (under jit)
# ---------------------------------------------------------------------------


class TestShippedAlgorithmPlans:
    def _round(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn = make_local_sgd_round(loss_fn, optim.sgd(0.05), server, cfg)
        return round_fn, params, server.init(params), data

    def test_local_sgd_round_under_jit(self):
        round_fn, params, sstate, data = self._round()
        jxp = jax.make_jaxpr(jax.jit(round_fn))(params, sstate, data)
        plan = drjax.build_plan(jxp, 4)
        kinds = stage_kinds(plan)
        # broadcast params -> client compute -> reduce deltas+loss -> server
        assert kinds[0] == "BROADCAST"
        assert "GROUP_COMPUTE" in kinds
        assert "REDUCE" in kinds
        assert kinds[-1] == "SERVER_COMPUTE"
        assert kinds.index("BROADCAST") < kinds.index("GROUP_COMPUTE")
        assert kinds.index("GROUP_COMPUTE") < kinds.index("REDUCE")
        assert_bitwise(plan, round_fn, (params, sstate, data))

    def test_async_round_under_jit(self):
        loss_fn, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn, init_pending = make_async_local_sgd_round(
            loss_fn, optim.sgd(0.05), server, cfg
        )
        pending = init_pending(params)
        sstate = server.init(params)
        jxp = jax.make_jaxpr(jax.jit(round_fn))(params, pending, sstate, data)
        plan = drjax.build_plan(jxp, 4)
        kinds = stage_kinds(plan)
        # server applies the stale delta BEFORE broadcasting
        assert kinds[0] == "SERVER_COMPUTE"
        assert "BROADCAST" in kinds and "REDUCE" in kinds
        assert_bitwise(plan, round_fn, (params, pending, sstate, data))

    def test_multi_round_trainer_has_loop_stage(self):
        round_fn, params, sstate, data = self._round()
        num_rounds = 3
        trainer = make_multi_round(round_fn, num_rounds)
        all_data = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * num_rounds), data
        )
        jxp = jax.make_jaxpr(jax.jit(trainer))(params, sstate, all_data)
        plan = drjax.build_plan(jxp, 4)
        loops = [s for s in plan.stages if isinstance(s, interp.LoopStage)]
        assert len(loops) == 1
        assert loops[0].trip_count == num_rounds
        body_kinds = stage_kinds(loops[0].body_plan)
        assert "BROADCAST" in body_kinds and "REDUCE" in body_kinds
        # 1 ulp: the jitted trainer fuses the per-round loss reduction, which
        # run_plan executes op by op (tests/_ulp.py).
        assert_bitwise(plan, trainer, (params, sstate, all_data), maxulp=1)


# ---------------------------------------------------------------------------
# stage_fns (jaxpr slicing) + beam emitter
# ---------------------------------------------------------------------------


class TestStageFns:
    def test_group_stage_fn_is_callable(self):
        @drjax.program(partition_size=3)
        def f(x, ys):
            xb = drjax.broadcast(x)
            z = drjax.map_fn(lambda a, b: a * b + 1.0, (xb, ys))
            return drjax.reduce_sum(z)

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 3)
        fns = plan.stage_fns()
        # exactly one local stage: the vmapped group compute
        (name,) = fns
        fn = fns[name]
        assert len(fn.input_vars) == 2
        assert len(fn.output_vars) == 1
        xb = np.broadcast_to(np.float32(2.0), (3,))
        ys = np.asarray(args[1])
        # one input is the broadcast output, the other the partitioned plan
        # input; distinguish them by membership in the plan invars
        ins = []
        for v in fn.input_vars:
            if v in plan.jaxpr.jaxpr.invars:
                ins.append(ys)
            else:
                ins.append(xb)
        (out,) = fn(*ins)
        np.testing.assert_allclose(out, xb * ys + 1.0)

    def test_stage_fns_cover_loop_bodies(self):
        @drjax.program(partition_size=3)
        def prog(m, ys):
            def body(m, _):
                g = drjax.reduce_mean(
                    drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), ys))
                )
                return m - g, None

            m, _ = jax.lax.scan(body, m, None, length=2)
            return m

        args = (jnp.float32(0.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        fns = plan.stage_fns()
        # loop body local stages are named stage_0_<i>
        assert any(k.startswith("stage_0_") for k in fns)


class TestBeamEmitter:
    def _maml_plan(self):
        def loss(x, y):
            return (x - y) ** 2

        def maml_loss(model, lr, task):
            g = jax.grad(loss)(model, task)
            return loss(model - lr * g, task)

        @drjax.program(partition_size=3)
        def f(model, lr, tasks):
            model_b = drjax.broadcast(model)
            lr_b = drjax.broadcast(lr)
            losses = drjax.map_fn(maml_loss, (model_b, lr_b, tasks))
            return drjax.reduce_mean(losses)

        args = (jnp.float32(0.1), jnp.float32(0.05), jnp.array([1.0, 2.0, 3.0]))
        return drjax.build_plan(jax.make_jaxpr(f)(*args), 3)

    def test_no_undefined_names_flat(self):
        assert_no_undefined_names(self._maml_plan().to_beam())

    def test_no_undefined_names_loop(self):
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
        beam_text = plan.to_beam()
        assert_no_undefined_names(beam_text)
        assert "for i0 in range(2):" in beam_text

    def test_no_undefined_names_shipped_round(self):
        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"] + params["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        loss_fn_, params, data = quadratic_setup()
        server = optim.fedavg_momentum(1.0)
        cfg = LocalSGDConfig(partition_size=4, num_local_steps=2)
        round_fn = make_local_sgd_round(
            loss_fn_, optim.sgd(0.05), server, cfg
        )
        sstate = server.init(params)
        jxp = jax.make_jaxpr(jax.jit(round_fn))(params, sstate, data)
        plan = drjax.build_plan(jxp, 4)
        assert_no_undefined_names(plan.to_beam())

    def test_stage_fn_names_match_beam_references(self):
        plan = self._maml_plan()
        beam_text = plan.to_beam()
        fns = plan.stage_fns()
        for ref in re.findall(r"fns\['([^']+)'\]", beam_text):
            assert ref in fns, f"beam references unknown stage fn {ref!r}"

    def test_beam_consts_contract(self):
        plan = self._maml_plan()
        beam_text = plan.to_beam()
        n_refs = len(set(re.findall(r"consts\[(\d+)\]", beam_text)))
        assert n_refs <= len(plan.beam_consts())

    def test_beam_consts_dedup_matches_emitter_index(self):
        """A const captured by a helper inlined in two plans must be listed
        once (the emitter's index table dedups; beam_consts must agree)."""
        const = jnp.array([1.0, 2.0, 3.0])
        helper = jax.jit(lambda xs: drjax.reduce_sum(xs * const))

        @drjax.program(partition_size=3)
        def g(a, all_b):
            top = helper(drjax.broadcast(a))

            def body(m, b):
                return m + helper(drjax.broadcast(b)), None

            m, _ = jax.lax.scan(body, top, all_b)
            return m

        args = (jnp.float32(1.0), jnp.arange(2, dtype=jnp.float32))
        plan = drjax.build_plan(jax.make_jaxpr(g)(*args), 3)
        beam_text = plan.to_beam()
        refs = {int(i) for i in re.findall(r"consts\[(\d+)\]", beam_text)}
        consts = plan.beam_consts()
        assert all(r < len(consts) for r in refs)
        # the shared const appears exactly once
        assert len(consts) == 1
        assert_bitwise(plan, g, args)

    def test_loop_xs_and_ys_emission(self):
        """Scan xs/ys plumbing: slice lambdas bind the iteration index as a
        default arg (not late-bound), partitioned xs slices are re-keyed per
        group, and consumed stacked ys become a real stacked PCollection."""

        @drjax.program(partition_size=3)
        def prog(m, all_data):
            def body(m, data):
                g = drjax.reduce_mean(
                    drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), data))
                )
                return m - 0.5 * g, g

            m, gs = jax.lax.scan(body, m, all_data)
            return m + jnp.sum(gs), gs

        args = (jnp.float32(0.3), jnp.arange(6, dtype=jnp.float32).reshape(2, 3))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        beam_text = plan.to_beam()
        assert_no_undefined_names(beam_text)
        # iteration index captured via default arg, not the loop variable
        assert "_i=i0" in beam_text
        # the (T=2, n=3) xs input is @SERVER; its per-round slice is
        # partitioned, so it must be re-keyed into a per-group PCollection
        assert "beam.FlatMap(lambda v: list(enumerate(v)))" in beam_text
        # consumed ys are stacked into one value, not left as a raw list
        assert "beam.Flatten()" in beam_text
        assert "np.stack([v for _, v in sorted(rows)])" in beam_text
        # executor still agrees with direct execution (op-by-op vs fused
        # scan body can differ in the last ulp, hence allclose not bitwise)
        outs = drjax.run_plan(plan, *args)
        for a, b in zip(outs, prog(*args)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6
            )

    def test_partitioned_ys_consumed_downstream(self):
        """A scan body emitting a partitioned per-iteration output: the
        (T, n, ...) stack is server-placed (time axis leads), so downstream
        consumption is SERVER_COMPUTE, and the Beam emitter collects the
        groups into a stacked value rather than leaking raw PCollections."""

        @drjax.program(partition_size=3)
        def prog(m, ys):
            def body(m, _):
                z = drjax.map_fn(
                    lambda a, b: a * b, (drjax.broadcast(m), ys)
                )
                g = drjax.reduce_mean(z)
                return m - 0.1 * g, z

            m, zs = jax.lax.scan(body, m, None, length=2)
            return m, jnp.sum(zs)

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        plan = drjax.build_plan(jax.make_jaxpr(prog)(*args), 3)
        assert stage_kinds(plan) == ["LOOP", "SERVER_COMPUTE"]
        outs = drjax.run_plan(plan, *args)
        for a, b in zip(outs, prog(*args)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        beam_text = plan.to_beam()
        assert_no_undefined_names(beam_text)
        # group ys are collected to a stacked server value inside the loop
        assert "collect groups to a stacked server value" in beam_text

    def test_reduce_of_broadcast_emits_replica_combine(self):
        """reduce(broadcast(x)) must combine n replicas of the server value,
        not call list() on a side-input object."""

        @drjax.program(partition_size=3)
        def f(x):
            return drjax.reduce_sum(drjax.broadcast(x))

        plan = drjax.build_plan(jax.make_jaxpr(f)(jnp.float32(2.0)), 3)
        beam_text = plan.to_beam()
        assert_no_undefined_names(beam_text)
        assert "_reduce_sum([v] * 3)" in beam_text
        assert "list(bc" not in beam_text
        (out,) = drjax.run_plan(plan, jnp.float32(2.0))
        np.testing.assert_allclose(out, 6.0)

    def test_reverse_scan_emits_reversed_iteration(self):
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
        beam_text = plan.to_beam()
        assert "reversed(range(2))" in beam_text
        assert_no_undefined_names(beam_text)
        assert_bitwise(plan, prog, args)

    def test_unstageable_comm_fails_loudly(self):
        """Communication hidden in a higher-order primitive the builder
        cannot stage (custom_linear_solve) must raise, not silently become
        a mislabeled LocalCompute stage."""

        @drjax.program(partition_size=3)
        def f(x, ys):
            def matvec(v):
                return v * 2.0

            def solve(mv, b):
                # a global reduce buried where the builder can't stage it
                return b / drjax.reduce_sum(drjax.broadcast(x))

            return jax.lax.custom_linear_solve(
                matvec, drjax.reduce_sum(ys), solve, solve
            )

        args = (jnp.float32(2.0), jnp.array([1.0, 2.0, 3.0]))
        with pytest.raises(AssertionError, match="not representable"):
            drjax.build_plan(jax.make_jaxpr(f)(*args), 3)

    def test_literal_src_exotic_dtypes(self):
        """bf16 literals must not emit np.bfloat16 (doesn't exist) or
        truncate the value to an int."""
        from repro.core.interpreter import _literal_src

        src = _literal_src(jnp.bfloat16(1.5))
        val = eval(src.split("#")[0], {"np": np})  # noqa: S307 - test-only
        assert float(val) == 1.5
        assert eval(_literal_src(jnp.float32(2.5)), {"np": np}) == np.float32(2.5)
        assert eval(_literal_src(np.int32(7)), {"np": np}) == 7
        assert eval(_literal_src(np.bool_(True)), {"np": np}) is True
