"""Fault tolerance, straggler mitigation, and elastic-scaling tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.runtime import (
    ElasticSchedule,
    FailureInjector,
    StragglerSimulator,
    rescale_partition,
    run_with_recovery,
    straggler_mask,
)
from repro.runtime.failure import SimulatedDeviceFailure
from repro.runtime.stragglers import effective_round_time


class TestRecovery:
    def test_recovers_from_injected_failures(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        injector = FailureInjector(fail_at=[7, 13])
        log = []

        def step_fn(step, state):
            injector.check(step)
            log.append(step)
            return {"x": state["x"] + 1.0}

        final, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0.0)}, num_steps=20,
            checkpoint_mgr=mgr, checkpoint_every=5,
        )
        assert stats["restarts"] == 2
        assert float(final["x"]) == 20.0  # exact replay: no lost/double steps

    def test_exceeding_max_restarts_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))

        def always_fail(step, state):
            raise SimulatedDeviceFailure("boom")

        with pytest.raises(RuntimeError, match="max_restarts"):
            run_with_recovery(
                always_fail, {"x": jnp.float32(0)}, 5, mgr, max_restarts=2
            )

    def test_resume_from_existing_checkpoint(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state0 = {"x": jnp.float32(0.0)}
        mgr.save(10, {"x": jnp.float32(10.0)})

        def step_fn(step, state):
            return {"x": state["x"] + 1.0}

        final, stats = run_with_recovery(step_fn, state0, 15, mgr)
        assert float(final["x"]) == 15.0
        assert stats["completed_steps"] == 5  # only 10..15 re-run


class TestRecoveryHardening:
    def test_non_recoverable_error_fails_fast(self, tmp_path):
        """Programming bugs propagate immediately — no restarts burned on an
        error every replay would hit again."""
        mgr = CheckpointManager(str(tmp_path))
        calls = []

        def step_fn(step, state):
            calls.append(step)
            raise TypeError("programming bug")

        with pytest.raises(TypeError, match="programming bug"):
            run_with_recovery(
                step_fn, {"x": jnp.float32(0)}, 5, mgr, max_restarts=5
            )
        assert calls == [0]

    @pytest.mark.parametrize("message", [
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 18.22G of 15.75G hbm.",
        "INTERNAL: Mosaic failed to compile TPU kernel: ran out of memory "
        "in memory space vmem",
    ])
    def test_compile_and_oom_errors_fail_fast(self, tmp_path, message):
        """A device error that every replay would hit again (the compiler
        refused the round, or it does not fit) propagates on the first try
        with its own message, while a simulated device failure in the same
        run still restores."""
        mgr = CheckpointManager(str(tmp_path))
        injector = FailureInjector(fail_at=[1])
        calls, recoveries = [], []

        def step_fn(step, state):
            calls.append(step)
            injector.check(step)
            if step == 3:
                raise jax.errors.JaxRuntimeError(message)
            return {"x": state["x"] + 1.0}

        with pytest.raises(jax.errors.JaxRuntimeError) as info:
            run_with_recovery(
                step_fn, {"x": jnp.float32(0)}, 5, mgr, checkpoint_every=1,
                on_recovery=lambda i, s: recoveries.append((i, s)),
            )
        assert str(info.value) == message
        assert recoveries == [(1, 1)]  # the simulated failure restored once
        assert calls == [0, 1, 1, 2, 3]  # the fatal step ran once, no replay

    def test_transient_runtime_error_still_restores(self, tmp_path):
        """A JAX runtime error that is neither a compile refusal nor an
        exhausted resource (a lost device) keeps the restore-and-replay."""
        mgr = CheckpointManager(str(tmp_path))
        fired = []

        def step_fn(step, state):
            if step == 2 and not fired:
                fired.append(step)
                raise jax.errors.JaxRuntimeError("UNAVAILABLE: device lost")
            return {"x": state["x"] + 1.0}

        final, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0)}, 4, mgr, checkpoint_every=1,
        )
        assert float(final["x"]) == 4.0
        assert stats["restarts"] == 1

    def test_custom_recoverable_allowlist(self, tmp_path):
        class FlakyStore(Exception):
            pass

        mgr = CheckpointManager(str(tmp_path))
        fired = []

        def step_fn(step, state):
            if step == 2 and not fired:
                fired.append(step)
                raise FlakyStore("transient")
            return {"x": state["x"] + 1.0}

        final, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0)}, 5, mgr,
            recoverable=(FlakyStore,),
        )
        assert float(final["x"]) == 5.0
        assert stats["restarts"] == 1

    def test_scratch_restart_does_not_overcount_progress(self, tmp_path):
        """Regression: a restart from the initial state (no checkpoint yet)
        replays the prefix; completed_steps must count forward progress
        once, with the replays tallied separately."""
        mgr = CheckpointManager(str(tmp_path))
        injector = FailureInjector(fail_at=[3])

        def step_fn(step, state):
            injector.check(step)
            return {"x": state["x"] + 1.0}

        final, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0)}, 5, mgr, checkpoint_every=10
        )
        assert float(final["x"]) == 5.0
        assert stats["scratch_restarts"] == 1
        assert stats["completed_steps"] == 5  # not 5 + the replayed prefix
        assert stats["replayed_steps"] == 3  # steps 0..2 re-run once

    def test_backoff_grows_exponentially(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        injector = FailureInjector(fail_at=[1, 2])

        def step_fn(step, state):
            injector.check(step)
            return {"x": state["x"] + 1.0}

        _, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0)}, 4, mgr,
            backoff_base_s=0.01, backoff_cap_s=30.0,
        )
        assert stats["restarts"] == 2
        # 0.01 * 2**0 + 0.01 * 2**1
        assert stats["backoff_s"] == pytest.approx(0.03)

    def test_backoff_respects_cap(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        injector = FailureInjector(fail_at=[1, 2, 3])

        def step_fn(step, state):
            injector.check(step)
            return {"x": state["x"] + 1.0}

        _, stats = run_with_recovery(
            step_fn, {"x": jnp.float32(0)}, 5, mgr,
            backoff_base_s=0.01, backoff_cap_s=0.015,
        )
        # 0.01, then 0.02 -> capped at 0.015, then 0.04 -> 0.015
        assert stats["backoff_s"] == pytest.approx(0.04)


class TestStragglers:
    def test_mask_respects_deadline(self):
        durations = np.array([1.0, 2.0, 50.0, 3.0])
        mask = straggler_mask(durations, deadline_s=10.0)
        np.testing.assert_array_equal(mask, [1, 1, 0, 1])

    def test_min_finishers_extends_deadline(self):
        durations = np.array([100.0, 200.0, 300.0, 400.0])
        mask = straggler_mask(durations, deadline_s=1.0, min_finishers=2)
        assert mask.sum() == 2
        np.testing.assert_array_equal(mask, [1, 1, 0, 0])

    def test_dropping_cuts_round_time(self):
        sim = StragglerSimulator(median_s=10.0, sigma=0.8)
        durations = sim.durations(round_idx=0, n=64)
        t_all = durations.max()
        deadline = float(np.percentile(durations, 90))
        t_drop = effective_round_time(durations, deadline, min_finishers=32)
        assert t_drop < t_all

    def test_masked_round_unbiased(self):
        """Masked mean equals mean over the finishers exactly."""
        from repro import core as drjax

        @drjax.program(partition_size=6)
        def f(xs, mask):
            return drjax.masked_reduce_mean(xs, mask)

        xs = jnp.arange(6, dtype=jnp.float32)
        mask = jnp.array([1, 1, 0, 1, 0, 1], jnp.float32)
        np.testing.assert_allclose(f(xs, mask), (0 + 1 + 3 + 5) / 4.0)


class TestStragglerEdgeCases:
    def test_min_finishers_equal_n_is_synchronous(self):
        """min_finishers == n keeps every group and waits for the slowest —
        the synchronous limit."""
        d = np.array([5.0, 50.0, 500.0])
        mask = straggler_mask(d, deadline_s=1.0, min_finishers=3)
        np.testing.assert_array_equal(mask, [1, 1, 1])
        assert effective_round_time(d, 1.0, min_finishers=3) == 500.0

    def test_min_finishers_clamped_to_cohort_size(self):
        d = np.array([5.0, 50.0, 500.0])
        big = straggler_mask(d, deadline_s=1.0, min_finishers=10)
        exact = straggler_mask(d, deadline_s=1.0, min_finishers=3)
        np.testing.assert_array_equal(np.asarray(big), np.asarray(exact))
        assert effective_round_time(d, 1.0, min_finishers=10) == 500.0

    def test_zero_min_finishers_means_no_floor(self):
        d = np.array([1.0, 2.0, 50.0])
        none = straggler_mask(d, deadline_s=10.0, min_finishers=None)
        zero = straggler_mask(d, deadline_s=10.0, min_finishers=0)
        np.testing.assert_array_equal(np.asarray(none), np.asarray(zero))

    def test_all_groups_miss_deadline(self):
        """Without a finisher floor an all-miss round yields the all-zero
        mask and the round ends at the deadline (you waited it out)."""
        d = np.array([20.0, 30.0, 40.0])
        mask = straggler_mask(d, deadline_s=10.0)
        np.testing.assert_array_equal(mask, [0, 0, 0])
        assert effective_round_time(d, 10.0) == 10.0

    def test_zero_weight_mask_composes_nan_free(self):
        """The all-zero mask must flow through masked_reduce_mean as zeros,
        not NaN — straggler_mask + masked reduction stay composable in the
        worst case."""
        from repro import core as drjax

        @drjax.program(partition_size=3)
        def f(xs, mask):
            return drjax.masked_reduce_mean(xs, mask)

        d = np.array([20.0, 30.0, 40.0])
        mask = straggler_mask(d, deadline_s=10.0)
        out = np.asarray(f(jnp.array([1.0, 2.0, 3.0]), mask))
        np.testing.assert_array_equal(out, 0.0)

    def test_min_finishers_floor_still_nan_free(self):
        """min_finishers > 0 on an all-miss round extends the deadline, so
        the mask is non-zero and the masked mean is over the k finishers."""
        from repro import core as drjax

        @drjax.program(partition_size=3)
        def f(xs, mask):
            return drjax.masked_reduce_mean(xs, mask)

        d = np.array([20.0, 30.0, 40.0])
        mask = straggler_mask(d, deadline_s=10.0, min_finishers=2)
        np.testing.assert_array_equal(np.asarray(mask), [1, 1, 0])
        np.testing.assert_allclose(
            np.asarray(f(jnp.array([1.0, 2.0, 3.0]), mask)), 1.5
        )
        assert effective_round_time(d, 10.0, min_finishers=2) == 30.0


class TestElastic:
    def test_cohort_size_tracks_devices(self):
        sched = ElasticSchedule(groups_per_device=2)
        assert sched.cohort_size(256) == 512
        assert sched.cohort_size(128) == 256  # one pod lost

    def test_available_mesh_shapes_returns_all_viable(self):
        from repro.runtime.elastic import available_mesh_shapes

        # 16 devices, mp=8: every halved fallback also tiles the pool
        shapes = available_mesh_shapes(16, 8)
        assert shapes == [(2, 8), (4, 4), (8, 2), (16, 1)]
        # preferred shape first even when fallbacks exist
        assert available_mesh_shapes(8, 4)[0] == (2, 4)

    def test_available_mesh_shapes_degraded_pool(self):
        from repro.runtime.elastic import available_mesh_shapes

        # 12 devices can't tile mp=8, but can tile 4, 2, 1
        shapes = available_mesh_shapes(12, 8)
        assert shapes == [(3, 4), (6, 2), (12, 1)]
        # a pool that only fits fully-data-parallel
        assert available_mesh_shapes(7, 4) == [(7, 1)]

    def test_available_mesh_shapes_placement_stack(self):
        """N-level form: inner levels keep their sizes, the OUTERMOST level
        absorbs the degraded pool, axis names come from
        launch.mesh.level_axes_for."""
        from repro.runtime.elastic import available_mesh_shapes

        # full 8-device (pods, clients) pool, one pod lost (6 devices left)
        shapes = available_mesh_shapes(
            6, placements={"pods": 4, "clients": 2}
        )
        assert shapes == [((3, 2), ("pod", "data"))]
        # model parallelism appends the "model" axis, halved fallbacks too
        shapes = available_mesh_shapes(
            16, 4, placements={"pods": 4, "clients": 2}
        )
        assert shapes == [
            ((2, 2, 4), ("pod", "data", "model")),
            ((4, 2, 2), ("pod", "data", "model")),
            ((8, 2, 1), ("pod", "data", "model")),
        ]
        # 3-level superpod stack: only the outermost (superpod) level scales
        shapes = available_mesh_shapes(
            12, placements={"superpods": 2, "pods": 3, "clients": 2}
        )
        assert shapes == [((2, 3, 2), ("superpod", "pod", "data"))]
        # a pool the inner levels can't tile yields no shapes
        assert available_mesh_shapes(
            5, placements={"pods": 4, "clients": 2}
        ) == []

    def test_rescale_shrink_and_grow(self):
        data = {"tokens": np.arange(8 * 3).reshape(8, 3)}
        small = rescale_partition(data, 8, 4)
        assert small["tokens"].shape == (4, 3)
        big = rescale_partition(data, 8, 12)
        assert big["tokens"].shape == (12, 3)

    def test_same_program_smaller_partition(self):
        """The SAME round function (re-jitted) works at any partition size —
        the paper's logical/physical decoupling is what makes this elastic."""
        import functools
        from repro import optim
        from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round
        from repro.models import registry

        cfg = registry.get_config("lm_350m").reduced()
        loss_fn = functools.partial(registry.loss_fn, cfg)
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        sstate = optim.fedavg_momentum(1.0).init(params)

        losses = {}
        for n in (8, 4):  # pod loss: 8 -> 4 groups
            round_fn = jax.jit(make_local_sgd_round(
                loss_fn, optim.sgd(0.05), optim.fedavg_momentum(1.0),
                LocalSGDConfig(partition_size=n, num_local_steps=1),
            ))
            batch = registry.make_concrete_batch(cfg, n, 16)
            data = {
                "tokens": batch["tokens"].reshape(n, 1, 1, 16),
                "labels": batch["labels"].reshape(n, 1, 1, 16),
            }
            _, _, metrics = round_fn(params, sstate, data)
            losses[n] = float(metrics["loss"])
        assert all(np.isfinite(v) for v in losses.values())
