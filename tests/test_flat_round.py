"""The flat local-SGD round's one-pass aggregation.

Without compression or a straggler mask, the clients hand ``reduce_mean``
their new parameters in their storage dtype, the reduction accumulates them
in f32 (``dtype=jnp.float32``), and the server forms ``mean - global``. The
delta composition ``reduce_mean(p_k - global)``, which compressed and masked
rounds keep, is the reference: the two agree up to f32 rounding.

Parameters are stored in bf16 here, as on the chip. An ulp is an f32 ulp at
the magnitude the f32 arithmetic works at: the larger of the global
parameter and the clients' new parameters.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as drjax
from repro import optim
from repro.algorithms import rounds
from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round
from repro.data.grouped import CohortSampler, GroupedCorpus
from repro.models import registry

STEPS, BATCH, SEQ = 2, 2, 16
# The tolerance of the round tests in test_algorithms.py.
RTOL = ATOL = 2e-4

SERVERS = {
    "local_sgd": lambda: optim.fedavg_momentum(1.0),
    "fedavg": lambda: optim.fedavg_momentum(1.0, momentum=0.9),
    "diloco": lambda: optim.diloco_optimizer(0.7, 0.9),
}


@pytest.fixture(scope="module")
def tiny_bf16():
    cfg = registry.get_config("lm_350m").reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    return cfg, params, functools.partial(registry.loss_fn, cfg)


def _data(cfg, n, round_idx=0):
    corpus = GroupedCorpus(vocab_size=cfg.vocab_size, num_groups=64)
    d = CohortSampler(corpus, cohort_size=n).round_batch(
        round_idx, STEPS, BATCH, SEQ)
    return {"tokens": d["tokens"], "labels": d["labels"]}


def _delta_round(loss_fn, client_opt, server_opt, cfg):
    """The delta composition: every client hands the reduction its
    (compressed) change, as compressed and masked rounds do."""
    client_update = rounds._make_client_update(loss_fn, client_opt, cfg)

    @drjax.program(partition_size=cfg.partition_size)
    def round_fn(global_params, server_state, round_data, mask=None):
        params_b = drjax.broadcast(global_params)
        deltas, losses = drjax.map_fn(client_update, (params_b, round_data))
        if mask is not None:
            mean_delta = drjax.masked_reduce_mean(deltas, mask)
            mean_loss = drjax.masked_reduce_mean(losses, mask)
        else:
            mean_delta = drjax.reduce_mean(deltas)
            mean_loss = drjax.reduce_mean(losses)
        new_params, new_state = rounds._server_update(
            server_opt, mean_delta, server_state, global_params)
        return new_params, new_state, {"loss": mean_loss}

    return round_fn


def _leaves(tree):
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("n", [2, 3, 8])
def test_mean_delta_within_two_ulps(tiny_bf16, n):
    cfg, params, loss_fn = tiny_bf16
    lcfg = LocalSGDConfig(partition_size=n, num_local_steps=STEPS)
    as_delta = rounds._make_client_update(loss_fn, optim.sgd(0.05), lcfg)
    as_params = rounds._make_client_update(loss_fn, optim.sgd(0.05), lcfg,
                                           as_delta=False)

    @jax.jit
    @drjax.program(partition_size=n)
    def both(p, data):
        pb = drjax.broadcast(p)
        deltas, _ = drjax.map_fn(as_delta, (pb, data))
        news, _ = drjax.map_fn(as_params, (pb, data))
        mean = drjax.reduce_mean(news, dtype=jnp.float32)
        one_pass = jax.tree_util.tree_map(
            lambda m, g: m - g.astype(jnp.float32), mean, p)
        return drjax.reduce_mean(deltas), one_pass, news

    ref, got, news = both(params, _data(cfg, n))
    moved = 0
    for r, g, p0, pk in zip(_leaves(ref), _leaves(got), _leaves(params),
                            _leaves(news)):
        assert g.dtype == r.dtype == np.float32
        scale = np.maximum(np.abs(p0), np.max(np.abs(pk), axis=0))
        np.testing.assert_array_less(np.abs(g - r),
                                     2 * np.spacing(scale) + 1e-45)
        moved += int(np.count_nonzero(r))
    assert moved > 0  # the round changed the parameters


@pytest.mark.parametrize("n", [2, 3, 8])
@pytest.mark.parametrize("server", sorted(SERVERS))
def test_round_matches_delta_composition(tiny_bf16, server, n):
    cfg, params, loss_fn = tiny_bf16
    server_opt = SERVERS[server]()
    lcfg = LocalSGDConfig(partition_size=n, num_local_steps=STEPS,
                          grad_clip=1.0)
    sstate = server_opt.init(params)
    data = _data(cfg, n)
    got = jax.jit(make_local_sgd_round(
        loss_fn, optim.sgd(0.05), server_opt, lcfg))(params, sstate, data)
    ref = jax.jit(_delta_round(
        loss_fn, optim.sgd(0.05), server_opt, lcfg))(params, sstate, data)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert g.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=RTOL, atol=ATOL)
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_leaves(got[0]), _leaves(params)))


@pytest.mark.parametrize("kind", ["int8", "topk", "masked"])
def test_delta_rounds_unchanged(tiny_bf16, kind):
    """Compressed and straggler-masked rounds still hand the reduction
    per-client deltas: their outputs are the delta composition's, bit for
    bit."""
    cfg, params, loss_fn = tiny_bf16
    n = 4
    server_opt = optim.fedavg_momentum(1.0, momentum=0.9)
    lcfg = LocalSGDConfig(
        partition_size=n, num_local_steps=STEPS,
        compression=None if kind == "masked" else kind,
        topk_fraction=0.1, straggler_mask=kind == "masked")
    sstate = server_opt.init(params)
    args = (params, sstate, _data(cfg, n))
    if kind == "masked":
        args += (jnp.asarray([1.0, 0.0, 1.0, 1.0]),)
    got = jax.jit(make_local_sgd_round(
        loss_fn, optim.sgd(0.05), server_opt, lcfg))(*args)
    ref = jax.jit(_delta_round(
        loss_fn, optim.sgd(0.05), server_opt, lcfg))(*args)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_flat_round_plan_has_one_broadcast_and_one_reduce(tiny_bf16):
    """Still a DrJAX program: the §5 plan broadcasts the parameters, maps
    the clients, reduces once (the parameters accumulated in f32, and the
    loss), then runs the server step."""
    cfg, params, loss_fn = tiny_bf16
    server_opt = optim.fedavg_momentum(1.0)
    # Three clients: the reduced model stacks its 2 layers on a leading
    # axis, which the plan builder would take for a group axis of 2.
    round_fn = make_local_sgd_round(
        loss_fn, optim.sgd(0.05), server_opt,
        LocalSGDConfig(partition_size=3, num_local_steps=STEPS))
    args = (params, server_opt.init(params), _data(cfg, 3))
    jxp = jax.make_jaxpr(jax.jit(round_fn))(*args)
    plan = drjax.build_plan(jxp, 3)
    kinds = [s.kind for s in plan.stages]
    phases = [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]
    assert phases == ["BROADCAST", "GROUP_COMPUTE", "REDUCE",
                      "SERVER_COMPUTE"]
    leaves = len(jax.tree_util.tree_leaves(params))
    reduces = [s for s in plan.stages if s.kind == "REDUCE"]
    assert kinds.count("BROADCAST") == leaves
    assert len(reduces) == leaves + 1  # the parameters and the loss
    assert all(s.op == "reduce_mean" for s in reduces)
    accumulated = [s for s in reduces if s.eqn.params.get("dtype") is not None]
    assert len(accumulated) == leaves
    for s in accumulated:
        assert s.eqn.invars[0].aval.dtype == jnp.bfloat16
        assert s.eqn.outvars[0].aval.dtype == jnp.float32
    hlo = jax.jit(round_fn).lower(*args).compile().as_text()
    for scope in ("drjax.broadcast[clients]", "drjax.map[clients]",
                  "drjax.reduce_mean[clients]"):
        assert scope in hlo


class TestAccumulatingReduce:
    """``reduce_mean`` with an accumulation ``dtype``."""

    @pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 32])
    @pytest.mark.parametrize("axes", [None, "data"])
    def test_mean_in_f32(self, n, axes):
        # A placement that names a mesh axis keeps the reduce (no mesh
        # here, so nothing is sharded), as do 32 groups; up to 16 groups on
        # a placement that names none are summed as slices.
        x = jax.random.normal(jax.random.PRNGKey(n), (n, 5, 7))
        xb = x.astype(jnp.bfloat16)

        @drjax.program(partition_size=n, partition_axes=axes)
        def f(v):
            return drjax.reduce_mean(v, dtype=jnp.float32)

        mean = jax.jit(f)(xb)
        assert mean.dtype == jnp.float32
        want = np.asarray(xb, np.float32).astype(np.float64)
        np.testing.assert_allclose(mean, want.mean(0), rtol=1e-6, atol=1e-7)

    def test_without_dtype_eqn_unchanged(self):
        @drjax.program(partition_size=3)
        def f(v):
            return drjax.reduce_mean(v)

        (eqn,) = [e for e in jax.make_jaxpr(f)(jnp.ones((3, 2))).eqns
                  if e.primitive.name == "drjax_reduce_mean"]
        assert "dtype" not in eqn.params

    def test_grad_and_vmap(self):
        n = 3

        @drjax.program(partition_size=n)
        def f(v):
            return jnp.sum(drjax.reduce_mean(v, dtype=jnp.float32) ** 2)

        xb = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)
        xb = xb.astype(jnp.bfloat16)
        g = jax.grad(f)(xb)
        assert g.dtype == jnp.bfloat16
        mean = np.asarray(xb, np.float32).mean(0)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.broadcast_to(2 * mean / n, (n, 4)),
                                   rtol=1e-2)
        batched = jax.vmap(f)(jnp.stack([xb, 2 * xb]))
        np.testing.assert_allclose(batched, [f(xb), f(2 * xb)], rtol=1e-6)
