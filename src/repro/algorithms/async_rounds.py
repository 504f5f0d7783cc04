"""Asynchronous (one-round-stale) local SGD — compute/communication overlap.

Synchronous rounds serialize: [local steps] → [reduce] → [server update] →
[broadcast]. At pod scale the reduce+broadcast leg can rival the compute
leg. The async variant overlaps them with
one round of staleness (the standard pipelined-DiLoCo trick):

    round r:   clients train on params_{r-1} while the server is still
               aggregating the deltas of round r-1;
    server:    applies delta_{r-1} as soon as it lands → params_r.

The returned step has signature
``(params, pending_delta, server_state, round_data) ->
  (new_params, new_pending_delta, server_state, metrics)``
where ``pending_delta`` is the in-flight aggregate. On hardware, the reduce
of ``new_pending_delta`` overlaps the next round's ``map_fn`` (they have no
data dependency — visible in the jaxpr and exploitable by the scheduler).
Staleness=1 is the classic delayed-gradient regime; convergence holds for
the outer optimizers used here (tested on the CPU-scale model).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro import core as drjax
from repro.algorithms.rounds import (
    LocalSGDConfig,
    _hier_axes,
    _make_client_update,
    _server_update,
)
from repro.optim.optimizers import Optimizer


def make_async_local_sgd_round(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    cfg: LocalSGDConfig,
    *,
    donate: bool = False,
):
    # The flat async round sends its deltas uncompressed.
    client_update = _make_client_update(
        loss_fn, client_opt, dataclasses.replace(cfg, compression=None)
    )

    @drjax.program(
        partition_size=cfg.partition_size,
        partition_axes=cfg.partition_axes,
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def async_round(params, pending_delta, server_state, round_data):
        # 1) apply the delta that finished aggregating during the last round
        params, server_state = _server_update(
            server_opt, pending_delta, server_state, params
        )
        # 2) launch this round's local training on the just-updated params
        params_b = drjax.broadcast(params)
        deltas, losses = drjax.map_fn(client_update, (params_b, round_data))
        # 3) aggregate — independent of (1)-(2) of the NEXT round, so on
        #    hardware this reduce overlaps the next round's map
        new_pending = drjax.reduce_mean(deltas)
        metrics = {"loss": drjax.reduce_mean(losses)}
        return params, new_pending, server_state, metrics

    def init_pending(params):
        # Match each param's dtype (bf16 params get bf16 pending deltas) so
        # the first server update isn't fed a dtype-mismatched aggregate.
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    if donate:
        # The async carry is (params, pending_delta, server_state): all
        # three are round-to-round state, so the hot loop donates all three.
        async_round = jax.jit(async_round, donate_argnums=(0, 1, 2))
    return async_round, init_pending


def make_hierarchical_async_round(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    cfg: LocalSGDConfig,
    *,
    donate: bool = False,
):
    """Pod-hierarchical asynchronous round (nested {pods, clients} stack).

    Same one-round-stale overlap as :func:`make_async_local_sgd_round`, but
    the delta aggregation is the two-stage hierarchical mean: the fast
    intra-pod leg (``reduce_mean@clients``) can complete while this pod's
    next map is being scheduled, and only the P pod partials cross the DCN
    leg. ``round_data`` leaves are (num_pods, clients_per_pod,
    num_local_steps, ...); ``cfg.partition_size`` counts clients per pod.
    """
    if cfg.num_pods < 1:
        raise ValueError(
            "make_hierarchical_async_round needs cfg.num_pods >= 1"
        )
    client_update = _make_client_update(loss_fn, client_opt, cfg)

    @drjax.program(
        placements={"pods": cfg.num_pods, "clients": cfg.partition_size},
        partition_axes=_hier_axes(cfg),
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def async_round(params, pending_delta, server_state, round_data):
        params, server_state = _server_update(
            server_opt, pending_delta, server_state, params
        )
        params_b = drjax.broadcast(params)
        deltas, losses = drjax.map_fn(client_update, (params_b, round_data))
        new_pending = drjax.hierarchical_reduce_mean(deltas)
        metrics = {"loss": drjax.hierarchical_reduce_mean(losses)}
        return params, new_pending, server_state, metrics

    def init_pending(params):
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    if donate:
        async_round = jax.jit(async_round, donate_argnums=(0, 1, 2))
    return async_round, init_pending
