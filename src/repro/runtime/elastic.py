"""Elastic scaling: change device count / cohort size without changing the
program.

The paper's decoupling of *logical* partition size from *physical* devices is
exactly what makes DrJAX elastic: a partition of n groups runs on any m | n
devices. When a pod is lost (or gained):

 1. pick the new mesh from the surviving devices;
 2. (optionally) pick a new cohort size n' compatible with m';
 3. re-jit the same round function for the new (n', mesh) — the *model* and
    *server state* are placement-free pytrees and transfer unchanged.

No resharding of training state is required beyond what pjit does on the new
mesh; client state is per-round (clients re-init from broadcast), so nothing
is lost with the failed pod — the defining fault-tolerance advantage of
MapReduce rounds over long-lived SPMD replicas.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class ElasticSchedule:
    """Cohort-size policy as the device pool grows/shrinks.

    ``groups_per_device`` keeps per-device load constant (weak scaling, the
    paper's Fig. 4 regime).
    """

    groups_per_device: int = 1

    def cohort_size(self, num_devices: int) -> int:
        return max(1, num_devices * self.groups_per_device)


def rescale_partition(
    round_data: dict, old_n: int, new_n: int
) -> dict:
    """Adapt a round's stacked cohort data from n to n' groups.

    Shrink: drop the tail groups (they simply aren't sampled).
    Grow: wrap-around repeat (callers normally just sample a bigger cohort).
    """
    def leaf(x):
        if not hasattr(x, "shape") or x.ndim == 0 or x.shape[0] != old_n:
            return x
        if new_n <= old_n:
            return x[:new_n]
        reps = -(-new_n // old_n)
        return np.concatenate([x] * reps, axis=0)[:new_n]

    return jax.tree_util.tree_map(leaf, round_data)


def make_elastic_hierarchical_round(
    loss_fn: Callable,
    client_opt,
    server_opt,
    cfg,
    *,
    loops: str = "native",
    donate_cross: bool = False,
    straggler_mask: bool = False,
):
    """Pod-hierarchical local SGD that survives pod dropout WITHOUT
    recompiling the per-client leg.

    Numerically equivalent to
    :func:`repro.algorithms.rounds.make_hierarchical_local_sgd_round`
    (uncompressed path), but compiled per placement level through the
    executor's split cache (:class:`repro.runtime.executor.
    ElasticHierarchicalRound`): the per-client leg is one compiled per-pod
    plan — ``cfg.partition_size`` clients, shapes independent of the pod
    count — dispatched once per pod; the cross-pod leg (mean of pod partials
    + server update) is a small executable keyed by the pod count. The
    returned object's ``step(params, server_state, round_data)`` accepts
    ``round_data`` leaves of shape ``(num_pods, clients_per_pod, ...)`` for
    ANY ``num_pods``, so a shrunken cohort after a pod loss re-uses the
    cached client executable and recompiles only the cross-pod leg.

    ``straggler_mask=True`` makes the round deadline-masked end to end:
    ``step`` then takes ``round_data = {"data": <leaves (num_pods,
    clients_per_pod, ...)>, "mask": (num_pods, clients_per_pod)}``. The
    per-pod leg reduces with ``drjax.masked_reduce_mean`` (an unbiased mean
    over that pod's finishers; a fully-dropped pod yields zeros) and also
    reduces the finisher count, and the cross-pod leg weights each pod
    partial by its finisher count — so the composition equals the flat
    masked mean over ALL finishers (the unbiasedness invariant the chaos
    soak asserts against :func:`repro.algorithms.rounds.
    make_local_sgd_round`'s masked path). The mask is data, not control
    flow: shapes are fixed per pod count and the per-client leg never
    recompiles when the finisher set changes.
    """
    from repro import core as drjax
    from repro.algorithms.rounds import _make_client_update, _server_update
    from repro.runtime.executor import ElasticHierarchicalRound

    client_update = _make_client_update(loss_fn, client_opt, cfg)

    program = drjax.program(
        partition_size=cfg.partition_size,
        partition_axes=cfg.partition_axes,
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )

    if straggler_mask:

        @program
        def client_leg(global_params, pod_batch):
            # Masked intra-pod leg: unbiased mean over the pod's finishers
            # plus the finisher count (the cross-pod weighting).
            params_b = drjax.broadcast(global_params)
            deltas, losses = drjax.map_fn(
                client_update, (params_b, pod_batch["data"])
            )
            mask = pod_batch["mask"]
            return (
                drjax.masked_reduce_mean(deltas, mask),
                drjax.masked_reduce_mean(losses, mask),
                drjax.reduce_sum(mask),
            )

        def cross_leg(global_params, server_state, partials):
            # Finisher-weighted cross-pod mean: sum_p(fin_p * mean_p) /
            # sum_p(fin_p) == the flat masked mean over all finishers. An
            # all-dropped cohort (every weight zero) yields zeros, matching
            # masked_reduce_mean's zero-weight contract.
            pod_deltas, pod_losses, pod_fin = partials
            total = jnp.sum(pod_fin)
            denom = jnp.maximum(total, 1.0)

            def wmean(d):
                w = pod_fin.reshape((-1,) + (1,) * (d.ndim - 1))
                s = jnp.sum(d * w, axis=0) / denom
                return jnp.where(total > 0, s, jnp.zeros_like(s))

            mean_delta = jax.tree_util.tree_map(wmean, pod_deltas)
            mean_loss = wmean(pod_losses)
            new_params, new_server_state = _server_update(
                server_opt, mean_delta, server_state, global_params
            )
            return new_params, new_server_state, {
                "loss": mean_loss,
                "finishers": total,
            }

    else:

        @program
        def client_leg(global_params, pod_data):
            # The per-pod program: intra-pod leg of the hierarchical round.
            params_b = drjax.broadcast(global_params)
            deltas, losses = drjax.map_fn(client_update, (params_b, pod_data))
            return drjax.reduce_mean(deltas), drjax.reduce_mean(losses)

        def cross_leg(global_params, server_state, partials):
            # Cross-pod leg: mean of the pod partials (the bytes that cross
            # the DCN) + the server optimizer step.
            pod_deltas, pod_losses = partials
            mean_delta = jax.tree_util.tree_map(
                lambda d: jnp.mean(d, axis=0), pod_deltas
            )
            new_params, new_server_state = _server_update(
                server_opt, mean_delta, server_state, global_params
            )
            return new_params, new_server_state, {
                "loss": jnp.mean(pod_losses, 0)
            }

    return ElasticHierarchicalRound(
        client_leg,
        cross_leg,
        clients_per_pod=cfg.partition_size,
        loops=loops,
        donate_cross=donate_cross,
    )


def available_mesh_shapes(num_devices: int,
                          model_parallelism: int = 1,
                          *,
                          placements=None) -> List:
    """All viable mesh shapes for a (possibly degraded) device pool.

    Tries the requested model parallelism first, then every halved fallback
    down to 1, keeping each shape that tiles the device pool exactly. The
    first entry is the preferred shape; later entries trade model parallelism
    for data parallelism (useful when the degraded pool can't tile the
    original model-parallel group).

    Legacy form (``placements=None``): returns ``(data, model)`` int pairs
    for a flat pool — unchanged historical behavior.

    With ``placements`` (any spec :func:`repro.launch.mesh.level_axes_for`
    accepts): the N-level generalization. Every level but the OUTERMOST
    keeps its size (the inner levels are fast-interconnect groups a dropout
    does not re-tile); the outermost level absorbs the degraded pool. Each
    entry is ``(shape, axes)`` with axis names from ``level_axes_for`` — the
    axis-tuple literals stay in ``launch/mesh.py`` so the
    ``mesh-axes-literal`` lint covers this path too.
    """
    if placements is None:
        shapes: List[Tuple[int, int]] = []
        mp = model_parallelism
        while mp >= 1:
            if num_devices % mp == 0:
                shape = (num_devices // mp, mp)
                if shape not in shapes:
                    shapes.append(shape)
            if mp == 1:
                break
            mp //= 2
        return shapes

    from repro.launch.mesh import _normalize_stack, level_axes_for

    stack = _normalize_stack(placements)
    if not stack:
        raise ValueError("placements must not be empty")
    level_axes = level_axes_for(stack)
    inner_sizes = tuple(s for _, s, _ in stack[1:])
    inner = 1
    for s in inner_sizes:
        inner *= s
    out: List[Tuple[Tuple[int, ...], Tuple[str, ...]]] = []
    mp = model_parallelism
    while mp >= 1:
        denom = inner * mp
        if denom and num_devices % denom == 0 and num_devices >= denom:
            shape: Tuple[int, ...] = (num_devices // denom,) + inner_sizes
            axes: Tuple[str, ...] = level_axes
            if model_parallelism > 1:
                shape = shape + (mp,)
                axes = axes + ("model",)
            if (shape, axes) not in out:
                out.append((shape, axes))
        if mp == 1:
            break
        mp //= 2
    return out


def pod_device_pool(num_pods: int, clients_per_pod: int,
                    devices=None) -> np.ndarray:
    """The host's devices as a ``(num_pods, clients_per_pod)`` object array.

    Row p holds pod p's local devices — the assignment the full
    ``{"pods": P, "clients": m}`` mesh factorizes over, and the unit of
    loss when a pod drops: :func:`mesh_for_surviving_pods` rebuilds the
    degraded mesh from the surviving rows.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    need = num_pods * clients_per_pod
    if len(devs) < need:
        raise ValueError(
            f"pod pool needs {need} devices ({num_pods} pods x "
            f"{clients_per_pod} clients) but only {len(devs)} are available"
        )
    pool = np.empty((num_pods, clients_per_pod), dtype=object)
    for i in range(num_pods):
        for j in range(clients_per_pod):
            pool[i, j] = devs[i * clients_per_pod + j]
    return pool


def mesh_for_surviving_pods(pool: np.ndarray, alive) -> jax.sharding.Mesh:
    """Degraded ``(pod, data)`` mesh over the surviving pods of ``pool``.

    ``alive`` is the ordered tuple of surviving pod ids (rows of ``pool``).
    The mesh keeps the per-pod client dimension intact — a dropout removes
    whole rows, never re-tiles within a pod — and goes through
    :func:`repro.launch.mesh.mesh_for_placements`'s ``devices=`` subset
    path so any N-level stack would factorize the same way.
    """
    from repro.launch.mesh import mesh_for_placements

    alive = tuple(int(a) for a in alive)
    if not alive:
        raise ValueError("need at least one surviving pod to build a mesh")
    sub = pool[list(alive), :]
    return mesh_for_placements(
        {"pods": sub.shape[0], "clients": sub.shape[1]},
        devices=sub.reshape(-1),
    )
