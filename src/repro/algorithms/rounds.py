"""MapReduce training rounds: local SGD / FedAvg / DiLoCo / FedSGD.

This is the paper's §4 workload, built from the building blocks:

    params_b = drjax.broadcast(global_params)           # server -> groups
    updated  = drjax.map_fn(client_update, (params_b, round_data))
    mean     = drjax.reduce_mean(updated, dtype=f32)    # groups -> server
    params   = server_opt(global_params, mean - global_params)

``client_update`` runs ``num_local_steps`` optimizer steps on the group's
batches — model- and optimizer-agnostic (any ``loss_fn(params, batch)``).

What the clients hand the reduction depends on the round. The flat,
uncompressed, unmasked round hands it each client's new parameters in their
storage dtype; the reduction accumulates them in f32, and the server forms
the f32 mean delta ``mean - global`` once. That is the delta form
``mean(p_k - global)`` up to f32 rounding, and on one device XLA fuses the
mean and the server step into one pass over the parameters. A compressed
round hands it per-client deltas, since the compressor (int8 with its
scales, top-k) acts on each client's change; a straggler-masked round does
too, since its weighted mean of an all-dropped cohort must be a zero
change, not zero parameters. The pod-hierarchical round reduces deltas with
``hierarchical_reduce_mean``, which compresses the pod partials.

Distribution: the partition axis shards over (pod, data); everything inside
``map_fn`` additionally uses the model's logical-axis annotations, so model
parallelism composes (paper: "shard computations over data partitions,
model, and within-data partitions simultaneously").

Options beyond the paper's baseline (all recorded in EXPERIMENTS.md §Perf):
 * straggler masks (over-provisioned cohorts, masked reduction);
 * delta compression (int8 with error-feedback) before the reduction;
 * weighted (FedAvg) and self-tuned (learned-weight) reductions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import core as drjax
from repro.compression import api as compression
from repro.optim.optimizers import Optimizer, apply_updates, clip_by_global_norm


@dataclasses.dataclass(frozen=True)
class LocalSGDConfig:
    partition_size: int
    num_local_steps: int = 4
    partition_axes: Any = None  # e.g. ("pod", "data") on the production mesh
    mesh: Any = None
    use_sharding_annotations: bool = True
    grad_clip: float = 0.0
    compression: Optional[str] = None  # None | "int8" | "topk"
    topk_fraction: float = 0.01
    straggler_mask: bool = False
    # Pod-hierarchical variants: number of slow-link domains. 0 = flat.
    # When > 0, partition_size counts clients PER POD and the program runs
    # under the nested {"pods": num_pods, "clients": partition_size} stack.
    num_pods: int = 0
    # Fused reduce+compress fast path for the hierarchical int8 aggregation:
    # None = auto (fuse when the compressor is recognized), False = force the
    # generic two-primitive composition, True = insist.
    fused_reduce: Optional[bool] = None


def _tree_sub(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: (x.astype(jnp.float32) - y.astype(jnp.float32)), a, b
    )


def _hier_axes(cfg: LocalSGDConfig):
    """Per-placement mesh axes for the nested {pods, clients} stack.

    Accepts a mapping (passed through), a (pod, data, ...) tuple (outermost
    axis to pods, the rest to clients), or a single axis name (to clients —
    the larger dimension; pods stay logical)."""
    axes = cfg.partition_axes
    if axes is None:
        return None
    if isinstance(axes, dict):
        return axes
    if isinstance(axes, (tuple, list)) and len(axes) >= 2:
        rest = tuple(axes[1:])
        return {"pods": axes[0], "clients": rest if len(rest) > 1 else rest[0]}
    if isinstance(axes, (tuple, list)):
        axes = axes[0]
    return {"pods": None, "clients": axes}


def _make_client_update(loss_fn: Callable, client_opt: Optimizer,
                        cfg: LocalSGDConfig, *, as_delta: bool = True):
    """num_local_steps optimizer steps on one group's batches -> (delta,
    loss), or (new params, loss) with ``as_delta=False``.

    Each leg binds under a ``jax.named_scope`` that profiles read:
    ``client_step`` (forward and backward), ``clip``, ``client_opt`` (the
    optimizer update and its application) and ``client_delta`` (the change
    and its compression)."""

    def client_update(params0, client_data):
        opt_state = client_opt.init(params0)

        def one_step(carry, batch):
            params, opt_state = carry
            with jax.named_scope("client_step"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if cfg.grad_clip:
                with jax.named_scope("clip"):
                    grads, _ = clip_by_global_norm(grads, cfg.grad_clip)
            with jax.named_scope("client_opt"):
                updates, opt_state = client_opt.update(grads, opt_state,
                                                       params)
                params = apply_updates(params, updates)
            return (params, opt_state), loss

        (params_new, _), losses = jax.lax.scan(
            one_step, (params0, opt_state), client_data
        )
        if not as_delta:
            return params_new, jnp.mean(losses)
        with jax.named_scope("client_delta"):
            delta = _tree_sub(_as_stored(params_new), params0)
            if cfg.compression == "int8":
                delta = compression.int8_roundtrip(delta)
            elif cfg.compression == "topk":
                delta = compression.topk_sparsify(delta, cfg.topk_fraction)
        return delta, jnp.mean(losses)

    return client_update


def _server_update(server_opt: Optimizer, delta, server_state, params):
    """The server step on the aggregated ``delta``, under the scope
    ``server_update``: (new params, new server state)."""
    with jax.named_scope("server_update"):
        updates, server_state = server_opt.update(delta, server_state, params)
        return apply_updates(params, updates), server_state


def _maybe_donate(round_fn: Callable, donate: bool) -> Callable:
    """Donation rule for round functions (see ROADMAP "Compiled plan
    executor"): the carried state — params (arg 0) and server state (arg 1)
    — is donated so the hot round loop updates it in place instead of
    copying every round. Opt-in because a donated caller must rebind its
    inputs (the reference/bitwise tests reuse theirs)."""
    if not donate:
        return round_fn
    return jax.jit(round_fn, donate_argnums=(0, 1))


def make_local_sgd_round(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    cfg: LocalSGDConfig,
    *,
    donate: bool = False,
):
    """Returns round_fn(global_params, server_state, round_data[, mask]).

    ``round_data`` leaves have shape (n, num_local_steps, ...per-step batch).
    Returns (new_params, new_server_state, metrics). ``donate=True`` returns
    the round jitted with params/server_state donated (the hot-loop form).

    Without compression or a straggler mask, the clients hand
    ``reduce_mean`` their new parameters, still in their storage dtype; it
    accumulates them in f32, and the server step subtracts the global
    parameters from that f32 mean. Where the clients' placement names no
    mesh axis, XLA fuses the mean and the server step into one pass per
    leaf that reads each client's parameters and the global once and
    writes the new global once. With ``cfg.compression`` the clients hand
    it their compressed deltas (the compressor acts on each client's
    change), and with a mask their deltas (an all-dropped cohort's masked
    mean is zero, which must mean no change).
    """
    client_update = _make_client_update(loss_fn, client_opt, cfg)
    client_params = _make_client_update(loss_fn, client_opt, cfg,
                                        as_delta=False)

    @drjax.program(
        partition_size=cfg.partition_size,
        partition_axes=cfg.partition_axes,
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def round_fn(global_params, server_state, round_data, mask=None):
        params_b = drjax.broadcast(global_params)
        masked = cfg.straggler_mask and mask is not None
        if masked or cfg.compression is not None:
            deltas, losses = drjax.map_fn(client_update,
                                          (params_b, round_data))
            if masked:
                mean_delta = drjax.masked_reduce_mean(deltas, mask)
                mean_loss = drjax.masked_reduce_mean(losses, mask)
            else:
                mean_delta = drjax.reduce_mean(deltas)
                mean_loss = drjax.reduce_mean(losses)
        else:
            updated, losses = drjax.map_fn(client_params,
                                           (params_b, round_data))
            mean_params = drjax.reduce_mean(updated, dtype=jnp.float32)
            mean_loss = drjax.reduce_mean(losses)
            with jax.named_scope("server_update"):
                mean_delta = _tree_sub(mean_params, global_params)
        new_params, new_server_state = _server_update(
            server_opt, mean_delta, server_state, global_params
        )
        metrics = {"loss": mean_loss}
        return new_params, new_server_state, metrics

    return _maybe_donate(round_fn, donate)


def make_hierarchical_local_sgd_round(
    loss_fn: Callable,
    client_opt: Optimizer,
    server_opt: Optimizer,
    cfg: LocalSGDConfig,
    *,
    donate: bool = False,
):
    """Pod-hierarchical local SGD: the nested-placement round (paper §6).

    Runs under the two-level stack ``{"pods": cfg.num_pods, "clients":
    cfg.partition_size}`` (``partition_size`` counts clients *per pod*).
    ``round_data`` leaves have shape (num_pods, clients_per_pod,
    num_local_steps, ...per-step batch); an optional straggler ``mask`` is
    (num_pods, clients_per_pod). The delta aggregation is the genuine
    two-stage reduction — ``reduce_mean@clients`` over ICI, then
    ``reduce_mean@pods`` over DCN, with ``cfg.compression`` (if set) applied
    to the per-pod partials that cross the slow leg — so the §5 plan of this
    round stages the aggregation as two placement-tagged shuffles.
    """
    if cfg.num_pods < 1:
        raise ValueError(
            "make_hierarchical_local_sgd_round needs cfg.num_pods >= 1"
        )
    # Where compression runs depends on the aggregation path. The masked
    # (straggler) reduction spans both levels in one weighted pass, so it
    # keeps the flat round's per-client compression; the unmasked path
    # compresses the pod PARTIALS instead — the value that actually crosses
    # the DCN leg — so the per-client leg runs uncompressed.
    client_cfg = (
        cfg if cfg.straggler_mask
        else dataclasses.replace(cfg, compression=None)
    )
    client_update = _make_client_update(loss_fn, client_opt, client_cfg)
    pod_compress = None
    if not cfg.straggler_mask:
        if cfg.compression == "int8":
            pod_compress = compression.int8_roundtrip
        elif cfg.compression == "topk":
            pod_compress = functools.partial(
                compression.topk_sparsify, fraction=cfg.topk_fraction
            )

    @drjax.program(
        placements={"pods": cfg.num_pods, "clients": cfg.partition_size},
        partition_axes=_hier_axes(cfg),
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def round_fn(global_params, server_state, round_data, mask=None):
        params_b = drjax.broadcast(global_params)
        deltas, losses = drjax.map_fn(client_update, (params_b, round_data))
        if cfg.straggler_mask and mask is not None:
            mean_delta = drjax.masked_reduce_mean(deltas, mask)
            mean_loss = drjax.masked_reduce_mean(losses, mask)
        else:
            # Two-stage mean with the pod partials (the bytes that cross the
            # DCN leg) optionally compressed.
            mean_delta = drjax.hierarchical_reduce_mean(
                deltas, compress_fn=pod_compress, use_fused=cfg.fused_reduce
            )
            mean_loss = drjax.hierarchical_reduce_mean(losses)
        new_params, new_server_state = _server_update(
            server_opt, mean_delta, server_state, global_params
        )
        metrics = {"loss": mean_loss}
        return new_params, new_server_state, metrics

    return _maybe_donate(round_fn, donate)


def make_multi_round(
    round_fn: Callable,
    num_rounds: int,
    *,
    jit: bool = False,
    donate: bool = True,
) -> Callable:
    """Stack ``num_rounds`` rounds of ``round_fn`` into one ``lax.scan``.

    ``round_fn`` is any ``(params, server_state, round_data) -> (params,
    server_state, metrics)`` round (e.g. from :func:`make_local_sgd_round`);
    ``all_data`` leaves carry a leading ``num_rounds`` axis. Because the scan
    body broadcasts and reduces every iteration, the §5 interpreter surfaces
    the trainer as a single ``LoopStage`` whose sub-plan makes the per-round
    communication explicit (one broadcast + one reduce per round) — the plan
    a federated/Beam backend would actually schedule.

    ``jit=True`` returns the trainer compiled, with the scan carry (params +
    server state) donated into the executable by default (``donate=False``
    to keep the caller's buffers alive): inside the scan XLA already updates
    the carry in place; donation extends that in-place discipline across the
    jit boundary, so N rounds trigger exactly one trace and zero carry
    copies (asserted in ``tests/test_executor.py``).
    """

    def trainer(params, server_state, all_data):
        def body(carry, round_data):
            params, server_state = carry
            params, server_state, metrics = round_fn(
                params, server_state, round_data
            )
            return (params, server_state), metrics

        (params, server_state), metrics = jax.lax.scan(
            body, (params, server_state), all_data, length=num_rounds
        )
        return params, server_state, metrics

    if jit:
        return jax.jit(trainer, donate_argnums=(0, 1) if donate else ())
    return trainer


def make_fedsgd_round(
    loss_fn: Callable,
    server_opt: Optimizer,
    cfg: LocalSGDConfig,
    *,
    learned_weights: bool = False,
):
    """Single-local-step gradient averaging (FedSGD).

    With ``learned_weights=True`` the reduction weights are a trainable
    input — the self-tuning reduction of paper §6 (gradients flow to the
    weights through MapReduce AD).
    """

    def client_grad(params, batch):
        with jax.named_scope("client_step"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads, loss

    @drjax.program(
        partition_size=cfg.partition_size,
        partition_axes=cfg.partition_axes,
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def round_fn(global_params, server_state, batches, weights=None):
        params_b = drjax.broadcast(global_params)
        grads, losses = drjax.map_fn(client_grad, (params_b, batches))
        if learned_weights and weights is not None:
            w = jax.nn.softmax(weights) * cfg.partition_size
            mean_grad = drjax.reduce_weighted_mean(grads, w)
            mean_loss = drjax.reduce_weighted_mean(losses, w)
        else:
            mean_grad = drjax.reduce_mean(grads)
            mean_loss = drjax.reduce_mean(losses)
        neg = jax.tree_util.tree_map(lambda g: -g, mean_grad)
        new_params, new_server_state = _server_update(
            server_opt, neg, server_state, global_params
        )
        return new_params, new_server_state, {"loss": mean_loss}

    return round_fn


def _as_stored(tree):
    """Each leaf in float32, rounded as its storage dtype rounds it.

    XLA may keep a value that is converted to a narrower float and back at
    the wider precision (``xla_allow_excess_precision``, on by default):
    on a TPU a client's bf16 parameters, converted back to f32 for the
    delta inside one fusion, come out unrounded. ``reduce_precision`` makes
    the rounding an op of its own, which no simplification removes."""
    def leaf(x):
        info = jnp.finfo(x.dtype)
        return jax.lax.reduce_precision(x.astype(jnp.float32),
                                        exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)

    return jax.tree_util.tree_map(leaf, tree)
