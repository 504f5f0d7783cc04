"""End-to-end training driver.

Trains any registry architecture with either plain data-parallel AdamW or
DrJAX local-SGD/DiLoCo rounds, with checkpoint/restart fault tolerance,
straggler-masked reductions, and (optional) delta compression.

CPU-scale example (reduced config, a few hundred rounds):

    PYTHONPATH=src python -m repro.launch.train \
        --arch lm_350m --reduced --algorithm diloco \
        --rounds 200 --cohort 8 --local-steps 4 --ckpt-dir /tmp/ckpt

On a real cluster, run unmodified under `jax.distributed` with
``--mesh single|multi`` (the production meshes from launch/mesh.py).

``--pods P`` (P > 1) trains DrJAX's nested round instead of the flat one:
``--cohort`` clients in P pods, a reduce inside each pod, then the mean
across pods, with ``--compression int8`` on the fused reduce+compress
kernel. Over several devices the round runs on a ``(pod P, data n/P)``
mesh of them; state is replicated and each round's batch sharded over the
mesh (:func:`round_layout`). On a host with four chips:

    python -m repro.launch.train --arch lm_350m --pods 2 --cohort 8 \
        --local-steps 1 --batch 4 --compression int8

Profiling a real run: ``--profile-dir DIR`` records rounds with the JAX
profiler into ``DIR`` (an ``.xplane.pb`` under ``DIR/plugins/profile/``),
``--profile-rounds START:END`` the rounds START..END-1 only (default: all).
Each round is a step (``StepTraceAnnotation("round")``) whose host spans are
``sample``, ``dispatch``, ``wait`` and ``readback``; ``checkpoint_save`` and
``restore`` mark the recovery loop's checkpoint traffic. The device ops of
each round carry the program's scopes (``client_step``, ``drjax.<op>[...]``,
``server_update``, ...) in their ``op_name``.

    PYTHONPATH=src python -m repro.launch.train --arch lm_350m --reduced \
        --rounds 10 --profile-dir /tmp/prof --profile-rounds 2:5
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, optim
from repro.algorithms.rounds import (
    LocalSGDConfig,
    make_hierarchical_local_sgd_round,
    make_local_sgd_round,
)
from repro.checkpoint import CheckpointManager
from repro.data.grouped import CohortSampler, GroupedCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_for_placements, placement_axes_for
from repro.models import registry
from repro.runtime.failure import FailureInjector, run_with_recovery
from repro.runtime.stragglers import StragglerSimulator, straggler_mask

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class RoundLayout:
    """Where a round's state and batches live.

    ``mesh``: the round's mesh, or None where it runs on one device;
    ``state(tree)``: parameters or server state onto the round's devices,
    replicated; ``batch(host)``: a sampler batch of shape ``(cohort, steps,
    batch, seq)`` as the round takes it."""

    mesh: Optional[jax.sharding.Mesh]
    state: Callable
    batch: Callable


def _as_is(tree):
    return tree


def _clients_per_pod(args) -> int:
    if args.pods < 1 or args.cohort % args.pods:
        raise ValueError(f"--pods {args.pods} does not divide --cohort "
                         f"{args.cohort}")
    return args.cohort // args.pods


def round_layout(args, devices=None) -> RoundLayout:
    """The layout of the round ``args`` asks for, on ``devices`` (default:
    every device).

    The flat round (``--pods 1``) takes its state and batches as they come.
    With ``--pods P`` the batch is regrouped to ``(P, cohort / P, steps,
    batch, seq)``; over n > 1 devices the round runs on a ``(pod P, data
    n / P)`` mesh, state replicated and the batch sharded over both axes,
    so each device holds ``cohort / n`` clients."""
    pods = args.pods
    if pods == 1:
        return RoundLayout(None, _as_is, _as_is)
    per_pod = _clients_per_pod(args)
    devices = list(jax.devices() if devices is None else devices)

    def regroup(host):
        return jax.tree_util.tree_map(
            lambda x: x.reshape((pods, per_pod) + x.shape[1:]), host)

    if len(devices) == 1:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return RoundLayout(None, lambda tree: jax.device_put(tree, one),
                           lambda host: jax.device_put(regroup(host), one))
    n = len(devices)
    if n % pods or per_pod % (n // pods):
        raise ValueError(
            f"--pods {pods} with --cohort {args.cohort} cannot run on {n} "
            f"devices: a (pod {pods}, data {n // pods}) mesh needs the "
            f"device count a multiple of the pods and the {per_pod} clients "
            "of a pod a multiple of the devices of a pod")
    mesh = mesh_for_placements({"pods": pods, "clients": n // pods},
                               devices=devices)
    axes = placement_axes_for(mesh)
    replicated = compat.replicated_sharding(mesh)
    sharded = compat.named_sharding(mesh, (axes["pods"], axes["clients"]))
    return RoundLayout(mesh, lambda tree: jax.device_put(tree, replicated),
                       lambda host: jax.device_put(regroup(host), sharded))


def build_round_fn(cfg, args, mesh=None):
    """The round ``args`` asks for, jitted with the parameters and the
    server state donated, and its server optimizer: ``(round_fn,
    server_opt)``.

    ``--pods 1`` builds the flat round. ``--pods P`` builds the nested
    round over P pods of ``cohort / P`` clients, on ``mesh`` (the
    :func:`round_layout` mesh; None: one device); with ``--compression
    int8`` the pod partials take the fused reduce+compress kernel, which is
    insisted on (a fallback to the generic composition raises)."""
    loss_fn = functools.partial(registry.loss_fn, cfg)
    client_opt = (
        optim.adamw(args.client_lr) if args.algorithm == "diloco"
        else optim.sgd(args.client_lr)
    )
    server_opt = {
        "local_sgd": optim.fedavg_momentum(1.0),
        "fedavg": optim.fedavg_momentum(1.0, momentum=0.9),
        "diloco": optim.diloco_optimizer(0.7, 0.9),
    }[args.algorithm]
    round_cfg = LocalSGDConfig(
        partition_size=args.cohort,
        num_local_steps=args.local_steps,
        grad_clip=1.0,
        compression=args.compression,
        straggler_mask=args.stragglers,
    )
    if args.pods == 1:
        round_fn = make_local_sgd_round(loss_fn, client_opt, server_opt,
                                        round_cfg)
    else:
        round_cfg = dataclasses.replace(
            round_cfg, partition_size=_clients_per_pod(args),
            num_pods=args.pods, mesh=mesh,
            partition_axes=placement_axes_for(mesh),
            fused_reduce=True if args.compression == "int8" else None,
        )
        round_fn = make_hierarchical_local_sgd_round(
            loss_fn, client_opt, server_opt, round_cfg)
    # Donate the carried state (params, server_state): the round loop below
    # rebinds both every round, so the executable updates them in place.
    return jax.jit(round_fn, donate_argnums=(0, 1)), server_opt


def _round_range(text: str) -> range:
    """``START:END`` -> the rounds START..END-1."""
    start, sep, end = text.partition(":")
    if not sep:
        raise argparse.ArgumentTypeError(f"want START:END, got {text!r}")
    return range(int(start), int(end))


class RoundProfiler:
    """Records ``rounds`` with the JAX profiler into ``log_dir``. The trace
    starts as the first of them begins and stops as a later round begins or
    training ends, so it holds their checkpoint saves too."""

    def __init__(self, log_dir: str, rounds: range):
        self.log_dir, self.rounds = log_dir, rounds
        self.state = "waiting"

    def round_begins(self, round_idx: int) -> None:
        if self.state == "waiting" and round_idx == self.rounds.start:
            jax.profiler.start_trace(self.log_dir)
            self.state = "tracing"
        elif round_idx >= self.rounds.stop:
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            jax.profiler.stop_trace()
            self.state = "done"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm_350m", choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--algorithm", default="local_sgd",
                    choices=("local_sgd", "fedavg", "diloco"))
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--cohort", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--compression", default=None,
                    choices=(None, "int8", "topk"))
    ap.add_argument("--pods", type=int, default=1,
                    help="train the nested round over this many pods of "
                         "cohort / pods clients (1: the flat round)")
    ap.add_argument("--stragglers", action="store_true")
    ap.add_argument("--straggler-deadline-pct", type=float, default=90.0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated failures at these rounds")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--chaos", action="store_true",
                    help="run the chaos soak harness instead of training: "
                         "composed fault injection (device failures, pod "
                         "dropout/regrowth, straggler deadlines, checkpoint "
                         "faults, serve traffic) with the production "
                         "invariants asserted (see repro.runtime.chaos)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-dir", default=None,
                    help="record rounds with the JAX profiler into this "
                         "directory")
    ap.add_argument("--profile-rounds", type=_round_range, default=None,
                    metavar="START:END",
                    help="with --profile-dir, record rounds START..END-1 "
                         "only (default: every round)")
    return ap.parse_args(argv)


def train(args, *, on_round=None) -> dict:
    """Train ``args.rounds`` rounds through :func:`run_with_recovery`.

    ``on_round(round_idx, state, metrics)`` sees every completed round's
    state. Returns the config, the per-round losses and seconds (host clock
    around the round, ended by ``block_until_ready``), the recovery stats,
    the final state and the checkpoint manager.
    """
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        args.seq = min(args.seq, 64)
        args.batch = min(args.batch, 4)

    params = registry.init_params(jax.random.PRNGKey(args.seed), cfg)
    layout = round_layout(args)
    round_fn, server_opt = build_round_fn(cfg, args, layout.mesh)
    server_state = server_opt.init(params)
    params, server_state = layout.state((params, server_state))

    corpus = GroupedCorpus(vocab_size=cfg.vocab_size)
    sampler = CohortSampler(corpus, cohort_size=args.cohort)
    strag = StragglerSimulator() if args.stragglers else None
    injector = FailureInjector(args.fail_at)
    mgr = CheckpointManager(args.ckpt_dir, keep_last_n=3)
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
    )
    logger.info("arch=%s params=%.2fM cohort=%d local_steps=%d",
                cfg.name, n_params / 1e6, args.cohort, args.local_steps)

    history, round_s = [], []
    profiler = (RoundProfiler(args.profile_dir,
                              args.profile_rounds or range(args.rounds))
                if args.profile_dir else None)

    def round_step(round_idx, state):
        if profiler is not None:
            profiler.round_begins(round_idx)
        with jax.profiler.StepTraceAnnotation("round", step_num=round_idx):
            return _round(round_idx, state)

    def _round(round_idx, state):
        injector.check(round_idx)
        params, server_state = state["params"], state["server"]
        with jax.profiler.TraceAnnotation("sample"):
            data = sampler.round_batch(
                round_idx, args.local_steps, args.batch, args.seq
            )
            batch = layout.batch(
                {"tokens": data["tokens"], "labels": data["labels"]})
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dispatch"):
            if strag is not None:
                durations = strag.durations(round_idx, args.cohort)
                deadline = float(
                    np.percentile(durations, args.straggler_deadline_pct)
                )
                mask = straggler_mask(durations, deadline,
                                      min_finishers=max(args.cohort // 2, 1))
                out = round_fn(params, server_state, batch,
                               layout.batch(mask))
            else:
                out = round_fn(params, server_state, batch)
        with jax.profiler.TraceAnnotation("wait"):
            params, server_state, metrics = jax.block_until_ready(out)
        round_s.append(time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation("readback"):
            loss = float(metrics["loss"])
        history.append(loss)
        if round_idx % args.log_every == 0:
            logger.info("round %d loss %.4f (%.2fs)", round_idx, loss,
                        round_s[-1])
        state = {"params": params, "server": server_state}
        if on_round is not None:
            on_round(round_idx, state, metrics)
        return state

    init_state = {"params": params, "server": server_state}
    try:
        final, stats = run_with_recovery(
            round_step, init_state, args.rounds, mgr,
            checkpoint_every=args.ckpt_every,
        )
    finally:
        if profiler is not None:
            profiler.stop()
    return {
        "cfg": cfg, "n_params": n_params, "history": history,
        "round_s": round_s, "stats": stats, "final": final, "ckpt": mgr,
    }


def main(argv=None):
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()

    if args.chaos:
        from repro.runtime.chaos import ChaosConfig, run_chaos_soak

        report = run_chaos_soak(ChaosConfig(
            rounds=args.rounds if args.rounds != 100 else 48,
            seed=args.seed,
            checkpoint_every=min(args.ckpt_every, 8),
            ckpt_dir=None,  # soak state is throwaway; never reuse --ckpt-dir
        ))
        logger.info(
            "chaos soak survived: %d failures, %d elastic events, "
            "%d fallback restores, bitwise=%s",
            report.device_failures, len(report.elastic_events),
            report.fallback_restores, report.oracle_bitwise_equal,
        )
        print(json.dumps(report.to_json(), indent=2))
        return

    result = train(args)
    history, stats = result["history"], result["stats"]
    logger.info("done: %d rounds, %d restarts, final loss %.4f",
                args.rounds, stats["restarts"],
                history[-1] if history else float("nan"))
    print(json.dumps({
        "arch": result["cfg"].name,
        "algorithm": args.algorithm,
        "rounds": args.rounds,
        "restarts": stats["restarts"],
        "first_loss": history[0] if history else None,
        "final_loss": history[-1] if history else None,
    }))


if __name__ == "__main__":
    main()
