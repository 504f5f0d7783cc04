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
"""

from __future__ import annotations

import argparse
import functools
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round
from repro.checkpoint import CheckpointManager
from repro.data.grouped import CohortSampler, GroupedCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.runtime.failure import FailureInjector, run_with_recovery
from repro.runtime.stragglers import StragglerSimulator, straggler_mask

logger = logging.getLogger(__name__)


def build_round_fn(cfg, args):
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
    round_fn = make_local_sgd_round(loss_fn, client_opt, server_opt, round_cfg)
    # Donate the carried state (params, server_state): the round loop below
    # rebinds both every round, so the executable updates them in place.
    return jax.jit(round_fn, donate_argnums=(0, 1)), server_opt


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
    round_fn, server_opt = build_round_fn(cfg, args)
    server_state = server_opt.init(params)

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

    def round_step(round_idx, state):
        injector.check(round_idx)
        params, server_state = state["params"], state["server"]
        data = sampler.round_batch(
            round_idx, args.local_steps, args.batch, args.seq
        )
        batch = {"tokens": data["tokens"], "labels": data["labels"]}
        t0 = time.perf_counter()
        if strag is not None:
            durations = strag.durations(round_idx, args.cohort)
            deadline = float(
                np.percentile(durations, args.straggler_deadline_pct)
            )
            mask = straggler_mask(durations, deadline,
                                  min_finishers=max(args.cohort // 2, 1))
            out = round_fn(params, server_state, batch, mask)
        else:
            out = round_fn(params, server_state, batch)
        params, server_state, metrics = jax.block_until_ready(out)
        round_s.append(time.perf_counter() - t0)
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
    final, stats = run_with_recovery(
        round_step, init_state, args.rounds, mgr,
        checkpoint_every=args.ckpt_every,
    )
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
