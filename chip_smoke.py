#!/usr/bin/env python3
"""Bring-up smoke of the DrJAX training round on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the paths that exist only across chips

One chip runs three phases in one process, on lm_350m at its published
widths (``configs/lm_350m.py``, random weights from seed 0) with a
cohort of 4 clients, 4 local steps, batch 4 and sequence 512:

* ``train``: 3 rounds through the donated round and the recovery loop that
  ``repro.launch.train`` runs, one checkpoint saved and restored bitwise;
* ``plan``: the same jit-wrapped round through ``build_plan`` and
  ``plan.compile()``, compared with the train phase's first round, and a
  control round (half the clients' data replaced by the other half's) that
  the comparison must reject;
* ``hier-int8``: the pod-hierarchical round (2 pods x 2 clients, int8
  cross-pod leg) whose compiled HLO must hold the Mosaic reduce+compress
  kernel, and that kernel against its jnp oracle on the round's own
  flat-packed client deltas.

``--chips 4`` runs only the sharded round (one client per chip on a
``(data=4, model=1)`` mesh) and the hier-int8 round on a ``(pod=2, data=2)``
mesh, each against the same round on one device, and prints every chip's
memory use.

Each phase prints its compile seconds (XLA and Mosaic backend compiles, as
JAX's monitoring reports them: ``compile_s`` for every compile in the phase,
``round_compile_s`` for the round's own program), its seconds per round
(host clock, ended by ``block_until_ready``), its losses and the device's
``peak_bytes_in_use``.
The last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU,
or when a phase fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat, compression, optim  # noqa: E402
from repro import core as drjax  # noqa: E402
from repro.algorithms import rounds as rounds_lib  # noqa: E402
from repro.data.grouped import CohortSampler, GroupedCorpus  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import registry  # noqa: E402

# The round's shape on one chip: lm_350m's 470M bf16 parameters with this
# cohort fit v5e's 16 GB (cohort 8 at batch 8, train.py's default, does not).
SHAPE = ("--cohort", "4", "--local-steps", "4", "--batch", "4",
         "--seq", "512")
ROUNDS = 3
PODS = 2
# Layers of the hier-int8 rounds. At all 24 the round needs 15.04 GB of
# temporaries and 0.94 GB of arguments, over a v5e's 15.75 GB: its four
# clients' f32 deltas (1.9 GB each) are live next to their flat-packed copy.
# Widths stay lm_350m's; depth is cut to 16 (11.6 GB).
HIER_LAYERS = 16

# Tolerances between two programs that compute the same round from the same
# parameters p0 and data (the plan executor's HLO and the jitted round, or
# one device and a mesh, whose client matmuls batch and whose reductions
# order differently).
#
# The loss is an f32 mean over clients, steps and tokens of bf16 matmul
# outputs; the two programs may round single bf16 values differently, 2**-9
# relative each, which averaged over ~10^4 tokens stays far below 1e-3. The
# loss is taken during the clients' local steps from p0, so it checks the
# client leg only: a fault in the reduce or the server update leaves it as
# it was.
LOSS_RTOL = 1e-3
# The round's output is checked as an update: over the whole parameter tree,
# ||a - b|| / ||b - p0||, the distance between the two rounds' parameters
# relative to the size of the update. Against ||b|| it would say nothing: one
# round moves a bf16 weight of ~0.02 by ~1e-5, under half its ulp (1.2e-4),
# so the weight does not move at all. The update is the few elements whose
# step clears half their ulp: 4e-6 to 4e-5 of ||p0|| on a TPU v5e. Two
# programs that round single values differently move different elements
# across that threshold, so even the same round reads far from 0: a mesh
# against one device read 0.255 (sharded round) and 0.300 (hier-int8) on a
# v5e. A round that skips the update reads 1. Every phase that compares
# also runs a control, the round with the second half of the cohort's data
# replaced by the first half's, which must read above the limit: 1.32 and
# 1.42 on the v5e, 0.57 to 0.85 on the reduced f32 model on the CPU, where
# the same round reads under 1e-4. The limit sits between the two regimes.
UPDATE_RTOL = 0.4
# The Mosaic kernel and the jnp oracle quantize the same partial mean; an
# ulp of difference in that mean can move a value across a rounding
# boundary, so they may differ by one int8 step (the row's scale), never
# more. The slack covers the f32 rounding of step * 1.
INT8_STEPS = 1.0 + 1e-5

# Events in which JAX compiles: the XLA backend compile, which includes the
# Mosaic kernel compile and is what the persistent compilation cache saves.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent in backend compiles, and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.per_fun = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, fun_name="", **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.per_fun[fun_name] += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return self.seconds, self.cache_hits, collections.Counter(self.per_fun)

    def since(self, mark):
        """(compile seconds, cache hits) since ``mark``."""
        return self.seconds - mark[0], self.cache_hits - mark[1]

    def fun_since(self, mark, fun_name: str) -> float:
        """Compile seconds of the jitted function ``fun_name`` since ``mark``."""
        return self.per_fun[fun_name] - mark[2][fun_name]


def memory(device=None) -> dict:
    """``bytes_in_use`` / ``peak_bytes_in_use`` of one device (the process
    peak so far: the backend does not reset it between phases)."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def all_devices_used(mem: dict) -> bool:
    """Every device that reports memory (a TPU does; the CPU does not) has
    held something."""
    return all(m["peak_bytes_in_use"] is None or m["peak_bytes_in_use"] > 0
               for m in mem.values())


def report(phase: str, **fields) -> dict:
    for key, value in fields.items():
        print(f"[{phase}] {key}: {json.dumps(value)}", flush=True)
    return {"phase": phase, **fields}


def finite(values) -> bool:
    return bool(np.all(np.isfinite(np.asarray(values, np.float64))))


def rel_close(a: float, b: float, rtol: float) -> bool:
    return abs(a - b) <= rtol * abs(b)


@jax.jit
def _update_norms(a, b, p0):
    """f32 norms, over the whole tree, of a - b, b - p0 and p0."""
    def leaves(tree):
        return [x.astype(jnp.float32) for x in jax.tree_util.tree_leaves(tree)]

    def norm(xs):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in xs))

    a, b, p0 = leaves(a), leaves(b), leaves(p0)
    return (norm([x - y for x, y in zip(a, b)]),
            norm([y - z for y, z in zip(b, p0)]), norm(p0))


def same_tree(a, b) -> bool:
    """Two host trees hold the same bytes."""
    return all(
        x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


def update_distance(a, b, p0) -> tuple:
    """(||a - b|| / ||b - p0||, ||b - p0|| / ||p0||) over the whole tree, in
    f32: how far round ``a`` is from round ``b``, both started from ``p0``,
    relative to ``b``'s update; and that update's size."""
    diff, update, base = (float(v) for v in _update_norms(a, b, p0))
    return diff / max(update, 1e-30), update / max(base, 1e-30)


def round_batch(args, cfg, round_idx: int) -> dict:
    """The batch ``launch.train`` feeds round ``round_idx``."""
    sampler = CohortSampler(GroupedCorpus(vocab_size=cfg.vocab_size),
                            cohort_size=args.cohort)
    data = sampler.round_batch(round_idx, args.local_steps, args.batch,
                               args.seq)
    return {"tokens": data["tokens"], "labels": data["labels"]}


def init_state(args, cfg, server_opt):
    params = registry.init_params(jax.random.PRNGKey(args.seed), cfg)
    return params, server_opt.init(params)


def smoke_args(ckpt_dir: str, extra=()) -> argparse.Namespace:
    return train_lib.parse_args(
        ["--arch", "lm_350m", *SHAPE, "--rounds", str(ROUNDS),
         "--ckpt-dir", ckpt_dir, "--ckpt-every", str(ROUNDS), *extra]
    )


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_train(args, clock):
    """``args.rounds`` rounds of ``launch.train``; returns the report, the config
    and round 0's loss and parameters (on the host) for the plan phase.
    ``compile_s`` also holds the eager compiles of parameter and optimizer
    init; ``round_compile_s`` is the round's own (``train.build_round_fn``)."""
    first = {}

    def on_round(idx, state, metrics):
        if idx == 0:
            first["params"] = jax.device_get(state["params"])

    mark = clock.mark()
    result = train_lib.train(args, on_round=on_round)
    compile_s, hits = clock.since(mark)
    round_compile_s = clock.fun_since(mark, "jit(round_fn)")
    losses = result["history"]
    check(len(losses) == args.rounds and finite(losses),
          f"train losses {losses}")
    check(result["stats"]["restarts"] == 0, f"restarts {result['stats']}")

    final = jax.device_get(result["final"])
    step, restored, _ = result["ckpt"].restore_latest(final)
    check(step == args.rounds, f"restored step {step}, want {args.rounds}")
    check(same_tree(final, restored),
          "restored checkpoint differs from the saved state")
    rep = report(
        "train", params_m=round(result["n_params"] / 1e6, 1),
        compile_s=compile_s, round_compile_s=round_compile_s,
        compile_cache_hits=hits, round_s=result["round_s"], losses=losses,
        checkpoint_restored_bitwise=True, **memory(),
    )
    first["loss"] = losses[0]
    return rep, result["cfg"], first


def control_batch(batch: dict) -> dict:
    """``batch`` with the second half of its outermost client axis (clients,
    or pods) replaced by the first half: a round whose mean update misses
    half the cohort, as a dropped shard or pod would."""
    out = {}
    for k, v in batch.items():
        v = np.array(v)
        half = v.shape[0] // 2
        v[v.shape[0] - half:] = v[:half]
        out[k] = v
    return out


def phase_plan(args, cfg, first, clock):
    """The same jit-wrapped round through ``build_plan`` and the compiled
    plan executor, on round 0's inputs; then the control round."""
    mark = clock.mark()
    round_fn, server_opt = train_lib.build_round_fn(cfg, args)
    params, sstate = init_state(args, cfg, server_opt)
    batch = round_batch(args, cfg, 0)
    closed, out_shape = jax.make_jaxpr(round_fn, return_shape=True)(
        params, sstate, batch
    )
    plan = drjax.build_plan(closed, args.cohort)
    compiled = plan.compile()
    tree = jax.tree_util.tree_structure(out_shape)

    def run(b):
        t0 = time.perf_counter()
        outs = jax.block_until_ready(
            compiled(*jax.tree_util.tree_leaves((params, sstate, b)))
        )
        new_params, _, metrics = jax.tree_util.tree_unflatten(tree, list(outs))
        return new_params, float(metrics["loss"]), time.perf_counter() - t0

    round_mark = clock.mark()
    new_params, loss, first_s = run(batch)  # this call compiles
    round_compile_s, _ = clock.since(round_mark)
    _, _, second_s = run(batch)
    ctl_params, ctl_loss, _ = run(control_batch(batch))
    compile_s, hits = clock.since(mark)

    diff, update = update_distance(new_params, first["params"], params)
    ctl_diff, _ = update_distance(ctl_params, first["params"], params)
    rep = report(
        "plan", stages=[s.kind for s in plan.stages], compile_s=compile_s,
        round_compile_s=round_compile_s, compile_cache_hits=hits,
        round_s=[first_s, second_s], losses=[loss],
        train_round0_loss=first["loss"], update_rel_norm=update,
        update_rel_diff=diff, control_loss=ctl_loss,
        control_update_rel_diff=ctl_diff, **memory(),
    )
    check(compiled.trace_count == 1, f"plan traced {compiled.trace_count}x")
    check(finite([loss]), f"plan loss {loss}")
    check(rel_close(loss, first["loss"], LOSS_RTOL),
          f"plan loss {loss} vs train round 0 {first['loss']}")
    check(diff <= UPDATE_RTOL,
          f"plan update differs from train round 0 by {diff}")
    check(ctl_diff > UPDATE_RTOL,
          f"the control round passes the update check ({ctl_diff})")
    return rep


def hier_round(cfg, args, devices):
    """The int8 pod-hierarchical round as ``launch.train --pods`` builds it
    on ``devices`` (a ``(pod, data)`` mesh of them, or one device): the
    jitted, donated round, its layout, its server optimizer and its client
    leg without compression. The fused kernel is insisted on: a fallback to
    the generic composition raises instead of passing unseen."""
    args = argparse.Namespace(**{**vars(args), "pods": PODS,
                                 "compression": "int8"})
    layout = train_lib.round_layout(args, devices)
    step, server_opt = train_lib.build_round_fn(cfg, args, layout.mesh)
    client_update = rounds_lib._make_client_update(
        functools.partial(registry.loss_fn, cfg), optim.sgd(args.client_lr),
        rounds_lib.LocalSGDConfig(partition_size=args.cohort // PODS,
                                  num_local_steps=args.local_steps,
                                  grad_clip=1.0),
    )
    return step, layout, server_opt, client_update


@functools.partial(jax.jit, static_argnames=("client_update", "interpret"))
def _kernel_vs_oracle(params, data, client_update, interpret):
    """Max distance, in int8 steps, between the Mosaic reduce+compress
    kernel and its jnp oracle on the flat-packed client deltas of a round."""
    deltas, _ = jax.vmap(jax.vmap(client_update, (None, 0)), (None, 0))(
        params, data
    )
    bufs, _ = compression.flat_pack(deltas, lead_ndim=2,
                                    cols=compression.PACK_COLS)
    worst = []
    for buf in bufs.values():
        kern = ops.reduce_compress_roundtrip(buf, axis=1, backend="pallas",
                                             interpret=interpret)
        ref = ops.reduce_compress_roundtrip(buf, axis=1, backend="jnp")
        part = jnp.mean(buf.astype(jnp.float32), axis=1)
        step = jnp.maximum(
            jnp.max(jnp.abs(part), axis=-1, keepdims=True) / 127.0, 1e-12
        )
        worst.append(jnp.max(jnp.abs(kern.astype(jnp.float32) - ref) / step))
    return jnp.max(jnp.stack(worst))


def phase_hier_int8(args, cfg, clock, *, kernel_marker="tpu_custom_call",
                    interpret=False):
    """``kernel_marker``: the text the compiled round's HLO must hold to
    show that the Mosaic kernel, not the jnp oracle, runs in the round."""
    mark = clock.mark()
    step, layout, server_opt, client_update = hier_round(
        cfg, args, jax.devices()[:1])
    params, sstate = layout.state(init_state(args, cfg, server_opt))

    def pod_batch(round_idx):
        return layout.batch(round_batch(args, cfg, round_idx))

    kernel_steps = float(_kernel_vs_oracle(
        params, pod_batch(0), client_update, interpret
    ))

    round_mark = clock.mark()
    compiled = step.lower(params, sstate, pod_batch(0)).compile()
    round_compile_s, _ = clock.since(round_mark)
    kernel = kernel_marker is None or kernel_marker in compiled.as_text()

    losses, round_s = [], []
    for r in range(ROUNDS):
        batch = pod_batch(r)
        t0 = time.perf_counter()
        params, sstate, metrics = jax.block_until_ready(
            compiled(params, sstate, batch)
        )
        round_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    compile_s, hits = clock.since(mark)
    rep = report(
        "hier-int8", pods=PODS, clients_per_pod=args.cohort // PODS,
        layers=cfg.num_layers, kernel_in_hlo=kernel_marker,
        kernel_vs_oracle_int8_steps=kernel_steps, compile_s=compile_s,
        round_compile_s=round_compile_s, compile_cache_hits=hits,
        round_s=round_s, losses=losses, **memory(),
    )
    check(kernel_steps <= INT8_STEPS,
          f"kernel vs oracle differ by {kernel_steps} int8 steps")
    check(kernel, f"{kernel_marker} missing from the hier-int8 round's HLO")
    check(finite(losses), f"hier-int8 losses {losses}")
    return rep


def run_one_chip(args, clock, *, hier_layers=None, kernel_marker="tpu_custom_call",
                 interpret=False):
    rep, cfg, first = phase_train(args, clock)
    reports = [rep, phase_plan(args, cfg, first, clock)]
    del first
    hier_cfg = cfg if hier_layers is None else dataclasses.replace(
        cfg, num_layers=hier_layers
    )
    reports.append(phase_hier_int8(args, hier_cfg, clock,
                                   kernel_marker=kernel_marker,
                                   interpret=interpret))
    return reports


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _timed_round(clock, step, params, sstate, batch):
    """Compile ``step`` for these arguments, run one round, then the control
    round from the same start: (loss, start and end parameters on the host,
    round compile s, round s, control end parameters), and the executable."""
    mark = clock.mark()
    compiled = step.lower(params, sstate, batch).compile()
    compile_s, _ = clock.since(mark)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, (params, sstate))
    start = jax.device_get((params, sstate))  # the round donates its inputs
    t0 = time.perf_counter()
    params, sstate, metrics = jax.block_until_ready(
        compiled(params, sstate, batch)
    )
    round_s = time.perf_counter() - t0
    end, loss = jax.device_get(params), float(metrics["loss"])
    del params, sstate  # free the device for the control round
    ctl = jax.tree_util.tree_map(
        lambda c, b: jax.device_put(c, b.sharding),
        control_batch(jax.device_get(batch)), batch,
    )
    ctl_params, _, _ = compiled(*jax.device_put(start, shardings), ctl)
    return (loss, start[0], end, compile_s, round_s,
            jax.device_get(ctl_params)), compiled


def _compare_rounds(phase, clock, mark, mesh_run, one_run, mem4, **fields):
    """Report a mesh round against its one-device run, then check them."""
    compile_s, hits = clock.since(mark)
    loss4, p0_4, params4, compile4, round_s4, _ = mesh_run
    loss1, p0_1, params1, compile1, round_s1, ctl1 = one_run
    diff, update = update_distance(params4, params1, p0_1)
    ctl_diff, _ = update_distance(ctl1, params1, p0_1)
    rep = report(
        phase, **fields, compile_s=compile_s,
        round_compile_s={"mesh": compile4, "one_device": compile1},
        compile_cache_hits=hits,
        round_s={"mesh": round_s4, "one_device": round_s1},
        losses={"mesh": loss4, "one_device": loss1},
        update_rel_norm=update, update_rel_diff=diff,
        control_update_rel_diff=ctl_diff, memory_per_device=mem4,
    )
    check(same_tree(p0_4, p0_1), "the mesh and one-device rounds start apart")
    check(finite([loss4, loss1]), f"losses {loss4} {loss1}")
    check(rel_close(loss4, loss1, LOSS_RTOL),
          f"{phase} loss {loss4} vs one device {loss1}")
    check(diff <= UPDATE_RTOL, f"{phase} update differs by {diff}")
    check(ctl_diff > UPDATE_RTOL,
          f"{phase}: the control round passes the update check ({ctl_diff})")
    check(all_devices_used(mem4), f"a chip holds nothing: {mem4}")
    return rep


def _sharded_round_once(cfg, args, mesh, clock):
    """One round of ``make_drjax_round_step`` on ``mesh``, and the memory of
    each of its devices right after it."""
    step, param_sh, server_sh, data_sh = steps_lib.make_drjax_round_step(
        cfg, mesh, partition_size=args.cohort,
        num_local_steps=args.local_steps, client_lr=args.client_lr,
        jit_donated=True,
    )
    params = jax.jit(lambda k: registry.init_params(k, cfg),
                     out_shardings=param_sh)(jax.random.PRNGKey(args.seed))
    sstate = jax.jit(optim.fedavg_momentum(1.0).init,
                     out_shardings=server_sh)(params)
    batch = round_batch(args, cfg, 0)
    batch = jax.device_put(
        batch, jax.tree_util.tree_map(data_sh, batch)
    )
    run, _ = _timed_round(clock, step, params, sstate, batch)
    return run, {d.id: memory(d) for d in mesh.devices.flat}


def phase_sharded_round(args, cfg, clock, devices):
    mark = clock.mark()
    mesh4 = compat.make_mesh((len(devices), 1), ("data", "model"),
                             devices=devices)
    run4, mem4 = _sharded_round_once(cfg, args, mesh4, clock)
    mesh1 = compat.make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    run1, _ = _sharded_round_once(cfg, args, mesh1, clock)
    return _compare_rounds("sharded-round", clock, mark, run4, run1, mem4,
                           mesh={"data": len(devices), "model": 1})


def _hier_once(cfg, args, devices, clock):
    """One hier-int8 round on ``devices``: the run, the memory of each
    device, the text of its executable and its mesh's shape (None on one
    device)."""
    step, layout, server_opt, _ = hier_round(cfg, args, devices)
    params, sstate = layout.state(init_state(args, cfg, server_opt))
    batch = layout.batch(round_batch(args, cfg, 0))
    run, compiled = _timed_round(clock, step, params, sstate, batch)
    shape = None if layout.mesh is None else dict(layout.mesh.shape)
    return (run, {d.id: memory(d) for d in devices}, compiled.as_text(),
            shape)


def phase_hier_sharded(args, cfg, clock, devices, kernel_marker):
    mark = clock.mark()
    run4, mem4, hlo4, mesh = _hier_once(cfg, args, devices, clock)
    run1, _, hlo1, _ = _hier_once(cfg, args, devices[:1], clock)
    rep = _compare_rounds("hier-int8-sharded", clock, mark, run4, run1, mem4,
                          mesh=mesh, layers=cfg.num_layers,
                          kernel_in_hlo=kernel_marker)
    check(kernel_marker is None
          or (kernel_marker in hlo4 and kernel_marker in hlo1),
          f"{kernel_marker} missing from a hier round")
    return rep


def run_four_chips(args, clock, devices, *, hier_layers=None,
                   kernel_marker="tpu_custom_call"):
    # Batch 2 per client: the one-device reference of the sharded round
    # (make_drjax_round_step's axis rules on a 1x1 mesh) needs 15.2 GB of a
    # v5e's 15.75 GB at batch 4, and 13.8 GB at batch 2.
    args = argparse.Namespace(**{**vars(args), "batch": min(args.batch, 2)})
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    hier_cfg = cfg if hier_layers is None else dataclasses.replace(
        cfg, num_layers=hier_layers
    )
    return [phase_sharded_round(args, cfg, clock, devices),
            phase_hier_sharded(args, hier_cfg, clock, devices, kernel_marker)]


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    opts = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if dev.memory_stats() is None:
        print("chip_smoke: the TPU reports no memory_stats()", file=sys.stderr)
        return 2
    if len(devices) != opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        args = smoke_args(ckpt)
        if opts.chips == 1:
            run_one_chip(args, clock, hier_layers=HIER_LAYERS)
        else:
            run_four_chips(args, clock, devices, hier_layers=HIER_LAYERS)
    print(f"total_s: {time.perf_counter() - t0:.1f}  compile_s: "
          f"{clock.seconds:.1f}  compile_cache_hits: {clock.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
