"""DrJAX's nested round of ``launch.train``: ``build_round_fn`` with
``--pods``, the pod partials through the fused int8 reduce+compress kernel,
on a ``(pod, data)`` mesh of the devices it is given (the cell's four;
one device where only one is given), jitted with the parameters and the
server state donated."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import program
from repro.launch import train as train_lib

REFERENCE = "reference_hier"
# build_round_fn fixes these; a traffic file that asks for others is refused.
FIXED = {"grad_clip": 1.0, "server_lr": 1.0}
# The program's own lower-precision path of this round: the pod partials
# sparsified to their top 1 % of entries in place of the int8 roundtrip.
VARIANTS = {"program_topk": {"compression": "topk"}}


def _pod0_everywhere(x):
    """Pod 0's clients' data in place of every pod's."""
    return jax.device_put(jnp.broadcast_to(x[:1], x.shape), x.sharding)


def exchange(step):
    """The cross-pod mean left out: the parameters take pod 0's partial in
    place of the mean of both pods' partials (the round run with every pod
    on pod 0's data), while the loss is the round's own."""
    def broken(params, sstate, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (params, sstate))
        _, _, metrics = step(params, sstate, batch)
        params, sstate, _ = step(*keep, jax.tree_util.tree_map(
            _pod0_everywhere, batch))
        return params, sstate, metrics

    return broken


FAULTS = {"exchange": exchange}


def build(c: dict, t: dict, devices: list, compression=None) -> program.Round:
    for key, value in FIXED.items():
        if t[key] != value:
            raise ValueError(f"{key} {t[key]}: launch.train's round uses {value}")
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    argv = ["--arch", c["arch"], "--algorithm", "local_sgd",
            "--pods", str(t["pods"]), "--cohort", str(t["cohort"]),
            "--local-steps", str(t["local_steps"]), "--batch", str(t["batch"]),
            "--seq", str(t["seq"]), "--client-lr", str(t["client_lr"]),
            "--compression", compression or t["compression"]]
    args = train_lib.parse_args(argv)
    layout = train_lib.round_layout(args, devices)
    step, server_opt = train_lib.build_round_fn(cfg, args, layout.mesh)
    state = (jax.sharding.NamedSharding(layout.mesh,
                                        jax.sharding.PartitionSpec())
             if layout.mesh is not None
             else jax.sharding.SingleDeviceSharding(devices[0]))
    return program.Round(
        step=step,
        init=program.make_init(c, server_opt, state),
        place=layout.batch,
        devices=devices,
    )
