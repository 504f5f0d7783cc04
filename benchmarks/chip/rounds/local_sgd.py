"""The flat local-SGD round of ``launch.train``: ``build_round_fn`` with
``--algorithm local_sgd`` and no compression, jitted with the parameters and
the server state donated."""

from __future__ import annotations

import jax

from benchmarks.chip import program
from repro.launch import train as train_lib

# build_round_fn fixes these; a traffic file that asks for others is refused.
FIXED = {"grad_clip": 1.0, "server_lr": 1.0}
# The program's own lower-precision path: int8 compression of the deltas.
VARIANTS = {"program_int8": {"compression": "int8"}}


def build(c: dict, t: dict, devices: list, compression=None) -> program.Round:
    for key, value in FIXED.items():
        if t[key] != value:
            raise ValueError(f"{key} {t[key]}: launch.train's round uses {value}")
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    argv = ["--arch", c["arch"], "--algorithm", "local_sgd",
            "--cohort", str(t["cohort"]), "--local-steps", str(t["local_steps"]),
            "--batch", str(t["batch"]), "--seq", str(t["seq"]),
            "--client-lr", str(t["client_lr"])]
    if compression:
        argv += ["--compression", compression]
    step, server_opt = train_lib.build_round_fn(cfg, train_lib.parse_args(argv))
    dev = devices[0]
    return program.Round(
        step=step,
        init=program.make_init(c, server_opt,
                               jax.sharding.SingleDeviceSharding(dev)),
        place=lambda batch: jax.device_put(batch, dev),
        devices=[dev],
    )
