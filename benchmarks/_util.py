"""Shared helpers: run a measurement snippet in a subprocess with a chosen
fake-device count (the device count is locked at first JAX init, so every
(devices, partition) point needs a fresh process)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def cpu_devices_env(devices: int) -> dict:
    """Environment for a child process that runs on `devices` virtual CPU
    devices. ``JAX_PLATFORMS=cpu`` keeps it off the accelerator: a chip
    belongs to one process, and the parent may hold it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


PREAMBLE = """
import json, time
import jax, jax.numpy as jnp
import numpy as np
from repro import compat
from repro import core as drjax
"""


def run_point(body: str, devices: int = 1, timeout: int = 540, **fmt) -> dict:
    script = PREAMBLE + textwrap.dedent(body).format(devices=devices, **fmt)
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=cpu_devices_env(devices),
        timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"benchmark point failed:\n{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


# A small but real transformer round used by fig4/fig5/fig6 (same workload
# family as the paper's local SGD: L layers, d_model, per-group batches).
LOCAL_SGD_SNIPPET = """
import functools
from repro.models import registry
from repro import optim
from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round
from repro.launch import mesh as mesh_lib

cfg = registry.get_config("lm_350m").reduced(
    num_layers=2, d_model=128, num_heads=4, head_dim=32, d_ff=512,
    vocab_size=1024,
)
loss_fn = functools.partial(registry.loss_fn, cfg)
params = registry.init_params(jax.random.PRNGKey(0), cfg)
N = {partition}
DEVICES = {devices}
LOCAL_STEPS, B, S = 4, 2, 64

mesh = None
part_axes = None
if DEVICES > 1:
    mesh = mesh_lib.make_mesh((DEVICES,), ("data",))
    part_axes = "data"
"""
