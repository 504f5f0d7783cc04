"""Chaos soak benchmark: survive composed faults, measure what they cost.

Runs :func:`repro.runtime.chaos.run_chaos_soak` (device failures + pod
dropout/regrowth + straggler deadlines + torn/corrupt checkpoints +
concurrent serve bursts with a scheduler fault) and prints the soak's
report as JSON:

* ``client_retraces`` / ``oracle_extra_traces`` — must stay 0 (the
  zero-retrace elasticity invariant);
* ``straggler.speedup`` and the masked-vs-sync p99/p50 tail ratios — the
  deadline-masking win;
* ``replayed_steps`` / ``fallback_restores`` — the replay cost of recovery
  under broken checkpoints;
* ``oracle_bitwise_equal`` — determinism under recovery.

* ``reshards`` / ``mesh_migrate_ms`` — physical-mode resharding (a real
  degraded (pod, data) mesh rebuilt from surviving devices per elastic
  event) and the cost of migrating server state onto it;
* ``mid_write_kills_injected`` / ``mid_write_kills_survived`` — writer
  killed mid-``arrays.npz``, survived via fallback restore;
* ``serve_p99_contended`` — serve p99 while a training round is in flight
  on the same devices (the co-location contention column).

``--smoke`` is the CI shape: ~20 rounds with 1 device failure, 1 elastic
event, straggler deadlines every round and a checkpoint fault.
``--physical`` runs the physical-mesh soak; it needs 8 host CPU devices and
re-execs itself on them (``JAX_PLATFORMS=cpu``, ``XLA_FLAGS``) unless its
environment already provides them. Run it as:

    PYTHONPATH=src python -m benchmarks.chaos [--smoke] [--physical]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

from repro.runtime.chaos import ChaosConfig, run_chaos_soak

from ._util import _REPO, cpu_devices_env

#: devices the physical soak needs (4 pods x 2 clients)
PHYSICAL_DEVICES = 8


def smoke_config(seed: int = 1) -> ChaosConfig:
    """~20-round CI soak: 1 failure + 1 elastic event + stragglers + 1
    checkpoint fault, serve traffic off (benchmarks.serve covers it).

    Default seed 1: the tail-ratio invariant (masked p99/p50 < sync
    p99/p50) is a statistical property; at 20 rounds a few seeds are too
    noisy to separate the distributions. The schedule is deterministic, so
    a passing seed passes forever."""
    return ChaosConfig(
        rounds=20,
        seed=seed,
        num_device_failures=1,
        num_elastic_events=1,
        num_ckpt_faults=1,
        checkpoint_every=4,
        audit_every=8,
        serve_traffic=False,
    )


def physical_config(seed: int = 1) -> ChaosConfig:
    """The 8-device physical-mesh soak: 4 pods x 2 clients on a real
    (pod, data) mesh, 2 elastic events (>= 1 dropout reshard + >= 1
    regrowth), 1 device failure and 1 mid-write checkpoint kill. Serve is
    off (the logical full soak records the contention column)."""
    return ChaosConfig(
        rounds=20,
        seed=seed,
        num_pods=4,
        clients_per_pod=2,
        num_device_failures=1,
        num_elastic_events=2,
        num_ckpt_faults=1,
        checkpoint_every=4,
        audit_every=8,
        serve_traffic=False,
        physical_mesh=True,
    )


def bench(smoke: bool = False, seed: int | None = None,
          physical: bool = False) -> dict:
    if physical:
        cfg = physical_config() if seed is None else physical_config(seed)
    elif smoke:
        cfg = smoke_config() if seed is None else smoke_config(seed)
    else:
        cfg = ChaosConfig() if seed is None else ChaosConfig(seed=seed)
    report = run_chaos_soak(cfg)  # asserts the production invariants
    point = report.to_json()
    point["mode"] = (
        "physical" if physical else ("smoke" if smoke else "full")
    )
    return point


def _on_cpu_pool() -> bool:
    """Does this process's environment already give it the CPU pool the
    physical soak needs (as :func:`cpu_devices_env` sets it)?"""
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  os.environ.get("XLA_FLAGS", ""))
    return (os.environ.get("JAX_PLATFORMS") == "cpu" and m is not None
            and int(m.group(1)) >= PHYSICAL_DEVICES)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="~20-round CI soak (1 failure, 1 elastic event, "
                         "stragglers, 1 ckpt fault)")
    ap.add_argument("--physical", action="store_true",
                    help="physical-mesh soak (real (pod, data) mesh, live "
                         f"resharding); re-execs on {PHYSICAL_DEVICES} CPU "
                         "devices unless the environment provides them")
    ap.add_argument("--json", action="store_true",
                    help="print the result as one machine-readable JSON line")
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args()
    if args.physical and not _on_cpu_pool():
        # Decided from the environment alone: touching JAX here would take
        # the accelerator, and the child could then not start.
        sys.exit(subprocess.run(
            [sys.executable, "-m", "benchmarks.chaos"] + sys.argv[1:],
            cwd=_REPO, env=cpu_devices_env(PHYSICAL_DEVICES),
        ).returncode)
    t0 = time.time()
    point = bench(smoke=args.smoke, seed=args.seed, physical=args.physical)
    point["bench_wall_s"] = round(time.time() - t0, 1)
    if args.json:
        print(json.dumps(point))
    else:
        print(json.dumps(point, indent=2))


if __name__ == "__main__":
    main()
