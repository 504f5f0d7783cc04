"""Sharded hierarchical-reduce point: subprocess wrapper for benchmarks.run.

The measurement itself lives in the dry-run driver
(``repro.launch.dryrun --hier-sweep``): it needs a multi-device (pod, data)
mesh, and the host device count is locked at JAX's first init — so it must
run in a fresh process with ``XLA_FLAGS`` forcing a small fake pool (the
same pattern as the fig4/fig5 weak-scaling benches). The sweep appends the
sharded point to this commit's ``BENCH_hier.json`` trajectory entry.
"""

from __future__ import annotations

import json
import subprocess
import sys

from ._util import _REPO, cpu_devices_env


def run(num_devices: int = 8):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--hier-sweep"],
        capture_output=True, text=True, cwd=_REPO, timeout=600,
        env=cpu_devices_env(num_devices),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"--hier-sweep failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    pt = payload["hier_sweep"]
    key = f"hier_sharded_P{pt['num_pods']}x{pt['mesh']['data']}dev"
    return [
        {
            "name": f"{key}_flat",
            "us_per_call": f"{pt['flat_us_per_call']:.1f}",
            "derived": f"devices={pt['devices']}",
        },
        {
            "name": f"{key}_hier",
            "us_per_call": f"{pt['hier_us_per_call']:.1f}",
            "derived": f"hier_vs_flat={pt['hier_vs_flat']:.2f}",
        },
        {
            "name": f"{key}_fused_int8",
            "us_per_call": f"{pt['fused_us_per_call']:.1f}",
            "derived": f"fused_vs_flat={pt['fused_vs_flat']:.2f}",
        },
    ]


if __name__ == "__main__":
    for row in run():
        print(f"{row['name']},{row['us_per_call']},{row['derived']}")
