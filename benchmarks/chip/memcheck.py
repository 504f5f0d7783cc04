#!/usr/bin/env python3
"""Compile each cell's round, and the reference's client step, for a
described TPU v5e without one, and print what each needs of a chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/memcheck.py [cell ...]

The cells are BENCHMARK.json's one-chip cells, or those named. Each
compiles for one device of a described ``v5e:2x2``. The kernel dispatch
asks JAX for the backend, which here is the CPU; this script steers it to
the TPU branch so that a round's HLO holds its Mosaic kernels as on the
chip. Nothing runs: the numbers are the compiler's, not a chip's.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402

from benchmarks.chip import reference, run, weights  # noqa: E402
from repro.kernels import ops  # noqa: E402

GB = 1e9


def described(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments_gb": m.argument_size_in_bytes / GB,
            "outputs_gb": m.output_size_in_bytes / GB,
            "temporaries_gb": m.temp_size_in_bytes / GB,
            "aliased_gb": m.alias_size_in_bytes / GB,
            "hbm_gb": run.hbm_bytes(compiled) / GB}


def check_cell(name: str, topo) -> dict:
    cell = run.load_cell(name)
    if cell["chips"] != 1:
        raise ValueError(f"{name}: only one-chip cells are compiled here")
    c, t = cell["config"], cell["traffic"]
    device = topo.devices[0]
    rnd = run.load_module("rounds", t["round"]).build(c, t, [device])
    params, sstate = jax.eval_shape(rnd.init, weights.seed_array(0))
    step_shape = (t["local_steps"], t["batch"], t["seq"])
    batch = {k: jax.ShapeDtypeStruct((t["cohort"],) + step_shape, jnp.int32)
             for k in ("tokens", "labels")}
    one = jax.sharding.SingleDeviceSharding(device)
    compiled = rnd.step.lower(described(params, one), described(sstate, one),
                              described(batch, one)).compile()
    text = compiled.as_text()
    out = {"round": memory(compiled),
           "kernel_in_hlo": "tpu_custom_call" in text}
    ref_step = reference.client_update.lower(
        reference.frozen(c), reference.frozen(t), None,
        described(params, one),
        jax.ShapeDtypeStruct(step_shape, jnp.int32),
        jax.ShapeDtypeStruct(step_shape, jnp.int32)).compile()
    out["reference_client_step"] = memory(ref_step)
    return out


def main(argv) -> int:
    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = argv or [w["name"] for w in bench["workloads"] if w["chips"] == 1]
    ops._on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(name, run.json.dumps(check_cell(name, topo), indent=1),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
