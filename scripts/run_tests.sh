#!/usr/bin/env bash
# Tier-1 test suite in one command:  scripts/run_tests.sh [pytest args]
#
#   scripts/run_tests.sh                 # full suite
#   scripts/run_tests.sh -m 'not slow'   # fast run (skips multi-device tests)
#
# REPRO_HOST_DEVICES (4 or 8, default 8) sets the fake host-device count for
# the multi-device worker that tests/conftest.py spawns (it exports
# XLA_FLAGS=--xla_force_host_platform_device_count=$REPRO_HOST_DEVICES into
# that worker's environment). XLA_FLAGS is deliberately NOT exported here:
# the main pytest process must keep the default single host device — only
# the session worker forces the count.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export REPRO_HOST_DEVICES="${REPRO_HOST_DEVICES:-8}"
# The suite runs on the CPU, also on a machine with an accelerator: the chip
# is checked by chip_smoke.py, and it belongs to one process at a time.
export JAX_PLATFORMS=cpu

# Repo lints (ROADMAP "Static analysis & lints"): the unified rule registry
# in repro.analysis.lints — compat surface, donation discipline, version
# branches, jit-of-plan-stages. Replaces the former inline compat grep and
# the scripts/check_donation.py invocation (both rules live in the
# registry); any violation fails tier-1.
python scripts/lint.py --json

# Examples smoke-run: the quickstart exercises the full authoring surface
# (flat + nested placements, plan IR, Beam emitter, fused compressed
# hierarchical reduce, compiled plan executor) end to end.
python examples/quickstart.py > /dev/null

# Compiled-vs-interpreted smoke check: plan.compile() must match the
# run_plan oracle to 1 ulp for a loop-carrying round program (full coverage
# in tests/test_executor.py). Not bitwise: compiled, XLA fuses the reduce
# and the update that run_plan executes op by op, and may accumulate in
# another order (tests/_ulp.py).
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro import core as drjax

@drjax.program(partition_size=3)
def two_rounds(m, ys):
    def body(m, _):
        g = drjax.reduce_mean(
            drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), ys)))
        return m - 0.5 * g, g
    m, gs = jax.lax.scan(body, m, None, length=2)
    return m, gs

args = (jnp.float32(0.3), jnp.array([1.0, 2.0, 3.0]))
plan = drjax.build_plan(jax.make_jaxpr(two_rounds)(*args), 3)
compiled = plan.compile()
ref = drjax.run_plan(plan, *args)
out = compiled(*args)
for a, b in zip(out, ref):
    np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b), maxulp=1)
compiled(*args)
assert compiled.trace_count == 1, "compiled plan retraced on a repeat call"
print("compiled-vs-interpreted smoke check: OK")
PY

# Pipelined-round smoke check: the 1F1B fill/drain schedule (stage-kind
# placement, stage_map + stage_transfer under one scan) must build a plan
# whose compiled executor is BITWISE equal to run_plan with zero retraces
# (full coverage in tests/test_pipeline.py).
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro import core as drjax
from repro.algorithms import PipelineConfig, make_pipelined_round

fns = (lambda x: x * 2.0, lambda x: x + 1.0)
round_fn = make_pipelined_round(
    fns, PipelineConfig(num_stages=2, num_microbatches=4))
mbs = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
act0 = jnp.zeros((2, 8), jnp.float32)
plan = drjax.build_plan(
    jax.make_jaxpr(round_fn)(mbs, act0), round_fn.drjax_context,
    partitioned_invars=(0, 1))
compiled = plan.compile()
ref = drjax.run_plan(plan, mbs, act0)
out = compiled(mbs, act0)
assert all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(out, ref)), \
    "compiled pipelined round diverged from run_plan (bitwise)"
compiled(mbs, act0)
assert compiled.trace_count == 1, "pipelined round retraced on repeat call"
plan.analyze().raise_if_errors()
print("pipelined-round smoke check: OK")
PY

# Fused reduce+compress smoke check: the interpret-mode Pallas kernel must be
# BITWISE equal to its jnp oracle (fast; full coverage in test_fused_reduce).
python - <<'PY'
import jax, jax.numpy as jnp
from repro.kernels import reduce_compress as rc, ref

x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 256), jnp.float32)
q, s = rc.reduce_compress(x, interpret=True)
qr, sr = jax.jit(ref.reduce_compress_ref)(x)
assert bool(jnp.all(q == qr)) and bool(jnp.all(s == sr)), \
    "fused reduce_compress kernel diverged from its jnp oracle"
qs = jnp.stack([q, -q])                     # two pods, (P, R, C)
ss = jnp.concatenate([s, 2 * s])            # their (P, R) lane-dense scales
back = jax.jit(lambda q, s: rc.dequant_accumulate(q, s, interpret=True))(
    qs, ss)
br = jax.jit(ref.dequant_accumulate_ref)(qs, ss)
assert bool(jnp.all(back == br)), \
    "dequant_accumulate kernel diverged from its jnp oracle"
print("fused-vs-oracle smoke check: OK")
PY

# Serve-runtime smoke: continuous batching vs static waves on a reduced
# config — asserts flat trace counts after bucket warmup and token-identical
# outputs across schedulers (perf-ordering assertions are skipped in smoke
# mode; the full comparison runs via python -m benchmarks.serve).
python -m benchmarks.serve --smoke > /dev/null
echo "serve continuous-batching smoke check: OK"

# Chaos smoke soak: ~20 rounds with 1 injected device failure, 1 elastic
# event, straggler deadlines and a checkpoint fault — asserts the production
# invariants (bitwise oracle equality, zero client-leg retraces, masked tail
# < sync tail, fallback past the broken checkpoint). The full composed soak
# (2 failures, 4 elastic events, serve traffic) runs in tests/test_chaos.py
# (slow) and benchmarks.chaos.
python -m benchmarks.chaos --smoke > /dev/null
echo "chaos smoke soak: OK"

# Physical chaos smoke: the same soak on a REAL 8-device (pod, data) mesh —
# pod dropout rebuilds a degraded mesh from surviving devices, server state
# migrates onto it, and a mid-arrays.npz writer kill must be survived via
# fallback restore. --physical re-execs in a subprocess under
# XLA_FLAGS=--xla_force_host_platform_device_count=8, so this process keeps
# its single default device (same isolation rule as the conftest worker).
python -m benchmarks.chaos --smoke --physical --json > /dev/null
echo "physical chaos smoke soak: OK"

exec python -m pytest -q "$@"
