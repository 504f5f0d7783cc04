"""Quickstart: the paper's authoring surface in 60 lines.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro import core as drjax

# --- Snippet 2: broadcast, map, reduce ------------------------------------


@drjax.program(partition_size=3)
def broadcast_double_and_sum(x):
    y = drjax.broadcast(x)
    z = drjax.map_fn(lambda a: 2 * a, y)
    return drjax.reduce_sum(z)


print("snippet 2:", broadcast_double_and_sum(jnp.float32(1.0)), "(expect 6)")


# --- Snippets 3-6: parallel MAML + MapReduce AD ----------------------------


def loss(x, y):
    return (x - y) ** 2


def maml_loss(model, lr, task):
    g = jax.grad(loss)(model, task)
    return loss(model - lr * g, task)


@drjax.program(partition_size=3)
def parallel_maml_loss(model, lr, tasks):
    model_b = drjax.broadcast(model)
    lr_b = drjax.broadcast(lr)
    losses = drjax.map_fn(maml_loss, (model_b, lr_b, tasks))
    return drjax.reduce_mean(losses)


args = (jnp.float32(0.0), jnp.float32(0.1), jnp.array([1.0, 2.0, 3.0]))
print("maml loss:", parallel_maml_loss(*args))
print("maml grad:", jax.grad(parallel_maml_loss)(*args),
      "(a DrJAX program too — MapReduce AD)")

# the jaxpr preserves the primitives (paper Snippet 5)
jxp = jax.make_jaxpr(parallel_maml_loss)(*args)
print("\njaxpr:\n", jxp)

# --- §5: interpret out to other platforms ----------------------------------

plan = drjax.build_plan(jxp, 3)
print("\nfederated plan:\n" + plan.to_text())
print("\nbeam pipeline:\n" + plan.to_beam())

outs = drjax.run_plan(plan, *args)
print("\nplan executor result:", outs[0], "== direct:", parallel_maml_loss(*args))

# --- §5 continued: control flow is transparent to the interpreter ----------

# A jitted program yields the SAME plan as the unjitted one: the interpreter
# inlines the pjit sub-jaxpr instead of seeing one opaque eqn.
jit_plan = drjax.build_plan(jax.make_jaxpr(jax.jit(parallel_maml_loss))(*args), 3)
print("\njit(f) plan stage kinds:",
      [s.kind for s in jit_plan.stages],
      "== unjitted:", [s.kind for s in plan.stages])

# A multi-round training loop (lax.scan whose body communicates) becomes one
# LOOP stage holding a sub-plan: per-round communication is explicit.


@drjax.program(partition_size=3)
def two_round_sgd(model, tasks):
    def body(m, _):
        grads = drjax.map_fn(lambda mm, t: 2.0 * (mm - t),
                             (drjax.broadcast(m), tasks))
        g = drjax.reduce_mean(grads)
        return m - 0.1 * g, g

    m, gs = jax.lax.scan(body, model, None, length=2)
    return m, gs


loop_args = (jnp.float32(0.0), jnp.array([1.0, 2.0, 3.0]))
loop_plan = drjax.build_plan(jax.make_jaxpr(two_round_sgd)(*loop_args), 3)
print("\nmulti-round plan (note the LOOP stage):\n" + loop_plan.to_text())
print("\nmulti-round beam pipeline:\n" + loop_plan.to_beam())

loop_outs = drjax.run_plan(loop_plan, *loop_args)
print("\nloop plan executor:", loop_outs[0],
      "== direct:", two_round_sgd(*loop_args)[0])

# --- nested placements: hierarchical MapReduce -----------------------------

# A placement STACK models clients inside pods (paper §6). Values partitioned
# at both levels carry two leading group axes; broadcast/reduce address one
# level with placement=..., and the default spans the whole stack.


@drjax.program(placements={"pods": 2, "clients": 4})
def pod_hierarchical_round(model, tasks):
    model_b = drjax.broadcast(model)                   # server -> (2, 4)
    grads = drjax.map_fn(lambda m, t: 2.0 * (m - t), (model_b, tasks))
    pod_partials = drjax.reduce_mean(grads, placement="clients")  # fast ICI leg
    return drjax.reduce_mean(pod_partials, placement="pods")      # slow DCN leg


tasks = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
hier_args = (jnp.float32(0.5), tasks)
print("\nhierarchical round:", pod_hierarchical_round(*hier_args))
print("hierarchical grad:", jax.grad(pod_hierarchical_round)(*hier_args),
      "(MapReduce AD is placement-correct)")

# The §5 interpreter stages the two legs as placement-tagged shuffles.
hier_plan = drjax.build_plan(
    jax.make_jaxpr(pod_hierarchical_round)(*hier_args),
    {"pods": 2, "clients": 4},
)
print("\nhierarchical plan (note REDUCE@clients then REDUCE@pods):\n"
      + hier_plan.to_text())
hier_outs = drjax.run_plan(hier_plan, *hier_args)
print("\nhierarchical plan executor:", hier_outs[0],
      "== direct:", pod_hierarchical_round(*hier_args))

# --- compiled plan executor: the whole plan as ONE executable ---------------

# run_plan dispatches each stage eagerly from Python (the reference
# semantics). plan.compile() lowers the ENTIRE plan — loop stages become
# lax.scan/while_loop, adjacent local stages fuse — into one donation-aware
# jitted executable, cached by (plan fingerprint, mesh, arg shapes): calling
# it across rounds triggers exactly one trace, and re-building the same plan
# re-uses the cached executable.

compiled_hier = hier_plan.compile()
print("\ncompiled hierarchical round:", compiled_hier(*hier_args)[0],
      "== run_plan:", hier_outs[0], "(bitwise on CPU)")
compiled_hier(*hier_args)
print("traces after 2 calls:", compiled_hier.trace_count,
      "(one executable, zero retraces across rounds)")

compiled_loop = loop_plan.compile()  # the LOOP-stage trainer from above
print("compiled multi-round trainer:", compiled_loop(*loop_args)[0],
      "== run_plan:", loop_outs[0],
      f"({compiled_loop.num_stage_units} fused stage units,"
      f" scan carry donated in-executable)")

# --- compressed hierarchical reduce: the fused fast path ---------------------

# The per-pod partials are the bytes that cross the slow DCN leg; quantizing
# them to int8 cuts that traffic ~4x. When the compressor is recognized
# (compression.int8_roundtrip carries the drjax_fused_compress tag),
# hierarchical_reduce_mean packs the tree into one (groups..., R, 256) buffer
# per dtype and binds a compress-tagged reduce_mean@clients whose execution
# is a SINGLE pass over the deltas (Pallas reduce+compress kernel on TPU, a
# fused jnp oracle elsewhere). The program still stages as two placement-
# tagged REDUCEs, and grad is identical to the unfused composition — the
# roundtrip is straight-through under MapReduce AD.

from repro.compression import int8_roundtrip


@drjax.program(placements={"pods": 2, "clients": 4})
def compressed_hier_mean(tree):
    return drjax.hierarchical_reduce_mean(tree, compress_fn=int8_roundtrip)


@drjax.program(placements={"pods": 2, "clients": 4})
def reference_hier_mean(tree):
    # use_fused=False forces the generic reduce -> quantize -> dequantize
    # composition.
    return drjax.hierarchical_reduce_mean(
        tree, compress_fn=int8_roundtrip, use_fused=False
    )


deltas = {"w": jnp.linspace(-1.0, 1.0, 2 * 4 * 6).reshape(2, 4, 6)}
fused_out = compressed_hier_mean(deltas)
ref_out = reference_hier_mean(deltas)
print("\nfused compressed mean:", fused_out["w"],
      "\nreference composition:", ref_out["w"])

g_fused = jax.grad(lambda t: compressed_hier_mean(t)["w"].sum())(deltas)
g_ref = jax.grad(lambda t: reference_hier_mean(t)["w"].sum())(deltas)
print("grad fused == unfused:",
      bool(jnp.all(g_fused["w"] == g_ref["w"])),
      "(straight-through roundtrip)")

fused_plan = drjax.build_plan(
    jax.make_jaxpr(compressed_hier_mean)(deltas), {"pods": 2, "clients": 4}
)
print("\nfused plan (still REDUCE@clients -> REDUCE@pods):\n"
      + fused_plan.to_text())

# --- static analysis: verify the plan WITHOUT running it --------------------

# plan.analyze() runs every static pass: placement safety (the full-depth
# generalization of check_locality — comm-free local stages even inside
# cond branches and while predicates, broadcast/reduce pairing), donation/
# aliasing (use-after-donate, why a donation would be dropped), retrace
# hazards (a scalar folded into the captured consts defeats the executable
# cache), and a per-stage communication-cost model read off the IR.

report = hier_plan.analyze(donate_argnums=(0,))
report.raise_if_errors()  # the oracle-suite gate: no errors, statically
print("\nstatic analysis of the hierarchical round:", report)

# The comm-cost pass splits the wire bytes by fabric: the clients-level
# shuffle rides fast intra-pod ICI, only the pods-level leg crosses the
# slow DCN — and a compress-tagged reduce is costed in its actual packed
# int8+per-256-block-scales wire format, not naive f32/4.
cost = fused_plan.comm_cost()
print("fused plan comm cost: dcn_bytes=%.0f ici_bytes=%.0f" % (
    cost.dcn_bytes, cost.ici_bytes))
for c in cost.per_stage:
    print(f"  {c.stage}: {c.op}@{c.placement} over {c.link}, "
          f"{c.wire_format}, {c.wire_bytes:.0f} B")

# --- N-level stacks: three replica levels ------------------------------------

# Placement stacks are no longer capped at two levels. A 3-level
# (superpods, pods, clients) stack factorizes onto a
# ("superpod", "pod", "data") mesh — `mesh_for_placements` accepts any
# ordered stack and `placement_axes_for` names each level's mesh axis.
# Reductions chain innermost-out, one fabric leg per level.


@drjax.program(placements={"superpods": 2, "pods": 2, "clients": 2})
def three_level_round(model, tasks):
    grads = drjax.map_fn(lambda m, t: 2.0 * (m - t),
                         (drjax.broadcast(model), tasks))
    p1 = drjax.reduce_mean(grads, placement="clients")    # intra-pod ICI
    p2 = drjax.reduce_mean(p1, placement="pods")          # intra-superpod
    return drjax.reduce_mean(p2, placement="superpods")   # cross-superpod DCN


tasks3 = jnp.arange(8, dtype=jnp.float32).reshape(2, 2, 2)
print("\n3-level round:", three_level_round(jnp.float32(0.5), tasks3))

# --- pipeline-stage placements: a 1F1B microbatch round ----------------------

# A placement can carry kind="stages" instead of the default "replicas":
# groups are pipeline stages, not data replicas. Broadcast/reduce are
# rejected at a stage level; per-stage compute is `stage_map` (one fn per
# stage) and stage-to-stage movement is `stage_transfer` (a shift along the
# stage axis — its transpose is the backward pipeline, free from AD).
# `make_pipelined_round` packages the fill/drain (1F1B) schedule: S stages
# and M microbatches run in M + S - 1 ticks under one lax.scan.

from repro.algorithms import (
    PipelineConfig, make_pipelined_round, pipeline_bubble_fraction,
)

S, M, D = 2, 4, 8
stage_fns = tuple((lambda s: (lambda x: x * (s + 1.0)))(s) for s in range(S))
round_fn = make_pipelined_round(
    stage_fns, PipelineConfig(num_stages=S, num_microbatches=M))

mbs = jnp.arange(M * D, dtype=jnp.float32).reshape(M, D)
act0 = jnp.zeros((S, D), jnp.float32)
outs, _ = round_fn(mbs, act0)
print("\npipelined round outs[0]:", outs[0],
      "(== stage chain applied to microbatch 0)")
print("bubble fraction (S-1)/(M+S-1):", pipeline_bubble_fraction(S, M))

# The interpreter stages the schedule as one LOOP whose body carries a
# TRANSFER eqn; plan.compile() lowers it to a single donation-aware
# executable, still bitwise-equal to the eager run_plan oracle.
pipe_plan = drjax.build_plan(
    jax.make_jaxpr(round_fn)(mbs, act0),
    round_fn.drjax_context,
    partitioned_invars=(0, 1),  # M may equal S; skip the shape heuristic
)
print("\npipelined plan (note the [stages] level and TRANSFER):\n"
      + pipe_plan.to_text())
compiled_pipe = pipe_plan.compile()
print("compiled pipeline:", compiled_pipe(mbs, act0)[0][0],
      "== run_plan:", drjax.run_plan(pipe_plan, mbs, act0)[0][0])
