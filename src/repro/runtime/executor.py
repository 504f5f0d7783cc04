"""Compiled plan executor: AOT lowering of MapReduce plans to one executable.

``run_plan`` (the §5 correctness oracle) dispatches every stage eagerly from
Python — one device round-trip per eqn, control flow owned by the host. That
is the right *reference* semantics, but the paper's systems claim is that
DrJAX programs "translate directly to XLA HLO": the staged plan should lower
to ONE donation-aware executable, compiled once, with zero per-round Python
overhead and zero retraces across rounds.

This module provides that compiled path:

* :func:`compile_plan` / ``plan.compile(...)`` — lower an entire
  :class:`~repro.core.interpreter.MapReducePlan` (including
  ``LoopStage``/``CondStage`` sub-plans) into a single ``jax.jit``
  executable. Loop stages become ``lax.scan``/``lax.while_loop`` (carries
  live in-place inside the executable), cond stages become ``lax.switch``,
  and adjacent ``GROUP_COMPUTE``/``SERVER_COMPUTE`` stages are **fused**
  into one compute unit so no intermediate materializes at an interpreter
  stage boundary. Bitwise-equal to ``run_plan`` on CPU (asserted by
  ``tests/test_executor.py`` over every control-flow test program).

* an **executable cache** keyed by ``(plan fingerprint, mesh key, arg
  shapes/dtypes, donation, loop mode)``. Two structurally identical plans —
  e.g. the same program re-traced — share one executable: compiling the
  second is a cache hit and triggers **zero** new traces
  (:func:`plan_fingerprint` hashes the canonical jaxpr print, the stage
  skeleton and the captured const values).

* donation plumbing: ``compile_plan(plan, donate_argnums=...)`` donates the
  carried arguments (params / server state in a round plan). The donation
  rule for this repo: **any jitted hot loop donates its carried state**;
  inputs that are re-used across calls (model params at serve time, eval
  batches) are never donated.

* per-stage sharding constraints: with ``mesh=``/``placement_axes=``, the
  output of every BROADCAST/REDUCE stage is pinned to its placement-stack
  sharding (k leading group axes each on their own mesh axes, reduce
  results replicated at the server) exactly as the primitive impls do under
  an ambient context.

* :class:`ElasticHierarchicalRound` (per-placement-level cache split): the
  per-client leg of a pod-hierarchical round is compiled ONCE from the
  per-pod plan — whose shapes do not mention the pod count — and dispatched
  per pod; only the tiny cross-pod leg is keyed by the pod count. Elastic
  pod dropout therefore recompiles the cross-pod leg and **never** the
  per-client leg (closing the ROADMAP elastic-resharding item).

Fallback: ``run_plan`` remains the eager reference executor; anything the
compiled path cannot express should raise at compile time, never silently
diverge.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from repro.compat import jaxpr as jaxpr_compat
from repro.core import interpreter as interp
from repro.core import placement as placement_lib
from repro.core import sharding as sharding_lib

__all__ = [
    "CompiledPlan",
    "ElasticHierarchicalRound",
    "FusedCompute",
    "TraceCounter",
    "clear_executor_cache",
    "compile_plan",
    "fingerprint_components",
    "fingerprint_parts",
    "fuse_stages",
    "plan_fingerprint",
]


# ---------------------------------------------------------------------------
# trace counting
# ---------------------------------------------------------------------------


class TraceCounter:
    """Counts how many times JAX (re)traces a wrapped function.

    ``jit`` only calls the underlying Python callable when tracing, so a
    plain side-effecting counter measures exactly the retrace count — the
    no-retrace invariants in ``tests/test_executor.py`` are asserted with
    this.
    """

    def __init__(self):
        self.count = 0

    def wrap(self, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            self.count += 1
            return fn(*args, **kwargs)

        return wrapped


# ---------------------------------------------------------------------------
# plan fingerprinting
# ---------------------------------------------------------------------------


def fingerprint_parts(plan) -> List[Tuple[str, bytes]]:
    """The named byte components ``plan_fingerprint`` hashes, in hash order.

    Concatenating the byte values in order reproduces the exact stream
    ``plan_fingerprint`` feeds sha1 — the fingerprint is defined over this
    decomposition, so the two can never drift. The names exist for the
    analysis layer: ``repro.analysis.explain_fingerprint_mismatch`` compares
    plans part by part to say *which* component broke executable sharing.
    """
    parts: List[Tuple[str, bytes]] = [
        ("placements", str(plan.placements).encode()),
        ("placement_kinds", str(tuple(plan.placement_kinds)).encode()),
        (
            "partitioned_invars",
            str(tuple(int(d) for d in plan.partitioned_invars)).encode(),
        ),
        (
            "partitioned_outvars",
            str(tuple(int(d) for d in plan.partitioned_outvars)).encode(),
        ),
        # The jaxpr pretty-printer assigns var names deterministically, so
        # the string is canonical for structurally identical programs (and
        # covers every sub-jaxpr, so LoopStage/CondStage bodies included).
        ("jaxpr", str(plan.jaxpr.jaxpr).encode()),
        (
            "stage_skeleton",
            "|".join(
                name + ":" + s.kind for name, s, _ in plan.named_stages()
            ).encode(),
        ),
    ]
    idx = 0
    for p in interp._all_plans(plan):
        for atom, val in p.const_env().items():
            arr = np.asarray(val)
            parts.append((
                f"const[{idx}]",
                str(getattr(atom, "aval", None)).encode()
                + str((arr.shape, str(arr.dtype))).encode()
                + arr.tobytes(),
            ))
            idx += 1
    return parts


def fingerprint_components(plan) -> List[Tuple[str, str]]:
    """Per-component sha1 hexdigests of :func:`fingerprint_parts`.

    Cheap to diff between two plans; used by the retrace-hazard analysis to
    explain fingerprint mismatches without shipping raw const bytes around.
    """
    return [
        (name, hashlib.sha1(data).hexdigest())
        for name, data in fingerprint_parts(plan)
    ]


def plan_fingerprint(plan) -> str:
    """Structural hash of a plan: canonical jaxpr print + placements + stage
    skeleton + captured const values.

    Two plans built from separate traces of the same program (same shapes)
    produce the same fingerprint — the executable cache uses this to share
    one compiled artifact across re-plans.
    """
    h = hashlib.sha1()
    for _name, data in fingerprint_parts(plan):
        h.update(data)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# stage fusion
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FusedCompute:
    """A maximal run of adjacent LocalCompute stages, fused into one unit.

    ``run_plan`` treats GROUP_COMPUTE → SERVER_COMPUTE adjacency as two
    dispatch units with a materialized boundary; inside one executable there
    is no placement barrier between purely local stages, so the compiled
    path evaluates the whole run as a single fused unit and lets XLA fuse
    across the former boundary.
    """

    eqns: List[Any]
    kinds: Tuple[str, ...]

    @property
    def kind(self) -> str:
        return "FUSED_COMPUTE"


def fuse_stages(stages: Sequence[Any]) -> List[Any]:
    """Merge adjacent LocalCompute stages (any placement) into FusedCompute."""
    out: List[Any] = []
    for s in stages:
        if isinstance(s, interp.LocalCompute):
            if out and isinstance(out[-1], FusedCompute):
                out[-1].eqns.extend(s.eqns)
                out[-1].kinds = out[-1].kinds + (s.kind,)
            else:
                out.append(FusedCompute(eqns=list(s.eqns), kinds=(s.kind,)))
        else:
            out.append(s)
    return out


# ---------------------------------------------------------------------------
# traceable plan evaluation
# ---------------------------------------------------------------------------


_UNROLL_LIMIT = 32


def _is_literal(a) -> bool:
    return isinstance(a, jex_core.Literal)


_is_dropvar = jaxpr_compat.is_dropvar


def _plan_consts(plan) -> Dict[Any, Any]:
    """Const env for a plan, hoisted once per compile (not per call/round):
    the values are closed over by the traced function and baked into the
    executable as constants instead of being re-bound every dispatch."""
    return plan.const_env()


class _PlanTracer:
    """Executes a plan with traceable control flow (jit-able end to end)."""

    def __init__(self, *, loops: str, constrain: Optional[Callable]):
        if loops not in ("native", "unroll", "auto"):
            raise ValueError(f"loops must be native|unroll|auto, got {loops!r}")
        self.loops = loops
        self.constrain = constrain
        self._consts: Dict[int, Dict[Any, Any]] = {}

    def consts_for(self, plan) -> Dict[Any, Any]:
        key = id(plan)
        if key not in self._consts:
            self._consts[key] = _plan_consts(plan)
        return self._consts[key]

    # -- main entry ----------------------------------------------------------

    def run(self, plan, args: Sequence[Any]) -> List[Any]:
        jaxpr = plan.jaxpr.jaxpr
        env: Dict[Any, Any] = dict(self.consts_for(plan))

        def read(a):
            if _is_literal(a):
                return a.val
            return env[a]

        def write(v, val):
            if not _is_dropvar(v):
                env[v] = val

        if len(args) != len(jaxpr.invars):
            raise TypeError(
                f"plan expects {len(jaxpr.invars)} flat args, got {len(args)}"
            )
        for v, val in zip(jaxpr.invars, args):
            write(v, val)

        for stage in fuse_stages(plan.stages):
            if isinstance(stage, FusedCompute):
                for eqn in stage.eqns:
                    for o, val in zip(eqn.outvars, interp._eval_eqn(eqn, read)):
                        write(o, val)
            elif isinstance(
                stage, (interp.Broadcast, interp.Reduce, interp.Transfer)
            ):
                eqn = stage.eqn
                vals = interp._eval_eqn(eqn, read)
                if self.constrain is not None:
                    names, i = interp._eqn_placement(eqn)
                    # Broadcast lands one level deeper (depth i+1), Reduce
                    # one level up (depth i); Transfer stays at the stage
                    # level's own depth i+1 — that constraint is what pins
                    # the stage axis so the shift lowers to neighbor
                    # collective-permute traffic.
                    depth = (
                        i if isinstance(stage, interp.Reduce) else i + 1
                    )
                    vals = [self.constrain(v, depth) for v in vals]
                for o, val in zip(eqn.outvars, vals):
                    write(o, val)
            elif isinstance(stage, interp.LoopStage):
                self._run_loop(stage, read, write)
            elif isinstance(stage, interp.CondStage):
                self._run_cond(stage, read, write)
            else:  # pragma: no cover - future stage kinds
                raise TypeError(f"unknown stage kind: {stage!r}")

        return [read(a) for a in plan.out_atoms]

    # -- control flow --------------------------------------------------------

    def _run_loop(self, stage, read, write):
        if stage.loop_kind == "scan":
            self._run_scan(stage, read, write)
        else:
            self._run_while(stage, read, write)

    def _run_scan(self, stage, read, write):
        eqn = stage.eqn
        params = eqn.params
        nc, ncar, length = params["num_consts"], params["num_carry"], params["length"]
        reverse = params.get("reverse", False)
        invals = [read(a) for a in eqn.invars]
        consts = invals[:nc]
        carry0 = invals[nc : nc + ncar]
        xs = invals[nc + ncar :]
        num_ys = len(eqn.outvars) - ncar
        unroll = self.loops == "unroll" or (
            self.loops == "auto" and length <= _UNROLL_LIMIT
        )
        if unroll:
            carry = list(carry0)
            ys: List[Tuple[Any, ...]] = []
            indices = range(length - 1, -1, -1) if reverse else range(length)
            for i in indices:
                xi = [x[i] for x in xs]
                outs = self.run(stage.body_plan, consts + carry + xi)
                carry = list(outs[:ncar])
                ys.append(tuple(outs[ncar:]))
            if reverse:
                ys.reverse()
            if length == 0:
                stacked = [
                    jnp.zeros(v.aval.shape, v.aval.dtype)
                    for v in eqn.outvars[ncar:]
                ]
            else:
                stacked = [
                    jnp.stack([ys[t][j] for t in range(length)])
                    for j in range(num_ys)
                ]
            for o, val in zip(eqn.outvars, carry + stacked):
                write(o, val)
            return

        def body(carry, x):
            xi = list(x) if x is not None else []
            outs = self.run(stage.body_plan, list(consts) + list(carry) + xi)
            return tuple(outs[:ncar]), tuple(outs[ncar:])

        carry, ys = jax.lax.scan(
            body,
            tuple(carry0),
            tuple(xs) if xs else None,
            length=length,
            reverse=reverse,
        )
        for o, val in zip(eqn.outvars, list(carry) + list(ys)):
            write(o, val)

    def _run_while(self, stage, read, write):
        eqn = stage.eqn
        params = eqn.params
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        invals = [read(a) for a in eqn.invars]
        cond_consts = invals[:cn]
        body_consts = invals[cn : cn + bn]
        carry0 = invals[cn + bn :]

        def cond_f(carry):
            if stage.cond_plan is not None:
                pred = self.run(stage.cond_plan, list(cond_consts) + list(carry))[0]
            else:
                cond_jaxpr = params["cond_jaxpr"]
                pred = jaxpr_compat.eval_jaxpr(
                    cond_jaxpr.jaxpr, cond_jaxpr.consts, *cond_consts, *carry
                )[0]
            return jnp.reshape(pred, ())

        def body_f(carry):
            return tuple(self.run(stage.body_plan, list(body_consts) + list(carry)))

        carry = jax.lax.while_loop(cond_f, body_f, tuple(carry0))
        for o, val in zip(eqn.outvars, carry):
            write(o, val)

    def _run_cond(self, stage, read, write):
        eqn = stage.eqn
        n = len(stage.branch_plans)
        idx = jnp.clip(jnp.asarray(read(eqn.invars[0])).astype(jnp.int32), 0, n - 1)
        ops = [read(a) for a in eqn.invars[1:]]

        def make_branch(bp):
            def branch(*operands):
                return tuple(self.run(bp, list(operands)))

            return branch

        outs = jax.lax.switch(idx, [make_branch(bp) for bp in stage.branch_plans], *ops)
        for o, val in zip(eqn.outvars, outs):
            write(o, val)


# ---------------------------------------------------------------------------
# sharding constraints from the placement stack
# ---------------------------------------------------------------------------


def _make_constrainer(plan, mesh, placement_axes):
    """A ``(value, depth) -> value`` sharding pin for stage boundaries.

    Builds a placement context over ``plan.placements`` with each level's
    mesh axes from ``placement_axes`` (name -> axis name(s)), then reuses
    the core sharding helpers: depth-k values pin their k leading group
    axes, depth-0 (server) values pin full replication.
    """
    if mesh is None:
        return None
    placement_axes = placement_axes or {}
    ctx = placement_lib.PlacementContext(
        placements=tuple(
            placement_lib.Placement(n, s, placement_axes.get(n), kind=k)
            for (n, s), k in zip(plan.placements, plan.placement_kinds)
        ),
        mesh=mesh,
    )

    def constrain(val, depth: int):
        if not hasattr(val, "ndim") or val.ndim == 0:
            return val
        if depth <= 0:
            return sharding_lib.constrain_replicated(val, ctx)
        return sharding_lib.constrain_partitioned(val, ctx, depth=depth)

    return constrain


# ---------------------------------------------------------------------------
# executable cache + CompiledPlan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _CacheEntry:
    fn: Callable  # the jitted executable
    counter: TraceCounter


_EXEC_CACHE: Dict[Any, _CacheEntry] = {}


def clear_executor_cache() -> None:
    _EXEC_CACHE.clear()


def executor_cache_size() -> int:
    return len(_EXEC_CACHE)


def _aval_key(args) -> Tuple:
    out = []
    for a in args:
        aval = jaxpr_compat.get_aval(a)
        out.append(
            (tuple(aval.shape), str(aval.dtype), bool(getattr(aval, "weak_type", False)))
        )
    return tuple(out)


def _mesh_key(mesh, placement_axes) -> Tuple:
    if mesh is None:
        return (None, None, None)
    return (
        tuple(zip(mesh.axis_names, mesh.devices.shape)),
        # Device IDENTITY matters: the same (axes, shape) remapped onto
        # different devices (elastic re-mapping around a failed pod) must
        # not share an executable whose constraints pin the old devices.
        tuple(d.id for d in mesh.devices.flat),
        tuple(sorted((placement_axes or {}).items())),
    )


class CompiledPlan:
    """A plan lowered to one donation-aware executable (lazily, per shapes).

    Calling it with concrete arrays looks up the executable cache under
    ``(fingerprint, mesh key, arg avals, donation, loop mode)`` and jits the
    traceable plan evaluation on a miss. ``trace_count`` exposes how many
    times the active executable has been traced (1 after warmup; 0 retraces
    across rounds is the hot-loop invariant).
    """

    def __init__(
        self,
        plan,
        *,
        mesh=None,
        placement_axes: Optional[Dict[str, Any]] = None,
        donate_argnums: Tuple[int, ...] = (),
        loops: str = "native",
    ):
        self.plan = plan
        self.mesh = mesh
        self.placement_axes = placement_axes
        self.donate_argnums = tuple(donate_argnums)
        self.loops = loops
        self.fingerprint = plan_fingerprint(plan)
        self._entry: Optional[_CacheEntry] = None

    def _entry_for(self, args) -> _CacheEntry:
        key = (
            self.fingerprint,
            _mesh_key(self.mesh, self.placement_axes),
            self.donate_argnums,
            self.loops,
            _aval_key(args),
        )
        entry = _EXEC_CACHE.get(key)
        if entry is None:
            tracer = _PlanTracer(
                loops=self.loops,
                constrain=_make_constrainer(
                    self.plan, self.mesh, self.placement_axes
                ),
            )
            plan = self.plan

            def fn(*flat_args):
                return tuple(tracer.run(plan, list(flat_args)))

            counter = TraceCounter()
            entry = _CacheEntry(
                fn=jax.jit(counter.wrap(fn), donate_argnums=self.donate_argnums),
                counter=counter,
            )
            _EXEC_CACHE[key] = entry
        self._entry = entry
        return entry

    def __call__(self, *args):
        return self._entry_for(args).fn(*args)

    def lower(self, *args):
        """AOT: ``compiled.lower(*specs).compile()`` (jax.stages passthrough)."""
        return self._entry_for(args).fn.lower(*args)

    @property
    def trace_count(self) -> int:
        return self._entry.counter.count if self._entry is not None else 0

    @property
    def num_stage_units(self) -> int:
        """Dispatch units after fusing adjacent local stages."""
        return len(fuse_stages(self.plan.stages))

    def donation_report(self):
        """Static donation/aliasing analysis for this plan's argnums.

        Answers, without compiling: which donated inputs alias an output,
        which donations are dropped (and why), and whether any stage reads
        a donated buffer after its alias target is produced. Returns a
        :class:`repro.analysis.AnalysisReport`.
        """
        from repro import analysis  # lazy: executor must not require analysis

        return analysis.donation_report(self)


def compile_plan(
    plan,
    *,
    mesh=None,
    placement_axes: Optional[Dict[str, Any]] = None,
    donate_argnums: Sequence[int] = (),
    loops: str = "native",
) -> CompiledPlan:
    """Lower a MapReducePlan into one donation-aware jitted executable.

    ``loops``: ``"native"`` (default — loop stages become ``lax.scan`` /
    ``lax.while_loop``, so carries update in place inside the executable),
    ``"unroll"`` (static-trip scans replayed iteration by iteration at trace
    time, exactly mirroring ``run_plan``'s op sequence), or ``"auto"``
    (unroll short scans, native otherwise). All modes are bitwise-equal to
    ``run_plan`` on CPU for the shipped programs.

    ``donate_argnums`` donates the given flat args (use for carried state:
    params / server state / pending deltas). ``mesh`` + ``placement_axes``
    ({placement name -> mesh axis}) install per-stage sharding constraints
    from the placement stack.
    """
    return CompiledPlan(
        plan,
        mesh=mesh,
        placement_axes=placement_axes,
        donate_argnums=tuple(donate_argnums),
        loops=loops,
    )


# ---------------------------------------------------------------------------
# elastic two-leg executor (per-placement-level cache split)
# ---------------------------------------------------------------------------


class ElasticHierarchicalRound:
    """Pod-hierarchical round compiled per placement LEVEL, elastically.

    The executable cache is split at the outermost placement boundary:

    * the **per-client leg** (broadcast -> client updates -> intra-pod
      ``reduce_mean@clients``) is compiled ONCE from the per-pod plan — its
      shapes never mention the pod count — and dispatched once per pod, the
      way a real two-fabric runtime ships one program to every pod;
    * the **cross-pod leg** (mean of the pod partials + server update) is a
      small executable keyed by the pod count.

    When a pod drops out mid-training the pod axis shrinks: the next
    :meth:`step` reuses the cached per-client executable unchanged (zero new
    traces — asserted in ``tests/test_executor.py``) and recompiles only the
    cross-pod leg.

    ``step(..., mesh=...)`` makes the split PHYSICAL: pass the current
    ``(pod, data)`` mesh (a degraded one after a dropout —
    ``repro.runtime.elastic.mesh_for_surviving_pods``) and

    * the server-side state (params + server state) is ``device_put``
      onto the mesh replicated — on a mesh CHANGE this is the elastic
      migration, counted in ``reshard_count`` / timed in
      ``mesh_migrate_ms``;
    * the stacked pod partials are ``device_put`` sharded over the mesh's
      outermost (pod) axis before the cross-pod executable consumes them —
      the simulated DCN hop;
    * the cross-pod executable cache is keyed by ``(avals, mesh key)``
      (device identity included), so each distinct surviving-pod mesh gets
      exactly one executable (``meshes_seen`` counts them);
    * the per-client leg stays pinned to ONE stable device for the whole
      run: its executable was traced once with single-device inputs, and a
      mesh-committed input would change the jit cache key and retrace it.
      Physically this models the per-pod program being dispatched to each
      pod's local slice unchanged — only the cross-pod reduction re-maps
      when the mesh shrinks.

    ``client_fn(params, pod_data) -> pod partials`` must be a flat DrJAX
    program over ``clients_per_pod`` groups (``@drjax.program(partition_size
    =clients_per_pod)``); ``cross_fn(params, server_state, *stacked
    partials) -> outputs`` is plain JAX over the ``(num_pods, ...)`` stacks.
    """

    def __init__(
        self,
        client_fn: Callable,
        cross_fn: Callable,
        *,
        clients_per_pod: int,
        loops: str = "native",
        donate_cross: bool = False,
    ):
        self.client_fn = client_fn
        self.cross_fn = cross_fn
        self.clients_per_pod = clients_per_pod
        self.loops = loops
        self.donate_cross = donate_cross
        self._client: Optional[CompiledPlan] = None
        self._client_out_tree = None
        self._cross_cache: Dict[Any, _CacheEntry] = {}
        # physical-mesh state (step(..., mesh=...))
        self._client_device = None  # stable home of the per-client leg
        self._active_mesh = None
        self._active_mesh_key = None
        self._mesh_keys_seen: set = set()
        self.reshard_count = 0
        self.mesh_migrate_ms = 0.0

    # -- per-client leg ------------------------------------------------------

    def _ensure_client(self, params, pod_slice):
        if self._client is not None:
            return
        from repro.core import build_plan  # local: keep module import light

        closed = jax.make_jaxpr(self.client_fn)(params, pod_slice)
        plan = build_plan(closed, self.clients_per_pod)
        self._client = compile_plan(plan, loops=self.loops)
        self._client_out_tree = jax.tree_util.tree_structure(
            jax.eval_shape(self.client_fn, params, pod_slice)
        )

    def _client_leg(self, params, pod_slice):
        self._ensure_client(params, pod_slice)
        flat = jax.tree_util.tree_leaves((params, pod_slice))
        outs = self._client(*flat)
        return jax.tree_util.tree_unflatten(self._client_out_tree, list(outs))

    # -- cross-pod leg -------------------------------------------------------

    def _cross_leg(self, params, server_state, partials):
        flat_key = (
            _aval_key(jax.tree_util.tree_leaves((params, server_state, partials))),
            self._active_mesh_key,
        )
        entry = self._cross_cache.get(flat_key)
        if entry is None:
            counter = TraceCounter()
            entry = _CacheEntry(
                fn=jax.jit(
                    counter.wrap(self.cross_fn),
                    donate_argnums=(0, 1) if self.donate_cross else (),
                ),
                counter=counter,
            )
            self._cross_cache[flat_key] = entry
        return entry.fn(params, server_state, partials)

    # -- physical mesh adoption ---------------------------------------------

    def _adopt_mesh(self, mesh, params, server_state):
        """Install ``mesh`` as the cross-pod leg's mesh; migrate state onto it.

        Every physical step replicates the server-side state onto the active
        mesh with ``device_put`` (a no-op view when it already lives there —
        this is also what re-commits numpy state after a checkpoint restore
        without splitting the executable cache). A transition between two
        live meshes is a RESHARD — the pod-dropout/regrowth re-mapping — and
        its state-migration wall time accumulates in ``mesh_migrate_ms``.
        """
        from repro.compat import shardings as _shardings

        key = _mesh_key(mesh, None)
        changed = key != self._active_mesh_key
        t0 = time.perf_counter() if changed else 0.0
        rep = _shardings.replicated_sharding(mesh)
        params = jax.device_put(params, rep)
        server_state = jax.device_put(server_state, rep)
        if changed:
            jax.block_until_ready((params, server_state))
            self.mesh_migrate_ms += (time.perf_counter() - t0) * 1e3
            if self._active_mesh_key is not None:
                self.reshard_count += 1
            self._active_mesh = mesh
            self._active_mesh_key = key
            self._mesh_keys_seen.add(key)
        return params, server_state

    # -- driver --------------------------------------------------------------

    def step(self, params, server_state, round_data, *, mesh=None):
        """One round: ``round_data`` leaves lead with (num_pods,
        clients_per_pod, ...); the pod count may change between calls.

        With ``mesh`` (the physical path) the mesh may also change between
        calls — the state migrates and only the cross-pod leg re-keys; see
        the class docstring for the invariants.
        """
        leaves = jax.tree_util.tree_leaves(round_data)
        if not leaves:
            raise ValueError("round_data must have at least one leaf")
        num_pods = leaves[0].shape[0]
        if mesh is not None:
            params, server_state = self._adopt_mesh(mesh, params, server_state)
            if self._client_device is None:
                self._client_device = jax.devices()[0]
            client_params = jax.device_put(params, self._client_device)
        else:
            client_params = params
        pod_outs = [
            self._client_leg(
                client_params,
                jax.tree_util.tree_map(lambda x: x[p], round_data),
            )
            for p in range(num_pods)
        ]
        partials = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *pod_outs
        )
        if mesh is not None:
            from repro.compat import shardings as _shardings

            # Ship the pod partials onto the mesh's outermost (pod) axis —
            # the cross-DCN hop — so the cross-pod executable consumes them
            # sharded one row per surviving pod.
            pod_sharding = _shardings.named_sharding(
                mesh, (mesh.axis_names[0],)
            )
            partials = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, pod_sharding)
                if getattr(x, "ndim", 0) >= 1 and x.shape[0] == num_pods
                else x,
                partials,
            )
        return self._cross_leg(params, server_state, partials)

    # -- introspection (tested invariants) -----------------------------------

    @property
    def client_trace_count(self) -> int:
        return self._client.trace_count if self._client is not None else 0

    @property
    def cross_compile_count(self) -> int:
        return len(self._cross_cache)

    @property
    def meshes_seen(self) -> int:
        """Distinct physical meshes adopted so far (0 in logical mode)."""
        return len(self._mesh_keys_seen)
