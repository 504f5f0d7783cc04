"""Control-flow-aware jaxpr interpreter: DrJAX programs → MapReduce plans.

Paper §5: because the building blocks are *primitives*, they survive into the
jaxpr. A custom interpreter can therefore recover the communication structure
of the program — which values are partitioned, where broadcasts and reductions
happen — and translate it to other platforms (Apache Beam, federated-learning
systems) where "all cross-machine communication is explicit, and the
processing in-between communication is entirely local".

Real DrJAX programs hide structure inside higher-order primitives: users wrap
programs in ``jit`` (one opaque ``pjit`` eqn), training loops live in
``lax.scan``, and branching in ``lax.cond``. This interpreter therefore walks
*into* control flow:

* call-like eqns (``pjit``, ``closed_call``, ``remat``, ``custom_jvp_call``,
  …) whose sub-jaxpr contains DrJAX communication are **inlined** via variable
  substitution — a jitted DrJAX program yields the same plan as the unjitted
  one;
* a ``scan``/``while`` whose body communicates becomes a :class:`LoopStage`
  holding a sub-plan and a trip count, so per-round communication is explicit
  in ``to_text()``/``to_beam()``;
* a ``cond`` whose branches communicate becomes a :class:`CondStage` with one
  sub-plan per branch;
* control flow with *no* communication inside stays an opaque local eqn (it is
  purely local compute, exactly what a Map worker would run).

Placement is tracked on a **placement lattice**: every value carries the
stack prefix of named placements whose group axes lead it (``()`` = server,
``("pods",)`` = pod-partitioned, ``("pods", "clients")`` = fully
partitioned). DrJAX eqns *move* values on the lattice — the addressed
placement travels in the primitive params, so ``REDUCE@clients`` and
``REDUCE@pods`` are distinct, placement-tagged stages and a hierarchical
reduction visibly stages as two shuffles. Local eqns join their inputs'
placements (longest prefix wins); loop carries are solved to a fixed point
over the lattice (a carry that *climbs* the lattice after one iteration
keeps the joined placement for the whole loop).

This module provides:

* :func:`build_plan` — segment a ``ClosedJaxpr`` into an ordered list of
  stages: ``ServerCompute``/``GroupCompute`` (:class:`LocalCompute`),
  :class:`Broadcast`, :class:`Reduce`, :class:`LoopStage`, :class:`CondStage`.
* emitters — ``plan.to_text()`` (federated-system style, recursive) and
  ``plan.to_beam()`` (an Apache Beam pipeline whose every referenced name is
  defined and whose local stages call the *real* callables from
  ``plan.stage_fns()``).
* :func:`run_plan` — a reference *plan executor* that runs the staged control
  flow (loop sub-plans iterated, cond branches selected). Equality with
  direct execution is the correctness test for the translation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from repro.compat import jaxpr as jaxpr_compat

_COMM = {
    "drjax_broadcast": "broadcast",
    "drjax_reduce_sum": "reduce_sum",
    "drjax_reduce_mean": "reduce_mean",
    "drjax_reduce_max": "reduce_max",
    "drjax_stage_transfer": "stage_transfer",
}

# A placement-set on the lattice: the stack prefix of placement names whose
# group axes lead a value. () is the server.
PlacementSet = Tuple[str, ...]


def _join(a: PlacementSet, b: PlacementSet) -> PlacementSet:
    """Lattice join: the deeper of two stack prefixes.

    Well-formed programs only ever join comparable prefixes; if two
    incomparable chains meet (e.g. across a flat/nested regrouping
    boundary), the deeper one wins — what matters downstream is how many
    group axes lead the value."""
    return a if len(a) >= len(b) else b


def _normalize_placements(spec) -> Tuple[Tuple[str, int, str], ...]:
    """Accept an int (one "clients" placement), an ordered mapping
    name -> size, a PlacementContext, or a (name, size[, kind]) sequence.
    Returns (name, size, kind) triples, kind defaulting to "replicas"."""
    if isinstance(spec, (int, np.integer)):
        return (("clients", int(spec), "replicas"),)
    if hasattr(spec, "placements"):  # PlacementContext
        return tuple(
            (p.name, p.size, getattr(p, "kind", "replicas"))
            for p in spec.placements
        )
    if isinstance(spec, Mapping):
        return tuple((str(n), int(s), "replicas") for n, s in spec.items())
    out = []
    for entry in spec:
        entry = tuple(entry)
        kind = str(entry[2]) if len(entry) > 2 else "replicas"
        out.append((str(entry[0]), int(entry[1]), kind))
    return tuple(out)


def _eqn_placement(eqn) -> Tuple[Tuple[str, ...], int]:
    """(stack names, addressed index) of a DrJAX eqn, from its params."""
    pctx = eqn.params.get("pctx")
    if pctx is None:  # defensive: a hand-built eqn without context
        return ("clients",), 0
    return pctx.names, pctx.index_of(eqn.params.get("placement"))

# Param keys under which call-like primitives stash their sub-jaxpr.
_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _is_literal(a) -> bool:
    return isinstance(a, jex_core.Literal)


_is_dropvar = jaxpr_compat.is_dropvar


def _eqn_subjaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v
        elif isinstance(v, jex_core.Jaxpr):
            yield jex_core.ClosedJaxpr(v, ())
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, jex_core.ClosedJaxpr):
                    yield item
                elif isinstance(item, jex_core.Jaxpr):
                    yield jex_core.ClosedJaxpr(item, ())


def _contains_comm(jaxpr) -> bool:
    """Does this (open) jaxpr bind a DrJAX primitive, at any nesting depth?"""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COMM:
            return True
        for sub in _eqn_subjaxprs(eqn):
            if _contains_comm(sub.jaxpr):
                return True
    return False


def _call_subjaxpr(eqn) -> Optional[Any]:
    """The sub-jaxpr of a call-like eqn (pjit/closed_call/remat/custom_*).

    Returns a ``ClosedJaxpr`` or ``None`` if the eqn is not call-like (or is a
    control-flow primitive, which gets its own stage kind instead).
    """
    if eqn.primitive.name in ("scan", "while", "cond"):
        return None
    for key in _CALL_JAXPR_KEYS:
        v = eqn.params.get(key)
        if isinstance(v, jex_core.ClosedJaxpr):
            return v
        if isinstance(v, jex_core.Jaxpr):
            return jex_core.ClosedJaxpr(v, ())
    return None


_fresh_var = jaxpr_compat.fresh_var


def _rewrite_eqn(eqn, resolve):
    """Rebuild an eqn with its invars resolved through the substitution."""
    new_invars = [resolve(a) for a in eqn.invars]
    if all(a is b for a, b in zip(new_invars, eqn.invars)):
        return eqn
    return eqn.replace(invars=new_invars)


# ---------------------------------------------------------------------------
# plan IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Stage:
    """Base class for plan stages."""


@dataclasses.dataclass
class LocalCompute(Stage):
    """A maximal run of non-communication eqns at a single placement."""

    at_groups: bool  # True: runs on every group; False: runs on the server
    eqns: List[Any] = dataclasses.field(default_factory=list)

    @property
    def kind(self) -> str:
        return "GROUP_COMPUTE" if self.at_groups else "SERVER_COMPUTE"


@dataclasses.dataclass
class Broadcast(Stage):
    """``broadcast@placement``: one level down the placement stack.

    ``placement`` is the addressed placement (the level whose group axis the
    broadcast inserts); ``source`` is the placement the operand lives at —
    ``"server"`` for the outermost level, else the next-outer placement."""

    eqn: Any = None
    kind: str = "BROADCAST"
    placement: str = "clients"
    source: str = "server"


@dataclasses.dataclass
class Reduce(Stage):
    """``reduce_*@placement``: one level up the placement stack.

    ``placement`` is the addressed placement (whose group axis the reduce
    removes); ``dest`` is where the result lands — ``"server"`` for the
    outermost level, else the next-outer placement."""

    op: str = "reduce_sum"
    eqn: Any = None
    kind: str = "REDUCE"
    placement: str = "clients"
    dest: str = "server"


@dataclasses.dataclass
class Transfer(Stage):
    """``stage_transfer@placement``: neighbor exchange along a stage level.

    ``placement`` is the addressed stage-kind placement. Each stage ships
    its slice ``shift`` neighbors down the pipeline (ICI traffic between
    adjacent stage shards); boundary slots are zero-filled unless ``wrap``.
    Unlike Broadcast/Reduce this stage does not move on the lattice: operand
    and result are both partitioned at the stage level's depth."""

    eqn: Any = None
    kind: str = "TRANSFER"
    placement: str = "stages"
    shift: int = 1
    wrap: bool = False


@dataclasses.dataclass
class LoopStage(Stage):
    """A scan/while whose body communicates: a sub-plan run per iteration.

    ``trip_count`` is the scan length, or ``None`` for a data-dependent
    ``while``. The body sub-plan's invars follow the loop binder convention
    (consts ++ carry [++ xs-slice for scan]).
    """

    eqn: Any = None
    body_plan: Optional["MapReducePlan"] = None
    trip_count: Optional[int] = None
    loop_kind: str = "scan"  # "scan" | "while"
    # while only: the predicate as a sub-plan, so communication inside the
    # loop condition (e.g. an adaptive-stopping reduce) is explicit too.
    cond_plan: Optional["MapReducePlan"] = None
    kind: str = "LOOP"


@dataclasses.dataclass
class CondStage(Stage):
    """A lax.cond whose branches communicate: one sub-plan per branch."""

    eqn: Any = None
    branch_plans: List["MapReducePlan"] = dataclasses.field(default_factory=list)
    kind: str = "COND"


@dataclasses.dataclass
class MapReducePlan:
    jaxpr: Any  # ClosedJaxpr
    partition_size: int  # total innermost groups (product over the stack)
    stages: List[Stage]
    # Lattice depth of each invar/outvar: the number of leading group axes
    # (0 = server). Bools compare equal to 0/1, so single-placement callers
    # keep seeing the legacy True/False surface.
    partitioned_invars: Tuple[int, ...]
    partitioned_outvars: Tuple[int, ...] = ()
    # The plan's placement stack, outermost first.
    placements: Tuple[Tuple[str, int], ...] = ()
    # Kind per placement level ("replicas" | "stages"), parallel to
    # ``placements`` (kept separate so legacy (name, size) consumers and
    # fingerprints of kind-free plans are untouched).
    placement_kinds: Tuple[str, ...] = ()
    # Full placement-sets (name prefixes) per invar/outvar.
    invar_placements: Tuple[PlacementSet, ...] = ()
    outvar_placements: Tuple[PlacementSet, ...] = ()
    # Values for constvars pulled in from inlined sub-jaxprs.
    extra_consts: Dict[Any, Any] = dataclasses.field(default_factory=dict)
    # jaxpr.outvars resolved through the inlining substitution: reading these
    # from the executor env yields the plan outputs.
    out_atoms: Tuple[Any, ...] = ()

    def __post_init__(self):
        if not self.out_atoms:
            self.out_atoms = tuple(self.jaxpr.jaxpr.outvars)
        if not self.placements:
            self.placements = (("clients", self.partition_size),)
        if not self.placement_kinds:
            self.placement_kinds = tuple("replicas" for _ in self.placements)
        if not self.invar_placements:
            names = tuple(n for n, _ in self.placements)
            self.invar_placements = tuple(
                names[: int(d)] for d in self.partitioned_invars
            )
        if not self.partitioned_outvars:
            self.partitioned_outvars = tuple(0 for _ in self.out_atoms)
        if not self.outvar_placements:
            names = tuple(n for n, _ in self.placements)
            self.outvar_placements = tuple(
                names[: int(d)] for d in self.partitioned_outvars
            )

    @property
    def placement_sizes(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.placements)

    # -- const environment --------------------------------------------------

    def const_env(self) -> Dict[Any, Any]:
        env = dict(zip(self.jaxpr.jaxpr.constvars, self.jaxpr.consts))
        env.update(self.extra_consts)
        return env

    def beam_consts(self) -> List[Any]:
        """Constant values for ``build_pipeline(..., consts=...)``.

        The list order matches the ``consts[i]`` indices in :meth:`to_beam`
        output (all plans depth-first, each plan's const env in order, first
        occurrence wins — the same dedup the emitter's index table uses).
        """
        seen: Dict[Any, Any] = {}
        for p in _all_plans(self):
            for atom, val in p.const_env().items():
                if atom not in seen:
                    seen[atom] = val
        return list(seen.values())

    # -- stage naming / traversal -------------------------------------------

    def named_stages(self, _prefix: str = ""):
        """Yield ``(name, stage, owner_plan)`` depth-first.

        Top-level stages are ``stage_0, stage_1, …``; a loop body's stages are
        ``stage_2_0, …``; cond branches ``stage_3_b0_0, …``.
        """
        for i, s in enumerate(self.stages):
            name = f"stage_{_prefix}{i}"
            yield name, s, self
            if isinstance(s, LoopStage):
                if s.cond_plan is not None:
                    yield from s.cond_plan.named_stages(f"{_prefix}{i}_c_")
                if s.body_plan is not None:
                    yield from s.body_plan.named_stages(f"{_prefix}{i}_")
            elif isinstance(s, CondStage):
                for b, bp in enumerate(s.branch_plans):
                    yield from bp.named_stages(f"{_prefix}{i}_b{b}_")

    # -- dataflow (per-stage inputs/outputs) ---------------------------------

    def stage_io(self) -> List[Tuple[Stage, List[Any], List[Any]]]:
        """For each top-level stage: (stage, input_atoms, output_vars).

        ``input_atoms`` are the non-literal atoms the stage reads that it does
        not itself define (in first-read order). ``output_vars`` are the vars
        it defines that a later stage reads or that are plan outputs.
        """
        reads: List[List[Any]] = []
        writes: List[List[Any]] = []
        for s in self.stages:
            reads.append(_stage_reads(s))
            writes.append(_stage_writes(s))
        out = []
        final = set(a for a in self.out_atoms if not _is_literal(a))
        for i, s in enumerate(self.stages):
            later = set()
            for r in reads[i + 1:]:
                later.update(r)
            outputs = [w for w in writes[i] if w in later or w in final]
            out.append((s, reads[i], outputs))
        return out

    def stage_fns(self) -> Dict[str, Callable]:
        """Real Python callables for every LocalCompute stage (jaxpr slicing).

        Each callable takes the stage's input atoms (see :meth:`stage_io`) as
        positional arguments — partitioned inputs stacked along the leading
        group axis — evaluates the stage's sliced eqns eagerly, and returns
        the stage's outputs as a tuple. Constants are closed over. Keys match
        :meth:`named_stages`.
        """
        fns: Dict[str, Callable] = {}
        io_cache: Dict[int, Dict[int, Tuple[List[Any], List[Any]]]] = {}
        const_cache: Dict[int, Dict[Any, Any]] = {}
        for name, stage, owner in self.named_stages():
            if not isinstance(stage, LocalCompute):
                continue
            key = id(owner)
            if key not in io_cache:
                io_cache[key] = {
                    id(s): (ins, outs) for s, ins, outs in owner.stage_io()
                }
                const_cache[key] = owner.const_env()
            ins, outs = io_cache[key][id(stage)]
            consts = const_cache[key]
            ins = [a for a in ins if a not in consts]
            fns[name] = _make_stage_fn(stage, ins, outs, consts)
        return fns

    # -- compiled execution --------------------------------------------------

    def compile(
        self,
        *,
        mesh=None,
        placement_axes=None,
        donate_argnums: Sequence[int] = (),
        loops: str = "native",
    ):
        """Lower the whole plan into ONE donation-aware jitted executable.

        Returns a :class:`repro.runtime.executor.CompiledPlan`: loop stages
        become ``lax.scan``/``lax.while_loop``, cond stages ``lax.switch``,
        adjacent local stages fuse, and executables are cached by
        ``(plan fingerprint, mesh shape, arg shapes/dtypes)``. Bitwise-equal
        to :func:`run_plan` on CPU (the correctness oracle); ``run_plan``
        stays the eager fallback. See the executor module for the donation
        rule and the elastic per-placement-level cache split.
        """
        from repro.runtime import executor as _executor  # lazy: no core->runtime cycle

        return _executor.compile_plan(
            self,
            mesh=mesh,
            placement_axes=placement_axes,
            donate_argnums=donate_argnums,
            loops=loops,
        )

    # -- emitters ----------------------------------------------------------

    def to_text(self) -> str:
        pp = _VarNamer()
        if len(self.placements) > 1 or "stages" in self.placement_kinds:
            header = (
                "MapReducePlan(placements="
                + "/".join(
                    f"{n}:{s}" + ("[stages]" if k == "stages" else "")
                    for (n, s), k in zip(self.placements, self.placement_kinds)
                )
                + ")"
            )
        else:
            header = f"MapReducePlan(partition_size={self.partition_size})"

        def place_tag(pl: PlacementSet) -> str:
            if not pl:
                return "SERVER"
            if len(self.placements) == 1 and len(pl) == 1:
                return "GROUPS"
            return "/".join(pl)

        lines = [
            header,
            "  inputs: "
            + ", ".join(
                f"{pp(v)}:{v.aval.str_short()} @{place_tag(pl)}"
                for v, pl in zip(
                    self.jaxpr.jaxpr.invars, self.invar_placements
                )
            ),
        ]
        lines.extend(_stage_text_lines(self.stages, indent=2, pp=pp))
        outs = ", ".join(pp(v) for v in self.jaxpr.jaxpr.outvars)
        lines.append(f"  outputs: {outs}")
        return "\n".join(lines)

    def to_beam(self) -> str:
        """An Apache Beam pipeline for this plan.

        Every referenced name is defined before use; local stages call the
        real callables from :meth:`stage_fns` (passed in as ``fns``).
        Partitioned values are keyed PCollections ``(group_id, value)``;
        server values are singleton PCollections; broadcasts become named
        side inputs. Loops with a static trip count unroll at pipeline
        construction time.
        """
        return _BeamEmitter(self).emit()

    # -- static analysis -----------------------------------------------------

    def analyze(
        self,
        *,
        donate_argnums: Sequence[int] = (),
        cross_validate: bool = False,
        comm_cost: bool = True,
    ):
        """Run every static-analysis pass over this plan without executing it.

        Returns a :class:`repro.analysis.AnalysisReport`: placement safety
        (the full-pass generalization of :meth:`check_locality`), donation/
        aliasing for the given ``donate_argnums``, retrace hazards over the
        captured consts, and the per-stage communication-cost model
        (``report.comm_cost``). ``report.ok`` is True iff no pass found an
        error; ``report.raise_if_errors()`` is the assert-style surface the
        oracle suite uses. ``cross_validate=True`` additionally checks the
        comm model against ``compat.cost_analysis`` on standalone-compiled
        reduce eqns (slow: one compile per comm stage).
        """
        from repro import analysis as _analysis  # lazy: no core->analysis cycle

        return _analysis.analyze_plan(
            self,
            donate_argnums=donate_argnums,
            cross_validate=cross_validate,
            comm_cost=comm_cost,
        )

    def comm_cost(self):
        """Static per-stage wire-byte model (DCN/ICI split, compress tags).

        Returns a :class:`repro.analysis.commcost.CommCostReport`; see
        ``report.dcn_bytes`` / ``report.ici_bytes`` / ``report.per_stage``.
        """
        from repro import analysis as _analysis  # lazy: no core->analysis cycle

        return _analysis.estimate_comm_cost(self)

    def subplans(self) -> List["MapReducePlan"]:
        """This plan and every nested sub-plan, depth-first in stage order."""
        return list(_all_plans(self))

    # -- structural checks --------------------------------------------------

    def communication_stages(self, recursive: bool = False) -> List[Stage]:
        out = []
        for name, s, _ in self.named_stages():
            if isinstance(s, (Broadcast, Reduce, Transfer)):
                if recursive or "_" not in name[len("stage_"):]:
                    out.append(s)
        return out

    def check_locality(self) -> None:
        """No communication primitive may hide inside a local stage.

        Checks *at any depth*: an opaque eqn whose sub-jaxpr communicates
        (e.g. a higher-order primitive the builder does not know how to
        stage, like ``custom_linear_solve``) fails loudly here instead of
        being silently mislabeled local compute.
        """
        for _, s, _ in self.named_stages():
            if isinstance(s, LocalCompute):
                for e in s.eqns:
                    if e.primitive.name in _COMM or any(
                        _contains_comm(sub.jaxpr)
                        for sub in _eqn_subjaxprs(e)
                    ):
                        raise AssertionError(
                            f"communication primitive inside {s.kind} stage "
                            f"(eqn {e.primitive.name}): this control-flow "
                            f"structure is not representable as a MapReduce "
                            f"plan yet"
                        )


def _stage_reads(stage: Stage) -> List[Any]:
    """Non-literal atoms a stage reads but does not define (first-read order)."""
    if isinstance(stage, LocalCompute):
        seen, defined, reads = set(), set(), []
        for eqn in stage.eqns:
            for a in eqn.invars:
                if _is_literal(a) or a in defined or a in seen:
                    continue
                seen.add(a)
                reads.append(a)
            defined.update(o for o in eqn.outvars if not _is_dropvar(o))
        return reads
    seen, reads = set(), []
    for a in stage.eqn.invars:
        if _is_literal(a) or a in seen:
            continue
        seen.add(a)
        reads.append(a)
    return reads


def _stage_writes(stage: Stage) -> List[Any]:
    if isinstance(stage, LocalCompute):
        return [o for e in stage.eqns for o in e.outvars if not _is_dropvar(o)]
    return [o for o in stage.eqn.outvars if not _is_dropvar(o)]


def _make_stage_fn(stage, ins, outs, consts):
    # Consts are hoisted into the closure ONCE (beam_consts-style): per call
    # we only bind the stage inputs, instead of re-binding every captured
    # constant into a fresh env — on the compiled path this also means the
    # constants are staged into the executable once, not re-staged per round.
    const_env = dict(consts)

    def fn(*vals):
        if len(vals) != len(ins):
            raise TypeError(
                f"stage fn expects {len(ins)} inputs, got {len(vals)}"
            )
        env = dict(zip(ins, vals))

        def read(a):
            if _is_literal(a):
                return a.val
            if a in env:
                return env[a]
            return const_env[a]

        for eqn in stage.eqns:
            results = _eval_eqn(eqn, read)
            for o, val in zip(eqn.outvars, results):
                if not _is_dropvar(o):
                    env[o] = val
        return tuple(read(o) for o in outs)

    fn.input_vars = list(ins)
    fn.output_vars = list(outs)
    return fn


class _VarNamer:
    """Stable short names (a, b, …, aa, …) for jaxpr atoms in to_text()."""

    def __init__(self):
        self._names: Dict[Any, str] = {}

    def __call__(self, atom) -> str:
        if _is_literal(atom):
            return repr(np.asarray(atom.val).tolist())
        if atom not in self._names:
            i = len(self._names)
            name = ""
            while True:
                name = chr(ord("a") + i % 26) + name
                i = i // 26 - 1
                if i < 0:
                    break
            self._names[atom] = name
        return self._names[atom]


def _stage_text_lines(
    stages: Sequence[Stage], indent: int, pp: Optional[_VarNamer] = None
) -> List[str]:
    pp = pp or _VarNamer()
    pad = " " * indent
    lines: List[str] = []
    for i, s in enumerate(stages):
        if isinstance(s, LocalCompute):
            ops = ", ".join(e.primitive.name for e in s.eqns)
            lines.append(f"{pad}stage {i}: {s.kind} [{ops}]")
        elif isinstance(s, Broadcast):
            route = (
                "server->groups"
                if s.source == "server"
                else f"{s.source}->{s.placement}"
            )
            lines.append(
                f"{pad}stage {i}: BROADCAST {route} @{s.placement} "
                f"({pp(s.eqn.invars[0])} -> {pp(s.eqn.outvars[0])})"
            )
        elif isinstance(s, Reduce):
            route = (
                "groups->server"
                if s.dest == "server"
                else f"{s.placement}->{s.dest}"
            )
            lines.append(
                f"{pad}stage {i}: {s.op.upper()} {route} @{s.placement} "
                f"({pp(s.eqn.invars[0])} -> {pp(s.eqn.outvars[0])})"
            )
        elif isinstance(s, Transfer):
            shift = f"{s.shift:+d}" + (" wrap" if s.wrap else "")
            lines.append(
                f"{pad}stage {i}: TRANSFER shift={shift} @{s.placement} "
                f"({pp(s.eqn.invars[0])} -> {pp(s.eqn.outvars[0])})"
            )
        elif isinstance(s, LoopStage):
            trip = "?" if s.trip_count is None else str(s.trip_count)
            lines.append(
                f"{pad}stage {i}: LOOP[{s.loop_kind}] trip_count={trip}:"
            )
            if s.cond_plan is not None and s.cond_plan.stages:
                lines.append(f"{pad}  cond:")
                lines.extend(
                    _stage_text_lines(s.cond_plan.stages, indent + 4, pp)
                )
                lines.append(f"{pad}  body:")
            lines.extend(
                _stage_text_lines(s.body_plan.stages, indent + 4, pp)
            )
        elif isinstance(s, CondStage):
            lines.append(
                f"{pad}stage {i}: COND over {len(s.branch_plans)} branches:"
            )
            for b, bp in enumerate(s.branch_plans):
                lines.append(f"{pad}  branch {b}:")
                lines.extend(_stage_text_lines(bp.stages, indent + 4, pp))
    return lines


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------


def trace(fn: Callable, *example_args) -> Any:
    """ClosedJaxpr of ``fn`` (which must already carry its drjax context)."""
    return jax.make_jaxpr(fn)(*example_args)


def _placement_depth(shape, sizes: Tuple[int, ...]) -> int:
    """Largest k such that the k leading dims match the k outermost
    placement sizes (the lattice-depth heuristic for undeclared inputs)."""
    k = 0
    while k < len(sizes) and k < len(shape) and shape[k] == sizes[k]:
        k += 1
    return k


def build_plan(
    closed: Any,
    partition_size,
    partitioned_invars: Optional[Sequence[Any]] = None,
) -> MapReducePlan:
    """Segment a jaxpr into MapReduce stages (recursing into control flow).

    ``partition_size`` is the placement spec: an int (the paper's flat API —
    one "clients" placement), an ordered mapping ``{"pods": P, "clients": m}``
    (outermost first), a ``PlacementContext``, or a (name, size) sequence.

    ``partitioned_invars[i]`` declares input i's position on the placement
    lattice: a bool (legacy: False = server, True = fully partitioned), an
    int depth (number of leading group axes), or a placement-name prefix
    tuple. If omitted, an input's depth is the longest prefix of placement
    sizes matching its leading dims — right for all examples here, but
    callers with ambiguous shapes should pass it explicitly.
    """
    triples = _normalize_placements(partition_size)
    placements = tuple((n, s) for n, s, _ in triples)
    kinds = tuple(k for _, _, k in triples)
    names = tuple(n for n, _ in placements)
    sizes = tuple(s for _, s in placements)
    total = math.prod(sizes)

    def norm_part(entry) -> PlacementSet:
        if isinstance(entry, tuple):
            return entry
        if entry is True:
            return names
        if entry is False or entry is None:
            return ()
        return names[: int(entry)]

    jaxpr = closed.jaxpr
    if partitioned_invars is None:
        invar_placements = tuple(
            names[: _placement_depth(v.aval.shape, sizes)]
            for v in jaxpr.invars
        )
    else:
        invar_placements = tuple(norm_part(e) for e in partitioned_invars)

    placed: Dict[Any, PlacementSet] = {}  # defining var -> placement prefix
    subst: Dict[Any, Any] = {}  # call-boundary var -> defining atom
    extra_consts: Dict[Any, Any] = {}
    stages: List[Stage] = []

    for v, p in zip(jaxpr.invars, invar_placements):
        placed[v] = p
    for v in jaxpr.constvars:
        placed[v] = ()

    def resolve(a):
        while not _is_literal(a) and a in subst:
            a = subst[a]
        return a

    def is_part(a) -> PlacementSet:
        a = resolve(a)
        if _is_literal(a):
            return ()
        return placed.get(a, ())

    def append_local(eqn, at_groups: bool):
        if (
            stages
            and isinstance(stages[-1], LocalCompute)
            and stages[-1].at_groups == at_groups
        ):
            stages[-1].eqns.append(eqn)
        else:
            stages.append(LocalCompute(at_groups=at_groups, eqns=[eqn]))

    def inline_call(eqn, sub):
        inner = sub.jaxpr
        for cv, cval in zip(inner.constvars, sub.consts):
            extra_consts[cv] = cval
            placed[cv] = ()
        for iv, outer in zip(inner.invars, eqn.invars):
            subst[iv] = resolve(outer)
        # Alpha-rename every var the body defines: jit caches one jaxpr per
        # function, so the same sub-jaxpr (same Var objects) can be inlined
        # at several call sites — without fresh outvars the second inline
        # would overwrite the first's values in the executor env.
        renamed = []
        for ie in inner.eqns:
            new_outvars = []
            for o in ie.outvars:
                if _is_dropvar(o):
                    new_outvars.append(o)
                else:
                    fresh = _fresh_var(o.aval)
                    subst[o] = fresh
                    new_outvars.append(fresh)
            renamed.append(ie.replace(outvars=new_outvars))
        emit(renamed)
        for outer_o, inner_o in zip(eqn.outvars, inner.outvars):
            if _is_dropvar(outer_o):
                continue
            subst[outer_o] = resolve(inner_o)

    def emit_scan(eqn):
        params = eqn.params
        nc, ncar = params["num_consts"], params["num_carry"]
        body = params["jaxpr"]  # ClosedJaxpr
        consts_p = [is_part(a) for a in eqn.invars[:nc]]
        carry_p = [is_part(a) for a in eqn.invars[nc : nc + ncar]]
        # xs binders see one slice per step: the scan axis is gone, so the
        # lattice-depth heuristic applies to the *sliced* aval.
        xs_p = [
            names[: _placement_depth(b.aval.shape, sizes)]
            for b in body.jaxpr.invars[nc + ncar :]
        ]
        # Fixed point over the carry on the placement lattice: a carry that
        # climbs the lattice after one iteration keeps the joined placement
        # for the whole loop.
        body_plan = None
        for _ in range(ncar + 1):
            body_plan = build_plan(
                body, triples,
                partitioned_invars=consts_p + carry_p + xs_p,
            )
            out_p = list(body_plan.outvar_placements[:ncar])
            new_carry = [_join(a, b) for a, b in zip(carry_p, out_p)]
            if new_carry == carry_p:
                break
            carry_p = new_carry
        stages.append(
            LoopStage(
                eqn=_rewrite_eqn(eqn, resolve),
                body_plan=body_plan,
                trip_count=params["length"],
                loop_kind="scan",
            )
        )
        outs_p = body_plan.outvar_placements
        # carry outputs keep the fixed-point placement; stacked ys are
        # server-placed: the new time axis leads, so the group axes (if any)
        # are no longer the leading axes and downstream consumption of the
        # whole (T, ...) stack happens at the server/driver.
        num_ys = len(eqn.outvars) - ncar
        for o, p in zip(
            eqn.outvars, list(outs_p[:ncar]) + [()] * num_ys
        ):
            if not _is_dropvar(o):
                placed[o] = p

    def emit_while(eqn):
        params = eqn.params
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        body = params["body_jaxpr"]  # ClosedJaxpr
        cond_consts_p = [is_part(a) for a in eqn.invars[:cn]]
        body_consts_p = [is_part(a) for a in eqn.invars[cn : cn + bn]]
        carry_p = [is_part(a) for a in eqn.invars[cn + bn :]]
        body_plan = None
        for _ in range(len(carry_p) + 1):
            body_plan = build_plan(
                body, triples,
                partitioned_invars=body_consts_p + carry_p,
            )
            out_p = list(body_plan.outvar_placements)
            new_carry = [_join(a, b) for a, b in zip(carry_p, out_p)]
            if new_carry == carry_p:
                break
            carry_p = new_carry
        # The predicate runs once per iteration too: plan it so communication
        # inside the cond (adaptive stopping) shows up as explicit stages.
        cond_plan = build_plan(
            params["cond_jaxpr"], triples,
            partitioned_invars=cond_consts_p + carry_p,
        )
        stages.append(
            LoopStage(
                eqn=_rewrite_eqn(eqn, resolve),
                body_plan=body_plan,
                trip_count=None,
                loop_kind="while",
                cond_plan=cond_plan,
            )
        )
        for o, p in zip(eqn.outvars, carry_p):
            if not _is_dropvar(o):
                placed[o] = p

    def emit_cond(eqn):
        branches = eqn.params["branches"]
        ops_p = [is_part(a) for a in eqn.invars[1:]]
        branch_plans = [
            build_plan(b, triples, partitioned_invars=ops_p)
            for b in branches
        ]
        stages.append(
            CondStage(
                eqn=_rewrite_eqn(eqn, resolve), branch_plans=branch_plans
            )
        )
        for i, o in enumerate(eqn.outvars):
            if not _is_dropvar(o):
                p = ()
                for bp in branch_plans:
                    p = _join(p, bp.outvar_placements[i])
                placed[o] = p

    def emit(eqns):
        for eqn in eqns:
            name = eqn.primitive.name
            has_comm = any(
                _contains_comm(sub.jaxpr) for sub in _eqn_subjaxprs(eqn)
            )
            if name == "drjax_broadcast":
                enames, i = _eqn_placement(eqn)
                in_pl = is_part(eqn.invars[0])
                # A broadcast at level i expects a depth-i operand; a deeper
                # operand on the SAME name chain would duplicate a level the
                # value already has — the result leaves the prefix lattice.
                if len(in_pl) > i and in_pl[: i + 1] == enames[: i + 1]:
                    raise ValueError(
                        f"broadcast@{enames[i]} over a value already "
                        f"partitioned at {in_pl}: only the next level of a "
                        f"value's placement prefix can be broadcast"
                    )
                stages.append(
                    Broadcast(
                        eqn=_rewrite_eqn(eqn, resolve),
                        placement=enames[i],
                        source=enames[i - 1] if i > 0 else "server",
                    )
                )
                for o in eqn.outvars:
                    if not _is_dropvar(o):
                        placed[o] = enames[: i + 1]
            elif name == "drjax_stage_transfer":
                enames, i = _eqn_placement(eqn)
                stages.append(
                    Transfer(
                        eqn=_rewrite_eqn(eqn, resolve),
                        placement=enames[i],
                        shift=int(eqn.params.get("shift", 1)),
                        wrap=bool(eqn.params.get("wrap", False)),
                    )
                )
                # No lattice movement: a transfer permutes values among the
                # stage groups, so the result stays at the stage level's
                # depth (i + 1 leading group axes).
                for o in eqn.outvars:
                    if not _is_dropvar(o):
                        placed[o] = enames[: i + 1]
            elif name in _COMM:
                enames, i = _eqn_placement(eqn)
                in_pl = is_part(eqn.invars[0])
                # Reducing an OUTER level of a deeper value (e.g.
                # reduce@pods of a (pods, clients) value) would yield
                # "clients without pods" — not a stack prefix, so neither
                # this lattice nor the Beam keying can represent it. Fail
                # loudly instead of emitting a wrong pipeline.
                if len(in_pl) > i + 1 and in_pl[: i + 1] == enames[: i + 1]:
                    raise ValueError(
                        f"{_COMM[name]}@{enames[i]} reduces an outer level "
                        f"of a value partitioned at {in_pl}: only the "
                        f"innermost level of a value's placement prefix can "
                        f"be reduced (reduce {in_pl[-1]!r} first)"
                    )
                stages.append(
                    Reduce(
                        op=_COMM[name],
                        eqn=_rewrite_eqn(eqn, resolve),
                        placement=enames[i],
                        dest=enames[i - 1] if i > 0 else "server",
                    )
                )
                for o in eqn.outvars:
                    if not _is_dropvar(o):
                        placed[o] = enames[:i]
            elif name == "scan" and has_comm:
                emit_scan(eqn)
            elif name == "while" and has_comm:
                emit_while(eqn)
            elif name == "cond" and has_comm:
                emit_cond(eqn)
            elif has_comm and (sub := _call_subjaxpr(eqn)) is not None and len(
                sub.jaxpr.invars
            ) == len(eqn.invars):
                inline_call(eqn, sub)
            else:
                eqn2 = _rewrite_eqn(eqn, resolve)
                p = ()
                for a in eqn.invars:
                    p = _join(p, is_part(a))
                for o in eqn.outvars:
                    if not _is_dropvar(o):
                        placed[o] = p
                append_local(eqn2, bool(p))

    emit(jaxpr.eqns)

    out_atoms = tuple(resolve(v) for v in jaxpr.outvars)
    outvar_placements = tuple(is_part(a) for a in jaxpr.outvars)
    plan = MapReducePlan(
        jaxpr=closed,
        partition_size=total,
        stages=stages,
        partitioned_invars=tuple(len(p) for p in invar_placements),
        partitioned_outvars=tuple(len(p) for p in outvar_placements),
        placements=placements,
        placement_kinds=kinds,
        invar_placements=invar_placements,
        outvar_placements=outvar_placements,
        extra_consts=extra_consts,
        out_atoms=out_atoms,
    )
    plan.check_locality()
    return plan


# ---------------------------------------------------------------------------
# reference plan executor (mini federated runtime)
# ---------------------------------------------------------------------------


def _eval_eqn(eqn, read):
    """Evaluate one jaxpr eqn eagerly."""
    invals = [read(v) for v in eqn.invars]
    subfuns, params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *invals, **params)
    return out if eqn.primitive.multiple_results else [out]


def run_plan(plan: MapReducePlan, *args):
    """Execute the plan stage by stage, honoring staged control flow.

    Partitioned values live as stacked arrays but are only *created* by
    Broadcast stages and only *consumed across groups* by Reduce stages;
    ``check_locality`` guarantees every GROUP_COMPUTE stage is group-elementwise
    (it came from a vmap body). Loop stages iterate their body sub-plan
    (scan semantics: consts ++ carry ++ xs-slices, stacked ys); cond stages
    select and run one branch sub-plan. This mirrors how a federated/Beam
    backend would run the plan: local stages per group, explicit communication
    between, with the driver owning control flow.
    """
    return _execute_plan(plan, list(args))


def _execute_plan(plan: MapReducePlan, args: List[Any]) -> List[Any]:
    jaxpr = plan.jaxpr.jaxpr
    env: Dict[Any, Any] = {}

    def read(a):
        if _is_literal(a):
            return a.val
        return env[a]

    def write(v, val):
        if not _is_dropvar(v):
            env[v] = val

    for v, val in zip(jaxpr.constvars, plan.jaxpr.consts):
        write(v, val)
    for v, val in plan.extra_consts.items():
        write(v, val)
    for v, val in zip(jaxpr.invars, args):
        write(v, val)

    for stage in plan.stages:
        if isinstance(stage, (Broadcast, Reduce, Transfer)):
            eqn = stage.eqn
            for o, val in zip(eqn.outvars, _eval_eqn(eqn, read)):
                write(o, val)
        elif isinstance(stage, LocalCompute):
            for eqn in stage.eqns:
                for o, val in zip(eqn.outvars, _eval_eqn(eqn, read)):
                    write(o, val)
        elif isinstance(stage, LoopStage):
            _run_loop_stage(stage, read, write)
        elif isinstance(stage, CondStage):
            _run_cond_stage(stage, read, write)
        else:  # pragma: no cover - future stage kinds
            raise TypeError(f"unknown stage kind: {stage!r}")

    return [read(a) for a in plan.out_atoms]


def _run_loop_stage(stage: LoopStage, read, write):
    eqn = stage.eqn
    params = eqn.params
    if stage.loop_kind == "scan":
        nc, ncar = params["num_consts"], params["num_carry"]
        length = params["length"]
        reverse = params.get("reverse", False)
        invals = [read(a) for a in eqn.invars]
        consts = invals[:nc]
        carry = list(invals[nc : nc + ncar])
        xs = invals[nc + ncar :]
        num_ys = len(eqn.outvars) - ncar
        ys: List[Tuple[Any, ...]] = []
        indices = range(length - 1, -1, -1) if reverse else range(length)
        for i in indices:
            xi = [x[i] for x in xs]
            outs = _execute_plan(stage.body_plan, consts + carry + xi)
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
    else:  # while
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        invals = [read(a) for a in eqn.invars]
        cond_consts = invals[:cn]
        body_consts = invals[cn : cn + bn]
        carry = list(invals[cn + bn :])

        def pred(carry):
            if stage.cond_plan is not None:
                return bool(
                    _execute_plan(stage.cond_plan, cond_consts + carry)[0]
                )
            cond_jaxpr = params["cond_jaxpr"]
            return bool(
                jaxpr_compat.eval_jaxpr(
                    cond_jaxpr.jaxpr, cond_jaxpr.consts, *cond_consts, *carry
                )[0]
            )

        while pred(carry):
            carry = list(_execute_plan(stage.body_plan, body_consts + carry))
        for o, val in zip(eqn.outvars, carry):
            write(o, val)


def _run_cond_stage(stage: CondStage, read, write):
    eqn = stage.eqn
    idx = int(read(eqn.invars[0]))
    idx = min(max(idx, 0), len(stage.branch_plans) - 1)
    ops = [read(a) for a in eqn.invars[1:]]
    outs = _execute_plan(stage.branch_plans[idx], ops)
    for o, val in zip(eqn.outvars, outs):
        write(o, val)


def count_primitives(closed: Any) -> Dict[str, int]:
    """Histogram of DrJAX primitives in a jaxpr (recursing into sub-jaxprs)."""
    counts: Dict[str, int] = {}

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _COMM:
                counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
            for sub in _eqn_subjaxprs(eqn):
                visit(sub.jaxpr)

    visit(closed.jaxpr)
    return counts


# ---------------------------------------------------------------------------
# Apache Beam emitter
# ---------------------------------------------------------------------------


_BEAM_PREAMBLE = """\
# Apache Beam pipeline generated from a MapReducePlan.
# `fns` are the real Python callables sliced out of the jaxpr:
#   fns = plan.stage_fns()
# Partitioned values are keyed PCollections of (group_id, value); server
# values are singleton PCollections; broadcasts are named side inputs.
# Group stages apply the sliced (group-batched) jaxpr to a 1-row stack per
# element; this assumes the sliced eqns are polymorphic in the leading axis
# (true for vmap-produced elementwise bodies).
import apache_beam as beam
import numpy as np


def _reduce_sum(vals):
  return np.sum(np.stack(list(vals)), axis=0)


def _reduce_mean(vals):
  vs = np.stack(list(vals))
  return np.sum(vs, axis=0) / vs.shape[0]


def _reduce_max(vals):
  return np.max(np.stack(list(vals)), axis=0)


def _lift(v, k):
  # One group's element -> a rank-(k + v.ndim) stack slice: group stages
  # apply the sliced (group-batched) jaxpr, which expects k leading group
  # axes (one per placement level of the value).
  v = np.asarray(v)
  return v.reshape((1,) * k + v.shape)


def _unkey(rows, shape):
  # (key_tuple, value) pairs -> one stacked array with the placement-stack
  # axes restored (row-major over the sorted key tuples).
  arr = np.stack([v for _, v in sorted(rows)])
  return arr.reshape(tuple(shape) + arr.shape[1:])


def _stage_shift(v, axis, shift, wrap):
  # stage_transfer on a stacked (non-keyed) value: roll the stage axis,
  # zero-filling the slots the shift vacated unless wrapping.
  out = np.roll(np.asarray(v), shift, axis=axis)
  if not wrap and shift != 0:
    idx = [slice(None)] * out.ndim
    idx[axis] = slice(0, shift) if shift > 0 else slice(shift, None)
    out[tuple(idx)] = 0
  return out
"""


class _BeamEmitter:
    """Emit a Beam pipeline where every referenced name is defined."""

    def __init__(self, plan: MapReducePlan):
        self.plan = plan
        self.lines: List[str] = []
        self.names: Dict[Any, str] = {}  # atom -> python identifier
        self.kinds: Dict[str, str] = {}  # identifier -> plain|server|group|side
        self._n = 0
        self._labels = 0
        self._indent = 1
        self._loop_vars: List[str] = []
        # broadcast output name -> (pre-broadcast source name, source kind);
        # lets a reduce over a broadcast re-materialize the n replicas
        self.side_src: Dict[str, Tuple[str, str]] = {}
        # Nested plans key partitioned PCollections by placement-path
        # TUPLES (g0, g1, ...); flat plans keep legacy int keys.
        self.nested = len(plan.placements) > 1
        # identifier -> number of key levels for "group"-kind values
        self.depths: Dict[str, int] = {}
        # consts[i] indices, matching plan.beam_consts()
        self._const_index: Dict[Any, int] = {}
        for p in _all_plans(plan):
            for atom in p.const_env():
                self._const_index.setdefault(atom, len(self._const_index))

    # -- low-level helpers ---------------------------------------------------

    def line(self, text: str):
        self.lines.append("  " * self._indent + text)

    def fresh(self, prefix: str = "t") -> str:
        self._n += 1
        return f"{prefix}{self._n}"

    def label(self) -> str:
        """A unique beam step label expression (f-string inside loops)."""
        self._labels += 1
        base = f"S{self._labels}"
        if self._loop_vars:
            suffix = "_".join("{%s}" % v for v in self._loop_vars)
            return f"f'{base}_{suffix}'"
        return f"'{base}'"

    def assign(self, name: str, rhs: str, kind: str, comment: str = ""):
        tail = f"  # {comment}" if comment else ""
        self.line(f"{name} = {rhs}{tail}")
        self.kinds[name] = kind

    # -- naming --------------------------------------------------------------

    def name_of(self, atom, plan: MapReducePlan) -> str:
        """Identifier for an atom, materializing literals/consts on demand."""
        if _is_literal(atom):
            name = self.fresh("lit")
            self.assign(name, _literal_src(atom.val), "plain", "literal")
            return name
        if atom in self.names:
            return self.names[atom]
        if atom in self._const_index:
            name = self.fresh("c")
            self.assign(
                name, f"np.asarray(consts[{self._const_index[atom]}])",
                "plain", "captured constant (see plan.beam_consts())",
            )
            self.names[atom] = name
            return name
        # An atom we never saw defined: surface it as an explicit hole rather
        # than emitting a dangling reference.
        name = self.fresh("undef")
        self.assign(name, "None", "plain", f"unbound atom {atom} (bug?)")
        self.names[atom] = name
        return name

    def bind(self, atom, name: str):
        self.names[atom] = name

    # -- conversions ---------------------------------------------------------

    def to_group(self, name: str) -> str:
        """Convert a server/plain value (stacked rows) into a keyed PColl."""
        kind = self.kinds.get(name, "plain")
        if kind == "group":
            return name
        out = self.fresh("g")
        n0 = self.plan.placement_sizes[0]
        if kind == "plain":
            if self.nested:
                self.assign(
                    out,
                    f"p | {self.label()} >> beam.Create("
                    f"[((j,), {name}[j]) for j in range({n0})])",
                    "group", "key by group (placement path)",
                )
            else:
                self.assign(
                    out,
                    f"p | {self.label()} >> beam.Create(list(enumerate({name})))",
                    "group", "key by group",
                )
        elif kind == "server":
            if self.nested:
                self.assign(
                    out,
                    f"{name} | {self.label()} >> "
                    f"beam.FlatMap(lambda v: [((j,), v[j]) for j in range({n0})])",
                    "group", "key by group (placement path)",
                )
            else:
                self.assign(
                    out,
                    f"{name} | {self.label()} >> "
                    f"beam.FlatMap(lambda v: list(enumerate(v)))",
                    "group", "key by group",
                )
        else:  # side input object: no pipeline handle; leave a typed hole
            self.assign(out, f"{name}", "group", "side input reused per group")
        self.depths[out] = 1
        return out

    def to_server(self, name: str) -> str:
        kind = self.kinds.get(name, "plain")
        if kind in ("server", "plain", "side"):
            return name
        out = self.fresh("s")
        depth = self.depths.get(name, 1)
        if self.nested or depth > 1:
            sizes = self.plan.placement_sizes[:depth]
            self.assign(
                out,
                f"{name} | {self.label()} >> beam.combiners.ToList() "
                f"| {self.label()} >> "
                f"beam.Map(lambda rows: _unkey(rows, {tuple(sizes)!r}))",
                "server", "collect groups to a stacked server value",
            )
        else:
            self.assign(
                out,
                f"{name} | {self.label()} >> beam.combiners.ToList() "
                f"| {self.label()} >> "
                f"beam.Map(lambda rows: np.stack([v for _, v in sorted(rows)]))",
                "server", "collect groups to a stacked server value",
            )
        return out

    # -- emission ------------------------------------------------------------

    def emit(self) -> str:
        plan = self.plan
        self.lines = _BEAM_PREAMBLE.splitlines()
        self.lines.append("")
        self.lines.append("")
        self.lines.append("def build_pipeline(p, args, fns, consts=()):")
        n = plan.partition_size
        if self.nested:
            all_sizes = tuple(plan.placement_sizes)
            self.assign(
                "groups",
                f"p | 'Groups' >> beam.Create("
                f"[(idx, ()) for idx in np.ndindex(*{all_sizes!r})])",
                "group", "one element per innermost group (placement path)",
            )
            self.depths["groups"] = len(all_sizes)
        else:
            self.assign(
                "groups",
                f"p | 'Groups' >> beam.Create([(g, ()) for g in range({n})])",
                "group", "one element per group",
            )
            self.depths["groups"] = 1
        for i, (v, part) in enumerate(
            zip(plan.jaxpr.jaxpr.invars, plan.partitioned_invars)
        ):
            name = self.fresh("in_")
            k = int(part)
            if k and (self.nested or k > 1):
                sizes = tuple(plan.placement_sizes[:k])
                self.assign(
                    name,
                    f"p | {self.label()} >> beam.Create("
                    f"[(idx, args[{i}][idx]) for idx in "
                    f"np.ndindex(*{sizes!r})])",
                    "group",
                    f"plan input {i} @{'/'.join(plan.invar_placements[i])}",
                )
                self.depths[name] = k
            elif k:
                self.assign(
                    name,
                    f"p | {self.label()} >> "
                    f"beam.Create(list(enumerate(args[{i}])))",
                    "group", f"plan input {i} @GROUPS",
                )
                self.depths[name] = 1
            else:
                self.assign(
                    name,
                    f"p | {self.label()} >> beam.Create([args[{i}]])",
                    "server", f"plan input {i} @SERVER",
                )
            self.bind(v, name)
        self.emit_plan_stages(plan, prefix="")
        outs = [self.name_of(a, plan) for a in plan.out_atoms]
        self.line(f"return [{', '.join(outs)}]")
        return "\n".join(self.lines)

    def emit_plan_stages(self, plan: MapReducePlan, prefix: str):
        for i, (stage, reads, outs) in enumerate(plan.stage_io()):
            sname = f"stage_{prefix}{i}"
            if isinstance(stage, Broadcast):
                self.emit_broadcast(stage, plan)
            elif isinstance(stage, Reduce):
                self.emit_reduce(stage, plan)
            elif isinstance(stage, Transfer):
                self.emit_transfer(stage, plan)
            elif isinstance(stage, LocalCompute):
                self.emit_local(stage, plan, sname, outs)
            elif isinstance(stage, LoopStage):
                self.emit_loop(stage, plan, f"{prefix}{i}", outs)
            elif isinstance(stage, CondStage):
                self.emit_cond(stage, plan, f"{prefix}{i}")

    def _stage_placement(self, stage) -> Tuple[int, int]:
        """(addressed stack index, addressed placement size) of a comm eqn."""
        pctx = stage.eqn.params.get("pctx")
        if pctx is None:
            return 0, self.plan.partition_size
        i = pctx.index_of(stage.eqn.params.get("placement"))
        return i, pctx.placements[i].size

    def emit_broadcast(self, stage: Broadcast, plan):
        src = self.name_of(stage.eqn.invars[0], plan)
        out = self.fresh("bc")
        i, size = self._stage_placement(stage)
        kind = self.kinds.get(src, "plain")
        if self.nested or i > 0:
            # Nested stacks materialize keyed PCollections (placement-path
            # tuple keys) instead of side inputs, so a later broadcast@inner
            # can extend the key and a reduce@inner can shorten it.
            tag = f"BROADCAST {stage.source}->{stage.placement}"
            if kind == "group":
                self.assign(
                    out,
                    f"{src} | {self.label()} >> beam.FlatMap("
                    f"lambda kv: [(kv[0] + (j,), kv[1]) "
                    f"for j in range({size})])",
                    "group", f"{tag} (extend placement path)",
                )
                self.depths[out] = self.depths.get(src, 1) + 1
            elif kind == "server":
                self.assign(
                    out,
                    f"p | {self.label()} >> beam.Create("
                    f"[(j,) for j in range({size})]) "
                    f"| {self.label()} >> beam.Map("
                    f"lambda k, _v: ((k,) if not isinstance(k, tuple) "
                    f"else k, _v), beam.pvalue.AsSingleton({src}))",
                    "group", f"{tag} (materialized per group)",
                )
                self.depths[out] = 1
            else:  # plain python value
                self.assign(
                    out,
                    f"p | {self.label()} >> beam.Create("
                    f"[((j,), {src}) for j in range({size})])",
                    "group", f"{tag} (materialized per group)",
                )
                self.depths[out] = 1
            self.bind(stage.eqn.outvars[0], out)
            return
        if kind == "server":
            self.assign(
                out, f"beam.pvalue.AsSingleton({src})", "side",
                "BROADCAST server->groups (side input)",
            )
            self.side_src[out] = (src, "server")
        else:  # plain python value: replicating it is free
            self.assign(out, src, "plain", "BROADCAST (replicated value)")
            self.side_src[out] = (src, "plain")
        self.bind(stage.eqn.outvars[0], out)

    def emit_reduce(self, stage: Reduce, plan):
        src = self.name_of(stage.eqn.invars[0], plan)
        combiner = f"_{stage.op}"
        out = self.fresh("r")
        kind = self.kinds.get(src, "plain")
        i, n = self._stage_placement(stage)
        if kind == "group":
            depth = self.depths.get(src, 1)
            if depth >= 2:
                # An inner-placement reduce: shorten the placement path by
                # one level and combine per remaining key — one shuffle per
                # stage, so a hierarchical reduce stages as two shuffles.
                self.assign(
                    out,
                    f"{src} | {self.label()} >> beam.Map("
                    f"lambda kv: (kv[0][:-1], kv[1])) "
                    f"| {self.label()} >> beam.CombinePerKey({combiner})",
                    "group",
                    f"{stage.op.upper()} {stage.placement}->{stage.dest} "
                    f"(combine per {stage.dest})",
                )
                self.depths[out] = depth - 1
                self.bind(stage.eqn.outvars[0], out)
                return
            if i + 1 != depth:
                self.line(
                    f"# NOTE: {stage.op}@{stage.placement} crosses a "
                    f"placement-regrouping boundary (value tracked at "
                    f"depth {depth}, eqn addresses level {i}); the global "
                    f"combine below approximates the per-{stage.dest} stage"
                )
        if src in self.side_src:
            # reducing a broadcast directly: combine n replicas of the
            # pre-broadcast server value (AsSingleton objects aren't listable)
            base, bkind = self.side_src[src]
            if bkind == "server":
                self.assign(
                    out,
                    f"{base} | {self.label()} >> "
                    f"beam.Map(lambda v: {combiner}([v] * {n}))",
                    "server", f"{stage.op.upper()} over {n} broadcast replicas",
                )
            else:
                self.assign(
                    out, f"{combiner}([{base}] * {n})", "plain",
                    f"{stage.op.upper()} over {n} broadcast replicas",
                )
        elif kind == "group":
            self.assign(
                out,
                f"{src} | {self.label()} >> beam.Values() "
                f"| {self.label()} >> beam.CombineGlobally({combiner})",
                "server", f"{stage.op.upper()} groups->server",
            )
        else:  # stacked plain/server value: reduce locally
            self.assign(
                out, f"{combiner}(list({src}))", "plain",
                f"{stage.op.upper()} over a stacked local value",
            )
        self.bind(stage.eqn.outvars[0], out)

    def emit_transfer(self, stage: Transfer, plan):
        src = self.name_of(stage.eqn.invars[0], plan)
        out = self.fresh("tx")
        i, size = self._stage_placement(stage)
        shift, wrap = stage.shift, stage.wrap
        kind = self.kinds.get(src, "plain")
        tag = f"TRANSFER shift={shift:+d} @{stage.placement}"
        if kind not in ("group",):
            # Stacked driver/server value: the shift is a local permutation.
            if kind == "server":
                self.assign(
                    out,
                    f"{src} | {self.label()} >> beam.Map("
                    f"lambda v: _stage_shift(v, {i}, {shift}, {wrap}))",
                    "server", tag,
                )
            else:
                self.assign(
                    out, f"_stage_shift({src}, {i}, {shift}, {wrap})",
                    "plain", tag,
                )
            self.bind(stage.eqn.outvars[0], out)
            return
        depth = self.depths.get(src, 1)
        tuple_keys = self.nested or depth > 1
        if tuple_keys:
            rekey = (
                f"lambda kv: (kv[0][:{i}] + ((kv[0][{i}] + {shift})"
                + (f" % {size}" if wrap else "")
                + f",) + kv[0][{i + 1}:], kv[1])"
            )
            in_range = f"lambda kv: 0 <= kv[0][{i}] < {size}"
        else:
            rekey = (
                f"lambda kv: ((kv[0] + {shift})"
                + (f" % {size}" if wrap else "")
                + ", kv[1])"
            )
            in_range = f"lambda kv: 0 <= kv[0] < {size}"
        if wrap:
            self.assign(
                out,
                f"{src} | {self.label()} >> beam.Map({rekey})",
                "group", f"{tag} (rotate stage keys)",
            )
        else:
            # Re-key each element to its destination stage, dropping the
            # ones that fall off the pipeline edge, and inject zero elements
            # for the vacated entry stages.
            moved = self.fresh("mv")
            self.assign(
                moved,
                f"{src} | {self.label()} >> beam.Map({rekey}) "
                f"| {self.label()} >> beam.Filter({in_range})",
                "group", f"{tag} (shift stage keys)",
            )
            aval = stage.eqn.outvars[0].aval
            elem_shape = tuple(aval.shape[depth:])
            zeros_expr = (
                f"np.zeros({elem_shape!r}, np.dtype({str(aval.dtype)!r}))"
            )
            if shift > 0:
                vac = f"range({min(shift, size)})"
            else:
                vac = f"range({max(size + shift, 0)}, {size})"
            if tuple_keys:
                sizes = tuple(self.plan.placement_sizes[:depth])
                keys = (
                    f"[k0 + (j,) + k1 for k0 in np.ndindex(*{sizes[:i]!r}) "
                    f"for j in {vac} "
                    f"for k1 in np.ndindex(*{sizes[i + 1:]!r})]"
                )
            else:
                keys = f"[j for j in {vac}]"
            zeros = self.fresh("zf")
            self.assign(
                zeros,
                f"p | {self.label()} >> beam.Create("
                f"[(k, {zeros_expr}) for k in {keys}])",
                "group", f"{tag} (zero-fill vacated stages)",
            )
            self.assign(
                out,
                f"({moved}, {zeros}) | {self.label()} >> beam.Flatten()",
                "group", tag,
            )
        self.depths[out] = depth
        self.bind(stage.eqn.outvars[0], out)

    def emit_local(self, stage: LocalCompute, plan, sname: str, outs):
        consts = plan.const_env()
        ins = [a for a in _stage_reads(stage) if a not in consts]
        in_names = [self.name_of(a, plan) for a in ins]
        raw = self.fresh("o")
        if stage.at_groups:
            self.emit_group_stage(sname, in_names, raw)
            k = self.depths.get(raw, 1)
            if k > 1:
                # the stage fn returned k leading singleton group axes —
                # strip all of them when projecting this group's element
                unwrap = repr((0,) * k)
                project = (
                    "lambda kv, _j={j}: (kv[0], kv[1][_j][" + unwrap + "])"
                )
            else:
                project = "lambda kv, _j={j}: (kv[0], kv[1][_j][0])"
        else:
            self.emit_server_stage(sname, in_names, raw)
            project = "lambda _t, _j={j}: _t[_j]"
        for j, o in enumerate(outs):
            name = self.fresh("t")
            if self.kinds[raw] == "plain":
                self.assign(name, f"{raw}[{j}]", "plain")
            else:
                self.assign(
                    name,
                    f"{raw} | {self.label()} >> "
                    f"beam.Map({project.format(j=j)})",
                    self.kinds[raw],
                )
                self.depths[name] = self.depths.get(raw, 1)
            self.bind(o, name)

    def emit_server_stage(self, sname: str, in_names: List[str], raw: str):
        kinds = [self.kinds.get(n, "plain") for n in in_names]
        if "server" not in kinds:
            # every input is a driver-side value: call the stage fn directly
            args = ", ".join(in_names)
            self.assign(
                raw, f"fns['{sname}']({args})", "plain",
                f"SERVER_COMPUTE {sname} (driver-side)",
            )
            return
        main_idx = kinds.index("server")
        params, extras = ["_v"], []
        exprs: List[str] = [""] * len(in_names)
        exprs[main_idx] = "_v"
        for i, (n, k) in enumerate(zip(in_names, kinds)):
            if i == main_idx:
                continue
            pname = f"_a{i}"
            params.append(pname)
            exprs[i] = pname
            extras.append(
                f"beam.pvalue.AsSingleton({n})" if k == "server" else n
            )
        lam = (
            f"lambda {', '.join(params)}: fns['{sname}']({', '.join(exprs)})"
        )
        extra = (", " + ", ".join(extras)) if extras else ""
        self.assign(
            raw,
            f"{in_names[main_idx]} | {self.label()} >> beam.Map({lam}{extra})",
            "server", f"SERVER_COMPUTE {sname}",
        )

    def emit_group_stage(self, sname: str, in_names: List[str], raw: str):
        kinds = [self.kinds.get(n, "plain") for n in in_names]
        gdepths = [
            self.depths.get(n, 1) if k == "group" else 0
            for n, k in zip(in_names, kinds)
        ]
        if self.nested or any(d > 1 for d in gdepths):
            self._emit_group_stage_nested(
                sname, in_names, kinds, gdepths, raw
            )
            return
        main = next(
            (n for n, k in zip(in_names, kinds) if k == "group"), None
        )
        if main is None:
            main = "groups"
        params, extras, exprs = ["kv"], [], []
        used_main = False
        for n, k in zip(in_names, kinds):
            if n == main and not used_main:
                used_main = True
                exprs.append("np.stack([kv[1]])")
            elif k == "group":
                pname = f"_d{len(params)}"
                params.append(pname)
                exprs.append(f"np.stack([{pname}[kv[0]]])")
                extras.append(f"beam.pvalue.AsDict({n})")
            elif k == "server":
                pname = f"_s{len(params)}"
                params.append(pname)
                exprs.append(pname)
                extras.append(f"beam.pvalue.AsSingleton({n})")
            else:  # side input object or plain value: pass through
                pname = f"_x{len(params)}"
                params.append(pname)
                exprs.append(pname)
                extras.append(n)
        lam = (
            f"lambda {', '.join(params)}: "
            f"(kv[0], fns['{sname}']({', '.join(exprs)}))"
        )
        extra = (", " + ", ".join(extras)) if extras else ""
        self.assign(
            raw,
            f"{main} | {self.label()} >> beam.Map({lam}{extra})",
            "group", f"GROUP_COMPUTE {sname} (per group)",
        )
        self.depths[raw] = 1

    def _emit_group_stage_nested(
        self, sname: str, in_names, kinds, gdepths, raw: str
    ):
        """Placement-path (tuple-keyed) variant of a group stage.

        The Map is keyed on the deepest group input; shallower group inputs
        are joined by their key *prefix* (kv[0][:depth]) — a pod-partitioned
        side value joins every client of that pod. Each group element is
        lifted to its own number of leading singleton group axes before the
        sliced (group-batched) stage fn sees it."""
        main, main_depth = None, 0
        for n, k, d in zip(in_names, kinds, gdepths):
            if k == "group" and d > main_depth:
                main, main_depth = n, d
        if main is None:
            main = "groups"
            main_depth = self.depths.get("groups", 1)
        params, extras, exprs = ["kv"], [], []
        used_main = False
        for n, k, d in zip(in_names, kinds, gdepths):
            if n == main and not used_main:
                used_main = True
                exprs.append(f"_lift(kv[1], {main_depth})")
            elif k == "group":
                pname = f"_d{len(params)}"
                params.append(pname)
                exprs.append(f"_lift({pname}[kv[0][:{d}]], {d})")
                extras.append(f"beam.pvalue.AsDict({n})")
            elif k == "server":
                pname = f"_s{len(params)}"
                params.append(pname)
                exprs.append(pname)
                extras.append(f"beam.pvalue.AsSingleton({n})")
            else:  # side input object or plain value: pass through
                pname = f"_x{len(params)}"
                params.append(pname)
                exprs.append(pname)
                extras.append(n)
        lam = (
            f"lambda {', '.join(params)}: "
            f"(kv[0], fns['{sname}']({', '.join(exprs)}))"
        )
        extra = (", " + ", ".join(extras)) if extras else ""
        self.assign(
            raw,
            f"{main} | {self.label()} >> beam.Map({lam}{extra})",
            "group", f"GROUP_COMPUTE {sname} (per placement path)",
        )
        self.depths[raw] = main_depth

    def emit_loop(self, stage: LoopStage, plan, path: str, outs):
        eqn = stage.eqn
        body = stage.body_plan
        loop_var = f"i{path.replace('_', '')}"
        if stage.loop_kind == "scan":
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            trip = stage.trip_count
            const_atoms = eqn.invars[:nc]
            carry_atoms = eqn.invars[nc : nc + ncar]
            xs_atoms = eqn.invars[nc + ncar :]
            carry_names = []
            for j, a in enumerate(carry_atoms):
                nm = self.fresh(f"carry{path}_")
                src = self.name_of(a, plan)
                self.assign(nm, src, self.kinds.get(src, "plain"),
                            f"loop {path} carry init")
                carry_names.append(nm)
            ys_names = []
            for j in range(len(eqn.outvars) - ncar):
                nm = self.fresh(f"ys{path}_")
                self.line(f"{nm} = []  # (iteration, value) pairs")
                self.kinds[nm] = "plain"
                ys_names.append(nm)
            iter_expr = (
                f"reversed(range({trip}))"
                if eqn.params.get("reverse", False)
                else f"range({trip})"
            )
            self.line(
                f"for {loop_var} in {iter_expr}:  "
                f"# LOOP[scan] {path}: one communication round per iteration"
            )
            self._indent += 1
            self._loop_vars.append(loop_var)
            binding_save = dict(self.names)
            # bind body invars: consts, carry, xs slices. Lambdas index with
            # a default arg (_i=loop_var) — Beam runs them after the
            # construction loop, when the loop variable holds its final value.
            for b, a in zip(body.jaxpr.jaxpr.invars[:nc], const_atoms):
                self.bind(b, self.name_of(a, plan))
            for b, nm in zip(
                body.jaxpr.jaxpr.invars[nc : nc + ncar], carry_names
            ):
                self.bind(b, nm)
            xs_binders = body.jaxpr.jaxpr.invars[nc + ncar :]
            xs_parts = body.partitioned_invars[nc + ncar :]
            for b, a, part in zip(xs_binders, xs_atoms, xs_parts):
                xs_name = self.name_of(a, plan)
                slice_nm = self.fresh("x")
                if self.kinds.get(xs_name) == "group":
                    self.assign(
                        slice_nm,
                        f"{xs_name} | {self.label()} >> beam.Map("
                        f"lambda kv, _i={loop_var}: (kv[0], kv[1][_i]))",
                        "group", "xs slice for this iteration",
                    )
                elif self.kinds.get(xs_name) == "server":
                    self.assign(
                        slice_nm,
                        f"{xs_name} | {self.label()} >> "
                        f"beam.Map(lambda v, _i={loop_var}: v[_i])",
                        "server", "xs slice for this iteration",
                    )
                else:
                    self.assign(
                        slice_nm, f"{xs_name}[{loop_var}]", "plain",
                        "xs slice for this iteration",
                    )
                # a slice that the body treats as partitioned must arrive as
                # a keyed per-group PCollection, not a stacked server value
                if part and self.kinds.get(slice_nm) != "group":
                    slice_nm = self.to_group(slice_nm)
                self.bind(b, slice_nm)
            # reconcile carry placement: body may expect partitioned carries
            for b, nm, part in zip(
                body.jaxpr.jaxpr.invars[nc : nc + ncar],
                carry_names,
                body.partitioned_invars[nc : nc + ncar],
            ):
                if part and self.kinds.get(self.names[b]) != "group":
                    self.bind(b, self.to_group(self.names[b]))
            self.emit_plan_stages(body, prefix=f"{path}_")
            new_carries = [self.name_of(a, body) for a in body.out_atoms[:ncar]]
            for nm, new in zip(carry_names, new_carries):
                self.assign(nm, new, self.kinds.get(new, "plain"),
                            "carry update")
            ys_kinds = []
            for nm, a in zip(ys_names, body.out_atoms[ncar:]):
                val = self.name_of(a, body)
                if self.kinds.get(val) == "group":
                    # a partitioned per-iteration output: collect the groups
                    # into one stacked (n, ...) server value before tagging
                    val = self.to_server(val)
                k = self.kinds.get(val, "plain")
                ys_kinds.append(k)
                if k == "server":
                    self.line(
                        f"{nm}.append({val} | {self.label()} >> "
                        f"beam.Map(lambda v, _i={loop_var}: (_i, v)))"
                    )
                else:
                    self.line(f"{nm}.append(({loop_var}, {val}))")
            self._loop_vars.pop()
            self._indent -= 1
            self.names = binding_save
            for o, nm in zip(eqn.outvars[:ncar], carry_names):
                if not _is_dropvar(o):
                    self.bind(o, nm)
            outs_set = set(outs)
            for o, nm, k in zip(eqn.outvars[ncar:], ys_names, ys_kinds):
                if _is_dropvar(o):
                    continue
                if o in outs_set and k == "server":
                    st = self.fresh("t")
                    self.assign(
                        st,
                        f"(tuple({nm}) | {self.label()} >> beam.Flatten() "
                        f"| {self.label()} >> beam.combiners.ToList() "
                        f"| {self.label()} >> beam.Map(lambda rows: "
                        f"np.stack([v for _, v in sorted(rows)])))",
                        "server", "stack per-iteration outputs",
                    )
                    self.bind(o, st)
                elif o in outs_set and k == "plain":
                    st = self.fresh("t")
                    self.assign(
                        st, f"np.stack([v for _, v in sorted({nm})])",
                        "plain", "stack per-iteration outputs",
                    )
                    self.bind(o, st)
                else:
                    self.bind(o, nm)
        else:  # while: Beam pipelines are static — driver must unroll
            cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
            body_consts = eqn.invars[cn : cn + bn]
            carry_atoms = eqn.invars[cn + bn :]
            carry_names = []
            for a in carry_atoms:
                nm = self.fresh(f"carry{path}_")
                src = self.name_of(a, plan)
                self.assign(nm, src, self.kinds.get(src, "plain"),
                            f"while {path} carry init")
                carry_names.append(nm)
            iters = f"num_iters_{path}"
            self.line(
                f"{iters} = 1  # LOOP[while] {path}: dynamic trip count — "
                f"resolve at driver time and rebuild"
            )
            self.line(f"for {loop_var} in range({iters}):")
            self._indent += 1
            self._loop_vars.append(loop_var)
            binding_save = dict(self.names)
            for b, a in zip(body.jaxpr.jaxpr.invars[:bn], body_consts):
                self.bind(b, self.name_of(a, plan))
            for b, nm in zip(body.jaxpr.jaxpr.invars[bn:], carry_names):
                self.bind(b, nm)
            self.emit_plan_stages(body, prefix=f"{path}_")
            new_carries = [self.name_of(a, body) for a in body.out_atoms]
            for nm, new in zip(carry_names, new_carries):
                self.assign(nm, new, self.kinds.get(new, "plain"),
                            "carry update")
            self._loop_vars.pop()
            self._indent -= 1
            self.names = binding_save
            for o, nm in zip(eqn.outvars, carry_names):
                if not _is_dropvar(o):
                    self.bind(o, nm)

    def emit_cond(self, stage: CondStage, plan, path: str):
        eqn = stage.eqn
        idx = self.name_of(eqn.invars[0], plan)
        self.line(
            f"# COND {path}: branch index lives in {idx}; a real driver "
            f"materializes it and builds one branch"
        )
        ops = eqn.invars[1:]
        branch_outs: List[List[str]] = []
        for b, bp in enumerate(stage.branch_plans):
            self.line(f"# -- branch {b} --")
            binding_save = dict(self.names)
            for binder, a in zip(bp.jaxpr.jaxpr.invars, ops):
                self.bind(binder, self.name_of(a, plan))
            self.emit_plan_stages(bp, prefix=f"{path}_b{b}_")
            branch_outs.append([self.name_of(a, bp) for a in bp.out_atoms])
            self.names = binding_save
        for j, o in enumerate(eqn.outvars):
            if _is_dropvar(o):
                continue
            nm = self.fresh("t")
            picks = ", ".join(outs[j] for outs in branch_outs)
            self.assign(
                nm, f"[{picks}][int(np.asarray({idx}))] "
                    f"if not isinstance({idx}, beam.pvalue.PCollection) "
                    f"else [{picks}][0]",
                self.kinds.get(branch_outs[0][j], "plain"),
                "cond output (select branch)",
            )
            self.bind(o, nm)


def _all_plans(plan: MapReducePlan):
    """Yield a plan and all its sub-plans, depth-first in stage order."""
    yield plan
    for s in plan.stages:
        if isinstance(s, LoopStage):
            if s.cond_plan is not None:
                yield from _all_plans(s.cond_plan)
            if s.body_plan is not None:
                yield from _all_plans(s.body_plan)
        elif isinstance(s, CondStage):
            for bp in s.branch_plans:
                yield from _all_plans(bp)


def _literal_src(val) -> str:
    arr = np.asarray(val)
    kind = arr.dtype.kind
    if arr.ndim == 0:
        if kind == "b":
            return str(bool(arr))
        if kind in "iu":
            return f"np.{arr.dtype}({int(arr)})"
        if kind == "f":
            return f"np.{arr.dtype}({float(arr)!r})"
        if kind == "c":
            return f"np.{arr.dtype}({complex(arr)!r})"
        # ml_dtypes scalars (bfloat16, float8_*): plain numpy has no such
        # constructor, so emit the nearest float32 value
        return f"np.float32({float(arr)!r})  # was {arr.dtype}"
    if kind in "bfciu":
        return f"np.asarray({arr.tolist()!r}, dtype=np.{arr.dtype})"
    return (
        f"np.asarray({np.asarray(arr, np.float32).tolist()!r}, "
        f"dtype=np.float32)  # was {arr.dtype}"
    )
