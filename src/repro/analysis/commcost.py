"""Communication-cost pass: per-stage wire bytes derived from the plan IR.

Replaces the napkin ``cross_pod_bytes`` spreadsheet model with numbers read
off the IR itself. For every Broadcast/Reduce/Transfer stage the pass
derives, from the eqn's operand/output avals and its placement params:

* the **link**: the eqn's addressed stack index splits the fabric — level 0
  (outermost, e.g. ``pods``) crosses the slow DCN leg, deeper levels ride
  ICI within a pod;
* the **endpoint count**: a reduce at index i collects from
  ``prod(shape[:i+1])`` groups, a broadcast at index i fans out to
  ``prod(shape[:i+1])`` destinations;
* the **per-endpoint payload** in actual wire format: a reduce tagged
  ``compress="int8"`` (the fused reduce+compress fast path) marks its
  output as int8-on-the-wire, so the next comm stage over that value
  counts ``1 byte/value + one f32 scale per PACK_COLS(=256)-block`` —
  exactly the packed wire format ``repro.compression`` ships — instead of
  the f32 nbytes. On a mesh the executable sends that format: the reduce
  after the fused one is tagged ``wire="int8"`` and exchanges the int8
  payload and its scales (``core.primitives._int8_wire_mean``). The count
  is one copy per pod; every device of a pod holds the pod's partial and
  sends it, so a pod of ``d`` devices puts ``d`` times the count on the
  wire. (The
  *unfused* roundtrip materializes f32 in the IR, so
  the IR-derived cost is honestly f32 there: compression that is invisible
  in the IR is invisible to a static pass.)

Loop stages multiply their body's (and ``while`` predicate's) costs by the
trip count; a data-dependent ``while`` counts one trip and raises an
``unknown-trip`` flag. Cond stages contribute their *most expensive*
branch to the totals (a static upper bound); every branch's stages are
still itemized, with ``counted=False`` on the losers.

:func:`cross_validate` closes the loop against the compiled program: each
plain (uncompressed) Reduce eqn is jitted standalone and its modeled
operand+output bytes compared with ``compat.cost_analysis``'s
parameter-0 accounting (``bytes accessed0{}``), within a tolerance. The
``model_scale`` knob exists for fault injection in tests — scaling the
model away from 1.0 must produce a mismatch finding, proving the check
can actually fail.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import interpreter as interp
from repro.core.interpreter import (
    Broadcast,
    CondStage,
    LoopStage,
    Reduce,
    Transfer,
    _eqn_placement,
    _is_dropvar,
    _is_literal,
)

from .findings import Finding

# One f32 scale per this many int8 values (repro.compression.PACK_COLS);
# duplicated as a plain int so the cost pass stays importable without the
# compression stack, and pinned to it in tests/test_analysis.py.
INT8_BLOCK = 256


def int8_wire_payload(values: int, block: int = INT8_BLOCK) -> float:
    """Wire bytes of ``values`` f32 numbers in the packed int8 format."""
    return values * 1.0 + math.ceil(values / block) * 4.0


@dataclasses.dataclass
class CommStageCost:
    stage: str  # named_stages anchor
    kind: str  # BROADCAST | REDUCE | TRANSFER
    op: str  # broadcast | reduce_sum | reduce_mean | reduce_max
    placement: str  # addressed placement name
    link: str  # "dcn" (outermost level) | "ici" (inner levels)
    endpoints: int  # senders (reduce) / receivers (broadcast)
    payload_bytes: float  # per-endpoint wire payload
    wire_format: str  # "native" | "int8+scales"
    multiplier: float  # loop-trip multiplier applied
    wire_bytes: float  # endpoints * payload * multiplier
    counted: bool = True  # False: a non-max cond branch (itemized only)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CommCostReport:
    per_stage: List[CommStageCost]
    dcn_bytes: float
    ici_bytes: float
    unknown_trips: bool
    findings: List[Finding] = dataclasses.field(default_factory=list)

    @property
    def total_bytes(self) -> float:
        return self.dcn_bytes + self.ici_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "dcn_bytes": self.dcn_bytes,
            "ici_bytes": self.ici_bytes,
            "total_bytes": self.total_bytes,
            "unknown_trips": self.unknown_trips,
            "per_stage": [c.to_dict() for c in self.per_stage],
        }


def _nbytes(aval, start: int = 0) -> Tuple[int, float]:
    """(element count, native bytes) of ``aval.shape[start:]``."""
    values = int(np.prod(aval.shape[start:], dtype=np.int64))
    return values, values * np.dtype(aval.dtype).itemsize


def estimate_comm_cost(plan) -> CommCostReport:
    """Static per-stage wire bytes for a plan (recursive, trip-multiplied)."""
    per_stage: List[CommStageCost] = []
    findings: List[Finding] = []
    state = {"unknown": False}
    dcn, ici = _walk(plan, "", 1.0, True, per_stage, findings, state)
    return CommCostReport(
        per_stage=per_stage,
        dcn_bytes=dcn,
        ici_bytes=ici,
        unknown_trips=state["unknown"],
        findings=findings,
    )


def _walk(
    plan, prefix: str, mult: float, counted: bool,
    per_stage: List[CommStageCost], findings: List[Finding], state,
) -> Tuple[float, float]:
    dcn = ici = 0.0
    # wire format of values within THIS plan: outputs of compress-tagged
    # reduces are int8+scales until local compute touches them again.
    fmt: Dict[Any, str] = {}
    for idx, stage in enumerate(plan.stages):
        sname = f"stage_{prefix}{idx}"
        if isinstance(stage, (Broadcast, Reduce, Transfer)):
            cost = _comm_cost(stage, sname, mult, counted, fmt)
            per_stage.append(cost)
            if cost.counted:
                if cost.link == "dcn":
                    dcn += cost.wire_bytes
                else:
                    ici += cost.wire_bytes
        elif isinstance(stage, LoopStage):
            trip = stage.trip_count
            if trip is None:
                state["unknown"] = True
                findings.append(Finding(
                    "commcost/unknown-trip", "info",
                    "while-loop trip count is data-dependent; its body and "
                    "predicate are counted once (scale externally by the "
                    "expected iteration count)",
                    stage=sname,
                ))
                m2 = mult
            else:
                m2 = mult * trip
            if stage.cond_plan is not None:
                d, i = _walk(
                    stage.cond_plan, f"{prefix}{idx}_c_", m2, counted,
                    per_stage, findings, state,
                )
                dcn += d
                ici += i
            d, i = _walk(
                stage.body_plan, f"{prefix}{idx}_", m2, counted,
                per_stage, findings, state,
            )
            dcn += d
            ici += i
        elif isinstance(stage, CondStage):
            branch_totals = []
            marks = []
            for b, bp in enumerate(stage.branch_plans):
                start = len(per_stage)
                d, i = _walk(
                    bp, f"{prefix}{idx}_b{b}_", mult, counted,
                    per_stage, findings, state,
                )
                branch_totals.append((d, i))
                marks.append((start, len(per_stage)))
            if branch_totals:
                best = max(
                    range(len(branch_totals)),
                    key=lambda b: sum(branch_totals[b]),
                )
                dcn += branch_totals[best][0]
                ici += branch_totals[best][1]
                for b, (lo, hi) in enumerate(marks):
                    if b != best:
                        for c in per_stage[lo:hi]:
                            c.counted = False
    return dcn, ici


def _comm_cost(stage, sname: str, mult: float, counted: bool, fmt) -> CommStageCost:
    eqn = stage.eqn
    enames, i = _eqn_placement(eqn)
    link = "dcn" if i == 0 else "ici"
    if isinstance(stage, Transfer):
        # Stage-to-stage activation hand-off: each stage ships its slot to
        # its neighbor over ICI (the collective-permute the lowering emits),
        # regardless of where the stage level sits in the stack. Non-wrap
        # boundary stages send nothing (their payload is zero-filled
        # locally), so |shift| stages per outer group drop out of the
        # endpoint count; a wrap (ring) transfer keeps every stage busy.
        aval = eqn.invars[0].aval
        size = aval.shape[i]
        shift = abs(int(eqn.params.get("shift", 1)))
        wrap = bool(eqn.params.get("wrap", False))
        outer = int(np.prod(aval.shape[:i], dtype=np.int64))
        senders = size if wrap else max(size - min(shift, size), 0)
        endpoints = outer * senders
        _values, native = _nbytes(aval, i + 1)
        return CommStageCost(
            stage=sname,
            kind="TRANSFER",
            op="stage_transfer",
            placement=stage.placement,
            link="ici",
            endpoints=endpoints,
            payload_bytes=float(native),
            wire_format="native",
            multiplier=mult,
            wire_bytes=endpoints * float(native) * mult,
            counted=counted,
        )
    if isinstance(stage, Reduce):
        aval = eqn.invars[0].aval
        endpoints = int(np.prod(aval.shape[: i + 1], dtype=np.int64))
        values, native = _nbytes(aval, i + 1)
        operand = eqn.invars[0]
        wire_format = (
            "int8+scales"
            if not _is_literal(operand) and fmt.get(operand) == "int8+scales"
            else "native"
        )
        payload = (
            int8_wire_payload(values)
            if wire_format == "int8+scales"
            else float(native)
        )
        out_fmt = (
            "int8+scales"
            if eqn.params.get("compress") == "int8"
            else None
        )
        for o in eqn.outvars:
            if not _is_dropvar(o) and out_fmt:
                fmt[o] = out_fmt
        kind, op = "REDUCE", stage.op
    else:  # Broadcast
        aval = eqn.outvars[0].aval
        endpoints = int(np.prod(aval.shape[: i + 1], dtype=np.int64))
        values, native = _nbytes(aval, i + 1)
        operand = eqn.invars[0]
        wire_format = (
            "int8+scales"
            if not _is_literal(operand) and fmt.get(operand) == "int8+scales"
            else "native"
        )
        payload = (
            int8_wire_payload(values)
            if wire_format == "int8+scales"
            else float(native)
        )
        kind, op = "BROADCAST", "broadcast"
    return CommStageCost(
        stage=sname,
        kind=kind,
        op=op,
        placement=stage.placement,
        link=link,
        endpoints=endpoints,
        payload_bytes=payload,
        wire_format=wire_format,
        multiplier=mult,
        wire_bytes=endpoints * payload * mult,
        counted=counted,
    )


def cross_validate(
    plan, *, tol: float = 0.05, model_scale: float = 1.0,
) -> List[Finding]:
    """Check the modeled geometry against the compiled program's costs.

    Every plain (uncompressed) Reduce eqn is jitted standalone; the XLA
    cost model's operand bytes of parameter 0 plus its output bytes must
    match the modeled ``endpoints * payload + output nbytes`` within
    ``tol``. Compressed and ``wire``-tagged reduces are excluded — their
    lowering contains quantization machinery whose memory accounting is not
    a wire model (they are pinned against the packed wire format math in
    tests instead). ``model_scale`` multiplies the modeled side; anything
    but 1.0 is fault injection for testing the check itself.

    Emits ``commcost/model-mismatch`` (error) per failing stage, or one
    ``commcost/no-cost-model`` (info) when the backend reports no costs.
    """
    import jax
    import jax.numpy as jnp

    from repro import compat

    findings: List[Finding] = []
    candidates = 0
    saw_cost_model = False
    for name, stage, _owner in plan.named_stages():
        if not isinstance(stage, Reduce):
            continue
        if (stage.eqn.params.get("compress") is not None
                or stage.eqn.params.get("wire") is not None):
            continue
        eqn = stage.eqn
        aval = eqn.invars[0].aval
        out_aval = eqn.outvars[0].aval
        prim = eqn.primitive
        subfuns, bind_params = prim.get_bind_params(dict(eqn.params))

        def fn(v, _subfuns=subfuns, _prim=prim, _params=bind_params):
            return _prim.bind(*_subfuns, v, **_params)

        x = jnp.zeros(aval.shape, aval.dtype)
        compiled = jax.jit(fn).lower(x).compile()
        cost = compat.cost_analysis(compiled)
        candidates += 1
        operand = cost.get("bytes accessed0{}")
        if operand is None:
            continue
        measured = operand + cost.get("bytes accessedout{}", 0.0)
        saw_cost_model = True
        _, in_bytes = _nbytes(aval)
        _, out_bytes = _nbytes(out_aval)
        modeled = (in_bytes + out_bytes) * model_scale
        rel = abs(modeled - float(measured)) / max(float(measured), 1.0)
        if rel > tol:
            findings.append(Finding(
                "commcost/model-mismatch", "error",
                f"{stage.op}@{stage.placement}: modeled "
                f"{modeled:.0f} bytes vs {float(measured):.0f} from "
                f"compat.cost_analysis ({rel * 100:.1f}% off, tolerance "
                f"{tol * 100:.0f}%)",
                stage=name,
            ))
    if candidates and not saw_cost_model:
        findings.append(Finding(
            "commcost/no-cost-model", "info",
            "backend reports no cost model; comm-cost cross-validation "
            "skipped",
        ))
    return findings
