"""Device time by leg of the round, and device idle inside and between rounds.

The program binds every leg of a DrJAX round under a ``jax.named_scope``:
``drjax.<op>[<placement>]`` for the building blocks, ``client_step``,
``clip``, ``client_opt``, ``client_delta`` and ``server_update`` for the
round's own legs. JAX adds ``transpose(`` to the backward pass and
``checkpoint/rematted_computation`` to the recomputed forward. The scopes
reach the compiled program as each HLO instruction's ``op_name``; a trace's
op event names only the instruction, so the readers map instruction to
``op_name`` through the compiled round's text (:func:`op_names`). A fused
op belongs to the leg of its root's ``op_name``.

A round's ops, on one device, are those that start between its ``sample``
span's start and the next round's; its extent runs from the first of them
to the end of the last. (Not from its ``dispatch`` span: the device's and
the host's clocks in a trace disagree by a tenth of a millisecond or more,
and the recorded round boundary has a round's first op start 0.107 ms
before its ``dispatch`` span, while ``sample`` starts about 0.7 ms before
``dispatch`` with the device idle.) Idle inside the extents is the
program's (``step_idle_ms``); idle outside them is the host's, while it
waits for the result, reads it back, samples and dispatches
(``host_idle_ms``). Every reading is device milliseconds per completed
round of the traced window, for the worst device.
"""

from __future__ import annotations

import bisect
import collections
import re
import sys

from benchmarks.chip import trace

# Leg -> the metric that reads it.
METRICS = {"forward": "client_fwd_ms", "backward": "client_bwd_ms",
           "remat": "remat_ms", "client_opt": "client_opt_ms",
           "aggregate": "aggregate_ms"}
# (substrings of an op_name, leg), first match wins.
RULES = (
    (("server_update", "drjax.reduce", "drjax.compress", "drjax.broadcast",
      "client_delta"), "aggregate"),
    (("clip", "client_opt"), "client_opt"),
    (("rematted_computation",), "remat"),
    (("transpose(",), "backward"),
    (("client_step",), "forward"),
)
# An op_name holding none of these predates the program's scopes.
PROGRAM_SCOPES = ("drjax.", "client_step", "server_update")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")


def leg(op_name: str) -> str:
    """The leg of the round an instruction with ``op_name`` belongs to."""
    for marks, name in RULES:
        if any(m in op_name for m in marks):
            return name
    return "other"


def op_names(hlo_text: str) -> dict:
    """Instruction name -> the ``op_name`` it is read by, from a compiled
    module's text.

    A fusion without metadata of its own takes its fused computation's
    root's, or, where the root has none, the last ``op_name`` in it. An
    instruction whose ``op_name`` belongs to no leg, or that has none (the
    copies, async slices and ``ConcatBitcast``s the compiler adds to move
    data between memories; the zeros JAX makes outside every scope for the
    backward pass's accumulators) takes the ``op_name`` of the nearest
    instruction that uses its result and belongs to a leg, else of the
    nearest one it reads that does: the leg that waits for it."""
    own, calls, last_in, root_of, reads = {}, {}, {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            own[name] = op.group(1)
            last_in[computation] = op.group(1)
        if line.lstrip().startswith("ROOT "):
            root_of[computation] = name
        call = _CALLS.search(rest)
        if call and " fusion(" in rest:
            calls[name] = call.group(1)
        reads[name] = _REF.findall(rest.partition("metadata=")[0])
    for name, comp in calls.items():
        if name not in own:
            found = own.get(root_of.get(comp), last_in.get(comp))
            if found is not None:
                own[name] = found
    users = collections.defaultdict(list)
    for name, operands in reads.items():
        for operand in operands:
            users[operand].append(name)
    out = dict(own)
    for name in reads:
        if leg(own.get(name, "")) == "other":
            found = (_nearest(name, users, own)
                     or _nearest(name, reads, own))
            if found is not None:
                out[name] = found
    return out


def _nearest(start, edges, named):
    """The ``op_name`` of the nearest instruction from ``start`` along
    ``edges`` whose ``op_name`` belongs to a leg, or None."""
    seen, frontier = {start}, [start]
    while frontier:
        step = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m in seen:
                    continue
                seen.add(m)
                if m in named and leg(named[m]) != "other":
                    return named[m]
                step.append(m)
        frontier = step
    return None


def instruction(event_name: str) -> str:
    """The HLO instruction name of an op event (named ``%name = ...`` or
    ``name``)."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def extents(ops, starts, lo, hi):
    """(start, end) of each round's ops on one device inside [lo, hi], the
    rounds starting at ``starts``; ops before the first start belong to a
    round that started before ``lo``."""
    bounds = [lo] + sorted(d for d in starts if lo < d < hi) + [hi]
    out = []
    for a, b in zip(bounds, bounds[1:]):
        mine = [o for o in ops if o.end > lo and a <= max(o.start, lo) < b]
        if mine:
            s = max(min(o.start for o in mine), lo)
            e = min(max(o.end for o in mine), hi)
            if e > s:
                out.append((s, e))
    return out


def idle_split(ops, starts, lo, hi):
    """(idle inside the rounds' extents, idle outside them, the in-step
    gaps), nanoseconds, on one device; the two sum to the window's idle."""
    busy = trace.busy(ops, lo, hi)
    inside = trace.subtract(trace.union(extents(ops, starts, lo, hi)), busy)
    step = trace.total(inside)
    return step, trace.total(trace.subtract([(lo, hi)], busy)) - step, inside


def leg_busy(ops, names: dict, lo, hi) -> dict:
    """Nanoseconds by leg (and ``other``) on one device: the union of the
    leg's op intervals inside [lo, hi]."""
    by_leg = collections.defaultdict(list)
    for o in ops:
        by_leg[leg(names.get(instruction(o.name), ""))].append(
            (o.start, o.end))
    return {k: trace.total(trace.union(trace.clip(v, lo, hi)))
            for k, v in by_leg.items()}


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _ms(ns: float, rounds: int) -> float:
    return ns / rounds / 1e6


def _report(tr, names, lo, hi, rounds, starts, legs_ns) -> None:
    """Leg shares of busy time and the in-step gaps, on stderr."""
    for d, by_leg in sorted(legs_ns.items()):
        busy = sum(by_leg.values())
        _log(f"device {d} legs, share of busy: " + ", ".join(
            f"{k} {100 * v / busy:.2f}%" for k, v in sorted(
                by_leg.items(), key=lambda kv: -kv[1])))
    ops = sorted(tr.ops[min(tr.ops)], key=lambda o: o.start)
    other = collections.Counter()
    for o in ops:
        name = instruction(o.name)
        if leg(names.get(name, "")) == "other":
            other[name] += max(min(o.end, hi) - max(o.start, lo), 0)
    _log("busiest ops of no leg, ms a round: " + ", ".join(
        f"{n} {_ms(v, rounds):.3f} ({names.get(n, 'no op_name')[-60:]})"
        for n, v in other.most_common(8)))
    op_starts = [o.start for o in ops]
    _, _, inside = idle_split(ops, starts, lo, hi)
    per_leg, per_round = collections.Counter(), collections.defaultdict(list)
    bounds = sorted(starts)
    for s, e in inside:
        nxt = ops[bisect.bisect_left(op_starts, e)] if e < hi else None
        name = instruction(nxt.name) if nxt else "window end"
        which = leg(names.get(name, "")) if nxt else "other"
        per_leg[which] += e - s
        per_round[bisect.bisect_right(bounds, s)].append((e - s, name, which))
    _log("in-step idle by the leg of the op that ends each gap, ms a round: "
         + ", ".join(f"{k} {_ms(v, rounds):.3f}"
                     for k, v in per_leg.most_common()))
    for r, gaps in sorted(per_round.items()):
        top = sorted(gaps, reverse=True)[:5]
        _log(f"round {r} largest in-step gaps, ms: " + ", ".join(
            f"{g / 1e6:.3f} before {n} ({w})" for g, n, w in top))


def readings(ctx) -> dict:
    """Every reading of this module for ``ctx``, computed once and kept in
    ``ctx["legs"]``: each leg's ``<metric>`` in ms a round, or None where
    the program binds none of the scopes, and the two idle readings.
    ``ctx["op_names"]`` is :func:`op_names` of the timed executable's
    text."""
    if "legs" in ctx:
        return ctx["legs"]
    tr, lo, hi, rounds = ctx["trace"], ctx["lo"], ctx["hi"], ctx["rounds"]
    starts = [s.start for s in tr.spans if s.name == "sample"]
    out = dict.fromkeys(METRICS.values())
    out.update(step_idle_ms=None, host_idle_ms=None)
    if tr.ops and starts:
        split = [idle_split(ops, starts, lo, hi)[:2]
                 for ops in tr.ops.values()]
        out["step_idle_ms"] = _ms(max(s for s, _ in split), rounds)
        out["host_idle_ms"] = _ms(max(h for _, h in split), rounds)
    names = ctx["op_names"]
    if tr.ops and any(m in n for n in names.values() for m in PROGRAM_SCOPES):
        legs_ns = {d: leg_busy(ops, names, lo, hi)
                   for d, ops in tr.ops.items()}
        for name, metric in METRICS.items():
            out[metric] = _ms(max(b.get(name, 0.0) for b in legs_ns.values()),
                              rounds)
        if starts:
            _report(tr, names, lo, hi, rounds, starts, legs_ns)
    ctx["legs"] = out
    return out

