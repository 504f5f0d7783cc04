"""Device ms a round, for the worst device, in which a collective runs and
no other op does: the collective time that no compute hides.

A collective is an op whose opcode or instruction name begins with
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``
or ``all-to-all``. A synchronous one runs for its op's length; an async one
from its ``-start`` op's start to its ``-done`` op's end (paired in order,
per kind), the transfer between the two halves included."""

from benchmarks.chip import legs, trace

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def collective(event_name: str):
    """(kind, half) of a collective op event, half "start", "done" or ""
    (synchronous); None for any other op."""
    name, _, opcode = trace.short_name(event_name).partition(" ")
    for word in (opcode, legs.instruction(name)):
        for kind in COLLECTIVES:
            if word.startswith(kind):
                rest = word[len(kind):]
                half = next((h for h in ("start", "done")
                             if rest.startswith("-" + h)), "")
                return kind, half
    return None


def is_collective(event_name: str) -> bool:
    return collective(event_name) is not None


def running(ops):
    """(start, end) of every collective among one device's ops."""
    out, started = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        c = collective(o.name)
        if c is None:
            continue
        kind, half = c
        if half == "start":
            started.setdefault(kind, []).append(o)
        elif half == "done" and started.get(kind):
            out.append((started[kind].pop(0).start, o.end))
        else:
            out.append((o.start, o.end))
    out += [(o.start, o.end) for pending in started.values() for o in pending]
    return out


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    if not ctx["trace"].ops:
        return None
    exposed = []
    for ops in ctx["trace"].ops.values():
        rest = [o for o in ops if not is_collective(o.name)]
        exposed.append(trace.total(trace.subtract(
            trace.union(trace.clip(running(ops), lo, hi)),
            trace.busy(rest, lo, hi))))
    return max(exposed) / ctx["rounds"] / 1e6
