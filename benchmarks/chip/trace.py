"""Reduce a profiler trace (``.xplane.pb``) to device intervals.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op (nested ops are merged by the interval union).
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation`` names
(``sample``, ``dispatch``, ``wait``, ``readback``) on the host plane. All
times are nanoseconds on the trace's clock.

    python -m benchmarks.chip.trace <file.xplane.pb>   # print its layout
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPANS = ("sample", "dispatch", "wait", "readback")
# The opcode in an HLO instruction's text: the first lowercase word followed
# by "(" after a space (layouts such as T(8,128) follow a colon).
OPCODE = re.compile(r" ([a-z][a-z0-9_.-]*)\(")


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """Device ops by device id, and the benchmark's host spans."""

    ops: dict
    spans: list

    def window(self):
        """(start, end) of the traced window: from the first host span to
        the end of the last."""
        if not self.spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


def union(intervals):
    """Merged, sorted (start, end) pairs covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """Parts of the merged ``intervals`` not covered by the merged
    ``cover``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(ops, lo, hi):
    """Union of the op intervals inside [lo, hi]."""
    return union(clip([(o.start, o.end) for o in ops], lo, hi))


def idle_gaps(ops, lo, hi):
    """(start, end) of the stretches of [lo, hi] in which no op runs."""
    return subtract([(lo, hi)], busy(ops, lo, hi))


def label(gap, spans) -> str:
    """The host span that overlaps ``gap`` most, or "no span"."""
    best, name = 0.0, "no span"
    for s in spans:
        ov = min(gap[1], s.end) - max(gap[0], s.start)
        if ov > best:
            best, name = ov, s.name
    return name


def short_name(name: str) -> str:
    """An op event's name is its whole HLO instruction; keep the
    instruction's name and opcode."""
    head, _, rest = name.partition(" = ")
    m = OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def op_seconds(trace: Trace, lo, hi) -> dict:
    """Seconds per op (:func:`short_name`) inside [lo, hi], averaged over
    the devices."""
    acc = collections.Counter()
    for ops in trace.ops.values():
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                acc[short_name(o.name)] += d
    n = max(len(trace.ops), 1)
    return {k: v / n / 1e9 for k, v in acc.items()}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {log_dir}, got {paths}")
    return paths[0]


def leaves(ops):
    """The ops of one line that hold no other op: a loop or call op whose
    body's ops are on the same line would count its time twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= o.end]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = leaves(
                        Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append(Op(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return Trace(ops=ops, spans=spans)


def describe(path: str, top: int = 12) -> None:
    """Print the planes, lines and busiest events of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            busiest = collections.Counter()
            for e in events:
                busiest[e.name] += e.duration_ns
            for name, ns in busiest.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms  {name}")
            if events:
                e = events[len(events) // 2]
                stats = {k: str(v)[:160] for k, v in e.stats}
                print(f"    sample event {e.name!r} at {e.start_ns}: {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
