"""The trace reduction and the per-layer metric readers on known intervals,
and on a stretch of a trace recorded on the chip."""

from __future__ import annotations

import pytest

from tiny import files
from benchmarks.chip import run, trace
from benchmarks.chip.trace import Op, Trace


def test_union_merges_overlaps_and_sorts():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_subtract_leaves_uncovered_parts():
    got = trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)])
    assert got == [(0, 2), (4, 8), (22, 25), (26, 30)]


def test_leaves_drop_ops_that_hold_others():
    ops = [Op("while", 0, 10), Op("fusion.1", 1, 4), Op("fusion.2", 5, 9),
           Op("copy", 12, 13)]
    assert [o.name for o in trace.leaves(ops)] == ["fusion.1", "fusion.2",
                                                   "copy"]


def test_busy_and_idle_gaps_clip_to_the_window():
    ops = [Op("a", -5, 2), Op("b", 1, 3), Op("c", 6, 8), Op("d", 9, 20)]
    assert trace.busy(ops, 0, 10) == [(0, 3), (6, 8), (9, 10)]
    assert trace.idle_gaps(ops, 0, 10) == [(3, 6), (8, 9)]


def test_label_takes_the_span_that_overlaps_most():
    spans = [Op("dispatch", 0, 4), Op("wait", 4, 20)]
    assert trace.label((2, 8), spans) == "wait"
    assert trace.label((30, 31), spans) == "no span"


def _ctx(ops_by_device, rounds=2, **extra):
    spans = [Op("sample", 0, 1), Op("readback", 99, 100)]
    tr = Trace(ops=ops_by_device, spans=spans)
    lo, hi = tr.window()
    cell = files("lm_1b", "local_sgd.c2h4")
    ctx = {"trace": tr, "lo": lo, "hi": hi, "rounds": rounds,
           "tokens_per_s": 1.0, "chips": len(ops_by_device),
           "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "config": cell["config"], "traffic": cell["traffic"]}
    ctx.update(extra)
    return ctx


def test_idle_pct_takes_the_worst_device():
    ctx = _ctx({0: [Op("f", 0, 90)], 1: [Op("f", 0, 60)]})
    assert run.load_module("metrics", "device_idle_pct").read(ctx) == 40.0


def test_mfu_from_tokens_per_second():
    from benchmarks.chip import flops

    cell = files("lm_1b", "local_sgd.c2h4")
    per_token = flops.flops_per_token(cell["config"], 512)
    ctx = _ctx({0: []}, tokens_per_s=197e12 / per_token / 2,
               config=cell["config"], traffic=cell["traffic"])
    ctx["chips"] = 1
    assert run.load_module("metrics", "mfu").read(ctx) == pytest.approx(50.0)


def _recorded(name):
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data", name)
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    ops = {int(d): trace.leaves(Op(*o) for o in v)
           for d, v in rec["ops"].items()}
    return Trace(ops=ops, spans=[Op(*s) for s in rec["spans"]]), rec


def _busy_by_bins(ops, lo, hi, step=1000.0):
    """Busy nanoseconds counted on a grid of ``step`` ns: a second way to
    the same number."""
    import numpy as np

    n = int((hi - lo) // step)
    hit = np.zeros(n, bool)
    for o in ops:
        a = max(int((o.start - lo) // step), 0)
        b = min(int(-(-(o.end - lo) // step)), n)
        hit[a:b] = True
    return hit.sum() * step


def test_recorded_round_boundary():
    """One chip, the end of one lm_1b round and the start of the next: the
    device idles while the host waits for the result, reads the loss back,
    samples and dispatches the next round."""
    tr, rec = _recorded("lm_1b_round_boundary.json.gz")
    lo, hi = rec["lo"], rec["hi"]
    ops = tr.ops[0]
    busy = trace.total(trace.busy(ops, lo, hi))
    assert busy == pytest.approx(_busy_by_bins(ops, lo, hi), abs=2e5)
    gaps = trace.idle_gaps(ops, lo, hi)
    assert trace.total(gaps) == pytest.approx(hi - lo - busy)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert 3.0e6 < longest[1] - longest[0] < 4.5e6
    assert trace.label(longest, tr.spans) == "wait"
