"""The per-leg readers: legs of known op names, the split of device idle
between the rounds' extents and the host, and the readings on known
intervals and on a stretch of a trace recorded on the chip."""

from __future__ import annotations

import gc

import jax
import pytest

from tiny import files, tiny_cell
from benchmarks.chip import flops, legs, run, trace, weights
from benchmarks.chip.trace import Op, Trace
from test_trace import _recorded

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# op_names from the lm_1b round compiled on the CPU (full remat).
MAP = "jit(round_fn)/drjax.map[clients]/vmap()/while/body/closed_call"
LAYER = f"{MAP}/client_step/transpose(jvp())/while/body/closed_call/checkpoint"
NAMED = {
    f"{LAYER}/rematted_computation/bsd,df->bsf/dot_general": "remat",
    f"{LAYER}/bsd,df->bsf/convert_element_type": "backward",
    f"{MAP}/client_step/jvp()/while/body/closed_call/closed_call/while/body/"
    "closed_call/bqhgk,bkhd->bqhgd/dot_general": "forward",
    f"{MAP}/clip/convert_element_type": "client_opt",
    f"{MAP}/client_opt/convert_element_type": "client_opt",
    "jit(round_fn)/drjax.map[clients]/vmap(client_delta)/sub": "aggregate",
    "jit(round_fn)/drjax.broadcast[clients]/broadcast_in_dim": "aggregate",
    "jit(round_fn)/drjax.reduce_mean[clients]/reduce_sum": "aggregate",
    "jit(round_fn)/drjax.reduce_compress[clients]/reduce_mean": "aggregate",
    "jit(round_fn)/drjax.compress[clients]/round": "aggregate",
    "jit(round_fn)/server_update/convert_element_type": "aggregate",
    f"{MAP}/sin": "other",
}


@pytest.mark.parametrize("op_name,leg", sorted(NAMED.items()))
def test_leg_of_known_op_names(op_name, leg):
    assert legs.leg(op_name) == leg


HLO = """\
HloModule m

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/server_update/mul"}
}

%fused_b (p: f32[4]) -> (f32[4], f32[4]) {
  %p.1 = f32[4]{0} parameter(0)
  %neg.2 = f32[4]{0} negate(%p.1), metadata={op_name="jit(f)/client_opt/neg"}
  ROOT %tuple.3 = (f32[4]{0}, f32[4]{0}) tuple(%neg.2, %p.1)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a
  %fusion.8 = (f32[4]{0}, f32[4]{0}) fusion(%x), kind=kLoop, calls=%fused_b
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %sub.10 = f32[4]{0} subtract(%copy-done.1, %x), metadata={op_name="x"}
  %add.9 = f32[4]{0} add(%sub.10, %fusion.7), metadata={op_name="jit(f)/drjax.reduce_sum[clients]/add" source_line=3}
  %copy.11 = f32[4]{0} copy(%add.9)
  %copy.12 = f32[4]{0} copy(%x)
  ROOT %tuple.13 = (f32[4]{0}, f32[4]{0}) tuple(%copy.11, %copy.12)
}
"""


def test_op_names_take_a_fusions_root():
    names = legs.op_names(HLO)
    assert names["fusion.7"] == "jit(f)/server_update/mul"
    assert names["fusion.8"] == "jit(f)/client_opt/neg"
    assert names["add.9"] == "jit(f)/drjax.reduce_sum[clients]/add"


def test_op_names_give_what_no_leg_names_the_leg_that_waits_for_it():
    """A copy the compiler added, or an op whose op_name belongs to no leg
    (``sub.10``), takes the nearest user of its result that belongs to a
    leg, else the nearest such instruction it reads, else keeps its own."""
    names = legs.op_names(HLO)
    reduce_sum = "jit(f)/drjax.reduce_sum[clients]/add"
    assert names["copy-start.1"] == names["copy-done.1"] == reduce_sum
    assert names["sub.10"] == reduce_sum
    assert names["copy.11"] == names["tuple.13"] == reduce_sum
    assert names["x"] == "jit(f)/server_update/mul"
    assert "copy.12" not in names


def test_instruction_of_an_op_event():
    assert legs.instruction(
        "%fusion.308 = bf16[10,8192]{1,0} fusion(f32[10,8192] %x)") \
        == "fusion.308"
    assert legs.instruction("copy-done.56") == "copy-done.56"


@pytest.fixture(scope="module")
def traced_run():
    """A tiny traced run of the cell, with a stand-in trace of one op (a
    CPU's trace has no TPU ops): the cell, the text of the executable it
    timed, and the ``op_names`` it handed the readers."""
    cell = tiny_cell("lm_1b.local_sgd.c2h4")
    timed, handed = [], []
    readings = legs.readings

    def record(ctx):
        handed.append(ctx["op_names"])
        return readings(ctx)

    stand_in = Trace(ops={0: [Op("%x.1 = f", 1, 2)]},
                     spans=[Op("sample", 0, 1), Op("wait", 2, 3)])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "find_xplane", lambda _: None)
        mp.setattr(trace, "load", lambda _: stand_in)
        mp.setattr(flops, "peaks", lambda _: PEAK)
        mp.setattr(legs, "readings", record)
        try:
            run.run_cell(cell, 2**33 + 5, 0.1, True, jax.devices()[:1],
                         break_step=lambda step: timed.append(step) or step)
        finally:
            gc.unfreeze()
    return cell, timed[0].as_text(), handed[0]


def _compiled_again(cell) -> str:
    """The cell's round built and compiled a second time, from shapes, as
    the readers did after the window before the run handed them the timed
    executable's names."""
    c, t = cell["config"], cell["traffic"]
    rnd = run.round_kind(t).build(c, t, jax.devices()[:cell["chips"]])
    params, sstate = jax.eval_shape(rnd.init, weights.seed_array(0))
    batch = rnd.place(run.round_batch(run.sampler(t, c["vocab_size"], 0),
                                      t, 0))
    return rnd.step.lower(params, sstate, batch).compile().as_text()


def test_the_tiny_round_compiled_here_names_every_leg(traced_run):
    _, text, _ = traced_run
    found = {legs.leg(n) for n in legs.op_names(text).values()}
    assert set(legs.METRICS) <= found


def test_readers_get_the_timed_executables_op_names(traced_run):
    """A traced run hands the readers the op_names of the executable it
    timed, and the legs read from them equal those read from the round
    compiled again."""
    cell, text, handed = traced_run
    again = legs.op_names(_compiled_again(cell))
    assert handed == legs.op_names(text) == again
    ops = [Op(f"%{n} = f", 2 * i, 2 * i + 1) for i, n in enumerate(handed)]
    spans = [Op("sample", 0, 1), Op("wait", 2 * len(ops), 2 * len(ops) + 1)]
    tr = Trace(ops={0: ops}, spans=spans)
    lo, hi = tr.window()

    def read(op_names):
        return legs.readings({"trace": tr, "lo": lo, "hi": hi, "rounds": 1,
                              "op_names": op_names})

    got = read(handed)
    assert got == read(again)
    assert all(got[m] > 0 for m in legs.METRICS.values()), got


# Two rounds in a window [0, 100), sampled from 10 and from 55, their ops
# running from 12 to 40 and from 57 to 90 with in-step gaps of 2 and 3.
STARTS = [10, 55]
OPS = [Op("%a.1 = f", 12, 20), Op("%b.2 = f", 22, 40),
       Op("%c.3 = f", 57, 70), Op("%d.4 = f", 73, 90)]


def test_extents_run_from_a_rounds_first_op_to_its_last():
    assert legs.extents(OPS, STARTS, 0, 100) == [(12, 40), (57, 90)]
    # The recorded stretch opens inside a round that started before it.
    assert legs.extents(OPS, [55], 15, 100) == [(15, 40), (57, 90)]


def test_idle_split_on_known_intervals():
    step, host, inside = legs.idle_split(OPS, STARTS, 0, 100)
    assert inside == [(20, 22), (70, 73)]
    assert (step, host) == (5, 12 + 17 + 10)
    assert step + host == trace.total(trace.idle_gaps(OPS, 0, 100))


def _ctx(ops_by_device, names, rounds=2):
    spans = [Op("readback", 0, 1)] + [Op("sample", d, d + 1)
                                      for d in STARTS] + [Op("wait", 99, 100)]
    tr = Trace(ops=ops_by_device, spans=spans)
    lo, hi = tr.window()
    cell = files("lm_1b", "local_sgd.c2h4")
    return {"trace": tr, "lo": lo, "hi": hi, "rounds": rounds,
            "tokens_per_s": 1.0, "chips": len(ops_by_device),
            "peak": PEAK,
            "config": cell["config"], "traffic": cell["traffic"],
            "op_names": names}


SCOPED = {"a.1": f"{MAP}/client_step/jvp()/dot", "b.2": f"{LAYER}/dot",
          "c.3": f"{MAP}/clip/mul", "d.4": "jit(round_fn)/server_update/add"}
METRICS = list(legs.METRICS.values()) + ["step_idle_ms", "host_idle_ms"]


def _read_all(ctx):
    return {m: run.load_module("metrics", m).read(ctx) for m in METRICS}


def test_readings_add_up_to_the_window():
    ctx = _ctx({0: OPS}, SCOPED)
    got = _read_all(ctx)
    ms = 1e-6 / 2
    assert got == pytest.approx({
        "client_fwd_ms": 8 * ms, "client_bwd_ms": 18 * ms, "remat_ms": 0.0,
        "client_opt_ms": 13 * ms, "aggregate_ms": 17 * ms,
        "step_idle_ms": 5 * ms, "host_idle_ms": 39 * ms})
    window_ms = (ctx["hi"] - ctx["lo"]) / 1e6 / ctx["rounds"]
    assert sum(got.values()) == pytest.approx(window_ms)
    idle = run.load_module("metrics", "device_idle_pct").read(ctx)
    assert 100 * (got["step_idle_ms"] + got["host_idle_ms"]) / window_ms \
        == pytest.approx(idle)


def test_readings_take_the_worst_device():
    late = [Op(o.name, o.start + 1, o.end) for o in OPS]
    got = _read_all(_ctx({0: OPS, 1: late}, SCOPED))
    assert got["client_fwd_ms"] == pytest.approx(8e-6 / 2)
    assert got["step_idle_ms"] == pytest.approx(7e-6 / 2)
    assert got["host_idle_ms"] == pytest.approx(41e-6 / 2)


def test_a_program_without_scopes_reads_no_leg():
    """The parent program's op_names hold only JAX's own marks: the leg
    readers return nothing, the idle readers still read."""
    loop = "jit(round_fn)/vmap()/while/body/closed_call"
    bare = {"a.1": f"{loop}/jvp()/dot",
            "b.2": f"{loop}/transpose(jvp())/checkpoint/dot",
            "c.3": f"{loop}/mul", "d.4": "jit(round_fn)/add"}
    assert not any(m in n for n in bare.values() for m in legs.PROGRAM_SCOPES)
    got = _read_all(_ctx({0: OPS}, bare))
    assert all(got[m] is None for m in legs.METRICS.values())
    assert got["step_idle_ms"] == pytest.approx(5e-6 / 2)


def test_recorded_round_boundary_idle_falls_between_rounds():
    """On the recorded stretch the 3-4.5 ms gap while the host waits, reads
    back, samples and dispatches is host idle, and the two idle readings
    sum to the stretch's idle."""
    tr, rec = _recorded("lm_1b_round_boundary.json.gz")
    lo, hi = rec["lo"], rec["hi"]
    ops = tr.ops[0]
    starts = [s.start for s in tr.spans if s.name == "sample"]
    step, host, inside = legs.idle_split(ops, starts, lo, hi)
    gaps = trace.idle_gaps(ops, lo, hi)
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert 3.0e6 < longest[1] - longest[0] < 4.5e6
    assert longest not in inside
    assert longest[1] - longest[0] <= host
    assert step + host == pytest.approx(trace.total(gaps))


def test_existing_readings_do_not_move():
    """device_idle_pct and the breakdown read the same on the recorded
    trace whether or not the new readers ran over it first."""
    tr, rec = _recorded("lm_1b_round_boundary.json.gz")
    lo, hi = rec["lo"], rec["hi"]

    def existing():
        ctx = {"trace": tr, "lo": lo, "hi": hi}
        worst = min(tr.ops, key=lambda d: trace.total(
            trace.busy(tr.ops[d], lo, hi)))
        return (run.load_module("metrics", "device_idle_pct").read(ctx),
                trace.op_seconds(tr, lo, hi),
                [(trace.label(g, tr.spans), g)
                 for g in trace.idle_gaps(tr.ops[worst], lo, hi)])

    before = existing()
    ctx = {"trace": tr, "lo": lo, "hi": hi, "rounds": 1, "op_names": {}}
    legs.readings(ctx)
    assert existing() == before


def test_in_step_gaps_are_logged_by_the_op_that_ends_them(capsys):
    legs.readings(_ctx({0: OPS}, SCOPED))
    err = capsys.readouterr().err
    assert "legs, share of busy: backward 32.14%" in err
    assert "round 1 largest in-step gaps, ms: 0.000 before b.2 (backward)" \
        in err
    assert "round 2 largest in-step gaps, ms: 0.000 before d.4 (aggregate)" \
        in err
