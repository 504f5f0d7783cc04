"""The nested cell ``lm_350m.hier_int8.p2c4h1``: it resolves to its round
kind, its plain reference computes what the program computes, and its
controls and faults fail its limits, on the CPU at a tiny size."""

from __future__ import annotations

import jax
import pytest

from tiny import files, shrink, tiny_cell

from benchmarks.chip import control, faults, reference_hier, run, weights

CELL = "lm_350m.hier_int8.p2c4h1"


def test_the_nested_cell_resolves_its_round_kind():
    """Four chips, the nested reference, the program's top-k pod partials
    as its variant, the exchange fault beside the shared ones, and both
    new readers among its metrics."""
    cell = run.load_cell(CELL)
    assert cell["chips"] == 4 and cell["traffic"]["round"] == "hier_int8"
    kind = run.round_kind(cell["traffic"])
    assert kind.reference.__file__ == f"{run.HERE}/reference_hier.py"
    assert kind.variants == {"program_topk": {"compression": "topk"}}
    assert set(kind.faults) == {*faults.FAULTS, "exchange"}
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"collective_exposed_ms",
            "reduce_compress_roundtrip_roofline"} <= reported


def test_nested_round_matches_the_program():
    """The reference against the program's nested int8 round on one
    device, in float32: the same loss, and each leaf's change within a
    thousandth of its norm (a partial at a rounding boundary of the int8
    grid may round to either side, one step of its row)."""
    cell = shrink(files("lm_350m", "hier_int8.p2c4h1"))
    c = {**cell["config"], "dtype": "float32"}
    t = cell["traffic"]
    rnd = run.load_module("rounds", t["round"]).build(c, t, jax.devices()[:1])
    words = weights.seed_array(11)
    params, sstate = rnd.init(words)
    p0 = reference_hier.init(reference_hier.frozen(c), words)
    batch = run.round_batch(run.sampler(t, c["vocab_size"], 2**40 + 3), t, 0)
    new, _, metrics = rnd.step(params, sstate, rnd.place(batch))
    ref_new, ref_loss = reference_hier.run_round(c, t, p0, batch)
    assert float(metrics["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    got = reference_hier.leaf_change_norms(new, p0)
    want = reference_hier.leaf_change_norms(ref_new, p0)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(a) == pytest.approx(float(b), rel=1e-3, abs=1e-9)


def test_the_nested_cells_controls_fail_its_limits():
    """On one device: the float8 control, the program's top-k pod
    partials, half the cohort and the cross-pod exchange left out are each
    judged not correct."""
    cell = tiny_cell(CELL)
    rows, _ = control.readings(cell, jax.devices()[:1], [2**33 + 3], 1,
                               log=lambda *_: None)
    (row,) = rows
    for name in ("control", "program_topk", "half_batch", "exchange"):
        assert row[name]["correct"] is False, (name, row[name])
    # Leaving out the exchange keeps the round's loss but not its update.
    assert row["exchange"]["loss_gap"] < row["half_batch"]["loss_gap"]
