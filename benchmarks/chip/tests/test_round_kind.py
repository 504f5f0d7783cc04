"""A round kind brings its own plain reference, variants and faults: the
harness resolves them by the kind's name and takes them from there, in the
output check of a run and in the readings of ``control.py``."""

from __future__ import annotations

import textwrap

import jax
import pytest

from tiny import tiny_cell

from benchmarks.chip import control, faults, run, weights

CELL = "lm_1b.local_sgd.c2h4"
SEED = 2**33 + 9

PLANTED_ROUND = '''
from benchmarks.chip import run

REFERENCE = "planted_reference"
VARIANTS = {"program_planted": {"compression": "int8"}}


def twice(step):
    """The round applied twice."""
    def broken(params, sstate, batch):
        params, sstate, _ = step(params, sstate, batch)
        return step(params, sstate, batch)

    return broken


FAULTS = {"planted_twice": twice}


def build(c, t, devices, **options):
    return run.load_module("rounds", "local_sgd").build(c, t, devices,
                                                        **options)
'''

PLANTED_REFERENCE = '''
from benchmarks.chip.reference import frozen, init, leaf_change_norms
from benchmarks.chip.reference import run_round as flat_round

QUANTS = []


def run_round(c, t, p, batch, quant=None):
    QUANTS.append(quant)
    return flat_round(c, t, p, batch, quant)
'''


def _plant(root, files: dict) -> str:
    (root / "rounds").mkdir(exist_ok=True)
    for name, body in files.items():
        (root / name).write_text(textwrap.dedent(body))
    return str(root)


def test_local_sgd_resolves_to_the_flat_reference():
    kind = run.round_kind({"round": "local_sgd"})
    assert kind.reference.__file__ == f"{run.HERE}/reference.py"
    assert kind.variants == {"program_int8": {"compression": "int8"}}
    assert kind.faults == faults.FAULTS


def test_a_planted_kind_brings_its_reference_variants_and_faults(
        tmp_path, monkeypatch):
    here = _plant(tmp_path, {"rounds/planted.py": PLANTED_ROUND,
                             "planted_reference.py": PLANTED_REFERENCE})
    cell = tiny_cell(CELL)
    cell["traffic"] = {**cell["traffic"], "round": "planted"}
    c, t = cell["config"], cell["traffic"]
    kind = run.round_kind(t, here)
    assert kind.reference.__file__ == f"{here}/planted_reference.py"
    assert set(kind.faults) == {*faults.FAULTS, "planted_twice"}

    words = weights.seed_array(SEED)
    sample = run.sampler(t, c["vocab_size"], SEED)
    batches = [run.round_batch(sample, t, r)
               for r in range(run.CHECK_ROUNDS)]
    got = run.reference_rounds(kind.reference, c, t, batches, words)
    assert kind.reference.QUANTS == [None] * run.CHECK_ROUNDS
    flat = run.round_kind({"round": "local_sgd"}).reference
    assert got == run.reference_rounds(flat, c, t, batches, words)

    builds, kinds = [], []
    round_kind = run.round_kind

    def resolve(traffic):
        k = round_kind(traffic, here)
        build = k.build
        k.build = lambda *a, **kw: builds.append(kw) or build(*a, **kw)
        kinds.append(k)
        return k

    monkeypatch.setattr(run, "round_kind", resolve)
    rows, summary = control.readings(cell, jax.devices()[:1], [SEED], 1,
                                     log=lambda *_: None)
    (row,) = rows
    assert builds == [{}, {"compression": "int8"}]
    assert set(row) - {"seed", "losses"} == {
        "sound", "control", "program_planted", "half_batch", "planted_twice"}
    assert kinds[0].reference.QUANTS == [None] * 3 + ["fp8"] * 3
    assert row["sound"]["loss_gap"] <= cell["limits"]["loss_gap"]
    for name in ("control", "program_planted", "half_batch", "planted_twice"):
        assert row[name]["correct"] is False, (name, row[name])
    assert summary["judged_correct"]["planted_twice"] == []


BAD = {
    "unknown_reference": ('REFERENCE = "no_such_reference"\n',
                          FileNotFoundError, r"unknown_reference\.py.*"
                          r"no_such_reference"),
    "fault_clash": ('FAULTS = {"half_batch": lambda step: step}\n',
                    ValueError, r"fault_clash\.py.*half_batch"),
    "variant_clash": ('VARIANTS = {"control": {}}\n', ValueError,
                      r"variant_clash\.py.*control"),
    "thin_reference": ('REFERENCE = "thin"\n', ValueError,
                       r"thin_reference\.py.*thin\.py.*leaf_change_norms"),
}


@pytest.mark.parametrize("name", sorted(BAD))
def test_a_kind_that_cannot_resolve_names_its_file(tmp_path, name):
    body, error, match = BAD[name]
    here = _plant(tmp_path, {
        f"rounds/{name}.py": body + "def build(c, t, devices): pass\n",
        "reference.py": PLANTED_REFERENCE,
        "thin.py": "def frozen(c): pass\ndef init(c, w): pass\n"
                   "def run_round(c, t, p, b, quant=None): pass\n",
        "planted_reference.py": PLANTED_REFERENCE})
    with pytest.raises(error, match=match):
        run.round_kind({"round": name}, here)
