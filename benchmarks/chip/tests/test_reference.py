"""The plain reference computes what the program computes: at a small size in
float32 on the CPU its loss, gradient and round match the program's on the
program's own sampler's batches."""

from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from tiny import files, shrink

from benchmarks.chip import program, reference, run, weights

CELLS = [("lm_1b", "local_sgd.c2h4")]


def _f32_cell(name):
    cell = shrink(files(*name))
    cell["config"] = {**cell["config"], "dtype": "float32"}
    return cell


def _batch(cell, seed=2**40 + 3):
    c, t = cell["config"], cell["traffic"]
    return run.round_batch(run.sampler(t, c["vocab_size"], seed), t, 0)


@pytest.mark.parametrize("name", CELLS)
def test_loss_and_gradient_match_the_program(name):
    from repro.models import registry

    cell = _f32_cell(name)
    c = cell["config"]
    cfg = program.model_config(c)
    p = reference.init(reference.frozen(c), weights.seed_array(5))
    data = _batch(cell)
    toks, labs = data["tokens"][0, 0], data["labels"][0, 0]
    ref_l, ref_g = jax.value_and_grad(
        functools.partial(reference.loss, c, None))(p, toks, labs)
    prog_l, prog_g = jax.value_and_grad(
        lambda q: registry.loss_fn(cfg, q, {"tokens": toks, "labels": labs})
    )(p)
    assert float(prog_l) == pytest.approx(float(ref_l), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(prog_g),
                    jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)


@pytest.mark.parametrize("name", CELLS)
def test_round_matches_the_program(name):
    cell = _f32_cell(name)
    c, t = cell["config"], cell["traffic"]
    rnd = run.load_module("rounds", t["round"]).build(c, t, jax.devices()[:1])
    words = weights.seed_array(11)
    params, sstate = rnd.init(words)
    p0 = reference.init(reference.frozen(c), words)
    batch = _batch(cell)
    new, _, metrics = rnd.step(params, sstate, rnd.place(batch))
    ref_new, ref_loss = reference.run_round(c, t, p0, batch)
    assert float(metrics["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    for a, b, w in zip(jax.tree_util.tree_leaves(new),
                       jax.tree_util.tree_leaves(ref_new),
                       jax.tree_util.tree_leaves(p0)):
        np.testing.assert_allclose(a - w, b - w, rtol=2e-3, atol=1e-7)


def test_sampler_is_fixed_by_the_seed():
    """The same seed gives the same batches; another seed other tokens of
    the same shapes."""
    cell = files("lm_1b", "local_sgd.c2h4")
    c, t = cell["config"], cell["traffic"]

    def batch(seed, r):
        return run.round_batch(run.sampler(t, c["vocab_size"], seed), t, r)

    a, b, other = batch(2**40 + 1, 5), batch(2**40 + 1, 5), batch(2**40 + 2, 5)
    want = (t["cohort"], t["local_steps"], t["batch"], t["seq"])
    assert a["tokens"].shape == want == other["tokens"].shape
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], other["tokens"])
