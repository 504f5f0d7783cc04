"""``launch.train`` builds DrJAX's nested round (``--pods``): over three
rounds it agrees with the plain nested reference
(``benchmarks/chip/reference_hier.py``) on one device and on a (pod 2,
data 2) mesh; without ``--pods`` it builds the flat round it always did.

The comparison runs in float32 on the CPU at a tiny size. Each round starts
the reference from the program's parameters before it, so that a difference
of one round does not carry into the next, and compares the new parameters
element by element against two tolerances:

* every element within one int8 step of the program: the mean over the two
  pods of each pod's row scale, plus one unit in the last place of the
  parameter. Program and reference sum their clients' float32 deltas in
  different orders, so a pod partial lying at a rounding boundary of the
  int8 grid may round to either side (one step of that pod), and the new
  parameter is rounded to float32 once;
* at most a thousandth of the elements farther apart than a hundredth of
  that step: such a boundary is rare (at most 6e-5 of the elements in the
  runs that set this tolerance, on one device and on the mesh).

The same round without the int8 roundtrip keeps every element within half
a step but moves 78 % of them by more than a hundredth of one: the second
tolerance sees whether the roundtrip ran.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402

SEED = 2**32 + 77
ROUNDS = 3
# A hundredth of an int8 step: farther apart than this, an element differs
# by more than float32 rounding.
NEAR = 1e-2
# At most this share of the elements may be farther apart than NEAR.
FAR_SHARE = 1e-3
LOSS_RTOL = 1e-5


def _cell():
    """lm_350m's config and the cell's traffic at a tiny size, in float32."""
    from benchmarks.chip.tests.tiny import SMALL_MODEL, files

    cell = files("lm_350m", "hier_int8.p2c4h1")
    c = {**cell["config"], **SMALL_MODEL, "dtype": "float32"}
    t = {**cell["traffic"], "batch": 2, "seq": 32}
    return c, t


def _argv(t, compression="int8", pods=True):
    argv = ["--arch", "lm_350m", "--cohort", str(t["cohort"]),
            "--local-steps", str(t["local_steps"]), "--batch",
            str(t["batch"]), "--seq", str(t["seq"]), "--client-lr",
            str(t["client_lr"])]
    if pods:
        argv += ["--pods", str(t["pods"])]
    if compression:
        argv += ["--compression", compression]
    return argv


def compare(devices, compression="int8") -> dict:
    """Three rounds of ``build_round_fn``'s nested round on ``devices``
    against the reference: per round, the largest distance of an element
    in int8 steps, the share of elements farther apart than ``NEAR`` steps,
    and the program's and the reference's loss."""
    from benchmarks.chip import program, run, weights
    from benchmarks.chip import reference_hier as ref

    c, t = _cell()
    cfg = program.model_config(c)
    args = train_lib.parse_args(_argv(t, compression))
    layout = train_lib.round_layout(args, devices)
    step, server_opt = train_lib.build_round_fn(cfg, args, layout.mesh)
    sample = run.sampler(t, c["vocab_size"], SEED)
    params = jax.device_get(ref.init(ref.frozen(c), weights.seed_array(SEED)))
    sstate = layout.state(server_opt.init(params))
    out = []
    for r in range(ROUNDS):
        batch = run.round_batch(sample, t, r)
        steps = [jax.tree_util.tree_map(ref.int8_step, partial) for partial, _
                 in ref.pod_partials(c, t, jax.device_put(params), batch)]
        want, want_loss = ref.run_round(c, t, jax.device_put(params), batch)
        new, sstate, metrics = step(layout.state(params), sstate,
                                    layout.batch(batch))
        params = jax.device_get(new)
        gaps = [np.maximum(np.abs(got - w) - np.spacing(np.abs(w)), 0)
                / ((s0 + s1) / 2)
                for got, w, s0, s1 in zip(
                    *map(jax.tree_util.tree_leaves,
                         (params, jax.device_get(want), *steps)))]
        gap = np.concatenate([g.ravel() for g in gaps])
        out.append({"steps": float(gap.max()),
                    "far": float(np.mean(gap > NEAR)),
                    "loss": float(metrics["loss"]), "want_loss": want_loss})
    return {"rounds": out, "mesh": layout.mesh is not None}


def _check(rounds):
    for r in rounds:
        assert r["steps"] <= 1.0, r
        assert r["far"] <= FAR_SHARE, r
        assert r["loss"] == pytest.approx(r["want_loss"], rel=LOSS_RTOL)


def test_nested_round_matches_the_reference_on_one_device():
    got = compare(jax.devices()[:1])
    assert not got["mesh"]
    _check(got["rounds"])


def test_nested_round_matches_the_reference_on_a_mesh(device_pool):
    got = device_pool.run(f"""
        import json, sys
        sys.path[:0] = [{REPO!r}, {os.path.join(REPO, "tests")!r}]
        import jax
        import test_hier_entry

        print(json.dumps(test_hier_entry.compare(jax.devices()[:4])))
    """)
    assert got["mesh"]
    _check(got["rounds"])


def test_the_round_without_int8_falls_outside_the_tolerances():
    rounds = compare(jax.devices()[:1], compression=None)["rounds"]
    assert all(r["far"] > FAR_SHARE for r in rounds), rounds


def test_without_pods_the_flat_round_is_built():
    """``build_round_fn`` without ``--pods`` lowers to the text of the
    flat round that ``make_local_sgd_round`` builds."""
    from repro import optim
    from repro.models import registry

    _, t = _cell()
    cfg = registry.get_config("lm_350m").reduced()
    args = train_lib.parse_args(_argv(t, compression=None, pods=False))
    step, server_opt = train_lib.build_round_fn(cfg, args)
    flat = jax.jit(make_local_sgd_round(
        functools.partial(registry.loss_fn, cfg), optim.sgd(args.client_lr),
        optim.fedavg_momentum(1.0),
        LocalSGDConfig(partition_size=args.cohort,
                       num_local_steps=args.local_steps, grad_clip=1.0)),
        donate_argnums=(0, 1))
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    shape = (args.cohort, args.local_steps, args.batch, args.seq)
    batch = {k: jax.ShapeDtypeStruct(shape, np.int32)
             for k in ("tokens", "labels")}
    sstate = jax.eval_shape(server_opt.init, params)
    assert (step.lower(params, sstate, batch).as_text()
            == flat.lower(params, sstate, batch).as_text())


@pytest.mark.parametrize("argv,devices,match", [
    (["--pods", "3", "--cohort", "8"], 1, "--pods 3 does not divide --cohort 8"),
    (["--pods", "2", "--cohort", "8"], 3, "cannot run on 3 devices"),
    (["--pods", "2", "--cohort", "4"], 8, "--cohort 4 cannot run on 8"),
])
def test_a_layout_the_pods_cannot_take_is_refused(argv, devices, match):
    args = train_lib.parse_args(argv + ["--compression", "int8"])
    with pytest.raises(ValueError, match=match):
        train_lib.round_layout(args, jax.devices()[:1] * devices)
