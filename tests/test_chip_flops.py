"""The chip benchmark's FLOP count against the compiler's.

``benchmarks/chip/flops.flops_per_token`` is the numerator of the ledger's
``mfu``. On a config where XLA's ``cost_analysis`` counts every op (layers
unscanned, no rematerialization, naive attention, float32), the count from
shapes must match the compiled training step's at each cell's published
widths and sequence, cut to 2 layers. The count leaves out the elementwise
work (norms, softmax, the loss), so it reads a little under the compiler's:
0.99915 for lm_1b's widths and 0.99819 for lm_350m's on the CPU backend.
"""

import dataclasses
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from repro import compat  # noqa: E402
from repro.models import registry  # noqa: E402

BAND = (0.995, 1.0)


@pytest.mark.parametrize("config,traffic", [
    ("lm_1b", "local_sgd.c2h4"),
    ("lm_350m", "hier_int8.p2c4h1"),
])
def test_flops_per_token_matches_cost_analysis(config, traffic):
    from benchmarks.chip import flops, program
    from benchmarks.chip.tests.tiny import files

    cell = files(config, traffic)
    c = {**cell["config"], "num_layers": 2}
    seq = cell["traffic"]["seq"]
    cfg = dataclasses.replace(
        program.model_config(c), scan_layers=False, remat="none",
        attn_impl="naive", dtype="float32")
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    batch = jax.eval_shape(lambda: registry.make_concrete_batch(cfg, 1, seq))

    def step(p, b):
        return jax.value_and_grad(lambda q: registry.loss_fn(cfg, q, b))(p)

    compiled = jax.jit(step).lower(params, batch).compile()
    ratio = flops.flops_per_token(c, seq) * seq / (
        compat.cost_analysis(compiled)["flops"])
    assert BAND[0] <= ratio <= BAND[1], f"{config}: count/HLO = {ratio:.5f}"
