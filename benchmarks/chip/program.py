"""Glue between the benchmark's files and the program under test.

``rounds/<kind>.py`` builds one kind of round from the program's public
entry points and returns a :class:`Round`; this module holds what every
kind shares: the program's model config from a config file, the check that
the benchmark's weight layout is the program's, and the jitted set-up that
makes the weights and the server state on the device from the seed.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

import benchmarks.chip.weights as weights
from repro.models import registry


@dataclasses.dataclass
class Round:
    """One cell's timed round and what set-up needs around it.

    ``step``: the jitted, donated round ``(params, server_state, batch) ->
    (params, server_state, metrics)``; ``init(words)``: weights and server
    state on the device from :func:`weights.seed_array`'s words; ``place``:
    a host batch onto the devices as the round takes it."""

    step: Any
    init: Callable
    place: Callable
    devices: list


def model_config(c: dict):
    """The program's ModelConfig for config file ``c``: the registry's arch
    with every field the file names set to the file's value."""
    cfg = registry.get_config(c["arch"])
    names = {f.name for f in dataclasses.fields(cfg)}
    return dataclasses.replace(cfg, **{k: v for k, v in c.items()
                                       if k in names})


def check_layout(cfg, c: dict) -> None:
    """Raise unless the program's parameters have the benchmark's layout."""
    shapes = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    got = {k: (tuple(v.shape), str(v.dtype))
           for k, v in weights.flatten(shapes).items()}
    want = {k: (tuple(shape), c["dtype"])
            for k, (shape, _) in weights.layout(c).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"parameter layout differs from the program's: {diff}")


def make_init(c: dict, server_opt, out_shardings=None) -> Callable:
    """``init(words) -> (params, server_state)``, one jitted call."""
    def init(words):
        params = weights.init_params(weights.key_from(words), c)
        return params, server_opt.init(params)

    return jax.jit(init, out_shardings=out_shardings)
