"""Faults planted under the timed round, to show that the output check
catches them. Each wraps the compiled round ``step(params, server_state,
batch)`` and returns a broken one with the same signature.

* ``unchanged``: the round runs, but its state comes back as it went in;
* ``half_batch``: the second half of the cohort is left out and the mean
  taken over the rest, by giving it the first half's data.

Every round kind can have these. A fault that only one kind can have, such
as an exchange between chips left out, is in that kind's ``FAULTS``
(``run.round_kind``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def unchanged(step):
    def broken(params, sstate, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (params, sstate))
        _, _, metrics = step(params, sstate, batch)
        return (*keep, metrics)

    return broken


def _repeat_first_half(x):
    """The cohort axis's first half in place of its second."""
    half = x.shape[0] // 2
    return jax.device_put(jnp.concatenate([x[:x.shape[0] - half], x[:half]]),
                          x.sharding)


def half_batch(step):
    def broken(params, sstate, batch):
        return step(params, sstate,
                    jax.tree_util.tree_map(_repeat_first_half, batch))

    return broken


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}
