"""Pipelined rounds over a stage-kind placement (1F1B-style microbatching).

A pipeline is a placement stack whose outermost level is *stage*-kind: the S
groups are not replicas of one computation but S different phases of it, and
they communicate by neighbor transfer (:func:`repro.core.stage_transfer`)
rather than broadcast/reduce. :func:`make_pipelined_round` builds the round
as a ``lax.scan`` over schedule ticks:

* tick ``t`` injects microbatch ``min(t, M-1)`` into stage 0's slot of the
  carried activation buffer (shape ``(S,) + activation``),
* every stage computes its phase on its slot (:func:`stage_map` — one vmap
  over the stage axis, or S heterogeneous per-stage functions),
* stage ``S-1``'s slot is drained as that tick's output,
* the buffer shifts by one stage (``stage_transfer(shift=1)``) for the next
  tick, zero-filling stage 0 until the next injection overwrites it.

The scan runs ``T = M + S - 1`` ticks; ticks before ``S-1`` drain pipeline
fill garbage, so the real outputs are ``outs[S-1:]`` — microbatch ``m``
emerges at tick ``m + S - 1``. The idle fraction of stage-ticks is the
classic pipeline bubble ``(S - 1) / (M + S - 1)``, which microbatching
amortizes away (:func:`pipeline_bubble_fraction`).

Under ``plan.compile`` this lowers to ONE donation-aware executable: the
scan carry (the activation buffer) is updated in place across ticks, each
slot pinned to its stage's mesh axis by the stage level's sharding
constraints, and the transfer is a collective-permute between stage shards.
``run_plan`` on the same plan is the eager bitwise oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro import core as drjax

__all__ = [
    "PipelineConfig",
    "make_pipelined_round",
    "pipeline_bubble_fraction",
]


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Shape of the pipelined round.

    ``num_stages`` is the stage-kind placement's size S; ``num_microbatches``
    M is the number of microbatches fed through per round. ``stage_axes``
    optionally names the mesh axis the stage level pins (conventionally
    ``"stage"`` — see ``repro.launch.mesh.level_axes_for``)."""

    num_stages: int
    num_microbatches: int
    stage_axes: Any = None
    mesh: Any = None
    use_sharding_annotations: bool = True


def pipeline_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Idle fraction of stage-ticks in the fill/drain schedule:
    ``(S - 1) / (M + S - 1)`` (more microbatches -> smaller bubble)."""
    s, m = num_stages, num_microbatches
    if s < 1 or m < 1:
        raise ValueError("need num_stages >= 1 and num_microbatches >= 1")
    return (s - 1) / (m + s - 1)


def make_pipelined_round(
    stage_fns: Union[Callable, Sequence[Callable]],
    cfg: PipelineConfig,
    *,
    donate: bool = False,
):
    """Build ``round_fn(microbatches, act0) -> (outs, act_final)``.

    ``stage_fns`` is one callable (the same phase at every stage) or a
    sequence of ``num_stages`` callables (heterogeneous phases). Every phase
    must map an activation to an activation of the SAME shape/dtype — the
    carried buffer has one fixed slot per stage.

    ``microbatches`` leaves carry a leading ``(M,)`` microbatch axis;
    ``act0`` is the stage-partitioned activation buffer (leaves of shape
    ``(S,) + activation`` — zeros for a cold start). ``outs`` leaves are
    ``(M,) + activation``: microbatch m's activation after all S phases.
    Returning ``act_final`` keeps the buffer a scan carry end to end, so
    with ``donate=True`` the round is jitted with ``act0`` donated — the
    buffer is updated in place across rounds instead of copied (the round
    loop's analogue of the params donation rule in ``rounds.py``).

    When segmenting with ``build_plan``, pass ``partitioned_invars=(0, 1)``:
    the microbatch axis M is not a placement axis, so the shape heuristic
    would misread ``microbatches`` whenever M happens to equal S.
    """
    s = cfg.num_stages
    m = cfg.num_microbatches
    if s < 1 or m < 1:
        raise ValueError("need num_stages >= 1 and num_microbatches >= 1")
    if not callable(stage_fns):
        stage_fns = tuple(stage_fns)
        if len(stage_fns) != s:
            raise ValueError(
                f"got {len(stage_fns)} stage functions for "
                f"{s} stages (or pass a single callable)."
            )
    ticks = m + s - 1

    partition_axes = (
        {"stages": cfg.stage_axes} if cfg.stage_axes is not None else None
    )

    @drjax.program(
        placements={"stages": s},
        placement_kinds={"stages": "stages"},
        partition_axes=partition_axes,
        mesh=cfg.mesh,
        use_sharding_annotations=cfg.use_sharding_annotations,
    )
    def round_fn(microbatches, act0):
        def tick(act, t):
            mb = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, jnp.minimum(t, m - 1), axis=0, keepdims=False
                ),
                microbatches,
            )
            act = jax.tree_util.tree_map(
                lambda a, v: a.at[0].set(v), act, mb
            )
            y = drjax.stage_map(stage_fns, act)
            out = jax.tree_util.tree_map(lambda x: x[s - 1], y)
            nxt = drjax.stage_transfer(y, shift=1)
            return nxt, out

        act_final, outs = jax.lax.scan(
            tick, act0, jnp.arange(ticks), length=ticks
        )
        # Ticks 0..S-2 drain fill garbage; microbatch m emerges at m + S - 1.
        outs = jax.tree_util.tree_map(lambda o: o[s - 1:], outs)
        return outs, act_final

    if donate:
        return jax.jit(round_fn, donate_argnums=(1,))
    return round_fn
