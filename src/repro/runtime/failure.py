"""Failure injection + checkpoint-restart recovery loop.

At pod scale, node failures are routine; the recovery contract here is the
standard one: on a step failure, restore the latest complete checkpoint and
replay from there (the data pipeline is deterministic in the step index, so
replay is exact). ``run_with_recovery`` is the driver used by
``launch/train.py`` and the chaos soak harness (``runtime/chaos.py``);
``FailureInjector`` simulates device loss in tests and examples.

The host spans ``checkpoint_save`` and ``restore`` (``jax.profiler``
annotations) mark checkpoint traffic in a profile of the loop.

Recovery policy:

 * only exceptions in the ``recoverable`` allowlist trigger a
   restore-and-replay — programming errors (``TypeError``/``ValueError``/...)
   propagate immediately instead of burning ``max_restarts`` on an error
   that every replay will hit again;
 * so do device errors that replay cannot cure (:func:`is_fatal`): a program
   the compiler refuses, or one that exhausts device memory, fails on the
   first try with the compiler's own message;
 * restarts back off exponentially (``backoff_base_s * 2**(restart-1)``,
   capped) so a crash-looping fleet does not hammer the checkpoint store;
 * ``stats["completed_steps"]`` counts *forward progress* (high-water mark
   of the step counter), never replayed work — a restart from scratch
   replays steps without re-counting them; ``stats["replayed_steps"]``
   counts the replays separately.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Optional, Tuple, Type

import jax

logger = logging.getLogger(__name__)


class SimulatedDeviceFailure(RuntimeError):
    pass


#: Default restart allowlist: injected/real device failures surface as
#: RuntimeError subclasses (XlaRuntimeError included); anything else is a
#: programming bug and should fail fast.
DEFAULT_RECOVERABLE: Tuple[Type[BaseException], ...] = (
    SimulatedDeviceFailure,
    RuntimeError,
)

# Status codes of device errors that every replay would raise again.
_FATAL_STATUS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT", "UNIMPLEMENTED")


def is_fatal(exc: BaseException) -> bool:
    """True for a JAX runtime error that a restore cannot cure: the program
    did not compile (XLA or Mosaic refused it) or did not fit the device."""
    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    msg = str(exc)
    return msg.startswith(_FATAL_STATUS) or "compil" in msg.lower()


class FailureInjector:
    """Raises SimulatedDeviceFailure at the given step indices (once each)."""

    def __init__(self, fail_at=()):
        self.fail_at = set(fail_at)
        self.failures = 0

    def check(self, step: int) -> None:
        if step in self.fail_at:
            self.fail_at.discard(step)
            self.failures += 1
            raise SimulatedDeviceFailure(f"injected failure at step {step}")


def run_with_recovery(
    step_fn: Callable[[int, Any], Any],
    init_state: Any,
    num_steps: int,
    checkpoint_mgr,
    *,
    checkpoint_every: int = 10,
    max_restarts: int = 5,
    recoverable: Tuple[Type[BaseException], ...] = DEFAULT_RECOVERABLE,
    backoff_base_s: float = 0.0,
    backoff_cap_s: float = 30.0,
    state_metadata: Optional[Callable[[Any], dict]] = None,
    on_restore: Optional[Callable[[Any, dict], Any]] = None,
    on_recovery: Optional[Callable[[int, Optional[int]], None]] = None,
) -> Tuple[Any, dict]:
    """Run ``state = step_fn(step, state)`` for num_steps with restart-on-fail.

    Returns (final_state, stats). Steps are 0-indexed; checkpoints are taken
    *after* the step completes and record ``step + 1`` as the resume point
    (the resume step is also injected into the checkpoint metadata under
    ``"step"``, so ``on_restore`` callbacks can see where they landed).

    Only exceptions matching ``recoverable`` and not :func:`is_fatal`
    trigger a restore; everything else propagates. ``backoff_base_s > 0`` sleeps
    ``min(backoff_cap_s, backoff_base_s * 2**(restart-1))`` before each
    restore.

    stats keys: ``restarts``, ``scratch_restarts`` (restarts with no
    checkpoint to restore), ``completed_steps`` (unique forward progress,
    replays excluded), ``replayed_steps``, ``backoff_s``.

    ``on_recovery(restart_index, restored_step_or_None)`` fires after every
    recovery restore (1-indexed restart counter; ``None`` means a
    from-scratch restart) — the observation point chaos harnesses use to
    audit which checkpoint each failure actually fell back to.
    """
    stats = {
        "restarts": 0,
        "scratch_restarts": 0,
        "completed_steps": 0,
        "replayed_steps": 0,
        "backoff_s": 0.0,
    }
    state = init_state
    step = 0
    with jax.profiler.TraceAnnotation("restore"):
        restored = checkpoint_mgr.restore_latest(state)
    if restored is not None:
        step, state, meta = restored
        if on_restore is not None:
            state = on_restore(state, meta)
        logger.info("resumed from checkpoint at step %d", step)

    start_step = step
    high_water = step  # completed_steps counts progress past this, once
    restarts = 0
    while step < num_steps:
        try:
            state = step_fn(step, state)
            step += 1
            if step > high_water:
                high_water = step
                stats["completed_steps"] = high_water - start_step
            else:
                stats["replayed_steps"] += 1
            if step % checkpoint_every == 0 or step == num_steps:
                meta = state_metadata(state) if state_metadata else {}
                meta = dict(meta, step=step)
                with jax.profiler.TraceAnnotation("checkpoint_save"):
                    checkpoint_mgr.save(step, state, metadata=meta,
                                        blocking=False)
        except recoverable as e:
            if is_fatal(e):
                raise
            restarts += 1
            stats["restarts"] = restarts
            if restarts > max_restarts:
                raise RuntimeError(
                    f"exceeded max_restarts={max_restarts}"
                ) from e
            if backoff_base_s > 0.0:
                delay = min(backoff_cap_s, backoff_base_s * 2 ** (restarts - 1))
                stats["backoff_s"] += delay
                time.sleep(delay)
            logger.warning("step %d failed (%s); restoring", step, e)
            with jax.profiler.TraceAnnotation("restore"):
                restored = checkpoint_mgr.restore_latest(state)
            if restored is None:
                # no checkpoint yet: restart from the initial state. The
                # step counter resets but completed_steps does not — the
                # replayed prefix is not new progress.
                state, step = init_state, 0
                stats["scratch_restarts"] += 1
                if on_recovery is not None:
                    on_recovery(restarts, None)
            else:
                step, state, meta = restored
                if on_restore is not None:
                    state = on_restore(state, meta)
                if on_recovery is not None:
                    on_recovery(restarts, step)
    checkpoint_mgr.wait()
    return state, stats
