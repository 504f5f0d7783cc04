"""The plain nested round: local SGD in pods with an int8 cross-pod leg, in
float32 jax.numpy.

Written from the paper's description of nested placements (broadcast to
every client of every pod; local SGD on each client; the mean of the
clients' deltas inside each pod; that pod partial sent across pods in int8
with one scale per row of 256 values; the mean over pods; the server
update). The clients' local steps, the decoder and the server step are
``reference.py``'s: every matmul in float32 at ``Precision.HIGHEST``,
parameters stored in the config's dtype and each update rounded to it. It
imports nothing of the program under test.

The int8 roundtrip of a pod partial, as the program's wire format states
it: each leaf flattened and zero-padded to a multiple of 256 values, cut
into rows of 256, each row scaled by its largest magnitude over 127
(at least 1e-12), rounded to the nearest integer in [-127, 127] and
multiplied back, in float32.

Departures from the program, none of which changes the result beyond
float32 rounding:

* clients run one after another, and each pod's deltas are summed in a
  running float32 sum in client order (the program sums a device's clients
  and then all-reduces across the devices of a pod);
* rows are cut leaf by leaf (the program packs every leaf into one buffer,
  each leaf padded to a row boundary, so its rows are these);
* the partial is the sum times 1 / clients per pod, as the kernel forms it.

So that a round at 24 layers of lm_350m fits one chip, it holds at most two
float32 trees (the running pod sum and the sum of the pods' roundtrips)
beside one client's update at a time. ``quant="fp8"`` is
``reference.py``'s control: every matmul operand rounded to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import (  # noqa: F401  (the reference API)
    client_update,
    frozen,
    init,
    leaf_change_norms,
    server_update,
)

F32 = jnp.float32
ROW = 256
INT8_MAX = 127.0


def _rows(x):
    """``x`` flattened, zero-padded to a multiple of 256, as rows of 256."""
    flat = x.reshape(-1)
    pad = (-flat.size) % ROW
    return jnp.pad(flat, (0, pad)).reshape(-1, ROW) if pad else x.reshape(-1, ROW)


def _unrows(rows, x):
    """Rows of :func:`_rows` back to ``x``'s shape."""
    return rows.reshape(-1)[:x.size].reshape(x.shape)


def _scales(rows):
    return jnp.maximum(jnp.max(jnp.abs(rows), -1, keepdims=True) / INT8_MAX,
                       1e-12)


def int8_roundtrip(x):
    """``x`` (float32) through the int8 wire format and back, row by row."""
    rows = _rows(x)
    scale = _scales(rows)
    return _unrows(jnp.clip(jnp.round(rows / scale), -INT8_MAX, INT8_MAX)
                   * scale, x)


def int8_step(x):
    """Per element of ``x``, the int8 step of its row: the scale."""
    rows = _rows(x)
    return _unrows(jnp.broadcast_to(_scales(rows), rows.shape), x)


@functools.partial(jax.jit, donate_argnums=(0,))
def add_delta(acc, p0, final):
    """``acc`` plus the client's change ``final - p0``, in float32."""
    return jax.tree_util.tree_map(
        lambda a, w0, w: a + (w.astype(F32) - w0.astype(F32)), acc, p0, final)


@jax.jit
def zeros(p):
    return jax.tree_util.tree_map(lambda w: jnp.zeros(w.shape, F32), p)


@functools.partial(jax.jit, static_argnames=("clients",),
                   donate_argnums=(0,))
def partial_mean(acc, clients):
    """A pod's sum of deltas to its partial, as the kernel forms it."""
    return jax.tree_util.tree_map(lambda a: a * (1.0 / clients), acc)


@functools.partial(jax.jit, donate_argnums=(0,))
def roundtrip(partial):
    return jax.tree_util.tree_map(int8_roundtrip, partial)


@functools.partial(jax.jit, donate_argnums=(0,))
def add(total, back):
    return jax.tree_util.tree_map(jnp.add, total, back)


@functools.partial(jax.jit, static_argnames=("pods",), donate_argnums=(0,))
def pod_mean(total, pods):
    return jax.tree_util.tree_map(lambda s: s / pods, total)


def pod_partials(c: dict, t: dict, p, batch, quant=None):
    """Each pod's float32 partial from parameters ``p`` on ``batch`` (tokens
    and labels of shape ``(cohort, steps, batch, seq)``, ``t["pods"]`` pods
    of ``cohort / pods`` clients in order), one pod at a time, with the
    losses of its clients."""
    cf, tf = frozen(c), frozen(t)
    pods = t["pods"]
    clients = batch["tokens"].shape[0] // pods
    for pod in range(pods):
        acc, losses = zeros(p), []
        for i in range(pod * clients, (pod + 1) * clients):
            final, value = client_update(cf, tf, quant, p, batch["tokens"][i],
                                         batch["labels"][i])
            acc = add_delta(acc, p, final)
            losses.append(value)
            del final
        yield partial_mean(acc, clients), losses


def run_round(c: dict, t: dict, p, batch, quant=None):
    """One plain nested round from parameters ``p`` on ``batch`` (see
    :func:`pod_partials`): (new parameters, loss)."""
    total, losses = None, []
    for partial, pod_losses in pod_partials(c, t, p, batch, quant):
        back = roundtrip(partial)
        total = back if total is None else add(total, back)
        losses += pod_losses
        del back
    new = server_update(p, jnp.float32(t["server_lr"]),
                        pod_mean(total, t["pods"]))
    return new, float(jnp.mean(jnp.stack(losses)))
