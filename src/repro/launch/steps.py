"""Step-function builders: train / prefill / decode, with shardings.

This is where model, optimizer, mesh and (optionally) the DrJAX round meet:

 * ``make_sgd_train_step`` — production data+model-parallel (+FSDP) training
   step for the 40-cell dry-run table;
 * ``make_drjax_round_step`` — the paper's local-SGD/DiLoCo round, partition
   axis over ("pod", "data");
 * ``make_prefill_step`` / ``make_decode_step`` — serving steps with donated
   KV caches.

Each builder returns ``(fn, in_specs, in_shardings, out_shardings)`` ready
for ``jax.jit(...).lower(*specs)``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.algorithms.rounds import LocalSGDConfig, make_local_sgd_round
from repro.models import registry
from repro.models import partitioning
from repro.models.partitioning import axis_rules, tree_shardings
from repro.launch.mesh import REPLICA_AXES, partition_axes_for


def _is_axes_leaf(v):
    return isinstance(v, tuple) and all(
        isinstance(e, (str, type(None))) for e in v
    )


def _optimizer_axes(opt_kind: str, param_axes_tree):
    if opt_kind == "adamw":
        return {
            "step": (),
            "m": param_axes_tree,
            "v": param_axes_tree,
        }
    if opt_kind == "sgd_momentum":
        return {"step": (), "mu": param_axes_tree}
    return {"step": ()}


def _shardings(axes_tree, mesh, rules=None, spec_tree=None):
    """Axes tree -> NamedShardings; with spec_tree, dims that don't divide the
    mesh axes fall back along the rule chain (shape-aware resolution)."""
    with axis_rules(mesh, rules):
        if spec_tree is None:
            return jax.tree_util.tree_map(
                lambda ax: partitioning.named_sharding(ax),
                axes_tree,
                is_leaf=_is_axes_leaf,
            )
        return jax.tree_util.tree_map(
            lambda ax, spec: partitioning.named_sharding(ax, spec.shape),
            axes_tree,
            spec_tree,
            is_leaf=_is_axes_leaf,
        )


def _replicated(mesh):
    return NamedSharding(mesh, P())


def fsdp_rules(enable: bool):
    return {"p_fsdp": (("data",), None) if enable else (None,)}


def strategy_rules(cfg, fsdp: bool):
    """Logical-axis rules for this arch's mesh strategy.

    ``tp``: model dims shard over the "model" axis (Megatron-style), batch
    over (pod, data). Right for >=8B models where TP amortizes.
    ``dp``: the model axis is repurposed as extra data parallelism — batch
    shards over (pod, data, model), model dims replicate. Right for small
    models where per-layer TP all-reduces would dominate.
    """
    rules = dict(fsdp_rules(fsdp))
    if cfg.mesh_strategy == "dp":
        dp_chain = (
            REPLICA_AXES + ("model",),
            REPLICA_AXES[1:] + ("model",),
            REPLICA_AXES,
            "data",
        )
        rules.update(
            {
                "batch": dp_chain,
                "kv_batch": dp_chain,
                "heads": (None,),
                "kv_heads": (None,),
                "kv_head_dim": (None,),
                "embed": (None,),
                "ff": (None,),
                "experts": (None,),
                "vocab": (None,),
                "recurrent_width": (None,),
                "p_heads": (None,),
                "p_kv_heads": (None,),
                "p_ff": (None,),
                "p_experts": (None,),
                "p_vocab": (None,),
                "p_fsdp": ((REPLICA_AXES[1:] + ("model",),) + (("data",), None))
                if fsdp
                else (None,),
            }
        )
    return rules


# ---------------------------------------------------------------------------
# production train step (per-cell baseline)
# ---------------------------------------------------------------------------


def make_sgd_train_step(
    cfg,
    mesh,
    *,
    optimizer: str = "adamw",
    lr: float = 3e-4,
    fsdp: bool = True,
    remat: Optional[str] = None,
):
    loss_fn = functools.partial(registry.loss_fn, cfg)
    opt = optim.adamw(lr) if optimizer == "adamw" else optim.sgd(lr)
    rules = strategy_rules(cfg, fsdp)

    def train_step(params, opt_state, batch):
        with axis_rules(mesh, rules):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            updates, new_opt_state = opt.update(grads, opt_state, params)
            new_params = optim.optimizers.apply_updates(params, updates)
        return new_params, new_opt_state, loss

    p_axes = registry.param_axes(cfg)
    o_axes = _optimizer_axes(
        "adamw" if optimizer == "adamw" else "sgd", p_axes
    )
    b_axes = registry.batch_axes(cfg)

    def shardings_for(specs):
        p_spec, o_spec, b_spec = specs
        param_sh = _shardings(p_axes, mesh, rules, p_spec)
        opt_sh = _shardings(o_axes, mesh, rules, o_spec)
        batch_sh = _shardings(b_axes, mesh, rules, b_spec)
        loss_sh = _replicated(mesh)
        return (param_sh, opt_sh, batch_sh), (param_sh, opt_sh, loss_sh)

    return train_step, shardings_for


def train_input_specs(cfg, batch: int, seq: int, mesh, *, optimizer="adamw",
                      fsdp: bool = True):
    """ShapeDtypeStructs for (params, opt_state, batch)."""
    opt = optim.adamw(3e-4) if optimizer == "adamw" else optim.sgd(0.1)
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg)
    )
    opt_state = jax.eval_shape(lambda: opt.init(params))
    batch_spec = registry.train_batch_spec(cfg, batch, seq)
    return params, opt_state, batch_spec


# ---------------------------------------------------------------------------
# DrJAX round step (the paper's technique, first-class)
# ---------------------------------------------------------------------------


def make_drjax_round_step(
    cfg,
    mesh,
    *,
    partition_size: int,
    num_local_steps: int = 4,
    client_lr: float = 0.05,
    server: str = "fedavg",  # fedavg | diloco | fedadam
    use_sharding_annotations: bool = True,
    compression: Optional[str] = None,
    fsdp: bool = False,
    jit_donated: bool = False,
):
    loss_fn = functools.partial(registry.loss_fn, cfg)
    server_opt = {
        "fedavg": optim.fedavg_momentum(1.0),
        "diloco": optim.diloco_optimizer(0.7, 0.9),
        "fedadam": optim.fedadam(1e-2),
    }[server]
    round_cfg = LocalSGDConfig(
        partition_size=partition_size,
        num_local_steps=num_local_steps,
        partition_axes=partition_axes_for(mesh),
        mesh=mesh,
        use_sharding_annotations=use_sharding_annotations,
        compression=compression,
    )
    inner = make_local_sgd_round(
        loss_fn, optim.sgd(client_lr), server_opt, round_cfg
    )
    rules = strategy_rules(cfg, fsdp)
    # Inside drjax.map_fn the partition axes (pod, data) belong to vmap's
    # spmd_axis_name and must NOT appear in client-side constraints. The
    # within-client batch may still shard over the remaining "model" axis
    # (dp strategy): clients × within-client parallelism compose (paper §3).
    client_batch_chain = ("model", None) if cfg.mesh_strategy == "dp" else (None,)
    rules["batch"] = client_batch_chain
    rules["kv_batch"] = client_batch_chain

    def round_step(params, server_state, round_data):
        with axis_rules(mesh, rules):
            return inner(params, server_state, round_data)

    if jit_donated:
        # The round-loop donation discipline: params + server_state are
        # carried state and update in place.
        round_step = jax.jit(round_step, donate_argnums=(0, 1))

    p_axes = registry.param_axes(cfg)
    param_sh = _shardings(p_axes, mesh, rules)
    server_sh = _shardings(
        {"step": (), "mu": p_axes} if server == "diloco" else
        ({"step": (), "m": p_axes, "v": p_axes} if server == "fedadam" else
         {"step": ()}),
        mesh, rules,
    )
    # round data: leading clients axis over (pod, data)
    part_axes = partition_axes_for(mesh)
    lead = part_axes if isinstance(part_axes, (str, type(None))) else tuple(part_axes)

    def data_sharding(spec):
        return NamedSharding(mesh, P(lead, *([None] * (len(spec.shape) - 1))))

    return round_step, param_sh, server_sh, data_sharding


def drjax_round_specs(cfg, *, partition_size: int, num_local_steps: int,
                      local_batch: int, seq: int, server: str = "fedavg"):
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg)
    )
    server_opt = {
        "fedavg": optim.fedavg_momentum(1.0),
        "diloco": optim.diloco_optimizer(),
        "fedadam": optim.fedadam(),
    }[server]
    server_state = jax.eval_shape(lambda: server_opt.init(params))
    data = {
        "tokens": jax.ShapeDtypeStruct(
            (partition_size, num_local_steps, local_batch, seq), jnp.int32
        ),
        "labels": jax.ShapeDtypeStruct(
            (partition_size, num_local_steps, local_batch, seq), jnp.int32
        ),
    }
    return params, server_state, data


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, mesh, *, fsdp: Optional[bool] = None,
                      tp_comm: Optional[str] = None,
                      max_len: Optional[int] = None):
    if tp_comm:
        import dataclasses
        cfg = dataclasses.replace(cfg, tp_comm=tp_comm)
    fsdp = (cfg.family == "moe") if fsdp is None else fsdp
    # serving always uses TP rules: memory (weights + KV) binds at decode,
    # so caches shard over the model axis regardless of the train strategy.
    rules = fsdp_rules(fsdp)
    # max_len sizes the prefill-built KV caches for the decode loop that
    # consumes them (the serve scheduler passes prompt_len + max_new).
    inner = registry.make_prefill_fn(cfg, max_len=max_len)

    def prefill_step(params, batch):
        with axis_rules(mesh, rules):
            return inner(params, batch)

    def shardings_for(specs):
        params, batch = specs
        param_sh = _shardings(registry.param_axes(cfg), mesh, rules, params)
        batch_sh = _shardings(registry.batch_axes(cfg), mesh, rules, batch)
        return (param_sh, batch_sh)

    return prefill_step, shardings_for


def make_decode_step(cfg, mesh, *, fsdp: Optional[bool] = None):
    fsdp = (cfg.family == "moe") if fsdp is None else fsdp
    rules = fsdp_rules(fsdp)  # TP rules at serve (see make_prefill_step)
    inner = registry.make_decode_fn(cfg)

    if cfg.is_encoder_decoder:

        def decode_step(params, token, caches, memory_kv):
            with axis_rules(mesh, rules):
                return inner(params, token, caches, memory_kv)

    else:

        def decode_step(params, token, caches):
            with axis_rules(mesh, rules):
                return inner(params, token, caches)

    mod = registry.family_module(cfg)

    def shardings_for(specs):
        params, token, caches, memkv = specs
        param_sh = _shardings(registry.param_axes(cfg), mesh, rules, params)
        cache_axes = (
            mod.cache_axes(cfg) if hasattr(mod, "cache_axes")
            else _encdec_cache_axes(cfg)
        )
        with axis_rules(mesh, rules):
            token_sh = partitioning.named_sharding(("batch", None), token.shape)
            cache_sh = jax.tree_util.tree_map(
                lambda ax, spec: partitioning.named_sharding(ax, spec.shape),
                cache_axes,
                caches,
                is_leaf=_is_axes_leaf,
            )
            memkv_sh = None
            if cfg.is_encoder_decoder:
                memkv_sh = tuple(
                    partitioning.named_sharding(
                        ("layers", "kv_batch", "seq", "kv_heads", "head_dim"),
                        m.shape,
                    )
                    for m in memkv
                )
        return (param_sh, token_sh, cache_sh, memkv_sh)

    return decode_step, shardings_for


# ---------------------------------------------------------------------------
# continuous-batching serve steps (slot pool — see launch/serve.py)
# ---------------------------------------------------------------------------


def _gather_slot(pool, dims, cslot):
    """Slice one slot out of the pool as a batch-1 cache tree.

    Batch-bearing leaves keep a size-1 batch axis (``keepdims``) — exactly
    the shape ``chunk_prefill`` consumes; pos-like leaves drop their leading
    slot axis back to the per-request layout.
    """

    def one(leaf, d):
        if d == registry.POS_LEAF:
            return jax.lax.dynamic_index_in_dim(leaf, cslot, 0, keepdims=False)
        return jax.lax.dynamic_index_in_dim(leaf, cslot, d, keepdims=True)

    return jax.tree_util.tree_map(one, pool, dims)


def _scatter_slot(pool, cache, dims, cslot):
    """Write a batch-1 cache tree back into its slot (in place under jit)."""

    def one(pl, cl, d):
        if d == registry.POS_LEAF:
            return jax.lax.dynamic_update_slice_in_dim(pl, cl[None], cslot, 0)
        return jax.lax.dynamic_update_slice_in_dim(pl, cl, cslot, d)

    return jax.tree_util.tree_map(one, pool, cache, dims)


def _reset_if(first, cache):
    """Zero a gathered slot cache when ``first`` (slot reuse: stale KV /
    recurrent state / pos from the previous occupant must not leak)."""
    return jax.tree_util.tree_map(
        lambda l: jnp.where(first, jnp.zeros_like(l), l), cache
    )


def _make_one_slot_decode(cfg):
    """Batch-1 decode of a single slot, for ``vmap`` over the slot axis.

    vmap strips the slot axis from every pool leaf; batch-bearing leaves
    re-insert a size-1 batch axis at their metadata index so the stock decode
    fn sees its normal (batch=1) layout. Per-slot decode also makes decode
    batch-size-invariant — MoE capacity assignment couples tokens across a
    batch, so decoding slots jointly would make a request's tokens depend on
    who else is in flight.
    """
    decode_fn = registry.make_decode_fn(cfg)
    dims = registry.cache_batch_dims(cfg)

    def one_slot(params, token, caches):
        caches = jax.tree_util.tree_map(
            lambda l, d: l if d == registry.POS_LEAF else jnp.expand_dims(l, d),
            caches,
            dims,
        )
        logits, new = decode_fn(params, token[None], caches)
        new = jax.tree_util.tree_map(
            lambda l, d: l if d == registry.POS_LEAF else jnp.squeeze(l, d),
            new,
            dims,
        )
        return logits[0], new

    return one_slot


def make_slot_decode_step(cfg, mesh, *, fsdp: Optional[bool] = None):
    """Decode every slot of the pool one token.

    ``slot_decode_step(params, tokens (slots, 1), pool)`` ->
    ``(next_tokens (slots, 1), pool)``. The greedy next token is computed on
    device so the scheduler can chain steps without a host round-trip; free
    slots decode garbage that the host never reads (fixed shapes beat
    masking — no recompilation as slots fill/drain).
    """
    fsdp = (cfg.family == "moe") if fsdp is None else fsdp
    rules = fsdp_rules(fsdp)  # TP rules at serve (see make_prefill_step)
    one_slot = _make_one_slot_decode(cfg)
    axes = registry.slot_vmap_axes(cfg)

    def slot_decode_step(params, tokens, pool):
        with axis_rules(mesh, rules):
            logits, pool = jax.vmap(
                one_slot, in_axes=(None, 0, axes), out_axes=(0, axes)
            )(params, tokens, pool)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return nxt, pool

    return slot_decode_step


def make_slot_chunk_step(cfg, mesh, *, fsdp: Optional[bool] = None):
    """Prefill one prompt chunk into one slot (no decode leg).

    ``slot_chunk_step(params, pool, cslot, ctokens (C,), cpos, cfirst)`` ->
    ``(chunk_token (), pool)``. Shapes specialize on the chunk length C —
    one trace per chunk bucket. ``cfirst`` (traced bool) zero-resets the slot
    before the first chunk so slot reuse never reallocates. The returned
    token is the greedy continuation after the chunk — only meaningful on a
    prompt's final chunk.
    """
    fsdp = (cfg.family == "moe") if fsdp is None else fsdp
    rules = fsdp_rules(fsdp)
    chunk_fn = registry.make_chunk_prefill_fn(cfg)
    dims = registry.cache_batch_dims(cfg)

    def slot_chunk_step(params, pool, cslot, ctokens, cpos, cfirst):
        with axis_rules(mesh, rules):
            cache = _reset_if(cfirst, _gather_slot(pool, dims, cslot))
            logits, cache = chunk_fn(params, ctokens[None], cache, cpos)
            pool = _scatter_slot(pool, cache, dims, cslot)
            ctok = jnp.argmax(logits[0], -1).astype(jnp.int32)
        return ctok, pool

    return slot_chunk_step


def make_serve_step(cfg, mesh, *, fsdp: Optional[bool] = None):
    """Fused continuous-batching step: decode all slots + one prefill chunk.

    ``serve_step(params, tokens (slots, 1), pool, cslot, ctokens (C,), cpos,
    cfirst, cemit)`` -> ``(next_tokens (slots, 1), pool)``. A newly admitted
    request's prefill chunk rides inside the same compiled step as the
    in-flight decodes, so admission never stalls decoding. The chunked
    slot's cache is gathered *before* the decode leg and scattered back
    *after* it — the decode leg's garbage write to that slot (it decodes
    every slot unconditionally) is overwritten wholesale, which is what
    makes at-most-one-request-mid-prefill a safe invariant. When ``cemit``
    is set (final chunk of a prompt) the chunk's greedy token is spliced
    into the device-side token feed at ``cslot`` so the request starts
    decoding on the very next step.
    """
    fsdp = (cfg.family == "moe") if fsdp is None else fsdp
    rules = fsdp_rules(fsdp)
    one_slot = _make_one_slot_decode(cfg)
    chunk_fn = registry.make_chunk_prefill_fn(cfg)
    dims = registry.cache_batch_dims(cfg)
    axes = registry.slot_vmap_axes(cfg)

    def serve_step(params, tokens, pool, cslot, ctokens, cpos, cfirst, cemit):
        with axis_rules(mesh, rules):
            cache = _reset_if(cfirst, _gather_slot(pool, dims, cslot))
            logits, pool = jax.vmap(
                one_slot, in_axes=(None, 0, axes), out_axes=(0, axes)
            )(params, tokens, pool)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            clogits, cache = chunk_fn(params, ctokens[None], cache, cpos)
            pool = _scatter_slot(pool, cache, dims, cslot)
            ctok = jnp.argmax(clogits[0], -1).astype(jnp.int32)
            nxt = nxt.at[cslot, 0].set(
                jnp.where(cemit, ctok, nxt[cslot, 0])
            )
        return nxt, pool

    return serve_step


def _encdec_cache_axes(cfg):
    from repro.models import attention

    base = attention.cache_axes(cfg)
    return jax.tree_util.tree_map(
        lambda ax: ("layers",) + ax, base, is_leaf=_is_axes_leaf
    )


def decode_input_specs(cfg, batch: int, max_len: int):
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg)
    )
    caches, extras = registry.decode_state_spec(cfg, batch, max_len)
    token = registry.decode_token_spec(cfg, batch)
    return params, token, caches, extras.get("memory_kv")


def prefill_input_specs(cfg, batch: int, seq: int):
    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg)
    )
    return params, registry.prefill_spec(cfg, batch, seq)
