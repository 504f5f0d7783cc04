"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes and no NaNs.

The FULL configs are checked by their widths and parameter counts only.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry

ASSIGNED = [a for a in registry.ARCH_IDS if not a.startswith("lm_")]


def _batch(cfg, b=2, s=16):
    return registry.make_concrete_batch(cfg, b, s, jax.random.PRNGKey(1))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_forward_loss_finite(arch):
    cfg = registry.get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    loss = registry.loss_fn(cfg, params, _batch(cfg))
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_updates_params_no_nans(arch):
    cfg = registry.get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    loss_fn = functools.partial(registry.loss_fn, cfg)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        p = jax.tree_util.tree_map(
            lambda w, gw: (w.astype(jnp.float32) - 0.01 * gw.astype(jnp.float32)
                           ).astype(w.dtype), p, g)
        return p, loss

    new_params, loss = step(params)
    assert jnp.isfinite(loss)
    # params changed and stayed finite
    changed = 0
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(new_params)):
        assert jnp.all(jnp.isfinite(b.astype(jnp.float32))), arch
        if not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32)):
            changed += 1
    assert changed > 0, f"{arch}: no parameter changed"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_matches_assignment(arch):
    """The full (non-reduced) configs carry the exact assigned shapes."""
    cfg = registry.get_config(arch)
    expected = {
        "phi35_moe": (32, 4096, 32, 8, 6400, 32064, 16, 2),
        "qwen3_moe": (94, 4096, 64, 4, 1536, 151936, 128, 8),
        "llava_next_34b": (60, 7168, 56, 8, 20480, 64000, 0, 0),
        "internlm2_20b": (48, 6144, 48, 8, 16384, 92544, 0, 0),
        "stablelm_3b": (32, 2560, 32, 32, 6912, 50304, 0, 0),
        "qwen2_72b": (80, 8192, 64, 8, 29568, 152064, 0, 0),
        "yi_34b": (60, 7168, 56, 8, 20480, 64000, 0, 0),
        "seamless_m4t_medium": (12, 1024, 16, 16, 4096, 256206, 0, 0),
        "recurrentgemma_2b": (26, 2560, 10, 1, 7680, 256000, 0, 0),
        "rwkv6_3b": (32, 2560, 40, 0, 8960, 65536, 0, 0),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size, cfg.num_experts, cfg.experts_per_token)
    assert got == expected, f"{arch}: {got} != {expected}"


def test_param_counts_in_expected_range():
    """Sanity: derived parameter counts are near the published sizes."""
    cases = {
        "qwen2_72b": (65e9, 80e9),
        "yi_34b": (30e9, 38e9),
        "internlm2_20b": (17e9, 23e9),
        "stablelm_3b": (2.3e9, 3.6e9),
        "rwkv6_3b": (2.2e9, 3.6e9),
        "recurrentgemma_2b": (2.0e9, 3.6e9),
        "phi35_moe": (38e9, 46e9),
        "qwen3_moe": (200e9, 260e9),
    }
    for arch, (lo, hi) in cases.items():
        n = registry.get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.1f}B outside [{lo/1e9},{hi/1e9}]"


def test_param_count_matches_init():
    """The config's parameter formula counts what ``init_params`` makes."""
    for arch in ("stablelm_3b", "phi35_moe", "rwkv6_3b"):
        cfg = registry.get_config(arch).reduced()
        params = registry.init_params(jax.random.PRNGKey(0), cfg)
        actual = sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params)
        )
        # account for vocab padding to multiples of 512
        padded = dataclasses.replace(
            cfg, vocab_size=-(-cfg.vocab_size // 512) * 512
        )
        expected = padded.param_count()
        assert abs(actual - expected) / expected < 0.25, (
            f"{arch}: init {actual} vs formula {expected}"
        )


def test_active_params_moe():
    """MoE active params are far below total (a6.6b / a22b naming)."""
    for arch, (lo, hi) in {
        "phi35_moe": (5e9, 9e9),
        "qwen3_moe": (15e9, 26e9),
    }.items():
        cfg = registry.get_config(arch)
        n = cfg.active_param_count()
        assert lo <= n <= hi, f"{arch}: active {n/1e9:.1f}B"
        assert n < cfg.param_count() / 2


@pytest.mark.parametrize("arch", ["stablelm_3b", "phi35_moe", "rwkv6_3b",
                                  "recurrentgemma_2b", "llava_next_34b"])
def test_prefill_decode_consistency(arch):
    """greedy decode after prefill == argmax of the train-mode forward."""
    cfg = registry.get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    if cfg.family == "vlm":
        rng = jax.random.PRNGKey(3)
        embeds = jax.random.normal(rng, (B, 4, cfg.d_model), jnp.float32)
        tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
        from repro.models import transformer
        logits_full, _, _ = transformer.forward(
            cfg, params, tokens, embeds=embeds, mode="train"
        )
        last_from_forward = logits_full[:, -1]
        last_from_prefill, _ = transformer.prefill(
            cfg, params, tokens, embeds=embeds
        )
    else:
        tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                    cfg.vocab_size)
        from repro.models import transformer
        logits_full, _, _ = transformer.forward(cfg, params, tokens,
                                                mode="train")
        last_from_forward = logits_full[:, -1]
        last_from_prefill, _ = transformer.prefill(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(last_from_prefill, np.float32),
        np.asarray(last_from_forward, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("arch", ["stablelm_3b", "rwkv6_3b",
                                  "recurrentgemma_2b"])
def test_incremental_decode_matches_full_forward(arch):
    """Decoding token-by-token reproduces the full-sequence logits."""
    cfg = registry.get_config(arch).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    from repro.models import transformer

    B, S = 1, 8
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                                cfg.vocab_size)
    logits_full, _, _ = transformer.forward(cfg, params, tokens, mode="train")

    # prefill on the first token only, then decode the rest step by step
    last, caches = transformer.prefill(cfg, params, tokens[:, :1], max_len=S)
    outs = [last]
    for t in range(1, S):
        last, caches = transformer.decode_step(cfg, params, tokens[:, t:t+1],
                                               caches)
        outs.append(last)
    stacked = jnp.stack(outs, axis=1)  # (B, S, V)
    np.testing.assert_allclose(
        np.asarray(stacked, np.float32),
        np.asarray(logits_full, np.float32),
        rtol=5e-2, atol=5e-2,
    )
