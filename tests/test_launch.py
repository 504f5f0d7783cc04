"""Launch-layer integration: step builders lower + compile on a small mesh.

Mini version of the production dry-run (8 fake devices, reduced configs),
covering every family's train/prefill/decode step builders end to end —
run inside the shared multi-device worker (see conftest.device_pool) so the
device count doesn't leak into this process and jax import + compile cache
are paid once per session.
"""

import textwrap

import pytest

_PRELUDE = """
    import json
    import jax, jax.numpy as jnp
    from repro import compat
    from repro.launch import steps as steps_lib, mesh as mesh_lib
    from repro.models import registry
    mesh = mesh_lib.make_mesh(
        (jax.device_count() // 2, 2), ("data", "model"))
"""


def _run(device_pool, body: str) -> dict:
    return device_pool.run(
        textwrap.dedent(_PRELUDE) + textwrap.dedent(body)
    )


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm_3b", "phi35_moe", "rwkv6_3b"])
def test_train_step_compiles_on_mesh(device_pool, arch):
    res = _run(device_pool, f"""
        cfg = registry.get_config("{arch}").reduced(
            d_model=64, num_heads=4, head_dim=16, vocab_size=512,
            dtype="bfloat16", attn_impl="blocked", q_block=8, kv_block=8)
        step, shardings_for = steps_lib.make_sgd_train_step(cfg, mesh)
        specs = steps_lib.train_input_specs(cfg, 8, 32, mesh)
        in_sh, out_sh = shardings_for(specs)
        compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=(0, 1)).lower(*specs).compile()
        print(json.dumps({{"ok": True,
                           "flops": compat.cost_analysis(compiled).get(
                               "flops", 0)}}))
    """)
    assert res["ok"] and res["flops"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm_3b", "recurrentgemma_2b"])
def test_decode_step_compiles_on_mesh(device_pool, arch):
    res = _run(device_pool, f"""
        cfg = registry.get_config("{arch}").reduced(
            d_model=64, num_heads=4, head_dim=16, vocab_size=512,
            dtype="bfloat16")
        step, shardings_for = steps_lib.make_decode_step(cfg, mesh)
        params, token, caches, memkv = steps_lib.decode_input_specs(cfg, 8, 64)
        shs = shardings_for((params, token, caches, memkv))
        compiled = jax.jit(step, in_shardings=shs[:3],
                           donate_argnums=(2,)).lower(
            params, token, caches).compile()
        print(json.dumps({{"ok": True}}))
    """)
    assert res["ok"]


@pytest.mark.slow
def test_drjax_round_step_compiles_on_mesh(device_pool):
    res = _run(device_pool, """
        cfg = registry.get_config("lm_350m").reduced(
            d_model=64, num_heads=4, head_dim=16, vocab_size=512,
            dtype="bfloat16", attn_impl="blocked", q_block=8, kv_block=8)
        step, param_sh, server_sh, data_sh_fn = steps_lib.make_drjax_round_step(
            cfg, mesh, partition_size=8, num_local_steps=2)
        specs = steps_lib.drjax_round_specs(
            cfg, partition_size=8, num_local_steps=2, local_batch=2, seq=32)
        data_sh = jax.tree_util.tree_map(data_sh_fn, specs[2])
        compiled = jax.jit(step, in_shardings=(param_sh, server_sh, data_sh),
                           donate_argnums=(0, 1)).lower(*specs).compile()
        hlo = compiled.as_text()
        print(json.dumps({"ok": True,
                          "has_allreduce": "all-reduce" in hlo}))
    """)
    assert res["ok"]
    assert res["has_allreduce"]  # the cross-group reduction shards


@pytest.mark.slow
def test_int8_prefill_variant_compiles(device_pool):
    res = _run(device_pool, """
        cfg = registry.get_config("qwen2_72b").reduced(
            d_model=64, num_heads=8, num_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=512, dtype="bfloat16",
            attn_impl="blocked", q_block=8, kv_block=8)
        step, shardings_for = steps_lib.make_prefill_step(
            cfg, mesh, tp_comm="int8")
        specs = steps_lib.prefill_input_specs(cfg, 8, 32)
        compiled = jax.jit(step, in_shardings=shardings_for(specs)).lower(
            *specs).compile()
        hlo = compiled.as_text()
        n_s8 = sum(1 for l in hlo.splitlines()
                   if "all-gather" in l and "s8[" in l)
        print(json.dumps({"ok": True, "s8": n_s8}))
    """)
    assert res["ok"]
    assert res["s8"] >= 1


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, and the helper
    then sets nothing; unset, it is the fixed <repo>/.jax_cache."""
    import os

    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        want = os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.REPO_ROOT, "ROADMAP.md"))
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.CACHE_ENV, want)
    try:
        assert compile_cache.enable_compile_cache() == want
        after = jax.config.jax_compilation_cache_dir
        assert after == (before if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
