"""``chip_smoke.py`` off the chip: it refuses the CPU, and its phases run
end to end at a tiny size with the Pallas kernels interpreted.

The script's own entry point insists on a TPU. These tests call its phase
functions directly, on the reduced lm_350m, and steer the kernel dispatch
here (not through an option of the script): ``ops._on_tpu`` answers True so
the hierarchical round takes the Mosaic path, and every ``pallas_call`` is
made in interpret mode, counted to show the kernel ran in the round.
"""

import json
import os
import subprocess
import sys

import pytest
from jax.experimental import pallas as pl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.fixture
def interpreted_kernels(monkeypatch):
    from repro.kernels import ops

    calls = []
    real = pl.pallas_call

    def pallas_call(*a, **kw):
        calls.append(kw.get("grid"))
        return real(*a, **{**kw, "interpret": True})

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    return calls


def test_one_chip_phases_tiny(tmp_path, interpreted_kernels):
    args = chip_smoke.smoke_args(str(tmp_path), ("--reduced",))
    clock = chip_smoke.CompileClock()
    reports = chip_smoke.run_one_chip(
        args, clock, kernel_marker=None, interpret=True
    )
    assert [r["phase"] for r in reports] == ["train", "plan", "hier-int8"]
    train, plan, hier = reports
    assert len(train["losses"]) == chip_smoke.ROUNDS
    assert train["checkpoint_restored_bitwise"]
    assert plan["update_rel_diff"] <= chip_smoke.UPDATE_RTOL
    assert plan["control_update_rel_diff"] > chip_smoke.UPDATE_RTOL
    assert "BROADCAST" in plan["stages"] and "REDUCE" in plan["stages"]
    assert hier["kernel_vs_oracle_int8_steps"] <= chip_smoke.INT8_STEPS
    assert len(hier["losses"]) == chip_smoke.ROUNDS
    assert interpreted_kernels, "the reduce+compress kernel never ran"


def test_one_chip_phases_print_one_line_each(tmp_path, capsys,
                                             interpreted_kernels):
    args = chip_smoke.smoke_args(str(tmp_path), ("--reduced",))
    args.rounds = 1
    chip_smoke.phase_train(args, chip_smoke.CompileClock())
    lines = capsys.readouterr().out.strip().splitlines()
    keys = {line.split("]")[1].split(":")[0].strip() for line in lines}
    assert {"compile_s", "round_s", "losses", "peak_bytes_in_use"} <= keys
    for line in lines:
        json.loads(line.split(": ", 1)[1])  # every value is JSON


def test_four_chip_phases_on_cpu_devices(device_pool):
    """``--chips 4``'s phases on four virtual CPU devices of the pool."""
    out = device_pool.run(f"""
        import sys, json, tempfile
        sys.path.insert(0, {REPO!r})
        import jax
        from jax.experimental import pallas as pl
        import chip_smoke
        from repro.kernels import ops
        real_on_tpu, real_call = ops._on_tpu, pl.pallas_call
        ops._on_tpu = lambda: True
        pl.pallas_call = lambda *a, **kw: real_call(*a, **{{**kw, "interpret": True}})
        try:
            with tempfile.TemporaryDirectory() as d:
                args = chip_smoke.smoke_args(d, ("--reduced",))
                reps = chip_smoke.run_four_chips(
                    args, chip_smoke.CompileClock(), jax.devices()[:4],
                    kernel_marker=None)
        finally:
            ops._on_tpu, pl.pallas_call = real_on_tpu, real_call
        print(json.dumps([
            {{"phase": r["phase"], "diff": r["update_rel_diff"],
              "control": r["control_update_rel_diff"],
              "devices": len(r["memory_per_device"])}} for r in reps]))
    """)
    assert [r["phase"] for r in out] == ["sharded-round", "hier-int8-sharded"]
    for r in out:
        assert r["diff"] <= chip_smoke.UPDATE_RTOL < r["control"]
        assert r["devices"] == 4
