"""Model FLOPs and the table of peaks."""

from __future__ import annotations

import pytest

from tiny import files
from benchmarks.chip import flops


def _config():
    return files("lm_1b", "local_sgd.c2h4")["config"]


def test_param_count_is_the_programs():
    """At any depth the benchmark counts the parameters the program's own
    config counts (``benchmarks/table1_flops.py`` takes 6 N from it)."""
    import dataclasses

    from repro.models import registry

    c = _config()
    cfg = registry.get_config("lm_1b")
    for layers in (c["num_layers"], c["full"]["num_layers"], cfg.num_layers):
        assert flops.param_count({**c, "num_layers": layers}) == (
            dataclasses.replace(cfg, num_layers=layers).param_count())


def test_full_depth_holds_table1_parameters():
    """The assumed full depth gives the paper's 1e9 parameters."""
    c = _config()
    full = {**c, **c["full"]}
    assert flops.param_count(full) == c["full"]["parameters"]
    assert flops.param_count(full) == pytest.approx(
        c["source_gives"]["parameters"], rel=0.01)


def test_matmul_params_of_the_cell():
    assert flops.matmul_params(_config()) == 738_197_504


def test_flops_per_token_adds_attention():
    round_flops = flops.flops_per_token(_config(), 512) * 2 * 4 * 4 * 512
    assert round_flops == pytest.approx(7.46e13, rel=2e-3)


def test_known_device_has_peaks():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
