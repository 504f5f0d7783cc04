"""The nested cell's readers: the kernel's bytes from the config's layout,
exposed collective time and the kernel's roofline share on synthetic
traces."""

from __future__ import annotations

import pytest

from tiny import files
from benchmarks.chip import kernel_bytes, run
from benchmarks.chip.trace import Op, Trace

CELL = ("lm_350m", "hier_int8.p2c4h1")
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
KERNEL_OP = ("jit(round_fn)/drjax.reduce_compress[clients]/shard_map/"
             "jit(reduce_compress_roundtrip)/vmap(reduce_compress_roundtrip)/"
             "pallas_call")


def test_kernel_bytes_of_a_small_layout():
    """2 layers, d 4, 1 head of 4, d_ff 8, vocab 3 (padded to 512 rows):
    every leaf padded to a row of 256 on its own."""
    c = {"num_layers": 2, "d_model": 4, "num_heads": 1, "num_kv_heads": 1,
         "head_dim": 4, "d_ff": 8, "vocab_size": 3, "tie_embeddings": False}
    # embed 512x4 = 2048 (8 rows), lm_head 4x512 (8), final_ln 4 (1),
    # ln1, ln2 2x4 (1 each), wq wk wv wo 2x4x1x4 = 32 (1 each),
    # wi wg wo 2x4x8 = 64 (1 each).
    rows = 8 + 8 + 1 + 2 + 4 + 3
    assert kernel_bytes.rows(c) == rows
    # f32 in, f32 back, int8 out per value; one f32 scale a row.
    assert kernel_bytes.roundtrip_bytes(c) == rows * 256 * 9 + rows * 4
    assert kernel_bytes.roundtrip_bytes(c, groups=2) == rows * 256 * 13 + rows * 4


def test_kernel_bytes_of_the_cell():
    """lm_350m's 469,812,224 parameters fill whole rows: 4.2 GB a call."""
    c = files(*CELL)["config"]
    assert kernel_bytes.rows(c) * 256 == c["full"]["parameters"]
    assert kernel_bytes.roundtrip_bytes(c) == pytest.approx(4.235e9, rel=1e-3)


def _ctx(ops, rounds=1, op_names=None):
    tr = Trace(ops=ops, spans=[Op("sample", 0, 1), Op("readback", 99, 100)])
    lo, hi = tr.window()
    return {"trace": tr, "lo": lo, "hi": hi, "rounds": rounds,
            "peak": PEAK, "config": files(*CELL)["config"],
            "op_names": op_names or {}}


def _read(metric, ctx):
    return run.load_module("metrics", metric).read(ctx)


def test_a_collective_under_compute_is_not_exposed():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}"
    ctx = _ctx({0: [Op("%fusion.1 = f32[8]{0} fusion(%x)", 0, 50),
                    Op(ar, 10, 40)]})
    assert _read("collective_exposed_ms", ctx) == 0.0


def test_a_collective_alone_reads_its_length():
    """10 ns alone of the all-reduce, 20 ns of an async all-gather's done
    half, over 2 rounds, on the worse of two devices."""
    ctx = _ctx({0: [Op("%fusion.1 = f32[8]{0} fusion(%x)", 0, 30),
                    Op("%all-reduce.1 = f32[8]{0} all-reduce(%f)", 20, 40),
                    Op("all-gather-done.3", 60, 80)],
                1: [Op("%all-reduce.1 = f32[8]{0} all-reduce(%f)", 20, 30)]},
               rounds=2)
    assert _read("collective_exposed_ms", ctx) == pytest.approx(30 / 2 / 1e6)


def test_an_async_collective_runs_from_its_start_to_its_done():
    """An async all-reduce runs from its start op to its done op: the
    transfer between them is exposed where nothing else runs (10 to 40) and
    hidden where a fusion does (60 to 90, under a fusion from 65 to 85)."""
    start = "%all-reduce-start.{} = f32[8]{{0}} all-reduce-start(%f)"
    done = "%all-reduce-done.{0} = f32[8]{{0}} all-reduce-done(%all-reduce-start.{0})"
    ctx = _ctx({0: [Op(start.format(1), 10, 12), Op(done.format(1), 38, 40),
                    Op(start.format(2), 60, 62), Op(done.format(2), 88, 90),
                    Op("%fusion.1 = f32[8]{0} fusion(%x)", 65, 85)]})
    assert _read("collective_exposed_ms", ctx) == pytest.approx(40 / 1e6)


def test_roofline_reads_nothing_without_the_kernel():
    ctx = _ctx({0: [Op("%fusion.1 = f32[8]{0} fusion(%x)", 0, 50)]},
               op_names={"fusion.1": "jit(round_fn)/drjax.reduce_mean[pods]"
                                     "/reduce_sum"})
    assert _read("reduce_compress_roundtrip_roofline", ctx) is None


def test_roofline_of_the_kernel_at_the_chips_bandwidth():
    """Kernel ops lasting exactly the cell's bytes at 819 GB/s read 100 %,
    their time a union over the slower device."""
    c = files(*CELL)["config"]
    ns = kernel_bytes.roundtrip_bytes(c) / PEAK["hbm_bytes_per_s"] * 1e9
    name = "vmap_reduce_compress_roundtrip_.1"
    ops = {0: [Op(f"%{name} = f32[1] custom-call()", 0, ns / 2),
               Op(f"%{name} = f32[1] custom-call()", ns / 4, ns)],
           1: [Op(f"%{name} = f32[1] custom-call()", 0, ns / 2)]}
    ctx = _ctx(ops, rounds=1, op_names={name: KERNEL_OP})
    ctx["hi"] = 2 * ns
    assert _read("reduce_compress_roundtrip_roofline", ctx) == pytest.approx(
        100.0)
