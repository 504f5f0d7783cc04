"""A cell of BENCHMARK.json shrunk to a size the CPU runs in seconds: the
same files, round kind and limits, at small widths, depth and batch."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

SMALL_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
               "vocab_size": 512}
SMALL_TRAFFIC = {"batch": 2, "seq": 32}


def workloads() -> list:
    """The cells of BENCHMARK.json: (name, its traffic file's contents)."""
    from benchmarks.chip import run

    bench = run._load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return [(w["name"], run.load_cell(w["name"])["traffic"])
            for w in bench["workloads"]]


def files(config: str, traffic: str) -> dict:
    """A configuration and a traffic mix from their files, limits aside."""
    from benchmarks.chip import run

    return {"config": run._load_json(f"{run.HERE}/configs/{config}.json"),
            "traffic": run._load_json(f"{run.HERE}/traffic/{traffic}.json")}


def shrink(cell: dict) -> dict:
    return {**cell, "config": {**cell["config"], **SMALL_MODEL},
            "traffic": {**cell["traffic"], **SMALL_TRAFFIC}}


def tiny_cell(name: str) -> dict:
    from benchmarks.chip import run

    return shrink(run.load_cell(name))
