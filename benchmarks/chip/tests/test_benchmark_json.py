"""BENCHMARK.json keeps to its contract, and every entry resolves to its
files by name."""

from __future__ import annotations

import ast
import json
import os
import re
import shutil

import pytest

import tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = os.path.join(run.ROOT, "BENCHMARK.json")


@pytest.fixture(scope="module")
def bench():
    with open(BENCH) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(BENCH) <= 64 * 1024


def test_entries_have_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [x["name"] for k in ("configs", "workloads") for x in bench[k]]
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in bench[k]]
    for name in names + metrics:
        assert NAME.match(name), name
    assert len(set(metrics)) == len(metrics)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_workload_resolves(bench):
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert hasattr(run.load_module("rounds", cell["traffic"]["round"]),
                       "build")
        assert set(cell["limits"]) == {"loss_gap", "grad1_gap",
                                       "change3_gap"}
        ends = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in ends and len(ends) >= 2
        assert cell["per_layer"], w["name"]


def test_every_workload_resolves_its_round_kind(bench):
    """A reference with the functions the harness calls and no import of
    the program, and the names of the rows control.py writes distinct."""
    for w in bench["workloads"]:
        kind = run.round_kind(run.load_cell(w["name"])["traffic"])
        for f in run.REFERENCE_API:
            assert callable(getattr(kind.reference, f)), (w["name"], f)
        with open(kind.reference.__file__) as src:
            tree = ast.parse(src.read())
        imported = [a.name for n in ast.walk(tree)
                    if isinstance(n, ast.Import) for a in n.names]
        imported += [n.module for n in ast.walk(tree)
                     if isinstance(n, ast.ImportFrom) and n.module]
        assert not [m for m in imported if m.split(".")[0] == "repro"]
        rows = ["sound", "control", *kind.variants, *kind.faults]
        assert len(set(rows)) == len(rows), (w["name"], rows)


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(run.load_module("metrics", m["name"]).read)
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}


def test_config_files_name_what_they_cut(bench):
    for c in bench["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
        for key in c["reduced"]:
            assert body[key] != body["full"][key]


def test_an_entry_without_files_fails(tmp_path, bench):
    bad = dict(bench)
    bad["workloads"] = [dict(bench["workloads"][0], name="lm_1b.nothing",
                             traffic="no_such_traffic")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bad))
    (tmp_path / "benchmarks" / "chip" / "configs").mkdir(parents=True)
    for c in bench["configs"]:
        shutil.copy(os.path.join(run.ROOT, c["file"]),
                    tmp_path / c["file"])
    with pytest.raises(FileNotFoundError):
        run.load_cell("lm_1b.nothing", root=str(tmp_path))
    with pytest.raises(FileNotFoundError):
        run.load_module("metrics", "no_such_metric")
    with pytest.raises(KeyError):
        run.load_cell("not_a_cell")
