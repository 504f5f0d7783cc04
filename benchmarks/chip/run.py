#!/usr/bin/env python3
"""Benchmark one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``, whose ``round`` names ``rounds/<round>.py``)
and the limits of its output check (``limits/<cell>.json``); each per-layer
metric is read by ``metrics/<metric>.py``. Adding a cell or a metric adds
files and entries, and edits none.

A round kind, ``rounds/<round>.py``, defines ``build(c, t, devices,
**variant) -> program.Round`` and may set three attributes, which
:func:`round_kind` resolves:

* ``REFERENCE``: the file name, under this directory, of the kind's plain
  reference (default ``"reference"``). A reference imports nothing of the
  program under test (``src/repro``) and defines ``frozen(c)``,
  ``init(frozen_c, words)``, ``run_round(c, t, p, batch, quant=None) ->
  (params, loss)``, where ``quant="fp8"`` is the control, and
  ``leaf_change_norms(p, p0)``;
* ``VARIANTS``: the program's own lower-precision paths, a variant's name
  to the ``build`` keyword arguments that switch it on (default none);
  ``control.py`` runs each against the reference;
* ``FAULTS``: faults that only this kind can have, a name to a wrapper of
  the compiled round as in ``faults.py``, whose faults every kind has.

A run: weights and server state from the seed in one jitted call; the
round compiled ahead of time; three rounds through the timed executable,
fed by the program's own ``CohortSampler`` as ``launch.train`` feeds it,
whose losses and parameter changes the output check reads, and one more
(set-up ends here); then the window, one round at a time for
``--seconds``, each sampled, dispatched, waited for and its loss read back. Any compile in the
window fails the run. After the window the program's state is freed and the
round kind's plain reference runs the same three rounds on the batches the
sampler gave the program; ``check.py`` decides ``correct``. ``--trace 1``
records the window with the profiler and reports the per-layer metrics
instead of the end-to-end ones.

The last line of stdout is the result as JSON. Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# Run as a script, this directory heads sys.path, where trace.py would
# shadow the standard library's trace; the modules here import as
# benchmarks.chip.* instead.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
# The persistent compilation cache, at a fixed path inside the checkout so
# that every run of a cell after the first finds its programs.
CACHE_DIR = os.path.join(HERE, ".jax_cache")
CHECK_ROUNDS = 3
# What every round kind's reference module defines.
REFERENCE_API = ("frozen", "init", "run_round", "leaf_change_norms")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def round_kind(t: dict, here: str = HERE) -> types.SimpleNamespace:
    """The round kind of traffic mix ``t`` (see the module docstring): its
    ``build``, its ``reference`` module, its ``variants`` and its
    ``faults``, those of ``faults.py`` among them."""
    from benchmarks.chip.faults import FAULTS as SHARED_FAULTS

    module = load_module("rounds", t["round"], here)
    where = module.__file__
    name = getattr(module, "REFERENCE", "reference")
    if not os.path.isfile(os.path.join(here, f"{name}.py")):
        raise FileNotFoundError(f"{where}: REFERENCE {name!r} is no file "
                                f"{name}.py in {here}")
    ref = load_module("", name, here)
    missing = [f for f in REFERENCE_API if not callable(getattr(ref, f, None))]
    own = getattr(module, "FAULTS", {})
    variants = getattr(module, "VARIANTS", {})
    clash = (set(own) & set(SHARED_FAULTS)) | (
        set(variants) & {"sound", "control", *SHARED_FAULTS, *own})
    if missing:
        raise ValueError(f"{where}: its reference {ref.__file__} lacks "
                         f"{missing}")
    if clash:
        raise ValueError(f"{where}: FAULTS or VARIANTS take the names "
                         f"{sorted(clash)}, which faults.py or control.py use")
    return types.SimpleNamespace(build=module.build, reference=ref,
                                 variants=variants,
                                 faults={**SHARED_FAULTS, **own})


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell of ``<root>/BENCHMARK.json`` needs, found by name:
    its entry, config, traffic, limits and the metrics it reports."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    limits = _load_json(os.path.join(HERE, "limits", f"{name}.json"))

    def mine(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in reported]
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic, "limits": limits, "end_to_end": end_to_end,
            "per_layer": per_layer}


class CompileClock:
    """Backend compiles and persistent-cache hits, as JAX reports them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.cache_hits


def hbm_bytes(compiled) -> int:
    """Bytes the compiled round needs on one device: arguments, outputs and
    temporaries, less what outputs alias from donated arguments."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _flat_floats(tree) -> dict:
    import benchmarks.chip.weights as weights

    return {k: float(v) for k, v in weights.flatten(tree).items()}


def sampler(t: dict, vocab_size: int, seed: int):
    """The program's cohort sampler over its group-keyed corpus, both seeded
    from ``seed``, as ``launch.train`` builds them."""
    from repro.data.grouped import CohortSampler, GroupedCorpus

    corpus = GroupedCorpus(vocab_size=vocab_size,
                           num_groups=t["corpus_groups"], seed=seed)
    return CohortSampler(corpus, cohort_size=t["cohort"], seed=seed)


def round_batch(sample, t: dict, r: int) -> dict:
    """Round ``r``'s batch from the sampler, as ``launch.train`` takes it."""
    data = sample.round_batch(r, t["local_steps"], t["batch"], t["seq"])
    return {"tokens": data["tokens"], "labels": data["labels"]}


def tokens_per_round(t: dict) -> int:
    return t["cohort"] * t["local_steps"] * t["batch"] * t["seq"]


def check_rounds(step, rnd, ref, batches, words, params, sstate):
    """The first rounds through ``step`` from the seed's weights, one on
    each of ``batches``: (params, server state, readings), the readings
    being the rounds' losses and the per-leaf norms (``ref``'s
    ``leaf_change_norms``) of the change of the parameters after the first
    round and after the last."""
    import jax

    out = {"losses": []}
    for r, batch in enumerate(batches):
        params, sstate, metrics = jax.block_until_ready(
            step(params, sstate, rnd.place(batch)))
        out["losses"].append(float(metrics["loss"]))
        if r in (0, CHECK_ROUNDS - 1):
            p0, _ = rnd.init(words)
            out[f"change{r + 1}"] = _flat_floats(
                ref.leaf_change_norms(params, p0))
            del p0
    return params, sstate, out


def reference_rounds(ref, c, t, batches, words, quant=None) -> dict:
    """The same readings of the plain reference ``ref``'s rounds on the
    same batches, on one chip."""
    cf = ref.frozen(c)
    out = {"losses": []}
    p = ref.init(cf, words)
    for r, batch in enumerate(batches):
        p, loss = ref.run_round(c, t, p, batch, quant)
        out["losses"].append(loss)
        if r in (0, CHECK_ROUNDS - 1):
            out[f"change{r + 1}"] = _flat_floats(
                ref.leaf_change_norms(p, ref.init(cf, words)))
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, break_step=None) -> dict:
    """Run one cell on ``devices``; return the result line as a dict.
    ``break_step(step) -> step`` replaces the timed round (tests plant
    faults with it)."""
    import jax

    from benchmarks.chip import check, flops, legs
    from benchmarks.chip import trace as trace_lib, weights

    c, t = cell["config"], cell["traffic"]
    kind = round_kind(t)
    rnd = kind.build(c, t, devices)
    clock = CompileClock()
    words = weights.seed_array(seed)
    sample = sampler(t, c["vocab_size"], seed)

    marks = {"built": time.perf_counter() - T_START}
    params, sstate = rnd.init(words)
    batches = [round_batch(sample, t, r) for r in range(CHECK_ROUNDS)]
    compiled = rnd.step.lower(params, sstate, rnd.place(batches[0])).compile()
    marks["compiled"] = time.perf_counter() - T_START
    hbm = hbm_bytes(compiled)
    step = compiled if break_step is None else break_step(compiled)

    # The first rounds go through the timed executable and feed; the output
    # check reads their losses and parameter changes, and the reference
    # later runs on the same batches.
    params, sstate, prog = check_rounds(step, rnd, kind.reference, batches,
                                        words, params, sstate)
    marks["checked"] = time.perf_counter() - T_START
    # One more round after the check freed its copy of the start weights, so
    # that the window opens on the memory layout the rounds keep.
    params, sstate, _ = jax.block_until_ready(step(
        params, sstate, rnd.place(round_batch(sample, t, CHECK_ROUNDS))))
    # Collect now and exempt what set-up built from later collections, so
    # that no full pass over the compiler's objects lands in the window.
    gc.collect()
    gc.freeze()

    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)
    mark = clock.mark()
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    rounds, failed, r, times = 0, 0, CHECK_ROUNDS + 1, []
    while True:
        t_round = time.perf_counter()
        with jax.profiler.TraceAnnotation("sample"):
            host = round_batch(sample, t, r)
        with jax.profiler.TraceAnnotation("dispatch"):
            out = step(params, sstate, rnd.place(host))
        with jax.profiler.TraceAnnotation("wait"):
            params, sstate, metrics = jax.block_until_ready(out)
        with jax.profiler.TraceAnnotation("readback"):
            loss = float(metrics["loss"])
        failed += not math.isfinite(loss)
        times.append(time.perf_counter() - t_round)
        rounds += 1
        r += 1
        if time.perf_counter() - t_window >= seconds:
            break
    window_s = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    compiles = (clock.compiles - mark[0], clock.cache_hits - mark[1])
    log("set-up marks, s: " + ", ".join(f"{k} {v:.2f}" for k, v in marks.items()))
    log(f"set-up {setup_s:.2f} s: {clock.compiles} compiles "
        f"({clock.seconds:.2f} s), {clock.cache_hits} cache hits; "
        f"window: {rounds} rounds in {window_s:.3f} s, compiles {compiles[0]}, "
        f"cache reads {compiles[1]}")
    log("round s: " + " ".join(f"{x:.4f}" for x in times))
    log(f"memory {rnd.devices[0].memory_stats()}")
    if compiles != (0, 0):
        raise RuntimeError(f"{compiles[0]} compiles and {compiles[1]} cache "
                           "reads inside the measured window")
    tokens_per_s = rounds * tokens_per_round(t) / window_s

    stats = [d.memory_stats() or {} for d in rnd.devices]
    device_kind = rnd.devices[0].device_kind
    device = {"platform": rnd.devices[0].platform, "kind": device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                       for s in stats)}
    result = {"correct": False, "attempted": rounds, "failed": failed,
              "metrics": {}, "device": device}

    if trace:
        tr = trace_lib.load(trace_lib.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        lo, hi = tr.window()
        ctx = {"trace": tr, "lo": lo, "hi": hi, "rounds": rounds,
               "tokens_per_s": tokens_per_s, "chips": len(rnd.devices),
               "peak": flops.peaks(device_kind), "config": c, "traffic": t,
               "op_names": legs.op_names(compiled.as_text())}
        for m in cell["per_layer"]:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        busy = {d: trace_lib.busy(ops, lo, hi) for d, ops in tr.ops.items()}
        if not busy:
            raise RuntimeError("the trace holds no device ops")
        device["busy_s"] = sum(trace_lib.total(b) for b in busy.values()
                               ) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ops = sorted(trace_lib.op_seconds(tr, lo, hi).items(),
                     key=lambda kv: -kv[1])[:10]
        worst = min(busy, key=lambda d: trace_lib.total(busy[d]))
        gaps = sorted(trace_lib.idle_gaps(tr.ops[worst], lo, hi),
                      key=lambda g: g[0] - g[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[trace_lib.label(g, tr.spans), (g[1] - g[0]) / 1e9]
                          for g in gaps]}
    else:
        values = {"tokens_per_s": tokens_per_s, "hbm_gb": hbm / 1e9,
                  "setup_s": setup_s}
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    # The reference runs once the program's state is freed, on one chip.
    del params, sstate, metrics, out, step, compiled
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_rounds(kind.reference, c, t, batches, words)
    log(f"reference {time.perf_counter() - t_ref:.2f} s; losses program "
        f"{prog['losses']} reference {ref['losses']}")

    log(f"leaves left out: {sorted(set(ref['change1']) - set(check.kept(ref)))}")
    numbers = check.readings(prog, ref)
    limits = cell["limits"]
    result["correct"] = check.judge(numbers, limits) and failed == 0
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in check.NUMBERS}
    for k in check.NUMBERS:
        log(f"check {k} {numbers[k]!r} limit {limits[k]!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    cell = load_cell(opts.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"run.py: JAX found no TPU (platform {devices[0].platform!r})")
        return 2
    if len(devices) < cell["chips"]:
        log(f"run.py: the cell needs {cell['chips']} chips, JAX sees "
            f"{len(devices)}")
        return 2
    result = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace),
                      devices[:cell["chips"]])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
