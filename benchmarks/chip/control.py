#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs, the control and the faults.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 12 --faulty 4 \\
        [--out readings.json]

In one process, with the round compiled once, for each of ``--seeds``
seeds (2**32 + 1000 + i by default) the program's first rounds through the
timed executable are compared with the round kind's plain reference on the
same sampler's batches (``check.py``'s numbers): the sound readings, whose
largest is a limit's lower reading. For the first ``--faulty`` seeds it
also compares, against the same reference,

* ``control``: the reference computed in float8 (its ``quant="fp8"``) in
  the program's place;
* each of the round kind's ``VARIANTS``, the program's own lower-precision
  paths (``program_int8``, the int8 compression of the client deltas, for
  ``local_sgd``);
* each of the round kind's faults (``faults.py``'s and its own
  ``FAULTS``), planted under the timed executable, but ``unchanged``, which
  reads 1 by construction and is not run.

Each row also holds ``check.judge``'s verdict on each under the cell's
limits. Prints one JSON line per seed and a summary: the largest sound and
least variant reading of each number, and every (variant, seed) the limits
judge correct. Exits 1 where a variant is judged correct or a sound run is
not. Needs the cell's chips, like run.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import run  # noqa: E402

# Faults not run: their readings are known without a run.
NOT_RUN = ("unchanged",)


def readings(cell, devices, seeds, faulty, log=print):
    """One row per seed: the sound readings and, on the first ``faulty``
    seeds, each variant's readings and ``check.judge``'s verdict on them
    under the cell's limits. Returns (rows, summary)."""
    from benchmarks.chip import check, weights

    c, t = cell["config"], cell["traffic"]
    kind = run.round_kind(t)
    builds = {"sound": kind.build(c, t, devices)}
    for name, options in kind.variants.items():
        builds[name] = kind.build(c, t, devices, **options)
    faults = {k: f for k, f in kind.faults.items() if k not in NOT_RUN}
    compiled = {}

    def program(name, words, batches):
        """``name``: "sound", a variant or a fault of the round kind."""
        key = name if name in builds else "sound"
        build = builds[key]
        params, sstate = build.init(words)
        if key not in compiled:
            compiled[key] = build.step.lower(
                params, sstate, build.place(batches[0])).compile()
        step = compiled[key]
        if name in faults:
            step = faults[name](step)
        params, sstate, out = run.check_rounds(step, build, kind.reference,
                                               batches, words, params, sstate)
        del params, sstate
        return out

    rows = []
    for i, seed in enumerate(seeds):
        words = weights.seed_array(seed)
        sample = run.sampler(t, c["vocab_size"], seed)
        batches = [run.round_batch(sample, t, r)
                   for r in range(run.CHECK_ROUNDS)]
        runs = {"sound": program("sound", words, batches)}
        if i < faulty:
            for name in (*faults, *kind.variants):
                runs[name] = program(name, words, batches)
        ref = run.reference_rounds(kind.reference, c, t, batches, words)
        if i < faulty:
            runs["control"] = run.reference_rounds(kind.reference, c, t,
                                                   batches, words, quant="fp8")
        row = {"seed": seed,
               "losses": {"program": runs["sound"]["losses"],
                          "reference": ref["losses"]}}
        for name, out in runs.items():
            numbers = check.readings(out, ref)
            row[name] = {**numbers,
                         "correct": check.judge(numbers, cell["limits"])}
        log(json.dumps(row))
        rows.append(row)
    return rows, summarize(rows)


def summarize(rows) -> dict:
    """The largest sound reading of each number, the least of each
    variant's, and every (variant, seed) the limits judge correct: for the
    sound runs that list should be empty, and so for every other."""
    from benchmarks.chip import check

    names = sorted({n for r in rows for n in r} - {"seed", "losses"})
    out = {"judged_correct": {n: [r["seed"] for r in rows
                                  if n in r and r[n]["correct"]]
                              for n in names}}
    out["judged_wrong_sound"] = [r["seed"] for r in rows
                                 if not r["sound"]["correct"]]
    for n in names:
        pick = max if n == "sound" else min
        out[f"{n}_{pick.__name__}"] = {
            k: pick(r[n][k] for r in rows if n in r) for k in check.NUMBERS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulty", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2**32 + 1000)
    ap.add_argument("--out")
    opts = ap.parse_args(argv)
    cell = run.load_cell(opts.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        run.log(f"control.py: needs {cell['chips']} TPU chips, found "
                f"{len(devices)} {devices[0].platform}")
        return 2
    seeds = [opts.first_seed + i for i in range(opts.seeds)]
    rows, summary = readings(cell, devices[:cell["chips"]], seeds, opts.faulty)
    print(json.dumps(summary), flush=True)
    if opts.out:
        with open(opts.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    passed = {n: s for n, s in summary["judged_correct"].items()
              if n != "sound" and s}
    if passed or summary["judged_wrong_sound"]:
        run.log(f"control.py: variants judged correct {passed}; sound seeds "
                f"judged not correct {summary['judged_wrong_sound']}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
