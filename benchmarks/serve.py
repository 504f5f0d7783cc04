"""Continuous batching vs static waves under a heavy-tailed arrival trace.

Drives both serve schedulers (:mod:`repro.launch.serve`) over the SAME
Poisson-arrival / lognormal-length request trace at a reduced config and
reports sustained throughput and latency percentiles:

* ``tokens_per_s`` / ``requests_per_s`` — sustained rates over the trace
  (scheduler-clock duration: the clock advances by measured step wall time
  and jumps over idle gaps);
* ``ttft_p50`` / ``ttft_p99`` — time-to-first-token (first-token clock
  minus arrival; for static waves this includes waiting for earlier waves
  to drain, which is exactly the effect continuous batching removes);
* ``itl_p50`` / ``itl_p99`` — inter-token latency, pooled across requests.

Both schedulers are warmed on a bucket-covering trace first (a prompt of
``2 * chunk - 1`` tokens touches every power-of-two chunk bucket), then the
measured run asserts the steady-state invariant: ZERO new traces under
arbitrary traffic (``prefill_traces`` / ``decode_traces`` flat).

The times are the host's wall clock on whatever backend runs the
schedulers; the report is printed as JSON. Run it as:

    PYTHONPATH=src python -m benchmarks.serve [--smoke]
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import numpy as np

from repro.launch.serve import (
    ContinuousBatchingScheduler,
    Request,
    StaticWaveScheduler,
)
from repro.models import registry

ARCH = "stablelm_3b"


def heavy_tailed_trace(rng, n: int, rate: float = 1.0, *,
                       mean_prompt: float = 10.0, mean_out: float = 8.0,
                       sigma: float = 0.8, max_prompt: int = 48,
                       max_out: int = 24):
    """Lognormal prompt/output lengths + Poisson arrivals — the traffic
    shape that collapses static waves (one straggler pins a whole wave)."""
    prompts = np.clip(
        rng.lognormal(np.log(mean_prompt), sigma, n), 1, max_prompt
    ).astype(int)
    outs = np.clip(
        rng.lognormal(np.log(mean_out), sigma, n), 1, max_out
    ).astype(int)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    return [
        {"prompt_len": int(p), "max_new": int(o), "arrival": float(a)}
        for p, o, a in zip(prompts, outs, arrivals)
    ]


def rescale_arrivals(trace, rate: float):
    """Rescale a unit-rate Poisson trace to ``rate`` req/s (gaps are
    exponential, so dividing timestamps by the rate is exact)."""
    return [dict(t, arrival=t["arrival"] / rate) for t in trace]


def _requests(trace, rng, vocab: int) -> List[Request]:
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, vocab, (t["prompt_len"],)).astype(np.int32),
            max_new=t["max_new"],
            arrival=t["arrival"],
        )
        for i, t in enumerate(trace)
    ]


def _metrics(reqs: List[Request]) -> dict:
    tokens = sum(len(r.generated) for r in reqs)
    duration = max(max(r.token_times) for r in reqs if r.token_times)
    ttft = np.array([r.t_first - r.arrival for r in reqs])
    gaps = np.concatenate(
        [np.diff(r.token_times) for r in reqs if len(r.token_times) > 1]
        or [np.zeros(1)]
    )
    return {
        "tokens": tokens,
        "duration_s": round(float(duration), 4),
        "tokens_per_s": round(tokens / duration, 1),
        "requests_per_s": round(len(reqs) / duration, 2),
        "ttft_p50_s": round(float(np.percentile(ttft, 50)), 4),
        "ttft_p99_s": round(float(np.percentile(ttft, 99)), 4),
        "itl_p50_s": round(float(np.percentile(gaps, 50)), 5),
        "itl_p99_s": round(float(np.percentile(gaps, 99)), 5),
    }


def bench(n: int = 24, slots: int = 4, chunk: int = 8, seed: int = 0,
          rate: Optional[float] = None, smoke: bool = False) -> dict:
    cfg = registry.get_config(ARCH).reduced()
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    max_len = 48 + 24
    cont = ContinuousBatchingScheduler(cfg, params, slots, max_len, chunk)
    stat = StaticWaveScheduler(cfg, params, slots, max_len, chunk)

    # --- warmup: touch every chunk bucket + the decode-only step ---
    def warm_reqs(rng):
        return [
            Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               (2 * chunk - 1,))
                    .astype(np.int32), max_new=4)
            for i in range(2)
        ]

    rng = np.random.default_rng(seed + 1)
    cont.run(warm_reqs(rng))
    stat.run(warm_reqs(rng))
    warm_traces = (cont.prefill_traces, cont.decode_traces,
                   stat.prefill_traces, stat.decode_traces)

    trace = heavy_tailed_trace(np.random.default_rng(seed), n)
    if rate is None:
        # calibrate offered load to this host: measure steady-state token
        # capacity with every slot busy, then overload 3x so the duration
        # is capacity-bound — that's where slot utilization (what the two
        # schedulers actually differ in) shows up as sustained tokens/s
        rng = np.random.default_rng(seed + 2)
        calib = [
            Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               (2 * chunk - 1,))
                    .astype(np.int32), max_new=16)
            for i in range(slots)
        ]
        cont.run(calib)
        toks = sum(len(r.generated) for r in calib)
        dur = max(max(r.token_times) for r in calib)
        mean_tokens = float(np.mean([t["max_new"] for t in trace]))
        rate = 3.0 * (toks / dur) / mean_tokens
    trace = rescale_arrivals(trace, rate)
    rng = np.random.default_rng(seed + 3)
    reqs_c = _requests(trace, rng, cfg.vocab_size)
    rng = np.random.default_rng(seed + 3)
    reqs_s = _requests(trace, rng, cfg.vocab_size)

    out_c = cont.run(reqs_c)
    out_s = stat.run(reqs_s)

    # steady-state invariant: flat trace counts under arbitrary traffic
    now_traces = (cont.prefill_traces, cont.decode_traces,
                  stat.prefill_traces, stat.decode_traces)
    assert now_traces == warm_traces, (
        f"serve steps retraced after bucket warmup: {warm_traces} -> "
        f"{now_traces}"
    )
    # scheduling must not change results: token-identical outputs
    assert all(out_c[i] == out_s[i] for i in out_c), (
        "continuous and static schedulers diverged on the same trace"
    )

    point = {
        "arch": ARCH,
        "requests": n,
        "slots": slots,
        "chunk": chunk,
        "rate_req_per_s": round(float(rate), 3),
        "pool_mb": round(
            registry.slot_pool_bytes(cfg, slots, max_len) / 2**20, 3
        ),
        "prefill_traces": cont.prefill_traces,
        "decode_traces": cont.decode_traces,
        "continuous": _metrics(reqs_c),
        "static": _metrics(reqs_s),
    }
    point["tokens_per_s_ratio"] = round(
        point["continuous"]["tokens_per_s"] / point["static"]["tokens_per_s"],
        3,
    )
    point["ttft_p99_ratio"] = round(
        point["static"]["ttft_p99_s"]
        / max(point["continuous"]["ttft_p99_s"], 1e-9),
        3,
    )
    if not smoke:
        assert point["tokens_per_s_ratio"] > 1.0, (
            "continuous batching did not beat static waves on sustained "
            f"tokens/s: {point}"
        )
        assert point["ttft_p99_ratio"] > 1.0, (
            "continuous batching did not beat static waves on p99 TTFT: "
            f"{point}"
        )
    return point


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run; skips the perf-ordering assertions "
                         "(still asserts flat traces + token identity)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args()
    n = args.requests or (8 if args.smoke else 24)
    t0 = time.time()
    point = bench(n=n, rate=args.rate, smoke=args.smoke)
    point["bench_wall_s"] = round(time.time() - t0, 1)
    import json

    print(json.dumps(point, indent=2))


if __name__ == "__main__":
    main()
