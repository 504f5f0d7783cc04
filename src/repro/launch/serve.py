"""Continuous-batching serve runtime over a slot-based KV-cache pool.

CPU-scale demo (reduced config):

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_3b --reduced \
        --requests 8 --max-new 16

Two schedulers share the same compiled building blocks
(:mod:`repro.launch.steps` slot-pool steps):

* :class:`ContinuousBatchingScheduler` — the production path. Requests are
  admitted *per step* from an arrival queue; a new request's prefill chunks
  ride inside the same compiled step as the in-flight decodes (fused
  ``make_serve_step``), so admission never stalls decoding and a finished
  slot is reassigned on the next step with no reallocation.
* :class:`StaticWaveScheduler` — the baseline the benchmark compares
  against: admit a wave, prefill it, decode it in lockstep, drain it
  entirely before admitting the next wave.

Admission model
---------------
At most ONE request is mid-prefill at any time. Its slot cache is gathered
*before* the fused step's decode leg and scattered back *after* it, so the
decode leg (which decodes every slot unconditionally — fixed shapes, no
masks) can never corrupt a partial prefill. Free slots decode garbage the
host discards. A request is admitted when a slot is free and
``prompt_len + max_new <= max_len``; its slot is zero-reset by the first
chunk (``cfirst``), so slot reuse is allocation-free for the life of the
server.

Bucketing knobs
---------------
Prompts are cut into power-of-two chunks ``<= chunk`` (greedy, largest
first, NO padding — padding would corrupt recurrent rglru/rwkv state). The
executable set is therefore bounded: one fused step per chunk bucket plus
one decode-only step, regardless of traffic. ``TraceCounter`` wraps both
legs; the steady-state invariant is *flat trace counts under arbitrary
traffic* (``prefill_traces`` / ``decode_traces``), asserted in
``tests/test_serve.py`` and ``benchmarks/serve.py``.

Donation posture
----------------
The slot pool is the scheduler's round-to-round state: every compiled step
donates it (``donate_argnums``) so XLA updates the fixed ``(slots, ...)``
buffers in place — no per-token cache copies, no allocation after startup.
Params are never donated (they serve every step); the token feed is not
donated because the host still fetches the *previous* step's tokens while
the next step runs.

Async-dispatch discipline
-------------------------
The host stays one step ahead of the device: step ``t`` is dispatched
before the host does bookkeeping for step ``t-1`` (one batched
``jax.device_get`` per step — never per-request scalar pulls), so
admission, slot bookkeeping, EOS handling and detokenization-equivalents
overlap the device compute. Greedy sampling chains on device
(``next_tokens`` feeds the next step without a host round-trip).

Termination: a slot stops as soon as ``cfg.eos_id`` is emitted (the EOS
token is kept in ``generated``) or after ``max_new`` tokens.
"""

from __future__ import annotations

import argparse
import json
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.runtime.executor import TraceCounter

DEFAULT_CHUNK = 16


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    arrival: float = 0.0  # seconds on the scheduler clock
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # timing (scheduler-clock seconds; filled by the schedulers)
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    token_times: List[float] = field(default_factory=list)


def chunk_schedule(n: int, chunk_max: int) -> List[int]:
    """Greedy binary decomposition of a prompt length into power-of-two
    chunks ``<= chunk_max``.

    Exact (no padding — padded positions would advance recurrent
    rglru/rwkv state) and bounded: every prompt length maps into the same
    ``log2(chunk_max)+1`` chunk buckets, so the compiled-step set stays
    fixed under arbitrary traffic.
    """
    if n <= 0 or chunk_max <= 0:
        raise ValueError(f"need n > 0 and chunk_max > 0, got {n}, {chunk_max}")
    out = []
    c = 1 << (chunk_max.bit_length() - 1)
    while n:
        while c > n:
            c >>= 1
        out.append(c)
        n -= c
    return out


@dataclass
class _Slot:
    req: Request
    chunks: List[int]
    pos: int = 0
    first: bool = True
    phase: str = "prefill"  # prefill | decode


class _SchedulerBase:
    """Shared slot-pool state + host-side bookkeeping.

    ``fault_hook(step_index)`` is the scheduler-level chaos hook: it is
    called once per dispatched-or-idle scheduler step with the monotonic
    1-based ``step_index`` and may raise (e.g.
    :class:`~repro.runtime.failure.SimulatedDeviceFailure`) to simulate a
    serving-fleet fault mid-run. A harness that catches the fault calls
    :meth:`reset_slots` and re-submits the unfinished requests — slot reuse
    is allocation-free (the first prefill chunk zero-resets a slot), so
    recovery never reallocates the pool and never retraces a compiled step.
    """

    def __init__(self, cfg, params, slots: int, max_len: int,
                 chunk: int = DEFAULT_CHUNK, mesh=None, fault_hook=None):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len, self.chunk = slots, max_len, chunk
        self.eos_id = cfg.eos_id
        self.fault_hook = fault_hook
        self.step_index = 0  # monotonic across run() calls
        self._pool = registry.init_slot_pool(cfg, slots, max_len)
        self._tokens = jnp.zeros((slots, 1), jnp.int32)
        self._slots: List[Optional[_Slot]] = [None] * slots
        self._prefill_counter = TraceCounter()
        self._decode_counter = TraceCounter()
        decode_step = steps_lib.make_slot_decode_step(cfg, mesh)
        self._decode = jax.jit(
            self._decode_counter.wrap(decode_step), donate_argnums=(2,)
        )

    def _tick(self) -> None:
        self.step_index += 1
        if self.fault_hook is not None:
            self.fault_hook(self.step_index)

    def reset_slots(self) -> None:
        """Drop all in-flight work after a fault: free every slot and zero
        the token feed. The pool buffers are kept — a reused slot is
        zero-reset by its first chunk — and trace counters are untouched,
        so post-recovery steps hit the same executables."""
        self._slots = [None] * self.slots
        self._tokens = jnp.zeros((self.slots, 1), jnp.int32)

    @property
    def prefill_traces(self) -> int:
        """Compiled-prefill trace count: one per chunk bucket, then flat."""
        return self._prefill_counter.count

    @property
    def decode_traces(self) -> int:
        """Decode trace count: one (fixed slot shapes), then flat."""
        return self._decode_counter.count

    def _check(self, req: Request):
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds max_len {self.max_len}"
            )

    def _collect(self, tokens_np, meta, clock: float) -> int:
        """Apply one fetched step's tokens to the requests that produced
        them. ``meta`` is the (slot, request) list snapshotted at dispatch —
        a request that finished in the interim (one-step dispatch lag)
        contributes no further tokens. Returns #requests finished."""
        ndone = 0
        for slot, req in meta:
            if req.done:
                continue
            tok = int(tokens_np[slot, 0])
            req.generated.append(tok)
            req.token_times.append(clock)
            if req.t_first is None:
                req.t_first = clock
            hit_eos = self.eos_id is not None and tok == self.eos_id
            if hit_eos or len(req.generated) >= req.max_new:
                req.done = True
                req.t_done = clock
                self._slots[slot] = None  # slot freed; reassigned, not realloc'd
                ndone += 1
        return ndone


class ContinuousBatchingScheduler(_SchedulerBase):
    """Per-step admission; prefill chunks fused into the decode step."""

    def __init__(self, cfg, params, slots: int, max_len: int,
                 chunk: int = DEFAULT_CHUNK, mesh=None, fault_hook=None):
        super().__init__(cfg, params, slots, max_len, chunk, mesh, fault_hook)
        serve_step = steps_lib.make_serve_step(cfg, mesh)
        # one trace per chunk bucket (ctokens shape specializes the step)
        self._serve = jax.jit(
            self._prefill_counter.wrap(serve_step), donate_argnums=(2,)
        )
        self._mid_prefill: Optional[int] = None

    def reset_slots(self) -> None:
        super().reset_slots()
        self._mid_prefill = None

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Drive all ``requests`` to completion, honoring ``arrival`` times
        on the scheduler clock (which advances by measured step wall time).
        """
        reqs = sorted(requests, key=lambda r: r.arrival)
        for r in reqs:
            self._check(r)
        clock = 0.0
        arrive_i = 0
        waiting: Deque[Request] = deque()
        pending: Deque[Tuple[jax.Array, list]] = deque()
        remaining = len(reqs)

        while remaining:
            t0 = time.perf_counter()
            self._tick()
            while arrive_i < len(reqs) and reqs[arrive_i].arrival <= clock:
                waiting.append(reqs[arrive_i])
                arrive_i += 1

            # admission: one request per step, single-mid-prefill invariant
            if self._mid_prefill is None and waiting:
                free = next(
                    (i for i, s in enumerate(self._slots) if s is None), None
                )
                if free is not None:
                    req = waiting.popleft()
                    self._slots[free] = _Slot(
                        req=req,
                        chunks=chunk_schedule(len(req.prompt), self.chunk),
                    )
                    self._mid_prefill = free

            meta = [
                (i, s.req)
                for i, s in enumerate(self._slots)
                if s is not None and s.phase == "decode"
            ]
            dispatched = True
            if self._mid_prefill is not None:
                i = self._mid_prefill
                st = self._slots[i]
                c = st.chunks.pop(0)
                ctokens = jnp.asarray(
                    st.req.prompt[st.pos : st.pos + c], jnp.int32
                )
                emit = not st.chunks
                self._tokens, self._pool = self._serve(
                    self.params,
                    self._tokens,
                    self._pool,
                    jnp.asarray(i, jnp.int32),
                    ctokens,
                    jnp.asarray(st.pos, jnp.int32),
                    jnp.asarray(st.first),
                    jnp.asarray(emit),
                )
                st.pos += c
                st.first = False
                if emit:  # chunk token spliced into the feed at slot i
                    st.phase = "decode"
                    self._mid_prefill = None
                    meta.append((i, st.req))
            elif meta:
                self._tokens, self._pool = self._decode(
                    self.params, self._tokens, self._pool
                )
            else:
                dispatched = False

            if dispatched:
                pending.append((self._tokens, meta))

            # host bookkeeping for earlier steps while this one runs on
            # device; keep exactly one step in flight
            while len(pending) > (1 if dispatched else 0):
                toks, m = pending.popleft()
                arr = np.asarray(jax.device_get(toks))  # one batched fetch
                remaining -= self._collect(arr, m, clock)

            if not dispatched and not pending:
                # idle: jump the clock to the next arrival
                if arrive_i < len(reqs):
                    clock = max(clock, reqs[arrive_i].arrival)
                continue
            clock += time.perf_counter() - t0

        return {r.rid: r.generated for r in reqs}


class StaticWaveScheduler(_SchedulerBase):
    """Wave-at-a-time baseline: admit up to ``slots`` requests, prefill them
    one by one (chunk steps into their slots), decode the wave in lockstep,
    and drain it completely before admitting the next wave. Shares the
    per-slot decode step (and chunk decomposition) with the continuous
    scheduler, so its outputs are the greedy oracle the continuous path is
    tested token-identical against — only the *scheduling* differs.
    """

    def __init__(self, cfg, params, batch: int, max_len: int,
                 chunk: int = DEFAULT_CHUNK, mesh=None, fault_hook=None):
        super().__init__(cfg, params, batch, max_len, chunk, mesh, fault_hook)
        self.batch = batch
        chunk_step = steps_lib.make_slot_chunk_step(cfg, mesh)
        self._chunk = jax.jit(
            self._prefill_counter.wrap(chunk_step), donate_argnums=(1,)
        )

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        reqs = sorted(requests, key=lambda r: r.arrival)
        for r in reqs:
            self._check(r)
        clock = 0.0
        arrive_i = 0
        waiting: Deque[Request] = deque()
        ndone = 0
        while ndone < len(reqs):
            while arrive_i < len(reqs) and reqs[arrive_i].arrival <= clock:
                waiting.append(reqs[arrive_i])
                arrive_i += 1
            if not waiting:
                clock = max(clock, reqs[arrive_i].arrival)
                continue
            wave = [waiting.popleft()
                    for _ in range(min(self.batch, len(waiting)))]
            clock = self._run_wave(wave, clock)
            ndone += len(wave)
        return {r.rid: r.generated for r in reqs}

    def run_wave(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Single-wave entry point (legacy API used by older tests)."""
        assert len(requests) <= self.batch
        self._run_wave(list(requests), 0.0)
        return {r.rid: r.generated for r in requests}

    def _run_wave(self, wave: List[Request], clock: float) -> float:
        # --- prefill, one request at a time into its slot ---
        first = np.zeros((self.slots, 1), np.int32)
        for slot, req in enumerate(wave):
            t0 = time.perf_counter()
            self._tick()
            pos, cfirst, ctok = 0, True, None
            for c in chunk_schedule(len(req.prompt), self.chunk):
                ctok, self._pool = self._chunk(
                    self.params,
                    self._pool,
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(req.prompt[pos : pos + c], jnp.int32),
                    jnp.asarray(pos, jnp.int32),
                    jnp.asarray(cfirst),
                )
                pos += c
                cfirst = False
            self._slots[slot] = _Slot(req=req, chunks=[], phase="decode")
            # wave-granular sync: the baseline blocks once per request here
            tok = int(ctok)
            clock += time.perf_counter() - t0
            first[slot, 0] = tok
            req.generated.append(tok)
            req.token_times.append(clock)
            req.t_first = clock
            if (self.eos_id is not None and tok == self.eos_id) or req.max_new <= 1:
                req.done = True
                req.t_done = clock
                self._slots[slot] = None

        # --- lockstep decode with the one-step-lag batched-fetch loop ---
        self._tokens = jnp.asarray(first)
        prev = None
        while True:
            t0 = time.perf_counter()
            self._tick()
            meta = [
                (i, s.req) for i, s in enumerate(self._slots) if s is not None
            ]
            dispatched = bool(meta)
            if dispatched:
                self._tokens, self._pool = self._decode(
                    self.params, self._tokens, self._pool
                )
            if prev is not None:
                toks, m = prev
                arr = np.asarray(jax.device_get(toks))  # one batched fetch
                self._collect(arr, m, clock)
                prev = None
            if not dispatched:
                break
            prev = (self._tokens, meta)
            clock += time.perf_counter() - t0
        for slot in range(self.slots):
            self._slots[slot] = None
        return clock


# legacy name: the static scheduler is the old BatchScheduler's successor
BatchScheduler = StaticWaveScheduler


def poisson_trace(rng, n: int, rate: float) -> List[float]:
    """Arrival times for ``n`` requests at ``rate`` req/s (Poisson process)."""
    gaps = rng.exponential(1.0 / rate, size=n)
    return list(np.cumsum(gaps))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b", choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scheduler", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=DEFAULT_CHUNK)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at t=0")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder or cfg.family == "vlm":
        raise SystemExit("serve runtime targets token-only decoder archs")

    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    arrivals = (
        poisson_trace(rng, args.requests, args.rate)
        if args.rate > 0
        else [0.0] * args.requests
    )
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=(args.prompt_len,))
            .astype(np.int32),
            max_new=args.max_new,
            arrival=arrivals[i],
        )
        for i in range(args.requests)
    ]
    cls = (
        ContinuousBatchingScheduler
        if args.scheduler == "continuous"
        else StaticWaveScheduler
    )
    sched = cls(cfg, params, args.slots,
                max_len=args.prompt_len + args.max_new, chunk=args.chunk)
    t0 = time.time()
    results = sched.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in results.values())
    ttfts = [r.t_first - r.arrival for r in reqs]
    print(json.dumps({
        "arch": cfg.name,
        "scheduler": args.scheduler,
        "requests": len(reqs),
        "generated_tokens": total_tokens,
        "wall_s": round(dt, 2),
        "tokens_per_s": round(total_tokens / dt, 1),
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
        "prefill_traces": sched.prefill_traces,
        "decode_traces": sched.decode_traces,
        "pool_mb": round(
            registry.slot_pool_bytes(cfg, args.slots,
                                     args.prompt_len + args.max_new) / 2**20,
            2,
        ),
    }))


if __name__ == "__main__":
    main()
