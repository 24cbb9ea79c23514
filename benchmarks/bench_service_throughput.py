"""Streaming AlignmentService throughput (open-loop arrival sweep).

The serving-layer numbers the co-processor pitch stands on (DESIGN.md
§8): a mixed-length request stream is pushed through the
`repro.serve.AlignmentService` — bounded-queue admission, continuous
length-class micro-batching, depth-k engine pipeline, device-side CIGAR
decode — first closed-loop (submit as fast as admission allows, the
saturation throughput), then open-loop at fractions of that rate (the
latency a client actually sees when the service is not saturated), with
both the static and the adaptive flush policy, and finally under a
bursty (Markov-modulated on/off) arrival process at the same mean rate.

Rows (per backend; pallas rows only with a TPU attached, as in
bench_engine_throughput — interpret mode is not a performance mode):

  service/closed_loop             saturation: reads/s, fill ratio,
                                  p50/p99 latency, dispatches, fetch bytes
  service/closed_loop_persistent  same, engine dispatch="persistent"
                                  (each flush = ONE device program)
  service/open_loop_<f>x          offered rate = f x closed-loop rate,
                                  policy="adaptive" (the headline row:
                                  fill ratio must survive sub-saturation)
  service/open_loop_<f>x_static   same offered schedule, legacy static
                                  min_fill/max_wait policy (the gap row)
  service/open_loop_<f>x_bursty[_static]
                                  Markov-modulated arrivals, same mean
                                  rate — the adaptive policy's reason to
                                  exist
  service/router_closed_loop_<N>r the same closed-loop stream through
                                  the replicated tier (AlignmentRouter
                                  over N single-engine replicas); the
                                  row's derived `scaling` is its rate
                                  over the 1-replica router rate —
                                  the tier's throughput-scaling factor,
                                  regression-gated alongside p99

Every row's `derived` records `offered_rate`, `burstiness`, `policy`,
and `arrival_seed`, so trajectories stay comparable across PRs: the
arrival schedule is a pure function of (n_pairs, rate, burstiness,
seed), never of wall-clock noise. The rest of the `derived` fields are
the service metrics dict flattened — the same numbers
`AlignmentService.stats()` serves live. Recorded into BENCH_engine.json
by CI (`--only engine` matches this module's "engine_service"
registration) and regression-gated by tools/check_bench_regression.py
(fill_ratio and p99 for service/* rows).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.core import MINIMAP2, AlignmentEngine
from repro.serve import AlignmentRouter, AlignmentService

#: Mixed length classes: two dispatch buckets, so the dispatcher really
#: micro-batches (per-class groups) instead of one degenerate bucket.
LENGTHS = (90, 250)

#: Fixed seed of the arrival-process RNG (satellite: trajectories must
#: be comparable across PRs — the schedule depends only on this).
ARRIVAL_SEED = 20240807

#: Bursty mode: arrivals speed up by this factor inside a burst; the
#: inter-burst gap stretches to keep the *mean* offered rate unchanged.
BURST_BOOST = 4.0
BURST_MEAN_LEN = 12


def _request_pool(n_pairs: int, seed: int = 73):
    rng = np.random.default_rng(seed)
    pairs = []
    for k in range(n_pairs):
        L = LENGTHS[k % len(LENGTHS)]
        read = rng.integers(0, 4, L).astype(np.int8)
        ref = read.copy()
        mut = rng.integers(0, L, max(L // 25, 1))
        ref[mut] = (ref[mut] + 1) % 4
        pairs.append((read, ref))
    return pairs


def arrival_schedule(n: int, rate: float, *, burstiness: float = 0.0,
                     seed: int = ARRIVAL_SEED) -> np.ndarray:
    """Offered arrival offsets (seconds from t0) for `n` requests at
    mean rate `rate`.

    burstiness=0 is the uniform open-loop schedule (spacing 1/rate).
    burstiness>0 is a Markov-modulated on/off process: bursts of
    geometric mean length BURST_MEAN_LEN arrive BURST_BOOST x faster
    than the mean, separated by gaps sized so the long-run rate stays
    `rate`; `burstiness` in (0, 1] scales how much of the slack moves
    into the gaps (1 = fully modulated). Deterministic in (n, rate,
    burstiness, seed)."""
    base = 1.0 / rate
    if burstiness <= 0.0:
        return np.arange(n) * base
    rng = np.random.default_rng(seed)
    t, times = 0.0, []
    while len(times) < n:
        burst = max(1, int(rng.geometric(1.0 / BURST_MEAN_LEN)))
        for _ in range(min(burst, n - len(times))):
            times.append(t)
            t += base / BURST_BOOST
        # Stretch the gap so the mean rate is preserved: each burst
        # arrival saved base * (1 - 1/BOOST) seconds.
        t += burstiness * burst * base * (1.0 - 1.0 / BURST_BOOST)
    return np.asarray(times[:n])


def _drive(engine, pairs, *, schedule=None, max_wait_ms: float,
           policy: str = "static"):
    """One service run: submit every pair (at the offered `schedule`
    offsets when open loop), resolve every future, return
    (wall_s, stats)."""
    with AlignmentService(engine, collect_tb=True, max_wait_ms=max_wait_ms,
                          policy=policy) as svc:
        t0 = time.perf_counter()
        futures = []
        for k, (read, ref) in enumerate(pairs):
            if schedule is not None:
                delay = t0 + schedule[k] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            futures.append(svc.submit(read, ref))
        for f in futures:
            f.result()
        wall = time.perf_counter() - t0
        stats = svc.stats()
    return wall, stats


def _drive_router(engines, pairs, *, max_wait_ms: float):
    """One replicated-tier run: the closed-loop stream through an
    `AlignmentRouter` over pre-warmed engines (one replica each)."""
    with AlignmentRouter(len(engines), engine_factory=lambda i: engines[i],
                         collect_tb=True,
                         max_wait_ms=max_wait_ms) as router:
        t0 = time.perf_counter()
        futures = [router.submit(read, ref) for read, ref in pairs]
        for f in futures:
            f.result()
        wall = time.perf_counter() - t0
        stats = router.stats()
    # The aggregate has no single policy name; the tier ran static.
    stats.setdefault("policy", "static")
    return wall, stats


def _derived(engine, stats, wall, n_pairs, *, offered_rate=0.0,
             burstiness=0.0, extra=""):
    return (f"reads_per_s={n_pairs / wall:.4g};"
            f"fill_ratio={stats['fill_ratio']:.2f};"
            f"p50_ms={stats['p50_ms']:.2f};p99_ms={stats['p99_ms']:.2f};"
            f"dispatches={stats['dispatches']};"
            f"bytes_fetched={stats['bytes_fetched']};"
            f"flush_timeout={stats['flush_timeout']};"
            f"flush_stall={stats['flush_stall']};"
            f"policy={stats['policy']};"
            f"offered_rate={offered_rate:.4g};burstiness={burstiness:g};"
            f"arrival_seed={ARRIVAL_SEED};"
            f"dispatch={engine.dispatch}{extra}")


def run(backends=("reference", "pallas"), smoke=False):
    n_pairs = 16 if smoke else 96
    fracs = (0.5,) if smoke else (0.5, 0.8)
    max_wait_ms = 4.0
    pairs = _request_pool(n_pairs)
    for backend in backends:
        if backend == "pallas":
            from repro.kernels.banded_dp.banded_dp import default_interpret
            if default_interpret():
                print("service: pallas rows skipped (interpret mode, "
                      "no TPU)", file=sys.stderr)
                continue
        engine = AlignmentEngine(backend=backend, sc=MINIMAP2, capacity=16)
        # Warm the jit caches: the timed runs measure serving, not XLA
        # compilation of each (bucket, band, t_max) program.
        _drive(engine, pairs, max_wait_ms=max_wait_ms)

        wall, stats = _drive(engine, pairs, max_wait_ms=max_wait_ms)
        closed_rate = n_pairs / wall
        emit("service/closed_loop", wall / n_pairs * 1e6,
             _derived(engine, stats, wall, n_pairs,
                      extra=f";n_pairs={n_pairs}"),
             backend=backend)

        # Persistent-dispatch service: each flush is ONE device program.
        eng_p = AlignmentEngine(backend=backend, sc=MINIMAP2, capacity=16,
                                dispatch="persistent")
        _drive(eng_p, pairs, max_wait_ms=max_wait_ms)  # warm
        wall_p, stats_p = _drive(eng_p, pairs, max_wait_ms=max_wait_ms)
        emit("service/closed_loop_persistent", wall_p / n_pairs * 1e6,
             _derived(eng_p, stats_p, wall_p, n_pairs,
                      extra=f";n_pairs={n_pairs}"),
             backend=backend)

        # Replicated tier at 1 and 2 replicas: same stream, same
        # engines-per-replica config; `scaling` is the 2r/1r throughput
        # ratio (1.0 on the 1r row). Each replica's engine is warmed
        # outside the timed window, like the single-service rows.
        router_rate = {}
        for n_replicas in (1, 2):
            engines = [AlignmentEngine(backend=backend, sc=MINIMAP2,
                                       capacity=16)
                       for _ in range(n_replicas)]
            for eng in engines:
                _drive(eng, pairs, max_wait_ms=max_wait_ms)
            wall_r, stats_r = _drive_router(engines, pairs,
                                            max_wait_ms=max_wait_ms)
            router_rate[n_replicas] = n_pairs / wall_r
            scaling = router_rate[n_replicas] / router_rate[1]
            emit(f"service/router_closed_loop_{n_replicas}r",
                 wall_r / n_pairs * 1e6,
                 _derived(engines[0], stats_r, wall_r, n_pairs,
                          extra=(f";n_pairs={n_pairs}"
                                 f";replicas={n_replicas}"
                                 f";scaling={scaling:.3f}")),
                 backend=backend)

        sweeps = [(frac, 0.0) for frac in fracs]
        sweeps += [(0.8, 1.0)] if not smoke else []
        for frac, burstiness in sweeps:
            rate = closed_rate * frac
            sched = arrival_schedule(n_pairs, rate, burstiness=burstiness)
            tag = (f"service/open_loop_{frac}x"
                   + ("_bursty" if burstiness else ""))
            for policy in ("adaptive", "static"):
                wall_o, stats_o = _drive(engine, pairs, schedule=sched,
                                         max_wait_ms=max_wait_ms,
                                         policy=policy)
                emit(tag + ("_static" if policy == "static" else ""),
                     wall_o / n_pairs * 1e6,
                     _derived(engine, stats_o, wall_o, n_pairs,
                              offered_rate=rate, burstiness=burstiness),
                     backend=backend)
