"""Closed-loop co-processor traffic through `AlignmentService.submit`.

An upstream pipeline keeps `outstanding_per_chip` x (mesh devices)
(read, true window) pairs in flight: each completed pair frees a slot
for the next. Set-up simulates the pool from the seed, compiles the
service's warm-up lengths as `launch/serve.py --warmup` does, then runs
warm-up passes of the same loop from a slice of its own until a pass
compiles nothing new.

The window stops submitting after `--seconds` and ends when the last
pair submitted inside it completes.
"""

from __future__ import annotations

import threading
import time

import harness
import program
import simulate
from loader import BenchError


def closed_loop(ctx, service, reads, refs, outstanding: int,
                deadline: float, *, use_all: bool = False):
    """Submit pairs in order, keeping `outstanding` in flight, until
    `deadline` (or, with `use_all`, until the pairs run out); wait for
    all. Returns the futures in submit order."""
    slots = threading.Semaphore(outstanding)
    futures = []
    k = 0
    while True:
        with ctx.span("bench.wait"):
            slots.acquire()
        if time.perf_counter() >= deadline:
            slots.release()
            break
        if k >= len(reads):
            if use_all:
                slots.release()
                break
            raise BenchError(f"pair pool of {len(reads)} exhausted; "
                             "raise pool_pairs")
        with ctx.span("bench.submit"):
            fut = harness.submit(service, reads[k], refs[k])
        fut.add_done_callback(lambda _f: slots.release())
        futures.append(fut)
        k += 1
        if fut.done() and fut.exception() is not None:
            break       # the service refuses all further requests
    with ctx.span("bench.wait"):
        for fut in futures:
            fut.exception()
    return futures


def run(ctx: harness.Context) -> dict:
    cfg, tr = ctx.config, ctx.traffic
    genome = simulate.random_genome(cfg["genome_bp"], ctx.rng("genome"))
    outstanding = int(tr["outstanding_per_chip"]) * max(
        int(cfg.get("mesh_devices", 1)), 1)
    pool, refs = simulate.pair_pool(genome, tr, int(tr["pool_pairs"]),
                                    ctx.rng("pool"))
    per_pass = 2 * outstanding
    n_warm = per_pass * int(tr["warmup_passes"])
    warm, warm_refs = simulate.pair_pool(genome, tr, n_warm,
                                         ctx.rng("warmup"))
    service = program.make_service(cfg, ctx.devices)
    if cfg.get("warmup"):
        program.warm_service(service, warm.reads, warm_refs)

    def warm_pass(k):
        part = slice(k * per_pass, (k + 1) * per_pass)
        closed_loop(ctx, service, warm.reads[part], warm_refs[part],
                    outstanding, float("inf"), use_all=True)

    with service:
        passes = harness.warm_until_quiet(ctx, warm_pass,
                                          int(tr["warmup_passes"]))
        before = program.service_counters(service)
        with ctx.window(), ctx.span("bench.window"):
            futures = closed_loop(ctx, service, pool.reads, refs,
                                     outstanding, ctx.t_window + ctx.seconds)
        after = program.service_counters(service)

    failed = sum(1 for f in futures if f.exception() is not None)
    answers = [(pool.reads[k], refs[k], f.result())
               for k, f in enumerate(futures) if f.exception() is None]
    return {
        "reads": len(futures) - failed,
        "attempted": len(futures),
        "failed": failed,
        "warmup_passes": passes,
        "shards": service.engine.num_shards,
        "counters": {k: after[k] - before[k] for k in after},
        "mode": cfg["mode"],
        "answers": answers,
    }

