"""Closed-loop read mapping through `ReadMapper.map_batch`.

One client maps the pool's reads in mini-batches of the configuration's
`minibatch_bases` and starts the next when the last returns; the window
ends when the last batch started inside it returns. Set-up builds the
reference and its minimizer index from the seed, simulates the pool
(in seeded chunks, so that a larger pool keeps the reads of a smaller
one), and maps warm-up batches of the same size from a slice of its
own until a pass builds no new program (or the mix's cap).

The service behind the mapper is wrapped so that every alignment it is
asked for in the window is kept with its answer: those are what the
reference checks and what the roofline counts. The index's `lookup` is
wrapped on this instance to time seeding. A batch that raises counts
all its reads as failed and ends the window.
"""

from __future__ import annotations

import time

import numpy as np

import harness
import program
import simulate
from loader import BenchError


class RecordingService:
    """Forwards to the service and keeps (read, ref, future) of each
    alignment submitted while `recording` is set."""

    def __init__(self, service):
        self._service = service
        self.recording = False
        self.calls = []

    def __getattr__(self, name):
        return getattr(self._service, name)

    def submit(self, read, ref, **kw):
        fut = self._service.submit(read, ref, **kw)
        if self.recording:
            self.calls.append((read, ref, fut))
        return fut


def run(ctx: harness.Context) -> dict:
    cfg, tr = ctx.config, ctx.traffic
    genome = simulate.random_genome(cfg["genome_bp"], ctx.rng("genome"))
    index = program.make_index(genome, cfg)
    lookup = index.lookup
    seed_s = [0.0]

    def timed_lookup(read):
        t0 = time.perf_counter()
        try:
            return lookup(read)
        finally:
            seed_s[0] += time.perf_counter() - t0

    index.lookup = timed_lookup
    batch = int(cfg["minibatch_bases"]) // int(tr["read_len"])
    pool = simulate.chunked_reads(genome, int(tr["pool_reads"]),
                                  int(tr["pool_chunk_reads"]),
                                  int(tr["read_len"]), tr["rates"],
                                  tr["rc_prob"], ctx.rng)
    warm = simulate.sample_reads(
        genome, np.full(batch * int(tr["warmup_passes"]), tr["read_len"]),
        tr["rates"], tr["rc_prob"], ctx.rng("warmup"))
    service = program.make_service(cfg, ctx.devices)
    front = RecordingService(service)
    mapper = program.make_mapper(index, front, cfg)
    mapped = program.mapped_status()

    with service:
        passes = harness.warm_until_quiet(
            ctx, lambda k: mapper.map_batch(
                warm.reads[k * batch:(k + 1) * batch]),
            int(tr["warmup_passes"]))
        before = program.service_counters(service)
        seed_s[0] = 0.0
        front.recording = True
        results, failed = [], 0
        with ctx.window(), ctx.span("bench.window"):
            deadline = ctx.t_window + ctx.seconds
            while time.perf_counter() < deadline:
                lo = len(results)
                if lo + batch > len(pool):
                    raise BenchError(f"read pool of {len(pool)} exhausted; "
                                     "raise pool_reads")
                try:
                    with ctx.span("bench.map_batch"):
                        results += mapper.map_batch(
                            pool.reads[lo:lo + batch])
                except Exception as exc:  # the batch's reads are failed
                    ctx.log(f"map_batch failed: {exc!r}")
                    failed = batch
                    break
        front.recording = False
        after = program.service_counters(service)

    hits = sum(1 for k, res in enumerate(results)
               if res.status == mapped
               and res.strand == pool.strands[k]
               and abs(res.ref_start - pool.loci[k]) <= max(res.band, 1))
    answers = [(read, ref, fut.result()) for read, ref, fut in front.calls
               if fut.done() and fut.exception() is None]
    return {
        "reads": len(results),
        "attempted": len(results) + failed,
        "failed": failed,
        "recall_hits": hits,
        "seed_s": seed_s[0],
        "warmup_passes": passes,
        "batch_reads": batch,
        "counters": {k: after[k] - before[k] for k in after},
        "mode": cfg["mode"],
        "answers": answers,
    }
