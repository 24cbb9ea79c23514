"""End-to-end read-mapping throughput (seed -> chain -> align).

The closed-loop number for the WHOLE front end the paper assumes around
the accelerator (Fig. 2(a)): minimizer seeding against a reference
index, jit'd anchor chaining, and banded semiglobal alignment of the
candidate windows through the streaming `AlignmentService` — measured
as reads mapped per second, with ground-truth recall recorded on the
same row so a "speedup" that trades away accuracy is caught by the
regression gate, not hidden by it.

Rows (per backend; pallas rows emit only with a TPU attached, as in
bench_engine_throughput — interpret mode is not a performance mode):

  mapper/closed_loop             saturation mapping rate: reads/s,
                                 recall, mapped/seed_capped counts,
                                 service fill ratio and p99
  mapper/closed_loop_persistent  same pipeline, engine
                                 dispatch="persistent"

Traffic is SKEWED, not uniform: `HOT_FRAC` of reads are drawn from a
hot region covering `HOT_REGION` of the reference (pinned-start
sampling), the rest uniformly — hot-region seeds concentrate index
lookups and alignment windows exactly the way real coverage piles up on
popular loci. The read set is a pure function of
(n_reads, ARRIVAL_SEED), and the `derived` string records the offered
traffic (`offered=closed_loop`, `hot_frac`, `hot_region`,
`arrival_seed`, profile and read length) so trajectories stay
comparable across PRs. Recorded into BENCH_engine.json by CI (`--only
engine` matches this module's "engine_mapper" registration) and gated
by tools/check_bench_regression.py: us_per_call growth > 25% or an
absolute recall drop > 0.005 fails the PR.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import emit
from repro.core import MINIMAP2, AlignmentEngine
from repro.data.genome import ReadSimulator, random_genome
from repro.map import MinimizerIndex, ReadMapper, STATUS_MAPPED, \
    STATUS_SEED_CAPPED
from repro.serve import AlignmentService

#: Fixed seed of the read/arrival process (satellite: trajectories must
#: be comparable across PRs — traffic depends only on this).
ARRIVAL_SEED = 20240808

GENOME_LEN = 200_000
READ_LEN = 150
PROFILE = "illumina"

#: Skew: this fraction of reads comes from a hot region covering
#: HOT_REGION of the reference.
HOT_FRAC = 0.3
HOT_REGION = 0.1


def _read_set(genome, n_reads: int):
    """n_reads simulated reads with ground-truth loci: HOT_FRAC drawn
    from the hot prefix region, the rest uniform, order shuffled
    deterministically."""
    sim = ReadSimulator(genome, PROFILE, seed=ARRIVAL_SEED, rc_prob=0.5)
    rng = np.random.default_rng(ARRIVAL_SEED)
    hot_hi = int(len(genome) * HOT_REGION) - READ_LEN
    reads = []
    for is_hot in rng.random(n_reads) < HOT_FRAC:
        start = int(rng.integers(0, hot_hi)) if is_hot else None
        reads.append(sim.sample(READ_LEN, start=start))
    return reads


def _drive(index, sim_reads, dispatch: str, backend: str):
    engine = AlignmentEngine(backend=backend, sc=MINIMAP2, capacity=32,
                             dispatch=dispatch, xdrop=400)
    raw = [sr.read for sr in sim_reads]
    with AlignmentService(engine, mode="semiglobal",
                          max_wait_ms=2.0) as svc:
        mapper = ReadMapper(index, svc, window_pad=24)
        mapper.map_batch(raw[:8])  # warm the dispatch signatures
        t0 = time.perf_counter()
        results = mapper.map_batch(raw)
        wall = time.perf_counter() - t0
        stats = svc.stats()
    return results, wall, stats


def run(backends=("reference", "pallas"), smoke=False):
    n_reads = 32 if smoke else 256
    genome = random_genome(GENOME_LEN, seed=7)
    index = MinimizerIndex(genome, k=13, w=8)
    sim_reads = _read_set(genome, n_reads)

    for backend in backends:
        if backend == "pallas":
            from repro.kernels.banded_dp.banded_dp import default_interpret
            if default_interpret():
                print("bench_mapper: skipping pallas rows (interpret "
                      "mode, no TPU)", file=sys.stderr)
                continue
        for dispatch in ("pipelined", "persistent"):
            results, wall, stats = _drive(index, sim_reads, dispatch,
                                          backend)
            mapped = sum(1 for r in results if r.status == STATUS_MAPPED)
            capped = sum(1 for r in results
                         if r.status == STATUS_SEED_CAPPED)
            correct = sum(
                1 for sr, r in zip(sim_reads, results)
                if r.status == STATUS_MAPPED and r.strand == sr.strand
                and abs(r.ref_start - sr.locus) <= max(r.band, 1))
            name = ("mapper/closed_loop" if dispatch == "pipelined"
                    else "mapper/closed_loop_persistent")
            emit(name, wall / n_reads * 1e6,
                 f"reads_per_s={n_reads / wall:.1f};"
                 f"recall={correct / n_reads:.4f};"
                 f"mapped={mapped};seed_capped={capped};"
                 f"n_reads={n_reads};offered=closed_loop;"
                 f"hot_frac={HOT_FRAC};hot_region={HOT_REGION};"
                 f"arrival_seed={ARRIVAL_SEED};profile={PROFILE};"
                 f"read_len={READ_LEN};"
                 f"fill_ratio={stats['fill_ratio']:.2f};"
                 f"p99_ms={stats['p99_ms']:.1f}",
                 backend=backend)


if __name__ == "__main__":
    run()
