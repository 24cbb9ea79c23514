"""Alignment serving launcher — the paper's co-processor role.

A thin client of the streaming `repro.serve.AlignmentService`: a
simulated sequencer emits read/window pairs at an open-loop arrival
rate, the service's background dispatcher micro-batches them by length
class and drives the mesh-sharded AlignmentEngine's dispatch pipeline
(device decode, depth-k lookahead), and the run reports the service
metrics dict — requests/s, p50/p99 latency, batch fill ratio, bytes
fetched, flush causes. The same binary on a TPU slice serves the
production mesh (the dry-run compiles exactly this dispatch at 16x16
and 2x16x16).

`--replicas N` (N > 1) serves the stream through the replicated tier
instead: an `repro.serve.AlignmentRouter` over N single-engine
replicas (DESIGN.md §11) — scale-OUT by dispatcher count, where the
mesh is scale-UP by device count, so the replicated path runs each
replica mesh-free.

Compiled programs persist in JAX's compilation cache
(`core.engine.enable_compilation_cache`: JAX_COMPILATION_CACHE_DIR when
set, else one fixed directory in the checkout), so a restarted replica
deserialises its dispatch programs instead of recompiling them.

    PYTHONPATH=src python -m repro.launch.serve --reads 512 --rate 2000 \
        --policy adaptive --warmup

    PYTHONPATH=src python -m repro.launch.serve --reads 512 --replicas 2
"""

from __future__ import annotations

import argparse
import time

import jax

from repro import obs
from repro.configs.rapidx import CONFIG as RAPIDX
from repro.core.engine import AlignmentEngine, enable_compilation_cache
from repro.data.genome import ReadSimulator, random_genome
from repro.launch.mesh import make_debug_mesh
from repro.serve import AlignmentRouter, AlignmentService


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reads", type=int, default=512,
                    help="total requests to stream through the service")
    ap.add_argument("--read-len", type=int, default=150,
                    help="base read length; the stream mixes 1x/2x")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in reads/s "
                         "(0 = closed loop, submit as fast as accepted)")
    ap.add_argument("--profile", default="illumina")
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--policy", choices=("static", "adaptive"),
                    default="adaptive",
                    help="flush policy: 'adaptive' holds bursty "
                         "sub-saturation traffic for fill inside a latency "
                         "budget; 'static' is the fixed min_fill/max_wait "
                         "rule")
    ap.add_argument("--depth", default="auto",
                    help="pipeline depth (max in-flight groups): an "
                         "integer, or 'auto' to autotune against measured "
                         "enqueue/finalize latency")
    ap.add_argument("--dispatch", choices=("pipelined", "persistent"),
                    default="pipelined",
                    help="engine dispatch mode; 'persistent' runs each "
                         "flush as ONE device program (single device, "
                         "implies --no-mesh)")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile the stream's dispatch signatures "
                         "before accepting traffic")
    ap.add_argument("--xdrop", type=int, default=None,
                    help="X-drop early-termination threshold: retire a "
                         "pair once its band max falls this far below "
                         "its running best (status != 0 in results; the "
                         "rejected counter / rejected_fraction gauge in "
                         "the metrics). Default: off")
    ap.add_argument("--no-mesh", action="store_true",
                    help="single-device engine (skip shard_map)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving-tier replica count: >1 routes the "
                         "stream through an AlignmentRouter over N "
                         "single-engine replicas with drain/failover "
                         "(scale-out; each replica runs mesh-free)")
    args = ap.parse_args()
    if args.reads <= 0:
        ap.error("--reads must be positive")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")

    enable_compilation_cache()
    n_dev = len(jax.devices())
    use_mesh = (not args.no_mesh and args.dispatch != "persistent"
                and args.replicas == 1)
    mesh = make_debug_mesh(data=n_dev, model=1) if use_mesh else None

    def make_engine(_i=0):
        return AlignmentEngine(
            backend="auto", sc=RAPIDX.scoring, capacity=args.capacity,
            mesh=mesh, dispatch=args.dispatch, xdrop=args.xdrop)

    engine = make_engine()
    print(f"[serve] devices={n_dev} backend={engine.backend_name} "
          f"shards={engine.num_shards} dispatch={engine.dispatch} "
          f"replicas={args.replicas} policy={args.policy} "
          f"scoring={RAPIDX.scoring.name}")

    genome = random_genome(1_000_000, seed=7)
    sim = ReadSimulator(genome, args.profile, seed=8)
    lengths = (args.read_len, args.read_len * 2)
    pairs = []
    for k in range(args.reads):
        ref, read = sim.sample(lengths[k % len(lengths)])
        pairs.append((read, ref))

    depth = args.depth if args.depth == "auto" else int(args.depth)
    # Warm the per-class dispatch signatures at the stream's maximum
    # true lengths so the first request pays no compile latency.
    warmup = None
    if args.warmup:
        warmup = [(max(len(rd) for rd, _ in grp),
                   max(len(rf) for _, rf in grp))
                  for grp in (pairs[0::2], pairs[1::2]) if grp]

    service_opts = dict(max_wait_ms=args.max_wait_ms, policy=args.policy,
                        max_inflight_groups=depth, warmup=warmup)
    if args.replicas > 1:
        # Replica 0 reuses the probe engine; the rest get their own
        # (an engine is owned by exactly one dispatcher thread).
        front = AlignmentRouter(
            args.replicas,
            engine_factory=lambda i: engine if i == 0 else make_engine(),
            **service_opts)
    else:
        front = AlignmentService(engine, **service_opts)

    period = 1.0 / args.rate if args.rate > 0 else 0.0
    t0 = time.perf_counter()
    with front:
        futures = []
        for k, (read, ref) in enumerate(pairs):
            if period:  # open-loop: hold the offered arrival schedule
                target = t0 + k * period
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            futures.append(front.submit(read, ref))
        scores = [f.result()["score"] for f in futures]
        stats = front.stats()
    wall = time.perf_counter() - t0

    mean = sum(int(s) for s in scores) / len(scores)
    print(f"[serve] {args.reads} reads in {wall:.2f}s "
          f"({args.reads / wall:.0f} reads/s) mean_score={mean:.1f}")
    tier = (f" replicas_serving={stats['replicas_serving']}"
            if "replicas_serving" in stats else
            f" depth={stats['pipeline_depth']}")
    print(f"[serve] p50={stats['p50_ms']:.1f}ms p99={stats['p99_ms']:.1f}ms "
          f"fill_ratio={stats['fill_ratio']:.2f} "
          f"dispatches={stats['dispatches']} "
          f"bytes_fetched={stats['bytes_fetched']} "
          f"rejected={stats['rejected']}{tier} "
          f"flushes=fill:{stats['flush_fill']}/timeout:"
          f"{stats['flush_timeout']}/stall:{stats['flush_stall']}")
    print(f"[serve] programs built: {obs.describe(obs.programs_built())}")


if __name__ == "__main__":
    main()
