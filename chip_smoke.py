#!/usr/bin/env python3
"""Chip smoke test: drive the aligner's main path once on a TPU.

Run from the repository root, as one process:

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the mesh phase only

One chip runs four phases through the public API:

  device   the attached device is a TPU, `AlignmentEngine(backend="auto")`
           resolves to the Pallas kernel, and the kernel is compiled
           (interpret mode resolved to False);
  engine   512 Illumina 150 bp pairs and 512 PacBio 7 kb pairs (the
           8192 class, band 100) with tracebacks, on-device decode and
           pipelined dispatch, compared bit for bit with the reference
           backend on the same chip (score, best cell, status, CIGAR),
           and 16 pairs against the exact full-DP oracle;
  variants one small batch each of narrow cells, X-drop and persistent
           dispatch, compared with the int32 pipelined run;
  mapping  a seeded 4,641,652 bp genome (the size of E. coli K-12
           MG1655): 4,096 Illumina 150 bp reads (half reverse-complemented)
           and 256 PacBio 1 kb reads through `ReadMapper` and
           `AlignmentService`, held to the recall floors of
           tests/test_mapper.py (0.99 and 0.95).

`--four-chips` runs only the engine on a 4-device ("data",) mesh and the
same engine on one device, compares them bit for bit, and checks that
the outputs span four devices and that the compiled program has no
collectives.

Every phase prints its wall seconds and the programs it built, by
function, with their backend seconds (`repro.obs.programs_built`; a
program loaded from the persistent cache counts as built). They are
smoke timings of one run, not benchmarks. A failed phase, or a host
without a TPU, exits non-zero with no result line. On success the last
line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# Keep the TPU runtime's logs out of /tmp (it reads this as it loads).
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SEED = 20221110
ECOLI_K12_BP = 4_641_652
MAPPING_FLOORS = {"illumina": 0.99, "pacbio": 0.95}
#: Result keys compared bit for bit between two engine runs.
COMPARED = ("score", "best_score", "best_i", "best_j", "final_lo", "status",
            "band")

def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what) -> None:
    """Fail the phase unless `ok` (an `assert` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def run_phase(name: str, fn, *args):
    """Run one phase; print its smoke timings; re-raise its failure."""
    from repro import obs

    before = obs.programs_built()
    t0 = time.perf_counter()
    try:
        return fn(*args)
    finally:
        wall = time.perf_counter() - t0
        built = {}
        for fun, now in obs.programs_built().items():
            was = before.get(fun, {"count": 0, "seconds": 0.0})
            built[fun] = {"count": now["count"] - was["count"],
                          "seconds": now["seconds"] - was["seconds"]}
        log(f"phase {name}: wall {wall:.3f}s, programs built: "
            f"{obs.describe(built)} (smoke timing, not a benchmark)")


def simulate_pairs(profile: str, read_len: int, count: int, seed: int,
                   genome_len: int = 1_000_000):
    """(reads, refs): `count` simulated reads and their true windows."""
    from repro.data.genome import ReadSimulator, random_genome

    sim = ReadSimulator(random_genome(genome_len, seed=seed), profile,
                        seed=seed + 1)
    reads, refs = [], []
    for _ in range(count):
        ref, read = sim.sample(read_len)
        reads.append(read)
        refs.append(ref)
    return reads, refs


def assert_same(name: str, got: dict, want: dict, rows=None) -> None:
    """Bit-for-bit equality of two engine results (over `rows`)."""
    rows = np.arange(len(want["score"])) if rows is None else rows
    for key in COMPARED:
        g, w = np.asarray(got[key])[rows], np.asarray(want[key])[rows]
        bad = np.flatnonzero(g != w)
        if bad.size:
            raise AssertionError(
                f"{name}: {key} differs at {bad.size} pairs, first row "
                f"{rows[bad[0]]}: {g[bad[0]]} vs {w[bad[0]]}")
    if "cigars" in want:
        bad = [int(i) for i in rows if got["cigars"][i] != want["cigars"][i]]
        if bad:
            raise AssertionError(f"{name}: CIGAR differs at {len(bad)} "
                                 f"pairs, first row {bad[0]}")
    log(f"{name}: {len(rows)} pairs bit-identical "
        f"({', '.join(COMPARED)}, cigars)")


def phase_device() -> None:
    import jax
    from repro.core.engine import AlignmentEngine
    from repro.kernels.banded_dp.banded_dp import resolve_interpret

    dev = jax.devices()[0]
    engine = AlignmentEngine(backend="auto")
    interpret = resolve_interpret(engine.backend.interpret)
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} backend={engine.backend_name} "
        f"interpret={interpret}")
    check(dev.platform == "tpu", dev.platform)
    check(engine.backend_name == "pallas", engine.backend_name)
    check(interpret is False, interpret)


def phase_engine(n_short: int, n_long: int, long_len: int, n_oracle: int):
    """Pallas vs reference on one ragged Illumina + PacBio request.
    Returns (reads, refs, pallas result) for the variants phase."""
    from repro.core.engine import AlignmentEngine
    from repro.core.full_dp import cigar_score, full_dp_score
    from repro.core.scoring import MINIMAP2

    reads, refs = simulate_pairs("illumina", 150, n_short, SEED)
    lr, lf = simulate_pairs("pacbio", long_len, n_long, SEED + 2)
    reads += lr
    refs += lf
    out = {}
    for backend in ("auto", "reference"):
        engine = AlignmentEngine(backend=backend)
        t0 = time.perf_counter()
        out[backend] = engine.align(reads, refs, collect_tb=True)
        log(f"engine[{engine.backend_name}]: {len(reads)} pairs in "
            f"{time.perf_counter() - t0:.3f}s, bands "
            f"{sorted(set(out[backend]['band'].tolist()))}")
    got = out["auto"]
    assert_same("engine pallas vs reference", got, out["reference"])
    check((got["status"] == 0).all(), "a pair retired with xdrop off")
    for i in range(n_oracle):
        want = full_dp_score(reads[i], refs[i], MINIMAP2)
        check(int(got["score"][i]) == want, (i, int(got["score"][i]), want))
        rescored = cigar_score(got["cigars"][i], reads[i], refs[i], MINIMAP2)
        check(rescored == want, (i, rescored, want))
    log(f"engine vs full_dp: {n_oracle} pairs exact (score, CIGAR rescore)")
    return reads, refs, got


def phase_variants(reads, refs, base, n_short: int, n_long: int) -> None:
    """Narrow cells, X-drop and persistent dispatch on a small batch."""
    from repro.core.engine import AlignmentEngine

    n_all = len(reads)
    rows = np.r_[0:n_short, n_all - n_long:n_all]
    sub_reads = [reads[i] for i in rows]
    sub_refs = [refs[i] for i in rows]
    want = {k: (np.asarray(v)[rows] if k != "cigars"
                else [v[i] for i in rows]) for k, v in base.items()}
    keep = np.arange(len(rows))
    for name, opts in (("narrow", {"cell_dtype": "narrow"}),
                       ("persistent", {"dispatch": "persistent"})):
        got = AlignmentEngine(backend="auto", **opts).align(
            sub_reads, sub_refs, collect_tb=True)
        assert_same(f"variant {name} vs int32 pipelined", got, want, keep)

    # X-drop: append random-vs-random junk pairs, which must retire;
    # every surviving pair must equal the X-drop-off run.
    rng = np.random.default_rng(SEED)
    junk = [rng.integers(0, 4, 150).astype(np.int8) for _ in range(16)]
    junk_refs = [rng.integers(0, 4, 150).astype(np.int8) for _ in junk]
    got = AlignmentEngine(backend="auto", xdrop=60).align(
        sub_reads + junk, sub_refs + junk_refs, collect_tb=True)
    retired = np.asarray(got["status"]) != 0
    check(retired[len(rows):].all(), got["status"][len(rows):])
    live = np.flatnonzero(~retired[:len(rows)])
    assert_same("variant xdrop survivors vs int32 pipelined", got, want,
                live)
    log(f"variant xdrop: {int(retired[len(rows):].sum())}/{len(junk)} junk "
        f"pairs retired, {len(rows) - live.size} real pairs retired")


def phase_mapping(genome_len: int, n_illumina: int, n_pacbio: int) -> None:
    from repro.core.engine import AlignmentEngine
    from repro.data.genome import ReadSimulator, random_genome
    from repro.map import MinimizerIndex, ReadMapper, STATUS_MAPPED
    from repro.serve import AlignmentService

    genome = random_genome(genome_len, seed=SEED)
    t0 = time.perf_counter()
    index = MinimizerIndex(genome, k=13, w=8)
    log(f"mapping: index of {genome_len} bp, {index.num_minimizers} "
        f"minimizers in {time.perf_counter() - t0:.3f}s")
    # (profile, read length, reads, engine base bandwidth), as in
    # tests/test_mapper.py's end-to-end accuracy cases.
    for profile, read_len, count, bw in (("illumina", 150, n_illumina, None),
                                         ("pacbio", 1000, n_pacbio, 64)):
        sim = ReadSimulator(genome, profile, seed=SEED + 7, rc_prob=0.5)
        truth = [sim.sample(read_len) for _ in range(count)]
        engine = AlignmentEngine(backend="auto", base_bandwidth=bw)
        t0 = time.perf_counter()
        with AlignmentService(engine, mode="semiglobal",
                              max_wait_ms=2.0) as svc:
            results = ReadMapper(index, svc, window_pad=24).map_batch(
                [sr.read for sr in truth])
            stats = svc.stats()
        wall = time.perf_counter() - t0
        hits = sum(1 for sr, r in zip(truth, results)
                   if r.status == STATUS_MAPPED and r.strand == sr.strand
                   and abs(r.ref_start - sr.locus) <= max(r.band, 1))
        recall = hits / count
        log(f"mapping {profile}: {count} reads in {wall:.3f}s, recall "
            f"{recall:.4f} (floor {MAPPING_FLOORS[profile]}), "
            f"{stats['completed']} alignments, "
            f"{stats['dispatches']} dispatches")
        check(recall >= MAPPING_FLOORS[profile], (profile, recall))


def phase_four_chips(n_short: int, n_long: int, long_len: int) -> None:
    """The engine sharded over a 4-device ("data",) mesh vs one device."""
    import jax
    from jax.sharding import Mesh
    from repro.core.engine import AlignmentEngine
    from repro.roofline.hlo_collectives import collective_bytes_by_kind

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, got {devices}")
    reads, refs = simulate_pairs("illumina", 150, n_short, SEED)
    lr, lf = simulate_pairs("pacbio", long_len, n_long, SEED + 2)
    reads += lr
    refs += lf
    mesh = Mesh(np.asarray(devices), ("data",))
    sharded = AlignmentEngine(backend="auto", mesh=mesh)
    single = AlignmentEngine(backend="auto")
    check(sharded.num_shards == 4, sharded.num_shards)
    want = single.align(reads, refs, collect_tb=True)
    got = sharded.align(reads, refs, collect_tb=True)
    assert_same("mesh(4) vs one device", got, want)

    # One group by hand: where its outputs live, and what was compiled.
    group = sharded.plan([len(r) for r in reads], [len(r) for r in refs])[0]
    idx = group.indices
    pending = sharded.enqueue_group([reads[i] for i in idx],
                                    [refs[i] for i in idx], group.spec,
                                    collect_tb=True)
    spans = {d for out in pending.outs for v in out.values()
             for d in v.sharding.device_set}
    sharded.finalize_group(pending)
    check(len(spans) == 4, spans)
    spec = group.spec
    runner = sharded.sharded_runner(band=spec.band, collect_tb=True,
                                    t_max=spec.t_max, decode="device")
    rows = spec.capacity * sharded.num_shards
    shapes = (jax.ShapeDtypeStruct((rows, spec.q_len), np.int8),
              jax.ShapeDtypeStruct((rows, spec.r_len), np.int8),
              jax.ShapeDtypeStruct((rows,), np.int32),
              jax.ShapeDtypeStruct((rows,), np.int32))
    text = runner.lower(*shapes).compile().as_text()
    collectives = collective_bytes_by_kind(text)["total_bytes"]
    check(collectives == 0, collectives)
    check("tpu_custom_call" in text, "no Pallas kernel in the program")
    log(f"mesh(4): outputs span {len(spans)} devices; compiled program has "
        f"0 collective bytes and the Pallas kernel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh engine against the "
                         "same engine on one device")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro import obs
    from repro.core.engine import enable_compilation_cache

    log(f"compile cache: {enable_compilation_cache()}")
    obs.install()
    try:
        run_phase("device", phase_device)
        if args.four_chips:
            run_phase("four_chips", phase_four_chips, 512, 128, 7000)
        else:
            reads, refs, base = run_phase("engine", phase_engine,
                                          512, 512, 7000, 16)
            run_phase("variants", phase_variants, reads, refs, base, 64, 16)
            run_phase("mapping", phase_mapping, ECOLI_K12_BP, 4096, 256)
    except Exception:  # noqa: BLE001 — report any phase failure, exit 1
        traceback.print_exc()
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
